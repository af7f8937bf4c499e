"""k-means assignment kernel (EcoVector build stage, §3.1.1).

Tiles X over the grid; the centroid table rides along in VMEM (it is the
small structure the paper keeps in the fast tier). Distances are one MXU
matmul per tile; argmin on the VPU.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _kernel(x_ref, c_ref, a_ref, d_ref):
    x = x_ref[...]                                   # [TN, d]
    c = c_ref[...]                                   # [NC, d]
    xc = jax.lax.dot_general(x, c, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)  # [TN, NC]
    cc = jnp.sum(c * c, axis=1)[None, :]
    xx = jnp.sum(x * x, axis=1, keepdims=True)
    d2 = xx - 2.0 * xc + cc
    a = jnp.argmin(d2, axis=1).astype(jnp.int32)
    a_ref[...] = a[:, None]
    d_ref[...] = jnp.min(d2, axis=1)[:, None]


@functools.partial(jax.jit, static_argnames=("tile", "interpret"))
def kmeans_assign(x, centroids, tile: int = 512,
                  interpret: bool | None = None):
    """x: [N, d]; centroids: [NC, d] -> (assign [N] i32, sqdist [N] f32).
    interpret=None resolves backend-aware (compiled on TPU, interpret
    elsewhere)."""
    if interpret is None:
        from repro.kernels.ops import default_interpret
        interpret = default_interpret()
    N, d = x.shape
    NC = centroids.shape[0]
    pad = (-N) % tile
    xp = jnp.pad(x, ((0, pad), (0, 0)))
    grid = (xp.shape[0] // tile,)
    a, dist = pl.pallas_call(
        _kernel,
        grid=grid,
        in_specs=[pl.BlockSpec((tile, d), lambda i: (i, 0)),
                  pl.BlockSpec((NC, d), lambda i: (0, 0))],
        out_specs=[pl.BlockSpec((tile, 1), lambda i: (i, 0)),
                   pl.BlockSpec((tile, 1), lambda i: (i, 0))],
        out_shape=[jax.ShapeDtypeStruct((xp.shape[0], 1), jnp.int32),
                   jax.ShapeDtypeStruct((xp.shape[0], 1), jnp.float32)],
        interpret=interpret,
    )(xp.astype(jnp.float32), centroids.astype(jnp.float32))
    return a[:N, 0], dist[:N, 0]
