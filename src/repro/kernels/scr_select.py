"""Fused batched SCR select kernel (§4 steps 1+2 on TPU).

`scr_score` computes every query x window similarity and leaves the
per-document best-window selection to a host Python scan. This kernel
fuses both: window embeddings live corpus-resident in HBM as one padded
[ND, CAPW, d] block per document (the SCR analogue of the EcoVector
[NC, CAP, d] cluster pack, DESIGN.md §6), the *scalar-prefetched*
retrieved-doc id matrix drives the BlockSpec index_map so only the
retrieved documents' blocks are DMA'd into VMEM, and each block's
query·window scores AND segment-argmax (best window id + score) come out
of one MXU matmul + row reduction — no [B, NW] score matrix ever leaves
the device.

Grid: (B, T) — T doc *tiles* per query (DOC_TILE document blocks DMA'd
and reduced per step). Each step owns its private (1, DOC_TILE) slice of
the output, so there are no revisited output blocks and no cross-step
merge: the segment boundaries are exactly the document blocks. The
per-document argmax is a masked min over window ids, so the first-max tie
rule is explicit rather than left to the lowering.

Doc ids < 0 are padding (queries that retrieved fewer than K docs):
their block index is clamped to 0 and every window masked, yielding the
(-NEG, -1) sentinel pair. Ties on the max score resolve to the lowest
window id, matching the host `max()` scan and `jnp.argmax`.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.ref import NEG

DEFAULT_DOC_TILE = 8


def _kernel(ids_ref, lens_ref, q_ref, *refs, capw: int, dt: int):
    data_refs = refs[:dt]                           # dt x [1, CAPW, d]
    out_s_ref, out_w_ref = refs[dt], refs[dt + 1]   # [1, dt] each
    b = pl.program_id(0)
    t = pl.program_id(1)
    q = q_ref[...]                                  # [1, d]
    col = jax.lax.broadcasted_iota(jnp.int32, (1, dt), 1)
    out_s = jnp.full((1, dt), -NEG, jnp.float32)
    out_w = jnp.full((1, dt), -1, jnp.int32)
    for j in range(dt):
        did = ids_ref[b, t * dt + j]
        safe = jnp.maximum(did, 0)                  # padded doc -> block 0
        w = data_refs[j][0]                         # [CAPW, d]
        s = jax.lax.dot_general(w, q, (((1,), (1,)), ((), ())),
                                precision=jax.lax.Precision.HIGHEST,
                                preferred_element_type=jnp.float32)
        s = s.T                                     # [1, CAPW]
        slot = jax.lax.broadcasted_iota(jnp.int32, (1, capw), 1)
        valid = (slot < lens_ref[safe]) & (did >= 0)
        s = jnp.where(valid, s, -NEG)
        # segment-argmax within the document block: first max wins ties,
        # matching the host scan (Python max / jnp.argmax semantics)
        best = jnp.max(s, axis=1, keepdims=True)                  # [1, 1]
        win = jnp.min(jnp.where(s == best, slot, capw), axis=1,
                      keepdims=True)                              # [1, 1]
        has = jnp.max(valid.astype(jnp.int32), axis=1, keepdims=True) > 0
        out_s = jnp.where(col == j, best, out_s)
        out_w = jnp.where(col == j, jnp.where(has, win, -1), out_w)
    out_s_ref[...] = out_s
    out_w_ref[...] = out_w


def _data_index(b, t, ids, ln, *, j, dt):
    # Padded doc ids (-1) are clamped to block 0; the kernel masks them.
    return (jnp.maximum(ids[b, t * dt + j], 0), 0, 0)


@functools.partial(jax.jit, static_argnames=("interpret", "doc_tile"))
def scr_select(q, data, lens, doc_ids, interpret: bool | None = None,
               doc_tile: int | None = None):
    """q: [B, d] f32 query batch; data: [ND, CAPW, d] f32 window-embedding
    blocks; lens: [ND] i32 valid windows per doc; doc_ids: [B, K] i32
    retrieved docs per query (ids < 0 are padding).

    Returns (scores [B, K] f32, wins [B, K] i32): the best window's
    query·window score and its within-document window id for every
    retrieved doc — (-NEG, -1) where the slot is padding or the document
    has no windows."""
    if interpret is None:
        from repro.kernels.ops import default_interpret
        interpret = default_interpret()
    B, d = q.shape
    ND, CAPW, _ = data.shape
    K = doc_ids.shape[1]
    if doc_tile is not None and doc_tile < 1:
        raise ValueError(f"doc_tile must be >= 1, got {doc_tile}")
    if B == 0 or K == 0 or ND == 0 or CAPW == 0:
        return (jnp.full((B, K), -NEG, jnp.float32),
                jnp.full((B, K), -1, jnp.int32))
    dt = min(doc_tile or DEFAULT_DOC_TILE, K)
    T = pl.cdiv(K, dt)
    doc_ids = doc_ids.astype(jnp.int32)
    if T * dt != K:                                 # pad to a whole tile
        doc_ids = jnp.pad(doc_ids, ((0, 0), (0, T * dt - K)),
                          constant_values=-1)

    # q rides as [B, 1, d] and the outputs as [B, T, 1, dt], leading dims
    # squeezed out of the blocks, so every block's last two dims equal
    # the array's at any B and T (Mosaic's block-shape rule)
    out_row = pl.BlockSpec((pl.Squeezed(), pl.Squeezed(), 1, dt),
                           lambda b, t, ids, ln: (b, t, 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,                      # doc_ids, lens
        grid=(B, T),
        in_specs=[
            pl.BlockSpec((pl.Squeezed(), 1, d),
                         lambda b, t, ids, ln: (b, 0, 0)),
            *[pl.BlockSpec((1, CAPW, d),
                           functools.partial(_data_index, j=j, dt=dt))
              for j in range(dt)],
        ],
        out_specs=[out_row, out_row],
    )
    kern = pl.pallas_call(
        functools.partial(_kernel, capw=CAPW, dt=dt),
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((B, T, 1, dt), jnp.float32),
                   jax.ShapeDtypeStruct((B, T, 1, dt), jnp.int32)],
        interpret=interpret,
    )
    data = data.astype(jnp.float32)
    out_s, out_w = kern(doc_ids, lens.astype(jnp.int32),
                        q.astype(jnp.float32)[:, None, :], *([data] * dt))
    return (out_s.reshape(B, T * dt)[:, :K],
            out_w.reshape(B, T * dt)[:, :K])
