"""EcoVector inverted-list scan kernel (the paper's §3.2 on TPU).

The mobile algorithm loads one inverted list at a time from flash into RAM
and searches its small graph. The TPU analogue: cluster blocks live in HBM
([NC, CAP, d], one block per cluster); the *scalar-prefetched* probe list
drives the BlockSpec index_map so only the probed clusters' blocks are
DMA'd into VMEM; distances for the whole (padded) cluster are one MXU
matmul; a running top-k merge lives in the revisited output block.
The merge is an unrolled masked-min selection (`_select_topk`): Mosaic
lowers neither `sort` nor a store at a traced position inside a kernel.

Grid: (B, T) — T probe *tiles* per query (PROBE_TILE clusters DMA'd and
scanned per step), sequential on a TPU core, so the output block for query
b is revisited T times (init at t == 0, merge otherwise). Tiling probes
amortizes the output-block revisits P/PROBE_TILE-fold versus the old
one-probe-per-step grid.

Probe ids < 0 are padding and contribute no candidates (DESIGN.md §4);
`route_and_scan` fuses centroid routing (matmul + lax.top_k) with the scan
so the whole route->scan path is one jitted device call.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.ref import NEG

DEFAULT_PROBE_TILE = 4


def _select_topk(rows_d, rows_i, k: int):
    """Stable k-smallest over the concatenation of `rows_d` ([1, n] rows,
    in flat candidate order) with their ids `rows_i`.

    Built only from what Mosaic lowers: row reductions, `broadcasted_iota`
    and `where`, unrolled over the static `k`. Step j takes the global
    minimum, resolves ties to the first row holding it and the lowest
    position in that row (flat order, matching `lax.top_k` in the
    reference), writes it to output slot j and masks it with +inf. The
    rows are never concatenated, so any row length lowers. Sentinel (NEG)
    candidates carry id -1 and surface only after every real one."""
    iotas = [jax.lax.broadcasted_iota(jnp.int32, r.shape, 1) for r in rows_d]
    slot = jax.lax.broadcasted_iota(jnp.int32, (1, k), 1)
    out_d = jnp.full((1, k), NEG, jnp.float32)
    out_i = jnp.full((1, k), -1, jnp.int32)
    big = jnp.int32(2 ** 30)
    for j in range(k):
        mins = [jnp.min(r, axis=1, keepdims=True) for r in rows_d]
        m = functools.reduce(jnp.minimum, mins)                  # [1, 1]
        taken = jnp.zeros((1, 1), jnp.bool_)
        sel = jnp.full((1, 1), -1, jnp.int32)
        nxt = []
        for r, ids, iota, rmin in zip(rows_d, rows_i, iotas, mins):
            here = (rmin == m) & ~taken
            pos = jnp.min(jnp.where(r == m, iota, big), axis=1,
                          keepdims=True)
            hit = here & (iota == pos)
            got = jnp.sum(jnp.where(hit, ids, 0), axis=1, keepdims=True)
            sel = jnp.where(here, got, sel)
            nxt.append(jnp.where(hit, jnp.inf, r))
            taken = taken | here
        rows_d = nxt
        out_d = jnp.where(slot == j, m, out_d)
        out_i = jnp.where(slot == j, sel, out_i)
    return out_d, out_i


def _kernel(probe_ref, lens_ref, bmap_ref, q_ref, *refs, k: int, cap: int,
            pt: int):
    data_refs = refs[:pt]                           # pt x [1, CAP, d]
    out_d_ref, out_i_ref = refs[pt], refs[pt + 1]   # [1, k] each
    t = pl.program_id(1)

    @pl.when(t == 0)
    def _init():
        out_d_ref[...] = jnp.full(out_d_ref.shape, NEG, jnp.float32)
        out_i_ref[...] = jnp.full(out_i_ref.shape, -1, jnp.int32)

    b = pl.program_id(0)
    q = q_ref[...]                                  # [1, d]
    qq = jnp.sum(q * q)
    cand_d = [out_d_ref[...]]                       # running top-k first:
    cand_i = [out_i_ref[...]]                       # it precedes in flat order
    for j in range(pt):
        cid = probe_ref[b, t * pt + j]
        blk = bmap_ref[jnp.maximum(cid, 0)]         # cluster -> scan block
        safe = jnp.maximum(blk, 0)                  # masked/padded -> block 0
        x = data_refs[j][0]                         # [CAP, d]
        # L2 distance via matmul on the MXU: ||x||^2 - 2 x.q + ||q||^2
        xx = jnp.sum(x * x, axis=1, keepdims=True)  # [CAP, 1]
        # full f32 passes: a one-pass bf16 product would reorder near
        # neighbours against the exact reference
        xq = jax.lax.dot_general(x, q, (((1,), (1,)), ((), ())),
                                 precision=jax.lax.Precision.HIGHEST,
                                 preferred_element_type=jnp.float32)
        dist = (xx - 2.0 * xq).T + qq               # [1, CAP]
        slot = jax.lax.broadcasted_iota(jnp.int32, (1, cap), 1)
        valid = (slot < lens_ref[safe]) & (cid >= 0) & (blk >= 0)
        cand_d.append(jnp.where(valid, dist, NEG))
        cand_i.append(jnp.where(valid, safe * cap + slot, -1))
    out_d_ref[...], out_i_ref[...] = _select_topk(cand_d, cand_i, k)


def _data_index(b, t, pr, ln, bm, *, j, pt):
    # Padded (-1) or unmapped probes are clamped to block 0; the kernel
    # masks their candidates, so the wasted DMA is harmless.
    return (jnp.maximum(bm[jnp.maximum(pr[b, t * pt + j], 0)], 0), 0, 0)


@functools.partial(jax.jit,
                   static_argnames=("k", "interpret", "probe_tile"))
def ecoscan(q, data, lens, probe_ids, k: int = 10,
            interpret: bool | None = None, probe_tile: int | None = None,
            block_map=None):
    """q: [B, d] f32; data: [R, CAP, d] f32; lens: [R] i32;
    probe_ids: [B, P] i32 (ids < 0 are skipped padding).
    Returns (dists [B, k], ids [B, k]) — ids are global slots r*CAP+j,
    -1 where fewer than k valid candidates exist.

    `block_map` ([NC] i32, optional) decouples *cluster ids* in
    `probe_ids` from *scan rows* in `data`: probing cluster c scans block
    row block_map[c]; entries < 0 mask the cluster entirely (its
    candidates never surface). Identity when omitted. This is what lets a
    tiered index scan an arbitrary hot subset plus a per-batch gathered
    cold scratch through the exact same kernel math (DESIGN.md §14).

    `interpret=None` compiles with Mosaic on a TPU and interprets
    elsewhere (`ops.default_interpret`)."""
    if interpret is None:
        from repro.kernels.ops import default_interpret
        interpret = default_interpret()
    B, d = q.shape
    R, CAP, _ = data.shape
    P = probe_ids.shape[1]
    if probe_tile is not None and probe_tile < 1:
        raise ValueError(f"probe_tile must be >= 1, got {probe_tile}")
    if P == 0:                                      # nothing probed
        return (jnp.full((B, k), NEG, jnp.float32),
                jnp.full((B, k), -1, jnp.int32))
    pt = min(probe_tile or DEFAULT_PROBE_TILE, P)
    T = pl.cdiv(P, pt)
    probe_ids = probe_ids.astype(jnp.int32)
    if T * pt != P:                                 # pad to a whole tile
        probe_ids = jnp.pad(probe_ids, ((0, 0), (0, T * pt - P)),
                            constant_values=-1)
    if block_map is None:
        block_map = jnp.arange(R, dtype=jnp.int32)

    # q and the outputs ride as [B, 1, .] with the batch dim squeezed out
    # of the block: a block's last two dims must equal the array's (or be
    # (8, 128)-aligned), which a (1, d) block over [B, d] is not for B > 1
    def row(n):
        return pl.BlockSpec((pl.Squeezed(), 1, n),
                            lambda b, t, pr, ln, bm: (b, 0, 0))

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,                      # probe_ids, lens, bmap
        grid=(B, T),
        in_specs=[
            row(d),
            *[pl.BlockSpec((1, CAP, d),
                           functools.partial(_data_index, j=j, pt=pt))
              for j in range(pt)],
        ],
        out_specs=[row(k), row(k)],
    )
    kern = pl.pallas_call(
        functools.partial(_kernel, k=k, cap=CAP, pt=pt),
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((B, 1, k), jnp.float32),
                   jax.ShapeDtypeStruct((B, 1, k), jnp.int32)],
        interpret=interpret,
    )
    data = data.astype(jnp.float32)
    out_d, out_i = kern(probe_ids, lens.astype(jnp.int32),
                        block_map.astype(jnp.int32),
                        q.astype(jnp.float32)[:, None, :], *([data] * pt))
    return out_d[:, 0], out_i[:, 0]


@functools.partial(jax.jit, static_argnames=("n_probe",))
def route_topk(q, centroids, n_probe: int):
    """Centroid routing: one MXU matmul + lax.top_k -> probes [B, n_probe].

    Shared by the fused `route_and_scan` and the tiered index's split
    route->gather->scan path, so both pick bitwise-identical probes."""
    q = q.astype(jnp.float32)
    cent = centroids.astype(jnp.float32)
    d2 = (jnp.sum(q * q, axis=1, keepdims=True)
          - 2.0 * q @ cent.T
          + jnp.sum(cent * cent, axis=1)[None, :])  # [B, NC]
    _, probes = jax.lax.top_k(-d2, n_probe)
    return probes.astype(jnp.int32)


@functools.partial(jax.jit,
                   static_argnames=("n_probe", "k", "interpret",
                                    "probe_tile"))
def route_and_scan(q, centroids, data, lens, n_probe: int = 4, k: int = 10,
                   interpret: bool | None = None,
                   probe_tile: int | None = None):
    """Fused route->scan: centroid routing (one MXU matmul + lax.top_k) and
    the ecoscan kernel inside a single jit — no host round-trip between
    choosing the probes and scanning them (DESIGN.md §4).

    q: [B, d]; centroids: [NC, d]; data/lens as in `ecoscan`.
    Returns (dists [B, k], slots [B, k], probes [B, n_probe])."""
    probes = route_topk(q, centroids, n_probe)
    dists, slots = ecoscan(q, data, lens, probes, k=k, interpret=interpret,
                           probe_tile=probe_tile)
    return dists, slots, probes
