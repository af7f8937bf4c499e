"""Flash-decode GQA attention kernels: one query position per row against
its KV cache, online softmax in VMEM scratch.

`decode_attention_paged` is the served decode attention: every paged
decode step of the dense and MoE engines (`models/dense.py`
`paged_attn_decode`) reads each slot's live pages in place from the page
pool through its table. `decode_attention` takes a slot-contiguous cache
[B, S, G, dh]."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG = -1e30


def _kernel(len_ref, q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
            ts: int, nsteps: int, scale: float):
    s_idx = pl.program_id(2)

    @pl.when(s_idx == 0)
    def _init():
        m_ref[...] = jnp.full(m_ref.shape, NEG, jnp.float32)
        l_ref[...] = jnp.zeros(l_ref.shape, jnp.float32)
        acc_ref[...] = jnp.zeros(acc_ref.shape, jnp.float32)

    b = pl.program_id(0)
    q = q_ref[0, 0]                                   # [Hg, dh]
    k = k_ref[0, :, 0, :]                             # [TS, dh]
    v = v_ref[0, :, 0, :]
    kv_len = len_ref[b]
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    pos = s_idx * ts + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    s = jnp.where(pos < kv_len, s, NEG)               # [Hg, TS]
    m_prev, l_prev = m_ref[...], l_ref[...]           # [Hg, 1]
    m_cur = jnp.max(s, axis=1, keepdims=True)
    m_new = jnp.maximum(m_prev, m_cur)
    p = jnp.exp(s - m_new)                            # [Hg, TS]
    corr = jnp.exp(m_prev - m_new)
    l_new = l_prev * corr + jnp.sum(p, axis=1, keepdims=True)
    pv = jax.lax.dot_general(p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
                             preferred_element_type=jnp.float32)
    acc_ref[...] = acc_ref[...] * corr + pv
    m_ref[...] = m_new
    l_ref[...] = l_new

    @pl.when(s_idx == nsteps - 1)
    def _finish():
        o_ref[0, 0] = (acc_ref[...] /
                       jnp.maximum(l_ref[...], 1e-30)).astype(o_ref.dtype)


def live_pages(kv_len, page_size: int):
    """Pages below each row's length, ceil(kv_len / page_size): the pages
    `decode_attention_paged` fetches and computes for that row, and no
    others."""
    return (kv_len + page_size - 1) // page_size


def _chunk_pages(page_size: int, width: int) -> int:
    """Pages per compute chunk: the fewest whose positions fill one
    128-lane row of scores, at most the table width."""
    return min(width, -(-128 // page_size))


def _paged_kernel(len_ref, cur_ref, tbl_ref, layer_ref, q_ref, kn_ref,
                  vn_ref, *refs, ps: int, cp: int, scale: float, hpg: int,
                  quant: bool):
    if quant:
        (ksn_ref, vsn_ref, k_hbm, v_hbm, ks_ref, vs_ref, o_ref, kbuf, vbuf,
         sems, m_ref, l_ref, acc_ref) = refs
    else:
        k_hbm, v_hbm, o_ref, kbuf, vbuf, sems, m_ref, l_ref, acc_ref = refs
    b, nb = pl.program_id(0), pl.num_programs(0)
    W = tbl_ref.shape[1]
    layer = layer_ref[0]
    slot = b % 2                              # double buffer across rows
    q = q_ref[0]                              # [H, lanes]
    H = q.shape[0]
    group = jax.lax.broadcasted_iota(jnp.int32, (H, 1), 0) // hpg

    def per_head(x):
        """[G, T] per-KV-head values -> [H, T], row h taking its group's."""
        out = jnp.zeros((H, x.shape[1]), jnp.float32)
        for g in range(x.shape[0]):
            out = jnp.where(group == g, x[g:g + 1], out)
        return out

    def copy(t, row, w, buf):
        src, dst = ((k_hbm, kbuf), (v_hbm, vbuf))[t]
        return pltpu.make_async_copy(src.at[layer, tbl_ref[row, w]],
                                     dst.at[buf, w], sems.at[buf, t, w])

    def fetch(row, buf):
        n = live_pages(len_ref[row], ps)
        for t in (0, 1):                      # K first: scores start early
            for w in range(W):
                @pl.when(w < n)
                def _():
                    copy(t, row, w, buf).start()

    def wait(t, w):
        @pl.when(w < live_pages(len_ref[b], ps))
        def _():
            copy(t, b, w, slot).wait()

    @pl.when(b == 0)
    def _():
        fetch(0, 0)

    @pl.when(b + 1 < nb)
    def _():
        fetch(b + 1, 1 - slot)                # lands while this row computes

    # the step's own token opens the softmax: m = its score, l = 1
    s = jnp.sum(q.astype(jnp.float32) *
                kn_ref[0].astype(q.dtype).astype(jnp.float32),
                axis=1, keepdims=True) * scale            # [H, 1]
    p = jnp.ones_like(s)
    if quant:
        s = s * per_head(ksn_ref[0])
        p = p * per_head(vsn_ref[0])
    m_ref[...] = s
    l_ref[...] = jnp.ones_like(s)
    acc_ref[...] = (p.astype(q.dtype).astype(jnp.float32) *
                    vn_ref[0].astype(q.dtype).astype(jnp.float32))

    n_old, cur = len_ref[b], cur_ref[b]
    for c0 in range(0, W, cp):
        n = min(cp, W - c0)
        lanes = slice(c0 * ps, (c0 + n) * ps)

        @pl.when(c0 * ps < n_old)
        def _chunk():
            for w in range(c0, c0 + n):
                wait(0, w)
            k = kbuf[slot, c0:c0 + n].reshape(n * ps, -1).astype(q.dtype)
            s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                    preferred_element_type=jnp.float32)
            s = s * scale                                 # [H, n*ps]
            if quant:
                s = s * per_head(ks_ref[0, :, lanes])
            pos = c0 * ps + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
            live = (pos < n_old) & (pos != cur)
            s = jnp.where(live, s, NEG)
            m_prev = m_ref[...]
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
            corr = jnp.exp(m_prev - m_new)
            p = jnp.exp(s - m_new)                        # exactly 0 if dead
            l_ref[...] = l_ref[...] * corr + jnp.sum(p, axis=1, keepdims=True)
            m_ref[...] = m_new
            for w in range(c0, c0 + n):
                wait(1, w)
            v = vbuf[slot, c0:c0 + n].reshape(n * ps, -1)
            # pages past the row's live ones hold stale VMEM: zero them
            # so that 0 * junk cannot be NaN
            row = c0 * ps + jax.lax.broadcasted_iota(jnp.int32, v.shape, 0)
            v = jnp.where(row < n_old, v, jnp.zeros_like(v)).astype(q.dtype)
            if quant:
                p = p * per_head(vs_ref[0, :, lanes])
            acc_ref[...] = acc_ref[...] * corr + jax.lax.dot_general(
                p.astype(q.dtype), v, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)

    o_ref[0] = (acc_ref[...] / l_ref[...]).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("interpret",))
def decode_attention_paged(q, new, pool, kv_len, cursor, table, layer,
                           interpret: bool | None = None):
    """Block-table flash decode, read in place from the layer-stacked
    page pool.

    q: [B, H, dh]. new: the step's own K/V rows, (k, v) [B, G, dh], or
    with int8 K/V (kq, vq, ks, vs), scales [B, G]. pool: the matching
    pools, K/V [L, P, ps, lanes] (a position's G heads of dh in one
    lane-dense row, `lanes` >= G*dh) and scales [L, P, ps, G]. kv_len
    [B]: each row's earlier positions, read through table [B, W] int32
    (entry w backs positions [w*ps, (w+1)*ps)) from layer `layer` (a
    traced scalar: no per-layer slice of the pool is made). cursor [B]:
    a ring cursor below kv_len that the step overwrites and that is left
    out; a cursor at or past kv_len leaves out nothing. Returns
    [B, H, dh] in q's dtype.

    One grid step per row. It DMAs the row's live K and V pages, the
    first `live_pages(kv_len, ps)` of its table, straight from the pool,
    while the previous row computes; unmapped tail entries are never
    fetched. Scores, running max, sum and accumulator are float32;
    probabilities meet V in q's dtype; positions past kv_len get exactly
    zero weight. With int8 K/V the scales enter the scores and the
    probabilities, as in `layers.decode_attention_q8`; a [ps, G] scale
    page is no whole lane row, so the scale planes reach the kernel
    gathered through the table, positions along lanes. The step's own
    token enters from `new`, so the kernel reads no page for it and
    does not wait on the scatter that stores it. Each KV head's query
    sits in its group's lanes of an [H, lanes] block (zeros elsewhere),
    so one matmul against a lane-dense page scores every head."""
    if interpret is None:
        from repro.kernels.ops import default_interpret
        interpret = default_interpret()
    B, H, dh = q.shape
    G, W = new[0].shape[1], table.shape[1]
    _, P, ps, lanes = pool[0].shape
    hpg, gd, quant = H // G, G * dh, len(new) == 4
    i32 = lambda x: jnp.asarray(x, jnp.int32)
    table = i32(table)

    def lane_rows(x, rows):            # [B, ..] -> [B, rows, lanes]
        x = x.reshape(B, rows, gd)
        return jnp.pad(x, ((0, 0), (0, 0), (0, lanes - gd)))

    qx = jnp.where(jnp.eye(G, dtype=bool)[None, :, None, :, None],
                   q.reshape(B, G, hpg, 1, dh), jnp.zeros((), q.dtype))
    blocks = [lane_rows(qx, H)] + [lane_rows(x, 1) for x in new[:2]]
    specs = [pl.BlockSpec((1, H, lanes), lambda b, *_: (b, 0, 0))] + [
        pl.BlockSpec((1, 1, lanes), lambda b, *_: (b, 0, 0))] * 2
    if quant:
        j = jnp.arange(W * ps)
        at = table[:, j // ps] * ps + (j % ps)    # [B, W*ps] pool rows
        scale_rows = [jnp.swapaxes(jnp.take(
            x[layer].reshape(P * ps, G), at, axis=0), 1, 2)
            for x in pool[2:]]                     # [B, G, W*ps]
        blocks += [x.reshape(B, G, 1) for x in new[2:]]
        specs += [pl.BlockSpec((1, G, 1), lambda b, *_: (b, 0, 0))] * 2
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,               # lens, cursors, table, layer
        grid=(B,),
        in_specs=specs + [pl.BlockSpec(memory_space=pl.ANY)] * 2 + (
            [pl.BlockSpec((1, G, W * ps), lambda b, *_: (b, 0, 0))] * 2
            if quant else []),
        out_specs=pl.BlockSpec((1, H, lanes), lambda b, *_: (b, 0, 0)),
        scratch_shapes=[pltpu.VMEM((2, W, ps, lanes), x.dtype)
                        for x in pool[:2]]
        + [pltpu.SemaphoreType.DMA((2, 2, W)),
           pltpu.VMEM((H, 1), jnp.float32),
           pltpu.VMEM((H, 1), jnp.float32),
           pltpu.VMEM((H, lanes), jnp.float32)],
    )
    out = pl.pallas_call(
        functools.partial(_paged_kernel, ps=ps, cp=_chunk_pages(ps, W),
                          scale=1.0 / (dh ** 0.5), hpg=hpg, quant=quant),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, H, lanes), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(i32(kv_len).reshape(B), i32(cursor).reshape(B), table,
      i32(layer).reshape(1), *blocks, *pool[:2],
      *(scale_rows if quant else []))
    # row h's context is its own group's lanes
    out = out[..., :gd].reshape(B, G, hpg, G, dh)
    out = jnp.diagonal(out, axis1=1, axis2=3)          # [B, hpg, dh, G]
    return jnp.moveaxis(out, -1, 1).reshape(B, H, dh)


@functools.partial(jax.jit, static_argnames=("ts", "interpret", "ring"))
def decode_attention(q, k, v, kv_len, ts: int = 512,
                     interpret: bool | None = None, ring: bool = False):
    """q: [B, H, dh]; k, v: [B, S, G, dh] (H % G == 0); kv_len: i32 scalar
    (shared length) or [B] vector (slot-paged batches where every request
    sits at its own position). `ring=True`: each row's cache is a
    sliding-window ring page whose write cursor is `kv_len % S` — every
    FILLED slot is valid (evicted positions were overwritten in place),
    so the per-row mask length is `min(kv_len, S)`; position order inside
    the ring is irrelevant because RoPE is baked into the stored keys.
    Returns [B, H, dh]."""
    if interpret is None:
        from repro.kernels.ops import default_interpret
        interpret = default_interpret()
    B, H, dh = q.shape
    S, G = k.shape[1], k.shape[2]
    Hg = H // G
    qg = q.reshape(B, G, Hg, dh)
    pad = (-S) % ts
    if pad:
        kz = jnp.zeros((B, pad, G, dh), k.dtype)
        k = jnp.concatenate([k, kz], axis=1)
        v = jnp.concatenate([v, kz], axis=1)
    Sp = k.shape[1]
    nsteps = Sp // ts
    scale = 1.0 / (dh ** 0.5)
    lens = jnp.broadcast_to(
        jnp.asarray(kv_len, jnp.int32).reshape(-1), (B,))
    if ring:
        lens = jnp.minimum(lens, S)    # per-slot ring: filled slots valid

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,                       # lens
        grid=(B, G, nsteps),
        in_specs=[
            pl.BlockSpec((1, 1, Hg, dh), lambda b, g, s, ln: (b, g, 0, 0)),
            pl.BlockSpec((1, ts, 1, dh), lambda b, g, s, ln: (b, s, g, 0)),
            pl.BlockSpec((1, ts, 1, dh), lambda b, g, s, ln: (b, s, g, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, Hg, dh), lambda b, g, s, ln: (b, g, 0, 0)),
        scratch_shapes=[pltpu.VMEM((Hg, 1), jnp.float32),
                        pltpu.VMEM((Hg, 1), jnp.float32),
                        pltpu.VMEM((Hg, dh), jnp.float32)],
    )
    out = pl.pallas_call(
        functools.partial(_kernel, ts=ts, nsteps=nsteps, scale=scale),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, G, Hg, dh), q.dtype),
        interpret=interpret,
    )(lens, qg, k, v)
    return out.reshape(B, H, dh)