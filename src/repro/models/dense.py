"""Dense decoder LM family: qwen2-72b, mistral-large-123b, nemotron-4-15b,
h2o-danube-1.8b (SWA), qwen2-vl-2b (M-RoPE + patch stub), gte-small
(bidirectional encoder), qwen2.5-0.5b.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.config import ModelConfig
from repro.dist.sharding import shard
from repro.kernels.decode_attention import decode_attention_paged
from repro.models import layers as L
from repro.models.common import ParamDef, attn_defs, embed_defs, mlp_defs

N_IMG = 256          # stubbed visual tokens (dynamic resolution fixed here)
IMG_GRID = 16        # 16x16 patch grid for M-RoPE spatial ids


# ------------------------------------------------------------- params


def defs(cfg: ModelConfig) -> dict:
    Ln = cfg.num_layers
    d = {"layers": {**attn_defs(cfg, Ln), **mlp_defs(cfg, Ln, cfg.d_ff)}}
    d.update(embed_defs(cfg))
    return d


# ------------------------------------------------------------- embedding


def embed_inputs(cfg: ModelConfig, params, batch):
    """Return (x [B,S,d], positions) handling modality stubs."""
    tokens = batch["tokens"]
    emb_scale = cfg.d_model ** 0.5 if cfg.tie_embeddings else 1.0
    if cfg.modality == "vision":
        patches = batch["patches"]                       # [B, N_IMG, d]
        txt = jnp.take(params["tok_embed"], tokens[:, N_IMG:], axis=0)
        img = patches.astype(txt.dtype) @ params["patch_proj"]
        x = jnp.concatenate([img, txt], axis=1) * emb_scale
        positions = mrope_positions(tokens.shape[1])[None]  # [1,S,3]
        positions = jnp.broadcast_to(positions, (x.shape[0],) + positions.shape[1:])
    else:
        x = jnp.take(params["tok_embed"], tokens, axis=0) * emb_scale
        positions = jnp.broadcast_to(jnp.arange(tokens.shape[1])[None],
                                     tokens.shape)
    return x.astype(jnp.bfloat16 if cfg.dtype == "bfloat16" else jnp.float32), positions


def mrope_positions(S: int, offset: int = 0):
    """Qwen2-VL M-RoPE ids [S,3]: patches get (0,h,w) on a grid; text
    continues at max(grid) + j on all three streams."""
    idx = jnp.arange(S)
    is_img = idx < N_IMG
    t = jnp.where(is_img, 0, IMG_GRID + idx - N_IMG)
    h = jnp.where(is_img, idx // IMG_GRID, IMG_GRID + idx - N_IMG)
    w = jnp.where(is_img, idx % IMG_GRID, IMG_GRID + idx - N_IMG)
    return jnp.stack([t + offset, h + offset, w + offset], axis=-1)


def _rope(cfg: ModelConfig, x, positions):
    if cfg.rope_type == "mrope":
        return L.apply_mrope(x, positions, cfg.mrope_sections, cfg.rope_theta)
    if cfg.rope_type == "rope":
        return L.apply_rope(x, positions, cfg.rope_theta)
    return x


# ------------------------------------------------------------- blocks


def _qkv(cfg: ModelConfig, lp, x, positions):
    hd = cfg.resolved_head_dim
    b, s, _ = x.shape
    q = x @ lp["wq"]
    k = x @ lp["wk"]
    v = x @ lp["wv"]
    if cfg.qkv_bias:
        q, k, v = q + lp["bq"], k + lp["bk"], v + lp["bv"]
    q = q.reshape(b, s, cfg.num_heads, hd)
    k = k.reshape(b, s, cfg.num_kv_heads, hd)
    v = v.reshape(b, s, cfg.num_kv_heads, hd)
    q = _rope(cfg, q, positions)
    k = _rope(cfg, k, positions)
    tp = L.tp_degree()
    q, _ = L.pad_heads(q, tp)
    k = L.expand_kv(k, tp)
    v = L.expand_kv(v, tp)
    q = shard(q, "batch", None, "tp", None)
    k = shard(k, "batch", None, "tp", None)
    v = shard(v, "batch", None, "tp", None)
    return q, k, v


def block(cfg: ModelConfig, lp, x, positions, *, seq_sp: bool,
          fake_quant_kv: bool = False):
    """One transformer block (training / prefill full-sequence path).

    `fake_quant_kv` (serving prefill of int8-KV configs): attention reads
    `dequantize_kv(quantize_kv(k))` instead of raw k/v — exactly the
    values every later decode step reads back from the int8 cache, so
    wave prefill and chunked paged prefill see bit-identical KV and the
    wave/continuous greedy-parity contract extends to `kv_quant` configs.
    Training never sets it."""
    h = cfg.num_heads
    res = x
    y = L.rmsnorm(x, lp["attn_norm"], cfg.norm_eps)
    q, k, v = _qkv(cfg, lp, y, positions)
    if fake_quant_kv and cfg.kv_quant:
        k = L.dequantize_kv(*L.quantize_kv(k), k.dtype)
        v = L.dequantize_kv(*L.quantize_kv(v), v.dtype)
    ctx = L.attention(q, k, v, causal=cfg.causal, window=cfg.sliding_window)
    ctx = ctx[:, :, :h, :]                           # drop padded heads
    y = ctx.reshape(ctx.shape[0], ctx.shape[1], -1) @ lp["wo"]
    # constrain the TP-contracted projections seq-sharded *pre-residual* so
    # SPMD lowers their reductions as reduce-scatter, not all-reduce
    y = shard(y, "batch", "seq_sp" if seq_sp else None, None)
    x = res + y
    x = shard(x, "batch", "seq_sp" if seq_sp else None, None)
    res = x
    y = L.rmsnorm(x, lp["mlp_norm"], cfg.norm_eps)
    y = L.mlp(y, lp["w1"], lp["w2"], lp.get("w3"), cfg.act)
    y = shard(y, "batch", "seq_sp" if seq_sp else None, None)
    x = res + y
    return shard(x, "batch", "seq_sp" if seq_sp else None, None)


def block_decode(cfg: ModelConfig, lp, x, pos, cache, idx,
                 window_cache: bool):
    """One block for a single decode position.

    cache: dict of FULL stacked arrays [L, B, Sc, G, dh], updated
    *in place* at layer `idx` (scan-carry form). Writing only the new
    token's slice and then slicing the layer keeps per-step cache traffic
    at ~1x the layer cache instead of the 4-6x that scan-ys collection
    costs (see EXPERIMENTS.md §Perf, hillclimb 1).
    """
    h = cfg.num_heads
    b = x.shape[0]
    res = x
    y = L.rmsnorm(x, lp["attn_norm"], cfg.norm_eps)
    if cfg.rope_type == "mrope":
        positions = mrope_positions_decode(pos, b)
    else:
        positions = jnp.full((b, 1), pos, jnp.int32)
    q, k, v = _qkv(cfg, lp, y, positions)
    cache = dict(cache)
    sc = cache["k"].shape[2]
    slot = pos % sc if window_cache else pos
    zero = jnp.int32(0)

    def put(name, val):
        pos5 = (idx, zero, slot, zero, zero)[: val.ndim + 1]
        cache[name] = jax.lax.dynamic_update_slice(
            cache[name], val[None].astype(cache[name].dtype), pos5)

    def layer(name):
        return jax.lax.dynamic_index_in_dim(cache[name], idx, 0,
                                            keepdims=False)

    if cfg.kv_quant:
        kq, ks = L.quantize_kv(k)
        vq, vs = L.quantize_kv(v)
        put("k", kq)
        put("k_s", ks)
        put("v", vq)
        put("v_s", vs)
        ctx = L.decode_attention_q8(
            q, layer("k"), layer("k_s"), layer("v"), layer("v_s"), pos + 1,
            window=cfg.sliding_window, ring=window_cache)
    else:
        put("k", k)
        put("v", v)
        ctx = L.decode_attention(
            q, layer("k").astype(k.dtype), layer("v").astype(v.dtype),
            pos + 1, window=cfg.sliding_window, ring=window_cache)
    ctx = ctx[:, :, :h, :]
    y = ctx.reshape(b, 1, -1) @ lp["wo"]
    x = res + y
    res = x
    y = L.rmsnorm(x, lp["mlp_norm"], cfg.norm_eps)
    y = L.mlp(y, lp["w1"], lp["w2"], lp.get("w3"), cfg.act)
    return res + y, cache


def _cache_layer(c: dict, name: str, idx):
    return jax.lax.dynamic_index_in_dim(c[name], idx, 0, keepdims=False)


def _pool_gather(pool, table, ps: int):
    """Gather a logical K/V buffer out of a page pool through a table
    (chunk prefill's read; decode reads the pool in place).

    pool [P, ps, ...] (one layer of the pool); table [..., W] int32 page
    ids — entry w backs logical positions [w*ps, (w+1)*ps). Returns the
    logically contiguous [..., W*ps, ...] buffer: flat element j comes
    from pool page table[j // ps] at in-page offset j % ps. Unmapped
    tail entries (callers fill them with page 0) produce junk the caller
    masks via kv_len — the gathered VALID prefix is element-for-element
    identical to what a slot-contiguous cache would hold, which is what
    keeps paged prefill bit-exact against the wave path."""
    P = pool.shape[0]
    flat = pool.reshape((P * ps,) + pool.shape[2:])
    W = table.shape[-1]
    j = jnp.arange(W * ps)
    idx = table[..., j // ps] * ps + (j % ps)
    return jnp.take(flat, idx, axis=0)


def _kv_rows(cfg: ModelConfig, k, v) -> dict:
    """What the pool stores for new positions k, v [N, G, dh]: the values
    themselves, or with `kv_quant` int8 values and [N, G] scales, keyed
    by pool tensor in the order the decode kernel takes them."""
    if cfg.kv_quant:
        kq, ks = L.quantize_kv(k)
        vq, vs = L.quantize_kv(v)
        return {"k": kq, "v": vq, "k_s": ks, "v_s": vs}
    return {"k": k, "v": v}


def _pool_write(c: dict, idx, pg, off, rows: dict) -> None:
    """Scatter `rows` (from `_kv_rows`) into layer `idx` of the pool at
    (page pg[i], offset off[i]); a page id past the pool (the sentinel)
    drops the write. K/V pages are lane-dense: a position's G heads of dh
    are one row, zero-padded to the pool's lanes."""
    for name, x in rows.items():
        x = x.reshape(pg.shape[0], -1)
        x = jnp.pad(x, ((0, 0), (0, c[name].shape[-1] - x.shape[1])))
        c[name] = c[name].at[idx, pg, off].set(x.astype(c[name].dtype),
                                               mode="drop")


def _slot_kv(c: dict, idx, row, ps: int, like):
    """One slot's logical K and V buffers [W*ps, G, dh] in `like`'s dtype,
    gathered from layer `idx` through its table row and dequantized when
    the pool holds int8 (`like` [.., G, dh] gives the head shape)."""
    def get(name):
        return _pool_gather(_cache_layer(c, name, idx), row, ps)

    gd = like.shape[-2] * like.shape[-1]
    k, v = (get(n)[:, :gd].reshape((-1,) + like.shape[-2:])
            for n in ("k", "v"))
    if "k_s" in c:
        return (L.dequantize_kv(k, get("k_s"), like.dtype),
                L.dequantize_kv(v, get("v_s"), like.dtype))
    return k.astype(like.dtype), v.astype(like.dtype)


def paged_attn_decode(cfg: ModelConfig, lp, y, pos, table, active, c, idx,
                      *, page_size: int, ring_len: int):
    """One layer of block-table paged decode attention, shared by the
    dense and moe families (moe.decode_step_paged reuses it verbatim;
    only the FFN differs between the two paged decode bodies).

    y [B,1,d] (already normed); pos [B] absolute per-slot positions;
    table [B, W] int32 page ids (this slot's mapped pages, in logical
    order; unmapped tail entries hold page 0 and are never read);
    active [B] bool; c: page-pool cache dict (`init_page_pool`),
    [L, P, ps, lanes] per tensor (+ [L, P, ps, G] scales when
    `kv_quant`). `ring_len` > 0 marks a sliding-window ring: logical
    position p lives at ring cursor p % ring_len. The new k/v scatter
    resolves (page, offset) through the table; inactive slots scatter to
    the out-of-bounds page sentinel and drop. Attention is the Pallas `decode_attention_paged`, which
    reads each active slot's earlier positions in place from the pool,
    through its table, from its first ceil(n / page_size) pages only (n
    = pos, or min(pos, ring_len) on a ring, whose overwritten cursor is
    left out; every filled ring slot is otherwise valid, position order
    inside the ring being irrelevant because RoPE is baked into the
    stored keys), and takes the step's own k/v from registers. Inactive
    slots read no page. Shared (refcounted) prefix pages are never
    written here: all decode writes land at positions >= the request's
    prompt length, which by the pager's COW contract sit in slot-private
    pages."""
    ps = page_size
    q, k, v = _qkv(cfg, lp, y, pos[:, None])
    npages = c["k"].shape[1]
    lw = pos % ring_len if ring_len else pos
    pg = jnp.take_along_axis(table, (lw // ps)[:, None], axis=1)[:, 0]
    pg = jnp.where(active, pg, npages)           # OOB sentinel -> drop
    rows = _kv_rows(cfg, k[:, 0], v[:, 0])
    _pool_write(c, idx, pg, lw % ps, rows)
    earlier = jnp.minimum(pos, ring_len) if ring_len else pos
    ctx = decode_attention_paged(
        q[:, 0], tuple(x.astype(c[n].dtype) for n, x in rows.items()),
        tuple(c[n] for n in rows), jnp.where(active, earlier, 0), lw,
        table, idx)
    return ctx[:, None], c


def paged_attn_chunk(cfg: ModelConfig, lp, y, positions, row, offset,
                     limit, c, idx, *, page_size: int, ring_len: int,
                     abs_len: int):
    """One layer of chunked block-table prefill attention (dense + moe
    shared).

    y [1,C,d] (already normed); row [W] int32 — the admitting slot's
    page-table row; offset/limit traced scalars (`limit` = offset + the
    chunk's REAL token count, pre-padding; `offset` can start past 0
    when the pager matched a cached prefix and skipped its chunks).
    Non-ring: scatter the chunk at logical [offset, offset+C) through
    the table and attend the gathered [W*ps] logical buffer (dequantized
    from int8 when `kv_quant`) with the same q_offset/kv_len masks the
    slot-contiguous path used — positions past the valid prefix are
    masked to exact-zero probability, so the longer gathered buffer
    changes nothing bitwise. Ring (sliding-window, ring_len > 0): the
    ring is re-materialized into ABSOLUTE position order (ring cursor j
    holds position `offset-1-((offset-1-j) % ring_len)`) in an [abs_len]
    buffer, the chunk is appended at its absolute offset, attention runs
    with the wave prefill's causal/window masks, and only the REAL
    tokens scatter back at ring cursors `p % ring_len` — the padded tail
    of a final ragged chunk must NOT evict positions still inside other
    queries' windows. Shared prefix pages are never written: every store
    lands at logical position >= offset >= the pager's matched length,
    which sits in slot-private (fresh or COW) pages. Returns
    (ctx [1,C,Hp,dh], c)."""
    ps = page_size
    csz = y.shape[1]
    q, k, v = _qkv(cfg, lp, y, positions)
    npages = c["k"].shape[1]
    W = row.shape[0]
    zero = jnp.int32(0)
    rows = _kv_rows(cfg, k[0], v[0])
    if cfg.kv_quant:
        k_new = L.dequantize_kv(rows["k"], rows["k_s"], k.dtype)
        v_new = L.dequantize_kv(rows["v"], rows["v_s"], v.dtype)
    else:
        k_new, v_new = k[0], v[0]
    p_new = offset + jnp.arange(csz)
    if ring_len:
        lw = p_new % ring_len
        dst_pg = jnp.where(p_new < limit, row[lw // ps], npages)
        # 1. history (pre-chunk ring contents) in absolute position order
        j = jnp.arange(ring_len)
        p_hist = offset - 1 - ((offset - 1 - j) % ring_len)
        hist_dst = jnp.where(p_hist >= 0, p_hist, abs_len)   # <0 -> drop
        kslot, vslot = (x[:ring_len] for x in _slot_kv(c, idx, row, ps,
                                                       k_new))
        g, dh = kslot.shape[1], kslot.shape[2]
        kfull = jnp.zeros((abs_len, g, dh), k_new.dtype
                          ).at[hist_dst].set(kslot, mode="drop")
        vfull = jnp.zeros((abs_len, g, dh), v_new.dtype
                          ).at[hist_dst].set(vslot, mode="drop")
        # 2. append the chunk at its absolute positions and attend
        kfull = jax.lax.dynamic_update_slice(kfull, k_new, (offset, zero,
                                                            zero))
        vfull = jax.lax.dynamic_update_slice(vfull, v_new, (offset, zero,
                                                            zero))
        ctx = L.attention(q, kfull[None], vfull[None], causal=True,
                          window=cfg.sliding_window, q_offset=offset,
                          kv_len=offset + csz)
        # 3. ring-write only the REAL tokens at their per-position cursors
        _pool_write(c, idx, dst_pg, lw % ps, rows)
        return ctx, c
    # non-ring: positions past the mapped width scatter to the sentinel
    # (a final ragged chunk's pad tail can cross the last mapped page)
    dst_pg = jnp.where(p_new // ps < W,
                       row[jnp.minimum(p_new // ps, W - 1)], npages)
    _pool_write(c, idx, dst_pg, p_new % ps, rows)
    kslot, vslot = (x[None] for x in _slot_kv(c, idx, row, ps, k_new))
    ctx = L.attention(q, kslot, vslot, causal=True,
                      window=cfg.sliding_window, q_offset=offset,
                      kv_len=offset + csz)
    return ctx, c


def decode_step_paged(cfg: ModelConfig, params, cache, token, pos, active,
                      table, *, page_size: int, ring_len: int = 0):
    """One decode step over a block-table paged cache (continuous
    batching).

    token [B,1] int32; pos [B] int32 — the per-slot write position (== the
    slot's current kv length); active [B] bool; table [B, W] int32 page
    ids (each slot's mapped pages in logical order; unmapped tail entries
    hold page 0 and are masked). Every slot advances one position at ITS
    OWN cursor: k/v land at pool page table[b, cursor//ps] offset
    cursor%ps via a scatter, attention gathers the slot's logical buffer
    through its table and masks each row to its own kv_len = pos[b]+1
    (clamped to `ring_len` for sliding-window rings, where every filled
    cursor is valid). Inactive slots (free, or mid-prefill-admission)
    scatter to the out-of-bounds page sentinel with mode="drop" so they
    cannot clobber a page another request is filling — or a SHARED prefix
    page mapped read-only into several slots; their logits rows are
    garbage the engine discards. Covers plain, sliding-window (ring) and
    int8-KV dense configs.
    """
    emb_scale = cfg.d_model ** 0.5 if cfg.tie_embeddings else 1.0
    x = jnp.take(params["tok_embed"], token, axis=0) * emb_scale
    x = x.astype(jnp.bfloat16 if cfg.dtype == "bfloat16" else jnp.float32)
    b = token.shape[0]
    pos = jnp.asarray(pos, jnp.int32)
    table = jnp.asarray(table, jnp.int32)

    def body(carry, inp):
        xc, cd = carry
        lp, idx = inp
        h = cfg.num_heads
        res = xc
        y = L.rmsnorm(xc, lp["attn_norm"], cfg.norm_eps)
        ctx, cd = paged_attn_decode(cfg, lp, y, pos, table, active, cd,
                                    idx, page_size=page_size,
                                    ring_len=ring_len)
        ctx = ctx[:, :, :h, :]
        xc = res + ctx.reshape(b, 1, -1) @ lp["wo"]
        res = xc
        y = L.rmsnorm(xc, lp["mlp_norm"], cfg.norm_eps)
        y = L.mlp(y, lp["w1"], lp["w2"], lp.get("w3"), cfg.act)
        return (res + y, cd), None

    idxs = jnp.arange(cfg.num_layers, dtype=jnp.int32)
    (x, cache), _ = jax.lax.scan(body, (x, dict(cache)),
                                 (params["layers"], idxs))
    x = L.rmsnorm(x, params["final_norm"], cfg.norm_eps)
    logits = logits_from_hidden(cfg, params, x)[:, 0]
    return logits, cache


def prefill_chunk_paged(cfg: ModelConfig, params, cache, tokens, row,
                        offset, limit=None, *, page_size: int,
                        ring_len: int = 0, abs_len: int = 0):
    """One prefill chunk of an admitted prompt, written through one
    slot's page-table row while the other slots keep decoding between
    chunks.

    tokens [1, C] int32; row [W] int32 (the slot's mapped pages); offset /
    limit: traced scalars (`limit` = offset + the chunk's real token
    count; defaults to offset + C; `offset` starts at the pager's matched
    prefix length when shared pages were mapped — their chunks are
    skipped entirely). `abs_len`: static length of the absolute-order
    scratch buffer ring re-materialization builds (sliding-window only).
    The chunk's k/v scatter to logical [offset, offset+C) through the
    row; its queries attend the gathered logical buffer [0, offset+C)
    causally (L.attention's q_offset/kv_len path), so a prompt longer
    than C is prefilled in several calls that all compile to the same
    [1, C] shape. On non-ring rows, positions past the prompt's true end
    (final ragged chunk padded up to C) write junk into slot-PRIVATE
    pages that is either overwritten by the next write at that position
    or masked by kv_len before anything attends it; ring rows drop those
    writes via `limit` (see `paged_attn_chunk`). Returns
    (chunk logits [1, C, V], cache).
    """
    emb_scale = cfg.d_model ** 0.5 if cfg.tie_embeddings else 1.0
    x = jnp.take(params["tok_embed"], tokens, axis=0) * emb_scale
    x = x.astype(jnp.bfloat16 if cfg.dtype == "bfloat16" else jnp.float32)
    c = tokens.shape[1]
    positions = offset + jnp.arange(c)[None, :]
    limit = offset + c if limit is None else limit
    row = jnp.asarray(row, jnp.int32)

    def body(carry, inp):
        xc, cd = carry
        lp, idx = inp
        h = cfg.num_heads
        res = xc
        y = L.rmsnorm(xc, lp["attn_norm"], cfg.norm_eps)
        ctx, cd = paged_attn_chunk(cfg, lp, y, positions, row, offset,
                                   limit, cd, idx, page_size=page_size,
                                   ring_len=ring_len, abs_len=abs_len)
        ctx = ctx[:, :, :h, :]
        xc = res + ctx.reshape(1, c, -1) @ lp["wo"]
        res = xc
        y = L.rmsnorm(xc, lp["mlp_norm"], cfg.norm_eps)
        y = L.mlp(y, lp["w1"], lp["w2"], lp.get("w3"), cfg.act)
        return (res + y, cd), None

    idxs = jnp.arange(cfg.num_layers, dtype=jnp.int32)
    (x, cache), _ = jax.lax.scan(body, (x, dict(cache)),
                                 (params["layers"], idxs))
    x = L.rmsnorm(x, params["final_norm"], cfg.norm_eps)
    logits = logits_from_hidden(cfg, params, x)
    return logits, cache


def mrope_positions_decode(pos, b):
    p = IMG_GRID + pos - N_IMG
    return jnp.broadcast_to(jnp.stack([p, p, p])[None, None, :], (b, 1, 3))


# ------------------------------------------------------------- forward


def _scan_blocks(cfg: ModelConfig, params, x, positions, *, seq_sp: bool,
                 collect_kv: bool = False, fake_quant_kv: bool = False):
    stacked = params["layers"]

    def body(xc, lp):
        if collect_kv:
            # recompute k/v for the cache (prefill)
            y = L.rmsnorm(xc, lp["attn_norm"], cfg.norm_eps)
            _, k, v = _qkv(cfg, lp, y, positions)
            out = block(cfg, lp, xc, positions, seq_sp=seq_sp,
                        fake_quant_kv=fake_quant_kv)
            return out, (k, v)
        return block(cfg, lp, xc, positions, seq_sp=seq_sp), None

    if cfg.remat:
        body = jax.checkpoint(body)
    x, kv = jax.lax.scan(body, x, stacked)
    return x, kv


def hidden_states(cfg: ModelConfig, params, batch, *, seq_sp: bool = False):
    x, positions = embed_inputs(cfg, params, batch)
    x = shard(x, "batch", "seq_sp" if seq_sp else None, None)
    x, _ = _scan_blocks(cfg, params, x, positions, seq_sp=seq_sp)
    return L.rmsnorm(x, params["final_norm"], cfg.norm_eps)


def logits_from_hidden(cfg: ModelConfig, params, x):
    head = params["tok_embed"].T if cfg.tie_embeddings else params["lm_head"]
    logits = x @ head.astype(x.dtype)
    return shard(logits, "batch", None, "tp")


def forward_logits(cfg: ModelConfig, params, batch, *, seq_sp: bool = False):
    return logits_from_hidden(cfg, params, hidden_states(
        cfg, params, batch, seq_sp=seq_sp))


def encode(cfg: ModelConfig, params, batch):
    """Mean-pooled, L2-normalised sentence embeddings (gte-small path)."""
    x = hidden_states(cfg, params, batch)
    mask = batch.get("mask")
    if mask is None:
        mask = jnp.ones(batch["tokens"].shape, x.dtype)
    mask = mask.astype(x.dtype)[..., None]
    pooled = jnp.sum(x * mask, axis=1) / jnp.maximum(jnp.sum(mask, axis=1), 1)
    pooled = pooled.astype(jnp.float32)
    return pooled / jnp.maximum(jnp.linalg.norm(pooled, axis=-1, keepdims=True), 1e-9)


# ------------------------------------------------------------- serving


def cache_len(cfg: ModelConfig, seq_len: int) -> int:
    if cfg.sliding_window:
        return min(seq_len, cfg.sliding_window)
    return seq_len


def kv_expanded_heads(cfg: ModelConfig) -> int:
    tp = L.tp_degree()
    return max(cfg.num_kv_heads, tp)


def init_cache(cfg: ModelConfig, b: int, seq_len: int, dtype=jnp.bfloat16):
    g, hd = kv_expanded_heads(cfg), cfg.resolved_head_dim
    sc = cache_len(cfg, seq_len)
    shape = (cfg.num_layers, b, sc, g, hd)
    if cfg.kv_quant:
        return {"k": jnp.zeros(shape, jnp.int8),
                "v": jnp.zeros(shape, jnp.int8),
                "k_s": jnp.zeros(shape[:-1], jnp.float32),
                "v_s": jnp.zeros(shape[:-1], jnp.float32)}
    return {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)}


def init_page_pool(cfg: ModelConfig, num_pages: int, page_size: int,
                   dtype=jnp.bfloat16):
    """Global block-table KV pool: [L, num_pages, page_size, lanes] per
    tensor (+ [L, num_pages, page_size, G] f32 scales for `kv_quant`).
    A page is lane-dense: each position's G heads of dh are one row of
    `lanes` = G*dh rounded up to whole 128-lane rows (zero-padded; the
    TPU moves only lane-aligned rows, and the decode kernel DMAs whole
    pages). Pages are the pager's allocation unit — a slot maps an
    ordered list of them through its [W] table row, and refcounted
    prefix pages can back several slots at once (serving/pager.py)."""
    g, hd = kv_expanded_heads(cfg), cfg.resolved_head_dim
    shape = (cfg.num_layers, num_pages, page_size, -(-g * hd // 128) * 128)
    if cfg.kv_quant:
        return {"k": jnp.zeros(shape, jnp.int8),
                "v": jnp.zeros(shape, jnp.int8),
                "k_s": jnp.zeros(shape[:-1] + (g,), jnp.float32),
                "v_s": jnp.zeros(shape[:-1] + (g,), jnp.float32)}
    return {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)}


def cache_specs(cfg: ModelConfig):
    axes = (None, "batch", None, "tp", None)
    if cfg.kv_quant:
        return {"k": axes, "v": axes, "k_s": axes[:-1], "v_s": axes[:-1]}
    return {"k": axes, "v": axes}


def prefill(cfg: ModelConfig, params, batch):
    """Full-sequence forward; returns (last-position logits, kv cache).

    For `kv_quant` configs the forward attends fake-quantized k/v (see
    `block`): the int8 cache is the single source of truth, so prefill
    must read the same values decode will — that is what makes the
    wave and chunked-paged prefill paths token-identical."""
    x, positions = embed_inputs(cfg, params, batch)
    x = shard(x, "batch", None, None)
    x, (k, v) = _scan_blocks(cfg, params, x, positions, seq_sp=False,
                             collect_kv=True, fake_quant_kv=True)
    x = L.rmsnorm(x, params["final_norm"], cfg.norm_eps)
    logits = logits_from_hidden(cfg, params, x[:, -1:, :])[:, 0]
    S = k.shape[2]
    sc = cache_len(cfg, S)
    if sc != S:  # SWA ring layout: position p lives in slot p % sc
        k = jnp.roll(k[:, :, S - sc:], shift=S % sc, axis=2)
        v = jnp.roll(v[:, :, S - sc:], shift=S % sc, axis=2)
    if cfg.kv_quant:
        kq, ks = L.quantize_kv(k)
        vq, vs = L.quantize_kv(v)
        return logits, {"k": kq, "k_s": ks, "v": vq, "v_s": vs}
    return logits, {"k": k, "v": v}


def decode_step(cfg: ModelConfig, params, cache, token, pos):
    """token [B,1] int32; pos scalar int32 (position being written).

    The cache rides in the scan CARRY (in-place per-layer updates), not in
    xs/ys — collecting updated caches as scan outputs double-buffers the
    whole cache and (on some backends) round-trips it through f32.
    """
    emb_scale = cfg.d_model ** 0.5 if cfg.tie_embeddings else 1.0
    x = jnp.take(params["tok_embed"], token, axis=0) * emb_scale
    x = x.astype(jnp.bfloat16 if cfg.dtype == "bfloat16" else jnp.float32)
    ring = cache["k"].shape[2] != 0 and cfg.sliding_window is not None

    def body(carry, inp):
        xc, c = carry
        lp, idx = inp
        xc, c = block_decode(cfg, lp, xc, pos, c, idx, ring)
        return (xc, c), None

    idxs = jnp.arange(cfg.num_layers, dtype=jnp.int32)
    (x, cache), _ = jax.lax.scan(body, (x, dict(cache)),
                                 (params["layers"], idxs))
    x = L.rmsnorm(x, params["final_norm"], cfg.norm_eps)
    logits = logits_from_hidden(cfg, params, x)[:, 0]
    return logits, cache
