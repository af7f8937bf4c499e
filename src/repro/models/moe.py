"""MoE decoder family: arctic-480b (128e top-2 + dense residual),
granite-moe-1b-a400m (32e top-8).

Expert parallelism: activations are replicated along the "model" axis
(they already are, post-attention-allreduce), experts are sharded along it.
Each model-shard routes its *local copy* of the tokens, keeps only the
tokens destined for its resident experts, runs them through a capacity-
bounded [E_local, C, d] buffer, and the final psum over "model" combines
expert outputs — the same collective a TP dense FFN already pays, so EP
here adds **zero** extra all-to-all traffic. This is a deliberate TPU
adaptation (see DESIGN.md §3): classic all-to-all dispatch assumes token
shards differ per expert-shard, which is not true in 2-D (data, model)
meshes with replicated activations.

Dispatch inside a shard uses the sort-based grouping trick (argsort by
expert id, cumsum offsets, capacity drop) — no [T, E, C] one-hot.
"""
from __future__ import annotations

from functools import partial
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.config import ModelConfig
from repro.dist.sharding import (fsdp_spans_pods, get_mesh, logical_to_spec,
                                 shard, shard_map)
from repro.models import layers as L
from repro.models.common import ParamDef, attn_defs, embed_defs, mlp_defs
from repro.models import dense


def defs(cfg: ModelConfig) -> dict:
    Ln, d, m = cfg.num_layers, cfg.d_model, cfg.moe
    layer = {**attn_defs(cfg, Ln)}
    layer["moe_norm"] = ParamDef((Ln, d), (None, "fsdp"), "zeros")
    layer["router"] = ParamDef((Ln, d, m.num_experts), (None, "fsdp", None))
    layer["we1"] = ParamDef((Ln, m.num_experts, d, m.expert_d_ff),
                            (None, "expert", "fsdp", None))
    layer["we2"] = ParamDef((Ln, m.num_experts, m.expert_d_ff, d),
                            (None, "expert", None, "fsdp"))
    if cfg.act == "swiglu":
        layer["we3"] = ParamDef((Ln, m.num_experts, d, m.expert_d_ff),
                                (None, "expert", "fsdp", None))
    if m.dense_residual:
        layer.update(mlp_defs(cfg, Ln, cfg.d_ff))
    else:
        layer["mlp_norm"] = layer.pop("moe_norm")  # single pre-FFN norm name
    out = {"layers": layer}
    out.update(embed_defs(cfg))
    return out


# ------------------------------------------------- quantised FSDP gather


def _q8_axis(w, axis):
    s = jnp.max(jnp.abs(w.astype(jnp.float32)), axis=axis, keepdims=True) \
        / 127.0
    s = jnp.maximum(s, 1e-20)
    q = jnp.round(w.astype(jnp.float32) / s).astype(jnp.int8)
    return q, s


@partial(jax.custom_vjp, nondiff_argnums=(1, 2, 3))
def q8_all_gather(w, axis_name, gather_axis, quant_axis):
    """ZeRO++-style int8 weight all-gather: quantise the local shard
    (per-row scales along `quant_axis`), gather int8 + scales, dequantise.
    Halves the FSDP gather's ICI bytes. Backward = the same
    reduce-scatter the bf16 gather would produce (straight-through)."""
    q, s = _q8_axis(w, quant_axis)
    qf = jax.lax.all_gather(q, axis_name, axis=gather_axis, tiled=True)
    sf = jax.lax.all_gather(s, axis_name, axis=gather_axis, tiled=True)
    return qf.astype(jnp.bfloat16) * sf.astype(jnp.bfloat16)


def _q8_fwd(w, axis_name, gather_axis, quant_axis):
    return (q8_all_gather(w, axis_name, gather_axis, quant_axis),
            jnp.zeros((), w.dtype))


def _q8_bwd(axis_name, gather_axis, quant_axis, res, g):
    gw = jax.lax.psum_scatter(g.astype(jnp.float32), axis_name,
                              scatter_dimension=gather_axis, tiled=True)
    return (gw.astype(res.dtype),)


q8_all_gather.defvjp(_q8_fwd, _q8_bwd)


# ----------------------------------------------------------- dispatch core


class ServingRows(NamedTuple):
    """Expert-rows of one serving-FFN call in one layer."""
    capacity: int     # buffer rows each expert computes
    routed: int       # expert-rows the real tokens were routed to
    computed: int     # expert-rows the experts compute


def serving_rows(cfg: ModelConfig, n_rows: int,
                 n_real: int | None = None) -> ServingRows:
    """The serving FFN (`moe_ffn(drop=False)`) over `n_rows` buffer rows,
    of which `n_real` are real tokens (default all). Capacity is
    `n_rows`: top_k picks distinct experts per token, so an expert
    receives at most one slot per row and none is ever dropped. Every
    expert computes its whole capacity, routed or not (on a mesh each
    shard computes its own experts' part); the real tokens were routed
    to `n_real * top_k` of those rows. `_local_moe` serves with this
    capacity and the engine traces these counts, and `computed` is
    derived from `capacity`, so a change to the capacity moves both."""
    real = n_rows if n_real is None else n_real
    capacity = n_rows
    return ServingRows(capacity, real * cfg.moe.top_k,
                       cfg.moe.num_experts * capacity)


def _local_moe(cfg: ModelConfig, x, router, we1, we2, we3, e_offset, E_total,
               capacity: int | None = None):
    """Token-choice top-k MoE over the experts resident in this shard.

    x: [T, d] local tokens; we*: [El, ...] local experts covering global
    ids [e_offset, e_offset + El). Returns (y [T, d] partial sum over local
    experts, aux load-balance loss term).

    `capacity` overrides the capacity_factor-derived per-expert buffer
    size. Serving paths pass T (`serving_rows`) — the true no-drop
    bound, since top_k assigns distinct experts per token: every token's
    output then depends only on its own row, which is what makes
    wave/paged decode bit-identical and a slot's tokens independent of
    its co-residents.
    Training keeps the capacity_factor drops.
    """
    m = cfg.moe
    T, d = x.shape
    El = we1.shape[0]
    k = m.top_k
    logits = (x.astype(jnp.float32) @ router.astype(jnp.float32))
    probs = jax.nn.softmax(logits, axis=-1)                     # [T, E]
    topv, topi = jax.lax.top_k(probs, k)                        # [T, k]
    topv = topv / jnp.maximum(topv.sum(-1, keepdims=True), 1e-9)
    # aux loss (computed identically on every shard; fine under psum/mean)
    f = jnp.zeros(E_total, jnp.float32).at[topi.reshape(-1)].add(1.0) / (T * k)
    pbar = probs.mean(0)
    aux = E_total * jnp.sum(f * pbar)

    C = capacity or max(4, int(T * k * m.capacity_factor) // E_total)
    eids = topi.reshape(-1)                                     # [T*k]
    local = (eids >= e_offset) & (eids < e_offset + El)
    leids = jnp.where(local, eids - e_offset, El)               # El = trash
    order = jnp.argsort(leids)
    sorted_ids = leids[order]
    counts = jnp.zeros(El + 1, jnp.int32).at[leids].add(1)
    starts = jnp.cumsum(counts) - counts
    pos = jnp.arange(T * k) - starts[sorted_ids]
    keep = (sorted_ids < El) & (pos < C)
    dest = jnp.where(keep, sorted_ids * C + pos, El * C)
    src_tok = order // k
    buf = jnp.zeros((El * C + 1, d), x.dtype).at[dest].set(x[src_tok])
    h = buf[: El * C].reshape(El, C, d)
    a = jnp.einsum("ecd,edf->ecf", h, we1)
    if cfg.act == "swiglu":
        a = jax.nn.silu(a) * jnp.einsum("ecd,edf->ecf", h, we3)
    elif cfg.act == "sq_relu":
        a = jnp.square(jax.nn.relu(a))
    else:
        a = jax.nn.gelu(a)
    out = jnp.einsum("ecf,efd->ecd", a, we2).reshape(El * C, d)
    out = jnp.concatenate([out, jnp.zeros((1, d), out.dtype)], axis=0)
    slot_vals = out[dest]                                       # [T*k, d]
    w = topv.reshape(-1)[order].astype(x.dtype)
    y = jnp.zeros((T, d), x.dtype).at[src_tok].add(
        jnp.where(keep[:, None], slot_vals * w[:, None], 0))
    return y, aux


def moe_ffn(cfg: ModelConfig, lp, x, *, out_scatter: bool = False,
            drop: bool = True):
    """x: [B, S, d] -> (y, aux). Uses shard_map EP on-mesh, local off-mesh.

    out_scatter (train/seq_sp path): the combining reduction over "model"
    is emitted as psum_scatter over the sequence dim instead of a full
    all-reduce — the residual stream is sequence-sharded anyway, so this
    halves the combine's ICI traffic and skips the re-shard.

    drop=False (every serving path: prefill, wave decode, paged decode):
    per-expert capacity is raised to the theoretical max T (top_k picks
    DISTINCT experts per token, so one expert can receive at most one
    slot per token) — no token is ever dropped, and a co-batched (or
    junk co-resident) token can never displace another request's
    expert slot. Op metadata names it `moe_ffn` (a named scope), so a
    profiler trace can attribute its operations.
    """
    with jax.named_scope("moe_ffn"):
        return _moe_ffn(cfg, lp, x, out_scatter=out_scatter, drop=drop)


def _moe_ffn(cfg: ModelConfig, lp, x, *, out_scatter: bool, drop: bool):
    b, s, d = x.shape
    mesh = get_mesh()
    m = cfg.moe
    if mesh is None or "model" not in mesh.axis_names:
        cap = None if drop else serving_rows(cfg, b * s).capacity
        y, aux = _local_moe(cfg, x.reshape(-1, d), lp["router"], lp["we1"],
                            lp["we2"], lp.get("we3"), 0, m.num_experts,
                            capacity=cap)
        return y.reshape(b, s, d), aux

    tp = mesh.shape["model"]
    El = m.num_experts // tp
    scatter = out_scatter and s % tp == 0
    batch_spec = logical_to_spec(mesh, ("batch", None, None))
    out_spec = logical_to_spec(mesh, ("batch", "seq_sp", None)) if scatter \
        else batch_spec
    fsdp_ax = ("pod", "data") if (fsdp_spans_pods() and
                                  "pod" in mesh.axis_names) else "data"

    def gather(wl, gather_axis, quant_axis):
        if m.int8_gather:
            return q8_all_gather(wl, fsdp_ax, gather_axis, quant_axis)
        return jax.lax.all_gather(wl, fsdp_ax, axis=gather_axis, tiled=True)

    def body(xl, router_l, we1_l, we2_l, we3_l):
        # ZeRO-3 per-layer gather of the FSDP ("data") weight dimension
        router_f = gather(router_l, 0, 1)
        we1_f = gather(we1_l, 1, 2)
        we2_f = gather(we2_l, 2, 1)
        we3_f = gather(we3_l, 1, 2) if cfg.act == "swiglu" else None
        midx = jax.lax.axis_index("model")
        xt = xl.reshape(-1, d)
        cap = None if drop else serving_rows(cfg, xt.shape[0]).capacity
        y, aux = _local_moe(cfg, xt, router_f, we1_f, we2_f, we3_f,
                            midx * El, m.num_experts, capacity=cap)
        y = y.reshape(xl.shape)
        if scatter:
            y = jax.lax.psum_scatter(y, "model", scatter_dimension=1,
                                     tiled=True)
        else:
            y = jax.lax.psum(y, "model")
        aux = jax.lax.psum(aux, "model") / tp
        return y, aux

    specs_in = (batch_spec, P(fsdp_ax, None), P("model", fsdp_ax, None),
                P("model", None, fsdp_ax),
                P("model", fsdp_ax, None) if cfg.act == "swiglu" else P())
    fn = shard_map(body, mesh=mesh, in_specs=specs_in,
                   out_specs=(out_spec, P()))
    we3 = lp.get("we3")
    if we3 is None:
        we3 = jnp.zeros((), x.dtype)
    y, aux = fn(x, lp["router"], lp["we1"], lp["we2"], we3)
    return y, aux


# ----------------------------------------------------------- blocks


def block(cfg: ModelConfig, lp, x, positions, *, seq_sp: bool,
          inference: bool = False):
    """One MoE transformer block. `inference` (serving prefill): expert
    capacity never drops tokens (see `moe_ffn(drop=False)`)."""
    h = cfg.num_heads
    sp = "seq_sp" if seq_sp else None
    res = x
    y = L.rmsnorm(x, lp["attn_norm"], cfg.norm_eps)
    q, k, v = dense._qkv(cfg, lp, y, positions)
    ctx = L.attention(q, k, v, causal=True)
    ctx = ctx[:, :, :h, :]
    y = ctx.reshape(ctx.shape[0], ctx.shape[1], -1) @ lp["wo"]
    y = shard(y, "batch", sp, None)   # reduce-scatter, not all-reduce
    x = res + y
    x = shard(x, "batch", sp, None)
    res = x
    norm_name = "moe_norm" if cfg.moe.dense_residual else "mlp_norm"
    y = L.rmsnorm(x, lp[norm_name], cfg.norm_eps)
    ymoe, aux = moe_ffn(cfg, lp, y, out_scatter=seq_sp, drop=not inference)
    if cfg.moe.dense_residual:
        yd = L.rmsnorm(x, lp["mlp_norm"], cfg.norm_eps)
        ydense = L.mlp(yd, lp["w1"], lp["w2"], lp.get("w3"), cfg.act)
        ymoe = ymoe + shard(ydense, "batch", sp, None)
    x = res + ymoe
    return shard(x, "batch", sp, None), aux


def hidden_states(cfg: ModelConfig, params, batch, *, seq_sp: bool = False):
    x, positions = dense.embed_inputs(cfg, params, batch)

    def body(carry, lp):
        xc, aux = carry
        xc, a = block(cfg, lp, xc, positions, seq_sp=seq_sp)
        return (xc, aux + a), None

    body_fn = jax.checkpoint(body) if cfg.remat else body
    (x, aux), _ = jax.lax.scan(body_fn, (x, jnp.zeros((), jnp.float32)),
                               params["layers"])
    return L.rmsnorm(x, params["final_norm"], cfg.norm_eps), aux


def forward_logits(cfg: ModelConfig, params, batch, *, seq_sp: bool = False):
    x, aux = hidden_states(cfg, params, batch, seq_sp=seq_sp)
    return dense.logits_from_hidden(cfg, params, x), aux


# ----------------------------------------------------------- serving

init_cache = dense.init_cache
init_page_pool = dense.init_page_pool
cache_specs = dense.cache_specs


def prefill(cfg: ModelConfig, params, batch):
    """Full-sequence forward; returns (last-position logits, kv cache).
    Inference capacity semantics: no expert ever drops a token (a
    co-batched prompt must not perturb another request's logits)."""
    x, positions = dense.embed_inputs(cfg, params, batch)

    def body(carry, lp):
        xc, aux = carry
        y = L.rmsnorm(xc, lp["attn_norm"], cfg.norm_eps)
        _, k, v = dense._qkv(cfg, lp, y, positions)
        xc, a = block(cfg, lp, xc, positions, seq_sp=False, inference=True)
        return (xc, aux + a), (k, v)

    body_fn = jax.checkpoint(body) if cfg.remat else body
    (x, _), (k, v) = jax.lax.scan(body_fn, (x, jnp.zeros((), jnp.float32)),
                                  params["layers"])
    x = L.rmsnorm(x, params["final_norm"], cfg.norm_eps)
    logits = dense.logits_from_hidden(cfg, params, x[:, -1:, :])[:, 0]
    return logits, {"k": k, "v": v}


def decode_step(cfg: ModelConfig, params, cache, token, pos):
    emb_scale = cfg.d_model ** 0.5 if cfg.tie_embeddings else 1.0
    x = jnp.take(params["tok_embed"], token, axis=0) * emb_scale
    x = x.astype(jnp.bfloat16 if cfg.dtype == "bfloat16" else jnp.float32)
    zero = jnp.int32(0)

    def body(carry, inp):
        xc, ck_all, cv_all = carry
        lp, idx = inp
        h = cfg.num_heads
        b = xc.shape[0]
        res = xc
        y = L.rmsnorm(xc, lp["attn_norm"], cfg.norm_eps)
        positions = jnp.full((b, 1), pos, jnp.int32)
        q, k, v = dense._qkv(cfg, lp, y, positions)
        # in-place carry update (see dense.block_decode)
        ck_all = jax.lax.dynamic_update_slice(
            ck_all, k[None].astype(ck_all.dtype), (idx, zero, pos, zero, zero))
        cv_all = jax.lax.dynamic_update_slice(
            cv_all, v[None].astype(cv_all.dtype), (idx, zero, pos, zero, zero))
        ck = jax.lax.dynamic_index_in_dim(ck_all, idx, 0, keepdims=False)
        cv = jax.lax.dynamic_index_in_dim(cv_all, idx, 0, keepdims=False)
        ctx = L.decode_attention(q, ck.astype(k.dtype), cv.astype(v.dtype),
                                 pos + 1)
        ctx = ctx[:, :, :h, :]
        xc = res + ctx.reshape(b, 1, -1) @ lp["wo"]
        res = xc
        norm_name = "moe_norm" if cfg.moe.dense_residual else "mlp_norm"
        y = L.rmsnorm(xc, lp[norm_name], cfg.norm_eps)
        ymoe, _ = moe_ffn(cfg, lp, y, drop=False)
        if cfg.moe.dense_residual:
            yd = L.rmsnorm(xc, lp["mlp_norm"], cfg.norm_eps)
            ymoe = ymoe + L.mlp(yd, lp["w1"], lp["w2"], lp.get("w3"), cfg.act)
        return (res + ymoe, ck_all, cv_all), None

    idxs = jnp.arange(cfg.num_layers, dtype=jnp.int32)
    (x, k, v), _ = jax.lax.scan(body, (x, cache["k"], cache["v"]),
                                (params["layers"], idxs))
    x = L.rmsnorm(x, params["final_norm"], cfg.norm_eps)
    logits = dense.logits_from_hidden(cfg, params, x)[:, 0]
    return logits, {"k": k, "v": v}


# ------------------------------------------------- slot-paged serving


def decode_step_paged(cfg: ModelConfig, params, cache, token, pos, active,
                      table, *, page_size: int, ring_len: int = 0):
    """MoE mirror of `dense.decode_step_paged`: the attention/cache layer
    is the shared `dense.paged_attn_decode` (block-table scatter/gather,
    OOB-drop for inactive slots, ring/int8 variants); only the FFN
    differs. Expert routing is per token, so the slot dimension threads
    straight through dispatch/combine — with `drop=False` capacity a
    slot's expert outputs depend only on its own row, never on
    co-residents."""
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
        ctx, cd = dense.paged_attn_decode(cfg, lp, y, pos, table, active,
                                          cd, idx, page_size=page_size,
                                          ring_len=ring_len)
        ctx = ctx[:, :, :h, :]
        xc = res + ctx.reshape(b, 1, -1) @ lp["wo"]
        res = xc
        norm_name = "moe_norm" if cfg.moe.dense_residual else "mlp_norm"
        y = L.rmsnorm(xc, lp[norm_name], cfg.norm_eps)
        ymoe, _ = moe_ffn(cfg, lp, y, drop=False)
        if cfg.moe.dense_residual:
            yd = L.rmsnorm(xc, lp["mlp_norm"], cfg.norm_eps)
            ymoe = ymoe + L.mlp(yd, lp["w1"], lp["w2"], lp.get("w3"),
                                cfg.act)
        return (res + ymoe, cd), None

    idxs = jnp.arange(cfg.num_layers, dtype=jnp.int32)
    (x, cache), _ = jax.lax.scan(body, (x, dict(cache)),
                                 (params["layers"], idxs))
    x = L.rmsnorm(x, params["final_norm"], cfg.norm_eps)
    logits = dense.logits_from_hidden(cfg, params, x)[:, 0]
    return logits, cache


def prefill_chunk_paged(cfg: ModelConfig, params, cache, tokens, row,
                        offset, limit=None, *, page_size: int,
                        ring_len: int = 0, abs_len: int = 0):
    """MoE mirror of `dense.prefill_chunk_paged` (shared
    `dense.paged_attn_chunk` block-table attention, drop-free MoE FFN)."""
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
        ctx, cd = dense.paged_attn_chunk(cfg, lp, y, positions, row,
                                         offset, limit, cd, idx,
                                         page_size=page_size,
                                         ring_len=ring_len,
                                         abs_len=abs_len)
        ctx = ctx[:, :, :h, :]
        xc = res + ctx.reshape(1, c, -1) @ lp["wo"]
        res = xc
        norm_name = "moe_norm" if cfg.moe.dense_residual else "mlp_norm"
        y = L.rmsnorm(xc, lp[norm_name], cfg.norm_eps)
        ymoe, _ = moe_ffn(cfg, lp, y, drop=False)
        if cfg.moe.dense_residual:
            yd = L.rmsnorm(xc, lp["mlp_norm"], cfg.norm_eps)
            ymoe = ymoe + L.mlp(yd, lp["w1"], lp["w2"], lp.get("w3"),
                                cfg.act)
        return (res + ymoe, cd), None

    idxs = jnp.arange(cfg.num_layers, dtype=jnp.int32)
    (x, cache), _ = jax.lax.scan(body, (x, dict(cache)),
                                 (params["layers"], idxs))
    x = L.rmsnorm(x, params["final_norm"], cfg.norm_eps)
    logits = dense.logits_from_hidden(cfg, params, x)
    return logits, cache
