"""Unified model API over all families.

  init_params / param_shapes / param_specs
  loss_fn                         (training objective, all families)
  serving_params                  (the weights a serving engine holds)
  prefill / decode_step           (serving)
  make_batch_specs / make_cache   (ShapeDtypeStruct builders for dry-run)
"""
from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp

from repro.config import ModelConfig, ShapeConfig
from repro.models import dense, encdec, mamba2, moe, rglru
from repro.models.common import (cast_params, init_tree, leaf_name,
                                 shape_tree, spec_tree)
from repro.models.encdec import DEC_RATIO

FAMILIES = {
    "dense": dense,
    "moe": moe,
    "encdec": encdec,
    "rglru": rglru,
    "mamba2": mamba2,
}

IGNORE_LABEL = -100


def family(cfg: ModelConfig):
    return FAMILIES[cfg.family]


def param_defs(cfg: ModelConfig) -> dict:
    return family(cfg).defs(cfg)


def init_params(cfg: ModelConfig, key, dtype=jnp.float32):
    custom = {
        "lam": rglru.lam_init,
        "dt_bias": mamba2.dt_bias_init,
        "a_log": mamba2.a_log_init,
    }
    return init_tree(param_defs(cfg), key, dtype, custom)


def param_shapes(cfg: ModelConfig, dtype=jnp.float32):
    return shape_tree(param_defs(cfg), dtype)


def param_specs(cfg: ModelConfig):
    return spec_tree(param_defs(cfg))


def count_params(tree) -> int:
    return sum(int(x.size) for x in jax.tree.leaves(tree))


# ------------------------------------------------------------------ loss


def compute_dtype(cfg: ModelConfig):
    return jnp.bfloat16 if cfg.dtype == "bfloat16" else jnp.float32


def loss_fn(cfg: ModelConfig, params, batch, *, seq_sp: bool = False,
            aux_coef: float = 0.01, z_coef: float = 0.0):
    """Causal-LM cross entropy (+ MoE aux loss). Returns (loss, metrics)."""
    params = cast_params(params, compute_dtype(cfg))
    aux = None
    if cfg.family == "moe":
        logits, aux = moe.forward_logits(cfg, params, batch, seq_sp=seq_sp)
    else:
        logits = family(cfg).forward_logits(cfg, params, batch, seq_sp=seq_sp)
    labels = batch["labels"]
    logits = logits.astype(jnp.float32)
    valid = (labels != IGNORE_LABEL)
    safe = jnp.where(valid, labels, 0)
    lse = jax.scipy.special.logsumexp(logits, axis=-1)
    true_logit = jnp.take_along_axis(logits, safe[..., None], axis=-1)[..., 0]
    nll = (lse - true_logit) * valid
    ntok = jnp.maximum(jnp.sum(valid), 1)
    loss = jnp.sum(nll) / ntok
    metrics = {"nll": loss, "ntokens": ntok}
    if z_coef:
        zl = z_coef * jnp.sum(jnp.square(lse) * valid) / ntok
        loss = loss + zl
        metrics["z_loss"] = zl
    if aux is not None:
        # aux was summed over layers inside the scan
        metrics["moe_aux"] = aux
        loss = loss + aux_coef * aux
    metrics["loss"] = loss
    return loss, metrics


# ------------------------------------------------------------------ serving


def prefill(cfg: ModelConfig, params, batch):
    return family(cfg).prefill(cfg, cast_params(params, compute_dtype(cfg)),
                               batch)


def serving_params(cfg: ModelConfig, params):
    """The weights a serving engine holds: `params` cast once to the
    compute dtype, so no jitted call re-casts float32 masters of the
    whole model (serving never updates its weights). `F32_KEEP` leaves
    stay float32, as `cast_params` leaves them, and so do the norm
    scales (leaves named `*norm`, a few vectors a layer): the norms
    compute in float32, and on a TPU v5e scales held in bfloat16 gave
    logits a bfloat16 step away from those of the per-call cast, where
    float32 ones give the same numbers bit for bit."""
    held = cast_params(params, compute_dtype(cfg))
    return jax.tree_util.tree_map_with_path(
        lambda path, master, cast: (master if leaf_name(path).endswith("norm")
                                    else cast), params, held)


def decode_step(cfg: ModelConfig, params, cache, token, pos):
    return family(cfg).decode_step(
        cfg, cast_params(params, compute_dtype(cfg)), cache, token, pos)


def supports_paged(cfg: ModelConfig) -> bool:
    """Whether the slot-paged decode path (continuous batching) covers
    this config: the dense and moe text decoder families — for dense
    including sliding-window (per-slot ring pages) and int8-KV
    (per-slot scales) variants. Still excluded: M-RoPE decode (bakes in
    a scalar position offset per image grid), non-causal encoders, the
    encdec / recurrent-state families (mamba2 / rglru keep fixed-size
    state, not paged KV), and moe+swa / moe+int8 combos — the paged
    helpers would handle them, but the legacy wave path (the parity
    baseline and `continuous=False` fallback) implements neither ring
    rolls nor KV quantization for moe, so claiming support would let
    `continuous=False` silently produce divergent tokens."""
    if cfg.modality != "text" or not cfg.causal or cfg.rope_type == "mrope":
        return False
    if cfg.family == "dense":
        return True
    return (cfg.family == "moe" and not cfg.kv_quant
            and cfg.sliding_window is None)


def decode_step_paged(cfg: ModelConfig, params, cache, token, pos, active,
                      table, *, page_size: int, ring_len: int = 0):
    """Per-slot-position decode step over a block-table page pool. token
    [B,1]; pos [B] (each slot's write position / current kv_len — the
    ring cursor `pos % ring_len` is derived inside for sliding-window
    configs); active [B] bool (inactive slots' cache writes are dropped);
    table [B, W] int32 per-slot page ids (`page_size` positions per
    page)."""
    assert supports_paged(cfg), cfg.name
    return family(cfg).decode_step_paged(
        cfg, cast_params(params, compute_dtype(cfg)), cache, token, pos,
        active, table, page_size=page_size, ring_len=ring_len)


def prefill_chunk_paged(cfg: ModelConfig, params, cache, tokens, row,
                        offset, limit=None, *, page_size: int,
                        ring_len: int = 0, abs_len: int = 0):
    """One [1, C] prefill chunk scattered through page-table row `row`
    ([W] int32) at logical `offset` of a block-table page pool; `limit` =
    offset + the chunk's real (pre-padding) length, `abs_len` the static
    absolute-order scratch length sliding-window ring reconstruction
    uses. Returns (chunk logits [1, C, V], cache)."""
    assert supports_paged(cfg), cfg.name
    return family(cfg).prefill_chunk_paged(
        cfg, cast_params(params, compute_dtype(cfg)), cache, tokens, row,
        offset, limit, page_size=page_size, ring_len=ring_len,
        abs_len=abs_len)


def init_page_pool(cfg: ModelConfig, num_pages: int, page_size: int,
                   dtype=jnp.bfloat16):
    """Block-table KV page pool [L, num_pages, page_size, G, dh]
    (+ scale planes for `kv_quant`) — the allocation the slot-paged
    serving engine maps per-request page tables into."""
    assert supports_paged(cfg), cfg.name
    return family(cfg).init_page_pool(cfg, num_pages, page_size, dtype)


def init_cache(cfg: ModelConfig, b: int, seq_len: int, dtype=jnp.bfloat16):
    return family(cfg).init_cache(cfg, b, seq_len, dtype)


def cache_specs(cfg: ModelConfig):
    return family(cfg).cache_specs(cfg)


def encode(cfg: ModelConfig, params, batch):
    """Sentence-embedding path (bidirectional mean-pooled encoder)."""
    return dense.encode(cfg, cast_params(params, compute_dtype(cfg)), batch)


# -------------------------------------------------- dry-run input builders


def batch_struct(cfg: ModelConfig, shape: ShapeConfig) -> dict:
    """ShapeDtypeStructs for one train batch of this (arch, shape) cell."""
    B, S = shape.global_batch, shape.seq_len
    i32 = jnp.int32
    bf16 = jnp.bfloat16
    if cfg.family == "encdec":
        return {
            "frames": jax.ShapeDtypeStruct((B, S, cfg.d_model), bf16),
            "dec_tokens": jax.ShapeDtypeStruct((B, S // DEC_RATIO), i32),
            "labels": jax.ShapeDtypeStruct((B, S // DEC_RATIO), i32),
        }
    out = {
        "tokens": jax.ShapeDtypeStruct((B, S), i32),
        "labels": jax.ShapeDtypeStruct((B, S), i32),
    }
    if cfg.modality == "vision":
        out["patches"] = jax.ShapeDtypeStruct((B, dense.N_IMG, cfg.d_model),
                                              bf16)
    return out


def batch_specs(cfg: ModelConfig) -> dict:
    """Logical sharding axes for each batch input."""
    if cfg.family == "encdec":
        return {"frames": ("batch", None, None), "dec_tokens": ("batch", None),
                "labels": ("batch", None)}
    out = {"tokens": ("batch", None), "labels": ("batch", None)}
    if cfg.modality == "vision":
        out["patches"] = ("batch", None, None)
    return out


def decode_inputs_struct(cfg: ModelConfig, shape: ShapeConfig):
    """(token, pos) structs for a decode cell; cache comes from init_cache
    via eval_shape."""
    B = shape.global_batch
    return {
        "token": jax.ShapeDtypeStruct((B, 1), jnp.int32),
        "pos": jax.ShapeDtypeStruct((), jnp.int32),
    }


def make_sample_batch(cfg: ModelConfig, B: int, S: int, key=None):
    """Small concrete batch for smoke tests."""
    key = key if key is not None else jax.random.PRNGKey(0)
    k1, k2 = jax.random.split(key)
    if cfg.family == "encdec":
        sd = max(S // DEC_RATIO, 8)
        return {
            "frames": jax.random.normal(k1, (B, S, cfg.d_model), jnp.float32),
            "dec_tokens": jax.random.randint(k2, (B, sd), 0, cfg.vocab_size),
            "labels": jax.random.randint(k2, (B, sd), 0, cfg.vocab_size),
        }
    out = {
        "tokens": jax.random.randint(k1, (B, S), 0, cfg.vocab_size),
        "labels": jax.random.randint(k2, (B, S), 0, cfg.vocab_size),
    }
    if cfg.modality == "vision":
        out["patches"] = jax.random.normal(
            k1, (B, dense.N_IMG, cfg.d_model), jnp.float32)
        # vision batches must be at least N_IMG + some text
        assert S > dense.N_IMG, "vision smoke batch needs S > N_IMG"
        out["labels"] = out["labels"].at[:, :dense.N_IMG].set(IGNORE_LABEL)
    return out
