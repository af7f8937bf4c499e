"""Param-definition helpers shared by all model families."""
from __future__ import annotations

from dataclasses import dataclass
from typing import Callable, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.config import ModelConfig


@dataclass(frozen=True)
class ParamDef:
    shape: Tuple[int, ...]
    axes: Tuple[Optional[str], ...]   # logical sharding axes
    init: str = "normal"              # normal | zeros | custom key
    scale: float = 0.02


def init_tree(defs, key, dtype, custom: dict[str, Callable] | None = None):
    """defs: nested dict of ParamDef -> nested dict of arrays."""
    flat, treedef = jax.tree.flatten(defs, is_leaf=lambda x: isinstance(x, ParamDef))
    keys = jax.random.split(key, len(flat))
    custom = custom or {}
    out = []
    for d, k in zip(flat, keys):
        if d.init == "zeros":
            out.append(jnp.zeros(d.shape, dtype))
        elif d.init == "normal":
            out.append((jax.random.normal(k, d.shape, jnp.float32) * d.scale
                        ).astype(dtype))
        else:
            out.append(custom[d.init](k, d.shape).astype(dtype))
    return jax.tree.unflatten(treedef, out)


def spec_tree(defs):
    return jax.tree.map(lambda d: d.axes, defs,
                        is_leaf=lambda x: isinstance(x, ParamDef))


def shape_tree(defs, dtype):
    return jax.tree.map(lambda d: jax.ShapeDtypeStruct(d.shape, dtype), defs,
                        is_leaf=lambda x: isinstance(x, ParamDef))


# Parameters kept in f32 regardless of compute dtype (recurrence-critical)
F32_KEEP = ("lam", "A_log", "dt_bias", "D")


def leaf_name(path) -> str:
    """The name of a param-tree leaf: the last key of its path."""
    last = path[-1]
    return getattr(last, "key", None) or str(last)


def cast_params(tree, dtype):
    """Mixed-precision policy: cast weights to compute dtype at use-site
    (differentiable, so grads flow to the f32 masters)."""
    def f(path, a):
        name = leaf_name(path)
        if name in F32_KEEP:
            return a
        return a.astype(dtype) if a.dtype == jnp.float32 else a
    return jax.tree_util.tree_map_with_path(f, tree)


def attn_defs(cfg: ModelConfig, L: int, prefix: str = "") -> dict:
    """Per-layer-stacked attention params."""
    d, hd = cfg.d_model, cfg.resolved_head_dim
    h, kv = cfg.num_heads, max(cfg.num_kv_heads, 1)
    defs = {
        f"{prefix}attn_norm": ParamDef((L, d), (None, "fsdp"), "zeros"),
        f"{prefix}wq": ParamDef((L, d, h * hd), (None, "fsdp", "tp")),
        f"{prefix}wk": ParamDef((L, d, kv * hd), (None, "fsdp", "tp")),
        f"{prefix}wv": ParamDef((L, d, kv * hd), (None, "fsdp", "tp")),
        f"{prefix}wo": ParamDef((L, h * hd, d), (None, "tp", "fsdp")),
    }
    if cfg.qkv_bias:
        defs[f"{prefix}bq"] = ParamDef((L, h * hd), (None, "tp"), "zeros")
        defs[f"{prefix}bk"] = ParamDef((L, kv * hd), (None, "tp"), "zeros")
        defs[f"{prefix}bv"] = ParamDef((L, kv * hd), (None, "tp"), "zeros")
    return defs


def mlp_defs(cfg: ModelConfig, L: int, d_ff: int, prefix: str = "") -> dict:
    d = cfg.d_model
    defs = {
        f"{prefix}mlp_norm": ParamDef((L, d), (None, "fsdp"), "zeros"),
        f"{prefix}w1": ParamDef((L, d, d_ff), (None, "fsdp", "tp")),
        f"{prefix}w2": ParamDef((L, d_ff, d), (None, "tp", "fsdp")),
    }
    if cfg.act == "swiglu":
        defs[f"{prefix}w3"] = ParamDef((L, d, d_ff), (None, "fsdp", "tp"))
    return defs


def embed_defs(cfg: ModelConfig) -> dict:
    d = cfg.d_model
    defs = {
        "tok_embed": ParamDef((cfg.vocab_padded, d), ("tp", "fsdp")),
        "final_norm": ParamDef((d,), ("fsdp",), "zeros"),
    }
    if not cfg.tie_embeddings:
        defs["lm_head"] = ParamDef((d, cfg.vocab_padded), ("fsdp", "tp"))
    if cfg.modality == "vision":
        defs["patch_proj"] = ParamDef((d, d), ("fsdp", "tp"))
    if cfg.modality == "audio":
        defs["frame_proj"] = ParamDef((d, d), ("fsdp", "tp"))
    return defs
