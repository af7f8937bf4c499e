"""The generator's weights, made by the benchmark from the run's seed in
one jitted call on the device, in the type they are served in.

Every value is a bfloat16 one, held in float32: the program keeps them
as float32 masters and casts them to bfloat16 in every call, as it does
its own, and the reference computes with the same float32 values. The
layout is the program's weight tree:
stacked per-layer matrices under "layers", the tied embedding and the
final norm beside them; the harness checks it against the program's own
declared shapes before serving.

The distribution is chosen so that greedy decoding of random weights is
not degenerate, which the correctness check needs: matrices are
N(0, GAIN^2 / fan_in), the tied embedding N(0, EMBED_STD^2), biases and
norm offsets N(0, SMALL_STD^2). At the program's own initialiser
(N(0, 0.02^2) everywhere) the residual stream stays dominated by the
scaled input embedding, every position's best next token is the token
itself, and a served token's gap to the reference is 0 whatever the
program computes (PERF.md, Findings).
"""
from __future__ import annotations

import math
from functools import partial
from typing import Dict, List, Tuple

GAIN = 1.5
EMBED_STD = 0.02
SMALL_STD = 0.02


def leaves(a) -> List[Tuple[str, tuple, str]]:
    """(path, shape, kind) of every weight of `a` (a reference.Arch), in
    sorted-path order; kind is "matrix", "embed" or "small"."""
    L, d, hd = a.layers, a.d, a.head_dim
    layer = {"attn_norm": ((L, d), "small"),
             "wq": ((L, d, a.heads * hd), "matrix"),
             "wk": ((L, d, a.kv_heads * hd), "matrix"),
             "wv": ((L, d, a.kv_heads * hd), "matrix"),
             "wo": ((L, a.heads * hd, d), "matrix"),
             "mlp_norm": ((L, d), "small")}
    if a.qkv_bias:
        layer.update(bq=((L, a.heads * hd), "small"),
                     bk=((L, a.kv_heads * hd), "small"),
                     bv=((L, a.kv_heads * hd), "small"))
    if a.experts:
        E, f = a.experts, a.ff
        layer.update(router=((L, d, E), "matrix"),
                     we1=((L, E, d, f), "matrix"),
                     we2=((L, E, f, d), "matrix"),
                     we3=((L, E, d, f), "matrix"))
    else:
        layer.update(w1=((L, d, a.ff), "matrix"),
                     w2=((L, a.ff, d), "matrix"),
                     w3=((L, d, a.ff), "matrix"))
    out = [(f"layers.{n}", *layer[n]) for n in layer]
    out += [("final_norm", (d,), "small"),
            ("tok_embed", (a.vocab_padded, d), "embed")]
    return sorted(out)


def _std(shape: tuple, kind: str) -> float:
    if kind == "matrix":
        return GAIN / math.sqrt(shape[-2])
    return EMBED_STD if kind == "embed" else SMALL_STD


def _make(spec, seed, dtype):
    import jax
    import jax.numpy as jnp
    keys = jax.random.split(jax.random.PRNGKey(seed), len(spec))
    return {name: (jax.random.normal(k, shape, jnp.float32)
                   * _std(shape, kind)).astype(jnp.bfloat16).astype(dtype)
            for (name, shape, kind), k in zip(spec, keys)}


def make(a, seed: int, dtype) -> Dict[str, object]:
    """Flat {path: array}: bfloat16 values, held in `dtype`."""
    import jax
    spec = tuple(leaves(a))
    return jax.jit(partial(_make, spec, dtype=dtype))(seed)


def nested(flat: Dict[str, object]) -> dict:
    """The program's tree: {"layers": {...}, "tok_embed", "final_norm"}."""
    out: dict = {"layers": {}}
    for name, arr in flat.items():
        if name.startswith("layers."):
            out["layers"][name.split(".", 1)[1]] = arr
        else:
            out[name] = arr
    return out
