"""Plain float32 references of what the timed path computes, and the
lower-precision controls that the limits are set against.

Nothing here imports the program. The generator's weights are the
benchmark's own (weights.py), made from the run's seed and upcast to
float32; the equations are written out below from the configuration
file's sizes. Retrieval and SCR are recomputed in float64 numpy over the
index's stored vectors.

The generator's equations, as served (PERF.md lists where they depart
from the published model): token embedding times sqrt(hidden) when tied;
RMSNorm x * rsqrt(mean(x^2) + 1e-5) * (1 + w); q, k, v projections (with
bias where the configuration has it) and rotary embedding on half-split
dimensions; causal grouped-query softmax attention at 1/sqrt(head_dim);
output projection and residual; a SwiGLU feed-forward (silu(x W1) * x W3)
W2, or for experts a softmax router over all experts, its top-k renormed
to sum to one, and the weighted sum of those experts' SwiGLU outputs;
a final RMSNorm and the tied embedding as the output head, over the
padded vocabulary the weights hold.
"""
from __future__ import annotations

import math
from dataclasses import dataclass
from functools import partial
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

HIGHEST = "highest"
NORM_EPS = 1e-5


@dataclass(frozen=True)
class Arch:
    """The generator's sizes, from the configuration file."""
    layers: int
    d: int
    heads: int
    kv_heads: int
    head_dim: int
    ff: int
    vocab: int
    vocab_padded: int
    rope_theta: float
    qkv_bias: bool
    experts: int = 0
    top_k: int = 0

    @classmethod
    def from_config(cls, conf: dict) -> "Arch":
        m = conf["model"]
        v = m["vocab_size"]
        return cls(layers=m["num_hidden_layers"], d=m["hidden_size"],
                   heads=m["num_attention_heads"],
                   kv_heads=m["num_key_value_heads"], head_dim=m["head_dim"],
                   ff=m["intermediate_size"], vocab=v,
                   vocab_padded=-(-v // 256) * 256,
                   rope_theta=float(m["rope_theta"]),
                   qkv_bias=bool(m.get("attention_bias", False)
                                 or m.get("qkv_bias", False)),
                   experts=m.get("num_local_experts", 0),
                   top_k=m.get("num_experts_per_tok", 0))


# ------------------------------------------------------------ precision


def fp8(x, axis: Optional[int] = -1):
    """x rounded to float8 e4m3 with one scale per slice along `axis`
    (the control's precision: the step below the configuration's
    bfloat16)."""
    import jax.numpy as jnp
    s = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / 448.0
    s = jnp.maximum(s, 1e-30)
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def _mm(x, w, low: bool):
    """x @ w in float32 at full precision; the control first rounds both
    operands to fp8 (x per row, w per output column)."""
    import jax.numpy as jnp
    if low:
        x, w = fp8(x, -1), fp8(w, -2)
    return jnp.matmul(x, w, precision=HIGHEST)


def _rms(x, w):
    import jax
    import jax.numpy as jnp
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + NORM_EPS) * (1.0 + w)


def _rope(x, pos, theta):
    import jax.numpy as jnp
    dh = x.shape[-1]
    freqs = 1.0 / (theta ** (jnp.arange(0, dh, 2, dtype=jnp.float32) / dh))
    ang = pos[:, None].astype(jnp.float32) * freqs          # [T, dh/2]
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = x[..., :dh // 2], x[..., dh // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def _layer(a: Arch, low: bool, x, w):
    """One decoder layer over a whole sequence x [T, d]."""
    import jax
    import jax.numpy as jnp
    T = x.shape[0]
    hd, H, G = a.head_dim, a.heads, a.kv_heads
    pos = jnp.arange(T)
    y = _rms(x, w["attn_norm"])
    q, k, v = (_mm(y, w[n], low) for n in ("wq", "wk", "wv"))
    if a.qkv_bias:
        q, k, v = q + w["bq"], k + w["bk"], v + w["bv"]
    q = _rope(q.reshape(T, H, hd), pos, a.rope_theta)
    k = _rope(k.reshape(T, G, hd), pos, a.rope_theta)
    v = v.reshape(T, G, hd)
    k = jnp.repeat(k, H // G, axis=1)
    v = jnp.repeat(v, H // G, axis=1)
    s = jnp.einsum("qhd,khd->hqk", q, k, precision=HIGHEST) / math.sqrt(hd)
    s = jnp.where(pos[None, :, None] >= pos[None, None, :], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    ctx = jnp.einsum("hqk,khd->qhd", p, v, precision=HIGHEST)
    x = x + _mm(ctx.reshape(T, H * hd), w["wo"], low)
    y = _rms(x, w["mlp_norm"])
    if not a.experts:
        h = jax.nn.silu(_mm(y, w["w1"], low)) * _mm(y, w["w3"], low)
        return x + _mm(h, w["w2"], low)
    probs = jax.nn.softmax(_mm(y, w["router"], False), axis=-1)   # [T, E]
    topv, topi = jax.lax.top_k(probs, a.top_k)
    topv = topv / topv.sum(-1, keepdims=True)
    gate = jnp.zeros_like(probs).at[jnp.arange(T)[:, None], topi].set(topv)
    out = jnp.zeros_like(x)
    for e in range(a.experts):
        h = (jax.nn.silu(_mm(y, w["we1"][e], low))
             * _mm(y, w["we3"][e], low))
        out = out + gate[:, e:e + 1] * _mm(h, w["we2"][e], low)
    return x + out


def _embed(a: Arch, table, ids):
    import jax.numpy as jnp
    return jnp.take(table, ids, axis=0) * math.sqrt(a.d)


def _head_gaps(a: Arch, x, xlow, final_norm, table, targets):
    """Per position: the reference's best logit, its logit at the served
    target, and (from the control's hidden states `xlow`) its logit at
    the token the fp8 control puts first. Logits span the padded
    vocabulary the weights hold."""
    import jax.numpy as jnp
    ref = _mm(_rms(x, final_norm), table.T, False)
    best = jnp.max(ref, axis=-1)
    got = jnp.take_along_axis(ref, targets[..., None], -1)[..., 0]
    if xlow is None:
        return best, got, got
    pick = jnp.argmax(_mm(_rms(xlow, final_norm), table.T, True), axis=-1)
    alt = jnp.take_along_axis(ref, pick[..., None], -1)[..., 0]
    return best, got, alt


class Generator:
    """The reference forward over whole sequences, a batch at a time and
    layer by layer (one compiled layer program, reused for every layer),
    in float32, and in fp8 for the control."""

    def __init__(self, a: Arch, flat: Dict[str, object]):
        """Takes the float32 weights out of `flat` (which ends empty),
        split per layer."""
        import jax
        self.a = a
        self.layers: List[dict] = [{} for _ in range(a.layers)]
        for name in [n for n in flat if n.startswith("layers.")]:
            stacked = flat.pop(name)          # one stacked leaf at a time
            for i in range(a.layers):
                self.layers[i][name.split(".", 1)[1]] = stacked[i]
            del stacked
        self.final_norm = flat.pop("final_norm")
        self.table = flat.pop("tok_embed")
        self._layer = {lo: jax.jit(jax.vmap(partial(_layer, a, lo),
                                            in_axes=(0, None)))
                       for lo in (False, True)}
        self._embed = jax.jit(partial(_embed, a))
        self._gaps = jax.jit(partial(_head_gaps, a))

    def hidden(self, ids, low: bool = False):
        x = self._embed(self.table, ids)
        for lw in self.layers:
            x = self._layer[low](x, lw)
        return x

    def gaps(self, seqs: Sequence[Tuple[Sequence[int], Sequence[int]]],
             length: int, control: bool = False) -> List[Tuple[np.ndarray,
                                                              np.ndarray]]:
        """For each (prompt, served tokens): how far each served token's
        reference logit lies below the reference's best at its position,
        and the same for the token the fp8 control puts first there
        (equal to the first with `control` off). Sequences are
        right-padded to `length`; attention is causal, so padding
        changes no earlier position."""
        import jax.numpy as jnp
        ids = np.zeros((len(seqs), length), np.int32)
        tgt = np.zeros((len(seqs), length), np.int32)
        for b, (prompt, served) in enumerate(seqs):
            full = list(prompt) + list(served)
            ids[b, :len(full) - 1] = full[:-1]
            tgt[b, :len(full) - 1] = full[1:]
        ids, tgt = jnp.asarray(ids), jnp.asarray(tgt)
        x = self.hidden(ids)
        xlow = self.hidden(ids, low=True) if control else None
        best, got, alt = (np.asarray(v) for v in
                          self._gaps(x, xlow, self.final_norm, self.table,
                                     tgt))
        out = []
        for b, (prompt, served) in enumerate(seqs):
            rows = slice(len(prompt) - 1, len(prompt) - 1 + len(served))
            out.append((best[b, rows] - got[b, rows],
                        best[b, rows] - alt[b, rows]))
        return out


# ------------------------------------------------------------ retrieval


@dataclass
class IndexData:
    """The index's stored state, read back to the host: centroids
    [NC, d], cluster rows [NC, CAP, d] with valid counts [NC] and the
    document id of every row [NC, CAP]."""
    centroids: np.ndarray
    rows: np.ndarray
    lens: np.ndarray
    doc_ids: np.ndarray

    def vectors(self) -> Dict[int, np.ndarray]:
        out = {}
        for c in range(len(self.lens)):
            for j in range(int(self.lens[c])):
                out[int(self.doc_ids[c, j])] = self.rows[c, j]
        return out


def _sq(a, b):
    a = np.asarray(a, np.float64)
    b = np.asarray(b, np.float64)
    return ((a - b) ** 2).sum(-1)


def route(ix: IndexData, q: np.ndarray, n_probe: int,
          dtype=np.float64) -> np.ndarray:
    """The n_probe nearest centroids, in float64 (or, for the control,
    from operands rounded to `dtype` first)."""
    c = ix.centroids.astype(dtype).astype(np.float64)
    d2 = _sq(c, q.astype(dtype).astype(np.float64)[None])
    return np.argsort(d2, kind="stable")[:n_probe]


def scan(ix: IndexData, q: np.ndarray, probes, k: int,
         dtype=np.float64) -> Tuple[np.ndarray, np.ndarray]:
    """Top-k (doc ids, squared distances) over the probed clusters."""
    ids, dists = [], []
    qq = q.astype(dtype).astype(np.float64)
    for c in probes:
        n = int(ix.lens[c])
        rows = ix.rows[c, :n].astype(dtype).astype(np.float64)
        dists.append(_sq(rows, qq[None]))
        ids.append(ix.doc_ids[c, :n])
    ids = np.concatenate(ids) if ids else np.zeros(0, np.int64)
    dists = np.concatenate(dists) if dists else np.zeros(0)
    o = np.argsort(dists, kind="stable")[:k]
    return ids[o].astype(np.int64), dists[o]


def retrieval_numbers(ix: IndexData, calls, *, tie: float = 1e-4) -> dict:
    """Over every recorded retrieval (q [B, d], ids [B, k], dists [B, k],
    k, n_probe), `retrieval_err`: the larger of

    - the largest amount by which the r-th returned document lies farther
      from its query (float64) than the reference's r-th, over all r
      (where two centroids tie at the probe boundary within `tie`, either
      routing is the reference's: a float32 squared distance over a few
      hundred dimensions can be off by d * 2^-23, about 2e-5), and
    - the largest gap between a returned distance and the float64
      distance of the returned document.
    """
    vec = ix.vectors()
    rank_gap, dist_err = 0.0, 0.0
    for q, ids, dists, k, n_probe in calls:
        for b in range(len(q)):
            got = [int(i) for i in ids[b] if i >= 0]
            if any(i not in vec for i in got):
                return {"retrieval_err": math.inf}
            dgot = np.sort(_sq(np.stack([vec[i] for i in got]), q[b][None])
                           ) if got else np.zeros(0)
            gaps = []
            for probes in _routings(ix, q[b], n_probe, tie):
                _, dref = scan(ix, q[b], probes, k)
                if len(dgot) < len(dref):
                    gaps.append(math.inf)
                else:
                    gaps.append(float(np.max(dgot[:len(dref)] - dref,
                                             initial=0.0)))
            rank_gap = max(rank_gap, min(gaps))
            for i, dv in zip(ids[b], dists[b]):
                if i >= 0:
                    dist_err = max(dist_err, abs(float(dv) - float(
                        _sq(vec[int(i)], q[b]))))
    return {"retrieval_err": max(rank_gap, dist_err)}


def _routings(ix: IndexData, q, n_probe: int, tie: float):
    """The reference routing, and where the n_probe-th and next centroids
    tie within `tie`, each way of breaking that tie."""
    d2 = _sq(ix.centroids, np.asarray(q, np.float64)[None])
    o = np.argsort(d2, kind="stable")
    yield o[:n_probe]
    if n_probe < len(o):
        edge = d2[o[n_probe - 1]]
        near = [c for c in o if abs(d2[c] - edge) <= tie]
        if len(near) > 1:
            keep = [c for c in o[:n_probe] if c not in near]
            for c in near:
                alt = keep + [x for x in near if x != c][:n_probe - len(keep)]
                if len(alt) == n_probe:
                    yield np.asarray(alt)


def control_retrievals(ix: IndexData, calls) -> list:
    """The control in the kernel's place: routing and scan from operands
    rounded to bfloat16 (the step below the kernels' float32)."""
    import ml_dtypes
    bf = ml_dtypes.bfloat16
    out = []
    for q, ids, dists, k, n_probe in calls:
        ci, cd = [], []
        for b in range(len(q)):
            i, dd = scan(ix, q[b], route(ix, q[b], n_probe, bf), k, bf)
            ci.append(np.pad(i, (0, k - len(i)), constant_values=-1))
            cd.append(np.pad(dd, (0, k - len(dd))))
        out.append((q, np.stack(ci), np.stack(cd), k, n_probe))
    return out


# ------------------------------------------------------------------ SCR


def scr_numbers(windows: np.ndarray, lens: np.ndarray, calls) -> dict:
    """Over every recorded SCR selection (q [B, d], doc ids [B, K],
    scores [B, K], windows chosen [B, K]), `scr_err`: the larger of

    - the largest amount by which the chosen window's float64 score lies
      below the document's best window, and
    - the largest gap between a returned score and the float64 score of
      the chosen window.
    """
    gap, err = 0.0, 0.0
    for q, doc_ids, scores, wins in calls:
        qq = np.asarray(q, np.float64)
        for b, j in np.argwhere(doc_ids >= 0):
            di = int(doc_ids[b, j])
            n = int(lens[di])
            if n == 0:
                continue
            s = np.asarray(windows[di, :n], np.float64) @ qq[b]
            w = int(wins[b, j])
            if not 0 <= w < n:
                return {"scr_err": math.inf}
            gap = max(gap, float(s.max() - s[w]))
            err = max(err, abs(float(scores[b, j]) - float(s[w])))
    return {"scr_err": max(gap, err)}


def control_selects(windows: np.ndarray, lens: np.ndarray, calls) -> list:
    """The control in `scr_select`'s place: scores from bfloat16 operands,
    first best window."""
    import ml_dtypes
    bf = ml_dtypes.bfloat16
    out = []
    for q, doc_ids, scores, wins in calls:
        cs = np.zeros(doc_ids.shape, np.float32)
        cw = np.full(doc_ids.shape, -1, np.int32)
        for b, j in np.argwhere(doc_ids >= 0):
            di = int(doc_ids[b, j])
            n = int(lens[di])
            if n == 0:
                continue
            s = (windows[di, :n].astype(bf).astype(np.float32)
                 @ q[b].astype(bf).astype(np.float32))
            cw[b, j] = int(np.argmax(s))
            cs[b, j] = s[cw[b, j]]
        out.append((q, doc_ids, cs, cw))
    return out
