"""Pure-jnp oracles for every Pallas kernel (the correctness contract)."""
from __future__ import annotations

import jax
import jax.numpy as jnp

# "+infinity" sentinel shared with the ecoscan kernel (plain float: jnp
# consts can't be captured inside Pallas kernel bodies).
NEG = 3.4e38


def ecoscan(q, data, lens, probe_ids, k, block_map=None):
    """EcoVector inverted-list scan reference.

    q: [B, d]; data: [R, CAP, d]; lens: [R] valid counts;
    probe_ids: [B, P] cluster ids per query (ids < 0 are skipped padding);
    block_map: optional [NC] i32 cluster-id -> scan-row indirection
    (entries < 0 mask the cluster; identity when omitted).
    Returns (dists [B,K], ids [B,K]) where ids are global slot ids
    row*CAP+j (-1 for missing candidates), L2 distances ascending.
    """
    B, d = q.shape
    R, CAP, _ = data.shape
    if block_map is None:
        block_map = jnp.arange(R, dtype=jnp.int32)
    blk = block_map[jnp.maximum(probe_ids, 0)]    # [B, P] scan rows
    safe = jnp.maximum(blk, 0)
    gathered = data[safe]                         # [B, P, CAP, d]
    diff = gathered - q[:, None, None, :]
    dist = jnp.sum(diff * diff, axis=-1)          # [B, P, CAP]
    slot = jnp.arange(CAP)[None, None, :]
    valid = ((slot < lens[safe][:, :, None])
             & (probe_ids[:, :, None] >= 0) & (blk[:, :, None] >= 0))
    dist = jnp.where(valid, dist, NEG)
    ids = jnp.where(valid, safe[:, :, None] * CAP + slot, -1)
    flat_d = dist.reshape(B, -1)
    flat_i = ids.reshape(B, -1).astype(jnp.int32)
    vals, idx = jax.lax.top_k(-flat_d, k)
    return -vals, jnp.take_along_axis(flat_i, idx, axis=1)


def route_and_scan(q, centroids, data, lens, n_probe, k):
    """Fused route->scan reference: dense centroid top-k then `ecoscan`."""
    q = q.astype(jnp.float32)
    cent = centroids.astype(jnp.float32)
    d2 = (jnp.sum(q * q, axis=1, keepdims=True) - 2.0 * q @ cent.T
          + jnp.sum(cent * cent, axis=1)[None, :])
    _, probes = jax.lax.top_k(-d2, n_probe)
    probes = probes.astype(jnp.int32)
    dists, slots = ecoscan(q, data, lens, probes, k)
    return dists, slots, probes


def kmeans_assign(x, centroids):
    """x: [N, d]; centroids: [NC, d] -> (assign [N] i32, sqdist [N])."""
    d2 = (jnp.sum(x * x, 1)[:, None] - 2 * x @ centroids.T +
          jnp.sum(centroids * centroids, 1)[None, :])
    a = jnp.argmin(d2, axis=1).astype(jnp.int32)
    return a, jnp.take_along_axis(d2, a[:, None], axis=1)[:, 0]


def scr_score(windows, q):
    """windows: [B, NW, d]; q: [B, d] -> cosine-style scores [B, NW]."""
    return jnp.einsum("bnd,bd->bn", windows, q)


def scr_select(q, data, lens, doc_ids):
    """Fused SCR select reference (§4 steps 1+2).

    q: [B, d]; data: [ND, CAPW, d] window-embedding blocks; lens: [ND]
    valid windows per doc; doc_ids: [B, K] retrieved docs per query
    (ids < 0 are padding). Returns (scores [B, K], wins [B, K]): the best
    window's query·window inner product and its within-doc window id per
    retrieved doc, (-NEG, -1) for padding slots / windowless docs. Ties
    resolve to the lowest window id (first max)."""
    B, K = doc_ids.shape
    if data.shape[0] == 0 or data.shape[1] == 0:    # no docs / no windows
        return (jnp.full((B, K), -NEG, jnp.float32),
                jnp.full((B, K), -1, jnp.int32))
    safe = jnp.maximum(doc_ids, 0)
    g = data[safe]                                  # [B, K, CAPW, d]
    s = jnp.einsum("bkwd,bd->bkw", g.astype(jnp.float32),
                   q.astype(jnp.float32))
    CAPW = data.shape[1]
    slot = jnp.arange(CAPW)[None, None, :]
    valid = (slot < lens[safe][:, :, None]) & (doc_ids[:, :, None] >= 0)
    s = jnp.where(valid, s, -NEG)
    wins = jnp.argmax(s, axis=-1).astype(jnp.int32)
    scores = jnp.take_along_axis(s, wins[..., None].astype(jnp.int32),
                                 axis=-1)[..., 0]
    wins = jnp.where(jnp.any(valid, axis=-1), wins, -1)
    return scores, wins


def pq_adc(lut, codes):
    """lut: [B, M, 256] distance tables; codes: [N, M] uint8 ->
    scores [B, N] = sum_m lut[b, m, codes[n, m]]."""
    g = jnp.take_along_axis(
        lut[:, None, :, :],                          # [B,1,M,256]
        codes.astype(jnp.int32)[None, :, :, None],   # [1,N,M,1]
        axis=3)[..., 0]                              # [B,N,M]
    return jnp.sum(g, axis=-1)


def decode_attention(q, k, v, kv_len, ring: bool = False):
    """q: [B, H, dh]; k,v: [B, S, G, dh]; H % G == 0. Softmax over the
    first kv_len positions (kv_len: scalar or per-row [B] vector).
    `ring=True`: per-slot sliding-window ring pages — every filled slot
    is valid, i.e. the mask length is min(kv_len, S) per row."""
    B, H, dh = q.shape
    S, G = k.shape[1], k.shape[2]
    qg = q.reshape(B, G, H // G, dh)
    s = jnp.einsum("bgnd,bsgd->bgns", qg, k) / jnp.sqrt(dh).astype(q.dtype)
    s = s.astype(jnp.float32)
    lens = jnp.broadcast_to(jnp.asarray(kv_len, jnp.int32).reshape(-1), (B,))
    if ring:
        lens = jnp.minimum(lens, S)
    mask = jnp.arange(S)[None, None, None, :] < lens[:, None, None, None]
    s = jnp.where(mask, s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bgns,bsgd->bgnd", p.astype(v.dtype), v)
    return o.reshape(B, H, dh)


def decode_attention_paged(q, new, pool, kv_len, cursor, table, layer):
    """Block-table decode reference, in float32. q: [B, H, dh]; new: the
    step's own rows, (k, v) [B, G, dh] or int8 (kq, vq, ks, vs) with
    scales [B, G]; pool: the matching pools [L, P, ps, lanes >= G*dh]
    (scales [L, P, ps, G]); kv_len [B]: earlier positions, read from layer
    `layer` through table [B, W] (entry w backs positions [w*ps,
    (w+1)*ps)); cursor [B]: a position among them left out. Gathers each
    row's logical buffer, dequantizes it, appends the step's own token
    and takes one softmax."""
    B, H, dh = q.shape
    _, P, ps, _ = pool[0].shape
    G, W = new[0].shape[1], table.shape[1]
    j = jnp.arange(W * ps)
    idx = table[:, j // ps] * ps + (j % ps)            # [B, W*ps]

    def gather(x):
        x = x[layer].reshape((P * ps, -1))[:, :G * dh].astype(jnp.float32)
        return jnp.take(x, idx, axis=0).reshape(B, W * ps, G, -1)

    kv = [gather(x) for x in pool]
    own = [x.astype(jnp.float32).reshape(B, 1, G, -1) for x in new]
    if len(pool) == 4:                                 # int8 and scales
        kv = [kv[0] * kv[2], kv[1] * kv[3]]
        own = [own[0] * own[2], own[1] * own[3]]
    k, v = (jnp.concatenate([a, o], axis=1) for a, o in zip(kv, own))
    valid = ((j[None] < kv_len[:, None]) & (j[None] != cursor[:, None]))
    valid = jnp.concatenate([valid, jnp.ones((B, 1), bool)], axis=1)
    hi = jax.lax.Precision.HIGHEST
    qg = q.astype(jnp.float32).reshape(B, G, H // G, dh)
    s = jnp.einsum("bgnd,bsgd->bgns", qg, k, precision=hi) / jnp.sqrt(dh)
    s = jnp.where(valid[:, None, None, :], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bgns,bsgd->bgnd", p, v, precision=hi)
    return o.reshape(B, H, dh)


def flash_prefill(q, k, v, *, causal=True, window=None):
    """q,k,v: [B, H, S, dh] (kv pre-expanded to H heads)."""
    B, H, S, dh = q.shape
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k) / jnp.sqrt(dh).astype(q.dtype)
    s = s.astype(jnp.float32)
    qi = jnp.arange(S)[:, None]
    ki = jnp.arange(S)[None, :]
    mask = jnp.ones((S, S), bool)
    if causal:
        mask &= ki <= qi
    if window is not None:
        mask &= (qi - ki) < window
    s = jnp.where(mask[None, None], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bhkd->bhqd", p.astype(v.dtype), v)
