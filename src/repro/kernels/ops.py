"""Jit'd dispatch wrappers: Pallas kernel on TPU (or interpret=True on CPU
for validation), pure-jnp reference otherwise. `use_pallas` is the build
switch; every kernel resolves interpret mode from the platform through
`default_interpret`, so nothing here catches a kernel failure and
silently answers some other way.
"""
from __future__ import annotations

import jax

from repro.kernels import ref
from repro.kernels.ecoscan import ecoscan as _ecoscan
from repro.kernels.ecoscan import route_and_scan as _route_and_scan
from repro.kernels.ecoscan import route_topk as _route_topk
from repro.kernels.kmeans_assign import kmeans_assign as _kmeans_assign
from repro.kernels.scr_score import scr_score as _scr_score
from repro.kernels.scr_select import scr_select as _scr_select
from repro.kernels.pq_adc import pq_adc as _pq_adc
from repro.kernels.decode_attention import decode_attention as _decode_attn
from repro.kernels.decode_attention import (
    decode_attention_paged as _decode_attn_paged)
from repro.kernels.flash_prefill import flash_prefill as _flash_prefill


def default_interpret() -> bool:
    """Backend-aware interpret default shared by every kernel dispatch:
    compiled Mosaic on real TPU, interpret mode (correctness-grade, runs
    the kernel body through XLA) everywhere else. Kernel entry points
    take `interpret=None` and resolve it here, so callers never hardcode
    a backend assumption."""
    return jax.default_backend() != "tpu"


def ecoscan(q, data, lens, probe_ids, k=10, use_pallas=True, block_map=None):
    if use_pallas:
        return _ecoscan(q, data, lens, probe_ids, k=k, block_map=block_map)
    return ref.ecoscan(q, data, lens, probe_ids, k, block_map=block_map)


def route_topk(q, centroids, n_probe=4, use_pallas=True):
    """Centroid routing only (matmul + lax.top_k) -> probes [B, n_probe].
    Same math as the routing half of `route_and_scan`, so a split
    route->scan caller picks bitwise-identical probes."""
    del use_pallas      # pure jnp either way; one implementation on purpose
    return _route_topk(q, centroids, n_probe)


def route_and_scan(q, centroids, data, lens, n_probe=4, k=10,
                   use_pallas=True):
    """One fused device call: centroid routing + probed-cluster scan.
    Returns (dists [B,k], slots [B,k], probes [B,n_probe])."""
    if use_pallas:
        return _route_and_scan(q, centroids, data, lens, n_probe=n_probe,
                               k=k)
    return ref.route_and_scan(q, centroids, data, lens, n_probe, k)


def kmeans_assign(x, centroids, use_pallas=True):
    if use_pallas:
        return _kmeans_assign(x, centroids)
    return ref.kmeans_assign(x, centroids)


def scr_score(windows, q, use_pallas=True):
    if use_pallas:
        return _scr_score(windows, q)
    return ref.scr_score(windows, q)


def scr_select(q, data, lens, doc_ids, use_pallas=True):
    """Fused SCR select: per-(query, retrieved doc) best window id and
    query·window score in one device call (DESIGN.md §7)."""
    if use_pallas:
        return _scr_select(q, data, lens, doc_ids)
    return ref.scr_select(q, data, lens, doc_ids)


def pq_adc(lut, codes, use_pallas=True):
    if use_pallas:
        return _pq_adc(lut, codes)
    return ref.pq_adc(lut, codes)


def decode_attention(q, k, v, kv_len, use_pallas=True, ring=False):
    """Flash-decode attention; `kv_len` scalar or per-row [B] vector,
    `ring=True` for per-slot sliding-window ring pages (mask length
    min(kv_len, S) per row)."""
    if use_pallas:
        return _decode_attn(q, k, v, kv_len, ring=ring)
    return ref.decode_attention(q, k, v, kv_len, ring=ring)


def decode_attention_paged(q, new, pool, kv_len, cursor, table, layer,
                           use_pallas=True):
    """Block-table flash decode read in place from the layer-stacked page
    pool [L, P, ps, lanes] through a per-row table [B, W]: each row's
    `kv_len` earlier positions (less a ring `cursor`) plus its own token
    `new` (scalar-prefetched on TPU, so only live pages are fetched)."""
    if use_pallas:
        return _decode_attn_paged(q, new, pool, kv_len, cursor, table, layer)
    return ref.decode_attention_paged(q, new, pool, kv_len, cursor, table,
                                      layer)


def flash_prefill(q, k, v, causal=True, window=None, use_pallas=True):
    if use_pallas:
        return _flash_prefill(q, k, v, causal=causal, window=window)
    return ref.flash_prefill(q, k, v, causal=causal, window=window)
