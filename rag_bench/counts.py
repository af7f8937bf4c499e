"""Algorithmic operations and bytes of each program on the served path.

They count the work any implementation has to do for the tokens and
queries actually served, whatever the program does beyond it, so a share
of a peak computed from them cannot pass 100% unless a time is wrong:

- weights are read once per program call, at the configuration's
  bfloat16 (2 bytes a parameter), whatever the program keeps them in;
- for experts, only those the call's tokens were routed to (the expected
  number of distinct experts when n tokens each pick k of E uniformly,
  since the program does not report its routing);
- real tokens only, never padding; attention over the live length;
- the output head only for the rows that are read (a prompt's last
  position, and each active decode row);
- retrieval and SCR rows that hold a vector, read once per call.

FLOPs count a multiply-add as 2.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Iterable, Sequence

BF16 = 2
F32 = 4


@dataclass(frozen=True)
class Model:
    layers: int
    d: int
    heads: int
    kv_heads: int
    head_dim: int
    ff: int
    vocab: int
    experts: int = 0
    top_k: int = 0
    qkv_bias: bool = False

    @classmethod
    def from_config(cls, conf: dict) -> "Model":
        m = conf["model"]
        return cls(layers=m["num_hidden_layers"], d=m["hidden_size"],
                   heads=m["num_attention_heads"],
                   kv_heads=m["num_key_value_heads"], head_dim=m["head_dim"],
                   ff=m["intermediate_size"], vocab=m["vocab_size"],
                   experts=m.get("num_local_experts", 0),
                   top_k=m.get("num_experts_per_tok", 0),
                   qkv_bias=bool(m.get("attention_bias", False)
                                 or m.get("qkv_bias", False)))

    # parameters per layer
    @property
    def attn_params(self) -> int:
        q = self.heads * self.head_dim
        kv = self.kv_heads * self.head_dim
        return 2 * self.d * q + 2 * self.d * kv

    @property
    def expert_params(self) -> int:
        return 3 * self.d * self.ff

    def ffn_params_used(self) -> int:
        """FFN parameters one token multiplies by."""
        if self.experts:
            return self.d * self.experts + self.top_k * self.expert_params
        return 3 * self.d * self.ff

    def layer_weight_bytes(self, tokens: int) -> float:
        """bf16 bytes of one layer's weights that `tokens` tokens need."""
        n = self.attn_params + 2 * self.d
        if self.qkv_bias:
            n += (self.heads + 2 * self.kv_heads) * self.head_dim
        if self.experts:
            n += self.d * self.experts
            n += self.distinct_experts(tokens) * self.expert_params
        else:
            n += 3 * self.d * self.ff
        return n * BF16

    def distinct_experts(self, tokens: int) -> float:
        if tokens <= 0:
            return 0.0
        e, k = self.experts, self.top_k
        return e * (1.0 - (1.0 - k / e) ** tokens)

    @property
    def kv_bytes_per_position(self) -> int:
        """bf16 K and V of one position across all layers."""
        return self.layers * 2 * self.kv_heads * self.head_dim * BF16

    def token_flops(self) -> int:
        """Linear-layer FLOPs of one token through every layer."""
        return 2 * self.layers * (self.attn_params + self.ffn_params_used())

    def attn_flops(self, kv_len: int) -> int:
        """Score and context FLOPs of one query over kv_len positions."""
        return 4 * self.layers * self.heads * self.head_dim * kv_len

    @property
    def head_flops(self) -> int:
        return 2 * self.d * self.vocab

    @property
    def head_bytes(self) -> int:
        return self.d * self.vocab * BF16


def prefill_chunk(m: Model, offset: int, n: int, last: bool):
    """(flops, bytes) of one prompt chunk: n real tokens at positions
    offset..offset+n-1, the output head only on the prompt's last row."""
    flops = n * m.token_flops()
    flops += sum(m.attn_flops(p + 1) for p in range(offset, offset + n))
    byts = m.layers * m.layer_weight_bytes(n)
    byts += (offset + n) * m.kv_bytes_per_position        # read + write
    if last:
        flops += m.head_flops
        byts += m.head_bytes
    return flops, byts


def decode_step(m: Model, kv_lens: Sequence[int]):
    """(flops, bytes) of one decode step over the active rows, each
    attending its own live length (its new position included)."""
    b = len(kv_lens)
    if b == 0:
        return 0, 0
    flops = b * (m.token_flops() + m.head_flops)
    flops += sum(m.attn_flops(n) for n in kv_lens)
    byts = m.layers * m.layer_weight_bytes(b) + m.head_bytes
    byts += sum(kv_lens) * m.kv_bytes_per_position          # K/V read
    byts += b * m.kv_bytes_per_position                     # new K/V
    return flops, byts


def route_and_scan(d: int, n_clusters: int, batch: int,
                   rows_per_query: Iterable[int], distinct_rows: int):
    """(flops, bytes) of one fused route-and-scan call: every query
    against every centroid, then against each valid row of its probed
    clusters; bytes read the centroids, the queries and each distinct
    probed row once (float32)."""
    rows = sum(rows_per_query)
    flops = 2 * d * (batch * n_clusters + rows)
    byts = F32 * d * (n_clusters + batch + distinct_rows)
    return flops, byts


def scr_select(d: int, batch: int, windows_per_pair: Iterable[int],
               distinct_windows: int):
    """(flops, bytes) of one SCR select call: each (query, document)
    pair scores the document's valid windows; bytes read the queries and
    each distinct document's windows once (float32)."""
    flops = 2 * d * sum(windows_per_pair)
    byts = F32 * d * (batch + distinct_windows)
    return flops, byts


def roofline_time(flops: float, byts: float, peaks: dict) -> float:
    """The least time the chip could take: the larger of the compute and
    the memory bound."""
    return max(flops / peaks["bf16_flops_per_s"],
               byts / peaks["hbm_bytes_per_s"])
