"""On-device sLM: a language model behind `serving.Engine`, with
tokenisation, so RAG pipelines run REAL generation.

The generator's `ModelConfig` is passed in: `get_config("qwen25_0_5b")`
is the paper's own generator at its published widths (what a chip
serves), `get_reduced("qwen25_0_5b")` the CPU smoke size the tests use.
Weights are random, made from `seed`. The point is not answer quality —
it is that the full on-device pipeline (EcoVector retrieval -> SCR ->
prefill -> decode loop) executes end to end, with measured (not
modelled) prefill/TTFT numbers next to the analytical Table-6
estimates.

Prompts are left-truncated to the last `max_prompt` tokens and left-PADDED
up to the next `pad_multiple` bucket: a handful of prefill shapes get
compiled (not one per ragged prompt length, which on CPU would dominate
every measurement this module exists to make), while measured prefill
time still scales with prompt size — the paper's SCR claim is precisely
that shorter prompts cut TTFT, so a condensed MobileRAG prompt must land
in a smaller bucket than the full-document Naive-RAG prompt.
"""
from __future__ import annotations

import time
from dataclasses import dataclass
from typing import TYPE_CHECKING, List, Optional

import numpy as np

from repro.data.tokenizer import HashTokenizer

if TYPE_CHECKING:
    from repro.config import ModelConfig


@dataclass
class SLMGeneration:
    tokens: List[int]               # generated token ids (pre-EOS)
    text: str                       # detokenised generation
    prompt_tokens: int              # true (pre-pad) prompt length
    ttft_s: float                   # measured prefill + first-token time
    decode_s: float = 0.0


class SLM:
    """Lazy Engine wrapper over the generator config `cfg`: the model
    stack is imported and initialised on first use, so merely
    constructing pipelines (or importing rag.py) stays free of the jax
    model chain."""

    def __init__(self, cfg: "ModelConfig", *, max_prompt: int = 256,
                 max_new: int = 24, pad_multiple: int = 32, seed: int = 0,
                 page_size: int = 32):
        self.cfg = cfg
        self.max_prompt = max_prompt
        self.max_new = max_new
        self.pad_multiple = pad_multiple
        self.seed = seed
        self.page_size = page_size
        self._engine = None
        self._tok: Optional[HashTokenizer] = None

    def _ensure(self):
        if self._engine is None:
            import jax
            from repro.models import model
            from repro.serving.engine import Engine
            cfg = self.cfg
            params = model.init_params(cfg, jax.random.PRNGKey(self.seed))
            self._engine = Engine(cfg, params,
                                  max_len=self.max_prompt + self.max_new,
                                  page_size=self.page_size)
            self._tok = HashTokenizer(cfg.vocab_size)
        return self._engine, self._tok

    def encode_prompt(self, prompt: str, *, bucket: bool = True) -> np.ndarray:
        """Bucketed ids: left-truncate to max_prompt, left-pad to the
        next pad_multiple so prompt length maps to few prefill shapes.
        `bucket=False` skips the padding: the continuous engine prefills
        in fixed-size chunks, so ragged lengths cost no extra compiles and
        a shorter (SCR-condensed) prompt pays for exactly its own
        tokens."""
        _, tok = self._ensure()
        ids = tok.encode(prompt)[-self.max_prompt:]
        if not bucket:
            return np.asarray(ids or [tok.pad_id], np.int32)
        m = self.pad_multiple
        bucket_len = min(self.max_prompt, -(-max(len(ids), 1) // m) * m)
        pad = bucket_len - len(ids)
        return np.asarray([tok.pad_id] * pad + ids, np.int32)

    def continuous(self, slots: int = 4):
        """The shared slot-paged ContinuousEngine over this sLM's params
        (the RagSession decode backend)."""
        eng, _ = self._ensure()
        return eng.continuous(slots)

    @property
    def tokenizer(self) -> HashTokenizer:
        return self._ensure()[1]

    def warmup(self) -> None:
        """Compile the prefill/decode executables off the measured path."""
        self.generate(["warmup"], max_new=1)

    def generate(self, prompts: List[str], max_new: Optional[int] = None,
                 *, warm_first: bool = True) -> List[SLMGeneration]:
        eng, tok = self._ensure()
        if max_new is None:
            max_new = self.max_new
        if not 1 <= max_new <= self.max_new:
            raise ValueError(
                f"max_new={max_new} outside [1, {self.max_new}]: the "
                "Engine KV budget is sized at construction — build "
                "SLM(max_new=...) larger instead")
        arrs = [self.encode_prompt(p) for p in prompts]
        if warm_first:
            # one throwaway pass over the same wave shapes so ttft_s
            # reports execution, not XLA compilation of a cold bucket
            eng.generate(arrs, max_new=1)
        res = eng.generate(arrs, max_new=max_new)
        out = []
        for p, r in zip(prompts, res):
            gen = [t for t in r.tokens if t != tok.eos_id]
            out.append(SLMGeneration(
                tokens=list(r.tokens),
                text=tok.decode(gen),
                prompt_tokens=min(len(tok.encode(p)), self.max_prompt),
                ttft_s=r.prefill_s,
                decode_s=r.decode_s))
        return out

    def measure_ttft(self, prompt: str, *, warm: bool = True) -> float:
        """Measured prefill + first-token wall time for one prompt (the
        real-generation counterpart of the Table-6 prompt_tps estimate).
        `warm` runs the same shape once unmeasured first, so a prompt
        landing in a not-yet-compiled bucket doesn't report jit time."""
        eng, _ = self._ensure()
        arr = self.encode_prompt(prompt)
        if warm:
            eng.generate_wave([arr], max_new=1)
        t0 = time.perf_counter()
        res = eng.generate_wave([arr], max_new=1)
        del res
        return time.perf_counter() - t0
