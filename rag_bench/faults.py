"""Faults planted under the timed path, for the tests that show a broken
program comes out not correct. Each is a context manager that patches
the program in this process only and restores it on exit; enter it
before the stack is built, so the engine's programs trace the fault.
Nothing here runs in a benchmark run."""
from __future__ import annotations

import contextlib

import numpy as np


@contextlib.contextmanager
def _patched(obj, name, new):
    old = getattr(obj, name)
    setattr(obj, name, new)
    try:
        yield
    finally:
        setattr(obj, name, old)


def state_unchanged():
    """A decode step that returns its KV state unchanged: the new
    position's K and V are never stored."""
    from repro.models import model
    orig = model.decode_step_paged

    def step(cfg, params, cache, *a, **k):
        logits, _ = orig(cfg, params, cache, *a, **k)
        return logits, cache
    return _patched(model, "decode_step_paged", step)


def half_batch():
    """Half of the decode batch left out: rows in the second half get the
    mean of the first half's logits in place of their own."""
    from repro.models import model
    orig = model.decode_step_paged

    def step(cfg, params, cache, *a, **k):
        logits, cache = orig(cfg, params, cache, *a, **k)
        h = logits.shape[0] // 2
        mean = logits[:h].mean(axis=0, keepdims=True)
        return logits.at[h:].set(mean.repeat(logits.shape[0] - h, 0)), cache
    return _patched(model, "decode_step_paged", step)


def token_altered():
    """Each token altered where the engine produces it (the next id)."""
    from repro.serving.engine import ContinuousEngine
    orig = ContinuousEngine._emit_token

    def emit(self, req, tok, events):
        return orig(self, req, (tok + 1) % self.cfg.vocab_size, events)
    return _patched(ContinuousEngine, "_emit_token", emit)


def retrieval_altered():
    """Each query's last retrieved document replaced by the next id."""
    from repro.core.ecovector import EcoVector
    orig = EcoVector.search_device_batched

    def search(self, q, *a, **k):
        ids, dists = orig(self, q, *a, **k)
        ids = ids.copy()
        n = len(self.assign)
        ids[:, -1] = np.where(ids[:, -1] >= 0, (ids[:, -1] + 1) % n, -1)
        return ids, dists
    return _patched(EcoVector, "search_device_batched", search)


def window_altered():
    """Each document's chosen SCR window moved to its neighbour."""
    from repro.kernels import ops
    orig = ops.scr_select

    def select(q, data, lens, doc_ids, **k):
        scores, wins = orig(q, data, lens, doc_ids, **k)
        wins = np.asarray(wins)
        return scores, np.where(wins >= 1, wins - 1, wins + 1)
    return _patched(ops, "scr_select", select)


FAULTS = {"state_unchanged": state_unchanged, "half_batch": half_batch,
          "token_altered": token_altered,
          "retrieval_altered": retrieval_altered,
          "window_altered": window_altered}
