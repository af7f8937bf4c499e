"""Universal paged decode: the continuous engine across the model zoo.

Covers the PR-5 acceptance contract (DESIGN.md §10):
  - paged-vs-wave greedy BIT-parity for sliding-window (ring pages),
    int8-KV (per-slot scales) and MoE configs — plus the swa+int8 combo;
  - ring-page wraparound where kv_len exceeds the window on SOME slots;
  - per-slot sampling: same (seed, request_id) => same tokens under
    1, 2 and 4 co-residents (fold_in PRNG streams);
  - `supports_paged` coverage and default routing of sampled requests
    through the ContinuousEngine.
"""
import dataclasses

import numpy as np
import pytest
import jax

from repro.configs import get_reduced
from repro.models import model
from repro.serving.engine import ContinuousEngine, Engine


def _cfg(kind: str):
    if kind == "swa":
        return get_reduced("h2o_danube_1_8b")
    if kind == "int8":
        return dataclasses.replace(get_reduced("qwen25_0_5b"),
                                   kv_quant=True)
    if kind == "moe":
        return get_reduced("granite_moe_1b_a400m")
    if kind == "swa_int8":
        return dataclasses.replace(get_reduced("h2o_danube_1_8b"),
                                   kv_quant=True)
    raise KeyError(kind)


def _prompts(seed=7, lens=(16, 24, 33, 40, 9)):
    rng = np.random.default_rng(seed)
    return [rng.integers(4, 500, n).astype(np.int32) for n in lens]


def test_supports_paged_covers_the_zoo():
    """swa / int8-KV / moe (and combos) are paged-capable; M-RoPE,
    encoders and recurrent-state families stay on the wave path."""
    for kind in ("swa", "int8", "moe", "swa_int8"):
        assert model.supports_paged(_cfg(kind)), kind
    assert model.supports_paged(get_reduced("qwen25_0_5b"))
    for arch in ("qwen2_vl_2b", "gte_small", "mamba2_780m",
                 "recurrentgemma_9b", "whisper_small"):
        assert not model.supports_paged(get_reduced(arch)), arch
    # moe+swa / moe+int8: the paged helpers would cover them, but the
    # wave baseline (continuous=False) implements neither — excluded so
    # the escape hatch can't silently diverge (DESIGN.md §10)
    moe = get_reduced("granite_moe_1b_a400m")
    assert not model.supports_paged(
        dataclasses.replace(moe, kv_quant=True))
    assert not model.supports_paged(
        dataclasses.replace(moe, sliding_window=64))


@pytest.mark.parametrize("kind", ["swa", "int8", "moe", "swa_int8"])
def test_paged_matches_wave_greedy(kind):
    """Acceptance: slot-paged continuous decode produces token-identical
    greedy output to the legacy wave path for every newly-covered family
    (mixed-length requests over fewer slots, so admission churn and
    chunked prefill are both exercised)."""
    cfg = _cfg(kind)
    params = model.init_params(cfg, jax.random.PRNGKey(0))
    eng = Engine(cfg, params, max_len=96, slots=2)
    prompts = _prompts()
    wave = eng.generate(prompts, max_new=6, continuous=False)
    cont = eng.generate(prompts, max_new=6, continuous=True)
    for i, (w, c) in enumerate(zip(wave, cont)):
        assert w.tokens == c.tokens, f"{kind} request {i} diverged"
        assert c.prefill_s > 0


def test_ring_page_wraparound_mixed_slots():
    """kv_len exceeds the sliding window on one slot while its
    co-resident stays inside it: the long slot's ring wraps (cursor
    pos % window evicts in place) without corrupting either request —
    both stay bit-identical to the wave path."""
    cfg = _cfg("swa")
    w = cfg.sliding_window
    assert w == 64
    params = model.init_params(cfg, jax.random.PRNGKey(1))
    eng = Engine(cfg, params, max_len=96, slots=2)
    rng = np.random.default_rng(11)
    prompts = [rng.integers(4, 500, 80).astype(np.int32),   # wraps: 80 > 64
               rng.integers(4, 500, 20).astype(np.int32)]   # stays inside
    wave = eng.generate(prompts, max_new=8, continuous=False)
    cont = eng.generate(prompts, max_new=8, continuous=True)
    for i, (wv, c) in enumerate(zip(wave, cont)):
        assert wv.tokens == c.tokens, f"slot {i} diverged across wraparound"
    # the per-slot ring really is bounded by the window: each table row
    # maps just enough pages to cover `window` positions, not max_len
    ce = eng.continuous(2)
    assert ce.ring_len == w
    assert ce.table_width == -(-w // ce.page_size)
    assert ce.cache["k"].shape[1] == ce.slots * ce.table_width  # pool pages
    assert ce.cache["k"].shape[2] == ce.page_size


@pytest.fixture(scope="module")
def dense_engine():
    cfg = get_reduced("qwen25_0_5b")
    params = model.init_params(cfg, jax.random.PRNGKey(0))
    return Engine(cfg, params, max_len=96)


def _run_sampled(cfg, params, target, co, *, rid=100, seed=5, max_new=8):
    """Target request sampled under `co` co-residents; returns its
    tokens."""
    ce = ContinuousEngine(cfg, params, slots=4, max_len=96)
    tid = ce.submit(target, max_new=max_new, rid=rid, greedy=False,
                    seed=seed)
    for i, p in enumerate(co):
        ce.submit(p, max_new=max_new, rid=i, greedy=False, seed=seed)
    res = {}
    while ce.pending:
        for ev in ce.step():
            if ev.kind == "done":
                res[ev.rid] = ev.result.tokens
    return res[tid]


def test_sampling_reproducible_across_coresident_mixes(dense_engine):
    """Acceptance: same (seed, request_id) => bit-identical sampled
    tokens with 1, 2 and 4 co-residents. The per-request stream
    fold_in(PRNGKey(seed), rid), advanced by the request's own draw
    counter, never touches a shared key."""
    cfg, params = dense_engine.cfg, dense_engine.params
    rng = np.random.default_rng(3)
    target = rng.integers(4, 500, 20).astype(np.int32)
    others = [rng.integers(4, 500, n).astype(np.int32)
              for n in (12, 28, 17, 22)]
    runs = [_run_sampled(cfg, params, target, others[:n])
            for n in (0, 1, 2, 4)]
    assert all(r == runs[0] for r in runs[1:]), runs
    # a different seed (or rid) gives a different stream
    ce = ContinuousEngine(cfg, params, slots=4, max_len=96)
    tid = ce.submit(target, max_new=8, rid=100, greedy=False, seed=6)
    res = {}
    while ce.pending:
        for ev in ce.step():
            if ev.kind == "done":
                res[ev.rid] = ev.result.tokens
    assert res[tid] != runs[0]


def test_sampled_requests_route_through_continuous(dense_engine):
    """The `greedy and supports_paged` gate is gone: generate(greedy=
    False) runs on the ContinuousEngine by default and is reproducible
    run-to-run (per-request streams), unlike the legacy shared-key wave
    sampler which it no longer uses."""
    prompts = _prompts(seed=5, lens=(14, 14, 22))
    a = dense_engine.generate(prompts, max_new=6, greedy=False, seed=3)
    b = dense_engine.generate(prompts, max_new=6, greedy=False, seed=3)
    for x, y in zip(a, b):
        assert x.tokens == y.tokens
    # draws really are sampled, not greedy
    g = dense_engine.generate(prompts, max_new=6)
    assert any(x.tokens != y.tokens for x, y in zip(a, g))


def test_moe_decode_never_drops_tokens():
    """Serving MoE capacity contract: expert buffers are sized T*k at
    inference, so a junk co-resident row can never displace a real
    token's expert slot (the property the parity/reproducibility tests
    above rely on). Verified by running the same request against wildly
    different co-resident token content."""
    cfg = _cfg("moe")
    params = model.init_params(cfg, jax.random.PRNGKey(2))
    rng = np.random.default_rng(9)
    target = rng.integers(4, 500, 18).astype(np.int32)

    def run(co_seed):
        ce = ContinuousEngine(cfg, params, slots=4, max_len=96)
        tid = ce.submit(target, max_new=6, rid=50)
        r2 = np.random.default_rng(co_seed)
        for i in range(3):
            ce.submit(r2.integers(4, 500, 16 + 8 * i).astype(np.int32),
                      max_new=6, rid=i)
        res = {}
        while ce.pending:
            for ev in ce.step():
                if ev.kind == "done":
                    res[ev.rid] = ev.result.tokens
        return res[tid]

    assert run(1) == run(2) == run(3)


@pytest.mark.parametrize("kind", ["swa", "int8", "moe", "swa_int8"])
def test_tracing_zero_interference_families(kind):
    """Tracing must not perturb decode for any paged family: the same
    mixed-length batch produces bit-identical tokens with a TraceSink
    attached and with tracing disabled."""
    from repro.serving.trace import TraceSink
    cfg = _cfg(kind)
    params = model.init_params(cfg, jax.random.PRNGKey(0))
    prompts = _prompts(seed=31, lens=(16, 33, 9))

    def run(trace):
        ce = ContinuousEngine(cfg, params, slots=2, max_len=96,
                              trace=trace)
        return [r.tokens for r in ce.generate(prompts, max_new=6)]

    sink = TraceSink()
    assert run(sink) == run(None)
    assert len(sink.query(comp="engine", name="done")) == len(prompts)
    assert len(sink.query(comp="engine", name="first_token")) \
        == len(prompts)


def _direct_greedy(cfg, params, prompt, max_new, *, slots, max_len,
                   chunk=32, ps=32, oversize_pages=2, eos=2):
    """Greedy tokens of one request driven straight through
    `model.prefill_chunk_paged` / `model.decode_step_paged` on `params`
    (float32 masters are cast inside every call), in slot 0 of `slots`,
    with the chunking and page-table width a `ContinuousEngine` of the
    same settings uses. Returns (tokens, the logits rows drawn from)."""
    W = -(-max_len // ps) + oversize_pages
    abs_len = -(-max_len // chunk) * chunk
    cache = model.init_page_pool(cfg, slots * W, ps,
                                 dtype=model.compute_dtype(cfg))
    table = np.zeros((slots, W), np.int32)
    table[0] = np.arange(W)
    prefill = jax.jit(lambda p, c, t, row, off, lim: model.prefill_chunk_paged(
        cfg, p, c, t, row, off, lim, page_size=ps, abs_len=abs_len))
    decode = jax.jit(lambda p, c, t, pos, act, tbl: model.decode_step_paged(
        cfg, p, c, t, pos, act, tbl, page_size=ps))
    for off in range(0, len(prompt), chunk):
        real = len(prompt[off:off + chunk])
        toks = np.zeros((1, chunk), np.int32)
        toks[0, :real] = prompt[off:off + chunk]
        logits, cache = prefill(params, cache, toks, table[0],
                                np.int32(off), np.int32(off + real))
    rows = [np.asarray(logits, np.float32)[0, real - 1]]
    out = [int(np.argmax(rows[-1]))]
    pos = np.zeros(slots, np.int32)
    pos[0] = len(prompt)
    active = np.zeros(slots, bool)
    active[0] = True
    last = np.zeros((slots, 1), np.int32)
    while len(out) < max_new and out[-1] != eos:
        last[0, 0] = out[-1]
        logits, cache = decode(params, cache, last, pos, active, table)
        rows.append(np.asarray(logits, np.float32)[0])
        out.append(int(np.argmax(rows[-1])))
        pos[0] += 1
    return out, np.stack(rows)


@pytest.mark.parametrize("arch", ["qwen25_0_5b", "granite_moe_1b_a400m"])
def test_engine_holds_compute_dtype_weights(arch):
    """Serving casts the float32 masters once, where the engine takes
    them: every leaf the engine holds is bfloat16 but the `F32_KEEP`
    leaves and the norm scales, the continuous engines share those
    arrays, neither jitted program takes any other float32 argument, and
    no reference to a float32 matrix survives construction."""
    import gc
    import weakref

    import jax.numpy as jnp
    from repro.models.common import F32_KEEP, leaf_name

    def kept(path):
        name = leaf_name(path)
        return name in F32_KEEP or name.endswith("norm")

    cfg = get_reduced(arch)
    assert model.compute_dtype(cfg) == jnp.bfloat16
    masters = model.init_params(cfg, jax.random.PRNGKey(0))
    assert all(x.dtype == jnp.float32 for x in jax.tree.leaves(masters))
    eng = Engine(cfg, masters, max_len=64, slots=2)
    ce = eng.continuous()
    held = jax.tree_util.tree_leaves_with_path(ce.params)
    assert any(kept(p) for p, _ in held)
    for path, x in held:
        assert x.dtype == (jnp.float32 if kept(path) else jnp.bfloat16), path
    for a, b in zip(jax.tree.leaves(eng.params), jax.tree.leaves(ce.params)):
        assert a is b

    s, w = ce.slots, ce.table_width
    decode = ce._decode.lower(
        ce.params, ce.cache, np.zeros((s, 1), np.int32),
        np.zeros(s, np.int32), np.zeros(s, bool), np.zeros((s, w), np.int32))
    chunk = ce._chunk.lower(
        ce.params, ce.cache, np.zeros((1, ce.prefill_chunk), np.int32),
        np.zeros(w, np.int32), np.int32(0), np.int32(1))
    for low in (decode, chunk):
        f32 = [p for p, a in jax.tree_util.tree_leaves_with_path(
            low.args_info) if a.dtype == jnp.float32]
        assert f32 and all(kept(p) for p in f32), f32

    refs = [weakref.ref(x) for p, x in
            jax.tree_util.tree_leaves_with_path(masters) if not kept(p)]
    del masters
    gc.collect()
    assert all(r() is None for r in refs)


@pytest.mark.parametrize("arch", ["qwen25_0_5b", "granite_moe_1b_a400m"])
def test_served_tokens_match_per_call_cast(arch):
    """An engine built from float32 masters serves, bit for bit, the
    greedy tokens of a loop that hands the same masters to
    `model.prefill_chunk_paged` / `model.decode_step_paged`, which cast
    them in every call: casting once changes no value the matmuls see,
    so the same loop on the engine's weights gives the same logits bit
    for bit."""
    cfg = get_reduced(arch)
    masters = model.init_params(cfg, jax.random.PRNGKey(4))
    prompts = _prompts(seed=13, lens=(40, 9, 33))
    ce = ContinuousEngine(cfg, masters, slots=2, max_len=96)
    served = [r.tokens for r in ce.generate(prompts, max_new=7)]
    for p, want in zip(prompts, served):
        toks, rows = _direct_greedy(cfg, masters, p, 7, slots=2, max_len=96)
        assert toks == want
        _, held = _direct_greedy(cfg, ce.params, p, 7, slots=2, max_len=96)
        np.testing.assert_array_equal(held, rows)


@pytest.mark.parametrize("n_rows, n_real, routed, computed", [
    (16, 15, 15 * 8, 32 * 16),     # a decode step: 16 slots, 15 active
    (32, 29, 29 * 8, 32 * 32),     # a prefill chunk's ragged tail
])
def test_serving_rows_at_published_widths(n_rows, n_real, routed, computed):
    """Granite-3.0-1B-A400M (32 experts, top-8): every expert computes
    the call's whole buffer, the real rows are routed to top_k each,
    and the serving capacity is the buffer (no drop)."""
    from repro.configs import get_config
    from repro.models import moe
    cfg = get_config("granite_moe_1b_a400m")
    assert moe.serving_rows(cfg, n_rows, n_real) == (n_rows, routed, computed)
    assert moe.serving_rows(cfg, n_rows).routed == n_rows * 8


def _engine_spans(cfg, slots=2):
    from repro.serving.trace import TraceSink
    sink = TraceSink()
    ce = ContinuousEngine(cfg, model.init_params(cfg, jax.random.PRNGKey(1)),
                          slots=slots, max_len=96, trace=sink)
    ce.generate(_prompts(seed=31, lens=(16, 33, 9)), max_new=6)
    spans = {n: [r for r in sink.query(comp="engine", name=n) if r.ph == "B"]
             for n in ("decode_step", "prefill_chunk")}
    assert all(spans.values()), spans
    return ce, spans


def test_moe_spans_count_routed_and_computed_rows():
    """A MoE engine's decode steps carry active slots x top_k x layers
    expert-rows routed and experts x slots x layers computed; its
    prefill chunks real tokens x top_k x layers and experts x chunk
    size x layers."""
    cfg = _cfg("moe")
    L, E, k = cfg.num_layers, cfg.moe.num_experts, cfg.moe.top_k
    ce, spans = _engine_spans(cfg)
    for r in spans["decode_step"]:
        assert r.attrs["moe_rows_routed"] == r.attrs["active"] * k * L
        assert r.attrs["moe_rows_computed"] == E * ce.slots * L
    assert {r.attrs["n"] for r in spans["prefill_chunk"]} > {32}
    for r in spans["prefill_chunk"]:
        assert r.attrs["moe_rows_routed"] == r.attrs["n"] * k * L
        assert r.attrs["moe_rows_computed"] == E * ce.prefill_chunk * L


def test_dense_spans_carry_no_moe_rows():
    _, spans = _engine_spans(get_reduced("qwen25_0_5b"))
    for recs in spans.values():
        for r in recs:
            assert not {"moe_rows_routed", "moe_rows_computed"} & set(r.attrs)


def test_moe_ffn_scope_names_ops_and_changes_no_arithmetic(monkeypatch):
    """The `moe_ffn` named scope shows in the paged programs' op
    metadata, and the programs lowered without it are the same text."""
    import contextlib
    import re
    cfg = _cfg("moe")
    ce = ContinuousEngine(cfg, model.init_params(cfg, jax.random.PRNGKey(0)),
                          slots=2, max_len=64)
    s, w = ce.slots, ce.table_width

    def lowered():
        decode = ce._decode.lower(
            ce.params, ce.cache, np.zeros((s, 1), np.int32),
            np.zeros(s, np.int32), np.zeros(s, bool),
            np.zeros((s, w), np.int32))
        chunk = ce._chunk.lower(
            ce.params, ce.cache, np.zeros((1, ce.prefill_chunk), np.int32),
            np.zeros(w, np.int32), np.int32(0), np.int32(1))
        return decode, chunk

    # in op names ("moe_ffn/dot_general"); a Python frame's has no "/"
    scope = re.compile(r'["/]moe_ffn/')
    named = lowered()
    for low in named:
        assert scope.search(low.as_text(debug_info=True))
    jax.clear_caches()
    monkeypatch.setattr(jax, "named_scope",
                        lambda name: contextlib.nullcontext())
    for low, plain in zip(named, lowered()):
        assert not scope.search(plain.as_text(debug_info=True))
        assert low.as_text() == plain.as_text()
