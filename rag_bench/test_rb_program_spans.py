"""The per-layer metrics read from the program's own spans
(`metrics/retrieve_wait_ms.py`, `slot_wait_ms.py`,
`prefill_ms_per_request.py`, `host_ms_per_step.py`,
`kv_page_use_share.py`), on two session steps recorded by a `TraceSink`
on a scripted clock, with the answers worked out by hand: once with the
profiler started and stopped outside every interval they measure, and
once with it started at 15 ms and stopped at 55 ms, inside some of
them, which those leave out (starting and stopping the profiler stalls
the serving loop)."""
import pytest

from rag_bench import observe
from rag_bench.run import reader
from repro.serving.trace import TraceSink

NAMES = ("retrieve_wait_ms", "slot_wait_ms", "prefill_ms_per_request",
         "host_ms_per_step", "kv_page_use_share")


def _two_steps(new_spans: bool = True) -> list:
    """Session requests 0 and 1 queued at 1 ms and retrieved one a step;
    engine request 0 admitted in step 1 and given its first token in
    step 2, engine request 1 admitted in step 2. A request queued before
    the window (session 9, engine 9, at 0 ms) is left out of every mean.
    `new_spans=False` records what a program without the step, readback
    and page spans records."""
    ms = [0.0]
    sink = TraceSink(clock=lambda: ms[0] / 1e3)

    def at(t):
        ms[0] = t

    def span(comp, name, t0, t1, rid=-1, src="e0", **attrs):
        at(t0)
        sink.emit(comp, name, rid, src=src, ph="B", **attrs)
        at(t1)
        sink.emit(comp, name, rid, src=src, ph="E")

    def step(t0, t1, body):
        if not new_spans:
            return body()
        at(t0)
        with sink.span("session", "step", src="s0", queued=1, decoding=1):
            body()
            at(t1)

    def readback(rid, name, t0, t1):
        if new_spans:
            span("engine", name, t0, t1, rid)

    def decode(t0, t1, rb0, rb1, reserved, live):
        pages = (dict(pages_reserved=reserved, pages_live=live)
                 if new_spans else {})
        at(t0)
        sink.emit("engine", "decode_step", src="e0", ph="B", active=1,
                  **pages)
        readback(-1, "decode_readback", rb0, rb1)
        at(t1)
        sink.emit("engine", "decode_step", src="e0", ph="E")

    def retrieve(t0, t1, rid):
        rids = {"rids": [rid]} if new_spans else {}
        span("session", "retrieve", t0, t1, src="s0", n=1, **rids)

    sink.emit("session", "queued", 9, src="s0")
    sink.emit("engine", "queued", 9, src="e0", prompt_len=100)
    at(1.0)
    sink.emit("session", "queued", 0, src="s0")
    sink.emit("session", "queued", 1, src="s0")

    def step1():
        retrieve(12.0, 17.0, 0)
        at(18.0)
        sink.emit("engine", "queued", 0, src="e0", prompt_len=100)
        at(21.0)
        sink.emit("engine", "admitted", 0, src="e0")
        sink.emit("engine", "admitted", 9, src="e0")
        span("engine", "prefill_chunk", 23.0, 24.0, 0, start=0, n=32)
        decode(25.0, 31.0, 26.0, 30.0, reserved=8, live=5)

    def step2():
        retrieve(41.0, 45.0, 1)
        at(46.0)
        sink.emit("engine", "queued", 1, src="e0", prompt_len=100)
        at(50.0)
        sink.emit("engine", "admitted", 1, src="e0")
        span("engine", "prefill_chunk", 51.0, 52.0, 0, start=96, n=4)
        readback(0, "prefill_readback", 53.0, 57.0)
        at(58.0)
        sink.emit("engine", "first_token", 0, src="e0")
        decode(60.0, 67.0, 61.0, 66.0, reserved=12, live=9)

    step(10.0, 32.0, step1)
    step(40.0, 70.0, step2)
    return sink.records()


def _observed(records, traced=(0.2, 0.3)) -> observe.Observed:
    """The window holds every record but the first two; the profiler
    ran over `traced` (seconds)."""
    return observe.Observed(records, (0.5e-3, 1.0), traced, None, {}, {},
                            {})


@pytest.mark.parametrize("name, want, want_profiled", [
    # retrieve begin - queued: 11 and 40; the second spans 15 ms
    ("retrieve_wait_ms", (11.0 + 40.0) / 2, 11.0),
    ("slot_wait_ms", (3.0 + 4.0) / 2, (3.0 + 4.0) / 2),  # admitted - queued
    # first token - admitted: 21 to 58 ms, across 55 ms
    ("prefill_ms_per_request", 37.0, None),
    # step less its readbacks: 22 - 4 (10 to 32 ms, across 15 ms), then
    # 30 - 4 - 5 (40 to 70 ms, across 55 ms)
    ("host_ms_per_step", (18.0 + 21.0) / 2, None),
    ("kv_page_use_share", (5 + 9) / (8 + 12) * 100.0,
     (5 + 9) / (8 + 12) * 100.0),
])
def test_reader_on_recorded_steps(name, want, want_profiled):
    recs = _two_steps()
    assert reader(name)(_observed(recs)) == pytest.approx(want)
    got = reader(name)(_observed(recs, traced=(15e-3, 55e-3)))
    if want_profiled is None:
        assert got is None
    else:
        assert got == pytest.approx(want_profiled)


@pytest.mark.parametrize("name", NAMES)
def test_reader_reads_nothing_from_an_older_program(name):
    """What a program without this tracing records gives the three
    metrics that need its spans nothing to read, and the two built on
    the engine's lifecycle records the same value; none raises."""
    got = reader(name)(_observed(_two_steps(new_spans=False)))
    if name in ("slot_wait_ms", "prefill_ms_per_request"):
        assert got == reader(name)(_observed(_two_steps()))
    else:
        assert got is None
    assert reader(name)(_observed([])) is None
