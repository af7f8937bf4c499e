"""`metrics/moe_routed_row_share.py` on engine spans recorded by a
`TraceSink` on a scripted clock, with the answer worked out by hand;
and on the same spans without the MoE attrs (a dense engine, or a
program that does not record them), where it reads nothing."""
import pytest

from rag_bench import observe
from rag_bench.run import reader
from repro.serving.trace import TraceSink


def _spans(moe: bool = True) -> list:
    """A prefill chunk at 5 ms (29 real of 32 rows) and decode steps at
    10 and 20 ms (15 and 16 of 16 slots active), for 2 layers of 4
    experts, top-2; a decode step at 0 ms lies before the window and a
    second source's chunk at 30 ms inside it."""
    ms = [0.0]
    sink = TraceSink(clock=lambda: ms[0] / 1e3)

    def span(name, t, src="e0", **attrs):
        if moe:
            rows = attrs.pop("rows")
            attrs.update(moe_rows_routed=attrs.pop("real") * 2 * 2,
                         moe_rows_computed=4 * rows * 2)
        else:
            attrs.pop("rows")
            attrs.pop("real")
        ms[0] = t
        sink.emit("engine", name, src=src, ph="B", **attrs)
        ms[0] = t + 1.0
        sink.emit("engine", name, src=src, ph="E")

    span("decode_step", 0.0, rows=16, real=16, active=16)
    span("prefill_chunk", 5.0, rows=32, real=29, n=29)
    span("decode_step", 10.0, rows=16, real=15, active=15)
    span("decode_step", 20.0, rows=16, real=16, active=16)
    span("prefill_chunk", 30.0, src="e1", rows=32, real=3, n=3)
    return sink.records()


def _observed(records):
    return observe.Observed(records, (2e-3, 1.0), (0.2, 0.3), None, {}, {},
                            {})


def test_share_on_recorded_spans():
    routed = (29 + 15 + 16 + 3) * 2 * 2
    computed = (32 + 16 + 16 + 32) * 4 * 2
    got = reader("moe_routed_row_share")(_observed(_spans()))
    assert got == pytest.approx(routed / computed * 100.0)


@pytest.mark.parametrize("records", [_spans(moe=False), []])
def test_reads_nothing_without_moe_attrs(records):
    assert reader("moe_routed_row_share")(_observed(records)) is None
