"""The traffic generator's determinism per seed, and the client-side
accounting from due times."""
import numpy as np
import pytest

from rag_bench import harness, traffic

MIX = {"loop": "open", "arrivals": "poisson",
       "answer_tokens": {"dist": "lognormal", "median": 16, "sigma": 0.6,
                         "min": 4, "max": 64}}
QUESTIONS = [f"q{i}" for i in range(50)]
BIG_SEED = 2 ** 31 + 12345


def test_same_seed_same_schedule():
    a = traffic.open_loop(MIX, 20.0, 10.0, QUESTIONS, BIG_SEED)
    b = traffic.open_loop(MIX, 20.0, 10.0, QUESTIONS, BIG_SEED)
    assert a == b
    assert len(a) == 200


def test_other_seed_same_work_in_another_order():
    a = traffic.open_loop(MIX, 20.0, 10.0, QUESTIONS, 1)
    b = traffic.open_loop(MIX, 20.0, 10.0, QUESTIONS, 2)
    assert sorted(r.max_new for r in a) == sorted(r.max_new for r in b)
    gaps = [np.diff([r.due_s for r in s] + [10.0]) for s in (a, b)]
    assert np.allclose(np.sort(gaps[0]), np.sort(gaps[1]))
    assert [r.max_new for r in a] != [r.max_new for r in b]
    assert [r.question for r in a] != [r.question for r in b]


def test_arrivals_span_the_window_at_the_rate():
    s = traffic.open_loop(MIX, 12.0, 20.0, QUESTIONS, 5)
    due = [r.due_s for r in s]
    assert due[0] == 0.0 and due == sorted(due) and due[-1] < 20.0
    assert len(s) == 240


def test_answer_lengths_follow_the_mix():
    lens = traffic.answer_lengths(MIX["answer_tokens"], 1001)
    assert lens.min() >= 4 and lens.max() <= 64
    assert np.median(lens) == 16


def test_closed_loop_pool_is_deterministic():
    mix = dict(MIX, loop="closed", clients=4, pool=32)
    assert (traffic.closed_loop_pool(mix, QUESTIONS, 9)
            == traffic.closed_loop_pool(mix, QUESTIONS, 9))


@pytest.mark.parametrize("seed", [9, BIG_SEED])
def test_closed_loop_blocks_hold_the_same_lengths(seed):
    mix = dict(MIX, loop="closed", clients=4, pool=96, block=32)
    pool = traffic.closed_loop_pool(mix, QUESTIONS, seed)
    blocks = [[r.max_new for r in pool[i:i + 32]] for i in (0, 32, 64)]
    want = sorted(traffic.answer_lengths(MIX["answer_tokens"], 32))
    assert all(sorted(b) == want for b in blocks)
    assert blocks[0] != blocks[1]


def _log():
    """A 10 s window starting at t=100 with four requests due in it and one
    due after it."""
    R = harness.ReqLog
    reqs = {
        0: R("a", 4, due=100.0, submit=100.01, tokens=[100.2, 100.3, 100.5],
             end=100.5, state="done"),
        1: R("b", 4, due=101.0, submit=101.5, tokens=[101.9, 102.0],
             end=102.0, state="done"),
        # due late in the window, first token only after it closes
        2: R("c", 4, due=109.0, submit=109.2, tokens=[110.4], end=110.4,
             state="done"),
        3: R("d", 4, due=105.0, submit=105.0, tokens=[], end=105.1,
             state="shed"),
        4: R("e", 4, due=111.0, submit=111.0),
    }
    return harness.WindowLog(start=100.0, end=110.0, reqs=reqs)


def test_ttft_from_due_time_and_age_at_close():
    m = harness.end_to_end(_log())
    # TTFTs 0.2, 0.9, 1.0 (age at close) and 5.0 (no token by the close)
    assert m["ttft_p50_ms"] == pytest.approx(950.0)
    assert m["ttft_p95_ms"] == pytest.approx(
        np.percentile([0.2, 0.9, 1.0, 5.0], 95) * 1e3)
    # gaps 0.1, 0.2, 0.1; the token after the close does not count
    assert m["itl_p95_ms"] == pytest.approx(
        np.percentile([0.1, 0.2, 0.1], 95) * 1e3)
    assert m["tokens_per_s"] == pytest.approx(5 / 10.0)


def test_outcome_counts_requests_due_in_the_window():
    out = harness.outcome(_log())
    assert out["attempted"] == 4
    assert out["failed"] == 1          # the shed one
    assert out["generator_late_max_ms"] == pytest.approx(500.0)
