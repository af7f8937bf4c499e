"""trace_reduce.py on a small recorded trace laid out as a TPU's: busy,
idle and per-program time, executions, and the naming of programs by
role."""
import pytest

from rag_bench import trace_reduce

US = 1_000_000       # picoseconds in a microsecond

# One TPU running three programs: two engine lambdas (fingerprints 5 and
# 7, one module name) and the fused route-and-scan; a host thread with
# the harness's annotations. Times in microseconds from the line's start.
MODULES = [("jit__lambda(5)", 0, 16), ("jit__lambda(5)", 40, 11),
           ("jit__lambda(7)", 20, 9), ("jit_route_and_scan(9)", 60, 5)]
OPS = [("%while.3 = (s32[]) while(%t)", 0, 15),
       ("%fusion.1 = bf16[16,896] fusion(%p)", 0, 10),
       ("%fusion.2 = bf16[16,896] fusion(%q)", 10, 5),
       ("%fusion.1 = bf16[16,896] fusion(%p)", 40, 10),
       ("%fusion.9 = f32[1,32] fusion(%r)", 20, 8),
       ("%custom-call.3 = f32[4,3] custom-call(%s)", 60, 4)]
ASYNC = [("%copy-start = s32[18] copy-start(%row)", 0, 64)]
HOST = [("session step", 0, 30), ("load generator", 30, 8),
        ("session step", 38, 30)]


def _line(lid, name, events, mid):
    evs = " ".join(f"events {{ metadata_id: {mid[n]} offset_ps: {s * US} "
                   f"duration_ps: {d * US} }}" for n, s, d in events)
    return f'lines {{ id: {lid} name: "{name}" timestamp_ns: 0 {evs} }}'


def _xspace() -> str:
    names = sorted({e[0] for e in MODULES + OPS + ASYNC + HOST})
    mid = {n: i + 1 for i, n in enumerate(names)}
    meta = " ".join(f'event_metadata {{ key: {i} value {{ id: {i} '
                    f'name: "{n}" }} }}' for n, i in mid.items())
    return (f'planes {{ id: 1 name: "/device:TPU:0" '
            f'{_line(1, "XLA Modules", MODULES, mid)} '
            f'{_line(2, "XLA Ops", OPS, mid)} '
            f'{_line(3, "Async XLA Ops", ASYNC, mid)} {meta} }} '
            f'planes {{ id: 2 name: "/host:CPU" '
            f'{_line(1, "python3", HOST, mid)} {meta} }}')


@pytest.fixture(scope="module")
def reduced():
    from jax.profiler import ProfileData
    return trace_reduce.reduce_profile(ProfileData.from_text_proto(_xspace()))


def test_busy_is_the_union_of_op_intervals(reduced):
    # ops: [0, 15] (two back to back), [20, 28], [40, 50], [60, 64]; the
    # async copy spanning everything is not work on the device
    assert reduced.chips == 1
    assert reduced.window_s == pytest.approx(64e-6)
    assert reduced.busy_s == pytest.approx((15 + 8 + 10 + 4) * 1e-6)


def test_per_program_time_runs_and_ops(reduced):
    progs = {(p.module, p.program_id): p for p in reduced.programs}
    assert set(progs) == {("jit__lambda", "5"), ("jit__lambda", "7"),
                          ("jit_route_and_scan", "9")}
    assert progs[("jit__lambda", "5")].time_s == pytest.approx(27e-6)
    assert progs[("jit__lambda", "5")].runs == 2
    assert progs[("jit__lambda", "7")].runs == 1
    # the loop op holds the two fusions, so its own time is 0
    assert progs[("jit__lambda", "5")].ops == pytest.approx(
        {"while.3": 0.0, "fusion.1": 20e-6, "fusion.2": 5e-6})
    assert progs[("jit_route_and_scan", "9")].ops == pytest.approx(
        {"custom-call.3": 4e-6})


def test_idle_time_is_split_by_host_activity(reduced):
    # idle: 15..20, 28..40 and 50..60. Host: session step 0..30 and
    # 38..68, load generator 30..38.
    idle = reduced.idle_by_host
    assert idle["session step"] == pytest.approx((5 + 2 + 2 + 10) * 1e-6)
    assert idle["load generator"] == pytest.approx(8e-6)
    assert idle.get("host, outside any annotation", 0.0) == pytest.approx(0)
    assert sum(idle.values()) == pytest.approx(
        reduced.window_s - reduced.busy_s)


def test_roles_by_module_name_and_execution_count(reduced):
    roles = trace_reduce.assign_roles(reduced, {
        "decode": ("jit__lambda", 2), "prefill_chunk": ("jit__lambda", 1),
        "route_and_scan": ("jit_route_and_scan", 1)})
    assert roles == {("jit__lambda", "5"): "decode",
                     ("jit__lambda", "7"): "prefill_chunk",
                     ("jit_route_and_scan", "9"): "route_and_scan"}
    assert trace_reduce.role_time(reduced, roles, "decode") == \
        pytest.approx(27e-6)
    assert trace_reduce.role_runs(reduced, roles, "prefill_chunk") == 1
    assert trace_reduce.role_time(reduced, roles, "scr_select") is None
    top = trace_reduce.top_ops(reduced, roles)
    assert top[0] == ["decode/fusion.1", pytest.approx(20e-6)]


def _programs(*runs):
    return trace_reduce.Reduced(1.0, 0.5, 1, [
        trace_reduce.Program(mod, pid, 0.1, n) for mod, pid, n in runs], {})


@pytest.mark.parametrize("programs, expected, roles", [
    # no program's executions equal decode's count: decode stays unnamed
    (_programs(("jit__lambda", "5", 9), ("jit__lambda", "7", 4)),
     {"decode": ("jit__lambda", 10), "prefill_chunk": ("jit__lambda", 4)},
     {("jit__lambda", "7"): "prefill_chunk"}),
    # two programs match decode's count: neither is taken for it
    (_programs(("jit__lambda", "5", 9), ("jit__lambda", "6", 9),
               ("jit__lambda", "7", 4)),
     {"decode": ("jit__lambda", 9), "prefill_chunk": ("jit__lambda", 4)},
     {("jit__lambda", "7"): "prefill_chunk"}),
    # a module of one role: every shape's program, when the runs add up
    (_programs(("jit_route_and_scan", "1", 18),
               ("jit_route_and_scan", "2", 3)),
     {"route_and_scan": ("jit_route_and_scan", 21)},
     {("jit_route_and_scan", "1"): "route_and_scan",
      ("jit_route_and_scan", "2"): "route_and_scan"}),
    # ... and none when they do not
    (_programs(("jit_route_and_scan", "1", 18),
               ("jit_route_and_scan", "2", 3)),
     {"route_and_scan": ("jit_route_and_scan", 20)}, {}),
])
def test_roles_need_exact_execution_counts(programs, expected, roles):
    assert trace_reduce.assign_roles(programs, expected) == roles


def test_span_bounds_the_window():
    from jax.profiler import ProfileData
    red = trace_reduce.reduce_profile(ProfileData.from_text_proto(_xspace()),
                                      span_ns=(0, 100_000))
    assert red.window_s == pytest.approx(100e-6)
    assert red.busy_s == pytest.approx(37e-6)


@pytest.mark.parametrize("name, split", [
    ("jit__lambda(15388027131515875373)", ("jit__lambda",
                                           "15388027131515875373")),
    ("jit_f", ("jit_f", ""))])
def test_split_module(name, split):
    assert trace_reduce.split_module(name) == split
