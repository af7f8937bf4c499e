"""run.py end to end on the CPU at reduced width: it refuses to run
without a TPU, and with the look for a chip skipped, a tiny cell comes
out correct while each fault planted under the timed path, and the
lower-precision control in the program's place, come out not correct."""
import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

from rag_bench import check, faults, harness, tiny

ROOT = Path(__file__).resolve().parent.parent
CELL = "qwen25-0.5b-rc1.squad-longform"


def _cli(cwd: Path, *extra):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=os.pathsep.join([str(ROOT / "src"), str(ROOT)]))
    return subprocess.run(
        [sys.executable, str(cwd / "rag_bench" / "run.py"), "--workload",
         CELL, "--seed", str(2 ** 31 + 3), "--seconds", "1", "--trace", "0",
         *extra], cwd=cwd, env=env, capture_output=True, text=True,
        timeout=300)


def test_cli_exits_nonzero_without_a_tpu():
    r = _cli(ROOT)
    assert r.returncode != 0
    assert "no TPU" in r.stderr
    assert not r.stdout.strip()


def test_cli_exits_nonzero_with_only_the_benchmark(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(ROOT / "rag_bench", tmp_path / "rag_bench",
                    ignore=shutil.ignore_patterns("cache", "__pycache__"))
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH="")
    r = subprocess.run([sys.executable, "rag_bench/run.py", "--workload",
                        CELL, "--seed", "1", "--seconds", "1", "--trace",
                        "0"], cwd=tmp_path, env=env, capture_output=True,
                       text=True, timeout=300)
    assert r.returncode != 0
    assert not r.stdout.strip()


@pytest.fixture(scope="module")
def tiny_bench(tmp_path_factory):
    """A one-cell benchmark at reduced width, and the index snapshot its
    first run builds (shared by the runs below)."""
    root = tmp_path_factory.mktemp("tiny")
    return tiny.write(root, loop="closed"), root / "cache"


@pytest.fixture
def reduced_program(monkeypatch):
    from repro.configs import get_reduced
    monkeypatch.setattr(harness, "model_config",
                        lambda conf: get_reduced(conf["arch"]))
    monkeypatch.setattr(harness, "configure_jax", lambda *a, **k: None)


def _run(tiny_bench, seed):
    from rag_bench import run
    bench_file, cache = tiny_bench
    args = run.parse_args(["--workload", "tiny.mix", "--seed", str(seed),
                           "--seconds", "2", "--trace", "0"])
    return run.run(args, platforms=("cpu",), bench_file=bench_file,
                   cache=cache, log=lambda *a: None)


def test_tiny_cell_is_correct(tiny_bench, reduced_program):
    res = _run(tiny_bench, 2 ** 31 + 11)
    assert res["correct"], res["checks"]
    assert res["attempted"] > 0 and res["failed"] == 0
    assert res["load"]["compiles_in_window"] == 0
    assert set(res["metrics"]) == {"ttft_p50_ms", "ttft_p95_ms",
                                   "itl_p95_ms", "tokens_per_s", "setup_s"}
    assert list(res)[-1] == "checks"
    json.dumps(res)


@pytest.mark.parametrize("fault", sorted(faults.FAULTS))
def test_fault_under_the_timed_path_is_not_correct(tiny_bench,
                                                   reduced_program, fault):
    with faults.FAULTS[fault]():
        res = _run(tiny_bench, 7)
    assert not res["correct"], res["checks"]


def test_lower_precision_control_is_not_correct(tiny_bench, reduced_program):
    bench_file, cache = tiny_bench
    cell = harness.load_cell("tiny.mix", bench_file)
    stack = harness.build(cell.config, cell.mix, 5, trace=False,
                          state_root=cache / "index")
    harness.warm(stack, cell.config, cell.mix, 5)
    stop = harness.record_outputs(stack)
    log = harness.drive(stack, cell.mix, None, 2.0, 5)
    stop()
    ev = check.gather(stack, log, 5, harness.sub_seed(5, "weights"),
                      int(cell.params["check_requests"]))
    harness.free(stack)
    prog, ctrl = check.numbers(ev, cell.config, control=True)
    limits = cell.params["limits"]
    assert check.verdict(prog, limits)[0], prog
    assert not check.verdict(ctrl, limits)[0], ctrl
    assert ctrl["logit_gap"] > 3 * prog["logit_gap"]
