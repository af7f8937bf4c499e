"""The MoE generator through run.py on the CPU at reduced width, in a
one-cell benchmark whose configuration is Granite-3.0-1B-A400M's
registry arch at the program's reduced size.

Computing in float32 (`float32_program`), the served MoE path comes out
correct against `reference.Generator`, while a fault planted in the
served MoE FFN and the fp8 control in the program's place come out not
correct: these test what the served path computes, where no rounding
can flip a routing decision. In the configuration's own bfloat16
(`bfloat16_program`) the same cell serves and its retrieval and SCR
are correct, but its `logit_gap` is not held to a limit: a router
near-tie that bfloat16 rounding flips moves a token's logits as far as
the fp8 control does, so the comparison cannot tell the two apart
(PERF.md, Open questions). Also, at the published widths and without
allocating, the benchmark's configuration file and weight layout agree
with the program's."""
import json
import math
from pathlib import Path

import pytest

from rag_bench import check, harness, reference, tiny, weights

ROOT = Path(__file__).resolve().parent.parent
CONFIGS = ROOT / "rag_bench" / "configs"
ARCH = "granite_moe_1b_a400m"

# get_reduced(ARCH): 4 experts of width 64, top-2, no q/k/v bias
TINY_MOE = dict(tiny.TINY_MODEL, intermediate_size=64, num_local_experts=4,
                num_experts_per_tok=2, rope_theta=10000.0, qkv_bias=False)


@pytest.fixture(scope="module")
def moe_bench(tmp_path_factory):
    """tiny.write's one-cell benchmark with its configuration swapped for
    the MoE arch, and the index snapshot its first run builds."""
    root = tmp_path_factory.mktemp("tiny_moe")
    bench_file = tiny.write(root, loop="closed")
    conf_file = root / "rag_bench" / "configs" / "tiny.json"
    conf = json.loads(conf_file.read_text())
    conf.update(arch=ARCH, model=TINY_MOE)
    conf_file.write_text(json.dumps(conf))
    return bench_file, root / "cache"


@pytest.fixture
def bfloat16_program(monkeypatch):
    """The program's reduced config as the registry gives it, computing
    in bfloat16 as the chip serves."""
    from repro.configs import get_reduced
    monkeypatch.setattr(harness, "model_config",
                        lambda conf: get_reduced(conf["arch"]))
    monkeypatch.setattr(harness, "configure_jax", lambda *a, **k: None)


@pytest.fixture
def float32_program(monkeypatch):
    """The program's reduced config, computing in float32. In bfloat16,
    at this size (2 layers, top-2 of 4 experts) a router near-tie that
    rounding flips swaps half of a token's FFN output, and the served
    tokens' widest gap (0.006-0.59 over 8 seeds) overlaps the fp8
    control's (0.34-1.24): no limit could tell them apart. In float32
    the same served path agrees with the reference to rounding (widest
    gap under 2e-7), so the tests that take this fixture hold it to
    tiny's limits."""
    import dataclasses
    from repro.configs import get_reduced
    monkeypatch.setattr(harness, "model_config",
                        lambda conf: dataclasses.replace(
                            get_reduced(conf["arch"]), dtype="float32"))
    monkeypatch.setattr(harness, "configure_jax", lambda *a, **k: None)


def test_tiny_model_is_the_reduced_program(monkeypatch):
    """The harness's own size check passes TINY_MOE against the
    program's reduced config (so the reference computes what runs)."""
    import repro.configs
    from repro.configs import get_reduced
    small = get_reduced(ARCH)
    monkeypatch.setattr(repro.configs, "get_config", lambda arch: small)
    cfg = harness.model_config({"arch": ARCH, "model": TINY_MOE})
    assert cfg.family == "moe" and cfg.moe.top_k >= 2
    assert cfg.moe.num_experts >= 4 and not cfg.qkv_bias


def _run(moe_bench, seed):
    from rag_bench import run
    bench_file, cache = moe_bench
    args = run.parse_args(["--workload", "tiny.mix", "--seed", str(seed),
                           "--seconds", "2", "--trace", "0"])
    return run.run(args, platforms=("cpu",), bench_file=bench_file,
                   cache=cache, log=lambda *a: None)


def test_moe_cell_is_correct(moe_bench, float32_program):
    res = _run(moe_bench, 2 ** 31 + 17)
    assert res["correct"], res["checks"]
    assert res["attempted"] > 0 and res["failed"] == 0
    assert res["load"]["compiles_in_window"] == 0


def test_moe_cell_serves_in_bfloat16(moe_bench, bfloat16_program):
    """The served path in the configuration's own bfloat16: every
    request finishes, nothing compiles in the window, and retrieval and
    SCR are correct. `logit_gap` is read but not held (module
    docstring)."""
    from repro.configs import get_reduced
    assert get_reduced(ARCH).dtype == "bfloat16"
    res = _run(moe_bench, 2 ** 31 + 29)
    assert res["attempted"] > 0 and res["failed"] == 0
    assert res["load"]["compiles_in_window"] == 0
    checks = res["checks"]
    for name in ("retrieval_err", "scr_err"):
        assert checks[name]["value"] <= checks[name]["limit"], checks
    assert math.isfinite(checks["logit_gap"]["value"]), checks


def _unnormalised_topk(monkeypatch):
    """The served FFN's top-k gates left as softmax probabilities, not
    renormalised to sum to one (the same as scaling each token's expert
    output by the sum of its top-k probabilities)."""
    import jax
    import jax.numpy as jnp
    from repro.models import moe
    orig = moe._local_moe

    def local(cfg, x, router, *a, **k):
        y, aux = orig(cfg, x, router, *a, **k)
        probs = jax.nn.softmax(x.astype(jnp.float32)
                               @ router.astype(jnp.float32), axis=-1)
        mass = jax.lax.top_k(probs, cfg.moe.top_k)[0].sum(-1, keepdims=True)
        return y * mass.astype(y.dtype), aux
    monkeypatch.setattr(moe, "_local_moe", local)


def test_fault_in_the_served_moe_ffn_is_not_correct(moe_bench,
                                                     float32_program,
                                                     monkeypatch):
    _unnormalised_topk(monkeypatch)
    res = _run(moe_bench, 7)
    assert not res["correct"], res["checks"]
    assert res["checks"]["logit_gap"]["value"] > tiny.TINY_LIMITS["logit_gap"]


def test_fp8_control_is_not_correct(moe_bench, float32_program):
    bench_file, cache = moe_bench
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


def test_config_file_agrees_with_the_program():
    """`granite-moe-1b-a400m-rc1.json` passes the harness's size check
    against the full-size program config, its weight layout
    (`weights.leaves`) is the program's `param_shapes` in structure,
    shapes and dtypes (through `jax.eval_shape`: nothing is allocated),
    and it is `granite-moe-1b-a400m.json` with one question a retrieval
    call and the departures in its notes."""
    import jax
    import jax.numpy as jnp
    from repro.models import model
    conf = harness.load_json(CONFIGS / "granite-moe-1b-a400m-rc1.json")
    cfg = harness.model_config(conf)
    arch = reference.Arch.from_config(conf)
    got = jax.eval_shape(lambda: weights.nested(
        weights.make(arch, 0, jnp.float32)))
    want = model.param_shapes(cfg)
    assert jax.tree.structure(got) == jax.tree.structure(want)
    assert ([(g.shape, g.dtype) for g in jax.tree.leaves(got)]
            == [(w.shape, w.dtype) for w in jax.tree.leaves(want)])
    assert arch.experts == 32 and arch.top_k == 8 and arch.kv_heads == 8

    base = harness.load_json(CONFIGS / "granite-moe-1b-a400m.json")
    assert conf["engine"] == dict(base["engine"], retrieve_chunk=1)
    assert set(conf["assumed"]) - set(base["assumed"]) == {"retrieval"}
    same = {k for k in base if base[k] == conf[k]}
    assert set(base) - same == {"name", "engine", "assumed", "notes"}
    assert conf["reduced"] == [] and "muP" in conf["notes"]
    for key in ("embedding_multiplier", "attention_multiplier",
                "residual_multiplier", "logits_scaling", "rms_norm_eps"):
        assert key in conf["model"] and key in conf["notes"], key


def test_granite_cell_is_in_the_benchmark():
    """BENCHMARK.json runs the configuration: its one-chip cell loads
    through the harness on the long-form mix with the Qwen cell's check,
    and `moe_routed_row_share` is read in that cell alone."""
    name = "granite-moe-1b-a400m-rc1.squad-longform"
    cell = harness.load_cell(name)
    assert cell.chips == 1
    assert cell.config == harness.load_json(
        CONFIGS / "granite-moe-1b-a400m-rc1.json")
    assert cell.mix == harness.load_json(
        ROOT / "rag_bench" / "traffic" / "squad-longform.json")
    qwen = harness.load_cell("qwen25-0.5b-rc1.squad-longform")
    assert cell.params == qwen.params
    bench = harness.load_json(ROOT / "BENCHMARK.json")
    share = [m for m in bench["per_layer"]
             if m["name"] == "moe_routed_row_share"]
    assert [m["workloads"] for m in share] == [[name]]
