"""Distributed correctness on a small multi-device mesh (subprocess with 8
forced host devices so the main test process keeps 1 device)."""
import os
import subprocess
import sys
import textwrap

import pytest

SCRIPT_SHARDED_RETRIEVAL = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import jax, jax.numpy as jnp, numpy as np
    from repro.core.distributed import (make_sharded_retrieval,
                                        reference_retrieval)
    from repro.launch.mesh import make_test_mesh
    mesh = make_test_mesh(4, 2)
    rng = np.random.default_rng(0)
    NC, CAP, d, B, k, P = 16, 32, 24, 4, 5, 6
    data = rng.normal(size=(NC, CAP, d)).astype(np.float32)
    lens = rng.integers(8, CAP + 1, NC).astype(np.int32)
    for c in range(NC):
        data[c, lens[c]:] = 0
    sid = (np.arange(NC * CAP).reshape(NC, CAP)).astype(np.int32)
    cent = data[:, 0, :].copy()
    q = rng.normal(size=(B, d)).astype(np.float32)
    ret = make_sharded_retrieval(mesh, k=k, n_probe=P)
    dists, ids = jax.jit(ret)(q, cent, data, lens, sid)
    rd, ri = reference_retrieval(q, cent, data, lens, sid, k=k, n_probe=P)
    np.testing.assert_allclose(np.asarray(dists), rd, rtol=1e-4, atol=1e-4)
    assert (np.asarray(ids) == ri).all(), (ids, ri)
    print("SHARDED-RETRIEVAL-OK")
""")

SCRIPT_TRAIN_PARITY = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import jax, jax.numpy as jnp, numpy as np
    from repro.config import RunConfig, ShapeConfig, TrainConfig
    from repro.configs import get_reduced
    from repro.dist.sharding import use_mesh
    from repro.launch.mesh import make_test_mesh
    from repro.models import model
    from repro.train import trainer
    cfg = get_reduced("h2o_danube_1_8b")
    shape = ShapeConfig("t", 32, 8, "train")
    run = RunConfig(model=cfg, shape=shape,
                    train=TrainConfig(grad_clip=0.0, warmup_steps=0))
    rng = np.random.default_rng(0)
    batch = {"tokens": jnp.asarray(rng.integers(4, 100, (8, 32)), jnp.int32),
             "labels": jnp.asarray(rng.integers(4, 100, (8, 32)), jnp.int32)}
    params, opt_state = trainer.make_states(run, key=jax.random.PRNGKey(0))
    # single-device result
    s1, _, _ = trainer.make_train_step(run, microbatches=1, seq_sp=False)
    p_ref, _, m_ref = s1(params, opt_state, batch)
    # sharded result on a 4x2 mesh
    mesh = make_test_mesh(4, 2)
    with use_mesh(mesh):
        s2, _, _ = trainer.make_train_step(run, microbatches=1)
        psh, osh, bsh = trainer.state_shardings(run, mesh)
        jit2 = jax.jit(s2, in_shardings=(psh, osh, bsh),
                       out_shardings=(psh, osh, None))
        p2, _, m2 = jit2(params, opt_state, batch)
    assert abs(float(m_ref["loss"]) - float(m2["loss"])) < 5e-3, \\
        (float(m_ref["loss"]), float(m2["loss"]))
    d = jax.tree.map(lambda a, b: float(jnp.max(jnp.abs(
        a.astype(jnp.float32) - b.astype(jnp.float32)))), p_ref, p2)
    worst = max(jax.tree.leaves(d))
    assert worst < 5e-2, worst
    print("TRAIN-PARITY-OK", float(m2["loss"]))
""")

SCRIPT_MOE_PARITY = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import jax, jax.numpy as jnp, numpy as np
    from repro.configs import get_reduced
    from repro.dist.sharding import use_mesh
    from repro.launch.mesh import make_test_mesh
    from repro.models import model
    cfg = get_reduced("granite_moe_1b_a400m")
    params = model.init_params(cfg, jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)
    batch = {"tokens": jnp.asarray(rng.integers(4, 100, (4, 32)), jnp.int32),
             "labels": jnp.asarray(rng.integers(4, 100, (4, 32)), jnp.int32)}
    l1, _ = model.loss_fn(cfg, params, batch)   # local (no mesh) MoE path
    mesh = make_test_mesh(2, 2)
    with use_mesh(mesh):
        l2, _ = jax.jit(lambda p, b: model.loss_fn(cfg, p, b))(params, batch)
    # shard_map EP with capacity drop may differ slightly from local path
    assert abs(float(l1) - float(l2)) < 0.05, (float(l1), float(l2))
    print("MOE-PARITY-OK", float(l1), float(l2))
""")


def _run(script):
    env = dict(os.environ, PYTHONPATH="src")
    env.pop("XLA_FLAGS", None)
    p = subprocess.run([sys.executable, "-c", script], env=env,
                       capture_output=True, text=True, timeout=560, cwd=".")
    assert p.returncode == 0, p.stdout[-2000:] + p.stderr[-3000:]
    return p.stdout


@pytest.mark.slow
def test_sharded_retrieval_matches_reference():
    assert "SHARDED-RETRIEVAL-OK" in _run(SCRIPT_SHARDED_RETRIEVAL)


@pytest.mark.slow
def test_train_step_parity_single_vs_mesh():
    assert "TRAIN-PARITY-OK" in _run(SCRIPT_TRAIN_PARITY)


@pytest.mark.slow
def test_moe_shard_map_parity():
    assert "MOE-PARITY-OK" in _run(SCRIPT_MOE_PARITY)
