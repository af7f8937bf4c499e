"""Elastic scaling: a checkpoint written under one mesh restores onto a
different mesh (lost/added hosts) with bit-identical values and working
training — the reshard_restore path of dist/fault.py."""
import os
import subprocess
import sys
import textwrap

import pytest

SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import jax, jax.numpy as jnp, numpy as np
    from repro.config import RunConfig, ShapeConfig, TrainConfig
    from repro.configs import get_reduced
    from repro.dist import checkpoint as ckpt
    from repro.dist.fault import reshard_restore
    from repro.dist.sharding import use_mesh, spec_tree_to_shardings
    from repro.launch.mesh import make_test_mesh
    from repro.models import model
    from repro.train import trainer, optimizer as opt

    tmp = os.environ["ELASTIC_TMP"]
    cfg = get_reduced("h2o_danube_1_8b")
    run = RunConfig(model=cfg, shape=ShapeConfig("t", 32, 8, "train"),
                    train=TrainConfig(warmup_steps=0))
    rng = np.random.default_rng(0)
    batch = {"tokens": jnp.asarray(rng.integers(4, 100, (8, 32)), jnp.int32),
             "labels": jnp.asarray(rng.integers(4, 100, (8, 32)), jnp.int32)}

    # ---- phase 1: train 2 steps on a 4x2 mesh, checkpoint
    mesh1 = make_test_mesh(4, 2)
    with use_mesh(mesh1):
        params, opt_state = trainer.make_states(run, key=jax.random.PRNGKey(0))
        step, _, _ = trainer.make_train_step(run, microbatches=1)
        psh, osh, bsh = trainer.state_shardings(run, mesh1)
        jstep = jax.jit(step, in_shardings=(psh, osh, bsh),
                        out_shardings=(psh, osh, None))
        for _ in range(2):
            params, opt_state, m1 = jstep(params, opt_state, batch)
        ckpt.save(tmp, 2, (params, opt_state))
        ref_loss = float(m1["loss"])

    # ---- phase 2: "lose half the cluster": restore onto a 2x2 mesh
    mesh2 = make_test_mesh(2, 2, devices=jax.devices()[:4])
    with use_mesh(mesh2):
        like = trainer.make_states(run, abstract=True)
        pspecs = model.param_specs(cfg)
        ospecs = opt.opt_state_specs(pspecs, "float32")
        (params2, opt2), start = reshard_restore(tmp, like, mesh2,
                                                 (pspecs, ospecs))
        assert start == 3, start
        # values identical to the mesh-1 state
        for a, b in zip(jax.tree.leaves(params), jax.tree.leaves(params2)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        # and training continues on the smaller mesh
        step2, _, _ = trainer.make_train_step(run, microbatches=1)
        psh2, osh2, bsh2 = trainer.state_shardings(run, mesh2)
        jstep2 = jax.jit(step2, in_shardings=(psh2, osh2, bsh2),
                         out_shardings=(psh2, osh2, None))
        params2, opt2, m2 = jstep2(params2, opt2, batch)
        assert np.isfinite(float(m2["loss"]))
    print("ELASTIC-OK", ref_loss, float(m2["loss"]))
""")


@pytest.mark.slow
def test_elastic_reshard_across_meshes(tmp_path):
    env = dict(os.environ, PYTHONPATH="src", ELASTIC_TMP=str(tmp_path))
    env.pop("XLA_FLAGS", None)
    p = subprocess.run([sys.executable, "-c", SCRIPT], env=env, cwd=".",
                       capture_output=True, text=True, timeout=560)
    assert p.returncode == 0, p.stdout[-2000:] + p.stderr[-3000:]
    assert "ELASTIC-OK" in p.stdout
