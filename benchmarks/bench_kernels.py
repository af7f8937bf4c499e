"""Kernel micro-benchmarks (interpret-mode on CPU: correctness-grade
timing only; real perf numbers come from the dry-run roofline terms)."""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import emit
from repro.kernels import ops, ref


def _time(fn, *args, reps=3, **kw):
    out = fn(*args, **kw)
    jax.block_until_ready(out)
    t0 = time.perf_counter()
    for _ in range(reps):
        out = fn(*args, **kw)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / reps


def run(mode="quick"):
    k0 = jax.random.PRNGKey(0)
    B, d, NC, CAP, P = 8, 128, 64, 256, 8
    q = jax.random.normal(k0, (B, d))
    data = jax.random.normal(k0, (NC, CAP, d))
    lens = jnp.full((NC,), CAP, jnp.int32)
    probes = jnp.tile(jnp.arange(P, dtype=jnp.int32)[None], (B, 1))
    t_ref = _time(ops.ecoscan, q, data, lens, probes, use_pallas=False)
    t_pal = _time(ops.ecoscan, q, data, lens, probes, use_pallas=True)
    emit("kernel.ecoscan.ref", t_ref * 1e6, f"B={B};P={P};CAP={CAP}")
    emit("kernel.ecoscan.pallas_interpret", t_pal * 1e6, "correctness-mode")

    # fused on-device route->scan vs host-routed two-step
    cent = jax.random.normal(jax.random.PRNGKey(7), (NC, d))

    def two_step(q, cent, data, lens, n_probe=P, k=10):
        qn = jax.device_get(q)
        cn = jax.device_get(cent)
        d2 = ((qn ** 2).sum(1)[:, None] - 2 * qn @ cn.T
              + (cn ** 2).sum(1)[None, :])
        import numpy as _np
        pr = jnp.asarray(_np.argsort(d2, 1)[:, :n_probe].astype(_np.int32))
        return ops.ecoscan(q, data, lens, pr, k=k)

    t_two = _time(two_step, q, cent, data, lens)
    t_fused = _time(ops.route_and_scan, q, cent, data, lens, n_probe=P)
    emit("kernel.route_scan.two_step", t_two * 1e6,
         "before: host argsort routing + scan")
    emit("kernel.route_scan.fused", t_fused * 1e6,
         f"after: one jitted route+scan;speedup={t_two / t_fused:.2f}x")

    x = jax.random.normal(k0, (4096, 128))
    c = jax.random.normal(k0, (64, 128))
    emit("kernel.kmeans_assign.ref",
         _time(ops.kmeans_assign, x, c, use_pallas=False) * 1e6, "N=4096")
    emit("kernel.kmeans_assign.pallas_interpret",
         _time(ops.kmeans_assign, x, c, use_pallas=True) * 1e6, "N=4096")

    w = jax.random.normal(k0, (4, 512, 384))
    qq = jax.random.normal(k0, (4, 384))
    emit("kernel.scr_score.ref",
         _time(ops.scr_score, w, qq, use_pallas=False) * 1e6, "NW=512")
    emit("kernel.scr_score.pallas_interpret",
         _time(ops.scr_score, w, qq, use_pallas=True) * 1e6, "NW=512")

    # fused SCR select: score + per-doc segment-argmax in one call over
    # the corpus-resident window pack (DESIGN.md §7)
    ND, CAPW, K = 256, 16, 8
    wdata = jax.random.normal(k0, (ND, CAPW, 384))
    wlens = jnp.full((ND,), CAPW, jnp.int32)
    dids = jax.random.randint(jax.random.PRNGKey(9), (4, K), 0, ND,
                              jnp.int32)
    emit("kernel.scr_select.ref",
         _time(ops.scr_select, qq, wdata, wlens, dids,
               use_pallas=False) * 1e6, f"ND={ND};CAPW={CAPW};K={K}")
    emit("kernel.scr_select.pallas_interpret",
         _time(ops.scr_select, qq, wdata, wlens, dids,
               use_pallas=True) * 1e6, f"ND={ND};CAPW={CAPW};K={K}")


if __name__ == "__main__":
    run()
