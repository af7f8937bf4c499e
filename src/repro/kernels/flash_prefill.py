"""Causal (optionally sliding-window) flash attention for prefill.

Tiled [TQ x TS] with online softmax in VMEM scratch. The causal band is
honoured *statically*: KV tiles strictly above the diagonal (or outside the
sliding window) are skipped by clamping the grid per q-tile via masking
inside the kernel; fully-masked tiles short-circuit to a no-op. kv heads
must be pre-expanded to the q head count by the wrapper (GQA).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG = -1e30


def _kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
            tq: int, ts: int, nsteps: int, scale: float, causal: bool,
            window):
    qi = pl.program_id(1)
    si = pl.program_id(2)

    @pl.when(si == 0)
    def _init():
        m_ref[...] = jnp.full(m_ref.shape, NEG, jnp.float32)
        l_ref[...] = jnp.zeros(l_ref.shape, jnp.float32)
        acc_ref[...] = jnp.zeros(acc_ref.shape, jnp.float32)

    q_start = qi * tq
    s_start = si * ts
    # static-ish band check (traced but cheap): skip fully-masked tiles
    needed = jnp.asarray(True)
    if causal:
        needed = needed & (s_start <= q_start + tq - 1)
    if window is not None:
        needed = needed & (s_start + ts - 1 >= q_start - window + 1)

    @pl.when(needed)
    def _compute():
        q = q_ref[0]                                  # [TQ, dh]
        k = k_ref[0]                                  # [TS, dh]
        v = v_ref[0]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        qpos = q_start + jax.lax.broadcasted_iota(jnp.int32, (tq, ts), 0)
        kpos = s_start + jax.lax.broadcasted_iota(jnp.int32, (tq, ts), 1)
        mask = jnp.ones((tq, ts), jnp.bool_)
        if causal:
            mask &= kpos <= qpos
        if window is not None:
            mask &= (qpos - kpos) < window
        s = jnp.where(mask, s, NEG)
        m_prev, l_prev = m_ref[...], l_ref[...]
        m_cur = jnp.max(s, axis=1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m_prev - m_new)
        l_new = l_prev * corr + jnp.sum(p, axis=1, keepdims=True)
        pv = jax.lax.dot_general(p.astype(v.dtype), v,
                                 (((1,), (0,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        acc_ref[...] = acc_ref[...] * corr + pv
        m_ref[...] = m_new
        l_ref[...] = l_new

    @pl.when(si == nsteps - 1)
    def _finish():
        o_ref[0] = (acc_ref[...] /
                    jnp.maximum(l_ref[...], 1e-30)).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("causal", "window", "tq", "ts",
                                             "interpret"))
def flash_prefill(q, k, v, *, causal: bool = True, window=None,
                  tq: int = 128, ts: int = 128,
                  interpret: bool | None = None):
    """q,k,v: [B, H, S, dh] (kv pre-expanded to H). Returns [B, H, S, dh].
    interpret=None resolves backend-aware (compiled on TPU, interpret
    elsewhere)."""
    if interpret is None:
        from repro.kernels.ops import default_interpret
        interpret = default_interpret()
    B, H, S, dh = q.shape
    import math
    qf = q.reshape(B * H, S, dh)
    kf = k.reshape(B * H, S, dh)
    vf = v.reshape(B * H, S, dh)
    pad = (-S) % math.lcm(tq, ts)
    if pad:
        z = jnp.zeros((B * H, pad, dh), q.dtype)
        qf = jnp.concatenate([qf, z], 1)
        kf = jnp.concatenate([kf, z], 1)
        vf = jnp.concatenate([vf, z], 1)
    Sp = qf.shape[1]
    nq, ns = Sp // tq, Sp // ts
    scale = 1.0 / (dh ** 0.5)
    out = pl.pallas_call(
        functools.partial(_kernel, tq=tq, ts=ts, nsteps=ns, scale=scale,
                          causal=causal, window=window),
        grid=(B * H, nq, ns),
        in_specs=[pl.BlockSpec((1, tq, dh), lambda b, i, j: (b, i, 0)),
                  pl.BlockSpec((1, ts, dh), lambda b, i, j: (b, j, 0)),
                  pl.BlockSpec((1, ts, dh), lambda b, i, j: (b, j, 0))],
        out_specs=pl.BlockSpec((1, tq, dh), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((B * H, Sp, dh), q.dtype),
        scratch_shapes=[pltpu.VMEM((tq, 1), jnp.float32),
                        pltpu.VMEM((tq, 1), jnp.float32),
                        pltpu.VMEM((tq, dh), jnp.float32)],
        interpret=interpret,
    )(qf, kf, vf)
    return out[:, :S].reshape(B, H, S, dh)