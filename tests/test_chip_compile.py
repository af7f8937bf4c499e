"""The served kernels compile for a TPU v5e at real widths.

Interpret mode runs a kernel body through XLA and accepts block shapes
and primitives that Mosaic refuses, so every kernel test on the CPU can
pass while nothing lowers on the chip. These tests hand the kernels to
the TPU compiler for a described (not attached) v5e chip. Nothing runs:
they check lowering and compilation only, about two seconds each. The
paged decode kernel also has a case that runs it, on a TPU only.

The topology is described inside a module-scoped fixture, never while a
module is imported: only one process at a time may load the TPU
compiler library, and every test worker imports this file.
"""
import functools
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import ref
from repro.kernels.decode_attention import decode_attention_paged
from repro.kernels.ecoscan import ecoscan, route_and_scan
from repro.kernels.kmeans_assign import kmeans_assign
from repro.kernels.scr_select import scr_select


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


def _compile(fn, sharding, *shapes):
    """Lower and compile `fn` for the described chip; raises what the
    chip's compiler would raise. Returns the compiled HLO text."""
    args = [jax.ShapeDtypeStruct(s, dt, sharding=sharding)
            for s, dt in shapes]
    return jax.jit(fn).lower(*args).compile().as_text()


F32, I32, BF16, I8 = jnp.float32, jnp.int32, jnp.bfloat16, jnp.int8


@pytest.mark.parametrize("d", [128, 384])
@pytest.mark.parametrize("B", [1, 8])
def test_route_and_scan_compiles_for_v5e(one_chip, B, d):
    NC, CAP = 1024, 1024
    fn = functools.partial(route_and_scan, n_probe=16, k=10,
                           interpret=False)
    hlo = _compile(fn, one_chip, ((B, d), F32), ((NC, d), F32),
                   ((NC, CAP, d), F32), ((NC,), I32))
    assert "tpu_custom_call" in hlo


def test_ecoscan_block_map_compiles_for_v5e(one_chip):
    B, d, R, CAP, NC, P = 8, 128, 512, 1024, 1024, 16
    fn = functools.partial(ecoscan, k=10, interpret=False)
    hlo = _compile(lambda q, x, ln, pr, bm: fn(q, x, ln, pr, block_map=bm),
                   one_chip, ((B, d), F32), ((R, CAP, d), F32),
                   ((R,), I32), ((B, P), I32), ((NC,), I32))
    assert "tpu_custom_call" in hlo


@pytest.mark.parametrize("K", [3, 10])
def test_scr_select_compiles_for_v5e(one_chip, K):
    """K=10 spans two doc tiles (T > 1) at the default tile of 8."""
    B, d, ND, CAPW = 8, 384, 4096, 32
    fn = functools.partial(scr_select, interpret=False)
    hlo = _compile(fn, one_chip, ((B, d), F32), ((ND, CAPW, d), F32),
                   ((ND,), I32), ((B, K), I32))
    assert "tpu_custom_call" in hlo


def test_kmeans_assign_compiles_for_v5e(one_chip):
    N, d, NC = 65536, 128, 1024
    fn = functools.partial(kmeans_assign, interpret=False)
    hlo = _compile(fn, one_chip, ((N, d), F32), ((NC, d), F32))
    assert "tpu_custom_call" in hlo


# Paged decode as both cells serve it: 16 slots, 18-page tables of 32
# positions, 24 layers, heads of 64; Qwen2.5-0.5B holds 14 query heads
# over 2 KV heads, Granite-3.0-1B-A400M 16 over 8. int8 K/V on Qwen's.
_DECODE = {"qwen": (14, 2, False), "granite": (16, 8, False),
           "qwen-int8": (14, 2, True)}
_B, _DH, _L, _PS, _W = 16, 64, 24, 32, 18


def _decode_shapes(cell):
    """(q, new, pool, kv_len, cursor, table, layer) shapes and dtypes."""
    H, G, quant = _DECODE[cell]
    P, lanes, kv = 1 + _B * _W, G * _DH, I8 if quant else BF16
    new = [((_B, G, _DH), kv)] * 2 + [((_B, G), F32)] * 2 * quant
    pool = [((_L, P, _PS, lanes), kv)] * 2 + [((_L, P, _PS, G), F32)] * 2 \
        * quant
    return (((_B, H, _DH), BF16), new, pool, ((_B,), I32), ((_B,), I32),
            ((_B, _W), I32), ((), I32))


@pytest.mark.parametrize("cell", list(_DECODE))
def test_decode_attention_paged_compiles_for_v5e(one_chip, cell):
    spec = lambda s: jax.ShapeDtypeStruct(*s, sharding=one_chip)
    args = [[spec(x) for x in a] if isinstance(a, list) else spec(a)
            for a in _decode_shapes(cell)]
    fn = functools.partial(decode_attention_paged, interpret=False)
    assert "tpu_custom_call" in jax.jit(fn).lower(*args).compile().as_text()


@pytest.mark.parametrize("cell", list(_DECODE))
def test_decode_attention_paged_runs_on_the_chip(cell):
    """On a TPU: the compiled kernel matches the float32 oracle for rows
    of length 0 to the full table, distinct pages per row, tails on
    page 0."""
    if jax.default_backend() != "tpu":
        pytest.skip("runs only on a TPU")
    from repro.models import layers as L
    H, G, quant = _DECODE[cell]
    q, new, pool, *_ = _decode_shapes(cell)
    keys = iter(jax.random.split(jax.random.PRNGKey(0), 8))
    draw = lambda *s: jax.random.normal(next(keys), s, BF16)
    if quant:
        (kq, ks), (vq, vs) = (L.quantize_kv(draw(_L, 1 + _B * _W, _PS, G, _DH))
                              for _ in "kv")
        pool = (kq.reshape(pool[0][0]), vq.reshape(pool[0][0]), ks, vs)
        (nkq, nks), (nvq, nvs) = (L.quantize_kv(draw(_B, G, _DH))
                                  for _ in "kv")
        new = (nkq, nvq, nks, nvs)
    else:
        pool = tuple(draw(*x[0]) for x in pool)
        new = tuple(draw(*x[0]) for x in new)
    lens = jnp.asarray(np.linspace(0, _W * _PS - 1, _B), I32)
    table = 1 + jnp.arange(_B * _W, dtype=I32).reshape(_B, _W)
    table = jnp.where(jnp.arange(_W) < -(-(lens[:, None] + 1) // _PS),
                      table, 0)
    args = (draw(*q[0]), new, pool, lens, lens, table, jnp.int32(_L - 1))
    o = decode_attention_paged(*args, interpret=False)
    np.testing.assert_allclose(np.asarray(o, np.float32),
                               np.asarray(ref.decode_attention_paged(*args)),
                               rtol=2e-2, atol=2e-2)
