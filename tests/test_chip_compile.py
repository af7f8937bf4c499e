"""The served retrieval kernels compile for a TPU v5e at real widths.

Interpret mode runs a kernel body through XLA and accepts block shapes
and primitives that Mosaic refuses, so every kernel test on the CPU can
pass while nothing lowers on the chip. These tests hand the kernels to
the TPU compiler for a described (not attached) v5e chip. Nothing runs:
they check lowering and compilation only, about two seconds each.

The topology is described inside a module-scoped fixture, never while a
module is imported: only one process at a time may load the TPU
compiler library, and every test worker imports this file.
"""
import functools
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

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


F32, I32 = jnp.float32, jnp.int32


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
