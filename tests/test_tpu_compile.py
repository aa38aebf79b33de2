"""Compile-only checks of the five Pallas kernels for a TPU v5e.

The kernels run interpreted on the CPU everywhere else in the suite, and the
interpreter accepts code the TPU compiler (Mosaic) refuses: a
``dynamic_slice``, or a block that breaks the (8, 128) tiling rule. Here
each kernel is lowered and compiled, at the widths the main path uses, for
a *described* ``v5e:2x2`` topology — no chip is needed — and the compiled
HLO must hold the kernel as a ``tpu_custom_call``.

Widths: inner code K=32 / R=80 with 4 KiB fragment payloads, a batch of
48 GF(256) systems with k=32 and 40 received symbols, a 1024-node x
256-fragment PRF grid, and 4096 PRF pairs (above ``PAIRS_KERNEL_MIN``).

The topology is described inside a module fixture, never at import: only
one process may load the TPU library at a time, so under pytest-xdist just
the worker that runs this file loads it.
"""
from __future__ import annotations

import os

import jax
import jax.numpy as jnp
import pytest

from repro.kernels.gf256_encode import gf256_encode_kernel
from repro.kernels.gf256_solve import gf256_solve_kernel
from repro.kernels.gf2_encode import gf2_encode_kernel
from repro.kernels.prf_select import (PAIRS_KERNEL_MIN, _prf_pairs_call,
                                      prf_select_kernel)

K_INNER, R_INNER = 32, 80
PAYLOAD = 4096          # fragment bytes
SOLVE_BATCH, SOLVE_M = 48, 40
PRF_NODES, PRF_FRAGS = 1024, 256
PAIRS = 2 * PAIRS_KERNEL_MIN


@pytest.fixture(scope="module")
def one_chip():
    """One device of a described v5e:2x2, with the persistent compile cache
    off: a compile for a described chip is written to the cache but cannot
    be read back without one."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc
    from jax.sharding import SingleDeviceSharding

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler installed here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


def _i32(sharding, *shape):
    return jax.ShapeDtypeStruct(shape, jnp.int32, sharding=sharding)


def _assert_kernel(lowered):
    hlo = lowered.compile().as_text()
    assert "tpu_custom_call" in hlo


def test_gf256_encode_compiles(one_chip):
    fn = jax.jit(lambda c, d: gf256_encode_kernel(
        c, d, tile_r=8, tile_l=512, interpret=False))
    _assert_kernel(fn.lower(_i32(one_chip, R_INNER, K_INNER),
                            _i32(one_chip, K_INNER, PAYLOAD)))


def test_gf2_encode_compiles(one_chip):
    fn = jax.jit(lambda m, d: gf2_encode_kernel(
        m, d, tile_r=8, tile_w=512, interpret=False))
    _assert_kernel(fn.lower(_i32(one_chip, R_INNER, K_INNER),
                            _i32(one_chip, K_INNER, PAYLOAD // 4)))


def test_gf256_solve_compiles(one_chip):
    # gf256_solve_batch's padding: rows to 8, columns and payload to 128
    mp = -(-SOLVE_M // 8) * 8
    fn = jax.jit(lambda a, y: gf256_solve_kernel(a, y, k=K_INNER,
                                                 interpret=False))
    _assert_kernel(fn.lower(_i32(one_chip, SOLVE_BATCH, mp, 128),
                            _i32(one_chip, SOLVE_BATCH, mp, PAYLOAD)))


def test_prf_select_compiles(one_chip):
    fn = jax.jit(lambda t, f: prf_select_kernel(t, f, interpret=False))
    _assert_kernel(fn.lower(_i32(one_chip, PRF_NODES, 2),
                            _i32(one_chip, PRF_FRAGS, 2)))


def test_prf_select_pairs_compiles(one_chip):
    plane = _i32(one_chip, PAIRS // 128, 128)
    _assert_kernel(_prf_pairs_call.lower(plane, plane, plane, plane,
                                         interpret=False))
