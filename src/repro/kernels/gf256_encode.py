"""Pallas TPU kernel: GF(256) rateless-code encode (coeff-matrix x blocks).

Computes ``out[r, l] = XOR_k gfmul(coeffs[r, k], data[k, l])`` — the inner
loop of VAULT fragment generation (the hot spot the paper covers with
wirehair on CPU, Fig. 10).

TPU adaptation: the field multiply is bit-sliced (8 rounds of
AND/XOR/shift/select), so the kernel is pure VPU element-wise work with no
gathers. Tiling: the coefficient tile (TR, K) stays resident in VMEM across
the payload dimension; payload tiles are lane-aligned multiples of 128.
Operands are carried as int32 byte values (one byte per lane) — a production
variant would bit-pack 4 bytes/lane; see kernels/EXAMPLE.md discussion in
DESIGN.md §4.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.backend import resolve_interpret

from repro.core.gf import GF_POLY

DEFAULT_TILE_R = 8
DEFAULT_TILE_L = 512


def _gfmul_tile(a, b):
    """Bit-sliced GF(256) multiply; a: (TR, 1) int32, b: (1, TL) int32."""
    res = jnp.zeros((a.shape[0], b.shape[1]), jnp.int32)
    for _ in range(8):
        res = res ^ jnp.where((b & 1) != 0, a, 0)
        hi = a & 0x80
        a = (a << 1) & 0xFF
        a = jnp.where(hi != 0, a ^ (GF_POLY & 0xFF), a)
        b = b >> 1
    return res


def _encode_kernel(c_ref, d_ref, o_ref, *, k_dim: int):
    c = c_ref[...]  # (TR, K) int32
    lanes = jax.lax.broadcasted_iota(jnp.int32, c.shape, 1)

    def body(k, acc):
        # Mosaic has no dynamic_slice: pick column k of the small
        # coefficient tile by an iota mask (exactly one lane survives the
        # sum) and row k of the payload tile by a dynamic sublane load.
        a = jnp.sum(jnp.where(lanes == k, c, 0), 1, keepdims=True)  # (TR, 1)
        b = d_ref[pl.ds(k, 1), :]  # (1, TL)
        return acc ^ _gfmul_tile(a, b)

    acc = jnp.zeros(o_ref.shape, jnp.int32)
    o_ref[...] = jax.lax.fori_loop(0, k_dim, body, acc)


@functools.partial(jax.jit, static_argnames=("tile_r", "tile_l", "interpret"))
def gf256_encode_kernel(
    coeffs: jax.Array,
    data: jax.Array,
    tile_r: int = DEFAULT_TILE_R,
    tile_l: int = DEFAULT_TILE_L,
    interpret: bool | None = None,
) -> jax.Array:
    """coeffs (R, K) int32, data (K, L) int32 -> (R, L) int32.

    R must be a multiple of tile_r and L of tile_l (ops.py pads).
    """
    r, k = coeffs.shape
    k2, l = data.shape
    assert k == k2, (coeffs.shape, data.shape)
    assert r % tile_r == 0 and l % tile_l == 0, (r, l, tile_r, tile_l)
    interpret = resolve_interpret(interpret)
    grid = (r // tile_r, l // tile_l)
    return pl.pallas_call(
        functools.partial(_encode_kernel, k_dim=k),
        grid=grid,
        in_specs=[
            pl.BlockSpec((tile_r, k), lambda i, j: (i, 0)),
            pl.BlockSpec((k, tile_l), lambda i, j: (0, j)),
        ],
        out_specs=pl.BlockSpec((tile_r, tile_l), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((r, l), jnp.int32),
        interpret=interpret,
    )(coeffs, data)
