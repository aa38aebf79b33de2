"""Pallas TPU kernel: batched GF(256) Gaussian solve (rateless decode).

Solves ``coeffs[b] @ X[b] = symbols[b]`` over GF(2^8) for a batch of
independent systems — the decode side of the RLNC rateless code
(``rateless.gf256_gaussian_solve``), which sits on the repair hot path:
every chunk repair that cannot be served from a warm cache pulls >= k
fragments and solves one such system.

The scalar reference solver maintains ``row == col`` throughout (each
column either finds a pivot at-or-below the diagonal and advances, or the
whole solve fails), so the batched form can run a fixed ``k``-step
Gauss-Jordan schedule: per column, pivot search is a masked first-nonzero
reduction over the trailing rows, the row swap is a pair of masked-select
rewrites (no gathers — TPU VPU friendly), the pivot inverse is the
addition-chain ``a^254 = a^2·a^4·a^8·a^16·a^32·a^64·a^128`` on the
bit-sliced multiplier, and elimination clears the column in *all* other
rows. Rank-deficient systems do not raise mid-kernel: each batch element
carries a sticky ``ok`` flag plus the first failing column, and the caller
(``rateless``) re-raises ``InsufficientFragments`` with the exact message
the scalar path produces.

Dispatch: :func:`gf256_solve_batch` mirrors the kernel in vectorized numpy
(bit-identical to the scalar reference on full-rank systems — pinned by
``tests/test_gf256_solve.py``) and routes to the Pallas kernel only above
a work threshold; in-simulator solves are single small systems and stay on
the numpy mirror, while benchmark/test batches exercise the kernel.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

from repro.core.gf import GF_EXP, GF_LOG, GF_POLY, gf_mul_np
from repro.kernels.backend import resolve_interpret

# below this many total symbol bytes (B*m*L) the numpy mirror wins: the
# in-sim decode is one (m ~ k+epsilon, L ~ fragment) system at a time,
# far under the threshold, so the simulator never pays a jax dispatch.
SOLVE_KERNEL_MIN = 1 << 16


# ------------------------------------------------------------ numpy mirror
# Sentinel log/exp pair for the single-system solver: _LOG2[0] is pushed to
# 1020, past every reachable true-log sum (max 254 + 254 + 255 = 763), and
# _EXP2 maps the whole sentinel range to 0 — so one fused gather computes
# exp[log f + log row - log pv] with GF(256) zero-propagation built in: no
# mod-255, no zero masks. exp2[i] = exp[i % 255] on the live range.
_LOG2 = GF_LOG.astype(np.int32).copy()
_LOG2[0] = 1020
_EXP2 = np.zeros(2560, np.uint8)
_EXP2[:765] = GF_EXP[np.arange(765) % 255]


def _solve1(
    a: np.ndarray, y: np.ndarray
) -> tuple[np.ndarray, np.ndarray, np.ndarray]:
    """Single-system fast path (``B == 1``) on one augmented matrix.

    Runs the exact pivot/elimination schedule of the batched loop below —
    identical over GF(256), which is exact integer algebra — but drops the
    batch axis and the per-step batch bookkeeping. The in-simulator repair
    decode solves one (m ~ k, L ~ fragment) system per repaired fragment,
    so this path's per-step numpy overhead is what the repair tick
    actually pays. Early-exits on the first rank-deficient column (the
    solution rows are garbage whenever ``ok`` is False either way).
    """
    m, k = a.shape
    aug = np.concatenate([a, y], axis=1)   # one array, half the op count
    exp2, log2 = _EXP2, _LOG2
    piv_log = np.empty(k, np.int32)
    for col in range(k):
        pv = aug[col, col]
        if pv == 0:
            nz = aug[col:, col] != 0
            if not nz.any():
                return (aug[:k, k:], np.zeros(1, bool),
                        np.full(1, col, np.int32))
            piv = col + int(np.argmax(nz))
            aug[[col, piv]] = aug[[piv, col]]
            pv = aug[col, col]
        row = aug[col]
        lpv = int(log2[pv])
        piv_log[col] = lpv
        # unnormalized Jordan step: subtract (f_i / pv) * row from every
        # other row — prod = exp2[log f + log row - log pv] in one fused
        # gather (sentinel logs zero-propagate). Leaving the pivot row
        # unnormalized keeps the pass this short; the diagonal is fixed
        # up once at the end (exact field algebra — identical solution).
        prod = exp2[log2[aug[:, col]][:, None] + (log2[row] + (255 - lpv))]
        prod[col] = 0
        aug ^= prod
    # rows hold pv_i * x_i — one vectorized normalize settles the output
    sol = aug[:k, k:]
    return (exp2[log2[sol] + (255 - piv_log)[:, None]],
            np.ones(1, bool), np.full(1, -1, np.int32))


def gf256_rank_prefix(coeffs: np.ndarray) -> tuple[bool, int]:
    """Minimum row prefix of ``coeffs`` (m, k) with full column rank.

    Returns ``(ok, n_pull)``. ``ok`` is False iff the *whole* row set is
    rank-deficient (``n_pull`` is then ``m``). Otherwise ``n_pull`` is the
    smallest prefix length whose rows solve — exactly the fragment count
    the incremental one-more-row retry loop in ``repair._pull_and_decode``
    reaches, at rank-only cost (no payload columns):

    The greedy at-or-below-diagonal pivot rule means appending rows below
    a prefix never changes the pivots chosen *within* that prefix (pivot
    search scans top-down, and eliminating a lower row never feeds back
    into upper rows), so the per-prefix retry runs nest and one
    row-echelon pass over the full matrix decides them all: the minimal
    solving prefix is ``1 + max(original row index of any pivot)``, and
    no prefix solves iff the full matrix is rank-deficient. Pivot choice
    matches ``_solve1``/``gf256_gaussian_solve_ref`` exactly (first
    nonzero at/below the diagonal).
    """
    a_full = np.asarray(coeffs, np.uint8)
    m, k = a_full.shape
    if m < k:
        return False, m
    exp2, log2 = _EXP2, _LOG2
    # Fast path: eliminate the k x k prefix alone. Pivot search scans
    # top-down, so as long as every column finds a pivot inside the first
    # k rows the full-matrix pass would choose the identical pivots (rows
    # below k are reachable only once the prefix runs out of nonzeros in
    # a column) — and then every pivot's original row index is < k, so
    # ``deep`` is decided by the prefix too. Rows k..m-1 receive the same
    # eliminations in the full pass but never feed back into the prefix,
    # so skipping them changes nothing. ~1/255 of random draws miss a
    # prefix pivot and fall through to the full pass below.
    a = a_full[:k].copy()
    orig = np.arange(k)
    deep = 0
    prefix_ok = True
    for col in range(k):
        nz = a[col:, col] != 0
        if not nz.any():
            prefix_ok = False
            break
        piv = col + int(np.argmax(nz))
        if piv != col:
            a[[col, piv]] = a[[piv, col]]
            orig[[col, piv]] = orig[[piv, col]]
        if orig[col] >= deep:
            deep = int(orig[col]) + 1
        if col + 1 < k:
            below = a[col + 1:]
            row = a[col]
            below ^= exp2[log2[below[:, col]][:, None]
                          + (log2[row] + (255 - int(log2[row[col]])))]
    if prefix_ok:
        return True, deep
    a = a_full.copy()
    orig = np.arange(m)
    deep = 0
    for col in range(k):
        nz = a[col:, col] != 0
        if not nz.any():
            return False, m
        piv = col + int(np.argmax(nz))
        if piv != col:
            a[[col, piv]] = a[[piv, col]]
            orig[[col, piv]] = orig[[piv, col]]
        if orig[col] >= deep:
            deep = int(orig[col]) + 1
        if col + 1 < m:
            below = a[col + 1:]
            # row-echelon only: rank and pivot order never depend on the
            # rows above the diagonal, so skip the Jordan half. Fused
            # sentinel-log gather as in _solve1 (zero factors propagate).
            row = a[col]
            below ^= exp2[log2[below[:, col]][:, None]
                          + (log2[row] + (255 - int(log2[row[col]])))]
    return True, deep


def gf256_solve_one(
    coeffs: np.ndarray, symbols: np.ndarray
) -> tuple[np.ndarray, bool, int]:
    """Single-system entry: ``(x, ok, fail_col)`` with scalar flags.

    The repair tick calls this once per repaired fragment; skipping the
    batch packaging (leading-axis reshape, batch flag arrays) keeps the
    per-call overhead at the numpy floor. Identical math to
    :func:`gf256_solve_np` with ``B == 1``.
    """
    x, ok, fail_col = _solve1(np.asarray(coeffs, np.uint8),
                              np.asarray(symbols, np.uint8))
    return x, bool(ok[0]), int(fail_col[0])


def gf256_solve_np(
    coeffs: np.ndarray, symbols: np.ndarray
) -> tuple[np.ndarray, np.ndarray, np.ndarray]:
    """Batched Gauss-Jordan over GF(256), vectorized across the batch.

    ``coeffs``: (B, m, k) uint8, ``symbols``: (B, m, L) uint8, m >= k.
    Returns ``(x, ok, fail_col)``: ``x`` (B, k, L) solutions (garbage rows
    where ``ok`` is False), ``ok`` (B,) bool full-rank flags, ``fail_col``
    (B,) int32 first rank-deficient column (-1 where ok). Element-for-
    element identical to the scalar ``rateless.gf256_gaussian_solve_ref``
    on every full-rank system, and flags exactly the column at which the
    scalar solver raises otherwise.
    """
    a = np.asarray(coeffs, np.uint8)
    y = np.asarray(symbols, np.uint8)
    B, m, k = a.shape
    assert y.shape[0] == B and y.shape[1] == m, (a.shape, y.shape)
    if B == 1:
        x, ok, fail_col = _solve1(a[0], y[0])
        return x[None], ok, fail_col
    a = a.copy()
    y = y.copy()
    ok = np.ones(B, bool)
    fail_col = np.full(B, -1, np.int32)
    bidx = np.arange(B)
    for col in range(k):
        nz = a[:, col:, col] != 0          # (B, m-col) pivot candidates
        has = nz.any(axis=1)
        fail_col[ok & ~has] = col
        ok &= has
        piv = col + np.argmax(nz, axis=1)  # first nonzero at/below diag
        piv = np.where(has, piv, col)      # failed lanes: no-op swap
        # vectorized row swap col <-> piv (identity when piv == col)
        tmp = a[bidx, piv].copy()
        a[bidx, piv] = a[bidx, col]
        a[bidx, col] = tmp
        tmp = y[bidx, piv].copy()
        y[bidx, piv] = y[bidx, col]
        y[bidx, col] = tmp
        pv = a[:, col, col]
        inv = GF_EXP[255 - GF_LOG[np.where(pv == 0, 1, pv)]]  # (B,)
        a[:, col] = gf_mul_np(a[:, col], inv[:, None])
        y[:, col] = gf_mul_np(y[:, col], inv[:, None])
        f = a[:, :, col].copy()            # (B, m) elimination factors
        f[:, col] = 0
        a ^= gf_mul_np(f[:, :, None], a[:, col:col + 1, :])
        y ^= gf_mul_np(f[:, :, None], y[:, col:col + 1, :])
    return y[:, :k], ok, fail_col


# ------------------------------------------------------------ pallas kernel
def _gfmul(a, b):
    """Bit-sliced GF(256) multiply (8-round Russian peasant), broadcasting
    int32 byte-value arrays — same VPU sequence as ``gf256_encode``."""
    res = jnp.zeros(jnp.broadcast_shapes(a.shape, b.shape), jnp.int32)
    for _ in range(8):
        res = res ^ jnp.where((b & 1) != 0, a, 0)
        hi = a & 0x80
        a = (a << 1) & 0xFF
        a = jnp.where(hi != 0, a ^ (GF_POLY & 0xFF), a)
        b = b >> 1
    return res


def _gfinv(a):
    """a^254 == a^-1 in GF(2^8), via the squaring addition chain
    2+4+8+16+32+64+128 = 254 (7 squarings + 6 multiplies, no tables)."""
    x2 = _gfmul(a, a)
    x4 = _gfmul(x2, x2)
    x8 = _gfmul(x4, x4)
    x16 = _gfmul(x8, x8)
    x32 = _gfmul(x16, x16)
    x64 = _gfmul(x32, x32)
    x128 = _gfmul(x64, x64)
    out = _gfmul(x2, x4)
    for t in (x8, x16, x32, x64, x128):
        out = _gfmul(out, t)
    return out


def _solve_kernel(a_ref, y_ref, x_ref, st_ref, *, k: int):
    a = a_ref[0]                     # (mp, kp) int32
    y = y_ref[0]                     # (mp, Lp) int32
    mp = a.shape[0]
    rows = jax.lax.broadcasted_iota(jnp.int32, (mp, 1), 0)
    lanes = jax.lax.broadcasted_iota(jnp.int32, (1, a.shape[1]), 1)

    def column(m, col):
        # column ``col`` of ``m`` as (rows, 1): an iota-mask pick (one lane
        # survives the sum), since Mosaic cannot lower dynamic_slice
        return jnp.sum(jnp.where(lanes == col, m, 0), 1, keepdims=True)

    def body(col, carry):
        a, y, ok, fail = carry
        colv = column(a, col)
        elig = (rows >= col) & (colv != 0)
        has = jnp.any(elig)
        fail = jnp.where(ok & ~has, col, fail)
        ok = ok & has
        piv = jnp.where(has, jnp.min(jnp.where(elig, rows, mp)), col)
        # swap rows col <-> piv via masked reductions (no TPU gathers);
        # identity when piv == col
        is_piv = rows == piv
        is_col = rows == col
        piv_a = jnp.sum(jnp.where(is_piv, a, 0), 0, keepdims=True)
        piv_y = jnp.sum(jnp.where(is_piv, y, 0), 0, keepdims=True)
        col_a = jnp.sum(jnp.where(is_col, a, 0), 0, keepdims=True)
        col_y = jnp.sum(jnp.where(is_col, y, 0), 0, keepdims=True)
        a = jnp.where(is_piv, col_a, jnp.where(is_col, piv_a, a))
        y = jnp.where(is_piv, col_y, jnp.where(is_col, piv_y, y))
        # normalize the pivot row (failed lanes continue on garbage; the
        # sticky ok flag gates the result)
        inv = _gfinv(column(piv_a, col))
        norm_a = _gfmul(piv_a, inv)
        norm_y = _gfmul(piv_y, inv)
        a = jnp.where(is_col, norm_a, a)
        y = jnp.where(is_col, norm_y, y)
        # eliminate the column everywhere else (Gauss-Jordan)
        f = jnp.where(is_col, 0, column(a, col))
        a = a ^ _gfmul(f, norm_a)
        y = y ^ _gfmul(f, norm_y)
        return a, y, ok, fail

    a, y, ok, fail = jax.lax.fori_loop(
        0, k, body, (a, y, jnp.bool_(True), jnp.int32(-1)))
    x_ref[...] = y[:x_ref.shape[1]][None]
    st_ref[...] = jnp.full(st_ref.shape, jnp.where(ok, jnp.int32(-1), fail),
                           jnp.int32)


@functools.partial(jax.jit, static_argnames=("k", "interpret"))
def gf256_solve_kernel(
    a: jax.Array, y: jax.Array, k: int, interpret: bool | None = None
) -> tuple[jax.Array, jax.Array]:
    """a (B, mp, kp) int32, y (B, mp, Lp) int32 -> (x (B, kp8, Lp), status
    (B, 1, 128)) with ``status[b, 0, 0] == -1`` iff full rank, else the
    first rank-deficient column. Grid = batch; each program reduces one
    system entirely in VMEM (the systems are k ~ tens wide — far under tile
    budgets). The status block spans the array's last two dims, which the
    TPU's (8, 128) tiling rule requires of a one-row block. Padding
    contract (``gf256_solve_batch`` arranges it): pad rows/columns are
    zero, so they are never eligible pivots and pass through elimination
    unchanged.
    """
    B, mp, kp = a.shape
    _, _, lp = y.shape
    kp8 = max(8, -(-k // 8) * 8)
    interpret = resolve_interpret(interpret)
    return pl.pallas_call(
        functools.partial(_solve_kernel, k=k),
        grid=(B,),
        in_specs=[
            pl.BlockSpec((1, mp, kp), lambda b: (b, 0, 0)),
            pl.BlockSpec((1, mp, lp), lambda b: (b, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, kp8, lp), lambda b: (b, 0, 0)),
            pl.BlockSpec((1, 1, 128), lambda b: (b, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, kp8, lp), jnp.int32),
            jax.ShapeDtypeStruct((B, 1, 128), jnp.int32),
        ],
        interpret=interpret,
    )(a, y)


# ----------------------------------------------------------------- dispatch
def gf256_solve_batch(
    coeffs: np.ndarray, symbols: np.ndarray, backend: str | None = None,
    interpret: bool | None = None,
) -> tuple[np.ndarray, np.ndarray, np.ndarray]:
    """Batched GF(256) solve with backend dispatch.

    ``coeffs`` (B, m, k), ``symbols`` (B, m, L) uint8 -> ``(x, ok,
    fail_col)`` as in :func:`gf256_solve_np`. ``backend``: ``"numpy"``,
    ``"kernel"``, or None = auto (kernel only above
    ``SOLVE_KERNEL_MIN`` total symbol bytes — single in-sim decodes stay
    on the numpy mirror). Both backends produce identical outputs
    (``tests/test_gf256_solve.py``).
    """
    coeffs = np.asarray(coeffs, np.uint8)
    symbols = np.asarray(symbols, np.uint8)
    B, m, k = coeffs.shape
    L = symbols.shape[2]
    if backend is None:
        backend = "kernel" if B * m * L >= SOLVE_KERNEL_MIN else "numpy"
    if backend == "numpy":
        return gf256_solve_np(coeffs, symbols)
    if backend != "kernel":
        raise ValueError(f"unknown backend {backend!r}")
    mp = -(-m // 8) * 8
    kp = -(-k // 128) * 128
    lp = -(-L // 128) * 128
    a = np.zeros((B, mp, kp), np.int32)
    a[:, :m, :k] = coeffs
    y = np.zeros((B, mp, lp), np.int32)
    y[:, :m, :L] = symbols
    x, st = gf256_solve_kernel(jnp.asarray(a), jnp.asarray(y), k=k,
                               interpret=interpret)
    fail_col = np.asarray(st)[:, 0, 0].astype(np.int32)
    ok = fail_col < 0
    return (np.asarray(x)[:, :k, :L].astype(np.uint8), ok, fail_col)
