#!/usr/bin/env python
"""Drive Vault's main path once on a TPU chip and check what comes out.

    python chip_smoke.py            # one chip: engine, protocol tick, kernels
    python chip_smoke.py --chips 4  # four chips: the sharded engine sweep only

One process, no children: a chip belongs to the process that first touches
JAX. It refuses to run, and prints no result, unless JAX reports a TPU.

One chip, three phases:

* engine — the paper's deployment (Vault, arXiv 2310.08403, Figs. 4-6):
  100K nodes, 10,000 objects x 10 chunks (100K chunk groups), outer code
  K=8, inner code K=32 / R=80, 26 failures per node-year, 1/3 Byzantine,
  48 h chunk cache, a Zipf read load, 6 h steps for one year, 8 seeds,
  through ``run_grid`` with a ``chunk_size``. It must lose no object (the
  Fig. 6 headline), repeat bit for bit, and a small cell must agree with
  the numpy reference ``simulation.simulate_vault`` within the tolerance of
  ``tests/test_scenarios.py``. ``trace_grid`` and ``targeted_grid`` run once.
* protocol — three protocol ticks of the 10K-node deployment of
  ``benchmarks/protocol_speed.py`` with the ARX VRF. The result must equal
  the same run with the pairs kernel routed to its numpy mirror, and the
  kernel must have run.
* kernels — the five Pallas kernels at the main path's widths, each
  bit-equal to its numpy mirror, each compiled (``tpu_custom_call`` in its
  HLO), none interpreted.

``--chips 4`` runs only the engine deployment at ``devices=4`` against
``devices=1`` at the same ``chunk_size``, in the same process, and
demands every field bit-identical and each dispatch's inputs split over
all four chips.

Times printed are host wall seconds, compilation included where said. The
last line of output is the JSON verdict the chip's users read:
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time

import numpy as np

ROOT = os.path.dirname(os.path.abspath(__file__))

# the paper's deployment (ROADMAP D1), one cell x 8 seeds
D1_CELL = dict(n_objects=10_000, n_chunks=10, k_outer=8, k_inner=32,
               r_inner=80, n_nodes=100_000, byz_fraction=1 / 3,
               churn_per_year=26.0, cache_ttl_hours=48.0, read_rate=1e4,
               zipf_alpha=1.1, step_hours=6.0, years=1.0)
D1_SEEDS = range(8)
D1_CHUNK = 4
# tests/test_scenarios.py's reference-equivalence cell and seed count
REF_CELL = dict(n_objects=60, n_chunks=4, k_outer=2, k_inner=8, r_inner=20,
                n_nodes=2000, byz_fraction=0.2, churn_per_year=26.0,
                step_hours=12.0, years=0.25)
REF_SEEDS = range(12)
PROTOCOL_NODES = 10_000
PROTOCOL_TICKS = 3
# kernel widths: inner code K/R, fragment bytes, solve batch and rows
K_INNER, R_INNER, PAYLOAD = 32, 80, 4096
SOLVE_BATCH, SOLVE_M = 48, 40
PRF_NODES, PRF_FRAGS = 1024, 256


def log(phase: str, msg: str) -> None:
    print(f"[{phase}] {msg}", flush=True)


def require_tpu(chips: int):
    """The chip's devices, or exit non-zero naming what is missing."""
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        sys.exit(f"chip_smoke: no TPU chip found: JAX's backend is "
                 f"{devs[0].platform!r}. This script runs on a TPU only.")
    if len(devs) < chips:
        sys.exit(f"chip_smoke: --chips {chips} needs {chips} TPU chips, "
                 f"JAX sees {len(devs)}")
    return devs


def expect(ok, what) -> None:
    """Fail the run unless ``ok`` (an assert that ``python -O`` keeps)."""
    if not ok:
        raise SystemExit(f"chip_smoke: FAILED: {what}")


def _close(a, b, rel=0.1, abs_tol=0.02):
    # tests/test_scenarios.py's tolerance
    return abs(a - b) <= rel * max(abs(a), abs(b)) + abs_tol


def _timed(fn, *args, **kw):
    t0 = time.perf_counter()
    out = fn(*args, **kw)
    return out, time.perf_counter() - t0


def _bit_equal(a, b) -> list[str]:
    """Names of the fields of two results that differ in any bit."""
    return [name for name, x, y in zip(a._fields, a, b)
            if not np.array_equal(np.asarray(x), np.asarray(y))]


# ------------------------------------------------------------------ engine
def engine_d1(cell=D1_CELL, seeds=D1_SEEDS, chunk_size=D1_CHUNK):
    from repro.core import scenarios as SC

    res, t_first = _timed(SC.run_grid, [cell], seeds=seeds, sampler="arx",
                          chunk_size=chunk_size)
    again, t_warm = _timed(SC.run_grid, [cell], seeds=seeds, sampler="arx",
                           chunk_size=chunk_size)
    n = len(seeds)
    groups = cell["n_objects"] * cell["n_chunks"]
    steps = SC.make_scenario(**cell).steps
    log("engine", f"D1: {groups} chunk groups x {n} seeds x {steps} steps; "
        f"host wall s: {t_first:.3f} first call (compile included), "
        f"{t_warm:.3f} warm")
    for name, x in zip(res._fields, res):
        x = np.asarray(x)
        expect(x.shape[:2] == (1, n), (name, x.shape))
        expect(np.isfinite(x).all(), name)
    diff = _bit_equal(res, again)
    expect(not diff, f"D1 not deterministic across calls: {diff}")
    lost = np.asarray(res.lost_objects)[0]
    log("engine", f"lost objects per seed {lost.tolist()}, repairs mean "
        f"{float(np.mean(res.repairs)):.1f}, cache hits mean "
        f"{float(np.mean(res.cache_hits)):.1f}, reads issued mean "
        f"{float(np.mean(res.reads_issued)):.1f}, failed reads "
        f"{int(np.sum(res.reads_failed))}")
    expect((lost == 0).all(), "(32,80) must lose nothing at 1/3 Byzantine")
    expect((np.asarray(res.repairs) > 0).all(), "no repair ran")
    expect((np.asarray(res.cache_hits) > 0).all(), "the cache never served")
    expect((np.asarray(res.reads_issued) > 0).all(), "no read was served")
    return res


def engine_reference(cell=REF_CELL, seeds=REF_SEEDS):
    """Small cell vs the numpy reference, plus trace_grid / targeted_grid."""
    from repro.core import scenarios as SC
    from repro.core import simulation as S

    n = len(seeds)
    res = SC.run_grid([cell], seeds=seeds)
    p = S.SimParams(**cell)
    ref = [S.simulate_vault(dataclasses.replace(p, seed=s)) for s in seeds]
    eng_t = np.asarray(res.repair_traffic_units[0], np.float64)
    ref_t = np.array([r.repair_traffic_units for r in ref])
    se = np.sqrt(eng_t.var() / n + ref_t.var() / n)
    eng_lost = float(np.mean(res.lost_fraction[0]))
    ref_lost = float(np.mean([r.lost_fraction for r in ref]))
    eng_h = float(np.mean(res.final_honest_mean[0]))
    ref_h = float(np.mean([r.final_honest_mean for r in ref]))
    log("engine", f"reference cell: traffic {eng_t.mean():.3f} vs numpy "
        f"{ref_t.mean():.3f}, lost {eng_lost:.4f} vs {ref_lost:.4f}, final "
        f"honest {eng_h:.3f} vs {ref_h:.3f}")
    expect(abs(eng_t.mean() - ref_t.mean()) < 5 * se + 0.02 * ref_t.mean(),
           "repair traffic off the numpy reference")
    expect(_close(eng_lost, ref_lost), "lost fraction off the reference")
    expect(_close(eng_h, ref_h, rel=0.05, abs_tol=0.5),
           "final honest mean off the reference")

    tr = SC.trace_grid([dict(k_inner=32, r_inner=80, byz_fraction=1 / 3,
                             churn_per_year=26.0, step_hours=6.0,
                             years=1.0)], seeds=range(8), sampler="arx")
    expect(tr.shape[:2] == (1, 8) and np.isfinite(tr).all(), tr.shape)
    log("engine", f"trace_grid {tr.shape}: honest fragments min "
        f"{float(tr.min()):.1f}, mean {float(tr.mean()):.2f}")
    expect(tr.min() >= 32, "a (32,80) group fell below k in Fig. 5's setting")

    chunks = (10, 12, 14)
    tg = SC.targeted_grid([dict(n_objects=300, n_chunks=c, k_outer=8,
                                byz_fraction=1 / 3, attack_frac=0.2,
                                n_nodes=100_000) for c in chunks],
                          seeds=range(8))
    means = np.asarray(tg).mean(axis=1)
    refs = [np.mean([S.targeted_attack_vault(
        S.SimParams(n_objects=300, n_chunks=c, byz_fraction=1 / 3), 0.2,
        seed=s) for s in range(8)]) for c in chunks]
    log("engine", f"targeted_grid lost fraction {means.round(4).tolist()} "
        f"vs numpy {np.round(refs, 4).tolist()}")
    for m, r in zip(means, refs):
        expect(_close(float(m), float(r), abs_tol=0.05),
               f"targeted_grid {m} vs numpy {r}")
    expect(means[2] <= means[1] <= means[0],
           "more chunks must not lose more under a targeted attack")


def engine_sharded(chips: int, cell=D1_CELL, seeds=D1_SEEDS,
                   chunk_size=D1_CHUNK):
    """The D1 sweep at ``devices=chips`` against ``devices=1``, both at
    ``chunk_size``. Each sharded dispatch's compiled input placement is
    read back: every input leaf must be split along its batch axis, one
    slice per chip, not held whole by one chip."""
    import jax

    from repro.core import scenarios as SC

    splits = []
    dispatch = SC._dispatch

    def recording(runner, batch):
        out = dispatch(runner, batch)
        placed = runner.lower(batch).compile().input_shardings[0]
        splits.append({(len(s.device_set), s.shard_shape(np.shape(x))[0],
                        np.shape(x)[0]) for s, x in zip(
                            jax.tree_util.tree_leaves(placed),
                            jax.tree_util.tree_leaves(batch))})
        return out

    one, t_one = _timed(SC.run_grid, [cell], seeds=seeds, sampler="arx",
                        chunk_size=chunk_size)
    SC._dispatch = recording
    try:
        many, t_many = _timed(SC.run_grid, [cell], seeds=seeds,
                              sampler="arx", chunk_size=chunk_size,
                              devices=chips)
    finally:
        SC._dispatch = dispatch
    log("sharded", f"D1 chunk {chunk_size}, host wall s (compile "
        f"included): devices=1 {t_one:.3f}, devices={chips} {t_many:.3f}; "
        f"{len(splits)} dispatches, inputs placed as (chips, batch rows "
        f"per chip, batch rows): {splits}")
    per_chip = chunk_size // chips
    expect(splits and all(s == {(chips, per_chip, chunk_size)}
                          for s in splits),
           f"inputs not split over {chips} of {jax.device_count()} chips")
    diff = _bit_equal(one, many)
    expect(not diff, f"devices={chips} diverges from devices=1 in {diff}")
    log("sharded", f"all {len(one._fields)} fields bit-identical to "
        f"devices=1; lost objects per seed "
        f"{np.asarray(many.lost_objects)[0].tolist()}")


# ---------------------------------------------------------------- protocol
def _protocol_run(p, pairs_kernel_min: int):
    """One protocol run with the pairs kernel taking every PRF batch of at
    least ``pairs_kernel_min`` pairs; returns (result, host wall s, kernel
    dispatches)."""
    from benchmarks import protocol_speed as PSB
    from repro.core import protocol_sim as PS
    from repro.kernels import prf_select as PRF

    calls = []
    kernel, threshold = PRF._prf_pairs_call, PRF.PAIRS_KERNEL_MIN

    def counted(*args, **kw):
        calls.append(1)
        return kernel(*args, **kw)

    PSB._clear_shared_caches()
    PRF._prf_pairs_call, PRF.PAIRS_KERNEL_MIN = counted, pairs_kernel_min
    try:
        res, wall = _timed(PS.run_protocol, p)
    finally:
        PRF._prf_pairs_call, PRF.PAIRS_KERNEL_MIN = kernel, threshold
    return res, wall, len(calls)


def protocol(n_nodes=PROTOCOL_NODES, ticks=PROTOCOL_TICKS):
    """Protocol ticks with every PRF batch on the chip, then with every
    batch on the numpy mirror ``arx_mix_np``.

    The Locate() rounds of a tick evaluate at most a few hundred pairs per
    batch, under the pairs kernel's dispatch threshold, so an unsteered
    run never reaches the kernel; this phase steers both runs itself."""
    from benchmarks import protocol_speed as PSB

    p = dataclasses.replace(PSB._base_params(n_nodes), vrf="arx",
                            steps=ticks)
    on_chip, t_chip, calls = _protocol_run(p, 1)
    mirror, t_np, mirror_calls = _protocol_run(p, sys.maxsize)
    log("protocol", f"{n_nodes} nodes, {on_chip.n_groups} groups, {ticks} "
        f"ticks: {calls} pairs-kernel dispatches; host wall s {t_chip:.3f} "
        f"on the kernel, {t_np:.3f} on the numpy mirror; repairs "
        f"{on_chip.repairs}, lost objects {on_chip.lost_objects}")
    expect(calls and not mirror_calls, (calls, mirror_calls))
    diff = _bit_equal(on_chip, mirror)
    expect(not diff, f"kernel run differs from numpy-mirror run in {diff}")


# ----------------------------------------------------------------- kernels
def _hlo_has_kernel(jitted, *args, **kw) -> bool:
    return "tpu_custom_call" in jitted.lower(*args, **kw).compile().as_text()


def kernels(k=K_INNER, r=R_INNER, payload=PAYLOAD, batch=SOLVE_BATCH,
            m=SOLVE_M, nodes=PRF_NODES, frags=PRF_FRAGS):
    import jax.numpy as jnp

    from repro.core import rateless as RL
    from repro.kernels import ops
    from repro.kernels.backend import default_interpret
    from repro.kernels.gf256_encode import gf256_encode_kernel
    from repro.kernels.gf256_solve import (gf256_solve_batch,
                                           gf256_solve_kernel, gf256_solve_np)
    from repro.kernels.gf2_encode import gf2_encode_kernel
    from repro.kernels.prf_select import (PAIRS_KERNEL_MIN, _prf_pairs_call,
                                          arx_mix_np, prf_select_kernel,
                                          prf_select_pairs)

    interp = default_interpret()   # what every kernel entry point uses
    rng = np.random.default_rng(0)
    results = {}

    def check(name, got, want, compiled):
        same = all(np.array_equal(g, w) for g, w in zip(got, want))
        results[name] = (same, compiled)
        log("kernels", f"{name}: bit-equal to mirror {same}, "
            f"tpu_custom_call in HLO {compiled}")

    blocks = rng.integers(0, 256, (k, payload), dtype=np.uint8)
    code = RL.RLNC(k=k, seed=b"chip-smoke")
    t0 = time.perf_counter()
    got = code.encode(blocks, range(r), backend="kernel")
    want = code.encode(blocks, range(r), backend="numpy")
    c = jnp.zeros((r, k), jnp.int32)
    d = jnp.zeros((k, payload), jnp.int32)
    check(f"gf256_encode ({r}x{k} @ {k}x{payload})", [got], [want],
          _hlo_has_kernel(gf256_encode_kernel, c, d, tile_r=8, tile_l=512,
                          interpret=interp))

    lt = RL.LTCode(k=k, seed=b"chip-smoke")
    got = lt.encode(blocks, range(r), backend="kernel")
    want = lt.encode(blocks, range(r), backend="numpy")
    d = jnp.zeros((k, payload // 4), jnp.int32)
    check(f"gf2_encode ({r}x{k} @ {k}x{payload // 4} words)", [got], [want],
          _hlo_has_kernel(gf2_encode_kernel, c, d, tile_r=8, tile_w=512,
                          interpret=interp))

    coeffs = np.stack([code.coeff_matrix(range(i, i + m))
                       for i in range(batch)])
    coeffs[1, :, 3] = 0                      # rank-deficient at column 3
    coeffs[2, :, k - 1] = coeffs[2, :, 0]    # deficient at the last column
    symbols = rng.integers(0, 256, (batch, m, payload), dtype=np.uint8)
    x, ok, fail = gf256_solve_batch(coeffs, symbols, backend="kernel")
    x_np, ok_np, fail_np = gf256_solve_np(coeffs, symbols)
    expect(0 < ok.sum() < batch, ok)
    mp = -(-m // 8) * 8
    check(f"gf256_solve ({batch} systems, k={k}, m={m}, L={payload}; "
          f"{int(ok.sum())} full rank)",
          [ok, fail, x[ok]], [ok_np, fail_np, x_np[ok_np]],
          _hlo_has_kernel(gf256_solve_kernel,
                          jnp.zeros((batch, mp, 128), jnp.int32),
                          jnp.zeros((batch, mp, payload), jnp.int32), k=k,
                          interpret=interp))

    pairs = 2 * PAIRS_KERNEL_MIN + 5
    tags = rng.integers(-2**31, 2**31, (pairs, 2), dtype=np.int64)
    fh = rng.integers(-2**31, 2**31, (pairs, 2), dtype=np.int64)
    tags, fh = tags.astype(np.int32), fh.astype(np.int32)
    got = prf_select_pairs(tags, fh)
    want = arx_mix_np(*(a.view(np.uint32) for a in
                        (tags[:, 0], tags[:, 1], fh[:, 0], fh[:, 1])))
    plane = jnp.zeros((-(-pairs // 1024) * 8, 128), jnp.int32)
    check(f"prf_select_pairs ({pairs} pairs)", [got.view(np.uint32)],
          [want], _hlo_has_kernel(_prf_pairs_call, plane, plane, plane,
                                  plane, interpret=interp))

    got = ops.prf_select(tags[:nodes], fh[:frags])
    want = arx_mix_np(tags[:nodes, 0:1].view(np.uint32),
                      tags[:nodes, 1:2].view(np.uint32),
                      fh[None, :frags, 0].view(np.uint32),
                      fh[None, :frags, 1].view(np.uint32))
    check(f"prf_select ({nodes} nodes x {frags} fragments)",
          [got.view(np.uint32)], [want],
          _hlo_has_kernel(prf_select_kernel,
                          jnp.zeros((nodes, 2), jnp.int32),
                          jnp.zeros((frags, 2), jnp.int32),
                          interpret=interp))
    log("kernels", f"host wall s {time.perf_counter() - t0:.3f} for all "
        "five (compile included)")
    bad = [name for name, (same, compiled) in results.items()
           if not (same and compiled)]
    expect(not bad, f"kernels not bit-equal or not compiled: {bad}")


# -------------------------------------------------------------------- main
def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="4: only the sharded engine sweep, on four chips")
    args = ap.parse_args(argv)
    devs = require_tpu(args.chips)

    sys.path[:0] = [os.path.join(ROOT, "src"), ROOT]
    from repro import config as CFG

    cache = CFG.apply_cache()
    print(f"device: {devs[0].device_kind} x {len(devs)}; compile cache "
          f"{cache}", flush=True)
    if args.chips > 1:
        engine_sharded(args.chips)
    else:
        engine_d1()
        engine_reference()
        protocol()
        kernels()
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": args.chips}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
