"""Scenario-engine throughput study: sampler × chunk-size × device-axis.

For each workload (a many-small-cell parameter grid and a medium Fig. 4
cell block — the same two regimes PR 1 measured) this times:

* every sampler in ``repro.core.samplers.SAMPLERS`` (exact / fast / arx),
* the best sampler with chunked dispatch,
* the best sampler sharded over local devices (only when the process has
  more than one — e.g. ``XLA_FLAGS=--xla_force_host_platform_device_count=2``),

reporting **compile time separately from steady-state run time** (the
first dispatch pays jit compile; timed runs are all warm) plus derived
``steps/s`` (batch-element time steps per second) and ``samples/s``
(binomial draws per second: 3 draws × groups per element-step — the
engine's sampler workload), so sampler improvements are directly
comparable across PRs. The per-cell numpy reference loop from
``simulation.py`` is timed once per workload as the baseline.

Emits ``results/bench/engine_speed.csv`` (full table) and
``results/bench/BENCH_engine_speed.json`` — the machine-readable
trajectory point future PRs diff against.

Device-scaling study (``python -m benchmarks.engine_speed --scaling``):
a virtual-CPU study, refused on any other platform (each child would need
the backend this process already holds). It times the grid-cells workload
at 1/2/4/8 *virtual host devices*
(``xla_force_host_platform_device_count``), each count in its own
subprocess because the flag is XLA-pre-init only. The sharded leg runs
the engine's ``devices=N`` dispatch — one jitted executable whose batch
axis splits over a ``shard_map`` mesh (``scenarios._compile_runner``).
Emits ``results/bench/engine_scaling.csv`` and
``results/bench/BENCH_scaling.json`` (the ``scaling_8dev`` trajectory
point CI gates via ``scripts/check_bench_regression.py``).

Scaling provenance: virtual host devices only parallelize across
*physical cores* — the XLA CPU backend runs one shard per device thread,
so an M-core host tops out near M× regardless of the device count, and a
1-core host measures ≈1× by construction (the committed point records
``host_cores`` so trajectory diffs stay like-for-like; near-linear
scaling is expected when cores ≥ devices, e.g. on real accelerator pods).
"""
from __future__ import annotations

import json
import os
import subprocess
import sys
import time

import jax

from benchmarks.common import RESULTS, SCALE, emit
from repro import config as CFG
from repro.core import scenarios as SC
from repro.core import simulation as S
from repro.core.samplers import SAMPLERS

SEEDS = tuple(range(8))
REPS = 3  # steady-state timing: best of REPS warm dispatches
SCALING_DEVICES = (1, 2, 4, 8)  # virtual-host-device counts, one per run


def _workloads():
    quick = SCALE == "quick"
    years = 0.5 if quick else 1.0
    # many small cells: (byz x R) grid, the scenario-sweep workload
    grid = [dict(n_objects=20 if quick else 50, k_inner=32, r_inner=r,
                 byz_fraction=f, churn_per_year=26.0, n_nodes=20_000,
                 step_hours=12.0, years=years)
            for f in (0.0, 0.1, 0.2, 0.33, 0.4, 0.5)
            for r in (64, 80, 112)]
    # medium cells: a Fig. 4 object-count x TTL block
    fig4 = [dict(n_objects=100 if quick else 400, churn_per_year=26.0,
                 cache_ttl_hours=ttl, n_nodes=20_000, step_hours=12.0,
                 years=years)
            for ttl in (0.0, 24.0, 48.0)]
    return [("grid-18cells", grid), ("fig4-3cells", fig4)]


def _work_units(cells) -> tuple[int, int]:
    """(element-steps, binomial samples) of useful work in one dispatch."""
    steps = samples = 0
    for c in cells:
        sc = SC.make_scenario(**c)
        g = int(sc.n_objects) * int(sc.n_chunks)
        steps += int(sc.steps) * len(SEEDS)
        samples += int(sc.steps) * 3 * g * len(SEEDS)
    return steps, samples


def _time_engine(name, cells, sampler, chunk=None, devices=None):
    kw = dict(seeds=SEEDS, sampler=sampler, chunk_size=chunk,
              devices=devices)
    t0 = time.time()
    res = SC.run_grid(cells, **kw)
    t_first = time.time() - t0
    ts = []
    for _ in range(REPS):
        t0 = time.time()
        res = SC.run_grid(cells, **kw)
        ts.append(time.time() - t0)
    t = min(ts)
    steps, samples = _work_units(cells)
    lost_m, _ = SC.mean_ci(res.lost_fraction)
    return {
        "regime": name, "sampler": sampler,
        "chunk": chunk or "", "devices": devices or 1,
        "cells": len(cells), "seeds": len(SEEDS),
        "engine_s": round(t, 3),
        "compile_s": round(max(t_first - t, 0.0), 2),
        "steps_per_s": int(steps / t),
        "samples_per_s": int(samples / t),
        "mean_lost": round(float(lost_m.mean()), 4),
    }


def _time_python_loop(cells) -> float:
    t0 = time.time()
    for c in cells:
        for s in SEEDS:
            S.simulate_vault(S.SimParams(seed=s, **{
                k: v for k, v in c.items()
                if k in ("n_objects", "n_chunks", "k_outer", "k_inner",
                         "r_inner", "n_nodes", "byz_fraction",
                         "churn_per_year", "cache_ttl_hours", "step_hours",
                         "years")}))
    return time.time() - t0


def run():
    n_dev = jax.local_device_count()
    rows = []
    for name, cells in _workloads():
        t_loop = _time_python_loop(cells)
        variants = [dict(sampler=s) for s in SAMPLERS]
        variants.append(dict(sampler="arx", chunk=48))
        if n_dev > 1:
            variants.append(dict(sampler="arx", devices=n_dev))
        for v in variants:
            row = _time_engine(name, cells, **v)
            row["python_loop_s"] = round(t_loop, 2)
            row["speedup_vs_loop"] = round(t_loop / row["engine_s"], 1)
            rows.append(row)
    emit("engine_speed", rows)

    best = {}
    for name, _ in _workloads():
        cand = [r for r in rows if r["regime"] == name]
        best[name] = max(cand, key=lambda r: r["steps_per_s"])
    point = {
        "bench": "engine_speed", "scale": SCALE, "devices": n_dev,
        "headline": {k: {kk: v[kk] for kk in
                         ("sampler", "chunk", "engine_s", "compile_s",
                          "steps_per_s", "samples_per_s", "python_loop_s",
                          "speedup_vs_loop")}
                     for k, v in best.items()},
        "rows": rows,
    }
    path = RESULTS / "BENCH_engine_speed.json"
    with open(path, "w") as f:
        json.dump(point, f, indent=1)
    hb = best["grid-18cells"]
    print(f"  -> best {hb['sampler']}: {hb['engine_s']}s steady "
          f"({hb['steps_per_s']:,} steps/s, {hb['samples_per_s']:,} "
          f"samples/s; compile {hb['compile_s']}s excluded); "
          f"python loop {hb['python_loop_s']}s -> {hb['speedup_vs_loop']}x")
    return rows


# --- device-scaling study -------------------------------------------------

_CHILD = """\
import json, sys, time
ndev, reps, seeds = int(sys.argv[1]), int(sys.argv[2]), int(sys.argv[3])
import jax   # topology comes from XLA_FLAGS (repro.config.subprocess_env)
assert jax.local_device_count() >= ndev, (jax.local_device_count(), ndev)
from repro.core import scenarios as SC
cells = json.loads(sys.stdin.read())
kw = dict(seeds=range(seeds), sampler="arx",
          devices=ndev if ndev > 1 else None)
t0 = time.time()
res = SC.run_grid(cells, **kw)
t_first = time.time() - t0
ts = []
for _ in range(reps):
    t0 = time.time()
    res = SC.run_grid(cells, **kw)
    ts.append(time.time() - t0)
print("RESULT " + json.dumps({
    "t": min(ts), "t_first": t_first,
    "mean_lost": float(res.lost_fraction.mean())}))
"""


def _time_scaling_leg(cells, ndev: int) -> dict:
    """Time the grid workload at ``ndev`` virtual host devices.

    One subprocess per count: ``xla_force_host_platform_device_count``
    only takes effect before XLA initializes, so the parent process
    (whatever its own topology) cannot measure other counts in-process.
    """
    env = CFG.subprocess_env(ndev)
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    src = os.path.join(root, "src")
    env["PYTHONPATH"] = src + (
        os.pathsep + env["PYTHONPATH"] if env.get("PYTHONPATH") else "")
    proc = subprocess.run(
        [sys.executable, "-c", _CHILD, str(ndev), str(REPS),
         str(len(SEEDS))],
        input=json.dumps(cells), env=env, capture_output=True, text=True)
    if proc.returncode != 0:
        raise RuntimeError(
            f"scaling leg devices={ndev} failed:\n{proc.stderr}")
    line = next(l for l in proc.stdout.splitlines()
                if l.startswith("RESULT "))
    out = json.loads(line[len("RESULT "):])
    steps, samples = _work_units(cells)
    t = out["t"]
    return {
        "regime": "grid-18cells", "devices": ndev,
        "engine_s": round(t, 3),
        "compile_s": round(max(out["t_first"] - t, 0.0), 2),
        "steps_per_s": int(steps / t),
        "samples_per_s": int(samples / t),
        "mean_lost": round(out["mean_lost"], 4),
    }


def run_scaling():
    platform = jax.devices()[0].platform
    if platform != "cpu":
        raise SystemExit(f"--scaling is a virtual-CPU study; refusing to run "
                         f"on the {platform} backend (use JAX_PLATFORMS=cpu)")
    name, cells = _workloads()[0]  # grid-cells: 18 cells x 8 seeds = 144
    rows = []
    for ndev in SCALING_DEVICES:
        row = _time_scaling_leg(cells, ndev)
        rows.append(row)
        print(f"  devices={ndev}: {row['engine_s']}s steady "
              f"({row['steps_per_s']:,} steps/s)")
    base = rows[0]["engine_s"]
    for row in rows:
        row["speedup_vs_1dev"] = round(base / row["engine_s"], 2)
    emit("engine_scaling", rows)

    at8 = next(r for r in rows if r["devices"] == 8)
    point = {
        "bench": "scaling", "scale": SCALE,
        "host_cores": os.cpu_count(),
        "note": ("virtual host devices scale with physical cores; "
                 "speedup_vs_1dev ~= min(devices, host_cores) and is "
                 "deliberately NOT a gated metric"),
        "headline": {"scaling_8dev": {
            k: at8[k] for k in ("devices", "engine_s", "compile_s",
                                "steps_per_s", "samples_per_s",
                                "speedup_vs_1dev")}},
        "rows": rows,
    }
    path = RESULTS / "BENCH_scaling.json"
    with open(path, "w") as f:
        json.dump(point, f, indent=1)
    print(f"  -> 8-device leg: {at8['engine_s']}s "
          f"({at8['steps_per_s']:,} steps/s, "
          f"{at8['speedup_vs_1dev']}x vs 1 device on "
          f"{os.cpu_count()}-core host) -> {path}")
    return rows


if __name__ == "__main__":
    if "--scaling" in sys.argv[1:]:
        run_scaling()
    else:
        run()
