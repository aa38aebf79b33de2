"""Whole runs of a small cell on the CPU, past the harness's look for a chip.

A dummy cell (a D1-like deployment of 200 objects for 400 steps, with reads)
is added to a copy of the benchmark as files of its own, and runs without an
edit to the harness. Then the timed path is broken underneath, one fault at a
time, and the check has to come out false; so has the lower-precision
control (the reference in bfloat16 in the program's place).
"""
import json
import os
import shutil
import sys
import time

import ml_dtypes
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
SRC = os.path.join(ROOT, "src")
if SRC not in sys.path:
    sys.path.insert(0, SRC)

from perfbench import bench as B  # noqa: E402
from perfbench import faults as F  # noqa: E402

SEED = 2**31 + 4321
CELL = "tiny-serve"
CONFIG = {
    "name": "vault-tiny", "source": "test deployment", "runner": "engine",
    "reference": "vault_numpy", "sampler": "arx", "precision": "float32",
    "deployment": {"n_nodes": 100000, "n_objects": 200, "n_chunks": 10,
                   "k_outer": 8, "k_inner": 32, "r_inner": 80,
                   "churn_per_year": 26.0, "byz_fraction": 0.4,
                   "cache_ttl_hours": 48.0, "step_hours": 6.0, "steps": 400},
    "grid": [{}], "reduced": {}, "assumed": {}, "guarantees": [], "chips": ""}
TRAFFIC = {"read_rate": 100.0, "zipf_alpha": 1.1, "cells_per_dispatch": 1,
           "seeds_per_dispatch": 2, "devices": 1}
# limits for this small cell, wide enough for its sampling noise (about
# 1e-3 on the counts; at 0.4 Byzantine groups die and some reads fail) and
# far below what each fault below reads
CHECK = {"cells_sampled": 1, "numbers": {
    "repairs": {"limit": 0.02}, "repair_traffic_units": {"limit": 0.02},
    "cache_hits": {"limit": 0.02}, "final_honest_mean": {"limit": 0.03},
    "alive_frac_trace": {"limit": 0.02}, "reads_issued": {"limit": 1e-6},
    "reads_hit": {"scale": "reads_issued", "limit": 0.4},
    "reads_miss": {"scale": "reads_issued", "limit": 0.05},
    "served_traffic_units": {"scale": "reads_issued", "limit": 0.05},
    "repeated_results": {"limit": 0, "varying": "alive_frac_trace"}}}


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    """A copy of the benchmark with one dummy cell added as files."""
    base = tmp_path_factory.mktemp("bench")
    shutil.copytree(os.path.join(ROOT, "perfbench"), base / "perfbench",
                    ignore=shutil.ignore_patterns("tests", "__pycache__"))
    with open(os.path.join(ROOT, "BENCHMARK.json")) as fh:
        spec = json.load(fh)
    spec["configs"].append({"name": "vault-tiny", "source": "test",
                            "file": "perfbench/configs/vault-tiny.json",
                            "reduced": [], "why": "test"})
    spec["workloads"].append({"name": CELL, "config": "vault-tiny",
                              "traffic": "tiny-reads", "chips": 1,
                              "why": "test"})
    (base / "BENCHMARK.json").write_text(json.dumps(spec))
    (base / "perfbench/configs/vault-tiny.json").write_text(json.dumps(CONFIG))
    (base / "perfbench/traffic/tiny-reads.json").write_text(json.dumps(TRAFFIC))
    (base / f"perfbench/checks/{CELL}.json").write_text(json.dumps(CHECK))
    return str(base)


def run(root, traced=False, seconds=0.3):
    import jax

    return B.execute(B.Bench(root), CELL, SEED, seconds, traced,
                     jax.devices()[:1], time.perf_counter())


@pytest.fixture
def engine(monkeypatch):
    """Patch ``run_grid`` with a wrapper of the real one."""
    from repro.core import scenarios

    real = scenarios.run_grid

    def patch(wrap):
        monkeypatch.setattr(scenarios, "run_grid",
                            lambda cells, **kw: wrap(real, cells, **kw))
    return patch


def test_dummy_cell_runs_and_is_correct(root):
    line, report = run(root)
    assert list(line) == ["correct", "attempted", "failed", "metrics",
                          "device", "compared"]
    assert line["correct"], report
    assert line["failed"] == 0 and line["attempted"] >= 2
    assert set(line["metrics"]) == {"sim_hours_per_s", "setup_s"}
    assert line["metrics"]["sim_hours_per_s"]["unit"] == "h/s"
    assert set(line["device"]) == {"platform", "kind", "count",
                                   "memory_peak_bytes"}
    assert list(line["compared"]) == list(CHECK["numbers"])
    assert report[-1].startswith("compared repeated_results: 0 limit 0")


def test_traced_line_has_breakdown(root):
    line, _ = run(root, traced=True)
    assert list(line) == ["correct", "attempted", "failed", "metrics",
                          "device", "breakdown", "compared"]
    assert line["correct"]
    assert {"busy_s", "window_s"} <= set(line["device"])
    assert set(line["breakdown"]) == {"device_ops", "idle_gaps"}
    # the CPU has no device plane: device readers find nothing and say so
    assert set(line["metrics"]) == {"compiles_in_window"}
    assert line["metrics"]["compiles_in_window"]["value"] == 0


def test_listed_metrics_stay_out_of_other_cells(root, monkeypatch):
    """A per-layer metric that lists its cells is not in the dummy cell's
    line, and its reader is never loaded there; every unlisted one is."""
    bench = B.Bench(root)
    per_layer = bench.spec["per_layer"]
    listed = {m["name"] for m in per_layer if "workloads" in m}
    assert listed
    assert [m["name"] for m in bench.metrics(True, CELL)] == [
        m["name"] for m in per_layer if m["name"] not in listed]
    assert bench.metrics(True, "d1-serve") == per_layer
    loaded, module = [], B.Bench.module

    def recording(self, kind, name):
        if kind == "metrics":
            loaded.append(name)
        return module(self, kind, name)

    monkeypatch.setattr(B.Bench, "module", recording)
    line, _ = run(root, traced=True)
    assert line["correct"]
    assert set(loaded) == {m["name"] for m in per_layer} - listed
    assert not listed & set(line["metrics"])


class IdleRunner:
    """A runner of no work."""

    seeds_per_dispatch = 1

    def __init__(self):
        self.ticks = 0

    def plan(self, rep):
        return [0]

    def dispatch(self, cells, seeds):
        self.ticks += 1
        return [{}]

    def hours(self, cells, seeds):
        return 1.0

    def state_bytes_per_step(self, cells, seeds):
        return 0


class CountingRunner(IdleRunner):
    """One that offers ``counters()``."""

    def counters(self):
        return {"ticks": self.ticks, "set_up": 7}


def test_window_keeps_the_counters_difference():
    runner = CountingRunner()
    runner.dispatch([0], [1])  # a warm-up, before the window
    win = B.measure(runner, SEED, 0.0, False, min_dispatches=3)
    assert win.dispatches == 3
    assert win.counters == {"ticks": 3, "set_up": 0}
    # a runner without counters() leaves them out
    assert B.measure(IdleRunner(), SEED, 0.0, False).counters is None


def test_fault_state_unchanged(root, engine):
    engine(F.state_unchanged)
    line, _ = run(root)
    assert not line["correct"]
    assert line["compared"]["repairs"]["value"] == 1.0


def test_fault_half_the_batch_left_out(root, engine):
    engine(F.half_batch)
    line, _ = run(root)
    assert not line["correct"]
    assert line["compared"]["repeated_results"]["value"] >= 1


def test_fault_answer_altered(root, engine):
    engine(F.traffic_altered)
    line, _ = run(root)
    assert not line["correct"]
    assert line["compared"]["repair_traffic_units"]["value"] > 0.05


def test_fault_hit_and_degraded_swapped(root, engine):
    engine(F.hit_degraded_swapped)
    line, _ = run(root)
    assert not line["correct"]
    assert line["compared"]["reads_hit"]["value"] > 0.4


def test_plant_takes_the_fault_out_again():
    from repro.core import scenarios

    real = scenarios.run_grid
    unplant = F.plant("half-batch")
    assert scenarios.run_grid is not real
    unplant()
    assert scenarios.run_grid is real


@pytest.mark.parametrize("ftype, sound", [(np.float64, True),
                                          (ml_dtypes.bfloat16, False)])
def test_reference_as_program(root, ftype, sound):
    """The reference in the program's place passes at its own precision and
    fails in bfloat16, the precision below the configuration's float32."""
    bench = B.Bench(root)
    runner = bench.module("runners", "engine").Runner(CONFIG, TRAFFIC)
    ref = bench.module("references", "vault_numpy")
    elements = [{"cell": 0, "seed": 1, "result": {}}]
    values, failed, chk = B.judge(bench, CELL, runner, ref, elements, SEED,
                                  ftype=ftype, stand_in=True)
    numbers = chk["numbers"]
    ok = all(values[n] <= spec["limit"] for n, spec in numbers.items())
    assert ok == sound, values
    assert bool(failed) != sound
