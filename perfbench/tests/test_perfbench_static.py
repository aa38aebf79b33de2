"""What can be checked without running a cell: that every file the benchmark
names is found by name, the contract's limits on ``BENCHMARK.json``, the
peaks table, the least-bytes arithmetic, the seed derivation, and that a run
on a machine without a chip prints no result."""
import json
import os
import re
import subprocess
import sys

import pytest

from perfbench import bench as B
from perfbench import check as C

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
SRC = os.path.join(ROOT, "src")
if SRC not in sys.path:
    sys.path.insert(0, SRC)

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


@pytest.fixture(scope="module")
def bench():
    return B.Bench(ROOT)


def test_benchmark_json_keys_and_names(bench):
    spec = bench.spec
    assert set(spec) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    assert spec["command"] == ["python3", "perfbench/run.py"]
    assert 1 <= spec["run_seconds"] <= 51
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) <= 64 * 1024
    names = [e["name"] for key in ("configs", "workloads", "end_to_end",
                                   "per_layer") for e in spec[key]]
    assert all(NAME.match(n) for n in names)
    assert len(set(names)) == len(names)
    for c in spec["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
    for w in spec["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert w["chips"] in (1, 4) and len(w["why"]) <= 200
    pairs = [(w["config"], w["traffic"]) for w in spec["workloads"]]
    assert len(set(pairs)) == len(pairs)
    assert sum(w["chips"] == 4 for w in spec["workloads"]) <= max(
        1, len(spec["workloads"]) // 2)
    setup = [m for m in spec["end_to_end"] if m["name"] == "setup_s"]
    assert setup and setup[0]["bound"] <= 0.25
    for m in spec["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    for m in spec["per_layer"]:
        assert m["moves"] in {e["name"] for e in spec["end_to_end"]}
        assert {"name", "unit", "better", "source", "layer", "moves"} <= set(m)
        assert set(m) <= {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
    # a full check of 24 cells fits its 43,200 s
    runs = 2 + 14 * 24
    assert runs * (spec["run_seconds"] + 60) + 24 * 180 + 1200 <= 43200


def test_every_file_is_found_by_name(bench):
    for w in bench.spec["workloads"]:
        cfg = bench.config(w["config"])
        traffic = bench.traffic(w["traffic"])
        chk = bench.check(w["name"])
        assert int(traffic.get("devices", 1)) == w["chips"]
        assert bench.module("runners", cfg["runner"]).Runner
        assert bench.module("references", cfg["reference"]).simulate_all
        assert chk["numbers"] and chk["cells_sampled"] >= 1
    cells = {w["name"] for w in bench.spec["workloads"]}
    for m in bench.spec["end_to_end"] + bench.spec["per_layer"]:
        assert callable(bench.module("metrics", m["name"]).read)
        # a metric scoped to cells names cells of BENCHMARK.json; an
        # end-to-end metric is reported by every cell
        if "workloads" in m:
            assert m in bench.spec["per_layer"]
            assert m["workloads"] and set(m["workloads"]) <= cells
    for cell in cells:
        assert "setup_s" in [m["name"] for m in bench.metrics(False, cell)]
        assert len(bench.metrics(False, cell)) >= 2
        assert bench.metrics(True, cell)


def test_configs_state_source_cuts_and_layout(bench):
    for entry in bench.spec["configs"]:
        cfg = bench.config(entry["name"])
        assert cfg["name"] == entry["name"] and cfg["source"] == entry["source"]
        assert sorted(cfg["reduced"]) == sorted(entry["reduced"])
        assert cfg["assumed"] and cfg["guarantees"] and cfg["chips"]
        assert cfg["precision"].startswith("float32")


def test_peaks_table_is_keyed_by_device_kind(bench):
    peaks = bench.peaks()
    v5e = peaks["TPU v5 lite"]
    assert v5e["hbm_bytes_per_s"] == 819e9 and v5e["hbm_bytes"] == 16e9
    assert "Google Cloud" in v5e["source"]
    with pytest.raises(B.NoChip):  # the CPU is never a device to measure
        B.require_chips(1, peaks)


def test_least_state_bytes_at_d1_and_fig6(bench):
    engine = bench.module("runners", "engine")
    d1 = engine.Runner(bench.config("vault-d1"), bench.traffic("zipf-reads"))
    # 100,000 groups x 4 seeds x 2 (read and write) x (4 + 4 + 1 + 4 + 4) B
    assert d1.state_bytes_per_step([0], [1, 2, 3, 4]) == 100_000 * 4 * 2 * 17
    fig6 = engine.Runner(bench.config("vault-fig6"), bench.traffic("sweep"))
    # 10,000 groups x 8 points x 8 seeds x 2 x (4 + 4 + 1) B, no cache state
    assert fig6.state_bytes_per_step(fig6.plan(0), list(range(8))) == (
        10_000 * 64 * 2 * 9)
    # at 819 GB/s a D1 step moves its least bytes in about 17 us
    assert 13.6e6 / 819e9 == pytest.approx(16.6e-6, rel=0.01)


def test_work_accounting_counts_asked_elements_only(bench):
    engine = bench.module("runners", "engine")
    d1 = engine.Runner(bench.config("vault-d1"), bench.traffic("zipf-reads"))
    year = 1460 * 6.0
    assert d1.max_steps == 1460
    assert d1.hours([0], [1, 2, 3, 4]) == 4 * year
    # three seeds in a chunk of four: the padding replica is no work asked for
    assert d1.hours([0], [1, 2, 3]) == 3 * year
    fig6 = engine.Runner(bench.config("vault-fig6"), bench.traffic("sweep"))
    assert len(fig6.groups) == 3 and fig6.chunk == 64
    assert [fig6.plan(r) for r in (0, 3)] == [list(range(8))] * 2
    assert fig6.hours(fig6.plan(1), list(range(8))) == 64 * year


def test_seeds_are_distinct_positive_int32_from_large_seeds():
    seeds = C.seeds_for(2**31 + 12345, 1, 0, 64)
    assert len(set(seeds)) == 64 and all(0 <= s < 2**31 for s in seeds)
    assert seeds == C.seeds_for(2**31 + 12345, 1, 0, 64)
    assert seeds != C.seeds_for(2**31 + 12345, 1, 1, 64)
    assert C.sample_cells(7, [3, 1, 3, 2], 2) == C.sample_cells(7, [1, 2, 3], 2)


def test_no_chip_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable, os.path.join(ROOT, "perfbench", "run.py"),
         "--workload", "d1-serve", "--seed", "1", "--seconds", "1",
         "--trace", "0"], capture_output=True, text=True, env=env,
        timeout=120, cwd=ROOT)
    assert out.returncode != 0
    assert out.stdout == ""
    assert "no accelerator" in out.stderr


def test_losing_points_are_held_to_their_own_limit():
    import numpy as np

    def res(repairs, live):
        return {"repairs": np.float64(repairs),
                "alive_frac_trace": np.array([1.0, live])}

    refs = {0: res(100.0, 1.0), 1: res(100.0, 0.5)}
    numbers = {"repairs": {"limit": 0.05}, "repairs.losing": {"limit": 0.2}}
    elements = [{"cell": 0, "seed": 1, "result": res(104.0, 1.0)},
                {"cell": 1, "seed": 1, "result": res(110.0, 0.5)}]
    values, failed = C.compare(elements, refs, numbers, losing_share=0.01)
    assert values == {"repairs": pytest.approx(0.04),
                      "repairs.losing": pytest.approx(0.1)}
    assert failed == {}
    # without the split every point is held to the one limit
    values, failed = C.compare(elements, refs, {"repairs": {"limit": 0.05}})
    assert values["repairs"] == pytest.approx(0.1) and failed == {1: ["repairs"]}
