"""The reduction of the engine's spans and scopes (``perfbench/scopes.py``),
the split ``profile_cell.py`` reports and the per-layer metrics read from
it, on synthetic traces and on one recorded on a v5e."""
import os
import sys

import pytest

from perfbench import bench as B
from perfbench import profile_cell as PC
from perfbench import scopes as S
from perfbench import trace as T

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
FIXTURES = os.path.join(ROOT, "perfbench", "tests", "fixtures")
if os.path.join(ROOT, "src") not in sys.path:
    sys.path.insert(0, os.path.join(ROOT, "src"))

# a compiled program's HLO, cut to what the scope rules look at
HLO = """HloModule jit_run

%fused_computation.1 (param_0: f32[4]) -> f32[4] {
  %param_0 = f32[4]{0} parameter(0)
  ROOT %add.1 = f32[4]{0} add(%param_0, %param_0), metadata={op_name="jit(run)/while/body/closed_call/vault.serve/cond/branch_1_fun/add"}
}

%region_0 (p: (f32[4])) -> (f32[4]) {
  %p = (f32[4]{0}) parameter(0)
  %gte = f32[4]{0} get-tuple-element(%p), index=0
  %fusion.1 = f32[4]{0} fusion(%gte), kind=kLoop, calls=%fused_computation.1, metadata={op_name="jit(run)/while/body/closed_call"}
  ROOT %tuple.1 = (f32[4]{0}) tuple(%fusion.1)
}

%region_1 (q: (f32[4])) -> (f32[4]) {
  %q = (f32[4]{0}) parameter(0)
  ROOT %tuple.2 = (f32[4]{0}) tuple(%q), metadata={op_name="jit(run)/while/body/closed_call/vault.serve/cond/branch_0_fun"}
}

%body (s: (f32[4], pred[])) -> (f32[4], pred[]) {
  %s = (f32[4]{0}, pred[]) parameter(0)
  %conditional.10 = (f32[4]{0}) conditional(%pred, %t, %t), branch_computations={%region_0, %region_1}
  %fusion.2 = f32[4]{0} fusion(%x), kind=kLoop, calls=%fused_computation.1, metadata={op_name="jit(run)/while/body/closed_call/vault.churn/mul"}
  ROOT %select.3 = f32[4]{0} select(%a, %b, %c), metadata={op_name="jit(run)/while/body/closed_call/vault.merge/jit(_where)/select_n"}
}

ENTRY %main (x: f32[4]) -> f32[4] {
  %x = f32[4]{0} parameter(0)
  ROOT %while.1 = (f32[4]{0}, pred[]) while(%init), condition=%cond, body=%body, metadata={op_name="jit(run)/while"}
}
"""


def load(name, cls=S.ScopedTrace):
    with open(os.path.join(FIXTURES, name)) as fh:
        return cls.from_json(fh.read())


def test_names_match_the_engine():
    from repro.core import scenarios as SC

    assert S.GRID == SC.SPAN_GRID
    assert S.PREPARE == (SC.SPAN_BUILD, SC.SPAN_STACK, SC.SPAN_LAUNCH)
    assert S.COLLECT == (SC.SPAN_FETCH, SC.SPAN_GATHER)
    assert S.PHASES == (SC.SCOPE_CHURN, SC.SCOPE_REPAIR, SC.SCOPE_SERVE,
                        SC.SCOPE_MERGE)


def test_hlo_scopes_own_and_inherited():
    of = S.hlo_scopes(HLO)
    assert of["add.1"] == "vault.serve"
    # a fusion whose own path has no phase takes its fused computation's
    assert of["fusion.1"] == "vault.serve"
    # a conditional XLA made takes the one phase of its branches
    assert of["conditional.10"] == "vault.serve"
    assert of["fusion.2"] == "vault.churn"
    assert of["select.3"] == "vault.merge"
    # the loop calls every phase: it is under none of them
    assert "while.1" not in of and "gte" not in of


def test_phase_time_is_the_union_of_nested_ops():
    tr = load("scoped_synthetic_trace.json")
    assert tr.has_scopes
    # cond.1 holds fusion.1: 1000 a run, not 1500
    assert tr.scoped_busy_s(("vault.churn",)) == pytest.approx(2000e-9)
    assert tr.scoped_busy_s(("vault.repair",)) == pytest.approx(800e-9)
    # cond.2 holds fusion.3 and fusion.4: 2000 a run, not 3800
    assert tr.scoped_busy_s(("vault.serve",)) == pytest.approx(4000e-9)
    # no operation ran under the merge scope
    assert tr.scoped_busy_s(("vault.merge",)) == 0.0
    assert tr.scoped_busy_s(S.PHASES) == pytest.approx(6800e-9)


def test_gap_parts_sum_to_the_gap():
    tr = load("scoped_synthetic_trace.json")
    # idle between the runs: [5000, 6000] and [6500, 9000]; the copy at
    # [6000, 6500] is device time
    assert tr.gaps_between_runs("jit_run") == pytest.approx([3500e-9])
    (parts,) = tr.gap_split("jit_run")
    prepare, collect, harness, total = parts
    assert prepare == pytest.approx(1800e-9)   # build, stack, launch
    assert collect == pytest.approx(1400e-9)   # fetch, then gather
    assert harness == pytest.approx(300e-9)    # between the grid calls
    assert prepare + collect + harness == pytest.approx(total)


def test_gaps_labelled_by_innermost_span():
    tr = load("scoped_synthetic_trace.json")
    assert tr.open_span(5500) == "vault.fetch"
    assert tr.longest_gaps(4) == [
        [T.OUTSIDE, pytest.approx(7000e-9)],
        ["vault.stack", pytest.approx(2500e-9)],
        ["vault.stack", pytest.approx(1000e-9)],
        ["vault.fetch", pytest.approx(1000e-9)]]


def test_profile_split_per_step_and_per_gap():
    tr = load("scoped_synthetic_trace.json")
    split = S.split(tr, "jit_run", dispatches=2, max_steps=4)
    assert split == pytest.approx({
        "churn_step_us": 0.25, "repair_step_us": 0.1, "serve_step_us": 0.5,
        "merge_step_us": 0.0, "unscoped_step_us": 0.15,
        "gap_prepare_ms": 1.8e-3, "gap_collect_ms": 1.4e-3,
        "gap_harness_ms": 0.3e-3})
    assert PC.span_ms(tr) == pytest.approx({
        "vault.grid": 7.7e-3, "vault.build": 2.5e-4, "vault.stack": 3.5e-4,
        "vault.launch": 7e-4, "vault.fetch": 6e-3, "vault.gather": 4e-4})
    # a program without scopes or spans gives no split
    assert S.split(load("synthetic_trace.json"), "jit_run", 2, 4) == {}


def test_json_round_trip_and_trim(monkeypatch):
    tr = load("scoped_synthetic_trace.json")
    assert S.ScopedTrace.from_json(tr.to_json()) == tr
    assert load("synthetic_trace.json").scopes == {}
    assert PC.trim(tr, "jit_run") == tr  # fewer operations than are kept
    monkeypatch.setattr(PC, "HEAD_OPS", 3)
    monkeypatch.setattr(PC, "GAP_OPS", 1)
    cut = PC.trim(tr, "jit_run")
    # the first three operations, and one on each side of the gap
    assert [n for n, _, _ in cut.ops["0"]] == [
        "while.1", "cond.1", "fusion.1", "copy.6", "copy-start.9", "while.1"]
    assert cut.scopes == {"0": {"cond.1": "vault.churn",
                                "fusion.1": "vault.churn"}}
    assert cut.gap_split("jit_run") == tr.gap_split("jit_run")


# the per-layer metrics accepted before the harness read the engine's labels
ACCEPTED = ("device_idle_share", "scan_step_us", "scan_roofline_share",
            "host_gap_ms", "compiles_in_window")
# those that read them, each from a part of ``scopes.split``
SPLIT = ("churn_step_us", "repair_step_us", "serve_step_us", "merge_step_us",
         "gap_prepare_ms", "gap_collect_ms")


def _run(trace, max_steps=4):
    chips = len(trace.devices) if trace else 1
    return B.Run(setup_s=1.0, window_s=1.0, hours=1.0, dispatches=3,
                 compiles_in_window=0, chips=chips,
                 max_steps=max_steps, state_bytes_per_step=1e6,
                 peaks={"hbm_bytes_per_s": 8.19e11},
                 program_prefix="jit_run", trace=trace)


def _readings(trace, names=ACCEPTED, max_steps=4):
    run = _run(trace, max_steps)
    bench = B.Bench(ROOT)
    return {name: bench.module("metrics", name).read(run) for name in names}


# what the accepted readers read from these fixtures: the first two before
# the engine had spans or scopes, the third before the harness read them
@pytest.mark.parametrize("name, expect", [
    ("synthetic_trace.json",
     {"device_idle_share": 45.0, "scan_step_us": 0.4583333333333334,
      "scan_roofline_share": 133.2001332001332, "host_gap_ms": 0.0025,
      "compiles_in_window": 0.0}),
    ("v5e_fig6_trace_head.json",
     {"device_idle_share": 68.80601057363668,
      "scan_step_us": 83228.32108333333,
      "scan_roofline_share": 0.0014670501640645607,
      "host_gap_ms": 16.732231, "compiles_in_window": 0.0}),
    ("v5e_d1_serve_scoped.json",
     {"device_idle_share": 0.24083352858390583,
      "scan_step_us": 2674288.0707500004,
      "scan_roofline_share": 4.565705670813515e-05,
      "host_gap_ms": 12.9139005, "compiles_in_window": 0.0})])
def test_accepted_readers_read_the_same(name, expect):
    plain = _readings(load(name, T.Trace))
    scoped = _readings(load(name))
    assert plain == scoped
    assert plain == pytest.approx(expect, rel=1e-6)


def test_split_readers_read_the_split():
    """Each reader of a part of the split returns what ``scopes.split``
    (and so ``profile_cell.py``) gives, and nothing where the trace has no
    scopes or engine spans."""
    bench = B.Bench(ROOT)
    assert set(SPLIT) <= {m["name"] for m in bench.spec["per_layer"]}
    tr = load("v5e_d1_serve_scoped.json")
    split = S.split(tr, "jit_run", dispatches=3, max_steps=1460)
    assert _readings(tr, SPLIT, max_steps=1460) == {
        name: split[name] for name in SPLIT}
    assert split == pytest.approx({
        "churn_step_us": 0.09041666666666667,
        "repair_step_us": 0.09902808219178083,
        "serve_step_us": 3.221498401826484,
        "merge_step_us": 0.012664383561643837,
        "unscoped_step_us": 7323.393024657535,
        "gap_prepare_ms": 4.086912, "gap_collect_ms": 8.609763500000001,
        "gap_harness_ms": 0.19947}, rel=1e-9)
    for plain in (load("v5e_d1_serve_scoped.json", T.Trace),
                  load("synthetic_trace.json"), None):
        assert _readings(plain, SPLIT) == dict.fromkeys(SPLIT)


def test_recorded_v5e_d1_serve_trace():
    """A trace of the three-dispatch d1-serve window recorded on one v5e
    with the engine's spans and scopes: the first 600 device operations and
    200 on each side of both gaps between dispatches."""
    tr = load("v5e_d1_serve_scoped.json")
    assert tr.devices == ["0"]
    runs = tr.program_runs("0", "jit_run")
    assert len(runs) == 3
    assert tr.gaps_between_runs("jit_run") == pytest.approx(
        [0.01377799, 0.012049811])
    parts = tr.gap_split("jit_run")
    assert parts == [pytest.approx((0.004291878, 0.009267192, 0.00020115,
                                    0.01377799)),
                     pytest.approx((0.003881946, 0.007952335, 0.00019779,
                                    0.012049811))]
    for prepare, collect, harness, total in parts:
        assert prepare + collect + harness == pytest.approx(total, rel=0.01)
    # the host was fetching the last dispatch's outputs at each gap's middle
    assert [tr.open_span((e + s) // 2)
            for (_, e), (s, _) in zip(runs, runs[1:])] == ["vault.fetch"] * 2
    assert set(tr.scopes["0"].values()) == set(S.PHASES)
    assert [tr.scoped_busy_s((p,)) for p in S.PHASES] == pytest.approx(
        [0.000396025, 0.000433743, 0.014110163, 0.00005547])
    assert all(" = " not in name for name, _, _ in tr.ops["0"])


def test_program_hlo_and_spans_from_a_cpu_profile(tmp_path):
    """A real grid call under the profiler: the HLO text taken at its
    dispatch names all four phases, and the reduction keeps the engine's
    spans beside the harness's (the CPU has no device plane to scope)."""
    import glob

    import jax

    from repro.core import scenarios as SC

    cell = dict(n_objects=10, n_chunks=4, k_outer=2, k_inner=8, r_inner=20,
                n_nodes=2000, byz_fraction=0.2, churn_per_year=26.0,
                cache_ttl_hours=24.0, step_hours=12.0, steps=8,
                read_rate=50.0)

    def grid():
        SC.run_grid([cell], seeds=range(2), sampler="arx")

    with jax.profiler.trace(str(tmp_path)):
        with jax.profiler.TraceAnnotation(T.WINDOW_SPAN):
            hlo = S.program_hlo(grid)
    assert set(S.hlo_scopes(hlo).values()) == set(S.PHASES)
    (path,) = glob.glob(str(tmp_path / "plugins/profile/*/*.xplane.pb"))
    tr = S.from_xspace(path, hlo)
    assert [n for n, _, _ in sorted(tr.spans, key=lambda s: s[1])] == [
        T.WINDOW_SPAN, S.GRID, *S.PREPARE, *S.COLLECT]
    assert not tr.has_scopes
