"""The trace reduction, on a synthetic trace and on one recorded on a v5e."""
import os

import pytest

from perfbench import trace as T

FIXTURES = os.path.join(os.path.dirname(__file__), "fixtures")


def load(name):
    with open(os.path.join(FIXTURES, name)) as fh:
        return T.Trace.from_json(fh.read())


def test_union_and_complement():
    assert T.union([(5, 7), (1, 3), (2, 4), (7, 8)]) == [(1, 4), (5, 8)]
    assert T.complement([(2, 3), (5, 9)], 0, 10) == [(0, 2), (3, 5), (9, 10)]
    assert T.complement([], 0, 10) == [(0, 10)]


def test_busy_and_idle_share():
    tr = load("synthetic_trace.json")
    assert tr.window_s == pytest.approx(1e-5)
    # device 0: [1000, 4000] and [6000, 7000]; the op before the window is
    # clipped away. Device 1: [1000, 5000] and [8000, 11000] (clipped).
    assert tr.busy_intervals("0") == [(1000, 4000), (6000, 7000)]
    assert tr.busy_intervals("1") == [(1000, 5000), (8000, 11000)]
    assert tr.busy_s() == pytest.approx((4000 + 7000) / 2 * 1e-9)


def test_program_time_and_gaps_between_runs():
    tr = load("synthetic_trace.json")
    assert tr.program_runs("0", "jit_run") == [(1000, 4000), (6000, 7000)]
    # only the runs named jit_run count; averaged over the two devices
    assert tr.program_busy_s("jit_run") == pytest.approx((4000 + 7000) / 2 * 1e-9)
    gaps = tr.gaps_between_runs("jit_run")
    assert sorted(gaps) == pytest.approx([2000e-9, 3000e-9])


def test_idle_gaps_labelled_by_open_span():
    tr = load("synthetic_trace.json")
    assert tr.idle_gaps("0") == [(4000, 6000), (7000, 11000)]
    assert tr.open_span(5000) == "perfbench.dispatch"
    assert tr.open_span(9000) == T.OUTSIDE
    longest = tr.longest_gaps(3)
    assert longest[0] == [T.OUTSIDE, pytest.approx(4000e-9)]
    # device 1's gap [5000, 8000] is inside the second dispatch span
    assert longest[1:] == [["perfbench.dispatch", pytest.approx(3000e-9)],
                           ["perfbench.dispatch", pytest.approx(2000e-9)]]


def test_top_ops_sum_runs_within_the_window():
    tr = load("synthetic_trace.json")
    top = dict((n, s) for n, s in tr.top_ops())
    # fusion.1: 2000 on device 0 (its run before the window is dropped)
    # plus 4000 on device 1, averaged over 2 devices
    assert top["fusion.1"] == pytest.approx(3000e-9)
    assert top["copy.3"] == pytest.approx((1000 + 3000) / 2 * 1e-9)
    assert list(top)[0] == "fusion.1"


def test_op_name_drops_hlo_text():
    assert T.op_name("%fusion.466 = s32[4,100000,1]{1,0} fusion(...)") == "fusion.466"
    assert T.op_name("while.3") == "while.3"


def test_json_round_trip():
    tr = load("synthetic_trace.json")
    assert T.Trace.from_json(tr.to_json()) == tr


def test_recorded_v5e_trace():
    """The head of a trace recorded on one v5e: three dispatches of the
    fig6-byz program (the first 800 of its device operations kept)."""
    tr = load("v5e_fig6_trace_head.json")
    assert tr.devices == ["0"]
    runs = tr.program_runs("0", "jit_run")
    assert len(runs) == 3
    assert tr.program_busy_s("jit_run") == pytest.approx(0.998739853)
    gaps = tr.gaps_between_runs("jit_run")
    assert gaps == pytest.approx([0.016870354, 0.016594108])
    # the gaps fall inside the harness's dispatch spans: the host was in
    # run_grid, fetching results and stacking the next batch
    assert all(tr.open_span((e + s) // 2) == "perfbench.dispatch"
               for (_, e), (s, _) in zip(runs, runs[1:]))
    assert all(" = " not in name for name, _, _ in tr.ops["0"])
