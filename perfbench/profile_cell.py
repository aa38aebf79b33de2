#!/usr/bin/env python3
"""Run one cell's traced window and split it by the engine's spans and scopes.

    python3 perfbench/profile_cell.py --workload d1-serve --seed 7

Sets the cell up and runs its window as ``run.py --trace 1`` does (the
compile cache keyed with op metadata, the compiled program's HLO text taken
from the warm-up dispatch, three dispatches under the JAX profiler, the
trace reduced with ``perfbench/scopes.py``). Results are not checked:
``run.py`` does that. The last line of standard output is one JSON object:

* ``metrics``: the cell's per-layer metrics of ``BENCHMARK.json``, read from
  this trace by their own readers; then the whole split
  (``scopes.split``), which adds ``unscoped_step_us`` (the program's device
  time a step under none of the phases) and ``gap_harness_ms`` (device-idle
  time a gap under no ``vault.grid``). A program without the scopes or
  spans leaves those out.
* ``window_s``, ``dispatches`` and ``dispatch_s`` (window over dispatches) of
  the traced window, on the host clock; ``span_ms``, each engine span's mean
  length;
* ``idle_gaps``: the longest device-idle gaps, each with the innermost span
  open over its midpoint.

``--fixture PATH`` also writes the trace as JSON, trimmed to the first
operations of the window and those around each gap between dispatches.
"""
import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
HEAD_OPS = 600  # operations kept from the start of the window
GAP_OPS = 200   # operations kept on each side of a gap between runs


def trim(trace, prefix: str):
    """``trace`` with each device's operations cut to the first
    ``HEAD_OPS`` of the window and ``GAP_OPS`` on each side of every gap
    between runs of the programs ``prefix``; scopes cut to match."""
    ops, scopes = {}, {}
    for dev, events in trace.ops.items():
        events = sorted((e for e in events if e[1] + e[2] > trace.window[0]),
                        key=lambda e: e[1])
        starts = [s for _, s, _ in events]
        keep = set(range(min(HEAD_OPS, len(events))))
        runs = trace.program_runs(dev, prefix)
        for (_, end), (start, _) in zip(runs, runs[1:]):
            lo = sum(s < end for s in starts)
            hi = sum(s < start for s in starts)
            keep |= set(range(max(lo - GAP_OPS, 0),
                              min(hi + GAP_OPS, len(events))))
        ops[dev] = [events[i] for i in sorted(keep)]
        of = trace.scopes.get(dev, {})
        scopes[dev] = {n: of[n] for n in {e[0] for e in ops[dev]} if n in of}
    return type(trace)(window=trace.window, ops=ops, modules=trace.modules,
                       spans=trace.spans, scopes=scopes)


def span_ms(trace) -> dict:
    """Mean milliseconds of each engine span in the window, by name."""
    from perfbench import scopes as S

    runs = {}
    for name, s, d in trace.spans:
        if name.startswith(S.PROGRAM_SPAN_PREFIX) and s >= trace.window[0]:
            runs.setdefault(name, []).append(d)
    return {name: 1e-6 * sum(d) / len(d) for name, d in runs.items()}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--fixture", default=None)
    args = ap.parse_args(argv)
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    from perfbench import bench as B
    from perfbench import scopes as S

    b = B.Bench(ROOT)
    try:
        devices = B.start(b, args.workload)
    except B.NoChip as e:
        print(f"perfbench: {e}; no result", file=sys.stderr)
        return 2
    with B.CompileCounter() as compiles:
        cell = B.set_up(b, args.workload, args.seed, traced=True)
        setup_s = time.perf_counter() - T0
        compiles.open_window()
        win = B.measure(cell.runner, args.seed, 0.0, True, B.TRACE_DISPATCHES,
                        cell.hlo)
    trace = win.trace
    run = B.reading(b, cell, win, setup_s, compiles.window, devices)
    metrics = B.read_metrics(b, args.workload, True, run)
    metrics.update(S.split(trace, cell.program_prefix, win.dispatches,
                           cell.runner.max_steps))
    if args.fixture:
        with open(args.fixture, "w") as fh:
            fh.write(trim(trace, cell.program_prefix).to_json())
    line = {"workload": args.workload, "seed": args.seed, "metrics": metrics,
            "window_s": win.seconds, "dispatches": win.dispatches,
            "dispatch_s": win.seconds / win.dispatches,
            "scoped_ops": sum(map(len, trace.scopes.values())),
            "span_ms": span_ms(trace),
            "idle_gaps": trace.longest_gaps(),
            "device": {"kind": devices[0].device_kind, "count": len(devices),
                       "busy_s": trace.busy_s(), "window_s": trace.window_s}}
    print(json.dumps(line))
    return 0


if __name__ == "__main__":
    sys.exit(main())
