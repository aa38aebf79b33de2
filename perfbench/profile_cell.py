#!/usr/bin/env python3
"""Run one cell's traced window and split it by the engine's spans and scopes.

    python3 perfbench/profile_cell.py --workload d1-serve --seed 7

Sets the cell up as ``run.py`` does (with op metadata in the compile
cache's key), taking the compiled program's HLO text from the warm-up
dispatch, runs the window of ``run.py --trace 1`` (three
dispatches) under the JAX profiler, and reduces the trace with
``perfbench/scopes.py``. Results are not checked: ``run.py`` does that. The
last line of standard output is one JSON object:

* ``metrics``: the per-layer metrics of ``BENCHMARK.json``, read from this
  trace by their own readers; then, per chip, ``<phase>_step_us`` for the
  scan body's phases (``churn``, ``repair``, ``serve``, ``merge``: device time
  under the phase's scope over ``dispatches x max_steps``, as
  ``scan_step_us`` divides) and ``unscoped_step_us`` (the program's device
  time under none of them); and ``gap_prepare_ms``, ``gap_collect_ms`` and
  ``gap_harness_ms``: the mean, over the gaps ``host_gap_ms`` averages, of
  the device-idle time under ``vault.build`` / ``vault.stack`` /
  ``vault.launch``, under ``vault.fetch`` / ``vault.gather``, and under no
  ``vault.grid``. A program without the scopes or spans leaves those out.
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
import glob  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

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


def split(trace, prefix: str, dispatches: int, max_steps: int) -> dict:
    """The phase and gap numbers this script adds to ``metrics``."""
    from perfbench import scopes as S

    out = {}
    if trace.has_scopes:
        steps = dispatches * max_steps
        for phase in S.PHASES:
            name = phase.split(".", 1)[1] + "_step_us"
            out[name] = 1e6 * trace.scoped_busy_s((phase,)) / steps
        rest = trace.program_busy_s(prefix) - trace.scoped_busy_s(S.PHASES)
        out["unscoped_step_us"] = 1e6 * rest / steps
    gaps = trace.gap_split(prefix)
    if gaps and any(name == S.GRID for name, _, _ in trace.spans):
        for i, part in enumerate(("prepare", "collect", "harness")):
            out[f"gap_{part}_ms"] = 1e3 * sum(g[i] for g in gaps) / len(gaps)
    return out


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
    import jax

    from perfbench import bench as B
    from perfbench import scopes as S

    b = B.Bench(ROOT)
    try:
        devices = B.start(b, args.workload)
    except B.NoChip as e:
        print(f"perfbench: {e}; no result", file=sys.stderr)
        return 2
    # The compile cache's key leaves op metadata out by default, so a cached
    # build of another version of the program (the same ops, other scopes)
    # could run here and hand over its metadata. Keyed with it, the program
    # that runs is built from this version's HLO.
    jax.config.update("jax_compilation_cache_include_metadata_in_key", True)
    cells = []
    with B.CompileCounter() as compiles:
        hlo = S.program_hlo(
            lambda: cells.append(B.set_up(b, args.workload, args.seed)))
        cell = cells[0]
        setup_s = time.perf_counter() - T0
        compiles.open_window()
        trace_dir = tempfile.mkdtemp(prefix="perfbench-profile-")
        try:
            jax.profiler.start_trace(trace_dir)
            try:
                win = B.measure(cell.runner, args.seed, 0.0, False,
                                B.TRACE_DISPATCHES)
            finally:
                jax.profiler.stop_trace()
            (path,) = glob.glob(os.path.join(trace_dir, "plugins", "profile",
                                             "*", "*.xplane.pb"))
            trace = S.from_xspace(path, hlo)
        finally:
            shutil.rmtree(trace_dir, ignore_errors=True)
    run = B.Run(setup_s=setup_s, window_s=win.seconds, hours=win.hours,
                dispatches=win.dispatches, compiles_in_window=compiles.window,
                chips=len(devices), max_steps=cell.runner.max_steps,
                state_bytes_per_step=win.state_bytes,
                peaks=b.peaks().get(devices[0].device_kind, {}),
                program_prefix=cell.program_prefix, trace=trace)
    metrics = {}
    for m in b.metrics(True):
        value = b.module("metrics", m["name"]).read(run)
        if value is not None:
            metrics[m["name"]] = value
    metrics.update(split(trace, cell.program_prefix, win.dispatches,
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
