#!/usr/bin/env python3
"""Read the two ends that a cell's check limits are set between.

    python3 perfbench/calibrate.py --workload d1-serve --seeds 101-112 \\
        --control-seeds 201-203 --seconds 30

In one process, after one set-up: for each seed of ``--seeds``, a window of
``--seconds`` and its check, as ``run.py`` makes them (the lower readings:
the largest value each number takes on sound runs); then for each seed of
``--control-seeds``, the control: the reference computed in bfloat16, one
step below the float32 the configuration states, in the program's place
(the upper readings: the smallest value each number takes). With
``--fault <name>`` (``perfbench/faults.py``), the program's windows run with
that fault planted underneath. Prints one JSON line per seed and a summary
line per number.
"""
import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def seed_list(text: str) -> list:
    out = []
    for part in text.split(","):
        lo, _, hi = part.partition("-")
        out += list(range(int(lo), int(hi or lo) + 1))
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=seed_list, default=[])
    ap.add_argument("--control-seeds", type=seed_list, default=[])
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--fault", default=None)
    args = ap.parse_args(argv)
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    import ml_dtypes

    from perfbench import bench as B
    from perfbench import faults

    b = B.Bench(ROOT)
    try:
        B.start(b, args.workload)
    except B.NoChip as e:
        print(f"calibrate: {e}", file=sys.stderr)
        return 2
    if args.fault:
        faults.plant(args.fault)
    cell = B.set_up(b, args.workload, 0)
    runner, ref = cell.runner, cell.reference
    print(json.dumps({"setup_s": time.perf_counter() - T0}), flush=True)
    readings = {"program": [], "control": []}
    for kind, seeds in (("program", args.seeds), ("control", args.control_seeds)):
        for seed in seeds:
            t = time.perf_counter()
            host = {}
            if kind == "program":
                win = B.measure(runner, seed, args.seconds, False)
                elements = win.elements
                host = {"sim_hours_per_s": win.hours / win.seconds,
                        "dispatches": win.dispatches,
                        "cpu_wait_steal_s": win.host}
                values, failed, _ = B.judge(b, args.workload, runner, ref,
                                            elements, seed)
            else:
                elements = [{"cell": c, "seed": 0, "result": {}}
                            for c in range(len(runner.cells))]
                values, failed, _ = B.judge(b, args.workload, runner, ref,
                                            elements, seed, ml_dtypes.bfloat16,
                                            stand_in=True)
            readings[kind].append(values)
            print(json.dumps({kind: seed, "fault": args.fault, **host,
                              "elements": len(elements),
                              "failed": len(failed), "seconds":
                              time.perf_counter() - t, "values": values}),
                  flush=True)
    numbers = b.check(args.workload)["numbers"]
    for name, spec in numbers.items():
        low = max((v[name] for v in readings["program"]), default=None)
        high = min((v[name] for v in readings["control"]), default=None)
        print(json.dumps({"number": name, "lower": low, "upper": high,
                          "limit": spec["limit"]}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
