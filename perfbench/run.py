#!/usr/bin/env python3
"""Run one benchmark cell on the chips of this machine and print its result.

    python3 perfbench/run.py --workload d1-serve --seed 7 --seconds 30 --trace 0

Set-up (JAX start, building the cell, one warm-up dispatch that compiles or
loads the cell's one program) is timed from the start of this process. The
window then runs back-to-back dispatches until the one in flight at
``--seconds`` returns. ``--trace 1`` runs a window of three of the same
dispatches under the profiler (a trace holds about a million device events a
second on each chip), reads it with the program's own spans and scopes, and
reports the cell's per-layer metrics instead of the end-to-end ones. After
the window, the check compares what the window produced with the plain
reference and prints each number compared beside its limit, last on
standard error and under ``compared`` in the result line, the last line of
standard output.

Exits 2 and prints no result when JAX finds no accelerator in
``perfbench/peaks.json`` or fewer chips than the cell asks for.
"""
import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    from perfbench import bench as B

    b = B.Bench(ROOT)
    try:
        devices = B.start(b, args.workload)
    except B.NoChip as e:
        print(f"perfbench: {e}; no result", file=sys.stderr)
        return 2
    line, report = B.execute(b, args.workload, args.seed, args.seconds,
                             bool(args.trace), devices, T0)
    sys.stdout.flush()
    for text in report:
        print(text, file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(line))
    return 0


if __name__ == "__main__":
    sys.exit(main())
