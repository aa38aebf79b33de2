#!/usr/bin/env python
"""Multi-device smoke: ``devices=N`` must be bit-identical to single-device.

A virtual-CPU study: it refuses to run on any platform but the CPU (on an
accelerator host, ``python chip_smoke.py --chips 4`` checks the same
property on real chips). Forces ``N`` virtual host devices
(``repro.config.set_host_devices`` must run before jax initializes, so
this script applies it itself) and runs the
scenario engine's sharded dispatch — one jitted executable whose batch
axis is split over a 1-D ``shard_map`` mesh (``scenarios._compile_runner``)
— against the plain single-device runner on the same cells, for all FOUR
grid runners (``run_grid`` / ``run_replicated_grid`` / ``trace_grid`` /
``targeted_grid``), including a deliberately uneven batch that exercises
the chunker's padding path. The samplers are counter-based, so any
divergence is a sharding bug, not noise.

Usage: ``python scripts/smoke_devices.py [N]`` (default 8; the CI
multi-device matrix runs the 2- and 8-virtual-device legs). Exits
non-zero on any mismatch.
"""
from __future__ import annotations

import os
import sys

N = int(sys.argv[1]) if len(sys.argv) > 1 else 8

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

from repro import config as CFG  # noqa: E402

if "--xla_force_host_platform_device_count" not in os.environ.get(
        "XLA_FLAGS", ""):
    CFG.set_host_devices(N)
# persistent compile cache: JAX_COMPILATION_CACHE_DIR as set, else the
# in-checkout directory of this device count (repro.config.cache_dir)
os.environ.setdefault("JAX_COMPILATION_CACHE_DIR", CFG.cache_dir(N))

import jax  # noqa: E402
import numpy as np  # noqa: E402

from repro.core import scenarios as SC  # noqa: E402

CELLS = [dict(n_objects=12, n_chunks=2, k_outer=2, k_inner=8,
              r_inner=20, n_nodes=2000, byz_fraction=0.25,
              churn_per_year=52.0, step_hours=12.0, years=0.05,
              cache_ttl_hours=24.0),
         dict(n_objects=8, n_chunks=3, k_outer=2, k_inner=16,
              r_inner=48, n_nodes=4000, byz_fraction=1 / 3,
              churn_per_year=26.0, step_hours=12.0, years=0.05)]


def _diff(tag: str, a, b) -> int:
    fields = getattr(a, "_fields", None)
    pairs = zip(fields, a, b) if fields else [(tag, a, b)]
    for name, x, y in pairs:
        if not np.array_equal(np.asarray(x), np.asarray(y)):
            print(f"FAIL: {tag}: field {name!r} diverges between "
                  f"single-device and devices={N}")
            return 1
    print(f"  {tag}: bit-identical")
    return 0


def main() -> int:
    platform = jax.devices()[0].platform
    if platform != "cpu":
        print(f"REFUSED: {platform} backend; this is a virtual-CPU study "
              "(run with JAX_PLATFORMS=cpu)")
        return 2
    avail = jax.local_device_count()
    if avail < N:
        print(f"FAIL: {avail} local device(s), need {N} "
              "(XLA_FLAGS was set too late?)")
        return 1
    rc = 0
    # 2N seeds: the batch splits cleanly across devices AND leaves a
    # second per-device element so the in-shard vmap axis is exercised
    even, odd = range(2 * N), range(2 * N + 1)
    rc |= _diff("run_grid",
                SC.run_grid(CELLS, seeds=even, sampler="arx"),
                SC.run_grid(CELLS, seeds=even, sampler="arx", devices=N))
    # odd seed count -> B % N != 0 -> the chunker's padding path
    rc |= _diff("run_grid[uneven]",
                SC.run_grid(CELLS[:1], seeds=odd, sampler="arx"),
                SC.run_grid(CELLS[:1], seeds=odd, sampler="arx", devices=N))
    rc |= _diff("run_replicated_grid",
                SC.run_replicated_grid(CELLS, seeds=even, sampler="arx"),
                SC.run_replicated_grid(CELLS, seeds=even, sampler="arx",
                                       devices=N))
    tcell = [dict(k_inner=8, r_inner=20, byz_fraction=0.2,
                  churn_per_year=52.0, step_hours=12.0, years=0.05)]
    rc |= _diff("trace_grid",
                SC.trace_grid(tcell, seeds=odd, sampler="arx"),
                SC.trace_grid(tcell, seeds=odd, sampler="arx", devices=N))
    gcell = [dict(n_objects=30, n_chunks=4, k_outer=2, byz_fraction=1 / 3,
                  attack_frac=0.1, n_nodes=1000)]
    rc |= _diff("targeted_grid",
                SC.targeted_grid(gcell, seeds=odd, sampler="arx"),
                SC.targeted_grid(gcell, seeds=odd, sampler="arx",
                                 devices=N))
    if rc:
        return 1
    print(f"devices={N} shard_map dispatch bit-identical to single-device "
          f"across all four grid runners (sampler=arx, incl. uneven batch)")
    return 0


if __name__ == "__main__":
    sys.exit(main())
