"""Faults planted in the timed path underneath the harness, each of which the
check has to find: every one wraps the engine's ``run_grid``. The tests plant
them in a small cell on the CPU; ``calibrate.py --fault <name>`` plants one
in a cell at its own size on the chip."""
from __future__ import annotations

import numpy as np


def state_unchanged(real, cells, **kw):
    """Every step returns the state it was given: no churn moves anything."""
    return real([dict(c, churn_per_year=0.0) for c in cells], **kw)


def half_batch(real, cells, seeds, **kw):
    """The second half of each dispatch's seeds is not run: its lanes repeat
    the first half's."""
    seeds = list(seeds)
    k = len(seeds) // 2
    return real(cells, seeds=seeds[:k] * 2, **kw)


def traffic_altered(real, cells, **kw):
    """The repair traffic is altered by 10% where it is produced."""
    res = real(cells, **kw)
    return res._replace(
        repair_traffic_units=np.asarray(res.repair_traffic_units) * 1.1)


def hit_degraded_swapped(real, cells, **kw):
    """Reads served from the cache and reads served degraded trade places."""
    res = real(cells, **kw)
    return res._replace(reads_hit=res.reads_degraded,
                        reads_degraded=res.reads_hit)


FAULTS = {"state-unchanged": state_unchanged, "half-batch": half_batch,
          "traffic-altered": traffic_altered,
          "hit-degraded-swapped": hit_degraded_swapped}


def plant(name: str):
    """Wrap ``scenarios.run_grid`` with the fault ``name``; returns the
    function that takes it out again."""
    from repro.core import scenarios

    real, fault = scenarios.run_grid, FAULTS[name]
    scenarios.run_grid = lambda cells, **kw: fault(real, cells, **kw)
    return lambda: setattr(scenarios, "run_grid", real)
