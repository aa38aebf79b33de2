"""Runner for configurations served by the batched engine's ``run_grid``.

A configuration names its deployment (``deployment``: ``make_scenario``
keys), the points of its sweep (``grid``: keys that differ from point to
point) and the sampler. A traffic mix adds the read load (``read_rate``,
``zipf_alpha``) and the dispatch layout: ``cells_per_dispatch`` sweep points
times ``seeds_per_dispatch`` seeds make one dispatch, split over ``devices``
chips. Dispatch ``rep`` runs the sweep's ``rep``-th group of points (cycling
through the groups) on the seeds it is given.
"""
from __future__ import annotations

import numpy as np

# the read load a traffic mix sets on every point of the sweep
TRAFFIC_KEYS = ("read_rate", "zipf_alpha")
# the compiled program every dispatch runs (the jitted ``run`` of the engine)
PROGRAM_PREFIX = "jit_run"
# least group state a step reads and writes once, in bytes per group:
# honest and Byzantine member counts (float32, the precision the
# configuration states), the live flag, and with the cache on the cached
# copy's timestamp and holder count (float32)
STATE_BYTES = 4 + 4 + 1
CACHE_STATE_BYTES = 4 + 4


class Runner:
    def __init__(self, config: dict, traffic: dict):
        from repro.core import scenarios

        self._sc = scenarios
        load = {k: traffic[k] for k in TRAFFIC_KEYS if k in traffic}
        self.cells = [{**config["deployment"], **point, **load}
                      for point in config.get("grid", [{}])]
        self.sampler = config["sampler"]
        self.devices = int(traffic.get("devices", 1))
        per = int(traffic["cells_per_dispatch"])
        if len(self.cells) % per:
            raise ValueError(f"{len(self.cells)} sweep points do not split "
                             f"into dispatches of {per}")
        self.groups = [list(range(i, i + per))
                       for i in range(0, len(self.cells), per)]
        self.seeds_per_dispatch = int(traffic["seeds_per_dispatch"])
        self.chunk = per * self.seeds_per_dispatch
        scen = [scenarios.make_scenario(**c) for c in self.cells]
        self.steps = [int(s.steps) for s in scen]
        self.step_hours = [float(s.step_hours) for s in scen]
        self.max_steps = max(self.steps)
        self.groups_of = [int(s.n_objects) * int(s.n_chunks) for s in scen]
        self.cache = [float(s.cache_ttl_hours) > 0 for s in scen]

    def plan(self, rep: int) -> list:
        """Sweep points of dispatch ``rep``."""
        return self.groups[rep % len(self.groups)]

    def dispatch(self, cells: list, seeds: list) -> list:
        """Run ``cells`` x ``seeds`` through ``run_grid`` in one chunk;
        return one ``{field: numpy value}`` per element, cell-major. Besides
        the engine's fields, ``honest_per_group``: the final honest mean
        times the final live share (honest members of live groups over all
        groups)."""
        res = self._sc.run_grid(
            [self.cells[i] for i in cells], seeds=seeds, sampler=self.sampler,
            chunk_size=self.chunk,
            devices=self.devices if self.devices > 1 else None)
        fields = {name: np.asarray(x) for name, x in zip(res._fields, res)}
        out = []
        for ci, cell in enumerate(cells):
            for si in range(len(seeds)):
                e = {name: x[ci, si] for name, x in fields.items()}
                e["honest_per_group"] = (e["final_honest_mean"].astype(np.float64)
                                         * e["alive_frac_trace"][self.steps[cell] - 1])
                out.append(e)
        return out

    def program_hlo(self, call) -> str:
        """Run ``call()`` and return the optimized HLO text of the program
        it dispatched, which names each operation's engine scope (a traced
        set-up reads the scan body's phases from it)."""
        from perfbench import scopes

        return scopes.program_hlo(call)

    def hours(self, cells: list, seeds: list) -> float:
        """Simulated deployment-hours of the elements asked for; the padding
        that fills a short chunk is not work asked for and does not count."""
        return float(sum(self.steps[i] * self.step_hours[i]
                         for i in cells)) * len(seeds)

    def state_bytes_per_step(self, cells: list, seeds: list) -> int:
        """Least bytes of group state one scan step moves for the elements."""
        per_elem = sum(self.groups_of[i] * 2 * (STATE_BYTES + CACHE_STATE_BYTES
                                                * self.cache[i])
                       for i in cells)
        return per_elem * len(seeds)
