"""The engine's profiler labels: host spans around each stage of a grid call,
name scopes around each phase of the scan body, and results that do not
depend on whether the profiler is on."""
import glob
import os
import re

import numpy as np
import pytest

import jax
from jax.profiler import ProfileData

from repro.core import scenarios as SC

CELL = dict(n_objects=10, n_chunks=4, k_outer=2, k_inner=8, r_inner=20,
            n_nodes=2000, byz_fraction=0.2, churn_per_year=26.0,
            cache_ttl_hours=24.0, step_hours=12.0, steps=8, read_rate=50.0)
SEEDS = range(3)


def _traced(tmp_path, **kw):
    """``run_grid`` under the profiler: its result and the engine's host
    spans as ``(name, start_ns, end_ns)``, in order of start."""
    with jax.profiler.trace(str(tmp_path)):
        res = SC.run_grid([CELL], seeds=SEEDS, sampler="arx", **kw)
    (path,) = glob.glob(os.path.join(str(tmp_path), "plugins", "profile",
                                     "*", "*.xplane.pb"))
    spans = [(e.name, e.start_ns, e.end_ns)
             for plane in ProfileData.from_file(path).planes
             if plane.name.startswith("/host:")
             for line in plane.lines for e in line.events
             if e.name.startswith("vault.")]
    return res, sorted(spans, key=lambda s: s[1])


@pytest.mark.parametrize("chunk_size, chunks", [(None, 1), (2, 2)])
def test_run_grid_spans_nest_in_order(tmp_path, chunk_size, chunks):
    _, spans = _traced(tmp_path, chunk_size=chunk_size)
    names = [n for n, _, _ in spans]
    assert names == ([SC.SPAN_GRID, SC.SPAN_BUILD]
                     + [SC.SPAN_STACK, SC.SPAN_LAUNCH, SC.SPAN_FETCH] * chunks
                     + [SC.SPAN_GATHER])
    (_, lo, hi), children = spans[0], spans[1:]
    assert all(lo <= s <= e <= hi for _, s, e in children)
    # the children follow one another: none overlaps the next
    assert all(e <= s for (_, _, e), (_, s, _) in zip(children, children[1:]))


def test_profiler_on_results_bit_identical(tmp_path):
    off = SC.run_grid([CELL], seeds=SEEDS, sampler="arx", chunk_size=2)
    on, _ = _traced(tmp_path, chunk_size=2)
    for name, a, b in zip(off._fields, off, on):
        assert isinstance(b, np.ndarray), name
        assert np.array_equal(a, b), name


def test_scan_body_phases_scoped_in_op_metadata():
    flat = SC._product([CELL], SEEDS)
    st = SC._Static(max_groups=40, max_objects=10, max_steps=8)
    text = SC._vault_batch(st, "arx", 2, 1).lower(
        SC._stack(flat)).compile().as_text()
    for scope in (SC.SCOPE_CHURN, SC.SCOPE_REPAIR, SC.SCOPE_SERVE,
                  SC.SCOPE_MERGE):
        assert re.search(rf'op_name="[^"]*/while/body/[^"]*{scope}/',
                         text), scope
