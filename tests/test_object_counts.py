"""Per-object counts of the chunk-group masks: a batch that shares one chunk
count sums rows of whole objects by a 0/1 matrix product, a batch that mixes
chunk counts keeps ``segment_sum``, and the two agree bit for bit."""
import glob
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.profiler import ProfileData

from repro.core import scenarios as SC

# served reads on a cached deployment whose ring is eclipsed for steps 4-9
# of 16, with enough churn and Byzantine members that objects degrade, fail
# and are lost; the second cell stores fewer objects, so it is padded
SERVE = dict(n_objects=40, n_chunks=5, k_outer=3, k_inner=8, r_inner=16,
             n_nodes=2000, byz_fraction=0.3, churn_per_year=40.0,
             cache_ttl_hours=6.0, step_hours=12.0, steps=16,
             read_rate=200.0, adv_policy="eclipse", attack_frac=0.3,
             attack_step=4, eclipse_steps=6)
SERVE_CELLS = [SERVE, dict(SERVE, n_objects=27)]
TARGETED = dict(n_objects=50, n_chunks=6, k_outer=4, byz_fraction=0.3,
                attack_frac=0.05, n_nodes=20_000)
TARGETED_CELLS = [TARGETED, dict(TARGETED, n_objects=35, attack_frac=0.2)]
SEEDS = range(3)


def _statics(flat, max_steps):
    """The same padded maxima with the reshape path and the scatter path."""
    st = SC._Static(
        max_groups=max(int(s.n_objects * s.n_chunks) for s in flat),
        max_objects=max(int(s.n_objects) for s in flat),
        max_steps=max_steps, shared_chunks=SC._shared_chunks(flat))
    assert st.shared_chunks > 0
    return st, st._replace(shared_chunks=0)


# chunk counts whose rows of whole objects fill 128 lanes in different ways
# (k = 128, 32, 64 and 8 objects a row), object counts that leave a part row
@pytest.mark.parametrize("n_chunks, n_objects, max_objects",
                         [(1, 7, 7), (4, 9, 13), (10, 30, 200), (48, 3, 9)])
def test_reshape_counts_equal_segment_sum(n_chunks, n_objects, max_objects):
    G = max_objects * n_chunks
    rng = np.random.default_rng(n_chunks)
    # every group is drawn, those past n_objects x n_chunks too
    masks = jnp.asarray(rng.random((3, G)) < 0.6)
    cells = SC._stack([SC.make_scenario(n_objects=n_objects,
                                        n_chunks=n_chunks)] * 3)
    st = SC._Static(max_groups=G, max_objects=max_objects, max_steps=1,
                    shared_chunks=n_chunks)
    count = jax.jit(jax.vmap(SC._per_object, in_axes=(None, 0, 0)),
                    static_argnums=0)
    got = np.asarray(count(st, cells, masks))
    want = np.asarray(count(st._replace(shared_chunks=0), cells, masks))
    oid = np.arange(G) // n_chunks
    assert got.dtype == np.float32
    assert np.array_equal(got, want)
    assert np.array_equal(got, np.stack(
        [np.bincount(oid, m, minlength=max_objects)
         for m in np.asarray(masks)]).astype(np.float32))


def test_run_grid_serving_bit_identical_to_scatter():
    flat = SC._product(SERVE_CELLS, SEEDS)
    st, st_scatter = _statics(flat, max_steps=16)
    res = SC.run_grid(SERVE_CELLS, seeds=SEEDS, sampler="arx")
    ref = SC._run_chunked(flat, SC._vault_batch(st_scatter, "arx", 2, 1),
                          None, 1, len(SEEDS))
    for name, a, b in zip(res._fields, res, ref):
        assert a.dtype == b.dtype, name
        assert np.array_equal(a, b), name
    # the objects counted were not all whole: every serving bucket and the
    # loss count saw both outcomes
    assert (res.reads_degraded > 0).any() and (res.reads_failed > 0).any()
    assert (res.reads_hit > 0).any() and (res.reads_miss > 0).any()
    assert (res.lost_objects > 0).any()
    assert (res.lost_objects < np.array([[40], [27]])).all()
    # the eclipse window moved what was served
    no_ecl = SC.run_grid([dict(c, attack_frac=0.0) for c in SERVE_CELLS],
                         seeds=SEEDS, sampler="arx")
    assert not np.array_equal(res.reads_degraded, no_ecl.reads_degraded)


def test_targeted_grid_bit_identical_to_scatter():
    flat = SC._product(TARGETED_CELLS, SEEDS)
    st, st_scatter = _statics(flat, max_steps=1)
    got = SC.targeted_grid(TARGETED_CELLS, seeds=SEEDS, sampler="arx")
    want = SC._run_chunked(flat, SC._targeted_batch(st_scatter, "arx", 1),
                           None, 1, len(SEEDS))
    assert got.dtype == want.dtype
    assert np.array_equal(got, want)
    assert (got > 0).any() and (got < 1).all()


def test_object_count_path_counter_and_build_span(tmp_path):
    # a horizon no other test compiles, so each grid builds a new runner
    uniform = [dict(SERVE, steps=3), dict(SERVE, steps=3, n_objects=20)]
    mixed = [dict(SERVE, steps=3), dict(SERVE, steps=3, n_chunks=4)]
    with jax.profiler.trace(str(tmp_path)):
        before = SC.OBJECT_COUNT_PATHS.copy()
        SC.run_grid(uniform, seeds=range(2), sampler="arx")
        assert SC.OBJECT_COUNT_PATHS - before == {"reshape": 1}
        before = SC.OBJECT_COUNT_PATHS.copy()
        SC.run_grid(mixed, seeds=range(2), sampler="arx")
        assert SC.OBJECT_COUNT_PATHS - before == {"scatter": 1}
        SC.trace_grid([dict(SERVE, steps=3)], seeds=range(2), sampler="arx")
    before = SC.OBJECT_COUNT_PATHS.copy()
    SC.targeted_grid([dict(n_objects=11, n_chunks=3, k_outer=2,
                           attack_frac=0.1)], seeds=range(2))
    assert SC.OBJECT_COUNT_PATHS - before == {"reshape": 1}
    (path,) = glob.glob(os.path.join(str(tmp_path), "plugins", "profile",
                                     "*", "*.xplane.pb"))
    builds = sorted((e.start_ns, dict(e.stats))
                    for plane in ProfileData.from_file(path).planes
                    if plane.name.startswith("/host:")
                    for line in plane.lines for e in line.events
                    if e.name == SC.SPAN_BUILD)
    # trace_grid's runner counts no objects: its span has no argument
    assert [stats for _, stats in builds] == [
        {"object_counts": "reshape"}, {"object_counts": "scatter"}, {}]
