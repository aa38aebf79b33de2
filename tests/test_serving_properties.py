"""Property-based invariants of the serving workload layer (PR 8).

Runs under real ``hypothesis`` when installed (CI property shard) and
under the seeded deterministic stand-in otherwise — see
``tests/_hypothesis_compat.py``, which defers to the real library itself.

The properties pin the engine's closed-form serving model
(``scenarios._vault_serve``) over randomly drawn knob settings:

* **request conservation** — hit + miss + degraded + failed counts
  partition the issued load exactly, and the hop histogram holds exactly
  the completed (non-failed) reads;
* **traffic monotonicity** — served traffic never decreases in the
  request rate (same seed, same scenario otherwise);
* **cache hit rate** — bounded by 1, and nonincreasing in the cache-holder
  death rate (the churn-aware cache model: dead holders stop serving —
  the over-credit leak this PR closes).
"""
import numpy as np

from _hypothesis_compat import given, settings, strategies as st

from repro.core import scenarios as SC

# one shared static geometry (6 objects x 3 chunks, 20 steps) so every
# run_grid call in this module reuses one compiled executable; all drawn
# knobs are traced scalars
GEO = dict(n_objects=6, n_chunks=3, k_outer=2, k_inner=6, r_inner=14,
           n_nodes=500, byz_fraction=0.1, step_hours=12.0, steps=20)
SEEDS = range(4)

read_rates = st.floats(min_value=0.0, max_value=2e6)
alphas = st.floats(min_value=0.0, max_value=2.0)
churns = st.floats(min_value=1.0, max_value=500.0)
ttls = st.floats(min_value=0.0, max_value=240.0)


def _run(cells):
    return SC.run_grid(cells, seeds=SEEDS)


@settings(max_examples=8, deadline=None, derandomize=True)
@given(rate=read_rates, alpha=alphas, churn=churns, ttl=ttls)
def test_request_conservation(rate, alpha, churn, ttl):
    """hits + misses + degraded + failed == issued, in every cell/seed,
    and the hop histogram holds exactly the completed reads."""
    r = _run([dict(GEO, read_rate=rate, zipf_alpha=alpha,
                   churn_per_year=churn, cache_ttl_hours=ttl)])
    issued = np.asarray(r.reads_issued, np.float64)
    buckets = sum(np.asarray(getattr(r, f), np.float64) for f in
                  ("reads_hit", "reads_miss", "reads_degraded",
                   "reads_failed"))
    np.testing.assert_allclose(buckets, issued, rtol=1e-5, atol=1e-3)
    np.testing.assert_allclose(issued, rate * GEO["steps"], rtol=1e-5,
                               atol=1e-3)
    completed = issued - np.asarray(r.reads_failed, np.float64)
    hist_mass = np.asarray(r.serve_hop_hist, np.float64).sum(axis=-1)
    np.testing.assert_allclose(hist_mass, completed, rtol=1e-5, atol=1e-3)


@settings(max_examples=8, deadline=None, derandomize=True)
@given(rate=st.floats(min_value=0.0, max_value=1e6), alpha=alphas,
       churn=churns, ttl=ttls, factor=st.floats(min_value=1.0, max_value=8.0))
def test_traffic_monotone_in_request_rate(rate, alpha, churn, ttl, factor):
    """Scaling the request rate up never reduces served traffic (per seed:
    identical RNG streams, closed-form load scaling)."""
    lo, hi = _run([
        dict(GEO, read_rate=rate, zipf_alpha=alpha, churn_per_year=churn,
             cache_ttl_hours=ttl),
        dict(GEO, read_rate=rate * factor, zipf_alpha=alpha,
             churn_per_year=churn, cache_ttl_hours=ttl),
    ]).served_traffic_units
    assert np.all(np.asarray(hi) >= np.asarray(lo) - 1e-6), (lo, hi)


@settings(max_examples=8, deadline=None, derandomize=True)
@given(rate=st.floats(min_value=1.0, max_value=1e6), alpha=alphas,
       churn=churns, ttl=ttls)
def test_hit_rate_is_a_probability(rate, alpha, churn, ttl):
    """0 <= reads_hit / reads_issued <= 1 in every cell and seed."""
    res = _run([dict(GEO, read_rate=rate, zipf_alpha=alpha,
                     cache_ttl_hours=ttl, churn_per_year=churn)])
    rates = (np.asarray(res.reads_hit, np.float64)
             / np.maximum(np.asarray(res.reads_issued, np.float64), 1e-9))
    assert np.all(rates <= 1.0 + 1e-9)
    assert np.all(rates >= -1e-9)


@settings(max_examples=8, deadline=None, derandomize=True)
@given(rate=st.floats(min_value=1.0, max_value=1e6), alpha=alphas)
def test_hit_rate_nonincreasing_in_holder_death_rate(rate, alpha):
    """Killing cache holders faster can only lower the hit rate.

    The TTL is held past the run horizon so warmth is *exactly* holder
    survival — with a short TTL churn also re-warms caches through repair
    misses (``cache_t = now`` on refresh), which legitimately makes hit
    rate non-monotone in churn as a whole; the churn-aware holder model
    this PR introduces is the death side, isolated here. (The old
    optimistic model kept hit rate flat in churn: see the leak-closure
    regression in test_cross_validation.py.) Seed-mean over
    well-separated churn rates."""
    horizon = GEO["steps"] * GEO["step_hours"]
    res = _run([dict(GEO, read_rate=rate, zipf_alpha=alpha,
                     cache_ttl_hours=horizon + 24.0, churn_per_year=c)
                for c in (5.0, 80.0, 400.0)])
    hit = np.asarray(res.reads_hit, np.float64)
    issued = np.asarray(res.reads_issued, np.float64)
    mean = (hit / np.maximum(issued, 1e-9)).mean(axis=1)  # [3 churn cells]
    assert mean[1] <= mean[0] + 1e-6, mean
    assert mean[2] <= mean[1] + 1e-6, mean


def test_protocol_conservation_single_run():
    """The protocol tick obeys the same conservation law (single seeded
    run — the statistical engine/protocol agreement lives in
    test_cross_validation.py; bit-level pins in test_serving_golden.py)."""
    from repro.core import protocol_sim as PS

    p = PS.ProtocolParams(n_nodes=60, n_objects=2, object_bytes=800,
                          k_outer=2, n_chunks=3, k_inner=5, r_inner=10,
                          churn_per_year=60.0, steps=6, read_rate=100.0,
                          cache_ttl_hours=48.0, seed=0)
    r = PS.run_protocol(p)
    assert (r.reads_hit + r.reads_miss + r.reads_degraded + r.reads_failed
            == r.reads_issued == 600)
    assert r.serve_hop_hist.sum() == r.reads_issued - r.reads_failed
    assert np.all(r.serve_trace[:, 0]
                  == r.serve_trace[:, 1:].sum(axis=1))


def test_fixed_order_sum_depends_on_the_row_alone():
    """The engine's serving sums add in one fixed pairwise order: a row's
    sum has the same bits alone, inside a batch of any width under
    jit(vmap), and in a numpy float32 replay of that order."""
    import jax
    import jax.numpy as jnp

    from repro.core import policies as P

    rng = np.random.default_rng(0)
    rows = (rng.random((4, 1000)) * 10.0 ** rng.integers(-6, 3, (4, 1000))
            ).astype(np.float32)

    def replay(x):
        x = np.concatenate([x, np.zeros(1024 - x.size, np.float32)])
        while x.size > 1:
            x = x[:x.size // 2] + x[x.size // 2:]
        return x[0]

    batched = jax.jit(jax.vmap(P.fixed_order_sum))
    want = np.array([replay(r) for r in rows])
    for width in (1, 2, 4):
        got = np.concatenate([np.asarray(batched(jnp.asarray(rows[i:i + width])))
                              for i in range(0, 4, width)])
        np.testing.assert_array_equal(got, want)
    np.testing.assert_array_equal(np.asarray(P.fixed_order_sum(rows)), want)
    assert P.fixed_order_sum(jnp.ones(1)) == 1.0
