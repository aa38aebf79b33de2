"""Plain numpy reference of one Vault deployment cell, for the benchmark's check.

It follows the group-level model of the paper (Vault, arXiv 2310.08403, sec.
6.1) and of the engine's documented semantics, written afresh: it imports
nothing of the program under test.

Per chunk group: honest members, Byzantine members, a live flag and, when the
chunk cache is on, the cached copy's timestamp and holder count. Each step of
``step_hours``:

1. churn: every member (and every cache holder) fails with probability
   ``1 - exp(-churn_per_year * step_hours / 8760)``;
2. a group with fewer than ``k_inner`` honest members is lost for good;
3. repair: each live group is refilled to ``r_inner`` members, each refill
   Byzantine with probability ``byz_fraction``. Without a cache a refilled
   fragment costs ``k_inner`` fragment pulls; with it, a warm copy (TTL holds
   and a holder lives) costs one fragment per refill, a cold one one chunk
   pull plus one fragment per refill after the first, and re-caches the chunk
   at one holder;
4. serving: ``read_rate`` reads spread over objects by Zipf(``zipf_alpha``)
   popularity. An object with fewer than ``k_outer`` live groups fails its
   reads; one with a dead group serves them degraded (4 hops); one with
   ``k_outer`` or more warm live groups serves them from the cache (2 hops);
   any other serves them by decode (3 hops). Completed reads move one object
   unit each.

Every float (probabilities, counts, sums, accumulators) is held in ``ftype``:
float64 for the reference, bfloat16 for the lower-precision control. Sums use
``np.sum(..., dtype=ftype)``, which for bfloat16 adds in sequence.

Regional link caps are not modelled: this reference supports ``region_cap =
0`` only. ``honest_per_group`` is the honest members of the live groups at
the end over all groups (a dead group counts 0): the final honest mean
weighted by the final live share, steady where almost every group dies.
"""
from __future__ import annotations

import math
import numpy as np

HOURS_PER_YEAR = 24 * 365.0
HIST_BINS = 16
HOPS_HIT, HOPS_MISS, HOPS_DEGRADED = 2, 3, 4
# cell keys this reference models, with the value each must have if given
_FIXED = {"churn_policy": ("iid", 0), "adv_policy": ("static", 0),
          "region_cap": (0, 0.0), "policy": (None,), "cache_churn": (True, 1),
          "frags_per_node": (1,), "attack_frac": (0, 0.0)}
_MODELLED = {"n_objects", "n_chunks", "k_outer", "k_inner", "r_inner",
             "n_nodes", "byz_fraction", "churn_per_year", "cache_ttl_hours",
             "step_hours", "years", "steps", "read_rate", "zipf_alpha"}


def _steps_of(cell: dict) -> int:
    if "steps" in cell:
        return int(cell["steps"])
    return int(round(cell.get("years", 1.0) * HOURS_PER_YEAR
                     / cell.get("step_hours", 6.0)))


def _validate(cell: dict) -> None:
    for key, value in cell.items():
        if key in _FIXED:
            if value not in _FIXED[key]:
                raise ValueError(f"reference models {key}={_FIXED[key][0]!r} "
                                 f"only, not {value!r}")
        elif key not in _MODELLED:
            raise ValueError(f"reference does not model {key!r}")


def zipf_weights(n_objects: int, alpha: float, ftype) -> np.ndarray:
    """Zipf(alpha) popularity of objects ranked 0 (hottest) up, summing to 1."""
    w = (np.arange(1, n_objects + 1, dtype=np.float64) ** -alpha).astype(ftype)
    return (w / np.sum(w, dtype=ftype)).astype(ftype)


def simulate(cell: dict, seed: int, ftype=np.float64) -> dict:
    """One realization of ``cell`` (``make_scenario``-style keys) from ``seed``.

    Returns the engine's result fields as numpy values (the per-step live
    fraction under ``alive_frac_trace``, the hop histogram under
    ``serve_hop_hist``) and ``honest_per_group``."""
    _validate(cell)
    f = ftype
    rng = np.random.default_rng(int(seed))
    n_obj, nc = int(cell["n_objects"]), int(cell["n_chunks"])
    r, k, k_outer = f(cell["r_inner"]), f(cell["k_inner"]), f(cell["k_outer"])
    G = n_obj * nc
    step_h = cell.get("step_hours", 6.0)
    ttl = f(cell.get("cache_ttl_hours", 0.0))
    has_cache = float(ttl) > 0.0
    byz_p = float(f(cell.get("byz_fraction", 0.0)))
    p_fail = float(f(-math.expm1(-cell.get("churn_per_year", 4.0)
                                 / HOURS_PER_YEAR * step_h)))
    frag_units = f(1.0) / (k_outer * k)
    chunk_units = f(1.0) / k_outer
    rate = f(cell.get("read_rate", 0.0))
    load = rate * zipf_weights(n_obj, cell.get("zipf_alpha", 1.1), f)
    zero, one = f(0.0), f(1.0)

    def draw(n, p):
        return rng.binomial(n.astype(np.int64), p).astype(f)

    byz = draw(np.full(G, r), byz_p)
    honest = r - byz
    alive = honest >= k
    cache_t = np.zeros(G, f)
    cache_h = np.full(G, r if has_cache else zero, f)
    traffic = repairs = hits = issued = zero
    hmin, mmax = math.inf, 0.0
    steps = _steps_of(cell)
    alive_n = np.zeros(steps, f)
    reads = {name: zero for name in ("hit", "miss", "degraded", "failed")}
    for t in range(steps):
        now = f((t + 1) * step_h)
        h = honest - draw(honest, p_fail)
        b = byz - draw(byz, p_fail)
        a = alive & (h >= k)
        deficit = np.maximum(np.where(a, r - (h + b), zero), zero)
        new_b = draw(deficit, byz_p)
        h = h + (deficit - new_b)
        b = b + new_b
        n_def = np.sum(deficit, dtype=f)
        repairs = repairs + n_def
        if has_cache:
            cache_h = np.maximum(cache_h - draw(cache_h, p_fail), zero)
            warm = ((now - cache_t) <= ttl) & (cache_h >= one)
            hit_frags = np.where(warm, deficit, np.maximum(deficit - one, zero))
            miss = ~warm & (deficit > zero)
            n_hit = np.sum(hit_frags, dtype=f)
            traffic = traffic + (n_hit * frag_units
                                 + np.sum(miss.astype(f), dtype=f) * chunk_units)
            hits = hits + n_hit
            cache_t = np.where(miss, now, cache_t)
            cache_h = np.where(miss, one, cache_h)
            warm = warm | miss
        else:
            traffic = traffic + n_def * k * frag_units
            warm = np.zeros(G, bool)
        if a.any():
            hmin = min(hmin, float(h[a].min()))
        mmax = max(mmax, float((h + b).max()))
        honest, byz, alive = h, b, a
        alive_n[t] = np.sum(a.astype(f), dtype=f)
        if float(rate) > 0.0:
            issued = issued + rate
            n_read = np.sum(a.reshape(n_obj, nc).astype(f), axis=1, dtype=f)
            n_warm = np.sum((a & warm).reshape(n_obj, nc).astype(f), axis=1,
                            dtype=f)
            failed = n_read < k_outer
            degraded = ~failed & (n_read < f(nc))
            hit = ~failed & ~degraded & (n_warm >= k_outer)
            for name, mask in (("hit", hit), ("miss", ~failed & ~degraded & ~hit),
                               ("degraded", degraded), ("failed", failed)):
                reads[name] = reads[name] + np.sum(np.where(mask, load, zero),
                                                   dtype=f)
    n_live = np.sum(alive.astype(f), dtype=f)
    honest_live = np.sum(np.where(alive, honest, zero), dtype=f)
    # no link cap: every read takes its base hop count
    hist = np.zeros(HIST_BINS, f)
    hist[HOPS_HIT] = reads["hit"]
    hist[HOPS_MISS] = reads["miss"]
    hist[HOPS_DEGRADED] = reads["degraded"]
    out = dict(
        repair_traffic_units=traffic, repairs=repairs, cache_hits=hits,
        lost_objects=int((alive.reshape(n_obj, nc).sum(axis=1)
                          < int(cell["k_outer"])).sum()),
        final_honest_mean=honest_live / n_live if float(n_live) > 0 else zero,
        honest_per_group=honest_live / f(G),
        honest_min=hmin if math.isfinite(hmin) else 0.0, members_max=mmax,
        alive_frac_trace=alive_n / f(G),
        reads_issued=issued, reads_hit=reads["hit"], reads_miss=reads["miss"],
        reads_degraded=reads["degraded"], reads_failed=reads["failed"],
        served_traffic_units=reads["hit"] + reads["miss"] + reads["degraded"],
        serve_hop_hist=hist)
    return {key: np.asarray(v, np.float64) for key, v in out.items()}


def simulate_all(jobs, ftype=np.float64) -> list:
    """:func:`simulate` of each ``(cell, seed)`` job."""
    return [simulate(cell, seed, ftype) for cell, seed in jobs]
