"""Batched JAX scenario engine for paper-scale durability sweeps.

``simulation.py`` is the numpy *reference* implementation: one
``(params, seed)`` point per call, a Python loop per time step. This module
is the production path: the full group state ``(honest, byz, cache_t,
alive)`` lives in batched arrays, every time step advances inside one jitted
``lax.scan``, and ``vmap`` runs a whole ``(parameter-grid × seeds ×
policies)`` sweep — e.g. all cells of Fig. 4 or Fig. 6 — as a single device
dispatch. Group counts, code parameters, churn rates, TTLs, and policy
selectors are all *traced* scalars, so heterogeneous cells (different
``n_objects``, ``n_chunks``, ``(K, R)``) share one compiled executable via
padding masks; only the padded maxima, and whether every cell has the same
``n_chunks``, are compile-time constants.

Scenario diversity is a first-class axis. Each policy is a pure function
composed into the scan body and selected per batch element. The policy
*definitions* — ids, per-step probabilities, burst/refill/kill arithmetic —
live in ``repro.core.policies`` (shared verbatim with the protocol-level
simulator ``repro.core.protocol_sim``, which is cross-validated against
this engine); see that module's docstring for the full catalogue:

* churn: ``"iid"`` (paper §6.1), ``"regional"`` correlated bursts,
  ``"diurnal"`` time-of-day rate modulation, and ``"pareto"``
  heavy-tailed session lengths (protected-cohort mean-field here;
  real session draws in the protocol layer);
* adversary: ``"static"`` (Fig. 6), ``"adaptive"`` re-join (BFT-DSN
  style), ``"targeted"`` greedy kill (A.3 cost model, time-resolved),
  ``"eclipse"`` ring partition (mean-field), ``"collude"``
  withholding (wasted-pull traffic, closed-form), and the composed
  ``"eclipse_targeted"`` product;
* cache: the ``cache_ttl_hours`` knob (0 disables), identical to the
  reference semantics (repair.py docstring / Fig. 4), with churn-aware
  holder retirement (a copy goes cold when all its holders die);
* serving: ``read_rate`` Zipf-popular Get() requests per step, classified
  hit/miss/degraded/failed closed-form per object with a retrieval-hop
  histogram and per-region bandwidth contention against repair
  (``region_cap`` — policies.py "serving arithmetic").

Public API:

* ``make_scenario(**kw)`` / ``from_simparams(p)`` — build one scenario cell;
* ``run_grid(cells, seeds)`` — chunked batched dispatch over cells × seeds,
  returns a ``ScenarioResult`` of ``[n_cells, n_seeds]`` arrays;
* ``run_replicated_grid(cells, seeds)`` — Ceph-like baseline, same churn;
* ``trace_grid(cells, seeds)`` — Fig. 5 per-step honest-fragment traces;
* ``targeted_grid(cells, seeds)`` — Fig. 6-bottom static attack sweep;
* ``mean_ci(x)`` — per-cell mean and 95% CI over the seed axis.

Performance knobs
-----------------

The grid runners expose three throughput knobs (benchmarked by
``benchmarks/engine_speed.py``; numbers below are the 2-core CPU host the
repo is tuned on):

* ``sampler=`` — ``"exact"`` (reference ``jax.random.binomial``),
  ``"fast"`` (threefry uniforms + inverse-CDF/Gaussian hybrid, ~3×), or
  ``"arx"`` (counter-based ARX uniforms reusing the ``kernels/prf_select``
  PRF, no per-step key hashing, ~4× over ``fast``). See
  ``repro/core/samplers.py`` for the validated error budgets. Benchmarks
  default to ``"arx"``; the API default stays ``"exact"`` so ad-hoc calls
  are reference-faithful.
* ``chunk_size=`` — split the flat ``cells × seeds`` batch into fixed-size
  chunks dispatched sequentially through ONE compiled executable (the jit
  cache is keyed on the padded maxima + chunk shape). Keeps device memory
  bounded on paper-scale sweeps and stops
  recompiles from dominating when many same-shaped sweeps run in one
  process. ``None`` = single dispatch (PR 1 behavior). Chunking is
  bit-for-bit neutral: every element's randomness derives only from its
  own ``(scenario, seed)``.
* ``devices=`` — shard each chunk's batch axis over this many local JAX
  devices (e.g. multiple CPU host devices via
  ``--xla_force_host_platform_device_count`` / ``repro.config``, or real
  accelerators). The SAME traced function compiles either way: plain
  ``jit`` at 1 device, ``jit`` of a ``shard_map`` over a 1-D ``"batch"``
  mesh above it — one sharded executable from laptop to pod, no
  per-shape ``pmap`` re-tracing. ``None``/``1`` = no device axis.
  ``chunk_size`` is rounded up to a multiple of ``devices`` and uneven
  batches are padded inside the chunker (replicas of the last element,
  sliced off afterwards) — bit-for-bit identical results either way.

The scan body itself is tuned for CPU: per-cell constants (failure
probabilities, refill rates, key material, active masks, unit costs) are
hoisted out of the scan, each step derives all of its churn/attack/repair
stream keys from one fused ``Sampler.streams`` call, state stays float32
end-to-end, and the scan is unrolled (``unroll=2``) to amortize loop
overhead.
"""
from __future__ import annotations

import collections
import functools
import math
from typing import Any, NamedTuple

import numpy as np

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec

from repro.core import policies as P
from repro.core.samplers import SAMPLERS, Sampler

# Policy ids re-exported from the shared definitions (repro.core.policies)
# so existing `scenarios.CHURN_*` / `scenarios.ADV_*` callers keep working.
HOURS_PER_YEAR = P.HOURS_PER_YEAR
CHURN_IID = P.CHURN_IID
CHURN_REGIONAL = P.CHURN_REGIONAL
CHURN_POLICIES = P.CHURN_POLICIES
CHURN_DIURNAL = P.CHURN_DIURNAL
CHURN_PARETO = P.CHURN_PARETO
ADV_STATIC = P.ADV_STATIC
ADV_ADAPTIVE = P.ADV_ADAPTIVE
ADV_TARGETED = P.ADV_TARGETED
ADV_ECLIPSE = P.ADV_ECLIPSE
ADV_COLLUDE = P.ADV_COLLUDE
ADV_ECLIPSE_TARGETED = P.ADV_ECLIPSE_TARGETED
ADVERSARY_POLICIES = P.ADVERSARY_POLICIES
N_REGIONS = P.N_REGIONS

_UNROLL = 2  # scan unroll factor (see "Performance knobs")

# Profiler names. Host spans (``jax.profiler.TraceAnnotation``) tile each
# grid call, so a device-idle gap between dispatches can be charged to what
# the host was doing; the children of ``vault.grid`` run once per call or
# once per chunk, never per element. Name scopes (``jax.named_scope``) tag
# every op of the scan body with its phase in the compiled program's
# metadata. ``perfbench/scopes.py`` reads both; ``docs/engine_guide.md``
# ("Profiling a sweep") says what each covers.
SPAN_GRID = "vault.grid"      # the whole public call
SPAN_BUILD = "vault.build"    # elements, padded maxima, runner lookup
SPAN_STACK = "vault.stack"    # one chunk's inputs stacked on the host
SPAN_LAUNCH = "vault.launch"  # compile on first call, transfer, enqueue
SPAN_FETCH = "vault.fetch"    # device wait, outputs copied to the host
SPAN_GATHER = "vault.gather"  # chunks joined, padding cut, reshaped
SCOPE_CHURN = "vault.churn"    # churn draws, burst thinning, attack
SCOPE_REPAIR = "vault.repair"  # the repair cond
SCOPE_SERVE = "vault.serve"    # the serve cond
SCOPE_MERGE = "vault.merge"    # finished elements keep their state


def _default_unroll(sampler: str) -> int:
    # unrolling doubles the traced body: worth ~2x runtime for the compact
    # fast/arx pipelines, but the exact rejection sampler's graph is huge
    # and compile-bound — keep it rolled
    return 1 if sampler == "exact" else _UNROLL


class Scenario(NamedTuple):
    """One sweep cell. Every leaf is a scalar (stacked to [B] when batched);
    all of them are traced, so cells with different values share one
    compiled executable."""

    n_objects: np.int32
    n_chunks: np.int32
    k_outer: np.float32
    k_inner: np.float32
    r_inner: np.float32
    n_nodes: np.float32
    byz_fraction: np.float32
    churn_per_year: np.float32
    cache_ttl_hours: np.float32
    step_hours: np.float32
    steps: np.int32
    churn_policy: np.int32
    adv_policy: np.int32
    burst_prob: np.float32
    burst_mult: np.float32
    adapt_boost: np.float32
    attack_frac: np.float32
    attack_step: np.int32
    eclipse_steps: np.int32
    frags_per_node: np.float32
    replication: np.float32
    read_rate: np.float32
    zipf_alpha: np.float32
    region_cap: np.float32
    cache_churn: np.int32
    seed: np.int32
    diurnal_amplitude: np.float32
    pareto_alpha: np.float32


class ScenarioResult(NamedTuple):
    """Grid-runner output; every leaf is ``[n_cells, n_seeds]`` (the trace
    leaf ``[n_cells, n_seeds, max_steps]``). ``protocol_sim.ProtocolResult``
    mirrors these fields one-to-one for cross-validation."""

    repair_traffic_units: jnp.ndarray  # object-size units (paper's unit)
    repairs: jnp.ndarray               # fragments regenerated
    cache_hits: jnp.ndarray            # warm-cache single-fragment repairs
    lost_objects: jnp.ndarray          # objects with < K_outer live chunks
    lost_fraction: jnp.ndarray         # lost_objects / n_objects
    final_honest_mean: jnp.ndarray     # mean honest frags over live groups
    honest_min: jnp.ndarray        # min honest seen in any live group
    members_max: jnp.ndarray       # max honest+byz seen in any group
    alive_frac_trace: jnp.ndarray  # [..., max_steps] live-group fraction
    # (per step; the grid runners prepend the [n_cells, n_seeds] axes)
    # --- serving workload (all zero when read_rate == 0) ---
    reads_issued: jnp.ndarray      # Get() requests issued over the run
    reads_hit: jnp.ndarray         # completed entirely from warm caches
    reads_miss: jnp.ndarray        # completed via fragment pulls + decode
    reads_degraded: jnp.ndarray    # completed past dead/eclipsed groups
    reads_failed: jnp.ndarray      # < K_outer chunks readable
    served_traffic_units: jnp.ndarray  # object units served to clients
    serve_hop_hist: jnp.ndarray    # [..., SERVE_HIST_BINS] hop histogram


def make_scenario(
    n_objects: int = 1000, n_chunks: int = 10, k_outer: int = 8,
    k_inner: int = 32, r_inner: int = 80, n_nodes: int = 100_000,
    byz_fraction: float = 0.0, churn_per_year: float = 4.0,
    cache_ttl_hours: float = 0.0, step_hours: float = 6.0,
    years: float = 1.0, steps: int | None = None,
    policy=None,
    churn_policy: int | str = CHURN_IID, adv_policy: int | str = ADV_STATIC,
    burst_prob: float = 0.05, burst_mult: float = 20.0,
    adapt_boost: float = 2.0, attack_frac: float = 0.0, attack_step: int = 0,
    eclipse_steps: int = 0, diurnal_amplitude: float = 0.6,
    pareto_alpha: float = 1.5, frags_per_node: int = 1, replication: int = 3,
    read_rate: float = 0.0, zipf_alpha: float = 1.1,
    region_cap: float = 0.0, cache_churn: bool = True,
    seed: int = 0,
) -> Scenario:
    """Build one sweep cell (all leaves traced — heterogeneous cells share
    one compiled executable).

    Deployment: ``n_objects`` stored objects of ``n_chunks`` chunks each
    (any ``k_outer`` recover an object), chunk groups of ``r_inner``
    members (any ``k_inner`` decode a chunk), on ``n_nodes`` peers of
    which ``byz_fraction`` follow the Fig. 6 Byzantine model.

    Dynamics: ``churn_per_year`` expected failures per node-year, advanced
    in ``step_hours``-wide steps for ``years`` (or an explicit ``steps``
    count, which wins); ``cache_ttl_hours`` enables the chunk cache
    (0 = off).

    Policies (shared definitions: ``repro.core.policies``): prefer the
    single ``policy=`` argument — a :class:`policies.PolicySpec` built
    from the combinators (``P.compose(P.eclipse(0.3), P.targeted_kill
    (0.25))``), a registered zoo name (``"iid_eclipse_targeted"``), or a
    plain policy name. It lowers through :func:`policies.resolve` to the
    same static ids + knob scalars, so compositions share the compiled
    executable with everything else. When given, ``policy`` sets
    ``churn_policy``/``adv_policy`` and the knob kwargs it carries
    (explicit knob kwargs it does *not* carry keep their values).

    .. deprecated:: PR 10
       The per-axis kwargs below remain supported and delegate through
       the same resolver (no behavior change), but new call sites should
       pass ``policy=``.

    ``churn_policy`` ``"iid"``/``"regional"``/``"diurnal"``/``"pareto"``
    (ids accepted) with ``burst_prob`` per-step burst probability,
    ``burst_mult`` rate multiplier, ``diurnal_amplitude`` rate-modulation
    depth, ``pareto_alpha`` session-tail index; ``adv_policy``
    ``"static"``/``"adaptive"``/``"targeted"``/``"eclipse"``/
    ``"collude"``/``"eclipse_targeted"`` with ``adapt_boost`` refill
    bias, ``attack_frac`` of ``n_nodes`` as kill budget at step
    ``attack_step`` (for the ``eclipse`` family: also the cut ring
    fraction, window ``[attack_step, attack_step + eclipse_steps)`` —
    the mean-field approximation of the protocol-level partition; the
    composed ``eclipse_targeted`` spends the same ``attack_frac`` on
    both), and ``frags_per_node`` cost amortization (A.3).
    ``replication`` sizes the Ceph-like baseline of
    :func:`run_replicated_grid`. ``seed`` is normally overridden by the
    grid runners' ``seeds`` axis.

    Serving workload (ROADMAP item 3; 0 = off): ``read_rate`` Get()
    requests per step over Zipf(``zipf_alpha``) object popularity, served
    closed-form inside the scan body; ``region_cap`` per-bandwidth-region
    per-step capacity in object units (serving and repair compete for it,
    stretching retrieval hops — :func:`policies.congestion_factor`).
    ``cache_churn=False`` restores the pre-serving optimistic cache model
    (cached copies survive their full TTL even when every holder has
    churned out) — kept only so the regression suite can demonstrate the
    over-credit; real sweeps should never disable it.

    Domain guard: ``r_inner, replication < 256`` (fast-sampler
    ``pow_int`` domain).
    """
    if policy is not None:
        low = P.resolve(policy)
        churn_policy, adv_policy = low.churn, low.adversary
        kn = low.knob_dict()
        burst_prob = kn.pop("burst_prob", burst_prob)
        burst_mult = kn.pop("burst_mult", burst_mult)
        adapt_boost = kn.pop("adapt_boost", adapt_boost)
        attack_frac = kn.pop("attack_frac", attack_frac)
        attack_step = kn.pop("attack_step", attack_step)
        eclipse_steps = kn.pop("eclipse_steps", eclipse_steps)
        diurnal_amplitude = kn.pop("diurnal_amplitude", diurnal_amplitude)
        pareto_alpha = kn.pop("pareto_alpha", pareto_alpha)
        if kn:  # a spec knob with no matching kwarg is a bug, not a no-op
            raise TypeError(f"unknown policy knobs: {sorted(kn)}")
    churn_policy = P.churn_policy_id(churn_policy)
    adv_policy = P.adv_policy_id(adv_policy)
    if r_inner >= 256 or replication >= 256:
        # the fast samplers compute (1-p)^n by 8-bit square-and-multiply
        # (samplers.pow_int) — beyond n=255 they would be silently wrong
        raise ValueError(
            f"r_inner={r_inner} / replication={replication} exceed the "
            "sampler domain (< 256); see repro/core/samplers.pow_int")
    if steps is None:
        steps = int(round(years * HOURS_PER_YEAR / step_hours))
    return Scenario(
        n_objects=np.int32(n_objects), n_chunks=np.int32(n_chunks),
        k_outer=np.float32(k_outer), k_inner=np.float32(k_inner),
        r_inner=np.float32(r_inner), n_nodes=np.float32(n_nodes),
        byz_fraction=np.float32(byz_fraction),
        churn_per_year=np.float32(churn_per_year),
        cache_ttl_hours=np.float32(cache_ttl_hours),
        step_hours=np.float32(step_hours), steps=np.int32(steps),
        churn_policy=np.int32(churn_policy), adv_policy=np.int32(adv_policy),
        burst_prob=np.float32(burst_prob), burst_mult=np.float32(burst_mult),
        adapt_boost=np.float32(adapt_boost),
        attack_frac=np.float32(attack_frac),
        attack_step=np.int32(attack_step),
        eclipse_steps=np.int32(eclipse_steps),
        frags_per_node=np.float32(frags_per_node),
        replication=np.float32(replication),
        read_rate=np.float32(read_rate), zipf_alpha=np.float32(zipf_alpha),
        region_cap=np.float32(region_cap),
        cache_churn=np.int32(bool(cache_churn)), seed=np.int32(seed),
        diurnal_amplitude=np.float32(diurnal_amplitude),
        pareto_alpha=np.float32(pareto_alpha),
    )


def from_simparams(p, **overrides) -> Scenario:
    """Build a scenario cell from a ``simulation.SimParams``."""
    kw = dict(
        n_objects=p.n_objects, n_chunks=p.n_chunks, k_outer=p.k_outer,
        k_inner=p.k_inner, r_inner=p.r_inner, n_nodes=p.n_nodes,
        byz_fraction=p.byz_fraction, churn_per_year=p.churn_per_year,
        cache_ttl_hours=p.cache_ttl_hours, step_hours=p.step_hours,
        years=p.years, seed=p.seed, churn_policy=p.churn_policy,
        diurnal_amplitude=p.diurnal_amplitude,
    )
    kw.update(overrides)
    return make_scenario(**kw)


# --------------------------------------------------------------- primitives
def _burst_draw(smp: Sampler, sc: Scenario, key):
    """Regional-burst coin for one step: (burst?, hit region index).

    Two scalar uniforms per element; the actual boosted thinning runs as a
    *second* binomial pass behind a ``lax.cond`` (see ``_burst_thin``), so
    i.i.d.-only batches never pay for it and the base churn draw keeps a
    scalar ``p`` (see ``samplers.binom_from_uniform``).
    """
    u = smp.uniform(key, (2,))
    return P.burst_from_uniforms(sc.churn_policy, sc.burst_prob, u[0], u[1])


def _targeted_kill(smp: Sampler, sc: Scenario, key, honest, alive):
    """Greedy cheapest-groups-first kill mask (A.3 cost model)."""
    cost = P.kill_cost(honest, sc.k_inner, sc.frags_per_node)
    cost = jnp.where(alive, cost, jnp.inf)
    # random tiebreak: equal-cost groups are indistinguishable behind the
    # outer code's opacity (same argument as targeted_attack_vault)
    tie = smp.uniform(key, cost.shape) * 1e-3
    order = jnp.argsort(cost + tie)
    csum = jnp.cumsum(cost[order])
    budget = sc.attack_frac * sc.n_nodes
    kill_sorted = csum <= budget
    return jnp.zeros_like(kill_sorted).at[order].set(kill_sorted)


# ------------------------------------------------------------- vault engine
class _Static(NamedTuple):
    max_groups: int
    max_objects: int
    max_steps: int
    # the chunk count every element of the batch shares; 0 when they differ
    shared_chunks: int = 0


# Runners built by ``_vault_batch`` / ``_targeted_batch``, by the way they
# count groups into objects (``_object_count_path``): ``"reshape"`` or
# ``"scatter"``. The word is also the ``object_counts`` argument of the
# ``vault.build`` span of each ``run_grid`` / ``targeted_grid`` call.
OBJECT_COUNT_PATHS = collections.Counter()


def _shared_chunks(flat) -> int:
    """The ``n_chunks`` every element of ``flat`` has, or 0 if they differ."""
    chunks = {int(s.n_chunks) for s in flat}
    return chunks.pop() if len(chunks) == 1 else 0


def _object_count_path(st: _Static) -> str:
    return "reshape" if st.shared_chunks else "scatter"


def _per_object(st: _Static, sc: Scenario, mask):
    """Per-object count of a ``[max_groups]`` group mask: float32
    ``[max_objects]``.

    Groups are object-major: group ``g`` belongs to object ``g //
    n_chunks``. When the batch shares one chunk count ``C``
    (``st.shared_chunks``), ``max_groups`` is exactly ``max_objects * C``
    and each object's groups are one contiguous run. The mask is then
    viewed as rows of ``k`` objects, ``k * C`` groups that fill whole
    128-lane tiles, and each row is multiplied by a 0/1 ``[k * C, k]``
    matrix that adds up each object's ``C`` chunks. On a v5e that costs
    11 us a call for 4 x 100K groups, against 39 us for a reduce over a
    ``[max_objects, C]`` view (which pads ``C`` lanes to 128) and 840 us
    for ``segment_sum``. A batch that mixes chunk counts still sums with
    ``segment_sum`` over each group's object id, which a TPU lowers to a
    scatter that applies its updates one after another (3.5 ms a call in
    the D1 serve step). bfloat16 holds 0 and 1 exactly and the product
    accumulates in float32; masks are 0/1 and an object has at most ``C``
    of them, so both paths add exactly and agree bit for bit.
    """
    if st.shared_chunks:
        C = st.shared_chunks
        k = 128 // math.gcd(C, 128)
        rows = -(-st.max_objects // k)
        m = jnp.pad(mask, (0, rows * k * C - st.max_groups))
        pick = jnp.arange(k * C)[:, None] // C == jnp.arange(k)
        counts = jnp.dot(m.reshape(rows, k * C).astype(jnp.bfloat16),
                         pick.astype(jnp.bfloat16),
                         preferred_element_type=jnp.float32)
        return counts.reshape(rows * k)[:st.max_objects]
    gidx = jnp.arange(st.max_groups, dtype=jnp.int32)
    obj_id = jnp.minimum(gidx // jnp.maximum(sc.n_chunks, 1),
                         st.max_objects - 1)
    return jax.ops.segment_sum(mask.astype(jnp.float32), obj_id,
                               num_segments=st.max_objects)


class _Inv(NamedTuple):
    """Per-element scan invariants, hoisted out of the step body."""

    base: Any              # sampler key carrier
    active: jnp.ndarray    # [G] bool: group is real, not padding
    p_fail: jnp.ndarray    # i.i.d. per-step failure probability
    refill_p: jnp.ndarray  # byzantine refill probability during repair
    frag_units: jnp.ndarray
    chunk_units: jnp.ndarray
    n_groups: jnp.ndarray  # float active-group count (alive-frac denom)


def _vault_init(st: _Static, smp: Sampler, sc: Scenario):
    """Per-element invariants + initial state (vmapped over the batch)."""
    G = st.max_groups
    gidx = jnp.arange(G, dtype=jnp.int32)
    active = gidx < sc.n_objects * sc.n_chunks
    base = smp.base(sc.seed)
    inv = _Inv(
        base=base,
        active=active,
        # pareto churn swaps in the protected-cohort mean-field hazard
        # (policies.pareto_p_fail, abstraction leak #5); every other
        # policy gets the plain i.i.d. probability value-identically
        p_fail=P.pareto_p_fail(
            sc.churn_policy, sc.churn_per_year, sc.pareto_alpha,
            sc.step_hours, P.p_fail_step(sc.churn_per_year, sc.step_hours)),
        refill_p=P.refill_byz_probability(
            sc.adv_policy, sc.byz_fraction, sc.adapt_boost),
        frag_units=1.0 / (sc.k_outer * sc.k_inner),
        chunk_units=1.0 / sc.k_outer,
        n_groups=jnp.maximum(sc.n_objects * sc.n_chunks, 1).astype(
            jnp.float32),
    )
    (k_init,) = smp.streams(smp.fold(base, 0), 1)
    byz0 = smp.binom(k_init, jnp.where(active, sc.r_inner, 0.0),
                     sc.byz_fraction)
    honest0 = jnp.where(active, sc.r_inner - byz0, 0.0)
    alive0 = active & (honest0 >= sc.k_inner)
    cache0 = jnp.zeros(G)  # client seeds caches at store time (t=0)
    # cached-copy holder count: the storing client seeds every group member
    # (vault._store_chunk caches at all r_inner holders when the TTL is on)
    cache_h0 = jnp.where(active & (sc.cache_ttl_hours > 0.0),
                         sc.r_inner, 0.0)
    zero = jnp.zeros(())
    state = (honest0, byz0, alive0, cache0, cache_h0,
             0.0, 0.0, 0.0, jnp.inf, 0.0,
             # serving accumulators: issued/hit/miss/degraded/failed reads,
             # served object units, retrieval-hop histogram
             zero, zero, zero, zero, zero, zero,
             jnp.zeros(P.SERVE_HIST_BINS))
    return inv, state


def _vault_churn(st: _Static, smp: Sampler, sc: Scenario, inv: _Inv,
                 state, t):
    """Per-element churn half-step: thin members with the *scalar* i.i.d.
    probability, return burst coordinates + repair/attack/burst keys."""
    kt = smp.fold(inv.base, t + 1)
    kc, kb, kp, kr, ka, kxh, kxb = smp.streams(kt, 7)
    honest, byz = state[0], state[1]
    # diurnal churn recomputes this step's probability from the modulated
    # rate; every other policy passes inv.p_fail through value-identically
    p_fail = P.diurnal_p_fail(sc.churn_policy, sc.churn_per_year,
                              sc.diurnal_amplitude, t, sc.step_hours,
                              inv.p_fail)
    # adaptive adversary: byzantine members never leave voluntarily
    p_fail_b = P.byz_churn_probability(sc.adv_policy, p_fail)
    h = honest - smp.binom(kc, honest, p_fail)
    b = byz - smp.binom(kb, byz, p_fail_b)
    burst, region = _burst_draw(smp, sc, kp)
    return h, b, burst, region, (kxh, kxb), kr, ka


def _burst_thin(st: _Static, smp: Sampler, sc: Scenario, inv: _Inv,
                h, b, burst, region, kx):
    """Per-element regional-burst second thinning (traced inside a cond:
    only executed on steps where some element actually bursts)."""
    gidx = jnp.arange(st.max_groups, dtype=jnp.int32)
    p_extra = P.burst_extra_probability(inv.p_fail, sc.burst_mult)
    hit = burst & (P.group_domain(gidx) == region)
    dh = smp.binom(kx[0], h, p_extra)
    db = smp.binom(kx[1], b,
                   P.byz_churn_probability(sc.adv_policy, p_extra))
    return h - jnp.where(hit, dh, 0.0), b - jnp.where(hit, db, 0.0)


def _vault_attack(smp: Sampler, sc: Scenario, h, alive, ka):
    """Per-element targeted greedy kill (only traced inside the cond).
    Family predicate: fires for ``targeted`` and the composed
    ``eclipse_targeted`` product alike."""
    attack = P.targeted_flag(sc.adv_policy)
    kill = _targeted_kill(smp, sc, ka, h, alive)
    return jnp.where(attack & kill, jnp.minimum(h, sc.k_inner - 1.0), h)


def _vault_repair(st: _Static, smp: Sampler, with_cache: bool, sc: Scenario,
                  inv: _Inv, state, h, b, kr, t):
    """Per-element repair + traffic half-step.

    Compiled twice — ``with_cache`` True (per-element TTL blend, holder
    churn on the cached copies) and False (all TTLs zero: no warm/miss
    bookkeeping at all) — and selected by a batch-level ``lax.cond``, so
    cache-free sweeps skip the extra [G]-wide selects and reductions
    entirely.

    Returns the repair part of the state plus the post-repair warm-cache
    mask and this step's repair traffic, both consumed by the serving
    stage (:func:`_vault_serve`).
    """
    (_, _, alive, cache_t, cache_h,
     traffic, repairs, hits, hmin, mmax) = state[:10]
    now = (t + 1.0) * sc.step_hours

    a = alive & (h >= sc.k_inner)  # decode impossible => absorbing
    deficit = jnp.maximum(jnp.where(a, sc.r_inner - (h + b), 0.0), 0.0)
    # eclipse mean-field (policies.ADV_ECLIPSE): groups inside the cut ring
    # segment get no repair — no refills, traffic, or cache warming — while
    # the partition window is open; churn keeps thinning them meanwhile.
    # One select per step; identity (all-False mask) for other policies.
    gidx_e = jnp.arange(st.max_groups, dtype=jnp.int32)
    ecl = (P.eclipse_active(sc.adv_policy, t, sc.attack_step,
                            sc.eclipse_steps)
           & P.eclipse_groups(gidx_e, sc.attack_frac, inv.n_groups))
    deficit = jnp.where(ecl, 0.0, deficit)
    # collusion withholding (policies.ADV_COLLUDE): every byzantine member
    # of a repairing group serves one corrupt row per decode gather that
    # is pulled, integrity-checked, and discarded — wasted transfers hit
    # the traffic lane only (b here is the pre-refill byzantine count the
    # gather actually sees). Charged as a separate additive term (exactly
    # zero for other policies) so the pre-existing traffic expressions
    # keep their fp summation order bit-identically.
    wasted_pulls = jnp.where(deficit > 0.0,
                             P.collusion_extra_pulls(sc.adv_policy, b), 0.0)
    new_b = smp.binom(kr, deficit, inv.refill_p)
    h = h + (deficit - new_b)
    b = b + new_b

    t_plain = (deficit.sum() * sc.k_inner * inv.frag_units
               + wasted_pulls.sum() * inv.frag_units)
    if with_cache:
        has_cache = sc.cache_ttl_hours > 0.0
        # churn-aware cache: holders of cached copies die like any other
        # member, so a copy is warm only while ≥1 holder survives AND its
        # TTL holds. cache_churn=0 freezes the holder count (the old
        # optimistic model, kept for the leak-regression test only).
        # Key material: a second fold at a disjoint counter (t+1+2^20), so
        # the seven original per-step streams stay bit-identical; the arx
        # fold is collision-free here for any horizon below 2^20 steps.
        (kcd,) = smp.streams(smp.fold(inv.base, t + 1 + (1 << 20)), 1)
        dead_h = smp.binom(kcd, cache_h, inv.p_fail)
        cache_h = jnp.where(sc.cache_churn > 0,
                            jnp.maximum(cache_h - dead_h, 0.0), cache_h)
        warm = (((now - cache_t) <= sc.cache_ttl_hours)
                & (cache_h >= 1.0))
        hit_frags = jnp.where(warm, deficit, jnp.maximum(deficit - 1.0, 0.0))
        miss_pulls = jnp.where(~warm & (deficit > 0), 1.0, 0.0)
        # colluder waste only on the miss path (warm repairs pull the
        # cached chunk from an honest holder — no group gather)
        t_cached = (hit_frags.sum() * inv.frag_units
                    + miss_pulls.sum() * inv.chunk_units
                    + (miss_pulls * wasted_pulls).sum() * inv.frag_units)
        refresh = has_cache & (miss_pulls > 0)
        new_cache = jnp.where(refresh, now, cache_t)
        # a miss-path repairer re-caches the decoded chunk: one new holder
        new_cache_h = jnp.where(refresh, 1.0, cache_h)
        traffic_add = jnp.where(has_cache, t_cached, t_plain)
        hits_add = jnp.where(has_cache, hit_frags.sum(), 0.0)
        warm_out = has_cache & (warm | refresh)
    else:
        new_cache = cache_t
        new_cache_h = cache_h
        traffic_add = t_plain
        hits_add = 0.0
        warm_out = jnp.zeros_like(a)

    new_state = (
        h, b, a, new_cache, new_cache_h,
        traffic + traffic_add,
        repairs + deficit.sum(),
        hits + hits_add,
        jnp.minimum(hmin, jnp.where(a, h, jnp.inf).min()),
        jnp.maximum(mmax, jnp.where(inv.active, h + b, 0.0).max()),
    )
    alive_frac = a.sum() / inv.n_groups
    return new_state, warm_out, traffic_add, alive_frac


def _vault_serve(st: _Static, sc: Scenario, inv: _Inv, rep_state, warm,
                 traffic_add, srv, t):
    """Per-element closed-form serving half-step (traced inside a cond:
    only executed when some batch element has ``read_rate > 0``).

    ``read_rate`` Get() requests are spread over objects by Zipf(α)
    popularity and classified per object from this step's group state
    (disjoint buckets, priority failed > degraded > hit > miss — the same
    rule the protocol-level ``_serve_tick`` applies per sampled request).
    Completed reads retrieve ``K_outer`` chunks = 1 object unit. Retrieval
    hops land in a histogram after congestion stretch: this step's repair
    + serving units spread over ``N_BW_REGIONS`` bandwidth domains against
    ``region_cap`` (:func:`policies.congestion_factor`).
    """
    issued, r_hit, r_miss, r_degr, r_fail, served, hist = srv
    a = rep_state[2]
    gidx = jnp.arange(st.max_groups, dtype=jnp.int32)
    ecl = (P.eclipse_active(sc.adv_policy, t, sc.attack_step,
                            sc.eclipse_steps)
           & P.eclipse_groups(gidx, sc.attack_frac, inv.n_groups))
    readable = a & ~ecl        # eclipsed groups hold data but can't serve
    warm_r = readable & warm

    n_read = _per_object(st, sc, readable)
    n_warm = _per_object(st, sc, warm_r)
    oidx = jnp.arange(st.max_objects, dtype=jnp.int32)
    obj_active = oidx < sc.n_objects
    load = sc.read_rate * P.zipf_weights(oidx, sc.zipf_alpha, sc.n_objects)

    failed_o = obj_active & (n_read < sc.k_outer)
    degr_o = obj_active & ~failed_o & (n_read < sc.n_chunks)
    hit_o = (obj_active & ~failed_o & ~degr_o
             & (n_warm >= sc.k_outer))  # all K_outer pulls can be cache pulls
    miss_o = obj_active & ~failed_o & ~degr_o & ~hit_o

    # fractional loads: a fixed addition order keeps these sums, and so the
    # serving fields, bit-identical at any chunk_size or device count
    n_fail = P.fixed_order_sum(load * failed_o)
    n_degr = P.fixed_order_sum(load * degr_o)
    n_hit = P.fixed_order_sum(load * hit_o)
    n_miss = P.fixed_order_sum(load * miss_o)
    served_add = n_hit + n_miss + n_degr  # completed reads × 1 object unit

    # serving and repair compete for the same per-region links
    per_region = (traffic_add + served_add) / P.N_BW_REGIONS
    factor = P.congestion_factor(per_region, sc.region_cap)
    for count, hops in ((n_hit, P.SERVE_HOPS_HIT),
                        (n_miss, P.SERVE_HOPS_MISS),
                        (n_degr, P.SERVE_HOPS_MISS
                         + P.SERVE_HOPS_DEGRADED_EXTRA)):
        hbin = P.effective_hops(hops, factor).astype(jnp.int32)
        hist = hist.at[hbin].add(count)

    # weights sum to 1 over active objects, so the four buckets conserve
    # sc.read_rate exactly (tests/test_serving_properties.py pins this)
    return (issued + sc.read_rate, r_hit + n_hit, r_miss + n_miss,
            r_degr + n_degr, r_fail + n_fail, served + served_add, hist)


def _vault_finalize(st: _Static, sc: Scenario, state) -> ScenarioResult:
    (honest, _, alive, _, _, traffic, repairs, hits, hmin, mmax,
     issued, r_hit, r_miss, r_degr, r_fail, served, hist) = state
    chunks_alive = _per_object(st, sc, alive)
    obj_active = jnp.arange(st.max_objects) < sc.n_objects
    lost = (obj_active & (chunks_alive < sc.k_outer)).sum()
    n_alive = alive.sum()
    fhm = jnp.where(n_alive > 0,
                    (honest * alive).sum() / jnp.maximum(n_alive, 1.0), 0.0)
    return ScenarioResult(
        repair_traffic_units=traffic, repairs=repairs, cache_hits=hits,
        lost_objects=lost.astype(jnp.int32),
        lost_fraction=lost / jnp.maximum(sc.n_objects, 1),
        final_honest_mean=fhm,
        honest_min=jnp.where(jnp.isfinite(hmin), hmin, 0.0),
        members_max=mmax, alive_frac_trace=jnp.zeros(()),  # filled by caller
        reads_issued=issued, reads_hit=r_hit, reads_miss=r_miss,
        reads_degraded=r_degr, reads_failed=r_fail,
        served_traffic_units=served, serve_hop_hist=hist,
    )


def _where_on(on, new, old):
    """Select per batch element, broadcasting [B] over state leaves."""
    mask = on.reshape(on.shape + (1,) * (new.ndim - on.ndim))
    return jnp.where(mask, new, old)


_BATCH_AXIS = "batch"  # the 1-D mesh axis the grid batch shards over


def _ndev(devices: int | None) -> int:
    """Validate and normalize the ``devices=`` knob (before any mesh or
    compiled runner is built, so the error is actionable)."""
    ndev = int(devices or 1)
    if ndev > 1:
        avail = jax.local_device_count()
        if ndev > avail:
            raise ValueError(
                f"devices={ndev} but only {avail} local JAX device(s); "
                "set --xla_force_host_platform_device_count or lower it")
    return ndev


def _compile_runner(run, devices: int = 1):
    """Compile a batched ``run`` into one executable for any topology.

    This is the single sharded-runner helper behind all four grid
    factories (``_vault_batch`` / ``_repl_batch`` / ``_trace_batch`` /
    ``_targeted_batch``). ``devices <= 1`` is a plain ``jit``. Otherwise
    the SAME traced ``run`` is wrapped in ``shard_map`` over a 1-D
    ``Mesh`` of the first ``devices`` local devices: every input leaf's
    leading batch axis splits across the mesh (``PartitionSpec``
    prefixes broadcast over the pytree), outputs concatenate back along
    it. No per-shape ``pmap`` re-trace, no host-side
    ``[devices, B/devices]`` reshape.

    Bit-exactness: the per-element math never crosses batch lanes (no
    collectives anywhere in the scan body), so shards compute exactly
    what the single-device executable computes. The only semantic
    difference is that batch-global ``.any()`` cond predicates become
    per-shard — and every such cond selects between branches that are
    arithmetically identical by construction (the conds exist purely to
    skip work; see ``_vault_repair``'s docstring). Sums of fractional
    floats are added in a fixed order (:func:`policies.fixed_order_sum`),
    since a TPU compiler orders a plain reduction per batch width; every
    other sum adds integer-valued floats, exact in any order. Locked down by
    ``scripts/smoke_devices.py`` and the subprocess tests in
    ``tests/test_scenarios.py`` / ``tests/test_samplers.py``.

    Inputs are deliberately NOT donated (``donate_argnums``). Donation +
    the persistent compilation cache mis-executes on replay: a freshly
    compiled CPU executable refuses the aliasing ("Some donated buffers
    were not usable" — int32 scenario leaves can't alias float outputs)
    and runs correctly, but the *deserialized* cache entry honors the
    requested input→output aliases, so the donated input buffer is freed
    while live outputs still point into it and the next executable to
    allocate scribbles over the results. Reproduced deterministically:
    warm-cache process running two runners corrupts the first runner's
    outputs (random fields each run); identical process without donation
    is bit-exact. Donation only ever bought flat memory on chunked
    sweeps — never correctness or measured speed on CPU — so it loses to
    the cache. ``tests/test_scenarios.py::
    test_warm_cache_two_runners_bitexact`` locks the regression down.
    """
    if devices <= 1:
        return jax.jit(run)
    mesh = Mesh(np.asarray(jax.devices()[:devices]), (_BATCH_AXIS,))
    sharded = shard_map(run, mesh=mesh,
                        in_specs=(PartitionSpec(_BATCH_AXIS),),
                        out_specs=PartitionSpec(_BATCH_AXIS),
                        check_vma=False)
    return jax.jit(sharded)


@functools.lru_cache(maxsize=None)
def _vault_batch(st: _Static, sampler: str, unroll: int = _UNROLL,
                 devices: int = 1):
    """Compile the batched engine: one lax.scan over time whose body is
    vmapped over the batch. (scan-of-vmap, not vmap-of-scan, so the
    targeted-attack sort can sit behind a real lax.cond and only execute
    on actual attack steps instead of being select-ed every step.)

    The cache key is ``(padded maxima and shared chunk count, sampler,
    unroll, devices)``; jit's own executable cache then keys on the batch
    shape, so fixed-size chunked dispatch reuses one compiled executable
    for every chunk. ``devices > 1`` shards the batch axis over a 1-D mesh
    — see :func:`_compile_runner`. ``st.shared_chunks`` picks how groups
    are counted into objects (:func:`_per_object`).
    """
    OBJECT_COUNT_PATHS[_object_count_path(st)] += 1
    smp = SAMPLERS[sampler]
    churn = jax.vmap(functools.partial(_vault_churn, st, smp),
                     in_axes=(0, 0, 0, None))
    burst_thin = jax.vmap(functools.partial(_burst_thin, st, smp))
    attack = jax.vmap(functools.partial(_vault_attack, smp))
    repair_cache = jax.vmap(functools.partial(_vault_repair, st, smp, True),
                            in_axes=(0, 0, 0, 0, 0, 0, None))
    repair_plain = jax.vmap(functools.partial(_vault_repair, st, smp, False),
                            in_axes=(0, 0, 0, 0, 0, 0, None))
    serve = jax.vmap(functools.partial(_vault_serve, st),
                     in_axes=(0, 0, 0, 0, 0, 0, None))

    def run(scb: Scenario):
        inv, init = jax.vmap(functools.partial(_vault_init, st, smp))(scb)
        cache_any = (scb.cache_ttl_hours > 0.0).any()
        serve_any = (scb.read_rate > 0.0).any()

        def body(state, t):
            with jax.named_scope(SCOPE_CHURN):
                h, b, burst, region, kx, kr, ka = churn(scb, inv, state, t)
                h, b = jax.lax.cond(
                    burst.any(),
                    lambda args: burst_thin(scb, inv, *args),
                    lambda args: (args[0], args[1]),
                    (h, b, burst, region, kx))
                hit_now = (P.targeted_flag(scb.adv_policy)
                           & (t == scb.attack_step))
                h = jax.lax.cond(
                    hit_now.any(),
                    lambda args: jnp.where(hit_now[:, None],
                                           attack(scb, *args), args[0]),
                    lambda args: args[0], (h, state[2], ka))
            with jax.named_scope(SCOPE_REPAIR):
                rep_state, warm, traffic_add, alive_frac = jax.lax.cond(
                    cache_any,
                    lambda args: repair_cache(*args),
                    lambda args: repair_plain(*args),
                    (scb, inv, state, h, b, kr, t))
            with jax.named_scope(SCOPE_SERVE):
                srv = jax.lax.cond(
                    serve_any,
                    lambda args: serve(*args),
                    lambda args: args[5],
                    (scb, inv, rep_state, warm, traffic_add, state[10:], t))
            with jax.named_scope(SCOPE_MERGE):
                on = t < scb.steps
                state = tuple(_where_on(on, n, o)
                              for n, o in zip(rep_state + srv, state))
                return state, jnp.where(on, alive_frac,
                                        state[2].sum(-1) / inv.n_groups)

        state, alive_tr = jax.lax.scan(body, init, jnp.arange(st.max_steps),
                                       unroll=unroll)
        res = jax.vmap(functools.partial(_vault_finalize, st))(scb, state)
        return res._replace(alive_frac_trace=alive_tr.T)

    return _compile_runner(run, devices)


def _stack(cells: list[Scenario]) -> Scenario:
    return jax.tree_util.tree_map(lambda *xs: np.stack(xs), *cells)


def _product(cells, seeds) -> list[Scenario]:
    out = []
    for cell in cells:
        if isinstance(cell, dict):
            cell = make_scenario(**cell)
        for s in seeds:
            out.append(cell._replace(seed=np.int32(s)))
    return out


def _dispatch(runner, batch):
    """Invoke a compiled runner (single indirection point for all four
    grid runners — kept so chunked and single dispatch share one call
    site). Donation was removed here (see :func:`_compile_runner`), so
    no warning filtering is needed anymore; pytest.ini still escalates
    any donation warning to an error to keep it that way."""
    return runner(batch)


def _run_chunked(flat: list, runner, chunk_size: int | None,
                 devices: int, n_seeds: int):
    """Dispatch ``flat`` elements through ``runner`` in fixed-size chunks
    and return host arrays of shape ``[len(flat) // n_seeds, n_seeds, ...]``.

    ``chunk_size=None`` (or one at least the batch) is one dispatch.
    Otherwise the element list is padded (with replicas of the last
    element, sliced off afterwards) to a multiple of ``chunk_size`` and
    dispatched chunk by chunk — every chunk has identical shapes, so jit
    compiles exactly once. ``runner`` is already topology-bound (see
    :func:`_compile_runner`); with ``devices > 1`` the chunk size is
    rounded up to a multiple of the device count so ``shard_map`` can split
    the batch axis evenly — uneven batches are handled entirely by the same
    padding path. Chunking and sharding are bit-for-bit neutral: element
    randomness depends only on the element itself, never on its batch
    position. Each chunk's outputs reach the host inside ``vault.fetch``.
    """
    B = len(flat)
    chunk_size = min(chunk_size or B, B)
    chunk_size = -(-chunk_size // devices) * devices
    padded = list(flat) + [flat[-1]] * ((-B) % chunk_size)
    outs = []
    for i in range(0, len(padded), chunk_size):
        with jax.profiler.TraceAnnotation(SPAN_STACK):
            batch = _stack(padded[i:i + chunk_size])
        with jax.profiler.TraceAnnotation(SPAN_LAUNCH):
            out = _dispatch(runner, batch)
        with jax.profiler.TraceAnnotation(SPAN_FETCH):
            # rebinding ``out`` frees the device buffers inside this span
            out = jax.tree_util.tree_map(np.asarray, out)
            outs.append(out)
    with jax.profiler.TraceAnnotation(SPAN_GATHER):
        cat = outs[0] if len(outs) == 1 else jax.tree_util.tree_map(
            lambda *xs: np.concatenate(xs, axis=0), *outs)
        return jax.tree_util.tree_map(
            lambda x: x[:B].reshape(B // n_seeds, n_seeds, *x.shape[1:]), cat)


def _grid(cells, seeds, chunk_size: int | None, devices: int | None,
          build):
    """The one path of the four grid runners: ``build(flat, ndev)`` returns
    the compiled runner, the elements it takes (``flat``, the ``cells x
    seeds`` scenarios, cell-major, or values derived from them) and the
    runner's object-count path (``None`` for runners that count none),
    recorded on the ``vault.build`` span; the result's leaves are
    ``[n_cells, n_seeds, ...]`` host arrays."""
    with jax.profiler.TraceAnnotation(SPAN_GRID):
        with jax.profiler.TraceAnnotation(SPAN_BUILD) as span:
            seeds = list(seeds)
            ndev = _ndev(devices)
            runner, elements, counts = build(_product(cells, seeds), ndev)
            if counts:
                span.set_metadata(object_counts=counts)
        return _run_chunked(elements, runner, chunk_size, ndev, len(seeds))


def run_grid(cells, seeds=range(8), sampler: str = "exact",
             chunk_size: int | None = None, devices: int | None = None,
             unroll: int | None = None) -> ScenarioResult:
    """Run cells × seeds vault scenarios as chunked batched dispatches.

    ``cells``: scenarios or kwargs-dicts for :func:`make_scenario`.
    ``sampler`` / ``chunk_size`` / ``devices``: see "Performance knobs" in
    the module docstring. Returns a :class:`ScenarioResult` whose leaves
    have shape ``[n_cells, n_seeds]`` (the trace leaf
    ``[n_cells, n_seeds, max_steps]``).
    """
    unroll = _default_unroll(sampler) if unroll is None else unroll

    def build(flat, ndev):
        st = _Static(
            max_groups=max(int(s.n_objects * s.n_chunks) for s in flat),
            max_objects=max(int(s.n_objects) for s in flat),
            max_steps=max(int(s.steps) for s in flat),
            shared_chunks=_shared_chunks(flat),
        )
        return (_vault_batch(st, sampler, unroll, ndev), flat,
                _object_count_path(st))

    return _grid(cells, seeds, chunk_size, devices, build)


# ------------------------------------------------------ replicated baseline
def _repl_init(st: _Static, smp: Sampler, sc: Scenario):
    O = st.max_objects
    oidx = jnp.arange(O, dtype=jnp.int32)
    active = oidx < sc.n_objects
    base = smp.base(sc.seed + 1)
    (k_init,) = smp.streams(smp.fold(base, 0), 1)
    bad0 = smp.binom(k_init, jnp.where(active, sc.replication, 0.0),
                     sc.byz_fraction)
    good0 = jnp.where(active, sc.replication - bad0, 0.0)
    alive0 = active & (good0 >= 1.0)
    inv = (base, active, P.p_fail_step(sc.churn_per_year, sc.step_hours))
    return inv, (good0, bad0, alive0, 0.0, 0.0)


def _repl_churn(st: _Static, smp: Sampler, sc: Scenario, inv, carry, t):
    base, _, p_fail = inv
    good, bad = carry[0], carry[1]
    kt = smp.fold(base, t + 1)
    kg, kb, kp, kr, kxg, kxb = smp.streams(kt, 6)
    g = good - smp.binom(kg, good, p_fail)
    b = bad - smp.binom(kb, bad, p_fail)
    burst, region = _burst_draw(smp, sc, kp)
    return g, b, burst, region, (kxg, kxb), kr


def _repl_burst_thin(st: _Static, smp: Sampler, sc: Scenario, inv,
                     g, b, burst, region, kx):
    oidx = jnp.arange(st.max_objects, dtype=jnp.int32)
    p_extra = P.burst_extra_probability(inv[2], sc.burst_mult)
    hit = burst & (P.group_domain(oidx) == region)
    dg = smp.binom(kx[0], g, p_extra)
    db = smp.binom(kx[1], b, p_extra)
    return g - jnp.where(hit, dg, 0.0), b - jnp.where(hit, db, 0.0)


def _repl_repair(st: _Static, smp: Sampler, sc: Scenario, inv, carry,
                 g, b, kr, t):
    _, _, alive, traffic, repairs = carry
    on = t < sc.steps
    a = alive & (g >= 1.0)  # no good replica left => object gone
    deficit = jnp.maximum(jnp.where(a, sc.replication - (g + b), 0.0), 0.0)
    # repair copies an unverifiable replica: good iff source good AND
    # the new holder is honest (contagious decay, Fig. 6); the source mix
    # is per-object, so this is the one genuinely per-lane ``p`` draw
    remaining = jnp.maximum(g + b, 1.0)
    p_good = jnp.where(a, g / remaining, 0.0) * (1.0 - sc.byz_fraction)
    new_good = smp.binom(kr, deficit, jnp.clip(p_good, 0.0, 1.0))
    g = g + new_good
    b = b + (deficit - new_good)
    pick = lambda new, old: jnp.where(on, new, old)
    carry = (pick(g, carry[0]), pick(b, carry[1]), jnp.where(on, a, alive),
             pick(traffic + deficit.sum(), traffic),
             pick(repairs + deficit.sum(), repairs))
    alive_frac = carry[2].sum() / jnp.maximum(sc.n_objects, 1)
    return carry, alive_frac


def _repl_finalize(st: _Static, sc: Scenario, inv, carry) -> ScenarioResult:
    good, bad, alive, traffic, repairs = carry
    active = inv[1]
    lost = (active & ~alive).sum()
    n_alive = alive.sum()
    fhm = jnp.where(n_alive > 0,
                    (good * alive).sum() / jnp.maximum(n_alive, 1.0), 0.0)
    alive_min = jnp.where(alive, good, jnp.inf).min()
    zero = jnp.zeros(())
    return ScenarioResult(
        repair_traffic_units=traffic, repairs=repairs,
        cache_hits=zero, lost_objects=lost.astype(jnp.int32),
        lost_fraction=lost / jnp.maximum(sc.n_objects, 1),
        final_honest_mean=fhm,
        honest_min=jnp.where(jnp.isfinite(alive_min), alive_min, 0.0),
        members_max=(good + bad).max(), alive_frac_trace=zero,
        # the replicated baseline has no serving layer
        reads_issued=zero, reads_hit=zero, reads_miss=zero,
        reads_degraded=zero, reads_failed=zero, served_traffic_units=zero,
        serve_hop_hist=jnp.zeros(P.SERVE_HIST_BINS),
    )


@functools.lru_cache(maxsize=None)
def _repl_batch(st: _Static, sampler: str, unroll: int = _UNROLL,
                devices: int = 1):
    """Scan-of-vmap replicated baseline (same scaffolding as the vault
    engine, so the regional-burst thinning sits behind a real cond)."""
    smp = SAMPLERS[sampler]
    churn = jax.vmap(functools.partial(_repl_churn, st, smp),
                     in_axes=(0, 0, 0, None))
    burst_thin = jax.vmap(functools.partial(_repl_burst_thin, st, smp))
    repair = jax.vmap(functools.partial(_repl_repair, st, smp),
                      in_axes=(0, 0, 0, 0, 0, 0, None))

    def run(scb: Scenario):
        inv, init = jax.vmap(functools.partial(_repl_init, st, smp))(scb)

        def body(carry, t):
            g, b, burst, region, kx, kr = churn(scb, inv, carry, t)
            g, b = jax.lax.cond(
                burst.any(),
                lambda args: burst_thin(scb, inv, *args),
                lambda args: (args[0], args[1]),
                (g, b, burst, region, kx))
            return repair(scb, inv, carry, g, b, kr, t)

        carry, alive_tr = jax.lax.scan(body, init, jnp.arange(st.max_steps),
                                       unroll=unroll)
        res = jax.vmap(functools.partial(_repl_finalize, st))(scb, inv, carry)
        return res._replace(alive_frac_trace=alive_tr.T)

    return _compile_runner(run, devices)


def run_replicated_grid(cells, seeds=range(8), sampler: str = "exact",
                        chunk_size: int | None = None,
                        devices: int | None = None) -> ScenarioResult:
    """Ceph-like replicated baseline, same grid semantics as run_grid."""
    def build(flat, ndev):
        st = _Static(max_groups=1,
                     max_objects=max(int(s.n_objects) for s in flat),
                     max_steps=max(int(s.steps) for s in flat))
        return (_repl_batch(st, sampler, _default_unroll(sampler), ndev),
                flat, None)

    return _grid(cells, seeds, chunk_size, devices, build)


# --------------------------------------------------------- Fig 5 trace grid
def _trace_single(max_steps: int, smp: Sampler, repair_interval_hours,
                  sc: Scenario):
    base = smp.base(sc.seed)
    p_fail = P.p_fail_step(sc.churn_per_year, sc.step_hours)
    (k_init,) = smp.streams(smp.fold(base, 0), 1)
    byz0 = smp.binom(k_init, sc.r_inner, sc.byz_fraction)
    honest0 = sc.r_inner - byz0

    def step(carry, t):
        honest, byz, since, absorbed = carry
        kt = smp.fold(base, t + 1)
        kh, kb, kr = smp.streams(kt, 3)
        h = honest - smp.binom(kh, honest, p_fail)
        b = byz - smp.binom(kb, byz, p_fail)
        absorbed_n = absorbed | (h < sc.k_inner)
        since_n = since + sc.step_hours
        do_rep = ~absorbed_n & (since_n >= repair_interval_hours)
        deficit = jnp.maximum(sc.r_inner - (h + b), 0.0)
        nb = smp.binom(kr, deficit, sc.byz_fraction)
        h = jnp.where(do_rep, h + deficit - nb, h)
        b = jnp.where(do_rep, b + nb, b)
        since_n = jnp.where(do_rep, 0.0, since_n)
        # absorbed groups freeze (numpy reference stops simulating them);
        # so do cells whose own horizon (sc.steps) has passed in a padded
        # heterogeneous batch
        frozen = absorbed | (t >= sc.steps)
        pick = lambda new, old: jnp.where(frozen, old, new)
        carry = (pick(h, honest), pick(b, byz), pick(since_n, since),
                 jnp.where(t >= sc.steps, absorbed, absorbed_n))
        return carry, carry[0]

    init = (honest0, byz0, 0.0, jnp.zeros((), bool))
    _, trace = jax.lax.scan(step, init, jnp.arange(max_steps),
                            unroll=_default_unroll(smp.name))
    return trace


@functools.lru_cache(maxsize=None)
def _trace_batch(max_steps: int, sampler: str, devices: int = 1):
    smp = SAMPLERS[sampler]
    vrun = jax.vmap(functools.partial(_trace_single, max_steps, smp),
                    in_axes=(0, 0))

    def run(batch):
        return vrun(batch[0], batch[1])

    return _compile_runner(run, devices)


def trace_grid(cells, seeds=range(8), repair_interval_hours: float = 24.0,
               sampler: str = "exact", chunk_size: int | None = None,
               devices: int | None = None) -> np.ndarray:
    """Honest-fragment traces of single chunk groups (Fig. 5), batched over
    cells × seeds. Returns ``[n_cells, n_seeds, max_steps]`` int64; cells
    with a shorter horizon than the padded maximum hold their last value
    for the remaining steps."""
    def build(flat, ndev):
        max_steps = max(int(s.steps) for s in flat)
        # _run_chunked stacks element lists as pytrees; pair each scenario
        # with its repair interval so the same chunking path applies.
        interval = np.float32(repair_interval_hours)
        return (_trace_batch(max_steps, sampler, ndev),
                [(interval, s) for s in flat], None)

    out = _grid(cells, seeds, chunk_size, devices, build)
    return out.astype(np.int64)


# --------------------------------------------------- Fig 6 targeted attacks
def _targeted_single(st: _Static, smp: Sampler, sc: Scenario):
    G = st.max_groups
    gidx = jnp.arange(G, dtype=jnp.int32)
    active = gidx < sc.n_objects * sc.n_chunks
    base = smp.base(sc.seed)
    k_init, ka = smp.streams(smp.fold(base, 0), 2)
    byz = smp.binom(k_init, jnp.where(active, sc.r_inner, 0.0),
                    sc.byz_fraction)
    honest = jnp.where(active, sc.r_inner - byz, 0.0)
    kill = _targeted_kill(smp, sc, ka, honest, active)
    chunks_alive = _per_object(st, sc, active & ~kill)
    obj_active = jnp.arange(st.max_objects) < sc.n_objects
    lost = (obj_active & (chunks_alive < sc.k_outer)).sum()
    return lost / jnp.maximum(sc.n_objects, 1)


@functools.lru_cache(maxsize=None)
def _targeted_batch(st: _Static, sampler: str, devices: int = 1):
    OBJECT_COUNT_PATHS[_object_count_path(st)] += 1
    run = jax.vmap(functools.partial(_targeted_single, st,
                                     SAMPLERS[sampler]))
    return _compile_runner(run, devices)


def targeted_grid(cells, seeds=range(8), sampler: str = "exact",
                  chunk_size: int | None = None,
                  devices: int | None = None) -> np.ndarray:
    """Lost-object fraction under the greedy targeted attack (Fig. 6
    bottom), batched over cells × seeds: ``[n_cells, n_seeds]`` float."""
    def build(flat, ndev):
        st = _Static(
            max_groups=max(int(s.n_objects * s.n_chunks) for s in flat),
            max_objects=max(int(s.n_objects) for s in flat), max_steps=1,
            shared_chunks=_shared_chunks(flat))
        return (_targeted_batch(st, sampler, ndev), flat,
                _object_count_path(st))

    return _grid(cells, seeds, chunk_size, devices, build)


# ------------------------------------------------------------- summarizing
def mean_ci(x: np.ndarray, axis: int = -1) -> tuple[np.ndarray, np.ndarray]:
    """Mean and 95% normal-approx confidence half-width over ``axis``
    (the seed axis of a grid result)."""
    x = np.asarray(x, np.float64)
    n = x.shape[axis]
    mean = x.mean(axis=axis)
    ci = 1.96 * x.std(axis=axis, ddof=1) / np.sqrt(n) if n > 1 else (
        np.zeros_like(mean))
    return mean, ci
