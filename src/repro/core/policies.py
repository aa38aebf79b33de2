"""Shared churn / adversary / cache policy definitions for both VAULT layers.

The repo simulates VAULT at two levels of abstraction:

* the **group-level statistical engine** (``repro.core.scenarios`` — batched
  JAX, whole parameter sweeps in one dispatch; ``repro.core.simulation`` is
  its numpy reference), and
* the **protocol-level simulator** (``repro.core.protocol_sim`` — real
  ``SimNetwork`` peers, VRF selection proofs, GF(256) coding, decentralized
  repair).

Cross-validating the two (``benchmarks/cross_validate.py``) only means
something if both layers run the *same* scenario policies, so the policy
identifiers and every piece of shared policy arithmetic live here — one
source of truth instead of three copies.

Every numeric helper takes an ``xp=`` array namespace (default
``jax.numpy``) so the same formula serves the traced JAX engine
(``xp=jnp`` — the op sequence is identical to the pre-refactor inlined
code, keeping compiled outputs bit-for-bit stable), the numpy reference
path (``xp=np``), and the scalar protocol simulator (``xp=np`` on python
floats).

Policy catalogue
----------------

Churn (``churn_policy``):

* ``iid`` (:data:`CHURN_IID`) — i.i.d. Poisson churn per node, the paper's
  own model (§6.1, Figs. 4–6).  Per-step failure probability is
  :func:`p_fail_step`.
* ``regional`` (:data:`CHURN_REGIONAL`) — correlated bursts: with
  probability ``burst_prob`` per step one of :data:`N_REGIONS` fault
  domains suffers ``burst_mult``× the base failure rate (rack/AZ outages,
  after *Topology-Aware Cooperative Data Protection*).  The burst is
  applied as a *second* thinning pass with :func:`burst_extra_probability`
  so composing it with the base pass equals one boosted pass exactly.
* ``diurnal`` (:data:`CHURN_DIURNAL`) — time-of-day churn modulation:
  the Poisson rate is scaled by ``1 + amplitude · sin(2π · hour/24)``
  sampled at each step's midpoint (:func:`diurnal_rate_factor`), so the
  rate integrates to the *same yearly total* as ``iid`` over any whole
  number of days (the sin samples over a full period sum to zero
  exactly — pinned by ``tests/test_policy_zoo.py``).  Both layers
  recompute the per-step probability with :func:`diurnal_p_fail`.
* ``pareto`` (:data:`CHURN_PARETO`) — heavy-tailed node session lengths:
  each node lives for an independent Pareto(α, x_m) session with
  ``x_m = mean · (α−1)/α`` so the mean session matches the ``iid``
  churn rate (:func:`pareto_session_from_uniform`).  The protocol layer
  draws real sessions and expires nodes deterministically; the engine
  runs the documented **protected-cohort mean-field**
  (:func:`pareto_p_fail`): every session survives at least ``x_m``, so
  the effective hazard seen by a randomly-inspected step is the
  α-discounted ``(1 − exp(−α·rate·dt))/α`` — strictly below the i.i.d.
  probability (Jensen), which makes the cross-validation gate
  **one-sided** (abstraction leak #5, same pattern as the eclipse
  mean-field below).

Adversary (``adv_policy``):

* ``static`` (:data:`ADV_STATIC`) — fixed Byzantine population fraction;
  repair refills draw Byzantine members at the population share
  (paper Fig. 6 top; the §4.4 CTMC assumes exactly this).
* ``adaptive`` (:data:`ADV_ADAPTIVE`) — BFT-DSN-style repair-path attack:
  Byzantine members never churn voluntarily
  (:func:`byz_churn_probability` → 0) and flood repair refills at
  ``adapt_boost``× their population share
  (:func:`refill_byz_probability`).
* ``targeted`` (:data:`ADV_TARGETED`) — greedy targeted kill at
  ``attack_step`` under the A.3 cost model (:func:`kill_cost`), budget
  ``attack_frac · n_nodes`` (paper Fig. 6 bottom).
* ``eclipse`` (:data:`ADV_ECLIPSE`) — partition adversary: the ring
  segment covering ``attack_frac`` of id space is cut off for
  ``eclipse_steps`` steps starting at ``attack_step``. Eclipsed nodes are
  *alive but unreachable* — they keep their fragments and views, but no
  claims or repairs cross the cut, so their groups churn without repair
  for the whole window. Only the protocol layer can express the cut
  itself; the engine runs the documented **mean-field approximation**
  (:func:`eclipse_groups`, :func:`eclipse_active`): VRF placement is
  ring-local, so a fraction ``attack_frac`` of chunk groups sit inside
  the segment, and those groups get repair (and refills, traffic, cache
  warming) suppressed during the window while i.i.d. churn continues.
  The approximation is *deterministic* where the protocol's eclipsed set
  is binomial across seeds (anchors are hash-uniform), and it charges
  whole groups where the protocol's segment-boundary groups straddle the
  cut — both documented leaks cross-validated by ``tests/test_eclipse.py``.
* ``collude`` (:data:`ADV_COLLUDE`) — BFT-DSN-style collusion /
  withholding: Byzantine nodes *store* their fragments, answer Locate()
  rounds and persistence claims like honest members (they pass audits),
  but serve deterministically **corrupt** payloads at pull time.  The
  protocol layer verifies every gathered row against its creator-recorded
  integrity tag (``chunks.payload_tag`` / ``SimNetwork.frag_tags`` —
  the simulation stand-in for the paper's verifiable-fragment property)
  and discards corrupt rows *after paying their transfer*, which
  exercises the GF(256) rank-deficiency retry path under adversarial
  rather than random deficiency.  The engine charges the analogous
  wasted pulls closed-form (:func:`collusion_extra_pulls`).  Withholding
  never *increases* decode success by construction (corrupt rows are
  discarded pre-decode; honest row sets are unchanged) — pinned on both
  tiers by ``tests/test_policy_zoo.py``.
* ``eclipse_targeted`` (:data:`ADV_ECLIPSE_TARGETED`) — the **composed**
  product ``compose(eclipse(...), targeted_kill(...))``: the greedy kill
  lands at ``attack_step`` *and* the partition window opens at the same
  step, so repair of the surviving groups is suppressed exactly while
  the damage is fresh.  Both attacks share the ``attack_frac`` knob (the
  kill budget and the cut-segment width — one adversary resource pool).
  The engine runs both mean-field pieces simultaneously; the
  cross-validation row is gated one-sided like eclipse (leak #4).

Cache policy is the scalar ``cache_ttl_hours`` knob (0 disables); the
hit/miss traffic semantics are documented in ``repair.py`` and reproduced
identically by both layers.

Combinator API
--------------

``PolicySpec`` (plus the combinators :func:`iid`, :func:`regional`,
:func:`diurnal`, :func:`pareto_sessions`, :func:`static`,
:func:`adaptive`, :func:`targeted_kill`, :func:`eclipse`,
:func:`collude`, and :func:`compose`) is the construction layer above
the int ids: a spec carries at most one churn id, one adversary id, and
a tuple of knob overrides, and **lowers** through :func:`resolve` to the
same static-int/branchless form the jitted scan body consumes.  The
lowering target is deliberately unchanged — per-policy behavior stays a
fixed table of scalars selected by id inside ``xp.where``/family-flag
predicates — so the grid axis can ``vmap`` over arbitrary compositions
without per-policy retraces (every batch element shares one compiled
executable; only the two id leaves and the knob scalars differ).
:func:`compose` is **later-wins per axis** (documented order), except
for adversary pairs registered in the product table
(eclipse × targeted → ``eclipse_targeted``).  The zoo registry
(:func:`zoo_members`) enumerates every named policy configuration with
its cross-validation gate; ``benchmarks/cross_validate.py``
auto-discovers its config matrix from it and
``scripts/check_policy_matrix.py`` guards the mapping.
"""
from __future__ import annotations

from dataclasses import dataclass

import jax.numpy as jnp

HOURS_PER_YEAR = 24 * 365.0

CHURN_IID = 0
CHURN_REGIONAL = 1
CHURN_DIURNAL = 2
CHURN_PARETO = 3
CHURN_POLICIES = {
    "iid": CHURN_IID, "regional": CHURN_REGIONAL, "diurnal": CHURN_DIURNAL,
    "pareto": CHURN_PARETO,
}

ADV_STATIC = 0
ADV_ADAPTIVE = 1
ADV_TARGETED = 2
ADV_ECLIPSE = 3
ADV_COLLUDE = 4
ADV_ECLIPSE_TARGETED = 5
ADVERSARY_POLICIES = {
    "static": ADV_STATIC, "adaptive": ADV_ADAPTIVE, "targeted": ADV_TARGETED,
    "eclipse": ADV_ECLIPSE, "collude": ADV_COLLUDE,
    "eclipse_targeted": ADV_ECLIPSE_TARGETED,
}

# Family membership: a behavior is keyed by *membership* in a family, not
# by equality with a single id, so composed policies (eclipse_targeted)
# light up every component behavior. Single-member families compile to the
# exact same one-equality predicate as the pre-combinator code — that is
# what keeps the lowering bit-identical for all pre-existing policies.
ADV_ADAPTIVE_FAMILY = (ADV_ADAPTIVE,)
ADV_TARGETED_FAMILY = (ADV_TARGETED, ADV_ECLIPSE_TARGETED)
ADV_ECLIPSE_FAMILY = (ADV_ECLIPSE, ADV_ECLIPSE_TARGETED)
ADV_COLLUDE_FAMILY = (ADV_COLLUDE,)
CHURN_REGIONAL_FAMILY = (CHURN_REGIONAL,)
CHURN_DIURNAL_FAMILY = (CHURN_DIURNAL,)
CHURN_PARETO_FAMILY = (CHURN_PARETO,)

N_REGIONS = 16  # regional-burst fault domains (racks/AZs)


def _member_flag(policy, members):
    """OR-chain membership predicate (works traced and on python ints)."""
    flag = policy == members[0]
    for m in members[1:]:
        flag = flag | (policy == m)
    return flag


def adaptive_flag(adv_policy):
    """True iff the adversary plays the adaptive-refill behavior."""
    return _member_flag(adv_policy, ADV_ADAPTIVE_FAMILY)


def targeted_flag(adv_policy):
    """True iff the adversary fires the greedy targeted kill."""
    return _member_flag(adv_policy, ADV_TARGETED_FAMILY)


def eclipse_flag(adv_policy):
    """True iff the adversary opens the eclipse partition window."""
    return _member_flag(adv_policy, ADV_ECLIPSE_FAMILY)


def collude_flag(adv_policy):
    """True iff Byzantine members collude (store + serve corrupt rows)."""
    return _member_flag(adv_policy, ADV_COLLUDE_FAMILY)


def regional_flag(churn_policy):
    """True iff churn runs the regional-burst second thinning."""
    return _member_flag(churn_policy, CHURN_REGIONAL_FAMILY)


def diurnal_flag(churn_policy):
    """True iff churn is diurnally modulated."""
    return _member_flag(churn_policy, CHURN_DIURNAL_FAMILY)


def pareto_flag(churn_policy):
    """True iff churn follows Pareto session lengths."""
    return _member_flag(churn_policy, CHURN_PARETO_FAMILY)


def churn_policy_id(policy) -> int:
    """Resolve a churn policy (name, id, or :class:`PolicySpec`) to its
    int id.  Back-compat shim over :func:`resolve` — spec churn axis
    defaults to ``iid`` when unset."""
    if isinstance(policy, PolicySpec):
        return CHURN_IID if policy.churn is None else int(policy.churn)
    return CHURN_POLICIES[policy] if isinstance(policy, str) else int(policy)


def adv_policy_id(policy) -> int:
    """Resolve an adversary policy (name, id, or :class:`PolicySpec`) to
    its int id.  Back-compat shim over :func:`resolve` — spec adversary
    axis defaults to ``static`` when unset."""
    if isinstance(policy, PolicySpec):
        return ADV_STATIC if policy.adversary is None else int(policy.adversary)
    return (ADVERSARY_POLICIES[policy] if isinstance(policy, str)
            else int(policy))


# ------------------------------------------------------------ churn arithmetic
def p_fail_step(churn_per_year, step_hours, xp=jnp):
    """Per-step per-node failure probability from a Poisson churn rate.

    ``churn_per_year`` is expected failures per node-year, ``step_hours``
    the step width in hours; returns ``1 - exp(-rate · dt)`` in [0, 1).
    """
    return -xp.expm1(-churn_per_year / HOURS_PER_YEAR * step_hours)


def diurnal_rate_factor(t, step_hours, amplitude, xp=jnp):
    """Diurnal churn-rate multiplier for step ``t``.

    ``1 + amplitude · sin(2π · hour/24)`` sampled at the step *midpoint*
    ``(t + 0.5) · step_hours`` (endpoint sampling would alias to the sin
    zeros whenever ``step_hours`` divides 12).  Over any whole number of
    days with an integer number of steps per day the factors average to
    exactly 1 — the modulation integrates to the same yearly rate as
    ``iid``. ``amplitude`` must stay in [0, 1) to keep the rate positive.
    """
    hour = (t + 0.5) * step_hours
    return 1.0 + amplitude * xp.sin(2.0 * xp.pi * hour / 24.0)


def diurnal_p_fail(churn_policy, churn_per_year, diurnal_amplitude, t,
                   step_hours, p_fail_base, xp=jnp):
    """Per-step failure probability with optional diurnal modulation.

    ``diurnal`` policy: :func:`p_fail_step` of the modulated rate for
    this step. Every other policy: ``p_fail_base`` unchanged (the select
    is value-identical, keeping pre-existing policies bit-stable)."""
    factor = diurnal_rate_factor(t, step_hours, diurnal_amplitude, xp=xp)
    return xp.where(diurnal_flag(churn_policy),
                    p_fail_step(churn_per_year * factor, step_hours, xp=xp),
                    p_fail_base)


def pareto_session_mean_hours(churn_per_year, xp=jnp):
    """Mean session length (hours) matching the i.i.d. churn rate."""
    return HOURS_PER_YEAR / xp.maximum(churn_per_year, 1e-9)


def pareto_xm_hours(mean_hours, alpha, xp=jnp):
    """Pareto scale ``x_m`` (minimum session) for a target mean.

    ``mean = x_m · α/(α−1)`` for α > 1, so ``x_m = mean · (α−1)/α``."""
    a = xp.maximum(alpha, 1.0 + 1e-6)
    return mean_hours * (a - 1.0) / a


def pareto_session_from_uniform(u, mean_hours, alpha, xp=jnp):
    """Pareto(α, x_m) session length from one uniform in [0, 1).

    Inverse CDF: ``x_m · (1−u)^(−1/α)``, with ``x_m`` chosen by
    :func:`pareto_xm_hours` so the mean matches ``mean_hours``."""
    a = xp.maximum(alpha, 1.0 + 1e-6)
    xm = pareto_xm_hours(mean_hours, alpha, xp=xp)
    return xm * (1.0 - u) ** (-1.0 / a)


def pareto_p_fail(churn_policy, churn_per_year, pareto_alpha, step_hours,
                  p_fail_base, xp=jnp):
    """Engine mean-field per-step failure probability under Pareto sessions.

    A Pareto(α, x_m) session is *protected* for its first ``x_m`` hours
    (no node can die younger than the scale), so the population a random
    step inspects is a mix of protected and at-risk cohorts.  The
    flux-matched closed form is the α-discounted hazard
    ``(1 − exp(−α·rate·dt))/α`` — equal to the i.i.d. probability at
    α → 1 and *strictly below* it for α > 1 (Jensen).  This
    under-estimates burst clustering of heavy-tailed respawns, so the
    cross-validation row is **one-sided** (abstraction leak #5: the
    engine is the optimistic bound on repair volume, the protocol's real
    session draws sit above it).  Other policies pass ``p_fail_base``
    through bit-identically."""
    a = xp.maximum(pareto_alpha, 1.0 + 1e-6)
    rate_dt = churn_per_year / HOURS_PER_YEAR * step_hours
    return xp.where(pareto_flag(churn_policy),
                    -xp.expm1(-a * rate_dt) / a, p_fail_base)


def burst_from_uniforms(churn_policy, burst_prob, u0, u1, xp=jnp):
    """Regional-burst coin for one step from two uniforms in (0, 1).

    Returns ``(burst, region)``: ``burst`` is True iff the policy is in
    the ``regional`` family and ``u0 < burst_prob``; ``region`` is the
    hit fault domain, ``floor(u1 · N_REGIONS)`` clipped to
    ``[0, N_REGIONS)``.
    """
    regional = regional_flag(churn_policy)
    burst = regional & (u0 < burst_prob)
    region = xp.minimum((u1 * N_REGIONS).astype(xp.int32), N_REGIONS - 1)
    return burst, region


def burst_extra_probability(p_base, burst_mult, xp=jnp):
    """Second-pass thinning probability realizing a ``burst_mult``× boost.

    Thinning survivors of a ``p_base`` pass with this probability equals a
    single ``min(p_base · burst_mult, 0.95)`` pass exactly (binomial
    thinning composition), so the burst costs nothing on non-burst steps.
    """
    boosted = xp.minimum(p_base * burst_mult, 0.95)
    return xp.clip((boosted - p_base)
                   / xp.maximum(1.0 - p_base, 1e-9), 0.0, 1.0)


def group_domain(gidx, n_regions: int = N_REGIONS):
    """Fault domain of group ``gidx`` in the group-level engine.

    The engine's topology-aware worst case: a chunk group's members are
    co-located, so whole groups map to domains (round-robin)."""
    return gidx % n_regions


def ring_domain(nid: int, ring: int, n_regions: int = N_REGIONS) -> int:
    """Fault domain of a node id in the protocol-level simulator.

    Nodes are binned by ring segment, so ring-adjacent nodes — the ones
    VRF placement co-selects into the same chunk groups — share a domain.
    This is the protocol-level realization of :func:`group_domain`'s
    co-location assumption."""
    return int(nid // -(-ring // n_regions))


# -------------------------------------------------------- adversary arithmetic
def byz_churn_probability(adv_policy, p_fail, xp=jnp):
    """Voluntary churn probability of Byzantine members.

    The adaptive adversary's members never leave on their own (they hold
    seats to starve honest refills); every other policy churns Byzantine
    members like honest ones."""
    return xp.where(adaptive_flag(adv_policy), 0.0, p_fail)


def refill_byz_probability(adv_policy, byz_fraction, adapt_boost, xp=jnp):
    """Probability that one repair refill lands on a Byzantine member.

    ``static``/``targeted``: the population share ``byz_fraction`` (VRF
    selection is uniform, §3.3).  ``adaptive``: boosted to
    ``clip(byz_fraction · adapt_boost, 0, 0.95)`` — the adversary races
    Locate() rounds, answering first for every open slot."""
    return xp.where(
        adaptive_flag(adv_policy),
        xp.clip(byz_fraction * adapt_boost, 0.0, 0.95),
        byz_fraction)


def collusion_extra_pulls(adv_policy, byz_count, xp=jnp):
    """Wasted fragment pulls a colluding group charges per decode gather.

    Under ``collude`` every Byzantine member of the group serves one
    corrupt row that is pulled, integrity-checked, and discarded — so a
    repairing group pays ``byz_count`` extra fragment transfers per
    chunk-decode gather. Zero for every other adversary (value-identical
    pass-through, additive-zero in the traffic lane)."""
    return xp.where(collude_flag(adv_policy), byz_count, 0.0)


def ring_segment(attack_frac: float, ring: int) -> tuple[int, int]:
    """The cut ring interval of the eclipse adversary (protocol layer).

    Deterministic ``[0, attack_frac · ring)`` — node ids are hash-uniform,
    so the segment's population share is ``attack_frac`` in expectation and
    the choice of origin carries no information."""
    return (0, int(attack_frac * ring))


def eclipse_active(adv_policy, t, attack_step, eclipse_steps, xp=jnp):
    """True while the eclipse window is open: ``attack_step ≤ t <
    attack_step + eclipse_steps`` under an ``eclipse``-family policy
    (plain eclipse or the composed eclipse+targeted product)."""
    return (eclipse_flag(adv_policy) & (t >= attack_step)
            & (t < attack_step + eclipse_steps))


def eclipse_groups(gidx, attack_frac, n_groups, xp=jnp):
    """Engine mean-field mask of eclipsed groups.

    VRF placement is ring-local, so the protocol's cut segment captures a
    fraction ``attack_frac`` of group anchors; the engine (which has no
    anchors) eclipses the first ``round(attack_frac · n_groups)`` groups —
    the right mean, no across-seed variance (documented approximation)."""
    n_ecl = xp.round(attack_frac * n_groups)
    return gidx < n_ecl


def kill_cost(honest, k_inner, frags_per_node, xp=jnp):
    """Per-group kill cost of the targeted adversary (A.3 eq. 17).

    Disconnecting a group needs ``honest − K_inner + 1`` honest removals,
    amortized by ``frags_per_node`` co-located fragments per node. Units:
    nodes (the attack budget is ``attack_frac · n_nodes``)."""
    cost = xp.maximum(honest - k_inner + 1.0, 0.0)
    return cost / xp.maximum(frags_per_node, 1.0)


# ---------------------------------------------------------- serving arithmetic
# The request-serving workload layer (ROADMAP item 3). Both tiers serve
# Zipf-popular whole-object Get() requests each step and classify every
# request into exactly one of four disjoint buckets (priority order):
#
#   failed    — fewer than K_outer chunks readable: the read cannot
#               complete (includes groups behind an eclipse cut);
#   degraded  — completes, but at least one chunk group is dead or
#               eclipsed, so the client fans wider and pays an extra hop;
#   hit       — completes entirely from warm cached chunk copies;
#   miss      — completes via fragment pulls + GF(256) decode.
#
# Latency is measured in *hops* (request→holder round trips), not sampled
# RTTs, so both tiers produce the same deterministic quantity:
# cache hit = anchor walk + cached-chunk pull (2), miss adds the
# fragment-gather round (3), degraded adds one more fan-out round (4).
# Per-region bandwidth caps stretch hops multiplicatively (congestion),
# which is how repair and serving compete for the same links.

#: Hop cost of a cache-hit read: candidate walk + whole-chunk pull.
SERVE_HOPS_HIT = 2.0
#: Hop cost of a decode-path read: walk + parallel fragment gather + decode.
SERVE_HOPS_MISS = 3.0
#: Extra hop a degraded read pays to fan out past dead/eclipsed groups.
SERVE_HOPS_DEGRADED_EXTRA = 1.0
#: Bins of the retrieval-hop histogram; effective hops clip to the last bin.
SERVE_HIST_BINS = 16
#: Bandwidth fault domains — one per ``network.REGIONS`` entry.
N_BW_REGIONS = 5


def fixed_order_sum(x):
    """Sum of ``x`` over its last axis, added in one fixed pairwise order.

    ``x.sum()`` leaves the order of the additions to the compiler, and the
    TPU compiler chooses it per compiled batch width: a vmapped sum of
    fractional floats then rounds differently at another ``chunk_size`` or
    device count. Halving the zero-padded axis with elementwise adds pins
    the order, so each row's sum depends on that row alone.
    """
    n = x.shape[-1]
    width = 1 << max(n - 1, 0).bit_length()
    x = jnp.pad(x, [(0, 0)] * (x.ndim - 1) + [(0, width - n)])
    while x.shape[-1] > 1:
        half = x.shape[-1] // 2
        x = x[..., :half] + x[..., half:]
    return x[..., 0]


def zipf_weights(obj_idx, zipf_alpha, n_objects, xp=jnp):
    """Zipf(α) popularity weights over objects, normalized to sum 1.

    ``obj_idx`` ranks objects by popularity (0 = hottest, weight
    ``(i+1)^-α``); indices ≥ ``n_objects`` (grid padding) get weight 0 and
    the rest renormalize over the active objects only.  ``zipf_alpha = 0``
    degenerates to uniform popularity. The ``jnp`` path normalizes with
    :func:`fixed_order_sum`, so the engine's weights do not depend on its
    batch width.
    """
    rank = xp.asarray(obj_idx, dtype=xp.float32) + 1.0
    w = rank ** -xp.asarray(zipf_alpha, dtype=xp.float32)
    w = xp.where(obj_idx < n_objects, w, 0.0)
    total = fixed_order_sum(w) if xp is jnp else w.sum()
    return w / xp.maximum(total, 1e-30)


def congestion_factor(load_units, region_cap, xp=jnp):
    """Latency stretch of a bandwidth region carrying ``load_units``.

    ``region_cap`` is the per-region per-step capacity in object units
    (0 or negative disables the model).  Under the cap the factor is 1;
    above it, hops stretch linearly with the overload ratio — the M/D/1
    heavy-traffic asymptote both tiers share.
    """
    cap = xp.asarray(region_cap, dtype=xp.float32)
    ratio = load_units / xp.maximum(cap, 1e-30)
    return xp.where(cap > 0.0, xp.maximum(ratio, 1.0), 1.0)


def effective_hops(hops, factor, xp=jnp):
    """Histogram bin of a read with base ``hops`` under congestion
    ``factor``: ``round(hops · factor)`` clipped to the last bin."""
    e = xp.round(hops * factor)
    return xp.clip(e, 0.0, SERVE_HIST_BINS - 1.0)


# ------------------------------------------------------------- combinator API
#: Knob keys a PolicySpec may carry — exactly the policy-parameter kwargs
#: of ``scenarios.make_scenario`` / ``protocol_sim.ProtocolParams``.
POLICY_KNOBS = ("burst_prob", "burst_mult", "adapt_boost", "attack_frac",
                "attack_step", "eclipse_steps", "diurnal_amplitude",
                "pareto_alpha")


@dataclass(frozen=True)
class PolicySpec:
    """One composable policy: at most one churn id, one adversary id, and
    a tuple of ``(knob, value)`` overrides (hashable, so specs can key
    caches and sit in grid cells).  Build specs with the combinators
    below and :func:`compose`; lower them with :func:`resolve`."""

    name: str
    churn: int | None = None
    adversary: int | None = None
    knobs: tuple = ()

    def knob_dict(self) -> dict:
        return dict(self.knobs)


def _spec(name, churn=None, adversary=None, **knobs) -> PolicySpec:
    kn = tuple((k, v) for k, v in knobs.items() if v is not None)
    for k, _ in kn:
        if k not in POLICY_KNOBS:
            raise TypeError(f"unknown policy knob {k!r}")
    return PolicySpec(name=name, churn=churn, adversary=adversary, knobs=kn)


def iid() -> PolicySpec:
    """i.i.d. Poisson churn (the paper's §6.1 model)."""
    return _spec("iid", churn=CHURN_IID)


def regional(burst_prob=None, burst_mult=None) -> PolicySpec:
    """Correlated regional-burst churn; ``None`` knobs keep defaults."""
    return _spec("regional", churn=CHURN_REGIONAL,
                 burst_prob=burst_prob, burst_mult=burst_mult)


def diurnal(amplitude=None) -> PolicySpec:
    """Diurnally modulated churn rate (see :func:`diurnal_rate_factor`)."""
    return _spec("diurnal", churn=CHURN_DIURNAL, diurnal_amplitude=amplitude)


def pareto_sessions(alpha=None) -> PolicySpec:
    """Heavy-tailed Pareto(α) session lengths (see :func:`pareto_p_fail`)."""
    return _spec("pareto", churn=CHURN_PARETO, pareto_alpha=alpha)


def static() -> PolicySpec:
    """Static Byzantine population fraction (Fig. 6 top)."""
    return _spec("static", adversary=ADV_STATIC)


def adaptive(boost=None) -> PolicySpec:
    """Adaptive repair-path adversary; ``boost`` = refill bias."""
    return _spec("adaptive", adversary=ADV_ADAPTIVE, adapt_boost=boost)


def targeted_kill(budget=None, attack_step=None) -> PolicySpec:
    """Greedy targeted kill; ``budget`` = ``attack_frac`` of n_nodes."""
    return _spec("targeted", adversary=ADV_TARGETED,
                 attack_frac=budget, attack_step=attack_step)


def eclipse(frac=None, window=None, attack_step=None) -> PolicySpec:
    """Ring-partition adversary; ``frac`` = ``attack_frac`` segment
    width, ``window`` = ``eclipse_steps``."""
    return _spec("eclipse", adversary=ADV_ECLIPSE, attack_frac=frac,
                 eclipse_steps=window, attack_step=attack_step)


def collude() -> PolicySpec:
    """Collusion/withholding adversary (BFT-DSN): Byzantine nodes pass
    audits but serve corrupt fragments at pull time."""
    return _spec("collude", adversary=ADV_COLLUDE)


#: Adversary product table for :func:`compose`: pairs that combine into a
#: genuinely composed behavior instead of later-wins. Symmetric by
#: construction (frozenset keys); absorbing (product ∘ component = product).
_ADV_PRODUCTS = {
    frozenset((ADV_ECLIPSE, ADV_TARGETED)): ADV_ECLIPSE_TARGETED,
    frozenset((ADV_ECLIPSE_TARGETED, ADV_TARGETED)): ADV_ECLIPSE_TARGETED,
    frozenset((ADV_ECLIPSE_TARGETED, ADV_ECLIPSE)): ADV_ECLIPSE_TARGETED,
}


def compose(*specs: PolicySpec) -> PolicySpec:
    """Fold specs left-to-right into one spec.

    Composition order is documented and deterministic: per axis (churn,
    adversary) the **later spec wins**, *except* adversary pairs listed
    in the product table (``eclipse × targeted → eclipse_targeted``,
    which is symmetric and absorbing).  Knobs merge later-wins per key.
    ``compose(x)`` is the identity, so composing a single combinator with
    nothing lowers exactly like the combinator itself."""
    if not specs:
        raise TypeError("compose() needs at least one PolicySpec")
    acc = specs[0]
    for s in specs[1:]:
        if not isinstance(s, PolicySpec):
            raise TypeError(f"compose() takes PolicySpec, got {type(s)}")
        churn = acc.churn if s.churn is None else s.churn
        if acc.adversary is None or s.adversary is None:
            adv = acc.adversary if s.adversary is None else s.adversary
        else:
            adv = _ADV_PRODUCTS.get(frozenset((acc.adversary, s.adversary)),
                                    s.adversary)
        knobs = dict(acc.knobs)
        knobs.update(s.knobs)
        acc = PolicySpec(name=f"{acc.name}+{s.name}", churn=churn,
                         adversary=adv, knobs=tuple(knobs.items()))
    return acc


class LoweredPolicy(tuple):
    """Static lowering of a spec: ``(churn id, adversary id, knob tuple)``.

    The ids are the exact ints the branchless scan body selects on;
    ``knobs`` are scalar overrides for the scenario/protocol kwargs of
    the same names. A plain tuple subclass so it stays hashable."""

    __slots__ = ()

    def __new__(cls, churn: int, adversary: int, knobs: tuple = ()):
        return super().__new__(cls, (int(churn), int(adversary),
                                     tuple(knobs)))

    @property
    def churn(self) -> int:
        return self[0]

    @property
    def adversary(self) -> int:
        return self[1]

    @property
    def knobs(self) -> tuple:
        return self[2]

    def knob_dict(self) -> dict:
        return dict(self[2])


def resolve(policy) -> LoweredPolicy:
    """THE resolver: lower a policy to its static-int/branchless form.

    Accepts a :class:`PolicySpec` (from the combinators or
    :func:`compose`), a registered zoo name (:func:`zoo_members`), a
    plain churn or adversary policy name (``"iid"``, ``"eclipse"``, …),
    or ``None`` (the iid/static baseline).  Unset axes default to
    ``iid``/``static``.  Plain ints are rejected — an int does not say
    *which* axis it belongs to; use the per-axis shims
    :func:`churn_policy_id` / :func:`adv_policy_id` for those."""
    if policy is None:
        return LoweredPolicy(CHURN_IID, ADV_STATIC)
    if isinstance(policy, LoweredPolicy):
        return policy
    if isinstance(policy, str):
        if policy in _ZOO:
            policy = _ZOO[policy].spec
        elif policy in CHURN_POLICIES:
            policy = _spec(policy, churn=CHURN_POLICIES[policy])
        elif policy in ADVERSARY_POLICIES:
            policy = _spec(policy, adversary=ADVERSARY_POLICIES[policy])
        else:
            raise KeyError(f"unknown policy name {policy!r}")
    if not isinstance(policy, PolicySpec):
        raise TypeError(
            f"cannot resolve {policy!r}: pass a PolicySpec or name "
            "(plain ints are axis-ambiguous; use churn_policy_id / "
            "adv_policy_id)")
    return LoweredPolicy(
        CHURN_IID if policy.churn is None else policy.churn,
        ADV_STATIC if policy.adversary is None else policy.adversary,
        policy.knobs)


# ----------------------------------------------------------------- policy zoo
@dataclass(frozen=True)
class StepFrac:
    """A step count expressed as an exact fraction of the horizon
    (``steps · num // den`` — integer arithmetic, so ``StepFrac(1, 3)``
    of 30 steps is exactly 10, where a float ``1/3`` would truncate)."""

    num: int
    den: int

    def resolve(self, steps: int) -> int:
        return int(steps) * self.num // self.den


@dataclass(frozen=True)
class ZooEntry:
    """One registered zoo member: a named policy configuration with its
    cross-validation contract.

    ``overrides`` are extra matched-config kwargs (``StepFrac`` values
    resolve against the horizon at build time); ``gate`` is how
    ``tests/test_cross_validation.py`` holds the row — ``"two_sided"``
    rows ride the blanket combined-CI gates, ``"one_sided"`` rows get
    dedicated bound tests (documented abstraction leaks); ``note`` says
    why."""

    name: str
    spec: PolicySpec
    overrides: tuple = ()
    gate: str = "two_sided"
    note: str = ""


_ZOO: dict[str, ZooEntry] = {}


def _register(entry: ZooEntry) -> ZooEntry:
    if entry.name in _ZOO:
        raise ValueError(f"duplicate zoo entry {entry.name!r}")
    if entry.gate not in ("two_sided", "one_sided"):
        raise ValueError(f"unknown gate {entry.gate!r}")
    _ZOO[entry.name] = entry
    return entry


def zoo_members() -> tuple[ZooEntry, ...]:
    """Every registered zoo entry, in registration order. The
    auto-discovery source for ``benchmarks/cross_validate.py`` (guarded
    by ``scripts/check_policy_matrix.py``)."""
    return tuple(_ZOO.values())


def zoo_entry(name: str) -> ZooEntry:
    return _ZOO[name]


def zoo_config_kwargs(entry: ZooEntry, steps: int) -> dict:
    """Matched-config kwargs of a zoo entry at horizon ``steps``:
    ``policy=`` spec plus the entry's overrides with ``StepFrac`` values
    resolved."""
    kw = {"policy": entry.spec}
    for k, v in entry.overrides:
        kw[k] = v.resolve(steps) if isinstance(v, StepFrac) else v
    return kw


# The zoo. Legacy entries reproduce the exact pre-combinator matched
# configs of benchmarks/cross_validate.py; new entries are the four
# ISSUE-10 zoo members. NOTE: scripts/check_policy_matrix.py ast-parses
# these _register(ZooEntry(name="...")) calls — keep them literal.
_register(ZooEntry(
    name="iid_static",
    spec=compose(iid(), static())))
_register(ZooEntry(
    name="regional_static",
    spec=compose(regional(burst_prob=0.15, burst_mult=8.0), static())))
_register(ZooEntry(
    name="iid_adaptive",
    spec=compose(iid(), adaptive(boost=2.0))))
_register(ZooEntry(
    name="iid_static_cache",
    spec=compose(iid(), static()),
    overrides=(("cache_ttl_hours", 48.0),)))
_register(ZooEntry(
    name="iid_targeted",
    spec=compose(iid(), targeted_kill(budget=0.25)),
    overrides=(("attack_step", StepFrac(1, 2)),),
    gate="one_sided",
    note="engine kill is the conservative bound (dedicated gates)"))
_register(ZooEntry(
    name="iid_eclipse",
    spec=compose(iid(), eclipse(frac=0.3)),
    overrides=(("churn_per_year", 80.0),
               ("attack_step", StepFrac(1, 4)),
               ("eclipse_steps", StepFrac(1, 3))),
    gate="one_sided",
    note="whole-group mean-field eclipse: engine is the conservative "
         "bound (abstraction leak #4)"))
_register(ZooEntry(
    name="diurnal_static",
    spec=compose(diurnal(amplitude=0.6), static()),
    note="same yearly rate as iid by construction; rides the blanket "
         "combined-CI gates"))
_register(ZooEntry(
    name="pareto_static",
    spec=compose(pareto_sessions(alpha=1.5), static()),
    gate="one_sided",
    note="protected-cohort mean-field: engine under-counts heavy-tailed "
         "respawn clustering (abstraction leak #5)"))
_register(ZooEntry(
    name="iid_collude",
    spec=compose(iid(), collude()),
    gate="one_sided",
    note="withholding only adds discarded-row traffic; decode metrics "
         "match static, traffic gated one-sided"))
_register(ZooEntry(
    name="iid_eclipse_targeted",
    spec=compose(iid(), eclipse(frac=0.25), targeted_kill(budget=0.25)),
    overrides=(("churn_per_year", 80.0),
               ("attack_step", StepFrac(1, 4)),
               ("eclipse_steps", StepFrac(1, 3))),
    gate="one_sided",
    note="composed product INVERTS the eclipse leak: the kill exploits "
         "the partition, so the protocol loses MORE than the engine's "
         "independent mean-field product — gated one-sided the other "
         "way (see tests/test_cross_validation.py)"))
