"""The comparison that decides a run's ``correct``.

Every element of the window (one sweep point on one seed) is an answer. Once
the window has closed, a few sweep points are drawn from the run's seed; the
plain reference simulates each of them once on its own seed, and every
window element of those points is compared with it, field by field.

The program and the reference draw their randomness from different
generators, so they agree in distribution and never bit for bit. A number
compared is therefore a relative gap: for a field ``f`` and an element ``e``
of point ``c``, ``|e.f - ref_c.f|`` over the largest of ``|ref_c.f|``, the
median of ``|ref.f|`` over the drawn points, and ``|ref_c.<scale>|`` where
the check names a scale field (a count of reads is measured against the reads
issued). Array fields use sums of absolute values. The number is the worst
element's gap; each element whose gap passes the limit counts as failed.

A number is named after its field. Where the check gives ``losing_share``, a
drawn point whose reference realization ends with more than that share of
its groups lost is *losing*: its counts hang on when each group died, so they
swing far more from seed to seed than those of a point that keeps its groups.
Then ``<field>`` compares the elements of the other points and
``<field>.losing`` those of the losing points, each under a limit of its own.

``repeated_results`` is exact: the number of pairs of window elements of one
sweep point, on different seeds, whose results agree in every bit while the
field the check names under ``varying`` varies over the run. That field (the
live-group fraction at every step) carries the timing of every group death,
so two seeds cannot agree on it by chance; a point where no group ever dies
gives results that differ in one count only, which two seeds can share, and
is left out. Its limit is 0.
"""
from __future__ import annotations

import math

import numpy as np

REPEATS = "repeated_results"
LOSING = "losing"


def seeds_for(seed: int, stream: int, rep: int, n: int) -> list:
    """``n`` distinct seeds in ``[0, 2**31)`` derived from the run's seed."""
    seq = np.random.SeedSequence([seed % 2**64, stream, rep])
    out = []
    for word in seq.generate_state(4 * n + 8, np.uint32):
        value = int(word) & 0x7FFFFFFF
        if value not in out:
            out.append(value)
        if len(out) == n:
            return out
    raise RuntimeError("seed collision")  # about 2**-40 likely


def sample_cells(seed: int, cells: list, k: int) -> list:
    """``k`` of the distinct sweep points in ``cells``, drawn from the seed."""
    distinct = sorted(set(cells))
    rng = np.random.default_rng([seed % 2**64, 3])
    pick = rng.choice(len(distinct), size=min(k, len(distinct)), replace=False)
    return sorted(distinct[i] for i in pick)


def _norm(x) -> float:
    return float(np.sum(np.abs(np.asarray(x, np.float64))))


def _repeats(elements, varying: str) -> tuple:
    seen, pairs, bad = {}, 0, set()
    for i, e in enumerate(elements):
        trace = np.asarray(e["result"][varying])
        if np.all(trace == trace.flat[0]):
            continue
        key = (e["cell"], b"".join(np.ascontiguousarray(
            np.asarray(e["result"][f], np.float64)).tobytes()
            for f in sorted(e["result"])))
        for j in seen.get(key, []):
            if elements[j]["seed"] != e["seed"]:
                pairs += 1
                bad.update((i, j))
        seen.setdefault(key, []).append(i)
    return pairs, bad


def losing(ref: dict, share) -> bool:
    """Whether a reference realization lost more than ``share`` of its
    groups by its last step (never, where ``share`` is None)."""
    return share is not None and 1.0 - float(
        np.asarray(ref["alive_frac_trace"])[-1]) > share


def compare(elements: list, refs: dict, numbers: dict,
            losing_share=None) -> tuple:
    """Gaps of the window's ``elements`` against the reference results.

    ``elements``: ``{"cell", "seed", "result"}`` per window element;
    ``refs``: sweep point -> reference result; ``numbers``: the check's
    name -> ``{"scale", "limit"}``; ``losing_share``: see the module's
    docstring. Returns ``(values, failed)``: each number's value, and for
    each element that failed the names of the numbers it failed."""
    values, failed = {}, {}
    for name, spec in numbers.items():
        if name == REPEATS:
            values[name], bad = _repeats(elements, spec["varying"])
            for i in bad:
                failed.setdefault(i, []).append(name)
            continue
        field, _, part = name.partition(".")
        med = float(np.median([_norm(r[field]) for r in refs.values()]))
        worst = 0.0
        for i, e in enumerate(elements):
            ref = refs.get(e["cell"])
            if ref is None or losing(ref, losing_share) != (part == LOSING):
                continue
            denom = max(_norm(ref[field]), med,
                        _norm(ref[spec["scale"]]) if "scale" in spec else 0.0)
            diff = _norm(np.asarray(e["result"][field], np.float64)
                         - np.asarray(ref[field], np.float64))
            gap = diff / denom if denom > 0 else (0.0 if diff == 0 else math.inf)
            if not gap <= spec["limit"]:
                failed.setdefault(i, []).append(name)
            worst = max(worst, gap) if not math.isnan(gap) else math.inf
        values[name] = worst
    return values, failed


def verdict(values: dict, numbers: dict) -> dict:
    """``name -> {"value", "limit"}`` in the check's order (``None`` for a
    value that is not a finite number)."""
    return {name: {"value": values[name] if math.isfinite(values[name])
                   else None, "limit": spec["limit"]}
            for name, spec in numbers.items()}


def passed(values: dict, numbers: dict) -> bool:
    return all(values[n] <= spec["limit"] for n, spec in numbers.items())
