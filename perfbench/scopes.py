"""The engine's own spans and scopes, reduced on top of ``trace.py``.

``trace.from_xspace`` keeps the harness's spans and every device operation
by name. The engine (``repro.core.scenarios``) adds two kinds of labels:

* host spans (``jax.profiler.TraceAnnotation``) that tile each grid call:
  ``vault.grid`` around the call, and inside it ``vault.build`` once, then
  ``vault.stack``, ``vault.launch`` and ``vault.fetch`` for each chunk, and
  ``vault.gather`` once;
* name scopes (``jax.named_scope``) around the phases of the scan body:
  ``vault.churn``, ``vault.repair``, ``vault.serve`` and ``vault.merge``.

A v5e trace's operation events carry no scope, so each operation's scope is
read from the compiled program's HLO text (``metadata={op_name=...}``),
which :func:`program_hlo` takes once, after the warm-up, from the engine's
one dispatch point. An operation that XLA made without a scope of its own (a
``conditional`` or a fusion it built) takes the one scope of the
computations it calls, if they have one and only one.

:class:`ScopedTrace` is a :class:`trace.Trace` that also holds the engine's
spans (so ``open_span`` and ``longest_gaps`` name the innermost span, the
program's or the harness's) and each device's operation scopes. Its methods
give the device time under each scope, as the union of the intervals of its
operations (a ``conditional`` and the fusions nested in it count once), and
split each device-idle gap between runs of the program by the engine span
open over it. :func:`step_us`, :func:`gap_ms` and :func:`split` turn those
into the numbers that the per-layer metrics (``perfbench/metrics/``) and
``profile_cell.py`` report, so the two never disagree.
"""
from __future__ import annotations

import dataclasses
import functools
import json
import re

from perfbench import trace as T

PROGRAM_SPAN_PREFIX = "vault."
GRID = "vault.grid"
# device-idle time between runs of the program, by what the host was doing
PREPARE = ("vault.build", "vault.stack", "vault.launch")
COLLECT = ("vault.fetch", "vault.gather")
# the phases of the scan body
PHASES = CHURN, REPAIR, SERVE, MERGE = (
    "vault.churn", "vault.repair", "vault.serve", "vault.merge")
# the parts of a gap, in the order of ``ScopedTrace.gap_split``
GAP_PARTS = ("prepare", "collect", "harness")

_COMPUTATION = re.compile(r"^(?:ENTRY )?%(\S+) .*\{$")
_INSTRUCTION = re.compile(r"^\s*(?:ROOT )?%(\S+) = ")
_OP_NAME = re.compile(r'metadata=\{op_name="([^"]*)"')
_CALLED = re.compile(
    r"(?:calls|to_apply|body|condition|branch_computations|"
    r"true_computation|false_computation)=\{?([^}),]+(?:, %[^}),]+)*)")


def scope_of(op_name: str) -> str | None:
    """The innermost engine scope in an op's name-scope path."""
    found = [part for part in op_name.split("/")
             if part.startswith(PROGRAM_SPAN_PREFIX)]
    return found[-1] if found else None


def hlo_scopes(text: str) -> dict:
    """``{instruction: engine scope}`` of a compiled program's HLO text, for
    the instructions that have one."""
    own, calls, comp, members = {}, {}, None, {}
    for line in text.splitlines():
        m = _COMPUTATION.match(line)
        if m:
            comp = m.group(1)
            members[comp] = []
            continue
        m = _INSTRUCTION.match(line)
        if not m or comp is None:
            continue
        name = m.group(1)
        members[comp].append(name)
        op = _OP_NAME.search(line)
        own[name] = scope_of(op.group(1)) if op else None
        calls[name] = [c.strip().lstrip("%") for group in _CALLED.findall(line)
                       for c in group.split(",")]

    @functools.lru_cache(maxsize=None)
    def inside(comp: str) -> frozenset:
        found = set()
        for name in members.get(comp, ()):
            found |= scopes(name)
        return frozenset(found)

    def scopes(name: str) -> frozenset:
        if own.get(name):
            return frozenset([own[name]])
        return frozenset().union(*(inside(c) for c in calls.get(name, ())))

    out = {}
    for name in own:
        found = scopes(name)
        if len(found) == 1:
            out[name] = next(iter(found))
    return out


def program_hlo(call) -> str:
    """Run ``call()`` with the engine's dispatch point recorded and return
    the optimized HLO text of the first program it dispatched ("" when it
    dispatched none). Lowering a runner that has run compiles nothing: JAX
    hands back the executable it holds, with the metadata of the HLO it was
    built from (from the persistent compile cache, that of whatever version
    of the program first built it, unless the cache is keyed with
    metadata)."""
    from repro.core import scenarios

    seen, dispatch = [], scenarios._dispatch

    def recording(runner, batch):
        if not seen:
            seen.append((runner, batch))
        return dispatch(runner, batch)

    scenarios._dispatch = recording
    try:
        call()
    finally:
        scenarios._dispatch = dispatch
    if not seen:
        return ""
    runner, batch = seen[0]
    return runner.lower(batch).compile().as_text()


@dataclasses.dataclass
class ScopedTrace(T.Trace):
    """A :class:`trace.Trace` whose ``spans`` include the engine's."""

    scopes: dict = dataclasses.field(default_factory=dict)
    # device id -> {operation name: engine scope}

    @classmethod
    def from_json(cls, text: str) -> "ScopedTrace":
        scopes = json.loads(text).get("scopes", {})
        return cls(**_fields(T.Trace.from_json(text)),
                   scopes={str(k): v for k, v in scopes.items()})

    @property
    def has_scopes(self) -> bool:
        return any(self.scopes.values())

    def scoped_busy_s(self, names) -> float:
        """Device seconds in which an operation under one of the scopes
        ``names`` ran, within the window, averaged over the devices."""
        total = 0
        for dev in self.devices:
            of = self.scopes.get(dev, {})
            ops = [(s, s + d) for name, s, d in self.ops[dev]
                   if of.get(name) in names]
            total += _length(T.union(T.clip(ops, *self.window)))
        return total * 1e-9 / max(len(self.devices), 1)

    def _span_intervals(self, names) -> list:
        return T.union([(s, s + d) for name, s, d in self.spans
                        if name in names])

    def gap_split(self, prefix) -> list:
        """Each device-idle gap between consecutive runs of the programs
        named ``prefix`` (the gaps of ``gaps_between_runs``, in its order),
        as seconds ``(prepare, collect, harness, total)``: the idle time
        covered by ``PREPARE`` spans, by ``COLLECT`` spans, and by no
        ``vault.grid`` span."""
        prepare = self._span_intervals(PREPARE)
        collect = self._span_intervals(COLLECT)
        grid = self._span_intervals((GRID,))
        out = []
        for dev in self.devices:
            runs = self.program_runs(dev, prefix)
            busy = self.busy_intervals(dev)
            for (_, end), (start, _) in zip(runs, runs[1:]):
                if start <= end:
                    continue
                idle = T.complement(busy, end, start)
                total = _length(idle)
                out.append(tuple(1e-9 * x for x in (
                    _overlap(idle, prepare), _overlap(idle, collect),
                    total - _overlap(idle, grid), total)))
        return out


def _fields(trace: T.Trace) -> dict:
    return {f.name: getattr(trace, f.name)
            for f in dataclasses.fields(T.Trace)}


def _length(intervals) -> int:
    return sum(e - s for s, e in intervals)


def _overlap(intervals, cover) -> int:
    """Length of ``intervals`` (disjoint) that the union ``cover`` covers."""
    return sum(_length(T.clip(cover, s, e)) for s, e in intervals)


def step_us(trace, phases, dispatches: int, max_steps: int) -> float | None:
    """Device microseconds a scan step under the scopes ``phases``, per
    chip: the union of their operations' intervals in the window over
    ``dispatches x max_steps``, the base ``scan_step_us`` divides by. None
    for a trace without engine scopes."""
    if not isinstance(trace, ScopedTrace) or not trace.has_scopes:
        return None
    return 1e6 * trace.scoped_busy_s(phases) / (dispatches * max_steps)


def gap_ms(trace, prefix: str) -> dict:
    """``{part: ms}`` for each of ``GAP_PARTS``: the mean, over the gaps
    between runs of the programs ``prefix`` (those ``host_gap_ms``
    averages), of the device-idle time under ``PREPARE`` spans, under
    ``COLLECT`` spans, and under no ``vault.grid``. Empty for a trace
    without the engine's spans or without such a gap."""
    if not isinstance(trace, ScopedTrace):
        return {}
    gaps = trace.gap_split(prefix)
    if not gaps or not any(name == GRID for name, _, _ in trace.spans):
        return {}
    return {part: 1e3 * sum(g[i] for g in gaps) / len(gaps)
            for i, part in enumerate(GAP_PARTS)}


def split(trace, prefix: str, dispatches: int, max_steps: int) -> dict:
    """The whole split of a traced window: ``<phase>_step_us`` for each of
    ``PHASES`` and ``unscoped_step_us`` (the program's device time under
    none of them), per chip and scan step; ``gap_<part>_ms`` for each of
    ``GAP_PARTS``. A trace without scopes or spans leaves those out."""
    out = {}
    for phase in PHASES:
        us = step_us(trace, (phase,), dispatches, max_steps)
        if us is not None:
            out[phase.split(".", 1)[1] + "_step_us"] = us
    if out:
        rest = trace.program_busy_s(prefix) - trace.scoped_busy_s(PHASES)
        out["unscoped_step_us"] = 1e6 * rest / (dispatches * max_steps)
    out.update((f"gap_{part}_ms", ms)
               for part, ms in gap_ms(trace, prefix).items())
    return out


def from_xspace(path: str, hlo_text: str = "") -> ScopedTrace:
    """Read a profiler ``.xplane.pb`` file as ``trace.from_xspace`` does,
    and add the engine's host spans and, from ``hlo_text``, each device
    operation's scope."""
    from jax.profiler import ProfileData

    base = T.from_xspace(path)
    spans = list(base.spans)
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                spans.extend([e.name, int(e.start_ns), int(e.duration_ns)]
                             for e in line.events
                             if e.name.startswith(PROGRAM_SPAN_PREFIX))
    of = hlo_scopes(hlo_text) if hlo_text else {}
    scopes = {dev: {name: of[name] for name in {n for n, _, _ in ops}
                    if name in of}
              for dev, ops in base.ops.items()}
    return ScopedTrace(**{**_fields(base), "spans": spans}, scopes=scopes)
