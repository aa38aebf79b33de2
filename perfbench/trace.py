"""Reduce a JAX profiler trace to the numbers the per-layer metrics read.

``from_xspace`` reads the ``.xplane.pb`` file that ``jax.profiler`` writes and
keeps three things, all in nanoseconds on the trace's one clock:

* for each device plane (``/device:<KIND>:<id>``): the ``XLA Ops`` events
  (one per operation that ran) and the ``XLA Modules`` events (one per run of
  a compiled program);
* the harness's own host spans (``jax.profiler.TraceAnnotation`` names that
  start with ``perfbench.``);
* the traced window: the harness span ``perfbench.window``.

``Trace`` holds that in plain lists, so a small trace can be kept as a JSON
fixture and reduced without a chip. Its methods do the arithmetic: the union
of busy intervals, the idle gaps with the harness span that was open, the
device time of one compiled program, and the operations that took longest.
"""
from __future__ import annotations

import dataclasses
import functools
import json
from collections import defaultdict

SPAN_PREFIX = "perfbench."
WINDOW_SPAN = "perfbench.window"
OUTSIDE = "outside harness spans"


def union(intervals):
    """Merge ``(start, end)`` pairs into sorted disjoint intervals."""
    merged = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return [(s, e) for s, e in merged]


def clip(intervals, lo, hi):
    return [(max(s, lo), min(e, hi)) for s, e in intervals if e > lo and s < hi]


def complement(intervals, lo, hi):
    """The parts of ``[lo, hi]`` that no interval covers, as gaps."""
    gaps, at = [], lo
    for s, e in union(clip(intervals, lo, hi)):
        if s > at:
            gaps.append((at, s))
        at = max(at, e)
    if hi > at:
        gaps.append((at, hi))
    return gaps


@dataclasses.dataclass
class Trace:
    window: tuple                 # (start_ns, end_ns) of the harness window
    ops: dict                     # device id -> [[name, start_ns, dur_ns]]
    modules: dict                 # device id -> [[name, start_ns, dur_ns]]
    spans: list                   # [[name, start_ns, dur_ns]] harness spans

    # ------------------------------------------------------------ storage
    def to_json(self) -> str:
        return json.dumps(dataclasses.asdict(self))

    @classmethod
    def from_json(cls, text: str) -> "Trace":
        d = json.loads(text)
        return cls(window=tuple(d["window"]),
                   ops={str(k): v for k, v in d["ops"].items()},
                   modules={str(k): v for k, v in d["modules"].items()},
                   spans=d["spans"])

    # ---------------------------------------------------------- reduction
    @property
    def devices(self) -> list:
        return sorted(self.ops, key=lambda d: int(d))

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) * 1e-9

    def _intervals(self, events):
        return [(s, s + d) for _, s, d in events]

    @functools.cached_property
    def _busy(self) -> dict:
        return {dev: union(clip(self._intervals(ops), *self.window))
                for dev, ops in self.ops.items()}

    def busy_intervals(self, dev) -> list:
        """Sorted disjoint intervals in which some operation ran on ``dev``,
        within the window."""
        return self._busy.get(dev, [])

    def busy_s(self) -> float:
        """Seconds in which some operation ran, averaged over the devices."""
        if not self.devices:
            return 0.0
        total = sum(e - s for dev in self.devices
                    for s, e in self.busy_intervals(dev))
        return total * 1e-9 / len(self.devices)

    def idle_gaps(self, dev) -> list:
        return complement(self.busy_intervals(dev), *self.window)

    def open_span(self, t) -> str:
        """The innermost harness span open at ``t`` (not the window span)."""
        best = None
        for name, s, d in self.spans:
            if name != WINDOW_SPAN and s <= t <= s + d:
                if best is None or d < best[1]:
                    best = (name, d)
        return best[0] if best else OUTSIDE

    def program_runs(self, dev, prefix) -> list:
        """Runs of the compiled programs whose name starts with ``prefix``,
        as sorted ``(start, end)`` within the window."""
        return sorted(clip([(s, s + d) for name, s, d in self.modules.get(dev, [])
                            if name.startswith(prefix)], *self.window))

    def program_busy_s(self, prefix) -> float:
        """Device seconds spent in those programs, averaged over devices."""
        if not self.devices:
            return 0.0
        total = sum(e - s for dev in self.devices
                    for s, e in self.program_runs(dev, prefix))
        return total * 1e-9 / len(self.devices)

    def gaps_between_runs(self, prefix) -> list:
        """Device-idle seconds between consecutive runs of the programs,
        one entry per consecutive pair on each device."""
        out = []
        for dev in self.devices:
            runs = self.program_runs(dev, prefix)
            busy = self.busy_intervals(dev)
            for (_, end), (start, _) in zip(runs, runs[1:]):
                if start <= end:
                    continue
                covered = sum(e - s for s, e in clip(busy, end, start))
                out.append((start - end - covered) * 1e-9)
        return out

    def top_ops(self, n=10) -> list:
        """``[name, seconds]`` of the operations that took longest, summed
        over their runs in the window and averaged over the devices."""
        by_name = defaultdict(int)
        for dev in self.devices:
            for name, s, d in self.ops[dev]:
                lo, hi = max(s, self.window[0]), min(s + d, self.window[1])
                if hi > lo:
                    by_name[name] += hi - lo
        k = max(len(self.devices), 1)
        ranked = sorted(by_name.items(), key=lambda kv: -kv[1])[:n]
        return [[name, ns * 1e-9 / k] for name, ns in ranked]

    def longest_gaps(self, n=10) -> list:
        """``[harness span open at the gap, seconds]`` of the longest idle
        gaps on any device."""
        gaps = [(e - s, self.open_span((s + e) // 2))
                for dev in self.devices for s, e in self.idle_gaps(dev)]
        gaps.sort(key=lambda g: -g[0])
        return [[label, ns * 1e-9] for ns, label in gaps[:n]]


def op_name(text: str) -> str:
    """An operation's name without its HLO text: ``%fusion.466 = s32[...]
    fusion(...)`` becomes ``fusion.466``."""
    return text.split(" = ", 1)[0].lstrip("%")


def from_xspace(path: str) -> Trace:
    """Read a profiler ``.xplane.pb`` file into a :class:`Trace`."""
    from jax.profiler import ProfileData

    ops, modules, spans = {}, {}, []
    short = functools.lru_cache(maxsize=None)(op_name)
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/device:"):
            dev = plane.name.rsplit(":", 1)[-1]
            if not dev.isdigit():
                continue
            for line in plane.lines:
                if line.name == "XLA Ops":
                    ops.setdefault(dev, []).extend(
                        [short(e.name), int(e.start_ns), int(e.duration_ns)]
                        for e in line.events)
                elif line.name == "XLA Modules":
                    modules.setdefault(dev, []).extend(
                        [e.name, int(e.start_ns), int(e.duration_ns)]
                        for e in line.events)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                spans.extend([e.name, int(e.start_ns), int(e.duration_ns)]
                             for e in line.events
                             if e.name.startswith(SPAN_PREFIX))
    windows = [(s, s + d) for name, s, d in spans if name == WINDOW_SPAN]
    if not windows:
        raise ValueError(f"{path}: no {WINDOW_SPAN} span in the trace")
    for dev in list(modules):
        ops.setdefault(dev, [])
    return Trace(window=windows[0], ops=ops, modules=modules, spans=spans)
