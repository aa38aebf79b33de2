"""The harness: finds a cell's files by name, sets the cell up, measures its
window, checks what the window produced, and assembles the result line.

Everything that belongs to one configuration, traffic mix, check or metric
sits in a file of its own under ``perfbench/``, found by the name that
``BENCHMARK.json`` gives:

* ``configs/<file>``: the deployment; its ``runner`` and ``reference`` name
  ``runners/<runner>.py`` and ``references/<reference>.py``;
* ``traffic/<traffic>.json``: the traffic mix and the dispatch layout;
* ``checks/<workload>.json``: the numbers compared and their limits;
* ``metrics/<metric>.py``: a reader ``read(run) -> float | None``, run in
  the cells that the metric's ``workloads`` list names, or in every cell
  where it has none.
"""
from __future__ import annotations

import dataclasses
import glob
import importlib.util
import json
import os
import shutil
import tempfile
import time

import jax
import jax.monitoring
import numpy as np

from perfbench import check as C
from perfbench import scopes as S
from perfbench import trace as T

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
# keys the persistent compile cache with op metadata (a traced set-up)
METADATA_KEY = "jax_compilation_cache_include_metadata_in_key"
# seed streams derived from the run's --seed (check.seeds_for)
WARM, WINDOW, REFERENCE, CONTROL = 0, 1, 2, 4
# a traced window runs this many dispatches (two gaps between them): a trace
# holds about a million device events a second on each chip, so it is kept
# as short as the per-layer metrics allow
TRACE_DISPATCHES = 3


class NoChip(RuntimeError):
    """JAX finds no accelerator the benchmark can measure, or too few."""


class Bench:
    """``BENCHMARK.json`` and the files it names, under ``root``."""

    def __init__(self, root: str):
        self.root = root
        with open(os.path.join(root, "BENCHMARK.json")) as fh:
            self.spec = json.load(fh)

    def _json(self, *parts) -> dict:
        with open(os.path.join(self.root, "perfbench", *parts)) as fh:
            return json.load(fh)

    def _entry(self, key: str, name: str) -> dict:
        for entry in self.spec[key]:
            if entry["name"] == name:
                return entry
        raise KeyError(f"no {key[:-1]} named {name!r} in BENCHMARK.json")

    def workload(self, name: str) -> dict:
        return self._entry("workloads", name)

    def config(self, name: str) -> dict:
        with open(os.path.join(self.root, self._entry("configs", name)["file"])) as fh:
            return json.load(fh)

    def traffic(self, name: str) -> dict:
        return self._json("traffic", f"{name}.json")

    def check(self, workload: str) -> dict:
        return self._json("checks", f"{workload}.json")

    def peaks(self) -> dict:
        return self._json("peaks.json")

    def metrics(self, traced: bool, workload: str) -> list:
        """The metrics the cell ``workload`` reports: the per-layer ones
        when traced, else the end-to-end ones; a metric with a
        ``workloads`` list only in the cells it lists. A reader that finds
        nothing to read in a cell returns ``None`` and its metric is left
        out of the line."""
        return [m for m in self.spec["per_layer" if traced else "end_to_end"]
                if workload in m.get("workloads", [workload])]

    def module(self, kind: str, name: str):
        """``perfbench/<kind>/<name>.py``, loaded by path."""
        path = os.path.join(self.root, "perfbench", kind, f"{name}.py")
        spec = importlib.util.spec_from_file_location(
            f"perfbench_{kind}_{name}".replace(".", "_").replace("-", "_"), path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        return mod


def require_chips(chips: int, peaks: dict) -> list:
    """The first ``chips`` accelerators, or :class:`NoChip`."""
    devs = jax.devices()
    if devs[0].platform == "cpu":
        raise NoChip("JAX finds no accelerator, only the CPU")
    if len(devs) < chips:
        raise NoChip(f"the cell needs {chips} chips, JAX finds {len(devs)}")
    if devs[0].device_kind not in peaks:
        raise NoChip(f"{devs[0].device_kind!r} is not in perfbench/peaks.json")
    return devs[:chips]


def use_compile_cache(root: str) -> str:
    """JAX's persistent compilation cache: ``JAX_COMPILATION_CACHE_DIR`` if
    set, else a fixed directory inside the checkout."""
    path = (os.environ.get("JAX_COMPILATION_CACHE_DIR")
            or os.path.join(root, ".jax_cache", "perfbench"))
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path


def start(bench: Bench, workload: str) -> list:
    """The chips the cell asks for, or :class:`NoChip`; with the compile cache
    in place."""
    devices = require_chips(int(bench.workload(workload)["chips"]),
                            bench.peaks())
    use_compile_cache(bench.root)
    return devices


class CompileCounter:
    """Counts the XLA compilations (cache loads included) that start after
    :meth:`open_window`, from JAX's own monitoring events."""

    def __init__(self):
        self.window = 0
        self._in_window = False

    def _listen(self, event, duration, **_):
        if event == COMPILE_EVENT and self._in_window:
            self.window += 1

    def __enter__(self):
        jax.monitoring.register_event_duration_secs_listener(self._listen)
        return self

    def open_window(self):
        self._in_window = True

    def __exit__(self, *exc):
        jax.monitoring.unregister_event_duration_listener(self._listen)


@dataclasses.dataclass
class Run:
    """What a metric reader may read about one run."""

    setup_s: float
    window_s: float
    hours: float                  # simulated deployment-hours completed
    dispatches: int
    compiles_in_window: int
    chips: int
    max_steps: int                # scan iterations of one dispatch
    state_bytes_per_step: float   # least bytes one step moves, all chips
    peaks: dict                   # this device's row of peaks.json
    program_prefix: str           # name of the dispatches' compiled program
    trace: T.Trace | None = None  # a scopes.ScopedTrace when traced
    counters: dict | None = None  # the runner's counters() over the window


@dataclasses.dataclass
class Cell:
    """A cell set up for its window: the runner, warm, and the reference."""

    runner: object
    reference: object             # the module of references/<reference>.py
    program_prefix: str
    build_s: float                # building the runner and its inputs
    warm_s: float                 # the warm-up dispatch: compile or cache load
    hlo: str = ""                 # the program's HLO text (traced set-up)


def set_up(bench: Bench, workload: str, seed: int,
           traced: bool = False) -> Cell:
    """Build the cell's runner and run one dispatch of exactly one chunk,
    which compiles (or loads from the cache) the one program the window
    runs. Traced, a runner that offers ``program_hlo(call)`` runs the
    warm-up through it and hands over the compiled program's HLO text,
    which names each operation's scope. The compile cache is then keyed
    with op metadata, so the program that runs is built from this
    version's HLO, not loaded from a build of another version (the same
    ops, other scopes)."""
    t = time.perf_counter()
    wl = bench.workload(workload)
    cfg = bench.config(wl["config"])
    traffic = bench.traffic(wl["traffic"])
    if int(traffic.get("devices", 1)) != int(wl["chips"]):
        raise ValueError(f"traffic {wl['traffic']!r} lays the batch over "
                         f"{traffic.get('devices', 1)} chips, the cell asks "
                         f"for {wl['chips']}")
    runner_mod = bench.module("runners", cfg["runner"])
    runner = runner_mod.Runner(cfg, traffic)
    built = time.perf_counter()
    seeds = C.seeds_for(seed, WARM, 0, runner.seeds_per_dispatch)
    hook = getattr(runner, "program_hlo", None) if traced else None
    hlo = ""
    if hook is None:
        runner.dispatch(runner.plan(0), seeds)
    else:
        keyed = getattr(jax.config, METADATA_KEY)
        jax.config.update(METADATA_KEY, True)
        try:
            hlo = hook(lambda: runner.dispatch(runner.plan(0), seeds))
        finally:
            jax.config.update(METADATA_KEY, keyed)
    return Cell(runner, bench.module("references", cfg["reference"]),
                runner_mod.PROGRAM_PREFIX, built - t,
                time.perf_counter() - built, hlo)


def host_times() -> tuple:
    """Seconds of host time so far: this process on a CPU (all threads), its
    threads waiting for a CPU (Linux schedstat), and the machine's CPUs taken
    by the hypervisor (steal, ``/proc/stat``). A reading the system does not
    offer is 0."""
    wait = 0.0
    for path in glob.glob("/proc/self/task/*/schedstat"):
        try:
            with open(path) as fh:
                wait += int(fh.read().split()[1]) * 1e-9
        except (OSError, IndexError, ValueError):
            pass
    steal = 0.0
    try:
        with open("/proc/stat") as fh:
            steal = int(fh.readline().split()[8]) / os.sysconf("SC_CLK_TCK")
    except (OSError, IndexError, ValueError):
        pass
    return time.process_time(), wait, steal


@dataclasses.dataclass
class Window:
    elements: list                # {"cell", "seed", "result"} per element
    hours: float
    dispatches: int
    seconds: float
    state_bytes: float            # least bytes per step of the last dispatch
    host: tuple                   # host_times() over the window
    trace: T.Trace | None = None
    counters: dict | None = None


def measure(runner, seed: int, seconds: float, traced: bool,
            min_dispatches: int = 1, hlo: str = "") -> Window:
    """Back-to-back dispatches until the one in flight at ``seconds`` returns
    and ``min_dispatches`` have run; with ``traced``, under the profiler,
    the trace reduced with the engine's spans and, from ``hlo``, its
    scopes. A runner that offers ``counters()`` (``{name: number}``) is
    read at the window's start and end, and the window keeps the
    difference."""
    elements, hours, rep, state = [], 0.0, 0, 0
    counters = getattr(runner, "counters", None)
    trace_dir = tempfile.mkdtemp(prefix="perfbench-trace-") if traced else None
    try:
        if traced:
            jax.profiler.start_trace(trace_dir)
        try:
            with jax.profiler.TraceAnnotation(T.WINDOW_SPAN):
                counted = counters() if counters else None
                host = host_times()
                start = time.perf_counter()
                while True:
                    cells = runner.plan(rep)
                    seeds = C.seeds_for(seed, WINDOW, rep,
                                        runner.seeds_per_dispatch)
                    with jax.profiler.TraceAnnotation("perfbench.dispatch"):
                        out = runner.dispatch(cells, seeds)
                    elements += [{"cell": c, "seed": s,
                                  "result": out[i * len(seeds) + j]}
                                 for i, c in enumerate(cells)
                                 for j, s in enumerate(seeds)]
                    hours += runner.hours(cells, seeds)
                    state = runner.state_bytes_per_step(cells, seeds)
                    rep += 1
                    if (time.perf_counter() - start >= seconds
                            and rep >= min_dispatches):
                        break
                took = time.perf_counter() - start
                host = tuple(b - a for a, b in zip(host, host_times()))
                if counters:
                    counted = {k: v - counted.get(k, 0)
                               for k, v in counters().items()}
        finally:
            if traced:
                jax.profiler.stop_trace()
        trace = None
        if traced:
            (path,) = glob.glob(os.path.join(trace_dir, "plugins", "profile",
                                             "*", "*.xplane.pb"))
            trace = S.from_xspace(path, hlo)
    finally:
        if trace_dir:
            shutil.rmtree(trace_dir, ignore_errors=True)
    return Window(elements, hours, rep, took, state, host, trace, counted)


def judge(bench: Bench, workload: str, runner, ref_mod, elements: list,
          seed: int, ftype=np.float64, stand_in: bool = False) -> tuple:
    """Compare the window's elements with the reference on the sweep points
    drawn from ``seed``. With ``stand_in``, the reference at ``ftype`` (the
    control) takes the program's place: one element per drawn point, on
    seeds of its own. Returns ``(values, failed, check spec)``: ``failed``
    maps each failed element's index to the numbers it failed."""
    chk = bench.check(workload)
    drawn = C.sample_cells(seed, [e["cell"] for e in elements],
                           chk["cells_sampled"])
    jobs = [(runner.cells[c], C.seeds_for(seed, REFERENCE, c, 1)[0])
            for c in drawn]
    refs = dict(zip(drawn, ref_mod.simulate_all(jobs)))
    if stand_in:
        seeds = [C.seeds_for(seed, CONTROL, c, 1)[0] for c in drawn]
        results = ref_mod.simulate_all(
            [(runner.cells[c], s) for c, s in zip(drawn, seeds)],
            ftype)
        elements = [{"cell": c, "seed": s, "result": r}
                    for c, s, r in zip(drawn, seeds, results)]
    values, failed = C.compare(elements, refs, chk["numbers"],
                               chk.get("losing_share"))
    return values, failed, chk


def memory_peak(devices) -> int:
    stats = [d.memory_stats() or {} for d in devices]
    return max(int(s.get("peak_bytes_in_use", 0)) for s in stats)


def reading(bench: Bench, cell: Cell, win: Window, setup_s: float,
            compiles: int, devices) -> Run:
    """What the metric readers read about a cell's run."""
    return Run(setup_s=setup_s, window_s=win.seconds, hours=win.hours,
               dispatches=win.dispatches, compiles_in_window=compiles,
               chips=len(devices), max_steps=cell.runner.max_steps,
               state_bytes_per_step=win.state_bytes,
               peaks=bench.peaks().get(devices[0].device_kind, {}),
               program_prefix=cell.program_prefix, trace=win.trace,
               counters=win.counters)


def read_metrics(bench: Bench, workload: str, traced: bool, run: Run) -> dict:
    """``{name: value}`` of the metrics the cell reports, by their readers;
    a metric whose reader finds nothing to read is left out."""
    values = {}
    for m in bench.metrics(traced, workload):
        value = bench.module("metrics", m["name"]).read(run)
        if value is not None:
            values[m["name"]] = value
    return values


def execute(bench: Bench, workload: str, seed: int, seconds: float,
            traced: bool, devices, t0: float) -> tuple:
    """Run one cell: set-up (counted from ``t0``), the window, the check.
    Returns the result line and the lines to print last on standard error."""
    reached = time.perf_counter() - t0
    with CompileCounter() as compiles:
        cell = set_up(bench, workload, seed, traced)
        setup_s = time.perf_counter() - t0
        compiles.open_window()
        if traced:
            win = measure(cell.runner, seed, 0.0, True, TRACE_DISPATCHES,
                          cell.hlo)
        else:
            win = measure(cell.runner, seed, seconds, False)
    mem = memory_peak(devices)
    values, failed, chk = judge(bench, workload, cell.runner, cell.reference,
                                win.elements, seed)
    run = reading(bench, cell, win, setup_s, compiles.window, devices)
    units = {m["name"]: m["unit"] for m in bench.metrics(traced, workload)}
    metrics = {name: {"value": value, "unit": units[name]}
               for name, value in read_metrics(bench, workload, traced,
                                               run).items()}
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices),
              "memory_peak_bytes": mem}
    line = {"correct": C.passed(values, chk["numbers"]) and not failed,
            "attempted": len(win.elements), "failed": len(failed),
            "metrics": metrics, "device": device}
    if win.trace is not None:
        device["busy_s"] = win.trace.busy_s()
        device["window_s"] = win.trace.window_s
        line["breakdown"] = {"device_ops": win.trace.top_ops(),
                             "idle_gaps": win.trace.longest_gaps()}
    line["compared"] = C.verdict(values, chk["numbers"])
    report = [f"set-up {setup_s} s: {reached} s to reach the chips, "
              f"{cell.build_s} s to build the cell, {cell.warm_s} s for the "
              f"warm-up dispatch",
              f"window {win.seconds} s: {win.dispatches} dispatches; the "
              f"process on a CPU {win.host[0]} s, its threads waiting for "
              f"one {win.host[1]} s, the machine's CPUs stolen "
              f"{win.host[2]} s"]
    report += [f"failed element: point {win.elements[i]['cell']} seed "
               f"{win.elements[i]['seed']}: {', '.join(names)}"
               for i, names in sorted(failed.items())[:20]]
    report += [f"compared {name}: {v['value']} limit {v['limit']}"
               for name, v in line["compared"].items()]
    return line, report
