"""One place for computation-environment knobs (the bayespec mold).

Benchmarks, tests, CI jobs, and ad-hoc scripts all need the same four
decisions made *before* JAX initializes its backends: float precision,
platform, virtual host-device count, and NaN debugging. Historically each
entry point re-derived them (``scripts/test.sh`` in bash,
``tests/conftest.py`` for subprocesses, ``scripts/smoke_devices.py`` by
hand); this module is the single source of truth they all consume.

Environment knobs (all optional):

* ``XLA_DEVICES`` — virtual host device count
  (``--xla_force_host_platform_device_count``). The scenario engine's
  ``devices=`` axis shards over these; see ``scenarios._compile_runner``.
* ``REPRO_PLATFORM`` — ``cpu`` / ``gpu`` / ``tpu``
  (``jax_platform_name``; GPU also gets the XLA perf-flag recipe).
* ``REPRO_X64`` — truthy enables float64 (``jax_enable_x64``).
* ``REPRO_DEBUG_NANS`` — truthy enables ``jax_debug_nans``.
* ``JAX_COMPILATION_CACHE_DIR`` — persistent compile cache. When set, that
  exact directory is used (and handed to child processes unchanged); when
  unset, the cache lives inside the checkout at ``.jax_cache/d<N>``,
  keyed by the virtual host-device count — see :func:`cache_dir`. Each
  process places its own cache with :func:`apply_cache`.

Import discipline: this module never imports ``jax`` at the top level, so
the pre-init knobs (:func:`set_host_devices`, :func:`cache_dir`,
:func:`subprocess_env`) are safe to call before the first ``import jax``
— and ``python -m repro.config`` (the shell exporter ``scripts/test.sh``
evals) never pays for a JAX import at all.
"""
from __future__ import annotations

import os
import re
import sys

# Matches the device-count flag (with its value) inside an XLA_FLAGS string.
_DEVICE_FLAG_RE = re.compile(
    r"--xla_force_host_platform_device_count=\d+\s*")

# In-checkout home of the compile cache when JAX_COMPILATION_CACHE_DIR is
# unset (listed in .gitignore). A fixed path: the directory is part of what
# makes a later run find its entries again.
DEFAULT_CACHE_ROOT = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")

# The XLA perf-flag recipe for GPU runs (bayespec's set_platform; see
# https://jax.readthedocs.io/en/latest/gpu_performance_tips.html).
GPU_XLA_FLAGS = (
    "--xla_gpu_triton_gemm_any=True "
    "--xla_gpu_enable_latency_hiding_scheduler=true "
    "--xla_gpu_enable_highest_priority_async_stream=true"
)


# ------------------------------------------------------- pre-init (env) ---
def device_flags(devices: int, base: str | None = None) -> str:
    """XLA_FLAGS string forcing ``devices`` virtual host devices.

    Any device-count flag already present in ``base`` is replaced; every
    other flag is preserved. Pure string function — usable for building
    subprocess environments without touching this process.
    """
    rest = _DEVICE_FLAG_RE.sub("", base or "").strip()
    flag = f"--xla_force_host_platform_device_count={int(devices)}"
    return f"{flag} {rest}".strip()


def set_host_devices(devices: int) -> None:
    """Force ``devices`` virtual host devices in THIS process.

    Only takes effect before JAX initializes its backends (the flag is
    read at backend setup, not at ``import jax``). Unlike bayespec's
    ``set_cpu_cores`` this deliberately does not clamp to the physical
    core count: oversubscribed virtual devices are exactly how CI
    exercises the sharded dispatch path on small runners.
    """
    os.environ["XLA_FLAGS"] = device_flags(
        devices, os.environ.get("XLA_FLAGS"))


def host_devices(env: dict | None = None) -> int:
    """Virtual host device count requested by ``XLA_DEVICES`` (default 1)."""
    env = os.environ if env is None else env
    return int(env.get("XLA_DEVICES") or 1)


def cache_dir(devices: int = 1, env: dict | None = None) -> str:
    """Persistent compilation-cache directory.

    ``JAX_COMPILATION_CACHE_DIR`` exactly as set, if it is set. Otherwise
    ``<checkout>/.jax_cache/d<devices>``: one directory per virtual host
    device count, so CPU runs at different topologies never replay each
    other's entries.
    """
    env = os.environ if env is None else env
    return (env.get("JAX_COMPILATION_CACHE_DIR")
            or os.path.join(DEFAULT_CACHE_ROOT, f"d{int(devices)}"))


def subprocess_env(devices: int, env: dict | None = None) -> dict:
    """Environment for a child process pinned to ``devices`` host devices.

    Sets the device-count flag (pre-init, so the child sees it) and the
    compilation-cache dir of :func:`cache_dir`. Used by
    ``tests/conftest.py`` and the device-scaling study in
    ``benchmarks/engine_speed.py``.
    """
    env = dict(os.environ if env is None else env)
    env["XLA_FLAGS"] = device_flags(devices, env.get("XLA_FLAGS"))
    env["JAX_COMPILATION_CACHE_DIR"] = cache_dir(devices, env)
    return env


# ----------------------------------------------------- jax.config knobs ---
def apply_cache(devices: int = 1, env: dict | None = None) -> str:
    """Point THIS process's persistent compilation cache at
    :func:`cache_dir` and return the directory."""
    import jax

    path = cache_dir(devices, env)
    jax.config.update("jax_compilation_cache_dir", path)
    return path


def enable_x64(use_x64: bool = True) -> None:
    """Default JAX arrays to float64 (else float32)."""
    import jax

    jax.config.update("jax_enable_x64", bool(use_x64))


def set_platform(platform: str = "cpu") -> None:
    """Pin the JAX platform (``cpu`` / ``gpu`` / ``tpu``).

    Only takes effect at the beginning of the program; ``gpu`` also
    applies :data:`GPU_XLA_FLAGS` (preserving any flags already set).
    """
    import jax

    jax.config.update("jax_platform_name", platform)
    if platform == "gpu":
        prev = os.environ.get("XLA_FLAGS", "")
        extra = " ".join(f for f in GPU_XLA_FLAGS.split() if f not in prev)
        if extra:
            os.environ["XLA_FLAGS"] = f"{prev} {extra}".strip()


def set_debug_nan(flag: bool = True) -> None:
    """Raise on the first NaN any jitted computation produces."""
    import jax

    jax.config.update("jax_debug_nans", bool(flag))


def _truthy(val: str | None) -> bool:
    return (val or "").strip().lower() not in ("", "0", "false", "no")


def apply_env(env: dict | None = None) -> dict:
    """Apply every knob present in the environment; return what was set.

    The one-call setup path shared by ``benchmarks/common.py`` (import
    time) and ad-hoc scripts. Must run before JAX initializes backends
    for the device count / platform to stick.
    """
    env = os.environ if env is None else env
    applied: dict = {}
    devices = host_devices(env)
    if env.get("XLA_DEVICES"):
        set_host_devices(devices)
        applied["devices"] = devices
    applied["cache_dir"] = apply_cache(devices, env)
    if env.get("REPRO_PLATFORM"):
        set_platform(env["REPRO_PLATFORM"])
        applied["platform"] = env["REPRO_PLATFORM"]
    if _truthy(env.get("REPRO_X64")):
        enable_x64(True)
        applied["x64"] = True
    if _truthy(env.get("REPRO_DEBUG_NANS")):
        set_debug_nan(True)
        applied["debug_nans"] = True
    return applied


# -------------------------------------------------------- shell exporter --
def shell_exports(env: dict | None = None) -> list[str]:
    """``export KEY="VAL"`` lines for shell consumers (scripts/test.sh).

    Derives XLA_FLAGS (device count from ``XLA_DEVICES``, default 1) from
    the same rules the Python consumers use, so bash and Python can never
    drift. The compile cache is deliberately not exported: every process
    places its own (:func:`apply_cache`), so a ``JAX_COMPILATION_CACHE_DIR``
    set from outside reaches each of them unchanged, and the in-checkout
    default stays keyed by each process's own device count.
    """
    env = os.environ if env is None else env
    flags = device_flags(host_devices(env), env.get("XLA_FLAGS"))
    return [f'export XLA_FLAGS="{flags}"']


def main(argv: list[str] | None = None) -> int:
    """CLI: print shell export lines (``eval "$(python -m repro.config)"``)."""
    for line in shell_exports():
        print(line)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
