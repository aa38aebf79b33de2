"""Unit tests for repro.config — the one environment-setup path.

These cover the pure string/env plumbing (no jax import, no subprocess):
device-flag rewriting, the compile-cache placement rules (an outside
directory exactly, else a fixed in-checkout one), subprocess environments
and the shell-export CLI contract that ``scripts/test.sh`` evals.
"""
from __future__ import annotations

import os
import subprocess
import sys

from repro import config as CFG


def test_device_flags_append_and_replace():
    assert CFG.device_flags(4, "") == \
        "--xla_force_host_platform_device_count=4"
    # replaces an existing count instead of stacking a second flag
    out = CFG.device_flags(8, "--xla_force_host_platform_device_count=2")
    assert out.count("xla_force_host_platform_device_count") == 1
    assert "=8" in out
    # unrelated flags survive
    out = CFG.device_flags(
        2, "--xla_cpu_foo=1 --xla_force_host_platform_device_count=4")
    assert "--xla_cpu_foo=1" in out and "=2" in out


def test_cache_dir_keyed_by_topology():
    # unset: a fixed in-checkout path, one directory per device count
    root = os.path.join(os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(CFG.__file__)))), ".jax_cache")
    assert CFG.DEFAULT_CACHE_ROOT == root
    assert CFG.cache_dir(1, {}) == os.path.join(root, "d1")
    assert CFG.cache_dir(8, {}) == os.path.join(root, "d8")


def test_cache_dir_set_from_outside_used_unchanged():
    # a directory placed from outside is used exactly: no device-count
    # suffix is added, and none is stripped
    for path in ("/some/dir", "/tmp/cc-d8"):
        env = {"JAX_COMPILATION_CACHE_DIR": path}
        assert CFG.cache_dir(1, env) == path
        assert CFG.cache_dir(4, env) == path


def test_subprocess_env_sets_flags_and_cache():
    env = CFG.subprocess_env(4, {"PATH": "/bin"})
    assert "--xla_force_host_platform_device_count=4" in env["XLA_FLAGS"]
    assert env["JAX_COMPILATION_CACHE_DIR"] == os.path.join(
        CFG.DEFAULT_CACHE_ROOT, "d4")
    assert env["PATH"] == "/bin"
    env = CFG.subprocess_env(4, {"JAX_COMPILATION_CACHE_DIR": "/some/dir"})
    assert env["JAX_COMPILATION_CACHE_DIR"] == "/some/dir"


def test_shell_exports_cli_round_trip():
    # scripts/test.sh does: eval "$(python -m repro.config)"
    out = subprocess.run(
        [sys.executable, "-m", "repro.config"],
        capture_output=True, text=True,
        env={"PATH": "/usr/bin:/bin", "XLA_DEVICES": "2",
             "JAX_COMPILATION_CACHE_DIR": "/some/dir",
             "PYTHONPATH": CFG.__file__.rsplit("/repro/", 1)[0]})
    assert out.returncode == 0, out.stderr
    lines = out.stdout.splitlines()
    assert any(l.startswith("export XLA_FLAGS=") and "=2" in l
               for l in lines)
    # the cache is placed per process (repro.config.apply_cache), never
    # exported: a child must not inherit its parent's device-keyed default
    assert not any("JAX_COMPILATION_CACHE_DIR" in l for l in lines)
