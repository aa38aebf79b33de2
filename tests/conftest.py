import pathlib
import subprocess
import sys

import pytest

REPO = pathlib.Path(__file__).resolve().parents[1]
SRC = REPO / "src"
sys.path.insert(0, str(SRC))

from repro import config as CFG  # noqa: E402

# this process's persistent compile cache: JAX_COMPILATION_CACHE_DIR as set,
# else <checkout>/.jax_cache/d<N> (children get theirs from subprocess_env)
CFG.apply_cache(CFG.host_devices())


def run_py(code: str, devices: int = 1, timeout: int = 300,
           cache_dir: str | None = None) -> str:
    """Run a python snippet in a subprocess with N host devices.

    Used by tests that need >1 device: the main pytest process must keep
    the default single-device jax (smoke tests measure that world), so
    multi-device checks fork with XLA_FLAGS set pre-init. The environment
    (device-count flag + compilation-cache dir) comes from repro.config;
    ``cache_dir`` overrides the compilation-cache location for tests that
    need a controlled cold/warm cache (e.g. the donation-replay
    regression).
    """
    env = CFG.subprocess_env(devices)
    env["PYTHONPATH"] = str(SRC)
    if cache_dir is not None:
        env["JAX_COMPILATION_CACHE_DIR"] = cache_dir
    out = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True,
        env=env, timeout=timeout,
    )
    if out.returncode != 0:
        raise AssertionError(
            f"subprocess failed:\nSTDOUT:\n{out.stdout}\nSTDERR:\n{out.stderr}"
        )
    return out.stdout


@pytest.fixture
def subproc():
    return run_py
