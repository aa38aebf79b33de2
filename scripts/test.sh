#!/usr/bin/env bash
# Tier-1 test runner. Environment setup (device count, XLA flags) comes
# from ONE place — `python -m repro.config` (see src/repro/config.py) —
# shared with tests/conftest.py, scripts/smoke_devices.py and
# benchmarks/common.py. Override the virtual device count with
# XLA_DEVICES=n (default 1; the main pytest process stays single-device —
# multi-device tests fork their own subprocesses with their own flags, see
# tests/conftest.py).
#
# Persistent XLA compilation cache: tests/conftest.py places it (repeat
# runs skip the ~9 s engine jit compiles) — JAX_COMPILATION_CACHE_DIR
# exactly, if set; otherwise <checkout>/.jax_cache/d<N>, keyed by each
# process's own virtual device count (repro.config.cache_dir).
set -euo pipefail
cd "$(dirname "$0")/.."

export PYTHONPATH="src${PYTHONPATH:+:$PYTHONPATH}"

eval "$(python -m repro.config)"

exec python -m pytest -x -q "$@"
