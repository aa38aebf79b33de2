"""Launcher drivers run end-to-end (subprocess smoke)."""
import os
import pathlib
import subprocess
import sys

REPO = pathlib.Path(__file__).resolve().parents[1]


def run_module(mod: str, *args: str, timeout=420) -> str:
    env = dict(os.environ)
    env["PYTHONPATH"] = str(REPO / "src")
    out = subprocess.run(
        [sys.executable, "-m", mod, *args], capture_output=True, text=True,
        env=env, timeout=timeout, cwd=REPO,
    )
    assert out.returncode == 0, out.stdout + out.stderr
    return out.stdout


def test_train_driver_with_drill():
    out = run_module(
        "repro.launch.train", "--arch", "internlm2-20b", "--steps", "12",
        "--batch", "2", "--seq", "32", "--ckpt-every", "5", "--kill-at", "7",
        "--kill-fraction", "0.25", "--vault-nodes", "120",
        "--log-every", "4",
    )
    assert "restore OK" in out
    assert "improved" in out.splitlines()[-1]


def test_serve_driver():
    out = run_module(
        "repro.launch.serve", "--arch", "qwen1.5-110b", "--batch", "2",
        "--prompt-len", "12", "--decode-steps", "4",
    )
    assert "decode:" in out and "tok/s" in out


def test_dryrun_single_cell_fast(tmp_path):
    """One real dry-run cell on the 512-device mesh, analysis skipped
    (the full sweep is results/dryrun; this guards the entry point)."""
    out = run_module(
        "repro.launch.dryrun", "--arch", "mamba2-2.7b", "--shape",
        "decode_32k", "--mesh", "single", "--no-analysis", "--tag", "smoke",
        "--out-dir", str(tmp_path), timeout=420,
    )
    assert "[ok]" in out
    assert (tmp_path / "mamba2-2.7b__decode_32k__single__smoke.json").exists()
