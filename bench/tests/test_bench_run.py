"""The command refuses to run where it cannot measure the chip."""

import os
import shutil
import subprocess
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent


def _run(cwd, extra_env=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **(extra_env or {}))
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "fwd-udp", "--seed",
         str(2**31 + 11), "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300,
    )


def _no_result_line(out: str) -> bool:
    lines = [ln for ln in out.strip().splitlines() if ln.strip()]
    return not lines or not lines[-1].lstrip().startswith("{")


def test_refuses_without_a_tpu():
    proc = _run(ROOT)
    assert proc.returncode != 0
    assert "no TPU" in proc.stderr
    assert _no_result_line(proc.stdout)


def test_refuses_in_a_checkout_of_only_the_benchmark(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    shutil.copytree(
        BENCH, tmp_path / "bench",
        ignore=shutil.ignore_patterns(".jax_cache", ".trace", "__pycache__"),
    )
    proc = _run(tmp_path, {"PYTHONPATH": ""})
    assert proc.returncode != 0
    assert _no_result_line(proc.stdout)
