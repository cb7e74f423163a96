"""bench/run.py refuses to measure anywhere but on a TPU, in a checkout."""

import os
import shutil
import subprocess
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]


def _run(cwd: Path, script: Path):
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env["JAX_PLATFORMS"] = "cpu"
    return subprocess.run(
        [sys.executable, str(script), "--workload", "lcbench-saturated",
         "--seed", str(2**33 + 5), "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_no_tpu_exits_nonzero_and_prints_no_result():
    out = _run(ROOT, ROOT / "bench/run.py")
    assert out.returncode != 0
    assert "metrics" not in out.stdout
    assert "no TPU" in out.stderr


def test_outside_a_checkout_exits_nonzero_and_prints_no_result(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copytree(ROOT / "tests/bench", tmp_path / "tests/bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    out = _run(tmp_path, tmp_path / "bench/run.py")
    assert out.returncode != 0
    assert "metrics" not in out.stdout
