"""chip_smoke.py: its phases at toy sizes on the CPU, and its refusals.

The phases run with interpret-mode kernels here; on the chip the same
functions run at deployment width with compiled kernels.  The script
itself must fail, and print no result line, wherever JAX finds no TPU and
wherever the repository around it is missing.
"""

import importlib.util
import os
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[1]
SCRIPT = ROOT / "chip_smoke.py"


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke", SCRIPT)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_served_phase_matches_cpu_twin_at_toy_size(smoke):
    out = smoke.phase_served(slices=4, horizon=30.0, min_live=50,
                             num_sessions=40, arrival_rate=1.0,
                             session_scale=10.0, m_min=2, m_max=16)
    assert out["divergence"] == "none"
    assert out["launches"] > 0 and out["live"] >= 50
    assert out["compiles"] > 0


def test_served_phase_refuses_a_small_pool(smoke):
    with pytest.raises(smoke.SmokeFailure, match="live pool"):
        smoke.phase_served(slices=4, horizon=5.0, min_live=10_000,
                           num_sessions=10, arrival_rate=1.0,
                           session_scale=10.0, m_min=2, m_max=8)


def test_scorer_phase_agrees_with_interpreted_kernels(smoke):
    out = smoke.phase_scorers(tenants=8, models_per_tenant=16,
                              interpret=True)
    assert len(set(out["picks"].values())) == 1
    assert out["impl"] == {"fused": "xla", "ops": "pallas-interpreted",
                           "sharded": "pallas_topk-interpreted"}


def test_scorer_phase_refuses_the_wrong_implementation(smoke, monkeypatch):
    # interpreted kernels that lower like compiled ones: the phase must
    # fail instead of reporting an implementation that did not run
    monkeypatch.setattr(smoke, "_lowers_to_kernel", lambda *a, **k: True)
    with pytest.raises(smoke.SmokeFailure, match="wrong implementation"):
        smoke.phase_scorers(tenants=4, models_per_tenant=8, interpret=True)


def test_batched_phase_matches_simulate(smoke):
    out = smoke.phase_batched(tenants=3, models_per_tenant=8,
                              devices=(1, 2), check=2)
    assert out["divergence"] == "none" and out["trials"] == 24


def test_device_phase_refuses_the_cpu(smoke):
    with pytest.raises(smoke.SmokeFailure, match="no TPU"):
        smoke.phase_device()


def test_sharded4_phase_on_forced_devices(forced_devices):
    res = forced_devices(f"""
        import json, sys
        sys.path.insert(0, {str(ROOT)!r})
        import chip_smoke
        out = chip_smoke.phase_sharded4(tenants=8, models_per_tenant=12,
                                        decisions=10)
        print(json.dumps(out))
    """, devices=4)
    assert res["divergence"] == "none"
    assert res["shards"] == 4 and res["decisions"] == 10


def test_smoke_never_imports_the_dry_run_tool():
    """``launch/dryrun.py`` rewrites XLA_FLAGS when imported and starts
    child processes: nothing on the chip path may pull it in."""
    out = subprocess.run(
        [sys.executable, "-c",
         "import os, sys, chip_smoke; "
         "print('repro.launch.dryrun' in sys.modules, "
         "os.environ.get('XLA_FLAGS'))"],
        cwd=ROOT, env={k: v for k, v in os.environ.items()
                       if k != "XLA_FLAGS"},
        capture_output=True, text=True, timeout=300)
    assert out.returncode == 0, out.stderr[-2000:]
    assert out.stdout.split() == ["False", "None"]


def _run_script(cwd: Path, env_extra: dict) -> subprocess.CompletedProcess:
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env.update(env_extra)
    return subprocess.run([sys.executable, "chip_smoke.py"], cwd=cwd,
                          env=env, capture_output=True, text=True,
                          timeout=300)


@pytest.mark.parametrize("where", ["checkout", "alone"])
def test_script_fails_without_tpu_or_repo(where, tmp_path):
    """Under JAX_PLATFORMS=cpu (no accelerator), and copied into a
    directory with nothing else of the repository, the script exits
    non-zero and prints no result line."""
    cwd = ROOT
    env = {"JAX_PLATFORMS": "cpu",
           "JAX_COMPILATION_CACHE_DIR": str(tmp_path / "cache")}
    if where == "alone":
        cwd = tmp_path / "alone"
        cwd.mkdir()
        shutil.copy(SCRIPT, cwd)
    out = _run_script(cwd, env)
    assert out.returncode != 0
    assert '"ok": true' not in out.stdout
