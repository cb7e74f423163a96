import json
import os
import subprocess
import sys
import textwrap
from pathlib import Path

import numpy as np
import pytest

SRC = str(Path(__file__).resolve().parents[1] / "src")


@pytest.fixture
def rng():
    return np.random.default_rng(0)


def random_psd(rng, n: int, scale: float = 1.0) -> np.ndarray:
    A = rng.standard_normal((n, n))
    K = A @ A.T / n + 0.25 * np.eye(n)
    return scale * K


def run_forced_devices_subprocess(code: str, devices: int = 8) -> dict:
    """Run ``code`` in a subprocess with ``devices`` faked CPU devices and
    return the JSON printed on its last stdout line.  Multi-device tests
    must run out of process: xla_force_host_platform_device_count only
    takes effect before jax initializes, and must not leak into the
    single-device test session.  Shared by test_sharding and test_shardgp —
    the env recipe here (JAX_PLATFORMS=cpu pins past minutes of libtpu
    probing on images that bundle it) must stay in one place."""
    prog = textwrap.dedent(code)
    out = subprocess.run(
        [sys.executable, "-c", prog],
        env={"PYTHONPATH": SRC, "PATH": "/usr/bin:/bin",
             "HOME": os.environ.get("HOME", ""),
             "JAX_PLATFORMS": "cpu",
             "XLA_FLAGS":
                 f"--xla_force_host_platform_device_count={devices}"},
        capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-4000:]
    return json.loads(out.stdout.strip().splitlines()[-1])


@pytest.fixture
def forced_devices():
    """The forced-device subprocess recipe as a fixture — test modules that
    only need to *run* code on a faked multi-device host take this instead
    of importing the helper, keeping the env recipe in one place."""
    return run_forced_devices_subprocess
