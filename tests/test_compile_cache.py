"""repro.compile_cache: where the persistent compilation cache lands.

The cache is turned on only by an entry point's call, so each placement
check runs in a fresh interpreter; the test process itself never turns
the cache on.
"""

import os
import subprocess
import sys
import textwrap
from pathlib import Path

import pytest

from repro.compile_cache import ENV_VAR, compile_cache_dir

SRC = str(Path(__file__).resolve().parents[1] / "src")


def test_dir_prefers_the_environment(monkeypatch, tmp_path):
    monkeypatch.setenv(ENV_VAR, str(tmp_path / "env"))
    assert compile_cache_dir(tmp_path / "checkout") == tmp_path / "env"


def test_dir_defaults_to_the_checkout(monkeypatch, tmp_path):
    monkeypatch.delenv(ENV_VAR, raising=False)
    assert compile_cache_dir(tmp_path) == tmp_path / ".jax_cache"


def test_import_leaves_the_cache_off():
    out = subprocess.run(
        [sys.executable, "-c", textwrap.dedent("""
            import jax, repro, repro.compile_cache, repro.core, repro.stream
            print(jax.config.jax_compilation_cache_dir)
        """)],
        env={**_base_env(), "PYTHONPATH": SRC}, capture_output=True,
        text=True, timeout=300)
    assert out.returncode == 0, out.stderr[-2000:]
    assert out.stdout.strip() == "None"


def _base_env() -> dict:
    return {k: v for k, v in os.environ.items()
            if k not in (ENV_VAR, "PYTHONPATH")}


@pytest.mark.parametrize("placed_by", ["env", "checkout"])
def test_compiles_land_where_placed(placed_by, tmp_path):
    checkout = tmp_path / "checkout"
    env = {**_base_env(), "PYTHONPATH": SRC, "JAX_PLATFORMS": "cpu"}
    if placed_by == "env":
        env[ENV_VAR] = str(tmp_path / "env")
    code = textwrap.dedent(f"""
        import jax, jax.numpy as jnp
        from repro.compile_cache import enable_compile_cache
        print(enable_compile_cache({str(checkout)!r}))
        jax.block_until_ready(jax.jit(lambda x: x * 2 + 1)(jnp.ones(8)))
    """)
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=300)
    assert out.returncode == 0, out.stderr[-2000:]
    want = tmp_path / "env" if placed_by == "env" else checkout / ".jax_cache"
    assert Path(out.stdout.strip()) == want
    assert any(want.iterdir())
    other = checkout / ".jax_cache" if placed_by == "env" else tmp_path / "env"
    assert not other.exists()
