"""Ahead-of-time compiles of the decision path for a described TPU v5e.

No chip is needed: the installed TPU compiler compiles for a ``v5e:2x2``
topology that is described, not attached, and refuses what the chip's
compiler would refuse (unaligned blocks, primitives Mosaic cannot lower).
Shapes are the deployment's: |L| = 100,096 live candidates (256 tenants x
391, a multiple of four shards) and N = 256 tenants.

The topology is described inside a module fixture, never while a module is
imported: only one process may hold the TPU library, and every test worker
imports this file.  The persistent compilation cache is off around the
compiles, since an entry compiled for an absent chip cannot be read back.
"""

import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, SingleDeviceSharding
from jax.sharding import PartitionSpec as P

N_MODELS = 100_096
N_TENANTS = 256
K_OBS = 64


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")   # else libtpu logs to /tmp
        try:
            desc = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        was = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        compilation_cache.reset_cache()
        try:
            yield desc
        finally:
            jax.config.update("jax_enable_compilation_cache", was)
            compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _scoring_args(sharding_of):
    """(mu, sd, best, member, cost, selected) shapes, each placed by
    ``sharding_of(name)``."""
    n, N = N_MODELS, N_TENANTS
    return (_sds((n,), jnp.float32, sharding_of("models")),
            _sds((n,), jnp.float32, sharding_of("models")),
            _sds((N,), jnp.float32, sharding_of("tenants")),
            _sds((N, n), jnp.bool_, sharding_of("member")),
            _sds((n,), jnp.float32, sharding_of("models")),
            _sds((n,), jnp.bool_, sharding_of("models")))


@pytest.mark.parametrize("kernel", ["eirate", "eirate_topk", "eirate_classes"])
def test_eirate_kernels_compile_for_v5e(one_chip, kernel):
    from repro.kernels import ei_score
    args = _scoring_args(lambda _: one_chip)
    if kernel == "eirate_classes":
        args = args[:4] + (_sds((3, N_MODELS), jnp.float32, one_chip),
                           args[5])
    kw = {"k": 4} if kernel == "eirate_topk" else {}
    fn = getattr(ei_score, f"{kernel}_pallas")
    compiled = jax.jit(lambda *a: fn(*a, interpret=False, **kw)) \
        .lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_gp_readout_kernel_compiles_for_v5e(one_chip):
    from repro.kernels.gp_readout import gp_readout_pallas
    n = N_MODELS
    compiled = jax.jit(
        lambda *a: gp_readout_pallas(*a, interpret=False, emit_sd=True)
    ).lower(_sds((K_OBS, n), jnp.float32, one_chip),
            _sds((K_OBS,), jnp.float32, one_chip),
            _sds((n,), jnp.float32, one_chip),
            _sds((n,), jnp.float32, one_chip)).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_fused_decision_compiles_for_v5e(one_chip):
    from repro.core.ei import choose_next_fused
    compiled = choose_next_fused.lower(
        *_scoring_args(lambda _: one_chip)).compile()
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes < 16e9
    assert "tpu_custom_call" not in compiled.as_text()   # the XLA path


@pytest.mark.parametrize("kernel", ["xla", "pallas_topk"])
def test_sharded_decision_compiles_on_v5e_2x2_mesh(topo, kernel,
                                                   monkeypatch):
    from repro.kernels import ops
    from repro.shardgp.score import P_MEMBER, P_MODELS, P_TENANTS, _decide
    # ops picks interpret mode from the backend, which is the CPU here:
    # steer it to the chip's choice so the kernel itself is compiled
    monkeypatch.setattr(ops, "_interpret_default", lambda: False)
    mesh = Mesh(np.asarray(topo.devices).reshape(-1), ("shard",))
    specs = {"models": P_MODELS, "tenants": P_TENANTS, "member": P_MEMBER}
    args = _scoring_args(lambda name: NamedSharding(mesh, specs[name]))
    speed = _sds((), jnp.float32, NamedSharding(mesh, P()))
    compiled = _decide.lower(*args, speed, mesh=mesh, kernel=kernel,
                             k=4).compile()
    text = compiled.as_text()
    assert mesh.devices.size == 4
    # the candidates' all_gather: the TPU compiler may rewrite it as an
    # all-reduce of dynamic-update-slices
    assert re.search(r"all-(gather|reduce)", text)
    assert ("tpu_custom_call" in text) == (kernel == "pallas_topk")


@pytest.mark.parametrize("m", [2_000, 15_625])
def test_W_keeps_a_row_major_layout_at_every_row_capacity(one_chip, m):
    """The TPU's default layout of each (rows, m) buffer W takes is
    row-major, as the fold's row loop reads it, so no fold or readout
    copies W (at 2,048 rows the TPU would lay W out column-major)."""
    from repro.core import gp

    def layout(rows):
        shape = _sds((rows, m), jnp.float32, one_chip)
        compiled = jax.jit(lambda W: W).lower(shape).compile()
        return compiled.input_formats[0][0].layout.major_to_minor

    assert layout(2_048) == (1, 0)
    for rows in gp.row_capacities(m):
        assert layout(rows) == (0, 1), rows
    rows = gp.row_capacities(m)[1]
    vec, i32 = _sds((m,), jnp.float32, one_chip), jnp.int32
    fold = gp._append_step.lower(
        _sds((rows, m), jnp.float32, one_chip), vec, vec, vec,
        _sds((), i32, one_chip), _sds((), jnp.float32, one_chip),
        _sds((), jnp.float32, one_chip), _sds((), i32, one_chip),
        _sds((), jnp.float32, one_chip)).compile()
    assert fold.memory_analysis().temp_size_in_bytes < rows * m * 4
