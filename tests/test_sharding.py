"""Sharding rules + small-mesh distributed execution (8 fake CPU devices).

The multi-device tests run in a subprocess so xla_force_host_platform_device_count
doesn't leak into the single-device test session.
"""

import pytest

from repro.sharding.rules import (
    DEFAULT_RULES,
    FSDP_RULES,
    AxisRules,
    ParamSpec,
)

from conftest import run_forced_devices_subprocess


def test_rules_lookup_and_override():
    assert DEFAULT_RULES.lookup("heads") == "model"
    assert DEFAULT_RULES.lookup("batch") == ("pod", "data")
    assert DEFAULT_RULES.lookup(None) is None
    assert FSDP_RULES.lookup("embed") == "data"
    r = DEFAULT_RULES.override(heads=None)
    assert r.lookup("heads") is None
    assert DEFAULT_RULES.lookup("heads") == "model"   # original untouched


def test_mesh_axes_deduplicates_repeated_axes():
    spec = DEFAULT_RULES.mesh_axes(("heads", "mlp"))   # both -> "model"
    assert spec[0] == "model" and spec[1] is None


@pytest.mark.slow
def test_train_step_runs_on_2x4_mesh():
    """Real sharded execution: smoke config, 2x4 mesh, loss finite, params
    actually sharded over the model axis."""
    res = run_forced_devices_subprocess("""
        import json
        import jax, jax.numpy as jnp, numpy as np
        from repro.configs import get_smoke_config
        from repro.launch.mesh import make_test_mesh
        from repro.launch.specs import build_cell
        from repro.models.layers import init_from_specs
        from repro.sharding.rules import DEFAULT_RULES

        mesh = make_test_mesh(data=2, model=4)
        cfg = get_smoke_config("qwen3-4b")
        cell = build_cell(cfg, "train_4k", mesh, DEFAULT_RULES)
        # materialize real (tiny) state matching the cell's sharding
        from repro.train.optimizer import OptConfig, adamw_init
        from repro.train.train_step import TrainState, make_train_step
        from repro.models import init_params
        import repro.launch.specs as specs_mod

        # shrink the batch for speed: reuse batch specs but with real data
        rng = np.random.default_rng(0)
        B, S = 8, 64
        batch = {"tokens": jnp.asarray(rng.integers(0, cfg.vocab_size, (B, S))),
                 "labels": jnp.asarray(rng.integers(0, cfg.vocab_size, (B, S)))}
        params = init_params(cfg, jax.random.PRNGKey(0))
        opt_cfg = OptConfig()
        state = TrainState(params=params, opt=adamw_init(params, opt_cfg))
        fn = make_train_step(cfg, opt_cfg, DEFAULT_RULES)
        from repro.sharding.rules import shardings_for_tree
        from repro.train.train_step import train_state_specs
        st_sh = shardings_for_tree(train_state_specs(cfg, opt_cfg), mesh, DEFAULT_RULES)
        state = jax.device_put(state, st_sh)
        with jax.sharding.set_mesh(mesh):
            step = jax.jit(fn, in_shardings=(st_sh, None), out_shardings=(st_sh, None))
            state2, metrics = step(state, batch)
        wq = state2.params["blocks"]["attn"]["wq"]
        nshards = len({(s.index) and str(s.index) for s in wq.addressable_shards})
        print(json.dumps({
            "loss": float(metrics["loss"]),
            "finite": bool(jnp.isfinite(metrics["loss"])),
            "wq_num_distinct_shards": len({str(s.index) for s in wq.addressable_shards}),
        }))
    """)
    assert res["finite"]
    assert 0 < res["loss"] < 20
    assert res["wq_num_distinct_shards"] == 4   # heads sharded over model axis


@pytest.mark.slow
def test_dryrun_cell_on_small_mesh_has_collectives():
    """Lower+compile a smoke train cell on a 2x4 mesh and check the SPMD
    module contains gradient collectives (all-reduce/reduce-scatter)."""
    res = run_forced_devices_subprocess("""
        import json
        import jax
        from repro.configs import get_smoke_config
        from repro.launch.mesh import make_test_mesh
        from repro.launch.specs import build_cell
        from repro.launch.hlo_analysis import parse_collectives
        from repro.sharding.rules import DEFAULT_RULES

        mesh = make_test_mesh(data=2, model=4)
        cfg = get_smoke_config("qwen3-4b")
        cell = build_cell(cfg, "train_4k", mesh, DEFAULT_RULES)
        with jax.sharding.set_mesh(mesh):
            compiled = jax.jit(cell.fn, in_shardings=cell.in_shardings,
                               out_shardings=cell.out_shardings,
                               donate_argnums=cell.donate_argnums
                               ).lower(*cell.args_sds).compile()
        stats = parse_collectives(compiled.as_text(), 8)
        print(json.dumps({"counts": stats.counts, "wire": stats.wire_bytes}))
    """)
    assert any(op in res["counts"] for op in ("all-reduce", "reduce-scatter"))
    assert res["wire"] > 0


def test_sanitize_drops_nondivisible_dims():
    import os
    # pure-python path: sanitize needs only mesh.shape
    class FakeMesh:
        shape = {"data": 2, "model": 4}
    from repro.sharding.rules import _sanitize_pspec, logical_to_pspec
    from jax.sharding import PartitionSpec as P
    spec = P("model", "data")
    out = _sanitize_pspec(spec, (6, 4), FakeMesh)   # 6 % 4 != 0 -> None
    assert out[0] is None and out[1] == "data"
    out2 = _sanitize_pspec(P(("pod", "data"), None), (4, 4), FakeMesh)  # pod absent
    assert out2[0] == "data"
