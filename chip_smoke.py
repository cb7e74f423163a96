#!/usr/bin/env python3
"""Chip smoke test: the served GP-EI decision path on a TPU, end to end.

    python chip_smoke.py             # one chip: device, served, scorers, batched
    python chip_smoke.py --chips 4   # four chips: device, sharded4 only

Phases, in order.  Each prints one line of findings; any failure exits
non-zero before the result line is printed.

  device    ``jax.devices()[0]`` must be a TPU.  The smoke never falls back
            to the CPU: without an accelerator it fails here.
  served    ``StreamEngine(scorer="fused")`` over a seeded Poisson churn
            trace whose live pool holds >= 10^4 candidates, bounded by a
            horizon to a few hundred decisions; then the same trace on the
            host CPU in the same process.  The two trial sequences are
            compared record by record (``stream.eventlog.first_divergence``);
            a divergence passes only as a near-tie (relative EIrate gap <=
            ``NEAR_TIE`` in the chip's own scores).
  scorers   one decision at |L| = 100,096 candidates and N = 256 tenants
            (the ``shard_scale`` shape) by each scorer implementation: fused
            (XLA), ops (the Pallas ``eirate`` kernel) and sharded (one shard,
            the Pallas top-k kernel).  The picks must agree, and both kernels
            must be compiled (a ``tpu_custom_call`` in the lowered program).
  batched   ``simulate_batch`` at the paper's Fig-5 shape (50 tenants x 50
            candidates, four episodes); the deterministic mdmt episode must
            equal ``simulate``'s trial sequence.
  sharded4  (``--chips 4``) a decision sequence at |L| = 100,096 by
            ``scorer="sharded"`` on a 4-shard mesh against ``scorer="fused"``
            on one chip, over the same index layout; the sequences must be
            equal.

The last line of stdout is ``{"ok": true, "device": {"platform": ...,
"kind": ..., "count": ...}}``.  Every phase is an importable function with
its sizes as arguments; the tests run them at toy sizes on the CPU with
interpret-mode kernels.
"""

from __future__ import annotations

import argparse
import dataclasses
import functools
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent
sys.path.insert(0, str(ROOT / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.compile_cache import enable_compile_cache  # noqa: E402
from repro.core import (ControlPlane, EpisodeSpec, simulate,  # noqa: E402
                        simulate_batch, synthetic_matern_problem)
from repro.core.ei import choose_next_fused  # noqa: E402
from repro.core.fleet import Fleet  # noqa: E402
from repro.core.tenancy import _matern_block_chol  # noqa: E402
from repro.kernels import ops  # noqa: E402
from repro.obs import ForensicsRecorder  # noqa: E402
from repro.shardgp import ShardedScorer  # noqa: E402
from repro.shardgp.score import _decide  # noqa: E402
from repro.stream import StreamEngine, poisson_churn_trace  # noqa: E402
from repro.stream.eventlog import first_divergence  # noqa: E402

#: largest relative EIrate gap a chip-vs-CPU divergence may show
NEAR_TIE = 1e-6

#: the served trace: Poisson arrivals, Pareto sessions, Zipf candidate sets
#: of 10..100 models; with 20 slices of unit-cost trials the live pool
#: passes 10^4 candidates by t = 50 after ~270 policy decisions
SERVED_TRACE = dict(num_sessions=500, arrival_rate=8.0, session_scale=30.0,
                    m_min=10, m_max=100)


class SmokeFailure(RuntimeError):
    """A phase ran but its result is wrong."""


class CompileLog:
    """Backend compiles and their seconds, from JAX's monitoring events."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        self.count = 0
        self.seconds = 0.0

    def _on_duration(self, event: str, duration: float, **_) -> None:
        if event == self.EVENT:
            self.count += 1
            self.seconds += duration

    def __enter__(self):
        jax.monitoring.register_event_duration_secs_listener(self._on_duration)
        return self

    def __exit__(self, *exc):
        jax.monitoring.unregister_event_duration_listener(self._on_duration)


def _lowers_to_kernel(fn, *args, **static) -> bool:
    """True when ``fn`` lowers to a compiled Pallas TPU kernel; an
    interpreted kernel lowers to plain XLA loops instead."""
    return "tpu_custom_call" in fn.lower(*args, **static).as_text()


def _mode(compiled: bool) -> str:
    return "compiled" if compiled else "interpreted"


# ---- phases -----------------------------------------------------------------

def phase_device() -> dict:
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise SmokeFailure(f"no TPU: JAX's first device is "
                           f"{devs[0].platform!r} ({devs[0].device_kind})")
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def _serve(trace, slices: int, horizon: float, seed: int):
    forensics = ForensicsRecorder()
    eng = StreamEngine(Fleet.partition_pod(16 * slices, slices), "mdmt",
                       seed=seed, scorer="fused", forensics=forensics)
    t0 = time.perf_counter()
    res = eng.run(trace, horizon=horizon)
    return res, eng.cp.num_models, time.perf_counter() - t0, forensics.records


def _first_split(recs: list[dict], twin: list[dict]) -> dict | None:
    """The first decision whose winner differs between the chip's and the
    CPU's forensics streams, with both winners' EIrate and the chip's own
    relative gap between its winner and the CPU's pick (None when the CPU's
    pick is not in the chip's top-k)."""
    for i, (a, b) in enumerate(zip(recs, twin)):
        wa, wb = a["winner"] or {}, b["winner"] or {}
        if wa.get("model") == wb.get("model"):
            continue
        chip_b = next((c["eirate"] for c in a["topk"]
                       if c["model"] == wb.get("model")), None)
        gap = (None if chip_b is None or not wa.get("eirate")
               else (wa["eirate"] - chip_b) / abs(wa["eirate"]))
        return {"decision": i, "chip": [wa.get("model"), wa.get("eirate")],
                "cpu": [wb.get("model"), wb.get("eirate")], "gap": gap}
    return None


def phase_served(*, slices: int = 20, horizon: float = 60.0,
                 min_live: int = 10_000, seed: int = 0, **trace_kw) -> dict:
    trace = poisson_churn_trace(seed=seed, **{**SERVED_TRACE, **trace_kw})
    with CompileLog() as log:
        res, live, wall, recs = _serve(trace, slices, horizon, seed)
    with jax.default_device(jax.devices("cpu")[0]):
        twin, twin_live, _, twin_recs = _serve(trace, slices, horizon, seed)
    div = first_divergence([dataclasses.astuple(t) for t in res.trials],
                           [dataclasses.astuple(t) for t in twin.trials])
    split = _first_split(recs, twin_recs) if div is not None else None
    out = {"decisions": res.decisions, "launches": res.policy_launches,
           "trials": len(res.trials), "live": live,
           "wall_s": wall, "compiles": log.count,
           "compile_s": log.seconds,
           "divergence": "none" if div is None else {
               "trial": div["offset"], "decision": split}}
    if live < min_live:
        raise SmokeFailure(f"served: live pool {live} < {min_live}: {out}")
    if res.policy_launches == 0:
        raise SmokeFailure(f"served: no policy decision was made: {out}")
    if div is not None and (split is None or split["gap"] is None
                            or split["gap"] > NEAR_TIE):
        raise SmokeFailure(f"served: chip and CPU trial sequences diverge "
                           f"beyond a near-tie: {out} (twin live "
                           f"{twin_live}, first differing trials {div})")
    return out


def scoring_plane(scorer: str, tenants: int, m: int, seed: int, *,
                  num_shards: int | None = None) -> ControlPlane:
    """A dynamic plane of ``tenants`` Matérn blocks of ``m`` candidates
    with lognormal costs and three seeded observations per tenant."""
    rng = np.random.default_rng(seed)
    K, _ = _matern_block_chol(m, 0.2, 0.04)
    cp = ControlPlane(np.random.default_rng(seed), scorer=scorer,
                      model_capacity=tenants * m, tenant_capacity=tenants,
                      num_shards=num_shards)
    handles = [cp.add_tenant(K, np.zeros(m), rng.lognormal(0.0, 0.5, m))
               for _ in range(tenants)]
    for h in handles:
        for li in rng.choice(m, size=3, replace=False):
            g = int(h.models[li])
            cp.record_start(g)
            cp.record_observation(g, float(rng.normal(0.0, 0.2)))
    return cp


def phase_scorers(*, tenants: int = 256, models_per_tenant: int = 391,
                  interpret: bool = False, seed: int = 0) -> dict:
    cp = scoring_plane("fused", tenants, models_per_tenant, seed)
    mu, sd = cp.gp.posterior_sd()
    args = (mu, sd, cp._best_j, cp._membership_j, cp._cost_j,
            cp._selected_j)
    fused_pick, _ = cp.choose_mdmt()
    eirate = jax.jit(functools.partial(ops.eirate, interpret=interpret))
    scores = eirate(*args)
    ops_pick = int(jnp.argmax(scores))
    sc = ShardedScorer(1, kernel="pallas_topk")
    sc.refresh(cp.membership, cp.cost)
    sd_host = np.sqrt(cp.gp.posterior_host()[1])
    sharded_pick, sharded_score = sc.decide(np.asarray(mu), sd_host,
                                            cp._best_j, cp.selected)
    _, fused_score = choose_next_fused(*args)
    compiled = not interpret
    impl = {
        "fused": _lowers_to_kernel(choose_next_fused, *args),
        "ops": _lowers_to_kernel(eirate, *args),
        "sharded": _lowers_to_kernel(
            _decide, np.asarray(mu), sd_host.astype(np.float32),
            cp._best_j, sc._member, sc._cost, cp.selected, jnp.float32(1.0),
            mesh=sc.mesh, kernel="pallas_topk", k=sc.topk),
    }
    out = {"models": cp.num_models, "tenants": tenants,
           "picks": {"fused": fused_pick, "ops": ops_pick,
                     "sharded": sharded_pick},
           "impl": {"fused": "pallas" if impl["fused"] else "xla",
                    "ops": "pallas-" + _mode(impl["ops"]),
                    "sharded": "pallas_topk-" + _mode(impl["sharded"])},
           "score_rel_diff": {
               "ops": float(abs(scores[ops_pick] - fused_score)
                            / abs(fused_score)),
               "sharded": float(abs(sharded_score - fused_score)
                                / abs(fused_score))}}
    if len(set(out["picks"].values())) != 1:
        raise SmokeFailure(f"scorers disagree: {out}")
    if impl["fused"] or impl["ops"] != compiled \
            or impl["sharded"] != compiled:
        raise SmokeFailure(f"scorers ran the wrong implementation: {out}")
    return out


def phase_batched(*, tenants: int = 50, models_per_tenant: int = 50,
                  devices: tuple[int, ...] = (1, 4, 16), check: int = 4,
                  seed: int = 0) -> dict:
    p = synthetic_matern_problem(num_users=tenants,
                                 num_models_per_user=models_per_tenant,
                                 seed=seed)
    specs = ([EpisodeSpec("mdmt", M, seed) for M in devices]
             + [EpisodeSpec("round_robin", check, seed)])
    with CompileLog() as log:
        t0 = time.perf_counter()
        batch = simulate_batch(p, specs)
        wall = time.perf_counter() - t0
    i = devices.index(check)
    batched = [(int(m), int(u), int(d)) for m, u, d in zip(
        batch.trial_model[i], batch.trial_user[i], batch.trial_device[i])
        if m >= 0]
    t0 = time.perf_counter()
    ref = simulate(p, "mdmt", num_devices=check, seed=seed)
    ref_wall = time.perf_counter() - t0
    div = first_divergence(batched, [(t.model, t.user_hint, t.device)
                                     for t in ref.trials])
    out = {"episodes": len(specs),
           "shape": f"{tenants}x{models_per_tenant}",
           "batch_wall_s": wall, "compiles": log.count,
           "compile_s": log.seconds,
           "simulate_wall_s": ref_wall,
           "checked": f"mdmt M={check}", "trials": len(batched),
           "divergence": "none" if div is None else div}
    if div is not None:
        raise SmokeFailure(f"simulate_batch != simulate: {out}")
    return out


def phase_sharded4(*, tenants: int = 256, models_per_tenant: int = 391,
                   shards: int = 4, decisions: int = 24,
                   seed: int = 0) -> dict:
    if len(jax.devices()) < shards:
        raise SmokeFailure(f"sharded4: {shards} devices needed, "
                           f"{len(jax.devices())} visible")
    planes = {s: scoring_plane(s, tenants, models_per_tenant, seed,
                               num_shards=shards)
              for s in ("fused", "sharded")}
    z_rng = np.random.default_rng(seed + 1)
    seqs: dict[str, list] = {s: [] for s in planes}
    wall = dict.fromkeys(planes, 0.0)
    for _ in range(decisions):
        for s, cp in planes.items():
            t0 = time.perf_counter()
            seqs[s].append(cp.choose_mdmt() or ())   # () = pool exhausted
            wall[s] += time.perf_counter() - t0
        pick = seqs["fused"][-1]
        if not pick or seqs["sharded"][-1] != pick:
            break
        z = float(z_rng.normal(0.0, 0.2))
        for cp in planes.values():
            cp.record_start(pick[0])
            cp.record_observation(pick[0], z)
    div = first_divergence(seqs["fused"], seqs["sharded"])
    out = {"models": planes["sharded"].num_models, "tenants": tenants,
           "shards": planes["sharded"]._sharded.num_shards,
           "decisions": len(seqs["sharded"]),
           "wall_s": wall,
           "divergence": "none" if div is None else div}
    if div is not None or len(seqs["sharded"]) < decisions:
        raise SmokeFailure(f"sharded4: sharded != fused: {out}")
    return out


def _report(name: str, out: dict) -> None:
    print(f"{name}: {json.dumps(out, sort_keys=True)}", flush=True)


def main(argv=None) -> None:
    p = argparse.ArgumentParser(
        description=__doc__.split("\n\n")[0],
        formatter_class=argparse.RawDescriptionHelpFormatter)
    p.add_argument("--chips", type=int, choices=(1, 4), default=1,
                   help="1: every one-chip phase (default); 4: only the "
                        "4-shard sharded path and its one-chip twin")
    args = p.parse_args(argv)
    cache = enable_compile_cache(ROOT)
    device = phase_device()
    _report("device", {**device, "compile_cache": str(cache)})
    if args.chips == 4:
        _report("sharded4", phase_sharded4())
    else:
        _report("served", phase_served())
        _report("scorers", phase_scorers())
        _report("batched", phase_batched())
    print(json.dumps({"ok": True, "device": device}))


if __name__ == "__main__":
    main()
