"""Capacity observability plane (DESIGN.md §15).

Three contracts:

* **analytic accounting** — ``resource_stats``/``capacity_stats``/
  ``occupancy`` report exactly the bytes/slots the closed-form formulas
  give ((2n²+3n)·itemsize alloc, (k·n+k)·itemsize active, 2·cap·4 readout)
  across the full tenant lifecycle (add → observe → retire → compact), and
  the accountant's projection is the least-squares slope at horizon.
* **observation-only + replay-stable** — a run with the accountant (and
  the memory watchdog) attached makes byte-identical decisions to a bare
  twin, and a crash-recovered run re-emits the identical capacity-sample
  suffix (the cursor rides in the engine snapshot; samples do not).
* **regression plane** — ``benchmarks/regress.py`` flags a synthetic 2x
  regression, stays quiet inside the noise floor, and *refuses* (skips)
  cross-environment / cross-schema / legacy comparisons instead of
  averaging apples with oranges.
"""

import dataclasses
import sys
from pathlib import Path

import numpy as np
import pytest

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

from conftest import random_psd
from repro.core.control_plane import ControlPlane
from repro.core.fleet import Fleet
from repro.core.gp import IncrementalGP
from repro.devplane import DevPlaneEngine, two_class_registry
from repro.obs import (
    CapacityAccountant,
    HealthMonitor,
    MetricsExporter,
    MetricsRegistry,
)
from repro.stream import (
    EventLog,
    FaultInjector,
    SimulatedCrash,
    StreamEngine,
    device_churn_trace,
    poisson_churn_trace,
    recover,
)
from test_eventlog import assert_replay_matches, run_reference


# ---- analytic byte accounting ------------------------------------------------

def _analytic(m: int, k: int, item: int) -> tuple[int, int]:
    """(alloc_bytes, active_bytes) for one block: W + K (m,m) each plus
    alpha/diag_acc/mu0 (m,) each; active = k Cholesky rows of W + k alpha."""
    return (2 * m * m + 3 * m) * item, (k * m + k) * item


def test_incremental_gp_resource_stats_analytic(rng):
    m = 7
    gp = IncrementalGP(random_psd(rng, m, 0.04), np.zeros(m))
    item = gp.K.dtype.itemsize
    for k in range(4):
        stats = gp.resource_stats()
        alloc, active = _analytic(m, k, item)
        assert stats["models"] == m and stats["obs"] == k
        assert stats["alloc_bytes"] == alloc
        assert stats["active_bytes"] == active
        assert stats["dtype_bytes"] == item
        if k < 4:
            gp.observe(k, float(rng.uniform()))


def test_block_gp_accounting_across_tenant_lifecycle(rng):
    """capacity_stats stays analytically exact through add_tenant /
    record_observation / retire_tenant / compact, keyed by tenant slot."""
    cp = ControlPlane(np.random.default_rng(0), model_capacity=64,
                      tenant_capacity=8, num_shards=2)
    sizes = {0: 3, 1: 5, 2: 4}
    obs_per = {0: 2, 1: 0, 2: 3}
    handles = {}
    for tid, m in sizes.items():
        h = cp.add_tenant(random_psd(rng, m, 0.04), np.zeros(m), np.ones(m))
        handles[h.tenant_id] = h
        for j in range(obs_per[tid]):
            g = int(h.models[j])
            cp.record_start(g)
            cp.record_observation(g, float(rng.uniform(0.2, 0.8)))

    def check(live: dict):
        stats = cp.capacity_stats()
        gp, layout = stats["gp"], stats["layout"]
        assert set(gp["tenants"]) == set(live)
        for tid, b in gp["tenants"].items():
            m, k, item = live[tid], obs_per[tid], b["dtype_bytes"]
            alloc, active = _analytic(m, k, item)
            assert (b["models"], b["obs"]) == (m, k)
            assert b["alloc_bytes"] == alloc and b["active_bytes"] == active
        assert gp["num_blocks"] == len(live)
        assert gp["obs_total"] == sum(obs_per[t] for t in live)
        assert gp["alloc_bytes"] == sum(
            b["alloc_bytes"] for b in gp["tenants"].values())
        assert gp["active_bytes"] == sum(
            b["active_bytes"] for b in gp["tenants"].values())
        assert gp["readout_bytes"] == 2 * gp["capacity"] * 4
        # layout occupancy: slot counts are exact, imbalance = max/mean
        live_slots = sum(live.values())
        assert layout["slots_live"] == live_slots
        assert sum(layout["per_shard"]) == live_slots
        assert layout["slots_total"] == \
            layout["slots_live"] + layout["slots_free"]
        counts = layout["per_shard"]
        if live_slots:
            assert layout["imbalance"] == pytest.approx(
                max(counts) / (live_slots / len(counts)))

    check(dict(sizes))
    cp.retire_tenant(1)
    check({0: 3, 2: 4})
    cp.compact()
    check({0: 3, 2: 4})


def test_accountant_projection_is_least_squares_slope():
    """Byte growth of 10 B/sim-s projected 60 s ahead => +600 B; the tick
    cursor samples once per window and round-trips through state_dict."""

    class _Shim:
        def __init__(self):
            self.bytes = 100.0
            self.fleet = type("F", (), {"slices": []})()
            self.health = None
            self.cp = self

        def capacity_stats(self):
            return {"gp": {"num_blocks": 1, "capacity": 8, "obs_total": 0,
                           "alloc_bytes": self.bytes, "active_bytes": 0,
                           "readout_bytes": 0, "tenants": {}},
                    "layout": None}

        def _capacity_extra(self):
            return {"scoring_passes": 5}

    shim = _Shim()
    reg = MetricsRegistry()
    acc = CapacityAccountant(reg, window=10.0, horizon=60.0)
    r0 = acc.sample(0.0, 0, shim)
    assert r0["gp_bytes_slope"] == 0.0
    assert r0["gp_bytes_projected"] == 100
    shim.bytes = 200.0
    acc.tick(10.0, 1, shim)
    r1 = acc.samples[-1]
    assert r1["gp_bytes_slope"] == pytest.approx(10.0)
    assert r1["gp_bytes_projected"] == 800     # 200 + 10 * 60
    assert r1["scoring_passes"] == 5           # _capacity_extra flows through
    # gauges published under capacity.*
    snap = reg.snapshot()["gauges"]
    assert snap["capacity.gp_bytes"]["value"] == 200
    assert snap["capacity.gp_bytes_projected"]["value"] == 800
    assert snap["capacity.scoring_passes"]["value"] == 5
    # tick is once-per-window...
    acc.tick(12.0, 2, shim)
    assert len(acc.samples) == 2
    # ...and the cursor + projection history survive a snapshot round-trip
    acc2 = CapacityAccountant(MetricsRegistry(), window=10.0, horizon=60.0)
    acc2.load_state(acc.state_dict())
    assert acc2.samples == []                  # suffix-only re-emission
    acc2.tick(15.0, 3, shim)
    assert acc2.samples == []                  # window 1 already emitted
    shim.bytes = 300.0
    acc2.tick(20.0, 4, shim)
    assert acc2.samples[-1]["gp_bytes_slope"] == pytest.approx(10.0)


def test_memory_runaway_watchdog_arms_and_rearms():
    h = HealthMonitor(memory_budget_bytes=1000.0)
    # projected over budget but measured under: warn, then disarm
    h.on_capacity(0.0, 1, bytes_now=500.0, projected_bytes=1200.0)
    h.on_capacity(1.0, 2, bytes_now=600.0, projected_bytes=1300.0)
    assert [(a.kind, a.severity) for a in h.alerts] == \
        [("memory_runaway", "warn")]
    # drop below 80% of budget re-arms without alerting
    h.on_capacity(2.0, 3, bytes_now=600.0, projected_bytes=700.0)
    assert len(h.alerts) == 1
    # measured over budget: page
    h.on_capacity(3.0, 4, bytes_now=1500.0, projected_bytes=1500.0)
    assert [(a.kind, a.severity) for a in h.alerts] == \
        [("memory_runaway", "warn"), ("memory_runaway", "page")]
    assert h.alerts[-1].detail["budget_bytes"] == 1000.0
    # no budget => no-op
    h2 = HealthMonitor()
    h2.on_capacity(0.0, 1, bytes_now=1e9, projected_bytes=1e9)
    assert h2.alerts == []


# ---- observation-only + replay-stable ----------------------------------------

def _churny_trace():
    return poisson_churn_trace(num_sessions=10, arrival_rate=1.2, seed=6,
                               m_min=2, m_max=8, session_scale=12.0,
                               num_failure_slices=1)


def _factory(bag):
    def make(**kw):
        reg = MetricsRegistry()
        planes = dict(
            metrics=reg,
            exporter=MetricsExporter(reg, window=5.0),
            health=HealthMonitor(slo={"device_utilization": 1.5},
                                 window=5.0, burn_windows=2, stall_k=4,
                                 queue_limit=2,
                                 memory_budget_bytes=4096.0),
            accounting=CapacityAccountant(reg, window=5.0))
        bag.append(planes)
        return StreamEngine(Fleet.partition_pod(16 * 3, 3), "mdmt",
                            seed=0, max_live_models=30, num_shards=2,
                            **planes, **kw)
    return make


def test_accounting_is_observation_only_and_tracks_final_state():
    trace = _churny_trace()
    bag = []
    eng = _factory(bag)()
    res = eng.run(trace)
    twin = StreamEngine(Fleet.partition_pod(16 * 3, 3), "mdmt", seed=0,
                        max_live_models=30, num_shards=2).run(trace)
    assert [dataclasses.astuple(t) for t in res.trials] == \
        [dataclasses.astuple(t) for t in twin.trials]

    acc = bag[0]["accounting"]
    assert len(acc.samples) >= 2
    # the end-of-run sample equals a fresh introspection of the final plane
    final = acc.samples[-1]
    stats = eng.cp.capacity_stats()
    assert final["gp_alloc_bytes"] == stats["gp"]["alloc_bytes"]
    assert final["gp_obs"] == stats["gp"]["obs_total"]
    assert final["slots_live"] == stats["layout"]["slots_live"]
    assert final["shard_slots"] == list(stats["layout"]["per_shard"])
    # devices gauge counts the live fleet by class
    assert sum(final["devices"].values()) == \
        sum(1 for s in eng.fleet.slices if not s.retired)
    # the engine auto-wired the exporter to the health plane: records and
    # the scrape surface both carry per-kind alert counts
    assert eng.exporter.health is eng.health
    assert all("alerts" in r for r in eng.exporter.records)
    if eng.health.alerts:
        kind = eng.health.alerts[0].kind
        assert f'health_alerts_total{{kind="{kind}"}}' \
            in eng.exporter.prometheus()


def test_capacity_samples_replay_stable_across_crash(tmp_path):
    """§15 replay contract: the sample cursor rides in the snapshot, the
    samples themselves do not — a recovered run re-emits exactly the
    uninterrupted run's sample suffix, record-for-record."""
    trace = _churny_trace()
    ref_bag = []
    ref_eng, ref_res = run_reference(_factory(ref_bag), trace)
    ref_samples = ref_bag[0]["accounting"].samples
    assert len(ref_samples) >= 3, "trace too short to exercise replay"
    n = ref_eng.event_index

    for crash_at in (2, n // 2, n - 1):
        bag = []
        make = _factory(bag)
        workdir = tmp_path / f"c{crash_at}"
        eng = make(log=EventLog(workdir / "log"),
                   snapshot_root=str(workdir / "snap"), snapshot_every=5,
                   fault=FaultInjector(crash_at, "before"))
        with pytest.raises(SimulatedCrash):
            eng.run(trace)
        eng.log.close()
        durable = EventLog.load(workdir / "log")
        eng2, resumed_from = recover(make, str(workdir / "snap"), durable)
        res2 = eng2.resume()
        prefix = [r for r in durable.processed if r[0] <= resumed_from]
        assert_replay_matches(ref_eng, ref_res, eng2, res2, prefix,
                              context=f"capacity_before_{crash_at}")
        # capacity samples are pure host introspection of replayed state:
        # the resumed suffix is byte-identical, not merely same-schedule
        assert bag[-1]["accounting"].samples == \
            [r for r in ref_samples if r["event_index"] > resumed_from]


def test_exporter_windows_and_capacity_under_device_churn():
    """Join/leave/preempt mid-window: export emission stays a deterministic
    once-per-window function of the event stream, and the capacity plane
    sees the fleet composition change."""
    trace = device_churn_trace(
        num_sessions=40, arrival_rate=1.0, seed=1, initial_slices=4,
        join_classes=(("fast", 16, 2.0), ("slow", 16, 1.0)),
        join_rate=0.05, leave_rate=0.03, preempt_rate=0.05,
        m_min=2, m_max=10, session_scale=25.0)
    reg_factory = two_class_registry

    def run_once():
        reg = MetricsRegistry()
        dreg = reg_factory(2.0, overhead=0.5)
        planes = dict(metrics=reg,
                      exporter=MetricsExporter(reg, window=5.0),
                      health=HealthMonitor(queue_limit=4),
                      accounting=CapacityAccountant(reg, window=5.0))
        eng = DevPlaneEngine(dreg.build_fleet([("slow", 2), ("fast", 2)]),
                             "mdmt", seed=0, registry=dreg,
                             launch_order="fastest", max_live_models=80,
                             **planes)
        res = eng.run(trace)
        return eng, res, planes

    eng, res, planes = run_once()
    recs = planes["exporter"].records
    assert len(recs) >= 3
    body, final = recs[:-1], recs[-1]
    assert final.get("final") is True and not body[-1].get("final")
    # one record per crossed window, strictly increasing, window = t//w
    windows = [r["window"] for r in body]
    assert windows == sorted(set(windows))
    assert all(r["window"] == int(r["t"] // 5.0) for r in body)
    assert all("alerts" in r for r in recs)     # health auto-wired

    # the device-churn trace must actually change fleet composition, and
    # the accounting samples must see it
    samples = planes["accounting"].samples
    compositions = {tuple(sorted(s["devices"].items())) for s in samples}
    assert len(compositions) >= 2
    # devplane _capacity_extra rides along in every sample
    assert all({"autoscale_joins", "autoscale_leaves",
                "scoring_passes"} <= set(s) for s in samples)

    # emission schedule is a pure function of the event stream
    eng2, res2, planes2 = run_once()
    keys = [(r["window"], r["t"], r["event_index"], bool(r.get("final")))
            for r in recs]
    keys2 = [(r["window"], r["t"], r["event_index"], bool(r.get("final")))
             for r in planes2["exporter"].records]
    assert keys == keys2
    assert planes2["accounting"].samples == samples


def test_prometheus_renders_alert_counts_and_capacity_gauges():
    reg = MetricsRegistry()
    reg.gauge("capacity.gp_bytes").set(1234)
    reg.gauge("capacity.shard_slots", {"shard": "0"}).set(7)
    h = HealthMonitor(memory_budget_bytes=100.0)
    h.on_capacity(0.0, 1, bytes_now=200.0, projected_bytes=200.0)
    exp = MetricsExporter(reg, window=5.0, health=h)
    text = exp.prometheus()
    assert "capacity_gp_bytes 1234" in text
    assert 'capacity_shard_slots{shard="0"} 7' in text
    assert "# TYPE health_alerts_total counter" in text
    assert 'health_alerts_total{kind="memory_runaway"} 1' in text
    # alert counts also fold into every windowed record
    exp.tick(0.1, 1)
    assert exp.records[0]["alerts"] == {"memory_runaway": 1}
    # without a health plane the series is absent entirely
    bare = MetricsExporter(reg, window=5.0)
    bare.tick(0.1, 1)
    assert "health_alerts_total" not in bare.prometheus()
    assert "alerts" not in bare.records[0]


# ---- device failures are loud (obs/profile.py, benchmarks/common.py) ---------

def test_capture_without_logdir_is_a_noop():
    from repro.obs import capture
    with capture(None) as running:
        assert running is False


def test_capture_raises_when_the_trace_cannot_start(monkeypatch, tmp_path):
    import jax.profiler
    from repro.obs import capture

    def refuse(logdir):
        raise RuntimeError("profiler unavailable")
    monkeypatch.setattr(jax.profiler, "start_trace", refuse)
    with pytest.raises(RuntimeError, match="profiler unavailable"):
        with capture(tmp_path):
            pass


def test_environment_stamp_names_the_device():
    import jax
    from benchmarks.common import environment
    env = environment()
    assert env["device_kind"] == jax.devices()[0].device_kind
    assert env["device_count"] == len(jax.devices())


def test_environment_stamp_raises_without_a_device(monkeypatch):
    import jax
    from benchmarks.common import environment

    def no_backend(*a, **k):
        raise RuntimeError("Unable to initialize backend 'tpu'")
    monkeypatch.setattr(jax, "devices", no_backend)
    with pytest.raises(RuntimeError, match="Unable to initialize"):
        environment()


# ---- perf-regression plane (benchmarks/regress.py) ---------------------------

from benchmarks import regress  # noqa: E402  (needs repo root on sys.path)
from benchmarks.common import BENCH_SCHEMA_VERSION  # noqa: E402

ENV = {"platform": "linux", "machine": "x86_64", "device_kind": "cpu",
       "device_count": 8, "fast": False}


def _payload(rows: dict, env=ENV, suite="demo", schema=BENCH_SCHEMA_VERSION):
    return {"schema_version": schema, "suite": suite, "git_sha": "deadbeef",
            "environment": dict(env) if env is not None else None,
            "rows": {k: {"us_per_call": float(v)} for k, v in rows.items()}}


def test_regress_flags_synthetic_2x_regression():
    verdict = regress.compare_suites(
        _payload({"hot": 10_000.0, "cold": 400.0}),
        _payload({"hot": 20_000.0, "cold": 400.0}),
        threshold=1.5, min_us=1000.0, allow_legacy=False)
    assert verdict["status"] == "regression"
    by_name = {r["name"]: r for r in verdict["rows"]}
    assert by_name["hot"]["status"] == "regression"
    assert by_name["hot"]["ratio"] == pytest.approx(2.0)
    assert by_name["cold"]["status"] == "ok"


def test_regress_noise_floor_needs_ratio_and_absolute_delta():
    # 3x ratio but only 6 µs absolute: scheduler jitter, not a regression
    v = regress.compare_suites(_payload({"tiny": 3.0}),
                               _payload({"tiny": 9.0}),
                               threshold=1.5, min_us=1000.0,
                               allow_legacy=False)
    assert v["status"] == "ok"
    # 2 ms absolute but ratio 1.2: inside the ratio threshold
    v = regress.compare_suites(_payload({"slow": 10_000.0}),
                               _payload({"slow": 12_000.0}),
                               threshold=1.5, min_us=1000.0,
                               allow_legacy=False)
    assert v["status"] == "ok"


def test_regress_refuses_cross_environment_and_cross_schema():
    other_env = dict(ENV, device_count=1)
    v = regress.compare_suites(_payload({"a": 1.0}),
                               _payload({"a": 9_999.0}, env=other_env),
                               threshold=1.5, min_us=1.0, allow_legacy=False)
    assert v["status"] == "skipped" and "device_count" in v["reason"]
    v = regress.compare_suites(_payload({"a": 1.0}, schema=0),
                               _payload({"a": 9_999.0}),
                               threshold=1.5, min_us=1.0, allow_legacy=False)
    assert v["status"] == "skipped" and "schema_version" in v["reason"]


def test_regress_legacy_baseline_skipped_unless_allowed():
    base = _payload({"a": 100.0}, env=None)
    fresh = _payload({"a": 100.0})
    v = regress.compare_suites(base, fresh, threshold=1.5, min_us=1.0,
                               allow_legacy=False)
    assert v["status"] == "skipped" and "legacy" in v["reason"]
    v = regress.compare_suites(base, fresh, threshold=1.5, min_us=1.0,
                               allow_legacy=True)
    assert v["status"] == "ok" and v["legacy_baseline"] is True


def test_regress_tracks_row_set_drift():
    v = regress.compare_suites(_payload({"gone": 1.0, "kept": 1.0}),
                               _payload({"kept": 1.0, "born": 1.0}),
                               threshold=1.5, min_us=1.0, allow_legacy=False)
    status = {r["name"]: r["status"] for r in v["rows"]}
    assert status == {"gone": "missing_in_fresh", "kept": "ok",
                      "born": "new_in_fresh"}
    assert v["status"] == "ok"        # drift alone is not a regression


def test_regress_cli_check_report_and_history(tmp_path):
    import json
    base_dir, fresh_dir = tmp_path / "base", tmp_path / "fresh"
    base_dir.mkdir(), fresh_dir.mkdir()
    (base_dir / "BENCH_demo.json").write_text(
        json.dumps(_payload({"hot": 10_000.0})))
    (fresh_dir / "BENCH_demo.json").write_text(
        json.dumps(_payload({"hot": 30_000.0})))
    report = tmp_path / "regress_report.json"
    history = tmp_path / "BENCH_history.jsonl"
    rc = regress.main(["--check", "--baseline-dir", str(base_dir),
                       "--fresh-dir", str(fresh_dir),
                       "--report", str(report), "--history", str(history)])
    assert rc == 1
    rep = json.loads(report.read_text())
    assert rep["suites"][0]["status"] == "regression"
    hist = [json.loads(line) for line in history.read_text().splitlines()]
    assert hist[0]["suite"] == "demo"
    assert hist[0]["rows"] == {"hot": 30_000.0}

    # identical payloads pass --check
    (fresh_dir / "BENCH_demo.json").write_text(
        json.dumps(_payload({"hot": 10_000.0})))
    assert regress.main(["--check", "--baseline-dir", str(base_dir),
                         "--fresh-dir", str(fresh_dir),
                         "--report", str(report)]) == 0
    # a fresh suite with no baseline passes by default, fails --strict
    (fresh_dir / "BENCH_new.json").write_text(
        json.dumps(_payload({"x": 1.0}, suite="new")))
    common = ["--check", "--baseline-dir", str(base_dir),
              "--fresh-dir", str(fresh_dir), "--report", str(report)]
    assert regress.main(common) == 0
    assert regress.main(common + ["--strict"]) == 1
    # no payloads at all is a usage error, not a silent pass
    empty = tmp_path / "empty"
    empty.mkdir()
    assert regress.main(["--check", "--fresh-dir", str(empty),
                         "--report", str(report)]) == 2
