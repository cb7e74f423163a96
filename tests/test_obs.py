"""Observability planes (DESIGN.md §13-§14): tracer determinism, metrics
registry (labels, histograms), streaming export, the health monitor's
detectors, per-decision forensics, span aggregation, the report plane, and
the two contracts that make every plane safe to leave wired into the
engines —

* **observation-only**: an instrumented run's decisions are byte-identical
  to a bare twin's (spans/exports/alerts/forensics observe the engine's
  jit programs, never change them), and processed-log records only grow
  their trace-id field when tracing is on;
* **replay-stable**: trace ids are processed-event indices, span ids count
  from 0 within each trace, export windows and alert content are pure
  functions of the sim-time event stream, so a crash-recovered run
  re-emits identical spans/windows/alerts for the replayed suffix
  (the crash-side half lives in tests/test_eventlog.py).
"""

from __future__ import annotations

import dataclasses
import json
import re
import time
from pathlib import Path

import numpy as np
import pytest

from repro.core import ControlPlane
from repro.core.fleet import Fleet
from repro.core.tenancy import _matern_block_chol
from repro.obs import (ALERT_KINDS, NULL_TRACER, ForensicsRecorder,
                       HealthMonitor, MetricsExporter, MetricsRegistry,
                       Tracer, aggregate_spans, prometheus_text,
                       write_report)
from repro.obs.metrics import Histogram
from repro.obs.report import _slo_section
from repro.obs.trace import ROOT_TRACE
from repro.stream import (EventLog, FaultInjector, SimulatedCrash,
                          StreamEngine, poisson_churn_trace, recover)
from repro.stream.workload import ChurnTrace, TenantArrive, TenantDepart


# ---- tracer -----------------------------------------------------------------

def test_span_ids_deterministic_nesting():
    def drive(tr):
        tr.begin_trace(5)
        with tr.span("a", k=1):
            with tr.span("b"):
                pass
        with tr.span("c"):
            pass

    tr = Tracer()
    drive(tr)
    recs = tr.records()
    # completion order: children close before parents
    assert [r["name"] for r in recs] == ["b", "a", "c"]
    by_name = {r["name"]: r for r in recs}
    assert by_name["a"]["span"] == 0 and by_name["a"]["parent"] is None
    assert by_name["b"]["span"] == 1 and by_name["b"]["parent"] == 0
    assert by_name["c"]["span"] == 2 and by_name["c"]["parent"] is None
    assert all(r["trace"] == 5 for r in recs)
    assert by_name["a"]["attrs"] == {"k": 1}
    # ids depend only on the code path: a second tracer driving the same
    # path emits the identical signature (this is the replay-oracle lever)
    tr2 = Tracer()
    drive(tr2)
    assert tr2.signature() == tr.signature()


def test_begin_trace_resets_span_ids():
    tr = Tracer()
    tr.begin_trace(0)
    with tr.span("x"):
        pass
    tr.begin_trace(1)
    with tr.span("x"):
        pass
    assert [(r["trace"], r["span"]) for r in tr.records()] == [(0, 0), (1, 0)]
    assert tr.signature(min_trace=1) == [(1, 0, None, "x", ())]


def test_disabled_tracer_is_inert():
    tr = Tracer(enabled=False)
    tr.begin_trace(3)
    assert tr.current_trace is None
    assert tr.span("a") is tr.span("b")    # the shared no-op manager
    with tr.span("a", big=1):
        pass
    obj = object()
    assert tr.sync(obj) is obj             # pass-through, no device sync
    assert tr.records() == [] and tr.signature() == []
    assert NULL_TRACER.enabled is False


def test_spans_survive_exceptions():
    tr = Tracer()
    tr.begin_trace(0)
    with pytest.raises(RuntimeError):
        with tr.span("outer"):
            with tr.span("inner"):
                raise RuntimeError("boom")
    assert [r["name"] for r in tr.records()] == ["inner", "outer"]
    assert tr._stack == []


def test_spans_before_begin_trace_land_in_root_trace():
    tr = Tracer()
    with tr.span("setup"):
        pass
    assert tr.records()[0]["trace"] == ROOT_TRACE


def test_to_json_roundtrip(tmp_path):
    tr = Tracer()
    tr.begin_trace(0)
    with tr.span("a", device=2):
        pass
    payload = json.loads(tr.to_json(tmp_path / "t.json").read_text())
    assert payload["spans"][0]["name"] == "a"
    assert payload["spans"][0]["attrs"] == {"device": 2}


# ---- metrics ----------------------------------------------------------------

def test_counter_and_gauge():
    reg = MetricsRegistry()
    c = reg.counter("c")
    c.inc()
    c.inc(2)
    g = reg.gauge("g")
    g.set(3.0)
    g.set(1.0)
    snap = reg.snapshot()
    assert snap["counters"]["c"] == 3
    assert snap["gauges"]["g"] == {"value": 1.0, "max": 3.0}
    assert reg.counter("c") is c           # get-or-create returns the handle


def test_histogram_percentiles_and_nonfinite():
    h = Histogram(bounds=(1.0, 2.0, 4.0, 8.0))
    for v in (0.5, 1.5, 3.0, 5.0):
        h.observe(v)
    h.observe(float("nan"))
    h.observe(float("inf"))
    h.observe(None)
    s = h.summary()
    assert s["count"] == 4 and s["dropped_non_finite"] == 3
    assert s["min"] == 0.5 and s["max"] == 5.0
    assert s["mean"] == pytest.approx(2.5)
    assert s["min"] <= s["p50"] <= s["p99"] <= s["max"]
    json.dumps(s, allow_nan=False)


def test_histogram_empty_summary_is_null_clean():
    s = Histogram().summary()
    assert s["count"] == 0 and s["p50"] is None and s["p99"] is None
    json.dumps(s, allow_nan=False)


def test_histogram_overflow_bucket_clamps_to_observed_max():
    h = Histogram(bounds=(1.0,))
    h.observe(100.0)
    assert h.counts == [0, 1]
    assert h.percentile(50) == 100.0
    assert h.saturated is True
    assert h.summary()["saturated"] is True


def test_histogram_saturated_flag_tracks_overflow_bucket_only():
    h = Histogram(bounds=(1.0, 2.0))
    h.observe(0.5)
    h.observe(2.0)                  # at the top bound: still in-range
    assert h.saturated is False
    assert h.summary()["saturated"] is False
    h.observe(2.1)
    assert h.counts == [1, 1, 1]
    assert h.saturated is True
    assert h.summary()["saturated"] is True


def test_histogram_bounds_validation():
    with pytest.raises(ValueError):
        Histogram(bounds=())
    with pytest.raises(ValueError):
        Histogram(bounds=(2.0, 1.0))


def test_registry_kind_collision():
    reg = MetricsRegistry()
    reg.counter("x")
    with pytest.raises(ValueError):
        reg.gauge("x")
    with pytest.raises(ValueError):
        reg.histogram("x")


def test_labeled_counters_and_gauges():
    reg = MetricsRegistry()
    reg.counter("launches", labels={"cls": "fast"}).inc(2)
    reg.counter("launches", labels={"cls": "slow"}).inc()
    reg.counter("launches").inc(5)      # the bare series coexists
    reg.gauge("depth", labels={"q": "admit"}).set(3.0)
    snap = reg.snapshot()
    assert snap["counters"]['launches{cls="fast"}'] == 2
    assert snap["counters"]['launches{cls="slow"}'] == 1
    assert snap["counters"]["launches"] == 5
    assert snap["gauges"]['depth{q="admit"}'] == {"value": 3.0, "max": 3.0}
    # get-or-create per label set: the hot-path per-call lookup is stable
    assert (reg.counter("launches", labels={"cls": "fast"})
            is reg.counter("launches", labels={"cls": "fast"}))
    json.dumps(snap, allow_nan=False)


def test_labeled_key_is_sorted_and_series_is_structured():
    reg = MetricsRegistry()
    c = reg.counter("m", labels={"b": "2", "a": "1"})
    assert 'm{a="1",b="2"}' in reg.snapshot()["counters"]
    assert reg.series("m") == [({"a": "1", "b": "2"}, c)]
    assert reg.series("nope") == []
    # a family prefix must not leak sibling families into series()
    reg.counter("meters").inc()
    assert reg.series("m") == [({"a": "1", "b": "2"}, c)]


def test_labeled_family_kind_collision():
    reg = MetricsRegistry()
    reg.counter("fam", labels={"x": "1"})
    with pytest.raises(ValueError):
        reg.gauge("fam", labels={"x": "2"})
    with pytest.raises(ValueError):
        reg.gauge("fam")                # the bare name shares the family


# ---- span aggregation -------------------------------------------------------

def test_aggregate_spans_paths_and_self_time():
    tr = Tracer()
    tr.begin_trace(0)
    with tr.span("root"):
        with tr.span("child"):
            pass
        with tr.span("child"):
            pass
    agg = aggregate_spans(tr.records())
    assert set(agg) == {"root", "root/child"}
    assert agg["root"]["count"] == 1 and agg["root/child"]["count"] == 2
    assert agg["root"]["self_us"] == pytest.approx(
        agg["root"]["total_us"] - agg["root/child"]["total_us"])


# ---- engine integration -----------------------------------------------------

def _trace():
    return poisson_churn_trace(num_sessions=6, arrival_rate=1.0, seed=3,
                               m_min=2, m_max=6, session_scale=10.0,
                               num_failure_slices=1)


def _factory(tracers=None, **cfg):
    """Engine factory for recover(): a fresh Fleet per engine (it is
    mutated) and, when ``tracers`` is given, a fresh enabled Tracer per
    engine (spans from the reference / crashed / recovered runs must never
    mix — exactly the crash-demo discipline in examples/)."""
    def make(**kw):
        if tracers is not None and "tracer" not in kw:
            tr = Tracer(enabled=True)
            tracers.append(tr)
            kw["tracer"] = tr
        return StreamEngine(Fleet.partition_pod(16 * 3, 3), "mdmt", seed=0,
                            max_live_models=30, num_shards=2, **cfg, **kw)
    return make


def test_traced_run_matches_untraced_and_stamps_records():
    trace = _trace()
    tr, reg = Tracer(enabled=True), MetricsRegistry()
    traced_log, plain_log = EventLog(), EventLog()
    eng = _factory()(tracer=tr, metrics=reg, log=traced_log)
    res = eng.run(trace)
    ref = _factory()(log=plain_log).run(trace)

    # the observation-only guarantee
    assert ([dataclasses.astuple(t) for t in res.trials]
            == [dataclasses.astuple(t) for t in ref.trials])
    assert res.telemetry.summary() == ref.telemetry.summary()

    # traced processed records carry the trace id (== the event index)...
    assert traced_log.processed
    assert all(len(r) == 5 and r[4] == r[0] for r in traced_log.processed)
    # ...while untraced records keep the legacy 4-field shape
    assert all(len(r) == 4 for r in plain_log.processed)

    names = {r["name"] for r in tr.records()}
    assert {"event", "decide", "posterior", "score", "launch",
            "gp_fold", "gp_flush", "posterior_upload", "admit", "retire",
            "mirrors"} <= names

    snap = reg.snapshot()
    assert snap["counters"]["engine.events"] == eng.event_index
    assert snap["counters"]["engine.launches"] == len(res.trials)
    assert snap["histograms"]["engine.decision_seconds"]["count"] > 0
    # no host-seconds "rate" under the benchmark's end-to-end metric's name
    assert "engine.decisions_per_s" not in snap["gauges"]
    assert any(k.endswith(".busy_fraction") for k in snap["gauges"])
    json.dumps(snap, allow_nan=False)


def test_replayed_suffix_reemits_identical_span_tree(tmp_path):
    trace = _trace()
    ref_tracers = []
    ref = _factory(ref_tracers)().run(trace)
    ref_tr = ref_tracers[0]

    tracers = []
    make = _factory(tracers)
    logdir, snapdir = tmp_path / "log", tmp_path / "snap"
    eng = make(log=EventLog(logdir), snapshot_root=str(snapdir),
               snapshot_every=5, fault=FaultInjector(15, "before"))
    with pytest.raises(SimulatedCrash):
        eng.run(trace)
    eng.log.close()

    eng2, resumed_from = recover(make, str(snapdir), EventLog.load(logdir))
    res2 = eng2.resume()

    # the replay oracle still holds under tracing...
    assert ([dataclasses.astuple(t) for t in res2.trials]
            == [dataclasses.astuple(t) for t in ref.trials])
    # ...and the recovered run re-emitted the reference's exact span tree
    # for the replayed suffix — ids are event indices, not tracer state
    suffix = ref_tr.signature(min_trace=resumed_from + 1)
    assert suffix, "crash point must leave a non-empty replayed suffix"
    assert eng2.tracer.signature(min_trace=resumed_from + 1) == suffix
    # the crashed prefix and the reference prefix also agree span-for-span
    crashed_tr = tracers[0]
    upto = min(s["trace"] for s in crashed_tr.records() if s["trace"] >= 0)
    assert (crashed_tr.signature(min_trace=upto)[:20]
            == [s for s in ref_tr.signature(min_trace=upto)
                if s[0] <= eng.event_index][:20])


# ---- spans and counts inside the decision path -------------------------------

def _block(m: int):
    """(K, mu0, cost) of one tenant's m-point Matérn prior, costs rising."""
    K, _ = _matern_block_chol(m, 0.3, 0.04)
    return K, np.zeros(m), np.linspace(1.0, 2.0, m)


def _one_tenant_trace(m: int = 4) -> ChurnTrace:
    """One tenant on one slice: arrives at 0, every model runs by t = 7,
    departs at 10."""
    K, mu0, cost = _block(m)
    z = np.linspace(0.1, 0.4, m)[::-1].copy()
    return ChurnTrace((TenantArrive(0.0, 0, K, mu0, cost, z),
                       TenantDepart(10.0, 0)))


def _trees(records) -> dict:
    """{event kind: [(span, parent, name, attrs) of each trace of it]}"""
    traces: dict = {}
    for r in sorted(records, key=lambda r: (r["trace"], r["span"])):
        traces.setdefault(r["trace"], []).append(
            (r["span"], r["parent"], r["name"], r["attrs"]))
    out: dict = {}
    for spans in traces.values():
        out.setdefault(spans[0][3]["kind"], []).append(spans)
    return out


@pytest.mark.parametrize("kind, tree", [
    # the first completion: the fold, then a decision (under the posterior
    # the device pool's upload after the admission, then the flush) and
    # its launch
    ("finish", [(0, None, "event"), (1, 0, "gp_fold"), (2, 0, "decide"),
                (3, 2, "posterior"), (4, 3, "posterior_upload"),
                (5, 3, "gp_flush"), (6, 2, "score"),
                (7, 0, "launch")]),
    # an arrival: admission with its mirror rebuild, then the warm start
    ("arrive", [(0, None, "event"), (1, 0, "admit"), (2, 1, "mirrors"),
                (3, 0, "launch")]),
    # a departure: retirement with its mirror rebuild; the freed slice's
    # decision finds nothing left to launch
    ("depart", [(0, None, "event"), (1, 0, "retire"), (2, 1, "mirrors"),
                (3, 0, "decide")]),
])
def test_span_tree_of_each_event_kind(kind, tree):
    tr = Tracer(enabled=True)
    StreamEngine(Fleet.partition_pod(16, 1), "mdmt", seed=0, warm_start=1,
                 tracer=tr).run(_one_tenant_trace())
    first = _trees(tr.records())[kind][0]
    assert [(s, p, n) for s, p, n, _ in first] == tree
    attrs = {n: a for _, _, n, a in first}
    assert attrs.get("admit", {"models": 4}) == {"models": 4}
    assert attrs.get("gp_flush", {"blocks": 1}) == {"blocks": 1}


def test_span_tree_of_a_later_completion():
    """The device pool outlives a decision: a later completion's flush
    dispatches the dirty block's readout, with no upload."""
    tr = Tracer(enabled=True)
    StreamEngine(Fleet.partition_pod(16, 1), "mdmt", seed=0, warm_start=1,
                 tracer=tr).run(_one_tenant_trace())
    later = _trees(tr.records())["finish"][1]
    assert [(s, p, n) for s, p, n, _ in later] == [
        (0, None, "event"), (1, 0, "gp_fold"), (2, 0, "decide"),
        (3, 2, "posterior"), (4, 3, "gp_flush"), (5, 2, "score"),
        (6, 0, "launch")]
    assert {n: a for _, _, n, a in later}["gp_flush"] == {"blocks": 1}


def _sum_counts(records, trace_id) -> dict:
    out: dict = {}
    for r in records:
        if r["trace"] == trace_id:
            for k, v in r["counts"].items():
                out[k] = out.get(k, 0) + v
    return out


@pytest.mark.parametrize("scorer", ["fused", "sharded"])
def test_counts_of_a_decision_follow_from_its_shapes(scorer):
    cp = ControlPlane(np.random.default_rng(0), scorer=scorer, num_shards=1)
    first = cp.add_tenant(*_block(3))
    tr = Tracer()
    cp.set_tracer(tr)
    m = 5
    model, other = int(first.models[0]), int(first.models[1])
    steps = [lambda: cp.add_tenant(*_block(m)),
             lambda: cp.record_start(model),
             lambda: cp.record_observation(model, 0.1),
             lambda: cp.choose_mdmt(),
             lambda: cp.record_observation(other, 0.05),
             lambda: cp.choose_mdmt()]
    picks = []
    for i, step in enumerate(steps):
        tr.begin_trace(i)
        with tr.span("step"):
            picks.append(step())
    assert picks[3] is not None and picks[5] is not None
    cap, slots = cp.capacity, cp.membership.shape[0]
    recs = tr.records()
    f32, scalar = 4, 4
    mirrors = slots * cap + cap * f32 + cap + slots * f32
    if scorer == "sharded":                 # its own membership and costs
        mirrors += slots * cap + cap * f32
    assert _sum_counts(recs, 0) == {
        "h2d_bytes": m * m * f32 + m * f32 + scalar + mirrors}
    assert _sum_counts(recs, 1) == {"h2d_bytes": 2 * scalar}
    # the fold's five scalars, and the first incumbent of tenant 0
    assert _sum_counts(recs, 2) == {"h2d_bytes": 5 * scalar + 2 * scalar}
    # a fold that improves no incumbent: its five scalars alone
    assert _sum_counts(recs, 4) == {"h2d_bytes": 5 * scalar}
    if scorer == "fused":
        # the first decision after an admission: the device pool uploaded
        # once from the host cache (means and variances), the dirty 3-wide
        # block's global ids and its readout's observation count; no block
        # read back; the pick read back (index, score)
        assert _sum_counts(recs, 3) == {
            "pool_uploads": 1,
            "host_syncs": 2,
            "d2h_bytes": 2 * scalar,
            "h2d_bytes": 2 * cap * f32 + 3 * 4 + scalar}
        # the next: the readout's count up, the pick back
        assert _sum_counts(recs, 5) == {
            "host_syncs": 2, "d2h_bytes": 2 * scalar, "h2d_bytes": scalar}
    else:
        # the dirty 3-wide block read back (mean, variance) with its
        # readout's observation count; the pool's upload (means, sds and
        # the selected mask, and the device speed); the pick read back
        # (index, score): every decision alike
        pool = cap * (2 * f32 + 1) + scalar
        for i in (3, 5):
            assert _sum_counts(recs, i) == {
                "host_syncs": 2 + 2,
                "d2h_bytes": 2 * 3 * f32 + 2 * scalar,
                "h2d_bytes": scalar + pool}
    assert tr.counts == {}


def _pool_uploads(cp, tr, trace_id: int) -> int:
    """The ``pool_uploads`` count of one decision, run as trace ``trace_id``."""
    tr.begin_trace(trace_id)
    assert cp.choose_mdmt() is not None
    return _sum_counts(tr.records(), trace_id).get("pool_uploads", 0)


@pytest.mark.parametrize("change", ["admit", "relocate"])
def test_pool_uploads_once_after_a_layout_change(change):
    """``pool_uploads`` reads 1 on the decision after an admission or a
    relocation (the device pool is uploaded anew) and 0 on the next."""
    cp = ControlPlane(np.random.default_rng(0), scorer="fused",
                      num_shards=4, model_capacity=16, tenant_capacity=4)
    tenants = [cp.add_tenant(*_block(3)) for _ in range(5)]
    tr = Tracer()
    cp.set_tracer(tr)
    assert _pool_uploads(cp, tr, 0) == 1        # after set-up's admissions
    assert _pool_uploads(cp, tr, 1) == 0
    if change == "admit":
        cp.add_tenant(*_block(2))
    else:
        for h in tenants[2:4]:         # shard 1 emptied, shard 0 full
            cp.retire_tenant(h.tenant_id)
        assert cp.compact(1.0)                  # at least one block moved
    g = int(np.flatnonzero(~cp.selected)[0])
    cp.record_start(g)
    cp.record_observation(g, 0.2)
    assert _pool_uploads(cp, tr, 2) == 1
    assert _pool_uploads(cp, tr, 3) == 0


def test_counts_land_on_the_innermost_open_span():
    tr = Tracer()
    tr.count("host_syncs", 1)
    tr.begin_trace(0)
    with tr.span("outer"):
        tr.count("h2d_bytes", 8)
        with tr.span("inner"):
            tr.count("h2d_bytes", 4)
            tr.count("h2d_bytes", 4)
    by_name = {r["name"]: r for r in tr.records()}
    assert by_name["inner"]["counts"] == {"h2d_bytes": 8}
    assert by_name["outer"]["counts"] == {"h2d_bytes": 8}
    assert tr.counts == {"host_syncs": 1}
    # counts are not part of the replay signature
    assert tr.signature() == [(0, 1, 0, "inner", ()), (0, 0, None, "outer", ())]


def test_disabled_tracer_counts_nothing():
    tr = Tracer(enabled=False)
    tr.count("host_syncs", 3)
    with tr.span("a"):
        tr.count("h2d_bytes", 4)
    assert tr.counts == {} and tr.records() == []
    _factory()().run(_trace())
    assert NULL_TRACER.counts == {} and NULL_TRACER.records() == []


# ---- report plane -----------------------------------------------------------

def test_write_report_renders_run_directory(tmp_path):
    trace = _trace()
    tr, reg = Tracer(enabled=True), MetricsRegistry()
    eng = _factory()(tracer=tr, metrics=reg)
    res = eng.run(trace)
    run_dir = write_report(
        tmp_path, "run0", telemetry=res.telemetry, tracer=tr, metrics=reg,
        result=res, meta={"seed": 0, "slo": {"device_utilization": 0.0,
                                             "ttfo_p99": 1e9}})
    payload = json.loads((run_dir / "summary.json").read_text())
    assert payload["run_id"] == "run0"
    assert payload["telemetry"]["trials"] == len(res.trials)
    assert payload["spans"] and payload["metrics"]["counters"]
    assert payload["num_spans"] == len(tr.records())

    html_text = (run_dir / "report.html").read_text()
    assert "run0" in html_text and "met" in html_text

    lines = (run_dir / "timeline.csv").read_text().splitlines()
    assert lines[0] == "kind,t,tenant,model,device,value"
    assert len(lines) > 1
    assert (run_dir / "trace.json").exists()


def test_write_report_minimal(tmp_path):
    run_dir = write_report(tmp_path, "empty")
    payload = json.loads((run_dir / "summary.json").read_text())
    assert payload["run_id"] == "empty" and payload["spans"] == {}
    assert (run_dir / "report.html").exists()
    assert not (run_dir / "trace.json").exists()


def _attainment(html_text: str, key: str) -> str:
    m = re.search(rf'<td class="l">{key}</td>'
                  r'<td>[^<]*</td><td>[^<]*</td>'
                  r'<td class="l">([^<]*)</td>', html_text)
    assert m, f"no SLO row for {key}"
    return m.group(1)


def test_slo_section_floor_vs_ceiling_semantics():
    summary = {"device_utilization": 0.8, "ttfo_p99": 50.0,
               "tenant_regret_max": 0.5}
    text = _slo_section(summary, {"device_utilization": 0.9,
                                  "ttfo_p99": 100.0,
                                  "tenant_regret_max": 0.1})
    # utilization targets are floors: 0.8 < 0.9 misses
    assert _attainment(text, "device_utilization") == "MISSED"
    # latency targets are ceilings: 50 <= 100 meets
    assert _attainment(text, "ttfo_p99") == "met"
    # regret targets are ceilings too: 0.5 > 0.1 misses
    assert _attainment(text, "tenant_regret_max") == "MISSED"
    # boundary values meet on both sides of the semantics split
    text = _slo_section({"device_utilization": 0.9, "ttfo_p99": 100.0},
                        {"device_utilization": 0.9, "ttfo_p99": 100.0})
    assert _attainment(text, "device_utilization") == "met"
    assert _attainment(text, "ttfo_p99") == "met"


def test_slo_section_missing_targets_and_values():
    text = _slo_section({"ttfo_p50": None, "serve_gap_p50": 1.0},
                        {"ttfo_p50": 5.0})
    # target set but the run produced no data
    assert _attainment(text, "ttfo_p50") == "no data"
    # value present but no target: ungraded, not "met"
    assert _attainment(text, "serve_gap_p50") == "–"
    # absent from both: still a row, still ungraded
    assert _attainment(text, "tenant_regret_mean") == "–"


# ---- streaming export -------------------------------------------------------

def test_exporter_windows_are_a_function_of_the_event_stream(tmp_path):
    reg = MetricsRegistry()
    c = reg.counter("n")
    path = tmp_path / "export.jsonl"
    ex = MetricsExporter(reg, path=str(path), window=10.0)
    ex.tick(0.0, 0)                 # window 0: emits
    c.inc()
    ex.tick(5.0, 1)                 # same window: silent
    ex.tick(23.0, 2)                # window 2 (idle window 1 emits nothing)
    ex.final(30.0, 3)
    ex.close()
    assert [(r["window"], r["event_index"]) for r in ex.records] == \
           [(0, 0), (2, 2), (3, 3)]
    assert ex.records[0]["metrics"]["counters"]["n"] == 0
    assert ex.records[1]["metrics"]["counters"]["n"] == 1
    assert ex.records[-1]["final"] is True
    # the JSONL stream is the in-memory list, write-through
    lines = [json.loads(s) for s in path.read_text().splitlines()]
    assert lines == ex.records


def test_exporter_cursor_state_roundtrip():
    reg = MetricsRegistry()
    ex = MetricsExporter(reg, window=10.0)
    ex.tick(25.0, 4)
    resumed = MetricsExporter(reg, window=10.0)
    resumed.load_state(json.loads(json.dumps(ex.state_dict())))
    resumed.tick(27.0, 5)           # same window as the pre-crash emit
    assert resumed.records == []
    resumed.tick(31.0, 6)
    assert [r["window"] for r in resumed.records] == [3]


def test_exporter_rejects_nonpositive_window():
    with pytest.raises(ValueError):
        MetricsExporter(MetricsRegistry(), window=0.0)


def test_prometheus_text_rendering():
    reg = MetricsRegistry()
    reg.counter("engine.events").inc(3)
    reg.counter("launches", labels={"cls": "fast"}).inc()
    reg.gauge("depth").set(2.0)
    h = reg.histogram("lat", bounds=(1.0, 2.0))
    h.observe(0.5)
    text = prometheus_text(reg.snapshot())
    assert "# TYPE engine_events_total counter" in text
    assert "engine_events_total 3" in text
    assert 'launches_total{cls="fast"} 1' in text      # labels pass through
    assert "# TYPE depth gauge" in text
    assert "depth 2.0" in text and "depth_max 2.0" in text
    assert "# TYPE lat summary" in text
    assert 'lat{quantile="0.5"} 0.5' in text
    assert "lat_sum 0.5" in text and "lat_count 1" in text
    # empty histograms render NaN quantiles, not a crash
    reg2 = MetricsRegistry()
    reg2.histogram("empty")
    assert 'empty{quantile="0.5"} NaN' in prometheus_text(reg2.snapshot())


# ---- health monitor ---------------------------------------------------------

def test_queue_runaway_fires_on_rise_and_rearms_on_drain():
    hm = HealthMonitor(queue_limit=4)
    for depth in (1, 2, 3):
        hm.on_event(float(depth), depth, queue_depth=depth, backlog=0)
    hm.on_event(4.0, 4, queue_depth=4, backlog=0)   # crosses while rising
    assert [(a.kind, a.severity) for a in hm.alerts] == \
           [("queue_runaway", "page")]
    assert hm.alerts[0].detail == {"depth": 4, "limit": 4}
    hm.on_event(5.0, 5, queue_depth=6, backlog=0)   # still high: no re-fire
    assert len(hm.alerts) == 1
    hm.on_event(6.0, 6, queue_depth=2, backlog=0)   # <= limit//2: re-arms
    hm.on_event(7.0, 7, queue_depth=5, backlog=0)
    assert [a.kind for a in hm.alerts] == ["queue_runaway"] * 2


def test_regret_stall_counts_and_rearms_on_improvement():
    hm = HealthMonitor(stall_k=3)
    hm.on_observation(0.0, 0, 7, True)
    for i in range(1, 4):
        hm.on_observation(float(i), i, 7, False)
    assert [a.kind for a in hm.alerts] == ["regret_stall"]
    assert hm.alerts[0].subject == "7"
    assert hm.alerts[0].detail["observations_since_improvement"] == 3
    hm.on_observation(4.0, 4, 7, False)     # still stalled: deduped
    assert len(hm.alerts) == 1
    hm.on_observation(5.0, 5, 7, True)      # improvement re-arms
    for i in range(6, 9):
        hm.on_observation(float(i), i, 7, False)
    assert [a.kind for a in hm.alerts] == ["regret_stall"] * 2
    # an unrelated tenant keeps its own counter
    hm.on_observation(9.0, 9, 8, False)
    assert len(hm.alerts) == 2


def test_gp_conditioning_threshold_and_per_window_dedupe():
    hm = HealthMonitor(window=10.0, conditioning_scale=10.0)
    hm.on_observation(1.0, 0, "t", True, d2=5e-6, jitter=1e-6)
    hm.on_observation(2.0, 1, "t", True, d2=5e-6, jitter=1e-6)   # same window
    hm.on_observation(12.0, 2, "t", True, d2=5e-6, jitter=1e-6)  # next window
    hm.on_observation(13.0, 3, "t", True, d2=1e-3, jitter=1e-6)  # healthy
    hm.on_observation(14.0, 4, "t", True)                         # no d2 fed
    assert [a.kind for a in hm.alerts] == ["gp_conditioning"] * 2
    assert hm.alerts[0].detail == {"model": -1, "d2": 5e-6, "jitter": 1e-6}
    assert [a.event_index for a in hm.alerts] == [0, 2]


def test_class_starvation_clock_only_runs_while_demand_present():
    hm = HealthMonitor(starvation_window=10.0)
    # idle WITHOUT demand: the clock keeps resetting, no alert ever
    for t in range(0, 30, 5):
        hm.on_event(float(t), t, queue_depth=0, backlog=0,
                    free_classes=("base",))
    assert hm.alerts == []
    # demand appears at t=30; last demand-free tick was t=25
    hm.on_event(30.0, 30, queue_depth=0, backlog=2, free_classes=("base",))
    assert hm.alerts == []                  # only 5s on the demand clock
    hm.on_event(35.0, 31, queue_depth=0, backlog=2, free_classes=("base",))
    assert [a.kind for a in hm.alerts] == ["class_starvation"]
    assert hm.alerts[0].subject == "base"
    assert hm.alerts[0].detail == {"idle_for": 10.0, "backlog": 2}
    # a launch on the class re-arms and restarts its clock
    hm.on_launch(36.0, 32, 0, 1, "base")
    hm.on_event(40.0, 33, queue_depth=0, backlog=2, free_classes=("base",))
    assert len(hm.alerts) == 1
    hm.on_event(47.0, 34, queue_depth=0, backlog=2, free_classes=("base",))
    assert len(hm.alerts) == 2


def test_slo_burn_rate_window_grading_and_rearm():
    vals = iter([0.1, 0.1, 0.9, 0.1, 0.1])
    summary_fn = lambda: {"device_utilization": next(vals)}  # noqa: E731
    hm = HealthMonitor(slo={"device_utilization": 0.5}, window=10.0,
                       burn_windows=2, burn_threshold=0.75)
    hm.on_event(10.0, 1, queue_depth=0, backlog=0, summary_fn=summary_fn)
    assert hm.alerts == []          # one window of history < burn_windows
    hm.on_event(20.0, 2, queue_depth=0, backlog=0, summary_fn=summary_fn)
    assert [(a.kind, a.severity) for a in hm.alerts] == [("slo_burn", "page")]
    assert hm.alerts[0].detail == {"burn_rate": 1.0, "value": 0.1,
                                   "target": 0.5}
    hm.on_event(30.0, 3, queue_depth=0, backlog=0, summary_fn=summary_fn)
    hm.on_event(40.0, 4, queue_depth=0, backlog=0, summary_fn=summary_fn)
    assert len(hm.alerts) == 1      # compliant window re-armed; burn 0.5 < .75
    hm.on_event(50.0, 5, queue_depth=0, backlog=0, summary_fn=summary_fn)
    assert len(hm.alerts) == 2      # two failing windows again: page again
    # mid-window events never grade (the iterator would raise StopIteration)
    hm.on_event(51.0, 6, queue_depth=0, backlog=0, summary_fn=summary_fn)


def test_slo_burn_uses_report_plane_floor_vs_ceiling_semantics():
    mk = lambda: HealthMonitor(slo={"ttfo_p99": 100.0}, window=10.0,  # noqa: E731
                               burn_windows=1, burn_threshold=0.5)
    hm = mk()
    hm.on_event(10.0, 1, queue_depth=0, backlog=0,
                summary_fn=lambda: {"ttfo_p99": 250.0})
    assert [a.kind for a in hm.alerts] == ["slo_burn"]      # ceiling exceeded
    hm2 = mk()
    hm2.on_event(10.0, 1, queue_depth=0, backlog=0,
                 summary_fn=lambda: {"ttfo_p99": 50.0})
    assert hm2.alerts == []                                  # under the ceiling


def test_health_state_roundtrip_reemits_exactly_the_suffix():
    def drive(hm, start):
        for i in range(start, start + 6):
            hm.on_observation(float(i), i, "t0", False)
            hm.on_event(float(i), i, queue_depth=i, backlog=0)

    cfg = dict(stall_k=9, queue_limit=8)
    prefix_hm = HealthMonitor(**cfg)
    drive(prefix_hm, 0)
    state = json.loads(json.dumps(prefix_hm.state_dict()))  # snapshot-safe

    full = HealthMonitor(**cfg)
    drive(full, 0)
    drive(full, 6)
    resumed = HealthMonitor(**cfg)
    resumed.load_state(state)
    assert resumed.alerts == [] and resumed.drain_new() == []
    drive(resumed, 6)
    # the resumed monitor emits the full run's alerts minus the prefix
    assert full.alerts[len(prefix_hm.alerts):] == resumed.alerts
    assert {a.kind for a in resumed.alerts} == {"regret_stall",
                                                "queue_runaway"}


def test_alert_record_roundtrip_and_drain():
    from repro.obs import Alert
    hm = HealthMonitor(queue_limit=1)
    hm.on_event(1.0, 1, queue_depth=1, backlog=0)
    (a,) = hm.drain_new()
    assert hm.drain_new() == []         # drained exactly once
    rec = json.loads(json.dumps(a.to_record(), allow_nan=False))
    assert Alert.from_record(rec) == a
    assert rec["kind"] in ALERT_KINDS


# ---- forensics --------------------------------------------------------------

def test_forensics_uniform_cost_counterfactual_flip():
    fr = ForensicsRecorder()
    fr.begin_event(3.0, 17)
    # model 11 wins on EIrate (0.5 vs 0.1) but model 4 has the larger EI
    # (1.0 vs 0.5): the pick is cheapness-driven and the counterfactual
    # flips it
    rec = fr.on_decision(scorer="fused", values=[0.5, 0.1], gids=[11, 4],
                         eff_costs=[1.0, 10.0], mu=[0.2, 0.4],
                         sd=[0.1, 0.3])
    assert (rec["t"], rec["event_index"], rec["seq"]) == (3.0, 17, 0)
    assert rec["winner"]["model"] == 11 and rec["runner_up"]["model"] == 4
    assert rec["winner"]["ei"] == pytest.approx(0.5)
    assert rec["runner_up"]["ei"] == pytest.approx(1.0)
    assert rec["winner"]["mu"] == 0.2 and rec["winner"]["sd"] == 0.1
    assert rec["margin"] == pytest.approx(0.4)
    assert rec["uniform_cost"] == {"model": 4, "changes_pick": True}
    # seq separates same-event decisions; a lone candidate has no runner-up
    rec2 = fr.on_decision(scorer="fused", values=[0.5], gids=[11],
                          eff_costs=[1.0])
    assert rec2["seq"] == 1 and rec2["runner_up"] is None
    assert rec2["margin"] is None
    assert rec2["uniform_cost"] == {"model": 11, "changes_pick": False}
    json.dumps(fr.records, allow_nan=False)


def test_forensics_truncates_padded_topk_tail(tmp_path):
    path = tmp_path / "forensics.jsonl"
    fr = ForensicsRecorder(path=str(path))
    fr.begin_event(0.0, 0)
    # -1e30 is the sharded scorer's masked-slot fill: the tail after it is
    # padding, not candidates — even if finite values follow
    rec = fr.on_decision(scorer="sharded", values=[1.0, -1e30, 0.5],
                         gids=[1, 2, 3], eff_costs=[1.0, 1.0, 1.0])
    assert [c["model"] for c in rec["topk"]] == [1]
    assert rec["runner_up"] is None
    fr.close()
    assert [json.loads(s) for s in path.read_text().splitlines()] == [rec]


# ---- engine integration: every plane at once --------------------------------

def test_all_planes_enabled_run_matches_bare_twin():
    trace = _trace()
    reg = MetricsRegistry()
    eng = _factory()(tracer=Tracer(enabled=True), metrics=reg,
                     exporter=MetricsExporter(reg, window=5.0),
                     health=HealthMonitor(slo={"device_utilization": 1.5},
                                          window=5.0, burn_windows=2),
                     forensics=ForensicsRecorder())
    res = eng.run(trace)
    ref = _factory()().run(trace)

    # the observation-only guarantee with the full stack attached
    assert ([dataclasses.astuple(t) for t in res.trials]
            == [dataclasses.astuple(t) for t in ref.trials])
    assert res.telemetry.summary() == ref.telemetry.summary()

    # every plane actually observed the run
    assert eng.exporter.records and eng.exporter.records[-1].get("final")
    assert eng.forensics.records
    assert all(r["winner"] is not None for r in eng.forensics.records)
    assert all(r["scorer"] for r in eng.forensics.records)
    # a >1.0 utilization floor is unreachable: the burn detector must page
    assert any(a.kind == "slo_burn" and a.severity == "page"
               for a in eng.health.alerts)
    # the engine forwarded every alert to the durable log, in order
    assert eng.log.alerts == [a.to_record() for a in eng.health.alerts]
    # labeled per-class launch counters (S1) fed from the launch path
    fam = reg.series("engine.launches_by_class")
    assert fam and all(set(labels) == {"cls"} for labels, _ in fam)
    assert sum(c.value for _, c in fam) == len(res.trials)


def test_devplane_batched_forensics_carries_class_and_seq():
    from repro.devplane import DevPlaneEngine, two_class_registry
    from repro.stream import device_churn_trace

    trace = device_churn_trace(
        num_sessions=8, arrival_rate=1.5, seed=2, initial_slices=4,
        join_classes=(("fast", 16, 2.0), ("slow", 16, 1.0)),
        join_rate=0.05, leave_rate=0.02, preempt_rate=0.03,
        m_min=2, m_max=6, session_scale=10.0)

    def make(**kw):
        reg = two_class_registry(2.0, overhead=0.5, chips=16)
        fleet = reg.build_fleet([("slow", 2), ("fast", 2)])
        return DevPlaneEngine(fleet, "mdmt", seed=0, registry=reg,
                              assign="batched", launch_order="fastest",
                              max_live_models=30, **kw)

    fr = ForensicsRecorder()
    res = make(forensics=fr).run(trace)
    ref = make().run(trace)
    assert ([dataclasses.astuple(t) for t in res.trials]
            == [dataclasses.astuple(t) for t in ref.trials])
    assert fr.records
    # batched per-class decisions stamp the class name
    classes = {r["device_class"] for r in fr.records}
    assert {"slow", "fast"} <= classes
    assert all(r["winner"]["cost"] > 0 for r in fr.records)


def test_batched_decision_records_one_forensics_row_per_class():
    import numpy as np
    from repro.core.control_plane import ControlPlane

    cp = ControlPlane(np.random.default_rng(0))
    m = 4
    cp.add_tenant(0.04 * np.eye(m), np.zeros(m), np.ones(m))
    fr = ForensicsRecorder()
    cp.set_forensics(fr)
    fr.begin_event(1.0, 5)
    v, g = cp.choose_mdmt_batch([4.0, 1.0], [0.25, 0.0], k=2,
                                class_names=["fast", "slow"])
    # one record per class row of the SAME event: seq separates them
    assert [(r["seq"], r["device_class"]) for r in fr.records] == \
           [(0, "fast"), (1, "slow")]
    assert all(r["event_index"] == 5 and r["t"] == 1.0 for r in fr.records)
    # effective costs are the class's affine row: cost/rate + overhead
    assert fr.records[0]["winner"]["cost"] == pytest.approx(1 / 4 + 0.25)
    assert fr.records[1]["winner"]["cost"] == pytest.approx(1.0)
    # and the recorded scores are the rows the assignment solver consumed
    assert fr.records[0]["winner"]["eirate"] == pytest.approx(float(v[0][0]))
    assert fr.records[1]["winner"]["eirate"] == pytest.approx(float(v[1][0]))


# ---- S2: the disabled stack must stay under 1% of a decision ----------------

def test_disabled_obs_stack_overhead_under_one_percent():
    bench = Path(__file__).resolve().parents[1] / "BENCH_decision_trace.json"
    if not bench.exists():
        pytest.skip("no committed decision-cost baseline to compare against")
    rows = json.loads(bench.read_text())["rows"]
    row = rows.get("decision_trace_L100000_S1")
    if row is None:
        pytest.skip("baseline lacks the L=100k reference row")
    decision_us = float(row["fused_us"])

    # the engine's per-event obs sites with every plane disabled: four
    # attribute loads + None checks (src/repro/stream/engine.py _drain)
    eng = _factory()()
    assert (eng.exporter is None and eng.health is None
            and eng.forensics is None and eng.metrics is None)

    def sites():
        if eng.forensics is not None:
            eng.forensics.begin_event(0.0, 0)
        if eng.metrics is not None:
            pass
        if eng.health is not None:
            eng._health_tick()
        if eng.exporter is not None:
            eng.exporter.tick(0.0, 0)

    iters = 20_000
    for _ in range(500):            # warm the attribute caches
        sites()
    t0 = time.perf_counter()
    for _ in range(iters):
        sites()
    site_us = (time.perf_counter() - t0) / iters * 1e6
    assert site_us < 0.01 * decision_us, (
        f"disabled obs stack costs {site_us:.3f}µs — more than 1% of the "
        f"committed L=100k decision ({decision_us:.0f}µs)")
