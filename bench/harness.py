"""One run of one cell: set-up, the paced window, the reference check.

Everything a cell needs is found by name: the cell in ``BENCHMARK.json``,
its configuration in ``bench/configs/<config>.json``, its traffic in
``bench/traffic/<traffic>.json`` and each per-layer metric's reader in
``bench/metrics/<base>.py`` (``<base>`` is the metric's name up to its
first dot).  A later cell, configuration or metric is a new file and a new
entry; nothing here names one.

The window drives ``StreamEngine.run`` itself (``policy="mdmt"``,
``scorer="fused"``, the default scoring kernel and the in-memory event
log; no forensics, health, exporter or accounting plane), paced from
outside by :class:`bench.pacer.PacedTracer`.
"""

from __future__ import annotations

import gc
import importlib.util
import json
import os
import time
from dataclasses import dataclass, field
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parents[1]

#: host spans the trace reduction labels idle gaps with
HOST_SPANS = ("event", "decide", "posterior", "score", "gp_fold", "launch",
              "compaction", "pacer_sleep")


class BenchError(RuntimeError):
    """The run cannot be made or measured as the cell asks."""


def prepare_process() -> None:
    """Before JAX starts: keep the compile cache in the checkout (a fixed
    ``<checkout>/.jax_cache``, whatever ``JAX_COMPILATION_CACHE_DIR``
    says) and let the TPU runtime write no log files (by default it would,
    under a fixed ``/tmp`` path)."""
    os.environ.pop("JAX_COMPILATION_CACHE_DIR", None)
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache(ROOT)


def load_benchmark(root: Path = ROOT) -> dict:
    return json.loads((root / "BENCHMARK.json").read_text())


def _load(root: Path, kind: str, name: str) -> dict:
    path = root / "bench" / kind / f"{name}.json"
    if not path.is_file():
        raise BenchError(f"no {kind} file bench/{kind}/{name}.json")
    return json.loads(path.read_text())


def find_cell(bench: dict, workload: str,
              root: Path = ROOT) -> tuple[dict, dict, dict]:
    """(cell entry, its configuration, its traffic), each by name."""
    cells = {c["name"]: c for c in bench["workloads"]}
    if workload not in cells:
        raise BenchError(f"no workload {workload!r} in BENCHMARK.json")
    cell = cells[workload]
    return (cell, _load(root, "configs", cell["config"]),
            _load(root, "traffic", cell["traffic"]))


def cell_metrics(bench: dict, workload: str, kind: str) -> list[dict]:
    """The ``end_to_end`` or ``per_layer`` metrics this cell reports."""
    return [m for m in bench[kind]
            if workload in m.get("workloads", [workload])]


def metric_reader(name: str, root: Path = ROOT):
    """The ``read`` function of ``bench/metrics/<base>.py``."""
    base = name.split(".")[0]
    path = root / "bench" / "metrics" / f"{base}.py"
    if not path.is_file():
        raise BenchError(f"no reader bench/metrics/{base}.py for {name!r}")
    spec = importlib.util.spec_from_file_location(f"bench_metric_{base}",
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def peaks_for(kind: str, root: Path = ROOT) -> dict:
    """The peak rates of a device kind; a kind not in the table is an
    error, never a default."""
    table = json.loads((root / "bench" / "peaks.json").read_text())
    if kind not in table:
        raise BenchError(f"device kind {kind!r} is not in bench/peaks.json")
    return table[kind]


def accelerator(chips: int) -> dict:
    """The devices JAX found; a run without ``chips`` TPUs stops here."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise BenchError(f"no TPU: JAX's first device is "
                         f"{devs[0].platform!r} ({devs[0].device_kind})")
    if len(devs) < chips:
        raise BenchError(f"{chips} chips needed, {len(devs)} found")
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


class CompileLog:
    """Backend compiles, from JAX's monitoring events (as ``chip_smoke``
    counts them)."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        self.count = 0
        self.seconds = 0.0

    def _on_duration(self, event: str, duration: float, **_) -> None:
        if event == self.EVENT:
            self.count += 1
            self.seconds += duration

    def __enter__(self):
        import jax
        jax.monitoring.register_event_duration_secs_listener(
            self._on_duration)
        return self

    def __exit__(self, *exc):
        import jax
        jax.monitoring.unregister_event_duration_listener(self._on_duration)


@dataclass
class RunView:
    """What a per-layer metric reader may read."""
    spans: list
    profile: dict | None
    compiles_in_window: int
    decide_live: list = field(default_factory=list)
    peaks: dict | None = None


class _Profiler:
    """Device trace of the window: the profiler and the window annotation
    start as the window opens and stop as it closes."""

    def __init__(self, directory: Path):
        self.dir = directory
        self.window = None

    def start(self) -> None:
        import jax
        from jax.profiler import ProfileOptions
        from bench.trace_reduce import WINDOW
        opts = ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1
        jax.profiler.start_trace(str(self.dir), profiler_options=opts)
        self.window = jax.profiler.TraceAnnotation(WINDOW)
        self.window.__enter__()

    def stop(self) -> dict:
        import jax
        from bench.trace_reduce import Trace
        self.window.__exit__(None, None, None)
        jax.effects_barrier()
        jax.profiler.stop_trace()
        found = sorted(self.dir.rglob("*.xplane.pb"))
        if not found:
            raise BenchError("the profiler wrote no trace")
        try:
            return Trace.from_file(found[-1]).reduce(HOST_SPANS)
        except ValueError as e:
            raise BenchError(f"trace {found[-1]}: {e}") from e


def prewarm(cp, sizes, grows: bool) -> None:
    """Compile before the window what the window may meet and the warm
    prefix may not have: the per-block programs (fold, readout) of every
    block size in the trace and, where tenants still arrive (``grows``),
    the scoring path at the next doubling of the pool's candidate and
    tenant capacities, which the pool can then reach inside the window.
    It runs on throwaway control planes; the engine's own state is not
    touched."""
    from repro.core.control_plane import ControlPlane

    n, N = cp.capacity, cp.membership.shape[0]
    shapes = [(n, N, sizes)]
    if grows:
        shapes += [(2 * n, N, sizes[:1]), (n, 2 * N, sizes[:1]),
                   (2 * n, 2 * N, sizes[:1])]
    for cap_n, cap_N, blocks in shapes:
        plane = ControlPlane(np.random.default_rng(0), scorer="fused",
                             model_capacity=cap_n, tenant_capacity=cap_N)
        for m in blocks:
            ids = plane.add_tenant(0.04 * np.eye(m), np.zeros(m),
                                   np.ones(m)).models
            plane.record_start(int(ids[0]))
            plane.record_observation(int(ids[0]), 0.1)
            plane.choose_mdmt()
            plane.record_start(int(ids[-1]))
            plane.record_failure(int(ids[-1]))


def _window_decisions(trials, first: int) -> set:
    """Trial indices of the window's policy decisions: all are checked."""
    return {j for j in range(first, len(trials))
            if trials[j].user_hint == -1}


def judge(gaps, errors, post_err: float, limits: dict):
    """``correct``, ``failed`` and the compared numbers beside their
    limits, for the picks' gaps and the final posterior's error of
    whatever sat in the program's place (the program, or the control)."""
    numbers = {
        "pick_gap": (max(gaps, default=float("nan")), limits["pick_gap"]),
        "posterior_err": (post_err, limits["posterior_err"]),
        "bookkeeping_errors": (len(errors), 0),
    }
    failed = sum(g > limits["pick_gap"] for g in gaps) + len(errors)
    correct = (bool(gaps) and failed == 0
               and post_err <= limits["posterior_err"])
    checks = {k: {"value": v, "limit": lim}
              for k, (v, lim) in numbers.items()}
    return correct, failed, checks


def _program_posterior(engine, result):
    """The run's final posterior, per tenant key, as float64 arrays."""
    mu, var = engine.cp.gp.posterior()
    mu, var = np.asarray(mu, np.float64), np.asarray(var, np.float64)

    def of(key):
        tr = result.tenants[key]
        ids = slice(tr.model_start, tr.model_start + tr.arrive.num_models)
        return mu[ids], var[ids]
    return of


def _probe() -> tuple:
    """This thread's and the process's CPU seconds so far (read at each
    window event's begin and end)."""
    return time.thread_time(), time.process_time()


def _host_counters() -> dict:
    """Host counters whose change over the window says where a stall's
    wall time went: this process's CPU seconds, the seconds in which some
    task waited for CPU, memory or I/O (``/proc/pressure``), the CPU time
    the hypervisor took (``steal`` of ``/proc/stat``) and the cgroup's CPU
    throttling, each where the machine has it."""
    out = {"process_cpu_s": time.process_time()}
    for res in ("cpu", "memory", "io"):
        try:
            first = Path(f"/proc/pressure/{res}").read_text().split("\n")[0]
            out[f"{res}_pressure_s"] = int(first.rsplit("total=", 1)[1]) / 1e6
        except (OSError, IndexError, ValueError):
            pass
    try:
        fields = Path("/proc/stat").read_text().split("\n", 1)[0].split()
        out["steal_s"] = int(fields[8]) / os.sysconf("SC_CLK_TCK")
    except (OSError, IndexError, ValueError):
        pass
    for path in ("/sys/fs/cgroup/cpu.stat", "/sys/fs/cgroup/cpu/cpu.stat"):
        try:
            for row in Path(path).read_text().splitlines():
                key, value = row.split()
                if key in ("nr_throttled", "throttled_usec", "throttled_time"):
                    out[key] = int(value)
        except (OSError, ValueError):
            continue
    return out


def _slowest(pacer, processed, top: int = 5) -> list:
    """The window's longest-running events, each as a dict: its wall ms,
    kind, trace time and seconds into the window, and over the event this
    thread's and the process's CPU ms (a diagnostic for stalls: a stall
    with little CPU of its own waited, on the OS or on another thread)."""
    n = len(pacer.due)
    kinds = [rec[2] for rec in processed[-n:]] if n else []
    took = [(e - b, i) for i, (b, e) in enumerate(zip(pacer.begin,
                                                      pacer.end))]
    out = []
    for d, i in sorted(took, reverse=True)[:top]:
        row = {"ms": 1e3 * d, "kind": kinds[i], "t": pacer.times[i],
               "at_s": pacer.begin[i] - pacer.window_start}
        if i < len(pacer.probe_end):
            b, e = pacer.probe_begin[i], pacer.probe_end[i]
            row.update(thread_cpu_ms=1e3 * (e[0] - b[0]),
                       process_cpu_ms=1e3 * (e[1] - b[1]))
        out.append(row)
    return out


def run_cell(workload: str, seed: int, seconds: float, trace: bool, *,
             t_start: float, require_tpu: bool = True,
             scratch: Path | None = None, root: Path = ROOT,
             control: bool = False, traffic: dict | None = None):
    """Make one run of ``workload``; return its result line and a dict of
    diagnostics.  ``require_tpu=False`` skips the look for a chip (the
    tests drive the rest of a run on the CPU that way); ``control=True``
    puts the bfloat16 control in the program's place for the check (the
    line's ``correct``, ``failed`` and ``checks`` are the control's; the
    program's own verdict goes to ``info["program"]``) and ``traffic``
    stands in for the cell's traffic file (calibration and the knee
    sweep)."""
    import jax
    from repro.core.fleet import Fleet
    from repro.stream import StreamEngine

    from bench.pacer import Pacer, PacedTracer, WindowClosed, nearest_rank
    from bench.reference import replay_run
    from bench.workload import make_trace

    bench = load_benchmark(root)
    cell, cfg, cell_traffic = find_cell(bench, workload, root)
    traffic = traffic or cell_traffic
    if require_tpu:
        device = accelerator(cell["chips"])
        peaks = peaks_for(device["kind"], root)
    else:
        d0 = jax.devices()[0]
        device = {"platform": d0.platform, "kind": d0.device_kind,
                  "count": len(jax.devices())}
        peaks = None

    chain = make_trace(cfg["tenants"], seed, name=workload)
    arrivals = {ev.tenant_key: ev for ev in chain
                if type(ev).__name__ == "TenantArrive"}
    if traffic["pacing"] == "unpaced":
        units_per_s = None
    elif traffic["pacing"] == "open_loop":
        units_per_s = traffic["events_per_s"] / cfg["events_per_unit"]
    else:
        raise BenchError(f"unknown pacing {traffic['pacing']!r}")

    compiles = CompileLog()
    profiler = _Profiler(scratch) if trace else None
    at_open = {}

    sizes = sorted({ev.num_models for ev in arrivals.values()})
    grows = any(ev.at >= cfg["warm_until"] for ev in arrivals.values())

    def on_open():
        prewarm(engine.cp, sizes, grows)
        at_open["compiles"] = compiles.count
        at_open["launches"] = tracer.launches
        at_open["host"] = _host_counters()
        if profiler is not None:
            profiler.start()

    def sleep(s):
        if profiler is None:
            time.sleep(s)
        else:
            with jax.profiler.TraceAnnotation("pacer_sleep"):
                time.sleep(s)

    pacer = Pacer(t_warm=cfg["warm_until"], seconds=seconds,
                  units_per_s=units_per_s, sleep=sleep, on_open=on_open,
                  probe=_probe)
    live_at_decide = []

    def on_decide():
        live_at_decide.append((engine.cp.num_models,
                               int(np.count_nonzero(engine.cp.tenant_live))))

    tracer = PacedTracer(pacer, enabled=trace, profiler=trace,
                         on_decide=on_decide if trace else None)

    fleet = cfg["fleet"]
    engine = StreamEngine(
        Fleet.partition_pod(fleet["total_chips"], fleet["slices"]), "mdmt",
        warm_start=cfg["warm_start"], seed=seed, scorer="fused",
        tracer=tracer)
    tracer.engine = engine
    gc_pauses = []

    def on_gc(phase, _info):
        if phase == "start":
            gc_pauses.append(time.perf_counter())
        elif pacer.open:
            gc_pauses[-1] = time.perf_counter() - gc_pauses[-1]
        else:
            gc_pauses.pop()

    gc.callbacks.append(on_gc)
    with compiles:
        try:
            engine.run(chain)
        except WindowClosed:
            pass
        else:
            raise BenchError("the trace ran out before the window closed")
        finally:
            gc.callbacks.remove(on_gc)
        host = _host_counters()
        profile = profiler.stop() if profiler is not None else None
    if pacer.window_start is None:
        raise BenchError("the window never opened")
    result = engine.resume(horizon=-np.inf)   # collects the run's record
    memory_peak = (jax.devices()[0].memory_stats() or {}).get(
        "peak_bytes_in_use")

    # ---- the check against the reference (after the window) ----------
    trials = result.trials
    window_first = at_open["launches"]
    limits = cfg["verify"]["limits"]
    rep = replay_run(arrivals, engine.log.processed, tracer.launches_before,
                     trials, check=_window_decisions(trials, window_first),
                     jitter=cfg["gp_jitter"], warm_start=cfg["warm_start"],
                     control=control)
    own = judge(rep.gaps, rep.errors,
                rep.posterior_error(_program_posterior(engine, result)),
                limits)
    if control:
        # the control in the program's place: its picks and its final
        # posterior go through the same comparison as the program's
        correct, failed, checks = judge(
            rep.control_gaps, rep.errors,
            rep.posterior_error(rep.control_posterior), limits)
    else:
        correct, failed, checks = own

    # ---- metrics -----------------------------------------------------
    window_s = pacer.window_s
    decisions = sum(1 for t in trials[window_first:] if t.user_hint == -1)
    e2e = {
        "setup_s": (pacer.window_start - t_start, "s"),
        "latency_p95_ms": (1e3 * nearest_rank(pacer.latencies(), 0.95),
                           "ms"),
        "decisions_per_s": (decisions / window_s, "decisions/s"),
    }
    metrics = {}
    if not trace:
        for m in cell_metrics(bench, workload, "end_to_end"):
            if m["name"] not in e2e:
                raise BenchError(f"no end-to-end metric {m['name']!r}")
            value, unit = e2e[m["name"]]
            metrics[m["name"]] = {"value": value, "unit": unit}
    else:
        view = RunView(spans=tracer.records(), profile=profile,
                       compiles_in_window=compiles.count
                       - at_open["compiles"],
                       decide_live=live_at_decide, peaks=peaks)
        for m in cell_metrics(bench, workload, "per_layer"):
            value = metric_reader(m["name"], root)(view)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}

    device["memory_peak_bytes"] = memory_peak
    line = {"correct": correct, "attempted": len(pacer.due),
            "failed": failed, "metrics": metrics, "device": device}
    if trace:
        device["busy_s"] = profile["busy_s"]
        device["window_s"] = profile["window_s"]
        line["breakdown"] = {"device_ops": profile["device_ops"],
                             "idle_gaps": profile["idle_gaps"]}
    late = pacer.lateness()
    info = {"window_s": window_s, "events": len(pacer.due),
            "decisions": decisions, "checked": len(rep.gaps),
            "compiles_in_window": compiles.count - at_open["compiles"],
            "compile_s_total": compiles.seconds,
            "live_models": engine.cp.num_models,
            "capacity": engine.cp.capacity,
            "tenant_slots": int(engine.cp.membership.shape[0]),
            "trace_t": [cfg["warm_until"], pacer.times[-1]
                        if pacer.times else None],
            "lateness_p50_ms": 1e3 * nearest_rank(late, 0.5),
            "lateness_max_ms": 1e3 * max(late),
            "lateness_first_quarter_ms": 1e3 * nearest_rank(
                late[:max(1, len(late) // 4)], 0.5),
            "lateness_last_quarter_ms": 1e3 * nearest_rank(
                late[-max(1, len(late) // 4):], 0.5),
            "overrun_s": pacer.end[-1] - pacer.window_start - seconds,
            "overslept_ms": 1e3 * pacer.overslept,
            "gc_pauses_in_window": len(gc_pauses),
            "gc_pause_max_ms": 1e3 * max(gc_pauses, default=0.0),
            "host_in_window": {k: v - at_open["host"][k]
                               for k, v in host.items()
                               if k in at_open["host"]},
            "events_over_100ms": sum(e - b > 0.1 for b, e in
                                     zip(pacer.begin, pacer.end)),
            "slowest_events": _slowest(pacer, engine.log.processed)}
    if control:
        info["program"] = {"correct": own[0], "failed": own[1],
                           "checks": own[2]}
    line["checks"] = checks
    return line, info
