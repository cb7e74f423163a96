"""Zero-dependency observability layer: tracing, metrics, reports.

The service's innermost hot path — one sharded GP-EI decision — is also its
scaling ceiling (BENCH_shard_scale.json: ~220ms at |L|=100k, weak-scaling
efficiency 0.16 at 8 shards).  You cannot tune what you cannot observe, so
this package gives the control plane three observation planes (DESIGN.md
§13):

  trace.py    :class:`Tracer` — nestable monotonic-clock spans with
              deterministic (trace, span) ids, a ``block_until_ready``-aware
              sync so device work is attributed to the span that launched
              it, and an optional ``jax.profiler`` trace-annotation bridge
              (spans show up in TensorBoard/Perfetto device profiles).
              Disabled tracers cost one branch per site; spans also
              carry counts (host syncs, bytes moved each way).  The
              cost, tracing off and on, measured on a TPU v5e, is in
              ``trace.py``'s docstring.

  metrics.py  :class:`MetricsRegistry` — counters, gauges, and fixed-bucket
              histograms with p50/p99 snapshots.  The streaming engines feed
              it (events, launches, decision latency, queue depth, compaction
              pause, snapshot latency, per-device busy fraction) and the
              snapshot exports through the existing telemetry JSON sink.

  report.py   :func:`write_report` — one experiment directory per run
              (``reports/<run_id>/`` with ``summary.json``,
              ``timeline.csv``, a self-contained ``report.html`` and the
              raw ``trace.json``), rendered from telemetry + trace + metrics
              payloads plus the live planes' alerts and forensics records.
              The multi-tenant operator view.

The *active* layer on top (DESIGN.md §14) turns the flight recorder into a
monitoring system:

  export.py     :class:`MetricsExporter` — sim-time-windowed registry
                snapshots streamed to append-only JSONL from inside the
                engine pop loops, plus a Prometheus text rendering.
  health.py     :class:`HealthMonitor` — SLO burn-rate alerts against the
                run's ``meta["slo"]`` targets and rule-based watchdogs
                (regret-stall, queue runaway, device-class starvation, GP
                conditioning), emitting structured :class:`Alert` records
                into telemetry and the durable event log.
  forensics.py  :class:`ForensicsRecorder` — per-decision attribution
                (winner/runner-up EIrate, μ/σ/cost decomposition, argmax
                margin, uniform-cost counterfactual) from the top-k the
                scoring program already materializes.

The *capacity* layer (DESIGN.md §15) accounts for the resources both of
the above spend:

  accounting.py :class:`CapacityAccountant` — per-tenant GP posterior byte
                accounting, shard slot occupancy + load imbalance, fleet
                composition, and a projected-bytes-at-horizon feed for the
                health plane's memory watchdog; published as labeled
                ``capacity.*`` gauges through the registry/exporter.
  profile.py    device-time attribution: ``jax.profiler`` capture windows,
                per-shard timing-skew probes, and a shard_map dispatch-
                overhead probe — the machinery behind BENCH_capacity.json's
                weak-scaling-gap decomposition.

Everything here is observation-only: a traced run's trial sequence is
byte-identical to an untraced run's (CI asserts it), spans/metrics never
enter engine snapshots, and trace ids are derived from processed-event
indices so a crash-recovered run re-emits the identical span tree for the
replayed suffix (tests/test_obs.py).
"""

from .accounting import CapacityAccountant  # noqa: F401
from .export import MetricsExporter, prometheus_text  # noqa: F401
from .forensics import ForensicsRecorder  # noqa: F401
from .health import ALERT_KINDS, Alert, HealthMonitor  # noqa: F401
from .metrics import Counter, Gauge, Histogram, MetricsRegistry  # noqa: F401
from .profile import capture  # noqa: F401
from .report import aggregate_spans, write_report  # noqa: F401
from .trace import NULL_TRACER, Tracer  # noqa: F401
