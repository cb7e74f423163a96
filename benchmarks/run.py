"""Benchmark driver: one function per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows (``derived`` packs each table's
figure-of-merit as ``key=value`` pairs joined by ``;``).

  PYTHONPATH=src python -m benchmarks.run [section ...] [--engine ENGINE]

Sections (default: all):
  fig2      single-device policy comparison, Azure + DeepLearning
  fig3      device-count sweep for MM-GP-EI
  fig4      policy comparison on four devices
  fig5      synthetic Matérn near-linear-speedup sweep
  control   control-plane microbenchmarks (GP/EI hot path)
  stream    streaming control plane under tenant churn (stream_churn)
  shard     sharded scoring plane: decision latency vs |L| x mesh size
            (shard_scale; multi-shard rows need forced host devices, e.g.
            XLA_FLAGS=--xla_force_host_platform_device_count=4)
  devchurn  elastic device plane: batched vs sequential assignment cost,
            device-aware vs speed-oblivious regret, autoscale (device_churn)
  eventlog  event-sourced durability: incremental vs full compaction pause,
            snapshot/restore/log-append cost (eventlog, DESIGN.md §12)
  dtrace    span-level cost attribution of one sharded decision + the
            disabled-tracer overhead bar (decision_trace, DESIGN.md §13;
            multi-shard rows need forced host devices)
  obs       live health plane: the all-planes-disabled per-event site
            stack as a share of a decision (< 1% bar) + per-plane enabled
            costs — export tick, health detectors, forensics record
            (obs_overhead, DESIGN.md §14)
  capacity  capacity plane: weak-scaling-gap decomposition into per-shard
            skew / all_gather / dispatch (>= 80% attributed bar at S=8),
            per-device skew probe, accounting-sample cost (capacity,
            DESIGN.md §15; multi-shard rows need forced host devices)
  chaos     failure-domain hardening: hardened engine vs failure-free twin
            regret bound + unsupervised stranding baseline (chaos,
            DESIGN.md §16)
  roofline  data-plane cost-model rooflines

Each section also records its rows to a machine-readable
``BENCH_<suite>.json`` (e.g. BENCH_control_plane.json,
BENCH_stream_churn.json) in the working directory — the committed perf
trajectory baseline.

Flags (forwarded to the figure scripts):
  --engine {event,batched}   episode engine for fig2-5.  ``event`` is the
                             host event loop (one episode at a time);
                             ``batched`` runs whole sweeps as a single
                             vmap(lax.scan) call via repro.core.sim_batched.
  --seeds S                  widen batched sweeps (fig5 many-seed mode).

Set BENCH_FAST=1 for a quick pass (fewer seeds/device counts).
"""

from __future__ import annotations

import argparse
import sys
import traceback
from pathlib import Path

from . import common
from .common import positive_int

SECTIONS = ("fig2", "fig3", "fig4", "fig5", "control", "stream", "shard",
            "devchurn", "eventlog", "dtrace", "obs", "capacity", "chaos",
            "roofline")

# section -> BENCH_<suite>.json written next to the CSV (perf trajectory)
SUITE_NAMES = {
    "fig2": "fig2", "fig3": "fig3", "fig4": "fig4", "fig5": "fig5",
    "control": "control_plane", "stream": "stream_churn",
    "shard": "shard_scale", "devchurn": "device_churn",
    "eventlog": "eventlog", "dtrace": "decision_trace",
    "obs": "obs_overhead", "capacity": "capacity", "chaos": "chaos",
    "roofline": "roofline",
}


def _parse_args():
    p = argparse.ArgumentParser(
        prog="python -m benchmarks.run",
        description=__doc__,
        formatter_class=argparse.RawDescriptionHelpFormatter)
    p.add_argument("sections", nargs="*", metavar="section",
                   help=f"benchmark sections to run: {', '.join(SECTIONS)} "
                        "(default: all)")
    p.add_argument("--engine", choices=("event", "batched"), default="event",
                   help="episode engine for fig2-5 (default: event)")
    p.add_argument("--seeds", type=positive_int, default=None,
                   help="seeds per configuration for fig2-5")
    p.add_argument("--smoke", action="store_true",
                   help="toy shapes for every suite (sets BENCH_FAST=1 "
                        "before section import) — the CI smoke job")
    # strict parse: run.py declares every flag the figure scripts accept, so
    # a typo'd flag fails loudly here instead of silently running defaults
    args = p.parse_args()
    bad = [s for s in args.sections if s not in SECTIONS]
    if bad:
        p.error(f"unknown section(s) {bad}; choose from {', '.join(SECTIONS)}")
    return args


def main() -> None:
    args = _parse_args()
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache(Path(__file__).resolve().parents[1])
    if args.smoke:
        # must precede the lazy section imports: they bind common.FAST then
        common.set_fast(True)
    want = list(args.sections) or list(SECTIONS)
    print("name,us_per_call,derived")
    failures = []
    for section in want:
        try:
            if section == "fig2":
                from . import fig2_single_device as m
            elif section == "fig3":
                from . import fig3_multi_device as m
            elif section == "fig4":
                from . import fig4_four_devices as m
            elif section == "fig5":
                from . import fig5_synthetic_speedup as m
            elif section == "control":
                from . import control_plane as m
            elif section == "stream":
                from . import stream_churn as m
            elif section == "shard":
                from . import shard_scale as m
            elif section == "devchurn":
                from . import device_churn as m
            elif section == "eventlog":
                from . import eventlog as m
            elif section == "dtrace":
                from . import decision_trace as m
            elif section == "obs":
                from . import obs_overhead as m
            elif section == "capacity":
                from . import capacity as m
            elif section == "chaos":
                from . import chaos as m
            elif section == "roofline":
                from . import roofline as m
            else:
                raise KeyError(section)
            common.begin_suite(SUITE_NAMES[section])
            m.main()
            path = common.end_suite()
            if path is not None:
                print(f"# wrote {path}", file=sys.stderr)
        except Exception:
            common.abort_suite()   # partial rows must not clobber baselines
            failures.append(section)
            traceback.print_exc()
    if failures:
        raise SystemExit(f"benchmark sections failed: {failures}")


if __name__ == "__main__":
    main()
