"""Shared benchmark utilities: timing, CSV row emission, engine selection.

Every benchmark prints rows:  name,us_per_call,derived
(one logical row per paper-table entry; `derived` packs the table's
figure-of-merit as `key=value` pairs joined by `;`).

The episode-driven figures (fig2/fig3/fig4/fig5) accept ``--engine
{event,batched}``: ``event`` is the host event loop in
``repro.core.scheduler``; ``batched`` runs the whole sweep as one
``vmap(lax.scan)`` call via ``repro.core.sim_batched`` (DESIGN.md §6).
``--seeds`` overrides the per-figure seed count for either engine
(many-seed batched sweeps are nearly free once the batch is compiled).
"""

from __future__ import annotations

import argparse
import json
import os
import time
from pathlib import Path


FAST = os.environ.get("BENCH_FAST", "0") == "1"

# Version of the BENCH_<suite>.json payload shape.  Bump when the envelope
# changes incompatibly; row keys may grow freely within a version.
#   1: {"schema_version", "git_sha", "suite", "rows": {name: {...}}}
#      (pre-versioned files were the bare rows dict); the optional
#      "environment" stamp (platform/device/fast metadata consumed by
#      benchmarks/regress.py) grew within version 1 — payloads without it
#      are legacy baselines, compared only under --allow-legacy.
BENCH_SCHEMA_VERSION = 1


def set_fast(value: bool = True) -> None:
    """Flip FAST at runtime (benchmarks.run --smoke).  Must run before the
    section modules are imported — they bind ``FAST`` at import time."""
    global FAST
    FAST = value
    os.environ["BENCH_FAST"] = "1" if value else "0"


def git_sha() -> str:
    """Short git SHA of the working tree (env override GIT_SHA for CI
    detached states), or "unknown" outside a repo."""
    sha = os.environ.get("GIT_SHA")
    if sha:
        return sha[:12]
    try:
        import subprocess
        out = subprocess.run(
            ["git", "rev-parse", "--short=12", "HEAD"],
            cwd=Path(__file__).resolve().parent, capture_output=True,
            text=True, timeout=10)
        if out.returncode == 0:
            return out.stdout.strip()
    except Exception:
        pass
    return "unknown"


def environment() -> dict:
    """The measurement environment stamp that rides in every BENCH payload.

    ``benchmarks/regress.py`` matches these fields before diffing two runs:
    timings from different platforms, device kinds/counts, or fast-mode
    settings are apples-to-oranges and must be refused, not averaged.
    A JAX that finds no device raises here: a payload stamped with no
    device would be a timing of nothing anyone can name.
    """
    import platform

    import jax
    devs = jax.devices()
    return {
        "platform": platform.system().lower() or "unknown",
        "machine": platform.machine() or "unknown",
        "python": platform.python_version(),
        "fast": FAST,
        "device_kind": devs[0].device_kind,
        "device_count": len(devs),
    }

# rows of the suite currently being recorded (None = recording disabled);
# benchmarks/run.py brackets each section with begin_suite()/end_suite() so
# the perf trajectory lands in machine-readable BENCH_<suite>.json files
# alongside the human-readable CSV on stdout.
_suite_name: str | None = None
_suite_rows: dict[str, dict] | None = None


def begin_suite(name: str) -> None:
    """Start recording emit() rows under suite ``name``."""
    global _suite_name, _suite_rows
    _suite_name = name
    _suite_rows = {}


def end_suite(out_dir: str | Path = ".") -> Path | None:
    """Write the recorded rows to BENCH_<suite>.json and stop recording.
    Returns the path (None if nothing was recorded).  Every emission is
    stamped with the schema version and the git SHA it was measured at, so
    the committed perf trajectory stays machine-comparable across PRs."""
    global _suite_name, _suite_rows
    name, rows = _suite_name, _suite_rows
    _suite_name = _suite_rows = None
    if name is None or rows is None:
        return None
    payload = {
        "schema_version": BENCH_SCHEMA_VERSION,
        "git_sha": git_sha(),
        "suite": name,
        "environment": environment(),
        "rows": rows,
    }
    path = Path(out_dir) / f"BENCH_{name}.json"
    path.write_text(json.dumps(payload, indent=2, sort_keys=True))
    return path


def abort_suite() -> None:
    """Stop recording WITHOUT writing — a failed section must not clobber
    the committed baseline with partial rows."""
    global _suite_name, _suite_rows
    _suite_name = _suite_rows = None


def positive_int(value: str) -> int:
    iv = int(value)
    if iv < 1:
        raise argparse.ArgumentTypeError(f"must be >= 1, got {iv}")
    return iv


def parse_engine_args(argv=None) -> argparse.Namespace:
    """Parse the shared --engine/--seeds flags.

    Tolerates bare section names (benchmarks.run passes sys.argv through)
    but rejects unknown *flags*, so a typo'd option fails loudly instead of
    silently running the default engine — also when a figure script is run
    directly (``python -m benchmarks.fig5_synthetic_speedup --engine ...``).
    """
    p = argparse.ArgumentParser(
        description="episode-engine selection (shared by fig2-5)")
    p.add_argument("--engine", choices=("event", "batched"), default="event")
    p.add_argument("--seeds", type=positive_int, default=None)
    # handled by benchmarks.run before sections import; accepted here so the
    # flag survives the strict stray-flag check when argv passes through
    p.add_argument("--smoke", action="store_true")
    args, rest = p.parse_known_args(argv)
    if args.smoke and not FAST:
        # standalone figure scripts bind FAST at import, long before this
        # parse — silently running full-size shapes would betray the flag
        p.error("--smoke only takes effect via `python -m benchmarks.run "
                "--smoke`; for a standalone figure script set BENCH_FAST=1")
    stray = [t for t in rest if t.startswith("-")]
    if stray:
        p.error(f"unrecognized arguments: {' '.join(stray)}")
    return args


def emit(name: str, us_per_call: float, **derived) -> None:
    packed = ";".join(f"{k}={v}" for k, v in derived.items())
    print(f"{name},{us_per_call:.1f},{packed}")
    if _suite_rows is not None:
        _suite_rows[name] = {"us_per_call": round(us_per_call, 1),
                             **{k: str(v) for k, v in derived.items()}}


def block_ready(x):
    """``jax.block_until_ready`` with a graceful identity fallback — the one
    device-timing primitive (re-exported from ``repro.obs.trace`` so the
    tracer's span sync and the benchmarks measure the same way)."""
    try:
        from repro.obs.trace import block_ready as _br
    except ImportError:       # benchmarks runnable without src on the path
        try:
            import jax
            return jax.block_until_ready(x)
        except Exception:
            return x
    return _br(x)


def time_us(fn, *args, iters: int = 20, warmup: int = 3, sync: bool = False,
            **kw) -> float:
    """Mean wall time of ``fn(*args, **kw)`` in µs, after ``warmup`` calls.

    Default blocks once after the loop — right for measuring steady-state
    dispatch throughput of an async pipeline.  ``sync=True`` blocks on every
    iteration (and on every warmup call), which is what a *latency* number
    needs: per-call time including execution, the recipe the old per-file
    ``decide_sync`` wrappers duplicated."""
    for _ in range(warmup):
        out = fn(*args, **kw)
        if sync:
            block_ready(out)
    t0 = time.perf_counter()
    if sync:
        for _ in range(iters):
            block_ready(fn(*args, **kw))
    else:
        for _ in range(iters):
            out = fn(*args, **kw)
        block_ready(out)
    return (time.perf_counter() - t0) / iters * 1e6


def timed(fn, *args, **kw):
    """One synced call: ``(seconds, result)``.  For one-shot costs (a
    compaction pass, a snapshot write) where an iteration loop would
    mutate state it shouldn't."""
    t0 = time.perf_counter()
    out = block_ready(fn(*args, **kw))
    return time.perf_counter() - t0, out
