"""Decision-path tracing: nestable spans with deterministic ids.

A :class:`Tracer` records *spans* — named, attributed, monotonic-clock
intervals — arranged in trees by nesting.  The design constraints come from
the control plane it instruments (DESIGN.md §13):

* **Deterministic ids.**  ``trace_id`` is set by the caller (the streaming
  engine uses the processed-event index, ``begin_trace(event_index)``) and
  ``span_id`` counts from 0 *within* each trace.  Ids therefore depend only
  on the code path taken, never on wall clock or randomness — which is what
  lets the crash-anywhere replay oracle assert that a recovered run
  re-emits the identical span tree for the replayed suffix, and what makes
  the trace id threaded into each EventLog processed record a stable
  correlation key.

* **Device-aware timing.**  JAX dispatch is async: the wall time of the
  Python call that *launches* a program says nothing about the program's
  cost.  ``tracer.sync(x)`` calls ``jax.block_until_ready`` when tracing is
  enabled — so the enclosing span measures execution, not dispatch (the
  same primitive ``benchmarks/common.time_us(sync=True)`` uses) — and is a
  pass-through when disabled, preserving the untraced pipeline's async
  behavior exactly.

* **Counts at the same boundaries.**  ``tracer.count(name, n)`` adds ``n``
  to the ``counts`` of the innermost open span's record (or to the
  tracer's root ``counts`` when no span is open).  The program counts
  ``host_syncs`` (blocking device→host readbacks the untraced path makes;
  the tracer's own ``sync`` is not one) and ``h2d_bytes`` / ``d2h_bytes``
  (arrays and Python scalars moved across the boundary, scalars at 4 B).
  Call sites guard with ``if tracer.enabled`` so an untraced run computes
  no byte count.  Counts are not part of ``signature()``.

* **Cheap when off.**  ``span()`` on a disabled tracer returns a shared
  no-op context manager: one branch + one ``with`` per site; ``sync`` and
  ``count`` return at once.  On a TPU v5e, in the benchmark's
  ``lcbench-saturated`` cell (about 10 ms a decision), the untraced
  program ran 98.57 decisions/s (median of three seeds) against 97.86
  for the same program without these sites, a difference inside the
  runs' 2-4% spread; traced, with the profiler, it ran 82.12 (-17%, about
  2 ms more a decision, most of it the syncs that make spans time
  execution).

* **Profiler bridge.**  ``Tracer(profiler=True)`` additionally enters a
  ``jax.profiler.TraceAnnotation`` per span, so host spans land in the
  device profile on the device trace's clock, alongside the
  ``jax.named_scope`` annotations compiled into the sharded decision
  program (``shardgp/score.py``).

Span records are plain dicts (``records()`` / ``to_json(path)``); the
structural view for equality testing is ``signature()`` — (trace, span,
parent, name, attrs) tuples with all timing and counts stripped.
"""

from __future__ import annotations

import json
import time
from pathlib import Path

TRACE_SCHEMA_VERSION = 1

ROOT_TRACE = -1   # trace id of spans opened before any begin_trace()

SCALAR_BYTES = 4  # a Python scalar moved across the device boundary, as counted


def block_ready(x):
    """``jax.block_until_ready`` if jax is importable, else identity — the
    one timing primitive shared by spans and the benchmark harness."""
    try:
        import jax
    except ImportError:      # pragma: no cover - jax is a core dependency
        return x
    return jax.block_until_ready(x)


class _NullSpan:
    """Shared no-op context manager returned by disabled tracers.  One
    instance, no per-call allocation."""

    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


_NULL_SPAN = _NullSpan()


class _Span:
    """One open span; closes (and records itself) on ``__exit__``."""

    __slots__ = ("tracer", "name", "attrs", "trace_id", "span_id",
                 "parent_id", "t0", "counts", "_annotation")

    def __init__(self, tracer: "Tracer", name: str, attrs: dict):
        self.tracer = tracer
        self.name = name
        self.attrs = attrs
        self.counts: dict = {}
        self._annotation = None

    def __enter__(self):
        tr = self.tracer
        self.trace_id = tr._trace_id
        self.span_id = tr._next_span
        tr._next_span += 1
        self.parent_id = tr._stack[-1].span_id if tr._stack else None
        tr._stack.append(self)
        if tr.profiler:
            self._annotation = tr._annotation(self.name)
            if self._annotation is not None:
                self._annotation.__enter__()
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        t1 = time.perf_counter()
        if self._annotation is not None:
            self._annotation.__exit__(*exc)
        tr = self.tracer
        # a crash inside a child may unwind out of order; pop to this span
        while tr._stack and tr._stack[-1] is not self:
            tr._stack.pop()
        if tr._stack:
            tr._stack.pop()
        tr.spans.append({
            "trace": self.trace_id,
            "span": self.span_id,
            "parent": self.parent_id,
            "name": self.name,
            "t0": self.t0,
            "dur_us": (t1 - self.t0) * 1e6,
            "attrs": self.attrs,
            "counts": self.counts,
        })
        return False


class Tracer:
    """Span collector with deterministic ids (module docstring).

    ``enabled=False`` (the engines' default) makes every method a cheap
    no-op; flip at construction, not mid-run — span ids are only meaningful
    for a consistent setting.
    """

    def __init__(self, enabled: bool = True, *, profiler: bool = False):
        self.enabled = enabled
        self.profiler = profiler and enabled
        self.spans: list[dict] = []
        self.counts: dict = {}      # counted while no span was open
        self._trace_id: int = ROOT_TRACE
        self._next_span: int = 0
        self._stack: list[_Span] = []

    # ---- recording ---------------------------------------------------------

    def begin_trace(self, trace_id: int) -> None:
        """Start a new trace: subsequent spans carry ``trace_id`` and span
        ids restart from 0.  The engine calls this with the processed-event
        index, which is what makes replayed suffixes re-emit identical
        ids."""
        if not self.enabled:
            return
        self._trace_id = trace_id
        self._next_span = 0
        self._stack.clear()

    def span(self, name: str, **attrs):
        """Context manager for one span.  Attrs must be deterministic
        (model ids, shard counts, event kinds — never wall-clock values):
        they are part of the replay-equality signature."""
        if not self.enabled:
            return _NULL_SPAN
        return _Span(self, name, attrs)

    def sync(self, x):
        """Block on device work before the enclosing span closes (enabled),
        or pass through untouched (disabled).  Values are identical either
        way — tracing never changes a decision."""
        if self.enabled:
            return block_ready(x)
        return x

    def count(self, name: str, n: int) -> None:
        """Add ``n`` to counter ``name`` of the innermost open span (the
        root ``counts`` when none is open); nothing when disabled."""
        if not self.enabled:
            return
        counts = self._stack[-1].counts if self._stack else self.counts
        counts[name] = counts.get(name, 0) + n

    @property
    def current_trace(self) -> int | None:
        """The trace id stamped into EventLog processed records (None when
        disabled — records keep their untraced 4-field shape)."""
        return self._trace_id if self.enabled else None

    def _annotation(self, name: str):
        try:  # pragma: no cover - exercised only with jax present (always)
            from jax.profiler import TraceAnnotation
        except ImportError:  # pragma: no cover
            return None
        return TraceAnnotation(name)

    # ---- export ------------------------------------------------------------

    def records(self) -> list[dict]:
        """Finished spans, in completion order (children before parents)."""
        return list(self.spans)

    def signature(self, min_trace: int | None = None) -> list[tuple]:
        """Structural view for equality tests: (trace, span, parent, name,
        sorted attr items), timing stripped.  ``min_trace`` keeps only
        traces with id >= it — the replayed-suffix comparison."""
        out = []
        for s in self.spans:
            if min_trace is not None and s["trace"] < min_trace:
                continue
            out.append((s["trace"], s["span"], s["parent"], s["name"],
                        tuple(sorted(s["attrs"].items()))))
        return out

    def to_json(self, path: str | Path) -> Path:
        path = Path(path)
        path.write_text(json.dumps(
            {"schema_version": TRACE_SCHEMA_VERSION, "spans": self.spans},
            indent=2, sort_keys=True, allow_nan=False))
        return path


NULL_TRACER = Tracer(enabled=False)

__all__ = ["Tracer", "NULL_TRACER", "ROOT_TRACE", "SCALAR_BYTES",
           "block_ready", "TRACE_SCHEMA_VERSION"]
