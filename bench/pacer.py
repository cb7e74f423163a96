"""Open-loop pacing of the engine's own event loop, from outside it.

``StreamEngine`` runs in trace time: each event carries a trace time, and
the engine's decisions depend only on the trace, never on the wall clock.
The benchmark therefore paces the real loop without copying it.  The
engine calls its tracer's ``begin_trace`` once per event, after setting
``engine._t`` to the event's trace time and before handling it;
:class:`PacedTracer` turns that call into the pacing hook.

:class:`Pacer` holds the schedule and the clock arithmetic, free of JAX:

* events before the warm point (trace time ``t_warm``) are set-up and run
  unpaced;
* the first event at or after it opens the window at wall time ``W0``;
* open loop: event ``i`` is due at ``W0 + (t_i - t_warm) / units_per_s``.
  The hook sleeps until then unless the engine is already late; an event
  is in the window when it is due before ``W0 + seconds``.  Every such
  event is handled, however late, and keeps its true latency: from when it
  was due to when the engine finished it, the launch pass it triggered
  included (the moment the next event begins);
* unpaced (``units_per_s=None``): events run back to back, and the window
  closes at the first event that begins after ``W0 + seconds``.

The first event outside the window raises :class:`WindowClosed` from
inside the hook, which ends ``engine.run``.
"""

from __future__ import annotations

import math
import time

from repro.obs import Tracer


class WindowClosed(Exception):
    """Raised from the pacing hook at the first event outside the window."""


def nearest_rank(values, q: float) -> float:
    """The ``q``-quantile (0 < q <= 1) by nearest rank: the smallest value
    with at least ``q`` of the samples at or below it."""
    xs = sorted(values)
    if not xs:
        raise ValueError("no samples")
    return xs[max(0, math.ceil(q * len(xs)) - 1)]


class Pacer:
    """The window's schedule and per-event timestamps (module docstring)."""

    def __init__(self, *, t_warm: float, seconds: float,
                 units_per_s: float | None, clock=time.perf_counter,
                 sleep=time.sleep, on_open=None, probe=None):
        if seconds <= 0:
            raise ValueError(f"seconds must be positive, got {seconds}")
        if units_per_s is not None and units_per_s <= 0:
            raise ValueError(f"units_per_s must be positive, got "
                             f"{units_per_s}")
        self.t_warm = t_warm
        self.seconds = seconds
        self.units_per_s = units_per_s
        self.clock = clock
        self.sleep = sleep
        self.on_open = on_open
        self.probe = probe
        self.window_start: float | None = None
        self.window_end: float | None = None
        self.due: list[float] = []
        self.begin: list[float] = []
        self.end: list[float] = []
        self.times: list[float] = []
        self.overslept = 0.0        # longest wake-up past a due time
        # ``probe()`` readings as each window event begins and ends (a
        # diagnostic of where a slow event's wall time went)
        self.probe_begin: list = []
        self.probe_end: list = []

    @property
    def open(self) -> bool:
        return self.window_start is not None and self.window_end is None

    def due_at(self, t: float) -> float:
        """Wall time at which the event of trace time ``t`` is due."""
        return self.window_start + (t - self.t_warm) / self.units_per_s

    def on_event(self, t: float) -> None:
        """Called as the event of trace time ``t`` begins."""
        now = self.clock()
        if self.window_end is not None:
            raise WindowClosed
        if self.window_start is None:
            if t < self.t_warm:
                return
            if self.on_open is not None:
                self.on_open()
            now = self.window_start = self.clock()
        else:
            self.end.append(now)        # the previous event is finished
            if self.probe is not None:
                self.probe_end.append(self.probe())
        limit = self.window_start + self.seconds
        if self.units_per_s is None:
            due = now
            outside = now >= limit
        else:
            due = self.due_at(t)
            outside = due >= limit
        if outside:
            self.window_end = now
            raise WindowClosed
        if due > now:
            self.sleep(due - now)
            now = self.clock()
            self.overslept = max(self.overslept, now - due)
        self.due.append(due)
        self.begin.append(now)
        self.times.append(t)
        if self.probe is not None:
            self.probe_begin.append(self.probe())

    def close(self) -> None:
        """End the window where the run itself ended (the trace ran out)."""
        if self.open:
            self.window_end = self.clock()
            if len(self.end) < len(self.due):
                self.end.append(self.window_end)

    @property
    def window_s(self) -> float:
        return self.window_end - self.window_start

    def latencies(self) -> list[float]:
        """Seconds from due to finished, for every event of the window."""
        return [e - d for d, e in zip(self.due, self.end)]

    def lateness(self) -> list[float]:
        """Seconds each event began after it was due (the generator's own
        delay plus any queue ahead of the event)."""
        return [b - d for d, b in zip(self.due, self.begin)]


class PacedTracer(Tracer):
    """The engine's tracer, with the pacing hook in ``begin_trace``.

    Besides pacing, it counts the ``launch`` spans the engine opens and
    notes, per event, how many launches came before it: that maps each
    trial to the event whose launch pass made it, which the reference
    replay needs.  ``on_decide`` (traced runs) is called as each policy
    decision inside the window begins.  With ``enabled=False`` no span is
    recorded and nothing else changes on the engine's path."""

    def __init__(self, pacer: Pacer, *, enabled: bool = False,
                 profiler: bool = False, on_decide=None):
        super().__init__(enabled, profiler=profiler)
        self.pacer = pacer
        self.on_decide = on_decide
        self.engine = None
        self.launches_before: list[int] = []   # per processed event
        self.launches = 0
        self._window_spans_only = False

    def begin_trace(self, trace_id: int) -> None:
        self.launches_before.append(self.launches)
        self.pacer.on_event(self.engine._t)
        if self.pacer.window_start is not None \
                and not self._window_spans_only:
            self.spans.clear()          # keep only the window's spans
            self._window_spans_only = True
        super().begin_trace(trace_id)

    def span(self, name: str, **attrs):
        if name == "launch":
            self.launches += 1
        elif name == "decide" and self.on_decide is not None \
                and self.pacer.open:
            self.on_decide()
        return super().span(name, **attrs)
