"""Reduction of a profiler trace (``.xplane.pb``) to device busy time,
per-program device time and labelled idle gaps.

Read with ``jax.profiler.ProfileData`` alone.  What it relies on:

* device planes are named ``/device:TPU:<n>``; on each, the line ``XLA
  Ops`` holds one event per operation that ran, and ``XLA Modules`` one
  event per program execution, named ``<program>(<id>)``;
* host planes are named ``/host:...``; a ``jax.profiler.TraceAnnotation``
  is an event named after it on the line of the thread that opened it;
* every event's ``start_ns`` is on one clock across planes (offsets from
  the profile's start).

The window is the host event named ``WINDOW`` (the harness opens that
annotation when the measured window starts and closes it at its end).
Busy time is the union of the device operations' intervals inside it,
averaged over the device planes; idle gaps are the holes in that union,
each labelled by the innermost host span open at its midpoint.  Device
operations are named ``<program>:<op>`` after the program execution that
holds them.
"""

from __future__ import annotations

import re
from collections import defaultdict

WINDOW = "bench_window"
NO_SPAN = "(no host span)"
_SUFFIX = re.compile(r"\(\d+\)$")


def program_name(event_name: str) -> str:
    """``jit_choose_next_fused(12)`` -> ``jit_choose_next_fused``."""
    return _SUFFIX.sub("", event_name)


def op_name(event_name: str) -> str:
    """``%fusion.73 = f32[16384]{0} fusion(...)`` -> ``%fusion.73``."""
    return event_name.split(" = ", 1)[0]


def union_length(intervals, lo: float, hi: float) -> float:
    """Length of the union of ``(start, end)`` intervals clipped to
    ``[lo, hi]``."""
    total, cur_s, cur_e = 0.0, None, None
    for s, e in sorted(intervals):
        s, e = max(s, lo), min(e, hi)
        if e <= s:
            continue
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        else:
            cur_e = max(cur_e, e)
    if cur_e is not None:
        total += cur_e - cur_s
    return total


def gaps(intervals, lo: float, hi: float) -> list[tuple[float, float]]:
    """The holes in the union of ``intervals`` inside ``[lo, hi]``."""
    out, at = [], lo
    for s, e in sorted(intervals):
        if e <= at:
            continue
        if s > at:
            out.append((at, min(s, hi)))
        at = max(at, e)
        if at >= hi:
            break
    if at < hi:
        out.append((at, hi))
    return [(s, e) for s, e in out if e > s]


def label_points(spans, points) -> list[str]:
    """For each time in ``points``, the name of the innermost of the
    (properly nested, one-thread) host ``spans`` that covers it."""
    spans = sorted(spans, key=lambda sp: (sp[1], -sp[2]))
    out, stack, i = [NO_SPAN] * len(points), [], 0
    for idx, t in sorted(enumerate(points), key=lambda p: p[1]):
        while i < len(spans) and spans[i][1] <= t:
            while stack and stack[-1][2] <= spans[i][1]:
                stack.pop()
            stack.append(spans[i])
            i += 1
        while stack and stack[-1][2] <= t:
            stack.pop()
        if stack:
            out[idx] = stack[-1][0]
    return out


class Trace:
    """One profile, split into device and host events."""

    def __init__(self, profile):
        self.ops: dict[str, list[tuple[str, float, float]]] = {}
        self.modules: dict[str, list[tuple[str, float, float]]] = {}
        self.host: list[tuple[str, float, float]] = []
        for plane in profile.planes:
            if plane.name.startswith("/device:TPU:"):
                for line in plane.lines:
                    if line.name in ("XLA Ops", "XLA Modules"):
                        dst = self.ops if line.name == "XLA Ops" \
                            else self.modules
                        dst[plane.name] = [
                            (e.name, e.start_ns, e.start_ns + e.duration_ns)
                            for e in line.events]
            elif plane.name.startswith("/host:"):
                for line in plane.lines:
                    self.host.extend(
                        (e.name, e.start_ns, e.start_ns + e.duration_ns)
                        for e in line.events)

    @classmethod
    def from_file(cls, path) -> "Trace":
        from jax.profiler import ProfileData
        return cls(ProfileData.from_file(str(path)))

    def window(self) -> tuple[float, float]:
        hits = [(s, e) for n, s, e in self.host if n == WINDOW]
        if not hits:
            raise ValueError(f"no {WINDOW!r} annotation in the trace")
        return hits[-1]

    def reduce(self, span_names, top: int = 10) -> dict:
        """Busy and idle seconds over the window, each program's device
        time, the operations that took most time and the idle time by the
        host span open during it."""
        lo, hi = self.window()
        if not self.ops:
            raise ValueError("no TPU operations in the trace")
        spans = [(n, s, e) for n, s, e in self.host
                 if n in span_names and e > lo and s < hi]
        busy, op_time, idle = [], defaultdict(float), defaultdict(float)
        for plane, evs in self.ops.items():
            iv = [(s, e) for _, s, e in evs if e > lo and s < hi]
            busy.append(union_length(iv, lo, hi))
            inside = [ev for ev in evs if ev[2] > lo and ev[1] < hi]
            owners = label_points(
                [(program_name(n), s, e)
                 for n, s, e in self.modules.get(plane, [])],
                [(s + e) / 2 for _, s, e in inside])
            for (name, s, e), owner in zip(inside, owners):
                op_time[f"{owner}:{op_name(name)}"] += min(e, hi) - max(s, lo)
            holes = gaps(iv, lo, hi)
            for (s, e), label in zip(holes, label_points(
                    spans, [(s + e) / 2 for s, e in holes])):
                idle[label] += e - s
        programs = defaultdict(list)
        for evs in self.modules.values():
            for name, s, e in evs:
                if s >= lo and e <= hi:
                    programs[program_name(name)].append((e - s) / 1e9)
        n = len(busy)
        return {
            "window_s": (hi - lo) / 1e9,
            "busy_s": sum(busy) / n / 1e9,
            "programs": dict(programs),
            "device_ops": sorted(([k, v / n / 1e9]
                                  for k, v in op_time.items()),
                                 key=lambda kv: -kv[1])[:top],
            "idle_gaps": sorted(([k, v / n / 1e9] for k, v in idle.items()),
                                key=lambda kv: -kv[1])[:top],
        }
