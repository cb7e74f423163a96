"""The trace reduction: interval arithmetic on synthetic intervals, and the
whole reduction on a short trace recorded on a TPU v5e."""

from pathlib import Path

import pytest

import tinybench  # noqa: F401  (puts the checkout on sys.path)
from bench.trace_reduce import (NO_SPAN, Trace, gaps, label_points,
                                program_name, union_length)

FIXTURE = Path(__file__).parent / "fixtures"


def test_union_of_overlapping_and_nested_intervals():
    iv = [(0, 10), (5, 15), (20, 30), (22, 25), (40, 50)]
    assert union_length(iv, 0, 100) == 15 + 10 + 10
    assert union_length(iv, 8, 45) == 7 + 10 + 5
    assert union_length([], 0, 10) == 0
    assert union_length([(0, 10)], 20, 30) == 0


def test_gaps_are_the_complement_of_the_union():
    iv = [(5, 15), (0, 10), (22, 25), (20, 30)]
    assert gaps(iv, 0, 40) == [(15, 20), (30, 40)]
    assert gaps(iv, -5, 12) == [(-5, 0)]
    assert gaps([], 0, 3) == [(0, 3)]
    lo, hi = 0, 100
    iv = [(3, 9), (12, 40), (35, 60), (80, 81)]
    assert union_length(iv, lo, hi) + sum(e - s for s, e in
                                          gaps(iv, lo, hi)) == hi - lo


def test_idle_points_get_the_innermost_open_span():
    spans = [("event", 0, 100), ("decide", 10, 50), ("score", 30, 45),
             ("gp_fold", 60, 70), ("event", 120, 130)]
    pts = [5, 35, 47, 65, 80, 110, 125]
    assert label_points(spans, pts) == [
        "event", "score", "decide", "gp_fold", "event", NO_SPAN, "event"]
    assert label_points(spans, [125, 5]) == ["event", "event"]


def test_program_names_drop_the_execution_id():
    assert program_name("jit__append_step(17)") == "jit__append_step"
    assert program_name("jit_f") == "jit_f"


def test_reduction_of_a_recorded_chip_trace():
    found = sorted(FIXTURE.glob("*.xplane.pb"))
    if not found:
        pytest.fail("the recorded chip trace is missing")
    r = Trace.from_file(found[0]).reduce(
        ("event", "decide", "posterior", "score", "gp_fold", "launch",
         "pacer_sleep"))
    assert 0 < r["busy_s"] <= r["window_s"]
    assert r["device_ops"] and len(r["device_ops"]) <= 10
    assert r["idle_gaps"] and len(r["idle_gaps"]) <= 10
    idle = sum(v for _, v in r["idle_gaps"])
    assert idle <= r["window_s"] - r["busy_s"] + 1e-9
    assert "jit_choose_next_fused" in r["programs"]
