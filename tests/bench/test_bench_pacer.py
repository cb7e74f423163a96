"""The pacer's schedule, latencies and tail against a fake clock."""

import pytest

import tinybench  # noqa: F401  (puts the checkout on sys.path)
from bench.pacer import Pacer, WindowClosed, nearest_rank


class FakeClock:
    """A clock that moves only when the engine works or the pacer sleeps."""

    def __init__(self):
        self.now = 100.0
        self.slept = []

    def __call__(self):
        return self.now

    def sleep(self, s):
        self.slept.append(s)
        self.now += s


def drive(pacer, clock, events):
    """Feed (trace time, seconds of work) pairs; return the events that
    were handled before the window closed."""
    handled = []
    for t, work in events:
        try:
            pacer.on_event(t)
        except WindowClosed:
            return handled
        handled.append(t)
        clock.now += work
    pacer.close()
    return handled


def test_warm_prefix_is_unpaced_and_opens_the_window_at_t_warm():
    clock = FakeClock()
    opened = []
    p = Pacer(t_warm=10.0, seconds=5.0, units_per_s=2.0, clock=clock,
              sleep=clock.sleep, on_open=lambda: opened.append(clock.now))
    drive(p, clock, [(1.0, 0.5), (9.0, 0.5), (10.0, 0.1)])
    assert opened == [101.0] and p.window_start == 101.0
    assert p.due == [101.0] and not clock.slept


def test_open_loop_due_times_sleep_and_latency():
    clock = FakeClock()
    p = Pacer(t_warm=0.0, seconds=10.0, units_per_s=2.0, clock=clock,
              sleep=clock.sleep)
    # due at W0 + t/2: 100, 101, 102, 102.5; the third event's work makes
    # the fourth late
    handled = drive(p, clock, [(0.0, 0.25), (2.0, 0.25), (4.0, 1.0),
                               (5.0, 0.5), (30.0, 0.0)])
    assert handled == [0.0, 2.0, 4.0, 5.0]
    assert p.due == [100.0, 101.0, 102.0, 102.5]
    assert p.begin == [100.0, 101.0, 102.0, 103.0]
    assert clock.slept == [0.75, 0.75]
    assert p.latencies() == [0.25, 0.25, 1.0, 1.0]
    assert p.lateness() == [0.0, 0.0, 0.0, 0.5]
    # t = 30 is due at 115, past the window's end at 110: it closes there
    assert p.window_end == 103.5 and p.window_s == 3.5


def test_late_events_inside_the_window_keep_their_true_latency():
    clock = FakeClock()
    p = Pacer(t_warm=0.0, seconds=2.0, units_per_s=1.0, clock=clock,
              sleep=clock.sleep)
    # the first event takes 5 s: the next three were all due inside the
    # 2 s window and are handled after it, none dropped
    handled = drive(p, clock, [(0.0, 5.0), (0.5, 0.1), (1.0, 0.1),
                               (1.5, 0.1), (2.5, 0.0)])
    assert handled == [0.0, 0.5, 1.0, 1.5]
    assert p.latencies() == pytest.approx([5.0, 4.6, 4.2, 3.8])
    assert not clock.slept


def test_unpaced_window_runs_back_to_back_and_closes_on_the_clock():
    clock = FakeClock()
    p = Pacer(t_warm=1.0, seconds=1.0, units_per_s=None, clock=clock,
              sleep=clock.sleep)
    handled = drive(p, clock, [(0.5, 0.3)] + [(1.0 + i, 0.3)
                                             for i in range(10)])
    assert handled == [0.5, 1.0, 2.0, 3.0, 4.0]
    assert not clock.slept
    assert p.window_s == pytest.approx(1.2)


def test_p95_is_over_every_event():
    lat = list(range(1, 101))            # 1..100
    assert nearest_rank(lat, 0.95) == 95
    assert nearest_rank([3.0], 0.95) == 3.0
    assert nearest_rank(list(range(20)), 0.95) == 18
    with pytest.raises(ValueError):
        nearest_rank([], 0.95)


def test_trace_running_out_closes_the_window_with_the_last_event():
    clock = FakeClock()
    p = Pacer(t_warm=0.0, seconds=100.0, units_per_s=1.0, clock=clock,
              sleep=clock.sleep)
    drive(p, clock, [(0.0, 0.5), (1.0, 0.5)])
    assert p.latencies() == [0.5, 0.5] and p.window_end == 101.5
