"""The readers of the GP's row loops and of W's growth, on synthetic
windows: device time per row folded or read out, the loops' share of
their bandwidth roofline, and growth per decision."""

import pytest

import tinybench  # noqa: F401  (puts the checkout on sys.path)
from bench.harness import RunView, metric_reader

PEAKS = {"hbm_bytes_per_s": 800e9}


def _span(trace, name, dur_us, **counts):
    return {"trace": trace, "span": 0, "parent": None, "name": name,
            "t0": 0.0, "dur_us": dur_us, "attrs": {}, "counts": counts}


def _window(grow_us=(500.0, 700.0)):
    """Two decisions: folds over 10 and 30 rows, readouts over 20 and 40,
    and a doubling of W for each entry of ``grow_us``."""
    spans = [_span(10, "gp_fold", 900, fold_rows=10),
             _span(10, "gp_flush", 300, readout_rows=20),
             _span(10, "decide", 1_000),
             _span(11, "gp_fold", 900, fold_rows=30),
             _span(11, "gp_flush", 300, readout_rows=40),
             _span(11, "decide", 1_000)]
    spans += [_span(11, "gp_grow", us) for us in grow_us]
    profile = {"programs": {"jit__append_step": [1e-6, 3e-6],
                            "jit__readout_into": [2e-6, 4e-6]}}
    # 2 live tenants of 100 candidates each at both decisions
    return RunView(spans=spans, profile=profile, compiles_in_window=0,
                   decide_live=[(200, 2), (200, 2)], peaks=PEAKS)


@pytest.mark.parametrize("metric, expected", [
    ("fold_row_ns.saturated", 4e-6 / 40 * 1e9),
    ("readout_row_ns.saturated", 6e-6 / 60 * 1e9),
    # 100 rows of 100 float32 values at 800 GB/s, over 10 us
    ("gp_roofline.saturated", 100 * (100 * 100 * 4 / 800e9) / 10e-6),
    ("grow_ms.saturated", (500 + 700) / 2 / 1e3),
])
def test_reader_of_row_loops_and_growth(metric, expected):
    assert metric_reader(metric)(_window()) == pytest.approx(expected)


def test_a_window_with_no_doubling_reads_no_growth():
    assert metric_reader("grow_ms.saturated")(_window(grow_us=())) == 0.0


@pytest.mark.parametrize("metric", ["fold_row_ns.saturated",
                                    "readout_row_ns.saturated",
                                    "gp_roofline.saturated",
                                    "grow_ms.saturated"])
def test_a_window_without_row_counts_gives_no_reading(metric):
    # a program that counts no rows (the fold and readout still ran)
    view = _window(grow_us=())
    for s in view.spans:
        s["counts"] = {}
    assert metric_reader(metric)(view) is None
