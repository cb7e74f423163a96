"""The per-layer readers of the spans and counts inside the program, on a
synthetic window of span records."""

import pytest

import tinybench  # noqa: F401  (puts the checkout on sys.path)
from bench.harness import RunView, metric_reader


def _span(trace, span, parent, name, dur_us, **counts):
    return {"trace": trace, "span": span, "parent": parent, "name": name,
            "t0": 0.0, "dur_us": dur_us, "attrs": {}, "counts": counts}


def _decision(trace, fold_us, flush_us, upload_us):
    """One completion's span records, as the program makes them: a fold,
    a decision whose posterior flushes one 2,000-wide block and uploads a
    1,024-slot pool, the pick's readback, and the launch."""
    return [
        _span(trace, 1, 0, "gp_fold", fold_us, h2d_bytes=20),
        _span(trace, 4, 3, "gp_flush", flush_us, host_syncs=2,
              h2d_bytes=4, d2h_bytes=16_000),
        _span(trace, 5, 3, "posterior_upload", upload_us, h2d_bytes=8_192),
        _span(trace, 3, 2, "posterior", flush_us + upload_us + 10),
        _span(trace, 6, 2, "score", 900, host_syncs=2, d2h_bytes=8),
        _span(trace, 2, 0, "decide", flush_us + upload_us + 1_000),
        _span(trace, 7, 0, "launch", 800, h2d_bytes=8),
        _span(trace, 0, None, "event", fold_us + flush_us + upload_us
              + 2_000),
    ]


def _view():
    spans = (_decision(10, 3_000, 1_000, 2_000)
             + _decision(11, 2_000, 1_400, 1_600))
    return RunView(spans=spans, profile=None, compiles_in_window=0)


@pytest.mark.parametrize("metric, expected", [
    ("fold_ms.saturated", 2.5),
    ("flush_ms.saturated", 1.2),
    ("host_syncs.saturated", 4.0),
    ("transfer_kb.saturated", (20 + 4 + 16_000 + 8_192 + 8 + 8) / 1024),
])
def test_reader_of_spans_and_counts(metric, expected):
    assert metric_reader(metric)(_view()) == pytest.approx(expected)


@pytest.mark.parametrize("metric", ["fold_ms.saturated", "flush_ms.saturated",
                                    "host_syncs.saturated",
                                    "transfer_kb.saturated"])
def test_a_program_without_the_spans_or_counts_gives_no_reading(metric):
    # a program that opens only the older spans and counts nothing
    old = [{k: v for k, v in s.items() if k != "counts"}
           for s in _view().spans
           if s["name"] in ("event", "decide", "posterior", "score",
                            "launch")]
    view = RunView(spans=old, profile=None, compiles_in_window=0)
    assert metric_reader(metric)(view) is None
