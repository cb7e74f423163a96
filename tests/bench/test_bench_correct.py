"""A whole run at a small size on the CPU: set-up, window, reference check
and the result line; and the bfloat16 control in the program's place,
which must come out not correct."""

import time

import pytest

from tinybench import tiny_root  # noqa: F401  (a fixture)
from bench.harness import run_cell

CELLS = {"tiny-churn-steady": ("latency_p95_ms", 1.0),
         "tiny-lc-steady": ("latency_p95_ms", 1.0),
         "tiny-lc-saturated": ("decisions_per_s", 0.3)}


def run(root, cell, seed, **kw):
    return run_cell(cell, seed, CELLS[cell][1], False,
                    t_start=time.perf_counter(), require_tpu=False,
                    root=root, **kw)


@pytest.mark.parametrize("cell", sorted(CELLS))
def test_a_sound_run_is_correct_and_reports_its_metrics(tiny_root, cell):
    line, info = run(tiny_root, cell, 2**33 + 1)
    assert line["correct"] is True and line["failed"] == 0
    assert info["checked"] > 0 and line["attempted"] > 0
    assert set(line["metrics"]) == {"setup_s", CELLS[cell][0]}
    assert all(m["value"] > 0 for m in line["metrics"].values())
    assert list(line)[-1] == "checks"
    for name, c in line["checks"].items():
        assert c["value"] <= c["limit"], name


@pytest.mark.parametrize("seed", [3, 4, 5])
def test_the_bfloat16_control_fails_a_limit(tiny_root, seed):
    for cell in sorted(CELLS):
        line, info = run(tiny_root, cell, seed, control=True)
        assert info["program"]["correct"] is True, (cell, info["program"])
        assert line["correct"] is False, (cell, line["checks"])
        assert line["failed"] > 0 or any(
            c["value"] > c["limit"] for c in line["checks"].values())
