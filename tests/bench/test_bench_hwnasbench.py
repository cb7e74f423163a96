"""The ``hwnasbench-18x15625`` deployment: its files are found by name, and
a tiny copy of it (18 tenants over one small grid) runs through the
harness to ``correct`` true, with W growing inside the window."""

import json
import time
from pathlib import Path

import pytest

from tinybench import tiny_root  # noqa: F401  (a fixture)
import repro.core.gp as gp
from bench.harness import (cell_metrics, find_cell, load_benchmark,
                           metric_reader, run_cell)

ROOT = Path(__file__).resolve().parents[2]
CELL, CONFIG = "hwnasbench-saturated", "hwnasbench-18x15625"


def test_the_configuration_its_cell_and_readers_are_found():
    bench = load_benchmark()
    cell, cfg, traffic = find_cell(bench, CELL)
    assert cell["config"] == CONFIG and cell["chips"] == 1
    assert traffic["pacing"] == "unpaced"
    entry = {c["name"]: c for c in bench["configs"]}[CONFIG]
    assert entry["reduced"] == []
    assert "2103.10584" in cfg["source"] and "2001.00326" in cfg["source"]
    t = cfg["tenants"]
    assert t["arrivals"] == {"kind": "at_start", "count": 18}
    assert t["sessions"] == {"kind": "none"}
    assert t["space"] == {"points": "grid", "levels": [5] * 6}
    assert t["candidates"] == {"kind": "fixed", "count": 5 ** 6}
    assert t["kernel"]["kind"] == "hamming"
    e2e = {m["name"] for m in cell_metrics(bench, CELL, "end_to_end")}
    assert e2e == {"decisions_per_s", "setup_s"}
    layer = {m["name"] for m in cell_metrics(bench, CELL, "per_layer")}
    assert {"fold_row_ns.saturated", "readout_row_ns.saturated",
            "gp_roofline.saturated", "grow_ms.saturated"} <= layer
    assert layer == {m["name"] for m in cell_metrics(
        bench, "lcbench-saturated", "per_layer")}
    for name in layer:
        assert callable(metric_reader(name))


@pytest.mark.parametrize("step", [gp.ROW_STEP, 4])
def test_a_tiny_copy_runs_unpaced_and_is_correct(tiny_root, monkeypatch,
                                                 step):
    # 18 tenants over a grid of four 3-level variables (81 candidates, one
    # prior object); at a row step of 4, W grows from 12 rows to 20, 36
    # and 68 as the tenants observe
    monkeypatch.setattr(gp, "ROW_STEP", step)
    bench = json.loads((tiny_root / "BENCHMARK.json").read_text())
    cfg = json.loads((ROOT / f"bench/configs/{CONFIG}.json").read_text())
    cfg["name"] = "tiny-hwnas"
    cfg["tenants"].update(candidates={"kind": "fixed", "count": 81},
                          space={"points": "grid", "levels": [3, 3, 3, 3]})
    cfg["warm_until"] = 2.0
    (tiny_root / "bench/configs/tiny-hwnas.json").write_text(json.dumps(cfg))
    bench["workloads"].append({"name": "tiny-hwnas-saturated",
                               "config": "tiny-hwnas", "traffic": "unpaced",
                               "chips": 1, "why": "a tiny copy"})
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "tiny-lc-saturated" in m.get("workloads", []):
            m["workloads"].append("tiny-hwnas-saturated")
    (tiny_root / "BENCHMARK.json").write_text(json.dumps(bench))
    line, info = run_cell("tiny-hwnas-saturated", 2**33 + 17, 0.5, False,
                          t_start=time.perf_counter(), require_tpu=False,
                          root=tiny_root)
    assert line["correct"] is True and line["failed"] == 0
    assert info["checked"] > 0 and info["live_models"] == 18 * 81
    assert info["compiles_in_window"] == 0
    assert set(line["metrics"]) == {"setup_s", "decisions_per_s"}
