"""Every cell, configuration, traffic mix and metric is found by name, and
BENCHMARK.json keeps the benchmark's contract."""

import json
import re
import time
from pathlib import Path

import pytest

from tinybench import tiny_root  # noqa: F401  (a fixture)
from bench.harness import (BenchError, cell_metrics, find_cell,
                           load_benchmark, metric_reader, peaks_for,
                           run_cell)

ROOT = Path(__file__).resolve().parents[2]
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


@pytest.fixture(scope="module")
def bench():
    return load_benchmark()


def test_top_level_keys_and_command(bench):
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert bench["command"] == ["python3", "bench/run.py"]
    for p in bench["paths"]:
        assert (ROOT / p).is_dir() and not p.startswith("/") and ".." not in p
    assert 1 <= bench["run_seconds"] <= 51
    assert isinstance(bench["run_seconds"], int)


def test_every_cell_finds_its_config_and_traffic(bench):
    for cell in bench["workloads"]:
        _, cfg, traffic = find_cell(bench, cell["name"])
        assert cfg["name"] == cell["config"]
        assert traffic["pacing"] in ("open_loop", "unpaced")
        assert cell["chips"] in (1, 4)
        assert NAME.match(cell["name"]) and 1 <= len(cell["why"]) <= 200


def test_every_config_is_used_and_lives_under_paths(bench):
    used = {c["config"] for c in bench["workloads"]}
    files = set()
    for cfg in bench["configs"]:
        assert cfg["name"] in used and NAME.match(cfg["name"])
        assert any(cfg["file"].startswith(p + "/") for p in bench["paths"])
        assert (ROOT / cfg["file"]).is_file() and cfg["file"] not in files
        files.add(cfg["file"])
        data = json.loads((ROOT / cfg["file"]).read_text())
        assert data["name"] == cfg["name"]
        for key in cfg["reduced"]:
            assert key in data and NAME.match(key)


def test_metrics_keep_the_contract(bench):
    names = set()
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    assert "setup_s" in e2e and e2e["setup_s"]["bound"] <= 0.25
    for m in bench["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    layers = {}
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert NAME.match(m["name"]) and m["name"] not in names
        names.add(m["name"])
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
        assert m["source"] in SOURCES
    for m in bench["per_layer"]:
        assert m["moves"] in e2e
        for w in m.get("workloads", []):
            # the cell reports the metric it moves
            assert w in e2e[m["moves"]].get("workloads", [w])
        layers.setdefault(m["name"].split(".")[0], m["layer"])
        assert layers[m["name"].split(".")[0]] == m["layer"]
    for cell in bench["workloads"]:
        w = cell["name"]
        assert {"setup_s"} < {m["name"] for m in
                             cell_metrics(bench, w, "end_to_end")}
        assert cell_metrics(bench, w, "per_layer")


def test_every_per_layer_metric_has_a_reader(bench):
    for m in bench["per_layer"]:
        assert callable(metric_reader(m["name"]))


def test_a_config_dropped_into_configs_is_picked_up(tiny_root):
    bench = load_benchmark(tiny_root)
    new = json.loads((tiny_root / "bench/configs/tiny-lc.json")
                     .read_text())
    new["name"] = "dropped-in"
    (tiny_root / "bench/configs/dropped-in.json").write_text(json.dumps(new))
    bench["workloads"].append({"name": "dropped-in.steady",
                               "config": "dropped-in",
                               "traffic": "open-loop", "chips": 1,
                               "why": "a new cell"})
    _, cfg, traffic = find_cell(bench, "dropped-in.steady", tiny_root)
    assert cfg["name"] == "dropped-in" and traffic["pacing"] == "open_loop"


def test_a_grid_config_dropped_into_configs_runs_and_is_correct(tiny_root):
    # a categorical search space (3 tenants x a grid of four 3-level
    # variables, the exponential Hamming kernel) as data files alone
    bench = json.loads((tiny_root / "BENCHMARK.json").read_text())
    grid = json.loads((tiny_root / "bench/configs/tiny-lc.json").read_text())
    grid["name"] = "tiny-grid"
    grid["tenants"].update(
        arrivals={"kind": "at_start", "count": 3},
        candidates={"kind": "fixed", "count": 81},
        space={"points": "grid", "levels": [3, 3, 3, 3]},
        kernel={"kind": "hamming", "length_scale": 1.0, "variance": 0.04})
    (tiny_root / "bench/configs/tiny-grid.json").write_text(json.dumps(grid))
    bench["workloads"].append({"name": "tiny-grid-saturated",
                               "config": "tiny-grid", "traffic": "unpaced",
                               "chips": 1, "why": "a grid cell"})
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "tiny-lc-saturated" in m.get("workloads", []):
            m["workloads"].append("tiny-grid-saturated")
    (tiny_root / "BENCHMARK.json").write_text(json.dumps(bench))

    def run(**kw):
        return run_cell("tiny-grid-saturated", 2**33 + 7, 0.2, False,
                        t_start=time.perf_counter(), require_tpu=False,
                        root=tiny_root, **kw)
    line, info = run()
    assert line["correct"] is True and line["failed"] == 0
    assert info["checked"] > 0 and info["live_models"] == 3 * 81
    assert set(line["metrics"]) == {"setup_s", "decisions_per_s"}
    line, info = run(control=True)
    assert info["program"]["correct"] is True and line["correct"] is False


def test_missing_files_and_unknown_devices_are_errors(tiny_root):
    bench = load_benchmark(tiny_root)
    bench["workloads"].append({"name": "ghost", "config": "nowhere",
                               "traffic": "unpaced", "chips": 1, "why": "x"})
    with pytest.raises(BenchError, match="nowhere"):
        find_cell(bench, "ghost", tiny_root)
    with pytest.raises(BenchError):
        find_cell(bench, "not-a-cell", tiny_root)
    with pytest.raises(BenchError):
        metric_reader("no_such_metric.steady")
    with pytest.raises(BenchError, match="peaks"):
        peaks_for("TPU v99")
    assert peaks_for("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
