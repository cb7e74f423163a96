"""Shared helpers of the CPU tests of ``bench/``.

Importing this module puts the checkout (for ``bench``) and ``src`` (for
``repro``) on ``sys.path``; the test modules import it first.  It is not a
``conftest.py``: a second module of that name would shadow
``tests/conftest.py`` for the tests that import helpers from it.

``tiny_root`` is a temporary checkout-shaped directory: a BENCHMARK.json
whose cells use the real metric readers and peaks table but a small copy
of ``lcbench-35x2000``, open loop and unpaced, and a small churn
deployment (tenants that arrive and depart, the generator's Poisson,
Pareto and Zipf path), so a whole run (set-up, window, reference check)
takes seconds on the CPU.
"""

import json
import shutil
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
for _p in (str(ROOT / "src"), str(ROOT)):
    if _p not in sys.path:
        sys.path.insert(0, _p)


#: a small churn deployment: Poisson arrivals, Pareto sessions, Zipf
#: candidate sets on an even grid of [0, 1], unit costs
TINY_CHURN = {
    "name": "tiny-churn",
    "precision": "float32",
    "tenants": {
        "structure_seed": 1,
        "arrivals": {"kind": "poisson", "rate": 2.0, "count": 200},
        "sessions": {"kind": "pareto", "alpha": 1.5, "scale": 10.0},
        "candidates": {"kind": "zipf", "s": 1.6, "min": 2, "max": 16},
        "space": {"dims": 1, "points": "linspace"},
        "kernel": {"kind": "matern52", "length_scale": 0.2,
                   "variance": 0.04},
        "cost": {"kind": "uniform"}},
    "fleet": {"total_chips": 32, "slices": 8},
    "warm_start": 2,
    "gp_jitter": 1e-06,
    "warm_until": 10.0,
    "events_per_unit": 12.0,
    "verify": {"limits": {"pick_gap": 0.005, "posterior_err": 0.002}},
}

#: cell -> (config, traffic); the suffix names the end-to-end metric
TINY_CELLS = {"tiny-churn-steady": ("tiny-churn", "open-loop"),
              "tiny-lc-steady": ("tiny-lc", "open-loop"),
              "tiny-lc-saturated": ("tiny-lc", "unpaced")}


def _tiny_configs() -> dict:
    lc = json.loads((ROOT / "bench/configs/lcbench-35x2000.json").read_text())
    lc["tenants"]["arrivals"]["count"] = 4
    lc["tenants"]["candidates"]["count"] = 300
    lc.update(fleet={"total_chips": 16, "slices": 4}, warm_until=2.0)
    return {"tiny-churn": TINY_CHURN, "tiny-lc": lc}


def make_tiny_root(dest: Path) -> Path:
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    (dest / "bench/configs").mkdir(parents=True)
    (dest / "bench/traffic").mkdir(parents=True)
    shutil.copytree(ROOT / "bench/metrics", dest / "bench/metrics")
    shutil.copy(ROOT / "bench/peaks.json", dest / "bench/peaks.json")
    for name, cfg in _tiny_configs().items():
        (dest / f"bench/configs/{name}.json").write_text(json.dumps(cfg))
    (dest / "bench/traffic/open-loop.json").write_text(json.dumps(
        {"pacing": "open_loop", "events_per_s": 400.0}))
    (dest / "bench/traffic/unpaced.json").write_text(json.dumps(
        {"pacing": "unpaced"}))
    bench["workloads"] = [
        {"name": name, "config": cfg, "traffic": traffic, "chips": 1,
         "why": "a small cell"}
        for name, (cfg, traffic) in TINY_CELLS.items()]
    if "latency_p95_ms" not in {m["name"] for m in bench["end_to_end"]}:
        bench["end_to_end"].append(
            {"name": "latency_p95_ms", "unit": "ms", "better": "lower",
             "bound": 0.25, "source": "host_clock", "workloads": []})
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "workloads" in m:
            suffix = ("saturated" if m.get("moves", m["name"])
                      == "decisions_per_s" else "steady")
            m["workloads"] = [w for w in TINY_CELLS if w.endswith(suffix)]
    (dest / "BENCHMARK.json").write_text(json.dumps(bench))
    return dest


@pytest.fixture
def tiny_root(tmp_path):
    return make_tiny_root(tmp_path / "root")
