#!/usr/bin/env python3
"""Readings that the limits of ``correct`` are set from.

    python3 bench/calibrate.py --workload <cell> --seeds 1,2,3 --seconds <s>
    python3 bench/calibrate.py --workload <cell> --seeds 1 --seconds <s> \
        --events-per-s 60,80,100 --no-control        # the knee sweep

Runs the cell once per seed in one process (set-up, window, reference
check, as ``bench/run.py`` does) and also puts the bfloat16 control in
the program's place on the same decisions: one JSON line per seed with
the run's verdict and numbers (``pick_gap``, ``posterior_err``) and the
control's (``control_*``), then a summary line with the largest run
reading and the smallest control reading of each, and how many runs and
controls came out correct.  Like the benchmark, it refuses to run without
a TPU.
"""

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seeds", required=True,
                   help="comma-separated seeds")
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--events-per-s", default=None,
                   help="comma-separated open-loop rates, each run in "
                        "place of the cell's own traffic (the knee sweep)")
    p.add_argument("--no-control", action="store_true")
    args = p.parse_args(argv)
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    from bench.harness import prepare_process, run_cell

    prepare_process()
    rates = ([None] if args.events_per_s is None else
             [float(r) for r in args.events_per_s.split(",")])
    runs = [(int(s), r) for r in rates for s in args.seeds.split(",")]
    rows = []
    t0 = T_START
    for seed, rate in runs:
        traffic = (None if rate is None else
                   {"pacing": "open_loop", "events_per_s": rate})
        line, info = run_cell(args.workload, seed, args.seconds, False,
                              t_start=t0, control=not args.no_control,
                              traffic=traffic)
        own = info.pop("program", line)
        row = {"seed": seed, "events_per_s": rate,
               "run_wall_s": time.perf_counter() - t0,
               "correct": own["correct"],
               **{k: c["value"] for k, c in own["checks"].items()},
               **{k: v["value"] for k, v in line["metrics"].items()},
               **info}
        if not args.no_control:
            row["control_correct"] = line["correct"]
            row.update({f"control_{k}": c["value"]
                        for k, c in line["checks"].items()})
        rows.append(row)
        print(json.dumps(row), flush=True)
        gc.collect()
        t0 = time.perf_counter()
    summary = {"seeds": len(rows),
               "correct": sum(r["correct"] for r in rows),
               "pick_gap_max": max(r["pick_gap"] for r in rows),
               "posterior_err_max": max(r["posterior_err"] for r in rows)}
    if not args.no_control:
        summary["control_correct"] = sum(r["control_correct"] for r in rows)
        summary["control_pick_gap_min"] = min(r["control_pick_gap"]
                                              for r in rows)
        summary["control_posterior_err_min"] = min(
            r["control_posterior_err"] for r in rows)
    print(json.dumps(summary), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
