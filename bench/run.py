#!/usr/bin/env python3
"""The chip benchmark of the served GP-EI decision path.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

Runs one cell of ``BENCHMARK.json`` on the machine it is started on: makes
the cell's tenant trace from the seed, replays its prefix unpaced up to the
warm point (set-up), then drives ``StreamEngine.run`` through a window of
``--seconds`` wall seconds paced as the cell's traffic file says, and last
checks the window's decisions and the final posterior against a float64
reference.  The last line of standard output is one JSON object:
``correct``, ``attempted``, ``failed``, ``metrics`` (the cell's end-to-end
metrics, or with ``--trace 1`` its per-layer metrics from a profiled
window), ``device`` and, last, ``checks``: each compared number beside its
limit, which also close standard error.

It exits non-zero, printing no result, where JAX finds no TPU or fewer
chips than the cell asks for, and outside a checkout of the repository.
The compile cache is ``<checkout>/.jax_cache``.
"""

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]


def _finite(x):
    """JSON has no inf or NaN: such a reading is printed as null."""
    return x if not isinstance(x, float) or math.isfinite(x) else None


def main(argv=None) -> int:
    p = argparse.ArgumentParser(
        description=__doc__.split("\n\n")[0],
        formatter_class=argparse.RawDescriptionHelpFormatter)
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = p.parse_args(argv)
    if not (ROOT / "src" / "repro").is_dir():
        print(f"bench: {ROOT} holds no src/repro: run from a checkout of "
              f"the repository", file=sys.stderr)
        return 2
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    from bench.harness import BenchError, prepare_process, run_cell

    prepare_process()
    try:
        with tempfile.TemporaryDirectory() as tmp:
            line, info = run_cell(args.workload, args.seed, args.seconds,
                                  bool(args.trace), t_start=T_START,
                                  scratch=Path(tmp))
    except BenchError as e:
        print(f"bench: {e}", file=sys.stderr)
        return 1
    checks = line["checks"]
    for c in checks.values():
        c["value"] = _finite(c["value"])
    print(json.dumps({k: _finite(v) for k, v in info.items()}),
          file=sys.stderr)
    for name, c in checks.items():
        print(f"check {name} {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
