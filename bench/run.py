#!/usr/bin/env python3
"""Run one cell of the chip benchmark and print its result line.

    python3 bench/run.py --workload dense28_b100k --seed 7 --seconds 10 --trace 0

Run from the root of a checkout, on a machine that holds the chips the cell
asks for.  ``--trace 0`` reports the cell's end-to-end metrics;
``--trace 1`` traces the window with ``jax.profiler`` and reports its
per-layer metrics.  The last line of standard output is one JSON object
with ``correct``, ``attempted``, ``failed``, ``metrics`` and ``device``;
the numbers ``correct`` compared, each beside its limit, come last in it
(``checks``) and as the last lines of standard error.  Off a TPU, or with
fewer chips than the cell asks for, it exits non-zero and prints no result.
"""
import time

T_START = time.perf_counter()   # process start, as near as Python gets

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parent.parent


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if args.seed < 0:
        ap.error("--seed is a whole number >= 0")
    if not (ROOT / "src" / "repro").is_dir():
        print(f"bench: no src/repro in {ROOT}: run from a checkout of the "
              "repository", file=sys.stderr)
        return 2
    sys.path.insert(0, str(ROOT))
    from bench.measure import NoChip, run_cell
    from bench.spec import load_cell

    cell = load_cell(args.workload)
    try:
        result = run_cell(cell, args.seed, args.seconds, bool(args.trace),
                          t_start=T_START)
    except NoChip as e:
        print(f"bench: {e}", file=sys.stderr)
        return 3
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
