#!/usr/bin/env python3
"""Read the two readings each correctness limit is set from, on the chip.

    python3 bench/calibrate.py --workloads dense28_b100k,afiro_b10k \
        --seeds 12 --control-seeds 3 --seconds 3 --out chiprun_out/calib.jsonl

For every cell, in one process: the sound program on ``--seeds`` seeds
(the lower reading of each number is the largest they give) and the
control, the program computed in bfloat16 through its entry point's
``dtype``, on ``--control-seeds`` seeds (the upper reading is the
smallest).  Each reading is a whole run at the cell's own size and load,
with a short window, checked as a benchmark run checks it.  The
benchmark's own runs never run this.  One JSON line per run goes to
``--out``; a summary per cell and number to standard output.
"""
import argparse
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workloads", required=True)
    ap.add_argument("--seeds", type=int, default=12)
    ap.add_argument("--control-seeds", type=int, default=3)
    ap.add_argument("--first-seed", type=int, default=3_000_000_000)
    ap.add_argument("--seconds", type=float, default=3.0)
    ap.add_argument("--out", required=True)
    args = ap.parse_args(argv)
    sys.path.insert(0, str(ROOT))
    import functools

    import jax.numpy as jnp

    from bench import program
    from bench.measure import run_cell
    from bench.spec import load_cell

    out = open(args.out, "a")
    for name in args.workloads.split(","):
        cell = load_cell(name)
        readings = {"sound": {}, "control": {}}
        control = functools.partial(program.entry, dtype=jnp.bfloat16)
        plan = [("sound", args.first_seed + k, None) for k in range(args.seeds)]
        plan += [("control", args.first_seed + 1000 + k, control)
                 for k in range(args.control_seeds)]
        for mode, seed, entry in plan:
            t0 = time.perf_counter()
            r = run_cell(cell, seed, args.seconds, False, t_start=t0,
                         entry=entry, log=lambda msg: None)
            line = {"cell": name, "mode": mode, "seed": seed,
                    "correct": r["correct"], "attempted": r["attempted"],
                    "failed": r["failed"], "checks": r["checks"],
                    "seconds": time.perf_counter() - t0}
            out.write(json.dumps(line) + "\n")
            out.flush()
            print(json.dumps(line), flush=True)
            for k, v in r["checks"].items():
                readings[mode].setdefault(k, []).append(
                    float("inf") if v["value"] is None else v["value"])
        for k in readings["sound"]:
            lower = max(readings["sound"][k])
            upper = min(readings["control"].get(k, [float("nan")]))
            print(f"{name} {k}: lower {lower!r} (max of "
                  f"{len(readings['sound'][k])}) upper {upper!r} (min of "
                  f"{len(readings['control'].get(k, []))}) ratio "
                  f"{upper / lower if lower else float('inf'):.3g}",
                  flush=True)
    out.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
