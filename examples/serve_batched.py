"""Batched LP solve service, minimal loop: a stream of perturbed fixture
batches solved with the telemetry plane on, reported as a per-wave
p50/p99 latency + solves/sec table derived from each wave's SolveReport.

    PYTHONPATH=src python examples/serve_batched.py [--fixture afiro]
        [--waves 4] [--batch 16] [--backend tableau] [--trace DIR]

This is the first concrete step on the ROADMAP item "Streaming solve
service: continuous batching over shape classes".  What exists here: a
synchronous wave loop over one shape class — each wave is a perturbed
re-solve of the fixture (the MPC/branch-and-bound repeated-solve
workload), warm-started from the previous wave's terminal state, solved
through the compaction scheduler with telemetry on, and summarized from
``LPResult.stats`` (``repro.obs.SolveReport``).  Still unbuilt: the async
admission loop (``FrontierScheduler``'s source/sink protocol is the
intended API), heterogeneous shape-class bucketing, and a Poisson load
generator.

``--trace DIR`` additionally re-solves the last wave through the
compaction scheduler under ``jax.profiler`` and writes the trace under
``DIR``, with a ``perfetto_trace.json.gz`` beside it: the library's
``lp.*`` spans (``lp.canonicalize`` -> ``lp.dispatch`` -> ``lp.segment[..]``
-> ``lp.bucket_gather`` -> ``lp.recover``) and the device's ops on one
timeline — load it at https://ui.perfetto.dev.
"""
from __future__ import annotations

import argparse

import glob
import os

import jax
import numpy as np

from repro.core import OPTIMAL, solve_batched, solve_batched_compacted
from repro.io.mps import fixture_path, perturbed_sequence, read_mps


def serve(fixture: str = "afiro", waves: int = 4, batch: int = 16,
          backend: str = "tableau", trace: str | None = None,
          seed: int = 0) -> list:
    g = read_mps(fixture_path(fixture))
    stream = perturbed_sequence(g, batch, waves, np.random.default_rng(seed))
    print(f"serving {waves} waves of {batch} perturbed {fixture!r} LPs "
          f"({g.m}x{g.n}) on the {backend!r} engine\n")
    header = (f"{'wave':>4}  {'B':>4}  {'optimal':>7}  {'iters p50':>9}  "
              f"{'iters p99':>9}  {'lat p50':>9}  {'lat p99':>9}  "
              f"{'solves/s':>8}")
    print(header)
    print("-" * len(header))
    rows = []
    warm = None
    for k, gb in enumerate(stream):
        # monolithic chunked driver: captures terminal state, so each wave
        # warm-starts from the previous one (the repeated-solve win)
        res = solve_batched(gb, backend=backend, warm=warm, telemetry=True)
        warm = res.warm
        rep = res.stats
        # per-LP latency model: the wave's wall-clock prorated by each LP's
        # share of the executed iterations (lockstep lanes finish together;
        # what differs per LP is how much work it contributed)
        iters = rep.iterations.astype(np.float64)
        if iters.sum() > 0:
            lat = rep.wall_s * iters / iters.sum()
        else:  # warm starts can re-solve the whole wave in zero pivots
            lat = np.full_like(iters, rep.wall_s / max(len(iters), 1))
        row = {
            "wave": k, "B": rep.batch_size,
            "optimal": int((np.asarray(res.status) == OPTIMAL).sum()),
            "iters_p50": float(np.percentile(iters, 50)),
            "iters_p99": float(np.percentile(iters, 99)),
            "latency_p50_s": float(np.percentile(lat, 50)),
            "latency_p99_s": float(np.percentile(lat, 99)),
            "solves_per_sec": rep.summary().get("solves_per_sec", 0.0),
        }
        rows.append(row)
        print(f"{row['wave']:>4}  {row['B']:>4}  {row['optimal']:>7}  "
              f"{row['iters_p50']:>9.0f}  {row['iters_p99']:>9.0f}  "
              f"{row['latency_p50_s'] * 1e3:>7.2f}ms  "
              f"{row['latency_p99_s'] * 1e3:>7.2f}ms  "
              f"{row['solves_per_sec']:>8.1f}")
    total_lps = sum(r["B"] for r in rows)
    total_wall = sum(r["B"] / r["solves_per_sec"] for r in rows
                     if r["solves_per_sec"])
    if total_wall:
        print(f"\n{total_lps} LPs in {total_wall:.3f}s "
              f"({total_lps / total_wall:.1f} solves/s sustained)")
    if trace is not None:
        # one compacted multi-segment re-solve of the final wave under the
        # profiler — the documented way to get a Perfetto trace
        with jax.profiler.trace(trace, create_perfetto_trace=True):
            solve_batched_compacted(stream[-1], backend=backend,
                                    telemetry=True)
        found = glob.glob(os.path.join(trace, "**", "perfetto_trace.json.gz"),
                          recursive=True)
        print(f"wrote a profiler trace of a compacted {fixture!r} solve to "
              f"{found[-1] if found else trace} (open at "
              "https://ui.perfetto.dev)")
    return rows


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--fixture", default="afiro")
    ap.add_argument("--waves", type=int, default=4)
    ap.add_argument("--batch", type=int, default=16)
    ap.add_argument("--backend", default="tableau",
                    choices=("tableau", "revised", "pdhg"))
    ap.add_argument("--trace", default=None, metavar="DIR",
                    help="write a profiler trace of the last wave under DIR")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    serve(fixture=args.fixture, waves=args.waves, batch=args.batch,
          backend=args.backend, trace=args.trace, seed=args.seed)


if __name__ == "__main__":
    main()
