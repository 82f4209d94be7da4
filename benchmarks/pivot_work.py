"""Executed-pivot-work benchmark: lockstep vs phase-compacted vs
compaction-scheduled batched simplex (the two-level work-elimination engine),
now crossed with the pluggable pricing engine.

For each Table-2 size (mixed feasible/infeasible batches, half needing
phase 1) this measures, per solver:

* executed lockstep steps,
* executed tableau-element updates (steps x occupied batch slots x tableau
  elements — the work unit of analysis/lp_perf.py; phase-compacted steps
  count the (m+1)(n+m+1) tableau, full steps the (m+2)(n+2m+1) one),
* wall-clock (median over post-compile runs),

and checks that all three solvers return *identical* statuses (they execute
identical pivot sequences; only dead work differs).

On top of that, a per-rule section runs the full two-level engine under each
pricing rule (core/pricing.py: dantzig / steepest_edge / devex) and records
per-LP executed pivots, element updates, wall-clock, and that every rule
agrees with Dantzig on statuses (rules change the path, never the
certificate).

A per-backend section (``workloads[].backends``, ``--backend`` selects)
crosses the tableau engine with the revised-simplex engine
(core/revised.py, dantzig + partial pricing): executed pivots, wall-clock,
tableau-element-equivalent updates (`revised_elements` — state written per
pivot, the unit the tableau's rank-1 update is charged in) and the honest
flops model (`analysis.lp_perf.revised_pivot_flops`, where the dense-square
tableau still wins and the crossover sits at n/m ~ 2-4), plus a
statuses-match check against the tableau engine.

A ``general_workloads`` section exercises the general-form pipeline on the
vendored real-instance MPS fixtures (io/mps.py + core/forms.py): each
fixture is batch-expanded by perturbation, solved by both engines in f32,
and compared against the float64 oracle *after recovery to original
coordinates* — plus a scaled-vs-unscaled f32 A/B that records whether
presolve equilibration changes iteration counts or statuses (it flips the
ill-scaled SC50B-class staircase from failing to solving).  These rows are
identical in --quick and full runs so scripts/bench_gate.py can gate status
regressions on real instances.

A ``sparse_workloads`` section A/Bs the shared-pattern sparse PDHG engine
(core/sparse.py) against the dense one on the staircase fixtures: the same
canonical LPs, one COO pattern across the batch, statuses/objectives
required to agree (same algorithm — only the matvecs change), and the
per-iteration element traffic recorded as the dense/sparse ratio
(~1/density) that scripts/bench_gate.py holds a floor under.

A ``warm_workloads`` section measures the warm-start engine (core/lp.py
WarmStart): a ``perturbed_sequence`` trajectory per fixture is re-solved
cold and warm-chained per engine, and the per-re-solve work ratio
(warm/cold mean iterations), status agreement and objective error are
recorded — scripts/bench_gate.py holds the ratio under 0.5 (a warm
re-solve must cost at most half a cold one) on top of the usual
baseline-relative bound.

A ``bnb_workloads`` section measures the branch-and-bound driver
(core/branch_bound.py) on the MIP fixtures: the same tree solved with
warm-started frontiers vs cold, per exact engine — recorded are the proven
objective, node/dispatch counts, total LP iterations both ways and their
``work_ratio`` (warm/cold).  scripts/bench_gate.py requires proven
optimality, an unchanged objective, and warm frontiers beating cold
(ratio < 1.0 hard, plus the baseline-relative bound).

A ``pallas_workloads`` section A/Bs the Pallas tile kernels
(src/repro/kernels/, run by the Pallas interpreter on a CPU) against their
JAX engines on small mixed batches: the tableau and revised kernels must
reproduce engine statuses *and* iteration counts exactly (they execute the
same pivot sequences), the PDHG kernel to tolerance; each kernel also runs
under the compaction scheduler (segment kernels + bucket gathers) with the
executed element traffic and bucket-shrink count recorded —
scripts/bench_gate.py holds a status floor and an element-traffic ceiling
per kernel row.  Wall-clock is recorded but informational only: these are
interpreter runs, not TPU timings.

The ``pdhg`` row additionally carries a ``malitsky_pock`` sub-row: the
adaptive-step-size rule (``step_rule="malitsky_pock"``) on the same
adversarial dense workload, recording the iteration cut vs the fixed-step
rule (statuses must keep agreeing — the rule changes the trajectory, not
the certificate).

Results land in ``BENCH_pivot_work.json`` next to this file so future PRs
have a perf trajectory to beat; a ``quick_workloads`` section re-runs the
--quick configuration (B=128) so scripts/bench_gate.py can diff a CI smoke
run against the committed baseline on exactly matching workloads.

  PYTHONPATH=src python -m benchmarks.pivot_work [--quick] [--out PATH]
                                                 [--backend tableau|revised|all]
"""
from __future__ import annotations

import argparse
import json
import os
import time

import numpy as np

from repro.analysis.lp_perf import (pdhg_iteration_flops, revised_pivot_flops,
                                    tableau_pivot_flops)
from repro.core import (LPBatch, OPTIMAL, pdhg_elements, random_lp_batch,
                        revised_elements, solve_batched_compacted,
                        solve_batched_jax, solve_batched_pdhg,
                        solve_batched_pdhg_compacted, solve_batched_revised,
                        solve_batched_revised_compacted)
from repro.core.compaction import auto_segment_k, total_elements, total_steps
from repro.core.lp import default_max_iters
from repro.core.pricing import PRICING_RULES
from repro.core.simplex import tableau_elements
from repro.obs.work import element_updates_lockstep, lockstep_steps

try:  # package and direct-script execution
    from .common import timeit
except ImportError:  # pragma: no cover
    from common import timeit

SIZES = ((5, 5), (10, 10), (28, 28), (50, 50), (100, 100))
QUICK_SIZES = ((5, 5), (28, 28))
GENERAL_FIXTURES = ("afiro", "sc50b_like")
SPARSE_FIXTURES = ("sc50b_like", "sc205_like")   # staircases: shared pattern
GENERAL_B = 32      # same in --quick and full runs: the gate matches on it
WARM_FIXTURES = ("afiro", "sc50b_like")  # same in both modes (gate keys on
WARM_B = 16                              # fixture/B/K); sc205 would push the
WARM_K = 4                               # smoke past its minute budget
BNB_FIXTURES = ("knapsack", "scheduling")  # assignment is root-integral
BNB_FRONTIER = 8                           # (1 node): nothing to A/B there
PALLAS_SIZES = ((5, 5), (12, 8))  # interpreter-sized: the kernels run on
PALLAS_B = 48                     # the Pallas CPU interpreter here, so the
PALLAS_TILE_B = 8                 # rows stay minutes, not hours


def mixed_batch(m: int, n: int, B: int, seed: int = 0) -> LPBatch:
    """Half feasible-start, half phase-1 LPs, shuffled — the workload where
    lockstep waste is worst (paper Table 4 mixed with Table 2)."""
    rng = np.random.default_rng(seed)
    half = B // 2
    b1 = random_lp_batch(rng, half, m, n, feasible_start=True)
    b2 = random_lp_batch(rng, B - half, m, n, feasible_start=False)
    batch = LPBatch(A=np.concatenate([b1.A, b2.A]),
                    b=np.concatenate([b1.b, b2.b]),
                    c=np.concatenate([b1.c, b2.c]))
    order = rng.permutation(B)
    return LPBatch(A=batch.A[order], b=batch.b[order], c=batch.c[order])


def measure_backends(batch: LPBatch, sched, segment_k: int, iters: int) -> dict:
    """Per-backend rows: the revised engine (dantzig + partial pricing) vs
    the tableau engine, monolithic and through the compaction scheduler.
    ``sched`` is the tableau engine's compaction-scheduled result (the
    statuses-match reference).

    On CPU the revised engine's triangular/eta solves are latency-bound
    (hundreds of tiny ops per lockstep step), so at the Table-2 tail the
    measured rows use a leading slice of the same workload (``B`` in the
    row records it): statuses are compared against the tableau result on
    that slice, and the element-reduction stays honest because the
    executed-work unit is per pivot — the tableau side is re-quantified on
    the identical slice."""
    m, n = batch.m, batch.n
    B = batch.batch
    # full batch through 28x28; 512 at 50x50; 256 at 100x100+
    B_rev = min(B, 512 if m < 100 else 256) if m >= 50 else B
    sub = LPBatch(A=np.asarray(batch.A)[:B_rev],
                  b=np.asarray(batch.b)[:B_rev],
                  c=np.asarray(batch.c)[:B_rev])
    tab_status = np.asarray(sched.status)[:B_rev]
    tab_iters = np.asarray(sched.iterations)[:B_rev].astype(np.int64)
    out = {
        "tableau": {
            "pivots_mean": float(sched.iterations.mean()),
            "elements_per_pivot": tableau_elements(m, n, compacted=True),
            "flops_per_pivot": tableau_pivot_flops(m, n, compacted=True),
            "statuses_match_tableau": True,
        }
    }
    for rule in ("dantzig", "partial"):
        partial = rule == "partial"
        res = solve_batched_revised(sub, pricing=rule)
        wall = timeit(lambda: solve_batched_revised(sub, pricing=rule),
                      warmup=0, iters=iters)
        stats = []
        res_sched = solve_batched_revised_compacted(
            sub, segment_k=segment_k, pricing=rule, stats_out=stats)
        steps = lockstep_steps(res.iterations)
        per_pivot = revised_elements(m, n, partial=partial)
        out[f"revised_{rule}"] = {
            "B": B_rev,
            "pivots_mean": float(res.iterations.astype(np.int64).mean()),
            "pivots_max": int(res.iterations.max()),
            "elements_per_pivot": per_pivot,
            "flops_per_pivot": revised_pivot_flops(m, n, partial=partial),
            "elements_lockstep": int(steps * B_rev * per_pivot),
            "elements_scheduled": int(total_elements(stats)),
            "wall_s": wall,
            "statuses_match_tableau": bool(
                np.array_equal(res.status, tab_status)),
            "scheduled_statuses_match": bool(
                np.array_equal(res_sched.status, tab_status)),
        }
        # tableau-element-equivalent reduction at matching (lockstep)
        # granularity on the identical LP slice: steps x slots x per-pivot
        out[f"revised_{rule}"]["element_reduction_vs_tableau"] = (
            element_updates_lockstep(tab_iters, m, n)
            / max(1, out[f"revised_{rule}"]["elements_lockstep"]))
    return out


def measure_general(fixture: str, B: int = GENERAL_B, *, iters: int = 1,
                    seed: int = 0, backends: str = "all") -> dict:
    """One fixture-backed general-form workload row: canonical-shape
    accounting, the selected f32 engines vs the float64 oracle after
    recovery (status parity + objective error + original-space
    feasibility), and the scaled-vs-unscaled f32 A/B on the source
    instance.  ``backends`` mirrors the CLI flag so a per-engine CI leg
    measures only its own engine."""
    from repro.analysis.lp_perf import canonical_work
    from repro.core import solve_batched_jax, solve_batched_reference
    from repro.io.mps import fixture_path, perturbed_batch, read_mps

    try:
        from .common import oracle_checks
    except ImportError:  # pragma: no cover - direct-script execution
        from common import oracle_checks

    g1 = read_mps(fixture_path(fixture))
    batch = perturbed_batch(g1, B, np.random.default_rng(seed))
    shapes = canonical_work(g1)
    ref = solve_batched_reference(batch)
    row = {
        "fixture": fixture, "B": B,
        "m": g1.m, "n": g1.n,
        "m_canonical": shapes["m_canonical"],
        "n_canonical": shapes["n_canonical"],
        "revised_wins_flops_canonical": shapes["revised_wins_flops"],
        "oracle_pivots_mean": float(ref.iterations.mean()),
        "backends": {},
    }
    engines = (("tableau", "revised", "pdhg") if backends == "all"
               else (backends,))
    for backend in engines:
        res = solve_batched_jax(batch, backend=backend)
        wall = timeit(lambda: solve_batched_jax(batch, backend=backend),
                      warmup=0, iters=iters)
        row["backends"][backend] = dict(
            oracle_checks(batch, res, ref),
            pivots_mean=float(res.iterations.astype(np.int64).mean()),
            wall_s=wall)
    # scaling A/B on the single source instance (deterministic)
    scaled = solve_batched_jax(g1, scale=True)
    raw = solve_batched_jax(g1, scale=False)
    row["scaling"] = {
        "scaled_status": int(scaled.status[0]),
        "scaled_iters": int(scaled.iterations[0]),
        "unscaled_status": int(raw.status[0]),
        "unscaled_iters": int(raw.iterations[0]),
        "changes_f32": bool(scaled.status[0] != raw.status[0]
                            or scaled.iterations[0] != raw.iterations[0]),
    }
    return row


def measure_sparse(fixture: str, B: int = GENERAL_B, *, iters: int = 1,
                   seed: int = 0) -> dict:
    """Shared-pattern sparse PDHG vs the dense engine on one staircase
    fixture batch: identical canonical LPs (one COO pattern shared across
    the batch, per-LP values), so statuses and objectives must agree up to
    float-sum association — the measurable difference is per-iteration
    element traffic, which the sparse path pays in nnz instead of m*n."""
    from repro.analysis.lp_perf import sparse_pdhg_iteration_flops
    from repro.core import (SparseLPBatch, canonicalize,
                            solve_batched_pdhg_sparse, sparse_pdhg_elements)
    from repro.io.mps import fixture_path, perturbed_batch, read_mps

    g = read_mps(fixture_path(fixture))
    gb = perturbed_batch(g, B, np.random.default_rng(seed))
    batch, _ = canonicalize(gb)
    sp = SparseLPBatch.from_dense(batch)
    m, n, nnz = sp.m, sp.n, sp.nnz
    dense = solve_batched_pdhg(batch)
    t_dense = timeit(lambda: solve_batched_pdhg(batch), warmup=0, iters=iters)
    sparse = solve_batched_pdhg_sparse(sp)
    t_sparse = timeit(lambda: solve_batched_pdhg_sparse(sp), warmup=0,
                      iters=iters)
    ok = (np.asarray(dense.status) == OPTIMAL) \
        & (np.asarray(sparse.status) == OPTIMAL)
    rel = (np.abs(sparse.objective[ok] - dense.objective[ok])
           / np.maximum(np.abs(dense.objective[ok]), 1e-12)).max() \
        if ok.any() else 0.0
    return {
        "fixture": fixture, "B": B, "m": m, "n": n, "nnz": nnz,
        "density": nnz / max(1, m * n),
        "elements_per_iter_dense": pdhg_elements(m, n),
        "elements_per_iter_sparse": sparse_pdhg_elements(nnz, m, n),
        "element_traffic_ratio":
            pdhg_elements(m, n) / sparse_pdhg_elements(nnz, m, n),
        "flops_per_iter_sparse": sparse_pdhg_iteration_flops(nnz, m, n),
        "iters_mean_dense": float(dense.iterations.astype(np.int64).mean()),
        "iters_mean_sparse": float(sparse.iterations.astype(np.int64).mean()),
        "status_match_dense_frac": float(
            (np.asarray(sparse.status) == np.asarray(dense.status)).mean()),
        "rel_obj_err_vs_dense": float(rel),
        "wall_s_dense": t_dense,
        "wall_s_sparse": t_sparse,
    }


def measure_warm(fixture: str, B: int = WARM_B, K: int = WARM_K, *,
                 seed: int = 0, backends: str = "all") -> dict:
    """Warm-start engine row: a ``perturbed_sequence`` trajectory (K nudged
    copies of one fixture batch, the repeated-solve workload from the
    reachability pipeline) solved cold at every step and warm-chained from
    the previous step's terminal state (``res.warm_start()``).  Records, per
    engine, the mean re-solve iteration counts cold vs warm, their ratio
    (``work_ratio`` — scripts/bench_gate.py holds this under 0.5: a warm
    re-solve must cost at most half a cold one), the cold-vs-warm status
    agreement, and the objective error on commonly-OPTIMAL LPs.  Step 0 is
    excluded from the means (both paths solve it cold — it only seeds the
    chain)."""
    from repro.core import solve_batched
    from repro.io.mps import fixture_path, perturbed_sequence, read_mps

    g = read_mps(fixture_path(fixture))
    seq = perturbed_sequence(g, B, K, np.random.default_rng(seed))
    engines = (("tableau", "revised", "pdhg") if backends == "all"
               else (backends,))
    row = {"fixture": fixture, "B": B, "K": K, "backends": {}}
    for backend in engines:
        cold_iters, warm_iters, match, errs = [], [], [], []
        ws = None
        for k, gb in enumerate(seq):
            cold = solve_batched(gb, backend=backend)
            if k > 0:
                warm = solve_batched(gb, backend=backend, warm=ws)
                cold_iters.append(np.asarray(cold.iterations, np.int64))
                warm_iters.append(np.asarray(warm.iterations, np.int64))
                match.append(np.asarray(warm.status)
                             == np.asarray(cold.status))
                ok = (np.asarray(cold.status) == OPTIMAL) \
                    & (np.asarray(warm.status) == OPTIMAL)
                if ok.any():
                    errs.append(float(
                        (np.abs(warm.objective[ok] - cold.objective[ok])
                         / np.maximum(np.abs(cold.objective[ok]),
                                      1e-12)).max()))
                ws = warm.warm_start()  # chain from the warm trajectory
            else:
                ws = cold.warm_start()
        cold_mean = float(np.concatenate(cold_iters).mean())
        warm_mean = float(np.concatenate(warm_iters).mean())
        row["backends"][backend] = {
            "cold_iters_mean": cold_mean,
            "warm_iters_mean": warm_mean,
            "work_ratio": warm_mean / max(cold_mean, 1e-12),
            "status_match_frac": float(np.concatenate(match).mean()),
            "rel_obj_err": float(max(errs)) if errs else 0.0,
        }
    return row


def measure_bnb(fixture: str, *, frontier: int = BNB_FRONTIER,
                backends: str = "all") -> dict:
    """Branch-and-bound row: the same MIP tree driven with warm-started
    frontiers and cold ones, per exact simplex engine.  Warm and cold runs
    fathom identically (same relaxation optima), so nodes match and the
    total-LP-iteration ``work_ratio`` isolates what parent-basis reuse
    saves across the tree.  PDHG is skipped — its iteration counts are not
    pivot work and its tree can differ (weaker safe bounds)."""
    from repro.core import branch_and_bound
    from repro.io.mps import fixture_path, read_mps

    g = read_mps(fixture_path(fixture))
    engines = [b for b in ("tableau", "revised")
               if backends in ("all", b)]
    row = {"fixture": fixture, "frontier": frontier, "backends": {}}
    for backend in engines:
        warm = branch_and_bound(g, backend=backend, frontier=frontier)
        wall = timeit(lambda: branch_and_bound(g, backend=backend,
                                               frontier=frontier),
                      warmup=0, iters=1)
        cold = branch_and_bound(g, backend=backend, frontier=frontier,
                                warm_start=False)
        row["backends"][backend] = {
            "objective": float(warm.objective),
            "proven": bool(warm.proven and cold.proven),
            "objective_match": bool(
                abs(warm.objective - cold.objective)
                <= 1e-6 * max(1.0, abs(cold.objective))),
            "nodes": int(warm.nodes),
            "nodes_cold": int(cold.nodes),
            "dispatches": int(warm.dispatches),
            "warm_lp_iters": int(warm.lp_iterations),
            "cold_lp_iters": int(cold.lp_iterations),
            "work_ratio": warm.lp_iterations / max(cold.lp_iterations, 1),
            "wall_s": wall,
        }
    return row


def measure_pallas(m: int, n: int, B: int = PALLAS_B, *,
                   tile_b: int = PALLAS_TILE_B, seed: int = 0,
                   backends: str = "all") -> dict:
    """One Pallas-kernel workload row: every selected tile kernel vs its
    JAX engine on the same mixed batch, monolithic and through the
    compaction scheduler (segment kernels with bucket gathers between
    launches).  The simplex kernels are pivot-exact — statuses and
    iteration counts must equal the engine's; PDHG agrees to ~tol (a
    different XLA compilation of the same rounds).  ``elements_scheduled``
    is the executed element traffic of the scheduled kernel run (the
    bench_gate ceiling); ``wall_s`` is an interpreter time, recorded for
    trend only."""
    from repro.kernels import solve_batched_pallas

    batch = mixed_batch(m, n, B, seed)
    engines = {
        "tableau": solve_batched_jax,
        "revised": solve_batched_revised,
        "pdhg": solve_batched_pdhg,
    }
    names = tuple(engines) if backends == "all" else (backends,)
    row = {"m": m, "n": n, "B": B, "tile_b": tile_b, "kernels": {}}
    for name in names:
        ref = engines[name](batch)
        t0 = time.time()
        pal = solve_batched_pallas(batch, backend=name, tile_b=tile_b)
        wall = time.time() - t0
        stats = []
        pal_sched = solve_batched_pallas(batch, backend=name, tile_b=tile_b,
                                         compaction=True, segment_k=6,
                                         stats_out=stats)
        ok = (np.asarray(ref.status) == OPTIMAL) \
            & (np.asarray(pal.status) == OPTIMAL)
        rel = (np.abs(pal.objective[ok] - ref.objective[ok])
               / np.maximum(np.abs(ref.objective[ok]), 1e-12)).max() \
            if ok.any() else 0.0
        buckets = [s.bucket for s in stats]
        row["kernels"][name] = {
            "status_match_engine_frac": float(
                (np.asarray(pal.status) == np.asarray(ref.status)).mean()),
            "iters_match_engine": bool(np.array_equal(
                np.asarray(pal.iterations), np.asarray(ref.iterations))),
            "rel_obj_err_vs_engine": float(rel),
            "segments": len(stats),
            "elements_scheduled": int(total_elements(stats)),
            "bucket_shrunk": bool(buckets and min(buckets) < max(buckets)),
            "scheduled_status_match_frac": float(
                (np.asarray(pal_sched.status)
                 == np.asarray(ref.status)).mean()),
            "wall_s_interpret": wall,
        }
    return row


def measure_pdhg(batch: LPBatch, sched, iters: int) -> dict:
    """The first-order engine's workload row: tolerance-based agreement
    with the (exact) tableau engine on statuses and objectives, iteration
    counts, honest flops per iteration, and the compaction round-trip
    (scheduled pdhg must agree with monolithic pdhg — gathers never touch
    an LP's own iterates).  Measured on a leading slice like the revised
    rows (PDHG runs thousands of per-LP iterations; the slice keeps the
    bench minutes bounded while the metrics stay per-LP)."""
    m, n = batch.m, batch.n
    B = batch.batch
    B_pdhg = min(B, 128 if m < 50 else 64)
    sub = LPBatch(A=np.asarray(batch.A)[:B_pdhg],
                  b=np.asarray(batch.b)[:B_pdhg],
                  c=np.asarray(batch.c)[:B_pdhg])
    tab_status = np.asarray(sched.status)[:B_pdhg]
    tab_obj = np.asarray(sched.objective)[:B_pdhg]
    res = solve_batched_pdhg(sub)
    wall = timeit(lambda: solve_batched_pdhg(sub), warmup=0, iters=iters)
    stats = []
    res_sched = solve_batched_pdhg_compacted(sub, stats_out=stats)
    it = res.iterations.astype(np.int64)
    ok = (res.status == OPTIMAL) & (tab_status == OPTIMAL)
    rel = (np.abs(res.objective[ok] - tab_obj[ok])
           / np.maximum(np.abs(tab_obj[ok]), 1e-12)).max() if ok.any() else 0.0
    # adaptive-step-size A/B on the same adversarial dense workload: the
    # Malitsky-Pock linesearch must cut iterations without moving statuses
    mp = solve_batched_pdhg(sub, step_rule="malitsky_pock")
    mp_it = mp.iterations.astype(np.int64)
    mp_ok = (res.status == OPTIMAL) & (mp.status == OPTIMAL)
    mp_rel = (np.abs(mp.objective[mp_ok] - res.objective[mp_ok])
              / np.maximum(np.abs(res.objective[mp_ok]), 1e-12)).max() \
        if mp_ok.any() else 0.0
    mp_row = {
        "iters_mean": float(mp_it.mean()),
        "iters_cut_vs_fixed": 1.0 - float(mp_it.mean()) / max(
            float(it.mean()), 1e-12),
        "status_match_fixed_frac": float((mp.status == res.status).mean()),
        "rel_obj_err_vs_fixed": float(mp_rel),
    }
    return {
        "B": B_pdhg,
        "iters_mean": float(it.mean()),
        "iters_max": int(it.max()),
        "flops_per_iter": pdhg_iteration_flops(m, n),
        "elements_per_iter": pdhg_elements(m, n),
        "elements_scheduled": int(total_elements(stats)),
        "wall_s": wall,
        "status_match_tableau_frac": float(
            (res.status == tab_status).mean()),
        "rel_obj_err_vs_tableau": float(rel),
        "scheduled_status_match_frac": float(
            (res_sched.status == res.status).mean()),
        "malitsky_pock": mp_row,
    }


def measure(m: int, n: int, B: int, *, segment_k: int | None = None,
            compact_threshold: float = 0.5, iters: int = 2,
            seed: int = 0, backends: str = "all") -> dict:
    batch = mixed_batch(m, n, B, seed)
    max_iters = default_max_iters(m, n)
    if segment_k is None:
        segment_k = auto_segment_k(m, n)  # the compaction auto-tune heuristic

    # --- seed lockstep (single combined loop, full tableau throughout) ------
    lock = solve_batched_jax(batch, phase_compaction=False)
    t_lock = timeit(lambda: solve_batched_jax(batch, phase_compaction=False),
                    warmup=0, iters=iters)  # first call above was the warmup
    piv = lock.iterations.astype(np.int64)
    steps_lock = lockstep_steps(piv)
    elems_lock = element_updates_lockstep(piv, m, n)

    # --- Level 1: phase-compacted two-loop solve ----------------------------
    pc = solve_batched_jax(batch)
    t_pc = timeit(lambda: solve_batched_jax(batch), warmup=0, iters=iters)
    # executed-step accounting via the scheduler with compaction disabled
    # (threshold=0, one segment per stage == the monolithic loop split)
    stats_pc = []
    pc2 = solve_batched_compacted(batch, segment_k=max_iters,
                                  compact_threshold=0.0, stats_out=stats_pc)
    elems_pc = total_elements(stats_pc)

    # --- Level 1+2: compaction-scheduled ------------------------------------
    # telemetry=True: the counter plane sources the pivot accounting below,
    # so BENCH rows and user-facing telemetry can never drift apart
    stats_sched = []
    sched = solve_batched_compacted(batch, segment_k=segment_k,
                                    compact_threshold=compact_threshold,
                                    stats_out=stats_sched, telemetry=True)
    t_sched = timeit(lambda: solve_batched_compacted(
        batch, segment_k=segment_k, compact_threshold=compact_threshold),
        warmup=0, iters=iters)
    elems_sched = total_elements(stats_sched)

    statuses_identical = bool(
        np.array_equal(lock.status, pc.status)
        and np.array_equal(lock.status, pc2.status)
        and np.array_equal(lock.status, sched.status))
    buckets = sorted({s.bucket for s in stats_sched}, reverse=True)

    # --- pricing rules x two-level engine ------------------------------------
    # (dantzig reuses the scheduled run above: same solver, same rule)
    rules = {}
    for rule in PRICING_RULES:
        if rule == "dantzig":
            r_res, r_stats, r_wall = sched, stats_sched, t_sched
        else:
            r_stats = []
            r_res = solve_batched_compacted(
                batch, segment_k=segment_k, compact_threshold=compact_threshold,
                pricing=rule, stats_out=r_stats)
            r_wall = timeit(lambda: solve_batched_compacted(
                batch, segment_k=segment_k,
                compact_threshold=compact_threshold, pricing=rule),
                warmup=0, iters=iters)
        r_piv = r_res.iterations.astype(np.int64)
        rules[rule] = {
            "pivots_mean": float(r_piv.mean()),
            "pivots_max": int(r_piv.max()),
            "pivots_total": int(r_piv.sum()),
            "steps": total_steps(r_stats),
            "elements": int(total_elements(r_stats)),
            "wall_s": r_wall,
            "statuses_match_dantzig": bool(
                np.array_equal(r_res.status, sched.status)),
        }
    dz_mean = rules["dantzig"]["pivots_mean"]
    for rule in PRICING_RULES:
        rules[rule]["pivot_cut_vs_dantzig"] = (
            1.0 - rules[rule]["pivots_mean"] / max(dz_mean, 1e-12))

    backend_rows = (measure_backends(batch, sched, segment_k, iters)
                    if backends in ("all", "revised") else {})
    pdhg_row = (measure_pdhg(batch, sched, iters)
                if backends in ("all", "pdhg") else {})

    # telemetry-sourced counters from the scheduled run's SolveReport; the
    # match flags assert they equal the bespoke LPResult-derived counts
    rep = sched.stats
    tel_piv = rep.iterations.astype(np.int64)
    telemetry_row = {
        "iterations_match_result": bool(
            np.array_equal(rep.iterations,
                           np.asarray(sched.iterations))),
        "iterations_match_lockstep": bool(np.array_equal(tel_piv, piv)),
        "useful_pivots": int(tel_piv.sum()),
        "phase1_pivots_total": int(rep.total("phase1_pivots")),
        "phase2_pivots_total": int(rep.total("phase2_pivots")),
        "bound_flips_total": int(rep.total("bound_flips")),
        "degenerate_pivots_total": int(rep.total("degenerate_pivots")),
        "elements_lockstep_from_telemetry": element_updates_lockstep(
            tel_piv, m, n),
    }

    return {
        "m": m, "n": n, "B": B, "mixed": True,
        "segment_k": segment_k, "compact_threshold": compact_threshold,
        "useful_pivots": int(tel_piv.sum()),
        "pivots_mean": float(tel_piv.mean()),
        "pivots_max": int(tel_piv.max()),
        "statuses_identical": statuses_identical,
        "telemetry": telemetry_row,
        "lockstep": {
            "steps": steps_lock,
            "elements": int(elems_lock),
            "wall_s": t_lock,
        },
        "phase_compacted": {
            "steps": total_steps(stats_pc),
            "elements": int(elems_pc),
            "wall_s": t_pc,
        },
        "scheduled": {
            "steps": total_steps(stats_sched),
            "elements": int(elems_sched),
            "wall_s": t_sched,
            "bucket_ladder": buckets,
            "segments": len(stats_sched),
            "survivor_curve": [s.survivors for s in stats_sched],
        },
        "rules": rules,
        "backends": backend_rows,
        "pdhg": pdhg_row,
        "reduction_phase_compacted": elems_lock / max(1, elems_pc),
        "reduction_scheduled": elems_lock / max(1, elems_sched),
        "reduction_steepest_edge": elems_lock / max(
            1, rules["steepest_edge"]["elements"]),
    }


def _measure_rows(sizes, B: int, quick: bool, backends: str) -> list:
    rows = []
    for (m, n) in sizes:
        iters = 1 if (quick or m >= 50) else 2
        r = measure(m, n, B, iters=iters, backends=backends)
        rows.append(r)
        print(f"pivot_work m={m} n={n} B={B}: "
              f"elems lockstep={r['lockstep']['elements']:.3e} "
              f"compacted={r['phase_compacted']['elements']:.3e} "
              f"scheduled={r['scheduled']['elements']:.3e} "
              f"(x{r['reduction_scheduled']:.2f}) "
              f"statuses_identical={r['statuses_identical']}")
        for rule, rr in r["rules"].items():
            print(f"  pricing={rule:<14} pivots_mean={rr['pivots_mean']:8.2f} "
                  f"(cut {rr['pivot_cut_vs_dantzig']:+.1%}) "
                  f"elems={rr['elements']:.3e} wall={rr['wall_s']:.3f}s "
                  f"statuses_match={rr['statuses_match_dantzig']}")
        for name, bb in r["backends"].items():
            if name == "tableau":
                continue
            print(f"  backend={name:<15} pivots_mean={bb['pivots_mean']:8.2f} "
                  f"elems={bb['elements_lockstep']:.3e} "
                  f"(x{bb['element_reduction_vs_tableau']:.1f} fewer element "
                  f"updates) wall={bb['wall_s']:.3f}s "
                  f"statuses_match={bb['statuses_match_tableau']}")
        if r["pdhg"]:
            pp = r["pdhg"]
            print(f"  backend=pdhg            iters_mean={pp['iters_mean']:8.0f} "
                  f"status_match={pp['status_match_tableau_frac']:.3f} "
                  f"rel_obj={pp['rel_obj_err_vs_tableau']:.1e} "
                  f"wall={pp['wall_s']:.3f}s "
                  f"sched_match={pp['scheduled_status_match_frac']:.3f}")
            mp = pp["malitsky_pock"]
            print(f"  step_rule=malitsky_pock iters_mean={mp['iters_mean']:8.0f} "
                  f"(cut {mp['iters_cut_vs_fixed']:+.1%} vs fixed) "
                  f"status_match={mp['status_match_fixed_frac']:.3f} "
                  f"rel_obj={mp['rel_obj_err_vs_fixed']:.1e}")
    return rows


def run(quick: bool = False, B: int = 4096, out: str | None = None,
        backends: str = "all") -> dict:
    sizes = QUICK_SIZES if quick else SIZES
    if quick:
        B = min(B, 128)
    if out is None:
        out = os.path.join(os.path.dirname(os.path.abspath(__file__)), "..",
                           "BENCH_pivot_work.json")
    out = os.path.abspath(out)
    # fail on an unwritable destination *before* burning benchmark minutes
    os.makedirs(os.path.dirname(out), exist_ok=True)
    t0 = time.time()
    rows = _measure_rows(sizes, B, quick, backends)
    if quick:
        quick_rows = rows
    else:
        # the --quick configuration again, so scripts/bench_gate.py can diff
        # a CI smoke run against this file on exactly matching workloads
        print("-- quick_workloads (bench_gate baseline) --")
        quick_rows = _measure_rows(QUICK_SIZES, 128, True, backends)
    print("-- general_workloads (fixture-backed, bench_gate baseline) --")
    general_rows = []
    for fixture in GENERAL_FIXTURES:
        r = measure_general(fixture, backends=backends)
        general_rows.append(r)
        print(f"general {r['fixture']} B={r['B']}: "
              f"{r['m']}x{r['n']} -> canonical "
              f"{r['m_canonical']}x{r['n_canonical']}  "
              + "  ".join(
                  f"{k}: match={v['status_match_oracle_frac']:.2f} "
                  f"err={v['rel_obj_err']:.1e}"
                  for k, v in r["backends"].items())
              + f"  scaling_changes_f32={r['scaling']['changes_f32']}")
    sparse_rows = []
    if backends in ("all", "pdhg"):
        print("-- sparse_workloads (shared-pattern PDHG, bench_gate "
              "baseline) --")
        for fixture in SPARSE_FIXTURES:
            r = measure_sparse(fixture)
            sparse_rows.append(r)
            print(f"sparse {r['fixture']} B={r['B']}: canonical "
                  f"{r['m']}x{r['n']} nnz={r['nnz']} "
                  f"(density {r['density']:.3f}) "
                  f"traffic x{r['element_traffic_ratio']:.1f} "
                  f"status_match={r['status_match_dense_frac']:.3f} "
                  f"rel_obj={r['rel_obj_err_vs_dense']:.1e} "
                  f"wall dense={r['wall_s_dense']:.3f}s "
                  f"sparse={r['wall_s_sparse']:.3f}s")
    print("-- warm_workloads (warm-start engine, bench_gate baseline) --")
    warm_rows = []
    for fixture in WARM_FIXTURES:
        r = measure_warm(fixture, backends=backends)
        warm_rows.append(r)
        for name, wb in r["backends"].items():
            ratio = wb["work_ratio"]
            cut = "all" if ratio == 0.0 else f"x{1.0 / ratio:.1f}"
            print(f"warm {r['fixture']} B={r['B']} K={r['K']} "
                  f"{name:<8} cold_iters={wb['cold_iters_mean']:8.1f} "
                  f"warm_iters={wb['warm_iters_mean']:8.1f} "
                  f"({cut} re-solve work eliminated) "
                  f"status_match={wb['status_match_frac']:.3f} "
                  f"rel_obj={wb['rel_obj_err']:.1e}")
    print("-- pallas_workloads (tile kernels vs engines, bench_gate "
          "baseline) --")
    pallas_rows = []
    for (pm, pn) in PALLAS_SIZES:
        r = measure_pallas(pm, pn, backends=backends)
        pallas_rows.append(r)
        for name, kk in r["kernels"].items():
            print(f"pallas {r['m']}x{r['n']} B={r['B']} "
                  f"{name:<8} status_match={kk['status_match_engine_frac']:.3f} "
                  f"iters_match={kk['iters_match_engine']} "
                  f"rel_obj={kk['rel_obj_err_vs_engine']:.1e} "
                  f"segments={kk['segments']} "
                  f"elems={kk['elements_scheduled']:.3e} "
                  f"shrunk={kk['bucket_shrunk']} "
                  f"wall={kk['wall_s_interpret']:.1f}s (interpret)")
    bnb_rows = []
    if backends in ("all", "tableau", "revised"):
        print("-- bnb_workloads (branch-and-bound driver, bench_gate "
              "baseline) --")
        for fixture in BNB_FIXTURES:
            r = measure_bnb(fixture, backends=backends)
            bnb_rows.append(r)
            for name, nb in r["backends"].items():
                print(f"bnb {r['fixture']} frontier={r['frontier']} "
                      f"{name:<8} obj={nb['objective']:10.4f} "
                      f"proven={nb['proven']} nodes={nb['nodes']} "
                      f"warm_iters={nb['warm_lp_iters']} "
                      f"cold_iters={nb['cold_lp_iters']} "
                      f"(x{nb['work_ratio']:.2f} of cold) "
                      f"wall={nb['wall_s']:.3f}s")
    result = {
        "benchmark": "pivot_work",
        "quick": quick,
        "backends": backends,
        "elapsed_s": time.time() - t0,
        "workloads": rows,
        "quick_workloads": quick_rows,
        "general_workloads": general_rows,
        "sparse_workloads": sparse_rows,
        "warm_workloads": warm_rows,
        "bnb_workloads": bnb_rows,
        "pallas_workloads": pallas_rows,
    }
    with open(out, "w") as f:
        json.dump(result, f, indent=2)
    print(f"wrote {out}")
    return result


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--quick", action="store_true",
                    help="short smoke: small sizes, B=128, 1 timing iter")
    ap.add_argument("--batch", type=int, default=4096)
    ap.add_argument("--out", type=str, default=None)
    ap.add_argument("--backend",
                    choices=("tableau", "revised", "pdhg", "all"),
                    default="all",
                    help="which solver engines get per-backend rows "
                         "(tableau base metrics are always measured; "
                         "'tableau' skips the revised and pdhg rows)")
    args = ap.parse_args()
    run(quick=args.quick, B=args.batch, out=args.out, backends=args.backend)


if __name__ == "__main__":
    main()
