#!/usr/bin/env bash
# CI entry point: tier-1 tests + executed-work benchmark smoke + bench gate.
#
#   scripts/check.sh                       # tier-1 pytest + tableau smoke + gate
#   scripts/check.sh --fast                # pytest + mps-roundtrip smoke
#   scripts/check.sh --backend revised     # suite + smoke for the revised engine
#   scripts/check.sh --backend pdhg        # suite + smoke for the first-order engine
#   scripts/check.sh --backend all         # suite + smoke once per backend
#
# The smoke also carries the general-form rows (vendored MPS fixtures through
# canonicalize -> solve -> recover vs the float64 oracle), the shared-pattern
# sparse rows on the pdhg/all legs (sparse-vs-dense PDHG agreement on the
# staircase fixtures + the nnz-scaled traffic ratio), the warm-start rows
# (perturbed fixture trajectories re-solved from the previous step's
# terminal state: each engine must at least halve re-solve work with
# unchanged statuses/objectives), and the fast path an mps-roundtrip check
# (parse fixtures, write, re-parse, assert equal).  Every leg also runs the
# telemetry smoke: the observability plane on a perturbed fixture batch —
# off by default (stats None, answers unchanged when enabled), on-device
# counters summing exactly to LPResult.iterations (and matching the f64
# oracle's lanes on the exact engines), and a compacted+traced solve
# exporting a valid Perfetto span tree.  The full legs start
# with a pallas smoke block: the revised tile kernel and the PDHG segment
# kernel (interpreted on the CPU) against their JAX engines — pivot-exactness for
# the simplex kernel, tolerance agreement plus a completed bucket shrink
# for PDHG under the compaction scheduler.
#
# Per backend the smoke run writes /tmp/pivot_work_smoke_<backend>.json
# (never the committed BENCH_pivot_work.json), asserts the absolute
# invariants (identical statuses across solvers/rules/backends, the
# work-elimination engine still eliminating work), and then
# scripts/bench_gate.py diffs it against the committed baseline so a >20%
# relative regression of reduction_scheduled / any rule's pivot cut /
# the revised backend's element reduction fails CI here rather than in a
# future bench run.
set -euo pipefail
cd "$(dirname "$0")/.."

BACKENDS="tableau"
FAST=0
while [[ $# -gt 0 ]]; do
  case "$1" in
    --fast) FAST=1 ;;
    --backend) shift; BACKENDS="${1:?--backend needs a value}" ;;
    --backend=*) BACKENDS="${1#*=}" ;;
    *) echo "usage: $0 [--fast] [--backend tableau|revised|all]" >&2; exit 2 ;;
  esac
  shift
done
case "$BACKENDS" in
  all) BACKENDS="tableau revised pdhg" ;;
  tableau|revised|pdhg) ;;
  *) echo "unknown backend '$BACKENDS' (tableau|revised|pdhg|all)" >&2; exit 2 ;;
esac

export PYTHONPATH="src${PYTHONPATH:+:$PYTHONPATH}"

mps_roundtrip_smoke() {
  echo "== mps-roundtrip smoke =="
  python - <<'EOF'
# parse every vendored fixture (LP and MIP), write it back, re-parse, assert
# bit-equality (seconds of work — the fixtures are tiny, nothing is solved)
import tempfile, os
import numpy as np
from repro.io.mps import (FIXTURE_NAMES, MIP_FIXTURE_NAMES, fixture_path,
                          read_mps, write_mps)

for name in FIXTURE_NAMES + MIP_FIXTURE_NAMES:
    g = read_mps(fixture_path(name))
    with tempfile.NamedTemporaryFile(suffix=".mps", delete=False) as f:
        path = f.name
    write_mps(g, path)
    g2 = read_mps(path)
    os.unlink(path)
    for field in ("A", "rhs", "c", "c0", "lb", "ub", "sense"):
        a, b = getattr(g, field), getattr(g2, field)
        assert np.array_equal(a, b), f"{name}: {field} changed in round-trip"
    assert g.maximize == g2.maximize
    if g.ranges is not None:
        assert np.array_equal(np.nan_to_num(g.ranges, nan=-1),
                              np.nan_to_num(g2.ranges, nan=-1)), name
    if g.integer is None:
        assert g2.integer is None, f"{name}: integer mask appeared"
    else:
        assert np.array_equal(g.integer, g2.integer), \
            f"{name}: integer mask changed in round-trip"
    mark = " (integer)" if g.integer is not None else ""
    print(f"  {name}: {g.m}x{g.n} round-trips bit-identically{mark}")
print("mps-roundtrip smoke OK")
EOF
}

bnb_smoke() {
  echo "== branch-and-bound smoke =="
  python - <<'EOF'
# solve the tiny knapsack frontier end-to-end on both exact simplex engines:
# proven optimality, the brute-force-verified objective, and warm frontiers
# beating cold ones (seconds of work — a 5-node tree on a 1x8 instance)
from repro.core import OPTIMAL, branch_and_bound
from repro.io.mps import fixture_path, read_mps

g = read_mps(fixture_path("knapsack"))
for backend in ("tableau", "revised"):
    warm = branch_and_bound(g, backend=backend, frontier=8)
    assert warm.status == OPTIMAL and warm.proven, \
        f"{backend}: {warm.summary()}"
    assert abs(warm.objective - 280.0) < 1e-6, \
        f"{backend}: objective {warm.objective} != 280 (brute-force optimum)"
    cold = branch_and_bound(g, backend=backend, frontier=8,
                            warm_start=False)
    assert warm.lp_iterations < cold.lp_iterations, \
        f"{backend}: warm {warm.lp_iterations} !< cold {cold.lp_iterations}"
    print(f"  {backend}: optimum 280 proven in {warm.nodes} nodes, "
          f"warm {warm.lp_iterations} vs cold {cold.lp_iterations} pivots")
print("branch-and-bound smoke OK")
EOF
}

telemetry_smoke() {
  local backend="${1:-tableau}"
  echo "== telemetry smoke (backend=$backend) =="
  TELEMETRY_BACKEND="$backend" python - <<'EOF'
# the observability plane on a perturbed fixture batch (seconds of work):
# disabled by default (stats None, answers identical to the telemetry run),
# counters summing exactly to LPResult.iterations, phase lanes matching the
# float64 oracle on the exact engines, and a compacted+traced solve whose
# lp.* spans reach both the SpanTracer and a jax.profiler trace
import glob, os, tempfile
import jax
import numpy as np
from jax.profiler import ProfileData
from repro.core import solve_batched, solve_batched_compacted
from repro.core.reference import solve_batched_reference_detailed
from repro.io.mps import fixture_path, perturbed_batch, read_mps
from repro.obs import SpanTracer

backend = os.environ["TELEMETRY_BACKEND"]
g = read_mps(fixture_path("afiro"))
gb = perturbed_batch(g, 8, np.random.default_rng(3))

off = solve_batched(gb, backend=backend)
assert off.stats is None, "telemetry off must leave LPResult.stats unset"
on = solve_batched(gb, backend=backend, telemetry=True)
rep = on.stats
assert rep is not None, "telemetry=True produced no SolveReport"
assert np.array_equal(np.asarray(off.status), np.asarray(on.status)) \
    and np.allclose(np.asarray(off.objective), np.asarray(on.objective),
                    equal_nan=True), \
    "turning telemetry on changed the answers"
assert np.array_equal(rep.iterations, np.asarray(on.iterations)), \
    "telemetry iteration lanes do not sum to LPResult.iterations"
assert int(rep.iterations.sum()) > 0, "counters never fired"
if backend in ("tableau", "revised"):
    oracle, p1 = solve_batched_reference_detailed(gb)
    assert np.array_equal(rep.iterations, np.asarray(oracle.iterations)), \
        f"{backend}: telemetry iterations diverged from the f64 oracle"
    assert np.array_equal(rep.lane("phase1_iters"), np.asarray(p1)), \
        f"{backend}: phase1_iters lane diverged from the f64 oracle"
    tag = "lanes == f64 oracle"
else:
    kkt = rep.lane("kkt_gap")
    assert np.all(np.isfinite(kkt)), "pdhg kkt_gap lane not finite"
    tag = "kkt lanes finite"

tracer = SpanTracer()
with tempfile.TemporaryDirectory() as log_dir:
    with jax.profiler.trace(log_dir):
        solve_batched_compacted(gb, backend=backend, telemetry=True,
                                tracer=tracer)
    (path,) = glob.glob(f"{log_dir}/**/*.xplane.pb", recursive=True)
    events = [ev.name for plane in ProfileData.from_file(path).planes
              if plane.name.startswith("/host:") for line in plane.lines
              for ev in line.events if ev.name.startswith("lp.")]
recorded = [s.name for r in tracer.roots for s in r.walk()]
assert any(n.startswith("lp.segment") for n in recorded), \
    "compacted solve recorded no segment spans"
assert sorted(recorded) == sorted(events), \
    "the profiler trace and the SpanTracer hold different spans"
print(f"  {backend}: {int(rep.iterations.sum())} iterations counted, "
      f"{tag}, {len(events)} spans")
print("telemetry smoke OK")
EOF
}

pallas_smoke() {
  echo "== pallas kernel smoke =="
  python - <<'EOF'
# both new tile kernels against their JAX engines on a tiny mixed batch
# (the Pallas interpreter on the CPU, ~a minute): the revised kernel
# must be pivot-exact, the PDHG segment kernel must agree to tolerance and
# complete at least one bucket shrink through the compaction scheduler
import numpy as np
from repro.core import (OPTIMAL, random_lp_batch, solve_batched_pdhg,
                        solve_batched_revised)
from repro.kernels import solve_batched_pallas

rng = np.random.default_rng(7)
batch = random_lp_batch(rng, B=16, m=5, n=5)

ref = solve_batched_revised(batch)
pal = solve_batched_pallas(batch, backend="revised", tile_b=8)
assert np.array_equal(ref.status, pal.status), "revised kernel: statuses"
assert np.array_equal(ref.iterations, pal.iterations), \
    "revised kernel: pivot counts diverged from core/revised.py"
ok = np.asarray(ref.status) == OPTIMAL
np.testing.assert_allclose(pal.objective[ok], ref.objective[ok],
                           rtol=1e-4, atol=1e-4)
print(f"  revised tile: {int(ok.sum())}/{batch.batch} OPTIMAL, "
      "statuses+pivots identical to the engine")

pref = solve_batched_pdhg(batch)
stats = []
ppal = solve_batched_pallas(batch, backend="pdhg", tile_b=8,
                            compaction=True, segment_k=4, stats_out=stats)
match = (np.asarray(ppal.status) == np.asarray(pref.status)).mean()
assert match >= 0.95, f"pdhg segment kernel: status agreement {match:.2f}"
buckets = [s.bucket for s in stats]
assert min(buckets) < max(buckets), \
    "pdhg segment kernel: no bucket shrink through the scheduler"
print(f"  pdhg segment tile: status match {match:.2f}, "
      f"bucket ladder {sorted(set(buckets), reverse=True)}")
print("pallas kernel smoke OK")
EOF
}

if [[ "$FAST" == 1 ]]; then
  echo "== tier-1 pytest (fast) =="
  python -m pytest -x -q
  mps_roundtrip_smoke
  bnb_smoke
  telemetry_smoke tableau
  echo "ALL CHECKS PASSED"
  exit 0
fi

pallas_smoke

for backend in $BACKENDS; do
  echo "== tier-1 pytest (backend=$backend) =="
  python -m pytest -x -q

  telemetry_smoke "$backend"

  smoke="/tmp/pivot_work_smoke_${backend}.json"
  echo "== pivot-work + pricing smoke (backend=$backend) =="
  python -m benchmarks.pivot_work --quick --backend "$backend" --out "$smoke"
  SMOKE_JSON="$smoke" python - <<'EOF'
import json, os
d = json.load(open(os.environ["SMOKE_JSON"]))
for w in d["workloads"]:
    assert w["statuses_identical"], f"status divergence at {w['m']}x{w['n']}"
    assert w["reduction_scheduled"] >= 1.0, \
        f"work-elimination regressed at {w['m']}x{w['n']}: {w['reduction_scheduled']:.2f}x"
    # pricing smoke: every rule must agree with Dantzig on statuses
    # (rules change the pivot path, never the certificate)
    for rule, rr in w["rules"].items():
        assert rr["statuses_match_dantzig"], \
            f"pricing rule {rule} diverged on statuses at {w['m']}x{w['n']}"
    assert w["rules"]["steepest_edge"]["pivot_cut_vs_dantzig"] > 0.0, \
        f"steepest_edge did not cut pivots at {w['m']}x{w['n']}"
    # telemetry smoke: the counter plane now sources the pivot accounting —
    # its lanes must match both LPResult.iterations and the lockstep count
    tel = w["telemetry"]
    assert tel["iterations_match_result"], \
        f"telemetry iterations != LPResult.iterations at {w['m']}x{w['n']}"
    assert tel["iterations_match_lockstep"], \
        f"telemetry iterations != lockstep accounting at {w['m']}x{w['n']}"
    # backend smoke: the revised engine must agree with the tableau engine
    # on every status, monolithic and through the compaction scheduler
    for name, bb in w.get("backends", {}).items():
        assert bb["statuses_match_tableau"], \
            f"backend {name} diverged on statuses at {w['m']}x{w['n']}"
        assert bb.get("scheduled_statuses_match", True), \
            f"backend {name} diverged under compaction at {w['m']}x{w['n']}"
    # pdhg smoke: the first-order engine is tolerance-based — statuses must
    # agree with the exact tableau on nearly every LP, objectives to ~tol,
    # and the compaction scheduler must not change its answers
    pp = w.get("pdhg") or {}
    if pp:
        assert pp["status_match_tableau_frac"] >= 0.9, \
            f"pdhg status agreement {pp['status_match_tableau_frac']:.2f}" \
            f" < 0.9 at {w['m']}x{w['n']}"
        assert pp["rel_obj_err_vs_tableau"] < 1e-3, \
            f"pdhg rel_obj_err {pp['rel_obj_err_vs_tableau']:.2e} at " \
            f"{w['m']}x{w['n']}"
        assert pp["scheduled_status_match_frac"] >= 0.95, \
            f"pdhg compaction round-trip " \
            f"{pp['scheduled_status_match_frac']:.2f} at {w['m']}x{w['n']}"
        # adaptive step sizes: the Malitsky-Pock linesearch must never
        # cost more iterations than the fixed step, with statuses agreeing
        mp = pp["malitsky_pock"]
        assert mp["iters_cut_vs_fixed"] >= 0.0, \
            f"malitsky_pock costs more than fixed at {w['m']}x{w['n']}: " \
            f"cut {mp['iters_cut_vs_fixed']:+.1%}"
        assert mp["status_match_fixed_frac"] >= 0.9, \
            f"malitsky_pock status agreement " \
            f"{mp['status_match_fixed_frac']:.2f} at {w['m']}x{w['n']}"
# pallas smoke: the tile kernels vs their engines — the simplex kernels
# must be pivot-exact (identical statuses AND iteration counts), the
# tolerance-based pdhg kernel agrees on nearly every status, and every
# kernel's compaction-scheduled run keeps agreeing with the engine
for pw in d.get("pallas_workloads", []):
    ptag = f"{pw['m']}x{pw['n']} B={pw['B']}"
    for name, kk in pw["kernels"].items():
        if name in ("tableau", "revised"):
            assert kk["status_match_engine_frac"] == 1.0 \
                and kk["iters_match_engine"], \
                f"pallas {ptag}: {name} kernel lost pivot-exactness"
        else:
            assert kk["status_match_engine_frac"] >= 0.9, \
                f"pallas {ptag}: {name} kernel status agreement " \
                f"{kk['status_match_engine_frac']:.2f} < 0.9"
        assert kk["scheduled_status_match_frac"] >= 0.9, \
            f"pallas {ptag}: {name} scheduled-kernel agreement " \
            f"{kk['scheduled_status_match_frac']:.2f} < 0.9"
# sparse smoke (pdhg/all legs): the shared-pattern sparse engine must
# agree with the dense engine on the staircase fixtures — same algorithm,
# the matvecs just pay nnz instead of m*n — and the recorded traffic
# ratio must show it actually did (dense/sparse elements ~ 1/density)
for sw in d.get("sparse_workloads", []):
    assert sw["status_match_dense_frac"] >= 0.95, \
        f"sparse {sw['fixture']}: sparse-vs-dense status agreement " \
        f"{sw['status_match_dense_frac']:.2f} < 0.95"
    assert sw["rel_obj_err_vs_dense"] < 2e-3, \
        f"sparse {sw['fixture']}: rel_obj_err_vs_dense " \
        f"{sw['rel_obj_err_vs_dense']:.2e}"
    assert sw["element_traffic_ratio"] > 2.0, \
        f"sparse {sw['fixture']}: element traffic ratio " \
        f"{sw['element_traffic_ratio']:.2f} — not scaling with nnz"
# warm smoke: the warm-start engine must at least halve the re-solve
# iteration count on the perturbed trajectories (hard bound — the same
# one bench_gate.py holds), with cold-vs-warm statuses agreeing and
# objectives unchanged (warm starts change the path, never the answer)
for ww in d.get("warm_workloads", []):
    for name, wb in ww["backends"].items():
        assert wb["work_ratio"] <= 0.5, \
            f"warm {ww['fixture']}: {name} work_ratio " \
            f"{wb['work_ratio']:.2f} > 0.5 — warm re-solves not halving work"
        assert wb["status_match_frac"] >= 0.95, \
            f"warm {ww['fixture']}: {name} cold-vs-warm status agreement " \
            f"{wb['status_match_frac']:.2f} < 0.95"
        assert wb["rel_obj_err"] < 2e-3, \
            f"warm {ww['fixture']}: {name} rel_obj_err {wb['rel_obj_err']:.2e}"
# bnb smoke: the branch-and-bound driver must prove optimality on the
# MIP fixtures at the brute-force-verified objective, and warm-started
# frontiers must strictly beat cold ones on the identical tree (the same
# bounds bench_gate.py holds against the committed baseline)
for nw in d.get("bnb_workloads", []):
    for name, nb in nw["backends"].items():
        assert nb["proven"], \
            f"bnb {nw['fixture']}: {name} did not prove optimality"
        assert nb["objective_match"], \
            f"bnb {nw['fixture']}: {name} objective {nb['objective']} " \
            f"missed the brute-force optimum"
        assert nb["work_ratio"] < 1.0, \
            f"bnb {nw['fixture']}: {name} work_ratio " \
            f"{nb['work_ratio']:.2f} >= 1.0 — warm frontiers not paying"
# general-form smoke: real fixtures through the MPS/canonicalization
# pipeline must track the float64 oracle after recovery
for gw in d.get("general_workloads", []):
    for name, bb in gw["backends"].items():
        assert bb["status_match_oracle_frac"] >= 0.95, \
            f"general {gw['fixture']}: {name} status agreement " \
            f"{bb['status_match_oracle_frac']:.2f} < 0.95"
        assert bb["rel_obj_err"] < 2e-3, \
            f"general {gw['fixture']}: {name} rel_obj_err " \
            f"{bb['rel_obj_err']:.2e}"
print("pivot-work smoke OK:",
      ", ".join(f"{w['m']}x{w['n']}: x{w['reduction_scheduled']:.2f}"
                for w in d["workloads"]))
print("telemetry smoke OK:",
      ", ".join(f"{w['m']}x{w['n']}: {w['telemetry']['useful_pivots']} pivots "
                "counted on-device"
                for w in d["workloads"]))
print("pricing smoke OK:",
      ", ".join(f"{w['m']}x{w['n']}: se cut "
                f"{w['rules']['steepest_edge']['pivot_cut_vs_dantzig']:.1%}"
                for w in d["workloads"]))
if d["workloads"][0].get("backends"):
    print("backend smoke OK:",
          ", ".join(f"{w['m']}x{w['n']}: revised x"
                    f"{w['backends']['revised_dantzig']['element_reduction_vs_tableau']:.1f}"
                    for w in d["workloads"]))
if d["workloads"][0].get("pdhg"):
    print("pdhg smoke OK:",
          ", ".join(f"{w['m']}x{w['n']}: match "
                    f"{w['pdhg']['status_match_tableau_frac']:.2f} "
                    f"({w['pdhg']['iters_mean']:.0f} iters)"
                    for w in d["workloads"]))
if d.get("general_workloads"):
    print("general-form smoke OK:",
          ", ".join(f"{gw['fixture']} ({gw['m_canonical']}x"
                    f"{gw['n_canonical']} canonical)"
                    for gw in d["general_workloads"]))
if d.get("sparse_workloads"):
    print("sparse smoke OK:",
          ", ".join(f"{sw['fixture']} (nnz={sw['nnz']}, traffic "
                    f"x{sw['element_traffic_ratio']:.1f})"
                    for sw in d["sparse_workloads"]))
if d.get("warm_workloads"):
    print("warm smoke OK:",
          ", ".join(f"{ww['fixture']}/{name} ratio "
                    f"{wb['work_ratio']:.2f}"
                    for ww in d["warm_workloads"]
                    for name, wb in ww["backends"].items()))
if d.get("pallas_workloads"):
    print("pallas smoke OK:",
          ", ".join(f"{pw['m']}x{pw['n']}/{name} match "
                    f"{kk['status_match_engine_frac']:.2f}"
                    f"{' shrunk' if kk['bucket_shrunk'] else ''}"
                    for pw in d["pallas_workloads"]
                    for name, kk in pw["kernels"].items()))
if d.get("bnb_workloads"):
    print("bnb smoke OK:",
          ", ".join(f"{nw['fixture']}/{name} ratio "
                    f"{nb['work_ratio']:.2f}"
                    for nw in d["bnb_workloads"]
                    for name, nb in nw["backends"].items()))
EOF

  echo "== bench-regression gate (backend=$backend) =="
  python scripts/bench_gate.py "$smoke"
done

echo "ALL CHECKS PASSED"
