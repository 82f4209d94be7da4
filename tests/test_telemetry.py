"""Solver telemetry plane (repro.obs): counter parity against the float64
oracle, survival through the compaction scheduler and the chunked-sorted
driver, the telemetry=False zero-overhead guarantee, and the span tracer
in a profiler trace and its event stream.

The iteration-attribution invariant under test everywhere:
``phase1_iters + phase2_iters == LPResult.iterations`` exactly, on every
engine and every scheduling path.  On well-conditioned workloads the f32
engines execute the oracle's pivot sequence, so the per-phase lanes must
also be bit-equal to the f64 reference's counts.
"""
import json

import jax
import numpy as np
import pytest

from repro.core import (OPTIMAL, LPBatch, random_lp_batch, solve_batched,
                        solve_batched_compacted, solve_batched_jax,
                        solve_batched_pdhg, solve_batched_pdhg_compacted,
                        solve_batched_reference_detailed,
                        solve_batched_revised,
                        solve_batched_revised_compacted)
from repro.io.mps import fixture_path, perturbed_batch, read_mps
from repro.obs import SolveReport, SpanTracer
from repro.obs.telemetry import ALL_LANES, F32_LANES, INT_LANES
from repro.obs.work import element_updates_lockstep, lockstep_steps

@pytest.fixture(scope="module", autouse=True)
def _release_telemetry_executables():
    """Drop this module's compiled executables when it finishes.

    Every telemetry=True solve retraces an engine with the counter lanes
    in the carry, so this module roughly doubles the number of large
    XLA CPU executables held by the process.  Keeping them alive pushes
    the suite's accumulated JIT code far enough that a *later* module's
    compile segfaults inside XLA (deterministically, at whatever compile
    happens to come next — test_warm.py in alphabetical order).  Clearing
    the caches releases the executables; later modules just recompile
    their own traces.
    """
    yield
    jax.clear_caches()


ENGINES = {
    "tableau": solve_batched_jax,
    "revised": solve_batched_revised,
    "pdhg": solve_batched_pdhg,
}
EXACT = ("tableau", "revised")  # pivot engines: oracle-exact paths
# fixtures where the f32 engines execute the f64 oracle's exact pivot
# sequence (the staircase fixtures diverge in float, not in telemetry)
PARITY_FIXTURES = ("afiro", "testprob")


def _mixed_batch(rng, B=24, m=6, n=6):
    """Half feasible-start, half phase-1 LPs — exercises both lanes."""
    half = B // 2
    b1 = random_lp_batch(rng, half, m, n, feasible_start=True)
    b2 = random_lp_batch(rng, B - half, m, n, feasible_start=False)
    batch = LPBatch(A=np.concatenate([b1.A, b2.A]),
                    b=np.concatenate([b1.b, b2.b]),
                    c=np.concatenate([b1.c, b2.c]))
    perm = rng.permutation(B)
    return LPBatch(A=batch.A[perm], b=batch.b[perm], c=batch.c[perm])


def _degenerate_batch(rng, B=8, m=6, n=6):
    """Feasible-start LPs with zeroed rhs rows: the first pivots hit
    min_ratio == 0, so the degenerate_pivots lane must fire."""
    batch = random_lp_batch(rng, B, m, n, feasible_start=True)
    b = batch.b.copy()
    b[:, :2] = 0.0
    return LPBatch(A=batch.A, b=b, c=batch.c)


def _assert_report_consistent(res, backend):
    rep = res.stats
    assert isinstance(rep, SolveReport)
    assert set(rep.counters) == set(ALL_LANES)
    np.testing.assert_array_equal(rep.iterations,
                                  np.asarray(res.iterations))
    for name in INT_LANES:
        assert rep.lane(name).dtype == np.int32
        assert (rep.lane(name) >= 0).all(), name
    return rep


# ---------------------------------------------------------------------------
# counter parity vs the float64 oracle
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("backend", EXACT)
@pytest.mark.parametrize("fixture", PARITY_FIXTURES)
def test_fixture_parity_vs_oracle(backend, fixture):
    g = read_mps(fixture_path(fixture))
    batch = perturbed_batch(g, 6, np.random.default_rng(0))
    ref, p1 = solve_batched_reference_detailed(batch)
    res = solve_batched(batch, backend=backend, telemetry=True)
    rep = _assert_report_consistent(res, backend)
    np.testing.assert_array_equal(res.status, ref.status)
    np.testing.assert_array_equal(rep.iterations, ref.iterations)
    np.testing.assert_array_equal(rep.lane("phase1_iters"), p1)


@pytest.mark.parametrize("backend", EXACT)
def test_dense_feasible_parity(backend):
    """Feasible-start dense batch: the engines skip phase 1 entirely (the
    oracle charges its feasibility check as one phase-1 iteration), so the
    phase-2 lane alone must be bit-equal to the oracle's phase-2 count."""
    batch = random_lp_batch(np.random.default_rng(3), 16, 6, 6,
                            feasible_start=True)
    ref, p1 = solve_batched_reference_detailed(batch)
    res = solve_batched(batch, backend=backend, telemetry=True)
    rep = _assert_report_consistent(res, backend)
    np.testing.assert_array_equal(res.status, ref.status)
    assert not rep.lane("phase1_iters").any()
    np.testing.assert_array_equal(rep.lane("phase2_iters"),
                                  np.asarray(ref.iterations) - p1)


def test_phase1_dense_parity_revised():
    """Phase-1-needing dense batch: the revised engine follows the oracle's
    pivot path exactly, so both per-phase lanes are bit-equal."""
    batch = random_lp_batch(np.random.default_rng(1), 16, 6, 6,
                            feasible_start=False)
    ref, p1 = solve_batched_reference_detailed(batch)
    res = solve_batched_revised(batch, telemetry=True)
    rep = _assert_report_consistent(res, "revised")
    np.testing.assert_array_equal(rep.iterations, ref.iterations)
    np.testing.assert_array_equal(rep.lane("phase1_iters"), p1)
    assert rep.lane("phase1_iters").any()
    assert rep.lane("phase2_iters").any()


@pytest.mark.parametrize("backend", EXACT)
def test_degenerate_pivots_lane(backend):
    batch = _degenerate_batch(np.random.default_rng(11))
    res = solve_batched(batch, backend=backend, telemetry=True)
    rep = _assert_report_consistent(res, backend)
    assert rep.lane("degenerate_pivots").any(), \
        "zeroed rhs rows must produce min_ratio == 0 pivots"
    # pivots can never exceed iterations (blocked/flip steps don't pivot)
    assert (rep.pivots <= rep.iterations).all()


def test_pdhg_lanes():
    batch = _mixed_batch(np.random.default_rng(5), B=12)
    res = solve_batched_pdhg(batch, telemetry=True)
    rep = _assert_report_consistent(res, "pdhg")
    # PDHG is single-phase: every iteration lands in the phase-2 lane
    assert not rep.lane("phase1_iters").any()
    ok = np.asarray(res.status) == OPTIMAL
    assert ok.any()
    for name in ("kkt_primal", "kkt_dual", "kkt_gap"):
        vals = rep.lane(name)[ok]
        assert np.isfinite(vals).all() and (vals >= 0).all(), name
    assert (rep.lane("omega")[ok] > 0).all()


def test_revised_refactor_lanes():
    batch = _mixed_batch(np.random.default_rng(7), B=16)
    res = solve_batched_revised(batch, refactor_period=4, telemetry=True)
    rep = _assert_report_consistent(res, "revised")
    assert rep.lane("refactorizations").any(), \
        "a period-4 refactor schedule must fire on multi-pivot solves"
    # the eta file is bounded by the refactor period
    assert (rep.lane("eta_len") <= 4).all()


# ---------------------------------------------------------------------------
# counters survive the compaction scheduler and the chunked driver
# ---------------------------------------------------------------------------

def test_counters_survive_bucket_shrink():
    batch = _mixed_batch(np.random.default_rng(9), B=32)
    mono = solve_batched_jax(batch, telemetry=True)
    stats = []
    sched = solve_batched_compacted(batch, segment_k=4, telemetry=True,
                                    stats_out=stats)
    buckets = [s.bucket for s in stats]
    assert min(buckets) < max(buckets), "batch too easy: no bucket shrink"
    rep = _assert_report_consistent(sched, "tableau")
    # scheduled == monolithic on every lane: gathers never touch counters
    for name in ALL_LANES:
        np.testing.assert_array_equal(rep.lane(name),
                                      mono.stats.lane(name), err_msg=name)


@pytest.mark.parametrize("solver", [solve_batched_revised_compacted,
                                    solve_batched_pdhg_compacted])
def test_counters_survive_compaction_other_engines(solver):
    batch = _mixed_batch(np.random.default_rng(13), B=16)
    res = solver(batch, segment_k=4, telemetry=True)
    _assert_report_consistent(res, solver.__name__)
    assert res.stats.iterations.any()


def test_counters_survive_chunked_sorted_roundtrip():
    batch = _mixed_batch(np.random.default_rng(15), B=24)
    mono = solve_batched_jax(batch, telemetry=True)
    chunked = solve_batched(batch, chunk_size=7, sort_by_difficulty=True,
                            telemetry=True)
    rep = _assert_report_consistent(chunked, "tableau")
    np.testing.assert_array_equal(chunked.status, mono.status)
    # the permute/chunk/unpermute round-trip must return every LP's own
    # counters to its original slot
    for name in ALL_LANES:
        np.testing.assert_array_equal(rep.lane(name),
                                      mono.stats.lane(name), err_msg=name)


# ---------------------------------------------------------------------------
# telemetry=False: the zero-overhead guarantee
# ---------------------------------------------------------------------------

def _core_jaxpr(backend, batch, **kw):
    from repro.core.pdhg import _solve_pdhg_core
    from repro.core.revised import _solve_revised_core
    from repro.core.simplex import _solve_core
    import jax.numpy as jnp

    A = jnp.asarray(batch.A, jnp.float32)
    b = jnp.asarray(batch.b, jnp.float32)
    c = jnp.asarray(batch.c, jnp.float32)
    ub = jnp.full((batch.batch, batch.n), jnp.inf, jnp.float32)
    m, n = batch.m, batch.n
    if backend == "tableau":
        fn = lambda: _solve_core(A, b, c, ub, m=m, n=n, max_iters=50,
                                 tol=1e-6, feas_tol=1e-5, **kw)
    elif backend == "revised":
        fn = lambda: _solve_revised_core(A, b, c, ub, m=m, n=n, max_iters=50,
                                         tol=1e-6, feas_tol=1e-5,
                                         refactor_period=4,
                                         pricing="dantzig", **kw)
    else:
        fn = lambda: _solve_pdhg_core(A, b, c, ub, m=m, n=n, max_iters=200,
                                      tol=1e-4, check_every=8, **kw)
    return str(jax.make_jaxpr(fn)())


@pytest.mark.parametrize("backend", ["tableau", "revised", "pdhg"])
def test_telemetry_off_is_default_and_trace_identical(backend):
    batch = random_lp_batch(np.random.default_rng(0), 4, 4, 4)
    default = _core_jaxpr(backend, batch)
    off = _core_jaxpr(backend, batch, telemetry=False)
    on = _core_jaxpr(backend, batch, telemetry=True)
    # the default path IS the telemetry-off path, byte-identical: the tel
    # slot is an empty pytree (None), adding no inputs, carries or outputs
    assert default == off
    # telemetry=True retraces with extra carry lanes and outputs
    assert on != off
    assert len(on) > len(off)


def test_off_state_has_no_extra_leaves():
    """The engine states carry ``tel=None`` when telemetry is off — JAX
    flattens None to zero leaves, so the off-path pytrees are structurally
    identical to the pre-telemetry states (that is the whole trick)."""
    from repro.core.simplex import solve_two_phase  # noqa: F401
    from repro.obs.telemetry import init_telemetry

    tel = init_telemetry(4)
    n_lanes = len(jax.tree_util.tree_leaves(tel))
    assert n_lanes == len(ALL_LANES) == len(INT_LANES) + len(F32_LANES)
    assert len(jax.tree_util.tree_leaves(None)) == 0


@pytest.mark.parametrize("backend", ["tableau", "revised", "pdhg"])
def test_stats_none_when_disabled(backend):
    batch = random_lp_batch(np.random.default_rng(2), 4, 4, 4)
    res = ENGINES[backend](batch)
    assert res.stats is None
    on = ENGINES[backend](batch, telemetry=True)
    # turning telemetry on never changes the answers
    np.testing.assert_array_equal(res.status, on.status)
    np.testing.assert_array_equal(res.iterations, on.iterations)


# ---------------------------------------------------------------------------
# span tracer: the profiler trace and the event stream
# ---------------------------------------------------------------------------

def test_scheduler_spans_in_profiler_trace(profile):
    batch = _mixed_batch(np.random.default_rng(21), B=32)
    tr = SpanTracer()
    solve_batched_compacted(batch, segment_k=4, telemetry=True)  # compile
    out = {}

    def call():
        with tr.span("solve", B=batch.batch):
            out["res"] = solve_batched_compacted(batch, segment_k=4,
                                                 telemetry=True, tracer=tr)
    events = [e for e in profile(call)
              if e[0] == "solve" or e[0].startswith("lp.")]
    rep = out["res"].stats
    assert rep.spans, "run_schedule must attach the tracer's span tree"
    names = {e[0] for e in events}
    assert any(nm.startswith("lp.segment[") for nm in names), names
    assert "lp.dispatch" in names
    # proper nesting: every span lies inside the caller's solve span
    (root,) = [e for e in events if e[0] == "solve"]
    assert root[3] == {"B": 32}
    for e in events:
        assert root[1] <= e[1] and e[2] <= root[2], e
    segments = [e for e in events if e[0].startswith("lp.segment[")]
    assert all({"k", "bucket", "max_steps", "steps", "survivors"}
               <= set(e[3]) for e in segments)
    # the tracer holds the same spans, and the flush instants
    recorded = [s.name for r in tr.roots for s in r.walk()]
    assert sorted(recorded) == sorted(e[0] for e in events)
    assert any(ev["name"] == "flush" for r in tr.roots for s in r.walk()
               for ev in s.events)


def test_jsonl_stream_unifies_segments_and_events():
    batch = _mixed_batch(np.random.default_rng(23), B=16)
    tr = SpanTracer()
    solve_batched_compacted(batch, segment_k=4, telemetry=True, tracer=tr)
    lines = [json.loads(ln) for ln in tr.to_jsonl().splitlines()]
    kinds = {(rec["type"], rec["name"]) for rec in lines}
    assert ("event", "flush") in kinds
    assert any(t == "span" and nm.startswith("lp.segment[") for t, nm in kinds)


def test_report_algebra_and_summary():
    batch = _mixed_batch(np.random.default_rng(25), B=12)
    res = solve_batched_jax(batch, telemetry=True)
    rep = res.stats
    assert rep.batch_size == 12
    sliced = rep.slice(2, 8)
    assert sliced.batch_size == 6
    np.testing.assert_array_equal(sliced.iterations, rep.iterations[2:8])
    idx = np.array([3, 1, 2])
    np.testing.assert_array_equal(rep.take(idx).iterations,
                                  rep.iterations[idx])
    back = SolveReport.concat([rep.slice(0, 5), rep.slice(5, 12)])
    np.testing.assert_array_equal(back.iterations, rep.iterations)
    s = rep.summary()
    assert s["batch_size"] == 12
    assert s["iterations_total"] == int(rep.iterations.sum())
    assert "phase2_iters" in s["lanes"]
    assert "SolveReport" in rep.render()


# ---------------------------------------------------------------------------
# the shared work-accounting helper (obs.work)
# ---------------------------------------------------------------------------

def test_work_helper_matches_bespoke_formula():
    from repro.core.simplex import tableau_elements

    iters = np.array([3, 7, 1, 4])
    assert lockstep_steps(iters) == 8
    assert element_updates_lockstep(iters, 5, 6) == \
        8 * 4 * tableau_elements(5, 6)
    # telemetry-sourced counts feed the same helper the bench uses
    batch = random_lp_batch(np.random.default_rng(27), 8, 5, 5)
    res = solve_batched_jax(batch, telemetry=True)
    assert element_updates_lockstep(res.stats.iterations, 5, 5) == \
        element_updates_lockstep(np.asarray(res.iterations), 5, 5)
