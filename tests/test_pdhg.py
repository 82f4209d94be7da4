"""Restarted-PDHG backend: oracle/scipy cross-checks, certificates,
restart/tolerance properties, compaction round-trip, Pallas parity, and
fixture-level three-backend agreement.

The first-order engine is *tolerance-based* (core/lp.py
``backend_spec("pdhg").exact is False``): statuses must agree with the
exact oracles at the configured tolerance and objectives to ~tol relative
— never bitwise.  Tolerances below are chosen a decade above the solver
tolerance so the tests pin behavior, not float noise.
"""
import numpy as np
import pytest

import jax.numpy as jnp
from jax.sharding import Mesh

import jax
from repro.core import (
    GeneralLPBatch, INFEASIBLE, LPBatch, OPTIMAL, UNBOUNDED,
    backend_spec, canonicalize, general_kkt, general_violation,
    solve_batched, solve_batched_compacted, solve_batched_jax,
    solve_batched_pdhg, solve_batched_pdhg_compacted,
    solve_batched_reference,
)
from repro.core.forms import LE
from repro.core.lp import canonicalize_backend
from repro.core.pdhg import kkt_residuals as _kkt_state  # noqa: F401 (api)
from repro.core.reference import random_lp_batch, random_sparse_lp_batch
from repro.io.mps import fixture_path, perturbed_batch, read_mps
from repro.kernels.ops import solve_batched_pallas

TOL = 1e-5          # the engine's f32 default
CHECK = 1e-3        # assertion budget: ~2 decades above TOL
# Cross-executor agreement budget: two different compilations (jit vs
# segment-jit vs shard_map) of the same rounds fuse differently in f32, so the
# restart trajectories — and the tol-satisfying points they stop at — drift
# apart by ~feasibility-slack x multiplier scale.  1e-3 relative is the
# honest contract for a tolerance-based engine (cf. the revised backend's
# batch-decomposition note in core/revised.py).
XTOL = 1e-3


def _rng(k: int) -> np.random.Generator:
    """Per-test generators: no shared module state, no order dependence."""
    return np.random.default_rng(k)


def _rel_obj_err(res, ref):
    ok = (np.asarray(res.status) == OPTIMAL) & (np.asarray(ref.status) == OPTIMAL)
    assert ok.any()
    return (np.abs(res.objective[ok] - ref.objective[ok])
            / np.maximum(np.abs(ref.objective[ok]), 1e-12)).max()


def _canonical_kkt(batch: LPBatch, res):
    """Backend-independent certificate on a canonical batch: primal/dual
    feasibility + duality gap of (x, y), relative."""
    ok = np.asarray(res.status) == OPTIMAL
    A = np.asarray(batch.A, np.float64)[ok]
    b = np.asarray(batch.b, np.float64)[ok]
    c = np.asarray(batch.c, np.float64)[ok]
    x = np.asarray(res.x, np.float64)[ok]
    y = np.asarray(res.y, np.float64)[ok]
    rp = np.maximum(np.einsum("bmn,bn->bm", A, x) - b, 0.0).max(axis=1) \
        / (1.0 + np.abs(b).max(axis=1))
    rd = np.maximum(c - np.einsum("bmn,bm->bn", A, y), 0.0).max(axis=1) \
        / (1.0 + np.abs(c).max(axis=1))
    p = np.einsum("bn,bn->b", c, x)
    d = np.einsum("bm,bm->b", b, y)
    gap = np.abs(p - d) / (1.0 + np.abs(p) + np.abs(d))
    return np.maximum(np.maximum(rp, rd), gap).max()


# ---------------------------------------------------------------------------
# oracle / scipy cross-checks
# ---------------------------------------------------------------------------

def test_dense_matches_oracle():
    batch = random_lp_batch(_rng(0), 16, 10, 10)
    ref = solve_batched_reference(batch)
    res = solve_batched_pdhg(batch)
    assert (res.status == ref.status).all()
    assert _rel_obj_err(res, ref) < CHECK
    assert _canonical_kkt(batch, res) < 10 * TOL


def test_dense_phase1_class_matches_oracle():
    batch = random_lp_batch(_rng(1), 16, 12, 12, feasible_start=False)
    ref = solve_batched_reference(batch)
    res = solve_batched_pdhg(batch)
    assert (res.status == ref.status).mean() >= 0.9
    assert _rel_obj_err(res, ref) < CHECK


def test_sparse_matches_oracle():
    batch = random_sparse_lp_batch(_rng(2), 16, 12, 16)
    ref = solve_batched_reference(batch)
    res = solve_batched_pdhg(batch)
    assert (res.status == ref.status).mean() >= 0.9
    assert _rel_obj_err(res, ref) < CHECK


def test_matches_scipy_on_general_min_problems():
    scipy_opt = pytest.importorskip("scipy.optimize")
    rng = np.random.default_rng(3)
    B, m, n = 6, 6, 5
    A = rng.uniform(-1.0, 2.0, size=(B, m, n))
    x0 = rng.uniform(0.5, 1.5, size=(B, n))
    rhs = np.einsum("bmn,bn->bm", A, x0) + rng.uniform(0.2, 1.0, size=(B, m))
    c = rng.uniform(0.2, 2.0, size=(B, n))      # bounded min: c > 0, x >= 0
    g = GeneralLPBatch.from_arrays(A, [LE] * m, rhs, c=c)
    res = solve_batched_pdhg(g)
    for k in range(B):
        sp = scipy_opt.linprog(c[k], A_ub=A[k], b_ub=rhs[k],
                               bounds=[(0, None)] * n, method="highs")
        assert res.status[k] == OPTIMAL and sp.status == 0
        assert abs(res.objective[k] - sp.fun) <= CHECK * (1 + abs(sp.fun))
        # dual certificate in scipy's (min) convention: row marginals <= 0
        np.testing.assert_allclose(res.y[k], sp.ineqlin.marginals,
                                   atol=5e-3, rtol=5e-3)


def test_degenerate_equality_batch():
    # equality rows canonicalize into <=-pairs — maximal degeneracy
    rng = np.random.default_rng(5)
    A = rng.uniform(-1.0, 1.0, size=(4, 3, 6))
    x0 = rng.uniform(0.2, 1.0, size=(4, 6))
    rhs = np.einsum("bmn,bn->bm", A, x0)
    c = rng.uniform(0.1, 1.0, size=(4, 6))
    g = GeneralLPBatch.from_arrays(A, ["E", "E", "L"], rhs, c=c,
                                   ub=np.full((4, 6), 3.0))
    ref = solve_batched_reference(g)
    res = solve_batched_pdhg(g)
    assert (res.status == ref.status).all()
    assert _rel_obj_err(res, ref) < CHECK
    assert general_violation(g, res.x)[res.status == OPTIMAL].max() < 1e-2


def test_infeasible_detected():
    # x1 + x2 <= -1 with x >= 0 is a clean Farkas certificate
    A = np.tile(np.array([[[1.0, 1.0], [-1.0, -1.0]]]), (4, 1, 1))
    b = np.tile(np.array([[-1.0, -2.0]]), (4, 1))
    c = np.ones((4, 2))
    res = solve_batched_pdhg(LPBatch.from_arrays(A, b, c))
    assert (res.status == INFEASIBLE).all()


def test_unbounded_detected():
    # max x1 with only -x1 <= 1: the primal ray is x1 -> inf
    A = np.tile(np.array([[[-1.0, 0.0]]]), (4, 1, 1))
    b = np.ones((4, 1))
    c = np.tile(np.array([[1.0, 0.0]]), (4, 1))
    res = solve_batched_pdhg(LPBatch.from_arrays(A, b, c))
    assert (res.status == UNBOUNDED).all()


# ---------------------------------------------------------------------------
# solver properties
# ---------------------------------------------------------------------------

def test_restart_invariance_of_certificates():
    # the check cadence changes restart timing and therefore the iterate
    # path, but never the certificate: statuses agree, objectives to ~tol
    batch = random_lp_batch(_rng(10), 8, 8, 8)
    a = solve_batched_pdhg(batch, check_every=8)
    b = solve_batched_pdhg(batch, check_every=32)
    assert (a.status == b.status).all()
    ok = a.status == OPTIMAL
    np.testing.assert_allclose(a.objective[ok], b.objective[ok], rtol=1e-3)


def test_tolerance_monotonicity():
    batch = random_lp_batch(_rng(11), 8, 8, 8)
    ref = solve_batched_reference(batch)
    errs = []
    for tol in (1e-2, 1e-3, 1e-5):
        res = solve_batched_pdhg(batch, tol=tol)
        assert (res.status == OPTIMAL).all()
        errs.append(_rel_obj_err(res, ref))
    # tightening the tolerance can only improve the objective (with slack
    # for the quantized check cadence)
    assert errs[2] <= errs[0] + 1e-6
    assert errs[2] < 10 * TOL


def test_iterations_count_and_cap():
    batch = random_lp_batch(_rng(12), 4, 6, 6)
    res = solve_batched_pdhg(batch, max_iters=64)
    # the cap quantizes to check rounds and binds
    assert (res.iterations <= 64).all()
    capped = solve_batched_pdhg(batch, tol=1e-12, max_iters=64)
    from repro.core import ITERATION_LIMIT
    assert (capped.status == ITERATION_LIMIT).all()


# ---------------------------------------------------------------------------
# composition: compaction, chunked driver, distributed, Pallas
# ---------------------------------------------------------------------------

def test_compaction_round_trip():
    batch = random_lp_batch(_rng(13), 24, 8, 8)
    mono = solve_batched_pdhg(batch)
    stats = []
    sched = solve_batched_pdhg_compacted(batch, segment_k=4,
                                         compact_threshold=0.75,
                                         stats_out=stats)
    assert (sched.status == mono.status).all()
    ok = mono.status == OPTIMAL
    np.testing.assert_allclose(sched.objective[ok], mono.objective[ok],
                               rtol=XTOL, atol=XTOL)
    # the bucket ladder actually shrank (PDHG iteration spread is wide)
    buckets = {s.bucket for s in stats}
    assert len(buckets) > 1 and min(buckets) < 24
    # duals survive the gather/flush path
    assert np.isfinite(sched.y[ok]).all()


def test_backend_kwarg_on_compacted_entry():
    batch = random_lp_batch(_rng(14), 8, 6, 6)
    a = solve_batched_compacted(batch, backend="pdhg")
    b = solve_batched_pdhg_compacted(batch)
    assert (a.status == b.status).all()


def test_chunked_driver_and_sorting():
    batch = random_lp_batch(_rng(15), 12, 6, 6)
    res = solve_batched(batch, backend="pdhg", chunk_size=5,
                        sort_by_difficulty=True)
    mono = solve_batched_pdhg(batch)
    assert (res.status == mono.status).all()
    ok = mono.status == OPTIMAL
    np.testing.assert_allclose(res.objective[ok], mono.objective[ok],
                               rtol=XTOL, atol=XTOL)
    assert res.y is not None and res.y.shape == (12, 6)


def test_distributed_entry_points():
    from repro.core import solve_shard_map

    mesh = Mesh(np.array(jax.devices()[:1]), ("dp",))
    batch = random_lp_batch(_rng(16), 6, 6, 6)
    mono = solve_batched_pdhg(batch)
    sm = solve_shard_map(batch, mesh, backend="pdhg")
    seg = solve_shard_map(batch, mesh, backend="pdhg", segment_k=8)
    for r in (sm, seg):
        assert (r.status == mono.status).all()
        ok = mono.status == OPTIMAL
        np.testing.assert_allclose(r.objective[ok], mono.objective[ok],
                                   rtol=XTOL, atol=XTOL)
    assert sm.y is not None and seg.y is not None


def test_pallas_interpret_parity():
    batch = random_lp_batch(_rng(17), 10, 8, 8)
    mono = solve_batched_pdhg(batch)
    pk = solve_batched_pallas(batch, backend="pdhg", tile_b=4)
    assert (pk.status == mono.status).all()
    ok = mono.status == OPTIMAL
    np.testing.assert_allclose(pk.objective[ok], mono.objective[ok],
                               rtol=1e-4, atol=1e-4)
    # the kernel emits the same certificate
    assert _canonical_kkt(batch, pk) < 10 * TOL


# ---------------------------------------------------------------------------
# fixtures: three-backend agreement + original-space certificates
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("fixture", ["afiro", "sc50b_like"])
def test_fixture_three_backend_agreement(fixture):
    g = read_mps(fixture_path(fixture))
    gb = perturbed_batch(g, 8, np.random.default_rng(0))
    ref = solve_batched_reference(gb)
    results = {b: solve_batched_jax(gb, backend=b)
               for b in ("tableau", "revised", "pdhg")}
    for name, res in results.items():
        assert (res.status == ref.status).all(), \
            f"{name} status parity failed on {fixture}"
        assert _rel_obj_err(res, ref) < 1e-4, name
    # all three emit an original-coordinate dual certificate
    for name, res in results.items():
        ok = res.status == OPTIMAL
        kkt = general_kkt(gb, res.x, res.y, res.z)
        scale = 1.0 + np.abs(gb.rhs).max() + np.abs(gb.c).max()
        assert kkt["max"][ok].max() < 5e-3 * scale, \
            f"{name} KKT violation on {fixture}: {kkt['max'][ok].max()}"


def test_fixture_pdhg_through_every_entry_point():
    g = read_mps(fixture_path("afiro"))
    gb = perturbed_batch(g, 4, np.random.default_rng(1))
    ref = solve_batched_reference(gb)
    mesh = Mesh(np.array(jax.devices()[:1]), ("dp",))
    from repro.core import solve_shard_map

    paths = {
        "jax": solve_batched_jax(gb, backend="pdhg"),
        "batched": solve_batched(gb, backend="pdhg"),
        "compacted": solve_batched_compacted(gb, backend="pdhg"),
        "shard_map": solve_shard_map(gb, mesh, backend="pdhg"),
        "pallas": solve_batched_pallas(gb, backend="pdhg"),
    }
    for name, res in paths.items():
        assert (res.status == ref.status).all(), name
        assert _rel_obj_err(res, ref) < 1e-4, name


# ---------------------------------------------------------------------------
# dual certificates are backend-uniform (simplex engines included)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("backend", ["tableau", "revised", "pdhg"])
def test_canonical_duals_all_backends(backend):
    batch = random_lp_batch(_rng(18), 8, 8, 8)
    res = solve_batched_jax(batch, backend=backend)
    ok = res.status == OPTIMAL
    assert ok.any() and res.y is not None and res.z is not None
    assert _canonical_kkt(batch, res) < 1e-3
    # z is definitionally c - A^T y (up to f32 matvec noise)
    z_chk = np.asarray(batch.c) - np.einsum("bmn,bm->bn",
                                            np.asarray(batch.A), res.y)
    np.testing.assert_allclose(res.z[ok], z_chk[ok], rtol=1e-3, atol=1e-2)
    # duals are NaN off-OPTIMAL
    bad = ~ok
    if bad.any():
        assert np.isnan(res.y[bad]).all()


def test_oracle_emits_duals():
    batch = random_lp_batch(_rng(19), 6, 6, 6)
    ref = solve_batched_reference(batch)
    assert ref.y is not None
    assert _canonical_kkt(batch, ref) < 1e-9


def test_recovered_duals_follow_min_convention():
    scipy_opt = pytest.importorskip("scipy.optimize")
    rng = np.random.default_rng(9)
    B, m, n = 4, 5, 4
    A = rng.uniform(-1.0, 2.0, size=(B, m, n))
    x0 = rng.uniform(0.5, 1.5, size=(B, n))
    rhs = np.einsum("bmn,bn->bm", A, x0) + rng.uniform(0.2, 1.0, size=(B, m))
    c = rng.uniform(0.2, 2.0, size=(B, n))
    g = GeneralLPBatch.from_arrays(A, [LE] * m, rhs, c=c)
    for backend in ("tableau", "revised"):
        res = solve_batched_jax(g, backend=backend)
        for k in range(B):
            sp = scipy_opt.linprog(c[k], A_ub=A[k], b_ub=rhs[k],
                                   bounds=[(0, None)] * n, method="highs")
            assert res.status[k] == OPTIMAL and sp.status == 0
            np.testing.assert_allclose(res.y[k], sp.ineqlin.marginals,
                                       atol=5e-4, rtol=5e-3)
            np.testing.assert_allclose(res.z[k], sp.lower.marginals,
                                       atol=5e-4, rtol=5e-3)


# ---------------------------------------------------------------------------
# registry
# ---------------------------------------------------------------------------

def test_registry_capabilities():
    assert canonicalize_backend("pdhg") == "pdhg"
    with pytest.raises(ValueError, match="unknown backend"):
        canonicalize_backend("simplex")
    assert backend_spec("tableau").exact
    assert backend_spec("revised").exact
    assert not backend_spec("pdhg").exact
    assert backend_spec("pdhg").supports_pallas
    assert backend_spec("revised").supports_pallas


def test_pdhg_rejects_pricing_rules():
    batch = random_lp_batch(_rng(20), 2, 4, 4)
    with pytest.raises(ValueError, match="pricing"):
        solve_batched_pdhg(batch, pricing="devex")
    with pytest.raises(ValueError, match="pricing"):
        solve_batched_jax(batch, backend="pdhg", pricing="steepest_edge")
