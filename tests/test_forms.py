"""General-form pipeline: canonicalize -> solve -> recover round-trips.

Property tests over random general-form batches (mixed senses, bounds,
frees, ranges, min/max) plus the vendored MPS fixtures: the canonical form
must match the float64 oracle, recovered objectives must equal c.x in
original coordinates bit-consistently across backends and pricing rules,
and presolve scaling must never change exact-arithmetic statuses.
"""
import dataclasses

import numpy as np
import pytest

from repro.core import (GeneralLPBatch, INFEASIBLE, LPBatch, OPTIMAL,
                        UNBOUNDED, canonical_shape, canonicalize,
                        general_violation, random_general_lp_batch,
                        solve_batched, solve_batched_jax,
                        solve_batched_reference)
from repro.core.forms import EQ, GE, LE, ensure_canonical
from repro.io.mps import fixture_path, perturbed_batch, read_mps

RNG = np.random.default_rng(11)


def _general(B=8, m=7, n=6, **kw):
    return random_general_lp_batch(RNG, B, m, n, **kw)


# ---------------------------------------------------------------------------
# canonicalize mechanics
# ---------------------------------------------------------------------------

def test_canonical_shape_growth():
    # equalities double, frees add columns; finite ubs are native (no rows)
    g = GeneralLPBatch.from_arrays(
        A=np.ones((1, 3, 2)), sense=[LE, GE, EQ], rhs=[[3.0, 1.0, 2.0]],
        lb=[[0.0, -np.inf]], ub=[[5.0, np.inf]], c=[[1.0, 1.0]])
    m_can, n_can = canonical_shape(g)
    # rows: 1 (L hi) + 1 (E hi) + 1 (G lo) + 1 (E lo) = 4; the finite ub
    # rides the bound vector instead of an identity row
    assert (m_can, n_can) == (4, 3)   # one free column split
    # legacy counterfactual: the row encoding would have paid one more row
    assert canonical_shape(g, bound_rows=True) == (5, 3)


def test_lower_bound_shift_and_constant():
    # min 2x + 3  s.t. x >= 4, x <= 9  -> optimum 11 at x = 4
    g = GeneralLPBatch.from_arrays(
        A=np.zeros((1, 1, 1)), sense=[LE], rhs=[[0.0]],
        lb=[[4.0]], ub=[[9.0]], c=[[2.0]], c0=3.0)
    res = solve_batched_reference(g)
    assert res.status[0] == OPTIMAL
    np.testing.assert_allclose(res.objective[0], 11.0)
    np.testing.assert_allclose(res.x[0], [4.0])


def test_maximize_sense():
    g = GeneralLPBatch.from_arrays(
        A=[[[1.0, 1.0]]], sense=[LE], rhs=[[4.0]], c=[[1.0, 2.0]],
        maximize=True)
    res = solve_batched_reference(g)
    np.testing.assert_allclose(res.objective[0], 8.0)


def test_free_variable_split():
    # min x  s.t.  x >= -5 encoded via a G row on a free variable
    g = GeneralLPBatch.from_arrays(
        A=[[[1.0]]], sense=[GE], rhs=[[-5.0]],
        lb=[[-np.inf]], c=[[1.0]])
    res = solve_batched_reference(g)
    assert res.status[0] == OPTIMAL
    np.testing.assert_allclose(res.objective[0], -5.0)
    np.testing.assert_allclose(res.x[0], [-5.0])


def test_ranged_rows():
    # 2 <= x1 + x2 <= 5 via an L row with a range; max x1 + x2
    g = GeneralLPBatch.from_arrays(
        A=[[[1.0, 1.0]]], sense=[LE], rhs=[[5.0]], ranges=[3.0],
        ub=[[4.0, 4.0]], c=[[1.0, 1.0]], maximize=True)
    res = solve_batched_reference(g)
    np.testing.assert_allclose(res.objective[0], 5.0)
    # minimize instead: floor of the range binds
    g2 = GeneralLPBatch.from_arrays(
        A=[[[1.0, 1.0]]], sense=[LE], rhs=[[5.0]], ranges=[3.0],
        ub=[[4.0, 4.0]], c=[[1.0, 1.0]])
    np.testing.assert_allclose(solve_batched_reference(g2).objective[0], 2.0)


def test_presolve_fixed_and_empty():
    # x0 fixed at 2 (substituted into the row), x2 empty column at its
    # cost-optimal bound; both removed from the canonical form
    g = GeneralLPBatch.from_arrays(
        A=[[[1.0, 1.0, 0.0]]], sense=[LE], rhs=[[10.0]],
        lb=[[2.0, 0.0, 0.0]], ub=[[2.0, np.inf, 7.0]],
        c=[[1.0, 1.0, 1.0]], maximize=True)
    lp, rec = canonicalize(g)
    assert lp.n == 1 and lp.m == 1
    res = solve_batched_reference(g)
    np.testing.assert_allclose(res.objective[0], 2.0 + 8.0 + 7.0)
    np.testing.assert_allclose(res.x[0], [2.0, 8.0, 7.0])


def test_presolve_empty_row_infeasible():
    A = np.zeros((2, 1, 1))
    g = GeneralLPBatch.from_arrays(
        A=A, sense=[GE], rhs=np.array([[1.0], [-1.0]]), c=np.zeros((2, 1)))
    res = solve_batched_reference(g)
    assert res.status[0] == INFEASIBLE       # 0 >= 1 impossible
    assert res.status[1] == OPTIMAL          # 0 >= -1 fine


def test_unbounded_general():
    g = GeneralLPBatch.from_arrays(   # min -x with x unconstrained above
        A=[[[0.0]]], sense=[LE], rhs=[[1.0]], c=[[-1.0]])
    assert solve_batched_reference(g).status[0] == UNBOUNDED


def test_empty_free_column_unbounded_not_presolved():
    # min y, y free-below with a finite ub and no constraint rows touching
    # it: the optimizing bound is -inf, so presolve must NOT substitute the
    # finite ub (that would certify a fake OPTIMAL at y = ub)
    g = GeneralLPBatch.from_arrays(
        A=[[[1.0, 0.0]]], sense=[LE], rhs=[[4.0]],
        lb=[[0.0, -np.inf]], ub=[[np.inf, 5.0]], c=[[0.0, 1.0]])
    for presolve in (True, False):
        assert solve_batched_reference(g, presolve=presolve).status[0] \
            == UNBOUNDED, presolve
    # flipped cost: ub IS the optimizing bound — presolve may drop it
    g2 = GeneralLPBatch.from_arrays(
        A=[[[1.0, 0.0]]], sense=[LE], rhs=[[4.0]],
        lb=[[0.0, -np.inf]], ub=[[np.inf, 5.0]], c=[[0.0, -1.0]])
    res = solve_batched_reference(g2)
    assert res.status[0] == OPTIMAL
    np.testing.assert_allclose(res.x[0, 1], 5.0)


def test_scaling_is_pow2_and_invertible():
    g = _general(B=4)
    lp_s, rec_s = canonicalize(g, scale=True)
    lp_u, rec_u = canonicalize(g, scale=False)
    r, s = rec_s.row_scale, rec_s.col_scale
    for arr in (r, s):
        fr, _ = np.frexp(arr)
        assert np.all(fr == 0.5), "scales must be powers of two"
    back = lp_s.A / r[:, :, None] / s[:, None, :]
    np.testing.assert_array_equal(back, lp_u.A)


def _dense_equilibrate(A, iters=2):
    """The dense (B, m, n) equilibration, kept here as the oracle the
    pattern path must match bit for bit."""
    B, m, n = A.shape
    r = np.ones((B, m))
    s = np.ones((B, n))
    W = np.abs(A)
    for _ in range(iters):
        cur = W * r[:, :, None] * s[:, None, :]
        nz = cur > 0
        big = np.where(nz, cur, -np.inf).max(axis=2)
        small = np.where(nz, cur, np.inf).min(axis=2)
        ok = np.isfinite(big) & (big > 0)
        r = r * np.where(ok, 1.0 / np.sqrt(np.where(ok, big * small, 1.0)), 1.0)
        cur = W * r[:, :, None] * s[:, None, :]
        nz = cur > 0
        big = np.where(nz, cur, -np.inf).max(axis=1)
        small = np.where(nz, cur, np.inf).min(axis=1)
        ok = np.isfinite(big) & (big > 0)
        s = s * np.where(ok, 1.0 / np.sqrt(np.where(ok, big * small, 1.0)), 1.0)
    pow2 = lambda v: np.exp2(np.round(np.log2(v)))  # noqa: E731
    return pow2(r), pow2(s)


def _sparse_general(rng, B, m, n, density, member_keep=1.0, spread=None):
    """A feasible ``<=`` batch on one random pattern of ``density``; each
    member keeps each pattern entry with probability ``member_keep``, and
    ``spread`` = (lo, hi) draws magnitudes as 10**U(lo, hi)."""
    support = rng.uniform(size=(m, n)) < density
    support[np.arange(m), rng.integers(n, size=m)] = True
    if spread is None:
        mag = rng.uniform(0.5, 3.0, size=(B, m, n))
    else:
        mag = 10.0 ** rng.uniform(*spread, size=(B, m, n))
    A = mag * rng.choice([-1.0, 1.0], size=(B, m, n))
    A *= support & (rng.uniform(size=(B, m, n)) < member_keep)
    return GeneralLPBatch.from_arrays(
        A, [LE] * m, rng.uniform(1.0, 2.0, size=(B, m)),
        ub=rng.uniform(1.0, 5.0, size=(B, n)),
        c=rng.uniform(-1.0, 1.0, size=(B, n)))


def _empty_row_and_column():
    g = _sparse_general(np.random.default_rng(24), 64, 20, 18, 0.2)
    A = g.A.copy()
    A[:, 7] = 0.0
    A[:, :, 11] = 0.0
    return dataclasses.replace(g, A=A), {"presolve": False, "scale": True}


SCALE_CASES = {
    "afiro_perturbed": lambda: (perturbed_batch(
        read_mps(fixture_path("afiro")), 256,
        np.random.default_rng(21)), {}),
    "sc50b_like": lambda: (perturbed_batch(
        read_mps(fixture_path("sc50b_like")), 32,
        np.random.default_rng(22)), {}),
    "dense_random": lambda: (GeneralLPBatch.from_arrays(
        np.random.default_rng(23).uniform(-3.0, 3.0, size=(128, 12, 10)),
        [LE, GE, EQ] * 4, np.ones((128, 12)), ub=np.full((128, 10), 4.0),
        c=np.ones((128, 10))), {}),
    "member_patterns_differ": lambda: (_sparse_general(
        np.random.default_rng(25), 96, 24, 20, 0.25, member_keep=0.7), {}),
    "empty_row_and_column": _empty_row_and_column,
    "single_lp": lambda: (_sparse_general(
        np.random.default_rng(26), 1, 30, 28, 0.15), {}),
    "magnitudes_1e-8_to_1e8": lambda: (_sparse_general(
        np.random.default_rng(27), 80, 22, 26, 0.3, spread=(-8.0, 8.0)), {}),
    "no_nonzeros": lambda: (GeneralLPBatch.from_arrays(
        np.zeros((16, 5, 4)), [LE] * 5, np.ones((16, 5)),
        ub=np.ones((16, 4)), c=np.ones((16, 4))),
        {"presolve": False, "scale": True}),
}

def _same_bits(got, want):
    if got is None or want is None:
        return got is None and want is None
    got, want = np.asarray(got), np.asarray(want)
    return (got.shape == want.shape and got.dtype == want.dtype
            and np.array_equal(got.view(np.uint64), want.view(np.uint64)))


@pytest.mark.parametrize("case", list(SCALE_CASES))
def test_scaling_matches_dense_oracle_bit_for_bit(case):
    """The equilibration over the shared pattern gives the dense
    algorithm's scales, and the scaled A, b, c and ub, to the bit (signed
    zeros included)."""
    g, kw = SCALE_CASES[case]()
    raw, _ = canonicalize(g, **{**kw, "scale": False})
    r, s = _dense_equilibrate(np.asarray(raw.A))
    lp, rec = canonicalize(g, **kw)
    assert _same_bits(rec.row_scale, r) and _same_bits(rec.col_scale, s)
    assert _same_bits(lp.A, raw.A * r[:, :, None] * s[:, None, :])
    assert _same_bits(lp.b, raw.b * r)
    assert _same_bits(lp.c, raw.c * s)
    assert _same_bits(lp.ub, None if raw.ub is None else raw.ub / s)
    if case == "empty_row_and_column":
        assert (rec.row_scale[:, 7] == 1.0).all()
        assert (rec.col_scale[:, 11] == 1.0).all()
    if case == "magnitudes_1e-8_to_1e8":
        assert np.ptp(np.log2(rec.row_scale)) > 20
    if case == "no_nonzeros":
        assert (rec.row_scale == 1.0).all() and (rec.col_scale == 1.0).all()


def test_ensure_canonical_passthrough():
    lp = LPBatch.from_arrays(np.ones((2, 3, 4)), np.ones((2, 3)),
                             np.ones((2, 4)))
    out, rec = ensure_canonical(lp)
    assert out is lp and rec is None


def test_mixed_bound_finiteness_rejected():
    lb = np.array([[0.0], [-np.inf]])
    g = GeneralLPBatch.from_arrays(
        A=np.ones((2, 1, 1)), sense=[LE], rhs=np.ones((2, 1)), lb=lb,
        c=np.ones((2, 1)))
    with pytest.raises(ValueError, match="batch-uniform"):
        canonicalize(g)


# ---------------------------------------------------------------------------
# canonicalize -> solve -> recover round-trip properties
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("seed,kw", [
    (0, {}),
    (1, {"eq_frac": 0.5}),
    (2, {"free_frac": 0.3}),
    (3, {"ranged_frac": 0.4}),
    (4, {"eq_frac": 0.3, "free_frac": 0.2, "ranged_frac": 0.3}),
])
def test_roundtrip_matches_scipy(seed, kw):
    """The whole pipeline (canonicalize -> f64 oracle -> recover) must agree
    with an independent general-form solver on statuses and objectives."""
    scipy_opt = pytest.importorskip("scipy.optimize")
    rng = np.random.default_rng(seed)
    g = random_general_lp_batch(rng, B=6, m=6, n=5, **kw)
    res = solve_batched_reference(g)
    lo, hi = g.row_bounds()
    for k in range(g.batch):
        fin_hi = np.isfinite(hi[k])
        fin_lo = np.isfinite(lo[k])
        A_ub = np.vstack([g.A[k][fin_hi], -g.A[k][fin_lo]])
        b_ub = np.concatenate([hi[k][fin_hi], -lo[k][fin_lo]])
        sign = -1.0 if g.maximize else 1.0
        sp = scipy_opt.linprog(sign * g.c[k], A_ub=A_ub, b_ub=b_ub,
                               bounds=list(zip(g.lb[k], g.ub[k])),
                               method="highs")
        want = {0: OPTIMAL, 2: INFEASIBLE, 3: UNBOUNDED}.get(sp.status)
        assert res.status[k] == want, f"LP {k}: {res.status[k]} vs scipy {want}"
        if want == OPTIMAL:
            obj_sp = sign * sp.fun + g.c0[k]
            np.testing.assert_allclose(res.objective[k], obj_sp, rtol=1e-7,
                                       atol=1e-7)
            assert general_violation(g, res.x)[k] < 1e-7


@pytest.mark.parametrize("backend,pricing", [
    ("tableau", "dantzig"), ("tableau", "steepest_edge"),
    ("tableau", "devex"), ("revised", "dantzig"), ("revised", "partial"),
])
def test_recovered_objective_is_c_dot_x(backend, pricing):
    """Recovered objectives must equal c.x + c0 in original coordinates
    bit-consistently (the recovery recomputes them from the recovered x)."""
    g = _general(B=12, m=6, n=6, eq_frac=0.3)
    res = solve_batched_jax(g, backend=backend, pricing=pricing)
    ok = res.status == OPTIMAL
    assert ok.any()
    recomputed = np.einsum("bn,bn->b", g.c, res.x) + g.c0
    np.testing.assert_array_equal(res.objective[ok], recomputed[ok])
    assert np.isnan(res.objective[~ok]).all()


def test_backends_agree_on_general_batches():
    g = _general(B=16, m=7, n=7, eq_frac=0.3, ranged_frac=0.2)
    ref = solve_batched_reference(g)
    tab = solve_batched_jax(g)
    rev = solve_batched_jax(g, backend="revised")
    assert (ref.status == tab.status).mean() >= 0.9
    assert (ref.status == rev.status).mean() >= 0.9
    ok = (ref.status == OPTIMAL) & (tab.status == OPTIMAL) \
        & (rev.status == OPTIMAL)
    assert ok.any()
    scale = np.maximum(1.0, np.abs(ref.objective[ok]))
    assert (np.abs(tab.objective[ok] - ref.objective[ok]) / scale).max() < 2e-3
    assert (np.abs(rev.objective[ok] - ref.objective[ok]) / scale).max() < 2e-3


def test_scaling_never_changes_oracle_statuses():
    for seed in range(3):
        rng = np.random.default_rng(100 + seed)
        g = random_general_lp_batch(rng, B=10, m=6, n=6, eq_frac=0.3,
                                    free_frac=0.2)
        s1 = solve_batched_reference(g, scale=True).status
        s0 = solve_batched_reference(g, scale=False).status
        np.testing.assert_array_equal(s1, s0)


def test_presolve_off_still_correct():
    g = _general(B=6, m=6, n=5)
    a = solve_batched_reference(g, presolve=True)
    b = solve_batched_reference(g, presolve=False)
    np.testing.assert_array_equal(a.status, b.status)
    ok = a.status == OPTIMAL
    np.testing.assert_allclose(a.objective[ok], b.objective[ok], rtol=1e-9)


def test_solve_batched_chunked_general():
    """solve_batched canonicalizes once and recovers the concatenated
    result across chunks."""
    g = _general(B=24, m=5, n=5)
    whole = solve_batched(g)
    chunked = solve_batched(g, chunk_size=7)
    np.testing.assert_array_equal(whole.status, chunked.status)
    ok = whole.status == OPTIMAL
    np.testing.assert_allclose(whole.objective[ok], chunked.objective[ok],
                               rtol=1e-6)
    assert whole.x.shape == (24, g.n)


def test_general_through_distributed_and_pallas():
    """The remaining entry points accept GeneralLPBatch directly: shard_map
    (one-shot and segmented) and the Pallas kernel all report in original
    coordinates."""
    import jax
    from jax.sharding import Mesh
    from repro.core import solve_shard_map
    from repro.kernels.ops import solve_batched_pallas

    g = _general(B=8, m=5, n=5, eq_frac=0.3)
    ref = solve_batched_reference(g)
    mesh = Mesh(np.array(jax.devices()[:1]), ("d",))
    outs = {
        "shard_map": solve_shard_map(g, mesh),
        "shard_map_seg": solve_shard_map(g, mesh, segment_k=8),
        "pallas": solve_batched_pallas(g),
        "pallas_compact": solve_batched_pallas(g, compaction=True,
                                               segment_k=8),
    }
    for name, res in outs.items():
        assert res.x.shape == (8, g.n), name
        assert (res.status == ref.status).mean() >= 0.85, name
        ok = (res.status == OPTIMAL) & (ref.status == OPTIMAL)
        scale = np.maximum(1.0, np.abs(ref.objective[ok]))
        err = np.abs(res.objective[ok] - ref.objective[ok]) / scale
        assert err.max() < 2e-3, name


def test_pallas_revised_runs_kernel_without_fallback():
    """General-form batches through solve_batched_pallas(backend="revised")
    run the tile kernel — no fallback warning may fire, and the recovered
    result must match the pure-JAX revised path."""
    import warnings as _w
    from repro.core.revised import solve_batched_revised
    from repro.kernels import ops
    from repro.kernels.ops import solve_batched_pallas

    g = _general(B=4, m=4, n=4)
    ref = solve_batched_revised(g)
    ops._WARNED.discard("revised-fallback")
    with _w.catch_warnings(record=True) as rec:
        _w.simplefilter("always")
        res = solve_batched_pallas(g, backend="revised", tile_b=4)
    hits = [x for x in rec if "falling back" in str(x.message)]
    assert not hits, "revised has a Pallas kernel; no fallback may fire"
    np.testing.assert_array_equal(res.status, ref.status)
    ok = res.status == OPTIMAL
    scale = np.maximum(1.0, np.abs(ref.objective[ok]))
    assert (np.abs(res.objective[ok] - ref.objective[ok]) / scale).max() \
        < 1e-4


def test_artificial_pinning_on_degenerate_equalities():
    """The phase-2 artificial-pinning rule: equality-pair canonical forms
    must not silently relax their rows (this failed before the fix)."""
    rng = np.random.default_rng(5)
    for _ in range(3):
        g = random_general_lp_batch(rng, B=8, m=8, n=6, eq_frac=0.6)
        res = solve_batched_reference(g)
        ok = res.status == OPTIMAL
        assert general_violation(g, res.x)[ok].max() < 1e-6
