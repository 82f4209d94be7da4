"""Property suite for the revised-simplex backend (core/revised.py).

The revised engine keeps (A, b, c) immutable and pivots on a product-form
basis inverse (eta file + periodic LU refactorization), so the invariants
split in two:

* **certificates** — statuses must match the tableau backend and the float64
  oracle on every batch class (dense, sparse, degenerate,
  infeasible/unbounded), and optimal objectives must agree to tolerance.
  Pivot *paths* may differ: revised recomputes f32 reduced costs instead of
  carrying them through rank-1 updates, so degenerate near-ties can order
  differently without changing any certificate.
* **engine invariance** — for a fixed engine configuration the pivot
  sequence is deterministic: refactorization period must not change
  certificates (period 1 = fresh LU every pivot is the exact reference),
  compaction-scheduler gathers must round-trip the eta/LU state, and
  partial pricing must agree with full pricing on final statuses.
"""
import warnings

import numpy as np
import pytest

from repro.core import (
    ITERATION_LIMIT,
    OPTIMAL,
    LPBatch,
    auto_compact_threshold,
    auto_refactor_period,
    random_lp_batch,
    random_sparse_lp_batch,
    revised_elements,
    solve_batched,
    solve_batched_compacted,
    solve_batched_jax,
    solve_batched_reference,
    solve_batched_revised,
    solve_batched_revised_compacted,
    solve_shard_map,
    tableau_elements,
)
from repro.analysis.lp_perf import (
    revised_crossover,
    revised_pivot_flops,
    tableau_pivot_flops,
)
from repro.core.revised import REVISED_RULES, canonicalize_revised_rule
from repro.distributed.sharding import make_mesh
from repro.kernels import solve_batched_pallas


def _mixed_batch(rng, B_each=8, m=10, n=8):
    f = random_lp_batch(rng, B_each, m, n, feasible_start=True)
    p1 = random_lp_batch(rng, B_each, m, n, feasible_start=False)
    return LPBatch(A=np.concatenate([f.A, p1.A]),
                   b=np.concatenate([f.b, p1.b]),
                   c=np.concatenate([f.c, p1.c]))


def _assert_same_certificates(a, b, rtol=1e-4):
    np.testing.assert_array_equal(a.status, b.status)
    ok = a.status == OPTIMAL
    np.testing.assert_allclose(a.objective[ok], b.objective[ok], rtol=rtol)


# ---------------------------------------------------------------------------
# certificates vs tableau backend and float64 oracle
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("pricing", REVISED_RULES)
def test_revised_matches_tableau_and_oracle_dense(pricing):
    batch = _mixed_batch(np.random.default_rng(11))
    rev = solve_batched_revised(batch, pricing=pricing)
    _assert_same_certificates(solve_batched_jax(batch), rev)
    _assert_same_certificates(solve_batched_reference(batch), rev)


@pytest.mark.parametrize("pricing", REVISED_RULES)
def test_revised_matches_oracle_sparse(pricing):
    batch = random_sparse_lp_batch(np.random.default_rng(7), B=12, m=14, n=10,
                                   density=0.15)
    rev = solve_batched_revised(batch, pricing=pricing)
    _assert_same_certificates(solve_batched_reference(batch), rev)


def test_revised_matches_oracle_degenerate():
    """Duplicated rows + zero slack at the optimum: degenerate pivots with
    theta = 0 must terminate with the same certificates."""
    rng = np.random.default_rng(23)
    base = random_lp_batch(rng, 12, 6, 6)
    A = np.concatenate([base.A, base.A[:, :3, :]], axis=1)  # duplicate rows
    b = np.concatenate([base.b, base.b[:, :3]], axis=1)
    batch = LPBatch.from_arrays(A, b, base.c)
    rev = solve_batched_revised(batch)
    _assert_same_certificates(solve_batched_reference(batch), rev)
    assert (rev.status == OPTIMAL).all()


def test_revised_infeasible_and_unbounded():
    # x0 <= 1 and -x0 <= -2 is infeasible; max x0 with only x1 bounded is
    # unbounded
    A_inf = np.zeros((3, 2, 2))
    A_inf[:, 0, 0] = 1.0
    A_inf[:, 1, 0] = -1.0
    b_inf = np.tile(np.array([1.0, -2.0]), (3, 1))
    inf = LPBatch.from_arrays(A_inf, b_inf, np.ones((3, 2)))
    A_unb = np.zeros((2, 1, 2))
    A_unb[:, 0, 1] = 1.0
    unb = LPBatch.from_arrays(A_unb, np.ones((2, 1)),
                              np.tile(np.array([1.0, 0.0]), (2, 1)))
    for batch in (inf, unb):
        tab = solve_batched_jax(batch)
        for pricing in REVISED_RULES:
            rev = solve_batched_revised(batch, pricing=pricing)
            np.testing.assert_array_equal(tab.status, rev.status)
            np.testing.assert_array_equal(
                solve_batched_reference(batch).status, rev.status)


def test_revised_solution_is_feasible():
    """The extracted x must satisfy Ax <= b, x >= 0 (not just the objective)."""
    batch = _mixed_batch(np.random.default_rng(31), m=8, n=12)
    rev = solve_batched_revised(batch)
    ok = rev.status == OPTIMAL
    assert ok.any()
    ax = np.einsum("bmn,bn->bm", batch.A[ok], rev.x[ok])
    assert (ax <= batch.b[ok] + 1e-3 * np.abs(batch.b[ok]) + 1e-3).all()
    assert (rev.x[ok] >= -1e-5).all()


# ---------------------------------------------------------------------------
# engine invariance
# ---------------------------------------------------------------------------

def test_refactorization_invariance():
    """Eta-file length is a cost knob, not a semantic: period 1 (fresh LU
    every pivot — the exact reference) and period 16 must produce the same
    certificates, and near-identical objectives."""
    batch = _mixed_batch(np.random.default_rng(5), m=12, n=12)
    r1 = solve_batched_revised(batch, refactor_period=1)
    r16 = solve_batched_revised(batch, refactor_period=16)
    _assert_same_certificates(r1, r16, rtol=1e-4)
    # and the auto-derived period agrees too
    rauto = solve_batched_revised(batch)
    _assert_same_certificates(r1, rauto, rtol=1e-4)
    assert auto_refactor_period(12, 12) == max(4, min(64, 6))


def test_compaction_gather_round_trip():
    """Bucket gathers carry the eta file / LU factors / basis across shrinks
    (with refactor-on-compact): the scheduled solve must reproduce the
    monolithic solve's certificates on every batch slot, and the bucket
    ladder must actually shrink."""
    rng = np.random.default_rng(17)
    batch = _mixed_batch(rng, B_each=24, m=10, n=10)
    mono = solve_batched_revised(batch)
    stats = []
    sched = solve_batched_revised_compacted(batch, segment_k=4,
                                            stats_out=stats)
    _assert_same_certificates(mono, sched)
    np.testing.assert_array_equal(mono.iterations, sched.iterations)
    buckets = {s.bucket for s in stats}
    assert len(buckets) > 1, f"no bucket shrink observed: {buckets}"
    assert all(s.elements == s.steps * s.bucket * revised_elements(10, 10)
               for s in stats)


def test_partial_pricing_agrees_with_full():
    """Partial pricing scans blocks (n+m > PARTIAL_BLOCK here, so the block
    schedule is real) and must reach the same final statuses as full
    pricing, monolithic and under the scheduler."""
    rng = np.random.default_rng(41)
    batch = random_lp_batch(rng, 24, 20, 110, feasible_start=False)
    full = solve_batched_revised(batch, pricing="dantzig")
    part = solve_batched_revised(batch, pricing="partial")
    _assert_same_certificates(full, part, rtol=1e-3)
    parts = solve_batched_revised_compacted(batch, segment_k=6,
                                            pricing="partial")
    _assert_same_certificates(full, parts, rtol=1e-3)
    # partial must actually have taken a different path somewhere (blocks
    # reorder entering choices on LPs with many candidate columns)
    assert not np.array_equal(full.iterations, part.iterations)


def test_revised_rejects_weighted_rules():
    batch = random_lp_batch(np.random.default_rng(0), 2, 4, 4)
    with pytest.raises(ValueError, match="tableau-only"):
        solve_batched_revised(batch, pricing="steepest_edge")
    with pytest.raises(ValueError, match="tableau-only"):
        canonicalize_revised_rule("devex")


# ---------------------------------------------------------------------------
# entry-point threading
# ---------------------------------------------------------------------------

def test_backend_on_solve_batched_and_chunking():
    rng = np.random.default_rng(3)
    batch = _mixed_batch(rng, B_each=16, m=8, n=8)
    base = solve_batched_revised(batch)
    via = solve_batched(batch, backend="revised")
    _assert_same_certificates(base, via)
    np.testing.assert_array_equal(base.iterations, via.iterations)
    chunked = solve_batched(batch, backend="revised", chunk_size=8,
                            sort_by_difficulty=True, compaction=True)
    _assert_same_certificates(base, chunked)


def test_backend_on_distributed_paths():
    rng = np.random.default_rng(13)
    batch = _mixed_batch(rng, B_each=8, m=6, n=6)
    mesh = make_mesh((1,), ("data",))
    base = solve_batched_revised(batch)
    sm = solve_shard_map(batch, mesh, backend="revised")
    _assert_same_certificates(base, sm)
    np.testing.assert_array_equal(base.iterations, sm.iterations)
    sms = solve_shard_map(batch, mesh, backend="revised", segment_k=4,
                          pricing="partial")
    np.testing.assert_array_equal(base.status, sms.status)


def test_backend_on_pallas_runs_tile_kernel():
    """backend="revised" on the Pallas entry point runs the real tile
    kernel (kernels/revised_tile.py): no fallback warning, statuses and
    pivot counts identical to the pure-JAX engine, objectives to f32
    tolerance (the dense basis inverse rounds differently than the
    engine's triangular solves)."""
    import warnings as _w
    from repro.kernels import ops

    rng = np.random.default_rng(29)
    batch = _mixed_batch(rng, B_each=8, m=6, n=6)
    base = solve_batched_revised(batch)
    ops._WARNED.discard("revised-fallback")
    ops._WARNED.discard("partial-pricing")
    with _w.catch_warnings():
        _w.simplefilter("error")       # any fallback warning is a failure
        pal = solve_batched_pallas(batch, backend="revised", tile_b=8)
    np.testing.assert_array_equal(base.status, pal.status)
    np.testing.assert_array_equal(base.iterations, pal.iterations)
    ok = base.status == OPTIMAL
    np.testing.assert_allclose(pal.objective[ok], base.objective[ok],
                               rtol=1e-4, atol=1e-4)
    # the tableau tile kernel still degrades partial->dantzig with its
    # one warning (full cost row is VMEM-resident there)
    with pytest.warns(UserWarning, match="partial pricing saves nothing"):
        ppal = solve_batched_pallas(batch, tile_b=8, pricing="partial")
    np.testing.assert_array_equal(solve_batched_jax(batch).status,
                                  ppal.status)


def test_unknown_backend_rejected_everywhere():
    batch = random_lp_batch(np.random.default_rng(0), 2, 4, 4)
    for fn in (lambda: solve_batched_jax(batch, backend="dense"),
               lambda: solve_batched(batch, backend="dense"),
               lambda: solve_batched_pallas(batch, backend="dense")):
        with pytest.raises(ValueError, match="unknown backend"):
            fn()


# ---------------------------------------------------------------------------
# work model + compaction auto-threshold satellite
# ---------------------------------------------------------------------------

def test_revised_element_model_beats_tableau_at_100():
    """The acceptance bar: at 100x100 (and up the Table-2 ladder) revised
    element updates per pivot undercut even the phase-compacted tableau's."""
    for (m, n) in [(100, 100), (150, 150), (100, 400)]:
        assert revised_elements(m, n) < tableau_elements(m, n, compacted=True)
        assert revised_elements(m, n, partial=True) < revised_elements(m, n)
    # flops model is honest: dense square stays tableau-territory, the
    # crossover appears as n grows past a few multiples of m
    assert revised_pivot_flops(100, 100) > tableau_pivot_flops(
        100, 100, compacted=True)
    xo = revised_crossover(100)
    assert xo is not None and 100 < xo < 1000
    assert revised_pivot_flops(100, xo, partial=True) < tableau_pivot_flops(
        100, xo, compacted=True)


def test_auto_compact_threshold():
    """Derived threshold: monotone in segment_k, never more eager than a
    gather can pay for at tiny segments, and a drop-in for the static 0.5
    (identical results, never more executed elements)."""
    assert auto_compact_threshold(1) < 0.5  # gather rivals a 1-pivot segment
    assert auto_compact_threshold(2) == pytest.approx(0.5)
    ts = [auto_compact_threshold(k) for k in (1, 2, 4, 8, 32, 200)]
    assert ts == sorted(ts) and ts[-1] <= 0.95
    rng = np.random.default_rng(37)
    batch = _mixed_batch(rng, B_each=24, m=8, n=8)
    stats_auto, stats_static = [], []
    auto = solve_batched_compacted(batch, segment_k=4,
                                   compact_threshold=None,
                                   stats_out=stats_auto)
    static = solve_batched_compacted(batch, segment_k=4,
                                     compact_threshold=0.5,
                                     stats_out=stats_static)
    np.testing.assert_array_equal(auto.status, static.status)
    np.testing.assert_array_equal(auto.iterations, static.iterations)
    assert (sum(s.elements for s in stats_auto)
            <= sum(s.elements for s in stats_static))


def test_revised_iteration_limit_reported():
    batch = random_lp_batch(np.random.default_rng(2), 6, 10, 10,
                            feasible_start=False)
    res = solve_batched_revised(batch, max_iters=2)
    assert (res.status == ITERATION_LIMIT).any()
    assert np.isnan(res.objective[res.status == ITERATION_LIMIT]).all()
