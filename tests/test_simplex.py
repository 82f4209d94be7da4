"""Batched simplex vs the float64 NumPy oracle (the GLPK stand-in)."""
import numpy as np
import pytest

from repro.core import (LPBatch, OPTIMAL, UNBOUNDED, INFEASIBLE,
                        random_lp_batch, random_sparse_lp_batch,
                        solve_batched, solve_batched_jax,
                        solve_batched_reference, max_chunk_size)

RNG = np.random.default_rng(7)


@pytest.mark.parametrize("m,n,feas", [
    (5, 5, True), (5, 5, False), (12, 8, True), (12, 8, False),
    (28, 28, True), (50, 40, True), (50, 40, False), (97, 71, True),
])
def test_matches_oracle(m, n, feas):
    batch = random_lp_batch(RNG, B=24, m=m, n=n, feasible_start=feas)
    ref = solve_batched_reference(batch)
    jx = solve_batched_jax(batch)
    assert (ref.status == jx.status).mean() >= 0.95
    ok = (ref.status == OPTIMAL) & (jx.status == OPTIMAL)
    assert ok.sum() > 0
    rel = np.abs(ref.objective[ok] - jx.objective[ok]) / np.abs(ref.objective[ok])
    assert rel.max() < 2e-3


def test_sparse_netlib_like():
    batch = random_sparse_lp_batch(RNG, B=16, m=71, n=97, density=0.08)
    ref = solve_batched_reference(batch)
    jx = solve_batched_jax(batch)
    ok = (ref.status == OPTIMAL) & (jx.status == OPTIMAL)
    rel = np.abs(ref.objective[ok] - jx.objective[ok]) / np.maximum(
        1.0, np.abs(ref.objective[ok]))
    assert rel.max() < 2e-3


def test_unbounded_detection():
    # maximize x1 with only a constraint on x2: unbounded
    A = np.array([[[0.0, 1.0]]])
    b = np.array([[1.0]])
    c = np.array([[1.0, 0.0]])
    batch = LPBatch.from_arrays(A, b, c)
    assert solve_batched_reference(batch).status[0] == UNBOUNDED
    assert solve_batched_jax(batch).status[0] == UNBOUNDED


def test_infeasible_detection():
    # x1 <= -1 with x1 >= 0: infeasible
    A = np.array([[[1.0]]])
    b = np.array([[-1.0]])
    c = np.array([[1.0]])
    batch = LPBatch.from_arrays(A, b, c)
    assert solve_batched_reference(batch).status[0] == INFEASIBLE
    assert solve_batched_jax(batch).status[0] == INFEASIBLE


def test_known_solution():
    # max x+y st x<=2, y<=3, x+y<=4  -> 4 at e.g. (1,3)
    A = np.array([[[1.0, 0.0], [0.0, 1.0], [1.0, 1.0]]])
    b = np.array([[2.0, 3.0, 4.0]])
    c = np.array([[1.0, 1.0]])
    res = solve_batched_jax(LPBatch.from_arrays(A, b, c))
    assert res.status[0] == OPTIMAL
    np.testing.assert_allclose(res.objective[0], 4.0, rtol=1e-5)


def test_chunked_driver_matches():
    batch = random_lp_batch(RNG, B=64, m=10, n=6)
    full = solve_batched_jax(batch)
    chunked = solve_batched(batch, chunk_size=17)
    np.testing.assert_array_equal(full.status, chunked.status)
    ok = full.status == OPTIMAL
    np.testing.assert_allclose(full.objective[ok], chunked.objective[ok],
                               rtol=1e-6)


def test_memory_planning_eq5():
    batch = random_lp_batch(RNG, B=4, m=10, n=6)
    n1 = max_chunk_size(batch, device_bytes=1 << 20)
    n2 = max_chunk_size(batch, device_bytes=1 << 22)
    assert n2 == 4 * n1 or abs(n2 - 4 * n1) <= 3  # linear in memory (Eq. 5)
    assert max_chunk_size(batch, device_bytes=1 << 30, n_devices=2) \
        == 2 * max_chunk_size(batch, device_bytes=1 << 30, n_devices=1)


class _FakeDevice:
    device_kind = "fake"

    def __init__(self, platform, stats):
        self.platform, self._stats = platform, stats

    def memory_stats(self):
        return self._stats


@pytest.mark.parametrize("platform,stats,want", [
    ("cpu", None, None),
    ("tpu", {"bytes_limit": 16_909_336_064}, 16_909_336_064),
    ("tpu", None, RuntimeError),
    ("tpu", {"bytes_in_use": 0}, RuntimeError),
])
def test_device_memory_bytes(platform, stats, want):
    """The planner reads the device's own HBM limit; a TPU that reports
    none is an error, never a guessed size."""
    from repro.core.batching import device_memory_bytes
    dev = _FakeDevice(platform, stats)
    if want is RuntimeError:
        with pytest.raises(RuntimeError, match="bytes_limit"):
            device_memory_bytes(dev)
    else:
        assert device_memory_bytes(dev) == want


def test_planned_chunks_are_equal_and_exact():
    """A batch over the memory plan splits into equal chunks (one compiled
    program) whose results equal the unchunked solve bitwise."""
    from repro.core.batching import BUDGET_FRACTION, PROGRAM_BYTES_FACTOR
    batch = random_lp_batch(RNG, B=50, m=10, n=6)
    per_lp = batch.bytes_per_lp() * PROGRAM_BYTES_FACTOR["tableau"]
    device_bytes = int(20 * per_lp / BUDGET_FRACTION) + 1
    assert max_chunk_size(batch, device_bytes) == 20
    sizes = []

    def solver(sub, **kw):
        sizes.append(sub.batch)
        return solve_batched_jax(sub, **kw)

    res = solve_batched(batch, solver=solver, device_bytes=device_bytes)
    assert sizes == [17, 17, 16]
    full = solve_batched_jax(batch)
    for f in ("status", "iterations", "objective", "x"):
        np.testing.assert_array_equal(getattr(res, f), getattr(full, f))


def test_rank1_update_snaps_cancellation_residue():
    """Entries that cancel to within CANCEL_ULPS rounding units of their
    operands become exactly 0 (what a correctly rounded divide leaves on an
    equality pair); a larger difference and a clean update are kept."""
    import jax.numpy as jnp
    from repro.core.lp import CANCEL_ULPS
    from repro.core.simplex import rank1_update
    eps = np.finfo(np.float32).eps
    T = np.array([[[3.0, 1.0, 2.0]]], np.float32)
    factor = np.ones((1, 1), np.float32)
    pivrow = np.array([[3.0 * (1 + eps), 0.5,
                        2.0 * (1 + (CANCEL_ULPS + 2) * eps)]], np.float32)
    out = np.asarray(rank1_update(jnp.asarray(T), jnp.asarray(factor),
                                  jnp.asarray(pivrow)))[0, 0]
    assert out[0] == 0.0
    assert out[1] == 0.5
    assert out[2] == np.float32(2.0) - pivrow[0, 2] != 0.0


def test_solution_feasibility():
    batch = random_lp_batch(RNG, B=32, m=12, n=8, feasible_start=False)
    res = solve_batched_jax(batch)
    ok = res.status == OPTIMAL
    act = np.einsum("bmn,bn->bm", np.abs(batch.A), np.abs(res.x)) \
        + np.abs(batch.b) + 1.0
    viol = (np.einsum("bmn,bn->bm", batch.A, res.x) - batch.b) / act
    assert viol[ok].max() <= 2e-4
    assert res.x[ok].min() >= -1e-5


def test_sorted_batching_matches_unsorted():
    rng = np.random.default_rng(21)
    f = random_lp_batch(rng, B=40, m=10, n=8, feasible_start=True)
    i = random_lp_batch(rng, B=40, m=10, n=8, feasible_start=False)
    mixed = LPBatch(A=np.concatenate([f.A, i.A]),
                    b=np.concatenate([f.b, i.b]),
                    c=np.concatenate([f.c, i.c]))
    perm = rng.permutation(80)
    mixed = LPBatch(A=mixed.A[perm], b=mixed.b[perm], c=mixed.c[perm])
    plain = solve_batched(mixed, chunk_size=16)
    srt = solve_batched(mixed, chunk_size=16, sort_by_difficulty=True)
    np.testing.assert_array_equal(plain.status, srt.status)
    ok = plain.status == OPTIMAL
    np.testing.assert_allclose(plain.objective[ok], srt.objective[ok],
                               rtol=1e-5)
