"""Pallas kernel sweeps (interpreted on the CPU) vs the pure-jnp oracle."""
import numpy as np
import pytest

from repro.core import OPTIMAL, random_lp_batch, solve_batched_reference
from repro.core.hyperbox import solve_hyperbox_ref
from repro.kernels import (pick_tile_b, solve_batched_pallas,
                           solve_hyperbox_pallas)

RNG = np.random.default_rng(11)


@pytest.mark.parametrize("m,n", [(5, 5), (10, 6), (28, 28), (50, 40)])
@pytest.mark.parametrize("feas", [True, False])
@pytest.mark.parametrize("tile_b", [1, 8, 32])
def test_simplex_kernel_sweep(m, n, feas, tile_b):
    batch = random_lp_batch(RNG, B=19, m=m, n=n, feasible_start=feas)
    ref = solve_batched_reference(batch)
    pal = solve_batched_pallas(batch, tile_b=tile_b)
    assert (ref.status == pal.status).mean() >= 0.95
    ok = (ref.status == OPTIMAL) & (pal.status == OPTIMAL)
    rel = np.abs(ref.objective[ok] - pal.objective[ok]) / np.abs(ref.objective[ok])
    assert rel.max() < 2e-3


def test_kernel_matches_jax_backend_bitwise_statuses():
    from repro.core import solve_batched_jax
    batch = random_lp_batch(RNG, B=33, m=12, n=8)
    jx = solve_batched_jax(batch)
    pal = solve_batched_pallas(batch, tile_b=8)
    np.testing.assert_array_equal(jx.status, pal.status)
    np.testing.assert_array_equal(jx.iterations, pal.iterations)


def test_tile_policy_respects_vmem():
    tb_small = pick_tile_b(300, 300, vmem_budget=2 << 20)
    tb_big = pick_tile_b(300, 300, vmem_budget=16 << 20)
    assert tb_small >= 1 and tb_big >= tb_small
    rows = 302
    cols = ((300 + 600 + 1) + 127) // 128 * 128
    assert tb_big * rows * cols * 4 <= (16 << 20) * 1.1


@pytest.mark.parametrize("n", [3, 7, 64, 130])
@pytest.mark.parametrize("dtype", ["float32"])
def test_hyperbox_kernel_sweep(n, dtype):
    lo = RNG.uniform(-4, 0, (57, n)).astype(dtype)
    hi = (lo + RNG.uniform(0.1, 3, (57, n))).astype(dtype)
    d = RNG.normal(size=(57, n)).astype(dtype)
    out = solve_hyperbox_pallas(lo, hi, d, tile_b=16)
    np.testing.assert_allclose(out, solve_hyperbox_ref(lo, hi, d),
                               rtol=2e-5, atol=1e-5)
