"""Compile the main solve path for a described TPU v5e, with no chip.

The TPU compiler ships with libtpu and compiles for a topology that is
described rather than attached.  These tests lower the Pallas tile kernels
with ``interpret=False`` (Mosaic) and the pure-JAX engines for one chip of
a ``v5e:2x2`` and check what only the chip's compiler can refuse: kernel
layouts, scoped VMEM, and whether a planned chunk fits the chip's HBM.
Nothing runs, so nothing here says anything about results or speed.

The topology is described inside a module fixture, never at import time:
only one process at a time may load libtpu, and every test worker imports
this file.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core import LPBatch
from repro.core.batching import BUDGET_FRACTION, max_chunk_size
from repro.core.lp import default_max_iters
from repro.kernels import pdhg_tile, revised_tile, simplex_tile

# HBM bytes_limit that one v5e chip reports through memory_stats() under
# jax 0.9.0 and libtpu 0.0.34; a described device reports no memory stats.
V5E_BYTES_LIMIT = 16_909_336_064
B = 4096


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no libtpu, or it is held by another process
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without one: keep the cache out of these compiles
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", enabled)


def _spec(sharding, shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _lowered(form, m, n, S):
    """Lower one tile-kernel form at (m, n) over B LPs with its default
    tile size, the way kernels/ops.py calls it."""
    f32, i32 = jnp.float32, jnp.int32
    if form == "tableau-whole":
        tb = simplex_tile.pick_tile_b(m, n)
        return simplex_tile.simplex_pallas.lower(
            S((B, m, n)), S((B, m)), S((B, n)), S((B, n)), m=m, n=n,
            tile_b=tb, max_iters=default_max_iters(m, n), interpret=False)
    if form.startswith("tableau-"):
        stage = form.split("-")[1]
        tb = simplex_tile.pick_tile_b(m, n)
        R, C = simplex_tile.full_dims(m, n)
        r, c = (R, C) if stage == "p1" else simplex_tile.compacted_dims(m, n)
        return simplex_tile.segment_pallas.lower(
            S((), i32), S((B, r, c)), S((B, R), i32), S((B, 1)),
            S((B, c), i32), S((B, c)), S((B, 1), i32), S((B, 1)),
            S((B, 1), i32), S((B, 1), i32), stage=stage, m=m, n=n,
            tile_b=tb, tol=1e-6, interpret=False)
    if form.startswith("revised-"):
        tb = revised_tile.pick_revised_tile_b(m, n)
        MC, NC2, NCP = revised_tile.revised_dims(m, n)
        return revised_tile.revised_segment_pallas.lower(
            S((), i32), S((B, MC, NC2)), S((B, NCP)), S((B, NCP)), S((B, 1)),
            S((B, MC, MC)), S((B, MC)), S((B, MC), i32), S((B, NCP), i32),
            S((B, 1), i32), S((B, 1), i32), S((B, 1), i32),
            stage=form.split("-")[1], m=m, n=n, tile_b=tb, tol=1e-6,
            K=revised_tile.auto_refactor_period(m, n), interpret=False)
    tb = pdhg_tile.pick_pdhg_tile_b(m, n)
    if form == "pdhg-whole":
        return pdhg_tile.pdhg_pallas.lower(
            S((B, m, n)), S((B, m)), S((B, n)), S((B, n)), m=m, n=n,
            tile_b=tb, max_iters=10_000, tol=1e-5, interpret=False)
    M, N = pdhg_tile.pdhg_dims(m, n)
    rows = dict(b=M, c=N, rsc=M, csc=N, eta=1, binf=1, cinf=1, ub=N, x=N,
                y=M, xs=N, ys=M, xr=N, yr=M, cnt=1, last=1, prev=1, omega=1)
    state = pdhg_tile.PdhgTileState(
        A=S((B, M, N)), **{k: S((B, w), f32) for k, w in rows.items()},
        phase=S((B, 1), i32), status=S((B, 1), i32), iters=S((B, 1), i32))
    return pdhg_tile.pdhg_segment_pallas.lower(
        S((), i32), state, m=m, n=n, tile_b=tb, tol=1e-5, interpret=False)


# 28x28 and 100x100 are the paper cells; 35x32 is canonical afiro, whose
# lane rows have no pad lanes, and 72x49 canonical sc50b_like
@pytest.mark.parametrize("m,n", [(28, 28), (100, 100), (35, 32), (72, 49)])
@pytest.mark.parametrize("form", [
    "tableau-whole", "tableau-p1", "tableau-p2", "revised-p1", "revised-p2",
    "pdhg-whole", "pdhg-segment"])
def test_tile_kernel_compiles_for_v5e(one_chip, form, m, n):
    S = lambda shape, dtype=jnp.float32: _spec(one_chip, shape, dtype)  # noqa: E731
    compiled = _lowered(form, m, n, S).compile()
    assert "tpu_custom_call" in compiled.as_text()


def _engine_core(engine, m, n, batch, S):
    from repro.core.pdhg import CHECK_EVERY, _solve_pdhg_core_state
    from repro.core.revised import (_solve_revised_core_state,
                                    auto_refactor_period)
    from repro.core.simplex import _solve_core, _solve_core_state
    args = (S((batch, m, n)), S((batch, m)), S((batch, n)), S((batch, n)))
    mi = default_max_iters(m, n)
    if engine == "tableau":
        return _solve_core.lower(*args, m=m, n=n, max_iters=mi, tol=1e-6,
                                 feas_tol=1e-5)
    if engine == "tableau-state":
        return _solve_core_state.lower(*args, None, None, None, m=m, n=n,
                                       max_iters=mi, tol=1e-6, feas_tol=1e-5)
    if engine == "revised":
        return _solve_revised_core_state.lower(
            *args, None, None, m=m, n=n, max_iters=mi, tol=1e-6,
            feas_tol=1e-5, refactor_period=auto_refactor_period(m, n),
            pricing="dantzig")
    return _solve_pdhg_core_state.lower(
        *args, None, None, None, m=m, n=n, max_iters=10_000, tol=1e-5,
        check_every=CHECK_EVERY)


@pytest.mark.parametrize("engine", ["tableau", "revised", "pdhg"])
def test_engine_compiles_for_v5e(one_chip, engine):
    S = lambda shape, dtype=jnp.float32: _spec(one_chip, shape, dtype)  # noqa: E731
    compiled = _engine_core(engine, 28, 28, B, S).compile()
    assert compiled.memory_analysis().temp_size_in_bytes >= 0


def test_planned_lp_100d_50k_chunk_fits_v5e_hbm(one_chip):
    """solve_batched's chunk plan for the paper's 100x100, B = 50,000 cell
    compiles to a program that fits one v5e's reported HBM limit (the
    unchunked batch does not: the compiler refuses it)."""
    S = lambda shape, dtype=jnp.float32: _spec(one_chip, shape, dtype)  # noqa: E731
    m = n = 100
    probe = LPBatch(A=np.ones((1, m, n)), b=np.ones((1, m)),
                    c=np.ones((1, n)))
    chunk = max_chunk_size(probe, V5E_BYTES_LIMIT)
    n_chunks = -(-50_000 // chunk)
    assert 1 < n_chunks <= 8
    chunk = -(-50_000 // n_chunks)     # solve_batched's equal chunks
    ma = _engine_core("tableau-state", m, n, chunk, S).compile() \
        .memory_analysis()
    used = (ma.temp_size_in_bytes + ma.argument_size_in_bytes
            + ma.output_size_in_bytes)
    assert used <= BUDGET_FRACTION * V5E_BYTES_LIMIT, (chunk, used)


def test_hyperbox_kernel_compiles_for_v5e(one_chip):
    from repro.kernels.hyperbox_kernel import hyperbox_pallas
    S = lambda shape, dtype=jnp.float32: _spec(one_chip, shape, dtype)  # noqa: E731
    compiled = hyperbox_pallas.lower(S((B, 28)), S((B, 28)), S((B, 28)),
                                     interpret=False).compile()
    assert "tpu_custom_call" in compiled.as_text()
