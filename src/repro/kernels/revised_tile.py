"""Revised-simplex tile kernel: BTRAN/FTRAN pivots on a VMEM-resident slab.

The pure-JAX engine (core/revised.py) prices and pivots on the basis
*factorization* — an LU of the basis matrix plus a product-form eta file —
instead of the dense tableau.  This kernel moves that hot loop into Pallas:
a ``(tile_b, ...)`` slab of LPs keeps its immutable data block, basis
inverse, basic solution, basis map and bound flags in VMEM and runs bounded
revised pivots (BTRAN -> pricing -> FTRAN -> sentinel min-ratio -> eta
append) without touching HBM between pivots.

Representation choice: ``lax.linalg.lu`` / ``triangular_solve`` do not lower
inside a Pallas kernel, so periodic refactorization is staged at *segment
boundaries* — the ISSUE's sanctioned alternative to an in-kernel LU.  The
host keeps a dense basis inverse ``Binv = B0^{-1}`` (computed from the same
``jax.lax.linalg`` LU path the engine uses, see `refactor_tile`), the kernel
applies it as two broadcast matvecs (BTRAN: ``Binv^T v``, FTRAN:
``Binv v``) and layers its *kernel-internal* eta file on top.  The eta file
never crosses the kernel boundary: a segment exits when the file fills
(``cnt == refactor_period``), the host refactorizes, and the next segment
starts from an empty file — exactly the engine's refactor-if-due schedule,
relocated to the segment clock.

Pivot semantics (pricing masks, rotating partial-pricing blocks, the bounded
sentinel ratio test, bound flips, phase-2 artificial pinning, the
``cnt += any(do_pivot)`` eta clock) mirror ``core.revised.revised_step``
statement-for-statement, re-expressed with one-hot lane masks instead of
gathers.  Parity contract: statuses match the pure-JAX engine exactly on the
test fixtures and objectives agree to f32 tolerance — bit-for-bit equality
is *not* promised because the dense inverse rounds differently from the
engine's triangular solves (the engine documents the same drift across its
own refactorization schedules).

Padded geometry (``revised_dims``): rows to a multiple of 8, candidate and
data lanes to multiples of 128.  Padding slots carry an identity slack basis
so their inverse stays finite, and are deactivated (ITERATION_LIMIT) before
the first segment.
"""
from __future__ import annotations

import functools
from typing import Any, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.lp import (BIG, INFEASIBLE, ITERATION_LIMIT, OPTIMAL,
                           UNBOUNDED)
from repro.obs.telemetry import (INT_LANE, INT_ROW_WIDTH, init_telemetry,
                                 lane_add, lane_set, tel_revised_update)
from repro.core.pricing import partial_geometry
from repro.core.revised import (auto_refactor_period, build_revised_state,
                                canonicalize_revised_rule,
                                inject_revised_warm)
from repro.core.simplex import _RUNNING, scatter_solution
from .tiling import VMEM_LIMIT_BYTES, compiler_params, pick_tile, round_up


def revised_dims(m: int, n: int):
    """Padded (rows, data lanes, candidate lanes) for an (m, n) LP:
    MC rows (multiple of 8), NC2 lanes over the full column layout
    (structurals | slacks | artificials), NCP lanes over the priced
    candidates (structurals | slacks)."""
    MC = round_up(max(m, 1), 8)
    NC2 = round_up(n + 2 * m, 128)
    NCP = round_up(n + m, 128)
    return MC, NC2, NCP


def pick_revised_tile_b(m: int, n: int, vmem_budget: int = VMEM_LIMIT_BYTES,
                        refactor_period: int | None = None,
                        dtype_size: int = 4) -> int:
    """Largest batch tile whose VMEM working set fits the budget: the
    double-buffered data block and basis inverse, the eta-file scratch,
    the one-hot pricing/FTRAN masks and the lane/row vectors (rows of
    width < 128 are lane-padded to 128)."""
    MC, NC2, NCP = revised_dims(m, n)
    ML = round_up(MC, 128)
    K = int(refactor_period or auto_refactor_period(m, n))
    block = MC * NC2 * dtype_size
    per_lp = (4 * MC * NC2 + 2 * MC * ML + 2 * MC * NCP + K * ML
              + 4 * (8 * NCP + 8 * ML)) * dtype_size
    return pick_tile(per_lp, block, vmem_budget)


class RevisedTileState(NamedTuple):
    """Padded revised-simplex state for the tile kernel; every leaf keeps the
    batch on axis 0 so the compaction scheduler's generic gathers apply
    unchanged.  ``Binv`` is the dense inverse of the *current* basis — valid
    exactly at segment boundaries, where the eta file is empty."""
    Abar: jax.Array    # (B, MC, NC2) immutable sign-adjusted columns
    cvec: jax.Array    # (B, NCP) phase-2 candidate costs (0 on pad lanes)
    ub: jax.Array      # (B, NCP) upper bounds (+inf beyond structurals)
    thr: jax.Array     # (B, 1) phase-1 feasibility threshold
    Binv: jax.Array    # (B, MC, MC) dense basis inverse (identity pad block)
    xB: jax.Array      # (B, MC) basic-variable values
    basis: jax.Array   # (B, MC) int32 column basic in each row
    onub: jax.Array    # (B, NCP) int32 nonbasic-at-upper flags
    phase: jax.Array   # (B, 1) int32
    status: jax.Array  # (B, 1) int32
    iters: jax.Array   # (B, 1) int32
    tel: Any = None    # optional obs.telemetry.TelemetryState ((B,) lanes)


# ---------------------------------------------------------------------------
# Host-side refactorization (the segment-boundary jax.lax.linalg path)
# ---------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("m", "n"))
def _refactor_binv(Abar_t, basis_t, *, m: int, n: int):
    """Dense inverse of the current basis matrix, gathered from the padded
    immutable columns: LU + two triangular solves against the row-permuted
    identity (the same ``jax.lax.linalg`` path as the engine's
    refactorization).  Padding rows/columns hold the identity so the pivot
    matvecs pass padded entries through untouched."""
    Ab = Abar_t[:, :m, :]
    bs = basis_t[:, :m].astype(jnp.int32)
    B0 = jnp.take_along_axis(Ab, bs[:, None, :], axis=2)
    lu, _, perm = lax.linalg.lu(B0)
    perm = perm.astype(jnp.int32)
    eye = jnp.broadcast_to(jnp.eye(m, dtype=Abar_t.dtype),
                           (B0.shape[0], m, m))
    pe = jnp.take_along_axis(eye, perm[:, :, None], axis=1)
    t = lax.linalg.triangular_solve(lu, pe, left_side=True, lower=True,
                                    unit_diagonal=True)
    Binv_m = lax.linalg.triangular_solve(lu, t, left_side=True, lower=False)
    MC = Abar_t.shape[1]
    mi = jnp.arange(MC)
    out = jnp.zeros((B0.shape[0], MC, MC), Abar_t.dtype)
    out = out.at[:, mi, mi].set(1.0)
    return out.at[:, :m, :m].set(Binv_m)


def refactor_tile(state: RevisedTileState, *, m: int, n: int
                  ) -> RevisedTileState:
    """Segment-boundary refactorization: recompute the dense basis inverse
    so the next kernel segment starts from an empty eta file.  On the
    telemetry trace this is where refactorizations are counted — the kernel
    relocates the engine's refactor-if-due schedule to the segment clock, so
    every boundary refactor of a still-running LP bumps its lane and resets
    the eta-file length (mirroring core.revised._refactor_state_jit)."""
    tel = state.tel
    if tel is not None:
        tel = tel_revised_update(tel, refactor=state.status == _RUNNING,
                                 eta_len=jnp.zeros_like(tel.eta_len))
    return state._replace(Binv=_refactor_binv(state.Abar, state.basis,
                                              m=m, n=n), tel=tel)


# ---------------------------------------------------------------------------
# State construction
# ---------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("m", "n", "tile_b"))
def _pad_tile_state(Abar, cvec, ub, thr, xB, basis, onub, phase, status,
                    iters, *, m: int, n: int, tile_b: int):
    B = Abar.shape[0]
    dtype = Abar.dtype
    MC, NC2, NCP = revised_dims(m, n)
    B_pad = round_up(max(B, 1), tile_b)
    idx = jnp.arange(m)
    Abar_t = jnp.zeros((B_pad, MC, NC2), dtype).at[:B, :m, :n + 2 * m].set(
        Abar)
    # padding slots get an identity slack basis: finite inverse, no work
    Abar_t = Abar_t.at[B:, idx, n + idx].set(1.0)
    cvec_t = jnp.zeros((B_pad, NCP), dtype).at[:B, :n + m].set(cvec)
    ub_t = jnp.full((B_pad, NCP), jnp.inf, dtype).at[:B, :n].set(ub)
    thr_t = jnp.zeros((B_pad, 1), dtype).at[:B, 0].set(thr)
    xB_t = jnp.zeros((B_pad, MC), dtype).at[:B, :m].set(xB)
    rowM = jnp.arange(MC, dtype=jnp.int32)
    basis_t = jnp.broadcast_to(n + rowM, (B_pad, MC)).astype(jnp.int32)
    basis_t = basis_t.at[:B, :m].set(basis.astype(jnp.int32))
    onub_t = jnp.zeros((B_pad, NCP), jnp.int32).at[:B, :n].set(
        onub.astype(jnp.int32))
    phase_t = jnp.full((B_pad, 1), 2, jnp.int32).at[:B, 0].set(phase)
    status_t = jnp.full((B_pad, 1), ITERATION_LIMIT,
                        jnp.int32).at[:B, 0].set(status)
    iters_t = jnp.zeros((B_pad, 1), jnp.int32).at[:B, 0].set(iters)
    Binv = _refactor_binv(Abar_t, basis_t, m=m, n=n)
    return RevisedTileState(Abar=Abar_t, cvec=cvec_t, ub=ub_t, thr=thr_t,
                            Binv=Binv, xB=xB_t, basis=basis_t, onub=onub_t,
                            phase=phase_t, status=status_t, iters=iters_t)


def build_revised_tile_state(A, b, c, ub=None, *, m: int, n: int,
                             tile_b: int, feas_tol: float,
                             warm_basis=None, warm_at_upper=None,
                             telemetry: bool = False) -> RevisedTileState:
    """Build (and optionally warm-inject) the engine's ``RevisedState``, then
    pad it onto the tile layout.  The engine's own builder and
    ``inject_revised_warm`` are reused verbatim so cold/skip/repair/cold-fallback
    decisions are identical to the pure-JAX path.  ``telemetry=True`` seeds
    zero counter lanes over the padded batch (padding slots stay zero — the
    scheduler's flush only reads real original indices)."""
    B = A.shape[0]
    st = build_revised_state(A, b, c, ub, feas_tol=feas_tol,
                             refactor_period=1)
    if warm_basis is not None:
        wonub = (jnp.zeros((B, n), bool) if warm_at_upper is None
                 else jnp.asarray(np.asarray(warm_at_upper), bool))
        st = inject_revised_warm(
            st, jnp.asarray(np.asarray(warm_basis), jnp.int32), wonub,
            m=m, n=n, feas_tol=feas_tol)
    state = _pad_tile_state(st.Abar, st.cvec, st.ub, st.thr, st.xB, st.basis,
                            st.onub, st.phase, st.status, st.iters,
                            m=m, n=n, tile_b=tile_b)
    if telemetry:
        state = state._replace(tel=init_telemetry(state.status.shape[0]))
    return state


# ---------------------------------------------------------------------------
# The segment kernel
# ---------------------------------------------------------------------------

def _revised_segment_kernel(steps_ref, Abar_ref, cvec_ref, ub_ref, thr_ref,
                            Binv_ref, xB_ref, basis_ref, onub_ref, phase_ref,
                            status_ref, iters_ref, *refs,
                            stage: str, m: int, n: int, tol: float,
                            K: int, rule: str, telemetry: bool = False):
    """Up to ``steps`` bounded revised pivots on one (tile_b, ...) slab.

    Mirrors ``core.revised.revised_step`` with the basis inverse applied as
    broadcast matvecs and the eta file kept kernel-internal: the loop exits
    when the stage's pending set empties, the step budget runs out, or the
    eta file fills (the host refactorizes between segments).

    With ``telemetry=True`` a packed (tile_b, INT_ROW_WIDTH) counter row
    rides the carry (extra input after ``iters``, extra output after ``it``)
    and every pivot bumps its lanes with the same masks the engine feeds
    ``tel_simplex_update`` / ``tel_revised_update``; the disabled trace is
    byte-identical to the pre-telemetry kernel."""
    # the eta file lives in two VMEM scratch refs indexed on their leading
    # axis (Pallas TPU lowers no dynamic_slice of a loop-carried value)
    *refs, etaR_ref, etaV_ref = refs
    if telemetry:
        ti_ref = refs[0]
        (xB_out, basis_out, onub_out, phase_out, status_out, iters_out,
         it_out, ti_out) = refs[1:]
    else:
        ti_ref = ti_out = None
        (xB_out, basis_out, onub_out, phase_out, status_out, iters_out,
         it_out) = refs
    steps = steps_ref[0, 0]
    Abar = Abar_ref[...]
    cvec = cvec_ref[...]
    ub = ub_ref[...]
    thr = thr_ref[...]
    Binv = Binv_ref[...]
    tile_b, MC, NC2 = Abar.shape
    NCP = cvec.shape[1]
    dtype = Abar.dtype
    ncand = n + m

    row = lax.broadcasted_iota(jnp.int32, (tile_b, MC), 1)
    lane = lax.broadcasted_iota(jnp.int32, (tile_b, NCP), 1)
    row3 = lax.broadcasted_iota(jnp.int32, (tile_b, MC, NCP), 1)
    lane3 = lax.broadcasted_iota(jnp.int32, (tile_b, MC, NC2), 2)
    row_ok = row < m
    col_ok = lane < ncand
    if rule == "partial":
        n_blocks, blk_sz = partial_geometry(ncand)

    def btran(v, cnt):
        # newest eta first, then the dense inverse transposed
        def body(i, v):
            k = cnt - 1 - i
            r = etaR_ref[k]
            ev = etaV_ref[k]
            dot = jnp.sum(ev * v, axis=1, keepdims=True)
            return jnp.where(row == r, dot, v)
        v = lax.fori_loop(0, cnt, body, v)
        return jnp.sum(Binv * v[:, :, None], axis=1)

    def ftran(a_e, cnt):
        # dense inverse first, then oldest eta first
        u = jnp.sum(Binv * a_e[:, None, :], axis=2)
        def body(k, v):
            r = etaR_ref[k]
            ev = etaV_ref[k]
            vr = jnp.sum(jnp.where(row == r, v, 0.0), axis=1, keepdims=True)
            upd = ev * vr
            return jnp.where(row == r, upd, v + upd)
        return lax.fori_loop(0, cnt, body, u)

    def pivot(carry):
        it, xB, basis, onub, phase, status, iters, cnt, ti = carry
        active = status == _RUNNING
        in_p1 = phase == 1
        in_p2 = phase == 2

        # ---- Step 1: BTRAN + pricing --------------------------------------
        # one-hot basic-lane map over the priced candidates (rows < m only);
        # 3-D masks are built from 3-D iotas and widened int rows — Mosaic
        # cannot lay out a boolean (tile_b, MC) -> (tile_b, MC, 1) broadcast
        basis3 = basis[:, :, None]
        hitc = (lane[:, None, :] == basis3) & (row3 < m)
        basis_c = jnp.sum(jnp.where(hitc, cvec[:, None, :], 0.0), axis=2)
        art = (basis >= ncand) & row_ok
        cB = jnp.where(in_p1, -art.astype(dtype),
                       jnp.where(row_ok, basis_c, 0.0))
        y = btran(cB, cnt)
        yA = jnp.sum(Abar[:, :, :NCP] * y[:, :, None], axis=1)
        d = jnp.where(in_p2, cvec, 0.0) - yA
        d = jnp.where(onub != 0, -d, d)
        is_basic = jnp.any(hitc & (basis3 < ncand), axis=1)
        d_full = jnp.where(col_ok & ~is_basic, d, -BIG)

        if rule == "partial":
            blk = iters % n_blocks
            lo = blk * blk_sz
            in_block = (lane >= lo) & (lane < lo + blk_sz)
            d_blk = jnp.where(in_block, d_full, -BIG)
            blk_max = jnp.max(d_blk, axis=1, keepdims=True)
            e_blk = jnp.argmax(d_blk, axis=1).astype(jnp.int32)[:, None]
            blk_improving = blk_max > tol
            e = jnp.where(blk_improving, e_blk,
                          jnp.argmax(d_full, axis=1).astype(jnp.int32)
                          [:, None])
            max_cost = jnp.where(blk_improving, blk_max,
                                 jnp.max(d_full, axis=1, keepdims=True))
        else:
            e = jnp.argmax(d_full, axis=1).astype(jnp.int32)[:, None]
            max_cost = jnp.max(d_full, axis=1, keepdims=True)

        is_opt = max_cost <= tol
        p1_obj = jnp.sum(jnp.where(art, xB, 0.0), axis=1, keepdims=True)
        p1_done = active & in_p1 & is_opt
        infeasible = p1_done & (p1_obj > thr)
        to_phase2 = p1_done & ~infeasible
        p2_done = active & in_p2 & is_opt

        # ---- Step 2: FTRAN + sentinel min-ratio ---------------------------
        a_e = jnp.sum(jnp.where(lane3 == e[:, :, None], Abar, 0.0), axis=2)
        u = ftran(a_e, cnt)
        onub_e = jnp.sum(jnp.where(lane == e, onub, 0), axis=1,
                         keepdims=True) != 0
        dir_e = jnp.where(onub_e, -1.0, 1.0).astype(dtype)
        ucol = dir_e * u
        valid_row = ucol > tol
        ratios = jnp.where(valid_row,
                           xB / jnp.where(valid_row, ucol, 1.0), BIG)
        ubB = jnp.min(jnp.where(hitc & (basis3 < n),
                                ub[:, None, :], jnp.inf), axis=2)
        hit_ub = (ucol < -tol) & jnp.isfinite(ubB)
        ratios = jnp.where(hit_ub,
                           (ubB - xB) / jnp.where(hit_ub, -ucol, 1.0),
                           ratios)
        pin = in_p2 & (basis >= ncand) & row_ok & (ucol < -tol)
        ratios = jnp.where(pin, 0.0, ratios)
        l = jnp.argmin(ratios, axis=1).astype(jnp.int32)[:, None]
        min_ratio = jnp.min(ratios, axis=1, keepdims=True)
        no_row = min_ratio >= BIG / 2

        wants_pivot = active & ~is_opt
        t_e = jnp.min(jnp.where((lane == e) & (lane < n), ub, jnp.inf),
                      axis=1, keepdims=True)
        do_flip = wants_pivot & (t_e < min_ratio)
        unbounded = wants_pivot & no_row & ~do_flip & in_p2
        stuck = wants_pivot & no_row & ~do_flip & in_p1
        do_pivot = wants_pivot & ~no_row & ~do_flip

        # ---- Step 3: O(m) update ------------------------------------------
        is_l = row == l
        ul = jnp.sum(jnp.where(is_l, u, 0.0), axis=1, keepdims=True)
        ul_safe = jnp.where(do_pivot, ul, 1.0)
        move = do_flip | do_pivot
        theta = jnp.where(do_flip, t_e,
                          jnp.where(do_pivot, min_ratio, 0.0))
        enter_val = jnp.where(onub_e, t_e - min_ratio, min_ratio)
        xB_new = jnp.where(is_l & do_pivot, enter_val, xB - theta * ucol)
        xB = jnp.where(move, xB_new, xB)

        is_e_n = (lane == e) & (lane < n)
        onub = jnp.where(do_flip & is_e_n, 1 - onub, onub)
        onub = jnp.where(do_pivot & is_e_n, 0, onub)
        jl = jnp.sum(jnp.where(is_l & row_ok, basis, 0), axis=1,
                     keepdims=True)
        hit_l = jnp.sum(jnp.where(is_l, hit_ub.astype(jnp.int32), 0),
                        axis=1, keepdims=True) != 0
        leave_up = do_pivot & hit_l & (jl < n)
        onub = jnp.where(leave_up & (lane == jl), 1, onub)

        r_eta = jnp.where(do_pivot, l, 0)
        eta = jnp.where(do_pivot, -u / ul_safe, 0.0)
        eta = jnp.where(row == r_eta,
                        jnp.where(do_pivot, 1.0 / ul_safe, 1.0), eta)
        etaR_ref[cnt] = r_eta
        etaV_ref[cnt] = eta
        cnt = cnt + jnp.any(do_pivot).astype(jnp.int32)

        basis = jnp.where(do_pivot & is_l, e, basis)
        status = jnp.where(infeasible, INFEASIBLE, status)
        status = jnp.where(unbounded, UNBOUNDED, status)
        status = jnp.where(stuck, ITERATION_LIMIT, status)
        status = jnp.where(p2_done, OPTIMAL, status)
        inc = active & ~p2_done & ~infeasible
        if ti is not None:
            # same masks core.revised.revised_step feeds tel_simplex_update;
            # attribution is on the pre-update phase (in_p1 captured above)
            ti = lane_add(ti, INT_LANE["phase1_iters"], inc & in_p1)
            ti = lane_add(ti, INT_LANE["phase2_iters"], inc & ~in_p1)
            ti = lane_add(ti, INT_LANE["phase1_pivots"], do_pivot & in_p1)
            ti = lane_add(ti, INT_LANE["phase2_pivots"], do_pivot & ~in_p1)
            ti = lane_add(ti, INT_LANE["bound_flips"], do_flip)
            ti = lane_add(ti, INT_LANE["degenerate_pivots"],
                          do_pivot & (min_ratio <= 0.0))
            # eta-file length is absolute (overwritten; the boundary
            # refactor zeroes it host-side in refactor_tile)
            ti = lane_set(ti, INT_LANE["eta_len"],
                          jnp.broadcast_to(cnt, (tile_b, 1)))
            if rule == "partial":
                ti = lane_add(ti, INT_LANE["block_rotations"],
                              active & ~blk_improving)
        phase = jnp.where(to_phase2, 2, phase)
        iters = iters + inc.astype(jnp.int32)
        return (it + 1, xB, basis, onub, phase, status, iters, cnt, ti)

    def cond(carry):
        it, xB, basis, onub, phase, status, iters, cnt, ti = carry
        if stage == "p1":
            pending = (status == _RUNNING) & (phase == 1)
        else:
            pending = status == _RUNNING
        return jnp.any(pending) & (it < steps) & (cnt < K)

    ti0 = ti_ref[...] if telemetry else None
    init = (jnp.int32(0), xB_ref[...], basis_ref[...], onub_ref[...],
            phase_ref[...], status_ref[...], iters_ref[...], jnp.int32(0),
            ti0)
    it, xB, basis, onub, phase, status, iters, _, ti = lax.while_loop(
        cond, pivot, init)

    xB_out[...] = xB
    basis_out[...] = basis
    onub_out[...] = onub
    phase_out[...] = phase
    status_out[...] = status
    iters_out[...] = iters
    it_out[...] = jnp.full((tile_b, 1), it, jnp.int32)
    if telemetry:
        ti_out[...] = ti


@functools.partial(
    jax.jit,
    static_argnames=("stage", "m", "n", "tile_b", "tol", "K", "interpret",
                     "pricing"))
def revised_segment_pallas(steps, Abar, cvec, ub, thr, Binv, xB, basis, onub,
                           phase, status, iters, tel_int=None, *, stage: str,
                           m: int, n: int, tile_b: int, tol: float, K: int,
                           interpret: bool,
                           pricing: str = "dantzig"):
    """Run up to ``steps`` revised pivots per tile (stage-aware early exit,
    eta-file boundary at ``K`` pivots).  Returns the mutated state leaves
    plus the per-LP executed-step count; call `refactor_tile` before the
    next segment.  ``tel_int`` is an optional (B, INT_ROW_WIDTH) packed
    telemetry row, carried through the kernel and returned as an eighth
    element when given."""
    B, MC, NC2 = Abar.shape
    NCP = cvec.shape[1]
    grid = (B // tile_b,)
    dtype = Abar.dtype
    telemetry = tel_int is not None
    vec = lambda i: (i, 0)
    cube = lambda i: (i, 0, 0)
    kernel = functools.partial(_revised_segment_kernel, stage=stage, m=m,
                               n=n, tol=float(tol), K=int(K),
                               rule=pricing, telemetry=telemetry)
    out_shape = [
        jax.ShapeDtypeStruct((B, MC), dtype),         # xB
        jax.ShapeDtypeStruct((B, MC), jnp.int32),     # basis
        jax.ShapeDtypeStruct((B, NCP), jnp.int32),    # onub
        jax.ShapeDtypeStruct((B, 1), jnp.int32),      # phase
        jax.ShapeDtypeStruct((B, 1), jnp.int32),      # status
        jax.ShapeDtypeStruct((B, 1), jnp.int32),      # iters
        jax.ShapeDtypeStruct((B, 1), jnp.int32),      # executed steps
    ]
    in_specs = [
        pl.BlockSpec((1, 1), lambda i: (0, 0)),             # steps
        pl.BlockSpec((tile_b, MC, NC2), cube),              # Abar
        pl.BlockSpec((tile_b, NCP), vec),                   # cvec
        pl.BlockSpec((tile_b, NCP), vec),                   # ub
        pl.BlockSpec((tile_b, 1), vec),                     # thr
        pl.BlockSpec((tile_b, MC, MC), cube),               # Binv
        pl.BlockSpec((tile_b, MC), vec),                    # xB
        pl.BlockSpec((tile_b, MC), vec),                    # basis
        pl.BlockSpec((tile_b, NCP), vec),                   # onub
        pl.BlockSpec((tile_b, 1), vec),                     # phase
        pl.BlockSpec((tile_b, 1), vec),                     # status
        pl.BlockSpec((tile_b, 1), vec),                     # iters
    ]
    out_specs = [
        pl.BlockSpec((tile_b, MC), vec),
        pl.BlockSpec((tile_b, MC), vec),
        pl.BlockSpec((tile_b, NCP), vec),
        pl.BlockSpec((tile_b, 1), vec),
        pl.BlockSpec((tile_b, 1), vec),
        pl.BlockSpec((tile_b, 1), vec),
        pl.BlockSpec((tile_b, 1), vec),
    ]
    operands = (Abar, cvec, ub, thr, Binv, xB, basis, onub, phase,
                status, iters)
    if telemetry:
        in_specs.append(pl.BlockSpec((tile_b, INT_ROW_WIDTH), vec))
        out_specs.append(pl.BlockSpec((tile_b, INT_ROW_WIDTH), vec))
        out_shape.append(jax.ShapeDtypeStruct((B, INT_ROW_WIDTH), jnp.int32))
        operands = operands + (tel_int,)
    steps_arr = jnp.full((1, 1), steps, jnp.int32)
    scratch = [pltpu.VMEM((int(K), tile_b, 1), jnp.int32),    # eta rows
               pltpu.VMEM((int(K), tile_b, MC), dtype)]       # eta columns
    return pl.pallas_call(kernel, grid=grid, in_specs=in_specs,
                          out_specs=out_specs, out_shape=out_shape,
                          scratch_shapes=scratch,
                          compiler_params=compiler_params(),
                          interpret=interpret)(steps_arr, *operands)


# ---------------------------------------------------------------------------
# Extraction
# ---------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("m", "n"))
def _extract_revised_tile_jit(state: RevisedTileState, *, m: int, n: int):
    """(x, obj, status, iters, y, z) off a segment-boundary state.  The dual
    BTRAN is a single ``Binv^T c_B`` matvec — valid because the eta file is
    empty at every boundary (the kernel never exports a non-empty file)."""
    ncand = n + m
    xBm = state.xB[:, :m]
    bm = state.basis[:, :m]
    x = scatter_solution(xBm, bm, n)
    cb = jnp.where(bm < ncand,
                   jnp.take_along_axis(state.cvec,
                                       jnp.minimum(bm, ncand - 1), axis=1),
                   0.0)
    obj = jnp.where(bm < n, cb * xBm, 0.0).sum(axis=1)
    onubn = state.onub[:, :n] != 0
    at_ub = jnp.where(onubn, state.ub[:, :n], 0.0)
    x = x + at_ub
    obj = obj + (state.cvec[:, :n] * at_ub).sum(axis=1)

    y_s = jnp.einsum("bij,bi->bj", state.Binv[:, :m, :m], cb)
    idx = jnp.arange(m)
    sign = state.Abar[:, idx, n + idx]
    y = sign * y_s
    z = state.cvec[:, :n] - jnp.einsum("bm,bmn->bn", y_s,
                                       state.Abar[:, :m, :n])
    status = jnp.where(state.status[:, 0] == _RUNNING, ITERATION_LIMIT,
                       state.status[:, 0])
    obj = jnp.where(status == OPTIMAL, obj, jnp.nan)
    opt = (status == OPTIMAL)[:, None]
    return (x, obj, status.astype(jnp.int8), state.iters[:, 0],
            jnp.where(opt, y, jnp.nan), jnp.where(opt, z, jnp.nan))


# ---------------------------------------------------------------------------
# Whole-solve driver
# ---------------------------------------------------------------------------

def revised_pallas(A, b, c, ub=None, *, m: int, n: int, tile_b: int,
                   max_iters: int, tol: float, feas_tol: float,
                   refactor_period: int | None = None,
                   pricing: str = "dantzig", interpret: bool,
                   warm_basis=None, warm_at_upper=None):
    """Whole-solve entry point: host loop of kernel segments with
    refactorization at every boundary.  Returns the standard 8-tuple
    (x, obj, status, iters, y, z, basis, onub) sliced to the caller's
    batch."""
    B = A.shape[0]
    rule = canonicalize_revised_rule(pricing)
    K = int(refactor_period or auto_refactor_period(m, n))
    state = build_revised_tile_state(A, b, c, ub, m=m, n=n, tile_b=tile_b,
                                     feas_tol=feas_tol,
                                     warm_basis=warm_basis,
                                     warm_at_upper=warm_at_upper)
    remaining = int(max_iters)
    while remaining > 0:
        if not bool((np.asarray(state.status) == _RUNNING).any()):
            break
        xB, basis, onub, phase, status, iters, it = revised_segment_pallas(
            jnp.int32(remaining), state.Abar, state.cvec, state.ub,
            state.thr, state.Binv, state.xB, state.basis, state.onub,
            state.phase, state.status, state.iters, stage="p2", m=m, n=n,
            tile_b=tile_b, tol=float(tol), K=K, interpret=interpret,
            pricing=rule)
        state = state._replace(xB=xB, basis=basis, onub=onub, phase=phase,
                               status=status, iters=iters)
        state = refactor_tile(state, m=m, n=n)
        remaining -= max(1, int(np.max(np.asarray(it))))
    x, obj, status, iters, y, z = _extract_revised_tile_jit(state, m=m, n=n)
    return (x[:B], obj[:B], status[:B], iters[:B], y[:B], z[:B],
            state.basis[:B, :m], state.onub[:B, :n] != 0)
