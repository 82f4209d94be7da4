"""Pallas TPU kernel: whole-solve batched simplex over VMEM-resident tiles.

CUDA design (paper Sec. 5) -> TPU realization:

* one CUDA block per LP, blocks scheduled over SMs
    -> one grid step per *tile* of ``tile_b`` LPs; the tile's tableaux live in
       VMEM for the entire solve (the paper keeps its tableau in global
       memory — VMEM residency is the TPU upgrade: zero HBM traffic between
       pivots, only the initial tableau in and the solution out).
* column-major tableau for warp-coalesced column operations
    -> the tableau tile is laid out (tile_b, rows, cols) with the *column*
       axis on the 128-lane dimension: Step-1 argmax (a "row operation") and
       the entering-column extraction (a "column operation") are both
       single-lane-axis reductions; the Step-3 rank-1 update is a fully
       aligned broadcast FMA. This is the same more-column-ops-than-row-ops
       argument as the paper's Sec. 5.3, transplanted to lanes.
* parallel reduction with MAX-sentinel (no warp divergence)
    -> ``jnp.where(col > tol, rhs/col, BIG)`` then lane-axis ``argmin`` — the
       VPU has no divergence, but the sentinel keeps the reduction dense and
       NaN-free exactly as in the paper.
* per-block early exit
    -> per-tile ``while_loop``: a tile whose LPs all terminated stops
       pivoting (grid steps execute sequentially per core, so early tiles
       hand their time to later ones); the segment kernels below additionally
       let core/compaction.py retire finished LPs *between* tiles — the
       bucket-ladder reconstruction of the paper's per-block exit.

Two-level work elimination (mirrors core/simplex.py):

* **Level 1 — phase-compacted tableaux.** The whole-solve kernel runs two
  chained while_loops: the combined two-phase step on the full
  (tile_b, R, C) tile until no LP in the tile still needs phase 1, then an
  in-register compaction that drops the m artificial columns and the phase-1
  objective row, then a pure phase-2 loop on the (tile_b, R2, C2) tile.
  On the lane-padded layout this saves whole 128-lane column blocks whenever
  round_up(n+m+1) < round_up(n+2m+1) (e.g. 100x100: 384 -> 256 lanes) and
  always saves the wasted phase-1-row FMAs.
* **Level 2 — segment kernels.** ``segment_pallas`` exposes the same loops
  as resumable K-pivot segments (state in/state out, dynamic step bound read
  from a scalar input) so the active-set compaction scheduler can shrink the
  batch between segments.

Every LP in the tile shares static shapes: full stage rows = m + 2 (two
objective rows: phase-2 and phase-1), cols = n + 2m + 1 padded to a lane
multiple, with the RHS moved to the *last padded* column so padding columns
(always zero, never allowed to enter) sit inertly in the middle; compacted
stage rows = m + 1, cols = n + m + 1 padded likewise.

Pricing (core/pricing.py) is threaded through both kernels as a static
``pricing`` argument: Step 1 scores candidates per rule, and the per-LP
weight vector — a (tile_b, C) lane-aligned row riding next to the tableau —
has its recurrence fused into `_tile_pivot`.  The whole-solve kernel
initializes weights in VMEM (nothing extra crosses HBM); the resumable
segment kernels carry them as explicit state so the active-set compaction
scheduler can gather them across bucket shrinks.
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core.lp import BIG, INFEASIBLE, ITERATION_LIMIT, OPTIMAL, UNBOUNDED
from repro.core.pricing import DEVEX_RESET
from repro.core.simplex import rank1_update
from repro.obs.telemetry import INT_LANE, INT_ROW_WIDTH, lane_add
from .tiling import VMEM_LIMIT_BYTES, compiler_params, pick_tile, round_up

_RUNNING = -1


def _iota(x, axis: int):
    """int32 index along ``axis`` broadcast to ``x``'s shape (2-D+ iotas
    are what Mosaic lowers)."""
    return jax.lax.broadcasted_iota(jnp.int32, x.shape, axis)


def compacted_dims(m: int, n: int) -> Tuple[int, int]:
    """(rows, lane-padded cols) of the phase-compacted tile."""
    return round_up(m + 1, 8), round_up(n + m + 1, 128)


def full_dims(m: int, n: int) -> Tuple[int, int]:
    """(rows, lane-padded cols) of the full two-phase tile."""
    return round_up(m + 2, 8), round_up(n + 2 * m + 1, 128)


def _tile_min_ratio(T, col_full, row_ids, pin_rows, basis, ub, lane,
                    *, m: int, tol: float):
    """Step 2: sentinel min-ratio over the constraint rows (lane-axis argmin).
    Returns (l, no_row, min_ratio).  ``pin_rows`` marks rows whose basic
    variable is an artificial pinned at zero (phase 2): when the entering
    column would grow one (negative coefficient), that row leaves at ratio 0
    instead — the same escape-prevention rule as core.simplex.simplex_step.

    Bounded case (b) rides in between (mirrors core.simplex._bounded_ratios):
    a basic variable the entering column drives *up* (col < -tol) binds at
    its own finite upper bound at ``(ub_B - rhs) / (-col)``.  ``ub`` is the
    (tile_b, C) lane row with +inf on every non-structural lane, so the
    basic bound is a min-select over the basis one-hot (min, not sum —
    inf * 0 poisons a sum) and all-+inf bounds reduce to the classic test."""
    C = T.shape[2]
    col = jnp.where(row_ids < m, col_full, 0.0)
    rhs = T[:, :, C - 1]                                        # (tile_b, R)
    valid = col > tol
    ratios = jnp.where(valid, rhs / jnp.where(valid, col, 1.0), BIG)
    b_rows = basis[:, :row_ids.shape[1]]
    hitb = lane[:, None, :] == b_rows[:, :, None]       # (tile_b, R, C)
    ubB = jnp.min(jnp.where(hitb, ub[:, None, :], jnp.inf), axis=2)
    hit = (col < -tol) & jnp.isfinite(ubB)
    ratios = jnp.where(hit, (ubB - rhs) / jnp.where(hit, -col, 1.0), ratios)
    ratios = jnp.where(pin_rows & (col < -tol), 0.0, ratios)
    min_ratio = jnp.min(ratios, axis=1, keepdims=True)
    l = jnp.argmin(ratios, axis=1)[:, None]                     # (tile_b, 1)
    no_row = min_ratio >= BIG / 2
    return l, no_row, min_ratio


def _tile_select(masked_cost, w, *, rule: str, tol: float):
    """Step 1 under a pricing rule, tile/broadcast form (lane-axis argmax of
    the rule's score; the optimality test stays the rule-independent max
    reduced cost).  Mirrors core.pricing.select_entering."""
    max_cost = jnp.max(masked_cost, axis=1, keepdims=True)
    if rule == "dantzig":
        e = jnp.argmax(masked_cost, axis=1)[:, None]
    else:
        improving = masked_cost > tol
        d = jnp.where(improving, masked_cost, 0.0)
        score = jnp.where(improving, d * d / w, -BIG)
        e = jnp.argmax(score, axis=1)[:, None]
    return e, max_cost


def _tile_flip(T, flip, ub, lane, col_full, e, t_e, wants_pivot, no_row,
               min_ratio):
    """Entering-bound flip (core.simplex._bound_moves, first move) on the
    lane-padded tile: when the entering variable hits its own finite upper
    bound before any basic variable binds (``t_e < min_ratio``), complement
    it in place — ``rhs -= t_e * col`` on every row (objective rows
    included) and negate the column — no pivot, no weight update (column
    negation is norm-invariant for the d^2/w pricing scores).  ``flip`` is
    the (tile_b, C) 0/1 complement-parity lane row."""
    C = T.shape[2]
    dtype = T.dtype
    do_flip = wants_pivot & (t_e < min_ratio)
    do_pivot = wants_pivot & ~no_row & ~do_flip
    is_rhs = (lane == C - 1).astype(dtype)                      # (tile_b, C)
    ub_e_term = jnp.where(do_flip, t_e, 0.0)
    T = T - (ub_e_term * col_full)[:, :, None] * is_rhs[:, None, :]
    flip_e = do_flip & (lane == e)
    sign = jnp.where(flip_e, -1.0, 1.0).astype(dtype)
    T = T * sign[:, None, :]
    flip = flip ^ flip_e.astype(flip.dtype)
    return T, flip, do_flip, do_pivot


def _tile_pivot(T, basis, w, flip, ub, col_full, row_ids, lane, e, l,
                do_pivot, *, m: int, n: int, rule: str):
    """Step 3: rank-1 pivot update + basis update, shared by the full and
    compacted tile steps (one copy keeps them bit-for-bit in sync with each
    other and with the pure-JAX `_pivot_update`).  The pricing-weight
    recurrence is fused here exactly as in the pure-JAX path: steepest-edge
    recomputes exact gammas off the live updated tile, devex applies its
    O(C) multiplicative update (with the non-priceable-column pin — see
    core.pricing.update_weights), dantzig passes weights through untouched.

    Leaving-at-upper complement (core.simplex._bound_moves, second move):
    a negative pivot element on a structural basic means the min ratio came
    from that variable hitting *its* upper bound.  Its tableau column is a
    unit vector, so complementing it reduces to rewriting the extracted
    pivot row — negate it, ``rhs_l -> ub_l - rhs_l``, restore the +1 basic
    entry — after which the pivot element is positive and the rank-1
    update proceeds classically."""
    dtype = T.dtype
    C = T.shape[2]
    is_l = row_ids == l                                         # (tile_b, R)
    pe = jnp.sum(col_full * is_l.astype(dtype), axis=1, keepdims=True)
    pivrow_raw = jnp.sum(T * is_l.astype(dtype)[:, :, None], axis=1)

    jl = jnp.sum(jnp.where(is_l & (row_ids < m), basis[:, :row_ids.shape[1]],
                           0), axis=1, keepdims=True)           # (tile_b, 1)
    need_comp = do_pivot & (pe < 0) & (jl < n)
    is_jl = lane == jl                                          # (tile_b, C)
    ub_jl = jnp.min(jnp.where(is_jl, ub, jnp.inf), axis=1, keepdims=True)
    comp_row = -pivrow_raw
    comp_row = comp_row + (jnp.where(need_comp, ub_jl, 0.0)
                           * (lane == C - 1).astype(dtype))
    comp_row = jnp.where(is_jl, 1.0, comp_row)
    pivrow_raw = jnp.where(need_comp, comp_row, pivrow_raw)
    pe = jnp.where(need_comp, -pe, pe)
    flip = flip ^ (need_comp & is_jl).astype(flip.dtype)

    pe_safe = jnp.where(do_pivot, pe, 1.0)
    pivrow = pivrow_raw / pe_safe
    T_new = rank1_update(T, col_full, pivrow)
    # replace (not re-add) the pivot row — matches the NumPy oracle.  The
    # (tile_b, R) and (tile_b, 1) masks are widened to the tile as f32
    # one-hots and compared there: Mosaic cannot lay out a boolean
    # (tile_b, R) -> (tile_b, R, 1) broadcast, and a select keeps the
    # replaced entries bit-exact where a one-hot multiply-add would not
    T_new = jnp.where(is_l.astype(dtype)[:, :, None] > 0.5,
                      pivrow[:, None, :], T_new)
    T = jnp.where(do_pivot.astype(dtype)[:, :, None] > 0.5, T_new, T)

    if rule == "steepest_edge":
        con = jnp.where((row_ids < m)[:, :, None], T, 0.0)
        w_new = 1.0 + jnp.sum(con * con, axis=1)
        w = jnp.where(do_pivot, w_new, w)
    elif rule == "devex":
        onehot_e = (lane == e).astype(dtype)
        w_e = jnp.sum(w * onehot_e, axis=1, keepdims=True)
        # leaving variable's column: basis at the pivot row, pre-update
        # (basis keeps the full-stage row height across both stages — slice
        # it to this tile's rows before masking with the tile-height iotas)
        b_rows = basis[:, :row_ids.shape[1]]
        r = jnp.sum(jnp.where(is_l & (row_ids < m), b_rows, 0), axis=1,
                    keepdims=True)
        w_new = jnp.maximum(w, pivrow * pivrow * w_e)
        w_leave = jnp.maximum(w_e / (pe_safe * pe_safe), 1.0)
        w_new = jnp.where(lane == r, w_leave, w_new)
        w_new = jnp.where(lane == e, 1.0, w_new)
        w_new = jnp.where(lane < n + m, w_new, 1.0)
        overflow = jnp.max(w_new, axis=1, keepdims=True) > DEVEX_RESET
        w_new = jnp.where(overflow, 1.0, w_new)
        w = jnp.where(do_pivot, w_new, w)

    basis_rows = jax.lax.broadcasted_iota(jnp.int32, basis.shape, 1)
    basis = jnp.where(do_pivot & (basis_rows == l) & (basis_rows < m),
                      e.astype(jnp.int32), basis)
    return T, basis, w, flip


def _tile_step(T, basis, w, flip, ub, phase, status, iters, ti=None, *,
               m: int, n: int, tol: float, thr, rule: str = "dantzig"):
    """One combined two-phase pivot across the (tile_b, R, C) tile.
    Broadcast/reduce formulation (no einsum) so every op lowers to
    VPU-friendly elementwise + lane reductions inside Pallas.

    ``ti`` is an optional (tile_b, INT_ROW_WIDTH) packed telemetry row
    (obs.telemetry.tel_to_rows); when present the step's counter lanes are
    bumped in-kernel and the row is returned as an eighth element — the
    ``ti=None`` trace is unchanged."""
    tile_b, R, C = T.shape
    dtype = T.dtype
    active = status == _RUNNING

    lane = jax.lax.broadcasted_iota(jnp.int32, (tile_b, C), 1)
    row_ids = jax.lax.broadcasted_iota(jnp.int32, (tile_b, R), 1)

    # ---- Step 1: entering column (pricing rule, lane-axis argmax) ----------
    cost = jnp.where((phase == 1), T[:, m + 1, :], T[:, m, :])
    col_ok = lane < (n + m)
    masked_cost = jnp.where(col_ok, cost, -BIG)
    e, max_cost = _tile_select(masked_cost, w, rule=rule, tol=tol)
    is_opt = max_cost <= tol

    p1_obj = T[:, m + 1, C - 1][:, None]
    p1_done = active & (phase == 1) & is_opt
    infeasible = p1_done & (p1_obj > thr)
    to_phase2 = p1_done & ~infeasible
    p2_done = active & (phase == 2) & is_opt

    # ---- Steps 2 + 3 --------------------------------------------------------
    onehot_e = (lane == e).astype(dtype)                        # (tile_b, C)
    col_full = jnp.sum(T * onehot_e[:, None, :], axis=2)        # (tile_b, R)
    pin_rows = (phase == 2) & (basis[:, :R] >= n + m) & (row_ids < m)
    l, no_row, min_ratio = _tile_min_ratio(T, col_full, row_ids, pin_rows,
                                           basis, ub, lane, m=m, tol=tol)

    wants_pivot = active & ~is_opt
    t_e = jnp.min(jnp.where(lane == e, ub, jnp.inf), axis=1, keepdims=True)
    T, flip, do_flip, do_pivot = _tile_flip(
        T, flip, ub, lane, col_full, e, t_e, wants_pivot, no_row, min_ratio)
    unbounded = wants_pivot & no_row & ~do_flip & (phase == 2)
    stuck = wants_pivot & no_row & ~do_flip & (phase == 1)

    T, basis, w, flip = _tile_pivot(T, basis, w, flip, ub, col_full, row_ids,
                                    lane, e, l, do_pivot, m=m, n=n, rule=rule)

    status = jnp.where(infeasible, INFEASIBLE, status)
    status = jnp.where(unbounded, UNBOUNDED, status)
    status = jnp.where(stuck, ITERATION_LIMIT, status)
    status = jnp.where(p2_done, OPTIMAL, status)
    inc = active & ~p2_done & ~infeasible
    if ti is not None:
        # same masks the engine feeds tel_simplex_update; attribution is on
        # the pre-update phase (captured before the to_phase2 write below)
        in_p1 = phase == 1
        ti = lane_add(ti, INT_LANE["phase1_iters"], inc & in_p1)
        ti = lane_add(ti, INT_LANE["phase2_iters"], inc & ~in_p1)
        ti = lane_add(ti, INT_LANE["phase1_pivots"], do_pivot & in_p1)
        ti = lane_add(ti, INT_LANE["phase2_pivots"], do_pivot & ~in_p1)
        ti = lane_add(ti, INT_LANE["bound_flips"], do_flip)
        ti = lane_add(ti, INT_LANE["degenerate_pivots"],
                      do_pivot & (min_ratio <= 0.0))
    phase = jnp.where(to_phase2, 2, phase)
    iters = iters + inc.astype(jnp.int32)
    if ti is not None:
        return T, basis, w, flip, phase, status, iters, ti
    return T, basis, w, flip, phase, status, iters


def _tile_step_p2(T, basis, w, flip, ub, phase, status, iters, ti=None, *,
                  m: int, n: int, tol: float, rule: str = "dantzig"):
    """One phase-2 pivot on the **compacted** (tile_b, R2, C2) tile: no
    artificial columns, no phase-1 row, no phase bookkeeping.  ``ti`` is the
    same optional packed telemetry row as `_tile_step`."""
    tile_b, R2, C2 = T.shape
    dtype = T.dtype
    active = (status == _RUNNING) & (phase == 2)

    lane = jax.lax.broadcasted_iota(jnp.int32, (tile_b, C2), 1)
    row_ids = jax.lax.broadcasted_iota(jnp.int32, (tile_b, R2), 1)

    cost = T[:, m, :]
    col_ok = lane < (n + m)
    masked_cost = jnp.where(col_ok, cost, -BIG)
    e, max_cost = _tile_select(masked_cost, w, rule=rule, tol=tol)
    is_opt = max_cost <= tol
    p2_done = active & is_opt

    onehot_e = (lane == e).astype(dtype)
    col_full = jnp.sum(T * onehot_e[:, None, :], axis=2)
    # the basis keeps full-stage column indices, so >= n+m still identifies
    # basic artificials on the compacted tile (every LP here is phase 2)
    pin_rows = (basis[:, :R2] >= n + m) & (row_ids < m)
    l, no_row, min_ratio = _tile_min_ratio(T, col_full, row_ids, pin_rows,
                                           basis, ub, lane, m=m, tol=tol)

    wants_pivot = active & ~is_opt
    t_e = jnp.min(jnp.where(lane == e, ub, jnp.inf), axis=1, keepdims=True)
    T, flip, do_flip, do_pivot = _tile_flip(
        T, flip, ub, lane, col_full, e, t_e, wants_pivot, no_row, min_ratio)
    unbounded = wants_pivot & no_row & ~do_flip

    T, basis, w, flip = _tile_pivot(T, basis, w, flip, ub, col_full, row_ids,
                                    lane, e, l, do_pivot, m=m, n=n, rule=rule)

    status = jnp.where(unbounded, UNBOUNDED, status)
    status = jnp.where(p2_done, OPTIMAL, status)
    inc = active & ~p2_done
    if ti is not None:
        # every LP on the compacted tile is phase 2
        ti = lane_add(ti, INT_LANE["phase2_iters"], inc)
        ti = lane_add(ti, INT_LANE["phase2_pivots"], do_pivot)
        ti = lane_add(ti, INT_LANE["bound_flips"], do_flip)
        ti = lane_add(ti, INT_LANE["degenerate_pivots"],
                      do_pivot & (min_ratio <= 0.0))
    iters = iters + inc.astype(jnp.int32)
    if ti is not None:
        return T, basis, w, flip, phase, status, iters, ti
    return T, basis, w, flip, phase, status, iters


def _compact_tile(T, *, m: int, n: int):
    """Drop artificial columns + phase-1 row on the lane-padded layout:
    (B, R, C) -> (B, R2, C2) with the RHS moved to the new last lane.
    Works on kernel tile values and on batched host arrays alike."""
    C = T.shape[2]
    R2, C2 = compacted_dims(m, n)
    # masked select over an aligned leading block (no scatter: Pallas TPU
    # cannot lower one); the RHS column is moved as a lane one-hot sum
    rhs = jnp.sum(jnp.where(_iota(T, 2) == C - 1, T, 0.0), axis=2)[:, :R2]
    T2 = T[:, :R2, :C2]
    row = _iota(T2, 1)
    lane = _iota(T2, 2)
    T2 = jnp.where(lane < n + m, T2, 0.0)
    T2 = jnp.where(lane == C2 - 1, rhs[:, :, None], T2)
    return jnp.where(row < m + 1, T2, 0.0)


def _compact_tile_weights(w, *, m: int, n: int):
    """Phase compaction of the lane-padded pricing-weight row:
    (B, C) -> (B, C2).  Dropped/pad lanes get weight 1 (never priced —
    they sit outside the ``lane < n+m`` entering mask)."""
    return _compact_tile_lane(w, 1.0, m=m, n=n)


def _compact_tile_lane(v, fill, *, m: int, n: int):
    """Phase compaction of a generic lane row (bound vector: fill=+inf,
    flip parity: fill=0): (B, C) -> (B, C2) keeping the n+m live lanes."""
    _, C2 = compacted_dims(m, n)
    v2 = v[:, :C2]
    return jnp.where(_iota(v2, 1) < n + m, v2, jnp.asarray(fill, v.dtype))


def _init_tile_weights(T, row_ids, *, m: int, rule: str):
    """In-VMEM weight init (mirrors core.pricing.init_weights on the padded
    layout): exact gammas for steepest_edge, ones otherwise."""
    if rule == "steepest_edge":
        con = jnp.where((row_ids < m)[:, :, None], T, 0.0)
        return 1.0 + jnp.sum(con * con, axis=1)
    return jnp.ones(T.shape[:1] + (T.shape[2],), T.dtype)


def _pad_lanes(v, width: int):
    """Zero-pad a (tile_b, k) lane row to ``width`` lanes (Mosaic has no
    zero-size vectors, so an already full row is returned as is)."""
    if v.shape[1] == width:
        return v
    return jnp.concatenate(
        [v, jnp.zeros((v.shape[0], width - v.shape[1]), v.dtype)], axis=1)


def _extract_tile(T2, basis, status, flip, ub, *, m: int, n: int, n_pad: int,
                  m_pad: int):
    """In-kernel solution extraction from the compacted tile: only
    (x, obj) and the dual certificate leave VMEM — the paper's "D2H-res"
    transfer shape.  The phase-2 objective row holds the certificate for
    free (see core.simplex.extract_duals): slack entries are -y, structural
    entries are the reduced costs z; both are NaN off-OPTIMAL.

    Flipped (complemented) structural lanes store ``ub - x``: map the
    primal back with ``x = ub - x_stored`` (a nonbasic-at-upper variable
    stores 0 and reads back ub) and negate the reduced cost, whose flagged
    sign means "profitable to *decrease* off the bound"."""
    tile_b, R2, C2 = T2.shape
    rhs = T2[:, :, C2 - 1]                                     # (tile_b, R2)
    b2 = basis[:, :R2]
    xcols = jax.lax.broadcasted_iota(jnp.int32, (tile_b, R2, n_pad), 2)
    hit = (b2[:, :, None] == xcols) & (b2[:, :, None] < n)
    x = jnp.sum(jnp.where(hit, rhs[:, :, None], 0.0), axis=1)
    flip_x = flip[:, :n_pad] != 0
    x = jnp.where(flip_x, ub[:, :n_pad] - x, x)
    obj = -T2[:, m, C2 - 1][:, None]
    opt = status == OPTIMAL
    obj = jnp.where(opt, obj, jnp.nan)
    y = _pad_lanes(-T2[:, m, n:n + m], m_pad)
    z = _pad_lanes(T2[:, m, :n], n_pad)
    z = jnp.where(flip_x, -z, z)
    y = jnp.where(opt, y, jnp.nan)
    z = jnp.where(opt, z, jnp.nan)
    return x, obj, y, z


def _simplex_kernel(T_ref, basis_ref, phase_ref, thr_ref, ub_ref,
                    x_ref, obj_ref, status_ref, iters_ref, y_ref, z_ref,
                    *, m: int, n: int, tol: float, max_iters: int,
                    rule: str = "dantzig"):
    """Whole-solve kernel: loop 1 (combined step, full tile) -> in-register
    phase compaction -> loop 2 (phase-2 step, compacted tile) -> extraction.
    The loops share one ``max_iters`` budget (loop 2 resumes loop 1's step
    counter), mirroring core.simplex.solve_two_phase.  Pricing weights and
    the bound-flip parity row are initialized and carried entirely in VMEM —
    selecting a smarter rule or adding variable bounds changes zero extra
    HBM traffic beyond the (tile_b, C) bound lane row itself."""
    T = T_ref[...]
    basis = basis_ref[...]
    phase = phase_ref[...]
    thr = thr_ref[...]
    ub = ub_ref[...]
    tile_b, R, C = T.shape
    status = jnp.full((tile_b, 1), _RUNNING, jnp.int32)
    iters = jnp.zeros((tile_b, 1), jnp.int32)
    row_ids = jax.lax.broadcasted_iota(jnp.int32, (tile_b, R), 1)
    w = _init_tile_weights(T, row_ids, m=m, rule=rule)
    flip = jnp.zeros((tile_b, C), jnp.int32)

    # ---- loop 1: full tile until no LP in the tile still needs phase 1 -----
    def cond1(state):
        T, basis, w, flip, phase, status, iters, it = state
        pending = (status == _RUNNING) & (phase == 1)
        return jnp.any(pending) & (it < max_iters)

    def body1(state):
        T, basis, w, flip, phase, status, iters, it = state
        T, basis, w, flip, phase, status, iters = _tile_step(
            T, basis, w, flip, ub, phase, status, iters, m=m, n=n, tol=tol,
            thr=thr, rule=rule)
        return T, basis, w, flip, phase, status, iters, it + 1

    T, basis, w, flip, phase, status, iters, it1 = jax.lax.while_loop(
        cond1, body1,
        (T, basis, w, flip, phase, status, iters, jnp.int32(0)))
    status = jnp.where((status == _RUNNING) & (phase == 1), ITERATION_LIMIT,
                       status)

    # ---- phase compaction + loop 2 on the small tile ------------------------
    T2 = _compact_tile(T, m=m, n=n)
    w2 = _compact_tile_weights(w, m=m, n=n)
    flip2 = _compact_tile_lane(flip, 0, m=m, n=n)
    ub2 = _compact_tile_lane(ub, jnp.inf, m=m, n=n)

    def cond2(state):
        T2, basis, w2, flip2, phase, status, iters, it = state
        return jnp.any(status == _RUNNING) & (it < max_iters)

    def body2(state):
        T2, basis, w2, flip2, phase, status, iters, it = state
        T2, basis, w2, flip2, phase, status, iters = _tile_step_p2(
            T2, basis, w2, flip2, ub2, phase, status, iters, m=m, n=n,
            tol=tol, rule=rule)
        return T2, basis, w2, flip2, phase, status, iters, it + 1

    T2, basis, w2, flip2, phase, status, iters, _ = jax.lax.while_loop(
        cond2, body2, (T2, basis, w2, flip2, phase, status, iters, it1))
    status = jnp.where(status == _RUNNING, ITERATION_LIMIT, status)

    x, obj, y, z = _extract_tile(T2, basis, status, flip2, ub2, m=m, n=n,
                                 n_pad=x_ref.shape[1], m_pad=y_ref.shape[1])
    x_ref[...] = x
    obj_ref[...] = obj
    status_ref[...] = status
    iters_ref[...] = iters
    y_ref[...] = y
    z_ref[...] = z


def _segment_kernel(steps_ref, T_ref, basis_ref, w_ref, flip_ref, ub_ref,
                    phase_ref, thr_ref, status_ref, iters_ref, *refs,
                    stage: str, m: int, n: int, tol: float,
                    rule: str = "dantzig", telemetry: bool = False):
    """Resumable K-pivot segment for the compaction scheduler: state in,
    state out (pricing weights and the bound-flip parity row included, so
    bucket gathers between segments preserve the rule's recurrence and the
    complement bookkeeping), step bound read from a scalar input (no
    recompile per K).  The bound lane row is read-only (input, no output).

    With ``telemetry=True`` one extra (tile_b, INT_ROW_WIDTH) packed counter
    row rides the carry (input after ``iters``, output after ``it``) and the
    pivot steps bump its lanes in VMEM; the default trace is byte-identical
    to the pre-telemetry kernel."""
    if telemetry:
        ti_ref = refs[0]
        (T_out, basis_out, w_out, flip_out, phase_out, status_out,
         iters_out, it_out, ti_out) = refs[1:]
    else:
        ti_ref = ti_out = None
        (T_out, basis_out, w_out, flip_out, phase_out, status_out,
         iters_out, it_out) = refs
    steps = steps_ref[0, 0]
    T = T_ref[...]
    basis = basis_ref[...]
    w = w_ref[...]
    flip = flip_ref[...]
    ub = ub_ref[...]
    phase = phase_ref[...]
    thr = thr_ref[...]
    status = status_ref[...]
    iters = iters_ref[...]
    ti0 = ti_ref[...] if telemetry else None
    tile_b = T.shape[0]

    # the telemetry row rides the carry as a pytree leaf; ``None`` is an
    # empty subtree, so the disabled loop carries exactly today's state
    if stage == "p1":
        def cond(state):
            T, basis, w, flip, phase, status, iters, ti, it = state
            pending = (status == _RUNNING) & (phase == 1)
            return jnp.any(pending) & (it < steps)

        def body(state):
            T, basis, w, flip, phase, status, iters, ti, it = state
            out = _tile_step(
                T, basis, w, flip, ub, phase, status, iters, ti, m=m, n=n,
                tol=tol, thr=thr, rule=rule)
            T, basis, w, flip, phase, status, iters = out[:7]
            ti = out[7] if telemetry else None
            return T, basis, w, flip, phase, status, iters, ti, it + 1
    else:
        def cond(state):
            T, basis, w, flip, phase, status, iters, ti, it = state
            return jnp.any(status == _RUNNING) & (it < steps)

        def body(state):
            T, basis, w, flip, phase, status, iters, ti, it = state
            out = _tile_step_p2(
                T, basis, w, flip, ub, phase, status, iters, ti, m=m, n=n,
                tol=tol, rule=rule)
            T, basis, w, flip, phase, status, iters = out[:7]
            ti = out[7] if telemetry else None
            return T, basis, w, flip, phase, status, iters, ti, it + 1

    T, basis, w, flip, phase, status, iters, ti, it = jax.lax.while_loop(
        cond, body,
        (T, basis, w, flip, phase, status, iters, ti0, jnp.int32(0)))

    T_out[...] = T
    basis_out[...] = basis
    w_out[...] = w
    flip_out[...] = flip
    phase_out[...] = phase
    status_out[...] = status
    iters_out[...] = iters
    it_out[...] = jnp.full((tile_b, 1), it, jnp.int32)
    if telemetry:
        ti_out[...] = ti


@functools.partial(
    jax.jit,
    static_argnames=("stage", "m", "n", "tile_b", "tol", "interpret",
                     "pricing"))
def segment_pallas(steps, T, basis, w, flip, ub, phase, thr, status, iters,
                   tel_int=None, *, stage: str, m: int, n: int, tile_b: int,
                   tol: float, interpret: bool,
                   pricing: str = "dantzig"):
    """Run one scheduler segment (<= ``steps`` pivots) over all tiles.
    Returns (T, basis, w, flip, phase, status, iters, it) with ``it`` the
    per-tile executed step count broadcast over the tile's rows.  ``ub`` is
    carried by the scheduler's state (gathered across bucket shrinks) but is
    read-only inside the kernel.

    ``tel_int`` is an optional (B, INT_ROW_WIDTH) packed telemetry row
    (obs.telemetry.tel_to_rows); when given it is carried through the kernel,
    its counter lanes bumped per pivot, and returned as a ninth element."""
    B, R_, C_ = T.shape
    grid = (B // tile_b,)
    Rb = basis.shape[1]
    Cw = w.shape[1]
    Cl = flip.shape[1]
    telemetry = tel_int is not None
    steps_arr = jnp.full((1, 1), steps, jnp.int32)
    kernel = functools.partial(_segment_kernel, stage=stage, m=m, n=n,
                               tol=float(tol), rule=pricing,
                               telemetry=telemetry)
    vec = lambda i: (i, 0)  # noqa: E731
    in_specs = [
        pl.BlockSpec((1, 1), lambda i: (0, 0)),
        pl.BlockSpec((tile_b, R_, C_), lambda i: (i, 0, 0)),
        pl.BlockSpec((tile_b, Rb), vec),
        pl.BlockSpec((tile_b, Cw), vec),
        pl.BlockSpec((tile_b, Cl), vec),
        pl.BlockSpec((tile_b, Cl), vec),
        pl.BlockSpec((tile_b, 1), vec),
        pl.BlockSpec((tile_b, 1), vec),
        pl.BlockSpec((tile_b, 1), vec),
        pl.BlockSpec((tile_b, 1), vec),
    ]
    out_specs = [
        pl.BlockSpec((tile_b, R_, C_), lambda i: (i, 0, 0)),
        pl.BlockSpec((tile_b, Rb), vec),
        pl.BlockSpec((tile_b, Cw), vec),
        pl.BlockSpec((tile_b, Cl), vec),
        pl.BlockSpec((tile_b, 1), vec),
        pl.BlockSpec((tile_b, 1), vec),
        pl.BlockSpec((tile_b, 1), vec),
        pl.BlockSpec((tile_b, 1), vec),
    ]
    out_shape = [
        jax.ShapeDtypeStruct((B, R_, C_), T.dtype),
        jax.ShapeDtypeStruct((B, Rb), jnp.int32),
        jax.ShapeDtypeStruct((B, Cw), T.dtype),
        jax.ShapeDtypeStruct((B, Cl), jnp.int32),
        jax.ShapeDtypeStruct((B, 1), jnp.int32),
        jax.ShapeDtypeStruct((B, 1), jnp.int32),
        jax.ShapeDtypeStruct((B, 1), jnp.int32),
        jax.ShapeDtypeStruct((B, 1), jnp.int32),
    ]
    operands = (steps_arr, T, basis, w, flip, ub, phase, thr, status, iters)
    if telemetry:
        in_specs.append(pl.BlockSpec((tile_b, INT_ROW_WIDTH), vec))
        out_specs.append(pl.BlockSpec((tile_b, INT_ROW_WIDTH), vec))
        out_shape.append(jax.ShapeDtypeStruct((B, INT_ROW_WIDTH), jnp.int32))
        operands = operands + (tel_int,)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        interpret=interpret,
        compiler_params=compiler_params(),
    )(*operands)


def pick_tile_b(m: int, n: int, vmem_budget: int = VMEM_LIMIT_BYTES,
                dtype_size: int = 4) -> int:
    """Choose the LP-tile batch so the working set fits the VMEM budget —
    the paper's Eq. (5)/(6) block-size limit recast as a VMEM tiling rule
    (and the reason our solver has no 511-dimension hard cap). Sized for
    loop 1 (the full tableau); the compacted loop-2 tile is strictly
    smaller.  Per LP: the tableau block in and out, each double-buffered by
    the pipeline, ~8 live full-tile temporaries of the pivot step, and the
    lane rows (kernels/tiling.py)."""
    R, C = full_dims(m, n)
    block = R * C * dtype_size
    per_lp = 12 * block + 32 * C * dtype_size
    return pick_tile(per_lp, block, vmem_budget)


def build_padded_tableau(A: jax.Array, b: jax.Array, c: jax.Array,
                         tile_b: int, feas_tol: float = 1e-5, ub=None
                         ) -> Tuple[jax.Array, jax.Array, jax.Array,
                                    jax.Array, jax.Array, int, int]:
    """Build (B_pad, R, C) tableaux with RHS in the last padded column,
    plus basis/phase/threshold and the (B_pad, C) upper-bound lane row
    (finite entries on structural lanes, +inf everywhere else — slack,
    artificial, RHS and padding lanes can never flip), padded so B divides
    into tiles."""
    B, m, n = A.shape
    dtype = A.dtype
    R, C = full_dims(m, n)
    B_pad = round_up(B, tile_b)

    neg = b < 0
    sign = jnp.where(neg, -1.0, 1.0).astype(dtype)
    T = jnp.zeros((B_pad, R, C), dtype=dtype)
    T = T.at[:B, :m, :n].set(A * sign[:, :, None])
    idx = jnp.arange(m)
    T = T.at[:B, idx, n + idx].set(sign)
    T = T.at[:B, idx, n + m + idx].set(jnp.where(neg, 1.0, 0.0).astype(dtype))
    T = T.at[:B, :m, C - 1].set(b * sign)
    T = T.at[:B, m, :n].set(c)
    p1 = (T[:B, :m, :] * neg[:, :, None].astype(dtype)).sum(axis=1)
    p1 = p1.at[:, n + m:n + 2 * m].set(0.0)
    T = T.at[:B, m + 1, :].set(p1)

    basis = jnp.full((B_pad, R), C - 1, jnp.int32)  # sentinel >= n for pad rows
    basis = basis.at[:B, :m].set(
        jnp.where(neg, n + m + idx[None, :], n + idx[None, :]).astype(jnp.int32))
    phase = jnp.ones((B_pad, 1), jnp.int32) * 2
    phase = phase.at[:B, 0].set(jnp.where(neg.any(axis=1), 1, 2))
    # padding LPs: all-zero tableau -> phase-2 cost row all zeros -> they
    # terminate OPTIMAL on the first check and never pivot.
    thr = jnp.zeros((B_pad, 1), dtype)
    thr = thr.at[:B, 0].set(feas_tol * jnp.maximum(1.0, T[:B, m + 1, C - 1]))
    ub_lane = jnp.full((B_pad, C), jnp.inf, dtype)
    if ub is not None:
        ub_lane = ub_lane.at[:B, :n].set(jnp.asarray(ub, dtype))
    return T, basis, phase, thr, ub_lane, R, C


@functools.partial(
    jax.jit,
    static_argnames=("m", "n", "tile_b", "max_iters", "tol", "feas_tol",
                     "interpret", "pricing"))
def simplex_pallas(A, b, c, ub=None, *, m: int, n: int, tile_b: int,
                   max_iters: int, tol: float = 1e-6, feas_tol: float = 1e-5,
                   interpret: bool, pricing: str = "dantzig"):
    """Solve the batch with the phase-compacted Pallas tile kernel. Returns
    (x, obj, status, iters) for the original (unpadded) batch.  ``pricing``
    selects the entering-column rule (core/pricing.py); ``ub`` adds native
    variable upper bounds (handled by the in-VMEM bounded ratio test, never
    as extra rows)."""
    B = A.shape[0]
    T, basis, phase, thr, ub_lane, R, C = build_padded_tableau(
        A, b, c, tile_b, feas_tol=feas_tol, ub=ub)
    B_pad = T.shape[0]
    grid = (B_pad // tile_b,)
    n_pad = round_up(n, 128)
    m_pad = round_up(m, 8)

    kernel = functools.partial(_simplex_kernel, m=m, n=n, tol=tol,
                               max_iters=max_iters, rule=pricing)
    x, obj, status, iters, y, z = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((tile_b, R, C), lambda i: (i, 0, 0)),
            pl.BlockSpec((tile_b, R), lambda i: (i, 0)),
            pl.BlockSpec((tile_b, 1), lambda i: (i, 0)),
            pl.BlockSpec((tile_b, 1), lambda i: (i, 0)),
            pl.BlockSpec((tile_b, C), lambda i: (i, 0)),
        ],
        out_specs=[
            pl.BlockSpec((tile_b, n_pad), lambda i: (i, 0)),
            pl.BlockSpec((tile_b, 1), lambda i: (i, 0)),
            pl.BlockSpec((tile_b, 1), lambda i: (i, 0)),
            pl.BlockSpec((tile_b, 1), lambda i: (i, 0)),
            pl.BlockSpec((tile_b, m_pad), lambda i: (i, 0)),
            pl.BlockSpec((tile_b, n_pad), lambda i: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B_pad, n_pad), A.dtype),
            jax.ShapeDtypeStruct((B_pad, 1), A.dtype),
            jax.ShapeDtypeStruct((B_pad, 1), jnp.int32),
            jax.ShapeDtypeStruct((B_pad, 1), jnp.int32),
            jax.ShapeDtypeStruct((B_pad, m_pad), A.dtype),
            jax.ShapeDtypeStruct((B_pad, n_pad), A.dtype),
        ],
        interpret=interpret,
        compiler_params=compiler_params(),
    )(T, basis, phase, thr, ub_lane)
    return (x[:B, :n], obj[:B, 0], status[:B, 0].astype(jnp.int8),
            iters[:B, 0], y[:B, :m], z[:B, :n])
