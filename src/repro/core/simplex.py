"""Batched two-phase simplex in pure JAX — the paper's solver, TPU-native.

Mapping from the paper's CUDA design (Sec. 5) to this implementation:

* one CUDA block per LP            ->  one batch slot per LP; the whole batch
                                       advances through `lax.while_loop`
* parallel reduction for Step 1/2  ->  `argmax` / `argmin` over the tableau
                                       axes (VPU cross-lane reductions)
* MAX-sentinel for invalid ratios  ->  identical `where(col>eps, b/col, BIG)`
* column-major coalesced layout    ->  dense (B, rows, cols) tiles; every
                                       pivot is a rank-1 update (outer
                                       product) which the TPU executes as
                                       aligned vector ops; the reduction
                                       vectors live on the minor (lane) axis
* per-block early exit             ->  active-mask: converged LPs perform
                                       masked no-ops (see core/distributed.py
                                       for per-shard termination and
                                       core/compaction.py for the active-set
                                       scheduler, which together restore true
                                       early exit)
* Dantzig entering rule (Step 1)   ->  pluggable pricing engine
                                       (core/pricing.py): ``pricing=`` selects
                                       dantzig (paper default, bit-identical),
                                       steepest_edge (exact gamma weights) or
                                       devex (approximate weights); per-LP
                                       weights ride in `SimplexState.w` and
                                       their recurrence is fused into the
                                       rank-1 pivot update

Two-level work elimination (this module is Level 1)
---------------------------------------------------

The paper's per-block exit means a CUDA block never executes a single dead
pivot.  A lockstep static-shape solver loses that twice over:

1. **Dead columns.**  The two-phase tableau carries `m` artificial columns
   and the phase-1 objective row through *every* phase-2 pivot, even though
   artificials can never re-enter the basis and the phase-1 row is never read
   again.  For m ~ n that is ~2x wasted FLOPs and bytes per pivot.
2. **Dead LPs.**  Converged LPs keep burning full pivot updates as masked
   no-ops until the slowest LP in the batch finishes
   (`analysis/lp_perf.py` measures this lockstep efficiency as mean/max).

Level 1 (here) fixes (1) structurally: the solve is **two chained
`while_loop`s**.  Loop 1 runs the combined step on the full
`(B, m+2, n+2m+1)` tableau until no LP is still in phase 1.  A one-shot
`compact_tableau` then drops the `m` artificial columns and the phase-1
objective row, and loop 2 finishes phase 2 on the `(B, m+1, n+m+1)`
tableau.  Dropping columns that can never enter and a row that is never
priced changes no pivot decision, so the pivot sequence — and therefore
statuses, iteration counts, x and objective — is identical to the
single-loop solver whenever the ``max_iters`` safety cap does not bind.
The two loops share one ``max_iters`` budget; when the cap *does* bind,
which LPs report ITERATION_LIMIT can differ from the single-loop schedule
(the cap is a runaway guard, not a semantic).  ``phase_compaction=False``
keeps the paper-faithful single loop for A/B benchmarks.

Level 2 — recovering per-block exit for dead LPs — is
`core/compaction.py`: the solve runs in segments of K pivots and survivors
are gathered into power-of-two buckets, so terminated LPs stop occupying
device lanes.

All LPs in the batch share one static tableau shape per loop (see
core/lp.py), so each loop is a single XLA computation: no host round-trips,
no dynamic shapes, shardable over any mesh axis with shard_map.
"""
from __future__ import annotations

import functools
from typing import Any, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from ..obs.report import report_from_counters
from ..obs.telemetry import init_telemetry, tel_simplex_update, tel_to_numpy
from .forms import ensure_canonical, finish_result, prepare_warm
from .lp import (
    BIG,
    CANCEL_ULPS,
    INFEASIBLE,
    ITERATION_LIMIT,
    OPTIMAL,
    UNBOUNDED,
    LPBatch,
    LPResult,
    WarmStart,
    canonicalize_backend,
    default_max_iters,
    resolve_backend,
)
from .pricing import (
    canonicalize_rule,
    compact_weights,
    init_weights,
    select_entering,
    update_weights,
)
from .transfer import lp_leaves, run, sharded_solve

_RUNNING = -1


class SimplexState(NamedTuple):
    T: jax.Array        # (B, rows, C) tableaux (full or phase-compacted)
    basis: jax.Array    # (B, m) int32
    phase: jax.Array    # (B,) int32 — 1 or 2
    status: jax.Array   # (B,) int32 — _RUNNING until terminal
    iters: jax.Array    # (B,) int32
    w: jax.Array        # (B, C) pricing weights (see core/pricing.py;
                        #  carried-but-unread under the dantzig rule)
    flip: jax.Array     # (B, n) bool — structural column stored complemented
                        #  (x' = ub - x); all-False when ub is all +inf
    ub: jax.Array       # (B, n) upper bounds (+inf = unbounded); structural
                        #  columns only, so column compaction never slices it
    it: jax.Array       # () int32 loop-local iteration counter
    tel: Any = None     # obs.TelemetryState counter lanes, or None (the
                        #  default) — None is an empty pytree subtree, so
                        #  the telemetry-off trace is identical to a state
                        #  without the field


class _StepConsts(NamedTuple):
    col_ok: np.ndarray    # (C,) bool — columns allowed to enter
    rows_iota: np.ndarray  # (rows,) int32 — for the pivot-row replacement
    row_m: np.ndarray     # (m,) int32 — for the basis update
    col_n: np.ndarray     # (n,) int32 — for the flip-flag scatter


@functools.lru_cache(maxsize=None)
def _step_consts(rows: int, m: int, n: int, C: int) -> _StepConsts:
    """Loop-invariant masks/iotas, built once per tableau geometry as NumPy
    constants so they are embedded in the jaxpr rather than recomputed by
    every pivot (hoisted out of `simplex_step`)."""
    return _StepConsts(
        col_ok=np.arange(C) < n + m,  # artificials + rhs never enter
        rows_iota=np.arange(rows, dtype=np.int32),
        row_m=np.arange(m, dtype=np.int32),
        col_n=np.arange(n, dtype=np.int32),
    )


def tableau_elements(m: int, n: int, compacted: bool = False) -> int:
    """Logical tableau elements touched by one pivot's rank-1 update —
    the unit of the executed-work model in analysis/lp_perf.py and
    benchmarks/pivot_work.py."""
    if compacted:
        return (m + 1) * (n + m + 1)
    return (m + 2) * (n + 2 * m + 1)


def build_tableau_jax(A: jax.Array, b: jax.Array, c: jax.Array):
    """JAX version of core.lp.build_tableau (same layout, any float dtype)."""
    B, m, n = A.shape
    dtype = A.dtype
    cols = n + 2 * m + 1
    neg = b < 0
    sign = jnp.where(neg, -1.0, 1.0).astype(dtype)

    T = jnp.zeros((B, m + 2, cols), dtype=dtype)
    T = T.at[:, :m, :n].set(A * sign[:, :, None])
    idx = jnp.arange(m)
    T = T.at[:, idx, n + idx].set(sign)
    T = T.at[:, idx, n + m + idx].set(jnp.where(neg, 1.0, 0.0).astype(dtype))
    T = T.at[:, :m, -1].set(b * sign)
    T = T.at[:, m, :n].set(c)
    p1 = (T[:, :m, :] * neg[:, :, None].astype(dtype)).sum(axis=1)
    p1 = p1.at[:, n + m:n + 2 * m].set(0.0)
    T = T.at[:, m + 1, :].set(p1)

    basis = jnp.where(neg, n + m + idx[None, :], n + idx[None, :]).astype(jnp.int32)
    phase = jnp.where(neg.any(axis=1), 1, 2).astype(jnp.int32)
    return T, basis, phase


def _gauss_solve(Bmat, rhs):
    """Batched ``B^-1 @ rhs`` via Gauss-Jordan with partial pivoting, built
    from the same per-LP elementwise/rank-1 ops as the pivot loop itself.

    ``jnp.linalg.solve`` in f32 returns *batch-size-dependent* results on
    some backends (different compilations reduce in different orders), which
    would make a chunked warm solve drift from an unchunked one; this
    routine's arithmetic is per-LP and batch-shape-invariant, keeping warm
    injection — like the cold pivot sequence — identical across chunkings.
    A singular matrix divides by ~0 and yields non-finite rows, which is
    exactly the callers' cold-fallback signal (mirroring linalg.solve's
    non-raising contract on singular batches)."""
    B, m, _ = Bmat.shape
    aug = jnp.concatenate([Bmat, rhs], axis=2)
    rows_iota = jnp.arange(m)

    def body(k, aug):
        cand = jnp.where(rows_iota[None, :] >= k,
                         jnp.abs(aug[:, :, k]), -jnp.inf)
        p = jnp.argmax(cand, axis=1)
        swap = jnp.where(rows_iota[None, :] == k, p[:, None],
                         jnp.where(rows_iota[None, :] == p[:, None], k,
                                   rows_iota[None, :]))
        aug = jnp.take_along_axis(aug, swap[:, :, None], axis=1)
        pivrow = aug[:, k, :] / aug[:, k, k][:, None]
        aug = aug - aug[:, :, k][:, :, None] * pivrow[:, None, :]
        return aug.at[:, k, :].set(pivrow)

    aug = jax.lax.fori_loop(0, m, body, aug)
    return aug[:, :, m:]


def inject_tableau_warm(A, b, c, ub, wb, wfl, *, m: int, n: int,
                        feas_tol: float):
    """Rebuild the two-phase tableau batch from a parent basis (warm start).

    ``wb`` (B, m) int32 is the parent basis, ``wfl`` (B, n) bool the parent
    nonbasic-at-upper flips.  Per LP, independently:

    * **skip** — the parent basis is still primal-feasible on the perturbed
      data: the tableau starts in phase 2 with no artificials;
    * **repair** — some basic values went negative: only those rows get an
      artificial (the new artificial's physical column is ``-B e_i``, so
      row-negating the computed tableau rows makes it basic at ``+|x_B_i|``)
      and a phase-1 objective summing exactly the violated rows drives them
      out through the ordinary pivot machinery — a repair phase 1 seeded
      from the parent basis instead of the all-artificial cold start;
    * **cold** — the basis is unusable (out-of-range indices, a singular
      basis matrix after the artificial->slack remap, non-finite solve):
      the ``ok`` flag is False and the caller swaps in the cold tableau.

    Parent artificials (degenerate, value 0, possible after equality-pair
    canonicalization) are remapped to the same row's slack: the swap flips
    at most a column sign, so the basis stays nonsingular, and a duplicate
    slack shows up as a singular solve -> cold fallback.  Flips on columns
    whose new ``ub`` went infinite are cleared (the complement no longer
    exists).  Returns ``(T, basis, phase, flip, ok)``.
    """
    B = A.shape[0]
    dtype = A.dtype
    idx = jnp.arange(m)
    in_range = ((wb >= 0) & (wb < n + 2 * m)).all(axis=1)
    wb2 = jnp.clip(jnp.where(wb >= n + m, wb - m, wb), 0, n + m - 1)
    wb2 = wb2.astype(jnp.int32)
    wfl = wfl & jnp.isfinite(ub)
    ubz = jnp.where(wfl, ub, 0.0).astype(dtype)
    # complement flipped structurals: x_j = ub_j - x'_j
    Af = jnp.where(wfl[:, None, :], -A, A)
    bf = b - jnp.einsum("bmn,bn->bm", A, ubz)
    cf = jnp.where(wfl, -c, c)
    obj_off = jnp.sum(c * ubz, axis=1)

    eye = jnp.broadcast_to(jnp.eye(m, dtype=dtype), (B, m, m))
    Acols = jnp.concatenate([Af, eye], axis=2)                 # (B, m, n+m)
    Bmat = jnp.take_along_axis(Acols, wb2[:, None, :], axis=2)
    body = _gauss_solve(Bmat, jnp.concatenate(
        [Acols, bf[:, :, None]], axis=2))                      # B^-1 [A | b]
    xB = body[:, :, -1]
    eps = feas_tol * jnp.maximum(1.0, jnp.max(jnp.abs(bf), axis=1))
    viol = xB < -eps[:, None]
    D = jnp.where(viol, -1.0, 1.0).astype(dtype)
    rows = D[:, :, None] * body          # violated rows negated: rhs >= 0
    cext = jnp.concatenate([cf, jnp.zeros((B, m), dtype)], axis=1)
    cB = jnp.where(viol, 0.0, jnp.take_along_axis(cext, wb2, axis=1))
    red = cext - jnp.einsum("bi,bij->bj", cB, rows[:, :, :n + m])

    T = jnp.zeros((B, m + 2, n + 2 * m + 1), dtype)
    T = T.at[:, :m, :n + m].set(rows[:, :, :n + m])
    T = T.at[:, idx, n + m + idx].set(jnp.where(viol, 1.0, 0.0).astype(dtype))
    T = T.at[:, :m, -1].set(rows[:, :, -1])
    T = T.at[:, m, :n + m].set(red)
    # row-m rhs: -(objective of the warm basic solution), offset included,
    # so -T[m, -1] stays the true unflipped objective through every pivot
    T = T.at[:, m, -1].set(-(jnp.sum(cB * rows[:, :, -1], axis=1) + obj_off))
    p1 = (rows * viol[:, :, None].astype(dtype)).sum(axis=1)   # (B, n+m+1)
    T = T.at[:, m + 1, :n + m].set(p1[:, :n + m])
    T = T.at[:, m + 1, -1].set(p1[:, -1])

    basis = jnp.where(viol, n + m + idx[None, :], wb2).astype(jnp.int32)
    phase = jnp.where(viol.any(axis=1), 1, 2).astype(jnp.int32)
    ok = in_range & jnp.isfinite(T).all(axis=(1, 2))
    return T, basis, phase, wfl & ok[:, None], ok


def rank1_update(T, factor, pivrow):
    """``T - factor (x) pivrow`` per LP ((B, R, C) - (B, R) x (B, C)), with
    cancellation residue snapped to zero (see ``lp.CANCEL_ULPS``).  Shared by
    the pure-JAX steps and the Pallas tile kernel so both stay bitwise
    equal; entries that cancel exactly are zero either way."""
    prod = factor[:, :, None] * pivrow[:, None, :]
    T_new = T - prod
    noise = CANCEL_ULPS * jnp.finfo(T.dtype).eps * jnp.maximum(
        jnp.abs(T), jnp.abs(prod))
    return jnp.where(jnp.abs(T_new) <= noise, 0.0, T_new)


def _pivot_update(T, w, basis, factor, pivrow_raw, pe, e, l, do_pivot,
                  rows_iota, *, m, n, rule):
    """Rank-1 pivot update shared by both steps: subtract the entering-column
    outer product everywhere, then *replace* the pivot row with the scaled row
    (matching the NumPy oracle exactly, instead of the subtract-then-add-back
    formulation which re-rounds the pivot row).

    The pricing-weight recurrence (core/pricing.py) is fused here: it reads
    the freshly updated tableau / scaled pivot row while they are live, so
    steepest-edge's exact gamma recompute and devex's O(C) update add no
    extra pass over state.  Under ``rule == "dantzig"`` the weights pass
    through untouched and the whole computation DCEs away."""
    pe_safe = jnp.where(do_pivot, pe, 1.0)
    pivrow = pivrow_raw / pe_safe[:, None]
    T_new = rank1_update(T, factor, pivrow)
    is_l = rows_iota[None, :, None] == l[:, None, None]
    T_new = jnp.where(is_l, pivrow[:, None, :], T_new)
    T_out = jnp.where(do_pivot[:, None, None], T_new, T)
    # leaving variable's column (basis *before* its own update) for devex
    r = jnp.take_along_axis(basis, l[:, None], axis=1)[:, 0]
    w = update_weights(rule, w, T_out, pivrow, pe_safe, e, r, do_pivot,
                       m=m, n=n)
    return T_out, w


def _bounded_ratios(ratios, col, rhs, basis, ub, *, n, tol):
    """Case (b) of the bounded-variable ratio test: a basic variable the
    entering column drives *up* (col < 0) may hit its own finite upper
    bound at ``(ub_B - rhs) / (-col)`` — slacks/artificials (basis >= n)
    have ub = +inf, so with all-+inf bounds this is the identity."""
    ubB = jnp.where(basis < n,
                    jnp.take_along_axis(ub, jnp.minimum(basis, n - 1), axis=1),
                    jnp.inf)
    hit = (col < -tol) & jnp.isfinite(ubB)
    return jnp.where(hit, (ubB - rhs) / jnp.where(hit, -col, 1.0), ratios)


def _bound_moves(T, flip, ub, basis, factor, pivrow_raw, pe, e, l,
                 wants_pivot, no_row, min_ratio, consts, *, n):
    """Resolve the two bounded-variable moves of one lockstep step.

    * **Entering-bound flip** (``ub_e < min_ratio``): the entering variable
      hits its own upper bound before any basic variable binds.  Complement
      it in place — ``rhs -= ub_e * col`` on every row (objective rows
      included, which keeps ``-T[m, -1]`` the true objective) and negate
      the column — no pivot, no weight update (column negation is
      norm-invariant for the d^2/w pricing scores).
    * **Leaving-at-upper complement**: the min ratio came from a basic
      variable hitting *its* bound (negative pivot element on a structural
      basic).  Its tableau column is a unit vector, so complementing it
      reduces to rewriting the pivot row: negate it, ``rhs_l -> ub_l -
      rhs_l``, restore the +1 basic entry — the pivot element turns
      positive and the rank-1 update proceeds classically.

    Returns ``(T, flip, pivrow_raw, pe, do_flip, do_pivot)``; with all-+inf
    ``ub`` both masks are all-False and every write is a masked identity.
    """
    B = T.shape[0]
    dtype = T.dtype
    ub_e = jnp.where(e < n,
                     jnp.take_along_axis(ub, jnp.minimum(e, n - 1)[:, None],
                                         axis=1)[:, 0],
                     jnp.inf).astype(dtype)
    do_flip = wants_pivot & (ub_e < min_ratio)
    do_pivot = wants_pivot & ~no_row & ~do_flip

    bidx = jnp.arange(B)
    is_e = consts.col_n[None, :] == e[:, None]           # (B, n)
    ub_e_term = jnp.where(do_flip, ub_e, 0.0).astype(dtype)
    T = T.at[:, :, -1].add(-ub_e_term[:, None] * factor)
    sign_e = jnp.where(do_flip, -1.0, 1.0).astype(dtype)
    T = T.at[bidx[:, None], consts.rows_iota[None, :], e[:, None]].multiply(
        sign_e[:, None])
    flip = flip ^ (do_flip[:, None] & is_e)

    jl = jnp.take_along_axis(basis, l[:, None], axis=1)[:, 0]
    need_comp = do_pivot & (pe < 0) & (jl < n)
    ub_jl = jnp.take_along_axis(ub, jnp.minimum(jl, n - 1)[:, None],
                                axis=1)[:, 0].astype(dtype)
    is_jl_full = (jnp.arange(T.shape[2], dtype=jnp.int32)[None, :]
                  == jl[:, None])                        # (B, C)
    comp_row = -pivrow_raw
    comp_row = comp_row.at[:, -1].add(jnp.where(need_comp, ub_jl, 0.0))
    comp_row = jnp.where(is_jl_full, 1.0, comp_row)
    pivrow_raw = jnp.where(need_comp[:, None], comp_row, pivrow_raw)
    pe = jnp.where(need_comp, -pe, pe)
    flip = flip ^ (need_comp[:, None] & is_jl_full[:, :n])
    return T, flip, pivrow_raw, pe, do_flip, do_pivot


def simplex_step(state: SimplexState, *, n: int, m: int, tol: float,
                 feas_thr, rule: str = "dantzig") -> SimplexState:
    """One lockstep pivot across the whole batch (masked for inactive LPs),
    on the **full** (B, m+2, n+2m+1) tableau.

    Implements Steps 1-3 of the paper's Sec. 4.1 with the Sec. 5.2 sentinel
    trick, as dense batched tensor ops.  Per-LP column/row extraction uses
    `take_along_axis` gathers (one element per batch row) instead of one-hot
    einsums; loop-invariant masks come pre-built from `_step_consts`.
    Step 1 delegates to the pricing engine (``rule``, static): dantzig keeps
    the paper's argmax bit-for-bit; steepest_edge/devex score candidates by
    d_j^2 / weight using the weights carried in ``state.w``.
    """
    T, basis, phase, status, iters, w, flip, ub, it = state[:9]
    tel = state.tel
    in_p1 = phase == 1  # pre-update phase, for telemetry attribution
    B, rows, C = T.shape
    consts = _step_consts(rows, m, n, C)
    active = status == _RUNNING

    # ---- Step 1: entering variable (pivot column) --------------------------
    cost = jnp.where((phase == 1)[:, None], T[:, m + 1, :], T[:, m, :])
    masked_cost = jnp.where(consts.col_ok[None, :], cost, -BIG)
    e, max_cost = select_entering(masked_cost, w, rule=rule, tol=tol,
                                  iters=iters, ncand=n + m)
    is_opt = max_cost <= tol

    # phase bookkeeping at optimality of the current objective row
    p1_obj = T[:, m + 1, -1]
    p1_done = active & (phase == 1) & is_opt
    infeasible = p1_done & (p1_obj > feas_thr)
    to_phase2 = p1_done & ~infeasible
    p2_done = active & (phase == 2) & is_opt

    # ---- Step 2: leaving variable (pivot row), sentinel min-ratio ----------
    factor = jnp.take_along_axis(T, e[:, None, None], axis=2)[:, :, 0]  # (B, rows)
    col = factor[:, :m]
    rhs = T[:, :m, -1]
    valid = col > tol
    ratios = jnp.where(valid, rhs / jnp.where(valid, col, 1.0), BIG)
    ratios = _bounded_ratios(ratios, col, rhs, basis, ub, n=n, tol=tol)
    # Phase 2 pins basic artificials at zero: an entering column that would
    # grow one (negative coefficient in its row) kicks it out at ratio 0
    # instead (negative pivot element, legal at zero rhs).  Degenerate
    # artificials left basic by phase 1 — routine under the equality pairs
    # core/forms.py emits — would otherwise silently re-relax their row.
    # An artificial phase 1 accepted at a small positive value (<= feas_thr)
    # makes this pivot set the entering variable to -rhs/|pivot| — a
    # bounded x>=0 violation of the same order as the feasibility debt
    # already accepted, vs. the unbounded row relaxation pinning prevents.
    pin = (phase == 2)[:, None] & (basis >= n + m) & (col < -tol)
    ratios = jnp.where(pin, 0.0, ratios)
    l = jnp.argmin(ratios, axis=1)
    min_ratio = jnp.min(ratios, axis=1)
    no_row = min_ratio >= BIG / 2

    wants_pivot = active & ~is_opt

    # ---- Step 3: bound moves + rank-1 pivot update (+ fused weights) -------
    pivrow_raw = jnp.take_along_axis(T, l[:, None, None], axis=1)[:, 0, :]
    pe = jnp.take_along_axis(col, l[:, None], axis=1)[:, 0]
    T, flip, pivrow_raw, pe, do_flip, do_pivot = _bound_moves(
        T, flip, ub, basis, factor, pivrow_raw, pe, e, l,
        wants_pivot, no_row, min_ratio, consts, n=n)
    unbounded = wants_pivot & no_row & ~do_flip & (phase == 2)
    stuck = wants_pivot & no_row & ~do_flip & (phase == 1)  # numerically impossible path
    T, w = _pivot_update(T, w, basis, factor, pivrow_raw, pe, e, l, do_pivot,
                         consts.rows_iota, m=m, n=n, rule=rule)
    basis = jnp.where(do_pivot[:, None] & (consts.row_m[None, :] == l[:, None]),
                      e[:, None].astype(jnp.int32), basis)

    status = jnp.where(infeasible, INFEASIBLE, status)
    status = jnp.where(unbounded, UNBOUNDED, status)
    status = jnp.where(stuck, ITERATION_LIMIT, status)
    status = jnp.where(p2_done, OPTIMAL, status)
    phase = jnp.where(to_phase2, 2, phase)
    inc = active & ~p2_done & ~infeasible
    iters = iters + inc.astype(jnp.int32)
    if tel is not None:
        tel = tel_simplex_update(tel, inc=inc, in_phase1=in_p1,
                                 do_pivot=do_pivot, do_flip=do_flip,
                                 degenerate=min_ratio <= 0.0)
    return SimplexState(T, basis, phase, status, iters, w, flip, ub, it + 1,
                        tel)


def phase2_step(state: SimplexState, *, n: int, m: int, tol: float,
                rule: str = "dantzig") -> SimplexState:
    """One lockstep phase-2 pivot on the **compacted** (B, m+1, n+m+1)
    tableau (artificial columns and the phase-1 objective row removed).

    Artificials can never enter (they were masked out of Step 1 already) and
    the phase-1 row is never priced in phase 2, so this performs exactly the
    pivots `simplex_step` would — at (m+1)(n+m+1)/((m+2)(n+2m+1)) of the
    per-pivot FLOPs/bytes.  ``rule`` selects the pricing engine exactly as in
    `simplex_step`; ``state.w`` is the phase-compacted weight vector."""
    T, basis, phase, status, iters, w, flip, ub, it = state[:9]
    tel = state.tel
    B, rows, C = T.shape          # rows == m + 1, C == n + m + 1
    consts = _step_consts(rows, m, n, C)
    active = (status == _RUNNING) & (phase == 2)

    cost = T[:, m, :]
    masked_cost = jnp.where(consts.col_ok[None, :], cost, -BIG)
    e, max_cost = select_entering(masked_cost, w, rule=rule, tol=tol,
                                  iters=iters, ncand=n + m)
    is_opt = max_cost <= tol
    p2_done = active & is_opt

    factor = jnp.take_along_axis(T, e[:, None, None], axis=2)[:, :, 0]
    col = factor[:, :m]
    rhs = T[:, :m, -1]
    valid = col > tol
    ratios = jnp.where(valid, rhs / jnp.where(valid, col, 1.0), BIG)
    ratios = _bounded_ratios(ratios, col, rhs, basis, ub, n=n, tol=tol)
    # basic artificials stay pinned at zero (see simplex_step); the basis
    # still indexes full-tableau columns, so >= n+m identifies them here too
    pin = (basis >= n + m) & (col < -tol)
    ratios = jnp.where(pin, 0.0, ratios)
    l = jnp.argmin(ratios, axis=1)
    min_ratio = jnp.min(ratios, axis=1)
    no_row = min_ratio >= BIG / 2

    wants_pivot = active & ~is_opt

    pivrow_raw = jnp.take_along_axis(T, l[:, None, None], axis=1)[:, 0, :]
    pe = jnp.take_along_axis(col, l[:, None], axis=1)[:, 0]
    T, flip, pivrow_raw, pe, do_flip, do_pivot = _bound_moves(
        T, flip, ub, basis, factor, pivrow_raw, pe, e, l,
        wants_pivot, no_row, min_ratio, consts, n=n)
    unbounded = wants_pivot & no_row & ~do_flip
    T, w = _pivot_update(T, w, basis, factor, pivrow_raw, pe, e, l, do_pivot,
                         consts.rows_iota, m=m, n=n, rule=rule)
    basis = jnp.where(do_pivot[:, None] & (consts.row_m[None, :] == l[:, None]),
                      e[:, None].astype(jnp.int32), basis)

    status = jnp.where(unbounded, UNBOUNDED, status)
    status = jnp.where(p2_done, OPTIMAL, status)
    inc = active & ~p2_done
    iters = iters + inc.astype(jnp.int32)
    if tel is not None:
        # active implies phase == 2 here, so everything lands in the
        # phase-2 lanes regardless of the stale phase entries
        tel = tel_simplex_update(tel, inc=inc, in_phase1=phase == 1,
                                 do_pivot=do_pivot, do_flip=do_flip,
                                 degenerate=min_ratio <= 0.0)
    return SimplexState(T, basis, phase, status, iters, w, flip, ub, it + 1,
                        tel)


def compact_tableau(T: jax.Array, *, m: int, n: int) -> jax.Array:
    """One-shot phase compaction: drop the m artificial columns and the
    phase-1 objective row: (B, m+2, n+2m+1) -> (B, m+1, n+m+1).

    Basis entries that still point at a (degenerate, value-0) artificial stay
    as-is: they are >= n, so solution extraction ignores them, and removing
    the column just pins that artificial to zero — which is exactly the
    feasibility phase 1 certified."""
    return jnp.concatenate([T[:, :m + 1, :n + m], T[:, :m + 1, -1:]], axis=2)


def scatter_solution(rhs: jax.Array, basis: jax.Array, n: int) -> jax.Array:
    """x[b, basis[b, i]] = rhs[b, i] for structural basis entries (basis < n),
    as a batched scatter-add (replaces the old one-hot einsum: no (B, m, n)
    intermediate)."""
    B = rhs.shape[0]
    contrib = jnp.where(basis < n, rhs, 0.0)
    safe = jnp.clip(basis, 0, n - 1)
    x = jnp.zeros((B, n), rhs.dtype)
    return x.at[jnp.arange(B)[:, None], safe].add(contrib)


def _unflip_solution(x, flip, ub):
    """Map complemented coordinates back: x = ub - x' on flipped columns
    (covers both flipped basics — ub - rhs — and flipped nonbasics at 0,
    which sit at their upper bound)."""
    if flip is None:
        return x
    return jnp.where(flip, ub.astype(x.dtype) - x, x)


def extract_solution_jax(T: jax.Array, basis: jax.Array, n: int,
                         flip=None, ub=None):
    """Read (x, objective) off **full** (rows = m+2) tableaux."""
    m = T.shape[1] - 2
    x = scatter_solution(T[:, :m, -1], basis[:, :m], n)
    x = _unflip_solution(x, flip, ub)
    objective = -T[:, m, -1]
    return x, objective


def extract_solution_compacted(T: jax.Array, basis: jax.Array, n: int,
                               flip=None, ub=None):
    """Read (x, objective) off **phase-compacted** (rows = m+1) tableaux."""
    m = T.shape[1] - 1
    x = scatter_solution(T[:, :m, -1], basis[:, :m], n)
    x = _unflip_solution(x, flip, ub)
    objective = -T[:, m, -1]
    return x, objective


def extract_duals(T: jax.Array, *, m: int, n: int, flip=None):
    """Dual certificate off a final tableau (full or phase-compacted — both
    keep structural columns 0..n-1 and slack columns n..n+m-1 in row m).

    The phase-2 objective row holds the reduced costs ``c - y.A``; the
    slack column j = n+i has original cost 0 and (sign-adjusted) column
    ``sign_i e_i``, so its entry is ``-y_i`` irrespective of the row's
    phase-1 sign flip: ``y = c_B B^-1`` falls out of the tableau for free.
    Flipped structural columns are stored complemented, so their entry is
    ``-z_j``; ``flip`` undoes the sign.  Returns (y, z) with y (B, m) the
    canonical row duals (>= 0 at optimality) and z (B, n) the structural
    reduced costs (<= 0 at lower bound, >= 0 at upper bound)."""
    y = -T[:, m, n:n + m]
    z = T[:, m, :n]
    if flip is not None:
        z = jnp.where(flip, -z, z)
    return y, z


def _mask_duals(y, z, status):
    """Duals are a certificate of optimality only: NaN elsewhere."""
    opt = (status == OPTIMAL)[:, None]
    return jnp.where(opt, y, jnp.nan), jnp.where(opt, z, jnp.nan)


def solve_two_phase(A, b, c, ub=None, *, m: int, n: int, max_iters: int,
                    tol: float, feas_tol: float, phase_compaction: bool = True,
                    pricing: str = "dantzig",
                    warm_basis=None, warm_at_upper=None, warm_weights=None,
                    full_state: bool = False, telemetry: bool = False):
    """Traceable two-phase solve body, shared by jit (`_solve_core`) and
    shard_map (`transfer.sharded_solve`).

    phase_compaction=True (default): loop 1 on the full tableau until no LP
    is still in phase 1, then `compact_tableau`, then loop 2 on the small
    tableau.  The two loops share one `max_iters` budget (loop 2 resumes the
    step counter where loop 1 stopped).
    phase_compaction=False: the paper-faithful single lockstep loop (the seed
    behavior), kept as the A/B baseline for benchmarks/pivot_work.py.
    ``pricing`` selects the entering-column rule (core/pricing.py); weights
    are initialized here and phase-compacted alongside the tableau.

    ``warm_basis``/``warm_at_upper`` ((B, m) int32 / (B, n) bool) seed the
    solve from a parent basis via `inject_tableau_warm`; each LP falls back
    to the cold tableau independently when its parent basis is unusable.
    ``warm_weights`` (any width >= n+m) overlays carried devex weights.
    ``full_state=True`` appends ``(basis, flip, w)`` to the return tuple so
    batched entry points can capture a ``WarmStart``.
    ``telemetry=True`` (static) seeds an ``obs.TelemetryState`` into the
    loop carry and appends it to the return tuple; with the default False
    the carry holds ``tel=None`` — an empty pytree subtree — so the traced
    program is unchanged.
    """
    rule = canonicalize_rule(pricing)
    B = A.shape[0]
    dtype = A.dtype
    if ub is None:
        ub = jnp.full((B, n), jnp.inf, dtype=dtype)
    else:
        ub = jnp.asarray(ub, dtype=dtype)
    T, basis, phase = build_tableau_jax(A, b, c)
    flip = jnp.zeros((B, n), dtype=bool)
    if warm_basis is not None:
        wfl = (jnp.zeros((B, n), bool) if warm_at_upper is None
               else jnp.asarray(warm_at_upper, bool))
        T_w, basis_w, phase_w, flip_w, ok = inject_tableau_warm(
            A, b, c, ub, jnp.asarray(warm_basis, jnp.int32), wfl,
            m=m, n=n, feas_tol=feas_tol)
        T = jnp.where(ok[:, None, None], T_w, T)
        basis = jnp.where(ok[:, None], basis_w, basis)
        phase = jnp.where(ok, phase_w, phase)
        flip = jnp.where(ok[:, None], flip_w, flip)
    # Phase-1 feasibility threshold is *relative* to the initial infeasibility
    # mass (f32 tableaux accumulate O(scale * eps) error through pivots).
    feas_thr = feas_tol * jnp.maximum(1.0, T[:, m + 1, -1])
    w = init_weights(rule, T, m)
    if warm_basis is not None and warm_weights is not None:
        ww = jnp.asarray(warm_weights, w.dtype)
        w = w.at[:, :n + m].set(
            jnp.where(ok[:, None], ww[:, :n + m], w[:, :n + m]))
    state = SimplexState(
        T=T, basis=basis, phase=phase,
        status=jnp.full((B,), _RUNNING, jnp.int32),
        iters=jnp.zeros((B,), jnp.int32),
        w=w,
        flip=flip,
        ub=ub,
        it=jnp.array(0, jnp.int32),
        tel=init_telemetry(B) if telemetry else None,
    )

    def body1(s: SimplexState):
        return simplex_step(s, n=n, m=m, tol=tol, feas_thr=feas_thr,
                            rule=rule)

    if not phase_compaction:
        def cond(s: SimplexState):
            return jnp.any(s.status == _RUNNING) & (s.it < max_iters)

        state = jax.lax.while_loop(cond, body1, state)
        status = jnp.where(state.status == _RUNNING, ITERATION_LIMIT, state.status)
        x, obj = extract_solution_jax(state.T, state.basis, n,
                                      flip=state.flip, ub=state.ub)
        y, z = extract_duals(state.T, m=m, n=n, flip=state.flip)
    else:
        # ---- loop 1: full tableau, until every LP has left phase 1 ---------
        def cond1(s: SimplexState):
            pending = (s.status == _RUNNING) & (s.phase == 1)
            return jnp.any(pending) & (s.it < max_iters)

        state = jax.lax.while_loop(cond1, body1, state)
        status = jnp.where((state.status == _RUNNING) & (state.phase == 1),
                           ITERATION_LIMIT, state.status)

        # ---- one-shot compaction + loop 2 on the small tableau -------------
        # (loop 2 inherits the step counter: one shared max_iters budget)
        state = SimplexState(
            T=compact_tableau(state.T, m=m, n=n), basis=state.basis,
            phase=state.phase, status=status, iters=state.iters,
            w=compact_weights(state.w, m=m, n=n),
            flip=state.flip, ub=state.ub,
            it=state.it, tel=state.tel)

        def cond2(s: SimplexState):
            return jnp.any(s.status == _RUNNING) & (s.it < max_iters)

        def body2(s: SimplexState):
            return phase2_step(s, n=n, m=m, tol=tol, rule=rule)

        state = jax.lax.while_loop(cond2, body2, state)
        status = jnp.where(state.status == _RUNNING, ITERATION_LIMIT, state.status)
        x, obj = extract_solution_compacted(state.T, state.basis, n,
                                            flip=state.flip, ub=state.ub)
        y, z = extract_duals(state.T, m=m, n=n, flip=state.flip)

    obj = jnp.where(status == OPTIMAL, obj, jnp.nan)
    y, z = _mask_duals(y, z, status)
    out = (x, obj, status.astype(jnp.int8), state.iters, y, z)
    if full_state:
        out = out + (state.basis, state.flip, state.w)
    if telemetry:
        out = out + (state.tel,)
    return out


@functools.partial(jax.jit, static_argnames=("m", "n", "max_iters", "tol",
                                             "feas_tol", "phase_compaction",
                                             "pricing", "telemetry"))
def _solve_core(A, b, c, ub, *, m: int, n: int, max_iters: int, tol: float,
                feas_tol: float, phase_compaction: bool = True,
                pricing: str = "dantzig", telemetry: bool = False):
    return solve_two_phase(A, b, c, ub, m=m, n=n, max_iters=max_iters, tol=tol,
                           feas_tol=feas_tol, phase_compaction=phase_compaction,
                           pricing=pricing, telemetry=telemetry)


@functools.partial(jax.jit, static_argnames=("m", "n", "max_iters", "tol",
                                             "feas_tol", "phase_compaction",
                                             "pricing", "telemetry"))
def _solve_core_state(A, b, c, ub, warm_basis, warm_at_upper, warm_weights,
                      *, m: int, n: int, max_iters: int, tol: float,
                      feas_tol: float, phase_compaction: bool = True,
                      pricing: str = "dantzig", telemetry: bool = False):
    """`_solve_core` + warm injection + terminal-state capture (the batched
    entry point's core; warm args may be None for a cold capture-only run)."""
    return solve_two_phase(A, b, c, ub, m=m, n=n, max_iters=max_iters, tol=tol,
                           feas_tol=feas_tol, phase_compaction=phase_compaction,
                           pricing=pricing, warm_basis=warm_basis,
                           warm_at_upper=warm_at_upper,
                           warm_weights=warm_weights, full_state=True,
                           telemetry=telemetry)


def solve_batched_jax(batch: LPBatch, *, dtype=jnp.float32, tol: float | None = None,
                      feas_tol: float | None = None, max_iters: int | None = None,
                      phase_compaction: bool = True,
                      pricing: str = "dantzig",
                      backend: str = "tableau",
                      refactor_period: int | None = None,
                      presolve: bool = True,
                      scale: bool | None = None,
                      warm: WarmStart | None = None,
                      telemetry: bool = False,
                      mesh=None) -> LPResult:
    """Solve a batch of LPs with the lockstep pure-JAX simplex.

    Phase-compacted by default (identical pivot sequence, ~35-50% fewer
    tableau elements per phase-2 pivot); ``phase_compaction=False`` restores
    the paper-faithful single-loop solver.  ``mesh`` spreads the batch over
    its devices, each with its own while loop (`transfer.sharded_solve`;
    ``solve_batched(mesh=)`` and core.distributed.solve_shard_map plan
    chunks for it); a mesh solve takes no ``warm`` and captures none.  For
    active-set compaction (retiring finished LPs mid-solve) use
    core.compaction.
    ``pricing`` selects the entering-column rule — "dantzig" (paper default),
    "steepest_edge", "devex" or "partial" (core/pricing.py); better rules
    trade a cheaper pivot *count* against a slightly costlier pivot.
    ``backend`` selects the solver engine: "tableau" (this module — dense
    tableaux, rank-1 pivot updates) or "revised" (core/revised.py — immutable
    constraint data, basis-factor updates, O(m^2)+pricing per pivot;
    ``refactor_period`` bounds its eta file, ``phase_compaction`` does not
    apply).  Statuses agree across backends; pivot paths may differ in f32.

    A ``GeneralLPBatch`` (core/forms.py) is accepted directly: it is
    canonicalized on ingestion (``presolve``/``scale`` control the presolve
    pass and geometric-mean equilibration) and the result is recovered into
    original coordinates.

    ``warm`` re-injects a previous solve's ``LPResult.warm_start()`` carrier
    (validated/re-scaled by forms.prepare_warm; per-LP skip/repair/cold, see
    `inject_tableau_warm`); the returned result always carries a fresh
    ``warm`` capture for the next solve in the sequence.
    """
    batch, rec = ensure_canonical(batch, presolve=presolve, scale=scale)
    if canonicalize_backend(backend) != "tableau":
        # registry dispatch (core/lp.py BACKEND_REGISTRY): the engine
        # modules own their extra kwargs; only the revised engine takes a
        # refactor_period
        solver = resolve_backend(backend)
        kwargs = dict(dtype=dtype, tol=tol, feas_tol=feas_tol,
                      max_iters=max_iters, pricing=pricing, warm=warm,
                      telemetry=telemetry, mesh=mesh)
        if backend == "revised":
            kwargs["refactor_period"] = refactor_period
        return finish_result(rec, solver(batch, **kwargs))
    m, n = batch.m, batch.n
    if max_iters is None:
        max_iters = default_max_iters(m, n)
    if tol is None:
        tol = 1e-6 if dtype == jnp.float32 else 1e-9
    if feas_tol is None:
        feas_tol = 1e-5 if dtype == jnp.float32 else 1e-7
    rule = canonicalize_rule(pricing)
    dt = jax.dtypes.canonicalize_dtype(dtype)
    settings = dict(m=m, n=n, max_iters=int(max_iters), tol=float(tol),
                    feas_tol=float(feas_tol),
                    phase_compaction=bool(phase_compaction), pricing=rule,
                    telemetry=bool(telemetry))
    if mesh is not None:
        return finish_result(rec, sharded_solve(
            solve_two_phase, batch, mesh, dt, backend="tableau", **settings))
    warm = prepare_warm(warm, rec, batch)
    leaves = lp_leaves(batch, dt) + [None, None, None]
    if warm is not None and warm.basis is not None:
        leaves[4] = (warm.basis, np.dtype(np.int32))
        if warm.at_upper is not None:
            leaves[5] = (warm.at_upper, np.dtype(bool))
        # carried weights are only meaningful for devex (its reference
        # framework is cross-solve state); steepest edge re-initializes
        # exactly from the warm tableau, dantzig/partial never read them
        if (rule == "devex" and warm.pricing == rule
                and warm.weights is not None
                and np.asarray(warm.weights).shape[1] >= n + m):
            leaves[6] = (warm.weights, dt)
    out, wall_s = run(functools.partial(_solve_core_state, **settings),
                      leaves, B=batch.batch, m=m, n=n)
    x, obj, status, iters, y, z, basis, flip, w = out[:9]
    stats = None
    if telemetry:
        stats = report_from_counters(tel_to_numpy(out[9]), wall_s=wall_s,
                                     backend="tableau")
    capture = WarmStart(m=m, n=n, basis=basis, at_upper=flip, weights=w,
                        pricing=rule)
    res = LPResult(x=x, objective=obj, status=status, iterations=iters,
                   y=y, z=z, warm=capture, stats=stats)
    return finish_result(rec, res)


def flops_per_pivot(m: int, n: int, compacted: bool = False) -> int:
    """Approximate FLOPs of one pivot across one tableau (for Table-5-style
    Gflop/s accounting): rank-1 update dominates: 2*rows*C plus the two
    reductions and the row scale."""
    if compacted:
        rows, C = m + 1, n + m + 1
    else:
        rows, C = m + 2, n + 2 * m + 1
    rank1 = 2 * rows * C
    reductions = 2 * C + 3 * m
    scale = C
    return rank1 + reductions + scale
