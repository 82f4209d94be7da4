"""Batched revised simplex — basis-factor updates instead of tableau updates.

The paper's solver (core/simplex.py) carries the *entire* dense tableau
through every pivot: one rank-1 update writes O(m*(n+2m)) elements, which is
what PR 1's work-elimination engine and PR 2's pricing rules multiply
against.  The classic fix — the **revised simplex method** — keeps the
constraint data immutable and maintains only a factorization of the m x m
basis matrix:

* ``Abar`` (B, m, n+2m) — the sign-adjusted constraint columns (structurals,
  slacks, artificials; exactly the tableau's column layout, so basis indices,
  statuses and solution extraction are interchangeable with the tableau
  backend).  **Never written after construction.**
* ``lu/perm`` — a batched LU factorization (``jax.lax.linalg.lu``) of the
  basis matrix at the last refactorization point.
* ``etaR/etaV`` — a product-form **eta file**: one rank-1 update factor per
  pivot since the last refactorization.  After pivot (l, e) with FTRAN column
  u = B^-1 a_e, the new basis inverse is E B^-1 with E the identity except
  column l = eta, eta_l = 1/u_l, eta_i = -u_i/u_l.
* every ``refactor_period`` pivots (and at every active-set compaction
  gather) the basis matrix is re-gathered from ``Abar`` and re-factorized,
  emptying the eta file — the standard stability/cost tradeoff.

Per pivot the solver runs:

1. **BTRAN**: y = B^-T c_B — reverse-order transposed eta applications, then
   a transposed LU solve.  O(m^2 + k*m).
2. **pricing**: reduced costs d_j = c_j - y . a_j over candidate columns.
   ``pricing="dantzig"`` prices all n+m candidates (O(m*(n+m)));
   ``pricing="partial"`` prices one rotating block of ``PARTIAL_BLOCK``
   columns (O(m*block)) and falls back to a full pass only for LPs whose
   block prices out (which is also where optimality is detected) — the
   contract extension in core/pricing.py, same block schedule as the tableau
   dialect and the float64 oracle.
3. **ratio test**: u = B^-1 a_e by FTRAN (LU solve + forward eta
   applications), then the paper's sentinel min-ratio over u.  O(m^2 + k*m).
4. **update**: x_B and one appended eta column — O(m) writes.  The tableau
   backend writes O(m*(n+2m)) elements here; this asymmetry is the whole
   point (see ``revised_elements``).

Phase handling mirrors the tableau backend exactly: the same two-phase
construction (phase-1 cost = -1 on artificials), the same per-LP phase
switch, feasibility threshold, status codes and iteration accounting — so on
well-conditioned batches the two backends execute the same pivot sequence
and report identical statuses (cross-checked in benchmarks/pivot_work.py and
tests/test_revised.py; float32 reduced costs are *recomputed* here rather
than carried incrementally, so long degenerate ties can order differently
without changing certificates).

Composition: ``RevisedBackend`` plugs into the active-set compaction
scheduler (core/compaction.py) — every state leaf keeps the batch on axis 0
so bucket gathers work unchanged, and ``take`` refactorizes after each
gather (**refactor-on-compact**) so segments always resume from a clean LU.

Reproducibility contract: unlike the tableau engine (whose per-LP rank-1
path is independent of batchmates, hence bitwise-invariant to batch
decomposition), the eta-file slot clock and the refactor trigger are shared
across the (local) batch — splitting a batch across shard_map shards or
compaction buckets shifts *when* each LP's basis is refactorized and hence
f32 rounding.  Identical batch composition is bitwise;
different decompositions guarantee identical certificates and
objectives/solutions to f32 tolerance (~1e-6), verified in
tests/test_revised.py.
``backend="revised"`` on solve_batched / solve_shard_map
routes here; ``solve_batched_pallas(backend="revised")`` runs the revised
tile kernel (kernels/revised_tile.py), which reuses this module's state
builder, warm injection and pivot semantics and validates against it.
"""
from __future__ import annotations

import functools
from typing import Any, List, NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from ..obs.report import report_from_counters
from ..obs.telemetry import (init_telemetry, tel_revised_update,
                             tel_simplex_update, tel_to_numpy)
from .forms import ensure_canonical, finish_result, prepare_warm
from .compaction import (
    CompactionConfig,
    JaxBackend,
    SegmentStat,
    auto_segment_k,
    init_orig,
    on_mesh,
    resolve_compact_threshold,
    run_schedule,
)
from .lp import (
    BIG,
    INFEASIBLE,
    ITERATION_LIMIT,
    OPTIMAL,
    UNBOUNDED,
    LPBatch,
    LPResult,
    WarmStart,
    default_max_iters,
)
from .pricing import (canonicalize_rule, partial_geometry,
                      partial_priced_candidates)
from .simplex import _RUNNING, scatter_solution
from .transfer import lp_leaves, run, sharded_solve

# Pricing rules the revised backend supports.  steepest_edge needs
# ||B^-1 a_j||^2 per candidate (O(m^2) per column without the tableau) and
# devex needs the full updated pivot row — both are tableau-dialect rules;
# the revised backend's lever is *partial* pricing instead.
REVISED_RULES = ("dantzig", "partial")


def canonicalize_revised_rule(pricing: str) -> str:
    rule = canonicalize_rule(pricing)
    if rule not in REVISED_RULES:
        raise ValueError(
            f"pricing rule {rule!r} is tableau-only; the revised backend "
            f"supports {REVISED_RULES} (steepest-edge/devex weights need "
            "the dense tableau the revised method exists to avoid)")
    return rule


def auto_refactor_period(m: int, n: int) -> int:
    """Eta-file length when the caller passes ``refactor_period=None``.

    Balancing the amortized refactorization cost (~(2/3)m^3/K flops per
    pivot) against the eta-application cost (~3*K*m per pivot, growing with
    the file) gives K* ~ m/2; clamp to keep tiny problems from refactoring
    every pivot and huge ones from dragging hundred-deep eta files."""
    return max(4, min(64, m // 2))


def revised_elements(m: int, n: int, *, refactor_period: int | None = None,
                     partial: bool = False, block: int | None = None) -> int:
    """Tableau-element-equivalent work of one revised pivot, in the repo's
    executed-work unit (state elements *written* per pivot — the unit
    ``simplex.tableau_elements`` charges the tableau's rank-1 update).

    The immutable (m, n+2m) block is never written; a pivot writes the BTRAN
    and FTRAN solution vectors, the updated basic solution and one eta column
    (4m), plus the priced reduced costs, plus the amortized refactorization
    (LU factors + the gathered basis matrix, 2m^2 every K pivots).  The
    O(m*(n+m)) -> O(m^2)/K + pricing drop is the revised method's claim;
    ``analysis.lp_perf.revised_pivot_flops`` gives the companion flops model
    (where triangular-solve *reads* are charged too, and the crossover is in
    the n/m aspect ratio rather than uniform)."""
    K = refactor_period or auto_refactor_period(m, n)
    priced = partial_priced_candidates(n + m, block, partial=partial)
    return int(4 * m + priced + (2 * m * m) // K)


class RevisedState(NamedTuple):
    """Resumable revised-simplex state; every leaf keeps the batch on axis 0
    so the compaction scheduler's generic gathers apply unchanged."""
    Abar: jax.Array      # (B, m, n+2m) immutable sign-adjusted columns
    cvec: jax.Array      # (B, n+m) phase-2 costs over candidate columns
    xB: jax.Array        # (B, m) basic-variable values
    basis: jax.Array     # (B, m) int32 — column basic in each row
    phase: jax.Array     # (B,) int32
    status: jax.Array    # (B,) int32 — _RUNNING until terminal
    iters: jax.Array     # (B,) int32
    lu: jax.Array        # (B, m, m) LU factors of the refactorization basis
    perm: jax.Array      # (B, m) int32 — row permutation (A[perm] = L U)
    perm_inv: jax.Array  # (B, m) int32 — its inverse, for transposed solves
    etaR: jax.Array      # (B, K) int32 — eta pivot rows
    etaV: jax.Array      # (B, K, m) — eta columns
    cnt: jax.Array       # (B,) int32 — live etas (uniform; array-shaped so
                         #  compaction gathers treat it like every leaf)
    onub: jax.Array      # (B, n) bool — nonbasic structural parked at its
                         #  *upper* bound (reduced-cost sign is flagged, the
                         #  immutable columns are never complemented)
    ub: jax.Array        # (B, n) upper bounds (+inf = unbounded)
    thr: jax.Array       # (B,) phase-1 feasibility threshold
    tel: Any = None      # obs.TelemetryState lanes or None (empty subtree:
                         #  the telemetry-off trace is unchanged)


def build_revised_state(A: jax.Array, b: jax.Array, c: jax.Array, ub=None, *,
                        feas_tol: float, refactor_period: int) -> RevisedState:
    """Initial state: tableau column layout (structurals | slacks |
    artificials), sign-adjusted rows, identity starting basis => LU of I."""
    B, m, n = A.shape
    dtype = A.dtype
    neg = b < 0
    sign = jnp.where(neg, -1.0, 1.0).astype(dtype)
    idx = jnp.arange(m)

    slack = jnp.zeros((B, m, m), dtype).at[:, idx, idx].set(sign)
    art = jnp.zeros((B, m, m), dtype).at[:, idx, idx].set(
        jnp.where(neg, 1.0, 0.0).astype(dtype))
    Abar = jnp.concatenate([A * sign[:, :, None], slack, art], axis=2)
    bbar = b * sign
    cvec = jnp.concatenate([c, jnp.zeros((B, m), dtype)], axis=1)

    basis = jnp.where(neg, n + m + idx[None, :],
                      n + idx[None, :]).astype(jnp.int32)
    phase = jnp.where(neg.any(axis=1), 1, 2).astype(jnp.int32)
    # same relative phase-1 threshold as the tableau backend: the initial
    # phase-1 objective is the total infeasibility mass sum_neg bbar_i
    thr = feas_tol * jnp.maximum(1.0, jnp.where(neg, bbar, 0.0).sum(axis=1))

    eye = jnp.broadcast_to(jnp.eye(m, dtype=dtype), (B, m, m))
    iota = jnp.broadcast_to(idx.astype(jnp.int32), (B, m))
    K = int(refactor_period)
    if ub is None:
        ub = jnp.full((B, n), jnp.inf, dtype=dtype)
    else:
        ub = jnp.asarray(ub, dtype=dtype)
    return RevisedState(
        Abar=Abar, cvec=cvec, xB=bbar, basis=basis, phase=phase,
        status=jnp.full((B,), _RUNNING, jnp.int32),
        iters=jnp.zeros((B,), jnp.int32),
        lu=eye, perm=iota, perm_inv=iota,
        etaR=jnp.zeros((B, K), jnp.int32),
        etaV=jnp.zeros((B, K, m), dtype),
        cnt=jnp.zeros((B,), jnp.int32),
        onub=jnp.zeros((B, n), dtype=bool), ub=ub, thr=thr)


# ---------------------------------------------------------------------------
# FTRAN / BTRAN
# ---------------------------------------------------------------------------

def _lu_solve(lu, perm, v):
    """x = B0^-1 v via P B0 = L U: x = U^-1 L^-1 v[perm]."""
    t = jnp.take_along_axis(v, perm, axis=1)[..., None]
    t = lax.linalg.triangular_solve(lu, t, left_side=True, lower=True,
                                    unit_diagonal=True)
    t = lax.linalg.triangular_solve(lu, t, left_side=True, lower=False)
    return t[..., 0]


def _lu_solve_t(lu, perm_inv, v):
    """y = B0^-T v via B0^T = U^T L^T P: solve the two transposed triangles,
    then undo the row permutation."""
    t = v[..., None]
    t = lax.linalg.triangular_solve(lu, t, left_side=True, lower=False,
                                    transpose_a=True)
    t = lax.linalg.triangular_solve(lu, t, left_side=True, lower=True,
                                    transpose_a=True, unit_diagonal=True)
    return jnp.take_along_axis(t[..., 0], perm_inv, axis=1)


def _apply_etas_fwd(v, etaR, etaV, cnt0, iota_m):
    """FTRAN tail: v <- E_k ... E_1 v, oldest eta first.
    (E v)_i = v_i + eta_i * v_r for i != r, (E v)_r = eta_r * v_r."""
    def body(k, v):
        r = lax.dynamic_index_in_dim(etaR, k, axis=1, keepdims=False)
        eta = lax.dynamic_index_in_dim(etaV, k, axis=1, keepdims=False)
        vr = jnp.take_along_axis(v, r[:, None], axis=1)
        upd = eta * vr
        return jnp.where(iota_m[None, :] == r[:, None], upd, v + upd)

    return lax.fori_loop(0, cnt0, body, v)


def _apply_etas_rev(v, etaR, etaV, cnt0, iota_m):
    """BTRAN head: v <- E_1^T ... E_k^T v, newest eta first.
    (E^T v)_j = v_j for j != r, (E^T v)_r = eta . v."""
    def body(i, v):
        k = cnt0 - 1 - i
        r = lax.dynamic_index_in_dim(etaR, k, axis=1, keepdims=False)
        eta = lax.dynamic_index_in_dim(etaV, k, axis=1, keepdims=False)
        dot = jnp.sum(eta * v, axis=1, keepdims=True)
        return jnp.where(iota_m[None, :] == r[:, None], dot, v)

    return lax.fori_loop(0, cnt0, body, v)


def _refactorize(Abar, basis):
    """Gather the current basis matrix from the immutable columns and LU it,
    emptying the eta file (cnt is reset by the caller)."""
    B0 = jnp.take_along_axis(Abar, basis[:, None, :].astype(jnp.int32), axis=2)
    lu, _, perm = lax.linalg.lu(B0)
    perm = perm.astype(jnp.int32)
    perm_inv = jnp.argsort(perm, axis=1).astype(jnp.int32)
    return lu, perm, perm_inv


def inject_revised_warm(state: RevisedState, wb, wonub, *, m: int, n: int,
                        feas_tol: float) -> RevisedState:
    """Seed a freshly built ``RevisedState`` from a parent basis (warm start).

    The revised analogue of ``simplex.inject_tableau_warm``, per LP:

    * **skip** — refactorize the parent basis against the *new* data, solve
      for the basic values; all nonnegative means phase 2 starts directly
      from the parent vertex (at-upper nonbasics contribute through the
      effective rhs);
    * **repair** — rows whose basic value went negative get a fresh
      artificial whose *physical column* is ``-(B e_i)``: the new basis
      matrix is the old one with those columns negated (still nonsingular),
      its basic solution is ``|x_B|`` elementwise, and the ordinary phase-1
      costs (-1 on columns >= n+m, which pricing never scans) drive the
      artificials back out — a repair phase 1 seeded from the parent basis;
    * **cold** — out-of-range indices or a singular parent basis (duplicate
      columns after the artificial->slack remap surface as a non-finite
      solve): the LP keeps the cold state.

    The slack diagonal — the row-sign record ``extract_duals_revised``
    reads — lives in columns n..n+m-1 and is never overwritten."""
    Abar, ub = state.Abar, state.ub
    B = Abar.shape[0]
    dtype = Abar.dtype
    ncand = n + m
    idx = jnp.arange(m)
    in_range = ((wb >= 0) & (wb < n + 2 * m)).all(axis=1)
    wb2 = jnp.clip(jnp.where(wb >= ncand, wb - m, wb), 0, ncand - 1)
    wb2 = wb2.astype(jnp.int32)
    onub_w = wonub & jnp.isfinite(ub)
    bbar = state.xB                      # cold state: xB == sign-adjusted b
    rhs_eff = bbar - jnp.einsum(
        "bmn,bn->bm", Abar[:, :, :n],
        jnp.where(onub_w, ub, 0.0).astype(dtype))
    lu, perm, perm_inv = _refactorize(Abar, wb2)
    xB = _lu_solve(lu, perm, rhs_eff)
    ok = in_range & jnp.isfinite(xB).all(axis=1)
    eps = feas_tol * jnp.maximum(1.0, jnp.max(jnp.abs(bbar), axis=1))
    viol = xB < -eps[:, None]

    Bcols = jnp.take_along_axis(Abar, wb2[:, None, :], axis=2)   # (B, m, m)
    art_w = jnp.where(viol[:, None, :], -Bcols, Abar[:, :, ncand:])
    Abar_w = jnp.concatenate([Abar[:, :, :ncand], art_w], axis=2)
    basis_w = jnp.where(viol, ncand + idx[None, :], wb2).astype(jnp.int32)
    lu2, perm2, pinv2 = _refactorize(Abar_w, basis_w)
    xB_w = jnp.where(viol, -xB, xB)
    phase_w = jnp.where(viol.any(axis=1), 1, 2).astype(jnp.int32)
    thr_w = feas_tol * jnp.maximum(
        1.0, jnp.where(viol, -xB, 0.0).sum(axis=1))

    ok2 = ok[:, None]
    ok3 = ok[:, None, None]
    return state._replace(
        Abar=jnp.where(ok3, Abar_w, Abar),
        xB=jnp.where(ok2, xB_w, state.xB),
        basis=jnp.where(ok2, basis_w, state.basis),
        phase=jnp.where(ok, phase_w, state.phase),
        lu=jnp.where(ok3, lu2, state.lu),
        perm=jnp.where(ok2, perm2, state.perm),
        perm_inv=jnp.where(ok2, pinv2, state.perm_inv),
        onub=jnp.where(ok2, onub_w, state.onub),
        thr=jnp.where(ok, thr_w, state.thr))


# ---------------------------------------------------------------------------
# One lockstep revised pivot
# ---------------------------------------------------------------------------

def revised_step(state: RevisedState, *, m: int, n: int, tol: float,
                 refactor_period: int, rule: str = "dantzig") -> RevisedState:
    """One lockstep revised-simplex pivot across the batch (masked for
    inactive LPs): refactor-if-due, BTRAN, pricing, FTRAN, min-ratio,
    eta-append — the Step 1-3 structure of simplex_step re-expressed on the
    basis factorization instead of the tableau."""
    (Abar, cvec, xB, basis, phase, status, iters, lu, perm, perm_inv,
     etaR, etaV, cnt, onub, ub, thr) = state[:16]
    tel = state.tel
    in_p1 = phase == 1  # pre-update phase, for telemetry attribution
    B = xB.shape[0]
    K = int(refactor_period)
    iota_m = jnp.arange(m, dtype=jnp.int32)
    ncand = n + m
    active = status == _RUNNING
    refac_due = cnt[0] >= K  # scalar; captured pre-reset for telemetry
    # nonbasic-at-upper flags over all candidates (slacks never flip: ub=inf)
    onub_pad = jnp.concatenate([onub, jnp.zeros((B, m), bool)], axis=1)

    # ---- periodic refactorization (eta file full) --------------------------
    def do_refac(_):
        l, p, pi = _refactorize(Abar, basis)
        return l, p, pi, jnp.zeros_like(cnt)

    lu, perm, perm_inv, cnt = lax.cond(
        cnt[0] >= K, do_refac, lambda _: (lu, perm, perm_inv, cnt),
        operand=None)
    cnt0 = cnt[0]

    # ---- Step 1: BTRAN + pricing ------------------------------------------
    # phase-2 costs: c on structurals (slacks 0); phase-1 costs: -1 on
    # artificials, 0 on candidates => candidate reduced costs -y.a_j
    basis_c = jnp.where(basis < ncand,
                        jnp.take_along_axis(
                            cvec, jnp.minimum(basis, ncand - 1), axis=1),
                        0.0)
    cB = jnp.where((phase == 1)[:, None],
                   -(basis >= ncand).astype(xB.dtype), basis_c)
    y = _apply_etas_rev(cB, etaR, etaV, cnt0, iota_m)
    y = _lu_solve_t(lu, perm_inv, y)

    in_p2 = (phase == 2)[:, None]

    # Basic columns are masked out of pricing: their reduced cost is exactly
    # zero in exact arithmetic (so the mask never changes a pivot), but here
    # it is *recomputed* as c_j - y.a_j and the f32 residual can exceed tol —
    # the tableau dialect zeroes the entering column exactly during the
    # rank-1 update and needs no mask; without it a basic column can
    # "re-enter" as a no-op pivot forever.
    bidx = jnp.arange(B)
    basis_safe = jnp.minimum(basis, ncand - 1)
    basis_mask_val = jnp.where(basis < ncand, -BIG, BIG)  # BIG => no-op min

    def price_full(_):
        # improvement score: d_j entering from the lower bound, -d_j from the
        # upper bound (an at-upper variable improves by *decreasing*, which
        # pays off when its reduced cost is positive)
        d = jnp.where(in_p2, cvec, 0.0) - jnp.einsum(
            "bm,bmn->bn", y, Abar[:, :, :ncand])
        d = jnp.where(onub_pad, -d, d)
        return d.at[bidx[:, None], basis_safe].min(basis_mask_val)

    if rule == "partial":
        n_blocks, blk_sz = partial_geometry(ncand)
        blk = (iters % n_blocks).astype(jnp.int32)
        cols = blk[:, None] * blk_sz + jnp.arange(blk_sz, dtype=jnp.int32)
        valid = cols < ncand
        cols_safe = jnp.minimum(cols, ncand - 1)
        Ablk = jnp.take_along_axis(Abar, cols_safe[:, None, :], axis=2)
        cblk = jnp.where(in_p2, jnp.take_along_axis(cvec, cols_safe, axis=1),
                         0.0)
        in_basis = (cols_safe[:, :, None] == basis[:, None, :]).any(axis=2)
        onub_blk = jnp.take_along_axis(onub_pad, cols_safe, axis=1)
        d_raw = cblk - jnp.einsum("bm,bmc->bc", y, Ablk)
        d_blk = jnp.where(valid & ~in_basis,
                          jnp.where(onub_blk, -d_raw, d_raw), -BIG)
        blk_max = jnp.max(d_blk, axis=1)
        e_blk = jnp.take_along_axis(
            cols_safe, jnp.argmax(d_blk, axis=1)[:, None], axis=1)[:, 0]
        blk_improving = blk_max > tol
        priced_out = active & ~blk_improving
        # the full fallback also carries the optimality test, so it runs
        # (for the whole batch) only when some active LP's block priced out
        need_full = jnp.any(priced_out)
        d_full = lax.cond(need_full, price_full,
                          lambda _: jnp.full((B, ncand), -BIG, xB.dtype),
                          operand=None)
        full_max = jnp.max(d_full, axis=1)
        e = jnp.where(blk_improving, e_blk,
                      jnp.argmax(d_full, axis=1).astype(jnp.int32))
        max_cost = jnp.where(blk_improving, blk_max, full_max)
    else:
        priced_out = None
        d_full = price_full(None)
        e = jnp.argmax(d_full, axis=1).astype(jnp.int32)
        max_cost = jnp.max(d_full, axis=1)

    is_opt = max_cost <= tol

    # phase bookkeeping at optimality of the current objective (pre-pivot)
    p1_obj = jnp.where(basis >= ncand, xB, 0.0).sum(axis=1)
    p1_done = active & (phase == 1) & is_opt
    infeasible = p1_done & (p1_obj > thr)
    to_phase2 = p1_done & ~infeasible
    p2_done = active & (phase == 2) & is_opt

    # ---- Step 2: FTRAN + sentinel min-ratio --------------------------------
    # the entering variable moves *down* from its upper bound when flagged:
    # the basic response to a unit move along the edge is -dir * u
    a_e = jnp.take_along_axis(Abar, e[:, None, None], axis=2)[:, :, 0]
    u = _lu_solve(lu, perm, a_e)
    u = _apply_etas_fwd(u, etaR, etaV, cnt0, iota_m)
    onub_e = jnp.take_along_axis(onub_pad, e[:, None], axis=1)[:, 0]
    dir_e = jnp.where(onub_e, -1.0, 1.0).astype(xB.dtype)
    ucol = dir_e[:, None] * u
    valid_row = ucol > tol
    ratios = jnp.where(valid_row, xB / jnp.where(valid_row, ucol, 1.0), BIG)
    # a basic variable the move drives *up* (ucol < 0) may hit its own
    # finite upper bound (slacks/artificials: ub = +inf, never binds)
    ubB = jnp.where(basis < n,
                    jnp.take_along_axis(ub, jnp.minimum(basis, n - 1),
                                        axis=1),
                    jnp.inf).astype(xB.dtype)
    hit_ub = (ucol < -tol) & jnp.isfinite(ubB)
    ratios = jnp.where(hit_ub,
                       (ubB - xB) / jnp.where(hit_ub, -ucol, 1.0), ratios)
    # phase 2 pins basic artificials at zero (same rule as the tableau
    # dialect's simplex_step): an entering column that would grow one leaves
    # it at ratio 0 on a negative pivot element instead
    pin = (phase == 2)[:, None] & (basis >= ncand) & (ucol < -tol)
    ratios = jnp.where(pin, 0.0, ratios)
    l = jnp.argmin(ratios, axis=1).astype(jnp.int32)
    min_ratio = jnp.min(ratios, axis=1)
    no_row = min_ratio >= BIG / 2

    wants_pivot = active & ~is_opt
    # entering variable's own bound: travel of ub_e parks it at the opposite
    # bound with no basis change (a bound flip; strict < is the tie-break
    # shared with the oracle and the tableau dialect)
    t_e = jnp.where(e < n,
                    jnp.take_along_axis(ub, jnp.minimum(e, n - 1)[:, None],
                                        axis=1)[:, 0],
                    jnp.inf).astype(xB.dtype)
    do_flip = wants_pivot & (t_e < min_ratio)
    unbounded = wants_pivot & no_row & ~do_flip & (phase == 2)
    stuck = wants_pivot & no_row & ~do_flip & (phase == 1)
    do_pivot = wants_pivot & ~no_row & ~do_flip

    # ---- Step 3: O(m) update — x_B, bound flags and one eta column ---------
    ul = jnp.take_along_axis(u, l[:, None], axis=1)[:, 0]
    ul_safe = jnp.where(do_pivot, ul, 1.0)
    move = do_flip | do_pivot
    theta = jnp.where(do_flip, t_e, jnp.where(do_pivot, min_ratio, 0.0))
    is_l = iota_m[None, :] == l[:, None]
    # entering variable's post-pivot value: theta above its departing bound
    enter_val = jnp.where(onub_e, t_e - min_ratio, min_ratio)
    xB_new = jnp.where(is_l & do_pivot[:, None], enter_val[:, None],
                       xB - theta[:, None] * ucol)
    xB = jnp.where(move[:, None], xB_new, xB)

    # bound-flag bookkeeping: a flip toggles the entering flag; a pivot
    # clears it (the variable is basic now) and marks the leaving variable
    # at-upper when the min ratio came from its upper-bound row
    col_n = jnp.arange(n, dtype=jnp.int32)
    is_e_n = col_n[None, :] == e[:, None]
    onub = onub ^ (do_flip[:, None] & is_e_n)
    onub = onub & ~(do_pivot[:, None] & is_e_n)
    jl = jnp.take_along_axis(basis, l[:, None], axis=1)[:, 0]
    hit_l = jnp.take_along_axis(hit_ub, l[:, None], axis=1)[:, 0]
    leave_up = do_pivot & hit_l & (jl < n)
    onub = onub | (leave_up[:, None]
                   & (col_n[None, :] == jl[:, None]))

    r_eta = jnp.where(do_pivot, l, 0)
    eta = jnp.where(do_pivot[:, None], -u / ul_safe[:, None], 0.0)
    eta = jnp.where(iota_m[None, :] == r_eta[:, None],
                    jnp.where(do_pivot, 1.0 / ul_safe, 1.0)[:, None], eta)
    zero = jnp.int32(0)
    etaR = lax.dynamic_update_slice(etaR, r_eta[:, None], (zero, cnt0))
    etaV = lax.dynamic_update_slice(etaV, eta[:, None, :], (zero, cnt0, zero))
    # non-pivoting LPs got an identity eta; skip the slot when nobody pivots
    cnt = cnt + jnp.any(do_pivot).astype(jnp.int32)

    basis = jnp.where(do_pivot[:, None] & is_l, e[:, None], basis)

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
        tel = tel_revised_update(tel, refactor=refac_due & active,
                                 eta_len=cnt, block_rotation=priced_out)
    return RevisedState(Abar, cvec, xB, basis, phase, status, iters,
                        lu, perm, perm_inv, etaR, etaV, cnt, onub, ub, thr,
                        tel)


def extract_solution_revised(state: RevisedState, n: int):
    """(x, objective) off the basic solution — no tableau to read.  Nonbasic
    structurals parked at their upper bound contribute ``ub_j`` to both."""
    x = scatter_solution(state.xB, state.basis, n)
    ncand = state.cvec.shape[1]
    cb = jnp.take_along_axis(state.cvec,
                             jnp.minimum(state.basis, ncand - 1), axis=1)
    obj = jnp.where(state.basis < n, cb * state.xB, 0.0).sum(axis=1)
    at_ub = jnp.where(state.onub, state.ub.astype(x.dtype), 0.0)
    x = x + at_ub
    obj = obj + (state.cvec[:, :n] * at_ub).sum(axis=1)
    return x, obj


def extract_duals_revised(state: RevisedState, n: int):
    """Dual certificate ``y = c_B B^-1`` off the final basis factors: one
    extra BTRAN (phase-2 costs), then the candidate pricing matvec for the
    structural reduced costs — the revised-simplex analogue of reading the
    tableau's objective row (simplex.extract_duals).

    The BTRAN solves against the *sign-adjusted* rows; the slack diagonal
    of ``Abar`` carries exactly that sign, so ``y = sign * y_scaled``
    reports the canonical-row duals (same convention as the tableau
    backend).  Returns (y (B, m), z (B, n))."""
    m = state.xB.shape[1]
    ncand = state.cvec.shape[1]
    iota_m = jnp.arange(m, dtype=jnp.int32)
    cB = jnp.where(state.basis < ncand,
                   jnp.take_along_axis(
                       state.cvec, jnp.minimum(state.basis, ncand - 1),
                       axis=1),
                   0.0)
    y_s = _apply_etas_rev(cB, state.etaR, state.etaV, state.cnt[0], iota_m)
    y_s = _lu_solve_t(state.lu, state.perm_inv, y_s)
    idx = jnp.arange(m)
    sign = state.Abar[:, idx, n + idx]          # slack diagonal = row sign
    y = sign * y_s
    z = state.cvec[:, :n] - jnp.einsum("bm,bmn->bn", y_s,
                                       state.Abar[:, :, :n])
    return y, z


def solve_revised(A, b, c, ub=None, *, m: int, n: int, max_iters: int,
                  tol: float, feas_tol: float, refactor_period: int,
                  pricing: str = "dantzig",
                  warm_basis=None, warm_at_upper=None,
                  full_state: bool = False, telemetry: bool = False):
    """Traceable whole-solve body (shared by jit and shard_map): one
    while_loop, per-LP phase switch inside the step (the revised method has
    no dead tableau columns, so there is nothing to phase-compact).

    ``warm_basis``/``warm_at_upper`` seed the solve from a parent basis via
    `inject_revised_warm` (per-LP skip/repair/cold); ``full_state=True``
    appends ``(basis, onub)`` to the return tuple for WarmStart capture."""
    rule = canonicalize_revised_rule(pricing)
    state = build_revised_state(A, b, c, ub, feas_tol=feas_tol,
                                refactor_period=refactor_period)
    if telemetry:
        state = state._replace(tel=init_telemetry(A.shape[0]))
    if warm_basis is not None:
        wonub = (jnp.zeros((A.shape[0], n), bool) if warm_at_upper is None
                 else jnp.asarray(warm_at_upper, bool))
        state = inject_revised_warm(state, jnp.asarray(warm_basis, jnp.int32),
                                    wonub, m=m, n=n, feas_tol=feas_tol)

    def cond(carry):
        s, it = carry
        return jnp.any(s.status == _RUNNING) & (it < max_iters)

    def body(carry):
        s, it = carry
        return revised_step(s, m=m, n=n, tol=tol,
                            refactor_period=refactor_period,
                            rule=rule), it + 1

    state, _ = lax.while_loop(cond, body, (state, jnp.int32(0)))
    status = jnp.where(state.status == _RUNNING, ITERATION_LIMIT, state.status)
    x, obj = extract_solution_revised(state, n)
    y, z = extract_duals_revised(state, n)
    obj = jnp.where(status == OPTIMAL, obj, jnp.nan)
    opt = (status == OPTIMAL)[:, None]
    y = jnp.where(opt, y, jnp.nan)
    z = jnp.where(opt, z, jnp.nan)
    out = (x, obj, status.astype(jnp.int8), state.iters, y, z)
    if full_state:
        out = out + (state.basis, state.onub)
    if telemetry:
        out = out + (state.tel,)
    return out


@functools.partial(jax.jit, static_argnames=("m", "n", "max_iters", "tol",
                                             "feas_tol", "refactor_period",
                                             "pricing", "telemetry"))
def _solve_revised_core(A, b, c, ub, *, m, n, max_iters, tol, feas_tol,
                        refactor_period, pricing, telemetry=False):
    return solve_revised(A, b, c, ub, m=m, n=n, max_iters=max_iters, tol=tol,
                         feas_tol=feas_tol, refactor_period=refactor_period,
                         pricing=pricing, telemetry=telemetry)


@functools.partial(jax.jit, static_argnames=("m", "n", "max_iters", "tol",
                                             "feas_tol", "refactor_period",
                                             "pricing", "telemetry"))
def _solve_revised_core_state(A, b, c, ub, warm_basis, warm_at_upper, *, m, n,
                              max_iters, tol, feas_tol, refactor_period,
                              pricing, telemetry=False):
    """`_solve_revised_core` + warm injection + terminal-state capture (the
    batched entry point's core; warm args may be None for a cold run)."""
    return solve_revised(A, b, c, ub, m=m, n=n, max_iters=max_iters, tol=tol,
                         feas_tol=feas_tol, refactor_period=refactor_period,
                         pricing=pricing, warm_basis=warm_basis,
                         warm_at_upper=warm_at_upper, full_state=True,
                         telemetry=telemetry)


def solve_batched_revised(batch: LPBatch, *, dtype=jnp.float32,
                          tol: float | None = None,
                          feas_tol: float | None = None,
                          max_iters: int | None = None,
                          refactor_period: int | None = None,
                          pricing: str = "dantzig",
                          presolve: bool = True,
                          scale: bool | None = None,
                          warm: WarmStart | None = None,
                          telemetry: bool = False,
                          mesh=None) -> LPResult:
    """Solve a batch of LPs with the lockstep revised simplex.

    Same LPBatch -> LPResult contract, status codes and defaults as
    ``solve_batched_jax`` — including GeneralLPBatch acceptance
    (canonicalize on ingestion, recover on the way out) and ``mesh``;
    ``pricing`` accepts "dantzig" (full pricing) or "partial" (rotating
    column blocks, core/pricing.py).  ``refactor_period`` bounds the eta
    file (None derives ~m/2 via `auto_refactor_period`).  ``warm`` accepts
    a `WarmStart` from a previous solve (any basis-carrying engine): its
    basis/at_upper leaves seed the eta-file via `inject_revised_warm`;
    the result's own ``warm`` field carries the terminal basis onward."""
    batch, rec = ensure_canonical(batch, presolve=presolve, scale=scale)
    m, n = batch.m, batch.n
    if max_iters is None:
        max_iters = default_max_iters(m, n)
    if refactor_period is None:
        refactor_period = auto_refactor_period(m, n)
    if tol is None:
        tol = 1e-6 if dtype == jnp.float32 else 1e-9
    if feas_tol is None:
        feas_tol = 1e-5 if dtype == jnp.float32 else 1e-7
    rule = canonicalize_revised_rule(pricing)
    dt = jax.dtypes.canonicalize_dtype(dtype)
    settings = dict(m=m, n=n, max_iters=int(max_iters), tol=float(tol),
                    feas_tol=float(feas_tol),
                    refactor_period=int(refactor_period), pricing=rule,
                    telemetry=bool(telemetry))
    if mesh is not None:
        return finish_result(rec, sharded_solve(
            solve_revised, batch, mesh, dt, backend="revised", **settings))
    warm = prepare_warm(warm, rec, batch)
    leaves = lp_leaves(batch, dt) + [None, None]
    if warm is not None and warm.basis is not None:
        leaves[4] = (warm.basis, np.dtype(np.int32))
        if warm.at_upper is not None:
            leaves[5] = (warm.at_upper, np.dtype(bool))
    out, wall_s = run(
        functools.partial(_solve_revised_core_state, **settings), leaves,
        B=batch.batch, m=m, n=n)
    x, obj, status, iters, y, z, basis, onub = out[:8]
    stats = None
    if telemetry:
        stats = report_from_counters(tel_to_numpy(out[8]), wall_s=wall_s,
                                     backend="revised")
    res = LPResult(x=x, objective=obj, status=status, iterations=iters,
                   y=y, z=z,
                   warm=WarmStart(m=m, n=n, basis=basis, at_upper=onub,
                                  pricing=rule),
                   stats=stats)
    return finish_result(rec, res)


# ---------------------------------------------------------------------------
# Active-set compaction integration
# ---------------------------------------------------------------------------

def segment_revised_phase1(state: RevisedState, steps, *, m: int, n: int,
                           tol: float, refactor_period: int,
                           rule: str = "dantzig"):
    """Run up to `steps` revised pivots; stops early once no LP is still in
    phase 1 (stage-1 contract of core.compaction.run_schedule)."""
    def cond(carry):
        s, it = carry
        pending = (s.status == _RUNNING) & (s.phase == 1)
        return jnp.any(pending) & (it < steps)

    def body(carry):
        s, it = carry
        return revised_step(s, m=m, n=n, tol=tol,
                            refactor_period=refactor_period,
                            rule=rule), it + 1

    return lax.while_loop(cond, body, (state, jnp.int32(0)))


def segment_revised_phase2(state: RevisedState, steps, *, m: int, n: int,
                           tol: float, refactor_period: int,
                           rule: str = "dantzig"):
    """Run up to `steps` revised pivots; stops early once every LP is
    terminal (stage-2 contract)."""
    def cond(carry):
        s, it = carry
        return jnp.any(s.status == _RUNNING) & (it < steps)

    def body(carry):
        s, it = carry
        return revised_step(s, m=m, n=n, tol=tol,
                            refactor_period=refactor_period,
                            rule=rule), it + 1

    return lax.while_loop(cond, body, (state, jnp.int32(0)))


_segment_rev_p1_jit = jax.jit(
    segment_revised_phase1,
    static_argnames=("m", "n", "tol", "refactor_period", "rule"))
_segment_rev_p2_jit = jax.jit(
    segment_revised_phase2,
    static_argnames=("m", "n", "tol", "refactor_period", "rule"))


@jax.jit
def _refactor_state_jit(state: RevisedState) -> RevisedState:
    lu, perm, perm_inv = _refactorize(state.Abar, state.basis)
    tel = state.tel
    if tel is not None:
        # refactor-on-compact counts as a refactorization for every
        # gathered (still-running) LP
        tel = tel_revised_update(
            tel, refactor=state.status == _RUNNING,
            eta_len=jnp.zeros_like(state.cnt))
    return state._replace(lu=lu, perm=perm, perm_inv=perm_inv,
                          cnt=jnp.zeros_like(state.cnt), tel=tel)


@functools.partial(jax.jit, static_argnames=("n",))
def _extract_revised_jit(state: RevisedState, *, n: int):
    x, obj = extract_solution_revised(state, n)
    y, z = extract_duals_revised(state, n)
    status = jnp.where(state.status == _RUNNING, ITERATION_LIMIT,
                       state.status)
    obj = jnp.where(status == OPTIMAL, obj, jnp.nan)
    opt = (status == OPTIMAL)[:, None]
    return (x, obj, status.astype(jnp.int8), state.iters,
            jnp.where(opt, y, jnp.nan), jnp.where(opt, z, jnp.nan))


class RevisedBackend(JaxBackend):
    """Compaction-scheduler backend for the revised simplex.

    Reuses JaxBackend's generic plumbing (status/phase host fetches, padding
    deactivation, bucket gathers via the tree-mapped take) — RevisedState
    keeps every leaf batched on axis 0, including the eta file and LU
    factors, exactly so those gathers stay generic.  ``take`` additionally
    refactorizes after every gather (refactor-on-compact): the gathered LU
    is still valid per LP, but restarting segments from a clean factor keeps
    the eta file short and bounds f32 drift across bucket shrinks."""

    def __init__(self, m, n, tol, feas_tol, dtype, pricing="dantzig",
                 refactor_period: int | None = None):
        super().__init__(m, n, tol, feas_tol, dtype, pricing="dantzig")
        self.rule = canonicalize_revised_rule(pricing)
        self.refactor_period = int(refactor_period
                                   or auto_refactor_period(m, n))

    def init(self, A, b, c, ub=None, warm: WarmStart | None = None,
             telemetry: bool = False) -> RevisedState:
        state = build_revised_state(A, b, c, ub, feas_tol=self.feas_tol,
                                    refactor_period=self.refactor_period)
        if telemetry:
            state = state._replace(tel=init_telemetry(A.shape[0]))
        if warm is not None and warm.basis is not None:
            wonub = (jnp.zeros((A.shape[0], self.n), bool)
                     if warm.at_upper is None
                     else jnp.asarray(np.asarray(warm.at_upper), bool))
            state = inject_revised_warm(
                state, jnp.asarray(np.asarray(warm.basis), jnp.int32),
                wonub, m=self.m, n=self.n, feas_tol=self.feas_tol)
        return state

    def segment_bodies(self) -> dict:
        """The unjitted segment runners by stage
        (`compaction.JaxBackend.segment_bodies`)."""
        kw = dict(m=self.m, n=self.n, tol=self.tol,
                  refactor_period=self.refactor_period, rule=self.rule)
        return {"p1": functools.partial(segment_revised_phase1, **kw),
                "p2": functools.partial(segment_revised_phase2, **kw)}

    def run_phase1(self, state, steps):
        state, it = _segment_rev_p1_jit(
            state, jnp.int32(steps), m=self.m, n=self.n, tol=self.tol,
            refactor_period=self.refactor_period, rule=self.rule)
        return state, int(it)

    def run_phase2(self, state, steps):
        state, it = _segment_rev_p2_jit(
            state, jnp.int32(steps), m=self.m, n=self.n, tol=self.tol,
            refactor_period=self.refactor_period, rule=self.rule)
        return state, int(it)

    def compact_columns(self, state: RevisedState) -> RevisedState:
        # nothing to drop: the revised method never materialized the
        # artificial columns' tableau, only their immutable data columns
        return state

    def take(self, state: RevisedState, idx) -> RevisedState:
        gathered = super().take(state, idx)
        return _refactor_state_jit(gathered)

    def extract(self, state: RevisedState, stage: str):
        return tuple(np.asarray(o)
                     for o in _extract_revised_jit(state, n=self.n))

    def elements_per_step(self, stage: str) -> int:
        return revised_elements(self.m, self.n,
                                refactor_period=self.refactor_period,
                                partial=(self.rule == "partial"))


def solve_batched_revised_compacted(
        batch: LPBatch, *, dtype=jnp.float32, tol: Optional[float] = None,
        feas_tol: Optional[float] = None, max_iters: Optional[int] = None,
        segment_k: Optional[int] = None,
        compact_threshold: Optional[float] = None,
        refactor_period: Optional[int] = None, pricing: str = "dantzig",
        stats_out: Optional[List[SegmentStat]] = None,
        presolve: bool = True, scale: Optional[bool] = None,
        warm: WarmStart | None = None,
        telemetry: bool = False, tracer=None, mesh=None) -> LPResult:
    """Revised simplex under the active-set compaction scheduler: K-pivot
    segments, power-of-two bucket gathers of survivors (eta file, LU factors
    and basis arrays gathered alongside), refactorization after every gather.
    Same contract as ``solve_batched_compacted`` (GeneralLPBatch and
    ``mesh`` accepted).
    ``warm`` seeds the initial state (the warm-derived leaves then ride the
    bucket gathers automatically); the compacted result reports
    ``warm=None``."""
    batch, rec = ensure_canonical(batch, presolve=presolve, scale=scale,
                                  tracer=tracer)
    m, n = batch.m, batch.n
    if max_iters is None:
        max_iters = default_max_iters(m, n)
    if segment_k is None:
        segment_k = auto_segment_k(m, n)
    if tol is None:
        tol = 1e-6 if dtype == jnp.float32 else 1e-9
    if feas_tol is None:
        feas_tol = 1e-5 if dtype == jnp.float32 else 1e-7
    backend, padded = on_mesh(
        RevisedBackend(m, n, tol, feas_tol, dtype, pricing=pricing,
                       refactor_period=refactor_period), batch, mesh)
    state = backend.init(jnp.asarray(padded.A, dtype),
                         jnp.asarray(padded.b, dtype),
                         jnp.asarray(padded.c, dtype),
                         ub=jnp.asarray(padded.upper_bounds(), dtype),
                         warm=prepare_warm(warm, rec, batch),
                         telemetry=telemetry)
    B = batch.batch
    state, orig = init_orig(backend, state, B)
    cfg = CompactionConfig(
        segment_k=int(segment_k),
        compact_threshold=resolve_compact_threshold(
            compact_threshold, int(segment_k)),
        pad_multiple=backend.pad_multiple)
    return finish_result(rec, run_schedule(backend, state, orig, B, n,
                                           max_iters=int(max_iters),
                                           config=cfg, stats_out=stats_out,
                                           tracer=tracer),
                         tracer=tracer)
