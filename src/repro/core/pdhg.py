"""Batched restarted PDHG — a first-order LP engine beside the two simplexes.

The paper's simplex-per-LP design wins on small/medium batched LPs, but its
scaling story (Sec. 6) stalls where per-pivot *sequential depth* dominates:
every pivot is a reduction -> ratio test -> rank-1 update chain that cannot
be parallelized across iterations.  GPU LP work has since moved to
first-order methods — PDLP / cuPDLP-style **restarted primal-dual hybrid
gradient** — whose iteration is nothing but matvecs: embarrassingly batched,
no pivoting, no basis state, tolerance-based convergence.  This module is
that solver family for the repo's canonical batches:

    maximize c.x   s.t.   A x <= b,  0 <= x <= u   (core/lp.py standard form;
                                                    u may be +inf columnwise)

with dual  min b.y + u.w  s.t.  A^T y + w >= c,  y, w >= 0.  One PDHG
iteration is

    x+ = clip(x + tau * (c - A^T y), 0, u)         # primal gradient + prox
    y+ = max(0, y + sigma * (A (2 x+ - x) - b))    # dual ascent on extrapolant

i.e. exactly one (B, m, n) einsum pair per iteration over the whole batch —
native bounds cost one clip, never an extra row.  The matvecs themselves are
injectable (``Matvecs``): core/sparse.py swaps in shared-pattern scatter-add
matvecs so structurally sparse batches pay O(nnz) instead of O(m*n) per
iteration with the identical round/restart/certificate logic.

The four PDLP ingredients, batched:

* **Diagonal preconditioning** — a few Ruiz (inf-norm) equilibration sweeps
  per LP; residuals and certificates are reported in *unscaled* space via
  elementwise unscaling (no second copy of A needed).
* **Step sizes from ||A||_2** — batched power iteration on A^T A estimates
  the per-LP spectral norm; tau * sigma = (0.9 / ||A||)^2 guarantees
  convergence, and the primal weight omega = sqrt(||c|| / ||b||) balances
  the primal/dual step split (tau = eta/omega, sigma = eta*omega).
* **KKT-residual restarts** — the iterate average since the last restart is
  evaluated alongside the current iterate every ``check_every`` iterations;
  when the better of the two ("candidate") decays the KKT residual enough
  (RESTART_SUFFICIENT) the solve restarts from the candidate.  Restarting
  to averages is what upgrades PDHG's O(1/k) ergodic rate to the linear
  rate observed on LPs (sharpness), and it is per-LP: each batch member
  restarts on its own schedule.
* **Per-LP convergence + certificates** — OPTIMAL when max(primal
  infeasibility, dual infeasibility, duality gap) <= tol in relative terms.
  Divergence is classified by testing the normalized iterate as an
  approximate Farkas ray: y >= 0 with A^T y >= -eps and b.y < 0 certifies
  INFEASIBLE, x >= 0 with A x <= eps and c.x > 0 certifies UNBOUNDED —
  both checked in unscaled space, both the *exact* Farkas conditions up to
  tolerance.  ``max_iters`` exhaustion reports ITERATION_LIMIT.

Unlike the simplex engines this convergence is **tolerance-based**
(``backend_spec("pdhg").exact is False``): statuses agree with the exact
oracles at the configured tolerance, objectives to ~tol relative, and the
returned point is interior-accurate rather than a vertex.  What PDHG gives
back is the **primal-dual certificate for free**: ``LPResult.y`` (row
duals) and ``LPResult.z`` (reduced costs c - A^T y) are the iterates
themselves, the same certificate the simplex backends now derive from the
final basis — backend-uniform, and mapped to original coordinates by
``forms.Recovery.recover_duals`` for general batches.

Composition mirrors the other engines: ``solve_pdhg`` is the traceable body
(jit/shard_map), ``solve_batched_pdhg`` the jitted entry,
``solve_batched_pdhg_compacted`` runs check-rounds as scheduler segments so
converged LPs retire into power-of-two buckets (PDHG's per-LP iteration
counts spread far wider than simplex pivot counts — mean/max ratios of
5-20x are routine — so active-set compaction pays off *harder* here), and
kernels/pdhg_tile.py holds the whole-solve Pallas tile kernel (fused
matvec + prox + restart check in VMEM).
"""
from __future__ import annotations

import functools
from typing import Any, List, NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..obs.report import report_from_counters
from ..obs.telemetry import init_telemetry, tel_pdhg_update, tel_to_numpy
from .forms import ensure_canonical, finish_result, prepare_warm
from .transfer import lp_leaves, run, sharded_solve
from .lp import (
    INFEASIBLE,
    ITERATION_LIMIT,
    OPTIMAL,
    UNBOUNDED,
    LPBatch,
    LPResult,
    WarmStart,
)

_RUNNING = -1

# Restart policy (PDLP-style, on the KKT residual of the restart candidate
# relative to the residual at the last restart): restart on *sufficient*
# decay, on *necessary* decay once the candidate has started regressing
# (oscillation), and artificially once the running average is much older
# than the last restart interval (stale-average guard).
RESTART_SUFFICIENT = 0.2
RESTART_NECESSARY = 0.9
# Adaptive primal weight (PDLP): at each restart, omega moves halfway (in
# log space) toward the observed dual/primal displacement ratio — the
# decisive ingredient on ill-conditioned dense instances (the paper's
# Sec.-6 random class goes from ~80% to 100% oracle status parity).
OMEGA_SMOOTHING = 0.5
OMEGA_MIN, OMEGA_MAX = 1e-4, 1e4
# Ruiz equilibration sweeps / power-iteration steps at setup.
RUIZ_ITERS = 10
POWER_ITERS = 40
# Safety factor on the spectral-norm bound: tau*sigma*||A||^2 = 0.9^2 < 1.
STEP_SAFETY = 0.9
# Convergence is checked (and restarts considered) every this many
# iterations; iteration counts are therefore quantized to it.
CHECK_EVERY = 16
# Farkas-ray classification: relative certificate tolerance, and the minimum
# normalized iterate magnitude before a ray is even considered (bounded
# convergent iterates stay small; diverging rays cross it immediately).
CERT_TOL = 1e-4
RAY_MIN_NORM = 1.0
# Malitsky-Pock linesearch (step_rule="malitsky_pock"): per-iteration dual
# backtracking that lets tau grow past the conservative spectral-norm bound
# on instances where the local curvature allows it — the fix for the
# adversarial dense stragglers that cap out under the fixed step.  A dual
# trial step at tau_try is accepted when
#   sqrt(beta) * tau_try * ||A^T y_try - A^T y|| <= MP_DELTA * ||y_try - y||
# (beta = omega^2, so sigma = beta * tau preserves the primal weight);
# rejection shrinks tau_try by MP_MU, and after MP_TRIALS rejections the
# iteration falls back to the known-safe fixed step (sqrt(beta) * tau0 =
# eta <= STEP_SAFETY / ||A||) and resets the growth clock.
MP_DELTA = 0.99
MP_MU = 0.7
MP_TRIALS = 6


def default_pdhg_max_iters(m: int, n: int) -> int:
    """Iteration cap for the first-order engine.  PDHG needs thousands of
    (cheap) iterations where simplex needs tens of (expensive) pivots; the
    cap only bounds the lockstep loop on pathological members (sized so the
    paper's ill-conditioned Sec.-6 random class converges with margin)."""
    return 200 * (m + n) + 30000


def pdhg_elements(m: int, n: int) -> int:
    """State elements touched per PDHG iteration (the executed-work unit of
    benchmarks/pivot_work.py): the two matvecs read the (m, n) data twice
    and write the four length-m/n vectors."""
    return 2 * m * n + 2 * (m + n)


class PdhgState(NamedTuple):
    """Resumable solver state; every leaf keeps the batch on axis 0 so the
    compaction scheduler's generic gathers apply unchanged.  The problem
    data rides in the state (like RevisedState's ``Abar``) because segment
    boundaries must be able to gather it alongside the iterates."""
    A: jax.Array        # (B, m, n) Ruiz-scaled data — or, under a sparse
                        #  matvec pair (core/sparse.py), the (B, nnz) scaled
                        #  value array of the shared pattern
    b: jax.Array        # (B, m) scaled rhs
    c: jax.Array        # (B, n) scaled objective
    rsc: jax.Array      # (B, m) row scales (residual unscaling)
    csc: jax.Array      # (B, n) col scales
    ub: jax.Array       # (B, n) scaled upper bounds (+inf = unbounded); the
                        #  prox step clips to [0, ub], so x <= ub holds
                        #  exactly at every iterate
    eta: jax.Array      # (B, 1) base step: tau*sig = eta^2 <= 1/||A||^2
    omega: jax.Array    # (B, 1) primal weight: tau = eta/omega, sig = eta*omega
    binf: jax.Array     # (B,) unscaled ||b||_inf (relative residual floor)
    cinf: jax.Array     # (B,) unscaled ||c||_inf
    x: jax.Array        # (B, n) primal iterate (scaled space)
    y: jax.Array        # (B, m) dual iterate (scaled space)
    xs: jax.Array       # (B, n) running primal sum since last restart
    ys: jax.Array       # (B, m) running dual sum
    xr: jax.Array       # (B, n) last-restart anchor (primal-weight update)
    yr: jax.Array       # (B, m) last-restart anchor
    cnt: jax.Array      # (B,) iterations in the running average
    last_res: jax.Array  # (B,) KKT residual at the last restart
    prev_res: jax.Array  # (B,) candidate residual at the previous check
    phase: jax.Array    # (B,) int32 — constant 2 (no phase 1; lets the
                        #  compaction scheduler's stage-1 pass no-op)
    status: jax.Array   # (B,) int32 — _RUNNING until terminal
    iters: jax.Array    # (B,) int32
    tel: Any = None     # obs.TelemetryState lanes or None (empty subtree:
                        #  the telemetry-off trace is unchanged)


# ---------------------------------------------------------------------------
# Matvec abstraction: the whole engine touches A only through Ax / A^T y
# ---------------------------------------------------------------------------

class Matvecs(NamedTuple):
    """The two matvecs PDHG is made of, as injectable closures.  ``data`` is
    whatever PdhgState.A holds — the dense (B, m, n) array here, a (B, nnz)
    shared-pattern value array in core/sparse.py — so one iteration/check/
    certificate implementation serves both storage formats."""
    ax: object    # (data, x: (B, n)) -> (B, m)
    aty: object   # (data, y: (B, m)) -> (B, n)


DENSE_MV = Matvecs(
    ax=lambda A, x: jnp.einsum("bmn,bn->bm", A, x),
    aty=lambda A, y: jnp.einsum("bmn,bm->bn", A, y))


# ---------------------------------------------------------------------------
# Setup: equilibration + step sizes
# ---------------------------------------------------------------------------

def ruiz_equilibrate(A: jax.Array, iters: int = RUIZ_ITERS):
    """Batched Ruiz (inf-norm) equilibration: returns (r, s) with
    r[:, :, None] * A * s[:, None, :] having rows/cols of ~unit inf-norm.
    All-zero rows/columns keep scale 1."""
    B, m, n = A.shape
    r = jnp.ones((B, m), A.dtype)
    s = jnp.ones((B, n), A.dtype)

    def body(_, rs):
        r, s = rs
        W = jnp.abs(A) * r[:, :, None] * s[:, None, :]
        rn = W.max(axis=2)
        r = r / jnp.sqrt(jnp.where(rn > 0, rn, 1.0))
        W = jnp.abs(A) * r[:, :, None] * s[:, None, :]
        cn = W.max(axis=1)
        s = s / jnp.sqrt(jnp.where(cn > 0, cn, 1.0))
        return r, s

    return jax.lax.fori_loop(0, iters, body, (r, s))


def power_sigma_max(A: jax.Array, iters: int = POWER_ITERS) -> jax.Array:
    """Batched power iteration on A^T A: per-LP spectral-norm estimate
    ||A||_2 (floored away from zero for all-zero members)."""
    B, m, n = A.shape
    v = jnp.full((B, n), 1.0 / np.sqrt(n), A.dtype)

    def body(_, v):
        w = jnp.einsum("bmn,bm->bn", A, jnp.einsum("bmn,bn->bm", A, v))
        nw = jnp.linalg.norm(w, axis=1, keepdims=True)
        return w / jnp.where(nw > 0, nw, 1.0)

    v = jax.lax.fori_loop(0, iters, body, v)
    return jnp.maximum(jnp.linalg.norm(jnp.einsum("bmn,bn->bm", A, v),
                                       axis=1), 1e-12)


def init_pdhg_state(A, b, c, ub=None) -> PdhgState:
    """Equilibrate, estimate step sizes, and seed the zero iterate.  ``ub``
    (unscaled, +inf = unbounded) is carried into scaled space as ub / csc
    since x_unscaled = x_scaled * csc."""
    B, m, n = A.shape
    dtype = A.dtype
    binf = jnp.abs(b).max(axis=1)
    cinf = jnp.abs(c).max(axis=1)
    r, s = ruiz_equilibrate(A)
    As = A * r[:, :, None] * s[:, None, :]
    bs = b * r
    cs = c * s
    if ub is None:
        ubs = jnp.full((B, n), jnp.inf, dtype)
    else:
        ubs = (jnp.asarray(ub, dtype) / s).astype(dtype)
    eta = STEP_SAFETY / power_sigma_max(As)
    nc = jnp.linalg.norm(cs, axis=1)
    nb = jnp.linalg.norm(bs, axis=1)
    omega = jnp.sqrt(jnp.where((nc > 0) & (nb > 0),
                               nc / jnp.maximum(nb, 1e-12), 1.0))
    omega = jnp.clip(omega, OMEGA_MIN, OMEGA_MAX)
    return PdhgState(
        A=As, b=bs, c=cs, rsc=r, csc=s, ub=ubs,
        eta=eta[:, None].astype(dtype),
        omega=omega[:, None].astype(dtype),
        binf=binf, cinf=cinf,
        x=jnp.zeros((B, n), dtype), y=jnp.zeros((B, m), dtype),
        xs=jnp.zeros((B, n), dtype), ys=jnp.zeros((B, m), dtype),
        xr=jnp.zeros((B, n), dtype), yr=jnp.zeros((B, m), dtype),
        cnt=jnp.zeros((B,), dtype),
        last_res=jnp.full((B,), jnp.inf, dtype),
        prev_res=jnp.full((B,), jnp.inf, dtype),
        phase=jnp.full((B,), 2, jnp.int32),
        status=jnp.full((B,), _RUNNING, jnp.int32),
        iters=jnp.zeros((B,), jnp.int32))


def inject_pdhg_warm(state: PdhgState, wx, wy, womega=None,
                     mv: Matvecs = DENSE_MV) -> PdhgState:
    """Seed the iterate from a parent solve's terminal point (warm start).

    ``wx``/``wy`` arrive in *unscaled canonical* coordinates (the WarmStart
    carrier convention) and are mapped into this state's Ruiz-scaled space,
    projected onto the feasible boxes (x into [0, ub], y into >= 0).  The
    **reset guard** makes a bad warm start harmless: each LP adopts the
    warm point only where its KKT residual is no worse than the zero
    iterate's — otherwise that LP silently starts cold.  ``womega`` carries
    the parent's adapted primal weight (clipped to the usual range); ``eta``
    is always re-estimated fresh from the new data (step sizes depend on
    ||A|| of *this* batch, not the parent's).  Restart bookkeeping
    (averages, anchors, residual history) starts clean from the adopted
    point, so the downstream round logic is oblivious to warm starts."""
    dtype = state.x.dtype
    xw = jnp.clip(jnp.asarray(wx, dtype) / state.csc, 0.0, state.ub)
    yw = jnp.maximum(jnp.asarray(wy, dtype) / state.rsc, 0.0)
    xw = jnp.where(jnp.isfinite(xw), xw, 0.0)
    yw = jnp.where(jnp.isfinite(yw), yw, 0.0)
    res_w = kkt_residuals(state, xw, yw, mv)
    res_0 = kkt_residuals(state, state.x, state.y, mv)
    adopt = jnp.isfinite(res_w) & (res_w <= res_0)
    x = jnp.where(adopt[:, None], xw, state.x)
    y = jnp.where(adopt[:, None], yw, state.y)
    omega = state.omega
    if womega is not None:
        ow = jnp.asarray(womega, dtype).reshape(-1, 1)
        ow = jnp.where(jnp.isfinite(ow),
                       jnp.clip(ow, OMEGA_MIN, OMEGA_MAX), state.omega)
        omega = jnp.where(adopt[:, None], ow, state.omega)
    return state._replace(x=x, y=y, xr=x, yr=y, omega=omega)


# ---------------------------------------------------------------------------
# Residuals + certificates
# ---------------------------------------------------------------------------

def kkt_residual_parts(s: PdhgState, x, y, mv: Matvecs = DENSE_MV):
    """Relative KKT residual components of a (scaled-space) point, reported
    for the *unscaled* problem: (primal infeasibility, dual infeasibility,
    duality gap).  Unscaling is elementwise — A itself is only touched
    through the two scaled matvecs.

    Bounded columns (finite ub) shift from the dual-infeasibility term to
    the dual objective: the dual of max c.x s.t. Ax <= b, 0 <= x <= u is
    min b.y + u.w s.t. A^T y + w >= c with w >= 0, so any positive reduced
    cost on a bounded column is absorbed by w_j = (c - A^T y)_j+ (at the
    price u_j * w_j in the gap) instead of counting as infeasibility."""
    ax = mv.ax(s.A, x)
    aty = mv.aty(s.A, y)
    rp = (jnp.maximum(ax - s.b, 0.0) / s.rsc).max(axis=1) / (1.0 + s.binf)
    zc = jnp.maximum(s.c - aty, 0.0)
    fin = jnp.isfinite(s.ub)
    rd = (jnp.where(fin, 0.0, zc) / s.csc).max(axis=1) / (1.0 + s.cinf)
    pobj = jnp.einsum("bn,bn->b", s.c, x)
    # scaled dots equal unscaled dots; u0_j * w0_j = ub_scaled_j * zc_j
    dobj = jnp.einsum("bm,bm->b", s.b, y) \
        + (jnp.where(fin, s.ub, 0.0) * zc).sum(axis=1)
    gap = jnp.abs(pobj - dobj) / (1.0 + jnp.abs(pobj) + jnp.abs(dobj))
    return rp, rd, gap


def kkt_residuals(s: PdhgState, x, y, mv: Matvecs = DENSE_MV):
    """max over the `kkt_residual_parts` triple — the convergence test."""
    rp, rd, gap = kkt_residual_parts(s, x, y, mv)
    return jnp.maximum(jnp.maximum(rp, rd), gap)


def _ray_certificates(s: PdhgState, active, mv: Matvecs = DENSE_MV):
    """Approximate Farkas-ray classification of diverging iterates.

    Works on the unscaled rays (y_u = r * y / ||.||, x_u = s * x / ||.||,
    both elementwise rescales of scaled matvecs):
      INFEASIBLE <- y_u >= 0, A^T y_u >= -eps (unbounded cols),
                    b.y_u + sum_fin u_j (A^T y_u)_j^- < -eps
      UNBOUNDED  <- x_u >= 0 supported on unbounded cols, A x_u <= eps,
                    c.x_u > eps
    Finite upper bounds relax the dual ray (the slack w_j = (A^T y_u)_j^-
    is admissible on bounded columns at cost u_j w_j) and restrict the
    primal ray: a recession direction of {Ax <= b, 0 <= x <= u} cannot
    move a bounded coordinate, so the candidate ray is the iterate
    *projected onto the unbounded columns* (bounded components sit at
    finite values <= u and are not part of any divergence).
    Bounded (convergent) iterates stay below RAY_MIN_NORM in normalized
    magnitude and are never classified."""
    fin = jnp.isfinite(s.ub)
    ubm = jnp.where(fin, s.ub, 0.0)
    # dual ray -> primal infeasibility
    yinf = jnp.abs(s.y * s.rsc).max(axis=1)
    yh = s.y / jnp.maximum(yinf, 1e-12)[:, None]
    aty_s = mv.aty(s.A, yh)
    aty_u = aty_s / s.csc                                # A0^T (r yh)
    by_u = jnp.einsum("bm,bm->b", s.b, yh)               # b0 . (r yh)
    # u0_j * max(0, -(A0^T yh)_j) = ub_scaled_j * max(0, -aty_scaled_j)
    uw = (ubm * jnp.maximum(-aty_s, 0.0)).sum(axis=1)
    ray_scale = 1.0 + s.binf + s.cinf
    infeas = active & (yinf > RAY_MIN_NORM) \
        & (jnp.where(fin, jnp.inf, aty_u).min(axis=1)
           >= -CERT_TOL * ray_scale) \
        & (by_u + uw <= -CERT_TOL * ray_scale)
    # primal ray -> unboundedness (supported on unbounded columns only; an
    # all-bounded LP has xinf == 0 and is never classified here)
    xray = jnp.where(fin, 0.0, s.x)
    xinf = jnp.abs(xray * s.csc).max(axis=1)
    xh = xray / jnp.maximum(xinf, 1e-12)[:, None]
    ax_u = mv.ax(s.A, xh) / s.rsc
    cx_u = jnp.einsum("bn,bn->b", s.c, xh)
    unbounded = active & (xinf > RAY_MIN_NORM) \
        & (ax_u.max(axis=1) <= CERT_TOL * ray_scale) \
        & (cx_u >= CERT_TOL * ray_scale)
    return infeas, unbounded


# ---------------------------------------------------------------------------
# The solver: fused iteration rounds + check/restart
# ---------------------------------------------------------------------------

def pdhg_round(s: PdhgState, *, tol: float,
               check_every: int = CHECK_EVERY,
               mv: Matvecs = DENSE_MV) -> PdhgState:
    """``check_every`` fused PDHG iterations followed by one convergence /
    restart / certificate check — the scheduler-visible unit of work (one
    "round").  Terminal LPs perform masked no-ops, exactly like the
    simplex engines' lockstep steps."""
    active0 = s.status == _RUNNING
    act = active0[:, None]
    tau = s.eta / s.omega
    sig = s.eta * s.omega

    def body(_, carry):
        x, y, xs, ys, cnt = carry
        aty = mv.aty(s.A, y)
        # the prox of [0, ub] indicator: clip (ub = +inf reduces to max)
        xn = jnp.clip(x + tau * (s.c - aty), 0.0, s.ub)
        ax2 = mv.ax(s.A, 2.0 * xn - x)
        yn = jnp.maximum(y + sig * (ax2 - s.b), 0.0)
        x = jnp.where(act, xn, x)
        y = jnp.where(act, yn, y)
        return (x, y, xs + jnp.where(act, x, 0.0),
                ys + jnp.where(act, y, 0.0), cnt + active0)

    x, y, xs, ys, cnt = jax.lax.fori_loop(
        0, check_every, body, (s.x, s.y, s.xs, s.ys, s.cnt))
    s = s._replace(x=x, y=y, xs=xs, ys=ys, cnt=cnt,
                   iters=s.iters + check_every * active0)
    if s.tel is not None:
        s = s._replace(tel=tel_pdhg_update(
            s.tel, inc_iters=check_every * active0))
    return _pdhg_check(s, tol=tol, mv=mv)


def _pdhg_check(s: PdhgState, *, tol: float,
                mv: Matvecs = DENSE_MV) -> PdhgState:
    """The round's convergence / restart / certificate check, shared by
    every step rule (the fixed-step and Malitsky-Pock rounds differ only
    in how they produce the iterates that land here)."""
    active0 = s.status == _RUNNING

    # ---- check: candidate = better of current iterate and running average --
    cc = jnp.maximum(s.cnt, 1.0)[:, None]
    xa, ya = s.xs / cc, s.ys / cc
    res_cur = kkt_residuals(s, s.x, s.y, mv)
    res_avg = kkt_residuals(s, xa, ya, mv)
    use_avg = res_avg < res_cur
    res = jnp.where(use_avg, res_avg, res_cur)
    xc = jnp.where(use_avg[:, None], xa, s.x)
    yc = jnp.where(use_avg[:, None], ya, s.y)

    converged = active0 & (res <= tol)
    # PDLP-style restarts: sufficient decay, or necessary decay once the
    # candidate has started regressing (the average has peaked)
    restart = (res <= RESTART_SUFFICIENT * s.last_res) \
        | ((res <= RESTART_NECESSARY * s.last_res) & (res > s.prev_res))
    restart = active0 & ~converged & restart
    adopt = (converged | restart)[:, None]
    x = jnp.where(adopt, xc, s.x)
    y = jnp.where(adopt, yc, s.y)
    xs = jnp.where(restart[:, None], 0.0, s.xs)
    ys = jnp.where(restart[:, None], 0.0, s.ys)
    cnt = jnp.where(restart, 0.0, s.cnt)
    last_res = jnp.where(restart, res, s.last_res)
    prev_res = jnp.where(restart, jnp.inf, res)

    # adaptive primal weight: at a restart, move omega (log-space, smoothed)
    # toward the dual/primal displacement ratio since the previous restart
    dx = jnp.linalg.norm(xc - s.xr, axis=1)
    dy = jnp.linalg.norm(yc - s.yr, axis=1)
    can_adapt = restart & (dx > 1e-10) & (dy > 1e-10)
    om = s.omega[:, 0]
    om_new = jnp.exp(OMEGA_SMOOTHING
                     * jnp.log(jnp.maximum(dy, 1e-12)
                               / jnp.maximum(dx, 1e-12))
                     + (1.0 - OMEGA_SMOOTHING) * jnp.log(om))
    omega = jnp.where(can_adapt, jnp.clip(om_new, OMEGA_MIN, OMEGA_MAX),
                      om)[:, None]
    xr = jnp.where(restart[:, None], xc, s.xr)
    yr = jnp.where(restart[:, None], yc, s.yr)

    infeas, unbounded = _ray_certificates(s, active0 & ~converged, mv)
    status = jnp.where(converged, OPTIMAL, s.status)
    status = jnp.where(infeas, INFEASIBLE, status)
    status = jnp.where(unbounded, UNBOUNDED, status)
    tel = s.tel
    if tel is not None:
        # component triple at the adopted candidate (extra matvecs only on
        # the telemetry trace); terminal LPs recompute frozen values
        rp_t, rd_t, gap_t = kkt_residual_parts(s, xc, yc, mv)
        tel = tel_pdhg_update(tel, restart=restart, kkt=(rp_t, rd_t, gap_t),
                              omega=omega)
    return s._replace(x=x, y=y, xs=xs, ys=ys, xr=xr, yr=yr, cnt=cnt,
                      last_res=last_res, prev_res=prev_res, omega=omega,
                      status=status, tel=tel)


def pdhg_round_mp(s: PdhgState, tau, tprev, *, tol: float,
                  check_every: int = CHECK_EVERY,
                  mv: Matvecs = DENSE_MV):
    """Malitsky-Pock round: ``check_every`` iterations with per-iteration
    dual linesearch (see the MP_* constants), then the same check as
    `pdhg_round`.  ``tau``/``tprev`` are (B, 1) per-LP primal steps carried
    across rounds (the linesearch extrapolates with theta = tau/tprev);
    returns ``(state, tau, tprev)``.  The primal weight keeps adapting at
    restarts exactly as under the fixed rule — the linesearch scales the
    step magnitude, omega keeps steering the primal/dual split."""
    active0 = s.status == _RUNNING
    act = active0[:, None]
    beta = s.omega ** 2
    sqb = s.omega                    # sqrt(beta), omega > 0 by construction
    tau0 = s.eta / s.omega
    sig0 = s.eta * s.omega

    def body(_, carry):
        x, y, xs, ys, cnt, tau, tprev = carry
        aty = mv.aty(s.A, y)
        xn = jnp.clip(x + tau * (s.c - aty), 0.0, s.ub)

        def trial(_, tc):
            tau_t, y_acc, t_acc, done = tc
            theta = tau_t / jnp.maximum(tau, 1e-30)
            xbar = xn + theta * (xn - x)
            y_try = jnp.maximum(
                y + beta * tau_t * (mv.ax(s.A, xbar) - s.b), 0.0)
            lhs = sqb * tau_t * jnp.linalg.norm(
                mv.aty(s.A, y_try) - aty, axis=1)[:, None]
            rhs = MP_DELTA * jnp.linalg.norm(y_try - y, axis=1)[:, None]
            # a zero dual move (rhs == 0 == lhs) is a fixed point: accept
            ok = ~done & (lhs <= rhs + 1e-30)
            y_acc = jnp.where(ok, y_try, y_acc)
            t_acc = jnp.where(ok, tau_t, t_acc)
            done = done | ok
            return (jnp.where(done, tau_t, tau_t * MP_MU), y_acc, t_acc,
                    done)

        theta0 = tau / jnp.maximum(tprev, 1e-30)
        init = (tau * jnp.sqrt(1.0 + theta0), jnp.zeros_like(y),
                jnp.zeros_like(tau), jnp.zeros_like(tau, bool))
        _, y_acc, t_acc, done = jax.lax.fori_loop(0, MP_TRIALS, trial, init)
        # fallback: the known-safe fixed step, and reset the growth clock
        y_fb = jnp.maximum(
            y + sig0 * (mv.ax(s.A, 2.0 * xn - x) - s.b), 0.0)
        yn = jnp.where(done, y_acc, y_fb)
        tau_n = jnp.where(done, t_acc, tau0)
        tprev_n = jnp.where(done, tau, tau0)
        x = jnp.where(act, xn, x)
        y = jnp.where(act, yn, y)
        tau = jnp.where(act, tau_n, tau)
        tprev = jnp.where(act, tprev_n, tprev)
        return (x, y, xs + jnp.where(act, x, 0.0),
                ys + jnp.where(act, y, 0.0), cnt + active0, tau, tprev)

    x, y, xs, ys, cnt, tau, tprev = jax.lax.fori_loop(
        0, check_every, body, (s.x, s.y, s.xs, s.ys, s.cnt, tau, tprev))
    s = s._replace(x=x, y=y, xs=xs, ys=ys, cnt=cnt,
                   iters=s.iters + check_every * active0)
    if s.tel is not None:
        s = s._replace(tel=tel_pdhg_update(
            s.tel, inc_iters=check_every * active0))
    return _pdhg_check(s, tol=tol, mv=mv), tau, tprev


def extract_pdhg(s: PdhgState, mv: Matvecs = DENSE_MV):
    """(x, obj, status, iters, y, z) in *unscaled* canonical coordinates.
    ``z = c - A^T y`` is the reduced-cost certificate; objective and duals
    are NaN off-OPTIMAL, matching the solver convention."""
    x = s.x * s.csc
    y = s.y * s.rsc
    obj = jnp.einsum("bn,bn->b", s.c, s.x)      # == c0 . x_unscaled
    z = s.c / s.csc - mv.aty(s.A, s.y) / s.csc
    status = jnp.where(s.status == _RUNNING, ITERATION_LIMIT, s.status)
    opt = (status == OPTIMAL)
    obj = jnp.where(opt, obj, jnp.nan)
    y = jnp.where(opt[:, None], y, jnp.nan)
    z = jnp.where(opt[:, None], z, jnp.nan)
    return x, obj, status.astype(jnp.int8), s.iters, y, z


def solve_pdhg(A, b, c, ub=None, *, m: int, n: int, max_iters: int,
               tol: float, feas_tol: float = 0.0,
               check_every: int = CHECK_EVERY,
               warm_x=None, warm_y=None, warm_omega=None,
               full_state: bool = False, step_rule: str = "fixed",
               telemetry: bool = False):
    """Traceable whole-solve body (shared by jit and shard_map):
    setup + one while_loop over check rounds.  ``feas_tol`` is accepted for
    entry-point uniformity but unused (PDHG has no phase 1 — feasibility is
    part of the KKT residual).  ``warm_x``/``warm_y``/``warm_omega`` seed
    the iterate via `inject_pdhg_warm` (per-LP reset guard included);
    ``full_state=True`` appends the terminal iterate leaves
    (x, y unscaled *pre NaN-mask*, omega, eta) for WarmStart capture.
    ``step_rule`` selects the iteration: "fixed" (default — the spectral
    step estimate) or "malitsky_pock" (per-iteration dual linesearch,
    see `pdhg_round_mp`)."""
    del feas_tol
    if step_rule not in ("fixed", "malitsky_pock"):
        raise ValueError(
            f"unknown step_rule {step_rule!r}: expected 'fixed' or "
            "'malitsky_pock'")
    state = init_pdhg_state(A, b, c, ub)
    if telemetry:
        state = state._replace(tel=init_telemetry(state.x.shape[0]))
    if warm_x is not None and warm_y is not None:
        state = inject_pdhg_warm(state, warm_x, warm_y, warm_omega)
    rounds = -(-int(max_iters) // int(check_every))

    if step_rule == "malitsky_pock":
        tau0 = state.eta / state.omega

        def cond_mp(carry):
            s, _, _, it = carry
            return jnp.any(s.status == _RUNNING) & (it < rounds)

        def body_mp(carry):
            s, tau, tprev, it = carry
            s, tau, tprev = pdhg_round_mp(s, tau, tprev, tol=tol,
                                          check_every=check_every)
            return s, tau, tprev, it + 1

        state, _, _, _ = jax.lax.while_loop(
            cond_mp, body_mp, (state, tau0, tau0, jnp.int32(0)))
    else:
        def cond(carry):
            s, it = carry
            return jnp.any(s.status == _RUNNING) & (it < rounds)

        def body(carry):
            s, it = carry
            return pdhg_round(s, tol=tol, check_every=check_every), it + 1

        state, _ = jax.lax.while_loop(cond, body, (state, jnp.int32(0)))
    out = extract_pdhg(state)
    if full_state:
        out = out + (state.x * state.csc, state.y * state.rsc,
                     state.omega[:, 0], state.eta[:, 0])
    if telemetry:
        out = out + (state.tel,)
    return out


@functools.partial(jax.jit, static_argnames=("m", "n", "max_iters", "tol",
                                             "check_every", "telemetry"))
def _solve_pdhg_core(A, b, c, ub, *, m, n, max_iters, tol, check_every,
                     telemetry=False):
    return solve_pdhg(A, b, c, ub, m=m, n=n, max_iters=max_iters, tol=tol,
                      check_every=check_every, telemetry=telemetry)


@functools.partial(jax.jit, static_argnames=("m", "n", "max_iters", "tol",
                                             "check_every", "step_rule",
                                             "telemetry"))
def _solve_pdhg_core_state(A, b, c, ub, warm_x, warm_y, warm_omega, *, m, n,
                           max_iters, tol, check_every,
                           step_rule="fixed", telemetry=False):
    """`_solve_pdhg_core` + warm injection + terminal-iterate capture (the
    batched entry point's core; warm args may be None for a cold run)."""
    return solve_pdhg(A, b, c, ub, m=m, n=n, max_iters=max_iters, tol=tol,
                      check_every=check_every, warm_x=warm_x, warm_y=warm_y,
                      warm_omega=warm_omega, full_state=True,
                      step_rule=step_rule, telemetry=telemetry)


def _check_pdhg_pricing(pricing: str) -> None:
    if pricing != "dantzig":
        raise ValueError(
            f"pricing rule {pricing!r} is a simplex concept; the pdhg "
            "backend has no pivot selection (every iteration touches every "
            "column).  Use the default pricing with backend='pdhg'.")


def solve_batched_pdhg(batch: LPBatch, *, dtype=jnp.float32,
                       tol: float | None = None,
                       feas_tol: float | None = None,
                       max_iters: int | None = None,
                       check_every: int = CHECK_EVERY,
                       pricing: str = "dantzig",
                       presolve: bool = True,
                       scale: bool | None = None,
                       warm: WarmStart | None = None,
                       step_rule: str = "fixed",
                       telemetry: bool = False,
                       mesh=None) -> LPResult:
    """Solve a batch with the restarted-PDHG first-order engine.

    Same LPBatch -> LPResult contract, GeneralLPBatch acceptance and
    ``mesh`` as every solver entry point.  Differences from the simplex
    engines:

    * ``tol`` is the *relative KKT tolerance* (primal/dual infeasibility
      and duality gap); OPTIMAL is tolerance-based, objectives are accurate
      to ~tol relative.  Default 1e-5 (f32) / 1e-8 (f64).
    * ``iterations`` counts PDHG iterations (quantized to ``check_every``)
      — typically 10^2-10^4, not comparable to pivot counts (see
      analysis.lp_perf.pdhg_crossover for the honest flops comparison).
    * ``LPResult.y``/``z`` are the native primal-dual certificate.
    * ``warm`` accepts a `WarmStart` carrying x/y iterates (any engine's —
      the simplex backends' vertex solutions work too); adoption is
      per-LP behind the `inject_pdhg_warm` reset guard, so a stale warm
      start can never do worse than cold.
    * ``step_rule="malitsky_pock"`` enables the per-iteration dual
      linesearch (`pdhg_round_mp`) — the default stays the fixed
      spectral-estimate step.
    """
    _check_pdhg_pricing(pricing)
    del feas_tol
    batch, rec = ensure_canonical(batch, presolve=presolve, scale=scale)
    m, n = batch.m, batch.n
    if max_iters is None:
        max_iters = default_pdhg_max_iters(m, n)
    if tol is None:
        tol = 1e-5 if dtype == jnp.float32 else 1e-8
    dt = jax.dtypes.canonicalize_dtype(dtype)
    settings = dict(m=m, n=n, max_iters=int(max_iters), tol=float(tol),
                    check_every=int(check_every), step_rule=str(step_rule),
                    telemetry=bool(telemetry))
    if mesh is not None:
        return finish_result(rec, sharded_solve(
            solve_pdhg, batch, mesh, dt, backend="pdhg", **settings))
    warm = prepare_warm(warm, rec, batch)
    leaves = lp_leaves(batch, dt) + [None, None, None]
    if warm is not None and warm.x is not None and warm.y is not None:
        leaves[4] = (np.nan_to_num(np.asarray(warm.x, np.float64),
                                   posinf=0.0, neginf=0.0), dt)
        leaves[5] = (np.nan_to_num(np.asarray(warm.y, np.float64),
                                   posinf=0.0, neginf=0.0), dt)
        if warm.omega is not None:
            leaves[6] = (warm.omega, dt)
    out, wall_s = run(functools.partial(_solve_pdhg_core_state, **settings),
                      leaves, B=batch.batch, m=m, n=n)
    x, obj, status, iters, y, z, wx_t, wy_t, om_t, eta_t = out[:10]
    stats = None
    if telemetry:
        stats = report_from_counters(tel_to_numpy(out[10]), wall_s=wall_s,
                                     backend="pdhg")
    res = LPResult(x=x, objective=obj, status=status, iterations=iters,
                   y=y, z=z,
                   warm=WarmStart(m=m, n=n, x=wx_t, y=wy_t, omega=om_t,
                                  eta=eta_t),
                   stats=stats)
    return finish_result(rec, res)


# ---------------------------------------------------------------------------
# Active-set compaction integration
# ---------------------------------------------------------------------------

def segment_pdhg(state: PdhgState, steps, *, tol: float,
                 check_every: int = CHECK_EVERY):
    """Run up to ``steps`` check rounds; stops early once every LP is
    terminal (stage-2 contract of core.compaction.run_schedule)."""
    def cond(carry):
        s, it = carry
        return jnp.any(s.status == _RUNNING) & (it < steps)

    def body(carry):
        s, it = carry
        return pdhg_round(s, tol=tol, check_every=check_every), it + 1

    return jax.lax.while_loop(cond, body, (state, jnp.int32(0)))


_segment_pdhg_jit = jax.jit(segment_pdhg,
                            static_argnames=("tol", "check_every"))


@jax.jit
def _extract_pdhg_jit(state: PdhgState):
    return extract_pdhg(state)


class PdhgBackend:
    """Compaction-scheduler backend for the first-order engine.

    The scheduler's unit of work ("step") is one check round of
    ``check_every`` PDHG iterations; there is no phase 1 (``phase`` is
    constant 2, so stage-1 no-ops) and no column compaction.  PDHG's
    iteration-count spread is far wider than simplex pivots' — easy LPs
    converge in a few hundred iterations while conditioning stragglers run
    thousands — which is exactly the distribution the power-of-two bucket
    ladder was built to exploit."""

    pad_multiple = 1

    def __init__(self, m: int, n: int, tol: float, dtype,
                 check_every: int = CHECK_EVERY):
        self.m, self.n = m, n
        self.tol = float(tol)
        self.dtype = dtype
        self.check_every = int(check_every)

    def init(self, A, b, c, ub=None, warm: WarmStart | None = None,
             telemetry: bool = False) -> PdhgState:
        state = init_pdhg_state(A, b, c, ub)
        if telemetry:
            state = state._replace(tel=init_telemetry(state.x.shape[0]))
        if warm is not None and warm.x is not None and warm.y is not None:
            dtype = state.x.dtype
            wx = jnp.asarray(np.nan_to_num(np.asarray(warm.x, np.float64),
                                           posinf=0.0, neginf=0.0), dtype)
            wy = jnp.asarray(np.nan_to_num(np.asarray(warm.y, np.float64),
                                           posinf=0.0, neginf=0.0), dtype)
            womega = (None if warm.omega is None
                      else jnp.asarray(np.asarray(warm.omega), dtype))
            state = inject_pdhg_warm(state, wx, wy, womega)
        return state

    def run_phase1(self, state, steps):
        return state, 0          # no phase 1: stage 1 is a no-op

    def segment_bodies(self) -> dict:
        """The unjitted segment runner of stage 2, the only stage
        (`compaction.JaxBackend.segment_bodies`)."""
        return {"p2": functools.partial(segment_pdhg, tol=self.tol,
                                        check_every=self.check_every)}

    def run_phase2(self, state, steps):
        state, it = _segment_pdhg_jit(state, jnp.int32(steps), tol=self.tol,
                                      check_every=self.check_every)
        return state, int(it)

    def compact_columns(self, state: PdhgState) -> PdhgState:
        return state             # nothing to drop: data is already minimal

    def limit_phase1(self, state: PdhgState) -> PdhgState:
        return state             # no LP is ever in phase 1

    def deactivate(self, state: PdhgState, valid) -> PdhgState:
        valid = jnp.asarray(np.asarray(valid).reshape(-1))
        status = jnp.where(valid, state.status, ITERATION_LIMIT)
        return state._replace(status=status.astype(state.status.dtype))

    def take(self, state: PdhgState, idx) -> PdhgState:
        idx = jnp.asarray(idx)
        return jax.tree_util.tree_map(lambda a: a[idx], state)

    def status_host(self, state) -> np.ndarray:
        return np.asarray(state.status).reshape(-1)

    def phase_host(self, state) -> np.ndarray:
        return np.asarray(state.phase).reshape(-1)

    def extract(self, state: PdhgState, stage: str):
        out = _extract_pdhg_jit(state)
        return tuple(np.asarray(o) for o in out)

    def elements_per_step(self, stage: str) -> int:
        return self.check_every * pdhg_elements(self.m, self.n)


def solve_batched_pdhg_compacted(
        batch: LPBatch, *, dtype=jnp.float32, tol: Optional[float] = None,
        feas_tol: Optional[float] = None, max_iters: Optional[int] = None,
        segment_k: Optional[int] = None,
        compact_threshold: Optional[float] = None,
        check_every: int = CHECK_EVERY, pricing: str = "dantzig",
        stats_out: Optional[List] = None,
        presolve: bool = True, scale: Optional[bool] = None,
        warm: WarmStart | None = None, runner=None,
        telemetry: bool = False, tracer=None, mesh=None) -> LPResult:
    """Restarted PDHG under the active-set compaction scheduler: K-round
    segments, power-of-two bucket gathers of still-running LPs (problem
    data, iterates, averages and restart state gathered alongside).  Same
    contract as ``solve_batched_compacted`` (``mesh`` included).

    Reproducibility: gathers never change an LP's own iterates, but the
    segment runner is a *different compilation* of the same rounds than
    the monolithic while_loop — XLA fuses the f32 matvecs differently, so
    the restart trajectories (and the tol-satisfying points they stop at)
    drift to ~tol: statuses agree, objectives to ~1e-3 relative (cf. the
    revised backend's batch-decomposition note).

    ``runner`` swaps the segment executor: a factory called as
    ``runner(m, n, tol, dtype, check_every=...)`` returning a
    PdhgBackend-compatible object (kernels.ops.PdhgPallasBackend runs the
    segments as Pallas tile kernels). A runner may return a batch-padded
    state from ``init`` (tile multiples); the padding slots are marked
    terminal here so the scheduler never counts them as active."""
    from .compaction import (CompactionConfig, init_orig, on_mesh,
                             resolve_compact_threshold, run_schedule)

    _check_pdhg_pricing(pricing)
    del feas_tol
    batch, rec = ensure_canonical(batch, presolve=presolve, scale=scale,
                                  tracer=tracer)
    m, n = batch.m, batch.n
    if max_iters is None:
        max_iters = default_pdhg_max_iters(m, n)
    if tol is None:
        tol = 1e-5 if dtype == jnp.float32 else 1e-8
    rounds = -(-int(max_iters) // int(check_every))
    if segment_k is None:
        # a handful of compaction checkpoints across the expected solve,
        # mirroring auto_segment_k's ~1/64-of-cap heuristic in round units
        segment_k = max(4, rounds // 64)
    backend, padded = on_mesh(
        PdhgBackend(m, n, tol, dtype, check_every=check_every)
        if runner is None
        else runner(m, n, tol, dtype, check_every=check_every), batch, mesh)
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
        compact_threshold=resolve_compact_threshold(compact_threshold,
                                                    int(segment_k)),
        pad_multiple=backend.pad_multiple)
    return finish_result(rec, run_schedule(backend, state, orig, B, n,
                                           max_iters=rounds, config=cfg,
                                           stats_out=stats_out,
                                           tracer=tracer),
                         tracer=tracer)
