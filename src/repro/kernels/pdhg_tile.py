"""Pallas TPU kernel: whole-solve batched restarted PDHG over VMEM tiles.

The simplex tile kernel (kernels/simplex_tile.py) keeps a *mutating*
tableau resident in VMEM; the PDHG tile keeps the **immutable** problem
data resident and mutates only the small iterate vectors — the same
VMEM-residency upgrade over HBM-looping XLA, applied to the first-order
engine (core/pdhg.py):

* one grid step per tile of ``tile_b`` LPs; the tile's (tile_b, M, N)
  constraint block, both iterate pairs, the running averages and the
  restart bookkeeping all live in VMEM for the entire solve — zero HBM
  traffic between iterations, solutions + certificates out at the end.
* the two matvecs per iteration are broadcast-FMA + axis reductions
  (``sum(A * x[:, None, :], axis=2)`` / ``sum(A * y[:, :, None], axis=1)``)
  — the VPU formulation the simplex tiles already use; no gathers, no
  scatters, no pivoting.
* the whole restart machinery — candidate selection between current and
  average iterate, sufficient/necessary decay tests, adaptive primal
  weight — is fused into the same loop: "fused matvec + prox + restart
  check in VMEM".
* per-tile early exit: the outer while_loop stops the moment every LP in
  the tile is terminal, so a tile of easy LPs hands its time to later
  tiles (grid steps execute sequentially per core).

Setup (Ruiz equilibration + power-iteration step sizes, core/pdhg.py) runs
as ordinary jitted JAX on the host side of the pallas_call — it is a
once-per-solve cost and keeping it outside the kernel lets the kernel
treat (A, b, c, scales, steps) as pure inputs.

Layout: A is (tile_b, M, N) with M = round8(m), N = round128(n); length-n
vectors ride as (tile_b, N) lane rows, length-m vectors as (tile_b, M)
rows (same convention as the simplex tile's ``basis``).  Upper bounds are
one more (tile_b, N) lane row (scaled, +inf on free and padded lanes):
the prox clips to [0, ub], bounded columns move their reduced cost into
the dual objective, and the Farkas rays get the bounded-column
relaxation/projection — all mirroring core/pdhg.py exactly.  Zero padding
is inert by construction: padded rows/columns have A = 0, b = 0, c = 0
and unit scales, so iterates, residuals and Farkas certificates never see
them; padded batch slots are all-zero LPs that converge on their first
check.
"""
from __future__ import annotations

import functools
from typing import Any, NamedTuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core.lp import INFEASIBLE, ITERATION_LIMIT, OPTIMAL, UNBOUNDED
from repro.obs.telemetry import (F32_LANE, F32_ROW_WIDTH, INT_LANE,
                                 INT_ROW_WIDTH, lane_add, lane_set,
                                 rows_to_tel, tel_to_rows)
from repro.core.pdhg import (
    CERT_TOL,
    CHECK_EVERY,
    OMEGA_MAX,
    OMEGA_MIN,
    OMEGA_SMOOTHING,
    RAY_MIN_NORM,
    RESTART_NECESSARY,
    RESTART_SUFFICIENT,
    init_pdhg_state,
)
from .tiling import VMEM_LIMIT_BYTES, compiler_params, pick_tile, round_up

_RUNNING = -1


def pdhg_dims(m: int, n: int):
    """(M, N) of the padded tile: rows to a sublane multiple, the minor
    (lane) axis to 128."""
    return round_up(m, 8), round_up(n, 128)


def pick_pdhg_tile_b(m: int, n: int, vmem_budget: int = VMEM_LIMIT_BYTES,
                     dtype_size: int = 4) -> int:
    """Tile batch so the working set fits VMEM: the double-buffered (M, N)
    data block plus ~4 live full-block matvec temporaries, and ~24 length-N
    and ~24 length-M iterate/state rows in and out (each lane-padded to
    128)."""
    M, N = pdhg_dims(m, n)
    block = M * N * dtype_size
    per_lp = 6 * block + 4 * (24 * N + 24 * max(M, 128)) * dtype_size
    return pick_tile(per_lp, block, vmem_budget)


def _mv(A, x):
    """(tile_b, M, N) @ (tile_b, N) -> (tile_b, M) as broadcast-FMA + lane
    reduction (VPU formulation; padded columns contribute zero)."""
    return jnp.sum(A * x[:, None, :], axis=2)


def _mtv(A, y):
    """(tile_b, M, N)^T @ (tile_b, M) -> (tile_b, N) via the sublane axis."""
    return jnp.sum(A * y[:, :, None], axis=1)


def _make_pdhg_round(A, b, c, r, s, eta, binf, cinf, ub, *, tol: float,
                     check_every: int, telemetry: bool = False):
    """Build the fused check-round closure both PDHG kernels run: one round
    = ``check_every`` prox iterations + the in-VMEM convergence / restart /
    certificate check, mirroring core.pdhg.pdhg_round exactly (same
    constants, same candidate rule, same adaptive primal weight).

    Carry layout (shared by the whole-solve and segment kernels):
    ``(it, x, y, xs, ys, xr, yr, cnt, last, prev, om, status, iters)``.
    With ``telemetry=True`` two packed counter rows — (tile_b,
    INT_ROW_WIDTH) int32 and (tile_b, F32_ROW_WIDTH) float32 — are appended
    to the carry and updated per round (iterations, adopted restarts, the
    KKT triple at the adopted candidate, the primal weight); the disabled
    closure is byte-identical to the pre-telemetry one."""
    dtype = A.dtype
    fin = jnp.isfinite(ub)
    ubm = jnp.where(fin, ub, 0.0)

    def kkt_parts(x, y):
        ax = _mv(A, x)
        aty = _mtv(A, y)
        rp = jnp.max(jnp.maximum(ax - b, 0.0) / r, axis=1, keepdims=True) \
            / (1.0 + binf)
        # bounded columns: positive reduced cost is absorbed by the bound
        # dual w_j = (c - A^T y)_j+ (core.pdhg.kkt_residuals)
        zc = jnp.maximum(c - aty, 0.0)
        rd = jnp.max(jnp.where(fin, 0.0, zc) / s, axis=1, keepdims=True) \
            / (1.0 + cinf)
        pobj = jnp.sum(c * x, axis=1, keepdims=True)
        dobj = jnp.sum(b * y, axis=1, keepdims=True) \
            + jnp.sum(ubm * zc, axis=1, keepdims=True)
        gap = jnp.abs(pobj - dobj) / (1.0 + jnp.abs(pobj) + jnp.abs(dobj))
        return rp, rd, gap

    def kkt(x, y):
        rp, rd, gap = kkt_parts(x, y)
        return jnp.maximum(jnp.maximum(rp, rd), gap)

    def body(carry):
        if telemetry:
            (it, x, y, xs, ys, xr, yr, cnt, last, prev, om, status,
             iters, ti, tf) = carry
        else:
            (it, x, y, xs, ys, xr, yr, cnt, last, prev, om, status,
             iters) = carry
            ti = tf = None
        active = status == _RUNNING          # (tile_b, 1)
        tau = eta / om
        sig = eta * om

        def step(_, st):
            x, y, xs, ys, cnt = st
            aty = _mtv(A, y)
            # prox of the [0, ub] indicator: clip (ub = +inf -> max)
            xn = jnp.clip(x + tau * (c - aty), 0.0, ub)
            ax2 = _mv(A, 2.0 * xn - x)
            yn = jnp.maximum(y + sig * (ax2 - b), 0.0)
            x = jnp.where(active, xn, x)
            y = jnp.where(active, yn, y)
            return (x, y, xs + jnp.where(active, x, 0.0),
                    ys + jnp.where(active, y, 0.0),
                    cnt + active.astype(dtype))

        x, y, xs, ys, cnt = jax.lax.fori_loop(
            0, check_every, step, (x, y, xs, ys, cnt))
        iters = jnp.where(active, iters + check_every, iters)

        cc = jnp.maximum(cnt, 1.0)
        xa, ya = xs / cc, ys / cc
        if telemetry:
            # keep the component triples so the adopted candidate's
            # residuals can be recorded without extra matvecs; selecting
            # precomputed parts equals recomputing at (xc, yc) exactly
            rp_c, rd_c, gap_c = kkt_parts(x, y)
            rp_a, rd_a, gap_a = kkt_parts(xa, ya)
            res_cur = jnp.maximum(jnp.maximum(rp_c, rd_c), gap_c)
            res_avg = jnp.maximum(jnp.maximum(rp_a, rd_a), gap_a)
        else:
            res_cur = kkt(x, y)
            res_avg = kkt(xa, ya)
        use_avg = res_avg < res_cur
        res = jnp.where(use_avg, res_avg, res_cur)
        xc = jnp.where(use_avg, xa, x)
        yc = jnp.where(use_avg, ya, y)

        converged = active & (res <= tol)

        # Farkas-ray classification (core.pdhg._ray_certificates, inlined)
        # on the PRE-adoption iterates — exactly the vectors pdhg_round
        # tests, so kernel and pure-JAX paths classify on the same round
        test = active & ~converged
        ray_scale = 1.0 + binf + cinf
        yinf = jnp.max(jnp.abs(y * r), axis=1, keepdims=True)
        yh = y / jnp.maximum(yinf, 1e-12)
        aty_s = _mtv(A, yh)
        aty_u = aty_s / s
        by_u = jnp.sum(b * yh, axis=1, keepdims=True)
        # bounded columns relax the dual ray at cost u_j (A^T yh)_j^-
        uw = jnp.sum(ubm * jnp.maximum(-aty_s, 0.0), axis=1, keepdims=True)
        infeas = test & (yinf > RAY_MIN_NORM) \
            & (jnp.min(jnp.where(fin, jnp.inf, aty_u), axis=1,
                       keepdims=True)
               >= -CERT_TOL * ray_scale) \
            & (by_u + uw <= -CERT_TOL * ray_scale)
        # primal ray projected onto unbounded columns (bounded coordinates
        # cannot recede; an all-bounded LP has xinf == 0, never classified)
        xray = jnp.where(fin, 0.0, x)
        xinf = jnp.max(jnp.abs(xray * s), axis=1, keepdims=True)
        xh = xray / jnp.maximum(xinf, 1e-12)
        ax_u = _mv(A, xh) / r
        cx_u = jnp.sum(c * xh, axis=1, keepdims=True)
        unbounded = test & (xinf > RAY_MIN_NORM) \
            & (jnp.max(ax_u, axis=1, keepdims=True)
               <= CERT_TOL * ray_scale) \
            & (cx_u >= CERT_TOL * ray_scale)

        restart = (res <= RESTART_SUFFICIENT * last) \
            | ((res <= RESTART_NECESSARY * last) & (res > prev))
        restart = active & ~converged & restart
        adopt = converged | restart
        x = jnp.where(adopt, xc, x)
        y = jnp.where(adopt, yc, y)
        xs = jnp.where(restart, 0.0, xs)
        ys = jnp.where(restart, 0.0, ys)
        cnt = jnp.where(restart, 0.0, cnt)
        last = jnp.where(restart, res, last)
        prev = jnp.where(restart, jnp.inf, res)

        # adaptive primal weight (core/pdhg.py OMEGA_* constants)
        dx = jnp.sqrt(jnp.sum((xc - xr) ** 2, axis=1, keepdims=True))
        dy = jnp.sqrt(jnp.sum((yc - yr) ** 2, axis=1, keepdims=True))
        can = restart & (dx > 1e-10) & (dy > 1e-10)
        om_new = jnp.exp(OMEGA_SMOOTHING
                         * jnp.log(jnp.maximum(dy, 1e-12)
                                   / jnp.maximum(dx, 1e-12))
                         + (1.0 - OMEGA_SMOOTHING) * jnp.log(om))
        om = jnp.where(can, jnp.clip(om_new, OMEGA_MIN, OMEGA_MAX), om)
        xr = jnp.where(restart, xc, xr)
        yr = jnp.where(restart, yc, yr)

        status = jnp.where(converged, OPTIMAL, status)
        status = jnp.where(infeas, INFEASIBLE, status)
        status = jnp.where(unbounded, UNBOUNDED, status)
        if telemetry:
            # mirrors core.pdhg: iterations use the pre-round active mask,
            # restarts count adopted restarts, the KKT lanes hold the
            # adopted candidate's triple, omega the post-update weight
            ti = lane_add(ti, INT_LANE["phase2_iters"],
                          check_every * active.astype(jnp.int32))
            ti = lane_add(ti, INT_LANE["restarts"], restart)
            tf = lane_set(tf, F32_LANE["kkt_primal"],
                          jnp.where(use_avg, rp_a, rp_c))
            tf = lane_set(tf, F32_LANE["kkt_dual"],
                          jnp.where(use_avg, rd_a, rd_c))
            tf = lane_set(tf, F32_LANE["kkt_gap"],
                          jnp.where(use_avg, gap_a, gap_c))
            tf = lane_set(tf, F32_LANE["omega"], om)
            return (it + 1, x, y, xs, ys, xr, yr, cnt, last, prev, om,
                    status, iters, ti, tf)
        return (it + 1, x, y, xs, ys, xr, yr, cnt, last, prev, om, status,
                iters)

    return body


@functools.partial(
    jax.jit,
    static_argnames=("m", "n", "tile_b", "max_iters", "tol", "check_every",
                     "interpret"))
def pdhg_pallas(A, b, c, ub=None, *, m: int, n: int, tile_b: int,
                max_iters: int, tol: float, check_every: int = CHECK_EVERY,
                interpret: bool):
    """Solve the batch with the PDHG tile kernel in one launch: a cold
    tile state run for the whole round budget by the segment kernel below
    (the same fused round closure), then the shared extraction epilogue.
    Returns (x, obj, status, iters, y, z) for the original (unpadded)
    batch — the same 6-tuple contract as every solve body.  ``ub`` is an
    optional (B, n) array of upper bounds (+inf = free above)."""
    B = A.shape[0]
    # setup outside the kernel: equilibration + step sizes (jitted JAX)
    state = build_pdhg_tile_state(init_pdhg_state(A, b, c, ub), m=m, n=n,
                                  tile_b=tile_b)
    rounds = -(-int(max_iters) // int(check_every))
    state, _ = pdhg_segment_pallas(
        jnp.int32(rounds), state, m=m, n=n, tile_b=tile_b, tol=tol,
        check_every=check_every, interpret=interpret)
    x, obj, status, iters, y, z = _extract_pdhg_tile_jit(state, m=m, n=n)
    return x[:B], obj[:B], status[:B], iters[:B], y[:B], z[:B]


# ---------------------------------------------------------------------------
# Segment kernel: resumable rounds for the compaction scheduler
# ---------------------------------------------------------------------------

class PdhgTileState(NamedTuple):
    """Padded resumable PDHG state for the segment kernel; every leaf keeps
    the batch on axis 0 so the compaction scheduler's generic gathers apply
    unchanged — the tile-layout analogue of core.pdhg.PdhgState."""
    A: jax.Array       # (B, M, N) Ruiz-scaled data
    b: jax.Array       # (B, M)
    c: jax.Array       # (B, N)
    rsc: jax.Array     # (B, M) row scales
    csc: jax.Array     # (B, N) col scales
    eta: jax.Array     # (B, 1) base step
    binf: jax.Array    # (B, 1) unscaled ||b||_inf
    cinf: jax.Array    # (B, 1) unscaled ||c||_inf
    ub: jax.Array      # (B, N) scaled upper bounds (+inf free/padded)
    x: jax.Array       # (B, N) primal iterate
    y: jax.Array       # (B, M) dual iterate
    xs: jax.Array      # (B, N) running primal sum since last restart
    ys: jax.Array      # (B, M) running dual sum
    xr: jax.Array      # (B, N) last-restart anchor
    yr: jax.Array      # (B, M) last-restart anchor
    cnt: jax.Array     # (B, 1) iterations in the running average
    last: jax.Array    # (B, 1) KKT residual at the last restart
    prev: jax.Array    # (B, 1) candidate residual at the previous check
    omega: jax.Array   # (B, 1) primal weight
    phase: jax.Array   # (B, 1) int32 — constant 2 (scheduler stage-1 no-op)
    status: jax.Array  # (B, 1) int32
    iters: jax.Array   # (B, 1) int32
    tel: Any = None    # optional obs.telemetry.TelemetryState ((B,) lanes)


@functools.partial(jax.jit, static_argnames=("m", "n", "tile_b"))
def build_pdhg_tile_state(s0, *, m: int, n: int, tile_b: int
                          ) -> PdhgTileState:
    """Pad an engine ``PdhgState`` (cold or warm-injected) onto the tile
    layout.  Padding slots are all-zero LPs deactivated outright; padded
    lanes are inert (A = b = c = 0, unit scales, +inf bounds).  A telemetry
    pytree riding the engine state is zero-padded leaf-wise (padding slots
    never accumulate: they are deactivated before the first round)."""
    B = s0.A.shape[0]
    dtype = s0.A.dtype
    M, N = pdhg_dims(m, n)
    B_pad = round_up(B, tile_b)

    def pad(a, rows, fill=0.0):
        out = jnp.full((B_pad, rows), fill, dtype)
        return out.at[:B, :a.shape[1]].set(a)

    def pad1(a, fill=0.0):
        return pad(a.reshape(B, 1), 1, fill)

    tel = s0.tel
    if tel is not None:
        tel = jax.tree.map(
            lambda v: jnp.zeros((B_pad,), v.dtype).at[:B].set(v), tel)
    Ap = jnp.zeros((B_pad, M, N), dtype).at[:B, :m, :n].set(s0.A)
    return PdhgTileState(
        A=Ap, b=pad(s0.b, M), c=pad(s0.c, N), rsc=pad(s0.rsc, M, 1.0),
        csc=pad(s0.csc, N, 1.0), eta=pad(s0.eta, 1, 1.0),
        binf=pad1(s0.binf), cinf=pad1(s0.cinf), ub=pad(s0.ub, N, jnp.inf),
        x=pad(s0.x, N), y=pad(s0.y, M), xs=pad(s0.xs, N), ys=pad(s0.ys, M),
        xr=pad(s0.xr, N), yr=pad(s0.yr, M), cnt=pad1(s0.cnt),
        last=pad1(s0.last_res, jnp.inf), prev=pad1(s0.prev_res, jnp.inf),
        omega=pad(s0.omega, 1, 1.0),
        phase=jnp.full((B_pad, 1), 2, jnp.int32).at[:B, 0].set(s0.phase),
        status=jnp.full((B_pad, 1), ITERATION_LIMIT,
                        jnp.int32).at[:B, 0].set(s0.status),
        iters=jnp.zeros((B_pad, 1), jnp.int32).at[:B, 0].set(s0.iters),
        tel=tel)


def _pdhg_segment_kernel(steps_ref, A_ref, b_ref, c_ref, r_ref, s_ref,
                         eta_ref, binf_ref, cinf_ref, ub_ref,
                         x_ref, y_ref, xs_ref, ys_ref, xr_ref, yr_ref,
                         cnt_ref, last_ref, prev_ref, om_ref, status_ref,
                         iters_ref, *refs,
                         tol: float, check_every: int,
                         telemetry: bool = False):
    """Resumable segment: up to ``steps`` check rounds of the *same* fused
    round closure the whole-solve kernel runs, with the full iterate /
    average / restart state streamed in and out so the compaction
    scheduler's bucket gathers happen between kernel segments.

    With ``telemetry=True`` the packed int32/float32 counter rows ride the
    carry (extra inputs after ``iters``, extra outputs after ``it``); the
    disabled trace is byte-identical to the pre-telemetry kernel."""
    if telemetry:
        ti_ref, tf_ref = refs[:2]
        (x_out, y_out, xs_out, ys_out, xr_out, yr_out, cnt_out, last_out,
         prev_out, om_out, status_out, iters_out, it_out, ti_out,
         tf_out) = refs[2:]
    else:
        ti_ref = tf_ref = ti_out = tf_out = None
        (x_out, y_out, xs_out, ys_out, xr_out, yr_out, cnt_out, last_out,
         prev_out, om_out, status_out, iters_out, it_out) = refs
    steps = steps_ref[0, 0]
    A = A_ref[...]
    round_body = _make_pdhg_round(
        A, b_ref[...], c_ref[...], r_ref[...], s_ref[...], eta_ref[...],
        binf_ref[...], cinf_ref[...], ub_ref[...],
        tol=tol, check_every=check_every, telemetry=telemetry)

    def cond(carry):
        it = carry[0]
        status = carry[11]
        return jnp.any(status == _RUNNING) & (it < steps)

    init = (jnp.int32(0), x_ref[...], y_ref[...], xs_ref[...], ys_ref[...],
            xr_ref[...], yr_ref[...], cnt_ref[...], last_ref[...],
            prev_ref[...], om_ref[...], status_ref[...], iters_ref[...])
    if telemetry:
        init = init + (ti_ref[...], tf_ref[...])
    out = jax.lax.while_loop(cond, round_body, init)
    (it, x, y, xs, ys, xr, yr, cnt, last, prev, om, status,
     iters) = out[:13]

    x_out[...] = x
    y_out[...] = y
    xs_out[...] = xs
    ys_out[...] = ys
    xr_out[...] = xr
    yr_out[...] = yr
    cnt_out[...] = cnt
    last_out[...] = last
    prev_out[...] = prev
    om_out[...] = om
    status_out[...] = status
    iters_out[...] = iters
    it_out[...] = jnp.full(it_out.shape, it, jnp.int32)
    if telemetry:
        ti_out[...] = out[13]
        tf_out[...] = out[14]


@functools.partial(
    jax.jit,
    static_argnames=("m", "n", "tile_b", "tol", "check_every", "interpret"))
def pdhg_segment_pallas(steps, state: PdhgTileState, *, m: int, n: int,
                        tile_b: int, tol: float,
                        check_every: int = CHECK_EVERY,
                        interpret: bool):
    """Run up to ``steps`` check rounds per tile and return
    ``(new_state, executed_rounds)`` — the PDHG analogue of the simplex
    ``segment_pallas`` protocol (early exit per tile once every LP in it is
    terminal).  A telemetry pytree on ``state.tel`` is packed onto dense
    counter rows around the kernel (obs.telemetry.tel_to_rows) and carried
    through VMEM; ``state.tel is None`` traces the pre-telemetry program."""
    B, M, N = state.A.shape
    grid = (B // tile_b,)
    dtype = state.A.dtype
    telemetry = state.tel is not None
    vec = lambda i: (i, 0)  # noqa: E731
    kernel = functools.partial(_pdhg_segment_kernel, tol=float(tol),
                               check_every=int(check_every),
                               telemetry=telemetry)
    spec_n = pl.BlockSpec((tile_b, N), vec)
    spec_m = pl.BlockSpec((tile_b, M), vec)
    spec_1 = pl.BlockSpec((tile_b, 1), vec)
    in_specs = [
        pl.BlockSpec((1, 1), lambda i: (0, 0)),          # steps
        pl.BlockSpec((tile_b, M, N), lambda i: (i, 0, 0)),
        spec_m, spec_n, spec_m, spec_n,                  # b c rsc csc
        spec_1, spec_1, spec_1,                          # eta binf cinf
        spec_n,                                          # ub
        spec_n, spec_m, spec_n, spec_m, spec_n, spec_m,  # x y xs ys xr yr
        spec_1, spec_1, spec_1, spec_1, spec_1, spec_1,  # cnt..iters
    ]
    out_specs = [
        spec_n, spec_m, spec_n, spec_m, spec_n, spec_m,
        spec_1, spec_1, spec_1, spec_1, spec_1, spec_1,
        spec_1,                                          # executed
    ]
    out_shape = [
        jax.ShapeDtypeStruct((B, N), dtype),
        jax.ShapeDtypeStruct((B, M), dtype),
        jax.ShapeDtypeStruct((B, N), dtype),
        jax.ShapeDtypeStruct((B, M), dtype),
        jax.ShapeDtypeStruct((B, N), dtype),
        jax.ShapeDtypeStruct((B, M), dtype),
        jax.ShapeDtypeStruct((B, 1), dtype),
        jax.ShapeDtypeStruct((B, 1), dtype),
        jax.ShapeDtypeStruct((B, 1), dtype),
        jax.ShapeDtypeStruct((B, 1), dtype),
        jax.ShapeDtypeStruct((B, 1), jnp.int32),
        jax.ShapeDtypeStruct((B, 1), jnp.int32),
        jax.ShapeDtypeStruct((B, 1), jnp.int32),
    ]
    operands = (jnp.full((1, 1), steps, jnp.int32), state.A, state.b,
                state.c, state.rsc, state.csc, state.eta, state.binf,
                state.cinf, state.ub, state.x, state.y, state.xs, state.ys,
                state.xr, state.yr, state.cnt, state.last, state.prev,
                state.omega, state.status, state.iters)
    if telemetry:
        ti, tf = tel_to_rows(state.tel)
        in_specs += [pl.BlockSpec((tile_b, INT_ROW_WIDTH), vec),
                     pl.BlockSpec((tile_b, F32_ROW_WIDTH), vec)]
        out_specs += [pl.BlockSpec((tile_b, INT_ROW_WIDTH), vec),
                      pl.BlockSpec((tile_b, F32_ROW_WIDTH), vec)]
        out_shape += [jax.ShapeDtypeStruct((B, INT_ROW_WIDTH), jnp.int32),
                      jax.ShapeDtypeStruct((B, F32_ROW_WIDTH), jnp.float32)]
        operands = operands + (ti, tf)
    outs = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        interpret=interpret,
        compiler_params=compiler_params(),
    )(*operands)
    (x, y, xs, ys, xr, yr, cnt, last, prev, om, status, iters,
     it) = outs[:13]
    tel = rows_to_tel(outs[13], outs[14]) if telemetry else None
    new = state._replace(x=x, y=y, xs=xs, ys=ys, xr=xr, yr=yr, cnt=cnt,
                         last=last, prev=prev, omega=om, status=status,
                         iters=iters, tel=tel)
    return new, it


@functools.partial(jax.jit, static_argnames=("m", "n"))
def _extract_pdhg_tile_jit(state: PdhgTileState, *, m: int, n: int):
    """(x, obj, status, iters, y, z) in unscaled coordinates off the padded
    iterates — the same epilogue as the whole-solve kernel."""
    status = jnp.where(state.status[:, 0] == _RUNNING, ITERATION_LIMIT,
                       state.status[:, 0])
    opt = (status == OPTIMAL)[:, None]
    obj = jnp.sum(state.c * state.x, axis=1)
    z = (state.c - _mtv(state.A, state.y)) / state.csc
    x = state.x * state.csc
    y = state.y * state.rsc
    return (x[:, :n], jnp.where(opt[:, 0], obj, jnp.nan),
            status.astype(jnp.int8), state.iters[:, 0],
            jnp.where(opt, y, jnp.nan)[:, :m],
            jnp.where(opt, z, jnp.nan)[:, :n])
