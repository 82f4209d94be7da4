"""Jit'd public wrappers for the Pallas kernels.

``solve_batched_pallas`` is a drop-in for core.simplex.solve_batched_jax
(same LPBatch -> LPResult contract) and is what core.batching dispatches to
when ``solver=`` is pointed here.  Interpret mode is not an option: it
follows the platform (`default_interpret`) — on a TPU every kernel is
compiled by Mosaic and a kernel the compiler refuses raises; anywhere else
the kernel body runs under the Pallas interpreter for validation.

``compaction=True`` routes the solve through the active-set compaction
scheduler (core/compaction.py) with Pallas segment kernels: the batch is
solved in K-pivot segments and surviving LPs are gathered into
power-of-two buckets (multiples of ``tile_b``) as others terminate — the
paper's per-block early exit rebuilt on static shapes. Defaults preserve the
one-shot whole-solve kernel semantics.

``pricing=`` selects the entering-column rule (core/pricing.py:
dantzig | steepest_edge | devex) on both the whole-solve and segment paths.
``pricing="partial"`` degrades to dantzig here with a warning: the tile
kernel keeps the full cost row resident in VMEM, so block-restricted pricing
saves nothing — the rule exists for the revised backend's pricing matvec.

``backend=`` dispatch follows the core/lp.py registry; every registered
backend has a real Pallas surface. ``backend="pdhg"`` (core/pdhg.py)
runs the first-order tile kernel (kernels/pdhg_tile.py — fused matvec +
prox + restart check in VMEM) for the whole round budget; with
``compaction=True``
the scheduler's segments run the resumable PDHG *segment* kernel, so
bucket gathers happen between kernel launches instead of abandoning
Pallas. ``backend="revised"`` (core/revised.py) runs the revised-simplex
tile kernel (kernels/revised_tile.py — BTRAN/FTRAN against a
VMEM-resident basis inverse + eta file, refactorization at segment
boundaries), monolithic or under the scheduler with refactor-on-gather.
A backend whose registry entry reports ``supports_pallas=False`` is
refused with a ValueError.

``warm=`` accepts the backend-uniform `WarmStart` carrier: the revised
kernel injects a parent basis (phase-1 skip / repair, exactly the
engine's `inject_revised_warm`), the pdhg paths inject iterates +
primal weight; the tableau tile kernel has no injection surface and
warns once before starting cold.

Like every solve_* entry point, a ``GeneralLPBatch`` (core/forms.py) is
accepted directly: canonicalize on ingestion (``presolve=``/``scale=``),
solve the canonical form in the kernel, recover into original coordinates.
"""
from __future__ import annotations

import functools
import warnings
from typing import List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.forms import ensure_canonical, finish_result, prepare_warm
from repro.core.lp import (ITERATION_LIMIT, OPTIMAL, LPBatch, LPResult,
                           WarmStart, backend_spec, default_max_iters)
from repro.core.compaction import (
    CompactionConfig, CompactionState, JaxBackend, SegmentStat,
    _take_jit, auto_segment_k, init_orig, resolve_compact_threshold,
    run_schedule,
)
from repro.obs.telemetry import init_telemetry, rows_to_tel, tel_to_rows
from repro.obs.trace import span
from repro.core.pdhg import PdhgBackend
from repro.core.pricing import canonicalize_rule
from repro.core.revised import RevisedBackend, canonicalize_revised_rule
from repro.core.simplex import _RUNNING, scatter_solution
from .simplex_tile import (
    _compact_tile, _compact_tile_lane, _compact_tile_weights,
    _init_tile_weights, build_padded_tableau, pick_tile_b, segment_pallas,
    simplex_pallas,
)
from .pdhg_tile import (
    _extract_pdhg_tile_jit, build_pdhg_tile_state, pdhg_segment_pallas,
    pick_pdhg_tile_b,
)
from .revised_tile import (
    _extract_revised_tile_jit, build_revised_tile_state, pick_revised_tile_b,
    refactor_tile, revised_pallas, revised_segment_pallas,
)
from .hyperbox_kernel import hyperbox_pallas


def default_interpret() -> bool:
    """The one interpret-mode rule of every kernel entry point: compiled on
    a TPU, interpreted anywhere else (the CPU test and rehearsal runs)."""
    return jax.default_backend() != "tpu"


# Degradation warnings fire once per process, not once per call:
# batched sweeps dispatch thousands of solves and a per-call warning is pure
# spam.  Keyed so distinct conditions still each get their one warning.
_WARNED: set = set()


def _warn_once(key: str, message: str) -> None:
    if key not in _WARNED:
        _WARNED.add(key)
        warnings.warn(message, stacklevel=3)


@functools.partial(jax.jit, static_argnames=("m", "n"))
def _compact_padded_jit(T, *, m, n):
    return _compact_tile(T, m=m, n=n)


@functools.partial(jax.jit, static_argnames=("m", "n"))
def _compact_padded_weights_jit(w, *, m, n):
    return _compact_tile_weights(w, m=m, n=n)


@functools.partial(jax.jit, static_argnames=("fill", "m", "n"))
def _compact_padded_lane_jit(v, *, fill, m, n):
    return _compact_tile_lane(v, fill, m=m, n=n)


@functools.partial(jax.jit, static_argnames=("m", "rule"))
def _init_padded_weights_jit(T, *, m, rule):
    row_ids = jax.lax.broadcasted_iota(jnp.int32, T.shape[:2], 1)
    return _init_tile_weights(T, row_ids, m=m, rule=rule)


@functools.partial(jax.jit, static_argnames=("m", "n"))
def _extract_padded_jit(T, basis, status, iters, flip, ub, *, m, n):
    C = T.shape[2]
    rows = T.shape[1]
    rhs = T[:, :, C - 1]
    x = scatter_solution(rhs, basis[:, :rows], n)
    # complemented structural lanes store ub - x; nonbasic-at-upper reads ub
    flip_x = flip[:, :n] != 0
    x = jnp.where(flip_x, ub[:, :n] - x, x)
    obj = -T[:, m, C - 1]
    # dual certificate off the padded tableau (structural + slack columns
    # keep their unpadded positions; see core.simplex.extract_duals)
    y = -T[:, m, n:n + m]
    z = jnp.where(flip_x, -T[:, m, :n], T[:, m, :n])
    status = jnp.where(status == _RUNNING, ITERATION_LIMIT, status)
    obj = jnp.where(status == OPTIMAL, obj, jnp.nan)
    opt = (status == OPTIMAL)[:, None]
    return (x, obj, status.astype(jnp.int8), iters,
            jnp.where(opt, y, jnp.nan), jnp.where(opt, z, jnp.nan))


class PallasBackend(JaxBackend):
    """Compaction-scheduler backend running Pallas segment kernels on the
    lane-padded tile layout (RHS in the last padded column). Bucket sizes
    are multiples of ``tile_b`` so every segment is a whole grid of tiles;
    executed-work accounting stays in logical (unpadded) tableau elements so
    numbers are comparable across backends."""

    def __init__(self, m, n, tol, feas_tol, tile_b, dtype=jnp.float32,
                 pricing="dantzig"):
        super().__init__(m, n, tol, feas_tol, dtype, pricing=pricing)
        self.tile_b = int(tile_b)
        self.interpret = default_interpret()
        self.pad_multiple = self.tile_b

    def init(self, A, b, c, ub=None, telemetry: bool = False
             ) -> CompactionState:
        T, basis, phase, thr, ub_lane, _, _ = build_padded_tableau(
            A, b, c, self.tile_b, feas_tol=self.feas_tol, ub=ub)
        B_pad = T.shape[0]
        # dantzig never reads weights: a (B, 1) stub keeps the segment
        # kernels from streaming a dead (B, C) lane row through HBM
        w = (jnp.ones((B_pad, 1), T.dtype) if self.rule in ("dantzig", "partial")
             else _init_padded_weights_jit(T, m=self.m, rule=self.rule))
        # flip parity and bound lane rows ride the state so bucket gathers
        # keep them aligned with their tableaux (ub is kernel-read-only)
        return CompactionState(
            T=T, basis=basis, phase=phase,
            status=jnp.full((B_pad, 1), _RUNNING, jnp.int32),
            iters=jnp.zeros((B_pad, 1), jnp.int32), w=w,
            flip=jnp.zeros((B_pad, T.shape[2]), jnp.int32), ub=ub_lane,
            thr=thr, tel=init_telemetry(B_pad) if telemetry else None)

    def _run(self, state: CompactionState, steps: int, stage: str):
        # counters cross the kernel boundary as one packed int32 row; the
        # f32 lanes are not touched by the tableau kernel and pass through
        rows = None if state.tel is None else tel_to_rows(state.tel)
        outs = segment_pallas(
            jnp.int32(steps), state.T, state.basis, state.w, state.flip,
            state.ub, state.phase, state.thr, state.status, state.iters,
            None if rows is None else rows[0],
            stage=stage, m=self.m, n=self.n, tile_b=self.tile_b,
            tol=self.tol, interpret=self.interpret, pricing=self.rule)
        T, basis, w, flip, phase, status, iters, it = outs[:8]
        tel = state.tel if rows is None else rows_to_tel(outs[8], rows[1])
        new = CompactionState(T=T, basis=basis, phase=phase, status=status,
                              iters=iters, w=w, flip=flip, ub=state.ub,
                              thr=state.thr, tel=tel)
        return new, int(np.max(np.asarray(it)))

    def run_phase1(self, state, steps):
        return self._run(state, steps, "p1")

    def run_phase2(self, state, steps):
        return self._run(state, steps, "p2")

    def compact_columns(self, state: CompactionState) -> CompactionState:
        w = (state.w if self.rule in ("dantzig", "partial")
             else _compact_padded_weights_jit(state.w, m=self.m, n=self.n))
        return state._replace(
            T=_compact_padded_jit(state.T, m=self.m, n=self.n), w=w,
            flip=_compact_padded_lane_jit(state.flip, fill=0, m=self.m,
                                          n=self.n),
            ub=_compact_padded_lane_jit(state.ub, fill=float("inf"),
                                        m=self.m, n=self.n))

    def extract(self, state: CompactionState, stage: str):
        return tuple(np.asarray(o) for o in _extract_padded_jit(
            state.T, state.basis, state.status.reshape(-1),
            state.iters.reshape(-1), state.flip, state.ub,
            m=self.m, n=self.n))


class RevisedPallasBackend(RevisedBackend):
    """Compaction-scheduler backend running the revised-simplex tile kernel
    (kernels/revised_tile.py) on the padded tile layout. Bucket sizes are
    multiples of ``tile_b`` so every segment is a whole grid of tiles; the
    host refactorizes the basis inverse at every segment boundary and after
    every bucket gather, so each kernel launch starts from an empty eta
    file. Work accounting (`elements_per_step`) is inherited from the
    pure-JAX revised backend — numbers stay comparable across executors."""

    def __init__(self, m, n, tol, feas_tol, tile_b, dtype=jnp.float32,
                 pricing="dantzig", refactor_period=None):
        super().__init__(m, n, tol, feas_tol, dtype, pricing=pricing,
                         refactor_period=refactor_period)
        self.tile_b = int(tile_b)
        self.interpret = default_interpret()
        self.pad_multiple = self.tile_b

    def init(self, A, b, c, ub=None, warm: WarmStart | None = None,
             telemetry: bool = False):
        wb = wu = None
        if warm is not None and warm.basis is not None:
            wb = jnp.asarray(np.asarray(warm.basis), jnp.int32)
            if warm.at_upper is not None:
                wu = jnp.asarray(np.asarray(warm.at_upper), bool)
        return build_revised_tile_state(
            A, b, c, ub, m=self.m, n=self.n, tile_b=self.tile_b,
            feas_tol=self.feas_tol, warm_basis=wb, warm_at_upper=wu,
            telemetry=telemetry)

    def _run(self, state, steps, stage):
        rows = None if state.tel is None else tel_to_rows(state.tel)
        outs = revised_segment_pallas(
            jnp.int32(steps), state.Abar, state.cvec, state.ub, state.thr,
            state.Binv, state.xB, state.basis, state.onub, state.phase,
            state.status, state.iters,
            None if rows is None else rows[0],
            stage=stage, m=self.m, n=self.n,
            tile_b=self.tile_b, tol=self.tol, K=self.refactor_period,
            interpret=self.interpret, pricing=self.rule)
        xB, basis, onub, phase, status, iters, it = outs[:7]
        tel = state.tel if rows is None else rows_to_tel(outs[7], rows[1])
        new = state._replace(xB=xB, basis=basis, onub=onub, phase=phase,
                             status=status, iters=iters, tel=tel)
        # the boundary refactor also counts refactorizations on the
        # telemetry trace (the kernel's eta file never crosses a segment)
        return (refactor_tile(new, m=self.m, n=self.n),
                int(np.max(np.asarray(it))))

    def run_phase1(self, state, steps):
        return self._run(state, steps, "p1")

    def run_phase2(self, state, steps):
        return self._run(state, steps, "p2")

    def take(self, state, idx):
        # generic leaf gather (RevisedTileState, not RevisedState, so skip
        # RevisedBackend's engine-state refactor), then refactor-on-compact
        gathered = _take_jit(state, jnp.asarray(idx))
        return refactor_tile(gathered, m=self.m, n=self.n)

    def extract(self, state, stage: str):
        return tuple(np.asarray(o) for o in _extract_revised_tile_jit(
            state, m=self.m, n=self.n)[:6])


class PdhgPallasBackend(PdhgBackend):
    """Compaction-scheduler backend running the resumable PDHG segment
    kernel (kernels/pdhg_tile.py). Same scheduling semantics as
    core.pdhg.PdhgBackend — one scheduler "step" is one check round of
    ``check_every`` iterations — with the rounds executed inside
    ``pallas_call`` on the padded tile layout, so iterates, averages and
    restart bookkeeping stay in VMEM between the scheduler's gathers."""

    def __init__(self, m, n, tol, dtype, check_every=None, *,
                 tile_b=None):
        from repro.core.pdhg import CHECK_EVERY
        super().__init__(m, n, tol, dtype,
                         check_every=(CHECK_EVERY if check_every is None
                                      else check_every))
        if tile_b is None:
            tile_b = pick_pdhg_tile_b(m, n)
        self.tile_b = int(tile_b)
        self.interpret = default_interpret()
        self.pad_multiple = self.tile_b

    def init(self, A, b, c, ub=None, warm: WarmStart | None = None,
             telemetry: bool = False):
        s0 = super().init(A, b, c, ub, warm=warm, telemetry=telemetry)
        return build_pdhg_tile_state(s0, m=self.m, n=self.n,
                                     tile_b=self.tile_b)

    def run_phase2(self, state, steps):
        state, it = pdhg_segment_pallas(
            jnp.int32(steps), state, m=self.m, n=self.n,
            tile_b=self.tile_b, tol=self.tol,
            check_every=self.check_every, interpret=self.interpret)
        return state, int(np.max(np.asarray(it)))

    def deactivate(self, state, valid):
        # tile status is (B, 1): a (B,) mask would broadcast to (B, B)
        valid = jnp.asarray(np.asarray(valid).reshape(-1, 1))
        status = jnp.where(valid, state.status, ITERATION_LIMIT)
        return state._replace(status=status.astype(state.status.dtype))

    def extract(self, state, stage: str):
        return tuple(np.asarray(o) for o in _extract_pdhg_tile_jit(
            state, m=self.m, n=self.n))


def solve_batched_pallas(batch: LPBatch, *, dtype=jnp.float32,
                         tile_b: Optional[int] = None,
                         max_iters: Optional[int] = None,
                         tol: Optional[float] = None,
                         feas_tol: float = 1e-5,
                         compaction: bool = False,
                         segment_k: Optional[int] = None,
                         compact_threshold: Optional[float] = None,
                         pricing: str = "dantzig",
                         backend: str = "tableau",
                         refactor_period: Optional[int] = None,
                         stats_out: Optional[List[SegmentStat]] = None,
                         presolve: bool = True,
                         scale: Optional[bool] = None,
                         warm: Optional[WarmStart] = None,
                         telemetry: bool = False,
                         tracer=None) -> LPResult:
    batch, rec = ensure_canonical(batch, presolve=presolve, scale=scale,
                                  tracer=tracer)
    m, n = batch.m, batch.n
    pricing = canonicalize_rule(pricing)
    warm = prepare_warm(warm, rec, batch)
    if telemetry and not compaction:
        # the whole-solve tile kernels have no counter plane: the resumable
        # segment kernels are where the packed rows ride (ISSUE 10)
        _warn_once(
            "pallas-whole-telemetry",
            "solve_batched_pallas(telemetry=True) requires compaction=True "
            "(counters ride the resumable segment kernels); the whole-solve "
            "kernel path returns stats=None")
        telemetry = False
    if not backend_spec(backend).supports_pallas:
        raise ValueError(f"solve_batched_pallas(backend={backend!r}): the "
                         f"registry reports no Pallas {backend} kernel")
    interpret = default_interpret()
    if backend == "pdhg":
        from repro.core.pdhg import _check_pdhg_pricing
        _check_pdhg_pricing(pricing)
        if compaction:
            # the scheduler's segments run the resumable PDHG segment
            # kernel; bucket gathers happen between kernel launches
            from repro.core.pdhg import solve_batched_pdhg_compacted
            runner = functools.partial(PdhgPallasBackend, tile_b=tile_b)
            return finish_result(rec, solve_batched_pdhg_compacted(
                batch, dtype=dtype, tol=tol, max_iters=max_iters,
                segment_k=segment_k, compact_threshold=compact_threshold,
                stats_out=stats_out, warm=warm, runner=runner,
                telemetry=telemetry, tracer=tracer), tracer=tracer)
        from repro.core.pdhg import default_pdhg_max_iters
        from .pdhg_tile import pdhg_pallas
        if warm is not None:
            _warn_once(
                "pdhg-whole-warm",
                "solve_batched_pallas(backend='pdhg', warm=...): the "
                "whole-solve tile kernel starts cold; use compaction=True "
                "for warm iterate injection through the segment kernel")
        if tol is None:
            tol = 1e-5 if dtype == jnp.float32 else 1e-8
        if max_iters is None:
            max_iters = default_pdhg_max_iters(m, n)
        if tile_b is None:
            tile_b = pick_pdhg_tile_b(m, n)
        x, obj, status, iters, y, z = pdhg_pallas(
            jnp.asarray(batch.A, dtype), jnp.asarray(batch.b, dtype),
            jnp.asarray(batch.c, dtype),
            jnp.asarray(batch.upper_bounds(), dtype),
            m=m, n=n, tile_b=int(tile_b),
            max_iters=int(max_iters), tol=float(tol), interpret=interpret)
        return finish_result(rec, LPResult(
            x=np.asarray(x), objective=np.asarray(obj),
            status=np.asarray(status), iterations=np.asarray(iters),
            y=np.asarray(y), z=np.asarray(z)))
    if backend == "revised":
        rule = canonicalize_revised_rule(pricing)
        if tol is None:
            tol = 1e-6 if dtype == jnp.float32 else 1e-9
        if max_iters is None:
            max_iters = default_max_iters(m, n)
        if tile_b is None:
            tile_b = pick_revised_tile_b(m, n,
                                         refactor_period=refactor_period)
        A = jnp.asarray(batch.A, dtype)
        b = jnp.asarray(batch.b, dtype)
        c = jnp.asarray(batch.c, dtype)
        ub = jnp.asarray(batch.upper_bounds(), dtype)
        if compaction:
            if segment_k is None:
                segment_k = auto_segment_k(m, n)
            runner = RevisedPallasBackend(
                m, n, tol, feas_tol, tile_b, dtype=dtype, pricing=rule,
                refactor_period=refactor_period)
            B = batch.batch
            with span("lp.dispatch", tracer, backend="revised-pallas", B=B,
                      m=m, n=n):
                state = runner.init(A, b, c, ub=ub, warm=warm,
                                    telemetry=telemetry)
                state, orig = init_orig(runner, state, B)
            cfg = CompactionConfig(
                segment_k=int(segment_k),
                compact_threshold=resolve_compact_threshold(
                    compact_threshold, int(segment_k)),
                pad_multiple=runner.pad_multiple)
            return finish_result(rec, run_schedule(
                runner, state, orig, B, n, max_iters=int(max_iters),
                config=cfg, stats_out=stats_out, tracer=tracer),
                tracer=tracer)
        wb = wu = None
        if warm is not None and warm.basis is not None:
            wb = jnp.asarray(np.asarray(warm.basis), jnp.int32)
            if warm.at_upper is not None:
                wu = jnp.asarray(np.asarray(warm.at_upper), bool)
        x, obj, status, iters, y, z, basis, onub = revised_pallas(
            A, b, c, ub, m=m, n=n, tile_b=int(tile_b),
            max_iters=int(max_iters), tol=float(tol),
            feas_tol=float(feas_tol), refactor_period=refactor_period,
            pricing=rule, interpret=interpret, warm_basis=wb,
            warm_at_upper=wu)
        res = LPResult(x=np.asarray(x), objective=np.asarray(obj),
                       status=np.asarray(status),
                       iterations=np.asarray(iters),
                       y=np.asarray(y), z=np.asarray(z),
                       warm=WarmStart(m=m, n=n, basis=np.asarray(basis),
                                      at_upper=np.asarray(onub),
                                      pricing=rule))
        return finish_result(rec, res)
    if warm is not None:
        _warn_once(
            "tableau-warm",
            "solve_batched_pallas(backend='tableau', warm=...): the "
            "tableau tile kernel has no warm-start injection; starting "
            "cold (backend='revised' and the pdhg segment path inject)")
    if pricing == "partial":
        _warn_once(
            "partial-pricing",
            "solve_batched_pallas(pricing='partial'): the tile kernel keeps "
            "the full cost row in VMEM, so partial pricing saves nothing "
            "here; using dantzig (identical certificates). Use "
            "backend='revised' for real block pricing.")
        pricing = "dantzig"
    if tol is None:
        tol = 1e-6 if dtype == jnp.float32 else 1e-9
    if tile_b is None:
        tile_b = pick_tile_b(m, n)
    if max_iters is None:
        max_iters = default_max_iters(m, n)
    if segment_k is None:
        segment_k = auto_segment_k(m, n)
    A = jnp.asarray(batch.A, dtype)
    b = jnp.asarray(batch.b, dtype)
    c = jnp.asarray(batch.c, dtype)
    ub = jnp.asarray(batch.upper_bounds(), dtype)

    if compaction:
        runner = PallasBackend(m, n, tol, feas_tol, tile_b, dtype=dtype,
                               pricing=pricing)
        B = batch.batch
        with span("lp.dispatch", tracer, backend="tableau-pallas", B=B,
                  m=m, n=n):
            state = runner.init(A, b, c, ub=ub, telemetry=telemetry)
            state, orig = init_orig(runner, state, B)
        cfg = CompactionConfig(
            segment_k=int(segment_k),
            compact_threshold=resolve_compact_threshold(
                compact_threshold, int(segment_k)),
            pad_multiple=runner.pad_multiple)
        return finish_result(rec, run_schedule(runner, state, orig, B, n,
                                               max_iters=int(max_iters),
                                               config=cfg,
                                               stats_out=stats_out,
                                               tracer=tracer),
                             tracer=tracer)

    x, obj, status, iters, y, z = simplex_pallas(
        A, b, c, ub, m=m, n=n, tile_b=int(tile_b), max_iters=int(max_iters),
        tol=float(tol), feas_tol=float(feas_tol), interpret=interpret,
        pricing=pricing)
    res = LPResult(x=np.asarray(x), objective=np.asarray(obj),
                   status=np.asarray(status), iterations=np.asarray(iters),
                   y=np.asarray(y), z=np.asarray(z))
    return finish_result(rec, res)


def solve_hyperbox_pallas(lo, hi, d, *, tile_b: int = 256) -> np.ndarray:
    out = hyperbox_pallas(jnp.asarray(lo, jnp.float32),
                          jnp.asarray(hi, jnp.float32),
                          jnp.asarray(d, jnp.float32),
                          tile_b=tile_b, interpret=default_interpret())
    return np.asarray(out)
