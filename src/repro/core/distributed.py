"""Multi-chip batched LP solving: lockstep (pjit) vs per-shard termination.

The paper gets load balancing from CUDA's block scheduler: each LP's block
exits as soon as *its* simplex terminates. A lockstep SPMD while-loop loses
that: every chip pivots until the globally slowest LP finishes (the loop
condition is an implicit cross-chip all-reduce). Two modes:

* ``solve_pjit``      — paper-faithful lockstep: one global `while_loop` over
                        the full sharded batch. Simple, but pays
                        max-iterations-over-batch on every chip + one scalar
                        all-reduce per pivot.
* ``solve_shard_map`` — per-shard termination: `shard_map` gives every chip
                        its own `while_loop` over its local LPs, so a chip
                        whose LPs converged early goes idle instead of
                        spinning (the TPU analogue of per-block exit). No
                        cross-chip communication at all — LPs are
                        embarrassingly parallel, which is the paper's point.

Both now run the phase-compacted two-loop solve (core/simplex.py) under the
hood, and ``solve_shard_map(..., segment_k=K)`` additionally composes with
the active-set compaction scheduler (core/compaction.py): each chip runs its
local while-loop for up to K pivots, the host counts global survivors, and
when the active fraction drops below ``compact_threshold`` the surviving LPs
are gathered into the next power-of-two bucket (padded to the device count)
and the solve resumes — per-shard exit *within* a segment, per-block exit
*across* segments.

Both shard the batch axis over every mesh axis (LP solving has no model
dimension to shard).  Called without a mesh, ``solve_shard_map`` builds
``make_mesh()``: every local device on one ``("data",)`` axis.  Its one-shot
solve is ``batching.solve_batched`` with a sharded chunk solver, so it
canonicalises, plans chunks against every chip's memory, recovers and
writes the ``lp.*`` spans as the one-chip path does.
"""
from __future__ import annotations

import contextlib
import functools
import time
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..obs.report import report_from_counters
from ..obs.telemetry import tel_to_numpy
from ..obs.trace import span
from .batching import solve_batched
from .forms import ensure_canonical, finish_result
from .lp import LPBatch, LPResult, canonicalize_backend, default_max_iters
from .simplex import (_HOST_STAGING, _host_cast, _upper_bounds,
                      solve_two_phase)
from .compaction import (
    CompactionConfig, CompactionState, JaxBackend, resolve_compact_threshold,
    run_schedule, segment_phase1, segment_phase2,
)
from .revised import (
    RevisedBackend, RevisedState, auto_refactor_period, solve_revised,
    segment_revised_phase1, segment_revised_phase2,
)
from .pdhg import (
    PdhgBackend, PdhgState, default_pdhg_max_iters, segment_pdhg, solve_pdhg,
)


def make_mesh(shape: Optional[Tuple[int, ...]] = None,
              axes: Tuple[str, ...] = ("data",)) -> Mesh:
    """A mesh over the first ``prod(shape)`` devices; with no ``shape``,
    every device on one axis (the data-parallel mesh ``solve_shard_map``
    builds when it is given none)."""
    if shape is None:
        shape = (len(jax.devices()),)
    devices = np.asarray(jax.devices()[: int(np.prod(shape))]).reshape(shape)
    return Mesh(devices, axes)


def _pad_batch(batch: LPBatch, multiple: int):
    """Pad the batch to a multiple of the shard count with trivial LPs
    (max 0 s.t. x <= 1): they solve in one phase-2 check."""
    B = batch.batch
    pad = (-B) % multiple
    if pad == 0:
        return batch, B
    A = np.concatenate([batch.A, np.tile(np.eye(batch.m, batch.n)[None], (pad, 1, 1))])
    b = np.concatenate([batch.b, np.ones((pad, batch.m))])
    c = np.concatenate([batch.c, np.zeros((pad, batch.n))])
    ub = None
    if batch.ub is not None:
        ub = np.concatenate([batch.ub, np.full((pad, batch.n), np.inf)])
    return LPBatch(A=A, b=b, c=c, ub=ub), B


def _solve_local(A, b, c, ub, *, m, n, max_iters, tol, feas_tol,
                 pricing="dantzig", backend="tableau",
                 refactor_period=None, telemetry=False):
    """The shared solve body — tableau (phase-compacted two-phase), revised
    (basis-factor updates) or pdhg (restarted first-order iterations) —
    callable under shard_map (local shapes) or pjit (global shapes).  All
    three return the same (x, obj, status, iters, y, z) 6-tuple, so the
    sharding specs are backend-independent.  ``telemetry=True`` appends the
    per-LP `obs.TelemetryState` counter lanes as a seventh member (every
    lane is batched on axis 0, so one extra batch-sharded spec covers the
    whole subtree)."""
    if backend == "revised":
        return solve_revised(
            A, b, c, ub, m=m, n=n, max_iters=max_iters, tol=tol,
            feas_tol=feas_tol,
            refactor_period=int(refactor_period or auto_refactor_period(m, n)),
            pricing=pricing, telemetry=telemetry)
    if backend == "pdhg":
        from .pdhg import _check_pdhg_pricing
        _check_pdhg_pricing(pricing)   # same contract as every pdhg entry
        return solve_pdhg(A, b, c, ub, m=m, n=n, max_iters=max_iters, tol=tol,
                          feas_tol=feas_tol, telemetry=telemetry)
    return solve_two_phase(A, b, c, ub, m=m, n=n, max_iters=max_iters, tol=tol,
                           feas_tol=feas_tol, pricing=pricing,
                           telemetry=telemetry)


def _backend_defaults(backend: str, max_iters, tol, m: int, n: int, dtype):
    """Per-engine loop-cap/tolerance defaults at the distributed entry
    points (``tol=None`` resolves per engine): the first-order engine
    needs a far larger iteration cap (cheap iterations) and interprets
    ``tol`` as a relative KKT tolerance with its own dtype-dependent
    default (1e-5 f32 / 1e-8 f64, matching solve_batched_pdhg); the
    simplex engines keep the historical 1e-6 reduced-cost tolerance."""
    if backend == "pdhg":
        if tol is None:
            tol = 1e-5 if dtype == jnp.float32 else 1e-8
        return max_iters or default_pdhg_max_iters(m, n), tol
    if tol is None:
        tol = 1e-6
    return max_iters or default_max_iters(m, n), tol




def _leaves(batch: LPBatch) -> tuple:
    """The arrays the engines take: (A, b, c, ub); a batch with no bounds
    gives ub as a broadcast of +inf (`simplex._upper_bounds`)."""
    return batch.A, batch.b, batch.c, _upper_bounds(batch)


def _put(host: list, mesh: Mesh, dtype) -> list:
    """Place each host-cast array (``simplex._host_cast``) batch-sharded
    over every mesh axis, so each device receives only its own LPs."""
    shard = NamedSharding(mesh, P(tuple(mesh.axis_names)))
    put = [jax.device_put(h, shard) for h in host]
    return [d if d.dtype == dtype else d.astype(dtype) for d in put]


def shard_batch(batch: LPBatch, mesh: Mesh, dtype):
    """Pad the batch to a multiple of the device count, cast it on the
    host and place it sharded (`_put`).  Returns ``(A, b, c, ub, axes,
    orig_B, padded)``."""
    padded, orig = _pad_batch(batch, mesh.size)
    dt = jax.dtypes.canonicalize_dtype(dtype)
    A, b, c, ub = _put([_host_cast(a, dt) for a in _leaves(padded)], mesh, dt)
    return A, b, c, ub, tuple(mesh.axis_names), orig, padded


def solve_pjit(batch: LPBatch, mesh: Mesh, *, dtype=jnp.float32,
               tol: Optional[float] = None, feas_tol: float = 1e-5,
               max_iters: Optional[int] = None, lower_only: bool = False,
               pricing: str = "dantzig", backend: str = "tableau",
               refactor_period: Optional[int] = None,
               presolve: bool = True, scale: Optional[bool] = None,
               telemetry: bool = False):
    """Lockstep global solve: batch sharded over all mesh axes, single global
    while_loop (the paper-faithful distributed baseline).  ``pricing``
    selects the entering-column rule (core/pricing.py); the per-LP weights
    are loop state sharded like the tableaux, so no rule adds cross-chip
    traffic.  ``backend="revised"`` runs the basis-factor engine
    (core/revised.py) — its eta file and LU factors are loop state sharded
    with the batch, so it too stays communication-free.  GeneralLPBatch
    inputs are canonicalized on the host before sharding (the canonical
    shape is what gets partitioned) and recovered after the gather."""
    canonicalize_backend(backend)
    batch, rec = ensure_canonical(batch, presolve=presolve, scale=scale)
    m, n = batch.m, batch.n
    max_iters, tol = _backend_defaults(backend, max_iters, tol, m, n, dtype)
    A, b, c, ub, axes, orig, _ = shard_batch(batch, mesh, dtype)
    spec = P(axes)  # batch dim sharded over every axis
    shard = NamedSharding(mesh, spec)
    fn = jax.jit(
        functools.partial(_solve_local, m=m, n=n, max_iters=max_iters,
                          tol=tol, feas_tol=feas_tol, pricing=pricing,
                          backend=backend, refactor_period=refactor_period,
                          telemetry=telemetry),
        in_shardings=(shard, shard, shard, shard),
        # the telemetry subtree's lanes are all batch-on-axis-0, so one
        # extra batch-sharded entry (a pytree prefix) covers every lane
        out_shardings=(shard,) * (7 if telemetry else 6),
    )
    if lower_only:
        return fn.lower(jax.ShapeDtypeStruct(A.shape, A.dtype),
                        jax.ShapeDtypeStruct(b.shape, b.dtype),
                        jax.ShapeDtypeStruct(c.shape, c.dtype),
                        jax.ShapeDtypeStruct(ub.shape, ub.dtype))
    t0 = time.perf_counter()
    out = fn(A, b, c, ub)
    x, obj, status, iters, y, z = out[:6]
    stats = None
    if telemetry:
        jax.block_until_ready(out[6])
        counters = {k: v[:orig] for k, v in tel_to_numpy(out[6]).items()}
        stats = report_from_counters(counters,
                                     wall_s=time.perf_counter() - t0,
                                     backend=backend)
    res = LPResult(x=np.asarray(x)[:orig], objective=np.asarray(obj)[:orig],
                   status=np.asarray(status)[:orig],
                   iterations=np.asarray(iters)[:orig],
                   y=np.asarray(y)[:orig], z=np.asarray(z)[:orig],
                   stats=stats)
    return finish_result(rec, res)


class _ShardMapBackend(JaxBackend):
    """Compaction-scheduler backend whose segment runners execute under
    shard_map: per-shard while-loops (each chip stops at its own segment
    convergence), host-level survivor gathering between segments."""

    def __init__(self, mesh: Mesh, m, n, tol, feas_tol, dtype,
                 pricing: str = "dantzig"):
        super().__init__(m, n, tol, feas_tol, dtype, pricing=pricing)
        self.mesh = mesh
        axes = tuple(mesh.axis_names)
        self.pad_multiple = int(np.prod(mesh.devices.shape))
        spec = P(axes)
        state_specs = CompactionState(
            **{f: spec for f in CompactionState._fields})
        rule = self.rule

        def p1(state, steps):
            state, it = segment_phase1(state, steps, m=m, n=n, tol=tol,
                                       rule=rule)
            return state, it.reshape(1)

        def p2(state, steps):
            state, it = segment_phase2(state, steps, m=m, n=n, tol=tol,
                                       rule=rule)
            return state, it.reshape(1)

        def wrap(fn):
            return jax.jit(jax.shard_map(
                fn, mesh=mesh,
                in_specs=(state_specs, P()),
                out_specs=(state_specs, spec),
                check_vma=False,
            ))

        self._p1 = wrap(p1)
        self._p2 = wrap(p2)

    def run_phase1(self, state, steps):
        state, it = self._p1(state, jnp.int32(steps))
        return state, int(np.max(np.asarray(it)))

    def run_phase2(self, state, steps):
        state, it = self._p2(state, jnp.int32(steps))
        return state, int(np.max(np.asarray(it)))


class _RevisedShardMapBackend(RevisedBackend):
    """Revised-simplex segment runners under shard_map: per-shard
    while-loops (each chip's eta file and LU factors stay chip-local since
    every RevisedState leaf is batched on axis 0), host-level survivor
    gathering — and refactor-on-compact — between segments."""

    def __init__(self, mesh: Mesh, m, n, tol, feas_tol, dtype,
                 pricing: str = "dantzig",
                 refactor_period: Optional[int] = None):
        super().__init__(m, n, tol, feas_tol, dtype, pricing=pricing,
                         refactor_period=refactor_period)
        self.mesh = mesh
        axes = tuple(mesh.axis_names)
        self.pad_multiple = int(np.prod(mesh.devices.shape))
        spec = P(axes)
        state_specs = RevisedState(
            **{f: spec for f in RevisedState._fields})
        rule, K = self.rule, self.refactor_period

        def p1(state, steps):
            state, it = segment_revised_phase1(
                state, steps, m=m, n=n, tol=tol, refactor_period=K,
                rule=rule)
            return state, it.reshape(1)

        def p2(state, steps):
            state, it = segment_revised_phase2(
                state, steps, m=m, n=n, tol=tol, refactor_period=K,
                rule=rule)
            return state, it.reshape(1)

        def wrap(fn):
            return jax.jit(jax.shard_map(
                fn, mesh=mesh,
                in_specs=(state_specs, P()),
                out_specs=(state_specs, spec),
                check_vma=False,
            ))

        self._p1 = wrap(p1)
        self._p2 = wrap(p2)

    def run_phase1(self, state, steps):
        state, it = self._p1(state, jnp.int32(steps))
        return state, int(np.max(np.asarray(it)))

    def run_phase2(self, state, steps):
        state, it = self._p2(state, jnp.int32(steps))
        return state, int(np.max(np.asarray(it)))


class _PdhgShardMapBackend(PdhgBackend):
    """First-order segment runners under shard_map: each chip advances its
    local LPs through check rounds independently (every PdhgState leaf —
    problem data, iterates, averages, restart state — is batched on axis 0,
    so the specs are uniform), host-level survivor gathering between
    segments.  There is no phase 1, so only the stage-2 runner is wrapped."""

    def __init__(self, mesh: Mesh, m, n, tol, dtype, check_every=None):
        kw = {} if check_every is None else {"check_every": check_every}
        super().__init__(m, n, tol, dtype, **kw)
        self.mesh = mesh
        axes = tuple(mesh.axis_names)
        self.pad_multiple = int(np.prod(mesh.devices.shape))
        spec = P(axes)
        state_specs = PdhgState(**{f: spec for f in PdhgState._fields})
        ce = self.check_every

        def p2(state, steps):
            state, it = segment_pdhg(state, steps, tol=self.tol,
                                     check_every=ce)
            return state, it.reshape(1)

        self._p2 = jax.jit(jax.shard_map(
            p2, mesh=mesh,
            in_specs=(state_specs, P()),
            out_specs=(state_specs, spec),
            check_vma=False,
        ))

    def run_phase2(self, state, steps):
        state, it = self._p2(state, jnp.int32(steps))
        return state, int(np.max(np.asarray(it)))


@functools.lru_cache(maxsize=None)
def _shard_map_solver(mesh: Mesh, m, n, max_iters, tol, feas_tol, pricing,
                      backend, refactor_period, telemetry):
    """The jitted one-shot solve under shard_map: each chip runs
    `_solve_local` over its own LPs, with its own while-loop.  Built once
    per mesh and solve settings, so repeated calls reuse the program."""
    spec = P(tuple(mesh.axis_names))
    local = functools.partial(_solve_local, m=m, n=n, max_iters=max_iters,
                              tol=tol, feas_tol=feas_tol, pricing=pricing,
                              backend=backend, refactor_period=refactor_period,
                              telemetry=telemetry)
    return jax.jit(jax.shard_map(
        local, mesh=mesh,
        in_specs=(spec, spec, spec, spec),
        # one extra batch-sharded prefix entry covers every telemetry lane
        out_specs=(spec,) * (7 if telemetry else 6),
        check_vma=False,
    ))


def _solve_shards(batch: LPBatch, *, mesh: Mesh, dtype=jnp.float32,
                  tol: Optional[float] = None, feas_tol: float = 1e-5,
                  max_iters: Optional[int] = None, pricing: str = "dantzig",
                  backend: str = "tableau",
                  refactor_period: Optional[int] = None,
                  telemetry: bool = False) -> LPResult:
    """One chunk of the one-shot `solve_shard_map`, the chunk solver that
    `solve_batched` calls: pad to a multiple of the chip count, cast on the
    host, put batch-sharded, run `_shard_map_solver`, wait, fetch, unpad.
    ``lp.d2h`` carries ``shard_max_iters``: the most iterations returned in
    each chip's contiguous block, the pivots that chip's loop ran."""
    m, n = batch.m, batch.n
    max_iters, tol = _backend_defaults(backend, max_iters, tol, m, n, dtype)
    shards = mesh.size
    padded, orig = _pad_batch(batch, shards)
    dt = jax.dtypes.canonicalize_dtype(dtype)
    with _HOST_STAGING.lease() as cast:
        with span("lp.h2d", shards=shards) as h2d:
            leaves = _leaves(padded)
            with span("lp.h2d.cast") as cast_span:
                host, cast_args = cast([(a, dt) for a in leaves])
                cast_span.set(**cast_args)
            with span("lp.h2d.put"):
                dev = _put(host, mesh, dt)
            h2d.set(bytes_in=sum(getattr(a, "nbytes", 0) for a in leaves),
                    bytes_out=sum(h.nbytes for h in host))
        # free the host copies now, as solve_batched_jax does
        del leaves, host
        fn = _shard_map_solver(mesh, m, n, int(max_iters), float(tol),
                               float(feas_tol), pricing, backend,
                               refactor_period, bool(telemetry))
        t0 = time.perf_counter()
        with span("lp.dispatch", B=padded.batch, m=m, n=n, shards=shards):
            out = fn(*dev)
        with span("lp.wait"):
            jax.block_until_ready(out)
        # every input the outputs read has been read (solve_batched_jax)
        del dev
    wall_s = time.perf_counter() - t0
    with span("lp.d2h") as d2h:
        x, obj, status, iters, y, z = (np.asarray(a) for a in out[:6])
        fetched = [x, obj, status, iters, y, z]
        stats = None
        if telemetry:
            counters = tel_to_numpy(out[6])
            fetched += counters.values()
            stats = report_from_counters(
                {k: v[:orig] for k, v in counters.items()}, wall_s=wall_s,
                backend=backend)
        d2h.set(arrays=len(fetched), bytes=sum(a.nbytes for a in fetched),
                shard_max_iters=iters.reshape(shards, -1).max(axis=1).tolist())
    return LPResult(x=x[:orig], objective=obj[:orig], status=status[:orig],
                    iterations=iters[:orig], y=y[:orig], z=z[:orig],
                    stats=stats)


def solve_shard_map(batch: LPBatch, mesh: Optional[Mesh] = None, *,
                    dtype=jnp.float32,
                    tol: Optional[float] = None, feas_tol: float = 1e-5,
                    max_iters: Optional[int] = None, lower_only: bool = False,
                    segment_k: Optional[int] = None,
                    compact_threshold: Optional[float] = None,
                    pricing: str = "dantzig", stats_out=None,
                    backend: str = "tableau",
                    refactor_period: Optional[int] = None,
                    presolve: bool = True, scale: Optional[bool] = None,
                    telemetry: bool = False, tracer=None,
                    device_bytes: Optional[int] = None):
    """Per-shard termination: each chip solves its local LPs to completion
    independently (no cross-chip sync per pivot).  ``mesh=None`` builds
    `make_mesh()`: every local device, data-parallel.

    ``segment_k=None`` (default) is the one-shot solve: `solve_batched`
    with `_solve_shards` as its chunk solver, so the batch is canonicalised,
    planned against every chip's memory (``device_bytes`` per chip, default
    the limit the first device reports) and run in chunks, each spread over
    the mesh.  ``segment_k=K`` runs the solve in K-pivot segments through
    the active-set compaction scheduler (see module docstring); results are
    identical, work shrinks with the survivor count
    (``compact_threshold=None`` derives the gather eagerness from
    `auto_compact_threshold`).  ``pricing`` selects the entering-column rule
    (core/pricing.py) in both modes, and ``backend="revised"`` the
    basis-factor engine (core/revised.py).  GeneralLPBatch inputs
    canonicalize on the host before sharding and recover after the gather,
    in both the one-shot and segmented modes."""
    canonicalize_backend(backend)
    if mesh is None:
        mesh = make_mesh()
    if segment_k is not None and lower_only:
        raise ValueError(
            "segment_k and lower_only cannot be combined: the segmented "
            "scheduler is a host-driven loop with no single lowerable "
            "computation")
    if stats_out is not None and segment_k is None:
        raise ValueError(
            "stats_out requires segment_k: the one-shot solve has no "
            "segment accounting to record")
    if segment_k is None and not lower_only:
        with (tracer.active() if tracer is not None
              else contextlib.nullcontext()):
            return solve_batched(
                batch, solver=_solve_shards, device_bytes=device_bytes,
                n_devices=mesh.size, pricing=pricing, backend=backend,
                presolve=presolve, scale=scale, mesh=mesh, dtype=dtype,
                tol=tol, feas_tol=feas_tol, max_iters=max_iters,
                refactor_period=refactor_period, telemetry=telemetry)

    batch, rec = ensure_canonical(batch, presolve=presolve, scale=scale,
                                  tracer=tracer)
    m, n = batch.m, batch.n
    max_iters, tol = _backend_defaults(backend, max_iters, tol, m, n, dtype)
    if lower_only:
        fn = _shard_map_solver(mesh, m, n, int(max_iters), float(tol),
                               float(feas_tol), pricing, backend,
                               refactor_period, bool(telemetry))
        B = -(-batch.batch // mesh.size) * mesh.size
        dt = jax.dtypes.canonicalize_dtype(dtype)
        return fn.lower(*(jax.ShapeDtypeStruct(shape, dt) for shape in
                          ((B, m, n), (B, m), (B, n), (B, n))))

    budget = max_iters
    if backend == "revised":
        runner = _RevisedShardMapBackend(
            mesh, m, n, tol, feas_tol, dtype, pricing=pricing,
            refactor_period=refactor_period)
    elif backend == "pdhg":
        from .pdhg import _check_pdhg_pricing
        _check_pdhg_pricing(pricing)
        runner = _PdhgShardMapBackend(mesh, m, n, tol, dtype)
        # the scheduler's step unit for pdhg is one check round
        budget = -(-max_iters // runner.check_every)
    else:
        runner = _ShardMapBackend(mesh, m, n, tol, feas_tol, dtype,
                                  pricing=pricing)
    padded, orig_B = _pad_batch(batch, runner.pad_multiple)
    state = runner.init(jnp.asarray(padded.A, dtype),
                        jnp.asarray(padded.b, dtype),
                        jnp.asarray(padded.c, dtype),
                        ub=jnp.asarray(padded.upper_bounds(), dtype),
                        telemetry=telemetry)
    B_pad = padded.batch
    orig = np.concatenate(
        [np.arange(orig_B), np.full(B_pad - orig_B, -1)]).astype(np.int64)
    # padding LPs are not real work: retire them before the first segment
    state = runner.deactivate(state, orig >= 0)
    cfg = CompactionConfig(
        segment_k=segment_k,
        compact_threshold=resolve_compact_threshold(compact_threshold,
                                                    segment_k),
        pad_multiple=runner.pad_multiple)
    return finish_result(rec, run_schedule(runner, state, orig, orig_B, n,
                                           max_iters=budget, config=cfg,
                                           stats_out=stats_out,
                                           tracer=tracer),
                         tracer=tracer)
