"""Active-set compaction scheduler — Level 2 of the work-elimination engine.

The paper's CUDA solver load-balances through per-block early exit (Sec. 5):
each LP's block returns the moment *its* simplex terminates, and the block
scheduler backfills the SM.  Lockstep static-shape solvers (JAX while-loop,
Pallas tile kernel) lose that: a converged LP keeps occupying its batch slot,
executing masked no-op pivots until the slowest LP in its termination group
finishes.  `analysis/lp_perf.py` measures that waste as 1 - mean/max over the
pivot distribution — up to ~2x on mixed feasible/infeasible batches.

This module is a static-shape-friendly reconstruction of per-block exit:

1. run the solve in **segments** of at most ``segment_k`` pivots (each
   segment is one XLA computation with its own early-stopping while-loop);
2. after each segment, count surviving ``_RUNNING`` LPs on the host
   (one tiny D2H transfer of the status vector);
3. when the active fraction drops below ``compact_threshold``, **gather** the
   survivors into the next power-of-two bucket size and resume.

Bucket sizes walk a fixed ladder (B, B/2, B/4, ..., times ``pad_multiple``
for sharded/tiled backends), so recompiles are amortized: every batch of the
same starting size reuses the same ladder of compiled segment programs.
Because gathering LPs never changes any LP's own tableau, the pivot sequence
— and therefore status/objective/iterations — is bit-identical to the
unsegmented solver.

The scheduler is backend-agnostic: a backend supplies segment runners and
state plumbing.  `JaxBackend` (here) runs the pure-JAX phase-compacted
solver; `ShardMapBackend` runs any such backend's segments under shard_map
(per-shard termination *inside* segments); `kernels.ops.PallasBackend` runs
the Pallas tile kernels.  Both levels compose: segments before column
compaction run on the full tableau (stage "p1"), segments after it on the
phase-compacted tableau (stage "p2") — see core/simplex.py for Level 1.
"""
from __future__ import annotations

import dataclasses
import functools
import time
from typing import Any, List, NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from ..obs.report import report_from_counters
from ..obs.telemetry import init_telemetry, tel_to_numpy, zeros_numpy
from ..obs.trace import span
from .forms import ensure_canonical, finish_result, prepare_warm
from .lp import (ITERATION_LIMIT, OPTIMAL, LPBatch, LPResult, WarmStart,
                 canonicalize_backend, default_max_iters, resolve_backend)
from .pricing import canonicalize_rule, compact_weights, init_weights
from .simplex import (
    _RUNNING,
    SimplexState,
    build_tableau_jax,
    compact_tableau,
    extract_duals,
    extract_solution_compacted,
    extract_solution_jax,
    inject_tableau_warm,
    phase2_step,
    simplex_step,
    tableau_elements,
)
from .transfer import _pad_batch, batch_spec, shard


class CompactionState(NamedTuple):
    """Resumable solver state; every leaf has the batch on axis 0 so generic
    gathers (`backend.take`) work across backends."""
    T: jax.Array       # tableaux (full in stage p1, compacted in stage p2)
    basis: jax.Array
    phase: jax.Array
    status: jax.Array
    iters: jax.Array
    w: jax.Array       # (B, C) pricing weights (core/pricing.py); gathered
                       # across segment boundaries like every other leaf
    flip: jax.Array    # (B, n) bool complement flags (bounded variables)
    ub: jax.Array      # (B, n) upper bounds (+inf = unbounded)
    thr: jax.Array     # per-LP phase-1 feasibility threshold
    tel: Any = None    # obs.TelemetryState lanes or None (empty subtree:
                       #  the telemetry-off trace is unchanged); rides the
                       #  bucket gathers like every other leaf


def auto_segment_k(m: int, n: int) -> int:
    """Segment length heuristic when the caller passes ``segment_k=None``:
    ~1/64 of the `default_max_iters` cap (floor 4), so a typical solve gets
    a handful of compaction checkpoints regardless of problem size instead
    of the one-size static 8.  Dantzig pivots O(m+n) times on the paper's
    classes, so this lands segments at roughly every 15% of the expected
    solve; steeper rules just hit the checkpoints sooner."""
    return max(4, default_max_iters(m, n) // 64)


def auto_compact_threshold(segment_k: int) -> float:
    """Compact-threshold heuristic when the caller passes
    ``compact_threshold=None``, tuned from the observed ``SegmentStat``
    survivor curves in BENCH_pivot_work.json (``scheduled.survivor_curve``).

    A gather costs ~2 state touches (read + scatter-write), i.e. roughly 2
    lockstep steps of the *new* bucket, while compacting at active fraction
    f saves (1 - f) * segment_k step-slots over the next segment alone — so
    a shrink pays off once segment_k >= 2 f / (1 - f), giving the eagerness
    curve f* = segment_k / (segment_k + 2).  The measured survivor curves
    collapse by 30-50% per segment (e.g. 2181 -> 1729 -> 150 of 4096 at
    5x5), so for the auto-derived segment_k (>= 4) every power-of-two shrink
    pays: the derived threshold sits above the pow2 ladder's own f <= 1/2
    shrink gate and never blocks one.  Only very short segments
    (segment_k <= 2, where gather overhead rivals the segment itself) get a
    stricter bar than the historical static 0.5."""
    if segment_k < 1:
        raise ValueError(f"segment_k must be >= 1, got {segment_k}")
    return min(0.95, segment_k / (segment_k + 2.0))


def resolve_compact_threshold(compact_threshold: Optional[float],
                              segment_k: int) -> float:
    """``None`` -> derived (`auto_compact_threshold`); floats pass through
    (0.5 was the historical static default)."""
    if compact_threshold is None:
        return auto_compact_threshold(segment_k)
    return float(compact_threshold)


@dataclasses.dataclass(frozen=True)
class CompactionConfig:
    segment_k: int = 8            # max pivots per segment
    compact_threshold: float = 0.5  # gather when active fraction < this
    pad_multiple: int = 1         # bucket sizes are multiples of this

    def __post_init__(self):
        if self.segment_k < 1:
            raise ValueError(f"segment_k must be >= 1, got {self.segment_k}")
        if self.pad_multiple < 1:
            raise ValueError(
                f"pad_multiple must be >= 1, got {self.pad_multiple}")


@dataclasses.dataclass
class SegmentStat:
    """Executed-work record for one segment (benchmarks/pivot_work.py)."""
    stage: str      # "p1" (full tableau) or "p2" (compacted)
    bucket: int     # batch slots occupied during the segment
    steps: int      # lockstep steps actually executed (<= segment_k)
    elements: int   # steps * bucket * tableau_elements(stage)
    survivors: int = -1  # RUNNING LPs observed after the segment (the
                         # survivor curve the auto-tune heuristic targets)


def total_elements(stats: List[SegmentStat]) -> int:
    return sum(s.elements for s in stats)


def total_steps(stats: List[SegmentStat]) -> int:
    return sum(s.steps for s in stats)


def next_bucket(active: int, pad_multiple: int = 1) -> int:
    """Next-power-of-two bucket >= active, rounded up to pad_multiple."""
    b = 1 << max(0, active - 1).bit_length()
    return -(-b // pad_multiple) * pad_multiple


def init_orig(backend, state, B: int):
    """Build the original-slot map for a freshly init'd backend state.

    Returns ``(state, orig)`` where ``orig[i]`` is the caller's batch index
    occupying slot ``i``.  A backend may return a batch-padded state from
    ``init`` (Pallas tile multiples); padding slots get ``orig == -1`` and
    are deactivated so the scheduler never counts them as active.
    """
    orig = np.arange(B, dtype=np.int64)
    B_state = int(state.status.size)     # the shape alone: no fetch
    if B_state > B:
        orig = np.concatenate(
            [orig, np.full(B_state - B, -1)]).astype(np.int64)
        state = backend.deactivate(state, orig >= 0)
    return state, orig


# ---------------------------------------------------------------------------
# Traceable segment runners (shared by JaxBackend and the shard_map backend)
# ---------------------------------------------------------------------------

def segment_phase1(state: CompactionState, steps, *, m: int, n: int,
                   tol: float, rule: str = "dantzig"):
    """Run up to `steps` combined (phase-1/phase-2) pivots on the full
    tableau; stops early once no LP is still in phase 1."""
    def cond(carry):
        s, it = carry
        pending = (s.status == _RUNNING) & (s.phase == 1)
        return jnp.any(pending) & (it < steps)

    def body(carry):
        s, it = carry
        ns = simplex_step(
            SimplexState(s.T, s.basis, s.phase, s.status, s.iters, s.w,
                         s.flip, s.ub, it, s.tel),
            n=n, m=m, tol=tol, feas_thr=s.thr, rule=rule)
        return CompactionState(ns.T, ns.basis, ns.phase, ns.status, ns.iters,
                               ns.w, ns.flip, ns.ub, s.thr, ns.tel), it + 1

    state, it = jax.lax.while_loop(cond, body, (state, jnp.int32(0)))
    return state, it


def segment_phase2(state: CompactionState, steps, *, m: int, n: int,
                   tol: float, rule: str = "dantzig"):
    """Run up to `steps` phase-2 pivots on the compacted tableau; stops early
    once every LP is terminal."""
    def cond(carry):
        s, it = carry
        return jnp.any(s.status == _RUNNING) & (it < steps)

    def body(carry):
        s, it = carry
        ns = phase2_step(
            SimplexState(s.T, s.basis, s.phase, s.status, s.iters, s.w,
                         s.flip, s.ub, it, s.tel),
            n=n, m=m, tol=tol, rule=rule)
        return CompactionState(ns.T, ns.basis, ns.phase, ns.status, ns.iters,
                               ns.w, ns.flip, ns.ub, s.thr, ns.tel), it + 1

    state, it = jax.lax.while_loop(cond, body, (state, jnp.int32(0)))
    return state, it


def segment_combined(state: CompactionState, steps, *, m: int, n: int,
                     tol: float, rule: str = "dantzig"):
    """Run up to `steps` combined two-phase pivots on the *full* tableau;
    stops early once every LP is terminal.

    Unlike the `segment_phase1` -> column-compaction -> `segment_phase2`
    ladder, this runner never changes the tableau layout — which is what
    the frontier scheduler needs: a lane must accept a cold *or* warm
    newcomer at any segment boundary, and a newcomer starts in phase 1,
    which the phase-compacted tableau cannot represent."""
    def cond(carry):
        s, it = carry
        return jnp.any(s.status == _RUNNING) & (it < steps)

    def body(carry):
        s, it = carry
        ns = simplex_step(
            SimplexState(s.T, s.basis, s.phase, s.status, s.iters, s.w,
                         s.flip, s.ub, it, s.tel),
            n=n, m=m, tol=tol, feas_thr=s.thr, rule=rule)
        return CompactionState(ns.T, ns.basis, ns.phase, ns.status, ns.iters,
                               ns.w, ns.flip, ns.ub, s.thr, ns.tel), it + 1

    state, it = jax.lax.while_loop(cond, body, (state, jnp.int32(0)))
    return state, it


_segment_phase1_jit = jax.jit(segment_phase1,
                              static_argnames=("m", "n", "tol", "rule"))
_segment_phase2_jit = jax.jit(segment_phase2,
                              static_argnames=("m", "n", "tol", "rule"))
_segment_combined_jit = jax.jit(segment_combined,
                                static_argnames=("m", "n", "tol", "rule"))


@functools.partial(jax.jit, static_argnames=("m", "n"))
def _compact_columns_jit(T, *, m, n):
    return compact_tableau(T, m=m, n=n)


@functools.partial(jax.jit, static_argnames=("m", "n"))
def _compact_weights_jit(w, *, m, n):
    return compact_weights(w, m=m, n=n)


@functools.partial(jax.jit, static_argnames=("n", "compacted"))
def _extract_jit(T, basis, status, iters, flip, ub, *, n, compacted):
    if compacted:
        x, obj = extract_solution_compacted(T, basis, n, flip=flip, ub=ub)
        m = T.shape[1] - 1
    else:
        x, obj = extract_solution_jax(T, basis, n, flip=flip, ub=ub)
        m = T.shape[1] - 2
    y, z = extract_duals(T, m=m, n=n, flip=flip)
    status = jnp.where(status == _RUNNING, ITERATION_LIMIT, status)
    obj = jnp.where(status == OPTIMAL, obj, jnp.nan)
    opt = (status == OPTIMAL)[:, None]
    return (x, obj, status.astype(jnp.int8), iters,
            jnp.where(opt, y, jnp.nan), jnp.where(opt, z, jnp.nan))


@jax.jit
def _take_jit(state, idx):
    return jax.tree_util.tree_map(lambda a: a[idx], state)


@jax.jit
def _scatter_jit(state, new_state, idx):
    """Write the j-lane ``new_state`` into lanes ``idx`` of ``state`` (the
    frontier scheduler's admission move — the inverse of a retirement
    gather)."""
    return jax.tree_util.tree_map(lambda a, b: a.at[idx].set(b),
                                  state, new_state)


class JaxBackend:
    """Segment runners for the pure-JAX phase-compacted solver."""

    pad_multiple = 1

    def __init__(self, m: int, n: int, tol: float, feas_tol: float, dtype,
                 pricing: str = "dantzig"):
        self.m, self.n = m, n
        self.tol, self.feas_tol = float(tol), float(feas_tol)
        self.dtype = dtype
        self.rule = canonicalize_rule(pricing)

    def init(self, A, b, c, ub=None, warm: WarmStart | None = None,
             telemetry: bool = False) -> CompactionState:
        T, basis, phase = build_tableau_jax(A, b, c)
        B = T.shape[0]
        if ub is None:
            ub = jnp.full((B, self.n), jnp.inf, dtype=T.dtype)
        else:
            ub = jnp.asarray(ub, dtype=T.dtype)
        flip = jnp.zeros((B, self.n), dtype=bool)
        ok = None
        if warm is not None and warm.basis is not None:
            wfl = (flip if warm.at_upper is None
                   else jnp.asarray(np.asarray(warm.at_upper), bool))
            T_w, basis_w, phase_w, flip_w, ok = inject_tableau_warm(
                A, b, c, ub, jnp.asarray(np.asarray(warm.basis), jnp.int32),
                wfl, m=self.m, n=self.n, feas_tol=self.feas_tol)
            T = jnp.where(ok[:, None, None], T_w, T)
            basis = jnp.where(ok[:, None], basis_w, basis)
            phase = jnp.where(ok, phase_w, phase)
            flip = jnp.where(ok[:, None], flip_w, flip)
        thr = self.feas_tol * jnp.maximum(1.0, T[:, self.m + 1, -1])
        # dantzig never reads weights: carry a (B, 1) stub so segments and
        # bucket gathers don't move a dead (B, C) array
        w = (jnp.ones((B, 1), T.dtype) if self.rule in ("dantzig", "partial")
             else init_weights(self.rule, T, self.m))
        if (ok is not None and self.rule == "devex"
                and warm.pricing == self.rule and warm.weights is not None
                and np.asarray(warm.weights).shape[1] >= self.n + self.m):
            ww = jnp.asarray(np.asarray(warm.weights), w.dtype)
            nm = self.n + self.m
            w = w.at[:, :nm].set(
                jnp.where(ok[:, None], ww[:, :nm], w[:, :nm]))
        return CompactionState(
            T=T, basis=basis, phase=phase,
            status=jnp.full((B,), _RUNNING, jnp.int32),
            iters=jnp.zeros((B,), jnp.int32), w=w,
            flip=flip, ub=ub, thr=thr,
            tel=init_telemetry(B) if telemetry else None)

    def run_phase1(self, state, steps):
        state, it = _segment_phase1_jit(state, jnp.int32(steps), m=self.m,
                                        n=self.n, tol=self.tol,
                                        rule=self.rule)
        return state, int(it)

    def run_phase2(self, state, steps):
        state, it = _segment_phase2_jit(state, jnp.int32(steps), m=self.m,
                                        n=self.n, tol=self.tol,
                                        rule=self.rule)
        return state, int(it)

    def segment_bodies(self) -> dict:
        """The unjitted segment runners by stage, each ``fn(state, steps)
        -> (state, steps run)``: what `ShardMapBackend` puts under
        shard_map."""
        kw = dict(m=self.m, n=self.n, tol=self.tol, rule=self.rule)
        return {"p1": functools.partial(segment_phase1, **kw),
                "p2": functools.partial(segment_phase2, **kw)}

    def run_combined(self, state, steps):
        state, it = _segment_combined_jit(state, jnp.int32(steps), m=self.m,
                                          n=self.n, tol=self.tol,
                                          rule=self.rule)
        return state, int(it)

    def scatter(self, state, new_state, idx) -> CompactionState:
        return _scatter_jit(state, new_state, jnp.asarray(idx))

    def compact_columns(self, state: CompactionState) -> CompactionState:
        w = (state.w if self.rule in ("dantzig", "partial")
             else _compact_weights_jit(state.w, m=self.m, n=self.n))
        return state._replace(
            T=_compact_columns_jit(state.T, m=self.m, n=self.n), w=w)

    def limit_phase1(self, state: CompactionState) -> CompactionState:
        """Budget exhausted while still in phase 1 -> iteration limit."""
        status = jnp.where(
            (state.status.reshape(-1) == _RUNNING)
            & (state.phase.reshape(-1) == 1),
            ITERATION_LIMIT, state.status.reshape(-1))
        return state._replace(status=status.reshape(state.status.shape))

    def deactivate(self, state: CompactionState, valid) -> CompactionState:
        """Mark padding slots terminal so they never count as active."""
        valid = jnp.asarray(np.asarray(valid).reshape(-1))
        status = jnp.where(valid, state.status.reshape(-1), ITERATION_LIMIT)
        return state._replace(status=status.reshape(state.status.shape).astype(
            state.status.dtype))

    def take(self, state: CompactionState, idx) -> CompactionState:
        return _take_jit(state, jnp.asarray(idx))

    def status_host(self, state) -> np.ndarray:
        return np.asarray(state.status).reshape(-1)

    def phase_host(self, state) -> np.ndarray:
        return np.asarray(state.phase).reshape(-1)

    def extract(self, state: CompactionState, stage: str):
        return tuple(np.asarray(o) for o in _extract_jit(
            state.T, state.basis, state.status.reshape(-1),
            state.iters.reshape(-1), state.flip, state.ub,
            n=self.n, compacted=(stage == "p2")))

    def elements_per_step(self, stage: str) -> int:
        return tableau_elements(self.m, self.n, compacted=(stage == "p2"))


class ShardMapBackend:
    """A scheduler backend whose segment runners (``segment_bodies``) run
    under shard_map over ``mesh``, the batch split by `transfer.batch_spec`:
    each chip runs its own while loop over its own LPs, and a segment's step
    count is the largest over the chips.  Every other call — init, gathers,
    column compaction, extraction — is the wrapped backend's own, on the
    whole sharded state; every state leaf is batched on axis 0.  Batches
    are padded to a multiple of the chip count (`on_mesh`)."""

    def __init__(self, base, mesh):
        self._base = base
        self.pad_multiple = mesh.size
        spec = batch_spec(mesh)
        self._runners = {stage: shard(_per_shard(body), mesh, (spec, P()))
                         for stage, body in base.segment_bodies().items()}

    def __getattr__(self, name):
        return getattr(self._base, name)

    def _run(self, stage, state, steps):
        state, it = self._runners[stage](state, jnp.int32(steps))
        return state, int(np.max(np.asarray(it)))

    def run_phase1(self, state, steps):
        if "p1" not in self._runners:        # an engine with no phase 1
            return self._base.run_phase1(state, steps)
        return self._run("p1", state, steps)

    def run_phase2(self, state, steps):
        return self._run("p2", state, steps)


def _per_shard(body):
    """``body`` with its step count as a one-element array, so the counts
    of all shards come back side by side."""
    def local(state, steps):
        state, it = body(state, steps)
        return state, it.reshape(1)
    return local


def on_mesh(backend, batch: LPBatch, mesh):
    """``backend`` and ``batch`` for a scheduled solve over ``mesh`` (none:
    as they are): the segments under `ShardMapBackend`, the batch padded
    with trivial LPs to a multiple of the chip count (`init_orig` retires
    them before the first segment)."""
    if mesh is None:
        return backend, batch
    return ShardMapBackend(backend, mesh), _pad_batch(batch, mesh.size)[0]


# ---------------------------------------------------------------------------
# The scheduler
# ---------------------------------------------------------------------------

def run_schedule(backend, state: CompactionState, orig: np.ndarray, B: int,
                 n: int, *, max_iters: int, config: CompactionConfig,
                 stats_out: Optional[List[SegmentStat]] = None,
                 tracer=None) -> LPResult:
    """Drive a backend through segmented stage-1 (full tableau) and stage-2
    (phase-compacted) solves with active-set compaction in between.

    ``orig`` maps each current batch slot to its index in the caller's batch
    (-1 for padding slots, which must already be terminal).  Results land in
    dense (B, ...) output arrays; retired LPs are flushed right before every
    compaction, survivors at the end.

    When the backend state carries telemetry lanes (``state.tel`` not None)
    the per-LP counters are flushed to host buffers alongside the results —
    each LP's lanes are read at its retirement gather, so counters survive
    the bucket shrinks — and the returned ``LPResult.stats`` holds the
    assembled `obs.SolveReport`.  ``tracer`` (an `obs.SpanTracer`) records
    segment / bucket-gather spans and flush events with bucket sizes and
    survivor counts.
    """
    t_start = time.perf_counter()
    np_dtype = np.dtype(jnp.zeros((), backend.dtype).dtype)
    out_x = np.zeros((B, n), np_dtype)
    out_obj = np.full((B,), np.nan, np_dtype)
    out_status = np.full((B,), ITERATION_LIMIT, np.int8)
    out_iters = np.zeros((B,), np.int32)
    # dual-certificate buffers sized lazily off the first flush (m is not a
    # scheduler parameter; every backend now extracts a 6-tuple)
    duals = {}
    tel_host = (zeros_numpy(B)
                if getattr(state, "tel", None) is not None else None)

    def flush(state, orig, stage):
        x, obj, status, iters, y, z = backend.extract(state, stage)
        sel = orig >= 0
        oi = orig[sel]
        out_x[oi] = x[sel]
        out_obj[oi] = obj[sel]
        out_status[oi] = status[sel]
        out_iters[oi] = iters[sel]
        if not duals:
            duals["y"] = np.full((B, y.shape[1]), np.nan, np_dtype)
            duals["z"] = np.full((B, z.shape[1]), np.nan, np_dtype)
        duals["y"][oi] = y[sel]
        duals["z"][oi] = z[sel]
        tel = getattr(state, "tel", None)
        if tel_host is not None and tel is not None:
            for name, vals in tel_to_numpy(tel).items():
                tel_host[name][oi] = vals[sel]
        if tracer is not None:
            tracer.event("flush", stage=stage, lps=int(sel.sum()))

    def maybe_compact(state, orig, stage):
        """Returns (state, orig, status_host) — the single D2H status fetch
        per segment lives here; callers reuse the returned host copy."""
        status = backend.status_host(state)
        running = status == _RUNNING
        n_run = int(running.sum())
        cur = len(orig)
        if n_run == 0:
            return state, orig, status
        bucket = next_bucket(n_run, config.pad_multiple)
        if bucket >= cur or n_run >= config.compact_threshold * cur:
            return state, orig, status
        # retire everyone's current results, then gather the survivors
        with span("lp.bucket_gather", tracer, stage=stage,
                         src_bucket=cur, dst_bucket=bucket,
                         survivors=n_run):
            flush(state, orig, stage)
            idx = np.nonzero(running)[0]
            pad = bucket - len(idx)
            fill = idx[np.arange(pad) % len(idx)]
            take_idx = np.concatenate([idx, fill])
            state = backend.take(state, take_idx)
            valid = np.arange(bucket) < len(idx)
            state = backend.deactivate(state, valid)
            orig = np.where(valid,
                            np.concatenate([orig[idx], orig[fill]]), -1)
        # post-gather host status is known without another transfer:
        # survivors are RUNNING, fill slots were just deactivated
        status = np.where(valid, _RUNNING, ITERATION_LIMIT)
        return state, orig, status

    def run_stage(state, orig, stage, runner, pending, budget):
        status = backend.status_host(state)
        seg = 0
        while budget > 0:
            if not pending(state, status):
                break
            steps = min(config.segment_k, budget)
            bucket = len(orig)
            with span(f"lp.segment[{stage}]", tracer, k=seg,
                      bucket=bucket, max_steps=steps) as sp:
                state, done = runner(state, steps)
                budget -= max(1, done)
                # a triggered bucket gather nests under its segment span
                state, orig, status = maybe_compact(state, orig, stage)
                survivors = int((status == _RUNNING).sum())
                # lane occupancy after the (possibly compacted) segment
                sp.set(steps=int(done), survivors=survivors,
                       occupancy=survivors / max(1, len(orig)))
            if stats_out is not None:
                # survivor count is compaction-invariant (gathers only drop
                # terminal LPs), so the post-compact host status serves both
                stats_out.append(SegmentStat(
                    stage=stage, bucket=bucket, steps=done,
                    elements=done * bucket * backend.elements_per_step(stage),
                    survivors=survivors))
            seg += 1
        return state, orig, budget

    def pending_p1(state, status):
        phase = backend.phase_host(state)
        return bool(np.any((status == _RUNNING) & (phase == 1)))

    def pending_p2(state, status):
        return bool(np.any(status == _RUNNING))

    # ---- stage 1: full tableau until every LP has left phase 1 -------------
    # (one max_iters budget shared across both stages, mirroring
    # simplex.solve_two_phase's shared step counter)
    state, orig, budget = run_stage(state, orig, "p1", backend.run_phase1,
                                    pending_p1, max_iters)
    state = backend.limit_phase1(state)

    # ---- one-shot column/row compaction + stage 2 ---------------------------
    state = backend.compact_columns(state)
    state, orig, _ = run_stage(state, orig, "p2", backend.run_phase2,
                               pending_p2, budget)

    flush(state, orig, "p2")
    stats = None
    if tel_host is not None:
        stats = report_from_counters(
            tel_host, wall_s=time.perf_counter() - t_start,
            backend=type(backend).__name__,
            spans=tuple(tracer.roots) if tracer is not None else ())
    return LPResult(x=out_x, objective=out_obj, status=out_status,
                    iterations=out_iters, y=duals["y"], z=duals["z"],
                    stats=stats)


def solve_batched_compacted(batch: LPBatch, *, dtype=jnp.float32,
                            tol: Optional[float] = None,
                            feas_tol: Optional[float] = None,
                            max_iters: Optional[int] = None,
                            segment_k: Optional[int] = None,
                            compact_threshold: Optional[float] = None,
                            pricing: str = "dantzig",
                            backend: str = "tableau",
                            stats_out: Optional[List[SegmentStat]] = None,
                            presolve: bool = True,
                            scale: Optional[bool] = None,
                            warm: WarmStart | None = None,
                            telemetry: bool = False,
                            tracer=None, mesh=None) -> LPResult:
    """Solve a batch with the two-level work-elimination engine (phase
    compaction + active-set compaction scheduler) on the pure-JAX backend.
    Accepts a GeneralLPBatch like every solver entry point (canonicalize on
    ingestion, recover on the way out).

    Bit-identical statuses/iterations to ``solve_batched_jax`` with the same
    ``pricing`` rule — only the executed device work changes.
    ``segment_k=None`` derives the segment length from `auto_segment_k`
    (scales with the `default_max_iters` cap); ``compact_threshold=None``
    derives the gather eagerness from `auto_compact_threshold` (tuned from
    the observed survivor curves).  ``stats_out`` (a list) collects
    per-segment SegmentStat records — executed work plus the observed
    survivor curve — for benchmarks/pivot_work.py.

    ``backend`` selects the solver engine under the scheduler: "tableau"
    (this module's JaxBackend), "revised" or "pdhg" route to the engine's
    own compacted entry point via the core/lp.py registry.

    ``warm`` seeds the initial state (warm-derived leaves then ride the
    bucket gathers automatically); compacted results report ``warm=None``
    (no terminal-state capture across the retirement buckets).

    ``mesh`` runs the segments under shard_map over its devices
    (`ShardMapBackend`, ``solve_shard_map(segment_k=)``)."""
    if canonicalize_backend(backend) != "tableau":
        return resolve_backend(backend, compacted=True)(
            batch, dtype=dtype, tol=tol, feas_tol=feas_tol,
            max_iters=max_iters, segment_k=segment_k,
            compact_threshold=compact_threshold, pricing=pricing,
            stats_out=stats_out, presolve=presolve, scale=scale, warm=warm,
            telemetry=telemetry, tracer=tracer, mesh=mesh)
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
        JaxBackend(m, n, tol, feas_tol, dtype, pricing=pricing), batch, mesh)
    with span("lp.dispatch", tracer, backend="tableau", B=batch.batch, m=m,
              n=n):
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
    res = run_schedule(backend, state, orig, B, n, max_iters=int(max_iters),
                       config=cfg, stats_out=stats_out, tracer=tracer)
    return finish_result(rec, res, tracer=tracer)


# ---------------------------------------------------------------------------
# Frontier refill: continuous batching over a work producer
# ---------------------------------------------------------------------------

class FrontierScheduler:
    """Continuous-batching counterpart of `run_schedule`: where the bucket
    ladder only ever *shrinks* a fixed batch, this scheduler keeps a fixed
    pool of ``lanes`` batch slots and **admits new LPs into lanes freed by
    retired ones** — the same gather machinery, run in reverse.

    Built for producers that generate work *from results*: the
    branch-and-bound driver (core/branch_bound.py) retires fathomed nodes
    and pushes their freshly-branched children, which the scheduler admits
    mid-solve — the device batch never drains below the available work, so
    a 2-node frontier does not serialize a 64-lane dispatch.

    Segments run the *combined* two-phase pivot on the full tableau
    (`segment_combined`) and never column-compact: a lane must accept a
    cold or warm newcomer at any segment boundary, and a newcomer starts
    in phase 1, which the phase-compacted layout cannot represent.  The
    per-lane pivot sequence is still bit-identical to the monolithic
    lockstep solver — admission scatters never touch other lanes'
    tableaux.

    Protocol (all arrays canonical-standard-form, batch axis 0):

    * ``source(k)`` — up to ``k`` new LPs, or ``None`` when no work is
      currently available: a tuple ``(A, b, c, ub, warm, tags)`` with
      ``j <= k`` members; ``warm`` is a j-member ``WarmStart`` or None;
      ``tags`` are nonnegative ints identifying each LP.
    * ``sink(tag, row)`` — called once per retired LP with a dict holding
      ``x``/``objective``/``status``/``iterations``/``y``/``z`` (the
      monolithic extraction contract) plus ``warm``, a 1-member
      ``WarmStart`` carrying the lane's terminal basis/flip state — the
      carrier children warm-start from.  ``sink`` may push work that a
      subsequent ``source`` call returns.

    ``run`` drives segments until every lane is free and ``source`` is
    exhausted; per-LP pivots are capped at ``max_iters`` (over-budget
    lanes retire as ITERATION_LIMIT), so it always terminates.
    """

    def __init__(self, m: int, n: int, *, lanes: int = 32,
                 dtype=jnp.float32, tol: Optional[float] = None,
                 feas_tol: Optional[float] = None,
                 max_iters: Optional[int] = None,
                 segment_k: Optional[int] = None,
                 pricing: str = "dantzig",
                 stats_out: Optional[List[SegmentStat]] = None,
                 tracer=None):
        if lanes < 1:
            raise ValueError(f"lanes must be >= 1, got {lanes}")
        self.m, self.n = int(m), int(n)
        self.lanes = next_bucket(int(lanes))
        self.dtype = dtype
        if tol is None:
            tol = 1e-6 if dtype == jnp.float32 else 1e-9
        if feas_tol is None:
            feas_tol = 1e-5 if dtype == jnp.float32 else 1e-7
        self.max_iters = int(max_iters if max_iters is not None
                             else default_max_iters(self.m, self.n))
        self.segment_k = int(segment_k if segment_k is not None
                             else auto_segment_k(self.m, self.n))
        self.stats_out = stats_out
        self.tracer = tracer
        self.backend = JaxBackend(self.m, self.n, tol, feas_tol, dtype,
                                  pricing=pricing)

    def _admit(self, state, tags, source):
        be = self.backend
        free = np.flatnonzero(tags < 0)
        if not len(free):
            return state, tags
        req = source(len(free))
        if req is None:
            return state, tags
        A, b, c, ub, warm, new_tags = req
        A = jnp.asarray(np.asarray(A), self.dtype)
        j = A.shape[0]
        if j > len(free) or j != len(new_tags):
            raise ValueError(f"source returned {j} LPs / {len(new_tags)} "
                             f"tags for {len(free)} free lanes")
        new_state = be.init(
            A, jnp.asarray(np.asarray(b), self.dtype),
            jnp.asarray(np.asarray(c), self.dtype),
            ub=None if ub is None else jnp.asarray(np.asarray(ub), self.dtype),
            warm=warm)
        if state is None:
            # bootstrap: replicate to fill all lanes, deactivate the padding
            if j < self.lanes:
                new_state = be.take(new_state, np.arange(self.lanes) % j)
                new_state = be.deactivate(new_state, np.arange(self.lanes) < j)
            state = new_state
            tags[:j] = new_tags
        else:
            idx = free[:j]
            state = be.scatter(state, new_state, idx)
            tags[idx] = new_tags
        if self.tracer is not None:
            self.tracer.event("admit", lps=int(j),
                              tags=[int(t) for t in new_tags],
                              occupied=int((tags >= 0).sum()),
                              lanes=self.lanes)
        return state, tags

    def run(self, source, sink) -> int:
        """Drain ``source`` through the lane pool; returns LPs retired."""
        be = self.backend
        tags = np.full(self.lanes, -1, np.int64)
        state = None
        retired = 0
        while True:
            state, tags = self._admit(state, tags, source)
            active = tags >= 0
            if not active.any():
                return retired
            with span("lp.segment[frontier]", self.tracer,
                      lanes=self.lanes, occupied=int(active.sum())) as sp:
                state, done = be.run_combined(state, self.segment_k)
                sp.set(steps=int(done))
            status = be.status_host(state)
            # per-LP budget: over-budget lanes retire as ITERATION_LIMIT
            over = (active & (status == _RUNNING)
                    & (np.asarray(state.iters).reshape(-1) >= self.max_iters))
            if over.any():
                state = be.deactivate(state, ~over)
                status = np.where(over, ITERATION_LIMIT, status)
            if self.stats_out is not None:
                self.stats_out.append(SegmentStat(
                    stage="frontier", bucket=self.lanes, steps=done,
                    elements=done * self.lanes * be.elements_per_step("p1"),
                    survivors=int((active & (status == _RUNNING)).sum())))
            done_mask = active & (status != _RUNNING)
            if done_mask.any():
                x, obj, st, it, y, z = be.extract(state, "p1")
                basis = np.asarray(state.basis)
                flip = np.asarray(state.flip)
                for i in np.flatnonzero(done_mask):
                    if self.tracer is not None:
                        self.tracer.event("retire", tag=int(tags[i]),
                                          lane=int(i), status=int(st[i]),
                                          iterations=int(it[i]))
                    sink(int(tags[i]), {
                        "x": x[i], "objective": obj[i],
                        "status": int(st[i]), "iterations": int(it[i]),
                        "y": y[i], "z": z[i],
                        "warm": WarmStart(m=self.m, n=self.n,
                                          basis=basis[i:i + 1],
                                          at_upper=flip[i:i + 1])})
                    retired += 1
                tags[done_mask] = -1
