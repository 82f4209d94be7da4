"""Batching routine — Algorithm 1 of the paper, adapted to HBM + async dispatch.

The paper sizes batches against GPU global memory (``N = floor(S / Y)``,
Eq. 5) and overlaps H2D/D2H copies with kernel execution via CUDA streams
(Sec. 5.4). Here:

* the memory budget is the HBM limit each device reports
  (``memory_stats()["bytes_limit"]``) x device count, spent per LP by the
  *compiled* program's footprint, not by one tableau,
* chunks run one after another, with no overlap: the default engine
  (``solve_batched_jax``) returns host arrays, so chunk *k* has been put,
  solved and fetched before chunk *k+1*'s transfer starts.  The paper's
  overlap of one chunk's H2D with another's solve is not built (ROADMAP
  A9); the ``chunk`` arg of the ``lp.*`` spans shows the order in a
  profiler trace,
* the chunks' host results are concatenated at the end.

One call is an ``lp.solve`` span (``obs.trace``) holding the
canonicalisation, the plan and each chunk's spans.
"""
from __future__ import annotations

import inspect
import itertools
import math
from typing import Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..obs.report import SolveReport
from ..obs.trace import span, tagged
from .compaction import solve_batched_compacted
from .forms import ensure_canonical, finish_result, prepare_warm
from .lp import (LPBatch, LPResult, WarmStart, canonicalize_backend,
                 resolve_backend)
from .simplex import solve_batched_jax

# Fraction of the device limit one chunk's program may claim: the rest
# holds the next chunk's inputs in flight and allocator slack.
BUDGET_FRACTION = 0.6
# Compiled device bytes per LP over LPBatch.bytes_per_lp (one tableau), per
# engine: the largest ratio memory_analysis() reports for the monolithic
# programs compiled for a v5e at 28x28 and 100x100 with B from 2048 to
# 100,000 (tableau 6.6, revised 9.8, pdhg 1.3), rounded up.  The loop
# carries two tableaux, phase compaction a third, and the ratio test
# (B, m, C) temporaries; tests/test_tpu_compile.py checks a planned chunk.
PROGRAM_BYTES_FACTOR = {"tableau": 8, "revised": 10, "pdhg": 2}
# the solve_id of each call's lp.solve span
_SOLVE_IDS = itertools.count()


def device_memory_bytes(device=None) -> Optional[int]:
    """The HBM limit the device reports (``memory_stats()["bytes_limit"]``).
    None where the backend reports no limit (the CPU, whose host memory is
    not planned); a TPU that reports none is an error, never a guess."""
    device = device or jax.devices()[0]
    limit = (device.memory_stats() or {}).get("bytes_limit")
    if limit is None:
        if device.platform == "tpu":
            raise RuntimeError(
                f"{device.device_kind} reports no memory_stats()"
                "['bytes_limit']; pass device_bytes= to plan chunks")
        return None
    return int(limit)


def max_chunk_size(batch: LPBatch, device_bytes: int, n_devices: int = 1,
                   dtype_size: int = 4, backend: str = "tableau") -> int:
    """Paper Eq. (5): N = floor(S / Y), with S = usable device bytes and
    Y = the engine's compiled bytes per LP."""
    usable = int(device_bytes * BUDGET_FRACTION) * n_devices
    per_lp = batch.bytes_per_lp(dtype_size) * PROGRAM_BYTES_FACTOR[
        canonicalize_backend(backend)]
    return max(1, usable // per_lp)


def difficulty_proxy(batch: LPBatch) -> np.ndarray:
    """Cheap per-LP difficulty estimate for sorted batching: LPs needing
    phase 1 (any b_i < 0) pivot roughly 2x as long as feasible-start ones, so
    grouping them keeps each lockstep chunk's max-iteration bound tight.

    Primary key: the count of infeasible rows (each one seeds an artificial
    that phase 1 must drive out).  Tie-break (a strictly sub-unit fraction,
    so it never reorders across counts): relative infeasibility mass — LPs
    starting deeper in the infeasible region tend to take more phase-1
    pivots.  With ``compaction=True`` this ordering is what makes buckets
    drain in waves: each chunk's survivor curve collapses together, so the
    power-of-two ladder shrinks early and often."""
    b = np.asarray(batch.b)
    neg = b < 0
    count = neg.sum(axis=1).astype(np.float64)
    mass = np.where(neg, -b, 0.0).sum(axis=1)
    frac = mass / (1.0 + mass.max()) if mass.max() > 0 else 0.0
    return count + frac


def solve_batched(batch: LPBatch, *, solver: Optional[Callable] = None,
                  chunk_size: Optional[int] = None,
                  device_bytes: Optional[int] = None,
                  mesh=None, sort_by_difficulty: bool = False,
                  compaction: bool = False, pricing: str = "dantzig",
                  backend: str = "tableau",
                  presolve: bool = True, scale: Optional[bool] = None,
                  warm: Optional[WarmStart] = None,
                  pad_to_bucket: bool = False,
                  **solver_kwargs) -> LPResult:
    """Chunked batched solve (Algorithm 1). ``solver`` defaults to the pure
    JAX lockstep solver; kernels.ops.solve_batched_pallas is drop-in.

    ``mesh`` spreads every chunk over the mesh's devices, each with its own
    while loop (the engines' ``mesh=``, `transfer.sharded_solve`), and
    plans chunks against all of their memory; it takes the built-in
    one-shot engines only: no ``solver``, ``compaction`` or ``warm``.

    ``sort_by_difficulty`` (beyond-paper optimization): lockstep SIMD chunks
    pay max-pivots-over-chunk; reordering LPs by ``difficulty_proxy`` so
    similar-difficulty problems share a chunk cuts total executed pivots
    (measured in analysis/lp_perf.py), then results are unpermuted.

    ``compaction=True`` routes each chunk through the active-set compaction
    scheduler (core/compaction.py): dead LPs are retired into power-of-two
    buckets mid-solve instead of burning masked pivots.  With ``solver=None``
    the solver becomes ``solve_batched_compacted``; a custom ``solver`` must
    accept a ``compaction`` kwarg itself (e.g. solve_batched_pallas) or a
    ValueError is raised.  Composes with sorting: sorted chunks converge in
    tighter waves, which is exactly what the bucket ladder exploits — the
    difficulty pre-pass makes buckets drain in waves instead of dribbling.
    Pass ``segment_k=``/``compact_threshold=`` through ``solver_kwargs`` to
    tune.

    ``pricing`` selects the entering-column rule (core/pricing.py) and is
    forwarded to the solver; a custom ``solver`` must accept it when a
    non-default rule is requested.

    ``backend`` selects the solver engine — "tableau" (dense rank-1 tableau
    updates) or "revised" (core/revised.py basis-factor updates); with
    ``solver=None`` it picks the matching compacted/monolithic solver, and a
    custom ``solver`` must accept a ``backend`` kwarg when "revised" is
    requested (solve_batched_pallas does).

    ``chunk_size=None`` plans chunks from ``device_bytes`` (default: the
    limit the first device reports, `device_memory_bytes`; per device on a
    mesh) and the engine's compiled bytes per LP; a batch that does not fit
    is split into equal chunks, so every chunk runs one compiled program.

    A ``GeneralLPBatch`` (core/forms.py) is canonicalized *once* up front —
    chunking, sorting and memory planning all operate on the canonical
    shape (Eq. 5 budgets the canonical tableau) — and the concatenated
    result is recovered into original coordinates at the end;
    ``presolve``/``scale`` control the canonicalization.

    ``telemetry=True`` (forwarded through ``solver_kwargs`` — every built-in
    engine accepts it) turns on the per-LP counter plane (``repro.obs``);
    each chunk's ``LPResult.stats`` SolveReport is concatenated, chunk
    results are unpermuted/unpadded alongside the other per-LP leaves, and
    the merged report lands on the returned ``LPResult.stats``.

    ``warm`` (core/lp.py WarmStart, usually ``parent.warm_start()``) seeds
    every engine from a parent solve; its per-LP leaves are permuted and
    chunk-sliced alongside ``A``/``b``/``c``, and chunk results' terminal
    states are re-concatenated/unpermuted so the returned ``LPResult.warm``
    chains into the next re-solve.

    ``pad_to_bucket=True`` pads the batch up to the next power of two by
    replicating members (results for the replicas are discarded, warm
    leaves ride along).  Callers that dispatch many variable-sized batches
    of one canonical shape — the branch-and-bound frontier loop — then
    compile one XLA program per pow2 bucket instead of one per batch size,
    at the cost of solving up to 2x LPs per dispatch (replicas terminate
    in lockstep with their originals, so wall-clock cost is near zero)."""
    with span("lp.solve", solve_id=next(_SOLVE_IDS), B=batch.batch,
              m=batch.m, n=batch.n):
        canonicalize_backend(backend)
        if mesh is not None and (solver is not None or compaction
                                 or warm is not None):
            raise ValueError(
                "mesh= runs the built-in one-shot engines: it takes no "
                "solver=, compaction=True or warm= (use "
                "solve_shard_map(segment_k=) for compaction over a mesh)")
        batch, rec = ensure_canonical(batch, presolve=presolve, scale=scale)
        warm = prepare_warm(warm, rec, batch)
        if solver is None:
            if backend != "tableau":
                # registry dispatch (core/lp.py BACKEND_REGISTRY): each engine
                # owns its monolithic and compaction-scheduled entry points
                solver = resolve_backend(backend, compacted=compaction)
            else:
                solver = (solve_batched_compacted if compaction
                          else solve_batched_jax)
            solver_kwargs["pricing"] = pricing
            if mesh is not None:
                solver_kwargs["mesh"] = mesh
        elif compaction or pricing != "dantzig" or backend != "tableau" \
                or warm is not None:
            # only introspect when a kwarg actually needs forwarding, so
            # non-introspectable callables keep working on the default path
            params = inspect.signature(solver).parameters
            has_varkw = any(p.kind is inspect.Parameter.VAR_KEYWORD
                            for p in params.values())
            if compaction:
                if "compaction" not in params and not has_varkw:
                    raise ValueError(
                        f"compaction=True but solver {getattr(solver, '__name__', solver)!r} "
                        "does not accept a 'compaction' kwarg; use solver=None "
                        "(solve_batched_compacted) or a compaction-aware solver such "
                        "as kernels.ops.solve_batched_pallas")
                solver_kwargs["compaction"] = True
            if pricing != "dantzig":
                if "pricing" in params or has_varkw:
                    solver_kwargs.setdefault("pricing", pricing)
                else:
                    raise ValueError(
                        f"pricing={pricing!r} requested but solver "
                        f"{getattr(solver, '__name__', solver)!r} does not accept "
                        "a 'pricing' kwarg; use solver=None or a pricing-aware "
                        "solver")
            if backend != "tableau":
                if "backend" in params or has_varkw:
                    solver_kwargs.setdefault("backend", backend)
                else:
                    raise ValueError(
                        f"backend={backend!r} requested but solver "
                        f"{getattr(solver, '__name__', solver)!r} does not accept "
                        "a 'backend' kwarg; use solver=None or a backend-aware "
                        "solver such as kernels.ops.solve_batched_pallas")
            if warm is not None and "warm" not in params and not has_varkw:
                raise ValueError(
                    f"warm= requested but solver "
                    f"{getattr(solver, '__name__', solver)!r} does not accept "
                    "a 'warm' kwarg; use solver=None or a warm-start-aware "
                    "solver")
        B = batch.batch
        perm = None
        if sort_by_difficulty and B > 1:
            perm = np.argsort(difficulty_proxy(batch), kind="stable")
            batch = LPBatch(A=np.asarray(batch.A)[perm],
                            b=np.asarray(batch.b)[perm],
                            c=np.asarray(batch.c)[perm],
                            ub=None if batch.ub is None
                            else np.asarray(batch.ub)[perm])
            if warm is not None:
                warm = warm.take(perm)
        unpad_B = None
        if pad_to_bucket and B > 1:
            Bp = 1 << (B - 1).bit_length()
            if Bp != B:
                idx = np.arange(Bp) % B
                batch = LPBatch(A=np.asarray(batch.A)[idx],
                                b=np.asarray(batch.b)[idx],
                                c=np.asarray(batch.c)[idx],
                                ub=None if batch.ub is None
                                else np.asarray(batch.ub)[idx])
                if warm is not None:
                    warm = warm.take(idx)
                unpad_B, B = B, Bp

        def call(i, sub, sub_warm):
            # warm is passed per-call (never via solver_kwargs) because each
            # chunk gets its own slice of the carrier
            with tagged(chunk=i):
                if sub_warm is not None:
                    return solver(sub, warm=sub_warm, **solver_kwargs)
                return solver(sub, **solver_kwargs)

        with span("lp.plan") as plan:
            if chunk_size is None:
                if device_bytes is None:
                    device_bytes = device_memory_bytes()
                chunk_size = B if device_bytes is None else max_chunk_size(
                    batch, device_bytes, 1 if mesh is None else mesh.size,
                    backend=backend)
                if chunk_size < B:
                    chunk_size = -(-B // -(-B // chunk_size))
            n_chunks = 1 if chunk_size >= B else math.ceil(B / chunk_size)
            plan.set(chunk_size=min(chunk_size, B), n_chunks=n_chunks)
        if n_chunks == 1:
            res = call(0, batch, warm)
            return finish_result(rec, _unpermute(_unpad(res, unpad_B), perm))

        pending = []
        for i in range(n_chunks):
            s, e = i * chunk_size, min((i + 1) * chunk_size, B)
            sub = LPBatch(A=batch.A[s:e], b=batch.b[s:e], c=batch.c[s:e],
                          ub=None if batch.ub is None else batch.ub[s:e])
            # the default engine returns host arrays, so this blocks until the
            # chunk is solved and fetched: the next chunk's transfer starts
            # after it (no overlap, ROADMAP A9)
            pending.append(call(i, sub, None if warm is None
                                else warm.slice(s, e)))

        def cat(field):
            vals = [getattr(r, field) for r in pending]
            if any(v is None for v in vals):  # a chunk without a certificate
                return None
            return np.concatenate([np.asarray(v) for v in vals])

        res = LPResult(x=cat("x"), objective=cat("objective"),
                       status=cat("status"), iterations=cat("iterations"),
                       y=cat("y"), z=cat("z"),
                       warm=WarmStart.concat([r.warm for r in pending]),
                       stats=SolveReport.concat([r.stats for r in pending]))
        return finish_result(rec, _unpermute(_unpad(res, unpad_B), perm))


def _unpad(res: LPResult, B) -> LPResult:
    """Drop the pad_to_bucket replica rows (no-op when B is None)."""
    if B is None:
        return res
    take = lambda a: None if a is None else np.asarray(a)[:B]  # noqa: E731
    return LPResult(x=take(res.x), objective=take(res.objective),
                    status=take(res.status), iterations=take(res.iterations),
                    y=take(res.y), z=take(res.z),
                    warm=None if res.warm is None else res.warm.slice(0, B),
                    stats=None if res.stats is None else res.stats.slice(0, B))


def _unpermute(res: LPResult, perm) -> LPResult:
    if perm is None:
        return res
    inv = np.empty_like(perm)
    inv[perm] = np.arange(len(perm))
    take = lambda a: None if a is None else np.asarray(a)[inv]  # noqa: E731
    return LPResult(x=take(res.x),
                    objective=take(res.objective),
                    status=take(res.status),
                    iterations=take(res.iterations),
                    y=take(res.y), z=take(res.z),
                    warm=None if res.warm is None else res.warm.take(inv),
                    stats=None if res.stats is None else res.stats.take(inv))
