"""Multi-chip batched LP solving: the mesh, and per-shard termination.

The paper gets load balancing from CUDA's block scheduler: each LP's block
exits as soon as *its* simplex terminates.  One lockstep while loop over a
batch spread across chips would lose that, every chip pivoting until the
globally slowest LP finishes.  Here LPs are independent, so a mesh splits
only the batch axis, over every mesh axis (the sharding rule,
`transfer.batch_spec`), and every chip runs its own while loop over its
own LPs under ``shard_map``: a chip whose LPs have converged goes idle (the
TPU analogue of per-block exit).  There is no cross-chip communication.

``solve_shard_map`` without ``segment_k`` is `batching.solve_batched` over
the mesh: it canonicalises, plans chunks against every chip's memory,
solves each chunk with its engine's program under ``shard_map``
(`transfer.sharded_solve`), recovers, and writes the ``lp.*`` spans as the
one-chip path does.  With ``segment_k=K`` it is the engine's compaction
scheduler (core/compaction.py) with its segments under ``shard_map``
(`compaction.ShardMapBackend`): each chip runs its local while loop for up
to K pivots, the host counts global survivors, and when the active
fraction drops below ``compact_threshold`` the surviving LPs are gathered
into the next power-of-two bucket (padded to the device count) — per-shard
exit *within* a segment, per-block exit *across* segments.  Called without
a mesh, ``solve_shard_map`` builds ``make_mesh()``: every local device on
one ``("data",)`` axis.
"""
from __future__ import annotations

import contextlib
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh

from . import transfer
from .batching import solve_batched
from .lp import LPBatch, canonicalize_backend, resolve_backend


def make_mesh(shape: Optional[Tuple[int, ...]] = None,
              axes: Tuple[str, ...] = ("data",)) -> Mesh:
    """A mesh over the first ``prod(shape)`` devices; with no ``shape``,
    every device on one axis (the data-parallel mesh ``solve_shard_map``
    builds when it is given none)."""
    if shape is None:
        shape = (len(jax.devices()),)
    devices = np.asarray(jax.devices()[: int(np.prod(shape))]).reshape(shape)
    return Mesh(devices, axes)


def solve_shard_map(batch: LPBatch, mesh: Optional[Mesh] = None, *,
                    dtype=jnp.float32,
                    tol: Optional[float] = None,
                    feas_tol: Optional[float] = None,
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

    ``segment_k=None`` (default) is the one-shot solve:
    ``solve_batched(batch, mesh=mesh)``, so the batch is canonicalised,
    planned against every chip's memory (``device_bytes`` per chip, default
    the limit the first device reports) and run in chunks, each spread over
    the mesh.  ``segment_k=K`` runs the solve in K-pivot segments through
    the engine's active-set compaction scheduler (see module docstring);
    results are identical, work shrinks with the survivor count
    (``compact_threshold=None`` derives the gather eagerness from
    `auto_compact_threshold`).  ``pricing`` selects the entering-column rule
    (core/pricing.py) in both modes, ``backend`` the engine, and the
    engine decides the defaults of ``tol``, ``feas_tol`` and
    ``max_iters`` as it does on one chip.  GeneralLPBatch inputs
    canonicalize on the host before sharding and recover after the gather.
    ``lower_only=True`` returns the one-shot mesh program of the first
    chunk, lowered and not run."""
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
    kw = dict(dtype=dtype, tol=tol, feas_tol=feas_tol, max_iters=max_iters,
              pricing=pricing, presolve=presolve, scale=scale,
              telemetry=telemetry, mesh=mesh)
    if backend == "revised":
        kw["refactor_period"] = refactor_period
    if segment_k is not None:
        return resolve_backend(backend, compacted=True)(
            batch, segment_k=segment_k, compact_threshold=compact_threshold,
            stats_out=stats_out, tracer=tracer, **kw)
    kw.update(backend=backend, device_bytes=device_bytes)
    if lower_only:
        return transfer.lower(solve_batched, batch, **kw)
    with (tracer.active() if tracer is not None
          else contextlib.nullcontext()):
        return solve_batched(batch, **kw)
