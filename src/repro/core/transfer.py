"""Host <-> device movement for the one-shot solves: one path for every
engine, on one chip or over a mesh.

An engine hands `run` its jitted program and its inputs as ``(array,
dtype)`` leaves.  `run` casts them on the host into a buffer kept across
calls (`HostStaging`), puts them on the device, dispatches the program,
waits, and fetches every output as a host NumPy array, under the
``lp.h2d`` (``.cast``, ``.put``), ``lp.dispatch``, ``lp.wait`` and
``lp.d2h`` spans.

The sharding rule: LPs are independent, so a mesh splits the batch axis
over every mesh axis and nothing else (`batch_spec`).  `shard` puts a
per-shard body under ``shard_map`` by that rule: each chip runs its own
while loop over its own LPs, with no cross-chip communication.
`sharded_solve` is the one-shot solve of any engine over a mesh.
"""
from __future__ import annotations

import contextlib
import contextvars
import functools
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..obs.report import report_from_counters
from ..obs.telemetry import tel_to_numpy
from ..obs.trace import span
from .lp import LPBatch, LPResult


def _host_cast(a, dtype):
    """The host half of ``jnp.asarray(a, dtype)``: NumPy's cast of host
    input (the input itself where it already has ``dtype``); device
    arrays pass through."""
    return a if isinstance(a, jax.Array) else np.asarray(a, dtype=dtype)


def _upper_bounds(batch: LPBatch) -> np.ndarray:
    """``batch.upper_bounds()`` without building its float64 +inf array
    when the batch has no bounds: a read-only broadcast of +inf with the
    same shape, dtype and ``nbytes``, which the cast fills from."""
    if batch.ub is None:
        return np.broadcast_to(np.inf, (batch.batch, batch.n))
    return batch.upper_bounds()


_ALIGN = 64   # bytes: every staged array starts on a cache line


def _cast_fresh(pairs):
    """The cast of `HostStaging.lease` into fresh memory: `_host_cast`."""
    return ([_host_cast(a, t) for a, t in pairs],
            {"staging": "fresh", "staged_bytes": 0})


class HostStaging:
    """One host buffer, kept across calls, into which the solve path casts
    its inputs before it puts them on the device.

    Cast into a fresh array, the float32 copy of a large float64 batch is
    mapped anew on every call, and its first-touch page faults cost several
    times the conversion itself (the 314 MB A block of 100,000 28x28 LPs).
    Cast into this buffer, a call no larger than an earlier one writes into
    pages that are already there.  The buffer grows to the largest call's
    cast output and keeps that much host memory for the life of the
    process: the size each such call used to allocate for itself.
    """

    def __init__(self):
        self._buf = np.empty(0, np.uint8)
        self._lock = threading.Lock()

    @contextlib.contextmanager
    def lease(self):
        """The buffer for one call.  Yields ``cast``: ``cast(pairs)`` of
        ``(array, dtype)`` pairs returns the host arrays `_host_cast` would
        (byte for byte) and the ``lp.h2d.cast`` args ``staging``
        (``"reused"``, ``"grown"``, or ``"fresh"`` where another call holds
        the lease and the cast goes to fresh memory) and ``staged_bytes``.
        Hold the lease until the call's outputs are ready and its device
        inputs dropped: until then a transfer may still read the buffer
        (on the CPU a device array may be the buffer itself)."""
        if not self._lock.acquire(blocking=False):
            yield _cast_fresh
            return
        try:
            yield self._cast
        finally:
            self._lock.release()

    def _cast(self, pairs):
        # a host array of another dtype is staged; an array that has the
        # dtype, a device array or a list keeps `_host_cast`
        slots, end = [], 0
        for a, t in pairs:
            if isinstance(a, np.ndarray) and a.dtype != t:
                size = a.size * t.itemsize
                slots.append((end, size))
                end += -(-size // _ALIGN) * _ALIGN
            else:
                slots.append(None)
        start = -self._buf.ctypes.data % _ALIGN
        staging = "reused"
        if end and start + end > self._buf.size:
            self._buf = np.empty(end + _ALIGN, np.uint8)
            start, staging = -self._buf.ctypes.data % _ALIGN, "grown"
        host = []
        for (a, t), slot in zip(pairs, slots):
            if slot is None:
                host.append(_host_cast(a, t))
                continue
            off, size = start + slot[0], slot[1]
            out = self._buf[off:off + size].view(t).reshape(a.shape)
            np.copyto(out, a, casting="unsafe")
            host.append(out)
        staged = sum(slot[1] for slot in slots if slot is not None)
        return host, {"staging": staging, "staged_bytes": staged}


_HOST_STAGING = HostStaging()


def _put(a, host, dtype):
    """The rest of ``jnp.asarray(a, dtype)`` once ``host = _host_cast(a,
    dtype)``: the same calls, so the same transfer and device ops."""
    if host is a:
        return jnp.asarray(a, dtype)
    return jax.lax.convert_element_type(host, dtype)


def batch_spec(mesh: Mesh) -> P:
    """The sharding rule: the batch axis over every mesh axis."""
    return P(tuple(mesh.axis_names))


def put_sharded(host, mesh: Mesh, dtype):
    """Place a host-cast array batch-sharded over ``mesh``, so each device
    receives only its own LPs, then give it ``dtype`` on the device."""
    d = jax.device_put(host, NamedSharding(mesh, batch_spec(mesh)))
    return d if d.dtype == dtype else d.astype(dtype)


def shard(body, mesh: Mesh, in_specs):
    """``body`` under ``shard_map`` over ``mesh``, jitted: every result is
    split by `batch_spec`, each argument as ``in_specs`` says."""
    return jax.jit(jax.shard_map(body, mesh=mesh, in_specs=in_specs,
                                 out_specs=batch_spec(mesh),
                                 check_vma=False))


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


def lp_leaves(batch: LPBatch, dtype) -> list:
    """The leaves every engine takes first: A, b, c and the upper bounds
    (`_upper_bounds`), each to be cast to ``dtype``."""
    return [(batch.A, dtype), (batch.b, dtype), (batch.c, dtype),
            (_upper_bounds(batch), dtype)]


def run(program, leaves, *, mesh: Mesh | None = None, **span_args):
    """Cast, put, dispatch, wait and fetch one chunk.

    ``leaves`` are the program's positional inputs as ``(array, dtype)``
    pairs; a ``None`` leaf is passed to the program as ``None``.  On one
    chip each leaf is put as ``jnp.asarray(array, dtype)`` would put it
    (`_put`); over ``mesh`` it is split by `batch_spec` (`put_sharded`),
    and the spans carry ``shards``.  ``span_args`` go on ``lp.dispatch``.
    Returns the program's outputs as host NumPy arrays, in the program's
    output structure, and the seconds from dispatch to ready."""
    shards = {} if mesh is None else {"shards": mesh.size}
    given = [leaf for leaf in leaves if leaf is not None]
    with _HOST_STAGING.lease() as cast:
        with span("lp.h2d", **shards) as h2d:
            with span("lp.h2d.cast") as cast_span:
                host, cast_args = cast(given)
                cast_span.set(**cast_args)
            with span("lp.h2d.put"):
                if mesh is None:
                    dev = [_put(a, h, t) for (a, t), h in zip(given, host)]
                else:
                    dev = [put_sharded(h, mesh, t)
                           for (_, t), h in zip(given, host)]
            h2d.set(bytes_in=sum(getattr(a, "nbytes", 0) for a, _ in given),
                    bytes_out=sum(h.nbytes for h in host))
        # free the host copies now, as jnp.asarray frees its temporaries
        del given, host
        put = iter(dev)
        args = [None if leaf is None else next(put) for leaf in leaves]
        t0 = time.perf_counter()
        with span("lp.dispatch", **span_args, **shards):
            out = program(*args)
        with span("lp.wait"):
            jax.block_until_ready(out)
        # the outputs are ready, so every input they read has been read:
        # the staging buffer may take the next call's inputs
        del dev, put, args
    wall_s = time.perf_counter() - t0
    with span("lp.d2h") as d2h:
        fetched = jax.tree.map(np.asarray, out)
        arrays = jax.tree.leaves(fetched)
        args = {"arrays": len(arrays), "bytes": sum(a.nbytes for a in arrays)}
        if mesh is not None:
            # every engine returns the iterations fourth; each chip's
            # contiguous block ran as many pivots as its largest count
            args["shard_max_iters"] = fetched[3].reshape(
                mesh.size, -1).max(axis=1).tolist()
        d2h.set(**args)
    return fetched, wall_s


@functools.lru_cache(maxsize=None)
def _sharded_program(mesh: Mesh, body, settings: tuple):
    """``body`` with its static ``settings`` under `shard`, taking the four
    `lp_leaves`.  Built once per mesh, body and settings, so repeated calls
    reuse the program."""
    spec = batch_spec(mesh)
    return shard(functools.partial(body, **dict(settings)), mesh,
                 (spec,) * 4)


_LOWERING: contextvars.ContextVar = contextvars.ContextVar(
    "repro_lowering", default=False)


class _Lowered(Exception):
    """Carries a lowered mesh program out of a solve (`lower`)."""

    def __init__(self, lowered):
        super().__init__("lowered")
        self.lowered = lowered


def lower(solve, *args, **kwargs):
    """The first mesh program ``solve(*args, **kwargs)`` would run, lowered
    for its inputs' shapes and not run (``solve_shard_map(lower_only=True)``).
    """
    token = _LOWERING.set(True)
    try:
        solve(*args, **kwargs)
    except _Lowered as e:
        return e.lowered
    finally:
        _LOWERING.reset(token)
    raise RuntimeError("the solve ran no mesh program to lower")


def sharded_solve(body, batch: LPBatch, mesh: Mesh, dtype, *, backend: str,
                  **settings) -> LPResult:
    """One chunk of an engine's one-shot solve over ``mesh``: pad to a
    multiple of the chip count (`_pad_batch`), `run` the engine's traceable
    ``body`` (its static ``settings`` bound) under `shard`, drop the
    padding.  Every engine's body returns ``(x, objective, status,
    iterations, y, z)``, then its telemetry lanes when
    ``settings["telemetry"]``; no warm start is captured."""
    padded, B = _pad_batch(batch, mesh.size)
    program = _sharded_program(mesh, body, tuple(sorted(settings.items())))
    leaves = lp_leaves(padded, dtype)
    if _LOWERING.get():
        raise _Lowered(program.lower(
            *(jax.ShapeDtypeStruct(np.shape(a), t) for a, t in leaves)))
    out, wall_s = run(program, leaves, mesh=mesh, B=padded.batch, m=batch.m,
                      n=batch.n)
    x, obj, status, iters, y, z = (a[:B] for a in out[:6])
    stats = None
    if settings.get("telemetry"):
        stats = report_from_counters(
            {k: v[:B] for k, v in tel_to_numpy(out[6]).items()},
            wall_s=wall_s, backend=backend)
    return LPResult(x=x, objective=obj, status=status, iterations=iters,
                    y=y, z=z, stats=stats)
