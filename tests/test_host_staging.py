"""The host staging buffer (`transfer.HostStaging`): the solve path casts its
inputs into one buffer kept across calls, byte for byte as the fresh cast
(`transfer._host_cast`) would, and says on ``lp.h2d.cast`` whether it
reused the buffer, grew it, or cast into fresh memory because another call
held it."""
import threading

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (LPBatch, random_lp_batch, simplex, solve_batched_jax,
                        transfer)
from repro.core.transfer import HostStaging, _host_cast, _upper_bounds
from repro.obs import SpanTracer

F32, I32 = np.dtype(np.float32), np.dtype(np.int32)
_rng = np.random.default_rng(11)
_lp = random_lp_batch(_rng, 5, 4, 3)
_bounded = LPBatch(A=_lp.A, b=_lp.b, c=_lp.c,
                   ub=np.where(_rng.random((5, 3)) < 0.5, np.inf,
                               _rng.uniform(1, 9, (5, 3))))

# each case is one call's (array, dtype) pairs, cast together
CASTS = {
    "A_b_c_and_no_ub": [(_lp.A, F32), (_lp.b, F32), (_lp.c, F32),
                        (_upper_bounds(_lp), F32)],
    "int_b_and_ub_given": [(_lp.A, F32), (np.arange(-4, 16).reshape(5, 4),
                                          F32),
                           (_upper_bounds(_bounded), F32)],
    "warm_leaves": [(_rng.integers(0, 7, (5, 4)), I32),
                    (_rng.integers(0, 2, (5, 3)).astype(np.int8),
                     np.dtype(bool)),
                    (_rng.random((5, 7)), F32)],
    "not_staged": [(_lp.A.astype(np.float32), F32),
                   (jnp.arange(4.0), F32),
                   ([[1, 2], [3, 4]], I32)],
}


def _bytes(a):
    return np.ascontiguousarray(a).view(f"u{a.dtype.itemsize}")


@pytest.mark.parametrize("case", sorted(CASTS))
def test_staged_cast_is_the_fresh_cast(case):
    pairs = CASTS[case]
    staging = HostStaging()
    infos = []
    # a first call of the same shapes grows the buffer with other values;
    # the second reuses it and must leave none of them behind
    for leaves in ([(np.zeros_like(np.asarray(a)), t) if
                    isinstance(a, np.ndarray) else (a, t) for a, t in pairs],
                   pairs):
        with staging.lease() as cast:
            host, info = cast(leaves)
        infos.append(info)
    staged = 0
    for (a, t), h in zip(pairs, host):
        want = _host_cast(a, t)
        if not isinstance(a, np.ndarray) or a.dtype == t:
            # not staged: exactly what the fresh cast gives
            assert (h is a) == (want is a)
            np.testing.assert_array_equal(np.asarray(h), np.asarray(want))
            continue
        assert h.dtype == want.dtype and h.shape == want.shape
        assert np.array_equal(_bytes(h), _bytes(want))
        assert h.ctypes.data % 64 == 0
        staged += want.nbytes
    grown = "grown" if staged else "reused"
    assert infos == [{"staging": grown, "staged_bytes": staged},
                     {"staging": "reused", "staged_bytes": staged}]


FIELDS = ("x", "objective", "status", "iterations", "y", "z")


def _solve(batch, backend="tableau"):
    """The result and the ``lp.h2d.cast`` args of one solve."""
    tracer = SpanTracer()
    with tracer.active():
        res = solve_batched_jax(batch, backend=backend)
    (cast,) = [s for root in tracer.roots for s in root.walk()
               if s.name == "lp.h2d.cast"]
    return res, cast.args


def _fresh(batch, backend):
    """The result of the fresh cast: the buffer held by another call."""
    with transfer._HOST_STAGING.lease():
        res, args = _solve(batch, backend)
    assert args == {"staging": "fresh", "staged_bytes": 0}
    return res


def _same(a, b):
    for f in FIELDS:
        assert np.array_equal(getattr(a, f), getattr(b, f), equal_nan=True), f


def _two_threads(batches, monkeypatch):
    """The first batch's solve holds the buffer, stopped before its
    dispatch, while a second thread solves the second batch."""
    real = simplex._solve_core_state
    holder, held, go = {}, threading.Event(), threading.Event()

    def gated(*args, **kwargs):
        if threading.current_thread() is holder["thread"]:
            held.set()
            assert go.wait(timeout=120)
        return real(*args, **kwargs)

    monkeypatch.setattr(simplex, "_solve_core_state", gated)
    out = [None, None]

    def run(k):
        out[k] = _solve(batches[k])

    holder["thread"] = threading.Thread(target=run, args=(0,))
    other = threading.Thread(target=run, args=(1,))
    holder["thread"].start()
    try:
        assert held.wait(timeout=120)
        other.start()
        other.join(timeout=120)
    finally:
        go.set()
        holder["thread"].join(timeout=120)
    assert not holder["thread"].is_alive() and not other.is_alive()
    return out


SCENARIOS = {
    # (batch sizes in call order, the staging each call reads, the engine)
    "same_shape": ((24, 24), ["grown", "reused"], "tableau"),
    "smaller_after_larger": ((40, 16), ["grown", "reused"], "tableau"),
    "two_threads": ((24, 24), ["grown", "fresh"], "tableau"),
    "revised_smaller_after_larger": ((40, 16), ["grown", "reused"],
                                     "revised"),
    "pdhg_smaller_after_larger": ((40, 16), ["grown", "reused"], "pdhg"),
}


@pytest.mark.parametrize("scenario", sorted(SCENARIOS))
def test_solve_stages_its_cast(scenario, monkeypatch):
    sizes, want, backend = SCENARIOS[scenario]
    batches = [random_lp_batch(np.random.default_rng(20 + k), B, 6, 5)
               for k, B in enumerate(sizes)]
    refs = [_fresh(b, backend) for b in batches]
    monkeypatch.setattr(transfer, "_HOST_STAGING", HostStaging())
    if scenario == "two_threads":
        out = _two_threads(batches, monkeypatch)
    else:
        out = []
        for b in batches:
            out.append(_solve(b, backend))
            if len(out) == 1:
                kept = {f: getattr(out[0][0], f).copy() for f in FIELDS}
        # the second call leaves the first call's result as it was
        for f in FIELDS:
            assert np.array_equal(getattr(out[0][0], f), kept[f],
                                  equal_nan=True), f
    assert [args["staging"] for _, args in out] == want
    for (res, _), ref in zip(out, refs):
        _same(res, ref)
