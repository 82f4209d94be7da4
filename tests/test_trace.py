"""The library's spans (``repro.obs.trace``): one span function whose
events land in a ``jax.profiler`` trace, on the clock of the device's ops,
and in a ``SpanTracer`` when one is active.  ``solve_batched`` opens one
``lp.solve`` span per call with a span at each layer boundary under it."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (GeneralLPBatch, random_lp_batch, solve_batched,
                        solve_batched_compacted)
from repro.core.forms import ensure_canonical
from repro.core.transfer import _host_cast, _put
from repro.io.mps import fixture_path, perturbed_batch, read_mps
from repro.obs import SpanTracer, span, tagged

BATCHES = {
    "standard": random_lp_batch(np.random.default_rng(5), 24, 6, 6),
    "general": perturbed_batch(read_mps(fixture_path("afiro")), 8,
                               np.random.default_rng(6)),
}
# the children of lp.solve, in order, for one chunk
CHILDREN = {
    "standard": ["lp.plan", "lp.h2d", "lp.dispatch", "lp.wait", "lp.d2h"],
    "general": ["lp.canonicalize", "lp.plan", "lp.h2d", "lp.dispatch",
                "lp.wait", "lp.d2h", "lp.recover"],
}
GRANDCHILDREN = {
    "lp.h2d": ["lp.h2d.cast", "lp.h2d.put"],
    "lp.canonicalize": ["lp.canonicalize.presolve", "lp.canonicalize.build",
                        "lp.canonicalize.scale"],
}


def _inside(ev, outer):
    return outer[1] <= ev[1] and ev[2] <= outer[2] and ev is not outer


def _children(events, parent):
    """The events directly inside ``parent``, in start order."""
    inner = [e for e in events if _inside(e, parent)]
    return [e for e in inner if not any(_inside(e, o) for o in inner)]


# the arrays each engine's one-chip program returns: x, objective, status,
# iterations, y, z and its warm-start capture
FETCHED = {"tableau": 9, "revised": 8, "pdhg": 10}


def _check_args(kind, by_name, batch, backend="tableau"):
    B = batch.batch
    assert {"solve_id", "B", "m", "n"} <= set(by_name["lp.solve"])
    assert (by_name["lp.solve"]["B"], by_name["lp.solve"]["m"],
            by_name["lp.solve"]["n"]) == (B, batch.m, batch.n)
    assert by_name["lp.plan"]["chunk_size"] == B
    assert by_name["lp.plan"]["n_chunks"] == 1
    h2d = by_name["lp.h2d"]
    assert h2d["chunk"] == 0
    # float64 host inputs become float32 device arrays: half the bytes
    assert h2d["bytes_in"] == 2 * h2d["bytes_out"] > 0
    assert by_name["lp.dispatch"]["B"] == B
    assert by_name["lp.wait"]["chunk"] == 0
    assert by_name["lp.d2h"]["arrays"] == FETCHED[backend]
    assert by_name["lp.d2h"]["bytes"] > 0
    if kind == "general":
        can = by_name["lp.canonicalize"]
        assert (can["B"], can["m"], can["n"]) == (B, batch.m, batch.n)
        assert (can["m_can"], can["n_can"]) == (35, 32)
        assert by_name["lp.recover"]["B"] == B


@pytest.mark.parametrize("kind", sorted(BATCHES))
def test_solve_spans_in_profiler_trace(profile, kind):
    batch = BATCHES[kind]
    solve_batched(batch)                      # compile outside the trace

    def call():
        with jax.profiler.TraceAnnotation("caller"):
            solve_batched(batch)
    events = [e for e in profile(call)
              if e[0] == "caller" or e[0].startswith("lp.")]
    (caller,) = [e for e in events if e[0] == "caller"]
    (solve,) = [e for e in events if e[0] == "lp.solve"]
    assert _children(events, caller) == [solve]
    kids = _children(events, solve)
    assert [e[0] for e in kids] == CHILDREN[kind]
    for a, b in zip(kids, kids[1:]):
        assert a[2] <= b[1], f"{a[0]} overlaps {b[0]}"
    for parent, names in GRANDCHILDREN.items():
        if parent in CHILDREN[kind]:
            (p,) = [e for e in kids if e[0] == parent]
            assert [e[0] for e in _children(events, p)] == names
    _check_args(kind, {e[0]: e[3] for e in events}, batch)


@pytest.mark.parametrize("backend", sorted(FETCHED))
@pytest.mark.parametrize("kind", sorted(BATCHES))
def test_solve_spans_reach_an_active_tracer(kind, backend):
    batch = BATCHES[kind]
    tracer = SpanTracer()
    with tracer.active():
        solve_batched(batch, backend=backend)
    (root,) = tracer.roots
    assert root.name == "lp.solve"
    assert [c.name for c in root.children] == CHILDREN[kind]
    for c in root.children:
        if c.name in GRANDCHILDREN:
            assert [g.name for g in c.children] == GRANDCHILDREN[c.name]
    for a, b in zip(root.children, root.children[1:]):
        assert root.t0 <= a.t0 <= a.t1 <= b.t0 <= b.t1 <= root.t1
    _check_args(kind, {s.name: s.args for s in root.walk()}, batch, backend)
    # nothing recorded once the block is left
    solve_batched(batch)
    assert len(tracer.roots) == 1


def test_chunks_run_one_after_another():
    batch = BATCHES["standard"]
    tracer = SpanTracer()
    with tracer.active():
        solve_batched(batch, chunk_size=8)
    (root,) = tracer.roots
    plan = root.children[0]
    assert plan.name == "lp.plan" and plan.args["n_chunks"] == 3
    chunks = [c for c in root.children if c.name != "lp.plan"]
    assert [(c.name, c.args["chunk"]) for c in chunks] == [
        (name, k) for k in range(3)
        for name in ("lp.h2d", "lp.dispatch", "lp.wait", "lp.d2h")]
    for a, b in zip(chunks, chunks[1:]):
        assert a.t1 <= b.t0


def test_explicit_tracer_records_the_spans_inside():
    tracer = SpanTracer()
    solve_batched_compacted(BATCHES["general"], segment_k=4, tracer=tracer)
    names = [s.name for s in tracer.roots]
    assert names[0] == "lp.canonicalize" and names[-1] == "lp.recover"
    assert "lp.dispatch" in names
    assert any(n.startswith("lp.segment[") for n in names)
    assert [c.name for c in tracer.roots[0].children] == \
        GRANDCHILDREN["lp.canonicalize"]
    dispatch = names.index("lp.dispatch")
    assert tracer.roots[dispatch].args["B"] == 8


@pytest.mark.parametrize("kind,B,nnz", [
    ("afiro", 64, 118),     # 10.5% of the 35 x 32 canonical A
    ("afiro", 1, 118),
    ("dense", 256, 12 * 10),
])
def test_scale_span_records_the_pattern(kind, B, nnz):
    rng = np.random.default_rng(B)
    if kind == "afiro":
        batch = perturbed_batch(read_mps(fixture_path("afiro")), B, rng)
    else:
        batch = GeneralLPBatch.from_arrays(
            rng.uniform(1.0, 2.0, size=(B, 12, 10)), ["L"] * 12,
            np.ones((B, 12)), c=np.ones((B, 10)))
    tracer = SpanTracer()
    with tracer.active():
        lp, _ = ensure_canonical(batch)
    (can,) = tracer.roots
    assert can.name == "lp.canonicalize"
    scale = can.children[-1]
    assert scale.name == "lp.canonicalize.scale"
    assert scale.args == {"nnz": nnz, "density": nnz / (lp.m * lp.n),
                          "path": "pattern"}


def test_tags_and_late_args():
    tracer = SpanTracer()
    with tagged(chunk=3):
        with span("lp.x", tracer, a=1) as sp:
            sp.set(b=2)
            with span("lp.y"):           # recorded: inside a recorded span
                pass
    with span("lp.z") as quiet:           # no tracer: the annotation alone
        quiet.set(c=3)
    assert quiet.record is None
    (root,) = tracer.roots
    assert root.args == {"chunk": 3, "a": 1, "b": 2}
    assert [(c.name, c.args) for c in root.children] == [("lp.y",
                                                          {"chunk": 3})]
    with span("lp.w", tracer) as sp:
        pass
    assert sp.record.args == {}


@pytest.mark.parametrize("value,dtype", [
    (np.linspace(-1, 1, 12).reshape(3, 4), jnp.float32),
    (np.arange(6, dtype=np.float32), jnp.float32),
    ([[1, 2], [3, 4]], jnp.int32),
    (jnp.arange(4.0), jnp.float32),
])
def test_cast_then_put_is_asarray(value, dtype):
    dt = jax.dtypes.canonicalize_dtype(dtype)
    got = _put(value, _host_cast(value, dt), dt)
    want = jnp.asarray(value, dtype)
    assert got.dtype == want.dtype and got.shape == want.shape
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
