"""solve_shard_map with no mesh: every local device on one "data" axis,
through solve_batched's canonicalise, plan, chunk and finish code.  Four
host devices in a subprocess, so that the main test process keeps its
single-device jax."""
import os
import subprocess
import sys
import textwrap

ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))

PRELUDE = """
import jax
import numpy as np
from repro.core import (OPTIMAL, random_lp_batch, solve_batched,
                        solve_batched_reference, solve_shard_map)
assert len(jax.devices()) == 4
# B = 37 is not a multiple of the four devices
batch = random_lp_batch(np.random.default_rng(3), B=37, m=12, n=8,
                        feasible_start=False)
FIELDS = ("status", "iterations", "x", "objective")


def same(a, b):
    for f in FIELDS:
        assert np.array_equal(getattr(a, f), getattr(b, f),
                              equal_nan=True), f
"""


def _run(code: str) -> str:
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = os.path.join(ROOT, "src")
    r = subprocess.run(
        [sys.executable, "-c", PRELUDE + textwrap.dedent(code)],
        capture_output=True, text=True, env=env, cwd=ROOT, timeout=600)
    assert r.returncode == 0, f"STDOUT:\n{r.stdout}\nSTDERR:\n{r.stderr[-4000:]}"
    return r.stdout


def test_no_mesh_matches_float64_reference():
    out = _run("""
        res = solve_shard_map(batch)
        ref = solve_batched_reference(batch)
        assert res.x.shape == (37, 8)
        # the limits of bench/configs/paper_dense_28.json
        assert (res.status == ref.status).all()
        ok = ref.status == OPTIMAL
        assert ok.sum() > 20
        obj_err = np.abs(res.objective[ok] - ref.objective[ok]) \\
            / np.maximum(1.0, np.abs(ref.objective[ok]))
        assert obj_err.max() < 5e-4, obj_err.max()
        x = res.x[ok].astype(np.float64)
        ax = np.einsum("bmn,bn->bm", batch.A[ok], x)
        mag = np.einsum("bmn,bn->bm", np.abs(batch.A[ok]), np.abs(x))
        infeas = np.maximum(ax - batch.b[ok], 0) / np.maximum(1.0, mag)
        assert infeas.max() < 2e-2 and (x >= -2e-2).all()
        gap = np.abs(np.einsum("bn,bn->b", batch.c[ok], x)
                     - res.objective[ok]) \\
            / np.maximum(1.0, np.abs(res.objective[ok]))
        assert gap.max() < 1e-2
        print("REF-OK")
    """)
    assert "REF-OK" in out


def test_no_mesh_is_bitwise_one_device_solve_batched():
    out = _run("""
        same(solve_shard_map(batch), solve_batched(batch))
        # float64: the mesh takes the engine's float64 tolerances too
        import jax.numpy as jnp
        jax.config.update("jax_enable_x64", True)
        wide = solve_shard_map(batch, dtype=jnp.float64)
        assert wide.x.dtype == np.float64
        same(wide, solve_batched(batch, dtype=jnp.float64))
        print("BITWISE-OK")
    """)
    assert "BITWISE-OK" in out


def test_chunked_run_equals_one_chunk():
    out = _run("""
        from repro.obs import SpanTracer
        whole = solve_shard_map(batch)
        tr = SpanTracer()
        with tr.active():
            # a budget of 12 LPs over the four devices
            chunked = solve_shard_map(batch, device_bytes=20_000)
        plan = [s for s in tr.roots[0].walk() if s.name == "lp.plan"][0]
        assert plan.args["n_chunks"] > 1, plan.args
        same(chunked, whole)
        print("CHUNKS-OK", plan.args)
    """)
    assert "CHUNKS-OK" in out


def test_general_form_recovers_original_coordinates():
    out = _run("""
        from repro.io.mps import fixture_path, perturbed_batch, read_mps
        g = perturbed_batch(read_mps(fixture_path("afiro")), 10,
                            np.random.default_rng(7))
        res = solve_shard_map(g)
        ref = solve_batched_reference(g)
        one = solve_batched(g)
        assert res.x.shape == (10, g.n)
        assert (res.status == ref.status).all()
        ok = ref.status == OPTIMAL
        err = np.abs(res.objective[ok] - ref.objective[ok]) \\
            / np.maximum(1.0, np.abs(ref.objective[ok]))
        assert err.max() < 2e-3, err.max()
        same(res, one)
        print("GENERAL-OK")
    """)
    assert "GENERAL-OK" in out


def test_spans_and_shard_max_iters():
    out = _run("""
        from repro.obs import SpanTracer
        tr = SpanTracer()
        res = solve_shard_map(batch, tracer=tr)
        spans = {}
        for root in tr.roots:
            for s in root.walk():
                spans.setdefault(s.name, []).append(s)
        for name in ("lp.solve", "lp.plan", "lp.h2d", "lp.h2d.cast",
                     "lp.h2d.put", "lp.dispatch", "lp.wait", "lp.d2h"):
            assert name in spans, (name, sorted(spans))
        h2d, = spans["lp.h2d"]
        assert h2d.args["shards"] == 4 and h2d.args["chunk"] == 0
        assert h2d.args["bytes_out"] * 2 == h2d.args["bytes_in"]
        dispatch, = spans["lp.dispatch"]
        assert dispatch.args["shards"] == 4
        assert (dispatch.args["B"], dispatch.args["m"],
                dispatch.args["n"]) == (40, 12, 8)
        d2h, = spans["lp.d2h"]
        assert d2h.args["arrays"] == 6 and d2h.args["bytes"] > 0
        # 40 padded LPs, 10 a device; the padding LPs take no pivot
        want = [int(res.iterations[k * 10:(k + 1) * 10].max())
                for k in range(4)]
        assert d2h.args["shard_max_iters"] == want, (d2h.args, want)
        print("SPANS-OK", want)
    """)
    assert "SPANS-OK" in out


def test_shards_cast_into_the_kept_buffer():
    out = _run("""
        import functools
        from repro.core import transfer
        from repro.core.distributed import make_mesh
        from repro.core.simplex import solve_two_phase
        from repro.obs import SpanTracer
        mesh = make_mesh()
        spec = transfer.batch_spec(mesh)
        program = transfer.shard(functools.partial(
            solve_two_phase, m=12, n=8, max_iters=250, tol=1e-6,
            feas_tol=1e-5), mesh, (spec,) * 4)
        other = random_lp_batch(np.random.default_rng(4), B=37, m=12, n=8,
                                feasible_start=False)

        def solve(b):
            padded, _ = transfer._pad_batch(b, mesh.size)
            tr = SpanTracer()
            with tr.active():
                out, _ = transfer.run(
                    program, transfer.lp_leaves(padded, np.dtype(np.float32)),
                    mesh=mesh, B=padded.batch, m=12, n=8)
            (cast,) = [s for root in tr.roots for s in root.walk()
                       if s.name == "lp.h2d.cast"]
            return out, cast.args["staging"]

        def equal(a, b):
            for u, v in zip(a, b):
                assert np.array_equal(u, v, equal_nan=True)

        first, s1 = solve(batch)
        kept = [a.copy() for a in first]
        second, s2 = solve(other)
        assert (s1, s2) == ("grown", "reused"), (s1, s2)
        equal(first, kept)    # the second call left the first result alone
        # the fresh cast, as before the buffer: another call holds it
        with transfer._HOST_STAGING.lease():
            (f1, t1), (f2, t2) = solve(batch), solve(other)
        assert (t1, t2) == ("fresh", "fresh"), (t1, t2)
        equal(first, f1)
        equal(second, f2)
        print("STAGING-OK")
    """)
    assert "STAGING-OK" in out
