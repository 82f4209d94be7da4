"""Multi-device behavior (8 host devices via subprocess so the main test
process keeps its single-device jax)."""
import os
import subprocess
import sys
import textwrap

import pytest

ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))


def _run(code: str, devices: int = 8):
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    env["PYTHONPATH"] = os.path.join(ROOT, "src")
    r = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                       capture_output=True, text=True, env=env, cwd=ROOT,
                       timeout=600)
    assert r.returncode == 0, f"STDOUT:\n{r.stdout}\nSTDERR:\n{r.stderr[-4000:]}"
    return r.stdout


def test_lp_solvers_sharded_match_reference():
    out = _run("""
        import numpy as np
        from repro.core import (OPTIMAL, random_lp_batch,
                                solve_batched_reference, solve_shard_map)
        from repro.distributed.sharding import make_mesh
        mesh = make_mesh((2, 4), ("data", "model"))
        rng = np.random.default_rng(0)
        batch = random_lp_batch(rng, B=37, m=12, n=8, feasible_start=False)
        ref = solve_batched_reference(batch)
        res = solve_shard_map(batch, mesh)
        ok = (ref.status == OPTIMAL) & (res.status == OPTIMAL)
        assert (ref.status == res.status).mean() >= 0.95
        rel = abs(ref.objective[ok] - res.objective[ok]) / abs(ref.objective[ok])
        assert rel.max() < 5e-4
        print("LP-OK")
    """)
    assert "LP-OK" in out


def test_lp_shard_map_segmented_compaction_bitwise():
    """solve_shard_map(segment_k=...) — per-shard segment loops + global
    bucket-ladder compaction — must be bit-identical to the single-device
    solver (same pivot sequences, only dead work removed)."""
    out = _run("""
        import numpy as np
        from repro.core import random_lp_batch, solve_batched_jax, solve_shard_map
        from repro.distributed.sharding import make_mesh
        mesh = make_mesh((2, 4), ("data", "model"))
        rng = np.random.default_rng(2)
        batch = random_lp_batch(rng, B=37, m=12, n=8, feasible_start=False)
        jx = solve_batched_jax(batch)
        stats = []
        res = solve_shard_map(batch, mesh, segment_k=4, stats_out=stats)
        assert np.array_equal(jx.status, res.status)
        assert np.array_equal(jx.iterations, res.iterations)
        assert np.array_equal(np.nan_to_num(jx.objective),
                              np.nan_to_num(res.objective))
        assert len(stats) >= 2 and all(s.bucket % 8 == 0 for s in stats)
        print("SEG-OK", len(stats))
    """)
    assert "SEG-OK" in out


def test_sharded_train_step_matches_single_device():
    out = _run("""
        import dataclasses
        import numpy as np, jax, jax.numpy as jnp
        from repro.configs import get_config
        from repro.models import build_model
        from repro.distributed.sharding import Sharder, make_mesh
        from repro.distributed.steps import make_train_step
        from repro.optim import get_optimizer
        from repro.launch.cells import build_cell

        cfg = dataclasses.replace(get_config("qwen3-32b").reduced(),
                                  n_heads=4, n_kv_heads=2, d_ff=128)
        rng = np.random.default_rng(0)
        B, S = 8, 32
        batch = {"tokens": jnp.asarray(rng.integers(0, cfg.vocab, (B, S)), jnp.int32),
                 "labels": jnp.asarray(rng.integers(0, cfg.vocab, (B, S)), jnp.int32)}

        # single device
        model0 = build_model(cfg, None)
        params, specs = model0.init(jax.random.PRNGKey(0))
        loss0 = float(model0.loss_fn(params, batch))

        # sharded on (2,4)
        mesh = make_mesh((2, 4), ("data", "model"))
        shd = Sharder(cfg, mesh)
        model1 = build_model(cfg, shd)
        sharded = jax.device_put(params, shd.param_shardings(specs))
        with mesh:
            loss1 = float(jax.jit(model1.loss_fn)(sharded, batch))
        assert abs(loss0 - loss1) < 5e-3, (loss0, loss1)

        # full train step lowers+runs on the mesh
        opt = get_optimizer(cfg.optimizer)
        step = make_train_step(model1, opt)
        opt_state = jax.jit(opt.init)(sharded)
        with mesh:
            p2, o2, metrics = jax.jit(step)(sharded, opt_state, batch)
        assert np.isfinite(float(metrics["loss"]))
        print("TRAIN-OK", loss0, loss1)
    """)
    assert "TRAIN-OK" in out


def test_moe_shard_map_matches_local():
    out = _run("""
        import dataclasses
        import numpy as np, jax, jax.numpy as jnp
        from repro.configs import get_config
        from repro.models import build_model
        from repro.distributed.sharding import Sharder, make_mesh

        cfg = dataclasses.replace(get_config("llama4-scout-17b-a16e").reduced(),
                                  capacity_factor=100.0)
        rng = np.random.default_rng(1)
        B, S = 8, 16
        batch = {"tokens": jnp.asarray(rng.integers(0, cfg.vocab, (B, S)), jnp.int32),
                 "labels": jnp.asarray(rng.integers(0, cfg.vocab, (B, S)), jnp.int32)}
        model0 = build_model(cfg, None)
        params, specs = model0.init(jax.random.PRNGKey(0))
        loss0 = float(model0.loss_fn(params, batch))
        mesh = make_mesh((2, 4), ("data", "model"))
        shd = Sharder(cfg, mesh)
        model1 = build_model(cfg, shd)
        sharded = jax.device_put(params, shd.param_shardings(specs))
        with mesh:
            loss1 = float(jax.jit(model1.loss_fn)(sharded, batch))
        # same routing, same experts; differences only from reduction order
        assert abs(loss0 - loss1) < 5e-3, (loss0, loss1)
        print("MOE-OK", loss0, loss1)
    """)
    assert "MOE-OK" in out


def test_checkpoint_reshard_across_meshes():
    out = _run("""
        import numpy as np, jax, jax.numpy as jnp, tempfile
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.checkpoint import CheckpointManager
        from repro.distributed.sharding import make_mesh

        tree = {"w": jnp.arange(64, dtype=jnp.float32).reshape(8, 8)}
        mesh1 = make_mesh((2, 4), ("data", "model"))
        sh1 = {"w": NamedSharding(mesh1, P("data", "model"))}
        t1 = jax.device_put(tree, sh1)
        d = tempfile.mkdtemp()
        mgr = CheckpointManager(d)
        mgr.save(0, t1)
        # elastic restore on a DIFFERENT mesh shape (simulates node loss)
        mesh2 = make_mesh((4, 2), ("data", "model"))
        sh2 = {"w": NamedSharding(mesh2, P("model", "data"))}
        t2 = mgr.restore(0, tree, shardings=sh2)
        np.testing.assert_allclose(np.asarray(t2["w"]), np.asarray(tree["w"]))
        print("RESHARD-OK")
    """)
    assert "RESHARD-OK" in out


def test_dryrun_entrypoint_smoke():
    """The real dryrun script on a small arch (512 host devices)."""
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(ROOT, "src")
    r = subprocess.run(
        [sys.executable, "-m", "repro.launch.dryrun", "--arch", "hymba-1.5b",
         "--shape", "decode_32k", "--out", "/tmp/test_dryrun_artifacts"],
        capture_output=True, text=True, env=env, cwd=ROOT, timeout=900)
    assert r.returncode == 0, r.stdout + r.stderr[-2000:]
    assert "OK" in r.stdout
