#!/usr/bin/env python3
"""Bring-up smoke run of the batched-LP solve path on a TPU.

    python chip_smoke.py             # one chip: every engine, paper sizes
    python chip_smoke.py --chips 4   # the 4-chip mesh path only

Drives the normal entry points (``repro.core.solve_batched``,
``repro.kernels.ops.solve_batched_pallas``; with ``--chips 4``
``solve_shard_map``) on the repo's own deployment,
``configs/paper_lp.py`` ``WORKLOADS``, at its published batch sizes, with
data made from ``--seed``.  Every phase is checked against the float64
oracle (``solve_batched_reference``) on a seeded subset of 256 LPs, and
the kernel and mesh phases against their reference engines as the solver
invariants require.  Any failed check exits non-zero with no result line.

The script refuses to run anywhere but on a TPU (no CPU fallback) and
needs the checkout's ``src/`` next to it.  The compile cache is the one
``JAX_COMPILATION_CACHE_DIR`` names, else ``<checkout>/.jax_cache``.

Per-phase lines give the device, the seconds spent in XLA/Mosaic
compilation during the phase's first (cold) call, and the wall time and
solves per second of a second (warm) call that ends in host arrays.
These timings are informational: they are not benchmark metrics.  The
last line of standard output is the JSON result
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": ...}}``.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent
SUBSET = 256        # LPs per oracle check
PALLAS_B = 4096     # batch of the engine and kernel phases (28x28)

# Oracle tolerances (status agreement over the subset, max relative
# objective error |obj - ref| / max(1, |ref|) over LPs both call OPTIMAL).
# The simplex engines pivot in float32 against the float64 oracle: the
# tableau and its updates round at ~1e-7 relative per pivot and a few
# hundred pivots compound that, which is why the repo's own oracle tests
# hold 2e-3; a near-degenerate LP may stop on another status, so 2% of the
# subset may disagree.  PDHG stops at a relative KKT residual of 1e-5 and
# its f32 iterates classify a few LPs differently within the iteration
# cap, so its bounds are looser (tests/test_pdhg.py holds 0.9 agreement).
SIMPLEX_TOL = {"min_status_agree": 0.98, "max_rel_obj": 2e-3}
PDHG_TOL = {"min_status_agree": 0.90, "max_rel_obj": 1e-2}


class SmokeFailure(Exception):
    pass


def _fail(msg: str) -> None:
    print(f"chip_smoke: {msg}", file=sys.stderr)
    sys.exit(1)


def _load():
    """Import JAX and the repo, and hold the run to a TPU."""
    if not (ROOT / "src" / "repro").is_dir():
        _fail(f"no src/repro next to {Path(__file__).name}: run it from a "
              "checkout of the repository")
    sys.path.insert(0, str(ROOT / "src"))
    import jax
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir",
                          str(ROOT / ".jax_cache"))
    try:
        devices = jax.devices()
    except RuntimeError as e:
        _fail(f"JAX found no usable backend: {e}")
    if devices[0].platform != "tpu":
        _fail(f"needs a TPU; JAX reports {devices[0].platform!r} "
              f"({devices[0].device_kind}). There is no CPU fallback.")
    return jax, devices


class CompileClock:
    """Sums XLA backend-compile durations reported by jax.monitoring."""

    def __init__(self, jax):
        self.total = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.total += duration


def take(batch, idx):
    """The LPs ``idx`` of an LPBatch or GeneralLPBatch (per-LP leaves are
    the array fields whose leading axis is the batch)."""
    B = batch.batch
    per_lp = {f.name: getattr(batch, f.name)[idx]
              for f in dataclasses.fields(batch)
              if getattr(getattr(batch, f.name), "shape", ())[:1] == (B,)}
    return dataclasses.replace(batch, **per_lp)


class Smoke:
    def __init__(self, jax, devices, seed: int):
        import numpy as np
        self.jax, self.np = jax, np
        self.dev = devices[0]
        self.seed = seed
        self.clock = CompileClock(jax)
        self.refs = {}

    def data(self, name: str, batch: int, offset: int):
        from repro.configs.paper_lp import WORKLOADS, build_batch
        w = {w.name: w for w in WORKLOADS}[name]
        t0 = time.perf_counter()
        b = build_batch(w, batch=batch,
                        rng=self.np.random.default_rng(self.seed + offset))
        print(f"data {name}: B={b.batch} m={b.m} n={b.n} "
              f"({type(b).__name__}) made in "
              f"{time.perf_counter() - t0:.2f}s", flush=True)
        return b

    def oracle(self, key: str, batch):
        """(subset indices, float64 reference result) of a batch, cached."""
        if key not in self.refs:
            from repro.core import solve_batched_reference
            rng = self.np.random.default_rng(self.seed + 1000 + len(self.refs))
            idx = self.np.sort(rng.choice(batch.batch, SUBSET, replace=False))
            self.refs[key] = (idx, solve_batched_reference(take(batch, idx)))
        return self.refs[key]

    def run(self, phase: str, solve, *, n_lps: int, key: str, batch, tol):
        """Cold call (compile + solve), warm call (timed), oracle check."""
        from repro.core import OPTIMAL
        np = self.np
        c0 = self.clock.total
        t0 = time.perf_counter()
        cold = solve()
        self.jax.block_until_ready(cold.objective)
        cold_s = time.perf_counter() - t0
        compile_s = self.clock.total - c0
        t0 = time.perf_counter()
        res = solve()
        self.jax.block_until_ready(res.objective)
        wall = time.perf_counter() - t0
        idx, ref = self.oracle(key, batch)
        status = np.asarray(res.status)[idx]
        agree = float((status == ref.status).mean())
        ok = (status == OPTIMAL) & (ref.status == OPTIMAL)
        obj = np.asarray(res.objective)[idx]
        rel = float(np.max(np.abs(obj[ok] - ref.objective[ok])
                           / np.maximum(1.0, np.abs(ref.objective[ok])),
                           initial=0.0))
        print(f"phase {phase}: device={self.dev.device_kind} "
              f"compile_s={compile_s:.2f} cold_s={cold_s:.2f} "
              f"solve_s={wall:.4f} solves_per_s={n_lps / wall:.0f} "
              f"(informational) oracle status_agree={agree:.4f} "
              f"max_rel_obj={rel:.3e} optimal={int(ok.sum())}/{SUBSET}",
              flush=True)
        if agree < tol["min_status_agree"] or rel > tol["max_rel_obj"]:
            raise SmokeFailure(f"{phase}: oracle disagreement (status "
                               f"{agree:.4f}, rel obj {rel:.3e}; bounds {tol})")
        if not np.array_equal(np.asarray(cold.status), np.asarray(res.status)):
            raise SmokeFailure(f"{phase}: two runs of one batch disagree")
        return res

    def same(self, phase: str, res, ref, fields):
        np = self.np
        for f in fields:
            a, b = np.asarray(getattr(res, f)), np.asarray(getattr(ref, f))
            if not np.array_equal(a, b, equal_nan=a.dtype.kind == "f"):
                bad = int((a != b).sum()) if a.shape == b.shape else -1
                raise SmokeFailure(f"{phase}: {f} differs from the reference "
                                   f"engine on {bad} entries")
        print(f"phase {phase}: {'/'.join(fields)} bitwise equal to the "
              f"reference engine", flush=True)


def single_chip(s: Smoke) -> None:
    from repro.core import solve_batched

    d28 = s.data("lp_28d_100k", 100_000, 0)
    s.run("solve_batched lp_28d_100k", lambda: solve_batched(d28),
          n_lps=d28.batch, key="28", batch=d28, tol=SIMPLEX_TOL)
    afiro = s.data("lp_afiro_100k", 100_000, 1)
    s.run("solve_batched lp_afiro_100k (canonicalize+recover)",
          lambda: solve_batched(afiro), n_lps=afiro.batch, key="afiro",
          batch=afiro, tol=SIMPLEX_TOL)
    chunked_100d(s)
    engines_and_kernels(s, d28)


def chunked_100d(s: Smoke) -> None:
    from repro.core import solve_batched
    from repro.core.batching import device_memory_bytes, max_chunk_size

    d100 = s.data("lp_100d_50k", 50_000, 2)
    limit = device_memory_bytes()
    chunk = max_chunk_size(d100, limit)
    print(f"plan lp_100d_50k: bytes_limit={limit} chunk<={chunk} "
          f"chunks={-(-d100.batch // chunk)}", flush=True)
    s.run("solve_batched lp_100d_50k (chunked)", lambda: solve_batched(d100),
          n_lps=d100.batch, key="100", batch=d100, tol=SIMPLEX_TOL)


def engines_and_kernels(s: Smoke, d28) -> None:
    from repro.core import LPBatch, solve_batched, solve_batched_jax
    from repro.kernels.ops import solve_batched_pallas

    small = LPBatch(A=d28.A[:PALLAS_B], b=d28.b[:PALLAS_B],
                    c=d28.c[:PALLAS_B])
    run = lambda name, f, tol: s.run(  # noqa: E731
        f"{name} lp_28d B={PALLAS_B}", f, n_lps=PALLAS_B, key="28s",
        batch=small, tol=tol)
    jx = run("solve_batched_jax", lambda: solve_batched_jax(small),
             SIMPLEX_TOL)
    rev = run("solve_batched backend=revised",
              lambda: solve_batched(small, backend="revised"), SIMPLEX_TOL)
    run("solve_batched backend=revised compaction",
        lambda: solve_batched(small, backend="revised", compaction=True),
        SIMPLEX_TOL)
    run("solve_batched backend=pdhg",
        lambda: solve_batched(small, backend="pdhg"), PDHG_TOL)
    run("solve_batched backend=pdhg compaction",
        lambda: solve_batched(small, backend="pdhg", compaction=True),
        PDHG_TOL)
    for compaction, form in ((False, "whole"), (True, "segment")):
        name = f"solve_batched_pallas tableau {form}"
        res = run(name, lambda: solve_batched_pallas(
            small, compaction=compaction), SIMPLEX_TOL)
        s.same(name, res, jx, ("status", "iterations"))
        name = f"solve_batched_pallas revised {form}"
        res = run(name, lambda: solve_batched_pallas(
            small, backend="revised", compaction=compaction), SIMPLEX_TOL)
        s.same(name, res, rev, ("status",))
        run(f"solve_batched_pallas pdhg {form}", lambda: solve_batched_pallas(
            small, backend="pdhg", compaction=compaction), PDHG_TOL)


def four_chips(s: Smoke) -> None:
    from repro.core import solve_batched, solve_shard_map, transfer
    from repro.core.distributed import make_mesh

    if len(s.jax.devices()) < 4:
        raise SmokeFailure(f"--chips 4 needs 4 devices, JAX reports "
                           f"{len(s.jax.devices())}")
    mesh = make_mesh((4,), ("data",))
    d28 = s.data("lp_28d_100k", 100_000, 0)
    f32 = s.np.dtype(s.np.float32)
    # the put every mesh solve makes of its host-cast leaves
    A = transfer.put_sharded(s.np.asarray(d28.A, f32), mesh, f32)
    rows = {sh.device.id: sh.data.shape[0] for sh in A.addressable_shards}
    print(f"spread lp_28d_100k over {len(rows)} devices: rows per device "
          f"{rows}", flush=True)
    if len(rows) != 4 or set(rows.values()) != {d28.batch // 4}:
        raise SmokeFailure(f"batch not spread over 4 devices: {rows}")
    one = s.run("solve_batched lp_28d_100k (1 chip)",
                lambda: solve_batched(d28), n_lps=d28.batch, key="28",
                batch=d28, tol=SIMPLEX_TOL)
    fields = ("status", "iterations", "objective", "x")
    res = s.run("solve_shard_map lp_28d_100k (4 chips)",
                lambda: solve_shard_map(d28, mesh), n_lps=d28.batch, key="28",
                batch=d28, tol=SIMPLEX_TOL)
    s.same("solve_shard_map lp_28d_100k (4 chips)", res, one, fields)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the 4-chip mesh path and its "
                         "1-chip reference")
    ap.add_argument("--seed", type=int, default=2018)
    args = ap.parse_args(argv)
    jax, devices = _load()
    print(f"device: {devices[0].platform} {devices[0].device_kind} "
          f"x{len(devices)}; jax {jax.__version__}; compile cache "
          f"{jax.config.jax_compilation_cache_dir}", flush=True)
    s = Smoke(jax, devices, args.seed)
    t0 = time.perf_counter()
    try:
        (four_chips if args.chips == 4 else single_chip)(s)
    except SmokeFailure as e:
        _fail(f"FAILED {e}")
    print(f"all phases passed in {time.perf_counter() - t0:.1f}s "
          f"(compile {s.clock.total:.1f}s)", flush=True)
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
