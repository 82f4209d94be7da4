"""One run of one cell: set-up, the measured window, the check of its
answers, and the metrics, as one result line.

Set-up (``setup_s``) is everything from process start to the first timed
call: JAX and the chip, the pool of distinct batches made from the seed,
and one untimed call per shape the window uses.  The window is one caller
in a closed loop: it hands the entry point the next batch of the pool,
waits for host arrays, and calls again; a call that starts before
``seconds`` have passed runs to its end, and the window runs from the
first call's start to the last call's end.  A traced run traces the
window's first calls only (``TRACE_S`` seconds and one pass over the
pool), so that the trace stays small enough to read within the run.
"""
from __future__ import annotations

import dataclasses
import json
import shutil
import sys
import time
from typing import Callable, Optional

import numpy as np

from bench import check, devtrace, gen, program
from bench.spec import BENCH, Cell, metric_reader

CACHE_DIR = BENCH / ".cache" / "jax"      # fixed: the path is in the key
TRACE_DIR = BENCH / ".cache" / "trace"
PEAKS = BENCH / "peaks.json"
TRACE_S = 10.0    # least seconds of the window a traced run traces


class NoChip(RuntimeError):
    """JAX finds no TPU, or fewer chips than the cell asks for."""


class CompileClock:
    """Counts XLA backend compilations and sums their seconds, from the
    events ``jax.monitoring`` reports."""

    def __init__(self, jax):
        self.count, self.seconds = 0, 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.count += 1
            self.seconds += duration


def init_jax(chips: int, require_tpu: bool = True):
    """JAX with the persistent compile cache in the checkout, and the
    first ``chips`` devices; raises ``NoChip`` off a TPU."""
    import jax
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    try:
        devices = jax.devices()
    except RuntimeError as e:
        raise NoChip(f"JAX found no usable backend: {e}") from e
    d = devices[0]
    if require_tpu and d.platform != "tpu":
        raise NoChip(f"needs a TPU; JAX reports {len(devices)} "
                     f"{d.platform} device(s) ({d.device_kind}). There is "
                     "no CPU fallback.")
    if len(devices) < chips:
        raise NoChip(f"the cell asks for {chips} chips; JAX reports "
                     f"{len(devices)} {d.platform} device(s)")
    return jax, devices[:chips]


def load_peaks(device_kind: str) -> dict:
    """The peak FLOP/s and bytes/s of one chip; an unknown kind is an
    error, never a default."""
    with open(PEAKS) as f:
        table = json.load(f)["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"{PEAKS.name}; known: {sorted(table)}")
    return table[device_kind]


def peak_bytes(stats: dict) -> int:
    """Device memory held at its peak: the buffers in use plus the space
    the runtime reserves for the compiled programs' temporaries, which
    ``peak_bytes_in_use`` leaves out on a TPU."""
    return int(stats.get("peak_bytes_in_use", 0)) + \
        int(stats.get("peak_bytes_reserved", 0))


@dataclasses.dataclass
class Call:
    pool_index: int
    t0: float
    t1: float
    out: dict           # x, objective, status, iterations (host arrays)


@dataclasses.dataclass
class Run:
    """What a metric reader sees of one run."""

    cell: Cell
    data: list          # the pool (gen.LPData)
    calls: list         # [Call] of the window
    setup_s: float
    devices: list
    memory: list        # memory_stats() of each device after the window
    trace: Optional[devtrace.Trace] = None
    traced: Optional[int] = None   # the first ``traced`` calls are traced

    @property
    def traced_calls(self) -> list:
        return self.calls if self.traced is None else self.calls[:self.traced]

    @property
    def window_s(self) -> float:
        return self.calls[-1].t1 - self.calls[0].t0

    @property
    def lps(self) -> int:
        return sum(self.data[c.pool_index].batch for c in self.calls)

    @property
    def latencies_s(self) -> np.ndarray:
        return np.array([c.t1 - c.t0 for c in self.calls])

    @property
    def peaks(self) -> dict:
        return load_peaks(self.devices[0].device_kind)


def window(call: Callable, inputs: list, seconds: float, jax,
           trace_dir: Optional[str] = None) -> tuple:
    """The closed loop: one caller, the pool in turn.  With ``trace_dir``
    the profiler traces the first calls, each inside the benchmark's call
    annotation, until ``TRACE_S`` seconds have passed and every batch of
    the pool has been called once; the window then runs on untraced.
    Returns the calls and how many of them were traced."""
    calls = []
    tracing = trace_dir is not None
    traced = None
    if tracing:
        jax.profiler.start_trace(trace_dir,
                                 profiler_options=trace_options(jax))
    start = time.perf_counter()
    k = 0
    try:
        while not calls or time.perf_counter() - start < seconds:
            p = k % len(inputs)
            t0 = time.perf_counter()
            if tracing:
                with jax.profiler.TraceAnnotation(devtrace.CALL):
                    out = call(inputs[p])
            else:
                out = call(inputs[p])
            calls.append(Call(p, t0, time.perf_counter(), out))
            k += 1
            if (tracing and k >= len(inputs)
                    and time.perf_counter() - start >= TRACE_S):
                jax.profiler.stop_trace()
                tracing, traced = False, k
    finally:
        if tracing:
            jax.profiler.stop_trace()
            traced = len(calls)
    return calls, traced


def trace_options(jax):
    """Profiler options for the traced calls: the device's XLA ops, the
    only device events the reduction reads, and the benchmark's own call
    annotations, without the Python tracer and the runtime's verbose host
    events.  Each traced result line gives its traced call count
    (``calls.traced``) beside the window's, so the cost of tracing shows."""
    opts = jax.profiler.ProfileOptions()
    opts.host_tracer_level = 1
    opts.python_tracer_level = 0
    opts.advanced_configuration = {"tpu_trace_mode": "TRACE_ONLY_XLA"}
    return opts


def run_cell(cell: Cell, seed: int, seconds: float, trace: bool, *,
             t_start: float, entry: Optional[Callable] = None,
             log=None) -> dict:
    """One run; returns the result line's object.  ``entry(core, config)``
    makes the function of one batch that the window drives; it defaults to
    ``program.entry``, and only calibration (the control) and tests (a
    planted fault) pass another."""
    log = log or (lambda msg: print(msg, file=sys.stderr, flush=True))
    jax, devices = init_jax(cell.chips)
    clock = CompileClock(jax)
    core = program.load_core()
    pool = gen.make_pool(cell.config, cell.traffic, cell.batch, seed)
    inputs = [program.to_input(core, d) for d in pool]
    call = (entry or program.entry)(core, cell.config)
    call(inputs[-1])                  # every batch of the pool has one shape
    setup_s = time.perf_counter() - t_start
    log(f"set-up {setup_s:.3f}s: {len(pool)} batches of {cell.batch} LPs "
        f"{pool[0].shape}, {clock.count} compiles ({clock.seconds:.3f}s)")

    compiles = clock.count
    if trace:
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
    calls, traced = window(call, inputs, seconds, jax,
                           trace_dir=str(TRACE_DIR) if trace else None)
    compiles = clock.count - compiles
    memory = [d.memory_stats() or {} for d in devices]
    red = None
    if trace:
        red = devtrace.read(str(TRACE_DIR), len(devices))
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
    run = Run(cell=cell, data=pool, calls=calls, setup_s=setup_s,
              devices=devices, memory=memory, trace=red, traced=traced)
    log(f"window {run.window_s:.3f}s: {len(calls)} calls, {run.lps} LPs, "
        f"{compiles} compiles inside; memory_stats {memory}")
    if red is not None:
        log(f"trace: {traced} calls, busy {red.busy_s():.6f}s of "
            f"{red.window_s():.6f}s, {red.busy_outside_calls_s():.6f}s of "
            "it outside calls")

    del inputs
    for c in calls:
        c.out = program.name_statuses(core, c.out)
    t_check = time.perf_counter()
    numbers = check.compare(pool, calls, seed, shards=len(devices))
    correct, checks = check.verdict(numbers, cell.config["limits"])
    log(f"check {time.perf_counter() - t_check:.3f}s")

    entries = cell.per_layer if trace else cell.end_to_end
    metrics = {}
    for m in entries:
        value = metric_reader(m["name"])(run)
        if value is not None:
            metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}
    d0 = devices[0]
    device = {"platform": d0.platform, "kind": d0.device_kind,
              "count": len(jax.devices()),
              "memory_peak_bytes": max(peak_bytes(s) for s in memory)}
    result = {"correct": bool(correct), "attempted": run.lps,
              "failed": int(sum((c.out["status"] == "iteration_limit").sum()
                                + (c.out["status"] == "unknown").sum()
                                for c in calls)),
              "metrics": metrics, "device": device,
              "compiles_in_window": compiles, "calls": _calls(calls)}
    if traced is not None:
        result["calls"]["traced"] = traced
    if red is not None:
        device["busy_s"] = red.busy_s()
        device["window_s"] = red.window_s()
        result["breakdown"] = red.breakdown()
    result["checks"] = {k: {"value": _finite(v["value"]), "limit": v["limit"]}
                        for k, v in checks.items()}
    for k, v in checks.items():
        log(f"check {k} = {v['value']!r} limit {v['limit']!r}")
    return result


def _calls(calls: list) -> dict:
    """Per-call seconds and most pivots of any LP, for reading a run's
    spread: each call where there are few, else their quartiles."""
    secs = [c.t1 - c.t0 for c in calls]
    its = [int(c.out["iterations"].max(initial=0)) for c in calls]
    if len(calls) > 32:
        q = lambda v: [float(x) for x in np.percentile(v, [0, 25, 50, 75, 95, 100])]  # noqa: E731
        return {"n": len(calls), "seconds_q": q(secs), "max_iters_q": q(its)}
    return {"n": len(calls), "seconds": secs, "max_iters": its,
            "pool_index": [c.pool_index for c in calls]}


def _finite(x):
    return float(x) if np.isfinite(x) else None
