"""``run.py`` refuses a machine without a TPU, and a directory that holds
only the benchmark, with no result line; the window traces its first
calls only."""
import os
import shutil
import subprocess
import sys

import pytest

from bench.spec import BENCH, ROOT


def _run(cwd, env_extra=None):
    env = {**os.environ, "JAX_PLATFORMS": "cpu", **(env_extra or {})}
    env.pop("PYTHONPATH", None)
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "dense28_b1k_loop",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_run_refuses_the_cpu():
    p = _run(ROOT)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "needs a TPU" in p.stderr and "cpu" in p.stderr


def test_run_refuses_a_directory_with_only_the_benchmark(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns(".cache", "__pycache__"))
    p = _run(tmp_path)
    assert p.returncode != 0
    assert p.stdout.strip() == ""


class _Profiler:
    """Records what the window asks of ``jax.profiler``."""

    def __init__(self):
        self.events, self.annotated = [], 0
        outer = self

        class Annotation:
            def __init__(self, name):
                outer.events.append(("annotate", name))

            def __enter__(self):
                outer.annotated += 1

            def __exit__(self, *exc):
                return False
        self.TraceAnnotation = Annotation

    def start_trace(self, path, profiler_options=None):
        self.events.append(("start", path))

    def stop_trace(self):
        self.events.append(("stop",))


class _Jax:
    def __init__(self):
        self.profiler = _Profiler()


@pytest.mark.parametrize("trace_s,seconds,pool,want", [
    (0.0, 0.05, 3, 3),       # one pass over the pool, then untraced
    (0.02, 0.08, 2, None),   # at least TRACE_S seconds
    (10.0, 0.03, 2, None),   # the window ends first: every call traced
])
def test_window_traces_its_first_calls(monkeypatch, trace_s, seconds,
                                       pool, want):
    import time

    from bench import measure
    monkeypatch.setattr(measure, "TRACE_S", trace_s)
    monkeypatch.setattr(measure, "trace_options", lambda jax: None)
    jax = _Jax()

    def call(x):
        time.sleep(0.005)
        return {"x": x}
    calls, traced = measure.window(call, list(range(pool)), seconds, jax,
                                   trace_dir="d")
    kinds = [e[0] for e in jax.profiler.events]
    assert kinds[0] == "start" and kinds.count("stop") == 1
    assert jax.profiler.annotated == traced
    assert [c.pool_index for c in calls] == [k % pool for k in range(len(calls))]
    if want is not None:
        assert traced == want < len(calls)
    elif trace_s >= seconds:
        assert traced == len(calls)
    else:
        assert pool <= traced < len(calls)
        assert calls[traced - 1].t1 - calls[0].t0 >= trace_s - 0.002
        assert kinds[-1] == "stop"


def test_window_untraced_touches_no_profiler():
    from bench import measure
    jax = _Jax()
    calls, traced = measure.window(lambda x: {"x": x}, [0, 1], 0.01, jax)
    assert traced is None and calls and jax.profiler.events == []
