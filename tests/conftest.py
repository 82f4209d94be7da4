import glob
import os
import sys

import pytest

# tests run against the source tree (PYTHONPATH=src also works)
sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))


@pytest.fixture
def profile(tmp_path):
    """``profile(fn)`` runs ``fn`` under a ``jax.profiler`` trace and
    returns the trace's host events as ``(name, start_ns, end_ns, args)``,
    outer before inner."""
    def run(fn):
        import jax
        from jax.profiler import ProfileData
        log_dir = str(tmp_path / "trace")
        with jax.profiler.trace(log_dir):
            fn()
        (path,) = glob.glob(f"{log_dir}/**/*.xplane.pb", recursive=True)
        events = []
        for plane in ProfileData.from_file(path).planes:
            if plane.name.startswith("/host:"):
                for line in plane.lines:
                    events += [(ev.name, ev.start_ns, ev.end_ns,
                                {k: v for k, v in ev.stats})
                               for ev in line.events]
        return sorted(events, key=lambda e: (e[1], -e[2]))
    return run
