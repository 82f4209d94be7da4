"""Whole runs on the CPU with the timed path broken underneath: ``correct``
comes out false for the control (the program in bfloat16) and for each
fault a cell can have, and true for the sound program."""
import json
import os
import subprocess
import sys

import pytest

from bench.spec import BENCH, ROOT, load_benchmark

CELLS = [w["name"] for w in load_benchmark()["workloads"]]
SCENARIOS = ("sound", "control", "unchanged", "half_batch", "altered")
CASES = [(c, s) for c in CELLS for s in SCENARIOS]


def _drive(cells, scenarios):
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    p = subprocess.run(
        [sys.executable, str(BENCH / "tests" / "fault_driver.py"),
         ",".join(cells), ",".join(scenarios)],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-4000:]
    return json.loads(p.stdout.strip().splitlines()[-1])


@pytest.fixture(scope="module")
def runs():
    return _drive(CELLS, SCENARIOS)


@pytest.mark.parametrize("cell,scenario", CASES,
                         ids=[f"{c}-{s}" for c, s in CASES])
def test_correct_separates_sound_from_broken(runs, cell, scenario):
    r = runs[f"{cell}/{scenario}"]
    assert r["correct"] is (scenario == "sound"), r["checks"]
