"""Drive whole runs of a cell on the CPU, at a size a test can hold, with
the timed path sound, computed in the lower precision (the control), or
broken underneath; print each run's ``correct`` and checked numbers.

    JAX_PLATFORMS=cpu python bench/tests/fault_driver.py CELL[,CELL] SCENARIO[,...]

Each run makes one timed call of ``BATCH`` LPs, so the sample the check
draws holds every answer of the window.  The cell's look for a chip is
skipped.
"""
import dataclasses
import functools
import json
import sys
import time
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench import measure, program  # noqa: E402
from bench.spec import load_cell  # noqa: E402

BATCH = 64
SEED = 2**31 + 77


def _edit(change):
    """An entry whose answers ``change`` alters after the program made
    them."""
    def entry(core, config):
        call = program.entry(core, config)

        def wrapped(batch):
            out = {k: np.array(v) for k, v in call(batch).items()}
            change(out)
            return out
        return wrapped
    return entry


def unchanged(optimal):
    """The solve returns its starting state: no pivot, x = 0, called
    optimal."""
    def change(out):
        for k in ("x", "objective", "iterations"):
            out[k][:] = 0
        out["status"][:] = optimal
    return change


def half_batch(out):
    """Half of the batch left out: its answers are the other half's."""
    h = len(out["objective"]) // 2
    for v in out.values():
        v[h:2 * h] = v[:h]


def altered(out):
    """One answer altered where it is produced."""
    out["objective"][0] *= 1.01


def main():
    cells, scenarios = sys.argv[1].split(","), sys.argv[2].split(",")
    core = program.load_core()
    import jax
    import jax.numpy as jnp
    jax.config.update("jax_enable_compilation_cache", False)
    # the look for a chip is skipped: this drives the rest of a run
    measure.init_jax = functools.partial(measure.init_jax, require_tpu=False)
    found = {}
    for name in cells:
        cell = load_cell(name)
        cell = dataclasses.replace(cell, traffic={**cell.traffic,
                                                  "batch": BATCH})
        for sc in scenarios:
            entry = None
            if sc == "control":
                entry = functools.partial(program.entry, dtype=jnp.bfloat16)
            elif sc != "sound":
                entry = _edit({"unchanged": unchanged(core.OPTIMAL),
                               "half_batch": half_batch,
                               "altered": altered}[sc])
            r = measure.run_cell(cell, SEED, 0.0, False,
                                 t_start=time.perf_counter(), entry=entry,
                                 log=lambda msg: None)
            found[f"{name}/{sc}"] = {"correct": r["correct"],
                                     "checks": r["checks"]}
    print(json.dumps(found))


if __name__ == "__main__":
    main()
