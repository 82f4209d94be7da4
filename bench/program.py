"""The system under test, as the benchmark drives it: its inputs, its entry
points and its answers.  Nothing else of the program is used.

The configuration's ``entry`` names the entry point of ``repro.core``.  A
cell hands it the batch alone.  It never names an engine, a pricing rule,
a chunk size or a kernel, so what is measured is what the program
chooses.  ``entry_kwargs`` exists for the
lower-precision control (``dtype``) and is empty in every benchmark run.
"""
from __future__ import annotations

import sys

import numpy as np

from bench.gen import LPData
from bench.spec import ROOT


def load_core():
    """``repro.core`` from the checkout's ``src``."""
    src = str(ROOT / "src")
    if src not in sys.path:
        sys.path.insert(0, src)
    import repro.core
    return repro.core


def to_input(core, data: LPData):
    """The batch as a user hands it to the program."""
    if data.form == "standard":
        return core.LPBatch(A=data.A, b=data.rhs, c=data.c)
    return core.GeneralLPBatch.from_arrays(
        data.A, data.sense, data.rhs, lb=data.lb, ub=data.ub, c=data.c,
        c0=data.c0, maximize=data.maximize, ranges=data.ranges)


def entry(core, config: dict, **entry_kwargs):
    """A function of one batch that runs the configuration's entry point
    and returns x, objective, status codes and iterations as host NumPy
    arrays: reading them waits for the device."""
    fn = getattr(core, config["entry"])

    def call(batch):
        res = fn(batch, **entry_kwargs)
        return {"x": np.asarray(res.x), "objective": np.asarray(res.objective),
                "status": np.asarray(res.status),
                "iterations": np.asarray(res.iterations)}
    return call


def name_statuses(core, out: dict) -> dict:
    """``out`` with its status codes replaced by the program's names for
    them ("optimal", "unbounded", "infeasible", "iteration_limit")."""
    codes = out["status"].astype(np.int64)
    table = np.array([core.STATUS_NAMES.get(k, "unknown")
                      for k in range(max(codes.max(initial=0), 0) + 1)])
    named = np.where(codes >= 0, table[np.clip(codes, 0, None)], "unknown")
    return {**out, "status": named}
