"""The plain reference: each LP solved on its own by HiGHS (SciPy), in
float64, from the benchmark's own data.  It imports nothing of the
program under test."""
from __future__ import annotations

import numpy as np
from scipy.optimize import linprog

from bench.gen import LPData

# HiGHS statuses as scipy.optimize.linprog reports them.
_STATUS = {0: "optimal", 2: "infeasible", 3: "unbounded"}


def solve(data: LPData) -> tuple:
    """(status names, objectives) of every LP in ``data``, in the LP's own
    sense and with its constant ``c0``; the objective is NaN unless the
    status is "optimal"."""
    lo, hi = data.row_bounds()
    sign = -1.0 if data.maximize else 1.0
    eq = lo == hi                                      # (B, m)
    status, obj = [], np.full(data.batch, np.nan)
    for i in range(data.batch):
        A = data.A[i]
        up = np.isfinite(hi[i]) & ~eq[i]
        dn = np.isfinite(lo[i]) & ~eq[i]
        A_ub = np.concatenate([A[up], -A[dn]])
        b_ub = np.concatenate([hi[i][up], -lo[i][dn]])
        r = linprog(sign * data.c[i],
                    A_ub=A_ub if len(b_ub) else None,
                    b_ub=b_ub if len(b_ub) else None,
                    A_eq=A[eq[i]] if eq[i].any() else None,
                    b_eq=lo[i][eq[i]] if eq[i].any() else None,
                    bounds=np.stack([data.lb[i], data.ub[i]], axis=1),
                    method="highs")
        status.append(_STATUS.get(r.status, "other"))
        if r.status == 0:
            obj[i] = sign * r.fun + data.c0[i]
    return np.array(status), obj
