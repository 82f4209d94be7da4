"""The least time the chip could take for the LPs' essential work.

Defined on the work any implementation must do, so it reads the same
whichever engine or kernel the program picks, and it cannot pass 100%:

* operations: one rank-1 update of the (m+1) x (n+1) dictionary per pivot,
  ``2 (m+1)(n+1)`` per returned iteration, at the shape the entry point
  received;
* bytes: the LP data in float32 (A, the right-hand side, c, and bounds
  where the configuration has any) read once, and x (float32), the
  objective (float32) and the status (int8) written once;
* least time: the larger of operations over the chips' peak FLOP/s and
  bytes over their peak bytes/s (``peaks.json``).
"""
from __future__ import annotations

import numpy as np


def ops_per_iteration(m: int, n: int) -> int:
    return 2 * (m + 1) * (n + 1)


def bytes_per_lp(data) -> int:
    m, n = data.shape
    bounds = int(bool(np.any(data.lb != 0.0))) + int(bool(np.any(np.isfinite(data.ub))))
    return 4 * (m * n + m + n + bounds * n) + 4 * n + 4 + 1


def least_time_s(run) -> tuple:
    """(seconds, "compute" or "memory") for the traced calls."""
    ops = bytes_ = 0
    for c in run.traced_calls:
        data = run.data[c.pool_index]
        ops += int(np.sum(c.out["iterations"], dtype=np.int64)) \
            * ops_per_iteration(*data.shape)
        bytes_ += data.batch * bytes_per_lp(data)
    chips = len(run.devices)
    peaks = run.peaks
    t_ops = ops / (peaks["flops_per_s"] * chips)
    t_bytes = bytes_ / (peaks["bytes_per_s"] * chips)
    return (t_ops, "compute") if t_ops >= t_bytes else (t_bytes, "memory")


def share_pct(run):
    """Least time over the device-busy time inside calls, in percent;
    None where no device op ran inside a call."""
    busy = run.trace.busy_in_calls_s()
    if busy <= 0:
        return None
    return 100.0 * least_time_s(run)[0] / busy
