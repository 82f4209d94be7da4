"""95th percentile of call latency, call to host arrays, over every call
of the window (host clock)."""
import numpy as np


def read(run):
    return float(np.percentile(run.latencies_s, 95)) * 1e3
