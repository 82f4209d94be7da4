"""Mean of the iterations the program returned, over the LPs of the
window's calls: an exact count of pivots."""
import numpy as np


def read(run):
    its = np.concatenate([c.out["iterations"] for c in run.calls])
    return float(its.mean())
