"""Mean over traced calls of (max - mean) / max of per-chip busy time
inside the call, in percent (``devtrace.Trace.imbalance``): how long the
other chips idle while the slowest chip's loop still pivots.  None where
no call kept a chip busy."""


def read(run):
    share = run.trace.imbalance()
    return None if share is None else 100.0 * share
