"""LPs returned by the window's calls over the window (host clock)."""


def read(run):
    return run.lps / run.window_s
