"""1 - (union of device-op intervals) / (traced window), in percent;
the mean over devices."""


def read(run):
    return 100.0 * run.trace.idle_share()
