"""The least time for the LPs' essential work (bench/roofline.py) over
the device-busy time inside calls, in percent."""
from bench import roofline


def read(run):
    return roofline.share_pct(run)
