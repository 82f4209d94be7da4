"""Device memory held at its peak (``measure.peak_bytes``: buffers in use
plus the programs' reserved temporaries) over ``bytes_limit``, on the
fullest device after the window, in percent; None where the device
reports no limit."""
from bench.measure import peak_bytes


def read(run):
    shares = [peak_bytes(s) / s["bytes_limit"] for s in run.memory
              if s.get("bytes_limit")]
    return 100.0 * max(shares) if shares else None
