"""The same reader as ``device_idle_pct.batch``, for the loop cell."""
from bench.spec import metric_reader

read = metric_reader("device_idle_pct.batch")
