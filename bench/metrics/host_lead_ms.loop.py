"""The same reader as ``host_lead_ms.batch``, for the loop cell."""
from bench.spec import metric_reader

read = metric_reader("host_lead_ms.batch")
