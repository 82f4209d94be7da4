"""The same reader as ``solve_roofline_pct.batch``, for the loop cell."""
from bench.spec import metric_reader

read = metric_reader("solve_roofline_pct.batch")
