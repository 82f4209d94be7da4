"""Solver observability plane: on-device counters, span tracing, reports.

Three layers (see ``docs/architecture.md`` § Observability):

* ``obs.telemetry`` — ``TelemetryState``, the per-LP counter pytree that
  rides through engine states, compaction gathers, the chunked driver and
  the Pallas segment kernels when ``telemetry=True``.
* ``obs.trace`` — ``span``, the library's host spans: profiler annotations
  on the device trace's clock, also recorded in a ``SpanTracer`` (nested
  wall-clock spans with a JSONL event stream) when one is active.
* ``obs.report`` — ``SolveReport``, the per-solve aggregate attached as
  ``LPResult.stats``.

``obs.work`` holds the shared tableau-element work accounting used by both
``analysis/lp_perf.py`` and ``benchmarks/pivot_work.py``.
"""
from .report import SolveReport, report_from_counters
from .telemetry import (ALL_LANES, F32_LANES, INT_LANES, TelemetryState,
                        init_telemetry, tel_to_numpy)
from .trace import Span, SpanTracer, span, tagged
from .work import element_updates_lockstep, lockstep_steps

__all__ = [
    "SolveReport", "report_from_counters",
    "TelemetryState", "init_telemetry", "tel_to_numpy",
    "ALL_LANES", "INT_LANES", "F32_LANES",
    "Span", "SpanTracer", "span", "tagged",
    "element_updates_lockstep", "lockstep_steps",
]
