"""The program's own host spans (``lp.solve``, ``lp.h2d``, ``lp.d2h`` ...,
written by ``repro.obs.trace.span``), reduced per call.

``read`` and ``breakdown`` work on the ``lp.*`` host events of a
``jax.profiler`` trace, on the same clock as the device ops of a
``devtrace.Trace``: where the device idles inside a call, which span the
host was in.  They take plain ``(name, start_ns, end_ns)`` tuples, so the
tests drive them with synthetic events.  ``replay`` records the same spans
with the profiler off, through a ``SpanTracer``.  ``span_report.py``
prints both for a cell.
"""
from __future__ import annotations

import bisect
import collections
import glob
import os

from bench import devtrace, program

PREFIX = "lp."
NONE = "none"          # time with no lp.* span open


def replay(config: dict, pool: list) -> list:
    """Seconds per span name, summed within each call, for one call per
    batch of ``pool`` with a ``SpanTracer`` active."""
    core = program.load_core()
    from repro.obs import SpanTracer
    call = program.entry(core, config)
    per_call = []
    for data in pool:
        batch = program.to_input(core, data)
        tracer = SpanTracer()
        with tracer.active():
            call(batch)
        sums = collections.Counter()
        for root in tracer.roots:
            for s in root.walk():
                sums[s.name] += s.dur_s
        per_call.append(sums)
    return per_call


def mean_ms(per_call: list) -> dict:
    """Per span name, the mean milliseconds per call, longest first."""
    total = collections.Counter()
    for c in per_call:
        total.update(c)
    return _longest_first(total, 1e3 / len(per_call))


def read(log_dir: str) -> list:
    """The ``lp.*`` host events of the trace ``jax.profiler`` wrote under
    ``log_dir``, as ``(name, start_ns, end_ns)``, outer before inner."""
    from jax.profiler import ProfileData
    paths = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(paths) != 1:
        raise RuntimeError(f"expected one trace under {log_dir}, found {paths}")
    out = []
    for plane in ProfileData.from_file(paths[0]).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                out += [(ev.name, ev.start_ns, ev.end_ns) for ev in line.events
                        if ev.name.startswith(PREFIX)]
    return sorted(out, key=lambda s: (s[1], -s[2]))


def _in_calls(calls: list, spans: list) -> list:
    """The spans that start inside each call, per call."""
    starts = [s for s, _ in calls]
    out = [[] for _ in calls]
    for sp in spans:
        k = bisect.bisect_right(starts, sp[1]) - 1
        if k >= 0 and sp[1] <= calls[k][1]:
            out[k].append(sp)
    return out


def _innermost(spans: list, t: float) -> str:
    inner = max((sp for sp in spans if sp[1] <= t < sp[2]),
                key=lambda sp: (sp[1], -sp[2]), default=None)
    return NONE if inner is None else inner[0]


def _by_innermost(lo: float, hi: float, spans: list, into: dict) -> None:
    """Add the length of [lo, hi] to ``into``, cut where spans open and
    close, each piece under the innermost span open over it."""
    cuts = sorted({lo, hi} | {t for _, s, e in spans for t in (s, e)
                              if lo < t < hi})
    for a, b in zip(cuts, cuts[1:]):
        into[_innermost(spans, (a + b) / 2)] += b - a


def _longest_first(d: dict, scale: float) -> dict:
    return {k: v * scale for k, v in sorted(d.items(), key=lambda kv: -kv[1])}


def breakdown(trace: devtrace.Trace, spans: list) -> dict:
    """Where the host was while the device idled, over the trace's calls:

    * ``span_ms``: per span name, the mean milliseconds per call;
    * ``idle_by_span``: seconds in which no device ran an op, summed over
      calls by the innermost span open, ``"none"`` where none was;
    * ``lead_by_span`` / ``tail_by_span``: the mean milliseconds from a
      call's start to its first device op / from its last op to its end,
      by the innermost span open (the parts of ``host_lead_ms`` and
      ``host_tail_ms``), over calls that ran an op;
    * ``first_op_in``: how many calls' first op started in each span."""
    union = devtrace.merge(iv for d in trace.devices.values()
                           for iv in d.intervals())
    per_call = _in_calls(trace.calls, spans)
    idle, lead, tail = (collections.Counter() for _ in range(3))
    first_in, n = collections.Counter(), 0
    for (s, e), cs in zip(trace.calls, per_call):
        for g0, g1 in devtrace.gaps(union, s, e):
            _by_innermost(g0, g1, cs, idle)
        first, last = trace._edges(s, e)
        if first is not None:
            n += 1
            _by_innermost(s, first, cs, lead)
            _by_innermost(last, e, cs, tail)
            first_in[_innermost(cs, first)] += 1
    total = collections.Counter()
    for cs in per_call:
        for name, s, e in cs:
            total[name] += e - s
    return {"span_ms": _longest_first(total, 1 / len(trace.calls) / 1e6),
            "idle_by_span": _longest_first(idle, 1e-9),
            "lead_by_span": _longest_first(lead, 1 / max(n, 1) / 1e6),
            "tail_by_span": _longest_first(tail, 1 / max(n, 1) / 1e6),
            "first_op_in": dict(first_in.most_common())}
