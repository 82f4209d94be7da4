"""From a ``jax.profiler`` trace to the intervals the per-layer metrics
read: the benchmark's own call annotations on the host, and the operations
each device ran, on one clock.

Busy time is the union of a device's operation intervals; its idle share
is one minus busy over the traced window (first call's start to last
call's end).  Everything here works on plain ``(start_ns, end_ns)``
intervals, so the tests drive it with synthetic events.
"""
from __future__ import annotations

import bisect
import dataclasses
import glob
import os
import re

CALL = "bench_call"          # the benchmark's TraceAnnotation around a call
DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"


def short_name(op: str) -> str:
    """An op's HLO instruction name and opcode ("fusion.278 (fusion)")
    from the full instruction text the TPU trace gives as its name."""
    m = re.match(r"%?([^\s=]+) = .*?\s([a-z][a-z0-9_-]*)\(", op)
    return f"{m[1]} ({m[2]})" if m else op[:100]


def merge(intervals) -> list:
    """The union of intervals, as sorted disjoint [start, end] pairs."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


class Busy:
    """Merged intervals of one device, with prefix sums, so that the busy
    time and the first and last op inside any span cost a bisection."""

    def __init__(self, merged: list):
        self.starts = [s for s, _ in merged]
        self.ends = [e for _, e in merged]
        self.prefix = [0.0]
        for s, e in merged:
            self.prefix.append(self.prefix[-1] + (e - s))

    def _span(self, lo, hi) -> tuple:
        return bisect.bisect_right(self.ends, lo), \
            bisect.bisect_left(self.starts, hi)

    def covered(self, lo: float, hi: float) -> float:
        """Length of [lo, hi] the intervals cover."""
        i, j = self._span(lo, hi)
        if i >= j:
            return 0.0
        return (self.prefix[j] - self.prefix[i]
                - max(0.0, lo - self.starts[i]) - max(0.0, self.ends[j - 1] - hi))

    def edges(self, lo: float, hi: float):
        """(first start, last end) of the intervals inside [lo, hi], or
        None where none falls inside."""
        i, j = self._span(lo, hi)
        if i >= j:
            return None
        return max(self.starts[i], lo), min(self.ends[j - 1], hi)

    def intervals(self) -> list:
        return list(zip(self.starts, self.ends))


def gaps(merged: list, lo: float, hi: float) -> list:
    """The parts of [lo, hi] that the merged intervals leave uncovered."""
    out, t = [], lo
    for s, e in merged:
        if e <= lo or s >= hi:
            continue
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if t < hi:
        out.append((t, hi))
    return out


@dataclasses.dataclass
class Trace:
    calls: list        # [(start_ns, end_ns)] of the benchmark's calls
    devices: dict      # device plane name -> Busy
    op_ns: dict        # op name -> nanoseconds, summed over devices

    @property
    def window(self) -> tuple:
        return self.calls[0][0], self.calls[-1][1]

    def window_s(self) -> float:
        lo, hi = self.window
        return (hi - lo) / 1e9

    def busy_s(self) -> float:
        """Device-busy seconds in the window, averaged over devices."""
        lo, hi = self.window
        return sum(d.covered(lo, hi) for d in self.devices.values()) \
            / len(self.devices) / 1e9

    def idle_share(self) -> float:
        return 1.0 - self.busy_s() / self.window_s()

    def busy_outside_calls_s(self) -> float:
        """Device-busy seconds in the window but outside every call,
        averaged over devices: in a closed loop, a measure of how far the
        device clock is off the host's."""
        return self.busy_s() - self.busy_in_calls_s()

    def busy_in_calls_s(self) -> float:
        """Device-busy seconds inside calls, averaged over devices."""
        return sum(d.covered(s, e) for d in self.devices.values()
                   for s, e in self.calls) / len(self.devices) / 1e9

    def _edges(self, s, e):
        """(first op start, last op end) inside [s, e] over all devices,
        or (None, None)."""
        found = [x for x in (d.edges(s, e) for d in self.devices.values()) if x]
        if not found:
            return None, None
        return min(f for f, _ in found), max(last for _, last in found)

    def lead_tail_ms(self) -> tuple:
        """Mean over calls that ran a device op of (call start to first op,
        last op to call end), in milliseconds; (None, None) if none did."""
        leads, tails = [], []
        for s, e in self.calls:
            first, last = self._edges(s, e)
            if first is not None:
                leads.append(first - s)
                tails.append(e - last)
        if not leads:
            return None, None
        return (sum(leads) / len(leads) / 1e6, sum(tails) / len(tails) / 1e6)

    def imbalance(self) -> float | None:
        """Mean over calls of (max - mean) / max of per-device busy time
        inside the call; None where no call kept a device busy."""
        shares = []
        for s, e in self.calls:
            busy = [d.covered(s, e) for d in self.devices.values()]
            if max(busy) > 0:
                shares.append((max(busy) - sum(busy) / len(busy)) / max(busy))
        return sum(shares) / len(shares) if shares else None

    def breakdown(self, top: int = 10) -> dict:
        """The device ops with most time (seconds per device; a loop's op
        holds its body's ops, which are listed too) and the
        longest gaps in which no device ran anything, named by where the
        host was: before a call's first op, after its last, between two of
        its ops, or between calls."""
        n = len(self.devices)
        ops = sorted(self.op_ns.items(), key=lambda kv: -kv[1])[:top]
        lo, hi = self.window
        union = merge(iv for d in self.devices.values()
                      for iv in d.intervals())
        starts = [s for s, _ in self.calls]
        cuts = sorted(t for call in self.calls for t in call)
        named = []
        for g0, g1 in gaps(union, lo, hi):
            # a gap that spans a call's start or end is split there
            inner = cuts[bisect.bisect_right(cuts, g0):bisect.bisect_left(cuts, g1)]
            for a, b in zip([g0] + inner, inner + [g1]):
                k = bisect.bisect_right(starts, a) - 1
                where = "between_calls"
                if k >= 0 and b <= self.calls[k][1]:
                    first, last = self._edges(*self.calls[k])
                    where = ("in_call.lead" if first is None or b <= first
                             else "in_call.tail" if a >= last
                             else "in_call.mid")
                named.append([where, (b - a) / 1e9])
        named.sort(key=lambda kv: -kv[1])
        return {"device_ops": [[k, v / n / 1e9] for k, v in ops],
                "idle_gaps": named[:top]}


def from_events(calls, device_ops: dict) -> Trace:
    """``calls``: [(start_ns, end_ns)]; ``device_ops``: device name ->
    [(name, start_ns, end_ns)]."""
    op_ns = {}
    for events in device_ops.values():
        for name, s, e in events:
            key = short_name(name)
            op_ns[key] = op_ns.get(key, 0.0) + (e - s)
    return Trace(calls=sorted(calls),
                 devices={d: Busy(merge((s, e) for _, s, e in ev))
                          for d, ev in sorted(device_ops.items())},
                 op_ns=op_ns)


def read(log_dir: str, n_devices: int) -> Trace:
    """The trace ``jax.profiler`` wrote under ``log_dir``: the call
    annotations of the host plane and the "XLA Ops" line of the first
    ``n_devices`` TPU planes."""
    from jax.profiler import ProfileData
    paths = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(paths) != 1:
        raise RuntimeError(f"expected one trace under {log_dir}, found {paths}")
    data = ProfileData.from_file(paths[0])
    calls, device_ops = [], {}
    for plane in data.planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                calls += [(ev.start_ns, ev.end_ns) for ev in line.events
                          if ev.name == CALL]
        elif plane.name.startswith(DEVICE_PREFIX):
            for line in plane.lines:
                if line.name == OPS_LINE:
                    device_ops[plane.name] = [
                        (ev.name, ev.start_ns, ev.end_ns) for ev in line.events]
    if not calls:
        raise RuntimeError("the trace holds no call annotations")
    if len(device_ops) < n_devices:
        names = [(p.name, [ln.name for ln in p.lines]) for p in data.planes]
        raise RuntimeError(f"the trace holds {len(device_ops)} device planes "
                           f"with '{OPS_LINE}', {n_devices} expected: {names}")
    keep = sorted(device_ops, key=_device_index)[:n_devices]
    return from_events(calls, {d: device_ops[d] for d in keep})


def _device_index(name: str) -> int:
    tail = name[len(DEVICE_PREFIX):]
    return int(tail) if tail.isdigit() else 1 << 30
