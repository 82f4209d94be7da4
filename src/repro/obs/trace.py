"""Host-side spans of a solve, on the profiler's clock, and a structured
event stream.

``span(name, tracer=None, **args)`` is the library's one span mechanism.
It always enters ``jax.profiler.TraceAnnotation(name, **args)``: a host
event that costs under a microsecond when no profiler is tracing, and that
a ``jax.profiler`` trace records, with its args, on the same clock as the
device's operations.  When a ``SpanTracer`` is passed as ``tracer=``, or
installed for a block with ``tracer.active()``, the span is recorded there
too, as a tree of ``Span`` objects with wall-clock bounds and the same
args; instantaneous events (an LP retiring, a branch-and-bound node
fathoming, a frontier admit) land in the same tracer.

The library's spans share the prefix ``lp.``.  One ``solve_batched`` call
is an ``lp.solve`` span holding ``lp.canonicalize`` (general-form input
only, with ``.presolve``, ``.build`` and ``.scale`` stages), ``lp.plan``,
then per chunk ``lp.h2d`` (``.cast``, ``.put``), ``lp.dispatch``,
``lp.wait`` and ``lp.d2h``, and last ``lp.recover`` (general-form input
only).  ``tagged(chunk=i)`` adds the chunk index to every span of a chunk.

For a timeline, trace with ``jax.profiler`` (``create_perfetto_trace=True``
also writes a file that https://ui.perfetto.dev opens, device ops and
spans on one timeline); ``SpanTracer.to_jsonl()`` is the structured event
stream.
"""
from __future__ import annotations

import contextvars
import dataclasses
import json
import time
from contextlib import contextmanager
from typing import Any, Callable, Optional

from jax.profiler import TraceAnnotation

_ACTIVE: contextvars.ContextVar = contextvars.ContextVar(
    "repro_span_tracer", default=None)
_TAGS: contextvars.ContextVar = contextvars.ContextVar(
    "repro_span_tags", default=None)


@dataclasses.dataclass
class Span:
    """One timed region.  ``t0``/``t1`` are seconds on the tracer clock."""

    name: str
    t0: float
    t1: float = 0.0
    depth: int = 0
    args: dict = dataclasses.field(default_factory=dict)
    children: list = dataclasses.field(default_factory=list)
    events: list = dataclasses.field(default_factory=list)

    @property
    def dur_s(self) -> float:
        return max(0.0, self.t1 - self.t0)

    def to_dict(self) -> dict:
        return {
            "type": "span", "name": self.name, "t0": self.t0, "t1": self.t1,
            "dur_s": self.dur_s, "depth": self.depth, "args": dict(self.args),
            "children": [c.to_dict() for c in self.children],
            "events": [dict(e) for e in self.events],
        }

    def walk(self):
        yield self
        for c in self.children:
            yield from c.walk()


class OpenSpan:
    """The context ``span`` returns: a profiler annotation, and the
    tracer's ``Span`` (``record``) while one is recording.  The spans
    opened inside a recorded span are recorded in the same tracer."""

    __slots__ = ("_ann", "_tracer", "_name", "_args", "_token", "record")

    def __init__(self, name: str, tracer, args: dict):
        self._ann = TraceAnnotation(name, **args)
        self._tracer = tracer
        self._name = name
        self._args = args
        self._token = None
        self.record: Optional[Span] = None

    def __enter__(self) -> "OpenSpan":
        self._ann.__enter__()
        if self._tracer is not None:
            self.record = self._tracer._open(self._name, self._args)
            self._token = _ACTIVE.set(self._tracer)
        return self

    def set(self, **args: Any) -> None:
        """Add args known only inside the span (a planned chunk size, the
        bytes a put made, a segment's survivors)."""
        self._ann.set_metadata(**args)
        if self.record is not None:
            self.record.args.update(args)

    def __exit__(self, *exc) -> bool:
        if self.record is not None:
            _ACTIVE.reset(self._token)
            self._tracer._close(self.record)
        self._ann.__exit__(*exc)
        return False


def span(name: str, tracer: "SpanTracer | None" = None,
         **args: Any) -> OpenSpan:
    """A span named ``name`` with scalar ``args``: always a profiler
    annotation, and a record in ``tracer`` (default: the tracer installed
    by ``SpanTracer.active``, if any)."""
    tags = _TAGS.get()
    if tags:
        args = {**tags, **args}
    return OpenSpan(name, tracer if tracer is not None else _ACTIVE.get(),
                    args)


@contextmanager
def tagged(**args: Any):
    """Every span opened inside the block carries ``args`` as well."""
    token = _TAGS.set({**(_TAGS.get() or {}), **args})
    try:
        yield
    finally:
        _TAGS.reset(token)


class SpanTracer:
    """Records a tree of nested spans plus instantaneous events."""

    def __init__(self, clock: Callable[[], float] = time.perf_counter):
        self._clock = clock
        self._origin = clock()
        self._stack: list[Span] = []
        self.roots: list[Span] = []
        self.root_events: list[dict] = []  # events recorded with no open span
        self._log: list[dict] = []  # completion-order structured stream

    def _now(self) -> float:
        return self._clock() - self._origin

    def span(self, name: str, **args: Any) -> OpenSpan:
        return span(name, tracer=self, **args)

    @contextmanager
    def active(self):
        """Record every span of the block here, the library's own spans
        included, without passing ``tracer=`` down."""
        token = _ACTIVE.set(self)
        try:
            yield self
        finally:
            _ACTIVE.reset(token)

    def _open(self, name: str, args: dict) -> Span:
        s = Span(name=name, t0=self._now(), depth=len(self._stack),
                 args=dict(args))
        (self._stack[-1].children if self._stack else self.roots).append(s)
        self._stack.append(s)
        return s

    def _close(self, s: Span) -> None:
        s.t1 = self._now()
        self._stack.pop()
        d = s.to_dict()
        d.pop("children")  # the stream is flat; nesting is via depth
        d.pop("events")
        self._log.append(d)

    def event(self, name: str, **args: Any) -> None:
        """Record an instantaneous event under the current span (or at the
        root when no span is open)."""
        e = {"type": "event", "name": name, "ts": self._now(),
             "depth": len(self._stack), "args": dict(args)}
        target = self._stack[-1].events if self._stack else self.root_events
        target.append({"name": name, "ts": e["ts"], "args": e["args"]})
        self._log.append(e)

    def to_jsonl(self, path: str | None = None) -> str:
        """Structured event stream: one JSON object per line, in completion
        order (events when recorded, spans when closed)."""
        text = "\n".join(json.dumps(rec, sort_keys=True) for rec in self._log)
        if path is not None:
            with open(path, "w") as fh:
                fh.write(text + ("\n" if text else ""))
        return text
