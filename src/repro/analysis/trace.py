"""Program spans and counters: the one tracing system of the host loops.

``span(name, **attrs)`` times a block of host code, ``count(name, n)`` adds
to a counter, and ``tracing()`` installs a :class:`Tracer` that records
both for its extent::

    with trace.tracing() as t:
        serve_scheduled(...)
    t.counters["serve.dispatches"]
    t.write("serve.trace.json")      # Chrome trace events; Perfetto reads it

Tracing is off by default.  Off, ``span`` returns one shared no-op object
(no clock read, no record, no profiler annotation) and ``count`` is one
global check, so instrumented loops cost a few hundred nanoseconds a call.

On, a span records its name, start and end (``time.perf_counter_ns``), the
index of the span open around it (its parent, -1 at top level) and its
attrs.  Each span is also entered as a ``jax.profiler.TraceAnnotation`` of
the same name, so a profiled run carries the program's spans on the
profiler's host plane, on the device ops' clock.  Every backend compile
becomes a ``compile`` span under the span that was open.  Records stay in
memory up to ``capacity`` spans; those beyond are counted in ``dropped``.

A tracer serves the one thread that runs the traced loop.
"""
from __future__ import annotations

import contextlib
import dataclasses
import json
import time

import jax

# jax.monitoring's event for one backend compile (a cache load included)
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"

_tracer: Tracer | None = None
_listening = False


@dataclasses.dataclass(slots=True, eq=False)
class SpanRecord:
    """One span: ``end_ns`` is None while it is open; ``parent`` is the
    index of the span open around it, -1 at top level."""

    name: str
    start_ns: int
    end_ns: int | None
    parent: int
    attrs: dict

    @property
    def seconds(self) -> float:
        return (self.end_ns - self.start_ns) / 1e9


class Tracer:
    """The spans and counters of one ``tracing()`` extent."""

    def __init__(self, capacity: int = 1 << 20):
        self.capacity = capacity
        self.spans: list[SpanRecord] = []
        self.counters: dict[str, int] = {}
        self.dropped = 0
        self._open: list[int] = []     # indices of the open spans, inner last

    def _parent(self) -> int:
        return self._open[-1] if self._open else -1

    def _add(self, rec: SpanRecord) -> int:
        """Append ``rec``; its index, or -1 when the buffer is full."""
        if len(self.spans) >= self.capacity:
            self.dropped += 1
            return -1
        self.spans.append(rec)
        return len(self.spans) - 1

    def named(self, name: str) -> list[SpanRecord]:
        return [s for s in self.spans if s.name == name]

    def write(self, path: str) -> None:
        """The closed spans as Chrome trace-event JSON (``"ph": "X"``,
        microseconds; attrs and the parent index under ``args``), and each
        counter's total as a counter event at the last span's end."""
        events = [{"name": s.name, "ph": "X", "pid": 1, "tid": 1,
                   "ts": s.start_ns / 1e3,
                   "dur": (s.end_ns - s.start_ns) / 1e3,
                   "args": {**s.attrs, "index": i, "parent": s.parent}}
                  for i, s in enumerate(self.spans) if s.end_ns is not None]
        last = max((e["ts"] + e["dur"] for e in events), default=0.0)
        events += [{"name": k, "ph": "C", "pid": 1, "tid": 1, "ts": last,
                    "args": {"value": v}} for k, v in self.counters.items()]
        with open(path, "w") as f:
            json.dump({"traceEvents": events, "displayTimeUnit": "ms",
                       "otherData": {"dropped_spans": self.dropped}}, f)


class _Span:
    __slots__ = ("tracer", "rec", "ann")

    def __init__(self, tracer: Tracer, name: str, attrs: dict):
        self.tracer = tracer
        self.rec = SpanRecord(name, 0, None, -1, attrs)

    def __enter__(self):
        self.rec.parent = self.tracer._parent()
        self.ann = jax.profiler.TraceAnnotation(self.rec.name)
        self.ann.__enter__()
        self.tracer._open.append(self.tracer._add(self.rec))
        self.rec.start_ns = time.perf_counter_ns()
        return self

    def __exit__(self, *exc):
        self.rec.end_ns = time.perf_counter_ns()
        self.tracer._open.pop()
        self.ann.__exit__(*exc)
        return False


class _NoSpan:
    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


_NO_SPAN = _NoSpan()


def span(name: str, **attrs):
    """A context manager that records a span while tracing is on."""
    t = _tracer
    if t is None:
        return _NO_SPAN
    return _Span(t, name, attrs)


def since(name: str, t_start: float, **attrs) -> None:
    """Record a span from ``t_start`` (a ``time.monotonic()`` reading in
    the past) to now, under the open span: for waits that began before
    the code that ends them ran, such as a request's time in a queue."""
    t = _tracer
    if t is None:
        return
    end = time.perf_counter_ns()
    start = end - int((time.monotonic() - t_start) * 1e9)
    t._add(SpanRecord(name, start, end, t._parent(), attrs))


def count(name: str, n: int = 1) -> None:
    """Add ``n`` to a counter while tracing is on."""
    t = _tracer
    if t is not None:
        t.counters[name] = t.counters.get(name, 0) + n


def enabled() -> bool:
    return _tracer is not None


def _on_duration(event: str, duration: float, **kw) -> None:
    t = _tracer
    if t is not None and event == COMPILE_EVENT:
        end = time.perf_counter_ns()
        t._add(SpanRecord("compile", end - int(duration * 1e9), end,
                          t._parent(), {}))


@contextlib.contextmanager
def tracing(capacity: int = 1 << 20):
    """Install a fresh :class:`Tracer` for the extent of the ``with``
    block and yield it; the tracer installed before (if any) comes back
    after."""
    global _tracer, _listening
    if not _listening:
        jax.monitoring.register_event_duration_secs_listener(_on_duration)
        _listening = True
    prev, _tracer = _tracer, Tracer(capacity)
    try:
        yield _tracer
    finally:
        _tracer = prev


@contextlib.contextmanager
def written_to(path: str | None):
    """Trace the ``with`` block and write the trace to ``path`` when it
    ends, however it ends; without a path, trace nothing."""
    if not path:
        yield None
        return
    with tracing() as t:
        try:
            yield t
        finally:
            t.write(path)
