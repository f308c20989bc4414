"""The program's own spans and counters (``repro.analysis.trace``), for the
per-layer readers of a traced run: the tracer to hold on for the window,
the program's host spans in the profiler's trace, and interval sums over
them.  Where the program under test has no tracer, ``tracing`` yields None
and ``program_events`` finds nothing, so every reader of them returns
None.

A cell reads these once its traced run (1) runs the window inside
``with program_trace.tracing() as tracer:`` and (2) hands its readers
``rctx["tracer"] = tracer``, ``rctx["program_events"] =
program_trace.program_events(ctx["trace_dir"])`` and ``rctx["pauses"]``:
the ``(start_ns, end_ns)`` intervals, on ``time.perf_counter_ns`` (the
tracer's clock), in which the cell itself held the loop up by starting or
stopping the profiler.  Stopping it writes the trace and stalls the loop
for seconds, so the readers of the whole window leave out what overlaps a
pause.
"""
from __future__ import annotations

import contextlib
import glob
import math
import os

import harness

# the program's span names start with these (``serve_scheduled``,
# ``FederatedTrainer``)
PREFIXES = ("serve.", "fed.")


def tracing():
    """The program's ``trace.tracing()``, which yields its tracer; where
    the program has none, a context that yields None."""
    try:
        from repro.analysis import trace
    except ImportError:
        return contextlib.nullcontext()
    return trace.tracing()


def program_events(directory: str) -> list:
    """[(name, start_ns, dur_ns)] of every host event named by the
    program's spans in the newest ``.xplane.pb`` under ``directory``: the
    profiler's clock, the one ``harness.trace_events`` gives the device
    ops."""
    from jax.profiler import ProfileData
    files = sorted(glob.glob(os.path.join(directory, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not files:
        return []
    data = ProfileData.from_file(files[-1])
    return [(e.name, e.start_ns, e.duration_ns)
            for plane in data.planes if plane.name.startswith("/host:")
            for line in plane.lines for e in line.events
            if e.name.startswith(PREFIXES)]


def clear_of(pauses, start, end) -> bool:
    """Whether [start, end] overlaps none of the ``pauses``."""
    return not any(ps < end and start < pe for ps, pe in pauses)


def union(intervals) -> list:
    """Sorted disjoint [start, end] covering the ``(start, end)``
    intervals."""
    return harness.merged(intervals, -math.inf, math.inf)


def overlap_ns(a, b) -> float:
    """Length of the intersection of the unions of ``a`` and ``b``."""
    a, b = union(a), union(b)
    total, j = 0.0, 0
    for s, e in a:
        while j < len(b) and b[j][1] <= s:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            total += min(e, b[k][1]) - max(s, b[k][0])
            k += 1
    return total


def minus(a, b) -> list:
    """The union of ``a`` with the union of ``b`` taken out."""
    out = []
    b = union(b)
    for s, e in union(a):
        for bs, be in b:
            if be <= s or bs >= e:
                continue
            if bs > s:
                out.append([s, bs])
            s = max(s, be)
            if s >= e:
                break
        if s < e:
            out.append([s, e])
    return out


def idle_by_span(gaps, events) -> dict:
    """Nanoseconds of the idle ``gaps`` under each innermost program span
    (the shortest that covers the instant; ``serve.queued`` spans, which
    overlap the loop's own, left out), and under none ("none")."""
    spans = sorted(((s, s + d, n) for n, s, d in events
                    if n != "serve.queued"), key=lambda x: x[1] - x[0])
    out = {}
    rest = union(gaps)
    for s, e, name in spans:
        if not rest:
            break
        hit = overlap_ns(rest, [(s, e)])
        if hit > 0:
            out[name] = out.get(name, 0.0) + hit
            rest = minus(rest, [(s, e)])
    out["none"] = sum(e - s for s, e in rest)
    return out
