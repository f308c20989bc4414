"""The readers of the program's own spans and counters
(``bench/program_trace.py`` and the metrics that use it), on hand-made
records and events; and the program's spans on the profiler's host plane
in a trace recorded on the CPU."""
import sys

import numpy as np
import pytest

import harness
import program_trace
from repro.analysis import trace
from repro.analysis.trace import SpanRecord, Tracer

MS = 10 ** 6


def tracer_of(records, counters=None):
    t = Tracer()
    t.spans = [SpanRecord(*r) for r in records]
    t.counters = dict(counters or {})
    return t


def test_queue_wait_p95_by_hand():
    # requests waiting 1..20 ms, beside an admit span that is not a wait
    t = tracer_of([("serve.queued", 5 * MS, (5 + k) * MS, 0, {"rid": k})
                   for k in range(1, 21)]
                  + [("serve.admit", 0, 500 * MS, -1, {})])
    read = lambda ctx: harness.read_metric("queue_wait_ms_p95.serve", ctx)
    assert read({"tracer": t}) == pytest.approx(
        np.percentile(np.arange(1, 21), 95))
    # a pause at 20-21 ms overlaps the waits of 16 ms and longer
    assert read({"tracer": t, "pauses": [(20 * MS, 21 * MS)]}) == \
        pytest.approx(np.percentile(np.arange(1, 16), 95))
    assert read({"tracer": tracer_of([])}) is None
    assert read({"tracer": None}) is None
    assert read({}) is None


def test_idle_host_and_idle_split_by_hand():
    # ns: ops [0,10) [30,50) [80,100); slice [0,110), so idle [10,30)
    # [50,80) [100,110).  Boundary [0,60) holds evict [10,20) and admit
    # [20,25); boundary [60,100) holds wait [60,75); a request's queued
    # span covers everything and is not host work
    events = {"device": [["a", 0, 10], ["b", 30, 20], ["a", 80, 20]],
              "host": [["bench.slice", 0, 110]]}
    program = [("serve.queued", 0, 110), ("serve.boundary", 0, 60),
               ("serve.chunk.evict", 10, 10), ("serve.admit", 20, 5),
               ("serve.boundary", 60, 40), ("serve.wait", 60, 15)]
    split = program_trace.idle_by_span(
        harness.idle_gaps(events, 0, 110), program)
    assert split == pytest.approx({"serve.chunk.evict": 10,
                                   "serve.admit": 5, "serve.boundary": 20,
                                   "serve.wait": 15, "none": 10})
    ctx = {"events": events, "slice": (0, 110), "program_events": program}
    assert harness.read_metric("idle_host.serve", ctx) == pytest.approx(
        100.0 * 35 / 110)
    assert harness.read_metric("idle_host.serve",
                               dict(ctx, program_events=[])) is None
    assert harness.read_metric("idle_host.serve",
                               dict(ctx, slice=None)) is None


def test_interval_sums_by_hand():
    assert program_trace.clear_of([(10, 20)], 0, 10)
    assert not program_trace.clear_of([(10, 20)], 0, 11)
    assert program_trace.clear_of([], 0, 100)
    a = [(0, 10), (5, 20), (30, 40)]
    assert program_trace.union(a) == [[0, 20], [30, 40]]
    assert program_trace.overlap_ns(a, [(15, 35), (38, 50)]) == 12
    assert program_trace.minus(a, [(2, 4), (15, 32)]) == [
        [0, 2], [4, 15], [32, 40]]
    assert program_trace.minus(a, []) == [[0, 20], [30, 40]]


def test_decode_occupancy_by_hand():
    read = lambda ctx: harness.read_metric("decode_occupancy.serve", ctx)
    t = tracer_of([], {"serve.decode_tokens": 30,
                       "serve.decode_slot_steps": 64})
    assert read({"tracer": t}) == pytest.approx(100.0 * 30 / 64)
    assert read({"tracer": tracer_of([], {"serve.decode_tokens": 3})}) \
        is None
    assert read({"tracer": None}) is None


def test_chunk_gap_by_hand():
    # three chunks: sync ends at 90 and 190 ms, calls start at 25, 140 and
    # 230 ms, so the gaps are 50 and 40 ms; a compile under the second
    # chunk changes nothing
    spans = []
    for t0, call, sync_end in ((0, 25, 90), (100, 140, 190),
                               (200, 230, 290)):
        i = len(spans)
        spans += [("fed.chunk", t0 * MS, (sync_end + 5) * MS, -1,
                   {"rounds": 5, "round0": i}),
                  ("fed.stage", t0 * MS, (call - 5) * MS, i, {}),
                  ("fed.upload", (call - 5) * MS, call * MS, i, {}),
                  ("fed.call", call * MS, (call + 2) * MS, i, {}),
                  ("fed.sync", (call + 2) * MS, sync_end * MS, i, {})]
        if t0 == 100:
            spans.append(("compile", 101 * MS, 102 * MS, i, {}))
    read = lambda ctx: harness.read_metric("chunk_gap_ms.train", ctx)
    assert read({"tracer": tracer_of(spans)}) == pytest.approx(45.0)
    # a profiler stop between the first two chunks leaves the 40 ms gap
    assert read({"tracer": tracer_of(spans),
                 "pauses": [(100 * MS, 130 * MS)]}) == pytest.approx(40.0)
    assert read({"tracer": tracer_of(spans[:5])}) is None
    assert read({"tracer": None}) is None


def test_tracing_yields_none_without_the_program_tracer(monkeypatch):
    import repro.analysis
    monkeypatch.delattr(repro.analysis, "trace")
    monkeypatch.setitem(sys.modules, "repro.analysis.trace", None)
    with program_trace.tracing() as t:
        assert t is None


def test_program_spans_on_the_profilers_host_plane(tmp_path):
    """A CPU trace: every program span the tracer recorded is on the host
    plane under its own name, nested as the tracer nested it."""
    import jax
    import jax.numpy as jnp
    jax.profiler.start_trace(str(tmp_path))
    with program_trace.tracing() as t:
        with trace.span("serve.boundary"):
            with trace.span("serve.chunk"):
                jnp.arange(5.0).sum().block_until_ready()
            with trace.span("serve.wait"):
                pass
        with trace.span("not.the.program"):
            pass
    jax.profiler.stop_trace()
    events = program_trace.program_events(str(tmp_path))
    assert sorted(n for n, _, _ in events) == [
        "serve.boundary", "serve.chunk", "serve.wait"]
    span = {n: (s, s + d) for n, s, d in events}
    for inner in ("serve.chunk", "serve.wait"):
        assert span["serve.boundary"][0] <= span[inner][0]
        assert span[inner][1] <= span["serve.boundary"][1]
    assert span["serve.chunk"][1] <= span["serve.wait"][0]
    assert len(t.named("serve.boundary")) == 1
    assert program_trace.program_events(str(tmp_path / "none")) == []
