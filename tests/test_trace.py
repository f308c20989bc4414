"""The program's tracer (``repro.analysis.trace``): off it records nothing
and reads no clock; on it nests spans, adds counters, caps its buffer,
turns compiles into spans and writes Chrome trace JSON.  The serving
scheduler and the federated trainer emit the spans they document."""
import json
import time
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.analysis import trace
from repro.configs.base import (FederatedConfig, LoRAConfig, ModelConfig,
                                OptimizerConfig)
from repro.core.federated import FederatedTrainer
from repro.data.synthetic import FederatedDataset
from repro.launch import serve
from repro.models.api import build_model


def _children(t, span, prefix):
    """Names of the children of ``span`` that start with ``prefix``."""
    i = t.spans.index(span)
    return [s.name for s in t.spans
            if s.parent == i and s.name.startswith(prefix)]


def test_off_records_nothing_and_reads_no_clock(monkeypatch):
    def no_clock():
        raise AssertionError("a clock was read with tracing off")

    def no_annotation(name):
        raise AssertionError("a profiler annotation with tracing off")

    monkeypatch.setattr(time, "perf_counter_ns", no_clock)
    monkeypatch.setattr(time, "monotonic", no_clock)
    monkeypatch.setattr(jax.profiler, "TraceAnnotation", no_annotation)
    assert not trace.enabled()
    outer = trace.span("a", rid=1)
    assert outer is trace.span("b")          # one shared no-op object
    with outer:
        with trace.span("c", rid=2):
            trace.count("n", 3)
            trace.since("q", 0.0, rid=2)
    monkeypatch.undo()
    with trace.tracing() as t:
        pass
    assert t.spans == [] and t.counters == {} and t.dropped == 0


def test_nesting_sets_parents_and_a_request_shares_its_rid():
    with trace.tracing() as t:
        with trace.span("req", rid=7):
            with trace.span("req.a", rid=7):
                pass
            with trace.span("req.b", rid=7):
                with trace.span("req.b.x", rid=7):
                    pass
        with trace.span("other"):
            pass
    assert [(s.name, s.parent) for s in t.spans] == [
        ("req", -1), ("req.a", 0), ("req.b", 0), ("req.b.x", 2),
        ("other", -1)]
    assert all(s.attrs == {"rid": 7} for s in t.spans[:4])
    for s in t.spans:
        assert s.start_ns <= s.end_ns
        if s.parent >= 0:
            p = t.spans[s.parent]
            assert p.start_ns <= s.start_ns and s.end_ns <= p.end_ns
    assert not trace.enabled()


def test_since_records_a_past_interval_under_the_open_span():
    with trace.tracing() as t:
        with trace.span("outer"):
            t_start = time.monotonic() - 0.25
            trace.since("waited", t_start, rid=3)
    waited = t.spans[1]
    assert (waited.name, waited.parent, waited.attrs) == ("waited", 0,
                                                          {"rid": 3})
    assert waited.seconds == pytest.approx(0.25, abs=0.05)


def test_counters_add_up_and_tracers_nest():
    with trace.tracing() as outer:
        trace.count("a")
        trace.count("a", 4)
        with trace.tracing() as inner:
            trace.count("a", 10)
            trace.count("b", 0)
        trace.count("a")
    assert outer.counters == {"a": 6}
    assert inner.counters == {"a": 10, "b": 0}


def test_buffer_cap_counts_what_it_drops():
    with trace.tracing(capacity=3) as t:
        for i in range(5):
            with trace.span(f"s{i}"):
                pass
        trace.since("late", time.monotonic())
    assert [s.name for s in t.spans] == ["s0", "s1", "s2"]
    assert t.dropped == 3


def test_compile_becomes_a_span_under_the_open_span():
    x = jnp.arange(7.0)
    with trace.tracing() as t:
        with trace.span("step"):
            jax.jit(lambda v: v * 3.5 + 0.25)(x).block_until_ready()
    compiles = t.named("compile")
    assert compiles and all(c.parent == 0 for c in compiles)
    assert all(0 < c.seconds and c.end_ns <= t.spans[0].end_ns
               for c in compiles)


def test_write_gives_chrome_trace_json(tmp_path):
    path = tmp_path / "t.json"
    with pytest.raises(RuntimeError):
        with trace.written_to(str(path)):
            with trace.span("a", rid=1):
                with trace.span("b"):
                    trace.count("n", 2)
            with trace.span("c"):
                raise RuntimeError("the job failed; the trace is written")
    doc = json.loads(path.read_text())
    events = doc["traceEvents"]
    spans = [e for e in events if e["ph"] == "X"]
    assert [e["name"] for e in spans] == ["a", "b", "c"]
    for e in spans:
        assert {"name", "ph", "ts", "dur", "pid", "tid", "args"} <= set(e)
        assert e["dur"] >= 0
    assert spans[0]["args"] == {"rid": 1, "index": 0, "parent": -1}
    assert spans[1]["args"]["parent"] == 0
    assert [(e["name"], e["args"]) for e in events if e["ph"] == "C"] == [
        ("n", {"value": 2})]
    assert doc["otherData"]["dropped_spans"] == 0
    with trace.written_to(None) as t:
        assert t is None and not trace.enabled()


# ------------------------------------------------------ the instrumented loops

def _serve_model():
    cfg = ModelConfig(name="traced", family="dense", num_layers=2,
                      d_model=32, num_heads=2, num_kv_heads=1, head_dim=16,
                      d_ff=64, vocab_size=64)
    model = build_model(cfg)
    return cfg, model, model.init(jax.random.key(0))


def test_serve_scheduled_spans_counters_and_first_token_stamp(monkeypatch):
    """Four requests, two slots: each request is queued once, every admit
    and chunk nests its steps under a boundary, the counters match the
    served tokens, and each first token is stamped after it reached the
    host (after its group's ``serve.admit.sync``)."""
    cfg, model, params = _serve_model()
    rng = np.random.default_rng(0)
    steps, arrivals = (3, 5, 2, 6), (0.0, 0.0, 0.01, 0.02)
    reqs = [serve.Request(rid=i, prompt=rng.integers(0, 64, 4, np.int32),
                          steps=s, arrival=a)
            for i, (s, a) in enumerate(zip(steps, arrivals))]
    # the scheduler's clock on the tracer's, so stamps compare exactly; its
    # first reading is the loop's start
    readings = []

    def monotonic():
        readings.append(time.perf_counter_ns() / 1e9)
        return readings[-1]

    monkeypatch.setattr(serve, "time", types.SimpleNamespace(
        monotonic=monotonic, sleep=time.sleep))
    with trace.tracing() as t:
        done = serve.serve_scheduled(model, params, reqs, max_batch=2,
                                     block_size=4, chunk=2, max_len=12,
                                     wait=True)
    t0 = readings[0]
    assert [len(r.tokens) for r in done] == list(steps)
    spans = t.spans
    queued = t.named("serve.queued")
    assert sorted(s.attrs["rid"] for s in queued) == [0, 1, 2, 3]
    admits = t.named("serve.admit")
    chunks = t.named("serve.chunk")
    boundaries = t.named("serve.boundary")
    assert all(b.parent == -1 for b in boundaries)
    by_rid = {}
    for a in admits:
        assert spans[a.parent].name == "serve.boundary"
        assert a.attrs["size"] == len(a.attrs["rids"])
        assert a.attrs["prompt_len"] == 4
        assert _children(t, a, "serve.") == ["serve.admit.stage",
                                             "serve.admit.call",
                                             "serve.admit.sync"]
        sync = next(s for s in spans if s.name == "serve.admit.sync"
                    and s.parent == spans.index(a))
        for rid in a.attrs["rids"]:
            by_rid[rid] = (a, sync)
    assert sorted(by_rid) == [0, 1, 2, 3]
    for q in queued:
        admit, _ = by_rid[q.attrs["rid"]]
        assert spans[q.parent].name == "serve.boundary"
        assert q.start_ns <= q.end_ns <= admit.start_ns
    for c in chunks:
        assert spans[c.parent].name == "serve.boundary"
        assert _children(t, c, "serve.") == [
            "serve.chunk.stage", "serve.chunk.call", "serve.chunk.sync",
            "serve.chunk.evict"]
    for w in t.named("serve.wait"):
        assert spans[w.parent].name == "serve.boundary"
    for r in done:
        _, sync = by_rid[r.rid]
        assert t0 + r.t_first >= sync.end_ns / 1e9
    served = sum(len(r.tokens) for r in done)
    assert t.counters["serve.decode_tokens"] == served - len(done)
    assert t.counters["serve.admitted"] == len(done)
    assert t.counters["serve.dispatches"] == len(admits) + len(chunks)
    assert t.counters["serve.decode_slot_steps"] == 2 * 2 * len(chunks)
    # recorded once, when the decode chunk is traced: both scanned
    # attention layers write their pool in place
    assert t.counters["serve.kv_inplace_layers"] == cfg.num_layers == 2


def test_trainer_chunk_spans_hold_their_four_steps():
    cfg = ModelConfig(name="traced", family="dense", num_layers=1,
                      d_model=32, num_heads=2, num_kv_heads=1, head_dim=16,
                      d_ff=64, vocab_size=64)
    model = build_model(cfg)
    tr = FederatedTrainer(
        model, FederatedDataset(64, 2, seq_len=8, batch_per_client=1,
                                seed=0),
        lora_cfg=LoRAConfig(rank=4),
        fed_cfg=FederatedConfig(num_clients=2, local_steps=1),
        opt_cfg=OptimizerConfig(name="sgd", lr=0.05), chunk_rounds=1)
    with trace.tracing() as t:
        tr.run(2)
    chunks = t.named("fed.chunk")
    assert [(c.parent, c.attrs) for c in chunks] == [
        (-1, {"rounds": 1, "round0": 0}), (-1, {"rounds": 1, "round0": 1})]
    for c in chunks:
        assert _children(t, c, "fed.") == [
            "fed.stage", "fed.upload", "fed.call", "fed.sync"]
    assert t.counters == {"fed.rounds": 2}
