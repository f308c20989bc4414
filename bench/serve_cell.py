"""Serving cells: an open-loop request stream through ``serve_scheduled``,
the continuous-batching scheduler over the paged KV pool and a static
``AdapterBank`` of per-tenant adapters.

Set-up makes the base weights and the bank from the seed, then warms every
program the window's traffic reaches: one admission prefill per group size
1..``max_batch`` at the mix's prompt length (with the scheduler's own slot
bookkeeping for each size) and the decode chunk.  The window sends the mix's
requests at their scheduled arrivals; ``serve_scheduled`` returns when all
are served.  Its admit and chunk engines are wrapped so that each call blocks
until its outputs are ready and is recorded as a span: the scheduler stamps a
request's first token on the host right after the admit call returns, so the
stamp follows the prefill's device work.
"""
from __future__ import annotations

import gc
import time

import numpy as np

import flops
import harness
import model as bmodel
import traffic


# finished requests the reference checks after the window, the longest
# among them
CHECK_REQUESTS = 6


class Engines:
    """``guard=`` for ``serve_scheduled``: wraps its admit and chunk engines
    with blocking spans (``bench.admit``, ``bench.decode_chunk``) and marks
    the host time between engine calls as ``bench.scheduler_idle``.  With
    ``detail`` each chunk span records its active positions and tenants."""

    LABELS = {"paged_admit": "bench.admit", "paged_chunk": "bench.decode_chunk"}

    def __init__(self, spans, detail: bool):
        self.spans, self.detail = spans, detail

    def wrap(self, name, fn):
        import jax
        label = self.LABELS[name]

        def call(*args, **kw):
            self.spans.close()
            info = None
            if self.detail and name == "paged_chunk":
                active = np.asarray(args[4])
                info = {"positions": np.asarray(args[3])[active].tolist(),
                        "tenants": len(set(np.asarray(
                            args[6].ids)[active].tolist())),
                        "steps": kw["steps"]}
            with self.spans.span(label, info):
                out = jax.block_until_ready(fn(*args, **kw))
            self.spans.open("bench.scheduler_idle")
            return out

        return call


def make_bank(cfg: dict, mix: dict, key):
    """``tenants`` adapters of rank ``rank`` on the mix's targets, stored as
    the bank serves them (scaling folded into B): A ~ N(0, 1/d), B ~ N(0,
    scale^2 / r), so each tenant's B A is about ``adapter_scale`` of the
    base weight it adapts."""
    import jax
    r = mix["rank"]
    return bmodel.make_lora(cfg, jax.random.fold_in(key, 3), rank=r,
                            targets=mix["targets"],
                            a_std=cfg["hidden_size"] ** -0.5,
                            b_std=mix["adapter_scale"] / r ** 0.5,
                            lead=(mix["tenants"],))


def warm_requests(mix: dict, vocab: int):
    """Admission groups of every size max_batch..1: all requests are present
    at once, and in the group of size g all but the last finish with their
    first token, so the next group has g - 1 free slots.  The last request
    of each group decodes one chunk."""
    from repro.launch.serve import Request
    rng = np.random.default_rng(0)
    reqs = []
    for g in range(mix["max_batch"], 0, -1):
        for i in range(g):
            reqs.append(Request(
                rid=len(reqs),
                prompt=rng.integers(0, vocab, mix["prompt_len"], np.int32),
                steps=1 if i < g - 1 else 1 + mix["chunk"],
                adapter_id=len(reqs) % mix["tenants"]))
    return reqs


def sample_for_check(done, k: int, seed: int):
    """``k`` finished requests drawn from the seed, the longest among them."""
    longest = max(range(len(done)), key=lambda i: len(done[i].tokens))
    rest = [i for i in range(len(done)) if i != longest]
    rng = traffic.rng_for(seed, 3)
    pick = rng.choice(len(rest), size=min(k - 1, len(rest)), replace=False)
    return [done[longest]] + [done[rest[i]] for i in sorted(pick)]


def logit_gaps(cfg, mix, params, bank, reqs, *, control=False):
    """For each sampled request, the reference's logits over its prompt and
    served tokens, and at every served position the gap between the
    reference's best logit and the served token's.  With ``control`` also
    the gap of the token a bfloat16 reference puts first.  Returns arrays of
    gaps over every sampled position."""
    import jax
    import jax.numpy as jnp
    fam = bmodel.family(cfg)
    p_len, o_max = mix["prompt_len"], mix["output"]["max"]
    total = p_len + o_max - 1

    def gaps(p, lora, seq, served):
        ref = fam.forward(cfg, p, seq, lora)[0, p_len - 1:]
        best = ref.max(-1)
        out = [best - jnp.take_along_axis(ref, served[:, None], -1)[:, 0]]
        if control:
            low = fam.forward(cfg, fam.cast(p, jnp.bfloat16), seq,
                              fam.cast(lora, jnp.bfloat16))[0, p_len - 1:]
            tok = jnp.argmax(low, -1)
            out.append(best - jnp.take_along_axis(ref, tok[:, None], -1)[:, 0])
        return out

    fn = jax.jit(gaps)
    prog, ctrl = [], []
    with jax.default_matmul_precision("highest"):
        for r in reqs:
            n = len(r.tokens)
            seq = np.zeros((1, total), np.int32)
            seq[0, :p_len] = r.prompt
            seq[0, p_len:p_len + n - 1] = r.tokens[:-1]
            served = np.zeros((o_max,), np.int32)
            served[:n] = r.tokens
            lora = jax.tree.map(lambda x: x[r.adapter_id], bank)
            out = fn(params, lora, jnp.asarray(seq), jnp.asarray(served))
            prog.append(np.asarray(out[0])[:n])
            if control:
                ctrl.append(np.asarray(out[1])[:n])
    return (np.concatenate(prog),
            np.concatenate(ctrl) if control else None)


def build(cfg: dict, mix: dict, seed: int):
    import jax
    from repro.core.lora import AdapterBank
    from repro.models.api import build_model
    fam = bmodel.family(cfg)
    model = build_model(fam.program_config(cfg))
    key = bmodel.seed_key(seed)
    params = fam.make_params(model, jax.random.fold_in(key, 1))
    bank_lora = make_bank(cfg, mix, key)
    bank = AdapterBank(lora=fam.program_lora(bank_lora),
                       ranks=(mix["rank"],) * mix["tenants"])
    return {"model": model, "params": params, "bank_lora": bank_lora,
            "bank": bank}


def serve_kwargs(mix: dict) -> dict:
    return {"max_batch": mix["max_batch"], "block_size": mix["block_size"],
            "chunk": mix["chunk"],
            "max_len": mix["prompt_len"] + mix["output"]["max"]}


def warm(state, mix, vocab):
    from repro.launch.serve import serve_scheduled
    serve_scheduled(state["model"], state["params"],
                    warm_requests(mix, vocab), bank=state["bank"],
                    wait=False, **serve_kwargs(mix))


def window_requests(mix, seconds, seed, vocab):
    from repro.launch.serve import Request
    return [Request(rid=i, prompt=q["prompt"], steps=q["steps"],
                    adapter_id=q["tenant"], arrival=q["arrival"])
            for i, q in enumerate(traffic.serve_requests(mix, seconds, seed,
                                                         vocab))]


def serve_window(state, mix, reqs, spans, *, detail, on_boundary=None):
    from repro.launch.serve import serve_scheduled
    done = serve_scheduled(state["model"], state["params"], reqs,
                           bank=state["bank"], wait=True,
                           guard=Engines(spans, detail),
                           on_boundary=on_boundary, **serve_kwargs(mix))
    spans.close()
    return done


def chunk_work(cfg, mix, info, pk) -> tuple[float, str]:
    """Least seconds of one decode chunk's work at the chip's peaks."""
    work = bmodel.family(cfg).decode_step_work
    total, bound = 0.0, "hbm"
    for i in range(info["steps"]):
        f, b = work(
            cfg, positions=[p + i for p in info["positions"]],
            tenants=info["tenants"], rank=mix["rank"],
            targets=mix["targets"])
        t, bound = flops.least_time_s(f, b, pk)
        total += t
    return total, bound


def run(ctx):
    cfg, mix, args, spans = ctx["cfg"], ctx["mix"], ctx["args"], ctx["spans"]
    meter, vocab = ctx["meter"], cfg["vocab_size"]
    state = build(cfg, mix, args.seed)
    warm(state, mix, vocab)
    reqs = window_requests(mix, args.seconds, args.seed, vocab)

    profile = harness.Profile(ctx["trace_dir"]) if args.trace else None
    t_from = args.seconds * harness.TRACE_START
    t_to = t_from + harness.TRACE_SECONDS
    clock = {}

    def on_boundary(_):
        now = time.monotonic() - clock["t"]
        if not profile.started and now >= t_from:
            profile.start()
        elif profile.started and not profile.stopped and now >= t_to:
            profile.stop()

    compiles0 = meter.compiles
    spans_before = len(spans.spans)
    clock["t"] = t_start = time.monotonic()
    setup_s = t_start - ctx["t0"]
    done = serve_window(state, mix, reqs, spans, detail=bool(args.trace),
                        on_boundary=on_boundary if profile else None)
    if profile and profile.started and not profile.stopped:
        profile.stop()
    window_compiles = meter.compiles - compiles0
    device = harness.device_info(ctx["chips"])

    failed = [r for r in done if r.timed_out or r.t_first is None
              or len(r.tokens) != r.steps]
    good = [r for r in done if r not in failed]
    ttft = [r.t_first - r.arrival for r in good]
    tpot = [(r.t_done - r.t_first) / (len(r.tokens) - 1) for r in good
            if len(r.tokens) > 1]
    last = max((r.t_done for r in good), default=float("nan"))
    out_tokens = sum(len(r.tokens) for r in good)
    lateness = max(0.0, max((r.t_first - r.arrival for r in good),
                            default=0.0))

    gc.collect()
    sample = sample_for_check(good, CHECK_REQUESTS, args.seed)
    gaps, _ = logit_gaps(cfg, mix, state["params"], state["bank_lora"], sample)
    # the mean gap separates the program from the bfloat16 control; the
    # widest gap does not (PERF.md, section 4)
    ok, checks = harness.judge({"mean_gap": float(gaps.mean())},
                               ctx["limits"])
    ok = ok and not failed

    result = {"correct": bool(ok), "attempted": len(done),
              "failed": len(failed), "device": device}
    notes = {"setup_s": setup_s, "requests": len(done),
             "compiles_in_window": window_compiles,
             "last_completion_s": last, "worst_ttft_s": lateness,
             "checked_tokens": int(gaps.size),
             "widest_gap": float(gaps.max())}
    if not args.trace:
        # a failed request misses every latency limit
        miss = [float("inf")] * len(failed)
        result["metrics"] = {
            "ttft_p95_ms": {"value": 1e3 * float(np.percentile(
                ttft + miss, 95)), "unit": "ms"},
            "tpot_p95_ms": {"value": 1e3 * float(np.percentile(
                tpot + miss, 95)), "unit": "ms"},
            "serve_tokens_per_s": {"value": out_tokens / last,
                                   "unit": "tokens/s"},
            "setup_s": {"value": setup_s, "unit": "s"}}
        return result, checks, notes

    events = harness.trace_events(ctx["trace_dir"])
    lo_hi = harness.slice_bounds(events)
    window_spans = spans.spans[spans_before:]
    rctx = {"events": events, "slice": lo_hi, "cfg": cfg, "mix": mix,
            "peaks": harness.peaks(device["kind"]),
            "admits": [s for s in window_spans if s.name == "bench.admit"],
            "chunks": [s for s in window_spans
                       if s.name == "bench.decode_chunk"
                       and profile.t0 <= s.t0 and s.t1 <= profile.t1],
            "chunk_work": lambda info: chunk_work(cfg, mix, info,
                                                  rctx["peaks"])}
    result["metrics"] = ctx["per_layer"](rctx)
    if rctx["chunks"]:
        notes["decode_bound"] = rctx["chunk_work"](rctx["chunks"][-1].info)[1]
    if lo_hi:
        lo, hi = lo_hi
        result["device"]["busy_s"] = harness.busy_ns(events, lo, hi) / 1e9
        result["device"]["window_s"] = (hi - lo) / 1e9
        result["breakdown"] = harness.breakdown(events, lo, hi)
    return result, checks, notes
