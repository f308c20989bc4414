#!/usr/bin/env python3
"""Readings that set a cell's correctness limits, and the knee sweep of a
serving cell.  Not part of a benchmark run; run it on the chip:

  python3 bench/calibrate.py --workload <cell> --seeds 1 2 3 ...
  python3 bench/calibrate.py --workload <serve cell> --sweep 6 8 10 12
  python3 bench/calibrate.py --workload <serve cell> --tails --seeds 1 2 3

All seeds in one process.  For each: the program's readings against the
float32 reference (the lower reading of each limit); for the first
``--controls`` seeds also the control's (the reference computed in bfloat16
in the program's place: the upper reading), and for a training cell a
planted fault (half of the clients left out of each round).
A serving cell serves ``--seconds`` of its mix per seed at the mix's rate
and compares the same sampled requests as a run does.  One JSON line per
seed.  ``--sweep`` serves the mix at each rate and prints, per rate, the
first- and second-half median time to first token and the drain after the
last arrival: a backlog that grows shows in both.
"""
import argparse
import gc
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path[:0] = [os.path.join(ROOT, "src"), HERE]


def train(cfg, mix, seeds, controls):
    import harness
    import train_cell
    for n, seed in enumerate(seeds):
        spans = harness.Spans()
        state = train_cell.build(cfg, mix, seed, spans)
        prog = train_cell.first_chunk(cfg, mix, state, spans)
        del state["trainer"]
        gc.collect()
        t = time.monotonic()
        ref = train_cell.reference_rounds(cfg, mix, state["params"], seed,
                                          state["key"])
        ref_s = time.monotonic() - t
        out = {"seed": seed, "program": train_cell.readings(prog, ref),
               "reference_s": ref_s, "loss": ref["loss"]}
        if n < controls:
            low = train_cell.reference_rounds(
                cfg, mix, state["params"], seed, state["key"],
                dtype="bfloat16")
            half = train_cell.reference_rounds(
                cfg, mix, state["params"], seed, state["key"],
                skip_half=True)
            out["control"] = train_cell.readings(low, ref)
            out["half_batch"] = train_cell.readings(half, ref)
        print(json.dumps(out), flush=True)
        del state
        gc.collect()


def gap_stats(gaps):
    """The widest gap, the mean gap and the share of positions whose token
    is not the reference's first."""
    return {"logit_gap": float(gaps.max()), "mean_gap": float(gaps.mean()),
            "flip_share": float((gaps > 0).mean())}


def serve(cfg, mix, seeds, seconds, controls):
    import harness
    import serve_cell
    for n, seed in enumerate(seeds):
        state = serve_cell.build(cfg, mix, seed)
        serve_cell.warm(state, mix, cfg["vocab_size"])
        reqs = serve_cell.window_requests(mix, seconds, seed,
                                          cfg["vocab_size"])
        done = serve_cell.serve_window(state, mix, reqs, harness.Spans(),
                                       detail=False)
        sample = serve_cell.sample_for_check(done, serve_cell.CHECK_REQUESTS,
                                             seed)
        t = time.monotonic()
        prog, ctrl = serve_cell.logit_gaps(cfg, mix, state["params"],
                                           state["bank_lora"], sample,
                                           control=n < controls)
        out = {"seed": seed, "program": gap_stats(prog),
               "tokens": int(prog.size), "reference_s": time.monotonic() - t}
        if ctrl is not None:
            out["control"] = gap_stats(ctrl)
        print(json.dumps(out), flush=True)
        del state, done
        gc.collect()


def sweep(cfg, mix, rates, seconds, seed):
    import numpy as np
    import harness
    import serve_cell
    state = serve_cell.build(cfg, mix, seed)
    serve_cell.warm(state, mix, cfg["vocab_size"])
    for rate in rates:
        m = dict(mix, rate=rate)
        reqs = serve_cell.window_requests(m, seconds, seed,
                                          cfg["vocab_size"])
        spans = harness.Spans()
        done = serve_cell.serve_window(state, m, reqs, spans, detail=False)
        order = sorted(done, key=lambda r: r.arrival)
        ttft = np.array([r.t_first - r.arrival for r in order])
        half = len(order) // 2
        chunks = spans.named("bench.decode_chunk")
        print(json.dumps({
            "rate": rate, "requests": len(done),
            "ttft_p50_first_half_ms": 1e3 * float(np.median(ttft[:half])),
            "ttft_p50_second_half_ms": 1e3 * float(np.median(ttft[half:])),
            "ttft_p95_ms": 1e3 * float(np.percentile(ttft, 95)),
            "drain_s": max(r.t_done for r in done) - order[-1].arrival,
            "tokens_per_s": sum(len(r.tokens) for r in done)
            / max(r.t_done for r in done),
            "chunk_ms_p50": 1e3 * float(np.median(
                [s.seconds for s in chunks])),
            "admit_ms_p50": 1e3 * float(np.median(
                [s.seconds for s in spans.named("bench.admit")]))}),
            flush=True)


def tails(cfg, mix, seeds, seconds):
    """``serve_tokens_per_s`` per seed with the mix's fixed tail and with
    none, in one process: what the fixed tail does to the rate's spread."""
    import harness
    import serve_cell
    state = serve_cell.build(cfg, mix, seeds[0])
    serve_cell.warm(state, mix, cfg["vocab_size"])
    for seed in seeds:
        for tail in (mix["fixed_tail_s"], 0.0):
            m = dict(mix, fixed_tail_s=tail)
            reqs = serve_cell.window_requests(m, seconds, seed,
                                              cfg["vocab_size"])
            done = serve_cell.serve_window(state, m, reqs, harness.Spans(),
                                           detail=False)
            last = max(r.t_done for r in done)
            print(json.dumps({
                "seed": seed, "fixed_tail_s": tail, "last_s": last,
                "tokens_per_s": sum(len(r.tokens) for r in done) / last}),
                flush=True)


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="*", default=[])
    ap.add_argument("--seconds", type=float, default=15.0)
    ap.add_argument("--sweep", type=float, nargs="*", default=[])
    ap.add_argument("--tails", action="store_true")
    ap.add_argument("--controls", type=int, default=3,
                    help="seeds (the first ones) that also read the control")
    args = ap.parse_args(argv)
    import jax
    import harness
    import model as bmodel
    import traffic
    harness.use_compile_cache(ROOT)
    if jax.default_backend() != "tpu":
        print("calibrate: no TPU", file=sys.stderr)
        return 3
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        cell = {w["name"]: w for w in json.load(f)["workloads"]}[
            args.workload]
    cfg, mix = bmodel.load_config(cell["config"]), traffic.load(
        cell["traffic"])
    if args.sweep:
        sweep(cfg, mix, args.sweep, args.seconds, args.seeds[0]
              if args.seeds else 1)
    elif args.tails:
        tails(cfg, mix, args.seeds, args.seconds)
    elif mix["kind"] == "train":
        train(cfg, mix, args.seeds, args.controls)
    else:
        serve(cfg, mix, args.seeds, args.seconds, args.controls)
    return 0


if __name__ == "__main__":
    sys.exit(main())
