"""Multi-tenant serving throughput: base vs 1 adapter vs K=8 banked adapters,
compiled engine vs host loop.

Measures greedy KV-cache generation on the shared 4-layer benchmark model for
three serving shapes:

  base       no adapters — the floor (one GEMM per projection)
  adapter1   one AdapterSet for the whole batch (classic LoRA serving)
  bank8      a K=8 mixed-rank AdapterBank, one adapter per request (the
             multi-tenant path — lazy ``requests()`` gather on the compiled
             engine, materialized per-step gather on the host loop)

and two engines:

  compiled   ONE host dispatch per generation: batched prefill fills the KV
             cache over the whole prompt, then a lax.scan decode loop runs
             entirely on device (``launch/serve.generate``)
  hostloop   the pre-engine oracle: one jitted dispatch per token, prompt
             fed through single-token decode steps

Reported per (engine, variant): end-to-end tokens/sec, prefill and decode
tokens/sec separately, and the host-dispatch count per generation call.
Prefill/decode are split by timing a prefill-only call and attributing the
remainder to decode.  The headline ratios:

  bank8_vs_adapter1     compiled bank8 / compiled adapter1 tokens/sec — the
                        cost of multi-tenancy (1.0 = free)
  compiled_vs_hostloop  per-variant speedup of the device-resident engine

Timing excludes compilation (every callable is warmed first), interleaves
the variants round-robin, and spans several fresh compiles of every
executable (XLA CPU compile luck is a ~±15% band — larger than the effects
measured here), taking the per-variant minimum, so neither machine noise nor
one compile's draw can skew the cross-variant ratios; results land in
EXPERIMENTS/bench_serve.json AND the repo-root BENCH_serve.json (committed,
so the serving-perf trajectory is reviewable across PRs).

The Poisson scenario measures the CONTINUOUS-BATCHING scheduler against
static batching on a stream: seeded Poisson arrivals (rate calibrated to a
fixed offered load against this machine's measured batch service time),
mixed short/long generations, same requests through both disciplines —

  scheduled   paged KV pool + chunked decode; newcomers admitted and
              finished requests evicted at chunk boundaries
              (``launch/serve.serve_scheduled``)
  static      batches of ``BATCH`` formed in arrival order, each batch
              waits for its last member and runs to its LONGEST request

reporting per-request p50/p99 latency and goodput (requested tokens / wall
clock).  Static batching pays twice at the tail — batch formation delay and
short requests riding long neighbors — which is exactly what the paged
scheduler removes; ``p99_static_over_scheduled`` is the headline.

The lifecycle scenario measures adapter HOT-SWAP UNDER LOAD: the same
saturated request stream through the scheduler three ways —

  static      a static AdapterBank (no publishes; the throughput ceiling)
  hotswap     a LiveAdapterBank with every tenant resident, a new adapter
              version published into a rotating slot every 4 scheduler
              boundaries through the ``on_boundary`` swap window (zero
              recompiles by construction — the swap donates one padded
              bank slot between decode chunks)
  overflow    a LiveAdapterBank with only half the tenants resident, so
              the stream drives LRU promotion/demotion through the
              host-RAM store (reported for information)

``hotswap_vs_static`` (scheduled tokens/sec ratio) is the headline: it
prices continuous publishing, and the CI floor pins it at >= 0.9x.

The quant scenario serves the same model from a QUANTIZED frozen base
(core/quant.py: int8 per-channel / int4 grouped, adapters fp) on the
compiled adapter1 path, reporting per mode the eligible-base footprint
reduction (packed bytes vs fp — the decode bandwidth story) and decode
tokens/sec vs fp.  Results land in the ``quant`` section of
BENCH_serve.json.

``--ci`` asserts the pinned regression floors (used by the serve-perf CI
smoke): bank8_vs_adapter1, compiled-vs-hostloop on the bank path, the
scheduler's p99 advantage over static batching, and int8 decode >= 0.9x fp.
"""
import argparse
import json
import os
import time

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import bench_config
from repro.analysis import trace
from repro.analysis.sanitizers import RecompileGuard
from repro.configs.base import LoRAConfig
from repro.core.lora import AdapterBank, LiveAdapterBank, init_adapter_set
from repro.launch import serve
from repro.models.api import build_model

OUT = os.path.join(os.path.dirname(__file__), "..", "EXPERIMENTS")
ROOT = os.path.join(os.path.dirname(__file__), "..")

BATCH = 8
PROMPT = 32
STEPS = 32
RANKS = (4, 8, 16, 8, 4, 16, 8, 8)

# CI regression floors (see --ci): deliberately below the locally measured
# numbers to absorb runner jitter, far above the pre-engine baseline
# (bank8_vs_adapter1 was 0.709 before the compiled engine + lazy gather).
CI_FLOOR_BANK_VS_ADAPTER = 0.75
CI_FLOOR_COMPILED_VS_HOSTLOOP = 1.3
# and the scheduler: static batching's p99 must stay >= this multiple of the
# scheduled p99 at the same offered load (locally ~2-4x; 1.1 absorbs jitter)
CI_FLOOR_STATIC_P99_OVER_SCHED = 1.1
# adapter lifecycle: the scheduler serving through a live bank that takes a
# publish every 4 boundaries must hold >= this fraction of the static-bank
# throughput (the swap is one donated slot write between chunks — cheap —
# and recompiles are zero by construction, so 0.9 is mostly runner jitter)
CI_FLOOR_HOTSWAP_VS_STATIC = 0.9
# quantized serving: int8 base decode must hold >= this fraction of fp
# decode tokens/sec.  On this CPU container the reference tier dequantizes
# ONCE per compiled call (launch/serve._prepare_base), so quant costs one
# scan-invariant dequant, not a per-step one — 0.9 absorbs jitter on top.
CI_FLOOR_INT8_DECODE_VS_FP = 0.9

# Poisson scenario shape: a skewed short/long mix at an offered load that
# saturates static batching.  Every static batch runs to its longest
# member, so most slot-steps are wasted on finished short requests — its
# request capacity is BATCH / t(64-step batch), which is exactly what the
# load calibrates against.  At 1.0x that, static rides its saturation
# point (batch-formation delay + short requests pinned for their batch's
# full 64 steps + a queue that random-walks upward), while the scheduler —
# which reclaims a short request's slot and blocks the moment it finishes
# — runs at ~75% utilization and stays flat.  The tail-latency gap is
# structural, not machine luck.
SCHED_N = 96
SCHED_PROMPT = 8
SCHED_STEPS = (8, 64)
SCHED_MIX = (0.75, 0.25)      # mostly short, some long — serving reality
SCHED_LOAD = 1.0
SCHED_BLOCK = 8
SCHED_CHUNK = 8
SCHED_TRIALS = 2


REPEATS = 7
# XLA CPU compilation is nondeterministic enough to matter: the SAME program
# recompiled lands within a ~±15% speed band (layout/fusion luck), which is
# larger than the cross-variant effects this bench reports.  So the timing
# runs over several fresh compiles of every executable and keeps the
# per-variant minimum — the program's achievable speed, not one compile's
# draw.
COMPILE_TRIALS = 3


def _time_all(timers, *, model, repeats=REPEATS, trials=COMPILE_TRIALS):
    """min seconds per callable across ``trials`` fresh compiles, each timed
    ``repeats`` times INTERLEAVED round-robin so a slow phase of the machine
    penalizes every variant equally instead of whichever happened to be on
    the clock (compile/warm-up always excluded).

    After each trial's warm pass a RecompileGuard watches every engine the
    warmup cached on the model: any executable-cache growth during the
    timed section means an unwarmed shape was compiling inside the
    measurement (the PR-6/7 bench bug class) — hard error, not a silently
    slow number."""
    best = {k: float("inf") for k in timers}
    for trial in range(trials):
        if trial:
            jax.clear_caches()
            model.__dict__.pop("_serve_jit_cache", None)
        for fn in timers.values():
            jax.block_until_ready(fn())
        guard = RecompileGuard()
        guard.watch_model(model)
        for _ in range(repeats):
            for k, fn in timers.items():
                t0 = time.perf_counter()
                jax.block_until_ready(fn())
                best[k] = min(best[k], time.perf_counter() - t0)
        guard.check()
    return best


def _rows(best, name, prompt_len, steps, batch, dispatches):
    """tokens/sec rows (end-to-end, prefill, decode) for one variant."""
    out = {}
    for engine in ("compiled", "hostloop"):
        t_full = best[(name, engine)]
        t_pre = best[(name, engine + "_prefill")]
        out[engine] = {
            "tokens_per_sec": batch * (prompt_len + steps) / t_full,
            "prefill_tokens_per_sec": batch * prompt_len / t_pre,
            "decode_tokens_per_sec": (batch * (steps - 1)
                                      / max(t_full - t_pre, 1e-9)),
            "host_dispatches": dispatches[engine],
        }
    return out


def _pct(sorted_vals, q):
    return sorted_vals[min(len(sorted_vals) - 1,
                           int(len(sorted_vals) * q))]


def _run_static_stream(model, params, bank, reqs, max_len):
    """Static-batching baseline on the same arrival stream: batches of
    ``BATCH`` in arrival order; each batch launches once its last member
    has arrived and runs to its longest request.  Returns per-request
    latencies (seconds from arrival to batch completion)."""
    lat = []
    t0 = time.monotonic()
    for i in range(0, len(reqs), BATCH):
        batch = reqs[i:i + BATCH]
        gap = batch[-1].arrival - (time.monotonic() - t0)
        if gap > 0:
            time.sleep(gap)
        s = max(r.steps for r in batch)
        ids = jnp.asarray([r.adapter_id for r in batch], jnp.int32)
        pr = jnp.asarray(np.stack([r.prompt for r in batch]))
        jax.block_until_ready(serve.generate_banked(
            model, params, bank, ids, pr, s, max_len))
        done = time.monotonic() - t0
        lat.extend(done - r.arrival for r in batch)
    return lat


def poisson_scenario(model, params, bank, *, load=SCHED_LOAD, n=SCHED_N,
                     seed=0):
    """Continuous batching vs static batching on one Poisson stream.

    The arrival rate is calibrated against THIS machine: one warm timed
    static batch gives the batch service time, and the rate is set to
    ``load`` of the resulting capacity — so the scenario stresses queueing
    identically on fast and slow runners."""
    rng = np.random.default_rng(seed)
    steps_list = rng.choice(SCHED_STEPS, n, p=SCHED_MIX)
    prompts = rng.integers(0, model.cfg.vocab_size,
                           (n, SCHED_PROMPT)).astype(np.int32)
    ids = (np.arange(n) % bank.size).astype(np.int32)
    max_len = SCHED_PROMPT + max(SCHED_STEPS)

    def mk_requests(arrivals):
        return [serve.Request(rid=i, prompt=prompts[i],
                              steps=int(steps_list[i]),
                              adapter_id=int(ids[i]),
                              arrival=float(arrivals[i]))
                for i in range(n)]

    # ---- warm every shape both disciplines can hit: static batches at
    # each distinct step count (full and trailing partial batch), scheduled
    # admission groups of 1..BATCH
    sizes = {BATCH} | ({n % BATCH} if n % BATCH else set())
    for s in sorted(set(SCHED_STEPS)):
        for b in sorted(sizes):
            jax.block_until_ready(serve.generate_banked(
                model, params, bank, jnp.asarray(ids[:b]),
                jnp.asarray(prompts[:b]), int(s), max_len))
    for g in range(1, BATCH + 1):
        serve.serve_scheduled(
            model, params, mk_requests(np.zeros(n))[:g], bank=bank,
            max_batch=BATCH, block_size=SCHED_BLOCK, chunk=SCHED_CHUNK,
            max_len=max_len, wait=False)

    # ---- calibrate: best measured batch service time -> arrival rate
    # (a single timing can land 50%+ off on a noisy runner, which would
    # halve or double the offered load; the best of three is stable)
    t_batch = float("inf")
    for _ in range(3):
        t0 = time.monotonic()
        jax.block_until_ready(serve.generate_banked(
            model, params, bank, jnp.asarray(ids[:BATCH]),
            jnp.asarray(prompts[:BATCH]), max(SCHED_STEPS), max_len))
        t_batch = min(t_batch, time.monotonic() - t0)
    rate = load * BATCH / t_batch                      # requests / second
    arrivals = np.cumsum(rng.exponential(1.0 / rate, n))

    # ---- timed runs, the same stream through both disciplines; several
    # trials, keeping each discipline's best (min-across-trials, like the
    # throughput section: the achievable number, not one trial's draw)
    toks = int(steps_list.sum())
    best = {"scheduled": None, "static": None}
    for _ in range(SCHED_TRIALS):
        t0 = time.monotonic()
        done = serve.serve_scheduled(model, params, mk_requests(arrivals),
                                     bank=bank, max_batch=BATCH,
                                     block_size=SCHED_BLOCK,
                                     chunk=SCHED_CHUNK, max_len=max_len,
                                     wait=True)
        wall = time.monotonic() - t0
        lats = sorted(r.t_done - r.arrival for r in done)
        t0 = time.monotonic()
        lat_static = sorted(_run_static_stream(
            model, params, bank, mk_requests(arrivals), max_len))
        wall_static = time.monotonic() - t0
        for name, ls, w in (("scheduled", lats, wall),
                            ("static", lat_static, wall_static)):
            row = {"p50_latency_ms": 1000 * _pct(ls, 0.50),
                   "p99_latency_ms": 1000 * _pct(ls, 0.99),
                   "goodput_tokens_per_sec": toks / w}
            if (best[name] is None
                    or row["p99_latency_ms"] < best[name]["p99_latency_ms"]):
                best[name] = row

    out = {"n": n, "load": load, "arrival_rate_per_s": rate,
           "prompt": SCHED_PROMPT, "steps_mix": sorted(set(SCHED_STEPS)),
           "steps_mix_p": list(SCHED_MIX), "max_batch": BATCH,
           "block_size": SCHED_BLOCK, "chunk": SCHED_CHUNK}
    for name in ("scheduled", "static"):
        out[name] = best[name]
        print(f"serve,{name},poisson,"
              f"{out[name]['goodput_tokens_per_sec']:.1f},"
              f"{out[name]['p50_latency_ms']:.0f},"
              f"{out[name]['p99_latency_ms']:.0f},-")
    out["p99_static_over_scheduled"] = (out["static"]["p99_latency_ms"]
                                        / out["scheduled"]["p99_latency_ms"])
    print(f"serve,ratio,p99_static_over_scheduled,"
          f"{out['p99_static_over_scheduled']:.2f}")
    return out


# lifecycle scenario shape: a saturated stream (everything already arrived
# — wait=False, pure scheduler throughput), uniform steps so the static and
# live runs retire identical token counts, one publish every SWAP_EVERY
# scheduler boundaries into a rotating tenant slot
LIFE_N = 48
LIFE_PROMPT = 8
LIFE_STEPS = 16
LIFE_SWAP_EVERY = 4
LIFE_TRIALS = 3


def lifecycle_scenario(model, params, bank, sets):
    """Hot-swap under load: scheduled throughput while publishing adapters.

    The same saturated stream runs through (a) the static bank, (b) a live
    bank taking a publish every ``LIFE_SWAP_EVERY`` boundaries (every
    tenant resident — isolates publish cost), and (c) a live bank with
    half the slots (adds LRU promotion/demotion churn; informational).
    Best-of-``LIFE_TRIALS`` wall time per discipline, tokens/sec and the
    ``hotswap_vs_static`` ratio reported."""
    rng = np.random.default_rng(7)
    prompts = rng.integers(0, model.cfg.vocab_size,
                           (LIFE_N, LIFE_PROMPT)).astype(np.int32)
    max_len = LIFE_PROMPT + LIFE_STEPS
    toks = LIFE_N * LIFE_STEPS

    def mk_requests():
        return [serve.Request(rid=i, prompt=prompts[i], steps=LIFE_STEPS,
                              adapter_id=int(i % bank.size), arrival=0.0)
                for i in range(LIFE_N)]

    def run(mk_bank, on_boundary_of=None):
        best = float("inf")
        meta = {}
        for _ in range(LIFE_TRIALS):
            b = mk_bank()
            hook = on_boundary_of(b) if on_boundary_of else None
            serve.serve_scheduled(model, params, mk_requests(), bank=b,
                                  max_batch=BATCH, block_size=SCHED_BLOCK,
                                  chunk=SCHED_CHUNK, max_len=max_len,
                                  wait=False, on_boundary=hook)   # warm
            b = mk_bank()
            hook = on_boundary_of(b) if on_boundary_of else None
            t0 = time.monotonic()
            serve.serve_scheduled(model, params, mk_requests(), bank=b,
                                  max_batch=BATCH, block_size=SCHED_BLOCK,
                                  chunk=SCHED_CHUNK, max_len=max_len,
                                  wait=False, on_boundary=hook)
            best = min(best, time.monotonic() - t0)
            if isinstance(b, LiveAdapterBank):
                meta = {"publishes": b.version, "hot_swaps": b.swaps,
                        "promotions": b.promotions, "demotions": b.demotions}
        return {"tokens_per_sec": toks / best, **meta}

    def swapping(live):
        def hook(i):
            if i and i % LIFE_SWAP_EVERY == 0:
                slot = (i // LIFE_SWAP_EVERY - 1) % len(sets)
                live.publish(slot, sets[(slot + 1) % len(sets)])
        return hook

    out = {"n": LIFE_N, "prompt": LIFE_PROMPT, "steps": LIFE_STEPS,
           "swap_every_boundaries": LIFE_SWAP_EVERY, "max_batch": BATCH,
           "static": run(lambda: bank),
           "hotswap": run(lambda: LiveAdapterBank.from_bank(
               bank, hot_slots=bank.size), swapping),
           "overflow": run(lambda: LiveAdapterBank.from_bank(
               bank, hot_slots=bank.size // 2), swapping)}
    out["hotswap_vs_static"] = (out["hotswap"]["tokens_per_sec"]
                                / out["static"]["tokens_per_sec"])
    out["overflow_vs_static"] = (out["overflow"]["tokens_per_sec"]
                                 / out["static"]["tokens_per_sec"])
    print("bench,lifecycle,variant,tokens_per_sec,publishes,hot_swaps,"
          "promotions")
    for name in ("static", "hotswap", "overflow"):
        r = out[name]
        print(f"serve,lifecycle,{name},{r['tokens_per_sec']:.1f},"
              f"{r.get('publishes', 0)},{r.get('hot_swaps', 0)},"
              f"{r.get('promotions', 0)}")
    print(f"serve,ratio,hotswap_vs_static,{out['hotswap_vs_static']:.3f}")
    print(f"serve,ratio,overflow_vs_static,{out['overflow_vs_static']:.3f}")
    return out


def quant_scenario(model, params, one, prompt, *, steps, max_len):
    """fp vs int8 vs int4 frozen base on the compiled adapter1 path.

    Per mode: eligible-base footprint (packed bytes vs the fp bytes the same
    leaves would occupy — ``quant_footprint``), compiled end-to-end and
    decode tokens/sec, and the decode ratio vs fp.  The footprint columns
    are the bandwidth story (the eligible GEMM weights are what decode
    streams every step); the CPU decode ratio only proves the engine-level
    dequant hoist keeps quantization ~free on the reference tier."""
    from repro.core.quant import quant_footprint, quantize_tree

    bases = {"fp": params,
             "int8": quantize_tree(params, "int8"),
             "int4": quantize_tree(params, "int4")}
    # one jitted prefill taking the base as a pytree argument: fp/int8/int4
    # land as three cache entries of a single wrapper instead of three
    # fresh jit objects built inside the loop (each with a cold cache)
    prefill = jax.jit(lambda b, a: model.prefill(
        b, model.init_cache(BATCH, max_len), prompt, a, last_only=True)[0])
    timers = {}
    for mode, base in bases.items():
        timers[(mode, "compiled")] = (
            lambda b=base: serve.generate(model, b, prompt, steps, max_len,
                                          one))
        timers[(mode, "compiled_prefill")] = lambda b=base: prefill(b, one)
    best = _time_all(timers, model=model)

    out = {}
    print("bench,quant,mode,base_mbytes,footprint_reduction,tokens_per_sec,"
          "decode_tps,decode_vs_fp")
    for mode, base in bases.items():
        foot = quant_footprint(base)
        t_full = best[(mode, "compiled")]
        t_pre = best[(mode, "compiled_prefill")]
        out[mode] = {
            "base_mbytes": foot["base_bytes"] / 1e6,
            "footprint_reduction": (foot["base_fp_bytes"]
                                    / foot["base_bytes"]),
            "tokens_per_sec": BATCH * (PROMPT + steps) / t_full,
            "decode_tokens_per_sec": (BATCH * (steps - 1)
                                      / max(t_full - t_pre, 1e-9)),
        }
    for mode in bases:
        out[mode]["decode_vs_fp"] = (out[mode]["decode_tokens_per_sec"]
                                     / out["fp"]["decode_tokens_per_sec"])
        r = out[mode]
        print(f"serve,quant,{mode},{r['base_mbytes']:.2f},"
              f"{r['footprint_reduction']:.2f},{r['tokens_per_sec']:.1f},"
              f"{r['decode_tokens_per_sec']:.1f},{r['decode_vs_fp']:.2f}")
    return out


def main(steps: int = STEPS, ci: bool = False):
    cfg = bench_config()
    model = build_model(cfg)
    params = model.init(jax.random.key(0))
    prompt = jax.random.randint(jax.random.key(1), (BATCH, PROMPT), 0,
                                cfg.vocab_size)
    max_len = PROMPT + steps

    sets = [init_adapter_set(params, jax.random.fold_in(jax.random.key(2), i),
                             LoRAConfig(rank=r), n_clients=len(RANKS))
            for i, r in enumerate(RANKS)]
    bank = AdapterBank.from_sets(sets)
    one = sets[1]
    ids = jnp.arange(BATCH) % bank.size

    # prefill-only calls (jitted standalone so the split is measurable;
    # last_only matches the program the compiled engine actually runs)
    prefill = jax.jit(lambda a: model.prefill(
        params, model.init_cache(BATCH, max_len), prompt, a,
        last_only=True)[0])

    variants = {
        "base": {
            "compiled": lambda: serve.generate(model, params, prompt, steps,
                                               max_len),
            "hostloop": lambda s=steps: serve.generate_hostloop(
                model, params, prompt, s, max_len),
            "prefill": lambda: prefill(None),
        },
        "adapter1": {
            "compiled": lambda: serve.generate(model, params, prompt, steps,
                                               max_len, one),
            "hostloop": lambda s=steps: serve.generate_hostloop(
                model, params, prompt, s, max_len, one),
            "prefill": lambda: prefill(one),
        },
        "bank8": {
            "compiled": lambda: serve.generate_banked(model, params, bank,
                                                      ids, prompt, steps,
                                                      max_len),
            "hostloop": lambda s=steps: serve.generate_banked_hostloop(
                model, params, bank, ids, prompt, s, max_len),
            "prefill": lambda: prefill(bank.requests(ids)),
        },
    }

    timers = {}
    for name, fns in variants.items():
        timers[(name, "compiled")] = fns["compiled"]
        timers[(name, "compiled_prefill")] = fns["prefill"]
        timers[(name, "hostloop")] = fns["hostloop"]
        # host-loop prefill phase ~= a steps=1 run (prompt fed token by token)
        timers[(name, "hostloop_prefill")] = lambda fns=fns: fns["hostloop"](1)
    best = _time_all(timers, model=model)

    results = {"batch": BATCH, "prompt": PROMPT, "steps": steps,
               "ranks": list(RANKS),
               "engines": {"compiled": {}, "hostloop": {}}}
    print("bench,engine,variant,tokens_per_sec,prefill_tps,decode_tps,"
          "host_dispatches")
    for name, fns in variants.items():
        dispatches = {}
        for engine in ("compiled", "hostloop"):
            with trace.tracing() as t:
                fns[engine]()
            dispatches[engine] = t.counters["serve.dispatches"]
        rows = _rows(best, name, PROMPT, steps, BATCH, dispatches)
        for engine, row in rows.items():
            results["engines"][engine][name] = row
            print(f"serve,{engine},{name},{row['tokens_per_sec']:.1f},"
                  f"{row['prefill_tokens_per_sec']:.1f},"
                  f"{row['decode_tokens_per_sec']:.1f},"
                  f"{row['host_dispatches']}")

    comp = results["engines"]["compiled"]
    host = results["engines"]["hostloop"]
    results["bank8_vs_adapter1"] = (comp["bank8"]["tokens_per_sec"]
                                    / comp["adapter1"]["tokens_per_sec"])
    results["compiled_vs_hostloop"] = {
        k: comp[k]["tokens_per_sec"] / host[k]["tokens_per_sec"]
        for k in comp}
    print(f"serve,ratio,bank8_vs_adapter1,"
          f"{results['bank8_vs_adapter1']:.3f}")
    for k, v in results["compiled_vs_hostloop"].items():
        print(f"serve,ratio,compiled_vs_hostloop_{k},{v:.2f}")

    results["quant"] = quant_scenario(model, params, one, prompt,
                                      steps=steps, max_len=max_len)
    results["scheduled_poisson"] = poisson_scenario(model, params, bank)
    results["lifecycle"] = lifecycle_scenario(model, params, bank, sets)

    os.makedirs(OUT, exist_ok=True)
    for path in (os.path.join(OUT, "bench_serve.json"),
                 os.path.join(ROOT, "BENCH_serve.json")):
        with open(path, "w") as f:
            json.dump(results, f, indent=2)
    print("# wrote EXPERIMENTS/bench_serve.json + BENCH_serve.json")

    if ci:
        rel = results["bank8_vs_adapter1"]
        spd = results["compiled_vs_hostloop"]["bank8"]
        assert rel >= CI_FLOOR_BANK_VS_ADAPTER, (
            f"bank8_vs_adapter1 regressed: {rel:.3f} < "
            f"{CI_FLOOR_BANK_VS_ADAPTER}")
        assert spd >= CI_FLOOR_COMPILED_VS_HOSTLOOP, (
            f"compiled engine speedup regressed: {spd:.2f}x < "
            f"{CI_FLOOR_COMPILED_VS_HOSTLOOP}x")
        tail = results["scheduled_poisson"]["p99_static_over_scheduled"]
        assert tail >= CI_FLOOR_STATIC_P99_OVER_SCHED, (
            f"scheduler p99 advantage regressed: static/scheduled "
            f"{tail:.2f}x < {CI_FLOOR_STATIC_P99_OVER_SCHED}x")
        q8 = results["quant"]["int8"]["decode_vs_fp"]
        assert q8 >= CI_FLOOR_INT8_DECODE_VS_FP, (
            f"int8 decode regressed vs fp: {q8:.2f}x < "
            f"{CI_FLOOR_INT8_DECODE_VS_FP}x (is the reference-tier dequant "
            "still hoisted out of the decode scan?)")
        hs = results["lifecycle"]["hotswap_vs_static"]
        assert hs >= CI_FLOOR_HOTSWAP_VS_STATIC, (
            f"hot-swap-under-load regressed: {hs:.3f}x < "
            f"{CI_FLOOR_HOTSWAP_VS_STATIC}x of static-bank throughput "
            "(is the slot swap still recompile-free?)")
        print(f"# CI floors hold: bank8_vs_adapter1={rel:.3f} "
              f">= {CI_FLOOR_BANK_VS_ADAPTER}, compiled_vs_hostloop(bank8)="
              f"{spd:.2f}x >= {CI_FLOOR_COMPILED_VS_HOSTLOOP}x, "
              f"p99 static/scheduled={tail:.2f}x >= "
              f"{CI_FLOOR_STATIC_P99_OVER_SCHED}x, int8 decode {q8:.2f}x "
              f">= {CI_FLOOR_INT8_DECODE_VS_FP}x fp, hotswap {hs:.3f}x "
              f">= {CI_FLOOR_HOTSWAP_VS_STATIC}x static")
    return results


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=STEPS)
    ap.add_argument("--ci", action="store_true",
                    help="assert the pinned perf floors (CI serve-perf job)")
    a = ap.parse_args()
    main(steps=a.steps, ci=a.ci)
