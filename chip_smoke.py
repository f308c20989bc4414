#!/usr/bin/env python3
"""Smoke run of both hot paths on one TPU, at the full width of gemma-2b.

One process drives, in order, through the entry points a user calls:

  train/reference  ``repro.launch.train.main`` — 4 clients, rank 64, two
                   federated rounds of one compiled chunk each, held-out
                   perplexity; the second round must reuse the first's
                   executable
  train/fused      the same run through ``FederatedTrainer`` with
                   ``use_pallas=True``: the fused LoRA kernels run forward
                   and backward; round losses and grad norms must match the
                   reference phase
  serve/reference  ``repro.launch.serve.main`` — a burst of 8 requests
                   through the continuous-batching scheduler — then
                   ``serve_scheduled`` on the same seeded requests with
                   non-zero tenant adapters
  serve/fused      the same on the fused tier (BGMV + paged-attention
                   kernels); the first decode step's logits of the two
                   tiers must agree

Every phase prints the seconds spent compiling, a timed step after
``block_until_ready``, and the device's memory high-water mark
(``peak_bytes_in_use`` is a process-lifetime maximum: a phase whose peak
is below an earlier one's shows the earlier value).  Weights are random,
made from fixed seeds.  The last stdout line is one JSON object:
``{"ok": true, "device": {"platform", "kind", "count"}}``.

  python chip_smoke.py             # one chip: the four phases above
  python chip_smoke.py --chips 4   # four chips: the trainer on a 2x2 mesh
                                   # against the same run on one device

Without a TPU, or without the ``repro`` package beside it, the script exits
non-zero and prints no result.
"""
from __future__ import annotations

import argparse
import dataclasses
import gc
import json
import math
import os
import sys
import time
import traceback

# Train/serve CLI arguments of the runs below.  Full published width; the
# depth, client count and sequence are the smoke's own (seq 128 keeps one
# round of 4 clients inside one chip's HBM next to the fp32 base).
TRAIN_ARGS = ["--arch", "gemma-2b", "--clients", "4", "--rank", "64",
              "--local-steps", "1", "--batch-per-client", "1", "--seq", "128",
              "--rounds", "2", "--chunk-rounds", "1"]
# a burst: every request has arrived before the first scheduler boundary
SERVE_ARGS = ["--arch", "gemma-2b", "--arrival-trace", "poisson:1e9:8",
              "--steps", "16", "--max-batch", "4"]

# Tolerances between the tiers.  On a TPU, XLA runs an fp32 matmul as one
# bf16 pass (operands rounded to 8 mantissa bits, relative error ~2^-9)
# while Mosaic's fp32 matmul keeps more of the mantissa, so the tiers differ
# by bf16 rounding carried through 18 layers.  A wrong kernel (block index,
# gamma, tenant gather) is off by O(1) relative; these bounds sit an order
# of magnitude below that and an order above the rounding.
LOSS_RTOL = 1e-2      # round loss and grad norm, fused vs reference
LOGITS_RTOL = 5e-2    # ||fused - ref|| / ||ref|| of first-step logits
MESH_RTOL = 5e-3      # per LoRA leaf, 2x2 mesh vs one device, fp32 matmuls

GiB = 2 ** 30


class SmokeFailure(Exception):
    pass


def check(cond, msg):
    if not cond:
        raise SmokeFailure(msg)


def rel_err(got, want):
    import numpy as np
    got = np.asarray(got, np.float64)
    want = np.asarray(want, np.float64)
    return float(np.linalg.norm(got - want) / max(np.linalg.norm(want),
                                                  1e-30))


# ------------------------------------------------------------ instrumentation

class CompileMeter:
    """Backend compiles (cache loads included) and the seconds spent tracing
    and lowering to them, seen through jax.monitoring."""

    EVENT = "/jax/core/compile/backend_compile_duration"
    TRACE_EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
                    "/jax/core/compile/jaxpr_to_mlir_module_duration")

    def __init__(self):
        import jax
        self.events = []
        self.trace_s = 0.0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, duration, **kw):
        if event == self.EVENT:
            self.events.append((kw.get("fun_name", "?"), duration))
        elif event in self.TRACE_EVENTS:
            self.trace_s += duration

    def _event(self, event, **kw):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    def count(self, fun_name):
        return sum(1 for name, _ in self.events if name == fun_name)


class Phase:
    """Prints one ``phase`` line: compile seconds and count, the timed step,
    and the device's bytes in use at entry/exit and high-water mark."""

    def __init__(self, name, meter):
        import jax
        self.name, self.meter = name, meter
        self.device = jax.devices()[0]
        self.step_s = None

    def __enter__(self):
        gc.collect()
        self.n0, self.h0 = len(self.meter.events), self.meter.cache_hits
        self.tr0 = self.meter.trace_s
        self.in_use0 = self.device.memory_stats()["bytes_in_use"]
        self.t0 = time.perf_counter()
        print(f"== {self.name}", flush=True)
        return self

    def __exit__(self, *exc):
        if exc[0] is not None:
            return False
        gc.collect()
        ev = self.meter.events[self.n0:]
        stats = self.device.memory_stats()
        step = "n/a" if self.step_s is None else f"{self.step_s:.4f}"
        print(f"phase {self.name}: compile_s={sum(d for _, d in ev):.2f} "
              f"trace_lower_s={self.meter.trace_s - self.tr0:.2f} "
              f"compiles={len(ev)} cache_hits="
              f"{self.meter.cache_hits - self.h0} step_s={step} "
              f"wall_s={time.perf_counter() - self.t0:.2f} "
              f"peak_hbm_bytes={stats['peak_bytes_in_use']} "
              f"({stats['peak_bytes_in_use'] / GiB:.2f} GiB of "
              f"{stats.get('bytes_limit', 0) / GiB:.2f}) "
              f"in_use_bytes entry={self.in_use0} "
              f"exit={stats['bytes_in_use']}", flush=True)
        slow = sorted(ev, key=lambda e: -e[1])[:3]
        print("  slowest compiles: " + ", ".join(
            f"{name} {secs:.2f}s" for name, secs in slow), flush=True)
        return False


def timed(fn):
    """(result, seconds) of ``fn()`` with every output array ready."""
    import jax
    t0 = time.perf_counter()
    out = jax.block_until_ready(fn())
    return out, time.perf_counter() - t0


# ------------------------------------------------------------------- phases

def train_trainer(cfg, argv):
    """The trainer ``repro.launch.train.main(argv)`` builds, for ``cfg``
    (same dataset, adapters, engine and seeds), without running it."""
    from repro.configs.base import (FederatedConfig, LoRAConfig,
                                    OptimizerConfig)
    from repro.core.federated import FederatedTrainer
    from repro.data.synthetic import FederatedDataset
    from repro.models.api import build_model
    a = dict(zip(argv[::2], argv[1::2]))
    n, seq = int(a["--clients"]), int(a["--seq"])
    ds = FederatedDataset(cfg.vocab_size, n, seq_len=seq,
                          batch_per_client=int(a["--batch-per-client"]),
                          seed=0)
    return FederatedTrainer(
        build_model(cfg), ds,
        lora_cfg=LoRAConfig(rank=int(a["--rank"]), targets=cfg.lora_targets),
        fed_cfg=FederatedConfig(num_clients=n,
                                local_steps=int(a["--local-steps"]),
                                rounds=int(a["--rounds"])),
        opt_cfg=OptimizerConfig(name="sgd", lr=5e-3), seed=0,
        chunk_rounds=int(a["--chunk-rounds"]))


def check_history(hist, rounds):
    check(len(hist) == rounds, f"expected {rounds} rounds, got {len(hist)}")
    for h in hist:
        check(math.isfinite(h["loss"]) and math.isfinite(h["grad_norm"]),
              f"non-finite round metrics {h}")


def phase_train_reference(meter):
    from repro.launch import train
    with Phase("train/reference", meter) as ph:
        tr = train.main(TRAIN_ARGS)
        check_history(tr.history, 2)
        n_chunk = meter.count("jit(run_chunk)")
        check(n_chunk == 1, f"run_chunk compiled {n_chunk}x over 2 rounds: "
              "the second round did not reuse the executable")
        ppl = tr.eval_perplexity()
        check(math.isfinite(ppl), f"held-out perplexity {ppl}")
        _, ph.step_s = timed(lambda: (tr.run_round(), tr.lora)[1])
        check(meter.count("jit(run_chunk)") == 1,
              "the timed round recompiled")
        hist = [dict(h) for h in tr.history[:2]]
        for h in hist:
            print(f"  round {h['round']}: loss={h['loss']!r} "
                  f"grad_norm={h['grad_norm']!r}")
        print(f"  held-out perplexity={ppl!r}")
        del tr
    return hist


def phase_train_fused(meter, ref_hist):
    from repro.configs import get_config
    from repro.kernels import dispatch
    with dispatch.scope(True):
        mode = dispatch.resolve_mode()
    check(mode == "pallas", f"use_pallas on a TPU resolves to '{mode}'")
    with Phase("train/fused", meter) as ph:
        dispatch.reset_stats()
        cfg = dataclasses.replace(get_config("gemma-2b"), use_pallas=True)
        tr = train_trainer(cfg, TRAIN_ARGS)
        tr.run(2)
        check_history(tr.history, 2)
        check(dispatch.stats["fused"] > 0,
              f"no fused lowering: dispatch.stats={dispatch.stats}")
        _, ph.step_s = timed(lambda: (tr.run_round(), tr.lora)[1])
        for h, r in zip(tr.history[:2], ref_hist):
            for k in ("loss", "grad_norm"):
                err = abs(h[k] - r[k]) / abs(r[k])
                print(f"  round {h['round']}: {k} fused={h[k]!r} "
                      f"reference={r[k]!r} rel_err={err:.3e} "
                      f"(tol {LOSS_RTOL})")
                check(err <= LOSS_RTOL, f"round {h['round']} {k}: fused "
                      f"{h[k]} vs reference {r[k]}")
        print(f"  dispatch.stats={dict(dispatch.stats)}")
        del tr


def check_served(done, vocab, n):
    check(len(done) == n, f"{len(done)} of {n} requests returned")
    for r in done:
        check(not r.timed_out and len(r.tokens) == r.steps,
              f"request {r.rid}: {len(r.tokens)}/{r.steps} tokens")
        check(all(0 <= t < vocab for t in r.tokens),
              f"request {r.rid}: token out of vocab {r.tokens}")


def serve_setup():
    """Base params, a bank of 4 tenants with non-zero adapters (zero-init B
    would make every tenant the base model) and the CLI's seeded burst."""
    import argparse as ap
    import jax
    from repro.configs import get_config
    from repro.launch import serve
    from repro.models.api import build_model
    cfg = get_config("gemma-2b")
    ns = ap.Namespace(resume=None, ranks="", rank=8, clients=4, alpha=8.0,
                      scaling="sfedlora")
    base, bank = serve.build_bank(ns, cfg, build_model(cfg))
    noise = lambda x, i: x + 0.02 * jax.random.normal(
        jax.random.fold_in(jax.random.key(5), i), x.shape, x.dtype)
    leaves, tdef = jax.tree.flatten(bank.lora)
    bank = dataclasses.replace(bank, lora=jax.tree.unflatten(
        tdef, [noise(x, i) for i, x in enumerate(leaves)]))
    reqs = lambda: serve.make_requests("poisson:1e9:8", prompt_len=4,
                                       steps=16, tenants=bank.size,
                                       vocab=cfg.vocab_size)
    return cfg, base, bank, reqs


def first_step_logits(model, base, bank, reqs):
    """Logits of one paged decode step at position 0 for the first
    ``max_batch`` requests — the paged-attention kernel and the BGMV GEMV
    on the fused tier, the gather path on the reference tier."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    rs = reqs()[:4]
    bs, mb = 8, 3
    cache = model.init_paged_cache(1 + 4 * mb, bs, 4)
    table = jnp.arange(1, 1 + 4 * mb, dtype=jnp.int32).reshape(4, mb)
    tok = jnp.asarray(np.stack([r.prompt[:1] for r in rs]), jnp.int32)
    ids = jnp.asarray([r.adapter_id for r in rs], jnp.int32)
    step = jax.jit(lambda p, c, t, a: model.decode_step(
        p, c, t, jnp.zeros((4,), jnp.int32), a, table=table))
    logits, _ = step(base, cache, tok, bank.requests(ids))
    return np.asarray(logits[:, 0, :model.cfg.vocab_size], np.float32)


def phase_serve(meter):
    """serve/reference runs the CLI first (its own params, freed on
    return), then both tiers serve one shared base and bank."""
    import numpy as np
    from repro.configs import get_config
    from repro.kernels import dispatch
    from repro.launch import serve
    from repro.models.api import build_model
    logits = {}
    for tier, use_pallas in (("reference", False), ("fused", True)):
        with Phase(f"serve/{tier}", meter) as ph:
            if not use_pallas:
                done = serve.main(SERVE_ARGS)
                check_served(done, get_config("gemma-2b").vocab_size, 8)
                del done
                gc.collect()
                cfg, base, bank, reqs = serve_setup()
            dispatch.reset_stats()
            model = build_model(dataclasses.replace(cfg,
                                                    use_pallas=use_pallas))
            done = serve.serve_scheduled(model, base, reqs(), bank=bank,
                                         max_batch=4, wait=False)
            check_served(done, cfg.vocab_size, 8)
            done, ph.step_s = timed(lambda: serve.serve_scheduled(
                model, base, reqs(), bank=bank, max_batch=4, wait=False))
            check_served(done, cfg.vocab_size, 8)
            toks = sum(len(r.tokens) for r in done)
            print(f"  warm scheduled run: {toks} tokens in "
                  f"{ph.step_s:.4f} s")
            logits[tier] = first_step_logits(model, base, bank, reqs)
            check(np.isfinite(logits[tier]).all(), "non-finite logits")
            print(f"  dispatch.stats={dict(dispatch.stats)}")
            if use_pallas:
                check(dispatch.stats["bgmv"] > 0 and
                      dispatch.stats["paged"] > 0,
                      f"fused serving skipped a kernel: {dispatch.stats}")
            del model, done
    err = rel_err(logits["fused"], logits["reference"])
    agree = float(np.mean(logits["fused"].argmax(-1)
                          == logits["reference"].argmax(-1)))
    print(f"  first-step logits fused vs reference: rel_err={err:.3e} "
          f"(tol {LOGITS_RTOL}) argmax agreement={agree:.2f}")
    check(err <= LOGITS_RTOL, f"first-step logits rel_err {err}")


def mesh_check(meter, argv, *, mesh_spec="2x2"):
    """The trainer on a ``mesh_spec`` mesh (clients over ``data``, base
    params over ``model``) against the same run on one device: every LoRA
    leaf must agree within MESH_RTOL.  ``argv`` are train CLI arguments.
    Both runs use fp32 matmuls (``highest``): at the TPU default, one bf16
    pass, two partitionings of the same step round differently, and the
    B leaves (pure accumulated gradients after two rounds) differed by
    1.6e-2 in the worst leaf on a v5e 2x2 — noise that would hide a
    sharding fault's O(1) error behind a loose bound."""
    import jax
    import numpy as np
    from repro.launch import train
    with jax.default_matmul_precision("highest"):
        with Phase(f"train/mesh-{mesh_spec}", meter) as ph:
            n0 = meter.count("jit(run_chunk)")
            tr = train.main(argv + ["--mesh", mesh_spec])
            check_history(tr.history, 2)
            specs = sorted({str(x.sharding.spec)
                            for x in jax.tree.leaves(tr.lora)})
            print(f"  lora sharding specs={specs}")
            check(all("data" in s for s in specs),
                  f"client dim not sharded over data: {specs}")
            n_chunk = meter.count("jit(run_chunk)") - n0
            check(n_chunk == 1, f"run_chunk compiled {n_chunk}x over 2 "
                  "rounds on the mesh: the second round did not reuse the "
                  "executable")
            mesh_leaves = [np.asarray(x) for x in jax.tree.leaves(tr.lora)]
            hist = [dict(h) for h in tr.history]
            _, ph.step_s = timed(lambda: (tr.run_round(), tr.lora)[1])
            del tr
        with Phase("train/one-device", meter) as ph:
            tr = train.main(argv)
            check_history(tr.history, 2)
            one_leaves = [np.asarray(x) for x in jax.tree.leaves(tr.lora)]
            for h, r in zip(hist, tr.history):
                print(f"  round {h['round']}: loss mesh={h['loss']!r} "
                      f"one-device={r['loss']!r}")
            _, ph.step_s = timed(lambda: (tr.run_round(), tr.lora)[1])
            del tr
    errs = [rel_err(m, o) for m, o in zip(mesh_leaves, one_leaves)]
    print(f"  LoRA leaves mesh vs one device: rel_err per leaf "
          f"{' '.join(f'{e:.3e}' for e in errs)}, worst {max(errs):.3e} "
          f"(tol {MESH_RTOL})")
    check(max(errs) <= MESH_RTOL, f"mesh run differs: rel_err {max(errs)}")


# --------------------------------------------------------------------- main

def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="4: run only the 2x2-mesh trainer check")
    args = ap.parse_args(argv)
    here = os.path.dirname(os.path.abspath(__file__))
    sys.path.insert(0, os.path.join(here, "src"))
    try:
        import repro  # noqa: F401
    except ImportError:
        print("chip_smoke: the repro package (src/repro) is not beside "
              "this script", file=sys.stderr)
        return 2
    import jax
    if jax.default_backend() != "tpu":
        print(f"chip_smoke: JAX found no TPU (backend "
              f"'{jax.default_backend()}'); this smoke runs on the chip only",
              file=sys.stderr)
        return 2
    devices = jax.devices()
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but JAX sees "
              f"{len(devices)} device(s)", file=sys.stderr)
        return 2
    from repro.launch.compile_cache import enable_compile_cache
    print(f"# compile cache: {enable_compile_cache()}")
    d = devices[0]
    print(f"# device: {d.platform} {d.device_kind} x{len(devices)} "
          f"jax {jax.__version__}", flush=True)
    meter = CompileMeter()
    t0 = time.perf_counter()
    try:
        if args.chips == 4:
            mesh_check(meter, TRAIN_ARGS)
        else:
            ref_hist = phase_train_reference(meter)
            phase_train_fused(meter, ref_hist)
            phase_serve(meter)
    except Exception:
        traceback.print_exc()
        print("chip_smoke: FAILED", file=sys.stderr)
        return 1
    print(f"# total {time.perf_counter() - t0:.1f} s, "
          f"{len(meter.events)} compiles, {meter.cache_hits} cache hits",
          flush=True)
    print(json.dumps({"ok": True, "device": {
        "platform": d.platform, "kind": d.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
