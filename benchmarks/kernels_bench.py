"""Kernel microbenchmarks: us_per_call for the Pallas kernels vs their jnp
references.  On a TPU backend the kernels compile through Mosaic and the
rows are named ``*_pallas_mosaic``; on any other backend they run under the
Pallas interpreter (Python emulation), the rows are named
``*_pallas_interp``, and their times say nothing about any chip — only the
derived FLOP counts carry over.  The first output line names the device.

Every row is REGISTERED first and the whole set is warmed before any timing
begins: a shape that first compiles inside a timed region poisons not just
its own row but (via allocator/compile-thread pressure) its neighbors' —
the engine-bench lesson, applied here so later-added rows can't regress the
harness.  Results land in EXPERIMENTS/bench_kernels.json AND the repo-root
BENCH_kernels.json (committed, so ``benchmarks/run.py table`` has a
cross-PR kernel trajectory).
"""
import json
import os
import time

import jax
import jax.numpy as jnp

from repro.analysis.sanitizers import RecompileGuard
from repro.core.quant import dequantize, quantize
from repro.kernels import ref
from repro.kernels.bgmv import (bgmv_gemv, bgmv_gemv_quant, bgmv_matmul,
                                bgmv_matmul_quant, bgmv_reference)
from repro.kernels.dispatch import fused_lora_apply
from repro.kernels.lora_matmul import lora_matmul_quant_vjp
from repro.kernels.ops import flash_mha, fused_lora_matmul, rglru_scan_op

OUT = os.path.join(os.path.dirname(__file__), "..", "EXPERIMENTS")
ROOT = os.path.join(os.path.dirname(__file__), "..")


def timeit(fn, *args, iters: int = 3):
    out = fn(*args)
    jax.block_until_ready(out)
    t0 = time.perf_counter()
    for _ in range(iters):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / iters * 1e6


def main(emit=print):
    key = jax.random.key(0)
    rows = []
    dev = jax.devices()[0]
    emit(f"# device: {dev.platform} {dev.device_kind} x{len(jax.devices())}")
    interp = jax.default_backend() != "tpu"
    tier = "interp" if interp else "mosaic"

    def add(name, fn, args, derived):
        """derived: callable us -> trailing CSV field (flop counts are
        static strings; achieved-rate fields need the measured time)."""
        rows.append((name.replace("_pallas_interp", f"_pallas_{tier}"),
                     fn, args, derived))

    # lora_matmul: (m,k,n,r) = (1024, 1024, 1024, 64)
    m, k, n, r = 1024, 1024, 1024, 64
    ks = jax.random.split(key, 4)
    x = jax.random.normal(ks[0], (m, k), jnp.float32)
    w = jax.random.normal(ks[1], (k, n), jnp.float32)
    a = jax.random.normal(ks[2], (r, k), jnp.float32) * 0.02
    b = jax.random.normal(ks[3], (n, r), jnp.float32) * 0.02
    flops = 2 * m * k * n + 2 * m * k * r + 2 * m * r * n
    add("lora_matmul_ref_jnp",
        jax.jit(lambda *t: ref.lora_matmul_ref(*t, 2.0)), (x, w, a, b),
        lambda us, f=flops: f"gflops={f/us/1e3:.2f}")
    add("lora_matmul_pallas_interp",
        lambda *t: fused_lora_matmul(*t, 2.0), (x, w, a, b),
        lambda us, f=flops: f"flops={f}")

    # quantized base variants: the fused kernels DMA the packed int tiles +
    # scales and dequantize in VMEM; the reference tier dequantizes the
    # whole weight up front (the parity-bounds policy).  The derived field
    # records the base-weight bytes each path moves from HBM.
    # one jitted dequant-reference shared by both widths: the packed tree is
    # a pytree argument, so int8/int4 land as two cache entries of a single
    # wrapper (an inline jit per loop iteration would rebuild the cache)
    dequant_ref = jax.jit(lambda x_, a_, b_, q_: ref.lora_matmul_ref(
        x_, dequantize(q_), a_, b_, 2.0))
    for bits, mode in ((8, "int8"), (4, "int4")):
        q = quantize(w, bits=bits)
        wbytes = q.nbytes
        add(f"lora_matmul_{mode}_ref_dequant",
            lambda x_, a_, b_, q=q: dequant_ref(x_, a_, b_, q), (x, a, b),
            lambda us, f=flops: f"gflops={f/us/1e3:.2f}")
        add(f"lora_matmul_{mode}_pallas_interp",
            lambda x_, a_, b_, q=q, bits=bits: lora_matmul_quant_vjp(
                x_, q.data, q.scales, a_, b_, 2.0, bits=bits,
                interpret=interp), (x, a, b),
            lambda us, wb=wbytes: f"w_bytes={wb}_vs_fp={w.nbytes}")

    # lora_matmul backward: fused custom-VJP kernels vs jnp autodiff.
    # dx mirrors the forward's three GEMMs (2mnk + 2mnr + 2mrk); dA and dB
    # add one rank-r reduction each (2mrk and 2mnr) — dW is dead-code-
    # eliminated: LoRA training never differentiates the base weights.
    bwd_flops = 2 * m * n * k + 4 * m * n * r + 4 * m * r * k
    add("lora_matmul_bwd_ref_jnp",
        jax.jit(jax.grad(
            lambda x_, a_, b_: ref.lora_matmul_ref(x_, w, a_, b_, 2.0).sum(),
            argnums=(0, 1, 2))), (x, a, b),
        lambda us, f=bwd_flops: f"gflops={f/us/1e3:.2f}")
    add("lora_matmul_bwd_pallas_interp",
        jax.jit(jax.grad(
            lambda x_, a_, b_: fused_lora_apply(x_, w, a_, b_, 2.0,
                                                interpret=interp).sum(),
            argnums=(0, 1, 2))), (x, a, b),
        lambda us, f=bwd_flops: f"flops={f}")

    # batched bank kernel (BGMV): the multi-tenant serving delta — per
    # request row, the shared base GEMM fused with that row's rank-r delta
    # gathered from the stacked bank by id inside the kernel.
    B, s, K = 8, 32, 8
    ks2 = jax.random.split(jax.random.key(1), 5)
    xb = jax.random.normal(ks2[0], (B, s, k), jnp.float32)
    ab = jax.random.normal(ks2[1], (K, r, k), jnp.float32) * 0.02
    bb = jax.random.normal(ks2[2], (K, n, r), jnp.float32) * 0.02
    ids = jnp.arange(B, dtype=jnp.int32) % K
    bflops = B * s * (2 * k * n + 2 * k * r + 2 * r * n)
    bgmv_ref = jax.jit(bgmv_reference)
    add("bgmv_matmul_ref_einsum", bgmv_ref, (xb, w, ab, bb, ids),
        lambda us, f=bflops: f"gflops={f/us/1e3:.2f}")
    add("bgmv_matmul_pallas_interp",
        lambda *t: bgmv_matmul(*t, interpret=interp), (xb, w, ab, bb, ids),
        lambda us, f=bflops: f"flops={f}")
    # decode shape: one token per request (the GEMV-form kernel)
    x1 = xb[:, :1]
    flops1 = B * (2 * k * n + 2 * k * r + 2 * r * n)
    add("bgmv_gemv_ref_einsum", bgmv_ref, (x1, w, ab, bb, ids),
        lambda us, f=flops1: f"gflops={f/us/1e3:.2f}")
    add("bgmv_gemv_pallas_interp",
        lambda x_, *t: bgmv_gemv(x_[:, 0], *t, interpret=interp),
        (x1, w, ab, bb, ids), lambda us, f=flops1: f"flops={f}")
    # quantized-base BGMV (decode is where packed bytes pay: the base GEMM
    # is the bandwidth term at batch-1 token shapes)
    for bits, mode in ((8, "int8"), (4, "int4")):
        q = quantize(w, bits=bits)
        add(f"bgmv_matmul_{mode}_pallas_interp",
            lambda x_, a_, b_, i_, q=q, bits=bits: bgmv_matmul_quant(
                x_, q.data, q.scales, a_, b_, i_, bits=bits,
                interpret=interp), (xb, ab, bb, ids),
            lambda us, wb=q.nbytes: f"w_bytes={wb}_vs_fp={w.nbytes}")
        add(f"bgmv_gemv_{mode}_pallas_interp",
            lambda x_, a_, b_, i_, q=q, bits=bits: bgmv_gemv_quant(
                x_[:, 0], q.data, q.scales, a_, b_, i_, bits=bits,
                interpret=interp), (x1, ab, bb, ids),
            lambda us, wb=q.nbytes: f"w_bytes={wb}_vs_fp={w.nbytes}")

    # flash attention: b=1, s=1024, h=4, d=64
    bq, sq, h, d = 1, 1024, 4, 64
    q_ = jax.random.normal(ks[0], (bq, sq, h, d), jnp.float32)
    kk = jax.random.normal(ks[1], (bq, sq, h, d), jnp.float32)
    v = jax.random.normal(ks[2], (bq, sq, h, d), jnp.float32)
    aflops = 4 * bq * h * sq * sq * d
    add("flash_attention_ref_jnp",
        jax.jit(lambda *t: ref.flash_attention_ref(*t, causal=True)),
        (q_, kk, v), lambda us, f=aflops: f"gflops={f/us/1e3:.2f}")
    add("flash_attention_pallas_interp",
        lambda *t: flash_mha(*t, causal=True), (q_, kk, v),
        lambda us, f=aflops: f"flops={f}")

    # flash attention, GQA serving shape: 8 query heads sharing 2 KV heads
    # (the wrapper's KV expansion) — the decode-cache-heavy config
    hq, hkv = 8, 2
    qg = jax.random.normal(ks[0], (bq, sq, hq, d), jnp.float32)
    kg = jax.random.normal(ks[1], (bq, sq, hkv, d), jnp.float32)
    vg = jax.random.normal(ks[2], (bq, sq, hkv, d), jnp.float32)
    gflops = 4 * bq * hq * sq * sq * d
    add("flash_attention_gqa_ref_jnp",
        jax.jit(lambda q2, k2, v2: ref.flash_attention_ref(
            q2, jnp.repeat(k2, hq // hkv, axis=2),
            jnp.repeat(v2, hq // hkv, axis=2), causal=True)), (qg, kg, vg),
        lambda us, f=gflops: f"gflops={f/us/1e3:.2f}")
    add("flash_attention_gqa_pallas_interp",
        lambda *t: flash_mha(*t, causal=True), (qg, kg, vg),
        lambda us, f=gflops: f"flops={f}")

    # rglru scan: (bt, s, d) = (4, 2048, 256)
    bt, sr, dr = 4, 2048, 256
    a_ = jax.random.uniform(ks[0], (bt, sr, dr), jnp.float32, 0.8, 0.999)
    b_ = jax.random.normal(ks[1], (bt, sr, dr), jnp.float32)
    from repro.models.rglru import rglru_scan as assoc_scan
    bytes_moved = 3 * bt * sr * dr * 4
    add("rglru_assoc_scan_jnp", jax.jit(assoc_scan), (a_, b_),
        lambda us, bm_=bytes_moved: f"gb_s={bm_/us/1e3:.2f}")
    add("rglru_scan_pallas_interp", rglru_scan_op, (a_, b_),
        lambda us, bm_=bytes_moved: f"bytes={bm_}")

    # ---- warm EVERY registered shape before ANY timing: compiles (and
    # interpret-mode tracing) never land inside a timed region
    for _, fn, args, _ in rows:
        jax.block_until_ready(fn(*args))

    # recompile sanitizer: each row's executable cache is snapshotted after
    # the warm pass; growth during the timed loop means a shape was
    # compiling on the clock — fail loudly instead of reporting it as slow
    guard = RecompileGuard()
    for name, fn, _, _ in rows:
        guard.watch(name, fn)

    emit("bench,name,us_per_call,derived")
    results = {}
    for name, fn, args, derived in rows:
        us = timeit(fn, *args)
        results[name] = {"us_per_call": round(us, 1)}
        emit(f"kernels,{name},{us:.1f},{derived(us)}")
    guard.check()

    os.makedirs(OUT, exist_ok=True)
    for path in (os.path.join(OUT, "bench_kernels.json"),
                 os.path.join(ROOT, "BENCH_kernels.json")):
        with open(path, "w") as f:
            json.dump(results, f, indent=2)
    emit("# wrote EXPERIMENTS/bench_kernels.json + BENCH_kernels.json")
    return results


if __name__ == "__main__":
    main()
