"""Multi-pod dry-run: lower + compile every (arch x input-shape x mesh)
combination on placeholder devices and record memory / cost / collective
statistics for the roofline analysis.

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch mistral-nemo-12b \
      --shape train_4k [--multi-pod] [--rank 64] [--out EXPERIMENTS/dryrun]
  PYTHONPATH=src python -m repro.launch.dryrun --all   # every combination
"""
# The VERY FIRST lines, before ANY other import: jax locks the device count
# on first init.  512 placeholder host devices cover both the 16x16 pod and
# the 2x16x16 multi-pod mesh.
import os
os.environ["XLA_FLAGS"] = ("--xla_force_host_platform_device_count=512 "
                           + os.environ.get("XLA_FLAGS", ""))

import argparse
import dataclasses
import json
import re
import time
import traceback

import jax
import jax.numpy as jnp

from repro.configs import (ASSIGNED, INPUT_SHAPES, LoRAConfig,
                           OptimizerConfig, config_for_shape, supports_shape)
from repro.core.federated import make_run_chunk
from repro.core.lora import AdapterSet, init_lora
from repro.launch.mesh import make_production_mesh, num_clients
from repro.models.api import build_model
from repro.sharding import rules
from repro.sharding.specs import use_mesh

_DTYPE_BYTES = {"f64": 8, "f32": 4, "f16": 2, "bf16": 2, "s64": 8, "s32": 4,
                "u64": 8, "u32": 4, "s16": 2, "u16": 2, "s8": 1, "u8": 1,
                "pred": 1, "c64": 8, "c128": 16}

_COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
                "collective-permute")


def collective_bytes(hlo_text: str):
    """Sum result-tensor bytes of every collective op in (post-SPMD) HLO.

    Convention: the result size is the per-op data volume proxy (all-reduce:
    operand==result; all-gather: result==full gathered tensor ~ moved bytes).
    """
    out = {c: 0 for c in _COLLECTIVES}
    counts = {c: 0 for c in _COLLECTIVES}
    # e.g.:  %ag = bf16[4,1024]{1,0} all-gather(%p), ...
    pat = re.compile(
        r"=\s+((?:\([^)]*\))|(?:[a-z0-9]+\[[0-9,]*\]))\S*\s+"
        r"(all-gather|all-reduce|reduce-scatter|all-to-all|collective-permute)"
        r"(?:-start)?\(")
    tup_pat = re.compile(r"([a-z0-9]+)\[([0-9,]*)\]")
    for m in pat.finditer(hlo_text):
        ty, op = m.group(1), m.group(2)
        total = 0
        for dt, dims in tup_pat.findall(ty):
            if dt not in _DTYPE_BYTES:
                continue
            n = 1
            for d in dims.split(","):
                if d:
                    n *= int(d)
            total += n * _DTYPE_BYTES[dt]
        out[op] += total
        counts[op] += 1
    return out, counts


def _build(arch: str, shape_name: str, mesh, rank: int, alpha: float,
           num_layers=None):
    """Returns (fn, in_specs tuple of ShapeDtypeStructs, in_shardings).

    ``num_layers`` overrides the depth (used by the unit-calibration passes
    that derive exact per-layer costs — see run_one)."""
    shape = INPUT_SHAPES[shape_name]
    cfg = config_for_shape(arch, shape_name)
    cfg = dataclasses.replace(cfg, dtype="bfloat16", param_dtype="bfloat16")
    if num_layers is not None:
        over = {"num_layers": num_layers}
        if cfg.encoder_layers:
            over["encoder_layers"] = num_layers
        cfg = dataclasses.replace(cfg, **over)
    model = build_model(cfg)
    lcfg = LoRAConfig(rank=rank, alpha=alpha, scaling="sfedlora",
                      targets=cfg.lora_targets)

    if shape.kind == "train":
        n = num_clients(mesh)
        opt_cfg = OptimizerConfig(name="sgd", lr=5e-3)
        # the REAL trainer engine (core/federated.py run_chunk), lowered with
        # explicit shardings — one scanned round per chunk for compile parity
        step = make_run_chunk(model, strategy="fedsa", opt_cfg=opt_cfg,
                              jit=False)

        def make_state():
            from repro.optim.optimizers import make_optimizer
            params = model.init(jax.random.key(0))
            l1 = init_lora(params, jax.random.key(1), lcfg)
            lora = jax.tree.map(
                lambda x: jnp.broadcast_to(x, (n,) + x.shape), l1)
            opt1 = make_optimizer(opt_cfg)[0](l1)
            opt = jax.tree.map(
                lambda x: jnp.broadcast_to(x, (n,) + x.shape), opt1)
            return params, lora, opt

        params_s, lora_tree_s, opt_s = jax.eval_shape(make_state)
        # the engine state is an AdapterSet: the scaling factor is static
        # treedef config derived from the LoRAConfig, so shape-level specs
        # only wrap the A/B tree
        lora_s = AdapterSet.from_config(lcfg, n_clients=n, lora=lora_tree_s)
        batch = model.input_specs(shape, n_clients=n)
        # (chunk_rounds=1, N, local_steps=1, per-client batch, ...)
        batch = {k: jax.ShapeDtypeStruct((1, v.shape[0], 1) + v.shape[1:],
                                         v.dtype) for k, v in batch.items()}
        key_s = jax.eval_shape(lambda: jax.random.key(0))
        ridx = jax.ShapeDtypeStruct((), jnp.int32)
        in_specs = (params_s, lora_s, opt_s, key_s, ridx, batch)
        repl = jax.NamedSharding(mesh, jax.sharding.PartitionSpec())
        in_shard = (rules.params_sharding(params_s, mesh),
                    rules.lora_sharding(lora_s, mesh),
                    rules.lora_sharding(opt_s, mesh),
                    repl, repl,
                    rules.chunked_inputs_sharding(batch, mesh))
        return step, in_specs, in_shard

    if shape.kind == "prefill":
        def prefill(params, batch):
            logits, _ = model.forward(params, batch)
            return logits
        params_s = jax.eval_shape(lambda: model.init(jax.random.key(0)))
        batch = model.input_specs(shape)
        in_shard = (rules.params_sharding(params_s, mesh),
                    rules.inputs_sharding(batch, mesh))
        return prefill, (params_s, batch), in_shard

    # decode
    def serve_step(params, cache, token, pos, table):
        return model.decode_step(params, cache, token, pos, table=table)
    params_s = jax.eval_shape(lambda: model.init(jax.random.key(0)))
    spec = model.input_specs(shape)
    in_shard = (rules.params_sharding(params_s, mesh),
                rules.cache_sharding(spec["cache"], mesh),
                rules.inputs_sharding(spec["token"], mesh),
                rules.inputs_sharding(spec["pos"], mesh),
                rules.inputs_sharding(spec["table"], mesh))
    return (serve_step, (params_s, spec["cache"], spec["token"], spec["pos"],
                         spec["table"]), in_shard)


def _compile_stats(arch, shape_name, mesh, rank, alpha, *, num_layers=None,
                   unroll=False):
    import repro.models
    from repro.models import attention as attn
    prev = repro.models.FULL_UNROLL
    prev_blk = (attn.Q_BLOCK, attn.KV_BLOCK)
    repro.models.FULL_UNROLL = unroll
    if unroll:
        # calibration passes: bigger attention tiles -> far fewer unrolled
        # bodies (flop/byte counts are tile-size invariant; these modules are
        # never executed and their memory stats are not used)
        attn.Q_BLOCK = attn.KV_BLOCK = 4096
    try:
        t0 = time.monotonic()
        fn, in_specs, in_shard = _build(arch, shape_name, mesh, rank, alpha,
                                        num_layers=num_layers)
        with use_mesh(mesh):
            lowered = jax.jit(fn, in_shardings=in_shard).lower(*in_specs)
            t_lower = time.monotonic() - t0
            compiled = lowered.compile()
            t_compile = time.monotonic() - t0 - t_lower
    finally:
        repro.models.FULL_UNROLL = prev
        attn.Q_BLOCK, attn.KV_BLOCK = prev_blk
    mem = compiled.memory_analysis()
    cost = compiled.cost_analysis()
    # jax returns one dict per device program here for some executables
    # (observed on the scanned train shapes); they are identical copies.
    if isinstance(cost, (list, tuple)):
        cost = cost[0] if cost else None
    coll, counts = collective_bytes(compiled.as_text())
    rec = {
        "lower_s": round(t_lower, 1), "compile_s": round(t_compile, 1),
        "flops": cost.get("flops", 0.0) if cost else None,
        "bytes_accessed": cost.get("bytes accessed", 0.0) if cost else None,
        "collective_bytes": coll, "collective_counts": counts,
    }
    for attr in ("temp_size_in_bytes", "argument_size_in_bytes",
                 "output_size_in_bytes", "generated_code_size_in_bytes"):
        if mem is not None and hasattr(mem, attr):
            rec[attr] = int(getattr(mem, attr))
    return rec


def run_one(arch: str, shape_name: str, *, multi_pod: bool, rank: int = 64,
            alpha: float = 8.0, verbose: bool = True, calibrate: bool = True):
    """Full-model compile (proof + memory stats) plus a two-point unit
    calibration: XLA's cost analysis counts while-loop bodies once, so the
    scanned full model under-reports loop work.  Compiling unrolled variants
    at 1x and 2x pattern length gives exact per-layer-group costs:
      per_group = stats(2) - stats(1);  outside = stats(1) - per_group;
      corrected = outside + per_group * (num_layers / pattern_len).
    """
    mesh = make_production_mesh(multi_pod=multi_pod)
    rec = _compile_stats(arch, shape_name, mesh, rank, alpha)
    rec = {"arch": arch, "shape": shape_name,
           "mesh": "2x16x16" if multi_pod else "16x16",
           "devices": int(mesh.devices.size), **rec}

    if calibrate:
        cfg = config_for_shape(arch, shape_name)
        plen = len(cfg.block_pattern)
        u1 = _compile_stats(arch, shape_name, mesh, rank, alpha,
                            num_layers=plen, unroll=True)
        u2 = _compile_stats(arch, shape_name, mesh, rank, alpha,
                            num_layers=2 * plen, unroll=True)
        groups = cfg.num_layers / plen

        def corr(f1, f2):
            per = max(f2 - f1, 0.0)
            outside = max(f1 - per, 0.0)
            return outside + per * groups

        rec["corrected"] = {
            "flops": corr(u1["flops"], u2["flops"]),
            "bytes_accessed": corr(u1["bytes_accessed"],
                                   u2["bytes_accessed"]),
            "collective_bytes": {
                k: corr(u1["collective_bytes"][k], u2["collective_bytes"][k])
                for k in u1["collective_bytes"]},
            "layer_groups": groups,
        }
        rec["unit_compile_s"] = round(u1["compile_s"] + u2["compile_s"], 1)
    if verbose:
        print(json.dumps(rec))
    return rec


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--rank", type=int, default=64)
    ap.add_argument("--out", default="EXPERIMENTS/dryrun")
    ap.add_argument("--opts", default="",
                    help="comma-separated beyond-paper opts (sharding/opts.py)")
    args = ap.parse_args()

    if args.opts:
        from repro.sharding.opts import set_opts
        set_opts([o for o in args.opts.split(",") if o])

    os.makedirs(args.out, exist_ok=True)
    combos = []
    if args.all:
        for arch in ASSIGNED:
            for shape in INPUT_SHAPES:
                for mp in (False, True):
                    combos.append((arch, shape, mp))
    else:
        combos.append((args.arch, args.shape, args.multi_pod))

    for arch, shape, mp in combos:
        tag = f"{arch}__{shape}__{'mp' if mp else 'sp'}"
        path = os.path.join(args.out, tag + ".json")
        if os.path.exists(path):
            print(f"SKIP(done) {tag}")
            continue
        if not supports_shape(arch, shape):
            rec = {"arch": arch, "shape": shape,
                   "mesh": "2x16x16" if mp else "16x16",
                   "skipped": "full-attention arch: long_500k requires "
                              "sub-quadratic attention (DESIGN.md §5)"}
            with open(path, "w") as f:
                json.dump(rec, f, indent=1)
            print(f"SKIP(policy) {tag}")
            continue
        try:
            rec = run_one(arch, shape, multi_pod=mp, rank=args.rank)
        except Exception as e:
            rec = {"arch": arch, "shape": shape, "error": str(e),
                   "trace": traceback.format_exc()[-2000:]}
            print(f"FAIL {tag}: {e}")
        with open(path, "w") as f:
            json.dump(rec, f, indent=1)


if __name__ == "__main__":
    main()
