"""Roofline analysis from the dry-run's compiled artifacts (deliverable g).

Per (arch x shape x mesh):
  compute term    = HLO_FLOPs(per-device) / peak_FLOP/s
  memory term     = HLO_bytes(per-device) / HBM_bw
  collective term = collective_bytes(per-device) / ICI_bw
plus MODEL_FLOPS = 6*N*D (train) / 2*N*D (prefill) / 2*N_active*B (decode),
the useful-compute ratio, the dominant bottleneck, and a what-would-move-it
note.  Hardware: the dry run's target chip, peaks from launch/peaks.py.

The XLA cost/memory analyses of an SPMD module are for the per-device
partitioned program, so no extra division by chip count is needed; chips
enter through the sharded shapes themselves.
"""
from __future__ import annotations

import glob
import json
import os

from repro.configs import INPUT_SHAPES, config_for_shape
from repro.launch.peaks import peaks

DRYRUN_DIR = os.path.join(os.path.dirname(__file__), "..", "EXPERIMENTS",
                          "dryrun")


def count_params(cfg):
    """Exact param count (+ active count for MoE) via eval_shape."""
    import jax
    from repro.models.api import build_model
    model = build_model(cfg)
    shapes = jax.eval_shape(lambda: model.init(jax.random.key(0)))
    total = active = 0
    def walk(node, in_moe):
        nonlocal total, active
        if isinstance(node, dict):
            for k, v in node.items():
                walk(v, in_moe or k == "moe")
            return
        n = 1
        for d in node.shape:
            n *= d
        total += n
        if in_moe and len(node.shape) >= 3 and cfg.moe:
            active += int(n * cfg.moe.top_k / max(cfg.moe.num_experts, 1))
        else:
            active += n
    walk(shapes, False)
    return total, active


def model_flops(arch, shape_name, cfg=None):
    """Architectural useful FLOPs for the whole step (global)."""
    cfg = cfg or config_for_shape(arch, shape_name)
    shape = INPUT_SHAPES[shape_name]
    total, active = count_params(cfg)
    if shape.kind == "train":
        tokens = shape.global_batch * shape.seq_len
        return 6 * active * tokens
    if shape.kind == "prefill":
        tokens = shape.global_batch * shape.seq_len
        return 2 * active * tokens
    return 2 * active * shape.global_batch          # decode: 1 token/seq


def analyze(rec, devices=None):
    if rec.get("skipped") or rec.get("error"):
        return None
    devices = devices or rec["devices"]
    src = rec.get("corrected", rec)   # unit-calibrated loop-exact stats
    pk = peaks()
    ct = (src["flops"] or 0) / pk["flops_bf16"]
    mt = (src["bytes_accessed"] or 0) / pk["hbm_bw"]
    cb = sum(src["collective_bytes"].values())
    lt = cb / pk["ici_bw"]
    terms = {"compute": ct, "memory": mt, "collective": lt}
    dom = max(terms, key=terms.get)
    mf = model_flops(rec["arch"], rec["shape"])
    hlo_global = (src["flops"] or 0) * devices
    ratio = mf / hlo_global if hlo_global else 0.0
    return {**rec, "compute_s": ct, "memory_s": mt, "collective_s": lt,
            "dominant": dom, "model_flops": mf,
            "useful_ratio": ratio, "collective_total_bytes": cb}


_SUGGEST = {
    "compute": "reduce recompute (remat policy) / raise useful-ratio toward 1",
    "memory": "fuse adapter GEMMs (Pallas lora_matmul), shard activations "
              "(sequence parallel), bf16 logits CE",
    "collective": "reshard to cut all-gathers (kv-head replication, "
                  "seq-parallel norm), overlap A-aggregation with compute",
}


def table(records, emit=print):
    emit("arch,shape,mesh,compute_s,memory_s,collective_s,dominant,"
         "model_flops,useful_ratio,note")
    rows = []
    for rec in records:
        if rec.get("skipped"):
            emit(f"{rec['arch']},{rec['shape']},{rec['mesh']},-,-,-,"
                 f"SKIP,-,-,{rec['skipped'][:40]}")
            continue
        if rec.get("error"):
            emit(f"{rec['arch']},{rec['shape']},{rec['mesh']},-,-,-,ERROR,-,-,"
                 f"{rec['error'][:60]}")
            continue
        a = analyze(rec)
        rows.append(a)
        emit(f"{a['arch']},{a['shape']},{a['mesh']},{a['compute_s']:.4f},"
             f"{a['memory_s']:.4f},{a['collective_s']:.4f},{a['dominant']},"
             f"{a['model_flops']:.3e},{a['useful_ratio']:.3f},"
             f"{_SUGGEST[a['dominant']][:50]}")
    return rows


def load_records(dirname=DRYRUN_DIR):
    recs = []
    for path in sorted(glob.glob(os.path.join(dirname, "*.json"))):
        with open(path) as f:
            recs.append(json.load(f))
    return recs


def quant_decode_table(emit=print):
    """Decode arithmetic-intensity accounting under quantized base weights.

    Bytes-moved uses the ACTUAL storage dtypes: fp leaves at their itemsize,
    packed leaves at their int8/int4-packed + scales bytes (the
    ``QuantizedLinear.nbytes`` accounting, on eval_shape trees — no real
    buffers).  Decode at small batch is bandwidth-bound: every step streams
    the whole parameter set once, so predicted per-token intensity is
    2*P*B FLOPs over the tree's stored bytes, and the predicted decode
    speedup from quantization is simply the byte ratio.  No decode ratio
    has been measured on a chip, so the measured column prints ``-``."""
    import jax
    import numpy as np

    from benchmarks.common import bench_config
    from repro.core.quant import quant_footprint, quantize_tree
    from repro.models.api import build_model

    cfg = bench_config()
    model = build_model(cfg)
    batch = 8
    trees = {"fp": jax.eval_shape(lambda: model.init(jax.random.key(0)))}
    for mode in ("int8", "int4"):
        trees[mode] = jax.eval_shape(
            lambda m=mode: quantize_tree(model.init(jax.random.key(0)), m))
    foot = {m: quant_footprint(t) for m, t in trees.items()}
    n_params = sum(int(np.prod(l.shape))
                   for l in jax.tree_util.tree_leaves(trees["fp"]))
    flops = 2 * n_params * batch

    emit("roofline,quant,mode,base_mbytes,total_mbytes,intensity_flops_per_"
         "byte,pred_decode_speedup,measured_decode_vs_fp")
    fp_bytes = foot["fp"]["total_bytes"]
    rows = []
    for mode in ("fp", "int8", "int4"):
        fo = foot[mode]
        row = {"mode": mode,
               "base_mbytes": fo["base_bytes"] / 1e6,
               "total_mbytes": fo["total_bytes"] / 1e6,
               "intensity": flops / fo["total_bytes"],
               "pred_decode_speedup": fp_bytes / fo["total_bytes"],
               "measured_decode_vs_fp": None}
        rows.append(row)
        meas = (f"{row['measured_decode_vs_fp']:.2f}"
                if row["measured_decode_vs_fp"] else "-")
        emit(f"roofline,quant,{mode},{row['base_mbytes']:.2f},"
             f"{row['total_mbytes']:.2f},{row['intensity']:.1f},"
             f"{row['pred_decode_speedup']:.2f},{meas}")
    return rows


def main(emit=print):
    recs = load_records()
    if not recs:
        emit("roofline,no_dryrun_records_found,run launch/dryrun.py first")
        quant_decode_table(emit)
        return []
    rows = table(recs, emit)
    quant_decode_table(emit)
    return rows


if __name__ == "__main__":
    main()
