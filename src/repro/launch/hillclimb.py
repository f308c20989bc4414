"""Perf hillclimb driver (EXPERIMENTS.md §Perf).

For each selected (arch x shape) pair, re-lowers the step with cumulative
beyond-paper optimization sets (sharding/opts.py) and records the roofline
terms per variant, so each hypothesis -> change -> before/after cycle is one
row.  Usage:

  PYTHONPATH=src python -m repro.launch.hillclimb \
      --pair mistral-nemo-12b:train_4k \
      --variants baseline expand_kv expand_kv+chunked_ce \
      --out EXPERIMENTS/hillclimb
"""
import os
os.environ["XLA_FLAGS"] = ("--xla_force_host_platform_device_count=512 "
                           + os.environ.get("XLA_FLAGS", ""))

import argparse
import json

from repro.launch import dryrun
from repro.launch.peaks import peaks
from repro.sharding import opts


def terms(rec):
    src = rec.get("corrected", rec)
    pk = peaks()
    return {"compute_s": src["flops"] / pk["flops_bf16"],
            "memory_s": src["bytes_accessed"] / pk["hbm_bw"],
            "collective_s": (sum(src["collective_bytes"].values())
                             / pk["ici_bw"]),
            "temp_gb": rec.get("temp_size_in_bytes", 0) / 1e9}


def run_variant(arch, shape, variant: str, *, multi_pod=False, rank=64):
    opts.reset()
    names = [] if variant == "baseline" else variant.split("+")
    opts.set_opts(names)
    try:
        rec = dryrun.run_one(arch, shape, multi_pod=multi_pod, rank=rank,
                             verbose=False, calibrate=True)
    finally:
        opts.reset()
    return {"arch": arch, "shape": shape, "variant": variant,
            **terms(rec), "raw": rec}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--pair", action="append", required=True,
                    help="arch:shape (repeatable)")
    ap.add_argument("--variants", nargs="+", default=["baseline"])
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--rank", type=int, default=64)
    ap.add_argument("--out", default="EXPERIMENTS/hillclimb")
    args = ap.parse_args()

    os.makedirs(args.out, exist_ok=True)
    print("arch,shape,variant,compute_s,memory_s,collective_s,temp_gb")
    for pair in args.pair:
        arch, shape = pair.split(":")
        for variant in args.variants:
            tag = f"{arch}__{shape}__{variant.replace('+', '_')}"
            path = os.path.join(args.out, tag + ".json")
            if os.path.exists(path):
                with open(path) as f:
                    r = json.load(f)
            else:
                try:
                    r = run_variant(arch, shape, variant,
                                    multi_pod=args.multi_pod, rank=args.rank)
                except Exception as e:
                    print(f"{arch},{shape},{variant},ERROR,{e}")
                    continue
                with open(path, "w") as f:
                    json.dump(r, f, indent=1)
            print(f"{arch},{shape},{variant},{r['compute_s']:.4f},"
                  f"{r['memory_s']:.4f},{r['collective_s']:.4f},"
                  f"{r['temp_gb']:.2f}")


if __name__ == "__main__":
    main()
