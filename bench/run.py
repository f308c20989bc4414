#!/usr/bin/env python3
"""Run one benchmark cell once, on the chips of the machine it starts on.

  python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

``BENCHMARK.json`` (beside ``bench/``) names each cell's configuration
(``bench/configs/<config>.json``) and traffic mix (``bench/traffic/
<mix>.json``); the mix's ``kind`` picks the driver (``train_cell.py`` or
``serve_cell.py``).  With ``--trace 0`` the result carries the cell's
end-to-end metrics; with ``--trace 1`` a profiled slice of the window gives
its per-layer metrics, each read by ``bench/metrics/<metric>.py``.  The last
line of stdout is one JSON object: correct, attempted, failed, metrics,
device (and breakdown when traced), then checks: every number compared with
the reference beside its limit, which are also the last lines of stderr.

A training mix may name a ``"mesh"`` (``"1x4"``): the cell then runs on a
mesh of that shape over its chips.  A mesh whose size is not the cell's
``chips``, or a cell on several chips whose mix names none, is refused (exit
2) before any device work.  Without a TPU, or with fewer chips than the cell
asks for, or without the system under test (``src/repro``) beside it, the
run exits non-zero and prints no result.
"""
import time

T0 = time.monotonic()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
# libtpu logs under /tmp unless told otherwise; a run writes only inside
# its checkout
os.environ.setdefault("TPU_LOG_DIR", "disabled")


def parse(argv):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def per_layer_reader(bench: dict, workload: str, reported: set):
    """The function that reads the cell's per-layer metrics from a traced
    run's context: each metric that lists this cell, or that lists none and
    moves an end-to-end metric the cell reports."""
    names = [m for m in bench["per_layer"]
             if (workload in m["workloads"] if "workloads" in m
                 else m["moves"] in reported)]

    def read(rctx):
        import harness
        out = {}
        for m in names:
            value = harness.read_metric(m["name"], rctx)
            if value is not None:
                out[m["name"]] = {"value": value, "unit": m["unit"]}
        return out

    return read


def main(argv=None) -> int:
    args = parse(argv)
    try:
        with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
            bench = json.load(f)
    except OSError as e:
        print(f"bench: no BENCHMARK.json beside bench/: {e}", file=sys.stderr)
        return 2
    cells = {w["name"]: w for w in bench["workloads"]}
    if args.workload not in cells:
        print(f"bench: unknown workload {args.workload!r}; known: "
              f"{sorted(cells)}", file=sys.stderr)
        return 2
    cell = cells[args.workload]
    sys.path[:0] = [os.path.join(ROOT, "src"), HERE]
    try:
        import repro  # noqa: F401
    except ImportError:
        print("bench: the system under test (src/repro) is not in this "
              "checkout", file=sys.stderr)
        return 2
    import jax
    import harness
    import model as bmodel
    import traffic
    try:
        cfg = bmodel.load_config(cell["config"])
    except ValueError as e:
        print(f"bench: {cell['config']}: {e}", file=sys.stderr)
        return 2
    mix = traffic.load(cell["traffic"])
    refused = harness.check_chips(cell, mix)
    if refused:
        print(f"bench: {args.workload}: {refused}", file=sys.stderr)
        return 2
    cache = harness.use_compile_cache(ROOT)
    if jax.default_backend() != "tpu":
        print(f"bench: JAX found no TPU (backend {jax.default_backend()!r}); "
              "the benchmark runs on the chip only", file=sys.stderr)
        return 3
    if jax.device_count() < cell["chips"]:
        print(f"bench: {args.workload} needs {cell['chips']} chips, JAX sees "
              f"{jax.device_count()}", file=sys.stderr)
        return 3
    reported = {m["name"] for m in bench["end_to_end"]
                if args.workload in m.get("workloads", [args.workload])}
    ctx = {"args": args, "cfg": cfg,
           "mix": mix, "chips": cell["chips"], "t0": T0,
           "meter": harness.CompileMeter(), "spans": harness.Spans(),
           "limits": harness.load_limits(args.workload),
           "trace_dir": os.path.join(ROOT, ".bench_trace", args.workload),
           "per_layer": per_layer_reader(bench, args.workload, reported)}
    if args.trace:
        shutil.rmtree(ctx["trace_dir"], ignore_errors=True)
    if mix["kind"] == "train":
        import train_cell as driver
    else:
        import serve_cell as driver
    result, checks, notes = driver.run(ctx)
    notes["compile_cache"] = cache
    notes["compiles"] = ctx["meter"].compiles
    notes["cache_hits"] = ctx["meter"].cache_hits
    harness.emit(result, checks, notes)
    return 0


if __name__ == "__main__":
    sys.exit(main())
