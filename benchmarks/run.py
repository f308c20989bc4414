"""Benchmark orchestrator — one module per paper table/figure.

``python -m benchmarks.run``           runs everything (CSV to stdout)
``python -m benchmarks.run fig2 fig8`` runs a subset
``python -m benchmarks.run table``     cross-PR trajectory of BENCH_*.json
``FAST=1``                             shortens training benches
"""
import glob
import json
import os
import subprocess
import sys
import time

SUITES = ("comm", "kernels", "engine", "roofline", "fig9", "fig3", "fig2",
          "fig4", "fig8", "tab12", "table")

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _flatten(obj, prefix=""):
    """Dotted-path numeric scalars of a nested benchmark dict."""
    out = {}
    if isinstance(obj, dict):
        for k, v in obj.items():
            out.update(_flatten(v, f"{prefix}{k}."))
    elif isinstance(obj, (int, float)) and not isinstance(obj, bool):
        out[prefix.rstrip(".")] = obj
    return out


def _git(*args):
    """Run git in the repo root; returns stdout or None on any failure."""
    try:
        proc = subprocess.run(["git", *args], cwd=ROOT, capture_output=True,
                              text=True, timeout=30)
    except (OSError, subprocess.TimeoutExpired):
        return None
    return proc.stdout if proc.returncode == 0 else None


def _warn_row(name, rev, why):
    """One ``__warning__`` CSV row; commas/newlines sanitized out of *why*."""
    why = str(why).replace(",", ";").replace("\n", " ")
    print(f"trajectory,{name},{rev},__warning__,{why}")


def trajectory() -> None:
    """Cross-PR trajectory table aggregated from repo-root ``BENCH_*.json``.

    Each benchmark run that lands in a PR rewrites its ``BENCH_<suite>.json``
    at the repo root, so git history holds one snapshot per PR.  This walks
    every committed revision of every ``BENCH_*.json`` (oldest first), adds
    the current working tree, flattens each snapshot to dotted scalar
    metrics, and prints one CSV row per metric:

        trajectory,<file>,<rev>,<metric>,<value>

    A historical revision that cannot be read (file renamed since, blob
    missing) or parsed (malformed snapshot from an old commit) emits a
    ``__warning__`` row instead of aborting the aggregation — the rest of
    the trajectory still prints.  A missing git repo degrades to the
    working-tree snapshot alone.
    """
    print("trajectory,file,rev,metric,value")
    for path in sorted(glob.glob(os.path.join(ROOT, "BENCH_*.json"))):
        name = os.path.basename(path)
        snapshots = []
        revs = (_git("log", "--reverse", "--format=%h", "--", name) or "").split()
        for rev in revs:
            blob = _git("show", f"{rev}:{name}")
            if blob is None:
                _warn_row(name, rev, "unreadable: git show failed "
                                     "(renamed or missing at this revision)")
                continue
            try:
                snapshots.append((rev, json.loads(blob)))
            except ValueError as e:
                _warn_row(name, rev, f"malformed JSON: {e}")
                continue
        try:
            with open(path) as f:
                worktree = json.load(f)
        except (OSError, ValueError) as e:
            _warn_row(name, "worktree", f"unreadable working-tree file: {e}")
            worktree = None
        if worktree is not None:
            if snapshots and snapshots[-1][1] == worktree:
                pass  # tree matches HEAD's snapshot; don't duplicate the row
            else:
                snapshots.append(("worktree", worktree))
        for rev, snap in snapshots:
            try:
                metrics = sorted(_flatten(snap).items())
            except Exception as e:  # a snapshot no current _flatten handles
                _warn_row(name, rev, f"unflattenable snapshot: {e}")
                continue
            if not metrics:
                _warn_row(name, rev, "no numeric metrics in snapshot")
                continue
            for metric, value in metrics:
                print(f"trajectory,{name},{rev},{metric},{value:g}")


def main() -> None:
    want = [a for a in sys.argv[1:] if not a.startswith("-")] or list(SUITES)
    fast = os.environ.get("FAST", "0") not in ("0", "")
    rounds = 10 if fast else None

    failed = []

    def run(name, fn, **kw):
        t0 = time.monotonic()
        print(f"# === {name} ===", flush=True)
        try:
            fn(**kw)
        except Exception as e:  # run the other suites, then exit non-zero
            import traceback
            print(f"{name},ERROR,{e}")
            traceback.print_exc()
            failed.append(name)
        print(f"# === {name} done in {time.monotonic()-t0:.1f}s ===", flush=True)

    if "comm" in want:
        from benchmarks import comm_table
        run("comm_table", comm_table.main)
    if "kernels" in want:
        from benchmarks import kernels_bench
        run("kernels_bench", kernels_bench.main)
    if "engine" in want:
        from benchmarks import engine_bench
        run("engine_bench", engine_bench.main,
            **({"rounds": rounds} if rounds else {}))
    if "roofline" in want:
        from benchmarks import roofline
        run("roofline", roofline.main)
    if "fig9" in want:
        from benchmarks import fig9_activations
        run("fig9_activations", fig9_activations.main,
            **({"rounds": rounds} if rounds else {}))
    if "fig3" in want:
        from benchmarks import fig3_gradnorms
        run("fig3_gradnorms", fig3_gradnorms.main,
            **({"rounds": rounds} if rounds else {}))
    if "fig2" in want:
        from benchmarks import fig2_convergence
        run("fig2_convergence", fig2_convergence.main,
            **({"rounds": rounds} if rounds else {}))
    if "fig4" in want:
        from benchmarks import fig4_clients
        run("fig4_clients", fig4_clients.main,
            **({"rounds": rounds} if rounds else {}))
    if "fig8" in want:
        from benchmarks import fig8_scaling_ablation
        run("fig8_scaling_ablation", fig8_scaling_ablation.main,
            **({"rounds": rounds} if rounds else {}))
    if "tab12" in want:
        from benchmarks import tab12_accuracy
        run("tab12_accuracy", tab12_accuracy.main,
            **({"rounds": rounds} if rounds else {}))
    if "table" in want:
        run("trajectory", trajectory)
    if failed:
        sys.exit(f"benchmark suites failed: {', '.join(failed)}")


if __name__ == "__main__":
    main()
