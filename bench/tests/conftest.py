"""The benchmark's own tests: run on the CPU at tiny sizes.

  JAX_PLATFORMS=cpu python -m pytest bench/tests -q
"""
import json
import os
import sys
import time
import types

import pytest

TESTS = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(TESTS)
ROOT = os.path.dirname(BENCH)
DATA = os.path.join(TESTS, "data")
sys.path[:0] = [os.path.join(ROOT, "src"), BENCH]

CELLS = {"train": ("tiny-layernorm", "tiny-fed",
                   "train-stablelm-1.6b-n8r64"),
         "serve": ("tiny-qknorm", "tiny-chat",
                   "serve-qwen3-8b-l6-poisson")}


def cell_ctx(kind, tmp_path, *, seed=2 ** 33 + 7, seconds=1.0, trace=0):
    """What ``bench/run.py`` hands a cell driver, for a tiny configuration
    and mix on the CPU, skipping only its look for a chip.  The limits are
    the tiny cells' own (``data/tiny-limits.json``)."""
    import harness
    import model as bmodel
    cfg_name, mix_name, workload = CELLS[kind]
    with open(os.path.join(DATA, mix_name + ".json")) as f:
        mix = json.load(f)
    return {"args": types.SimpleNamespace(workload=workload, seed=seed,
                                          seconds=seconds, trace=trace),
            "cfg": bmodel.load_config(cfg_name, DATA), "mix": mix,
            "chips": 1, "t0": time.monotonic(),
            "meter": harness.CompileMeter(), "spans": harness.Spans(),
            "limits": json.load(open(os.path.join(
                DATA, "tiny-limits.json")))["limits"],
            "trace_dir": str(tmp_path / "trace"),
            "per_layer": lambda rctx: {}}


@pytest.fixture
def ctx_for(tmp_path):
    return lambda kind, **kw: cell_ctx(kind, tmp_path, **kw)
