"""A training cell on a mesh over several chips, on four virtual CPU devices.

JAX fixes its device count when it starts, so the cell runs in a subprocess
with ``--xla_force_host_platform_device_count=4`` (as the program's own
``tests/test_sharding_multidevice.py`` does): the tiny training cell on
one device and on a ``1x4`` mesh, and the base weights made with and
without the mesh.  The refusals of a mesh that does not match the cell's
chips run ``bench/run.py`` itself.
"""
import json
import os
import shutil
import subprocess
import sys

import pytest

from conftest import BENCH, DATA, ROOT, TESTS

import harness

SCRIPT = r"""
import json, os, pathlib, sys
sys.path[:0] = [os.environ["BENCH_TESTS"]]
import conftest  # puts src/ and bench/ on the path
import jax
import numpy as np
import model as bmodel
import train_cell
from repro.launch.mesh import mesh_from_spec
from repro.models.api import build_model

out = {"devices": jax.device_count()}
mesh = mesh_from_spec("1x4")
for name in ("tiny-layernorm", "tiny-qknorm"):
    cfg = bmodel.load_config(name, conftest.DATA)
    model = build_model(bmodel.family(cfg).program_config(cfg))
    key = jax.random.key(11)
    one = jax.tree.leaves(bmodel.make_params(model, key))
    spread = jax.tree.leaves(bmodel.make_params(model, key, mesh=mesh))
    out[name] = {
        "equal": all(np.array_equal(np.asarray(a), np.asarray(b))
                     for a, b in zip(one, spread)),
        "leaves": len(one),
        # leaves of which no device holds the whole
        "split": sum(max(s.data.size for s in b.addressable_shards) < b.size
                     for b in spread),
        "on_devices": len(set().union(*(b.devices() for b in spread)))}

first = train_cell.first_chunk
for label, mix_extra, chips in (("one", {}, 1), ("mesh", {"mesh": "1x4"}, 4)):
    seen = {}

    def record(*a, **kw):
        seen.update(first(*a, **kw))
        return seen

    train_cell.first_chunk = record
    ctx = conftest.cell_ctx("train",
                            pathlib.Path(os.environ["BENCH_TMP"]) / label)
    ctx["mix"] = {**ctx["mix"], **mix_extra}
    ctx["chips"] = chips
    result, checks, notes = train_cell.run(ctx)
    out[label] = {"correct": result["correct"], "checks": checks,
                  "loss": seen["loss"], "norms": seen["norms"].tolist(),
                  "reference_loss": notes["reference_loss"],
                  "compiles_in_window": notes["compiles_in_window"],
                  "rounds": notes["rounds"]}
print(json.dumps(out))
"""


@pytest.fixture(scope="module")
def four(tmp_path_factory):
    """The script's readings, from one subprocess on four CPU devices."""
    env = {**os.environ, "JAX_PLATFORMS": "cpu",
           "XLA_FLAGS": "--xla_force_host_platform_device_count=4",
           "BENCH_TESTS": TESTS,
           "BENCH_TMP": str(tmp_path_factory.mktemp("mesh"))}
    out = subprocess.run([sys.executable, "-c", SCRIPT], cwd=ROOT, env=env,
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-4000:]
    return json.loads(out.stdout.strip().splitlines()[-1])


def test_four_devices(four):
    assert four["devices"] == 4


@pytest.mark.parametrize("name", ["tiny-layernorm", "tiny-qknorm"])
def test_params_on_a_mesh_equal_one_device(four, name):
    """The base made on the mesh, each device making its share, holds the
    same values as the base made on one device, and is spread."""
    got = four[name]
    assert got["equal"]
    assert got["on_devices"] == 4
    assert got["split"] >= 1


@pytest.mark.parametrize("label", ["one", "mesh"])
def test_train_cell_correct(four, label):
    got = four[label]
    assert got["correct"], got["checks"]
    assert got["rounds"] > 0
    # the window's first chunk reuses the set-up chunk's program
    assert got["compiles_in_window"] == 0


def test_train_cell_on_a_mesh_matches_one_device(four):
    """The same rounds on a 1x4 mesh as on one device: each round's loss
    and every leaf's change, from the program and from the reference."""
    one, mesh = four["one"], four["mesh"]
    assert mesh["loss"] == pytest.approx(one["loss"], rel=1e-5)
    assert mesh["reference_loss"] == pytest.approx(one["reference_loss"],
                                                   rel=1e-5)
    assert mesh["norms"] == pytest.approx(one["norms"], rel=1e-4)


# ------------------------------------------------------ chips against mesh

TRAIN = {"kind": "train"}


@pytest.mark.parametrize("chips,mix,refused", [
    (1, TRAIN, None),
    (4, {**TRAIN, "mesh": "1x4"}, None),
    (4, {**TRAIN, "mesh": "2x2"}, None),
    (1, {**TRAIN, "mesh": "1x4"}, "spans 4 chips"),
    (4, {**TRAIN, "mesh": "1x2"}, "spans 2 chips"),
    (4, TRAIN, "names no mesh"),
    (4, {**TRAIN, "mesh": "1x"}, "positive whole numbers"),
    (1, {"kind": "serve", "mesh": "1"}, "serving runs on one chip"),
], ids=["one-chip", "1x4", "2x2", "mesh-over-one-chip", "mesh-too-small",
        "no-mesh", "malformed", "serve-mesh"])
def test_check_chips(chips, mix, refused):
    got = harness.check_chips({"chips": chips}, mix)
    if refused is None:
        assert got is None
    else:
        assert refused in got


@pytest.fixture
def checkout(tmp_path):
    """A checkout whose BENCHMARK.json adds two training cells whose chips
    and mesh disagree."""
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    base = next(w for w in bench["workloads"] if w["chips"] == 1
                and w["name"].startswith("train-"))
    bench["workloads"] += [
        {**base, "name": "mesh-over-one-chip", "traffic": "tiny-fed-1x4"},
        {**base, "name": "four-chips-no-mesh", "chips": 4}]
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    shutil.copytree(BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    with open(os.path.join(DATA, "tiny-fed.json")) as f:
        mix = {**json.load(f), "mesh": "1x4"}
    (tmp_path / "bench" / "traffic" / "tiny-fed-1x4.json").write_text(
        json.dumps(mix))
    os.symlink(os.path.join(ROOT, "src"), tmp_path / "src")
    return tmp_path


@pytest.mark.parametrize("workload,says", [
    ("mesh-over-one-chip", "spans 4 chips"),
    ("four-chips-no-mesh", "names no mesh")])
def test_command_refuses_chips_and_mesh_apart(checkout, workload, says):
    """Exit 2 and no result, before the look for a TPU (which exits 3)."""
    out = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", workload, "--seed",
         "1", "--seconds", "1", "--trace", "0"], cwd=checkout,
        env={**os.environ, "JAX_PLATFORMS": "cpu"}, capture_output=True,
        text=True, timeout=120)
    assert out.returncode == 2 and out.stdout == ""
    assert says in out.stderr and "no TPU" not in out.stderr
