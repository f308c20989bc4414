"""Dry-run machinery unit tests that need no multi-device compile: the
collective-bytes HLO parser, shape policy, input specs, and sharding rules."""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import PartitionSpec as P

from repro.configs import (ASSIGNED, INPUT_SHAPES, LONG_CONTEXT_OK,
                           config_for_shape, get_config, supports_shape)
from repro.models.api import build_model

# import the parser without triggering the XLA_FLAGS device split
import importlib.util
import os
import sys

_spec = importlib.util.spec_from_file_location(
    "_dryrun_parse", os.path.join(os.path.dirname(__file__), "..", "src",
                                  "repro", "launch", "dryrun.py"))


def _load_parser():
    # dryrun sets XLA_FLAGS at import; jax is already initialized in tests so
    # the flag has no effect here — safe to import for the pure functions.
    mod = importlib.util.module_from_spec(_spec)
    _spec.loader.exec_module(mod)
    return mod


HLO = """
  %ag = bf16[4,1024]{1,0} all-gather(%p), replica_groups={}
  %ar = f32[512]{0} all-reduce(%q), to_apply=%sum
  %aa = (bf16[2,8]{1,0}, bf16[2,8]{1,0}) all-to-all(%a, %b)
  %cp = u32[16]{0} collective-permute(%c)
  %ags = bf16[64]{0} all-gather-start(%p)
  %dot = f32[4,4]{1,0} dot(%x, %y)
"""


def test_collective_bytes_parser():
    mod = _load_parser()
    out, counts = mod.collective_bytes(HLO)
    assert out["all-gather"] == 4 * 1024 * 2 + 64 * 2      # incl. -start
    assert counts["all-gather"] == 2
    assert out["all-reduce"] == 512 * 4
    assert out["all-to-all"] == 2 * (2 * 8 * 2)            # tuple result
    assert out["collective-permute"] == 16 * 4
    assert counts["reduce-scatter"] == 0


def test_long_context_policy():
    for arch in ASSIGNED:
        ok = supports_shape(arch, "long_500k")
        assert ok == (LONG_CONTEXT_OK[arch] is not None)
    assert not supports_shape("roberta-large", "decode_32k")
    assert supports_shape("roberta-large", "train_4k")


def test_sliding_window_variant_selected():
    cfg = config_for_shape("mistral-nemo-12b", "long_500k")
    assert cfg.attn_window == 4096
    cfg = config_for_shape("mistral-nemo-12b", "train_4k")
    assert cfg.attn_window is None
    # natively sub-quadratic archs keep their config
    cfg = config_for_shape("recurrentgemma-9b", "long_500k")
    assert cfg.block_pattern == ("rglru", "rglru", "attn")


@pytest.mark.parametrize("arch", sorted(ASSIGNED))
def test_input_specs_all_shapes(arch):
    """input_specs produce consistent ShapeDtypeStructs for every shape
    (no allocation — pure eval_shape)."""
    for name, shape in INPUT_SHAPES.items():
        if not supports_shape(arch, name):
            continue
        cfg = config_for_shape(arch, name)
        model = build_model(cfg)
        if shape.kind == "train":
            spec = model.input_specs(shape, n_clients=16)
            assert spec["tokens"].shape[0] == 16
            assert spec["tokens"].shape[1] == shape.global_batch // 16
        elif shape.kind == "prefill":
            spec = model.input_specs(shape)
            tok_s = spec["tokens"].shape[1]
            if cfg.family == "vlm":
                assert tok_s == shape.seq_len - cfg.num_patches
            else:
                assert tok_s == shape.seq_len
        else:
            spec = model.input_specs(shape)
            b = shape.global_batch
            assert spec["token"].shape == (b, 1)
            # each request owns a virtual ring of seq_len positions in
            # blocks of 8 — the window's, where the arch caps attention
            ring = min(shape.seq_len, cfg.attn_window or shape.seq_len)
            mb = -(-ring // 8)
            assert spec["table"].shape == (b, mb)
            assert spec["table"].dtype == jnp.int32
            leaves = jax.tree.leaves(spec["cache"])
            assert leaves, arch
            pools = [x for p, x in jax.tree_util.tree_flatten_with_path(
                spec["cache"])[0] if p[-1].key == "k_pool"]
            for pool in pools:       # (layers,) + (blocks, 8, kh, hd)
                assert pool.shape[-4:-2] == (b * mb, 8), arch


def test_param_spec_rules():
    from repro.sharding.rules import param_spec
    mesh = jax.make_mesh((1, 1), ("data", "model"))

    class FakeMesh:
        axis_names = ("data", "model")
        shape = {"data": 16, "model": 16}
    m = FakeMesh()
    assert param_spec(("embed",), (131072, 5120), m) == P("model", None)
    assert param_spec(("stack", "repeat", "p0", "attn", "q"),
                      (40, 5120, 4096), m) == P(None, None, "model")
    # kv dim not divisible -> replicated
    assert param_spec(("stack", "repeat", "p0", "attn", "k"),
                      (40, 5120, 8 * 128), m) == P(None, None, "model")
    assert param_spec(("stack", "repeat", "p0", "moe", "w_gate"),
                      (24, 64, 2048, 1408), m) == P(None, "model", None, None)
    assert param_spec(("stack", "tail", "t0", "mlp", "w_down"),
                      (14336, 5120), m) == P("model", None)
    assert param_spec(("final_scale",), (5120,), m) == P(None)


def test_cache_spec_pool_rules():
    """The KV pool's block dim shards over the batch axes where it divides
    (like a batch dim); the kv-head dim, else the head dim, over model."""
    from repro.sharding.rules import cache_spec

    class FakeMesh:
        axis_names = ("data", "model")
        shape = {"data": 16, "model": 16}
    m = FakeMesh()
    rep = ("repeat", "p0")
    assert cache_spec(rep + ("k_pool",), (40, 4096, 8, 16, 128), m) == P(
        None, "data", None, "model", None)
    # 8 kv heads do not divide 16: the head dim takes model
    assert cache_spec(rep + ("v_pool",), (40, 4096, 8, 8, 128), m) == P(
        None, "data", None, None, "model")
    assert cache_spec(rep + ("pos_pool",), (40, 4096, 8), m) == P(
        None, "data", None)
    # a block count the batch axes do not divide stays replicated
    assert cache_spec(("tail", "t0", "self", "k_pool"), (1025, 8, 16, 64),
                      m) == P(None, None, "model", None)


@pytest.mark.parametrize("env,want_repo", [({}, True),
                                           ({"JAX_COMPILATION_CACHE_DIR":
                                             "/elsewhere"}, False)])
def test_compile_cache_placement(env, want_repo):
    """The variable, when set, wins and the program sets nothing; otherwise
    the fixed repo-local directory (never a temp name)."""
    from repro.launch.compile_cache import REPO_CACHE, cache_dir
    got = cache_dir(env)
    assert got == (REPO_CACHE if want_repo else None)
    assert REPO_CACHE.name == ".jax_cache"
    assert (REPO_CACHE.parent / "src" / "repro").is_dir()


def test_peaks_table_rejects_unknown_device():
    from repro.launch.peaks import TARGET_KIND, peaks
    assert peaks(TARGET_KIND)["flops_bf16"] == 197e12
    assert peaks()["hbm_bw"] == 819e9
    with pytest.raises(ValueError, match="no published peaks"):
        peaks("cpu")


@pytest.mark.parametrize("spec", ["1", "1x1", "1x1x1"])
def test_meshes_use_auto_axes(spec):
    """Explicit axes (JAX 0.9's make_mesh default) reject the model's
    embedding gather; every mesh the launchers build is Auto."""
    from jax.sharding import AxisType
    from repro.launch.mesh import mesh_from_spec
    mesh = mesh_from_spec(spec)
    assert all(t == AxisType.Auto for t in mesh.axis_types)
