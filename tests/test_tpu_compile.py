"""Compile the Pallas kernels of both hot paths for a described TPU v5e.

No chip is needed: the TPU compiler compiles for a ``v5e:2x2`` topology
that is described, not attached, and refuses what Mosaic would refuse on
the chip (block shapes off the (8, 128) tiling, too much VMEM) — which the
interpret-mode tests cannot see.  Shapes are gemma-2b's: d_model 2048,
8 query heads of 256 over one KV head, LoRA rank 64 for training and the
serving bank's rank 8.  Every test asserts the kernel survived lowering as
a ``tpu_custom_call``.  The serving engines are compiled too, with the
frozen base's bfloat16 view, to show that no step converts a weight back
to float32.

The topology is described inside a fixture (never at import), so every
xdist worker collects the same tests and only the worker that runs this
file loads the TPU library.
"""
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.experimental import topologies
from jax.experimental.compilation_cache import compilation_cache
from jax.sharding import SingleDeviceSharding

from repro.configs.base import ModelConfig
from repro.core.lora import AdapterSet
from repro.core.quant import quantize
from repro.kernels import dispatch
from repro.kernels.bgmv import bgmv_gemv, bgmv_gemv_quant, bgmv_matmul
from repro.kernels.paged_attention import paged_attention
from repro.launch import serve
from repro.models.api import build_model

D, H, KV, HD = 2048, 8, 1, 256          # gemma-2b width and head geometry


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "not here"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a described-chip compile cannot be read back without the chip: keep
    # these compiles out of any persistent cache
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", prev)
    compilation_cache.reset_cache()


def _sds(sharding, shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _assert_kernel(fn, *args):
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


# ----------------------------------------------------- training: fused LoRA

@pytest.mark.parametrize("m", [128, 512])     # 1 client / 4 clients x seq 128
def test_fused_lora_forward_backward(one_chip, m):
    """q projection (d -> h*hd = 2048) with a rank-64 adapter: the custom-VJP
    forward and its fused backward kernels."""
    s = lambda *shape: _sds(one_chip, shape)
    gamma = 2.0

    def loss(x, w, a, b):
        return dispatch.fused_lora_apply(x, w, a, b, gamma,
                                         interpret=False).sum()

    _assert_kernel(lambda *t: dispatch.fused_lora_apply(*t, gamma,
                                                        interpret=False),
                   s(m, D), s(D, H * HD), s(64, D), s(H * HD, 64))
    _assert_kernel(jax.grad(loss, argnums=(0, 2, 3)),
                   s(m, D), s(D, H * HD), s(64, D), s(H * HD, 64))


@pytest.mark.parametrize("n", [H * HD, KV * HD])     # q and v projections
def test_fused_lora_int8_forward_backward(one_chip, n):
    wq = jax.eval_shape(lambda w: quantize(w, 8), _sds(None, (D, n)))
    wq = jax.tree.map(lambda t: _sds(one_chip, t.shape, t.dtype), wq)
    s = lambda *shape: _sds(one_chip, shape)

    def loss(x, w, a, b):
        return dispatch.fused_lora_apply_quant(x, w, a, b, 2.0,
                                               interpret=False).sum()

    _assert_kernel(jax.grad(loss, argnums=(0, 2, 3)),
                   s(512, D), wq, s(64, D), s(n, 64))


# --------------------------------------------------------- serving: BGMV

@pytest.mark.parametrize("bsz,s,r", [(4, 4, 8), (8, 32, 64)])
def test_bgmv_matmul(one_chip, bsz, s, r):
    sd = lambda *shape: _sds(one_chip, shape)
    _assert_kernel(lambda *t: bgmv_matmul(*t), sd(bsz, s, D),
                   sd(D, H * HD), sd(8, r, D), sd(8, H * HD, r),
                   _sds(one_chip, (bsz,), jnp.int32))


@pytest.mark.parametrize("bsz,r,n", [(4, 8, H * HD), (4, 8, KV * HD),
                                     (8, 64, H * HD)])
def test_bgmv_gemv(one_chip, bsz, r, n):
    sd = lambda *shape: _sds(one_chip, shape)
    _assert_kernel(lambda *t: bgmv_gemv(*t), sd(bsz, D), sd(D, n),
                   sd(8, r, D), sd(8, n, r),
                   _sds(one_chip, (bsz,), jnp.int32))


@pytest.mark.parametrize("bits", [8, 4])
def test_bgmv_gemv_quant(one_chip, bits):
    wq = jax.eval_shape(lambda w: quantize(w, bits), _sds(None, (D, H * HD)))
    sd = lambda *shape: _sds(one_chip, shape)
    _assert_kernel(
        lambda x, wd, ws, a, b, ids: bgmv_gemv_quant(x, wd, ws, a, b, ids,
                                                     bits=bits),
        sd(4, D), _sds(one_chip, wq.data.shape, wq.data.dtype),
        sd(*wq.scales.shape), sd(8, 8, D), sd(8, H * HD, 8),
        _sds(one_chip, (4,), jnp.int32))


# ------------------------------------------------- serving: paged attention

@pytest.mark.parametrize("bs,window,softcap", [(8, None, None),
                                               (16, None, None),
                                               (16, 4096, 50.0)])
def test_paged_attention(one_chip, bs, window, softcap):
    bsz, mb = 4, 3
    pool = 1 + bsz * mb
    sd = lambda *shape: _sds(one_chip, shape)
    i32 = lambda *shape: _sds(one_chip, shape, jnp.int32)
    _assert_kernel(
        lambda *t: paged_attention(*t, window=window, softcap=softcap),
        sd(bsz, H, HD), sd(pool, bs, KV, HD), sd(pool, bs, KV, HD),
        i32(pool, bs), i32(bsz, mb), i32(bsz))


# ------------------------------------------- serving: the bfloat16 base view

@pytest.mark.parametrize("engine", ["admit", "chunk"])
def test_serving_view_reads_weights_as_bfloat16(one_chip, engine):
    """The engines given the base's bfloat16 view (``serve.serving_base``)
    hold no float32 value of a weight's shape, stacked or per layer: XLA
    reads the bfloat16 operand directly, the head's ``astype`` included."""
    cfg = ModelConfig(name="view", family="dense", num_layers=2,
                      d_model=256, num_heads=2, num_kv_heads=1, head_dim=128,
                      d_ff=768, vocab_size=1024, qk_norm=True,
                      tie_embeddings=False)
    model = build_model(cfg)
    flat, treedef = jax.tree_util.tree_flatten_with_path(
        jax.eval_shape(model.init, jax.random.key(0)))
    dot_only = [model.dot_only(tuple(k.key for k in path)) for path, _ in flat]
    params = treedef.unflatten([
        _sds(one_chip, leaf.shape, jnp.bfloat16 if dot else leaf.dtype)
        for (_, leaf), dot in zip(flat, dot_only)])
    shapes = {leaf.shape[i:] for (_, leaf), dot in zip(flat, dot_only)
              if dot for i in (0, leaf.ndim - 2)}
    b, mb, bs, plen = 4, 4, 8, 16
    cache = jax.tree.map(
        lambda t: _sds(one_chip, t.shape, t.dtype),
        jax.eval_shape(lambda: model.init_paged_cache(1 + b * mb, bs, b)))
    i32 = lambda *shape: _sds(one_chip, shape, jnp.int32)
    lora = {t: {"a": _sds(one_chip, (3, 2, 8, 256)),
                "b": _sds(one_chip, (3, 2, n, 8))}
            for t, n in (("q", 256), ("v", 128))}
    adapters = lambda g: AdapterSet(lora={"stack": {"repeat": {"p0": {
        "attn": lora}}}}, gamma=1.0, rank=8, batched=True, ids=i32(g))
    if engine == "admit":
        lowered = serve._jit_paged_admit(model).lower(
            params, cache, i32(2, plen), i32(2, mb), i32(2), i32(2 * mb),
            adapters(2))
    else:
        lowered = serve._jit_paged_chunk(model).lower(
            params, cache, i32(b, 1), i32(b),
            _sds(one_chip, (b,), jnp.bool_), i32(b, mb), adapters(b),
            steps=4)
    text = lowered.compile().as_text()
    f32_weights = [m for shape in shapes for m in re.findall(
        r"f32\[%s\]" % ",".join(map(str, shape)), text)]
    assert not f32_weights
    # the pattern matches how the text writes these shapes: as the
    # program's bfloat16 arguments
    assert all(re.search(r"bf16\[%s\]" % ",".join(map(str, shape)), text)
               for shape in shapes if len(shape) == 3)
