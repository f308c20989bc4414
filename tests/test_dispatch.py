"""Kernel-dispatch subsystem: custom-VJP fwd+bwd parity vs the ref.py oracle
(interpret mode, including padded non-block-divisible shapes), tier selection,
and proof that the model forward/backward route through the dispatcher when
``use_pallas`` is enabled."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import LoRAConfig, ModelConfig
from repro.core.lora import AdapterSet, init_lora
from repro.kernels import dispatch, ref
from repro.kernels.lora_matmul import lora_matmul_vjp
from repro.models.api import build_model


@pytest.fixture(autouse=True)
def _clean_dispatch():
    dispatch.force_mode(None)
    dispatch.reset_stats()
    yield
    dispatch.force_mode(None)


def _operands(m, k, n, r, seed=0):
    ks = jax.random.split(jax.random.key(seed), 4)
    x = jax.random.normal(ks[0], (m, k), jnp.float32)
    w = jax.random.normal(ks[1], (k, n), jnp.float32) * k ** -0.5
    a = jax.random.normal(ks[2], (r, k), jnp.float32) * 0.05
    b = jax.random.normal(ks[3], (n, r), jnp.float32) * 0.05
    return x, w, a, b


# ------------------------------------------------------- custom-VJP parity

@pytest.mark.parametrize("m,k,n,r", [(64, 64, 64, 4), (128, 256, 128, 16)])
def test_vjp_forward_parity(m, k, n, r):
    x, w, a, b = _operands(m, k, n, r)
    out = lora_matmul_vjp(x, w, a, b, 1.5, bm=64, bn=64, bk=64, interpret=True)
    want = ref.lora_matmul_ref(x, w, a, b, 1.5)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=2e-5, atol=2e-4)


@pytest.mark.parametrize("m,k,n,r", [(64, 64, 64, 8), (128, 128, 64, 4)])
def test_vjp_backward_parity(m, k, n, r):
    x, w, a, b = _operands(m, k, n, r, seed=7)
    gamma = 2.0
    cot = jax.random.normal(jax.random.key(99), (m, n))

    def fused(*t):
        return (lora_matmul_vjp(*t, gamma, bm=64, bn=64, bk=64,
                                interpret=True) * cot).sum()

    def reference(*t):
        return (ref.lora_matmul_ref(*t, gamma) * cot).sum()

    got = jax.grad(fused, argnums=(0, 1, 2, 3))(x, w, a, b)
    want = jax.grad(reference, argnums=(0, 1, 2, 3))(x, w, a, b)
    for g1, g2, name in zip(got, want, "xwab"):
        np.testing.assert_allclose(np.asarray(g1), np.asarray(g2),
                                   rtol=1e-4, atol=1e-4, err_msg=f"d{name}")


@pytest.mark.parametrize("m,k,n,r", [(50, 70, 30, 3), (100, 300, 130, 5)])
def test_dispatch_pads_non_divisible_shapes(m, k, n, r):
    """fused_lora_apply zero-pads to block multiples and slices back — fwd
    and bwd exact for shapes no block size divides."""
    x, w, a, b = _operands(m, k, n, r, seed=3)
    gamma = 1.3
    out = dispatch.fused_lora_apply(x, w, a, b, gamma, interpret=True)
    want = ref.lora_matmul_ref(x, w, a, b, gamma)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=1e-4, atol=1e-4)

    def fused(*t):
        return dispatch.fused_lora_apply(*t, gamma, interpret=True).sum()

    def reference(*t):
        return ref.lora_matmul_ref(*t, gamma).sum()

    got = jax.grad(fused, argnums=(0, 1, 2, 3))(x, w, a, b)
    want_g = jax.grad(reference, argnums=(0, 1, 2, 3))(x, w, a, b)
    for g1, g2, name in zip(got, want_g, "xwab"):
        assert g1.shape == g2.shape
        np.testing.assert_allclose(np.asarray(g1), np.asarray(g2),
                                   rtol=1e-4, atol=1e-4, err_msg=f"d{name}")


# --------------------------------------------------------- tier selection

def test_mode_reference_without_use_pallas():
    assert dispatch.resolve_mode() == "reference"
    dispatch.force_mode("interpret")     # forced tier never overrides off
    assert dispatch.resolve_mode() == "reference"


def test_mode_forced_inside_scope():
    with dispatch.scope(True):
        dispatch.force_mode("interpret")
        assert dispatch.resolve_mode() == "interpret"
        dispatch.force_mode(None)
        # CPU backend without REPRO_KERNEL_INTERPRET falls back to reference
        if jax.default_backend() != "tpu":
            import os
            if os.environ.get("REPRO_KERNEL_INTERPRET") in (None, "0", "false"):
                assert dispatch.resolve_mode() == "reference"
    assert dispatch.resolve_mode() == "reference"


def test_force_mode_rejects_unknown():
    with pytest.raises(ValueError):
        dispatch.force_mode("cuda")


def test_fused_tier_handles_empty_operands():
    """Zero-sized dims (empty batch) return empty results on the fused tier
    instead of crashing — same behavior as the reference tier."""
    _, w, a, b = _operands(8, 32, 16, 4)
    empty = jnp.zeros((0, 32), jnp.float32)
    want = dispatch.lora_linear(empty, w, {"a": a, "b": b}, 1.5)
    with dispatch.scope(True):
        dispatch.force_mode("interpret")
        got = dispatch.lora_linear(empty, w, {"a": a, "b": b}, 1.5)
    assert got.shape == want.shape == (0, 16)


def test_interpret_env_truthiness(monkeypatch):
    """Only affirmative values enable the interpreter tier — 'False', 'off',
    or an empty value must not silently route training through emulation."""
    if jax.default_backend() == "tpu":
        pytest.skip("TPU selects the pallas tier before the interpret env")
    with dispatch.scope(True):
        for val, want in [("1", "interpret"), ("true", "interpret"),
                          ("ON", "interpret"), ("0", "reference"),
                          ("False", "reference"), ("off", "reference"),
                          ("", "reference")]:
            monkeypatch.setenv("REPRO_KERNEL_INTERPRET", val)
            assert dispatch.resolve_mode() == want, val


# ------------------------------------------------- model-stack integration

def _tiny_cfg(use_pallas):
    return ModelConfig(name="t", family="dense", num_layers=2, d_model=32,
                       num_heads=2, num_kv_heads=2, head_dim=16, d_ff=64,
                       vocab_size=64, use_pallas=use_pallas)


def _tiny_setup(cfg):
    model = build_model(cfg)
    params = model.init(jax.random.key(0))
    lora = init_lora(params, jax.random.key(2), LoRAConfig(rank=4))
    lora = jax.tree.map(lambda x: x + 0.02, lora)       # make B nonzero
    return model, params, lora


def test_model_forward_routes_through_dispatch():
    """With use_pallas on (interpret tier), the forward provably runs the
    fused kernel — and matches the reference path numerically."""
    dispatch.force_mode("interpret")
    toks = jax.random.randint(jax.random.key(1), (2, 8), 0, 64)
    results = {}
    for flag in (False, True):
        model, params, lora = _tiny_setup(_tiny_cfg(flag))
        dispatch.reset_stats()
        logits, _ = model.forward(params, {"tokens": toks},
                                  adapters=AdapterSet(lora=lora, gamma=1.1))
        results[flag] = (np.asarray(logits), dict(dispatch.stats))
    assert results[False][1]["fused"] == 0
    assert results[True][1]["fused"] > 0
    np.testing.assert_allclose(results[True][0], results[False][0],
                               rtol=5e-4, atol=5e-4)


def test_training_grads_match_reference_path():
    """jax.grad of the model loss wrt LoRA params agrees between the fused
    custom-VJP tier and the reference tier — the round-step hot loop is safe
    to route through the kernels."""
    dispatch.force_mode("interpret")
    toks = jax.random.randint(jax.random.key(4), (2, 8), 0, 64)
    grads = {}
    for flag in (False, True):
        model, params, lora = _tiny_setup(_tiny_cfg(flag))

        def loss_fn(l):
            return model.loss(params, {"tokens": toks},
                              adapters=AdapterSet(lora=l, gamma=1.1))[0]

        grads[flag] = jax.grad(loss_fn)(lora)
    for g1, g2 in zip(jax.tree.leaves(grads[True]),
                      jax.tree.leaves(grads[False])):
        np.testing.assert_allclose(np.asarray(g1), np.asarray(g2),
                                   rtol=2e-3, atol=2e-5)


def test_fused_tier_matches_reference_dtype_promotion():
    """Mixed precision (bf16 activations, fp32 weights): the fused tier must
    produce the same output dtype as the reference tier's `x @ w` promotion,
    so toggling use_pallas never changes downstream numerics."""
    x, w, a, b = _operands(16, 32, 16, 4)
    xb = x.astype(jnp.bfloat16)
    lora = {"a": a, "b": b}
    ref_out = dispatch.lora_linear(xb, w, lora, 1.5)       # reference tier
    with dispatch.scope(True):
        dispatch.force_mode("interpret")
        fused_out = dispatch.lora_linear(xb, w, lora, 1.5)
    assert fused_out.dtype == ref_out.dtype == jnp.float32
    np.testing.assert_allclose(np.asarray(fused_out), np.asarray(ref_out),
                               rtol=1e-2, atol=1e-2)
    # pure-bf16 operands stay bf16 on both tiers
    wb, ab, bb = (t.astype(jnp.bfloat16) for t in (w, a, b))
    ref_out = dispatch.lora_linear(xb, wb, {"a": ab, "b": bb}, 1.5)
    with dispatch.scope(True):
        fused_out = dispatch.lora_linear(xb, wb, {"a": ab, "b": bb}, 1.5)
    assert fused_out.dtype == ref_out.dtype == jnp.bfloat16
    # fp32 adapters on a bf16 base also promote identically
    ref_out = dispatch.lora_linear(xb, wb, {"a": a, "b": b}, 1.5)
    with dispatch.scope(True):
        fused_out = dispatch.lora_linear(xb, wb, {"a": a, "b": b}, 1.5)
    assert fused_out.dtype == ref_out.dtype == jnp.float32


def test_fused_tier_rejects_traced_gamma():
    """gamma is baked into the kernels at trace time; a traced gamma on the
    fused tier must fail with a clear message, not a ConcretizationTypeError
    deep inside (callers jit with gamma in static_argnames, as
    FederatedTrainer.eval_perplexity does)."""
    x, w, a, b = _operands(16, 32, 16, 4)
    lora = {"a": a, "b": b}
    with dispatch.scope(True):
        dispatch.force_mode("interpret")
        with pytest.raises(TypeError, match="static"):
            jax.jit(lambda g: dispatch.lora_linear(x, w, lora, g))(
                jnp.asarray(1.5))


def test_jitted_round_step_routes_and_matches_reference():
    """The actual hot loop — make_fed_round_step's jit(vmap(scan(grad(...))))
    — runs on the fused tier and produces the same loss/grad-norm as the
    reference tier (guards the custom-VJP pallas_call against vmap/scan
    batching regressions)."""
    from repro.configs.base import FederatedConfig, OptimizerConfig
    from repro.core.federated import FederatedTrainer
    from repro.data.synthetic import FederatedDataset
    dispatch.force_mode("interpret")
    metrics = {}
    for flag in (False, True):
        cfg = _tiny_cfg(flag)
        model = build_model(cfg)
        ds = FederatedDataset(cfg.vocab_size, 2, seq_len=8, batch_per_client=2)
        tr = FederatedTrainer(model, ds, lora_cfg=LoRAConfig(rank=4),
                              fed_cfg=FederatedConfig(num_clients=2,
                                                      local_steps=1),
                              opt_cfg=OptimizerConfig(lr=1e-2))
        dispatch.reset_stats()
        metrics[flag] = (tr.run_round(), dict(dispatch.stats))
    assert metrics[False][1]["fused"] == 0
    assert metrics[True][1]["fused"] > 0
    for key in ("loss", "grad_norm"):
        np.testing.assert_allclose(metrics[True][0][key],
                                   metrics[False][0][key], rtol=1e-4)


def test_eval_perplexity_on_fused_tier():
    """FederatedTrainer.eval_perplexity jits the loss with static gamma —
    must work with use_pallas enabled."""
    from repro.configs.base import FederatedConfig, OptimizerConfig
    from repro.core.federated import FederatedTrainer
    from repro.data.synthetic import FederatedDataset
    dispatch.force_mode("interpret")
    cfg = _tiny_cfg(True)
    model = build_model(cfg)
    ds = FederatedDataset(cfg.vocab_size, 2, seq_len=8, batch_per_client=2)
    tr = FederatedTrainer(model, ds, lora_cfg=LoRAConfig(rank=4),
                          fed_cfg=FederatedConfig(num_clients=2,
                                                  local_steps=1),
                          opt_cfg=OptimizerConfig(lr=1e-2))
    dispatch.reset_stats()
    ppl = tr.eval_perplexity(batch=2)
    assert dispatch.stats["fused"] > 0
    assert np.isfinite(ppl) and ppl > 1.0


def test_decode_step_routes_through_dispatch(paged_cache):
    dispatch.force_mode("interpret")
    model, params, lora = _tiny_setup(_tiny_cfg(True))
    cache, table = paged_cache(model, 2, 16)
    dispatch.reset_stats()
    logits, _ = model.decode_step(params, cache, jnp.zeros((2, 1), jnp.int32),
                                  jnp.zeros((2,), jnp.int32),
                                  adapters=AdapterSet(lora=lora, gamma=1.1),
                                  table=table)
    assert dispatch.stats["fused"] > 0
    assert logits.shape[:2] == (2, 1)
