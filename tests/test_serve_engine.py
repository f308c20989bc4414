"""Device-resident generation engine: prefill parity with the token-by-token
path, compiled generate vs the host-loop oracle (bit-identical tokens, one
host dispatch), every block family's cache fill, sampling semantics, and the
serve jit-cache lifetime regression."""
import dataclasses
import gc
import weakref

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.analysis import trace
from repro.configs.base import LoRAConfig, ModelConfig
from repro.core.lora import AdapterBank, init_adapter_set
from repro.kernels import dispatch
from repro.launch import serve
from repro.models.api import build_model


def _cfg(use_pallas=False, num_layers=3, **kw):
    base = dict(name="eng", family="dense", num_layers=num_layers,
                d_model=32, num_heads=2, num_kv_heads=1, head_dim=16,
                d_ff=64, vocab_size=64, use_pallas=use_pallas)
    base.update(kw)
    return ModelConfig(**base)


def _nonzero(aset, seed=9, scale=0.03):
    return dataclasses.replace(aset, lora=jax.tree.map(
        lambda x: x + scale * jax.random.normal(jax.random.key(seed), x.shape),
        aset.lora))


@pytest.fixture(autouse=True)
def _clean_dispatch():
    dispatch.force_mode(None)
    yield
    dispatch.force_mode(None)


@pytest.fixture(scope="module")
def served():
    cfg = _cfg()
    model = build_model(cfg)
    params = model.init(jax.random.key(0))
    sets = [_nonzero(init_adapter_set(params, jax.random.key(10 + i),
                                      LoRAConfig(rank=r)), seed=20 + i)
            for i, r in enumerate((2, 8, 4))]
    bank = AdapterBank.from_sets(sets)
    prompt = jax.random.randint(jax.random.key(3), (3, 5), 0, 64)
    return model, params, sets[1], bank, prompt


# ------------------------------------------------------------ prefill parity

def test_prefill_logits_match_forward(served):
    model, params, aset, _, prompt = served
    full, _ = model.forward(params, {"tokens": prompt}, adapters=aset)
    pre, _ = model.prefill(params, model.init_cache(3, 9), prompt, aset)
    np.testing.assert_allclose(np.asarray(full), np.asarray(pre),
                               rtol=1e-5, atol=1e-5)


def test_prefill_cache_matches_token_by_token(served):
    """The cache prefill returns equals what p sequential decode_step calls
    produce — and decoding continues identically from either."""
    model, params, aset, _, prompt = served
    b, p = prompt.shape
    _, pre_cache = model.prefill(params, model.init_cache(b, p + 3), prompt,
                                 aset)
    loop_cache = model.init_cache(b, p + 3)
    step = jax.jit(model.decode_step)
    loop_logits = []
    for t in range(p):
        lg, loop_cache = step(params, loop_cache, prompt[:, t:t + 1],
                              jnp.full((b,), t), aset)
        loop_logits.append(lg)
    for (path, x), (_, y) in zip(
            jax.tree_util.tree_flatten_with_path(pre_cache)[0],
            jax.tree_util.tree_flatten_with_path(loop_cache)[0]):
        np.testing.assert_allclose(np.asarray(x), np.asarray(y),
                                   rtol=1e-5, atol=1e-5, err_msg=str(path))
    tok = jnp.full((b, 1), 7, jnp.int32)
    pos = jnp.full((b,), p)
    l1, _ = step(params, pre_cache, tok, pos, aset)
    l2, _ = step(params, loop_cache, tok, pos, aset)
    np.testing.assert_allclose(np.asarray(l1), np.asarray(l2),
                               rtol=1e-5, atol=1e-5)


def test_prefill_logits_match_stepwise_logits(served):
    """Satellite: prefill-then-decode logits parity with the old token-by-
    token path, position by position."""
    model, params, aset, _, prompt = served
    b, p = prompt.shape
    pre, _ = model.prefill(params, model.init_cache(b, p), prompt, aset)
    cache = model.init_cache(b, p)
    step = jax.jit(model.decode_step)
    outs = []
    for t in range(p):
        lg, cache = step(params, cache, prompt[:, t:t + 1],
                         jnp.full((b,), t), aset)
        outs.append(lg)
    stepped = jnp.concatenate(outs, axis=1)
    np.testing.assert_allclose(np.asarray(pre), np.asarray(stepped),
                               rtol=1e-5, atol=1e-5)


def test_prefill_sliding_window_overflow():
    """A prompt longer than a sliding-window cache keeps exactly the ring-
    buffer survivors the sequential decode would have kept."""
    cfg = _cfg(attn_window=4)
    model = build_model(cfg)
    params = model.init(jax.random.key(0))
    prompt = jax.random.randint(jax.random.key(5), (2, 7), 0, 64)
    b, p = prompt.shape
    _, pre_cache = model.prefill(params, model.init_cache(b, p + 2), prompt)
    cache = model.init_cache(b, p + 2)
    step = jax.jit(model.decode_step)
    for t in range(p):
        _, cache = step(params, cache, prompt[:, t:t + 1], jnp.full((b,), t))
    for (path, x), (_, y) in zip(
            jax.tree_util.tree_flatten_with_path(pre_cache)[0],
            jax.tree_util.tree_flatten_with_path(cache)[0]):
        np.testing.assert_allclose(np.asarray(x), np.asarray(y),
                                   rtol=1e-5, atol=1e-5, err_msg=str(path))


# --------------------------------------------- compiled engine vs host loop

@pytest.mark.parametrize("variant", ["base", "adapter1", "bank"])
def test_compiled_generate_bit_identical_to_hostloop(served, variant):
    """Acceptance: compiled generation (prefill + scan decode) emits tokens
    BIT-IDENTICAL to the token-by-token host loop, for every serving
    signature, in one host dispatch."""
    model, params, aset, bank, prompt = served
    ids = jnp.asarray([2, 0, 1], jnp.int32)
    steps, max_len = 6, 11
    if variant == "base":
        comp = lambda: serve.generate(model, params, prompt, steps, max_len)
        host = lambda: serve.generate_hostloop(model, params, prompt, steps,
                                               max_len)
    elif variant == "adapter1":
        comp = lambda: serve.generate(model, params, prompt, steps, max_len,
                                      aset)
        host = lambda: serve.generate_hostloop(model, params, prompt, steps,
                                               max_len, aset)
    else:
        comp = lambda: serve.generate_banked(model, params, bank, ids,
                                             prompt, steps, max_len)
        host = lambda: serve.generate_banked_hostloop(model, params, bank,
                                                      ids, prompt, steps,
                                                      max_len)
    with trace.tracing() as t:
        got = comp()
        assert t.counters["serve.dispatches"] == 1
        want = host()
    assert t.counters["serve.dispatches"] == 1 + prompt.shape[1] + steps - 1
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_compiled_generate_interpret_tier():
    """The engine survives the fused kernel tiers: with use_pallas +
    interpret mode, compiled banked generation still matches the host-loop
    oracle token for token (CI serve-perf smoke runs this)."""
    cfg = _cfg(use_pallas=True, num_layers=1)
    model = build_model(cfg)
    params = model.init(jax.random.key(0))
    sets = [_nonzero(init_adapter_set(params, jax.random.key(30 + i),
                                      LoRAConfig(rank=r)), seed=40 + i)
            for i, r in enumerate((2, 4))]
    bank = AdapterBank.from_sets(sets)
    prompt = jax.random.randint(jax.random.key(6), (2, 4), 0, 64)
    ids = jnp.asarray([1, 0], jnp.int32)
    dispatch.force_mode("interpret")
    dispatch.reset_stats()
    got = serve.generate_banked(model, params, bank, ids, prompt, 4, 8)
    assert dispatch.stats["bgmv"] > 0          # kernel tier actually ran
    want = serve.generate_banked_hostloop(model, params, bank, ids, prompt,
                                          4, 8)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("pattern,extra", [
    (("rglru",), dict(rglru_d_state=32)),
    (("mlstm",), {}),
    (("attn", "rglru"), dict(rglru_d_state=32)),   # hybrid + tail block
    (("slstm",), {}),
])
def test_compiled_generate_recurrent_families(pattern, extra):
    """Prefill fills every cache kind (KV ring buffer, RG-LRU state + conv
    tail, mLSTM matrix memory, sLSTM scalar state): compiled generation
    matches the host loop for recurrent and hybrid stacks too."""
    cfg = _cfg(num_layers=3, block_pattern=pattern, **extra)
    model = build_model(cfg)
    params = model.init(jax.random.key(1))
    prompt = jax.random.randint(jax.random.key(7), (2, 5), 0, 64)
    got = serve.generate(model, params, prompt, 5, 10)
    want = serve.generate_hostloop(model, params, prompt, 5, 10)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


# ----------------------------------------------------------------- sampling

def test_temperature_sampling_semantics(served):
    model, params, aset, _, prompt = served
    greedy = serve.generate(model, params, prompt, 6, 11, aset)
    t0 = serve.generate(model, params, prompt, 6, 11, aset, temperature=0.0,
                        key=jax.random.key(5))
    np.testing.assert_array_equal(np.asarray(greedy), np.asarray(t0))
    s1 = serve.generate(model, params, prompt, 6, 11, aset, temperature=0.7,
                        key=jax.random.key(5))
    s2 = serve.generate(model, params, prompt, 6, 11, aset, temperature=0.7,
                        key=jax.random.key(5))
    np.testing.assert_array_equal(np.asarray(s1), np.asarray(s2))
    assert s1.shape == greedy.shape
    np.testing.assert_array_equal(np.asarray(s1[:, :prompt.shape[1]]),
                                  np.asarray(prompt))


def test_generated_tokens_stay_in_vocab():
    """Neither greedy nor sampling may emit a padded-vocab id: the lm head
    projects to vocab_padded (multiple of 256) and the padding rows carry
    untrained nonzero logits — both engines slice to the real vocab."""
    cfg = _cfg(num_layers=1, vocab_size=64)       # vocab_padded == 256
    model = build_model(cfg)
    params = model.init(jax.random.key(0))
    prompt = jax.random.randint(jax.random.key(8), (4, 3), 0, 64)
    for temp, key in ((2.5, jax.random.key(11)), (0.0, None)):
        seq = serve.generate(model, params, prompt, 12, 15,
                             temperature=temp, key=key)
        assert int(jnp.max(seq)) < cfg.vocab_size, f"temperature={temp}"
    host = serve.generate_hostloop(model, params, prompt, 12, 15)
    assert int(jnp.max(host)) < cfg.vocab_size
    np.testing.assert_array_equal(
        np.asarray(serve.generate(model, params, prompt, 12, 15)),
        np.asarray(host))


def test_compiled_generate_audio_family():
    """Encoder-decoder (xattn) stacks generate through the compiled engine
    too: prefill without an encoder output keeps the cache's cross K/V —
    the token-by-token path's semantics — instead of crashing."""
    cfg = _cfg(num_layers=2, family="audio", block_pattern=("xattn",),
               encoder_layers=1, encoder_frames=4, encoder_d_model=32)
    model = build_model(cfg)
    params = model.init(jax.random.key(2))
    prompt = jax.random.randint(jax.random.key(9), (2, 4), 0, 64)
    got = serve.generate(model, params, prompt, 4, 8)
    want = serve.generate_hostloop(model, params, prompt, 4, 8)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_generate_rejects_zero_steps(served):
    model, params, *_ , prompt = served
    with pytest.raises(ValueError, match="steps"):
        serve.generate(model, params, prompt, 0, 8)


# ------------------------------------------------------- jit-cache lifetime

def test_serve_jit_cache_does_not_pin_models():
    """Satellite regression: the serve-layer jit caches must not keep dead
    models (and their compiled executables) alive for process lifetime, as
    the old ``lru_cache(maxsize=None)`` did.  The cache lives on the model,
    so the model+executables become collectable garbage together."""
    cfg = _cfg(num_layers=1)
    model = build_model(cfg)
    params = model.init(jax.random.key(0))
    prompt = jnp.zeros((1, 2), jnp.int32)
    serve.generate(model, params, prompt, 2, 4)
    serve.generate_hostloop(model, params, prompt, 2, 4)
    assert "_serve_jit_cache" in model.__dict__     # caches exist...
    ref = weakref.ref(model)
    del model
    gc.collect()
    assert ref() is None                            # ...and die with it


def test_serve_jit_cache_reuses_executables(served):
    """Re-entering generate must reuse the per-model jitted program (the
    whole point of the cache): no new entry, same function object."""
    model, params, _, _, prompt = served
    serve.generate(model, params, prompt, 2, 7)
    fn1 = model.__dict__["_serve_jit_cache"]["generate"]
    serve.generate(model, params, prompt, 2, 7)
    assert model.__dict__["_serve_jit_cache"]["generate"] is fn1
