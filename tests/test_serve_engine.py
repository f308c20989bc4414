"""The serving engine against the plain reference: prefill parity with the
token-by-token decode on the block pool, scheduled greedy tokens checked
against ``Model.forward`` for every serving signature and block family, the
CLI's routing through the scheduler, and the serve jit-cache lifetime
regression."""
import argparse
import dataclasses
import gc
import weakref

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.analysis import trace
from repro.configs import get_config
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


def _stepwise(model, params, cache, table, prompt, adapters=None):
    """The prompt fed through single-token decode steps: (logits (b, p, V),
    cache)."""
    b, p = prompt.shape
    step = jax.jit(lambda c, t, pos: model.decode_step(
        params, c, t, pos, adapters, table=table))
    outs = []
    for t in range(p):
        lg, cache = step(cache, prompt[:, t:t + 1], jnp.full((b,), t))
        outs.append(lg)
    return jnp.concatenate(outs, axis=1), cache


def _assert_trees_close(x, y):
    for (path, a), (_, b) in zip(jax.tree_util.tree_flatten_with_path(x)[0],
                                 jax.tree_util.tree_flatten_with_path(y)[0]):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-5, atol=1e-5, err_msg=str(path))


def _serve_rows(model, params, prompt, steps, *, bank=None, ids=None,
                **kw):
    """Every row of ``prompt`` as one request arrived at time 0; the
    scheduled tokens per row."""
    prompt = np.asarray(prompt, np.int32)
    ids = np.zeros(len(prompt), np.int32) if ids is None else np.asarray(ids)
    reqs = [serve.Request(rid=i, prompt=prompt[i], steps=steps,
                          adapter_id=int(ids[i])) for i in range(len(prompt))]
    kw.setdefault("max_batch", len(prompt))
    done = serve.serve_scheduled(model, params, reqs, bank=bank, wait=False,
                                 **kw)
    return [r.tokens for r in done]


# ------------------------------------------------------------ prefill parity

def test_prefill_logits_match_forward(served, paged_cache):
    model, params, aset, _, prompt = served
    full, _ = model.forward(params, {"tokens": prompt}, adapters=aset)
    cache, table = paged_cache(model, 3, 9)
    pre, _ = model.prefill(params, cache, prompt, aset, table=table)
    np.testing.assert_allclose(np.asarray(full), np.asarray(pre),
                               rtol=1e-5, atol=1e-5)


def test_prefill_cache_matches_token_by_token(served, paged_cache):
    """The pool prefill fills equals what p sequential decode_step calls
    produce — and decoding continues identically from either."""
    model, params, aset, _, prompt = served
    b, p = prompt.shape
    cache, table = paged_cache(model, b, p + 3)
    _, pre_cache = model.prefill(params, cache, prompt, aset, table=table)
    _, loop_cache = _stepwise(model, params, cache, table, prompt, aset)
    _assert_trees_close(pre_cache, loop_cache)
    tok = jnp.full((b, 1), 7, jnp.int32)
    pos = jnp.full((b,), p)
    l1, _ = model.decode_step(params, pre_cache, tok, pos, aset, table=table)
    l2, _ = model.decode_step(params, loop_cache, tok, pos, aset,
                              table=table)
    np.testing.assert_allclose(np.asarray(l1), np.asarray(l2),
                               rtol=1e-5, atol=1e-5)


def test_prefill_logits_match_stepwise_logits(served, paged_cache):
    """Prefill logits equal the token-by-token decode's, position by
    position."""
    model, params, aset, _, prompt = served
    b, p = prompt.shape
    cache, table = paged_cache(model, b, p)
    pre, _ = model.prefill(params, cache, prompt, aset, table=table)
    stepped, _ = _stepwise(model, params, cache, table, prompt, aset)
    np.testing.assert_allclose(np.asarray(pre), np.asarray(stepped),
                               rtol=1e-5, atol=1e-5)


def test_prefill_sliding_window_overflow(paged_cache):
    """A prompt longer than a sliding-window ring keeps exactly the ring
    survivors the sequential decode would have kept."""
    cfg = _cfg(attn_window=4)
    model = build_model(cfg)
    params = model.init(jax.random.key(0))
    prompt = jax.random.randint(jax.random.key(5), (2, 7), 0, 64)
    b, p = prompt.shape
    cache, table = paged_cache(model, b, p + 2, block_size=2)
    assert table.shape[1] * 2 == 4                  # window-sized ring
    _, pre_cache = model.prefill(params, cache, prompt, table=table)
    _, loop_cache = _stepwise(model, params, cache, table, prompt)
    _assert_trees_close(pre_cache, loop_cache)


# --------------------------------------------- scheduled tokens vs forward

@pytest.mark.parametrize("variant", ["base", "adapter1", "bank"])
def test_scheduled_tokens_match_forward(served, variant, greedy_vs_forward):
    """Every serving signature — the base, one adapter (a bank of one
    tenant), a mixed-rank bank — serves greedy tokens of ``Model.forward``
    over the prompt and the tokens before them, in one admission dispatch
    plus one per decode chunk."""
    model, params, aset, bank, prompt = served
    steps, chunk = 6, 4
    ids = [2, 0, 1]
    if variant == "base":
        serve_bank, row_adapters = None, [None] * 3
    elif variant == "adapter1":
        serve_bank, ids = AdapterBank.from_sets([aset]), [0, 0, 0]
        row_adapters = [aset] * 3
    else:
        serve_bank = bank
        row_adapters = [bank.adapter(k) for k in ids]
    with trace.tracing() as t:
        tokens = _serve_rows(model, params, prompt, steps, bank=serve_bank,
                             ids=ids, chunk=chunk)
    assert t.counters["serve.dispatches"] == 1 + -(-(steps - 1) // chunk)
    for i, toks in enumerate(tokens):
        assert len(toks) == steps
        greedy_vs_forward(model, params, prompt[i], toks, row_adapters[i])


def test_scheduled_interpret_tier_matches_forward(greedy_vs_forward):
    """The engine survives the fused kernel tiers: with use_pallas +
    interpret mode, scheduled banked generation runs the BGMV kernel
    bodies and still serves the reference forward's greedy tokens (CI
    serve-perf runs this)."""
    cfg = _cfg(use_pallas=True, num_layers=1)
    model = build_model(cfg)
    params = model.init(jax.random.key(0))
    sets = [_nonzero(init_adapter_set(params, jax.random.key(30 + i),
                                      LoRAConfig(rank=r)), seed=40 + i)
            for i, r in enumerate((2, 4))]
    bank = AdapterBank.from_sets(sets)
    prompt = jax.random.randint(jax.random.key(6), (2, 4), 0, 64)
    ids = [1, 0]
    dispatch.force_mode("interpret")
    dispatch.reset_stats()
    tokens = _serve_rows(model, params, prompt, 4, bank=bank, ids=ids)
    assert dispatch.stats["bgmv"] > 0          # kernel tier actually ran
    for i, toks in enumerate(tokens):
        greedy_vs_forward(model, params, prompt[i], toks,
                          bank.adapter(ids[i]))


@pytest.mark.parametrize("pattern,extra", [
    (("rglru",), dict(rglru_d_state=32)),
    (("mlstm",), {}),
    (("attn", "rglru"), dict(rglru_d_state=32)),   # hybrid + tail block
    (("slstm",), {}),
])
def test_scheduled_recurrent_families_match_forward(pattern, extra,
                                                    greedy_vs_forward):
    """Admission fills every cache kind (pool blocks, RG-LRU state + conv
    tail, mLSTM matrix memory, sLSTM scalar state) and decode carries it:
    recurrent and hybrid stacks serve the forward's greedy tokens too."""
    cfg = _cfg(num_layers=3, block_pattern=pattern, **extra)
    model = build_model(cfg)
    params = model.init(jax.random.key(1))
    prompt = jax.random.randint(jax.random.key(7), (2, 5), 0, 64)
    for i, toks in enumerate(_serve_rows(model, params, prompt, 5)):
        greedy_vs_forward(model, params, prompt[i], toks)


def test_generated_tokens_stay_in_vocab(greedy_vs_forward):
    """No served token may be a padded-vocab id: the lm head projects to
    vocab_padded (multiple of 256) and the padding rows carry untrained
    nonzero logits — the engine takes its argmax over the real vocab."""
    cfg = _cfg(num_layers=1, vocab_size=64)       # vocab_padded == 256
    model = build_model(cfg)
    assert model.vocab_padded == 256
    params = model.init(jax.random.key(0))
    prompt = jax.random.randint(jax.random.key(8), (4, 3), 0, 64)
    for i, toks in enumerate(_serve_rows(model, params, prompt, 12)):
        assert max(toks) < cfg.vocab_size
        greedy_vs_forward(model, params, prompt[i], toks)


def _audio_cfg():
    return _cfg(num_layers=2, family="audio", block_pattern=("xattn",),
                encoder_layers=1, encoder_frames=4, encoder_d_model=32)


def test_scheduled_audio_family_completes(greedy_vs_forward):
    """Encoder-decoder (xattn) stacks serve through the scheduler: admission
    without an encoder output keeps the cache's cross K/V — zeros, the
    token-by-token path's semantics — so the cross-attention adds nothing to
    a served token.  With its output projection zeroed it adds nothing to
    ``Model.forward`` either, which then checks the self-attention pools and
    the cross K/V that the decode scan carries."""
    cfg = _audio_cfg()
    model = build_model(cfg)
    params = model.init(jax.random.key(2))
    cross = params["stack"]["repeat"]["p0"]["cross"]
    cross["o"] = jnp.zeros_like(cross["o"])
    frames = jnp.zeros((1, cfg.encoder_frames, cfg.d_model))
    prompt = jax.random.randint(jax.random.key(9), (2, 4), 0, 64)
    for i, toks in enumerate(_serve_rows(model, params, prompt, 4)):
        assert len(toks) == 4
        greedy_vs_forward(model, params, prompt[i], toks, frames=frames)


def test_decode_reads_each_layers_cross_kv(paged_cache):
    """A prefill given the encoder output fills each layer's cross K/V, and
    every decode step reads its own layer's from the carried cache: the
    prefill's logits and the steps' after it are ``Model.forward``'s over
    the same frames."""
    cfg = _audio_cfg()
    model = build_model(cfg)
    params = model.init(jax.random.key(2))
    b, p, k = 2, 4, 3
    seq = jax.random.randint(jax.random.key(11), (b, p + k), 0, 64)
    frames = jax.random.normal(jax.random.key(12),
                               (b, cfg.encoder_frames, cfg.d_model))
    full, _ = model.forward(params, {"tokens": seq, "frames": frames})
    cache, table = paged_cache(model, b, p + k)
    enc_out = model._encode(params, {"frames": frames})
    logits, cache = model.prefill(params, cache, seq[:, :p], table=table,
                                  enc_out=enc_out)
    step = jax.jit(lambda c, t, pos: model.decode_step(params, c, t, pos,
                                                       table=table))
    outs = [logits]
    for t in range(p, p + k):
        lg, cache = step(cache, seq[:, t:t + 1], jnp.full((b,), t))
        outs.append(lg)
    np.testing.assert_allclose(np.asarray(full),
                               np.asarray(jnp.concatenate(outs, axis=1)),
                               rtol=1e-5, atol=1e-5)


# ----------------------------------------------------------------- the CLI

CLI = ["--arch", "gemma-2b", "--reduced", "--steps", "4"]


def test_cli_burst_serves_batch_through_scheduler(greedy_vs_forward):
    """Without --arrival-trace the CLI serves --batch requests, all arrived
    at time 0, through the scheduler: the seeded 4-token prompts, tenants
    round-robin over the bank, greedy tokens of the forward."""
    with trace.tracing() as t:
        done = serve.main(CLI + ["--batch", "3", "--clients", "2",
                                 "--max-batch", "2"])
    assert [r.rid for r in done] == [0, 1, 2]
    assert [r.adapter_id for r in done] == [0, 1, 0]
    assert all(len(r.tokens) == 4 for r in done)
    assert t.counters["serve.admitted"] == 3
    cfg = get_config("gemma-2b").reduced()
    model = build_model(cfg)
    ns = argparse.Namespace(resume=None, ranks="", rank=8, clients=2,
                            alpha=8.0, scaling="sfedlora")
    base, bank = serve.build_bank(ns, cfg, model)
    prompts = np.asarray(jax.random.randint(jax.random.key(2), (3, 4), 0,
                                            cfg.vocab_size))
    for r in done:
        np.testing.assert_array_equal(r.prompt, prompts[r.rid])
        greedy_vs_forward(model, base, r.prompt, r.tokens,
                          bank.adapter(r.adapter_id))


def test_cli_merge_serves_tenant_tokens(greedy_vs_forward):
    """--merge 0 serves the merged base with no bank: every request gets
    the tokens tenant 0 gets from the bank."""
    done = serve.main(CLI + ["--batch", "2", "--merge", "0"])
    assert len(done) == 2 and all(len(r.tokens) == 4 for r in done)
    cfg = get_config("gemma-2b").reduced()
    model = build_model(cfg)
    ns = argparse.Namespace(resume=None, ranks="", rank=8, clients=4,
                            alpha=8.0, scaling="sfedlora")
    base, bank = serve.build_bank(ns, cfg, model)
    for r in done:
        greedy_vs_forward(model, base, r.prompt, r.tokens, bank.adapter(0))


# ------------------------------------------------------- jit-cache lifetime

def test_serve_jit_cache_does_not_pin_models():
    """The serve-layer jit caches must not keep dead models (and their
    compiled executables) alive for process lifetime, as an
    ``lru_cache(maxsize=None)`` keyed on models would.  The cache lives on
    the model, so the model+executables become collectable garbage
    together."""
    cfg = _cfg(num_layers=1)
    model = build_model(cfg)
    params = model.init(jax.random.key(0))
    _serve_rows(model, params, np.zeros((1, 2), np.int32), 2)
    assert {"paged_admit", "paged_chunk"} <= set(
        model.__dict__["_serve_jit_cache"])         # caches exist...
    ref = weakref.ref(model)
    del model
    gc.collect()
    assert ref() is None                            # ...and die with it


def test_serve_jit_cache_reuses_executables(served):
    """Serving again must reuse the per-model jitted programs (the whole
    point of the cache): same function objects, no new executable."""
    model, params, _, _, prompt = served
    _serve_rows(model, params, prompt, 3)
    cache = model.__dict__["_serve_jit_cache"]
    fns = {k: cache[k] for k in ("paged_admit", "paged_chunk")}
    sizes = {k: f._cache_size() for k, f in fns.items()}
    _serve_rows(model, params, prompt, 3)
    for k, f in fns.items():
        assert cache[k] is f
        assert f._cache_size() == sizes[k]
