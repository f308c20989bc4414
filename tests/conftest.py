"""Shared test harness options and serving fixtures.

``pytest --recompile-guard`` wraps every jitted serving engine built
through ``serve._model_jit`` in a :class:`repro.analysis.sanitizers.
RecompileGuard` (wrap mode, fresh guard per test): a recompile on a
previously-served signature, or unbounded treedef churn at fixed avals,
fails the offending test at the offending call instead of showing up as
slowness.  Off by default — the guard adds a per-call signature hash.

``paged_cache`` builds an empty serving cache and its block table;
``greedy_vs_forward`` checks served tokens against ``Model.forward``.
"""
import dataclasses

import jax.numpy as jnp
import numpy as np
import pytest


def pytest_addoption(parser):
    parser.addoption(
        "--recompile-guard", action="store_true", default=False,
        help="run serve's jitted engines under a RecompileGuard "
             "(recompiles on served signatures become hard errors)")


@pytest.fixture(autouse=True)
def _recompile_guard(request, monkeypatch):
    if not request.config.getoption("--recompile-guard"):
        yield
        return
    from repro.analysis.sanitizers import RecompileGuard
    from repro.launch import serve

    guard = RecompileGuard(max_treedef_variants=8)
    orig = serve._model_jit

    def guarded_model_jit(model, name, builder):
        # the raw jitted fn stays in model._serve_jit_cache (tests probe
        # _cache_size there); only the handle serve dispatches through is
        # wrapped, so attribution lands on the engine name
        fn = orig(model, name, builder)
        return guard.wrap(name, fn, cache_probe=fn)

    monkeypatch.setattr(serve, "_model_jit", guarded_model_jit)
    yield guard


@pytest.fixture
def paged_cache():
    """``make(model, b, max_len, block_size=4) -> (cache, table)``: an empty
    serving cache for ``b`` requests and their block table.  Request i owns
    pool blocks ``1 + i * mb .. (i + 1) * mb``, a virtual ring of ``mb *
    block_size`` slots covering ``max_len`` positions, or the attention
    window where the model has a shorter one; block 0 stays the
    scheduler's null block."""
    def make(model, b, max_len, block_size=4):
        win = model.cfg.attn_window
        ring = min(max_len, win) if win else max_len
        mb = -(-ring // block_size)
        cache = model.init_paged_cache(1 + b * mb, block_size, b)
        table = jnp.arange(1, 1 + b * mb, dtype=jnp.int32).reshape(b, mb)
        return cache, table
    return make


@pytest.fixture
def greedy_vs_forward():
    """``check(model, params, prompt, tokens, adapters=None, tol=1e-4,
    frames=None)``: served tokens against the plain reference,
    ``Model.forward`` on the reference kernel tier over the prompt followed
    by the served tokens (and an encoder-decoder model's ``frames``).
    Every token must be a real-vocabulary id whose logit at the previous
    position lies within ``tol`` of that position's maximum over the real
    vocabulary: a greedy choice, up to near-ties.  Returns the largest
    gap."""
    from repro.models.api import build_model

    def check(model, params, prompt, tokens, adapters=None, tol=1e-4,
              frames=None):
        vocab = model.cfg.vocab_size
        prompt = np.asarray(prompt, np.int32)
        tokens = np.asarray(tokens, np.int32)
        assert tokens.size and 0 <= tokens.min() and tokens.max() < vocab, \
            f"token outside the real vocabulary {vocab}: {tokens}"
        ref = build_model(dataclasses.replace(model.cfg, use_pallas=False))
        seq = np.concatenate([prompt, tokens])[None]
        batch = {"tokens": jnp.asarray(seq)}
        if frames is not None:
            batch["frames"] = frames
        logits, _ = ref.forward(params, batch, adapters=adapters)
        lg = np.asarray(logits[0, len(prompt) - 1:-1, :vocab], np.float32)
        gap = lg.max(-1) - lg[np.arange(len(tokens)), tokens]
        assert gap.max() <= tol, (
            f"served token off the forward's greedy choice by "
            f"{gap.max():.3g} at step {int(gap.argmax())}: {tokens}")
        return float(gap.max())
    return check
