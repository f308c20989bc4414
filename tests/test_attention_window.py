"""Sliding-window decode: a window-sized virtual ring in the block pool must
reproduce full-sequence windowed attention even after wrapping (pos >
window) — the mechanism behind the long_500k shapes for mistral-nemo/gemma
variants."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import ModelConfig
from repro.models.api import build_model


@pytest.mark.parametrize("window,seq", [(4, 14), (6, 13), (8, 8)])
def test_ring_buffer_wraparound(window, seq, paged_cache):
    cfg = ModelConfig(name="w", family="dense", num_layers=2, d_model=64,
                      num_heads=4, num_kv_heads=2, head_dim=16, d_ff=128,
                      vocab_size=64, attn_window=window)
    model = build_model(cfg)
    params = model.init(jax.random.key(0))
    toks = jax.random.randint(jax.random.key(1), (2, seq), 0, 64)
    full, _ = model.forward(params, {"tokens": toks})
    # decode with a ring of EXACTLY the window size: forces wrap
    cache, table = paged_cache(model, 2, seq, block_size=window // 2)
    assert table.shape[1] * (window // 2) == window
    outs = []
    for t in range(seq):
        lg, cache = model.decode_step(params, cache, toks[:, t:t + 1],
                                      jnp.full((2,), t), table=table)
        outs.append(lg[:, 0])
    dec = jnp.stack(outs, 1)
    np.testing.assert_allclose(np.asarray(dec), np.asarray(full), rtol=2e-4,
                               atol=2e-4)


def test_long_position_decode_is_finite(paged_cache):
    """Decode at position ~500k with a window-sized ring (the long_500k
    semantics: state size independent of absolute position)."""
    cfg = ModelConfig(name="w", family="dense", num_layers=2, d_model=64,
                      num_heads=4, num_kv_heads=2, head_dim=16, d_ff=128,
                      vocab_size=64, attn_window=8)
    model = build_model(cfg)
    params = model.init(jax.random.key(0))
    cache, table = paged_cache(model, 1, 524_288)
    assert table.shape == (1, 2)                     # capped at the window
    assert cache["repeat"]["p0"]["k_pool"].shape[1:3] == (3, 4)
    pos = jnp.array([524_287], jnp.int32)
    lg, cache2 = model.decode_step(params, cache, jnp.ones((1, 1), jnp.int32),
                                   pos, table=table)
    assert bool(jnp.isfinite(lg).all())
