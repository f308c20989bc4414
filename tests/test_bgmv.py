"""BGMV kernel tier: fused multi-adapter parity (interpret vs the einsum
reference) across mixed ranks / rank masks, dispatch routing for banked
{"a","b","ids"} nodes, and K=1 vs single-adapter equivalence."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import LoRAConfig
from repro.core.lora import AdapterBank, init_adapter_set
from repro.kernels import dispatch
from repro.kernels.bgmv import bgmv_gemv, bgmv_matmul, bgmv_reference


@pytest.fixture(autouse=True)
def _clean_dispatch():
    dispatch.force_mode(None)
    dispatch.reset_stats()
    yield
    dispatch.force_mode(None)


def _bank_operands(B, s, k, n, K, r, seed=0):
    ks = jax.random.split(jax.random.key(seed), 5)
    x = jax.random.normal(ks[0], (B, s, k))
    w = jax.random.normal(ks[1], (k, n)) * k ** -0.5
    a = jax.random.normal(ks[2], (K, r, k)) * 0.05
    b = jax.random.normal(ks[3], (K, n, r)) * 0.05
    ids = jax.random.randint(ks[4], (B,), 0, K, jnp.int32)
    return x, w, a, b, ids


# ------------------------------------------------------------ kernel parity

@pytest.mark.parametrize("B,s,k,n,K,r", [
    (4, 8, 64, 64, 4, 8),          # block-divisible
    (5, 3, 70, 50, 3, 9),          # nothing divides: padding in every dim
    (8, 1, 128, 96, 8, 16),        # decode shape through the matmul form
    (2, 6, 32, 256, 5, 4),         # n spans two blocks
])
def test_bgmv_matmul_parity(B, s, k, n, K, r):
    x, w, a, b, ids = _bank_operands(B, s, k, n, K, r, seed=B + r)
    got = bgmv_matmul(x, w, a, b, ids, interpret=True)
    want = bgmv_reference(x, w, a, b, ids)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("B,k,n,K,r", [
    (4, 64, 64, 4, 8), (7, 70, 50, 3, 5), (8, 128, 300, 8, 16)])
def test_bgmv_gemv_parity(B, k, n, K, r):
    x, w, a, b, ids = _bank_operands(B, 1, k, n, K, r, seed=B)
    got = bgmv_gemv(x[:, 0], w, a, b, ids, interpret=True)
    want = bgmv_reference(x, w, a, b, ids)[:, 0]
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


def test_bgmv_traced_ids_under_jit():
    """ids are traced (one executable per tenant-mix is the whole point):
    the scalar-prefetch index_maps must work on runtime values."""
    x, w, a, b, ids = _bank_operands(4, 2, 64, 64, 4, 8, seed=3)
    f = jax.jit(lambda i: bgmv_matmul(x, w, a, b, i, interpret=True))
    for perm in (ids, ids[::-1], jnp.zeros_like(ids)):
        np.testing.assert_allclose(
            np.asarray(f(perm)), np.asarray(bgmv_reference(x, w, a, b, perm)),
            rtol=2e-5, atol=2e-5)


def test_bgmv_mixed_rank_zero_padding_exact():
    """A mixed-rank bank stores zero-padded adapters; the kernel must treat
    the padding as exactly free — each row matches the UNPADDED single-
    adapter reference for its tenant."""
    B, s, k, n, K, r_max = 6, 4, 64, 64, 3, 16
    ranks = (4, 16, 7)
    ks = jax.random.split(jax.random.key(9), 2 + K * 2)
    x = jax.random.normal(ks[0], (B, s, k))
    w = jax.random.normal(ks[1], (k, n)) * k ** -0.5
    a_list, b_list = [], []
    for i, ri in enumerate(ranks):
        ai = jax.random.normal(ks[2 + 2 * i], (ri, k)) * 0.05
        bi = jax.random.normal(ks[3 + 2 * i], (n, ri)) * 0.05
        a_list.append(jnp.pad(ai, ((0, r_max - ri), (0, 0))))
        b_list.append(jnp.pad(bi, ((0, 0), (0, r_max - ri))))
    a, b = jnp.stack(a_list), jnp.stack(b_list)
    ids = jnp.asarray([0, 1, 2, 2, 0, 1], jnp.int32)
    got = bgmv_matmul(x, w, a, b, ids, interpret=True)
    for row, tid in enumerate(ids):
        ri = ranks[int(tid)]
        want = (x[row] @ w + (x[row] @ a[tid, :ri].T) @ b[tid, :, :ri].T)
        np.testing.assert_allclose(np.asarray(got[row]), np.asarray(want),
                                   rtol=2e-5, atol=2e-5,
                                   err_msg=f"row {row} tenant {int(tid)}")


# ---------------------------------------------------------- dispatch routing

def test_dispatch_banked_node_routes_to_bgmv():
    """A lazy bank node ({"a","b","ids"}) takes the BGMV kernel on the
    interpret tier and the einsum expression on the reference tier — same
    numbers either way."""
    x, w, a, b, ids = _bank_operands(4, 3, 32, 48, 4, 8, seed=5)
    node = {"a": a, "b": b, "ids": ids}
    want = dispatch.lora_linear(x, w, node, 1.0)        # reference tier
    assert dispatch.stats["bgmv"] == 0
    with dispatch.scope(True):
        dispatch.force_mode("interpret")
        got = dispatch.lora_linear(x, w, node, 1.0)
    assert dispatch.stats["bgmv"] == 1
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


def test_dispatch_materialized_batched_routes_to_bgmv():
    """Pre-gathered (B, r, k) leaves (AdapterBank.gather) also take the
    kernel on fused tiers — ids default to the identity map."""
    x, w, a, b, ids = _bank_operands(4, 1, 32, 48, 4, 8, seed=6)
    ag, bg = jnp.take(a, ids, axis=0), jnp.take(b, ids, axis=0)
    node = {"a": ag, "b": bg}
    want = dispatch.lora_linear(x, w, node, 1.0)
    with dispatch.scope(True):
        dispatch.force_mode("interpret")
        got = dispatch.lora_linear(x, w, node, 1.0)
    assert dispatch.stats["bgmv"] == 1                  # gemv form (s == 1)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


def test_dispatch_banked_requires_matching_rows():
    x, w, a, b, ids = _bank_operands(4, 2, 32, 32, 4, 4)
    with pytest.raises(ValueError, match="batched adapters"):
        dispatch.lora_linear(x, w, {"a": a, "b": b, "ids": ids[:2]}, 1.0)


# ------------------------------------------------- K=1 vs single-adapter

@pytest.mark.parametrize("tier", ["reference", "interpret"])
def test_bank_k1_equals_single_adapter(tier):
    """A K=1 bank served to every row is the single-adapter path: same
    projection, only the adapter plumbing differs."""
    B, s, k, n, r = 4, 3, 64, 64, 8
    ks = jax.random.split(jax.random.key(11), 4)
    x = jax.random.normal(ks[0], (B, s, k))
    w = jax.random.normal(ks[1], (k, n)) * k ** -0.5
    a1 = jax.random.normal(ks[2], (r, k)) * 0.05
    b1 = jax.random.normal(ks[3], (n, r)) * 0.05
    node = {"a": a1[None], "b": b1[None],
            "ids": jnp.zeros((B,), jnp.int32)}
    with dispatch.scope(tier == "interpret"):
        if tier == "interpret":
            dispatch.force_mode("interpret")
        got = dispatch.lora_linear(x, w, node, 1.0)
        single = dispatch.lora_linear(x, w, {"a": a1, "b": b1}, 1.0)
    np.testing.assert_allclose(np.asarray(got), np.asarray(single),
                               rtol=2e-5, atol=2e-5)


def test_model_bank_gather_vs_requests_bit_identical(paged_cache):
    """Through the full model stack, the lazy requests() view decodes
    bit-identically to the materialized gather() path (the reference
    einsums see the same operands in the same contraction order)."""
    from repro.configs.base import ModelConfig
    from repro.models.api import build_model
    cfg = ModelConfig(name="t", family="dense", num_layers=2, d_model=32,
                      num_heads=2, num_kv_heads=1, head_dim=16, d_ff=64,
                      vocab_size=64)
    model = build_model(cfg)
    params = model.init(jax.random.key(0))
    def nonzero(aset, seed):
        return dataclasses.replace(aset, lora=jax.tree.map(
            lambda t: t + 0.03 * jax.random.normal(jax.random.key(seed),
                                                   t.shape), aset.lora))
    sets = [nonzero(init_adapter_set(params, jax.random.key(10 + i),
                                     LoRAConfig(rank=ri)), 20 + i)
            for i, ri in enumerate((2, 8, 4))]
    bank = AdapterBank.from_sets(sets)
    ids = jnp.asarray([2, 0], jnp.int32)
    toks = jax.random.randint(jax.random.key(3), (2, 4), 0, 64)
    cache, table = paged_cache(model, 2, 8)
    step = jax.jit(lambda p, c, t, pos, a: model.decode_step(
        p, c, t, pos, a, table=table))
    lg_g, _ = step(params, cache, toks[:, :1],
                   jnp.zeros((2,), jnp.int32), bank.gather(ids))
    lg_r, _ = step(params, cache, toks[:, :1],
                   jnp.zeros((2,), jnp.int32), bank.requests(ids))
    np.testing.assert_array_equal(np.asarray(lg_g), np.asarray(lg_r))
    pg, _ = model.prefill(params, cache, toks, bank.gather(ids), table=table)
    pr, _ = model.prefill(params, cache, toks, bank.requests(ids),
                          table=table)
    np.testing.assert_array_equal(np.asarray(pg), np.asarray(pr))
