"""First-class adapter API: AdapterSet/AdapterBank units, LoRA-aware
KV-cache decode, multi-tenant banked serving, and train-vs-serve checkpoint
parity."""
import dataclasses
import os
import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint.io import (load_adapter_state, save_federated_state)
from repro.configs.base import (FederatedConfig, LoRAConfig, ModelConfig,
                                OptimizerConfig)
from repro.core.federated import (FederatedTrainer, make_fed_round_step,
                                  make_run_chunk)
from repro.core.lora import (AdapterBank, AdapterSet, adapter_rank,
                             init_adapter_set, init_lora, pad_rank_tree)
from repro.data.synthetic import FederatedDataset
from repro.kernels import dispatch
from repro.models.api import build_model
from repro.optim.optimizers import make_optimizer


def _cfg(use_pallas=False, num_layers=2):
    return ModelConfig(name="aset", family="dense", num_layers=num_layers,
                       d_model=32, num_heads=2, num_kv_heads=1, head_dim=16,
                       d_ff=64, vocab_size=64, use_pallas=use_pallas)


def _nonzero(aset, seed=9, scale=0.03):
    """Give B (zero-init) real values so adapter effects are visible."""
    return dataclasses.replace(aset, lora=jax.tree.map(
        lambda x: x + scale * jax.random.normal(jax.random.key(seed), x.shape),
        aset.lora))


@pytest.fixture(scope="module")
def tiny():
    cfg = _cfg()
    model = build_model(cfg)
    return cfg, model, model.init(jax.random.key(0))


# ------------------------------------------------------------- AdapterSet

def test_adapter_set_pytree_roundtrip(tiny):
    _, model, params = tiny
    aset = init_adapter_set(params, jax.random.key(1), LoRAConfig(rank=4),
                            n_clients=3)
    leaves, td = jax.tree.flatten(aset)
    back = jax.tree.unflatten(td, leaves)
    assert back.gamma == aset.gamma and back.rank == 4
    assert back.alpha == aset.alpha
    # static gamma lives in the treedef: different gammas, different treedefs
    other = dataclasses.replace(aset, gamma=1.0)
    assert jax.tree.structure(other) != td


def test_adapter_set_uniform_collapse(tiny):
    _, model, params = tiny
    lora = init_lora(params, jax.random.key(1), LoRAConfig(rank=4))
    uniform = AdapterSet(lora=lora, gamma=(2.0, 2.0, 2.0))
    assert isinstance(uniform.gamma, float) and uniform.gamma == 2.0
    mixed = AdapterSet(lora=lora, gamma=(1.0, 2.0))
    assert not isinstance(mixed.gamma, float)
    # an all-ones rank mask masks nothing -> canonicalized away entirely
    assert AdapterSet(lora=lora, rank_mask=jnp.ones((3, 4))).rank_mask is None
    assert AdapterSet(lora=lora,
                      rank_mask=jnp.asarray([[1., 1., 0., 0.]])
                      ).rank_mask is not None


def test_fold_gamma_static_and_traced(tiny):
    _, model, params = tiny
    aset = _nonzero(init_adapter_set(params, jax.random.key(1),
                                     LoRAConfig(rank=4)))
    folded = dataclasses.replace(aset, gamma=2.5).fold_gamma()
    assert folded.gamma == 1.0
    for (pa, a), (pb, b) in zip(
            jax.tree_util.tree_flatten_with_path(aset.lora)[0],
            jax.tree_util.tree_flatten_with_path(folded.lora)[0]):
        name = pa[-1].key
        ref = np.asarray(a) * (2.5 if name == "b" else 1.0)
        np.testing.assert_array_equal(np.asarray(b), ref)
    # traced gamma folds under jit and the model still sees a static scale
    out = jax.jit(lambda s, g: dataclasses.replace(
        s, gamma=g).fold_gamma().gamma)(aset, jnp.float32(3.0))
    assert float(out) == 1.0


def test_stack_unstack_roundtrip(tiny):
    _, model, params = tiny
    s1 = _nonzero(init_adapter_set(params, jax.random.key(1),
                                   LoRAConfig(rank=4)), seed=1)
    s2 = _nonzero(init_adapter_set(params, jax.random.key(2),
                                   LoRAConfig(rank=4, alpha=4.0)), seed=2)
    stacked = AdapterSet.stack([s1, s2])
    assert jax.tree.leaves(stacked.lora)[0].shape[0] == 2
    u1, u2 = stacked.unstack()
    for a, b in zip(jax.tree.leaves(u1.lora), jax.tree.leaves(s1.lora)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    # mixed ranks refuse to stack raw — the bank handles padding
    s8 = init_adapter_set(params, jax.random.key(3), LoRAConfig(rank=8))
    with pytest.raises(ValueError, match="uniform ranks"):
        AdapterSet.stack([s1, s8])
    bank = AdapterBank.from_sets([s1, s8])
    assert bank.ranks == (4, 8) and adapter_rank(bank.lora) == 8


def test_pad_rank_tree_exact(tiny):
    """Zero rank padding is exact: padded forward == unpadded forward."""
    _, model, params = tiny
    aset = _nonzero(init_adapter_set(params, jax.random.key(1),
                                     LoRAConfig(rank=4), n_clients=2))
    toks = jax.random.randint(jax.random.key(5), (2, 8), 0, 64)
    ref, _ = model.forward(params, {"tokens": toks}, adapters=aset)
    padded = dataclasses.replace(aset, lora=pad_rank_tree(aset.lora, 16),
                                 rank=16)
    out, _ = model.forward(params, {"tokens": toks}, adapters=padded)
    np.testing.assert_array_equal(np.asarray(ref), np.asarray(out))


def test_merge_equals_runtime(tiny):
    _, model, params = tiny
    aset = _nonzero(init_adapter_set(params, jax.random.key(1),
                                     LoRAConfig(rank=4), n_clients=3))
    toks = jax.random.randint(jax.random.key(6), (2, 8), 0, 64)
    runtime, _ = model.forward(params, {"tokens": toks}, adapters=aset)
    merged, _ = model.forward(aset.merge(params), {"tokens": toks})
    np.testing.assert_allclose(np.asarray(runtime), np.asarray(merged),
                               rtol=1e-4, atol=1e-4)


# ------------------------------------------------- LoRA-aware decode parity

def _greedy_positions(model, params, adapters, toks, paged_cache):
    """Per-position logits from the KV-cache decode loop over given tokens."""
    b, s = toks.shape
    cache, table = paged_cache(model, b, s)
    step = jax.jit(lambda p, c, t, pos, a: model.decode_step(
        p, c, t, pos, a, table=table))
    outs = []
    for t in range(s):
        logits, cache = step(params, cache, toks[:, t:t + 1],
                             jnp.full((b,), t), adapters)
        outs.append(logits)
    return jnp.concatenate(outs, axis=1)


@pytest.mark.parametrize("tier", ["reference", "interpret"])
def test_decode_step_matches_forward_with_adapters(tier, paged_cache):
    """KV-cache decode with an AdapterSet == full forward, position by
    position, on the reference AND interpret kernel tiers."""
    num_layers = 2 if tier == "reference" else 1
    cfg = _cfg(use_pallas=(tier == "interpret"), num_layers=num_layers)
    model = build_model(cfg)
    params = model.init(jax.random.key(0))
    aset = _nonzero(init_adapter_set(params, jax.random.key(1),
                                     LoRAConfig(rank=4), n_clients=2))
    toks = jax.random.randint(jax.random.key(3), (2, 6), 0, 64)
    dispatch.force_mode(tier if tier == "interpret" else None)
    try:
        full, _ = model.forward(params, {"tokens": toks}, adapters=aset)
        stepped = _greedy_positions(model, params, aset, toks,
                                    paged_cache)
    finally:
        dispatch.force_mode(None)
    np.testing.assert_allclose(np.asarray(full), np.asarray(stepped),
                               rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("tier", ["reference", "interpret"])
def test_banked_decode_matches_per_adapter_loop(tier, paged_cache):
    """A mixed-rank AdapterBank batch decodes like a python loop over the
    same requests served one adapter at a time."""
    num_layers = 2 if tier == "reference" else 1
    cfg = _cfg(use_pallas=(tier == "interpret"), num_layers=num_layers)
    model = build_model(cfg)
    params = model.init(jax.random.key(0))
    sets = [_nonzero(init_adapter_set(params, jax.random.key(10 + i),
                                      LoRAConfig(rank=r)), seed=20 + i)
            for i, r in enumerate((2, 8, 4))]
    bank = AdapterBank.from_sets(sets)
    toks = jax.random.randint(jax.random.key(4), (3, 5), 0, 64)
    ids = jnp.asarray([2, 0, 1])
    dispatch.force_mode(tier if tier == "interpret" else None)
    try:
        batched = _greedy_positions(model, params, bank.gather(ids), toks,
                                    paged_cache)
        rows = [
            _greedy_positions(model, params, bank.adapter(int(k)),
                              toks[i:i + 1], paged_cache)
            for i, k in enumerate(ids)]
    finally:
        dispatch.force_mode(None)
    loop = jnp.concatenate(rows, axis=0)
    np.testing.assert_allclose(np.asarray(batched), np.asarray(loop),
                               rtol=2e-4, atol=2e-4)


def test_bank_k8_mixed_rank_bit_identical_conformance(paged_cache):
    """Acceptance: a K=8 mixed-rank AdapterBank batched decode is
    bit-identical to K single-adapter decodes.

    The K reference decodes run at the SAME batch shape (every row served by
    adapter k) because XLA GEMM tiling is shape-dependent: equal shapes make
    the comparison exact and prove request isolation — row i's tokens depend
    only on its own adapter, never on what the other rows were served."""
    cfg = _cfg()
    model = build_model(cfg)
    params = model.init(jax.random.key(0))
    ranks = (2, 4, 8, 8, 16, 2, 4, 16)
    sets = [_nonzero(init_adapter_set(params, jax.random.key(30 + i),
                                      LoRAConfig(rank=r, alpha=float(2 + i))),
                     seed=40 + i)
            for i, r in enumerate(ranks)]
    bank = AdapterBank.from_sets(sets)
    assert bank.size == 8 and bank.ranks == ranks
    K = bank.size
    prompt = jax.random.randint(jax.random.key(5), (K, 2), 0, 64)

    cache0, table = paged_cache(model, K, 8)
    step = jax.jit(lambda cache, tok, pos, ids: model.decode_step(
        params, cache, tok, pos, adapters=bank.gather(ids), table=table))

    def decode(ids):
        cache = cache0
        tok = prompt[:, :1]
        seq = [tok]
        for t in range(6):
            logits, cache = step(cache, tok, jnp.full((K,), t), ids)
            tok = (prompt[:, t + 1:t + 2] if t + 1 < prompt.shape[1]
                   else jnp.argmax(logits[:, -1:], -1).astype(jnp.int32))
            seq.append(tok)
        return jnp.concatenate(seq, axis=1)

    mixed = decode(jnp.arange(K))
    for k in range(K):
        single = decode(jnp.full((K,), k))
        np.testing.assert_array_equal(np.asarray(mixed[k]),
                                      np.asarray(single[k]))


# --------------------------------------------- train-vs-serve checkpointing

def _tiny_trainer(model, ranks=None, n=2):
    ds = FederatedDataset(64, n, seq_len=16, batch_per_client=2, seed=3)
    return FederatedTrainer(
        model, ds,
        lora_cfg=LoRAConfig(rank=4, ranks=ranks),
        fed_cfg=FederatedConfig(num_clients=n, local_steps=1,
                                aggregation="fedsa"),
        opt_cfg=OptimizerConfig(name="sgd", lr=0.05), seed=3)


def test_train_vs_serve_logit_parity(tmp_path):
    """Satellite regression: --resume restores the TRAINED AdapterSet (gamma
    + rank mask included) and serves logits bit-identical to the trainer's
    own client adapters — serve.py can no longer decode random weights."""
    model = build_model(_cfg())
    tr = _tiny_trainer(model, ranks=(2, 4))
    tr.run(2)
    path = str(tmp_path / "ck.npz")
    tr.save(path)
    base, aset = load_adapter_state(path)
    assert aset.rank_mask is not None and aset.alpha == tr.lora_cfg.alpha
    toks = jnp.asarray(tr.dataset.eval_batch(4))
    for c in range(2):
        train_side, _ = model.forward(tr.base, {"tokens": toks},
                                      adapters=tr.client_adapters(c))
        serve_side, _ = model.forward(base, {"tokens": toks},
                                      adapters=aset.client(c))
        np.testing.assert_array_equal(np.asarray(train_side),
                                      np.asarray(serve_side))
    # and through the bank (gamma folded at registration)
    bank = AdapterBank.from_adapter_set(aset)
    assert bank.ranks == (2, 4)
    banked, _ = model.forward(
        base, {"tokens": jnp.broadcast_to(toks[:1], (2,) + toks.shape[1:])},
        adapters=bank.gather(jnp.asarray([0, 1])))
    per0, _ = model.forward(base, {"tokens": toks[:1]},
                            adapters=tr.client_adapters(0))
    np.testing.assert_allclose(np.asarray(banked[0]), np.asarray(per0[0]),
                               rtol=2e-5, atol=2e-6)


def test_legacy_checkpoint_upgrade(tmp_path):
    """Checkpoints written before adapter_meta upgrade via lora_cfg; without
    it they raise a clear error."""
    model = build_model(_cfg())
    tr = _tiny_trainer(model)
    tr.run(1)
    path = str(tmp_path / "legacy.npz")
    # simulate a pre-adapter-API checkpoint: no adapter_meta
    save_federated_state(path, tr.base, tr.lora, tr.opt_state, tr.round_idx)
    with pytest.raises(ValueError, match="adapter_meta"):
        load_adapter_state(path)
    lcfg = tr.lora_cfg
    with pytest.warns(UserWarning, match="legacy checkpoint"):
        base, aset = load_adapter_state(path, lora_cfg=lcfg)
    # the recomputed gamma matches what the trainer derived
    assert aset.gamma == pytest.approx(tr.gamma)
    toks = jnp.asarray(tr.dataset.eval_batch(2))
    a, _ = model.forward(base, {"tokens": toks},
                         adapters=aset.client(0))
    b, _ = model.forward(tr.base, {"tokens": toks},
                         adapters=tr.client_adapters(0))
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_trainer_adapters_surface(tiny):
    """FederatedTrainer exposes the state as AdapterSets."""
    _, model, params = tiny
    tr = _tiny_trainer(model, ranks=(2, 4))
    aset = tr.adapters
    assert aset.rank == 4 and aset.rank_mask is not None
    c0 = tr.client_adapters(0)
    assert c0.rank == 2 and float(np.asarray(c0.rank_mask).sum()) == 2.0
    assert c0.gamma == tr.client_gamma(0)
    tr.run_round()
    assert np.isfinite(tr.eval_perplexity(batch=2))
