"""Adapter lifecycle: versioned bank hot-swap + host-overflow LRU.

Four layers of guarantees, strongest first:

  * ``AdapterBank.publish`` swaps exactly one padded slot, bumps the
    version, leaves every other tenant bit-untouched, and rejects
    rank-ceiling / structure violations instead of silently reshaping.
  * ZERO RECOMPILES: what the serving engines trace (``bank.requests``)
    keeps its treedef and leaf shapes across publishes, and the jitted
    engine caches (paged admit/chunk, the slot-swap executable itself) do
    not grow when publishes land between or during serves.
  * ``LiveAdapterBank`` residency: LRU promotion into free-then-oldest
    slots, pinned slots never evicted (impossible acquires defer, not
    corrupt), demotion is free because the host store is authoritative,
    and an overflowing live bank serves token-identically to a static
    bank holding every tenant.
  * Train->serve: ``FederatedTrainer.publish_adapters`` /
    ``publish_adapter_state`` stream round results into a live bank with
    logit parity bit-identical to the trainer's own stacked adapters —
    across hot swaps, at fixed shapes.

Plus the tenant-identity regressions the lifecycle depends on: evicted
engine slots reset their ids_arr entry (stale ids would corrupt LRU
accounting), and out-of-range adapter ids raise at the host boundary
instead of being clamp-gathered to the last tenant.
"""
import dataclasses
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint.io import publish_adapter_state
from repro.configs.base import (FederatedConfig, LoRAConfig, ModelConfig,
                                OptimizerConfig)
from repro.core.federated import FederatedTrainer
from repro.core.lora import (AdapterBank, AdapterSet, LiveAdapterBank,
                             _bank_slot_swap, init_adapter_set)
from repro.data.synthetic import FederatedDataset
from repro.kernels import dispatch
from repro.launch import serve
from repro.models.api import build_model


def _cfg(use_pallas=False, num_layers=2):
    return ModelConfig(name="lifec", family="dense", num_layers=num_layers,
                       d_model=32, num_heads=2, num_kv_heads=1, head_dim=16,
                       d_ff=64, vocab_size=64, use_pallas=use_pallas)


@pytest.fixture(autouse=True)
def _clean_dispatch():
    dispatch.force_mode(None)
    yield
    dispatch.force_mode(None)


@pytest.fixture(scope="module")
def tiny():
    cfg = _cfg()
    model = build_model(cfg)
    return cfg, model, model.init(jax.random.key(0))


def _mk_set(params, cfg, rank, seed, n_clients=1):
    return init_adapter_set(params, jax.random.key(seed),
                            LoRAConfig(rank=rank, alpha=8.0,
                                       targets=cfg.lora_targets),
                            n_clients=n_clients)


def _mk_bank(params, cfg, ranks=(4, 8, 4)):
    return AdapterBank.from_sets(
        [_mk_set(params, cfg, r, 10 + i) for i, r in enumerate(ranks)])


def _leaves(tree):
    return [np.asarray(x) for x in jax.tree.leaves(tree)]


# --------------------------------------------------- versioned bank publish

def test_bank_publish_swaps_one_slot(tiny):
    cfg, model, params = tiny
    bank = _mk_bank(params, cfg)
    new = _mk_set(params, cfg, 4, seed=99)
    b2 = bank.publish(1, new, donate=False)
    assert (bank.version, b2.version) == (0, 1)
    assert b2.ranks == (4, 4, 4) and b2.size == bank.size
    # slot 1 now holds the prepared+padded new set; slots 0/2 bit-untouched
    from repro.core.lora import adapter_rank, pad_rank_tree
    want = pad_rank_tree(new.prepared().lora, bank.r_max)
    for got, exp in zip(_leaves(b2.adapter(1).lora), _leaves(want)):
        np.testing.assert_array_equal(got, exp)
    for k in (0, 2):
        for got, exp in zip(_leaves(b2.adapter(k).lora),
                            _leaves(bank.adapter(k).lora)):
            np.testing.assert_array_equal(got, exp)


def test_bank_publish_rejects_bad_inputs(tiny):
    cfg, model, params = tiny
    bank = _mk_bank(params, cfg)
    new = _mk_set(params, cfg, 4, seed=5)
    with pytest.raises(ValueError, match="out of range"):
        bank.publish(bank.size, new)
    with pytest.raises(ValueError, match="exceeds the bank's r_max"):
        bank.publish(0, _mk_set(params, cfg, 16, seed=6))
    broken = dataclasses.replace(
        new, lora={"oops": jax.tree.leaves(new.lora)[0]})
    with pytest.raises(ValueError, match="structure"):
        bank.publish(0, broken)


def test_bank_version_is_not_a_cache_key(tiny):
    """The invariant behind zero-recompile swaps: what jit traces — the
    bank's requests() view — has an identical treedef and identical leaf
    shapes before and after a publish (even one changing the slot's active
    rank), and the version counter never enters the pytree."""
    cfg, model, params = tiny
    bank = _mk_bank(params, cfg)
    b2 = bank.publish(2, _mk_set(params, cfg, 8, seed=7), donate=False)
    ids = jnp.asarray([0, 1, 2])
    assert (jax.tree.structure(bank.requests(ids))
            == jax.tree.structure(b2.requests(ids)))
    assert ([x.shape for x in jax.tree.leaves(bank.requests(ids))]
            == [x.shape for x in jax.tree.leaves(b2.requests(ids))])
    # version is host-only bookkeeping: flatten/unflatten drops it
    leaves, td = jax.tree.flatten(b2)
    assert jax.tree.unflatten(td, leaves).version == 0


def test_publish_zero_recompile_between_serves(tiny):
    """Publishing between serve_scheduled runs reuses every executable:
    neither engine program (paged admit/chunk) nor the slot-swap jit gains
    an entry."""
    cfg, model, params = tiny
    bank = _mk_bank(params, cfg)
    prompt = np.full(4, 7, np.int32)

    def run(bank):
        reqs = [serve.Request(rid=i, prompt=prompt, steps=4, adapter_id=i)
                for i in range(3)]
        done = serve.serve_scheduled(model, params, reqs, bank=bank,
                                     max_batch=3, chunk=3, wait=False)
        return [r.tokens for r in done]

    out0 = run(bank)
    bank = bank.publish(0, _mk_set(params, cfg, 4, seed=19))  # warm the swap
    engines = [model._serve_jit_cache[k] for k in ("paged_admit",
                                                   "paged_chunk")]
    sizes = [f._cache_size() for f in engines]
    swap_c = _bank_slot_swap._cache_size()
    for slot in (0, 1, 2):
        bank = bank.publish(slot, _mk_set(params, cfg, 4, seed=20 + slot))
        run(bank)
    assert [f._cache_size() for f in engines] == sizes
    assert _bank_slot_swap._cache_size() == swap_c
    assert bank.version == 4
    # and the published adapters actually serve: same shapes out
    out3 = run(bank)
    assert [len(t) for t in out0] == [len(t) for t in out3]


# ------------------------------------------------------- live bank residency

def test_live_bank_lru_promotion_and_pinning(tiny):
    cfg, model, params = tiny
    sets = [_mk_set(params, cfg, 4, seed=30 + t) for t in range(4)]
    live = LiveAdapterBank.from_sets(sets, hot_slots=2)
    assert live.tenants == [0, 1, 2, 3]
    assert live.resident(0) and live.resident(1) and not live.resident(2)

    # promote 2: tenant 0 is older (never touched) -> slot 0 is the victim
    live.touch([1])
    sm = live.acquire([2], ())
    assert sm == {2: live.tenant_slot[2]}
    assert not live.resident(0) and live.resident(1) and live.resident(2)
    assert (live.promotions, live.demotions) == (1, 1)

    # pinned slots never evicted: with both slots pinned, acquire defers
    pinned = set(live.tenant_slot.values())
    assert live.acquire([0], pinned) is None
    assert not live.resident(0)          # nothing changed on the failed path

    # unknown tenants are an error, not a clamp
    with pytest.raises(KeyError, match="unknown tenant 9"):
        live.acquire([9], ())


def test_live_bank_publish_resident_vs_overflow(tiny):
    cfg, model, params = tiny
    sets = [_mk_set(params, cfg, 4, seed=40 + t) for t in range(3)]
    live = LiveAdapterBank.from_sets(sets, hot_slots=2)
    new = _mk_set(params, cfg, 4, seed=77)
    # resident tenant: host store AND device slot update (one hot swap)
    v = live.publish(0, new)
    assert v == 1 and live.swaps == 1 and live.bank.version == 1
    # overflow tenant: host store only — no device traffic
    v = live.publish(2, new)
    assert v == 1 and live.swaps == 1
    # a brand-new tenant registers at version 0
    assert live.publish(7, new) == 0
    assert 7 in live.store and not live.resident(7)
    # when tenant 2 is later promoted, it must carry the PUBLISHED weights
    sm = live.acquire([2], pinned={live.tenant_slot[0]})
    from repro.core.lora import pad_rank_tree
    want = pad_rank_tree(new.prepared().lora, live.r_max)
    for got, exp in zip(_leaves(live.bank.adapter(sm[2]).lora),
                        _leaves(want)):
        np.testing.assert_array_equal(got, exp)


def test_scheduled_live_overflow_token_identity(tiny):
    """An overflowing live bank (2 hot slots, 4 tenants, promotion/demotion
    churn through the stream) serves the exact tokens of a static bank
    holding all 4 tenants on device."""
    cfg, model, params = tiny
    sets = [_mk_set(params, cfg, r, seed=50 + i, n_clients=4)
            for i, r in enumerate((4, 8, 4, 8))]
    static = AdapterBank.from_sets(sets)
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, cfg.vocab_size, 4).astype(np.int32)
               for _ in range(8)]

    def mk():
        return [serve.Request(rid=i, prompt=prompts[i], steps=6,
                              adapter_id=i % 4) for i in range(8)]

    done_s = serve.serve_scheduled(model, params, mk(), bank=static,
                                   max_batch=2, chunk=3, wait=False)
    live = LiveAdapterBank.from_sets(sets, hot_slots=2)
    done_l = serve.serve_scheduled(model, params, mk(), bank=live,
                                   max_batch=2, chunk=3, wait=False)
    assert live.promotions > 0 and live.demotions > 0
    for a, b in zip(done_s, done_l):
        assert a.tokens == b.tokens


def test_scheduled_swap_window_zero_recompile_and_deterministic(tiny):
    """Publishes landing mid-serve through on_boundary: the paged engine's
    executables do not grow, and the run is deterministic (same stream +
    same publish schedule twice -> identical tokens)."""
    cfg, model, params = tiny
    sets = [_mk_set(params, cfg, 4, seed=60 + t) for t in range(3)]
    pub = _mk_set(params, cfg, 4, seed=88)
    rng = np.random.default_rng(1)
    prompts = [rng.integers(0, cfg.vocab_size, 4).astype(np.int32)
               for _ in range(6)]

    def run():
        live = LiveAdapterBank.from_sets(sets, hot_slots=2)

        def on_boundary(i):
            if i == 2:
                live.publish(0, pub)        # resident: device hot swap
                live.publish(2, pub)        # overflow: host store only

        reqs = [serve.Request(rid=i, prompt=prompts[i], steps=6,
                              adapter_id=i % 3) for i in range(6)]
        done = serve.serve_scheduled(model, params, reqs, bank=live,
                                     max_batch=2, chunk=3, wait=False,
                                     on_boundary=on_boundary)
        assert live.swaps >= 1
        return [r.tokens for r in done]

    first = run()
    admit_c = model._serve_jit_cache["paged_admit"]._cache_size()
    chunk_c = model._serve_jit_cache["paged_chunk"]._cache_size()
    assert first == run()
    assert model._serve_jit_cache["paged_admit"]._cache_size() == admit_c
    assert model._serve_jit_cache["paged_chunk"]._cache_size() == chunk_c


# ----------------------------------------------------------- train -> serve

def _tiny_trainer(model, n=3):
    ds = FederatedDataset(64, n, seq_len=16, batch_per_client=2, seed=3)
    return FederatedTrainer(
        model, ds, lora_cfg=LoRAConfig(rank=4, alpha=8.0),
        fed_cfg=FederatedConfig(num_clients=n, local_steps=1,
                                aggregation="fedsa"),
        opt_cfg=OptimizerConfig(name="sgd", lr=0.05), seed=3)


def test_trainer_publish_logit_parity_across_swap(tiny):
    """The acceptance bar: after a round publishes into a live bank —
    including hot swaps of resident tenants — serve-side logits through the
    live bank are BIT-IDENTICAL to the trainer's own stacked adapters at
    fixed shapes, for every tenant (resident and promoted-from-host)."""
    cfg, model, params = tiny
    tr = _tiny_trainer(model, n=3)
    live = LiveAdapterBank.from_sets(
        [tr.client_adapters(c) for c in range(3)], hot_slots=2)
    tr.run(2)
    assert tr.publish_adapters(live) == 3        # 2 hot swaps + 1 host write
    assert live.swaps == 2
    toks = jnp.asarray(tr.dataset.eval_batch(2))
    static = AdapterBank.from_adapter_set(tr.adapters)   # train-side stack
    for c in range(3):
        sm = live.acquire([c], ())
        serve_side, _ = model.forward(
            tr.base, {"tokens": toks},
            adapters=live.bank.gather(jnp.asarray([sm[c]] * toks.shape[0])))
        train_side, _ = model.forward(
            tr.base, {"tokens": toks},
            adapters=static.gather(jnp.asarray([c] * toks.shape[0])))
        np.testing.assert_array_equal(np.asarray(serve_side),
                                      np.asarray(train_side))


def test_publish_adapter_state_roundtrip(tiny, tmp_path):
    """Checkpoint handoff: trainer saves, the server publishes every client
    from the file into a live bank; the served rows equal the restored
    stacked set exactly."""
    cfg, model, params = tiny
    tr = _tiny_trainer(model, n=2)
    tr.run(1)
    path = str(tmp_path / "round.npz")
    tr.save(path)
    live = LiveAdapterBank.from_sets(
        [tr.client_adapters(c) for c in range(2)], hot_slots=2)
    tr.run(1)                                    # trainer moves on...
    tr.save(path)                                # ...and re-publishes
    base, n = publish_adapter_state(path, live)
    assert n == 2 and live.version == 2
    static = AdapterBank.from_adapter_set(tr.adapters)
    for c in range(2):
        for got, exp in zip(_leaves(live.bank.adapter(live.tenant_slot[c]).lora),
                            _leaves(static.adapter(c).lora)):
            np.testing.assert_array_equal(got, exp)


# ------------------------------------------------ tenant-identity regressions

class _RecordingBank:
    """Duck-typed AdapterBank wrapper recording every ids array the
    scheduler gathers — the satellite-1 pin needs to SEE what idle slots
    request."""

    def __init__(self, bank):
        self._bank = bank
        self.seen = []

    @property
    def size(self):
        return self._bank.size

    def requests(self, ids):
        self.seen.append(np.asarray(ids).copy())
        return self._bank.requests(ids)


def test_evicted_slot_resets_tenant_id(tiny):
    """satellite 1: finish() clears ids_arr[slot].  Admit tenants (0, 2) on
    two slots with different step counts; after the short request finishes,
    every later full-width gather must read 0 for its slot — a stale 2
    would keep driving LRU/residency accounting for an idle slot."""
    cfg, model, params = tiny
    rec = _RecordingBank(_mk_bank(params, cfg))
    rng = np.random.default_rng(2)
    reqs = [serve.Request(rid=0, prompt=rng.integers(0, 64, 4).astype(np.int32),
                          steps=9, adapter_id=0),
            serve.Request(rid=1, prompt=rng.integers(0, 64, 4).astype(np.int32),
                          steps=2, adapter_id=2)]
    serve.serve_scheduled(model, params, reqs, bank=rec, max_batch=2,
                          chunk=3, wait=False)
    full = [ids for ids in rec.seen if ids.shape == (2,)]
    slot1 = [int(ids[1]) for ids in full]
    assert 2 in slot1, "tenant 2 never gathered while running"
    tail = slot1[slot1.index(2) + 1:]
    assert tail and all(s == 0 for s in tail[1:]), \
        f"stale tenant id after eviction: {slot1}"


def test_out_of_range_adapter_id_raises(tiny):
    """satellite 2: ids past the bank raise at the host boundary (gather
    would silently clamp to the last tenant) — naming the offending rid."""
    cfg, model, params = tiny
    bank = _mk_bank(params, cfg)
    reqs = [serve.Request(rid=i, prompt=np.zeros(4, np.int32), steps=2,
                          adapter_id=k) for i, k in enumerate((0, 3))]
    with pytest.raises(ValueError, match="clamp"):
        serve.serve_scheduled(model, params, reqs, bank=bank, max_batch=2,
                              wait=False)
    reqs = [serve.Request(rid=5, prompt=np.zeros(4, np.int32), steps=2,
                          adapter_id=-1)]
    with pytest.raises(ValueError, match="rid=5"):
        serve.serve_scheduled(model, params, reqs, bank=bank, max_batch=2,
                              wait=False)
    live = LiveAdapterBank.from_sets(
        [_mk_set(params, cfg, 4, seed=1)], hot_slots=1)
    reqs = [serve.Request(rid=3, prompt=np.zeros(4, np.int32), steps=2,
                          adapter_id=4)]
    with pytest.raises(ValueError, match="rid=3"):
        serve.serve_scheduled(model, params, reqs, bank=live, max_batch=2,
                              wait=False)


def test_make_requests_validates_trace_ids(tmp_path):
    p = tmp_path / "trace.json"
    p.write_text(json.dumps([{"arrival": 0.0, "adapter": 1},
                             {"arrival": 0.1, "adapter": 5}]))
    with pytest.raises(ValueError, match="rid=1"):
        serve.make_requests(str(p), prompt_len=4, steps=4, tenants=2,
                            vocab=64)


# ---------------------------------------------------------- interpret tier

def test_lifecycle_interpret_tier(tiny):
    """CI serve-perf proof: swap parity + zero recompiles survive the fused
    BGMV interpret tier (kernel bodies engaged, ids-indexed BlockSpecs)."""
    dispatch.force_mode("interpret")
    dispatch.reset_stats()
    cfg = _cfg(use_pallas=True)
    model = build_model(cfg)
    params = model.init(jax.random.key(0))
    sets = [_mk_set(params, cfg, 4, seed=70 + t, n_clients=2)
            for t in range(3)]
    static = AdapterBank.from_sets(sets)
    pub = _mk_set(params, cfg, 4, seed=91, n_clients=2)
    rng = np.random.default_rng(4)
    prompts = [rng.integers(0, cfg.vocab_size, 4).astype(np.int32)
               for _ in range(4)]

    def mk():
        return [serve.Request(rid=i, prompt=prompts[i], steps=5,
                              adapter_id=i % 3) for i in range(4)]

    done_s = serve.serve_scheduled(model, params, mk(), bank=static,
                                   max_batch=2, chunk=3, wait=False)
    live = LiveAdapterBank.from_sets(sets, hot_slots=2)
    done_l = serve.serve_scheduled(model, params, mk(), bank=live,
                                   max_batch=2, chunk=3, wait=False)
    for a, b in zip(done_s, done_l):
        assert a.tokens == b.tokens
    assert dispatch.stats["bgmv"] > 0, "BGMV kernel tier never engaged"
    admit_c = model._serve_jit_cache["paged_admit"]._cache_size()
    chunk_c = model._serve_jit_cache["paged_chunk"]._cache_size()
    serve.serve_scheduled(
        model, params, mk(), bank=live, max_batch=2, chunk=3, wait=False,
        on_boundary=lambda i: live.publish(0, pub) if i == 1 else None)
    assert live.swaps >= 1
    assert model._serve_jit_cache["paged_admit"]._cache_size() == admit_c
    assert model._serve_jit_cache["paged_chunk"]._cache_size() == chunk_c
