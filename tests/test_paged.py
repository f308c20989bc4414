"""Paged KV cache + continuous-batching scheduler.

Four layers of guarantees, strongest first:

  * BlockPool allocator invariants, property-based (hypothesis when
    installed, a seeded op-sequence sweep otherwise): no block aliasing
    across outstanding allocations, the null block 0 is never handed out,
    frees return capacity exactly, double frees raise without corrupting.
  * Paged fill puts every token's K/V and position at its virtual-ring
    slot ``pool[table[i, (p % vlen) // bs], (p % vlen) % bs]`` —
    including sliding-window ring overflow (prompt longer than the ring,
    only the last vlen positions survive) — and writes nothing else.
  * The Pallas paged-attention kernel matches the exact-softmax oracle
    (kernels/ref.py) to fp32 tolerance across window/softcap variants.
  * The scheduler serves greedy tokens of ``Model.forward`` (the plain
    reference) at a static schedule, on the reference tier and under the
    Pallas interpreter (BGMV adapter kernels engaged, and the paged
    attention kernel on the stacked pools), through slot/block churn
    (waves recycling freed slots and blocks into a donated pool), and for
    per-slot recurrent state (rglru blocks reset at admission).
  * The compiled decode chunk writes the pool in place: no pool-shaped
    copy, fill or dynamic-update-slice, and the pool aliased from the
    call's input to its output.
"""
import random
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.analysis import trace
from repro.configs.base import LoRAConfig, ModelConfig
from repro.core.lora import AdapterBank, init_adapter_set
from repro.kernels import dispatch
from repro.kernels.paged_attention import paged_attention
from repro.kernels.ref import paged_attention_ref
from repro.launch import serve
from repro.models import attention
from repro.models.api import build_model

try:
    from hypothesis import given, settings, strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:
    HAVE_HYPOTHESIS = False


def _cfg(use_pallas=False, num_layers=3, **kw):
    base = dict(name="paged", family="dense", num_layers=num_layers,
                d_model=32, num_heads=2, num_kv_heads=1, head_dim=16,
                d_ff=64, vocab_size=64, use_pallas=use_pallas)
    base.update(kw)
    return ModelConfig(**base)


@pytest.fixture(autouse=True)
def _clean_dispatch():
    dispatch.force_mode(None)
    yield
    dispatch.force_mode(None)


# ------------------------------------------------- BlockPool allocator invariants

def _check_pool_ops(num_blocks, ops):
    """Replay an (alloc n | free i)* op sequence against a fresh pool,
    asserting the allocator invariants after every op."""
    pool = serve.BlockPool(num_blocks)
    held = []                     # outstanding allocations, each a list
    capacity = num_blocks - 1     # block 0 reserved
    for kind, arg in ops:
        outstanding = sum(len(h) for h in held)
        if kind == "alloc":
            got = pool.alloc(arg)
            if arg > capacity - outstanding:
                assert got is None, "over-allocation must refuse, not split"
            else:
                assert got is not None and len(got) == arg
                assert len(set(got)) == arg
                assert all(0 < b < num_blocks for b in got), \
                    "null block 0 handed out"
                taken = {b for h in held for b in h}
                assert not (set(got) & taken), "block aliased across requests"
                held.append(got)
        elif held:
            blocks = held.pop(arg % len(held))
            before = pool.available
            pool.free(blocks)
            assert pool.available == before + len(blocks)
            if blocks:
                with pytest.raises(ValueError):
                    pool.free(blocks)                 # double free raises...
                assert pool.available == before + len(blocks)  # ...harmlessly
    assert pool.available == capacity - sum(len(h) for h in held)


if HAVE_HYPOTHESIS:
    @settings(max_examples=200, deadline=None)
    @given(num_blocks=st.integers(2, 40),
           ops=st.lists(st.tuples(st.sampled_from(["alloc", "free"]),
                                  st.integers(0, 8)), max_size=60))
    def test_block_pool_invariants(num_blocks, ops):
        _check_pool_ops(num_blocks, ops)
else:
    def test_block_pool_invariants():
        rng = random.Random(0)
        for _ in range(300):
            num_blocks = rng.randint(2, 40)
            ops = [(rng.choice(["alloc", "free"]), rng.randint(0, 8))
                   for _ in range(rng.randint(0, 60))]
            _check_pool_ops(num_blocks, ops)


def test_block_pool_rejects_degenerate_size():
    with pytest.raises(ValueError):
        serve.BlockPool(1)        # no room for the null block + any request


def test_block_pool_double_free_names_blocks():
    """The double-free error must NAME the offending blocks — the message
    is what a scheduler bug report hangs on."""
    pool = serve.BlockPool(8)
    got = pool.alloc(3)
    pool.free(got)
    with pytest.raises(ValueError) as ei:
        pool.free(got)
    msg = str(ei.value)
    assert "double free" in msg
    for b in got:
        assert str(b) in msg
    # a mixed batch reports exactly the not-held blocks
    held = pool.alloc(2)
    with pytest.raises(ValueError) as ei:
        pool.free(held + [got[0]])
    assert str(got[0]) in str(ei.value)
    assert pool.available == 5          # failed free released nothing


def test_block_pool_duplicate_in_one_call_raises():
    pool = serve.BlockPool(8)
    b = pool.alloc(1)[0]
    before = pool.available
    with pytest.raises(ValueError):
        pool.free([b, b])
    assert pool.available == before     # refused atomically
    pool.free([b])                      # the block is still cleanly held


# ------------------------------------------------- paged fill layout

def _check_paged_fill_layout(seed, batch, mb, bs, s):
    """Random prompt fill of ``s`` positions into rings of ``mb * bs``
    slots: each surviving token (the last vlen positions) sits at its
    virtual-ring slot, and every other slot — the null block's too —
    stays unwritten."""
    cfg = _cfg()
    vlen = mb * bs
    kk, kv = jax.random.split(jax.random.key(seed))
    k = jax.random.normal(kk, (batch, s, cfg.num_kv_heads, cfg.head_dim))
    v = jax.random.normal(kv, (batch, s, cfg.num_kv_heads, cfg.head_dim))
    positions = jnp.broadcast_to(jnp.arange(s)[None], (batch, s))
    paged = attention.init_paged_kv_cache(cfg, 1 + batch * mb, bs, k.dtype)
    table = jnp.arange(1, 1 + batch * mb, dtype=jnp.int32).reshape(batch, mb)
    paged = attention.fill_paged_kv_cache(paged, k, v, positions, table)
    kp, vp, pp = (np.asarray(paged[n])
                  for n in ("k_pool", "v_pool", "pos_pool"))
    k, v, tab = np.asarray(k), np.asarray(v), np.asarray(table)
    written = np.zeros(pp.shape, bool)
    for i in range(batch):
        for p in range(max(0, s - vlen), s):
            blk, off = tab[i, (p % vlen) // bs], (p % vlen) % bs
            np.testing.assert_array_equal(kp[blk, off], k[i, p])
            np.testing.assert_array_equal(vp[blk, off], v[i, p])
            assert pp[blk, off] == p
            written[blk, off] = True
    assert (pp[~written] == -1).all(), "fill wrote outside the survivors"


if HAVE_HYPOTHESIS:
    @settings(max_examples=40, deadline=None)
    @given(seed=st.integers(0, 2**31 - 1), batch=st.integers(1, 3),
           mb=st.integers(1, 4), bs=st.sampled_from([1, 2, 4]),
           extra=st.integers(-3, 12))
    def test_paged_fill_layout(seed, batch, mb, bs, extra):
        # extra > 0 overflows the ring (sliding-window prompt longer than
        # the ring) — only the survivors land; extra < 0 leaves slots empty
        _check_paged_fill_layout(seed, batch, mb, bs, max(1, mb * bs + extra))
else:
    def test_paged_fill_layout():
        rng = random.Random(1)
        for _ in range(40):
            bs = rng.choice([1, 2, 4])
            mb = rng.randint(1, 4)
            _check_paged_fill_layout(rng.randint(0, 2**31 - 1),
                                     rng.randint(1, 3), mb, bs,
                                     max(1, mb * bs + rng.randint(-3, 12)))


# ------------------------------------------------- Pallas kernel vs exact oracle

@pytest.mark.parametrize("window,softcap", [(None, None), (6, None),
                                            (None, 30.0), (6, 30.0)])
def test_paged_attention_kernel_matches_oracle(window, softcap):
    b, h, kh, hd, bsz, mb = 3, 4, 2, 16, 4, 3
    npool = 1 + b * mb
    key = jax.random.key(0)
    kq, kk, kv = jax.random.split(key, 3)
    q = jax.random.normal(kq, (b, h, hd), jnp.float32)
    k_pool = jax.random.normal(kk, (npool, bsz, kh, hd), jnp.float32)
    v_pool = jax.random.normal(kv, (npool, bsz, kh, hd), jnp.float32)
    table = jnp.arange(1, 1 + b * mb, dtype=jnp.int32).reshape(b, mb)
    # staggered fill levels incl. one wrapped request
    pos_pool = jnp.full((npool, bsz), -1, jnp.int32)
    vlen = mb * bsz
    for i, filled in enumerate((vlen // 2, vlen, vlen + 3)):
        pos = jnp.arange(filled, dtype=jnp.int32)
        vslot = pos % vlen
        pos_pool = pos_pool.at[table[i, vslot // bsz], vslot % bsz].set(pos)
    qpos = jnp.asarray([vlen // 2 - 1, vlen - 1, vlen + 2], jnp.int32)
    out = paged_attention(q, k_pool, v_pool, pos_pool, table, qpos,
                          window=window, softcap=softcap, interpret=True)
    ref = paged_attention_ref(q, k_pool, v_pool, pos_pool, table, qpos,
                              window=window, softcap=softcap)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


# ------------------------------------------------- scheduled vs forward

def _bank(model, params, ranks=(4, 8)):
    cfg = model.cfg
    sets = [init_adapter_set(params, jax.random.fold_in(jax.random.key(1), i),
                             LoRAConfig(rank=r, alpha=8.0,
                                        targets=cfg.lora_targets),
                             n_clients=len(ranks))
            for i, r in enumerate(ranks)]
    return AdapterBank.from_sets(sets)


def _run_static_vs_forward(check, cfg, *, bank_ranks=None, B=4, p=8,
                           steps=12, block_size=4, chunk=5, max_len=None):
    """All-at-once arrivals, uniform shapes: every request's scheduled
    greedy tokens are the forward's over its prompt and tokens."""
    model = build_model(cfg)
    params = model.init(jax.random.key(0))
    bank = _bank(model, params, bank_ranks) if bank_ranks else None
    prompt = np.asarray(jax.random.randint(jax.random.key(2), (B, p), 0,
                                           cfg.vocab_size), np.int32)
    ids = np.arange(B, dtype=np.int32) % (bank.size if bank else 1)
    reqs = [serve.Request(rid=i, prompt=prompt[i], steps=steps,
                          adapter_id=int(ids[i])) for i in range(B)]
    done = serve.serve_scheduled(model, params, reqs, bank=bank, max_batch=B,
                                 block_size=block_size, chunk=chunk,
                                 max_len=max_len or p + steps, wait=False)
    for r in done:
        assert len(r.tokens) == steps
        check(model, params, r.prompt, r.tokens,
              bank.adapter(r.adapter_id) if bank else None)
    return model


def test_scheduled_matches_forward_base(greedy_vs_forward):
    _run_static_vs_forward(greedy_vs_forward, _cfg())


def test_scheduled_matches_forward_banked(greedy_vs_forward):
    _run_static_vs_forward(greedy_vs_forward, _cfg(), bank_ranks=(4, 8))


def test_scheduled_matches_forward_sliding_window_overflow(
        greedy_vs_forward):
    # max_len 8 < prompt+steps 17: each request wraps its window-sized
    # virtual ring (6 slots in blocks of 2); the forward's windowed
    # attention is the reference
    _run_static_vs_forward(greedy_vs_forward, _cfg(attn_window=6), p=5,
                           steps=12, max_len=8, block_size=2)


def test_scheduled_matches_forward_recurrent_blocks(greedy_vs_forward):
    # per-slot recurrent state (rglru h/conv tail) must come back fresh at
    # admission and merge without disturbing attention pools
    _run_static_vs_forward(greedy_vs_forward,
                           _cfg(num_layers=4, block_pattern=("rglru", "attn")),
                           B=2, p=6, steps=8)


def test_scheduled_matches_forward_interpret_tier(greedy_vs_forward):
    # the full serving stack under the Pallas interpreter: BGMV adapter
    # kernel bodies run inside both engine programs
    dispatch.force_mode("interpret")
    dispatch.reset_stats()
    _run_static_vs_forward(greedy_vs_forward, _cfg(use_pallas=True),
                           bank_ranks=(4, 8), B=2, p=5, steps=6, chunk=3)
    assert dispatch.stats["bgmv"] > 0, "BGMV kernel tier never engaged"


def test_scheduled_pallas_paged_kernel_matches_forward(greedy_vs_forward,
                                                      monkeypatch):
    # the Pallas tier's paged attention gets one layer's pools out of the
    # stacked carry; the kernel runs under the interpreter (base-only
    # projections stay one XLA GEMM on every tier)
    from repro.kernels import paged_attention as kernel
    compiled = kernel.paged_attention
    monkeypatch.setattr(kernel, "paged_attention",
                        lambda *a, **kw: compiled(*a, **kw, interpret=True))
    dispatch.force_mode("pallas")
    dispatch.reset_stats()
    _run_static_vs_forward(greedy_vs_forward,
                           _cfg(use_pallas=True, num_kv_heads=2),
                           B=2, p=5, steps=6, chunk=3)
    assert dispatch.stats["paged"] > 0, "paged kernel never engaged"


def test_scheduled_churn_matches_forward(greedy_vs_forward):
    """Staggered completion: 6 requests through 2 engine slots — three
    waves recycling freed slots AND freed blocks.  Every request still
    serves the forward's greedy tokens, proving freed blocks are reset
    before reuse and per-slot merge doesn't leak."""
    cfg = _cfg()
    model = build_model(cfg)
    params = model.init(jax.random.key(0))
    bank = _bank(model, params)
    N, p, steps, max_len = 6, 6, 10, 16
    prompt = np.asarray(jax.random.randint(jax.random.key(3), (N, p), 0,
                                           cfg.vocab_size), np.int32)
    ids = np.asarray([0, 1, 1, 0, 0, 1], np.int32)
    reqs = [serve.Request(rid=i, prompt=prompt[i], steps=steps,
                          adapter_id=int(ids[i])) for i in range(N)]
    with trace.tracing() as t:
        done = serve.serve_scheduled(model, params, reqs, bank=bank,
                                     max_batch=2, block_size=4, chunk=4,
                                     max_len=max_len, wait=False)
    assert t.counters["serve.admitted"] == N
    for r in done:
        assert len(r.tokens) == steps
        greedy_vs_forward(model, params, r.prompt, r.tokens,
                          bank.adapter(r.adapter_id))


@pytest.mark.parametrize("kw", [
    dict(num_kv_heads=2),
    dict(attn_window=6),
    dict(num_layers=4, block_pattern=("rglru", "attn")),
    dict(num_layers=7, block_pattern=("mlstm", "attn", "slstm")),
], ids=["gqa", "window", "rglru", "xlstm"])
def test_scheduled_churn_reuses_the_donated_pool(kw, greedy_vs_forward,
                                                 monkeypatch):
    """Requests of several lengths through 2 slots: chunks end mid-request,
    finished requests' blocks go to later admissions, and every decode
    chunk donates the cache it is given.  Each cache a chunk took is
    deleted after the call (the pool was written in place), none is read
    again, and every request serves the forward's greedy tokens."""
    build = serve._jit_paged_chunk
    given = []

    def recording(model):
        fn = build(model)

        def chunk(params, cache, *args, **kw):
            given.append(cache)
            return fn(params, cache, *args, **kw)
        return chunk

    monkeypatch.setattr(serve, "_jit_paged_chunk", recording)
    cfg = _cfg(**kw)
    model = build_model(cfg)
    params = model.init(jax.random.key(0))
    steps = (7, 3, 9, 4, 6)
    prompt = np.asarray(jax.random.randint(jax.random.key(8), (5, 5), 0,
                                           cfg.vocab_size), np.int32)
    reqs = [serve.Request(rid=i, prompt=prompt[i], steps=s)
            for i, s in enumerate(steps)]
    done = serve.serve_scheduled(model, params, reqs, max_batch=2,
                                 block_size=2, chunk=3, max_len=14,
                                 wait=False)
    assert len(given) > 3
    assert all(leaf.is_deleted()
               for c in given for leaf in jax.tree.leaves(c))
    for r in done:
        assert len(r.tokens) == r.steps
        greedy_vs_forward(model, params, r.prompt, r.tokens)


def _pool_shapes(text, shapes):
    """(opcode, shape) of every instruction in compiled HLO ``text`` whose
    result has one of ``shapes`` (``"f32[3,9,3,2,16]"`` spelling)."""
    found = []
    for m in re.finditer(r"= (\w+\[[\d,]*\])(?:\{[^}]*\})? ([\w-]+)\(",
                         text):
        if m.group(1) in shapes:
            found.append((m.group(2), m.group(1)))
    return found


def test_decode_chunk_writes_the_pool_in_place():
    """The compiled decode chunk of a 3-layer GQA stack keeps its KV pool
    in one buffer: no copy, broadcast or dynamic-update-slice has a pool
    leaf's stacked shape (the step writes its rows by scatter into the
    carried pool), and each pool parameter is aliased to an output."""
    cfg = _cfg(num_heads=4, num_kv_heads=2)
    model = build_model(cfg)
    params = model.init(jax.random.key(0))
    b, mb, bs = 2, 4, 3
    cache = model.init_paged_cache(1 + b * mb, bs, b)
    assert {(a.dtype.name, a.shape) for a in jax.tree.leaves(cache)} == {
        ("float32", (3, 9, 3, 2, 16)), ("int32", (3, 9, 3))}
    pools = {"f32[3,9,3,2,16]", "s32[3,9,3]"}
    i32 = lambda *shape: jnp.zeros(shape, jnp.int32)
    text = serve._jit_paged_chunk(model).lower(
        params, cache, i32(b, 1), i32(b), jnp.ones((b,), bool), i32(b, mb),
        None, steps=4).compile().as_text()
    moved = [op for op in _pool_shapes(text, pools)
             if op[0] in ("copy", "broadcast", "dynamic-update-slice")]
    assert not moved, moved
    assert any(op == "scatter" for op, _ in _pool_shapes(text, pools))
    entry = text[text.index("\nENTRY "):]
    entry = entry[:entry.index("\n}")]
    params_at = {int(m.group(2)) for m in re.finditer(
        r"= (\w+\[[\d,]*\])\{[^}]*\} parameter\((\d+)\)", entry)
        if m.group(1) in pools}
    assert len(params_at) == len(jax.tree.leaves(cache))
    header = text[:text.index("\n")]
    aliased = {int(n) for n in re.findall(r"\}: \((\d+), \{\}",
                                          header)}
    assert params_at <= aliased, (params_at, header[:300])


def test_scheduled_mixed_lengths_and_steps_complete():
    """Heterogeneous stream: mixed prompt lengths (FIFO same-length
    admission groups), mixed step counts (mid-chunk finishes truncate),
    more requests than slots.  Everyone completes with exactly their
    requested token count, and the run is deterministic."""
    cfg = _cfg()
    model = build_model(cfg)
    params = model.init(jax.random.key(0))
    rng = np.random.default_rng(0)

    def mk():
        return [serve.Request(
            rid=i,
            prompt=rng_prompts[i],
            steps=int(steps_list[i]),
            adapter_id=0) for i in range(7)]

    plens = [4, 4, 6, 6, 4, 6, 4]
    steps_list = [1, 5, 9, 3, 7, 2, 4]
    rng_prompts = [rng.integers(0, cfg.vocab_size, n).astype(np.int32)
                   for n in plens]
    out = []
    for _ in range(2):
        done = serve.serve_scheduled(model, params, mk(), max_batch=3,
                                     block_size=4, chunk=4, wait=False)
        assert [len(r.tokens) for r in done] == steps_list
        out.append([r.tokens for r in done])
    assert out[0] == out[1]


def test_scheduled_immediate_finish_latency_sane():
    """steps=1 requests finish AT admission (their only token comes from
    the prefill); under wait=True their t_done is taken from t_first, so
    both timestamps must exist, be monotone w.r.t. arrival, and yield
    non-negative latency — the latency metrics aggregate them."""
    cfg = _cfg()
    model = build_model(cfg)
    params = model.init(jax.random.key(0))
    prompt = np.asarray(jax.random.randint(jax.random.key(5), (3, 4), 0,
                                           cfg.vocab_size), np.int32)
    reqs = [serve.Request(rid=i, prompt=prompt[i], steps=s, arrival=0.0)
            for i, s in enumerate((1, 1, 4))]
    done = serve.serve_scheduled(model, params, reqs, max_batch=3,
                                 block_size=4, chunk=2, wait=True)
    for r in done:
        assert r.t_first is not None and r.t_done is not None
        assert r.t_done >= r.t_first >= 0.0
        assert len(r.tokens) == r.steps
    for r in done[:2]:                  # immediate finishers: one timestamp
        assert r.t_done == r.t_first


def test_scheduled_block_starvation_waits_not_fails(greedy_vs_forward):
    """With exactly one request's worth of blocks, admission serializes:
    every request still completes with the forward's greedy tokens (the
    head of the queue waits for blocks instead of deadlocking or
    aliasing)."""
    cfg = _cfg()
    model = build_model(cfg)
    params = model.init(jax.random.key(0))
    prompt = np.asarray(jax.random.randint(jax.random.key(4), (3, 4), 0,
                                           cfg.vocab_size), np.int32)
    reqs = [serve.Request(rid=i, prompt=prompt[i], steps=5)
            for i in range(3)]
    done = serve.serve_scheduled(model, params, reqs, max_batch=1,
                                 block_size=4, chunk=2, max_len=12,
                                 wait=False)
    assert all(len(r.tokens) == 5 for r in done)
    for r in done:
        greedy_vs_forward(model, params, r.prompt, r.tokens)


# ------------------------------------------------- deadline-bounded serving

def test_deadline_evicts_at_chunk_boundary_with_exact_prefix():
    """Graceful degradation: a request with deadline_steps=8 inside a
    steps=32 ask is evicted at a chunk boundary with EXACTLY 8 tokens,
    marked timed_out, counted by the serve.timeouts counter — and its
    tokens are a bit-exact prefix of the un-deadlined run (eviction only
    ever happens between chunks, so it cannot perturb decode numerics)."""
    cfg = _cfg()
    model = build_model(cfg)
    params = model.init(jax.random.key(0))
    prompt = np.asarray(jax.random.randint(jax.random.key(5), (1, 6), 0,
                                           cfg.vocab_size), np.int32)
    full = serve.serve_scheduled(
        model, params,
        [serve.Request(rid=0, prompt=prompt[0], steps=32)],
        max_batch=2, block_size=4, chunk=4, max_len=40, wait=False)
    with trace.tracing() as t:
        done = serve.serve_scheduled(
            model, params,
            [serve.Request(rid=0, prompt=prompt[0], steps=32,
                           deadline_steps=8)],
            max_batch=2, block_size=4, chunk=4, max_len=40, wait=False)
    (r,) = done
    assert r.timed_out and len(r.tokens) == 8
    assert t.counters["serve.timeouts"] == 1
    np.testing.assert_array_equal(np.asarray(r.tokens),
                                  np.asarray(full[0].tokens)[:8])
    # an un-deadlined sibling is untouched
    assert not full[0].timed_out and len(full[0].tokens) == 32


def test_deadline_frees_slot_for_queued_request():
    """The evicted request's slot and blocks go back to the pool: a queued
    third request (max_batch=2) is admitted after the eviction and every
    request completes -- deadlined ones at their cap, the rest in full."""
    cfg = _cfg()
    model = build_model(cfg)
    params = model.init(jax.random.key(0))
    prompt = np.asarray(jax.random.randint(jax.random.key(6), (3, 4), 0,
                                           cfg.vocab_size), np.int32)
    reqs = [serve.Request(rid=0, prompt=prompt[0], steps=24,
                          deadline_steps=4),
            serve.Request(rid=1, prompt=prompt[1], steps=24,
                          deadline_steps=4),
            serve.Request(rid=2, prompt=prompt[2], steps=6)]
    with trace.tracing() as t:
        done = serve.serve_scheduled(model, params, reqs, max_batch=2,
                                     block_size=4, chunk=4, max_len=32,
                                     wait=False)
    by_rid = {r.rid: r for r in done}
    assert len(by_rid) == 3
    assert by_rid[0].timed_out and len(by_rid[0].tokens) == 4
    assert by_rid[1].timed_out and len(by_rid[1].tokens) == 4
    assert not by_rid[2].timed_out and len(by_rid[2].tokens) == 6
    assert t.counters["serve.timeouts"] == 2


def test_deadline_not_hit_is_a_noop():
    """A deadline looser than steps changes nothing: same tokens as the
    un-deadlined run, no timeout flagged."""
    cfg = _cfg()
    model = build_model(cfg)
    params = model.init(jax.random.key(0))
    prompt = np.asarray(jax.random.randint(jax.random.key(7), (1, 5), 0,
                                           cfg.vocab_size), np.int32)
    with trace.tracing() as t:
        runs = [serve.serve_scheduled(
            model, params,
            [serve.Request(rid=0, prompt=prompt[0], steps=6,
                           deadline_steps=d)],
            max_batch=1, block_size=4, chunk=3, max_len=16, wait=False)
            for d in (None, 32)]
    assert "serve.timeouts" not in t.counters
    for run in runs:
        assert not run[0].timed_out and len(run[0].tokens) == 6
    np.testing.assert_array_equal(runs[0][0].tokens, runs[1][0].tokens)


def test_make_requests_deadline_default_and_trace_override(tmp_path):
    trace = tmp_path / "trace.json"
    trace.write_text('[{"arrival": 0.0, "steps": 8},'
                     ' {"arrival": 0.0, "steps": 8, "deadline": 2}]')
    reqs = serve.make_requests(str(trace), prompt_len=4, steps=8, tenants=1,
                               vocab=64, deadline_steps=5)
    assert reqs[0].deadline_steps == 5          # module default applies
    assert reqs[1].deadline_steps == 2          # trace record overrides
    trace.write_text('[{"arrival": 0.0, "steps": 8, "deadline": 0}]')
    with pytest.raises(ValueError, match="deadline_steps"):
        serve.make_requests(str(trace), prompt_len=4, steps=8, tenants=1,
                            vocab=64)
