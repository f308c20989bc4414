"""GQA/MQA attention: full-sequence (train/prefill) and single-token decode.

Supports causal masking, sliding windows, qk-norm, logit soft-capping, and
bidirectional (encoder) attention.  Softmax always runs in float32.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

from repro.models import scan_unroll
from repro.models.layers import apply_rope, linear, rms_norm
from repro.sharding import opts
from repro.sharding.specs import constrain

NEG_INF = -1e30


def _maybe_expand_kv(cfg, q, k, v):
    """Under the ``expand_kv`` opt: repeat KV heads to the full head count and
    constrain the head dim onto the `model` axis, so attention shards by head
    instead of computing (partially) replicated when kv_heads < axis size."""
    if not opts.enabled("expand_kv"):
        return q, k, v
    h = q.shape[2]
    kh = k.shape[2]
    if kh != h:
        rep = h // kh
        k = jnp.repeat(k, rep, axis=2)
        v = jnp.repeat(v, rep, axis=2)
    spec = (("pod", "data"), None, "model", None)
    return (constrain(q, spec), constrain(k, spec), constrain(v, spec))


def attn_params(cfg, key, *, cross: bool = False, d_model=None):
    d = d_model or cfg.d_model
    pdt = jnp.dtype(cfg.param_dtype)
    kq, kk, kv, ko = jax.random.split(key, 4)
    s = d ** -0.5
    p = {
        "q": (jax.random.normal(kq, (d, cfg.q_dim)) * s).astype(pdt),
        "k": (jax.random.normal(kk, (d, cfg.kv_dim)) * s).astype(pdt),
        "v": (jax.random.normal(kv, (d, cfg.kv_dim)) * s).astype(pdt),
        "o": (jax.random.normal(ko, (cfg.q_dim, d)) * (cfg.q_dim ** -0.5)).astype(pdt),
    }
    if cfg.qk_norm:
        p["q_norm_scale"] = jnp.ones((cfg.head_dim,), pdt)
        p["k_norm_scale"] = jnp.ones((cfg.head_dim,), pdt)
    return p


def _project_qkv(cfg, params, x, kv_x=None, adapters=None, positions=None,
                 kv_positions=None, use_rope=True):
    """Returns q (b,s,h,hd), k/v (b,t,kh,hd) with RoPE + qk-norm applied."""
    kv_x = x if kv_x is None else kv_x
    b, s, _ = x.shape
    t = kv_x.shape[1]
    lq = (adapters or {}).get("q")
    lk = (adapters or {}).get("k")
    lv = (adapters or {}).get("v")
    q = linear(x, params["q"], lq).reshape(b, s, cfg.num_heads, cfg.head_dim)
    k = linear(kv_x, params["k"], lk).reshape(b, t, cfg.num_kv_heads, cfg.head_dim)
    v = checkpoint_name(linear(kv_x, params["v"], lv).reshape(
        b, t, cfg.num_kv_heads, cfg.head_dim), "v")
    # q and k are named where the backward's last read of them is: the
    # norm's backward reads its input; past rotary only the attention's
    # matmuls read them, as operands the TPU keeps in bfloat16
    if cfg.qk_norm:
        q = rms_norm(checkpoint_name(q, "q"), params["q_norm_scale"])
        k = rms_norm(checkpoint_name(k, "k"), params["k_norm_scale"])
    if use_rope:
        if positions is None:
            positions = jnp.arange(s)[None, :]
        if kv_positions is None:
            kv_positions = jnp.arange(t)[None, :]
        q = apply_rope(q, positions, cfg.rope_theta)
        k = apply_rope(k, kv_positions, cfg.rope_theta)
    if not cfg.qk_norm:
        q, k = checkpoint_name(q, "q"), checkpoint_name(k, "k")
    return q, k, v


def attention_core(cfg, q, k, v, mask):
    """q (b,s,h,hd), k/v (b,t,kh,hd), mask (b,1,s,t) or (b,kh,g,s,t)-broadcastable."""
    b, s, h, hd = q.shape
    kh = k.shape[2]
    g = h // kh
    qg = q.reshape(b, s, kh, g, hd)
    scores = jnp.einsum("bskgd,btkd->bkgst", qg.astype(jnp.float32),
                        k.astype(jnp.float32)) * (hd ** -0.5)
    if cfg.attn_logit_softcap:
        c = cfg.attn_logit_softcap
        scores = c * jnp.tanh(scores / c)
    scores = jnp.where(mask[:, None, None, :, :], scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bkgst,btkd->bskgd", probs, v.astype(jnp.float32))
    return out.reshape(b, s, h, hd).astype(q.dtype)


def make_mask(positions_q, positions_kv, *, causal: bool, window=None,
              valid_kv=None):
    """(b, s_q, s_kv) boolean mask."""
    pq = positions_q[:, :, None]
    pk = positions_kv[:, None, :]
    m = jnp.ones(jnp.broadcast_shapes(pq.shape, pk.shape), bool)
    if causal:
        m &= pk <= pq
    if window is not None:
        m &= pq - pk < window
    if valid_kv is not None:
        m &= valid_kv[:, None, :]
    return m


BLOCKWISE_THRESHOLD = 2048   # use flash-style blocked attention above this
Q_BLOCK = 1024
KV_BLOCK = 1024


def blockwise_attention(cfg, q, k, v, positions_q, positions_kv, *, causal,
                        window):
    """Flash-style attention in pure JAX: outer scan over q blocks, inner
    remat'd scan over kv blocks carrying (acc, m, l).  Memory O(s*hd);
    backward recomputes blocks (scan + jax.checkpoint) instead of storing
    the (s, t) score matrix.  The Pallas kernel in repro/kernels mirrors this
    on real TPUs."""
    b, s, h, hd = q.shape
    t = k.shape[1]
    kh = k.shape[2]
    g = h // kh
    bq = min(Q_BLOCK, s)
    bk = min(KV_BLOCK, t)
    nq, nk = -(-s // bq), -(-t // bk)
    pad_q, pad_k = nq * bq - s, nk * bk - t
    qf = jnp.pad(q.astype(jnp.float32), ((0, 0), (0, pad_q), (0, 0), (0, 0)))
    kf = jnp.pad(k.astype(jnp.float32), ((0, 0), (0, pad_k), (0, 0), (0, 0)))
    vf = jnp.pad(v.astype(jnp.float32), ((0, 0), (0, pad_k), (0, 0), (0, 0)))
    pq = jnp.pad(positions_q, ((0, 0), (0, pad_q)), constant_values=-1)
    pk = jnp.pad(positions_kv, ((0, 0), (0, pad_k)), constant_values=2**30)
    qf = qf.reshape(b, nq, bq, kh, g, hd).transpose(1, 0, 2, 3, 4, 5)
    kf = kf.reshape(b, nk, bk, kh, hd).transpose(1, 0, 2, 3, 4)
    vf = vf.reshape(b, nk, bk, kh, hd).transpose(1, 0, 2, 3, 4)
    pqb = pq.reshape(b, nq, bq).transpose(1, 0, 2)
    pkb = pk.reshape(b, nk, bk).transpose(1, 0, 2)
    scale = hd ** -0.5

    def kv_step(carry, xs):
        acc, m, l, qblk, pq_blk = carry
        kblk, vblk, pk_blk = xs
        sc = jnp.einsum("bqkgd,btkd->bkgqt", qblk, kblk) * scale
        if cfg.attn_logit_softcap:
            c = cfg.attn_logit_softcap
            sc = c * jnp.tanh(sc / c)
        msk = make_mask(pq_blk, pk_blk, causal=causal, window=window)
        sc = jnp.where(msk[:, None, None, :, :], sc, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(sc, axis=-1))
        p = jnp.where(sc <= NEG_INF / 2, 0.0, jnp.exp(sc - m_new[..., None]))
        corr = jnp.exp(m - m_new)
        l_new = l * corr + p.sum(-1)
        acc_new = acc * corr[..., None] + jnp.einsum("bkgqt,btkd->bkgqd", p, vblk)
        return (acc_new, m_new, l_new, qblk, pq_blk), None

    def q_block(qblk, pq_blk):
        if opts.enabled("seq_parallel_attn") and h % 16 != 0:
            # context parallelism: shard the query block's seq dim over
            # `model` when heads can't divide the axis (8-head archs)
            qblk = constrain(qblk, (None, "model", None, None, None))
        acc0 = jnp.zeros((b, kh, g, bq, hd), jnp.float32)
        m0 = jnp.full((b, kh, g, bq), NEG_INF, jnp.float32)
        l0 = jnp.zeros((b, kh, g, bq), jnp.float32)
        (acc, m, l, _, _), _ = jax.lax.scan(
            jax.checkpoint(kv_step), (acc0, m0, l0, qblk, pq_blk),
            (kf, vf, pkb), unroll=scan_unroll(nk))
        out = acc / jnp.maximum(l, 1e-30)[..., None]
        return out.transpose(0, 3, 1, 2, 4).reshape(b, bq, h, hd)

    _, outs = jax.lax.scan(lambda _, xs: (None, q_block(*xs)), None,
                           (qf, pqb), unroll=scan_unroll(nq))
    out = outs.transpose(1, 0, 2, 3, 4).reshape(b, nq * bq, h, hd)[:, :s]
    return out.astype(q.dtype)


def attention_fullseq(cfg, params, x, *, causal=True, adapters=None,
                      positions=None, kv_x=None, use_rope=True, window=None):
    """Full-sequence attention (training / prefill / encoder / cross)."""
    b, s, _ = x.shape
    if positions is None:
        positions = jnp.broadcast_to(jnp.arange(s)[None, :], (b, s))
    kv_pos = (positions if kv_x is None else
              jnp.broadcast_to(jnp.arange(kv_x.shape[1])[None, :], (b, kv_x.shape[1])))
    q, k, v = _project_qkv(cfg, params, x, kv_x=kv_x, adapters=adapters,
                           positions=positions, kv_positions=kv_pos,
                           use_rope=use_rope)
    win = window if window is not None else cfg.attn_window
    t = k.shape[1]
    q, k, v = _maybe_expand_kv(cfg, q, k, v)
    if max(s, t) > BLOCKWISE_THRESHOLD:
        out = blockwise_attention(cfg, q, k, v, positions, kv_pos,
                                  causal=causal, window=win if causal else None)
    else:
        mask = make_mask(positions, kv_pos, causal=causal,
                         window=win if causal else None)
        out = attention_core(cfg, q, k, v, mask)
    lo = (adapters or {}).get("o")
    return checkpoint_name(linear(out.reshape(b, s, -1), params["o"], lo),
                           "attn_out")


# ----------------------------------------------------------------- paged KV cache
#
# Serving keeps K/V in a SHARED block pool (num_blocks, block_size, kh, hd)
# per layer, plus a per-request block table (b, blocks_per_req) int32
# mapping virtual block j of request i to a pool block.  A request's view of
# the pool is a virtual ring of vlen = blocks_per_req * block_size slots:
# the token at absolute position p lands in virtual slot p % vlen, i.e. pool
# block table[i, (p % vlen) // block_size] at offset (p % vlen) %
# block_size.  A ring at least as long as the attention window (or the whole
# request) loses nothing attention reads: a slot is overwritten only by the
# position vlen later, by which time the old one has left the window.
#
# Block 0 is the NULL block: the scheduler points idle batch slots' table
# rows at it, so their (discarded) decode writes land harmlessly in a block
# no live request ever owns.  The pos pool doubles as the validity mask
# (entry >= 0 == written).


def init_paged_kv_cache(cfg, num_blocks: int, block_size: int, dtype):
    """Per-layer shared pool.  The per-request geometry (how many blocks a
    request owns) is the block TABLE's width, not a pool property."""
    return {
        "k_pool": jnp.zeros((num_blocks, block_size, cfg.num_kv_heads,
                             cfg.head_dim), dtype),
        "v_pool": jnp.zeros((num_blocks, block_size, cfg.num_kv_heads,
                             cfg.head_dim), dtype),
        "pos_pool": jnp.full((num_blocks, block_size), -1, jnp.int32),
    }


def paged_gather(cache, table, layer=None):
    """Materialize each request's virtual ring view of the pool:
    (k (b, vlen, kh, hd), v (b, vlen, kh, hd), pos (b, vlen)).  With
    ``layer`` the pools are stacked over layers and the view is of that
    layer's, gathered straight from the stack."""
    b, mb = table.shape
    lead = () if layer is None else (layer,)
    bs = cache["k_pool"].shape[len(lead) + 1]
    kh, hd = cache["k_pool"].shape[len(lead) + 2:]
    k = cache["k_pool"][lead + (table,)].reshape(b, mb * bs, kh, hd)
    v = cache["v_pool"][lead + (table,)].reshape(b, mb * bs, kh, hd)
    pos = cache["pos_pool"][lead + (table,)].reshape(b, mb * bs)
    return k, v, pos


def fill_paged_kv_cache(cache, k, v, positions, table):
    """Write a whole prompt's K/V rows into each request's pool blocks at
    their virtual-ring slots (``positions % vlen``), the slots the
    token-by-token decode would have used.  On overflow only the last
    ``vlen`` positions land — the survivors of sequential ring writes."""
    bs = cache["k_pool"].shape[1]
    vlen = table.shape[1] * bs
    if k.shape[1] > vlen:
        k, v, positions = k[:, -vlen:], v[:, -vlen:], positions[:, -vlen:]
    vslot = positions % vlen                                # (b, s)
    blk = jnp.take_along_axis(table, vslot // bs, axis=1)   # (b, s)
    off = vslot % bs
    return {"k_pool": cache["k_pool"].at[blk, off].set(k),
            "v_pool": cache["v_pool"].at[blk, off].set(v),
            "pos_pool": cache["pos_pool"].at[blk, off].set(positions)}


def attention_prefill_paged(cfg, params, x, cache, positions, table, *,
                            adapters=None):
    """Whole-prompt attention that fills the requests' POOL blocks — the
    batched form of running :func:`attention_decode_paged` once per prompt
    token on empty blocks.  The attention itself is over the prompt's own
    K/V.  x (b, s, d), positions (b, s).  Returns (out, new_cache)."""
    b, s, _ = x.shape
    q, k, v = _project_qkv(cfg, params, x, adapters=adapters,
                           positions=positions, kv_positions=positions)
    new_cache = fill_paged_kv_cache(cache, k, v, positions, table)
    win = cfg.attn_window
    q, k, v = _maybe_expand_kv(cfg, q, k, v)
    if s > BLOCKWISE_THRESHOLD:
        out = blockwise_attention(cfg, q, k, v, positions, positions,
                                  causal=True, window=win)
    else:
        mask = make_mask(positions, positions, causal=True, window=win)
        out = attention_core(cfg, q, k, v, mask)
    y = linear(out.reshape(b, s, -1), params["o"],
               (adapters or {}).get("o"))
    return y, new_cache


def attention_decode_paged(cfg, params, x, cache, table, pos, *,
                           layer=None, adapters=None):
    """One-token decode against the block pool.  x (b,1,d); table (b,
    blocks_per_req) int32; pos (b,) absolute position.  With ``layer`` the
    pools are stacked over the scanned layers (a leading layer dim): the
    step writes its rows at ``[layer, block, offset]`` and reads that
    layer's ring from the same buffer, which the layer scan then carries
    on in place.

    Reference tier gathers each request's blocks into its virtual ring
    (:func:`paged_gather`) and masks by the gathered positions; on the
    pallas tier the gather never materializes — the kernel's BlockSpecs
    stream pool blocks through the block table via scalar prefetch."""
    from repro.kernels import dispatch

    b = x.shape[0]
    lead = () if layer is None else (layer,)
    bs = cache["k_pool"].shape[len(lead) + 1]
    vlen = table.shape[1] * bs
    q, k, v = _project_qkv(cfg, params, x, adapters=adapters,
                           positions=pos[:, None], kv_positions=pos[:, None])
    vslot = pos % vlen                                  # (b,)
    bidx = jnp.arange(b)
    blk = table[bidx, vslot // bs]
    off = vslot % bs
    at = lead + (blk, off)
    new_cache = {"k_pool": cache["k_pool"].at[at].set(k[:, 0]),
                 "v_pool": cache["v_pool"].at[at].set(v[:, 0]),
                 "pos_pool": cache["pos_pool"].at[at].set(pos)}
    if dispatch.resolve_mode() == "pallas":
        from repro.kernels.paged_attention import paged_attention
        dispatch.stats["paged"] += 1
        # the kernel takes one layer's pools: a copy of it on this tier
        pools = (new_cache if layer is None
                 else {n: a[layer] for n, a in new_cache.items()})
        out = paged_attention(
            q[:, 0], pools["k_pool"], pools["v_pool"],
            pools["pos_pool"], table, pos,
            window=cfg.attn_window, softcap=cfg.attn_logit_softcap)[:, None]
    else:
        kg, vg, pg = paged_gather(new_cache, table, layer)
        mask = make_mask(pos[:, None], pg, causal=True,
                         window=cfg.attn_window, valid_kv=pg >= 0)
        out = attention_core(cfg, q, kg, vg, mask)
    lo = (adapters or {}).get("o")
    y = linear(out.reshape(b, 1, -1), params["o"], lo)
    return y, new_cache
