"""Generic block-stack transformer machinery.

A model is a stack of blocks drawn from ``cfg.block_pattern`` (repeated to
``num_layers``).  Full repeats of the pattern run under one ``jax.lax.scan``
(stacked params — keeps HLO size independent of depth); the remainder runs
unrolled.  Every block kind supports:

  init_block(cfg, key, kind)                             -> params pytree
  apply_block(..., mode="fullseq")                       -> (x, aux)
  init_paged_block_cache(cfg, kind, num_blocks,
                         block_size, batch, dtype)       -> cache pytree
  apply_block(..., mode="decode", cache=, pos=, table=,
              layer=)                                    -> (x, aux, cache)

Kinds: "attn" (GQA attention + MLP/MoE), "xattn" (self+cross attention + MLP,
for encoder-decoder), "rglru" (RG-LRU temporal mix + MLP), "mlstm", "slstm".
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.analysis import trace
from repro.models import scan_unroll
from repro.models import attention as attn_mod
from repro.models import rglru as rglru_mod
from repro.models import xlstm as xlstm_mod
from repro.models.attention import (attn_params, attention_fullseq,
                                    attention_decode_paged,
                                    attention_prefill_paged,
                                    init_paged_kv_cache, _project_qkv,
                                    attention_core, make_mask)
from repro.models.layers import (apply_norm, linear, mlp_apply, mlp_params,
                                 norm_params)
from repro.models.moe import moe_apply, moe_params


# ----------------------------------------------------------------- block init

def init_block(cfg, key, kind: str):
    d = cfg.d_model
    ks = jax.random.split(key, 4)
    p = {}
    if kind in ("attn", "xattn"):
        p.update(norm_params(cfg, d, "ln1"))
        p["attn"] = attn_params(cfg, ks[0])
        if kind == "xattn":
            p.update(norm_params(cfg, d, "lnx"))
            p["cross"] = attn_params(cfg, ks[1])
        p.update(norm_params(cfg, d, "ln2"))
        if cfg.moe is not None:
            p["moe"] = moe_params(cfg, ks[2])
        else:
            p["mlp"] = mlp_params(cfg, ks[2], d, cfg.d_ff)
    elif kind == "rglru":
        p.update(norm_params(cfg, d, "ln1"))
        p["rglru"] = rglru_mod.rglru_params(cfg, ks[0])
        p.update(norm_params(cfg, d, "ln2"))
        p["mlp"] = mlp_params(cfg, ks[1], d, cfg.d_ff)
    elif kind == "mlstm":
        p.update(norm_params(cfg, d, "ln1"))
        p["mlstm"] = xlstm_mod.mlstm_params(cfg, ks[0])
    elif kind == "slstm":
        p.update(norm_params(cfg, d, "ln1"))
        p["slstm"] = xlstm_mod.slstm_params(cfg, ks[0])
    else:
        raise ValueError(kind)
    return p


def init_paged_block_cache(cfg, kind: str, num_blocks: int, block_size: int,
                           batch: int, dtype, cross_len: int = 0):
    """Serving cache of one block: attention KV lives in a shared block
    pool (no batch dim — requests own pool blocks through the block table),
    while recurrent blocks and cross-attention K/V carry constant-size
    PER-SLOT state (``batch`` = engine slot count) — admit/evict for those
    is a slot-level state swap, not paging."""
    if kind == "attn":
        return init_paged_kv_cache(cfg, num_blocks, block_size, dtype)
    if kind == "xattn":
        return {"self": init_paged_kv_cache(cfg, num_blocks, block_size,
                                            dtype),
                "cross_k": jnp.zeros((batch, cross_len, cfg.num_kv_heads,
                                      cfg.head_dim), dtype),
                "cross_v": jnp.zeros((batch, cross_len, cfg.num_kv_heads,
                                      cfg.head_dim), dtype)}
    if kind == "rglru":
        return rglru_mod.rglru_init_state(cfg, batch, dtype)
    if kind == "mlstm":
        return xlstm_mod.mlstm_init_state(cfg, batch, dtype)
    if kind == "slstm":
        return xlstm_mod.slstm_init_state(cfg, batch, dtype)
    raise ValueError(kind)


# ----------------------------------------------------------------- block apply

def _cross_attention(cfg, params, x, ck, cv, adapters=None):
    """Cross-attention against precomputed encoder K/V (no masking, no RoPE)."""
    b, s, _ = x.shape
    lq = (adapters or {}).get("q")
    q = linear(x, params["q"], lq).reshape(b, s, cfg.num_heads,
                                           cfg.head_dim)
    mask = jnp.ones((b, s, ck.shape[1]), bool)
    out = attention_core(cfg, q, ck, cv, mask)
    return linear(out.reshape(b, s, -1), params["o"],
                  (adapters or {}).get("o"))


def build_cross_kv(cfg, p_cross, enc_out):
    """Project encoder output to per-layer cross K/V (no RoPE)."""
    b, t, _ = enc_out.shape
    k = linear(enc_out, p_cross["k"]).reshape(b, t, cfg.num_kv_heads,
                                              cfg.head_dim)
    v = linear(enc_out, p_cross["v"]).reshape(b, t, cfg.num_kv_heads,
                                              cfg.head_dim)
    return k, v


def _at_layer(tree, layer):
    """One layer of a stacked cache subtree (all of it without a layer)."""
    return tree if layer is None else jax.tree.map(lambda a: a[layer], tree)


def apply_block(cfg, kind, p, x, *, adapters=None, positions=None,
                causal=True, mode="fullseq", cache=None, pos=None,
                enc_out=None, table=None, layer=None):
    """``mode``: "fullseq" (train/encode — no cache), "prefill" (whole
    prompt in one pass, cache filled as the token-by-token decode would
    have), "decode" (one token against the cache).  Prefill and decode
    return (x, aux, new_cache); fullseq returns (x, aux).

    Attention K/V live in the block pool (models/attention.py), addressed
    through ``table`` (b, blocks_per_req) int32; prefill and decode of an
    attention block need it.  Non-attention state is per-slot.

    A scanned block's decode gets its index ``layer``: ``cache`` then holds
    every scanned layer of this block's state (a leading layer dim), and
    the block reads and writes its own layer in it, so the whole stacked
    cache stays one buffer through the layer scan."""
    adapters = adapters or {}
    aux = jnp.zeros((), jnp.float32)
    h1 = apply_norm(cfg, x, p, "ln1")
    new_cache = None
    # a recurrent block's decode steps its layer's per-slot state; the
    # attention pools are read and written in place by
    # attention_decode_paged
    per_slot = mode == "decode" and kind not in ("attn", "xattn")
    state = _at_layer(cache, layer) if per_slot else cache

    if kind in ("attn", "xattn"):
        self_cache = (None if cache is None
                      else cache["self"] if kind == "xattn" else cache)
        if mode == "fullseq":
            a = attention_fullseq(cfg, p["attn"], h1, causal=causal,
                                  adapters=adapters.get("attn"),
                                  positions=positions)
        elif mode == "prefill":
            a, self_cache = attention_prefill_paged(
                cfg, p["attn"], h1, self_cache, positions, table,
                adapters=adapters.get("attn"))
        else:
            a, self_cache = attention_decode_paged(
                cfg, p["attn"], h1, self_cache, table, pos, layer=layer,
                adapters=adapters.get("attn"))
        x = x + a
        if kind == "xattn":
            hx = apply_norm(cfg, x, p, "lnx")
            if mode == "decode" or (mode == "prefill" and enc_out is None):
                # decode reads the cache's cross K/V; prefill without an
                # encoder output keeps them too (the token-by-token path's
                # semantics: a fresh cache cross-attends zeros)
                ck, cv = cache["cross_k"], cache["cross_v"]
            else:
                ck, cv = build_cross_kv(cfg, p["cross"], enc_out)
            x = x + _cross_attention(cfg, p["cross"], hx,
                                     _at_layer(ck, layer),
                                     _at_layer(cv, layer),
                                     adapters=adapters.get("cross"))
        h2 = apply_norm(cfg, x, p, "ln2")
        if cfg.moe is not None:
            mo, aux = moe_apply(cfg, p["moe"], h2,
                                adapters=adapters.get("moe"))
            x = x + mo
        else:
            x = x + mlp_apply(cfg, p["mlp"], h2,
                              adapters=adapters.get("mlp"))
        if mode != "fullseq":
            new_cache = ({"self": self_cache, "cross_k": ck, "cross_v": cv}
                         if kind == "xattn" else self_cache)

    elif kind == "rglru":
        if mode == "fullseq":
            r = rglru_mod.rglru_apply_fullseq(cfg, p["rglru"], h1,
                                              adapters.get("rglru"))
        elif mode == "prefill":
            r, new_cache = rglru_mod.rglru_apply_prefill(
                cfg, p["rglru"], h1, cache, positions, adapters.get("rglru"))
        else:
            r, new_cache = rglru_mod.rglru_apply_decode(
                cfg, p["rglru"], h1, state, pos, adapters.get("rglru"))
        x = x + r
        h2 = apply_norm(cfg, x, p, "ln2")
        x = x + mlp_apply(cfg, p["mlp"], h2)

    elif kind == "mlstm":
        if mode == "fullseq":
            m = xlstm_mod.mlstm_apply_fullseq(cfg, p["mlstm"], h1,
                                              adapters.get("mlstm"))
        elif mode == "prefill":
            m, new_cache = xlstm_mod.mlstm_apply_prefill(
                cfg, p["mlstm"], h1, cache, positions, adapters.get("mlstm"))
        else:
            m, new_cache = xlstm_mod.mlstm_apply_decode(
                cfg, p["mlstm"], h1, state, pos, adapters.get("mlstm"))
        x = x + m

    elif kind == "slstm":
        if mode == "fullseq":
            s_ = xlstm_mod.slstm_apply_fullseq(cfg, p["slstm"], h1,
                                               adapters.get("slstm"))
        elif mode == "prefill":
            s_, new_cache = xlstm_mod.slstm_apply_prefill(
                cfg, p["slstm"], h1, cache, positions, adapters.get("slstm"))
        else:
            s_, new_cache = xlstm_mod.slstm_apply_decode(
                cfg, p["slstm"], h1, state, pos, adapters.get("slstm"))
        x = x + s_
    else:
        raise ValueError(kind)

    if mode == "fullseq":
        return x, aux
    if per_slot and layer is not None:
        new_cache = jax.tree.map(lambda a, n: a.at[layer].set(n), cache,
                                 new_cache)
    return x, aux, new_cache


# ----------------------------------------------------------------- the stack

def stack_layout(num_layers: int, pattern):
    m = len(pattern)
    return num_layers // m, tuple(pattern[:num_layers % m])


def init_stack(cfg, key, *, num_layers=None, pattern=None):
    num_layers = num_layers or cfg.num_layers
    pattern = pattern or cfg.block_pattern
    repeats, tail = stack_layout(num_layers, pattern)
    k_rep, k_tail = jax.random.split(key)
    out = {"repeat": {}, "tail": {}}
    if repeats:
        for j, kind in enumerate(pattern):
            keys = jax.random.split(jax.random.fold_in(k_rep, j), repeats)
            out["repeat"][f"p{j}"] = jax.vmap(
                lambda k, kd=kind: init_block(cfg, k, kd))(keys)
    for i, kind in enumerate(tail):
        out["tail"][f"t{i}"] = init_block(cfg, jax.random.fold_in(k_tail, i),
                                          kind)
    return out


def init_paged_stack_cache(cfg, num_blocks, block_size, batch, dtype, *,
                           num_layers=None, pattern=None, cross_len=0):
    """Stack cache for the serving engine: attention layers hold
    SHARED pools (repeat leaves gain a leading layer dim as usual), every
    other layer kind holds per-slot state for ``batch`` engine slots."""
    num_layers = num_layers or cfg.num_layers
    pattern = pattern or cfg.block_pattern
    repeats, tail = stack_layout(num_layers, pattern)
    mk = lambda kind: init_paged_block_cache(cfg, kind, num_blocks,
                                             block_size, batch, dtype,
                                             cross_len=cross_len)
    out = {"repeat": {}, "tail": {}}
    if repeats:
        for j, kind in enumerate(pattern):
            out["repeat"][f"p{j}"] = jax.tree.map(
                lambda a: jnp.broadcast_to(a, (repeats,) + a.shape).copy(),
                mk(kind))
    for i, kind in enumerate(tail):
        out["tail"][f"t{i}"] = mk(kind)
    return out


# The scheduler's cache surgery (launch/serve.py): a paged stack cache mixes
# two leaf families — SHARED pool subtrees (dicts holding "k_pool", no batch
# dim beyond the repeat-layer one) and PER-SLOT leaves (batch axis 0 in the
# tail, axis 1 under the repeat stacking).  Admission prefills newcomers on a
# view whose pools ARE the engine pools (their functional update only
# touches the newcomers' blocks) and whose per-slot leaves are fresh inits
# at the group size, then the merge takes pool subtrees wholesale and
# scatters per-slot leaves into the newcomers' slots.


def _walk_paged(c, v, fn_pool, fn_leaf, axis):
    if isinstance(c, dict):
        if "k_pool" in c:
            return fn_pool(c, None if v is None else v, axis)
        return {k: _walk_paged(c[k], None if v is None else v[k],
                               fn_pool, fn_leaf, axis) for k in c}
    return fn_leaf(c, v, axis)


def _map_paged_cache(cache, view, fn_pool, fn_leaf):
    return {"repeat": {k: _walk_paged(cache["repeat"][k],
                                      None if view is None
                                      else view["repeat"][k],
                                      fn_pool, fn_leaf, 1)
                       for k in cache["repeat"]},
            "tail": {k: _walk_paged(cache["tail"][k],
                                    None if view is None
                                    else view["tail"][k],
                                    fn_pool, fn_leaf, 0)
                     for k in cache["tail"]}}


def paged_prefill_view(cfg, cache, batch, dtype, *, num_layers=None,
                       pattern=None, cross_len=0):
    """Admission view: engine pools shared, fresh per-slot state for a
    ``batch``-request newcomer group (zero recurrences, -1e30 stabilizer
    states — what a request's first decode step starts from)."""
    fresh = init_paged_stack_cache(cfg, 1, 1, batch, dtype,
                                   num_layers=num_layers, pattern=pattern,
                                   cross_len=cross_len)
    return _map_paged_cache(cache, fresh,
                            lambda c, v, axis: c,
                            lambda c, v, axis: v)


def merge_paged_cache(cache, view, slots):
    """Fold an admission view back into the engine cache: pool subtrees
    come back wholesale (only the newcomers' blocks changed), per-slot
    leaves scatter into the newcomers' ``slots``."""
    return _map_paged_cache(
        cache, view,
        lambda c, v, axis: v,
        lambda c, v, axis: (c.at[slots].set(v) if axis == 0
                            else c.at[:, slots].set(v)))


def reset_paged_blocks(cache, blocks):
    """Invalidate ``blocks`` (1-D int32) in every layer's pos pool before
    reuse: freed blocks keep stale ``pos >= 0`` entries that the validity
    mask would otherwise re-admit into a new owner's attention."""
    def pool(c, v, axis):
        pp = (c["pos_pool"].at[blocks].set(-1) if axis == 0
              else c["pos_pool"].at[:, blocks].set(-1))
        return {**c, "pos_pool": pp}
    return _map_paged_cache(cache, None, pool, lambda c, v, axis: c)


# The per-layer tensors that the backward of a frozen base reads, named with
# ``checkpoint_name``: the q, k and v projections (attention.py, where the
# backward last reads them), the attention's output projection, and the
# MLP's gate and up (layers.py).  Keeping one spares the backward its
# matmul; down's output is read by no backward, so it has no name.
SAVEABLE = ("q", "k", "v", "attn_out", "mlp_gate", "mlp_up")


def saveable_widths(cfg):
    """``{name: (width, d_in)}`` per token and layer for a scanned stack of
    dense attention blocks: keeping one element of ``name`` spares the
    backward ``2 * d_in`` recomputed FLOPs.  Empty for any other stack."""
    if (cfg.family != "dense" or cfg.moe is not None
            or tuple(cfg.block_pattern) != ("attn",)):
        return {}
    d = cfg.d_model
    widths = {"q": (cfg.q_dim, d), "k": (cfg.kv_dim, d),
              "v": (cfg.kv_dim, d), "attn_out": (d, cfg.q_dim),
              "mlp_up": (cfg.d_ff, d)}
    if cfg.mlp_variant in ("swiglu", "geglu"):
        widths["mlp_gate"] = (cfg.d_ff, d)
    return {n: widths[n] for n in SAVEABLE if n in widths}


def choose_saved(cfg, *, tokens: int, itemsize: int, budget: int):
    """The names a layer scan over ``tokens`` tokens a step keeps, and their
    bytes: saveable tensors taken greedily by recomputed FLOPs spared per
    byte kept (ties in ``SAVEABLE`` order), each only while the total over
    every scanned layer stays within ``budget`` bytes."""
    layers = stack_layout(cfg.num_layers, cfg.block_pattern)[0]
    widths = saveable_widths(cfg)
    order = sorted(widths, key=lambda n: -widths[n][1])
    names, total = [], 0
    for n in order:
        nbytes = layers * tokens * widths[n][0] * itemsize
        if total + nbytes <= budget:
            names.append(n)
            total += nbytes
    return tuple(sorted(names, key=SAVEABLE.index)), total


def apply_stack(cfg, stack_params, x, *, adapters=None, positions=None,
                causal=True, pattern=None, remat=True, saved=(),
                enc_out=None):
    """Full-sequence forward.  Returns (x, aux_sum).

    ``adapters`` is the prepared "stack" subtree of an AdapterSet (scaling
    folded, mask applied); banked per-request trees must be in scan layout
    (see :func:`batched_scan_layout`).  With ``remat`` each scanned layer
    keeps its input for the backward, and the tensors of ``SAVEABLE`` that
    ``saved`` names; the backward recomputes the rest of the layer."""
    pattern = pattern or cfg.block_pattern
    adapters = adapters or {}
    rep_p = stack_params.get("repeat", {})
    rep_lora = adapters.get("repeat") or _empty_like_stack(rep_p)

    def one_rep(h, xs):
        ps, los = xs
        from repro.sharding import opts as _opts
        if _opts.enabled("seq_parallel_residual"):
            from repro.sharding.specs import constrain as _constrain
            h = _constrain(h, (None, "model", None))
        aux = jnp.zeros((), jnp.float32)
        for j, kind in enumerate(pattern):
            h, a = apply_block(cfg, kind, ps[f"p{j}"], h,
                               adapters=los.get(f"p{j}"),
                               positions=positions, causal=causal,
                               enc_out=enc_out)
            aux = aux + a
        return h, aux

    aux_total = jnp.zeros((), jnp.float32)
    if rep_p:
        from repro.sharding import opts
        if remat and opts.enabled("remat_dots"):
            # save matmul outputs across the scan, recompute only elementwise
            body = jax.checkpoint(
                one_rep,
                policy=jax.checkpoint_policies.dots_with_no_batch_dims_saveable)
        elif remat and saved:
            # no CSE barrier: inside a scan the backward's recomputation
            # cannot merge with the forward anyway, and with tensors kept
            # the barrier cost more than they spared (a TPU v5e round of
            # stablelm-1.6b at N 8: 273 ms without it, 304 with, 302 with
            # nothing kept)
            body = jax.checkpoint(
                one_rep, prevent_cse=False,
                policy=jax.checkpoint_policies.save_only_these_names(*saved))
        elif remat:
            # with nothing kept the barrier stays: without it that round
            # ran 310 ms against 302
            body = jax.checkpoint(one_rep)
        else:
            body = one_rep
        n_rep = jax.tree.leaves(rep_p)[0].shape[0]
        x, auxs = jax.lax.scan(body, x, (rep_p, rep_lora),
                               unroll=scan_unroll(n_rep))
        aux_total = aux_total + auxs.sum()
    kinds = _tail_kinds(cfg, pattern, stack_params)
    for i, kind in enumerate(kinds):
        x, a = apply_block(cfg, kind, stack_params["tail"][f"t{i}"], x,
                           adapters=(adapters.get("tail") or {}).get(f"t{i}"),
                           positions=positions, causal=causal,
                           enc_out=enc_out)
        aux_total = aux_total + a
    return x, aux_total


def _tail_kinds(cfg, pattern, stack_params):
    n_tail = len(stack_params.get("tail") or {})
    return tuple(pattern[:n_tail])


def prefill_stack(cfg, stack_params, cache, x, positions, *, adapters=None,
                  pattern=None, enc_out=None, table=None):
    """Whole-prompt forward that also fills every block cache in ONE pass —
    the batched replacement for feeding the prompt through single-token
    decode steps.  Returns (x, aux_sum, new_cache); the cache comes back
    exactly as the token-by-token decode would have left it (pool blocks,
    recurrence states, conv tails).  ``table`` addresses the attention
    pools; it is the same for every layer (each layer has its own pool of
    identical geometry), so it rides the scan closure."""
    pattern = pattern or cfg.block_pattern
    adapters = adapters or {}
    rep_p = stack_params.get("repeat", {})
    rep_lora = adapters.get("repeat") or _empty_like_stack(rep_p)

    def scan_body(h, xs):
        ps, los, cs = xs
        new_cs = {}
        aux = jnp.zeros((), jnp.float32)
        for j, kind in enumerate(pattern):
            h, a, nc = apply_block(cfg, kind, ps[f"p{j}"], h,
                                   adapters=los.get(f"p{j}"),
                                   positions=positions, mode="prefill",
                                   cache=cs[f"p{j}"], enc_out=enc_out,
                                   table=table)
            new_cs[f"p{j}"] = nc
            aux = aux + a
        return h, (new_cs, aux)

    aux_total = jnp.zeros((), jnp.float32)
    new_cache = {"repeat": {}, "tail": {}}
    if rep_p:
        n_rep = jax.tree.leaves(rep_p)[0].shape[0]
        x, (new_cache["repeat"], auxs) = jax.lax.scan(
            scan_body, x, (rep_p, rep_lora, cache["repeat"]),
            unroll=scan_unroll(n_rep))
        aux_total = aux_total + auxs.sum()
    kinds = _tail_kinds(cfg, pattern, stack_params)
    for i, kind in enumerate(kinds):
        key = f"t{i}"
        x, a, nc = apply_block(cfg, kind, stack_params["tail"][key], x,
                               adapters=(adapters.get("tail") or {}).get(key),
                               positions=positions, mode="prefill",
                               cache=cache["tail"][key], enc_out=enc_out,
                               table=table)
        new_cache["tail"][key] = nc
        aux_total = aux_total + a
    return x, aux_total, new_cache


def decode_stack(cfg, stack_params, cache, x, pos, *, adapters=None,
                 pattern=None, table=None):
    """One-token decode through the stack.  Returns (x, new_cache).

    The layer scan carries the whole stacked ``cache["repeat"]`` and scans
    each layer's index beside its params: every block writes its step into
    its own layer of the one buffer (the attention pools at ``[layer,
    block, offset]``) and reads its layer back from it, so no step builds
    another pool.  Recorded once per trace as the counter
    ``serve.kv_inplace_layers``: the scanned attention layers whose pool
    the step writes in place."""
    pattern = pattern or cfg.block_pattern
    adapters = adapters or {}
    rep_p = stack_params.get("repeat", {})
    rep_lora = adapters.get("repeat") or _empty_like_stack(rep_p)

    def scan_body(carry, xs):
        h, cs = carry
        i, ps, los = xs
        cs = dict(cs)
        for j, kind in enumerate(pattern):
            h, _, cs[f"p{j}"] = apply_block(
                cfg, kind, ps[f"p{j}"], h, adapters=los.get(f"p{j}"),
                mode="decode", cache=cs[f"p{j}"], pos=pos, table=table,
                layer=i)
        return (h, cs), None

    new_cache = {"repeat": {}, "tail": {}}
    if rep_p:
        n_rep = jax.tree.leaves(rep_p)[0].shape[0]
        (x, new_cache["repeat"]), _ = jax.lax.scan(
            scan_body, (x, cache["repeat"]),
            (jnp.arange(n_rep), rep_p, rep_lora), unroll=scan_unroll(n_rep))
        trace.count("serve.kv_inplace_layers",
                    n_rep * sum(k in ("attn", "xattn") for k in pattern))
    kinds = _tail_kinds(cfg, pattern, stack_params)
    for i, kind in enumerate(kinds):
        key = f"t{i}"
        x, _, nc = apply_block(cfg, kind, stack_params["tail"][key], x,
                               adapters=(adapters.get("tail") or {}).get(key),
                               mode="decode",
                               cache=cache["tail"][key], pos=pos, table=table)
        new_cache["tail"][key] = nc
    return x, new_cache


def _empty_like_stack(rep_p):
    """LoRA-free stand-in (no leaves, scans alongside params)."""
    return {k: {} for k in rep_p}


def batched_scan_layout(stack_adapters):
    """Reorder a banked per-request adapter tree for the layer scans.

    ``AdapterBank.gather`` puts the request dim first on every leaf; the
    repeated blocks scan over their layer dim, which must lead.  Swap the
    (request, layer) axes on the "repeat" subtree only — tail leaves carry
    no layer dim and stay request-leading, which is exactly the 3-D
    per-request shape the dispatch layer's batched path expects."""
    if not stack_adapters:
        return stack_adapters
    out = dict(stack_adapters)
    rep = stack_adapters.get("repeat")
    if rep:
        out["repeat"] = jax.tree.map(lambda x: jnp.swapaxes(x, 0, 1), rep)
    return out


def _attach_ids(tree, ids):
    """Insert the request->tenant map into every adapter node of a LAZY bank
    tree: ``{"a", "b"}`` nodes become ``{"a", "b", "ids"}`` — the layout the
    dispatch layer's banked path consumes."""
    def walk(node):
        if isinstance(node, dict):
            if node and set(node) <= {"a", "b"}:
                return {**node, "ids": ids}
            return {k: walk(v) for k, v in node.items()}
        return node
    return walk(tree)


def banked_scan_layout(stack_adapters, ids):
    """Scan layout for a LAZY bank tree (``AdapterBank.requests``): leaves
    stay tenant-stacked ``(K, ...)`` and ``ids`` (B,) maps batch rows to
    tenants.

    Repeat leaves ``(K, layers, ...)`` swap to ``(layers, K, ...)`` so the
    layer scans slice one ``(K, ...)`` bank page per layer; ``ids``
    broadcasts to ``(layers, B)`` so every scan step carries the same
    request map.  The bank itself is never gathered here — each projection
    gathers (or the BGMV kernel's index_map does) from its own ``(K, ...)``
    page."""
    if not stack_adapters:
        return stack_adapters
    out = dict(stack_adapters)
    rep = stack_adapters.get("repeat")
    if rep:
        swapped = jax.tree.map(lambda x: jnp.swapaxes(x, 0, 1), rep)
        n_rep = jax.tree.leaves(swapped)[0].shape[0]
        out["repeat"] = _attach_ids(
            swapped, jnp.broadcast_to(ids, (n_rep,) + ids.shape))
    tail = stack_adapters.get("tail")
    if tail:
        out["tail"] = _attach_ids(tail, ids)
    return out
