"""Pallas-TPU paged-attention decode kernel.

Continuous batching stores KV state in a SHARED block pool
(num_blocks, block_size, kh, hd); each request's blocks are named by a row
of the block table (B, blocks_per_req).
The reference tier materializes a request's view with an XLA gather
(``models/attention.py::paged_gather``) — an HBM copy of the whole working
set every decode step.  This kernel never materializes it: the K/V/pos
BlockSpecs index the POOL through the block table via scalar prefetch,

    grid (B, blocks_per_req), j innermost
    k_pool block (1, bs, kh, hd) at index (table[i, j], 0, 0, 0)

— the same ids-indexed DMA-schedule trick the BGMV kernels use for the
adapter bank, applied to cache blocks instead of adapter pages.  Softmax
runs as a flash-style running (m, l, acc) accumulation across a request's
blocks in VMEM scratch, so the per-step working set is one block, not the
virtual ring.

Numerics: the streaming accumulation is mathematically exact but not
bit-identical to the one-shot softmax of the gather path, so the engine
routes here only on the compiled ``pallas`` tier (``dispatch.resolve_mode``)
— interpret/reference-tier serving keeps the gather path.  Parity with
:func:`repro.kernels.ref.paged_attention_ref` is asserted to fp32
tolerance in tests/test_paged.py.

Validity masking needs no extra operand: the pos pool rides along as a
third table-indexed input, and an entry is attendable iff
``0 <= pos <= qpos`` (and within the sliding window) — exactly the gather
path's mask formula.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _paged_attn_kernel(table_ref, qpos_ref, q_ref, k_ref, v_ref, pos_ref,
                       out_ref, acc_ref, m_ref, l_ref, *, mb, window,
                       softcap):
    """One (request, block) cell per grid step; j innermost streams request
    i's blocks through VMEM while (acc, m, l) carry the running softmax."""
    del table_ref  # consumed by the index_maps, not the body
    i = pl.program_id(0)
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    kh, g, hd = acc_ref.shape[0], acc_ref.shape[1], acc_ref.shape[2]
    h = kh * g
    qp = qpos_ref[i]
    q = q_ref[...].astype(jnp.float32).reshape(kh, g, hd)      # (kh, g, hd)
    k = k_ref[0].astype(jnp.float32)                           # (bs, kh, hd)
    v = v_ref[0].astype(jnp.float32)
    pos = pos_ref[0]                                           # (1, bs)

    scores = jnp.einsum("kgd,skd->kgs", q, k) * hd ** -0.5
    if softcap:
        scores = softcap * jnp.tanh(scores / softcap)
    valid = (pos >= 0) & (pos <= qp)
    if window is not None:
        valid &= qp - pos < window
    scores = jnp.where(valid[None], scores, NEG_INF)

    m_prev = m_ref[...]
    m_new = jnp.maximum(m_prev, jnp.max(scores, axis=-1))
    p = jnp.where(scores <= NEG_INF / 2, 0.0,
                  jnp.exp(scores - m_new[..., None]))
    corr = jnp.exp(m_prev - m_new)
    m_ref[...] = m_new
    l_ref[...] = l_ref[...] * corr + p.sum(-1)
    acc_ref[...] = (acc_ref[...] * corr[..., None]
                    + jnp.einsum("kgs,skd->kgd", p, v))

    @pl.when(j == mb - 1)
    def _final():
        out = acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)[..., None]
        out_ref[...] = out.reshape(1, h, hd).astype(out_ref.dtype)


def paged_attention(q, k_pool, v_pool, pos_pool, table, qpos, *, window=None,
                    softcap=None, interpret: bool = False):
    """One-token paged attention: q (B, h, hd), k_pool/v_pool
    (P, bs, kh, hd), pos_pool (P, bs) int32, table (B, mb) int32, qpos (B,)
    int32.  Returns (B, h, hd) in q.dtype.

    The pool blocks a request never owns are never touched: the grid visits
    (i, j) -> pool block table[i, j] only.  Production shapes keep hd a lane
    multiple and bs a sublane multiple; no padding is applied here."""
    b, h, hd = q.shape
    _, bs, kh, _ = k_pool.shape
    mb = table.shape[1]
    g = h // kh
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,                       # (table, qpos)
        grid=(b, mb),
        in_specs=[
            pl.BlockSpec((1, h, hd), lambda i, j, table, qpos: (i, 0, 0)),
            pl.BlockSpec((1, bs, kh, hd),
                         lambda i, j, table, qpos: (table[i, j], 0, 0, 0)),
            pl.BlockSpec((1, bs, kh, hd),
                         lambda i, j, table, qpos: (table[i, j], 0, 0, 0)),
            pl.BlockSpec((1, 1, bs),
                         lambda i, j, table, qpos: (table[i, j], 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, h, hd), lambda i, j, table, qpos: (i, 0, 0)),
        scratch_shapes=[pltpu.VMEM((kh, g, hd), jnp.float32),
                        pltpu.VMEM((kh, g), jnp.float32),
                        pltpu.VMEM((kh, g), jnp.float32)],
    )
    return pl.pallas_call(
        functools.partial(_paged_attn_kernel, mb=mb, window=window,
                          softcap=softcap),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, h, hd), q.dtype),
        interpret=interpret,
    )(jnp.asarray(table, jnp.int32), jnp.asarray(qpos, jnp.int32),
      q, k_pool, v_pool, pos_pool.reshape(-1, 1, bs))
