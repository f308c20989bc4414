"""Fused multi-adapter BGMV Pallas-TPU kernels for banked LoRA serving.

Multi-tenant serving applies a DIFFERENT adapter to every request row: row i
of ``x`` is served with tenant ``ids[i]``'s (A, B) pair out of a stacked
:class:`~repro.core.lora.AdapterBank`.  The pre-kernel implementation paid
for that twice — a materialized per-request gather (copying every adapter
leaf to a (B, ...) tree each decode step) followed by two unfused batched
einsums on top of the shared base GEMM.

These kernels fuse all of it into one pass over ``x``:

  grid (B, nn, nk), k innermost.  For request row i (block row of x):
    - the A/B BlockSpecs index the STACKED bank leaves by ``ids[i]`` via
      scalar prefetch (``pltpu.PrefetchScalarGridSpec``) — the per-request
      gather happens in the kernel's DMA schedule, no (B, r, k) copy of the
      bank ever materializes in HBM
    - during the n==0 sweep, p[i] += x[i,k] @ A[ids[i],k]^T  (rank-r
      intermediate lives in VMEM scratch)
    - every (n, k) step accumulates out[i,n] += x[i,k] @ W[k,n] (the shared
      base GEMM, fused rather than re-read)
    - at k == nk-1, out[i,n] += p[i] @ B[ids[i],n]^T

Rank masking is free by construction: bank registration stores each tenant's
adapter zero-padded to r_max (``AdapterBank.from_sets``), and zero rank
rows/columns contribute nothing to either rank-r GEMM — mixed-rank banks run
the same kernel at the same cost as uniform-rank ones, no mask multiplies.

Two entry points share one kernel:

  ``bgmv_matmul``  x (B, s, k) — prefill / full-sequence forward, one
                   (s, k) block row per request
  ``bgmv_gemv``    x (B, k)    — single-token decode, run as (B, 1, k) with
                   the unit s dim unpadded (a block equal to the full dim is
                   Mosaic-legal; a (1, bk) block of a 2-D (B, k) is not)

The bank is gamma-free: registration folds every tenant's scaling factor
into its B (``AdapterSet.fold_gamma``), so these kernels have no gamma
parameter — the scale is structurally 1.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.lora_matmul import _quant_w_shapes, dequant_block
from repro.kernels.tiling import LANE, SUBLANE, block, pad_last2, round_up

# kernel block defaults (n, k dims); s and r stay whole in VMEM — serving
# shapes keep both small (s = prompt length or 1, r <= 512 per the paper)
BN, BK = 256, 512


# ------------------------------------------------------------------ kernels

def _bgmv_kernel(ids_ref, x_ref, w_ref, a_ref, b_ref, out_ref, p_ref, *, nk):
    """One request row per i-step; A/B blocks arrive pre-gathered by the
    ids-indexed BlockSpecs.  Mirrors lora_matmul's accumulation schedule."""
    del ids_ref  # consumed by the index_maps, not the body
    n = pl.program_id(1)
    k = pl.program_id(2)

    @pl.when((n == 0) & (k == 0))
    def _init_p():
        p_ref[...] = jnp.zeros_like(p_ref)

    @pl.when(k == 0)
    def _init_out():
        out_ref[...] = jnp.zeros_like(out_ref)

    xb = x_ref[0].astype(jnp.float32)           # (s, bk)

    @pl.when(n == 0)
    def _acc_p():   # p += x[i,k] @ A[ids[i],k]^T       (A block (1, r, bk))
        p_ref[...] += xb @ a_ref[0].astype(jnp.float32).T

    out_ref[0] += xb @ w_ref[...].astype(jnp.float32)

    @pl.when(k == nk - 1)
    def _apply_lora():   # out += p @ B[ids[i],n]^T     (B block (1, bn, r))
        out_ref[0] += p_ref[...] @ b_ref[0].astype(jnp.float32).T


def _bgmv_call(x, w, a, b, ids, *, bn, bk, interpret):
    """x (B, s, k) padded, w (k, n) padded, a (K, r, k), b (K, n, r),
    ids (B,) int32 -> (B, s, n) fp32."""
    bsz, s, kdim = x.shape
    n = w.shape[1]
    r = a.shape[1]
    nn, nk = n // bn, kdim // bk
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(bsz, nn, nk),
        in_specs=[
            pl.BlockSpec((1, s, bk), lambda i, j, k, ids: (i, 0, k)),    # x
            pl.BlockSpec((bk, bn), lambda i, j, k, ids: (k, j)),         # w
            pl.BlockSpec((1, r, bk), lambda i, j, k, ids: (ids[i], 0, k)),
            pl.BlockSpec((1, bn, r), lambda i, j, k, ids: (ids[i], j, 0)),
        ],
        out_specs=pl.BlockSpec((1, s, bn), lambda i, j, k, ids: (i, 0, j)),
        scratch_shapes=[pltpu.VMEM((s, r), jnp.float32)],
    )
    return pl.pallas_call(
        functools.partial(_bgmv_kernel, nk=nk),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((bsz, s, n), jnp.float32),
        interpret=interpret,
    )(ids, x, w, a, b)


# ------------------------------------------------------------------ wrappers

def _pad_operands(w, a, b, kdim, n, r):
    bn = block(n, BN, LANE)
    bk = block(kdim, BK, LANE)
    kp, np_ = round_up(kdim, bk), round_up(n, bn)
    rp = round_up(r, SUBLANE)
    w = pad_last2(w, kp, np_)
    a = pad_last2(a, rp, kp)
    b = pad_last2(b, np_, rp)
    return w, a, b, bn, bk, kp, np_


def bgmv_matmul(x, w, a, b, ids, *, interpret: bool = False):
    """y[i] = x[i] @ W + (x[i] @ A[ids[i]]^T) @ B[ids[i]]^T, fused.

    x (B, s, k), w (k, n), a (K, r, k), b (K, n, r), ids (B,) int.
    Returns (B, s, n) in fp32 (the dispatcher casts per its promotion rule).
    Zero-pads every dim to block multiples — zero rows/cols are exact."""
    bsz, s, kdim = x.shape
    n = w.shape[1]
    r = a.shape[1]
    w, a, b, bn, bk, kp, np_ = _pad_operands(w, a, b, kdim, n, r)
    sp = round_up(s, SUBLANE)
    if sp != s or kp != kdim:
        x = jnp.pad(x, ((0, 0), (0, sp - s), (0, kp - kdim)))
    ids = jnp.asarray(ids, jnp.int32)
    y = _bgmv_call(x, w, a, b, ids, bn=bn, bk=bk, interpret=interpret)
    if sp != s or np_ != n:
        y = y[:, :s, :n]
    return y


def bgmv_gemv(x, w, a, b, ids, *, interpret: bool = False):
    """Single-token variant: x (B, k) -> (B, n) fp32, the decode GEMV shape.
    Runs the matmul kernel on x as (B, 1, k) with the unit s dim left
    unpadded: a (1, bk) block equals the array's full s dim, which Mosaic
    accepts, so no request row pads to a sublane multiple."""
    bsz, kdim = x.shape
    n = w.shape[1]
    r = a.shape[1]
    w, a, b, bn, bk, kp, np_ = _pad_operands(w, a, b, kdim, n, r)
    if kp != kdim:
        x = jnp.pad(x, ((0, 0), (0, kp - kdim)))
    ids = jnp.asarray(ids, jnp.int32)
    y = _bgmv_call(x[:, None], w, a, b, ids, bn=bn, bk=bk,
                   interpret=interpret)[:, 0]
    if np_ != n:
        y = y[:, :n]
    return y


# ------------------------------------------------------- quantized variants
#
# Banked serving over a PACKED frozen base (core/quant.py): the shared base
# GEMM dequantizes its (bk, bn) tile in VMEM (lora_matmul.dequant_block)
# while the per-request A/B gather stays exactly as above — adapters are fp
# by the LoRA contract, only the base is packed.

def _bgmv_kernel_q(ids_ref, x_ref, wd_ref, ws_ref, a_ref, b_ref, out_ref,
                   p_ref, *, nk, bits):
    del ids_ref
    n = pl.program_id(1)
    k = pl.program_id(2)

    @pl.when((n == 0) & (k == 0))
    def _init_p():
        p_ref[...] = jnp.zeros_like(p_ref)

    @pl.when(k == 0)
    def _init_out():
        out_ref[...] = jnp.zeros_like(out_ref)

    xb = x_ref[0].astype(jnp.float32)

    @pl.when(n == 0)
    def _acc_p():
        p_ref[...] += xb @ a_ref[0].astype(jnp.float32).T

    out_ref[0] += xb @ dequant_block(wd_ref[...], ws_ref[...], bits)

    @pl.when(k == nk - 1)
    def _apply_lora():
        out_ref[0] += p_ref[...] @ b_ref[0].astype(jnp.float32).T


def _pad_quant_operands(wd, ws, a, b, bits, kdim, n, r):
    """Packed-base twin of :func:`_pad_operands`: data rows pad to kp (int8)
    or kp/2 (int4 nibble pairs), scale rows to 1 / kp/G; zero scales make
    the padding dequantize to exact zeros."""
    bn = block(n, BN, LANE)
    bk = block(kdim, BK, LANE)
    kp, np_ = round_up(kdim, bk), round_up(n, bn)
    rp = round_up(r, SUBLANE)
    if bits == 8:
        wd = pad_last2(wd, kp, np_)
        ws = pad_last2(ws, 1, np_)
    else:
        gsize = (wd.shape[-2] * 2) // ws.shape[-2]
        wd = pad_last2(wd, kp // 2, np_)
        ws = pad_last2(ws, kp // gsize, np_)
    a = pad_last2(a, rp, kp)
    b = pad_last2(b, np_, rp)
    return wd, ws, a, b, bn, bk, kp, np_


def _bgmv_quant_call(x, wd, ws, a, b, ids, *, bits, bn, bk, interpret):
    """x (B, s, k) padded, wd/ws padded per :func:`_pad_quant_operands`,
    a (K, r, k), b (K, n, r), ids (B,) int32 -> (B, s, n) fp32."""
    bsz, s, kp = x.shape
    np_ = wd.shape[-1]
    r = a.shape[1]
    gsize = 0 if bits == 8 else kp // ws.shape[-2]
    bwd, bws = _quant_w_shapes(bits, gsize, bk, bn)
    nn, nk = np_ // bn, kp // bk
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(bsz, nn, nk),
        in_specs=[
            pl.BlockSpec((1, s, bk), lambda i, j, k, ids: (i, 0, k)),
            pl.BlockSpec(bwd, lambda i, j, k, ids: (k, j)),
            (pl.BlockSpec(bws, lambda i, j, k, ids: (0, j)) if bits == 8
             else pl.BlockSpec(bws, lambda i, j, k, ids: (k, j))),
            pl.BlockSpec((1, r, bk), lambda i, j, k, ids: (ids[i], 0, k)),
            pl.BlockSpec((1, bn, r), lambda i, j, k, ids: (ids[i], j, 0)),
        ],
        out_specs=pl.BlockSpec((1, s, bn), lambda i, j, k, ids: (i, 0, j)),
        scratch_shapes=[pltpu.VMEM((s, r), jnp.float32)],
    )
    return pl.pallas_call(
        functools.partial(_bgmv_kernel_q, nk=nk, bits=bits),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((bsz, s, np_), jnp.float32),
        interpret=interpret,
    )(ids, x, wd, ws, a, b)


def bgmv_matmul_quant(x, wd, ws, a, b, ids, *, bits, interpret: bool = False):
    """:func:`bgmv_matmul` over a packed base: x (B, s, k), wd/ws per
    ``dequant_block``, a (K, r, k), b (K, n, r), ids (B,) -> (B, s, n)."""
    bsz, s, kdim = x.shape
    n = wd.shape[-1]
    r = a.shape[1]
    wd, ws, a, b, bn, bk, kp, np_ = _pad_quant_operands(
        wd, ws, a, b, bits, kdim, n, r)
    sp = round_up(s, SUBLANE)
    if sp != s or kp != kdim:
        x = jnp.pad(x, ((0, 0), (0, sp - s), (0, kp - kdim)))
    ids = jnp.asarray(ids, jnp.int32)
    y = _bgmv_quant_call(x, wd, ws, a, b, ids, bits=bits, bn=bn, bk=bk,
                         interpret=interpret)
    if sp != s or np_ != n:
        y = y[:, :s, :n]
    return y


def bgmv_gemv_quant(x, wd, ws, a, b, ids, *, bits, interpret: bool = False):
    """Single-token packed-base variant: x (B, k) -> (B, n) fp32, run as
    (B, 1, k) through the matmul kernel like :func:`bgmv_gemv`."""
    bsz, kdim = x.shape
    n = wd.shape[-1]
    r = a.shape[1]
    wd, ws, a, b, bn, bk, kp, np_ = _pad_quant_operands(
        wd, ws, a, b, bits, kdim, n, r)
    if kp != kdim:
        x = jnp.pad(x, ((0, 0), (0, kp - kdim)))
    ids = jnp.asarray(ids, jnp.int32)
    y = _bgmv_quant_call(x[:, None], wd, ws, a, b, ids, bits=bits, bn=bn,
                         bk=bk, interpret=interpret)[:, 0]
    if np_ != n:
        y = y[:, :n]
    return y


def bgmv_reference(x, w, a, b, ids):
    """Pure-jnp oracle: gather + batched einsum — operation-for-operation the
    pre-kernel materialized path, so the reference tier stays bit-identical
    to what shipped before the fused tier existed."""
    y = x @ w
    xa = jnp.einsum("bsk,brk->bsr", x, jnp.take(a, ids, axis=0))  # lint: disable=R5 -- oracle runs under trace; ids validated at the serve host boundary (check_adapter_ids)
    return y + jnp.einsum("bsr,bor->bso", xa, jnp.take(b, ids, axis=0))  # lint: disable=R5 -- same host-boundary check as the gather above
