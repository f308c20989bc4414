"""Jit'd wrappers over the Pallas kernels — the public kernel API.

On a TPU backend the kernels compile through Mosaic.  On any other backend
they run under the Pallas interpreter (Python emulation: the kernels'
numerics at Python speed, for tests and debugging — never a timing).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.flash_attention import flash_attention
from repro.kernels.lora_matmul import lora_matmul
from repro.kernels.rglru_scan import rglru_scan_pallas


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


@functools.partial(jax.jit, static_argnames=("gamma",))
def fused_lora_matmul(x, w, a, b, gamma: float):
    """Batched fused y = x@W + gamma*(x A^T) B^T; x (..., m, k)."""
    x2 = x.reshape(-1, x.shape[-1])
    out = lora_matmul(x2, w, a, b, gamma, interpret=_interpret())
    return out.reshape(*x.shape[:-1], w.shape[1])


@functools.partial(jax.jit, static_argnames=("causal", "window"))
def flash_mha(q, k, v, *, causal=True, window=None):
    """q (b, s, h, d), k/v (b, t, kh, d) with GQA expansion. -> (b, s, h, d)."""
    b, s, h, d = q.shape
    t, kh = k.shape[1], k.shape[2]
    if kh != h:
        rep = h // kh
        k = jnp.repeat(k, rep, axis=2)
        v = jnp.repeat(v, rep, axis=2)
    qf = q.transpose(0, 2, 1, 3).reshape(b * h, s, d)
    kf = k.transpose(0, 2, 1, 3).reshape(b * h, t, d)
    vf = v.transpose(0, 2, 1, 3).reshape(b * h, t, d)
    o = flash_attention(qf, kf, vf, causal=causal, window=window,
                        interpret=_interpret())
    return o.reshape(b, h, s, d).transpose(0, 2, 1, 3)


@jax.jit
def rglru_scan_op(a, b):
    """Diagonal linear recurrence h_t = a_t h_{t-1} + b_t; a, b (bt, s, d)."""
    return rglru_scan_pallas(a, b, interpret=_interpret())
