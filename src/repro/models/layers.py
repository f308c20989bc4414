"""Shared neural-net building blocks (pure functions over explicit pytrees)."""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

from repro.kernels.dispatch import lora_linear


def rms_norm(x, scale, eps: float = 1e-6):
    var = jnp.mean(jnp.square(x.astype(jnp.float32)), axis=-1, keepdims=True)
    y = x.astype(jnp.float32) * jax.lax.rsqrt(var + eps)
    return (y * scale.astype(jnp.float32)).astype(x.dtype)


def layer_norm(x, scale, bias, eps: float = 1e-5):
    xf = x.astype(jnp.float32)
    mu = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.var(xf, axis=-1, keepdims=True)
    y = (xf - mu) * jax.lax.rsqrt(var + eps)
    return (y * scale.astype(jnp.float32) + bias.astype(jnp.float32)).astype(x.dtype)


def apply_norm(cfg, x, params, prefix: str):
    if cfg.norm == "layernorm":
        return layer_norm(x, params[f"{prefix}_scale"], params[f"{prefix}_bias"])
    return rms_norm(x, params[f"{prefix}_scale"])


def norm_params(cfg, d: int, prefix: str):
    p = {f"{prefix}_scale": jnp.ones((d,), _pdt(cfg))}
    if cfg.norm == "layernorm":
        p[f"{prefix}_bias"] = jnp.zeros((d,), _pdt(cfg))
    return p


def _pdt(cfg):
    return jnp.dtype(cfg.param_dtype)


# --------------------------------------------------------------------------- RoPE

def rope_angles(positions, head_dim: int, theta: float):
    """positions (..., s) int -> cos/sin (..., s, head_dim//2) float32."""
    half = head_dim // 2
    freqs = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = positions.astype(jnp.float32)[..., None] * freqs
    return jnp.cos(ang), jnp.sin(ang)


def apply_rope(x, positions, theta: float):
    """x (..., s, h, hd); positions broadcastable to (..., s)."""
    cos, sin = rope_angles(positions, x.shape[-1], theta)
    cos = cos[..., None, :]   # (..., s, 1, half)
    sin = sin[..., None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


def sinusoidal_embedding(length: int, dim: int):
    pos = jnp.arange(length, dtype=jnp.float32)[:, None]
    div = jnp.exp(-jnp.log(10_000.0) * jnp.arange(0, dim, 2, dtype=jnp.float32) / dim)
    emb = jnp.zeros((length, dim), jnp.float32)
    emb = emb.at[:, 0::2].set(jnp.sin(pos * div))
    emb = emb.at[:, 1::2].set(jnp.cos(pos * div))
    return emb


# --------------------------------------------------------------------------- MLP

def mlp_params(cfg, key, d_in: int, d_ff: int):
    k1, k2, k3 = jax.random.split(key, 3)
    s_in = d_in ** -0.5
    s_ff = d_ff ** -0.5
    pdt = _pdt(cfg)
    p = {"w_up": (jax.random.normal(k2, (d_in, d_ff)) * s_in).astype(pdt),
         "w_down": (jax.random.normal(k3, (d_ff, d_in)) * s_ff).astype(pdt)}
    if cfg.mlp_variant in ("swiglu", "geglu"):
        p["w_gate"] = (jax.random.normal(k1, (d_in, d_ff)) * s_in).astype(pdt)
    return p


def mlp_apply(cfg, params, x, adapters=None):
    """Gated MLP.  ``adapters`` reserved for adapter-on-mlp variants.  The
    three GEMMs route through ``linear`` so a quantized frozen base
    (core/quant.py packed leaves) hits the dequant-in-VMEM kernel tier; with
    fp leaves ``linear`` reduces to the same single XLA GEMM as before."""
    up = checkpoint_name(linear(x, params["w_up"]), "mlp_up")
    if cfg.mlp_variant == "swiglu":
        gate = checkpoint_name(linear(x, params["w_gate"]), "mlp_gate")
        h = jax.nn.silu(gate) * up
    elif cfg.mlp_variant == "geglu":
        gate = checkpoint_name(linear(x, params["w_gate"]), "mlp_gate")
        h = jax.nn.gelu(gate, approximate=True) * up
    else:
        h = jax.nn.gelu(up, approximate=True)
    return linear(h, params["w_down"])


def linear(x, w, adapters=None):
    """y = x W (+ (x A^T) B^T) — the LoRA-aware projection primitive.

    ``adapters`` is ``{"a": (r, d_in), "b": (d_out, r)}`` or None — an
    adapter node of an ``AdapterSet`` already in prepared form (the scaling
    factor folded into B, rank mask applied), so the projection itself is
    scale-free.  Routed through ``repro.kernels.dispatch`` so configs with
    ``use_pallas`` hit the fused Pallas kernel (with fused custom-VJP
    backward) instead of three XLA GEMMs; leaves with a leading request dim
    (``AdapterBank.gather``) take the batched multi-tenant path.
    """
    return lora_linear(x, w, adapters, 1.0)
