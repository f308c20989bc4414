"""RG-LRU recurrent block (RecurrentGemma / Griffin, arXiv:2402.19427).

TPU adaptation: the diagonal linear recurrence h_t = a_t*h_{t-1} + b_t runs as
``jax.lax.associative_scan`` (log-depth, MXU/VPU friendly) instead of a
sequential CUDA scan.  Decode keeps O(1) state: the recurrence hidden plus a
(width-1) causal-conv tail.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

CONV_WIDTH = 4
_C = 8.0  # RG-LRU gate sharpness constant


def rglru_params(cfg, key):
    d = cfg.d_model
    dr = cfg.rglru_d_state or d
    pdt = jnp.dtype(cfg.param_dtype)
    ks = jax.random.split(key, 6)
    s = d ** -0.5
    sr = dr ** -0.5
    # Lambda init so that a = sigmoid(lam)^c in [0.9, 0.999]
    u = jax.random.uniform(ks[5], (dr,), jnp.float32, 0.9, 0.999)
    lam = jnp.log(u ** (1.0 / _C) / (1 - u ** (1.0 / _C)))
    return {
        "wx": (jax.random.normal(ks[0], (d, dr)) * s).astype(pdt),     # x branch
        "wy": (jax.random.normal(ks[1], (d, dr)) * s).astype(pdt),     # gate branch
        "conv": (jax.random.normal(ks[2], (CONV_WIDTH, dr)) *
                 CONV_WIDTH ** -0.5).astype(pdt),
        "w_a": (jax.random.normal(ks[3], (dr, dr)) * sr).astype(pdt),  # recurrence gate
        "w_i": (jax.random.normal(ks[4], (dr, dr)) * sr).astype(pdt),  # input gate
        "lam": lam.astype(pdt),
        "w_out": (jax.random.normal(ks[2], (dr, d)) * sr).astype(pdt),
    }


def _causal_conv(x, w, tail=None):
    """Depthwise causal conv, width W.  x (b,s,dr), w (W,dr).
    ``tail`` (b, W-1, dr) are the trailing inputs from previous steps."""
    width = w.shape[0]
    if tail is None:
        tail = jnp.zeros((x.shape[0], width - 1, x.shape[-1]), x.dtype)
    xp = jnp.concatenate([tail, x], axis=1)
    out = sum(xp[:, i:i + x.shape[1]] * w[i] for i in range(width))
    new_tail = xp[:, -(width - 1):]
    return out, new_tail


def _gates(params, xb):
    """a_t (recurrence coeff) and gated input, elementwise over (.., dr)."""
    r = jax.nn.sigmoid(xb @ params["w_a"])
    i = jax.nn.sigmoid(xb @ params["w_i"])
    log_a = -_C * r * jax.nn.softplus(-params["lam"].astype(jnp.float32))
    a = jnp.exp(log_a)
    b = jnp.sqrt(jnp.maximum(1.0 - jnp.exp(2.0 * log_a), 1e-6)) * (i * xb)
    return a, b


def rglru_scan(a, b, h0=None):
    """Associative scan of h_t = a_t h_{t-1} + b_t over axis 1. a,b (b,s,dr)."""
    if h0 is not None:
        b = b.at[:, 0].add(a[:, 0] * h0)
    def comb(l, r):
        (al, bl), (ar, br) = l, r
        return al * ar, bl * ar + br
    _, h = jax.lax.associative_scan(comb, (a, b), axis=1)
    return h


def rglru_apply_fullseq(cfg, params, x, adapters=None):
    """x (b,s,d) -> (b,s,d).  LoRA (if given) adapts wx / wy projections."""
    from repro.models.layers import linear
    xb = linear(x, params["wx"], (adapters or {}).get("wx"))
    yb = linear(x, params["wy"], (adapters or {}).get("wy"))
    xb, _ = _causal_conv(xb, params["conv"])
    xf = xb.astype(jnp.float32)
    a, b = _gates(params, xf)
    h = rglru_scan(a, b)
    out = h * jax.nn.gelu(yb.astype(jnp.float32), approximate=True)
    return (out @ params["w_out"].astype(jnp.float32)).astype(x.dtype)


def rglru_init_state(cfg, batch, dtype):
    dr = cfg.rglru_d_state or cfg.d_model
    return {"h": jnp.zeros((batch, dr), jnp.float32),
            "conv_tail": jnp.zeros((batch, CONV_WIDTH - 1, dr), dtype)}


def rglru_apply_prefill(cfg, params, x, cache, positions, adapters=None):
    """Whole-prompt RG-LRU that also returns the decode cache: the
    associative scan continues from ``cache`` (h0 + conv tail), and the
    final recurrence state / trailing conv inputs become the new cache —
    one batched pass instead of s sequential decode steps."""
    from repro.models.layers import linear
    xb = linear(x, params["wx"], (adapters or {}).get("wx"))
    yb = linear(x, params["wy"], (adapters or {}).get("wy"))
    xb, new_tail = _causal_conv(xb, params["conv"], cache["conv_tail"])
    xf = xb.astype(jnp.float32)
    a, b = _gates(params, xf)
    h = rglru_scan(a, b, h0=cache["h"])
    out = h * jax.nn.gelu(yb.astype(jnp.float32), approximate=True)
    y = (out @ params["w_out"].astype(jnp.float32)).astype(x.dtype)
    return y, {"h": h[:, -1], "conv_tail": new_tail}


def rglru_apply_decode(cfg, params, x, cache, pos, adapters=None):
    """One-token step.  x (b,1,d)."""
    from repro.models.layers import linear
    xb = linear(x, params["wx"], (adapters or {}).get("wx"))
    yb = linear(x, params["wy"], (adapters or {}).get("wy"))
    xb, new_tail = _causal_conv(xb, params["conv"], cache["conv_tail"])
    xf = xb[:, 0].astype(jnp.float32)
    a, b = _gates(params, xf)
    h = a * cache["h"] + b
    out = h * jax.nn.gelu(yb[:, 0].astype(jnp.float32), approximate=True)
    y = (out @ params["w_out"].astype(jnp.float32)).astype(x.dtype)
    return y[:, None, :], {"h": h, "conv_tail": new_tail}
