"""xLSTM blocks (arXiv:2405.04517): mLSTM (matrix memory, parallel train form,
recurrent decode form) and sLSTM (scalar memory, sequential scan).

TPU adaptation: mLSTM trains with the stabilized parallel (attention-like)
formulation — an O(T^2) einsum that maps onto the MXU — and decodes with the
O(1) recurrent matrix-memory update.  sLSTM is inherently sequential (hidden-
state feedback through nonlinearities) and runs as ``jax.lax.scan``.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.models import scan_unroll


# ================================================================== mLSTM

def mlstm_params(cfg, key):
    d = cfg.d_model
    h = cfg.num_heads
    hd = cfg.head_dim
    pdt = jnp.dtype(cfg.param_dtype)
    ks = jax.random.split(key, 7)
    s = d ** -0.5
    return {
        "q": (jax.random.normal(ks[0], (d, h * hd)) * s).astype(pdt),
        "k": (jax.random.normal(ks[1], (d, h * hd)) * s).astype(pdt),
        "v": (jax.random.normal(ks[2], (d, h * hd)) * s).astype(pdt),
        "w_i": (jax.random.normal(ks[3], (d, h)) * s).astype(pdt),
        "w_f": (jax.random.normal(ks[4], (d, h)) * s).astype(pdt),
        "b_f": jnp.full((h,), 3.0, pdt),        # bias toward remembering
        "o": (jax.random.normal(ks[5], (h * hd, d)) * (h * hd) ** -0.5).astype(pdt),
        "ogate": (jax.random.normal(ks[6], (d, h * hd)) * s).astype(pdt),
    }


def _mlstm_qkv(cfg, params, x, adapters):
    from repro.models.layers import linear
    b, s, _ = x.shape
    h, hd = cfg.num_heads, cfg.head_dim
    q = linear(x, params["q"], (adapters or {}).get("q")).reshape(b, s, h, hd)
    k = linear(x, params["k"], (adapters or {}).get("k")).reshape(b, s, h, hd)
    v = linear(x, params["v"], (adapters or {}).get("v")).reshape(b, s, h, hd)
    return (q.astype(jnp.float32), k.astype(jnp.float32) * hd ** -0.5,
            v.astype(jnp.float32))


MLSTM_CHUNK = 256


def _mlstm_fullseq(cfg, params, x, adapters=None, carry0=None):
    """Stabilized chunkwise-parallel form: within-chunk O(C^2) on the MXU,
    across-chunk recurrent matrix-memory carry (scan).  x (b,s,d).
    Returns (out, final_carry) — the carry after the LAST REAL token, so
    prefill can hand it to the recurrent decode form as the cache."""
    b, s, d = x.shape
    h, hd = cfg.num_heads, cfg.head_dim
    q, k, v = _mlstm_qkv(cfg, params, x, adapters)
    xf = x.astype(jnp.float32)
    log_i = xf @ params["w_i"].astype(jnp.float32)                       # (b,s,h)
    log_f = jax.nn.log_sigmoid(xf @ params["w_f"].astype(jnp.float32)
                               + params["b_f"].astype(jnp.float32))

    c = min(MLSTM_CHUNK, s)
    pad = (-s) % c
    if pad:
        zpad = lambda a: jnp.pad(a, ((0, 0), (0, pad)) + ((0, 0),) * (a.ndim - 2))
        q, k, v, log_f = map(zpad, (q, k, v, log_f))
        # identity-safe padding: pad forget gates decay 0 (log_f = 0) and
        # pad input gates -inf (log_i = -1e30), so pad tokens neither decay
        # nor write the carried state — the final carry is exactly the state
        # after the last real token.  Real-position outputs are causal and
        # unaffected either way; pad outputs are sliced off.
        log_i = jnp.pad(log_i, ((0, 0), (0, pad), (0, 0)),
                        constant_values=-1e30)
    nc = q.shape[1] // c
    chunked = lambda a: a.reshape(b, nc, c, *a.shape[2:]).swapaxes(0, 1)
    qc, kc, vc, lic, lfc = map(chunked, (q, k, v, log_i, log_f))

    causal = jnp.tril(jnp.ones((c, c), bool))

    def chunk_step(carry, xs):
        C_st, n_st, m_st = carry                     # (b,h,hd,hd) (b,h,hd) (b,h)
        qb, kb, vb, li, lf = xs                      # (b,c,h,hd) ... (b,c,h)
        bacc = jnp.cumsum(lf, axis=1)                # (b,c,h)
        total = bacc[:, -1]                          # (b,h)
        # intra-chunk decay matrix D[t,u] = bacc[t]-bacc[u]+li[u]
        dmat = bacc[:, :, None, :] - bacc[:, None, :, :] + li[:, None, :, :]
        dmat = jnp.where(causal[None, :, :, None], dmat, -jnp.inf)
        m_intra = jnp.max(dmat, axis=2)              # (b,c,h)
        m_inter = m_st[:, None, :] + bacc            # (b,c,h)
        m_t = jnp.maximum(jnp.maximum(m_intra, m_inter), -1e30)
        w = jnp.where(jnp.isfinite(dmat), jnp.exp(dmat - m_t[:, :, None, :]), 0.0)
        sc = jnp.einsum("bthd,buhd->btuh", qb, kb) * w
        num = jnp.einsum("btuh,buhd->bthd", sc, vb)
        nvec = jnp.einsum("btuh,buhd->bthd", w, kb)
        inter_w = jnp.exp(m_inter - m_t)             # (b,c,h)
        num = num + inter_w[..., None] * jnp.einsum("bthd,bhde->bthe", qb, C_st)
        nvec = nvec + inter_w[..., None] * n_st[:, None]
        den = jnp.maximum(jnp.abs(jnp.einsum("bthd,bthd->bth", qb, nvec)),
                          jnp.exp(-m_t))
        out = num / den[..., None]                   # (b,c,h,hd)
        # ---- state update to end of chunk
        key_d = li + total[:, None] - bacc           # (b,c,h)
        m_new = jnp.maximum(m_st + total, jnp.max(key_d, axis=1))
        kw = jnp.exp(key_d - m_new[:, None])         # (b,c,h)
        carry_w = jnp.exp(m_st + total - m_new)      # (b,h)
        C_new = (carry_w[..., None, None] * C_st +
                 jnp.einsum("buh,buhd,buhe->bhde", kw, kb, vb))
        n_new = carry_w[..., None] * n_st + jnp.einsum("buh,buhd->bhd", kw, kb)
        return (C_new, n_new, m_new), out

    if carry0 is None:
        carry0 = (jnp.zeros((b, h, hd, hd), jnp.float32),
                  jnp.zeros((b, h, hd), jnp.float32),
                  jnp.full((b, h), -1e30, jnp.float32))
    # NOTE: deliberately NOT unrolled under FULL_UNROLL — at 32k tokens the
    # 128-chunk unroll explodes compile time, and the intra-chunk O(C^2) part
    # it would make countable is <=5% of mLSTM flops (projections dominate).
    # The dry-run calibration documents this as a known <=5% undercount.
    carry, outs = jax.lax.scan(jax.checkpoint(chunk_step), carry0,
                               (qc, kc, vc, lic, lfc))
    out = outs.swapaxes(0, 1).reshape(b, nc * c, h, hd)[:, :s]
    ogate = jax.nn.sigmoid(xf @ params["ogate"].astype(jnp.float32))
    out = out.reshape(b, s, -1) * ogate
    return (out @ params["o"].astype(jnp.float32)).astype(x.dtype), carry


def mlstm_apply_fullseq(cfg, params, x, adapters=None):
    return _mlstm_fullseq(cfg, params, x, adapters)[0]


def mlstm_apply_prefill(cfg, params, x, cache, positions, adapters=None):
    """Whole-prompt mLSTM continuing from ``cache``; the chunk scan's final
    carry (exact thanks to identity-safe padding) becomes the decode cache."""
    out, (C, n, m) = _mlstm_fullseq(
        cfg, params, x, adapters, carry0=(cache["C"], cache["n"], cache["m"]))
    return out, {"C": C, "n": n, "m": m}


def mlstm_init_state(cfg, batch, dtype):
    h, hd = cfg.num_heads, cfg.head_dim
    return {"C": jnp.zeros((batch, h, hd, hd), jnp.float32),
            "n": jnp.zeros((batch, h, hd), jnp.float32),
            "m": jnp.full((batch, h), -1e30, jnp.float32)}


def mlstm_apply_decode(cfg, params, x, cache, pos, adapters=None):
    """Recurrent matrix-memory step.  x (b,1,d)."""
    b = x.shape[0]
    q, k, v = _mlstm_qkv(cfg, params, x, adapters)
    q, k, v = q[:, 0], k[:, 0], v[:, 0]                                   # (b,h,hd)
    xf = x[:, 0].astype(jnp.float32)
    log_i = xf @ params["w_i"].astype(jnp.float32)                        # (b,h)
    log_f = jax.nn.log_sigmoid(xf @ params["w_f"].astype(jnp.float32)
                               + params["b_f"].astype(jnp.float32))
    m_new = jnp.maximum(log_f + cache["m"], log_i)
    fw = jnp.exp(log_f + cache["m"] - m_new)[..., None]
    iw = jnp.exp(log_i - m_new)[..., None]
    c_new = fw[..., None] * cache["C"] + iw[..., None] * (k[..., :, None] *
                                                          v[..., None, :])
    n_new = fw * cache["n"] + iw * k
    num = jnp.einsum("bhd,bhde->bhe", q, c_new)
    den = jnp.maximum(jnp.abs(jnp.einsum("bhd,bhd->bh", q, n_new)),
                      jnp.exp(-m_new))
    out = num / den[..., None]
    ogate = jax.nn.sigmoid(xf @ params["ogate"].astype(jnp.float32))
    out = out.reshape(b, -1) * ogate
    y = (out @ params["o"].astype(jnp.float32)).astype(x.dtype)
    return y[:, None, :], {"C": c_new, "n": n_new, "m": m_new}


# ================================================================== sLSTM

def slstm_params(cfg, key):
    d = cfg.d_model
    nh = cfg.slstm_num_heads
    hd = d // nh
    pdt = jnp.dtype(cfg.param_dtype)
    ks = jax.random.split(key, 9)
    s = d ** -0.5
    sr = hd ** -0.5
    p = {}
    for name, kk in zip(("z", "i", "f", "o"), ks[:4]):
        p[f"w_{name}"] = (jax.random.normal(kk, (d, d)) * s).astype(pdt)
    for name, kk in zip(("z", "i", "f", "o"), ks[4:8]):
        p[f"r_{name}"] = (jax.random.normal(kk, (nh, hd, hd)) * sr).astype(pdt)
    p["b_f"] = jnp.full((d,), 3.0, pdt)
    p["w_proj"] = (jax.random.normal(ks[8], (d, d)) * s).astype(pdt)
    return p


def _slstm_step(cfg, params, carry, x_t):
    """carry: (h (b,d), c, n, m); x_t: pre-projected gates (b, 4, d)."""
    nh = cfg.slstm_num_heads
    h, c, n, m = carry
    b, d = h.shape
    hd = d // nh
    hh = h.reshape(b, nh, hd)

    def rec(name):
        return jnp.einsum("bnh,nhk->bnk", hh, params[f"r_{name}"].astype(
            jnp.float32)).reshape(b, d)

    z = jnp.tanh(x_t[:, 0] + rec("z"))
    log_i = x_t[:, 1] + rec("i")
    log_f = jax.nn.log_sigmoid(x_t[:, 2] + rec("f")
                               + params["b_f"].astype(jnp.float32))
    o = jax.nn.sigmoid(x_t[:, 3] + rec("o"))
    m_new = jnp.maximum(log_f + m, log_i)
    iw = jnp.exp(log_i - m_new)
    fw = jnp.exp(log_f + m - m_new)
    c_new = fw * c + iw * z
    n_new = jnp.maximum(fw * n + iw, 1e-6)
    h_new = o * c_new / n_new
    return (h_new, c_new, n_new, m_new), h_new


def _slstm_gate_inputs(params, x):
    xf = x.astype(jnp.float32)
    gates = [xf @ params[f"w_{g}"].astype(jnp.float32) for g in ("z", "i", "f", "o")]
    return jnp.stack(gates, axis=-2)          # (b, s, 4, d)


def _slstm_fullseq(cfg, params, x, adapters=None, carry=None):
    b, s, d = x.shape
    gi = _slstm_gate_inputs(params, x)
    if adapters is not None and "z" in adapters:
        # gate-input adapter (prepared form: scale already folded into B)
        za, zb = adapters["z"]["a"], adapters["z"]["b"]
        gi = gi.at[:, :, 0].add((x @ za.T) @ zb.T)
    if carry is None:
        carry = (jnp.zeros((b, d), jnp.float32),) * 2 + (
            jnp.full((b, d), 1e-6, jnp.float32),
            jnp.full((b, d), -1e30, jnp.float32))
    step = lambda c, xt: _slstm_step(cfg, params, c, xt)
    carry, hs = jax.lax.scan(step, carry, jnp.swapaxes(gi, 0, 1))
    h = jnp.swapaxes(hs, 0, 1)                # (b, s, d)
    return (h @ params["w_proj"].astype(jnp.float32)).astype(x.dtype), carry


def slstm_apply_fullseq(cfg, params, x, adapters=None):
    return _slstm_fullseq(cfg, params, x, adapters)[0]


def slstm_apply_prefill(cfg, params, x, cache, positions, adapters=None):
    """Whole-prompt sLSTM continuing from ``cache``; the scan carry IS the
    decode cache, so prefill-then-decode matches the sequential path."""
    out, (h, c, n, m) = _slstm_fullseq(
        cfg, params, x, adapters,
        carry=(cache["h"], cache["c"], cache["n"], cache["m"]))
    return out, {"h": h, "c": c, "n": n, "m": m}


def slstm_init_state(cfg, batch, dtype):
    d = cfg.d_model
    z = jnp.zeros((batch, d), jnp.float32)
    return {"h": z, "c": z, "n": jnp.full((batch, d), 1e-6, jnp.float32),
            "m": jnp.full((batch, d), -1e30, jnp.float32)}


def slstm_apply_decode(cfg, params, x, cache, pos, adapters=None):
    gi = _slstm_gate_inputs(params, x)[:, 0]  # (b, 4, d)
    carry = (cache["h"], cache["c"], cache["n"], cache["m"])
    (h, c, n, m), _ = _slstm_step(cfg, params, carry, gi)
    y = (h @ params["w_proj"].astype(jnp.float32)).astype(x.dtype)
    return y[:, None, :], {"h": h, "c": c, "n": n, "m": m}
