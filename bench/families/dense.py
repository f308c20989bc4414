"""The dense family: a decoder of identical pre-norm layers (causal
grouped-query attention with rotary positions and optional q/k RMS norm, a
SwiGLU MLP), an embedding and an untied head, with LoRA on the projections a
job adapts.  The interface each family provides is in ``__init__.py``.

The reference below is straightforward ``jax.numpy``: no kernel, cache or
batching of the system under test, and nothing of the program imported.
Where ``precision.matmul_operand_bytes`` is 2 a matrix product takes its
float32 operands as bfloat16 and sums in float32 (one pass of the TPU's
matrix unit).  The work counts are ``flops.py``'s, which count this layer.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from flops import decode_step_work, train_step_flops  # noqa: F401

# configuration-file key -> the program's ModelConfig field
_FIELDS = {"hidden_size": "d_model", "intermediate_size": "d_ff",
           "num_hidden_layers": "num_layers",
           "num_attention_heads": "num_heads",
           "num_key_value_heads": "num_kv_heads", "head_dim": "head_dim",
           "vocab_size": "vocab_size", "rope_theta": "rope_theta",
           "norm": "norm", "tie_word_embeddings": "tie_embeddings"}


def program_config(cfg: dict):
    """The program's ModelConfig for a configuration file: the registry
    entry ``cfg["arch"]`` with the file's sizes.  A size that differs from
    the registry's must be listed in the file's ``reduced``, or the run
    stops: the benchmark would otherwise measure another model than the one
    it names."""
    from repro.configs import get_config
    registry = get_config(cfg["arch"])
    changed = [k for k, f in _FIELDS.items()
               if getattr(registry, f) != cfg[k]]
    if set(changed) - set(cfg["reduced"]):
        raise ValueError(f"{cfg['name']}: {sorted(set(changed))} differ from "
                         f"the program's {cfg['arch']} but are not in "
                         f"'reduced' {cfg['reduced']}")
    mc = dataclasses.replace(
        registry, param_dtype=cfg["torch_dtype"], dtype=cfg["torch_dtype"],
        **{f: cfg[k] for k, f in _FIELDS.items()})
    if mc.qk_norm != bool(cfg.get("qk_norm", False)):
        raise ValueError(f"{cfg['name']}: qk_norm differs from the program")
    if mc.mlp_variant != "swiglu" or cfg["hidden_act"] != "silu":
        raise ValueError(f"{cfg['name']}: the reference computes a SwiGLU MLP")
    if mc.parallel_residual or mc.attn_window or mc.attn_logit_softcap:
        raise ValueError(
            f"{cfg['name']}: the dense family has no parallel residual, "
            "window or logit soft-cap; a configuration with them names a "
            "family that computes them (\"family\": \"<name>\", "
            "bench/families/<name>.py)")
    return mc


def lora_shapes(cfg: dict, rank: int, targets, lead=()):
    """{target: {"a": lead + (L, r, d_in), "b": lead + (L, d_out, r)}}."""
    d, L = cfg["hidden_size"], cfg["num_hidden_layers"]
    hd = cfg["head_dim"]
    out = {"q": cfg["num_attention_heads"] * hd,
           "v": cfg["num_key_value_heads"] * hd}
    return {t: {"a": lead + (L, rank, d), "b": lead + (L, out[t], rank)}
            for t in targets}


def program_lora(lora: dict) -> dict:
    """The same adapters in the program's tree layout."""
    return {"stack": {"repeat": {"p0": {"attn": lora}}}}


def _norm(cfg, x, scale, bias=None):
    if cfg["norm"] == "layernorm":
        mu = jnp.mean(x, -1, keepdims=True)
        var = jnp.mean(jnp.square(x - mu), -1, keepdims=True)
        return (x - mu) / jnp.sqrt(var + cfg["layer_norm_eps"]) * scale + bias
    eps = cfg["rms_norm_eps"]
    return x / jnp.sqrt(jnp.mean(jnp.square(x), -1, keepdims=True) + eps) \
        * scale


def _rope(x, theta):
    """x (b, s, h, hd) at positions 0..s-1, rotating the two halves of every
    head (the file states ``partial_rotary_factor`` 1.0)."""
    s, hd = x.shape[1], x.shape[-1]
    half = hd // 2
    freq = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * freq
    cos = jnp.cos(ang)[None, :, None, :].astype(x.dtype)
    sin = jnp.sin(ang)[None, :, None, :].astype(x.dtype)
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def _dot(cfg, spec, a, b):
    """``einsum(spec, a, b)`` at the stated precision: float32 operands go
    in as bfloat16 where the configuration multiplies in one bfloat16 pass,
    and the products are summed in float32."""
    if a.dtype == jnp.float32 and cfg["precision"]["matmul_operand_bytes"] == 2:
        return jnp.einsum(spec, a.astype(jnp.bfloat16), b.astype(jnp.bfloat16),
                          preferred_element_type=jnp.float32)
    return jnp.einsum(spec, a, b)


def _proj(cfg, x, w, lora, gamma):
    """x W (+ (x A^T) (gamma B)^T), the scale folded into B."""
    y = _dot(cfg, "bsi,io->bso", x, w)
    if lora is not None:
        h = _dot(cfg, "bsi,ri->bsr", x, lora["a"])
        y = y + _dot(cfg, "bsr,or->bso", h, gamma * lora["b"])
    return y


def _layer(cfg, gamma, x, ws):
    p, lora = ws
    lora = lora or {}
    b, s, _ = x.shape
    h, kh, hd = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                 cfg["head_dim"])
    at = p["attn"]
    y = _norm(cfg, x, p["ln1_scale"], p.get("ln1_bias"))
    q = _proj(cfg, y, at["q"], lora.get("q"), gamma).reshape(b, s, h, hd)
    k = _proj(cfg, y, at["k"], lora.get("k"), gamma).reshape(b, s, kh, hd)
    v = _proj(cfg, y, at["v"], lora.get("v"), gamma).reshape(b, s, kh, hd)
    if cfg.get("qk_norm"):
        q = _norm({"norm": "rmsnorm", "rms_norm_eps": cfg["rms_norm_eps"]},
                  q, at["q_norm_scale"])
        k = _norm({"norm": "rmsnorm", "rms_norm_eps": cfg["rms_norm_eps"]},
                  k, at["k_norm_scale"])
    q, k = _rope(q, cfg["rope_theta"]), _rope(k, cfg["rope_theta"])
    k = jnp.repeat(k, h // kh, axis=2)
    v = jnp.repeat(v, h // kh, axis=2)
    scores = _dot(cfg, "bqhd,bkhd->bhqk", q, k) * jnp.asarray(hd ** -0.5,
                                                              x.dtype)
    causal = jnp.tril(jnp.ones((s, s), bool))
    scores = jnp.where(causal, scores, -jnp.inf)
    att = _dot(cfg, "bhqk,bkhd->bqhd", jax.nn.softmax(scores, -1), v)
    x = x + _proj(cfg, att.reshape(b, s, h * hd), at["o"], lora.get("o"),
                  gamma)
    m = p["mlp"]
    y = _norm(cfg, x, p["ln2_scale"], p.get("ln2_bias"))
    up = (jax.nn.silu(_dot(cfg, "bsi,io->bso", y, m["w_gate"]))
          * _dot(cfg, "bsi,io->bso", y, m["w_up"]))
    x = x + _dot(cfg, "bsi,io->bso", up, m["w_down"])
    return x, None


def cast(tree, dtype):
    return jax.tree.map(lambda a: a.astype(dtype), tree)


def forward(cfg, params, tokens, lora=None, gamma=1.0):
    """Logits (b, s, vocab) over the real vocabulary.  ``params`` and
    ``lora`` ({target: {"a": (L, r, d_in), "b": (L, d_out, r)}}) come in the
    dtype the forward computes in."""
    assert cfg.get("partial_rotary_factor", 1.0) == 1.0
    x = params["embed"][tokens]
    layers = params["stack"]["repeat"]["p0"]
    body = jax.checkpoint(lambda x, ws: _layer(cfg, gamma, x, ws))
    x, _ = jax.lax.scan(body, x, (layers, lora))
    x = _norm(cfg, x, params["final_scale"], params.get("final_bias"))
    return _dot(cfg, "bsi,io->bso", x,
                params["lm_head"][:, :cfg["vocab_size"]])


def loss(cfg, params, tokens, lora=None, gamma=1.0):
    """Mean next-token cross-entropy over every row and position, in float32
    whatever the forward's dtype."""
    logits = forward(cfg, params, tokens, lora, gamma)[:, :-1]
    logits = logits.astype(jnp.float32)
    lse = jax.scipy.special.logsumexp(logits, -1)
    tgt = jnp.take_along_axis(logits, tokens[:, 1:, None], -1)[..., 0]
    return jnp.mean(lse - tgt)
