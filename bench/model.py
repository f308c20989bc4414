"""A configuration file turned into what the system under test runs, and the
weights and adapters the benchmark makes for it from ``--seed``.

The benchmark makes every weight itself, on the device, in one jitted call:
the program's own ``init`` is used only through ``jax.eval_shape`` to learn
the layout its functions take.  The reference (``reference.py``) reads the
same arrays; nothing it compares against was made by the program.
"""
from __future__ import annotations

import json
import os

import jax
import jax.numpy as jnp

HERE = os.path.dirname(os.path.abspath(__file__))

# configuration-file key -> the program's ModelConfig field
_FIELDS = {"hidden_size": "d_model", "intermediate_size": "d_ff",
           "num_hidden_layers": "num_layers",
           "num_attention_heads": "num_heads",
           "num_key_value_heads": "num_kv_heads", "head_dim": "head_dim",
           "vocab_size": "vocab_size", "rope_theta": "rope_theta",
           "norm": "norm", "tie_word_embeddings": "tie_embeddings"}


def load_config(name: str, directory: str | None = None) -> dict:
    with open(os.path.join(directory or os.path.join(HERE, "configs"),
                           name + ".json")) as f:
        return json.load(f)


def program_config(cfg: dict):
    """The program's ModelConfig for a configuration file: the registry
    entry ``cfg["arch"]`` with the file's sizes.  A size that differs from
    the registry's must be listed in the file's ``reduced``, or the run
    stops: the benchmark would otherwise measure another model than the one
    it names."""
    import dataclasses
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
        raise ValueError(f"{cfg['name']}: the reference has no parallel "
                         "residual, window or logit soft-cap")
    return mc


def seed_key(seed: int):
    """A JAX key from a seed of any size (the driver's exceed 32 bits)."""
    seed = int(seed)
    k = jax.random.key(seed & 0xFFFFFFFF)
    return jax.random.fold_in(k, (seed >> 32) & 0xFFFFFFFF)


def _leaf_init(path, shape, dtype, key):
    name = str(getattr(path[-1], "key", path[-1]))
    if name.endswith("_scale"):
        return (1.0 + 0.1 * jax.random.normal(key, shape)).astype(dtype)
    if name.endswith("_bias"):
        return (0.1 * jax.random.normal(key, shape)).astype(dtype)
    fan_in = shape[-1] if name == "embed" else shape[-2]
    return (jax.random.normal(key, shape) * fan_in ** -0.5).astype(dtype)


def make_params(model, key):
    """Base weights in the program's layout, made on the device in one jitted
    call.  Norm scales are 1 + 0.1 N(0, 1) and biases 0.1 N(0, 1), so a path
    that skipped one would show; matrices are N(0, 1/fan_in)."""
    shapes = jax.eval_shape(model.init, jax.random.key(0))
    leaves, treedef = jax.tree_util.tree_flatten_with_path(shapes)

    @jax.jit
    def init(key):
        return jax.tree_util.tree_unflatten(treedef, [
            _leaf_init(path, s.shape, s.dtype, jax.random.fold_in(key, i))
            for i, (path, s) in enumerate(leaves)])

    return init(key)


def lora_shapes(cfg: dict, rank: int, targets, lead=()):
    """{target: {"a": lead + (L, r, d_in), "b": lead + (L, d_out, r)}}."""
    d, L = cfg["hidden_size"], cfg["num_hidden_layers"]
    hd = cfg["head_dim"]
    out = {"q": cfg["num_attention_heads"] * hd,
           "v": cfg["num_key_value_heads"] * hd}
    return {t: {"a": lead + (L, rank, d), "b": lead + (L, out[t], rank)}
            for t in targets}


def make_lora(cfg: dict, key, *, rank: int, targets, a_std: float,
              b_std: float, lead=(), shared_lead: bool = False):
    """Adapter weights {target: {"a", "b"}} with leading dims ``lead``.
    ``shared_lead``: every index of the leading dims gets the same values
    (a federated job whose clients start from one adapter)."""
    shapes = lora_shapes(cfg, rank, targets, () if shared_lead else lead)

    @jax.jit
    def init(key):
        out = {}
        for i, t in enumerate(sorted(shapes)):
            ka, kb = jax.random.split(jax.random.fold_in(key, i))
            a = a_std * jax.random.normal(ka, shapes[t]["a"], jnp.float32)
            b = b_std * jax.random.normal(kb, shapes[t]["b"], jnp.float32)
            if shared_lead:
                a = jnp.broadcast_to(a, lead + a.shape)
                b = jnp.broadcast_to(b, lead + b.shape)
            out[t] = {"a": a, "b": b}
        return out

    return init(key)


def program_lora(lora: dict) -> dict:
    """The same adapters in the program's tree layout."""
    return {"stack": {"repeat": {"p0": {"attn": lora}}}}
