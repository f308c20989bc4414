"""A configuration file turned into what the system under test runs, and the
weights and adapters the benchmark makes for it from ``--seed``.

A configuration's family (``bench/families/``) holds what the benchmark
knows of its architecture: the program's config, the adapter layout, the
reference and the work counts.  The benchmark makes every weight itself, on
the device, in one jitted call: the program's own ``init`` is used only
through ``jax.eval_shape`` to learn the layout its functions take.  The
family's reference reads the same arrays; nothing it compares against was
made by the program.
"""
from __future__ import annotations

import functools
import importlib.util
import json
import os

import jax
import jax.numpy as jnp

HERE = os.path.dirname(os.path.abspath(__file__))
# directories holding family modules; a name in two is the first one's
FAMILY_DIRS = [os.path.join(HERE, "families")]
# what every family module provides (bench/families/__init__.py)
INTERFACE = ("program_config", "lora_shapes", "program_lora", "forward",
             "loss", "cast", "train_step_flops", "decode_step_work")


def load_config(name: str, directory: str | None = None) -> dict:
    """A configuration file; its family is loaded and checked here, so a
    file naming an unknown family stops before any device work."""
    with open(os.path.join(directory or os.path.join(HERE, "configs"),
                           name + ".json")) as f:
        cfg = json.load(f)
    family(cfg)
    return cfg


def family(cfg: dict):
    """The family module a configuration names (``dense`` where it names
    none)."""
    return load_family(cfg.get("family", "dense"))


def load_family(name: str):
    """The family module ``<name>.py``, loaded by path once per process and
    checked for the whole interface."""
    found = {}
    for d in reversed(FAMILY_DIRS):
        found.update({f[:-3]: os.path.join(d, f) for f in os.listdir(d)
                      if f.endswith(".py") and f != "__init__.py"})
    if name not in found:
        raise ValueError(f"unknown family {name!r}; known: {sorted(found)}")
    return _load(found[name])


@functools.cache
def _load(path: str):
    name = os.path.basename(path)[:-3]
    spec = importlib.util.spec_from_file_location(
        "bench_family_" + name.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    missing = [f for f in INTERFACE if not callable(getattr(mod, f, None))]
    if missing:
        raise ValueError(f"family {name!r} ({path}) lacks {missing}")
    if not hasattr(mod, "make_params"):
        mod.make_params = make_params
    return mod


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


def _placed(mesh, shardings):
    """``jax.jit`` keywords that make a call's outputs on ``mesh`` in the
    program's placement, ``shardings(rules)`` of ``repro.sharding.rules``,
    so no chip ever holds the whole tree; none without a mesh."""
    if mesh is None:
        return {}
    from repro.sharding import rules
    return {"out_shardings": shardings(rules)}


def make_params(model, key, mesh=None):
    """Base weights in the program's layout, made on the device in one jitted
    call.  Norm scales are 1 + 0.1 N(0, 1) and biases 0.1 N(0, 1), so a path
    that skipped one would show; matrices are N(0, 1/fan_in).  On a
    ``mesh`` each chip makes only its share, in the program's placement;
    the values are the same as without one."""
    shapes = jax.eval_shape(model.init, jax.random.key(0))
    leaves, treedef = jax.tree_util.tree_flatten_with_path(shapes)

    @functools.partial(jax.jit, **_placed(
        mesh, lambda rules: rules.params_sharding(shapes, mesh)))
    def init(key):
        return jax.tree_util.tree_unflatten(treedef, [
            _leaf_init(path, s.shape, s.dtype, jax.random.fold_in(key, i))
            for i, (path, s) in enumerate(leaves)])

    return init(key)


def make_lora(cfg: dict, key, *, rank: int, targets, a_std: float,
              b_std: float, lead=(), shared_lead: bool = False, mesh=None):
    """Adapter weights {target: {"a", "b"}} with leading dims ``lead``.
    ``shared_lead``: every index of the leading dims gets the same values
    (a federated job whose clients start from one adapter).  On a ``mesh``
    they are made in the program's placement of a client-stacked adapter."""
    shapes = family(cfg).lora_shapes(cfg, rank, targets,
                                      () if shared_lead else lead)
    placement = lambda rules: rules.lora_sharding(jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s, jnp.float32),
        family(cfg).lora_shapes(cfg, rank, targets, lead),
        is_leaf=lambda x: isinstance(x, tuple)), mesh)

    @functools.partial(jax.jit, **_placed(mesh, placement))
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
