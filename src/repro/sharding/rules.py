"""PartitionSpec assignment for every pytree in the system.

Policy (DESIGN.md §6):
  - base params: tensor-parallel over "model" (out-features of up-projections,
    in-features of down-projections, vocab dim of embed/head, expert dim of
    MoE stacks); stacked layer dims replicated.
  - LoRA: A replicated (the aggregated client-shared object), B model-sharded
    on d_out; leading client dim over ("pod","data").
  - batch dims over ("pod","data"); decode caches: the KV pool's block dim
    (per-slot state's batch dim) if divisible; kv-heads, else head dim,
    over "model" when divisible.

Every rule checks divisibility and degrades to replication, so the same code
serves the 16x16 pod, the 2x16x16 multi-pod, and 1-device CPU tests.
"""
from __future__ import annotations

import jax
from jax.sharding import NamedSharding, PartitionSpec as P


def _div(size, mesh, axes) -> bool:
    if axes is None:
        return True
    names = (axes,) if isinstance(axes, str) else tuple(axes)
    prod = 1
    for n in names:
        if n not in mesh.axis_names:
            return False
        prod *= mesh.shape[n]
    return size % prod == 0 and prod > 1


def _maybe(size, mesh, axes):
    return axes if _div(size, mesh, axes) else None


def batch_axes(mesh):
    return tuple(a for a in ("pod", "data") if a in mesh.axis_names)


# ------------------------------------------------------------------ params

# leaf-name -> which trailing dim gets "model"   (-1 = last, -2, ... ; None)
_COL = {"q": -1, "k": -1, "v": -1, "w_gate": -1, "w_up": -1, "shared_gate": -1,
        "shared_up": -1, "wx": -1, "wy": -1, "w_z": -1, "w_i": -1, "w_f": -1,
        "w_o": -1, "ogate": -1, "w_a": -1, "lm_head": -1, "patch_proj": -1,
        "w_proj": -1}
_ROW = {"o": -2, "w_down": -2, "shared_down": -2, "w_out": -2, "embed": -2}
_EXPERT = ("moe",)          # subtree name whose 3D leaves shard dim -3


def param_spec(path_keys, shape, mesh) -> P:
    """path_keys: tuple of str keys from the pytree root to the leaf."""
    leaf = path_keys[-1]
    parent = path_keys[-2] if len(path_keys) > 1 else ""
    nd = len(shape)
    spec = [None] * nd
    if leaf == "embed":
        from repro.sharding.opts import enabled
        if enabled("embed_dshard"):
            if _div(shape[-1], mesh, "model"):
                spec[-1] = "model"
            return P(*spec)

    def set_model(dim):
        d = dim % nd
        if _div(shape[d], mesh, "model"):
            spec[d] = "model"

    if parent == "moe" and nd >= 3 and leaf in ("w_gate", "w_up", "w_down"):
        set_model(-3)                      # expert-parallel stacks
    elif parent == "moe" and len(path_keys) > 2 and nd >= 4:
        set_model(-3)
    elif leaf in _COL and not (parent == "moe" and leaf in ("w_gate", "w_up")):
        set_model(_COL[leaf])
    elif leaf in _ROW:
        set_model(_ROW[leaf])
    elif leaf == "r_z" or leaf.startswith("r_") and nd == 3:
        set_model(-1)
    # stacked-layer leading dims / norms / biases stay replicated
    # MoE stacked under repeat: path ... 'moe' 'w_gate' with nd==4 (L,E,d,ff)
    if parent == "moe" and leaf in ("w_gate", "w_up", "w_down") and nd == 4:
        spec = [None] * nd
        if _div(shape[1], mesh, "model"):
            spec[1] = "model"
    return P(*spec)


def tree_specs(tree, mesh, spec_fn):
    """Map a path-aware spec function over a pytree -> NamedSharding tree."""
    def walk(node, path):
        if isinstance(node, dict):
            return {k: walk(v, path + (k,)) for k, v in node.items()}
        if isinstance(node, (list, tuple)):
            t = [walk(v, path + (f"#{i}",)) for i, v in enumerate(node)]
            return type(node)(t)
        return NamedSharding(mesh, spec_fn(path, node.shape, mesh))
    return walk(tree, ())


def params_sharding(params, mesh):
    return tree_specs(params, mesh, param_spec)


# ------------------------------------------------------------------- LoRA

def lora_spec(path_keys, shape, mesh, *, client_dim: bool) -> P:
    leaf = path_keys[-1]          # "a" or "b"
    nd = len(shape)
    spec = [None] * nd
    if client_dim:
        ca = batch_axes(mesh)
        if ca and _div(shape[0], mesh, ca):
            spec[0] = ca if len(ca) > 1 else ca[0]
    if leaf == "b" and _div(shape[-2], mesh, "model"):
        spec[-2] = "model"        # B rows follow the base weight's out dim
    return P(*spec)


def lora_sharding(lora, mesh, *, client_dim=True):
    """Shardings for a LoRA tree — or an :class:`AdapterSet`, which comes
    back as an AdapterSet of shardings (same treedef: gamma/rank mask are
    static aux data, so only the A/B leaves need placements)."""
    from repro.core.lora import AdapterSet
    if isinstance(lora, AdapterSet):
        import dataclasses
        return dataclasses.replace(
            lora, lora=lora_sharding(lora.lora, mesh, client_dim=client_dim))
    return tree_specs(lora, mesh,
                      lambda p, s, m: lora_spec(p, s, m,
                                                client_dim=client_dim))


# ------------------------------------------------------------------- cache

def cache_spec(path_keys, shape, mesh) -> P:
    leaf = path_keys[-1]
    nd = len(shape)
    stacked = any(k.startswith("p") and k[1:].isdigit() for k in path_keys)
    off = 1 if (stacked and "repeat" in path_keys) else 0
    spec = [None] * nd
    ba = batch_axes(mesh)
    bdim = off                                # batch dim position
    bsz = shape[bdim] if nd > bdim else 0
    batch_ok = ba and _div(bsz, mesh, ba)
    if leaf in ("k_pool", "v_pool", "pos_pool"):  # (blocks, bs[, kh, hd])
        if batch_ok:                          # the block dim, like a batch
            spec[bdim] = ba if len(ba) > 1 else ba[0]
        if leaf != "pos_pool":
            if _div(shape[off + 2], mesh, "model"):
                spec[off + 2] = "model"
            elif _div(shape[off + 3], mesh, "model"):
                spec[off + 3] = "model"
    elif leaf in ("cross_k", "cross_v"):
        if batch_ok:
            spec[bdim] = ba if len(ba) > 1 else ba[0]
        if _div(shape[off + 2], mesh, "model"):
            spec[off + 2] = "model"
    elif leaf in ("h", "c", "n", "conv_tail"):  # recurrent states (b, ..., d)
        if batch_ok:
            spec[bdim] = ba if len(ba) > 1 else ba[0]
        if _div(shape[-1], mesh, "model"):
            spec[-1] = "model"
    elif leaf == "C":                          # mlstm (b, h, hd, hd)
        if batch_ok:
            spec[bdim] = ba if len(ba) > 1 else ba[0]
        if _div(shape[-1], mesh, "model"):
            spec[-1] = "model"
    elif leaf == "m":
        if batch_ok:
            spec[bdim] = ba if len(ba) > 1 else ba[0]
    return P(*spec)


def cache_sharding(cache, mesh):
    return tree_specs(cache, mesh, cache_spec)


# ------------------------------------------------------------------- inputs

def input_spec(path_keys, shape, mesh, *, client_dim: bool) -> P:
    """tokens (b, s) / (N, steps, b, s); frames/patches analogous."""
    nd = len(shape)
    spec = [None] * nd
    ba = batch_axes(mesh)
    if not ba:
        return P(*spec)
    ax = ba if len(ba) > 1 else ba[0]
    if client_dim:
        if _div(shape[0], mesh, ba):
            spec[0] = ax
    else:
        if _div(shape[0], mesh, ba):
            spec[0] = ax
    return P(*spec)


def inputs_sharding(batch, mesh, *, client_dim=False):
    return tree_specs(batch, mesh,
                      lambda p, s, m: input_spec(p, s, m,
                                                 client_dim=client_dim))


def chunked_input_spec(path_keys, shape, mesh) -> P:
    """Scan-staged training batches (chunk_rounds, N, steps, b, s): the
    leading scan dim stays replicated, the client dim (dim 1) shards over
    the client/batch axes when divisible."""
    nd = len(shape)
    spec = [None] * nd
    ba = batch_axes(mesh)
    if nd > 1 and ba and _div(shape[1], mesh, ba):
        spec[1] = ba if len(ba) > 1 else ba[0]
    return P(*spec)


def chunked_inputs_sharding(batch, mesh):
    return tree_specs(batch, mesh, chunked_input_spec)
