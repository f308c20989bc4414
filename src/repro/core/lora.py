"""LoRA parameter trees and the first-class adapter API.

The LoRA tree has the same {"stack": {"repeat": {"p0": ...}, "tail": ...}}
shape as the base params, but each targeted projection leaf ``w (d_in, d_out)``
becomes ``{"a": (r, d_in), "b": (d_out, r)}`` (stacked over the scan dim for
repeated blocks, and over the client dim in federated training).

Initialization follows the paper / standard LoRA: A ~ N(0, sigma^2), B = 0.

The unit the rest of the codebase consumes is :class:`AdapterSet` — the A/B
tree, the scaling factor gamma, the (optional) per-client rank mask, and the
rank/alpha metadata traveling as ONE registered pytree.  Every place that
used to thread ``(lora, gamma, rank_mask)`` as loose arguments (the model
API, the federated engine, checkpointing, serving) takes a single
``adapters=`` argument instead, and every gamma fold — static, traced,
per-client — happens in exactly one place: :meth:`AdapterSet.fold_gamma`.
:class:`AdapterBank` stacks K prepared adapter sets for multi-tenant
serving: per-request adapter ids gather from the bank inside one compiled
decode step.
"""
from __future__ import annotations

import dataclasses
import functools
import warnings
from typing import Any, Tuple

import jax
import jax.numpy as jnp
import numpy as onp

from repro.analysis.hostcheck import check_adapter_ids
from repro.core.quant import QuantizedLinear, dequantize

# which leaves inside each block subtree are adaptable, per target name
_TARGET_SUBTREES = ("attn", "cross", "mlstm", "rglru")
_TARGET_LEAVES = {
    "q": ("attn/q", "cross/q", "mlstm/q"),
    "k": ("attn/k", "cross/k", "mlstm/k"),
    "v": ("attn/v", "cross/v", "mlstm/v"),
    "o": ("attn/o", "cross/o", "mlstm/o"),
    "wx": ("rglru/wx",),
    "wy": ("rglru/wy",),
}


def _targeted_paths(targets):
    out = set()
    for t in targets:
        out.update(_TARGET_LEAVES.get(t, ()))
    return out


def init_lora(params, key, lora_cfg, *, targets=None):
    """Build a LoRA tree for every targeted projection found in ``params``.

    Works on the full model params (walks into "stack"/"encoder") and keeps
    leading stack dims, so scanned blocks get stacked adapters.
    """
    targets = _targeted_paths(targets or lora_cfg.targets)
    r = lora_cfg.rank
    std = lora_cfg.init_std
    counter = [0]

    def walk(node, path):
        if isinstance(node, dict):
            out = {}
            for k, v in node.items():
                sub = walk(v, path + (k,))
                if sub is not None:
                    out[k] = sub
            return out or None
        # leaf array: check if its (parent, name) is targeted
        tail = "/".join(path[-2:])
        if tail not in targets:
            return None
        arr = node
        lead = arr.shape[:-2]              # stacked scan dims
        d_in, d_out = arr.shape[-2:]
        counter[0] += 1
        ka = jax.random.fold_in(key, counter[0])
        a = jax.random.normal(ka, lead + (r, d_in), jnp.float32) * std
        b = jnp.zeros(lead + (d_out, r), jnp.float32)
        return {"a": a.astype(arr.dtype), "b": b.astype(arr.dtype)}

    return walk(params, ()) or {}


def lora_tree_for_model(model, key, lora_cfg):
    """LoRA tree from the model config alone (via eval_shape init)."""
    params = jax.eval_shape(lambda: model.init(jax.random.key(0)))
    shapes = jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype), params)
    return init_lora(shapes, key, lora_cfg)


def merge_lora(params, lora, gamma):
    """W0 + gamma * B A merged into the base weights (inference-time,
    zero-latency deployment — the paper's 'no inference cost' property)."""
    def merge_node(p_node, l_node):
        if not (isinstance(l_node, dict)):
            return p_node
        if set(l_node) == {"a", "b"}:
            a, b = l_node["a"], l_node["b"]
            delta = jnp.einsum("...or,...ri->...io", b, a) * gamma
            if isinstance(p_node, QuantizedLinear):
                # merged weights leave packed form: the sum W0 + gamma B A is
                # not representable on W0's quantization grid.  Callers that
                # want a packed merged base re-quantize the result.
                w = dequantize(p_node)
                return w + delta.astype(w.dtype)
            return (p_node + delta.astype(p_node.dtype))
        if isinstance(p_node, dict):
            return {k: merge_node(v, l_node.get(k, None))
                    for k, v in p_node.items()}
        return p_node

    return merge_node(params, lora)


def num_lora_params(lora) -> int:
    return sum(x.size for x in jax.tree.leaves(lora))


# ------------------------------------------------------- heterogeneous ranks
#
# Heterogeneous per-client ranks use a PADDED representation: every client
# allocates rank r_max = max(ranks) so the client-stacked trees, the
# lax.scan engine, and the mesh sharding of the client dim all keep one
# uniform shape — client i's rows r_i..r_max of A (and columns of B) are
# inert: zero at init, gradient-masked during local steps, re-masked after
# every server aggregate, and excluded from aggregation means.

def rank_mask(ranks, r_max: int = 0):
    """(N, r_max) float32 mask: row i is r_i ones then r_max - r_i zeros."""
    ranks = tuple(int(r) for r in ranks)
    if not ranks or any(r < 1 for r in ranks):
        raise ValueError(f"per-client ranks must all be >= 1, got {ranks}")
    r_max = r_max or max(ranks)
    if max(ranks) > r_max:
        raise ValueError(f"rank {max(ranks)} exceeds padded r_max={r_max}")
    return (jnp.arange(r_max)[None, :]
            < jnp.asarray(ranks)[:, None]).astype(jnp.float32)


def _walk_ab(tree, fn_a, fn_b):
    """Apply fn_a / fn_b to the a / b leaves of every adapter node (the one
    canonical adapter-tree walker — ``core/aggregation`` imports it as
    ``_map_ab``).  Nodes holding only one of the two matrices (e.g. the
    output of :func:`split_ab`) are tolerated."""
    def walk(node):
        if isinstance(node, dict):
            if node and set(node) <= {"a", "b"}:
                out = {}
                if "a" in node:
                    out["a"] = fn_a(node["a"])
                if "b" in node:
                    out["b"] = fn_b(node["b"])
                return out
            return {k: walk(v) for k, v in node.items()}
        return node
    return walk(tree)


def rank_leaf_mask(mask, x, which: str):
    """Broadcast a (N, r) rank mask against a client-stacked adapter leaf:
    the rank dim is axis -2 on 'a' leaves ((N, ..., r, d_in)) and axis -1
    on 'b' leaves ((N, ..., d_out, r))."""
    n, r = mask.shape
    if which == "a":
        shape = (n,) + (1,) * (x.ndim - 3) + (r, 1)
    else:
        shape = (n,) + (1,) * (x.ndim - 2) + (r,)
    return mask.reshape(shape).astype(x.dtype)


def apply_rank_mask(lora_stacked, mask):
    """Zero the inactive rank rows of A / columns of B per client.

    ``lora_stacked`` has a leading client dim on every leaf
    (a: (N, ..., r, d_in), b: (N, ..., d_out, r)); ``mask`` is (N, r).
    """
    fa = lambda x: x * rank_leaf_mask(mask, x, "a")
    fb = lambda x: x * rank_leaf_mask(mask, x, "b")
    return _walk_ab(lora_stacked, fa, fb)


def mask_rank_tree(lora, mask_row):
    """Single-client version of :func:`apply_rank_mask` (``mask_row`` (r,)
    — typically a traced row under the engine's vmap over clients): zeroes
    rank rows of A / columns of B, e.g. for per-client gradient masking."""
    fa = lambda x: x * mask_row[..., :, None].astype(x.dtype)
    fb = lambda x: x * mask_row.astype(x.dtype)
    return _walk_ab(lora, fa, fb)


def scale_lora_b(lora, scale):
    """Scale every B matrix by ``scale`` (may be traced).

    Folding a per-client gamma_i into B — y = xW + 1.0 * (x A^T)(gamma B)^T
    — is mathematically identical to gamma * B A and keeps the gamma passed
    to the kernels a static 1.0, which the fused Pallas tier requires."""
    fb = lambda x: x * jnp.asarray(scale, x.dtype)
    return _walk_ab(lora, lambda a: a, fb)


def split_ab(lora):
    """Split a LoRA tree into (A-only tree, B-only tree) with the same
    structure — used by the selective-aggregation strategies.  Nodes holding
    only one of the two matrices (e.g. the output of a previous split) yield
    an empty dict on the missing side."""
    def pick(node, which):
        if isinstance(node, dict):
            if node and set(node) <= {"a", "b"}:
                return {which: node[which]} if which in node else {}
            return {k: pick(v, which) for k, v in node.items()}
        return node

    return pick(lora, "a"), pick(lora, "b")


# ----------------------------------------------------------- first-class API
#
# AdapterSet / AdapterBank: (lora, gamma, rank_mask) as one pytree.

def adapter_rank(lora) -> int:
    """The (padded) rank of a LoRA tree, read off the first A leaf."""
    for leaf in jax.tree.leaves(lora):
        return int(leaf.shape[-2])   # a: (..., r, d_in) visited first ("a"<"b")
    return 0


def pad_rank_tree(lora, r_max: int):
    """Zero-pad every adapter to rank ``r_max`` (rows of A, columns of B).

    Zero rank rows/columns contribute nothing to x A^T B^T, so padding is
    exact — it is how mixed-rank adapters share one stacked bank."""
    def pad(x, axis):
        extra = r_max - x.shape[axis]
        if extra < 0:
            raise ValueError(
                f"adapter rank {x.shape[axis]} exceeds r_max={r_max}")
        if extra == 0:
            return x
        widths = [(0, 0)] * x.ndim
        widths[axis] = (0, extra)
        return jnp.pad(x, widths)
    return _walk_ab(lora, lambda a: pad(a, a.ndim - 2),
                    lambda b: pad(b, b.ndim - 1))


def _static_gamma(gamma) -> bool:
    return isinstance(gamma, (int, float))


@dataclasses.dataclass(frozen=True)
class AdapterSet:
    """A/B tree + scaling factor + rank mask + metadata as ONE pytree.

    ``gamma`` is a python float (static — baked into the trace, the fused
    kernel tier's requirement) or a jax scalar/(N,) array (traced or
    per-client — folded into B by :meth:`fold_gamma` so the kernels still
    see a static scale).  ``rank_mask`` is ``(r,)`` for a single client or
    ``(N, r)``/``(K, r)`` for client-stacked / bank-gathered sets; ``None``
    means every rank row is active.  ``rank``/``alpha`` are bookkeeping
    metadata (checkpoint round-trips, bank registration).  ``batched`` marks
    a per-request set from an :class:`AdapterBank`: either every leaf
    carries a leading request dim pairing with the batch row of ``x``
    (``gather`` — materialized) or the leaves stay bank-stacked ``(K, ...)``
    with ``ids`` mapping batch rows to tenants (``requests`` — the lazy
    form whose gather happens at the projection site, in-kernel on the
    BGMV tier).

    Pytree layout: ``lora`` is a child; ``gamma`` and ``rank_mask`` are
    CONFIG, not state — when they are concrete host values (a float, a
    materialized array) they ride in the treedef as static aux data, so
    under jit they become trace-time constants: a float gamma is baked into
    the fused Pallas kernels exactly like the old static argument, and an
    all-ones rank mask constant-folds to nothing, keeping the uniform-rank
    path bit-identical to the mask-free one.  Only traced values (a
    per-request mask from ``AdapterBank.gather``, a per-client gamma_i
    under the engine's vmap) become pytree children.  Two sets with
    different static configs compile separately — by design.
    """
    lora: Any
    gamma: Any = 1.0
    rank_mask: Any = None
    rank: int = 0
    alpha: float = 0.0
    batched: bool = False
    ids: Any = None          # (B,) request->tenant map for lazy banked sets

    def __post_init__(self):
        # Normalize concrete config to HOST values once, here: pytree
        # flatten runs inside jaxlib's C++ dispatch, where a device->host
        # transfer is not safe — by construction the flatten below only
        # ever serializes numpy data.  Traced values pass through.
        g = self.gamma
        if isinstance(g, (tuple, list)):
            gs = [float(x) for x in g]
            g = gs[0] if all(x == gs[0] for x in gs) \
                else onp.asarray(gs, onp.float32)
            object.__setattr__(self, "gamma", g)
        elif isinstance(g, jax.Array) and not isinstance(g, jax.core.Tracer):
            g = onp.asarray(g)
            object.__setattr__(self, "gamma",
                               float(g) if g.ndim == 0 else g)
        m = self.rank_mask
        if m is not None and not isinstance(m, jax.core.Tracer):
            m = onp.asarray(m)
            # canonical form: an all-ones mask masks nothing — collapse it
            # to None (exactly like uniform gammas collapse to one float),
            # so uniform-rank federations take the homogeneous fast path
            # bit-for-bit instead of compiling degenerate mask multiplies
            object.__setattr__(self, "rank_mask",
                               None if m.all() else m)

    # ---------------------------------------------------------- constructors

    @classmethod
    def from_config(cls, lora_cfg, *, n_clients: int = 1, lora=None,
                    rank_mask=None) -> "AdapterSet":
        """AdapterSet for a :class:`LoRAConfig`: the scheme's scaling factor
        gamma = scaling(alpha, r, N) is derived HERE — call sites never
        assemble gamma by hand.  ``lora`` may be a real A/B tree, a
        shape-level stand-in (dryrun), or None to fill in later."""
        from repro.core.scaling import scaling_factor
        gamma = scaling_factor(lora_cfg.scaling, lora_cfg.alpha,
                               lora_cfg.rank, n_clients)
        return cls(lora=lora, gamma=gamma, rank_mask=rank_mask,
                   rank=lora_cfg.rank, alpha=lora_cfg.alpha)

    # ------------------------------------------------------------ transforms

    def masked(self) -> "AdapterSet":
        """Zero the inactive rank rows of A / columns of B per the mask.

        Idempotent (the mask is 0/1), and a bitwise no-op on adapters that
        already satisfy the mask invariant — gradients taken through the
        masked tree come out exactly zero at inactive coordinates."""
        if self.rank_mask is None:
            return self
        m = jnp.asarray(self.rank_mask)
        lora = (mask_rank_tree(self.lora, m) if m.ndim == 1
                else apply_rank_mask(self.lora, m))
        return dataclasses.replace(self, lora=lora)

    def fold_gamma(self) -> "AdapterSet":
        """Fold gamma into B: y = xW + (x A^T)(gamma B)^T == xW + gamma B A x.

        THE one place gamma is folded.  Handles a static float (folded at
        trace time), a traced scalar (per-client gamma_i under the engine's
        vmap), and a per-client/per-tenant (N,) array on a stacked tree.
        The result always carries the static ``gamma=1.0`` the fused Pallas
        tier requires."""
        g = self.gamma
        if _static_gamma(g):
            if float(g) == 1.0:
                return self
            lora = scale_lora_b(self.lora, float(g))
        else:
            garr = jnp.asarray(g)
            if garr.ndim == 0:
                lora = scale_lora_b(self.lora, garr)
            else:
                fb = lambda x: x * garr.reshape(
                    garr.shape + (1,) * (x.ndim - 1)).astype(x.dtype)
                lora = _walk_ab(self.lora, lambda a: a, fb)
        return dataclasses.replace(self, lora=lora, gamma=1.0)

    def prepared(self) -> "AdapterSet":
        """Mask + fold: the canonical form the model stack consumes
        (plain A/B tree, implicit scale 1)."""
        return self.masked().fold_gamma()

    def merge(self, params):
        """W0 + gamma * B A merged into the base weights (inference-time,
        zero-latency deployment — the paper's 'no inference cost'
        property)."""
        return merge_lora(params, self.prepared().lora, 1.0)

    # ---------------------------------------------------------- stack/unstack

    @classmethod
    def stack(cls, sets) -> "AdapterSet":
        """Stack K same-rank sets along a new leading dim (clients/tenants).

        Uniform float gammas stay one static float; mixed gammas become a
        (K,) array child.  Mixed ranks must be padded first — see
        :meth:`AdapterBank.from_sets`, which handles that."""
        sets = list(sets)
        if not sets:
            raise ValueError("AdapterSet.stack needs at least one set")
        ranks = {adapter_rank(s.lora) for s in sets}
        if len(ranks) > 1:
            raise ValueError(
                f"AdapterSet.stack needs uniform ranks, got {sorted(ranks)}; "
                "pad first (AdapterBank.from_sets does this)")
        lora = jax.tree.map(lambda *xs: jnp.stack(xs), *[s.lora for s in sets])
        gammas = [s.gamma for s in sets]
        if all(_static_gamma(g) for g in gammas):
            gamma = tuple(float(g) for g in gammas)   # __post_init__ collapses
        else:
            gamma = jnp.stack([jnp.asarray(g, jnp.float32) for g in gammas])
        r = ranks.pop()
        if any(s.rank_mask is not None for s in sets):
            rows = [jnp.ones((r,), jnp.float32) if s.rank_mask is None
                    else jnp.asarray(s.rank_mask, jnp.float32)
                    for s in sets]
            mask = jnp.stack(rows)
        else:
            mask = None
        return cls(lora=lora, gamma=gamma, rank_mask=mask, rank=r,
                   alpha=sets[0].alpha)

    def unstack(self):
        """The inverse of :meth:`stack`: K single-client sets."""
        n = jax.tree.leaves(self.lora)[0].shape[0]
        return [self.client(i) for i in range(n)]

    def client(self, i: int) -> "AdapterSet":
        """Client ``i``'s slice of a client-stacked set (its own gamma_i and
        rank-mask row included)."""
        g = self.gamma
        if not _static_gamma(g) and jnp.asarray(g).ndim >= 1:
            g = jnp.asarray(g)[i]
        m = None if self.rank_mask is None else jnp.asarray(self.rank_mask)[i]
        return dataclasses.replace(
            self, lora=jax.tree.map(lambda x: x[i], self.lora), gamma=g,
            rank_mask=m, batched=False)

    def num_params(self) -> int:
        return num_lora_params(self.lora)


def _encode_static(v):
    """Encode a concrete config value as hashable treedef aux data; traced
    values return None (they must travel as pytree children).  Only host
    (numpy/python) values reach the array branch — ``AdapterSet`` normalizes
    at construction, because flatten may run inside jaxlib's C++ dispatch
    where device->host transfers are unsafe."""
    if v is None:
        return ("none",)
    if isinstance(v, (int, float)):
        return ("float", float(v))
    if isinstance(v, onp.ndarray):
        return ("array", v.shape, str(v.dtype), v.tobytes())
    return None


def _decode_static(enc):
    if enc[0] == "none":
        return None
    if enc[0] == "float":
        return enc[1]
    _, shape, dtype, buf = enc
    # .copy(): own the memory rather than viewing the treedef's bytes
    return onp.frombuffer(buf, dtype=dtype).reshape(shape).copy()


def _aset_flatten(s):
    g_aux = _encode_static(s.gamma)
    m_aux = _encode_static(s.rank_mask)
    children = (s.lora,
                None if m_aux is not None else s.rank_mask,
                None if g_aux is not None else s.gamma,
                s.ids)
    aux = (g_aux, m_aux, s.rank, s.alpha, s.batched)
    return children, aux


def _aset_unflatten(aux, children):
    lora, mask_child, gamma_child, ids = children
    g_aux, m_aux, rank, alpha, batched = aux
    gamma = gamma_child if g_aux is None else _decode_static(g_aux)
    rank_mask = mask_child if m_aux is None else _decode_static(m_aux)
    return AdapterSet(lora=lora, gamma=gamma, rank_mask=rank_mask,
                      rank=rank, alpha=alpha, batched=batched, ids=ids)


jax.tree_util.register_pytree_node(AdapterSet, _aset_flatten, _aset_unflatten)


def init_adapter_set(params, key, lora_cfg, *, n_clients: int = 1,
                     targets=None) -> AdapterSet:
    """Fresh AdapterSet for ``params`` with the scheme's scaling factor.

    The single constructor call sites use instead of assembling
    (init_lora, scaling_factor, rank metadata) by hand."""
    return AdapterSet.from_config(
        lora_cfg, n_clients=n_clients,
        lora=init_lora(params, key, lora_cfg, targets=targets))


@functools.partial(jax.jit, donate_argnums=(0,))
def _bank_slot_swap(lora, new, slot):
    """One stacked-bank slot replaced on device: the bank leaves are DONATED,
    so on backends that support donation the update happens in the bank's own
    buffers — no second copy of a fleet-sized bank ever exists.  ``slot`` is
    traced, so every slot of a given bank shape shares ONE executable."""
    return jax.tree.map(lambda L, x: L.at[slot].set(x.astype(L.dtype)),
                        lora, new)


@dataclasses.dataclass(frozen=True)
class AdapterBank:
    """K prepared adapter sets stacked for multi-tenant serving.

    Registration folds each tenant's gamma into its B and pads mixed ranks
    to ``r_max`` under a (K, r_max) rank mask, so the bank is one uniform
    stacked tree: a compiled decode step gathers per-request adapters with
    ``bank.gather(ids)`` (ids traced — one executable serves every tenant
    mix) and routes them through the batched adapter path in
    ``kernels/dispatch``.

    ``version`` counts slot publishes (:meth:`publish`) — host-side
    bookkeeping for the adapter lifecycle, deliberately NOT part of the
    pytree (neither child nor treedef aux): a version bump must never change
    the jit cache key, or every publish would recompile the serving engines.
    It therefore does not survive a flatten/unflatten round trip.
    """
    lora: Any                                 # leaves (K,) + leaf shape
    rank_mask: Any = None                     # (K, r_max) or None
    ranks: Tuple[int, ...] = ()               # per-tenant active ranks
    version: int = 0                          # publish counter (host-only)

    @property
    def size(self) -> int:
        return jax.tree.leaves(self.lora)[0].shape[0]

    @property
    def r_max(self) -> int:
        return adapter_rank(self.lora)

    @classmethod
    def from_sets(cls, sets) -> "AdapterBank":
        """Register K AdapterSets (possibly mixed-rank) as one bank."""
        sets = [s.prepared() for s in sets]
        ranks = tuple(adapter_rank(s.lora) for s in sets)
        r_max = max(ranks)
        padded = [pad_rank_tree(s.lora, r_max) for s in sets]
        lora = jax.tree.map(lambda *xs: jnp.stack(xs), *padded)
        return cls(lora=lora, rank_mask=rank_mask(ranks, r_max), ranks=ranks)

    @classmethod
    def from_adapter_set(cls, stacked: AdapterSet, ranks=None) -> "AdapterBank":
        """Register a client-stacked AdapterSet (e.g. a restored federated
        checkpoint: every client becomes a tenant)."""
        prepared = stacked.prepared()
        n = jax.tree.leaves(prepared.lora)[0].shape[0]
        r_pad = adapter_rank(prepared.lora)
        if ranks is None:
            if stacked.rank_mask is not None:
                ranks = tuple(int(r) for r in
                              onp.asarray(stacked.rank_mask).sum(axis=-1))
            else:
                ranks = (r_pad,) * n
        return cls(lora=prepared.lora, rank_mask=rank_mask(ranks, r_pad),
                   ranks=tuple(int(r) for r in ranks))

    def publish(self, slot: int, aset: AdapterSet, *,
                donate: bool = True) -> "AdapterBank":
        """Atomically replace tenant ``slot`` with ``aset`` — the versioned
        bank update that lets federated rounds re-publish adapters while
        serving continues.

        The new set is prepared (rank-masked, gamma folded into B) and
        zero-padded to the bank's ``r_max``, so the stacked leaves keep
        EXACTLY their shapes and dtypes: every executable compiled against
        the bank (decode chunks, admission prefills) stays
        valid — swapping a slot triggers zero recompiles (asserted in
        tests/test_lifecycle.py).  A set whose rank exceeds ``r_max`` is
        rejected rather than silently reshaping the bank.

        With ``donate=True`` (default) the old leaves are donated to the
        update: the returned bank REPLACES ``self``, whose buffers may be
        invalidated — drop the old reference.  Pass ``donate=False`` to keep
        the old bank readable (e.g. A/B comparison in tests).
        """
        if not 0 <= int(slot) < self.size:
            raise ValueError(f"slot {slot} out of range for a bank of "
                             f"{self.size} tenants")
        slot = int(slot)
        prepared = aset.prepared()
        r = adapter_rank(prepared.lora)
        r_max = self.r_max
        if r > r_max:
            raise ValueError(
                f"published rank {r} exceeds the bank's r_max={r_max}: "
                "slot shapes are padded-stable by construction — rebuild "
                "the bank (AdapterBank.from_sets) to grow the rank ceiling")
        padded = pad_rank_tree(prepared.lora, r_max)
        bank_leaves, bank_def = jax.tree.flatten(self.lora)
        new_leaves, new_def = jax.tree.flatten(padded)
        if bank_def != new_def:
            raise ValueError(
                "published adapter tree structure does not match the "
                f"bank's: {new_def} vs {bank_def}")
        for bl, nl in zip(bank_leaves, new_leaves):
            if bl.shape[1:] != nl.shape:
                raise ValueError(
                    f"published adapter leaf shape {nl.shape} does not "
                    f"match the bank slot shape {bl.shape[1:]}")
        if donate:
            with warnings.catch_warnings():
                # XLA CPU cannot honor donation and warns; the swap is still
                # correct there (one extra copy), and real accelerators
                # donate in place
                warnings.filterwarnings(
                    "ignore", message="Some donated buffers were not usable")
                lora = _bank_slot_swap(self.lora, padded,
                                       jnp.asarray(slot, jnp.int32))
        else:
            lora = jax.tree.map(
                lambda L, x: L.at[slot].set(x.astype(L.dtype)),
                self.lora, padded)
        ranks = list(self.ranks or (r_max,) * self.size)
        ranks[slot] = r
        return AdapterBank(lora=lora, rank_mask=rank_mask(tuple(ranks), r_max),
                           ranks=tuple(ranks), version=self.version + 1)

    def gather(self, ids) -> AdapterSet:
        """Per-request adapters, MATERIALIZED: ``ids`` (b,) int tenant
        indices (may be traced).  Returns a ``batched`` AdapterSet whose
        leaves carry a leading request dim — gamma is already folded, so it
        serves under the static scale 1 every kernel tier accepts.  No rank
        mask rides along: bank registration stored the sets exactly masked
        and zero-padded, so a gathered mask would only re-multiply every
        A/B leaf by its own zero pattern on every decode step.

        Copies every adapter leaf per call — prefer :meth:`requests` on the
        serving hot path, which defers the gather to the projection site."""
        check_adapter_ids(ids, self.size, what="gather id")
        ids = jnp.asarray(ids)
        lora = jax.tree.map(lambda x: x[ids], self.lora)
        return AdapterSet(lora=lora, gamma=1.0,
                          rank=adapter_rank(lora), batched=True)

    def requests(self, ids) -> AdapterSet:
        """Per-request adapters, LAZY: the bank leaves stay stacked
        ``(K, ...)`` and the request->tenant map rides along as ``ids``, so
        the gather happens per projection — inside the BGMV kernel via its
        ids-indexed BlockSpecs on the fused tiers, or as a per-layer XLA
        gather on the reference tier — instead of materializing ``(B, ...)``
        copies of every adapter leaf each generation step."""
        check_adapter_ids(ids, self.size, what="request id")
        return AdapterSet(lora=self.lora, gamma=1.0,
                          rank=adapter_rank(self.lora), batched=True,
                          ids=jnp.asarray(ids, jnp.int32))

    def adapter(self, k: int) -> AdapterSet:
        """Tenant ``k`` as a plain single AdapterSet (the per-adapter loop
        the bank's batched decode is conformance-tested against)."""
        mask = None if self.rank_mask is None else self.rank_mask[k]
        return AdapterSet(lora=jax.tree.map(lambda x: x[k], self.lora),
                          gamma=1.0, rank_mask=mask,
                          rank=int(self.ranks[k]) if self.ranks else 0)


jax.tree_util.register_pytree_node(
    AdapterBank,
    lambda b: ((b.lora, b.rank_mask), (b.ranks,)),
    lambda aux, ch: AdapterBank(lora=ch[0], rank_mask=ch[1], ranks=aux[0]))


class LiveAdapterBank:
    """Adapter lifecycle at fleet scale: an HBM-resident hot set over a
    host-RAM tenant store.

    The device :class:`AdapterBank` holds ``hot_slots`` padded slots; the
    full tenant population lives host-side as numpy trees (prepared —
    gamma-folded, rank-masked — and zero-padded to ``r_max``, so promotion
    is a pure copy).  This serves a bank that does NOT fit in HBM: a
    request for a non-resident tenant promotes it into the least-recently-
    used unpinned slot at the next chunk boundary; the evictee is demoted
    to the host store for free, because the store is always authoritative
    (:meth:`publish` writes host first, then swaps the device slot only if
    the tenant is resident).

    This object is intentionally NOT a pytree: it is host-side lifecycle
    state (residency map, LRU clock, versions).  Compiled code only ever
    sees ``live.bank`` — a plain AdapterBank whose shapes never change, so
    promotions, demotions, and publishes all reuse the same executables.

    Recency is driven by the request ids flowing through
    ``launch/serve.serve_scheduled`` (admission + every decode chunk calls
    :meth:`touch` / :meth:`acquire`), which is why stale tenant ids on idle
    engine slots are a correctness hazard there — see the ids_arr reset in
    ``serve_scheduled``'s eviction path.
    """

    def __init__(self, *, bank: AdapterBank, store: dict, slot_tenant):
        self.bank = bank
        self.store = store                    # tenant -> {lora, rank, version}
        self.slot_tenant = [int(t) for t in slot_tenant]
        if len(self.slot_tenant) != bank.size:
            raise ValueError("slot_tenant must name every device slot")
        self.tenant_slot = {t: s for s, t in enumerate(self.slot_tenant)
                            if t >= 0}
        self._tick = 0
        self._last_used = [0] * len(self.slot_tenant)
        self.version = 0                      # global publish counter
        self.promotions = 0
        self.demotions = 0
        self.swaps = 0                        # in-place resident publishes

    # ------------------------------------------------------------ properties

    @property
    def hot_slots(self) -> int:
        return len(self.slot_tenant)

    @property
    def r_max(self) -> int:
        return self.bank.r_max

    @property
    def tenants(self):
        return sorted(self.store)

    def has(self, tenant) -> bool:
        return int(tenant) in self.store

    def resident(self, tenant) -> bool:
        return int(tenant) in self.tenant_slot

    def tenant_version(self, tenant) -> int:
        return self.store[int(tenant)]["version"]

    # ---------------------------------------------------------- constructors

    @classmethod
    def from_sets(cls, sets, *, hot_slots: int,
                  r_max: int = 0) -> "LiveAdapterBank":
        """Register tenants 0..len(sets)-1; the first ``hot_slots`` of them
        start device-resident.  ``r_max`` (default: the max rank seen) is
        the bank's permanent rank ceiling — later publishes may use any
        rank up to it."""
        sets = list(sets)
        if not sets:
            raise ValueError("LiveAdapterBank needs at least one tenant")
        prepared = [s.prepared() for s in sets]
        ranks = [adapter_rank(p.lora) for p in prepared]
        r_max = int(r_max) or max(ranks)
        if max(ranks) > r_max:
            raise ValueError(f"rank {max(ranks)} exceeds r_max={r_max}")
        store = {t: {"lora": jax.tree.map(onp.asarray,
                                          pad_rank_tree(p.lora, r_max)),
                     "rank": r, "version": 0}
                 for t, (p, r) in enumerate(zip(prepared, ranks))}
        return cls._build(store, hot_slots=hot_slots, r_max=r_max)

    @classmethod
    def from_bank(cls, bank: AdapterBank, *, hot_slots: int
                  ) -> "LiveAdapterBank":
        """Wrap a static AdapterBank: every bank row becomes a host-store
        tenant (row index = tenant id) and the first ``hot_slots`` start
        resident — ``--hot-slots`` on the serve CLI takes this path."""
        host = jax.tree.map(onp.asarray, bank.lora)
        ranks = bank.ranks or (bank.r_max,) * bank.size
        store = {t: {"lora": jax.tree.map(lambda x, t=t: x[t], host),
                     "rank": int(ranks[t]), "version": 0}
                 for t in range(bank.size)}
        return cls._build(store, hot_slots=hot_slots, r_max=bank.r_max)

    @classmethod
    def _build(cls, store, *, hot_slots: int, r_max: int) -> "LiveAdapterBank":
        if hot_slots < 1:
            raise ValueError(f"need >= 1 hot slot, got {hot_slots}")
        tenants = sorted(store)
        resident = tenants[:hot_slots]
        template = store[tenants[0]]["lora"]
        rows, slot_tenant, slot_ranks = [], [], []
        for s in range(hot_slots):
            if s < len(resident):
                t = resident[s]
                rows.append(store[t]["lora"])
                slot_tenant.append(t)
                slot_ranks.append(store[t]["rank"])
            else:                      # spare slot: zeros (inert by padding)
                rows.append(jax.tree.map(onp.zeros_like, template))
                slot_tenant.append(-1)
                slot_ranks.append(r_max)
        lora = jax.tree.map(lambda *xs: jnp.stack(xs), *rows)
        bank = AdapterBank(lora=lora,
                           rank_mask=rank_mask(tuple(slot_ranks), r_max),
                           ranks=tuple(slot_ranks))
        return cls(bank=bank, store=store, slot_tenant=slot_tenant)

    # -------------------------------------------------------------- lifecycle

    def publish(self, tenant, aset: AdapterSet) -> int:
        """Publish a new adapter version for ``tenant`` (new tenants
        register on first publish).  The host store is updated first —
        authoritative, so demotion never needs a device->host copy — and a
        RESIDENT tenant's device slot is hot-swapped atomically via
        :meth:`AdapterBank.publish` (zero recompiles; in-flight decode
        chunks finish on the adapters they gathered, the next chunk serves
        the new version).  Returns the tenant's new version number."""
        tenant = int(tenant)
        prepared = aset.prepared()
        r = adapter_rank(prepared.lora)
        if r > self.r_max:
            raise ValueError(
                f"tenant {tenant}: published rank {r} exceeds the bank's "
                f"r_max={self.r_max} — shapes are padded-stable; rebuild "
                "the live bank to grow the rank ceiling")
        padded = pad_rank_tree(prepared.lora, self.r_max)
        ver = (self.store[tenant]["version"] + 1 if tenant in self.store
               else 0)
        self.store[tenant] = {"lora": jax.tree.map(onp.asarray, padded),
                              "rank": r, "version": ver}
        self.version += 1
        s = self.tenant_slot.get(tenant)
        if s is not None:
            self.bank = self.bank.publish(s, AdapterSet(lora=padded))
            self.swaps += 1
        return ver

    def touch(self, tenants) -> None:
        """Advance the LRU clock for every resident tenant in ``tenants`` —
        called with the ids observed at each admission / decode chunk."""
        self._tick += 1
        for t in tenants:
            s = self.tenant_slot.get(int(t))
            if s is not None:
                self._last_used[s] = self._tick

    def acquire(self, tenants, pinned=()):
        """Device slots for ``tenants``, promoting non-resident ones from
        the host store into free or least-recently-used slots.  ``pinned``
        slots (those gathered by still-running requests) are never evicted.
        Returns {tenant: slot}, or None when the distinct tenants cannot
        all be made resident without evicting a pinned slot — the caller
        defers admission to a later chunk boundary (running requests finish
        and unpin, so deferral always makes progress)."""
        want = list(dict.fromkeys(int(t) for t in tenants))
        for t in want:
            if t not in self.store:
                raise KeyError(f"unknown tenant {t}: store holds "
                               f"{self.tenants}")
        keep = {int(p) for p in pinned}
        keep |= {self.tenant_slot[t] for t in want if t in self.tenant_slot}
        missing = [t for t in want if t not in self.tenant_slot]
        free = [s for s in range(self.hot_slots)
                if self.slot_tenant[s] < 0 and s not in keep]
        victims = sorted((s for s in range(self.hot_slots)
                          if self.slot_tenant[s] >= 0 and s not in keep),
                         key=lambda s: self._last_used[s])
        if len(missing) > len(free) + len(victims):
            return None
        for t in missing:
            s = free.pop(0) if free else victims.pop(0)
            self._promote(t, s)
        self.touch(want)
        return {t: self.tenant_slot[t] for t in want}

    def _promote(self, tenant: int, slot: int) -> None:
        old = self.slot_tenant[slot]
        if old >= 0:
            # demotion is free: the host store already holds the evictee
            del self.tenant_slot[old]
            self.demotions += 1
        rec = self.store[tenant]
        self.bank = self.bank.publish(slot, AdapterSet(lora=rec["lora"]))
        self.slot_tenant[slot] = tenant
        self.tenant_slot[tenant] = slot
        self.promotions += 1


def as_adapter_set(adapters):
    """Normalize an ``adapters=`` argument.

    Returns None when no adapters were given.  A raw A/B dict passed as
    ``adapters`` is wrapped with scale 1 (it is already a prepared tree).
    (The PR 4 ``lora=``/``gamma=`` kwarg shim lived here for one release
    and is gone — pass an AdapterSet.)"""
    if adapters is None:
        return None
    if isinstance(adapters, AdapterSet):
        return adapters
    return AdapterSet(lora=adapters)
