"""Federated LoRA fine-tuning: a compiled multi-round engine.

One federated round (paper §3):
  1. every client runs ``local_steps`` SGD/AdamW steps on its LoRA params
     (vmap over the client dim — on a mesh the client dim shards over
     ``data``/``pod`` axes, so local training is collective-free),
  2. the server aggregates per the strategy (FedSA/SFed: mean of A only —
     one small all-reduce over the client axes),
  3. the aggregate is broadcast back (same collective).

Engine architecture (the ROADMAP "fast as the hardware allows" move):

  round body   one round as a pure function of (state, batches, round_idx,
               weights) — shared by every execution mode below.
  run_chunk    ``jax.lax.scan`` of the round body over a *chunk* of rounds,
               entirely on device.  A carried PRNG key is split once per
               round inside the scan; partial participation is sampled from
               it with ``jax.random`` (choice without replacement); batches
               either stream in as stacked scan inputs (host data) or are
               synthesized on device by a ``batch_fn`` (``jax.random``
               inside the scan — zero host traffic).  Client/optimizer
               carries are donated, and the stacked per-round metrics come
               back in one transfer, so the host syncs once per chunk
               instead of once per round.
  FederatedTrainer   a thin host wrapper that keeps the public API (``run``,
               ``run_round``, ``eval_perplexity``, ``history``) and calls
               ``run_chunk`` in chunks of ``chunk_rounds`` (default: the
               ``log_every`` stride, else the whole request).  ``run_round``
               is a chunk of one, so per-round and chunked execution are the
               same compiled computation and stay bit-identical.

The scaling factor gamma = scaling_factor(scheme, alpha, r, N) multiplies the
adapter product in every forward pass — SFed-LoRA's contribution is that this
is sqrt(N/r), tied to the *distribution config*, not just the adapter shape.
"""
from __future__ import annotations

import contextlib
import dataclasses
import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.analysis import trace
from repro.core.aggregation import get_strategy
from repro.core.quant import dequantize_tree, has_quantized
from repro.core.lora import (AdapterSet, apply_rank_mask, init_lora,
                             mask_rank_tree, rank_mask)
from repro.core.scaling import per_client_gammas, scaling_factor
from repro.models.transformer import choose_saved
from repro.optim.optimizers import apply_updates, global_norm, make_optimizer


def participation_weights(key, num_clients: int, num_sampled: int):
    """(N,) 0/1 mask with exactly ``num_sampled`` ones, sampled uniformly
    without replacement from the round's PRNG key (device-side)."""
    perm = jax.random.permutation(key, num_clients)
    return jnp.zeros((num_clients,), jnp.float32).at[perm[:num_sampled]].set(1.0)


def device_bytes_limit():
    """The bytes one device of the round may hold
    (``memory_stats()["bytes_limit"]``), or None where its backend reports
    none, as the CPU does."""
    from repro.sharding.specs import current_mesh
    mesh = current_mesh()
    dev = (mesh.devices.flat[0] if mesh is not None
           else jax.local_devices()[0])
    return (dev.memory_stats() or {}).get("bytes_limit")


def _nbytes(tree) -> int:
    return sum(math.prod(x.shape) * jnp.dtype(x.dtype).itemsize
               for x in jax.tree.leaves(tree))


def _step_tokens(batches) -> int:
    """Tokens one local step runs over all clients: ``batches`` leaves are
    ``(N, local_steps, rows, seq)``."""
    n, _, rows, seq = batches["tokens"].shape[:4]
    return n * rows * seq


def round_rest_bytes(model, base, lora_N, opt_N, batches) -> int:
    """The device bytes a round can hold at once when its layer scans
    keep only each layer's input, counted as the TPU's compiler counts
    them (``memory_analysis().peak_memory_in_bytes``) and summed as if
    all were live together: the arguments; a bfloat16 copy of each
    float32 matrix of the base that is read only as a matmul operand,
    made once per chunk; the adapters again, layer-major for the scan,
    and their gradient; the head's logits, their softmax and their
    gradient in float32, and the gradient's bfloat16 copy; the layer
    inputs the scan keeps.  Shapes are global, so on a mesh this counts
    the whole round on one device."""
    cfg = model.cfg
    tokens = _step_tokens(batches)
    copies = sum(
        math.prod(x.shape) * 2
        for path, x in jax.tree_util.tree_flatten_with_path(base)[0]
        if x.dtype == jnp.float32
        and model.dot_only(tuple(k.key for k in path)))
    logits = tokens * model.vocab_padded * (3 * 4 + 2)
    inputs = (cfg.num_layers * tokens * cfg.d_model
              * jnp.dtype(cfg.dtype).itemsize)
    return (_nbytes(base) + 3 * _nbytes(lora_N) + _nbytes(opt_N)
            + _nbytes(batches) + copies + logits + inputs)


# The share of the device's bytes the chooser leaves unclaimed.  In
# compiles for a described v5e a chosen set's peak came up to 0.24 GB over
# the full-layer checkpoint's peak plus the set's bytes, and
# round_rest_bytes counted that peak 0.5-2.4 GB high.
SAVED_MARGIN = 0.01


def saved_residuals(model, base, lora_N, opt_N, batches, limit):
    """The tensors (``transformer.SAVEABLE``) that every client's layer
    scan keeps for its backward in a round over ``batches``, and their
    bytes: as many as fit ``limit`` bytes, less ``SAVED_MARGIN`` of it,
    beside :func:`round_rest_bytes`.  No limit keeps none."""
    if limit is None:
        return (), 0
    budget = (int(limit * (1 - SAVED_MARGIN))
              - round_rest_bytes(model, base, lora_N, opt_N, batches))
    return choose_saved(model.cfg, tokens=_step_tokens(batches),
                        itemsize=jnp.dtype(model.cfg.dtype).itemsize,
                        budget=budget)


def _round_saved(model, base, lora_N, opt_N, batches):
    """:func:`saved_residuals` on the device the round runs on, recorded
    once per trace of the round: a span ``fed.saved`` (attr ``saved``,
    the names) and the counter ``fed.saved_residual_bytes``."""
    names, nbytes = saved_residuals(model, base, lora_N, opt_N, batches,
                                    device_bytes_limit())
    with trace.span("fed.saved", saved=list(names)):
        if nbytes:
            trace.count("fed.saved_residual_bytes", nbytes)
    return names


def _make_client_local(model, strat, opt_cfg):
    """The per-client local-training scan (``local_steps`` optimizer steps
    on one client's adapter state), shared by the synchronous and the
    buffered round bodies — the two engines must differ only in the
    server-side delivery/aggregation path, never in client compute."""
    _, opt_update = make_optimizer(opt_cfg)

    def client_local(base, lora, opt_state, batches, round_idx, mask_row,
                     gamma_i, gamma_static, saved=()):
        def step(carry, batch):
            lo, st = carry
            def loss_fn(l):
                # no rank_mask here: the engine maintains the mask invariant
                # externally (zero-init, grad masking below, re-mask after
                # aggregation), so ``l`` is already exactly masked — passing
                # the mask would only add a redundant traced multiply to the
                # hot loop (and break bit-identity with the uniform-rank
                # fast path)
                aset = AdapterSet(
                    lora=l,
                    gamma=gamma_static if gamma_i is None else gamma_i)
                return model.loss(base, batch, adapters=aset, saved=saved)
            (loss, m), grads = jax.value_and_grad(loss_fn, has_aux=True)(lo)
            gnorm = global_norm(grads)
            grads = strat.mask_grads(grads, round_idx)
            if mask_row is not None:
                grads = mask_rank_tree(grads, mask_row)
            if opt_cfg.grad_clip:
                from repro.optim.optimizers import clip_by_global_norm
                grads = clip_by_global_norm(grads, opt_cfg.grad_clip)
            updates, st = opt_update(grads, st, lo)
            lo = apply_updates(lo, updates)
            return (lo, st), {"loss": loss, "grad_norm": gnorm}

        (lora, opt_state), ms = jax.lax.scan(step, (lora, opt_state), batches)
        return lora, opt_state, ms

    return client_local


def make_round_body(model, *, strategy, opt_cfg, track_update_norm=False):
    """Returns round_body(base, adapters, opt_N, batches, round_idx, weights).

    ``adapters`` is a client-stacked :class:`AdapterSet`: its ``lora`` tree
    and ``opt_N`` carry a leading client dim, ``batches`` leaves are
    (N, local_steps, batch, ...).  Returns (adapters', opt_N, metrics).

    The scaling factor and the per-client rank mask are READ OFF the
    AdapterSet — the engine no longer threads them as loose arguments:

      - a python-float ``adapters.gamma`` (homogeneous, or uniform
        per-client gammas collapsed by AdapterSet) stays static and is
        folded into B at trace time by the model API;
      - a per-client (N,) ``adapters.gamma`` reaches each client as a
        traced gamma_i under the vmap and is folded into that client's B
        inside the loss (``AdapterSet.fold_gamma``), so the gamma reaching
        the kernels is always the static 1.0 the fused Pallas tier needs;
      - ``adapters.rank_mask`` (N, r_max) enables heterogeneous per-client
        ranks in the padded representation: client gradients are masked to
        the active rank rows and the server aggregate is rank-aware (see
        ``core/aggregation``).

    ``track_update_norm`` adds a per-round ``update_norm`` metric: the
    gamma-scaled norm of the post-aggregation adapter movement, the series
    the collapse sentinel (``repro.analysis.stability_check``) judges
    against the Theorem 4.2 moment-scale prediction.  Opt-in so the
    default metrics treedef (and every pinned bit-identity test) is
    untouched.
    """
    strat = get_strategy(strategy)
    client_local = _make_client_local(model, strat, opt_cfg)

    def round_body(base, adapters, opt_N, batches, round_idx, weights=None):
        """``weights`` (N,) non-negative: 0 = non-sampled (keeps its local
        state, only receives the aggregate); positive values additionally
        weight the server mean (e.g. by client example counts)."""
        lora_N = adapters.lora
        mask_N = adapters.rank_mask
        g = adapters.gamma
        static = isinstance(g, (int, float))
        gamma_N = None if static else jnp.asarray(g, jnp.float32)
        saved = _round_saved(model, base, lora_N, opt_N, batches)
        new_lora, new_opt, ms = jax.vmap(
            functools.partial(client_local,
                              gamma_static=g if static else None,
                              saved=saved),
            in_axes=(None, 0, 0, 0, None,
                     None if mask_N is None else 0,
                     None if gamma_N is None else 0))(
                base, lora_N, opt_N, batches, round_idx, mask_N, gamma_N)
        if weights is not None:
            sel = lambda new, old: jax.tree.map(
                lambda a, b: jnp.where(
                    weights.reshape((-1,) + (1,) * (a.ndim - 1)) > 0, a, b),
                new, old)
            new_lora = sel(new_lora, lora_N)
            new_opt = sel(new_opt, opt_N)
        new_lora = strat.aggregate(new_lora, round_idx, weights=weights,
                                   rank_mask=mask_N)
        metrics = {"loss": ms["loss"].mean(), "grad_norm": ms["grad_norm"].mean()}
        if track_update_norm:
            # gamma-scaled aggregated adapter movement: to first order the
            # effective-weight step is gamma*(dB·A + B·dA), so |gamma|*|d
            # lora| tracks the Thm 4.2 moment scale the sentinel checks
            g_scale = abs(g) if static else jnp.mean(jnp.abs(gamma_N))
            metrics["update_norm"] = g_scale * global_norm(
                jax.tree.map(lambda a, b: a - b, new_lora, lora_N))
        return dataclasses.replace(adapters, lora=new_lora), new_opt, metrics

    return round_body


def _tree_where(row_mask, new, old):
    """Per-client row select over two identically-shaped stacked trees."""
    return jax.tree.map(
        lambda a, b: jnp.where(
            row_mask.reshape((-1,) + (1,) * (a.ndim - 1)), a, b), new, old)


def _quantize_rho(rho: float) -> float:
    """Quantize the carried gamma correction rho = sqrt(N_eff/N) before
    the trainer folds it statically into the next chunk's gamma: each
    distinct gamma compiles its own executable (it rides the AdapterSet
    treedef), so an unquantized rho would recompile every chunk under
    sustained faults.  Two decimals bounds the executable set at ~100.
    rho >= 0.995 passes through as exactly 1.0, keeping the staleness-0
    fold a bitwise no-op."""
    rho = float(rho)
    if rho >= 0.995:
        return 1.0
    return max(round(rho, 2), 0.01)


def make_buffered_round_body(model, *, strategy, opt_cfg, fault_model=None,
                             track_update_norm=False):
    """The async FedBuff-style round body: returns
    round_body(base, adapters, opt_N, tau, rho, batches, round_idx,
    k_fault, part, size_w, expected) -> (adapters', opt_N', tau', rho',
    metrics).

    One round, fully inside the scan (no host clocks, no per-arrival
    jits):

      1. every sampled client WITHOUT an in-flight upload trains locally
         (in-flight clients hold their pending update and skip the round —
         their state is the update still in transit);
      2. the fault model draws this round's drop/straggle/corrupt masks
         from ``k_fault``; corruption applies to a COPY of the upload,
         never the client's local state;
      3. the server screens arrivals (non-finite always rejected; norm
         outliers vs ``screen_mult`` x the candidate median when screening is
         on), caps the accepted buffer at ``buffer_size`` in client-index
         order (overflow stays in flight), and aggregates the accepted
         uploads with staleness weights ``(1 + tau)^-beta`` composed with
         the size weights;
      4. clients still in flight (stragglers + overflow) bump tau and keep
         local state; everyone else resets tau and receives the broadcast
         on exactly the leaves the inner strategy aggregates
         (``agg_leaf_flags``) — dropped/rejected clients therefore resync
         from the server, losing their corrupt/lost update;
      5. the carried correction factor rho' = sqrt(N_eff_mass / expected)
         is the Theorem 4.2 staleness correction: gamma_eff = gamma * rho
         = alpha*sqrt(N_eff/r) (see
         ``repro.core.scaling.staleness_corrected_gamma``).  The trainer
         applies it at CHUNK boundaries as a static gamma fold (the
         engine's per-gamma-executable specialization) rather than as an
         in-scan runtime multiply: a runtime gamma would block XLA's
         constant-folding of gamma into the loss graph and break the
         staleness-0 bit-identity by ulps.  Within a chunk the body
         trains with the chunk-start gamma_eff and carries rho for the
         metrics and the next fold.

    At zero faults, M = N, and tau = 0, every mask is the constant it is
    in the synchronous engine and rho stays exactly 1.0, so this body is
    BIT-identical to ``make_round_body`` (pinned by the conformance
    harness): ``where(True, new, old)`` is ``new``, the weighted mean
    with all-ones weights equals the fast-path mean bitwise (both lower
    to sum * reciprocal — see ``aggregate_clients``), and the gamma fold
    ``gamma * 1.0`` is exact, so the same executable keeps serving.

    ``expected`` is the round's sampled-client count (static python int) —
    the denominator that makes N_eff = N at full delivery.
    """
    from repro.core.aggregation import (BufferedStrategy, combine_received,
                                        per_client_finite, per_client_norm)
    from repro.core.faults import FaultModel
    strat = get_strategy(strategy)
    if not isinstance(strat, BufferedStrategy):
        raise ValueError(
            "make_buffered_round_body needs a BufferedStrategy (wrap the "
            "inner method with aggregation.buffered(...))")
    inner = strat.inner
    fault_model = fault_model or FaultModel()
    client_local = _make_client_local(model, strat, opt_cfg)

    def round_body(base, adapters, opt_N, tau, rho, batches, round_idx,
                   k_fault, part=None, size_w=None, expected=None):
        lora_N = adapters.lora
        mask_N = adapters.rank_mask
        g = adapters.gamma
        n = jax.tree.leaves(lora_N)[0].shape[0]
        expected = n if expected is None else expected
        # gamma stays STATIC exactly as in make_round_body — the trainer
        # already folded the previous chunk's rho into adapters.gamma, so
        # the client compute graph is the synchronous engine's graph
        static = isinstance(g, (int, float))
        gamma_N = None if static else jnp.asarray(g, jnp.float32)
        saved = _round_saved(model, base, lora_N, opt_N, batches)
        new_lora, new_opt, ms = jax.vmap(
            functools.partial(client_local,
                              gamma_static=g if static else None,
                              saved=saved),
            in_axes=(None, 0, 0, 0, None,
                     None if mask_N is None else 0,
                     None if gamma_N is None else 0))(
                base, lora_N, opt_N, batches, round_idx, mask_N, gamma_N)

        sampled = (jnp.ones((n,), bool) if part is None else part > 0)
        in_flight = tau > 0
        trained = sampled & ~in_flight
        local_lora = _tree_where(trained, new_lora, lora_N)
        local_opt = _tree_where(trained, new_opt, opt_N)

        fr = fault_model.sample(k_fault, n)
        attempting = sampled | in_flight
        dropped = attempting & fr["drop"]
        straggling = attempting & ~dropped & fr["straggle"]
        arrived = attempting & ~dropped & ~straggling
        upload = fault_model.corrupt_tree(
            jax.random.fold_in(k_fault, 1), local_lora,
            arrived & fr["corrupt"])

        rejected = jnp.zeros((n,), bool)
        if strat.screen:
            finite = per_client_finite(upload)
            norms = per_client_norm(
                jax.tree.map(lambda u, o: u - o, upload, lora_N))
            cand = arrived & finite
            cnt = cand.sum()
            # judge against the candidate MEDIAN, not the mean: a finite
            # norm-bomb inflates the mean by ~its own norm/N, so at small
            # N it could never exceed mult x mean; the median stays at the
            # clean level for up to half the cohort corrupted
            med = jnp.sort(jnp.where(cand, norms, jnp.inf))[
                jnp.maximum(cnt - 1, 0) // 2]
            outlier = (norms > strat.screen_mult * med) & (cnt > 1)
            rejected = arrived & (~finite | outlier)
        accepted = arrived & ~rejected
        if strat.buffer_size:
            # cap the buffer in client-index order; overflow stays in
            # flight and ages like a straggler
            csum = jnp.cumsum(accepted.astype(jnp.int32))
            in_buf = accepted & (csum <= strat.buffer_size)
            overflow = accepted & ~in_buf
            accepted = in_buf
        else:
            overflow = jnp.zeros((n,), bool)

        disc = (1.0 + tau.astype(jnp.float32)) ** (-strat.beta)
        w_up = accepted.astype(jnp.float32) * disc
        if size_w is not None:
            w_up = w_up * size_w
        # the aggregate's keep=False fallback rows must be the same mixed
        # new/old tree the synchronous engine feeds it — and replacing
        # non-accepted rows also keeps NaN/Inf uploads out of the weighted
        # sums (0 * NaN would still poison them)
        san = _tree_where(accepted, upload, local_lora)
        agg = inner.aggregate(san, round_idx, weights=w_up,
                              rank_mask=mask_N)

        pend = straggling | overflow
        fa, fb = inner.agg_leaf_flags(round_idx)
        out_lora = combine_received(local_lora, agg, ~pend, fa, fb)
        tau_next = jnp.where(pend, tau + 1, 0).astype(tau.dtype)
        mass = (accepted.astype(jnp.float32) * disc).sum()
        n_eff = n * mass / expected
        # floor at one effective client: a fully-lost round must not zero
        # the next round's gammas (maximum(x, 1) == x bitwise at x >= 1,
        # so the staleness-0 path still carries rho == 1.0 exactly)
        rho_next = jnp.sqrt(jnp.maximum(mass, 1.0) / expected)

        metrics = {"loss": ms["loss"].mean(),
                   "grad_norm": ms["grad_norm"].mean(),
                   "n_eff": n_eff, "gamma_scale": rho_next,
                   "delivered": accepted.sum().astype(jnp.float32),
                   "rejected": rejected.sum().astype(jnp.float32),
                   "stale": pend.sum().astype(jnp.float32)}
        if track_update_norm:
            # same form as the synchronous metric — the chunk-start gamma
            # already carries the staleness correction
            g_scale = abs(g) if static else jnp.mean(jnp.abs(gamma_N))
            metrics["update_norm"] = g_scale * global_norm(
                jax.tree.map(lambda a, b: a - b, out_lora, lora_N))
        return (dataclasses.replace(adapters, lora=out_lora), local_opt,
                tau_next, rho_next, metrics)

    return round_body


def make_fed_round_step(model, *, strategy, opt_cfg, donate: bool = True,
                        jit: bool = True):
    """Single-round entry point (back-compat shim over the round body).

    Returns round_step(base, adapters, opt_N, batches, round_idx, weights).
    With ``jit=False`` returns the raw function (multi-device tests wrap it
    in their own pjit with explicit shardings).
    """
    round_step = make_round_body(model, strategy=strategy, opt_cfg=opt_cfg)
    if not jit:
        return round_step
    return jax.jit(round_step, donate_argnums=(1, 2) if donate else ())


def _pin_to_mesh(adapters, opt_N):
    """Hold the chunk's outgoing client state to the placement it came in
    with (client dim over the client axes) when traced under a mesh.  Left
    free, the partitioner may hand back a replicated leaf (FedSA's shared A
    on a TPU 2x2), and the next chunk's new input sharding recompiles."""
    from repro.sharding import rules
    from repro.sharding.specs import current_mesh
    mesh = current_mesh()
    if mesh is None:
        return adapters, opt_N
    wsc = jax.lax.with_sharding_constraint
    lora = wsc(adapters.lora, rules.lora_sharding(adapters.lora, mesh))
    opt_N = wsc(opt_N, rules.lora_sharding(opt_N, mesh))
    return dataclasses.replace(adapters, lora=lora), opt_N


def make_run_chunk(model, *, strategy, opt_cfg, participation: float = 1.0,
                   batch_fn=None, client_weights=None,
                   donate: bool = True, jit: bool = True,
                   track_update_norm: bool = False, fault_model=None):
    """Build the chunked scan executor.

    Returns run_chunk(base, adapters, opt_N, key, round0, batches=None,
    num_rounds=None) -> (adapters, opt_N, key, metrics), where ``adapters``
    is the client-stacked :class:`AdapterSet` the scan carries (A/B tree +
    gamma(s) + rank mask as ONE pytree — the scaling config cannot
    desynchronize from the state it scales).

      - ``key``     carried PRNG key; split once per round inside the scan
                    (participation sampling and on-device batch synthesis
                    both derive from it, so per-round and chunked execution
                    consume randomness identically).
      - ``round0``  traced scalar: global index of the chunk's first round
                    (rolora alternation, schedules, resume).
      - ``batches`` host-staged data with a leading (num_rounds,) dim on
                    every leaf — required unless the engine was built with a
                    ``batch_fn(key, round_idx) -> batches`` that generates
                    data on device inside the scan, in which case the static
                    ``num_rounds`` sets the chunk length.
      - metrics come back stacked: {"loss": (num_rounds,), ...}.

    ``client_weights`` (N,) are static per-client aggregation weights
    (e.g. example counts for size-weighted FedAvg); they compose with the
    sampled participation mask inside the scan.

    ``adapters``/``opt_N``/``key`` are donated when ``jit`` and ``donate``.

    A :class:`~repro.core.aggregation.BufferedStrategy` switches to the
    async buffered engine: the scan additionally carries ``async_state``
    ({"tau": (N,) int32 staleness counters, "rho": scalar f32 gamma
    correction}) and the signature becomes run_chunk(base, adapters,
    opt_N, key, round0, async_state, batches=None, num_rounds=None) ->
    (adapters, opt_N, key, async_state, metrics).  ``fault_model``
    (:class:`~repro.core.faults.FaultModel`) injects deterministic
    drop/straggle/corrupt faults from a per-round key derived from the
    carried scan key — identical to the synchronous key stream, so the
    two engines consume randomness identically at staleness 0.
    """
    from repro.core.aggregation import BufferedStrategy
    strat = get_strategy(strategy)
    buffered = isinstance(strat, BufferedStrategy)
    if fault_model is not None and not buffered:
        raise ValueError(
            "fault injection needs the buffered engine — wrap the "
            "strategy with aggregation.buffered(...) (the synchronous "
            "scan cannot represent an in-flight upload)")
    if buffered:
        round_body = make_buffered_round_body(
            model, strategy=strat, opt_cfg=opt_cfg, fault_model=fault_model,
            track_update_norm=track_update_norm)
    else:
        round_body = make_round_body(model, strategy=strat, opt_cfg=opt_cfg,
                                     track_update_norm=track_update_norm)
    size_w = None if client_weights is None else jnp.asarray(
        client_weights, jnp.float32)

    def run_chunk(base, adapters, opt_N, key, round0, async_state=None,
                  batches=None, num_rounds=None):
        # packed frozen base on the reference tier: dequantize UP FRONT,
        # once per compiled chunk — scan-invariant, so XLA materializes the
        # fp view once instead of per round-step.  Fused tiers keep the base
        # packed (per-tile VMEM dequant inside the kernels).
        if has_quantized(base):
            from repro.kernels import dispatch
            with dispatch.scope(model.cfg.use_pallas):
                if dispatch.resolve_mode() == "reference":
                    base = dequantize_tree(base)
        num_clients = jax.tree.leaves(adapters.lora)[0].shape[0]
        num_sampled = max(1, int(round(participation * num_clients)))
        if buffered and async_state is None:
            raise ValueError(
                "the buffered engine carries async_state — pass "
                "{'tau': (N,) int32, 'rho': f32 scalar} (init: zeros, 1.0)")

        def scan_step(carry, xs):
            if buffered:
                aset_c, opt_c, k, tau_c, rho_c = carry
            else:
                aset_c, opt_c, k = carry
            k, k_round = jax.random.split(k)
            # identical split order to the synchronous engine, then a
            # SEPARATE fold for faults: the data/sampling streams match at
            # staleness 0 and the fault stream is chunking-invariant
            k_data, k_sample = jax.random.split(k_round)
            if batch_fn is None:
                round_idx, b = xs
            else:
                round_idx = xs
                b = batch_fn(k_data, round_idx)
            part = None
            if participation < 1.0:
                part = participation_weights(k_sample, num_clients,
                                             num_sampled)
            if buffered:
                k_fault = jax.random.fold_in(k_round, 7)
                aset_c, opt_c, tau_c, rho_c, metrics = round_body(
                    base, aset_c, opt_c, tau_c, rho_c, b, round_idx,
                    k_fault, part=part, size_w=size_w,
                    expected=num_sampled)
                return (aset_c, opt_c, k, tau_c, rho_c), metrics
            weights = part
            if size_w is not None:
                weights = size_w if weights is None else weights * size_w
            aset_c, opt_c, metrics = round_body(base, aset_c, opt_c, b,
                                                round_idx, weights)
            return (aset_c, opt_c, k), metrics

        if batch_fn is None:
            if batches is None:
                raise ValueError("run_chunk needs `batches` unless the "
                                 "engine was built with a batch_fn")
            n_r = jax.tree.leaves(batches)[0].shape[0]
            xs = (round0 + jnp.arange(n_r), batches)
        else:
            if num_rounds is None:
                raise ValueError("run_chunk needs a static `num_rounds` "
                                 "when batches are generated on device")
            xs = round0 + jnp.arange(num_rounds)
        if buffered:
            carry0 = (adapters, opt_N, key, async_state["tau"],
                      async_state["rho"])
            (adapters, opt_N, key, tau, rho), ms = jax.lax.scan(
                scan_step, carry0, xs)
            adapters, opt_N = _pin_to_mesh(adapters, opt_N)
            return adapters, opt_N, key, {"tau": tau, "rho": rho}, ms
        (adapters, opt_N, key), ms = jax.lax.scan(
            scan_step, (adapters, opt_N, key), xs)
        adapters, opt_N = _pin_to_mesh(adapters, opt_N)
        return adapters, opt_N, key, ms

    if not jit:
        return run_chunk
    return jax.jit(run_chunk, static_argnames=("num_rounds",),
                   donate_argnums=((1, 2, 3, 5) if buffered else (1, 2, 3))
                   if donate else ())


@dataclasses.dataclass(frozen=True)
class WatchdogConfig:
    """Collapse-watchdog policy for :class:`FederatedTrainer`.

    At every chunk boundary the watchdog judges the chunk's per-round
    ``update_norm`` series with ``stability_report`` (Theorem 4.2).  On a
    failed verdict it rolls the trainer back to the last-good snapshot
    (taken before the chunk) and retries with a recovery action chosen by
    :func:`repro.analysis.stability_check.recovery_action`:

      - ``rescale`` (config half violated): adopt the paper's own fix,
        gamma = alpha*sqrt(N/r) — a mis-scaled gamma is deterministic in
        (gamma, r, N); no amount of retrying fixes it.  Disabled via
        ``rescale_gamma=False`` (then every recovery is a backoff).
      - ``backoff`` (measured drift): multiply participation by
        ``backoff`` (floored at one client) and advance the fault seed,
        so the retry samples a smaller, fresh cohort.

    After ``max_retries`` failed retries of the same chunk the watchdog
    raises :class:`~repro.analysis.stability_check.ScalingCollapseError`.
    Verdicts need >= 2 norms, so chunks of one round are judged on the
    trailing window only once enough history exists.
    """
    max_retries: int = 2
    backoff: float = 0.5
    rescale_gamma: bool = True
    scale_tol: float = 4.0
    trend_tol: float = 8.0


class FederatedTrainer:
    """Host-level orchestration: state, chunked rounds, evaluation.

    ``data_mode``:
      "host"    batches come from ``dataset.round_batch`` on the host and are
                staged per chunk as stacked scan inputs (default — preserves
                the exact host data stream).
      "device"  batches are synthesized inside the scan from the carried PRNG
                key via :class:`repro.data.synthetic.DeviceFederatedData`
                (same topic tables as the host dataset; zero host traffic —
                the large-N stress-test path).

    ``chunk_rounds`` caps how many rounds one ``run_chunk`` call scans over
    (default: the ``log_every`` stride, else the whole ``run`` request).
    ``mesh``: when given, base params are tensor-sharded and the client dim of
    LoRA/optimizer state shards over the mesh's client axes ("pod"/"data")
    per ``sharding/rules.py``.

    Heterogeneous clients: ``lora_cfg.ranks`` (one rank per client) switches
    to the padded-rank representation — every client allocates
    r_max = max(ranks), a per-client rank mask keeps the extra rows inert
    (zero-init, grad-masked, excluded from and re-masked after aggregation),
    and each client trains/serves with its own gamma_i = scaling(alpha, r_i,
    N).  ``fed_cfg.weight_by_size`` additionally weights the server mean by
    the dataset's per-client example counts (``dataset.size_weights``).
    With all ranks equal this path is bit-identical to the homogeneous
    engine (tests/test_conformance.py).

    Under :func:`repro.analysis.trace.tracing` each chunk is a ``fed.chunk``
    span (attrs ``rounds``, ``round0``) holding ``fed.stage`` (the
    ``round_batch`` calls and their stack), ``fed.upload`` (the batches'
    copy to the device), ``fed.call`` (the engine call, an enqueue) and
    ``fed.sync`` (the wait for the chunk's metrics); the watchdog's host
    copy is ``fed.snapshot``.  Counters: ``fed.rounds``, ``fed.retries``.
    """

    def __init__(self, model, dataset, *, lora_cfg, fed_cfg, opt_cfg,
                 seed: int = 0, base_params=None, data_mode: str = "host",
                 chunk_rounds: int = 0, mesh=None,
                 track_stability: bool = False, watchdog=None):
        self.model = model
        self.dataset = dataset
        self.fed_cfg = fed_cfg
        self.opt_cfg = opt_cfg
        self.data_mode = data_mode
        self.chunk_rounds = chunk_rounds
        self.mesh = mesh
        # the collapse watchdog judges every chunk, so it needs the
        # update_norm metric the sentinel consumes
        self.watchdog = watchdog
        self.watchdog_events = []
        # opt-in per-round update_norm metric feeding stability_report();
        # off by default so the engine's metrics treedef stays pinned
        self.track_stability = track_stability or watchdog is not None
        # async buffered engine: an explicit buffer config or any fault
        # injection switches the scan to the FedBuff-style round body
        self.async_mode = (fed_cfg.buffer_size is not None
                           or fed_cfg.faults is not None)
        n = fed_cfg.num_clients
        ranks = lora_cfg.ranks
        if ranks is not None:
            # heterogeneous per-client ranks: padded representation at
            # r_max with a per-client rank mask (see core/lora.py)
            ranks = tuple(int(r) for r in ranks)
            if len(ranks) != n:
                raise ValueError(
                    f"lora_cfg.ranks has {len(ranks)} entries but "
                    f"num_clients={n}")
            self.ranks = ranks
            self.rank_mask = rank_mask(ranks)
            self.gammas = per_client_gammas(lora_cfg.scaling, lora_cfg.alpha,
                                            ranks, n)
            # uniform gamma stays a concrete float (and the engine's static
            # fast path); truly mixed gammas have no single value
            self.gamma = (self.gammas[0]
                          if len(set(self.gammas)) == 1 else None)
            lora_cfg = dataclasses.replace(lora_cfg, rank=max(ranks))
        else:
            self.ranks = None
            self.rank_mask = None
            self.gamma = scaling_factor(lora_cfg.scaling, lora_cfg.alpha,
                                        lora_cfg.rank, n)
            self.gammas = (self.gamma,) * n
        self.lora_cfg = lora_cfg      # reflects the padded rank when het
        key = jax.random.key(seed)
        kb, kl = jax.random.split(key)
        if base_params is not None:
            self.base = base_params
        elif mesh is not None:
            # initialize straight into the mesh placement: an eager init
            # materializes the whole base on one device before resharding,
            # which a base larger than one chip's HBM cannot survive
            from repro.sharding import rules
            shapes = jax.eval_shape(model.init, kb)
            # lint: disable=R2 -- runs once per trainer, not per step; the out_shardings belong to this trainer's mesh
            self.base = jax.jit(
                model.init,
                out_shardings=rules.params_sharding(shapes, mesh))(kb)
        else:
            self.base = model.init(kb)
        lora1 = init_lora(self.base, kl, lora_cfg,
                          targets=lora_cfg.targets)
        # FedSA init: all clients start from the SAME A (and B=0)
        self.lora = jax.tree.map(
            lambda x: jnp.broadcast_to(x, (n,) + x.shape).copy(), lora1)
        if self.rank_mask is not None:
            # client i's rows r_i..r_max of A start (and stay) exactly zero
            self.lora = apply_rank_mask(self.lora, self.rank_mask)
        opt_init, _ = make_optimizer(opt_cfg)
        opt1 = opt_init(lora1)
        self.opt_state = jax.tree.map(
            lambda x: jnp.broadcast_to(x, (n,) + x.shape).copy(), opt1)
        self.client_weights = None
        if fed_cfg.weight_by_size:
            if not hasattr(dataset, "size_weights"):
                raise ValueError(
                    "fed_cfg.weight_by_size needs a dataset exposing "
                    "size_weights (per-client example counts)")
            self.client_weights = jnp.asarray(dataset.size_weights,
                                              jnp.float32)

        if data_mode == "device":
            from repro.data.synthetic import DeviceFederatedData
            self.device_data = DeviceFederatedData.from_host(dataset)
        elif data_mode != "host":
            raise ValueError(f"unknown data_mode '{data_mode}'")
        self._build_engine()
        # async carry: per-client staleness counters + the gamma correction
        # factor rho = sqrt(N_eff/N) (1.0 = fully synchronous)
        self.async_state = None
        # the staleness correction the NEXT chunk's gamma is folded with
        # (quantized host mirror of async_state["rho"]; 1.0 = synchronous)
        self._rho_host = 1.0
        if self.async_mode:
            self.async_state = {"tau": jnp.zeros((n,), jnp.int32),
                                "rho": jnp.asarray(1.0, jnp.float32)}
        # all round-level randomness (participation sampling, device-side
        # data) flows from this carried JAX key — no separate host RNG
        self._key = jax.random.key(seed + 31337)
        self.round_idx = 0
        self.history = []
        if mesh is not None:
            self._place_on_mesh(mesh)
        # cached so repeated evals reuse one compilation (a float gamma
        # rides in the AdapterSet treedef, so it stays trace-static — the
        # fused kernel tier's requirement — and each distinct gamma gets
        # its own executable, exactly like the old static_argnames path)
        self._eval_loss = jax.jit(
            lambda p, b, adapters: model.loss(p, b, adapters=adapters))

    def _build_engine(self):
        """(Re)build the compiled chunk executor from the current config,
        rank mask, size weights, and (device mode) data tables.  ``restore``
        calls this again when the checkpointed data partition differs from
        the constructed one — the old executor's baked-in weights/tables
        would otherwise silently go stale."""
        batch_fn = None
        if self.data_mode == "device":
            device_data = self.device_data
            local_steps = self.fed_cfg.local_steps
            batch_fn = lambda k, ridx: {
                "tokens": device_data.sample_round(k, local_steps)}
        strategy = self.fed_cfg.aggregation
        fault_model = None
        if self.async_mode:
            from repro.core.aggregation import buffered
            from repro.core.faults import FaultModel
            strategy = buffered(
                strategy, buffer_size=self.fed_cfg.buffer_size or 0,
                beta=self.fed_cfg.staleness_beta,
                screen=self.fed_cfg.screen_updates,
                screen_mult=self.fed_cfg.screen_norm_mult)
            fault_model = FaultModel(self.fed_cfg.faults)
        self._run_chunk = make_run_chunk(
            self.model, strategy=strategy,
            opt_cfg=self.opt_cfg,
            participation=self.fed_cfg.participation, batch_fn=batch_fn,
            client_weights=self.client_weights, donate=True,
            track_update_norm=self.track_stability,
            fault_model=fault_model)

    @functools.cached_property
    def round_step(self):
        """Single-round entry over externally supplied batches (callers with
        modality stubs the synthetic dataset cannot produce):
        round_step(base, adapters, opt_N, batches, round_idx, weights=None)
        with ``adapters`` a client-stacked AdapterSet (``trainer.adapters``).
        Compiled lazily — the engine itself runs through ``run_chunk``."""
        return make_fed_round_step(
            self.model, strategy=self.fed_cfg.aggregation,
            opt_cfg=self.opt_cfg, donate=False)

    # ------------------------------------------------------------- adapters

    @property
    def adapters(self) -> AdapterSet:
        """The trainer's client-stacked AdapterSet: the A/B state plus the
        per-client scaling factors and rank mask as one pytree — the unit
        the engine carries, checkpoints serialize, and serving registers
        into an AdapterBank."""
        gamma = self.gammas if self.ranks is not None else self.gamma
        return AdapterSet(lora=self.lora, gamma=gamma,
                          rank_mask=self.rank_mask,
                          rank=self.lora_cfg.rank, alpha=self.lora_cfg.alpha)

    def client_adapters(self, client: int) -> AdapterSet:
        """Client ``client``'s personalized AdapterSet (own gamma_i and
        rank-mask row) — what that client deploys."""
        mask = None if self.rank_mask is None else self.rank_mask[client]
        r = self.ranks[client] if self.ranks else self.lora_cfg.rank
        return AdapterSet(
            lora=jax.tree.map(lambda x: x[client], self.lora),
            gamma=self.gammas[client], rank_mask=mask, rank=int(r),
            alpha=self.lora_cfg.alpha)

    # ------------------------------------------------------------- sharding

    def _place_on_mesh(self, mesh):
        from repro.sharding import rules
        self.base = jax.device_put(self.base,
                                   rules.params_sharding(self.base, mesh))
        self.lora = jax.device_put(self.lora,
                                   rules.lora_sharding(self.lora, mesh))
        self.opt_state = jax.device_put(
            self.opt_state, rules.lora_sharding(self.opt_state, mesh))
        # the scan key and async counters come back from a chunk replicated
        # over the mesh: start them there, or the second chunk recompiles
        rep = NamedSharding(mesh, P())
        self._key = jax.device_put(self._key, rep)
        if self.async_state is not None:
            self.async_state = jax.device_put(self.async_state, rep)

    def _mesh_scope(self):
        if self.mesh is None:
            return contextlib.nullcontext()
        from repro.sharding.specs import use_mesh
        return use_mesh(self.mesh)

    # -------------------------------------------------------------- running

    def _stage_batches(self, num_rounds: int):
        """Host data for the next ``num_rounds`` rounds, stacked for the
        scan: leaves (num_rounds, N, local_steps, batch, seq)."""
        with trace.span("fed.stage"):
            nb = np.stack([self.dataset.round_batch(self.fed_cfg.local_steps)
                           for _ in range(num_rounds)])
        with trace.span("fed.upload"):
            batches = {"tokens": jnp.asarray(nb)}
            if self.mesh is not None:
                from repro.sharding import rules
                batches = jax.device_put(
                    batches, rules.chunked_inputs_sharding(batches,
                                                           self.mesh))
        return batches

    def _train_adapters(self) -> AdapterSet:
        """The AdapterSet the next chunk trains with: the configured
        adapters, with the staleness correction rho folded into gamma
        (gamma_eff = gamma * rho, Theorem 4.2's alpha*sqrt(N_eff/r)).
        The fold is STATIC — gamma rides the treedef — so the staleness-0
        path (rho == 1.0) reuses the synchronous executable bit-exactly."""
        aset = self.adapters
        if self.async_state is None or self._rho_host == 1.0:
            return aset
        g = aset.gamma
        g = (tuple(x * self._rho_host for x in g) if isinstance(g, tuple)
             else g * self._rho_host)
        return dataclasses.replace(aset, gamma=g)

    def _run_one_chunk(self, num_rounds: int):
        with trace.span("fed.chunk", rounds=num_rounds,
                        round0=self.round_idx):
            kwargs = {}
            if self.data_mode == "device":
                kwargs["num_rounds"] = num_rounds
            else:
                kwargs["batches"] = self._stage_batches(num_rounds)
            with self._mesh_scope(), trace.span("fed.call"):
                if self.async_mode:
                    (aset, self.opt_state, self._key, self.async_state,
                     ms) = self._run_chunk(
                        self.base, self._train_adapters(), self.opt_state,
                        self._key, jnp.asarray(self.round_idx, jnp.int32),
                        self.async_state, **kwargs)
                else:
                    aset, self.opt_state, self._key, ms = self._run_chunk(
                        self.base, self.adapters, self.opt_state, self._key,
                        jnp.asarray(self.round_idx, jnp.int32), **kwargs)
            # only the A/B tree is engine state (gamma/rank mask are static
            # config riding in the AdapterSet treedef — the trainer keeps its
            # own uniform-rank mask even though the canonical AdapterSet form
            # collapses an all-ones mask to None)
            self.lora = aset.lora
            with trace.span("fed.sync"):
                if self.async_mode:
                    self._rho_host = _quantize_rho(
                        float(self.async_state["rho"]))
                ms = {k: np.asarray(v) for k, v in ms.items()}
            trace.count("fed.rounds", num_rounds)
            out = []
            for i in range(num_rounds):
                self.round_idx += 1
                m = {k: float(v[i]) for k, v in ms.items()}
                m["round"] = self.round_idx
                self.history.append(m)
                out.append(m)
            return out

    # ------------------------------------------------------------- watchdog

    def _snapshot(self):
        """Host copy of everything a chunk mutates — taken BEFORE the
        chunk runs (the engine donates its device buffers, so the copies
        must leave the device first)."""
        host = lambda t: jax.tree.map(lambda x: np.asarray(x), t)
        snap = {"lora": host(self.lora), "opt": host(self.opt_state),
                "key": np.asarray(jax.random.key_data(self._key)),
                "round": self.round_idx, "hist": len(self.history),
                "events": len(self.watchdog_events),
                "rho_host": self._rho_host}
        if self.async_state is not None:
            snap["async"] = host(self.async_state)
        if self.data_mode == "host" and hasattr(self.dataset, "rng_state"):
            snap["data_state"] = self.dataset.rng_state()
        return snap

    def _rollback(self, snap):
        """Restore the last-good snapshot (state, PRNG streams, history)."""
        dev = lambda t: jax.tree.map(jnp.asarray, t)
        self.lora = dev(snap["lora"])
        self.opt_state = dev(snap["opt"])
        self._key = jax.random.wrap_key_data(jnp.asarray(snap["key"]))
        self.round_idx = snap["round"]
        del self.history[snap["hist"]:]
        self._rho_host = snap["rho_host"]
        if "async" in snap:
            self.async_state = {
                "tau": jnp.asarray(snap["async"]["tau"], jnp.int32),
                "rho": jnp.asarray(snap["async"]["rho"], jnp.float32)}
        if "data_state" in snap and hasattr(self.dataset, "set_rng_state"):
            self.dataset.set_rng_state(snap["data_state"])
        if self.mesh is not None:
            self._place_on_mesh(self.mesh)

    def _chunk_report(self, chunk_len: int):
        """Stability verdict over the chunk just run (its own norms only —
        a mid-run gamma rescale must not make the trend straddle two
        scaling regimes).  Falls back to the trailing two-round window for
        chunks of one; None when there is not enough history yet."""
        wd = self.watchdog
        norms = [h["update_norm"] for h in self.history
                 if "update_norm" in h]
        norms = norms[-max(chunk_len, 2):]
        if len(norms) < 2:
            return None
        from repro.analysis.stability_check import stability_report
        gamma = (self.gamma if self.gamma is not None
                 else float(np.mean(self.gammas)))
        return stability_report(
            norms, gamma=gamma, r=self.lora_cfg.rank,
            n_clients=self.fed_cfg.num_clients, alpha=self.lora_cfg.alpha,
            scale_tol=wd.scale_tol, trend_tol=wd.trend_tol)

    def _recover(self, report, retries: int):
        """Apply the retry policy for a failed chunk verdict."""
        from repro.analysis.stability_check import recovery_action
        wd = self.watchdog
        action = recovery_action(report, scale_tol=wd.scale_tol)
        n = self.fed_cfg.num_clients
        if action == "rescale" and wd.rescale_gamma:
            # adopt the paper's factor: gamma = alpha*sqrt(N/r) (per-client
            # gamma_i under heterogeneous ranks).  gamma rides in the
            # AdapterSet treedef, so the next chunk recompiles once with
            # the new static scale — no engine rebuild needed.
            if self.ranks is not None:
                self.gammas = per_client_gammas(
                    "sfedlora", self.lora_cfg.alpha, self.ranks, n)
                self.gamma = (self.gammas[0]
                              if len(set(self.gammas)) == 1 else None)
            else:
                self.gamma = scaling_factor(
                    "sfedlora", self.lora_cfg.alpha, self.lora_cfg.rank, n)
                self.gammas = (self.gamma,) * n
            self.lora_cfg = dataclasses.replace(self.lora_cfg,
                                                scaling="sfedlora")
            detail = f"gamma->{(self.gamma or self.gammas[0]):.4g} (sfedlora)"
        else:
            action = "backoff"
            p = max(self.fed_cfg.participation * wd.backoff, 1.0 / n)
            faults = self.fed_cfg.faults
            if faults is not None:
                faults = dataclasses.replace(faults, seed=faults.seed + 1)
            self.fed_cfg = dataclasses.replace(self.fed_cfg,
                                               participation=p,
                                               faults=faults)
            # participation and the fault seed are baked into the compiled
            # scan — rebuild (rare: only on a recovery event)
            self._build_engine()
            detail = f"participation->{p:.3g}, fault seed advanced"
        self.watchdog_events.append(
            {"round": self.round_idx, "verdict": report.verdict,
             "action": action, "detail": detail, "retry": retries + 1})

    def _run_chunk_watched(self, chunk: int):
        """Run one chunk under the watchdog: snapshot, run, judge; on a
        failed verdict roll back, recover, retry (bounded)."""
        if self.watchdog is None:
            return self._run_one_chunk(chunk)
        from repro.analysis.stability_check import ScalingCollapseError
        retries = 0
        while True:
            with trace.span("fed.snapshot"):
                snap = self._snapshot()
            out = self._run_one_chunk(chunk)
            report = self._chunk_report(chunk)
            if report is None or report.ok:
                return out
            if retries >= self.watchdog.max_retries:
                raise ScalingCollapseError(
                    f"watchdog: chunk ending at round {self.round_idx} "
                    f"still '{report.verdict}' after {retries} "
                    f"retries: {report}")
            self._rollback(snap)
            self._recover(report, retries)
            retries += 1
            trace.count("fed.retries")

    # -------------------------------------------------------------- running

    def run_round(self):
        """One federated round (a chunk of one — same compiled round body as
        chunked execution, so the two stay bit-identical)."""
        return self._run_chunk_watched(1)[0]

    def run(self, rounds=None, log_every: int = 0):
        # each distinct chunk length compiles its own scan; a trailing
        # partial chunk (rounds % stride != 0) therefore costs one extra
        # compile — pick chunk_rounds dividing the round budget to avoid it
        rounds = rounds or self.fed_cfg.rounds
        done = 0
        while done < rounds:
            chunk = min(self.chunk_rounds or log_every or rounds,
                        rounds - done)
            for m in self._run_chunk_watched(chunk):
                if log_every and m["round"] % log_every == 0:
                    print(f"round {m['round']:4d}  loss {m['loss']:.4f}  "
                          f"|g| {m['grad_norm']:.3e}  "
                          f"ppl {np.exp(m['loss']):.2f}")
            done += chunk
        return self.history

    def client_gamma(self, client: int) -> float:
        """The scaling factor client ``client`` trains and serves with
        (gamma_i = scaling(alpha, r_i, N) under heterogeneous ranks)."""
        return self.gammas[client]

    @property
    def gamma_eff(self) -> float:
        """The staleness-corrected scaling factor the NEXT chunk trains
        with: gamma * rho where rho = sqrt(N_eff/N) from the last buffered
        round, quantized for the static treedef fold (1.0 — i.e. plain
        gamma — when synchronous or before any round has run)."""
        base = (self.gamma if self.gamma is not None
                else float(np.mean(self.gammas)))
        return base * self._rho_host

    def stability_report(self, **kwargs):
        """Judge the run's per-round ``update_norm`` series against the
        Theorem 4.2 moment-scale prediction (requires
        ``track_stability=True``; see repro.analysis.stability_check)."""
        from repro.analysis.stability_check import stability_report
        norms = [h["update_norm"] for h in self.history
                 if "update_norm" in h]
        if len(norms) < 2:
            raise ValueError(
                "stability_report needs >= 2 rounds of update_norm history "
                "— construct the trainer with track_stability=True and run "
                "at least two rounds")
        gamma = (self.gamma if self.gamma is not None
                 else float(np.mean(self.gammas)))
        return stability_report(
            norms, gamma=gamma, r=self.lora_cfg.rank,
            n_clients=self.fed_cfg.num_clients, alpha=self.lora_cfg.alpha,
            **kwargs)

    def publish_adapters(self, live, clients=None) -> int:
        """Push the current round's adapters into a live serving bank.

        ``live`` is a :class:`~repro.core.lora.LiveAdapterBank`; each
        client's personalized AdapterSet (own gamma_i folded in, rank-mask
        row applied) is published under its client index as the tenant id.
        Resident tenants hot-swap on device between decode chunks with zero
        recompiles; the rest land in the host store.  Returns the number of
        tenants published."""
        clients = range(self.fed_cfg.num_clients) if clients is None else clients
        n = 0
        for c in clients:
            live.publish(int(c), self.client_adapters(int(c)))
            n += 1
        return n

    def eval_perplexity(self, batch: int = 16, client: int = 0) -> float:
        """Held-out perplexity using client ``client``'s personalized model."""
        toks = jnp.asarray(self.dataset.eval_batch(batch))
        loss, _ = self._eval_loss(self.base, {"tokens": toks},
                                  self.client_adapters(client))
        return float(jnp.exp(loss))

    # ----------------------------------------------------------- checkpoint

    def save(self, path: str) -> None:
        """Checkpoint state + round index + PRNG key (+ the host dataset's
        RNG stream state, the per-client rank mask, and the data-partition
        state) so a restored run continues bit-exactly.  The whole
        AdapterSet round-trips: gammas/alpha/ranks/scaling ride along as
        ``adapter_meta`` so serving can rebuild it without the trainer."""
        from repro.checkpoint.io import save_federated_state
        data_state = None
        if self.data_mode == "host" and hasattr(self.dataset, "rng_state"):
            data_state = self.dataset.rng_state()
        partition_state = None
        if hasattr(self.dataset, "partition_state"):
            partition_state = self.dataset.partition_state()
        meta = {
            "gammas": np.asarray(self.gammas, np.float32),
            "alpha": float(self.lora_cfg.alpha),
            "rank": int(self.lora_cfg.rank),
            "ranks": np.asarray(self.ranks if self.ranks is not None
                                else (self.lora_cfg.rank,)
                                * self.fed_cfg.num_clients, np.int64),
            "scaling": self.lora_cfg.scaling,
        }
        async_state = None
        if self.async_state is not None:
            async_state = {k: np.asarray(v)
                           for k, v in self.async_state.items()}
        save_federated_state(path, self.base, self.lora, self.opt_state,
                             self.round_idx, key=self._key,
                             data_state=data_state,
                             rank_mask=self.rank_mask,
                             partition_state=partition_state,
                             adapter_meta=meta,
                             async_state=async_state)

    def restore(self, path: str) -> None:
        from repro.checkpoint.io import load_federated_state
        base, lora, opt, rnd, key, data_state, extras = load_federated_state(
            path, full=True)
        ck_mask = extras.get("rank_mask")
        if (ck_mask is None) != (self.rank_mask is None) or (
                ck_mask is not None and not np.array_equal(
                    np.asarray(ck_mask), np.asarray(self.rank_mask))):
            raise ValueError(
                "checkpoint per-client rank mask does not match this "
                "trainer's configured ranks — restore with the same "
                "lora_cfg.ranks the run was saved with")
        if "partition_state" in extras and hasattr(self.dataset,
                                                   "set_partition_state"):
            unchanged = (hasattr(self.dataset, "partition_state") and
                         self.dataset.partition_state()
                         == extras["partition_state"])
            self.dataset.set_partition_state(extras["partition_state"])
            if not unchanged:
                # the compiled engine baked in the constructed partition's
                # size weights / device data tables — rebuild it so the
                # resumed run aggregates under the CHECKPOINTED partition
                if self.data_mode == "device":
                    from repro.data.synthetic import DeviceFederatedData
                    self.device_data = DeviceFederatedData.from_host(
                        self.dataset)
                if self.client_weights is not None:
                    self.client_weights = jnp.asarray(
                        self.dataset.size_weights, jnp.float32)
                self._build_engine()
        self.base, self.lora, self.opt_state = base, lora, opt
        self.round_idx = rnd
        # drop history entries from beyond the restored round so consumers
        # never mix two timelines
        self.history = [h for h in self.history if h["round"] <= rnd]
        if key is not None:
            self._key = key
        if data_state is not None and hasattr(self.dataset, "set_rng_state"):
            self.dataset.set_rng_state(data_state)
        if self.async_mode:
            ck_async = extras.get("async_state")
            if ck_async is not None:
                self.async_state = {
                    "tau": jnp.asarray(ck_async["tau"], jnp.int32),
                    "rho": jnp.asarray(ck_async["rho"], jnp.float32)}
            else:
                # legacy (synchronous-era) checkpoint: fresh async carry
                self.async_state = {
                    "tau": jnp.zeros((self.fed_cfg.num_clients,), jnp.int32),
                    "rho": jnp.asarray(1.0, jnp.float32)}
            # the fold mirror is derived, not stored — recompute it so the
            # resumed chunk trains with the same gamma_eff the
            # uninterrupted run would have used
            self._rho_host = _quantize_rho(float(self.async_state["rho"]))
        if self.mesh is not None:
            self._place_on_mesh(self.mesh)
