"""Multi-tenant batched LoRA serving from an AdapterBank.

One engine serves every request: the continuous-batching scheduler
:func:`serve_scheduled`.  It serves a STREAM of requests over a fixed set of
engine slots:

  * KV state lives in per-layer SHARED block pools (``init_paged_cache``)
    addressed through a per-slot block table.  :class:`BlockPool` hands
    blocks out and takes them back on the host, so a finished request's
    memory is reusable the moment it completes.
  * Admission is one jitted prefill per same-length newcomer group
    (:func:`_jit_paged_admit`) on a VIEW whose pools ARE the engine pools
    and whose per-slot state is fresh (``transformer.paged_prefill_view``);
    merging scatters the newcomers' slot state back without touching
    continuing requests.  It emits each newcomer's first token.
  * Decode runs in CHUNKS (:func:`_jit_paged_chunk`): one jitted lax.scan
    of ``chunk`` greedy steps over all engine slots, active or not — idle
    slots' table rows point at the reserved null block 0, so their
    discarded writes land where no live request ever looks.  Between chunks
    the host admits newly-arrived requests into free slots and evicts
    finished ones.

Decoding is greedy over the real vocabulary.  The bank's adapters reach the
engine through ``AdapterBank.requests(ids)`` — the LAZY per-request view:
adapter leaves stay tenant-stacked and each projection gathers its own rows
(in-kernel via the BGMV tier's ids-indexed BlockSpecs on fused tiers), so
serving K heterogeneous-rank tenants never materializes per-request copies
of the bank.

  # a burst of --batch requests over fresh random adapters (API smoke):
  PYTHONPATH=src python -m repro.launch.serve --arch gemma-2b --reduced \
      --steps 16 --batch 8 --clients 4

  # serve a TRAINED federated checkpoint (every client becomes a tenant):
  PYTHONPATH=src python -m repro.launch.train --reduced --save /tmp/ck.npz ...
  PYTHONPATH=src python -m repro.launch.serve --arch gemma-2b --reduced \
      --resume /tmp/ck.npz --steps 16 --batch 8

  # a seeded Poisson request stream:
  PYTHONPATH=src python -m repro.launch.serve --arch gemma-2b --reduced \
      --arrival-trace poisson:20:32 --steps 16

``--merge CLIENT`` folds one client's adapters into the base weights and
serves that single tenant with no adapter work at all.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.analysis import trace
from repro.analysis.hostcheck import check_adapter_ids
from repro.analysis.sanitizers import guard_transfers
from repro.checkpoint.io import load_adapter_state
from repro.configs import ARCHS, get_config
from repro.configs.base import LoRAConfig
from repro.core.lora import (AdapterBank, AdapterSet, LiveAdapterBank,
                             init_adapter_set)
from repro.core.quant import (QuantizedLinear, apply_quant_flag,
                              dequantize_tree, has_quantized,
                              requantize_merged)
from repro.kernels import dispatch
from repro.launch.compile_cache import enable_compile_cache
from repro.models.api import build_model
from repro.models.transformer import (merge_paged_cache, paged_prefill_view,
                                      reset_paged_blocks)

def _model_jit(model, name: str, builder):
    """Per-model jit cache stored ON the model object itself.

    The previous ``functools.lru_cache(maxsize=None)`` keyed on Model
    instances pinned every model (and its compiled executables) for process
    lifetime.  An attribute cache makes the model own its executables: the
    model <-> jitted-fn reference cycle is ordinary gc-collectable garbage,
    so dropping the model frees everything (regression-tested)."""
    cache = model.__dict__.setdefault("_serve_jit_cache", {})
    fn = cache.get(name)
    if fn is None:
        fn = builder(model)
        cache[name] = fn
    return fn


# ------------------------------------------------------------ engine programs

def _prepare_base(m, params):
    """Loop-invariant handling of a packed frozen base (core/quant.py).

    On the REFERENCE tier the policy is dequantize-up-front: doing it here,
    once per compiled call, makes the fp view scan-invariant — XLA
    materializes it once instead of re-dequantizing every decode step
    (mirrors the federated engine's run_chunk hoist).  Fused tiers return
    the params untouched: the kernels dequantize per-tile in VMEM and the
    packed bytes are exactly what keeps decode bandwidth-cheap."""
    if not has_quantized(params):
        return params
    with dispatch.scope(m.cfg.use_pallas):
        if dispatch.resolve_mode() == "reference":
            return dequantize_tree(params)
    return params


# matmul precisions at which XLA's dot on a TPU rounds float32 operands to
# bfloat16 in one pass (None is the default)
_ONE_BF16_PASS = (None, "default", "fastest", "bfloat16")

_to_bf16 = jax.jit(lambda ws: [w.astype(jnp.bfloat16) for w in ws])


def _dots_round_to_bf16(m) -> bool:
    """Whether every dot the engines run on the frozen base already rounds
    a float32 weight to bfloat16: XLA's dots (the reference kernel tier; a
    Pallas kernel makes its own) on a TPU at the default matmul precision."""
    if jax.default_backend() != "tpu":
        return False
    if jax.config.jax_default_matmul_precision not in _ONE_BF16_PASS:
        return False
    with dispatch.scope(m.cfg.use_pallas):
        return dispatch.resolve_mode() == "reference"


def serving_base(m, params):
    """The frozen base as one serving run reads it, made once per run.

    Where every dot already rounds a float32 weight to bfloat16
    (:func:`_dots_round_to_bf16`), the float32 leaves the model reads only
    as dot operands (``Model.dot_only``: the projections and an untied head)
    are cast to bfloat16 in one jitted call.  Each dot then gets the operand
    it made itself before, so the tokens are the same, while no engine call
    converts a weight again and no step reads one at 4 bytes.  The
    embedding, norms, packed (QuantizedLinear) and narrower leaves pass
    through; elsewhere ``params`` comes back as it is.  Recorded as the
    span ``serve.prepare`` (``leaves``, ``bytes``) and the counter
    ``serve.base_bf16_leaves``."""
    flat, treedef = jax.tree_util.tree_flatten_with_path(
        params, is_leaf=lambda x: isinstance(x, QuantizedLinear))
    picked = []
    if _dots_round_to_bf16(m):
        picked = [i for i, (path, leaf) in enumerate(flat)
                  if not isinstance(leaf, QuantizedLinear)
                  and leaf.dtype == jnp.float32
                  and m.dot_only(tuple(getattr(k, "key", k) for k in path))]
    nbytes = sum(flat[i][1].size * 2 for i in picked)
    trace.count("serve.base_bf16_leaves", len(picked))
    if not picked:
        return params
    with trace.span("serve.prepare", leaves=len(picked), bytes=nbytes):
        leaves = [leaf for _, leaf in flat]
        cast = jax.block_until_ready(_to_bf16([leaves[i] for i in picked]))
        for i, w in zip(picked, cast):
            leaves[i] = w
        return treedef.unflatten(leaves)


def _prepare_adapters(m, adapters):
    """Loop-invariant adapter preparation, shared by both engine programs:
    gamma folds, rank masking, the bank's per-request gather,
    and the (K, layers) -> (layers, K) scan relayout all run ONCE per
    compiled call — left inside decode_step they re-run EVERY token (XLA
    does not hoist the relayout transposes or gathers out of a scan;
    together ~2MB of copies per step at bench scale).  The ids are fixed
    for the whole call, so the lazy bank view materializes its request rows
    here — one (B, ...) gather; decode_step then consumes a prepared
    pass-through tree.  (The in-kernel BGMV gather still serves direct
    decode_step/prefill callers, where ids change per step.)"""
    if (adapters is not None and adapters.batched
            and adapters.ids is not None):
        adapters = dataclasses.replace(
            adapters,
            lora=jax.tree.map(lambda x: x[adapters.ids], adapters.lora),
            ids=None)
    tree = m._stack_adapters(adapters)
    return None if tree is None else AdapterSet(lora={"stack": tree})


# ----------------------------------------------- continuous-batching scheduler

class BlockPool:
    """Host-side free-list allocator over the paged cache's block axis.

    Block 0 is the NULL block: idle engine slots' table rows point at it,
    so their discarded decode writes land in a block no live request owns.
    It is never handed out — `alloc` serves blocks 1..num_blocks-1 only."""

    def __init__(self, num_blocks: int):
        if num_blocks < 2:
            raise ValueError(f"need >= 2 blocks (one is the reserved null "
                             f"block), got {num_blocks}")
        self.num_blocks = num_blocks
        self._free = list(range(num_blocks - 1, 0, -1))
        self._held = set()

    @property
    def available(self) -> int:
        return len(self._free)

    def alloc(self, n: int):
        """n blocks, or None if the pool can't cover them (caller defers
        admission — nothing is partially allocated)."""
        if n > len(self._free):
            return None
        blocks = [self._free.pop() for _ in range(n)]
        self._held.update(blocks)
        return blocks

    def free(self, blocks) -> None:
        blocks = list(blocks)
        bad = [b for b in blocks if b not in self._held]
        if bad or len(set(blocks)) != len(blocks):
            raise ValueError(f"freeing blocks not held (double free?): "
                             f"{bad or blocks}")
        for b in blocks:
            self._held.discard(b)
            self._free.append(b)


@dataclasses.dataclass
class Request:
    """One generation request for the scheduler.  ``steps`` counts generated
    tokens (prompt excluded); ``arrival`` is seconds
    from scheduler start.  ``adapter_id`` is the TENANT identity — a row of
    a static AdapterBank, or a store tenant of a LiveAdapterBank (which may
    live in host RAM until this request promotes it); it is validated at
    the host boundary, never clamped.  The scheduler fills the bookkeeping
    fields: ``tokens`` (the generated ids, first token included),
    ``t_first`` / ``t_done`` (completion-relative timestamps for latency
    metrics).

    ``deadline_steps`` caps how many tokens the scheduler will spend on
    this request before evicting it at the next chunk boundary (graceful
    degradation under load): a request that hits the cap finishes with
    its tokens truncated, ``timed_out`` set, and the ``serve.timeouts``
    trace counter bumped — its slot and blocks recycle immediately."""
    rid: int
    prompt: np.ndarray
    steps: int
    adapter_id: int = 0
    arrival: float = 0.0
    deadline_steps: int | None = None
    slot: int = -1
    blocks: list = dataclasses.field(default_factory=list)
    tokens: list = dataclasses.field(default_factory=list)
    t_first: float | None = None
    t_done: float | None = None
    timed_out: bool = False


def _jit_paged_admit(model):
    """Jitted admission program: invalidate the newcomers' (possibly
    recycled) blocks, prefill the same-length group on the shared-pool
    view, scatter its per-slot state into the engine slots, and emit each
    newcomer's first token.  One executable per (group, prompt) shape."""
    def build(m):
        def admit(params, cache, prompts, table_rows, slots, blocks,
                  adapters):
            g, _ = prompts.shape
            vocab = m.cfg.vocab_size
            params = _prepare_base(m, params)
            adapters = _prepare_adapters(m, adapters)
            cache = reset_paged_blocks(cache, blocks)
            cross = (m.cfg.encoder_frames if m.cfg.family == "audio" else 0)
            view = paged_prefill_view(m.cfg, cache, g,
                                      jnp.dtype(m.cfg.dtype),
                                      cross_len=cross)
            logits, view = m.prefill(params, view, prompts, adapters,
                                     last_only=True, table=table_rows)
            cache = merge_paged_cache(cache, view, slots)
            tok = jnp.argmax(logits[:, -1, :vocab], -1).astype(jnp.int32)
            return cache, tok
        return jax.jit(admit)
    return _model_jit(model, "paged_admit", build)


def _jit_paged_chunk(model):
    """Jitted decode chunk: ``steps`` greedy tokens for every engine slot
    in one lax.scan.  ``active`` gates token emission and position
    advance; inactive slots still run (static shapes) but write into the
    null block and their outputs are discarded host-side.  The chunk
    donates ``cache``: the pool is written in place from the call's input
    to its output, so a caller rebinds it from the result and never reads
    the one it passed."""
    def build(m):
        def chunk_run(params, cache, tok, pos, active, table, adapters, *,
                      steps):
            vocab = m.cfg.vocab_size
            params = _prepare_base(m, params)
            adapters = _prepare_adapters(m, adapters)

            def step(carry, _):
                cache, tok, pos = carry
                lg, cache = m.decode_step(params, cache, tok, pos, adapters,
                                          table=table)
                nxt = jnp.argmax(lg[:, -1, :vocab], -1).astype(jnp.int32)
                nxt = jnp.where(active, nxt, 0)
                pos = jnp.where(active, pos + 1, pos)
                return (cache, nxt[:, None], pos), nxt

            (cache, tok, pos), toks = jax.lax.scan(
                step, (cache, tok, pos), None, length=steps)
            return cache, tok, pos, toks.T
        return jax.jit(chunk_run, static_argnames=("steps",),
                       donate_argnums=(1,))
    return _model_jit(model, "paged_chunk", build)


def serve_scheduled(model, params, requests, *, bank=None, max_batch=4,
                    block_size=8, chunk=8, max_len=None, wait=True,
                    on_boundary=None, guard=None, transfer_guard=False):
    """Continuous-batching serve loop: admit / decode-chunk / evict until
    every request completes.  Returns the requests (mutated in place —
    ``tokens``, ``t_first``, ``t_done`` filled) sorted by rid.

    ``requests``: Request list; arrivals are seconds from loop start and
    are honored against the wall clock (``wait=False`` treats every
    request as already arrived — deterministic tests).  ``bank``: optional
    AdapterBank (each request's ``adapter_id`` indexes a bank row) or
    :class:`~repro.core.lora.LiveAdapterBank` (``adapter_id`` names a
    store tenant; non-resident tenants are LRU-promoted into hot slots at
    admission, slots gathered by running requests stay pinned, and
    publishes land between chunks with zero recompiles).
    ``max_len`` bounds prompt+steps per request and sizes the per-request
    block count; the pool holds exactly ``max_batch`` requests' worth of
    blocks plus the null block, so admission can never deadlock behind
    block starvation with a free slot.

    ``on_boundary(i)``: optional hook called at every scheduler boundary
    (before admission, between decode chunks) with a running boundary
    index — the adapter-lifecycle swap window: publishing into a live bank
    here is atomic with respect to decode chunks (the chunk already
    dispatched gathered the old slots; the next gathers the new).

    ``guard``: optional :class:`repro.analysis.sanitizers.RecompileGuard`
    — the admit/chunk engines are wrapped so any executable-cache growth
    on an already-served signature (e.g. a publish that churns the bank
    treedef) raises with the offending avals.  ``transfer_guard=True``
    additionally runs both engines under
    ``jax.transfer_guard("disallow")``; enable it on warmed shapes with
    device-resident params (tracing/compiling under the guard would trip
    on legitimate staging transfers).

    Under :func:`repro.analysis.trace.tracing` every loop iteration is a
    ``serve.boundary`` span holding ``serve.admit`` per group (children
    ``.stage``, ``.call``, ``.sync``), ``serve.chunk`` (children
    ``.stage``, ``.call``, ``.sync``, ``.evict``) or ``serve.wait``; a
    request's time from its arrival to its group's admission is a
    ``serve.queued`` span; the counters are ``serve.dispatches``,
    ``serve.admitted``, ``serve.timeouts``, ``serve.decode_tokens`` (tokens
    kept from chunks) and ``serve.decode_slot_steps`` (slots x steps
    run), and, once per trace of the decode chunk,
    ``serve.kv_inplace_layers`` (``transformer.decode_stack``).  Before the
    loop, :func:`serving_base` makes the frozen base's view for the run
    (span ``serve.prepare``, counter ``serve.base_bf16_leaves``)."""
    reqs = sorted(requests, key=lambda r: (r.arrival, r.rid))
    if not reqs:
        return []
    live = bank if isinstance(bank, LiveAdapterBank) else None
    if bank is not None and live is None:
        # host-boundary id validation: an out-of-range id would be silently
        # clamp-gathered to the LAST tenant's adapter.  A live bank's store
        # may legitimately grow mid-run (a publish from on_boundary), so
        # its tenants are checked at admission time instead.
        for r in reqs:
            check_adapter_ids([r.adapter_id], bank.size,
                              what=f"request rid={r.rid}: adapter_id")
    need = max(len(r.prompt) + r.steps for r in reqs)
    max_len = max_len or need
    win = model.cfg.attn_window
    # a sliding-window model may wrap its virtual ring (vlen = blocks *
    # block_size) as long as the ring still covers the window
    if need > max_len and (win is None or max_len < win):
        raise ValueError(f"request needs {need} positions > max_len "
                         f"{max_len}")
    # each request owns a virtual ring covering max_len positions, or the
    # window on a sliding-window model: position p lives in ring slot
    # p % vlen, vlen = mb * block_size
    ring = min(max_len, win) if win else max_len
    mb = -(-ring // block_size)
    pool = BlockPool(1 + max_batch * mb)
    cache = model.init_paged_cache(pool.num_blocks, block_size, max_batch)
    table = jnp.zeros((max_batch, mb), jnp.int32)
    tok = jnp.zeros((max_batch, 1), jnp.int32)
    pos = jnp.zeros((max_batch,), jnp.int32)
    active = jnp.zeros((max_batch,), bool)
    ids_arr = np.zeros((max_batch,), np.int32)
    free_slots = list(range(max_batch))
    params = serving_base(model, params)
    admit = _jit_paged_admit(model)
    chunk_run = _jit_paged_chunk(model)
    if guard is not None:
        admit = guard.wrap("paged_admit", admit)
        chunk_run = guard.wrap("paged_chunk", chunk_run)
    if transfer_guard:
        admit = guard_transfers(admit)
        chunk_run = guard_transfers(chunk_run)
    t0 = time.monotonic()
    clock = ((lambda: time.monotonic() - t0) if wait
             else (lambda: float("inf")))
    pending, running = list(reqs), []

    cur_bank = (lambda: live.bank) if live is not None else (lambda: bank)

    def finish(r, now):
        r.t_done = now
        running.remove(r)
        free_slots.append(r.slot)
        free_slots.sort()
        pool.free(r.blocks)
        nonlocal active, table
        active = active.at[r.slot].set(False)
        table = table.at[r.slot].set(0)         # back to the null block
        # reset the slot's tenant id: a stale id would keep being gathered
        # for the idle slot every chunk (harmless to outputs — the slot is
        # inactive — but it corrupts LRU/residency accounting, which keys
        # promotion and slot pinning on the observed ids)
        ids_arr[r.slot] = 0

    def next_group(now):
        """The next admission group and, for a live bank, its tenants' hot
        slots: FIFO same-length requests that have arrived, up to the free
        slots and blocks.  The head of the queue is never overtaken (a
        shorter-prompt request behind it cannot jump ahead), which keeps
        the loop deterministic and starvation-free."""
        plen = len(pending[0].prompt)
        group = []
        for r in pending:
            if (r.arrival <= now and len(r.prompt) == plen
                    and len(group) < len(free_slots)
                    and pool.available >= mb * (len(group) + 1)):
                group.append(r)
            else:
                break
        slot_map = None
        if group and live is not None:
            for r in group:
                if not live.has(r.adapter_id):
                    raise ValueError(
                        f"request rid={r.rid}: unknown tenant "
                        f"{r.adapter_id} (store holds {live.tenants})")
            # hot slots gathered by running requests are pinned; shrink the
            # group from the tail (head keeps FIFO priority) until its
            # distinct tenants fit the unpinned hot set, deferring admission
            # entirely when even the head cannot be promoted
            pinned = {int(ids_arr[r.slot]) for r in running}
            while group:
                slot_map = live.acquire([r.adapter_id for r in group],
                                        pinned)
                if slot_map is not None:
                    break
                group.pop()
        return group, slot_map

    def admit_group(group, slot_map):
        nonlocal cache, table, tok, pos, active
        plen = len(group[0].prompt)
        with trace.span("serve.admit.stage"):
            for r in group:
                pending.remove(r)
            slots = [free_slots.pop(0) for _ in group]
            rows = np.zeros((len(group), mb), np.int32)
            gather_ids = np.zeros((len(group),), np.int32)
            for i, (r, s) in enumerate(zip(group, slots)):
                r.slot, r.blocks = s, pool.alloc(mb)
                rows[i] = r.blocks
                gather_ids[i] = (slot_map[int(r.adapter_id)]
                                 if live is not None else r.adapter_id)
                ids_arr[s] = gather_ids[i]
            sl = jnp.asarray(slots, jnp.int32)
            table = table.at[sl].set(jnp.asarray(rows))
            prompts = jnp.asarray(np.stack([r.prompt for r in group]),
                                  jnp.int32)
            adapters = (cur_bank().requests(jnp.asarray(gather_ids))
                        if bank is not None else None)
        with trace.span("serve.admit.call"):
            trace.count("serve.dispatches")
            cache, first = admit(params, cache, prompts, jnp.asarray(rows),
                                 sl, jnp.asarray(rows.reshape(-1)), adapters)
            tok = tok.at[sl, 0].set(first)
            pos = pos.at[sl].set(plen)
            active = active.at[sl].set(True)
        with trace.span("serve.admit.sync"):
            first_host = np.asarray(first)
        # the first token is on the host from here
        tnow = clock()
        trace.count("serve.admitted", len(group))
        for i, r in enumerate(group):
            r.tokens = [int(first_host[i])]
            r.t_first = None if tnow == float("inf") else tnow
            running.append(r)
        for r in [r for r in group if r.steps <= 1]:
            finish(r, r.t_first)
        for r in [r for r in group
                  if r in running and r.deadline_steps is not None
                  and len(r.tokens) >= r.deadline_steps]:
            r.timed_out = True
            trace.count("serve.timeouts")
            finish(r, r.t_first)

    def decode_chunk():
        nonlocal cache, tok, pos
        with trace.span("serve.chunk.stage"):
            if live is not None:
                # recency driven by the ids flowing through the scheduler
                live.touch([r.adapter_id for r in running])
            adapters = (cur_bank().requests(jnp.asarray(ids_arr))
                        if bank is not None else None)
        with trace.span("serve.chunk.call"):
            trace.count("serve.dispatches")
            cache, tok, pos, toks = chunk_run(params, cache, tok, pos,
                                              active, table, adapters,
                                              steps=chunk)
        trace.count("serve.decode_slot_steps", max_batch * chunk)
        with trace.span("serve.chunk.sync"):
            toks = np.asarray(toks)
        tnow = clock()
        kept = 0
        with trace.span("serve.chunk.evict"):
            for r in list(running):
                # a deadline caps how many tokens this request may consume;
                # the prefix generated up to the cap is identical to an
                # un-deadlined run (eviction happens between chunks, never
                # inside one)
                cap = (r.steps if r.deadline_steps is None
                       else min(r.steps, r.deadline_steps))
                take = max(0, min(chunk, cap - len(r.tokens)))
                r.tokens.extend(int(t) for t in toks[r.slot, :take])
                kept += take
                if len(r.tokens) >= r.steps:
                    finish(r, None if tnow == float("inf") else tnow)
                elif len(r.tokens) >= cap:
                    r.timed_out = True
                    trace.count("serve.timeouts")
                    finish(r, None if tnow == float("inf") else tnow)
        trace.count("serve.decode_tokens", kept)

    boundary = 0
    while pending or running:
        with trace.span("serve.boundary"):
            if on_boundary is not None:
                # the swap window: between decode chunks / admission groups
                on_boundary(boundary)
            boundary += 1
            now = clock()
            while pending and free_slots and pending[0].arrival <= now:
                group, slot_map = next_group(now)
                if not group:
                    break
                if trace.enabled():
                    # each request waited from its arrival (the loop's
                    # start when arrivals are not honored) until now
                    for r in group:
                        trace.since("serve.queued",
                                    t0 + (r.arrival if wait else 0.0),
                                    rid=r.rid)
                with trace.span("serve.admit", rids=[r.rid for r in group],
                                size=len(group),
                                prompt_len=len(group[0].prompt)):
                    admit_group(group, slot_map)
            if running:
                with trace.span("serve.chunk"):
                    decode_chunk()
            elif pending:
                gap = pending[0].arrival - clock()
                if gap > 0:
                    with trace.span("serve.wait"):
                        time.sleep(min(gap, 0.02))
    return sorted(reqs, key=lambda r: r.rid)


def make_requests(trace, *, prompt_len, steps, tenants, vocab, seed=0,
                  deadline_steps=None):
    """Request list from an arrival trace.

    ``trace`` is either ``poisson:RATE:N`` (N arrivals, RATE req/s, seeded
    exponential inter-arrival gaps) or a path to
    a JSON list of ``{"arrival": s, "steps": n, "adapter": k, "deadline":
    d}`` records.  Prompts are seeded random ids, round-robin adapters
    unless the trace names them.  ``deadline_steps`` is the default
    per-request token budget (None = no deadline); a trace record's
    ``deadline`` overrides it."""
    rng = np.random.default_rng(seed)
    if trace.startswith("poisson:"):
        _, rate, n = trace.split(":")
        gaps = rng.exponential(1.0 / float(rate), int(n))
        recs = [{"arrival": float(t)} for t in np.cumsum(gaps)]
    else:
        with open(trace) as f:
            recs = json.load(f)
    def _deadline(rec):
        d = rec.get("deadline", deadline_steps)
        return None if d is None else int(d)
    reqs = [Request(rid=i,
                    prompt=rng.integers(0, vocab, prompt_len).astype(
                        np.int32),
                    steps=int(rec.get("steps", steps)),
                    adapter_id=int(rec.get("adapter", i % max(tenants, 1))),
                    arrival=float(rec.get("arrival", 0.0)),
                    deadline_steps=_deadline(rec))
            for i, rec in enumerate(recs)]
    for r in reqs:   # a bad trace record must fail here, not serve tenant N-1
        if not 0 <= r.adapter_id < tenants:
            raise ValueError(
                f"request rid={r.rid}: adapter {r.adapter_id} out of range "
                f"for {tenants} tenants (trace record names a tenant the "
                "bank does not hold)")
        if r.deadline_steps is not None and r.deadline_steps < 1:
            raise ValueError(
                f"request rid={r.rid}: deadline_steps={r.deadline_steps} "
                "must be >= 1 (the admission prefill always emits the "
                "first token)")
    return reqs


# ------------------------------------------------------------------ CLI

def build_bank(args, cfg, model):
    """AdapterBank from a checkpoint (``--resume``) or fresh random sets.

    Returns (base_params, bank).  With ``--resume`` the bank registers the
    TRAINED stacked AdapterSet — per-client gammas fold into B, rank masks
    carry over — so serving uses exactly what training produced (and the
    checkpoint's base weights serve; nothing is initialized from scratch)."""
    if args.resume:
        lcfg = LoRAConfig(rank=args.rank, alpha=args.alpha,
                          scaling=args.scaling, targets=cfg.lora_targets)
        base, aset = load_adapter_state(args.resume, lora_cfg=lcfg)
        return base, AdapterBank.from_adapter_set(aset)
    params = model.init(jax.random.key(0))
    ranks = ([int(r) for r in args.ranks.split(",")] if args.ranks
             else [args.rank] * args.clients)
    sets = [init_adapter_set(
        params, jax.random.fold_in(jax.random.key(1), k),
        LoRAConfig(rank=r, alpha=args.alpha, scaling=args.scaling,
                   targets=cfg.lora_targets),
        n_clients=len(ranks)) for k, r in enumerate(ranks)]
    return params, AdapterBank.from_sets(sets)


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="gemma-2b", choices=sorted(ARCHS))
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--rank", type=int, default=8)
    ap.add_argument("--ranks", default="",
                    help="comma-separated per-tenant ranks for a fresh "
                         "mixed-rank bank, e.g. 4,8,16")
    ap.add_argument("--alpha", type=float, default=8.0)
    ap.add_argument("--scaling", default="sfedlora",
                    choices=("lora", "rslora", "sfedlora", "za", "zb"))
    ap.add_argument("--batch", type=int, default=4,
                    help="without --arrival-trace: this many requests, all "
                         "arrived at time 0")
    ap.add_argument("--steps", type=int, default=16,
                    help="generated tokens per request (greedy)")
    ap.add_argument("--clients", type=int, default=4,
                    help="tenant count for a fresh bank (ignored with "
                         "--resume: every checkpointed client serves)")
    ap.add_argument("--resume", default=None,
                    help="federated checkpoint (.npz) to serve: restores "
                         "the trained AdapterSet — gammas and rank mask "
                         "included — and registers every client in the bank")
    ap.add_argument("--quant", default="none", choices=("none", "int8", "int4"),
                    help="serve from a quantized frozen base: one-shot "
                         "post-load quantization of the eligible GEMM "
                         "weights (int8 per-channel / int4 grouped); "
                         "adapters stay fp, kernels dequant in VMEM")
    ap.add_argument("--quant-group", type=int, default=64,
                    help="int4 group size (power of two <= 128)")
    ap.add_argument("--merge", type=int, default=None, metavar="CLIENT",
                    help="classic single-tenant path: merge this client's "
                         "adapters into the base weights (zero serving "
                         "overhead) and serve every request from it")
    ap.add_argument("--arrival-trace", default=None,
                    help="serve a request STREAM with arrivals honored "
                         "against the wall clock instead of a burst at "
                         "time 0: 'poisson:RATE:N' (seeded Poisson "
                         "arrivals) or a JSON trace file of {arrival, "
                         "steps, adapter} records")
    ap.add_argument("--max-batch", type=int, default=4,
                    help="scheduler engine slots (concurrent requests)")
    ap.add_argument("--block-size", type=int, default=8,
                    help="KV tokens per pool block (paged cache)")
    ap.add_argument("--chunk", type=int, default=8,
                    help="decode steps per scheduler chunk (admission / "
                         "eviction happen at chunk boundaries)")
    ap.add_argument("--deadline-steps", type=int, default=None,
                    help="per-request token budget for the scheduler: "
                         "requests still running at this many tokens are "
                         "evicted (truncated) at the next chunk boundary "
                         "and counted as timeouts")
    ap.add_argument("--hot-slots", type=int, default=0,
                    help="serve the bank through a LiveAdapterBank with "
                         "this many device-resident slots; the remaining "
                         "tenants overflow to host RAM and are LRU-promoted "
                         "on demand (0 = whole bank on device, no overflow)")
    ap.add_argument("--trace-out", default=None, metavar="PATH",
                    help="trace the job's spans and counters and write them "
                         "to PATH as Chrome trace-event JSON (Perfetto)")
    args = ap.parse_args(argv)
    with trace.written_to(args.trace_out):
        return _serve(args)


def _serve(args):
    enable_compile_cache()

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    model = build_model(cfg)
    base, bank = build_bank(args, cfg, model)
    # one-shot post-load quantization (or flag/checkpoint reconciliation: a
    # packed checkpoint under a mismatched --quant is a hard error)
    src = (f"checkpoint '{args.resume}'" if args.resume else "fresh base")
    base = apply_quant_flag(base, args.quant, args.quant_group, source=src)
    if args.arrival_trace:
        reqs = make_requests(args.arrival_trace, prompt_len=4,
                             steps=args.steps, tenants=bank.size,
                             vocab=cfg.vocab_size,
                             deadline_steps=args.deadline_steps)
    else:
        # a burst: seeded 4-token prompts, tenants round-robin over the bank
        prompts = np.asarray(jax.random.randint(
            jax.random.key(2), (args.batch, 4), 0, cfg.vocab_size), np.int32)
        reqs = [Request(rid=i, prompt=prompts[i], steps=args.steps,
                        adapter_id=i % bank.size,
                        deadline_steps=args.deadline_steps)
                for i in range(args.batch)]
    serve_bank = bank
    if args.merge is not None:
        merged = bank.adapter(args.merge).merge(base)
        if has_quantized(base):
            # merge_lora dequantizes packed leaves to fold the adapter in;
            # re-pack onto the checkpoint's grid or --merge --quant would
            # silently serve fp weights and lose the whole footprint win
            merged = requantize_merged(merged, base)
        base, serve_bank = merged, None
    elif args.hot_slots:
        serve_bank = LiveAdapterBank.from_bank(bank, hot_slots=args.hot_slots)
    t0 = time.monotonic()
    done = serve_scheduled(model, base, reqs, bank=serve_bank,
                           max_batch=args.max_batch,
                           block_size=args.block_size, chunk=args.chunk,
                           wait=bool(args.arrival_trace))
    dt = time.monotonic() - t0
    lats = sorted(r.t_done - r.arrival for r in done if r.t_done is not None)
    p50 = lats[len(lats) // 2] if lats else 0.0
    p99 = lats[min(len(lats) - 1, int(len(lats) * 0.99))] if lats else 0.0
    toks = sum(len(r.tokens) for r in done)
    n_to = sum(1 for r in done if r.timed_out)
    served = (f"merged tenant {args.merge}" if args.merge is not None
              else f"{bank.size} tenants (ranks "
                   f"{','.join(str(r) for r in bank.ranks)})")
    print(f"# {args.arch} scheduled serve: {len(done)} requests, {served}, "
          f"max_batch={args.max_batch} block={args.block_size} "
          f"chunk={args.chunk}  "
          + (f"p50={p50*1000:.0f}ms p99={p99*1000:.0f}ms "
             if args.arrival_trace else "")
          + f"goodput={toks/dt:.1f} tok/s"
          + (f" timeouts={n_to}" if args.deadline_steps else ""))
    if isinstance(serve_bank, LiveAdapterBank):
        print(f"# live bank: {serve_bank.hot_slots}/{len(serve_bank.tenants)} "
              f"slots hot, {serve_bank.promotions} promotions, "
              f"{serve_bank.demotions} demotions")
    return done


if __name__ == "__main__":
    main()
