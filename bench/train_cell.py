"""Training cells: federated LoRA rounds through ``FederatedTrainer.run``,
which stages a chunk of ``chunk_rounds`` rounds of client batches on the
host and calls the compiled round engine (``make_run_chunk``) once per chunk.

Set-up builds one trainer from the seed (base weights and the starting
adapter made by the benchmark), runs its first chunk through the same
``run`` call and feed the window uses, and keeps what that chunk left
behind: each round's loss and the change of every adapter leaf.  The
window then times further chunks on the same object.  After it, the
reference repeats the first chunk's rounds from the same weights and data,
and the two are compared.

A mix with ``"mesh": "<DxM>"`` runs on that mesh over the cell's chips:
the trainer gets the mesh, and the benchmark makes the base and the
starting adapter in the program's placement, so no chip holds the whole
base.
"""
from __future__ import annotations

import gc
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

import harness
import model as bmodel
import traffic


class TimedData:
    """The job's data source, with every ``round_batch`` the trainer stages
    recorded as a ``bench.stage`` span."""

    def __init__(self, data, spans):
        self.data, self.spans = data, spans

    def round_batch(self, local_steps: int = 1):
        with self.spans.span("bench.stage"):
            return self.data.round_batch(local_steps)


def round_tokens(mix: dict) -> int:
    return (mix["clients"] * mix["local_steps"] * mix["batch_per_client"]
            * mix["seq_len"])


def round_flops(cfg: dict, mix: dict) -> float:
    return mix["local_steps"] * bmodel.family(cfg).train_step_flops(
        cfg, sequences=mix["clients"] * mix["batch_per_client"],
        seq=mix["seq_len"], rank=mix["rank"], targets=mix["targets"])


def start_lora(cfg: dict, mix: dict, key, *, lead=None, mesh=None):
    """The adapter every client starts from (FedSA: one shared A; B too is
    shared and non-zero, as when a federated job resumes from a pretrained
    adapter, so that the first round already moves A), stacked over the
    clients (``lead``: other leading dims; ``()`` gives one client's), on
    ``mesh`` in the program's placement."""
    return bmodel.make_lora(cfg, jax.random.fold_in(key, 2),
                            rank=mix["rank"], targets=mix["targets"],
                            a_std=mix["a_std"], b_std=mix["b_std"],
                            lead=(mix["clients"],) if lead is None else lead,
                            shared_lead=True, mesh=mesh)


def mix_mesh(mix: dict):
    """The mesh a mix names (``"mesh": "1x4"``), built by the program's own
    ``mesh_from_spec`` over this machine's chips; None without the key."""
    if "mesh" not in mix:
        return None
    from repro.launch.mesh import mesh_from_spec
    return mesh_from_spec(mix["mesh"])


@jax.jit
def _squares(lo, l0):
    return jnp.concatenate([
        jnp.sum(jnp.square(a - b), axis=(0, *range(2, a.ndim)))
        for a, b in zip(jax.tree.leaves(lo), jax.tree.leaves(l0))])


def change_norms(trees, lora0) -> np.ndarray:
    """Norm of every leaf's change from ``lora0`` (one client's start, with
    a leading dim of 1), per leaf of the program's adapter tree and per
    layer, over all clients: ``trees`` are client-stacked trees in the
    program's layout that together hold every client once.  In the tree's
    order."""
    return np.sqrt(sum(np.asarray(_squares(t, lora0), np.float64)
                       for t in trees))


def build(cfg: dict, mix: dict, seed: int, spans):
    """The trainer and what set-up made for it.  On the mix's mesh the base
    weights, the starting adapter and the optimizer state are made or put
    in the program's placement (``repro.sharding.rules``), as the trainer's
    own ``_place_on_mesh`` puts its state."""
    from repro.configs.base import (FederatedConfig, LoRAConfig,
                                    OptimizerConfig)
    from repro.core.federated import FederatedTrainer
    from repro.models.api import build_model
    from repro.sharding import rules
    fam = bmodel.family(cfg)
    model = build_model(fam.program_config(cfg))
    key = bmodel.seed_key(seed)
    mesh = mix_mesh(mix)
    params = fam.make_params(model, jax.random.fold_in(key, 1), mesh=mesh)
    n = mix["clients"]
    trainer = FederatedTrainer(
        model, TimedData(traffic.FederatedData(mix, cfg["vocab_size"], seed),
                         spans),
        lora_cfg=LoRAConfig(rank=mix["rank"], alpha=mix["alpha"],
                            scaling=mix["scaling"],
                            targets=tuple(mix["targets"])),
        fed_cfg=FederatedConfig(num_clients=n,
                                local_steps=mix["local_steps"],
                                aggregation=mix["aggregation"],
                                partition="dirichlet",
                                dirichlet_alpha=mix["dirichlet_alpha"]),
        opt_cfg=OptimizerConfig(name=mix["optimizer"], lr=mix["lr"]),
        base_params=params, chunk_rounds=mix["chunk_rounds"], mesh=mesh)
    # free the trainer's own adapter first: the two need not share a chip
    trainer.lora = trainer.opt_state = None
    trainer.lora = fam.program_lora(start_lora(cfg, mix, key, mesh=mesh))
    opt = {"t": jnp.zeros((n,), jnp.int32)}
    trainer.opt_state = (opt if mesh is None else
                         jax.device_put(opt, rules.lora_sharding(opt, mesh)))
    return {"model": model, "params": params, "trainer": trainer, "key": key,
            "mesh": mesh}


def first_chunk(cfg, mix, state, spans):
    """Run the first chunk through the window's own call; returns each
    round's loss and the leaf change norms after the chunk."""
    trainer = state["trainer"]
    with spans.span("bench.run_chunk"):
        trainer.run(mix["chunk_rounds"])
    lora0 = bmodel.family(cfg).program_lora(
        start_lora(cfg, mix, state["key"], lead=(1,), mesh=state["mesh"]))
    out = {"loss": [h["loss"] for h in trainer.history],
           "norms": change_norms([trainer.lora], lora0)}
    del lora0
    return out


_add = jax.jit(lambda x, y: jax.tree.map(jnp.add, x, y))


def reference_rounds(cfg, mix, params, seed, key, *, dtype="float32",
                     skip_half=False):
    """The first chunk's rounds computed by the reference: every client's
    local SGD steps on its own rows, then FedSA (the mean of A over the
    clients; each B stays with its client).  A round's loss is the mean
    of each step's loss over clients and steps.  ``dtype="bfloat16"``
    computes forward and backward in bfloat16 (the control); ``skip_half``
    leaves the second half of the clients out of the round (a planted
    fault).

    It goes one client and one step at a time and keeps one A, the running
    sum of the clients' new A and each client's B, so that it fits beside
    the base.  Where ``params`` lie on a mesh, the adapters and rows are
    replicated over it and the compiler partitions each step."""
    fam = bmodel.family(cfg)
    dt = jnp.dtype(dtype)
    gamma = traffic.sfedlora_gamma(mix)
    lr, n, steps = mix["lr"], mix["clients"], mix["local_steps"]
    data = traffic.FederatedData(mix, cfg["vocab_size"], seed)
    first = jax.tree.leaves(params)[0].sharding
    put = ((lambda t: jax.device_put(t, NamedSharding(first.mesh, P())))
           if isinstance(first, NamedSharding) else (lambda t: t))
    lora0 = put(start_lora(cfg, mix, key, lead=()))
    p = params if dt == jnp.float32 else fam.cast(params, dt)
    grad = jax.jit(jax.value_and_grad(
        lambda lo, p, toks: fam.loss(cfg, p, toks, fam.cast(lo, dt), gamma)))
    update = jax.jit(lambda lo, g: jax.tree.map(lambda x, y: x - lr * y,
                                                lo, g))
    trained = n // 2 if skip_half else n
    a = {t: lora0[t]["a"] for t in lora0}
    bs = [{t: lora0[t]["b"] for t in lora0}] * n
    del lora0
    out = {"loss": []}
    with jax.default_matmul_precision("highest"):
        for r in range(mix["chunk_rounds"]):
            batch = data.round_batch(steps)
            losses, total = [], None
            for i in range(n):
                lo = {t: {"a": a[t], "b": bs[i][t]} for t in a}
                for s in range(steps if i < trained else 0):
                    loss, g = grad(lo, p, put(jnp.asarray(batch[i, s])))
                    lo = update(lo, g)
                    losses.append(float(loss))
                if i < trained:
                    mine = {t: lo[t]["a"] for t in lo}
                    total = mine if total is None else _add(total, mine)
                bs[i] = {t: lo[t]["b"] for t in lo}
                del lo
            a = jax.tree.map(lambda x: x / trained, total)
            del total
            out["loss"].append(float(np.mean(losses)))
        one = lambda tree: fam.program_lora(jax.tree.map(
            lambda x: x[None], tree))
        out["norms"] = change_norms(
            [one({t: {"a": a[t], "b": b[t]} for t in a}) for b in bs],
            one(put(start_lora(cfg, mix, key, lead=()))))
    return out


def readings(prog: dict, ref: dict) -> dict:
    """The numbers compared.  ``loss_rel``: worst relative gap of a round's
    loss.  ``change_gap``: worst leaf's gap between the program's and the
    reference's change norms after the chunk, against the larger of that
    leaf's reference norm and the median leaf's.  Leaves whose reference
    change is under a thousandth of the median leaf's move by rounding
    alone and are left out."""
    lp, lr = np.asarray(prog["loss"]), np.asarray(ref["loss"])
    npg, nrf = prog["norms"], ref["norms"]
    keep = nrf >= 1e-3 * np.median(nrf)
    npg, nrf = npg[keep], nrf[keep]
    den = np.maximum(nrf, np.median(nrf))
    return {"loss_rel": float(np.max(np.abs(lp - lr) / np.abs(lr))),
            "change_gap": float(np.max(np.abs(npg - nrf) / den))}


def run(ctx) -> tuple[dict, dict, dict]:
    """One run of a training cell.  Returns (result, checks, notes)."""
    cfg, mix, args, spans = ctx["cfg"], ctx["mix"], ctx["args"], ctx["spans"]
    meter = ctx["meter"]
    state = build(cfg, mix, args.seed, spans)
    prog = first_chunk(cfg, mix, state, spans)
    trainer = state["trainer"]

    profile = harness.Profile(ctx["trace_dir"]) if args.trace else None
    trace_from = args.seconds * harness.TRACE_START
    compiles0 = meter.compiles
    spans_before = len(spans.spans)
    t_start = time.monotonic()
    setup_s = t_start - ctx["t0"]
    rounds = 0
    traced_rounds = 0
    while True:
        if profile and not profile.started and (
                time.monotonic() - t_start >= trace_from):
            profile.start()
        with spans.span("bench.run_chunk"):
            trainer.run(mix["chunk_rounds"])
        rounds += mix["chunk_rounds"]
        if profile and profile.started and not profile.stopped:
            traced_rounds += mix["chunk_rounds"]
            if time.monotonic() - t_start >= trace_from + harness.TRACE_SECONDS:
                profile.stop()
        if time.monotonic() - t_start >= args.seconds and (
                not profile or profile.stopped):
            break
    window_s = time.monotonic() - t_start
    window_compiles = meter.compiles - compiles0
    device = harness.device_info(ctx["chips"])
    finite = all(np.isfinite(h["loss"]) for h in trainer.history)
    stage = [s for s in spans.spans[spans_before:] if s.name == "bench.stage"]

    del trainer, state["trainer"]
    gc.collect()
    ref = reference_rounds(cfg, mix, state["params"], args.seed, state["key"])
    ok, checks = harness.judge(readings(prog, ref),
                               ctx["limits"])
    ok = ok and finite

    result = {"correct": bool(ok), "attempted": rounds,
              "failed": 0 if finite else rounds, "device": device}
    notes = {"setup_s": setup_s, "window_s": window_s, "rounds": rounds,
             "compiles_in_window": window_compiles,
             "program_loss": prog["loss"], "reference_loss": ref["loss"]}
    if not args.trace:
        result["metrics"] = {
            "train_tokens_per_s": {"value": rounds * round_tokens(mix)
                                   / window_s, "unit": "tokens/s"},
            "setup_s": {"value": setup_s, "unit": "s"}}
        return result, checks, notes

    events = harness.trace_events(ctx["trace_dir"], ctx["chips"])
    lo_hi = harness.slice_bounds(events)
    rctx = {"events": events, "slice": lo_hi, "spans": spans,
            "stage": stage, "rounds_in_slice": traced_rounds,
            "round_flops": round_flops(cfg, mix), "chips": ctx["chips"],
            "peaks": harness.peaks(device["kind"]), "cfg": cfg, "mix": mix}
    result["metrics"] = ctx["per_layer"](rctx)
    if lo_hi:
        lo, hi = lo_hi
        result["device"]["busy_s"] = harness.mean_busy_ns(events, lo,
                                                          hi) / 1e9
        result["device"]["window_s"] = (hi - lo) / 1e9
        result["breakdown"] = harness.breakdown(events, lo, hi)
    return result, checks, notes
