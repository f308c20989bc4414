"""Federated LoRA fine-tuning launcher.

Reduced width, on any backend (the Pallas kernels are not used unless the
model config sets ``use_pallas``):
  PYTHONPATH=src python -m repro.launch.train --arch gemma-2b --reduced \
      --rank 64 --scaling sfedlora --clients 4 --rounds 30 --chunk-rounds 10

Full published width fits one TPU v5e at a short sequence (see
chip_smoke.py): ... --arch gemma-2b --clients 4 --rank 64 --seq 128 \
      --batch-per-client 1 --local-steps 1

On a mesh the same entry point shards the client dim over the mesh's client
axes ("pod","data") and runs the compiled scan engine:
  ... --mesh 4x2 --clients 8 --chunk-rounds 10 --data-mode device
(see launch/dryrun.py for the compile-only proof of the production meshes).

Heterogeneous clients (per-client ranks + per-client gamma_i, Dirichlet
non-IID sizes, size-weighted aggregation):
  ... --clients 4 --ranks 4,8,16,16 --partition dirichlet \
      --dirichlet-alpha 0.3 --weight-by-size
"""
from __future__ import annotations

import argparse

from repro.analysis import trace
from repro.configs import ARCHS, get_config
from repro.configs.base import FederatedConfig, LoRAConfig, OptimizerConfig
from repro.core.aggregation import STRATEGIES
from repro.core.federated import FederatedTrainer
from repro.core.quant import apply_quant_flag, quantize_tree
from repro.data.synthetic import FederatedDataset
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import mesh_from_spec
from repro.models.api import build_model


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="gemma-2b", choices=sorted(ARCHS))
    ap.add_argument("--reduced", action="store_true",
                    help="smoke-scale variant (CPU)")
    ap.add_argument("--rank", type=int, default=8)
    ap.add_argument("--ranks", default="",
                    help="comma-separated per-client ranks (heterogeneous "
                         "clients), e.g. 4,8,16,16; overrides --rank — "
                         "clients pad to max(ranks) with a rank mask and "
                         "train with their own gamma_i")
    ap.add_argument("--alpha", type=float, default=8.0)
    ap.add_argument("--scaling", default="sfedlora",
                    choices=("lora", "rslora", "sfedlora", "za", "zb"))
    ap.add_argument("--strategy", default="fedsa", choices=STRATEGIES)
    ap.add_argument("--clients", type=int, default=3)
    ap.add_argument("--local-steps", type=int, default=2)
    ap.add_argument("--rounds", type=int, default=20)
    ap.add_argument("--participation", type=float, default=1.0,
                    help="fraction of clients sampled per round")
    ap.add_argument("--optimizer", default="sgd", choices=("sgd", "adamw"))
    ap.add_argument("--lr", type=float, default=5e-3)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--batch-per-client", type=int, default=4)
    ap.add_argument("--partition", default="iid",
                    choices=("iid", "dirichlet"))
    ap.add_argument("--dirichlet-alpha", type=float, default=0.5,
                    help="Dir(alpha) concentration for the non-IID "
                         "partition (topic mixtures AND client sizes)")
    ap.add_argument("--weight-by-size", action="store_true",
                    help="weight the server aggregate by per-client "
                         "example counts instead of a plain mean")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chunk-rounds", type=int, default=0,
                    help="rounds per compiled scan chunk (0: one chunk per "
                         "log stride)")
    ap.add_argument("--data-mode", default="host", choices=("host", "device"),
                    help="host: stage dataset batches per chunk; device: "
                         "synthesize batches inside the scan via jax.random")
    ap.add_argument("--mesh", default="",
                    help="mesh spec: 'DxM'/'PxDxM' (e.g. 4x2, 2x16x16), "
                         "'pod', 'multipod'; empty = no mesh")
    ap.add_argument("--quant", default="none", choices=("none", "int8", "int4"),
                    help="store the frozen base quantized (int8 per-channel "
                         "/ int4 grouped); LoRA state stays fp — kernels "
                         "dequantize per-tile in VMEM (core/quant.py)")
    ap.add_argument("--quant-group", type=int, default=64,
                    help="int4 group size (power of two <= 128)")
    ap.add_argument("--faults", default="",
                    help="deterministic fault injection spec, e.g. "
                         "'dropout=0.1,straggle=geom:0.3,corrupt=0.01' "
                         "(see repro.core.faults.parse_faults); implies the "
                         "async buffered engine")
    ap.add_argument("--buffer", type=int, default=None, metavar="M",
                    help="async buffered aggregation: cap the per-round "
                         "buffer at M accepted uploads (0 = no cap, M = N "
                         "— bit-identical to the synchronous engine at "
                         "zero faults)")
    ap.add_argument("--staleness-beta", type=float, default=0.5,
                    help="staleness discount exponent: an upload tau "
                         "rounds old aggregates with weight (1+tau)^-beta")
    ap.add_argument("--no-screen", action="store_true",
                    help="disable server-side screening of non-finite / "
                         "norm-outlier uploads before aggregation")
    ap.add_argument("--screen-mult", type=float, default=10.0,
                    help="reject finite uploads whose norm exceeds this "
                         "multiple of the round median")
    ap.add_argument("--watchdog", type=int, default=None, metavar="RETRIES",
                    help="collapse watchdog: judge every chunk against the "
                         "Theorem 4.2 sentinel; on a failed verdict roll "
                         "back to the chunk-start snapshot and retry "
                         "(rescale gamma / back off participation) up to "
                         "RETRIES times before raising")
    ap.add_argument("--save", default=None, help="checkpoint path (.npz)")
    ap.add_argument("--resume", default=None,
                    help="checkpoint to restore (incl. PRNG key + round, so "
                         "the run continues bit-exactly)")
    ap.add_argument("--trace-out", default=None, metavar="PATH",
                    help="trace the job's spans and counters and write them "
                         "to PATH as Chrome trace-event JSON (Perfetto)")
    args = ap.parse_args(argv)
    with trace.written_to(args.trace_out):
        return _train(args)


def _train(args):
    enable_compile_cache()

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    model = build_model(cfg)
    ranks = (tuple(int(r) for r in args.ranks.split(","))
             if args.ranks else None)
    ds = FederatedDataset(cfg.vocab_size, args.clients, seq_len=args.seq,
                          batch_per_client=args.batch_per_client,
                          partition=args.partition,
                          dirichlet_alpha=args.dirichlet_alpha,
                          seed=args.seed)
    mesh = mesh_from_spec(args.mesh)
    base_params = None
    if args.quant != "none":
        if mesh is not None:
            raise SystemExit("--quant is single-host for now (packed leaves "
                             "carry no sharding annotations); drop --mesh")
        # replicate the trainer's base-init split so the packed tree
        # quantizes the *identical* fp base the fp run would have trained on
        import jax
        kb, _ = jax.random.split(jax.random.key(args.seed))
        base_params = quantize_tree(model.init(kb), args.quant,
                                    args.quant_group)
    faults = None
    if args.faults:
        from repro.core.faults import parse_faults
        faults = parse_faults(args.faults)
    watchdog = None
    if args.watchdog is not None:
        from repro.core.federated import WatchdogConfig
        watchdog = WatchdogConfig(max_retries=args.watchdog)
    tr = FederatedTrainer(
        model, ds,
        lora_cfg=LoRAConfig(rank=args.rank, ranks=ranks, alpha=args.alpha,
                            scaling=args.scaling, targets=cfg.lora_targets),
        fed_cfg=FederatedConfig(num_clients=args.clients,
                                local_steps=args.local_steps,
                                rounds=args.rounds,
                                aggregation=args.strategy,
                                partition=args.partition,
                                dirichlet_alpha=args.dirichlet_alpha,
                                participation=args.participation,
                                weight_by_size=args.weight_by_size,
                                buffer_size=args.buffer,
                                staleness_beta=args.staleness_beta,
                                screen_updates=not args.no_screen,
                                screen_norm_mult=args.screen_mult,
                                faults=faults),
        opt_cfg=OptimizerConfig(name=args.optimizer, lr=args.lr),
        seed=args.seed, base_params=base_params, data_mode=args.data_mode,
        chunk_rounds=args.chunk_rounds, mesh=mesh, watchdog=watchdog)
    if args.resume:
        tr.restore(args.resume)
        # an fp checkpoint restored under --quant is packed once here; a
        # packed checkpoint under a mismatched flag is a hard error
        tr.base = apply_quant_flag(tr.base, args.quant, args.quant_group,
                                   source=f"checkpoint '{args.resume}'")
        print(f"# resumed from {args.resume} at round {tr.round_idx}")
    aset = tr.adapters     # scaling factors travel with the state
    gamma_str = (f"gamma={aset.gamma:.4f} rank={args.rank}" if ranks is None
                 else "gammas=" + ",".join(f"{g:.3f}" for g in tr.gammas)
                 + f" ranks={args.ranks}")
    print(f"# {args.arch}{' (reduced)' if args.reduced else ''}  "
          f"strategy={args.strategy} scaling={args.scaling} "
          f"{gamma_str} N={args.clients}"
          + (" weight-by-size" if args.weight_by_size else "")
          + (f" mesh={args.mesh}" if args.mesh else "")
          + (f" quant={args.quant}" if args.quant != "none" else "")
          + (f" buffer={'N' if args.buffer == 0 else args.buffer}"
             if tr.async_mode else "")
          + (f" faults[{args.faults}]" if args.faults else "")
          + (f" watchdog(retries={args.watchdog})" if watchdog else ""))
    tr.run(args.rounds, log_every=max(1, args.rounds // 10))
    if tr.async_mode:
        last = tr.history[-1]
        print(f"# async: gamma_eff={tr.gamma_eff:.4f} "
              f"n_eff={last['n_eff']:.2f} delivered={last['delivered']:.0f} "
              f"rejected={last['rejected']:.0f} stale={last['stale']:.0f}")
    for ev in tr.watchdog_events:
        print(f"# watchdog: round {ev['round']} verdict={ev['verdict']} "
              f"-> {ev['action']} ({ev['detail']}, retry {ev['retry']})")
    ppl = tr.eval_perplexity()
    print(f"# final held-out perplexity: {ppl:.3f}")
    if args.save:
        tr.save(args.save)
        print(f"# saved -> {args.save}")
    return tr


if __name__ == "__main__":
    main()
