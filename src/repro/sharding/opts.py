"""Beyond-paper performance switches (hillclimbed in EXPERIMENTS.md §Perf).

All default False = paper-faithful baseline.  The dry-run enables subsets via
``--opts a,b,c`` so baseline and optimized variants lower separately.

  expand_kv          repeat GQA KV heads to the full head count before the
                     attention einsums so the head dim shards cleanly over
                     `model` (kills replicated-attention redundant compute
                     when kv_heads < model-axis size).
  seq_parallel_attn  shard the query block's sequence dim over `model` inside
                     blockwise attention when heads don't divide the axis
                     (context parallelism; paligemma/gemma 8-head case).
  chunked_ce         compute the CE loss in sequence chunks so the (b, s, V)
                     logits tensor never materializes (memory-term fix).
  remat_dots         layer-scan checkpoint saves dot outputs instead of
                     recomputing the whole block (compute-term fix, costs
                     memory).
  moe_grouped        per-batch-row MoE dispatch groups: router cumsum and
                     capacity are group-local, buffers shard (group->data,
                     expert->model) (collective/memory-term fix).
  seq_parallel_residual  shard the residual stream's sequence dim over
                     `model` between blocks (Megatron-SP analogue): norms and
                     per-token ops run seq-sharded, activations stored 1/16
                     per device (memory-term fix; GSPMD inserts the gathers
                     at the attention boundary).
"""
from __future__ import annotations

OPTS = {
    "expand_kv": False,
    "seq_parallel_attn": False,
    "chunked_ce": False,
    "remat_dots": False,
    "moe_grouped": False,
    "seq_parallel_residual": False,
    # decode-path: shard the embedding table on d instead of vocab, making
    # the token lookup shard-local (kills the full-table all-gather that
    # dominates decode collective terms); the tied head pays a small
    # (b, 1, V) psum instead.
    "embed_dshard": False,
}


def enabled(name: str) -> bool:
    return OPTS[name]


def set_opts(names, value: bool = True) -> None:
    for n in names:
        if n not in OPTS:
            raise ValueError(f"unknown opt '{n}'; options {sorted(OPTS)}")
        OPTS[n] = value


def reset() -> None:
    for k in OPTS:
        OPTS[k] = False
