"""Model FLOPs and required bytes of the dense family's layer
(``families/dense.py`` takes its two work counts from here), and the least
time of any family's work at the chip's peaks (``least_time_s``).

Nothing here calls the system under test: the counts come from the widths
in ``bench/configs/<config>.json`` alone, so a later change to the program
cannot change the yardstick.

Conventions, each a choice of what the work *requires*:

- A matrix product of (m, k) by (k, n) is 2*m*k*n FLOPs.
- Attention is causal: query i reads keys 0..i, so a sequence of s tokens
  costs 2*2*h*hd * s*(s+1)/2 per layer forward (scores and values); masked
  work is not counted.
- The head is over the real vocabulary, not the program's padded one.
- LoRA training freezes the base: the backward pass needs the activation
  gradient of every matrix product (as many FLOPs as its forward) but no
  base-weight gradient.  The adapters need both.  Recomputation is not
  counted.
- Decode reads every base weight once per step, each active request's keys
  and values up to its position, and the adapter rows of each distinct
  tenant in the batch, all at the width of the matrix unit's operands
  (``precision.matmul_operand_bytes``: 2 where the configuration multiplies
  in one bfloat16 pass, whatever the stored width).  That is the least a
  program must move, so the share of the roofline cannot pass 100%.
"""
from __future__ import annotations


def widths(cfg: dict) -> dict:
    """The sizes the counts use, read from a configuration file."""
    d = cfg["hidden_size"]
    h, kh = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    hd = cfg.get("head_dim") or d // h
    return {"d": d, "h": h, "kh": kh, "hd": hd, "ff": cfg["intermediate_size"],
            "L": cfg["num_hidden_layers"], "V": cfg["vocab_size"],
            "wbytes": cfg["precision"]["matmul_operand_bytes"]}


def layer_matmul_params(cfg: dict) -> int:
    """Weights of one layer's matrix products (q, k, v, o and the gated MLP)."""
    w = widths(cfg)
    q, kv = w["h"] * w["hd"], w["kh"] * w["hd"]
    return w["d"] * q + 2 * w["d"] * kv + q * w["d"] + 3 * w["d"] * w["ff"]


def matmul_params(cfg: dict) -> int:
    """Every weight a token's forward multiplies: all layers plus the head."""
    w = widths(cfg)
    return w["L"] * layer_matmul_params(cfg) + w["d"] * w["V"]


def lora_params_per_layer(cfg: dict, rank: int, targets) -> int:
    """Adapter weights (A and B) of one layer for one client or tenant."""
    w = widths(cfg)
    out = {"q": w["h"] * w["hd"], "k": w["kh"] * w["hd"],
           "v": w["kh"] * w["hd"], "o": w["d"]}
    inp = {"q": w["d"], "k": w["d"], "v": w["d"], "o": w["h"] * w["hd"]}
    return sum(rank * (inp[t] + out[t]) for t in targets)


def attn_fwd_flops(cfg: dict, seq: int) -> float:
    """Causal self-attention of one sequence of ``seq`` tokens, all layers."""
    w = widths(cfg)
    return 4.0 * w["h"] * w["hd"] * seq * (seq + 1) / 2 * w["L"]


def train_step_flops(cfg: dict, *, sequences: int, seq: int, rank: int,
                     targets) -> float:
    """One optimizer step of LoRA fine-tuning over ``sequences`` rows of
    ``seq`` tokens: forward plus activation-gradient backward of every base
    matrix product, forward and backward of the causal attention (the
    backward is twice the forward), and the adapters' forward, activation
    gradient and weight gradient."""
    w = widths(cfg)
    tokens = sequences * seq
    base = 2.0 * 2.0 * matmul_params(cfg) * tokens
    attn = 3.0 * attn_fwd_flops(cfg, seq) * sequences
    lora = 3.0 * 2.0 * lora_params_per_layer(cfg, rank, targets) * w["L"] \
        * tokens
    return base + attn + lora


def decode_step_work(cfg: dict, *, positions, tenants: int, rank: int,
                     targets) -> tuple[float, float]:
    """(FLOPs, bytes) one decode step requires for active requests at
    absolute ``positions`` (each attends to position+1 keys), serving
    ``tenants`` distinct adapters."""
    w = widths(cfg)
    n = len(positions)
    ctx = sum(int(p) + 1 for p in positions)
    lora = lora_params_per_layer(cfg, rank, targets) * w["L"]
    flops = (2.0 * matmul_params(cfg) * n
             + 4.0 * w["h"] * w["hd"] * ctx * w["L"]
             + 2.0 * lora * n)
    kv_bytes = 2.0 * w["kh"] * w["hd"] * w["wbytes"] * w["L"] * ctx
    nbytes = (matmul_params(cfg) * w["wbytes"] + kv_bytes
              + lora * w["wbytes"] * tenants)
    return flops, nbytes


def least_time_s(flops: float, nbytes: float, peaks: dict) -> tuple[float,
                                                                    str]:
    """The least time the chip could take, and which bound sets it."""
    t_c = flops / peaks["flops_bf16"]
    t_m = nbytes / peaks["hbm_bw"]
    return (t_c, "compute") if t_c >= t_m else (t_m, "hbm")
