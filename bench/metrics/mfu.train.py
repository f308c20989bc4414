"""Model FLOP utilization of the training rounds in the traced slice: the
FLOPs those rounds require (bench/flops.py: forward and activation-gradient
backward of every matrix product, causal attention, the adapters; no
recomputation) over the slice's length times the cell's chips times one
chip's bf16 peak.  The slice holds whole rounds, so host time between them
counts against it."""


def read(ctx):
    if not ctx["slice"] or not ctx["rounds_in_slice"]:
        return None
    lo, hi = ctx["slice"]
    seconds = (hi - lo) / 1e9
    return 100.0 * ctx["rounds_in_slice"] * ctx["round_flops"] / (
        seconds * ctx["chips"] * ctx["peaks"]["flops_bf16"])
