"""Decode chunks' share of the chip's roofline in the traced slice: the
least time the chunks' work could take (per step, the larger of its FLOPs
over the bf16 peak and its required bytes over HBM bandwidth: every base
weight once, each active request's keys and values up to its position, the
adapter rows of each distinct tenant; bench/flops.py) over the chunks'
measured blocking spans.  Decode at these sizes is bound by HBM bytes."""


def read(ctx):
    chunks = [s for s in ctx["chunks"] if s.info and s.info["positions"]]
    if not chunks:
        return None
    least = sum(ctx["chunk_work"](s.info)[0] for s in chunks)
    return 100.0 * least / sum(s.seconds for s in chunks)
