"""Median milliseconds of an admission call (the group prefill that emits
each newcomer's first token), from the benchmark's blocking span around
every admit call in the window."""
import statistics


def read(ctx):
    if not ctx["admits"]:
        return None
    return 1e3 * statistics.median(s.seconds for s in ctx["admits"])
