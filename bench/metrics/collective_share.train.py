"""Share of the traced slice of training rounds, in percent, in which a
collective runs on a device, averaged over the cell's devices: the union of
the intervals of the device's collective ops (all-reduce, all-gather,
reduce-scatter, all-to-all and collective-permute, each also as its
``-start`` and ``-done`` ops, which hold the device while it starts and
waits for an asynchronous one) over the slice.  From the trace.  None
where no device ran a collective (a cell on one chip)."""
import re

import harness

COLLECTIVE = re.compile(
    r"%?(all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute)"
    r"\b")


def read(ctx):
    if not ctx["slice"]:
        return None
    lo, hi = ctx["slice"]
    shares, found = [], False
    for dev in harness.per_device(ctx["events"]):
        spans = [(s, s + d) for name, s, d in dev["device"]
                 if COLLECTIVE.match(name)]
        found = found or bool(spans)
        shares.append(sum(e - s for s, e in harness.merged(spans, lo, hi))
                      / (hi - lo))
    if not found:
        return None
    return 100.0 * sum(shares) / len(shares)
