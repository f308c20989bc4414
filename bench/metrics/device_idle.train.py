"""Share of the traced slice of training rounds in which no op ran on a
device, in percent, averaged over the cell's devices: 1 - (union of the
device's op intervals) / slice.  From the trace."""
import harness


def read(ctx):
    if not ctx["slice"] or not ctx["events"]["device"]:
        return None
    lo, hi = ctx["slice"]
    return 100.0 * (1.0 - harness.mean_busy_ns(ctx["events"], lo, hi)
                    / (hi - lo))
