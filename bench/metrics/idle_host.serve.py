"""Share of the traced slice, in percent, in which no op ran on the device
while the scheduler did host work: the slice's idle gaps split by the
innermost program span (``serve.*``, on the profiler's clock) over them,
less the part under ``serve.wait`` (the sleep until the next arrival) and
the part under no span."""
import harness
import program_trace


def read(ctx):
    events = ctx.get("program_events")
    if not events or not ctx["slice"] or not ctx["events"]["device"]:
        return None
    lo, hi = ctx["slice"]
    split = program_trace.idle_by_span(
        harness.idle_gaps(ctx["events"], lo, hi),
        [e for e in events if e[0].startswith("serve.")])
    host = sum(v for k, v in split.items() if k not in ("serve.wait", "none"))
    return 100.0 * host / (hi - lo)
