"""95th percentile, over the window's requests, of the milliseconds a
request waited from its scheduled arrival to the start of its group's
admission: the program's ``serve.queued`` spans, one per request, recorded
by the program's tracer for the whole window.  A request whose wait
overlaps a pause of the benchmark's own (``rctx["pauses"]``: starting or
stopping the profiler) is left out."""
import numpy as np

import program_trace


def read(ctx):
    tracer = ctx.get("tracer")
    if tracer is None:
        return None
    pauses = ctx.get("pauses", ())
    waits = [s.seconds for s in tracer.named("serve.queued")
             if program_trace.clear_of(pauses, s.start_ns, s.end_ns)]
    if not waits:
        return None
    return 1e3 * float(np.percentile(waits, 95))
