"""Mean milliseconds, over consecutive chunks of the window, from one
chunk's ``fed.sync`` end (its metrics on the host) to the next chunk's
``fed.call`` start (its dispatch): the host time between two chunks'
device work, from the program's tracer.  A gap that overlaps a pause of the
benchmark's own (``rctx["pauses"]``: starting or stopping the profiler) is
left out."""
import program_trace


def read(ctx):
    tracer = ctx.get("tracer")
    if tracer is None:
        return None
    # each step's parent is its fed.chunk; chunks run in index order
    spans = tracer.spans
    calls = {s.parent: s.start_ns for s in spans if s.name == "fed.call"}
    syncs = {s.parent: s.end_ns for s in spans if s.name == "fed.sync"}
    chunks = sorted(set(calls) & set(syncs))
    pauses = ctx.get("pauses", ())
    gaps = [calls[b] - syncs[a] for a, b in zip(chunks, chunks[1:])
            if program_trace.clear_of(pauses, syncs[a], calls[b])]
    if not gaps:
        return None
    return sum(gaps) / len(gaps) / 1e6
