"""Share of the decode chunks' slot-steps that produced a kept token, in
percent, over the window: the program's counters ``serve.decode_tokens``
(tokens kept from each chunk) over ``serve.decode_slot_steps`` (engine
slots x steps of each chunk call)."""


def read(ctx):
    tracer = ctx.get("tracer")
    steps = tracer.counters.get("serve.decode_slot_steps", 0) if tracer else 0
    if not steps:
        return None
    return 100.0 * tracer.counters.get("serve.decode_tokens", 0) / steps
