"""Host milliseconds per round that the trainer spends staging the round's
client batches: the benchmark's span around each ``round_batch`` call the
trainer makes during the window, averaged over the rounds."""


def read(ctx):
    if not ctx["stage"]:
        return None
    return 1e3 * sum(s.seconds for s in ctx["stage"]) / len(ctx["stage"])
