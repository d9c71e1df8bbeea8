"""Mean milliseconds per step the loop waited for its next placed batch
(the benchmark's span around each fetch from the feed)."""


def read(ctx):
    waits = ctx.get("input_wait_s")
    if ctx.get("kind") != "train" or not waits:
        return None
    return 1e3 * sum(waits) / len(waits)
