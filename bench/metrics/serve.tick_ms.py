"""Mean milliseconds of one ``ServingEngine.step()`` over the window (the
benchmark's span around each call)."""


def read(ctx):
    ticks = ctx.get("tick_s")
    if ctx.get("kind") != "serve" or not ticks:
        return None
    return 1e3 * sum(ticks) / len(ticks)
