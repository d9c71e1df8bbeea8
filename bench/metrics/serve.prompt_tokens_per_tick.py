"""Prompt tokens the engine admitted per tick over the window (the engine's
own ``prompt_tokens`` and ``steps`` counts)."""


def read(ctx):
    if ctx.get("kind") != "serve" or not ctx.get("ticks"):
        return None
    return ctx["prompt_tokens"] / ctx["ticks"]
