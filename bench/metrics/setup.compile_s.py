"""Seconds JAX spent compiling, or loading compiled programs from the
persistent cache, during set-up (JAX's monitoring events)."""


def read(ctx):
    return ctx.get("setup_compile_s")
