"""Milliseconds per traced step in which a collective of the backward pass
runs on chip 0 and no other op does.  Read by
``phases.READERS``."""
import phases as P


def read(ctx):
    return P.READERS["tmp.bwd_exposed_ms"](ctx)
