"""Milliseconds per traced step in which a collective of the forward pass
runs on chip 0 and no other op does.  Read by
``phases.READERS``."""
import phases as P


def read(ctx):
    return P.READERS["tmp.fwd_exposed_ms"](ctx)
