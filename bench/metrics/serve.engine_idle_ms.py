"""Chip-0 idle milliseconds per traced tick in gaps whose middle lies
under one of the engine's own ``serve.*`` spans.  Read by
``phases.READERS``."""
import phases as P


def read(ctx):
    return P.READERS["serve.engine_idle_ms"](ctx)
