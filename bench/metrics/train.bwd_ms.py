"""Chip-0 busy milliseconds per traced step in ops of the backward pass,
recompute inside it included: their scope path holds ``transpose(``.  Read by
``phases.READERS``."""
import phases as P


def read(ctx):
    return P.READERS["train.bwd_ms"](ctx)
