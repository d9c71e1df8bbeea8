"""Chip-0 busy milliseconds per traced step in ops of the forward pass:
their scope path holds ``jvp(`` and not ``transpose(``.  Read by
``phases.READERS``."""
import phases as P


def read(ctx):
    return P.READERS["train.fwd_ms"](ctx)
