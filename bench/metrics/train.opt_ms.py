"""Chip-0 busy milliseconds per traced step in the optimizer update: ops
whose scope path holds ``optim.`` (``optim.adamw``) and neither pass.  Read by
``phases.READERS``."""
import phases as P


def read(ctx):
    return P.READERS["train.opt_ms"](ctx)
