"""Share of the window's slot-ticks that held a request: the engine's
``slot_ticks`` over ticks x slots.  Read by
``phases.READERS``."""
import phases as P


def read(ctx):
    return P.READERS["serve.slot_occupancy_pct"](ctx)
