"""95th percentile, in milliseconds, of the window's finished requests'
wait from submission to a slot (the engine's ``t_admit - t_submit``).  Read by
``phases.READERS``."""
import phases as P


def read(ctx):
    return P.READERS["serve.queue_wait_p95_ms"](ctx)
