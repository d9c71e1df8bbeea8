"""95th percentile, in milliseconds, of the window's finished requests'
time from admission to the first output token (the engine's ``t_first -
t_admit``): the one-token-per-tick prompt path.  Read by
``phases.READERS``."""
import phases as P


def read(ctx):
    return P.READERS["serve.prefill_p95_ms"](ctx)
