"""Milliseconds per step in which a collective (all-reduce, all-gather,
reduce-scatter, collective-permute, the two ends of an asynchronous
all-reduce) runs on chip 0, from the trace."""


def read(ctx):
    tr = ctx.get("trace")
    if ctx.get("kind") != "train" or not tr or not tr["collective_s"] \
            or not ctx.get("steps"):
        return None
    return 1e3 * tr["collective_s"] / ctx["steps"]
