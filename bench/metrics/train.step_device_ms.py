"""Milliseconds per step in which an op ran on chip 0: the union of the
trace's op intervals over the traced steps."""


def read(ctx):
    tr = ctx.get("trace")
    if ctx.get("kind") != "train" or not tr or not ctx.get("steps"):
        return None
    return 1e3 * tr["busy_s_dev0"] / ctx["steps"]
