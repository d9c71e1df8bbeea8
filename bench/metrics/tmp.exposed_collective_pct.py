"""Share of the traced window in which a collective runs on chip 0 and no
other op does: communication the schedule left exposed."""


def read(ctx):
    tr = ctx.get("trace")
    if ctx.get("kind") != "train" or not tr or not tr["collective_s"]:
        return None
    return 100.0 * tr["exposed_collective_s"] / tr["window_s"]
