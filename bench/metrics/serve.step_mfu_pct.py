"""The decode tick's share of the chip's roofline: for each traced tick the
larger of its operations over the bf16 peak and its bytes (every weight
once, the cache of each active request up to its position) over the HBM
bandwidth, summed, over the time in which an op ran on chip 0."""


def read(ctx):
    tr = ctx.get("trace")
    if ctx.get("kind") != "serve" or not tr or not tr["busy_s_dev0"] \
            or not ctx.get("ticks_traced"):
        return None
    return 100.0 * ctx["roofline_s"] / tr["busy_s_dev0"]
