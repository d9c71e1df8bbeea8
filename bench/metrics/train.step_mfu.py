"""The whole step's share of the chip's bf16 peak while the chip works:
model FLOPs of the traced steps (no recompute) per chip, over the time in
which an op ran on chip 0."""


def read(ctx):
    tr = ctx.get("trace")
    if ctx.get("kind") != "train" or not tr or not tr["busy_s_dev0"] \
            or not ctx.get("steps"):
        return None
    work = ctx["model_flops_per_step"] * ctx["steps"] / ctx["chips"]
    return 100.0 * work / (tr["busy_s_dev0"] * ctx["peak_flops"])
