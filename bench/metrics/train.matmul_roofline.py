"""The matmul kernels' share of the chip's bf16 peak: the matmul work the
compiled step executes (walked from its HLO, recompute included) over the
trace's time in ops that run a matmul, on chip 0."""


def read(ctx):
    tr = ctx.get("trace")
    if ctx.get("kind") != "train" or not tr or not tr["matmul_s"] \
            or not ctx.get("steps"):
        return None
    work = ctx["executed_dot_flops_per_step"] * ctx["steps"]
    return 100.0 * work / (tr["matmul_s"] * ctx["peak_flops"])
