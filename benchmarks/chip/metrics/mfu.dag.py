"""The whole run's share of the chip's bf16 peak: the operations of the
fan-out tasks completed in the window (2 n^3 each), over the window
times the peak."""


def read(ctx):
    n = ctx.work.n
    ops = ctx.count("tiled_matmul").flops(n, n, n) * ctx.work.fan_out_completed
    return 100.0 * ops / (ctx.seconds * ctx.peaks["bf16_flops_per_s"])
