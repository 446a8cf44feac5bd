"""The tiled A^T B kernel's share of its roofline: calls in the traced
window times the least time one call could take (counts/tiled_matmul),
over the kernel's device time in the trace.  The kernel is found by its
program's name (`jit_tiled_matmul`)."""

KERNEL = "tiled_matmul"


def read(ctx):
    tr = ctx.trace
    if tr is None:
        return None
    names = [m for m in tr.module_s if KERNEL in m]
    kernel_s = sum(tr.module_s[m] for m in names)
    calls = sum(tr.module_calls[m] for m in names)
    if not kernel_s or not calls:
        return None
    n = ctx.work.n
    least, _bound = ctx.count(KERNEL).least_s(n, n, n, ctx.peaks)
    return 100.0 * least * calls / kernel_s
