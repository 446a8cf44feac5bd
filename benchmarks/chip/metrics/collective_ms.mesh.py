"""Device time per step of the collective operations (all-gather,
all-reduce, all-to-all, collective-permute, reduce-scatter and their
async halves), from the trace, averaged over the chips."""


def read(ctx):
    if ctx.trace is None or not ctx.work.steps:
        return None
    return ctx.trace.collective_s() / ctx.work.steps * 1e3
