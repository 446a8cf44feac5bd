"""95th percentile latency of every request due in the window, from when
it was due to be sent to its response; a missing one counts as the
wait."""


def read(ctx):
    return ctx.work.latency_ms(0.95)
