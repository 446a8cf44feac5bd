"""Median time a served request waited between its admission by the
Frontend (`ServeRequest.t_enqueue`) and the start of the task body that
served it (the benchmark's span around `execute_batch`)."""

from common import percentile


def read(ctx):
    waits = ctx.work.queue_waits
    return percentile(sorted(waits), 0.5) * 1e3 if waits else None
