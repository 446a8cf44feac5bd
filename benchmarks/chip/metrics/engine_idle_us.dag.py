"""What the engine costs the chip per task: device-idle seconds of the
traced window whose innermost host span starts with `engine.` or
`client.` (the program's spans, `repro.core.engine.tracing.span`), over
the tasks completed in the window, in microseconds.  Beside
`task_overhead_us.dag`, the engine's host time per task, this is the part
of it the chip waits through.  None where no idle gap lies under such a
span: a program without these spans."""

LAYERS = ("engine.", "client.")


def read(ctx):
    if ctx.trace is None or not ctx.work.completed:
        return None
    named = [s for s, what in ctx.trace.gaps if what.startswith(LAYERS)]
    if not named:
        return None
    return sum(named) / ctx.work.completed * 1e6
