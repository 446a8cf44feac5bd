"""The engine's cost per task: worker-seconds of the window not spent in
a task body, over the tasks completed in it (engine RUN_START/RUN_END
events, clipped to the window)."""


def read(ctx):
    d = ctx.work
    if not d.completed:
        return None
    return (ctx.seconds * d.workers - d.run_s) / d.completed * 1e6
