"""Tasks of the DAG completed inside the window, over the window."""


def read(ctx):
    return ctx.work.completed / ctx.seconds
