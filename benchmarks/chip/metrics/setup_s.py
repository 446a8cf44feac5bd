"""Set-up: process start to the window's start (imports, data, build,
compiles or cache loads, warm-up), on the host clock."""


def read(ctx):
    return ctx.setup_s
