"""Wall time of the window's whole steps over their number: from the
window's start to the end of the last step, each step ending when its
outputs are ready on the devices."""


def read(ctx):
    return ctx.work.step_ms()
