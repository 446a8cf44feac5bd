"""Seconds inside the window spent tracing, lowering and compiling or
loading from the persistent cache (JAX's monitoring events): work the
serving path redoes per batch where it builds a new `jax.jit` each
call."""


def read(ctx):
    return ctx.compile["seconds"]
