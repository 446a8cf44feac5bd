"""Share of the mesh verbs' calls that found their jitted program kept
(`mesh_ops.jit_cache_info()`), over the whole run, set-up included.  A
call that misses builds a new `jax.jit`, so traces, lowers and compiles
or loads its program again.  Nothing where the program keeps no count."""


def read(ctx):
    info = getattr(getattr(ctx.work, "ops", None), "jit_cache_info", None)
    if info is None:
        return None
    counts = info()
    calls = counts["hits"] + counts["misses"]
    return 100.0 * counts["hits"] / calls if calls else None
