"""Share of the served batches' `greedy_generate` calls that found the
model's jitted prefill and decode steps kept, for a batch shape they had
run (`serve_step.jit_cache_info()`), over the whole run, set-up included.
A call that misses traces, lowers and compiles or loads its programs
again.  Nothing where the program keeps no count."""


def read(ctx):
    from repro.runtime import serve_step

    info = getattr(serve_step, "jit_cache_info", None)
    if info is None:
        return None
    counts = info()
    calls = counts["hits"] + counts["misses"]
    return 100.0 * counts["hits"] / calls if calls else None
