"""The chip's idle share while the server had work: 100 x the device-idle
seconds of the traced window whose innermost host span does not end in
`.idle`, over the window.  The program names its threads' waits for work
`engine.idle` (the dispatch loop's poll sleep with nothing to run) and
`frontend.idle` (the coalescer on an empty queue with no batch in
flight), so what is left is idle time the serving path itself caused:
lowering, dispatch, sync, batch formation.  None where no gap is named
`.idle`: a program without these spans cannot tell the two apart.

Known error: a request held for the Frontend's batch deadline
(`max_wait_ms`) while the engine polls is counted as `engine.idle`; at
most `max_wait_ms` a batch (20 ms x 19 batches, about 0.4 s, under 1% of
a 51 s window in serve.qwen2-vl-2b.chat)."""

IDLE = ".idle"


def read(ctx):
    if ctx.trace is None:
        return None
    gaps = ctx.trace.gaps
    if not any(what.endswith(IDLE) for _s, what in gaps):
        return None
    stalled = sum(s for s, what in gaps if not what.endswith(IDLE))
    return 100.0 * stalled / ctx.trace.window_s
