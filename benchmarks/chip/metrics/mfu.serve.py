"""The serving steps' share of the chip's bf16 peak: model operations of
the requests served (at published widths, from the count that the
configuration names under `count`), over the task bodies' time (engine
RUN_START/RUN_END of the batch tasks) times the peak."""


def read(ctx):
    d = ctx.work
    if not d.batch_run_s or not d.served:
        return None
    per = ctx.count(ctx.cfg["count"]).request_flops(
        ctx.cfg, d.prompt_len, d.max_new)
    return 100.0 * per * len(d.served) / (
        d.batch_run_s * ctx.peaks["bf16_flops_per_s"])
