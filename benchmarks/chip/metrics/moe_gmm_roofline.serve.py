"""The MoE expert kernel's share of its roofline while serving: the least
time of the expert products of the batches completed in the traced
window (counts/moe_gmm at each batch's rows and experts hit, from the
program's counter `serve_step.moe_counts()`), over the device time of the
ops named `moe_gmm` (the kernel's `pallas_call` name) in the window.
None where the program keeps no such counter or runs no such op.

The profiler keeps a bounded number of device events: in a 51 s traced
run of serve.deepseek-v2-lite.chat-2k it kept the first 4.55 million, to
32.1 s, ops and programs alike.  So the batches' least time is taken in
the share of their serving-step programs (`jit_prefill_step`,
`jit_serve_step`: one a step, `calls` / MoE layers of them a batch) that
the trace holds.

Known error: a batch still running when the trace ends has part of its
steps and ops in it, and no counts; the share reads low by at most one
batch's part."""

KERNEL = "moe_gmm"
STEPS = ("jit_prefill_step", "jit_serve_step")


def read(ctx):
    tr = ctx.trace
    if tr is None:
        return None
    from repro.runtime import serve_step

    records = getattr(serve_step, "moe_counts", None)
    if records is None:
        return None
    d, cfg = ctx.work, ctx.cfg
    kernel_s = sum(s for text, s in tr.op_s.items()
                   if text.lstrip("%").startswith(KERNEL))
    done = [r for r in records() if d.t0 <= r["t_done"] <= d.t1]
    traced = sum(n for m, n in tr.module_calls.items() if m.startswith(STEPS))
    if not kernel_s or not done or not traced:
        return None
    moe_layers = cfg["num_hidden_layers"] - cfg["first_k_dense_replace"]
    steps = sum(r["calls"] for r in done) / moe_layers
    D, F = cfg["hidden_size"], cfg["moe_intermediate_size"]
    count = ctx.count(KERNEL)
    least = sum(count.least_s(r["rows"], r["experts_hit"], D, F,
                              ctx.peaks)[0] for r in done)
    return 100.0 * least * min(traced / steps, 1.0) / kernel_s
