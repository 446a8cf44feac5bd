"""The served MoE model's counts and kernel metric, against numbers worked
by hand: `counts/deepseek-v2-lite.py` (`mfu.serve`'s operations),
`counts/moe_gmm.py`, and the `moe_gmm_roofline.serve` reader over a
trace and a counter made by hand."""
from types import SimpleNamespace

import pytest

import devtrace
import registry
import run

BENCH = registry.benchmark()
CFG = registry.config(BENCH, "deepseek-v2-lite")
PEAKS = run.peaks_for("TPU v5 lite")
ROOFLINE = registry.metric("moe_gmm_roofline.serve")
GMM = registry.count("moe_gmm")


def test_request_flops_at_published_widths():
    """Per token: 27 attention layers of 13,762,560 weights (q 6,291,456;
    latent 1,179,648; k and v up 1,048,576 each; o 4,194,304), the dense
    FFN's 67,239,936, and 26 MoE layers of 131,072 router weights and 3.5
    gated experts of 8,650,752 (1.5 routed here, 2 shared): 1,229,455,360
    weights, 2,458,910,720 operations.  The head: 2 x 2048 x 102,400.
    Attention: 2 x 27 x 16 x (192 + 128) = 276,480 a query and context
    token.  A 4-token prompt and 2 tokens: 5 token passes, 2 heads and
    4 x 5 / 2 + 5 = 15 query-context pairs."""
    body, head, pair = 2_458_910_720, 419_430_400, 276_480
    count = registry.count(CFG["count"])
    assert count.request_flops(CFG, 4, 2) == 5 * body + 2 * head + 15 * pair
    assert count.request_flops(CFG, 1, 1) == body + head + pair


def test_moe_gmm_count():
    """6 decode rows over 2 hit experts of D 2048, F 1408: 2 x 3 x 6 x
    2048 x 1408 operations; bytes 2 x (2 x 3 x 2048 x 1408 + 6 x 3 x
    2048), bound by HBM bandwidth."""
    assert GMM.flops(6, 2048, 1408) == 103_809_024
    assert GMM.bytes_moved(6, 2, 2048, 1408) == 34_676_736
    least, bound = GMM.least_s(6, 2, 2048, 1408, PEAKS)
    assert bound == "memory"
    assert least == pytest.approx(34_676_736 / 819e9)
    least, bound = GMM.least_s(12288, 16, 2048, 1408, PEAKS)
    assert bound == "compute"
    assert least == pytest.approx(2 * 3 * 12288 * 2048 * 1408 / 197e12)


# two batches of 64 steps (26 MoE layers x 64 calls each) in the window
STEPS = {"jit_prefill_step(123)": 2, "jit_serve_step(456)": 126,
         "jit_broadcast_in_dim(7)": 126}


def _ctx(op_s, t0=10.0, t1=20.0, modules=STEPS):
    trace = devtrace.Reduced(window_s=t1 - t0, busy_s=1.0, devices=1,
                             op_s=op_s, module_calls=dict(modules))
    return SimpleNamespace(trace=trace, work=SimpleNamespace(t0=t0, t1=t1),
                           cfg=CFG, peaks=PEAKS, count=registry.count)


OPS = {"%moe_gmm.1 = bf16[16,2048]{1,0} custom-call(%a, %b), "
       "custom_call_target=\"tpu_custom_call\"": 0.004,
       "%moe_gmm.2 = bf16[12288,2048]{1,0} custom-call(%c)": 0.006,
       "%fusion.3 = bf16[1,2048]{1,0} fusion(%moe_gmm.1)": 5.0}
RECORDS = [{"t_done": 9.0, "rows": 999, "experts_hit": 99, "calls": 9},
           {"t_done": 12.0, "rows": 6, "experts_hit": 2, "calls": 1664},
           {"t_done": 19.5, "rows": 3000, "experts_hit": 16, "calls": 1664},
           {"t_done": 21.0, "rows": 999, "experts_hit": 99, "calls": 9}]


@pytest.fixture
def counter(monkeypatch):
    from repro.runtime import serve_step

    monkeypatch.setattr(serve_step, "moe_counts", lambda: list(RECORDS))
    return serve_step


def test_roofline_reads_the_window_batches_over_the_kernel_time(counter):
    """The least times of the two batches done inside [10, 20] (6 rows on
    2 experts; 3,000 rows on 16), over the 10 ms of ops named moe_gmm;
    the fusion that reads the kernel's output is not the kernel."""
    least = sum(GMM.least_s(r, e, 2048, 1408, PEAKS)[0]
                for r, e in ((6, 2), (3000, 16)))
    assert ROOFLINE.read(_ctx(OPS)) == pytest.approx(100 * least / 0.010)


def test_roofline_takes_the_share_of_steps_the_trace_kept(counter):
    """A trace that kept 96 of the two batches' 128 serving steps (the
    profiler's event bound) holds three quarters of their kernel time,
    so three quarters of their least time is compared with it."""
    least = sum(GMM.least_s(r, e, 2048, 1408, PEAKS)[0]
                for r, e in ((6, 2), (3000, 16)))
    kept = {"jit_prefill_step(123)": 2, "jit_serve_step(456)": 94}
    assert ROOFLINE.read(_ctx(OPS, modules=kept)) == pytest.approx(
        100 * least * 0.75 / 0.010)


def test_roofline_reads_nothing_without_a_trace_kernel_or_batch(counter):
    assert ROOFLINE.read(SimpleNamespace(trace=None)) is None
    no_kernel = {k: v for k, v in OPS.items() if k.startswith("%fusion")}
    assert ROOFLINE.read(_ctx(no_kernel)) is None
    assert ROOFLINE.read(_ctx(OPS, t0=30.0, t1=40.0)) is None
    assert ROOFLINE.read(_ctx(OPS, modules={})) is None


def test_roofline_reads_nothing_from_a_program_without_the_counter(
        monkeypatch):
    from repro.runtime import serve_step

    monkeypatch.delattr(serve_step, "moe_counts")
    assert ROOFLINE.read(_ctx(OPS)) is None
