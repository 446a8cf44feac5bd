"""The trace reduction, on a small trace recorded on a TPU v5 lite by
record_trace.py: three 512^3 tiled A^T B calls inside `bench.window`,
each followed by a 20 ms host sleep in `bench.sleep`."""
from pathlib import Path

import pytest

import devtrace

TRACE = Path(__file__).resolve().parent / "small.xplane.pb"


@pytest.fixture(scope="module")
def reduced():
    return devtrace.reduce_trace(str(TRACE))


def test_window_and_busy_time(reduced):
    assert reduced.devices == 1
    assert reduced.window_s == pytest.approx(0.064674988)
    # three kernel runs of 13.2-13.5 us each, and nothing else
    assert reduced.busy_s == pytest.approx(40.024e-6, rel=1e-6)
    assert 0.999 < reduced.idle_share < 1.0


def test_kernel_found_by_program_name(reduced):
    names = [m for m in reduced.module_s if "tiled_matmul" in m]
    assert len(names) == 1
    assert reduced.module_calls[names[0]] == 3
    assert reduced.module_s[names[0]] == pytest.approx(40.036e-6, rel=1e-6)


def test_breakdown_names_ops_and_idle_gaps(reduced):
    bd = reduced.breakdown()
    assert bd["device_ops"] == [["tiled_matmul.1", pytest.approx(40.024e-6)]]
    gaps = dict(bd["idle_gaps"])
    # the host slept through nearly all the idle time
    assert gaps["bench.sleep"] > 0.06
    assert sum(gaps.values()) == pytest.approx(
        reduced.window_s - reduced.busy_s, rel=1e-9)
    assert not reduced.collective_s()


def test_collectives_are_recognised_by_their_hlo():
    assert devtrace.is_collective(
        "%all-gather-start.1 = (s32[8]) all-gather-start(s32[2] %p)")
    assert devtrace.is_collective(
        "%cp.3 = s32[4,128] collective-permute(s32[4,128] %x)")
    assert not devtrace.is_collective(
        "%fusion.2 = f32[8] fusion(f32[8] %a), kind=kLoop")
    assert devtrace.op_name("%tiled_matmul.1 = f32[2] custom-call()") \
        == "tiled_matmul.1"
