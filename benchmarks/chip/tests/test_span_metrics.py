"""The readers of the program's spans, over idle gaps named by hand: what
`engine_idle_us.dag` and `server_idle_share.serve` count, and when they
read nothing."""
from types import SimpleNamespace

import pytest

import devtrace
import registry

ENGINE = registry.metric("engine_idle_us.dag")
SERVER = registry.metric("server_idle_share.serve")


def _ctx(gaps, completed=100, window_s=10.0):
    trace = devtrace.Reduced(window_s=window_s, busy_s=1.0, devices=1,
                             gaps=gaps)
    return SimpleNamespace(trace=trace,
                           work=SimpleNamespace(completed=completed))


GAPS = [(0.004, "engine.round"), (0.002, "engine.steal"),
        (0.001, "client.submit"), (0.003, "client.resolve"),
        (0.5, "engine.idle"), (0.25, "frontend.idle"),
        (0.2, "frontend.wait"), (1.0, "lower_sharding_computation"),
        (0.05, "no host span"), (0.04, "bench.fan_out"),
        (0.006, "ReadSyncFlag")]


def test_engine_idle_counts_engine_and_client_spans_per_task():
    # 0.004 + 0.002 + 0.001 + 0.003 + 0.5 s over 100 tasks
    assert ENGINE.read(_ctx(GAPS)) == pytest.approx(5100.0)


def test_engine_idle_prefix_is_the_layer_not_a_substring():
    gaps = [(0.01, "engine.run"), (1.0, "bench.engine.x"),
            (1.0, "engines"), (1.0, "my_client.submit")]
    assert ENGINE.read(_ctx(gaps, completed=10)) == pytest.approx(1000.0)


def test_server_idle_leaves_out_only_the_idle_spans():
    # everything but engine.idle and frontend.idle: 1.306 s of 10 s
    assert SERVER.read(_ctx(GAPS)) == pytest.approx(13.06)


@pytest.mark.parametrize("reader", [ENGINE, SERVER])
def test_no_trace_reads_nothing(reader):
    assert reader.read(SimpleNamespace(
        trace=None, work=SimpleNamespace(completed=100))) is None


def test_engine_idle_with_no_completed_task_reads_nothing():
    assert ENGINE.read(_ctx(GAPS, completed=0)) is None


@pytest.mark.parametrize("reader", [ENGINE, SERVER])
def test_a_program_without_the_spans_reads_nothing(reader):
    gaps = [(0.5, "no host span"), (1.0, "lower_sharding_computation"),
            (0.2, "bench.serve_batch")]
    assert reader.read(_ctx(gaps)) is None
