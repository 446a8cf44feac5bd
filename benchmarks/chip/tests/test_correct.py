"""What decides `correct`, at sizes a CPU holds: each cell's sound run
passes, and its control and each fault its timed path can have come out
not correct.  Each run skips the harness's look for a chip and drives
the rest of a run (set-up, window, finish, check) with the timed path
broken underneath.
"""
import functools

import jax
import jax.numpy as jnp
import pytest

import registry
import run

BENCH = registry.benchmark()
PEAKS = run.peaks_for("TPU v5 lite")


def run_tiny(workload, cfg_over, traffic_over, *, patch=None, seed=2**33 + 17,
             seconds=1.0):
    """One run of `workload` at a small size; `patch(work)` breaks it."""
    cell = registry.cell(BENCH, workload)
    cfg = {**registry.config(BENCH, cell["config"]), **cfg_over}
    traffic = {**registry.traffic(cell["traffic"]), **traffic_over}
    base = registry.kind(traffic["kind"])

    class Workload(base.Workload):
        def __init__(self, *a, **kw):
            super().__init__(*a, **kw)
            if patch is not None:
                patch(self)

    return run.run_cell(
        BENCH, cell, cfg, traffic, seed=seed, seconds=seconds, trace=False,
        devices=jax.devices()[:cell["chips"]], t_start=run.T_START,
        peaks=PEAKS, kind_module=type("kind", (), {"Workload": Workload}))


# ------------------------------------------------------------------- dag
DAG = dict(n=256, fan_out=16, group=4, check_waves=2)


def interpret(d):
    from repro.kernels.tiled_matmul.ops import tiled_matmul

    d.kernel = functools.partial(tiled_matmul, interpret=True)


def altered_answer(d):
    interpret(d)
    task = d.task

    def fan_out_task(kernel, a, b, x):
        y = task(kernel, a, b, x)
        return y.at[0].add(1e-3 * jnp.max(jnp.abs(y)))

    d.task = fan_out_task


def half_the_inputs(d):
    interpret(d)

    def fan_in_task(*ys):
        half = ys[:max(len(ys) // 2, 1)]
        return jnp.sum(jnp.stack(half), 0) * (len(ys) / len(half))

    d.combine = fan_in_task


def dag_control(d):
    interpret(d)
    d.use_control()


def test_dag_sound_run_is_correct():
    res = run_tiny("metg.atb-1024", {}, DAG, patch=interpret)
    assert res["correct"], res["checks"]
    assert res["metrics"]["dag_tasks_per_s"]["value"] > 0


@pytest.mark.parametrize("fault", [altered_answer, half_the_inputs,
                                   dag_control])
def test_dag_fault_is_not_correct(fault):
    res = run_tiny("metg.atb-1024", {}, DAG, patch=fault)
    assert not res["correct"], (fault.__name__, res["checks"])


# ----------------------------------------------------------------- serve
SERVE_CFG = dict(hidden_size=256, num_attention_heads=4,
                 num_key_value_heads=2, num_hidden_layers=2,
                 intermediate_size=512, vocab_size=2048,
                 rope_scaling={"type": "mrope", "mrope_section": [8, 12, 12]},
                 program_overrides={"vocab_pad_multiple": 16},
                 # at this size a sound run reads 0.0 to 0.004
                 limits={"served_token_logit_gap": 0.02})
SERVE = dict(prompt_len=24, max_new=6, rate_per_s=4.0, check_requests=4,
             warmup_requests=1)


def altered_token(d):
    from repro.runtime import serve_step

    real = serve_step.greedy_generate
    V = d.cfg["vocab_size"]

    def greedy_generate(*a, **kw):
        return (real(*a, **kw) + 1) % V

    d._restore = (serve_step, "greedy_generate", real)
    serve_step.greedy_generate = greedy_generate


def cache_unchanged(d):
    from repro.runtime import serve_step

    real = serve_step.make_decode_step

    def make_decode_step(model):
        step = real(model)

        def stale(params, tokens, positions, cache):
            tok, _new = step(params, tokens, positions, cache)
            return tok, cache

        return stale

    d._restore = (serve_step, "make_decode_step", real)
    serve_step.make_decode_step = make_decode_step


def serve_control(d):
    d.use_control()


def _serve(patch=None):
    holder = {}

    def wrapped(d):
        holder["d"] = d
        if patch is not None:
            patch(d)

    try:
        return run_tiny("serve.qwen2-vl-2b.chat", SERVE_CFG, SERVE,
                        patch=wrapped, seconds=1.5)
    finally:
        restore = getattr(holder.get("d"), "_restore", None)
        if restore is not None:
            setattr(*restore)


def test_serve_sound_run_is_correct():
    res = _serve()
    assert res["correct"], res["checks"]
    assert res["attempted"] == 6 and res["failed"] == 0


@pytest.mark.parametrize("fault", [altered_token, cache_unchanged,
                                   serve_control])
def test_serve_fault_is_not_correct(fault):
    res = _serve(fault)
    assert not res["correct"], (fault.__name__, res["checks"])


# ------------------------------------------------------------------- bsp
# 4 x 131072 rows of values below 64: sums and prefix sums pass 2^24,
# where float32 starts to round, and stay below 2^31
BSP = dict(rows_per_chip=131072, width=8, value_high=64)


def scan_without_exchange(d):
    from repro.core.mpi_list import mesh_ops

    def dfm_scan(mesh, f, dfm):
        k = len(mesh.devices.flat)
        blocks = dfm.reshape(k, dfm.shape[0] // k, *dfm.shape[1:])
        return jnp.cumsum(blocks, 1).reshape(dfm.shape)

    d.ops = _ops_with(mesh_ops, dfm_scan=dfm_scan)


def group_without_exchange(d):
    from repro.core.mpi_list import mesh_ops

    def group(mesh, dest, dfm):
        k = len(mesh.devices.flat)
        n = dfm.shape[0] // k
        order = jnp.concatenate([
            i * n + jnp.argsort(dest[i * n:(i + 1) * n], stable=True)
            for i in range(k)])
        return jnp.take(dfm, order, 0)

    d.ops = _ops_with(mesh_ops, group=group)


def altered_map(d):
    from repro.core.mpi_list import mesh_ops

    def dfm_map(mesh, f, dfm, **kw):
        out = mesh_ops.dfm_map(mesh, f, dfm, **kw)
        return out.at[0, 0].add(1) if out.ndim == 2 else out

    d.ops = _ops_with(mesh_ops, dfm_map=dfm_map)


def half_the_rows(d):
    from repro.core.mpi_list import mesh_ops

    def dfm_sum(mesh, dfm):
        half = dfm[:dfm.shape[0] // 2]
        return mesh_ops.dfm_sum(mesh, half) * 2

    d.ops = _ops_with(mesh_ops, dfm_sum=dfm_sum)


def bsp_control(d):
    d.use_control()


def _ops_with(module, **over):
    ns = {k: getattr(module, k) for k in dir(module) if not k.startswith("_")}
    ns.update(over)
    return type("ops", (), {k: staticmethod(v) if callable(v) else v
                            for k, v in ns.items()})


def _bsp(patch=None):
    def wrapped(d):
        setup = d.setup

        def patched_setup():
            setup()
            if patch is not None:
                patch(d)
                d.last = d.step()

        d.setup = patched_setup

    return run_tiny("bsp.dfm-4chip", BSP, {}, patch=wrapped, seconds=0.5)


def test_bsp_sound_run_is_correct():
    res = _bsp()
    assert res["correct"], res["checks"]
    assert res["device"]["count"] == 4


@pytest.mark.parametrize("fault", [scan_without_exchange,
                                   group_without_exchange, altered_map,
                                   half_the_rows, bsp_control])
def test_bsp_fault_is_not_correct(fault):
    res = _bsp(fault)
    assert not res["correct"], (fault.__name__, res["checks"])
    assert sum(c["value"] for c in res["checks"].values()) > 0
