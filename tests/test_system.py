"""End-to-end behaviour tests for the paper's system: the three schedulers
driving real framework work (training steps, campaign files), and the
checkpoint/restart path."""
import json
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest


def test_dwork_drives_training_steps(tmp_path):
    """dwork as the work-distribution layer: training steps are tasks; a
    crashing worker's steps are re-executed by the survivor; the final
    model state matches an uninterrupted run (determinism via per-step
    data/seed in task metadata)."""
    from repro.configs import RunConfig, get_config
    from repro.core.dwork import Client, InProcTransport, TaskServer
    from repro.core.dwork.api import ExitResp, NotFound, TaskMsg
    from repro.models.common import Options
    from repro.models.model import build_model
    from repro.optim.adamw import init_opt
    from repro.runtime.train_step import make_train_step

    cfg = get_config("deepseek-7b").reduced()
    model = build_model(cfg, Options(q_block=32, kv_block=32))
    rc = RunConfig(total_steps=8, warmup_steps=1)
    step_fn = jax.jit(make_train_step(model, rc))

    def run_with_dwork(crash: bool):
        params = model.init(jax.random.PRNGKey(0))
        opt = init_opt(params, rc)
        srv = TaskServer()
        driver = Client(InProcTransport(srv), "driver")
        # sequential chain: step i depends on step i-1
        for i in range(6):
            driver.create(f"step{i}", deps=[f"step{i-1}"] if i else [])
        state = {"params": params, "opt": opt}

        def execute(worker, fail_at=None):
            cl = Client(InProcTransport(srv), worker)
            n = 0
            while True:
                r = cl.steal()
                if isinstance(r, ExitResp):
                    return
                if isinstance(r, NotFound):
                    return
                for name, _ in r.tasks:
                    if fail_at is not None and n >= fail_at:
                        cl.exit()          # crash before completing
                        return
                    i = int(name[4:])
                    key = jax.random.PRNGKey(100 + i)
                    batch = {"tokens": jax.random.randint(
                        key, (2, 32), 0, cfg.vocab_size)}
                    batch["labels"] = jnp.roll(batch["tokens"], -1, 1)
                    state["params"], state["opt"], _ = step_fn(
                        state["params"], state["opt"], batch)
                    cl.complete(name)
                    n += 1

        if crash:
            execute("w0", fail_at=2)       # dies holding step2
            execute("w1")                  # survivor finishes
        else:
            execute("w0")
        assert srv.stats()["completed"] == 6
        return state["params"]

    p_clean = run_with_dwork(crash=False)
    p_crash = run_with_dwork(crash=True)
    for a, b in zip(jax.tree_util.tree_leaves(p_clean),
                    jax.tree_util.tree_leaves(p_crash)):
        assert float(jnp.max(jnp.abs(a - b))) < 1e-6


def test_pmake_campaign_files(tmp_path):
    """pmake end-to-end with the paper's script/log file conventions."""
    rules = """
gen:
  resources: {time: 1, nrs: 1}
  out: {d: "data_{n}.txt"}
  script: "echo payload-{n} > data_{n}.txt"
sum:
  resources: {time: 1, nrs: 1}
  inp: {a: "data_1.txt", b: "data_2.txt"}
  out: {s: "summary.txt"}
  script: "cat data_1.txt data_2.txt > summary.txt"
"""
    targets = 't:\n  dirname: .\n  out: {s: "summary.txt"}\n'
    from repro.core.pmake import PMake
    pm = PMake(rules, targets, root=str(tmp_path), total_nodes=2)
    stats = pm.run()
    assert stats["done"] == 3 and stats["errors"] == 0
    assert (tmp_path / "summary.txt").read_text() == \
        "payload-1\npayload-2\n"


@pytest.mark.parametrize("platforms,slots", [(None, 1), ("tpu", 1),
                                              ("cpu", 4)])
def test_campaign_runs_one_train_per_chip(monkeypatch, platforms, slots):
    """Each popen'd train takes every chip on its host: unless JAX is held
    to the CPU, the campaign runs them one at a time whatever --nodes
    says."""
    from repro.launch.campaign import train_slots
    if platforms is None:
        monkeypatch.delenv("JAX_PLATFORMS", raising=False)
    else:
        monkeypatch.setenv("JAX_PLATFORMS", platforms)
    assert train_slots(4) == slots


def test_mpilist_is_the_data_pipeline():
    """The training pipeline is an mpi-list program: verify its batches
    flow through a real train step without NaNs."""
    from repro.configs import RunConfig, get_config
    from repro.data.pipeline import Pipeline
    from repro.models.common import Options
    from repro.models.model import build_model
    from repro.optim.adamw import init_opt
    from repro.runtime.train_step import make_train_step

    cfg = get_config("rwkv6-1.6b").reduced()
    model = build_model(cfg, Options(q_block=32, kv_block=32))
    rc = RunConfig(total_steps=3, warmup_steps=1)
    params = model.init(jax.random.PRNGKey(0))
    opt = init_opt(params, rc)
    step = jax.jit(make_train_step(model, rc))
    pipe = Pipeline(cfg.vocab_size, 32, 2, seed=1, n_ranks=3)
    for batch in pipe.batches(3):
        params, opt, m = step(params, opt,
                              {k: jnp.asarray(v) for k, v in batch.items()})
        assert np.isfinite(float(m["loss"]))


def test_train_checkpoint_restart_bitexact(tmp_path):
    """Crash/restart via the checkpoint layer reproduces the uninterrupted
    optimizer trajectory (same data => identical params)."""
    from repro.checkpoint import ckpt
    from repro.configs import RunConfig, get_config
    from repro.models.common import Options
    from repro.models.model import build_model
    from repro.optim.adamw import init_opt
    from repro.runtime.train_step import make_train_step

    cfg = get_config("gemma2-2b").reduced()
    model = build_model(cfg, Options(q_block=32, kv_block=32))
    rc = RunConfig(total_steps=6, warmup_steps=1)
    step = jax.jit(make_train_step(model, rc))

    def batch_for(i):
        key = jax.random.PRNGKey(500 + i)
        b = {"tokens": jax.random.randint(key, (2, 32), 0, cfg.vocab_size)}
        b["labels"] = jnp.roll(b["tokens"], -1, 1)
        return b

    params = model.init(jax.random.PRNGKey(0))
    opt = init_opt(params, rc)
    for i in range(4):
        params, opt, _ = step(params, opt, batch_for(i))
    ref = params

    params = model.init(jax.random.PRNGKey(0))
    opt = init_opt(params, rc)
    for i in range(2):
        params, opt, _ = step(params, opt, batch_for(i))
    ckpt.save(str(tmp_path), 2, {"p": params, "o": opt})
    # "crash"; restart from disk
    abs_tree = jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype),
        {"p": params, "o": opt})
    tree = ckpt.restore(str(tmp_path), 2, abs_tree)
    params, opt = tree["p"], tree["o"]
    for i in range(2, 4):
        params, opt, _ = step(params, opt, batch_for(i))
    for a, b in zip(jax.tree_util.tree_leaves(ref),
                    jax.tree_util.tree_leaves(params)):
        assert float(jnp.max(jnp.abs(a - b))) == 0.0
