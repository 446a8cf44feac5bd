"""End-to-end training driver (example-scale on CPU, mesh-ready).

    PYTHONPATH=src python -m repro.launch.train --arch gemma2-2b --reduced \
        --steps 50 --global-batch 8 --seq 128 --ckpt-dir /tmp/run1

Features exercised: mpi-list data pipeline, AdamW + clipping + schedule,
remat/microbatching, async checkpointing with restart (--resume picks up
the latest step), metrics JSONL.
"""
from __future__ import annotations

import argparse
import json
import time
from pathlib import Path

import jax
import numpy as np

from repro.checkpoint import ckpt
from repro.configs import RunConfig, get_config
from repro.launch import compile_cache
from repro.data.pipeline import Pipeline
from repro.models.common import Options, param_count
from repro.models.model import build_model
from repro.optim.adamw import init_opt
from repro.runtime.train_step import make_train_step


def build(args):
    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    if args.layers:
        cfg = cfg.replace(n_layers=args.layers)
    if args.d_model:
        cfg = cfg.replace(d_model=args.d_model,
                          d_ff=args.d_ff or 4 * args.d_model,
                          head_dim=max(32, args.d_model // cfg.n_heads))
    opts = Options(q_block=min(512, args.seq), kv_block=min(512, args.seq),
                   moe_group=min(1024, args.global_batch * args.seq),
                   remat=args.remat)
    model = build_model(cfg, opts)
    rc = RunConfig(remat=args.remat, microbatches=args.microbatches,
                   lr=args.lr, warmup_steps=min(100, args.steps // 10 + 1),
                   total_steps=args.steps, seed=args.seed)
    return cfg, model, rc


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="gemma2-2b")
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--layers", type=int, default=0)
    ap.add_argument("--d-model", type=int, default=0)
    ap.add_argument("--d-ff", type=int, default=0)
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--global-batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--remat", default="none")
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--ckpt-dir", default="")
    ap.add_argument("--ckpt-every", type=int, default=20)
    ap.add_argument("--resume", action="store_true")
    ap.add_argument("--metrics-out", default="")
    args = ap.parse_args(argv)

    compile_cache.enable()
    cfg, model, rc = build(args)
    key = jax.random.PRNGKey(rc.seed)
    params = model.init(key)
    opt_state = init_opt(params, rc)
    print(f"[train] arch={cfg.name} params={param_count(params):,}")

    start_step = 0
    ckpter = None
    if args.ckpt_dir:
        ckpter = ckpt.AsyncCheckpointer(args.ckpt_dir)
        if args.resume:
            last = ckpt.latest_step(args.ckpt_dir)
            if last is not None:
                abs_tree = jax.tree_util.tree_map(
                    lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype),
                    {"params": params, "opt": opt_state})
                tree = ckpt.restore(args.ckpt_dir, last, abs_tree)
                params, opt_state = tree["params"], tree["opt"]
                start_step = last
                print(f"[train] resumed from step {last}")

    pipe = Pipeline(cfg.vocab_size, args.seq, args.global_batch, seed=rc.seed)
    step_fn = jax.jit(make_train_step(model, rc), donate_argnums=(0, 1))

    metrics_path = Path(args.metrics_out) if args.metrics_out else None
    if metrics_path:
        metrics_path.parent.mkdir(parents=True, exist_ok=True)
    logf = open(metrics_path, "a") if metrics_path else None

    t0 = time.time()
    losses = []
    for i, batch in enumerate(pipe.batches(args.steps - start_step)):
        step = start_step + i + 1
        jb = {k: jax.numpy.asarray(v) for k, v in batch.items()}
        if cfg.mrope:
            B, S = jb["tokens"].shape
            jb["mrope_positions"] = jax.numpy.broadcast_to(
                jax.numpy.arange(S)[None, None], (3, B, S))
        if cfg.family == "audio":
            B = jb["tokens"].shape[0]
            jb["encoder_frames"] = jax.numpy.zeros(
                (B, cfg.encoder.n_frames, cfg.d_model), jax.numpy.bfloat16)
        params, opt_state, m = step_fn(params, opt_state, jb)
        loss = float(m["loss"])
        losses.append(loss)
        rec = {"step": step, "loss": loss,
               "grad_norm": float(m["grad_norm"]), "lr": float(m["lr"]),
               "wall_s": round(time.time() - t0, 2)}
        if logf:
            logf.write(json.dumps(rec) + "\n")
            logf.flush()
        if step % max(1, args.steps // 10) == 0 or step == args.steps:
            print(f"[train] step {step} loss {loss:.4f} "
                  f"gnorm {rec['grad_norm']:.3f}")
        if ckpter and (step % args.ckpt_every == 0 or step == args.steps):
            ckpter.save(step, {"params": params, "opt": opt_state},
                        {"loss": loss})
    if ckpter:
        ckpter.wait()
    assert np.isfinite(losses).all(), "NaN/inf loss"
    if len(losses) > 10:
        assert np.mean(losses[-5:]) < np.mean(losses[:5]), \
            "loss did not decrease"
    print(f"[train] done: first {losses[0]:.4f} -> last {losses[-1]:.4f}")
    return losses


if __name__ == "__main__":
    main()
