"""Continuous-serving driver: generation requests through the futures
client's resident engine + METG-batching frontend.

The serving session rides the same front door as everything else
(`repro.client.Client`): `client.serve(execute_batch)` attaches a
bounded-admission `Frontend` that coalesces requests into engine tasks
sized by the METG model for the live worker count (the paper's
granularity guidance automated) or by the max-wait deadline, and the
resident engine dispatches them with faults/leases/tracing intact — a
worker crash requeues its in-flight requests.  Per-request p50/p95/p99
latency comes straight from the trace.

    PYTHONPATH=src python -m repro.launch.serve --arch qwen2-vl-2b --reduced \
        --requests 12 --max-new 8

Without `--reduced` the model is built at its published widths.
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.client import Client
from repro.configs import get_config
from repro.core.engine.tracing import span
from repro.launch import compile_cache
from repro.models.common import Options
from repro.models.model import build_model
from repro.runtime.serve_step import greedy_generate


def parse_args(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="gemma2-2b")
    ap.add_argument("--reduced", action="store_true",
                    help="the tiny same-family config (CPU smoke runs); "
                         "without it, the published widths")
    ap.add_argument("--seed", type=int, default=0,
                    help="seeds the random weights and the prompts")
    ap.add_argument("--requests", type=int, default=12)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--max-new", type=int, default=8)
    ap.add_argument("--workers", type=int, default=1)
    ap.add_argument("--max-wait-ms", type=float, default=20.0,
                    help="frontend deadline before a partial batch ships")
    ap.add_argument("--stats-port", type=int, default=None,
                    help="serve /stats, /health, /metrics on this port "
                         "while requests run (0 = ephemeral; see "
                         "python -m repro.core.obs.top)")
    ap.add_argument("--trace-out", default=None,
                    help="write the session as a Perfetto-loadable "
                         "Chrome trace (.trace.json) at exit")
    return ap.parse_args(argv)


def build(args):
    """(cfg, model, params) at the size `args` asks for, with random
    weights from `--seed`, held as the model serves them
    (`Model.serving_weights`): the float32 tree is not kept."""
    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    model = build_model(cfg, Options(q_block=64, kv_block=64, moe_group=64))
    params = model.serving_weights(model.init(jax.random.PRNGKey(args.seed)))
    return cfg, model, params


def make_batch(cfg, toks):
    """The model inputs for a (B, S) block of prompt tokens: text-only
    M-RoPE positions for the vlm family, silent frames for audio."""
    with span("serve.make_batch"):
        b = {"tokens": toks}
        if cfg.mrope:
            B, S = toks.shape
            b["mrope_positions"] = jnp.broadcast_to(
                jnp.arange(S)[None, None], (3, B, S))
        if cfg.family == "audio":
            b["encoder_frames"] = jnp.zeros(
                (toks.shape[0], cfg.encoder.n_frames, cfg.d_model),
                jnp.bfloat16)
        return b


def main(argv=None, built=None):
    """Serve `--requests` generation requests and return the engine's
    final report.  `built` passes in the (cfg, model, params) of `build`,
    so a caller that already holds the weights does not build a second
    copy."""
    args = parse_args(argv)
    compile_cache.enable()
    cfg, model, params = built if built is not None else build(args)

    def execute_batch(prompts):
        b = make_batch(cfg, jnp.asarray(np.stack(prompts)))
        out = greedy_generate(model, params, b, args.max_new,
                              args.prompt_len + args.max_new + 1)
        assert out.shape == (len(prompts), args.max_new)
        assert not bool(jnp.any(out < 0))
        return [np.asarray(row) for row in out]

    rng = np.random.default_rng(args.seed)
    prompts = [rng.integers(2, cfg.vocab_size, size=args.prompt_len)
               .astype(np.int32) for _ in range(args.requests)]
    # compile the one-request shape before any request is leased: a cold
    # first batch must not outlive the lease and be requeued as a dead
    # worker's
    t0 = time.time()
    execute_batch(prompts[:1])
    print(f"[serve] warm-up batch (compile included) "
          f"{time.time() - t0:.1f}s")

    client = Client(scheduler="dwork", workers=args.workers,
                    lease_timeout=120.0)
    if args.stats_port is not None:
        srv = client.stats_server(port=args.stats_port)
        print(f"[serve] live stats at {srv.url}/stats "
              f"(/health, /metrics; dashboard: python -m "
              f"repro.core.obs.top --url {srv.url})")
    frontend = client.serve(execute_batch,
                            max_queue=max(args.requests, 16),
                            max_batch=max(args.requests, 1),
                            max_wait_s=args.max_wait_ms * 1e-3,
                            per_request_s0=0.05)
    print(f"[serve] METG batch target for {args.workers} worker(s): "
          f"{frontend.target_batch()}")

    t0 = time.time()
    reqs = [frontend.submit(p) for p in prompts]
    done = 0
    for r in reqs:
        assert r.wait(600.0), f"request {r.name} never completed"
        assert r.ok, f"request {r.name} failed: {r.error}"
        assert r.value.shape == (args.max_new,)
        done += 1
    report = client.close()
    if args.trace_out:
        report.trace.to_chrome_trace(args.trace_out)
        print(f"[serve] Chrome trace written to {args.trace_out} "
              f"(open in https://ui.perfetto.dev)")
    lat = report.trace.latency_report()
    print(f"[serve] all {done} requests served in {time.time() - t0:.1f}s; "
          f"batches={lat.n_batches} mean_batch={lat.mean_batch:.1f}")
    print(f"[serve] latency ms: p50={lat.p50_s * 1e3:.1f} "
          f"p95={lat.p95_s * 1e3:.1f} p99={lat.p99_s * 1e3:.1f}")
    print(f"[serve] server stats: {report.backend_stats}")
    return report


if __name__ == "__main__":
    main()
