"""Bring-up check: the runtime's device path on a TPU, end to end.

    python chip_smoke.py                # one chip: the dag and serve phases
    python chip_smoke.py --four-chips   # four chips: the mesh phase, alone

Phases, each checked against a plain reference:

  dag    A `Client(scheduler="dwork")` session on the in-process transport
         runs a fan-out/fan-in DAG of the paper's METG task, single-
         precision tiled A^T B at 4096 x 4096 through the compiled Pallas
         kernel, with futures passed as dependencies; then one
         `Client(scheduler="mpi_list").map` over the same blocks.  Checked
         against NumPy in float64.
  serve  `repro.launch.serve.main` serves 8 requests (prompt 128, 16 new
         tokens) with qwen2-vl-2b at its published widths and random
         weights.  Decoding one token through the cache is checked against
         the full forward pass.
  mesh   (--four-chips) the mpi-list mesh bridge (map, reduce, scan,
         group) over a `data` axis of 4 devices, on a distributed list of
         about 1 GiB per chip.  Checked exactly against NumPy.

It exits non-zero, and prints no result, unless JAX finds a TPU; any phase
that fails raises.  The last line of stdout is the result, as JSON.
"""
from __future__ import annotations

import argparse
import gc
import json
import sys
import time
from importlib import metadata
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))


class CompileMeter:
    """Seconds in XLA's backend compile (a persistent-cache hit counts
    only its read) and the number of cache hits, from JAX's monitoring
    events."""

    def __init__(self):
        self.seconds = 0.0
        self.compiles = 0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, duration_secs, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += duration_secs
            self.compiles += 1

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    def mark(self):
        return self.seconds, self.compiles, self.cache_hits, time.time()

    def line(self, phase, start):
        s, n, h, t = start
        return (f"[{phase}] wall_s={time.time() - t:.2f} "
                f"compile_s={self.seconds - s:.2f} "
                f"compiles={self.compiles - n} "
                f"cache_hits={self.cache_hits - h}")


def check(ok, what):
    """A failed check raises (an `assert` would vanish under -O)."""
    if not ok:
        raise RuntimeError(f"check failed: {what}")


def rel_err(got, ref) -> float:
    """max |got - ref| over max |ref|, in float64."""
    got = np.asarray(got, np.float64)
    ref = np.asarray(ref, np.float64)
    return float(np.max(np.abs(got - ref)) / np.max(np.abs(ref)))


# ------------------------------------------------------------------ dag
# Full f32 products give a relative error near sqrt(K) * 2^-24 (~1e-6 at
# K = 4096) against float64.  One bf16 pass, which the task must not fall
# back to, gives ~2^-9 (~2e-3).  1e-4 passes the first with margin and
# fails the second.
DAG_TOL = 1e-4


def dag_phase(seed: int, *, n: int = 4096, blocks: int = 8,
              fan_out: int = 256, group: int = 16):
    from repro.client import Client
    from repro.kernels.tiled_matmul.ops import tiled_matmul

    ka, kb, kx = jax.random.split(jax.random.PRNGKey(seed), 3)
    A = [jax.random.normal(k, (n, n), jnp.float32)
         for k in jax.random.split(ka, blocks)]
    B = [jax.random.normal(k, (n, n), jnp.float32)
         for k in jax.random.split(kb, blocks)]
    X = jax.random.normal(kx, (fan_out, n), jnp.float32)

    def overlap(a, b, x):
        """The METG task, C = A^T B, reduced to C x for the reference.  It
        ends when the device has finished, so a task's time is its device
        time and at most one 64 MiB C is in flight."""
        return jax.block_until_ready(
            jnp.dot(tiled_matmul(a, b), x,
                    precision=jax.lax.Precision.HIGHEST))

    def total(*ys):
        return jnp.sum(jnp.stack(ys), axis=0)

    pair = [(t % blocks, (t // blocks) % blocks) for t in range(fan_out)]
    with Client(scheduler="dwork", workers=1) as c:
        fan = [c.submit(overlap, A[i], B[j], X[t])
               for t, (i, j) in enumerate(pair)]
        fan_in = [c.submit(total, *fan[g:g + group])
                  for g in range(0, fan_out, group)]
        root = c.submit(total, *fan_in)
        ys = jax.block_until_ready(c.gather(fan))
        zs = jax.block_until_ready(c.gather(fan_in))
        top = jax.block_until_ready(root.result())
        dag_stats = c.close().backend_stats
    n_tasks = len(fan) + len(fan_in) + 1

    with Client(scheduler="mpi_list", workers=blocks) as c:
        ms = jax.block_until_ready(
            c.gather(c.map(overlap, A, B, [X[0]] * blocks)))
        map_stats = c.close().backend_stats

    # NumPy float64 reference: (A_i^T B_j) x = A_i^T (B_j x)
    A64 = [np.asarray(a, np.float64) for a in A]
    B64 = [np.asarray(b, np.float64) for b in B]
    X64 = np.asarray(X, np.float64)
    ref = np.zeros((fan_out, n))
    for i in range(blocks):
        for j in range(blocks):
            ts = [t for t, p in enumerate(pair) if p == (i, j)]
            if ts:
                ref[ts] = (A64[i].T @ (B64[j] @ X64[ts].T)).T
    ref_in = [ref[g:g + group].sum(0) for g in range(0, fan_out, group)]
    ref_map = [A64[i].T @ (B64[i] @ X64[0]) for i in range(blocks)]
    errs = {"fan_out": max(rel_err(y, r) for y, r in zip(ys, ref)),
            "fan_in": max(rel_err(z, r) for z, r in zip(zs, ref_in)),
            "root": rel_err(top, ref.sum(0)),
            "mpi_list_map": max(rel_err(m, r) for m, r in zip(ms, ref_map))}
    print(f"[dag] tasks={n_tasks} map_tasks={blocks} "
          f"rel_err={json.dumps(errs)} tol={DAG_TOL}")
    print(f"[dag] dwork stats {dag_stats}")
    print(f"[dag] mpi_list stats {map_stats}")
    for stats in (dag_stats, map_stats):
        check(stats["errors"] == 0 and stats["requeued"] == 0, stats)
    check(max(errs.values()) < DAG_TOL, errs)


# ---------------------------------------------------------------- serve
SERVE_ARGV = ["--arch", "qwen2-vl-2b", "--requests", "8",
              "--prompt-len", "128", "--max-new", "16"]
# Activations are bf16 (unit roundoff 2^-9 ~ 2e-3), and the decode step
# attends with bf16 probabilities where the blockwise forward keeps them
# in f32; the difference grows through the 28 layers.  On the CPU, 28
# layers of this family at widths 128 and 384 gave 0.018 to 0.053 of the
# largest logit, and the same step from an empty cache gave 1.2 to 1.4.
# 0.1 passes the first with margin and fails a cache that holds the
# wrong keys.
SERVE_TOL = 0.1


def serve_phase(seed: int, argv=SERVE_ARGV):
    from repro.launch import serve
    from repro.models.common import param_count
    from repro.runtime.serve_step import (make_decode_step,
                                          make_prefill_step,
                                          prefill_into_cache)

    argv = [*argv, "--seed", str(seed)]
    args = serve.parse_args(argv)
    t0 = time.time()
    cfg, model, params = built = serve.build(args)
    jax.block_until_ready(params)
    print(f"[serve] {cfg.name}: layers={cfg.n_layers} d_model={cfg.d_model} "
          f"heads={cfg.n_heads}/{cfg.n_kv_heads} vocab={cfg.vocab_size} "
          f"params={param_count(params):,} built in {time.time() - t0:.1f}s")

    # decode through the cache == the full forward pass, one position on
    S = args.prompt_len
    rng = np.random.default_rng(seed + 1)
    toks = jnp.asarray(rng.integers(2, cfg.vocab_size, (1, S)), jnp.int32)
    prefill = jax.jit(make_prefill_step(model))
    decode = jax.jit(make_decode_step(model))
    step_logits = jax.jit(model.decode_step)
    tok, cache = prefill_into_cache(model, params,
                                    serve.make_batch(cfg, toks), S + 1,
                                    prefill, decode)
    pos = jnp.full((1,), S, jnp.int32)
    got, _ = step_logits(params, tok, pos, cache)
    full = serve.make_batch(cfg, jnp.concatenate([toks, tok[:, None]], 1))
    want = jax.jit(lambda p, b: model.forward(p, b)[0][:, -1])(params, full)
    V = cfg.vocab_size
    err = rel_err(got[:, :V], want[:, :V])
    # the same step from an empty cache: what a cache fault would look like
    blank, _ = step_logits(params, tok, pos, model.init_cache(1, S + 1))
    contrast = rel_err(blank[:, :V], want[:, :V])
    print(f"[serve] decode-vs-forward rel_err={err:.3e} tol={SERVE_TOL} "
          f"(empty cache: {contrast:.3e}); argmax agree="
          f"{bool(jnp.argmax(got[:, :V]) == jnp.argmax(want[:, :V]))}")
    check(err < SERVE_TOL, err)

    report = serve.main(argv, built=built)
    stats = report.backend_stats
    check(stats["errors"] == 0 and stats["requeued"] == 0, stats)
    check(stats["completed"] == stats["tasks"], stats)


# ----------------------------------------------------------------- mesh
def mesh_phase(seed: int, *, rows_per_chip: int = 1 << 21, width: int = 128):
    """int32 rows of `width`: 2^21 x 128 x 4 B = 1 GiB per chip.  Values
    below 8 keep every sum and prefix sum exact in int32."""
    from repro.core.mpi_list import mesh_ops

    devices = jax.devices()
    check(len(devices) == 4, f"--four-chips needs 4 devices, got {devices}")
    mesh = jax.make_mesh((4,), ("data",))
    R = 4 * rows_per_chip
    rows = mesh_ops.data_sharding(mesh, 2)

    def on_four(x, what):
        """Each of the 4 devices holds one contiguous block of rows."""
        shards = x.addressable_shards
        check(len({s.device for s in shards}) == 4, (what, x.sharding))
        check(all(s.data.shape[0] == x.shape[0] // 4 for s in shards), what)

    dfm = jax.jit(lambda k: jax.random.randint(k, (R, width), 0, 8, jnp.int32),
                  out_shardings=rows)(jax.random.PRNGKey(seed))
    on_four(dfm, "input")
    host = np.asarray(dfm).astype(np.int64)

    sq = mesh_ops.dfm_map(mesh, lambda r: r * r + 1, dfm)
    on_four(sq, "map")
    host_sq = host * host + 1
    check(np.array_equal(np.asarray(sq), host_sq), "map")
    col = host_sq.sum(0)
    check(np.array_equal(np.asarray(mesh_ops.dfm_sum(mesh, sq)), col), "sum")
    red = mesh_ops.dfm_reduce(mesh, lambda a, b: a + b, sq)
    check(np.array_equal(np.asarray(red), col), "reduce")
    scan = mesh_ops.dfm_scan(mesh, lambda a, b: a + b, sq)
    on_four(scan, "scan")
    check(np.array_equal(np.asarray(scan), np.cumsum(host_sq, 0)), "scan")
    del scan
    dest = mesh_ops.dfm_map(mesh, lambda r: jnp.sum(r) % 4, dfm)
    grouped = mesh_ops.group(mesh, dest, dfm)
    on_four(grouped, "group")
    order = np.argsort(np.asarray(dest), kind="stable")
    check(np.array_equal(np.asarray(grouped), host[order]), "group")
    print(f"[mesh] rows={R} width={width} "
          f"bytes_per_chip={rows_per_chip * width * 4} "
          f"map/sum/reduce/scan/group exact on 4 devices")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run the four-chip mesh phase and nothing else")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU (JAX found {dev.platform}); nothing run",
              file=sys.stderr)
        return 1
    try:
        libtpu = metadata.version("libtpu")
    except metadata.PackageNotFoundError:
        libtpu = "not installed as a package"
    print(f"[device] kind={dev.device_kind} count={len(jax.devices())} "
          f"jax={jax.__version__} libtpu={libtpu}")

    from repro.launch import compile_cache
    print(f"[device] compile cache: {compile_cache.enable()}")
    meter = CompileMeter()

    phases = ([("mesh", mesh_phase)] if args.four_chips
              else [("dag", dag_phase), ("serve", serve_phase)])
    for name, phase in phases:
        start = meter.mark()
        phase(args.seed)
        print(meter.line(name, start))
        gc.collect()

    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
