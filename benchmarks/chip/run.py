"""The on-chip benchmark: one run of one cell of BENCHMARK.json.

    python3 benchmarks/chip/run.py --workload metg.atb-1024 --seed 7 \
        --seconds 10 --trace 0

A run sets up (makes its data from the seed, builds, warms every shape up
through the timed path), measures for `--seconds`, waits for the work
that was due in the window, reads the device's peak memory, frees the
program's state, and compares what the timed path produced with the
configuration's plain reference.  Its last line on stdout is one JSON
object; the numbers compared, each beside its limit, are the last lines
on stderr and the last key of that object.  `--trace 1` profiles the
window and reports the cell's per-layer metrics in place of its
end-to-end ones.

It runs on a TPU and nowhere else: with no TPU, or fewer chips than the
cell asks for, it exits non-zero and prints no result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()   # set-up counts from here: imports and all

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402
from types import SimpleNamespace  # noqa: E402

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]
CACHE_DIR = ROOT / ".jax_cache"
for _p in (str(ROOT / "src"), str(HERE)):
    if _p not in sys.path:
        sys.path.insert(0, _p)

import registry  # noqa: E402


def parse_args(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def peaks_for(kind: str) -> dict:
    table = registry.load_json(HERE / "peaks.json")["devices"]
    if kind not in table:
        raise KeyError(f"device kind {kind!r} is not in peaks.json; "
                       f"add its published peaks there")
    return table[kind]


def enable_compile_cache() -> str:
    """JAX's persistent compile cache at `<checkout>/.jax_cache`, a fixed
    path inside the checkout, keeping every program however fast it
    compiled, so that only a cell's first run in a checkout compiles.  The
    program's own `compile_cache.enable()` takes the directory from
    `JAX_COMPILATION_CACHE_DIR`, so it is given the same one."""
    import jax

    from repro.launch import compile_cache

    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(CACHE_DIR)
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return compile_cache.enable()


def memory_peak(devices) -> int:
    peaks = []
    for d in devices:
        stats = d.memory_stats() or {}
        peaks.append(int(stats.get("peak_bytes_in_use", 0)))
    return max(peaks)


def run_cell(bench: dict, cell: dict, cfg: dict, traffic: dict, *,
             seed: int, seconds: float, trace: bool, devices,
             t_start: float, peaks: dict, kind_module=None,
             reference=None) -> dict:
    """Set up, measure, check; returns the result line's object.
    `kind_module` and `reference` default to the files named by the
    traffic's kind and the cell's configuration."""
    import jax

    from compile_meter import CompileMeter

    meter = CompileMeter()
    kind_module = kind_module or registry.kind(traffic["kind"])
    reference = reference or registry.reference(cell["config"])
    work = kind_module.Workload(cfg, traffic, seed=seed, devices=devices,
                                  reference=reference)
    work.setup()
    setup_s = time.perf_counter() - t_start
    print(f"[setup] {setup_s:.3f} s; compile {meter.mark()}", flush=True)

    trace_dir = tempfile.mkdtemp(prefix="bench_trace_") if trace else None
    try:
        if trace:
            import devtrace

            jax.profiler.start_trace(
                trace_dir, profiler_options=devtrace.profile_options())
        start = meter.mark()
        with jax.profiler.TraceAnnotation("bench.window"):
            work.window(seconds)
        in_window = meter.since(start)
        if trace:
            jax.profiler.stop_trace()
        work.finish()
        mem = memory_peak(devices)
        reduced = None
        if trace:
            reduced = devtrace.reduce_trace(devtrace.find_xplane(trace_dir))
    finally:
        if trace_dir:
            shutil.rmtree(trace_dir, ignore_errors=True)
    for line in work.report_lines():
        print(line, flush=True)
    print(f"[window] compiles={in_window['compiles']} "
          f"real_compiles={in_window['real_compiles']} "
          f"cache_loads={in_window['cache_hits']} "
          f"compile_s={in_window['backend_s']:.6f} "
          f"trace_s={in_window['trace_s']:.6f} "
          f"lower_s={in_window['lower_s']:.6f}", flush=True)

    work.release()
    gc.collect()
    checks = work.check()
    correct = bool(checks) and all(v <= lim for v, lim in checks.values()) \
        and work.failed == 0

    ctx = SimpleNamespace(
        seconds=seconds, setup_s=setup_s, work=work, trace=reduced,
        compile=in_window, peaks=peaks, cfg=cfg, traffic=traffic,
        count=registry.count)
    metrics = {}
    for m in registry.cell_metrics(bench, cell["name"], trace):
        value = registry.metric(m["name"]).read(ctx)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    dev = devices[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices()), "memory_peak_bytes": mem}
    result = {"correct": correct, "attempted": work.attempted,
              "failed": work.failed, "metrics": metrics, "device": device}
    if reduced is not None:
        device["busy_s"] = reduced.busy_s
        device["window_s"] = reduced.window_s
        result["breakdown"] = reduced.breakdown()
    result["checks"] = {k: {"value": v, "limit": lim}
                        for k, (v, lim) in checks.items()}
    return result


def main(argv=None) -> int:
    args = parse_args(argv)
    bench = registry.benchmark()
    cell = registry.cell(bench, args.workload)
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < cell["chips"]:
        print(f"run.py: {cell['name']} needs {cell['chips']} TPU chip(s); "
              f"JAX found {len(devices)} {devices[0].platform} device(s)",
              file=sys.stderr)
        return 2
    peaks = peaks_for(devices[0].device_kind)
    print(f"[device] {devices[0].device_kind} x{len(devices)} "
          f"jax={jax.__version__} cache={enable_compile_cache()}",
          flush=True)
    result = run_cell(
        bench, cell, registry.config(bench, cell["config"]),
        registry.traffic(cell["traffic"]), seed=args.seed,
        seconds=args.seconds, trace=bool(args.trace),
        devices=devices[:cell["chips"]], t_start=T_START, peaks=peaks)
    for name, c in result["checks"].items():
        print(f"check {name} = {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
