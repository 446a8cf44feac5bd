"""Find the highest request rate an open-loop cell sustains: a sweep on
the chip, run once when the cell's rate is chosen.

    python3 benchmarks/chip/sweep.py --workload serve.qwen2-vl-2b.chat \
        --seconds 40 --seed 5

One process sets the cell up once, sends a few windows of one request
each to time a request alone, then offers load at fractions and
multiples of the rate that time allows, each for `--seconds`.  Every
window goes through the cell's own `window()` and `finish()`, so the
latencies and queue waits are the benchmark's.  For each rate it prints
one JSON line: requests completed per second, p50 and p95 latency, and
how the queue wait grew from the first half of the window to the second
(a backlog that keeps growing means the rate is past the knee).
"""
from __future__ import annotations

import argparse
import json
import sys

import run
from run import registry

FRACTIONS = (0.5, 0.7, 0.8, 0.9, 1.0, 1.1)
ALONE_WINDOWS, ALONE_S = 4, 0.5


def offer(d, rate: float, seconds: float, close: bool = False):
    """One window of the cell's traffic at `rate`; the workload keeps its
    latencies and queue waits."""
    d.rate = rate
    d.window(seconds)
    d.finish(close=close)
    return d


def sweep(d, seconds: float, fractions=FRACTIONS):
    """Yield one dict per rate, after timing a request alone."""
    alone = [offer(d, 1.0 / ALONE_S, ALONE_S).latency_ms(0.5) * 1e-3
             for _ in range(ALONE_WINDOWS)]
    service_s = sorted(alone)[len(alone) // 2]
    yield {"service_s_alone": service_s, "samples": alone}
    for i, frac in enumerate(fractions):
        offer(d, frac / service_s, seconds, close=i == len(fractions) - 1)
        waits, half = d.queue_waits, len(d.queue_waits) // 2
        yield {
            "fraction_of_alone_rate": frac, "offered_per_s": d.rate,
            "requests": d.attempted, "ok": d.attempted - d.failed,
            "completed_per_s":
                (d.attempted - d.failed) / (d.t_last_done - d.t0),
            "p50_ms": d.latency_ms(0.5), "p95_ms": d.latency_ms(0.95),
            "queue_wait_first_half_ms": sum(waits[:half]) / max(half, 1) * 1e3,
            "queue_wait_second_half_ms":
                sum(waits[half:]) / max(len(waits) - half, 1) * 1e3}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seed", type=int, required=True)
    args = ap.parse_args(argv)
    bench = registry.benchmark()
    cell = registry.cell(bench, args.workload)
    import jax

    if jax.devices()[0].platform != "tpu":
        print("sweep.py needs a TPU", file=sys.stderr)
        return 2
    run.enable_compile_cache()
    traffic = registry.traffic(cell["traffic"])
    d = registry.kind(traffic["kind"]).Workload(
        registry.config(bench, cell["config"]), traffic, seed=args.seed,
        devices=jax.devices()[:cell["chips"]],
        reference=registry.reference(cell["config"]))
    d.setup()
    for line in sweep(d, args.seconds):
        print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
