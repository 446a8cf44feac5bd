"""The control of a cell: its plain reference put in the program's place at
the next precision below the configuration's, run through the rest of the
harness.  Its readings set the upper end of each limit; a control that
the limits do not fail means the comparison cannot see that change.

    python3 benchmarks/chip/control.py --workload metg.atb-1024 \
        --seconds 3 --seeds 101 102 103

It needs the cell's chips, like run.py, and prints one JSON line per
seed with the numbers compared and their limits.  The benchmark's own
runs never run it.
"""
from __future__ import annotations

import argparse
import gc
import json
import sys

import run
from run import registry


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)
    bench = registry.benchmark()
    cell = registry.cell(bench, args.workload)
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < cell["chips"]:
        print("control.py: no TPU, or too few chips", file=sys.stderr)
        return 2
    peaks = run.peaks_for(devices[0].device_kind)
    run.enable_compile_cache()
    traffic = registry.traffic(cell["traffic"])
    base = registry.kind(traffic["kind"])

    class Control(base.Workload):
        def __init__(self, *a, **kw):
            super().__init__(*a, **kw)
            self.use_control()

    kind = type("control_kind", (), {"Workload": Control})
    for seed in args.seeds:
        t0 = run.time.perf_counter()
        res = run.run_cell(
            bench, cell, registry.config(bench, cell["config"]), traffic,
            seed=seed, seconds=args.seconds, trace=False,
            devices=devices[:cell["chips"]], t_start=t0, peaks=peaks,
            kind_module=kind)
        print(json.dumps({"control": True, "workload": cell["name"],
                          "seed": seed, "correct": res["correct"],
                          "checks": res["checks"]}), flush=True)
        gc.collect()
    return 0


if __name__ == "__main__":
    sys.exit(main())
