"""Find the benchmark's pieces by the names BENCHMARK.json gives them.

Each configuration, traffic mix, metric reader, kernel count and traffic
kind is a file of its own, so a later change adds a file and an entry
and edits nothing that is there:

    configs/<config>.json    the configuration's sizes, as run
    configs/<config>.py      its plain reference (`check(...)`, `control(...)`)
    configs/<config>.program.py  for a served model: its published keys
                             mapped onto the program's config
    traffic/<traffic>.json   a traffic mix: `kind` plus the kind's parameters
    kinds/<kind>.py          the one general generator of that kind (`Workload`)
    metrics/<metric>.py      a reader: `read(ctx) -> float | None`
    counts/<kernel>.py       operations and bytes of one call, from shapes
"""
from __future__ import annotations

import importlib.util
import json
from pathlib import Path

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]


def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def benchmark(root: Path = ROOT) -> dict:
    return load_json(root / "BENCHMARK.json")


def cell(bench: dict, workload: str) -> dict:
    for w in bench["workloads"]:
        if w["name"] == workload:
            return w
    raise KeyError(f"no workload {workload!r} in BENCHMARK.json; "
                   f"have {[w['name'] for w in bench['workloads']]}")


def config_entry(bench: dict, name: str) -> dict:
    for c in bench["configs"]:
        if c["name"] == name:
            return c
    raise KeyError(f"no config {name!r} in BENCHMARK.json")


def config(bench: dict, name: str, root: Path = ROOT) -> dict:
    return load_json(root / config_entry(bench, name)["file"])


def traffic(name: str, base: Path = HERE) -> dict:
    return load_json(base / "traffic" / f"{name}.json")


def _module(path: Path, label: str):
    if not path.is_file():
        raise FileNotFoundError(f"{label}: no file {path}")
    spec = importlib.util.spec_from_file_location(
        f"benchmarks_chip_{label}_{path.stem}".replace("-", "_")
        .replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def reference(config_name: str, base: Path = HERE):
    return _module(base / "configs" / f"{config_name}.py", "reference")


def program_binding(config_name: str, base: Path = HERE):
    return _module(base / "configs" / f"{config_name}.program.py", "binding")


def kind(name: str, base: Path = HERE):
    return _module(base / "kinds" / f"{name}.py", "kind")


def metric(name: str, base: Path = HERE):
    return _module(base / "metrics" / f"{name}.py", "metric")


def count(name: str, base: Path = HERE):
    return _module(base / "counts" / f"{name}.py", "count")


def cell_metrics(bench: dict, workload: str, trace: bool) -> list[dict]:
    """The metrics this cell reports: with a trace its per-layer metrics,
    without one its end-to-end metrics.  A metric with a `workloads` key
    belongs to the cells it lists; one without belongs to every cell."""
    group = bench["per_layer"] if trace else bench["end_to_end"]
    return [m for m in group
            if workload in m.get("workloads", [workload])]
