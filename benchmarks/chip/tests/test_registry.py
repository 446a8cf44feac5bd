"""The harness finds every piece by the name BENCHMARK.json gives it."""
import json

import registry

BENCH = registry.benchmark()


def _write(path, text):
    path.parent.mkdir(parents=True, exist_ok=True)
    path.write_text(text)


def test_new_pieces_are_found_by_name(tmp_path):
    """A configuration, a traffic mix, a metric reader, a kernel count and
    a traffic kind placed in their directories are found by name, with
    nothing else edited."""
    _write(tmp_path / "configs" / "toy-model.json", json.dumps({"d": 8}))
    _write(tmp_path / "configs" / "toy-model.py", "def check():\n    return 3\n")
    _write(tmp_path / "configs" / "toy-model.program.py",
           "def program_config(cfg, registered):\n    return registered + 1\n")
    _write(tmp_path / "traffic" / "toy-mix.json",
           json.dumps({"kind": "toy", "rate_per_s": 2.0}))
    _write(tmp_path / "kinds" / "toy.py", "class Workload:\n    NAME = 'toy'\n")
    _write(tmp_path / "metrics" / "toy_ms.serve.py",
           "def read(ctx):\n    return ctx * 2.0\n")
    _write(tmp_path / "counts" / "toy_kernel.py",
           "def flops(n):\n    return 2.0 * n ** 3\n")
    bench = {"configs": [{"name": "toy-model",
                          "file": "configs/toy-model.json"}],
             "workloads": [{"name": "toy.cell", "config": "toy-model",
                            "traffic": "toy-mix", "chips": 1}],
             "end_to_end": [{"name": "setup_s"}],
             "per_layer": [{"name": "toy_ms.serve", "workloads": ["toy.cell"]},
                           {"name": "other", "workloads": ["x"]}]}
    cell = registry.cell(bench, "toy.cell")
    assert registry.config(bench, cell["config"], root=tmp_path) == {"d": 8}
    traffic = registry.traffic(cell["traffic"], base=tmp_path)
    assert traffic["rate_per_s"] == 2.0
    assert registry.kind(traffic["kind"], base=tmp_path).Workload.NAME == "toy"
    assert registry.reference("toy-model", base=tmp_path).check() == 3
    assert registry.program_binding("toy-model", base=tmp_path) \
        .program_config({}, 1) == 2
    assert registry.metric("toy_ms.serve", base=tmp_path).read(4) == 8.0
    assert registry.count("toy_kernel", base=tmp_path).flops(2) == 16.0
    assert [m["name"] for m in registry.cell_metrics(bench, "toy.cell", True)] \
        == ["toy_ms.serve"]
    assert [m["name"] for m in registry.cell_metrics(bench, "toy.cell", False)] \
        == ["setup_s"]


def test_every_name_in_benchmark_json_resolves():
    """Each cell's configuration, reference, traffic and kind load, each
    metric has its reader, and each cell reports setup_s, another
    end-to-end metric and a per-layer metric that moves one it reports."""
    for c in BENCH["configs"]:
        assert (registry.ROOT / c["file"]).is_file(), c["file"]
        ref = registry.reference(c["name"]).__dict__
        assert any(callable(ref.get(f))
                   for f in ("wave", "step", "served_gap")), c["name"]
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert callable(registry.metric(m["name"]).read), m["name"]
    for w in BENCH["workloads"]:
        traffic = registry.traffic(w["traffic"])
        assert hasattr(registry.kind(traffic["kind"]), "Workload")
        if traffic["kind"] == "openloop":       # a served model's own files
            cfg = registry.config(BENCH, w["config"])
            binding = registry.program_binding(w["config"])
            assert callable(binding.program_config), w["config"]
            assert callable(registry.count(cfg["count"]).request_flops)
        e2e = [m["name"] for m in registry.cell_metrics(BENCH, w["name"], False)]
        layer = registry.cell_metrics(BENCH, w["name"], True)
        assert "setup_s" in e2e and len(e2e) >= 2, w["name"]
        assert layer and all(m["moves"] in e2e for m in layer), w["name"]


def test_unknown_device_kind_is_an_error():
    import run

    assert run.peaks_for("TPU v5 lite")["bf16_flops_per_s"] == 197e12
    try:
        run.peaks_for("TPU v9 imaginary")
    except KeyError:
        return
    raise AssertionError("an unknown device kind must raise")


def test_mfu_serve_counts_the_configured_model():
    """mfu.serve takes its operations from the count the configuration
    names, whatever model is served."""
    from types import SimpleNamespace

    seen = []

    def count(name):
        seen.append(name)
        return SimpleNamespace(request_flops=lambda cfg, S, new: 1e12)

    ctx = SimpleNamespace(
        work=SimpleNamespace(batch_run_s=2.0, served=[0, 1], prompt_len=4,
                             max_new=2),
        cfg={"count": "toy-decoder"}, count=count,
        peaks={"bf16_flops_per_s": 1e14})
    assert registry.metric("mfu.serve").read(ctx) == 1.0
    assert seen == ["toy-decoder"]
