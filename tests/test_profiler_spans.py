"""The program's profiler spans, read back from a JAX profiler trace on the
CPU: every span name of the engine, client, Frontend and serving layers,
their metadata and nesting, the names the mesh verbs give their programs,
and that the engine, client and serving modules still import without
JAX (docs/observability.md, "Profiler spans")."""
import glob
import json
import os
import subprocess
import sys
import textwrap
import time
from collections import defaultdict
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.client import Client
from repro.configs import get_config
from repro.launch import serve
from repro.models.common import Options
from repro.models.model import build_model
from repro.runtime.serve_step import greedy_generate

SRC = str(Path(__file__).resolve().parents[1] / "src")

ENGINE_SPANS = ["engine.round", "engine.ingest", "engine.steal",
                "engine.run", "engine.notify", "engine.idle"]
CLIENT_SPANS = ["client.submit", "client.resolve"]
FRONTEND_SPANS = ["frontend.idle", "frontend.wait", "frontend.dispatch",
                  "frontend.resolve"]
SERVE_SPANS = ["serve.make_batch", "serve.cast_weights", "serve.prefill",
               "serve.decode"]
MESH_VERBS = ["map", "reduce", "sum", "scan", "group"]


def _profile_options():
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    return opts


def _host_spans(log_dir) -> list:
    """(name, start, end, stats, thread) of every host event."""
    from jax.profiler import ProfileData

    path, = glob.glob(os.path.join(str(log_dir), "**", "*.xplane.pb"),
                      recursive=True)
    out = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for i, line in enumerate(plane.lines):
            for ev in line.events:
                out.append((ev.name, ev.start_ns,
                            ev.start_ns + ev.duration_ns,
                            {k: v for k, v in ev.stats},
                            (plane.name, i)))
    return out


def task_body(x):
    with jax.profiler.TraceAnnotation("test.task_body"):
        return x + 1


@pytest.fixture(scope="module")
def spans(tmp_path_factory):
    """One profiled stretch: a three-task DAG, then two requests through
    `client.serve` with a trivial batch body, then one served generation
    step of a reduced model."""
    cfg = get_config("qwen2-vl-2b").reduced()
    model = build_model(cfg, Options(q_block=32, kv_block=32))
    params = model.init(jax.random.PRNGKey(0))
    prompts = np.random.default_rng(0).integers(
        2, cfg.vocab_size, (2, 8)).astype(np.int32)

    def generate(batch):
        b = serve.make_batch(cfg, jnp.asarray(np.stack(batch)))
        return list(np.asarray(greedy_generate(model, params, b, 2, 11)))

    def slow_double(batch):
        time.sleep(0.02)            # the coalescer waits with it in flight
        return [2 * x for x in batch]

    log_dir = tmp_path_factory.mktemp("profile")
    jax.profiler.start_trace(str(log_dir),
                             profiler_options=_profile_options())
    try:
        with Client(scheduler="dwork", workers=1) as c:
            a = c.submit(task_body, 1, key="body-a")
            b = c.submit(task_body, a, key="body-b")
            assert c.gather([c.submit(task_body, b, key="body-c")]) == [4]
            time.sleep(0.02)        # the resident loop polls with no work
        for execute, payloads in ((slow_double, [1, 2]),
                                  (generate, list(prompts))):
            with Client(scheduler="dwork", workers=1) as c:
                fe = c.serve(execute, max_batch=1, max_wait_s=0.005)
                time.sleep(0.02)    # an empty queue, nothing in flight
                reqs = [fe.submit(p) for p in payloads]
                for r in reqs:
                    assert r.wait(120.0) and r.ok, r.error
    finally:
        jax.profiler.stop_trace()
    return _host_spans(log_dir)


def _named(spans, name):
    return [s for s in spans if s[0] == name]


def _inside(inner, outer):
    return (inner[4] == outer[4] and outer[1] <= inner[1]
            and inner[2] <= outer[2])


@pytest.mark.parametrize(
    "name", ENGINE_SPANS + CLIENT_SPANS + FRONTEND_SPANS + SERVE_SPANS)
def test_every_layer_span_is_in_the_trace(spans, name):
    assert _named(spans, name), f"no {name!r} span in the trace"


def test_span_names_carry_no_identifiers(spans):
    """Identifiers ride as metadata (event stats), so gaps group by name."""
    names = {s[0] for s in spans}
    ours = {n for n in names if n.split(".")[0] in
            ("engine", "client", "frontend", "serve", "mesh")}
    assert ours == set(ENGINE_SPANS + CLIENT_SPANS + FRONTEND_SPANS
                       + SERVE_SPANS)


def test_engine_run_names_its_task_and_holds_the_task_body(spans):
    runs = {s[3].get("task"): s for s in _named(spans, "engine.run")}
    assert {"body-a", "body-b", "body-c"} <= set(runs)
    bodies = _named(spans, "test.task_body")
    assert len(bodies) == 3
    for key in ("body-a", "body-b", "body-c"):
        assert sum(_inside(b, runs[key]) for b in bodies) == 1
    for run in runs.values():                # each run inside one round
        assert any(_inside(run, r) for r in _named(spans, "engine.round"))


def test_frontend_dispatch_shares_the_batch_name_with_its_run(spans):
    runs = {s[3].get("task"): s for s in _named(spans, "engine.run")}
    dispatches = _named(spans, "frontend.dispatch")
    assert len(dispatches) == 4              # four requests, batches of 1
    for d in dispatches:
        batch = d[3]["batch"]
        assert batch.startswith("__batch") and d[3]["size"] == 1
        assert batch in runs
        assert runs[batch][1] >= d[1]        # dispatched, then run


def test_serving_steps_and_delivery_nest_in_the_batch_run(spans):
    runs = [s for s in _named(spans, "engine.run")
            if str(s[3].get("task")).startswith("__batch")]
    for name in SERVE_SPANS + ["frontend.resolve"]:
        for s in _named(spans, name):
            assert any(_inside(s, r) for r in runs), name
    prefill = _named(spans, "serve.prefill")[0]
    decode = _named(spans, "serve.decode")[0]
    assert prefill[2] <= decode[1]


def test_idle_spans_are_the_only_ones_named_idle(spans):
    idle = {s[0] for s in spans if s[0].endswith(".idle")}
    assert idle == {"engine.idle", "frontend.idle"}


def test_program_imports_without_jax():
    code = textwrap.dedent("""
        import sys
        import repro.core.engine, repro.client, repro.core.serving
        from repro.core.engine.tracing import span
        with span("engine.run", task="t"), span("engine.idle"):
            pass
        assert span("x") is span("y")
        print("jax" in sys.modules)
    """)
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, timeout=120,
                         env={**os.environ, "PYTHONPATH": SRC})
    assert out.returncode == 0, out.stderr
    assert out.stdout.strip() == "False"


@pytest.fixture(scope="module")
def mesh_run(tmp_path_factory):
    """Each mesh verb twice on four virtual CPU devices, in a process of
    its own (the device count is fixed when JAX starts), with the lowered
    modules dumped, the backend compiles of each round counted, and a
    profiler trace around both rounds."""
    tmp = tmp_path_factory.mktemp("mesh")
    code = textwrap.dedent(f"""
        import json, os
        import jax, jax.numpy as jnp
        jax.config.update("jax_dump_ir_to", {str(tmp / "ir")!r})
        from repro.core.mpi_list import mesh_ops as ops
        n = [0]
        jax.monitoring.register_event_duration_secs_listener(
            lambda event, secs, **_: n.__setitem__(0, n[0] + (
                event == "/jax/core/compile/backend_compile_duration")))
        mesh = jax.make_mesh((4,), ("data",))
        x = ops.scatter(mesh, jnp.arange(32, dtype=jnp.int32))
        dest = jax.block_until_ready(x % 4)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace({str(tmp / "trace")!r},
                                 profiler_options=opts)
        compiles = []
        for _ in range(2):
            before = n[0]
            sq = ops.dfm_map(mesh, lambda v: v * v, x)
            ops.dfm_reduce(mesh, lambda a, b: a + b, sq)
            ops.dfm_sum(mesh, sq)
            ops.dfm_scan(mesh, lambda a, b: a + b, x)
            ops.group(mesh, dest, ops.repartition(mesh, x))
            jax.block_until_ready(sq)
            compiles.append(n[0] - before)
        jax.profiler.stop_trace()
        print(json.dumps({{"dumped": sorted(os.listdir({str(tmp / "ir")!r})),
                          "compiles": compiles}}))
    """)
    env = {**os.environ, "PYTHONPATH": SRC, "JAX_PLATFORMS": "cpu",
           "XLA_FLAGS": "--xla_force_host_platform_device_count=4"}
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, timeout=300, env=env)
    assert out.returncode == 0, out.stderr[-3000:]
    got = json.loads(out.stdout.strip().splitlines()[-1])
    names = defaultdict(int)
    for s in _host_spans(tmp / "trace"):
        names[s[0]] += 1
    return got["dumped"], names, got["compiles"]


@pytest.mark.parametrize("verb", MESH_VERBS)
def test_mesh_verb_names_its_program_and_span(mesh_run, verb):
    dumped, names, _ = mesh_run
    assert any(f"_jit_mesh_{verb}_" in f for f in dumped), dumped
    assert names[f"mesh.{verb}"] == 2
    assert names[f"PjitFunction(mesh_{verb})"] >= 2


def test_mesh_second_round_adds_no_compile(mesh_run):
    dumped, _, compiles = mesh_run
    assert compiles[0] >= len(MESH_VERBS) and compiles[1] == 0, compiles
    for verb in MESH_VERBS:                  # one program a verb, kept
        assert sum(f.endswith(f"_jit_mesh_{verb}_compile.mlir")
                   for f in dumped) == 1, dumped


def test_mesh_verbs_jit_no_anonymous_function(mesh_run):
    dumped, names, _ = mesh_run
    assert names["mesh.repartition"] == 2
    assert not any("lambda" in f for f in dumped), dumped
    assert not any("lambda" in n for n in names if n.startswith("Pjit"))
