"""Record the small device trace that test_devtrace.py reads, on a TPU.

    python3 benchmarks/chip/tests/record_trace.py <out_dir>

Inside a `bench.window` span: three calls of the tiled A^T B kernel at
512^3, each waited for, with a host-side sleep of 20 ms between them, so
the device has three busy stretches and gaps the host spent in
`bench.sleep`.  Prints the planes, lines and the first events of each,
and copies the `.xplane.pb` to `<out_dir>/small.xplane.pb`.
"""
from __future__ import annotations

import shutil
import sys
import tempfile
import time
from pathlib import Path

HERE = Path(__file__).resolve().parent
sys.path[:0] = [str(HERE.parent), str(HERE.parents[2] / "src")]


def main(out_dir: str) -> int:
    import jax
    import jax.numpy as jnp

    import devtrace
    from repro.kernels.tiled_matmul.ops import tiled_matmul

    if jax.devices()[0].platform != "tpu":
        print("record_trace.py needs a TPU", file=sys.stderr)
        return 2
    a = jax.random.normal(jax.random.PRNGKey(0), (512, 512), jnp.float32)
    jax.block_until_ready(tiled_matmul(a, a))
    tmp = tempfile.mkdtemp()
    jax.profiler.start_trace(tmp, profiler_options=devtrace.profile_options())
    with jax.profiler.TraceAnnotation("bench.window"):
        for _ in range(3):
            jax.block_until_ready(tiled_matmul(a, a))
            with jax.profiler.TraceAnnotation("bench.sleep"):
                time.sleep(0.02)
    jax.profiler.stop_trace()
    path = devtrace.find_xplane(tmp)
    from jax.profiler import ProfileData

    for plane in ProfileData.from_file(path).planes:
        print("plane", plane.name)
        for line in plane.lines:
            evs = list(line.events)
            print("  line", repr(line.name), len(evs))
            for ev in evs[:4]:
                print("    ", repr(ev.name), ev.start_ns, ev.duration_ns,
                      dict(list(ev.stats)[:6]))
    Path(out_dir).mkdir(parents=True, exist_ok=True)
    shutil.copy(path, Path(out_dir) / "small.xplane.pb")
    red = devtrace.reduce_trace(path)
    print("reduced", red.window_s, red.busy_s, red.module_s, red.module_calls,
          red.breakdown())
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))
