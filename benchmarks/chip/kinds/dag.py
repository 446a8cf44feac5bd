"""Traffic kind `dag`: waves of a Task Bench tree of device tasks.

Each wave is `fan_out` tasks C = A_i^T B_j through the program's tiled
matmul kernel, each reduced by a vector x (so a task returns n numbers,
not n^2), then `fan_out / group` fan-in tasks that each sum `group`
fan-out results passed as futures, then one root that sums the fan-ins.
A session of `Client(scheduler="dwork", workers=1)` runs the waves, with
at most `outstanding_waves` in flight.  Every task returns when the
device has finished its work, so the chip idles while the engine
schedules.  Traffic parameters: n, fan_out, group, outstanding_waves,
check_waves.
"""
from __future__ import annotations

import time
from collections import deque

import jax
import jax.numpy as jnp
import numpy as np

from common import clipped_run_s, host_rng, rel_err, seed32, wait_future


def fan_out_task(kernel, a, b, x):
    with jax.profiler.TraceAnnotation("bench.fan_out"):
        return jax.block_until_ready(
            jnp.dot(kernel(a, b), x, precision=jax.lax.Precision.HIGHEST))


def fan_in_task(*ys):
    with jax.profiler.TraceAnnotation("bench.fan_in"):
        return jax.block_until_ready(jnp.sum(jnp.stack(ys), axis=0))


def pair(t: int, blocks: int) -> tuple:
    """The (A, B) blocks of fan-out task t: each of the blocks^2 pairs in
    turn."""
    return t % blocks, (t // blocks) % blocks


def x_row(t: int, wave: int, fan_out: int) -> int:
    """Each wave reduces its tasks by other vectors, so waves differ."""
    return (t + wave) % fan_out


class Wave:
    __slots__ = ("index", "fan", "fan_in", "root")

    def __init__(self, index, fan, fan_in, root):
        self.index, self.fan, self.fan_in, self.root = \
            index, fan, fan_in, root

    def futures(self):
        return [*self.fan, *self.fan_in, self.root]


class Workload:
    def __init__(self, cfg, traffic, *, seed, devices, reference):
        from repro.kernels.tiled_matmul.ops import tiled_matmul

        self.cfg, self.traffic, self.seed = cfg, traffic, seed
        self.reference = reference
        self.n = int(traffic["n"])
        self.fan_out = int(traffic["fan_out"])
        self.group = int(traffic["group"])
        self.outstanding = int(traffic["outstanding_waves"])
        self.blocks = int(cfg["blocks"])
        self.kernel = tiled_matmul
        self.task = fan_out_task
        self.combine = fan_in_task
        self.device = devices[0]
        self.waves_done, self.live = [], deque()
        self.t0 = self.t1 = 0.0

    def use_control(self):
        """Run the reference's lower-precision task in the kernel's place
        (control.py)."""
        self.task = self.reference.control_task

    # ------------------------------------------------------------ set-up
    def setup(self):
        from repro.client import Client

        n, k, f = self.n, self.blocks, self.fan_out

        @jax.jit
        def make(key):
            ka, kb, kx = jax.random.split(key, 3)
            return (jax.random.normal(ka, (k, n, n), jnp.float32),
                    jax.random.normal(kb, (k, n, n), jnp.float32),
                    jax.random.normal(kx, (f, n), jnp.float32))

        A, B, X = make(jax.random.PRNGKey(seed32(self.seed)))
        self.A = [A[i] for i in range(k)]
        self.B = [B[i] for i in range(k)]
        self.X = [X[t] for t in range(f)]
        del A, B, X
        self.client = Client(scheduler="dwork", workers=1)
        self.next_wave = 0
        warm = self.submit_wave()                 # every shape, one wave
        jax.block_until_ready(warm.root.result(timeout=600))
        self.first_window_wave = self.next_wave

    def submit_wave(self) -> Wave:
        c, w = self.client, self.next_wave
        self.next_wave += 1
        fan = []
        for t in range(self.fan_out):
            i, j = pair(t, self.blocks)
            fan.append(c.submit(self.task, self.kernel, self.A[i], self.B[j],
                                self.X[x_row(t, w, self.fan_out)]))
        fan_in = [c.submit(self.combine, *fan[g:g + self.group])
                  for g in range(0, self.fan_out, self.group)]
        return Wave(w, fan, fan_in, c.submit(self.combine, *fan_in))

    # ------------------------------------------------------------ window
    def window(self, seconds: float):
        self.t0 = time.perf_counter()
        self.t1 = t_end = self.t0 + seconds
        while True:
            now = time.perf_counter()
            if now >= t_end:
                break
            with jax.profiler.TraceAnnotation("bench.submit_wave"):
                while len(self.live) < self.outstanding:
                    self.live.append(self.submit_wave())
            wait_future(self.live[0].root, t_end - now)
            while self.live and self.live[0].root.done():
                self.waves_done.append(self.live.popleft())

    def finish(self, timeout: float = 120.0):
        """Wait for the waves still in flight, close the session, and
        bring the checked waves and the operands to the host."""
        deadline = time.perf_counter() + timeout
        while self.live:
            wave = self.live.popleft()
            wait_future(wave.root, deadline - time.perf_counter())
            self.waves_done.append(wave)
        self.client.close(timeout=timeout)
        events = list(self.client.engine.tracer.events)
        from repro.core.engine.model import (COMPLETED, FAILED, RUN_END,
                                             RUN_START)
        inside = [e for e in events if self.t0 <= e.t <= self.t1]
        self.completed = sum(1 for e in inside if e.event == COMPLETED)
        self.fan_out_completed = sum(
            1 for e in inside if e.event == COMPLETED
            and e.task.startswith(self.task.__name__))
        self.engine_failed = sum(1 for e in events if e.event == FAILED)
        self.run_s = clipped_run_s(events, self.t0, self.t1, RUN_START,
                                   RUN_END)
        waves = [w for w in self.waves_done
                 if w.index >= self.first_window_wave]
        futs = [f for w in waves for f in w.futures()]
        self.attempted = len(futs)
        self.failed = sum(1 for f in futs
                          if not f.done() or f.exception(0) is not None)
        self.workers = 1
        ok = [w for w in waves if all(f.done() and f.exception(0) is None
                                      for f in w.futures())]
        rng = host_rng(self.seed, 1)
        k = min(int(self.traffic["check_waves"]), len(ok))
        picks = sorted(rng.choice(len(ok), size=k, replace=False)) if k \
            else []
        self.checked = [
            (ok[p].index,
             np.stack([np.asarray(f.result()) for f in ok[p].fan]),
             np.stack([np.asarray(f.result()) for f in ok[p].fan_in]),
             np.asarray(ok[p].root.result()))
            for p in picks]
        self.host_A = [np.asarray(a) for a in self.A]
        self.host_B = [np.asarray(b) for b in self.B]
        self.host_X = np.stack([np.asarray(x) for x in self.X])
        self.waves_in_window = len(waves)

    def release(self):
        """Drop every device array the session and the operands hold."""
        self.waves_done = []
        self.live = deque()
        self.client = None
        self.A = self.B = self.X = None

    # ------------------------------------------------------------- check
    def check(self) -> dict:
        """Largest relative error of the sampled waves' fan-out, fan-in
        and root values against the float64 reference."""
        if not self.checked:
            return {}
        errs = {"fan_out_rel_err": 0.0, "fan_in_rel_err": 0.0,
                "root_rel_err": 0.0}
        for w, fan, fan_in, root in self.checked:
            tasks = [(*pair(t, self.blocks), x_row(t, w, self.fan_out))
                     for t in range(self.fan_out)]
            ref_fan, ref_in, ref_root = self.reference.wave(
                self.host_A, self.host_B, self.host_X, tasks,
                group=self.group)
            errs["fan_out_rel_err"] = max(
                errs["fan_out_rel_err"],
                max(rel_err(g, r) for g, r in zip(fan, ref_fan)))
            errs["fan_in_rel_err"] = max(
                errs["fan_in_rel_err"],
                max(rel_err(g, r) for g, r in zip(fan_in, ref_in)))
            errs["root_rel_err"] = max(errs["root_rel_err"],
                                       rel_err(root, ref_root))
        limits = self.cfg["limits"]
        return {k: (v, float(limits[k])) for k, v in errs.items()}

    def report_lines(self):
        yield (f"[dag] n={self.n} waves_in_window={self.waves_in_window} "
               f"tasks_completed_in_window={self.completed} "
               f"fan_out_completed={self.fan_out_completed} "
               f"run_s={self.run_s:.6f} failed={self.failed} "
               f"engine_failed={self.engine_failed} "
               f"checked_waves={[c[0] for c in self.checked]}")
