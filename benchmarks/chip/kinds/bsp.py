"""Traffic kind `bsp`: bulk-synchronous steps of mpi-list verbs over a
distributed list sharded on the cell's chips.

A step is map(r -> r*r + 1), sum over rows, inclusive scan (+) down the
rows, map(row -> sum(row) % chips) and group by that destination, all
through `repro.core.mpi_list.mesh_ops` on a `data` axis over the chips.
Steps run back to back; a step ends when all its outputs are ready on
the devices.  The list is int32, drawn on the devices from the seed, and
every step of the window draws a new one (in one jitted call, keyed by
the seed and the step's number) before its verbs, so no step repeats the
input of another: the step is timed with that draw, a few milliseconds
of HBM writes.
"""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from common import seed32


def square_plus_one(r):
    return r * r + 1


def add(a, b):
    return a + b


class Workload:
    def __init__(self, cfg, traffic, *, seed, devices, reference):
        self.cfg, self.traffic, self.seed = cfg, traffic, seed
        self.reference = reference
        self.devices = devices
        self.chips = len(devices)
        if self.chips != int(cfg["chips"]):
            raise ValueError(f"{cfg['name']} runs on {cfg['chips']} chips")
        self.rows = int(cfg["rows_per_chip"]) * self.chips
        self.width = int(cfg["width"])
        self.control = False
        self.steps = 0
        self.last = None

    def use_control(self):
        """Sum and scan the list in float32 in place of int32 (control.py):
        the exactness the configuration states, broken."""
        self.control = True

    def setup(self):
        from repro.core.mpi_list import mesh_ops

        self.ops = mesh_ops
        self.mesh = jax.make_mesh((self.chips,), ("data",),
                                  devices=self.devices)
        R, W, hi = self.rows, self.width, int(self.cfg["value_high"])
        self.key = jax.random.PRNGKey(seed32(self.seed))
        self.draw = jax.jit(
            lambda k, i: jax.random.randint(jax.random.fold_in(k, i), (R, W),
                                            0, hi, jnp.int32),
            out_shardings=mesh_ops.data_sharding(self.mesh, 2))
        self.dfm = self.draw(self.key, 0)
        self.last = self.step()                  # every program, once

    def step(self) -> dict:
        m, ops, chips = self.mesh, self.ops, self.chips
        with jax.profiler.TraceAnnotation("bench.map"):
            sq = ops.dfm_map(m, square_plus_one, self.dfm)
        acc = sq.astype(jnp.float32) if self.control else sq
        with jax.profiler.TraceAnnotation("bench.sum"):
            col = ops.dfm_sum(m, acc)
        with jax.profiler.TraceAnnotation("bench.scan"):
            scan = ops.dfm_scan(m, add, acc)
        with jax.profiler.TraceAnnotation("bench.group"):
            dest = ops.dfm_map(m, lambda r: jnp.sum(r) % chips, self.dfm)
            grp = ops.group(m, dest, self.dfm)
        if self.control:
            col, scan = col.astype(jnp.int32), scan.astype(jnp.int32)
        with jax.profiler.TraceAnnotation("bench.step_wait"):
            return jax.block_until_ready(
                {"map": sq, "sum": col, "scan": scan, "group": grp})

    def window(self, seconds: float):
        self.t0 = time.perf_counter()
        t_end = self.t0 + seconds
        while time.perf_counter() < t_end:
            self.last = None                      # one step's outputs live
            with jax.profiler.TraceAnnotation("bench.draw"):
                self.dfm = None
                self.dfm = self.draw(self.key, self.steps + 1)
            self.last = self.step()
            self.steps += 1
        self.t1 = time.perf_counter()

    def finish(self):
        self.attempted = self.steps
        self.failed = 0
        self.host = {k: np.asarray(v) for k, v in self.last.items()}
        self.host_x = np.asarray(self.dfm)

    def release(self):
        self.last = self.dfm = None

    def check(self) -> dict:
        """Elements of the last step's outputs that differ from the
        reference over that step's own list; every verb exact."""
        ref = self.reference.step(self.host_x, self.chips)
        limits = self.cfg["limits"]
        out = {}
        for verb, want in ref.items():
            got = self.host[verb]
            bad = want.size if got.shape != want.shape else \
                int(np.count_nonzero(got != want))
            out[f"{verb}_mismatches"] = (bad, limits[f"{verb}_mismatches"])
        self.host = self.host_x = None
        return out

    def step_ms(self) -> float:
        return (self.t1 - self.t0) / self.steps * 1e3

    def report_lines(self):
        yield (f"[bsp] rows={self.rows} width={self.width} chips={self.chips} "
               f"steps={self.steps} window_s={self.t1 - self.t0:.6f} "
               f"step_ms={self.step_ms():.3f}")
