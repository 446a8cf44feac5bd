"""Traffic kind `openloop`: generation requests sent on a fixed schedule to
`client.serve`, whatever the server's pace.

Arrivals: `round(rate_per_s * seconds)` requests whose gaps are the
quantiles of an exponential distribution of mean 1 / rate (a Poisson
process's gaps, spread evenly over its distribution), in one fixed
shuffled order, stretched to fill the window exactly.  Every seed sends
this one schedule: at four fifths of the knee a burst's backlog drains
over tens of seconds, so a schedule that moved with the seed would move
the tail more than the server does.  The seed draws the prompts.
Prompts are `prompt_len` random tokens; each request asks for `max_new`
greedy tokens.

The model is the configuration's: `configs/<config>.program.py` maps its
published keys onto the program's config, and the reference beside it
makes the weights and reads the served tokens' logits.  The task body is
the one `repro.launch.serve` gives the engine: `serve.make_batch` and
`serve_step.greedy_generate` over the batch the Frontend formed.  A request's latency runs from when it was due to be
sent to when its response was delivered; one that fails or never
finishes counts as missing.  Traffic parameters: rate_per_s, prompt_len,
max_new, warmup_requests, check_requests, max_wait_ms, per_request_s0,
drain_s.
"""
from __future__ import annotations

import math
import time

import jax
import jax.numpy as jnp
import numpy as np

from common import clipped_run_s, host_rng, percentile, seed32
from registry import program_binding

GAP_ORDER = 20211021          # the one order of the gaps, for every seed


def arrival_offsets(n: int, rate: float, seconds: float) -> np.ndarray:
    """Send times, in seconds from the window's start, of n requests."""
    q = (np.arange(n) + 0.5) / n
    gaps = -np.log1p(-q) / rate
    np.random.default_rng(GAP_ORDER).shuffle(gaps)
    gaps *= seconds / gaps.sum()
    return np.concatenate([[0.0], np.cumsum(gaps)[:-1]])


class Workload:
    def __init__(self, cfg, traffic, *, seed, devices, reference):
        self.cfg, self.traffic, self.seed = cfg, traffic, seed
        self.reference = reference
        self.rate = float(traffic["rate_per_s"])
        self.prompt_len = int(traffic["prompt_len"])
        self.max_new = int(traffic["max_new"])
        self.control = False
        self.batch_start = {}

    def use_control(self):
        """Read the fp8 reference's choices in place of the served tokens
        (control.py)."""
        self.control = True

    # ------------------------------------------------------------ set-up
    def setup(self):
        from repro.client import Client
        from repro.configs import get_config
        from repro.launch import serve
        from repro.models.common import Options
        from repro.models.model import build_model
        from repro.runtime.serve_step import greedy_generate

        pcfg = self.pcfg = program_binding(self.cfg["name"]).program_config(
            self.cfg, get_config(self.cfg["program_arch"]))
        # the model options of `repro.launch.serve.build`
        model = build_model(pcfg, Options(q_block=64, kv_block=64,
                                          moe_group=64))
        key = jax.random.PRNGKey(seed32(self.seed))
        self.params = self.reference.make_weights(
            jax.eval_shape(model.init, key), key)
        jax.block_until_ready(self.params)
        S, new = self.prompt_len, self.max_new
        params, batch_start = self.params, self.batch_start

        def execute_batch(prompts):
            with jax.profiler.TraceAnnotation("bench.serve_batch"):
                t = time.perf_counter()
                for p in prompts:
                    batch_start[id(p)] = t
                b = serve.make_batch(pcfg, jnp.asarray(np.stack(prompts)))
                out = np.asarray(greedy_generate(model, params, b, new,
                                                 S + new + 1))
            return [row for row in out]

        self.client = Client(scheduler="dwork", workers=1,
                             lease_timeout=600.0)
        tr = self.traffic
        self.frontend = self.client.serve(
            execute_batch, max_queue=1 << 16, max_batch=64,
            max_wait_s=float(tr["max_wait_ms"]) * 1e-3,
            per_request_s0=float(tr["per_request_s0"]))
        rng = host_rng(self.seed, 3)
        warm = [rng.integers(2, pcfg.vocab_size, S).astype(np.int32)
                for _ in range(int(tr["warmup_requests"]))]
        for p in warm:                      # one at a time: batch of one
            r = self.frontend.submit(p)
            if not r.wait(900.0) or not r.ok:
                self.client.close(drain=False, timeout=5.0)
                raise RuntimeError(f"warm-up request failed: {r.error}")
        self.rng_prompts = rng

    # ------------------------------------------------------------ window
    def window(self, seconds: float):
        n = max(1, int(round(self.rate * seconds)))
        offsets = arrival_offsets(n, self.rate, seconds)
        self.requests, self.due, self.sent = [], [], []
        self.prompts = [self.rng_prompts.integers(
            2, self.pcfg.vocab_size, self.prompt_len).astype(np.int32)
            for _ in range(n)]
        self.t0 = t0 = time.perf_counter()
        self.t1 = t0 + seconds
        for off, p in zip(offsets, self.prompts):
            due = t0 + off
            wait = due - time.perf_counter()
            if wait > 0:
                time.sleep(wait)
            with jax.profiler.TraceAnnotation("bench.submit"):
                self.sent.append(time.perf_counter())
                self.requests.append(self.frontend.submit(p))
            self.due.append(due)
        rest = self.t1 - time.perf_counter()
        if rest > 0:
            time.sleep(rest)

    def finish(self, close: bool = True):
        """Wait for the window's requests (up to `drain_s` past its end),
        then read latencies, queue waits and batches; `close=False` keeps
        the session open for another window."""
        deadline = self.t1 + float(self.traffic["drain_s"])
        for r in self.requests:
            r.wait(max(deadline - time.perf_counter(), 0.0))
        self.t_end = time.perf_counter()
        from repro.core.engine.model import BATCH_FORMED, RUN_END, RUN_START

        if close:
            try:
                self.client.close(timeout=5.0)
            except RuntimeError as e:            # a request still running
                print(f"[serve] close: {e!r}", flush=True)
        events = list(self.client.engine.tracer.events)
        batch = lambda name: name.startswith("__batch")   # noqa: E731
        self.batch_run_s = clipped_run_s(events, self.t0, math.inf,
                                         RUN_START, RUN_END, keep=batch)
        sizes = [e.extra.get("size", 0) for e in events
                 if e.event == BATCH_FORMED and e.t >= self.t0]
        self.batches = len(sizes)
        self.mean_batch = sum(sizes) / len(sizes) if sizes else 0.0
        lat, waits, self.served, done = [], [], [], []
        for r, due, p in zip(self.requests, self.due, self.prompts):
            if r.done and r.ok:
                lat.append(r.t_done - due)
                waits.append(self.batch_start[id(p)] - r.t_enqueue)
                self.served.append((p, np.asarray(r.value)))
                done.append(r.t_done)
            else:
                lat.append(math.inf)
        self.latencies = sorted(lat)
        self.queue_waits = waits          # in the order the requests were sent
        self.t_last_done = max(done, default=self.t1)
        self.attempted = len(self.requests)
        self.failed = sum(1 for x in lat if math.isinf(x))
        late = [s - d for s, d in zip(self.sent, self.due)]
        self.late_max_ms = max(late) * 1e3 if late else 0.0
        self.late_mean_ms = sum(late) / len(late) * 1e3 if late else 0.0

    def latency_ms(self, q: float) -> float:
        """The q-th percentile of every request's latency; a missing one
        counts as the time the run waited for it."""
        waited = self.t_end - min(self.due)
        vals = [min(x, waited) for x in self.latencies]
        return percentile(vals, q) * 1e3

    def release(self):
        """Drop the serving session; keep the weights, which are the
        benchmark's own, for the reference."""
        self.client = self.frontend = None
        self.requests = []

    # ------------------------------------------------------------- check
    def check(self) -> dict:
        """Widest gap between the reference's best logit and the logit of
        the token served, over a sample of served requests drawn from the
        seed."""
        if not self.served:
            return {}
        rng = host_rng(self.seed, 4)
        k = min(int(self.traffic["check_requests"]), len(self.served))
        picks = sorted(rng.choice(len(self.served), size=k, replace=False))
        seqs = np.stack([np.concatenate([self.served[i][0],
                                         self.served[i][1]])
                         for i in picks])
        gaps = self.reference.served_gap(self.params, seqs, self.prompt_len,
                                         self.cfg, fp8=self.control)
        self.params = None
        limit = float(self.cfg["limits"]["served_token_logit_gap"])
        return {"served_token_logit_gap": (float(np.max(gaps)), limit)}

    def report_lines(self):
        yield (f"[gen] requests={len(self.due)} rate_per_s={self.rate} "
               f"late_ms_max={self.late_max_ms:.3f} "
               f"late_ms_mean={self.late_mean_ms:.3f}")
        yield (f"[serve] batches={self.batches} mean_batch={self.mean_batch} "
               f"batch_run_s={self.batch_run_s:.6f} failed={self.failed} "
               f"p50_ms={self.latency_ms(0.5):.3f} "
               f"p95_ms={self.latency_ms(0.95):.3f} "
               f"queue_wait_p50_ms="
               f"{percentile(sorted(self.queue_waits), 0.5) * 1e3:.3f}")
