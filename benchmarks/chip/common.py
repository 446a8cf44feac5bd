"""Small pieces the traffic kinds share."""
from __future__ import annotations

import threading

import numpy as np


def seed32(seed: int) -> int:
    """A 31-bit key for `jax.random.PRNGKey` from any whole seed (the
    benchmark's seeds go past what 32 signed bits hold)."""
    ss = np.random.SeedSequence(seed % (1 << 64))
    return int(ss.generate_state(1, np.uint32)[0] & 0x7FFFFFFF)


def host_rng(seed: int, stream: int) -> np.random.Generator:
    """An independent NumPy stream per purpose (traffic, sampling)."""
    return np.random.default_rng([seed % (1 << 64), stream])


def rel_err(got, ref) -> float:
    """max |got - ref| over max |ref|, in float64."""
    got = np.asarray(got, np.float64)
    ref = np.asarray(ref, np.float64)
    return float(np.max(np.abs(got - ref)) / np.max(np.abs(ref)))


def percentile(sorted_vals, q: float) -> float:
    """Linear-interpolated percentile of an ascending list, q in [0, 1]
    (the arithmetic of the program's `tracing.percentile`)."""
    if not sorted_vals:
        return 0.0
    if len(sorted_vals) == 1:
        return float(sorted_vals[0])
    pos = q * (len(sorted_vals) - 1)
    lo = int(pos)
    hi = min(lo + 1, len(sorted_vals) - 1)
    frac = pos - lo
    return float(sorted_vals[lo] * (1.0 - frac) + sorted_vals[hi] * frac)


def wait_future(fut, timeout: float) -> bool:
    """Wait up to `timeout` seconds for a client future; True once done."""
    if fut.done():
        return True
    ev = threading.Event()
    fut.add_done_callback(lambda _f: ev.set())
    return ev.wait(max(timeout, 0.0))


def clipped_run_s(events, t0: float, t1: float, run_start: str,
                  run_end: str, keep=lambda name: True) -> float:
    """Seconds of task bodies inside [t0, t1], from an engine trace's
    RUN_START / RUN_END pairs."""
    open_, total = {}, 0.0
    for e in events:
        if e.event == run_start and keep(e.task):
            open_[e.task] = e.t
        elif e.event == run_end and e.task in open_:
            s = max(open_.pop(e.task), t0)
            end = min(e.t, t1)
            if end > s:
                total += end - s
    return total
