"""From a profiler trace to device busy time, op times and idle gaps.

The JAX profiler writes `<dir>/plugins/profile/<time>/<host>.xplane.pb`.
Each device is a plane `/device:TPU:<n>`; its line `XLA Ops` holds one
event per operation that ran, and `XLA Modules` one per program.  Host
threads are planes `/host:...`; the benchmark's own spans
(`jax.profiler.TraceAnnotation`) sit there, among them `bench.window`,
which bounds the measured window.  Times are nanoseconds.  A device's
clock runs apart from the host's by up to a few milliseconds, so each
device plane is shifted onto the host's clock: by the least delay, over
the programs both sides name by `run_id`, from a program's end on the
device to the host's `CompleteCallbacks` for it (the host cannot see an
end before it happens).

Busy time is the union of a device's op intervals inside the window; idle
is the rest.  Each idle gap is named after the innermost host span that
covers its midpoint, so a gap says what the host was doing meanwhile.
"""
from __future__ import annotations

import glob
import os
import re
from collections import defaultdict
from dataclasses import dataclass, field

WINDOW_SPAN = "bench.window"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
HOST_DONE = "CompleteCallbacks"
COLLECTIVE = re.compile(
    r"\b(all-gather|all-reduce|all-to-all|collective-permute|reduce-scatter"
    r"|collective-broadcast)(-start|-done)?\(")


def profile_options():
    """Host spans without Python's function tracer, which would time every
    call of the program and slow it down."""
    import jax

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    opts.enable_hlo_proto = False
    return opts


def find_xplane(log_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return paths[-1]


def _union(intervals):
    """Merged, sorted, disjoint intervals."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return out


def op_name(text: str) -> str:
    """An op event's name is its HLO instruction, `%name = type op(...)`:
    keep the name, and the full text for what the name does not say."""
    return text.split(" = ", 1)[0].lstrip("%")


def is_collective(text: str) -> bool:
    return COLLECTIVE.search(text) is not None


@dataclass
class Reduced:
    window_s: float
    busy_s: float                       # mean over the devices
    devices: int
    op_s: dict = field(default_factory=dict)       # op text -> s, mean
    module_s: dict = field(default_factory=dict)   # program -> s, mean
    module_calls: dict = field(default_factory=dict)
    gaps: list = field(default_factory=list)       # (s, host span), device 0

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s

    def collective_s(self) -> float:
        return sum(s for n, s in self.op_s.items() if is_collective(n))

    def breakdown(self, top: int = 10) -> dict:
        by_name = defaultdict(float)
        for text, s in self.op_s.items():
            by_name[op_name(text)] += s
        ops = sorted(by_name.items(), key=lambda kv: -kv[1])[:top]
        by_host = defaultdict(float)
        for s, what in self.gaps:
            by_host[what] += s
        gaps = sorted(by_host.items(), key=lambda kv: -kv[1])[:top]
        return {"device_ops": [[n, s] for n, s in ops],
                "idle_gaps": [[n, s] for n, s in gaps]}


def _events(line, shift=0.0):
    return [(ev.name, ev.start_ns + shift, ev.start_ns + ev.duration_ns + shift)
            for ev in line.events]


def _run_id(ev):
    for k, v in ev.stats:
        if k == "run_id":
            return v
    return None


def _host_done(planes) -> dict:
    """run_id -> earliest host time at which its completion was seen."""
    done = {}
    for plane in planes:
        for line in plane.lines:
            for ev in line.events:
                if ev.name == HOST_DONE:
                    rid = _run_id(ev)
                    if rid is not None:
                        done[rid] = min(done.get(rid, ev.start_ns),
                                        ev.start_ns)
    return done


def _shift(plane, host_done: dict) -> float:
    """Nanoseconds to add to this device's times to put them on the
    host's clock (0 where no program is named on both sides)."""
    diffs = []
    for line in plane.lines:
        if line.name != MODULES_LINE:
            continue
        for ev in line.events:
            rid = _run_id(ev)
            if rid in host_done:
                diffs.append(host_done[rid] - (ev.start_ns + ev.duration_ns))
    return min(diffs) if diffs else 0.0


def reduce_trace(path: str) -> Reduced:
    """Reduce one `.xplane.pb` to the window's device numbers."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    host, devices, host_planes = [], [], []    # host: events per thread
    for plane in pd.planes:
        if plane.name.startswith("/host:"):
            host_planes.append(plane)
            for line in plane.lines:
                host.append(_events(line))
        elif plane.name.startswith("/device:") and "TPU" in plane.name:
            devices.append(plane)
    host_done = _host_done(host_planes)
    windows = [(s, e) for line in host for n, s, e in line
               if n == WINDOW_SPAN]
    if not windows:
        raise ValueError(f"{path}: no {WINDOW_SPAN!r} span on the host")
    w0, w1 = windows[0]
    if not devices:
        raise ValueError(f"{path}: no TPU device plane")
    devices.sort(key=lambda p: p.name)

    busy, op_s, mod_s, mod_n = [], defaultdict(float), defaultdict(float), \
        defaultdict(float)
    gaps = []
    for i, plane in enumerate(devices):
        lines = {ln.name: ln for ln in plane.lines}
        shift = _shift(plane, host_done)
        ops = []
        for name, s, e in _events(lines[OPS_LINE], shift) \
                if OPS_LINE in lines else []:
            s, e = max(s, w0), min(e, w1)
            if e > s:
                ops.append((s, e))
                op_s[name] += (e - s) * 1e-9
        if MODULES_LINE in lines:
            for name, s, e in _events(lines[MODULES_LINE], shift):
                s, e = max(s, w0), min(e, w1)
                if e > s:
                    mod_s[name] += (e - s) * 1e-9
                    mod_n[name] += 1
        merged = _union(ops)
        busy.append(sum(e - s for s, e in merged) * 1e-9)
        if i == 0:
            gaps = _name_gaps(merged, w0, w1, host)
    n = len(devices)
    return Reduced(
        window_s=(w1 - w0) * 1e-9, busy_s=sum(busy) / n, devices=n,
        op_s={k: v / n for k, v in op_s.items()},
        module_s={k: v / n for k, v in mod_s.items()},
        module_calls={k: v / n for k, v in mod_n.items()},
        gaps=gaps)


def _name_gaps(merged, w0, w1, host):
    """Idle intervals of one device, each with the innermost host span
    that covers its midpoint ("no host span" where none does)."""
    idle, t = [], w0
    for s, e in merged:
        if s > t:
            idle.append((t, s))
        t = max(t, e)
    if w1 > t:
        idle.append((t, w1))
    mids = [(s + e) / 2 for s, e in idle]
    best = [None] * len(idle)
    for line in host:
        _innermost(line, mids, best)
    return [((e - s) * 1e-9, b[1] if b else "no host span")
            for (s, e), b in zip(idle, best)]


def _innermost(line, mids, best):
    """For each sorted point of `mids`, the shortest span of one host
    thread that covers it (spans of one thread nest), kept in `best`
    where it is shorter than what another thread gave."""
    events = sorted((s, -e, n) for n, s, e in line
                    if e > s and n != WINDOW_SPAN)
    stack, i = [], 0
    for q, mid in enumerate(mids):
        while i < len(events) and events[i][0] <= mid:
            s, neg_e, n = events[i]
            while stack and stack[-1][1] < s:
                stack.pop()
            stack.append((s, -neg_e, n))
            i += 1
        while stack and stack[-1][1] < mid:
            stack.pop()
        if stack:
            s, e, n = stack[-1]
            if best[q] is None or e - s < best[q][0]:
                best[q] = (e - s, n)
