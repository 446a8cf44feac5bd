"""The engine side of the proc transport's peer-to-peer data plane.

A result above `inline_bytes` stays in its producing worker's store and
the front door records only its location (`comm/proc.py`).  `DataPlane`
is what the engine process does with such values: fetch them for
`RemoteValue` handles, attribute every transfer, and recompute a value
whose only copy died with its worker.  Each `ProcBackend` owns one; an
engine on another transport holds one with zero totals.
"""
from __future__ import annotations

import threading
import time
from typing import Optional

from repro.core.dwork.api import Fetch, ValueMsg
from repro.core.engine.comm import core as comm_core
from repro.core.engine.comm.serialize import RemoteValue, loads
from repro.core.engine.model import REQUEUED, XFER

MAX_RECOMPUTES = 3          # recompute aliases per lost value
FETCH_DEADLINE_S = 30.0     # a reader's wait for a recomputed value


class DataPlane:
    """Transfer totals, fetch and lost-value recompute.  `backend` is
    the `ProcBackend` whose front door holds the value store and the
    location table (None: totals only).  `fetch()` runs on client
    threads and asks the dispatch loop for recomputes through `wanted`,
    so every backend Create stays on the loop's thread."""

    def __init__(self, backend=None):
        self.backend = backend
        self.lock = threading.Lock()     # totals, attempts, wanted
        self.totals = {"peer": [0, 0, 0.0], "hub": [0, 0, 0.0]}
        self.lost_total = 0              # recomputes issued
        self.metrics = None              # obs sink (XferMetrics)
        self.conns: dict = {}            # data_addr -> Comm
        self.attempts: dict = {}         # lost name -> recomputes issued
        self.pending: dict = {}          # lost name -> live alias
        self.wanted: set = set()         # recomputes asked by readers
        self.tasks: dict = {}            # the engine's task registry
        self.done = None                 # engine: has `name` terminated?
        self.live = False                # a dispatch loop can recompute

    def snapshot(self) -> dict:
        """{"lost": n, "by_path": {path: [count, bytes, seconds]}}, taken
        under the plane's lock."""
        with self.lock:
            return {"lost": self.lost_total,
                    "by_path": {p: list(v) for p, v in self.totals.items()}}

    def record(self, task: str, worker: Optional[str], path: str,
               nbytes: int, dt: float):
        """Attribute one dependency-value transfer: an `xfer` trace event
        (never sampled — fetches are rare next to rpcs), the per-path
        running totals, and the obs metrics sink when wired."""
        self.backend.tracer.emit(XFER, task=task, worker=worker, path=path,
                                 n=int(nbytes), dt=float(dt))
        with self.lock:
            tot = self.totals.setdefault(path, [0, 0, 0.0])
            tot[0] += 1
            tot[1] += int(nbytes)
            tot[2] += float(dt)
        m = self.metrics
        if m is not None:
            m.observe(path, int(nbytes), float(dt))

    # ------------------------------------------------- dispatch-loop side
    def open(self, tasks: dict, done):
        # a run starts: the engine's registry holds the packed calls, and
        # done(name) is its exactly-once terminal test
        self.tasks, self.done, self.live = tasks, done, True

    def _call(self, name: str):
        task = self.tasks.get(name)
        return task.meta.get("__call__") if task is not None else None

    def _recompute(self, missing: str) -> Optional[str]:
        """Ensure a recompute of a lost value is in flight: reuse the
        pending store-as alias when it has not itself terminated, else
        create a fresh one from the task's packed call.  -> the alias
        name, or None when recompute is impossible (no packed call) or
        the attempt budget is spent — callers fail/raise then."""
        with self.lock:
            alias = self.pending.get(missing)
            if alias is not None and not self.done(alias):
                return alias
            k = self.attempts.get(missing, 0) + 1
            self.attempts[missing] = k     # exhausted marks stop waiters
            call = self._call(missing)
            if call is None or k > MAX_RECOMPUTES:
                return None
            alias = f"{missing}~r{k}"
            self.pending[missing] = alias
            self.lost_total += 1
        self.backend.create(alias, deps=(), meta={
            "__call__": call, "__store_as__": missing})
        return alias

    def _revive(self, missing: str):
        if self._recompute(missing) is not None:
            self.backend.tracer.emit(REQUEUED, task=missing, n=1,
                                     via="xfer_lost")

    def lost_on(self, worker: str):
        """`worker` died: recompute the values whose ONLY copy it held
        now, not when (if ever) a dependent trips over the hole —
        client-facing RemoteValues have no dependent task."""
        door = self.backend.door
        for missing, loc in list(door.locations.items()):
            # the location stays: a consumer's dial then fails and reports
            # the value lost, where a hub NotFound would fail it for real
            if loc[0] == worker and missing not in door.values \
                    and missing in self.tasks:
                self._revive(missing)

    def requeue_lost(self, worker: str, name: str,
                     missing: str) -> Optional[str]:
        """Transfer-requeue `name`, withheld because its dependency value
        `missing` died unreplicated, behind a recompute of the value ->
        None, or the reason it must fail."""
        b = self.backend
        if missing in b.door.values:
            # the value resurfaced (a spill/exit-flush landed after the
            # worker's fetch failed): plain requeue
            b.transfer(worker, name, [])
            return None
        alias = self._recompute(missing)
        if alias is None:
            return (f"dependency value {missing!r} lost (producer died "
                    "before replication); recompute exhausted or no "
                    "packed call")
        b.tracer.emit(REQUEUED, task=name, n=1, via="xfer_lost")
        b.transfer(worker, name, [alias])
        return None

    def serve_wanted(self) -> bool:
        """Recompute what readers asked for; True if they asked."""
        if not self.wanted:
            return False
        with self.lock:
            wanted = list(self.wanted)
            self.wanted.clear()
        values = self.backend.door.values
        for missing in wanted:
            if missing not in values:
                self._revive(missing)
        return True

    def close(self, results: dict):
        """After the workers' exit flush: materialise outstanding
        RemoteValues while the door still exists (the handles are shared
        with client futures, so get() caches for them too), then close
        the peer connections."""
        for res in results.values():
            v = res.value
            if isinstance(v, RemoteValue):
                try:
                    res.value = v.get()
                except Exception:  # noqa: BLE001 — unrecoverable value
                    pass           # keep the handle; reads raise
        for comm in self.conns.values():
            _close(comm)
        self.conns.clear()

    # ------------------------------------------------- client-thread side
    def _fetch_once(self, name: str):
        """One fetch attempt: the hub's value store first (a spill or
        exit-flush may have landed), then a direct dial of the producing
        worker's data listener.  -> (payload, path) or (None, None)."""
        door = self.backend.door
        payload = door.values.get(name)
        path = "hub"
        if payload is None:
            loc = door.locations.get(name)
            if loc is not None and loc[1]:
                addr = loc[1]
                resp = None
                try:
                    comm = self.conns.get(addr)
                    if comm is None:
                        comm = comm_core.connect(addr)
                        self.conns[addr] = comm
                    resp = comm.request(Fetch(task=name))
                except Exception:  # noqa: BLE001 — producer gone mid-dial
                    stale = self.conns.pop(addr, None)
                    if stale is not None:
                        _close(stale)
                if isinstance(resp, ValueMsg):
                    payload = resp.payload
                    path = "peer"
            if payload is None:
                payload = door.values.get(name)  # a spill raced us in
        return (payload, path) if payload is not None else (None, None)

    def fetch(self, name: str):
        """RemoteValue materializer, called from client threads
        (`Future.result()`, `gather`).  Cache-miss recovery: when the
        value is gone AND the dispatch loop is live AND the task has a
        packed call with attempt budget left, ask the loop to recompute
        it and poll until the store-as lands the value back on the hub.
        Raises KeyError only when genuinely unrecoverable."""
        t0 = time.perf_counter()
        deadline = t0 + FETCH_DEADLINE_S
        next_ask = t0
        while True:
            payload, path = self._fetch_once(name)
            if payload is not None:
                self.record(name, None, path, len(payload),
                            time.perf_counter() - t0)
                return loads(payload)
            now = time.perf_counter()
            recomputable = (
                self.live and now < deadline
                and self._call(name) is not None
                and self.attempts.get(name, 0) <= MAX_RECOMPUTES)
            if not recomputable:
                raise KeyError(
                    f"value for {name!r} is unrecoverable: not on the hub "
                    "and its producing worker cannot serve it")
            if now >= next_ask:   # re-ask ~1/s: idempotent while an alias
                with self.lock:              # is live, rolls to the next
                    self.wanted.add(name)    # attempt once one fails
                next_ask = now + 1.0
            time.sleep(0.02)


def _close(comm):
    try:
        comm.close()
    except Exception:  # noqa: BLE001 — the peer is already gone
        pass
