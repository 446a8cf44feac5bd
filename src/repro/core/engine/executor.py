"""The unified worker pool: one execution substrate for all three schedulers.

One dispatch loop, `Engine.run`, drives workers against a scheduler
backend (`ServerBackend` / `ShardedBackend` / `TreeBackend`, or the proc
transport's `ProcBackend`), generalizing the paper's three execution
loops:

  * dwork  (§2.2) — workers Steal-n batches and Complete tasks; the loop
    IS the paper's Fig. 2 CLIENT-LOOP, with per-worker fault injection.
  * pmake  (§2.1) — tasks carry `slots` (nodes) and `priority` (EFT);
    the launch step is pmake's "greedy highest-priority-first onto free
    nodes", with `capacity` total slots.
  * mpi-list (§2.3) — each bulk step submits one task per rank; per-rank
    times (plus injected straggler jitter) feed the Gumbel sync-gap model.

Every round takes the same shared steps, each written once here: abort,
mailbox ingest, membership commands (`_join`), a worker's death
(`_bury`), the retry-policy decision (`_retry`), an executed task's
terminal bookkeeping (`_finish`), and the termination and stall test;
at exit, the journal sync, the owned-backend close and the
`EngineReport`.  One call a round differs by transport, the transport
step of `rounds.py`: `InlineRounds` for "inproc" (tasks run inline,
fully deterministic — the default for tests, fault injection and
pure-overhead measurement), "thread" (a slot-bounded thread pool, for
blocking task bodies) and "tree" (every worker RPC crosses a real TCP
forwarding tree, paper §4); `ProcRounds` for "proc" (worker processes;
the data plane behind them lives in `comm/`).

Modes:
  * batch (default) — `run()` drains a pre-submitted task universe and
    returns when every task reaches a terminal state (or the pool stalls).
  * resident (`Engine(resident=True)`) — `start()` runs the same dispatch
    loop open-ended in a background thread; `submit()` keeps accepting
    work while workers are live (thread-safe), `drain()` blocks until the
    submitted universe is terminal, `shutdown()` stops the loop and
    returns the `EngineReport`.  `add_worker()` / `lose_worker()` change
    pool membership on the fly, and `self.steal_n` is re-read every round
    so batch size can track the live worker count.  Faults, heartbeat
    leases, and lifecycle tracing behave exactly as in batch mode; a
    server-side "all done" is treated as "idle" rather than termination
    until `shutdown()` is requested.  While idle, steals back off to one
    probe per `IDLE_PROBE_ROUNDS` rounds (a new `submit()` wakes the pool
    immediately via a submission epoch) so an idle service doesn't flood
    the trace with empty round-trips.  `repro.core.serving.Frontend`
    layers admission control and dynamic request batching on top.

Hot path: completions are buffered per worker and piggybacked onto that
worker's next steal as ONE `CompleteSteal` round-trip (the Fig. 2
batch-then-drain rhythm — `steal_n` amortizes both protocol directions),
the pending set is a priority heap with incrementally-maintained
per-worker outstanding counts (no per-round rescans/sorts), and every
lifecycle transition is emitted to the `TraceRecorder`, from which
`tracing.OverheadReport` computes empirical per-task overhead and METG.
"""
from __future__ import annotations

import threading
import time
from collections import deque
from dataclasses import dataclass, field
from pathlib import Path
from typing import Callable, Optional

from repro.core.engine.comm import core as comm_core
from repro.core.engine.comm.data_plane import DataPlane
from repro.core.engine.comm.serialize import dumps_call
from repro.core.engine.faults import FaultPlan
from repro.core.engine.journal import Journal
from repro.core.engine.model import (CANCELLED, COMPLETED, CREATED, FAILED,
                                     READY, RETRIED, RUN_END, RUN_START,
                                     WORKER_DEAD, EngineTask, RetryPolicy,
                                     TaskResult, WorkerCrash)
from repro.core.engine.rounds import InlineRounds, ProcRounds
from repro.core.engine.tracing import OverheadReport, TraceRecorder, span

# transport families live in the comm registry (repro.core.engine.comm);
# this tuple stays as the public "what can I pass" surface
TRANSPORTS = comm_core.transport_names()



@dataclass
class EngineReport:
    results: dict                      # task -> TaskResult (last execution)
    trace: TraceRecorder
    workers: int                       # effective parallelism (overhead math)
    wall_s: float
    pool_workers: int = 1              # configured pool size (reporting)
    errors: set = field(default_factory=set)
    stalled: bool = False
    backend_stats: dict = field(default_factory=dict)

    @property
    def completed(self) -> set:
        return {n for n, r in self.results.items() if r.ok}

    def overhead(self) -> OverheadReport:
        return self.trace.report(workers=self.workers)


class Engine:
    def __init__(self, *, workers: int = 1, capacity: Optional[int] = None,
                 transport: str = "inproc", steal_n: int = 1, shards: int = 1,
                 backend=None, tracer: Optional[TraceRecorder] = None,
                 faults: Optional[FaultPlan] = None, clock=None,
                 lease_timeout: Optional[float] = None, poll: float = 0.001,
                 max_idle_rounds: Optional[int] = None, tree_fanout: int = 4,
                 tree_levels: int = 1, resident: bool = False,
                 keep_results: bool = True,
                 on_result: Optional[Callable] = None,
                 retry: Optional[RetryPolicy] = None,
                 journal=None, proc_host: str = "127.0.0.1",
                 proc_port: int = 0, heartbeat_s: float = 0.5,
                 inline_bytes: int = 65536,
                 spill_bytes: int = 64 * 1024 * 1024):
        fam = comm_core.family(transport)   # raises on an unknown name
        self.workers = max(int(workers), 0)
        self.capacity = capacity if capacity is not None else max(workers, 1)
        self.transport = transport
        self.steal_n = max(int(steal_n), 1)
        self.faults = faults
        # engine-wide transient-failure policy (per-task `retry=` on
        # submit overrides it); None = a failure poisons immediately
        self.retry = retry
        # durable control plane: a write-ahead `Journal` (or a directory
        # path, which constructs and OWNS one — closed when the dispatch
        # loop exits).  Off by default: journaling is opt-in so the
        # fault-free hot path pays only a None check.
        self._owns_journal = isinstance(journal, (str, Path))
        self.journal = Journal(journal) if self._owns_journal else journal
        self.poll = poll
        self.lease_timeout = lease_timeout
        self.heartbeat_s = max(float(heartbeat_s), 0.05)
        # peer-to-peer data plane knobs (transport="proc"): results above
        # `inline_bytes` serialized payload stay in the producing worker's
        # local store (the hub tracks the LOCATION); `spill_bytes` is each
        # worker's LRU byte budget before owned values spill to the hub
        self.inline_bytes = max(int(inline_bytes), 0)
        self.spill_bytes = max(int(spill_bytes), 0)
        self.resident = bool(resident)
        # result plumbing for the futures client: `on_result(name, ok,
        # res, error)` fires exactly once per task name, at its FIRST
        # terminal transition (requeued re-executions never re-fire),
        # always outside the engine lock so the handler may call back in.
        # `res` is the TaskResult when the task executed here, None for
        # poisoned/cancelled/fail-fast tasks.  The handler must not raise
        # (a raise would kill the dispatch loop); the client guards its
        # user-visible callbacks itself.
        self.on_result = on_result
        # called (once, from the dying loop thread) if the resident
        # dispatch loop exits with an error, so a client can fail its
        # pending futures instead of leaving waiters hanging until
        # shutdown() re-raises
        self.on_loop_error: Optional[Callable] = None
        # resident services that hold results elsewhere (futures) can opt
        # out of the EngineReport.results history table (bounded state)
        self.keep_results = bool(keep_results)
        self.tracer = tracer or TraceRecorder(clock=clock)
        if backend is not None and getattr(backend, "tracer", None) is None:
            backend.tracer = self.tracer
        # under proc, a caller-supplied TaskServer/hub adaptation (the
        # run_pool shim) is fronted with a ProcBackend, whose listener
        # and processes are ours to close though the inner one is not
        self._owns_backend = backend is None or (
            transport == "proc" and not hasattr(backend, "data"))
        if self._owns_backend:
            # the comm registry owns the backend recipe per transport
            # family (shards > 1 composes inside each builder: a
            # ShardedHub behind the tree, or sharded under proc)
            backend = fam.make_backend(
                inner=backend, workers=self.workers, shards=shards,
                lease_timeout=lease_timeout, clock=clock,
                tracer=self.tracer, tree_fanout=tree_fanout,
                tree_levels=tree_levels, steal_n=self.steal_n,
                resident=self.resident, proc_host=proc_host,
                proc_port=proc_port, heartbeat_s=self.heartbeat_s,
                inline_bytes=self.inline_bytes,
                spill_bytes=self.spill_bytes)
        self.backend = backend
        # the proc backend's data plane; elsewhere one that only holds
        # zero transfer totals (xfer_totals and /stats read alike)
        self.data_plane = getattr(backend, "data", None) or DataPlane()
        if self.journal is not None:
            # backends journal the requeue records their verbs observe
            # (Exit recycling, lease expiry) — the engine journals
            # create/terminal itself
            backend.journal = self.journal
        # the dispatch-rate multiplier the METG retunes see (serving
        # batch targets, elastic steal_n): authoritative from the
        # backend, so a caller-supplied hub/backend is counted too
        self.shards = getattr(backend, "n_shards", max(int(shards), 1))
        # long enough for a heartbeat lease to expire while idling
        if max_idle_rounds is None:
            max_idle_rounds = 500
            if lease_timeout:
                max_idle_rounds = max(500, int(2 * lease_timeout / poll))
        self.max_idle_rounds = max_idle_rounds
        # engine-local task registry (fn/priority/slots + ready tracking)
        self.tasks: dict[str, EngineTask] = {}
        self._waiting: dict[str, set] = {}
        self._succs: dict[str, list] = {}
        self._pass_worker = False
        # ---------------------------------------------- resident-mode state
        # _cond guards the registry + counters that submit() (any thread)
        # and the dispatch loop both touch; batch mode never takes it.
        # Built over a plain Lock: the re-entrancy of the default RLock is
        # never needed, and both threads take this once per task/batch, so
        # acquisition cost is on the submit hot path.
        self._cond = threading.Condition(threading.Lock())
        self._inflight = 0              # submitted, not yet terminal
        self._terminal: set[str] = set()
        self._failed: set[str] = set()
        self._epoch = 0                 # bumped on submit/requeue: wakes idle
        # resident submissions go through a mailbox: submit() appends
        # under a SHORT _cond hold (atomic w.r.t. cancel and the prune
        # keep-set) and the dispatch loop ingests in batches on its own
        # thread — the single-writer rule that keeps client threads off
        # the server lock on every task.  `_unsent` tracks names still
        # in the mailbox so cancel() can withdraw them engine-side.
        self._mailbox: deque = deque()
        self._unsent: set[str] = set()
        self._commands: deque = deque()  # ("add"|"lose", worker) membership
        self._live = self.workers       # live (not dead) worker count
        self._next_wid = self.workers   # auto worker naming for add_worker()
        self._stop = False              # drain-then-exit requested
        self._abort = False             # exit now, abandon pending work
        self._thread: Optional[threading.Thread] = None
        self._report: Optional[EngineReport] = None
        self._loop_error: Optional[BaseException] = None
        # -------------------------------------------------- observability
        # plain tables the dispatch loop maintains unconditionally (one
        # list-slot hit per completion); `repro.core.obs` reads them via
        # zero-cost callback instruments, and worker_stats()/
        # tasks_done_total() are the monitoring probes over them
        self.worker_deaths = 0
        self.exec_failed = 0                  # executions raised / not-ok
        self.retries_total = 0                # re-enqueues by RetryPolicy
        self._attempts: dict[str, int] = {}   # failed executions per task
        self._wstats: dict[str, list] = {}    # worker -> [done_n, busy_s]
        self._dead_workers: set = set()
        # names whose payloads must survive prune_terminal: a done future
        # holding a remote value handle lifted into a later submit's
        # arguments (the dependent has no dep edge the keep-set would see)
        self._pinned: set = set()

    # ------------------------------------------------------------- submit
    def submit(self, name: str, fn: Optional[Callable] = None, *,
               deps=(), meta: Optional[dict] = None, priority: float = 0.0,
               slots: int = 1,
               retry: Optional[RetryPolicy] = None) -> EngineTask:
        """Register a task.  Submit producers before dependents: the task
        server forward-declares an unknown dep as a READY stub and treats
        a later Create of the same name as a no-op (dwork §2.2 semantics),
        so a dependent submitted first would run before its producer.
        In resident mode this is thread-safe and may be called while the
        dispatch loop is running.  `retry` overrides the engine-wide
        `RetryPolicy` for this task."""
        if self.transport == "proc" and fn is not None:
            meta = dict(meta or {})
            if "__call__" not in meta:
                # pack the callable for the worker process NOW: an
                # unpicklable fn raises SerializationError at submit
                # time, naming the task — never opaquely in a worker
                meta["__call__"] = dumps_call(fn, task=name)
        task = EngineTask(name=name, fn=fn, deps=tuple(deps),
                          meta=dict(meta or {}), slots=max(int(slots), 1),
                          priority=priority, retry=retry)
        if not self.resident:
            self.tasks[name] = task
            self.backend.create(name, deps=task.deps, meta=task.meta)
            if self.journal is not None:
                self.journal.append_create(name, task.deps, task.meta)
            if task.deps:
                # deps ride the CREATED event so a saved/exported trace is
                # self-describing for critical-path analysis; dep-less
                # tasks (the dispatch hot path) keep the bare emit
                self.tracer.emit(CREATED, task=name, deps=list(task.deps))
                self._waiting[name] = set(task.deps)
                for d in task.deps:
                    self._succs.setdefault(d, []).append(name)
            else:
                self.tracer.emit(CREATED, task=name)
                self.tracer.emit(READY, task=name)
            return task
        # resident: mailbox enqueue.  The dispatch loop ingests creates in
        # batches at the top of its round (graph registration, failed-dep
        # fail-fast, server Create, _inflight accounting — all on the
        # loop thread), so a submitting client thread never crosses the
        # SERVER lock per task — the cross-thread lock+GIL ping-pong that
        # used to dominate per-future overhead.  The short _cond hold
        # here is cheap (the loop takes _cond per round/batch, not per
        # task) and makes submission atomic w.r.t. prune_terminal's
        # keep-set snapshot.  The task server keys history by name
        # forever, so a duplicate Create is a server-side no-op —
        # accepting one here would count an _inflight slot that never
        # drains and wedge drain()/shutdown(): names are single-use.
        with self._cond:
            if name in self.tasks:
                raise ValueError(f"task name {name!r} already submitted "
                                 "(resident task names are single-use)")
            self.tasks[name] = task
            self._unsent.add(name)
            self._mailbox.append(task)
            self._epoch += 1   # wakes an idle-probing loop immediately
        return task

    def _ingest_mailbox(self):
        """Dispatch-thread ingestion of mailboxed submissions: register
        the engine-side graph, fail-fast tasks whose producer already
        failed, count `_inflight`, then Create server-side — the
        single-writer half of the mailboxed resident submit()."""
        notify = self.on_result
        pending: list = []
        creates: list = []
        emit = self.tracer.emit
        with self._cond:
            while self._mailbox:
                task = self._mailbox.popleft()
                name = task.name
                self._unsent.discard(name)
                if name in self._terminal:
                    continue                      # cancelled before ingest
                live = None
                if task.deps:
                    failed_dep = next((d for d in task.deps
                                       if d in self._failed), None)
                    if failed_dep is not None:
                        # the producer already failed: creating this
                        # server-side would dangle forever (the server
                        # poisons successors at failure time, not at
                        # create time) — fail it engine-side
                        self._terminal.add(name)
                        self._failed.add(name)
                        why = f"dependency {failed_dep} failed"
                        emit(CREATED, task=name, deps=list(task.deps))
                        emit(FAILED, task=name, error=why)
                        j = self.journal
                        if j is not None:
                            j.append_create(name, task.deps, task.meta)
                            j.append_terminal(name, False, why)
                        if notify is not None:
                            pending.append((name, False, None, why))
                        continue
                    live = [d for d in task.deps
                            if d not in self._terminal]
                    if live:
                        self._waiting[name] = set(live)
                        for d in live:
                            self._succs.setdefault(d, []).append(name)
                self._inflight += 1
                creates.append((task, not live))
            if self._inflight <= 0:
                self._cond.notify_all()   # every ingested task failed fast
        if creates:
            self.backend.create_many(
                [(t.name, t.deps, t.meta) for t, _ in creates])
            # CREATED/READY stamped here, on the loop thread, so a
            # submitting client thread adds no events (and no span) of
            # its own — the dispatch window stays the measured quantity,
            # exactly as on the batch path where creation precedes run()
            j = self.journal
            for task, ready in creates:
                if j is not None:
                    j.append_create(task.name, task.deps, task.meta)
                if task.deps:
                    emit(CREATED, task=task.name, deps=list(task.deps))
                else:
                    emit(CREATED, task=task.name)
                if ready:
                    emit(READY, task=task.name)
        for note in pending:
            notify(*note)

    def _on_terminal_unlocked(self, name: str):
        if name not in self._succs:
            return
        for succ in self._succs.pop(name):
            w = self._waiting.get(succ)
            if w is None:
                continue
            w.discard(name)
            if not w:
                del self._waiting[succ]
                self.tracer.emit(READY, task=succ)

    def _note_terminal_many(self, batch: list):
        """Terminal bookkeeping for `(name, ok, res, error)` entries under
        ONE lock hold: count each task's FIRST terminal state so `drain()`
        can wait on the submitted universe, ready a success's successors,
        and deliver each to `on_result` exactly once.  A failure walks the
        engine-side successor graph the way the server poisons its own,
        so transitively-doomed tasks count as terminal too.  The dispatch
        loop calls this once per drained steal batch, so the lock
        ping-pong with a submitting client thread amortizes over
        `steal_n` tasks (measurably so: per-future client overhead).
        Notifications fire after the lock is released (the handler may
        call back into the engine)."""
        notify = self.on_result
        want = notify is not None
        pending: list = []
        with span("engine.notify"):
            with self._cond:
                n = 0
                for name, ok, res, error in batch:
                    if ok:
                        self._on_terminal_unlocked(name)
                    n += self._note_locked(name, ok, res, error, pending,
                                           want)
                self._inflight -= n
                if self._inflight <= 0:
                    self._cond.notify_all()
            for note in pending:
                notify(*note)

    def _note_locked(self, name: str, ok: bool, res, error,
                     pending: list, want: bool) -> int:
        """Shared terminal-transition body (caller holds `_cond`): returns
        how many tasks reached terminal (1 + poisoned successors), and
        appends `on_result` notifications to `pending` when `want`.  A
        name absent from the task registry is a resurrected server stub
        (a pruned name re-declared as a dependency): it is remembered as
        terminal so it can't loop, but contributes no inflight count and
        no notification — it was never a submitted task."""
        if name in self._terminal:
            return 0
        self._terminal.add(name)
        self._attempts.pop(name, None)      # bounded retry state
        known = name in self.tasks
        if error is None and res is not None:
            error = res.error
        if want and known:
            pending.append((name, ok, res, error))
        j = self.journal
        if j is not None:
            if ok:
                j.append_terminal(name, True)
            elif error == "cancelled" and res is None:
                j.append_cancel(name)
            else:
                j.append_terminal(name, False, error)
        n = 1 if known else 0
        if not ok:
            self._failed.add(name)
            stack = [name]
            while stack:
                for succ in self._succs.pop(stack.pop(), []):
                    self._waiting.pop(succ, None)
                    if succ in self._terminal:
                        continue
                    self._terminal.add(succ)
                    self._failed.add(succ)
                    self._attempts.pop(succ, None)
                    why = f"poisoned by {name}"
                    self.tracer.emit(FAILED, task=succ, error=why)
                    if j is not None:
                        j.append_terminal(succ, False, why)
                    if want:
                        pending.append((succ, False, None, why))
                    n += 1
                    stack.append(succ)
        return n

    # ---------------------------------------------------- resident control
    def start(self, execute: Optional[Callable] = None, *,
              pass_worker: bool = False) -> "Engine":
        """Launch the dispatch loop in a background thread (resident mode
        only).  `execute(name, meta)` as in `run()`; with
        `pass_worker=True` the callback receives `(name, meta, worker)` so
        per-worker behavior (runtime.elastic) needs no engine surgery."""
        if not self.resident:
            raise RuntimeError("start() requires Engine(resident=True); "
                               "use run() for batch mode")
        if self._thread is not None:
            raise RuntimeError("engine already started")
        self._stop = self._abort = False
        self._report = None
        self._loop_error = None
        self._thread = threading.Thread(
            target=self._serve, args=(execute, pass_worker),
            name="engine-resident", daemon=True)
        self._thread.start()
        return self

    def _serve(self, execute, pass_worker):
        try:
            self._report = self.run(execute, pass_worker=pass_worker)
        except BaseException as e:  # noqa: BLE001 — surfaced by shutdown()
            self._loop_error = e
        finally:
            with self._cond:
                self._cond.notify_all()   # unblock drain() on a loop crash
            if self._loop_error is not None \
                    and self.on_loop_error is not None:
                try:
                    self.on_loop_error(self._loop_error)
                except Exception:    # noqa: BLE001 — the loop is already
                    pass             # dead; shutdown() reports the cause

    def drain(self, timeout: Optional[float] = None) -> bool:
        """Block until every submitted task is terminal (True) or the
        timeout expires (False).  Does not stop the loop.  With a
        journal attached, a successful drain syncs it — "drained" then
        also means "durable"."""
        with self._cond:
            ok = self._cond.wait_for(
                lambda: (self._inflight <= 0 and not self._mailbox)
                or self._loop_error is not None,
                timeout)
        if ok and self.journal is not None:
            self.journal.sync()
        return ok

    def shutdown(self, *, drain: bool = True,
                 timeout: Optional[float] = None) -> Optional[EngineReport]:
        """Stop the resident loop and return its EngineReport.  With
        `drain=True` (default) outstanding work finishes first; with
        `drain=False` pending work is abandoned (the server keeps it).
        Idempotent: shutting down a resident engine that was never
        started is a no-op returning None, and a second shutdown() is a
        no-op returning the first call's report — `Client.__exit__` and
        finalizers can call it unconditionally."""
        if not self.resident:
            raise RuntimeError("shutdown() requires Engine(resident=True); "
                               "batch mode returns its report from run()")
        if self._thread is None:
            return self._report
        if drain:
            self.drain(timeout)
        else:
            self._abort = True
        self._stop = True
        self._thread.join(timeout)
        if self._thread.is_alive():      # wedged mid-drain: force exit
            self._abort = True
            self._thread.join(timeout)
            if self._thread.is_alive():
                # execute() is blocked and cannot observe the abort flag;
                # keep the handle so a later shutdown() can retry, and
                # report the bounded stop honestly instead of hanging
                raise RuntimeError(
                    "resident loop did not stop within timeout "
                    "(execute blocked?)")
        self._thread = None
        if self._loop_error is not None:
            raise self._loop_error
        return self._report

    @property
    def started(self) -> bool:
        """True while the resident dispatch loop is running."""
        return self._thread is not None

    def live_workers(self) -> int:
        """Workers currently alive (pool size minus deaths) — the P that
        METG-aware batching should adapt to."""
        return max(self._live, 0)

    def tasks_done_total(self) -> int:
        """Task executions that reached COMPLETED/FAILED on a worker.
        Requeued re-executions count each time: this is the throughput
        counter the windowed tasks/s rate diffs, not the terminal-name
        count (`OverheadReport.n_tasks`)."""
        return sum(st[0] for st in list(self._wstats.values()))

    def dep_table(self) -> dict:
        """Monitoring snapshot of the dependency graph: task name ->
        tuple of dependency names, for every registered task that has
        dependencies.  Read under the GIL (approximate while the loop
        runs, like `worker_stats`); the critical-path analyzer
        (`repro.core.obs.critical_path`) joins it against the trace —
        exported traces carry the same edges on their CREATED events."""
        return {n: t.deps for n, t in list(self.tasks.items()) if t.deps}

    def worker_stats(self) -> dict:
        """Monitoring snapshot: worker -> {done, busy_s, alive}.  Read
        under the GIL from the loop's own tables — approximate while the
        loop runs, never blocking it.  `busy_s` sums real execution time
        (TaskResult t_start..t_end), so diffing two snapshots gives a
        per-worker busy fraction over the window."""
        dead = self._dead_workers
        return {w: {"done": st[0], "busy_s": st[1], "alive": w not in dead}
                for w, st in list(self._wstats.items())}

    def add_worker(self, name: Optional[str] = None) -> str:
        """Grow the live pool (resident mode): the worker joins the steal
        rotation at the top of the next dispatch round."""
        if not self.resident:
            raise RuntimeError("membership changes require "
                               "Engine(resident=True)")
        if name is None:
            name = f"w{self._next_wid}"
        self._next_wid += 1
        with self._cond:
            self._commands.append(("add", name))
            self._epoch += 1
        return name

    def lose_worker(self, name: str):
        """Driver-side failure detection (paper: Exit may be called by the
        user to recover from a node failure): mark the worker dead and
        recycle everything it still holds."""
        if not self.resident:
            raise RuntimeError("membership changes require "
                               "Engine(resident=True)")
        with self._cond:
            self._commands.append(("lose", name))
            self._epoch += 1

    def cancel(self, name: str) -> bool:
        """Withdraw a submitted task that no worker has stolen yet.  True
        means the task will never run: the server poisons it (and its
        transitive successors) under its own lock, so a concurrent Steal
        can never hand it out afterwards.  False means the cancel lost
        the race — the task is already stolen, terminal, or unknown.
        Cancellation counts as a failure terminal state: dependents are
        poisoned, drain() unblocks, and on_result fires with
        error=\"cancelled\"."""
        notify = self.on_result
        pending: list = []
        withdrawn = False
        with self._cond:
            if name not in self.tasks or name in self._terminal:
                return False
            if name in self._unsent:
                # still in the mailbox: withdraw it engine-side before the
                # loop ever ingests it (ingest skips terminal names).  The
                # withdrawn task itself was never counted in _inflight
                # (counting happens at ingest), but poisoned successors in
                # the walk WERE ingested — a dependent that forward-
                # declared this name as a string dep — so n-1 of the walk
                # must be decremented.  Unsent successors are not in
                # _succs and fail fast at their own ingest via _failed.
                self._unsent.discard(name)
                self.tracer.emit(CANCELLED, task=name)
                n = self._note_locked(name, False, None, "cancelled",
                                      pending, notify is not None)
                self._inflight -= (n - 1)
                if self._inflight <= 0:
                    self._cond.notify_all()
                withdrawn = True
        if withdrawn:
            for note in pending:
                notify(*note)
            return True
        if not self.backend.cancel(name):
            return False
        self.tracer.emit(CANCELLED, task=name)
        self._note_terminal_many([(name, False, None, "cancelled")])
        return True

    def prune_terminal(self, *, backend: bool = True) -> int:
        """Bounded-state hook: drop terminal tasks from the engine-side
        history tables (tasks/_terminal/_failed) and, with `backend=True`,
        from the server's tables too.  Names still referenced as
        dependencies by a not-yet-ingested (mailboxed) submission are
        kept, so auto-pruning (`Client(prune_every=)`) cannot race a
        concurrent submit into resurrecting a pruned dep as a READY
        stub.  Beyond that, the contract matches
        `TaskServer.prune_terminal`: only prune names that no FUTURE
        submit will reference as a dependency (single-use names — the
        futures client and serving frontend satisfy this).  Returns the
        number of entries dropped across both layers."""
        with self._cond:
            keep: set = set(self._pinned)
            for task in self._mailbox:
                keep.update(task.deps)
            if self.transport == "proc":
                # a worker may still Fetch a dependency VALUE for any
                # in-flight dependent — keep those payloads fetchable
                for n, t in self.tasks.items():
                    if t.deps and n not in self._terminal:
                        keep.update(t.deps)
            prunable = [n for n in self._terminal
                        if n not in self._succs and n not in keep]
            for n in prunable:
                self._terminal.discard(n)
                self._failed.discard(n)
                self.tasks.pop(n, None)
            # the backend half runs under the same hold: submit() also
            # takes _cond, so no submission can slip a new dep reference
            # in while the server tables are being scanned with this
            # keep-set (lock order engine._cond -> server.lock is used
            # nowhere in reverse)
            n_backend = (self.backend.prune_terminal(keep=keep)
                         if backend else 0)
        return len(prunable) + n_backend

    def pin(self, name: str):
        """Exempt `name`'s payload from prune_terminal: a terminal task
        whose (remote) value is lifted into a later submission's
        arguments has no dependency edge the prune keep-set would see —
        the worker resolving the new task must still be able to Fetch
        it (the futures client pins lifted remote values)."""
        with self._cond:
            self._pinned.add(name)

    # ----------------------------------------------------------- recovery
    @classmethod
    def recover(cls, journal_dir, **engine_kw) -> "Engine":
        """Rebuild an engine from a journal directory after a crash.

        Replays checkpoint + WAL into the control-plane state, then:

          * terminal names (completed / failed / cancelled) seed the
            exactly-once accounting — they never re-run, never re-fire
            `on_result`, and dependents treat completed producers as
            satisfied;
          * every created-but-not-terminal task is re-submitted with its
            surviving dependencies, which re-marks leased-but-unfinished
            work from the crashed run as ready (the journal records no
            leases: an assignment that never completed is work to redo);
          * a pending task whose producer failed before the crash is
            poisoned immediately, exactly as the live engine would have.

        The returned engine journals into the SAME directory (appends
        continue where the crashed run stopped), so a recovered session
        is itself recoverable.  Task `fn` closures are not journaled —
        run the recovered engine with an `execute(name, meta)` callback
        (the by-name style of the dwork/pmake adapters), carrying
        whatever the callback needs in each task's `meta`.

        `engine_kw` is forwarded to the constructor (workers, transport,
        shards, resident=..., retry=..., ...).  Works with all three
        backends: recovery happens above the backend, which starts
        empty and receives the re-created universe."""
        state = Journal.replay(journal_dir)
        eng = cls(journal=str(journal_dir), **engine_kw)
        eng._recovered = state
        terminal = state.terminal()
        eng._terminal |= terminal
        eng._failed.update(state.failed)
        eng._failed.update(state.cancelled)
        completed = state.completed
        journal = eng.journal
        for name, deps, meta in state.pending():
            live = tuple(d for d in deps if d not in completed)
            bad = next((d for d in live if d in eng._failed), None)
            if bad is not None:
                eng._terminal.add(name)
                eng._failed.add(name)
                why = f"dependency {bad} failed"
                eng.tracer.emit(FAILED, task=name, error=why)
                journal.append_terminal(name, False, why)
                continue
            eng.submit(name, deps=live, meta=meta)
        return eng

    # -------------------------------------------------------------- exec
    def _execute_registered(self, name: str, meta: dict):
        task = self.tasks.get(name)
        if task is None or task.fn is None:
            return (True, None)
        return (True, task.fn())

    def _run_one(self, exec_fn, name: str, meta: dict,
                 worker: str) -> TaskResult:
        tracer = self.tracer
        with span("engine.run", task=name):
            tracer.emit4(RUN_START, name, worker)
            t0 = time.perf_counter()
            ok, value, err, crashed = True, None, None, False
            try:
                if self._pass_worker:
                    out = exec_fn(name, meta, worker)
                else:
                    out = exec_fn(name, meta)
                if isinstance(out, tuple):
                    ok, value = bool(out[0]), out[1]
                elif out is None:
                    ok = True
                elif isinstance(out, bool):
                    ok = out
                else:
                    ok, value = True, out
            except WorkerCrash as e:
                ok, err, crashed = False, repr(e), True
            except Exception as e:                    # noqa: BLE001
                ok, err = False, repr(e)
            t1 = time.perf_counter()
            virtual = 0.0
            if self.faults is not None:
                virtual = self.faults.delay_s(name, worker)
                if self.faults.force_fail(name, worker,
                                          self._attempts.get(name, 0)):
                    ok, err = False, err or "injected fault"
                tracer.emit(RUN_END, task=name, worker=worker,
                            virtual_s=virtual)
            else:
                tracer.emit4(RUN_END, name, worker)
        return TaskResult(task=name, ok=ok, worker=worker, t_start=t0,
                          t_end=t1, value=value, error=err,
                          virtual_s=virtual, crashed=crashed)

    # --------------------------------------------------------------- run
    def run(self, execute: Optional[Callable] = None, *,
            pass_worker: bool = False) -> EngineReport:
        """Run until every task reaches a terminal state (or all workers
        die / the pool stalls).  `execute(name, meta)` may return bool,
        (ok, value), or None (success); default runs the submitted `fn`.
        In resident mode the loop instead runs until `shutdown()`."""
        resident = self.resident
        t_wall0 = time.perf_counter()
        # per-run state the shared steps and the transport step share
        self._alive = [f"w{i}" for i in range(self.workers)]
        self._dead_workers = set()           # monitoring view (GIL reads)
        for w in self._alive:
            self._wstats.setdefault(w, [0, 0.0])
        self._live = self._peak = len(self._alive)
        self._results = {}
        self._record_results = self.keep_results or not resident
        # terminal accounting runs in resident mode (drain bookkeeping)
        # and whenever a result listener OR a journal is attached (the
        # journal records terminal transitions at the same chokepoint);
        # `_terminal` then doubles as the duplicate-completion guard so
        # `keep_results=False` sessions stay exactly-once too
        self._noting = (resident or self.on_result is not None
                        or self.journal is not None)
        self._seen = self._terminal if self._noting else ()
        self._pass_worker = pass_worker and execute is not None
        step = (ProcRounds if self.transport == "proc" else InlineRounds)(
            self, execute)
        self._step = step
        stalled = False
        idle_rounds = 0
        try:
            while True:
                with span("engine.round"):
                    self._progress = False
                    stopping = not resident or self._stop
                    if resident:
                        if self._abort:
                            break
                        if self._mailbox:
                            with span("engine.ingest"):
                                self._ingest_mailbox()
                            self._progress = True
                        if self._commands:
                            self._apply_commands()
                    step.round(stopping)
                    # termination (batch mode, or resident after shutdown())
                    if stopping and step.quiet():
                        if self._live <= 0:
                            # every worker died: unless the work was
                            # already drained, that is a stall, not a
                            # clean finish.  A resident pool counts its
                            # submitted universe instead (it may
                            # legitimately stop with zero workers).
                            stalled = (self._inflight > 0
                                       or bool(self._mailbox)) \
                                if resident else not step.drained()
                            break
                        if step.drained():
                            break
                    if self._progress:
                        idle_rounds = 0
                        continue
                    idle_rounds += 1
                    if idle_rounds >= self.max_idle_rounds and stopping:
                        # nothing moved for max_idle_rounds: only work
                        # that can no longer finish is a stall (a cycle,
                        # or every lease held by a dead worker) — running
                        # tasks and retry backoffs are just waiting
                        if not step.in_flight():
                            stalled = True
                            break
                        idle_rounds = 0
                    with span("engine.idle"):
                        time.sleep(self.poll)
        finally:
            step.close()
            journal = self.journal
            if journal is not None:
                # a clean exit is fully durable; an owned journal (built
                # from a path) is closed with the loop
                journal.sync()
                if self._owns_journal:
                    journal.close()
            if self._owns_backend:
                # in the finally so a mid-run RPC failure can't leak the
                # tree's sockets/threads or the proc listener; stats()/
                # errors() below only read in-process state
                self.backend.close()
        return EngineReport(
            results=self._results, trace=self.tracer,
            workers=max(step.parallelism(), 1),
            pool_workers=max(self._peak, 1),
            wall_s=time.perf_counter() - t_wall0,
            errors=self.backend.errors(), stalled=stalled,
            backend_stats=self.backend.stats())

    # ------------------------------------------------ shared round steps
    def _finish(self, w: str, res: TaskResult, notes: Optional[list]):
        """Record an executed task's end — the one terminal step of every
        transport: the worker's stats, the result (when kept),
        `exec_failed`, the COMPLETED or FAILED event, then the terminal
        note.  A caller that batches passes `notes` and hands the list
        to `_note_terminal_many` once (one lock hold per batch); else the
        note is taken here, or with no terminal accounting on, a success
        just readies its successors."""
        name, ok = res.task, res.ok
        st = self._wstats[w]
        st[0] += 1
        st[1] += res.t_end - res.t_start
        if self._record_results:
            self._results[name] = res
        if ok:
            self.tracer.emit4(COMPLETED, name, w)
        else:
            self.exec_failed += 1
            self.tracer.emit(FAILED, task=name, worker=w, error=res.error)
        if notes is not None:
            notes.append((name, ok, res, None))
        elif self._noting:
            self._note_terminal_many([(name, ok, res, None)])
        elif ok:        # failed tasks never ready their successors
            with span("engine.notify"):
                self._on_terminal_unlocked(name)

    def _done(self, name: str) -> bool:
        """Has `name` reached its terminal state in this run?  A
        completion that arrives again after a requeue is dropped or
        reported, never re-recorded."""
        return name in self._results or name in self._seen

    def _apply_commands(self):
        """Membership commands queued by add_worker()/lose_worker()."""
        with self._cond:
            cmds = list(self._commands)
            self._commands.clear()
        for cmd, w in cmds:
            if cmd == "add":
                self._join(w, add=True)
            elif w in self._alive and w not in self._dead_workers:
                self._bury(w, reason="lose")

    def _join(self, w: str, *, add: bool = False):
        """Fold a worker into the live pool: a new one (add_worker, or a
        process whose Hello arrived — a CLI worker's Hello is
        add_worker-on-connect), or a recovered node rejoining under its
        old id, which revives with a clean slate."""
        alive, dead = self._alive, self._dead_workers
        if w in alive and w not in dead:
            return                                    # already live
        dead.discard(w)
        if w not in alive:
            alive.append(w)
        self._wstats.setdefault(w, [0, 0.0])
        self._live = len(alive) - len(dead)
        self._peak = max(self._peak, self._live)
        self._progress = True
        self._step.joined(w, add)

    def _bury(self, w: str, *, announce: bool = True, **extra):
        """Retire a dead worker mid-stream — a crash, an injected death,
        lose_worker(), or a process that exited or went stale: count and
        trace the death, then let the transport recycle what the worker
        held (an announced Exit requeues its assignment; a silent death
        relies on heartbeat-lease expiry)."""
        self._dead_workers.add(w)
        self.worker_deaths += 1
        self.tracer.emit(WORKER_DEAD, worker=w, **extra)
        self._step.buried(w, announce)
        self._live = len(self._alive) - len(self._dead_workers)
        self._progress = True
        if self.resident:
            self._epoch += 1     # its requeued work is stealable again

    def _retry_policy(self, name: str) -> Optional[RetryPolicy]:
        task = self.tasks.get(name)
        if task is not None and task.retry is not None:
            return task.retry
        return self.retry

    def _will_retry(self, name: str, err: Optional[str]) -> bool:
        """Would `_retry` re-run this failure?  Charges nothing: the proc
        backend asks it on its handler threads (GIL-grade dict reads)."""
        pol = self._retry_policy(name)
        return pol is not None and pol.should_retry(
            self._attempts.get(name, 0) + 1, err)

    def _retry(self, name: str, w: str, err: Optional[str]):
        """Charge a failed execution against its RetryPolicy.  None: fail
        for real.  Else the retry is counted and traced, and the backoff
        before the re-run is returned."""
        pol = self._retry_policy(name)
        if pol is None:
            return None
        attempt = self._attempts.get(name, 0) + 1
        self._attempts[name] = attempt
        if not pol.should_retry(attempt, err):
            return None
        delay = pol.delay_s(name, attempt)
        self.retries_total += 1
        self.tracer.emit(RETRIED, task=name, worker=w, attempt=attempt,
                         delay_s=delay)
        return delay

    # ------------------------------------------------------- proc transport
    @property
    def comm_address(self) -> Optional[str]:
        """Where `python -m repro.core.engine.comm.worker --connect` dials
        (`tcp://host:port`) — None for in-process transports."""
        return getattr(self.backend, "address", None)

    def worker_pids(self) -> dict:
        """worker -> OS pid for every handshaken worker process
        (transport="proc"; empty for in-process transports)."""
        fn = getattr(self.backend, "worker_pids", None)
        return fn() if fn is not None else {}

    def wait_workers(self, n: Optional[int] = None,
                     timeout: float = 30.0) -> bool:
        """Block until `n` workers (default: the configured pool size)
        have completed their Hello handshake.  True once reached; in-
        process transports return True immediately (workers are the
        dispatch loop itself)."""
        fn = getattr(self.backend, "worker_pids", None)
        if fn is None:
            return True
        want = self.workers if n is None else int(n)
        deadline = time.monotonic() + timeout
        while len(fn()) < want:
            if time.monotonic() >= deadline:
                return False
            time.sleep(0.01)
        return True

    @property
    def xfer_totals(self) -> dict:
        """Per-path [count, bytes, seconds] of dependency-value
        transfers (the data plane's live totals; zeros outside proc)."""
        return self.data_plane.totals

    @property
    def xfer_lost_total(self) -> int:
        """Lost-value recomputes the data plane has issued."""
        return self.data_plane.lost_total

    @property
    def xfer_metrics(self):
        """The obs sink every transfer is observed into (None: off)."""
        return self.data_plane.metrics

    @xfer_metrics.setter
    def xfer_metrics(self, sink):
        self.data_plane.metrics = sink

    # ------------------------------------------------------------ helpers
    def _priority_of(self, name: str, meta: dict) -> float:
        task = self.tasks.get(name)
        if task is not None:
            return task.priority
        return float(meta.get("priority", 0.0)) if meta else 0.0

    def _slots_of(self, name: str, meta: dict) -> int:
        task = self.tasks.get(name)
        if task is not None:
            return task.slots
        return int(meta.get("slots", 1)) if meta else 1

