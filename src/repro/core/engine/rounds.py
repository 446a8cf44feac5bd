"""The transport steps of the engine's one dispatch loop.

`Engine.run` (executor.py) makes one call a round that differs by
transport, `step.round(stopping)`; the step answers the loop's shared
steps through a few hooks: `joined` / `buried` (after the engine's own
membership bookkeeping), `quiet` / `drained` / `in_flight` (the
termination and stall test), `parallelism` and `close`.  A step works on
the engine's per-run state and calls its shared steps (`_finish`,
`_bury`, `_join`, `_retry`); it keeps no bookkeeping another transport
also needs.
"""
from __future__ import annotations

import time
from concurrent.futures import ThreadPoolExecutor
from heapq import heapify, heappop, heappush

from repro.core.engine.backends import DONE, EMPTY
from repro.core.engine.model import REQUEUED, STOLEN, TaskResult
from repro.core.engine.tracing import span

# resident idle backoff: with no pending submissions, each worker probes
# the server once per this many rounds (lease reaping still happens on
# probes); a submit() bumps the epoch and re-enables steals immediately
IDLE_PROBE_ROUNDS = 16


class InlineRounds:
    """inproc, thread and tree: the dispatch loop's own virtual
    workers complete+steal, run what they stole, and die on plan."""

    def __init__(self, eng, execute):
        self.eng = eng
        self.exec_fn = execute or eng._execute_registered
        alive = eng._alive
        self.steals = {w: 0 for w in alive}
        self.done_flag = {w: False for w in alive}
        self.outstanding = {w: 0 for w in alive}  # stolen, not yet finished
        self.finished = {w: [] for w in alive}    # (name, ok) to piggyback
        # hot-path state, all maintained incrementally (no per-round scans):
        self.heap: list = []            # (-priority, seq, item) to launch
        self.n_pending = 0
        self.pending_names: set = set()
        self.running: dict = {}         # thread transport in-flight
        self.free = eng.capacity
        self.seq = 0
        self.rounds = 0
        self.quiet_epoch = -1           # resident idle gate (IDLE_PROBE_...)
        # launch gate: popping the heap is pointless until something can
        # change the outcome (a slot freed, new steals, a death scrub) —
        # without it a full backlog gets drained/re-pushed every poll
        self.try_launch = True
        self.backoff_wait = False       # heap entries held for a backoff
        self.inline = eng.transport != "thread"
        self.pool = (None if self.inline
                     else ThreadPoolExecutor(max_workers=eng.capacity))
        # fault-free inline runs drain a priority-0 batch straight from
        # the steal response — no heap round-trip, no pending bookkeeping.
        # (With faults the slow path keeps the steal->death->launch window
        # so a dying worker observably holds stolen-but-unstarted tasks.)
        self.fast_drain = self.inline and eng.faults is None

    def round(self, stopping: bool):
        eng = self.eng
        self.rounds += 1
        self.backoff_wait = False
        alive, dead = eng._alive, eng._dead_workers
        n_alive = max(len(alive), 1)
        if eng.resident:
            # steal_n is re-read every round so membership-aware
            # batching (elastic: pick_batch_size on remesh) applies
            # without restarting the loop
            steal_n = max(int(eng.steal_n), 1)
            epoch0 = eng._epoch
            steal_ok = (stopping or epoch0 != self.quiet_epoch
                        or self.rounds % IDLE_PROBE_ROUNDS == 0)
        else:
            steal_n, steal_ok = eng.steal_n, True
        pending_limit = n_alive * steal_n + eng.capacity
        # 1) reap finished thread-pool tasks into per-worker batches
        if self.running:
            self._reap()
        # 2) complete+steal — one RPC flushes a worker's finished batch
        # AND steals its next one (Fig. 2 batch-then-drain); a worker
        # steals only while it holds fewer than steal_n outstanding
        # tasks; rotation keeps the order fair
        if n_alive == 1:
            rotation = alive
        else:
            start = self.rounds % n_alive
            rotation = alive[start:] + alive[:start]
        finished, outstanding = self.finished, self.outstanding
        done_flag, running = self.done_flag, self.running
        pending_names = self.pending_names
        results, seen = eng._results, eng._seen
        complete_steal = eng.backend.complete_steal
        priority_of = eng._priority_of
        emit4, run_one, finish = eng.tracer.emit4, eng._run_one, eng._finish
        exec_fn = self.exec_fn
        for w in rotation:
            if w in dead:
                continue
            batch = finished[w]
            want_steal = (steal_ok
                          and not done_flag[w]
                          and outstanding[w] < steal_n
                          and self.n_pending < pending_limit)
            if not batch and not want_steal:
                continue
            with span("engine.steal"):
                got = complete_steal(w, batch, steal_n if want_steal else 0)
            if batch:
                finished[w] = []
                eng._progress = True
            if not want_steal:
                continue
            if got == DONE:
                # resident pre-stop: the server saying "all done" just
                # means "idle right now" — more work may be submitted,
                # so keep the worker in the rotation
                if stopping:
                    done_flag[w] = True
                continue
            if got == EMPTY:
                continue
            self.steals[w] += len(got)
            accepted = []
            for name, meta in got:
                rec = running.get(name)
                if (name in pending_names
                        or (rec is not None and rec["worker"] not in dead)):
                    # duplicate steal after a lease-expiry requeue while a
                    # LIVE copy is still held (pending or in flight): the
                    # copy's Complete clears every stale assignment
                    # server-side, so just drop it.  A copy held only by a
                    # DEAD worker is accepted — its completion was
                    # discarded, so this re-steal is the only way forward.
                    continue
                prior = results.get(name)
                if prior is not None or name in seen:
                    # already terminal engine-side: a stale requeue
                    # duplicate with no live copy, or a pruned name a later
                    # dep re-declared as a server stub — report its
                    # terminal state instead of dropping it, so the
                    # server's join accounting (and any dependents) can
                    # move.  Never re-execute.
                    ok_prior = (prior.ok if prior is not None
                                else name not in eng._failed)
                    finished[w].append((name, ok_prior))
                    eng._progress = True
                    continue
                accepted.append((name, meta))
            if not accepted:
                continue
            eng._progress = True
            # drain a batch inline ONLY when nothing in it (or already
            # pending) carries a priority — otherwise a prio-0 item would
            # run before a higher-priority one later in the same batch/heap
            if not (self.fast_drain and not self.heap and all(
                    priority_of(name, meta) == 0.0
                    for name, meta in accepted)):
                for name, meta in accepted:
                    emit4(STOLEN, name, w)
                    outstanding[w] += 1
                    self._push(name, meta, w)
                continue
            # steal order == seq order: each completion rides on this
            # worker's next CompleteSteal.  With terminal accounting on,
            # the notes are batched: ONE lock hold for the whole drained
            # batch, amortizing the client-thread lock ping-pong over
            # steal_n
            done = finished[w]
            notes = [] if eng._noting else None
            for name, meta in accepted:
                emit4(STOLEN, name, w)
                res = run_one(exec_fn, name, meta, w)
                if res.crashed:
                    # the rest of the batch is still assigned server-side:
                    # Exit recycles it with the in-flight task
                    eng._bury(w, crash=True)
                    break
                if not res.ok and self._retried(w, res, meta):
                    # the fast path never counted this steal in
                    # outstanding: the heap re-enqueue must
                    outstanding[w] += 1
                    continue
                done.append((name, res.ok))
                finish(w, res, notes)
            if notes:
                eng._note_terminal_many(notes)
        # resident idle gate: a fully quiet round (no completions, no
        # steals served) arms the backoff until the epoch moves
        if eng.resident and not stopping and not eng._progress and steal_ok:
            self.quiet_epoch = epoch0
        # 3) fault injection: worker deaths (between steal & launch, so a
        #    dying worker holds stolen-but-unstarted tasks)
        faults = eng.faults
        if faults is not None:
            for w in alive:
                if w not in dead and faults.should_die(w, self.steals[w]):
                    silent = faults.dies_silently(w)
                    # announced death: Exit recycles assignment; silent
                    # death: heartbeat-lease expiry recycles
                    eng._bury(w, announce=not silent, silent=silent)
        # 4) launch: greedy highest-priority-first into free slots
        if self.heap and self.try_launch:
            self._launch()

    def _retried(self, w: str, res: TaskResult, meta) -> bool:
        # a transiently-failed execution is re-enqueued onto the launch
        # heap with a not-before stamp (seeded-jitter backoff) instead of
        # reporting Complete(ok=False): the worker keeps its scheduler-
        # side assignment, so a retry costs zero protocol round-trips
        delay = self.eng._retry(res.task, w, res.error)
        if delay is None:
            return False
        self.eng._wstats[w][1] += res.t_end - res.t_start
        self._push(res.task, meta, w, time.perf_counter() + delay)
        return True

    def _settle(self, w: str, res: TaskResult, meta):
        """A launched (heap or thread-pool) execution's end."""
        if res.crashed:
            self.eng._bury(w, crash=True)
        elif res.ok or not self._retried(w, res, meta):
            self.outstanding[w] -= 1
            self.finished[w].append((res.task, res.ok))
            self.eng._finish(w, res, None)

    def _push(self, name: str, meta, w: str, t_ready=None):
        eng = self.eng
        self.seq += 1
        heappush(self.heap, (
            -eng._priority_of(name, meta), self.seq,
            {"name": name, "meta": meta, "worker": w,
             "slots": eng._slots_of(name, meta), "t_ready": t_ready}))
        self.pending_names.add(name)
        self.n_pending += 1
        self.try_launch = True

    def _reap(self):
        eng = self.eng
        running = self.running
        for name in [n for n, r in running.items() if r["fut"].done()]:
            rec = running.pop(name)
            self.free += rec["slots"]
            eng._progress = True
            self.try_launch = True
            w = rec["worker"]
            if w in eng._dead_workers:
                continue          # lost completion: requeued via Exit
            self._settle(w, rec["fut"].result(), rec["meta"])

    def _launch(self):
        eng = self.eng
        heap, dead = self.heap, eng._dead_workers
        self.try_launch = False
        held = []
        while heap:
            entry = heappop(heap)
            it = entry[2]
            name, w = it["name"], it["worker"]
            if w in dead:                             # late scrub
                self.pending_names.discard(name)
                self.n_pending -= 1
                continue
            t_ready = it["t_ready"]
            if t_ready is not None and t_ready > time.perf_counter():
                held.append(entry)            # retry backoff pending
                self.backoff_wait = True
                continue
            if name in self.running:
                # a dead worker's copy is still in flight; wait for it to
                # drain before re-launching
                held.append(entry)
                continue
            slots = min(it["slots"], eng.capacity)
            if slots > self.free:
                held.append(entry)
                continue
            self.pending_names.discard(name)
            self.n_pending -= 1
            eng._progress = True
            if not self.inline:
                self.free -= slots
                fut = self.pool.submit(eng._run_one, self.exec_fn, name,
                                       it["meta"], w)
                self.running[name] = {"worker": w, "fut": fut,
                                      "slots": slots, "meta": it["meta"]}
                continue
            # a crash's _bury scrubs this worker's remaining heap
            # entries; `held` is re-checked next pass
            self._settle(w, eng._run_one(self.exec_fn, name, it["meta"], w),
                         it["meta"])
        for entry in held:
            heappush(heap, entry)
        if self.backoff_wait:
            # a held backoff entry needs another launch pass once its
            # deadline arrives, whatever else the round did
            self.try_launch = True

    # ------------------------------------------------ shared-step hooks
    def joined(self, w: str, add: bool):
        # only copies still in flight from an old incarnation stay
        # attributed to a revived worker
        self.steals.setdefault(w, 0)
        self.done_flag[w] = False
        self.finished[w] = []
        self.outstanding[w] = sum(1 for r in self.running.values()
                                  if r["worker"] == w)

    def buried(self, w: str, announce: bool):
        # flush the completions the dead worker already reported (a
        # result the engine recorded is never lost), Exit it when its
        # death is announced, and scrub its pending launches
        eng = self.eng
        if self.finished[w]:
            eng.backend.complete_steal(w, self.finished[w], 0)
            self.finished[w] = []
        if announce:
            eng.backend.exit_worker(w)
        heap, dead = self.heap, eng._dead_workers
        if heap:
            kept = [e for e in heap if e[2]["worker"] not in dead]
            if len(kept) != len(heap):
                for e in heap:
                    if e[2]["worker"] in dead:
                        self.pending_names.discard(e[2]["name"])
                heap[:] = kept
                heapify(heap)
                self.n_pending = len(heap)
        self.try_launch = True

    def quiet(self) -> bool:
        return not self.running and not self.n_pending

    def drained(self) -> bool:
        # every live worker saw the server's DONE and flushed its
        # completions; with none alive, one of them saw DONE first
        dead = self.eng._dead_workers
        live = [w for w in self.eng._alive if w not in dead]
        if not live:
            return any(self.done_flag.values())
        return (all(self.done_flag[w] for w in live)
                and not any(self.finished[w] for w in live))

    def in_flight(self) -> bool:
        return bool(self.running) or self.backoff_wait

    def parallelism(self) -> int:
        # the inline transports run tasks serially, and the thread pool
        # is sized by `capacity`, so overhead accounting must not
        # multiply wall time by phantom workers
        return 1 if self.inline else min(self.eng._peak, self.eng.capacity)

    def close(self):
        if self.pool is not None:
            self.pool.shutdown(wait=True)


class ProcRounds:
    """proc — supervision, not execution: worker processes run tasks
    against the ProcBackend on its handler threads, and a round drains
    what it queued and supervises liveness."""

    def __init__(self, eng, execute):
        self.eng = eng
        b = self.backend = eng.backend
        # serialize the execute callback BEFORE spawning anything: an
        # unpicklable callback must fail fast, not hang a handshake
        b.prepare(execute=execute, pass_worker=eng._pass_worker,
                  steal_n=eng.steal_n, resident=eng.resident)
        # the door WITHHOLDS failures this predicate approves (the task
        # stays leased): the policy decision is the engine's, but the
        # suppression must happen at the wire, before the scheduler
        # learns of the failure and poisons dependents
        b.retry_check = eng._will_retry
        b.data.open(eng.tasks, eng._done)
        b.start_pool(eng._alive)
        # liveness grace: a worker busy on a long task still heartbeats
        # (daemon thread), so staleness only means the PROCESS is gone or
        # wedged; locally-spawned processes are additionally poll()ed
        # and surface within one round of dying
        self.grace = max(3.0 * eng.heartbeat_s, 1.0)
        self.retry_pending: list = []        # (t_ready, worker, task)

    def round(self, stopping: bool):
        eng, b = self.eng, self.backend
        # records before joins: a worker's Hello is queued before any of
        # its records, so every record's worker has joined below
        recs = b.drain_records()
        for w in b.drain_joined():
            eng._join(w)
        if recs:
            eng._progress = True
            notes = [] if eng._noting else None
            for res in b.results(recs, eng._done):
                eng._finish(res.worker, res, notes)
            if notes:
                eng._note_terminal_many(notes)
        # lease requeues observed at the wire (an expired lease reaped by
        # another worker's steal)
        n_rq = b.drain_requeued()
        if n_rq:
            eng.tracer.emit(REQUEUED, n=n_rq, via="lease")
            if eng.journal is not None:
                eng.journal.append_requeue(n_rq, "lease")
            eng._progress = True
        # completions the door withheld because a dependency value is
        # unrecoverable: the data plane recomputes it and requeues the
        # dependent — the peer-to-peer data plane's zero-loss contract
        for w, name, missing in b.drain_lost():
            eng._progress = True
            if not self._stale(w, name):
                why = b.data.requeue_lost(w, name, missing)
                if why is not None:
                    self._fail(w, name, why)
        # transiently-failed completions the door withheld on
        # _will_retry's word: charge the attempt and queue the
        # Transfer-requeue behind the policy's backoff
        for w, name, err in b.drain_failed():
            eng._progress = True
            if self._stale(w, name):
                continue
            delay = eng._retry(name, w, err)
            if delay is None:
                # the budget ran out between the wire check and this
                # drain: fail for real
                self._fail(w, name, err)
            else:
                self.retry_pending.append(
                    (time.perf_counter() + delay, w, name))
        if self.retry_pending:
            now = time.perf_counter()
            due = [e for e in self.retry_pending if e[0] <= now]
            if due:
                self.retry_pending = [e for e in self.retry_pending
                                      if e[0] > now]
                for _t, w, name in due:
                    # a dead worker's lease was requeued by its Exit
                    if w not in eng._dead_workers:
                        b.transfer(w, name, [])
                eng._progress = True
        # client threads asking for a lost value to be recomputed: every
        # backend Create stays on this thread
        if b.data.serve_wanted():
            eng._progress = True
        # liveness: a SIGKILLed process surfaces as a crash, a wedged one
        # as stale; either way its in-flight work requeues via Exit
        for w, reason in b.check_dead(self.grace):
            if w in eng._alive and w not in eng._dead_workers:
                eng._bury(w, crash=True, reason=reason)

    def _stale(self, w: str, name: str) -> bool:
        # a withheld completion of a task that already completed
        # elsewhere (a requeue duplicate): just clear the stale lease
        if not self.eng._done(name):
            return False
        self.backend.complete(w, name, ok=name not in self.eng._failed)
        return True

    def _fail(self, w: str, name: str, why: str):
        self.backend.complete(w, name, ok=False)
        self.eng._finish(w, TaskResult(task=name, ok=False, worker=w,
                                       error=why), None)

    # ------------------------------------------------ shared-step hooks
    def joined(self, w: str, add: bool):
        if add:
            self.backend.spawn(w)

    def buried(self, w: str, announce: bool):
        # terminate the process (lose_worker; a crashed or fenced one is
        # gone already), Exit its leases, recompute what only it held
        self.backend.kill_worker(w)
        self.backend.exit_worker(w)
        self.backend.data.lost_on(w)

    def quiet(self) -> bool:
        return not self.backend.has_records()

    def drained(self) -> bool:
        eng = self.eng
        if eng.resident:
            with eng._cond:
                return eng._inflight <= 0 and not eng._mailbox
        return self.backend.all_done()

    def in_flight(self) -> bool:
        # workers alive but nothing moving: only a true deadlock
        # (nothing ready, nothing leased) is a stall — long-running
        # tasks are just busy
        st = self.backend.stats()
        return bool(st.get("ready", 0) or st.get("assigned", 0)
                    or self.backend.all_done())

    def parallelism(self) -> int:
        return self.eng._peak

    def close(self):
        # refuse recompute asks, drain-stop the workers (their exit flush
        # replicates every owned value to the hub), then let the data
        # plane materialise remote results and close its peer conns
        b = self.backend
        b.data.live = False
        b.stop_pool()
        b.data.close(self.eng._results)
