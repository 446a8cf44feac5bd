"""Unified execution engine tests: lifecycle tracing, worker-pool
transports, fault injection (dead workers, poisoned tasks, heartbeat
leases, seeded stragglers), sharded routing, and the empirical-vs-analytic
METG crosscheck for all three schedulers (the paper's §3-§6 claims,
measured on the running code)."""
import pytest

from repro.core.dwork import Client, InProcTransport, TaskServer, run_pool
from repro.core.engine import (COMPLETED, CREATED, FAILED, READY, RUN_END,
                               RUN_START, STOLEN, Engine, FaultPlan,
                               ManualClock, RetryPolicy, TraceRecorder,
                               crosscheck)
from repro.core.metg import METGModel, PAPER_DWORK_RTT
from repro.core.mpi_list import Context
from repro.core.pmake import PMake


def flat_engine(n, workers=4, **kw):
    eng = Engine(workers=workers, transport="inproc", **kw)
    for i in range(n):
        eng.submit(f"t{i}", fn=lambda: None)
    return eng


def diamond_engine(n=1000, workers=4, **kw):
    """1 root -> (n-2) parallel mids -> 1 sink (the 1k diamond DAG)."""
    eng = Engine(workers=workers, transport="inproc", **kw)
    mids = [f"mid{i}" for i in range(n - 2)]
    eng.submit("root", fn=lambda: None)
    for m in mids:
        eng.submit(m, fn=lambda: None, deps=["root"])
    eng.submit("sink", fn=lambda: None, deps=mids)
    return eng, mids


# ---------------------------------------------------------------- basics


def test_dag_execution_values_and_order():
    eng = Engine(workers=2, transport="inproc")
    eng.submit("a", fn=lambda: 1)
    eng.submit("b", fn=lambda: 2, deps=["a"])
    eng.submit("c", fn=lambda: 3, deps=["a", "b"])
    rep = eng.run()
    assert rep.completed == {"a", "b", "c"} and not rep.stalled
    assert rep.results["c"].value == 3
    runs = [e.task for e in rep.trace.of(RUN_START)]
    assert runs.index("a") < runs.index("b") < runs.index("c")


def test_lifecycle_event_order_deterministic_clock():
    clk = ManualClock(tick=1e-6)
    eng = Engine(workers=1, transport="inproc", clock=clk)
    eng.submit("x", fn=lambda: "v")
    eng.submit("y", fn=lambda: "w", deps=["x"])
    rep = eng.run()
    for task in ("x", "y"):
        ts = {ev: next(e.t for e in rep.trace.of(ev) if e.task == task)
              for ev in (CREATED, READY, STOLEN, RUN_START, RUN_END,
                         COMPLETED)}
        assert (ts[CREATED] <= ts[READY] <= ts[STOLEN] <= ts[RUN_START]
                <= ts[RUN_END] <= ts[COMPLETED]), (task, ts)


def test_priority_order_within_one_steal_batch_fast_path():
    """Regression: the fault-free inline fast path must not drain a
    prio-0 task before a higher-priority one later in the SAME batch."""
    order = []
    eng = Engine(workers=1, transport="inproc", steal_n=4)
    eng.submit("low", fn=lambda: order.append("low"), priority=0.0)
    eng.submit("high", fn=lambda: order.append("high"), priority=9.0)
    rep = eng.run()
    assert order == ["high", "low"] and rep.completed == {"high", "low"}


def test_priority_and_slots_pmake_semantics():
    """The launch step is pmake's greedy highest-priority-first; a task
    wanting more slots than the allocation is clamped, not starved."""
    order = []
    eng = Engine(workers=2, transport="inproc", steal_n=8)
    eng.submit("low", fn=lambda: order.append("low"), priority=1.0)
    eng.submit("hi", fn=lambda: order.append("hi"), priority=10.0, slots=16)
    rep = eng.run()
    assert order == ["hi", "low"] and rep.completed == {"hi", "low"}


def test_steal_n_batching_reduces_rpcs():
    n1 = flat_engine(200, steal_n=1).run().overhead().n_rpc
    n8 = flat_engine(200, steal_n=8).run().overhead().n_rpc
    assert n8 < n1


def test_sharded_routing():
    eng = Engine(workers=4, shards=2, steal_n=4, transport="inproc")
    for i in range(200):
        eng.submit(f"s{i}", deps=[f"s{i - 20}"] if i >= 20 else ())
    rep = eng.run()
    assert len(rep.completed) == 200 and not rep.stalled
    assert len(rep.backend_stats["shards"]) == 2
    # both shards actually served tasks (hash routing + work stealing)
    assert all(s["completed"] > 0 for s in rep.backend_stats["shards"])


# ---------------------------------------------- the CompleteSteal batch verb


def test_complete_steal_one_round_trip_both_directions():
    """CompleteSteal applies the finished batch FIRST, then serves the
    steal — so completing a producer and stealing its freed successor
    works in a single round-trip."""
    from repro.core.dwork.api import ExitResp, TaskMsg
    srv = TaskServer()
    cl = Client(InProcTransport(srv), "w0")
    cl.create("a")
    cl.create("b", deps=["a"])
    cl.create("c")
    got = cl.steal(n=2)
    assert [t for t, _m in got.tasks] == ["a", "c"]
    r = cl.complete_steal([("a", True), ("c", True)], n=2)
    assert isinstance(r, TaskMsg)
    assert [t for t, _m in r.tasks] == ["b"]       # freed by the batch
    assert srv.counters["completed"] == 2
    # complete-only (n=0) returns ExitResp and never steals
    assert isinstance(cl.complete_steal([("b", True)], n=0), ExitResp)
    assert srv.counters["completed"] == 3
    assert isinstance(cl.steal(), ExitResp)        # everything terminal


def test_complete_steal_failed_batch_entry_poisons():
    from repro.core.dwork.api import ExitResp
    srv = TaskServer()
    cl = Client(InProcTransport(srv), "w0")
    cl.create("a")
    cl.create("kid", deps=["a"])
    cl.steal()
    assert isinstance(cl.complete_steal([("a", False)], n=1), ExitResp)
    assert srv.errors == {"a", "kid"}


def test_complete_clears_duplicate_assignment_after_requeue():
    """A late Complete for a task that was lease-requeued and re-stolen
    must clear the re-stealer's assignment too (exactly-once terminal:
    no stale server-side state for any holder)."""
    srv = TaskServer(lease_timeout=0.0)    # immediate expiry
    slow = Client(InProcTransport(srv), "slow")
    slow.create("a")
    assert slow.steal().tasks[0][0] == "a"
    fast = Client(InProcTransport(srv), "fast")
    assert fast.steal().tasks[0][0] == "a"         # re-stolen after expiry
    slow.complete("a")                             # late straggler report
    assert srv.assigned.get("fast", set()) == set()
    assert srv.assigned.get("slow", set()) == set()
    assert srv.counters["completed"] == 1


def test_complete_steal_wire_round_trip():
    from repro.core.dwork.api import CompleteSteal, decode, encode
    msg = CompleteSteal(worker="w0", done=[("a", True), ("b", False)], n=3)
    back = decode(encode(msg))
    assert isinstance(back, CompleteSteal)
    assert back.worker == "w0" and back.n == 3
    assert [(t, bool(ok)) for t, ok in back.done] == \
        [("a", True), ("b", False)]


def test_engine_batches_rpcs_via_complete_steal():
    """The engine's dispatch loop must piggyback completions on steals:
    at steal_n=8 a 200-task flat run needs far fewer round-trips than
    one per task (plus the 200 creates)."""
    rep = flat_engine(200, steal_n=8).run()
    ov = rep.overhead()
    ops = {op for op in ov.rpc_by_op}
    assert "complete_steal" in ops
    assert "complete" not in ops           # no unbatched completes
    assert ov.n_rpc < 200 + 200 // 4       # creates + amortized dispatch


# --------------------------------------------------------- fault injection


def test_dead_worker_mid_1k_diamond_zero_lost_tasks():
    """Kill a worker mid-run of a 1k-task diamond DAG: its stolen-but-
    unfinished tasks are recycled (Exit -> FRONT of queue), no task is
    lost, and every successor eventually completes.  Deterministic: the
    inproc transport round-robins with no wall-clock dependence."""
    faults = FaultPlan(seed=7).kill_worker("w2", after_steals=100)
    eng, mids = diamond_engine(1000, workers=4, steal_n=8, faults=faults)
    rep = eng.run()
    assert not rep.stalled
    assert len(rep.completed) == 1000            # zero lost tasks
    assert rep.completed >= set(mids) | {"root", "sink"}
    ov = rep.overhead()
    assert ov.n_requeued >= 1                    # the dead worker's batch
    dead = [e for e in rep.trace.events if e.event == "worker_dead"]
    assert [e.worker for e in dead] == ["w2"]
    # w2 never completes anything after death: its results were discarded
    assert rep.backend_stats["completed"] == 1000


def test_failed_task_poisons_transitive_successors_in_diamond():
    faults = FaultPlan(seed=7).fail_task("mid500")
    eng, mids = diamond_engine(1000, workers=4, steal_n=8, faults=faults)
    rep = eng.run()
    assert not rep.stalled
    assert rep.errors == {"mid500", "sink"}      # transitive poisoning
    assert len(rep.completed) == 998             # everything else completed
    # zero lost: every task reached a terminal state
    assert len(rep.completed) + len(rep.errors) == 1000


def test_silent_death_recovered_by_heartbeat_lease():
    """A silently-dead worker sends no Exit; the heartbeat lease (manual
    clock — deterministic) expires and its tasks are re-queued."""
    clk = ManualClock(tick=1e-3)
    faults = FaultPlan(seed=3).kill_worker("w1", after_steals=2, silent=True)
    eng = Engine(workers=2, transport="inproc", steal_n=2, clock=clk,
                 lease_timeout=0.05, faults=faults)
    for i in range(20):
        eng.submit(f"x{i}", fn=lambda: None)
    rep = eng.run()
    assert len(rep.completed) == 20 and not rep.stalled
    assert rep.overhead().n_requeued >= 1


def test_lease_requeue_exactly_once_engine_server():
    """Engine-level mirror of the dwork lease regression (runs without
    hypothesis): expired lease requeues to the FRONT exactly once; a late
    Complete never causes a double-execution."""
    clock = {"now": 0.0}
    srv = TaskServer(lease_timeout=1.0, clock=lambda: clock["now"])
    slow = Client(InProcTransport(srv), "slow")
    slow.create("a")
    slow.create("b")
    assert slow.steal().tasks[0][0] == "a"
    clock["now"] = 2.0
    srv._reap_leases()
    assert srv.counters["requeued"] == 1
    assert list(srv.ready)[0] == "a"             # FRONT of the deque
    slow.complete("a")                           # late straggler Complete
    assert srv.counters["requeued"] == 1         # no double-requeue
    rep = run_pool(srv, lambda n, m: True, workers=2)
    assert rep.backend_stats["completed"] == 2   # "a" exactly once
    assert srv.counters["completed"] == 2
    assert "a" not in rep.results                # stale entry never served
    assert srv.counters["stolen"] == 2           # a once (slow), b once


def test_run_pool_inherits_server_lease_for_idle_budget():
    """run_pool must size the engine's idle budget from the server's
    heartbeat lease: a silently-dead worker's tasks are reaped after
    lease expiry instead of being abandoned as a premature stall."""
    clk = ManualClock(tick=1e-3)
    srv = TaskServer(lease_timeout=1.0, clock=clk)
    boss = Client(InProcTransport(srv), "boss")
    for i in range(6):
        boss.create(f"t{i}")
    rep = run_pool(srv, lambda n, m: True, workers=2, steal_n=2, clock=clk,
                   faults=FaultPlan(seed=1).kill_worker(
                       "w0", after_steals=1, silent=True))
    assert len(rep.completed) == 6 and not rep.stalled
    assert rep.overhead().n_requeued >= 1


def test_straggler_injection_deterministic_with_seed():
    def run_ctx(seed):
        C = Context(16, engine_workers=4, straggler_sigma=1e-3, seed=seed)
        C.scatter(list(range(64))).map(lambda x: x + 1)
        return C.virtual_gaps[0]

    assert run_ctx(42) == run_ctx(42)
    assert run_ctx(42) != run_ctx(43)


def test_dead_worker_with_inflight_task_thread_transport():
    """Announced death while a task is mid-flight on the thread pool: the
    requeued task is re-stolen by a live worker and must eventually run
    (the dead copy's completion is discarded, so the re-steal is its only
    way forward)."""
    import time as _t
    faults = FaultPlan(seed=5).kill_worker("w1", after_steals=3)
    eng = Engine(workers=2, transport="thread", steal_n=2, faults=faults,
                 poll=0.002)
    for i in range(12):
        eng.submit(f"t{i}", fn=lambda: _t.sleep(0.05))
    rep = eng.run()
    assert len(rep.completed) == 12 and not rep.stalled
    assert rep.backend_stats["assigned"] == 0    # nothing stuck leased


def test_lease_shorter_than_task_keeps_server_state_clean():
    """A task longer than the heartbeat lease is re-stolen while its live
    copy runs; the suppressed duplicate must not leave a stale entry in
    the server's assigned map once the task completes."""
    import time as _t
    eng = Engine(workers=2, transport="thread", lease_timeout=0.05,
                 poll=0.002, steal_n=1)
    eng.submit("slowpoke", fn=lambda: _t.sleep(0.2))
    for i in range(4):
        eng.submit(f"quick{i}", fn=lambda: None)
    rep = eng.run()
    assert len(rep.completed) == 5 and not rep.stalled
    assert rep.backend_stats["assigned"] == 0    # no stale leases


def test_pmake_global_priority_on_node_limited_allocation():
    """With one node, the high-EFT rule's tasks must all launch before any
    low-priority ones — global greedy priority, not per-batch."""
    import tempfile
    rules = """
big:
  resources: {time: 600, nrs: 1}
  out: {o: "big_{n}.out"}
  script: "echo {n}"
small:
  resources: {time: 1, nrs: 1}
  out: {o: "small_{n}.out"}
  script: "echo {n}"
"""
    targets = ('t:\n  dirname: .\n  loop: {n: "range(6)"}\n'
               '  tgt: {b: "big_{n}.out", s: "small_{n}.out"}\n')
    ran = []
    pm = PMake(rules, targets, root=tempfile.mkdtemp(), total_nodes=1,
               transport="inproc", runner=lambda t: ran.append(t.rule.name)
               or True)
    stats = pm.run()
    assert stats["done"] == 12 and stats["errors"] == 0
    assert ran[:6] == ["big"] * 6                # EFT order, all batches


def test_straggler_crosscheck_requires_injected_sigma():
    C = Context(8, engine_workers=2)             # engine mode, no injection
    C.scatter(list(range(16))).map(lambda x: x)
    with pytest.raises(ValueError):
        C.straggler_crosscheck()


def test_pmake_chain_respects_dependency_order():
    """Regression: tasks must be submitted producers-first; a dependent
    submitted before its producer would be forward-declared READY and run
    against missing inputs (3-level chain with a slow upstream)."""
    import tempfile
    import time as _t
    rules = """
stage_a:
  resources: {time: 1, nrs: 1}
  out: {o: "a.txt"}
  script: "echo a > a.txt"
stage_b:
  resources: {time: 1, nrs: 1}
  inp: {i: "a.txt"}
  out: {o: "b.txt"}
  script: "cp a.txt b.txt"
stage_c:
  resources: {time: 1, nrs: 1}
  inp: {i: "b.txt"}
  out: {o: "c.txt"}
  script: "cp b.txt c.txt"
"""
    targets = 't:\n  dirname: .\n  tgt: {o: "c.txt"}\n'
    ran = []

    def runner(task):
        ran.append(task.rule.name)
        _t.sleep(0.05 if task.rule.name == "stage_a" else 0.0)
        return True

    pm = PMake(rules, targets, root=tempfile.mkdtemp(), total_nodes=4,
               runner=runner)
    stats = pm.run()
    assert stats["done"] == 3 and stats["errors"] == 0
    assert ran == ["stage_a", "stage_b", "stage_c"]


def test_overhead_report_pairs_reexecutions_sequentially():
    """A requeued task emits two run_start/run_end pairs; compute time
    must pair them per execution, never across (no negative durations)."""
    from repro.core.engine import RUN_END, RUN_START, STOLEN as ST
    tr = TraceRecorder(clock=lambda: 0.0)

    def ev(event, t, task, **extra):
        e = tr.emit(event, task=task, **extra)
        e.t = t

    ev(ST, 0.0, "x")
    ev(RUN_START, 1.0, "x")
    ev(RUN_END, 2.0, "x")              # first execution: 1s
    ev(ST, 4.0, "x")                   # requeued + re-stolen
    ev(RUN_START, 5.0, "x")
    ev(RUN_END, 7.0, "x")              # second execution: 2s
    ev(COMPLETED, 7.0, "x")
    rep = tr.report(workers=1)
    assert rep.compute_s == pytest.approx(3.0)
    assert rep.dispatch_s == pytest.approx(2.0)   # 1s + 1s stolen->start


def test_all_workers_dead_with_remaining_work_reports_stall():
    """Every worker dying mid-run must NOT look like a clean finish:
    the abandoned tasks are a stall the caller can detect."""
    faults = (FaultPlan(seed=1).kill_worker("w0", after_steals=1)
              .kill_worker("w1", after_steals=1))
    eng = Engine(workers=2, steal_n=2, faults=faults, max_idle_rounds=30)
    for i in range(50):
        eng.submit(f"t{i}", fn=lambda: None)
    rep = eng.run()
    assert len(rep.completed) < 50
    assert rep.stalled                           # not a clean exit


def test_thread_overhead_accounting_capped_by_capacity():
    """ThreadPoolExecutor is sized by `capacity`; phantom workers above
    it must not be billed as idle scheduler overhead."""
    import time as _t
    eng = Engine(workers=8, capacity=2, transport="thread", steal_n=1,
                 poll=0.002)
    for i in range(8):
        eng.submit(f"t{i}", fn=lambda: _t.sleep(0.03))
    rep = eng.run()
    assert len(rep.completed) == 8
    assert rep.workers == 2                      # min(workers, capacity)
    # 8 x 30ms over 2 real slots: overhead must stay far below the
    # ~90ms/task that billing 6 phantom workers would produce
    assert rep.overhead().per_task_overhead_s < 0.03


# -------------------------------------- the 1,000-task METG acceptance run


def work(x):
    return x * x


@pytest.fixture(scope="module")
def thousand_task_results():
    """One identical 1,000-task workload (square 1000 ints) through all
    three schedulers via the engine, with traces.

    GC is paused during the measured runs: with the full suite's heap
    (jax caches etc.) resident, gen-2 collections otherwise land inside
    the trace spans and swamp the ~30 us/task scheduler overhead."""
    import gc
    gc.collect()
    gc.disable()
    out = {}

    # dwork: 1000 independent tasks on a TaskServer, engine pool
    srv = TaskServer()
    boss = Client(InProcTransport(srv), "boss")
    for i in range(1000):
        boss.create(f"sq{i}", meta={"x": i})
    rep = run_pool(srv, lambda name, meta: (True, work(meta["x"])),
                   workers=4, steal_n=1)
    out["dwork"] = rep

    # pmake: 1000-target ruleset, engine pool with runner override
    rules = ('sq:\n  resources: {time: 1, nrs: 1}\n'
             '  out: {o: "sq_{n}.out"}\n  script: "echo {n}"\n')
    targets = ('all:\n  dirname: .\n  loop:\n    n: "range(1000)"\n'
               '  tgt: {o: "sq_{n}.out"}\n')
    import tempfile
    pm = PMake(rules, targets, root=tempfile.mkdtemp(), total_nodes=4,
               transport="inproc", runner=lambda t: True)
    out["pmake_stats"] = pm.run()
    out["pmake"] = pm.report

    # mpi-list: the same 1000 elements, 16 ranks, engine-backed supersteps
    C = Context(16, engine_workers=4, straggler_sigma=1e-3, seed=0)
    dfm = C.scatter(list(range(1000))).map(work)
    out["mpilist_collect"] = dfm.collect()
    out["mpilist_ctx"] = C
    gc.enable()
    return out


def test_identical_workload_completes_on_all_three(thousand_task_results):
    r = thousand_task_results
    assert len(r["dwork"].completed) == 1000 and not r["dwork"].stalled
    assert all(r["dwork"].results[f"sq{i}"].value == i * i
               for i in range(0, 1000, 97))
    assert r["pmake_stats"]["done"] == 1000
    assert r["pmake_stats"]["errors"] == 0
    assert r["mpilist_collect"] == [work(i) for i in range(1000)]


def test_empirical_overhead_crosschecks_analytic_metg(thousand_task_results):
    """tracing.py reports empirical per-task overhead for each scheduler,
    same order of magnitude as the core/metg.py analytic laws evaluated
    with constants measured from the same traces."""
    r = thousand_task_results

    # dwork: METG(P) = rtt * P / steal_n, rtt measured at the server
    ov = r["dwork"].overhead()
    assert ov.n_tasks == 1000 and ov.per_task_overhead_s > 0
    model = METGModel.from_measured(rtt_s=ov.rpc_per_task_s)
    chk = crosscheck("dwork", ov.per_task_overhead_s,
                     model.dwork_metg(r["dwork"].workers * 4, steal_n=1))
    assert chk["same_order"], chk
    # and our in-proc RTT analog is within ~30x of the paper's 23 us
    assert crosscheck("dwork-rtt", ov.rpc_per_task_s, PAPER_DWORK_RTT,
                      factor=30.0)["same_order"]

    # pmake: METG = launch + alloc; our "launch" constant is the measured
    # per-task scheduler round-trip cost, cross-checked against the
    # independent span-based overhead (wall minus compute, per task)
    pv = r["pmake"].overhead()
    assert pv.n_tasks == 1000 and pv.per_task_overhead_s > 0
    pmodel = METGModel.from_measured(launch_s=pv.rpc_per_task_s)
    chk = crosscheck("pmake", pv.per_task_overhead_s, pmodel.pmake_metg(4))
    assert chk["same_order"], chk

    # mpi-list: sync gap vs Gumbel sigma*sqrt(2 ln P) at the injected sigma
    chk = thousand_task_results["mpilist_ctx"].straggler_crosscheck()
    assert chk["same_order"], chk


def test_trace_counts_conserved(thousand_task_results):
    """Every created task is stolen and reaches exactly one terminal event
    (requeues may add extra steals, never extra completions)."""
    tr = thousand_task_results["dwork"].trace
    assert tr.count(COMPLETED) == 1000
    assert tr.count(STOLEN) >= 1000
    done_tasks = {e.task for e in tr.of(COMPLETED)}
    assert len(done_tasks) == 1000


# ------------------------------------------------- one loop, four paths

def _contract_dag(marker: str) -> list:
    """-> [(name, fn, deps)]: a diamond, a failure poisoning two
    successors, and a task that fails once (the marker file makes the
    first run fail in any process) and succeeds on its retry."""
    import os

    def flaky():
        if not os.path.exists(marker):
            open(marker, "w").close()
            raise RuntimeError("transient")
        return 5

    def bad():
        raise ValueError("boom")

    return [("a", lambda: 1, ()), ("b", lambda: 2, ("a",)),
            ("c", lambda: 3, ("a",)), ("d", lambda: 4, ("b", "c")),
            ("bad", bad, ()), ("bad1", lambda: 6, ("bad",)),
            ("bad2", lambda: 7, ("bad1",)), ("flaky", flaky, ())]


@pytest.mark.parametrize("path", ["inproc-fast", "inproc-heap", "thread",
                                  "proc"])
def test_terminal_contract_on_every_path(path, tmp_path):
    """The one dispatch loop keeps the same terminal contract on every
    transport path: one COMPLETED or FAILED event and one on_result per
    task, worker stats that count every executed completion, exec_failed
    equal to the failures, and a complete result table."""
    seen = []
    kw = dict(workers=2, on_result=lambda name, *_: seen.append(name),
              retry=RetryPolicy(max_attempts=3, retry_on=("transient",)))
    if path == "inproc-fast":
        kw["resident"] = True
    elif path != "inproc-heap":
        kw["transport"] = path
    if path == "proc":
        kw["heartbeat_s"] = 0.1
    eng = Engine(**kw)
    if eng.resident:
        eng.start()
    prio = 1.0 if path == "inproc-heap" else 0.0
    dag = _contract_dag(str(tmp_path / "flaky-ran"))
    for name, fn, deps in dag:
        eng.submit(name, fn, deps=deps, priority=prio)
    if eng.resident:
        assert eng.drain(timeout=60)
        rep = eng.shutdown()
    else:
        rep = eng.run()
    names = [name for name, _, _ in dag]
    poisoned = {"bad1", "bad2"}
    executed = [n for n in names if n not in poisoned]
    ends = [e for e in rep.trace.events if e.event in (COMPLETED, FAILED)]
    assert sorted(e.task for e in ends) == sorted(names)
    assert sorted(seen) == sorted(names)
    ran = [e for e in ends if e.worker is not None]
    assert sorted(e.task for e in ran) == sorted(executed)
    assert sum(s["done"] for s in eng.worker_stats().values()) == len(ran)
    assert eng.exec_failed == 1 and eng.retries_total == 1
    assert sorted(rep.results) == sorted(executed)
    assert {n: r.value for n, r in rep.results.items() if r.ok} == \
        {"a": 1, "b": 2, "c": 3, "d": 4, "flaky": 5}
    assert not rep.results["bad"].ok and "boom" in rep.results["bad"].error
    assert not rep.stalled
