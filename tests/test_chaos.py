"""Seeded chaos matrix over the self-healing multi-process runtime.

Reference analog: ``testing/BaseFailureRecoveryTest.java`` — every fault
shape the deterministic ``FaultSchedule`` can inject (worker kill, RPC
drop mid-frame, straggler delay, spool truncation, fail-after-publish,
injected user error) is driven against TPC-H q1/q3 style queries under
the retry policies that can recover from it, asserting:

- results equal the fault-free run on the SAME cluster (and the local
  oracle) — recovery must never change answers;
- ``task_launches`` match the expected attempt shape (no silent
  double-launch, no producer re-runs under retry-from-spool);
- USER errors fail fast with ZERO retry attempts;
- dead workers get REPLACED (spawn + register + replica re-sync) and
  the replacement serves subsequent queries.

All cases run 2 workers on the micro schema to stay far under the ~10 s
per-case tier-1 budget rule.
"""

import threading
import time

import pytest

from trino_tpu.connectors.tpch import TpchConnector
from trino_tpu.events import EventListener
from trino_tpu.parallel.fault import FaultSchedule
from trino_tpu.parallel.process_runner import ProcessQueryRunner
from trino_tpu.resources.tpch_queries import TPCH_QUERIES
from trino_tpu.runner import LocalQueryRunner
from trino_tpu.sql.analyzer import Session
from trino_tpu.types import TrinoError

CATALOGS = {"tpch": {"connector": "tpch", "page_rows": 4096},
            "memory": {"connector": "memory"}}
Q1 = ("select l_returnflag, l_linestatus, count(*), sum(l_quantity) "
      "from lineitem group by l_returnflag, l_linestatus")
Q3 = TPCH_QUERIES[3]


class _Recorder(EventListener):
    def __init__(self):
        self.replaced = []
        self.retries = []

    def worker_replaced(self, event):
        self.replaced.append(event)

    def task_retry(self, event):
        self.retries.append(event)


RECORDER = _Recorder()


def _mk_session(**props):
    s = Session(catalog="tpch", schema="micro")
    s.properties.update({"retry_initial_backoff": 0.02,
                         "retry_max_backoff": 0.2, **props})
    return s


@pytest.fixture(scope="module")
def local():
    return LocalQueryRunner({"tpch": TpchConnector(page_rows=4096)},
                            Session(catalog="tpch", schema="micro"))


@pytest.fixture(scope="module")
def task_cluster():
    """retry_policy=TASK over the spooled barrier shape — the full
    fault-tolerant stack: retry-from-spool, speculation, replacement."""
    # speculation off by default in this module: a cold replacement
    # worker's first-task warmup (seconds) would otherwise let a
    # legitimate speculative win rescue a faulted task BEFORE the
    # task-retry path each test means to pin down; the dedicated
    # straggler test re-enables it
    s = _mk_session(streaming_execution=False, retry_policy="TASK",
                    speculative_execution_enabled=False,
                    speculation_min_seconds=0.3)
    with ProcessQueryRunner(CATALOGS, s, n_workers=2, desired_splits=4,
                            broadcast_threshold=300.0,
                            heartbeat_interval=0.25,
                            event_listeners=[RECORDER]) as c:
        c.fault_schedule = FaultSchedule(seed=42)
        yield c


@pytest.fixture(scope="module")
def barrier_cluster():
    """retry_policy=QUERY, streaming off: barrier stages whose results
    are pulled over get_results — the transient-RPC-retry seam."""
    s = _mk_session(streaming_execution=False, retry_policy="QUERY")
    with ProcessQueryRunner(CATALOGS, s, n_workers=2, desired_splits=4,
                            broadcast_threshold=300.0,
                            heartbeat_interval=0.25) as c:
        c.fault_schedule = FaultSchedule(seed=42)
        yield c


@pytest.fixture(scope="module")
def stream_cluster():
    """retry_policy=QUERY, streaming on (the default shape): outputs
    are not durable, every fault recovers via full-query retry."""
    s = _mk_session(retry_policy="QUERY")
    with ProcessQueryRunner(CATALOGS, s, n_workers=2, desired_splits=4,
                            broadcast_threshold=300.0,
                            heartbeat_interval=0.25) as c:
        c.fault_schedule = FaultSchedule(seed=42)
        yield c


def _await_capacity(c, timeout=90):
    """Wait for self-healing to restore every worker slot."""
    deadline = time.time() + timeout
    while time.time() < deadline:
        if all(c.heal()):
            return
        time.sleep(0.1)
    raise AssertionError(f"cluster never healed: {c.heartbeat()}")


def _next_qid(c):
    return f"q{c._task_seq + 1}a0"


def _launches_since(c, mark):
    return c.task_launches[mark:]


# ----------------------------------------------------------- TASK policy ----


def test_task_clean_baselines(local, task_cluster):
    """Fault-free anchors (also warms the per-cluster compile caches so
    later straggler medians are tight)."""
    c = task_cluster
    c._q1_clean = sorted(c.execute(Q1).rows)
    c._q3_clean = c.execute(Q3).rows
    assert c._q1_clean == sorted(local.execute(Q1).rows)
    assert c._q3_clean == local.execute(Q3).rows


def test_kill_worker_mid_query_task_policy(task_cluster):
    """THE acceptance scenario: a seeded FaultSchedule kills a worker
    mid-query under TASK policy — correct results, completed producer
    stages NOT re-run (task_launches), all recovery inside attempt 0,
    and the replacement worker serves the next query."""
    c = task_cluster
    _await_capacity(c)
    qid = _next_qid(c)
    c.fault_schedule.add(f"{qid}.f1", "kill-worker")
    mark = len(c.task_launches)
    res = c.execute(Q1)
    assert sorted(res.rows) == c._q1_clean
    launches = _launches_since(c, mark)
    assert all("a0." in t for t in launches), launches
    f0 = [t for t in launches if f"{qid}.f0." in t]
    f1 = [t for t in launches if f"{qid}.f1." in t]
    assert len(f0) == 2, f"producer stage re-ran: {f0}"
    assert len(f1) == 3, f"expected exactly one retried task: {f1}"
    rec = res.stats["recovery"]
    assert rec["task_retries"] == 1
    assert rec["retries_by_type"].get("EXTERNAL") == 1
    assert rec["query_retries"] == 0
    # self-healing: the killed slot comes back and serves queries
    _await_capacity(c)
    assert sorted(c.execute(Q1).rows) == c._q1_clean


def test_kill_worker_q3_join_task_policy(task_cluster):
    """Same fault against the join+TopN pipeline (more fragments, merge
    output): recovery stays inside attempt 0."""
    c = task_cluster
    _await_capacity(c)
    qid = _next_qid(c)
    c.fault_schedule.add(f"{qid}.f1", "kill-worker")
    mark = len(c.task_launches)
    res = c.execute(Q3)
    assert res.rows == c._q3_clean
    launches = _launches_since(c, mark)
    assert all("a0." in t for t in launches), launches
    _await_capacity(c)


def test_fail_after_spool_publish_first_publish_wins(task_cluster):
    """A task that fails AFTER publishing its spool output retries; the
    duplicate publish is discarded (first-publish-wins hard link) and
    results stay exact."""
    c = task_cluster
    _await_capacity(c)
    qid = _next_qid(c)
    c.fault_schedule.add(f"{qid}.f0", "fail-after-publish")
    mark = len(c.task_launches)
    res = c.execute(Q1)
    assert sorted(res.rows) == c._q1_clean
    launches = _launches_since(c, mark)
    assert all("a0." in t for t in launches), launches
    assert any(".r1" in t for t in launches
               if f"{qid}.f0." in t), launches
    assert res.stats["recovery"]["retries_by_type"].get("INTERNAL") == 1


def test_truncate_spool_frame_query_retry(task_cluster):
    """A torn spool file must fail loudly (never partial rows); a task
    retry re-reads the same bytes, so recovery comes from the QUERY
    retry rebuilding the exchange under a fresh attempt id."""
    c = task_cluster
    _await_capacity(c)
    qid = _next_qid(c)
    c.fault_schedule.add(f"{qid}.f0", "truncate-spool")
    mark = len(c.task_launches)
    res = c.execute(Q1)
    assert sorted(res.rows) == c._q1_clean
    launches = _launches_since(c, mark)
    assert any("a1." in t for t in launches), launches
    assert res.stats["recovery"]["query_retries"] >= 1


def test_straggler_speculative_redispatch(task_cluster):
    """A task delayed far past its sibling's median is re-dispatched on
    another worker; the speculative attempt wins and the query never
    waits out the straggler's full delay."""
    c = task_cluster
    _await_capacity(c)
    qid = _next_qid(c)
    c.fault_schedule.add(f"{qid}.f0", "delay", delay_s=4.0)
    mark = len(c.task_launches)
    c.session.properties["speculative_execution_enabled"] = True
    try:
        res = c.execute(Q1)
    finally:
        c.session.properties["speculative_execution_enabled"] = False
    assert sorted(res.rows) == c._q1_clean
    launches = _launches_since(c, mark)
    assert any(t.endswith(".spec") for t in launches), launches
    rec = res.stats["recovery"]
    assert rec["speculative_launched"] >= 1
    assert rec["speculative_wins"] >= 1
    assert rec["query_retries"] == 0
    assert all("a0." in t for t in launches), launches


def test_user_error_is_never_retried(task_cluster):
    """A USER-typed failure (deterministic) fails the query fast: zero
    task retries, zero query retries, and the TrinoError names the real
    remote failure including its traceback."""
    c = task_cluster
    _await_capacity(c)
    qid = _next_qid(c)
    c.fault_schedule.add(f"{qid}.f0", "user-error")
    mark = len(c.task_launches)
    before = (c.recovery_total.task_retries,
              c.recovery_total.query_retries)
    with pytest.raises(TrinoError) as ei:
        c.execute(Q1)
    assert ei.value.code == "DIVISION_BY_ZERO"
    assert "injected user error" in str(ei.value)
    assert "remote traceback" in str(ei.value)
    launches = _launches_since(c, mark)
    assert not any(".r1" in t or ".spec" in t or "a1." in t
                   for t in launches), launches
    assert (c.recovery_total.task_retries,
            c.recovery_total.query_retries) == before


def test_query_deadline_enforced_as_user_error(task_cluster):
    """query_max_run_time caps the query across all RPCs and raises
    EXCEEDED_TIME_LIMIT — classified USER, so no retry burns the
    remaining budget on a doomed query."""
    c = task_cluster
    _await_capacity(c)
    mark = len(c.task_launches)
    c.session.properties["query_max_run_time"] = 0.001
    try:
        with pytest.raises(TrinoError) as ei:
            c.execute(Q1)
    finally:
        del c.session.properties["query_max_run_time"]
    assert ei.value.code == "EXCEEDED_TIME_LIMIT"
    launches = _launches_since(c, mark)
    assert not any("a1." in t for t in launches), launches


def test_worker_replacement_resyncs_replicated_tables(task_cluster):
    """Replacement is a full re-register: the new process receives the
    replicated memory-catalog tables, so distributed scans of local
    replicas stay correct after the swap."""
    c = task_cluster
    _await_capacity(c)
    c.execute("create table memory.default.chaos_t as "
              "select n_nationkey k, n_name from tpch.micro.nation")
    victim = c.workers[0]
    victim.proc.kill()
    victim.proc.wait(timeout=10)
    _await_capacity(c)
    assert c.workers[0].proc.pid != victim.proc.pid
    res = c.execute("select count(*) from memory.default.chaos_t")
    assert res.rows == [(25,)]
    c.execute("drop table memory.default.chaos_t")


def test_explain_analyze_surfaces_recovery(task_cluster):
    """EXPLAIN ANALYZE on the process runner renders the recovery
    counters (attempts, retries by type, backoff) for a faulted run."""
    c = task_cluster
    _await_capacity(c)
    qid = _next_qid(c)
    c.fault_schedule.add(f"{qid}.f1", "error")
    res = c.execute("explain analyze " + Q1)
    text = "\n".join(r[0] for r in res.rows)
    assert "Recovery:" in text, text
    assert "task retries" in text
    assert "INTERNAL=1" in text


def test_chaos_events_recorded(task_cluster):
    """The event listener SPI observed the module's self-healing:
    replacements and typed retries fanned out to listeners."""
    assert any(e.new_pid != e.old_pid for e in RECORDER.replaced)
    assert any(e.error_type == "EXTERNAL" for e in RECORDER.retries)
    assert any(e.speculative for e in RECORDER.retries)


# ---------------------------------------------------------- QUERY policy ----


def test_rpc_drop_mid_frame_recovers_in_place(local, barrier_cluster):
    """A connection torn mid-frame during a result pull is retried at
    the transport layer (each get_results response is an independent
    snapshot): NO task relaunch, NO query retry — zero extra launches
    vs the fault-free run."""
    c = barrier_cluster
    mark0 = len(c.task_launches)
    clean = sorted(c.execute(Q1).rows)
    assert clean == sorted(local.execute(Q1).rows)
    clean_count = len(c.task_launches) - mark0
    qid = _next_qid(c)
    c.fault_schedule.add(f"{qid}.f1", "drop-connection")
    mark = len(c.task_launches)
    res = c.execute(Q1)
    assert sorted(res.rows) == clean
    launches = _launches_since(c, mark)
    # identical attempt shape to the fault-free run: no silent
    # double-launch anywhere
    assert len(launches) == clean_count, (launches, clean_count)
    assert not any(".r1" in t or "a1." in t for t in launches), launches
    assert res.stats["recovery"]["retries_by_type"].get(
        "EXTERNAL", 0) >= 1
    assert res.stats["recovery"]["query_retries"] == 0


def test_kill_worker_streaming_query_retry(local, stream_cluster):
    """Streaming outputs are not durable: a killed worker loses them,
    the query retries wholesale on the healed cluster, answers stay
    exact."""
    c = stream_cluster
    clean = sorted(c.execute(Q1).rows)
    assert clean == sorted(local.execute(Q1).rows)
    qid = _next_qid(c)
    c.fault_schedule.add(f"{qid}.f0", "kill-worker")
    mark = len(c.task_launches)
    res = c.execute(Q1)
    assert sorted(res.rows) == clean
    launches = _launches_since(c, mark)
    assert any("a1." in t for t in launches), launches
    assert res.stats["recovery"]["query_retries"] >= 1
    _await_capacity(c)


def test_rpc_drop_streaming_replays_in_place(stream_cluster):
    """A mid-frame drop on the streaming pull RECOVERS IN PLACE: the
    producer retains unacked frames (_RetainedStream), so the channel
    reconnects and replays them byte-identically from its cursor — zero
    full-query restarts for a single dropped connection, identical
    attempt shape to the fault-free run."""
    c = stream_cluster
    _await_capacity(c)
    mark0 = len(c.task_launches)
    clean = sorted(c.execute(Q1).rows)
    clean_count = len(c.task_launches) - mark0
    qid = _next_qid(c)
    c.fault_schedule.add(f"{qid}.f0", "drop-connection")
    mark = len(c.task_launches)
    res = c.execute(Q1)
    assert sorted(res.rows) == clean
    assert res.stats["recovery"]["query_retries"] == 0
    launches = _launches_since(c, mark)
    assert len(launches) == clean_count, (launches, clean_count)
    assert not any("a1." in t for t in launches), launches


def test_rpc_drop_streaming_repeated_drops_still_replay(stream_cluster):
    """Several torn connections across the query's streaming pulls
    (one drop per producer task, on both fragments) all replay in
    place — drops on independent streams never accumulate toward any
    shared budget or escalate to a query retry."""
    c = stream_cluster
    _await_capacity(c)
    clean = sorted(c.execute(Q3).rows)
    qid = _next_qid(c)
    c.fault_schedule.add(f"{qid}.f0", "drop-connection", times=2)
    c.fault_schedule.add(f"{qid}.f1", "drop-connection")
    res = c.execute(Q3)
    assert sorted(res.rows) == clean
    assert res.stats["recovery"]["query_retries"] == 0


def test_user_error_fails_fast_streaming(stream_cluster):
    """The taxonomy propagates transitively through streaming pulls:
    a USER error in a mid-plan task surfaces as the original error with
    zero query retries."""
    c = stream_cluster
    _await_capacity(c)
    qid = _next_qid(c)
    c.fault_schedule.add(f"{qid}.f0", "user-error")
    mark = len(c.task_launches)
    with pytest.raises(TrinoError) as ei:
        c.execute(Q1)
    assert ei.value.code == "DIVISION_BY_ZERO"
    assert "injected user error" in str(ei.value)
    launches = _launches_since(c, mark)
    assert not any("a1." in t for t in launches), launches


# ---------------------------------------------------- memory governance ----


def test_memory_escalation_retry(barrier_cluster):
    """THE memory-governance acceptance path: an attempt that dies with
    INSUFFICIENT_RESOURCES (per-query cap far below the working set)
    re-admits with a GROWN budget — max(retry_initial_memory, 2x the
    observed peak the worker piggybacked on its failure response) — and
    a halved task width, instead of replaying the identical doomed
    plan."""
    c = barrier_cluster
    _await_capacity(c)
    clean = sorted(c.execute(Q1).rows)
    saved = dict(c.session.properties)
    c.session.properties.update({"query_max_memory_bytes": 60_000,
                                 "retry_initial_memory": 1 << 30})
    mark = len(c.task_launches)
    try:
        res = c.execute(Q1)
    finally:
        c.session.properties.clear()
        c.session.properties.update(saved)
    assert sorted(res.rows) == clean
    rec = res.stats["recovery"]
    assert rec["memory_escalations"] >= 1
    assert rec["retries_by_type"].get("INSUFFICIENT_RESOURCES", 0) >= 1
    launches = _launches_since(c, mark)
    # width reduction: the escalated attempt (a1) runs its partitioned
    # fragments at half width -> no .t1 tasks
    a1 = [t for t in launches if "a1." in t]
    assert a1, launches
    assert not any(".t1" in t for t in a1), a1
    # the configured session must come back untouched (overrides are
    # per-attempt state, not global mutation)
    assert c.session.properties == saved


def test_low_memory_killer_kills_policy_victim(barrier_cluster):
    """Cluster-overcommit: with a blocked node attributing the largest
    reservation to the in-flight query, the governance tick kills
    exactly the policy-chosen victim (EXCEEDED_CLUSTER_MEMORY); the
    victim then SUCCEEDS on retry while a concurrent query finishes
    unharmed."""
    from trino_tpu.events import EventListener

    class KillRecorder(EventListener):
        def __init__(self):
            self.kills = []

        def memory_kill(self, event):
            self.kills.append(event)

    c = barrier_cluster
    _await_capacity(c)
    rec = KillRecorder()
    c.event_manager.add(rec)
    clean = sorted(c.execute(Q1).rows)
    victim_qid = _next_qid(c)
    # slow the victim's scan tasks so the kill window is open
    c.fault_schedule.add(f"{victim_qid}.f1", "delay", times=2,
                         delay_s=1.5)
    results = {}

    def run_victim():
        results["victim"] = sorted(c.execute(Q1).rows)

    th = threading.Thread(target=run_victim, daemon=True)
    th.start()
    time.sleep(0.4)  # victim tasks are now sleeping in their delay
    # a blocked node reports the victim attempt as its largest holder
    # (synthetic worker id: real heartbeats never overwrite it)
    c.cluster_memory.update(99, {
        "max_bytes": 1000, "reserved_bytes": 1000, "blocked_events": 1,
        "queries": {victim_qid: {"reserved": 900, "peak": 900},
                    "tiny_q": {"reserved": 100, "peak": 100}}})
    assert c.run_memory_governance() == victim_qid
    # a concurrent query sails through while the victim is dying
    assert sorted(c.execute(Q1).rows) == clean
    th.join(timeout=60)
    assert not th.is_alive()
    c.cluster_memory.forget_worker(99)
    assert results["victim"] == clean
    assert [e.query_id for e in rec.kills] == [victim_qid]
    assert rec.kills[0].policy == "total-reservation-on-blocked-nodes"


def test_barrier_driver_observes_abort_at_page_boundaries():
    """The kill path's lever INSIDE a worker: a barrier (non-streaming)
    task polls its abort flag at every page-move quantum, so an
    abort_task broadcast (low-memory kill, superseded attempt) stops
    the driver mid-execution — not after it drained its pipeline."""
    from trino_tpu.parallel.fault import RemoteTaskError
    from trino_tpu.parallel.remote_exchange import run_barrier_driver

    class Driver:
        def __init__(self, finish_at=None, abort=None, abort_at=None):
            self.quanta = 0
            self._finish_at = finish_at
            self._abort = abort
            self._abort_at = abort_at

        def process(self):
            self.quanta += 1
            if self._abort_at == self.quanta:
                self._abort.set()
            return self._finish_at == self.quanta

        def close(self):
            self.closed = True

    # pre-set abort: not a single page moves
    d = Driver()
    with pytest.raises(RemoteTaskError):
        run_barrier_driver(d, _set_event())
    assert d.quanta == 0 and d.closed  # its scan reads ahead no more
    # abort lands mid-run: observed at the NEXT page boundary
    ev = threading.Event()
    d = Driver(abort=ev, abort_at=7)
    with pytest.raises(RemoteTaskError):
        run_barrier_driver(d, ev)
    assert d.quanta == 7
    # flag never set: the driver runs to completion untouched
    d = Driver(finish_at=3)
    run_barrier_driver(d, threading.Event())
    assert d.quanta == 3
    # a driver that can NEVER finish still terminates (stuck-pipeline
    # bound), it does not spin the worker thread forever
    with pytest.raises(RemoteTaskError):
        run_barrier_driver(Driver(), threading.Event(), max_quanta=100)


def _set_event():
    ev = threading.Event()
    ev.set()
    return ev


def test_heartbeat_piggybacks_pool_snapshots(barrier_cluster):
    """Stats parity: what the ClusterMemoryManager aggregated from the
    heartbeat must equal what the workers report when asked directly."""
    from trino_tpu.parallel.rpc import call

    c = barrier_cluster
    _await_capacity(c)
    c.execute(Q1)
    c.heartbeat()
    stats = c.cluster_memory.cluster_stats()
    direct = []
    for w in c.workers:
        resp = call(w.addr, {"op": "ping"}, timeout=10)
        assert resp.get("memory") is not None
        direct.append(resp["memory"])
    assert stats["workers"] == len(c.workers)
    assert stats["total_max_bytes"] == sum(m["max_bytes"]
                                           for m in direct)
    # per-query peaks flowed through: the finished query left its peak
    # in some worker's released-peaks section
    peaks = [q["peak"] for m in direct
             for q in m.get("queries", {}).values()]
    assert any(p > 0 for p in peaks)
    # EXPLAIN ANALYZE surfaces the cluster view
    res = c.execute("explain analyze " + Q1)
    text = "\n".join(r[0] for r in res.rows)
    assert "Cluster memory:" in text


# ------------------------------------------------- hybrid join chaos ----


def _spill_records():
    """Hybrid-join spill records currently in the coordinator's HBO
    store (worker demotions ride task responses into it — the witness
    that a fault actually demoted build partitions, not just fired)."""
    from trino_tpu.telemetry import stats_store

    st = stats_store.store()
    with st._lock:
        return [h.spill for s in st._stmts.values()
                for h in s["nodes"].values() if h.spill is not None]


def test_revoke_memory_mid_build_hybrid_join(barrier_cluster):
    """A seeded revoke-memory fault forces a full pool revocation
    early in the join stage (mid-BUILD): the builder enters
    partitioned mode and demotes partitions in place — the query
    completes byte-equal with ZERO retries of any kind."""
    c = barrier_cluster
    _await_capacity(c)
    clean = c.execute(Q3).rows
    before = len(_spill_records())
    qid = _next_qid(c)
    c.fault_schedule.add(f"{qid}.", "revoke-memory", times=16,
                         countdown=2)
    res = c.execute(Q3)
    assert res.rows == clean
    rec = res.stats["recovery"]
    assert rec["query_retries"] == 0, rec
    assert rec["task_retries"] == 0, rec
    assert len(_spill_records()) > before, \
        "no partition demotion reached the coordinator's history store"


def test_revoke_memory_mid_probe_hybrid_join(barrier_cluster):
    """Same fault armed DEEP into the task (mid-PROBE / downstream):
    cold probe rows park beside their build partition and replay in
    the deferred per-partition passes — still byte-equal, still zero
    retries."""
    c = barrier_cluster
    _await_capacity(c)
    clean = c.execute(Q3).rows
    qid = _next_qid(c)
    c.fault_schedule.add(f"{qid}.", "revoke-memory", times=16,
                         countdown=24)
    res = c.execute(Q3)
    assert res.rows == clean
    rec = res.stats["recovery"]
    assert rec["query_retries"] == 0, rec
    assert rec["task_retries"] == 0, rec


def test_kill_worker_during_partitioned_spill_join(task_cluster):
    """kill-worker lands while the join stage is running partitioned
    (a revoke-memory fault demoted build partitions first): TASK
    policy recovers inside attempt 0 and the answer stays byte-equal
    to the fault-free oracle."""
    c = task_cluster
    _await_capacity(c)
    clean = getattr(c, "_q3_clean", None) or c.execute(Q3).rows
    qid = _next_qid(c)
    c.fault_schedule.add(f"{qid}.", "revoke-memory", times=16,
                         countdown=2)
    c.fault_schedule.add(f"{qid}.f1", "kill-worker")
    mark = len(c.task_launches)
    res = c.execute(Q3)
    assert res.rows == clean
    launches = _launches_since(c, mark)
    assert all("a0." in t for t in launches), launches
    rec = res.stats["recovery"]
    assert rec["query_retries"] == 0
    _await_capacity(c)


# ------------------------------- elastic cluster + partial-stage retry ----


@pytest.fixture(scope="module")
def elastic_cluster():
    """partial_stage_retry over the default streaming shape: producers
    retain their serialized frames (durable streams), tee pages into
    the external spool backend, and consumers resolve lost producers
    through the coordinator's resolve_task op — the elastic-cluster
    fault model where task output outlives its worker."""
    s = _mk_session(retry_policy="QUERY", partial_stage_retry=True)
    with ProcessQueryRunner(CATALOGS, s, n_workers=2, desired_splits=4,
                            broadcast_threshold=300.0,
                            heartbeat_interval=0.25) as c:
        c.fault_schedule = FaultSchedule(seed=42)
        yield c


def test_partial_retry_restarts_only_lost_tasks(local, elastic_cluster):
    """THE acceptance scenario: a producer-task worker dies mid-stream
    during a multi-stage streaming query. ONLY the lost tasks restart
    (same wire ids, ``.r1`` markers), consumers resume from their ack
    cursors, results stay byte-equal, and the query-retry counter stays
    at ZERO — no wholesale re-execution."""
    c = elastic_cluster
    clean = sorted(c.execute(Q1).rows)
    assert clean == sorted(local.execute(Q1).rows)
    qid = _next_qid(c)
    c.fault_schedule.add(f"{qid}.f1", "kill-worker")
    mark = len(c.task_launches)
    res = c.execute(Q1)
    assert sorted(res.rows) == clean
    rec = res.stats["recovery"]
    assert rec["query_retries"] == 0, rec
    launches = _launches_since(c, mark)
    assert not any("a1." in t for t in launches), launches
    assert any(".r1" in t for t in launches), launches
    _await_capacity(c)
    assert sorted(c.execute(Q1).rows) == clean


def test_partial_retry_join_pipeline(elastic_cluster):
    """Same fault against the join+TopN pipeline (4 fragments, merge
    output): the resolve cascade repoints merge channels too, still
    zero query retries, still byte-equal."""
    c = elastic_cluster
    _await_capacity(c)
    clean = c.execute(Q3).rows
    qid = _next_qid(c)
    c.fault_schedule.add(f"{qid}.f1", "kill-worker")
    res = c.execute(Q3)
    assert res.rows == clean
    assert res.stats["recovery"]["query_retries"] == 0
    _await_capacity(c)


def test_scale_down_mid_query_streaming(elastic_cluster):
    """retire_worker(drain=True) while a streaming query runs: the
    slot drains (finishes its tasks) before the process dies, the
    in-flight query loses nothing, and the shrunk cluster keeps
    answering correctly."""
    c = elastic_cluster
    _await_capacity(c)
    clean = sorted(c.execute(Q1).rows)
    assert c.add_workers(1, reason="test-grow") == 1
    results = {}

    def run_q():
        results["r"] = c.execute(Q1)

    th = threading.Thread(target=run_q, daemon=True)
    th.start()
    time.sleep(0.05)
    assert c.retire_worker(len(c.workers) - 1, drain=True, timeout=60)
    th.join(timeout=60)
    assert not th.is_alive()
    assert sorted(results["r"].rows) == clean
    assert results["r"].stats["recovery"]["query_retries"] == 0
    assert len(c.workers) == 2
    assert sorted(c.execute(Q1).rows) == clean


def test_scale_down_mid_query_barrier(elastic_cluster):
    """Drain-based retire under the barrier shape: stage results on the
    draining worker are pulled before it exits — loss-free, zero
    retries of any kind."""
    c = elastic_cluster
    _await_capacity(c)
    saved = dict(c.session.properties)
    c.session.properties["streaming_execution"] = False
    try:
        clean = sorted(c.execute(Q1).rows)
        assert c.add_workers(1, reason="test-grow") == 1
        results = {}

        def run_q():
            results["r"] = c.execute(Q1)

        th = threading.Thread(target=run_q, daemon=True)
        th.start()
        time.sleep(0.05)
        assert c.retire_worker(len(c.workers) - 1, drain=True,
                               timeout=60)
        th.join(timeout=60)
        assert not th.is_alive()
    finally:
        c.session.properties.clear()
        c.session.properties.update(saved)
    assert sorted(results["r"].rows) == clean
    # a stage launch may race the retire onto the dying slot; the
    # lost-worker seam absorbs it as a task retry — never a query retry
    assert results["r"].stats["recovery"]["query_retries"] == 0
    assert len(c.workers) == 2


def test_membership_churn_races_heal(elastic_cluster):
    """A worker dies the moment the membership is also growing: the
    heal loop replaces the dead slot while add_workers registers a new
    one — no lost slots, no double-registration, queries stay exact,
    and the ledger recorded every transition."""
    c = elastic_cluster
    _await_capacity(c)
    clean = sorted(c.execute(Q1).rows)
    joined_before, retired_before = c.cluster.counts()
    victim = c.workers[0]
    victim.proc.kill()
    assert c.add_workers(1, reason="churn") == 1
    _await_capacity(c)
    assert sorted(c.execute(Q1).rows) == clean
    assert c.retire_worker(len(c.workers) - 1, drain=True, timeout=60)
    assert len(c.workers) == 2
    joined, retired = c.cluster.counts()
    assert joined >= joined_before + 2   # churn join + heal replacement
    assert retired >= retired_before + 2  # killed slot + drained retire
    active = [n for n in c.cluster.snapshot() if n.state == "active"]
    assert len(active) == len(c.workers)


def test_kill_after_publish_served_from_spool(task_cluster):
    """A worker dies right AFTER durably publishing a task's output:
    the output outlives the process — the coordinator adopts the
    published spool bytes instead of relaunching the task (zero
    retries), and the dead slot heals in the background."""
    c = task_cluster
    _await_capacity(c)
    clean = getattr(c, "_q1_clean", None) or sorted(c.execute(Q1).rows)
    pids = sorted(w.proc.pid for w in c.workers)
    qid = _next_qid(c)
    c.fault_schedule.add(f"{qid}.f1", "kill-after-publish")
    mark = len(c.task_launches)
    res = c.execute(Q1)
    assert sorted(res.rows) == clean
    launches = _launches_since(c, mark)
    assert not any(".r1" in t for t in launches
                   if f"{qid}.f1." in t), launches
    rec = res.stats["recovery"]
    assert rec["task_retries"] == 0, rec
    assert rec["query_retries"] == 0, rec
    _await_capacity(c)
    # the fault really killed a process: one slot healed to a new pid
    assert sorted(w.proc.pid for w in c.workers) != pids


def test_stream_spool_corruption_is_loud_and_typed():
    """A corrupted committed spool object fails the reader with the
    typed SpoolCorruption — short reads and checksum mismatches never
    surface as silently-partial rows."""
    import os

    from trino_tpu import types as T
    from trino_tpu.block import Page
    from trino_tpu.parallel.spool import SpoolCorruption
    from trino_tpu.parallel.spool_backend import (
        LocalFileSpoolBackend, SpooledTaskWriter, committed_attempt,
        open_committed_partition, partition_key)

    be = LocalFileSpoolBackend()
    try:
        w = SpooledTaskWriter(be, "qx", 0, 0, 0, 1)
        w.add(0, Page.from_pylists([T.BIGINT, T.VARCHAR],
                                   [[1, 2], ["a", "b"]]))
        assert w.commit()
        assert committed_attempt(be, "qx", 0, 0) == 0
        path = os.path.join(be.base_dir,
                            partition_key("qx", 0, 0, 0, 0))
        with open(path, "r+b") as f:
            f.truncate(os.path.getsize(path) - 3)
        with pytest.raises(SpoolCorruption):
            open_committed_partition(be, "qx", 0, 0, 0).pages()
    finally:
        be.remove_all()


def test_sizing_seed_ships_to_joining_worker(elastic_cluster):
    """Exchange-sizing knowledge crosses the membership boundary: a
    joining worker is configured with the coordinator's merged sizing
    history and acknowledges how many entries it imported."""
    from trino_tpu.parallel.device_exchange import SIZING_HISTORY

    c = elastic_cluster
    _await_capacity(c)
    SIZING_HISTORY.import_seed(
        [[[["bigint"], "chaos-synthetic", 2, 4], 321.0, 3, None]])
    assert c.add_workers(1, reason="seed-test") == 1
    try:
        assert c.workers[-1].sizing_seeded >= 1
    finally:
        assert c.retire_worker(len(c.workers) - 1, drain=True,
                               timeout=60)
    assert len(c.workers) == 2


def test_system_runtime_nodes_reflects_ledger(elastic_cluster):
    """system.runtime.nodes is the SQL view of the membership ledger:
    one ACTIVE row per live slot, RETIRED rows for everything the
    module churned through, generations monotonic."""
    c = elastic_cluster
    _await_capacity(c)
    rows = c.execute(
        "select node_id, address, state, pid, generation "
        "from system.runtime.nodes").rows
    active = [r for r in rows if r[2] == "ACTIVE"]
    assert len(active) == len(c.workers)
    live_pids = {w.proc.pid for w in c.workers}
    assert {r[3] for r in active} == live_pids
    assert any(r[2] == "RETIRED" for r in rows)
    gens = [r[4] for r in rows]
    assert gens == sorted(gens)
    # elastic metrics families are registered alongside
    fams = {f["name"] for f in c.metrics_families()}
    assert {"trino_cluster_size", "trino_nodes_total",
            "trino_autoscaler_target_workers"} <= fams


# ------------------------------------- the autoscaler on a live cluster ----


def test_autoscaler_grows_a_live_cluster_under_a_burst_and_drains_it():
    """Queue depth against a resource group of two makes the autoscaler
    grow the membership 2 -> 4 while the burst runs; every answer of
    the burst is the clean one with no query retry; a statement planned
    at the grown width places tasks on the joiners; idle drains the
    cluster back to its floor one worker at a time and the answer is
    still the clean one."""
    from trino_tpu.resource_groups import ResourceGroupManager

    rg = ResourceGroupManager.from_config({"groups": [
        {"name": "global", "max_concurrency": 2, "max_queued": 10_000}]})
    s = _mk_session(retry_policy="QUERY", partial_stage_retry=True,
                    autoscale_enabled=True, autoscale_min_workers=2,
                    autoscale_max_workers=4, autoscale_cooldown_s=0.5,
                    autoscale_up_queue_depth=1,
                    autoscale_down_idle_ticks=4)
    with ProcessQueryRunner(CATALOGS, s, n_workers=2, desired_splits=4,
                            heartbeat_interval=0.25,
                            resource_groups=rg) as c:
        clean = sorted(c.execute(Q1).rows)
        lock = threading.Lock()
        burst, failures = [], []
        # the burst keeps the queue pressed until the membership has
        # grown: spawning a worker takes seconds
        grown = threading.Event()

        def one():
            for _ in range(40):
                if grown.is_set():
                    return
                try:
                    r = c.execute(Q1)
                except Exception as e:  # reported below
                    with lock:
                        failures.append(repr(e))
                    return
                with lock:
                    burst.append((sorted(r.rows) == clean,
                                  r.stats["recovery"]["query_retries"]))

        threads = [threading.Thread(target=one) for _ in range(8)]
        for t in threads:
            t.start()
        deadline = time.time() + 90
        while any(t.is_alive() for t in threads):
            if len(c.workers) >= 4 or time.time() > deadline:
                grown.set()
            time.sleep(0.05)
        for t in threads:
            t.join()
        assert failures == []
        assert len(c.workers) == 4
        assert burst and all(same for same, _ in burst)
        assert all(retries == 0 for _, retries in burst)
        mark = len(c.task_launches)
        assert sorted(c.execute(Q1).rows) == clean
        assert any(".t2" in t for t in c.task_launches[mark:])
        deadline = time.time() + 120
        while time.time() < deadline and len(c.workers) > 2:
            time.sleep(0.2)
        assert len(c.workers) == 2
        assert sorted(c.execute(Q1).rows) == clean
        snap = c.autoscaler.snapshot()
        assert snap["scale_ups"] >= 1 and snap["scale_downs"] >= 2
