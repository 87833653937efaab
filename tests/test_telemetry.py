"""End-to-end query telemetry: distributed trace spans, the cluster
metrics registry, and system.runtime introspection.

Reference analog: the reference's OpenTelemetry span instrumentation +
JMX/metrics exposition + QuerySystemTable/TaskSystemTable, exercised
across REAL process boundaries: a 2-worker ProcessQueryRunner produces
one connected trace tree per query (coordinator + worker spans merged
via RPC piggyback), a Prometheus scrape surface, and SQL-queryable
runtime state.  The module-scoped cluster keeps worker spawns to one
pair; the kill-worker chaos case runs LAST (its replacement worker is
cold).
"""

import json
import threading
import time

import pytest

from trino_tpu.parallel.process_runner import ProcessQueryRunner
from trino_tpu.sql.analyzer import Session
from trino_tpu.telemetry.metrics import (ClusterMetrics, MetricsRegistry,
                                         parse_prometheus,
                                         render_prometheus)
from trino_tpu.telemetry.tracing import (NULL_TRACER, Tracer, span_tree,
                                         trace_line)

CATALOGS = {"tpch": {"connector": "tpch", "page_rows": 4096}}

Q3ISH = ("select c.c_custkey, o.o_orderkey from customer c "
         "join orders o on c.c_custkey = o.o_custkey "
         "where c.c_mktsegment = 'BUILDING' "
         "order by o.o_orderkey limit 10")


@pytest.fixture(scope="module")
def cluster():
    runner = ProcessQueryRunner(
        CATALOGS, Session(catalog="tpch", schema="micro"),
        n_workers=2, desired_splits=4, broadcast_threshold=300.0,
        heartbeat_interval=None)
    yield runner
    runner.close()


# -- tracer / metrics core ------------------------------------------------


def test_null_tracer_zero_cost():
    from trino_tpu.parallel.rpc import with_trace

    with NULL_TRACER.span("query") as s:
        assert s.context() is None and not s
        with NULL_TRACER.span("child", parent=s) as c:
            c.set("k", 1)
    assert NULL_TRACER.finished() == []
    req = with_trace({"op": "run_task"}, s)
    assert "trace" not in req  # nothing ships when tracing is off


def test_cross_process_parenting():
    t = Tracer(process="coordinator")
    with t.span("query") as root:
        ctx = root.context(attempt=2, speculative=False)
        assert ctx["trace_id"] == t.trace_id
        assert ctx["traceparent"].startswith(f"00-{t.trace_id}-")
        w = Tracer(process="worker-9", trace_id=ctx["trace_id"])
        with w.span("task x", parent=ctx) as task:
            assert task.parent_id == root.span_id
            assert task.trace_id == t.trace_id
        t.add_finished(w.finished())
    roots, children, orphans = span_tree(t.finished())
    assert len(roots) == 1 and not orphans
    assert children[root.span_id][0]["name"] == "task x"


def test_metrics_exposition_roundtrip():
    reg = MetricsRegistry()
    reg.counter("trino_t_total", "help").inc(2, kind="a")
    reg.counter("trino_t_total").inc(3, kind="b")
    reg.gauge("trino_g").set(1.25)
    reg.histogram("trino_h").observe(0.4)
    reg.gauge_fn("trino_live", "pull-time", lambda: 7.0)
    cm = ClusterMetrics()
    cm.update(0, [{"name": "trino_g", "type": "gauge", "help": "",
                   "samples": [[{}, 9.0]]}])
    text = render_prometheus(cm.collect(reg.collect()))
    parsed = parse_prometheus(text)
    assert parsed["trino_t_total"][
        '{kind="a",process="coordinator"}'] == 2.0
    # worker sample merged under its own labels, same family
    assert parsed["trino_g"][
        '{process="worker",worker="0"}'] == 9.0
    assert parsed["trino_h_count"]['{process="coordinator"}'] == 1.0
    assert parsed["trino_live"]['{process="coordinator"}'] == 7.0


def test_event_history_ring_and_stats_payload():
    from trino_tpu.events import EventListenerManager, QueryMonitor

    mgr = EventListenerManager(history_capacity=2)
    m1 = QueryMonitor(mgr, "alice", "select 1")
    m1.created()
    assert [e.query_id for e in mgr.running()] == [m1.query_id]
    m1.completed(5, stats={"peak_memory_bytes": 123, "wall_ms": 1.5})
    assert mgr.running() == []
    for i in range(3):  # ring: capacity 2 evicts the oldest
        m = QueryMonitor(mgr, "alice", f"select {i}")
        m.created()
        m.completed(1)
    hist = mgr.history(10)
    assert len(hist) == 2
    assert all(e.state == "FINISHED" for e in hist)
    # the first query's stats payload was ring-evicted with it; a fresh
    # completion still carries stats through
    m2 = QueryMonitor(mgr, "bob", "select 2")
    m2.created()
    m2.completed(1, stats={"peak_memory_bytes": 7})
    assert mgr.history(1)[0].stats == {"peak_memory_bytes": 7}


# -- distributed trace assembly -------------------------------------------


def test_q3_distributed_trace_tree(cluster):
    res = cluster.execute(Q3ISH)
    assert len(res.rows) == 10
    spans = res.stats["trace"]
    roots, children, orphans = span_tree(spans)
    assert len(roots) == 1 and roots[0]["name"] == "query"
    assert orphans == [], [s["name"] for s in orphans]
    assert len({s["trace_id"] for s in spans}) == 1
    workers = {s["process"] for s in spans
               if s["process"].startswith("worker-")}
    assert len(workers) >= 2, workers
    # worker task spans exist for every non-output fragment and carry
    # their fragment id
    tasks = [s for s in spans
             if s["attrs"].get("span_kind") == "task"
             and s["process"].startswith("worker-")]
    assert tasks and all(s["attrs"].get("fragment") is not None
                         for s in tasks)
    assert trace_line(spans).startswith("Trace: ")


def test_barrier_operator_spans_account_for_task_wall(cluster):
    """In barrier mode a task's wall is spent INSIDE operator calls
    (exchange pulls included), so per-task operator busy must sum to
    within 10% of the exec span."""
    cluster.session.properties["streaming_execution"] = False
    try:
        res = cluster.execute(Q3ISH)
    finally:
        cluster.session.properties.pop("streaming_execution", None)
    spans = res.stats["trace"]
    _, children, orphans = span_tree(spans)
    assert orphans == []
    execs = [s for s in spans if s["attrs"].get("span_kind") == "exec"
             and s["process"].startswith("worker-")]
    assert execs
    wall = sum(e["end"] - e["start"] for e in execs)
    busy = sum(o["end"] - o["start"]
               for e in execs
               for o in children.get(e["span_id"], ())
               if o["attrs"].get("span_kind") == "operator")
    assert wall > 0
    assert busy >= 0.9 * wall, \
        f"operator spans {busy * 1e3:.1f}ms vs exec {wall * 1e3:.1f}ms"


def test_explain_analyze_trace_line(cluster):
    res = cluster.execute("explain analyze " + Q3ISH)
    text = "\n".join(r[0] for r in res.rows)
    assert "Trace: " in text and "critical path" in text


def test_tracing_disabled_is_clean(cluster):
    cluster.session.properties["query_tracing_enabled"] = False
    try:
        res = cluster.execute("select count(*) from nation")
    finally:
        cluster.session.properties.pop("query_tracing_enabled", None)
    assert "trace" not in (res.stats or {})


# -- metrics + system.runtime ---------------------------------------------


def test_protocol_scrape_end_to_end(cluster):
    """CI smoke: boot ProtocolServer over the live cluster, run a query
    through the HTTP protocol, scrape /v1/metrics and /v1/query/{id}."""
    import urllib.error
    import urllib.request

    from trino_tpu.client import Client
    from trino_tpu.server.protocol import ProtocolServer

    cluster.heartbeat()  # pull worker metric snapshots in
    srv = ProtocolServer(cluster, page_size=100).start()
    try:
        expected = cluster.execute(
            "select count(*) from lineitem").rows[0][0]
        res = Client(srv.uri).execute(
            "select count(*) c from lineitem")
        assert res.rows == [[expected]]
        with urllib.request.urlopen(srv.uri + "/v1/metrics") as r:
            assert "text/plain" in r.headers["Content-Type"]
            text = r.read().decode()
        parsed = parse_prometheus(text)
        # exchange, memory, recovery AND per-worker series all present
        assert "trino_exchange_splits_total" in parsed
        assert "trino_recovery_events_total" in parsed
        assert "trino_cluster_memory_bytes" in parsed
        assert any('process="worker"' in lbl
                   for lbl in parsed.get("trino_node_memory_bytes", {}))
        assert "trino_http_statements_total" in parsed
        # /v1/query/{id}: the finished query's stats tree, with trace
        qid = list(srv.finished)[-1]
        with urllib.request.urlopen(srv.uri + f"/v1/query/{qid}") as r:
            info = json.loads(r.read())
        assert info["state"] == "FINISHED" and info["rows"] == 1
        assert info["stats"]["wall_ms"] > 0
        assert info["stats"]["trace"], "trace spans missing from stats"
        srv.evict_query(qid)
        with pytest.raises(urllib.error.HTTPError) as err:
            urllib.request.urlopen(srv.uri + f"/v1/query/{qid}")
        assert err.value.code == 404
    finally:
        srv.stop()


def test_system_runtime_shows_running_query(cluster):
    """A concurrently-executing query must appear in
    system.runtime.queries with state RUNNING, and its tasks in
    system.runtime.tasks — live introspection, not post-hoc history."""
    marker = "select c_custkey from customer where c_custkey < 77"
    qid = f"q{cluster._task_seq + 1}a0"
    cluster.fault_schedule.add(f"{qid}.f", "delay", times=2,
                               delay_s=3.0)
    done = {}

    def run():
        done["res"] = cluster.execute(marker)

    th = threading.Thread(target=run, daemon=True)
    th.start()
    deadline = time.monotonic() + 10
    saw_running = saw_tasks = False
    while time.monotonic() < deadline and not (saw_running
                                               and saw_tasks):
        rows = cluster.execute(
            "select query, state from system.runtime.queries "
            "where state = 'RUNNING'").rows
        saw_running = saw_running or any(r[0] == marker for r in rows)
        trows = cluster.execute(
            "select task_id, worker, state "
            "from system.runtime.tasks").rows
        saw_tasks = saw_tasks or len(trows) > 0
        time.sleep(0.1)
    th.join(timeout=30)
    assert saw_running, "running query never surfaced"
    assert saw_tasks, "its tasks never surfaced"
    assert len(done["res"].rows) == 76
    # completed: the history-backed row carries rows + wall
    hist = cluster.execute(
        "select query, state, rows from system.runtime.queries "
        f"where query = '{marker.replace(chr(39), chr(39) * 2)}' "
        "and state = 'FINISHED'").rows
    assert hist and hist[-1][2] == 76


def test_system_runtime_metrics_sql(cluster):
    rows = cluster.execute(
        "select name, labels, value from system.runtime.metrics "
        "where name = 'trino_recovery_events_total'").rows
    kinds = {r[1] for r in rows}
    assert any("task_attempts" in k for k in kinds)
    assert all(r[2] >= 0 for r in rows)
    # completed-query counter reflects this module's activity
    rows = cluster.execute(
        "select value from system.runtime.metrics "
        "where name = 'trino_queries_total' "
        "and labels like '%FINISHED%'").rows
    assert rows and rows[0][0] >= 1


def test_completed_event_carries_stats_payload(cluster):
    cluster.execute("select count(*) from orders")
    last = cluster.event_manager.history(1)[0]
    assert last.state == "FINISHED"
    assert last.stats["wall_ms"] > 0
    assert last.stats["recovery"] is not None
    assert last.stats["wall_breakdown"]  # coordinator span breakdown


# -- chaos: retried attempts as sibling spans (runs LAST: the killed
# -- worker's replacement is cold) ----------------------------------------


def test_retried_attempt_is_sibling_span_tagged_with_taxonomy(cluster):
    qid = f"q{cluster._task_seq + 1}a0"
    cluster.fault_schedule.add(f"{qid}.f1.t0", "kill-worker")
    cluster.session.properties.update(
        streaming_execution=False, retry_policy="TASK",
        speculative_execution_enabled=False)
    try:
        res = cluster.execute(
            "select l_returnflag, count(*) from lineitem "
            "group by l_returnflag")
    finally:
        for k in ("streaming_execution", "retry_policy",
                  "speculative_execution_enabled"):
            cluster.session.properties.pop(k, None)
    assert len(res.rows) == 3
    spans = res.stats["trace"]
    _, children, orphans = span_tree(spans)
    assert orphans == []
    attempts = [s for s in spans
                if s["attrs"].get("span_kind") == "attempt"
                and f"{qid}.f1.t0" in s["attrs"].get("task_id", "")]
    assert len(attempts) >= 2, [s["name"] for s in spans]
    # all attempts of the task are SIBLINGS under one fragment span
    assert len({s["parent_id"] for s in attempts}) == 1
    failed = [s for s in attempts if s["attrs"].get("error_type")]
    won = [s for s in attempts if not s["attrs"].get("error_type")]
    assert failed and won
    assert failed[0]["attrs"]["error_type"] == "EXTERNAL"  # taxonomy
    assert failed[0]["attrs"]["attempt"] == 0
    assert won[0]["attrs"]["attempt"] >= 1
    cluster.heal()  # restore 2 live workers for any later module