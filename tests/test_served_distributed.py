"""The served four-chip path: ``ProtocolServer`` over
``DistributedQueryRunner`` through ``Client``.

This is the whole path of the benchmark's ``mesh4_q3_exchange`` cell, on
four of the virtual CPU devices ``conftest.py`` asks for. The cluster is
built by the cell's own runner kind (``benchmark/systems/distributed``)
from the cell's own configuration file (workers, splits, page size,
session properties: ``device_exchange`` on, joins PARTITIONED), so the
two cannot drift apart; only the schema is cut to ``tiny``. The
statements are the benchmark's four templates at their validation
parameters, checked against the sqlite oracle of ``test_tpch_oracle``.
"""

import threading
import time
import urllib.request

import pytest

from benchmark import traffic
from benchmark.systems import distributed
from chip_smoke import ServedResult
from test_tpch_oracle import assert_same, load_sqlite, to_sqlite
from trino_tpu.client import Client
from trino_tpu.connectors.tpch import TpchConnector
from trino_tpu.parallel.device_exchange import DeviceExchange
from trino_tpu.server.protocol import ProtocolServer
from trino_tpu.telemetry import tracing
from trino_tpu.types import TrinoError

SCHEMA = "tiny"
CONFIG = dict(traffic.load_json("configs", "tpch_sf1_4chip.json"),
              schema=SCHEMA)


def template_sql(name: str) -> str:
    """The benchmark's template ``name`` at its validation parameters."""
    template = traffic.load_template(name)
    return traffic.instantiate(template, template.meta["validation"]).sql


def build_runner():
    """The cluster as the cell's runner kind builds it."""
    return distributed.build(CONFIG)


@pytest.fixture(scope="module")
def runner():
    return build_runner()


@pytest.fixture(scope="module")
def server(runner):
    server = ProtocolServer(runner).start()
    yield server
    server.stop()


@pytest.fixture(scope="module")
def client(server):
    return Client(server.uri, timeout=600.0)


@pytest.fixture(scope="module")
def oracle():
    return load_sqlite(TpchConnector(page_rows=8192), SCHEMA)


def statement_roots(since: float):
    """Root spans of the served statements finished since ``since``
    (``time.perf_counter`` seconds), oldest first."""
    traces, lost = tracing.RING.since(since)
    assert not lost
    return [s for spans in traces for s in spans
            if s["parent_id"] is None and s["name"] == "statement"]


def test_cluster_is_the_cells_configuration(runner):
    assert CONFIG["runner"]["kind"] == "distributed"
    assert (runner.n_workers, runner.desired_splits) == (4, 8)
    assert runner.session.properties == {
        "device_exchange": True, "join_distribution_type": "PARTITIONED"}


@pytest.mark.parametrize("name", ["q1", "q3", "q6", "q13"])
def test_served_template_equals_sqlite(name, client, oracle):
    sql = template_sql(name)
    want = oracle.execute(to_sqlite(sql)).fetchall()
    assert want
    res = client.execute(sql)
    assert_same(ServedResult(res), want, ordered="order by" in sql.lower())


def test_q3_runs_five_collectives_and_finishes_its_root(client):
    """q3 under PARTITIONED joins crosses five hash boundaries, each one
    ``all_to_all`` over the mesh, and the statement's tree reaches
    ``tracing.RING`` with its root ``FINISHED``."""
    t0 = time.perf_counter()
    before = DeviceExchange.total_collectives
    res = client.execute(template_sql("q3"))
    assert len(res.rows) == 10
    assert DeviceExchange.total_collectives - before == 5
    root, = statement_roots(t0)
    assert root["attrs"]["state"] == "FINISHED"
    assert root["t1"] >= root["t0"]


def test_q3_tree_has_plan_execute_and_a_task_span_a_task(client):
    """A distributed statement's tree is the local runner's — ``parse``,
    ``plan``, ``execute`` under ``statement.run`` — with one ``task``
    span a task under ``execute``, each on its device, the operators'
    spans under their task, and the tasks' blocking reads counted on
    the root."""
    t0 = time.perf_counter()
    client.execute(template_sql("q3"))
    root, = statement_roots(t0)
    traces, _ = tracing.RING.since(t0)
    tree, = [spans for spans in traces if root in spans]
    by_name = {}
    for s in tree:
        by_name.setdefault(s["name"], []).append(s)
    assert [len(by_name[n]) for n in ("parse", "plan", "execute")] == \
        [1, 1, 1]
    run, = by_name["statement.run"]
    execute, = by_name["execute"]
    assert execute["parent_id"] == run["span_id"]
    tasks = by_name["task"]
    assert all(t["parent_id"] == execute["span_id"] for t in tasks)
    by_fragment = {}
    for t in tasks:
        by_fragment.setdefault(t["attrs"]["fragment"], []).append(
            (t["attrs"]["task"], t["attrs"]["device"]))
    assert len(by_fragment) >= 6        # five hash boundaries
    for placed in by_fragment.values():
        # worker i runs on device i; a single-task fragment on device 0
        assert sorted(placed) in ([(0, 0)], [(i, i) for i in range(4)])
    task_ids = {t["span_id"] for t in tasks}
    operators = [s for s in tree
                 if s["attrs"].get("span_kind") == "operator"]
    assert operators and all(s["parent_id"] in task_ids for s in operators)
    assert not tracing.span_tree(tree)[2]       # no orphan
    assert root["attrs"]["host_syncs"] > 0
    assert root["attrs"]["host_sync_s"] > 0


def test_failing_statement_reaches_the_client_with_its_root_closed(client):
    t0 = time.perf_counter()
    with pytest.raises(TrinoError) as err:
        client.execute("select no_such_column from lineitem")
    assert "no_such_column" in str(err.value)
    root, = statement_roots(t0)
    assert root["attrs"]["state"] == "FAILED"
    # the server goes on answering
    assert client.execute("select count(*) from nation").rows == [[25]]


def test_cancelled_statement_reaches_the_client_with_its_root_closed(
        monkeypatch):
    """A statement cancelled (``DELETE`` on its ``nextUri``) while the
    cluster is running it: the waiting client gets an error, the root
    span ends ``CANCELED``, and the cluster's late answer is dropped."""
    runner = build_runner()
    real = runner.execute
    started, release, answered = (threading.Event(), threading.Event(),
                                  threading.Event())

    def gated(sql):
        started.set()
        assert release.wait(60)
        try:
            return real(sql)
        finally:
            answered.set()

    monkeypatch.setattr(runner, "execute", gated)
    server = ProtocolServer(runner).start()
    errors = []

    def wait_for_answer():
        try:
            Client(server.uri, timeout=120.0).execute(
                "select count(*) from orders")
        except TrinoError as e:
            errors.append(e)

    t0 = time.perf_counter()
    waiting = threading.Thread(target=wait_for_answer)
    try:
        waiting.start()
        assert started.wait(60)
        qid, = list(server.queries)
        req = urllib.request.Request(
            f"{server.uri}/v1/statement/executing/{qid}/0",
            method="DELETE")
        with urllib.request.urlopen(req, timeout=60) as resp:
            assert resp.status == 204
        waiting.join(60)
        assert not waiting.is_alive()
        release.set()
        assert answered.wait(120)
    finally:
        release.set()
        waiting.join(60)
        server.stop()
    assert len(errors) == 1 and errors[0].code == "NOT_FOUND"
    root, = statement_roots(t0)
    assert root["attrs"]["state"] == "CANCELED"
    assert root["attrs"]["query_id"] == qid


def test_explain_through_the_server_answers(client):
    """``EXPLAIN`` of q3 through the served cluster answers with the
    plan (both joins, the three scans under the schema the session
    names) and runs nothing: no collective, a ``FINISHED`` root."""
    t0 = time.perf_counter()
    before = DeviceExchange.total_collectives
    res = client.execute("explain " + template_sql("q3"))
    text = "\n".join(row[0] for row in res.rows)
    assert text.count("Join inner") == 2
    for table in ("lineitem", "orders", "customer"):
        assert f"TableScan tpch.{SCHEMA}.{table}" in text
    assert "TopN" in text
    assert DeviceExchange.total_collectives == before
    root, = statement_roots(t0)
    assert root["attrs"]["state"] == "FINISHED"
