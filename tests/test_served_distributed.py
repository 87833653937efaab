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
from trino_tpu.parallel import device_exchange
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


# -- the exchange seen from inside: one ``exchange`` span a collective ------

PHASES = ("assemble_s", "size_s", "run_s", "readback_s", "slice_s")
EXCHANGE_SITES = ("exchange_count", "exchange_ready", "exchange_overflow",
                  "exchange_readback")


def fresh_exchanges():
    """No sizing history and no built exchange program: the next
    statement counts before it sizes and lowers ``exchanged`` anew, as
    a first statement does — whatever this process ran before."""
    device_exchange.SIZING_HISTORY.reset()
    device_exchange._exchange_program.cache_clear()


def served_tree(client, sql):
    """(root, spans by name, all spans) of one served statement."""
    t0 = time.perf_counter()
    client.execute(sql)
    root, = statement_roots(t0)
    traces, _ = tracing.RING.since(t0)
    tree, = [spans for spans in traces if root in spans]
    by_name = {}
    for s in tree:
        by_name.setdefault(s["name"], []).append(s)
    return root, by_name, tree


@pytest.fixture(scope="module")
def first_q3(client):
    fresh_exchanges()
    return served_tree(client, template_sql("q3"))


def test_q3_tree_has_one_exchange_span_a_collective(first_q3):
    """Five collectives, five ``exchange`` spans, each under the
    ``task`` span of the consumer that triggered it and on that task's
    device; what a span says moved is what its consumers' exchange
    sources took in."""
    root, by_name, tree = first_q3
    exchanges = by_name["exchange"]
    assert len(exchanges) == 5
    tasks = {t["span_id"]: t for t in by_name["task"]}
    moved, taken = {}, {}
    for ex in exchanges:
        task = tasks[ex["parent_id"]]
        assert ex["attrs"]["device"] == task["attrs"]["device"]
        assert ex["attrs"]["fragment"] != task["attrs"]["fragment"]
        consumer = task["attrs"]["fragment"]
        moved[consumer] = moved.get(consumer, 0) + ex["attrs"]["rows"]
    assert len({ex["attrs"]["fragment"] for ex in exchanges}) == 5
    for s in tree:
        if s["name"] == "ExchangeSourceOperator":
            consumer = tasks[s["parent_id"]]["attrs"]["fragment"]
            if consumer in moved:
                taken[consumer] = taken.get(consumer, 0) + s["attrs"]["rows"]
    assert moved == taken and all(moved.values())


def test_exchange_span_phases_tile_it_and_bytes_are_lanes(first_q3):
    _, by_name, _ = first_q3
    d = 4
    for ex in by_name["exchange"]:
        a = ex["attrs"]
        seconds = ex["t1"] - ex["t0"]
        assert abs(sum(a[p] for p in PHASES) - seconds) \
            <= 0.05 * seconds + 0.002
        assert a["sizing_used"] == "exact" and a["count_collectives"] == 1
        assert a["a2a_retries"] == 0 and a["data_collectives"] == 1
        assert a["bytes_moved"] == \
            a["data_collectives"] * d * d * a["per_dest"] * a["lane_bytes"]
        assert a["rows"] * a["lane_bytes"] <= a["bytes_moved"]
        assert a["per_dest"] <= a["cap"]
        assert a["skew_ratio"] >= 1.0 and a["splits"] == 0
        assert a["lowered"] >= 1      # ``exchanged``, built anew


def test_exchange_reads_are_host_syncs_with_sites_of_their_own(first_q3):
    root, by_name, _ = first_q3
    by_why = root["attrs"]["host_sync_by_why"]
    assert {site: by_why[site][0] for site in EXCHANGE_SITES} == \
        dict.fromkeys(EXCHANGE_SITES, len(by_name["exchange"]))
    assert root["attrs"]["host_syncs"] == sum(n for n, _ in by_why.values())


def test_consumers_that_waited_for_the_barrier_say_so(first_q3):
    """``exchange_wait_s`` on the ``task`` spans of the consumers that
    found the collective running: never on the one task of a fragment
    that triggered its only exchange, so on at most n - 1 an
    exchange."""
    _, by_name, _ = first_q3
    n = 4
    tasks = {t["span_id"]: t for t in by_name["task"]}
    triggered = {}
    for ex in by_name["exchange"]:
        task = tasks[ex["parent_id"]]
        triggered.setdefault(task["attrs"]["fragment"], []).append(task)
    for fragment, triggers in triggered.items():
        waited = [t for t in tasks.values()
                  if t["attrs"]["fragment"] == fragment
                  and "exchange_wait_s" in t["attrs"]]
        assert len(waited) <= min(n, len(triggers) * (n - 1))
        assert all(t["attrs"]["exchange_wait_s"] > 0 for t in waited)
        if len(triggers) == 1:
            assert triggers[0] not in waited
    assert not any("exchange_wait_s" in t["attrs"] for t in tasks.values()
                   if t["attrs"]["fragment"] not in triggered)


def test_first_statement_counts_its_lowerings_by_program(first_q3, client):
    """The root's ``lowerings`` name ``exchanged`` in a statement that
    had to build it; the same statement again lowers it no more,
    whichever task triggers each collective this time."""
    root, _, _ = first_q3
    a = root["attrs"]
    assert a["lowerings"] > 0 and a["lowering_s"] > 0
    traces, trace_s, lower_s, compile_s = \
        a["lowerings_by_program"]["exchanged"]
    assert traces == 5 and min(trace_s, lower_s, compile_s) > 0
    assert a["lowerings"] <= sum(
        row[0] for row in a["lowerings_by_program"].values())
    line = tracing.lowering_line([root])
    assert line.startswith(f"Lowerings: {a['lowerings']} programs, ")
    again, by_name, _ = served_tree(client, template_sql("q3"))
    assert "exchanged" not in again["attrs"].get("lowerings_by_program", {})
    assert [ex["attrs"]["lowered"] for ex in by_name["exchange"]] == [0] * 5
    assert {ex["attrs"]["sizing_used"] for ex in by_name["exchange"]} == \
        {"history"}
    assert "exchange_count" not in again["attrs"]["host_sync_by_why"]


def test_a_task_quantum_and_an_exchange_phase_are_annotations(
        runner, monkeypatch):
    """What a profile's host lines read: ``task:f<fragment>.t<task>``
    around a quantum, ``exchange.<phase>`` inside a collective."""
    names = set()
    real = tracing.annotation

    def recording(name):
        names.add(name)
        return real(name)

    monkeypatch.setattr(tracing, "annotation", recording)
    runner.execute(template_sql("q1"))
    assert {"exchange." + p[:-2] for p in PHASES} <= names
    assert {n for n in names if n.startswith("task")} >= \
        {f"task:f0.t{t}" for t in range(4)}
    assert "task" not in names


def test_tracing_off_leaves_no_span_no_counter(monkeypatch):
    """``query_tracing_enabled=false``: no tree, nothing in the ring, no
    annotation, and the lowering listener returns at its first line."""
    runner = build_runner()
    runner.session.properties["query_tracing_enabled"] = False
    monkeypatch.setattr(tracing, "annotation", lambda name: pytest.fail(
        f"annotation {name!r} with tracing off"))
    t0 = time.perf_counter()
    fresh_exchanges()
    res = runner.execute(template_sql("q1"))
    assert len(res.rows) == 4
    assert "trace" not in (res.stats or {})
    assert tracing.RING.since(t0) == ([], False)
    assert tracing.current_span() is None
    assert tracing._on_lowering(
        "/jax/core/compile/jaxpr_trace_duration", 1.0, fun_name="f") is None


def test_a_repeated_local_statement_lowers_nothing():
    """One chip: the second run of a statement finds every program in
    jit's cache, and its root carries no ``lowerings`` at all."""
    from benchmark.systems import local

    config = dict(traffic.load_json("configs", "tpch_tiny_1chip.json"))
    runner = local.build(config)
    sql = template_sql("q6")
    first = runner.execute(sql)
    second = runner.execute(sql)
    assert first.rows == second.rows
    root, = [s for s in second.stats["trace"] if s["parent_id"] is None]
    assert "lowerings" not in root["attrs"]
    assert "lowerings_by_program" not in root["attrs"]
    assert tracing.lowering_line(second.stats["trace"]) is None
