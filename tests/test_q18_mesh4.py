"""TPC-H Q18 served by four workers over tables sharded across four
devices, as the cell ``mesh4_q18_exchange`` runs it: the benchmark's q18
template through ``ProtocolServer`` + ``Client`` over the
``distributed_resident`` runner kind built from the cell's own
configuration file, on four of the virtual CPU devices ``conftest.py``
asks for, with the schema cut to ``tiny``.

One cluster and one statement shape a module.  The spec's QUANTITY
range leaves no order at ``tiny``, so the thresholds are
``test_q18_semijoin.py``'s: 842, 68, 12 and 0 orders pass at 200, 250,
275 and 300.  Every answer is held to the benchmark's numpy reference
and to a ``LocalQueryRunner`` over the generator's catalog, exactly; the
``exchange`` spans to what they were given (``rows`` = ``rows_in``) and to
the rows the settled plan moves (the semi join on ``orders``, beneath the
inner joins, since PR 45), the statement roots to one fragment plan from
the second statement on.
"""

import time

import jax
import numpy as np
import pytest

from benchmark import compare, traffic
from benchmark.references import q18 as q18_reference
from benchmark.references.hosttables import HostTables
from benchmark.systems import distributed_resident
from trino_tpu import types as T
from trino_tpu.block import DevicePage, Page
from trino_tpu.client import Client
from trino_tpu.connectors.tpch import TpchConnector
from trino_tpu.parallel.device_exchange import DeviceExchange
from trino_tpu.runner import LocalQueryRunner
from trino_tpu.server.protocol import ProtocolServer
from trino_tpu.sql.analyzer import Session
from trino_tpu.telemetry import tracing

SCHEMA = "tiny"
FILE = traffic.load_json("configs", "tpch_sf1_resident_q18_4chip.json")
SISTER = traffic.load_json("configs", "tpch_sf1_resident_4chip.json")
CONFIG = dict(FILE, schema=SCHEMA)
WORKERS = CONFIG["runner"]["workers"]
TEMPLATE = traffic.load_template("q18")
PASSING = {200: 842, 250: 68, 275: 12, 300: 0}
#: the statements the module serves, in order: the first two settle the
#: plan (connector statistics, then history), the rest run it
SERVED = [250, 250, 250, 200, 275, 300]


def sql_at(quantity: int) -> str:
    return traffic.instantiate(TEMPLATE, {"QUANTITY": quantity}).sql


@pytest.fixture(scope="module")
def runner():
    return distributed_resident.build(CONFIG)


@pytest.fixture(scope="module")
def served(runner):
    """``[(quantity, typed rows, statement trace)]`` of ``SERVED``, each
    statement once through the server."""
    server = ProtocolServer(runner).start()
    client = Client(server.uri, timeout=900.0)
    out = []
    try:
        for quantity in SERVED:
            t0 = time.perf_counter()
            res = client.execute(sql_at(quantity))
            traces, lost = tracing.RING.since(t0)
            assert not lost
            trace, = [t for t in traces if any(
                s["parent_id"] is None and s["name"] == "statement"
                for s in t)]
            out.append((quantity,
                        compare.typed_rows(res.columns, res.rows), trace))
    finally:
        server.stop()
    return out


@pytest.fixture(scope="module")
def tables():
    return HostTables(SCHEMA)


@pytest.fixture(scope="module")
def local():
    """A client of one worker over the generator's catalog: another
    runner, another connector, no exchange."""
    server = ProtocolServer(LocalQueryRunner(
        {"tpch": TpchConnector(page_rows=8192)},
        Session(catalog="tpch", schema=SCHEMA))).start()
    yield Client(server.uri, timeout=900.0)
    server.stop()


def root_of(trace):
    root, = [s for s in trace if s["parent_id"] is None]
    return root


def exchanges(trace):
    return [s["attrs"] for s in trace if s["name"] == "exchange"]


def last_served(served, quantity):
    return [s for s in served if s[0] == quantity][-1]


def test_cluster_is_the_cells_configuration(runner):
    for key in ("schema", "runner", "connector", "session_properties"):
        assert FILE[key] == SISTER[key]     # letter for letter
    assert FILE["chips"] == FILE["workers"] == WORKERS == 4
    assert FILE["architecture"] is None
    assert (runner.n_workers, runner.desired_splits) == (4, 8)
    assert runner.session.properties == {
        "device_exchange": True, "join_distribution_type": "PARTITIONED"}


def test_each_device_holds_a_share_of_lineitem_and_orders(runner):
    mem = runner.metadata.connectors[CONFIG["connector"]["catalog"]]
    for table in ("lineitem", "orders"):
        pages = mem.tables[(SCHEMA, table)].pages
        assert {p.device.id for p in pages} == set(range(WORKERS))
        assert all(p.rows > 0 for p in pages)


@pytest.mark.parametrize("quantity", sorted(PASSING))
def test_served_q18_equals_the_reference(quantity, served, tables):
    want = q18_reference.reference(tables, {"QUANTITY": quantity})
    assert len(want) == min(PASSING[quantity], 100)
    _, got, _ = last_served(served, quantity)
    assert compare.mismatches(got, want, ordered=True) == 0


@pytest.mark.parametrize("quantity", sorted(PASSING))
def test_served_q18_equals_the_local_runner(quantity, served, local):
    res = local.execute(sql_at(quantity))
    want = compare.typed_rows(res.columns, res.rows)
    assert len(want) == min(PASSING[quantity], 100)
    _, got, _ = last_served(served, quantity)
    assert compare.mismatches(got, want, ordered=True) == 0


def test_plan_settles_at_the_second_statement(served):
    roots = [root_of(trace)["attrs"] for _, _, trace in served]
    assert all(r["state"] == "FINISHED" for r in roots)
    assert len({r["shape_fp"] for r in roots}) == 1
    # no literal in the fingerprint: four thresholds, one plan; the
    # first statement's is another (connector statistics: lineitem is
    # the last join's build, from history on its probe)
    assert len({r["plan_fp"] for r in roots[1:]}) == 1
    assert roots[0]["plan_fp"] != roots[1]["plan_fp"]
    # the three scans, the first level's partials, the passing keys to
    # the semi join, its output to customer's join, that join's to
    # lineitem's, the last aggregation's partials; an empty exchange
    # is a span all the same (QUANTITY 300 leaves the semi join's
    # build and all that follows it no row)
    assert [len(exchanges(trace)) for _, _, trace in served] \
        == [8] * len(served)


def first_level_partials(runner) -> int:
    """Groups the first level's partial aggregations send: the distinct
    ``l_orderkey`` of each device's share of ``lineitem``."""
    mem = runner.metadata.connectors[CONFIG["connector"]["catalog"]]
    data = mem.tables[(SCHEMA, "lineitem")]
    key, = [i for i, c in enumerate(data.columns)
            if c.name == "l_orderkey"]
    return sum(len(np.unique(np.concatenate(
        [np.asarray(p.to_page().blocks[key].data) for p in pages])))
        for pages in data.by_device().values())


def test_every_exchange_delivers_the_rows_it_was_given(runner, served,
                                                       tables):
    """The semi join filters ``orders`` before anything joins it
    (``PushSemiJoinBelowJoin``): every base row crosses once, the first
    level's partial groups once, and after the semi join only the
    passing orders move — its build, its output, the join with
    ``customer`` and the last aggregation's groups (an order's lines
    meet on one device).  No joined ``lineitem`` row is sent again."""
    base_rows = sum(tables.row_count(t) for t in TEMPLATE.tables)
    partials = first_level_partials(runner)
    assert tables.row_count("orders") <= partials \
        <= tables.row_count("lineitem")
    for quantity, _, trace in served:
        spans = exchanges(trace)
        assert all(a["rows"] == a["rows_in"] for a in spans)
        assert all(0 <= a.get("rows_stayed", 0) <= a["rows"]
                   for a in spans)
        moved = sum(a["rows"] for a in spans)
        assert moved == base_rows + partials + 4 * PASSING[quantity]


def test_collectives_of_a_statement_are_its_exchange_spans(runner, served):
    before = DeviceExchange.total_collectives
    res = runner.execute(sql_at(250))
    spans = exchanges(res.stats["trace"])
    ran = [a for a in spans if a["rows"]]
    assert DeviceExchange.total_collectives - before == len(ran)
    assert all(a["data_collectives"] == 1 and a["a2a_retries"] == 0
               for a in ran)


def hand_made_exchange(pages_by_task):
    """A four-way ``DeviceExchange`` on column 0 over ``pages_by_task``
    (task -> pages), collected; the pages each partition received."""
    ex = DeviceExchange(WORKERS, jax.devices()[:WORKERS], sizing="exact")
    ex.configure([T.BIGINT, T.BIGINT], [0])
    for task, pages in pages_by_task.items():
        for page in pages:
            ex.add_page(task, page)
    ex.set_no_more_pages()
    return ex, {p: ex.pages(p) for p in range(WORKERS)}


@pytest.fixture(scope="module")
def hashed():
    """4 x 5,000 rows of distinct keys through one exchange."""
    keys = np.random.default_rng(43).permutation(20_000)
    pages = {}
    for task in range(WORKERS):
        mine = keys[task::WORKERS]
        page = Page.from_pylists([T.BIGINT, T.BIGINT],
                                 [mine.tolist(), (mine * 3).tolist()])
        with jax.default_device(jax.devices()[task]):
            pages[task] = [DevicePage.from_page(page)]
    return hand_made_exchange(pages)


def test_a_uniform_hash_leaves_a_quarter_of_the_rows_where_they_were(
        hashed):
    stats = hashed[0].stats
    assert stats["rows_in"] == stats["rows"] == 20_000
    assert 0.22 < stats["rows_stayed"] / stats["rows"] < 0.28


def test_an_exchange_on_the_key_its_input_is_partitioned_on_moves_nothing(
        hashed):
    """What one exchange delivered, sent through a second on the same
    key: every row's destination is the device it lies on."""
    again, received = hand_made_exchange(hashed[1])
    stats = again.stats
    assert stats["rows_in"] == stats["rows"] == stats["rows_stayed"] \
        == 20_000
    for partition in range(WORKERS):
        once = sorted(k for page in hashed[1][partition] for k in
                      np.asarray(page.cols[0])[np.asarray(page.valid)])
        twice = sorted(k for page in received[partition] for k in
                       np.asarray(page.cols[0])[np.asarray(page.valid)])
        assert once == twice and len(once) == stats["partition_rows"][
            partition]
