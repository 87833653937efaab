"""Tables sharded over four chips: the memory connector behind
``DistributedQueryRunner`` with four workers.

A distributed CTAS leaves each writer task's pages on that task's
device; a table on several devices splits by device, each split naming
the one that holds its pages; the runner gives a split to the task on
that device, so no page crosses from one device to another inside a
scan; the account of table bytes is kept by device and
``max_data_per_node`` bounds a device's share.

The cluster is the one the benchmark's ``mesh4_q1_resident`` cell runs:
built by the cell's own runner kind
(``benchmark/systems/distributed_resident``) from the cell's own
configuration file, on four of the virtual CPU devices ``conftest.py``
asks for, with the schema cut to ``tiny`` and stored pages cut to 4,096
lanes (``lineitem``: 16 pages).  Statements are the benchmark's
templates at their validation parameters, against the sqlite oracle of
``test_tpch_oracle``.
"""

import time
from collections import Counter

import pytest

from benchmark import traffic
from benchmark.systems import distributed_resident
from chip_smoke import ServedResult
from test_tpch_oracle import assert_same, load_sqlite, to_sqlite
from trino_tpu.client import Client
from trino_tpu.connectors import memory
from trino_tpu.connectors.spi import ConnectorSplit, TableHandle
from trino_tpu.connectors.tpch import TpchConnector
from trino_tpu.exec import local_planner
from trino_tpu.exec.local_planner import splits_of_task
from trino_tpu.exec.memory import (resident_table_bytes,
                                   resident_table_bytes_by_device)
from trino_tpu.runner import LocalQueryRunner
from trino_tpu.server.protocol import ProtocolServer
from trino_tpu.sql.analyzer import Session
from trino_tpu.telemetry import tracing
from trino_tpu.types import TrinoError

SCHEMA = "tiny"
PAGE_ROWS = 4096
LINEITEM_ROWS = 59814       # the generator's lineitem at tiny
CONFIG = dict(traffic.load_json("configs", "tpch_sf1_resident_4chip.json"),
              schema=SCHEMA)
WORKERS = CONFIG["runner"]["workers"]
COUNT_AND_SUM = "select count(*), sum(l_extendedprice) from lineitem"


def template_sql(name: str) -> str:
    """The benchmark's template ``name`` at its validation parameters."""
    template = traffic.load_template(name)
    return traffic.instantiate(template, template.meta["validation"]).sql


def build_cluster(config=CONFIG):
    """The cluster as the cell's runner kind builds it, its stored
    pages cut to ``PAGE_ROWS`` lanes; ``(runner, memory connector)``."""
    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(memory, "PAGE_ROWS", PAGE_ROWS)
        runner = distributed_resident.build(config)
    return runner, runner.metadata.connectors[config["connector"]["catalog"]]


@pytest.fixture(scope="module")
def cluster():
    return build_cluster()


@pytest.fixture(scope="module")
def runner(cluster):
    return cluster[0]


@pytest.fixture(scope="module")
def mem(cluster):
    return cluster[1]


@pytest.fixture(scope="module")
def client(runner):
    server = ProtocolServer(runner).start()
    yield Client(server.uri, timeout=600.0)
    server.stop()


@pytest.fixture(scope="module")
def oracle():
    return load_sqlite(TpchConnector(page_rows=8192), SCHEMA)


def scan_spans(spans):
    """The resident scans' operator spans of a statement's tree."""
    return [s for s in spans if "resident_bytes" in s["attrs"]]


def served_tree(since: float):
    """The span tree of the one served statement finished since
    ``since`` (``time.perf_counter`` seconds), from the program's ring."""
    traces, lost = tracing.RING.since(since)
    assert not lost
    tree, = [spans for spans in traces
             if any(s["parent_id"] is None and s["name"] == "statement"
                    for s in spans)]
    return tree


def array_ids(page):
    return {id(a) for a in list(page.cols) + list(page.nulls)}


# -- the load: each writer's pages on its own device -------------------------

def test_each_device_holds_a_quarter_of_lineitems_pages(mem):
    pages = mem.tables[(SCHEMA, "lineitem")].pages
    assert len(pages) == 16
    by_device = Counter(p.device.id for p in pages)
    assert sorted(by_device) == list(range(WORKERS))
    assert max(by_device.values()) - min(by_device.values()) <= 1
    for page in pages:      # every array of a page lies where it says
        arrays = list(page.cols) + list(page.nulls) + [page.valid]
        assert {d for a in arrays for d in a.devices()} == {page.device}


def test_the_devices_pages_are_disjoint_and_where_their_splits_say(mem):
    md = mem.metadata()
    handle = md.get_table_handle(SCHEMA, "lineitem")
    columns = md.get_columns(handle)
    splits = mem.split_manager().get_splits(handle, 8)
    # two a device, in the devices' order whoever wrote first
    assert [s.device for s in splits] == \
        [d for d in range(WORKERS) for _ in range(2)]
    stored = {frozenset(array_ids(p)): p
              for p in mem.tables[(SCHEMA, "lineitem")].pages}
    seen = Counter()
    import jax

    for split in splits:
        with jax.default_device(jax.devices()[split.device]):
            source = mem.page_source(split, columns)
            while (page := source.get_next_device_page()) is not None:
                assert page.device.id == split.device
                assert not page.transferred
                seen[frozenset(array_ids(page))] += 1
    # every stored page through exactly one split, none twice
    assert set(seen) == set(stored) and set(seen.values()) == {1}


def test_the_shares_together_are_the_source_table(mem):
    source = TpchConnector(page_rows=8192)
    md = source.metadata()
    handle = md.get_table_handle(SCHEMA, "lineitem")
    split, = source.split_manager().get_splits(handle, 1)
    reader = source.page_source(split, md.get_columns(handle))
    want = []
    while (page := reader.get_next_page()) is not None:
        want.extend(page.to_rows())
    got = [r for p in mem.tables[(SCHEMA, "lineitem")].host_pages()
           for r in p.to_rows()]
    assert len(got) == len(want) == LINEITEM_ROWS
    assert sorted(got) == sorted(want)


def test_a_small_table_lies_on_one_device_and_one_task_scans_it(mem, runner):
    """``nation`` is one page: its split has no address, one task
    reads it, the others scan nothing."""
    md = mem.metadata()
    handle = md.get_table_handle(SCHEMA, "nation")
    split, = mem.split_manager().get_splits(handle, 8)
    assert split.device is None
    res = runner.execute("select count(*) from nation")
    assert res.rows == [(25,)]
    scans = scan_spans(res.stats["trace"])
    assert sum(s["attrs"]["resident_pages"] for s in scans) == 1


def test_served_count_and_sum_equal_the_generators(client, oracle):
    want = oracle.execute(COUNT_AND_SUM).fetchall()
    assert_same(ServedResult(client.execute(COUNT_AND_SUM)), want,
                ordered=False)


# -- scans stay on their device, and the tree says so ------------------------

@pytest.mark.parametrize("name", ["q1", "q3", "q6", "q13"])
def test_served_template_equals_sqlite_and_no_page_crosses(name, client,
                                                           oracle):
    sql = template_sql(name)
    want = oracle.execute(to_sqlite(sql)).fetchall()
    assert want
    t0 = time.perf_counter()
    res = client.execute(sql)
    assert_same(ServedResult(res), want, ordered="order by" in sql.lower())
    tree = served_tree(t0)
    scans = scan_spans(tree)
    assert scans
    for s in scans:     # a task with no page of a small table scans 0
        assert s["attrs"]["transferred_bytes"] == 0
        assert s["attrs"]["local_bytes"] == s["attrs"]["resident_bytes"]
        assert s["attrs"]["uploaded_bytes"] == 0
    assert sum(s["attrs"]["local_bytes"] for s in scans) > 0
    # each scan span hangs under the task that ran it, on a device
    tasks = {s["span_id"]: s for s in tree if s["name"] == "task"}
    assert all(s["parent_id"] in tasks for s in scans)


def test_a_local_runner_reads_the_table_whole_and_counts_the_transfer(
        runner, oracle):
    local = LocalQueryRunner(runner.metadata.connectors,
                             Session(catalog="memory", schema=SCHEMA))
    res = local.execute(COUNT_AND_SUM)
    assert_same(res, oracle.execute(COUNT_AND_SUM).fetchall(),
                ordered=False)
    scan, = scan_spans(res.stats["trace"])
    assert scan["attrs"]["resident_pages"] == 16
    assert scan["attrs"]["local_bytes"] + \
        scan["attrs"]["transferred_bytes"] == scan["attrs"]["resident_bytes"]
    # three devices' shares of four crossed to the runner's device
    assert scan["attrs"]["transferred_bytes"] > \
        2 * scan["attrs"]["local_bytes"] > 0


def by_stride(splits, task_id, task_count, task_devices=None):
    """The assignment before splits had addresses."""
    return [s for i, s in enumerate(splits) if i % task_count == task_id]


def all_but_the_first(splits, task_id, task_count, task_devices=None):
    return splits_of_task(splits[1:], task_id, task_count, task_devices)


def the_first_twice(splits, task_id, task_count, task_devices=None):
    return splits_of_task(splits + splits[:1], task_id, task_count,
                          task_devices)


def test_a_split_handed_to_the_wrong_task_shows_in_the_counter(
        runner, oracle, monkeypatch):
    monkeypatch.setattr(local_planner, "splits_of_task", by_stride)
    res = runner.execute(COUNT_AND_SUM)
    assert_same(res, oracle.execute(COUNT_AND_SUM).fetchall(),
                ordered=False)
    scans = scan_spans(res.stats["trace"])
    assert sum(s["attrs"]["transferred_bytes"] for s in scans) > 0
    assert sum(s["attrs"]["local_bytes"] + s["attrs"]["transferred_bytes"]
               for s in scans) == \
        sum(s["attrs"]["resident_bytes"] for s in scans)


@pytest.mark.parametrize("assignment", [all_but_the_first, the_first_twice],
                         ids=lambda f: f.__name__)
def test_a_split_dropped_or_given_twice_changes_the_answer(
        assignment, runner, oracle, monkeypatch):
    (count, total), = oracle.execute(COUNT_AND_SUM).fetchall()
    monkeypatch.setattr(local_planner, "splits_of_task", assignment)
    (got_count, got_total), = runner.execute(COUNT_AND_SUM).rows
    assert got_count != count and float(got_total) != pytest.approx(total)


# -- split assignment --------------------------------------------------------

def _splits(devices):
    table = TableHandle("memory", "s", "t")
    return [ConnectorSplit(table, i, len(devices), device=d)
            for i, d in enumerate(devices)]


@pytest.mark.parametrize("devices, task_devices, want", [
    # no address: by stride, as before
    ([None] * 5, [0, 1], [[0, 2, 4], [1, 3]]),
    ([None] * 3, None, [[0, 2], [1]]),
    # every split to the task on its device, whatever its position
    ([3, 3, 0, 1, 2, 2], [0, 1, 2, 3], [[2], [3], [4, 5], [0, 1]]),
    # two tasks on one device share its splits round robin
    ([0, 0, 0, 1], [0, 1, 0, 1], [[0, 2], [3], [1], []]),
    # no task on the device (fewer workers than devices hold pages, a
    # single-task fragment): over all the tasks, none lost
    ([0, 1, 2, 3], [0, 1], [[0, 2], [1, 3]]),
    ([2, 3], [0], [[0, 1]]),
    # addresses without a layout (a local runner): by stride
    ([0, 1, 2], None, [[0, 2], [1]]),
], ids=["unaddressed", "unaddressed_no_layout", "addressed",
        "two_tasks_a_device", "device_without_a_task",
        "single_task_fragment", "addressed_no_layout"])
def test_splits_of_task(devices, task_devices, want):
    splits = _splits(devices)
    n = len(want)
    got = [[s.split_id for s in splits_of_task(splits, t, n, task_devices)]
           for t in range(n)]
    assert got == want
    # every split read by exactly one task
    assert sorted(i for ids in got for i in ids) == list(range(len(splits)))


# -- the account: by device, bounded a device --------------------------------

def test_bytes_by_device_add_up_to_the_nodes_tables(mem):
    by_device = mem.account.by_device()
    assert sorted(by_device) == list(range(WORKERS))
    assert sum(by_device.values()) == mem.account.reserved == \
        sum(mem.resident_bytes_by_table().values())
    node = resident_table_bytes_by_device()
    assert sum(node.values()) == resident_table_bytes()
    assert all(node[d] >= by_device[d] for d in by_device)
    lineitem = Counter()
    for page in mem.tables[(SCHEMA, "lineitem")].pages:
        lineitem[page.device.id] += page.nbytes
    page_bytes = max(p.nbytes for p in
                     mem.tables[(SCHEMA, "lineitem")].pages)
    assert max(lineitem.values()) - min(lineitem.values()) <= page_bytes


def test_a_ctas_past_one_devices_limit_leaves_nothing_on_any(cluster):
    """``max_data_per_node`` bounds a device's share: a limit that the
    whole table passes and a quarter of it does not lets the load
    through; one under a device's share fails the statement, takes the
    table back from all four sinks and leaves no byte reserved."""
    _, loaded = cluster
    share = max(n for (key, _), n in loaded.account._held.items()
                if key == (SCHEMA, "lineitem"))
    whole = loaded.resident_bytes_by_table()[f"{SCHEMA}.lineitem"]
    runner, mem = build_cluster(dict(
        CONFIG, runner=dict(CONFIG["runner"], load=[])))
    ctas = "create table lineitem as select * from tpch.tiny.lineitem"
    mem.account._max_bytes = share
    assert share < whole
    assert runner.execute(ctas).rows == [(LINEITEM_ROWS,)]
    assert mem.account.reserved == whole
    runner.execute("drop table lineitem")
    assert mem.account.reserved == 0 and mem.account.by_device() == {}

    mem.account._max_bytes = share // 2
    with pytest.raises(TrinoError) as err:
        runner.execute(ctas)
    assert err.value.code == "MEMORY_LIMIT_EXCEEDED"
    assert (SCHEMA, "lineitem") not in mem.tables
    assert mem.account.reserved == 0 and mem.account.by_device() == {}
    # the catalog goes on: the same statement under the old limit
    mem.account._max_bytes = share
    assert runner.execute(ctas).rows == [(LINEITEM_ROWS,)]
    assert sorted(mem.account.by_device()) == list(range(WORKERS))


def test_drop_releases_all_four_shares():
    runner, mem = build_cluster(dict(
        CONFIG, runner=dict(CONFIG["runner"], load=["orders"])))
    before = resident_table_bytes_by_device()
    held = mem.account.by_device()
    assert sorted(held) == list(range(WORKERS)) and all(held.values())
    runner.execute("drop table orders")
    assert mem.account.by_device() == {}
    after = resident_table_bytes_by_device()
    assert {d: before[d] - after.get(d, 0) for d in held} == held
