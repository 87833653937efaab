"""Tables that live on the device: the memory connector's store, the
scan's pass-through of resident pages, the write path and the account
of table bytes.

Reference analog: ``plugin/trino-memory`` tests (``TestMemorySmoke``,
``TestMemoryPagesStore``) — CTAS from tpch, read back, limits.
"""

import numpy as np
import pytest

from test_tpch_oracle import assert_same, load_sqlite, to_sqlite
from trino_tpu.block import DevicePage, Page
from trino_tpu.client import Client
from trino_tpu.connectors import spi
from trino_tpu.connectors.memory import MemoryConnector
from trino_tpu.connectors.spi import ResidentPage
from trino_tpu.connectors.tpch import TpchConnector
from trino_tpu.exec.dynamic_filter import DynamicFilter
from trino_tpu.exec.memory import (NodeMemoryExceededError, NodeMemoryPool,
                                   TableMemoryAccount, pool_from_session,
                                   resident_table_bytes)
from trino_tpu.ops.operator import TableScanOperator, _ScanPages
from trino_tpu.resources.tpch_queries import TPCH_QUERIES
from trino_tpu.runner import LocalQueryRunner
from trino_tpu.server.protocol import ProtocolServer
from trino_tpu.sql.analyzer import Session
from trino_tpu.types import TrinoError

SCHEMA = "tiny"
LOADED = ["lineitem", "orders", "customer", "part", "partsupp", "supplier",
          "nation", "region"]


def make_runner(schema=SCHEMA, tables=(), page_rows=None, **memory):
    """A runner whose session is the memory catalog, ``tables`` loaded
    from the generator by CTAS; ``page_rows`` cuts stored pages smaller
    than the connector's 262,144 lanes."""
    mem = MemoryConnector(schemas=[schema], **memory)
    if page_rows:
        mem.page_rows = page_rows
    runner = LocalQueryRunner(
        {"tpch": TpchConnector(page_rows=8192), "memory": mem},
        Session(catalog="memory", schema=schema), desired_splits=4)
    for t in tables:
        runner.execute(f"create table {t} as "
                       f"select * from tpch.{schema}.{t}")
    return runner, mem


@pytest.fixture(scope="module")
def loaded():
    # pages of 8,192 lanes: lineitem is eight pages, so scans, splits
    # and dictionaries are exercised across pages
    return make_runner(tables=LOADED, page_rows=8192)


@pytest.fixture(scope="module")
def served(loaded):
    runner, _ = loaded
    tpch = LocalQueryRunner({"tpch": TpchConnector(page_rows=8192)},
                            Session(catalog="tpch", schema=SCHEMA),
                            desired_splits=4)
    servers = [ProtocolServer(r).start() for r in (runner, tpch)]
    yield [Client(s.uri) for s in servers]
    for s in servers:
        s.stop()


@pytest.fixture(scope="module")
def oracle():
    return load_sqlite(TpchConnector(page_rows=8192), SCHEMA)


@pytest.mark.parametrize("qid", [1, 3, 6, 9, 13])
def test_loaded_tables_answer_as_the_generator_does(qid, loaded, served,
                                                    oracle):
    """(a) Through the protocol server the loaded tables and the tpch
    catalog give the same rows; and the sqlite oracle agrees."""
    sql = TPCH_QUERIES[qid]
    resident, generated = (c.execute(sql) for c in served)
    assert resident.rows == generated.rows and resident.rows
    runner, _ = loaded
    assert_same(runner.execute(sql),
                oracle.execute(to_sqlite(sql)).fetchall(),
                "order by" in sql.lower())


@pytest.mark.parametrize("qid", [1, 3, 6])
def test_scan_of_a_resident_table_uploads_nothing(qid, loaded,
                                                  monkeypatch):
    """(b) No upload, no host pass over stored rows, no concat: the
    stored pages are device pages and pass through as they lie."""
    runner, mem = loaded
    for (_, table), data in mem.tables.items():
        assert data.pages and all(isinstance(p, ResidentPage) and
                                  isinstance(p, DevicePage)
                                  for p in data.pages), table
    calls = {"from_page": 0, "enforce": 0, "concat": 0, "upload": 0}

    def counting(key, real):
        def call(*args, **kwargs):
            calls[key] += 1
            return real(*args, **kwargs)
        return call

    monkeypatch.setattr(DevicePage, "from_page", staticmethod(
        counting("from_page", DevicePage.from_page)))
    monkeypatch.setattr(spi, "enforce_constraint_page", counting(
        "enforce", spi.enforce_constraint_page))
    monkeypatch.setattr(Page, "concat", staticmethod(
        counting("concat", Page.concat)))
    monkeypatch.setattr(_ScanPages, "_upload", counting(
        "upload", _ScanPages._upload))
    res = runner.execute(TPCH_QUERIES[qid])
    assert res.rows
    assert calls == {"from_page": 0, "enforce": 0, "concat": 0,
                     "upload": 0}
    scans = [s for s in res.stats["trace"]
             if s["name"] == "TableScanOperator"]
    assert scans and all(
        s["attrs"]["resident_pages"] > 0 and s["attrs"]["resident_bytes"]
        > 0 and s["attrs"]["uploaded_bytes"] == 0
        and s["attrs"]["generate_s"] == 0.0
        and s["attrs"]["upload_s"] == 0.0 for s in scans)


def test_generator_scan_counts_uploaded_bytes():
    """The other side of ``resident_scan_pct``: a scan of generated
    pages uploads every byte and takes none as resident."""
    tpch = LocalQueryRunner({"tpch": TpchConnector(page_rows=8192)},
                            Session(catalog="tpch", schema=SCHEMA))
    res = tpch.execute("select sum(l_quantity) from lineitem")
    scan, = [s for s in res.stats["trace"]
             if s["name"] == "TableScanOperator"]
    assert scan["attrs"]["uploaded_bytes"] > 0
    assert scan["attrs"]["resident_pages"] == 0
    assert scan["attrs"]["resident_bytes"] == 0


def scan_valid_masks(conn, handle, columns, df_channel, df):
    """Live lanes, in scan order, of a scan under one dynamic filter."""
    scan = TableScanOperator(conn, columns,
                             dynamic_filters=[(df_channel, df)])
    for split in conn.split_manager().get_splits(handle, 1):
        scan.add_split(split)
    scan.no_more_splits()
    kept = []
    while not scan.is_finished():
        page = scan.get_output()
        if page is not None:
            key = np.asarray(page.cols[df_channel])
            kept.append(key[np.asarray(page.valid)])
    return np.concatenate(kept)


def test_dynamic_filter_masks_a_resident_page_as_an_uploaded_one(loaded):
    """(c) The same build-side domain applied by the scan to a resident
    page and to an uploaded page of the same rows keeps the same rows."""
    _, mem = loaded
    tpch = TpchConnector(page_rows=8192)
    kept = []
    for conn in (mem, tpch):
        md = conn.metadata()
        handle = md.get_table_handle(SCHEMA, "lineitem")
        columns = [c for c in md.get_columns(handle)
                   if c.name in ("l_orderkey", "l_quantity")]
        df = DynamicFilter("o_orderkey")
        keys = np.arange(1, 40000, 7, dtype=np.int64)
        df.collect(keys, np.zeros(len(keys), bool),
                   np.ones(len(keys), bool))
        kept.append(scan_valid_masks(conn, handle, columns, 0, df))
        assert df.pruned_rows > 0
    assert len(kept[0]) and np.array_equal(kept[0], kept[1])


def test_table_bytes_are_reserved_until_the_table_is_dropped():
    """(d) The account: up by the table's device bytes on CTAS, still
    there after the query, down on DROP TABLE."""
    runner, mem = make_runner()
    node_before = resident_table_bytes()
    assert mem.account.reserved == 0
    res = runner.execute(
        "create table orders as select * from tpch.tiny.orders")
    write, = [s for s in res.stats["trace"] if s["name"] == "table_write"]
    assert write["attrs"]["rows"] == 15000
    assert write["attrs"]["pages"] == 1
    assert write["attrs"]["host_recode_s"] > 0
    held = mem.account.reserved
    # 15,000 rows in 16,384 lanes: 4 int64 + 5 int32 columns, the valid
    # mask of a page that is not full, one shared all-False null mask
    assert write["attrs"]["device_bytes"] == 16384 * (4 * 8 + 5 * 4 + 1)
    assert held == write["attrs"]["device_bytes"] + 16384
    assert mem.resident_bytes_by_table() == {"tiny.orders": held}
    assert resident_table_bytes() == node_before + held
    runner.execute("select count(*) from orders")
    assert mem.account.reserved == held
    text = "\n".join(r[0] for r in runner.execute(
        "explain analyze select count(*) from orders").rows)
    assert f"resident tables {resident_table_bytes()} bytes" in text
    assert "[resident 1 pages, " in text
    runner.execute("drop table orders")
    assert mem.account.reserved == 0
    assert resident_table_bytes() == node_before


def test_write_past_max_data_per_node_fails_and_leaves_no_half_table():
    """(d) A write that would pass the limit fails with the limit in its
    message; nothing of it stays, and what was there is untouched."""
    runner, mem = make_runner(max_data_per_node=3_000_000,
                              page_rows=8192)
    runner.execute("create table orders as select * from tpch.tiny.orders")
    held = mem.account.reserved
    assert 0 < held < 3_000_000
    version = mem.data_version()
    with pytest.raises(TrinoError) as failed:
        runner.execute(
            "create table lineitem as select * from tpch.tiny.lineitem")
    assert "Memory limit [3000000] for memory connector exceeded" \
        in str(failed.value)
    assert failed.value.code == "MEMORY_LIMIT_EXCEEDED"
    assert mem.metadata().get_table_handle(SCHEMA, "lineitem") is None
    assert mem.account.reserved == held
    assert mem.data_version() > version
    with pytest.raises(TrinoError):
        runner.execute("insert into orders select * from tpch.tiny.orders "
                       "union all select * from tpch.tiny.orders "
                       "union all select * from tpch.tiny.orders")
    assert mem.account.reserved == held
    assert runner.execute("select count(*) from orders").rows == [(15000,)]


@pytest.mark.parametrize("page_rows", [8192, None],
                         ids=["tail_page", "single_page"])
def test_write_whose_last_page_passes_the_limit_leaves_no_half_table(
        page_rows):
    """(d) The page that trips the limit is the one ``finish()`` stores
    (the tail of two pages, or a small table's only page): the CTAS
    target is gone all the same, nothing stays reserved, a retry is
    not met by TABLE_ALREADY_EXISTS."""
    ctas = "create table orders as select * from tpch.tiny.orders"
    _, sized = make_runner(tables=["orders"], page_rows=page_rows)
    full = sized.account.reserved
    pages = len(sized.tables[(SCHEMA, "orders")].pages)
    assert pages == (2 if page_rows else 1)
    runner, mem = make_runner(max_data_per_node=full - 1000,
                              page_rows=page_rows)
    with pytest.raises(TrinoError) as failed:
        runner.execute(ctas)
    assert failed.value.code == "MEMORY_LIMIT_EXCEEDED"
    assert f"[{full - 1000}]" in str(failed.value)
    assert mem.metadata().get_table_handle(SCHEMA, "orders") is None
    assert mem.account.reserved == 0
    assert mem.resident_bytes_by_table() == {}
    mem.account._max_bytes = full
    assert runner.execute(ctas).rows == [(15000,)]
    assert runner.execute("select count(*) from orders").rows == [(15000,)]
    assert mem.account.reserved == full
    # an INSERT whose tail page does not fit is taken back whole
    with pytest.raises(TrinoError):
        runner.execute("insert into orders select * from tpch.tiny.orders")
    assert mem.account.reserved == full
    assert runner.execute("select count(*) from orders").rows == [(15000,)]
    assert len(mem.tables[(SCHEMA, "orders")].pages) == pages


def test_table_bytes_are_charged_to_the_nodes_pool():
    """(d) On a worker the account charges the node's pool: queries are
    admitted against what the tables leave, a table the node has no
    room for is refused and recorded nowhere, and what was held before
    the pool came is charged when it comes."""
    node = NodeMemoryPool(1000)
    account = TableMemoryAccount(max_bytes=10_000)
    account.reserve(("s", "early"), 100)
    account.attach(node)
    assert (node.reserved, node.table_bytes) == (100, 100)
    account.reserve(("s", "t"), 500)
    assert (node.reserved, node.table_bytes) == (600, 600)
    assert node.snapshot()["table_bytes"] == 600
    query = node.create_query_pool("q", 1000).create_context("op")
    with pytest.raises(NodeMemoryExceededError):
        query.reserve(500)
    query.reserve(300)
    with pytest.raises(NodeMemoryExceededError) as refused:
        account.reserve(("s", "u"), 200)
    assert "resident tables" in str(refused.value)
    assert account.by_table() == {("s", "early"): 100, ("s", "t"): 500}
    assert (node.reserved, node.table_bytes) == (900, 600)
    account.release(("s", "t"))
    assert (node.reserved, node.table_bytes) == (400, 100)
    query.reserve(500)
    assert node.reserved == 900 and node.peak_bytes == 900


def test_a_local_query_gets_what_the_resident_tables_leave():
    """(d) A runner with no node pool: a query's own limit is the
    node's memory less the resident tables, and comes back on DROP."""
    import gc

    runner, mem = make_runner()
    gc.collect()                        # accounts of runners now gone
    others = resident_table_bytes()     # other tests' tables, if any
    node = others + 2_000_000
    runner.session.properties["node_max_memory_bytes"] = node
    runner.session.properties["spill_enabled"] = False
    assert pool_from_session(runner.session).max_bytes == 2_000_000
    runner.execute("create table orders as select * from tpch.tiny.orders")
    held = mem.account.reserved
    assert 0 < held < 1_000_000
    assert pool_from_session(runner.session).max_bytes == 2_000_000 - held
    # the group-by below peaks at some 19 kB
    runner.session.properties["node_max_memory_bytes"] = \
        others + held + 10_000
    sql = "select o_custkey, count(*) from orders group by o_custkey"
    with pytest.raises(TrinoError) as failed:
        runner.execute(sql)
    assert "memory" in str(failed.value).lower()
    runner.session.properties["node_max_memory_bytes"] = node
    assert len(runner.execute(sql).rows) == 1000
    runner.execute("drop table orders")
    assert pool_from_session(runner.session).max_bytes == 2_000_000


def test_a_write_moves_the_version_and_cached_entries_miss():
    """(e) Every write bumps ``data_version``; a cached plan and a
    cached result over the table are not served after it."""
    runner, mem = make_runner()
    runner.session.properties["result_cache_enabled"] = True
    runner.execute("create table t (x bigint)")
    runner.execute("insert into t values (1), (2)")
    sql = "select count(*), sum(x) from t"
    assert runner.execute(sql).rows == [(2, 3)]
    assert runner.execute(sql).stats.get("result_cache") == "hit"
    version = mem.data_version()
    runner.execute("insert into t values (10)")
    assert mem.data_version() > version
    after = runner.execute(sql)
    assert after.rows == [(3, 13)]
    assert after.stats.get("result_cache") != "hit"
    assert after.stats.get("plan_cache") != "hit"
    version = mem.data_version()
    assert runner.execute("delete from t where x = 2").rows == [(1,)]
    assert mem.data_version() > version
    assert runner.execute(sql).rows == [(2, 11)]


def test_string_columns_keep_one_dictionary_across_pages(loaded):
    """(f) Every page of a table codes a string column into the table's
    one pool, so a group-by over several pages groups by value."""
    runner, mem = loaded
    data = mem.tables[(SCHEMA, "lineitem")]
    assert len(data.pages) == 8
    mode = [c.name for c in data.columns].index("l_shipmode")
    pools = {id(p.dictionaries[mode]) for p in data.pages}
    assert pools == {id(data.dicts[mode])}
    assert sorted(data.dicts[mode].values) == sorted(
        ["AIR", "FOB", "MAIL", "RAIL", "REG AIR", "SHIP", "TRUCK"])
    sql = ("select l_shipmode, count(*), sum(l_quantity) from {} "
           "group by l_shipmode order by l_shipmode")
    got = runner.execute(sql.format("lineitem")).rows
    assert len(got) == 7
    assert got == runner.execute(sql.format("tpch.tiny.lineitem")).rows


def test_a_handle_that_carries_a_constraint_is_refused_not_ignored(loaded):
    """The connector declines pushdown (the plan keeps its Filter, so
    q6's answer above is exact); a handle that carries a constraint all
    the same is refused, never answered with rows it rejects."""
    from dataclasses import replace

    from trino_tpu.predicate import Domain, TupleDomain

    runner, mem = loaded
    md = mem.metadata()
    handle = md.get_table_handle(SCHEMA, "nation")
    constraint = TupleDomain.of({"n_nationkey": Domain.single(3)})
    assert md.apply_filter(handle, constraint) is None
    assert "constraint{" not in runner.explain(
        "select * from nation where n_nationkey = 3")
    split, = mem.split_manager().get_splits(handle, 1)
    pushed = replace(split, table=replace(handle, constraint=constraint))
    with pytest.raises(TrinoError) as refused:
        mem.page_source(pushed, md.get_columns(handle))
    assert refused.value.code == "NOT_SUPPORTED"


def test_pages_are_cut_at_write_time_and_rows_keep_their_order():
    """Live lanes of the pipeline's pages (a filter leaves holes) are
    staged in order and cut into full pages plus one tail."""
    runner, mem = make_runner(page_rows=4096)
    runner.execute("create table l as select l_orderkey, l_linenumber, "
                   "l_comment from tpch.tiny.lineitem "
                   "where l_quantity < 30")
    data = mem.tables[(SCHEMA, "l")]
    rows = [p.rows for p in data.pages]
    want = runner.execute("select l_orderkey, l_linenumber, l_comment "
                          "from tpch.tiny.lineitem where l_quantity < 30")
    assert sum(rows) == len(want.rows) == data.row_count
    assert set(rows[:-1]) == {4096} and 0 < rows[-1] <= 4096
    assert all(p.capacity == 4096 for p in data.pages[:-1])
    # full pages share the table's all-True mask and all-False null mask
    assert len({id(p.valid) for p in data.pages[:-1]}) == 1
    assert len({id(n) for p in data.pages for n in p.nulls
                if p.capacity == 4096}) == 1
    got = [r for p in data.host_pages() for r in p.to_rows()]
    assert got == want.rows


def test_insert_and_delete_keep_their_meaning_on_the_store():
    runner, mem = make_runner(page_rows=4096)
    runner.execute("create table o as select o_orderkey, o_orderstatus, "
                   "o_comment from tpch.tiny.orders")
    runner.execute("insert into o select o_orderkey + 100000, "
                   "o_orderstatus, o_comment from tpch.tiny.orders "
                   "where o_orderstatus = 'F'")
    data = mem.tables[(SCHEMA, "o")]
    f_rows, = runner.execute("select count(*) from tpch.tiny.orders "
                             "where o_orderstatus = 'F'").rows[0]
    assert runner.execute("select count(*) from o").rows == \
        [(15000 + f_rows,)]
    held = mem.account.reserved
    assert runner.execute(
        "delete from o where o_orderkey > 100000").rows == [(f_rows,)]
    assert all(isinstance(p, ResidentPage) for p in data.pages)
    assert 0 < mem.account.reserved < held
    assert runner.execute(
        "select o_orderstatus, count(*) from o group by o_orderstatus "
        "order by 1").rows == runner.execute(
        "select o_orderstatus, count(*) from tpch.tiny.orders "
        "group by o_orderstatus order by 1").rows
    assert runner.execute("delete from o").rows == [(15000,)]
    assert data.pages == [] and data.row_count == 0
    assert runner.execute("select count(*) from o").rows == [(0,)]


def test_a_host_page_put_into_the_list_is_taken_onto_the_device():
    """Replicas and fixtures append host pages to ``data.pages``: the
    next read adopts them, and ``host_pages`` serves replication."""
    from trino_tpu import types as T

    runner, mem = make_runner()
    runner.execute("create table t (x bigint, s varchar)")
    data = mem.tables[(SCHEMA, "t")]
    page = Page.from_pylists([T.BIGINT, T.VARCHAR],
                             [[1, 2, 3], ["a", "b", "a"]])
    data.pages.append(page)
    assert data.row_count == 3
    assert [p.to_rows() for p in data.host_pages()] == [page.to_rows()]
    assert runner.execute("select s, sum(x) from t group by s "
                          "order by s").rows == [("a", 4), ("b", 2)]
    assert all(isinstance(p, ResidentPage) for p in data.pages)
    assert mem.account.reserved > 0
    assert [r for p in data.host_pages() for r in p.to_rows()] == \
        page.to_rows()


def test_a_reader_on_another_device_gets_the_page_there(loaded):
    """A scan pinned to another device (a distributed worker's task)
    reads the pages through a device-to-device transfer."""
    import jax

    _, mem = loaded
    devices = jax.devices()
    if len(devices) < 2:
        pytest.skip("one device")
    md = mem.metadata()
    handle = md.get_table_handle(SCHEMA, "nation")
    columns = md.get_columns(handle)
    split, = mem.split_manager().get_splits(handle, 1)
    stored = mem.tables[(SCHEMA, "nation")].pages[0]
    with jax.default_device(devices[1]):
        resident = mem.page_source(split, columns).get_next_device_page()
    assert stored.device == devices[0]
    assert resident.cols[0].devices() == {devices[1]}
    assert resident.device == devices[1]
    assert resident.rows == 25
    assert np.array_equal(np.asarray(resident.cols[0]),
                          np.asarray(stored.cols[0]))
