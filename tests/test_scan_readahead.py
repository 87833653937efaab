"""The scan's read-ahead (``ops/operator.py``): a host page source is
walked by a producer thread at most ``READAHEAD_PAGES`` ahead of the
driver.  Same pages in the same order as the walk made on one thread,
no more than two pages held, the producer's failure raised as itself,
no thread left behind by a scan cut short, none started where no second
page exists or the table lives on the device, uploads on the driver
thread's device, dictionaries untouched by re-encoding."""

import gc
import threading
import time
from dataclasses import replace

import jax
import numpy as np
import pytest

from trino_tpu.connectors.memory import MemoryConnector
from trino_tpu.connectors.tpch import TpchConnector
from trino_tpu.exec.driver import Driver
from trino_tpu.exec.dynamic_filter import DynamicFilter
from trino_tpu.ops.operator import (READAHEAD_PAGES, Operator,
                                    OutputCollectorOperator,
                                    TableScanOperator, _ScanPages)
from trino_tpu.predicate import Domain, Range, TupleDomain, ValueSet
from trino_tpu.runner import LocalQueryRunner
from trino_tpu.sql.analyzer import Session
from trino_tpu.types import TrinoError

SCHEMA = "tiny"
PAGE_ROWS = 1024
COLUMNS = {
    "lineitem": ["l_returnflag", "l_linestatus", "l_quantity",
                 "l_extendedprice", "l_discount", "l_tax", "l_shipdate"],
    "orders": ["o_orderkey", "o_custkey", "o_orderdate",
               "o_orderpriority", "o_clerk"],
    "customer": ["c_custkey", "c_name", "c_mktsegment", "c_acctbal"],
}
#: lineitem's pages at ``PAGE_ROWS`` orders a page over four splits
LINEITEM_PAGES = 16
#: a pushed-down constraint per table (the connector masks rows under it)
CONSTRAINTS = {
    "lineitem": {"l_quantity": Domain(ValueSet.of_ranges(
        Range(None, False, 2400, False)), False)},
    "orders": {"o_orderpriority": Domain.of_values("1-URGENT", "5-LOW")},
    "customer": {"c_custkey": Domain(ValueSet.of_ranges(
        Range(100, True, 1200, True)), False)},
}


def producers():
    return [t for t in threading.enumerate() if t.name == "scan-readahead"]


def wait_until(condition, timeout=10.0):
    deadline = time.monotonic() + timeout
    while not condition() and time.monotonic() < deadline:
        time.sleep(0.002)
    return condition()


def table(conn, name, constrained=False):
    md = conn.metadata()
    handle = md.get_table_handle(SCHEMA, name)
    by_name = {c.name: c for c in md.get_columns(handle)}
    if constrained:
        handle = replace(handle,
                         constraint=TupleDomain.of(CONSTRAINTS[name]))
    return handle, [by_name[n] for n in COLUMNS[name]]


def make_scan(conn, name, splits=4, coalesce=None, constrained=False, **kw):
    handle, columns = table(conn, name, constrained)
    scan = TableScanOperator(conn, columns, coalesce_rows=coalesce, **kw)
    for split in conn.split_manager().get_splits(handle, splits):
        scan.add_split(split)
    scan.no_more_splits()
    return scan


def host_view(page):
    """What a device page holds, on the host: live mask, columns, null
    masks, and its dictionaries by identity."""
    return (np.asarray(page.valid),
            [np.asarray(c) for c in page.cols],
            [np.asarray(n) for n in page.nulls],
            [id(d) if d is not None else None for d in page.dictionaries])


def drain(scan):
    pages = []
    while not scan.is_finished():
        page = scan.get_output()
        if page is not None:
            pages.append(page)
    return pages


def walked_on_this_thread(conn, name, splits, coalesce, constrained):
    """The synchronous path: the scan's own walk, every page asked for
    on the calling thread."""
    scan = make_scan(conn, name, splits, coalesce, constrained)
    walk, pages = scan._pages, []
    assert isinstance(walk, _ScanPages)
    while True:
        got = walk.next_page()
        if got is None:
            assert walk.done
            return pages
        pages.append(got[0])


def generated_rows(conn, name, splits, constrained):
    """The connector's pages, split after split, column by column: the
    reference no code of the scan touches."""
    handle, columns = table(conn, name, constrained)
    cols = [[] for _ in columns]
    for split in conn.split_manager().get_splits(handle, splits):
        source = conn.page_source(split, columns)
        while (page := source.get_next_page()) is not None:
            for out, block in zip(cols, page.blocks):
                out.append(block.numpy().data)
    return [np.concatenate(c) for c in cols]


@pytest.mark.parametrize("constrained", [False, True],
                         ids=["all_rows", "pushed_down"])
@pytest.mark.parametrize("splits", [1, 4])
@pytest.mark.parametrize("coalesce", [None, 3])
@pytest.mark.parametrize("name", list(COLUMNS))
def test_read_ahead_gives_the_synchronous_pages(name, coalesce, splits,
                                                constrained):
    # pages of 1,024 rows (customer has 1,500: 128), coalesced by threes
    page_rows = PAGE_ROWS if name != "customer" else 128
    conn = TpchConnector(page_rows=page_rows)
    coalesce = coalesce and coalesce * page_rows
    before = producers()
    scan = make_scan(conn, name, splits, coalesce, constrained)
    first = scan.get_output()
    assert scan._ahead is not None      # a thread makes the rest
    ahead = [first] + drain(scan)
    assert producers() == before
    want = walked_on_this_thread(conn, name, splits, coalesce, constrained)
    assert len(ahead) == len(want) >= 2
    for got, expected in zip(ahead, want):
        for a, b in zip(host_view(got), host_view(expected)):
            if isinstance(a, list):
                assert len(a) == len(b)
                assert all(np.array_equal(x, y) for x, y in zip(a, b))
            else:
                assert np.array_equal(a, b)
    # and, joined, they are the connector's rows in the splits' order
    rows = generated_rows(conn, name, splits, constrained)
    for channel, reference in enumerate(rows):
        live = np.concatenate([np.asarray(p.cols[channel])[
            np.asarray(p.valid)] for p in ahead])
        assert np.array_equal(live, reference)


class CountingConnector(TpchConnector):
    """Counts host pages handed out; ``fail_at`` raises on that page."""

    def __init__(self, fail_at=None, **kw):
        super().__init__(**kw)
        self.generated = 0
        self.fail_at = fail_at
        self.error = TrinoError("page source failed", "GENERIC_INTERNAL_ERROR")

    def page_source(self, split, columns):
        source = super().page_source(split, columns)
        conn, real = self, source.get_next_page

        def get_next_page():
            if conn.generated == conn.fail_at:
                raise conn.error
            page = real()
            if page is not None:
                conn.generated += 1
            return page
        source.get_next_page = get_next_page
        return source


def test_at_most_two_pages_are_held():
    conn = CountingConnector(page_rows=PAGE_ROWS)
    scan = make_scan(conn, "lineitem")
    taken = 0
    while not scan.is_finished():
        if scan.get_output() is None:
            continue
        taken += 1
        # the producer runs up to its bound, and stays there
        ahead = min(READAHEAD_PAGES, LINEITEM_PAGES - taken)
        assert wait_until(lambda: conn.generated == taken + ahead)
        time.sleep(0.02)
        assert conn.generated == taken + ahead
    assert taken == conn.generated == LINEITEM_PAGES


def test_producer_exception_surfaces_from_get_output_as_itself():
    conn = CountingConnector(fail_at=3, page_rows=PAGE_ROWS)
    before = producers()
    scan = make_scan(conn, "lineitem")
    pages = [scan.get_output() for _ in range(3)]
    assert all(p is not None for p in pages)
    for _ in range(2):      # and again, should it be asked again
        with pytest.raises(TrinoError) as raised:
            scan.get_output()
        assert raised.value is conn.error
    scan.close()
    assert producers() == before and scan.is_finished()


def test_a_drivers_failing_scan_raises_the_connectors_error():
    conn = CountingConnector(fail_at=4, page_rows=PAGE_ROWS)
    before = producers()
    driver = Driver([make_scan(conn, "lineitem"), OutputCollectorOperator()])
    with pytest.raises(TrinoError) as raised:
        driver.run_to_completion()
    assert raised.value is conn.error
    assert producers() == before


# -- a scan cut short leaves no producer -------------------------------------

def test_limit_leaves_no_producer():
    runner = LocalQueryRunner({"tpch": TpchConnector(page_rows=PAGE_ROWS)},
                              Session(catalog="tpch", schema=SCHEMA))
    before = producers()
    res = runner.execute("select l_orderkey from lineitem limit 5")
    assert len(res.rows) == 5
    assert producers() == before


def test_an_aborted_task_leaves_no_producer():
    from trino_tpu.parallel.fault import RemoteTaskError
    from trino_tpu.parallel.remote_exchange import run_barrier_driver

    conn = TpchConnector(page_rows=PAGE_ROWS)
    before = producers()
    scan = make_scan(conn, "lineitem")
    driver = Driver([scan, OutputCollectorOperator()])
    assert not driver.process() and len(producers()) == len(before) + 1
    abort = threading.Event()
    abort.set()
    with pytest.raises(RemoteTaskError):
        run_barrier_driver(driver, abort)
    assert producers() == before and scan.is_finished()


class FailsOnItsSecondPage(Operator):
    def __init__(self):
        self.pages = 0

    def add_input(self, page):
        self.pages += 1
        if self.pages == 2:
            raise TrinoError("downstream failed", "GENERIC_INTERNAL_ERROR")

    def is_finished(self):
        return False


def test_a_failing_downstream_operator_leaves_no_producer():
    conn = TpchConnector(page_rows=PAGE_ROWS)
    before = producers()
    driver = Driver([make_scan(conn, "lineitem"), FailsOnItsSecondPage()])
    with pytest.raises(TrinoError, match="downstream failed"):
        driver.run_to_completion()
    assert producers() == before


def test_finish_leaves_no_producer_and_ends_the_scan():
    conn = CountingConnector(page_rows=PAGE_ROWS)
    before = producers()
    scan = make_scan(conn, "lineitem")
    assert scan.get_output() is not None
    assert len(producers()) == len(before) + 1
    scan.finish()
    assert producers() == before
    assert scan.is_finished() and scan.get_output() is None
    assert conn.generated <= 1 + READAHEAD_PAGES


def test_a_scan_dropped_unfinished_takes_its_producer_along():
    conn = TpchConnector(page_rows=PAGE_ROWS)
    before = producers()
    scan = make_scan(conn, "lineitem")
    assert scan.get_output() is not None
    assert len(producers()) == len(before) + 1
    del scan
    gc.collect()
    assert wait_until(lambda: producers() == before)


def test_many_scans_at_once_each_give_their_own_pages_in_order():
    """More scans than cores on one connector (shared dictionaries),
    threads switched every 10 us: each gives the synchronous walk's
    rows in order, and no producer outlives its scan."""
    import sys

    conn = TpchConnector(page_rows=PAGE_ROWS)
    want = generated_rows(conn, "orders", 4, False)
    before, results = producers(), {}

    def task(i):
        pages = drain(make_scan(conn, "orders", coalesce=(i % 2) * 2500))
        results[i] = [np.concatenate([np.asarray(p.cols[c])[
            np.asarray(p.valid)] for p in pages]) for c in range(len(want))]

    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        threads = [threading.Thread(target=task, args=(i,))
                   for i in range(24)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(120)
    finally:
        sys.setswitchinterval(interval)
    assert not any(t.is_alive() for t in threads) and len(results) == 24
    for got in results.values():
        assert all(np.array_equal(a, b) for a, b in zip(got, want))
    assert producers() == before


# -- where nothing is read ahead ----------------------------------------------

@pytest.mark.parametrize("name", list(COLUMNS))
def test_a_one_page_scan_starts_no_thread(name, monkeypatch):
    started = []
    monkeypatch.setattr(threading.Thread, "start",
                        lambda self: started.append(self.name))
    conn = TpchConnector()      # 65,536-row pages: tiny coalesces into one
    scan = make_scan(conn, name, splits=8, coalesce=conn.page_rows)
    pages = drain(scan)
    assert len(pages) == 1 and scan._ahead is None and started == []


def test_a_resident_source_starts_no_thread(monkeypatch):
    mem = MemoryConnector(schemas=[SCHEMA])
    mem.page_rows = 8192
    runner = LocalQueryRunner(
        {"tpch": TpchConnector(page_rows=8192), "memory": mem},
        Session(catalog="memory", schema=SCHEMA))
    runner.execute("create table lineitem as "
                   "select * from tpch.tiny.lineitem")
    started = []
    monkeypatch.setattr(threading.Thread, "start",
                        lambda self: started.append(self.name))
    md = mem.metadata()
    handle = md.get_table_handle(SCHEMA, "lineitem")
    scan = TableScanOperator(mem, md.get_columns(handle)[:4])
    for split in mem.split_manager().get_splits(handle, 4):
        scan.add_split(split)
    scan.no_more_splits()
    pages = drain(scan)
    assert len(pages) > 2 and scan._ahead is None and started == []


# -- placement, late filters, dictionaries ------------------------------------

def test_pages_land_on_the_driver_threads_device():
    device = jax.devices()[1]
    assert device != jax.devices()[0]
    conn = TpchConnector(page_rows=PAGE_ROWS)
    scan = make_scan(conn, "lineitem")
    pages = []

    def task():
        with jax.default_device(device):
            pages.extend(drain(scan))

    thread = threading.Thread(target=task)
    thread.start()
    thread.join(60)
    assert not thread.is_alive() and len(pages) == LINEITEM_PAGES
    for page in pages:
        for array in [page.valid, *page.cols, *page.nulls]:
            assert array.devices() == {device}


def test_a_filter_that_arrives_late_applies_to_pages_made_before_it():
    conn = CountingConnector(page_rows=PAGE_ROWS)
    handle, columns = table(conn, "orders")
    df = DynamicFilter("o_orderkey")
    scan = make_scan(conn, "orders", dynamic_filters=[(0, df)])
    first = scan.get_output()
    assert np.asarray(first.valid).sum() == PAGE_ROWS
    # the producer has made its pages; only now is the domain known
    assert wait_until(lambda: conn.generated == 1 + READAHEAD_PAGES)
    keys = np.arange(1, 20000, 7, dtype=np.int64)
    df.collect(keys, np.zeros(len(keys), bool), np.ones(len(keys), bool))
    for page in drain(scan):
        kept = np.asarray(page.cols[0])[np.asarray(page.valid)]
        assert len(kept) and np.isin(kept, keys).all()
        assert len(kept) < PAGE_ROWS


@pytest.mark.parametrize("name,column", [("customer", "c_name"),
                                         ("orders", "o_clerk"),
                                         ("lineitem", "l_returnflag")])
def test_dictionaries_are_the_synchronous_walks(name, column):
    """A string column's pool is the connector's one object, and holds
    the same values under the same codes whichever thread encoded."""
    ahead_conn = TpchConnector(page_rows=PAGE_ROWS // 4)
    sync_conn = TpchConnector(page_rows=PAGE_ROWS // 4)
    channel = COLUMNS[name].index(column)
    ahead = drain(make_scan(ahead_conn, name))
    sync = walked_on_this_thread(sync_conn, name, 4, None, False)
    pool = ahead_conn.table(name).dicts[column]
    assert all(p.dictionaries[channel] is pool for p in ahead)
    assert pool.values == sync_conn.table(name).dicts[column].values
    for got, expected in zip(ahead, sync):
        assert np.array_equal(np.asarray(got.cols[channel]),
                              np.asarray(expected.cols[channel]))


def test_encoding_values_already_present_does_not_touch_the_pool():
    """What lets the producer encode page k+1 into the connector's
    shared pool while operators read it for page k: once the values are
    in (q1's flags after the first page, every column from a
    deployment's second statement on) ``encode`` only looks up — the
    list, the index and the cached sort ranks are left as they are."""
    conn = TpchConnector(page_rows=PAGE_ROWS)
    drain(make_scan(conn, "lineitem"))
    pools = conn.table("lineitem").dicts
    kept = {}
    for column in ("l_returnflag", "l_linestatus"):
        pool = pools[column]
        kept[column] = (list(pool.values), dict(pool._index),
                        pool.sort_rank())
        assert pool._sort_rank is kept[column][2]
    drain(make_scan(conn, "lineitem"))
    for column, (values, index, ranks) in kept.items():
        pool = pools[column]
        assert pool.values == values and pool._index == index
        assert pool._sort_rank is ranks
