"""Memory accounting + host spill.

Reference analog: TestMemoryPools / TestMemoryRevokingScheduler — a query
under an artificially low memory cap completes when spill is enabled
(revoking operators park state in host RAM) and fails with
EXCEEDED_LOCAL_MEMORY_LIMIT when it is not.
"""

import pytest

from trino_tpu.connectors.tpch import TpchConnector
from trino_tpu.exec.memory import (MemoryExceededError, QueryMemoryPool,
                                   device_page_bytes)
from trino_tpu.runner import LocalQueryRunner
from trino_tpu.sql.analyzer import Session
from trino_tpu.types import TrinoError

# an aggregation + join + sort query with real state to account
# (q18 shape: big build side, big agg)
SQL = ("select l_orderkey, sum(l_quantity) qty from lineitem "
       "group by l_orderkey order by qty desc, l_orderkey limit 10")

JOIN_SQL = ("select o_orderpriority, count(*) from orders o, lineitem l "
            "where o.o_orderkey = l.l_orderkey and l_quantity > 30 "
            "group by o_orderpriority order by o_orderpriority")

# An aggregation's partials are as wide as their groups, so parking them
# on the host compacts nothing: an aggregation spills AND completes under
# a cap only where groups repeat across many pages, so that the chunked
# merge reduces them. 200 parts over ~24 pages of 256 rows do.
AGG_SPILL_SQL = ("select l_partkey, sum(l_quantity) qty from lineitem "
                 "group by l_partkey order by qty desc, l_partkey limit 10")
AGG_SPILL_PAGE_ROWS = 64


def make_runner(page_rows=1024, **props):
    session = Session(catalog="tpch", schema="micro")
    session.properties.update(props)
    return LocalQueryRunner({"tpch": TpchConnector(page_rows=page_rows)},
                            session, desired_splits=8)


@pytest.fixture(scope="module")
def baseline_rows():
    return {sql: make_runner().execute(sql).rows
            for sql in (SQL, JOIN_SQL, AGG_SPILL_SQL)}


def test_accounting_records_peak():
    res = make_runner().execute(SQL)
    mem = res.stats["memory"]
    assert mem["peak_bytes"] > 0
    assert mem["spill_events"] == 0
    assert mem["reserved_bytes"] == 0  # everything released at finish


def test_low_cap_without_spill_fails():
    r = make_runner(query_max_memory_bytes=120_000, spill_enabled=False)
    with pytest.raises(TrinoError) as exc:
        r.execute(SQL)
    assert exc.value.code == "EXCEEDED_LOCAL_MEMORY_LIMIT"


def test_low_cap_with_spill_completes(baseline_rows):
    r = make_runner(AGG_SPILL_PAGE_ROWS, query_max_memory_bytes=300_000,
                    spill_enabled=True)
    res = r.execute(AGG_SPILL_SQL)
    assert res.rows == baseline_rows[AGG_SPILL_SQL]
    mem = res.stats["memory"]
    assert mem["spill_events"] > 0
    assert mem["spilled_bytes"] > 0


def test_join_spill_matches_baseline(baseline_rows):
    # the join's build is what spills (the aggregation above it keeps
    # 16 lanes a page); HBO off so a recorded run does not re-size it
    r = make_runner(query_max_memory_bytes=60_000, spill_enabled=True,
                    hbo_enabled=False)
    res = r.execute(JOIN_SQL)
    assert res.rows == baseline_rows[JOIN_SQL]
    assert res.stats["memory"]["spill_events"] > 0


SORT_SQL = "select * from lineitem order by l_extendedprice"


def test_host_sort_under_low_cap_matches_device_sort():
    """A cap too small for the whole-input device sort falls back to the
    host-merge path (page-at-a-time download + lexsort + chunked
    re-upload) and must produce the same ordering."""
    want = make_runner().execute(SORT_SQL)
    r = make_runner(query_max_memory_bytes=1_000_000, spill_enabled=True)
    res = r.execute(SORT_SQL)
    assert res.stats["memory"]["spill_events"] > 0
    # ties on l_extendedprice make exact row order plan-dependent;
    # compare the multiset and the sort-key ordering
    assert sorted(res.rows) == sorted(want.rows)
    prices = [row[5] for row in res.rows]  # l_extendedprice
    assert prices == sorted(prices)


def test_pool_revokes_largest_first():
    pool = QueryMemoryPool(1000, spill_enabled=True)
    order = []
    a = pool.create_context("a")
    b = pool.create_context("b")
    a.set_revoke_callback(lambda: order.append("a") or 600)
    b.set_revoke_callback(lambda: order.append("b") or 300)
    a.reserve(600)
    b.reserve(300)
    c = pool.create_context("c")
    c.reserve(500)  # must revoke a (largest) to fit
    assert order == ["a"]
    assert pool.reserved == 300 + 500
    assert pool.spill_events == 1


def test_pool_raises_when_spill_disabled():
    pool = QueryMemoryPool(100, spill_enabled=False)
    ctx = pool.create_context("x")
    ctx.reserve(90)
    with pytest.raises(MemoryExceededError):
        ctx.reserve(20)


def test_device_page_bytes():
    import jax.numpy as jnp

    from trino_tpu import types as T
    from trino_tpu.block import DevicePage

    page = DevicePage([T.BIGINT], [jnp.zeros(16, dtype=jnp.int64)],
                      [jnp.zeros(16, dtype=bool)],
                      jnp.ones(16, dtype=bool), [None])
    # 16*8 data + 16 nulls + 16 valid
    assert device_page_bytes(page) == 16 * 8 + 16 + 16
