"""TupleDomain algebra + connector pushdown negotiation.

Reference analog: ``spi/predicate/TestTupleDomain.java`` /
``TestDomain.java`` / ``TestSortedRangeSet.java`` and
``TestPushPredicateIntoTableScan.java``.
"""

import numpy as np
import pytest

from trino_tpu import session_properties as SP
from trino_tpu.connectors.tpch import TpchConnector
from trino_tpu.connectors.tpcds import TpcdsConnector
from trino_tpu.predicate import (Domain, Range, TupleDomain, ValueSet,
                                 domain_mask)
from trino_tpu.runner import LocalQueryRunner
from trino_tpu.sql.analyzer import Session

# ------------------------------------------------------------ algebra ----


def test_range_basics():
    r = Range(1, True, 5, False)        # [1, 5)
    assert r.includes(1) and r.includes(4) and not r.includes(5)
    assert not r.includes(0)
    with pytest.raises(ValueError):
        Range(5, True, 1, True)
    with pytest.raises(ValueError):
        Range(3, False, 3, True)        # (3,3] is empty
    assert Range.single(3).includes(3)


def test_value_set_union_intersect_complement():
    a = ValueSet.of_ranges(Range(0, True, 10, True))
    b = ValueSet.of_ranges(Range(5, True, 20, True))
    u = a.union(b)
    assert u.ranges == (Range(0, True, 20, True),)
    i = a.intersect(b)
    assert i.ranges == (Range(5, True, 10, True),)
    c = a.complement()
    assert len(c.ranges) == 2
    assert c.includes(-1) and c.includes(11)
    assert not c.includes(0) and not c.includes(10)
    # complement round-trips
    assert c.complement().ranges == a.ranges
    # disjoint stay disjoint; touching-at-excluded stay separate
    d = ValueSet.of_ranges(Range(0, True, 1, False),
                           Range(1, False, 2, True))
    assert len(d.ranges) == 2
    # touching-at-included merge
    e = ValueSet.of_ranges(Range(0, True, 1, True),
                           Range(1, False, 2, True))
    assert e.ranges == (Range(0, True, 2, True),)


def test_value_set_discrete():
    v = ValueSet.of(3, 1, 2, 2)
    assert [r.low for r in v.ranges] == [1, 2, 3]
    assert v.includes(2) and not v.includes(4)
    assert ValueSet.all_().intersect(v) == v
    assert v.union(ValueSet.none()) == v
    assert ValueSet.none().is_none


def test_domain_null_handling():
    d = Domain.single(5)
    assert not d.includes(None) and d.includes(5)
    n = Domain.only_null()
    assert n.includes(None) and not n.includes(5)
    u = d.union(n)
    assert u.includes(None) and u.includes(5)
    assert d.complement().includes(None)
    assert Domain.not_null().intersect(Domain.all_()) == Domain.not_null()
    assert d.intersect(Domain.single(6)).is_none


def test_tuple_domain():
    td1 = TupleDomain.of({"a": Domain.single(1),
                          "b": Domain.not_null()})
    td2 = TupleDomain.of({"a": Domain.of_values(1, 2)})
    inter = td1.intersect(td2)
    assert inter.domain("a") == Domain.single(1)
    assert inter.domain("b") == Domain.not_null()
    assert inter.domain("c").is_all
    # contradiction collapses to NONE
    none = td1.intersect(TupleDomain.of({"a": Domain.single(9)}))
    assert none.is_none
    assert TupleDomain.none().intersect(td1).is_none
    # column-wise union keeps only both-sided columns
    u = td1.union(td2)
    assert u.domain("a") == Domain.of_values(1, 2)
    assert u.domain("b").is_all


def test_domain_mask_numpy():
    data = np.array([1, 5, 7, 9, 3], dtype=np.int64)
    nulls = np.array([False, False, True, False, False])
    d = Domain(ValueSet.of_ranges(Range(3, True, 7, True)), False)
    assert domain_mask(data, nulls, None, d).tolist() == \
        [False, True, False, False, True]
    d2 = Domain(ValueSet.of_ranges(Range(3, True, 7, True)), True)
    assert domain_mask(data, nulls, None, d2).tolist() == \
        [False, True, True, False, True]


def test_domain_mask_pooled():
    from trino_tpu.block import Dictionary

    d = Dictionary(["AUTOMOBILE", "BUILDING", "MACHINERY"])
    data = np.array([0, 1, 2, 1], dtype=np.int32)
    dom = Domain.single("BUILDING")
    assert domain_mask(data, None, d, dom).tolist() == \
        [False, True, False, True]


# ----------------------------------------------------------- pushdown ----


def _runners(connectors, schema, catalog):
    on = LocalQueryRunner(connectors,
                          Session(catalog=catalog, schema=schema))
    sess = Session(catalog=catalog, schema=schema)
    SP.set_property(sess.properties, "filter_pushdown_enabled", False)
    off = LocalQueryRunner(connectors, sess)
    return on, off


def _scan_rows(runner, sql):
    """TableScan output rows from EXPLAIN ANALYZE operator stats."""
    res = runner.execute("explain analyze " + sql)
    rows = 0
    seen = False
    for (line,) in res.rows:
        if "TableScanOperator" in line:
            seen = True
            rows += int(line.split(":")[1].strip().split(" ")[0])
    assert seen, "no TableScanOperator line in EXPLAIN ANALYZE"
    return rows


def test_tpch_scan_pruning_by_stats():
    on, off = _runners({"tpch": TpchConnector(page_rows=2048)},
                       "micro", "tpch")
    sql = ("select count(*) from lineitem "
           "where l_shipdate <= date '1995-06-17' and l_quantity < 10")
    assert on.execute(sql).rows == off.execute(sql).rows
    pruned = _scan_rows(on, sql)
    full = _scan_rows(off, sql)
    assert pruned < full / 4, (pruned, full)
    # EXPLAIN shows the constraint on the scan
    plan = on.explain(sql)
    assert "constraint{" in plan and "l_shipdate" in plan


def test_tpcds_scan_pruning_by_stats():
    on, off = _runners({"tpcds": TpcdsConnector(page_rows=2048)},
                       "micro", "tpcds")
    sql = ("select count(*) from store_sales "
           "where ss_quantity between 1 and 20")
    assert on.execute(sql).rows == off.execute(sql).rows
    pruned = _scan_rows(on, sql)
    full = _scan_rows(off, sql)
    assert pruned < full, (pruned, full)
    assert "constraint{" in on.explain(sql)


def test_pushdown_correctness_matrix():
    on, off = _runners({"tpch": TpchConnector(page_rows=1024)},
                       "micro", "tpch")
    for sql in [
        "select count(*) from orders where o_orderkey in (1,2,3) "
        "or o_orderkey > 5000",
        "select count(*) from orders where o_orderdate <> "
        "date '1995-03-15'",
        "select count(*) from customer where c_mktsegment = 'BUILDING'",
        "select count(*) from nation where n_name > 'M' "
        "or n_name = 'CHINA'",
        "select count(*) from lineitem where l_discount between "
        "0.05 and 0.07",
        "select count(*) from part where p_size >= 10 and p_size <= 20",
        # residual + pushable mix: length() is not extractable
        "select count(*) from nation where n_regionkey = 2 "
        "and length(n_name) > 5",
        # contradiction: never matches
        "select count(*) from nation where n_regionkey = 2 "
        "and n_regionkey = 3",
    ]:
        assert on.execute(sql).rows == off.execute(sql).rows, sql


def test_pushdown_through_joins_micro():
    """Pushdown composes with join planning + dynamic filtering."""
    from trino_tpu.resources.tpch_queries import TPCH_QUERIES

    on, off = _runners({"tpch": TpchConnector(page_rows=2048)},
                       "micro", "tpch")
    for q in (3, 6, 12):
        assert sorted(on.execute(TPCH_QUERIES[q]).rows) == \
            sorted(off.execute(TPCH_QUERIES[q]).rows), f"q{q}"


def test_truncating_cast_stays_residual():
    """cast(-2.6 as bigint) truncates toward zero (-2) in the kernel;
    extraction must NOT floor it to -3 and drop the conjunct (round-4
    review finding)."""
    on, off = _runners({"tpch": TpchConnector(page_rows=512)},
                       "micro", "tpch")
    sql = ("select count(*) from nation "
           "where n_regionkey - 4 <= cast(-2.6 as bigint)")
    assert on.execute(sql).rows == off.execute(sql).rows
    # directly on a column: the cast literal is non-integral -> residual
    sql2 = ("select count(*) from nation "
            "where n_regionkey <= cast(2.6 as bigint)")
    assert on.execute(sql2).rows == off.execute(sql2).rows == [(15,)]


def test_float_ne_keeps_nan_rows():
    """d <> 5.0 keeps NaN rows under IEEE not_equal; pushdown must not
    prune them (round-4 review finding)."""
    from trino_tpu.connectors.memory import MemoryConnector

    mem = MemoryConnector()
    on, off = _runners({"mem": mem}, "default", "mem")
    on.execute("create table t (d double)")
    on.execute("insert into t values (5.0), (1.5)")
    on.execute("insert into t select nan()")
    for r in (on, off):
        rows = r.execute("select count(*) from t where d <> 5.0").rows
        assert rows == [(2,)], rows


def test_partial_enforcement_residual_refiltered():
    """ConstraintApplicationResult semantics: a connector enforcing only
    ONE of two offered column domains returns the other as the RESIDUAL
    TupleDomain; the engine keeps filtering that column itself and the
    answer stays correct (reference:
    spi/connector/ConstraintApplicationResult.java remainingFilter)."""
    from trino_tpu.connectors.spi import negotiate_constraint
    from trino_tpu.connectors.tpch import TpchMetadata

    class OneColumnMetadata(TpchMetadata):
        offered_cols = []

        def apply_filter(self, table, constraint):
            OneColumnMetadata.offered_cols.append(
                sorted(constraint.as_dict().keys()))
            # the connector only knows how to prune on the key
            return negotiate_constraint(
                table, constraint,
                (c.name for c in self.get_columns(table)),
                enforceable={"n_nationkey"})

    class OneColumnTpch(TpchConnector):
        def metadata(self):
            return OneColumnMetadata(self)

    # (the memory connector declines pushdown since its tables live on
    # the device, and refuses a handle that carries a constraint)
    on, off = _runners({"tpch": OneColumnTpch()}, "tiny", "tpch")
    sql = ("select n_nationkey, n_regionkey from nation "
           "where n_nationkey >= 2 and n_regionkey <= 0")
    rows_on = sorted(on.execute(sql).rows)
    assert rows_on == [(5, 0), (14, 0), (15, 0), (16, 0)]
    assert rows_on == sorted(off.execute(sql).rows)
    # both domains were offered together at least once (the iterative
    # engine may re-offer the residual alone on later passes); only the
    # key landed on the handle
    assert ["n_nationkey", "n_regionkey"] in OneColumnMetadata.offered_cols
    plan = on.explain(sql)
    assert "constraint{n_nationkey" in plan \
        and "constraint{n_nationkey, n_regionkey" not in plan
    # the residual conjunct stays as an engine-side filter
    assert "n_regionkey" in plan.split("TableScan")[0]
