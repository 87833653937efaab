"""Dynamic filtering: build-side domains prune probe-side scans.

Reference analog: TestDynamicFiltering — a selective build side makes
the probe scan emit measurably fewer rows, without changing results.
"""

import numpy as np
import pytest

from trino_tpu.connectors.tpch import TpchConnector
from trino_tpu.exec.dynamic_filter import DynamicFilter, resolve_scan_column
from trino_tpu.runner import LocalQueryRunner
from trino_tpu.sql.analyzer import Session

SEMI_SQL = ("select count(*) from lineitem where l_orderkey in "
            "(select o_orderkey from orders where "
            "o_orderpriority = '1-URGENT' and o_totalprice > 150000)")

JOIN_SQL = ("select count(*), sum(l_quantity) from orders o, lineitem l "
            "where o.o_orderkey = l.l_orderkey "
            "and o.o_orderdate >= date '1995-01-01' "
            "and o.o_orderdate < date '1995-02-01'")


def run(sql, enabled=True):
    session = Session(catalog="tpch", schema="micro")
    session.properties["enable_dynamic_filtering"] = enabled
    r = LocalQueryRunner({"tpch": TpchConnector(page_rows=2048)}, session,
                         desired_splits=4)
    return r.execute(sql)


@pytest.mark.parametrize("sql", [SEMI_SQL, JOIN_SQL])
def test_results_unchanged_and_rows_pruned(sql):
    off = run(sql, enabled=False)
    on = run(sql, enabled=True)
    assert on.rows == off.rows
    assert "dynamic_filters" not in (off.stats or {})
    dfs = on.stats["dynamic_filters"]
    assert dfs, "no dynamic filter registered"
    total_pruned = sum(d["pruned_rows"] for d in dfs)
    total_scanned = sum(d["scanned_rows"] for d in dfs)
    assert all(d["ready"] for d in dfs)
    # the build sides are selective: most probe rows must be pruned
    assert total_pruned > 0.5 * total_scanned > 0


def test_left_join_not_filtered():
    """LEFT probes keep unmatched rows — no dynamic filter may apply."""
    sql = ("select count(*) from orders o left join lineitem l "
           "on o.o_orderkey = l.l_orderkey and l.l_quantity > 49")
    res = run(sql, enabled=True)
    assert "dynamic_filters" not in (res.stats or {})
    assert res.rows == run(sql, enabled=False).rows


def test_empty_build_prunes_everything():
    sql = ("select count(*) from lineitem where l_orderkey in "
           "(select o_orderkey from orders where o_totalprice < 0)")
    res = run(sql, enabled=True)
    assert res.rows == [(0,)]
    dfs = res.stats["dynamic_filters"]
    assert dfs and dfs[0]["build_rows"] == 0
    assert dfs[0]["pruned_rows"] == dfs[0]["scanned_rows"] > 0


def test_resolve_through_projection():
    """The scan walk follows renaming projections but stops at computed
    expressions."""
    from trino_tpu.planner.logical_planner import LogicalPlanner, Metadata
    from trino_tpu.planner.optimizer import optimize
    from trino_tpu.planner.plan import TableScanNode
    from trino_tpu.sql.parser import parse_statement

    meta = Metadata({"tpch": TpchConnector()})
    session = Session(catalog="tpch", schema="micro")
    planner = LogicalPlanner(meta, session)
    root = planner.plan(parse_statement(
        "select l_orderkey k from lineitem where l_quantity > 10"))
    root = optimize(root, meta, planner.allocator)
    sym = root.outputs[0]
    hit = resolve_scan_column(root.source, sym.name)
    assert hit is not None
    scan, pos = hit
    assert isinstance(scan, TableScanNode)
    assert scan.assignments[pos][0].type == sym.type


def test_filter_domain_semantics():
    import jax.numpy as jnp
    import numpy as np

    df = DynamicFilter("t")
    df.collect(jnp.asarray(np.array([5, 7, 9, 0], dtype=np.int64)),
               jnp.asarray(np.array([False, False, False, True])),
               jnp.asarray(np.array([True, True, True, True])))
    col = jnp.asarray(np.array([4, 5, 6, 7, 9, 10], dtype=np.int64))
    nulls = jnp.zeros(6, dtype=bool)
    valid = jnp.ones(6, dtype=bool)
    keep = np.asarray(df.apply(col, nulls, valid))
    assert keep.tolist() == [False, True, False, True, True, False]
    assert df.pruned_rows == 3
    assert df.scanned_rows == 6


def test_dynamic_filter_to_domain():
    """The build-side key domain interops with the TupleDomain model
    (round-4: dynamic filters re-expressed on predicate.Domain)."""
    import jax.numpy as jnp
    import numpy as np

    from trino_tpu.exec.dynamic_filter import DynamicFilter

    df = DynamicFilter("t")
    col = jnp.asarray(np.array([5, 9, 5, 12], dtype=np.int64))
    nulls = jnp.zeros(4, dtype=bool)
    valid = jnp.ones(4, dtype=bool)
    df.collect(col, nulls, valid)
    dom = df.to_domain()
    assert dom.includes(5) and dom.includes(9) and dom.includes(12)
    assert not dom.includes(7) and not dom.includes(None)

    empty = DynamicFilter("e")
    empty.collect(col, jnp.ones(4, dtype=bool), valid)  # all null keys
    assert empty.to_domain().is_none


# -- the value set as a membership table (PR 42) -------------------------
#
# Every case builds the same filter twice — as the build's arrays allow
# (the table, where they do) and with the table's bound shrunk to
# nothing, which is the sorted set and the search every filter had
# before — and holds both to one numpy oracle, mask by mask.

I64, I32 = np.iinfo(np.int64), np.iinfo(np.int32)


def _keys(values, dtype=np.int64, nulls=(), invalid=()):
    """(col, nulls, valid) host arrays; ``nulls`` / ``invalid`` are
    lane positions."""
    col = np.asarray(values, dtype=dtype)
    n = np.zeros(col.shape[0], dtype=bool)
    v = np.ones(col.shape[0], dtype=bool)
    n[list(nulls)] = True
    v[list(invalid)] = False
    return col, n, v


def _many_keys():
    from trino_tpu.exec.dynamic_filter import MAX_VALUE_SET

    return np.arange(MAX_VALUE_SET + 1, dtype=np.int64) * 3 - 1000


#: name -> (build (col, nulls, valid), probe column values, the form
#: the unforced filter's set takes, collect from host arrays)
TABLE_CASES = {
    "int64": (_keys([5, 7, 9, 7, 4000]), [4, 5, 6, 7, 9, 10, 4000, 4001],
              "table", False),
    "int32": (_keys([5, 7, 9, 7, 4000], np.int32),
              [4, 5, 6, 7, 9, 10, 4000, 4001], "table", False),
    "date": (_keys([9131, 9133, 9496], np.int32),    # days since epoch
             [9130, 9131, 9132, 9133, 9496, 9497], "table", False),
    "negative_keys": (_keys([-50, -3, 0, 12]),
                      [-51, -50, -4, -3, -1, 0, 1, 12, 13], "table", False),
    "keys_at_int64_max": (_keys([I64.max - 3, I64.max]),
                          [I64.min, -1, 0, I64.max - 4, I64.max - 3,
                           I64.max - 1, I64.max], "table", False),
    "keys_at_int64_min": (_keys([I64.min, I64.min + 5]),
                          [I64.min, I64.min + 1, I64.min + 5, I64.min + 6,
                           0, I64.max], "table", False),
    "keys_at_int32_extremes": (_keys([I32.max - 2, I32.max], np.int32),
                               [I32.min, 0, I32.max - 2, I32.max - 1,
                                I32.max], "table", False),
    "both_int64_extremes": (_keys([I64.min, 0, I64.max]),   # range 2^64
                            [I64.min, I64.min + 1, -1, 0, 1, I64.max],
                            "sorted", False),
    "null_and_invalid_build_lanes": (
        _keys([5, 6, 7, 8, 9], nulls=[1], invalid=[3, 4]),
        [4, 5, 6, 7, 8, 9], "table", False),
    "all_null_build": (_keys([5, 6, 7], nulls=[0, 1, 2]), [4, 5, 6, 7, 8],
                       None, False),
    "all_invalid_build": (_keys([5, 6, 7], invalid=[0, 1, 2]),
                          [4, 5, 6, 7, 8], None, False),
    "single_key": (_keys([42]), [41, 42, 43], "table", False),
    "past_max_value_set": (_keys(_many_keys()),
                           [-1001, -1000, -999, -997, 5, 392213, 392215,
                            392216, 392217], None, False),
    "float_keys": (_keys([1.5, 2.5, 2.5, 8.0], np.float64),
                   [1.0, 1.5, 2.0, 2.5, 8.0, 9.0, np.nan], "sorted", False),
    "float_keys_with_nan": (_keys([1.5, np.nan, 8.0], np.float64),
                            [1.0, 1.5, 2.0, 8.0, np.nan, 9.0], "sorted",
                            False),
    "float_keys_all_nan": (_keys([np.nan, np.nan], np.float64),
                           [1.0, np.nan], None, False),
    "host_arrays": (_keys([5, 7, 9, 7, 4000], nulls=[2]),
                    [4, 5, 6, 7, 9, 10, 4000, 4001], "sorted", True),
}


def _collected(build, from_host):
    import jax.numpy as jnp

    df = DynamicFilter("t")
    df.collect(*(build if from_host else map(jnp.asarray, build)))
    return df


def _oracle(build, col, nulls, valid):
    bcol, bnulls, bvalid = build
    keys = bcol[bvalid & ~bnulls]
    live = valid & ~nulls
    if keys.dtype.kind == "f":
        allow_nan = np.isnan(keys).any()
        keys = keys[~np.isnan(keys)]
        nan_pass = live & np.isnan(col) & allow_nan
    else:
        nan_pass = np.zeros_like(live)
    from trino_tpu.exec.dynamic_filter import MAX_VALUE_SET

    if np.unique(keys).shape[0] > MAX_VALUE_SET:
        member = (col >= keys.min()) & (col <= keys.max())
    else:
        member = np.isin(col, keys)
    return (live & member) | nan_pass


@pytest.mark.parametrize("case", sorted(TABLE_CASES))
def test_table_filter_equals_sorted_set_filter(case, monkeypatch):
    import jax.numpy as jnp

    from trino_tpu.exec import dynamic_filter

    build, probe_values, form, from_host = TABLE_CASES[case]
    as_built = _collected(build, from_host)
    monkeypatch.setattr(dynamic_filter, "TABLE_MAX_CODES", 0)
    searched = _collected(build, from_host)
    assert as_built.stats()["set"] == form
    assert searched.stats()["set"] == (form and "sorted")
    assert as_built.stats()["has_value_set"] == (form is not None)
    assert as_built.build_rows == searched.build_rows == int(
        (build[2] & ~build[1]).sum())

    dtype = build[0].dtype
    keys = build[0][build[2] & ~build[1]]
    keys = keys[keys == keys]                        # finite ones
    pages = [_keys(probe_values, dtype,
                   nulls=[1], invalid=[len(probe_values) - 2])]
    pages.append(_keys(probe_values, dtype))
    if keys.size and dtype.kind == "i":
        # pages wholly below and wholly above [lo, hi], where the
        # dtype has room for one
        info = np.iinfo(dtype)
        lo, hi = int(keys.min()), int(keys.max())
        if lo - 3 >= info.min:
            pages.append(_keys([lo - 3, lo - 2, lo - 1], dtype))
        if hi + 3 <= info.max:
            pages.append(_keys([hi + 1, hi + 2, hi + 3], dtype))
    pruned = seen = 0
    for col, nulls, valid in pages:
        want = _oracle(build, col, nulls, valid)
        for df in (as_built, searched):
            got = np.asarray(df.apply(jnp.asarray(col), jnp.asarray(nulls),
                                      jnp.asarray(valid)))
            assert got.tolist() == want.tolist(), (df.set_form, col)
        pruned += int((valid & ~want).sum())
        seen += int(valid.sum())
    for df in (as_built, searched):
        assert (df.pruned_rows, df.scanned_rows) == (pruned, seen)
    assert as_built.to_domain() == searched.to_domain()


def test_table_is_reserved_or_refused_by_the_builders_memory(monkeypatch):
    """The table's bytes go through the memory context ``collect`` is
    handed: kept reserved while the set lives, given back where the
    build shows more than MAX_VALUE_SET keys, and a pool without room
    leaves the sorted set."""
    import jax.numpy as jnp

    from trino_tpu.exec import dynamic_filter
    from trino_tpu.exec.memory import (OperatorMemoryContext,
                                      QueryMemoryPool as MemoryPool)

    build = [jnp.asarray(a) for a in _keys([5, 7, 9, 7, 4000])]
    pool = MemoryPool(1 << 20)
    ctx = OperatorMemoryContext(pool, "build")
    df = DynamicFilter("t")
    df.collect(*build, ctx)
    assert df.set_form == "table"
    assert ctx.reserved == df.table_bytes == 4096       # padded 3,996 codes

    ctx2 = OperatorMemoryContext(pool, "build2")
    df2 = DynamicFilter("t")
    with monkeypatch.context() as m:
        m.setattr(dynamic_filter, "MAX_VALUE_SET", 2)
        df2.collect(*build, ctx2)
    assert df2.set_form is None and df2.table_bytes == ctx2.reserved == 0

    tight = OperatorMemoryContext(MemoryPool(6000), "tight")
    df3 = DynamicFilter("t")
    df3.collect(*build, tight)                        # 2 x 4096 > 6000
    assert df3.set_form == "sorted" and tight.reserved == 0
    col = jnp.asarray(np.array([5, 6, 4000], dtype=np.int64))
    off, on = jnp.zeros(3, dtype=bool), jnp.ones(3, dtype=bool)
    assert np.asarray(df3.apply(col, off, on)).tolist() == \
        np.asarray(df.apply(col, off, on)).tolist() == [True, False, True]


def test_other_bounds_same_padded_range_compile_nothing(monkeypatch):
    """A second filter whose keys lie elsewhere but span the same
    padded range runs the three programs the first compiled, and
    ``collect`` reads scalars only: the key column stays on the
    device."""
    import jax.numpy as jnp

    from trino_tpu import jit_stats
    from trino_tpu.telemetry import tracing

    reads, syncs = [], []
    host_read, host_sync = tracing.host_read, tracing.host_sync

    def counted_read(x, why):
        out = host_read(x, why)
        reads.append((why, out.size))
        return out

    def counted_sync(why):
        syncs.append(why)
        return host_sync(why)

    monkeypatch.setattr(tracing, "host_read", counted_read)
    monkeypatch.setattr(tracing, "host_sync", counted_sync)
    programs = ("dynamic_filter_span", "dynamic_filter_table",
                "dynamic_filter_mask")
    rng = np.random.default_rng(7)
    for i, (lo, hi) in enumerate([(100, 1100), (-70000, -69100)]):
        keys = rng.integers(lo, hi + 1, size=512)
        keys[:2] = lo, hi
        build = _keys(keys, nulls=[5], invalid=[6])
        df = DynamicFilter(f"f{i}")
        with tracing.Tracer(ring=None).span("statement"):
            df.collect(*map(jnp.asarray, build))
        assert df.set_form == "table" and df.table_bytes == 1024
        col, nulls, valid = _keys(np.arange(lo - 24, lo + 1000), nulls=[30])
        mask = np.asarray(df.apply(*map(jnp.asarray, (col, nulls, valid))))
        assert mask.tolist() == _oracle(build, col, nulls, valid).tolist()
        if i == 0:
            traced = jit_stats.total_for(*programs)
    assert jit_stats.total_for(*programs) == traced
    assert reads == [("dynamic_filter_collect", 3),
                     ("dynamic_filter_collect", 1)] * 2
    assert syncs == ["dynamic_filter_collect"] * 4    # no bare sync


def _tpch_filters(qid, forced_search, monkeypatch):
    from trino_tpu.exec import dynamic_filter
    from trino_tpu.resources.tpch_queries import TPCH_QUERIES
    from trino_tpu.telemetry import stats_store

    stats_store.store().clear()         # both runs plan from no history
    if forced_search:
        monkeypatch.setattr(dynamic_filter, "TABLE_MAX_CODES", 0)
    runner = LocalQueryRunner({"tpch": TpchConnector()},
                              Session(catalog="tpch", schema="tiny"))
    res = runner.execute(TPCH_QUERIES[qid])
    return res.rows, res.stats["dynamic_filters"]


@pytest.mark.parametrize("qid", [3, 9, 18])
def test_tpch_filters_prune_the_rows_the_search_pruned(qid, monkeypatch):
    """Per filter of a whole statement, ``pruned_rows`` and
    ``scanned_rows`` are what the sorted set and the search gave (the
    parent's algorithm, forced here by a bound of nothing), and a value
    set exists for the same filters."""
    rows, filters = _tpch_filters(qid, False, monkeypatch)
    rows_s, filters_s = _tpch_filters(qid, True, monkeypatch)
    assert rows == rows_s
    assert filters and len(filters) == len(filters_s)
    for got, want in zip(filters, filters_s):
        assert got.pop("set") == (want.pop("set") and "table")
        assert got == want
    assert any(f["has_value_set"] for f in filters)
    assert sum(f["scanned_rows"] for f in filters) > 0


def test_scan_span_counts_the_value_sets_it_tested(monkeypatch):
    """A traced statement's scan spans say how many page·filter
    applications tested a value set and how many of them the table
    answered; a filter forced to the search counts under the first
    only, and a scan without a filter counts nothing."""
    from trino_tpu.exec import dynamic_filter

    def scans(forced_search):
        if forced_search:
            monkeypatch.setattr(dynamic_filter, "TABLE_MAX_CODES", 0)
        trace = run(JOIN_SQL).stats["trace"]
        return {s["attrs"]["df_member_pages"]: s["attrs"]["df_table_pages"]
                for s in trace if s["name"] == "TableScanOperator"}

    by_table = scans(False)
    assert by_table.pop(0) == 0                 # the build side's scan
    (tested, answered), = by_table.items()
    assert tested == answered > 0
    assert scans(True) == {0: 0, tested: 0}
