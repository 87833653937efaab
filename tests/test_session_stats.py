"""Session properties + EXPLAIN ANALYZE stats (reference analog:
SystemSessionProperties + ExplainAnalyzeOperator tests)."""

import pytest

from trino_tpu.connectors.tpch import TpchConnector
from trino_tpu.runner import LocalQueryRunner
from trino_tpu.sql.analyzer import Session


@pytest.fixture()
def runner():
    return LocalQueryRunner({"tpch": TpchConnector(page_rows=4096)},
                            Session(catalog="tpch", schema="micro"))


def test_set_show_session(runner):
    rows = runner.execute("show session").rows
    names = [r[0] for r in rows]
    assert "task_concurrency" in names and "desired_splits" in names
    runner.execute("set session desired_splits = 2")
    rows = dict((r[0], r[1]) for r in runner.execute("show session").rows)
    assert rows["desired_splits"] == "2"
    # invalid property
    with pytest.raises(Exception):
        runner.execute("set session no_such_prop = 1")
    with pytest.raises(Exception):
        runner.execute("set session task_concurrency = 0")


def test_session_property_affects_execution(runner):
    runner.execute("set session desired_splits = 1")
    assert runner.execute("select count(*) from nation").rows == [(25,)]


def test_explain_analyze(runner):
    res = runner.execute(
        "explain analyze select n_regionkey, count(*) from nation "
        "group by n_regionkey")
    text = "\n".join(r[0] for r in res.rows)
    assert "Aggregation" in text
    assert "TableScanOperator" in text
    assert "rows" in text and "ms" in text


def test_join_distribution_type_session():
    from trino_tpu.parallel.distributed import DistributedQueryRunner

    conn = TpchConnector(page_rows=4096)
    s = Session(catalog="tpch", schema="micro")
    s.properties["join_distribution_type"] = "PARTITIONED"
    d = DistributedQueryRunner({"tpch": conn}, s, n_workers=2)
    plan = d.explain("select count(*) from nation, region "
                     "where n_regionkey = r_regionkey")
    assert "hash" in plan
    s2 = Session(catalog="tpch", schema="micro")
    s2.properties["join_distribution_type"] = "BROADCAST"
    d2 = DistributedQueryRunner({"tpch": conn}, s2, n_workers=2)
    plan2 = d2.explain("select count(*) from nation, region "
                       "where n_regionkey = r_regionkey")
    assert "broadcast" in plan2


def test_ntile_ignores_padding(runner):
    rows = runner.execute(
        "select ntile(2) over (order by n_nationkey) nt from nation").rows
    counts = {}
    for (v,) in rows:
        counts[v] = counts.get(v, 0) + 1
    assert counts == {1: 13, 2: 12}


def test_explain_ctas_does_not_create_table():
    from trino_tpu.connectors.memory import MemoryConnector

    r = LocalQueryRunner({"memory": MemoryConnector()},
                         Session(catalog="memory", schema="default"))
    r.execute("explain create table t1 as select 1 x")
    # planning must not have created t1
    res = r.execute("create table t1 as select 1 x")
    assert res.rows == [(1,)]


def test_session_property_case_insensitive(runner):
    runner.execute("set session join_distribution_type = 'broadcast'")
    vals = dict((r[0], r[1])
                for r in runner.execute("show session").rows)
    assert vals["join_distribution_type"] == "BROADCAST"


@pytest.mark.parametrize("statement, refusal", [
    ("set session join_strategy = 'MATMUL'",
     "unknown session property: join_strategy"),
    ("set session matmul_join_max_key_range = 64",
     "unknown session property: matmul_join_max_key_range"),
    ("set session aggregation_strategy = 'GLOBAL_HASH'",
     "unknown session property: aggregation_strategy"),
    ("set session global_hash_agg_max_table = 1024",
     "unknown session property: global_hash_agg_max_table"),
    ("set session device_exchange_sizing = 'legacy'",
     "value 'legacy' out of range for device_exchange_sizing"),
], ids=["join_strategy", "matmul_join_max_key_range",
        "aggregation_strategy", "global_hash_agg_max_table",
        "device_exchange_sizing_legacy"])
def test_names_removed_with_their_kernels_are_refused(runner, statement,
                                                      refusal):
    """The join's probe and the grouping's merge are chosen where the
    keys are seen (PR 46): the switches that chose them in the planner,
    and the exchange sizing mode no deployment selected, are outside
    input like any other unknown name or value."""
    from trino_tpu.types import TrinoError

    before = dict(runner.session.properties)
    with pytest.raises(TrinoError) as refused:
        runner.execute(statement)
    assert refusal in str(refused.value)
    assert refused.value.code == "INVALID_SESSION_PROPERTY"
    assert runner.session.properties == before
    assert len(runner.execute("show session").rows) == 67
