"""Memo-based iterative optimizer: rules, exploration, join ordering.

Reference analog: the IterativeOptimizer/Memo tests
(``sql/planner/iterative/``) and ``TestReorderJoins`` — rule fixpoint
per group, pattern matching through the lookup, cost-based join-order
exploration with provenance in EXPLAIN.
"""

import pytest

from trino_tpu.connectors.tpch import TpchConnector
from trino_tpu.resources.tpch_queries import TPCH_QUERIES
from trino_tpu.runner import LocalQueryRunner
from trino_tpu.sql.analyzer import Session


@pytest.fixture(scope="module")
def runner():
    return LocalQueryRunner({"tpch": TpchConnector(page_rows=2048)},
                            Session(catalog="tpch", schema="micro"))


def test_q9_join_order_explored(runner):
    """The round-3/4 carried criterion: q9's six-relation region gets a
    cost-based order — no CrossJoin survives, the selective %green%
    filter sits under the join against part, and EXPLAIN names the
    rule."""
    plan = runner.explain(TPCH_QUERIES[9])
    assert "CrossJoin" not in plan
    assert "ReorderJoins" in plan
    # the selective filter was sunk into its relation (below some join)
    like_line = [l for l in plan.splitlines() if "like" in l][0]
    scan_part = [l for l in plan.splitlines()
                 if "TableScan tpch.micro.part " in l][0]
    join_lines = [l for l in plan.splitlines() if "Join inner" in l]
    assert join_lines, plan
    depth = len(like_line) - len(like_line.lstrip())
    join_depth = min(len(l) - len(l.lstrip()) for l in join_lines)
    assert depth > join_depth, "filter not pushed below the join region"
    assert len(scan_part) - len(scan_part.lstrip()) > depth


def test_q9_rows_unchanged_by_reorder(runner):
    rows = runner.execute(TPCH_QUERIES[9]).rows
    assert len(rows) == 54
    assert rows == sorted(rows, key=lambda r: (r[0], -r[1]))


def test_q9_plan_settles_within_four_statements():
    """History re-plans a shape from what its last run read; the fourth
    statement's physical plan is the third's, and so are its rows (q9
    ran under four plans in five statements while a scan's history was
    what a dynamic filter had left of it and a join's history was served
    to the same criteria over other relations:
    ``tests/test_q9_deep_join.py`` holds the served path to it)."""
    from trino_tpu.telemetry import stats_store

    stats_store.store().clear()
    fresh = LocalQueryRunner({"tpch": TpchConnector(page_rows=2048)},
                             Session(catalog="tpch", schema="micro"))
    runs = [fresh.execute(TPCH_QUERIES[9]) for _ in range(4)]
    plans = [[s for s in r.stats["trace"] if s["parent_id"] is None][0]
             ["attrs"]["plan_fp"] for r in runs]
    assert plans[3] == plans[2]
    assert all(r.rows == runs[0].rows for r in runs)
    line, = [ln for ln in fresh.explain(TPCH_QUERIES[9]).splitlines()
             if "TableScan tpch.micro.lineitem" in ln]
    assert "[source=hbo]" in line
    rows = fresh.execute("select count(*) from lineitem").rows[0][0]
    assert f"est~{rows} rows" in line


def test_provenance_in_explain(runner):
    plan = runner.explain(
        "select n_name from nation where n_regionkey = 2 "
        "order by n_name limit 3")
    assert "Optimizer rules applied:" in plan
    assert "PushFilterIntoTableScan" in plan


def test_limit_over_sort_becomes_topn(runner):
    plan = runner.explain(
        "select o_custkey from orders order by o_totalprice limit 5")
    assert "TopN" in plan
    assert "LimitOverSortToTopN" in plan or "Limit" not in plan


def test_filter_pushes_through_aggregation(runner):
    """HAVING-style key conjuncts sink below the aggregation."""
    plan = runner.explain(
        "select * from (select l_returnflag f, count(*) c from lineitem "
        "group by l_returnflag) where f = 'A'")
    lines = plan.splitlines()
    agg = [i for i, l in enumerate(lines) if "Aggregation" in l][0]
    constrained_scan = [i for i, l in enumerate(lines)
                        if "constraint{l_returnflag" in l]
    assert constrained_scan and constrained_scan[0] > agg, plan
    rows = runner.execute(
        "select * from (select l_returnflag f, count(*) c from lineitem "
        "group by l_returnflag) where f = 'A'").rows
    assert rows == [("A", 1590)]


@pytest.mark.parametrize("query", [3, 16, 18, 20, 21])
def test_exploration_terminates_and_is_idempotent(query, runner):
    """Re-optimizing an already-optimal plan must not diverge (the
    ReorderJoins termination argument: the DP is deterministic with
    optimal substructure; PushSemiJoinBelowJoin's: a semi join only
    ever moves towards a leaf — q16, q18, q20 and q21 are the texts it
    fires in, once to five times)."""
    p1 = runner.explain(TPCH_QUERIES[query])
    p2 = runner.explain(TPCH_QUERIES[query])
    assert p1 == p2
    assert ("PushSemiJoinBelowJoin" in p1) == (query != 3)


def test_merge_limits_rule():
    from trino_tpu.planner.memo import (IterativeOptimizer, Lookup,
                                        Memo, RuleContext)
    from trino_tpu.planner.plan import LimitNode, ValuesNode
    from trino_tpu.planner.rules import MergeLimits
    from trino_tpu.planner.symbols import Symbol
    from trino_tpu import types as T

    v = ValuesNode([Symbol("x", T.BIGINT)], [])
    plan = LimitNode(LimitNode(v, 10, 0), 3, 0)
    memo = Memo()
    gid = memo.insert(plan)
    ctx = RuleContext(Lookup(memo), None, None, None)
    out = MergeLimits().apply(memo.node(gid), ctx)
    assert isinstance(out, LimitNode) and out.count == 3
    assert not isinstance(ctx.lookup.resolve(out.source), LimitNode)


def test_join_region_through_views(runner):
    """Regions flatten through group references left by other rules
    (filters/projections between joins)."""
    sql = ("select c.c_name, sum(l.l_quantity) q from customer c, "
           "orders o, lineitem l where c.c_custkey = o.o_custkey and "
           "o.o_orderkey = l.l_orderkey and c.c_mktsegment = 'BUILDING' "
           "group by c.c_name order by q desc limit 5")
    plan = runner.explain(sql)
    assert "CrossJoin" not in plan
    assert "ReorderJoins" in plan
    rows = runner.execute(sql).rows
    assert len(rows) == 5


# -- PushSemiJoinBelowJoin ---------------------------------------------

#: a probe relation whose key ``nk`` repeats (1..6, thousands of rows
#: each) and is NULL for every seventh customer's orders
_O = ("(select o_orderkey ok, o_custkey ck, nullif(o_custkey % 7, 0) nk "
      "from orders) o")
_IN = "nk in (select n_nationkey from nation where n_nationkey > 3)"
_NOT_EXISTS = ("not exists (select 1 from nation "
               "where n_nationkey = nk and n_nationkey > 3)")
_Q18 = ("select c_name, o_orderkey, l_linenumber "
        "from customer, orders, lineitem where o_orderkey {} "
        "(select l_orderkey from lineitem group by l_orderkey "
        "having sum(l_quantity) > 250) "
        "and c_custkey = o_custkey and o_orderkey = l_orderkey "
        "and o_orderkey < 2000")

#: id -> (statement, firings of the rule, what lies under the semi or
#: anti join's probe side once projections and filters are looked
#: through)
SEMI_SHAPES = {
    "semi_through_inner": (
        f"select ok, nk, c_name from {_O} join customer "
        f"on ck = c_custkey where {_IN}", 1, "leaf"),
    "anti_through_inner": (
        f"select ok, nk, c_name from {_O} join customer "
        f"on ck = c_custkey where {_NOT_EXISTS}", 1, "leaf"),
    "semi_through_cross": (
        f"select ok, nk, r_name from {_O}, region where {_IN}",
        1, "leaf"),
    "anti_through_cross": (
        f"select ok, nk, r_name from {_O}, region where {_NOT_EXISTS}",
        1, "leaf"),
    "null_aware_not_in_through_cross": (
        f"select ok, nk, r_name from {_O}, region where nk not in "
        "(select n_nationkey from nation where n_nationkey > 3)",
        1, "leaf"),
    "semi_through_filter_over_cross": (
        f"select ok, nk, c_name from {_O}, customer "
        f"where ck = c_custkey and c_acctbal > 0 and {_IN}", 1, "leaf"),
    "anti_through_filter_over_inner": (
        f"select ok, nk, c_name from {_O} join customer "
        f"on ck = c_custkey where c_acctbal > 0 and {_NOT_EXISTS}",
        1, "leaf"),
    "semi_two_levels_to_the_scan": (_Q18.format("in"), 2, "leaf"),
    "anti_two_levels_to_the_scan": (_Q18.format("not in"), 2, "leaf"),
    "keys_from_both_inputs": (
        "select o_orderkey, c_name from orders, customer "
        "where o_custkey = c_custkey and exists (select 1 from lineitem "
        "where l_orderkey = o_orderkey and l_suppkey = c_nationkey)",
        0, "join"),
    "filter_expr_names_both_inputs": (
        "select o_orderkey, c_name from orders, customer "
        "where o_custkey = c_custkey and exists (select 1 from lineitem "
        "where l_orderkey = o_orderkey and l_suppkey <> c_nationkey)",
        0, "join"),
    "null_extended_side_of_a_left_join": (
        "select c_custkey, o_orderkey from customer left join orders "
        "on c_custkey = o_custkey where not exists (select 1 "
        "from lineitem where l_orderkey = o_orderkey "
        "and l_quantity > 45)", 0, "join"),
    "no_join_beneath": (
        "select o_orderpriority, count(*) from orders where exists "
        "(select 1 from lineitem where l_orderkey = o_orderkey and "
        "l_commitdate < l_receiptdate) group by o_orderpriority",
        0, "leaf"),
}


@pytest.fixture(scope="module")
def tiny_runners():
    """Two runners over ``tiny`` (a plan cache each): one plans with the
    rule, the other under ``parents_rules``."""
    return [LocalQueryRunner({"tpch": TpchConnector(page_rows=2048)},
                             Session(catalog="tpch", schema="tiny"))
            for _ in range(2)]


@pytest.fixture
def parents_rules(monkeypatch):
    """From the call on, ``default_rules()`` is the parent's set: the
    same rules less ``PushSemiJoinBelowJoin``."""
    from trino_tpu.planner import rules

    full = rules.default_rules

    def without():
        monkeypatch.setattr(rules, "default_rules", lambda: [
            r for r in full()
            if not isinstance(r, rules.PushSemiJoinBelowJoin)])

    return without


def _firings(root) -> int:
    return sum(name == "PushSemiJoinBelowJoin"
               for name, _ in root.optimizer_trace)


def _filtering_joins(node):
    from trino_tpu.planner.plan import JoinNode

    found = [node] if isinstance(node, JoinNode) \
        and node.join_type in ("semi", "anti") else []
    for s in node.sources:
        found += _filtering_joins(s)
    return found


@pytest.mark.parametrize("shape", sorted(SEMI_SHAPES))
def test_semi_join_moves_below_the_join_that_one_input_keys(
        shape, tiny_runners, parents_rules):
    from trino_tpu.planner.plan import (CrossJoinNode, FilterNode,
                                        JoinNode, ProjectNode)
    from trino_tpu.sql.parser import parse_statement

    sql, firings, beneath = SEMI_SHAPES[shape]
    pushed, parent = tiny_runners
    root = pushed.plan_statement(parse_statement(sql))
    assert _firings(root) == firings
    # it stays a semi / anti join, and where it is says whether it moved
    joins = _filtering_joins(root)
    assert joins and all(j.join_type == ("anti" if "not " in sql
                                         else "semi") for j in joins)
    probe = joins[0].left
    while isinstance(probe, (FilterNode, ProjectNode)):
        probe = probe.source
    assert isinstance(probe, (JoinNode, CrossJoinNode)) \
        == (beneath == "join")
    if firings:
        got = pushed.execute(sql).rows
        parents_rules()
        assert _firings(parent.plan_statement(parse_statement(sql))) == 0
        want = parent.execute(sql).rows
        assert got and sorted(got, key=repr) == sorted(want, key=repr)
        if "nk" in sql:
            keys = [r[1] for r in got]
            assert len(keys) > len(set(keys))      # duplicate keys
            assert (None in keys) == ("not exists" in sql)


@pytest.mark.parametrize("name", ["q1", "q3", "q6", "q9", "q13"])
def test_rule_leaves_plans_without_a_semi_join_as_the_parent_made_them(
        name, tiny_runners, parents_rules):
    """The benchmark's other templates plan no semi or anti join: zero
    firings, and the physical plan's fingerprint (a statement root's
    ``plan_fp``) is the one the parent's rule set gives."""
    from benchmark import traffic
    from trino_tpu.planner.plan import plan_tree_str
    from trino_tpu.sql.parser import parse_statement

    template = traffic.load_template(name)
    stmt = parse_statement(traffic.instantiate(
        template, template.meta["validation"]).sql)
    pushed, parent = tiny_runners
    root = pushed.plan_statement(stmt)
    fingerprint = pushed._make_local_planner().plan(root).fingerprint()
    parents_rules()
    root_parent = parent.plan_statement(stmt)
    assert _firings(root) == 0 and not _filtering_joins(root)
    assert plan_tree_str(root) == plan_tree_str(root_parent)
    assert root.optimizer_trace == root_parent.optimizer_trace
    assert fingerprint == parent._make_local_planner().plan(
        root_parent).fingerprint()


def _memory_tiny_runner():
    """A runner over ``tiny`` in the Memory connector (row counts, no
    distinct counts), with no history from an earlier test."""
    from trino_tpu.connectors.memory import MemoryConnector
    from trino_tpu.telemetry import stats_store

    stats_store.store().clear()
    runner = LocalQueryRunner(
        {"tpch": TpchConnector(page_rows=2048),
         "memory": MemoryConnector("memory", schemas=["tiny"])},
        Session(catalog="memory", schema="tiny"))
    for table in ("customer", "orders", "lineitem", "nation"):
        runner.execute(f"create table {table} as "
                       f"select * from tpch.tiny.{table}")
    return runner


def _builds(res):
    return [s["attrs"]["input_rows"] for s in res.stats["trace"]
            if s["name"] == "HashBuilderOperator"]


def _q18(quantity):
    from benchmark import traffic

    return traffic.instantiate(traffic.load_template("q18"),
                               {"QUANTITY": quantity}).sql


_SLOT_IN_A_NESTED_BUILD = (
    "select o_orderpriority, count(*), sum(t.cnt) from orders o "
    "join (select c_custkey, count(n_nationkey) as cnt from customer c "
    "{kind} join (select n_nationkey from nation "
    "where n_regionkey = {v}) n on c.c_nationkey = n.n_nationkey "
    "group by c_custkey) t on o.o_custkey = t.c_custkey "
    "join lineitem l on l.l_orderkey = o.o_orderkey "
    "group by o_orderpriority order by o_orderpriority")

#: statement texts by binding: the region's only slot lies under a
#: join's build input inside one of its relations
SLOT_UNDER_A_BUILD = {
    "q18_semi_join": _q18,
    "nested_left_join": lambda v: _SLOT_IN_A_NESTED_BUILD.format(
        kind="left", v=v),
    "nested_inner_join": lambda v: _SLOT_IN_A_NESTED_BUILD.format(
        kind="inner", v=v),
}


@pytest.mark.parametrize("shape", sorted(SLOT_UNDER_A_BUILD))
def test_template_region_with_its_slot_under_a_build_uses_history(shape):
    """A plan template's region prices from connector estimates alone
    when a relation holds a ``ParamRef`` on its probe side. A slot
    under a join's build input inside a relation (q18's HAVING under
    the semi join's filtering source, a literal under a nested left or
    inner join's right input) is in a build pipeline wherever the
    relation lands, so the template built at the second statement is
    ordered by what the first one read: ``lineitem`` probes, and is not
    the 60 k-row build the Memory connector's estimates make it. The
    invariant under it: such a template is never batched (a parameter
    in an aux pipeline is refused), so no lane shares a build with
    another binding — and each binding's rows are what the statement
    gives without a template."""
    from trino_tpu.cache import PlanTemplate
    from trino_tpu.exec.batched import BatchIneligible, vmappable_stages
    from trino_tpu.expr.compiler import param_raw
    from trino_tpu.planner.optimizer import template_param_slots
    from trino_tpu.planner.rules import _probe_side_param_slots

    sql = SLOT_UNDER_A_BUILD[shape]
    bindings = (250, 275, 260) if shape == "q18_semi_join" else (1, 2, 3)
    runner = _memory_tiny_runner()
    lineitem = runner.execute("select count(*) from lineitem").rows[0][0]
    results = [runner.execute(sql(v)) for v in bindings]
    assert [r.stats.get("plan_template") for r in results] \
        == [None, "hit", "hit"]
    assert max(_builds(results[0])) == lineitem     # connector estimates
    assert max(_builds(results[1])) < lineitem
    assert max(_builds(results[2])) < lineitem
    [template] = [t for t in runner.query_cache.templates._entries.values()
                  if isinstance(t, PlanTemplate)]
    dropped = set(template_param_slots(template.root)) \
        - _probe_side_param_slots(template.root)
    assert dropped == {0}
    local = runner._make_local_planner(params={
        0: param_raw(template.param_types[0], bindings[0])})
    try:
        with pytest.raises(BatchIneligible):
            vmappable_stages(local.plan(template.root))
    finally:
        local.memory_pool.close()
    runner.execute("set session plan_template_enabled = false")
    for v, res in zip(bindings, results):
        assert res.rows == runner.execute(sql(v)).rows


def test_template_ordered_by_one_binding_serves_one_that_keeps_most_rows():
    """The history that orders q18's template is one literal binding's
    (QUANTITY 250 leaves 68 of ``tiny``'s 15,000 orders). A binding
    under which the semi join keeps most of ``orders`` (50: 11,403) runs
    the same plan: its answer is exact, and its largest build is what
    the semi join kept — never more than ``orders``, which is the build
    the parent's plan sorts for every binding."""
    runner = _memory_tiny_runner()
    orders = runner.execute("select count(*) from orders").rows[0][0]
    kept = runner.execute(
        "select count(*) from (select l_orderkey from lineitem "
        "group by l_orderkey having sum(l_quantity) > 50)").rows[0][0]
    assert orders // 2 < kept < orders
    results = [runner.execute(_q18(q)) for q in (250, 275, 50, 260)]
    fps = [[s for s in r.stats["trace"] if s["name"] == "statement"][0]
           ["attrs"]["plan_fp"] for r in results]
    assert fps[1] == fps[2] == fps[3] != fps[0]
    assert max(_builds(results[2])) == kept
    assert max(_builds(results[3])) < 100   # and history is not poisoned
    runner.execute("set session plan_template_enabled = false")
    assert results[2].rows == runner.execute(_q18(50)).rows
    assert len(results[2].rows) == 100
