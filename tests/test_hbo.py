"""History-based statistics (round 13): estimate-vs-actual attribution
that closes the loop into the cost model.

The heart is the acceptance loop: a repeated query whose CONNECTOR
estimate is wrong must demonstrably plan from recorded history on the
second run (EXPLAIN shows the observed rows with source=hbo), with
results byte-equal to the first run — and ``hbo_enabled=false`` must
restore exactly the pre-HBO engine (no store writes, plan-cache key
unchanged, zero extra jit traces).  Around it: fingerprint canonics
(literals out, children out), EWMA merge math, sidecar persistence +
corrupt-sidecar loudness, data_version invalidation both ways,
adaptive-verdict seeding, the progress fallback, and every
observability surface (plan_stats SQL, trino_hbo_* metrics, slow-query
worst-misestimate, EXPLAIN ANALYZE Q-error)."""

import json
import warnings

import pytest

from trino_tpu import jit_stats
from trino_tpu.connectors.memory import MemoryConnector
from trino_tpu.connectors.spi import ColumnStatistics, TableStatistics
from trino_tpu.runner import LocalQueryRunner
from trino_tpu.sql.analyzer import Session
from trino_tpu.sql.parser import parse_statement
from trino_tpu.telemetry import stats_store
from trino_tpu.telemetry.stats_store import (
    DEFAULT_EWMA_ALPHA, HboContext, NodeHistory, RuntimeStatsStore,
    merge_actuals, plan_node_fp, q_error, statement_fingerprint)


@pytest.fixture(autouse=True)
def _fresh_store():
    """Every test starts from an empty process-wide store (the global
    accumulates across the whole pytest process otherwise)."""
    stats_store.store().clear()
    yield
    stats_store.store().clear()


def _mem_runner(connector=None, **session_props):
    s = Session(catalog="memory", schema="default")
    s.properties.update(session_props)
    return LocalQueryRunner({"memory": connector or MemoryConnector()},
                            s)


# ---------------------------------------------------------------------------
# the lying connector: truthful data, wrong statistics


class _LyingMetadata:
    def __init__(self, inner, lies):
        self._inner = inner
        self._lies = lies

    def get_statistics(self, table):
        return self._lies.get((table.schema, table.table)) \
            or self._inner.get_statistics(table)

    def __getattr__(self, name):
        return getattr(self._inner, name)


class LyingMemoryConnector(MemoryConnector):
    """Real memory-connector data under fabricated statistics — the
    stale-metastore scenario HBO exists to survive."""

    def __init__(self, lies):
        super().__init__()
        self.lies = lies

    def metadata(self):
        return _LyingMetadata(super().metadata(), self.lies)


def _join_runner(**session_props):
    """fact(4 rows) join dim(3 rows), with stats claiming both are in
    the tens and hundreds of millions until history corrects them."""
    lies = {
        ("default", "dim"): TableStatistics(
            row_count=50_000_000.0,
            columns={"k": ColumnStatistics(distinct_count=10.0,
                                           min_value=0, max_value=99),
                     "name": ColumnStatistics(distinct_count=10.0)}),
        ("default", "fact"): TableStatistics(row_count=500_000_000.0),
    }
    r = _mem_runner(LyingMemoryConnector(lies), **session_props)
    r.execute("create table fact (fk bigint, amt bigint)")
    r.execute("create table dim (k bigint, name bigint)")
    r.execute("insert into fact values (1, 10), (2, 20), (3, 30), "
              "(1, 40)")
    r.execute("insert into dim values (1, 100), (2, 200), (3, 300)")
    return r


JOIN_SQL = ("select f.fk, d.name, f.amt from fact f "
            "join dim d on f.fk = d.k order by f.amt")


def _dim_estimate(explain_text: str):
    """What EXPLAIN says history knows of the build side: ``dim``'s
    scan line ends ``est~3 rows [source=hbo]`` once a run recorded its
    3 rows; the connector's 50,000,000 is never rendered."""
    (line,) = [ln for ln in explain_text.splitlines()
               if "TableScan memory.default.dim" in ln]
    _, est, tail = line.partition(" est~")
    return tail if est else None


# ---------------------------------------------------------------------------
# fingerprints


def test_statement_fingerprint_parameterizes_literals():
    from trino_tpu.cache import normalize_statement

    a = normalize_statement(parse_statement(
        "select v from t where k = 5"))[0]
    b = normalize_statement(parse_statement(
        "select v from t where k = 9"))[0]
    c = normalize_statement(parse_statement(
        "select v from t where k < 9"))[0]
    assert statement_fingerprint(a) == statement_fingerprint(b)
    assert statement_fingerprint(a) != statement_fingerprint(c)


def test_plan_node_fp_canonicalizes_literals_and_children():
    r = _mem_runner()
    r.execute("create table t (k bigint, v bigint)")
    r.execute("insert into t values (1, 10)")
    roots = [r.create_plan(f"select v from t where k = {lit}")
             for lit in (5, 9)]

    def by_type(root):
        out = {}

        def walk(n):
            out.setdefault(type(n).__name__, []).append(plan_node_fp(n))
            for s in n.sources:
                walk(s)

        walk(root)
        return out

    a, b = by_type(roots[0]), by_type(roots[1])
    # same shape, different literal vectors -> identical fingerprints
    # node for node (k=5's history must steer k=9's plan)
    assert a == b


def test_agg_step_canonicalization_single_shares_final():
    """Exchange planning splits single -> partial+final AFTER the
    optimizer ran: the single-step node the cost rules consult must
    share its fingerprint with the final node the executed operator
    records under, while partial keeps its own stream."""
    from trino_tpu.planner.plan import AggregationNode, ValuesNode
    from trino_tpu.planner.symbols import Symbol
    from trino_tpu import types as T

    src = ValuesNode([Symbol("k", T.BIGINT)], [])
    single = AggregationNode(src, [Symbol("k", T.BIGINT)], [], "single")
    final = AggregationNode(src, [Symbol("k", T.BIGINT)], [], "final")
    partial = AggregationNode(src, [Symbol("k", T.BIGINT)], [],
                              "partial")
    assert plan_node_fp(single) == plan_node_fp(final)
    assert plan_node_fp(single) != plan_node_fp(partial)


# ---------------------------------------------------------------------------
# store mechanics


def test_ewma_update_math():
    st = RuntimeStatsStore()
    a = DEFAULT_EWMA_ALPHA
    st.record_query("s1", "snap", [{"fp": "n1", "name": "Scan",
                                    "rows": 100.0}])
    h = st.lookup("s1", "n1", "snap")
    assert h.rows == 100.0 and h.runs == 1   # first run seeds exactly
    st.record_query("s1", "snap", [{"fp": "n1", "name": "Scan",
                                    "rows": 200.0}])
    h = st.lookup("s1", "n1", "snap")
    assert h.rows == pytest.approx((1 - a) * 100.0 + a * 200.0)
    assert h.runs == 2


def test_material_only_on_decision_nodes():
    st = RuntimeStatsStore()
    # non-decision node with a terrible estimate: not material
    assert st.record_query("s1", "snap", [
        {"fp": "n1", "name": "Filter", "rows": 1000.0,
         "est_rows": 1.0}]) is False
    # decision node (join input) with the same misestimate: material
    assert st.record_query("s2", "snap", [
        {"fp": "n2", "name": "Scan", "rows": 1000.0, "est_rows": 1.0,
         "decision": True}]) is True
    # converged history: recording the same value again is not material
    assert st.record_query("s2", "snap", [
        {"fp": "n2", "name": "Scan", "rows": 1000.0,
         "est_rows": 1000.0, "decision": True}]) is False


def test_data_version_invalidation_both_ways():
    st = RuntimeStatsStore()
    st.record_query("s1", "snapA", [{"fp": "n1", "name": "Scan",
                                     "rows": 10.0}])
    assert st.lookup("s1", "n1", "snapA").rows == 10.0
    # a moved snapshot drops the statement's history loudly
    assert st.lookup("s1", "n1", "snapB") is None
    assert st.invalidations == 1
    assert st.lookup("s1", "n1", "snapA") is None  # gone for good
    # re-recording under the new snapshot serves again...
    st.record_query("s1", "snapB", [{"fp": "n1", "name": "Scan",
                                     "rows": 20.0}])
    assert st.lookup("s1", "n1", "snapB").rows == 20.0
    # ...and recording under a THIRD snapshot discards the merge base
    # instead of blending across versions
    st.record_query("s1", "snapC", [{"fp": "n1", "name": "Scan",
                                     "rows": 99.0}])
    h = st.lookup("s1", "n1", "snapC")
    assert h.rows == 99.0 and h.runs == 1


def test_persistence_roundtrip(tmp_path):
    path = str(tmp_path / "hbo.json")
    st = RuntimeStatsStore()
    st.record_query("s1", "snapA",
                    [{"fp": "n1", "name": "Scan", "rows": 42.0,
                      "peak_bytes": 1024.0,
                      "adaptive": {"verdict": "passthrough"}}],
                    scan_rows=42.0, peak_bytes=2048.0)
    st.save(path)
    fresh = RuntimeStatsStore()
    assert fresh.load(path) is True
    h = fresh.lookup("s1", "n1", "snapA")
    assert h.rows == 42.0 and h.runs == 1
    assert h.adaptive == {"verdict": "passthrough"}
    hint = fresh.statement_hint("s1", "snapA")
    assert hint["scan_rows"] == 42.0 and hint["peak_bytes"] == 2048.0


def test_corrupt_sidecar_is_loud(tmp_path):
    path = tmp_path / "hbo.json"
    path.write_text("{this is not json")
    st = RuntimeStatsStore()
    with pytest.warns(RuntimeWarning, match="corrupt"):
        assert st.load(str(path)) is False
    assert st.corrupt_loads == 1
    assert st.counters()["statements"] == 0
    # structurally-valid JSON with the wrong schema is just as corrupt
    path.write_text(json.dumps({"something": "else"}))
    with pytest.warns(RuntimeWarning, match="corrupt"):
        assert st.load(str(path)) is False
    assert st.corrupt_loads == 2


def test_merge_actuals_sums_shards():
    merged = merge_actuals([
        [{"fp": "a", "name": "Scan", "rows": 10.0, "bytes": 0.0,
          "wall_ms": 1.0, "flops": 0.0, "peak_bytes": 0.0}],
        [{"fp": "a", "name": "Scan", "rows": 5.0, "bytes": 0.0,
          "wall_ms": 2.0, "flops": 0.0, "peak_bytes": 0.0,
          "adaptive": {"verdict": "aggregate"}}],
    ])
    assert len(merged) == 1
    assert merged[0]["rows"] == 15.0
    assert merged[0]["wall_ms"] == 3.0
    assert merged[0]["adaptive"] == {"verdict": "aggregate"}


def test_q_error():
    assert q_error(10, 1000) == 100.0
    assert q_error(1000, 10) == 100.0
    assert q_error(0, 0) == 1.0   # floored at one row


# ---------------------------------------------------------------------------
# the acceptance loop: a misestimated join is planned from history on
# re-run


def test_misestimated_join_plans_from_history_on_rerun():
    r = _join_runner()
    ex1 = r.explain(JOIN_SQL)
    assert _dim_estimate(ex1) is None        # the connector's lie stands
    res1 = r.execute(JOIN_SQL)
    assert res1.stats["hbo"]["material"] is True
    assert r.query_cache.plans.hbo_invalidations >= 1
    ex2 = r.explain(JOIN_SQL)
    # the loop closed: recorded build-side cardinality beat the lie
    assert _dim_estimate(ex2) == "3 rows [source=hbo]"
    res2 = r.execute(JOIN_SQL)
    assert res2.rows == res1.rows            # byte-equal re-plan
    # converged: the third run re-uses the re-planned cached plan
    res3 = r.execute(JOIN_SQL)
    assert res3.rows == res1.rows
    assert res3.stats.get("plan_cache") == "hit"


def test_hbo_disabled_restores_pre_hbo_behavior():
    r = _join_runner(hbo_enabled=False)
    store = stats_store.store()
    res1 = r.execute(JOIN_SQL)
    assert "hbo" not in (res1.stats or {})
    assert store.counters()["records"] == 0      # no store writes
    assert store.counters()["misses"] == 0       # not even consulted
    before = jit_stats.total()
    res2 = r.execute(JOIN_SQL)
    assert res2.rows == res1.rows
    # the plan-cache hit path is untouched: zero jit traces, no
    # hbo invalidation ever fired
    assert res2.stats.get("plan_cache") == "hit"
    assert jit_stats.total() == before
    assert r.query_cache.plans.hbo_invalidations == 0
    # and no estimate from history: the lie stands uncorrected
    assert _dim_estimate(r.explain(JOIN_SQL)) is None


def test_literal_sibling_shares_history():
    """A recorded run must steer every literal vector of the shape:
    ``amt >= 0``'s history plans ``amt >= 15`` too (the WHERE literal
    is parameterized out of the statement shape AND canonicalized out
    of the node fingerprints, pushed-down domain bounds included)."""
    r = _join_runner()
    tpl = ("select f.fk, d.name, f.amt from fact f "
           "join dim d on f.fk = d.k where f.amt >= {} order by f.amt")
    r.execute(tpl.format(0))
    assert _dim_estimate(r.explain(tpl.format(15))) \
        == "3 rows [source=hbo]"


# ---------------------------------------------------------------------------
# EXPLAIN ANALYZE + slow-query surfaces


def test_explain_analyze_renders_qerror_and_worst():
    r = _join_runner()
    r.execute(JOIN_SQL)
    out = "\n".join(row[0] for row in r.execute(
        "explain analyze " + JOIN_SQL).rows)
    assert "q=" in out
    assert "est " in out
    assert "Worst misestimate:" in out


def test_slow_query_log_carries_worst_misestimate():
    from trino_tpu.events import EventListener

    events = []

    class Listener(EventListener):
        def query_completed(self, e):
            events.append(e)

    r = _join_runner(slow_query_log_threshold=1e-9)
    r.event_manager.listeners.append(Listener())
    r.execute(JOIN_SQL)
    slow = [e for e in events
            if (e.stats or {}).get("slow_query")]
    assert slow, "no slow-query record fired"
    worst = slow[-1].stats["slow_query"]["worst_misestimate"]
    assert worst is not None
    assert worst["qerror"] >= 2.0
    assert worst["name"]
    # and system.runtime.queries renders it in the slow column
    rows = r.execute("select slow from system.runtime.queries "
                     "where slow is not null").rows
    assert any("misest=" in row[0] for row in rows)


# ---------------------------------------------------------------------------
# progress fallback, admission hint


class _StatlessMemory(MemoryConnector):
    """A connector that reports NO statistics at all (the progress
    fraction would stay 0 forever without the HBO fallback)."""

    def metadata(self):
        inner = super().metadata()

        class M:
            def get_statistics(self, table, _inner=inner):
                return TableStatistics()

            def __getattr__(self, name, _inner=inner):
                return getattr(_inner, name)

        return M()


def test_progress_falls_back_to_hbo_actuals():
    from trino_tpu.telemetry.progress import QueryProgress

    r = _mem_runner(_StatlessMemory())
    r.execute("create table t (k bigint)")
    r.execute("insert into t values (1), (2), (3)")
    sql = "select count(*) c from t"
    p1 = QueryProgress("q1")
    r.execute(sql, progress=p1)
    assert p1.total_rows == 0               # connector knows nothing
    assert p1.estimate_source == "connector"
    assert p1.fraction() == 1.0             # terminal anyway
    p2 = QueryProgress("q2")
    r.execute(sql, progress=p2)
    assert p2.total_rows == 3               # history filled the gap
    assert p2.estimate_source == "hbo"
    assert p2.to_dict()["estimate_source"] == "hbo"


def test_admission_hint_lowers_memory_charge():
    from trino_tpu.resource_groups import (ResourceGroupManager,
                                           ResourceGroupSpec)

    groups = ResourceGroupManager([ResourceGroupSpec("all")])
    r = _mem_runner()
    r.resource_groups = groups
    r.execute("create table t (k bigint)")
    r.execute("insert into t values (1), (2)")
    sql = "select sum(k) s from t"
    r.execute(sql)
    hinted = r._hbo_admission_bytes(sql)
    assert hinted is not None
    assert hinted >= 64 << 20               # floored
    assert hinted < 8 << 30                 # way under the default cap
    # second execution rides the hinted admission without error
    assert r.execute(sql).rows == [(3,)]


# ---------------------------------------------------------------------------
# adaptive partial aggregation seeding


def test_adaptive_seed_applies_and_reports_source():
    import numpy as np

    from trino_tpu import types as T
    from trino_tpu.ops.aggregation import HashAggregationOperator

    op = HashAggregationOperator(
        [T.BIGINT, T.BIGINT], [0], [], step="partial",
        adaptive_seed={"verdict": "passthrough"})
    assert op.passthrough and op._adaptive_decided
    assert "seeded by hbo" in op.metrics()["adaptive"]
    mask = [1, 0] * 8
    op2 = HashAggregationOperator(
        [T.BIGINT, T.BIGINT], [0], [], step="partial",
        adaptive_key_buckets=16,
        adaptive_seed={"verdict": "range-split", "pass_buckets": mask})
    assert op2._adaptive_decided and not op2.passthrough
    assert list(np.asarray(op2._pass_buckets).astype(int)) == mask
    assert op2.metrics()["adaptive_verdict"]["pass_buckets"] == mask
    # a re-tuned bucket knob must NOT misapply a stale mask
    op3 = HashAggregationOperator(
        [T.BIGINT, T.BIGINT], [0], [], step="partial",
        adaptive_key_buckets=8,
        adaptive_seed={"verdict": "range-split", "pass_buckets": mask})
    assert not op3._adaptive_decided
    op4 = HashAggregationOperator(
        [T.BIGINT, T.BIGINT], [0], [], step="partial",
        adaptive_seed={"verdict": "aggregate"})
    assert op4._adaptive_decided and not op4.passthrough
    assert op4.metrics()["adaptive_verdict"] == {"verdict": "aggregate"}


def test_adaptive_verdict_recorded_and_seeded_e2e():
    """A partial agg over mostly-unique keys decides pass-through;
    the verdict lands in history and the next run's operator starts
    decided (seeded by hbo), with identical results."""
    from trino_tpu.parallel.distributed import DistributedQueryRunner

    conn = MemoryConnector()
    s = Session(catalog="memory", schema="default")
    s.properties["adaptive_partial_aggregation_min_rows"] = 64
    r = DistributedQueryRunner({"memory": conn}, s, n_workers=2,
                               desired_splits=2)
    LocalQueryRunner({"memory": conn}, s).execute(
        "create table u (k bigint, v bigint)")
    LocalQueryRunner({"memory": conn}, s).execute(
        "insert into u values " + ", ".join(
            f"({i}, {i % 7})" for i in range(512)))
    sql = "select k, sum(v) s from u group by k order by k limit 5"
    res1 = r.execute(sql)
    # the partial-agg verdict was recorded under the statement shape
    snap = [e for e in stats_store.store().snapshot()
            if e.get("adaptive")]
    assert snap, "no adaptive verdict recorded"
    assert snap[0]["adaptive"]["verdict"] in ("passthrough",
                                              "range-split")
    res2 = r.execute(sql)
    assert res2.rows == res1.rows


# ---------------------------------------------------------------------------
# observability surfaces


def test_plan_stats_sql_catalog():
    r = _mem_runner()
    r.execute("create table t (k bigint)")
    r.execute("insert into t values (1), (2)")
    r.execute("select count(*) c from t")
    rows = r.execute(
        "select statement, node, name, runs, rows "
        "from system.runtime.plan_stats").rows
    assert rows
    names = {row[2] for row in rows}
    assert "TableScanOperator" in names
    assert all(row[3] >= 1 for row in rows)


def test_hbo_metric_families_and_prometheus_roundtrip():
    from trino_tpu.telemetry.metrics import (parse_prometheus,
                                             render_prometheus)

    r = _mem_runner()
    r.execute("create table t (k bigint)")
    r.execute("insert into t values (1)")
    r.execute("select count(*) c from t")
    fams = {f["name"]: f for f in r.metrics_families()}
    assert "trino_hbo_store_entries" in fams
    assert "trino_hbo_lookups_total" in fams
    assert "trino_hbo_qerror" in fams
    assert fams["trino_hbo_qerror"]["type"] == "histogram"
    text = render_prometheus(r.metrics_families())
    parsed = parse_prometheus(text)
    assert "trino_hbo_qerror_count" in parsed
    assert "trino_hbo_records_total" in parsed
    # misestimate histogram actually observed something
    assert sum(parsed["trino_hbo_qerror_count"].values()) >= 1


def test_store_bounded_lru():
    st = RuntimeStatsStore(max_statements=4)
    for i in range(10):
        st.record_query(f"s{i}", "snap", [{"fp": "n", "name": "X",
                                           "rows": 1.0}])
    assert st.counters()["statements"] == 4
    assert st.lookup("s9", "n", "snap") is not None
    assert st.lookup("s0", "n", "snap") is None


# ---------------------------------------------------------------------------
# distributed + sidecar e2e


def test_distributed_runner_records_and_reuses_history(tmp_path):
    from trino_tpu.parallel.distributed import DistributedQueryRunner

    conn = MemoryConnector()
    s = Session(catalog="memory", schema="default")
    local = LocalQueryRunner({"memory": conn}, s)
    local.execute("create table t (k bigint, v bigint)")
    local.execute("insert into t values (1, 10), (2, 20), (3, 30)")
    r = DistributedQueryRunner({"memory": conn}, s, n_workers=2,
                               desired_splits=2)
    sql = "select k, sum(v) s from t group by k order by k"
    res1 = r.execute(sql)
    assert res1.stats.get("hbo", {}).get("recorded", 0) > 0
    assert stats_store.store().counters()["records"] == 1
    res2 = r.execute(sql)
    assert res2.rows == res1.rows
    # EXPLAIN ANALYZE renders per-node q-errors from the same store
    out = "\n".join(row[0] for row in r.execute(
        "explain analyze " + sql).rows)
    assert "q=" in out


def test_process_runner_worker_actuals_piggyback():
    """The multi-process path: worker tasks tag operators, their
    actuals ride the task responses back, and the coordinator's store
    records the merged query — no extra RPC, byte-equal repeats."""
    from trino_tpu.parallel.process_runner import ProcessQueryRunner

    catalogs = {"tpch": {"connector": "tpch", "page_rows": 4096}}
    runner = ProcessQueryRunner(
        catalogs, Session(catalog="tpch", schema="micro"),
        n_workers=2, desired_splits=4)
    try:
        sql = ("select o_orderstatus, count(*) c from orders "
               "group by o_orderstatus order by o_orderstatus")
        res1 = runner.execute(sql)
        assert res1.stats.get("hbo", {}).get("recorded", 0) > 0
        c = stats_store.store().counters()
        assert c["records"] == 1
        # scan actuals arrived from WORKER processes (the coordinator
        # only runs the output stage, which has no table scans)
        snap = stats_store.store().snapshot()
        assert any(e["name"] == "TableScanOperator" and e["rows"] > 0
                   for e in snap), snap
        res2 = runner.execute(sql)
        assert res2.rows == res1.rows
    finally:
        runner.close()


def test_seed_export_import_roundtrip_and_bounds():
    """export_seed ships the MOST RECENT statements, bounded;
    import_seed folds them into an empty (worker) store losslessly."""
    src = RuntimeStatsStore()
    for i in range(40):
        src.record_query(f"s{i}", "snap",
                         [{"fp": "n", "name": "Scan", "rows": float(i)}])
    seed = src.export_seed(max_statements=8)
    assert len(seed["statements"]) == 8
    assert {s["fp"] for s in seed["statements"]} == \
        {f"s{i}" for i in range(32, 40)}   # recency, not insertion
    dst = RuntimeStatsStore()
    assert dst.import_seed(seed) == 8
    assert dst.counters()["statements"] == 8
    h = dst.lookup("s39", "n", "snap")
    assert h is not None and h.rows == 39.0


def test_seed_existing_statements_win():
    """A worker that already observed fresher actuals must not regress
    to the coordinator's shipped EWMA."""
    dst = RuntimeStatsStore()
    dst.record_query("s", "snap",
                     [{"fp": "n", "name": "Scan", "rows": 100.0}])
    src = RuntimeStatsStore()
    src.record_query("s", "snap",
                     [{"fp": "n", "name": "Scan", "rows": 5.0}])
    src.record_query("other", "snap",
                     [{"fp": "n", "name": "Scan", "rows": 7.0}])
    # the return value counts what was ACTUALLY imported: "s" already
    # exists (kept), only "other" lands
    assert dst.import_seed(src.export_seed()) == 1
    assert dst.lookup("s", "n", "snap").rows == 100.0   # kept
    assert dst.lookup("other", "n", "snap").rows == 7.0  # gained


def test_seed_malformed_warns_and_imports_nothing():
    dst = RuntimeStatsStore()
    with pytest.warns(RuntimeWarning, match="hbo seed"):
        ok = dst.import_seed({"statements": [{"fp": "x"}]})
    assert not ok
    assert dst.counters()["statements"] == 0
    assert dst.counters()["corrupt_loads"] == 1


def test_worker_configure_imports_seed_over_rpc():
    """The real configure handler: an hbo_seed payload lands in the
    worker-local store and the response reports the seeded count."""
    import threading

    from trino_tpu.parallel.rpc import call
    from trino_tpu.parallel.worker import WorkerServer

    src = RuntimeStatsStore()
    src.record_query("seeded-stmt", "snap",
                     [{"fp": "n", "name": "Scan", "rows": 3.0}])
    stats_store.store().clear()
    server = WorkerServer(0)
    threading.Thread(target=server.serve_forever, daemon=True).start()
    try:
        resp = call(("127.0.0.1", server.port), {
            "op": "configure", "catalogs": {},
            "properties": {}, "hbo_seed": src.export_seed()})
        assert resp["ok"] and resp["hbo_seeded"] == 1
        # in-process server shares this process's store: the seed is
        # visible right here
        assert stats_store.store().lookup("seeded-stmt", "n", "snap") \
            is not None
    finally:
        server.server.shutdown()
        stats_store.store().clear()


def test_process_runner_ships_seed_and_binding_to_workers():
    """E2E over real worker subprocesses: after the coordinator learns
    a statement's actuals, a newly spawned (replacement-shaped) worker
    receives the bounded history seed at configure — workers no longer
    plan from nothing."""
    from trino_tpu.parallel.process_runner import ProcessQueryRunner

    catalogs = {"tpch": {"connector": "tpch", "page_rows": 4096}}
    runner = ProcessQueryRunner(
        catalogs, Session(catalog="tpch", schema="micro"),
        n_workers=2, desired_splits=4)
    new = None
    try:
        # the initial workers spawned against an empty store
        assert all(w.hbo_seeded == 0 for w in runner.workers)
        sql = ("select o_orderstatus, count(*) c from orders "
               "group by o_orderstatus order by o_orderstatus")
        res1 = runner.execute(sql)
        assert res1.stats.get("hbo", {}).get("recorded", 0) > 0
        # a worker spawned NOW (the replacement path) gets the learned
        # history piggybacked on its configure
        new = runner._spawn_worker_process(generation=1)
        assert new.hbo_seeded >= 1
        # and the run_task binding carries the statement key workers
        # need to look that history up
        from trino_tpu.parallel.process_runner import _QueryCtx
        ctx = _QueryCtx(runner.session, "qtest")
        from trino_tpu.telemetry.stats_store import HboContext
        ctx.hbo = HboContext("fp", "snap", stats_store.store())
        assert runner._hbo_binding(ctx) == {"stmt_fp": "fp",
                                            "snap": "snap"}
        ctx.hbo = None
        assert runner._hbo_binding(ctx) is None
        res2 = runner.execute(sql)
        assert res2.rows == res1.rows
    finally:
        if new is not None:
            new.proc.kill()
        runner.close()


def test_sidecar_survives_process_restart_simulation(tmp_path):
    path = str(tmp_path / "hbo.json")
    r = _join_runner(hbo_store_path=path)
    res1 = r.execute(JOIN_SQL)
    # "restart": clear the process store, build a fresh runner over the
    # same catalog state; the sidecar restores the learned history
    stats_store.store().clear()
    r2 = _join_runner(hbo_store_path=path)
    with warnings.catch_warnings():
        warnings.simplefilter("error")      # a corrupt load would raise
        ex = r2.explain(JOIN_SQL)
    assert _dim_estimate(ex) == "3 rows [source=hbo]"
    assert r2.execute(JOIN_SQL).rows == res1.rows

# ---------------------------------------------------------------------------
# plan exploration: history drives join ORDER and exchange DISTRIBUTION


def _star_runner(**session_props):
    """fact(12) joining dim1(50) and dim2(2) where the connector's lies
    INVERT the dimension sizes: estimates say join dim1 first, recorded
    actuals say join dim2 first."""
    lies = {
        ("default", "f"): TableStatistics(row_count=500_000.0),
        ("default", "d1"): TableStatistics(row_count=2.0),
        ("default", "d2"): TableStatistics(row_count=50_000.0),
    }
    r = _mem_runner(LyingMemoryConnector(lies), **session_props)
    r.execute("create table f (k bigint, j bigint, v bigint)")
    r.execute("create table d1 (k bigint, a bigint)")
    r.execute("create table d2 (j bigint, b bigint)")
    r.execute("insert into f values " + ", ".join(
        f"({i % 3 + 1}, {i % 2 + 1}, {i * 10})" for i in range(12)))
    r.execute("insert into d1 values " + ", ".join(
        f"({i + 1}, {i * 100})" for i in range(50)))
    r.execute("insert into d2 values (1, 7), (2, 8)")
    return r


STAR_SQL = ("select f.k, f.j, f.v, d1.a, d2.b from f "
            "join d1 on f.k = d1.k join d2 on f.j = d2.j "
            "order by f.v")


def _reorder_detail(explain_text: str) -> str:
    for line in explain_text.splitlines():
        if "ReorderJoins" in line and "[" in line:
            return line
    return ""


def _scan_order(explain_text: str, *tables: str):
    pos = {t: explain_text.find(f"memory.default.{t}") for t in tables}
    assert all(p >= 0 for p in pos.values()), explain_text
    return sorted(tables, key=lambda t: pos[t])


def test_hbo_reorders_join_order_on_rerun():
    r = _star_runner()
    ex1 = r.explain(STAR_SQL)
    # estimates alone (d1 claims 2 rows): d1 joins first, no history tag
    assert "(hbo reordered)" not in ex1
    assert _scan_order(ex1, "d1", "d2") == ["d1", "d2"]
    res1 = r.execute(STAR_SQL)
    assert res1.stats["hbo"]["material"] is True
    ex2 = r.explain(STAR_SQL)
    # recorded cardinalities re-priced the DP: relations tagged [hbo],
    # and the chosen order CHANGED versus estimates alone — the
    # actually-2-row d2 now joins first
    d2 = _reorder_detail(ex2)
    assert "[hbo]" in d2
    assert "(hbo reordered)" in d2
    assert _scan_order(ex2, "f", "d1", "d2") != \
        _scan_order(ex1, "f", "d1", "d2")
    assert stats_store.store().plan_flips.get("join_order", 0) >= 1
    res2 = r.execute(STAR_SQL)
    assert res2.rows == res1.rows            # byte-equal flip
    sorted_by_oracle = sorted(res1.rows, key=lambda t: t[2])
    assert res1.rows == sorted_by_oracle


def test_reorder_gate_keeps_connector_order():
    r = _star_runner(hbo_reorder_joins_enabled=False)
    r.execute(STAR_SQL)
    ex = r.explain(STAR_SQL)
    assert "(hbo reordered)" not in ex
    assert _scan_order(ex, "d1", "d2") == ["d1", "d2"]
    assert stats_store.store().plan_flips.get("join_order", 0) == 0


def test_shared_calculator_memoizes_region_estimates(monkeypatch):
    """One optimize() run prices every (group, version) region ONCE:
    the per-run shared calculator + RuleContext region memo must make
    strictly fewer estimator calls than fresh per-application
    calculators (the pre-round-20 behavior) on a q3-shaped plan."""
    from trino_tpu.planner.memo import RuleContext
    from trino_tpu.planner.stats import StatsCalculator

    conn = MemoryConnector()
    seed = _mem_runner(conn)
    seed.execute("create table f (k bigint, j bigint, v bigint)")
    seed.execute("create table d1 (k bigint, a bigint)")
    seed.execute("create table d2 (j bigint, b bigint)")
    seed.execute("insert into f values (1, 1, 10), (2, 2, 20)")
    seed.execute("insert into d1 values (1, 100), (2, 200)")
    seed.execute("insert into d2 values (1, 7), (2, 8)")
    sql = ("select f.k, f.j, f.v, d1.a, d2.b from f "
           "join d1 on f.k = d1.k join d2 on f.j = d2.j")

    calls = {"n": 0}
    orig_stats = StatsCalculator.stats

    def counting(self, node):
        calls["n"] += 1
        return orig_stats(self, node)

    monkeypatch.setattr(StatsCalculator, "stats", counting)
    _mem_runner(conn).explain(sql)
    shared = calls["n"]

    # pre-shared-calculator behavior: no cross-rule region memo and a
    # fresh calculator per shared_stats() consult
    monkeypatch.setattr(RuleContext, "_region_key",
                        lambda self, leaf: None)

    def fresh(self):
        return StatsCalculator(self.metadata, history=self.hbo)

    monkeypatch.setattr(RuleContext, "shared_stats", fresh)
    calls["n"] = 0
    _mem_runner(conn).explain(sql)
    assert shared < calls["n"], \
        f"shared calculator made {shared} estimator calls, " \
        f"per-application calculators made {calls['n']}"


def _dist_pair(**session_props):
    """Distributed runner over a lying build side: the connector claims
    2 build rows (broadcast territory under threshold=50); the table
    actually has 200 (partitioned territory)."""
    from trino_tpu.parallel.distributed import DistributedQueryRunner

    lies = {
        ("default", "probe"): TableStatistics(row_count=100_000.0),
        ("default", "build"): TableStatistics(row_count=2.0),
    }
    conn = LyingMemoryConnector(lies)
    s = Session(catalog="memory", schema="default")
    # keep join ORDER pinned to connector estimates so the witness
    # isolates the distribution decision
    s.properties["hbo_reorder_joins_enabled"] = False
    s.properties.update(session_props)
    local = LocalQueryRunner({"memory": conn}, s)
    local.execute("create table probe (k bigint, v bigint)")
    local.execute("create table build (k bigint, w bigint)")
    local.execute("insert into probe values " + ", ".join(
        f"({i % 200 + 1}, {i})" for i in range(40)))
    local.execute("insert into build values " + ", ".join(
        f"({i + 1}, {i * 3})" for i in range(200)))
    r = DistributedQueryRunner({"memory": conn}, s, n_workers=2,
                               desired_splits=2, broadcast_threshold=50)
    return r


DIST_SQL = ("select probe.k, probe.v, build.w from probe "
            "join build on probe.k = build.k order by probe.v")


def test_distribution_flips_to_partitioned_on_rerun():
    r = _dist_pair()
    ex1 = r.explain(DIST_SQL)
    assert "distribution=broadcast [source=connector]" in ex1
    res1 = r.execute(DIST_SQL)
    # the 2-vs-200 build misestimate sits on a DISTRIBUTION decision
    # node: material, so the cached fragment plan is invalidated
    assert res1.stats["hbo"]["material"] is True
    assert r.plan_cache.hbo_invalidations >= 1
    ex2 = r.explain(DIST_SQL)
    assert "distribution=partitioned [source=hbo]" in ex2
    assert stats_store.store().plan_flips.get("distribution", 0) >= 1
    res2 = r.execute(DIST_SQL)
    assert res2.rows == res1.rows            # byte-equal flip
    # converged: the third run reuses the re-planned cached fragments
    res3 = r.execute(DIST_SQL)
    assert res3.rows == res1.rows
    assert res3.stats.get("plan_cache") == "hit"


def test_distribution_gate_keeps_connector_choice():
    r = _dist_pair(hbo_distribution_enabled=False)
    r.execute(DIST_SQL)
    ex = r.explain(DIST_SQL)
    # est~ annotations stay history-fed (a different, ungated surface);
    # the DISTRIBUTION decision itself must ignore the observed rows
    assert "distribution=broadcast [source=connector]" in ex
    assert "distribution=partitioned" not in ex
    assert "distribution=broadcast [source=hbo]" not in ex
    assert stats_store.store().plan_flips.get("distribution", 0) == 0


def test_plan_fp_says_only_what_is_executed():
    """History teaches the planner a group count (3, where the
    connector states 50,000,000 rows and no distinct count) and the
    fragments stay what they were: so does their fingerprint, the
    statement root's ``plan_fp``.  (Before PR 46 an aggregation carried
    a label no operator read, history flipped it ``exchange`` ->
    ``global-hash``, and the fingerprint moved with it.)"""
    import re

    from trino_tpu.parallel.distributed import DistributedQueryRunner
    from trino_tpu.planner.fragmenter import (fragments_fingerprint,
                                              fragments_str)

    conn = LyingMemoryConnector({("default", "sales"): TableStatistics(
        row_count=50_000_000.0)})
    s = Session(catalog="memory", schema="default")
    local = LocalQueryRunner({"memory": conn}, s)
    local.execute("create table sales (g bigint, v bigint)")
    local.execute("insert into sales values " + ", ".join(
        f"({i % 3}, {i})" for i in range(40)))
    r = DistributedQueryRunner({"memory": conn}, s, n_workers=2,
                               desired_splits=2)
    stmt = parse_statement(
        "select g, sum(v) from sales group by g order by g")

    def planned():
        fragments = r.create_fragments(stmt, hbo=r._hbo_context(stmt))
        return fragments_fingerprint(fragments), fragments_str(fragments)

    fp1, text1 = planned()
    assert "source=hbo" not in text1
    res = r.execute("select g, sum(v) from sales group by g order by g")
    assert res.rows == [(0, 273), (1, 247), (2, 260)]
    fp2, text2 = planned()
    assert "est~40 rows [source=hbo]" in text2   # history reached the plan
    assert re.sub(r" est~\d+ rows \[source=hbo\]", "", text2) == text1
    assert fp2 == fp1
    (root,) = [sp for sp in res.stats["trace"]
               if sp["name"] == "statement"]
    assert root["attrs"]["plan_fp"] == fp1


def test_spill_hint_refuses_broadcast():
    """A build that spilled on a prior run must not be replicated even
    when its observed cardinality is comfortably under the broadcast
    threshold."""
    from trino_tpu.parallel.distributed import DistributedQueryRunner

    conn = MemoryConnector()
    s = Session(catalog="memory", schema="default")
    s.properties["hbo_reorder_joins_enabled"] = False
    local = LocalQueryRunner({"memory": conn}, s)
    local.execute("create table probe (k bigint, v bigint)")
    local.execute("create table build (k bigint, w bigint)")
    local.execute("insert into probe values (1, 10), (2, 20), (3, 30)")
    local.execute("insert into build values (1, 7), (2, 8), (3, 9)")
    r = DistributedQueryRunner({"memory": conn}, s, n_workers=2,
                               desired_splits=2, broadcast_threshold=50)
    sql = ("select probe.k, probe.v, build.w from probe "
           "join build on probe.k = build.k order by probe.v")
    res1 = r.execute(sql)
    # 3 observed build rows < 50: still broadcast
    assert "distribution=broadcast" in r.explain(sql)
    # inject a spill record onto every recorded node of the statement
    # (the hybrid-join runtime does this for the build it spilled)
    store = stats_store.store()
    for stmt_fp, st in list(store._stmts.items()):
        store.record_query(stmt_fp, st["snap"], [
            {"fp": fp, "name": h.name, "rows": h.rows,
             "spill": {"fanout": 4, "fraction": 0.5}}
            for fp, h in st["nodes"].items()])
    ex = r.explain(sql)
    assert "distribution=partitioned [source=hbo]" in ex
    assert r.execute(sql).rows == res1.rows


def test_plan_flips_metric_family():
    store = stats_store.store()
    store.record_query("s", "snap", [{"fp": "n", "name": "X",
                                      "rows": 1.0}])
    store.note_plan_flip("join_order")
    store.note_plan_flip("distribution")
    store.note_plan_flip("distribution")
    fams = {f["name"]: f for f in store.families()}
    fam = fams["trino_hbo_plan_flips"]
    assert fam["type"] == "counter"
    by_kind = {tuple(sorted(l.items())): v for l, v in fam["samples"]}
    assert by_kind[(("kind", "join_order"),)] == 1
    assert by_kind[(("kind", "distribution"),)] == 2
    assert store.counters()["plan_flips"] == 3
