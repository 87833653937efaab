"""Single-launch batched execution (round 16).

The contract under test: a same-shape admission burst rides ONE plan
template whose literals are opaque ``ParamRef`` slots, executes every
vmappable pipeline stage as ONE device launch for the whole batch, and
demuxes per-statement results that are BYTE-EQUAL to the serial path —
with per-tenant ACL and the result cache enforced per member exactly as
serial execution would.  The fallback taxonomy must be loud (counted by
reason, never silently wrong), and a repeat burst must perform ZERO new
jit traces with exactly one launch per vmapped stage, profiler-counted
independent of the batch depth B.
"""

import numpy as np
import pytest

from trino_tpu import jit_stats
from trino_tpu import types as T
from trino_tpu.block import Block, Page
from trino_tpu.connectors.memory import MemoryConnector
from trino_tpu.expr.ir import Literal, ParamRef, param_indices
from trino_tpu.ops.output import OutputBuffer
from trino_tpu.runner import LocalQueryRunner, QueryResult
from trino_tpu.security import (AccessDeniedError, RuleBasedAccessControl,
                                TableRule)
from trino_tpu.sql.analyzer import Session


def _mem_runner(**kwargs):
    return LocalQueryRunner({"memory": MemoryConnector()},
                            Session(catalog="memory", schema="default"),
                            **kwargs)


@pytest.fixture()
def runner():
    r = _mem_runner()
    r.execute("create table t (k bigint, v bigint)")
    r.execute("insert into t values (1, 10), (2, 20), (3, 30), "
              "(4, 40), (5, 50), (6, 60), (7, 70), (8, 80)")
    return r


BURST = ["select v from t where k = %d" % i for i in range(1, 9)]
EXPECT = [[(10 * i,)] for i in range(1, 9)]


# -- IR opacity -----------------------------------------------------------


def test_paramref_is_not_a_literal():
    """The whole template design rests on this: every plan-time
    constant reader is ``isinstance(_, Literal)``-gated, so ParamRef is
    opaque BY CONSTRUCTION, not by auditing each reader."""
    p = ParamRef(T.BIGINT, 0)
    assert not isinstance(p, Literal)
    assert param_indices(p) == {0}
    from trino_tpu.expr.ir import Call
    expr = Call("add", T.BIGINT, (ParamRef(T.BIGINT, 1),
                                  Literal(T.BIGINT, 5)))
    assert param_indices(expr) == {1}


# -- serial template reuse ------------------------------------------------


def test_serial_template_reuse_across_literals(runner):
    """Second-and-later uses of a shape ride the template: same root,
    different literal bindings, correct per-literal rows."""
    r1 = runner.execute("select v from t where k = 1")
    r2 = runner.execute("select v from t where k = 2")
    r3 = runner.execute("select v from t where k = 3")
    assert (r1.rows, r2.rows, r3.rows) == ([(10,)], [(20,)], [(30,)])
    # first use misses (below min_shape_uses), later ones hit
    assert r1.stats.get("plan_template") is None
    assert r2.stats.get("plan_template") == "hit"
    assert r3.stats.get("plan_template") == "hit"
    tc = runner.query_cache.templates
    assert tc.builds == 1 and tc.hits >= 1
    assert not tc.fallbacks


def test_template_disabled_by_session_property(runner):
    runner.execute("set session plan_template_enabled = false")
    for i in (1, 2, 3):
        res = runner.execute("select v from t where k = %d" % i)
        assert res.stats.get("plan_template") is None
    assert runner.query_cache.templates.builds == 0


# -- batched execution: byte-equality matrix ------------------------------


def test_batch_matches_serial_oracle(runner):
    serial = [runner.execute(s).rows for s in BURST]
    fresh = _mem_runner()
    fresh.execute("create table t (k bigint, v bigint)")
    fresh.execute("insert into t values (1, 10), (2, 20), (3, 30), "
                  "(4, 40), (5, 50), (6, 60), (7, 70), (8, 80)")
    out = fresh.execute_batch(BURST)
    assert [o.rows for o in out] == serial == EXPECT
    assert all(o.stats.get("plan_template") == "hit" for o in out)
    assert fresh.query_cache.batched_launches == 8


def test_batch_mixed_literals_and_duplicates(runner):
    """Identical literal vectors coalesce to one lane; results still
    demux to every submitter positionally."""
    sqls = [BURST[0], BURST[3], BURST[0], BURST[5], BURST[3]]
    out = runner.execute_batch(sqls)
    assert [o.rows for o in out] == [EXPECT[0], EXPECT[3], EXPECT[0],
                                     EXPECT[5], EXPECT[3]]


def test_batch_failing_member_demuxes_positionally(runner):
    """A statement that fails analysis fails ONLY its own slot; the
    healthy same-shape members still batch."""
    sqls = [BURST[0], "select nope from t where k = 2", BURST[2]]
    out = runner.execute_batch(sqls)
    assert out[0].rows == EXPECT[0]
    assert isinstance(out[1], Exception)
    assert out[2].rows == EXPECT[2]


def test_batch_mixed_shapes_grouped(runner):
    """Two interleaved shapes each batch within their own group."""
    sqls = [BURST[0], "select k from t where v = 20", BURST[2],
            "select k from t where v = 40", BURST[4],
            "select k from t where v = 60"]
    out = runner.execute_batch(sqls)
    assert [o.rows for o in out] == [EXPECT[0], [(2,)], EXPECT[2],
                                     [(4,)], EXPECT[4], [(6,)]]


def test_batch_mixed_tenants_acl_enforced_per_member():
    """Per-tenant ACL is enforced per STATEMENT: the denied tenant's
    member fails with AccessDenied, everyone else's lanes execute."""
    acl = RuleBasedAccessControl([
        TableRule(user="alice", privileges=["SELECT"]),
    ])
    r = LocalQueryRunner({"memory": MemoryConnector()},
                         Session(catalog="memory", schema="default"),
                         access_control=acl)
    # seed as alice (the only user with write-side privileges absent;
    # memory DDL goes through create/insert checks — use ALLOW_ALL
    # runner to seed, sharing the connector)
    seed = LocalQueryRunner(r.metadata.connectors,
                            Session(catalog="memory", schema="default"))
    seed.execute("create table t (k bigint, v bigint)")
    seed.execute("insert into t values (1, 10), (2, 20), (3, 30)")
    out = r.execute_batch(["select v from t where k = 1",
                           "select v from t where k = 2"], user="alice")
    assert [o.rows for o in out] == [[(10,)], [(20,)]]
    out2 = r.execute_batch(["select v from t where k = 1",
                            "select v from t where k = 2"], user="mallory")
    # execute_batch itself raises for a user denied query execution?
    # RuleBasedAccessControl only gates tables here, so both members
    # fail the per-member table check positionally
    assert all(isinstance(o, AccessDeniedError) for o in out2)


def test_batch_result_cache_hit_short_circuits_lane(runner):
    """A member whose full key hits the result cache is served WITHOUT
    occupying a vmap lane — and stores from batched lanes feed later
    serial hits byte-equally."""
    runner.execute("set session result_cache_enabled = true")
    runner.execute(BURST[0])                      # seed result cache
    before = runner.query_cache.batched_launches
    out = runner.execute_batch([BURST[0], BURST[1], BURST[2]])
    assert [o.rows for o in out] == EXPECT[:3]
    assert out[0].stats.get("result_cache") == "hit"
    assert runner.query_cache.result_shortcircuits == 1
    # only the two cache-missing members occupied lanes (padded to 2)
    assert runner.query_cache.batched_launches - before == 2
    # lane-computed results landed in the result cache for serial reuse
    assert runner.execute(BURST[1]).stats.get("result_cache") == "hit"


def test_batch_zero_traces_and_single_launch_per_stage(runner):
    """THE acceptance witness: a repeat same-shape burst of 8 performs
    ZERO new jit traces and each vmapped stage runs as exactly ONE
    device launch, profiler-counted independent of B."""
    from trino_tpu.telemetry import profiler as prof

    assert [o.rows for o in runner.execute_batch(BURST)] == EXPECT
    prof.reset()
    before = jit_stats.counts()
    with prof.profiling(True):
        out = runner.execute_batch(BURST)
        snap = prof.snapshot()
    after = jit_stats.counts()
    assert [o.rows for o in out] == EXPECT
    assert after == before, "repeat burst must not trace anything new"
    batched = [e for e in snap if e["name"] == "page_processor_batched"]
    assert batched, "burst did not ride the vmapped entry"
    assert all(e["calls"] == 1 for e in batched), \
        [(e["key"], e["calls"]) for e in batched]
    # nothing fell back to per-statement serial launches
    assert not any(e["name"] == "page_processor" and e["calls"] > 0
                   for e in snap)


def test_batch_depth_chunking(runner):
    """Bursts beyond batched_execution_max_depth chunk; every chunk
    demuxes correctly."""
    runner.execute("set session batched_execution_max_depth = 4")
    out = runner.execute_batch(BURST)
    assert [o.rows for o in out] == EXPECT
    depths = {o.stats.get("batched_depth") for o in out}
    assert depths == {4}


def test_batch_depth_padding_power_of_two(runner):
    """B=3 pads to the 4-lane bucket (bounded jit cache size), and the
    padding lane's rows are discarded."""
    out = runner.execute_batch(BURST[:3])
    assert [o.rows for o in out] == EXPECT[:3]
    assert {o.stats.get("batched_depth") for o in out} == {4}


# -- fallback taxonomy ----------------------------------------------------


def test_fallback_string_param(runner):
    runner.execute("create table s (name varchar, v bigint)")
    runner.execute("insert into s values ('a', 1), ('b', 2)")
    sqls = ["select v from s where name = 'a'",
            "select v from s where name = 'b'"]
    out = runner.execute_batch(sqls)
    assert [o.rows for o in out] == [[(1,)], [(2,)]]
    assert runner.query_cache.templates.fallbacks.get("string_param")


def test_fallback_ordinal_param(runner):
    """GROUP BY 1 ordinals are extracted as literals — the silent
    value-dependence hazard the pre-walk guard catches BEFORE any
    planning: templating the ordinal would re-aim the grouping key."""
    sqls = ["select k, count(*) from t where v > %d group by 1" % i
            for i in (5, 25)]
    out = runner.execute_batch(sqls)
    assert sorted(out[0].rows) == [(i, 1) for i in range(1, 9)]
    assert sorted(out[1].rows) == [(i, 1) for i in range(3, 9)]
    assert runner.query_cache.templates.fallbacks.get("ordinal_param")


def test_fallback_value_dependent(runner):
    """A literal the compiled path NEEDS as a python value — the lag()
    window offset shifts by a trace-time constant — fails the trial
    plan and falls back loudly at template build, never silently."""
    sqls = ["select lag(v, %d) over (order by k) from t" % i
            for i in (1, 2)]
    out = runner.execute_batch(sqls)
    assert out[0].rows[:3] == [(None,), (10,), (20,)]
    assert out[1].rows[:3] == [(None,), (None,), (10,)]
    fb = runner.query_cache.templates.fallbacks
    assert fb.get("value_dependent"), fb


def test_fallback_plan_shape_not_vmappable(runner):
    """A stage the masked pipeline genuinely cannot vmap (ORDER BY's
    sort) still answers correctly — through the serial path — and
    counts the round-17 taxonomy reason, not a catch-all."""
    sqls = ["select v from t where k > %d order by v" % i for i in (5, 6)]
    out = runner.execute_batch(sqls)
    assert [o.rows for o in out] == [[(60,), (70,), (80,)],
                                     [(70,), (80,)]]
    fb = runner.query_cache.templates.fallbacks
    assert fb.get("unsupported_stage") == 1, fb
    assert runner.query_cache.batched_launches == 0


def test_global_aggregation_now_vmaps(runner):
    """The round-16 fallback case — count(*) over a filtered scan — is
    a masked vmapped lane as of round 17: no fallback, one batched
    launch per member, byte-equal demux."""
    sqls = ["select count(*) from t where k > %d" % i for i in (1, 2)]
    out = runner.execute_batch(sqls)
    assert [o.rows for o in out] == [[(7,)], [(6,)]]
    assert not runner.query_cache.templates.fallbacks
    assert runner.query_cache.templates.dispositions.get(
        "agg_stage_vmapped") == 1
    assert runner.query_cache.batched_launches == 2


def test_nondeterministic_and_writes_never_batch(runner):
    out = runner.execute_batch(
        ["insert into t values (100, 1000)",
         "insert into t values (100, 1000)"])
    assert all(not isinstance(o, Exception) for o in out)
    # both INSERTs ran (no coalescing, no template)
    assert runner.execute("select count(*) from t where k = 100"
                          ).rows == [(2,)]
    assert runner.query_cache.batched_launches == 0


def test_batched_execution_disabled_property(runner):
    runner.execute("set session batched_execution_enabled = false")
    out = runner.execute_batch(BURST)
    assert [o.rows for o in out] == EXPECT
    assert runner.query_cache.batched_launches == 0


# -- metrics surface ------------------------------------------------------


def test_template_counters_scrapeable(runner):
    runner.execute_batch(BURST)
    c = runner.query_cache.counters()
    for key in ("template_hits", "template_misses", "template_builds",
                "template_fallbacks", "template_entries",
                "batched_launches", "result_shortcircuits"):
        assert key in c, key
    assert c["template_builds"] >= 1
    assert c["batched_launches"] >= 8
    fams = runner.metrics_families()
    names = {f["name"] for f in fams}
    assert "trino_plan_template_total" in names
    assert "trino_plan_template_entries" in names


# -- host hot-partition lanes (carried follow-on) -------------------------


def _page(v, rows=1):
    a = np.full(rows, v, dtype=np.int64)
    return Page([Block(T.BIGINT, a, None, None)], rows)


class TestOutputBufferHotLanes:
    def test_split_scales_capacity_and_full_needs_all_lanes(self):
        buf = OutputBuffer(4, max_pending_pages=2)
        buf.enqueue(1, _page(1))
        buf.enqueue(1, _page(2))
        assert buf.full([1])
        assert buf.split_partition(1, 4)
        assert not buf.full([1]), "extra lanes must add slack"
        for i in range(3, 11):
            buf.enqueue(1, _page(i))
        assert buf.full([1]), "full only when EVERY lane is at bound"

    def test_drain_preserves_rows_across_lanes(self):
        buf = OutputBuffer(2, max_pending_pages=4)
        buf.split_partition(0, 3)
        vals = list(range(10))
        for v in vals:
            buf.enqueue(0, _page(v))
        buf.set_no_more_pages()
        got = []
        while buf.has_page(0):
            p = buf.poll(0)
            got.append(int(np.asarray(p.block(0).data)[0]))
        assert buf.at_end(0)
        assert sorted(got) == vals
        assert buf.poll(0) is None

    def test_barrier_pages_snapshot_sees_all_lanes(self):
        buf = OutputBuffer(2)
        buf.split_partition(1, 2)
        for v in range(5):
            buf.enqueue(1, _page(v))
        assert len(buf.pages(1)) == 5
        assert buf.pages(0) == []

    def test_stats_parity_with_device_exchange(self):
        buf = OutputBuffer(4, max_pending_pages=2)
        buf.split_partition(2, 4)
        buf.enqueue(2, _page(7, rows=3))
        s = buf.stats
        assert s["hot_partitions"] == [2]
        assert s["splits"] == 1 and s["split_ways"] == 4
        assert s["hot_spread"] == {2: 4}
        assert s["partition_rows"][2] == 3

    def test_broadcast_and_merge_never_split(self):
        assert not OutputBuffer(2, broadcast=True).split_partition(0, 4)
        # merge-kind: the producer gate — hash-only callers request
        # splits; a merge operator never calls split_partition
        from trino_tpu.ops.output import PartitionedOutputOperator
        buf = OutputBuffer(2, max_pending_pages=2)
        op = PartitionedOutputOperator([T.BIGINT], [0], buf,
                                       kind="merge",
                                       hot_split_threshold=0.1)
        assert buf._hot_lanes == {}

    def test_hash_producer_splits_hot_partition(self):
        """One dominant key drives >threshold of rows -> its partition
        grows lanes automatically."""
        from trino_tpu.block import DevicePage
        from trino_tpu.ops.output import PartitionedOutputOperator

        buf = OutputBuffer(4, max_pending_pages=8)
        op = PartitionedOutputOperator([T.BIGINT, T.BIGINT], [0], buf,
                                       kind="hash",
                                       hot_split_threshold=0.5)
        keys = np.zeros(64, dtype=np.int64)       # all rows, one key
        vals = np.arange(64, dtype=np.int64)
        page = Page([Block(T.BIGINT, keys, None, None),
                     Block(T.BIGINT, vals, None, None)], 64)
        op.add_input(DevicePage.from_page(page))
        assert len(buf._hot_lanes) == 1
        (hot_p, ways), = buf._hot_lanes.items()
        assert ways == 4
        assert buf.stats["hot_partitions"] == [hot_p]
        # every row still lands in the hot partition's lanes
        total = sum(p.num_rows for p in buf.pages(hot_p))
        assert total == 64

    def test_unbounded_buffer_never_splits(self):
        from trino_tpu.block import DevicePage
        from trino_tpu.ops.output import PartitionedOutputOperator

        buf = OutputBuffer(4)    # barrier mode: no pending bound
        op = PartitionedOutputOperator([T.BIGINT], [0], buf,
                                       kind="hash",
                                       hot_split_threshold=0.5)
        keys = np.zeros(16, dtype=np.int64)
        page = Page([Block(T.BIGINT, keys, None, None)], 16)
        op.add_input(DevicePage.from_page(page))
        assert buf._hot_lanes == {}


# -- optimizer opacity ----------------------------------------------------


def test_optimizer_template_param_slots(runner):
    """The optimized template root reports its surviving ParamRef
    slots; a non-template plan reports none."""
    from trino_tpu.planner.optimizer import template_param_slots

    for i in (1, 2):
        runner.execute("select v from t where k = %d" % i)
    tc = runner.query_cache.templates
    (tmpl,) = [v for v in tc._entries.values()
               if not isinstance(v, str)]
    assert template_param_slots(tmpl.root) == (0,)
    plain = runner.plan_statement(
        runner.query_cache.parse("select v from t where k = 1",
                                 runner.session).stmt, hbo=None)
    assert template_param_slots(plain) == ()
    assert any(name == "PlanTemplate"
               for name, _ in tmpl.root.optimizer_trace)


# -- round 17: masked aggregation & join lanes ----------------------------


def _star_runner(nfact=64, nhot=0, **kwargs):
    """Star shape the batched join targets: a big param-filtered fact
    (probe) against a small param-free dim (build — the cost-based join
    order keeps the smaller side on the build)."""
    r = _mem_runner(**kwargs)
    r.execute("create table f (k bigint, v bigint)")
    r.execute("create table d (k bigint, w bigint)")
    r.execute("insert into f values "
              + ", ".join("(%d, %d)" % (i % 4, i) for i in range(nfact)))
    drows = ["(0, %d)" % i for i in range(nhot)] \
        + ["(%d, %d)" % (k, k * 10) for k in (0, 1, 2, 3)]
    r.execute("insert into d values " + ", ".join(drows))
    return r


def _rows(res):
    return sorted(res.rows, key=repr)


AGG_BURST = ["select k, count(*) c, sum(v) s from f where v > %d group by k"
             % (i * 7) for i in range(8)]


def test_batch_group_by_byte_equal_and_counted():
    serial = _star_runner()
    oracle = [_rows(serial.execute(s)) for s in AGG_BURST]
    r = _star_runner()
    out = r.execute_batch(AGG_BURST)
    assert [_rows(o) for o in out] == oracle
    assert r.query_cache.templates.dispositions.get(
        "agg_stage_vmapped") == 1
    assert not r.query_cache.templates.fallbacks
    assert r.query_cache.batched_launches == 8
    assert r.query_cache.batched_spills == 0


def test_batch_agg_zero_new_traces_on_repeat():
    """Repeat aggregating burst: ZERO new jit traces — the masked agg
    kernels are cached by shape config, never by operator identity."""
    r = _star_runner()
    first = r.execute_batch(AGG_BURST)
    before = jit_stats.counts()
    again = r.execute_batch(AGG_BURST)
    assert jit_stats.counts() == before, \
        "repeat agg burst must not trace anything new"
    assert [_rows(o) for o in again] == [_rows(o) for o in first]


def test_batch_agg_null_group_keys():
    """NULL group keys form their own group in every lane, byte-equal
    to serial (the mask must not conflate invalid rows with NULLs)."""
    serial, r = _star_runner(), _star_runner()
    for q in (serial, r):
        q.execute("insert into f values (null, 3), (null, 100), "
                  "(null, 200)")
    oracle = [_rows(serial.execute(s)) for s in AGG_BURST]
    out = r.execute_batch(AGG_BURST)
    assert [_rows(o) for o in out] == oracle


def test_batch_agg_all_rows_masked_empty_lane():
    """A member whose filter keeps ZERO rows yields an empty GROUP BY
    result from its all-masked lane while sibling lanes aggregate."""
    burst = ["select k, count(*) c from f where v > %d group by k" % x
             for x in (10, 10 ** 6, 20)]
    serial = _star_runner()
    oracle = [_rows(serial.execute(s)) for s in burst]
    assert oracle[1] == []
    r = _star_runner()
    out = r.execute_batch(burst)
    assert [_rows(o) for o in out] == oracle
    assert r.query_cache.templates.dispositions.get(
        "agg_stage_vmapped") == 1


@pytest.mark.parametrize("sql", [
    "select f.v, d.w from f join d on f.k = d.k where f.v > %d",
    "select f.v, d.w from f left join d on f.k = d.k where f.v > %d",
    "select v from f where k in (select k from d) and v > %d",
    "select v from f where k not in (select k from d) and v > %d",
], ids=["inner", "left", "semi", "anti"])
def test_batch_join_matrix_byte_equal(sql):
    burst = [sql % (i * 11) for i in range(8)]
    serial = _star_runner()
    # anti needs probe keys missing from the dim to produce rows
    oracle_extra = "insert into f values (7, 1), (8, 2), (9, 500)"
    serial.execute(oracle_extra)
    oracle = [_rows(serial.execute(s)) for s in burst]
    r = _star_runner()
    r.execute(oracle_extra)
    out = r.execute_batch(burst)
    assert [_rows(o) for o in out] == oracle
    assert r.query_cache.templates.dispositions.get(
        "join_stage_vmapped") == 1
    assert not r.query_cache.templates.fallbacks


def test_batch_join_then_group_by_one_pipeline():
    """Join AND aggregation in the same pipeline both vmap: the probe
    feeds masked expanded pages straight into the masked agg barrier."""
    burst = ["select f.k, count(*) c, sum(d.w) s from f join d "
             "on f.k = d.k where f.v > %d group by f.k" % (i * 17)
             for i in range(8)]
    serial = _star_runner()
    oracle = [_rows(serial.execute(s)) for s in burst]
    r = _star_runner()
    out = r.execute_batch(burst)
    assert [_rows(o) for o in out] == oracle
    disp = r.query_cache.templates.dispositions
    assert disp.get("join_stage_vmapped") == 1
    assert disp.get("agg_stage_vmapped") == 1
    assert not r.query_cache.templates.fallbacks


def test_batch_lane_overflow_falls_back_alone():
    """One member probes a hot build key hard enough to overflow the
    unified expansion capacity: THAT lane alone replays serially
    (counted ``lane_overflow``); sibling lanes keep their vmapped
    results, all byte-equal."""
    burst = ["select count(*) from f join d on f.k = d.k where f.v < %d"
             % x for x in (16, 32, 16, 900)]
    serial = _star_runner(1024, nhot=64)
    oracle = [_rows(serial.execute(s)) for s in burst]
    r = _star_runner(1024, nhot=64)
    r.execute("set session join_max_expand_lanes = 1024")
    out = r.execute_batch(burst)
    assert [_rows(o) for o in out] == oracle
    assert r.query_cache.batched_spills == 1
    assert r.query_cache.templates.fallbacks.get("lane_overflow") == 1
    # the two x=16 members coalesced; of the 3 lanes, 2 stayed vmapped
    assert r.query_cache.batched_launches == 2


def test_kernel_sizing_history_stabilizes_capacity():
    """The history behind the unified lane capacities above (and the
    hybrid join's fan-out): a repeat shape re-lands on one compiled
    program."""
    from trino_tpu.ops.kernel_sizing import ShapeSizingHistory

    h = ShapeSizingHistory()
    key = ("test", "shape")
    assert h.suggest(key, 1000) == 1024
    # fast-up: a larger need grows immediately
    assert h.suggest(key, 5000) == 8192
    # slow-down: a shrunken need keeps the remembered bucket (EWMA)
    assert h.suggest(key, 900) >= 2048
    # the need is a floor even on a cold key
    assert h.suggest(("other",), 17) == 32
    # repeated small needs eventually decay the remembered level
    for _ in range(12):
        got = h.suggest(key, 900)
    assert got == 1024


def test_batch_agg_failing_member_demuxes_positionally():
    r = _star_runner()
    sqls = [AGG_BURST[0], "select nope from f group by k", AGG_BURST[2]]
    out = r.execute_batch(sqls)
    assert not isinstance(out[0], Exception)
    assert isinstance(out[1], Exception)
    assert not isinstance(out[2], Exception)
    serial = _star_runner()
    assert _rows(out[0]) == _rows(serial.execute(AGG_BURST[0]))
    assert _rows(out[2]) == _rows(serial.execute(AGG_BURST[2]))


def test_batch_agg_denied_member_demuxes_positionally():
    """A member denied by ACL fails ONLY its own slot; the aggregating
    siblings still ride the vmapped lane."""
    acl = RuleBasedAccessControl([
        TableRule(user="alice", table="f|d", privileges=["SELECT"]),
    ])
    seed = _star_runner()
    seed.execute("create table secret (k bigint)")
    seed.execute("insert into secret values (1)")
    r = LocalQueryRunner(seed.metadata.connectors,
                         Session(catalog="memory", schema="default"),
                         access_control=acl)
    out = r.execute_batch(
        [AGG_BURST[0], "select k from secret", AGG_BURST[2]],
        user="alice")
    assert not isinstance(out[0], Exception)
    assert isinstance(out[1], AccessDeniedError)
    assert not isinstance(out[2], Exception)


def test_batched_burst_records_hbo_actuals():
    """Satellite 1: batched lanes feed HBO again — per-lane actuals are
    EXACT mask popcounts (padded lanes excluded), recorded per member
    under the shared statement fingerprint."""
    from trino_tpu.telemetry import stats_store

    stats_store.store().clear()
    try:
        r = _star_runner()
        out = r.execute_batch(AGG_BURST[:4])
        assert all(not isinstance(o, Exception) for o in out)
        c = stats_store.store().counters()
        assert c["records"] == 4, c
        snap = stats_store.store().snapshot()
        names = {e["name"] for e in snap}
        assert "TableScanOperator" in names
        assert "HashAggregationOperator" in names
        assert all(e["rows"] >= 0 for e in snap)
    finally:
        stats_store.store().clear()


def test_disposition_taxonomy_and_legacy_alias():
    """Satellite 2: dispositions say what actually ran; the retired
    ``non_fp_stage`` key stays scrapeable one release as an alias of
    ``unsupported_stage``."""
    r = _star_runner()
    r.execute_batch(AGG_BURST)
    r.execute_batch(["select v from f where k > %d order by v" % i
                     for i in (1, 2)])
    disp = r.query_cache.templates.dispositions
    assert disp.get("agg_stage_vmapped") == 1
    fb = r.query_cache.templates.fallbacks
    assert fb.get("unsupported_stage") == 1
    fams = {f["name"]: f for f in r.metrics_families()}
    tmpl = fams["trino_plan_template_total"]
    by_label = {tuple(sorted(labels.items())): value
                for labels, value in tmpl["samples"]}
    legacy = by_label.get((("outcome", "fallback:non_fp_stage"),))
    assert legacy == 1, by_label
    assert by_label.get((("outcome", "fallback:unsupported_stage"),)) \
        == 1


# -- round 17: distributed template-seed coherence ------------------------


def test_template_seed_roundtrip_and_bounds():
    from trino_tpu.cache import TemplateSeedStore

    src = TemplateSeedStore()
    for i in range(40):
        src.note("fp%d" % i, i + 1)
    src.note_fallback_shape("bad", "value_dependent")
    seed = src.export_seed(max_shapes=8)
    assert len(seed["shapes"]) == 8
    hot = {fp for fp, _, _ in seed["shapes"][:7]}
    assert hot <= {"fp%d" % i for i in range(32, 40)}
    dst = TemplateSeedStore()
    assert dst.import_seed(seed) == 8
    assert dst.uses("fp39") == 40


def test_template_seed_max_merge_and_first_verdict_wins():
    """Use totals max-merge (a worker that observed MORE uses must not
    regress); a locally proven fallback verdict is never overwritten by
    a remote one."""
    from trino_tpu.cache import TemplateSeedStore

    dst = TemplateSeedStore()
    dst.note("s", 10)
    dst.note_fallback_shape("s", "string_param")
    src = TemplateSeedStore()
    src.note("s", 3)
    src.note_fallback_shape("s", "value_dependent")
    src.note("other", 7)
    assert dst.import_seed(src.export_seed()) == 1   # only "other" news
    assert dst.uses("s") == 10
    assert dst.fallback_reason("s") == "string_param"
    assert dst.uses("other") == 7


def test_template_seed_malformed_warns_and_imports_nothing():
    from trino_tpu.cache import TemplateSeedStore

    dst = TemplateSeedStore()
    with pytest.warns(RuntimeWarning, match="template seed"):
        assert dst.import_seed({"shapes": [["fp"]]}) == 0
    assert dst.corrupt_loads == 1
    assert dst.uses("fp") == 0


def test_seeded_runner_rides_template_on_first_statement():
    """THE coherence contract: a fresh (replacement) runner whose seed
    store carries an earned shape builds AND rides the template on its
    very FIRST statement — no local re-earn of min_shape_uses."""
    from trino_tpu.cache import template_seeds
    from trino_tpu.telemetry import stats_store
    from trino_tpu.telemetry.stats_store import statement_fingerprint

    # without this, the HBO statement hint could admit the build on its
    # own and mask a broken seed path
    stats_store.store().clear()
    probe = _mem_runner()
    probe.execute("create table t (k bigint, v bigint)")
    probe.execute("insert into t values (1, 10), (2, 20)")
    pq = probe.query_cache.parse("select v from t where k = 1",
                                 probe.session)
    template_seeds().note(statement_fingerprint(pq.shape), 50)

    r = _mem_runner()          # the "replacement worker"
    r.execute("create table t (k bigint, v bigint)")
    r.execute("insert into t values (1, 10), (2, 20)")
    res = r.execute("select v from t where k = 2")
    assert res.rows == [(20,)]
    assert res.stats.get("plan_template") == "hit"
    assert r.query_cache.templates.builds == 1


def test_seeded_fallback_skips_local_trial():
    """A cluster-proved value-dependent shape is negative-cached from
    the seed WITHOUT paying a local trial plan (builds stays 0)."""
    from trino_tpu.cache import template_seeds
    from trino_tpu.telemetry.stats_store import statement_fingerprint

    probe = _mem_runner()
    probe.execute("create table t (k bigint, v bigint)")
    probe.execute("insert into t values (1, 10), (2, 20)")
    pq = probe.query_cache.parse("select v from t where k = 1",
                                 probe.session)
    fp = statement_fingerprint(pq.shape)
    template_seeds().note(fp, 50)
    template_seeds().note_fallback_shape(fp, "value_dependent")

    r = _mem_runner()
    r.execute("create table t (k bigint, v bigint)")
    r.execute("insert into t values (1, 10), (2, 20)")
    res = r.execute("select v from t where k = 2")
    assert res.rows == [(20,)]
    assert r.query_cache.templates.builds == 0
    assert r.query_cache.templates.fallbacks.get("value_dependent") == 1


def test_template_seed_disabled_by_session_property():
    from trino_tpu.cache import template_seeds
    from trino_tpu.telemetry import stats_store
    from trino_tpu.telemetry.stats_store import statement_fingerprint

    # the HBO statement hint is its own first-use admission path (PR
    # 15); clear the process store so THIS test isolates the seed knob
    stats_store.store().clear()
    probe = _mem_runner()
    probe.execute("create table t (k bigint, v bigint)")
    probe.execute("insert into t values (1, 10)")
    pq = probe.query_cache.parse("select v from t where k = 1",
                                 probe.session)
    template_seeds().note(statement_fingerprint(pq.shape), 50)

    r = _mem_runner()
    r.execute("create table t (k bigint, v bigint)")
    r.execute("insert into t values (1, 10)")
    r.execute("set session plan_template_seed_enabled = false")
    res = r.execute("select v from t where k = 1")
    assert res.rows == [(10,)]
    # first use, seed ignored: below min_shape_uses, no build
    assert res.stats.get("plan_template") is None
    assert r.query_cache.templates.builds == 0


def test_worker_configure_imports_template_seed_over_rpc():
    """The real configure handler: a template_seed payload lands in the
    worker-process seed store and the response reports the count —
    mirroring the HBO seed transport."""
    import threading

    from trino_tpu.cache import template_seeds
    from trino_tpu.parallel.rpc import call
    from trino_tpu.parallel.worker import WorkerServer

    from trino_tpu.cache import TemplateSeedStore
    src = TemplateSeedStore()
    src.note("seeded-shape", 9)
    template_seeds().clear()
    server = WorkerServer(0)
    threading.Thread(target=server.serve_forever, daemon=True).start()
    try:
        resp = call(("127.0.0.1", server.port), {
            "op": "configure", "catalogs": {}, "properties": {},
            "template_seed": src.export_seed()})
        assert resp["ok"] and resp["template_seeded"] == 1
        # in-process server shares this process's store
        assert template_seeds().uses("seeded-shape") == 9
        # heartbeat path: a DELTA seed rides the ping the same way
        src.note("hotter-shape", 4)
        resp2 = call(("127.0.0.1", server.port), {
            "op": "ping", "template_seed": src.export_seed()})
        assert resp2["ok"] and resp2.get("template_seeded") == 1
        assert template_seeds().uses("hotter-shape") == 4
    finally:
        server.server.shutdown()
        template_seeds().clear()


@pytest.mark.slow
def test_process_runner_ships_template_seed_to_replacement_worker():
    """E2E over real worker subprocesses: after the coordinator earns a
    shape, a worker spawned NOW (the replacement path) receives the
    template seed at configure — and the heartbeat ships deltas to
    stale workers without re-sending an unchanged seed."""
    from trino_tpu.cache import template_seeds
    from trino_tpu.parallel.process_runner import ProcessQueryRunner

    catalogs = {"tpch": {"connector": "tpch", "page_rows": 4096}}
    runner = ProcessQueryRunner(
        catalogs, Session(catalog="tpch", schema="micro"),
        n_workers=2, desired_splits=4)
    new = None
    try:
        # initial workers spawned against an empty seed store
        assert all(w.template_seeded == 0 for w in runner.workers)
        template_seeds().note("earned-shape", 25)
        new = runner._spawn_worker_process(generation=1)
        assert new.template_seeded >= 1
        assert new.template_seed_version == template_seeds().version
        # the ORIGINAL workers are stale: one heartbeat catches them up
        stale = [w for w in runner.workers if w is not new]
        assert any(w.template_seed_version < template_seeds().version
                   for w in stale)
        runner.heartbeat()
        assert all(w.template_seed_version == template_seeds().version
                   for w in runner.workers)
        # steady state: a second heartbeat has no delta to ship
        v = template_seeds().version
        runner.heartbeat()
        assert all(w.template_seed_version == v for w in runner.workers)
    finally:
        if new is not None:
            new.proc.kill()
        runner.close()
        template_seeds().clear()
