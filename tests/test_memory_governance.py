"""Cluster memory governance: pool hierarchy, disk spill tier, killer
policies, memory-aware retry sizing, resource-group memory limits.

Reference analogs: TestMemoryPools (node pool + per-query reservations),
TestFileSingleStreamSpiller (checksummed spill files),
TestTotalReservationOnBlockedNodesLowMemoryKiller (victim determinism),
TestPartitionMemoryEstimator (peak-driven retry budgets) and the
resource-group memory-limit tests.

Everything here is in-process (no worker spawns — the process-level
integration rides tests/test_chaos.py's module cluster).
"""

import os
import threading

import numpy as np
import pytest

from trino_tpu.connectors.tpch import TpchConnector
from trino_tpu.exec.memory import (DiskSpilledPage, NodeMemoryExceededError,
                                   NodeMemoryPool, QueryMemoryPool,
                                   SpilledPage, spill_pages)
from trino_tpu.exec.serde import (parse_spill_frame, read_spill_file,
                                  spill_frame, write_spill_file)
from trino_tpu.parallel.cluster_memory import (ClusterMemoryManager,
                                               MemoryEstimator,
                                               QueryKilledError, killer_for)
from trino_tpu.parallel.fault import (INSUFFICIENT_RESOURCES,
                                      DecayingFailureStats,
                                      classify_error_code)
from trino_tpu.resource_groups import (ResourceGroupManager,
                                       ResourceGroupSpec)
from trino_tpu.runner import LocalQueryRunner
from trino_tpu.sql.analyzer import Session
from trino_tpu.types import TrinoError

# An aggregation's partials are as wide as their groups, so parking them
# on the host compacts nothing: an aggregation spills AND completes under
# a cap only where groups repeat across many pages, so that the chunked
# merge reduces them. 200 parts over ~24 pages of 256 rows do
# (AGG_PAGE_ROWS connector rows a page, AGG_CAP bytes).
AGG_SQL = ("select l_partkey, sum(l_quantity) qty from lineitem "
           "group by l_partkey order by qty desc, l_partkey limit 10")
AGG_PAGE_ROWS = 64
AGG_CAP = 300_000
JOIN_SQL = ("select o_orderpriority, count(*) from orders o, lineitem l "
            "where o.o_orderkey = l.l_orderkey and l_quantity > 30 "
            "group by o_orderpriority order by o_orderpriority")
SORT_SQL = "select * from lineitem order by l_extendedprice, l_orderkey"


def make_runner(page_rows=1024, **props):
    session = Session(catalog="tpch", schema="micro")
    session.properties.update(props)
    return LocalQueryRunner({"tpch": TpchConnector(page_rows=page_rows)},
                            session, desired_splits=8)


@pytest.fixture(scope="module")
def baselines():
    r = make_runner()
    return {sql: r.execute(sql).rows
            for sql in (AGG_SQL, JOIN_SQL, SORT_SQL)}


# ------------------------------------------------- disk spill oracle ----


@pytest.mark.parametrize("sql,cap,page_rows,props", [
    (AGG_SQL, AGG_CAP, AGG_PAGE_ROWS, {}),
    # the join's build is what spills; HBO off as for the hybrid join
    # below, so a recorded run does not re-size it
    (JOIN_SQL, 60_000, 1024, {"hbo_enabled": False}),
    (SORT_SQL, 1_000_000, 1024, {})])
def test_disk_spill_oracle(sql, cap, page_rows, props, baselines):
    """agg / join / sort forced through the DISK tier
    (spill_host_memory_bytes=0 demotes every parked page) must return
    byte-equal rows to the unconstrained run — the acceptance bar for
    the spill subsystem."""
    r = make_runner(page_rows, query_max_memory_bytes=cap,
                    spill_enabled=True, spill_to_disk_enabled=True,
                    spill_host_memory_bytes=0, **props)
    res = r.execute(sql)
    mem = res.stats["memory"]
    assert mem["spill_events"] > 0
    assert mem["disk_spill_events"] > 0, mem
    assert mem["disk_spilled_bytes"] > 0
    if sql is SORT_SQL:
        # ties make exact order plan-dependent: compare multiset + keys
        assert sorted(res.rows) == sorted(baselines[sql])
    else:
        assert res.rows == baselines[sql]


def test_disk_spill_files_reaped_after_query():
    r = make_runner(AGG_PAGE_ROWS, query_max_memory_bytes=AGG_CAP,
                    spill_enabled=True, spill_to_disk_enabled=True,
                    spill_host_memory_bytes=0)
    res = r.execute(AGG_SQL)
    assert res.stats["memory"]["disk_spill_events"] > 0
    root = os.path.join("/tmp/trino_tpu_spill", str(os.getpid()))
    leftovers = []
    if os.path.isdir(root):
        for d in os.listdir(root):
            leftovers.extend(os.listdir(os.path.join(root, d)))
    assert leftovers == []


def test_host_tier_preferred_until_ledger_full(baselines):
    """With a roomy host budget the disk tier must stay cold — the
    tiers are ordered, not parallel."""
    r = make_runner(AGG_PAGE_ROWS, query_max_memory_bytes=AGG_CAP,
                    spill_enabled=True, spill_to_disk_enabled=True,
                    spill_host_memory_bytes=1 << 30)
    res = r.execute(AGG_SQL)
    mem = res.stats["memory"]
    assert mem["spill_events"] > 0
    assert mem["disk_spill_events"] == 0
    assert res.rows == baselines[AGG_SQL]


# ------------------------------------------------- spill frame serde ----


def _arrays():
    cols = [np.arange(64, dtype=np.int64),
            np.linspace(0, 1, 64).astype(np.float64)]
    nulls = [np.zeros(64, dtype=bool), (np.arange(64) % 7 == 0)]
    valid = np.arange(64) < 50
    return cols, nulls, valid


def test_spill_frame_roundtrip(tmp_path):
    cols, nulls, valid = _arrays()
    c2, n2, v2 = parse_spill_frame(spill_frame(cols, nulls, valid))
    for a, b in zip(cols + nulls + [valid], c2 + n2 + [v2]):
        assert a.dtype == b.dtype
        assert np.array_equal(a, b)
    path = str(tmp_path / "s.bin")
    write_spill_file(path, cols, nulls, valid)
    assert not os.path.exists(path + ".tmp")  # atomic: no temp residue
    c3, n3, v3 = read_spill_file(path)
    assert np.array_equal(c3[0], cols[0]) and np.array_equal(v3, valid)


def test_spill_file_streaming_read_write(tmp_path):
    """The streaming spill paths (chunked compressobj write + bounded
    incremental read) interoperate both ways with the one-shot frame
    forms, and a corrupted/torn FILE fails loudly on the streaming
    read — CRC verifies before any array is handed back."""
    from trino_tpu.exec.serde import _SPILL_CHUNK

    cols = [np.arange(_SPILL_CHUNK // 4 + 7, dtype=np.int64)]  # > chunk
    nulls = [np.zeros(len(cols[0]), dtype=bool)]
    valid = np.arange(len(cols[0])) < 50
    path = str(tmp_path / "big.bin")
    write_spill_file(path, cols, nulls, valid)
    # streaming write -> one-shot parse (format unchanged on disk)
    c1, n1, v1 = parse_spill_frame(open(path, "rb").read())
    assert np.array_equal(c1[0], cols[0])
    # streaming read
    c2, n2, v2 = read_spill_file(path)
    assert np.array_equal(c2[0], cols[0])
    assert np.array_equal(v2, valid)
    assert c2[0].flags.writeable
    # one-shot write -> streaming read
    with open(path, "wb") as f:
        f.write(spill_frame(cols, nulls, valid))
    c3, _, _ = read_spill_file(path)
    assert np.array_equal(c3[0], cols[0])
    # corruption: flipped body byte, then a torn tail
    blob = bytearray(open(path, "rb").read())
    blob[len(blob) // 2] ^= 0xFF
    with open(path, "wb") as f:
        f.write(blob)
    with pytest.raises(TrinoError):
        read_spill_file(path)
    with open(path, "wb") as f:
        f.write(spill_frame(cols, nulls, valid)[: len(blob) // 2])
    with pytest.raises(TrinoError):
        read_spill_file(path)


def test_spill_frame_detects_corruption(tmp_path):
    cols, nulls, valid = _arrays()
    frame = bytearray(spill_frame(cols, nulls, valid))
    frame[20] ^= 0xFF  # flip a body byte: CRC must catch it
    with pytest.raises(TrinoError):
        parse_spill_frame(bytes(frame))
    with pytest.raises(TrinoError):
        parse_spill_frame(frame[: len(frame) // 2])  # torn frame


def test_disk_spilled_page_roundtrip():
    import jax.numpy as jnp

    from trino_tpu import types as T
    from trino_tpu.block import DevicePage

    page = DevicePage([T.BIGINT], [jnp.arange(32, dtype=jnp.int64)],
                      [jnp.zeros(32, dtype=bool)],
                      jnp.arange(32) < 20, [None])
    pool = QueryMemoryPool(1 << 20, spill_enabled=True,
                           spill_to_disk=True, host_spill_limit=0)
    pages = [page]
    freed = spill_pages(pages, pool)
    assert freed > 0
    assert isinstance(pages[0], DiskSpilledPage)
    assert os.path.exists(pages[0].path)
    back = pages[0].to_device()
    assert np.array_equal(np.asarray(back.cols[0])[:20], np.arange(20))
    assert int(np.asarray(back.valid).sum()) == 20
    pool.close()


def _device_page(rows: int):
    import jax.numpy as jnp

    from trino_tpu import types as T
    from trino_tpu.block import DevicePage

    return DevicePage([T.BIGINT], [jnp.arange(rows, dtype=jnp.int64)],
                      [jnp.zeros(rows, dtype=bool)],
                      jnp.ones(rows, dtype=bool), [None])


def test_ledger_demotes_across_operator_lists():
    """Cross-operator-list demotion (PR 4 follow-on): when the spilling
    operator's own list cannot bring the node ledger under its limit,
    the LARGEST parked pages of OTHER tracked lists demote — the last
    spiller is rarely the biggest holder."""
    node = NodeMemoryPool(1 << 30, host_spill_limit=1 << 30)
    a = node.create_query_pool("qa", 1 << 30, spill_enabled=True,
                               spill_to_disk=True)
    b = node.create_query_pool("qb", 1 << 30, spill_enabled=True,
                               spill_to_disk=True)
    ca = a.create_context("a-agg")
    cb = b.create_context("b-join")
    # operator A parks BIG pages while the ledger has headroom
    a_pages = [_device_page(4096), _device_page(4096)]
    with ca.lock:
        spill_pages(a_pages, a, ca.lock)
    assert all(isinstance(p, SpilledPage) and
               not isinstance(p, DiskSpilledPage) for p in a_pages)
    # tighten the (shared, node-wide) limit, then operator B spills a
    # SMALL page: its own list can't cover the overage
    node.host_ledger.limit_bytes = 1024
    b_pages = [_device_page(32)]
    with cb.lock:
        spill_pages(b_pages, b, cb.lock)
    assert any(isinstance(p, DiskSpilledPage) for p in a_pages), \
        "demotion never reached the other operator's list"
    assert node.host_ledger.cross_list_demotions >= 1
    # A's disk pages reload transparently and carry A's spill files
    back = next(p for p in a_pages if isinstance(p, DiskSpilledPage))
    assert os.path.exists(back.path)
    assert int(np.asarray(back.to_device().valid).sum()) == 4096
    # closing A drops its lists from the ledger's candidates
    node.release_query("qa")
    assert not any(t[2] is a for t in node.host_ledger._tracked)
    node.release_query("qb")


def test_ledger_cross_list_skips_busy_foreign_locks():
    """A foreign operator actively holding its context lock is skipped
    (never blocked on): cooperative demotion must not deadlock two
    concurrently-spilling operators."""
    node = NodeMemoryPool(1 << 30, host_spill_limit=1 << 30)
    a = node.create_query_pool("qa", 1 << 30, spill_enabled=True,
                               spill_to_disk=True)
    b = node.create_query_pool("qb", 1 << 30, spill_enabled=True,
                               spill_to_disk=True)
    ca = a.create_context("a-op")
    cb = b.create_context("b-op")
    a_pages = [_device_page(4096)]
    with ca.lock:
        spill_pages(a_pages, a, ca.lock)
    node.host_ledger.limit_bytes = 64

    held = threading.Event()
    release = threading.Event()

    def hold_a():
        with ca.lock:
            held.set()
            release.wait(5)

    t = threading.Thread(target=hold_a)
    t.start()
    held.wait(5)
    b_pages = [_device_page(32)]
    with cb.lock:
        spill_pages(b_pages, b, cb.lock)  # must return, not deadlock
    assert not isinstance(a_pages[0], DiskSpilledPage)  # skipped
    release.set()
    t.join()
    node.release_query("qa")
    node.release_query("qb")


def test_default_node_memory_bytes_falls_back_on_cpu():
    from trino_tpu.exec.memory import default_node_memory_bytes

    # the CPU backend reports no memory stats -> documented fallback
    assert default_node_memory_bytes(fallback=123) in (123,) or \
        default_node_memory_bytes(fallback=123) > 1 << 28


# ------------------------------------------- node pool (cross-query) ----


def test_node_pool_cross_query_revoke_largest_first():
    node = NodeMemoryPool(1000)
    a = node.create_query_pool("qa", 1000, spill_enabled=True)
    b = node.create_query_pool("qb", 1000, spill_enabled=True)
    order = []
    ca = a.create_context("a-op")
    cb = b.create_context("b-op")
    ca.set_revoke_callback(lambda: order.append("qa") or 600)
    cb.set_revoke_callback(lambda: order.append("qb") or 300)
    ca.reserve(600)
    cb.reserve(300)
    assert node.reserved == 900
    # qc needs 500: node over budget -> revoke qa (largest) only
    c = node.create_query_pool("qc", 1000, spill_enabled=True)
    cc = c.create_context("c-op")
    cc.reserve(500)
    assert order == ["qa"]
    assert node.reserved == 300 + 500
    assert node.cross_query_revokes == 1


def test_node_pool_blocked_raises_insufficient_resources():
    node = NodeMemoryPool(1000)
    a = node.create_query_pool("qa", 1000, spill_enabled=False)
    a.create_context("x").reserve(900)
    b = node.create_query_pool("qb", 1000, spill_enabled=False)
    with pytest.raises(NodeMemoryExceededError) as exc:
        b.create_context("y").reserve(500)
    assert classify_error_code(exc.value.code) == INSUFFICIENT_RESOURCES
    assert node.blocked_events == 1
    assert node.snapshot()["blocked_events"] == 1
    # the failed reservation must not leak into either pool
    assert b.reserved == 0
    assert node.reserved == 900


def test_node_pool_snapshot_tracks_per_query_and_release():
    node = NodeMemoryPool(1 << 20)
    a = node.create_query_pool("qa", 1 << 20)
    a.create_context("x").reserve(1234)
    snap = node.snapshot()
    assert snap["queries"]["qa"]["reserved"] == 1234
    node.release_query("qa")
    assert node.reserved == 0
    # released peaks survive for the retry estimator
    assert node.snapshot()["queries"]["qa"]["peak"] == 1234


# ------------------------------------------------- killer policies ------


def _snap(worker_id, blocked, queries, max_bytes=1000):
    return {"max_bytes": max_bytes,
            "reserved_bytes": sum(q["reserved"] for q in queries.values()),
            "blocked_events": 1 if blocked else 0,
            "queries": queries}


def test_killer_blocked_nodes_policy_is_deterministic():
    mgr = ClusterMemoryManager("total-reservation-on-blocked-nodes")
    # node 0 blocked: qa holds 70 there; node 1 healthy: qb holds 900
    mgr.update(0, _snap(0, True, {"qa": {"reserved": 70, "peak": 70},
                                  "qb": {"reserved": 30, "peak": 30}}))
    mgr.update(1, _snap(1, False, {"qb": {"reserved": 900, "peak": 900}}))
    # blocked-nodes policy ignores qb's off-node bulk: qa dies
    assert mgr.maybe_kill() == "qa"
    with pytest.raises(QueryKilledError) as exc:
        mgr.check_killed("qa")
    assert exc.value.code == "EXCEEDED_CLUSTER_MEMORY"
    assert classify_error_code(exc.value.code) == INSUFFICIENT_RESOURCES
    # the flag was consumed: the retry attempt runs clean
    mgr.check_killed("qa")


def test_killer_total_reservation_policy():
    mgr = ClusterMemoryManager("total-reservation")
    mgr.update(0, _snap(0, True, {"qa": {"reserved": 70, "peak": 0},
                                  "qb": {"reserved": 30, "peak": 0}}))
    mgr.update(1, _snap(1, False, {"qb": {"reserved": 900, "peak": 0}}))
    assert mgr.maybe_kill() == "qb"  # cluster-wide largest


def test_killer_tie_breaks_lexicographically():
    mgr = ClusterMemoryManager("total-reservation-on-blocked-nodes")
    mgr.update(0, _snap(0, True, {"qz": {"reserved": 50, "peak": 0},
                                  "qa": {"reserved": 50, "peak": 0}}))
    assert mgr.maybe_kill() == "qa"


def test_killer_none_policy_and_no_blocked_nodes():
    mgr = ClusterMemoryManager("none")
    mgr.update(0, _snap(0, True, {"qa": {"reserved": 50, "peak": 0}}))
    assert mgr.maybe_kill() is None
    mgr2 = ClusterMemoryManager("total-reservation-on-blocked-nodes")
    mgr2.update(0, _snap(0, False, {"qa": {"reserved": 50, "peak": 0}}))
    assert mgr2.maybe_kill() is None
    with pytest.raises(TrinoError):
        killer_for("bogus")


def test_killer_fires_once_per_victim():
    """Worker snapshots keep naming a dying victim for a few
    heartbeats, and the victim popping its flag must not re-register:
    one pressure episode = one kill, one event."""
    mgr = ClusterMemoryManager("total-reservation-on-blocked-nodes")
    snap = _snap(0, True, {"qa": {"reserved": 70, "peak": 70}})
    mgr.update(0, snap)
    assert mgr.maybe_kill() == "qa"
    with pytest.raises(QueryKilledError):
        mgr.check_killed("qa")           # flag consumed
    mgr.update(0, snap)                  # stale heartbeat, still blocked
    assert mgr.maybe_kill() is None      # no duplicate kill
    assert mgr.kill_count == 1


def test_blocked_delta_survives_interleaved_heartbeats():
    """A heartbeat that stores a blocked delta without a governance
    tick must not lose the signal when the next (unblocked) heartbeat
    arrives: deltas accumulate until a kill consumes them."""
    mgr = ClusterMemoryManager("total-reservation-on-blocked-nodes")
    mgr.update(0, _snap(0, True, {"qa": {"reserved": 70, "peak": 0}}))
    # next ping: worker's delta already consumed -> blocked_events 0
    mgr.update(0, _snap(0, False, {"qa": {"reserved": 70, "peak": 0}}))
    assert mgr.maybe_kill() == "qa"


def test_blocked_signal_not_latched_past_a_no_victim_tick():
    """A pressure episode that resolves before governance runs (the
    blocking query failed and released) must not leave the node marked
    blocked: the tick that found no victim consumes the signal, so a
    later innocent query is not killed."""
    mgr = ClusterMemoryManager("total-reservation-on-blocked-nodes")
    mgr.update(0, _snap(0, True, {}))     # blocked, nothing killable
    assert mgr.maybe_kill() is None
    # innocent newcomer, no new blocked events
    mgr.update(0, _snap(0, False, {"qb": {"reserved": 50, "peak": 0}}))
    assert mgr.maybe_kill() is None
    assert mgr.kill_count == 0


def test_query_max_total_memory_cap_kills():
    mgr = ClusterMemoryManager("none", query_max_total_bytes=100)
    mgr.update(0, _snap(0, False, {"qa": {"reserved": 80, "peak": 0}}))
    mgr.update(1, _snap(1, False, {"qa": {"reserved": 60, "peak": 0}}))
    assert mgr.maybe_kill() == "qa"  # 140 > 100 across nodes
    stats = mgr.cluster_stats()
    assert stats["kills"] == 1 and stats["workers"] == 2


# --------------------------------------------- estimator + escalation ---


def test_memory_estimator_grows_from_observed_peak():
    est = MemoryEstimator()
    est.record_peak("q7a0", 500_000)
    est.record_peak("q7a0", 400_000)      # lower later peak: keep max
    assert est.peak_for("q7a0") == 500_000
    # 2x observed peak wins over the failed budget when peak is larger
    assert est.next_budget("q7a0", 120_000, 0) == 1_000_000
    # floor wins when both are tiny
    assert est.next_budget("q7a0", 120_000, 8 << 20) == 8 << 20
    # no observation: grow from the failed budget itself
    assert est.next_budget("q9a1", 300_000, 0) == 600_000


# --------------------------------------------- decaying failure stats ---


def test_decaying_failure_stats_halve_per_half_life():
    s = DecayingFailureStats(half_life_s=60.0)
    s.record(now=0.0)
    assert s.score(now=0.0) == pytest.approx(1.0)
    assert s.score(now=60.0) == pytest.approx(0.5, rel=1e-3)
    s.record(now=60.0)
    assert s.score(now=60.0) == pytest.approx(1.5, rel=1e-3)
    assert s.score(now=180.0) == pytest.approx(1.5 / 4, rel=1e-3)
    assert s.total == 2


def test_prefer_healthy_placement():
    from trino_tpu.parallel.process_runner import prefer_healthy

    class W:
        def __init__(self):
            self.failure_stats = DecayingFailureStats()

    good, bad = W(), W()
    bad.failure_stats.record()
    assert prefer_healthy([bad, good]) == [good]
    # nobody healthy: fall back to everyone rather than starve
    good.failure_stats.record()
    assert prefer_healthy([bad, good]) == [bad, good]


# --------------------------------------------- resource group limits ----


def test_resource_group_hard_memory_limit_blocks_admission():
    mgr = ResourceGroupManager([ResourceGroupSpec(
        "g", max_concurrency=10, hard_memory_limit_bytes=1000)])
    g = mgr.select("alice")
    g.acquire(memory_bytes=700)
    admitted = threading.Event()

    def second():
        g.acquire(timeout=5, memory_bytes=700)  # 1400 > 1000: waits
        admitted.set()

    t = threading.Thread(target=second, daemon=True)
    t.start()
    assert not admitted.wait(0.2)
    g.release(memory_bytes=700)    # frees headroom -> second admits
    assert admitted.wait(5)
    g.release(memory_bytes=700)


def test_resource_group_soft_memory_limit_stops_new_admissions():
    mgr = ResourceGroupManager([ResourceGroupSpec(
        "g", max_concurrency=10, soft_memory_limit_bytes=500)])
    g = mgr.select("alice")
    g.acquire(memory_bytes=600)    # first query may overshoot the soft cap
    admitted = threading.Event()

    def second():
        g.acquire(timeout=5, memory_bytes=10)
        admitted.set()

    threading.Thread(target=second, daemon=True).start()
    assert not admitted.wait(0.2)  # soft-exceeded: no NEW admissions
    g.release(memory_bytes=600)
    assert admitted.wait(5)
    g.release(memory_bytes=10)


def test_resource_group_rejects_unsatisfiable_budget():
    """A budget above the hard limit can never fit: reject loudly
    instead of queueing forever."""
    mgr = ResourceGroupManager([ResourceGroupSpec(
        "g", hard_memory_limit_bytes=1000)])
    g = mgr.select("alice")
    with pytest.raises(TrinoError) as exc:
        g.acquire(timeout=1, memory_bytes=2000)
    assert exc.value.code == "QUERY_REJECTED"
    assert g.running == 0 and g.memory_reserved == 0


def test_resource_group_memory_limits_from_config():
    mgr = ResourceGroupManager.from_config({"groups": [
        {"name": "g", "soft_memory_limit_bytes": 123,
         "hard_memory_limit_bytes": 456}]})
    spec = mgr.select("anyone").spec
    assert spec.soft_memory_limit_bytes == 123
    assert spec.hard_memory_limit_bytes == 456


# --------------------------------------------- surfaces ----------------


def test_session_properties_registered():
    from trino_tpu import session_properties as SP

    for name in ("query_max_total_memory", "spill_to_disk_enabled",
                 "memory_killer_policy", "retry_initial_memory",
                 "node_max_memory_bytes", "spill_host_memory_bytes",
                 "scan_coalesce_enabled"):
        assert name in SP.REGISTRY, name
    props = {}
    SP.set_property(props, "memory_killer_policy", "TOTAL-RESERVATION")
    assert props["memory_killer_policy"] == "total-reservation"
    with pytest.raises(TrinoError):
        SP.set_property(props, "memory_killer_policy", "nuke-everything")


def test_protocol_stats_carry_recovery_and_cluster_memory():
    from trino_tpu.runner import QueryResult
    from trino_tpu.server.protocol import ProtocolServer
    from trino_tpu import types as T

    class Stub:
        def execute(self, sql):
            return QueryResult(["x"], [T.BIGINT], [(1,)], stats={
                "memory": {"peak_bytes": 7},
                "recovery": {"task_attempts": 3},
                "cluster_memory": {"workers": 2, "kills": 1},
            })

    srv = ProtocolServer(Stub()).start()
    try:
        import json
        import urllib.request

        doc = json.loads(urllib.request.urlopen(urllib.request.Request(
            f"{srv.uri}/v1/statement", data=b"select 1",
            method="POST")).read())
        for _ in range(100):
            if "data" in doc or "error" in doc:
                break
            doc = json.loads(
                urllib.request.urlopen(doc["nextUri"]).read())
        assert doc["stats"]["recovery"]["task_attempts"] == 3
        assert doc["stats"]["clusterMemory"]["kills"] == 1
        assert doc["stats"]["memory"]["peak_bytes"] == 7
    finally:
        srv.stop()


def test_scan_coalesce_upload_batches():
    """Split-fragmented small pages coalesce to the connector page size
    before upload: one device batch instead of eight."""
    from trino_tpu.ops.operator import TableScanOperator

    conn = TpchConnector(page_rows=512)
    meta = conn.metadata()
    table = meta.get_table_handle("micro", "lineitem")
    cols = meta.get_columns(table)
    counts, totals = {}, {}
    for coalesce in (None, 1 << 16):
        scan = TableScanOperator(conn, cols, coalesce_rows=coalesce)
        for s in conn.split_manager().get_splits(table, 8):
            scan.add_split(s)
        scan.no_more_splits()
        pages = []
        while True:
            p = scan.get_output()
            if p is None and scan.is_finished():
                break
            if p is not None:
                pages.append(p)
        counts[coalesce] = len(pages)
        totals[coalesce] = sum(int(np.asarray(p.valid).sum())
                               for p in pages)
    assert totals[None] == totals[1 << 16]  # never changes row counts
    assert counts[None] > 1
    assert counts[1 << 16] == 1


def test_local_explain_analyze_shows_disk_spill():
    r = make_runner(AGG_PAGE_ROWS, query_max_memory_bytes=AGG_CAP,
                    spill_enabled=True, spill_to_disk_enabled=True,
                    spill_host_memory_bytes=0)
    res = r.execute("explain analyze " + AGG_SQL)
    text = "\n".join(row[0] for row in res.rows)
    assert "disk" in text and "spills" in text


# ------------------------------------------------- hybrid hash join ----

#: no aggregation above the join: the hybrid acceptance bar is about
#: the JOIN surviving a pool far smaller than its build, not about
#: the agg's own spill behaviour (and ORDER BY pins row order — cold
#: partitions emit after the resident stream)
HYBRID_SQL = ("select o_orderkey, o_orderpriority, l_quantity "
              "from orders o, lineitem l "
              "where o.o_orderkey = l.l_orderkey and l_quantity > 45 "
              "order by o_orderkey, l_quantity limit 50")


@pytest.fixture(scope="module")
def hybrid_baseline():
    return make_runner(hbo_enabled=False).execute(HYBRID_SQL).rows


def test_hybrid_join_over_pool_completes_without_retry(hybrid_baseline):
    """The tentpole acceptance bar: a join whose build + probe
    transients exceed the pool several times over completes in ONE
    attempt — partition demotions (partition_spills > 0) instead of a
    MemoryExceededError/retry — and returns byte-equal rows."""
    r = make_runner(query_max_memory_bytes=60_000, spill_enabled=True,
                    spill_to_disk_enabled=True, spill_host_memory_bytes=0,
                    hbo_enabled=False)
    res = r.execute(HYBRID_SQL)
    mem = res.stats["memory"]
    assert mem["partition_spills"] > 0, mem
    assert mem["partition_spilled_bytes"] > 0
    assert mem["peak_bytes"] <= 60_000
    assert res.rows == hybrid_baseline


def test_hybrid_disabled_property_restores_wholesale_spill(
        hybrid_baseline):
    """hybrid_join_enabled=false falls back to the wholesale park-
    everything path: still byte-equal, zero partition demotions."""
    r = make_runner(query_max_memory_bytes=150_000, spill_enabled=True,
                    spill_to_disk_enabled=True, spill_host_memory_bytes=0,
                    hbo_enabled=False, hybrid_join_enabled=False)
    res = r.execute(HYBRID_SQL)
    assert res.stats["memory"]["partition_spills"] == 0
    assert res.rows == hybrid_baseline


def test_hybrid_second_run_sizes_fanout_from_hbo(hybrid_baseline):
    """First constrained run records its spill record into the HBO
    store; the SECOND run's builder sizes fan-out from it
    (source=hbo) before any revocation pressure."""
    from trino_tpu.ops import join as J

    states = []
    orig = J.HashBuilderOperator._init_partitions

    def spy(self):
        orig(self)
        states.append(self._hstate)

    J.HashBuilderOperator._init_partitions = spy
    try:
        r = make_runner(query_max_memory_bytes=60_000,
                        spill_enabled=True, spill_to_disk_enabled=True,
                        spill_host_memory_bytes=0)
        res1 = r.execute(HYBRID_SQL)
        assert res1.rows == hybrid_baseline
        assert states and states[-1].source == "local"
        first_fanout = states[-1].fanout
        res2 = r.execute(HYBRID_SQL)
        assert res2.rows == hybrid_baseline
        assert states[-1].source == "hbo", \
            "second run did not consume the HBO spill record"
        assert states[-1].fanout >= first_fanout
    finally:
        J.HashBuilderOperator._init_partitions = orig


def _skewed_join_page(fanout: int, heavy_pid_rows: int,
                      light_pid_rows: int):
    """One bigint key page whose rows are HEAVILY skewed onto a single
    partition of ``fanout`` (returns the page and the heavy pid)."""
    import jax.numpy as jnp

    from trino_tpu import types as T
    from trino_tpu.block import DevicePage
    from trino_tpu.ops import join as J

    hs = J.HybridJoinState(fanout)
    keys = np.arange(16384, dtype=np.int64)
    pids = hs.partition_ids([keys], [np.zeros(keys.size, bool)],
                            [T.BIGINT], [None])
    heavy = int(np.bincount(pids, minlength=fanout).argmax())
    picked = [keys[pids == heavy][:heavy_pid_rows]]
    for pid in range(fanout):
        if pid != heavy:
            picked.append(keys[pids == pid][:light_pid_rows])
    col = np.concatenate(picked)
    page = DevicePage([T.BIGINT], [jnp.asarray(col)],
                      [jnp.zeros(col.size, dtype=bool)],
                      jnp.ones(col.size, dtype=bool), [None])
    return page, heavy


def test_hybrid_mid_build_revocation_demotes_largest_in_place():
    """Satellite unit: one revocation demotes exactly the LARGEST
    resident partition — the rest of the build stays on device (in
    place), the pool counts one partition spill."""
    from trino_tpu import types as T
    from trino_tpu.block import DevicePage
    from trino_tpu.ops import join as J

    pool = QueryMemoryPool(1 << 20, spill_enabled=True)
    ctx = pool.create_context("build")
    bridge = J.JoinBridge()
    op = J.HashBuilderOperator(
        [T.BIGINT], [0], bridge, memory_context=ctx,
        hybrid={"fanout": 4, "max_depth": 3, "hint": None})
    page, heavy = _skewed_join_page(4, 1024, 16)
    op.add_input(page)
    with ctx.lock:
        freed = op._revoke()
    hs = bridge.hybrid
    assert freed > 0
    assert hs.demotions == 1
    assert set(hs.spilled_build) == {heavy}, \
        "demotion did not pick the largest resident partition"
    assert hs.resident == frozenset(range(4)) - {heavy}
    assert any(isinstance(p, DevicePage) for p in op._pages), \
        "revocation spilled the whole build instead of one partition"
    assert pool.stats()["partition_spills"] == 1
    assert hs.spill_fraction() > 0.5  # the heavy partition dominated
    ctx.close()
    pool.close()


def test_hybrid_recursive_repartition_depth_bound():
    """Satellite unit: an oversized cold partition repartitions with a
    depth-salted hash while depth < max_depth; AT the bound it must
    reserve-or-raise instead of recursing forever."""
    import jax.numpy as jnp

    from trino_tpu import types as T
    from trino_tpu.exec.memory import MemoryExceededError
    from trino_tpu.ops import join as J

    types_ = [T.BIGINT]
    cols = [jnp.arange(64, dtype=jnp.int64)]
    nulls = [jnp.zeros(64, dtype=bool)]
    b = J._assemble_build_side(types_, [0], cols, nulls,
                               jnp.ones(64, dtype=bool), 64, [None])
    bridge = J.JoinBridge()
    bridge.set_build(b)
    op = J.LookupJoinOperator(types_, [0], bridge, "inner")
    op._ready = []
    pool = QueryMemoryPool(64, spill_enabled=True)  # nothing fits
    ctx = pool.create_context("build")
    hs = J.HybridJoinState(4, max_depth=2)
    hs.ctx = ctx
    keys = np.arange(4096, dtype=np.int64)
    sp = J._host_spilled(types_, [keys], [np.zeros(keys.size, bool)],
                         keys.size, [None])
    spp = J._host_spilled(types_, [keys[:128]],
                          [np.zeros(128, bool)], 128, [None])
    # below the bound: splits into depth-1 children at the queue FRONT
    op._deferred = [{"depth": 0, "build": [sp], "probe": [spp]}]
    op._advance_deferred(hs)
    assert hs.repartitions == 1
    assert op._deferred and all(e["depth"] == 1 for e in op._deferred)
    child_rows = sum(int(np.asarray(p.valid).sum())
                     for e in op._deferred for p in e["build"])
    assert child_rows == keys.size  # no rows lost across the split
    # the depth-salted hash actually redistributed the partition
    assert len(op._deferred) > 1
    # AT the bound: no further recursion — the reserve failure surfaces
    op._deferred = [{"depth": 2, "build": [sp], "probe": [spp]}]
    with pytest.raises(MemoryExceededError):
        op._advance_deferred(hs)
    assert hs.repartitions == 1  # did not split past max_depth
    assert hs.max_depth_seen == 1
    ctx.close()
    pool.close()


def test_hybrid_spill_record_hbo_roundtrip():
    """Satellite unit: the spill record survives NodeHistory serde and
    EWMA merges verbatim (it is replaced, never averaged)."""
    from trino_tpu.telemetry.stats_store import NodeHistory

    rec = {"fanout": 16, "source": "local", "fraction": 0.25,
           "partitions_spilled": 3, "demotions": 3, "repartitions": 0,
           "max_depth": 0}
    h = NodeHistory("fp0", "JoinNode")
    h.merge({"rows": 100.0, "spill": rec}, alpha=0.3)
    assert h.spill == rec
    # a later run WITHOUT spill keeps the last observed record
    h.merge({"rows": 120.0}, alpha=0.3)
    assert h.spill == rec
    # a later run with a new record replaces it outright
    rec2 = dict(rec, fanout=32, fraction=0.5)
    h.merge({"rows": 90.0, "spill": rec2}, alpha=0.3)
    assert h.spill == rec2
    back = NodeHistory.from_dict(h.to_dict())
    assert back.spill == rec2 and back.runs == 3


# ---------------------------- the join under a shrinking share of its peak ----

#: no aggregation above the join (its finish-merge has a cliff of its
#: own); ORDER BY + LIMIT pin the rows
LADDER_SQL = ("select o_orderdate, o_shippriority, l_extendedprice "
              "from orders o, lineitem l "
              "where o.o_orderkey = l.l_orderkey "
              "order by l_extendedprice desc, o_orderdate limit 10")


@pytest.fixture(scope="module")
def ladder_baseline():
    res = make_runner(256, hbo_enabled=False).execute(LADDER_SQL)
    return res.rows, res.stats["memory"]["peak_bytes"]


@pytest.mark.parametrize("pct", [100, 50, 25])
def test_join_answers_the_same_at_a_share_of_its_own_peak(
        pct, ladder_baseline):
    """A join given 100 %, 50 % and 25 % of the memory its own
    unconstrained run peaked at degrades instead of dying: nothing is
    killed, the rows are those of the unconstrained run, the peak stays
    under the cap, and at a quarter it runs partitioned (demotions
    happened), not by the luck of a roomy plan."""
    rows, peak = ladder_baseline
    cap = max(1, peak * pct // 100)
    res = make_runner(256, hbo_enabled=False, query_max_memory_bytes=cap,
                      spill_enabled=True,
                      spill_to_disk_enabled=True).execute(LADDER_SQL)
    mem = res.stats["memory"]
    assert res.rows == rows
    assert mem["peak_bytes"] <= cap
    if pct == 25:
        assert mem["partition_spills"] > 0, mem
