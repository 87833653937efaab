"""Device-collective exchange inside DistributedQueryRunner.

The flagship TPU-native path (SURVEY.md §2.8): hash stage boundaries run
as one all_to_all over the mesh. These tests assert the collective
ACTUALLY runs (not silently falling back to the host path) and that
results are identical either way.
"""

import pytest

from trino_tpu.connectors.tpch import TpchConnector
from trino_tpu.parallel import distributed as dist_mod
from trino_tpu.parallel.device_exchange import DeviceExchange
from trino_tpu.parallel.distributed import DistributedQueryRunner
from trino_tpu.sql.analyzer import Session


@pytest.fixture(scope="module")
def conn():
    return TpchConnector(page_rows=2048)


def _runner(conn, device: bool, n_workers: int = 3):
    s = Session(catalog="tpch", schema="micro")
    s.properties["device_exchange"] = device
    return DistributedQueryRunner({"tpch": conn}, s, n_workers=n_workers,
                                  desired_splits=8,
                                  broadcast_threshold=300.0)


def _key(row):
    return tuple(("\0" if v is None else str(v)) for v in row)


QUERIES = [
    # group-by: partial agg -> hash exchange -> final agg
    "select l_returnflag, l_linestatus, count(*), sum(l_quantity) "
    "from lineitem group by l_returnflag, l_linestatus",
    # string group keys: pool unification + value-stable routing
    "select l_shipmode, count(*) from lineitem group by l_shipmode",
    # partitioned join: both sides hash-exchange on orderkey
    "select o_orderpriority, count(*) from orders, lineitem "
    "where o_orderkey = l_orderkey and l_quantity < 10 "
    "group by o_orderpriority",
]


@pytest.mark.parametrize("sql", QUERIES)
def test_device_vs_host_exchange_identical(conn, sql):
    dev = _runner(conn, True)
    host = _runner(conn, False)
    drows = sorted(dev.execute(sql).rows, key=_key)
    hrows = sorted(host.execute(sql).rows, key=_key)
    assert drows == hrows


def test_collective_actually_runs(conn, monkeypatch):
    """Guard against silent host fallback: the a2a path must execute for
    a plain group-by."""
    ran = []
    orig = DeviceExchange._collect

    def spying_collect(self):
        out = orig(self)
        ran.append(self.data_collectives > 0)
        return out

    monkeypatch.setattr(DeviceExchange, "_collect", spying_collect)
    r = _runner(conn, True)
    res = r.execute("select l_returnflag, count(*) from lineitem "
                    "group by l_returnflag")
    assert len(res.rows) == 3
    assert any(ran), "device exchange fell back to host path"


def test_device_exchange_disabled_uses_host(conn):
    r = _runner(conn, False)
    frag = None
    for f in r.create_fragments(
            "select l_returnflag, count(*) from lineitem "
            "group by l_returnflag"):
        if f.output_kind == "hash":
            frag = f
    assert frag is not None
    assert r._device_exchange_for(frag, r.n_workers) is None


def test_device_exchange_chosen_for_hash(conn):
    r = _runner(conn, True)
    frag = None
    for f in r.create_fragments(
            "select l_returnflag, count(*) from lineitem "
            "group by l_returnflag"):
        if f.output_kind == "hash":
            frag = f
    assert isinstance(r._device_exchange_for(frag, r.n_workers),
                      DeviceExchange)
    # task-count mismatch -> host fallback
    assert r._device_exchange_for(frag, r.n_workers + 1) is None


@pytest.mark.parametrize("n_devices", [1, 2])
@pytest.mark.parametrize("sql", QUERIES)
def test_fewer_devices_than_partitions(conn, monkeypatch, sql, n_devices):
    """Single-chip degeneracy: p partitions on d < p devices (partition
    p lives on device p % d, ids carried through the collective). The
    flagship path must EXECUTE — not fall back — and match the host
    path. Ref: operator/output/PartitionedOutputOperator.java (which has
    no such coupling because its buffers are host-side)."""
    import jax

    real = jax.devices()
    monkeypatch.setattr(jax, "devices",
                        lambda *a, **k: real[:n_devices])
    ran = []
    orig = DeviceExchange._collect

    def spying_collect(self):
        assert self.d == min(n_devices, self.n)
        out = orig(self)
        ran.append(self.data_collectives > 0)
        return out

    monkeypatch.setattr(DeviceExchange, "_collect", spying_collect)
    dev = _runner(conn, True)
    drows = sorted(dev.execute(sql).rows, key=_key)
    monkeypatch.undo()
    host = _runner(conn, False)
    hrows = sorted(host.execute(sql).rows, key=_key)
    assert drows == hrows
    assert any(ran), "device exchange fell back to host path"


# -------------------- q1 through the planner over 2, 4 and 8 devices ----

Q1ISH_EXPLAIN = ("explain analyze select l_returnflag, l_linestatus, "
                 "count(*), sum(l_quantity) from lineitem "
                 "group by l_returnflag, l_linestatus")


def _device_lines(res):
    return [row[0].strip() for row in res.rows
            if "exchange [device]" in row[0]]


def _local(conn):
    from trino_tpu.runner import LocalQueryRunner

    return LocalQueryRunner({"tpch": conn},
                            Session(catalog="tpch", schema="micro"))


@pytest.fixture
def fresh_history():
    from trino_tpu.parallel.device_exchange import SIZING_HISTORY

    SIZING_HISTORY.reset()
    yield
    SIZING_HISTORY.reset()


@pytest.mark.parametrize("n_workers", [2, 8])
def test_q1_over_two_and_eight_workers_equals_local(conn, n_workers):
    """TPC-H q1 planned and run over 2 and over all 8 virtual devices,
    its partial groups crossing the mesh in one ``all_to_all``, equals
    single-process execution."""
    from trino_tpu.resources.tpch_queries import TPCH_QUERIES

    before = DeviceExchange.total_collectives
    got = _runner(conn, True, n_workers).execute(TPCH_QUERIES[1]).rows
    assert DeviceExchange.total_collectives > before
    want = _local(conn).execute(TPCH_QUERIES[1]).rows
    assert len(got) == len(want) == 4
    for g, w in zip(got, want):
        assert g[:2] == w[:2] and g[-1] == w[-1]
        assert [float(v) for v in g[2:-1]] == \
            pytest.approx([float(v) for v in w[2:-1]], rel=1e-9)


def test_count_first_sizing_plans_run_with_zero_retries(conn,
                                                        fresh_history):
    """On the planner path a shape not seen before is sized by the
    counting collective: one count, one data ``all_to_all``, no
    doubling retry."""
    counts = DeviceExchange.total_count_collectives
    lines = _device_lines(_runner(conn, True, 4).execute(Q1ISH_EXPLAIN))
    assert lines
    for line in lines:
        assert "retries=0" in line and "collectives=1+1" in line, line
    assert DeviceExchange.total_count_collectives - counts == len(lines)


def test_partition_overflow_is_retried_and_answers_the_same(conn,
                                                            fresh_history):
    """A statement whose exchange shape the history has seen only small
    is presized too small: the lanes overflow, the collective is re-run
    at doubled capacity until it fits, the answer equals single-process
    execution, and the shape is re-learned (no retry the next time)."""
    import re

    sql = ("select l_orderkey, count(*) c from lineitem "
           "where l_orderkey < {} group by l_orderkey")
    r = _runner(conn, True, 4)

    def retries(bound):
        line, = _device_lines(r.execute("explain analyze "
                                        + sql.format(bound)))
        return int(re.search(r"retries=(\d+)", line).group(1))

    assert retries(40) == 0          # counted: fits
    assert retries(40) == 0          # presized from history: fits
    assert retries(4_000_000) >= 1   # presized from 39 rows: overflows
    got = sorted(r.execute(sql.format(4_000_000)).rows)
    want = sorted(_local(conn).execute(sql.format(4_000_000)).rows)
    assert got == want and len(got) > 1000
    assert retries(4_000_000) == 0   # re-learned
