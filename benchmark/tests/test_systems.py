"""The runner kinds under ``systems/``, ``build_system``'s lookup of
them, and ``TimedRunner`` showing the server what the runner shows."""

import inspect
import json
import os

import pytest

from benchmark import run
from benchmark.systems import distributed, local
from benchmark.tests import sqlite_oracle
from benchmark.tests.test_references import assert_rows


def config(name, schema="tiny"):
    with open(os.path.join(run.HERE, "configs", name + ".json")) as f:
        return dict(json.load(f), schema=schema)


def test_local_builds_what_build_system_built():
    from trino_tpu.runner import LocalQueryRunner

    cfg = config("tpch_sf1_1chip")
    cfg["session_properties"] = {"result_cache_enabled": False}
    runner = local.build(cfg)
    assert type(runner) is LocalQueryRunner
    assert runner.desired_splits == cfg["runner"]["desired_splits"] == 8
    assert (runner.session.catalog, runner.session.schema) == \
        ("tpch", "tiny")
    assert runner.session.properties == {"result_cache_enabled": False}
    conn = runner.metadata.connectors["tpch"]
    assert conn.page_rows == cfg["connector"]["page_rows"] == 65536


def test_distributed_builds_the_configurations_cluster():
    from trino_tpu.parallel.distributed import DistributedQueryRunner

    cfg = config("tpch_sf1_4chip")
    runner = distributed.build(cfg)
    assert type(runner) is DistributedQueryRunner
    assert (runner.n_workers, runner.desired_splits) == (4, 8)
    assert runner.session.schema == "tiny"
    assert runner.session.properties == {
        "device_exchange": True, "join_distribution_type": "PARTITIONED"}


def test_unknown_runner_kind_names_the_missing_file():
    cfg = config("tpch_sf1_1chip")
    cfg["runner"] = {"kind": "worker_cluster"}
    with pytest.raises(ValueError, match="systems/worker_cluster.py"):
        run.build_system(cfg, [])


class SqlOnly:
    """A runner whose ``execute`` takes the SQL text and nothing else,
    as ``DistributedQueryRunner``'s does."""

    def __init__(self, runner):
        self._runner = runner
        self.session = runner.session

    def execute(self, sql):
        return self._runner.execute(sql)


def test_timed_runner_shows_what_the_runner_shows():
    from trino_tpu.client import Client
    from trino_tpu.server.protocol import ProtocolServer

    inner = local.build(config("tpch_tiny_1chip"))
    calls = []
    timed = run.TimedRunner(inner, calls)
    assert list(inspect.signature(timed.execute).parameters) == \
        list(inspect.signature(inner.execute).parameters)
    assert hasattr(timed, "execute_batch")
    assert timed.session is inner.session

    bare = run.TimedRunner(SqlOnly(inner), calls)
    assert list(inspect.signature(bare.execute).parameters) == ["sql"]
    assert not hasattr(bare, "execute_batch")
    server = ProtocolServer(bare).start()
    try:
        assert not server._batching_enabled()
        sql = "select count(*) from nation"
        res = Client(server.uri, timeout=600.0).execute(sql)
    finally:
        server.stop()
    assert res.rows == [[25]] or res.rows == [(25,)]
    (t0, t1, sqls), = calls
    assert t0 < t1 and sqls == [sql]


def test_served_distributed_q3_equals_sqlite():
    """``ProtocolServer`` over the configuration's cluster (4 workers on
    4 virtual CPU devices, device exchange, PARTITIONED join) behind
    ``TimedRunner``: q3 through ``Client`` equals the sqlite oracle, five
    collectives ran, and the statement's root span finished."""
    from trino_tpu.client import Client
    from trino_tpu.parallel.device_exchange import DeviceExchange
    from trino_tpu.telemetry import tracing

    from benchmark import compare, traffic

    template = traffic.load_template("q3")
    inst = traffic.instantiate(template, template.meta["validation"])
    calls = []
    server = run.build_system(config("tpch_sf1_4chip"), calls)
    before = DeviceExchange.total_collectives
    try:
        res = Client(server.uri, timeout=600.0).execute(inst.sql)
    finally:
        server.stop()
    assert DeviceExchange.total_collectives - before == 5
    db = sqlite_oracle.load("tiny", template.meta["columns"])
    oracle = db.execute(sqlite_oracle.to_sqlite(inst.sql)).fetchall()
    assert len(oracle) == 10
    assert_rows(compare.typed_rows(res.columns, res.rows), oracle)
    assert len(calls) == 1 and calls[0][2] == [inst.sql]
    traces, _ = tracing.RING.since(calls[0][0] - 60.0)
    roots = [s for spans in traces for s in spans
             if s["parent_id"] is None and s["name"] == "statement"]
    assert roots and roots[-1]["attrs"]["state"] == "FINISHED"


def test_unused_devices():
    used = {"id": 0, "peak_bytes_in_use": 5, "busy_s": 0.1}
    assert run.unused_devices([used, {"id": 1, "peak_bytes_in_use": 7}]) \
        == []
    no_memory = {"id": 2, "peak_bytes_in_use": 0, "busy_s": 0.1}
    no_work = {"id": 3, "peak_bytes_in_use": 9, "busy_s": 0.0}
    assert run.unused_devices([used, no_memory, no_work]) == \
        [no_memory, no_work]
