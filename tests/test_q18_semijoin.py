"""TPC-H Q18 on the served path over resident tables, as the cell
``sf1_q18_semijoin`` runs it: the benchmark's q18 template through
``ProtocolServer`` + ``Client`` over the ``local_resident`` runner kind,
on ``tiny``.

The spec's QUANTITY range (312-315) leaves no order at ``tiny``, so the
thresholds are lowered: 842, 68, 12 and 0 orders pass at 200, 250, 275
and 300 (the first is cut by the 100-row limit, the last is empty).
The answers are held to the benchmark's numpy reference, exactly, and to
sqlite; the operators' counters the cell's per-layer metrics read
(``merge_lanes``, ``groups_out``, ``join_type``, ``input_rows``) to numpy
counts over the same data.  The semi join runs on ``orders`` itself,
beneath the inner joins (``PushSemiJoinBelowJoin``, since PR 45).
"""

import json
import os
import time

import numpy as np
import pytest

from benchmark import compare, traffic
from benchmark.references import q18 as q18_reference
from benchmark.references.hosttables import HostTables
from benchmark.systems import local_resident
from benchmark.tests import sqlite_oracle
from benchmark.tests.test_references import assert_rows
from trino_tpu.client import Client
from trino_tpu.connectors import memory
from trino_tpu.server.protocol import ProtocolServer
from trino_tpu.telemetry import tracing
from trino_tpu.telemetry.tracing import span_tree

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TEMPLATE = traffic.load_template("q18")
PASSING = {200: 842, 250: 68, 275: 12, 300: 0}


def serve(**session_properties):
    """The cell's configuration cut to ``tiny`` behind a started server:
    ``(server, client)``."""
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "tpch_sf1_resident_q18_1chip.json")) as f:
        config = json.load(f)
    config["schema"] = "tiny"
    config["session_properties"].update(session_properties)
    server = ProtocolServer(local_resident.build(config)).start()
    return server, Client(server.uri)


@pytest.fixture(scope="module")
def client():
    server, client = serve()
    yield client
    server.stop()


@pytest.fixture(scope="module")
def tables():
    return HostTables("tiny")


@pytest.fixture(scope="module")
def oracle():
    return sqlite_oracle.load("tiny", TEMPLATE.meta["columns"])


@pytest.fixture(scope="module")
def order_sums(tables):
    """``sum(l_quantity)`` of every order of ``lineitem``, scale 2."""
    lkey, qty = tables.columns("lineitem", ["l_orderkey", "l_quantity"])
    return np.bincount(lkey, weights=qty).astype(np.int64)[np.unique(lkey)]


def served(client, template, **params):
    """One instance through the server: ``(typed rows, statement trace)``."""
    sql = traffic.instantiate(template, params).sql
    t0 = time.perf_counter()
    res = client.execute(sql)
    traces, lost = tracing.RING.since(t0)
    assert not lost
    trace, = [t for t in traces if span_tree(t)[0][0]["name"] == "statement"]
    return compare.typed_rows(res.columns, res.rows), trace


def operators(trace):
    return [s for s in trace if s["attrs"].get("span_kind") == "operator"]


@pytest.mark.parametrize("quantity", sorted(PASSING))
def test_q18_equals_reference_and_sqlite(quantity, client, tables, oracle,
                                         order_sums):
    assert int((order_sums > quantity * 100).sum()) == PASSING[quantity]
    want = q18_reference.reference(tables, {"QUANTITY": quantity})
    assert len(want) == min(PASSING[quantity], 100)
    sql = traffic.instantiate(TEMPLATE, {"QUANTITY": quantity}).sql
    assert_rows(want, oracle.execute(sqlite_oracle.to_sqlite(sql)).fetchall())
    got, _ = served(client, TEMPLATE, QUANTITY=quantity)
    assert compare.mismatches(got, want, ordered=True) == 0


def test_threshold_equal_to_an_orders_sum_is_strict(client, tables,
                                                    order_sums):
    """The control: at a threshold some order's sum equals, ``>`` and
    ``>=`` give different answers, the engine gives the first and the
    comparison tells the second from it."""
    threshold = int(np.sort(order_sums)[-10])
    assert threshold % 100 == 0             # quantities are whole numbers
    over = int((order_sums > threshold).sum())
    at_least = int((order_sums >= threshold).sum())
    assert 0 < over < at_least <= 100
    quantity = threshold // 100
    strict = q18_reference.reference(tables, {"QUANTITY": quantity})
    loose = q18_reference.reference(tables, {"QUANTITY": quantity - 1})
    assert (len(strict), len(loose)) == (over, at_least)
    got, _ = served(client, TEMPLATE, QUANTITY=quantity)
    assert compare.mismatches(got, strict, ordered=True) == 0
    assert compare.mismatches(got, loose, ordered=True) > 0


def test_many_partials_merge_and_count_their_groups(monkeypatch, tables):
    """Stored pages of 8,192 lanes: the first level keeps a partial per
    page of ``lineitem`` and merges eight of them."""
    monkeypatch.setattr(memory, "PAGE_ROWS", 8192)
    server, small_pages = serve()
    try:
        got, trace = served(small_pages, TEMPLATE, QUANTITY=250)
    finally:
        server.stop()
    want = q18_reference.reference(tables, {"QUANTITY": 250})
    assert compare.mismatches(got, want, ordered=True) == 0
    lkey = tables.column("lineitem", "l_orderkey")
    first_level, = [s["attrs"] for s in operators(trace)
                    if s["name"] == "HashAggregationOperator"
                    and s["attrs"]["input_rows"] == len(lkey)]
    assert first_level["partial_lanes"]["pages"] >= 8
    assert first_level["merge_calls"] >= 1
    assert first_level["merge_lanes"] >= first_level["partial_lanes"]["kept"]
    assert first_level["groups_out"] == len(np.unique(lkey))
    assert first_level["rows"] == first_level["groups_out"]


def test_semi_join_span_says_its_type_and_its_input(client, tables,
                                                    order_sums):
    """The semi join sits on the scan of ``orders``, beneath the inner
    joins (``PushSemiJoinBelowJoin``): what it is handed is ``orders``
    — every row of it where no dynamic filter masks the scan, and the
    rows its own build's filter left (68 keys, a membership table:
    exact) where one does — and ``lineitem`` joins the passing orders
    alone."""
    lkey = tables.column("lineitem", "l_orderkey")
    passing_lines = int(np.isin(
        lkey, np.unique(lkey)[order_sums > 250 * 100]).sum())
    unfiltered, plain = serve(enable_dynamic_filtering=False)
    try:
        _, unmasked = served(plain, TEMPLATE, QUANTITY=250)
    finally:
        unfiltered.stop()
    _, masked = served(client, TEMPLATE, QUANTITY=250)
    for trace, handed in ((unmasked, tables.row_count("orders")),
                          (masked, PASSING[250])):
        ops = operators(trace)
        semi, = [s for s in ops if s["attrs"].get("join_type") == "semi"]
        scan = ops[ops.index(semi) - 1]
        assert scan["name"] == "TableScanOperator"
        assert semi["attrs"]["input_rows"] == scan["attrs"]["rows"] \
            == handed
        assert semi["attrs"]["rows"] == PASSING[250]
        inner = [s["attrs"] for s in ops[ops.index(semi) + 1:]
                 if s["attrs"].get("join_type") == "inner"]
        # orders x customer, then x lineitem, whichever side probes
        assert [j["rows"] for j in inner] == [PASSING[250], passing_lines]
    assert scan["attrs"]["df_table_pages"] == scan["attrs"]["pages"] > 0


@pytest.mark.parametrize("name,syncs", [("q3", 26), ("q13", 14)])
def test_counters_add_no_device_read(name, syncs, client):
    """The warm ``host_syncs`` of the q3 and q13 templates over the same
    runner are what they were before the counters came (q3: 24 until
    PR 42, whose table-backed dynamic filters read their number of
    distinct keys as a second scalar — one more read for each of q3's
    two filters, and no key column crossing to the host)."""
    template = traffic.load_template(name)
    served(client, template, **template.meta["validation"])
    _, trace = served(client, template, **template.meta["validation"])
    root, = span_tree(trace)[0]
    assert root["attrs"]["host_syncs"] == syncs
