"""TPC-H Q21 on the served path over resident tables, as the cell
``sf1_q21_antijoin`` runs it: the benchmark's q21 template through
``ProtocolServer`` + ``Client`` over the ``local_resident`` runner kind,
on ``tiny`` (100 suppliers: 2-7 of a nation hold a waiting line).

The answers are held to the benchmark's numpy reference, exactly, and to
sqlite; the reference's three controls to the data and to a hand-made
table; the counters the cell's per-layer metrics read (``join_type``,
``input_rows``, ``residual_rows``) to numpy counts over the same data.
Both subqueries run as joins on l1 itself, beneath the inner joins
(``PushSemiJoinBelowJoin``), each with the residual ``l_suppkey <>
l1.l_suppkey`` on its key.
"""

import json
import os

import numpy as np
import pytest

from benchmark import compare, traffic
from benchmark.references import q21 as q21_reference
from benchmark.references.hosttables import HostTables, days
from benchmark.systems import local_resident
from benchmark.tests import sqlite_oracle
from benchmark.tests.test_references import assert_rows
from test_q18_semijoin import served
from trino_tpu.client import Client
from trino_tpu.server.protocol import ProtocolServer
from trino_tpu.telemetry import stats_store
from trino_tpu.telemetry.tracing import span_tree

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TEMPLATE = traffic.load_template("q21")
#: suppliers of the nation with a waiting line at ``tiny``
SUPPLIERS = {"ALGERIA": 3, "CHINA": 4, "FRANCE": 6, "PERU": 7,
             "SAUDI ARABIA": 2}
CONTROLS = {"any_other_line": {"other": "line"},
            "no_not_exists": {"not_exists": False},
            "any_status": {"status_f": False}}


def serve():
    """A fresh history and the cell's configuration cut to ``tiny``
    behind a started server: ``(server, runner)``."""
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "tpch_sf1_resident_q21_1chip.json")) as f:
        config = json.load(f)
    config["schema"] = "tiny"
    stats_store.store().clear()
    runner = local_resident.build(config)
    return ProtocolServer(runner).start(), runner


@pytest.fixture(scope="module")
def client():
    server, _ = serve()
    yield Client(server.uri)
    server.stop()


@pytest.fixture(scope="module")
def tables():
    return HostTables("tiny")


@pytest.fixture(scope="module")
def oracle():
    return sqlite_oracle.load("tiny", TEMPLATE.meta["columns"])


def joins(trace):
    return {s["attrs"]["join_type"]: s["attrs"] for s in trace
            if s["attrs"].get("join_type") in ("semi", "anti")}


def root_of(trace):
    root, = span_tree(trace)[0]
    return root["attrs"]


@pytest.mark.parametrize("nation", sorted(SUPPLIERS))
def test_q21_equals_reference_and_sqlite(nation, client, tables, oracle):
    want = q21_reference.reference(tables, {"NATION": nation})
    assert len(want) == SUPPLIERS[nation]
    sql = traffic.instantiate(TEMPLATE, {"NATION": nation}).sql
    assert_rows(want, oracle.execute(sqlite_oracle.to_sqlite(sql)).fetchall())
    got, _ = served(client, TEMPLATE, NATION=nation)
    assert compare.mismatches(got, want, ordered=True) == 0


@pytest.mark.parametrize("control", sorted(CONTROLS))
def test_each_control_fails_the_comparison(control, client, tables):
    """Each control answers another question than q21's, the comparison
    tells its answer from the reference's and from the engine's."""
    want = q21_reference.reference(tables, {"NATION": "FRANCE"})
    wrong = q21_reference.reference(tables, {"NATION": "FRANCE"},
                                    **CONTROLS[control])
    assert compare.mismatches(wrong, want, ordered=True) > 0
    got, _ = served(client, TEMPLATE, NATION="FRANCE")
    assert compare.mismatches(got, wrong, ordered=True) > 0


# Twelve lines over five orders, (order, supplier, late); every line's
# commit date is one day
LINES = [(1, 1, True), (1, 1, True), (1, 2, False),  # 1 twice late, 2 not
         (2, 1, True), (2, 3, True),                 # both late
         (3, 1, True), (3, 1, False),                # one supplier
         (4, 1, True), (4, 2, False),                # status O
         (5, 3, True), (5, 2, False), (5, 1, False)]  # 3 alone is late
STATUS = {1: "F", 2: "F", 3: "F", 4: "O", 5: "F"}
COMMIT = days("1995-01-10")


class HandMade:
    """``LINES`` and ``STATUS`` as a ``HostTables``: suppliers 1 and 2 of
    nation HERE, 3 of THERE."""

    TABLES = {
        "supplier": {"s_suppkey": np.array([1, 2, 3]),
                     "s_name": (np.array([0, 1, 2]), ["S1", "S2", "S3"]),
                     "s_nationkey": np.array([7, 7, 8])},
        "nation": {"n_nationkey": np.array([7, 8]),
                   "n_name": (np.array([0, 1]), ["HERE", "THERE"])},
        "orders": {"o_orderkey": np.array(sorted(STATUS)),
                   "o_orderstatus": (np.array(
                       ["FO".index(v) for _, v in sorted(STATUS.items())]),
                       ["F", "O"])},
        "lineitem": {"l_orderkey": np.array([ln[0] for ln in LINES]),
                     "l_suppkey": np.array([ln[1] for ln in LINES]),
                     "l_commitdate": np.full(len(LINES), COMMIT),
                     "l_receiptdate": np.array(
                         [COMMIT + ln[2] for ln in LINES])},
    }

    def columns(self, table, names):
        return [self.TABLES[table][n] for n in names]


@pytest.mark.parametrize("nation,keywords,want", [
    # order 1 counts both of supplier 1's late lines; order 2 neither
    # supplier; order 3 has no second supplier; order 4 is not F
    ("HERE", {}, [("S1", 2)]),
    ("THERE", {}, [("S3", 1)]),
    # lines for suppliers: order 1's two late lines bar each other and
    # order 3's punctual line of the same supplier stands in for another
    ("HERE", {"other": "line"}, [("S1", 1)]),
    ("HERE", {"not_exists": False}, [("S1", 3)]),
    ("THERE", {"not_exists": False}, [("S3", 2)]),
    ("HERE", {"status_f": False}, [("S1", 3)]),
], ids=["here", "there", "any_other_line", "no_not_exists",
        "no_not_exists_there", "any_status"])
def test_reference_on_a_hand_made_table(nation, keywords, want):
    assert len(LINES) == 12
    assert q21_reference.reference(HandMade(), {"NATION": nation},
                                   **keywords) == want


def test_join_spans_say_their_type_input_and_residual(client, tables):
    """The semi join is handed the nation's late lines (the supplier
    join's dynamic filter is a membership table: exact), the anti join
    what the semi join kept; each puts every line of the probe row's
    order that its build holds through the residual predicate."""
    skey, snation = tables.columns("supplier", ["s_suppkey", "s_nationkey"])
    nkey, (ncodes, nnames) = tables.columns(
        "nation", ["n_nationkey", "n_name"])
    lorder, lsupp, commit, receipt = tables.columns(
        "lineitem", ["l_orderkey", "l_suppkey", "l_commitdate",
                     "l_receiptdate"])
    france = nkey[ncodes == nnames.index("FRANCE")]
    late = receipt > commit
    lines = np.bincount(lorder)
    late_lines = np.bincount(lorder[late], minlength=len(lines))
    width = int(lsupp.max()) + 1
    pairs = np.unique(lorder * width + lsupp)
    suppliers = np.bincount(pairs // width, minlength=len(lines))
    late_pairs = np.unique(lorder[late] * width + lsupp[late])
    late_suppliers = np.bincount(late_pairs // width, minlength=len(lines))

    l1 = late & np.isin(lsupp, skey[np.isin(snation, france)])
    semi_kept = l1 & (suppliers[lorder] >= 2)
    anti_kept = semi_kept & (late_suppliers[lorder] == 1)

    # the settled plan: a shape's first plan builds on ``orders``, whose
    # filter then masks l1's scan as well (status F: about half)
    served(client, TEMPLATE, NATION="FRANCE")
    _, trace = served(client, TEMPLATE, NATION="FRANCE")
    by_type = joins(trace)
    semi, anti = by_type["semi"], by_type["anti"]
    assert (semi["input_rows"], semi["rows"]) == \
        (int(l1.sum()), int(semi_kept.sum()))
    assert (anti["input_rows"], anti["rows"]) == \
        (int(semi_kept.sum()), int(anti_kept.sum()))
    assert semi["residual_rows"] == semi["expand_rows"] == \
        int(lines[lorder[l1]].sum())
    assert anti["residual_rows"] == anti["expand_rows"] == \
        int(late_lines[lorder[semi_kept]].sum())
    for join in (semi, anti):
        assert join["residual_rows"] <= join["residual_lanes"] \
            == join["expand_lanes"] <= 2 * max(join["residual_rows"], 16)
        assert join["direct_probe_pages"] == join["probe_pages"] > 0
    inner = [s["attrs"] for s in trace
             if s["attrs"].get("join_type") == "inner"]
    assert inner and not any("residual_rows" in j for j in inner)


@pytest.mark.parametrize("name", ["q18", "q3"])
def test_joins_without_a_residual_say_none(name, client):
    template = traffic.load_template(name)
    _, trace = served(client, template, **template.meta["validation"])
    typed = [s["attrs"] for s in trace if "join_type" in s["attrs"]]
    assert typed and not any(
        "residual_rows" in j or "residual_lanes" in j for j in typed)


@pytest.mark.parametrize("name,syncs", [("q21", 47), ("q18", 32),
                                        ("q3", 26)])
def test_counters_add_no_device_read(name, syncs, client):
    """The warm ``host_syncs`` of q21 pinned, and those of the q18 and q3
    templates over the same runner what they were before the residual
    counters came (plain host adds of a total ``_expand`` had read)."""
    template = traffic.load_template(name)
    served(client, template, **template.meta["validation"])
    _, trace = served(client, template, **template.meta["validation"])
    assert root_of(trace)["host_syncs"] == syncs


def test_three_statements_leave_one_plan(client):
    """History re-plans the shape from what its last run read; the plan
    that reads ``lineitem`` three times under two identical filters has
    to settle."""
    served(client, TEMPLATE, NATION="PERU")     # a shape's first plan
    fps = set()                                 # is the connector's
    for _ in range(3):
        _, trace = served(client, TEMPLATE, NATION="PERU")
        fps.add(root_of(trace)["plan_fp"])
    assert len(fps) == 1


def test_a_masked_filter_files_nothing_and_the_plan_stays(tables):
    """Statement 1 (the connector's row counts) builds on ``orders``
    and its filter reads every row of status ``F``; history then probes
    with ``orders`` under the dynamic filter of the waiting lines'
    build, which leaves the filter some tens of rows.  Filed, those
    rows pulled the node's history down statement by statement until
    ``orders`` looked the smaller side again: statement 11 built on it,
    read it whole, and the order swung back (at SF1 inside a window)."""
    (ocodes, ostatus), = tables.columns("orders", ["o_orderstatus"])
    status_f = int((np.array(ostatus)[ocodes] == "F").sum())
    server, runner = serve()
    client = Client(server.uri)
    fps, masked, orders_bytes = [], [], None
    try:
        for nation in (sorted(SUPPLIERS) * 3)[:14]:
            _, trace = served(client, TEMPLATE, NATION=nation)
            fps.append(root_of(trace)["plan_fp"])
            scans = [s["attrs"] for s in trace
                     if s["name"] == "TableScanOperator"]
            if orders_bytes is None:        # statement 1 reads it whole
                orders_bytes, = [s["resident_bytes"] for s in scans
                                 if s["rows"] == 15000]
            masked.extend(s["rows"] for s in scans
                          if s["resident_bytes"] == orders_bytes)
        explain = runner.explain(
            traffic.instantiate(TEMPLATE, {"NATION": "PERU"}).sql)
    finally:
        server.stop()
    assert masked[0] == 15000 and max(masked[1:]) < 400
    assert len(set(fps[1:])) == 1 and fps[0] != fps[1]
    line, = [ln for ln in explain.splitlines() if "o_orderstatus" in ln
             and "Filter" in ln]
    assert f"est~{status_f} rows [source=hbo]" in line


def test_explain_analyze_names_the_residual(client):
    sql = traffic.instantiate(TEMPLATE, {"NATION": "FRANCE"}).sql
    text = "\n".join(r[0] for r in client.execute(
        "explain analyze " + sql).rows)
    residual = [ln for ln in text.splitlines() if "residual over" in ln]
    assert len(residual) == 2
    assert all("LookupJoinOperator" in ln and "probe direct" in ln
               for ln in residual)
