"""TPC-H Q9 on the served path over resident tables, as the cell
``sf1_q9_join6`` runs it: the benchmark's q9 template through
``ProtocolServer`` + ``Client`` over the ``local_resident`` runner kind,
on ``tiny`` - six relations, five joins, one of them on two columns.

The answers are held to the benchmark's numpy reference, exactly, at
three colours; two controls (``partsupp`` joined on ``ps_partkey`` alone,
float32 sums) show that the comparison can fail.  Then what the cell is
there to guard: the plan settles.  History-based statistics re-plan a
statement shape from what its last run read, and before this suite's
repairs q9 ran under four plans in five statements, the second of them
building on ``lineitem`` (``telemetry/stats_store.py``: a scan's history
was what a dynamic filter's mask left of it, and a join's history was
served to the same criteria hung over other relations).
"""

import json
import os
import time

import numpy as np
import pytest

from benchmark import compare, traffic
from benchmark.references import q9 as q9_reference
from benchmark.references.hosttables import HostTables
from benchmark.systems import local_resident
from trino_tpu.client import Client
from trino_tpu.connectors.tpch import TpchConnector
from trino_tpu.resources.tpch_queries import TPCH_QUERIES
from trino_tpu.runner import LocalQueryRunner
from trino_tpu.server.protocol import ProtocolServer
from trino_tpu.sql.analyzer import Session
from trino_tpu.telemetry import stats_store, tracing
from trino_tpu.telemetry.tracing import span_tree

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TEMPLATE = traffic.load_template("q9")
COLORS = ("green", "red", "almond")     # almond leaves 173 of 175 groups


def test_template_is_the_engines_q9_with_one_hole():
    assert TEMPLATE.sql.format(COLOR="green") == TPCH_QUERIES[9].strip()
    assert TEMPLATE.sql.count("{") == 1


def resident_runner():
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "tpch_sf1_resident_q9_1chip.json")) as f:
        config = json.load(f)
    config["schema"] = "tiny"
    return local_resident.build(config)


@pytest.fixture(scope="module")
def tables():
    return HostTables("tiny")


@pytest.fixture(scope="module")
def served_run():
    """A fresh history, the cell's configuration cut to ``tiny`` behind
    a started server, and eight statements over two colours as a run's
    warm-up and window send them: ``[(typed rows, trace, explain)]``."""
    stats_store.store().clear()
    runner = resident_runner()
    server = ProtocolServer(runner).start()
    client = Client(server.uri)
    out = []
    try:
        for color in ("green", "red", "red", "green") * 2:
            sql = traffic.instantiate(TEMPLATE, {"COLOR": color}).sql
            t0 = time.perf_counter()
            res = client.execute(sql)
            traces, lost = tracing.RING.since(t0)
            assert not lost
            trace, = [t for t in traces
                      if span_tree(t)[0][0]["name"] == "statement"]
            out.append((color, compare.typed_rows(res.columns, res.rows),
                        trace, runner.explain(sql)))
    finally:
        server.stop()
    return out


def named(trace, name):
    return [s for s in trace if s["name"] == name]


def root_of(trace):
    root, = [s for s in trace if s["parent_id"] is None]
    return root["attrs"]


@pytest.fixture(scope="module")
def client():
    server = ProtocolServer(resident_runner()).start()
    yield Client(server.uri)
    server.stop()


@pytest.mark.parametrize("color", COLORS)
def test_q9_equals_reference(color, client, tables):
    want = q9_reference.reference(tables, {"COLOR": color})
    assert len(want) == (173 if color == "almond" else 175)
    res = client.execute(traffic.instantiate(TEMPLATE,
                                             {"COLOR": color}).sql)
    got = compare.typed_rows(res.columns, res.rows)
    assert compare.mismatches(got, want, ordered=True) == 0


@pytest.mark.parametrize("control", [
    {"partsupp_key": "part"}, {"acc": np.float32}], ids=str)
def test_controls_fail_the_comparison(control, served_run, tables):
    """``partsupp`` joined on one column of its key takes another
    supplier's cost; a float32 sum cannot hold 10**11 units."""
    color, got, _, _ = served_run[-1]
    sound = q9_reference.reference(tables, {"COLOR": color})
    wrong = q9_reference.reference(tables, {"COLOR": color}, **control)
    assert compare.mismatches(got, sound, ordered=True) == 0
    assert compare.mismatches(got, wrong, ordered=True) > 0


def test_every_statement_gives_the_reference(served_run, tables):
    for color, got, _, _ in served_run:
        want = q9_reference.reference(tables, {"COLOR": color})
        assert compare.mismatches(got, want, ordered=True) == 0


def test_a_scans_history_is_the_rows_it_read(served_run, tables):
    """Whichever filters a plan hung on the scan: ``lineitem`` reads
    59,814 rows after every statement, though a dynamic filter masks
    nine in ten of them at the scan from the first statement on."""
    rows = tables.row_count("lineitem")
    for _, _, trace, explain in served_run:
        line, = [ln for ln in explain.splitlines()
                 if "TableScan memory.tiny.lineitem" in ln]
        assert f"est~{rows} rows [source=hbo]" in line
        scan = max(named(trace, "TableScanOperator"),
                   key=lambda s: s["attrs"]["resident_bytes"])
        assert 0 < scan["attrs"]["rows"] < rows // 5    # masked there


def build_rows(trace):
    return [s["attrs"]["input_rows"]
            for s in named(trace, "HashBuilderOperator")]


def test_the_second_statement_does_not_build_on_lineitem(served_run,
                                                         tables):
    """Plan 1 (connector statistics) builds on ``orders`` and
    ``partsupp``; history then makes the joined rows the build.  Before
    the repair statement 2 built on ``lineitem x supplier x nation``
    (59,814 rows here, 6.0 M at SF1: the sum was 63,215)."""
    first, second = (build_rows(t) for _, _, t, _ in served_run[:2])
    assert {tables.row_count("orders"),
            tables.row_count("partsupp")} <= set(first)
    assert max(second) < tables.row_count("lineitem") // 5
    assert sum(second) < sum(first)


def test_the_plan_settles(served_run):
    """From the third statement of the shape on the physical plan is
    one plan, and from the fourth on nothing is lowered or compiled."""
    roots = [root_of(t) for _, _, t, _ in served_run]
    assert len({r["shape_fp"] for r in roots}) == 1
    assert len({r["plan_fp"] for r in roots[2:]}) == 1
    assert roots[0]["plan_fp"] != roots[2]["plan_fp"]
    assert [r.get("lowerings", 0) for r in roots[3:]] == [0] * 5
    by_color = {}
    for color, _, trace, _ in served_run[2:]:
        by_color.setdefault(color, []).append(build_rows(trace))
    assert all(len(rows) == 3 and rows == rows[:1] * 3
               for rows in by_color.values())


def test_the_two_column_key_probes_the_sorted_index(served_run):
    """The settled plan's counters: the build ``partsupp`` probes is
    keyed by a pair of bigints (``hashed``: no direct-address table),
    every other build by one column."""
    _, _, trace, _ = served_run[-1]
    builders = named(trace, "HashBuilderOperator")
    assert sorted(b["attrs"]["key_mode"] for b in builders) == \
        ["hashed"] + ["single"] * 4
    assert all(b["attrs"]["build_lanes"] >= b["attrs"]["input_rows"]
               for b in builders)
    joins = named(trace, "LookupJoinOperator")
    assert len(joins) == 5
    fell_back = [j for j in joins if j["attrs"].get("probe_fallback")]
    assert [j["attrs"]["probe_fallback"] for j in fell_back] == \
        ["hashed key mode"]
    pages = sum(j["attrs"]["probe_pages"] for j in joins)
    direct = sum(j["attrs"]["direct_probe_pages"] for j in joins)
    assert 0 < direct < pages
    for j in joins:
        assert j["attrs"]["probe_lanes"] >= j["attrs"]["input_rows"]
        assert j["attrs"]["probe_lanes"] % j["attrs"]["probe_pages"] == 0


def test_a_build_behind_a_join_is_as_wide_as_its_rows(served_run):
    """A join expands a probe page at its matches' width, so a build fed
    by a chain of joins behind a selective filter is sorted at its rows'
    width, not at the masked pages': within twice its rows' padded size
    in every statement (at SF1 the two 323 k-row builds were sorted at
    2,097,152 and 1,048,576 lanes).  A build fed straight by a filtered
    scan keeps the scan's width (``part``: 105 rows in 2,048 lanes)."""
    from trino_tpu.block import padded_size

    for _, _, trace, _ in served_run:
        ops = [s for s in trace if s["attrs"].get("span_kind") == "operator"]
        behind_a_join = [
            b["attrs"] for a, b in zip(ops, ops[1:])
            if b["name"] == "HashBuilderOperator"
            and a["name"] == "LookupJoinOperator"]
        assert behind_a_join
        for attrs in behind_a_join:
            assert attrs["build_lanes"] <= \
                2 * padded_size(attrs["input_rows"]), attrs
        joins = named(trace, "LookupJoinOperator")
        assert sum(j["attrs"]["expand_rows"] for j in joins) > 0
        for j in joins:
            assert j["attrs"]["expand_rows"] <= j["attrs"]["expand_lanes"] \
                <= 2 * padded_size(j["attrs"]["expand_rows"])
    _, _, trace, _ = served_run[-1]
    assert max(b["attrs"]["build_lanes"] // b["attrs"]["input_rows"]
               for b in named(trace, "HashBuilderOperator")) > 8


def test_generator_catalog_q9_joins_on_the_one_path():
    """Over the generator's catalog, which states key ranges (from them
    the planner made ``supplier x nation`` a one-hot matmul join until
    PR 46), every join is a lookup join: ``nation``'s 25 keys are probed
    by direct address, ``partsupp``'s two-column key by search, and each
    build that has a key range reads it once, on the statement's
    account."""
    runner = LocalQueryRunner({"tpch": TpchConnector(page_rows=2048)},
                              Session(catalog="tpch", schema="tiny"))
    trace = runner.execute(TPCH_QUERIES[9]).stats["trace"]
    joins = [s for s in trace if "Join" in s["name"]]
    assert len(joins) == 5
    assert {s["name"] for s in joins} == {"LookupJoinOperator"}
    # the probe that was handed supplier's 100 rows
    (nation,) = [j["attrs"] for j in joins
                 if j["attrs"]["input_rows"] == 100]
    assert nation["direct_probe_pages"] == nation["probe_pages"] == 1
    assert nation["direct_table_bytes"] == 4 * 32   # 25 codes + 1, padded
    builds = named(trace, "HashBuilderOperator")
    ranged = [b for b in builds if b["attrs"]["key_mode"] != "hashed"]
    assert len(builds) == 5 and len(ranged) == 4
    by_why = root_of(trace)["host_sync_by_why"]
    assert by_why["join_key_range"][0] == len(ranged)
    assert "matmul_join_key_range" not in by_why
