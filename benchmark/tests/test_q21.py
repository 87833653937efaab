"""The q21 template's pieces: the template against the engine's own q21
text, the traffic file's draw, ``references/q21.py`` against sqlite on
``tiny`` and against the committed answer at SF1 with its three controls,
the reader the cell brings (``join_residual_rows_per_query``) over a
hand-built ring and over a rehearsed run, and the cell's entries in
``BENCHMARK.json`` by name."""

import argparse
import os

import pytest

from benchmark import compare, run, traffic
from benchmark.layer_metrics import join_residual_rows_per_query
from benchmark.references import q21
from benchmark.references.hosttables import HostTables
from benchmark.tests import sqlite_oracle
from benchmark.tests.test_q9 import build, join, with_operators
from benchmark.tests.test_references import assert_rows
from benchmark.tests.test_rehearse import BENCH, on_tiny
from benchmark.tests.test_span_metrics import facts, publish
from trino_tpu.resources.tpch_queries import TPCH_QUERIES
from trino_tpu.telemetry import tracing

CELL = "sf1_q21_antijoin"
CONFIG = "tpch_sf1_resident_q21_1chip"
CONTROLS = ({"other": "line"}, {"not_exists": False}, {"status_f": False})
#: suppliers of the nation with a waiting line at ``tiny``
SUPPLIERS = {"FRANCE": 6, "PERU": 7, "SAUDI ARABIA": 2}


def test_template_renders_to_the_engines_q21():
    template = traffic.load_template("q21")
    inst = traffic.instantiate(template, template.meta["validation"])
    assert inst.sql == TPCH_QUERIES[21].strip()
    assert template.sql.count("{") == 1
    assert template.tables == ["supplier", "lineitem", "orders", "nation"]


@pytest.mark.parametrize("seed", [7, 2147483659, 4100000447])
def test_traffic_file_draws_two_nations(seed):
    file = traffic.load_json("traffic", "q21_stream1.json")
    traffic.check_traffic(file)
    pool = traffic.build_pool(file, seed)
    names = traffic.load_template("q21").meta["params"]["NATION"]["choice"]
    assert len(names) == len(set(names)) == 25
    nations = [dict(i.params)["NATION"] for i in pool]
    assert len(set(nations)) == 2 and set(nations) <= set(names)
    assert pool == traffic.build_pool(file, seed)


@pytest.fixture(scope="module")
def oracle():
    template = traffic.load_template("q21")
    return template, sqlite_oracle.load("tiny", template.meta["columns"])


@pytest.mark.parametrize("nation", sorted(SUPPLIERS))
def test_reference_equals_sqlite_on_tiny(nation, oracle):
    template, db = oracle
    inst = traffic.instantiate(template, {"NATION": nation})
    rows = q21.reference(HostTables("tiny"), dict(inst.params))
    assert len(rows) == SUPPLIERS[nation]
    assert_rows(rows, db.execute(sqlite_oracle.to_sqlite(inst.sql))
                .fetchall())


@pytest.mark.parametrize("schema,nation", [("tiny", "FRANCE"),
                                           ("sf1", "SAUDI ARABIA")])
def test_reference_and_its_controls(schema, nation, monkeypatch):
    """At SF1 and the validation value the reference gives the committed
    100 rows (``tests/sf1_expected.py``); each control gives another
    answer.  (``other="line"`` differs only where a supplier has two
    lines in an order: at SF1 it moves the first 100 rows of 10 of the 25
    nations, the validation value among them.)"""
    tables = HostTables(schema)
    rows = q21.reference(tables, {"NATION": nation})
    if schema == "sf1":
        monkeypatch.syspath_prepend(os.path.join(run.ROOT, "tests"))
        from sf1_expected import EXPECTED

        assert len(rows) == 100
        assert_rows(rows, EXPECTED[21])
    for control in CONTROLS:
        wrong = q21.reference(tables, {"NATION": nation}, **control)
        assert compare.mismatches(wrong, rows, ordered=True) > 0


@pytest.fixture()
def ring(monkeypatch):
    ring = tracing.TraceRing(capacity=8)
    monkeypatch.setattr(tracing, "RING", ring)
    return ring


def residual(rows, lanes, **attrs):
    return join(residual_rows=rows, residual_lanes=lanes, **attrs)


#: case -> ([operator spans per statement], value)
CASES = {
    "a_semi_and_an_anti_join": ([
        [build(input_rows=6005405, key_mode="single"),
         residual(737000, 1048576, join_type="semi"),
         residual(535000, 786432, join_type="anti"),
         join(join_type="inner", expand_rows=8076)],
        [residual(700000, 1048576, join_type="semi"),
         residual(500000, 786432, join_type="anti")]], 1236000.0),
    "residual_joins_that_found_no_candidate": ([
        [residual(0, 16, join_type="anti")]], 0.0),
    "joins_without_a_residual": ([
        [join(join_type="semi", expand_rows=9),
         join(join_type="inner", expand_rows=7)]], None),
    "joins_without_the_counter": ([[join(input_rows=9)]], None),
    "no_join_in_the_window": ([[build(input_rows=5)]], None),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_reader_over_a_hand_built_ring(case, ring):
    statements, want = CASES[case]
    publish(ring, with_operators(           # before the window: not read
        "warm", 90.0, [residual(10 ** 9, 1 << 30)]))
    for i, ops in enumerate(statements):
        publish(ring, with_operators(f"s{i}", 110.0 + 10 * i, ops))
    got = join_residual_rows_per_query.read(facts())
    assert got == (pytest.approx(want) if want is not None else None)


def test_reader_none_when_the_ring_lost_a_statement(ring):
    for i in range(12):                 # capacity 8: the first are gone
        publish(ring, with_operators(f"s{i}", 110.0 + i,
                                     [residual(5, 16)]))
    assert join_residual_rows_per_query.read(facts()) is None


def test_cell_over_a_rehearsed_run(tmp_path):
    """The cell on ``tiny``: the reader reads the program's own spans,
    both subqueries show as a probe of a semi or anti join, the window
    runs under one plan with no compile, off resident pages."""
    cell, = [w for w in BENCH["workloads"] if w["name"] == CELL]
    bench, cell = on_tiny(cell, tmp_path)
    args = argparse.Namespace(seed=4100000447, seconds=1.0, trace=1,
                              rehearse_cpu=True)
    line = run.run_cell(bench, cell, args)
    assert line["correct"] is True
    values = {k: v["value"] for k, v in line["metrics"].items()}
    assert values["plans_in_window"] == 1
    assert values["compiles_in_window"] == 0
    assert values["resident_scan_pct"] == 100.0
    assert values["direct_probe_pct"] == 100.0
    assert values["dyn_filter_table_pct"] == 100.0
    tables = HostTables("tiny")
    lines = tables.row_count("lineitem")
    # the nation's late lines, twice less what the semi join dropped:
    # a few of a hundred suppliers' share of 37,641 late lines
    assert 0 < values["semi_probe_rows_per_query"] < lines // 5
    # every candidate is a line of a probed row's order: 1-7 a row
    assert values["semi_probe_rows_per_query"] \
        < values["join_residual_rows_per_query"] \
        < 7 * values["semi_probe_rows_per_query"]
    # both builds on lineitem, whole and late lines only
    assert lines + lines // 2 < values["join_build_rows_per_query"] \
        < 2 * lines + tables.row_count("orders")


def by_name(entries, name):
    entry, = [e for e in entries if e["name"] == name]
    return entry


def test_benchmark_entries_by_name():
    config = by_name(BENCH["configs"], CONFIG)
    assert config["file"] == f"benchmark/configs/{CONFIG}.json"
    assert config["reduced"] == ["scale", "query_set", "workers"]
    cell = by_name(BENCH["workloads"], CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == \
        (CONFIG, "q21_stream1", 1)
    assert by_name(BENCH["per_layer"], "join_residual_rows_per_query") == {
        "name": "join_residual_rows_per_query", "unit": "rows",
        "better": "lower", "source": "program_counter",
        "layer": "operators", "moves": "rows_per_s", "workloads": [CELL]}
    for name in ("rows_per_s", "query_p50_s"):
        assert CELL in by_name(BENCH["end_to_end"], name)["workloads"]
    listed = {m["name"] for m in BENCH["per_layer"]
              if CELL in m.get("workloads", ())}
    with_q18 = {m["name"] for m in BENCH["per_layer"]
                if "sf1_q18_semijoin" in m.get("workloads", ())}
    assert listed == with_q18 | {"dyn_filter_table_pct",
                                 "join_residual_rows_per_query"}
