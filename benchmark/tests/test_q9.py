"""The q9 template's pieces: ``references/q9.py`` against sqlite on
``tiny`` at the validation colour and two others, and against the
committed sqlite answer at SF1; the three readers the cell brings
(``join_probe_fill_pct``, ``join_build_rows_per_query``,
``plans_in_window``) over a hand-built ring and over a rehearsed run."""

import argparse
import os

import pytest

from benchmark import run, traffic
from benchmark.layer_metrics import (join_build_rows_per_query,
                                     join_probe_fill_pct, plans_in_window)
from benchmark.references import q9
from benchmark.references.hosttables import HostTables
from benchmark.tests import sqlite_oracle
from benchmark.tests.test_references import assert_rows
from benchmark.tests.test_rehearse import BENCH, on_tiny
from benchmark.tests.test_span_metrics import (facts, publish, span,
                                               statement)
from trino_tpu.telemetry import tracing

GROUPS = {"green": 175, "red": 175, "almond": 173}


def in_sqlite(sql):
    """sqlite has no ``extract``; dates are ISO text there."""
    return sqlite_oracle.to_sqlite(sql).replace(
        "extract(year from o_orderdate)",
        "cast(substr(o_orderdate, 1, 4) as integer)")


@pytest.fixture(scope="module")
def oracle():
    template = traffic.load_template("q9")
    return template, sqlite_oracle.load("tiny", template.meta["columns"])


@pytest.mark.parametrize("color", sorted(GROUPS))
def test_reference_equals_sqlite_on_tiny(color, oracle):
    template, db = oracle
    inst = traffic.instantiate(template, {"COLOR": color})
    rows = q9.reference(HostTables("tiny"), dict(inst.params))
    assert len(rows) == GROUPS[color]
    assert_rows(rows, db.execute(in_sqlite(inst.sql)).fetchall())


def test_reference_equals_the_committed_answer_at_sf1(monkeypatch):
    """``tests/sf1_expected.py`` holds sqlite's 175 rows at ``green``."""
    monkeypatch.syspath_prepend(os.path.join(run.ROOT, "tests"))
    from sf1_expected import EXPECTED

    template = traffic.load_template("q9")
    rows = q9.reference(HostTables("sf1"), template.meta["validation"])
    assert len(rows) == 175
    assert_rows(rows, EXPECTED[9])


@pytest.fixture()
def ring(monkeypatch):
    ring = tracing.TraceRing(capacity=8)
    monkeypatch.setattr(tracing, "RING", ring)
    return ring


def with_operators(trace, t0, operators, **root_attrs):
    """A served statement with one operator span per ``(name, attrs)``
    and ``root_attrs`` on its root."""
    spans = statement(trace, t0, 0.01, 1.0, 0.05)
    spans[-1]["attrs"].update(root_attrs)
    run_span, = [s for s in spans if s["name"] == "statement.run"]
    ops = [span(trace, name, t0 + 0.1 + i / 100, t0 + 0.5,
                run_span["span_id"], rows=10, **attrs)
           for i, (name, attrs) in enumerate(operators)]
    return spans[:-1] + ops + spans[-1:]        # the root ends last


def build(**attrs):
    return "HashBuilderOperator", attrs


def join(**attrs):
    return "LookupJoinOperator", attrs


#: reader -> case -> ([(operator spans, root attrs)] per statement, value)
CASES = {
    join_probe_fill_pct: {
        "masked_pages_through_two_joins": ([
            ([join(input_rows=323326, probe_lanes=6029312),
              join(input_rows=323326, probe_lanes=2326528)], {})] * 2,
            100.0 * 646652 / 8355840),
        "full_pages": ([([join(input_rows=262144, probe_lanes=262144)],
                         {})], 100.0),
        "a_matmul_join_counts_too": ([
            ([("MatmulJoinOperator", dict(input_rows=10, probe_lanes=40)),
              join(input_rows=30, probe_lanes=40)], {})], 50.0),
        "joins_without_the_counter": ([([join(input_rows=9)], {})], None),
        "no_join_in_the_window": ([([build(input_rows=5)], {})], None),
    },
    join_build_rows_per_query: {
        "the_settled_plan": ([
            ([build(input_rows=r, key_mode=m, build_lanes=2 * r)
              for r, m in ((25, "single"), (10755, "single"),
                           (10000, "single"), (323326, "hashed"),
                           (323326, "single"))], {})] * 3, 667432.0),
        "a_statement_that_built_on_the_fact_table": ([
            ([build(input_rows=6005405, key_mode="single")], {}),
            ([build(input_rows=323326, key_mode="single")], {})],
            3164365.5),
        "builds_without_the_counter": ([([build(input_rows=7)], {})],
                                       None),
        "no_build_in_the_window": ([([join(input_rows=7)], {})], None),
    },
    plans_in_window: {
        "one_plan": ([([], dict(plan_fp="a", shape_fp="s"))] * 3, 1),
        "the_plan_moved": ([([], dict(plan_fp=p, shape_fp="s"))
                            for p in "aaba"], 2),
        "two_templates_one_plan_each": ([
            ([], dict(plan_fp=p, shape_fp=s))
            for p, s in (("a", "s"), ("b", "t"), ("a", "s"))], 1),
        "the_largest_over_templates": ([
            ([], dict(plan_fp=p, shape_fp=s))
            for p, s in (("a", "s"), ("b", "t"), ("c", "t"),
                         ("d", "t"))], 3),
        "roots_without_the_fingerprint": ([([], {})] * 2, None),
    },
}


@pytest.mark.parametrize("reader,case", [
    (reader, case) for reader, cases in CASES.items()
    for case in sorted(cases)], ids=lambda v: v if isinstance(v, str)
    else v.__name__.rsplit(".", 1)[-1])
def test_readers_over_a_hand_built_ring(reader, case, ring):
    statements, want = CASES[reader][case]
    publish(ring, with_operators(           # before the window: not read
        "warm", 90.0, [join(input_rows=1, probe_lanes=1000),
                       build(input_rows=10 ** 9, key_mode="single")],
        plan_fp="cold", shape_fp="s"))
    for i, (ops, root_attrs) in enumerate(statements):
        publish(ring, with_operators(f"s{i}", 110.0 + 10 * i, ops,
                                     **root_attrs))
    got = reader.read(facts())
    assert got == (pytest.approx(want) if want is not None else None)


def test_readers_over_a_rehearsed_run(tmp_path):
    """The cell on ``tiny``: the three readers read the program's own
    spans, the two-column key shows in ``direct_probe_pct`` and the
    window runs under one plan, the one warm-up settled on."""
    cell, = [w for w in BENCH["workloads"] if w["name"] == "sf1_q9_join6"]
    bench, cell = on_tiny(cell, tmp_path)
    args = argparse.Namespace(seed=4100000447, seconds=1.0, trace=1,
                              rehearse_cpu=True)
    line = run.run_cell(bench, cell, args)
    assert line["correct"] is True
    values = {k: v["value"] for k, v in line["metrics"].items()}
    assert values["plans_in_window"] == 1
    assert values["compiles_in_window"] == 0
    assert values["resident_scan_pct"] == 100.0
    assert 0 < values["direct_probe_pct"] < 100
    assert 0 < values["join_probe_fill_pct"] < 50
    tables = HostTables("tiny")
    # the settled plan builds on neither fact table nor orders/partsupp
    assert 0 < values["join_build_rows_per_query"] < \
        tables.row_count("partsupp")
