"""The q18 template's pieces: ``references/q18.py`` against sqlite on
``tiny`` (at thresholds lowered from the spec's 312-315, which leave no
order there: 842, 68, 12 and 0 orders pass) and against the committed
sqlite answer at SF1 and the validation value, and the two readers the cell
brings, ``agg_merge_lanes_per_query`` and ``semi_probe_rows_per_query``,
over a hand-built ring and over a rehearsed run."""

import argparse
import os

import pytest

from benchmark import run, traffic
from benchmark.layer_metrics import (agg_merge_lanes_per_query,
                                     semi_probe_rows_per_query)
from benchmark.references import q18
from benchmark.references.hosttables import HostTables
from benchmark.tests import sqlite_oracle
from benchmark.tests.test_references import assert_rows
from benchmark.tests.test_rehearse import BENCH, on_tiny
from benchmark.tests.test_span_metrics import (facts, publish, span,
                                               statement)
from trino_tpu.telemetry import tracing

PASSING = {200: 100, 250: 68, 275: 12, 300: 0}     # rows, after the limit


@pytest.fixture(scope="module")
def oracle():
    template = traffic.load_template("q18")
    return template, sqlite_oracle.load("tiny", template.meta["columns"])


@pytest.mark.parametrize("quantity", sorted(PASSING))
def test_reference_equals_sqlite_on_tiny(quantity, oracle):
    template, db = oracle
    inst = traffic.instantiate(template, {"QUANTITY": quantity})
    rows = q18.reference(HostTables("tiny"), dict(inst.params))
    assert len(rows) == PASSING[quantity]
    assert_rows(rows, db.execute(sqlite_oracle.to_sqlite(inst.sql))
                .fetchall())


def test_reference_equals_the_committed_answer_at_sf1(monkeypatch):
    """``tests/sf1_expected.py`` holds sqlite's 58 rows at QUANTITY 300
    (``sf1_validation.json`` is not this template's to extend)."""
    monkeypatch.syspath_prepend(os.path.join(run.ROOT, "tests"))
    from sf1_expected import EXPECTED

    template = traffic.load_template("q18")
    rows = q18.reference(HostTables("sf1"), template.meta["validation"])
    assert len(rows) == 58
    assert_rows(rows, EXPECTED[18])


@pytest.fixture()
def ring(monkeypatch):
    ring = tracing.TraceRing(capacity=8)
    monkeypatch.setattr(tracing, "RING", ring)
    return ring


def with_operators(trace, t0, *operators):
    """A served statement with one operator span per ``(name, attrs)``."""
    spans = statement(trace, t0, 0.01, 1.0, 0.05)
    run_span, = [s for s in spans if s["name"] == "statement.run"]
    ops = [span(trace, name, t0 + 0.1 + i / 100, t0 + 0.5,
                run_span["span_id"], rows=10, **attrs)
           for i, (name, attrs) in enumerate(operators)]
    return spans[:-1] + ops + spans[-1:]        # the root ends last


def agg(**attrs):
    return "HashAggregationOperator", attrs


def join(**attrs):
    return "LookupJoinOperator", attrs


#: reader -> case -> (operator spans per statement of the window, value)
CASES = {
    agg_merge_lanes_per_query: {
        "two_levels_one_merges": ([
            [agg(merge_calls=1, merge_lanes=4194304, groups_out=1500000),
             agg(merge_calls=0, merge_lanes=0, groups_out=5)],
            [agg(merge_calls=2, merge_lanes=2097152),
             agg(merge_calls=0, merge_lanes=0)]], 3145728.0),
        "one_partial_each": ([[agg(merge_calls=0, merge_lanes=0)]], 0.0),
        "aggregations_without_the_counter": ([[agg(), agg()]], None),
        "no_aggregation_in_the_window": ([[join(join_type="inner")]], None),
    },
    semi_probe_rows_per_query: {
        "semi_above_the_joins": ([
            [join(join_type="inner", input_rows=1500000),
             join(join_type="inner", input_rows=6001215),
             join(join_type="semi", input_rows=6001215)]] * 2, 6001215.0),
        "semi_and_anti_both_count": ([
            [join(join_type="semi", input_rows=100),
             join(join_type="anti", input_rows=50)],
            [join(join_type="left", input_rows=70)]], 75.0),
        "joins_of_other_types": ([[join(join_type="inner",
                                        input_rows=9)]], 0.0),
        "joins_without_the_counter": ([[join(probe_pages=3)]], None),
        "no_join_in_the_window": ([[agg(merge_lanes=0)]], None),
    },
}


@pytest.mark.parametrize("reader,case", [
    (reader, case) for reader, cases in CASES.items()
    for case in sorted(cases)], ids=lambda v: v if isinstance(v, str)
    else v.__name__.rsplit(".", 1)[-1])
def test_readers_over_a_hand_built_ring(reader, case, ring):
    statements, want = CASES[reader][case]
    publish(ring, with_operators(           # before the window: not read
        "warm", 90.0, agg(merge_lanes=7), join(join_type="semi",
                                               input_rows=7)))
    for i, ops in enumerate(statements):
        publish(ring, with_operators(f"s{i}", 110.0 + 10 * i, *ops))
    got = reader.read(facts())
    assert got == (pytest.approx(want) if want is not None else None)


def test_readers_over_a_rehearsed_run(tmp_path, monkeypatch):
    """The cell on ``tiny`` with stored pages of 8,192 lanes, so that
    the first level merges its partials: both readers read the program's
    own spans.  (312-315 leaves no order at tiny: the answer is empty,
    the semi join still probes every joined ``lineitem`` row.)"""
    from trino_tpu.connectors import memory

    monkeypatch.setattr(memory, "PAGE_ROWS", 8192)
    cell, = [w for w in BENCH["workloads"]
             if w["name"] == "sf1_q18_semijoin"]
    bench, cell = on_tiny(cell, tmp_path)
    args = argparse.Namespace(seed=2147483659, seconds=1.0, trace=1,
                              rehearse_cpu=True)
    line = run.run_cell(bench, cell, args)
    assert line["correct"] is True
    values = {k: v["value"] for k, v in line["metrics"].items()}
    tables = HostTables("tiny")
    # the merge holds each order's group at least once
    assert values["agg_merge_lanes_per_query"] >= \
        tables.row_count("orders")
    assert values["semi_probe_rows_per_query"] == \
        tables.row_count("lineitem")
    assert values["resident_scan_pct"] == 100.0
    assert values["compiles_in_window"] == 0
