"""``exchange_rows_per_query`` and ``exchange_stay_pct`` over a
hand-built ring: the rows a statement's exchanges delivered, and of
them the share that arrived on the device that sent them; None where
the program opens no ``exchange`` span, and for the second also where
its spans carry no ``rows_stayed`` (the parent of the PR that brought
it) or the window delivered no row; the entries in ``BENCHMARK.json``;
and the rehearsal of the three four-chip cells on ``tiny`` that prints
both."""

import argparse
import json
import os

import pytest

from benchmark import run
from benchmark.layer_metrics import (exchange_rows_per_query,
                                     exchange_stay_pct)
from benchmark.tests.test_rehearse import BENCH, on_tiny
from benchmark.tests.test_span_metrics import (facts, publish, ring,  # noqa: F401
                                               span)

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
MESH4 = [w["name"] for w in BENCH["workloads"] if w["chips"] == 4]


def statement(trace, t0, *exchanges):
    """One distributed statement of 2 s from ``t0`` with an ``exchange``
    span per entry of ``exchanges`` (the span's counters)."""
    root = span(trace, "statement", t0, t0 + 2.0)
    task = span(trace, "task", t0, t0 + 2.0, root["span_id"], task=0)
    spans = [span(trace, "exchange", t0 + 0.1 + i / 10, t0 + 0.2 + i / 10,
                  task["span_id"], **attrs)
             for i, attrs in enumerate(exchanges)]
    return [task] + spans + [root]


#: statements of the window -> (rows a statement, stay %)
CASES = {
    "a_uniform_hash_and_one_that_moved_nothing": ([
        [dict(rows_in=4_000, rows=4_000, rows_stayed=1_000),
         dict(rows_in=6_000, rows=6_000, rows_stayed=6_000)],
        [dict(rows_in=2_000, rows=2_000, rows_stayed=500)]],
        6_000.0, 100 * 7_500 / 12_000),
    "an_empty_exchange_counts_nothing": ([
        [dict(rows_in=0, rows=0),
         dict(rows_in=800, rows=800, rows_stayed=200)]], 800.0, 25.0),
    "spans_without_the_counter": ([
        [dict(rows=4_000), dict(rows=6_000)]], 10_000.0, None),
    "only_empty_exchanges": ([[dict(rows_in=0, rows=0)]], 0.0, None),
    "no_exchange_in_the_window": ([[]], None, None),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_rows_and_stay(case, ring):
    statements, rows, stay = CASES[case]
    # warm-up: before the window, must not count
    publish(ring, statement("warm", 90.0, dict(
        rows_in=99_000, rows=99_000, rows_stayed=99_000)))
    for i, exchanges in enumerate(statements):
        publish(ring, statement(f"s{i}", 110.0 + 10 * i, *exchanges))
    f = facts()
    got_rows = exchange_rows_per_query.read(f)
    got_stay = exchange_stay_pct.read(f)
    assert got_rows == (pytest.approx(rows) if rows is not None else None)
    assert got_stay == (pytest.approx(stay) if stay is not None else None)


def test_none_with_tracing_off(ring):
    assert exchange_rows_per_query.read(facts()) is None
    assert exchange_stay_pct.read(facts()) is None


def test_none_when_the_ring_lost_a_statement(ring):
    for i in range(12):                 # capacity 8: the first are gone
        publish(ring, statement(f"s{i}", 110.0 + i, dict(
            rows_in=10, rows=10, rows_stayed=3)))
    assert exchange_rows_per_query.read(facts()) is None
    assert exchange_stay_pct.read(facts()) is None


def test_per_layer_entries():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    by_name = {m["name"]: m for m in bench["per_layer"]}
    for name, unit in (("exchange_rows_per_query.mesh4", "count"),
                       ("exchange_stay_pct.mesh4", "%")):
        assert by_name[name] == {
            "name": name, "unit": unit, "better": "lower",
            "source": "program_counter", "layer": "exchange",
            "moves": "rows_per_s.mesh4", "workloads": MESH4}
    assert len(MESH4) == 3


@pytest.mark.parametrize("cell", [w for w in BENCH["workloads"]
                                  if w["chips"] == 4],
                         ids=lambda w: w["name"])
def test_rehearsed_four_chip_cells_report_both(cell, tmp_path):
    """The four-chip cells on ``tiny`` and four virtual CPU devices:
    the traced line carries both; about a quarter of the rows stay
    under q3's and q1's uniform hashes, over half under q18's plan."""
    bench, cell = on_tiny(cell, tmp_path)
    args = argparse.Namespace(seed=4300000007, seconds=1.0, trace=1,
                              rehearse_cpu=True)
    line = run.run_cell(bench, cell, args)
    assert line["correct"] is True and line["failed"] == 0
    got = {name: line["metrics"][f"{name}.mesh4"]["value"]
           for name in ("exchange_rows_per_query", "exchange_stay_pct")}
    print(cell["name"], got)
    assert got["exchange_rows_per_query"] > 0
    assert 20 < got["exchange_stay_pct"] <= 100
