"""``join_expand_fill_pct`` over a hand-built ring: the window's match
totals over its expansions' widths, None where no join expanded a page
and None where the program's join spans keep no such counter (the
parent of the PR that brought it)."""

import json
import os

import pytest

from benchmark.layer_metrics import join_expand_fill_pct
from benchmark.tests.test_span_metrics import (facts, publish, span,
                                               statement)
from trino_tpu.telemetry import tracing

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


@pytest.fixture()
def ring(monkeypatch):
    ring = tracing.TraceRing(capacity=8)
    monkeypatch.setattr(tracing, "RING", ring)
    return ring


def joined(trace, t0, *joins):
    """A served statement with one join operator span per entry of
    ``joins`` (the span's counters)."""
    spans = statement(trace, t0, 0.01, 1.0, 0.05)
    run_span, = [s for s in spans if s["name"] == "statement.run"]
    ops = [span(trace, "LookupJoinOperator", t0 + 0.1 + i / 100,
                t0 + 0.5, run_span["span_id"], rows=10, **attrs)
           for i, attrs in enumerate(joins)]
    return spans[:-1] + ops + spans[-1:]        # the root ends last


CASES = {
    "pages_as_wide_as_their_matches": ([
        [dict(probe_lanes=1 << 18, expand_lanes=1 << 14,
              expand_rows=1 << 14),
         dict(probe_lanes=1 << 14, expand_lanes=1 << 10,
              expand_rows=1 << 10)]] * 2, 100.0),
    "padding_only": ([
        [dict(probe_lanes=23 << 18, expand_lanes=23 << 14,
              expand_rows=323_000),
         dict(probe_lanes=4 << 18, expand_lanes=(3 << 17) + (1 << 13),
              expand_rows=78_560)]],
        100.0 * (323_000 + 78_560) / ((23 << 14) + (3 << 17) + (1 << 13))),
    "sized_from_the_page": ([
        [dict(probe_lanes=1 << 18, expand_lanes=1 << 18,
              expand_rows=13_000)],
        [dict(probe_lanes=1 << 18, expand_lanes=1 << 17,
              expand_rows=15_000)]], 100.0 * 28_000 / (3 << 17)),
    "a_semi_join_answered_by_a_table": ([
        [dict(probe_lanes=1 << 18, expand_lanes=0, expand_rows=0),
         dict(probe_lanes=1 << 18, expand_lanes=64, expand_rows=16)]],
        25.0),
    "nothing_expanded": ([[dict(probe_lanes=1 << 18, expand_lanes=0,
                                expand_rows=0)]], None),
    "joins_without_the_counter": ([[dict(probe_lanes=1 << 18,
                                         input_rows=900), dict()]], None),
    "no_join_in_the_window": ([[]], None),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_join_expand_fill_pct(case, ring):
    statements, want = CASES[case]
    publish(ring, joined("warm", 90.0, dict(expand_lanes=1 << 20,
                                            expand_rows=1)))
    for i, joins in enumerate(statements):
        publish(ring, joined(f"s{i}", 110.0 + 10 * i, *joins))
    got = join_expand_fill_pct.read(facts())
    assert got == (pytest.approx(want) if want is not None else None)


def test_none_when_the_ring_lost_a_statement(ring):
    for i in range(12):                 # capacity 8: the first are gone
        publish(ring, joined(f"s{i}", 110.0 + i, dict(
            expand_lanes=32, expand_rows=20)))
    assert join_expand_fill_pct.read(facts()) is None


def test_per_layer_entry():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    entry, = [m for m in bench["per_layer"]
              if m["name"] == "join_expand_fill_pct"]
    assert entry == {
        "name": "join_expand_fill_pct", "unit": "%", "better": "higher",
        "source": "program_counter", "layer": "kernels",
        "moves": "rows_per_s",
        "workloads": ["sf1_q9_join6", "sf1_q3_join", "sf1_q13_outer",
                      "sf1_q18_semijoin"]}
    cells = {w["name"] for w in bench["workloads"]}
    assert set(entry["workloads"]) <= cells
