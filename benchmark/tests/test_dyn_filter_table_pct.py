"""``dyn_filter_table_pct`` over a hand-built ring: 100 where every
value set a scan tested was a membership table, the applications' share
where a filter fell back to the search, None where no scan tested a
value set (min / max filters only, or no filter) and None where the
program's scan spans keep no such counter (the parent of the PR that
brought it)."""

import json
import os

import pytest

from benchmark.layer_metrics import dyn_filter_table_pct
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


def scanned(trace, t0, *scans):
    """A served statement with one scan operator span per entry of
    ``scans`` (the span's counters)."""
    spans = statement(trace, t0, 0.01, 1.0, 0.05)
    run_span, = [s for s in spans if s["name"] == "statement.run"]
    ops = [span(trace, "TableScanOperator", t0 + 0.1 + i / 100,
                t0 + 0.5, run_span["span_id"], rows=10, **attrs)
           for i, attrs in enumerate(scans)]
    return spans[:-1] + ops + spans[-1:]        # the root ends last


CASES = {
    "every_set_a_table": ([
        [dict(resident_bytes=1 << 20, df_member_pages=23,
              df_table_pages=23),
         dict(resident_bytes=1 << 18, df_member_pages=8, df_table_pages=8),
         dict(resident_bytes=1 << 10, df_member_pages=0,
              df_table_pages=0)]] * 2, 100.0),
    "one_filter_searched": ([
        [dict(df_member_pages=12, df_table_pages=12),
         dict(df_member_pages=4, df_table_pages=0)],
        [dict(df_member_pages=4, df_table_pages=4)]], 80.0),
    "no_set_a_table": ([[dict(df_member_pages=5, df_table_pages=0)]], 0.0),
    "min_max_filters_only": ([[dict(df_member_pages=0, df_table_pages=0),
                               dict(df_member_pages=0,
                                    df_table_pages=0)]], None),
    "scans_without_the_counter": ([[dict(resident_bytes=1 << 20),
                                    dict()]], None),
    "no_scan_in_the_window": ([[]], None),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_dyn_filter_table_pct(case, ring):
    statements, want = CASES[case]
    publish(ring, scanned("warm", 90.0, dict(df_member_pages=9,
                                             df_table_pages=0)))
    for i, scans in enumerate(statements):
        publish(ring, scanned(f"s{i}", 110.0 + 10 * i, *scans))
    got = dyn_filter_table_pct.read(facts())
    assert got == (pytest.approx(want) if want is not None else None)


def test_none_when_the_ring_lost_a_statement(ring):
    for i in range(12):                 # capacity 8: the first are gone
        publish(ring, scanned(f"s{i}", 110.0 + i, dict(
            df_member_pages=1, df_table_pages=1)))
    assert dyn_filter_table_pct.read(facts()) is None


def test_per_layer_entry():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    entry, = [m for m in bench["per_layer"]
              if m["name"] == "dyn_filter_table_pct"]
    assert entry == bench["per_layer"][-1] == {
        "name": "dyn_filter_table_pct", "unit": "%", "better": "higher",
        "source": "program_counter", "layer": "kernels",
        "moves": "rows_per_s",
        "workloads": ["sf1_q9_join6", "sf1_q3_join"]}
