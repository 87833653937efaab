"""``direct_probe_pct`` over a hand-built ring: 100 where every join's
probe pages came from the direct-address table, the pages' share where a
build fell back, None where the program's join spans keep no such counter
(the parent of the PR that brought it) and None where the window ran no
join."""

import pytest

from benchmark.layer_metrics import direct_probe_pct
from benchmark.tests.test_span_metrics import (facts, publish, span,
                                               statement)
from trino_tpu.telemetry import tracing


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
    "every_page_direct": ([
        [dict(probe_pages=12, direct_probe_pages=12,
              direct_table_bytes=33554432),
         dict(probe_pages=3, direct_probe_pages=3,
              direct_table_bytes=1048576)]] * 2, 100.0),
    "one_build_fell_back": ([
        [dict(probe_pages=12, direct_probe_pages=12,
              direct_table_bytes=33554432),
         dict(probe_pages=4, direct_probe_pages=0,
              probe_fallback="hashed key mode")],
        [dict(probe_pages=4, direct_probe_pages=4,
              direct_table_bytes=1048576)]], 80.0),
    "no_page_direct": ([[dict(probe_pages=5, direct_probe_pages=0,
                              probe_fallback="float key")]], 0.0),
    "joins_without_the_counter": ([[dict(), dict()]], None),
    "no_join_in_the_window": ([[]], None),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_direct_probe_pct(case, ring):
    statements, want = CASES[case]
    publish(ring, joined("warm", 90.0, dict(probe_pages=9,
                                            direct_probe_pages=0)))
    for i, joins in enumerate(statements):
        publish(ring, joined(f"s{i}", 110.0 + 10 * i, *joins))
    got = direct_probe_pct.read(facts())
    assert got == (pytest.approx(want) if want is not None else None)


def test_none_when_the_ring_lost_a_statement(ring):
    for i in range(12):                 # capacity 8: the first are gone
        publish(ring, joined(f"s{i}", 110.0 + i, dict(
            probe_pages=1, direct_probe_pages=1)))
    assert direct_probe_pct.read(facts()) is None
