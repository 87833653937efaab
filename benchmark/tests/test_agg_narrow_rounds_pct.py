"""``agg_narrow_rounds_pct`` over a hand-built ring: the narrow rounds'
share of all probe rounds the window's aggregations ran, 0 where every
page resolved in its first round, None where the program's aggregation
spans keep no such counter (the parent of the PR that brought it), where
no probe round ran, and where the window ran no aggregation."""

import pytest

from benchmark.layer_metrics import agg_narrow_rounds_pct
from benchmark.tests.test_q18 import agg, ring, with_operators  # noqa: F401
from benchmark.tests.test_span_metrics import facts, publish


def aggregated(trace, t0, *aggregations):
    """A served statement with one aggregation operator span per entry
    of ``aggregations`` (the span's counters)."""
    return with_operators(trace, t0, *(agg(**a) for a in aggregations))


CASES = {
    "long_chains_run_narrow": ([
        [dict(probe_rounds=223, probe_rounds_narrow=199, merge_lanes=2097152),
         dict(probe_rounds=1, probe_rounds_narrow=0, merge_lanes=0)]] * 2,
        100.0 * 199 / 224),
    "statements_weigh_by_their_rounds": ([
        [dict(probe_rounds=60, probe_rounds_narrow=50)],
        [dict(probe_rounds=20, probe_rounds_narrow=10)]], 75.0),
    "every_page_in_one_round": ([[dict(probe_rounds=24,
                                       probe_rounds_narrow=0)]], 0.0),
    "no_probe_round_ran": ([[dict(probe_rounds=0,
                                  probe_rounds_narrow=0)]], None),
    "aggregations_without_the_counter": ([[dict(merge_lanes=0),
                                           dict()]], None),
    "no_aggregation_in_the_window": ([[]], None),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_agg_narrow_rounds_pct(case, ring):
    statements, want = CASES[case]
    publish(ring, aggregated("warm", 90.0, dict(probe_rounds=9,
                                                probe_rounds_narrow=9)))
    for i, aggregations in enumerate(statements):
        publish(ring, aggregated(f"s{i}", 110.0 + 10 * i, *aggregations))
    got = agg_narrow_rounds_pct.read(facts())
    assert got == (pytest.approx(want) if want is not None else None)


def test_none_when_the_ring_lost_a_statement(ring):
    for i in range(12):                 # capacity 8: the first are gone
        publish(ring, aggregated(f"s{i}", 110.0 + i, dict(
            probe_rounds=4, probe_rounds_narrow=3)))
    assert agg_narrow_rounds_pct.read(facts()) is None
