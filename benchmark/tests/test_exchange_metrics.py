"""The five readers over the distributed runner's ``exchange`` spans and
the root's ``lowerings`` counters (``exchange_s_per_query``,
``exchange_host_pct``, ``exchange_fill_pct``, ``lowerings_per_query``,
``lowering_s_per_query``) over hand-made span lists — None without the
span, the arithmetic with two exchanges, a statement outside the window
left out — and the rehearsal of both four-chip cells on ``tiny`` that
prints their ``.mesh4`` variants."""

import argparse

import pytest

from benchmark import run
from benchmark.tests.test_rehearse import BENCH, on_tiny
from benchmark.tests.test_span_metrics import (facts, publish, read, ring,  # noqa: F401
                                               span)
from trino_tpu.telemetry import tracing

READERS = ("exchange_s_per_query", "exchange_host_pct", "exchange_fill_pct",
           "lowerings_per_query", "lowering_s_per_query")


def statement(trace, t0, exchanges=(), lowerings=None):
    """One distributed statement of 2 s from ``t0``: a root, one task,
    and under it an ``exchange`` span a ``(start, seconds, run_s, rows,
    lane_bytes, bytes_moved)``."""
    root = span(trace, "statement", t0, t0 + 2.0)
    if lowerings:
        root["attrs"].update(lowerings=lowerings[0], lowering_s=lowerings[1])
    task = span(trace, "task", t0, t0 + 2.0, root["span_id"], task=0)
    spans = [task]
    for at, seconds, run_s, rows, lane_bytes, moved in exchanges:
        spans.append(span(trace, "exchange", t0 + at, t0 + at + seconds,
                          task["span_id"], run_s=run_s, rows=rows,
                          lane_bytes=lane_bytes, bytes_moved=moved))
    return spans + [root]


def test_none_without_the_span(ring):
    """A program that opens no ``exchange`` span: the three exchange
    readers say nothing; one that counts lowerings reads 0 for a
    statement that lowered nothing."""
    publish(ring, statement("a", 110.0))
    for name in READERS[:3]:
        assert read(name, facts()) is None
    assert read("lowerings_per_query", facts()) == 0
    assert read("lowering_s_per_query", facts()) == 0


def test_none_where_the_program_counts_no_lowerings(ring, monkeypatch):
    publish(ring, statement("a", 110.0, lowerings=(3, 0.25)))
    monkeypatch.delattr(tracing, "lowering_line")
    assert read("lowerings_per_query", facts()) is None
    assert read("lowering_s_per_query", facts()) is None


def test_none_with_tracing_off(ring):
    for name in READERS:
        assert read(name, facts()) is None


def test_the_arithmetic_with_two_exchanges_and_the_window(ring):
    # warm-up: before the window, ten times the work, must not count
    publish(ring, statement("warm", 90.0,
                            [(0.1, 5.0, 1.0, 10_000, 10, 1_000_000)],
                            lowerings=(400, 30.0)))
    publish(ring, statement(
        "a", 110.0,
        [(0.1, 0.5, 0.1, 1_000, 10, 40_960),      # 0.4 s on the host
         (1.0, 0.3, 0.2, 3_000, 20, 81_920)],     # 0.1 s on the host
        lowerings=(12, 0.5)))
    publish(ring, statement("b", 120.0,
                            [(0.2, 0.2, 0.2, 500, 10, 5_000)]))
    f = facts()
    assert read("exchange_s_per_query", f) == pytest.approx((0.8 + 0.2) / 2)
    assert read("exchange_host_pct", f) == pytest.approx(100 * 0.5 / 1.0)
    assert read("exchange_fill_pct", f) == pytest.approx(
        100 * (10_000 + 60_000 + 5_000) / (40_960 + 81_920 + 5_000))
    assert read("lowerings_per_query", f) == pytest.approx(6.0)
    assert read("lowering_s_per_query", f) == pytest.approx(0.25)


@pytest.mark.parametrize(
    "cell", [w for w in BENCH["workloads"] if w["chips"] == 4],
    ids=lambda w: w["name"])
def test_rehearsed_four_chip_cells_report_the_five(cell, tmp_path):
    """Both four-chip cells on ``tiny`` and four virtual CPU devices:
    the traced line carries the five, each a number."""
    bench, cell = on_tiny(cell, tmp_path)
    args = argparse.Namespace(seed=3900000007, seconds=1.0, trace=1,
                              rehearse_cpu=True)
    line = run.run_cell(bench, cell, args)
    assert line["correct"] is True and line["failed"] == 0
    got = {name: line["metrics"][f"{name}.mesh4"]["value"]
           for name in READERS}
    print(got)
    assert got["exchange_s_per_query"] > 0
    assert 0 < got["exchange_host_pct"] < 100
    assert 0 < got["exchange_fill_pct"] <= 100
    assert got["lowerings_per_query"] >= 0
    assert got["lowering_s_per_query"] >= 0
