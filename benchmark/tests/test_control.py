"""The comparison can fail: a perturbed value, the lower-precision
control, and a whole run whose served answers are altered at the runner.

The configurations guarantee "every answer exact", so the control is the
reference itself with its sums accumulated in float32 (or int32), put in
the program's place.  ``control_readings`` is also what was run by hand
at SF1 for PERF.md: ``python -m benchmark.tests.test_control sf1 11 12 13``.

The four-chip configuration also guarantees that every worker's data is
in the answer, so its control is the reference over three quarters of
``lineitem``: one worker's share left out of the exchange.
"""

import argparse
import importlib
import sys

import numpy as np
import pytest

from benchmark import compare, traffic
from benchmark.references.hosttables import HostTables


def control_readings(schema, traffic_name, seed, tables=None):
    """Per instance of the seed's pool: mismatched values of the exact
    reference against itself (sound, 0 by construction of an exact
    comparison) and of the float32- and int32-accumulating controls."""
    tables = tables or HostTables(schema)
    pool = traffic.build_pool(
        traffic.load_json("traffic", traffic_name + ".json"), seed)
    out = []
    for inst in pool:
        ref = importlib.import_module(
            f"benchmark.references.{inst.template.name}")
        ordered = inst.template.meta["ordered"]
        want = ref.reference(tables, dict(inst.params))
        reading = {"instance": inst.key, "limit": 0,
                   "sound": compare.mismatches(want, want, ordered)}
        for acc in (np.float32, np.int32):
            got = ref.reference(tables, dict(inst.params), acc=acc)
            reading[acc.__name__] = compare.mismatches(got, want, ordered)
        out.append(reading)
    return out


@pytest.mark.parametrize("traffic_name",
                         ["q3_stream1", "q1_stream1", "q1-3-6-13_streams8"])
def test_lower_precision_control_fails(traffic_name):
    tables = HostTables("tiny")
    for seed in (1, 2, 3000000019):
        readings = control_readings("tiny", traffic_name, seed, tables)
        assert all(r["sound"] == 0 for r in readings)
        # the control has to fail one of the cell's numbers, not each
        # (q13 returns small counts, which float32 holds exactly)
        # (int32 sums are read too, but wrap only at SF1's magnitudes)
        assert any(r["float32"] > 0 for r in readings), readings


class LostShare:
    """``HostTables`` less one of ``of`` workers' share of ``lineitem``:
    ``by="key"`` drops the rows whose ``l_orderkey`` falls to that worker
    (an exchange partition lost: whole orders vanish), ``by="row"``
    every ``of``-th row (a worker's pages of the scan lost: nearly every
    order loses some of its lines)."""

    def __init__(self, tables, by, lost=3, of=4):
        self.tables = tables
        key = tables.column("lineitem", "l_orderkey")
        share = key if by == "key" else np.arange(len(key))
        self.keep = share % of != lost

    def columns(self, table, names):
        cols = self.tables.columns(table, names)
        return [c[self.keep] for c in cols] if table == "lineitem" else cols


def lost_share_readings(schema, seed, tables=None):
    """Per q3 instance of the seed's pool: mismatched values of the
    reference over ``lineitem`` less a worker's share, by key and by
    row, against the whole reference (limit 0)."""
    from benchmark.references import q3

    tables = tables or HostTables(schema)
    pool = traffic.build_pool(
        traffic.load_json("traffic", "q3_stream1.json"), seed)
    out = []
    for inst in pool:
        want = q3.reference(tables, dict(inst.params))
        out.append({"instance": inst.key, "limit": 0, **{
            by: compare.mismatches(
                q3.reference(LostShare(tables, by), dict(inst.params)),
                want, ordered=True) for by in ("key", "row")}})
    return out


def test_lost_worker_share_is_a_mismatch():
    """q3 answers with ten rows, so a lost exchange partition shows only
    where it held one of the ten (or of those that move up): one of a
    run's instances fails, not each.  A lost share of the scan's rows
    changes nearly every order's revenue and fails every instance."""
    tables = HostTables("tiny")
    readings = [r for seed in (1, 2, 3000000019)
                for r in lost_share_readings("tiny", seed, tables)]
    assert all(r["row"] > 0 for r in readings), readings
    assert any(r["key"] > 0 for r in readings), readings


def test_one_perturbed_value_is_a_mismatch():
    want = [("A", 1, compare.Decimal("2.50")), ("B", 2, None)]
    assert compare.mismatches(list(want), want, ordered=True) == 0
    assert compare.mismatches(list(reversed(want)), want, ordered=False) == 0
    assert compare.mismatches(list(reversed(want)), want, ordered=True) > 0
    bad = [("A", 1, compare.Decimal("2.51")), ("B", 2, None)]
    assert compare.mismatches(bad, want, ordered=True) == 1
    assert compare.mismatches(want[:1], want, ordered=True) == 3
    assert compare.mismatches([("A", 1.0 + 1e-6)], [("A", 1.0)], True) == 1
    assert compare.mismatches([("A", 1.0 + 1e-12)], [("A", 1.0)], True) == 0


def test_run_with_altered_answers_is_not_correct(monkeypatch, capsys):
    """The rest of a run (server, client streams, window, check) driven
    without the look for a chip, with one value of every fourth answer
    altered where the runner produces it: ``correct`` comes out false."""
    from benchmark import run

    state = {"n": 0}
    real = run.TimedRunner._timed

    def altered(self, fn, sqls, *args, **kwargs):
        result = real(self, fn, sqls, *args, **kwargs)
        for res in (result if isinstance(result, list) else [result]):
            state["n"] += 1
            if state["n"] % 4 == 0 and getattr(res, "rows", None):
                row = list(res.rows[0])
                row[-1] = row[-1] + 1
                res.rows[0] = tuple(row)
        return result

    monkeypatch.setattr(run.TimedRunner, "_timed", altered)
    args = argparse.Namespace(seed=5, seconds=2.0, trace=0,
                              rehearse_cpu=True)
    import json
    import os

    with open(os.path.join(run.ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cell, = [w for w in bench["workloads"]
             if w["traffic"] == "q1-3-6-13_streams8"]
    line = run.run_cell(bench, cell, args)
    assert line["correct"] is False
    assert line["failed"] == 0 and line["attempted"] > 0


def test_run_with_a_lost_exchange_partition_is_not_correct(monkeypatch,
                                                          tmp_path):
    """The four-chip cell's run on tiny (four virtual CPU devices), with
    the exchange broken underneath: the consumer of partition 3 is
    handed no pages, so one worker's share never reaches the join.
    ``correct`` comes out false."""
    from benchmark import run
    from benchmark.tests.test_rehearse import BENCH, on_tiny
    from trino_tpu.parallel.device_exchange import DeviceExchange

    real = DeviceExchange.pages
    monkeypatch.setattr(
        DeviceExchange, "pages",
        lambda self, partition: [] if partition == 3
        else real(self, partition))
    cell, = [w for w in BENCH["workloads"] if w["chips"] == 4]
    bench, cell = on_tiny(cell, tmp_path)
    args = argparse.Namespace(seed=5, seconds=2.0, trace=0,
                              rehearse_cpu=True)
    line = run.run_cell(bench, cell, args)
    assert line["correct"] is False
    assert line["failed"] == 0 and line["attempted"] > 0


if __name__ == "__main__":
    schema, seeds = sys.argv[1], [int(s) for s in sys.argv[2:]]
    shared = HostTables(schema)
    for name in ("q3_stream1", "q1_stream1", "q1-3-6-13_streams8"):
        for seed in seeds:
            for r in control_readings(schema, name, seed, shared):
                print(name, seed, r)
    for seed in seeds:
        for r in lost_share_readings(schema, seed, shared):
            print("lost share of lineitem", seed, r)
