"""The six span readers (``queue_ms``, ``deliver_ms``, ``planning_ms``,
``scan_host_s_per_query``, ``host_syncs_per_query``,
``host_sync_s_per_query``) over a synthetic ``RunFacts`` and a hand-built
ring — window selection, batch members, None on eviction and on tracing
off — and the CPU rehearsal that prints their ``.concurrent`` variants."""

import importlib
import json
import os
import subprocess
import sys

import pytest

from benchmark import run, span_facts
from trino_tpu.telemetry import tracing

READERS = ("queue_ms", "deliver_ms", "planning_ms",
           "scan_host_s_per_query", "host_syncs_per_query",
           "host_sync_s_per_query")


def read(name, facts):
    return importlib.import_module(
        f"benchmark.layer_metrics.{name}").read(facts)


def span(trace, name, t0, t1, parent=None, **attrs):
    return {"trace_id": trace, "span_id": f"{trace}/{name}/{t0}",
            "parent_id": parent, "name": name, "t0": t0, "t1": t1,
            "start": t0, "end": t1, "attrs": attrs}


def statement(trace, t0, queued, run_s, deliver, plan_ms=(1.0, 2.0, 0.5, 4.0),
              scan=(0.25, 0.05), syncs=(3, 0.01), batch=None):
    """One served statement: submit at ``t0``, then ``queued``, ``run_s``
    and ``deliver`` seconds; its planning spans under ``statement.run``
    unless it rode in a batch's shared work."""
    root = span(trace, "statement", t0, t0 + queued + run_s + deliver)
    if syncs:
        root["attrs"].update(host_syncs=syncs[0], host_sync_s=syncs[1])
    rid = root["span_id"]
    run_attrs = {"batch": batch} if batch else {}
    t_run = t0 + queued
    run_span = span(trace, "statement.run", t_run, t_run + run_s, rid,
                    **run_attrs)
    spans = [span(trace, "statement.queued", t0, t_run, rid), run_span,
             span(trace, "statement.deliver", t_run + run_s,
                  t_run + run_s + deliver, rid)]
    t = t_run
    for name, ms in zip(span_facts.PLANNING, plan_ms or ()):
        spans.append(span(trace, name, t, t + ms / 1e3,
                          run_span["span_id"]))
        t += ms / 1e3
    if scan:
        spans.append(span(trace, "TableScanOperator", t, t + 0.1,
                          run_span["span_id"], generate_s=scan[0],
                          upload_s=scan[1]))
    return spans + [root]               # the root ends last


@pytest.fixture()
def ring(monkeypatch):
    ring = tracing.TraceRing(capacity=8)
    monkeypatch.setattr(tracing, "RING", ring)
    return ring


def publish(ring, spans):
    ring.publish(spans, spans[-1]["t1"])


def facts(t_open=100.0, t_close=200.0):
    return run.RunFacts(window_open=t_open, window_close=t_close)


def test_window_selection(ring):
    publish(ring, statement("warm", 90.0, 0.5, 1.0, 0.5))     # before
    publish(ring, statement("edge", 99.5, 0.2, 1.0, 0.1))     # straddles
    publish(ring, statement("a", 110.0, 0.010, 1.0, 0.050))
    publish(ring, statement("b", 120.0, 0.030, 1.0, 0.070,
                            plan_ms=(2.0, 4.0, 1.0, 8.0),
                            scan=(0.45, 0.15), syncs=(5, 0.03)))
    publish(ring, statement("after", 199.5, 0.1, 1.0, 0.1))   # explain
    f = facts()
    assert read("queue_ms", f) == pytest.approx(20.0)
    assert read("deliver_ms", f) == pytest.approx(60.0)
    assert read("planning_ms", f) == pytest.approx((7.5 + 15.0) / 2)
    assert read("scan_host_s_per_query", f) == pytest.approx(0.45)
    assert read("host_syncs_per_query", f) == pytest.approx(4.0)
    assert read("host_sync_s_per_query", f) == pytest.approx(0.02)


def test_batch_members_get_their_share(ring):
    # two members vmapped in one batch: planning, scan and syncs are the
    # batch's; a third statement served alone
    batch = [span("B", "parse", 110.0, 110.001, "B/batch.run/110.0"),
             span("B", "plan", 110.001, 110.004, "B/batch.run/110.0"),
             span("B", "local_plan", 110.004, 110.010,
                  "B/batch.run/110.0"),
             span("B", "execute", 110.010, 110.5, "B/batch.run/110.0",
                  generate_s=0.2, upload_s=0.1),
             span("B", "batch.run", 110.0, 110.6, batch_size=2,
                  host_syncs=8, host_sync_s=0.04)]
    bid = batch[-1]["span_id"]
    publish(ring, batch)
    for trace in ("m1", "m2"):
        publish(ring, statement(trace, 109.9, 0.1, 0.6, 0.05, plan_ms=None,
                                scan=None, syncs=None, batch=bid))
    publish(ring, statement("solo", 130.0, 0.0, 1.0, 0.05,
                            plan_ms=(1.0, 1.0, 1.0, 1.0), scan=(0.3, 0.0),
                            syncs=(2, 0.01)))
    f = facts()
    # members: (1 + 3 + 6) / 2 = 5 ms each; solo: 4 ms -> median 5
    assert read("planning_ms", f) == pytest.approx(5.0)
    assert read("scan_host_s_per_query", f) == \
        pytest.approx((0.15 + 0.15 + 0.3) / 3)
    assert read("host_syncs_per_query", f) == pytest.approx((4 + 4 + 2) / 3)
    assert read("host_sync_s_per_query", f) == \
        pytest.approx((0.02 + 0.02 + 0.01) / 3)
    assert read("queue_ms", f) == pytest.approx(100.0)


@pytest.mark.parametrize("name", READERS)
def test_none_when_the_ring_lost_a_statement_of_the_window(ring, name):
    for i in range(ring.capacity + 3):
        publish(ring, statement(f"s{i}", 101.0 + i, 0.01, 0.5, 0.05))
    assert read(name, facts()) is None
    # what was lost ended before a later window opened: that one reads
    assert read(name, facts(t_open=105.0)) is not None


@pytest.mark.parametrize("name", READERS)
def test_none_with_tracing_off_or_no_ring(ring, name, monkeypatch):
    assert read(name, facts()) is None          # nothing was published
    monkeypatch.delattr(tracing, "RING")        # the parent's program
    assert read(name, facts()) is None


def test_rehearsal_prints_the_six_concurrent_metrics():
    proc = subprocess.run(
        [sys.executable, os.path.join(run.HERE, "run.py"), "--workload",
         "tiny_streams8", "--rehearse-cpu", "--trace", "1", "--seconds",
         "5", "--seed", "2147483659"],
        capture_output=True, text=True, timeout=900)
    assert proc.returncode == 0, proc.stderr[-2000:]
    line = json.loads(proc.stdout.strip().splitlines()[-1])
    assert line["correct"] is True
    for name in READERS:
        assert line["metrics"][name + ".concurrent"]["value"] >= 0
    m = {k: v["value"] for k, v in line["metrics"].items()}
    # the two waits outside the runner call make up the protocol's lump
    assert m["queue_ms.concurrent"] + m["deliver_ms.concurrent"] \
        <= m["protocol_ms.concurrent"] * 1.5
    assert m["planning_ms.concurrent"] < m["plan_ms.concurrent"]
    assert m["host_syncs_per_query.concurrent"] > 1
