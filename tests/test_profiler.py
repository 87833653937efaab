"""Compiled-program profiler: registry core, EXPLAIN ANALYZE VERBOSE,
system.runtime.kernels, query progress, OTLP export, and the slow-query
log."""

import json
import threading
import time

import jax
import jax.numpy as jnp
import pytest

from trino_tpu import jit_stats
from trino_tpu.connectors.tpch import TpchConnector
from trino_tpu.runner import LocalQueryRunner, QueryResult
from trino_tpu.sql.analyzer import Session
from trino_tpu.telemetry import profiler
from trino_tpu.telemetry.profiler import instrument


@pytest.fixture(autouse=True)
def _profiler_off():
    """Every test leaves the process-global profiler disabled — other
    suites assert zero-overhead behavior."""
    yield
    profiler.enable(False)


@pytest.fixture(scope="module")
def local_runner():
    return LocalQueryRunner({"tpch": TpchConnector(page_rows=4096)},
                            Session(catalog="tpch", schema="micro"))


# -- registry core ---------------------------------------------------------


def _fresh_kernel(name):
    from functools import partial

    @partial(jax.jit, static_argnames=("n",))
    def f(x, y, n):
        return (x * 2.0 + y).reshape(n, -1)

    return instrument(name, f, static_argnames=("n",))


def _entries_for(name):
    return [e for e in profiler.snapshot() if e["name"] == name]


def test_costs_recorded_once_per_compile():
    """One registry entry per (name, signature); repeat shapes execute
    the stored program — compiles stays 1 while calls grow — and the
    recorded compile wall / cost analysis are non-trivial."""
    f = _fresh_kernel("t_registry_core")
    x = jnp.arange(8, dtype=jnp.float32)
    y = jnp.ones(8, dtype=jnp.float32)
    profiler.enable()
    try:
        r1 = f(x, y, n=2)
        r2 = f(x, y, n=2)
        assert (jnp.asarray(r1) == jnp.asarray(r2)).all()
        entries = _entries_for("t_registry_core")
        assert len(entries) == 1
        e = entries[0]
        assert e["compiles"] == 1 and e["calls"] == 2
        assert e["compile_ms"] > 0 and e["trace_ms"] > 0
        assert e["flops"] > 0
        assert e["bytes_accessed"] > 0
        assert e["fallbacks"] == 0
        # a new static value is a DIFFERENT program -> second entry
        f(x, y, n=4)
        assert len(_entries_for("t_registry_core")) == 2
        # a new shape too
        f(jnp.arange(16, dtype=jnp.float32),
          jnp.ones(16, dtype=jnp.float32), n=2)
        assert len(_entries_for("t_registry_core")) == 3
    finally:
        profiler.enable(False)


def test_dynamic_python_scalar_does_not_fragment_registry():
    """A weak-typed python scalar argument keys by type, not value —
    jax compiles one program for it and so must the registry."""

    @jax.jit
    def g(x, s):
        return x * s

    w = instrument("t_weak_scalar", g)
    x = jnp.arange(4, dtype=jnp.float32)
    profiler.enable()
    try:
        assert float(w(x, 2.0)[2]) == 4.0
        assert float(w(x, 3.5)[2]) == 7.0
        assert len(_entries_for("t_weak_scalar")) == 1
        assert _entries_for("t_weak_scalar")[0]["compiles"] == 1
    finally:
        profiler.enable(False)


def test_profiling_off_is_zero_cost():
    """Disabled, the wrapper adds no registry entries, no extra jit
    traces, and only trivial call overhead over the bare jit product."""
    f = _fresh_kernel("t_zero_overhead")
    x = jnp.arange(8, dtype=jnp.float32)
    y = jnp.ones(8, dtype=jnp.float32)
    t0 = jit_stats.total_for("nonexistent")  # keep import honest
    assert t0 == 0
    before_traces = jit_stats.thread_total()
    f(x, y, n=2)  # first call traces once, exactly like bare jit
    assert jit_stats.thread_total() == before_traces
    # (the test kernel has no bump; assert via the registry instead)
    assert _entries_for("t_zero_overhead") == []
    # repeat calls: no traces, no registry, and dispatch wall within a
    # small factor of the bare jitted callable
    jitted = f.jit
    n = 300
    t0 = time.perf_counter()
    for _ in range(n):
        jitted(x, y, n=2)
    bare = time.perf_counter() - t0
    t0 = time.perf_counter()
    for _ in range(n):
        f(x, y, n=2)
    wrapped = time.perf_counter() - t0
    assert _entries_for("t_zero_overhead") == []
    # generous bound: the off-path is one attribute check; anything
    # past 5x bare dispatch means profiling leaked into the hot path
    assert wrapped < bare * 5 + 0.05, (wrapped, bare)


def test_profiling_scopes_refcount():
    """A plain query's no-op scope exiting must not clobber a profiled
    scope still running on another thread (the scopes refcount)."""
    plain = profiler.profiling(False)
    verbose = profiler.profiling(True)
    plain.__enter__()
    verbose.__enter__()
    plain.__exit__(None, None, None)
    assert profiler.enabled(), "no-op scope exit disabled profiling"
    verbose.__exit__(None, None, None)
    assert not profiler.enabled()
    # sticky manual enable survives scope exits
    profiler.enable()
    scope = profiler.profiling(True)
    scope.__enter__()
    scope.__exit__(None, None, None)
    assert profiler.enabled()
    profiler.enable(False)
    assert not profiler.enabled()


def test_tracer_arguments_bypass_profiling():
    """A profiled kernel invoked inside another trace stages out
    inline — nothing recorded, results exact."""
    inner = _fresh_kernel("t_tracer_bypass")

    @jax.jit
    def outer(x, y):
        return inner(x, y, n=2).sum()

    profiler.enable()
    try:
        out = outer(jnp.arange(8, dtype=jnp.float32),
                    jnp.ones(8, dtype=jnp.float32))
        assert float(out) == float((jnp.arange(8) * 2.0 + 1).sum())
        assert _entries_for("t_tracer_bypass") == []
    finally:
        profiler.enable(False)


# -- EXPLAIN ANALYZE VERBOSE ----------------------------------------------


def _explain_text(res):
    return "\n".join(r[0] for r in res.rows)


def test_explain_analyze_verbose_local(local_runner):
    sql = ("explain analyze verbose select l_returnflag, "
           "sum(l_quantity) q from lineitem group by l_returnflag")
    text = _explain_text(local_runner.execute(sql))
    assert "flops" in text and "compile" in text
    assert "Kernels:" in text
    # plain EXPLAIN ANALYZE stays cost-free (profiler off)
    plain = _explain_text(local_runner.execute(
        "explain analyze select count(*) from region"))
    assert "Kernels:" not in plain


@pytest.fixture(scope="module")
def dist_runner():
    from trino_tpu.parallel.distributed import DistributedQueryRunner

    return DistributedQueryRunner(
        {"tpch": TpchConnector(page_rows=4096)},
        Session(catalog="tpch", schema="micro"),
        n_workers=2, desired_splits=4, broadcast_threshold=300.0)


@pytest.mark.parametrize("qid", [1, 3])
def test_explain_analyze_verbose_distributed(dist_runner, qid):
    """The acceptance surface: EXPLAIN ANALYZE VERBOSE on q1/q3
    distributed shows per-operator flops/bytes/compile-ms, and a
    repeat-shape run adds ZERO new compile entries."""
    from trino_tpu.resources.tpch_queries import TPCH_QUERIES

    sql = "explain analyze verbose " + TPCH_QUERIES[qid]
    text = _explain_text(dist_runner.execute(sql))
    assert "[cost " in text and "flops" in text, text
    assert "compile" in text
    assert "Kernels:" in text
    before = profiler.totals()
    res2 = dist_runner.execute(sql)
    text2 = _explain_text(res2)
    after = profiler.totals()
    # what the second run lowered, by program name, and how each
    # exchange was sized: the statement's own record of what it built
    spans = res2.stats["trace"]
    root, = [s for s in spans if s["parent_id"] is None]
    lowered = {name: row[0] for name, row in root["attrs"].get(
        "lowerings_by_program", {}).items() if row[2] or row[3]}
    sized = [(s["attrs"]["fragment"], s["attrs"]["cap"],
              s["attrs"]["per_dest"], s["attrs"]["sizing_used"],
              s["attrs"]["lowered"])
             for s in spans if s["name"] == "exchange"]
    assert after["compiles"] == before["compiles"], (
        f"repeat-shape VERBOSE run recompiled: lowered {lowered}; "
        f"exchanges (fragment, cap, per_dest, sizing, lowered) {sized}")
    assert "0 new, 0 compiles this run" in text2, text2


def test_system_runtime_kernels_sql(local_runner):
    # VERBOSE above populated the registry; the catalog serves it
    res = local_runner.execute(
        "select name, compiles, compile_ms, flops from "
        "system.runtime.kernels")
    assert res.rows, "kernels table empty after a profiled run"
    names = {r[0] for r in res.rows}
    assert "page_processor" in names
    for _name, compiles, compile_ms, _flops in res.rows:
        assert compiles >= 1
        assert compile_ms >= 0.0


# -- query progress --------------------------------------------------------


def test_progress_monotonic_unit():
    from trino_tpu.telemetry.progress import QueryProgress

    p = QueryProgress("q1", total_rows=100)
    seen = [p.fraction()]
    for _ in range(12):
        p.add_rows(17)  # overshoots the estimate deliberately
        seen.append(p.fraction())
    assert seen == sorted(seen), "progress moved backwards"
    assert seen[-1] == 1.0
    p.state = "FINISHED"
    assert p.fraction() == 1.0
    d = p.to_dict()
    assert d["rows_scanned"] == 204 and d["total_rows_estimate"] == 100


def test_progress_fed_by_execution(local_runner):
    from trino_tpu.telemetry.progress import QueryProgress

    p = QueryProgress("t_exec")
    res = local_runner.execute(
        "select count(*) from lineitem", progress=p)
    assert res.rows[0][0] > 0
    assert p.state == "FINISHED"
    assert p.rows_scanned > 0
    assert p.total_rows > 0, "connector statistics estimate missing"
    assert p.tasks_done == p.tasks_total > 0
    assert p.fraction() == 1.0


def test_protocol_live_query_info_serves_partial_stats():
    """GET /v1/query/{id} on a RUNNING query returns live state +
    progress instead of the old stats:null placeholder."""
    from trino_tpu.server.protocol import ProtocolServer

    started = threading.Event()
    release = threading.Event()

    class StubRunner:
        session = None

        def execute(self, sql, user=None, progress=None):
            if progress is not None:
                progress.state = "RUNNING"
                progress.total_rows = 10
                progress.add_rows(4)
            started.set()
            assert release.wait(10)
            return QueryResult(["c"], [], [(1,)])

    server = ProtocolServer(StubRunner(), port=0)
    try:
        doc = server.submit("select 1")
        qid = doc["id"]
        assert started.wait(10)
        info = server.query_info(qid)
        assert info["state"] in ("QUEUED", "RUNNING")
        assert info["stats"] is not None, "live query served no stats"
        assert info["stats"]["elapsed_ms"] >= 0
        prog = info["stats"]["progress"]
        assert prog["rows_scanned"] == 4
        assert prog["fraction"] == pytest.approx(0.4)
        release.set()
        deadline = time.time() + 10
        while time.time() < deadline:
            info = server.query_info(qid)
            if info and info.get("state") == "FINISHED":
                break
            time.sleep(0.02)
        assert info["state"] == "FINISHED"
    finally:
        release.set()
        server.stop()


# -- OTLP export -----------------------------------------------------------


class _FakeCollector:
    """Stdlib OTLP collector: captures POSTed bodies."""

    def __init__(self):
        from http.server import (BaseHTTPRequestHandler,
                                 ThreadingHTTPServer)

        outer = self

        class Handler(BaseHTTPRequestHandler):
            def log_message(self, fmt, *args):
                pass

            def do_POST(self):
                n = int(self.headers.get("Content-Length", 0))
                outer.bodies.append(json.loads(self.rfile.read(n)))
                self.send_response(200)
                self.send_header("Content-Length", "2")
                self.end_headers()
                self.wfile.write(b"{}")

        self.bodies = []
        self.httpd = ThreadingHTTPServer(("127.0.0.1", 0), Handler)
        self.endpoint = (f"http://127.0.0.1:"
                         f"{self.httpd.server_address[1]}/v1/traces")
        threading.Thread(target=self.httpd.serve_forever,
                         daemon=True).start()

    def stop(self):
        self.httpd.shutdown()
        self.httpd.server_close()


def _spans():
    from trino_tpu.telemetry.tracing import Tracer

    t = Tracer(process="coordinator")
    with t.span("query") as root:
        with t.span("plan", parent=root):
            pass
    return t.finished()


def test_otlp_export_to_fake_collector():
    from trino_tpu.telemetry.tracing import export_otlp

    collector = _FakeCollector()
    try:
        spans = _spans()
        assert export_otlp(collector.endpoint, spans) is True
        assert len(collector.bodies) == 1
        body = collector.bodies[0]
        rs = body["resourceSpans"]
        otlp_spans = [s for r in rs
                      for sc in r["scopeSpans"] for s in sc["spans"]]
        assert len(otlp_spans) == len(spans)
        for s in otlp_spans:
            assert len(s["traceId"]) == 32
            assert len(s["spanId"]) == 16
            assert int(s["endTimeUnixNano"]) >= \
                int(s["startTimeUnixNano"])
        # exactly one root (no parentSpanId)
        assert sum("parentSpanId" not in s for s in otlp_spans) == 1
    finally:
        collector.stop()


def test_otlp_export_failures_are_silent():
    from trino_tpu.telemetry.tracing import export_otlp

    # refused connection, junk endpoint, empty input: never raises
    assert export_otlp("http://127.0.0.1:9/v1/traces", _spans()) is False
    assert export_otlp("not a url", _spans()) is False
    assert export_otlp("", _spans()) is False
    assert export_otlp("http://127.0.0.1:9/v1/traces", []) is False


# -- slow-query log --------------------------------------------------------


def test_slow_query_log_local():
    runner = LocalQueryRunner(
        {"tpch": TpchConnector(page_rows=4096)},
        Session(catalog="tpch", schema="micro",
                properties={"slow_query_log_threshold": 1e-9}))
    runner.execute("select count(*) from region")
    last = runner.event_manager.history(1)[-1]
    slow = (last.stats or {}).get("slow_query")
    assert slow is not None, "slow-query record missing from event"
    assert slow["wall_ms"] > 0
    assert slow["threshold_s"] == 1e-9
    # surfaced in system.runtime.queries history (the `slow` column)
    res = runner.execute(
        "select query, slow from system.runtime.queries "
        "where state = 'FINISHED'")
    flagged = [r for r in res.rows if r[1] is not None]
    assert flagged, "slow column empty in system.runtime.queries"
    assert "wall=" in flagged[0][1]


def test_fast_queries_not_flagged(local_runner):
    local_runner.execute("select count(*) from region")
    last = local_runner.event_manager.history(1)[-1]
    assert "slow_query" not in (last.stats or {})
