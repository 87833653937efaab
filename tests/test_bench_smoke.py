"""Bench-harness smoke tests: the perf plumbing cannot silently rot.

The fast test asserts both group-by paths (hash table vs sort oracle)
produce identical q1 results on the micro schema through the REAL bench
pipeline builders. The slow-marked test runs the bench measurement
child itself (BENCH_SCHEMA=micro, CPU) end-to-end and checks the
RESULT line carries the rate, the per-stage breakdown, and jit-trace
counts.
"""

import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _drain(sink):
    from trino_tpu.block import Page

    if not sink.pages:
        return []
    return Page.concat(sink.pages).to_rows()


def test_q1_hash_and_sort_paths_identical():
    from trino_tpu.benchmarks import build_q1_driver, scan_q1_pages
    from trino_tpu.connectors.tpch import TpchConnector

    conn = TpchConnector(page_rows=4096)
    pages = scan_q1_pages(conn, "micro", desired_splits=4)
    rows = {}
    for label, hg in (("hash", True), ("sort", False)):
        driver, sink = build_q1_driver(conn, "micro",
                                       source_pages=list(pages),
                                       hash_grouping=hg)
        driver.run_to_completion()
        rows[label] = sorted(_drain(sink))
    assert rows["hash"] == rows["sort"]
    assert len(rows["hash"]) == 4  # the 4 (returnflag, linestatus) groups


def test_q18_hash_and_sort_paths_identical():
    from trino_tpu.benchmarks import build_q18_driver, scan_q18_pages
    from trino_tpu.connectors.tpch import TpchConnector

    conn = TpchConnector(page_rows=4096)
    pages = scan_q18_pages(conn, "micro", desired_splits=4)
    rows = {}
    agg_groups = {}
    for label, hg in (("hash", True), ("sort", False)):
        driver, sink = build_q18_driver(pages, hash_grouping=hg,
                                        collect_stats=True)
        driver.run_to_completion()
        rows[label] = sorted(_drain(sink))
        agg_groups[label] = next(
            st.output_rows for st in driver.stats
            if st.name.startswith("HashAggregation"))
    # the HAVING may filter micro down to nothing — the large-group
    # aggregation itself is the point: both paths must produce the same
    # (large) group count and the same final rows
    assert rows["hash"] == rows["sort"]
    assert agg_groups["hash"] == agg_groups["sort"] > 1000


def _load_bench():
    """Import bench.py by path (it is an entry script, not a package
    module; importing it runs no measurement)."""
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        "_bench_under_test", os.path.join(REPO, "bench.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_bench_ratchet_flags_regression(capsys):
    """A CPU rate below its COMMITTED cached baseline must produce an
    explicit *_regressed line in state (round 5's q1 0.928 sailed
    through silently); same-run solo baselines are exempt."""
    bench = _load_bench()
    res = {"query": "q1", "schema": "tiny", "rate": 900.0}
    state = {}
    bench._emit(state, res, "_cpu_fallback", 1000.0, cached_base=True)
    out = capsys.readouterr().out
    assert '"vs_baseline": 0.9' in out
    regressed = state.get("regressed", [])
    assert len(regressed) == 1
    line = json.loads(regressed[0])
    assert line["metric"] == "tpch_q1_tiny_rows_per_sec_regressed"
    assert line["value"] == 0.9

    # at/above baseline: no regression flag
    state2 = {}
    bench._emit(state2, res, "_cpu_fallback", 900.0, cached_base=True)
    assert not state2.get("regressed")
    # same-run solo baseline: exempt however low the ratio
    state3 = {}
    bench._emit(state3, res, "_cpu_fallback", 10_000.0, cached_base=False)
    assert not state3.get("regressed")
    # per-chip TPU lines have no TPU baseline to ratchet against
    state4 = {}
    bench._emit(state4, res, "_per_chip", 10_000.0, cached_base=True)
    assert not state4.get("regressed")


def test_bench_hbo_qerror_ratchet():
    """The HBO estimate-quality ratchet: quantiles above their
    committed baseline x the tolerance regress (Q-error is
    lower-better, so the bound is an UPPER one); no baseline = no
    ratchet; the committed cache must actually carry the baselines."""
    bench = _load_bench()
    cache = {"hbo_qerror_p50": 2.0, "hbo_qerror_p90": 10.0}
    # at baseline: ratio 1.0, clean
    ratios, regressed = bench._qerror_ratchet(2.0, 10.0, cache)
    assert ratios == {"hbo_qerror_p50": 1.0, "hbo_qerror_p90": 1.0}
    assert regressed == []
    # inside the tolerance: clean
    _, regressed = bench._qerror_ratchet(2.4, 10.0, cache)
    assert regressed == []
    # beyond it: the regressed quantile is named
    ratios, regressed = bench._qerror_ratchet(2.0, 20.0, cache)
    assert regressed == ["hbo_qerror_p90"]
    assert ratios["hbo_qerror_p90"] == 2.0
    # BETTER estimates (lower qerror) never regress
    _, regressed = bench._qerror_ratchet(1.0, 1.0, cache)
    assert regressed == []
    # no committed baseline: ratio 0.0, never regressed
    ratios, regressed = bench._qerror_ratchet(99.0, 99.0, {})
    assert ratios == {"hbo_qerror_p50": 0.0, "hbo_qerror_p90": 0.0}
    assert regressed == []
    # the REAL committed cache carries both baselines (the ratchet is
    # armed, not latent)
    committed = json.load(open(os.path.join(REPO,
                                            ".bench_cpu_cache.json")))
    assert committed.get("hbo_qerror_p50", 0) > 0
    assert committed.get("hbo_qerror_p90", 0) > 0


@pytest.mark.slow
def test_bench_chaos_smoke_child():
    """The bench harness's chaos role (BENCH_ROLE=chaos): a seeded
    kill-worker fault under retry_policy=TASK must recover to the exact
    fault-free answer and report its recovery counters — run as the real
    child process so the fault-injection code paths cannot rot outside
    the test suite."""
    env = dict(os.environ, BENCH_ROLE="chaos", JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, "-u", os.path.join(REPO, "bench.py")],
        env=env, capture_output=True, text=True, timeout=300)
    assert proc.returncode == 0, proc.stderr[-2000:]
    lines = [line for line in proc.stdout.splitlines()
             if line.startswith("CHAOS_RESULT ")]
    assert len(lines) == 1, proc.stdout[-2000:]
    out = json.loads(lines[0][len("CHAOS_RESULT "):])
    assert out["ok"] is True
    assert out["recovery"]["task_retries"] >= 1
    assert out["workers_alive"] == [True, True]


@pytest.mark.slow
def test_bench_skew_smoke_child():
    """The bench harness's skew role (BENCH_ROLE=skew): a zipf-keyed
    device exchange with hot-partition splitting must byte-match the
    unsplit oracle while spreading the hot partition over >= 2
    receiver lanes with zero retries, and scaled-writer CTAS must
    byte-match the unscaled plan while rebalancing — run as the real
    child process so the skew code paths cannot rot outside the test
    suite."""
    env = dict(os.environ, BENCH_ROLE="skew", JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, "-u", os.path.join(REPO, "bench.py")],
        env=env, capture_output=True, text=True, timeout=300)
    assert proc.returncode == 0, proc.stderr[-2000:]
    lines = [line for line in proc.stdout.splitlines()
             if line.startswith("SKEW_RESULT ")]
    assert len(lines) == 1, proc.stdout[-2000:]
    out = json.loads(lines[0][len("SKEW_RESULT "):])
    assert out["ok"] is True
    assert out["splits"] >= 1
    assert max(out["hot_spread"].values()) >= 2
    assert out["a2a_retries"] == 0
    assert out["lane_skew_split"] < out["lane_skew_unsplit"]
    assert out["rebalances"] >= 1
    assert out["rows_per_s"] > 0


@pytest.mark.slow
def test_bench_elastic_smoke_child():
    """The bench harness's elastic-cluster role (BENCH_ROLE=elastic):
    a queue-depth burst against a max_concurrency=2 resource group
    must make the autoscaler grow the membership 2 -> 4 mid-burst, the
    grown cluster must place tasks on the joiners, and idle must drain
    back down to the floor with zero lost rows and zero query retries
    — run as the real child process so the membership/autoscaler paths
    cannot rot outside the test suite."""
    env = dict(os.environ, BENCH_ROLE="elastic", JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, "-u", os.path.join(REPO, "bench.py")],
        env=env, capture_output=True, text=True, timeout=560)
    assert proc.returncode == 0, proc.stderr[-2000:]
    lines = [line for line in proc.stdout.splitlines()
             if line.startswith("ELASTIC_RESULT ")]
    assert len(lines) == 1, proc.stdout[-2000:]
    out = json.loads(lines[0][len("ELASTIC_RESULT "):])
    assert out["ok"] is True
    assert out["peak_workers"] >= 4
    assert out["final_workers"] == 2
    assert out["scaled_width_tasks"] is True
    directions = [d["direction"] for d in out["decisions"]]
    assert "up" in directions and directions.count("down") >= 2
    assert out["failures"] == []


@pytest.mark.slow
def test_bench_kernels_smoke_child():
    """The bench harness's kernel-strategy role (BENCH_ROLE=kernels):
    the matmul join must byte-match the sorted-index oracle across the
    NDV sweep, the three SQL-level join strategies must agree, the
    global-hash aggregation must match the exchange shape and the host
    oracle, and the crossover NDVs must be reported — run as the real
    child process so the kernel-strategy paths cannot rot outside the
    test suite."""
    env = dict(os.environ, BENCH_ROLE="kernels", JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, "-u", os.path.join(REPO, "bench.py")],
        env=env, capture_output=True, text=True, timeout=580)
    assert proc.returncode == 0, proc.stderr[-2000:]
    lines = [line for line in proc.stdout.splitlines()
             if line.startswith("KERNELS_RESULT ")]
    assert len(lines) == 1, proc.stdout[-2000:]
    out = json.loads(lines[0][len("KERNELS_RESULT "):])
    assert out["ok"] is True
    assert out["join_sql_three_strategies_equal"] is True
    assert len(out["join_sweep"]) == 3
    assert all(r["matmul_rows_per_s"] > 0 for r in out["join_sweep"])
    assert len(out["agg_sweep"]) == 3
    assert "join_crossover_ndv" in out and "agg_crossover_ndv" in out


@pytest.mark.slow
def test_bench_qps_smoke_child():
    """The bench harness's multi-tenant throughput role (BENCH_ROLE=
    qps): 8 concurrent HTTP protocol clients over a zipf tenant mix
    must report p50/p99 + queries/sec for a cache-disabled and a
    cache-enabled phase, with plan-cache hits, ZERO retraces on the
    repeat probe, bounded _QueryState growth, and >= 1.5x QPS from the
    caches — run as the real child process so the whole admission-to-
    execution path cannot rot outside the test suite."""
    env = dict(os.environ, BENCH_ROLE="qps", JAX_PLATFORMS="cpu",
               BENCH_QPS_SCHEMA="micro", BENCH_QPS_QUERIES="12",
               BENCH_QPS_TENANTS="6", BENCH_QPS_RATCHET_MIN="0.4")
    proc = subprocess.run(
        [sys.executable, "-u", os.path.join(REPO, "bench.py")],
        env=env, capture_output=True, text=True, timeout=560)
    assert proc.returncode == 0, proc.stderr[-2000:]
    lines = [line for line in proc.stdout.splitlines()
             if line.startswith("QPS_RESULT ")]
    assert len(lines) == 1, proc.stdout[-2000:]
    out = json.loads(lines[0][len("QPS_RESULT "):])
    assert out["ok"] is True
    assert out["clients"] == 8
    assert out["cached"]["queries"] == out["uncached"]["queries"] == 96
    assert out["cached"]["p99_ms"] > 0 and out["cached"]["qps"] > 0
    assert out["speedup"] >= 1.5
    assert out["plan_cache"]["plan_hits"] > 0
    assert out["probe_traces"] == 0
    assert out["query_states_left"] <= 16
    assert out["batching"]["batches"] >= 1


@pytest.mark.slow
def test_bench_measure_child_micro_cpu():
    env = dict(os.environ, BENCH_ROLE="measure", BENCH_PLATFORM="cpu",
               BENCH_SCHEMA="micro", BENCH_QUERIES="q1,q18",
               BENCH_REPEATS="2")
    env.pop("BENCH_DEADLINE", None)
    proc = subprocess.run(
        [sys.executable, "-u", os.path.join(REPO, "bench.py")],
        env=env, capture_output=True, text=True, timeout=420)
    assert proc.returncode == 0, proc.stderr[-2000:]
    results = [json.loads(line[len("RESULT "):])
               for line in proc.stdout.splitlines()
               if line.startswith("RESULT ")]
    assert [r["query"] for r in results] == ["q1", "q18"]
    for r in results:
        assert r["rate"] > 0
        assert r["stages"]["stage_ms"]["agg"] >= 0
        assert set(r["stages"]["stage_ms"]) >= {
            "scan", "filter_project", "agg", "join", "exchange"}
        assert r["jit_traces"].get("hash_group_ids", 0) > 0
