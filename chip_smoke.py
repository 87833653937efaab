"""Chip smoke: the served SQL path on one TPU chip at TPC-H SF1.

One process — server thread, client and runner together, because a chip
belongs to one process at a time.  SQL text goes through
``POST /v1/statement`` (``ProtocolServer`` over a ``LocalQueryRunner``,
driven by ``trino_tpu.client.Client``); every result is compared with an
oracle that is independent of the engine: the committed sqlite answers of
``tests/sf1_expected.py`` for TPC-H q6/q1/q3/q13, and a plain numpy
group-by over the connector's host pages for one grouped query whose
states are int32 (the shapes that take the Pallas segment-reduce kernel
on a TPU backend).

    python chip_smoke.py              one chip (what the driver runs)
    python chip_smoke.py --chips 4    only the four-device phase:
                                      DistributedQueryRunner + DeviceExchange
                                      on q3 and q18, both over the
                                      generator's catalog (host pages).  The
                                      served q18 over tables sharded across
                                      the four chips' HBM is the benchmark's
                                      cell now (mesh4_q18_exchange, PR 43),
                                      not a phase of this script
    python chip_smoke.py --queries 6,1,3,18,13
                                      one chip, q18 included: it passes, but
                                      takes 317 s cold + 149 s warm on a v5e
                                      (PR 22), which leaves the whole smoke
                                      too little room under its 1200 s limit

    python chip_smoke.py --queries 9  q9 over the generator's catalog: six
                                      relations, every join a lookup join;
                                      supplier x nation probes nation by
                                      direct address like every other
                                      single-key build (partsupp's
                                      two-column key is hashed and probed
                                      by search).  Not run on a chip
                                      through this script yet (PR 41 was
                                      refused a chip for it; the CPU
                                      rehearsal passes).  On a v5e q9 has
                                      run served over resident tables
                                      (cell sf1_q9_join6, PR 41): 258 s
                                      the first statement, 125 s the second
                                      (history's new plan compiles), 10.1 s
                                      each from the third on

Without a TPU the script exits non-zero.  ``--allow-cpu`` (with
``--schema tiny``) is the CPU rehearsal; such a run never prints a
``"platform": "tpu"`` line.  Any failed phase raises: nothing is caught.

The earlier JSON lines are single readings for the next planner (cold and
warm seconds, compile counts, memory) — not a benchmark.  The last line of
stdout is ``{"ok": true, "device": {...}}``.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))

#: TPC-H queries of the one-chip phase, in run order. q18 is left to
#: ``--queries`` and to the four-device phase (see the module docstring).
SMOKE_QUERIES = (6, 1, 3, 13)
#: the four-device phase: the two partitioned-join queries
MESH_QUERIES = (3, 18)
#: grouped query whose value states are int32 (dates, a string rank):
#: on a TPU backend both grouping paths reduce them in the Pallas kernel
GROUPED_SQL = (
    "select l_returnflag, l_linestatus, min(l_shipdate), "
    "max(l_shipdate), min(l_shipmode), count(*) "
    "from lineitem group by 1, 2")


#: the same int32 states over more groups than a page reduces densely
#: (``ops/hashtable.DENSE_GROUPS``): 1,024 at SF1 and on ``tiny``, so
#: every page takes the scatter branch, whose int32 states are the
#: Pallas kernel's on a TPU
MANY_GROUPS_SQL = (
    "select l_partkey % 1024, min(l_shipdate), max(l_shipdate), count(*) "
    "from lineitem group by 1")


def say(**doc):
    print(json.dumps(doc), flush=True)


def check(ok, message):
    """A failed phase stops the smoke (an ``assert`` would vanish under
    ``python -O``)."""
    if not ok:
        raise RuntimeError(f"chip_smoke: {message}")


class CompileCounter:
    """XLA programs JAX asked its backend for, and how many of those the
    persistent compile cache answered (``jax.monitoring`` events)."""

    def __init__(self):
        import jax

        self.requests = 0
        self.cache_hits = 0
        jax.monitoring.register_event_listener(self._on_event)

    def _on_event(self, event, **_):
        if event == "/jax/compilation_cache/compile_requests_use_cache":
            self.requests += 1
        elif event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    def read(self):
        return self.requests, self.cache_hits


class ServedResult:
    """A ``ClientResult`` in the shape ``assert_same`` compares: typed
    columns, decimals as ``Decimal`` (the protocol ships them as text)."""

    def __init__(self, client_result):
        from decimal import Decimal

        from trino_tpu import types as T

        self.types = [T.parse_type(c["type"])
                      for c in client_result.columns]
        self.rows = [
            tuple(Decimal(v) if v is not None and t.is_decimal else v
                  for v, t in zip(row, self.types))
            for row in client_result.rows]


def timed_twice(client, sql, counter):
    """Run ``sql`` cold then warm through the client; returns the warm
    result and one reading per run."""
    from trino_tpu import jit_stats

    runs = []
    res = None
    for _ in range(2):
        traces0, (req0, hit0) = jit_stats.total(), counter.read()
        t0 = time.perf_counter()
        res = client.execute(sql)
        secs = time.perf_counter() - t0
        (req1, hit1) = counter.read()
        runs.append({
            "seconds": secs,
            "jit_traces": jit_stats.total() - traces0,
            "xla_programs": req1 - req0,
            "from_compile_cache": hit1 - hit0,
        })
    return res, runs


def query_line(name, res, runs, paths_before):
    from trino_tpu.ops.aggregation import grouping_path_totals

    after = grouping_path_totals()
    say(query=name, rows=len(res.rows),
        cold_s=runs[0]["seconds"], warm_s=runs[1]["seconds"],
        cold_compiles=runs[0], warm_compiles=runs[1],
        grouping_path_counts={
            k: after[k] - paths_before.get(k, 0) for k in after
            if after[k] != paths_before.get(k, 0)},
        note="single readings, client-side wall clock")


def host_group_reference(conn, schema):
    """``GROUPED_SQL`` by plain numpy over the connector's host pages:
    no JAX, no engine operator."""
    import datetime

    import numpy as np

    names = ["l_returnflag", "l_linestatus", "l_shipdate", "l_shipmode"]
    meta = conn.metadata()
    handle = meta.get_table_handle(schema, "lineitem")
    cols = [c for n in names for c in meta.get_columns(handle)
            if c.name == n]
    groups = {}
    for split in conn.split_manager().get_splits(handle, 1):
        src = conn.page_source(split, cols)
        while (page := src.get_next_page()) is not None:
            flag, status, ship, mode = (b.numpy() for b in page.blocks)
            check(all(b.nulls is None or not b.nulls.any()
                      for b in (flag, status, ship, mode)),
                  "lineitem columns of the reference hold NULLs")
            nstatus = len(status.dictionary)
            key = flag.data.astype(np.int64) * nstatus + status.data
            for k in np.unique(key):
                rows = key == k
                gk = (flag.dictionary.values[int(k) // nstatus],
                      status.dictionary.values[int(k) % nstatus])
                lo, hi = int(ship.data[rows].min()), \
                    int(ship.data[rows].max())
                smode = min(mode.dictionary.values[c]
                            for c in np.unique(mode.data[rows]))
                cnt = int(rows.sum())
                if gk in groups:
                    g = groups[gk]
                    groups[gk] = (min(g[0], lo), max(g[1], hi),
                                  min(g[2], smode), g[3] + cnt)
                else:
                    groups[gk] = (lo, hi, smode, cnt)
    epoch = datetime.date(1970, 1, 1)

    def iso(days):
        return (epoch + datetime.timedelta(days=days)).isoformat()

    return sorted((f, s, iso(lo), iso(hi), m, n)
                  for (f, s), (lo, hi, m, n) in groups.items())


def host_many_groups_reference(conn, schema):
    """``MANY_GROUPS_SQL`` by plain numpy over the connector's host
    pages: no JAX, no engine operator."""
    import datetime

    import numpy as np

    meta = conn.metadata()
    handle = meta.get_table_handle(schema, "lineitem")
    cols = [c for n in ("l_partkey", "l_shipdate")
            for c in meta.get_columns(handle) if c.name == n]
    lo = np.full(1024, np.iinfo(np.int64).max)
    hi = np.full(1024, np.iinfo(np.int64).min)
    cnt = np.zeros(1024, dtype=np.int64)
    for split in conn.split_manager().get_splits(handle, 1):
        src = conn.page_source(split, cols)
        while (page := src.get_next_page()) is not None:
            part, ship = (b.numpy().data for b in page.blocks)
            key = part % 1024
            np.minimum.at(lo, key, ship)
            np.maximum.at(hi, key, ship)
            cnt += np.bincount(key, minlength=1024)
    epoch = datetime.date(1970, 1, 1)

    def iso(days):
        return (epoch + datetime.timedelta(days=int(days))).isoformat()

    return sorted((int(k), iso(lo[k]), iso(hi[k]), int(cnt[k]))
                  for k in np.flatnonzero(cnt))


def grouped_phase(client, conn, schema, counter):
    """The int32-state grouped query: checked against the host
    reference, and the kernel's use asserted for the backend in use."""
    from trino_tpu.ops import pallas_kernels
    from trino_tpu.ops.aggregation import grouping_path_totals
    from trino_tpu.telemetry import profiler

    mode = pallas_kernels.pallas_mode()
    paths = grouping_path_totals()
    calls0 = pallas_kernels.kernel_calls
    res, runs = timed_twice(client, GROUPED_SQL, counter)
    kernel_calls = pallas_kernels.kernel_calls - calls0
    got = sorted(tuple(r) for r in res.rows)
    want = host_group_reference(conn, schema)
    check(got == want, f"grouped query: engine={got}\nhost={want}")
    query_line("grouped_int32_states", res, runs, paths)

    # that query's four groups reduce densely; this one's pages have too
    # many, so the scatter branch — the kernel on a TPU — executes
    paths = grouping_path_totals()
    res, runs = timed_twice(client, MANY_GROUPS_SQL, counter)
    got = sorted(tuple(r) for r in res.rows)
    want = host_many_groups_reference(conn, schema)
    check(got == want, "many-group query differs from the host reference: "
          f"{[p for p in zip(got, want) if p[0] != p[1]][:3]}")
    query_line("many_groups_int32_states", res, runs, paths)
    dense = grouping_path_totals()["dense"] - paths["dense"]
    check(dense == 0, f"{dense} many-group pages reduced densely")

    # a profiled run keeps each compiled program: look for the kernel
    profiler.reset()
    with profiler.profiling(True):
        client.execute(GROUPED_SQL)
    with_kernel = sorted({
        name for name, compiled in profiler.compiled_programs()
        if "tpu_custom_call" in compiled.as_text()})
    profiler.reset()
    say(phase="pallas_segment_reduce", pallas_mode=mode or "off",
        kernel_calls_traced=kernel_calls,
        programs_with_custom_call=with_kernel,
        note=("TPU-only sort + Pallas kernel branch ran on the chip "
              "(in the many-group query's scatter branch)"
              if mode == "tpu" else
              "kernel NOT in the chip path: this backend is not a TPU"))
    if mode == "tpu":
        check(kernel_calls > 0, "TPU backend but the kernel never traced")
        check(with_kernel, "no profiled program holds tpu_custom_call")
    elif not mode:
        check(kernel_calls == 0 and not with_kernel,
              "Pallas kernel ran although pallas_mode() is off")


def memory_lines(devices):
    from trino_tpu.exec.memory import default_node_memory_bytes
    from trino_tpu.telemetry import profiler

    for d in devices:
        ms = d.memory_stats() or {}
        say(phase="device_memory", device=str(d),
            peak_bytes_in_use=ms.get("peak_bytes_in_use"),
            bytes_limit=ms.get("bytes_limit"),
            memory_stats_reported=bool(ms))
    say(phase="node_memory",
        default_node_memory_bytes=default_node_memory_bytes(),
        profiler_device_memory_stats=profiler.device_memory_stats())


def profiler_reads_lines():
    """Whether the best-effort readers of telemetry/profiler.py return
    real values on this backend (what the benchmark PR can read)."""
    from trino_tpu.telemetry import profiler

    rows = profiler.snapshot()
    say(phase="profiler_reads", programs=len(rows),
        cost_analysis_flops=sum(1 for r in rows if r["flops"]),
        cost_analysis_bytes=sum(1 for r in rows if r["bytes_accessed"]),
        memory_analysis=sum(1 for r in rows if r["argument_bytes"]
                            or r["output_bytes"] or r["temp_bytes"]),
        note="programs whose analysis returned non-zero values")


def expected_rows(schema, conn):
    """qid -> oracle rows: the committed sqlite answers at sf1; for the
    CPU rehearsal a sqlite database loaded from the same generator."""
    if schema == "sf1":
        from sf1_expected import EXPECTED

        return lambda qid: EXPECTED[qid]
    from test_tpch_oracle import load_sqlite, to_sqlite
    from trino_tpu.resources.tpch_queries import TPCH_QUERIES

    db = load_sqlite(conn, schema)
    return lambda qid: db.execute(to_sqlite(TPCH_QUERIES[qid])).fetchall()


def one_chip_phase(schema, queries, counter):
    import jax

    from test_tpch_oracle import assert_same
    from trino_tpu.client import Client
    from trino_tpu.connectors.tpch import TpchConnector
    from trino_tpu.ops.aggregation import grouping_path_totals
    from trino_tpu.resources.tpch_queries import TPCH_QUERIES
    from trino_tpu.runner import LocalQueryRunner
    from trino_tpu.server.protocol import ProtocolServer
    from trino_tpu.sql.analyzer import Session
    from trino_tpu.telemetry import profiler

    conn = TpchConnector(page_rows=1 << 16)
    expected = expected_rows(schema, conn)
    runner = LocalQueryRunner({"tpch": conn},
                              Session(catalog="tpch", schema=schema),
                              desired_splits=8)
    server = ProtocolServer(runner).start()
    try:
        client = Client(server.uri, timeout=1100.0)
        for qid in queries:
            sql = TPCH_QUERIES[qid]
            paths = grouping_path_totals()
            res, runs = timed_twice(client, sql, counter)
            assert_same(ServedResult(res), expected(qid),
                        ordered="order by" in sql.lower())
            query_line(f"q{qid}", res, runs, paths)
        grouped_phase(client, conn, schema, counter)
        # one more served query with the profiler on, to show what its
        # best-effort readers return on this backend
        profiler.reset()
        with profiler.profiling(True):
            client.execute(TPCH_QUERIES[6])
        profiler_reads_lines()
        profiler.reset()
    finally:
        server.stop()
    memory_lines(jax.local_devices()[:1])


def mesh_phase(schema, n_devices):
    """DistributedQueryRunner over ``n_devices`` with the device
    exchange (``lax.all_to_all`` over the mesh, one process)."""
    import jax

    from test_tpch_oracle import assert_same
    from trino_tpu.connectors.tpch import TpchConnector
    from trino_tpu.parallel.device_exchange import DeviceExchange
    from trino_tpu.parallel.distributed import DistributedQueryRunner
    from trino_tpu.resources.tpch_queries import TPCH_QUERIES
    from trino_tpu.sql.analyzer import Session

    conn = TpchConnector(page_rows=1 << 16)
    expected = expected_rows(schema, conn)
    session = Session(catalog="tpch", schema=schema)
    session.properties["device_exchange"] = True
    session.properties["join_distribution_type"] = "PARTITIONED"
    runner = DistributedQueryRunner({"tpch": conn}, session,
                                    n_workers=n_devices, desired_splits=8)
    for qid in MESH_QUERIES:
        sql = TPCH_QUERIES[qid]
        before = DeviceExchange.total_collectives
        t0 = time.perf_counter()
        res = runner.execute(sql)
        secs = time.perf_counter() - t0
        assert_same(res, expected(qid), ordered="order by" in sql.lower())
        collectives = DeviceExchange.total_collectives - before
        check(collectives > 0, f"q{qid}: no device collective ran")
        say(query=f"q{qid}", runner="DistributedQueryRunner",
            workers=n_devices, rows=len(res.rows), seconds=secs,
            all_to_all_collectives=collectives,
            note="single reading, first (cold) run")
    devices = jax.local_devices()[:n_devices]
    memory_lines(devices)
    if devices[0].platform != "cpu":
        idle = [str(d) for d in devices
                if not (d.memory_stats() or {}).get("peak_bytes_in_use")]
        check(not idle, f"devices that held no operator data: {idle}")


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the four-device phase")
    ap.add_argument("--schema", choices=("sf1", "tiny"), default="sf1",
                    help="tiny is for the CPU rehearsal")
    ap.add_argument("--queries", default=SMOKE_QUERIES,
                    type=lambda s: tuple(int(q) for q in s.split(",")),
                    help="TPC-H queries of the one-chip phase "
                         "(default %(default)s)")
    ap.add_argument("--allow-cpu", action="store_true",
                    help="rehearse on a CPU backend (never a TPU result)")
    args = ap.parse_args(argv)

    import jax

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu" and not args.allow_cpu:
        sys.exit(f"chip_smoke: JAX found no TPU (platform "
                 f"{dev.platform!r}); not continuing on it")
    if len(devices) < args.chips:
        sys.exit(f"chip_smoke: --chips {args.chips} but JAX reports "
                 f"{len(devices)} device(s)")

    sys.path.insert(0, ROOT)
    sys.path.insert(0, os.path.join(ROOT, "tests"))
    import trino_tpu  # noqa: F401  (turns x64 on before any array)
    from trino_tpu.compile_cache import enable_compile_cache

    cache_dir = enable_compile_cache()
    counter = CompileCounter()
    say(phase="start", platform=dev.platform, device_kind=dev.device_kind,
        count=len(devices), jax=jax.__version__, libtpu=_libtpu_version(),
        compile_cache_dir=cache_dir, schema=args.schema,
        chips=args.chips)

    if args.chips == 4:
        mesh_phase(args.schema, 4)
    else:
        one_chip_phase(args.schema, args.queries, counter)
    requests, hits = counter.read()
    say(phase="done", xla_programs=requests, from_compile_cache=hits)
    say(ok=True, device={"platform": dev.platform,
                         "kind": dev.device_kind, "count": len(devices)})
    return 0


def _libtpu_version():
    from importlib import metadata

    try:
        return metadata.version("libtpu")
    except metadata.PackageNotFoundError:
        return None


if __name__ == "__main__":
    sys.exit(main())
