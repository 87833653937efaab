"""A statement's span tree on the served path (``ProtocolServer`` ->
``LocalQueryRunner`` -> ``Driver`` -> operators): one tree per statement
recorded by ``telemetry/tracing.py``, stamped on ``perf_counter``,
mirrored into the JAX profiler's trace, with the host-side counters on
its spans — over the benchmark's four templates (q1/q3/q6/q13 on
``tpch.tiny``) and the four ways the server serves a statement.
"""

import glob
import json
import os
import threading
import time
import urllib.request

import pytest

from trino_tpu.client import Client
from trino_tpu.connectors.tpch import TpchConnector
from trino_tpu.runner import LocalQueryRunner
from trino_tpu.server.protocol import ProtocolServer
from trino_tpu.sql.analyzer import Session
from trino_tpu.telemetry import tracing
from trino_tpu.telemetry.tracing import span_tree, trace_line

TEMPLATES = ("q1", "q3", "q6", "q13")
PLAN_SPANS = ("parse", "plan", "access_check", "local_plan")
RUNNER_SPANS = PLAN_SPANS + ("execute", "fetch_rows", "hbo_record")


def _instances(pool: int = 3) -> dict:
    """``{template: [sql, ...]}``: seeded instances of the benchmark's
    templates (``benchmark/queries``), the statements its cells send."""
    from benchmark.traffic import build_pool

    traffic = {"streams": 1, "loop": "closed", "templates": list(TEMPLATES),
               "draw": {"pool": pool}, "warmup_passes": 1,
               "trace_seconds": [0, 1]}
    out: dict = {}
    for inst in build_pool(traffic, 11):
        out.setdefault(inst.template.name, []).append(inst.sql)
    return out


SQL = _instances()


def _runner(**props) -> LocalQueryRunner:
    session = Session(catalog="tpch", schema="tiny")
    session.properties.update(props)
    return LocalQueryRunner({"tpch": TpchConnector()}, session,
                            desired_splits=8)


@pytest.fixture(scope="module")
def runner():
    return _runner()


def _root(spans):
    roots, _, _ = span_tree(spans)
    assert len(roots) == 1, [s["name"] for s in roots]
    return roots[0]


def _assert_one_sound_tree(spans, trace_id=None):
    """One root, no orphan, one id, children inside their parents on
    perf_counter."""
    roots, children, orphans = span_tree(spans)
    assert [s["name"] for s in roots] in (["statement"], ["batch.run"])
    assert orphans == []
    ids = {s["trace_id"] for s in spans}
    assert len(ids) == 1
    if trace_id is not None:
        assert ids == {trace_id}
    by_id = {s["span_id"]: s for s in spans}
    for s in spans:
        assert s["t1"] >= s["t0"]
        parent = by_id.get(s["parent_id"])
        if parent is not None:
            assert parent["t0"] <= s["t0"] and s["t1"] <= parent["t1"], \
                (parent["name"], s["name"])
    return roots[0], children


class ManualExecutor:
    """Runs the server's executor tasks when told to, on this thread: a
    burst submitted before ``run()`` is drained as ONE admission batch,
    so how each member is served is decided by the statements alone."""

    def __init__(self):
        self.tasks = []

    def submit(self, fn, *args):
        self.tasks.append((fn, args))

    def shutdown(self, wait=False):
        pass

    def run(self):
        while self.tasks:
            fn, args = self.tasks.pop(0)
            fn(*args)


def _burst(server, sqls):
    """Submit ``sqls`` as one burst, drain it, fetch every result;
    returns each statement's finished trace, in submission order."""
    t0 = time.perf_counter()
    ids = [server.submit(sql)["id"] for sql in sqls]
    server.executor.run()
    for qid in ids:
        doc = server.poll(qid, 0)
        assert "error" not in doc, doc
    traces, lost = tracing.RING.since(t0)
    assert not lost
    by_id = {_root_of(t)["trace_id"]: t for t in traces}
    return [by_id[qid] for qid in ids], \
        [t for t in traces if _root_of(t)["name"] == "batch.run"]


def _root_of(spans):
    return next(s for s in spans if s["parent_id"] is None)


# -- the runner's own tree ----------------------------------------------------

@pytest.mark.parametrize("template", TEMPLATES)
def test_runner_opens_its_own_root_with_no_current_span(runner, template):
    res = runner.execute(SQL[template][0])
    spans = res.stats["trace"]
    root, children = _assert_one_sound_tree(spans)
    assert root["attrs"]["served_by"] == "solo"
    names = [s["name"] for s in children[root["span_id"]]]
    for name in RUNNER_SPANS:
        assert names.count(name) == 1, (name, names)
    # the finished tree is in the process-wide ring, the same list
    traces, _ = tracing.RING.since(root["t0"])
    assert any(t is spans for t in traces)
    # one span per operator under ``execute``, from the driver's stats
    execute = next(s for s in spans if s["name"] == "execute")
    ops = children[execute["span_id"]]
    assert all(o["attrs"]["span_kind"] == "operator" for o in ops)
    scans = [o for o in ops if o["name"] == "TableScanOperator"]
    assert scans
    for scan in scans:
        a = scan["attrs"]
        assert a["generate_s"] > 0 and a["upload_s"] > 0


def test_scan_spans_carry_the_read_ahead_counters_and_their_reader():
    """A traced q1 whose scan has sixteen pages: all but the first come
    from the producer, and ``scan_wait_s_per_query`` reads the mean of
    the scans' ``wait_s``; a resident scan reads ahead nothing and
    waits 0.0."""
    from benchmark.layer_metrics import scan_wait_s_per_query
    from benchmark.run import RunFacts
    from trino_tpu.connectors.memory import MemoryConnector

    mem = MemoryConnector(schemas=["tiny"])
    mem.page_rows = 8192
    r = LocalQueryRunner(
        {"tpch": TpchConnector(page_rows=1024), "memory": mem},
        Session(catalog="tpch", schema="tiny"), desired_splits=4)
    r.execute("create table memory.tiny.lineitem as select * from lineitem")

    def scans_of(sqls):
        t_open = time.perf_counter()
        traces = [r.execute(sql).stats["trace"] for sql in sqls]
        facts = RunFacts(window_open=t_open,
                         window_close=time.perf_counter())
        return [s["attrs"] for spans in traces for s in spans
                if s["name"] == "TableScanOperator"], facts

    scans, facts = scans_of(SQL["q1"][:2])
    assert len(scans) == 2
    for a in scans:
        assert a["pages"] == 16 and a["readahead_pages"] == 15
        assert 0 <= a["readahead_ready"] <= 15
        assert a["wait_s"] >= 0.0 and a["generate_s"] > 0
    assert scan_wait_s_per_query.read(facts) == pytest.approx(
        sum(a["wait_s"] for a in scans) / 2)

    scans, facts = scans_of(
        ["select sum(l_quantity) from memory.tiny.lineitem"])
    assert scans[0]["resident_pages"] > 1
    assert scans[0]["readahead_pages"] == 0 and scans[0]["wait_s"] == 0.0
    assert scan_wait_s_per_query.read(facts) == 0.0


@pytest.mark.parametrize("template", TEMPLATES)
def test_plan_attributes_flip_between_executions(template, monkeypatch):
    from trino_tpu import cache
    from trino_tpu.telemetry.stats_store import store

    # a shape earns its template by uses or history, both process-wide;
    # with history off it is uses alone, and no misestimate drops a plan
    store().clear()
    monkeypatch.setattr(cache, "_TEMPLATE_SEEDS", cache.TemplateSeedStore())
    r = _runner(hbo_enabled=False)

    def plan_attrs(sql):
        spans = r.execute(sql).stats["trace"]
        return next(s for s in spans if s["name"] == "plan")["attrs"]

    first, second, third = SQL[template]
    a = plan_attrs(first)
    assert (a["plan_cache"], a["template"]) == ("miss", "miss")
    a = plan_attrs(first)
    assert (a["plan_cache"], a["template"]) == ("hit", "not_consulted")
    # another literal vector of the shape: the plan cache misses and the
    # shape's template answers — or says why it cannot
    b = plan_attrs(second)
    assert b["plan_cache"] == "miss"
    # (q1's interval literal is part of its shape: every instance is a
    # shape of its own and never earns one)
    expected = {"q1": "miss", "q6": "hit",
                "q3": "string_param", "q13": "string_param"}[template]
    assert b["template"] == expected
    assert plan_attrs(third)["template"] == expected


@pytest.mark.parametrize("template", TEMPLATES)
def test_host_syncs_repeat_exactly(runner, template):
    sql = SQL[template][0]
    runner.execute(sql)                     # warm: plan cache, programs

    def counters():
        root = _root(runner.execute(sql).stats["trace"])
        by_why = {why: n for why, (n, _s) in
                  root["attrs"]["host_sync_by_why"].items()}
        return root["attrs"]["host_syncs"], by_why

    n1, why1 = counters()
    n2, why2 = counters()
    assert n1 == n2 and why1 == why2
    assert n1 == sum(why1.values()) > 0
    assert why1["driver_row_counts"] >= 1 and why1["page_to_host"] >= 1
    assert _root(runner.execute(sql).stats["trace"]
                 )["attrs"]["host_sync_s"] > 0


@pytest.mark.parametrize("template", TEMPLATES)
def test_operator_rows_and_hbo_equal_the_per_page_count(template,
                                                        monkeypatch):
    """The driver keeps the pages' masks and counts them once; the
    numbers — per operator and in what HBO stores — must equal those of
    the per-page blocking ``page.count()`` it replaced."""
    from trino_tpu.exec import driver as driver_mod
    from trino_tpu.telemetry.stats_store import store

    sql = SQL[template][0]

    def run():
        store().clear()
        r = _runner()
        res = r.execute(sql)
        ops = [(s["name"], s["attrs"]["rows"], s["attrs"]["pages"])
               for s in res.stats["trace"]
               if s["attrs"].get("span_kind") == "operator"]
        hint = r._hbo_context(r.query_cache.parse(sql, r.session).stmt
                              ).statement_hint()
        return res.rows, ops, res.stats["hbo"], hint["scan_rows"]

    rows_new, ops_new, hbo_new, scan_new = run()
    blocking_reads = []

    def per_page_count(self, i, page):      # the parent's driver.py:186-188
        blocking_reads.append(1)
        self.stats[i].output_pages += 1
        self.stats[i].output_rows += page.count()

    monkeypatch.setattr(driver_mod.Driver, "_note_rows", per_page_count)
    rows_old, ops_old, hbo_old, scan_old = run()
    assert blocking_reads
    assert rows_new == rows_old
    assert ops_new == ops_old
    assert any(rows for _, rows, _ in ops_new)
    assert hbo_new == hbo_old
    assert scan_new == scan_old > 0


def test_driver_reads_row_counts_once_per_operator(runner):
    """No blocking read per page for the driver's own bookkeeping: the
    ``driver_row_counts`` syncs of a statement number at most its
    operators, however many pages moved."""
    r = _runner()
    r.session.properties["desired_splits"] = 16
    res = r.execute(SQL["q6"][0])
    spans = res.stats["trace"]
    ops = [s for s in spans if s["attrs"].get("span_kind") == "operator"]
    n, _ = _root(spans)["attrs"]["host_sync_by_why"]["driver_row_counts"]
    assert n <= len(ops)
    assert sum(o["attrs"]["pages"] for o in ops) >= len(ops) - 1


def test_tracing_disabled_leaves_everything_untouched(monkeypatch):
    r = _runner(query_tracing_enabled=False)
    annotations = []
    real = tracing.annotation
    monkeypatch.setattr(tracing, "annotation",
                        lambda name: annotations.append(name) or real(name))
    t0 = time.perf_counter()
    res = r.execute(SQL["q3"][0])
    batch = r.execute_batch(SQL["q6"])
    assert "trace" not in (res.stats or {})
    assert all("trace" not in (b.stats or {}) for b in batch)
    assert tracing.RING.since(t0) == ([], False)
    assert annotations == []
    assert tracing.current_span() is None
    # the server opens nothing either
    server = ProtocolServer(r)
    server.executor = ManualExecutor()
    qid = server.submit(SQL["q6"][0])["id"]
    server.executor.run()
    assert server.poll(qid, 0)["data"]
    assert tracing.RING.since(t0) == ([], False)
    assert annotations == []
    assert not server.query_info(qid)["stats"].get("trace")


# -- how the server serves a statement --------------------------------------

SERVED = {
    # mode -> (template, the burst's statements)
    "solo": ("q3", lambda sqls: sqls[:1]),
    "vmapped": ("q6", lambda sqls: sqls),
    "coalesced": ("q1", lambda sqls: [sqls[0]] * 3),
    "serial_in_batch": ("q13", lambda sqls: sqls),
}


@pytest.mark.parametrize("mode", list(SERVED))
def test_served_by(runner, mode):
    template, pick = SERVED[mode]
    sqls = pick(SQL[template])
    server = ProtocolServer(runner)
    server.executor = ManualExecutor()
    _burst(server, sqls)                    # earn the template, warm
    statements, batches = _burst(server, sqls)
    assert len(batches) == 1
    batch_root, _ = _assert_one_sound_tree(batches[0])
    assert batch_root["attrs"]["batch_size"] == len(sqls)
    seen = []
    for spans in statements:
        root, children = _assert_one_sound_tree(spans)
        assert root["trace_id"] == root["attrs"]["query_id"]
        assert root["attrs"]["state"] == "FINISHED"
        assert root["attrs"]["batch_size"] == len(sqls)
        seen.append(root["attrs"]["served_by"])
        kids = {s["name"]: s for s in children[root["span_id"]]}
        assert set(kids) == {"statement.queued", "statement.run",
                             "statement.deliver"}
        run = kids["statement.run"]
        assert run["attrs"]["batch"] == batch_root["span_id"]
        assert run["attrs"]["served_by"] == root["attrs"]["served_by"]
        # the member's span is its wait inside the batch: it lasts
        # until the batch returns
        assert batch_root["t0"] <= run["t0"] <= batch_root["t1"] \
            <= run["t1"]
        under_run = [s["name"] for s in children.get(run["span_id"], ())]
        if root["attrs"]["served_by"] in ("solo", "serial_in_batch"):
            # its own work hangs under its own statement
            for name in RUNNER_SPANS:
                assert name in under_run
            assert root["attrs"]["host_syncs"] > 0
        else:
            assert under_run == []
    expected = {"solo": ["solo"],
                "vmapped": ["vmapped"] * 3,
                "coalesced": ["serial_in_batch", "coalesced", "coalesced"],
                "serial_in_batch": ["serial_in_batch"] * 3}[mode]
    assert seen == expected
    if mode == "vmapped":
        # the lanes' shared work — and its counters — are the batch's
        names = [s["name"] for s in batches[0]]
        for name in ("parse", "plan", "local_plan", "execute",
                     "fetch_rows"):
            assert name in names
        assert batch_root["attrs"]["host_syncs"] > 0
        execute = next(s for s in batches[0] if s["name"] == "execute")
        assert execute["attrs"]["generate_s"] > 0


def test_execute_batch_without_a_server_opens_member_roots(runner):
    t0 = time.perf_counter()
    results = runner.execute_batch(SQL["q13"])
    traces, _ = tracing.RING.since(t0)
    roots = [_root_of(t) for t in traces]
    assert sorted(r["name"] for r in roots) == \
        ["batch.run"] + ["statement"] * 3
    batch = next(r for r in roots if r["name"] == "batch.run")
    for res in results:
        root, _ = _assert_one_sound_tree(res.stats["trace"])
        assert root["attrs"]["batch"] == batch["span_id"]
        assert root["attrs"]["served_by"] == "serial_in_batch"
    assert len({id(res.stats["trace"]) for res in results}) == 3


def test_protocol_spans_tile_the_clients_latency(runner):
    """8 client threads through the HTTP server: for every statement
    queued + run + deliver fit inside what its client waited, and the
    three cover nearly all of the statement span."""
    server = ProtocolServer(runner).start()
    try:
        for sqls in SQL.values():           # warm every statement
            for sql in sqls:
                Client(server.uri).execute(sql)
        t_open = time.perf_counter()
        seconds = {}
        lock = threading.Lock()
        errors = []

        def stream(idx):
            client = Client(server.uri)
            order = [sql for t in TEMPLATES for sql in SQL[t]]
            order = order[idx % len(order):] + order[:idx % len(order)]
            for sql in order[:6]:
                t0 = time.perf_counter()
                try:
                    client.execute(sql)
                except Exception as e:      # noqa: BLE001
                    errors.append(e)
                    return
                t1 = time.perf_counter()
                with lock:
                    seconds.setdefault(sql, []).append((t0, t1))

        threads = [threading.Thread(target=stream, args=(i,))
                   for i in range(8)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=300)
        assert not any(t.is_alive() for t in threads)
        assert errors == []
    finally:
        server.stop()
    traces, lost = tracing.RING.since(t_open)
    assert not lost
    statements = [t for t in traces if _root_of(t)["name"] == "statement"]
    assert len(statements) == 48
    modes = set()
    for spans in statements:
        root, children = _assert_one_sound_tree(spans)
        modes.add(root["attrs"]["served_by"])
        parts = {s["name"]: s["t1"] - s["t0"]
                 for s in children[root["span_id"]]}
        tiled = sum(parts[n] for n in ("statement.queued",
                                       "statement.run",
                                       "statement.deliver"))
        whole = root["t1"] - root["t0"]
        # the client that waited for this statement: the one call of
        # this text whose interval holds the statement span
        sql = server_sql(root, statements_sql=seconds)
        assert sql is not None
        t0, t1 = sql
        assert tiled <= whole <= (t1 - t0) + 1e-6
    assert modes <= {"solo", "vmapped", "coalesced", "serial_in_batch"}


def server_sql(root, statements_sql):
    """The client interval (t0, t1) that encloses ``root``."""
    for intervals in statements_sql.values():
        for t0, t1 in intervals:
            if t0 <= root["t0"] and root["t1"] <= t1:
                return t0, t1
    return None


def test_failed_and_cancelled_statements_end_their_trees(runner):
    server = ProtocolServer(runner)
    server.executor = ManualExecutor()
    t0 = time.perf_counter()
    bad = server.submit("select no_such_column from lineitem")["id"]
    gone = server.submit(SQL["q6"][0])["id"]
    server.cancel(gone)
    server.executor.run()
    # a failed query stays pollable: only the first reply ends its tree
    for _ in range(3):
        assert "error" in server.poll(bad, 0)
    traces, _ = tracing.RING.since(t0)
    states = [(_root_of(t)["attrs"]["query_id"],
               _root_of(t)["attrs"]["state"]) for t in traces
              if _root_of(t)["name"] == "statement"]
    assert sorted(states) == sorted([(bad, "FAILED"), (gone, "CANCELED")])
    failed = next(t for t in traces
                  if _root_of(t)["attrs"].get("query_id") == bad)
    _assert_one_sound_tree(failed)
    assert [s["name"] for s in failed].count("statement.deliver") == 1
    assert any("error" in s["attrs"] for s in failed)


def test_terminal_state_is_published_after_delivery_starts(
        runner, monkeypatch):
    """A poll that sees FINISHED or FAILED serves the last page and
    ends the tree, so the deliver span has to be open by then: solo,
    in a batch, and on failure."""
    from trino_tpu.server import protocol

    states = []
    real = protocol._QueryState.start_deliver

    def start_deliver(q):
        states.append(q.state)
        real(q)

    monkeypatch.setattr(protocol._QueryState, "start_deliver",
                        start_deliver)
    server = ProtocolServer(runner)
    server.executor = ManualExecutor()
    t0 = time.perf_counter()
    qids = [server.submit(sql)["id"] for sql in
            [SQL["q3"][0], "select no_such_column from lineitem"]
            + SQL["q6"]]
    server.executor.run()
    assert states == ["RUNNING"] * len(qids)
    for qid in qids:
        server.poll(qid, 0)
    traces, _ = tracing.RING.since(t0)
    statements = [t for t in traces if _root_of(t)["name"] == "statement"]
    assert len(statements) == len(qids)
    for spans in statements:
        assert "statement.deliver" in [s["name"] for s in spans]
        assert "served_by" in _root_of(spans)["attrs"]


# -- the operator's views of the same data ------------------------------------

def test_query_info_and_explain_analyze_show_the_tree(runner):
    server = ProtocolServer(runner).start()
    try:
        client = Client(server.uri)
        client.execute(SQL["q3"][0])
        res = client.execute("explain analyze " + SQL["q3"][0])
        lines = [row[0] for row in res.rows]
        trace = [ln for ln in lines if ln.startswith("Trace: ")]
        assert len(trace) == 1
        assert "(0 orphans)" in trace[0]
        assert "critical path: statement" in trace[0]
        # where the host stood waiting for the device, by site
        syncs = [ln for ln in lines if ln.startswith("Host syncs: ")]
        assert len(syncs) == 1
        assert "join_expand_total" in syncs[0]
        time.sleep(0.2)                     # the last poll ends the root
        with server._finished_lock:
            qid = next(q for q, info in server.finished.items()
                       if info["query"] == SQL["q3"][0])
        with urllib.request.urlopen(
                f"{server.uri}/v1/query/{qid}") as resp:
            info = json.loads(resp.read())
    finally:
        server.stop()
    spans = info["stats"]["trace"]
    root, children = _assert_one_sound_tree(spans, trace_id=qid)
    names = {s["name"] for s in spans}
    assert {"statement", "statement.queued", "statement.run",
            "statement.deliver", "execute", "TableScanOperator",
            "LookupJoinOperator"} <= names
    assert trace_line(spans).startswith("Trace: ")
    # GET /v1/query/{id} carries the same per-site wait
    assert "join_expand_total" in root["attrs"]["host_sync_by_why"]


# -- on the profiler's clock --------------------------------------------------

def test_span_names_are_host_events_of_a_jax_profile(runner, tmp_path):
    import jax
    from jax.profiler import ProfileData

    sql = SQL["q3"][0]
    runner.execute(sql)
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0         # as benchmark/run.py traces
    options.enable_hlo_proto = False
    jax.profiler.start_trace(str(tmp_path), profiler_options=options)
    try:
        t0 = time.perf_counter()
        res = runner.execute(sql)
    finally:
        jax.profiler.stop_trace()
    path, = glob.glob(os.path.join(str(tmp_path), "plugins", "profile",
                                   "*", "*.xplane.pb"))
    profile = ProfileData.from_file(path)
    host, = [p for p in profile.planes if p.name == "/host:CPU"]
    events = {}
    for line in host.lines:
        for ev in line.events:
            events.setdefault(ev.name, []).append(ev.duration_ns)
    for name in ("statement",) + RUNNER_SPANS:
        assert name in events, name
    for name in ("op:TableScanOperator.get_output",
                 "op:LookupJoinOperator.add_input",
                 "op:HashAggregationOperator.finish",
                 "scan.generate", "scan.upload",
                 "sync:driver_row_counts", "sync:join_expand_total",
                 "sync:agg_overflow", "sync:page_to_host"):
        assert name in events, name
    # the annotation is the span: same duration on both clocks
    spans = res.stats["trace"]
    execute = next(s for s in spans if s["name"] == "execute")
    assert execute["t0"] >= t0
    span_s = execute["t1"] - execute["t0"]
    event_s = max(events["execute"]) / 1e9
    assert abs(span_s - event_s) < 0.05 * span_s + 0.002
    # and every counted sync is an annotation of the profile
    by_why = _root(spans)["attrs"]["host_sync_by_why"]
    for why, (n, _s) in by_why.items():
        assert len(events["sync:" + why]) == n, why
