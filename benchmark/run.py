"""One run of one benchmark cell: ``python benchmark/run.py --workload
<name> --seed <n> --seconds <s> --trace <0|1>``.

One process holds the chip(s): ``ProtocolServer`` thread, client threads
and the configuration's runner (``systems/<runner.kind>.py``) together,
SQL text in through ``POST /v1/statement``.  A run is set-up (JAX up,
server up, warm-up of every statement of the seed's pool, a fixed number
of passes) -> window (closed loop; streams stop issuing ``--seconds`` after it
opened, it closes when the last statement in flight completes, every
statement issued in it counts) -> check (every statement's rows against
the numpy reference, outside both clocks) -> one JSON line.

Nothing here names a cell, a query, a configuration, a runner kind or a
metric: they are entries of ``BENCHMARK.json`` and files beside this one
(README.md).
"""

from __future__ import annotations

import time

T_START = time.perf_counter()          # process start, for setup_s

import argparse                         # noqa: E402
import functools                        # noqa: E402
import importlib                        # noqa: E402
import json                             # noqa: E402
import os                               # noqa: E402
import shutil                           # noqa: E402
import sys                              # noqa: E402
import threading                        # noqa: E402
from dataclasses import dataclass, field  # noqa: E402
from typing import List, Optional       # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
COMPILE_CACHE = os.path.join(ROOT, ".jax_cache")
TRACE_DIR = os.path.join(ROOT, ".bench_trace")
OWN_SPANS = ("runner.execute", "client.execute")


def say(**doc):
    print(json.dumps(doc), flush=True)


# -- what a run hands to the metric readers ---------------------------------

@dataclass
class Statement:
    stream: int
    instance: object
    t_issue: float
    t_done: float = 0.0
    columns: Optional[list] = None
    rows: Optional[list] = None
    error: Optional[str] = None

    @property
    def seconds(self) -> float:
        return self.t_done - self.t_issue


@dataclass
class RunFacts:
    """Everything a reader under ``end_to_end/`` or ``layer_metrics/``
    may read.  Times are ``time.perf_counter`` seconds."""
    setup_s: float = 0.0
    window_open: float = 0.0
    window_close: float = 0.0
    statements: List[Statement] = field(default_factory=list)
    #: base-table rows one statement of a template reads, by template name
    rows_read: dict = field(default_factory=dict)
    #: bytes of one pass over a template's referenced columns
    input_bytes: dict = field(default_factory=dict)
    #: (t0, t1, [sql, ...]) of every runner.execute / execute_batch call
    runner_calls: list = field(default_factory=list)
    compiles_in_window: int = 0
    explain_ms: List[float] = field(default_factory=list)
    #: trace_reduce.reduce(...) of the traced slice, and its bounds
    trace: Optional[dict] = None
    trace_open: float = 0.0
    trace_close: float = 0.0
    memory_peak_bytes: int = 0
    peaks: Optional[dict] = None

    @property
    def window_s(self) -> float:
        return self.window_close - self.window_open

    @property
    def finished(self) -> List[Statement]:
        return [s for s in self.statements if s.error is None]

    def traced_share(self, s: Statement) -> float:
        """The share of a statement's time that lies inside the traced
        slice (0 to 1)."""
        overlap = min(s.t_done, self.trace_close) - \
            max(s.t_issue, self.trace_open)
        return max(0.0, overlap) / s.seconds if s.seconds > 0 else 0.0

    def statements_traced(self) -> float:
        """Statements inside the traced slice, a statement that only
        partly overlaps it counting by its share."""
        return sum(self.traced_share(s) for s in self.finished)


# -- the system under test ---------------------------------------------------

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


class TimedRunner:
    """Delegates to the runner and records when each ``execute`` /
    ``execute_batch`` call ran: the benchmark's own span at the boundary
    between protocol and engine (the program opens none on this path).

    ``ProtocolServer`` decides what to hand a runner from what it shows
    (the parameters of ``execute``, whether there is an
    ``execute_batch``), so the wrapper shows what the runner shows: each
    wrapped call keeps the runner's own signature, and there is an
    ``execute_batch`` only where the runner has one."""

    def __init__(self, runner, calls: list):
        self._runner = runner
        self._calls = calls
        self.execute = self._wrap(runner.execute, lambda sql: [sql])
        if hasattr(runner, "execute_batch"):
            self.execute_batch = self._wrap(runner.execute_batch, list)

    def __getattr__(self, name):
        return getattr(self._runner, name)

    def _wrap(self, fn, sqls_of):
        @functools.wraps(fn)        # inspect.signature reads the runner's
        def call(first, *args, **kwargs):
            return self._timed(fn, sqls_of(first), first, *args, **kwargs)
        return call

    def _timed(self, fn, sqls, *args, **kwargs):
        import jax

        t0 = time.perf_counter()
        try:
            with jax.profiler.TraceAnnotation(OWN_SPANS[0]):
                return fn(*args, **kwargs)
        finally:
            self._calls.append((t0, time.perf_counter(), list(sqls)))


def build_system(config: dict, calls: list):
    """The runner as ``systems/<runner.kind>.py`` builds it from the
    configuration file, behind a started protocol server."""
    from trino_tpu.server.protocol import ProtocolServer

    kind = config["runner"]["kind"]
    module = f"benchmark.systems.{kind}"
    try:
        system = importlib.import_module(module)
    except ModuleNotFoundError as e:
        if e.name != module:
            raise
        raise ValueError(
            f"runner kind {kind!r}: no benchmark/systems/{kind}.py "
            "(a runner kind is a file there with build(config))") from e
    return ProtocolServer(TimedRunner(system.build(config), calls)).start()


# -- driving the streams -----------------------------------------------------

def drive(server_uri, sequences, stop_after=None, count=None,
          on_done=None) -> List[Statement]:
    """Closed loop: one thread and one client per stream.  A stream
    issues until ``stop_after`` (a perf_counter time) or for ``count``
    statements.  Returns every statement issued, failed ones too."""
    import jax

    from trino_tpu.client import Client

    out: List[Statement] = []
    lock = threading.Lock()
    gate = threading.Barrier(len(sequences))

    def stream(idx, seq):
        client = Client(server_uri, timeout=1100.0)
        gate.wait()
        issued = 0
        while (count is None or issued < count) and \
                (stop_after is None or time.perf_counter() < stop_after):
            st = Statement(idx, next(seq), time.perf_counter())
            issued += 1
            try:
                with jax.profiler.TraceAnnotation(OWN_SPANS[1]):
                    res = client.execute(st.instance.sql)
                st.columns, st.rows = res.columns, res.rows
            except Exception as e:  # a failed statement is a result
                st.error = f"{type(e).__name__}: {e}"
            st.t_done = time.perf_counter()
            with lock:
                out.append(st)
            if on_done is not None:
                on_done.set()

    threads = [threading.Thread(target=stream, args=(i, s), daemon=True)
               for i, s in enumerate(sequences)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    return sorted(out, key=lambda s: s.t_issue)


def warm_up(server_uri, pool, traffic, seed, counter) -> list:
    """``warmup_passes`` passes, every stream running the whole pool once
    per pass, streams together as in the window: the same work in every
    run, so set-up is steady.  Up to two passes more while the last one
    still asked XLA for a program."""
    from benchmark.traffic import stream_sequence

    passes = []
    planned = traffic["warmup_passes"]
    while len(passes) < planned or (
            passes[-1]["xla_programs"] and len(passes) < planned + 2):
        before = (counter.requests, counter.cache_hits)
        t0 = time.perf_counter()
        done = drive(server_uri,
                     [stream_sequence(pool, seed, s)
                      for s in range(traffic["streams"])], count=len(pool))
        failed = [s.error for s in done if s.error]
        if failed:
            raise RuntimeError(f"warm-up statement failed: {failed[0]}")
        passes.append({"seconds": time.perf_counter() - t0,
                       "statements": len(done),
                       "xla_programs": counter.requests - before[0],
                       "from_compile_cache":
                           counter.cache_hits - before[1]})
    return passes


class Tracer(threading.Thread):
    """Traces from the window's opening to the first statement completed
    after ``lo`` seconds, or to ``hi`` seconds if none completes before."""

    def __init__(self, directory, lo_hi, facts: RunFacts):
        super().__init__(daemon=True)
        self.directory = directory
        self.lo, self.hi = lo_hi
        self.started = threading.Event()
        self.statement_done = threading.Event()
        self.facts = facts

    def run(self):
        import jax

        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        options.enable_hlo_proto = False
        jax.profiler.start_trace(self.directory, profiler_options=options)
        self.facts.trace_open = time.perf_counter()
        self.started.set()
        time.sleep(self.lo)
        self.statement_done.clear()
        self.statement_done.wait(self.hi - self.lo)
        self.facts.trace_close = time.perf_counter()
        jax.profiler.stop_trace()


# -- check -------------------------------------------------------------------

def check(statements, tables) -> dict:
    """Every statement of the window against the reference of its
    instance over ``tables`` (a ``HostTables``); prints each count
    compared beside its limit (0)."""
    from benchmark import compare

    wanted, report = {}, {}
    for st in statements:
        if st.error is not None:
            continue
        inst = st.instance
        if inst.key not in wanted:
            ref = importlib.import_module(
                f"benchmark.references.{inst.template.name}")
            wanted[inst.key] = ref.reference(tables, dict(inst.params))
        bad = compare.mismatches(
            compare.typed_rows(st.columns, st.rows), wanted[inst.key],
            ordered=inst.template.meta["ordered"])
        rep = report.setdefault(inst.key, {
            "instance": inst.key, "statements": 0, "rows": len(st.rows),
            "reference_rows": len(wanted[inst.key]),
            "mismatched_values": 0, "limit": 0})
        rep["statements"] += 1
        rep["mismatched_values"] += bad
    for rep in report.values():
        say(check=rep)
    return report


# -- one run -------------------------------------------------------------------

def force_cpu_devices(n: int):
    """Ask XLA's CPU backend for ``n`` virtual devices (a rehearsal of a
    cell on ``n`` chips); has to run before jax starts, and leaves a
    count the environment already states alone."""
    forced = "--xla_force_host_platform_device_count"
    flags = os.environ.get("XLA_FLAGS", "")
    if n > 1 and forced not in flags:
        os.environ["XLA_FLAGS"] = f"{flags} {forced}={n}".strip()


def unused_devices(per_device) -> list:
    """The devices of the cell that held no memory or, in a traced run,
    ran no operation in the traced slice."""
    return [d for d in per_device if not d["peak_bytes_in_use"]
            or not d.get("busy_s", True)]


def read_metrics(package, entries, cell, facts) -> dict:
    """``{name: {"value", "unit"}}`` for the metrics of ``entries`` that
    list this cell (or list none); a reader that returns None is left
    out.  ``<reader>.<variant>`` is read by ``<reader>.py``: variants
    exist where cells need bounds or ``moves`` of their own."""
    out = {}
    for m in entries:
        if "workloads" in m and cell["name"] not in m["workloads"]:
            continue
        reader = importlib.import_module(
            f"benchmark.{package}.{m['name'].split('.')[0]}")
        value = reader.read(facts)
        if value is not None:
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out


def run_cell(bench, cell, args) -> dict:
    """One run of ``cell`` (an entry of ``bench["workloads"]``); prints
    and returns the result line."""
    config_entry, = [c for c in bench["configs"]
                     if c["name"] == cell["config"]]
    with open(os.path.join(ROOT, config_entry["file"])) as f:
        config = json.load(f)
    if ROOT not in sys.path:
        sys.path.insert(0, ROOT)
    from benchmark.traffic import (build_pool, check_traffic, load_json,
                                   stream_sequence)

    traffic = load_json("traffic", cell["traffic"] + ".json")
    check_traffic(traffic)

    # the compile cache lives at one fixed path inside this checkout,
    # whatever the environment says: the path is part of its key
    os.environ["JAX_COMPILATION_CACHE_DIR"] = COMPILE_CACHE
    if args.rehearse_cpu:
        os.environ["JAX_PLATFORMS"] = "cpu"
        force_cpu_devices(cell["chips"])
    import jax

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu" and not args.rehearse_cpu:
        sys.exit(f"run.py: JAX found no TPU (platform {dev.platform!r})")
    if dev.platform == "tpu" and args.rehearse_cpu:
        sys.exit("run.py: --rehearse-cpu on a TPU backend")
    if len(devices) < cell["chips"]:
        sys.exit(f"run.py: the cell asks for {cell['chips']} chip(s), "
                 f"JAX reports {len(devices)}")
    import trino_tpu  # noqa: F401  (x64 on before any array)
    from trino_tpu.compile_cache import enable_compile_cache

    from trino_tpu.parallel.device_exchange import DeviceExchange

    from benchmark import roofline, trace_reduce
    from benchmark.references.hosttables import HostTables

    enable_compile_cache()
    # no eviction: set-up stays the same from the second run on only if
    # every program of the first is still there (a machine may set
    # JAX_COMPILATION_CACHE_MAX_SIZE, and jax's evicting cache fails on
    # entries written without it)
    jax.config.update("jax_compilation_cache_max_size", -1)
    counter = CompileCounter()
    facts = RunFacts()
    if dev.platform == "tpu":
        facts.peaks = roofline.peaks_for(dev.device_kind)
    say(phase="start", workload=cell["name"], seed=args.seed,
        platform=dev.platform, device_kind=dev.device_kind,
        count=len(devices), jax=jax.__version__,
        compile_cache_dir=COMPILE_CACHE)

    pool = build_pool(traffic, args.seed)
    tables = HostTables(config["schema"])
    for inst in pool:
        t = inst.template
        rows = {name: tables.row_count(name) for name in t.tables}
        facts.rows_read[t.name] = sum(rows.values())
        facts.input_bytes[t.name] = roofline.input_bytes(
            t.meta["columns"], rows)
    server = build_system(config, facts.runner_calls)
    try:
        passes = warm_up(server.uri, pool, traffic, args.seed, counter)
        say(phase="warm_up", pool=[i.key for i in pool], passes=passes)
        del facts.runner_calls[:]

        tracer = None
        if args.trace:
            shutil.rmtree(TRACE_DIR, ignore_errors=True)
            tracer = Tracer(TRACE_DIR, traffic["trace_seconds"], facts)
            tracer.start()
            tracer.started.wait()
        compiles0 = counter.requests
        collectives0 = DeviceExchange.total_collectives
        # each stream walks the pool again from its first cycle
        sequences = [stream_sequence(pool, args.seed, s)
                     for s in range(traffic["streams"])]
        facts.window_open = time.perf_counter()
        facts.setup_s = facts.window_open - T_START
        facts.statements = drive(
            server.uri, sequences,
            stop_after=facts.window_open + args.seconds,
            on_done=tracer.statement_done if tracer else None)
        facts.window_close = max(s.t_done for s in facts.statements)
        facts.compiles_in_window = counter.requests - compiles0
        if tracer:
            tracer.join()
            from trino_tpu.client import Client

            client = Client(server.uri, timeout=1100.0)
            for inst in pool:
                t0 = time.perf_counter()
                client.execute("explain " + inst.sql)
                facts.explain_ms.append((time.perf_counter() - t0) * 1e3)
    finally:
        server.stop()

    from trino_tpu import jit_stats
    from trino_tpu.ops.aggregation import grouping_path_totals

    per_device = [{"id": d.id, "peak_bytes_in_use": int(
        (d.memory_stats() or {}).get("peak_bytes_in_use", 0))}
        for d in devices[:cell["chips"]]]
    facts.memory_peak_bytes = max(
        d["peak_bytes_in_use"] for d in per_device)
    say(phase="window", seconds_asked=args.seconds,
        window_s=facts.window_s, statements=len(facts.statements),
        finished=len(facts.finished),
        by_template={t: sum(1 for s in facts.statements
                            if s.instance.template.name == t)
                     for t in facts.rows_read},
        compiles_in_window=facts.compiles_in_window,
        # device collectives of the exchange (0 on one chip), over the
        # statements the window issued
        collectives_per_statement=(
            DeviceExchange.total_collectives - collectives0)
        / max(len(facts.statements), 1),
        jit_traces=jit_stats.total(),
        grouping_path_counts=grouping_path_totals(),
        xla_programs=counter.requests,
        from_compile_cache=counter.cache_hits)

    report = check(facts.statements, tables)
    failed = len(facts.statements) - len(facts.finished)
    correct = failed == 0 and bool(report) and all(
        r["mismatched_values"] == 0 for r in report.values())

    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices),
              "memory_peak_bytes": facts.memory_peak_bytes,
              "per_device": per_device}
    line = {"correct": correct, "attempted": len(facts.statements),
            "failed": failed}
    if args.trace:
        facts.trace = trace_reduce.reduce(
            trace_reduce.load(trace_reduce.find_xplane(TRACE_DIR)),
            own_spans=OWN_SPANS)
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        line["metrics"] = read_metrics("layer_metrics", bench["per_layer"],
                                       cell, facts)
        device["busy_s"] = facts.trace["busy_s"]
        device["window_s"] = facts.trace_close - facts.trace_open
        planes = {p["plane"]: p for p in facts.trace["per_device"]}
        for d in per_device:        # a device with no plane ran nothing
            d.update(planes.get(f"{trace_reduce.DEVICE_PREFIX}{d['id']}",
                                {"busy_s": 0.0}))
        line["breakdown"] = {"device_ops": facts.trace["device_ops"],
                             "idle_gaps": facts.trace["idle_gaps"]}
    else:
        line["metrics"] = read_metrics("end_to_end", bench["end_to_end"],
                                       cell, facts)
    line["device"] = device
    # each number compared beside its limit: the line's last key, and
    # the last lines on standard error
    line["compared"] = {"failed_statements": {"value": failed, "limit": 0},
                        **{key: {"value": rep["mismatched_values"],
                                 "limit": rep["limit"],
                                 "statements": rep["statements"]}
                           for key, rep in report.items()}}
    for name, entry in line["compared"].items():
        print(json.dumps({"compared": name, **entry}), file=sys.stderr,
              flush=True)
    unused = unused_devices(per_device)
    if dev.platform != "cpu" and unused:
        # a run of another deployment than the cell's: no result line
        sys.exit(f"run.py: devices of the cell that did no work: {unused}")
    print(json.dumps(line), flush=True)
    return line


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse-cpu", action="store_true",
                    help="run on a CPU backend (sandbox, benchmark/tests); "
                         "the device of such a run says cpu")
    args = ap.parse_args(argv)
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cells = {w["name"]: w for w in bench["workloads"]}
    if args.workload not in cells:
        sys.exit(f"run.py: no workload {args.workload!r} in BENCHMARK.json")
    run_cell(bench, cells[args.workload], args)
    return 0


if __name__ == "__main__":
    sys.exit(main())
