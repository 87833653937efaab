"""Distributed tracing core: spans, context propagation, OTLP export.

Reference analog: the reference engine's OpenTelemetry instrumentation —
``io.opentelemetry.api.trace.Span`` opened per query/stage/task/operator
with ``TrinoAttributes``, context propagated to workers in task requests
(W3C ``traceparent``), and the resulting timeline viewable in any trace
UI.  Here the core is dependency-free: spans are plain dicts once
finished, context is a small dict riding the task RPC envelope, and the
one exporter is OTLP JSON over HTTP (``to_otlp`` / ``export_otlp``).

Cost model: tracing must be zero-cost when off — ``NULL_TRACER.span()``
returns a shared no-op span, and spans are NEVER opened inside jit'd
code (host-side boundaries only), so no compiled program changes.
In-memory spans are per statement, stage and operator (tens per
statement), never per page: per-page detail is a profiler annotation
(``annotation``: one flag test unless a profile is being taken) plus a
plain add on the owning span's attributes (``host_sync``).

Clock model: span ``start``/``end`` are epoch seconds (``time.time()`` —
the only clock that aligns across processes on one host) with the
duration measured on ``perf_counter``; ``t0``/``t1`` are the same
instants as ``time.perf_counter()`` seconds, the clock the benchmark's
``RunFacts`` use.  A span entered with ``with`` is also a
``jax.profiler.TraceAnnotation``: an event of ``/host:CPU`` on the
profiler's clock, the one the device planes are on.

Linkage inside a process: a ``ContextVar`` holds the span a ``with``
entered; ``span(name)`` opens a child of it.  ``ProtocolServer`` enters a
statement's ``statement.run`` on the executor thread, so the runner below
needs no argument; a runner called with no current span opens its own
root (``root_scope``).  A runner whose tasks run on other threads
(``DistributedQueryRunner``) opens a ``task`` span a task under its
``execute`` and enters it (``use_span``) around each quantum, so what a
task's operators count lands in the statement's tree; a device
exchange opens an ``exchange`` span a collective under the task that
triggered it (``parallel/device_exchange.py``).  Finished trees of
served statements go to ``RING``.

Counters on the root: a statement's blocking device-to-host reads
(``host_sync``) and the programs JAX traced, lowered or compiled for it
(``lowerings``, fed by one ``jax.monitoring`` listener) are plain adds
on the root span's attributes, from whichever thread ran that part of
the statement.
"""

from __future__ import annotations

import collections
import contextlib
import contextvars
import os
import threading
import time
from typing import Dict, Iterable, List, Optional, Tuple

import numpy as np

#: the span entered by the innermost ``with`` on this thread / context
_CURRENT: contextvars.ContextVar = contextvars.ContextVar(
    "trino_tpu_current_span", default=None)


#: the root's counters are added to by every thread that runs a part of
#: the statement
_ROOT_COUNTERS = threading.Lock()


def annotation(name: str):
    """``jax.profiler.TraceAnnotation(name)``: a host event of the
    profiler's own trace while one is being taken, a flag test when
    none is."""
    from jax.profiler import TraceAnnotation

    return TraceAnnotation(name)


def _new_id(nbytes: int = 8) -> str:
    return os.urandom(nbytes).hex()


class Span:
    """One timed operation. Context-manager: exceptions mark the span
    failed (``error`` attribute) and still finish it."""

    __slots__ = ("tracer", "trace_id", "span_id", "parent_id", "name",
                 "process", "start", "end", "t0", "t1", "attrs", "parent",
                 "root", "_scope")

    def __init__(self, tracer: "Tracer", name: str,
                 parent_id: Optional[str], parent: Optional["Span"] = None,
                 **attrs):
        self.tracer = tracer
        self.trace_id = tracer.trace_id
        self.span_id = _new_id()
        self.parent_id = parent_id
        self.name = name
        self.process = tracer.process
        self.start = time.time()
        self.t0 = time.perf_counter()
        self.end: Optional[float] = None
        self.t1: Optional[float] = None
        self.attrs = attrs
        #: the parent span when it is of this tracer, and the root of
        #: this span's tree in this process: the statement's counters
        #: (``host_sync``) are kept on the root
        self.parent = parent
        self.root: "Span" = parent.root if parent is not None else self
        self._scope = None

    def set(self, key: str, value):
        self.attrs[key] = value

    def context(self, **extra) -> dict:
        """The propagation envelope shipped in task RPCs (W3C
        traceparent semantics: version-trace_id-parent_id-flags, carried
        as a dict so extra baggage — attempt number, fragment — rides
        along without string parsing)."""
        ctx = {"traceparent":
               f"00-{self.trace_id}-{self.span_id}-01",
               "trace_id": self.trace_id, "span_id": self.span_id}
        ctx.update(extra)
        return ctx

    def finish(self):
        if self.end is None:
            self.t1 = time.perf_counter()
            self.end = self.start + (self.t1 - self.t0)
            self.tracer._record(self.to_dict())
            if self.parent_id is None and self.tracer.ring is not None:
                self.tracer.ring.publish(self.tracer.finished(), self.t1)

    def to_dict(self) -> dict:
        """The finished-span dict; of a span still open, a snapshot
        that ends now."""
        t1 = self.t1 if self.t1 is not None else time.perf_counter()
        return {
            "trace_id": self.trace_id, "span_id": self.span_id,
            "parent_id": self.parent_id, "name": self.name,
            "process": self.process, "start": self.start,
            "end": self.start + (t1 - self.t0),
            "t0": self.t0, "t1": t1,
            "attrs": dict(self.attrs),
        }

    def __enter__(self) -> "Span":
        """Entered on one thread and left on the same: the span is the
        context's current span and a profiler annotation meanwhile.
        Spans that start on one thread and end on another are waits;
        they are opened and ``finish()``ed without ``with``."""
        self._scope = use_span(self)
        self._scope.__enter__()
        return self

    def __exit__(self, exc_type, exc, tb):
        if exc is not None:
            self.attrs.setdefault("error", repr(exc))
        scope, self._scope = self._scope, None
        scope.__exit__(None, None, None)
        self.finish()
        return False


class _NullSpan:
    """The zero-cost-when-off span: every operation is a no-op and
    ``context()`` is None, so nothing is shipped downstream either."""

    __slots__ = ()
    trace_id = span_id = parent_id = None

    def set(self, key, value):
        pass

    def context(self, **extra):
        return None

    def finish(self):
        pass

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False

    def __bool__(self):
        return False


NULL_SPAN = _NullSpan()


def parse_context(ctx: Optional[dict]) -> Tuple[Optional[str],
                                                Optional[str]]:
    """(trace_id, parent_span_id) from a propagation envelope; accepts
    the dict form or a bare traceparent string."""
    if not ctx:
        return None, None
    if isinstance(ctx, str):
        parts = ctx.split("-")
        if len(parts) == 4:
            return parts[1], parts[2]
        return None, None
    if ctx.get("trace_id"):
        return ctx["trace_id"], ctx.get("span_id")
    return parse_context(ctx.get("traceparent"))


class Tracer:
    """Per-query (coordinator) or per-task (worker) span factory.
    Finished spans accumulate as plain dicts — cheap to ship over the
    task RPC response (the heartbeat-piggyback pattern) and to merge
    coordinator-side into one tree."""

    def __init__(self, process: str = "coordinator",
                 trace_id: Optional[str] = None, enabled: bool = True,
                 ring: Optional["TraceRing"] = None):
        self.enabled = enabled
        self.process = process
        self.trace_id = trace_id or _new_id(8)
        #: where the finished tree goes when a root span of this tracer
        #: finishes (the served path passes ``RING``)
        self.ring = ring
        self._finished: List[dict] = []

    def span(self, name: str, parent=None, **attrs):
        """Open a span. ``parent`` is a Span, a propagation-context
        dict, or None (root)."""
        if not self.enabled:
            return NULL_SPAN
        local_parent = None
        if isinstance(parent, Span):
            parent_id = parent.span_id
            if parent.tracer is self:
                local_parent = parent
        elif parent is None or isinstance(parent, _NullSpan):
            parent_id = None
        else:
            tid, parent_id = parse_context(parent)
            if tid:
                self.trace_id = tid
        if local_parent is None and not _listening:
            _listen_for_lowerings()
        return Span(self, name, parent_id, local_parent, **attrs)

    def _record(self, span_dict: dict):
        self._finished.append(span_dict)

    def add_finished(self, spans: Optional[Iterable[dict]]):
        """Merge remote (worker-produced) finished spans in."""
        if spans:
            self._finished.extend(spans)

    def finished(self) -> List[dict]:
        """The live list of finished spans, not a copy: what
        ``QueryResult.stats["trace"]``, the server's ``finished`` ring
        and ``RING`` all hold, so a span that finishes after the runner
        returned (``statement.deliver``, the root) is in all three."""
        return self._finished


NULL_TRACER = Tracer(enabled=False)


class TraceRing:
    """Bounded process-wide store of finished statement traces (each the
    tracer's live span list), oldest evicted first."""

    def __init__(self, capacity: int = 4096):
        self.capacity = capacity
        self._entries: collections.deque = collections.deque()
        self._lock = threading.Lock()
        #: ``t1`` (perf_counter) of the newest root evicted so far
        self._evicted_t1 = float("-inf")

    def publish(self, spans: List[dict], t1: float):
        with self._lock:
            self._entries.append((t1, spans))
            while len(self._entries) > self.capacity:
                self._evicted_t1 = max(self._evicted_t1,
                                       self._entries.popleft()[0])

    def since(self, t: float) -> Tuple[List[List[dict]], bool]:
        """(the traces whose root finished at or after ``t`` on
        ``perf_counter``, whether any such trace was evicted)."""
        with self._lock:
            return ([spans for t1, spans in self._entries if t1 >= t],
                    self._evicted_t1 >= t)


#: finished traces of the statements this process served (roots
#: ``statement``) and of the batches they rode in (roots ``batch.run``)
RING = TraceRing()


def current_span() -> Optional[Span]:
    return _CURRENT.get()


def span(name: str, **attrs):
    """A child of the context's current span (``NULL_SPAN`` when there
    is none: tracing is off for this statement)."""
    cur = _CURRENT.get()
    if cur is None:
        return NULL_SPAN
    return cur.tracer.span(name, parent=cur, **attrs)


def snapshot() -> List[dict]:
    """The current statement's tree as it stands: its finished spans
    and, ending now, the open ones from the current span up to its
    root (EXPLAIN ANALYZE renders its ``Trace:`` line from inside the
    statement)."""
    cur = _CURRENT.get()
    if cur is None:
        return []
    spans = list(cur.tracer.finished())
    while cur is not None:
        spans.append(cur.to_dict())
        cur = cur.parent
    return spans


@contextlib.contextmanager
def use_span(span, label: Optional[str] = None):
    """``span`` is the context's current span (and a profiler
    annotation, ``label`` or the span's name) inside the block, and is
    NOT ended by it: for a span that somebody else ends (a batch
    member's ``statement.run``, a task's span around each quantum)."""
    if not span:
        yield span
        return
    with annotation(label or span.name):
        token = _CURRENT.set(span)
        try:
            yield span
        finally:
            _CURRENT.reset(token)


def span_set(key: str, value):
    """Set an attribute on the context's current span, if any."""
    cur = _CURRENT.get()
    if cur is not None:
        cur.attrs[key] = value


def span_add(key: str, value):
    """Add ``value`` to a counter on the context's current span, if
    any."""
    cur = _CURRENT.get()
    if cur is not None:
        cur.attrs[key] = cur.attrs.get(key, 0) + value


@contextlib.contextmanager
def root_scope(name: str, enabled: bool, **attrs):
    """The scope of one runner call.  Under a caller's span
    (``ProtocolServer`` entered one) opens nothing; with
    no current span opens — when ``enabled`` — a root of its own, whose
    finished tree goes to ``RING``."""
    if _CURRENT.get() is not None or not enabled:
        yield
        return
    with Tracer(ring=RING).span(name, **attrs):
        yield


@contextlib.contextmanager
def host_sync(why: str):
    """Around a blocking device-to-host read.  Counts one sync and its
    wall seconds on the statement's root (``host_syncs``,
    ``host_sync_s``, and by ``why`` under ``host_sync_by_why``, which
    EXPLAIN ANALYZE prints: ``sync_line``) and is the annotation
    ``sync:<why>``; with no current span, nothing."""
    cur = _CURRENT.get()
    if cur is None:
        yield
        return
    with annotation("sync:" + why):
        t0 = time.perf_counter()
        try:
            yield
        finally:
            dt = time.perf_counter() - t0
            attrs = cur.root.attrs
            with _ROOT_COUNTERS:    # a distributed statement's tasks
                attrs["host_syncs"] = attrs.get("host_syncs", 0) + 1
                attrs["host_sync_s"] = attrs.get("host_sync_s", 0.0) + dt
                by_why = attrs.setdefault("host_sync_by_why", {})
                n, s = by_why.get(why, (0, 0.0))
                by_why[why] = (n + 1, s + dt)


def host_read(x, why: str) -> np.ndarray:
    """``np.asarray(x)`` of a device array, as one ``host_sync``."""
    with host_sync(why):
        return np.asarray(x)


#: ``jax.monitoring`` duration events of a program on its way to the
#: device, and the slot of ``lowerings_by_program``'s row each adds to
_LOWERING_EVENTS = {
    "/jax/core/compile/jaxpr_trace_duration": 1,
    "/jax/core/compile/jaxpr_to_mlir_module_duration": 2,
    "/jax/core/compile/backend_compile_duration": 3,
}
_listening = False


def _on_lowering(event: str, seconds: float, fun_name: str = "?", **_):
    """The process's one ``jax.monitoring`` duration listener.  JAX
    calls it on the thread that traces, lowers or compiles, whose
    current span is the statement's (a task's, an exchange's): the
    statement's root gets ``lowerings`` (programs lowered to an MLIR
    module, each one request to the backend), ``lowering_s`` (seconds
    of the three events together) and ``lowerings_by_program`` =
    ``{name: [traces, trace_s, lower_s, compile_s]}``.

    What fires when (JAX 0.9, checked on the CPU backend): a call that
    hits jit's in-memory cache fires nothing; a program not seen by
    this process fires all three, also when the persistent compile
    cache answers — ``compile_s`` is then the retrieval.  The trace
    event fires for every jitted function traced on the way, jnp's own
    inside a program's body too (under their own names, with no lower
    or compile seconds, and inside the outer program's ``trace_s``), so
    ``lowering_s`` bounds the thread-seconds from above and a name's
    ``traces`` is not a count of programs.  With no current span
    (tracing off, or outside a statement) it returns at once."""
    cur = _CURRENT.get()
    if cur is None:
        return
    slot = _LOWERING_EVENTS.get(event)
    if slot is None:
        return
    if fun_name.startswith("jit(") and fun_name.endswith(")"):
        fun_name = fun_name[4:-1]       # lower / compile say jit(<name>)
    attrs = cur.root.attrs
    with _ROOT_COUNTERS:
        if slot == 2:
            attrs["lowerings"] = attrs.get("lowerings", 0) + 1
        attrs["lowering_s"] = attrs.get("lowering_s", 0.0) + seconds
        row = attrs.setdefault("lowerings_by_program", {}).setdefault(
            fun_name, [0, 0.0, 0.0, 0.0])
        if slot == 1:
            row[0] += 1
        row[slot] += seconds


def _listen_for_lowerings():
    """Install ``_on_lowering``, once a process (when its first root
    opens: a process that never traces a statement never listens)."""
    global _listening
    with _ROOT_COUNTERS:
        if _listening:
            return
        _listening = True
    import jax

    jax.monitoring.register_event_duration_secs_listener(_on_lowering)


def add_driver_spans(tracer: Tracer, driver, parent) -> int:
    """Emit one span per operator of a finished Driver from its
    collected stats (the driver records first/last activity timestamps;
    span duration is the operator's BUSY wall so operator spans of one
    task sum to ~the task's execution wall). Returns spans emitted."""
    if not tracer.enabled or not getattr(driver, "collect_stats", False):
        return 0
    anchor = getattr(driver, "epoch_anchor", None)
    if anchor is None:
        return 0
    # pull operator-reported metrics (the scans', aggregations' and
    # joins' counters below) into the stats entries so the spans carry
    # them — streaming output drivers have no other stats-rendering path
    collect = getattr(driver, "collect_operator_metrics", None)
    if collect is not None:
        collect()
    epoch0, pc0 = anchor
    parent_id = parent.span_id if isinstance(parent, Span) else \
        parse_context(parent)[1]
    n = 0
    for i, st in enumerate(driver.stats):
        if st.first_ns == 0:
            continue  # operator never ran a quantum
        start = epoch0 + (st.first_ns - pc0) / 1e9
        span = {
            "trace_id": tracer.trace_id, "span_id": _new_id(),
            "parent_id": parent_id, "name": st.name,
            "process": tracer.process, "start": start,
            "end": start + st.wall_ns / 1e9,
            # perf_counter_ns and perf_counter are one clock
            "t0": st.first_ns / 1e9,
            "t1": (st.first_ns + st.wall_ns) / 1e9,
            "attrs": {"rows": st.output_rows, "pages": st.output_pages,
                      "busy_ms": round(st.wall_ns / 1e6, 3),
                      "compiles": st.compile_count,
                      "span_kind": "operator",
                      "last_activity": epoch0 + (st.last_ns - pc0) / 1e9},
        }
        # profiler cost attribution (EXPLAIN ANALYZE VERBOSE): the span
        # carries its operator's flops/bytes/compile wall so the
        # critical path can split compile-vs-execute
        if st.flops or st.compile_ms:
            span["attrs"]["flops"] = st.flops
            span["attrs"]["device_bytes"] = st.device_bytes
            span["attrs"]["compile_ms"] = round(st.compile_ms, 3)
        if i:
            # what the operator before it in the chain handed it
            span["attrs"]["input_rows"] = driver.stats[i - 1].output_rows
        if st.metrics:
            # the scan operator's host-side counters, the aggregation's
            # partial widths, merges, groups and probe rounds, the
            # join's type and probe counters and the build's index,
            # under their names
            for key in ("generate_s", "upload_s", "wait_s",
                        "readahead_pages", "readahead_ready",
                        "resident_pages", "resident_bytes",
                        "local_bytes", "transferred_bytes",
                        "uploaded_bytes", "df_member_pages",
                        "df_table_pages", "partial_lanes",
                        "merge_calls", "merge_lanes", "groups_out",
                        "probe_rounds", "probe_rounds_narrow",
                        "join_type", "probe_pages", "direct_probe_pages",
                        "direct_table_bytes", "probe_fallback",
                        "probe_lanes", "expand_lanes", "expand_rows",
                        "residual_lanes", "residual_rows",
                        "build_row_lanes", "key_mode", "build_lanes",
                        "build_carried_cols"):
                if st.metrics.get(key) is not None:
                    span["attrs"][key] = st.metrics[key]
        tracer._record(span)
        n += 1
    return n


# -- tree assembly + analysis ---------------------------------------------


def span_tree(spans: List[dict]) -> Tuple[List[dict],
                                          Dict[str, List[dict]],
                                          List[dict]]:
    """(roots, children-by-parent-id, orphans). An orphan is a non-root
    span whose parent_id matches no span in the set — the connectivity
    property the distributed assembly must preserve."""
    by_id = {s["span_id"]: s for s in spans}
    children: Dict[str, List[dict]] = {}
    roots, orphans = [], []
    for s in spans:
        pid = s.get("parent_id")
        if pid is None:
            roots.append(s)
        elif pid in by_id:
            children.setdefault(pid, []).append(s)
        else:
            orphans.append(s)
    for kids in children.values():
        kids.sort(key=lambda s: s["start"])
    return roots, children, orphans


def critical_path(spans: List[dict]) -> List[dict]:
    """Root-to-leaf chain following, at each level, the child whose end
    time is latest — the spans that bound the query's wall clock."""
    roots, children, _ = span_tree(spans)
    if not roots:
        return []
    path = [max(roots, key=lambda s: s["end"] - s["start"])]
    while True:
        kids = children.get(path[-1]["span_id"])
        if not kids:
            return path
        path.append(max(kids, key=lambda s: s["end"]))


def trace_line(spans: List[dict]) -> Optional[str]:
    """One EXPLAIN ANALYZE line: the critical path with per-span
    durations, plus tree-health counts.  When operator spans carry
    profiler cost attribution (VERBOSE runs), the line also splits the
    path's wall into compile vs execute — the "why was it slow"
    attribution PR 6's where-did-time-go line could not give."""
    if not spans:
        return None
    path = critical_path(spans)
    _, _, orphans = span_tree(spans)
    steps = " > ".join(
        f"{s['name']} {(s['end'] - s['start']) * 1e3:.1f}ms"
        for s in path)
    line = (f"Trace: {len(spans)} spans ({len(orphans)} orphans), "
            f"critical path: {steps}")
    # compile wall over the WHOLE tree (operator spans are leaves, so
    # no double counting): the critical path frequently ends on a
    # consumer waiting at an exchange while the compile burned inside
    # producer tasks — attribution must not vanish with it.  Summed
    # compile can exceed the root wall when processes compile in
    # parallel; execute clamps at zero.
    compile_ms = sum(s.get("attrs", {}).get("compile_ms", 0.0)
                     for s in spans)
    if compile_ms:
        total_ms = (path[0]["end"] - path[0]["start"]) * 1e3
        line += (f" [compile {compile_ms:.1f}ms / execute "
                 f"{max(total_ms - compile_ms, 0.0):.1f}ms]")
    return line


def sync_line(spans: List[dict]) -> Optional[str]:
    """One EXPLAIN ANALYZE line: the statement's blocking
    device-to-host reads (``host_sync``) by site, longest wait first —
    where the host stood waiting for the device."""
    sites: Dict[str, Tuple[int, float]] = {}
    for s in spans:
        if s.get("parent_id") is None:
            sites.update(s.get("attrs", {}).get("host_sync_by_why", {}))
    if not sites:
        return None
    by_wait = sorted(sites.items(), key=lambda kv: -kv[1][1])
    return (f"Host syncs: {sum(n for n, _ in sites.values())} blocking "
            f"reads, {sum(t for _, t in sites.values()) * 1e3:.1f}ms "
            "waiting (" + ", ".join(
                f"{why} {n}x {t * 1e3:.1f}ms" for why, (n, t) in by_wait)
            + ")")


def lowering_line(spans: List[dict], top: int = 4) -> Optional[str]:
    """One EXPLAIN ANALYZE line: the programs the statement lowered
    (``_on_lowering``), those that took longest first."""
    programs: Dict[str, list] = {}
    count, seconds = 0, 0.0
    for s in spans:
        if s.get("parent_id") is None:
            attrs = s.get("attrs", {})
            count += attrs.get("lowerings", 0)
            seconds += attrs.get("lowering_s", 0.0)
            programs.update(attrs.get("lowerings_by_program", {}))
    if not programs:
        return None
    by_seconds = sorted(((name, row) for name, row in programs.items()
                         if row[2] or row[3]),
                        key=lambda kv: -sum(kv[1][1:]))
    shown = ", ".join(f"{name} {row[0]}x {sum(row[1:]) * 1e3:.1f}ms"
                      for name, row in by_seconds[:top])
    more = ", …" if len(by_seconds) > top else ""
    return (f"Lowerings: {count} programs, {seconds * 1e3:.1f}ms"
            + (f" ({shown}{more})" if shown else ""))


def slow_query_record(spans: Optional[List[dict]], wall_ms: float,
                      threshold_s: float,
                      worst_misestimate: Optional[dict] = None) -> dict:
    """The structured slow-query log record
    (``slow_query_log_threshold``): wall + threshold, the trace
    critical path, the top-3 cost-attributed operators (by busy wall,
    carrying flops/compile-ms when the profiler recorded them), and —
    when history-based statistics recorded the run — the worst-Q-error
    plan node (name, estimate, actual): misestimates surface exactly
    where slow queries are triaged.  One builder shared by every
    runner so the system.runtime.queries renderings cannot drift."""
    record = {"wall_ms": round(wall_ms, 2), "threshold_s": threshold_s,
              "critical_path": None, "top_operators": [],
              "worst_misestimate": worst_misestimate}
    if spans:
        record["critical_path"] = [
            {"name": s["name"],
             "ms": round((s["end"] - s["start"]) * 1e3, 1)}
            for s in critical_path(spans)]
        ops = [s for s in spans
               if s.get("attrs", {}).get("span_kind") == "operator"]
        ops.sort(key=lambda s: -s["attrs"].get("busy_ms", 0.0))
        record["top_operators"] = [
            {"name": s["name"],
             "busy_ms": s["attrs"].get("busy_ms", 0.0),
             "flops": s["attrs"].get("flops", 0.0),
             "compile_ms": s["attrs"].get("compile_ms", 0.0)}
            for s in ops[:3]]
    return record


# -- OTLP JSON-over-HTTP export --------------------------------------------


def to_otlp(spans: List[dict], service: str = "trino-tpu") -> dict:
    """The OTLP/HTTP JSON body (`ExportTraceServiceRequest`): one
    resourceSpans entry per process, span/trace ids zero-padded to the
    OTLP widths (16/8 bytes hex), attrs as typed attribute pairs."""
    by_process: Dict[str, List[dict]] = {}
    for s in spans:
        by_process.setdefault(s.get("process") or "?", []).append(s)

    def attr_value(v):
        if isinstance(v, bool):
            return {"boolValue": v}
        if isinstance(v, int):
            return {"intValue": str(v)}
        if isinstance(v, float):
            return {"doubleValue": v}
        return {"stringValue": str(v)}

    resource_spans = []
    for process, group in sorted(by_process.items()):
        otlp_spans = []
        for s in group:
            attrs = [{"key": k, "value": attr_value(v)}
                     for k, v in sorted(s.get("attrs", {}).items())
                     if isinstance(v, (str, int, float, bool))]
            span = {
                "traceId": (s.get("trace_id") or "").rjust(32, "0"),
                "spanId": (s.get("span_id") or "").rjust(16, "0"),
                "name": s["name"],
                "kind": 1,  # SPAN_KIND_INTERNAL
                "startTimeUnixNano": str(int(s["start"] * 1e9)),
                "endTimeUnixNano": str(int(s["end"] * 1e9)),
                "attributes": attrs,
            }
            if s.get("parent_id"):
                span["parentSpanId"] = s["parent_id"].rjust(16, "0")
            otlp_spans.append(span)
        resource_spans.append({
            "resource": {"attributes": [
                {"key": "service.name",
                 "value": {"stringValue": f"{service}:{process}"}}]},
            "scopeSpans": [{"scope": {"name": "trino-tpu"},
                            "spans": otlp_spans}],
        })
    return {"resourceSpans": resource_spans}


def export_otlp(endpoint: str, spans: List[dict],
                timeout: float = 2.0) -> bool:
    """Best-effort POST of the finished span tree to an OTLP/HTTP
    collector (``tracing_otlp_endpoint``).  Returns True on a 2xx ack;
    every failure — bad endpoint, refused connection, non-2xx — is
    swallowed (an observability export must never fail or stall a
    query; the reference exporter contract)."""
    if not endpoint or not spans:
        return False
    import json as _json
    import urllib.request

    try:
        body = _json.dumps(to_otlp(spans)).encode()
        req = urllib.request.Request(
            endpoint, data=body,
            headers={"Content-Type": "application/json"},
            method="POST")
        with urllib.request.urlopen(req, timeout=timeout) as resp:
            return 200 <= resp.status < 300
    except Exception:  # qlint: ignore[taxonomy] span export is best-effort: a dead collector must never fail the query path
        return False
