"""Distributed query execution: fragment DAG over N in-process workers.

Reference analog: ``testing/trino-testing/.../DistributedQueryRunner.java``
(N TestingTrinoServers in one JVM) driving the fragment execution of
``execution/scheduler/PipelinedQueryScheduler.java``. Here: every
fragment runs ``n_workers`` parallel tasks (threads — JAX releases the
GIL during device compute); stage boundaries are OutputBuffers fed by
PartitionedOutputOperators. Stages execute bottom-up with a barrier per
fragment, i.e. the spooled-exchange (fault-tolerant) execution shape;
the streaming pipelined overlap and the device-collective all_to_all
boundary (parallel/exchange.py) layer on top of the same fragment
contract.

Cache-coherence note (round 17): in-process workers share this
process's ``cache.template_seeds()`` and ``telemetry.stats_store``
singletons, so template-earn state and HBO history are trivially
coherent here — the configure()/heartbeat seed piggyback lives in the
multi-process runner (``parallel/process_runner.py``), where each
worker owns its own stores.
"""

from __future__ import annotations

from typing import Dict, List, Optional

from .. import session_properties as SP
from .. import types as T
from ..block import Page
from ..connectors.spi import Connector
from ..exec.local_planner import (LocalExecutionPlanner,
                                  PhysicalPipeline, grouping_options)
from ..ops.output import OutputBuffer, PartitionedOutputOperator
from ..planner.exchanges import add_exchanges
from ..planner.fragmenter import (PlanFragment, fragment_plan,
                                  fragments_fingerprint, fragments_str)
from ..planner.logical_planner import LogicalPlanner, Metadata
from ..planner.optimizer import optimize
from ..planner.plan import OutputNode
from ..runner import QueryResult
from ..sql import ast
from ..sql.analyzer import Session
from ..sql.parser import parse_statement


class DistributedQueryRunner:
    """Executes SQL over a simulated multi-worker cluster in one
    process."""

    def __init__(self, connectors: Dict[str, Connector],
                 session: Optional[Session] = None,
                 n_workers: Optional[int] = None,
                 desired_splits: int = 8,
                 broadcast_threshold: Optional[float] = None):
        from .. import session_properties as SP

        connectors = dict(connectors)
        if "system" not in connectors:
            # in-process workers share this runner's memory, so system
            # tables work without the coordinator-routing the
            # multi-process runner needs
            from ..connectors.system import SystemConnector

            connectors["system"] = SystemConnector(source=self)
        self.metadata = Metadata(connectors)
        self.session = session or Session(
            catalog=next(iter(connectors), None))
        self.n_workers = n_workers if n_workers is not None \
            else SP.value(self.session, "task_concurrency")
        self.desired_splits = desired_splits
        self.broadcast_threshold = broadcast_threshold \
            if broadcast_threshold is not None \
            else SP.value(self.session, "broadcast_join_threshold")
        from ..cache import PlanCache

        #: fragment-plan cache (same PlanCache + key discipline as the
        #: local runner's): repeat statements skip plan/optimize/
        #: exchange planning, and a MATERIAL history misestimate on a
        #: decision node — join inputs, grouped aggs, and the
        #: DISTRIBUTION build sides — invalidates the shape so the
        #: next run re-plans from history
        self.plan_cache = PlanCache()

    # ------------------------------------------------------------------

    def metrics_families(self) -> list:
        """system.runtime.metrics source: the in-process runner exports
        the process-level families (jit traces, exchange counters)."""
        from ..telemetry.metrics import process_families

        return process_families()

    def create_fragments(self, sql_or_stmt,
                         hbo=None) -> List[PlanFragment]:
        stmt = sql_or_stmt if isinstance(sql_or_stmt, ast.Statement) \
            else parse_statement(sql_or_stmt)
        planner = LogicalPlanner(self.metadata, self.session)
        root = planner.plan(stmt)
        from .. import session_properties as SP

        root = optimize(root, self.metadata, planner.allocator,
                        self.session, hbo=hbo)
        trace = getattr(root, "optimizer_trace", None)
        root = add_exchanges(
            root, self.metadata, planner.allocator,
            self.broadcast_threshold,
            SP.value(self.session, "join_distribution_type"),
            scale_writers=SP.value(self.session, "scale_writers_enabled"),
            hbo=hbo if SP.value(self.session,
                                "hbo_distribution_enabled") else None)
        if trace is not None:  # exchange planning rebuilt the root node
            root.optimizer_trace = trace
        self._root = root
        self._fragments = fragment_plan(root)
        return self._fragments

    def explain(self, sql: Optional[str], stmt=None) -> str:
        from ..planner.optimizer import provenance_lines

        if stmt is None:
            stmt = parse_statement(sql)
        # EXPLAIN plans through the statement's history view, so the
        # rendered join order and distribution choices are exactly
        # what the next execution would run
        text = fragments_str(self.create_fragments(
            stmt, hbo=self._hbo_context(stmt)))
        prov = provenance_lines(self._root)
        return text + ("\n" + "\n".join(prov) if prov else "")

    def execute(self, sql: str) -> QueryResult:
        """One statement.  Its spans hang under the caller's current
        span (``ProtocolServer`` enters ``statement.run``), or under a
        ``statement`` root of the call's own, as the local runner's do:
        ``parse``, ``plan``, ``execute`` and under that one ``task`` a
        task, inside which the task's operators run."""
        from ..telemetry import tracing

        with tracing.root_scope(
                "statement", SP.value(self.session,
                                      "query_tracing_enabled"),
                served_by="solo", batch_size=1):
            res = self._execute_sql(sql)
            cur = tracing.current_span()
            if cur is not None:
                res.stats = dict(res.stats or {},
                                 trace=cur.tracer.finished())
            return res

    def _execute_sql(self, sql: str) -> QueryResult:
        from ..telemetry import tracing

        with tracing.span("parse"):
            stmt = parse_statement(sql)
        if isinstance(stmt, ast.Explain) and stmt.analyze and \
                isinstance(stmt.statement, (ast.QueryStatement,
                                            ast.Insert,
                                            ast.CreateTableAsSelect)):
            # DML included: the writer path's exchange surface (scaled
            # writers' rebalance counters) is only observable here
            return self._explain_analyze(stmt.statement,
                                         verbose=stmt.verbose)
        if not isinstance(stmt, ast.QueryStatement):
            if isinstance(stmt, (ast.Insert, ast.CreateTableAsSelect)):
                # writes distribute: scaled writer tasks in the source
                # stage, rowcounts summed (exchanges._v_TableWriterNode)
                return self._execute_query(stmt)
            # remaining DDL doesn't distribute; delegate
            from ..runner import LocalQueryRunner

            return LocalQueryRunner(self.metadata.connectors,
                                    self.session).execute(sql)
        return self._execute_query(stmt)

    def _explain_analyze(self, stmt: ast.QueryStatement,
                         verbose: bool = False) -> QueryResult:
        """Distributed EXPLAIN ANALYZE: run collecting the query/stage/
        task stats tree and render it (reference: the QueryStats
        hierarchy + planprinter; round-2 verdict flagged its absence).
        VERBOSE enables the compiled-program profiler so per-operator
        rows carry flops / bytes / compile-ms and a Kernels line shows
        what this run compiled vs reused."""
        from ..telemetry import profiler

        before = profiler.totals() if verbose else None
        with profiler.profiling(verbose):
            res = self._execute_query(stmt, collect_stats=True)
        tree = res.stats["query_stats"]
        # _execute_query already planned + fragmented; render those
        lines = fragments_str(self._fragments).splitlines()
        lines.append("")
        lines.extend(tree.render())
        if verbose:
            from ..runner import _kernels_line

            lines.append(_kernels_line(before, profiler.totals()))
        from ..telemetry import tracing

        spans = tracing.snapshot()
        for line in (tracing.sync_line(spans),
                     tracing.lowering_line(spans)):
            if line:
                lines.append(line)
        return QueryResult(["Query Plan"], [T.VARCHAR],
                           [(line,) for line in lines],
                           stats={"query_stats": tree.to_dict()})

    def _execute_query(self, stmt: ast.QueryStatement,
                       collect_stats: bool = False) -> QueryResult:
        """Profiling envelope around the execution body: the
        ``query_profiling_enabled`` session knob turns the compiled-
        program registry on for this query (EXPLAIN ANALYZE VERBOSE
        layers its own ``profiling(True)`` on top)."""
        from ..telemetry.profiler import profiling

        with profiling(SP.value(self.session,
                                "query_profiling_enabled")):
            return self._execute_query_body(stmt, collect_stats)

    def _hbo_context(self, stmt):
        """History-based-statistics binding (same exclusions as the
        local runner: hbo_enabled off, non-queries, unversioned
        catalogs -> None)."""
        if not SP.value(self.session, "hbo_enabled"):
            return None
        from ..telemetry.stats_store import HboContext

        return HboContext.for_statement(
            stmt, self.session, self.metadata,
            alpha=SP.value(self.session, "hbo_ewma_alpha"))

    def _execute_query_body(self, stmt: ast.QueryStatement,
                            collect_stats: bool = False) -> QueryResult:
        import time as _time

        from ..exec.stats import QueryStatsTree, StageStatsTree

        from ..telemetry import tracing

        with tracing.span("plan") as plan_span:
            self._hbo = hbo_ctx = self._hbo_context(stmt)
            key = self._plan_cache_key(stmt)
            cached = self.plan_cache.lookup(key) \
                if key is not None else None
            plan_hit = cached is not None
            plan_span.set("plan_cache", "hit" if plan_hit else "miss")
            if cached is not None:
                self._root, self._fragments = cached
                fragments = self._fragments
            else:
                fragments = self.create_fragments(stmt, hbo=hbo_ctx)
                if key is not None:
                    self.plan_cache.store(
                        key, (self._root, self._fragments), 128)
            if plan_span:
                # which fragment plan ran, and of which statement
                # shape, as the local runner's root says: a shape whose
                # plan_fp moves between statements was re-planned
                plan_span.root.attrs["plan_fp"] = \
                    fragments_fingerprint(fragments)
                if key is not None:
                    from ..telemetry.stats_store import \
                        statement_fingerprint

                    plan_span.root.attrs["shape_fp"] = \
                        statement_fingerprint(key[0])
        self._plan_shape = key[0] if key is not None else None
        root: OutputNode = self._root
        buffers: Dict[int, OutputBuffer] = {}
        result_pages: List[Page] = []
        from ..exec.memory import pool_from_session

        # one pool per query across all tasks: device HBM is a
        # per-process resource (reference: ClusterMemoryManager enforcing
        # a query's global limit over per-node reservations)
        self._memory_pool = pool_from_session(self.session)
        self._stage_stats: List[StageStatsTree] = []
        # history recording needs per-operator row counts, so HBO turns
        # the stats-collecting driver path on even for plain execute()
        self._collect_stats = collect_stats or hbo_ctx is not None
        t0 = _time.perf_counter()

        # tasks run as cooperative generators on the process-wide
        # TaskExecutor: concurrent queries time-share the pool through
        # the multilevel feedback queue instead of each query pinning
        # its own threads (reference: TaskExecutor.java per worker JVM)
        from ..exec.task_executor import shared_executor

        executor = shared_executor()
        streaming = SP.value(self.session, "streaming_execution")
        try:
            # the parent of the tasks' spans: they run on the
            # executor's threads, where no span is current
            with tracing.span("execute") as self._exec_span:
                if streaming:
                    result_pages = self._execute_streaming(
                        executor, fragments, root, buffers)
                else:
                    for frag in fragments:
                        ntasks = 1 if frag.partitioning == "single" \
                            else self.n_workers
                        if frag.output_kind == "output":
                            collected = self._run_output_fragment(
                                executor, frag, root, ntasks, buffers)
                            result_pages = collected
                        else:
                            buffers[frag.fragment_id] = \
                                self._run_fragment(executor, frag, ntasks,
                                                   buffers)

            with tracing.span("fetch_rows"):
                rows: List[tuple] = []
                for p in result_pages:
                    rows.extend(p.to_rows())
            stats = {"memory": self._memory_pool.stats()}
        except BaseException:
            # reap spill files + free residue even when the query dies
            self._memory_pool.close()
            self._take_back_created(root)
            raise
        names = root.column_names
        types_ = [s.type for s in root.outputs]
        if streaming:
            stats["streaming_overlap"] = {
                fid: buf.overlapped for fid, buf in buffers.items()
                if isinstance(buf, OutputBuffer)}
        if plan_hit:
            stats["plan_cache"] = "hit"
        if hbo_ctx is not None:
            summary = self._hbo_record(hbo_ctx, root, stats)
            if summary:
                stats["hbo"] = summary
        if collect_stats:
            # attach each stage's output-boundary exchange skew stats —
            # only now, after every consumer ran: the device collective
            # is consumer-triggered, so producer-stage completion would
            # be too early to read it
            by_stage = {s.stage_id: s for s in self._stage_stats}
            for fid, buf in buffers.items():
                stage = by_stage.get(fid)
                if stage is not None:
                    stage.exchange = getattr(buf, "stats", None)
            tree = QueryStatsTree(
                stages=self._stage_stats,
                wall_ms=(_time.perf_counter() - t0) * 1e3,
                memory=self._memory_pool.stats())
            if hbo_ctx is not None:
                tree.estimates = self._hbo_estimates
                tree.worst_misestimate = (stats.get("hbo") or
                                          {}).get("worst")
            stats["query_stats"] = tree
        self._memory_pool.close()  # reap spill files, free residue
        return QueryResult(names, types_, rows, stats=stats)

    def _take_back_created(self, root):
        """A failed CTAS leaves no table.  The writer task that failed
        dropped the target (``TableWriterOperator``'s undo), but every
        task creates it where it finds none, so one that started after
        that drop has made it again (the analyzer rejected a target
        that was there before the statement)."""
        from ..planner.plan import TableWriterNode

        stack = [root]
        while stack:
            node = stack.pop()
            stack.extend(node.sources)
            if isinstance(node, TableWriterNode) and node.create:
                md = self.metadata.connectors[node.catalog].metadata()
                handle = md.get_table_handle(node.schema, node.table_name)
                if handle is not None:
                    md.drop_table(handle)

    def _plan_cache_key(self, stmt) -> Optional[tuple]:
        """Fragment-plan cache key, or None when uncacheable: mirrors
        the local runner's discipline (shape + literals + session and
        snapshot fingerprints — SET SESSION and DDL/writes move the
        key), plus the planning inputs owned by this runner."""
        if not SP.value(self.session, "plan_cache_enabled"):
            return None
        if not isinstance(stmt, ast.QueryStatement):
            return None
        from ..cache import (normalize_statement, session_fingerprint,
                             snapshot_fingerprint, statement_catalogs)

        shape, literals = normalize_statement(stmt)
        snap = snapshot_fingerprint(
            statement_catalogs(stmt, self.session), self.metadata)
        if snap is None:
            return None
        return (shape, literals, session_fingerprint(self.session),
                snap, self.n_workers, self.desired_splits,
                self.broadcast_threshold)

    def _hbo_record(self, hbo_ctx, root, stats) -> Optional[dict]:
        """Fold this query's per-node actuals (summed across every
        stage's tasks) into the history store; stashes the estimate
        map for EXPLAIN ANALYZE's per-node Q-error rendering.  A
        material misestimate on a decision node (join input, grouped
        agg, or a DISTRIBUTION build side) drops cached fragment plans
        of the shape — the next run re-plans against history."""
        op_stats = [o for s in self._stage_stats
                    for t in s.tasks for o in t.operators]
        est = hbo_ctx.estimates(root, self.metadata)
        self._hbo_estimates = est[0]
        scan_rows = sum(o.output_rows for o in op_stats
                        if o.name == "TableScanOperator")
        mem = stats.get("memory") or {}
        summary = hbo_ctx.record(root, self.metadata, op_stats,
                                 peak_bytes=mem.get("peak_bytes", 0),
                                 scan_rows=scan_rows, estimates=est)
        shape = getattr(self, "_plan_shape", None)
        if summary and summary["material"] and shape is not None:
            self.plan_cache.invalidate_shape(shape)
        return summary

    # ----------------------------------------------- streaming mode ----

    def _execute_streaming(self, executor, fragments, root: OutputNode,
                           buffers: Dict[int, "OutputBuffer"]):
        """All stages run CONCURRENTLY: every fragment's tasks are
        submitted at once, exchange sources consume pages as producers
        enqueue them (parking on listen tokens while empty), and
        bounded buffers push backpressure upstream (reference:
        execution/scheduler/PipelinedQueryScheduler.java:155)."""
        import threading

        from ..exec.stats import StageStatsTree

        max_pending = SP.value(self.session, "exchange_max_pending_pages")
        plans = []
        for frag in fragments:
            ntasks = 1 if frag.partitioning == "single" \
                else self.n_workers
            out = None
            if frag.output_kind != "output":
                device_ex = self._device_exchange_for(frag, ntasks)
                if device_ex is not None:
                    out = device_ex
                elif frag.output_kind == "single":
                    out = OutputBuffer(1, max_pending_pages=max_pending)
                elif frag.output_kind == "merge":
                    # one partition PER PRODUCER: each task's sorted run
                    # stays separate for the consumer's k-way merge
                    out = OutputBuffer(ntasks,
                                       max_pending_pages=max_pending)
                elif frag.output_kind == "broadcast":
                    out = OutputBuffer(self.n_workers, broadcast=True)
                else:
                    out = OutputBuffer(self.n_workers,
                                       max_pending_pages=max_pending)
                    out.rebalancer = self._rebalancer_for(frag)
                buffers[frag.fragment_id] = out
            plans.append((frag, ntasks, out))

        futures = []
        stages = []
        results: List[List[Page]] = []
        for frag, ntasks, out in plans:
            stage = StageStatsTree(frag.fragment_id, frag.partitioning,
                                   frag.output_kind)
            stages.append(stage)
            is_output = frag.output_kind == "output"
            if is_output:
                results = [[] for _ in range(ntasks)]
            # producers-done wiring: the LAST task of the fragment to
            # exit (normally or not) marks the stream ended, so
            # consumers always unblock
            remaining = [ntasks]
            rlock = threading.Lock()

            def wrapped(gen, out=out, remaining=remaining, rlock=rlock):
                try:
                    yield from gen
                finally:
                    with rlock:
                        remaining[0] -= 1
                        last = remaining[0] == 0
                    if last and out is not None:
                        out.set_no_more_pages()

            for t in range(ntasks):
                gen = self._task_gen(frag, ntasks, t, out, buffers,
                                     stage, root if is_output else None,
                                     results if is_output else None,
                                     streaming=True)
                futures.append(executor.submit(wrapped(gen)))

        self._wait_all(futures,
                       [b for b in buffers.values()])
        if getattr(self, "_collect_stats", False):
            for stage in stages:
                stage.tasks.sort(key=lambda t: t.task_id)
                self._stage_stats.append(stage)
        return [p for r in results for p in r]

    def _wait_all(self, futures, bufs):
        """Wait for every task; on the first error, abort all buffers so
        parked producers/consumers unwind instead of deadlocking, then
        keep waiting so no generator outlives the query."""
        errors: List[BaseException] = []
        aborted = False
        pending = list(futures)
        while pending:
            still = []
            for f in pending:
                if f._event.wait(0.02):
                    if f._error is not None:
                        errors.append(f._error)
                else:
                    still.append(f)
            if errors and not aborted:
                aborted = True
                for b in bufs:
                    b.abort()
            pending = still
        if errors:
            raise errors[0]

    # ------------------------------------------------------------------

    def _make_reader(self, buffers: Dict[int, OutputBuffer], task_id: int,
                     streaming: bool = False):
        def reader(fragment_id: int, kind: str):
            buf = buffers[fragment_id]
            if kind == "merge":
                # per-producer sorted streams for the k-way merge
                if streaming:
                    return [buf.channel(p)
                            for p in range(buf.num_partitions)]
                return [(lambda p=p: buf.pages(p))
                        for p in range(buf.num_partitions)]
            part = 0 if kind == "single" else task_id
            if streaming:
                from .device_exchange import DeviceExchange

                if isinstance(buf, DeviceExchange):
                    return buf.channel(part)
                return buf.channel(part, consumer_id=task_id)

            def thunk():
                return buf.pages(part)

            return thunk

        return reader

    def _task_gen(self, frag: PlanFragment, ntasks: int, t: int, out,
                  buffers, stage, root: Optional[OutputNode],
                  results: Optional[List[List[Page]]],
                  streaming: bool = False):
        """Task ``t`` placed on its device: worker ``t``'s operators run
        on device ``t % d`` — the same layout the device exchange uses
        for its slabs, so a task's pages are already where the
        collective reads them and its partition arrives where it runs."""
        import jax

        from ..telemetry import tracing
        from .device_exchange import task_device

        device = task_device(t, self.n_workers, jax.devices())
        parent = getattr(self, "_exec_span", None)
        span = parent.tracer.span(
            "task", parent=parent, fragment=frag.fragment_id, task=t,
            device=device.id) if parent else tracing.NULL_SPAN
        # a thread's line in a profile says whose quantum it ran
        label = f"task:f{frag.fragment_id}.t{t}"
        steps = self._task_steps(frag, ntasks, t, out, buffers, stage,
                                 root, results, streaming, span)
        # jax.default_device and the current span are the thread's and
        # the executor may resume a task on another thread: enter them
        # around each quantum, never across a yield
        try:
            while True:
                with jax.default_device(device), \
                        tracing.use_span(span, label):
                    try:
                        item = next(steps)
                    except StopIteration:
                        return
                yield item
        finally:
            span.finish()

    def _task_steps(self, frag: PlanFragment, ntasks: int, t: int, out,
                    buffers, stage, root: Optional[OutputNode],
                    results: Optional[List[List[Page]]],
                    streaming: bool = False, span=None):
        """One task of one fragment as a cooperative generator. ``out``
        is the fragment's output (OutputBuffer | DeviceExchange | None
        for the output fragment, which collects into ``results[t]``).
        In streaming mode a no-progress quantum yields Blocked(tokens)
        so the executor parks the task.  ``span`` is the task's span:
        its drivers' operator spans hang under it."""
        import jax

        from ..exec.driver import Driver
        from ..exec.local_planner import project_to_wire_layout
        from ..exec.stats import TaskStatsTree
        from ..exec.task_executor import Blocked
        from ..telemetry import tracing
        from .device_exchange import task_device

        devices = jax.devices()
        planner = LocalExecutionPlanner(
            self.metadata, self.desired_splits, task_id=t,
            task_count=ntasks,
            # where each task of this fragment runs: an addressed split
            # goes to the task on its device
            task_devices=[task_device(i, self.n_workers, devices).id
                          for i in range(ntasks)],
            exchange_reader=self._make_reader(buffers, t, streaming),
            memory_pool=self._memory_pool,
            join_max_lanes=SP.value(self.session,
                                    "join_max_expand_lanes"),
            dynamic_filtering=SP.value(
                self.session, "enable_dynamic_filtering"),
            scan_coalesce=SP.value(self.session, "scan_coalesce_enabled"),
            hbo=getattr(self, "_hbo", None),
            **grouping_options(self.session.properties))
        collect = getattr(self, "_collect_stats", False)
        task = TaskStatsTree(t)
        if root is not None:
            plan = planner.plan(OutputNode(frag.root, root.column_names,
                                           root.outputs))
            pipelines = plan.pipelines
        else:
            ops, layout, types_ = planner.visit(frag.root)
            ops, layout, types_, key_channels = project_to_wire_layout(
                frag, ops, layout, types_)
            from .device_exchange import DeviceExchange

            if isinstance(out, DeviceExchange):
                from .device_exchange import DeviceExchangeSinkOperator

                ops.append(DeviceExchangeSinkOperator(
                    types_, key_channels, out, t))
            else:
                ops.append(PartitionedOutputOperator(
                    types_, key_channels, out, frag.output_kind,
                    task_partition=t,
                    rebalancer=getattr(out, "rebalancer", None),
                    hot_split_threshold=SP.value(
                        self.session, "hot_partition_split_threshold")))
            planner.pipelines.append(PhysicalPipeline(ops))
            pipelines = planner.pipelines
        for p in pipelines:
            d = Driver(p.operators, collect_stats=collect)
            try:
                for _ in range(10_000_000):
                    if d.process():
                        break
                    if streaming:
                        # park only after a NO-PROGRESS quantum: a
                        # blocked source with runnable downstream work
                        # must keep running
                        toks = [] if d.last_moved else d.blocked_tokens()
                        yield Blocked(toks) if toks else None
                    else:
                        yield  # quantum boundary: hand the thread back
                else:
                    raise T.TrinoError("driver did not finish",
                                       "GENERIC_INTERNAL_ERROR")
            finally:
                # a task dropped between quanta leaves no scan reading
                # ahead
                d.close()
            if collect:
                d.collect_operator_metrics()
                task.operators.extend(d.stats)
                if span:
                    tracing.add_driver_spans(span.tracer, d, span)
        if root is not None and results is not None:
            results[t] = plan.sink.pages
        if collect:
            stage.tasks.append(task)

    def _rebalancer_for(self, frag: PlanFragment):
        """The scaled-writer rebalancer for a scale_writers hash
        boundary (see rebalancer.writer_rebalancer for the sharing
        contract)."""
        if frag.output_kind != "hash" or not frag.scale_writers:
            return None
        from .rebalancer import writer_rebalancer

        return writer_rebalancer(
            (str(s.type) for s in frag.output_symbols), self.n_workers,
            SP.value(self.session, "rebalance_min_collectives"))

    def _device_exchange_for(self, frag: PlanFragment, ntasks: int):
        """The flagship TPU-native path: a hash stage boundary between
        co-resident stages runs as one all_to_all collective over the
        mesh instead of host-side partitioning (SURVEY.md §2.8). Returns
        None when the fragment must take the host path."""
        from .. import session_properties as SP

        if frag.output_kind != "hash" or ntasks != self.n_workers:
            return None
        if frag.scale_writers:
            # scaled-writer boundaries rebalance on the HOST: the
            # partition->lane map mutates across pages, which a compiled
            # collective cannot follow (and writers consume host pages)
            return None
        if not SP.value(self.session, "device_exchange"):
            return None
        from .device_exchange import (DeviceExchange,
                                      device_exchange_supported)

        if not device_exchange_supported(
                [s.type for s in frag.output_symbols]):
            return None
        import jax

        # fewer devices than workers is fine: DeviceExchange lays p
        # partitions over d devices (p % d) and carries partition ids
        # through the collective, so a single real chip still executes
        # the flagship path
        devices = jax.devices()
        return DeviceExchange(
            self.n_workers, devices,
            sizing=SP.value(self.session, "device_exchange_sizing"),
            hot_split_threshold=SP.value(
                self.session, "hot_partition_split_threshold"),
            fragment_id=frag.fragment_id)

    def _run_fragment(self, executor, frag: PlanFragment, ntasks: int,
                      buffers: Dict[int, OutputBuffer]):
        # consumer partition count: single -> 1, hash -> n_workers,
        # broadcast -> replicated
        device_ex = self._device_exchange_for(frag, ntasks)
        if device_ex is not None:
            out = device_ex
        elif frag.output_kind == "single":
            out = OutputBuffer(1)
        elif frag.output_kind == "merge":
            out = OutputBuffer(ntasks)  # one partition per producer
        elif frag.output_kind == "broadcast":
            out = OutputBuffer(self.n_workers, broadcast=True)
        else:
            out = OutputBuffer(self.n_workers)
            out.rebalancer = self._rebalancer_for(frag)

        from ..exec.stats import StageStatsTree

        stage = StageStatsTree(frag.fragment_id, frag.partitioning,
                               frag.output_kind)
        executor.run_all([
            self._task_gen(frag, ntasks, t, out, buffers, stage, None,
                           None)
            for t in range(ntasks)])
        if getattr(self, "_collect_stats", False):
            stage.tasks.sort(key=lambda t: t.task_id)
            self._stage_stats.append(stage)
        return out

    def _run_output_fragment(self, executor, frag: PlanFragment,
                             root: OutputNode, ntasks: int,
                             buffers) -> List[Page]:
        from ..exec.stats import StageStatsTree

        results: List[List[Page]] = [[] for _ in range(ntasks)]
        stage = StageStatsTree(frag.fragment_id, frag.partitioning,
                               frag.output_kind)
        executor.run_all([
            self._task_gen(frag, ntasks, t, None, buffers, stage, root,
                           results)
            for t in range(ntasks)])
        if getattr(self, "_collect_stats", False):
            stage.tasks.sort(key=lambda t: t.task_id)
            self._stage_stats.append(stage)
        return [p for r in results for p in r]
