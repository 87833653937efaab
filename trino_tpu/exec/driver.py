"""Driver: the inner execution loop moving pages through an operator chain.

Reference analog: ``operator/Driver.java:380-486`` (processInternal) — walk
adjacent operator pairs, move one page per iteration, finish-propagate.
Synchronous for now; the task executor adds cooperative quanta on top
(reference: execution/executor/TaskExecutor.java).
"""

from __future__ import annotations

import contextlib
import time
from dataclasses import dataclass, field
from typing import List, Optional, Sequence

import numpy as np

from .. import jit_stats
from ..connectors.spi import ConnectorSplit
from ..telemetry import profiler, tracing
from ..ops.operator import Operator, SourceOperator


@dataclass
class OperatorStats:
    """Per-operator execution stats (reference:
    operator/OperatorStats.java — wall/cpu nanos, rows/pages in+out).
    ``compile_count`` is the number of jit traces (XLA cache misses)
    attributed to this operator's calls: after warmup it must stay flat
    for same-shape pages — silent retracing is the classic JAX perf
    bug, and this counter makes it assertable."""

    name: str
    #: live rows handed downstream.  The driver keeps each page's mask
    #: and reads the masks once, when it has finished (or when
    #: ``collect_operator_metrics`` is called): 0 until then
    output_rows: int = 0
    output_pages: int = 0
    #: wall time inside this operator's calls.  JAX dispatch is
    #: asynchronous and the driver itself never waits for the device,
    #: so this is DISPATCH time wherever the operator does not sync of
    #: its own accord (the statement root's ``host_sync_by_why`` says
    #: how long the traced blocking reads waited); it is not device time
    wall_ns: int = 0
    compile_count: int = 0
    #: XLA cost attribution (telemetry.profiler thread deltas): flops /
    #: bytes accessed by this operator's compiled programs per
    #: execution, and the compile wall it paid — all zero unless the
    #: profiler was enabled (EXPLAIN ANALYZE VERBOSE)
    flops: float = 0.0
    device_bytes: float = 0.0
    compile_ms: float = 0.0
    #: perf_counter_ns of this operator's first/last active quantum —
    #: with the driver's ``epoch_anchor`` these place the operator on a
    #: cross-process trace timeline (telemetry.tracing.add_driver_spans)
    first_ns: int = 0
    last_ns: int = 0
    #: operator-reported metrics (exchange skew stats etc.), pulled from
    #: ``op.metrics()`` once the driver finishes — the OperatorStats
    #: analog of the reference's per-operator Metrics map
    metrics: Optional[dict] = None
    #: canonical plan-node fingerprint this operator realizes (set by
    #: the local planner when history-based statistics are recording;
    #: telemetry.stats_store keys actuals by it) — None outside HBO
    node_fp: Optional[str] = None
    #: the operator sits between a scan that a dynamic filter masked
    #: and its chain's first join (set with ``metrics``): its rows are
    #: what the mask left, the count of the plan that hung the filter
    #: there and not of the operator's node, so history files nothing
    masked_input: bool = False

    def line(self) -> str:
        ms = self.wall_ns / 1e6
        base = (f"{self.name}: {self.output_rows} rows, "
                f"{self.output_pages} pages, {ms:.1f}ms, "
                f"{self.compile_count} compiles")
        if self.flops or self.device_bytes or self.compile_ms:
            base += (f" [cost {self.flops:.3g} flops, "
                     f"{self.device_bytes:.3g} bytes, "
                     f"compile {self.compile_ms:.1f}ms]")
        if self.metrics:
            m = self.metrics
            if m.get("probe_pages"):
                # the join's candidate lookup: pages answered from the
                # build's direct-address table, or why it has none
                base += (f" [probe direct {m['direct_probe_pages']}/"
                         f"{m['probe_pages']} pages")
                if m.get("direct_table_bytes"):
                    base += f", table {m['direct_table_bytes'] / 1e6:.1f} MB"
                if m.get("probe_fallback"):
                    base += f", sorted index: {m['probe_fallback']}"
                if "residual_rows" in m:
                    # a join whose key carries a residual predicate:
                    # the candidate matches it gathered and tested
                    base += (f", residual over {m['residual_rows']} rows "
                             f"in {m['residual_lanes']} lanes")
                base += (f", {m.get('build_row_lanes', 0)} build rows "
                         "through perm]")
            if "build_lanes" in m:
                # the join's build: the (key, row) index's width and
                # the columns gathered into sorted order at that width
                base += (f" [index {m['build_lanes']} lanes, "
                         f"{m.get('build_carried_cols', 0)} columns "
                         "carried]")
            if m.get("adaptive"):
                # the adaptive partial-agg decision (pass-through or
                # per-key-range split)
                base += f" [adaptive {m['adaptive']}]"
            if m.get("grouping_paths"):
                # pages by grouping path; ``dense`` are the ``hash``
                # pages few enough in groups to reduce without a scatter
                base += " [grouping " + " ".join(
                    f"{k}={v}" for k, v in m["grouping_paths"].items()) + "]"
            if m.get("partial_lanes", {}).get("pages"):
                # aggregation partials: lanes in, lanes kept for the
                # merge, and the width the last merge ran at
                pl = m["partial_lanes"]
                base += (f" [partial lanes {pl['in']}->{pl['kept']} over "
                         f"{pl['pages']} pages, merge {pl['merge']}]")
            if m.get("probe_rounds"):
                # the grouping's probe rounds over the pages whose flags
                # the step read, and those run over the unresolved alone
                base += (f" [probe rounds {m['probe_rounds']}, "
                         f"{m['probe_rounds_narrow']} narrow]")
            if m.get("resident_pages"):
                # a scan of a table that lives on the device: pages and
                # bytes taken as they lay, nothing uploaded
                # (of them ``transferred`` from another device's share)
                base += (f" [resident {m['resident_pages']} pages, "
                         f"{m['resident_bytes'] / 1e9:.2f} GB")
                if m.get("transferred_bytes"):
                    base += (f", {m['transferred_bytes'] / 1e9:.2f} GB "
                             "transferred")
                base += "]"
            extras = " ".join(
                f"{k}={m[k]}" for k in ("skew_ratio", "lane_skew_ratio",
                                        "per_dest", "a2a_retries",
                                        "sizing", "first_page_ms")
                if m.get(k) is not None)
            # split/rebalance/replay counters only when the mechanism
            # engaged (a zero on every boundary would be noise)
            extras += "".join(
                f" {k}={m[k]}" for k in ("splits", "rebalances",
                                         "reconnects", "replayed_frames")
                if m.get(k))
            if extras:
                base += f" [exchange {extras}]"
        return base


#: an operator's pending masks are read early once they hold this many
#: bytes (a thousand full pages): bounds what a long scan keeps alive
_PENDING_MASK_BYTES = 64 << 20


class Driver:
    """Executes one operator chain to completion."""

    def __init__(self, operators: Sequence[Operator],
                 collect_stats: bool = False):
        assert operators, "empty pipeline"
        self.operators: List[Operator] = list(operators)
        self.collect_stats = collect_stats
        #: whether the most recent process() quantum moved any page —
        #: tasks only park on blocked tokens after a no-progress quantum
        self.last_moved = False
        self.stats: List[OperatorStats] = [
            OperatorStats(type(op).__name__,
                          node_fp=getattr(op, "_hbo_fp", None))
            for op in operators]
        #: (epoch seconds, perf_counter_ns) at driver creation: converts
        #: the stats' first_ns/last_ns to wall-clock span timestamps
        self.epoch_anchor = (time.time(), time.perf_counter_ns()) \
            if collect_stats else None
        #: per operator, the live-row masks of the pages it handed
        #: downstream whose rows are not counted yet, and their bytes
        self._pending_masks: List[list] = [[] for _ in operators]
        self._pending_bytes = [0] * len(self.operators)
        #: the statement is traced: operator calls are profiler
        #: annotations too
        self._traced = collect_stats and \
            tracing.current_span() is not None

    @property
    def source(self) -> Optional[SourceOperator]:
        head = self.operators[0]
        return head if isinstance(head, SourceOperator) else None

    def add_split(self, split: ConnectorSplit):
        src = self.source
        assert src is not None, "pipeline has no source operator"
        src.add_split(split)

    def no_more_splits(self):
        src = self.source
        if src is not None:
            src.no_more_splits()

    def _timed_call(self, idx: int, call: str, fn, *args):
        """Run one operator call attributing wall/compiles/activity to
        stats[idx] (finish propagation and tail drains can
        do real work: an aggregation's finish builds its output state).
        With tracing on the call is the profiler annotation
        ``op:<Operator>.<call>``."""
        st = self.stats[idx]
        c0 = jit_stats.thread_total()
        p0 = profiler.thread_totals()
        with tracing.annotation(f"op:{st.name}.{call}") \
                if self._traced else contextlib.nullcontext():
            t0 = time.perf_counter_ns()
            try:
                out = fn(*args)
            finally:
                t1 = time.perf_counter_ns()
        st.wall_ns += t1 - t0
        st.compile_count += jit_stats.thread_total() - c0
        self._attribute_cost(st, p0)
        if st.first_ns == 0:
            st.first_ns = t0
        st.last_ns = t1
        return out

    @staticmethod
    def _attribute_cost(st: OperatorStats, before):
        """Fold the profiler's thread-delta (flops/bytes/compile wall
        of programs run since ``before``) into the operator stats —
        zeros end to end unless profiling is enabled."""
        flops, bytes_, compile_ms, _ = profiler.thread_totals()
        st.flops += flops - before[0]
        st.device_bytes += bytes_ - before[1]
        st.compile_ms += compile_ms - before[2]

    def process(self) -> bool:
        """One scheduling quantum: move pages between adjacent operators.
        Returns True if the driver is fully finished.  A driver that has
        finished, or whose operator raised, has closed its operators."""
        try:
            done = self._move_pages()
        except BaseException:
            self.close()
            raise
        if done:
            self.close()
        return done

    def close(self):
        """Nothing more will be asked of the operators: the source
        releases what outlives a call (a scan stopped short of its end —
        LIMIT, a failure downstream, an aborted task — stops its
        producer thread)."""
        src = self.source
        if src is not None:
            src.close()

    def _move_pages(self) -> bool:
        ops = self.operators
        moved = False
        for i in range(len(ops) - 1):
            cur, nxt = ops[i], ops[i + 1]
            # finish propagation
            if cur.is_finished() and not nxt._finishing:
                if self.collect_stats:
                    self._timed_call(i + 1, "finish", nxt.finish)
                else:
                    nxt.finish()
            if nxt.needs_input():
                if self.collect_stats:
                    page = self._timed_call(i, "get_output",
                                            cur.get_output)
                    if page is not None:
                        self._note_rows(i, page)
                else:
                    page = cur.get_output()
                if page is not None:
                    if self.collect_stats:
                        self._timed_call(i + 1, "add_input",
                                         nxt.add_input, page)
                    else:
                        nxt.add_input(page)
                    moved = True
        # drain the tail operator (sinks produce no output)
        if self.collect_stats:
            self._timed_call(len(ops) - 1, "get_output",
                             ops[-1].get_output)
        else:
            ops[-1].get_output()
        if not moved:
            # nothing moved: push finish from the head if it is done
            if ops[0].is_finished() and not ops[0]._finishing:
                if self.collect_stats:
                    self._timed_call(0, "finish", ops[0].finish)
                else:
                    ops[0].finish()
        self.last_moved = moved
        done = ops[-1].is_finished()
        if done and self.collect_stats:
            self.resolve_row_counts()
        return done

    def _note_rows(self, i: int, page):
        """Operator ``i`` handed ``page`` downstream.  Its rows are
        counted later, from its mask: no host round trip per page and
        no program of the driver's own."""
        self.stats[i].output_pages += 1
        self._pending_masks[i].append(page.valid)
        self._pending_bytes[i] += page.valid.size
        if self._pending_bytes[i] > _PENDING_MASK_BYTES:
            self._count_pending(i)

    def _count_pending(self, i: int):
        masks = self._pending_masks[i]
        if masks:
            import jax

            with tracing.host_sync("driver_row_counts"):
                self.stats[i].output_rows += sum(
                    int(np.count_nonzero(m)) for m in jax.device_get(masks))
            del masks[:]
            self._pending_bytes[i] = 0

    def resolve_row_counts(self):
        """Count the pending masks into ``stats[i].output_rows``: one
        blocking read per operator, made when the driver has finished
        (or by whoever reads the stats of a driver that was cut
        short)."""
        for i in range(len(self.stats)):
            self._count_pending(i)

    def collect_operator_metrics(self):
        """Pull per-operator metrics (exchange skew stats etc.) into the
        stats entries. Call after the driver finished: exchange sources
        only know their stats once the upstream collective ran."""
        self.resolve_row_counts()
        for op, st in zip(self.operators, self.stats):
            m = getattr(op, "metrics", None)
            if callable(m):
                got = m()
                if got:
                    st.metrics = dict(got)
            # per-operator memory high-water mark (the context's peak
            # survives close()) — history-based statistics record it
            ctx = getattr(op, "_ctx", None)
            peak = getattr(ctx, "peak", 0) if ctx is not None else 0
            if peak:
                st.metrics = dict(st.metrics or {}, peak_bytes=peak)
        masked = False
        for st in self.stats:
            m = st.metrics or {}
            if "rows_read" in m:            # the chain's scan
                masked = m["rows_read"] > st.output_rows
            elif "join_type" in m:
                masked = False
            else:
                st.masked_input = masked

    def blocked_tokens(self) -> List:
        """Listen tokens of currently-blocked operators. Meaningful
        after a ``process()`` quantum that made no progress: the task
        parks on these instead of spinning (reference:
        Driver.java:380-486 blocked-future handling)."""
        toks = []
        for op in self.operators:
            t = op.blocked_token()
            if t is not None:
                toks.append(t)
        return toks

    def run_to_completion(self, max_quanta: int = 1_000_000):
        for _ in range(max_quanta):
            if self.process():
                return
        raise RuntimeError("driver did not finish (stuck pipeline?)")


class Pipeline:
    """A driver factory: operator constructors for one pipeline of a task
    (reference analog: DriverFactory from LocalExecutionPlanner)."""

    def __init__(self, make_operators, is_source: bool = True):
        self._make = make_operators
        self.is_source = is_source

    def create_driver(self) -> Driver:
        return Driver(self._make())
