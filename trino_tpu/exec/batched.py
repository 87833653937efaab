"""Single-launch batched execution (rounds 16-17): run a same-shape
admission burst through ONE vmapped device launch per pipeline stage.

The serial batch path executes B same-shape statements as B separate
walks of the shared compiled programs — B launches per stage where the
programs differ only in the literal scalars they were called with.
With plan templates (``cache.PlanTemplate``) the literals are opaque
``ParamRef`` slots, so the per-stage program is ONE function of a
parameter vector; stacking the burst's literal vectors on a leading
``(B,)`` axis and ``vmap``-ing the stage (DrJAX-style lifting of the
map over statements into the compiled program) executes the whole
burst per scan page in a single launch, then demuxes member pages by
slicing the batch axis.

Round 17 extends the vmappable stage set past filter/project:

- **masked execution**: filtered rows are never compacted per lane
  (compaction would break the shape uniformity vmap needs); each stage
  carries a ``(B, n)`` validity mask and the only compaction happens at
  the final host demux (``DevicePage.to_page``).
- **aggregation** (``HashAggregationOperator``, step ``single``): the
  raw GroupByHash/sort-reduce kernels already mask invalid rows to a
  sentinel slot, so per-page partials, the concat merge, and the final
  projection all run as ``jit(vmap(...))`` lane programs. Per-lane
  dense group ids and counts demux on the host like any other column.
- **joins** (``LookupJoinOperator``, probed by the two binary searches
  over the sorted index): the build side is literal-independent by
  template construction (the aux pipelines are proved param-free), so
  ONE serial build serves all B lanes with its arrays broadcast
  (``in_axes=None``); probes mask invalid probe rows. inner/left
  expand at a lane capacity unified across the batch; semi/anti are
  pure mask updates.
- **per-lane overflow falls back alone**: a lane whose join expansion
  exceeds the unified capacity (or whose agg hash table exhausts its
  probe budget) is marked spilled — the runner re-runs that member
  (only) serially; the other lanes' results stay byte-equal and are
  served from the batch.

Lane capacities unify via ``KERNEL_SIZING`` pow2 fast-up so a repeat
burst compiles ZERO new programs: the kernel cache below is keyed by
value-level stage config (never operator identity — each burst replans
the template into fresh operators).

Eligibility is still narrower than template eligibility: a template
whose plan holds an unsupported stage (limits, full-outer joins,
residual join filters, exchanges, partial-step aggregations) EXECUTES
correctly through the shared template serially — zero retraces, B
launches. ``BatchIneligible.reason`` feeds the fallback taxonomy
counters either way, so the gap is loud, not silent.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Dict, List, Optional, Sequence, Set, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .. import types as T
from ..block import DevicePage, Dictionary, Page, padded_size
from ..expr.compiler import pad_lut, param_raw
from ..ops.aggregation import (HashAggregationOperator, _final_project,
                               _group_reduce_impl, _init_states,
                               _merge_states, _rank_and_inverse,
                               _ranks_to_codes, _state_plan)
from ..ops.hashtable import (_hash_group_ids_impl,
                             _hash_segment_reduce_impl, hashable_key_types)
from ..ops.join import (LookupJoinOperator, _expand_verified_impl,
                        _finalize_join_impl, _key_u64, _probe_counts_impl,
                        _semi_matched_impl)
from ..ops.kernel_sizing import KERNEL_SIZING
from ..ops.operator import (FilterProjectOperator, OutputCollectorOperator,
                            TableScanOperator)
from ..ops.sortkeys import group_operands
from ..telemetry.profiler import instrument
from ..telemetry.tracing import host_read, span_set


class BatchIneligible(Exception):
    """This plan/batch cannot ride the vmapped path; ``reason`` is one
    of the fallback-taxonomy tags documented in COMPONENTS.md."""

    def __init__(self, reason: str):
        super().__init__(reason)
        self.reason = reason


@dataclass
class BatchResult:
    """One batched execution's demuxed output.

    pages:        host pages per member (spilled members get none here)
    spilled:      member positions that overflowed a per-lane capacity
                  and must re-run serially (counted by the runner)
    dispositions: what actually ran beyond filter/project stages
                  (``agg_stage_vmapped`` / ``join_stage_vmapped``) —
                  feeds the same taxonomy counters as the fallbacks
    stage_rows:   per HBO-fingerprinted stage: exact per-lane output
                  row counts from the mask popcounts (rows key is a
                  ``(D,)`` host array over the PADDED batch; the runner
                  records real, non-spilled lanes only)
    scan_rows:    rows the shared scan produced (lane-invariant)
    """

    pages: List[List[Page]]
    spilled: Set[int]
    dispositions: List[str]
    stage_rows: List[dict]
    scan_rows: int


def vmappable_stages(plan) -> Tuple[List, TableScanOperator, List[Tuple],
                                    List[str]]:
    """Classify a plan for batching: returns (aux_pipelines, scan,
    stages, dispositions) or raises ``BatchIneligible`` with the
    taxonomy reason.

    ``stages`` is the main pipeline's interior as ("fp" | "agg" |
    "join", operator) pairs; ``aux_pipelines`` (join builds) are proved
    param-free so one serial run serves every lane."""
    pipelines = list(plan.pipelines)
    mains = [p for p in pipelines
             if p.operators and isinstance(p.operators[-1],
                                           OutputCollectorOperator)]
    if len(mains) != 1:
        raise BatchIneligible("no_collect_tail")
    main = mains[0].operators
    aux = [p for p in pipelines if p is not mains[0]]
    for p in aux:
        for op in p.operators:
            if isinstance(op, FilterProjectOperator) \
                    and op.processor.param_indices:
                # a literal reaching a build pipeline would break the
                # one-build-serves-all-lanes invariant
                raise BatchIneligible("unsupported_stage")
    if not main or not isinstance(main[0], TableScanOperator):
        raise BatchIneligible("no_scan_head")
    stages: List[Tuple] = []
    dispositions: List[str] = []
    seen_param = False
    for op in main[1:-1]:
        if isinstance(op, FilterProjectOperator):
            if op.processor.param_indices:
                seen_param = True
            stages.append(("fp", op))
        elif isinstance(op, HashAggregationOperator):
            # the batch axis must exist before a masked stage can demux
            # per lane; step single only (partial/final splits belong
            # to the exchange plans the template path never takes)
            if op.step != "single" or not seen_param:
                raise BatchIneligible("unsupported_stage")
            stages.append(("agg", op))
            if "agg_stage_vmapped" not in dispositions:
                dispositions.append("agg_stage_vmapped")
        elif isinstance(op, LookupJoinOperator):
            if op.join_type not in ("inner", "left", "semi", "anti") \
                    or op.filter_fn is not None or not seen_param:
                raise BatchIneligible("unsupported_stage")
            stages.append(("join", op))
            if "join_stage_vmapped" not in dispositions:
                dispositions.append("join_stage_vmapped")
        else:
            raise BatchIneligible("unsupported_stage")
    return aux, main[0], stages, dispositions


def check_params_consumed(fps: Sequence[FilterProjectOperator],
                          num_params: int):
    """Every literal slot of the shape must reach a compiled stage:
    an unconsumed slot would mean two members with different literals
    produce identical (wrong for one of them) results."""
    consumed = set()
    for fp in fps:
        consumed.update(fp.processor.param_indices)
    if consumed != set(range(num_params)):
        raise BatchIneligible("params_unconsumed")


def stack_bindings(fps: Sequence[FilterProjectOperator], param_types,
                   bindings: Sequence[Tuple]) -> List[Tuple]:
    """Per-stage stacked parameter tensors: for each stage, a tuple
    (one entry per consumed slot, in ``param_indices`` order) of
    ``(D,)`` arrays over the padded batch ``bindings`` (python literal
    values per global slot, one tuple per batch lane)."""
    out = []
    for fp in fps:
        idxs = fp.processor.param_indices
        out.append(tuple(
            np.stack([np.asarray(param_raw(param_types[i], vals[i]))
                      for vals in bindings])
            for i in idxs))
    return out


# ---------------------------------------------------------------------------
# the vmapped lane-kernel cache
#
# One jit(vmap(lane)) program per (kernel, value-config) pair, cached
# module-wide: a repeat burst replans the template into FRESH operator
# objects, so keying by operator identity would retrace every burst.
# Lane statics close over the factory args; runtime arrays (columns,
# LUTs, the shared build index) are traced operands.

_KERNEL_CACHE: Dict = {}


def _batched_kernel(name: str, cfg: Tuple, build_lane):
    key = (name, cfg)
    fn = _KERNEL_CACHE.get(key)
    if fn is None:
        fn = instrument(name,
                        jax.jit(jax.vmap(build_lane(), in_axes=(0, None))),
                        key=key)
        _KERNEL_CACHE[key] = fn
    return fn


# ---------------------------------------------------------------------------
# aggregation lanes


def _agg_group_lane(aggs: Tuple, key_channels: Tuple, key_types: Tuple,
                    key_pooled: Tuple, kinds: Tuple, str_state: Tuple,
                    hash_path: bool, intermediate: bool):
    """One lane of a masked GROUP BY page: the vmappable mirror of
    ``HashAggregationOperator._aggregate_page`` built from the raw
    kernel impls. Invalid rows hash to the sentinel slot (hash path)
    or sort last into the dump segment (sort path); pooled-key rank
    LUTs and string-state inverse LUTs arrive as traced operands so no
    host pool walk runs inside the trace.

    The segment reduce always runs the lax segment-op path
    (``pallas=""``): it is vmap-safe everywhere and byte-identical to
    the host path on CPU, where the batch-equality oracle runs. Its
    choice between the dense and the scatter reduction is per lane, so
    under ``vmap`` it is a select and a lane pays for both (the dense
    one is the small part)."""
    nkeys = len(key_channels)

    def lane(batched, shared):
        cols, nulls, valid = batched
        key_luts, state_luts, inv_luts = shared
        state_cols: List = []
        if intermediate:
            idx, k = nkeys, 0
            for a in aggs:
                m = len(_state_plan(a))
                raws = [cols[idx + j] for j in range(m)]
                luts = [state_luts[k + j] for j in range(m)]
                idx += m
                k += m
                state_cols.extend(_merge_states(a, raws, valid,
                                                rank_luts=luts))
        else:
            k = 0
            for a in aggs:
                state_cols.extend(_init_states(a, cols, nulls, valid,
                                               rank_lut=state_luts[k]))
                k += len(_state_plan(a))
        key_ops: List = []
        key_raws: List = []
        for c, t, pooled, lut in zip(key_channels, key_types, key_pooled,
                                     key_luts):
            col = cols[c]
            if pooled:
                ops = group_operands(lut[col], nulls[c], T.BIGINT)
            else:
                ops = group_operands(col, nulls[c], t)
            key_ops.extend(ops)
            key_raws.append(col)
        key_nulls = tuple(nulls[c] for c in key_channels)
        if hash_path:
            gid, group_rows, ngroups, overflow, *_rounds = \
                _hash_group_ids_impl(tuple(key_ops), valid, exact=True)
            out_keys, out_key_nulls, reduced, out_valid = \
                _hash_segment_reduce_impl(
                    gid, group_rows, ngroups, tuple(key_raws), key_nulls,
                    tuple(state_cols), kinds, pallas="")
        else:
            overflow = jnp.zeros((), dtype=bool)
            out_keys, out_key_nulls, reduced, out_valid = \
                _group_reduce_impl(
                    tuple(key_ops), tuple(key_raws), tuple(state_cols),
                    valid, num_keys=nkeys, num_states=len(state_cols),
                    kinds=kinds, pallas="")
        reduced = _ranks_to_codes(list(reduced), str_state, inv_luts)
        zero_null = jnp.zeros_like(out_valid)
        out_cols = tuple(out_keys) + tuple(reduced)
        out_nulls = tuple(jnp.asarray(n) for n in out_key_nulls) \
            + tuple(zero_null for _ in reduced)
        return out_cols, out_nulls, out_valid, overflow

    return lane


def _agg_finalize_lane(aggs: Tuple, nkeys: int):
    """One lane of ``HashAggregationOperator._finalize``: final
    projections over the merged intermediate layout."""

    def lane(batched, shared):
        del shared
        cols, nulls, valid = batched
        if nkeys == 0:
            # global aggregation emits exactly one row, even over zero
            # input rows (lane 0 then holds empty-input states)
            valid = valid | (jnp.arange(valid.shape[0]) == 0)
        out_cols = list(cols[:nkeys])
        out_nulls = list(nulls[:nkeys])
        idx = nkeys
        for a in aggs:
            m = len(_state_plan(a))
            states = [cols[idx + j] for j in range(m)]
            idx += m
            raw, null = _final_project(a, states)
            out_cols.append(raw.astype(a.output_type.storage))
            out_nulls.append(null | ~valid)
        return tuple(out_cols), tuple(out_nulls), valid

    return lane


def _agg_cfg(op: HashAggregationOperator) -> Tuple:
    """Value-level kernel-cache key for an aggregation stage (repr'd
    types keep the tuple hashable)."""
    return (tuple((a.function, a.arg_channel, repr(a.arg_type),
                   repr(a.output_type), a.distinct)
                  for a in op.aggregates),
            tuple(op.group_channels),
            tuple(repr(t) for t in op.input_types),
            op.hash_grouping)


class _AggAccumulator:
    """Barrier state of one vmapped aggregation stage: per-page masked
    partials accumulate, then merge + finalize once the scan drains —
    the stacked mirror of the serial partials list / ``_merge_partials``
    / ``_finalize`` walk, kept call-for-call equivalent so every lane
    is byte-equal to its serial oracle."""

    def __init__(self, op: HashAggregationOperator, depth: int):
        self.op = op
        self.depth = depth
        self.parts: List[Tuple] = []   # (cols, nulls, valid), (D, cap)
        self.caps: List[int] = []
        self.overflow = jnp.zeros((depth,), dtype=bool)
        key_types = [op.input_types[c] for c in op.group_channels]
        self.hash_path = op.hash_grouping and hashable_key_types(key_types)
        self.nkeys = len(op.group_channels)

    def _capture_dicts(self, page: "_BatchPage"):
        # mirrors add_input's capture; lanes share pages, so pools are
        # lane-invariant by construction (stability is asserted on the
        # serial path these same pages would take)
        op = self.op
        for i, c in enumerate(op.group_channels):
            d = page.dicts[c]
            if d is not None:
                op._group_dicts[i] = d
        k = 0
        for a in op.aggregates:
            for _ in _state_plan(a):
                if op._str_state[k]:
                    d = page.dicts[a.arg_channel]
                    if d is not None:
                        op._state_dicts[k] = d
                k += 1

    def _luts(self) -> Tuple:
        """(key_rank_luts, state_rank_luts, inverse_luts) as traced
        operands. Rank LUTs pad to pow2 (codes never index past the
        real pool, so padding is unread and the shape bucket is
        stable); inverse LUTs keep their EXACT pool length — the
        rank->code clamp bound must match the host path bit-for-bit."""
        op = self.op
        key_luts = []
        for i, c in enumerate(op.group_channels):
            if getattr(op.input_types[c], "is_pooled", False):
                rank, _ = _rank_and_inverse(op._group_dicts[i])
                key_luts.append(jnp.asarray(pad_lut(rank)))
            else:
                key_luts.append(None)
        state_luts: List = []
        inv_luts: List = []
        for k, is_str in enumerate(op._str_state):
            if is_str:
                rank, inv = _rank_and_inverse(op._state_dicts[k])
                state_luts.append(jnp.asarray(pad_lut(rank)))
                inv_luts.append(jnp.asarray(inv))
            else:
                state_luts.append(None)
                inv_luts.append(None)
        return tuple(key_luts), tuple(state_luts), tuple(inv_luts)

    def feed(self, page: "_BatchPage"):
        self._capture_dicts(page)
        op = self.op
        key_types = tuple(op.input_types[c] for c in op.group_channels)
        pooled = tuple(getattr(t, "is_pooled", False) for t in key_types)
        kern = _batched_kernel(
            "batched_agg_partial", ("partial", _agg_cfg(op), pooled),
            lambda: _agg_group_lane(
                tuple(op.aggregates), tuple(op.group_channels), key_types,
                pooled, op._kinds, tuple(op._str_state), self.hash_path,
                intermediate=False))
        out_cols, out_nulls, out_valid, overflow = kern(
            (page.cols, page.nulls, page.valid), self._luts())
        self.overflow = self.overflow | overflow
        self.parts.append((out_cols, out_nulls, out_valid))
        self.caps.append(int(out_valid.shape[-1]))

    def finalize(self) -> "_BatchPage":
        op = self.op
        types = op._intermediate_types()
        nkeys = self.nkeys
        for i in range(nkeys):
            # a scan that saw no input never captured key dictionaries;
            # string outputs still need (empty) pools
            if op._group_dicts[i] is None and types[i].is_pooled:
                op._group_dicts[i] = Dictionary()
        if not self.parts:
            # no input: zero groups — except global aggregation, which
            # emits one group of empty-input states (serial-identical
            # cap-16 zero page, broadcast across the batch)
            cap = 16
            cols = tuple(jnp.broadcast_to(jnp.zeros(cap, dtype=t.storage),
                                          (self.depth, cap))
                         for t in types)
            nulls = tuple(jnp.zeros((self.depth, cap), dtype=bool)
                          for _ in types)
            valid = jnp.zeros((self.depth, cap), dtype=bool)
            if nkeys == 0:
                valid = valid.at[:, 0].set(True)
            merged = (cols, nulls, valid)
        elif len(self.parts) == 1:
            # single partial: merged output IS the partial (the serial
            # path returns parts[0] unchanged for a non-partial step)
            merged = self.parts[0]
        else:
            total = sum(self.caps)
            # the serial merge concatenates at padded_size(total);
            # KERNEL_SIZING only ever grows the capacity, and a larger
            # table changes neither gid first-occurrence order nor the
            # reduced values — masked padding rows are dead lanes
            cap = KERNEL_SIZING.suggest(
                ("batched_agg_merge", _agg_cfg(op)), padded_size(total))
            ncols = len(self.parts[0][0])
            cols2, nulls2 = [], []
            for i in range(ncols):
                cols2.append(_pad_lanes(jnp.concatenate(
                    [p[0][i] for p in self.parts], axis=-1), cap))
                nulls2.append(_pad_lanes(jnp.concatenate(
                    [p[1][i] for p in self.parts], axis=-1), cap))
            valid = _pad_lanes(jnp.concatenate(
                [p[2] for p in self.parts], axis=-1), cap)
            inter_key_types = tuple(types[:nkeys])
            pooled = tuple(getattr(t, "is_pooled", False)
                           for t in inter_key_types)
            kern = _batched_kernel(
                "batched_agg_merge", ("merge", _agg_cfg(op), pooled),
                lambda: _agg_group_lane(
                    tuple(op.aggregates), tuple(range(nkeys)),
                    inter_key_types, pooled, op._kinds,
                    tuple(op._str_state), self.hash_path,
                    intermediate=True))
            out_cols, out_nulls, out_valid, overflow = kern(
                (tuple(cols2), tuple(nulls2), valid), self._luts())
            self.overflow = self.overflow | overflow
            merged = (out_cols, out_nulls, out_valid)
        fin = _batched_kernel(
            "batched_agg_finalize", ("finalize", _agg_cfg(op)),
            lambda: _agg_finalize_lane(tuple(op.aggregates), nkeys))
        f_cols, f_nulls, f_valid = fin(merged, None)
        agg_dicts = []
        k = 0
        for a in op.aggregates:
            agg_dicts.append(op._state_dicts[k]
                             if op._str_state[k] else None)
            k += len(_state_plan(a))
        dicts = list(op._group_dicts) + agg_dicts
        return _BatchPage(list(op.output_types), f_cols, f_nulls, f_valid,
                          dicts, True)


# ---------------------------------------------------------------------------
# join lanes


def _join_probe_lane(key_channels: Tuple, key_pooled: Tuple,
                     key_types: Tuple, key_mode: str):
    """One lane's candidate ranges against the SHARED sorted build
    keys (build arrays broadcast via ``in_axes=None``). Pooled probe
    keys remap into the build's code space through the same LUT the
    serial ``_probe_key_cols`` builds; masked probe rows count 0."""

    def lane(batched, shared):
        cols, nulls, valid = batched
        remap_luts, bkeys = shared
        pkey_cols = [remap_luts[i][cols[c]] if key_pooled[i] else cols[c]
                     for i, c in enumerate(key_channels)]
        pkey, panynull = _key_u64(
            pkey_cols, [nulls[c] for c in key_channels], list(key_types),
            key_mode)
        pusable = valid & ~panynull if panynull is not None else valid
        lo, count = _probe_counts_impl(bkeys, pkey, pusable)
        return lo, count

    return lane


def _join_expand_lane(key_channels: Tuple, key_pooled: Tuple,
                      out_cap: int, left: bool):
    """One inner/left lane: expand candidates at the unified capacity
    (their build rows through the shared index's ``perm``, the build's
    columns lying in arrival order), verify raw keys, gather the joined
    output (left appends the unmatched-probe lanes at the end, exactly
    like the serial path — output row order is capacity-independent, so
    a grown capacity stays byte-equal after compaction)."""

    def lane(batched, shared):
        cols, nulls, valid, lo, count = batched
        remap_luts, perm, bkey_cols, bcols, bnulls = shared
        pkey_cols = [remap_luts[i][cols[c]] if key_pooled[i] else cols[c]
                     for i, c in enumerate(key_channels)]
        probe_idx, build_row, keep = _expand_verified_impl(
            lo, count, perm, tuple(pkey_cols), bkey_cols, out_cap=out_cap)
        return _finalize_join_impl(
            tuple(cols), tuple(nulls), valid, bcols, bnulls,
            probe_idx, build_row, keep, left=left)

    return lane


def _join_semi_lane(key_channels: Tuple, key_pooled: Tuple, out_cap: int,
                    anti: bool):
    """One semi/anti lane: a pure mask update over the probe page."""

    def lane(batched, shared):
        cols, valid, lo, count = batched
        remap_luts, perm, bkey_cols = shared
        pkey_cols = [remap_luts[i][cols[c]] if key_pooled[i] else cols[c]
                     for i, c in enumerate(key_channels)]
        matched = _semi_matched_impl(
            lo, count, perm, tuple(pkey_cols), bkey_cols,
            probe_cap=valid.shape[0], out_cap=out_cap)
        return valid & ~matched if anti else valid & matched

    return lane


# ---------------------------------------------------------------------------
# the batched driver


@dataclass
class _BatchPage:
    """One page mid-pipeline: columns either shared (param-free prefix,
    1-D) or stacked over the batch axis (2-D, ``batched=True``)."""

    types: List
    cols: Tuple
    nulls: Tuple
    valid: "jax.Array"
    dicts: List
    batched: bool


def _pad_lanes(arr, cap: int):
    """Pad the row (last) axis to ``cap`` with zeros/False."""
    n = arr.shape[-1]
    if n == cap:
        return arr
    pad = jnp.zeros(arr.shape[:-1] + (cap - n,), dtype=arr.dtype)
    return jnp.concatenate([arr, pad], axis=-1)


def execute_batched(plan, param_types, bindings: Sequence[Tuple],
                    num_members: int) -> BatchResult:
    """Drive the plan with the whole padded batch in one launch per
    stage per scan page.

    ``bindings`` is the PADDED batch (length D >= num_members); result
    pages demux positionally for the first ``num_members`` lanes only.
    Returns host pages per member, byte-equal to running each member
    through the serial path (same raw kernels, same rawness — padding
    lanes compute and are discarded), plus the spilled-lane set, the
    stage dispositions, and the mask-popcount row actuals."""
    aux, scan, stages, dispositions = vmappable_stages(plan)
    fps = [op for kind, op in stages if kind == "fp"]
    check_params_consumed(fps, len(param_types))
    fp_params = iter(stack_bindings(fps, param_types, bindings))
    stage_params = [next(fp_params) if kind == "fp" else None
                    for kind, _op in stages]

    # the shared build side(s): literal-independent by template
    # construction (vmappable_stages proved the aux pipelines are
    # param-free), so ONE serial run serves every lane
    from .driver import Driver

    for p in aux:
        Driver(p.operators).run_to_completion()

    depth = len(bindings)
    spill = np.zeros(depth, dtype=bool)
    agg_accs: Dict[int, _AggAccumulator] = {
        k: _AggAccumulator(op, depth)
        for k, (kind, op) in enumerate(stages) if kind == "agg"}
    rows_acc: Dict[int, object] = {}
    scan_rows_acc: Optional[object] = None
    final: List[_BatchPage] = []

    def note_rows(k: int, op, valid):
        if getattr(op, "_hbo_fp", None) is None:
            return
        r = jnp.sum(valid, axis=-1) if valid.ndim == 2 \
            else jnp.full((depth,), jnp.sum(valid))
        rows_acc[k] = r if k not in rows_acc else rows_acc[k] + r

    def apply_fp(k: int, op, page: _BatchPage) -> _BatchPage:
        proc = op.processor
        params = stage_params[k]
        if not page.batched and not params:
            # param-free prefix stage: members are identical here —
            # one UNBATCHED launch shared by the whole burst
            dp = proc.process(DevicePage(list(page.types), list(page.cols),
                                         list(page.nulls), page.valid,
                                         list(page.dicts)))
            return _BatchPage(proc.output_types, tuple(dp.cols),
                              tuple(dp.nulls), dp.valid,
                              list(dp.dictionaries), False)
        mode = "carried" if page.batched else "shared"
        cols, nulls, valid, dicts = proc.process_batched(
            page.cols, page.nulls, page.valid, page.dicts, params or (),
            mode)
        return _BatchPage(proc.output_types, tuple(cols), tuple(nulls),
                          valid, list(dicts), True)

    def apply_join(k: int, op, page: _BatchPage) -> _BatchPage:
        b = op.bridge.build
        assert b is not None, "probe started before build finished"
        hs = getattr(op.bridge, "hybrid", None)
        if hs is not None and hs.spilled_build:
            # the vmapped probe only sees the resident index; a build
            # that went hybrid under memory pressure must not silently
            # drop its cold partitions — fail the batch loudly (the
            # caller re-runs lanes serially on lane_overflow fallbacks,
            # and batched templates never run memory-governed anyway)
            raise RuntimeError(
                "batched probe over a hybrid-spilled build")
        kc = tuple(op.probe_keys)
        pooled = tuple(op.probe_types[c].is_pooled for c in kc)
        key_types = tuple(T.BIGINT if p else op.probe_types[c]
                          for c, p in zip(kc, pooled))
        # probe-pool -> build-pool code remaps: host LUT work once per
        # pool pair (the operator caches it); padding is unread (codes
        # never index past the real pool)
        remap_luts = tuple(
            jnp.asarray(pad_lut(np.asarray(
                op._remap(page.dicts[c], b.dictionaries[bc]))))
            if p else None
            for c, bc, p in zip(kc, b.key_channels, pooled))
        cfg = (kc, pooled, tuple(repr(t) for t in key_types), b.key_mode)
        probe = _batched_kernel(
            "batched_join_probe", ("probe",) + cfg,
            lambda: _join_probe_lane(kc, pooled, key_types, b.key_mode))
        lo, count = probe((page.cols, page.nulls, page.valid),
                          (remap_luts, b.key_sorted))
        # ONE deliberate host sync per probe page: the unified lane
        # capacity must be a static shape. Already-spilled lanes are
        # excluded so their (re-run serially anyway) fan-out cannot
        # inflate the shared capacity.
        totals = np.where(spill, 0, host_read(jnp.sum(count, axis=-1),
                                              "batched_join_totals"))
        need = int(totals.max()) if totals.size else 16
        lane_cap = KERNEL_SIZING.suggest(
            ("batched_join_expand",) + cfg,
            max(min(need, op.max_lanes), 16))
        while lane_cap > op.max_lanes and lane_cap > 16:
            lane_cap >>= 1  # budget checked POST-padding, like every path
        over = totals > lane_cap
        if over.any():
            spill[:] = spill | over
        bkey_cols = tuple(b.cols[c] for c in b.key_channels)
        if op.join_type in ("semi", "anti"):
            kern = _batched_kernel(
                "batched_join_semi",
                ("semi", op.join_type, lane_cap) + cfg,
                lambda: _join_semi_lane(kc, pooled, lane_cap,
                                        op.join_type == "anti"))
            new_valid = kern((page.cols, page.valid, lo, count),
                             (remap_luts, b.perm, bkey_cols))
            return _BatchPage(page.types, page.cols, page.nulls,
                              new_valid, page.dicts, True)
        left = op.join_type == "left"
        kern = _batched_kernel(
            "batched_join_expand", ("expand", left, lane_cap) + cfg,
            lambda: _join_expand_lane(kc, pooled, lane_cap, left))
        out_cols, out_nulls, out_valid = kern(
            (page.cols, page.nulls, page.valid, lo, count),
            (remap_luts, b.perm, bkey_cols, b.cols, b.nulls))
        return _BatchPage(list(op.output_types), out_cols, out_nulls,
                          out_valid, list(page.dicts) + list(b.dictionaries),
                          True)

    def run_from(i: int, page: _BatchPage):
        for k in range(i, len(stages)):
            kind, op = stages[k]
            if kind == "fp":
                page = apply_fp(k, op, page)
            elif kind == "join":
                page = apply_join(k, op, page)
            else:
                agg_accs[k].feed(page)
                return
            note_rows(k, op, page.valid)
        if not page.batched:
            # cannot happen after check_params_consumed with
            # param_types non-empty; guard for the zero-literal case
            raise BatchIneligible("params_unconsumed")
        final.append(page)

    try:
        while True:
            dpage = scan.get_output()
            if dpage is None:
                if scan.is_finished():
                    break
                continue
            cnt = jnp.sum(dpage.valid)
            scan_rows_acc = cnt if scan_rows_acc is None \
                else scan_rows_acc + cnt
            run_from(0, _BatchPage(list(dpage.types), tuple(dpage.cols),
                                   tuple(dpage.nulls), dpage.valid,
                                   list(dpage.dictionaries), False))
    finally:
        scan.close()    # a stage that raised leaves no scan reading ahead
    # the shared scan's host-side counters, on the span around this call
    # (no driver ran it, so no operator span carries them)
    for key, value in (getattr(scan, "metrics", dict)() or {}).items():
        span_set(key, value)

    # agg barriers drain in stage order: each finalize feeds the
    # remaining stages (which may include another barrier downstream)
    for k in sorted(agg_accs):
        acc = agg_accs[k]
        page = acc.finalize()
        spill[:] = spill | host_read(acc.overflow, "batched_agg_overflow")
        note_rows(k, stages[k][1], page.valid)
        run_from(k + 1, page)

    spilled = {m for m in range(num_members) if spill[m]}
    out_pages: List[List[Page]] = [[] for _ in range(num_members)]
    for page in final:
        for m in range(num_members):
            if m in spilled:
                continue
            member = DevicePage(list(page.types),
                                [c[m] for c in page.cols],
                                [n[m] for n in page.nulls],
                                page.valid[m], list(page.dicts))
            host = member.to_page()
            if host.num_rows:
                out_pages[m].append(host)
    scan_rows = int(host_read(scan_rows_acc, "batched_stage_rows")) \
        if scan_rows_acc is not None else 0
    stage_rows = [
        {"fp": getattr(stages[k][1], "_hbo_fp", None),
         "name": type(stages[k][1]).__name__,
         "rows": host_read(rows_acc[k], "batched_stage_rows")}
        for k in sorted(rows_acc)]
    if getattr(scan, "_hbo_fp", None) is not None:
        # the shared scan is lane-invariant: every lane observed it
        stage_rows.insert(0, {"fp": scan._hbo_fp,
                              "name": type(scan).__name__,
                              "rows": np.full(depth, scan_rows)})
    return BatchResult(out_pages, spilled, dispositions, stage_rows,
                       scan_rows)
