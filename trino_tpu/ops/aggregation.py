"""Hash aggregation, TPU-first.

Reference analog: ``operator/HashAggregationOperator.java`` +
``operator/MultiChannelGroupByHash.java`` (vectorized open-addressing
putIfAbsent) + the bytecode-compiled accumulators
(``operator/aggregation/AccumulatorCompiler.java``).

Grouping runs one of two paths:

- **hash** (default): the vectorized open-addressing table of
  ``ops/hashtable.py`` assigns each row a dense group id via bounded
  linear-probe rounds of masked scatter/gather — no sort, and state
  columns never ride through comparator operands. The reduce then
  runs over the hash-assigned gids, and the page's own group count
  picks its way on the device: up to ``hashtable.DENSE_GROUPS`` groups
  (q1's four, a global aggregate's one) each state is compared with
  those ids and reduced under the mask — **dense**, no scatter; beyond
  that, the segment scatter (one cheap gid-only sort first when the
  Pallas TPU kernel — which requires sorted segments — is active). A
  global aggregate (no key columns) builds no table at all: its group
  ids are known in closed form. ``path_counts["dense"]`` counts the
  ``hash`` pages that took the dense way, where the step reads the
  group count (``single``/``final``). Float grouping keys and
  probe-budget overflow fall back to:
- **sort** (oracle/fallback): normalize key columns to (null-bit,
  uint64) operand pairs, ``lax.sort`` the batch lexicographically,
  detect group boundaries by adjacent-row comparison, cumsum dense
  group ids, segment-reduce. Forceable via the ``hash_grouping_enabled``
  session property for cross-checking.

Streaming: each input page is partially aggregated on device, partials
accumulate; ``finish`` re-groups the concatenated partials and applies
final projections. This mirrors the reference's partial/final adapter
split and keeps memory proportional to groups, not input rows: where the
host reads a page's flags anyway (steps ``single``/``final`` on the hash
path) the group count comes with them, and the partial is kept
``padded_size(ngroups)`` lanes wide; elsewhere it is as wide as its page.

**Adaptive partial aggregation** (reference:
``adaptive_partial_aggregation_enabled``; "Partial Partial Aggregates",
PAPERS.md): a partial-step operator observes its groups-to-rows
reduction ratio; once enough rows show grouping is not reducing
(ratio above threshold), it stops aggregating and passes pages through
in the intermediate keys+states layout — the final step re-groups, so
results are unchanged while the partial stops burning time on
high-cardinality keys.

**Per-key-range decision** ("Partial Partial Aggregates" proper): the
observation window tracks the reduction ratio PER KEY-RANGE BUCKET
(the hashed key space split into ``adaptive_key_buckets`` ranges), and
the pass-through switch flips per bucket — a skewed stream keeps
aggregating its hot (duplicate-heavy) ranges while cold (mostly-
unique) ranges pass through ungrouped, instead of one all-or-nothing
stream decision.  A decided split emits two pages per input page (the
aggregated hot-range partial + the cold-range pass-through), both in
the intermediate layout the final step re-groups anyway.
"""

from __future__ import annotations

import threading
from dataclasses import dataclass
from functools import partial
from typing import List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .. import jit_stats
from .. import types as T
from ..block import DevicePage, padded_size
from ..telemetry.profiler import instrument
from ..telemetry.tracing import host_read, host_sync
from ..types import TypeError_
from .hashtable import (DENSE_GROUPS, _mix_operands, hash_group_ids,
                        hash_segment_reduce, hashable_key_types)
from .operator import Operator
from .sortkeys import group_operands, sort_carrying

#: adaptive partial aggregation: minimum observed input rows before the
#: reduction ratio is trusted (reference default: 100k rows)
ADAPTIVE_MIN_ROWS = 100_000
#: groups/rows ratio above which the partial step stops aggregating
ADAPTIVE_RATIO_THRESHOLD = 0.9
#: key-range buckets the pass-through decision is made over (1 = one
#: global per-stream decision; ``adaptive_partial_aggregation_key_
#: range_buckets``)
ADAPTIVE_KEY_BUCKETS = 8


# ---------------------------------------------------------------------------
# aggregate function descriptors
# (reference analog: operator/aggregation/* builtin implementations)


@dataclass(frozen=True)
class AggCall:
    """One aggregate in a GROUP BY: function over an input channel."""

    function: str                 # count | count_star | sum | avg | min | max
    arg_channel: Optional[int]    # None for count(*)
    arg_type: Optional[T.Type]
    output_type: T.Type
    distinct: bool = False


def resolve_agg_type(function: str, arg_type: Optional[T.Type]) -> T.Type:
    if function in ("count", "count_star"):
        return T.BIGINT
    if function == "sum":
        if arg_type.is_decimal:
            return T.decimal_type(18, arg_type.scale)
        if arg_type in (T.REAL, T.DOUBLE):
            return T.DOUBLE
        if arg_type in (T.TINYINT, T.SMALLINT, T.INTEGER, T.BIGINT):
            return T.BIGINT
        raise TypeError_(f"cannot sum {arg_type}")
    if function == "avg":
        if arg_type.is_decimal:
            return arg_type
        return T.DOUBLE
    if function in ("min", "max", "arbitrary", "any_value"):
        return arg_type
    if function in ("stddev", "stddev_samp", "stddev_pop", "variance",
                    "var_samp", "var_pop", "geometric_mean"):
        return T.DOUBLE
    if function in ("bool_and", "bool_or", "every"):
        if arg_type != T.BOOLEAN:
            raise TypeError_(f"{function} expects boolean, got {arg_type}")
        return T.BOOLEAN
    if function == "count_if":
        if arg_type != T.BOOLEAN:
            raise TypeError_(f"count_if expects boolean, got {arg_type}")
        return T.BIGINT
    if function == "approx_distinct":
        return T.BIGINT
    if function == "approx_percentile":
        # same-type contract as the reference; the sketch rewrite
        # rounds back for integers (logical_planner._plan_dd_percentile)
        if arg_type in (T.TINYINT, T.SMALLINT, T.INTEGER, T.BIGINT):
            return T.BIGINT
        if arg_type in (T.REAL, T.DOUBLE):
            return T.DOUBLE
        if arg_type.is_decimal:
            return arg_type
        raise TypeError_(
            f"approx_percentile does not support {arg_type} yet")
    raise TypeError_(f"unknown aggregate function {function}")


# Each aggregate lowers to a list of (reduce_kind, state_dtype) states:
#   sum   -> [sum(x), count(nonnull)]
#   count -> [count(nonnull)]
#   avg   -> [sum(x), count(nonnull)]
#   min   -> [min(x or +sentinel), count]
#   max   -> [max(x or -sentinel), count]
#   stddev/variance -> [sum(x), sum(x^2), count]  (as float64)


def _state_plan(agg: AggCall):
    f = agg.function
    if f in ("count_star", "count", "count_if"):
        return [("sum", jnp.int64)]
    if f in ("sum", "avg"):
        dt = jnp.float64 if (agg.arg_type in (T.REAL, T.DOUBLE)) else jnp.int64
        return [("sum", dt), ("sum", jnp.int64)]
    if f in ("min", "arbitrary", "any_value", "bool_and", "every"):
        return [("min", None), ("sum", jnp.int64)]
    if f in ("max", "bool_or"):
        return [("max", None), ("sum", jnp.int64)]
    if f == "geometric_mean":
        return [("sum", jnp.float64), ("sum", jnp.int64)]
    if f in ("stddev", "stddev_samp", "stddev_pop", "variance", "var_samp",
             "var_pop"):
        return [("sum", jnp.float64), ("sum", jnp.float64),
                ("sum", jnp.int64)]
    raise TypeError_(f"unknown aggregate function {f}")


def intermediate_state_types(function: str,
                             arg_type: Optional[T.Type]) -> List[T.Type]:
    """SQL types of one aggregate's partial-state columns (the wire
    layout of partial-aggregation exchange pages). String min/max
    states are VARCHAR: partials carry dictionary CODES so exchanges
    unify pools; the reduce itself runs on lexicographic ranks (codes
    are pool-order, not value-order) and maps back to codes at every
    page boundary."""
    call = AggCall(function, None, arg_type, T.BIGINT)
    out: List[T.Type] = []
    for (kind, dt) in _state_plan(call):
        if kind in ("min", "max"):
            if arg_type in (T.REAL, T.DOUBLE):
                out.append(T.DOUBLE)
            elif arg_type == T.BOOLEAN:
                out.append(T.BIGINT)  # 0/1 lanes (bool_and/bool_or)
            else:
                out.append(arg_type or T.BIGINT)
        else:
            out.append(T.DOUBLE if dt == jnp.float64 else T.BIGINT)
    return out


_RANK_INV_CACHE: dict = {}


def _rank_and_inverse(dictionary):
    """(rank_lut, inverse_lut): rank_lut[code] = dense lex rank;
    inverse_lut[rank] = FIRST code of that rank (aligned pools may
    repeat values). Cached per (pool, size) — pools are append-only."""
    import numpy as np

    if dictionary is None or len(dictionary) == 0:
        return (np.zeros(1, dtype=np.int64), np.zeros(1, dtype=np.int32))
    key = (id(dictionary), len(dictionary))
    hit = _RANK_INV_CACHE.get(key)
    if hit is not None and hit[0] is dictionary:
        return hit[1], hit[2]
    ranks = dictionary.sort_rank().astype(np.int64)
    nr = int(ranks.max()) + 1 if len(ranks) else 1
    inv = np.zeros(nr, dtype=np.int32)
    # reversed scatter: the FIRST code of each rank lands last, winning
    inv[ranks[::-1]] = np.arange(len(ranks) - 1, -1, -1, dtype=np.int32)
    if len(_RANK_INV_CACHE) >= 256:
        _RANK_INV_CACHE.clear()
    _RANK_INV_CACHE[key] = (dictionary, ranks, inv)
    return ranks, inv


def _init_states(agg: AggCall, cols, nulls, valid, dicts=None,
                 rank_lut=None) -> List:
    """Per-row initial state columns for one aggregate.

    ``rank_lut``: precomputed lexicographic-rank LUT ARRAY for a pooled
    min/max arg (the batched executor passes it as a traced vmap
    operand so the host-side ``_rank_and_inverse`` pool walk never runs
    inside a trace); None = derive it from ``dicts`` on host."""
    f = agg.function
    if f == "count_star":
        return [valid.astype(jnp.int64)]
    raw = cols[agg.arg_channel]
    nl = nulls[agg.arg_channel]
    live = valid & ~nl
    if f == "count":
        return [live.astype(jnp.int64)]
    if f == "count_if":
        return [(live & raw.astype(bool)).astype(jnp.int64)]
    if f in ("bool_and", "every", "bool_or"):
        # min/max over {0,1}; dead lanes take the neutral sentinel
        neutral = 1 if f != "bool_or" else 0
        x = jnp.where(live, raw.astype(jnp.int64), neutral)
        return [x, live.astype(jnp.int64)]
    if f == "geometric_mean":
        x = raw.astype(jnp.float64)
        if agg.arg_type is not None and agg.arg_type.is_decimal:
            x = x / (10.0 ** agg.arg_type.scale)
        # log(0) = -inf => result 0; log(<0) = NaN => result NaN (the
        # reference's semantics); dead lanes are masked by `live`
        return [jnp.where(live, jnp.log(x), 0.0),
                live.astype(jnp.int64)]
    if f in ("arbitrary", "any_value"):
        f = "min"  # deterministic pick: the smallest value
        agg = AggCall("min", agg.arg_channel, agg.arg_type,
                      agg.output_type)
    if f in ("sum", "avg"):
        if agg.arg_type in (T.REAL, T.DOUBLE):
            x = raw.astype(jnp.float64)
            return [jnp.where(live, x, 0.0), live.astype(jnp.int64)]
        x = raw.astype(jnp.int64)
        return [jnp.where(live, x, 0), live.astype(jnp.int64)]
    if f in ("min", "max"):
        if agg.arg_type is not None and agg.arg_type.is_pooled:
            # reduce on lexicographic RANKS (codes are pool-order);
            # _map_rank_states restores codes after the reduce
            if rank_lut is None:
                rank_lut, _ = _rank_and_inverse(
                    dicts[agg.arg_channel] if dicts is not None else None)
            ranks = jnp.asarray(rank_lut)[raw]
            info = jnp.iinfo(jnp.int64)
            sent = info.max if f == "min" else info.min
            x = jnp.where(live, ranks, jnp.asarray(sent, dtype=jnp.int64))
            return [x, live.astype(jnp.int64)]
        if agg.arg_type in (T.REAL, T.DOUBLE):
            sent = jnp.inf if f == "min" else -jnp.inf
            x = jnp.where(live, raw.astype(jnp.float64), sent)
        else:
            if raw.dtype == jnp.bool_:
                raw = raw.astype(jnp.int64)
            info = jnp.iinfo(raw.dtype)
            sent = info.max if f == "min" else info.min
            x = jnp.where(live, raw, jnp.asarray(sent, dtype=raw.dtype))
        return [x, live.astype(jnp.int64)]
    # stddev family
    x = jnp.where(live, raw.astype(jnp.float64), 0.0)
    if agg.arg_type is not None and agg.arg_type.is_decimal:
        x = x / (10.0 ** agg.arg_type.scale)
    return [x, x * x, live.astype(jnp.int64)]


def _merge_states(agg: AggCall, state_cols, valid, state_dicts=None,
                  rank_luts=None) -> List:
    """Partial-state columns re-entering a (final) aggregation: states
    combine with their own reduce kinds. min/max values are neutralized
    to their sentinel on invalid lanes AND on empty partials (count
    state 0 — e.g. the one empty-input row a global partial emits),
    which would otherwise contribute a bogus 0. String min/max states
    arrive as codes and re-enter the reduce as lexicographic ranks.
    ``rank_luts``: per-state precomputed rank LUT arrays (traced vmap
    operands, see ``_init_states``); None = derive from
    ``state_dicts`` on host."""
    plan = _state_plan(agg)
    count = state_cols[-1]  # every aggregate's last state is its count
    is_str = agg.arg_type is not None and agg.arg_type.is_pooled
    out = []
    for j, ((kind, _dt), s) in enumerate(zip(plan, state_cols)):
        if kind == "sum":
            z = jnp.zeros((), dtype=s.dtype)
            out.append(jnp.where(valid, s, z))
        else:
            live = valid & (count > 0)
            if is_str and kind in ("min", "max"):
                rank_lut = rank_luts[j] if rank_luts is not None else None
                if rank_lut is None:
                    rank_lut, _ = _rank_and_inverse(
                        state_dicts[j] if state_dicts is not None
                        else None)
                s = jnp.asarray(rank_lut)[s]
                info = jnp.iinfo(jnp.int64)
                sent = info.max if kind == "min" else info.min
                out.append(jnp.where(live, s.astype(jnp.int64),
                                     jnp.asarray(sent, dtype=jnp.int64)))
                continue
            if kind == "min":
                sent = jnp.inf if s.dtype == jnp.float64 \
                    else jnp.iinfo(s.dtype).max
            else:
                sent = -jnp.inf if s.dtype == jnp.float64 \
                    else jnp.iinfo(s.dtype).min
            out.append(jnp.where(live, s, jnp.asarray(sent, dtype=s.dtype)))
    return out


def _final_project(agg: AggCall, states: List):
    """states (per-group reduced) -> (raw, null) in output_type storage."""
    f = agg.function
    ot = agg.output_type
    if f in ("count", "count_star", "count_if"):
        return states[0], jnp.zeros(states[0].shape, dtype=jnp.bool_)
    cnt = states[-1]
    null = cnt == 0
    if f == "sum":
        return states[0].astype(ot.storage), null
    if f == "avg":
        s = states[0]
        if ot.is_decimal:
            from ..expr.functions import div_round_half_up
            return div_round_half_up(s, jnp.maximum(cnt, 1)), null
        return s.astype(jnp.float64) / jnp.maximum(cnt, 1), null
    if f in ("min", "max", "arbitrary", "any_value"):
        return states[0].astype(ot.storage), null
    if f in ("bool_and", "every", "bool_or"):
        return (states[0] != 0), null
    if f == "geometric_mean":
        return jnp.exp(states[0] / jnp.maximum(cnt, 1)), null
    # stddev family
    s, s2 = states[0], states[1]
    n = jnp.maximum(cnt, 1).astype(jnp.float64)
    mean = s / n
    m2 = jnp.maximum(s2 / n - mean * mean, 0.0)
    pop = f in ("stddev_pop", "var_pop")
    denom = jnp.where(pop, n, jnp.maximum(n - 1, 1))
    var = m2 * n / denom
    if f.startswith("stddev"):
        var = jnp.sqrt(var)
    null = null | (~jnp.asarray(pop) & (cnt < 2))
    return var, null


# ---------------------------------------------------------------------------
# the grouping kernel


def _group_reduce_impl(key_ops: Tuple, key_raws: Tuple,
                       state_cols: Tuple, valid, num_keys: int,
                       num_states: int, kinds: Tuple, pallas: str = ""):
    """Sort-group-reduce one batch.

    key_ops: flattened (null_bit, u64) pairs for each group key
    key_raws: the raw key columns (carried through the sort)
    state_cols: per-row state columns (carried through the sort)
    Returns (group_key_raws, group_key_nullbits, reduced_states, out_valid).

    Raw implementation: the batched executor composes it under its own
    ``jit(vmap(...))`` wrappers (calling the instrumented binding
    inside a trace would run profiler host bookkeeping per lane); host
    callers use the jitted+instrumented ``_group_reduce`` below.
    """
    jit_stats.bump("sort_group_reduce")
    cap = valid.shape[0]
    # invalid lanes sort last: leading operand = ~valid
    (_, *s_keyops), carried = sort_carrying(
        [(~valid).astype(jnp.uint8)] + list(key_ops),
        list(key_raws) + list(state_cols) + [valid])
    s_keyraws = carried[:num_keys]
    s_states = carried[num_keys:-1]
    s_valid = carried[-1]

    # boundary: first row, or any key operand differs from previous row
    diff = jnp.zeros(cap, dtype=bool).at[0].set(True)
    for op in s_keyops:
        prev = jnp.roll(op, 1)
        d = op != prev
        diff = diff | d.at[0].set(True)
    boundary = diff & s_valid
    gid = jnp.cumsum(boundary.astype(jnp.int32)) - 1
    # invalid lanes -> dump segment
    gid = jnp.where(s_valid, gid, cap)

    # the hot scatter: Pallas kernel on TPU (lax segment ops elsewhere)
    # — see ops/pallas_kernels.py
    from .pallas_kernels import segment_reduce

    reduced = []
    for kind, col in zip(kinds, s_states):
        r = segment_reduce(col, gid, num_segments=cap + 1, kind=kind,
                           mode=pallas)
        reduced.append(r[:cap])

    # group keys: first sorted row of each segment
    first_idx = jax.ops.segment_min(
        jnp.arange(cap, dtype=jnp.int32), gid, num_segments=cap + 1)[:cap]
    ngroups = jnp.sum(boundary.astype(jnp.int32))
    out_valid = jnp.arange(cap, dtype=jnp.int32) < ngroups
    safe_idx = jnp.where(out_valid, first_idx, 0)
    out_key_raws = tuple(kr[safe_idx] for kr in s_keyraws)
    out_key_nulls = tuple(s_keyops[2 * i][safe_idx] > 0
                          for i in range(num_keys))
    return out_key_raws, out_key_nulls, tuple(reduced), out_valid


_group_reduce = instrument(
    "sort_group_reduce",
    partial(jax.jit, static_argnames=("num_states", "num_keys", "kinds",
                                      "pallas"))(_group_reduce_impl),
    static_argnames=("num_states", "num_keys", "kinds", "pallas"))


def _ranks_to_codes(state_cols: List, str_state: Sequence[bool],
                    inv_luts: Sequence) -> List:
    """String min/max value states: lexicographic RANK -> the
    representative CODE, driven by precomputed inverse LUT ARRAYS (the
    trace-safe mirror of ``HashAggregationOperator._states_rank_to_code``
    — the batched executor passes the LUTs as traced vmap operands).
    Dead/sentinel lanes clamp into range; count==0 nulls them
    downstream. LUTs keep their EXACT pool length so the clamp bound
    matches the host path bit-for-bit."""
    for k, is_str in enumerate(str_state):
        if is_str:
            inv = inv_luts[k]
            r = jnp.clip(state_cols[k], 0, inv.shape[0] - 1)
            state_cols[k] = inv[r].astype(jnp.int32)
    return state_cols


@partial(jax.jit, static_argnames=("buckets",))
def _bucket_reduction_stats(key_ops: Tuple, valid, group_rows, ngroups,
                            buckets: int):
    """(2, buckets) per-key-range observation of one page: row 0 =
    live rows per bucket, row 1 = groups (leader rows) per bucket.
    The bucket is a stable hash of the grouping operands, so a key's
    rows land in the same range bucket on every page.  Sums across
    axis 1 give the page totals, so this is the ONE host fetch the
    adaptive window pays per observed page."""
    jit_stats.bump("agg_bucket_stats")
    cap = valid.shape[0]
    b = (_mix_operands(key_ops, cap)
         % np.uint64(buckets)).astype(jnp.int32)
    rows = jnp.zeros((buckets + 1,), dtype=jnp.int32)
    rows = rows.at[jnp.where(valid, b, buckets)].add(1)
    leader = jnp.arange(cap, dtype=jnp.int32) < ngroups
    lb = b[group_rows]
    groups = jnp.zeros((buckets + 1,), dtype=jnp.int32)
    groups = groups.at[jnp.where(leader, lb, buckets)].add(1)
    return jnp.stack([rows[:buckets], groups[:buckets]])


_bucket_reduction_stats = instrument(
    "agg_bucket_stats", _bucket_reduction_stats,
    static_argnames=("buckets",))


@partial(jax.jit, static_argnames=("buckets",))
def _key_range_pass_mask(key_ops: Tuple, pass_buckets, buckets: int):
    """Per-row pass-through mask from the decided per-bucket verdicts
    (same stable hash as ``_bucket_reduction_stats``)."""
    jit_stats.bump("agg_key_range_mask")
    n = key_ops[0].shape[0]
    b = (_mix_operands(key_ops, n) % np.uint64(buckets)).astype(jnp.int32)
    return pass_buckets[b]


_key_range_pass_mask = instrument(
    "agg_key_range_mask", _key_range_pass_mask,
    static_argnames=("buckets",))


@partial(jax.jit, static_argnames=("keep",))
def _narrow_lanes(arrays, keep: int):
    """Every array of the pytree cut to its first ``keep`` lanes, in one
    program: a grouping result holds its groups in a dense prefix."""
    jit_stats.bump("agg_narrow_partial")
    return jax.tree_util.tree_map(lambda a: a[:keep], arrays)


_narrow_lanes = instrument("agg_narrow_partial", _narrow_lanes,
                           static_argnames=("keep",))


#: process-wide pages per grouping path (the per-operator
#: ``path_counts`` summed; chip_smoke / test observability)
_path_totals = {"hash": 0, "dense": 0, "sort": 0, "passthrough": 0,
                "range_split": 0}
_path_totals_lock = threading.Lock()


def grouping_path_totals() -> dict:
    with _path_totals_lock:
        return dict(_path_totals)


class HashAggregationOperator(Operator):
    """GROUP BY over device batches (see module docstring).

    step: 'single' (raw in, final out), 'partial' (raw in, states out),
    'final' (states in, final out) — mirroring the reference's
    PARTIAL/FINAL/SINGLE AggregationNode steps.
    """

    def __init__(self, input_types: Sequence[T.Type],
                 group_channels: Sequence[int],
                 aggregates: Sequence[AggCall], step: str = "single",
                 memory_context=None, hash_grouping: bool = True,
                 adaptive_partial: bool = True,
                 adaptive_ratio: float = ADAPTIVE_RATIO_THRESHOLD,
                 adaptive_min_rows: int = ADAPTIVE_MIN_ROWS,
                 adaptive_key_buckets: int = ADAPTIVE_KEY_BUCKETS,
                 adaptive_seed: Optional[dict] = None):
        assert step in ("single", "partial", "final")
        self.input_types = list(input_types)
        self.group_channels = list(group_channels)
        self.aggregates = list(aggregates)
        self.step = step
        self.hash_grouping = hash_grouping
        self.adaptive_partial = adaptive_partial and step == "partial"
        self.adaptive_ratio = adaptive_ratio
        self.adaptive_min_rows = adaptive_min_rows
        self.adaptive_key_buckets = max(1, int(adaptive_key_buckets)) \
            if group_channels else 1
        #: adaptive observation window (hash path only: the group count
        #: is already on host from the per-page stats fetch)
        self._adaptive_rows = 0
        self._adaptive_groups = 0
        self._adaptive_decided = False
        #: per-key-range (2, buckets) accumulated [rows, groups]
        self._bucket_stats = np.zeros((2, self.adaptive_key_buckets),
                                      dtype=np.int64)
        #: True once the partial step switched to pass-through
        self.passthrough = False
        #: per-bucket verdicts when the decision SPLIT the key space
        #: (device bool (buckets,)); None = no split decided
        self._pass_buckets = None
        self._pending: List[DevicePage] = []  # pass-through output queue
        #: pages grouped per path, for EXPLAIN/observability
        self.path_counts = {"hash": 0, "dense": 0, "sort": 0,
                            "passthrough": 0, "range_split": 0}
        self._partials: List = []  # DevicePage | SpilledPage entries
        #: group count of the newest ``_aggregate_page`` result, where the
        #: page's ``agg_overflow`` read brought one (exact hash path)
        self._newest_groups: Optional[int] = None
        #: pages aggregated, their summed capacity, the summed capacity of
        #: the partials kept for them, the capacity of the last merge call
        self._lanes = {"pages": 0, "in": 0, "kept": 0, "merge": 0}
        #: merges of kept partials over the operator's life and their
        #: summed capacity: the lanes grouped a second time
        self._merge_calls = 0
        self._merge_lanes = 0
        #: ``hash_group_ids``' probe rounds over the pages whose flags the
        #: step read, and those of them run over a narrow buffer of the
        #: rows still unresolved
        self._probe_rounds = 0
        self._probe_rounds_narrow = 0
        #: group count of the output page, where the last grouping read one
        self._groups_out: Optional[int] = None
        self._emitted = False
        self._done = False
        self._group_dicts: List = [None] * len(group_channels)
        self._kinds = tuple(k for a in self.aggregates
                            for (k, _) in _state_plan(a))
        # per-state: True for a string min/max VALUE state (reduced as a
        # rank, carried across pages as a code in the arg's pool)
        self._str_state: List[bool] = []
        for a in self.aggregates:
            is_str = a.arg_type is not None and a.arg_type.is_pooled
            for (k, _) in _state_plan(a):
                self._str_state.append(is_str and k in ("min", "max"))
        self._state_dicts: List = [None] * len(self._str_state)
        #: where the adaptive verdict came from: "observed" (this run's
        #: window decided) or "hbo" (seeded from recorded history)
        self._adaptive_source = "observed"
        if adaptive_seed and self.adaptive_partial:
            self._apply_adaptive_seed(adaptive_seed)
        self._ctx = memory_context
        if self._ctx is not None:
            self._ctx.set_revoke_callback(self._revoke)

    def _apply_adaptive_seed(self, seed: dict):
        """Pre-decide the adaptive window from a recorded verdict
        (history-based statistics): pass-through/aggregate apply
        directly; a range-split verdict applies only when the bucket
        count matches the recording (a re-tuned bucket knob re-runs
        the observation window instead of misapplying a stale mask)."""
        verdict = seed.get("verdict")
        if verdict == "passthrough":
            self.passthrough = True
        elif verdict == "range-split":
            mask = seed.get("pass_buckets")
            if not mask or len(mask) != self.adaptive_key_buckets:
                return
            self._pass_buckets = jnp.asarray(
                np.asarray(mask, dtype=bool))
        elif verdict != "aggregate":
            return
        self._adaptive_decided = True
        self._adaptive_source = "hbo"

    # output layout: group key columns, then state/final columns per agg
    @property
    def output_types(self) -> List[T.Type]:
        if self.step == "partial":
            return self._intermediate_types()
        keys = [self.input_types[c] for c in self.group_channels]
        return keys + [a.output_type for a in self.aggregates]

    def needs_input(self) -> bool:
        return not self._finishing

    def _count_path(self, path: str):
        self.path_counts[path] += 1
        with _path_totals_lock:
            _path_totals[path] += 1

    def add_input(self, page: DevicePage):
        # capture group-key dictionaries (assumed stable pools per column)
        for i, c in enumerate(self.group_channels):
            d = page.dictionaries[c]
            if d is not None:
                prev = self._group_dicts[i]
                if prev is not None and prev is not d:
                    raise TypeError_(
                        "group key dictionaries changed across pages; "
                        "exchange must unify pools")
                self._group_dicts[i] = d
        # string min/max state pools: same stability contract
        intermediate = self.step == "final"
        nkeys = len(self.group_channels)
        k = 0
        for a in self.aggregates:
            for _ in _state_plan(a):
                if self._str_state[k]:
                    ch = (nkeys + k) if intermediate else a.arg_channel
                    d = page.dictionaries[ch]
                    if d is not None:
                        prev = self._state_dicts[k]
                        if prev is not None and prev is not d:
                            raise TypeError_(
                                "aggregate arg dictionaries changed "
                                "across pages; exchange must unify pools")
                        self._state_dicts[k] = d
                k += 1
        if self.passthrough:
            # adaptive partial aggregation tripped: emit the page in the
            # intermediate keys+states layout without grouping at all
            self._count_path("passthrough")
            self._pending.append(self._passthrough_page(page))
            return
        key_operands = None
        if self._pass_buckets is not None:
            # per-key-range split: cold (mostly-unique) ranges pass
            # through ungrouped, hot ranges keep aggregating — the
            # final step re-groups both, so results are unchanged.
            # The grouping operands feed both the mask and the
            # aggregation below (they don't depend on validity), so
            # compute them once.
            self._count_path("range_split")
            key_types = [self.input_types[c] for c in self.group_channels]
            key_operands = self._grouping_operands(
                page, self.group_channels, key_types)
            mask = _key_range_pass_mask(tuple(key_operands[0]),
                                        self._pass_buckets,
                                        self.adaptive_key_buckets)
            self._pending.append(self._passthrough_page(
                _masked_page(page, page.valid & mask)))
            page = _masked_page(page, page.valid & ~mask)
        partial, self._newest_groups = self._aggregate_page(
            page, intermediate=intermediate, key_operands=key_operands)
        self._lanes["pages"] += 1
        self._lanes["in"] += page.capacity
        self._lanes["kept"] += partial.capacity
        if self._ctx is None:
            self._partials.append(partial)
            return
        from ..exec.memory import reserve_and_append

        reserve_and_append(self._ctx, self._partials, partial)

    def _revoke(self) -> int:
        """Park device partials in host RAM (called by the pool under
        this context's lock; reference: Operator.startMemoryRevoke),
        overflowing to the disk tier when the host ledger is full."""
        from ..exec.memory import spill_pages

        return spill_pages(self._partials, self._ctx.pool,
                           self._ctx.lock)

    def _aggregate_page(self, page: DevicePage, intermediate: bool,
                        key_operands=None
                        ) -> Tuple[DevicePage, Optional[int]]:
        """intermediate=False: page is raw input rows (layout:
        self.input_types, keys at self.group_channels).
        intermediate=True: page is partial-agg output (layout:
        _intermediate_types — keys at channels [0..nkeys), then states).
        ``key_operands``: precomputed (key_ops, key_raws) from the
        range-split path (raw layout only) — skips recomputing them.

        Returns the partial and its group count where the grouping path
        read one (exact hash path): the partial is then
        ``padded_size(ngroups)`` lanes wide, else as wide as ``page``."""
        nkeys = len(self.group_channels)
        if intermediate:
            key_channels = list(range(nkeys))
            key_types = self._intermediate_types()[:nkeys]
        else:
            key_channels = self.group_channels
            key_types = [self.input_types[c] for c in self.group_channels]

        if key_operands is not None:
            key_ops, key_raws = key_operands
        else:
            key_ops, key_raws = self._grouping_operands(
                page, key_channels, key_types)

        if intermediate:
            # states laid out after the keys
            state_cols: List = []
            idx = nkeys
            for a in self.aggregates:
                plan = _state_plan(a)
                raw_states = [page.cols[idx + j] for j in range(len(plan))]
                raw_dicts = [page.dictionaries[idx + j]
                             for j in range(len(plan))]
                idx += len(plan)
                state_cols.extend(_merge_states(a, raw_states, page.valid,
                                                raw_dicts))
        else:
            state_cols = []
            for a in self.aggregates:
                state_cols.extend(_init_states(a, page.cols, page.nulls,
                                               page.valid,
                                               page.dictionaries))

        from .pallas_kernels import pallas_mode

        mode = pallas_mode()
        result = ngroups = None
        if self.hash_grouping and hashable_key_types(key_types):
            result, ngroups = self._hash_group_page(
                page, key_ops, key_raws, key_channels, state_cols, mode,
                observe=not intermediate)
        if result is None:
            self._count_path("sort")
            result = _group_reduce(
                tuple(key_ops), tuple(key_raws), tuple(state_cols),
                page.valid, num_keys=len(self.group_channels),
                num_states=len(state_cols), kinds=self._kinds,
                pallas=mode)
        keep = page.capacity if ngroups is None else padded_size(ngroups)
        if keep < page.capacity:
            # the groups are lanes [0, ngroups): keep a partial as wide as
            # they are, not as wide as the page that made it — the merge
            # pays by lane, and the cap-wide outputs are dropped here
            result = _narrow_lanes(result, keep=keep)
        out_keys, out_key_nulls, reduced, out_valid = result

        # string min/max: reduced RANK -> representative CODE in the
        # captured pool (dead/sentinel lanes clamp; count==0 nulls them)
        reduced = self._states_rank_to_code(list(reduced))

        no_nulls = jnp.zeros_like(out_valid)
        cols = list(out_keys) + reduced
        nulls = [jnp.asarray(n) for n in out_key_nulls] \
            + [no_nulls] * len(reduced)
        dicts = list(self._group_dicts) + self._state_dict_tail()
        return DevicePage(self._intermediate_types(), cols, nulls,
                          out_valid, dicts), ngroups

    def _grouping_operands(self, page: DevicePage, key_channels,
                           key_types):
        """(key_ops, key_raws) grouping operands of one page — pooled
        keys group by lexicographic RANK, not raw code: aligned
        (derived) pools may hold one value under several codes.  The
        representative raw code still rides along for output.  Also
        the stable per-row key identity the key-range bucketing
        hashes, so observation and split agree on every key's
        bucket."""
        key_ops: List = []
        key_raws: List = []
        for c, t in zip(key_channels, key_types):
            col = page.cols[c]
            if getattr(t, "is_pooled", False):
                rank_lut, _ = _rank_and_inverse(page.dictionaries[c])
                ops = group_operands(jnp.asarray(rank_lut)[col],
                                     page.nulls[c], T.BIGINT)
            else:
                ops = group_operands(col, page.nulls[c], t)
            key_ops.extend(ops)
            key_raws.append(col)
        return key_ops, key_raws

    def _hash_group_page(self, page: DevicePage, key_ops, key_raws,
                         key_channels, state_cols, mode: str,
                         observe: bool):
        """Hash-path grouping of one page: (result, ngroups). A None
        result => the caller falls back to the sort oracle (probe-budget
        overflow); ngroups is the page's group count on the host where
        the step reads the page's flags anyway (exact), else None."""
        exact = self.step != "partial"
        gid, group_rows, ngroups, overflow, full, narrow = hash_group_ids(
            tuple(key_ops), page.valid, exact=exact)
        key_nulls = tuple(page.nulls[c] for c in key_channels)
        # dispatch the reduce SPECULATIVELY, before the overflow sync:
        # the device chews on it while the host waits on the scalar, and
        # the (astronomically rare) overflow page just wastes one launch
        result = hash_segment_reduce(gid, group_rows, ngroups,
                                     tuple(key_raws), key_nulls,
                                     tuple(state_cols), self._kinds,
                                     pallas=mode)
        count = None
        if exact:
            # one wait for four scalars of the one program
            with host_sync("agg_overflow"):
                overflow, count, full, narrow = jax.device_get(
                    (overflow, ngroups, full, narrow))
            self._probe_rounds += int(full) + int(narrow)
            self._probe_rounds_narrow += int(narrow)
            if overflow:
                return None, None
            count = int(count)
            if count <= DENSE_GROUPS:
                # the reduce's own branch on the same scalar: a "hash"
                # page whose states took no scatter
                self._count_path("dense")
        elif observe and self.adaptive_partial \
                and not self._adaptive_decided:
            self._observe_reduction(key_ops, page.valid, group_rows,
                                    ngroups)
        self._count_path("hash")
        return result, count

    def _states_rank_to_code(self, state_cols: List) -> List:
        """String min/max value states: lexicographic RANK -> the
        representative CODE in the captured pool (the intermediate-page
        wire contract). Dead/sentinel lanes clamp into range; their
        count state of 0 nulls them downstream."""
        for k, is_str in enumerate(self._str_state):
            if is_str:
                _, inv = _rank_and_inverse(self._state_dicts[k])
                r = jnp.clip(state_cols[k], 0, len(inv) - 1)
                state_cols[k] = jnp.asarray(inv)[r].astype(jnp.int32)
        return state_cols

    def _observe_reduction(self, key_ops, valid, group_rows, ngroups):
        """Accumulate the groups/rows ratio PER KEY-RANGE BUCKET; once
        enough rows are observed, flip pass-through per bucket: all
        buckets non-reducing -> whole-stream pass-through (the classic
        switch), a mix -> range split (reference: adaptive partial
        aggregation; "Partial Partial Aggregates", PAPERS.md)."""
        stats = host_read(_bucket_reduction_stats(
            tuple(key_ops), valid, group_rows, ngroups,
            self.adaptive_key_buckets),
            "agg_adaptive_stats").astype(np.int64)
        self._bucket_stats += stats
        self._adaptive_rows += int(stats[0].sum())
        self._adaptive_groups += int(stats[1].sum())
        if self._adaptive_rows < self.adaptive_min_rows:
            return
        self._adaptive_decided = True
        rows_b, groups_b = self._bucket_stats
        b = self.adaptive_key_buckets
        # a bucket flips only with its share of the evidence: a range
        # barely seen keeps aggregating (the safe default)
        evid = rows_b >= max(1, self.adaptive_min_rows // (2 * b))
        ratios = groups_b / np.maximum(rows_b, 1)
        pass_b = evid & (ratios > self.adaptive_ratio)
        if pass_b.all():
            self.passthrough = True
        elif pass_b.any():
            self._pass_buckets = jnp.asarray(pass_b)

    def _passthrough_page(self, page: DevicePage) -> DevicePage:
        """Raw input page -> intermediate keys+states layout, ungrouped
        (every row its own group; the final step re-groups, so results
        are unchanged — partial aggregation is only a reduction)."""
        state_cols: List = []
        for a in self.aggregates:
            state_cols.extend(_init_states(a, page.cols, page.nulls,
                                           page.valid, page.dictionaries))
        # string min/max states travel as CODES (same wire contract as
        # the reduced path): map rank values back through the pool
        state_cols = self._states_rank_to_code(state_cols)
        cols = [page.cols[c] for c in self.group_channels]
        nulls = [page.nulls[c] for c in self.group_channels]
        no_nulls = jnp.zeros(page.capacity, dtype=bool)
        for s in state_cols:
            cols.append(s)
            nulls.append(no_nulls)
        dicts = list(self._group_dicts) + self._state_dict_tail()
        return DevicePage(self._intermediate_types(), cols, nulls,
                          page.valid, dicts)

    def _intermediate_types(self) -> List[T.Type]:
        keys = [self.input_types[c] for c in self.group_channels]
        states: List[T.Type] = []
        for a in self.aggregates:
            states.extend(intermediate_state_types(a.function, a.arg_type))
        return keys + states

    def get_output(self) -> Optional[DevicePage]:
        if self._pending:
            return self._pending.pop(0)
        if not self._finishing or self._emitted:
            return None
        self._emitted = True
        self._done = True
        merged, ngroups = self._merge_partials()
        self._groups_out = ngroups
        self._partials = []
        if self.step in ("single", "final"):
            merged = self._finalize(merged)
        if self._ctx is not None:
            self._ctx.close()  # output page is in flight, not retained
        # every downstream program is shaped by capacity: hand on a page
        # as wide as the groups. With a count it already is; without one
        # (sort path, partial step) it is as wide as what was merged
        return merged if ngroups is not None else merged.trimmed()

    def _merge_partials(self) -> Tuple[DevicePage, Optional[int]]:
        """The partials as one page, and its group count where known
        (as ``_aggregate_page`` returns them)."""
        types = self._intermediate_types()
        nkeys = len(self.group_channels)
        # a task that saw no input never captured key dictionaries;
        # string outputs still need (empty) pools
        from ..block import Dictionary

        for i in range(nkeys):
            if self._group_dicts[i] is None and types[i].is_pooled:
                self._group_dicts[i] = Dictionary()
        if self._ctx is not None:
            # once merging starts the partials stop being revocable; if
            # the single-chunk transient (concat + result ~= 2x total)
            # wouldn't fit, prepare_finish parks everything on host and
            # the chunked merge below brings it back under budget
            from ..exec.memory import prepare_finish

            prepare_finish(self._ctx, self._partials)
        if not self._partials:
            # no input: zero groups — except global aggregation, which
            # emits exactly one group of empty-input states (count=0,
            # sum=NULL), per SQL semantics
            cap = 16
            cols = [jnp.zeros(cap, dtype=t.storage) for t in types]
            nulls = [jnp.zeros(cap, dtype=bool) for _ in types]
            valid = jnp.zeros(cap, dtype=bool)
            if nkeys == 0:
                valid = valid.at[0].set(True)
            dicts = list(self._group_dicts) + self._state_dict_tail()
            return DevicePage(types, cols, nulls, valid, dicts), \
                int(nkeys == 0)
        from ..exec.memory import SpilledPage, device_page_bytes

        parts = self._partials
        if len(parts) == 1 and self.step != "partial" \
                and not isinstance(parts[0], SpilledPage):
            return parts[0], self._newest_groups  # the sole partial's
        # merge in budget-bounded chunks: each round touches at most
        # ~budget bytes of HBM (uploads + concat), so spilled state
        # re-enters the device incrementally (reference analog:
        # MergingHashAggregationBuilder merging sorted spill runs)
        budget = None
        if self._ctx is not None:
            # each chunk's transient is 2x its bytes (concat + result):
            # cap chunks at max/4 so the transient stays under max/2
            budget = max(self._ctx.pool.max_bytes // 4, 1 << 16)
        while True:
            chunks: List[List] = []
            cur: List = []
            cur_bytes = 0
            for p in parts:
                nb = device_page_bytes(p)
                if cur and len(cur) >= 2 and budget is not None \
                        and cur_bytes + nb > budget:
                    chunks.append(cur)
                    cur, cur_bytes = [], 0
                cur.append(p)
                cur_bytes += nb
            chunks.append(cur)
            if len(chunks) == 1:
                return self._merge_chunk(chunks[0])
            parts = [self._merge_chunk(c)[0] for c in chunks]

    def _merge_chunk(self, chunk: List
                     ) -> Tuple[DevicePage, Optional[int]]:
        """Concatenate one chunk of partials (uploading spilled ones) and
        re-group with merge semantics: (page, ngroups) as
        ``_aggregate_page`` returns them."""
        from ..exec.memory import SpilledPage, device_page_bytes

        types = self._intermediate_types()
        nkeys = len(self.group_channels)
        total = sum(device_page_bytes(p) for p in chunk)
        transient = 0
        if self._ctx is not None:
            # uploads (spilled entries re-entering HBM) + concat buffer +
            # result (bounded by the concat)
            uploads = sum(device_page_bytes(p) for p in chunk
                          if isinstance(p, SpilledPage))
            transient = uploads + 2 * total
            self._ctx.reserve(transient, revocable=False)
        dev = [p.to_device() if isinstance(p, SpilledPage) else p
               for p in chunk]
        if len(dev) == 1 and self.step != "partial" and \
                isinstance(chunk[0], SpilledPage):
            out, ngroups = dev[0], None
        else:
            cap = padded_size(sum(p.capacity for p in dev))
            self._lanes["merge"] = cap
            self._merge_calls += 1
            self._merge_lanes += cap
            cols, nulls = [], []
            for i in range(len(types)):
                c = jnp.concatenate([p.cols[i] for p in dev])
                n = jnp.concatenate([p.nulls[i] for p in dev])
                cols.append(_pad_to(c, cap))
                nulls.append(_pad_to(n, cap))
            valid = _pad_to(jnp.concatenate([p.valid for p in dev]), cap)
            page = DevicePage(
                types, cols, nulls, valid,
                list(self._group_dicts) + self._state_dict_tail())
            out, ngroups = self._aggregate_page(page, intermediate=True)
        if self._ctx is not None:
            # release the transient + the chunk inputs' reservations,
            # keep the merged result reserved
            freed = transient + sum(device_page_bytes(p) for p in chunk
                                    if not isinstance(p, SpilledPage))
            self._ctx.free(freed)
            self._ctx.reserve(device_page_bytes(out), revocable=False)
        return out, ngroups

    def _finalize(self, merged: DevicePage) -> DevicePage:
        nkeys = len(self.group_channels)
        if nkeys == 0:
            # global aggregation always emits exactly one row, even over
            # zero input rows (lane 0 then holds empty-input states)
            one = jnp.arange(merged.capacity) == 0
            merged = DevicePage(merged.types, merged.cols, merged.nulls,
                                merged.valid | one, merged.dictionaries)
        out_cols = list(merged.cols[:nkeys])
        out_nulls = list(merged.nulls[:nkeys])
        idx = nkeys
        for a in self.aggregates:
            plan = _state_plan(a)
            states = [merged.cols[idx + j] for j in range(len(plan))]
            idx += len(plan)
            raw, null = _final_project(a, states)
            out_cols.append(raw.astype(a.output_type.storage))
            out_nulls.append(null | ~merged.valid)
        types = self.output_types
        agg_dicts = []
        k = 0
        for a in self.aggregates:
            plan = _state_plan(a)
            agg_dicts.append(self._state_dicts[k]
                             if self._str_state[k] else None)
            k += len(plan)
        dicts = list(self._group_dicts) + agg_dicts
        return DevicePage(types, out_cols, out_nulls, merged.valid, dicts)

    def _state_dict_tail(self) -> List:
        """Dictionaries for the state columns of an intermediate-layout
        page (string min/max value states keep their pool)."""
        return [self._state_dicts[k] if self._str_state[k] else None
                for k in range(len(self._str_state))]

    def metrics(self) -> dict:
        """Grouping-path observability for EXPLAIN ANALYZE: pages per
        path and, once the adaptive window decided, what it decided
        (whole-stream pass-through vs the per-key-range split)."""
        out = {"grouping_paths": {k: v for k, v in
                                  self.path_counts.items() if v},
               "partial_lanes": dict(self._lanes),
               "merge_calls": self._merge_calls,
               "merge_lanes": self._merge_lanes,
               "probe_rounds": self._probe_rounds,
               "probe_rounds_narrow": self._probe_rounds_narrow}
        if self._groups_out is not None:
            out["groups_out"] = self._groups_out
        seeded = " (seeded by hbo)" \
            if self._adaptive_source == "hbo" else ""
        if self.passthrough:
            out["adaptive"] = "passthrough" + seeded
        elif self._pass_buckets is not None:
            out["adaptive"] = (
                f"range-split "
                f"{int(np.asarray(self._pass_buckets).sum())}/"
                f"{self.adaptive_key_buckets} buckets pass through"
                + seeded)
        if self.adaptive_partial and self._adaptive_decided:
            # the decided verdict, machine-readable: history-based
            # statistics store it and seed the next run's operator
            if self.passthrough:
                verdict: dict = {"verdict": "passthrough"}
            elif self._pass_buckets is not None:
                verdict = {"verdict": "range-split",
                           "pass_buckets": [
                               int(b) for b in
                               np.asarray(self._pass_buckets)]}
            else:
                verdict = {"verdict": "aggregate"}
            out["adaptive_verdict"] = verdict
        return out

    def is_finished(self) -> bool:
        return self._done


def _masked_page(page: DevicePage, valid) -> DevicePage:
    """The same page under a different validity mask (columns shared)."""
    return DevicePage(page.types, page.cols, page.nulls, valid,
                      page.dictionaries)


def _pad_to(arr, cap: int):
    n = arr.shape[0]
    if n == cap:
        return arr
    pad = jnp.zeros((cap - n,), dtype=arr.dtype)
    return jnp.concatenate([arr, pad])
