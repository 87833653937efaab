"""Vectorized open-addressing GroupByHash primitive.

Reference analog: ``operator/MultiChannelGroupByHash.java`` (the
putIfAbsent loop assigning dense group ids) — redesigned as a fully
vectorized page-at-a-time kernel instead of a row-at-a-time loop, the
hash-based plan shape of "Global Hash Tables Strike Back!" (PAPERS.md).

Design:
  - keys arrive as the engine's normalized grouping operands
    (``ops/sortkeys.group_operands``: a (tag_u8, u64) pair per key
    column) — all integer lanes, so one splitmix64 mix per operand
    yields the bucket hash;
  - the table is ``2 * capacity`` slots (power of two, load factor
    <= 0.5) storing the REPRESENTATIVE ROW INDEX of the group that owns
    each slot (``capacity`` = empty sentinel), plus one dummy slot that
    absorbs masked scatters;
  - insert-or-lookup runs a bounded number of linear-probe ROUNDS, each
    round fully vectorized over its lanes: every unresolved row probes
    ``(h + round) & mask``, empty slots are claimed by scatter-min on
    row index, claimants re-gather the installed owner and compare full
    keys by gathering the owner row's operands — equal keys join the
    owner's group, colliders advance to the next probe. A round is five
    gathers / scatters on the table and a gather a key operand, and on
    a TPU each costs by the lanes it is given; the loop runs as many
    rounds as the page's LONGEST collision chain, while the mean chain
    is a little over one: a q1 page (4 groups) takes 1 round, a q3 page
    4, a q13 or first-level q18 page (65-100 k groups in 262,144 lanes)
    9, q18's merge (1.5 M groups in 2,097,152 lanes) 16 — and after the
    first round 5-11 % of the lanes are still looking, after the second
    1-3 %;
  - so the probe NARROWS: the page's own width probes only while more
    rows are unresolved than an eighth of its lanes (``_NARROW_BY``; the
    count is taken on the device each round, nothing is assumed of the
    data: a full page runs its first round wide, a page of few valid
    rows — a selective join's output — none); then the unresolved
    rows' indices, first slots and key operands are gathered once into
    a buffer that wide, in row order (``_first_lanes``), the same round
    runs over the buffer against the same table — the row index that
    claims a slot is the original one, so the smallest probing row
    still wins, and the round counter and its budget carry on — and
    one scatter puts the owners found back. The buffer narrows again
    the same way (``_NARROW_LEVELS``); a page that resolved at its own
    width (every q1 page) skips all of it under a ``lax.cond``. In round ``r`` the same rows probe the same
    slots as in a loop that ran every round over the whole page, so the
    outputs are equal lane for lane; ``rounds_full`` / ``rounds_narrow``
    say how the rounds were run (``probe_rounds`` on the aggregation's
    span);
  - dense group ids are a cumsum over "row owns itself" leaders, so gid
    order is first-occurrence order (matching the reference's
    putIfAbsent numbering), with no sort anywhere.

Rows still unresolved after the probe budget either overflow (exact
mode: the caller falls back to the sort-based oracle) or become
singleton groups (partial aggregation tolerates duplicate groups — the
final step re-groups, per "Partial Partial Aggregates", PAPERS.md).

Which way a page's states are reduced (``hash_segment_reduce``) is
decided on the device, by the page's own group count:
  - at most ``DENSE_GROUPS`` groups (q1's four, a global aggregate's
    one): **dense** — each state column is compared with the group ids
    ``[0, DENSE_GROUPS)`` and reduced under the mask, one fused read of
    ``gid`` and the column, no scatter and no table; exact in int64 in
    any order (a float ``sum`` adds in another order, as between any two
    reductions); the group keys are gathered for those ids alone;
  - more: **scatter** — ``jax.ops.segment_*`` into ``cap + 1``
    segments (the Pallas kernel for the 32-bit states on a TPU), whose
    cost is by the lane whatever the number of groups, and a key
    gather as wide as the page.
``ngroups`` is an operand of the program, so the branch is a
``lax.cond`` inside it: no knob, no history, one cache key. A page no
wider than ``DENSE_GROUPS`` lanes is dense by its shape. With no key
columns (a global aggregate) there is one group at most, which is known
when the program is traced: the group ids are written in closed form,
no table is built (``_keyless_group_ids``), and the reduce is one
masked reduction a state with no branch in it.

Float keys are NOT hashed here: the TPU x64 rewriter cannot bitcast
f64<->u64 (see ops/sortkeys.py), so float grouping keys keep the
sort-based path. ``hashable_key_types`` is the gate.
"""

from __future__ import annotations

from functools import partial
from typing import Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .. import jit_stats
from .. import types as T
from ..telemetry.profiler import instrument

#: linear-probe rounds per page: with load factor <= 0.5 and a 64-bit
#: mixed hash, an unresolved row after 32 probes is astronomically rare
#: for non-adversarial input; adversarial input falls back / singles out.
PROBE_ROUNDS = 32

#: a page with at most this many groups has its states reduced by
#: compare-and-sum instead of a scatter (module docstring). The dense
#: cost grows with it and the scatter's does not; chosen on a v5e from a
#: sweep at 262,144 lanes and 15 int64 columns (PERF.md §5, PR 29).
DENSE_GROUPS = 128

#: the probe goes on over the rows still unresolved alone once they fit a
#: buffer this many times narrower than the lanes probing, and does so
#: again, ``_NARROW_LEVELS`` times at most. Pages of any width: on a v5e
#: it pays from 1,024 lanes up and is even at 256 (the probe-round
#: sweep, PERF.md section 5, PR 40)
_NARROW_BY = 8
_NARROW_LEVELS = 2

_M1 = np.uint64(0xBF58476D1CE4E5B9)
_M2 = np.uint64(0x94D049BB133111EB)
_M3 = np.uint64(0x9E3779B97F4A7C15)  # golden-ratio increment


def hashable_key_types(key_types: Sequence[T.Type]) -> bool:
    """True when every grouping key can take the hash path (integer
    operands only — floats keep the sort path, see module docstring)."""
    return all(t not in (T.DOUBLE, T.REAL) for t in key_types)


def splitmix64(x):
    """The splitmix64 finalizer over uint64 lanes (public-domain
    constant set; also the reference's XxHash-style mixing role)."""
    x = (x + _M3).astype(jnp.uint64)
    x = (x ^ (x >> np.uint64(30))) * _M1
    x = (x ^ (x >> np.uint64(27))) * _M2
    return x ^ (x >> np.uint64(31))


def _mix_operands(key_ops: Tuple, n: int):
    """Combine the flattened (tag, key) operand columns into one 64-bit
    hash per row. Zero key columns (global aggregation) hash to 0."""
    h = jnp.zeros((n,), dtype=jnp.uint64)
    for op in key_ops:
        h = splitmix64(h ^ op.astype(jnp.uint64))
    return h


def _first_lanes(mask, size: int):
    """int32[size]: the positions of ``mask``'s first ``size`` set lanes,
    ascending, and ``mask.shape[0]`` in the lanes past them. A prefix
    count gives every set lane its place and one scatter at those
    (ascending) places puts it there; unset lanes and the set ones past
    ``size`` go to a dummy lane."""
    width = mask.shape[0]
    place = jnp.cumsum(mask, dtype=jnp.int32) - 1
    place = jnp.where(mask, jnp.minimum(place, size), size)
    lanes = jnp.full((size + 1,), width, dtype=jnp.int32)
    lanes = lanes.at[place].set(jnp.arange(width, dtype=jnp.int32))
    return lanes[:size]


def _probe_widths(cap: int) -> Tuple[int, ...]:
    """The lane widths a page's probe may run at, the page's own first."""
    widths = [cap]
    while len(widths) <= _NARROW_LEVELS and widths[-1] >= _NARROW_BY:
        widths.append(widths[-1] // _NARROW_BY)
    return tuple(widths)


def _hash_group_ids_impl(key_ops: Tuple, valid,
                         rounds: int = PROBE_ROUNDS,
                         exact: bool = True):
    """Vectorized insert-or-lookup over one page.

    Raw (un-jitted, un-instrumented) implementation: the batched
    executor composes it under its own ``jit(vmap(...))`` wrappers —
    calling the instrumented public name inside a trace would run the
    profiler's host bookkeeping per vmap lane. Host callers use the
    ``hash_group_ids`` binding below.

    key_ops: flattened (tag_u8, u64) grouping operands (integer dtypes).
    valid:   bool lane mask; invalid lanes get the dump gid ``capacity``.

    Returns (gid, group_rows, ngroups, overflow, rounds_full,
    rounds_narrow):
      gid        int32 (cap,)   dense group id per row, first-occurrence
                                order; invalid lanes get ``cap``
      group_rows int32 (cap,)   representative row index per group id
      ngroups    int32 scalar   number of groups assigned
      overflow   bool scalar    exact mode only: some row exhausted its
                                probe budget and NO gid is trustworthy
                                (caller must fall back). In non-exact
                                mode always False: unresolved rows become
                                their own singleton groups.
      rounds_full, rounds_narrow
                 int32 scalars  probe rounds run over the page's own
                                lanes, and over a narrow buffer of the
                                rows still unresolved (module docstring)
    """
    if not key_ops:
        return _keyless_group_ids(valid)
    jit_stats.bump("hash_group_ids")
    cap = valid.shape[0]
    # 2x capacity rounded up to a power of two (pages are pow2-padded
    # already; defend against odd capacities so the & mask stays sound)
    tsize = 1 << max(2 * cap - 1, 1).bit_length()
    mask = np.uint64(tsize - 1)
    row_idx = jnp.arange(cap, dtype=jnp.int32)

    h = _mix_operands(key_ops, cap)
    slot0 = (h & mask).astype(jnp.int32)

    # slot -> owning row index; ``cap`` = empty; slot ``tsize`` is the
    # dummy that absorbs scatters from masked-off lanes
    table0 = jnp.full((tsize + 1,), cap, dtype=jnp.int32)
    # a lane's owner row; ``cap`` = still looking (invalid lanes never do)
    rep0 = jnp.where(valid, cap, row_idx)

    def probe(rows, slot0, own, widths, r, table, rep, left):
        """Probe rounds over one set of lanes — the page's, or a buffer
        of the rows still unresolved: ``rows`` their row indices,
        ``slot0`` and ``own`` their first slots and key operands — until
        the ``left`` unresolved ones fit ``widths[1]`` lanes, then over
        those alone. Returns (rep, rounds run at each of ``widths``)."""
        narrow = widths[1] if len(widths) > 1 else 0

        def probe_round(carry):
            r, table, rep, _left = carry
            active = rep == cap
            slot = jnp.where(active, (slot0 + r) & (tsize - 1), tsize)
            owner = table[slot]
            empty = active & (owner == cap)
            # claim empty slots: smallest probing row index wins the install
            claim = jnp.full((tsize + 1,), cap, dtype=jnp.int32)
            claim = claim.at[jnp.where(empty, slot, tsize)].min(rows)
            winner = empty & (claim[slot] == rows)
            table = table.at[jnp.where(winner, slot, tsize)].set(rows)
            owner = table[slot]
            # full-key compare against the (possibly just-installed) owner
            owner_safe = jnp.clip(owner, 0, cap - 1)
            eq = active & (owner < cap)
            for mine, op in zip(own, key_ops):
                eq = eq & (mine == op[owner_safe])
            rep = jnp.where(eq, owner, rep)
            return r + 1, table, rep, jnp.sum(rep == cap, dtype=jnp.int32)

        def keep_probing(carry):
            r, _table, _rep, left = carry
            return (r < rounds) & (left > narrow)

        r_in = r
        r, table, rep, left = jax.lax.while_loop(
            keep_probing, probe_round, (r, table, rep, left))
        ran = (r - r_in,)
        if not narrow:
            return rep, ran

        def narrowed(r, table, rep):
            # the budget can end the loop with more left than fit: the
            # rounds are spent then, and what the buffer holds goes back
            # as it came
            at = _first_lanes(rep == cap, narrow)
            live = at < rows.shape[0]
            safe = jnp.where(live, at, 0)
            rep_n, ran_n = probe(
                jnp.where(live, rows[safe], cap), slot0[safe],
                tuple(op[safe] for op in own), widths[1:], r, table,
                jnp.where(live, cap, 0), jnp.minimum(left, narrow))
            return rep.at[at].set(rep_n, mode="drop"), ran_n

        def done(r, table, rep):
            return rep, (jnp.zeros((), jnp.int32),) * (len(widths) - 1)

        rep, ran_n = jax.lax.cond(left > 0, narrowed, done, r, table, rep)
        return rep, ran + ran_n

    rep, ran = probe(row_idx, slot0, key_ops, _probe_widths(cap),
                     jnp.zeros((), dtype=jnp.int32), table0, rep0,
                     jnp.sum(valid, dtype=jnp.int32))

    unresolved = rep == cap
    if exact:
        overflow = jnp.any(unresolved)
    else:
        # partial aggregation tolerates duplicate groups: unresolved
        # rows lead their own singleton group
        rep = jnp.where(unresolved, row_idx, rep)
        overflow = jnp.zeros((), dtype=bool)

    leader = valid & (rep == row_idx)
    prefix = jnp.cumsum(leader.astype(jnp.int32)) - 1  # leader gid
    rep_safe = jnp.clip(rep, 0, cap - 1)
    gid = jnp.where(valid & (rep < cap), prefix[rep_safe], cap)
    ngroups = jnp.sum(leader.astype(jnp.int32))
    group_rows = jnp.zeros((cap + 1,), dtype=jnp.int32)
    group_rows = group_rows.at[jnp.where(leader, prefix, cap)].set(row_idx)
    return (gid, group_rows[:cap], ngroups, overflow, ran[0],
            sum(ran[1:], jnp.zeros((), jnp.int32)))


def _keyless_group_ids(valid):
    """``_hash_group_ids_impl`` with no key columns, in closed form:
    every valid row is group 0, so there is nothing to hash, probe or
    install. Same outputs, the empty page's included."""
    jit_stats.bump("keyless_group_ids")
    cap = valid.shape[0]
    row_idx = jnp.arange(cap, dtype=jnp.int32)
    gid = jnp.where(valid, 0, cap).astype(jnp.int32)
    first = jnp.min(jnp.where(valid, row_idx, cap))
    some = first < cap
    group_rows = jnp.zeros((cap,), dtype=jnp.int32).at[0].set(
        jnp.where(some, first, 0))
    none = jnp.zeros((), dtype=jnp.int32)
    return (gid, group_rows, some.astype(jnp.int32),
            jnp.zeros((), dtype=bool), none, none)


# profiled entry points (telemetry.profiler): cost/compile
# attribution under EXPLAIN ANALYZE VERBOSE; plain calls when off
hash_group_ids = instrument(
    "hash_group_ids",
    partial(jax.jit, static_argnames=("rounds", "exact"))(
        _hash_group_ids_impl),
    static_argnames=("rounds", "exact"))


def _scatter_reduce(gid, state_cols: Tuple, kinds: Tuple, pallas: str):
    """State columns reduced by gid into ``cap + 1`` segments.

    The Pallas segment kernel requires non-decreasing gids (steps <= 1),
    so the states it takes (int32/float32 on a TPU backend) are sorted
    by gid (``sort_carrying``: one two-operand sort, a gather each).
    The other states reduce by the unsorted gid in
    ``jax.ops.segment_*`` and are never sorted — a carried column is
    what makes a sort slow to compile for the TPU.
    """
    from .pallas_kernels import segment_reduce, takes_kernel
    from .sortkeys import sort_carrying

    cap = gid.shape[0]
    in_kernel = [takes_kernel(c.dtype, cap + 1, pallas)
                 for c in state_cols]
    if any(in_kernel):
        (s_gid,), s_states = sort_carrying(
            [gid], [c for c, k in zip(state_cols, in_kernel) if k])
        s_states = iter(s_states)
    reduced = []
    for kind, col, kernel in zip(kinds, state_cols, in_kernel):
        if kernel:
            r = segment_reduce(next(s_states), s_gid,
                               num_segments=cap + 1, kind=kind,
                               mode=pallas)
        else:
            r = segment_reduce(col, gid, num_segments=cap + 1, kind=kind,
                               mode="")
        reduced.append(r[:cap])
    return tuple(reduced)


# a sum accumulates in its column's own dtype, as ``segment_sum`` does
_DENSE_OPS = {"sum": partial(jnp.sum, promote_integers=False),
              "min": jnp.min, "max": jnp.max}


def _identity(kind: str, dtype):
    """What ``jax.ops.segment_<kind>`` leaves in an empty segment."""
    if kind == "sum":
        return 0
    if jnp.issubdtype(dtype, jnp.floating):
        return jnp.inf if kind == "min" else -jnp.inf
    info = jnp.iinfo(dtype)
    return info.max if kind == "min" else info.min


def _group_keys(key_raws: Tuple, key_nulls: Tuple, safe_idx, out_valid):
    """Each group's key columns, read at its representative row."""
    return (tuple(kr[safe_idx] for kr in key_raws),
            tuple(kn[safe_idx] & out_valid for kn in key_nulls))


def _widened(head, fill, cap: int):
    """``head`` followed by ``fill`` up to ``cap`` lanes."""
    tail = jnp.full((cap - head.shape[0],), fill, head.dtype)
    return jnp.concatenate([head, tail])


def _dense_reduce(gid, safe_idx, out_valid, key_raws: Tuple,
                  key_nulls: Tuple, state_cols: Tuple, kinds: Tuple,
                  k: int):
    """``_scatter_reduce`` and ``_group_keys`` for a page whose gids all
    lie below ``k``, with nothing as wide as the page but the reads:
    compare ``gid`` with each of those ids and reduce the column under
    the mask; gather the keys of those ids alone. The results fill lanes
    ``[0, k)`` of outputs as wide as the scatter's; past them a state
    holds its kind's identity (what ``jax.ops.segment_*`` leaves in an
    empty segment) and a key row 0's (what index 0 gathers). Invalid
    lanes carry ``gid == cap`` and match no id."""
    cap = gid.shape[0]
    hit = gid[None, :] == jnp.arange(k, dtype=gid.dtype)[:, None]
    reduced = []
    for kind, col in zip(kinds, state_cols):
        ident = jnp.asarray(_identity(kind, col.dtype), col.dtype)
        r = _DENSE_OPS[kind](jnp.where(hit, col[None, :], ident), axis=1)
        reduced.append(_widened(r, ident, cap))
    raws, nulls = _group_keys(key_raws, key_nulls, safe_idx[:k],
                              out_valid[:k])
    return (tuple(_widened(r, kr[0], cap) for r, kr in zip(raws, key_raws)),
            tuple(_widened(n, False, cap) for n in nulls), tuple(reduced))


def _hash_segment_reduce_impl(gid, group_rows, ngroups, key_raws: Tuple,
                              key_nulls: Tuple, state_cols: Tuple,
                              kinds: Tuple, pallas: str = ""):
    """Reduce state columns by hash-assigned gid and gather group keys.

    Raw implementation (see ``_hash_group_ids_impl`` for why); host
    callers use the jitted+instrumented ``hash_segment_reduce`` below.

    The page's group count picks the way on the device (module
    docstring): ``_dense_reduce`` up to ``DENSE_GROUPS`` groups,
    ``_scatter_reduce`` and a page-wide key gather beyond. Under ``vmap``
    the predicate is batched, the ``cond`` becomes a select and a lane
    pays for both. Rows cannot differ on no key column, so a keyless
    page has at most one group, known when the program is traced: it
    is one masked reduction a state, with no branch.

    Returns (group_key_raws, group_key_nulls, reduced_states, out_valid)
    in the exact shape contract of ``aggregation._group_reduce``.
    """
    jit_stats.bump("hash_segment_reduce")
    cap = gid.shape[0]
    limit = min(DENSE_GROUPS if key_raws else 1, cap)
    out_valid = jnp.arange(cap, dtype=jnp.int32) < ngroups
    safe_idx = jnp.where(out_valid, group_rows, 0)

    def dense():
        return _dense_reduce(gid, safe_idx, out_valid, key_raws, key_nulls,
                             state_cols, kinds, limit)

    def scatter():
        return _group_keys(key_raws, key_nulls, safe_idx, out_valid) \
            + (_scatter_reduce(gid, state_cols, kinds, pallas),)

    if limit == cap or not key_raws:
        out = dense()
    else:
        out = jax.lax.cond(ngroups <= limit, dense, scatter)
    return (*out, out_valid)


hash_segment_reduce = instrument(
    "hash_segment_reduce",
    partial(jax.jit, static_argnames=("kinds", "pallas"))(
        _hash_segment_reduce_impl),
    static_argnames=("kinds", "pallas"))
