"""Global-hash device aggregation: ONE table across the mesh.

Reference analog: "Global Hash Tables Strike Back!" (PAPERS.md,
arXiv 2505.04153) — a single shared hash table updated by every thread
beats partition-then-aggregate for GROUP BY across a wide NDV range.
On a TPU mesh the translation is: instead of the exchange+merge-final
shape the planner builds (all_to_all of partial groups, then per-device
re-grouping — ``parallel/device_exchange`` under a FINAL aggregation),
every device owns a REPLICATED open-addressing table and updates it
with collective scatter-adds: local scatter into the table, one
``psum``/``pmin``/``pmax`` per state column to merge the replicas.  For low-NDV grouping
the table is tiny, so the collectives move O(table) bytes instead of
O(partial groups) rows — and no re-grouping kernel runs at all.

Insert protocol (the claim loop — ``ops/hashtable.py``'s vectorized
insert-or-lookup lifted to the mesh):

- group keys pack injectively into one uint64 (``pack_keys``; the cost
  model gates on packability), hashed by the same splitmix64 finalizer
  the local GroupByHash uses;
- each probe round, unresolved rows propose slot ``(h + r) & mask``;
  the candidate key per slot is the scatter-MIN of proposers, globally
  agreed by ``lax.pmin`` over the mesh, and lands only in still-empty
  slots — every device applies the identical update, so the replicas
  never diverge;
- rows whose key owns their slot are resolved; colliders advance.
  Rows unresolved after the (static) round budget are reported so the
  caller can fall back to the exchange path — exactness first.

Single-device mode (``axis_name=None``) drops the collectives and is
the oracle the tests compare against the sort-based reduce.
"""

from __future__ import annotations

from functools import partial
from typing import Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .. import jit_stats
from .hashtable import splitmix64

#: empty-slot sentinel: packed keys reserve it by construction
#: (``pack_keys`` biases every operand by +1, so all-ones cannot occur
#: within the gated bit budget)
EMPTY = np.uint64(0xFFFFFFFFFFFFFFFF)

#: linear-probe round budget (mirrors ``hashtable.PROBE_ROUNDS``): with
#: load factor <= 0.5 an unresolved row after 32 probes is
#: astronomically rare; the caller falls back on overflow regardless
PROBE_ROUNDS = 32


def pack_keys(cols: Sequence, nulls: Sequence, widths: Tuple[int, ...]):
    """Injective uint64 packing of non-negative key operands: each
    column takes ``width`` bits holding value+1 (0 = NULL), so distinct
    key tuples — including NULLs — pack to distinct u64s and the
    all-ones EMPTY sentinel is unreachable.  Traced helper: call inside
    the jit'd program; the caller gates that values fit the widths."""
    acc = jnp.zeros(cols[0].shape, dtype=jnp.uint64)
    for c, nl, w in zip(cols, nulls, widths):
        v = c.astype(jnp.int64).view(jnp.uint64) + np.uint64(1)
        if nl is not None:
            v = jnp.where(nl, np.uint64(0), v)
        acc = (acc << np.uint64(w)) | v
    return acc


def unpack_keys(packed, widths: Tuple[int, ...]):
    """Inverse of ``pack_keys``: [(value_i64, null_bool)] per column."""
    out = []
    shift = 0
    for w in reversed(widths):
        v = (packed >> np.uint64(shift)) & np.uint64((1 << w) - 1)
        null = v == 0
        out.append(((v - np.uint64(1)).astype(jnp.int64)
                    & np.int64((1 << w) - 1), null))
        shift += w
    return list(reversed(out))


@partial(jax.jit, static_argnames=("table_size", "rounds", "axis_name"))
def global_hash_insert(packed, valid, table_size: int,
                       rounds: int = PROBE_ROUNDS,
                       axis_name: Optional[str] = None):
    """Claim-loop insert into the replicated global table.

    Returns (table, slot_of, resolved, unresolved): ``table`` holds the
    owning packed key per slot (EMPTY = free) — identical on every
    device; ``slot_of``/``resolved`` are this device's per-row
    assignments; ``unresolved`` is the GLOBAL count of live rows that
    exhausted the probe budget (nonzero => caller must fall back)."""
    jit_stats.bump("global_hash_insert")
    mask = np.uint64(table_size - 1)
    h = splitmix64(packed)
    slot0 = (h & mask).astype(jnp.int32)

    def probe_round(r, carry):
        table, resolved, slot_of = carry
        active = ~resolved
        slot = jnp.where(active, (slot0 + r) & jnp.int32(table_size - 1),
                         table_size)
        # candidate owner per slot: scatter-min locally (masked lanes
        # land in the dummy slot), pmin globally — all devices install
        # the identical winner into still-empty slots
        claim = jnp.full((table_size + 1,), EMPTY, dtype=jnp.uint64)
        claim = claim.at[slot].min(packed)
        claim = claim[:table_size]
        if axis_name is not None:
            claim = jax.lax.pmin(claim, axis_name)
        table = jnp.where(table == EMPTY, claim, table)
        owner = table[jnp.clip(slot, 0, table_size - 1)]
        won = active & (owner == packed)
        slot_of = jnp.where(won, slot, slot_of)
        return table, resolved | won, slot_of

    table0 = jnp.full((table_size,), EMPTY, dtype=jnp.uint64)
    table, resolved, slot_of = jax.lax.fori_loop(
        0, rounds, probe_round,
        (table0, ~valid, jnp.zeros_like(slot0)))
    unresolved = jnp.sum((valid & ~resolved).astype(jnp.int32))
    if axis_name is not None:
        unresolved = jax.lax.psum(unresolved, axis_name)
    return table, slot_of, resolved, unresolved


@partial(jax.jit, static_argnames=("table_size", "kinds", "axis_name"))
def global_hash_reduce(slot_of, resolved, valid, state_cols: Tuple,
                       kinds: Tuple, table_size: int,
                       axis_name: Optional[str] = None):
    """Collective scatter-reduce of per-row states into the global
    table: local scatter by assigned slot, then one psum/pmin/pmax per
    state column merges the replicas.  States arrive sentinel-
    neutralized (``aggregation._merge_states``/``_init_states``), so
    empty slots hold each kind's neutral element and ``_final_project``
    nulls them via the count state."""
    jit_stats.bump("global_hash_reduce")
    idx = jnp.where(resolved & valid, slot_of, table_size)
    out = []
    for kind, col in zip(kinds, state_cols):
        is_float = jnp.issubdtype(col.dtype, jnp.floating)
        if kind == "sum":
            acc = jnp.zeros((table_size + 1,), dtype=col.dtype)
            acc = acc.at[idx].add(col)[:table_size]
            if axis_name is not None:
                acc = jax.lax.psum(acc, axis_name)
        elif kind == "min":
            sent = jnp.inf if is_float else jnp.iinfo(col.dtype).max
            acc = jnp.full((table_size + 1,), sent, dtype=col.dtype)
            acc = acc.at[idx].min(col)[:table_size]
            if axis_name is not None:
                acc = jax.lax.pmin(acc, axis_name)
        else:
            sent = -jnp.inf if is_float else jnp.iinfo(col.dtype).min
            acc = jnp.full((table_size + 1,), sent, dtype=col.dtype)
            acc = acc.at[idx].max(col)[:table_size]
            if axis_name is not None:
                acc = jax.lax.pmax(acc, axis_name)
        out.append(acc)
    return tuple(out)
