"""Device-collective exchange: hash repartitioning as XLA all-to-all.

Reference analog: the ENTIRE pull-based HTTP shuffle path —
``operator/output/PartitionedOutputOperator.java`` + ``PagePartitioner``
(producer side) and ``operator/ExchangeOperator.java`` +
``DirectExchangeClient`` (consumer side), SURVEY.md §2.8.

TPU-first redesign: when producer and consumer stages are co-resident on a
pod slice, a stage boundary needs no serialization, no HTTP, no buffers —
each device bucket-sorts its rows by destination partition and one XLA
``all_to_all`` over ICI delivers every row to its owner. The host never
touches the data.

Capacity model: all_to_all needs equal-sized lanes, so each device sends a
fixed ``per_dest`` lanes to each destination. Rows beyond capacity are
counted in the returned ``overflow`` (host checks and can re-run with a
larger factor); with hash partitioning overflow implies heavy skew.

Count-first sizing: instead of guessing ``per_dest`` and paying the 2x
re-run cliff on overflow, callers can first run a tiny counting
collective (``partition_histogram`` + psum/pmax over the mesh — O(n*d)
scalars, negligible vs the payload) to learn the exact max
(sender, destination) load and size the data ``all_to_all`` exactly;
the overflow retry then remains only as a bug backstop. See
``parallel/device_exchange._count_program``.
"""

from __future__ import annotations

import zlib
from functools import partial
from typing import Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map

from .. import jit_stats
from .. import types as T
from ..ops.sortkeys import sort_carrying


def string_hash_lut(d) -> np.ndarray:
    """code -> stable value hash (crc32): equal strings route equally
    regardless of which dictionary pool coded them. THE one definition —
    host and device exchange paths must agree or mixed-path joins break."""
    if d is None or len(d) == 0:
        return np.zeros(1, dtype=np.uint64)
    return np.asarray([zlib.crc32(("" if v is None else v).encode())
                       for v in d.values], dtype=np.uint64)


def key_to_u64(raw, nulls, type_: T.Type, lut: Optional[jnp.ndarray] = None):
    """Value-stable uint64 normalization of one key column for partition
    hashing (device op). ``lut`` is the string channel's crc LUT. THE one
    definition shared by the host path (ops/output.PartitionedOutput-
    Operator) and the device collective (parallel/device_exchange)."""
    if type_.is_string:
        k = lut[raw]
    elif type_ in (T.DOUBLE, T.REAL):
        # deterministic quantization (equal floats -> equal id); f64<->u64
        # bitcasts don't lower on the TPU x64 path
        k = (jnp.asarray(raw, jnp.float64)
             * 65536.0).astype(jnp.int64).view(jnp.uint64)
    elif type_ == T.BOOLEAN:
        k = raw.astype(jnp.uint64)
    else:
        k = raw.astype(jnp.int64).view(jnp.uint64)
    return jnp.where(nulls, jnp.uint64(0), k)


def hash_partition_ids(keys_u64: Sequence, num_partitions: int):
    """Combine pre-normalized uint64 key columns into partition ids.

    Mirrors the reference's InterpretedHashGenerator (CRC-style combined
    row hash -> partition), using splitmix64 finalization per column.
    """
    acc = jnp.zeros(keys_u64[0].shape, dtype=jnp.uint64)
    for k in keys_u64:
        z = (k + np.uint64(0x9E3779B97F4A7C15)) * np.uint64(0xBF58476D1CE4E5B9)
        z = z ^ (z >> np.uint64(27))
        acc = acc * np.uint64(31) + z
    acc = acc ^ (acc >> np.uint64(33))
    return (acc % np.uint64(num_partitions)).astype(jnp.int32)


def partition_histogram(part_ids, valid, num_partitions: int):
    """Per-destination live-row counts of ONE sender (device op): the
    count-first pass each sender runs before a collective to size its
    lanes from data instead of a capacity guess. Dead rows drop into a
    discarded overflow slot."""
    idx = jnp.where(valid, part_ids, num_partitions).astype(jnp.int32)
    hist = jnp.zeros((num_partitions + 1,), jnp.int32).at[idx].add(
        1, mode="drop")
    return hist[:num_partitions]


@partial(jax.jit, static_argnames=("num_partitions", "per_dest", "axis_name"))
def repartition_a2a(cols: Tuple, nulls: Tuple, valid, part_ids,
                    num_partitions: int, per_dest: int,
                    axis_name: str = "x"):
    """Inside shard_map: route each live row to the device owning its
    partition. Returns (cols, nulls, valid, overflow_count) with capacity
    num_partitions * per_dest on each receiver.

    Implementation: bucket-sort rows by destination, lay them into a
    (num_partitions, per_dest) send grid, one lax.all_to_all, flatten.
    """
    jit_stats.bump("repartition_a2a")
    cap = valid.shape[0]
    # sort rows by (invalid, destination): live rows grouped by dest
    dest = jnp.where(valid, part_ids, num_partitions)
    (s_dest,), s_rest = sort_carrying(
        [dest.astype(jnp.int32)], list(cols) + list(nulls) + [valid])
    ncols = len(cols)
    s_cols, s_nulls, s_valid = (s_rest[:ncols], s_rest[ncols:2 * ncols],
                                s_rest[-1])

    # position of each row within its destination bucket
    start = jnp.searchsorted(s_dest, jnp.arange(num_partitions,
                                                dtype=jnp.int32))
    pos = jnp.arange(cap, dtype=jnp.int32) - start[jnp.clip(
        s_dest, 0, num_partitions - 1)]
    in_grid = s_valid & (pos < per_dest)
    overflow = jnp.sum(s_valid & ~in_grid)

    # scatter into the (num_partitions * per_dest) send grid
    slot = jnp.where(in_grid,
                     jnp.clip(s_dest, 0, num_partitions - 1) * per_dest + pos,
                     num_partitions * per_dest)  # dropped lanes -> overflow slot

    def to_grid(col):
        grid = jnp.zeros((num_partitions * per_dest + 1,), dtype=col.dtype)
        grid = grid.at[slot].set(col, mode="drop")
        return grid[:-1].reshape(num_partitions, per_dest)

    g_cols = [to_grid(c) for c in s_cols]
    g_nulls = [to_grid(n) for n in s_nulls]
    g_valid = to_grid(in_grid)

    # the collective: row i of my grid goes to device i
    def a2a(x):
        return jax.lax.all_to_all(x, axis_name, split_axis=0, concat_axis=0,
                                  tiled=True)

    r_cols = tuple(a2a(c).reshape(-1) for c in g_cols)
    r_nulls = tuple(a2a(n).reshape(-1) for n in g_nulls)
    r_valid = a2a(g_valid).reshape(-1)
    return r_cols, r_nulls, r_valid, overflow
