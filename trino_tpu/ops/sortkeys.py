"""Sortable-key normalization: any SQL value -> order-preserving operands.

The TPU-first replacement for the reference's compiled comparators
(``sql/gen/OrderingCompiler.java``, ``operator/PagesIndexOrdering``): instead
of runtime-generated compare functions over row addresses, every key column
becomes a pair of operands — (null-placement bit, order-preserving uint64) —
and multi-key ordering is ``lax.sort`` with ``num_keys=2k``: XLA's native
lexicographic sort. No sentinel tricks, so no collisions at type extremes.

Value encodings:
- signed ints / dates / timestamps / decimals: x XOR sign-bit bias
- doubles: IEEE-754 total-order trick (flip all bits for negatives,
  flip sign bit for non-negatives)
- booleans: 0/1
- strings: dictionary sort-rank (host LUT over the pool, device gather)
- DESC: bitwise complement of the value operand (null bit independent)
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from .. import types as T
from ..block import Dictionary

_SIGN64 = np.uint64(1 << 63)


def sort_carrying(keys, payloads, is_stable: bool = False):
    """Rows ordered lexicographically by ``keys`` with ``payloads``
    carried along: (sorted_keys, sorted_payloads).

    Shaped by what XLA:TPU takes to COMPILE a sort (described v5e,
    PR 22): the time grows steeply with the number and width of the
    operands — 65,536 rows: 10 s for an int32 key + one int32 payload,
    47 s with two int64 payloads, 129 s with four, over 300 s with
    nine; three u64 keys 184 s against 31 s for one. Carried columns
    and compound comparators would put minutes of compile in front of
    every join build, ORDER BY and exchange. So each sort here has ONE
    key and carries only the row index: several keys chain stable
    single-key sorts from the least significant key up (16,384 rows,
    five keys: 15 s against 69 s), and every key and payload is then
    one gather by the permutation, which compiles in milliseconds."""
    perm = jnp.arange(keys[0].shape[0], dtype=jnp.int32)
    stable = is_stable or len(keys) > 1
    for i, key in enumerate(reversed(keys)):
        _, perm = jax.lax.sort([key if i == 0 else key[perm], perm],
                               num_keys=1, is_stable=stable)
    return [k[perm] for k in keys], [p[perm] for p in payloads]


@dataclass(frozen=True)
class SortKey:
    channel: int
    ascending: bool = True
    nulls_last: bool = True  # SQL default: NULLS LAST for ASC


def _rank_lut(d: Optional[Dictionary]) -> jnp.ndarray:
    if d is None or len(d) == 0:
        return jnp.zeros(1, dtype=jnp.uint64)
    return jnp.asarray(d.sort_rank().astype(np.uint64))


def value_u64(raw, type_: T.Type, dictionary: Optional[Dictionary] = None):
    """Order-preserving uint64 encoding of raw lanes (nulls not handled).

    NOT used for DOUBLE/REAL: the TPU x64 rewriter cannot lower
    f64<->u64 bitcasts, so float keys stay float operands (lax.sort
    compares them natively); see sort_operands/group_operands.
    """
    if type_.is_pooled:
        # strings AND pooled composites (array/map/row): codes are pool
        # insertion order, so sort on the pool's value rank instead
        # (Dictionary.sort_rank totalizes tuples/None)
        return _rank_lut(dictionary)[raw]
    if type_ == T.BOOLEAN:
        return raw.astype(jnp.uint64)
    if type_ in (T.DOUBLE, T.REAL):
        raise AssertionError("float keys use native float operands")
    return raw.astype(jnp.int64).view(jnp.uint64) ^ _SIGN64


def sort_operands(raw, nulls, type_: T.Type,
                  dictionary: Optional[Dictionary] = None,
                  ascending: bool = True, nulls_last: bool = True) -> List:
    """[placement_bit_u8, key] — ascending lex order over the pair equals
    the requested SQL order. key is uint64 except for DOUBLE/REAL, which
    sort as native f64 (desc = negate; NaN sorts as +inf, i.e. largest,
    matching the engine's NaN convention)."""
    is_float = type_ in (T.DOUBLE, T.REAL)
    if is_float:
        key = jnp.asarray(raw, dtype=jnp.float64)
        key = jnp.where(jnp.isnan(key), jnp.inf, key)
        if not ascending:
            key = -key
    else:
        key = value_u64(raw, type_, dictionary)
        if not ascending:
            key = ~key
    if nulls is None:
        null_bit = jnp.zeros(raw.shape, dtype=jnp.uint8)
    else:
        bit = nulls if nulls_last else ~nulls
        null_bit = bit.astype(jnp.uint8)
        zero = 0.0 if is_float else np.uint64(0)
        key = jnp.where(nulls, zero, key)
    return [null_bit, key]


def group_operands(raw, nulls, type_: T.Type) -> List:
    """[tag_u8, key] for equality grouping: NULL is one distinct group;
    +0.0/-0.0 group together; NaNs group together (tag bit 2 marks NaN so
    float compares need no NaN-equality). Strings group by raw code —
    callers canonicalize cross-dictionary codes first."""
    if type_ in (T.DOUBLE, T.REAL):
        f = jnp.asarray(raw, dtype=jnp.float64)
        f = jnp.where(f == 0.0, 0.0, f)
        nan = jnp.isnan(f)
        key = jnp.where(nan, 0.0, f)
        tag = nan.astype(jnp.uint8) * np.uint8(2)
        if nulls is not None:
            tag = jnp.where(nulls, np.uint8(1), tag)
            key = jnp.where(nulls, 0.0, key)
        return [tag, key]
    if type_ == T.BOOLEAN:
        key = raw.astype(jnp.uint64)
    else:
        key = raw.astype(jnp.int64).view(jnp.uint64)
    if nulls is None:
        null_bit = jnp.zeros(raw.shape, dtype=jnp.uint8)
    else:
        null_bit = nulls.astype(jnp.uint8)
        key = jnp.where(nulls, np.uint64(0), key)
    return [null_bit, key]
