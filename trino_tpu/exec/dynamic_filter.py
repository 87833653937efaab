"""Dynamic filtering: build-side key domains pruning probe-side scans.

Reference analog: ``server/DynamicFilterService.java:107,278`` +
``operator/DynamicFilterSourceOperator.java`` + the ``TupleDomain``
predicate model (``spi/predicate/``).  There, build-side values stream to
a coordinator service and reach probe scans as TupleDomains; here the
planner links the two sides directly: the join build publishes its key
domain (min/max + a sorted value set when small) into a ``DynamicFilter``
that the probe-side TableScan applies to every page BEFORE rows enter
the pipeline.

TPU-first details: the scan applies the domain as a lane-mask update (no
compaction, no host sync — pruned-row counts accumulate in device
scalars read once at query end), one jitted program a page and filter
(``jit__dynamic_filter_mask``: the range compares, the membership test, the
NaN pass-through and both counts; ``lo`` / ``hi`` are traced, so another
statement's bounds compile nothing).  The value set takes the form the
build shows it can:

* a **membership table** where the build's keys are signed integers
  (bigint, integer, date) on the device and their observed range
  ``hi - lo + 1`` fits ``TABLE_MAX_CODES``: ``table[c] = 1`` iff key
  ``lo + c`` is in the build, one byte a code, made on the device by one
  scatter; a page's test is ``in_range & table[col - lo]`` — one gather.
  ``collect`` then reads scalars only (live rows, min, max; then the
  table's distinct count) and the key column never crosses to the host;
* a **sorted set** and a binary search (``searchsorted`` + equality over
  a padded sorted array, inside the same program) for everything else:
  float keys (NaN handling below), a key range past the bound, a table
  the builder's memory context refuses, and build arrays handed over on
  the host (the spilled-partition path).  That path pulls the column and
  runs ``np.unique`` on the host, as every filter did before.

Either way a value set exists exactly when the build has at most
``MAX_VALUE_SET`` distinct keys; above that the filter is min / max only.

Scheduling guarantee: pipelines of a task run build-before-probe (the
physical planner sequences them), so the filter is complete before the
first probe page is scanned — the engine-level analog of Trino's
"wait for dynamic filters" scan blocking.
"""

from __future__ import annotations

from functools import partial
from typing import List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .. import jit_stats
from ..block import padded_size
from ..telemetry import tracing
from ..telemetry.profiler import instrument
from .memory import MemoryExceededError, NodeMemoryExceededError

#: value sets larger than this keep only min/max (reference analog:
#: dynamic-filtering.small.max-distinct-values-per-driver)
MAX_VALUE_SET = 1 << 17

#: the widest key range a membership table may cover (one byte a code,
#: padded to a power of two): 16 Mi codes — every TPC-H SF1 key,
#: ``l_orderkey``'s 6.0 M included.  A wider build keeps the sorted set.
TABLE_MAX_CODES = 1 << 24


@jax.jit
def _dynamic_filter_span(col, nulls, valid):
    """int64[3]: the live (valid, non-null) lanes' number, least and
    greatest key of a signed-integer column."""
    jit_stats.bump("dynamic_filter_span")
    live = valid & ~nulls
    info = jnp.iinfo(col.dtype)
    return jnp.stack([
        jnp.sum(live, dtype=jnp.int64),
        jnp.min(jnp.where(live, col, info.max)).astype(jnp.int64),
        jnp.max(jnp.where(live, col, info.min)).astype(jnp.int64)])


_dynamic_filter_span = instrument("dynamic_filter_span",
                                  _dynamic_filter_span)


@partial(jax.jit, static_argnames=("kp",))
def _dynamic_filter_table(col, nulls, valid, lo, kp: int):
    """(int8[kp] membership table over ``key - lo``, its number of
    distinct keys) in one scatter: dead and null lanes go past the end
    and are dropped.  ``kp`` >= the number of codes."""
    jit_stats.bump("dynamic_filter_table")
    live = valid & ~nulls
    idx = jnp.where(live, col.astype(jnp.int64) - lo, kp).astype(jnp.int32)
    table = jnp.zeros(kp, dtype=jnp.int8).at[idx].set(1, mode="drop")
    return table, jnp.sum(table, dtype=jnp.int32)


_dynamic_filter_table = instrument("dynamic_filter_table",
                                   _dynamic_filter_table,
                                   static_argnames=("kp",))


@partial(jax.jit, static_argnames=("form",))
def _dynamic_filter_mask(col, nulls, valid, lo, hi, members, allow_nan,
                         pruned, seen, form: Optional[str]):
    """One page under one filter: the new valid mask and the running
    ``pruned`` / ``seen`` counts.  ``form`` says what ``members`` is: a
    membership table over ``col - lo`` (``"table"``), a padded sorted
    array (``"sorted"``) or nothing (min / max only).  An empty build
    has ``lo`` > ``hi`` and keeps no lane but, for a float key, the NaN
    lanes ``allow_nan`` lets through."""
    jit_stats.bump("dynamic_filter_mask")
    live = valid & ~nulls
    keep = live & (col >= lo) & (col <= hi)
    if form == "table":
        # in range, ``col - lo`` < TABLE_MAX_CODES; elsewhere it may
        # wrap, and the ``where`` discards it
        code = jnp.where(keep, col - lo, 0).astype(jnp.int32)
        keep = keep & (members[code] != 0)
    elif form == "sorted":
        members = members.astype(col.dtype)
        idx = jnp.clip(jnp.searchsorted(members, col), 0,
                       members.shape[0] - 1)
        keep = keep & (members[idx] == col)
    if jnp.issubdtype(col.dtype, jnp.floating):
        keep = keep | (live & allow_nan & jnp.isnan(col))
    return (keep,
            pruned + jnp.sum(valid & ~keep, dtype=jnp.int64),
            seen + jnp.sum(valid, dtype=jnp.int64))


_dynamic_filter_mask = instrument("dynamic_filter_mask",
                                  _dynamic_filter_mask,
                                  static_argnames=("form",))


class DynamicFilter:
    """Domain of one join-key column, filled at build publish."""

    def __init__(self, label: str = ""):
        self.label = label
        self.ready = False
        self.allow_nan = False     # build side had NaN float keys
        self.lo = None             # numpy scalar in the key's storage dtype
        self.hi = None
        #: the value set's form — ``"table"``, ``"sorted"`` or None (min /
        #: max only) — and its device array: int8[kp] over ``key - lo``,
        #: or the sorted distinct keys padded with the greatest
        self.set_form: Optional[str] = None
        self._members = None
        self.n_values = 0          # distinct keys of the value set
        #: bytes of the membership table, which the builder's memory
        #: context keeps reserved (the sorted set goes unaccounted)
        self.table_bytes = 0
        #: (pruned, seen) device accumulators once a page was seen (no
        #: hot sync)
        self._counts = None
        self.build_rows = 0

    # -- build side -----------------------------------------------------

    def collect(self, col, nulls, valid, ctx=None):
        """Collect the domain from the build side's arrays (called once
        at HashBuilder publish).  Device arrays of a signed-integer key
        stay on the device: two scalar reads.  ``ctx`` is the builder's
        memory context, asked for the table's bytes."""
        if isinstance(col, jax.Array) \
                and jnp.issubdtype(col.dtype, jnp.signedinteger) \
                and self._collect_on_device(col, nulls, valid, ctx):
            return
        self._collect_on_host(col, nulls, valid)

    def _set_empty(self):
        # no (finite) build keys: the range matches nothing; NaN lanes
        # still pass when the build had NaN keys
        self.lo, self.hi = np.int64(1), np.int64(0)
        self.ready = True

    def _collect_on_device(self, col, nulls, valid, ctx) -> bool:
        """The domain without the column leaving the device; False
        where the observed range or the memory context allows no
        table (nothing is set then)."""
        n, lo, hi = (int(v) for v in tracing.host_read(
            _dynamic_filter_span(col, nulls, valid), "dynamic_filter_collect"))
        self.build_rows = n
        if n == 0:
            self._set_empty()
            return True
        if hi - lo + 1 > TABLE_MAX_CODES:
            return False
        kp = padded_size(hi - lo + 1)
        if ctx is not None:
            # an optional index, like the join's direct-address table:
            # it takes what is free and makes no operator spill for
            # it.  The zeros and the scattered table are both alive
            # while it is built
            pool = ctx.pool
            if pool.reserved + 2 * kp > pool.max_bytes:
                return False
            try:
                ctx.reserve(2 * kp, revocable=False)
            except (MemoryExceededError, NodeMemoryExceededError):
                return False
        table, count = _dynamic_filter_table(col, nulls, valid,
                                             np.int64(lo), kp=kp)
        self.lo, self.hi = col.dtype.type(lo), col.dtype.type(hi)
        self.n_values = int(tracing.host_read(count,
                                              "dynamic_filter_collect"))
        if self.n_values <= MAX_VALUE_SET:
            self.set_form, self._members, self.table_bytes = \
                "table", table, kp
        if ctx is not None:
            ctx.free(2 * kp - self.table_bytes, revocable=False)
        self.ready = True
        return True

    def _collect_on_host(self, col, nulls, valid):
        """The domain by ``np.unique`` over the live keys on the host:
        one transfer of the column if it lay on the device."""
        with tracing.host_sync("dynamic_filter_collect"):
            live = np.asarray(valid) & ~np.asarray(nulls)
            vals = np.asarray(col)[live]
        self.build_rows = int(vals.shape[0])
        if np.issubdtype(vals.dtype, np.floating):
            # NaN build keys: np.unique sorts NaN last, so hi would be
            # NaN and `col <= hi` would prune EVERYTHING.  The engine
            # treats NaN as joinable with itself (sortkeys tags NaN
            # groups), so drop NaNs from the domain and pass NaN probe
            # lanes through.
            nan_mask = np.isnan(vals)
            self.allow_nan = bool(nan_mask.any())
            vals = vals[~nan_mask]
        if vals.shape[0] == 0:
            self._set_empty()
            return
        uniq = np.unique(vals)
        self.lo, self.hi = uniq[0], uniq[-1]
        self.n_values = int(uniq.shape[0])
        if self.n_values <= MAX_VALUE_SET:
            padded = np.full(padded_size(self.n_values), uniq[-1],
                             dtype=uniq.dtype)
            padded[:self.n_values] = uniq
            self.set_form, self._members = "sorted", jnp.asarray(padded)
        self.ready = True

    # -- probe side -----------------------------------------------------

    def apply(self, col, nulls, valid):
        """valid-mask update for one scanned page (device, no sync):
        one program."""
        if not self.ready:
            return valid
        kind = col.dtype.type
        pruned, seen = self._counts or (np.int64(0), np.int64(0))
        keep, pruned, seen = _dynamic_filter_mask(
            col, nulls, valid, kind(self.lo), kind(self.hi),
            self._members, np.bool_(self.allow_nan), pruned, seen,
            form=self.set_form)
        self._counts = (pruned, seen)
        return keep

    def to_domain(self):
        """The collected build-side key domain as a ``predicate.Domain``
        — the engine's TupleDomain interop form (reference:
        DynamicFilterService handing TupleDomains to connector scans).
        NaN admission can't be expressed as a range and stays a device-
        side flag; the device ``apply`` path remains the enforcement.
        A small value set is listed — read back from the device here,
        the only place that asks for it."""
        from ..predicate import Domain, Range, ValueSet

        if not self.ready:
            return Domain.all_()
        if self.lo > self.hi:  # no finite build keys
            return Domain.none()
        if self.set_form is not None \
                and padded_size(self.n_values) <= 1024:
            members = np.asarray(self._members)
            uniq = np.unique(members) if self.set_form == "sorted" \
                else self.lo.item() + np.flatnonzero(members)
            return Domain(ValueSet.of(*(v.item() for v in uniq)), False)
        return Domain(ValueSet.of_ranges(
            Range(self.lo.item(), True, self.hi.item(), True)), False)

    # -- observability ---------------------------------------------------

    @property
    def pruned_rows(self) -> int:
        return 0 if self._counts is None else int(tracing.host_read(
            self._counts[0], "dynamic_filter_stats"))

    @property
    def scanned_rows(self) -> int:
        return 0 if self._counts is None else int(tracing.host_read(
            self._counts[1], "dynamic_filter_stats"))

    def stats(self) -> dict:
        return {
            "filter": self.label,
            "ready": self.ready,
            "build_rows": self.build_rows,
            "scanned_rows": self.scanned_rows,
            "pruned_rows": self.pruned_rows,
            "has_value_set": self.set_form is not None,
            "set": self.set_form,
        }


def resolve_scan_column(node, symbol_name: str):
    """Walk a probe-side plan subtree to the TableScan column feeding
    ``symbol_name``, through renaming projections, filters, limits, and
    probe sides of nested joins (reference analog: the source-symbol
    walk in ``DynamicFilterService.getSourceSymbol``).  Returns
    ``(scan_node, channel)`` or None when the symbol is computed or
    crosses a pipeline boundary (union, aggregation, remote source)."""
    from ..planner.plan import (CrossJoinNode, FilterNode, JoinNode,
                                ProjectNode, SortNode, TableScanNode)
    from ..planner.symbols import SymbolRef

    name = symbol_name
    while True:
        if isinstance(node, TableScanNode):
            for pos, (s, _c) in enumerate(node.assignments):
                if s.name == name:
                    return node, pos
            return None
        # NOTE: Limit/TopN are NOT transparent — pruning below a LIMIT
        # changes which rows it selects.  Sort alone is row-preserving.
        if isinstance(node, (FilterNode, SortNode)):
            node = node.source
            continue
        if isinstance(node, ProjectNode):
            expr = None
            for s, e in node.assignments:
                if s.name == name:
                    expr = e
                    break
            if not isinstance(expr, SymbolRef):
                return None
            name = expr.name
            node = node.source
            continue
        if isinstance(node, (JoinNode, CrossJoinNode)):
            # probe-side symbols pass through the join unchanged; build
            # symbols won't resolve below and fall out as None
            node = node.left
            continue
        return None


def plan_dynamic_filters(planner, left_node, criteria, join_type: str
                         ) -> List[Tuple[object, DynamicFilter]]:
    """Register a DynamicFilter per eligible equi-clause: returns
    [(build_symbol, filter)] and records the probe-scan attachment in
    ``planner._scan_dfs``.  Inner and semi joins only: LEFT/ANTI probes
    must keep unmatched rows."""
    out: List[Tuple[object, DynamicFilter]] = []
    if join_type not in ("inner", "semi") or not criteria:
        return out
    for lsym, rsym in criteria:
        if lsym.type.is_string or rsym.type.is_string:
            continue  # string keys join via dictionary codes; pools differ
        target = resolve_scan_column(left_node, lsym.name)
        if target is None:
            continue
        scan_node, pos = target
        df = DynamicFilter(label=f"{lsym.name}<-{rsym.name}")
        planner._scan_dfs.setdefault(id(scan_node), []).append((pos, df))
        planner.dynamic_filters.append(df)
        out.append((rsym, df))
    return out
