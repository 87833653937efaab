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
compaction, no host sync — pruned-row counts accumulate in a device
scalar read once at query end), and the value-set membership test is a
``searchsorted`` + equality over a padded sorted array, the same
XLA-native binary-search idiom the join probe uses.

Scheduling guarantee: pipelines of a task run build-before-probe (the
physical planner sequences them), so the filter is complete before the
first probe page is scanned — the engine-level analog of Trino's
"wait for dynamic filters" scan blocking.
"""

from __future__ import annotations

from typing import List, Optional, Tuple

import numpy as np

from ..block import padded_size
from ..telemetry.tracing import host_read, host_sync

#: value sets larger than this keep only min/max (reference analog:
#: dynamic-filtering.small.max-distinct-values-per-driver)
MAX_VALUE_SET = 1 << 17


class DynamicFilter:
    """Domain of one join-key column, filled at build publish."""

    def __init__(self, label: str = ""):
        self.label = label
        self.ready = False
        self.allow_nan = False     # build side had NaN float keys
        self.lo = None             # numpy scalar in the key's storage dtype
        self.hi = None
        self._values: Optional[np.ndarray] = None  # sorted unique, padded
        self._values_dev = None
        self._pruned_dev = None    # lazy device accumulator (no hot sync)
        self._seen_dev = None
        self.build_rows = 0

    # -- build side -----------------------------------------------------

    def collect(self, col, nulls, valid):
        """Collect the domain from build-side device arrays (called once
        at HashBuilder publish; one device->host transfer)."""
        import jax.numpy as jnp

        with host_sync("dynamic_filter_collect"):
            live = np.asarray(valid & ~nulls)
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
            # no (finite) build keys: range matches nothing; NaN lanes
            # still pass when the build had NaN keys
            self.lo, self.hi = np.int64(1), np.int64(0)
            self.ready = True
            return
        uniq = np.unique(vals)
        self.lo, self.hi = uniq[0], uniq[-1]
        if uniq.shape[0] <= MAX_VALUE_SET:
            cap = padded_size(int(uniq.shape[0]))
            padded = np.full(cap, uniq[-1], dtype=uniq.dtype)
            padded[:uniq.shape[0]] = uniq
            self._values = padded
            self._values_dev = jnp.asarray(padded)
        self.ready = True

    # -- probe side -----------------------------------------------------

    def apply(self, col, nulls, valid):
        """valid-mask update for one scanned page (device, no sync)."""
        import jax.numpy as jnp

        if not self.ready:
            return valid
        if self.lo > self.hi:  # no finite build keys
            keep = jnp.zeros_like(valid)
        else:
            keep = valid & ~nulls & \
                (col >= jnp.asarray(self.lo, dtype=col.dtype)) & \
                (col <= jnp.asarray(self.hi, dtype=col.dtype))
            if self._values_dev is not None:
                vs = self._values_dev.astype(col.dtype)
                idx = jnp.clip(jnp.searchsorted(vs, col), 0,
                               vs.shape[0] - 1)
                keep = keep & (vs[idx] == col)
        if self.allow_nan:
            keep = keep | (valid & ~nulls & jnp.isnan(col))
        pruned = jnp.sum((valid & ~keep).astype(jnp.int64))
        seen = jnp.sum(valid.astype(jnp.int64))
        self._pruned_dev = pruned if self._pruned_dev is None \
            else self._pruned_dev + pruned
        self._seen_dev = seen if self._seen_dev is None \
            else self._seen_dev + seen
        return keep

    def to_domain(self):
        """The collected build-side key domain as a ``predicate.Domain``
        — the engine's TupleDomain interop form (reference:
        DynamicFilterService handing TupleDomains to connector scans).
        NaN admission can't be expressed as a range and stays a device-
        side flag; the device ``apply`` path remains the enforcement."""
        from ..predicate import Domain, Range, ValueSet

        if not self.ready:
            return Domain.all_()
        if self.lo > self.hi:  # no finite build keys
            return Domain.none()
        if self._values is not None and self._values.shape[0] <= 1024:
            uniq = np.unique(self._values)
            return Domain(ValueSet.of(*(v.item() for v in uniq)), False)
        return Domain(ValueSet.of_ranges(
            Range(self.lo.item(), True, self.hi.item(), True)), False)

    # -- observability ---------------------------------------------------

    @property
    def pruned_rows(self) -> int:
        return 0 if self._pruned_dev is None else int(
            host_read(self._pruned_dev, "dynamic_filter_stats"))

    @property
    def scanned_rows(self) -> int:
        return 0 if self._seen_dev is None else int(
            host_read(self._seen_dev, "dynamic_filter_stats"))

    def stats(self) -> dict:
        return {
            "filter": self.label,
            "ready": self.ready,
            "build_rows": self.build_rows,
            "scanned_rows": self.scanned_rows,
            "pruned_rows": self.pruned_rows,
            "has_value_set": self._values is not None,
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
