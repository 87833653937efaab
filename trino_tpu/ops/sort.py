"""Sort + TopN operators.

Reference analog: ``operator/OrderByOperator.java`` (PagesIndex + compiled
PagesIndexOrdering) and ``operator/TopNOperator.java``.

TPU redesign: ordering keys normalize to (null-bit, u64) operand pairs
(ops/sortkeys.py) and the whole batch sorts in one ``lax.sort`` carrying
all payload columns. TopN keeps a running device-resident top-N: each
incoming page concatenates with the current candidates, sorts, truncates —
memory stays O(N + page).
"""

from __future__ import annotations

from functools import partial
from typing import List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from .. import types as T
from ..block import DevicePage, padded_size
from ..telemetry.profiler import instrument
from .operator import Operator
from .sortkeys import SortKey, sort_carrying, sort_operands


@partial(jax.jit, static_argnames=("num_key_ops",))
def _sorted_by(key_ops, cols, nulls, valid, num_key_ops: int):
    """Sort carrying all columns; invalid lanes last."""
    from .. import jit_stats

    jit_stats.bump("sort_by")
    _, s = sort_carrying(
        [(~valid).astype(jnp.uint8)] + list(key_ops),
        list(cols) + list(nulls) + [valid], is_stable=True)
    n = len(cols)
    return tuple(s[:n]), tuple(s[n:2 * n]), s[-1]


# profiled entry point (telemetry.profiler): cost/compile attribution
# under EXPLAIN ANALYZE VERBOSE; a plain call when profiling is off
_sorted_by = instrument("sort_by", _sorted_by,
                        static_argnames=("num_key_ops",))


def _make_key_ops(page: DevicePage, keys: Sequence[SortKey]):
    ops = []
    for k in keys:
        ops.extend(sort_operands(
            page.cols[k.channel], page.nulls[k.channel],
            page.types[k.channel], page.dictionaries[k.channel],
            ascending=k.ascending,
            nulls_last=k.nulls_last if k.nulls_last is not None
            else k.ascending))
    return tuple(ops)


def _concat_pages(pages: List[DevicePage], cap: int) -> DevicePage:
    from ..block import unify_dictionaries

    types = pages[0].types
    dicts = unify_dictionaries(pages, len(types))
    cols, nulls = [], []
    for i in range(len(types)):
        cols.append(_pad(jnp.concatenate([p.cols[i] for p in pages]), cap))
        nulls.append(_pad(jnp.concatenate([p.nulls[i] for p in pages]), cap,
                          fill=True))
    valid = _pad(jnp.concatenate([p.valid for p in pages]), cap)
    return DevicePage(types, cols, nulls, valid, dicts)


def _pad(arr, cap, fill=False):
    n = arr.shape[0]
    if n == cap:
        return arr
    if arr.dtype == bool:
        pad = jnp.full((cap - n,), fill, dtype=bool)
    else:
        pad = jnp.zeros((cap - n,), dtype=arr.dtype)
    return jnp.concatenate([arr, pad])


class OrderByOperator(Operator):
    """Full sort at finish (reference: OrderByOperator.java)."""

    def __init__(self, input_types: Sequence[T.Type],
                 sort_keys: Sequence[SortKey], memory_context=None):
        self.input_types = list(input_types)
        self.sort_keys = list(sort_keys)
        self._pages: List = []  # DevicePage | SpilledPage
        self._out: List[DevicePage] = []
        self._emitted = False
        self._done = False
        self._ctx = memory_context
        if self._ctx is not None:
            self._ctx.set_revoke_callback(self._revoke)

    def add_input(self, page: DevicePage):
        if self._ctx is None:
            self._pages.append(page)
            return
        from ..exec.memory import reserve_and_append

        reserve_and_append(self._ctx, self._pages, page)

    def _revoke(self) -> int:
        from ..exec.memory import spill_pages

        return spill_pages(self._pages, self._ctx.pool, self._ctx.lock)

    def _pop_out(self) -> DevicePage:
        item = self._out.pop(0)
        # host-sorted chunks upload lazily, one per quantum, so the
        # full sorted relation is never device-resident at once
        return item() if callable(item) else item

    def get_output(self) -> Optional[DevicePage]:
        if self._out:
            return self._pop_out()
        if not self._finishing or self._emitted:
            if self._emitted:
                self._done = True
            return None
        self._emitted = True
        if not self._pages:
            self._done = True
            return None
        self._out = self._sort_all()
        self._pages = []
        if self._ctx is not None:
            self._ctx.close()
        if self._out:
            return self._pop_out()
        self._done = True
        return None

    def _sort_all(self) -> List[DevicePage]:
        from ..exec.memory import SpilledPage, device_page_bytes

        if self._ctx is not None:
            from ..exec.memory import prepare_finish

            pool = self._ctx.pool
            total, uploads = prepare_finish(self._ctx, self._pages)
            if pool.reserved + uploads + 2 * total > pool.max_bytes:
                # the whole-input device sort cannot fit alongside the
                # pool's other reservations: host-merge path
                return self._host_sort(pool.max_bytes // 4)
            # transient: uploads + concat + sorted copy; released when
            # the sorted pages flow downstream
            self._ctx.reserve(uploads + 2 * total, revocable=False)
        self._pages = [p.to_device() if isinstance(p, SpilledPage) else p
                       for p in self._pages]
        cap = padded_size(sum(p.capacity for p in self._pages))
        page = _concat_pages(self._pages, cap)
        key_ops = _make_key_ops(page, self.sort_keys)
        cols, nulls, valid = _sorted_by(key_ops, tuple(page.cols),
                                        tuple(page.nulls), page.valid,
                                        num_key_ops=len(key_ops))
        return [DevicePage(page.types, list(cols), list(nulls), valid,
                           page.dictionaries)]

    def _host_sort(self, chunk_budget: int) -> List[DevicePage]:
        """Bounded-HBM sort: per page, compute the order-encoding key
        operands on device (a small per-page kernel), download the live
        rows, then lexsort on host and re-emit the ordered rows as
        budget-sized DevicePages.  Device residency is one page + one
        output chunk; the full relation lives in host RAM — the same
        spill domain the revoke path uses (reference analog:
        OrderByOperator's spill-merge via FileSingleStreamSpiller,
        with host RAM standing in for disk)."""
        from ..exec.memory import SpilledPage, device_page_bytes

        from ..block import unify_dictionaries

        host_cols: List[List[np.ndarray]] = []
        host_nulls: List[List[np.ndarray]] = []
        host_ops: List[List[np.ndarray]] = []
        dicts = unify_dictionaries(self._pages, len(self.input_types))
        for p in self._pages:
            nb = device_page_bytes(p)
            if self._ctx is not None:
                # one page resident at a time (plus its key operands)
                self._ctx.reserve(2 * nb, revocable=False)
            dev = p.to_device() if isinstance(p, SpilledPage) else p
            ops = _make_key_ops(dev, self.sort_keys)
            keep = np.nonzero(np.asarray(dev.valid))[0]
            host_cols.append([np.asarray(c)[keep] for c in dev.cols])
            host_nulls.append([np.asarray(n)[keep] for n in dev.nulls])
            host_ops.append([np.asarray(o)[keep] for o in ops])
            if self._ctx is not None:
                self._ctx.free(2 * nb)
        nch = len(self.input_types)
        cols = [np.concatenate([pc[i] for pc in host_cols])
                for i in range(nch)]
        nulls = [np.concatenate([pn[i] for pn in host_nulls])
                 for i in range(nch)]
        nops = len(host_ops[0])
        ops = [np.concatenate([po[j] for po in host_ops])
               for j in range(nops)]
        # np.lexsort: LAST key is primary -> reverse the operand order
        order = np.lexsort(tuple(reversed(ops))) if ops else np.arange(0)
        n = order.shape[0]
        # output chunk rows sized so a chunk stays within the budget
        row_bytes = max(1, sum(c.dtype.itemsize + 1 for c in cols) + 1)
        chunk_rows = max(1024, chunk_budget // (2 * row_bytes))
        out: List = []
        types_ = list(self.input_types)

        def make_chunk(idx):
            # deferred: uploads when the driver pulls this chunk, so one
            # chunk is device-resident at a time
            def thunk():
                k = idx.shape[0]
                cap = padded_size(k)
                ccols, cnulls = [], []
                for c, nl in zip(cols, nulls):
                    cc = np.zeros(cap, dtype=c.dtype)
                    cc[:k] = c[idx]
                    nn = np.zeros(cap, dtype=bool)
                    nn[:k] = nl[idx]
                    ccols.append(jnp.asarray(cc))
                    cnulls.append(jnp.asarray(nn))
                v = np.zeros(cap, dtype=bool)
                v[:k] = True
                return DevicePage(types_, ccols, cnulls, jnp.asarray(v),
                                  list(dicts))

            return thunk

        for s in range(0, n, chunk_rows):
            out.append(make_chunk(order[s:s + chunk_rows]))
        return out

    def is_finished(self) -> bool:
        return self._done


class TopNOperator(Operator):
    """ORDER BY ... LIMIT n with bounded memory (reference:
    TopNOperator.java / GroupedTopNBuilder)."""

    def __init__(self, input_types: Sequence[T.Type],
                 sort_keys: Sequence[SortKey], n: int):
        self.input_types = list(input_types)
        self.sort_keys = list(sort_keys)
        self.n = n
        self._top: Optional[DevicePage] = None
        self._emitted = False
        self._done = False

    def add_input(self, page: DevicePage):
        pages = [self._top, page] if self._top is not None else [page]
        cap = padded_size(sum(p.capacity for p in pages))
        merged = _concat_pages(pages, cap)
        key_ops = _make_key_ops(merged, self.sort_keys)
        cols, nulls, valid = _sorted_by(key_ops, tuple(merged.cols),
                                        tuple(merged.nulls), merged.valid,
                                        num_key_ops=len(key_ops))
        keep = padded_size(max(self.n, 16))
        if keep < cap:
            cols = tuple(c[:keep] for c in cols)
            nulls = tuple(x[:keep] for x in nulls)
            valid = valid[:keep]
        valid = valid & (jnp.arange(valid.shape[0]) < self.n)
        self._top = DevicePage(merged.types, list(cols), list(nulls), valid,
                               merged.dictionaries)

    def get_output(self) -> Optional[DevicePage]:
        if not self._finishing or self._emitted:
            return None
        self._emitted = True
        self._done = True
        return self._top

    def is_finished(self) -> bool:
        return self._done
