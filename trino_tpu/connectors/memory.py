"""In-memory table connector: tables that live on the chip.

Reference analog: ``plugin/trino-memory`` (``MemoryConnector.java``,
``MemoryMetadata``, ``MemoryPagesStore``) — "stores all data and
metadata in RAM on workers", bounded by ``memory.max-data-per-node``.
For an engine whose workers are TPU chips, RAM on workers is HBM: a
table's pages are kept as ``DevicePage``s on the device of the task
that wrote them, cut once at write time to ``page_rows`` lanes, string
columns as codes into the table's one dictionary (which stays on the
host, as everywhere in the engine).  A scan takes the pages as they
lie (``ResidentPageSource``): no host page, no concat, no upload.

A table written by the tasks of several workers lies on several
devices, each writer's pages on its own (reference: every worker keeps
what its writer tasks wrote).  Its splits then cover the pages of one
device each and name it (``ConnectorSplit.device``: the reference's
``MemorySplit`` carries its worker's address and is not remotely
accessible), and the scheduler gives a split to the task on that
device, so a scan reads only what lies where it runs.  A reader with
no task there (a ``LocalQueryRunner`` over a table four workers wrote,
a cluster of fewer workers than devices hold pages) still reads every
page: it gets a device-to-device copy, never a host round trip, and
the scan counts its bytes as ``transferred_bytes`` beside
``local_bytes``.

The device bytes of every table are reserved in the connector's
``TableMemoryAccount`` (``exec/memory.py``) by table and by device when
written, stay reserved after the writing query ends and fall on ``DROP
TABLE``; a write that takes one device's share past
``max_data_per_node`` fails and leaves no half table on any device.
Nothing spills, is evicted or falls back to host pages.  Writes append
under a lock so scaled/parallel writers can share one sink target.
"""

from __future__ import annotations

import threading
import time
from functools import lru_cache
from types import SimpleNamespace
from typing import Dict, List, Optional, Sequence, Tuple, Union

import numpy as np

from ..block import Block, DevicePage, Dictionary, Page, padded_size
from ..types import TrinoError
from .spi import (ColumnHandle, Connector, ConnectorMetadata,
                  ConnectorPageSink, ConnectorPageSource,
                  ConnectorSplit, ConnectorSplitManager, ResidentPage,
                  TableHandle, TableStatistics)

#: lanes of a stored page: what a scan of the generator's ``lineitem``
#: hands its pipeline (65,536 orders a connector page, four lines an
#: order), so programs over resident pages are shaped like today's
PAGE_ROWS = 1 << 18


def _device_id(device) -> Optional[int]:
    """A jax device's id: what a split's address and the account's
    shares are keyed by."""
    return getattr(device, "id", None)


@lru_cache(maxsize=None)
def _kernels():
    """The store's device programs; all run at write time only."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    @jax.jit
    def live_prefix(valid):
        """(live lanes, whether they are a dense prefix)."""
        n = valid.sum(dtype=jnp.int32)
        dense = (valid == (jnp.arange(valid.shape[0]) < n)).all()
        return jnp.stack([n, dense.astype(jnp.int32)])

    @jax.jit
    def compact(arrays, valid):
        """Live lanes gathered to a dense prefix, in order."""
        idx = jnp.nonzero(valid, size=valid.shape[0], fill_value=0)[0]
        return tuple(a[idx] for a in arrays)

    @jax.jit
    def stage_in(stage, pieces, fill):
        return tuple(lax.dynamic_update_slice(s, p, (fill,))
                     for s, p in zip(stage, pieces))

    def cut(stage, fill, cap):
        """The first ``cap`` staged lanes as a page of ``fill`` live
        rows: (columns, null masks, valid, which columns hold a NULL)."""
        half = len(stage) // 2
        valid = jnp.arange(cap) < fill
        cols = tuple(s[:cap] for s in stage[:half])
        nulls = tuple(s[:cap] & valid for s in stage[half:])
        has_null = jnp.stack([n.any() for n in nulls]) if nulls \
            else jnp.zeros(0, bool)
        return cols, nulls, valid, has_null

    @jax.jit
    def shift(stage):
        """Drop the first half of every staging buffer."""
        return tuple(jnp.concatenate([s[s.shape[0] // 2:],
                                      jnp.zeros(s.shape[0] // 2, s.dtype)])
                     for s in stage)

    return SimpleNamespace(live_prefix=live_prefix, compact=compact,
                           stage_in=stage_in, shift=shift,
                           cut=jax.jit(cut, static_argnums=2))


class _TableData:
    """One table: columns, the table-wide dictionaries of its string
    columns, and its pages.  ``pages`` holds ``ResidentPage``s; a host
    ``Page`` put there from outside (a replica's sync, a test) is taken
    onto the device the next time the table is read."""

    def __init__(self, columns: List[ColumnHandle],
                 conn: "MemoryConnector", key: Tuple[str, str]):
        self.columns = columns
        self.conn = conn
        self.key = key      # (schema, table): the account's key
        self._pages: List[Union[ResidentPage, Page]] = []
        self.lock = threading.RLock()
        # canonical per-column pools: appended pages re-encode into these
        # so scans present stable code spaces (group-by/join correctness)
        self.dicts = [Dictionary() if c.type.is_string else None
                      for c in columns]
        #: per string column, for the source dictionary it last met:
        #: (uid, codes of its values in this table's pool, whether that
        #: mapping is the identity) — grown with the source, so a load
        #: from one pool re-codes each value once
        self._remaps: Dict[int, tuple] = {}
        #: all-False / all-True masks shared by the pages of a capacity
        #: on a device
        self._masks: Dict[Tuple[bool, int, object], object] = {}
        #: DROP TABLE took the table away: a sink that still writes
        #: into it fails instead of reserving bytes nothing releases —
        #: with ``write_failure``, the error a sibling sink of the same
        #: write met last, where that is why the table went (a CTAS
        #: taken back): the statement's tasks then all fail alike
        self.dropped = False
        self.write_failure: Optional[TrinoError] = None

    # -- the page list ---------------------------------------------------

    @property
    def pages(self) -> List[Union[ResidentPage, Page]]:
        return self._pages

    @pages.setter
    def pages(self, new):
        new = list(new)
        with self.lock:
            kept = {id(p) for p in new}
            self._release(p for p in self._pages if id(p) not in kept)
            self._pages = new

    def _release(self, pages):
        for p in pages:
            if isinstance(p, ResidentPage) and p.nbytes:
                self.conn.account.release(self.key, p.nbytes,
                                          _device_id(p.device))

    @property
    def row_count(self) -> int:
        return sum(p.rows if isinstance(p, ResidentPage) else p.num_rows
                   for p in self._pages)

    def resident(self) -> List[ResidentPage]:
        """The table's pages, every one on the device."""
        with self.lock:
            for i, p in enumerate(self._pages):
                if not isinstance(p, ResidentPage):
                    self._pages[i] = self.adopt(p)
            return list(self._pages)

    def by_device(self) -> Dict[Optional[int], List[ResidentPage]]:
        """The table's pages by the id of the device they lie on, each
        device's in the order they were stored."""
        groups: Dict[Optional[int], List[ResidentPage]] = {}
        for p in self.resident():
            groups.setdefault(_device_id(p.device), []).append(p)
        return groups

    def host_pages(self) -> List[Page]:
        """Host copies of the pages (replication to other processes)."""
        with self.lock:
            return [p.to_page() if isinstance(p, ResidentPage) else p
                    for p in self._pages]

    def replace(self, pages: Sequence[Page]):
        """The table's content becomes ``pages`` (host pages: DELETE)."""
        with self.lock:
            self.pages = []
            self._pages = [self.adopt(p) for p in pages if p.num_rows]

    def adopt(self, page: Page) -> ResidentPage:
        """A host page, re-coded and put on the device as it is."""
        page = self.canonicalize(page)
        dp = DevicePage.from_page(page)
        has_null = [b.nulls is not None and bool(np.any(b.nulls))
                    for b in page.blocks]
        return self.stored(dp.cols, dp.nulls, dp.valid, page.num_rows,
                           has_null)

    # -- string columns ----------------------------------------------------

    def recode(self, i: int, source: Optional[Dictionary]
               ) -> Optional[np.ndarray]:
        """Codes of ``source``'s values in column ``i``'s table-wide
        pool, by source code; None where the codes need no change."""
        d = self.dicts[i]
        if d is None or source is None or source is d:
            return None
        with self.lock:
            uid, remap, identity = self._remaps.get(i, (None, None, True))
            if uid != source.uid:
                remap, identity = np.empty(0, np.int32), True
            done = len(remap)
            if done < len(source):
                more = d.encode(source.values[done:len(source)])
                identity = identity and bool(np.array_equal(
                    more, np.arange(done, done + len(more))))
                remap = np.concatenate([remap, more])
            self._remaps[i] = (source.uid, remap, identity)
            return None if identity else remap

    def canonicalize(self, page: Page) -> Page:
        blocks = []
        for i, c in enumerate(self.columns):
            b = page.block(i).numpy()
            remap = self.recode(i, b.dictionary)
            if self.dicts[i] is not None and \
                    b.dictionary is not self.dicts[i]:
                data = b.data if remap is None else remap[b.data]
                blocks.append(Block(c.type, data, b.nulls, self.dicts[i]))
            else:
                blocks.append(b)
        return Page(blocks, page.num_rows)

    # -- making a stored page -------------------------------------------------

    def _mask(self, value: bool, cap: int, device):
        """The table's shared all-``value`` mask of ``cap`` lanes on
        ``device``: a page's arrays all lie where its columns do."""
        import jax
        import jax.numpy as jnp

        m = self._masks.get((value, cap, device))
        if m is None:
            self.conn.account.reserve(self.key, cap, _device_id(device))
            with jax.default_device(device):
                m = self._masks[(value, cap, device)] = \
                    jnp.full(cap, value, bool)
        return m

    def stored(self, cols, nulls, valid, rows: int,
               has_null: Sequence[bool]) -> ResidentPage:
        """A ``ResidentPage`` of these arrays, its bytes reserved.  Columns
        without a NULL share the table's all-False mask and a full page
        the all-True one, so a page holds little but its columns."""
        cap = int(valid.shape[0])
        own = cap * sum(c.dtype.itemsize for c in cols) \
            + cap * sum(1 for h in has_null if h) \
            + (cap if rows < cap else 0)
        device = next(iter((cols[0] if cols else valid).devices()))
        with self.lock:
            if self.dropped:
                raise self.write_failure or TrinoError(
                    f"Table '{'.'.join(self.key)}' was dropped while "
                    "it was being written", "TABLE_NOT_FOUND")
            nulls = [n if h else self._mask(False, cap, device)
                     for n, h in zip(nulls, has_null)]
            if rows == cap:
                valid = self._mask(True, cap, device)
            self.conn.account.reserve(self.key, own, _device_id(device))
        return ResidentPage([c.type for c in self.columns], list(cols),
                            list(nulls), valid, list(self.dicts),
                            rows=rows, nbytes=own, device=device)


class MemoryMetadata(ConnectorMetadata):
    def __init__(self, conn: "MemoryConnector"):
        self.conn = conn

    def list_schemas(self) -> List[str]:
        return sorted(self.conn.schemas)

    def list_tables(self, schema: str) -> List[str]:
        return sorted(t for (s, t) in self.conn.tables if s == schema)

    def get_table_handle(self, schema, table) -> Optional[TableHandle]:
        if (schema, table) in self.conn.tables:
            return TableHandle(self.conn.catalog_name, schema, table)
        return None

    # apply_filter: the SPI's default — declined.  The rows are on the
    # device; the plan keeps its Filter, which fuses the predicate into
    # the program that reads the page anyway (no launch of its own, no
    # host pass over stored rows).

    def get_columns(self, table: TableHandle) -> List[ColumnHandle]:
        return self.conn.tables[(table.schema, table.table)].columns

    def get_statistics(self, table: TableHandle) -> TableStatistics:
        data = self.conn.tables[(table.schema, table.table)]
        return TableStatistics(row_count=float(data.row_count))

    def create_table(self, schema: str, table: str,
                     columns: List[ColumnHandle]) -> TableHandle:
        with self.conn.lock:
            if (schema, table) in self.conn.tables:
                raise TrinoError(f"Table '{schema}.{table}' already exists",
                                 "TABLE_ALREADY_EXISTS")
            self.conn.tables[(schema, table)] = _TableData(
                list(columns), self.conn, (schema, table))
            self.conn.schemas.add(schema)
            self.conn._version += 1      # DDL invalidates cached plans
        return TableHandle(self.conn.catalog_name, schema, table)

    def drop_table(self, table: TableHandle):
        with self.conn.lock:
            data = self.conn.tables.pop((table.schema, table.table), None)
            if data is not None:
                with data.lock:     # no page is stored past this point
                    data.dropped = True
                    self.conn.account.release(data.key)
            self.conn._version += 1      # DDL invalidates cached plans


class MemorySplitManager(ConnectorSplitManager):
    def __init__(self, conn: "MemoryConnector"):
        self.conn = conn

    def get_splits(self, table: TableHandle,
                   desired_splits: int) -> List[ConnectorSplit]:
        """A table on one device: ``desired_splits`` strides over its
        pages, readable from anywhere.  A table spread over several:
        each device's share of the splits strides over that device's
        pages and carries its address."""
        data = self.conn.tables[(table.schema, table.table)]
        groups = data.by_device()
        if len(groups) <= 1:
            groups = {None: data.pages}
        share = max(1, desired_splits // len(groups))
        cuts = []
        for device, pages in sorted(groups.items(),
                                    key=lambda g: -1 if g[0] is None
                                    else g[0]):    # not by who wrote first
            k = max(1, min(share, len(pages)))
            cuts += [(device, i, k, len(pages)) for i in range(k)]
        return [ConnectorSplit(table, split_id, len(cuts), i, n,
                               info={"stride": k}, device=device)
                for split_id, (device, i, k, n) in enumerate(cuts)]


class ResidentPageSource(ConnectorPageSource):
    """A split's pages as they lie on the device.  Column selection
    picks arrays (no copy); a reader that runs on another device (the
    one its thread is pinned to by ``jax.default_device``, else the
    process's first) gets the page copied there, marked
    ``transferred``."""

    provides_device_pages = True

    def __init__(self, pages: Sequence[ResidentPage],
                 ordinals: Sequence[int]):
        self._pages = iter(pages)
        self._ordinals = list(ordinals)
        self._done = False

    def get_next_device_page(self) -> Optional[ResidentPage]:
        p = next(self._pages, None)
        if p is None:
            self._done = True
            return None
        import jax

        from ..exec.memory import device_page_bytes

        o = self._ordinals
        page = ResidentPage([p.types[i] for i in o], [p.cols[i] for i in o],
                            [p.nulls[i] for i in o], p.valid,
                            [p.dictionaries[i] for i in o],
                            rows=p.rows, device=p.device)
        here = jax.config.jax_default_device or jax.local_devices()[0]
        if p.device is not None and here != p.device:
            page.cols, page.nulls, page.valid = jax.device_put(
                (page.cols, page.nulls, page.valid), here)
            page.device, page.transferred = here, True
        page.nbytes = device_page_bytes(page)
        return page

    def get_next_page(self) -> Optional[Page]:
        """A host copy, for a caller that asks for one."""
        page = self.get_next_device_page()
        return None if page is None else page.to_page()

    def is_finished(self) -> bool:
        return self._done


class MemoryPageSink(ConnectorPageSink):
    """Writes pages into a table, on the device: live lanes are staged
    in arrival order and cut into pages of ``page_rows`` lanes; only the
    re-coding of string columns into the table's pool runs on the host.
    A write of a single small page is stored as it came."""

    accepts_device_pages = True

    def __init__(self, data: _TableData, conn: "MemoryConnector"):
        self.data = data
        self.conn = conn
        data.write_failure = None       # a new write
        self.rows = 0
        self.host_recode_s = 0.0
        self._written: List[ResidentPage] = []
        self._held = None       # the first piece, until a second comes
        self._stage = None      # 2 * page_rows lanes a column and mask
        self._fill = 0

    def append_page(self, page: Page):
        self.append_device_page(DevicePage.from_page(page))

    def append_device_page(self, page: DevicePage) -> int:
        from ..telemetry.tracing import host_read

        n, dense = (int(v) for v in host_read(
            _kernels().live_prefix(page.valid), "table_write"))
        if n == 0:
            return 0
        cols = self._recoded(page)
        arrays = tuple(cols) + tuple(page.nulls)
        if not dense:
            arrays = _kernels().compact(arrays, page.valid)
        if self._stage is None and self._held is None \
                and n < self.conn.page_rows:
            self._held = (arrays, n)
        else:
            self._stage_piece(arrays, n)
        self.rows += n
        return n

    def _recoded(self, page: DevicePage) -> list:
        """The page's columns, string codes moved into the table's pools
        (the mapping is made on the host, applied on the device)."""
        import jax.numpy as jnp

        t0 = time.perf_counter()
        cols = list(page.cols)
        for i, source in enumerate(page.dictionaries):
            remap = self.data.recode(i, source)
            if remap is not None and len(remap):
                table = np.zeros(padded_size(len(remap)), np.int32)
                table[:len(remap)] = remap
                cols[i] = jnp.take(jnp.asarray(table), cols[i],
                                   mode="clip")
        self.host_recode_s += time.perf_counter() - t0
        return cols

    def _stage_piece(self, arrays, n: int):
        import jax.numpy as jnp

        rows = self.conn.page_rows
        if self._stage is None:
            self._stage = tuple(jnp.zeros(2 * rows, a.dtype)
                                for a in arrays)
            held, self._held = self._held, None
            if held is not None:
                self._stage_piece(*held)
        cap = int(arrays[0].shape[0])
        for lo in range(0, n, rows):
            piece = arrays if cap <= rows else \
                tuple(a[lo:lo + rows] for a in arrays)
            self._stage = _kernels().stage_in(self._stage, piece,
                                              np.int32(self._fill))
            self._fill += min(n - lo, rows)
            if self._fill >= rows:
                self._cut(self._stage, rows, rows)
                self._stage = _kernels().shift(self._stage)
                self._fill -= rows

    def _cut(self, arrays, fill: int, cap: int):
        """Store the first ``cap`` lanes of ``arrays`` (columns, then
        their null masks), ``fill`` of them live, as a page."""
        from ..telemetry.tracing import host_read

        cols, nulls, valid, has_null = _kernels().cut(
            arrays, np.int32(fill), cap)
        self._store(cols, nulls, valid, fill,
                    host_read(has_null, "table_write"))

    def _store(self, cols, nulls, valid, rows, has_null):
        try:
            page = self.data.stored(cols, nulls, valid, rows, has_null)
        except TrinoError as e:
            self.data.write_failure = e
            raise
        with self.data.lock:
            self.data.pages.append(page)
        self._written.append(page)
        # bump per page, not only at finish: a cached read overlapping a
        # half-complete write must already see a moved snapshot version
        self.conn.bump_version()

    def finish(self) -> dict:
        if self._held is not None:
            (arrays, n), self._held = self._held, None
            self._cut(arrays, n, min(padded_size(n),
                                     int(arrays[0].shape[0])))
        elif self._fill:
            self._cut(self._stage, self._fill, padded_size(self._fill))
        self._stage, self._fill = None, 0
        return {"rows": self.rows, "pages": len(self._written),
                "device_bytes": sum(p.nbytes for p in self._written),
                "host_recode_s": self.host_recode_s}

    def abort(self):
        """Take back what this sink wrote (a failed write leaves no
        half table)."""
        with self.data.lock:
            mine = {id(p) for p in self._written}
            self.data.pages = [p for p in self.data.pages
                               if id(p) not in mine]
        self._written, self._held, self._stage = [], None, None
        self._fill = 0
        self.conn.bump_version()


class MemoryConnector(Connector):
    name = "memory"

    def __init__(self, catalog_name: str = "memory",
                 schemas: Sequence[str] = ("default",),
                 max_data_per_node: Optional[int] = None):
        from ..exec.memory import TableMemoryAccount

        self.catalog_name = catalog_name
        self.schemas = set(schemas)
        self.tables: Dict[Tuple[str, str], _TableData] = {}
        self.lock = threading.Lock()
        self._version = 0
        #: lanes of a stored page (a power of two; no setting: tests set
        #: the attribute to cut small tables into several pages)
        self.page_rows = PAGE_ROWS
        #: Trino's ``memory.max-data-per-node``: the most device bytes
        #: this connector's tables may hold; None is the node's memory
        #: less what a query may take (``query_max_memory_bytes``)
        self.account = TableMemoryAccount(max_data_per_node)

    def data_version(self) -> int:
        """Snapshot version for the plan/result caches: every DDL and
        every written page bumps it, so dependent cache entries miss."""
        return self._version

    def bump_version(self):
        with self.lock:
            self._version += 1

    def resident_bytes_by_table(self) -> Dict[str, int]:
        """Device bytes held, by ``schema.table``."""
        return {f"{s}.{t}": n
                for (s, t), n in self.account.by_table().items()}

    def metadata(self) -> ConnectorMetadata:
        return MemoryMetadata(self)

    def split_manager(self) -> ConnectorSplitManager:
        return MemorySplitManager(self)

    def page_source(self, split: ConnectorSplit,
                    columns: Sequence[ColumnHandle]) -> ConnectorPageSource:
        if split.table.constraint is not None:
            # apply_filter declines, so no handle of this connector
            # carries a constraint; one that does (a subclass accepted
            # it) must not be answered with rows it rejects
            raise TrinoError(
                f"{split.table.qualified_name}: the memory connector "
                "enforces no pushed-down constraint", "NOT_SUPPORTED")
        data = self.tables[(split.table.schema, split.table.table)]
        stride = (split.info or {}).get("stride", 1)
        pages = data.resident() if split.device is None else \
            data.by_device().get(split.device, [])
        return ResidentPageSource(pages[split.row_start::stride],
                                  [c.ordinal for c in columns])

    def page_sink(self, table: TableHandle,
                  columns: Sequence[ColumnHandle]) -> ConnectorPageSink:
        return MemoryPageSink(self.tables[(table.schema, table.table)],
                              self)
