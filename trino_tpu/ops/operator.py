"""Operator protocol + simple relational operators.

Reference analog: ``core/trino-main/.../operator/Operator.java:21-93``
(needsInput/addInput/getOutput/finish/isBlocked) and the simple operators
(LimitOperator, ValuesOperator, TableScanOperator, ScanFilterAndProject).

Pages flowing between operators are ``DevicePage``s — padded device
batches with validity masks — so a pipeline's hot ops chain on device
without host round-trips. Host boundaries are scans (numpy -> device) and
output (device -> numpy).
"""

from __future__ import annotations

import contextlib
import queue
import threading
import time
import weakref
from typing import List, Optional, Sequence

import numpy as np

from ..block import DevicePage, Page
from ..connectors.spi import ColumnHandle, Connector, ConnectorSplit
from ..expr.compiler import PageProcessor
from ..telemetry import tracing


class Operator:
    """One stage of a pipeline (reference: operator/Operator.java)."""

    def needs_input(self) -> bool:
        return not self._finishing

    def add_input(self, page: DevicePage):
        raise NotImplementedError

    def get_output(self) -> Optional[DevicePage]:
        return None

    def finish(self):
        self._finishing = True

    def is_finished(self) -> bool:
        raise NotImplementedError

    def blocked_token(self):
        """Non-None when the operator cannot progress until an external
        event fires; the token's ``on_ready(cb)`` re-schedules the
        parked task (reference: Operator.java isBlocked returning a
        ListenableFuture)."""
        return None

    _finishing = False


class SourceOperator(Operator):
    """Pipeline head driven by splits (reference: SourceOperator.java)."""

    def add_split(self, split: ConnectorSplit):
        raise NotImplementedError

    def no_more_splits(self):
        pass

    def close(self):
        """The driver is done with this source, finished or not:
        release what outlives a call (a scan's producer thread)."""

    def add_input(self, page):
        raise AssertionError("source operators take splits, not pages")

    def needs_input(self) -> bool:
        return False


#: device pages the producer of a host scan may hold ahead of the driver
READAHEAD_PAGES = 2


def _timed(counters: Optional[dict], name: str, key: str, fn, *args):
    """``fn(*args)``; in a traced statement (``counters`` is its scan's)
    its wall is added to ``counters[key]`` and the call is the profiler
    annotation ``name`` on the calling thread."""
    if counters is None:
        return fn(*args)
    with tracing.annotation(name):
        t0 = time.perf_counter()
        try:
            return fn(*args)
        finally:
            counters[key] += time.perf_counter() - t0


class _ScanPages:
    """A scan's device pages in the order its splits were added, before
    dynamic filters: the connector's host pages generated, coalesced and
    uploaded, or a resident source's pages as they lie.  One walk,
    whichever thread calls ``next_page`` (one at a time): the driver's,
    or the producer's of a ``_ReadAhead``.  It knows its connector and
    not its operator, so an operator dropped unfinished can be collected
    and its producer stopped."""

    def __init__(self, connector: Connector,
                 columns: Sequence[ColumnHandle],
                 coalesce_rows: Optional[int]):
        self.connector = connector
        self.columns = columns
        self.coalesce_rows = coalesce_rows
        self.splits: List[ConnectorSplit] = []
        self.no_more_splits = False
        #: the walk has given its last page
        self.done = False
        #: the operator's counters in a traced statement
        self.counters: Optional[dict] = None
        self._source = None
        #: the source opened last yields host pages
        self._on_host = False
        self._buffer: List[Page] = []
        self._buffered_rows = 0
        #: one walker at a time: the driver's thread hands the walk to
        #: the producer's and takes it back in ``close()``
        self._lock = threading.Lock()

    def may_have_more_on_host(self) -> bool:
        """After a page: another may follow it, made on the host, and
        every split is known — what a producer thread is started on."""
        with self._lock:
            return self._on_host and self.no_more_splits and (
                bool(self.splits) or (self._source is not None
                                      and not self._source.is_finished()))

    def close(self):
        with self._lock:
            if self._source is not None:
                self._source.close()
                self._source = None
            self._buffer = []
            self.done = True

    def _upload(self, page: Page):
        dp = _timed(self.counters, "scan.upload", "upload_s",
                    DevicePage.from_page, page)
        if self.counters is not None:
            from ..exec.memory import device_page_bytes

            self.counters["uploaded_bytes"] += device_page_bytes(dp)
        return dp, page.num_rows

    def _flush(self):
        pages, self._buffer = self._buffer, []
        self._buffered_rows = 0
        return self._upload(pages[0] if len(pages) == 1
                            else _timed(self.counters, "scan.generate",
                                        "generate_s", Page.concat, pages))

    def _next_resident(self):
        """The source's next page that holds a row, as it lies on the
        device; row and byte counts come from its metadata (no sync)."""
        while True:
            page = self._source.get_next_device_page()
            if page is None or page.rows:
                break
        if page is None:
            return None
        if self.counters is not None:
            self.counters["resident_pages"] += 1
            self.counters["resident_bytes"] += page.nbytes
            self.counters["transferred_bytes" if page.transferred
                          else "local_bytes"] += page.nbytes
        return page, page.rows

    def next_page(self):
        """``(device page, its rows)``, or None: at the end (``done``),
        while splits are still to come, or where the source stalled."""
        with self._lock:
            return self._next_page()

    def _next_page(self):
        while True:
            if self._source is None:
                if self.splits:
                    self._source = self.connector.page_source(
                        self.splits.pop(0), self.columns)
                    self._on_host = \
                        not self._source.provides_device_pages
                elif self._buffer:
                    return self._flush()
                else:
                    self.done = self.no_more_splits
                    return None
            if not self._on_host:
                got = self._next_resident()
                if got is not None:
                    return got
                self._source.close()
                self._source = None
                continue
            page = _timed(self.counters, "scan.generate", "generate_s",
                          self._source.get_next_page)
            if page is None:
                if self._source.is_finished():
                    self._source.close()
                    self._source = None
                    continue
                # source stalled: don't sit on buffered rows
                return self._flush() if self._buffer else None
            if page.num_rows == 0:
                continue
            target = self.coalesce_rows
            if target and page.num_rows < target:
                self._buffer.append(page)
                self._buffered_rows += page.num_rows
                if self._buffered_rows >= target:
                    return self._flush()
                continue
            if self._buffer:
                self._buffer.append(page)
                self._buffered_rows += page.num_rows
                return self._flush()
            return self._upload(page)


class _ReadAhead:
    """The producer of a host scan: a thread that walks ``pages`` at
    most ``READAHEAD_PAGES`` ahead of ``take()``, the page it is making
    included, uploading to ``device`` (``jax.default_device`` is
    thread-local: the driver's thread says where its task runs)."""

    def __init__(self, pages: _ScanPages, device):
        self._pages = pages
        self._device = device
        self._room = threading.Semaphore(READAHEAD_PAGES)
        #: ``(page and rows | None at the end, exception | None)``
        self._made: queue.SimpleQueue = queue.SimpleQueue()
        self._last = None
        self._stopped = threading.Event()
        self.thread = threading.Thread(target=self._run, daemon=True,
                                       name="scan-readahead")
        self.thread.start()

    def _run(self):
        import jax

        try:
            with jax.default_device(self._device):
                while True:
                    self._room.acquire()
                    if self._stopped.is_set():
                        return
                    got = self._pages.next_page()
                    if got is not None:
                        self._made.put((got, None))
                    elif self._pages.done:
                        self._made.put((None, None))
                        return
                    else:
                        # the source stalled: ask again shortly
                        self._room.release()
                        if self._stopped.wait(0.001):
                            return
        except BaseException as e:  # noqa: BLE001 - raised by take()
            self._made.put((None, e))

    def ready(self) -> bool:
        return self._last is not None or not self._made.empty()

    def take(self):
        """The next page and its rows, waiting for it if need be; None
        at the end; the producer's exception where it failed."""
        if self._last is None:
            got, error = self._made.get()
            if got is not None:
                self._room.release()
                return got
            self._last = (None, error)
        if self._last[1] is not None:
            raise self._last[1]
        return None

    def stop(self):
        """No page is started after this; the thread ends once the one
        it is making is made."""
        self._stopped.set()
        self._room.release()

    def close(self):
        """``stop()``, wait for the thread's end, drop what it made."""
        self.stop()
        self.thread.join()
        self._made = queue.SimpleQueue()
        self._last = (None, None)


class TableScanOperator(SourceOperator):
    """Hands a connector's pages to its pipeline as device pages
    (reference: operator/TableScanOperator.java).

    Small pages (split tails: a table cut into many splits yields pages
    far below the connector's page size) COALESCE on host up to
    ``coalesce_rows`` before the upload, so downstream kernels see one
    full device batch instead of one launch per fragment (reference:
    ``operator/MergePages.java`` — the min-page-size rewindow in front
    of expensive operators).

    A host source is READ AHEAD.  The first page is generated and
    uploaded by ``get_output`` itself; if another may follow it, the
    rest of the walk (``_ScanPages``: the same splits in the same
    order, the same coalescing) moves to a producer thread that stays at
    most ``READAHEAD_PAGES`` pages ahead, and ``get_output`` takes a
    finished page from it, blocking until one is there.  So the host's
    page generation runs while the driver's thread waits for the device
    (an aggregation's per-page ``host_sync``) instead of after it.  A
    scan whose first page is its only one starts no thread.  Dynamic
    filters, the progress counter and the end of the scan stay on the
    driver's thread: a filter that arrives late applies to every page
    taken after it.  ``close()`` — from ``finish()``, or from the
    driver when its pipeline ends or fails — stops the producer and
    drops what it held.

    A source whose table lives on the device
    (``provides_device_pages``) is not uploaded from and not read
    ahead: its pages pass through as they lie, dynamic filters applied,
    nothing buffered."""

    def __init__(self, connector: Connector, columns: Sequence[ColumnHandle],
                 dynamic_filters: Sequence = (),
                 coalesce_rows: Optional[int] = None,
                 progress=None):
        #: telemetry.progress.QueryProgress fed each page's row count as
        #: it is handed out — a plain int add, never a device sync
        self.progress = progress
        # [(channel, DynamicFilter)] — join build-side domains applied to
        # every scanned page as a lane-mask update (reference analog:
        # dynamic-filter TupleDomains pushed into ConnectorPageSource)
        self.dynamic_filters = list(dynamic_filters)
        self._pages = _ScanPages(connector, list(columns), coalesce_rows)
        self._ahead: Optional[_ReadAhead] = None
        self._done = False
        #: rows the scan read, before any dynamic filter's mask: what
        #: history-based statistics file under the scan node (a filter
        #: belongs to the plan that hung it there, its effect to the
        #: join above, whose own history holds it)
        self._rows_read = 0
        #: host-side counters of a traced statement (None: tracing off).
        #: ``generate_s`` (the connector's page generation and the
        #: coalescing concat) and ``upload_s`` (pad + host-to-device
        #: copy) are the host's share of the scan: on the driver's
        #: thread for the first page — where they are bare on the
        #: statement's path, the device idle — and on the producer's
        #: for the rest, where they overlap whatever the driver does
        #: or waits for.  ``wait_s`` is what stayed on the path of that
        #: rest: seconds ``get_output`` waited for a page the producer
        #: had not finished; ``readahead_pages`` counts the pages taken
        #: from the producer and ``readahead_ready`` those that were
        #: ready when asked for.  From page metadata: the pages and
        #: bytes taken as they lay on the device against the bytes
        #: uploaded, and of the resident bytes those that lay on the
        #: scan's own device (``local_bytes``) against those copied
        #: from another (``transferred_bytes``).  Of its dynamic filters:
        #: the page·filter applications that tested a value set
        #: (``df_member_pages``) and those of them answered by the
        #: filter's membership table (``df_table_pages``)
        self._counters: Optional[dict] = None
        self._counters_known = False

    def add_split(self, split: ConnectorSplit):
        self._pages.splits.append(split)

    def no_more_splits(self):
        self._pages.no_more_splits = True

    def metrics(self) -> Optional[dict]:
        return dict(self._counters or {}, rows_read=self._rows_read)

    def _filtered(self, dp: DevicePage) -> DevicePage:
        c = self._counters
        for ch, df in self.dynamic_filters:
            dp = DevicePage(dp.types, dp.cols, dp.nulls,
                            df.apply(dp.cols[ch], dp.nulls[ch],
                                     dp.valid),
                            dp.dictionaries)
            if c is not None and df.set_form is not None:
                c["df_member_pages"] += 1
                c["df_table_pages"] += df.set_form == "table"
        return dp

    def _take_ahead(self):
        """The producer's next page (None at its end)."""
        ready = self._ahead.ready()
        c = self._counters
        got = self._ahead.take() if ready or c is None else \
            _timed(c, "scan.wait", "wait_s", self._ahead.take)
        if c is not None and got is not None:
            c["readahead_pages"] += 1
            c["readahead_ready"] += ready
        return got

    def get_output(self) -> Optional[DevicePage]:
        if self._done:
            return None
        if not self._counters_known:
            # first call: the statement's span, if any, is current now
            self._counters_known = True
            if tracing.current_span() is not None:
                self._counters = self._pages.counters = {
                    "generate_s": 0.0, "upload_s": 0.0, "wait_s": 0.0,
                    "readahead_pages": 0, "readahead_ready": 0,
                    "resident_pages": 0, "resident_bytes": 0,
                    "local_bytes": 0, "transferred_bytes": 0,
                    "uploaded_bytes": 0,
                    "df_member_pages": 0, "df_table_pages": 0}
        if self._ahead is not None:
            got = self._take_ahead()
            if got is None:
                self.close()
        else:
            got = self._pages.next_page()
            self._done = self._pages.done
            if got is not None and self._pages.may_have_more_on_host():
                import jax

                self._ahead = _ReadAhead(self._pages,
                                         jax.config.jax_default_device)
                # an operator dropped unfinished takes its producer along
                weakref.finalize(self, self._ahead.stop)
        if got is None:
            return None
        page, rows = got
        self._rows_read += rows
        if self.progress is not None:
            self.progress.add_rows(rows)
        return self._filtered(page)

    def finish(self):
        super().finish()
        self.close()

    def close(self):
        """The scan gives nothing more: no producer is left running and
        no page it made is held."""
        self._done = True
        ahead, self._ahead = self._ahead, None
        if ahead is not None:
            ahead.close()
        self._pages.close()

    def is_finished(self) -> bool:
        return self._done


class FilterProjectOperator(Operator):
    """Fused filter+project via a compiled PageProcessor (reference:
    ScanFilterAndProjectOperator / FilterAndProjectOperator +
    operator/project/PageProcessor.java)."""

    def __init__(self, processor: PageProcessor, params: tuple = ()):
        self.processor = processor
        #: template-parameter bindings (round 16): raw scalars for the
        #: processor's consumed slots — a template plan executed for one
        #: statement binds its literal vector here instead of retracing
        self.params = tuple(params)
        self._pending: Optional[DevicePage] = None
        self._done = False

    def needs_input(self) -> bool:
        return self._pending is None and not self._finishing

    def add_input(self, page: DevicePage):
        assert self._pending is None
        self._pending = self.processor.process(page, self.params)

    def get_output(self) -> Optional[DevicePage]:
        out, self._pending = self._pending, None
        if out is None and self._finishing:
            self._done = True
        return out

    def is_finished(self) -> bool:
        return self._done


def _running_valid_kernel():
    import jax
    import jax.numpy as jnp

    @jax.jit
    def kernel(valid, seen, lo, hi):
        """Keep live lanes whose running ordinal (seen so far + position
        within this page) lands in (lo, hi]; returns the new mask and
        the updated device-resident total."""
        run = jnp.cumsum(valid.astype(jnp.int64)) + seen
        new_valid = valid & (run > lo) & (run <= hi)
        return new_valid, run[-1]

    return kernel


_RUNNING_VALID = None


def _running_valid(valid, seen, lo, hi):
    global _RUNNING_VALID
    if _RUNNING_VALID is None:
        _RUNNING_VALID = _running_valid_kernel()
    return _RUNNING_VALID(valid, seen, lo, hi)


class LimitOperator(Operator):
    """LIMIT n (reference: operator/LimitOperator.java).

    Device-resident: the running row count stays a device scalar and the
    mask trim is one fused kernel — no per-page host pull of the valid
    mask (round-2 verdict weak #5). Early exit still works: the scalar
    is fetched ASYNC after each page and read one page later, so the
    driver stops pulling input at most one page after the limit fills,
    without ever stalling on a device round-trip."""

    def __init__(self, limit: int):
        self.limit = limit
        self._seen = None          # device scalar: rows passed so far
        self._known_seen = 0       # host view, one page stale
        self._pending: Optional[DevicePage] = None
        self._done = False

    def needs_input(self) -> bool:
        if self._seen is not None:
            # the async copy issued in add_input has usually landed;
            # this read is then free
            self._known_seen = int(tracing.host_read(self._seen,
                                                     "limit_seen"))
        return (self._pending is None and self._known_seen < self.limit
                and not self._finishing)

    def add_input(self, page: DevicePage):
        if self._known_seen >= self.limit:
            return
        import jax.numpy as jnp

        seen = jnp.int64(0) if self._seen is None else self._seen
        new_valid, self._seen = _running_valid(
            page.valid, seen, jnp.int64(0), jnp.int64(self.limit))
        try:
            self._seen.copy_to_host_async()
        except AttributeError:
            pass
        self._pending = DevicePage(page.types, page.cols, page.nulls,
                                   new_valid, page.dictionaries)

    def get_output(self) -> Optional[DevicePage]:
        out, self._pending = self._pending, None
        if out is None and (self._finishing
                            or self._known_seen >= self.limit):
            self._done = True
        return out

    def is_finished(self) -> bool:
        return self._done


class ValuesOperator(SourceOperator):
    """Inline literal rows (reference: operator/ValuesOperator.java).
    ``coalesce_rows`` applies the scan's small-page coalescing to
    pre-materialized host pages."""

    def __init__(self, pages: Sequence[Page],
                 coalesce_rows: Optional[int] = None):
        self._pages = list(pages)
        self.coalesce_rows = coalesce_rows
        self._done = False

    def add_split(self, split):
        raise AssertionError("values has no splits")

    def get_output(self) -> Optional[DevicePage]:
        if not self._pages:
            self._done = True
            return None
        if not self.coalesce_rows:
            return DevicePage.from_page(self._pages.pop(0))
        batch, rows = [], 0
        while self._pages and rows < self.coalesce_rows:
            batch.append(self._pages.pop(0))
            rows += batch[-1].num_rows
        return DevicePage.from_page(batch[0] if len(batch) == 1
                                    else Page.concat(batch))

    def is_finished(self) -> bool:
        return self._done


class OffsetOperator(Operator):
    """OFFSET n: drops the first n live rows (reference:
    operator/OffsetOperator.java). Fully device-resident — no control
    flow depends on the running count, so it never syncs to host."""

    def __init__(self, offset: int):
        self.offset = offset
        self._seen = None
        self._pending: Optional[DevicePage] = None
        self._done = False

    def needs_input(self) -> bool:
        return self._pending is None and not self._finishing

    def add_input(self, page: DevicePage):
        import jax.numpy as jnp

        seen = jnp.int64(0) if self._seen is None else self._seen
        new_valid, self._seen = _running_valid(
            page.valid, seen, jnp.int64(self.offset),
            jnp.int64(np.iinfo(np.int64).max))
        self._pending = DevicePage(page.types, page.cols, page.nulls,
                                   new_valid, page.dictionaries)

    def get_output(self) -> Optional[DevicePage]:
        out, self._pending = self._pending, None
        if out is None and self._finishing:
            self._done = True
        return out

    def is_finished(self) -> bool:
        return self._done


class EnforceSingleRowOperator(Operator):
    """Scalar-subquery guard: exactly one output row — errors on more,
    emits an all-NULL row on zero (reference:
    operator/EnforceSingleRowOperator.java)."""

    def __init__(self, types):
        self.types = list(types)
        self._rows = 0
        self._pages: List[DevicePage] = []
        self._emitted = False
        self._done = False

    def add_input(self, page: DevicePage):
        n = page.count()
        if not n:
            return
        self._rows += n
        if self._rows > 1:  # fail fast, don't buffer the stream
            from ..types import TrinoError

            raise TrinoError("Scalar sub-query has returned multiple rows",
                             "SUBQUERY_MULTIPLE_ROWS")
        self._pages.append(page)

    def get_output(self) -> Optional[DevicePage]:
        if not self._finishing or self._emitted:
            return None
        self._emitted = True
        self._done = True
        if self._rows == 1:
            return self._pages[0]
        # one all-NULL row
        row = Page.from_pylists(self.types,
                                [[None]] * len(self.types) or [])
        if not self.types:
            return None
        return DevicePage.from_page(row)

    def is_finished(self) -> bool:
        return self._done


class DeferredPagesSourceOperator(SourceOperator):
    """Source over host pages produced by earlier pipelines of the same
    task (union inputs, materialized intermediates). The thunk is called
    at first poll — after upstream pipelines completed."""

    def __init__(self, pages_thunk):
        self._thunk = pages_thunk
        self._pages = None
        self._done = False

    def add_split(self, split):
        raise AssertionError("deferred source has no splits")

    def get_output(self) -> Optional[DevicePage]:
        if self._pages is None:
            self._pages = list(self._thunk())
        if self._pages:
            page = self._pages.pop(0)
            if page.num_rows == 0:
                return self.get_output()
            return DevicePage.from_page(page)
        self._done = True
        return None

    def is_finished(self) -> bool:
        return self._done


class TableWriterOperator(Operator):
    """Feeds pages to a ConnectorPageSink; at finish emits one row with
    the written count (reference: operator/TableWriterOperator.java +
    TableFinishOperator.java — commit folded into sink.finish()).

    A sink that keeps its pages on the device
    (``accepts_device_pages``) is handed the pipeline's pages as they
    are; any other gets host pages.  A write that fails is taken back
    (``sink.abort()``, then ``undo``: the CTAS target is dropped).  The
    write is the statement's ``table_write`` span, with what the sink's
    ``finish()`` reports."""

    def __init__(self, sink, undo=None):
        self.sink = sink
        self.undo = undo
        self.rows = 0
        self._span = None
        self._emitted = False
        self._done = False

    def add_input(self, page: DevicePage):
        if self._span is None:
            self._span = tracing.span("table_write")
        with self._taken_back_on_failure():
            if self.sink.accepts_device_pages:
                self.rows += self.sink.append_device_page(page)
                return
            host = page.to_page()
            if host.num_rows:
                self.rows += host.num_rows
                self.sink.append_page(host)

    @contextlib.contextmanager
    def _taken_back_on_failure(self):
        """Around every call of the sink that can store a page (the
        tail page is stored by ``finish()``)."""
        try:
            yield
        except Exception:
            self.sink.abort()
            if self.undo is not None:
                self.undo()
            if self._span:
                self._span.finish()
            raise

    def get_output(self) -> Optional[DevicePage]:
        if not self._finishing or self._emitted:
            return None
        self._emitted = True
        self._done = True
        with self._taken_back_on_failure():
            written = self.sink.finish()
        if self._span:
            for key in ("rows", "pages", "device_bytes", "host_recode_s"):
                if key in (written or {}):
                    self._span.set(key, written[key])
            self._span.finish()
        from .. import types as T

        return DevicePage.from_page(
            Page.from_pylists([T.BIGINT], [[self.rows]]))

    def is_finished(self) -> bool:
        return self._done


class OutputCollectorOperator(Operator):
    """Pipeline sink: densifies device pages back to host Pages
    (reference analog: TaskOutputOperator feeding the OutputBuffer)."""

    def __init__(self):
        self.pages: List[Page] = []
        self._done = False

    def add_input(self, page: DevicePage):
        host = page.to_page()
        if host.num_rows:
            self.pages.append(host)

    def get_output(self):
        return None

    def finish(self):
        super().finish()
        self._done = True

    def is_finished(self) -> bool:
        return self._done
