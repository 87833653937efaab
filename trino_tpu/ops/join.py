"""Hash joins, TPU-first.

Reference analog: ``operator/join/HashBuilderOperator.java`` (build side:
PagesIndex + JoinHash open-addressing) + ``LookupJoinOperator.java`` /
``JoinProbe`` (probe side), plus ``SetBuilderOperator``/``ChannelSet`` for
semi joins.

TPU redesign: open-addressing probes are scatter/gather-chase loops that
map poorly to XLA. Instead the build side gets a **sorted index**: key
columns normalize to uint64 (exact for single keys; packed or hashed for
multi-key), one ``lax.sort`` of (key, row) orders the keys and says which
arrival lane each sorted position came from (``perm``) — the build's
columns are never moved: they stay in arrival order and are read through
``perm``, at the lanes a probe page's matches take — and a probe looks up
each probe row's candidate range ``(lo, count)`` in that index. Which lookup
runs is read off the build, once, when it is published
(``_attach_direct_table``): a build whose keys are exact and span a range
the chip can hold a table over gets a **direct-address table** of offsets
over ``key - klo`` (one scatter-add and one cumsum over the sorted rows),
and a probe page then costs two gathers (``_probe_direct_counts``); any
other build (hashed or float keys, a key at the u64 sentinel, a range past
``DIRECT_TABLE_MAX_BYTES``, no memory for the table, hybrid partitions)
keeps the two ``searchsorted`` calls (``_probe_counts``: XLA-native
vectorized binary search, log2(build) dependent gathers each) and says
why in the operator's metrics. Both give the same ``(lo, count)`` bit for
bit, so everything downstream is one path. Matches expand into a
static-capacity output: ``cumsum(count)`` gives every probe row's end
lane, and each output lane finds its row as the number of rows that end
at or before it — a histogram of those ends over the lanes and its
prefix sum (``_lane_rows``: one scatter-add at ascending indices, no
loop), or a binary search a lane where the expansion is far narrower
than the page (a selective join). The capacity is the page's own match
total, padded to a power of two (jit shapes are static, so some host
value must pick it): the lookup is enqueued with the total as an unread
device scalar, and the total is read — and the expansion enqueued at
that size — only when the probe pipeline is already ``pipeline_depth``
pages deep, so the host never blocks on the page it just looked up. An
output page is thus as wide as its matches whatever the probe page's
width was: the join is where a page a selective filter masked gets
dense again. Candidates are verified against the raw key columns, so
hash collisions cost only capacity, never correctness. Unmatched-probe
lanes for LEFT/ANTI come from a segment-OR over verified matches.

Two-operator split with a JoinBridge mirrors the reference; the physical
planner runs the build pipeline to completion before the probe pipeline.
"""

from __future__ import annotations

import threading
from dataclasses import dataclass
from functools import partial
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .. import types as T
from ..block import DevicePage, padded_size
from ..telemetry.profiler import instrument
from ..telemetry.tracing import host_read
from .operator import Operator
from .sortkeys import group_operands


def _canonical_codes(codes, dictionary):
    """Map dictionary codes to the FIRST code of their value, so equal
    strings in an aligned (duplicate-valued) pool compare equal by code."""
    if dictionary is None or len(dictionary) == 0:
        return codes
    canon = np.fromiter(
        (dictionary.lookup(v) for v in dictionary.values),
        dtype=np.int32, count=len(dictionary))
    if (canon == np.arange(len(canon), dtype=np.int32)).all():
        return codes  # already canonical (the common, dedup'd pool)
    return jnp.asarray(canon)[codes]


def _key_u64(cols, nulls, types_, mode: str) -> Tuple:
    """(key_u64, any_null): combined uint64 join key per row.

    mode (STATIC, decided once on the build side and shared via the
    bridge so both sides encode identically):
    - 'single': one key, exact order-preserving u64
    - 'packed': two keys, both known to fit 32 bits — exact pack
    - 'hashed': splitmix-combined (collisions verified against raw keys)
    """
    ops = []
    anynull = None
    for c, nl, t in zip(cols, nulls, types_):
        null_bit, key = group_operands(c, nl, t)
        if key.dtype == jnp.float64:
            # float join keys: frexp-based u64 (no f64 bitcast on TPU);
            # 2 dropped mantissa bits => rare extra candidates, all
            # filtered by the raw-key verify pass
            m, e = jnp.frexp(key)
            mant = (jnp.abs(m) * np.float64(1 << 53)).astype(jnp.int64) >> 2
            sign = (key < 0).astype(jnp.int64)
            key = (((e.astype(jnp.int64) + 1100) << np.int64(52))
                   | mant | (sign << np.int64(63))).view(jnp.uint64)
        ops.append(key)
        anynull = null_bit.astype(bool) if anynull is None \
            else (anynull | null_bit.astype(bool))
    if mode == "single":
        return ops[0], anynull
    if mode == "packed":
        hi, lo = ops[0], ops[1]
        return (hi << np.uint64(32)) | (lo & np.uint64(0xFFFFFFFF)), anynull
    return _hash_combine(ops), anynull


def _hash_combine(ops):
    acc = jnp.zeros(ops[0].shape, dtype=jnp.uint64)
    for k in ops:
        z = (k + np.uint64(0x9E3779B97F4A7C15)) * np.uint64(0xBF58476D1CE4E5B9)
        z = z ^ (z >> np.uint64(29))
        acc = (acc * np.uint64(31)) ^ z
    return acc


#: where the build's dead lanes (invalid or null-key rows) sort to
_U64_SENTINEL = np.uint64(0xFFFFFFFFFFFFFFFF)


@jax.jit
def _build_sorted(key_u64, anynull, valid):
    """The build's index, (key_sorted, perm): ONE sort of (key, row) and
    nothing else — no column is carried and none is gathered, the key
    the sort hands back is the sorted key.

    ``perm[i]`` is the arrival lane of sorted position i. Null-key or
    invalid lanes are dead: they take the u64 sentinel and sort last —
    among usable rows whose key IS the sentinel (a bigint -1), so the
    key cannot tell them apart; the payload does: a dead lane carries
    ``~lane`` (negative), which costs no third operand
    (``sortkeys.sort_carrying`` on what operands cost to compile) and
    no gather. ``perm >= 0`` is the sorted positions' usable flag,
    ``_arrival_rows`` decodes the lane."""
    from .. import jit_stats

    jit_stats.bump("join_build_sorted")
    usable = valid & ~anynull
    row = jnp.arange(key_u64.shape[0], dtype=jnp.int32)
    return tuple(jax.lax.sort(
        [jnp.where(usable, key_u64, _U64_SENTINEL),
         jnp.where(usable, row, ~row)], num_keys=1))


def _arrival_rows(perm, build_idx):
    """(build_row, live): the arrival lanes of sorted positions
    ``build_idx`` — one int32 gather at the caller's lanes — and whether
    each is a usable build row."""
    p = perm[build_idx]
    return jnp.where(p < 0, ~p, p), p >= 0


# profiled entry point (telemetry.profiler): cost/compile attribution
# under EXPLAIN ANALYZE VERBOSE; a plain call when profiling is off
_build_sorted = instrument("join_build_sorted", _build_sorted)


# Raw (un-jitted, un-instrumented) probe-kernel implementations: the
# batched executor composes them under its own jit(vmap(...)) wrappers
# with the build arrays broadcast (in_axes=None), so one param-free
# build serves every lane of a literal batch. Host callers use the
# jitted+instrumented bindings below.
def _probe_counts_impl(build_keys, probe_keys, probe_usable):
    from .. import jit_stats

    jit_stats.bump("join_probe_counts")
    lo = jnp.searchsorted(build_keys, probe_keys, side="left")
    hi = jnp.searchsorted(build_keys, probe_keys, side="right")
    count = jnp.where(probe_usable, hi - lo, 0)
    return lo, count


_probe_counts = instrument("join_probe_counts",
                           jax.jit(_probe_counts_impl))


#: an expansion at least this many times narrower than its probe page
#: keeps the search a lane: the histogram's scatter touches every probe
#: row (9 ns a row on a v5e), the search only its ``out_cap`` lanes
#: (0.2 us a lane), and they cross between rows / 32 and rows / 16 (the
#: expansion sweep, PERF.md section 5). A selective join's pages are of
#: that shape.
_SEARCH_WHEN_NARROWER = 32


def _lane_rows(off_end, out_cap: int):
    """int32[out_cap]: the probe row of every output lane, given the
    rows' ascending ends ``off_end = cumsum(count)``.

    Lane j belongs to the row p with ``off_end[p-1] <= j < off_end[p]``,
    i.e. p is the number of rows whose ``off_end <= j``. The ends ascend
    and so do the lanes, so that number is a merge, not a search a lane:
    a histogram of ``off_end`` over the lanes (one scatter-add at
    ascending indices; rows ending at or past ``out_cap`` fall off the
    end, no lane reaches them) and its prefix sum. Dead lanes
    (j >= total) read the last row either way."""
    rows = off_end.shape[0]
    if out_cap * _SEARCH_WHEN_NARROWER <= rows:
        j = jnp.arange(out_cap, dtype=off_end.dtype)
        ended = jnp.searchsorted(off_end, j, side="right")
    else:
        hist = jnp.zeros(out_cap, dtype=jnp.int32).at[off_end].add(
            1, mode="drop", indices_are_sorted=True)
        ended = jnp.cumsum(hist)
    return jnp.minimum(ended, rows - 1).astype(jnp.int32)


def _expand_matches_impl(lo, count, perm, out_cap: int):
    """Candidate pairs: output lane j -> (probe_row, build_row), the
    build row as an ARRIVAL lane of the build (``perm`` of the sorted
    position ``lo + k``), so every later read of a build column is one
    gather of the column where it arrived. The row's build offset comes
    by one gather of ``lo - (off_end - count)``; all lane arithmetic is
    int32 (``out_cap`` is bounded by ``max_lanes``, the build by the
    device). A candidate that is a dead lane of the build (reachable
    only by a probe key at the u64 sentinel) is no lane."""
    from .. import jit_stats

    jit_stats.bump("join_expand_matches")
    off_end = jnp.cumsum(count)
    total = off_end[-1]
    j = jnp.arange(out_cap, dtype=jnp.int32)
    probe_idx = _lane_rows(off_end, out_cap)
    delta = (lo - (off_end - count)).astype(jnp.int32)
    build_row, live = _arrival_rows(
        perm, jnp.maximum(j + delta[probe_idx], 0))
    return probe_idx, build_row, (j < total) & live


_expand_matches = instrument(
    "join_expand_matches",
    partial(jax.jit, static_argnames=("out_cap",))(_expand_matches_impl),
    static_argnames=("out_cap",))


# -- the direct-address probe ------------------------------------------------
#
# For a build whose u64 keys are exact and span [klo, khi], ``offsets[c]``
# is the number of usable build rows with key < klo + c: exactly what
# ``searchsorted(side="left")`` answers for key klo + c (dead lanes sort
# to the sentinel, past every usable key below it), and
# ``offsets[c + 1]`` is what ``side="right"`` answers.

#: the most a direct-address table may take (int32 offsets, padded to a
#: power of two): 64 Mi codes — TPC-H's order keys up to SF10. A build
#: whose key range needs more keeps the sorted-index probe.
DIRECT_TABLE_MAX_BYTES = 256 << 20


@jax.jit
def _key_span(key_sorted, perm):
    """u64[3]: the usable build rows' number, least and greatest key
    (dead lanes sort last, at the sentinel, so position n - 1 holds the
    greatest usable key — the sentinel itself where a usable key ties
    with them)."""
    n = jnp.sum(perm >= 0, dtype=jnp.int32)
    return jnp.stack([n.astype(jnp.uint64), key_sorted[0],
                      key_sorted[jnp.maximum(n - 1, 0)]])


def _span_range(span):
    """(n_usable, klo, number of codes) from ``_key_span``'s result,
    inside a traced program; no codes for an empty build."""
    n, klo = span[0], span[1]
    return n, klo, jnp.where(n == 0, np.uint64(0),
                             span[2] - klo + np.uint64(1))


@partial(jax.jit, static_argnames=("kp",))
def _build_direct_offsets(key_sorted, span, kp: int):
    """int32[kp] offsets over ``key - klo`` in ONE pass over the sorted
    keys: a scatter-add of ones (indices ascending: the keys are
    sorted; dead lanes lie at the sentinel, past the range of a build
    that gets a table, go past the end and are dropped) and a cumsum.
    ``kp`` > the number of codes, so ``offsets[range]`` = usable rows."""
    from .. import jit_stats

    jit_stats.bump("join_direct_table")
    _, klo, krange = _span_range(span)
    off = key_sorted - klo
    idx = jnp.where(off < krange, off, np.uint64(kp)).astype(jnp.int32)
    cnt = jnp.zeros(kp, dtype=jnp.int32).at[idx].add(
        1, mode="drop", indices_are_sorted=True)
    return jnp.cumsum(cnt) - cnt


_build_direct_offsets = instrument("join_direct_table",
                                   _build_direct_offsets,
                                   static_argnames=("kp",))


@jax.jit
def _probe_direct_counts(offsets, span, probe_keys, probe_usable):
    """``_probe_counts``' (lo, count) by two gathers: bit-identical for
    every usable probe row below the u64 sentinel (at the sentinel the
    searches count the build's dead lanes as candidates, which the
    expansion drops by ``perm``'s sign; a build with a usable key there
    gets no table)."""
    from .. import jit_stats

    jit_stats.bump("join_probe_direct")
    n, klo, krange = _span_range(span)
    off = probe_keys - klo  # u64: wraps below klo -> out of range
    in_range = probe_usable & (off < krange)
    code = jnp.where(in_range, off, np.uint64(0)).astype(jnp.int32)
    first = offsets[code]
    count = jnp.where(in_range, offsets[code + 1] - first, 0)
    lo = jnp.where(in_range, first,
                   jnp.where(probe_keys < klo, 0, n.astype(jnp.int32)))
    return lo, count


_probe_direct_counts = instrument("join_probe_direct",
                                  _probe_direct_counts)


@dataclass
class DirectTable:
    offsets: "jax.Array"   # int32[kp], kp = padded_size(codes + 1)
    span: "jax.Array"      # u64[3] on the device: n_usable, klo, khi

    @property
    def nbytes(self) -> int:
        return int(self.offsets.nbytes)


@dataclass
class BuildSide:
    """A published build: the index — ``key_sorted`` (u64, dead lanes at
    the sentinel) and ``perm`` (int32: sorted position -> arrival lane,
    ``~lane`` for a dead one; ``_build_sorted``) — over columns, null
    masks and ``valid`` that lie in ARRIVAL order, as the builder
    concatenated them. Nothing but the index is sorted."""
    key_sorted: "jax.Array"
    perm: "jax.Array"
    valid: "jax.Array"
    cols: Tuple
    nulls: Tuple
    types: List
    dictionaries: List
    key_channels: List
    key_mode: str = "single"
    #: the direct-address table over the build's keys, or None and why
    #: not (``_attach_direct_table``); every operator that probes this
    #: build shares it
    direct: Optional[DirectTable] = None
    direct_fallback: Optional[str] = None


def _attach_direct_table(b: BuildSide, ctx=None) -> None:
    """Give ``b`` its direct-address table if what the build shows
    allows one — exact keys, an observed key range whose table fits
    ``DIRECT_TABLE_MAX_BYTES`` and the operator's memory — else the
    reason. One blocking read (three scalars) a build."""
    from ..exec.memory import MemoryExceededError, NodeMemoryExceededError

    if b.key_mode == "hashed":
        b.direct_fallback = "hashed key mode"
        return
    if any(b.types[c] in (T.DOUBLE, T.REAL) for c in b.key_channels):
        b.direct_fallback = "float key"
        return
    span = _key_span(b.key_sorted, b.perm)
    n, klo, khi = (int(v) for v in host_read(span, "join_key_range"))
    if n and khi == int(_U64_SENTINEL):
        b.direct_fallback = "key at the u64 sentinel"
        return
    key_range = khi - klo + 1 if n else 0
    kp = padded_size(key_range + 1)
    nbytes = 4 * kp
    if nbytes > DIRECT_TABLE_MAX_BYTES:
        b.direct_fallback = (f"key range {key_range} past the table's "
                             f"bound ({DIRECT_TABLE_MAX_BYTES >> 20} MiB)")
        return
    if ctx is not None:
        # the table is an optional index: it takes what is free and
        # never makes another operator spill for it. The counts and
        # their cumsum are both alive while it is built
        pool = ctx.pool
        if pool.reserved + 2 * nbytes > pool.max_bytes:
            b.direct_fallback = "memory reservation refused"
            return
        try:
            ctx.reserve(2 * nbytes, revocable=False)
        except (MemoryExceededError, NodeMemoryExceededError):
            b.direct_fallback = "memory reservation refused"
            return
    offsets = _build_direct_offsets(b.key_sorted, span, kp=kp)
    if ctx is not None:
        ctx.free(nbytes, revocable=False)
    b.direct = DirectTable(offsets, span)


class JoinBridge:
    """Hand-off from the build pipeline to the probe pipeline (reference:
    operator/join/JoinBridge.java / PartitionedLookupSourceFactory)."""

    def __init__(self):
        self.build: Optional[BuildSide] = None
        self.release = None  # set by the builder; probe calls at finish
        #: HybridJoinState once the builder entered partitioned mode
        #: under memory pressure; None on the (common) fully-resident
        #: path.  The probe routes rows by it and runs the deferred
        #: per-partition unspill->probe passes at finish.
        self.hybrid: Optional["HybridJoinState"] = None

    def set_build(self, b: BuildSide):
        self.build = b

    def destroy(self):
        """Probe side is done: drop the build index + its memory
        reservation (reference: LookupSourceFactory destroy)."""
        self.build = None
        if self.release is not None:
            self.release()
            self.release = None


# -- dynamic hybrid hash join ------------------------------------------------
#
# Grace/hybrid-style degradation ("Design Trade-offs for a Robust Dynamic
# Hybrid Hash Join"): under memory pressure the build input is partitioned
# by a splitmix64 sub-hash of the join key; hot partitions stay resident on
# device and feed the normal sorted-index path, cold partitions park
# page-at-a-time through the spill tiers (host ledger -> CRC-framed disk
# files).  Probe rows of cold partitions spill alongside their build
# partition and join in per-partition unspill->probe passes at finish; a
# partition that still exceeds the pool on unspill recursively repartitions
# with a depth-salted hash.


def _splitmix64_np(z: np.ndarray) -> np.ndarray:
    """splitmix64 finalizer over a uint64 numpy array (wraps mod 2^64)."""
    z = (z ^ (z >> np.uint64(30))) * np.uint64(0xBF58476D1CE4E5B9)
    z = (z ^ (z >> np.uint64(27))) * np.uint64(0x94D049BB133111EB)
    return z ^ (z >> np.uint64(31))


def _salt_for_depth(depth: int) -> int:
    """Per-recursion-level hash salt: the same key must land in DIFFERENT
    sub-partitions when an oversized partition repartitions, or recursion
    could never split it."""
    return (0x9E3779B97F4A7C15 * (depth + 1)) & 0xFFFFFFFFFFFFFFFF


class HybridJoinState:
    """Resident-set bookkeeping shared by the build and probe operators
    of one hybrid hash join.

    ``_lock`` guards the partition table: ``resident`` (the device-
    resident partition ids) and the cold-partition spill lists mutate
    under it from several threads — the build driver routing input, the
    pool's revocation callback demoting partitions (any reserving
    thread), and the probe driver spilling cold probe rows."""

    def __init__(self, fanout: int, max_depth: int = 3,
                 source: str = "local", depth: int = 0):
        self._lock = threading.RLock()
        self.fanout = fanout
        self.max_depth = max_depth
        self.source = source        # fanout provenance: hbo|session|local
        self.depth = depth
        self.salt = _salt_for_depth(depth)
        self.resident = frozenset(range(fanout))
        #: pid -> [SpilledPage] (build rows of demoted partitions)
        self.spilled_build: Dict[int, List] = {}
        #: pid -> [SpilledPage] (probe rows parked beside their build)
        self.spilled_probe: Dict[int, List] = {}
        self.demotions = 0          # revocation-driven partition demotions
        self.repartitions = 0       # recursive splits on unspill
        self.max_depth_seen = depth
        self.spilled_build_rows = 0
        self.total_build_rows = 0
        #: the build's memory context (set by the builder): the probe's
        #: deferred passes reserve partition transients against it
        self.ctx = None
        #: pooled-key value-hash LUT cache (dict objects pinned so a
        #: reused id() can never alias a dead pool)
        self._hash_luts: Dict[tuple, tuple] = {}

    # -- partition table mutations (all under _lock) --------------------

    def demote(self, pid: int, pages: List, rows: int):
        """Revocation demoted partition ``pid``: drop it from the
        resident set and park its build pages."""
        with self._lock:
            self.resident = self.resident - {pid}
            self.spilled_build.setdefault(pid, []).extend(pages)
            self.spilled_build_rows += rows
            self.demotions += 1

    def route_build_spill(self, pid: int, page, rows: int):
        """A build page arriving for an already-cold partition parks
        directly (the page-at-a-time path — no device residency)."""
        with self._lock:
            self.resident = self.resident - {pid}
            self.spilled_build.setdefault(pid, []).append(page)
            self.spilled_build_rows += rows

    def add_probe_spill(self, pid: int, page):
        with self._lock:
            self.spilled_probe.setdefault(pid, []).append(page)

    def count_build_rows(self, rows: int):
        with self._lock:
            self.total_build_rows += rows

    def note_depth(self, depth: int):
        with self._lock:
            self.repartitions += 1
            self.max_depth_seen = max(self.max_depth_seen, depth)

    def spill_fraction(self) -> float:
        with self._lock:
            return self.spilled_build_rows / max(1, self.total_build_rows)

    # -- partition hash --------------------------------------------------

    def _value_hash_lut(self, d) -> np.ndarray:
        """code -> stable-within-process value hash for one pool, so
        both sides partition pooled keys by VALUE (their code spaces
        differ until the probe-side remap, which happens later)."""
        key = (id(d), len(d) if d else 0)
        hit = self._hash_luts.get(key)
        if hit is not None:
            return hit[0]
        if d is None or len(d) == 0:
            lut = np.zeros(1, dtype=np.uint64)
        else:
            lut = np.fromiter(
                (hash(v) & 0xFFFFFFFFFFFFFFFF for v in d.values),
                dtype=np.uint64, count=len(d))
        self._hash_luts[key] = (lut, d)
        return lut

    def partition_ids(self, cols: List[np.ndarray],
                      nulls: List[np.ndarray], types_, dicts,
                      salt: Optional[int] = None,
                      fanout: Optional[int] = None) -> np.ndarray:
        """Per-row partition id from the raw key VALUES (host arrays).

        Value-based — not code- or storage-based — so build and probe
        rows with join-equal keys land in the same partition even when
        their dictionaries or integer widths differ.  Null keys hash to
        partition of key 0; they are routed resident by the callers
        (they match nothing, and LEFT/ANTI must emit them exactly
        once)."""
        salt = self.salt if salt is None else salt
        fanout = self.fanout if fanout is None else fanout
        acc = np.zeros(cols[0].shape[0], dtype=np.uint64)
        for c, nl, t, d in zip(cols, nulls, types_, dicts):
            if t.is_pooled:
                lut = self._value_hash_lut(d)
                codes = np.clip(c.astype(np.int64), 0, len(lut) - 1)
                k = lut[codes]
            elif np.issubdtype(c.dtype, np.floating):
                f = c.astype(np.float64)
                f = np.where(f == 0.0, 0.0, f)   # -0.0 joins +0.0
                k = f.view(np.uint64)
                k = np.where(np.isnan(f),
                             np.uint64(0x7FF8000000000000), k)
            elif c.dtype == bool:
                k = c.astype(np.uint64)
            else:
                k = c.astype(np.int64).view(np.uint64)
            k = np.where(nl, np.uint64(0), k)
            acc = (acc * np.uint64(31)) ^ _splitmix64_np(
                k + np.uint64(0x9E3779B97F4A7C15))
        pid = _splitmix64_np(acc ^ np.uint64(salt)) \
            & np.uint64(fanout - 1)
        return pid.astype(np.int64)


def _host_spilled(types_, cols: List[np.ndarray], nulls: List[np.ndarray],
                  k: int, dicts):
    """An in-RAM SpilledPage over k extracted host rows (pow2-padded),
    charge-able to the ledger and demotable to the disk tier like any
    other parked page."""
    from ..block import padded_size
    from ..exec.memory import SpilledPage

    cap = padded_size(max(int(k), 1))
    page = SpilledPage.__new__(SpilledPage)
    page.types = list(types_)
    page.dictionaries = list(dicts)
    page.cols = [_np_pad(c, cap) for c in cols]
    page.nulls = [_np_pad(n, cap, fill=True) for n in nulls]
    v = np.zeros(cap, dtype=bool)
    v[:k] = True
    page.valid = v
    return page


def _assemble_build_side(input_types, key_channels, cols, nulls, valid,
                         cap: int, dicts) -> BuildSide:
    """Canonicalize key codes, pick the key mode, normalize to u64 and
    sort (key, row): the tail of the build publish, shared by the
    resident index and each deferred cold-partition index (the hybrid
    join builds one per unspilled partition; the mode decision is
    type-static, so every partition encodes identically). The columns
    go into the ``BuildSide`` as they came."""
    kc = list(key_channels)
    cols = list(cols)
    # pooled keys (strings AND array/map/row composites) join on
    # dictionary CODES in the build's pool: the build side uses its
    # own codes as plain ints; the probe side remaps its codes into
    # this pool (LookupJoinOperator._remap), so both sides feed
    # _key_u64 the same integer key space.
    # CANONICALIZE build key codes first: aligned pools (derived by
    # transforms) may map one value to several codes, and
    # code-equality must mean value-equality for the join keys.
    # Canonical codes decode to the same values, so rewriting the
    # stored column is output-safe.
    for c in kc:
        if input_types[c].is_pooled:
            cols[c] = _canonical_codes(cols[c], dicts[c])
    key_types = [T.BIGINT if input_types[c].is_pooled
                 else input_types[c] for c in kc]
    mode = "single" if len(kc) == 1 else "hashed"
    if len(kc) == 2:
        # static decision — no device sync: pack two keys iff both
        # are provably 32-bit lanes (4-byte integer/bool storage, or
        # pooled codes, int32 by construction; sign-extension keeps
        # the low 32 bits injective). Floats are excluded: their
        # frexp encoding uses all 64 bits, so truncation would mass-
        # collide. The u64 key is only a bucketing function —
        # candidates are verified against raw keys — so a
        # conservative choice is safe either way.
        fits32 = [
            input_types[c].is_pooled
            or (t.storage is not None
                and np.dtype(t.storage).kind in "iub"
                and np.dtype(t.storage).itemsize <= 4)
            for c, t in zip(kc, key_types)]
        mode = "packed" if all(fits32) else "hashed"
    key, anynull = _key_u64([cols[c] for c in kc],
                            [nulls[c] for c in kc], key_types, mode)
    key_sorted, perm = _build_sorted(
        key, anynull if anynull is not None
        else jnp.zeros(cap, dtype=bool), valid)
    return BuildSide(key_sorted, perm, valid, tuple(cols), tuple(nulls),
                     list(input_types), dicts, kc, mode)


def _build_side_from_spilled(input_types, key_channels,
                             pages: List) -> BuildSide:
    """One cold partition's index from its parked pages: host concat
    (disk-parked pages stream back through serde.read_spill_file via
    host()), one upload, then the shared assembly tail."""
    from ..block import unify_dictionaries

    hosts = [p.host() for p in pages]
    cap = padded_size(sum(p.capacity for p in hosts))
    cols, nulls = [], []
    for i in range(len(input_types)):
        c = np.concatenate([p.cols[i] for p in hosts])
        n = np.concatenate([p.nulls[i] for p in hosts])
        cols.append(jnp.asarray(_np_pad(c, cap)))
        nulls.append(jnp.asarray(_np_pad(n, cap, fill=True)))
    v = np.concatenate([p.valid for p in hosts])
    valid = jnp.asarray(_np_pad(v, cap))
    dicts = unify_dictionaries(hosts, len(input_types))
    return _assemble_build_side(input_types, key_channels, cols, nulls,
                                valid, cap, dicts)


class HashBuilderOperator(Operator):
    """Accumulates the build side and publishes it as a ``BuildSide``:
    its pages concatenated in arrival order, and the sorted (key, row)
    index over them — no column is carried through the sort."""

    def __init__(self, input_types: Sequence[T.Type],
                 key_channels: Sequence[int], bridge: JoinBridge,
                 memory_context=None, dynamic_filters: Sequence = (),
                 hybrid: Optional[dict] = None):
        self.input_types = list(input_types)
        self.key_channels = list(key_channels)
        self.bridge = bridge
        # [(channel, DynamicFilter)] to fill at publish (reference:
        # DynamicFilterSourceOperator collecting build values)
        self.dynamic_filters = list(dynamic_filters)
        #: hybrid-hash-join options from the planner: {"fanout": session
        #: override (0=auto), "max_depth": recursion bound, "hint": the
        #: HBO spill record of this node's last run (sizes fan-out with
        #: source=hbo), or None when hybrid degradation is off (FULL
        #: OUTER, or disabled by session property)
        self._hybrid = hybrid
        self._hstate: Optional[HybridJoinState] = None
        #: parallel to _pages in partitioned mode: the partition id of
        #: each device page, or -1 for a not-yet-split mixed page
        self._page_pid: List[int] = []
        self._pages: List = []  # DevicePage | SpilledPage
        self._published: dict = {}
        self._done = False
        self._ctx = memory_context
        if self._ctx is not None:
            self._ctx.set_revoke_callback(self._revoke)

    def add_input(self, page: DevicePage):
        if self._ctx is None:
            self._pages.append(page)
            return
        if self._hstate is not None:
            self._add_input_partitioned(page)
            return
        from ..exec.memory import reserve_and_append

        reserve_and_append(self._ctx, self._pages, page)
        with self._ctx.lock:
            if self._hstate is not None:
                # the reserve above fired the FIRST revocation:
                # partitioned mode began mid-append, so _init_partitions
                # counted only the pages before this one — pair and
                # count this page now or the spill fraction overshoots
                # (and a later _split_mixed would drop the page)
                while len(self._page_pid) < len(self._pages):
                    self._page_pid.append(-1)
                self._hstate.count_build_rows(int(
                    np.count_nonzero(np.asarray(page.valid))))

    def _revoke(self) -> int:
        """Memory revocation (runs under the context lock, on whatever
        thread needed the bytes).  Hybrid path: enter partitioned mode
        on the first call and demote the LARGEST resident partition —
        the resident set shrinks IN PLACE and the query keeps building.
        Fallback (hybrid off / FULL OUTER): park everything in host RAM
        wholesale (the pre-hybrid CONSUMING_INPUT -> SPILLING_INPUT
        transition, with the disk tier below host RAM when the ledger
        overflows)."""
        from ..exec.memory import spill_pages

        if self._hybrid is None:
            return spill_pages(self._pages, self._ctx.pool,
                               self._ctx.lock)
        if self._hstate is None:
            self._init_partitions()
        return self._demote_next()

    # -- hybrid: partitioned build --------------------------------------

    def _init_partitions(self):
        """First revocation: decide the fan-out and enter partitioned
        mode.  Fan-out precedence: explicit session property, then the
        HBO spill hint of this node's previous run (source=hbo — the
        second run sizes fan-out right), then pool headroom vs bytes
        accumulated so far; always pow2 via KERNEL_SIZING."""
        from ..exec.memory import device_page_bytes
        from .kernel_sizing import KERNEL_SIZING

        opts = self._hybrid or {}
        hint = opts.get("hint") or {}
        if opts.get("fanout"):
            fanout, source = int(opts["fanout"]), "session"
        elif hint.get("fanout"):
            # size from the previous run's observed spill: a build that
            # spilled a meaningful fraction gets a finer fan-out so each
            # partition fits without recursion; one that barely spilled
            # keeps its grain
            fanout, source = int(hint["fanout"]), "hbo"
            frac = float(hint.get("fraction") or 0.0)
            if frac > 0.5:
                fanout *= 4
            elif frac > 0.125:
                fanout *= 2
            if int(hint.get("repartitions") or 0) > 0:
                fanout *= 2
        else:
            pool = self._ctx.pool
            dev_bytes = sum(device_page_bytes(p) for p in self._pages
                            if isinstance(p, DevicePage))
            # target: one partition should fit in ~1/4 of the pool; the
            # build is typically mid-stream when pressure hits, so the
            # seen bytes are doubled as the cardinality guess
            per_part = max(1, pool.max_bytes // 4)
            need = max(4, -(-dev_bytes * 2 // per_part))
            fanout = KERNEL_SIZING.suggest(
                ("hybrid_join_fanout", len(self.key_channels)),
                need, minimum=4)
            source = "local"
        fanout = max(2, min(int(fanout), 256))
        self._hstate = HybridJoinState(
            fanout, max_depth=int(opts.get("max_depth", 3)),
            source=source)
        # the probe's deferred per-partition passes charge their
        # transients (and spilled probe pages) to the build's context,
        # which stays open for the probe's lifetime via bridge.release
        self._hstate.ctx = self._ctx
        self.bridge.hybrid = self._hstate
        self._page_pid = [-1] * len(self._pages)
        self._hstate.count_build_rows(sum(
            int(np.count_nonzero(np.asarray(p.valid)))
            for p in self._pages))

    def _key_cols_host(self, cols, nulls, dicts):
        """(cols, nulls, types, dicts) of the key channels as host
        arrays, feeding HybridJoinState.partition_ids."""
        kc = self.key_channels
        return ([np.asarray(cols[c]) for c in kc],
                [np.asarray(nulls[c]) for c in kc],
                [self.input_types[c] for c in kc],
                [dicts[c] for c in kc])

    def _split_mixed(self):
        """Split every mixed (-1) page into per-partition pages: rows of
        resident partitions repack into one device page per partition
        present; rows of cold partitions park as SpilledPages (caller
        holds the context lock)."""
        from ..exec.memory import SpilledPage

        hs = self._hstate
        pages, pids = self._pages, self._page_pid
        if len(pids) < len(pages):
            # a page appended by a reserve whose own revocation rewrote
            # these lists has no pid yet — it is mixed by construction;
            # dropping it (the old zip truncation) lost build rows
            pids = pids + [-1] * (len(pages) - len(pids))
        out_pages: List = []
        out_pids: List[int] = []
        buckets: Dict[int, List[tuple]] = {}
        for pg, pid in zip(pages, pids):
            if pid != -1 or isinstance(pg, SpilledPage):
                out_pages.append(pg)
                out_pids.append(pid)
                continue
            cols = [np.asarray(c) for c in pg.cols]
            nulls = [np.asarray(n) for n in pg.nulls]
            valid = np.asarray(pg.valid)
            kcols, knulls, ktypes, kdicts = self._key_cols_host(
                cols, nulls, pg.dictionaries)
            rowpid = hs.partition_ids(kcols, knulls, ktypes, kdicts)
            for pid_ in np.unique(rowpid[valid]):
                pid_ = int(pid_)
                keep = np.nonzero(valid & (rowpid == pid_))[0]
                rows = ([c[keep] for c in cols],
                        [n[keep] for n in nulls], len(keep),
                        pg.dictionaries, pg.types)
                buckets.setdefault(pid_, []).append(rows)
        for pid_, parts in sorted(buckets.items()):
            cols = [np.concatenate([p[0][i] for p in parts])
                    for i in range(len(self.input_types))]
            nulls = [np.concatenate([p[1][i] for p in parts])
                     for i in range(len(self.input_types))]
            k = sum(p[2] for p in parts)
            sp = _host_spilled(parts[0][4], cols, nulls, k, parts[0][3])
            if pid_ in hs.resident:
                out_pages.append(sp.to_device())
                out_pids.append(pid_)
            else:
                self._park_spilled(pid_, sp, k, probe=False)
        self._pages[:] = out_pages
        self._page_pid[:] = out_pids

    def _park_spilled(self, pid: int, sp, rows: int, probe: bool):
        """Charge one cold-partition page to the host ledger and demote
        through the disk tier when the ledger overflows (caller holds
        the context lock)."""
        hs = self._hstate
        pool = self._ctx.pool
        if probe:
            hs.add_probe_spill(pid, sp)
            plist = hs.spilled_probe[pid]
        else:
            hs.route_build_spill(pid, sp, rows)
            plist = hs.spilled_build[pid]
        pool.host_ledger.charge(sp)
        pool.host_ledger.track(plist, self._ctx.lock, pool)
        pool.maybe_demote(plist)

    def _demote_next(self) -> int:
        """Demote resident partitions LARGEST-first until device bytes
        actually came free; returns the bytes freed (the partial-
        revocation contract: one demotion per loop round, repeated by
        revoke_up_to while more is needed).  Caller holds the context
        lock."""
        from ..exec.memory import SpilledPage, device_page_bytes

        hs = self._hstate
        before = sum(device_page_bytes(p) for p in self._pages
                     if isinstance(p, DevicePage))
        self._split_mixed()
        pool = self._ctx.pool
        freed_any = False
        while True:
            sizes: Dict[int, int] = {}
            for pg, pid in zip(self._pages, self._page_pid):
                if pid >= 0 and pid in hs.resident \
                        and isinstance(pg, DevicePage):
                    sizes[pid] = sizes.get(pid, 0) \
                        + device_page_bytes(pg)
            after = sum(device_page_bytes(p) for p in self._pages
                        if isinstance(p, DevicePage))
            if before - after > 0 and freed_any:
                break
            if not sizes:
                break
            victim = max(sizes, key=lambda p: sizes[p])
            vpages, vrows = [], 0
            keep_pages, keep_pids = [], []
            for pg, pid in zip(self._pages, self._page_pid):
                if pid == victim and isinstance(pg, DevicePage):
                    sp = SpilledPage(pg)
                    vrows += int(np.count_nonzero(sp.valid))
                    vpages.append(sp)
                else:
                    keep_pages.append(pg)
                    keep_pids.append(pid)
            self._pages[:] = keep_pages
            self._page_pid[:] = keep_pids
            hs.demote(victim, vpages, vrows)
            for sp in vpages:
                pool.host_ledger.charge(sp)
            pool.host_ledger.track(hs.spilled_build[victim],
                                   self._ctx.lock, pool)
            pool.maybe_demote(hs.spilled_build[victim])
            pool.record_partition_spill(sizes[victim], 1)
            freed_any = True
        after = sum(device_page_bytes(p) for p in self._pages
                    if isinstance(p, DevicePage))
        return max(before - after, 0)

    def _add_input_partitioned(self, page: DevicePage):
        """Partitioned-mode input routing: resident-partition rows stay
        on device (one compacted page), cold-partition rows park
        directly beside their partition — page-at-a-time, never
        resident."""
        from ..exec.memory import device_page_bytes

        hs = self._hstate
        cols = [np.asarray(c) for c in page.cols]
        nulls = [np.asarray(n) for n in page.nulls]
        valid = np.asarray(page.valid)
        kcols, knulls, ktypes, kdicts = self._key_cols_host(
            cols, nulls, page.dictionaries)
        rowpid = hs.partition_ids(kcols, knulls, ktypes, kdicts)
        hs.count_build_rows(int(np.count_nonzero(valid)))
        with hs._lock:
            resident = hs.resident
        cold_pids = [int(p) for p in np.unique(rowpid[valid])
                     if int(p) not in resident]
        if not cold_pids:
            self.add_input_resident(page)
            return
        cold_rows = np.isin(rowpid, np.asarray(cold_pids))
        res_valid = valid & ~cold_rows
        dev = None
        if res_valid.any():
            sp = _host_spilled(
                page.types, [c[res_valid] for c in cols],
                [n[res_valid] for n in nulls],
                int(np.count_nonzero(res_valid)), page.dictionaries)
            dev = sp.to_device()
            self._ctx.reserve(device_page_bytes(dev))
        with self._ctx.lock:
            if dev is not None:
                self._pages.append(dev)
                self._page_pid.append(-1)
            for pid_ in cold_pids:
                keep = np.nonzero(valid & (rowpid == pid_))[0]
                sp = _host_spilled(
                    page.types, [c[keep] for c in cols],
                    [n[keep] for n in nulls], len(keep),
                    page.dictionaries)
                self._park_spilled(pid_, sp, len(keep), probe=False)

    def add_input_resident(self, page: DevicePage):
        from ..exec.memory import reserve_and_append

        reserve_and_append(self._ctx, self._pages, page)
        with self._ctx.lock:
            # the reserve above may have revoked: _split_mixed rewrites
            # both lists to arbitrary lengths, so resync rather than
            # compare against a pre-reserve snapshot (unpaired pages
            # are always trailing appends, mixed by construction)
            while len(self._page_pid) < len(self._pages):
                self._page_pid.append(-1)

    def metrics(self) -> dict:
        """What was published: how the keys were assembled (``single``
        / ``packed`` / ``hashed`` - the last has no direct-address
        table), the index's width in lanes and the columns gathered
        into sorted order at that width (none: the probe translates its
        matches' lanes instead, the join's ``build_row_lanes``)."""
        out = dict(self._published)
        hs = self._hstate
        if hs is None:
            return out
        with hs._lock:
            return {**out, "hybrid_spill": {
                "fanout": hs.fanout,
                "source": hs.source,
                "fraction": round(hs.spilled_build_rows
                                  / max(1, hs.total_build_rows), 4),
                "partitions_spilled": len(hs.spilled_build),
                "demotions": hs.demotions,
                "repartitions": hs.repartitions,
                "max_depth": hs.max_depth_seen,
            }}

    def get_output(self):
        if self._finishing and not self._done:
            self._publish()
            self._done = True
        return None

    def _publish(self):
        from ..exec.memory import SpilledPage, device_page_bytes

        if self._ctx is not None and self._hybrid is not None:
            # publish owns the state; hybrid path: when the index +
            # its concat/sort transients do not fit the pool, shrink
            # the RESIDENT SET instead of parking the whole build —
            # demoted partitions move to the probe's deferred
            # per-partition passes, so the published index covers
            # exactly what fits
            from ..exec.memory import MemoryExceededError

            with self._ctx.lock:
                self._ctx.set_revoke_callback(None)
                if self._hstate is not None \
                        and self._hstate.spilled_build:
                    # straggler mixed pages: a page appended by the very
                    # reserve call whose revocation demoted a partition
                    # still carries that partition's rows under pid -1.
                    # Route them now — a cold row baked into the
                    # resident index would never be probed (its probe
                    # rows all park for the deferred pass, which reads
                    # only spilled_build).
                    self._split_mixed()

            def _demote_once() -> int:
                with self._ctx.lock:
                    if self._hstate is None:
                        self._init_partitions()
                    freed = self._demote_next()
                if freed > 0:
                    self._ctx.pool.record_spill(freed)
                    self._ctx.free(freed)
                return freed

            budget = max(1, self._ctx.pool.max_bytes // 4)
            while True:
                total = sum(device_page_bytes(p) for p in self._pages)
                uploads = sum(device_page_bytes(p) for p in self._pages
                              if isinstance(p, SpilledPage))
                if total > budget and _demote_once() > 0:
                    # the RETAINED index must leave headroom for the
                    # probe and everything downstream — same 1/4-pool
                    # target the fan-out sizing uses
                    continue
                try:
                    self._ctx.reserve(
                        uploads + total + self._index_bytes(),
                        revocable=False)
                    break
                except MemoryExceededError:
                    if _demote_once() <= 0:
                        raise
        elif self._ctx is not None:
            # publish owns the state; the build index it creates is
            # retained (non-revocable) for the probe's lifetime
            from ..exec.memory import prepare_finish

            total, uploads = prepare_finish(self._ctx, self._pages)
            all_spilled = bool(self._pages) and all(
                isinstance(p, SpilledPage) for p in self._pages)
            # transient: the concatenation and the sort's two operands
            # with its two results, plus per-page re-uploads on the
            # mixed path (the all-spilled path concatenates in host RAM
            # and uploads once — no per-page residency)
            self._ctx.reserve(
                (0 if all_spilled else uploads) + total
                + self._index_bytes(), revocable=False)
        if self._pages:
            spilled = [p for p in self._pages if isinstance(p, SpilledPage)]
            if spilled and len(spilled) == len(self._pages):
                # pressure path: concatenate in host RAM, upload once
                # (host() loads disk-parked pages back into RAM first)
                hosts = [p.host() for p in self._pages]
                cap = padded_size(sum(p.capacity for p in hosts))
                cols, nulls = [], []
                nch = len(self.input_types)
                for i in range(nch):
                    c = np.concatenate([p.cols[i] for p in hosts])
                    n = np.concatenate([p.nulls[i] for p in hosts])
                    cols.append(jnp.asarray(_np_pad(c, cap)))
                    nulls.append(jnp.asarray(_np_pad(n, cap, fill=True)))
                v = np.concatenate([p.valid for p in hosts])
                valid = jnp.asarray(_np_pad(v, cap))
                dicts = self._unified_dicts(hosts)
            else:
                pages = [p.to_device() if isinstance(p, SpilledPage) else p
                         for p in self._pages]
                cap = padded_size(sum(p.capacity for p in pages))
                cols, nulls = [], []
                nch = len(self.input_types)
                for i in range(nch):
                    cols.append(_pad_concat([p.cols[i] for p in pages], cap))
                    nulls.append(_pad_concat([p.nulls[i] for p in pages],
                                             cap, fill=True))
                valid = _pad_concat([p.valid for p in pages], cap)
                dicts = self._unified_dicts(pages)
        else:
            from ..block import Dictionary

            cap = 16
            cols = [jnp.zeros(cap, dtype=t.storage) for t in self.input_types]
            nulls = [jnp.ones(cap, dtype=bool) for _ in self.input_types]
            valid = jnp.zeros(cap, dtype=bool)
            dicts = [Dictionary() if t.is_pooled else None
                     for t in self.input_types]
        self._collect_dynamic_filters(cols, nulls, valid)
        build = _assemble_build_side(
            self.input_types, self.key_channels, cols, nulls, valid,
            cap, dicts)
        self._pages = []  # release the input pages; only the index remains
        if self._ctx is not None:
            # retain what was published: the index — sorted key (8B) and
            # perm (4B) — over valid (1B) and the per-channel data/null
            # lanes as they arrived, and its dynamic filters' membership
            # tables
            retained = cap * (13 + sum(c.dtype.itemsize + 1 for c in cols)) \
                + sum(df.table_bytes for _, df in self.dynamic_filters)
            self._ctx.close()
            self._ctx.reserve(retained, revocable=False)
            self.bridge.release = self._ctx.close
        if self._hstate is not None and self._hstate.spilled_build:
            # memory is short and the cold partitions' passes probe
            # indexes of their own: the resident part keeps the searches
            build.direct_fallback = "hybrid partitions"
        else:
            _attach_direct_table(build, self._ctx)
        self._published = {"key_mode": build.key_mode,
                           "build_lanes": int(build.key_sorted.shape[0]),
                           "build_carried_cols": 0}
        self.bridge.set_build(build)

    def _index_bytes(self) -> int:
        """What sorting the accumulated pages takes beside their
        concatenation: ``_build_sorted``'s two operands and two results,
        (u64 key, int32 row) a lane each."""
        cap = padded_size(sum(p.capacity for p in self._pages))
        return 2 * cap * (8 + 4)

    def _collect_dynamic_filters(self, cols, nulls, valid):
        """Fill the join's dynamic filters over ALL build rows — the
        resident arrays plus every cold-partition page: a filter built
        from the resident set alone would wrongly prune probe rows that
        match only spilled build rows."""
        if not self.dynamic_filters:
            return
        hs = self._hstate
        spilled = []
        if hs is not None:
            with hs._lock:
                spilled = [p for ps in hs.spilled_build.values()
                           for p in ps]
        if not spilled:
            for ch, df in self.dynamic_filters:
                df.collect(cols[ch], nulls[ch], valid, self._ctx)
            return
        hosts = [p.host() for p in spilled]
        sv = np.concatenate([np.asarray(valid)]
                            + [h.valid for h in hosts])
        for ch, df in self.dynamic_filters:
            c = np.concatenate([np.asarray(cols[ch])]
                               + [h.cols[ch] for h in hosts])
            n = np.concatenate([np.asarray(nulls[ch])]
                               + [h.nulls[ch] for h in hosts])
            df.collect(c, n, sv)

    def _unified_dicts(self, pages):
        from ..block import unify_dictionaries

        return unify_dictionaries(pages, len(self.input_types))

    def is_finished(self) -> bool:
        return self._done


class LookupJoinOperator(Operator):
    """Probe side. join_type: inner | left | full | semi | anti.

    Output layout: all probe channels, then (inner/left/full) all build
    channels — build channels NULL on unmatched left rows. semi/anti emit
    probe channels only. FULL OUTER additionally OR-accumulates a
    matched flag per build row (in the build's arrival order) across all
    probe pages and, once the probe side finishes, emits one final page
    of unmatched build rows — the build's own columns under that mask —
    with NULL probe channels (reference: LookupJoinOperator's
    OuterLookupSource / buildOuter position iterator,
    operator/join/LookupJoinOperator.java:36)."""

    #: bound on candidate-expansion lanes per kernel launch: a probe page
    #: whose total match count pads beyond this is sliced into contiguous
    #: row chunks (greedy, from the per-row counts pulled to host ONCE)
    #: and joined one chunk per driver quantum, so skewed or high-fanout
    #: joins never materialize all pairs — neither in one buffer nor as a
    #: backlog of pending output pages (round-2 verdict: unbounded
    #: _expand_matches blows HBM at scale)
    max_lanes = 1 << 20

    #: probe pages whose lookup (candidate ranges, match total) is
    #: enqueued on the device and whose expansion waits for that total.
    #: The oldest page's total is read — ONE scalar, computed
    #: pipeline_depth-1 pages ago and thus long since done — only when
    #: the pipeline is full or upstream stalls, so the host never blocks
    #: on kernels it just enqueued (round-3 verdict:
    #: int(jnp.sum(count)) serialized host and device per probe page);
    #: the page is then expanded at ``padded_size(total)`` lanes, its
    #: matches' own width, whatever the probe page's was
    pipeline_depth = 4

    def __init__(self, probe_types: Sequence[T.Type],
                 probe_key_channels: Sequence[int], bridge: JoinBridge,
                 join_type: str = "inner",
                 filter_fn=None, max_lanes: Optional[int] = None,
                 memory_limited: bool = False):
        assert join_type in ("inner", "left", "full", "semi", "anti")
        self.probe_types = list(probe_types)
        self.probe_keys = list(probe_key_channels)
        self.bridge = bridge
        self.join_type = join_type
        self.filter_fn = filter_fn  # optional post-join residual filter
        if max_lanes is not None:
            self.max_lanes = max_lanes
        if memory_limited:
            # pool-governed query: the pending buffers are invisible to
            # the memory manager's reserve/revoke machinery, so keep the
            # pre-pipelining one-page-in-flight footprint
            self.pipeline_depth = 1
        self._pending: List[dict] = []   # looked up, awaiting expansion
        self._ready: List[DevicePage] = []
        self._added_since_get = False
        self._done = False
        #: deferred cold-partition work queue (hybrid join): None until
        #: the probe input finished, then [{"depth", "build", "probe"}]
        #: processed one partition per get_output call
        self._deferred: Optional[List[dict]] = None
        # FULL OUTER state: per-build-row matched flag in the build's
        # arrival order (device, cap+1 lanes — the last is the
        # dead-lane sink) + the dictionary
        # pools of the last probe page (the unmatched-build page's probe
        # channels are all-NULL, but string channels still need a pool)
        self._build_matched = None
        self._probe_dicts = None
        self._emitted_unmatched = False
        # probe-dict -> build-dict code remap LUTs for pooled join keys
        self._remap_cache: dict = {}
        #: probe pages looked up, those of them looked up in the
        #: build's direct-address table, and the build's table or why
        #: it has none (kept here: the bridge drops the build at finish)
        self._probe_pages = 0
        #: the probe pages' widths summed: what every kernel of the
        #: probe ran over, whatever share of the lanes held a row
        self._probe_lanes = 0
        self._direct_pages = 0
        self._direct_table_bytes = 0
        self._probe_fallback: Optional[str] = None
        #: the expansions' widths summed and the matches they held (each
        #: page's total, read once to size its expansion): plain adds
        self._expand_lanes = 0
        self._expand_rows = 0

    def metrics(self) -> dict:
        """Which probe ran: pages by lookup, the table's size or why
        the build has none (EXPLAIN ANALYZE, the operator span)."""
        out = {"join_type": self.join_type,
               "probe_pages": self._probe_pages,
               "probe_lanes": self._probe_lanes,
               "direct_probe_pages": self._direct_pages,
               "expand_lanes": self._expand_lanes,
               "expand_rows": self._expand_rows,
               # every expansion lane's sorted position is translated
               # to its arrival lane through the build's ``perm``: what
               # the probe pays for a build that carries no column
               "build_row_lanes": self._expand_lanes}
        if self.filter_fn is not None:
            # a residual predicate on the key: every expansion's lanes
            # were gathered from both sides and run through it
            out["residual_lanes"] = self._expand_lanes
            out["residual_rows"] = self._expand_rows
        if self._direct_table_bytes:
            out["direct_table_bytes"] = self._direct_table_bytes
        elif self._probe_fallback:
            out["probe_fallback"] = self._probe_fallback
        return out

    @property
    def output_types(self) -> List[T.Type]:
        b = self.bridge.build
        if self.join_type in ("semi", "anti"):
            return list(self.probe_types)
        return list(self.probe_types) + list(b.types)

    def needs_input(self) -> bool:
        return (not self._ready
                and len(self._pending) < self.pipeline_depth
                and not self._finishing)

    def add_input(self, page: DevicePage):
        """Enqueue what needs no size — the page's keys in the build's
        key space, each row's candidate range and the page's match
        total — WITHOUT reading anything back; the expansion is sized
        from that total in get_output, once the pipeline is deep enough
        to have hidden this page's latency."""
        b = self.bridge.build
        assert b is not None, "probe started before build finished"
        hs = self.bridge.hybrid
        if hs is not None and hs.spilled_build:
            # hybrid join: rows of cold build partitions park beside
            # their partition for the deferred unspill->probe pass;
            # null-key rows always stay resident (they match nothing
            # and LEFT/ANTI must emit them exactly once)
            page = self._route_probe(page, hs)
            if page is None:
                self._added_since_get = True
                return
        pkey_cols, pkey, pusable = self._probe_keys_u64(page, b)
        self._direct_table_bytes = b.direct.nbytes if b.direct else 0
        self._probe_fallback = b.direct_fallback
        lo, count = self._probe_lo_count(b, pkey, pusable)
        self._pending.append(
            _looked_up(b, page, pkey_cols, pusable, lo, count))
        self._added_since_get = True

    def _route_probe(self, page: DevicePage,
                     hs: HybridJoinState) -> Optional[DevicePage]:
        """Split one probe page by build partition: cold-partition rows
        spill beside their build partition, the rest probe the resident
        index now (valid-mask restriction — each probe row joins in
        exactly one pass)."""
        kc = self.probe_keys
        kcols = [np.asarray(page.cols[c]) for c in kc]
        knulls = [np.asarray(page.nulls[c]) for c in kc]
        ktypes = [self.probe_types[c] for c in kc]
        kdicts = [page.dictionaries[c] for c in kc]
        valid = np.asarray(page.valid)
        anynull = np.zeros_like(valid)
        for nl in knulls:
            anynull |= nl
        rowpid = hs.partition_ids(kcols, knulls, ktypes, kdicts)
        with hs._lock:
            cold_pids = np.fromiter(hs.spilled_build, dtype=np.int64)
        cold = valid & ~anynull & np.isin(rowpid, cold_pids)
        if not cold.any():
            return page
        hcols = [np.asarray(c) for c in page.cols]
        hnulls = [np.asarray(n) for n in page.nulls]
        ctx = hs.ctx
        for pid_ in np.unique(rowpid[cold]):
            pid_ = int(pid_)
            keep = np.nonzero(cold & (rowpid == pid_))[0]
            sp = _host_spilled(page.types, [c[keep] for c in hcols],
                               [n[keep] for n in hnulls], len(keep),
                               page.dictionaries)
            hs.add_probe_spill(pid_, sp)
            if ctx is not None:
                pool = ctx.pool
                pool.host_ledger.charge(sp)
                with ctx.lock:
                    pool.host_ledger.track(hs.spilled_probe[pid_],
                                           ctx.lock, pool)
                    pool.maybe_demote(hs.spilled_probe[pid_])
        res_valid = valid & ~cold
        if not res_valid.any():
            return None
        return DevicePage(page.types, page.cols, page.nulls,
                          jnp.asarray(res_valid), page.dictionaries)

    def _probe_lo_count(self, b: "BuildSide", pkey, pusable):
        """Each probe row's candidate range (lo, count) against the
        sorted build index — two gathers from the build's
        direct-address table where it has one, else two XLA-native
        vectorized binary searches."""
        if b.direct is not None:
            self._direct_pages += 1
            return _probe_direct_counts(b.direct.offsets, b.direct.span,
                                        pkey, pusable)
        return _probe_counts(b.key_sorted, pkey, pusable)

    def get_output(self):
        """The next joined page. The oldest looked-up page is expanded
        (its total read, its expansion and gathers enqueued at that
        size) once ``pipeline_depth`` pages are looked up, upstream
        stalls or the input has ended; then the hybrid join's parked
        partitions, then FULL OUTER's unmatched build rows."""
        if self._ready:
            return self._ready.pop(0)
        if self._pending and (self._finishing
                              or len(self._pending) >= self.pipeline_depth
                              or not self._added_since_get):
            self._expand(self._pending.pop(0))
            self._added_since_get = False
            if self._ready:
                return self._ready.pop(0)
        self._added_since_get = False
        if self._finishing and not self._pending:
            hs = self.bridge.hybrid
            if hs is not None and self._deferred is None:
                self._init_deferred(hs)
            while self._deferred and not self._ready:
                self._advance_deferred(hs)
            if self._ready:
                return self._ready.pop(0)
            if self.join_type == "full" and not self._emitted_unmatched:
                self._emitted_unmatched = True
                return self._unmatched_build_page()
            if not self._done:
                self.bridge.destroy()
            self._done = True
        return None

    def _expand(self, rec: dict):
        """Expand one looked-up page at its matches' own width: read the
        total (the deferred scalar; for a parked page of the hybrid
        pass, the one just enqueued) and run one expansion at
        ``padded_size(total)`` lanes, or row chunks under the lane
        budget where that passes it."""
        tot = int(host_read(rec["total"], "join_expand_total"))
        self._expand_rows += tot
        for *probe, lane_cap in self._chunk_units(rec, tot):
            self._expand_lanes += lane_cap
            out, keep, brow = self._make_out(rec["b"], *probe, lane_cap)
            self._mark_full(keep, brow, rec["page"].dictionaries)
            self._ready.append(out)

    def _chunk_units(self, rec: dict, total: int) -> List:
        """(page, pkey_cols, pusable, lo, count, lane_cap) units whose
        expansions fit the lane budget; greedy contiguous row chunks
        from the per-row counts (host copy only on this over-budget
        path). A single row exceeding the budget still becomes its own
        unit: out_cap grows to its fan-out, which no slicing avoids."""
        page, pkey_cols, pusable = rec["page"], rec["pkey_cols"], \
            rec["pusable"]
        lo, count = rec["lo"], rec["count"]
        if padded_size(max(total, 16)) <= self.max_lanes:
            return [(page, pkey_cols, pusable, lo, count,
                     padded_size(max(total, 16)))]
        counts = host_read(count, "join_chunk_counts")
        units: List = []
        n = counts.shape[0]
        i = 0
        while i < n:
            j = i
            run = 0
            while j < n and (j == i or
                             padded_size(max(run + int(counts[j]), 16))
                             <= self.max_lanes):
                run += int(counts[j])
                j += 1
            cap = padded_size(j - i)
            sl = slice(i, j)
            sub = DevicePage(page.types,
                             [_pad_dev(c[sl], cap) for c in page.cols],
                             [_pad_dev(x[sl], cap) for x in page.nulls],
                             _pad_dev(page.valid[sl], cap),
                             page.dictionaries)
            units.append((sub, [_pad_dev(k[sl], cap) for k in pkey_cols],
                          _pad_dev(pusable[sl], cap),
                          _pad_dev(lo[sl], cap), _pad_dev(count[sl], cap),
                          padded_size(max(run, 16))))
            i = j
        return units

    # -- hybrid: deferred cold-partition passes --------------------------

    def _init_deferred(self, hs: HybridJoinState):
        """Snapshot the cold-partition work queue once the probe input
        finished (the resident set is frozen after build publish, so
        the snapshot is race-free)."""
        with hs._lock:
            pids = sorted(set(hs.spilled_build) | set(hs.spilled_probe))
            self._deferred = [
                {"depth": hs.depth,
                 "build": list(hs.spilled_build.get(pid, ())),
                 "probe": list(hs.spilled_probe.get(pid, ()))}
                for pid in pids]

    def _advance_deferred(self, hs: HybridJoinState):
        """Unspill one cold partition and probe it: build a
        per-partition sorted index from the parked build pages, then
        run every parked probe page against it.  A partition whose
        index would not fit the pool repartitions with a depth-salted
        hash instead (children joined depth-first, recursion bounded
        by hybrid_join_max_depth)."""
        from ..exec.memory import MemoryExceededError, device_page_bytes

        entry = self._deferred.pop(0)
        if not entry["probe"]:
            # probe-driven join types only (FULL OUTER never goes
            # hybrid): no parked probe rows means no output
            return
        ctx = hs.ctx
        est = sum(device_page_bytes(p) for p in entry["build"])
        # index + sort transients ~4x the partition bytes; an oversized
        # partition repartitions rather than thrash the pool
        need = 4 * max(est, 1)
        if ctx is not None and entry["depth"] < hs.max_depth \
                and need > ctx.pool.max_bytes:
            self._split_deferred(hs, entry)
            return
        if ctx is not None:
            try:
                ctx.reserve(need, revocable=False)
            except MemoryExceededError:
                if entry["depth"] < hs.max_depth:
                    self._split_deferred(hs, entry)
                    return
                raise
        try:
            b = self.bridge.build
            bp = _build_side_from_spilled(
                b.types, b.key_channels, entry["build"]) \
                if entry["build"] else self._empty_build_side(b)
            for sp in entry["probe"]:
                self._probe_spilled_page(bp, sp)
        finally:
            if ctx is not None:
                ctx.free(need, revocable=False)

    def _split_deferred(self, hs: HybridJoinState, entry: dict):
        """Recursive repartition: re-hash the partition's build AND
        probe pages at depth+1 with a fresh salt; children go to the
        FRONT of the queue (depth-first keeps the parked-page peak
        bounded by one partition's lineage)."""
        depth = entry["depth"] + 1
        hs.note_depth(depth)
        salt = _salt_for_depth(depth)
        sub_fanout = 4  # quarters per level: depth 3 = 64x the fan-out
        b = self.bridge.build
        bsplit = self._split_spilled(hs, entry["build"], b.types,
                                     b.key_channels, salt, sub_fanout)
        psplit = self._split_spilled(hs, entry["probe"],
                                     self.probe_types, self.probe_keys,
                                     salt, sub_fanout)
        for q in sorted(set(bsplit) | set(psplit), reverse=True):
            self._deferred.insert(0, {
                "depth": depth,
                "build": bsplit.get(q, []),
                "probe": psplit.get(q, [])})

    def _split_spilled(self, hs: HybridJoinState, pages: List, types_,
                       key_channels, salt: int, fanout: int) -> dict:
        """Partition parked pages by a re-salted key hash (host work;
        disk-parked pages stream back through host())."""
        buckets: dict = {}
        for p in pages:
            h = p.host()
            kcols = [h.cols[c] for c in key_channels]
            knulls = [h.nulls[c] for c in key_channels]
            ktypes = [types_[c] for c in key_channels]
            kdicts = [h.dictionaries[c] for c in key_channels]
            rowpid = hs.partition_ids(kcols, knulls, ktypes, kdicts,
                                      salt=salt, fanout=fanout)
            for q in np.unique(rowpid[h.valid]):
                q = int(q)
                keep = np.nonzero(h.valid & (rowpid == q))[0]
                buckets.setdefault(q, []).append(_host_spilled(
                    h.types, [c[keep] for c in h.cols],
                    [n[keep] for n in h.nulls], len(keep),
                    h.dictionaries))
        return buckets

    def _empty_build_side(self, b: "BuildSide") -> "BuildSide":
        """A zero-row index (recursive splits can leave a probe-only
        sub-bucket; LEFT/ANTI must still emit its rows unmatched)."""
        from ..block import Dictionary

        cap = 16
        cols = [jnp.zeros(cap, dtype=t.storage) for t in b.types]
        nulls = [jnp.ones(cap, dtype=bool) for _ in b.types]
        valid = jnp.zeros(cap, dtype=bool)
        dicts = [Dictionary() if t.is_pooled else None for t in b.types]
        return _assemble_build_side(b.types, b.key_channels, cols,
                                    nulls, valid, cap, dicts)

    def _probe_spilled_page(self, b: "BuildSide", sp):
        """One parked probe page against one per-partition index, by
        the two binary searches: a re-indexed partition carries no
        direct-address table."""
        page = sp.to_device()
        pkey_cols, pkey, pusable = self._probe_keys_u64(page, b)
        lo, count = _probe_counts(b.key_sorted, pkey, pusable)
        self._expand(_looked_up(b, page, pkey_cols, pusable, lo, count))

    def _mark_full(self, keep, build_row, pdicts):
        """FULL OUTER bookkeeping: OR an expansion's kept lanes into the
        per-build-row matched flags."""
        if self.join_type != "full" or keep is None:
            return
        b = self.bridge.build
        bcap = int(b.valid.shape[0])
        if self._build_matched is None:
            self._build_matched = jnp.zeros(bcap + 1, dtype=bool)
        self._build_matched = _mark_build_matched(
            self._build_matched, keep, build_row)
        self._probe_dicts = pdicts

    def _unmatched_build_page(self) -> DevicePage:
        """FULL OUTER tail: build rows no kept lane ever matched, with
        all probe channels NULL — the build's own columns under another
        mask, nothing gathered."""
        from ..block import Dictionary

        b = self.bridge.build
        cap = int(b.valid.shape[0])
        unmatched = b.valid if self._build_matched is None \
            else b.valid & ~self._build_matched[:cap]
        pcols = [jnp.zeros(cap, dtype=t.storage) for t in self.probe_types]
        pnulls = [jnp.ones(cap, dtype=bool) for _ in self.probe_types]
        pdicts = self._probe_dicts
        if pdicts is None:
            pdicts = [Dictionary() if t.is_pooled else None
                      for t in self.probe_types]
        return DevicePage(self.output_types, pcols + list(b.cols),
                          pnulls + list(b.nulls), unmatched,
                          list(pdicts) + list(b.dictionaries))

    def is_finished(self) -> bool:
        return self._done

    def _remap(self, probe_dict, build_dict):
        """Probe-pool code -> build-pool code LUT (-1 = absent, matches
        nothing; always canonical first-occurrence codes, so aligned
        pools with duplicate values compare correctly). Host work once
        per (probe pool, build pool) pair; the gather runs on device.
        The cache entry pins both dict objects: bare id() keys would go
        stale if a pool were GC'd and its address reused."""
        key = (id(probe_dict), len(probe_dict) if probe_dict else 0,
               id(build_dict), len(build_dict) if build_dict else 0)
        hit = self._remap_cache.get(key)
        if hit is not None:
            return hit[0]
        if build_dict is None:
            lut = np.full(max(1, len(probe_dict or ())), -1,
                          dtype=np.int64)
        else:
            lut = np.fromiter(
                (build_dict.lookup(v) for v in probe_dict.values),
                dtype=np.int64,
                count=len(probe_dict)) if probe_dict and \
                len(probe_dict) else np.full(1, -1, dtype=np.int64)
        lut = jnp.asarray(lut)
        if len(self._remap_cache) >= 128:  # evict BEFORE inserting
            self._remap_cache.clear()
        self._remap_cache[key] = (lut, probe_dict, build_dict)
        return lut

    def _probe_key_cols(self, page: DevicePage, b: "BuildSide"):
        """Per key channel: the probe column transformed into the build's
        key space (identity for unpooled types; canonical code remap for
        pooled keys — also when pools are shared, since an aligned pool
        may hold duplicate values under distinct codes)."""
        out = []
        types_ = []
        for i, c in enumerate(self.probe_keys):
            t = self.probe_types[c]
            if t.is_pooled:
                pd = page.dictionaries[c]
                bd = b.dictionaries[b.key_channels[i]]
                out.append(self._remap(pd, bd)[page.cols[c]])
                types_.append(T.BIGINT)
            else:
                out.append(page.cols[c])
                types_.append(t)
        return out, types_

    def _probe_keys_u64(self, page: DevicePage, b: "BuildSide"):
        """(key columns in the build's key space, their u64 key, the
        rows that can match) of one probe page, counted as looked up."""
        pkey_cols, key_types = self._probe_key_cols(page, b)
        pkey, panynull = _key_u64(pkey_cols,
                                  [page.nulls[c] for c in self.probe_keys],
                                  key_types, b.key_mode)
        pusable = page.valid & ~panynull if panynull is not None \
            else page.valid
        self._probe_pages += 1
        self._probe_lanes += int(page.valid.shape[0])
        return pkey_cols, pkey, pusable

    def _make_out(self, b: "BuildSide", page: DevicePage, pkey_cols,
                  pusable, lo, count, lane_cap: int) -> Tuple:
        """One expansion at static capacity ``lane_cap`` against build
        side ``b`` (the resident index, or a per-partition index during
        the deferred hybrid pass): returns (out_page, keep, build_row).
        keep/build_row (the lanes' build rows in arrival order) feed
        the FULL OUTER marker and are None for semi/anti (no build
        channels in the output)."""
        if self.join_type in ("semi", "anti"):
            if self.filter_fn is None:
                matched = _semi_matched(
                    lo, count, b.perm,
                    tuple(pkey_cols),
                    tuple(b.cols[c] for c in b.key_channels),
                    page.valid.shape[0], out_cap=lane_cap)
            else:
                # residual-filtered semi/anti (q21's l3.l_suppkey <>
                # l1.l_suppkey): expand candidate lanes, verify keys,
                # evaluate the filter over the combined probe+build row,
                # then segment-OR back onto probe rows
                probe_idx, build_row, keep = _expand_verified(
                    lo, count, b.perm,
                    tuple(pkey_cols),
                    tuple(b.cols[c] for c in b.key_channels),
                    out_cap=lane_cap)
                lanes = _gather_lanes(page, b, probe_idx, build_row, keep)
                matched = _segment_any(self.filter_fn(lanes).valid,
                                       probe_idx, page.valid.shape[0])
            if self.join_type == "semi":
                new_valid = page.valid & matched
            else:
                new_valid = page.valid & ~matched
            return (DevicePage(page.types, page.cols, page.nulls,
                               new_valid, page.dictionaries), None, None)

        probe_idx, build_row, keep = _expand_verified(
            lo, count, b.perm,
            tuple(pkey_cols),
            tuple(b.cols[c] for c in b.key_channels), out_cap=lane_cap)
        if self.filter_fn is not None:
            # ON-clause residual runs BEFORE left-join padding: lanes
            # failing it make the probe row unmatched, not dropped
            lanes = _gather_lanes(page, b, probe_idx, build_row, keep)
            keep = self.filter_fn(lanes).valid
        out_cols, out_nulls, out_valid = _finalize_join(
            tuple(page.cols), tuple(page.nulls), page.valid,
            tuple(b.cols), tuple(b.nulls),
            probe_idx, build_row, keep,
            left=self.join_type in ("left", "full"))
        types = self.output_types
        dicts = list(page.dictionaries) + list(b.dictionaries)
        return (DevicePage(types, list(out_cols), list(out_nulls),
                           out_valid, dicts), keep, build_row)


def _looked_up(b: "BuildSide", page: DevicePage, pkey_cols, pusable, lo,
               count) -> dict:
    """A probe page with its candidate ranges and its match total on the
    device: what ``LookupJoinOperator._expand`` sizes an expansion from."""
    return {"b": b, "page": page, "pkey_cols": pkey_cols,
            "pusable": pusable, "lo": lo, "count": count,
            "total": jnp.sum(count)}


def _finalize_join_impl(pcols, pnulls, pvalid, bcols, bnulls,
                        probe_idx, build_row, keep, left: bool):
    """Gather joined output lanes (``build_row``: the build's arrival
    lanes, where ``bcols`` lie); for LEFT, append one lane per probe
    row, valid iff the row matched no kept lane (NULL build columns).

    Raw implementation (see ``_probe_counts_impl``); host callers use
    the jitted ``_finalize_join`` binding below."""
    lane_cap = probe_idx.shape[0]
    if left:
        matched = _segment_any_impl(keep, probe_idx, pvalid.shape[0])
        n_extra = pvalid.shape[0]
        extra_probe = jnp.arange(n_extra, dtype=probe_idx.dtype)
        probe_idx = jnp.concatenate([probe_idx, extra_probe])
        build_row = jnp.concatenate(
            [build_row, jnp.zeros(n_extra, dtype=build_row.dtype)])
        keep = jnp.concatenate([keep, pvalid & ~matched])
        build_is_null = jnp.concatenate(
            [jnp.zeros(lane_cap, dtype=bool),
             jnp.ones(n_extra, dtype=bool)])
    else:
        build_is_null = jnp.zeros(lane_cap, dtype=bool)

    out_cols = tuple(c[probe_idx] for c in pcols) + \
        tuple(c[build_row] for c in bcols)
    out_nulls = tuple(n[probe_idx] for n in pnulls) + \
        tuple(n[build_row] | build_is_null for n in bnulls)
    return out_cols, out_nulls, keep


_finalize_join = partial(jax.jit, static_argnames=("left",))(
    _finalize_join_impl)


def _gather_lanes(page: DevicePage, b: "BuildSide", probe_idx, build_row,
                  keep) -> DevicePage:
    """Combined probe+build rows for candidate lanes (residual-filter
    evaluation layout: probe channels, then build channels)."""
    return DevicePage(
        list(page.types) + list(b.types),
        [c[probe_idx] for c in page.cols]
        + [c[build_row] for c in b.cols],
        [n[probe_idx] for n in page.nulls]
        + [n[build_row] for n in b.nulls],
        keep,
        list(page.dictionaries) + list(b.dictionaries))


def _expand_verified_impl(lo, count, perm, pkey_cols, bkey_cols,
                          out_cap: int):
    """Candidate lanes (probe_idx, build_row, keep) with raw-key
    verification applied; ``bkey_cols`` lie in the build's arrival
    order, where ``build_row`` points.

    Raw implementation (see ``_probe_counts_impl``); host callers use
    the jitted ``_expand_verified`` binding below."""
    probe_idx, build_row, keep = _expand_matches_impl(
        lo, count, perm, out_cap)
    for pc, bc in zip(pkey_cols, bkey_cols):
        keep = keep & (pc[probe_idx] == bc[build_row])
    return probe_idx, build_row, keep


_expand_verified = partial(jax.jit, static_argnames=("out_cap",))(
    _expand_verified_impl)


@jax.jit
def _mark_build_matched(acc, keep, build_row):
    """OR kept lanes into the per-build-row matched accumulator, in the
    build's arrival order like its ``valid`` (last lane of ``acc`` is
    the dead-lane sink)."""
    sink = acc.shape[0] - 1
    return acc.at[jnp.where(keep, build_row, sink)].max(True)


def _segment_any_impl(keep, probe_idx, probe_cap: int):
    """OR of ``keep`` lanes per probe row."""
    matched = jnp.zeros(probe_cap + 1, dtype=bool)
    matched = matched.at[jnp.where(keep, probe_idx, probe_cap)].max(True)
    return matched[:-1]


_segment_any = partial(jax.jit, static_argnames=("probe_cap",))(
    _segment_any_impl)


def _semi_matched_impl(lo, count, perm, pkey_cols, bkey_cols,
                       probe_cap: int, out_cap: int):
    """Per-probe-row matched flag: expand candidates, verify raw keys,
    segment-OR back onto probe rows (collision-safe for any key mode).

    Raw implementation (see ``_probe_counts_impl``); host callers use
    the jitted ``_semi_matched`` binding below."""
    probe_idx, _, keep = _expand_verified_impl(
        lo, count, perm, pkey_cols, bkey_cols, out_cap)
    return _segment_any_impl(keep, probe_idx, probe_cap)


_semi_matched = partial(jax.jit, static_argnames=("probe_cap", "out_cap"))(
    _semi_matched_impl)


def _pad_dev(arr, cap: int):
    """Pad a device array slice to cap lanes with zeros/False (padding
    lanes are dead: valid False, count 0)."""
    n = arr.shape[0]
    if n == cap:
        return arr
    return jnp.concatenate(
        [arr, jnp.zeros((cap - n,), dtype=arr.dtype)])


def _np_pad(arr: np.ndarray, cap: int, fill: bool = False) -> np.ndarray:
    n = arr.shape[0]
    if n == cap:
        return arr
    out = np.full(cap, fill, dtype=bool) if arr.dtype == bool \
        else np.zeros(cap, dtype=arr.dtype)
    out[:n] = arr
    return out


def _pad_concat(arrays, cap: int, fill: bool = False):
    cat = jnp.concatenate(list(arrays))
    n = cat.shape[0]
    if n == cap:
        return cat
    pad = jnp.full((cap - n,), fill, dtype=cat.dtype) if cat.dtype == bool \
        else jnp.zeros((cap - n,), dtype=cat.dtype)
    return jnp.concatenate([cat, pad])
