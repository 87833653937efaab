"""MXU-native matmul join: the probe as a blocked one-hot matmul.

Reference analog: "Density-optimized Intersection-free Mapping and
Matrix Multiplication for Join-Project Operations" (PAPERS.md,
arXiv 2206.04995) — equi-join over low-NDV keys expressed as dense
matrix products over one-hot key encodings, with a density-optimized
mapping of the (sparse) key domain onto matrix indices.

Adaptation to this engine's join machinery (``ops/join.py``):

- **Mapping** (the paper's density-optimized, intersection-free map):
  keys normalize to order-preserving uint64 (the build side already
  did, for its sorted index), and the observed build key range
  ``[klo, khi]`` maps identically onto dense codes ``key - klo``.
  Chosen by the COST MODEL from connector NDV/min-max stats
  (``planner/optimizer.choose_join_strategy``); the operator re-checks
  the actual range at build time and falls back to the sorted-index
  probe when the mapping would not be dense enough.  Dictionary-coded
  (string/composite) keys are already dense codes in the build's pool,
  so the same range map covers them with no special case.
- **Build aggregate matrix**: a one-time ``(K, 2)`` table over the key
  domain — ``cnt[k]`` (build rows with code k) and ``first[k]`` (their
  first position in the code-sorted build).  Because the u64 map is
  monotone, the existing sorted build index IS code-sorted, and
  ``(first, cnt)`` are bit-identical to the oracle's two
  ``searchsorted`` results.
- **Probe** (the hot path, per page): blocked one-hot encode the probe
  codes and one f32 matmul against the build table yields ``(count,
  lo)`` per probe row — the MXU replaces the binary-search gather
  chase.  f32 accumulation is EXACT: each one-hot row has exactly one
  nonzero lane and table values stay below 2^24 (build size is gated).
  Semi/anti joins finish right there (``matched = count > 0`` — the
  paper's join-project-as-matmul membership); inner/left joins feed
  the byte-identical (lo, count) into the shared candidate-expansion
  and finalize kernels of the sorted-index operator.

Static one-hot width (the jit cache key) rides ``KERNEL_SIZING`` so
repeat queries with a jittering key range reuse the compiled program.

Batched execution (round 17): ``exec/batched.py`` probes every join —
matmul-strategy or not — through the shared sorted-index impls
(``_probe_counts_impl`` et al.) under one ``jit(vmap(...))`` program.
That is sound precisely because of the bit-identity above: ``(lo,
count)`` from the matmul probe equals the sorted-index result byte for
byte, so a burst may ride the masked sorted-index lane while the
serial path keeps the MXU probe, with byte-equal demuxed pages.
"""

from __future__ import annotations

from functools import partial
from typing import Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from .. import jit_stats
from .. import types as T
from ..block import DevicePage
from ..telemetry.profiler import instrument
from ..telemetry.tracing import host_read
from .join import (_U64_SENTINEL, BuildSide, JoinBridge,
                   LookupJoinOperator, _key_span)
from .kernel_sizing import KERNEL_SIZING

#: default cap on the dense key domain (``matmul_join_max_key_range``):
#: the one-hot width, i.e. per-probe-row MACs — the density knob that
#: bounds the matmul's O(rows * range) work to its low-NDV win region
DEFAULT_MAX_KEY_RANGE = 1024

#: builds past this lose f32-exact counts/positions (2^24) — THE one
#: definition; the cost model (planner/optimizer.choose_join_strategy)
#: imports it so planner estimate and operator re-check cannot drift
MAX_BUILD_ROWS = 1 << 24

#: probe-row / key-domain block sizes of the one-hot matmul (pow2, so
#: they divide every padded page capacity and table width)
_MB = 1024
_KB = 512


@partial(jax.jit, static_argnames=("kp",))
def _build_code_table(key_sorted, klo, k_range, kp: int):
    """The (kp, 2) f32 build aggregate matrix over dense key codes:
    column 0 = cnt[k] (usable build rows with code k), column 1 =
    first[k] (their first sorted position).  One-time per build; codes
    beyond the observed range (padding lanes) hold zeros.  Bit-equal to
    the oracle's searchsorted pair: unusable rows sort to the u64
    sentinel, past every in-range probe value."""
    jit_stats.bump("matmul_join_build_table")
    codes = jnp.arange(kp, dtype=jnp.uint64)
    ks = klo + codes  # wraps past k_range; masked below
    lo = jnp.searchsorted(key_sorted, ks, side="left")
    hi = jnp.searchsorted(key_sorted, ks, side="right")
    live = codes < k_range
    cnt = jnp.where(live, hi - lo, 0)
    first = jnp.where(live, lo, 0)
    return jnp.stack([cnt, first], axis=1).astype(jnp.float32)


# profiled entry points (telemetry.profiler): cost/compile
# attribution under EXPLAIN ANALYZE VERBOSE; plain calls when off
_build_code_table = instrument("matmul_join_build_table",
                               _build_code_table,
                               static_argnames=("kp",))


def _blocked_onehot_matmul(codes, table):
    """(m, C) = OneHot(codes) @ table, blocked (_MB x _KB): out[i, :] =
    table[codes[i], :] computed as dense f32 dots — the MXU form of the
    probe (codes == kp select the all-zero no-match row).  HIGHEST
    precision keeps f32 matmuls off the MXU's bf16 passes so integer
    payloads below 2^24 stay exact."""
    m = codes.shape[0]
    kp, c = table.shape
    mb, kb = min(m, _MB), min(kp, _KB)
    n_mb, n_kb = m // mb, kp // kb
    lanes = jnp.arange(kb, dtype=codes.dtype)

    def body(g, acc):
        mi, ki = g // n_kb, g % n_kb
        c_blk = jax.lax.dynamic_slice(codes, (mi * mb,), (mb,))
        t_blk = jax.lax.dynamic_slice(table, (ki * kb, 0), (kb, c))
        onehot = (c_blk[:, None] == ki * kb + lanes[None, :]).astype(
            jnp.float32)
        part = jnp.dot(onehot, t_blk,
                       precision=jax.lax.Precision.HIGHEST)
        cur = jax.lax.dynamic_slice(acc, (mi * mb, 0), (mb, c))
        return jax.lax.dynamic_update_slice(acc, cur + part, (mi * mb, 0))

    acc = jnp.zeros((m, c), dtype=jnp.float32)
    return jax.lax.fori_loop(0, n_mb * n_kb, body, acc)


@jax.jit
def _matmul_lo_count(pkey, pusable, klo, k_range, table):
    """Per-probe-row (lo, count) via the blocked one-hot matmul —
    byte-identical to ``join._probe_counts`` for every usable row
    (dead/unmatched rows get count 0 and a clamped lo no kernel
    reads)."""
    jit_stats.bump("matmul_join_probe")
    kp = table.shape[0]
    off = pkey - klo  # u64: wraps below klo -> huge -> out of range
    in_range = pusable & (off < k_range)
    codes = jnp.where(in_range, off,
                      jnp.uint64(kp)).astype(jnp.int32)
    out = _blocked_onehot_matmul(codes, table)
    # int64, matching the searchsorted oracle: a high-fanout page's
    # count SUM must not wrap int32 in the expansion cumsum
    count = out[:, 0].astype(jnp.int64)
    lo = out[:, 1].astype(jnp.int64)
    return lo, count


_matmul_lo_count = instrument("matmul_join_probe", _matmul_lo_count)


@partial(jax.jit, static_argnames=("anti",))
def _membership_page_valid(valid, count, anti: bool):
    """Semi/anti output mask straight from the matmul counts (exact
    codes: count > 0 IS raw-key membership, no expansion or verify)."""
    jit_stats.bump("matmul_join_membership")
    matched = count > 0
    return valid & ~matched if anti else valid & matched


_membership_page_valid = instrument(
    "matmul_join_membership", _membership_page_valid,
    static_argnames=("anti",))


class MatmulJoinOperator(LookupJoinOperator):
    """The matmul strategy: identical operator contract and output to
    ``LookupJoinOperator`` (it IS one), with the probe's candidate
    lookup replaced by the blocked one-hot matmul and semi/anti
    finishing directly on the membership counts.  Falls back to the
    inherited sorted-index probe — per build, with the reason surfaced
    in metrics — whenever the density map is infeasible (multi-key
    build, empty/oversized build, key range past ``max_key_range``)."""

    def __init__(self, probe_types: Sequence[T.Type],
                 probe_key_channels: Sequence[int], bridge: JoinBridge,
                 join_type: str = "inner", filter_fn=None,
                 max_lanes: Optional[int] = None,
                 memory_limited: bool = False,
                 max_key_range: int = DEFAULT_MAX_KEY_RANGE,
                 strategy_detail: str = ""):
        super().__init__(probe_types, probe_key_channels, bridge,
                         join_type, filter_fn, max_lanes, memory_limited)
        self.max_key_range = max_key_range
        #: the cost-model estimate that picked this strategy (rendered
        #: into EXPLAIN ANALYZE next to what actually ran)
        self.strategy_detail = strategy_detail
        self._mm = None  # (klo u64, k_range u64, table) once built
        self._fallback_reason: Optional[str] = None

    def metrics(self) -> dict:
        out = super().metrics()
        out["strategy"] = "matmul" if self._fallback_reason is None \
            else "matmul->sorted-index"
        if self._fallback_reason is not None:
            out["fallback"] = self._fallback_reason
        elif self._mm is not None:
            out["key_range"] = int(self._mm[1])
            out["onehot_width"] = int(self._mm[2].shape[0])
        if self.strategy_detail:
            out["estimate"] = self.strategy_detail
        return out

    def _ensure_table(self, b: BuildSide) -> bool:
        """Build the (K, 2) aggregate matrix once per build; False =>
        fall back to the inherited sorted-index probe."""
        if self._mm is not None:
            return True
        if self._fallback_reason is not None:
            return False
        reason = None
        klo = khi = np.uint64(0)
        if b.key_mode != "single":
            reason = f"{b.key_mode} key mode (needs one equi key)"
        else:
            # the build's own span where its direct-address table
            # left one on the device: one blocking read either way
            span = b.direct.span if b.direct is not None \
                else _key_span(b.key_sorted, b.usable_sorted)
            n_usable, klo, khi = host_read(span, "matmul_join_key_range")
            n_usable = int(n_usable)
            if n_usable == 0:
                reason = "empty build"
            elif n_usable > MAX_BUILD_ROWS:
                reason = f"build {n_usable} rows > f32-exact bound"
            elif khi == _U64_SENTINEL:
                reason = "key at the u64 sentinel"
            elif int(khi - klo) + 1 > self.max_key_range:
                reason = (f"key range {int(khi - klo) + 1} > "
                          f"max {self.max_key_range}")
        if reason is not None:
            self._fallback_reason = reason
            return False
        k_range = int(khi - klo) + 1
        # history key = the JOIN's shape, not just the key type: the
        # probe layout + the planner's estimate string distinguish
        # unrelated joins (whose ranges would otherwise contaminate one
        # another's EWMA) while staying stable across repeat queries
        key_t = self.probe_types[self.probe_keys[0]]
        shape_key = ("matmul-join", str(key_t),
                     tuple(str(t) for t in self.probe_types),
                     tuple(self.probe_keys), self.strategy_detail)
        kp = KERNEL_SIZING.suggest(shape_key, k_range, minimum=_KB)
        table = _build_code_table(b.key_sorted, klo,
                                  np.uint64(k_range), kp=kp)
        self._mm = (klo, np.uint64(k_range), table)
        return True

    # -- the strategy seams of LookupJoinOperator ----------------------

    def _probe_direct(self, page: DevicePage, b: BuildSide, pkey,
                      pusable) -> Optional[DevicePage]:
        """Semi/anti without a residual filter: membership IS the
        matmul count — emit the masked page with no expansion at all."""
        if self.join_type not in ("semi", "anti") \
                or self.filter_fn is not None \
                or not self._ensure_table(b):
            return None
        klo, k_range, table = self._mm
        _lo, count = _matmul_lo_count(pkey, pusable, klo, k_range, table)
        valid = _membership_page_valid(page.valid, count,
                                       anti=self.join_type == "anti")
        return DevicePage(page.types, page.cols, page.nulls, valid,
                          page.dictionaries)

    def _probe_lo_count(self, b: BuildSide, pkey, pusable):
        if not self._ensure_table(b):
            return super()._probe_lo_count(b, pkey, pusable)
        klo, k_range, table = self._mm
        return _matmul_lo_count(pkey, pusable, klo, k_range, table)
