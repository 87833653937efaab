"""Pallas TPU kernels for the hot grouping path.

SURVEY.md §7 names the group-by scatter ("segment reduce over sorted
group ids") as the one native kernel of the build: it sits under every
GROUP BY (ops/aggregation._group_reduce) and under the distinct /
first-row machinery. Reference analog: the row-at-a-time update loops of
``operator/MultiChannelGroupByHash.java:199-294`` and
``operator/aggregation/*Accumulator`` — redesigned here for the TPU
memory system instead of translated.

Kernel design (TPU-first, not a scatter):
  After the engine's bucket sort, group ids are NON-DECREASING WITH
  STEPS OF AT MOST 1 (they are a cumsum of boundary bits). So a chunk of
  C consecutive rows touches at most C consecutive segments, and every
  contribution of chunk i lands inside a single 128-aligned window of
  the output that starts at ``align_down(gid[i*C])``. That turns the
  scatter-add into:
    - grid over row chunks (sequential on a TensorCore, so read-modify-
      write accumulation into the output block is race-free),
    - per chunk, a one-hot (C x W) binning matrix against the window,
    - SUM: two MXU matmuls on a hi/lo 16-bit split (exact for int32 and
      for float32 inputs that are int-valued), or one for floats,
    - MIN/MAX: masked VPU reduce over the same one-hot,
    - one dynamic-slice update of the aligned window — contiguous, tile-
      aligned, no scatter unit needed.
  The scalar-prefetch operand carries each chunk's window start so the
  index map / store offset is known before the chunk's data arrives.

Dispatch: ``segment_reduce`` uses the Pallas kernel when the default
backend is TPU (or when TRINO_TPU_PALLAS forces it — tests run it in
interpret mode on CPU) and the dtype is int32/float32; anything else
takes the identical-semantics ``jax.ops.segment_*`` path. Both paths are
cross-checked in tests/test_pallas_kernels.py.
"""

from __future__ import annotations

import functools
import os

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..telemetry.profiler import instrument

_CHUNK = 512          # rows per grid step
_LANE = 128           # TPU lane width: window starts are lane-aligned
_WIN = _CHUNK + _LANE  # aligned window covering any chunk's segments


def pallas_mode() -> str:
    """'tpu' (compiled), 'interpret' (forced, CPU), or '' (disabled)."""
    # trace-static mode switch: read once per compile, by design
    forced = os.environ.get(  # qlint: ignore[trace-purity, cache-coherence] trace-static process-mode knob, read once per compile by design
        "TRINO_TPU_PALLAS", "")
    if forced in ("0", "off"):
        return ""
    if jax.default_backend() == "tpu":
        return "tpu"
    if forced:
        return "interpret"
    return ""


#: dtypes the compiled TPU kernel handles; 64-bit dtypes additionally
#: run under interpret mode (CPU tests with x64 — on TPU hardware f64
#: does not exist and the engine runs 32-bit storage)
_SUPPORTED = ("int32", "float32")
_SUPPORTED_INTERPRET = _SUPPORTED + ("int64", "float64", "uint64")

#: largest ``num_segments`` the kernel takes. Its output block stays
#: whole in VMEM across the grid; the v5e compiler accepts that for a
#: pow2 group table of 2^23 (+ the dump segment) and refuses 2^24
#: (scoped vmem). Larger reductions take ``jax.ops.segment_*`` — a
#: shape-static choice made at trace time, on every backend.
_MAX_SEGMENTS = (1 << 23) + 1

_IDENTITY = {
    ("sum", "int32"): 0,
    ("sum", "float32"): 0.0,
    ("sum", "int64"): 0,
    ("sum", "uint64"): 0,
    ("sum", "float64"): 0.0,
    ("min", "int32"): np.iinfo(np.int32).max,
    ("min", "float32"): np.inf,
    ("min", "int64"): np.iinfo(np.int64).max,
    ("min", "uint64"): np.iinfo(np.uint64).max,
    ("min", "float64"): np.inf,
    ("max", "int32"): np.iinfo(np.int32).min,
    ("max", "float32"): -np.inf,
    ("max", "int64"): np.iinfo(np.int64).min,
    ("max", "uint64"): 0,
    ("max", "float64"): -np.inf,
}

#: process-wide count of kernel executions (test observability)
kernel_calls = 0


def _kernel(starts_ref, col_ref, gid_ref, out_ref, *, kind: str,
            dtype: str):
    i = pl.program_id(0)
    ident = _IDENTITY[(kind, dtype)]

    @pl.when(i == 0)
    def _init():
        out_ref[:] = jnp.full(out_ref.shape, ident, out_ref.dtype)

    # window starts are lane-aligned by construction (see the caller);
    # Mosaic needs the hint to prove the dynamic slice is tile-aligned
    start = pl.multiple_of(starts_ref[i], _LANE)
    col = col_ref[0, 0, :]                   # (C,)
    local = gid_ref[0, 0, :] - start         # (C,) window offsets
    # one-hot binning matrix: onehot[r, w] == row r feeds window slot w.
    # wslots spans exactly [0, _WIN), so rows outside the window match
    # no slot — no separate in-window mask (Mosaic cannot reshape a
    # 1-D bool vector to a column)
    wslots = jax.lax.broadcasted_iota(jnp.int32, (_CHUNK, _WIN), 1)
    onehot = local[:, None] == wslots

    if kind == "sum":
        if dtype in ("int64", "uint64", "float64"):
            # interpret-mode-only path (64-bit never reaches the TPU
            # kernel): masked add keeps int64 sums exact
            contrib = jnp.where(onehot, col[:, None],
                                jnp.asarray(0, col.dtype))
            win = jnp.sum(contrib, axis=0)
        elif dtype == "int32":
            # exact int32 via three f32 MXU passes on a 12/12/8-bit
            # split: every per-chunk part-sum is bounded by C * 2^12 =
            # 2^21 (lo/mid) or C * 2^7 = 2^16 (hi), all far inside
            # f32's 2^24 exact-integer range
            oh = onehot.astype(jnp.float32)

            def dot(v):
                # HIGHEST precision: the default lowers f32 MXU matmuls
                # to bf16 passes whose 8-bit mantissa would round the
                # 12-bit parts — the exactness argument needs true f32
                return jax.lax.dot_general(
                    v[None, :], oh, (((1,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32,
                    precision=jax.lax.Precision.HIGHEST)[0]

            # int32 scalars, not Python ints: with x64 on a bare literal
            # is weakly typed i64, which Mosaic cannot lower
            m12, s12, s24 = jnp.int32(0xFFF), jnp.int32(12), jnp.int32(24)
            lo_s = dot((col & m12).astype(jnp.float32))
            mid_s = dot(((col >> s12) & m12).astype(jnp.float32))
            hi_s = dot((col >> s24).astype(jnp.float32))
            win = ((hi_s.astype(jnp.int32) << s24)
                   + (mid_s.astype(jnp.int32) << s12)
                   + lo_s.astype(jnp.int32))
        else:
            oh = onehot.astype(jnp.float32)
            win = jax.lax.dot_general(
                col[None, :], oh, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
                precision=jax.lax.Precision.HIGHEST)[0]
        upd = out_ref[0, pl.dslice(start, _WIN)] + win
    else:
        contrib = jnp.where(onehot, col[:, None],
                            jnp.asarray(ident, col.dtype))
        # pairwise halving tree instead of reduce_min/max: Mosaic has no
        # integer reduction lowering, but elementwise minimum/maximum
        # lowers for every dtype; _CHUNK is a power of two
        op = jnp.minimum if kind == "min" else jnp.maximum
        while contrib.shape[0] > 1:
            half = contrib.shape[0] // 2
            contrib = op(contrib[:half], contrib[half:])
        win = contrib[0]
        cur = out_ref[0, pl.dslice(start, _WIN)]
        upd = jnp.minimum(cur, win) if kind == "min" \
            else jnp.maximum(cur, win)
    out_ref[0, pl.dslice(start, _WIN)] = upd


@functools.partial(jax.jit,
                   static_argnames=("num_segments", "kind", "interpret"))
def _segment_reduce_pallas(col, gid, num_segments: int, kind: str,
                           interpret: bool):
    from .. import jit_stats

    jit_stats.bump("segment_reduce_pallas")
    n = col.shape[0]
    dtype = str(col.dtype)
    ident = _IDENTITY[(kind, dtype)]
    n_chunks = max(1, -(-n // _CHUNK))
    n_pad = n_chunks * _CHUNK
    # output sized so every clamped window fits; padding rows carry an
    # out-of-window gid so they contribute nothing
    s_alloc = ((num_segments + _LANE - 1) // _LANE) * _LANE + _WIN
    if n_pad != n:
        col = jnp.concatenate(
            [col, jnp.full((n_pad - n,), ident, col.dtype)])
        gid = jnp.concatenate(
            [gid, jnp.full((n_pad - n,), s_alloc, gid.dtype)])
    gid = gid.astype(jnp.int32)
    starts = jnp.clip((gid[::_CHUNK] // _LANE) * _LANE, 0, s_alloc - _WIN)

    # chunks are blocked as (1, 1, C) windows of a (n_chunks, 1, C)
    # array: Mosaic requires each of the last two BLOCK dims to be
    # divisible by the (8, 128) tile or equal to the array dim — the
    # former 2-D (1, C) block over a (n_chunks, C) array violated the
    # sublane rule whenever n_chunks > 1 and only ever lowered in
    # interpret mode (caught by the AOT lowering smoke test)
    # (x64 is on: index maps must return int32 zeros, not Python ints)
    z = np.int32(0)
    out = pl.pallas_call(
        functools.partial(_kernel, kind=kind, dtype=dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(n_chunks,),
            in_specs=[
                pl.BlockSpec((1, 1, _CHUNK), lambda i, s: (i, z, z)),
                pl.BlockSpec((1, 1, _CHUNK), lambda i, s: (i, z, z)),
            ],
            out_specs=pl.BlockSpec((1, s_alloc), lambda i, s: (z, z)),
        ),
        out_shape=jax.ShapeDtypeStruct((1, s_alloc), col.dtype),
        interpret=interpret,
    )(starts, col.reshape(n_chunks, 1, _CHUNK),
      gid.reshape(n_chunks, 1, _CHUNK))
    return out[0, :num_segments]


# profiled entry point (telemetry.profiler): the Pallas program's
# cost/compile attribution when called from host (inside another
# trace the wrapper stages out inline); plain call when off
_segment_reduce_pallas = instrument(
    "segment_reduce_pallas", _segment_reduce_pallas,
    static_argnames=("num_segments", "kind", "interpret"))


def takes_kernel(dtype, num_segments: int, mode: str) -> bool:
    """Whether ``segment_reduce`` hands this reduction to the Pallas
    kernel under ``mode`` — a trace-static choice by dtype and size."""
    ok = _SUPPORTED if mode == "tpu" else _SUPPORTED_INTERPRET
    return bool(mode) and str(dtype) in ok \
        and num_segments <= _MAX_SEGMENTS


def segment_reduce(col, gid, num_segments: int, kind: str,
                   mode: str = None):
    """Segment reduction over SORTED group ids (steps of <= 1, larger
    jumps only into discarded trailing segments). Drop-in for
    ``jax.ops.segment_{sum,min,max}`` on the engine's grouping path;
    auto-selects the Pallas kernel on TPU.

    ``mode``: pass the caller's pallas_mode() when calling from inside a
    jitted function whose cache key includes it — re-deriving the mode
    at trace time would bake the first-seen mode into every later cache
    hit."""
    if mode is None:
        mode = pallas_mode()
    if takes_kernel(col.dtype, num_segments, mode):
        global kernel_calls
        kernel_calls += 1
        return _segment_reduce_pallas(col, gid, num_segments, kind,
                                      interpret=(mode != "tpu"))
    if kind == "sum":
        return jax.ops.segment_sum(col, gid, num_segments=num_segments)
    if kind == "min":
        return jax.ops.segment_min(col, gid, num_segments=num_segments)
    return jax.ops.segment_max(col, gid, num_segments=num_segments)
