"""The sweep ``ops/join._build_sorted`` was cut down from (PERF.md §5).

A join's build index is one sort of (u64 key, int32 row). Until PR 48 the
build also gathered every carried array — the key again, ``usable``,
``valid``, each column and each null mask — into sorted order at the
build's padded width, and the probe gathered the same columns again at
its matches' lanes. Since PR 48 the columns stay where they arrived and
the probe translates its lanes through the sort's permutation. This
times, on the device this process holds, both sides of that trade:

(a) ``sort``: the (u64, int32) sort alone — ``_build_sorted`` as the
    tree keeps it — at ``--lanes`` build lanes;
(b) ``carried``: what the parent added to it, the gathers by the
    permutation of key, usable, valid and ``--cols`` bigint columns with
    their null masks, at the same lanes (a program of its own so that
    one sort is compiled a width: the chip's compiler takes the better
    part of a minute a sort), and ``parent``: the parent's whole
    ``_build_sorted`` (``sortkeys.sort_carrying``) at ``--parent-points``
    to hold the sum against;
(c) ``out_*``: an output gather of 2 / 4 bigint columns with their null
    masks at ``--out-lanes`` lanes out of a build of ``--out-build``
    lanes, ``asc`` by ascending sorted positions into a sorted copy (the
    parent's ``c_sorted[build_idx]``; q13's shape: a probe in key
    order), ``runs`` by runs of 8 consecutive positions starting
    anywhere (a probe in any other order), and ``perm`` by
    ``perm[build_idx]`` into the columns where they arrived, the
    translation included (the change's).

Every form's result is held equal to numpy's at its first call. Seconds
are host clock around ``block_until_ready``, the least of ``--reps``
calls after a warm-up call. Run it on the chip:

    chiprun -- python scripts/build_index_sweep.py
"""

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np  # noqa: E402

import trino_tpu  # noqa: E402,F401  (x64 on before any array exists)
import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from trino_tpu.ops.join import _arrival_rows, _build_sorted  # noqa: E402
from trino_tpu.ops.sortkeys import sort_carrying  # noqa: E402

_SENTINEL = np.uint64(0xFFFFFFFFFFFFFFFF)


@jax.jit
def parent_build(key_u64, anynull, cols, nulls, valid):
    """``_build_sorted`` as it was until PR 48."""
    usable = valid & ~anynull
    sort_key = jnp.where(usable, key_u64, _SENTINEL)
    (s_key,), s = sort_carrying(
        [sort_key], [usable, valid] + list(cols) + list(nulls))
    n = len(cols)
    return s_key, s[0], s[1], tuple(s[2:2 + n]), tuple(s[2 + n:])


@jax.jit
def carried(perm, key_u64, usable, valid, cols, nulls):
    """The parent's gathers alone, by a permutation already there."""
    return (key_u64[perm], usable[perm], valid[perm],
            tuple(c[perm] for c in cols), tuple(n[perm] for n in nulls))


@jax.jit
def out_sorted(build_idx, cols, nulls):
    """The parent's output gather: sorted copies at sorted positions."""
    return (tuple(c[build_idx] for c in cols),
            tuple(n[build_idx] for n in nulls))


@jax.jit
def out_arrival(build_idx, perm, cols, nulls):
    """The change's: arrival-order columns at ``perm[build_idx]``."""
    build_row, _ = _arrival_rows(perm, build_idx)
    return (tuple(c[build_row] for c in cols),
            tuple(n[build_row] for n in nulls))


def timed(fn, args, reps):
    t0 = time.perf_counter()
    out = jax.block_until_ready(fn(*args))
    first = time.perf_counter() - t0
    best = float("inf")
    for _ in range(reps):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        best = min(best, time.perf_counter() - t0)
    return best, first, out


def build(rng, lanes, ncols):
    """A build of ``lanes`` lanes, seven eighths of them rows: keys at
    random over the rows' number (duplicates, as a fact table's foreign
    key), bigint columns, a twentieth of each NULL."""
    rows = lanes - lanes // 8
    key = rng.integers(0, rows, lanes).astype(np.uint64)
    valid = np.arange(lanes) < rows
    cols = [rng.integers(-1 << 40, 1 << 40, lanes) for _ in range(ncols)]
    nulls = [rng.random(lanes) < 0.05 for _ in range(ncols)]
    return key, valid, cols, nulls


def emit(dev, **line):
    print(json.dumps(dict(device=dev.device_kind, **line)), flush=True)


def ints(text):
    return [int(v) for v in text.split(",") if v]


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--reps", type=int, default=5)
    ap.add_argument("--lanes", default=",".join(
        str(1 << k) for k in range(18, 24)))
    ap.add_argument("--cols", default="0,2,4,10")
    ap.add_argument("--parent-points", default="2097152x2,8388608x4",
                    help="lanes x columns that run the parent's program")
    ap.add_argument("--out-lanes", default=",".join(
        str(1 << k) for k in range(17, 22)))
    ap.add_argument("--out-cols", default="2,4")
    ap.add_argument("--out-build", default="2097152,8388608")
    args = ap.parse_args()
    dev = jax.devices()[0]
    rng = np.random.default_rng(48)

    for lanes in ints(args.lanes):
        key, valid, cols, nulls = build(rng, lanes, max(ints(args.cols)))
        d_key, d_valid = jnp.asarray(key), jnp.asarray(valid)
        d_cols = [jnp.asarray(c) for c in cols]
        d_nulls = [jnp.asarray(n) for n in nulls]
        no_null = jnp.zeros(lanes, dtype=bool)
        s, first, (key_sorted, perm) = timed(
            _build_sorted.jit, (d_key, no_null, d_valid), args.reps)
        order = np.argsort(np.where(valid, key, _SENTINEL), kind="stable")
        np.testing.assert_array_equal(
            np.asarray(key_sorted), np.where(valid, key, _SENTINEL)[order])
        p = np.asarray(perm)
        rows = np.where(p < 0, ~p, p)
        assert (np.sort(rows) == np.arange(lanes)).all()
        np.testing.assert_array_equal(p >= 0, valid[rows])
        np.testing.assert_array_equal(key[rows][p >= 0],
                                      np.asarray(key_sorted)[p >= 0])
        emit(dev, what="sort", lanes=lanes, seconds=s, first_s=first)
        d_rows = jnp.asarray(rows.astype(np.int32))
        for ncols in ints(args.cols):
            s_c, first, got = timed(
                carried, (d_rows, d_key, d_valid, d_valid,
                          d_cols[:ncols], d_nulls[:ncols]), args.reps)
            for c, g in zip(cols[:ncols], got[3]):
                np.testing.assert_array_equal(np.asarray(g), c[rows])
            emit(dev, what="carried", lanes=lanes, cols=ncols,
                 seconds=s_c, first_s=first, with_sort_s=s + s_c,
                 us_per_lane=1e6 * s_c / lanes)
            if f"{lanes}x{ncols}" in args.parent_points.split(","):
                s_p, first, got = timed(
                    parent_build, (d_key, no_null, tuple(d_cols[:ncols]),
                                   tuple(d_nulls[:ncols]), d_valid),
                    args.reps)
                np.testing.assert_array_equal(np.asarray(got[0]),
                                              np.asarray(key_sorted))
                emit(dev, what="parent", lanes=lanes, cols=ncols,
                     seconds=s_p, first_s=first)

    for lanes in ints(args.out_build):
        key, valid, cols, nulls = build(rng, lanes,
                                        max(ints(args.out_cols)))
        rows = lanes - lanes // 8
        perm = np.concatenate([rng.permutation(rows),
                               ~np.arange(rows, lanes)]).astype(np.int32)
        arrival = np.where(perm < 0, ~perm, perm)
        d_perm = jnp.asarray(perm)
        d_cols = [jnp.asarray(c) for c in cols]
        d_nulls = [jnp.asarray(n) for n in nulls]
        # the parent's sorted copies: sorted position i holds arrival[i]
        d_scols = [jnp.asarray(c[arrival]) for c in cols]
        d_snulls = [jnp.asarray(n[arrival]) for n in nulls]
        for out in ints(args.out_lanes):
            if out > lanes:
                continue
            asc = np.sort(rng.integers(0, rows, out)).astype(np.int32)
            starts = rng.integers(0, rows - 8, out // 8)
            runs = (starts[:, None] + np.arange(8)).reshape(-1).astype(
                np.int32)
            for ncols in ints(args.out_cols):
                line = {}
                for name, idx in (("asc", asc), ("runs", runs)):
                    d_idx = jnp.asarray(idx)
                    s_s, _, got_s = timed(
                        out_sorted, (d_idx, d_scols[:ncols],
                                     d_snulls[:ncols]), args.reps)
                    s_a, _, got_a = timed(
                        out_arrival, (d_idx, d_perm, d_cols[:ncols],
                                      d_nulls[:ncols]), args.reps)
                    for a, b, c in zip(got_s[0], got_a[0], cols):
                        np.testing.assert_array_equal(np.asarray(a),
                                                      c[arrival][idx])
                        np.testing.assert_array_equal(np.asarray(a),
                                                      np.asarray(b))
                    line[f"out_sorted_{name}_s"] = s_s
                    line[f"out_perm_{name}_s"] = s_a
                emit(dev, what="out", build_lanes=lanes, out_lanes=out,
                     cols=ncols, **line)


if __name__ == "__main__":
    main()
