"""The sweep ``ops/join._expand_matches_impl`` was chosen from (PERF.md §5).

Times, on the device this process holds, four ways to find every output
lane's probe row from the probe's per-row ``(lo, count)`` — the per-lane
binary search the function ran until PR 38 (``search``), the histogram of
``off_end`` and its prefix sum (``hist``), a scatter of row ids at each
row's first lane and a running maximum (``cummax``), and
``searchsorted(method="sort")`` (``sort``) — and the function the tree
keeps (``kept``: the histogram, and the search with int32 lanes where the
expansion is ``_SEARCH_WHEN_NARROWER`` times narrower than its page), at
the join cells' page shapes. Every candidate's three
outputs are held equal to the search's on all lanes, dead ones included,
at every point. Seconds are host clock around ``block_until_ready``, the
least of ``--reps`` calls after a warm-up call. ``sort`` runs only at
``--sort-points`` (``all`` for everywhere): the chip's compiler takes
100-136 s a shape over it, against 4-15 s for each of the others (v5e
described here, PR 38), so every point would be half an hour of
compilation. Run it on the chip:

    chiprun -- python scripts/expand_sweep.py
"""

import argparse
import json
import os
import sys
import time
from functools import partial

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np  # noqa: E402

import trino_tpu  # noqa: E402,F401  (x64 on before any array exists)
import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from trino_tpu.ops.join import _expand_matches_impl  # noqa: E402


def _by_search(lo, count, out_cap, method):
    off_end = jnp.cumsum(count)
    total = off_end[-1]
    j = jnp.arange(out_cap, dtype=jnp.int64)
    probe_idx = jnp.searchsorted(off_end, j, side="right", method=method)
    probe_idx = jnp.clip(probe_idx, 0, count.shape[0] - 1)
    start = off_end[probe_idx] - count[probe_idx]
    build_idx = lo[probe_idx] + (j - start)
    return (probe_idx.astype(jnp.int32),
            jnp.clip(build_idx, 0, None).astype(jnp.int32), j < total)


def search(lo, count, out_cap):
    """The function as it was: log2(rows) rounds of a gather a lane."""
    return _by_search(lo, count, out_cap, "scan")


def sort(lo, count, out_cap):
    """The same with JAX's sort-based search."""
    return _by_search(lo, count, out_cap, "sort")


def _lanes_from(probe_idx, lo, count, off_end, out_cap):
    j = jnp.arange(out_cap, dtype=jnp.int32)
    delta = (lo - (off_end - count)).astype(jnp.int32)
    return (probe_idx, jnp.maximum(j + delta[probe_idx], 0),
            j < off_end[-1])


def hist(lo, count, out_cap):
    """Rows ending at or before each lane: histogram + prefix sum."""
    off_end = jnp.cumsum(count)
    h = jnp.zeros(out_cap, dtype=jnp.int32).at[off_end].add(
        1, mode="drop", indices_are_sorted=True)
    probe_idx = jnp.minimum(jnp.cumsum(h), count.shape[0] - 1)
    return _lanes_from(probe_idx, lo, count, off_end, out_cap)


def cummax(lo, count, out_cap):
    """Row ids scattered at each row's first lane, then a running
    maximum. Rows that share a first lane are a run of empty rows and
    the row that follows them, so the maximum is the search's answer,
    on the dead lanes too (trailing empty rows start at ``total``)."""
    off_end = jnp.cumsum(count)
    rows = count.shape[0]
    marks = jnp.zeros(out_cap, dtype=jnp.int32).at[off_end - count].max(
        jnp.arange(rows, dtype=jnp.int32), mode="drop",
        indices_are_sorted=True)
    return _lanes_from(jax.lax.cummax(marks), lo, count, off_end, out_cap)


CANDIDATES = (("search", search), ("hist", hist), ("cummax", cummax),
              ("sort", sort), ("kept", _expand_matches_impl))


def timed(fn, args, reps):
    t0 = time.perf_counter()
    jax.block_until_ready(fn(*args))
    first = time.perf_counter() - t0
    best = float("inf")
    for _ in range(reps):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        best = min(best, time.perf_counter() - t0)
    return best, first


def page(rng, rows, out_cap, fill):
    """A probe page's (lo, count): ``fill * out_cap`` matches dealt to
    rows at random (so most rows of a narrow expansion match nothing),
    ``lo`` anywhere in a 1.5 M-row build."""
    count = np.bincount(rng.integers(0, rows, int(fill * out_cap)),
                        minlength=rows).astype(np.int32)
    lo = rng.integers(0, 1_500_000, rows).astype(np.int32)
    return jnp.asarray(lo), jnp.asarray(count)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--reps", type=int, default=5)
    ap.add_argument("--rows", default="65536,262144,524288")
    ap.add_argument("--caps", default="16,1024,65536,262144,2097152")
    ap.add_argument("--fill", type=float, default=0.8,
                    help="matches over out_cap (above 1: an overflow)")
    ap.add_argument("--what", default="search,hist,cummax,sort,kept")
    ap.add_argument("--sort-points", default="262144x262144",
                    help="rows x out_cap points that run 'sort', or all")
    args = ap.parse_args()
    dev = jax.devices()[0]
    rng = np.random.default_rng(38)
    for rows in (int(r) for r in args.rows.split(",")):
        for out_cap in (int(c) for c in args.caps.split(",")):
            lo, count = page(rng, rows, out_cap, args.fill)
            want = None
            for name, fn in CANDIDATES:
                if name not in args.what.split(","):
                    continue
                if name == "sort" and args.sort_points != "all" and \
                        f"{rows}x{out_cap}" not in args.sort_points.split(","):
                    continue
                run = jax.jit(partial(fn, out_cap=out_cap))
                s, first = timed(run, (lo, count), args.reps)
                got = run(lo, count)
                if want is None:
                    want = got
                equal = all(a.dtype == b.dtype and bool(jnp.array_equal(a, b))
                            for a, b in zip(got, want))
                print(json.dumps(dict(
                    device=dev.device_kind, what=name, rows=rows,
                    out_cap=out_cap, total=int(jnp.sum(count)),
                    seconds=s, first_s=first, equals_search=equal)),
                    flush=True)
                assert equal, (name, rows, out_cap)


if __name__ == "__main__":
    main()
