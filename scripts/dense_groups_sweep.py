"""The sweep ``ops/hashtable.DENSE_GROUPS`` was chosen from (PERF.md §5).

Times, on the device this process holds, the two ways a page's state
columns are reduced — ``_dense_reduce`` at several group limits and
``_scatter_reduce`` — at q1's page shapes (15 int64 ``sum`` columns), the
branching program on either side of its limit, and the keyless group
ids. Seconds are host clock around ``block_until_ready``, the least of
``--reps`` calls after a warm-up call. Run it on the chip:

    chiprun -- python scripts/dense_groups_sweep.py
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

from trino_tpu.ops import hashtable as H  # noqa: E402


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


def dense_states(gid, cols, kinds):
    none = jnp.zeros((0,), dtype=jnp.int32)
    return H._dense_reduce(gid, none, none, (), (), cols, kinds,
                           H.DENSE_GROUPS)[2]


def page(rng, lanes, groups, ncols, dtype=np.int64):
    valid = rng.random(lanes) < 0.98
    gid = np.where(valid, rng.integers(0, groups, lanes), lanes)
    cols = tuple(jnp.asarray(rng.integers(-10**9, 10**9, lanes).astype(dtype))
                 for _ in range(ncols))
    return jnp.asarray(gid.astype(np.int32)), cols, jnp.asarray(valid)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--reps", type=int, default=5)
    ap.add_argument("--cols", type=int, default=15)
    ap.add_argument("--limits", default="8,32,64,128,256,512")
    args = ap.parse_args()
    limits = [int(k) for k in args.limits.split(",")]
    dev = jax.devices()[0]
    rng = np.random.default_rng(29)
    kinds = ("sum",) * args.cols
    chosen = H.DENSE_GROUPS

    def say(**kw):
        print(json.dumps(dict(device=dev.device_kind, **kw)), flush=True)

    for lanes in (262144, 524288):
        gid, cols, valid = page(rng, lanes, 4, args.cols)
        scatter = jax.jit(lambda g, c: H._scatter_reduce(g, c, kinds, ""))
        s, first = timed(scatter, (gid, cols), args.reps)
        say(what="scatter", lanes=lanes, groups=4, seconds=s, first_s=first)
        want = scatter(gid, cols)
        for k in limits:
            H.DENSE_GROUPS = k
            g, c, _ = page(rng, lanes, k, args.cols)
            dense = jax.jit(lambda g, c: dense_states(g, c, kinds))
            s, first = timed(dense, (g, c), args.reps)
            equal = all(bool(jnp.array_equal(a, b)) for a, b in
                        zip(dense(gid, cols), want))
            say(what="dense", lanes=lanes, limit=k, seconds=s,
                first_s=first, equals_scatter=equal)
        g, c, _ = page(rng, lanes, 1 << 16, args.cols)
        s, first = timed(scatter, (g, c), args.reps)
        say(what="scatter", lanes=lanes, groups=1 << 16, seconds=s,
            first_s=first)
        for kind, dtype in (("min", np.int64), ("sum", np.int32)):
            H.DENSE_GROUPS = chosen
            g, c, _ = page(rng, lanes, 4, 1, dtype)
            for name, fn in (
                    ("dense", lambda g, c: dense_states(g, c, (kind,))),
                    ("scatter",
                     lambda g, c: H._scatter_reduce(g, c, (kind,), ""))):
                s, first = timed(jax.jit(fn), (g, c), args.reps)
                say(what=name, lanes=lanes, kind=kind,
                    dtype=np.dtype(dtype).name, limit=chosen, cols=1,
                    seconds=s, first_s=first)

        # the program the operator runs, on either side of its limit,
        # with q1's two key columns to gather
        H.DENSE_GROUPS = chosen
        rows = jnp.asarray(rng.permutation(lanes).astype(np.int32))
        raws = (jnp.arange(lanes, dtype=jnp.int32),) * 2
        nulls = (jnp.zeros((lanes,), dtype=bool),) * 2
        for groups in (1, 4, chosen, chosen + 1):
            g, c, _ = page(rng, lanes, groups, args.cols)
            fn = lambda g, c, n: H.hash_segment_reduce(  # noqa: E731
                g, rows, n, raws, nulls, c, kinds, pallas="")
            s, first = timed(fn, (g, c, jnp.int32(groups)), args.reps)
            say(what="hash_segment_reduce", lanes=lanes, groups=groups,
                limit=chosen, keys=2, seconds=s, first_s=first)
        s, first = timed(lambda v: H.hash_group_ids((), v, exact=True),
                         (valid,), args.reps)
        say(what="keyless_group_ids", lanes=lanes, seconds=s, first_s=first)
        g, c, _ = page(rng, lanes, 1, 2)
        fn = lambda g, c: H.hash_segment_reduce(  # noqa: E731
            g, rows, jnp.int32(1), (), (), c, kinds[:2], pallas="")
        s, first = timed(fn, (g, c), args.reps)
        say(what="hash_segment_reduce", lanes=lanes, groups=1, keys=0,
            cols=2, seconds=s, first_s=first)
        key = jnp.asarray(rng.integers(0, 4, lanes).astype(np.uint64))
        tag = jnp.zeros((lanes,), dtype=jnp.uint8)
        s, first = timed(
            lambda v: H.hash_group_ids((tag, key), v, exact=True),
            (valid,), args.reps)
        say(what="hash_group_ids", lanes=lanes, groups=4, seconds=s,
            first_s=first)


if __name__ == "__main__":
    main()
