"""The sweep ``ops/hashtable._hash_group_ids_impl``'s narrowing was chosen
from (PERF.md §5).

Times, on the device this process holds, the probe loop as it was until
PR 40 (``parent``: every round over all of the page's lanes, kept here as
a copy) against the function the tree keeps with 0, 1, 2 and 3 levels of
narrowing (``levels0`` … ``levels3``: after the first rounds the rows
still unresolved go on alone in a buffer an eighth as wide, and again),
the kept number of levels with the compaction done two other ways
(``nonzero``: ``jnp.nonzero(size=...)``; ``sorted``: a scatter-min at
non-decreasing places with ``indices_are_sorted``), with a buffer a
quarter or a sixteenth as wide as the lanes before it where the tree has
an eighth (``by4``, ``by16``), and with no buffer under 1,024 lanes
(``floor1024``: pages under 8,192 lanes keep one loop — the tree until
the sweep's points under that floor showed no loss), at the grouping
cells' page shapes: 262,144 lanes with 4 groups (q1), 65,536 (a q18
page: runs of 1-7 rows a key) and 100,000 (a q13 page: keys at random),
2,097,152 lanes with 1.5 M (q18's merge: every valid lane a key of its
own), narrower pages down to 256 lanes, and 262,144 lanes of which 42
are valid (a selective join's output: q18's last aggregation, five key
columns). Key columns after the first are functions of it, so the
groups are the same and a round gathers that many times the operands.
Every candidate's four outputs are held equal to the first one's on all
lanes. Seconds are host clock around ``block_until_ready``, the least
of ``--reps`` calls after a warm-up call; ``rounds`` are the function's
own two counters; a candidate the chip's compiler refuses prints its
error and the sweep goes on. Run it on the chip:

    chiprun -- python scripts/probe_rounds_sweep.py
    chiprun -- python scripts/probe_rounds_sweep.py \
        --points 262144x65536 --cols 1 --what levels2,levels3,nonzero,sorted
    chiprun -- python scripts/probe_rounds_sweep.py \
        --points 262144x42 --cols 5 --what parent,levels2
    chiprun -- python scripts/probe_rounds_sweep.py \
        --points 4096x1024,1024x256 --cols 1 --what parent,floor1024,kept
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

from trino_tpu import types as T  # noqa: E402
from trino_tpu.ops import hashtable as H  # noqa: E402
from trino_tpu.ops.sortkeys import group_operands  # noqa: E402


def parent(key_ops, valid, rounds=H.PROBE_ROUNDS):
    """``_hash_group_ids_impl`` (exact) as it was before PR 40."""
    cap = valid.shape[0]
    tsize = 1 << max(2 * cap - 1, 1).bit_length()
    row_idx = jnp.arange(cap, dtype=jnp.int32)
    h = H._mix_operands(key_ops, cap)
    slot0 = (h & np.uint64(tsize - 1)).astype(jnp.int32)
    table0 = jnp.full((tsize + 1,), cap, dtype=jnp.int32)
    rep0 = jnp.where(valid, cap, row_idx)

    def probe_round(carry):
        r, table, rep, resolved = carry
        active = ~resolved
        slot = jnp.where(active, (slot0 + r) & (tsize - 1), tsize)
        owner = table[slot]
        empty = active & (owner == cap)
        claim = jnp.full((tsize + 1,), cap, dtype=jnp.int32)
        claim = claim.at[jnp.where(empty, slot, tsize)].min(row_idx)
        winner = empty & (claim[slot] == row_idx)
        table = table.at[jnp.where(winner, slot, tsize)].set(row_idx)
        owner = table[slot]
        owner_safe = jnp.clip(owner, 0, cap - 1)
        eq = active & (owner < cap)
        for op in key_ops:
            eq = eq & (op == op[owner_safe])
        rep = jnp.where(eq, owner, rep)
        return r + 1, table, rep, resolved | eq

    def keep_probing(carry):
        r, _table, _rep, resolved = carry
        return (r < rounds) & jnp.any(~resolved)

    r, _, rep, resolved = jax.lax.while_loop(
        keep_probing, probe_round,
        (jnp.zeros((), dtype=jnp.int32), table0, rep0, ~valid))
    overflow = jnp.any(~resolved)
    leader = valid & (rep == row_idx)
    prefix = jnp.cumsum(leader.astype(jnp.int32)) - 1
    rep_safe = jnp.clip(rep, 0, cap - 1)
    gid = jnp.where(valid & (rep < cap), prefix[rep_safe], cap)
    ngroups = jnp.sum(leader.astype(jnp.int32))
    group_rows = jnp.zeros((cap + 1,), dtype=jnp.int32)
    group_rows = group_rows.at[jnp.where(leader, prefix, cap)].set(row_idx)
    return (gid, group_rows[:cap], ngroups, overflow, r,
            jnp.zeros((), jnp.int32))


def _first_lanes_nonzero(mask, size):
    return jnp.nonzero(mask, size=size,
                       fill_value=mask.shape[0])[0].astype(jnp.int32)


def _first_lanes_sorted(mask, size):
    """Each lane writes at the count of set lanes before it — its own
    place if it is set, else the next set lane's, with a value that
    loses the min there: the places never descend."""
    width = mask.shape[0]
    before = jnp.cumsum(mask, dtype=jnp.int32) - mask
    lane = jnp.where(mask, jnp.arange(width, dtype=jnp.int32), width)
    lanes = jnp.full((size + 1,), width, dtype=jnp.int32)
    return lanes.at[jnp.minimum(before, size)].min(
        lane, indices_are_sorted=True)[:size]


def kept(levels=None, first_lanes=None, by=None, min_lanes=None):
    """The tree's function, traced with ``levels`` levels of narrowing,
    each ``by`` times narrower, and ``first_lanes`` for its compaction
    (None: as the tree has them); ``min_lanes``: with no buffer under
    that many lanes, as the tree was when the floor was swept."""
    names = ("_NARROW_LEVELS", "_first_lanes", "_NARROW_BY")
    asked = (levels, first_lanes, by)

    def run(key_ops, valid):
        was = [getattr(H, n) for n in names] + [H._probe_widths]
        for n, v in zip(names, asked):
            if v is not None:
                setattr(H, n, v)
        if min_lanes:
            widths = H._probe_widths
            H._probe_widths = lambda cap: tuple(
                w for w in widths(cap) if w == cap or w >= min_lanes)
        try:
            return H._hash_group_ids_impl(key_ops, valid, exact=True)
        finally:
            for n, v in zip(names + ("_probe_widths",), was):
                setattr(H, n, v)
    return run


CANDIDATES = (("parent", parent), ("levels0", kept(0)),
              ("levels1", kept(1)), ("levels2", kept(2)),
              ("levels3", kept(3)), ("kept", kept()),
              ("floor1024", kept(min_lanes=1024)),
              ("by4", kept(by=4)), ("by16", kept(by=16)),
              ("nonzero", kept(first_lanes=_first_lanes_nonzero)),
              ("sorted", kept(first_lanes=_first_lanes_sorted)))


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


def page(rng, lanes, groups, cols):
    """(key operands, valid) of a page of ``lanes`` lanes and about
    ``groups`` groups, in the shape its cell's pages have."""
    valid = np.ones(lanes, dtype=bool)
    if groups * 1024 <= lanes and groups > 4:
        # a selective join's output: a valid row a group, few of them
        keys = rng.integers(0, 1 << 40, size=lanes).astype(np.int64)
        valid = np.zeros(lanes, dtype=bool)
        valid[rng.choice(lanes, groups, replace=False)] = True
    elif groups * 4 > lanes * 2:
        # a merge of kept partials: every valid lane a key of its own
        keys = rng.permutation(groups * 4)[:lanes].astype(np.int64)
        valid = np.arange(lanes) < groups
    elif groups * 4 == lanes:
        # lineitem by l_orderkey: runs of 1-7 rows a key, keys sparse
        runs = rng.integers(1, 8, size=lanes)
        keys = np.repeat(np.arange(lanes), runs)[:lanes].astype(np.int64)
        keys = (keys // 8) * 32 + keys % 8
    else:
        keys = rng.integers(0, groups, size=lanes).astype(np.int64)
    ops = list(group_operands(jnp.asarray(keys), None, T.BIGINT))
    for c in range(1, cols):
        ops += group_operands(jnp.asarray(keys % (6 + c)), None, T.BIGINT)
    return tuple(ops), jnp.asarray(valid)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--reps", type=int, default=5)
    ap.add_argument("--points", default="262144x4,262144x65536,"
                    "262144x100000,2097152x1500000,65536x16384,8192x2048",
                    help="lanes x groups, comma-separated")
    ap.add_argument("--cols", default="1,2")
    ap.add_argument("--what", default="parent,levels0,levels1,levels2")
    args = ap.parse_args()
    dev = jax.devices()[0]
    rng = np.random.default_rng(40)
    for point in args.points.split(","):
        lanes, groups = (int(v) for v in point.split("x"))
        for cols in (int(c) for c in args.cols.split(",")):
            key_ops, valid = page(rng, lanes, groups, cols)
            want = None
            for name, fn in CANDIDATES:
                if name not in args.what.split(","):
                    continue
                run = jax.jit(fn)
                point = dict(device=dev.device_kind, what=name, lanes=lanes,
                             cols=cols)
                try:
                    s, first = timed(run, (key_ops, valid), args.reps)
                except Exception as e:  # the chip's compiler refused it
                    print(json.dumps(dict(
                        point, error=str(e).splitlines()[0][:200])),
                        flush=True)
                    continue
                got = run(key_ops, valid)
                if want is None:
                    want = got
                equal = all(a.dtype == b.dtype and bool(jnp.array_equal(a, b))
                            for a, b in zip(got[:4], want[:4]))
                print(json.dumps(dict(
                    point, groups=int(got[2]), seconds=s,
                    first_call_s=first, rounds_full=int(got[4]),
                    rounds_narrow=int(got[5]), overflow=bool(got[3]),
                    equal=equal)), flush=True)


if __name__ == "__main__":
    main()
