"""The sweep behind ``exec/dynamic_filter.py``'s membership table
(PERF.md §5).

Times, on the device this process holds, one page's mask under a dynamic
filter whose value set is the membership table over ``key - lo``
(``table``: one gather), the padded sorted set and its binary search in
the same program (``sorted``), the search as the filter ran it until
PR 42 (``eager``: ``jnp.searchsorted`` and the dozen operations around it,
each a program of its own) and min / max only (``range``), at the shapes
the SF1 cells run: a resident page of 262,144 lanes against ``part``'s
≈ 10.6 k colour keys of q9 (range 200,000) and against 131,072 keys over
``l_orderkey``'s 6.0 M codes (the widest table a value set can have at
SF1), and a host page of 65,536 lanes against q3's ≈ 30 k customer keys.
Then the build side: ``_dynamic_filter_span`` and
``_dynamic_filter_table`` over a build column of 2^21 and 2^20 lanes
against pulling the column to the host and ``np.unique`` there
(``host``).  Every candidate's mask is held equal to
the first one's.  Seconds are host clock around ``block_until_ready``,
the least of ``--reps`` calls after a warm-up call.  Run it on the chip:

    chiprun -- python scripts/dyn_filter_sweep.py
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

from trino_tpu.exec import dynamic_filter as DF  # noqa: E402

#: (page lanes, distinct keys, key range)
POINTS = [(262144, 10600, 200000), (262144, 131072, 6000000),
          (65536, 30000, 150000)]
BUILDS = [(1 << 21, 323000, 6000000), (1 << 20, 146000, 6000000),
          (1 << 14, 10600, 200000)]


def least(fn, reps):
    jax.block_until_ready(fn())
    out = []
    for _ in range(reps):
        t0 = time.perf_counter()
        jax.block_until_ready(fn())
        out.append(time.perf_counter() - t0)
    return min(out) * 1e3


def eager(df, col, nulls, valid):
    """``DynamicFilter.apply`` as it was before PR 42."""
    keep = valid & ~nulls & \
        (col >= jnp.asarray(df.lo, dtype=col.dtype)) & \
        (col <= jnp.asarray(df.hi, dtype=col.dtype))
    vs = df._members
    idx = jnp.clip(jnp.searchsorted(vs, col), 0, vs.shape[0] - 1)
    keep = keep & (vs[idx] == col)
    return keep, jnp.sum((valid & ~keep).astype(jnp.int64)), \
        jnp.sum(valid.astype(jnp.int64))


def build_arrays(rng, lanes, keys, key_range):
    live = min(lanes - lanes // 8, 4 * keys)
    pool = rng.choice(key_range, size=keys, replace=False) + 1
    col = np.zeros(lanes, dtype=np.int64)
    col[:live] = rng.choice(pool, size=live)
    col[:keys] = pool
    valid = np.arange(lanes) < live
    return [jnp.asarray(a) for a in (col, np.zeros(lanes, bool), valid)]


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--reps", type=int, default=5)
    ap.add_argument("--out", default="chiprun_out/dyn_filter_sweep.json")
    args = ap.parse_args()
    rng = np.random.default_rng(42)
    dev = jax.devices()[0]
    lines = [{"device": dev.device_kind, "platform": dev.platform}]
    print(json.dumps(lines[0]))
    for lanes, keys, key_range in POINTS:
        build = build_arrays(rng, DF.padded_size(4 * keys), keys, key_range)
        by_form, bound = {}, DF.TABLE_MAX_CODES
        for form in ("table", "sorted"):
            # a bound of nothing forces the sorted set and the search
            DF.TABLE_MAX_CODES = bound if form == "table" else 0
            by_form[form] = DF.DynamicFilter(form)
            by_form[form].collect(*build)
            assert by_form[form].set_form == form
        DF.TABLE_MAX_CODES = bound
        by_form["range"] = DF.DynamicFilter("range")
        by_form["range"].collect(*build)
        by_form["range"].set_form = by_form["range"]._members = None
        page = [jnp.asarray(a) for a in (
            rng.integers(1, key_range + 1, size=lanes),
            np.zeros(lanes, bool), np.ones(lanes, bool))]
        want = np.asarray(by_form["table"].apply(*page))
        line = {"lanes": lanes, "keys": keys, "range": key_range,
                "table_bytes": by_form["table"].table_bytes,
                "kept": int(want.sum())}
        for form, df in by_form.items():
            if form != "range":
                assert (np.asarray(df.apply(*page)) == want).all(), form
            line[form + "_ms"] = least(lambda: df.apply(*page), args.reps)
        got = eager(by_form["sorted"], *page)
        assert (np.asarray(got[0]) == want).all()
        line["eager_ms"] = least(
            lambda: eager(by_form["sorted"], *page), args.reps)
        lines.append(line)
        print(json.dumps(line))
    for lanes, keys, key_range in BUILDS:
        build = build_arrays(rng, lanes, keys, key_range)
        line = {"build_lanes": lanes, "keys": keys, "range": key_range}
        line["span_ms"] = least(lambda: DF._dynamic_filter_span(*build),
                                args.reps)
        kp = DF.padded_size(key_range)
        line["table_ms"] = least(lambda: DF._dynamic_filter_table(
            *build, np.int64(1), kp=kp), args.reps)

        def device():
            df = DF.DynamicFilter("d")
            df.collect(*build)
            return df._members

        def host():
            df = DF.DynamicFilter("h")
            df._collect_on_host(*build)
            return df._members

        line["collect_device_ms"] = least(device, args.reps)
        line["collect_host_ms"] = least(host, args.reps)
        lines.append(line)
        print(json.dumps(line))
    os.makedirs(os.path.dirname(args.out), exist_ok=True)
    with open(args.out, "w") as f:
        json.dump(lines, f, indent=1)


if __name__ == "__main__":
    main()
