"""Global-hash device aggregation (ops/global_hash_agg.py): the
replicated-table kernel against host oracles, its overflow contract,
the key packing, and the kernel sizing history.

No planned statement runs this kernel (ROADMAP debt G); here the
kernel itself is pinned down on one device and on the 8-virtual-device
mesh with every reduce kind.
"""

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from jax.sharding import Mesh, PartitionSpec as P

from trino_tpu.ops.global_hash_agg import (EMPTY, global_hash_insert,
                                           global_hash_reduce, pack_keys,
                                           unpack_keys)
from trino_tpu.parallel.exchange import shard_map


def test_pack_unpack_roundtrip_with_nulls():
    k1 = jnp.asarray([0, 5, 1 << 20, 3, 7], dtype=jnp.int64)
    n1 = jnp.asarray([False, False, False, True, False])
    k2 = jnp.asarray([9, 0, 2, 4, 1 << 30], dtype=jnp.int64)
    packed = pack_keys([k1, k2], [n1, None], (32, 32))
    assert int(jnp.sum(packed == EMPTY)) == 0
    (v1, u1), (v2, u2) = unpack_keys(packed, (32, 32))
    got1 = np.asarray(v1)
    assert np.array_equal(np.asarray(u1), np.asarray(n1))
    assert np.array_equal(got1[~np.asarray(n1)],
                          np.asarray(k1)[~np.asarray(n1)])
    assert not np.asarray(u2).any()
    assert np.array_equal(np.asarray(v2), np.asarray(k2))
    # distinct tuples pack to distinct u64s
    assert len(set(np.asarray(packed).tolist())) == 5


def _host_groupby(keys, vals, valid):
    out = {}
    for k, v, va in zip(keys, vals, valid):
        if va:
            s, c, mn, mx = out.get(int(k), (0, 0, 1 << 62, -(1 << 62)))
            out[int(k)] = (s + int(v), c + 1, min(mn, int(v)),
                           max(mx, int(v)))
    return out


def test_single_device_kernel_matches_host_oracle():
    rng = np.random.default_rng(2)
    n, ndv, ts = 4096, 300, 1024
    keys = rng.integers(0, ndv, n)
    vals = rng.integers(-500, 500, n).astype(np.int64)
    valid = rng.random(n) > 0.1
    packed = pack_keys([jnp.asarray(keys)], [None], (32,))
    table, slot_of, resolved, unresolved = global_hash_insert(
        packed, jnp.asarray(valid), ts)
    assert int(unresolved) == 0
    v = jnp.asarray(vals)
    va = jnp.asarray(valid)
    info = jnp.iinfo(jnp.int64)
    sums, cnts, mns, mxs = global_hash_reduce(
        slot_of, resolved, va,
        (jnp.where(va, v, 0), va.astype(jnp.int64),
         jnp.where(va, v, info.max), jnp.where(va, v, info.min)),
        ("sum", "sum", "min", "max"), ts)
    t = np.asarray(table)
    occ = t != np.uint64(EMPTY)
    got = {}
    for slot in np.nonzero(occ)[0]:
        key = int((t[slot] & np.uint64(0xFFFFFFFF)) - 1)
        got[key] = (int(np.asarray(sums)[slot]),
                    int(np.asarray(cnts)[slot]),
                    int(np.asarray(mns)[slot]),
                    int(np.asarray(mxs)[slot]))
    assert got == _host_groupby(keys, vals, valid)


def test_mesh_kernel_matches_host_oracle_all_kinds():
    rng = np.random.default_rng(7)
    n_dev, rows, ndv, ts = 8, 1024, 150, 512
    keys = rng.integers(0, ndv, (n_dev, rows))
    vals = rng.integers(-100, 900, (n_dev, rows)).astype(np.int64)
    valid = rng.random((n_dev, rows)) > 0.05
    mesh = Mesh(np.asarray(jax.devices()[:n_dev]), ("x",))
    info = jnp.iinfo(jnp.int64)

    @partial(shard_map, mesh=mesh, in_specs=(P("x"),) * 3,
             out_specs=(P("x"),) * 5, check_vma=False)
    def prog(k, v, va):
        k, v, va = k[0], v[0], va[0]
        packed = pack_keys([k], [None], (32,))
        table, slot_of, resolved, unresolved = global_hash_insert(
            packed, va, ts, axis_name="x")
        sums, cnts, mns, mxs = global_hash_reduce(
            slot_of, resolved, va,
            (jnp.where(va, v, 0), va.astype(jnp.int64),
             jnp.where(va, v, info.max), jnp.where(va, v, info.min)),
            ("sum", "sum", "min", "max"), ts, axis_name="x")
        i = jax.lax.axis_index("x")
        sh = ts // 8
        sl = lambda a: jax.lax.dynamic_slice(a, (i * sh,), (sh,))  # noqa: E731
        return (sl(table)[None], sl(sums)[None], sl(cnts)[None],
                sl(mns)[None], sl(mxs)[None])

    t, s, c, mn, mx = prog(jnp.asarray(keys), jnp.asarray(vals),
                           jnp.asarray(valid))
    t = np.asarray(t).reshape(-1)
    s, c, mn, mx = (np.asarray(a).reshape(-1) for a in (s, c, mn, mx))
    occ = t != np.uint64(EMPTY)
    got = {}
    for slot in np.nonzero(occ)[0]:
        key = int((t[slot] & np.uint64(0xFFFFFFFF)) - 1)
        got[key] = (int(s[slot]), int(c[slot]), int(mn[slot]),
                    int(mx[slot]))
    want = _host_groupby(keys.reshape(-1), vals.reshape(-1),
                         valid.reshape(-1))
    assert got == want
    # the replicated table resolved every live row identically
    assert len(got) <= ndv


def test_reduce_handles_float32_min_max_states():
    """REAL aggregates carry float32 min/max states — the sentinel
    selection must branch on floating-ness, not float64 equality
    (jnp.iinfo on f32 raises at trace time)."""
    rng = np.random.default_rng(11)
    n, ts = 512, 64
    keys = rng.integers(0, 20, n)
    vals = rng.standard_normal(n).astype(np.float32)
    packed = pack_keys([jnp.asarray(keys)], [None], (32,))
    valid = jnp.ones(n, dtype=bool)
    table, slot_of, resolved, unresolved = global_hash_insert(
        packed, valid, ts)
    assert int(unresolved) == 0
    v = jnp.asarray(vals)
    mns, mxs = global_hash_reduce(
        slot_of, resolved, valid, (v, v), ("min", "max"), ts)
    t = np.asarray(table)
    for slot in np.nonzero(t != np.uint64(EMPTY))[0]:
        key = int((t[slot] & np.uint64(0xFFFFFFFF)) - 1)
        sel = vals[keys == key]
        assert np.asarray(mns)[slot] == sel.min()
        assert np.asarray(mxs)[slot] == sel.max()


def test_probe_budget_overflow_is_reported_not_wrong():
    """More distinct keys than the table can hold: the kernel must
    REPORT unresolved rows (the caller's fallback trigger), and every
    row it did resolve must still aggregate correctly."""
    rng = np.random.default_rng(5)
    n, ts = 512, 16  # 512 distinct keys into 16 slots
    keys = np.arange(n)
    packed = pack_keys([jnp.asarray(keys)], [None], (32,))
    valid = jnp.ones(n, dtype=bool)
    table, slot_of, resolved, unresolved = global_hash_insert(
        packed, valid, ts)
    assert int(unresolved) > 0
    assert int(unresolved) == n - int(jnp.sum(resolved))
    sums, = global_hash_reduce(
        slot_of, resolved, valid, (jnp.asarray(keys, jnp.int64),),
        ("sum",), ts)
    t = np.asarray(table)
    for slot in np.nonzero(t != np.uint64(EMPTY))[0]:
        key = int((t[slot] & np.uint64(0xFFFFFFFF)) - 1)
        # resolved rows of this key all carry value == key
        r = np.asarray(resolved) & (keys == key)
        assert int(np.asarray(sums)[slot]) == int(keys[r].sum())


def test_kernel_sizing_history_stabilizes_capacity():
    from trino_tpu.ops.kernel_sizing import ShapeSizingHistory

    h = ShapeSizingHistory()
    key = ("test", "shape")
    assert h.suggest(key, 1000) == 1024
    # fast-up: a larger need grows immediately
    assert h.suggest(key, 5000) == 8192
    # slow-down: a shrunken need keeps the remembered bucket (EWMA)
    assert h.suggest(key, 900) >= 2048
    # the need is a floor even on a cold key
    assert h.suggest(("other",), 17) == 32
    # repeated small needs eventually decay the remembered level
    for _ in range(12):
        got = h.suggest(key, 900)
    assert got == 1024


@pytest.mark.parametrize("override,expect", [
    ("AUTOMATIC", "global-hash"),
    ("EXCHANGE", "exchange"),
    ("GLOBAL_HASH", "global-hash"),
])
def test_agg_strategy_cost_rule_and_override(override, expect):
    from trino_tpu.planner.optimizer import choose_agg_strategy

    strat, detail = choose_agg_strategy(10, 4, override=override)
    assert strat == expect
    assert detail
    # AUTOMATIC flips past the table cap
    strat, detail = choose_agg_strategy(1 << 20, 4)
    assert strat == "exchange"


def test_agg_strategy_annotation_in_explain():
    """The planner annotates grouped aggregations with the cost-model
    pick + estimate, honoring the session override both ways."""
    from trino_tpu.connectors.tpch import TpchConnector
    from trino_tpu.runner import LocalQueryRunner
    from trino_tpu.sql.analyzer import Session

    sql = ("select l_returnflag, count(*) from lineitem "
           "group by l_returnflag")

    def runner(**props):
        s = Session(catalog="tpch", schema="micro")
        s.properties.update(props)
        return LocalQueryRunner(
            {"tpch": TpchConnector(page_rows=4096)}, s)

    plan = runner().explain(sql)
    # l_returnflag ndv=3: deep inside the global-hash win region
    assert "strategy=global-hash" in plan
    assert "groups" in plan
    assert "strategy=global-hash" not in runner(
        aggregation_strategy="EXCHANGE").explain(sql)
    # past the cap the rule flips to exchange (override forces it back)
    high = ("select l_orderkey, count(*) from lineitem "
            "group by l_orderkey")
    assert "strategy=global-hash" not in runner(
        global_hash_agg_max_table=16).explain(high)
    assert "strategy=global-hash" in runner(
        aggregation_strategy="GLOBAL_HASH").explain(high)
