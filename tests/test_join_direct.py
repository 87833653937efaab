"""The join's direct-address probe (``ops/join.py``): a build whose keys
are exact and span a range the chip can hold a table over answers each
probe row's candidate range ``(lo, count)`` by two gathers from an offsets
table over ``key - klo``; every other build keeps the two ``searchsorted``
calls and says why.

Both lookups must agree bit for bit, so everything downstream of the
lookup is one path: the table is held to the searches over adversarial
key sets, every fallback is held to its reason and to the same rows, and
q3- / q13-shaped statements of every join type are held to sqlite with
the operator's counter reading ``direct``.
"""

import datetime

import numpy as np
import pytest

from test_tpch_oracle import assert_same, load_sqlite, to_sqlite
from trino_tpu import jit_stats
from trino_tpu import types as T
from trino_tpu.block import DevicePage, Dictionary, Page
from trino_tpu.connectors.tpch import TpchConnector
from trino_tpu.exec.memory import QueryMemoryPool
from trino_tpu.ops import join as J
from trino_tpu.resources.tpch_queries import TPCH_QUERIES
from trino_tpu.runner import LocalQueryRunner
from trino_tpu.sql.analyzer import Session


def _device_page(types_, cols, dicts=None):
    if dicts is None:
        dicts = [Dictionary() if t.is_pooled else None for t in types_]
    return DevicePage.from_page(Page.from_pylists(types_, cols, dicts))


def _publish(types_, key_channels, build_cols, page_rows=512,
             memory_context=None, hybrid=None):
    """A published build: (bridge, builder)."""
    bridge = J.JoinBridge()
    build = J.HashBuilderOperator(types_, list(key_channels), bridge,
                                  memory_context=memory_context,
                                  hybrid=hybrid)
    dicts = [Dictionary() if t.is_pooled else None for t in types_]
    for lo in range(0, len(build_cols[0]), page_rows):
        build.add_input(_device_page(
            types_, [c[lo:lo + page_rows] for c in build_cols], dicts))
    build.finish()
    build.get_output()
    return bridge, build


def _probe_keys(types_, key_channels, bridge, probe_cols):
    """The probe page's u64 keys and usable mask, as ``add_input``
    computes them."""
    b = bridge.build
    op = J.LookupJoinOperator(types_, list(key_channels), bridge, "inner")
    page = _device_page(types_, probe_cols)
    pkey_cols, key_types = op._probe_key_cols(page, b)
    pkey, anynull = J._key_u64(pkey_cols,
                               [page.nulls[c] for c in key_channels],
                               key_types, b.key_mode)
    return pkey, page.valid & ~anynull


def _both_lookups(types_, key_channels, build_cols, probe_cols):
    bridge, _ = _publish(types_, key_channels, build_cols)
    b = bridge.build
    assert b.direct is not None, b.direct_fallback
    pkey, pusable = _probe_keys(types_, key_channels, bridge, probe_cols)
    want = J._probe_counts(b.key_sorted, pkey, pusable)
    got = J._probe_direct_counts(b.direct.offsets, b.direct.span, pkey,
                                 pusable)
    return got, want, np.asarray(pusable), np.asarray(pkey)


def _payload(n):
    return list(range(n))


def _dense(rng):
    b = [int(v) for v in rng.permutation(700)]
    p = [int(v) for v in rng.integers(0, 700, 900)]
    return [T.BIGINT, T.BIGINT], (0,), [b, _payload(700)], \
        [p, _payload(900)]


def _sparse(rng):
    # range 40 x rows
    b = [int(v) for v in rng.choice(40 * 500, 500, replace=False) + 7]
    p = b[:300] + [int(v) for v in rng.integers(0, 40 * 500 + 20, 600)]
    return [T.BIGINT, T.BIGINT], (0,), [b, _payload(500)], \
        [p, _payload(900)]


def _duplicates(rng):
    # fan-out 1..64 a key
    b = [k for k in range(40) for _ in range(int(rng.integers(1, 65)))]
    b = [b[i] for i in rng.permutation(len(b))]
    p = [int(v) for v in rng.integers(-3, 45, 400)]
    return [T.BIGINT, T.BIGINT], (0,), [b, _payload(len(b))], \
        [p, _payload(400)]


def _negative(rng):
    # two's complement wraps negatives to the top of u64: a range that
    # ends at -2 lies one below the sentinel
    b = [int(v) for v in rng.integers(-900, -1, 600)]
    p = [int(v) for v in rng.integers(-1000, 50, 800)]
    return [T.BIGINT, T.BIGINT], (0,), [b, _payload(600)], \
        [p, _payload(800)]


def _int32_keys(rng):
    b = [int(v) for v in rng.integers(100, 5000, 600)]
    p = [int(v) for v in rng.integers(0, 5200, 800)]
    return [T.INTEGER, T.BIGINT], (0,), [b, _payload(600)], \
        [p, _payload(800)]


def _dates(rng):
    day0 = (datetime.date(1995, 1, 1) - datetime.date(1970, 1, 1)).days
    b = [day0 + int(v) for v in rng.integers(0, 400, 500)]
    p = [day0 + int(v) for v in rng.integers(-30, 430, 700)]
    return [T.DATE, T.BIGINT], (0,), [b, _payload(500)], \
        [p, _payload(700)]


def _varchar(rng):
    vocab = [f"k{i:03d}" for i in range(80)]
    b = [vocab[i] for i in rng.integers(0, 50, 600)]
    p = [vocab[i] for i in rng.integers(0, 80, 800)]
    return [T.VARCHAR, T.BIGINT], (0,), [b, _payload(600)], \
        [p, _payload(800)]


def _packed(rng):
    # two 32-bit keys pack exactly; the first is one value, so the
    # packed keys span the second's range
    b0 = [7] * 500
    b1 = [int(v) for v in rng.integers(0, 300, 500)]
    p0 = [int(v) for v in rng.choice([6, 7, 8], 700)]
    p1 = [int(v) for v in rng.integers(0, 320, 700)]
    return [T.INTEGER, T.INTEGER], (0, 1), [b0, b1], [p0, p1]


def _null_build_keys(rng):
    b = [int(v) if rng.random() > 0.2 else None
         for v in rng.integers(0, 300, 600)]
    p = [int(v) for v in rng.integers(0, 320, 700)]
    return [T.BIGINT, T.BIGINT], (0,), [b, _payload(600)], \
        [p, _payload(700)]


def _null_probe_keys(rng):
    b = [int(v) for v in rng.integers(0, 300, 600)]
    p = [int(v) if rng.random() > 0.2 else None
         for v in rng.integers(0, 320, 700)]
    return [T.BIGINT, T.BIGINT], (0,), [b, _payload(600)], \
        [p, _payload(700)]


def _empty_build(rng):
    p = [int(v) for v in rng.integers(-5, 50, 300)]
    return [T.BIGINT, T.BIGINT], (0,), [[], []], [p, _payload(300)]


def _all_null_build(rng):
    p = [int(v) for v in rng.integers(-5, 50, 300)]
    return [T.BIGINT, T.BIGINT], (0,), [[None] * 40, _payload(40)], \
        [p, _payload(300)]


def _outside_the_range(rng):
    b = [int(v) for v in rng.integers(1000, 2000, 500)]
    p = list(range(990, 1000)) + list(range(2000, 2010)) \
        + [0, 999, 1000, 1999, 2000, 1 << 40, -(1 << 40), -2]
    return [T.BIGINT, T.BIGINT], (0,), [b, _payload(500)], \
        [p, _payload(len(p))]


def _one_key(rng):
    return [T.BIGINT, T.BIGINT], (0,), [[42] * 30, _payload(30)], \
        [[41, 42, 43], _payload(3)]


CASES = {
    "dense": _dense, "sparse_40x": _sparse, "duplicates_1_64": _duplicates,
    "negative": _negative, "int32": _int32_keys, "dates": _dates,
    "varchar_codes": _varchar, "packed_two_keys": _packed,
    "null_build_keys": _null_build_keys,
    "null_probe_keys": _null_probe_keys, "empty_build": _empty_build,
    "all_null_build": _all_null_build,
    "below_klo_above_khi": _outside_the_range, "one_key": _one_key,
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_table_equals_the_two_searches(case):
    rng = np.random.default_rng(len(case) * 131 + 7)
    (lo, count), (want_lo, want_count), usable, pkey = _both_lookups(
        *CASES[case](rng))
    assert lo.dtype == want_lo.dtype and count.dtype == want_count.dtype
    assert lo.shape == want_lo.shape
    assert usable.any()
    np.testing.assert_array_equal(np.asarray(lo)[usable],
                                  np.asarray(want_lo)[usable])
    # a probe key of -1, or a string the build's pool lacks (code -1),
    # is the u64 sentinel: the one place the counts differ (the test
    # below); everywhere else they are the searches' to the bit
    below = usable & (pkey != J._U64_SENTINEL)
    np.testing.assert_array_equal(np.asarray(count)[below],
                                  np.asarray(want_count)[below])
    assert not np.asarray(count)[usable & ~below].any()
    # a probe row that is not usable counts nothing in either
    assert not np.asarray(count)[~usable].any()
    assert not np.asarray(want_count)[~usable].any()


def test_probe_key_at_the_sentinel_counts_nothing():
    """-1 is the u64 sentinel the build's dead lanes sort to: the
    searches hand such a probe row every dead lane as a candidate (the
    raw-key verification drops them), the table hands it none; ``lo`` is
    the same, and so are the joined rows."""
    types_ = [T.BIGINT, T.BIGINT]
    build_cols = [[1, 2, 3, None, 5], _payload(5)]
    probe_cols = [[-1, 3, 5, -1], _payload(4)]
    (lo, count), (want_lo, want_count), usable, pkey = _both_lookups(
        types_, (0,), build_cols, probe_cols)
    at = pkey == J._U64_SENTINEL
    assert at.sum() == 2 and usable[at].all()
    np.testing.assert_array_equal(np.asarray(lo), np.asarray(want_lo))
    assert not np.asarray(count)[at].any()
    assert (np.asarray(want_count)[at] > 0).all()
    np.testing.assert_array_equal(np.asarray(count)[~at],
                                  np.asarray(want_count)[~at])
    for join_type in ("inner", "left", "anti"):
        got, op = _join_rows(join_type, types_, (0,), build_cols,
                             probe_cols)
        assert got == _brute(join_type, build_cols, probe_cols)
        assert op.metrics()["direct_probe_pages"] == 1


# -- whole joins: rows, counters, fallbacks ---------------------------------


def _join_rows(join_type, types_, key_channels, build_cols, probe_cols,
               page_rows=512, bridge=None):
    if bridge is None:
        bridge, _ = _publish(types_, key_channels, build_cols, page_rows)
    probe = J.LookupJoinOperator(types_, list(key_channels), bridge,
                                 join_type)
    rows = []
    pdicts = [Dictionary() if t.is_pooled else None for t in types_]
    for lo in range(0, len(probe_cols[0]), page_rows):
        probe.add_input(_device_page(
            types_, [c[lo:lo + page_rows] for c in probe_cols], pdicts))
        while (p := probe.get_output()) is not None:
            rows.extend(p.to_page().to_rows())
    probe.finish()
    while not probe.is_finished():
        p = probe.get_output()
        if p is not None:
            rows.extend(p.to_page().to_rows())
    return sorted(rows, key=repr), probe


def _brute(join_type, build_cols, probe_cols, key_channels=(0,)):
    """The join by nested loops over python rows."""
    brows = list(zip(*build_cols))
    prows = list(zip(*probe_cols))

    def key(row):
        k = tuple(row[c] for c in key_channels)
        return None if None in k else k

    out = []
    nb = len(build_cols)
    matched_b = set()
    for p in prows:
        hits = [i for i, b in enumerate(brows)
                if key(p) is not None and key(p) == key(b)]
        matched_b.update(hits)
        if join_type == "semi":
            out.extend([p] if hits else [])
        elif join_type == "anti":
            out.extend([] if hits else [p])
        else:
            out.extend(p + brows[i] for i in hits)
            if not hits and join_type in ("left", "full"):
                out.append(p + (None,) * nb)
    if join_type == "full":
        out.extend((None,) * len(probe_cols) + b
                   for i, b in enumerate(brows) if i not in matched_b)
    return sorted(out, key=repr)


def _uniform_nulls(rng):
    build_cols = [[int(v) if rng.random() > 0.1 else None
                   for v in rng.integers(50, 400, 700)], _payload(700)]
    probe_cols = [[int(v) if rng.random() > 0.1 else None
                   for v in rng.integers(0, 450, 1100)], _payload(1100)]
    # 351 codes + 1, padded
    return [T.BIGINT, T.BIGINT], build_cols, probe_cols, 3, 4 * 512


def _zipf_nulls_150(rng):
    # a skewed probe over a build of 150 keys, a tenth of both null
    def keys(values):
        return [int(v) if rng.random() >= 0.1 else None for v in values]

    build_cols = [keys(rng.integers(0, 150, 768)), _payload(768)]
    probe_cols = [keys(rng.zipf(1.8, 1024) % 229), _payload(1024)]
    return [T.BIGINT, T.BIGINT], build_cols, probe_cols, 2, 4 * 256


def _varchar_two_pools(rng):
    # dictionary codes: ``_publish`` and ``_join_rows`` give build and
    # probe a pool each, and the probe's holds 20 strings the build's
    # lacks, so the probe remaps its codes into the build's pool
    vocab = [f"k{i:03d}" for i in range(60)]
    bk = [vocab[i] if rng.random() > 0.05 else None
          for i in rng.integers(0, 40, 900)]
    pk = [vocab[i] if rng.random() > 0.05 else None
          for i in rng.integers(0, 60, 1100)]
    return [T.VARCHAR, T.BIGINT], [bk, _payload(900)], \
        [pk, _payload(1100)], 3, 4 * 64


DISTRIBUTIONS = {"uniform_nulls": _uniform_nulls,
                 "zipf_nulls_150": _zipf_nulls_150,
                 "varchar_two_pools": _varchar_two_pools}


@pytest.mark.parametrize("distribution", sorted(DISTRIBUTIONS))
@pytest.mark.parametrize("join_type",
                         ["inner", "left", "full", "semi", "anti"])
def test_join_types_over_the_table(join_type, distribution):
    rng = np.random.default_rng(17)
    types_, build_cols, probe_cols, pages, table_bytes = \
        DISTRIBUTIONS[distribution](rng)
    got, op = _join_rows(join_type, types_, (0,), build_cols, probe_cols)
    assert got == _brute(join_type, build_cols, probe_cols)
    m = op.metrics()
    assert m["probe_pages"] == pages and m["direct_probe_pages"] == pages
    assert m["direct_table_bytes"] == table_bytes
    assert "probe_fallback" not in m


def _hashed(rng):
    cols = [[int(v) for v in rng.integers(0, 30, 300)] for _ in range(3)]
    probe = [[int(v) for v in rng.integers(0, 30, 400)] for _ in range(3)]
    return [T.BIGINT] * 3, (0, 1, 2), cols, probe, "hashed key mode"


def _two_wide_keys(rng):
    # two bigint keys do not pack into 64 bits: hashed
    cols = [[int(v) for v in rng.integers(0, 30, 300)] for _ in range(2)]
    probe = [[int(v) for v in rng.integers(0, 30, 400)] for _ in range(2)]
    return [T.BIGINT] * 2, (0, 1), cols, probe, "hashed key mode"


def _float_key(rng):
    b = [float(v) / 4 for v in rng.integers(0, 200, 300)]
    p = [float(v) / 4 for v in rng.integers(0, 220, 400)]
    return [T.DOUBLE, T.BIGINT], (0,), [b, _payload(300)], \
        [p, _payload(400)], "float key"


def _past_the_bound(rng):
    b = [0, 5, 1 << 40, (1 << 40) + 1]
    p = [0, 1, 5, 1 << 40, 1 << 41]
    return [T.BIGINT, T.BIGINT], (0,), [b, _payload(4)], \
        [p, _payload(5)], "past the table's bound"


def _mixed_signs(rng):
    # negatives wrap to the top of u64, so a build on both sides of
    # zero spans nearly all of it
    b = [int(v) for v in rng.integers(-50, 50, 300) if v != -1]
    p = [int(v) for v in rng.integers(-60, 60, 400)]
    return [T.BIGINT, T.BIGINT], (0,), [b, _payload(len(b))], \
        [p, _payload(400)], "past the table's bound"


def _sentinel_key(rng):
    b = [int(v) for v in rng.integers(-40, 0, 300)]
    assert -1 in b
    p = [int(v) for v in rng.integers(-50, 5, 400)]
    return [T.BIGINT, T.BIGINT], (0,), [b, _payload(300)], \
        [p, _payload(400)], "key at the u64 sentinel"


def _packed_wide(rng):
    # packed keys with two first keys span more than 2**32 codes
    b0 = [int(v) for v in rng.integers(0, 2, 300)]
    b1 = [int(v) for v in rng.integers(0, 50, 300)]
    p0 = [int(v) for v in rng.integers(0, 3, 400)]
    p1 = [int(v) for v in rng.integers(0, 55, 400)]
    return [T.INTEGER, T.INTEGER], (0, 1), [b0, b1], [p0, p1], \
        "past the table's bound"


FALLBACKS = {
    "hashed_three_keys": _hashed, "hashed_two_wide_keys": _two_wide_keys,
    "float_key": _float_key, "range_past_the_bound": _past_the_bound,
    "mixed_signs": _mixed_signs, "sentinel_key": _sentinel_key,
    "packed_wide": _packed_wide,
}


@pytest.mark.parametrize("case", sorted(FALLBACKS))
def test_builds_without_a_table_keep_the_searches(case):
    rng = np.random.default_rng(23)
    types_, keys, build_cols, probe_cols, reason = FALLBACKS[case](rng)
    bridge, _ = _publish(types_, keys, build_cols)
    assert bridge.build.direct is None
    assert reason in bridge.build.direct_fallback
    for join_type in ("inner", "anti"):
        got, op = _join_rows(join_type, types_, keys, build_cols,
                             probe_cols)
        assert got == _brute(join_type, build_cols, probe_cols, keys)
        m = op.metrics()
        assert m["probe_pages"] == 1 and m["direct_probe_pages"] == 0
        assert reason in m["probe_fallback"]
        assert "direct_table_bytes" not in m


def test_range_bound_is_in_bytes(monkeypatch):
    """One constant, in bytes: 1,000 codes need a 4 KiB table."""
    types_ = [T.BIGINT, T.BIGINT]
    cols = [[0, 999], [1, 2]]
    monkeypatch.setattr(J, "DIRECT_TABLE_MAX_BYTES", 4096)
    bridge, _ = _publish(types_, (0,), cols)
    assert bridge.build.direct.nbytes == 4096
    monkeypatch.setattr(J, "DIRECT_TABLE_MAX_BYTES", 4095)
    bridge, _ = _publish(types_, (0,), cols)
    assert bridge.build.direct is None
    assert "key range 1000 past" in bridge.build.direct_fallback


@pytest.mark.parametrize("pool_bytes, table", [(1 << 20, True),
                                               (30_000, False)],
                         ids=["reserved", "refused"])
def test_table_is_reserved_beside_the_build(pool_bytes, table):
    """The table's bytes come out of the builder's memory context; a
    pool without room for them keeps the searches, spills nothing for
    it, and the rows are the same."""
    rng = np.random.default_rng(29)
    types_ = [T.BIGINT, T.BIGINT]
    build_cols = [[int(v) for v in rng.integers(0, 3000, 256)],
                  _payload(256)]
    probe_cols = [[int(v) for v in rng.integers(0, 3100, 300)],
                  _payload(300)]
    pool = QueryMemoryPool(pool_bytes, spill_enabled=True)
    ctx = pool.create_context("join-build")
    bridge, _ = _publish(types_, (0,), build_cols, memory_context=ctx)
    b = bridge.build
    retained = 256 * (13 + 2 * 9)    # key 8 + perm 4 + valid 1
    if table:
        assert b.direct.nbytes == 4 * 4096
        assert ctx.reserved == retained + b.direct.nbytes
    else:
        assert b.direct is None
        assert b.direct_fallback == "memory reservation refused"
        assert ctx.reserved == retained
    assert pool.stats()["spill_events"] == 0
    got, op = _join_rows("inner", types_, (0,), build_cols, probe_cols,
                         bridge=bridge)
    assert got == _brute("inner", build_cols, probe_cols)
    assert (op.metrics()["direct_probe_pages"] == 1) is table
    assert ctx.reserved == 0    # the probe's finish released the build
    pool.close()


def test_hybrid_partitions_keep_the_searches():
    """A build that went partitioned under memory pressure: the resident
    part and every cold partition's pass use their sorted indexes."""
    types_ = [T.BIGINT]
    keys = [int(v) for v in range(4096)]
    pool = QueryMemoryPool(1 << 22, spill_enabled=True)
    ctx = pool.create_context("join-build")
    bridge = J.JoinBridge()
    build = J.HashBuilderOperator(
        types_, [0], bridge, memory_context=ctx,
        hybrid={"fanout": 4, "max_depth": 3, "hint": None})
    build.add_input(_device_page(types_, [keys]))
    with ctx.lock:
        assert build._revoke() > 0
    build.finish()
    build.get_output()
    assert bridge.hybrid.spilled_build
    assert bridge.build.direct is None
    assert bridge.build.direct_fallback == "hybrid partitions"
    probe_cols = [[int(v) for v in range(-10, 4200, 3)]]
    got, op = _join_rows("inner", types_, (0,), [keys], probe_cols,
                         bridge=bridge)
    assert got == _brute("inner", [keys], probe_cols)
    m = op.metrics()
    assert m["direct_probe_pages"] == 0 and m["probe_pages"] > 1
    assert m["probe_fallback"] == "hybrid partitions"
    pool.close()


def test_operators_of_one_build_share_its_table():
    rng = np.random.default_rng(31)
    types_ = [T.BIGINT, T.BIGINT]
    build_cols = [[int(v) for v in rng.integers(0, 300, 400)],
                  _payload(400)]
    bridge, _ = _publish(types_, (0,), build_cols)
    table = bridge.build.direct
    ops = [J.LookupJoinOperator(types_, [0], bridge, jt)
           for jt in ("inner", "semi")]
    page = _device_page(types_, [[1, 2, 3], [4, 5, 6]])
    for op in ops:
        op.add_input(page)
        assert bridge.build.direct is table
        assert op.metrics()["direct_table_bytes"] == table.nbytes


def test_one_padded_length_compiles_one_probe_program():
    """Key range and row count change from build to build; the table's
    length is padded and ``klo`` / the range are traced, so builds whose
    ranges pad alike share the table's and the probe's programs."""
    types_ = [T.BIGINT, T.BIGINT]
    rng = np.random.default_rng(37)
    # warm both programs at these shapes
    builds = [(1000, 3500, 300), (70_000, 72_900, 350), (5, 3900, 280)]
    probe = [int(v) for v in rng.integers(0, 80_000, 512)]
    traced = []
    for klo, khi, rows in builds:
        keys = [klo, khi] + [int(v) for v in
                             rng.integers(klo, khi + 1, rows - 2)]
        before = jit_stats.total_for("join_direct_table",
                                     "join_probe_direct")
        bridge, _ = _publish(types_, (0,), [keys, _payload(rows)])
        b = bridge.build
        assert b.direct.nbytes == 4 * 4096, (klo, khi)
        assert b.key_sorted.shape == (512,)
        pkey, pusable = _probe_keys(types_, (0,), bridge,
                                    [probe, _payload(512)])
        got = J._probe_direct_counts(b.direct.offsets, b.direct.span,
                                     pkey, pusable)
        want = J._probe_counts(b.key_sorted, pkey, pusable)
        np.testing.assert_array_equal(np.asarray(got[0]),
                                      np.asarray(want[0]))
        np.testing.assert_array_equal(np.asarray(got[1]),
                                      np.asarray(want[1]))
        traced.append(jit_stats.total_for("join_direct_table",
                                          "join_probe_direct") - before)
    assert traced[0] <= 2 and traced[1:] == [0, 0], traced


# -- statements against sqlite ----------------------------------------------

SCHEMA = "tiny"

Q3 = """select l_orderkey, sum(l_extendedprice * (1 - l_discount)) as revenue,
    o_orderdate, o_shippriority
from customer, orders, lineitem
where c_mktsegment = 'BUILDING' and c_custkey = o_custkey
  and l_orderkey = o_orderkey and o_orderdate < date '1995-03-15'
  and l_shipdate > date '1995-03-15'
group by l_orderkey, o_orderdate, o_shippriority
order by revenue desc, o_orderdate limit 10"""

Q13 = """select c_count, count(*) as custdist
from (select c_custkey, count(o_orderkey) as c_count
      from customer left outer join orders
        on c_custkey = o_custkey
       and o_comment not like '%special%requests%'
      group by c_custkey) as c_orders
group by c_count order by custdist desc, c_count desc"""

STATEMENTS = {
    "inner_q3": (Q3, True),
    "left_q13": (Q13, True),
    "right_q13": ("""select c_count, count(*) as custdist
from (select c_custkey, count(o_orderkey) as c_count
      from (select * from orders
            where o_comment not like '%special%requests%') o
      right outer join customer on c_custkey = o_custkey
      group by c_custkey) as c_orders
group by c_count order by custdist desc, c_count desc""", True),
    "full_orders_customer": ("""select count(*), count(c_custkey),
    count(o_orderkey), sum(o_totalprice)
from customer full outer join
     (select * from orders where o_orderdate < date '1993-01-01') o
  on c_custkey = o_custkey""", False),
    "semi_orders_with_late_lines": ("""select o_orderpriority, count(*)
from orders
where o_orderdate < date '1995-03-15'
  and o_orderkey in (select l_orderkey from lineitem
                     where l_shipdate > date '1995-03-15')
group by o_orderpriority order by o_orderpriority""", True),
    "anti_customers_without_orders": ("""select c_mktsegment, count(*)
from customer
where c_custkey not in (select o_custkey from orders)
group by c_mktsegment order by c_mktsegment""", True),
}


# the statements that ran the one-hot matmul probe while the planner
# chose a join's kernel from estimated key ranges (before PR 46), on the
# schema where it chose it: dimension joins over a few dozen keys, semi
# and anti joins among them.  (sql, ordered, joins whose build key is
# two 64-bit columns — ``partsupp``'s — which no table covers)
MICRO_STATEMENTS = {
    "micro_customer_orders": ("""select c.c_custkey, o.o_orderkey
from customer c join orders o on c.c_custkey = o.o_custkey""", False, 0),
    **{f"micro_q{q:02d}": (TPCH_QUERIES[q], True, hashed)
       for q, hashed in [(5, 1), (8, 0), (10, 0), (11, 0), (15, 0),
                         (16, 0), (20, 1), (21, 0)]},
}


@pytest.fixture(scope="module")
def conn():
    return TpchConnector(page_rows=8192)


@pytest.fixture(scope="module")
def runner(conn):
    return LocalQueryRunner({"tpch": conn},
                            Session(catalog="tpch", schema=SCHEMA))


@pytest.fixture(scope="module")
def oracle(conn):
    return load_sqlite(conn, SCHEMA)


@pytest.fixture(scope="module")
def micro(conn):
    """(runner, sqlite) over ``tpch.micro``."""
    return (LocalQueryRunner({"tpch": conn},
                             Session(catalog="tpch", schema="micro")),
            load_sqlite(conn, "micro"))


@pytest.mark.parametrize("schema, sql, ordered, hashed", [
    pytest.param(SCHEMA, *STATEMENTS[name], 0, id=name)
    for name in sorted(STATEMENTS)] + [
    pytest.param("micro", *MICRO_STATEMENTS[name], id=name)
    for name in sorted(MICRO_STATEMENTS)])
def test_statements_probe_by_direct_address(schema, sql, ordered, hashed,
                                            runner, oracle, micro):
    if schema == "micro":
        runner, oracle = micro
    res = runner.execute(sql)
    assert_same(res, oracle.execute(to_sqlite(sql)).fetchall(), ordered)
    names = [s["name"] for s in res.stats["trace"]]
    joins = [s["attrs"] for s in res.stats["trace"]
             if s["name"] == "LookupJoinOperator"]
    assert joins and not [n for n in names
                          if "Join" in n and n != "LookupJoinOperator"], names
    by_search = [a for a in joins if "probe_fallback" in a]
    assert len(by_search) == hashed, by_search
    for attrs in joins:
        assert attrs["probe_pages"] > 0
        if attrs in by_search:
            assert attrs["probe_fallback"] == "hashed key mode"
            assert attrs["direct_probe_pages"] == 0
            continue
        assert attrs["direct_probe_pages"] == attrs["probe_pages"], attrs
        assert attrs["direct_table_bytes"] > 0


def test_explain_analyze_says_which_probe_ran(runner):
    text = "\n".join(r[0] for r in runner.execute(
        "explain analyze " + Q3).rows)
    assert text.count("[probe direct ") == 2, text
    assert "join_key_range 2x" in text
    hashed = """select count(*) from lineitem l, partsupp ps
where l.l_partkey = ps.ps_partkey and l.l_suppkey = ps.ps_suppkey"""
    text = "\n".join(r[0] for r in runner.execute(
        "explain analyze " + hashed).rows)
    assert "[probe direct 0/" in text, text
    assert "sorted index: hashed key mode" in text
