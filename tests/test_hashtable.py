"""Hash-based vs sort-based grouping cross-checks.

The vectorized open-addressing table (ops/hashtable.py) is the default
grouping path; the sort path is retained as the correctness oracle.
These tests drive both paths over adversarial key distributions —
all-null keys, a single group, near-capacity cardinality (forcing
linear-probe chains at load factor 0.5), multi-key pages, int64 and
float32 state columns — and require identical results.
"""

import numpy as np
import jax.numpy as jnp
import pytest

from trino_tpu import types as T
from trino_tpu.block import DevicePage, Dictionary, Page, padded_size
from trino_tpu.ops.aggregation import AggCall, HashAggregationOperator, \
    resolve_agg_type
from trino_tpu.ops.hashtable import (DENSE_GROUPS, PROBE_ROUNDS,
                                     _hash_group_ids_impl,
                                     _hash_segment_reduce_impl,
                                     _mix_operands, _probe_widths,
                                     hash_group_ids, hash_segment_reduce,
                                     hashable_key_types)
from trino_tpu.ops.sortkeys import group_operands


# ---------------------------------------------------------------- primitive


def _reference_gids(keys_cols, nulls_cols, n):
    """First-occurrence dense group ids over tuples of (is_null, value)."""
    seen = {}
    out = []
    for i in range(n):
        k = tuple((bool(nc[i]), None if nc[i] else int(kc[i]))
                  for kc, nc in zip(keys_cols, nulls_cols))
        out.append(seen.setdefault(k, len(seen)))
    return out, len(seen)


@pytest.mark.parametrize("nvals,n,cap", [
    (4, 13, 16),          # few groups
    (1, 13, 16),          # single group
    (10**9, 61, 64),      # near-capacity: all keys distinct
    (50, 1000, 1024),
    (10**9, 1021, 1024),  # near-capacity at a real page size
])
def test_hash_gids_match_reference(nvals, n, cap):
    rng = np.random.default_rng(n * 31 + nvals % 97)
    keys = rng.integers(-nvals, nvals, size=cap).astype(np.int64)
    nulls = rng.random(cap) < 0.15
    valid = np.zeros(cap, dtype=bool)
    valid[:n] = True
    ops = group_operands(jnp.asarray(keys), jnp.asarray(nulls), T.BIGINT)
    gid, group_rows, ngroups, overflow, *_ = hash_group_ids(
        tuple(ops), jnp.asarray(valid))
    gid, group_rows = np.asarray(gid), np.asarray(group_rows)
    assert not bool(overflow)
    ref, nref = _reference_gids([keys], [nulls], n)
    assert int(ngroups) == nref
    assert gid[:n].tolist() == ref
    assert (gid[n:] == cap).all()
    for g in range(nref):
        r = group_rows[g]
        assert gid[r] == g and (gid[:r] != g).all(), \
            "group_rows must point at the FIRST row of each group"


def test_hash_gids_multi_key_and_all_null():
    cap = 64
    n = 50
    rng = np.random.default_rng(7)
    k1 = rng.integers(0, 5, size=cap).astype(np.int64)
    k2 = rng.integers(0, 4, size=cap).astype(np.int64)
    n1 = np.zeros(cap, dtype=bool)
    n2 = np.ones(cap, dtype=bool)     # second key entirely NULL
    valid = np.arange(cap) < n
    ops = group_operands(jnp.asarray(k1), jnp.asarray(n1), T.BIGINT) \
        + group_operands(jnp.asarray(k2), jnp.asarray(n2), T.BIGINT)
    gid, _rows, ngroups, overflow, *_ = hash_group_ids(
        tuple(ops), jnp.asarray(valid))
    assert not bool(overflow)
    ref, nref = _reference_gids([k1, k2], [n1, n2], n)
    assert int(ngroups) == nref  # all-null key contributes one dimension
    assert np.asarray(gid)[:n].tolist() == ref


def test_probe_budget_overflow_is_flagged():
    """With a 1-round budget, near-capacity distinct keys must collide
    and exact mode must report overflow instead of wrong gids; the
    non-exact (partial) mode resolves by singleton groups instead."""
    cap = 256
    keys = np.arange(cap, dtype=np.int64) * 7919
    valid = np.ones(cap, dtype=bool)
    ops = group_operands(jnp.asarray(keys), None, T.BIGINT)
    _gid, _rows, _ng, overflow, *_ = hash_group_ids(
        tuple(ops), jnp.asarray(valid), rounds=1, exact=True)
    assert bool(overflow)
    gid, _rows, ngroups, overflow, *_ = hash_group_ids(
        tuple(ops), jnp.asarray(valid), rounds=1, exact=False)
    assert not bool(overflow)
    # every row got SOME group; duplicates allowed, coverage is dense
    gid = np.asarray(gid)
    ng = int(ngroups)
    assert ng >= cap // 2 and (gid < ng).all()


def test_hashable_key_types_gate():
    assert hashable_key_types([T.BIGINT, T.varchar_type(10), T.DATE])
    assert not hashable_key_types([T.BIGINT, T.DOUBLE])
    assert not hashable_key_types([T.REAL])
    assert hashable_key_types([])


@pytest.mark.parametrize("live", ["some", "all", "none"])
def test_keyless_gids_in_closed_form(live):
    """No key columns: every valid row is group 0, the first valid row
    represents it, an empty page has no group — the keyed contract
    without a table."""
    cap = 64
    valid = {"some": np.arange(cap) % 5 == 3, "all": np.ones(cap, bool),
             "none": np.zeros(cap, bool)}[live]
    gid, group_rows, ngroups, overflow, *_ = hash_group_ids(
        (), jnp.asarray(valid))
    assert not bool(overflow)
    assert int(ngroups) == int(valid.any())
    assert gid.dtype == jnp.int32 and group_rows.dtype == jnp.int32
    assert np.asarray(gid).tolist() == np.where(valid, 0, cap).tolist()
    first = int(np.argmax(valid)) if valid.any() else 0
    assert np.asarray(group_rows).tolist() == [first] + [0] * (cap - 1)


# ------------------------------------------- the probe's narrow buffer


def _parent_loop(key_ops, valid, rounds=PROBE_ROUNDS, exact=True):
    """The probe as it ran before the narrowing, plainly: every round
    over every lane still unresolved, an empty slot to the smallest row
    probing it. Returns the four outputs and the rounds run."""
    ops = [np.asarray(op) for op in key_ops]
    valid = np.asarray(valid)
    cap = len(valid)
    tsize = 1 << max(2 * cap - 1, 1).bit_length()
    h = np.asarray(_mix_operands(tuple(key_ops), cap))
    slot0 = (h & np.uint64(tsize - 1)).astype(np.int64)
    row = np.arange(cap)
    table = np.full(tsize, cap)
    rep = np.where(valid, cap, row)
    r = 0
    while r < rounds and (rep == cap).any():
        act = np.flatnonzero(rep == cap)
        slot = (slot0[act] + r) & (tsize - 1)
        empty = table[slot] == cap
        claim = np.full(tsize, cap)
        np.minimum.at(claim, slot[empty], act[empty])
        winner = empty & (claim[slot] == act)
        table[slot[winner]] = act[winner]
        owner = table[slot]
        eq = np.ones(len(act), dtype=bool)
        for op in ops:
            eq &= op[act] == op[owner]
        rep[act[eq]] = owner[eq]
        r += 1
    unresolved = rep == cap
    overflow = bool(exact and unresolved.any())
    if not exact:
        rep = np.where(unresolved, row, rep)
    leader = valid & (rep == row)
    prefix = np.cumsum(leader) - 1
    gid = np.where(valid & (rep < cap), prefix[np.minimum(rep, cap - 1)],
                   cap)
    group_rows = np.zeros(cap + 1, dtype=np.int64)
    group_rows[np.where(leader, prefix, cap)] = row
    return (gid, group_rows[:cap], int(leader.sum()), overflow), r


def _assert_equals_parent_loop(got, key_ops, valid, **kwargs):
    """All four outputs of ``got`` bit for bit, and its two counters'
    sum; returns (rounds_full, rounds_narrow)."""
    gid, group_rows, ngroups, overflow, full, narrow = got
    want, rounds = _parent_loop(key_ops, valid, **kwargs)
    assert gid.dtype == jnp.int32 and group_rows.dtype == jnp.int32
    assert overflow.dtype == jnp.bool_ and full.dtype == narrow.dtype
    assert np.array_equal(np.asarray(gid), want[0])
    assert np.array_equal(np.asarray(group_rows), want[1])
    assert (int(ngroups), bool(overflow)) == want[2:]
    assert int(full) + int(narrow) == rounds
    return int(full), int(narrow)


def _keys_sharing_slots(cap, chains, spacing, seed):
    """Distinct int64 keys in chains: ``chains`` = [(how many chains,
    keys a chain)], the keys of a chain all hashing to one first slot of
    a ``cap``-lane page's table, the chains' slots ``spacing`` apart."""
    tsize = 2 * cap
    candidates = np.arange(60 * cap, dtype=np.int64)
    ops = group_operands(jnp.asarray(candidates), None, T.BIGINT)
    h = np.asarray(_mix_operands(tuple(ops), len(candidates)))
    slot0 = (h & np.uint64(tsize - 1)).astype(np.int64)
    order = np.argsort(slot0, kind="stable")
    starts = np.searchsorted(slot0[order], np.arange(tsize))
    keys, slot = [], 0
    for count, length in chains:
        for _ in range(count):
            at = starts[slot]
            assert slot0[order[at + length - 1]] == slot
            keys.extend(candidates[order[at:at + length]])
            slot += spacing
    assert slot <= tsize
    keys = np.asarray(keys, dtype=np.int64)
    return np.random.default_rng(seed).permutation(keys)


def _page_of(keys, cap):
    """Key operands and valid mask of ``keys`` in the first lanes of a
    ``cap``-lane page."""
    padded = np.zeros(cap, dtype=np.int64)
    padded[:len(keys)] = keys
    ops = group_operands(jnp.asarray(padded), None, T.BIGINT)
    return tuple(ops), np.arange(cap) < len(keys)


def _late_narrowing_page(cap=8192):
    """1,150 chains of six keys and 100 of ten: over ``cap // 8`` lanes
    stay unresolved through round 5, 400 go on from round 7."""
    assert _probe_widths(cap)[1] == cap // 8 == 1024
    return _page_of(_keys_sharing_slots(
        cap, [(1150, 6), (100, 10)], spacing=13, seed=cap), cap)


def _narrow_page(cap, shape):
    rng = np.random.default_rng(cap + len(shape))
    valid = np.ones(cap, dtype=bool)
    nulls = [None]
    if shape == "all_distinct":
        keys = [rng.permutation(cap).astype(np.int64) * 7919]
    elif shape == "four_groups":
        keys = [rng.integers(0, 4, size=cap).astype(np.int64)]
    elif shape == "quarter_groups_scattered_invalid":
        keys = [rng.integers(0, cap // 4, size=cap).astype(np.int64)]
        valid = rng.random(cap) >= 0.15
    else:
        assert shape == "two_columns_with_nulls"
        keys = [rng.integers(0, 300, size=cap).astype(np.int64),
                rng.integers(0, 40, size=cap).astype(np.int64)]
        nulls = [jnp.asarray(rng.random(cap) < 0.1),
                 jnp.asarray(rng.random(cap) < 0.3)]
        valid = rng.random(cap) >= 0.05
    ops = []
    for k, n in zip(keys, nulls):
        ops.extend(group_operands(jnp.asarray(k), n, T.BIGINT))
    null_cols = [np.zeros(cap, dtype=bool) if n is None else np.asarray(n)
                 for n in nulls]
    return tuple(ops), valid, keys, null_cols


@pytest.mark.parametrize("exact", [True, False])
@pytest.mark.parametrize("shape", [
    "all_distinct", "four_groups", "quarter_groups_scattered_invalid",
    "two_columns_with_nulls"])
@pytest.mark.parametrize("cap", [8192, 65536])
def test_narrowed_probe_equals_the_full_width_loop(cap, shape, exact):
    """Pages wide enough to narrow: every output equal to the loop that
    ran every round over the whole page, and the gids to first
    occurrence."""
    assert len(_probe_widths(cap)) > 1
    ops, valid, keys, nulls = _narrow_page(cap, shape)
    got = hash_group_ids(ops, jnp.asarray(valid), exact=exact)
    full, narrow = _assert_equals_parent_loop(got, ops, valid, exact=exact)
    assert not bool(got[3])
    assert (full, narrow) == (1, 0) if shape == "four_groups" \
        else narrow > 0
    if cap == 8192:
        live = np.flatnonzero(valid)
        ref, nref = _reference_gids([k[live] for k in keys],
                                    [n[live] for n in nulls], len(live))
        assert int(got[2]) == nref
        assert np.asarray(got[0])[live].tolist() == ref


@pytest.mark.parametrize("exact", [True, False])
@pytest.mark.parametrize("live,groups", [(7, 7), (900, 4), (3000, 3000)])
def test_sparse_page_probes_in_the_narrow_buffer_alone(live, groups, exact):
    """Few valid rows scattered over a wide page (a selective join's
    output): they fit the buffer before any round, so none runs at the
    page's width — and 7 rows skip the first buffer's width too."""
    cap = 65536
    assert _probe_widths(cap) == (65536, 8192, 1024)
    rng = np.random.default_rng(live)
    valid = np.zeros(cap, dtype=bool)
    valid[rng.choice(cap, live, replace=False)] = True
    keys = rng.integers(0, 1 << 40, size=groups)[
        rng.integers(0, groups, size=cap)].astype(np.int64)
    ops = tuple(group_operands(jnp.asarray(keys), None, T.BIGINT)
                + group_operands(jnp.asarray(keys % 11), None, T.BIGINT))
    got = hash_group_ids(ops, jnp.asarray(valid), exact=exact)
    full, narrow = _assert_equals_parent_loop(got, ops, valid, exact=exact)
    assert full == 0 and narrow >= 1 and not bool(got[3])


def test_probe_stays_wide_while_many_rows_are_unresolved():
    """Keys built to share first slots: the count on the device keeps
    the page's own width for six rounds, and the narrow buffer takes the
    long chains' last rounds."""
    ops, valid = _late_narrowing_page()
    got = hash_group_ids(ops, jnp.asarray(valid))
    full, narrow = _assert_equals_parent_loop(got, ops, valid)
    assert full >= 6 and narrow >= 3 and not bool(got[3])
    assert int(got[2]) == int(valid.sum())


def _chain_of_three_page(cap=8192):
    """Four keys over most lanes and one chain of three: round 1 leaves
    two rows, a budget of two rounds ends in the narrow buffer."""
    rng = np.random.default_rng(3)
    chain = _keys_sharing_slots(cap, [(1, 3)], spacing=1, seed=3)
    keys = rng.integers(-4, 0, size=cap - 100).astype(np.int64)
    keys[rng.choice(len(keys), 3, replace=False)] = chain
    return _page_of(keys, cap)


@pytest.mark.parametrize("exact", [True, False])
@pytest.mark.parametrize("ends_in", ["narrow_buffer", "full_width"])
def test_probe_budget_is_one_budget_across_widths(ends_in, exact):
    """``rounds=2`` against chains of three and more: ``overflow`` in
    exact mode, singleton groups in non-exact mode, on the lanes the
    full-width loop leaves — also where the budget ends with more rows
    unresolved than the narrow buffer holds."""
    ops, valid = _chain_of_three_page() if ends_in == "narrow_buffer" \
        else _late_narrowing_page()
    got = hash_group_ids(ops, jnp.asarray(valid), rounds=2, exact=exact)
    full, narrow = _assert_equals_parent_loop(got, ops, valid, rounds=2,
                                              exact=exact)
    assert (full, narrow) == ((1, 1) if ends_in == "narrow_buffer"
                              else (2, 0))
    assert bool(got[3]) == exact
    if not exact:
        # every row has a group; the chain's last rows lead their own
        assert (np.asarray(got[0])[valid] < int(got[2])).all()


@pytest.mark.parametrize("exact", [True, False])
def test_narrowed_probe_under_vmap_with_a_page_that_needs_no_buffer(exact):
    """Two pages batched, one resolved in its first round: the batched
    predicate makes the ``cond`` a select and the loops run to the
    slower page — each page's outputs are still its own."""
    import jax

    cap = 8192
    pages = [_narrow_page(cap, shape)[:2]
             for shape in ("four_groups", "all_distinct")]
    ops = tuple(jnp.stack(cols) for cols in zip(*(p[0] for p in pages)))
    valid = jnp.stack([jnp.asarray(p[1]) for p in pages])
    got = jax.jit(jax.vmap(
        lambda o, v: _hash_group_ids_impl(o, v, exact=exact)))(ops, valid)
    rounds = [_assert_equals_parent_loop(
        tuple(out[i] for out in got), *pages[i], exact=exact)
        for i in range(2)]
    assert rounds[0] == (1, 0) and rounds[1][1] > 0


def test_probe_rounds_reach_the_operator_with_no_read_of_their_own():
    """A many-group aggregation over pages wide enough to narrow: the
    span carries the rounds of every page and merge whose flags the step
    read, most of them narrow, and the reads are one a page and one a
    merge as before."""
    from trino_tpu.connectors.tpch import TpchConnector
    from trino_tpu.runner import LocalQueryRunner
    from trino_tpu.sql.analyzer import Session

    runner = LocalQueryRunner(
        {"tpch": TpchConnector(page_rows=8192)},
        Session(catalog="tpch", schema="tiny"), desired_splits=1)
    res = runner.execute("select l_orderkey, sum(l_quantity) from lineitem "
                         "group by l_orderkey")
    assert len(res.rows) == 15000
    spans = res.stats["trace"]
    root, = [s for s in spans if s["name"] == "statement"]
    agg, = [s["attrs"] for s in spans if "probe_rounds" in s["attrs"]]
    calls = agg["partial_lanes"]["pages"] + agg["merge_calls"]
    assert agg["partial_lanes"]["pages"] > 1 and agg["merge_calls"] >= 1
    assert root["attrs"]["host_sync_by_why"]["agg_overflow"][0] == calls
    assert agg["probe_rounds"] > 2 * calls
    assert 0 < agg["probe_rounds_narrow"] <= agg["probe_rounds"] - calls
    text = runner.execute(
        "explain analyze select l_orderkey, sum(l_quantity) from lineitem "
        "group by l_orderkey").rows
    assert f"[probe rounds {agg['probe_rounds']}, " \
        f"{agg['probe_rounds_narrow']} narrow]" in \
        "\n".join(r[0] for r in text)


_SEGMENT_OPS = {"sum": "segment_sum", "min": "segment_min",
                "max": "segment_max"}


def _gid_page(rng, cap, ngroups, dtype):
    """A page of ``cap`` lanes with exactly ``ngroups`` groups, a fifth
    of its lanes invalid (gid == cap); none valid when ``ngroups`` is 0."""
    if ngroups == 0:
        valid = np.zeros(cap, dtype=bool)
        gid = np.full(cap, cap)
    else:
        valid = rng.random(cap) < 0.8
        gid = rng.integers(0, ngroups, size=cap)
        at = rng.permutation(cap)[:ngroups]
        gid[at], valid[at] = np.arange(ngroups), True
        gid = np.where(valid, gid, cap)
    col = rng.integers(-10**6, 10**6, size=cap).astype(dtype)
    return jnp.asarray(gid.astype(np.int32)), jnp.asarray(col)


@pytest.mark.parametrize("dtype", [np.int64, np.int32])
@pytest.mark.parametrize("kind", ["sum", "min", "max"])
@pytest.mark.parametrize("ngroups", [0, 1, 4, DENSE_GROUPS,
                                     DENSE_GROUPS + 1, 4 * DENSE_GROUPS])
def test_segment_reduce_branches_equal_segment_ops(ngroups, kind, dtype):
    """Both branches of the reduce and the boundary between them: the
    states equal ``jax.ops.segment_*`` lane for lane — groups, empty
    lanes (the kind's identity) and the all-invalid page."""
    import jax

    cap = 8 * DENSE_GROUPS
    rng = np.random.default_rng(ngroups * 7 + len(kind))
    gid, col = _gid_page(rng, cap, ngroups, dtype)
    key = jnp.arange(cap, dtype=jnp.int64) * 3
    rows = jnp.asarray(rng.permutation(cap).astype(np.int32))
    key_null = jnp.asarray(rng.random(cap) < 0.3)
    keys, key_nulls, (got,), out_valid = hash_segment_reduce(
        gid, rows, jnp.int32(ngroups), (key,), (key_null,), (col,), (kind,))
    want = getattr(jax.ops, _SEGMENT_OPS[kind])(
        col, gid, num_segments=cap + 1)[:cap]
    assert got.dtype == want.dtype and got.shape == want.shape
    assert np.array_equal(np.asarray(got), np.asarray(want))
    live = np.arange(cap) < ngroups
    assert np.array_equal(np.asarray(out_valid), live)
    # group keys: the representative row's, row 0's past the groups
    at = np.where(live, np.asarray(rows), 0)
    assert np.array_equal(np.asarray(keys[0]), np.asarray(key)[at])
    assert np.array_equal(np.asarray(key_nulls[0]),
                          np.asarray(key_null)[at] & live)


def _reduce_jaxpr(*args):
    import jax

    return str(jax.make_jaxpr(
        lambda *a: _hash_segment_reduce_impl(*a[:-1], kinds=a[-1]),
        static_argnums=6)(*args))


def test_segment_reduce_is_dense_by_shape_on_a_narrow_page():
    """A page no wider than the limit cannot have more groups than it:
    no ``cond`` is traced, and the states still equal the scatter's."""
    import jax

    cap = DENSE_GROUPS // 2
    rng = np.random.default_rng(9)
    gid, col = _gid_page(rng, cap, cap, np.int64)
    none = jnp.zeros(cap, dtype=bool)
    args = (gid, jnp.zeros(cap, jnp.int32), jnp.int32(cap), (gid,),
            (none,), (col, col), ("sum", "max"))
    jaxpr = _reduce_jaxpr(*args)
    assert "cond" not in jaxpr and "scatter" not in jaxpr
    got = hash_segment_reduce(*args)[2]
    assert np.array_equal(got[0], jax.ops.segment_sum(
        col, gid, num_segments=cap + 1)[:cap])
    assert np.array_equal(got[1], jax.ops.segment_max(
        col, gid, num_segments=cap + 1)[:cap])


@pytest.mark.parametrize("ngroups", [0, 1])
def test_keyless_reduce_is_one_masked_reduction_with_no_branch(ngroups):
    """No key column, so one group at most, known at trace time: no
    ``cond``, no scatter, whatever the page's width; lane 0 holds the
    reduction, the other lanes the kind's identity."""
    import jax

    cap = 8 * DENSE_GROUPS
    rng = np.random.default_rng(ngroups)
    gid, col = _gid_page(rng, cap, ngroups, np.int64)
    args = (gid, jnp.zeros(cap, jnp.int32), jnp.int32(ngroups), (), (),
            (col, col, col), ("sum", "min", "max"))
    jaxpr = _reduce_jaxpr(*args)
    assert "cond" not in jaxpr and "scatter" not in jaxpr
    keys, key_nulls, got, out_valid = hash_segment_reduce(*args)
    assert keys == () and key_nulls == ()
    assert int(np.asarray(out_valid).sum()) == ngroups
    for kind, r in zip(("sum", "min", "max"), got):
        want = getattr(jax.ops, _SEGMENT_OPS[kind])(
            col, gid, num_segments=cap + 1)[:cap]
        assert np.array_equal(np.asarray(r), np.asarray(want)), kind


def test_segment_reduce_under_vmap_with_lanes_on_both_sides():
    """The admission batcher's ``jit(vmap(lane))``: the batched group
    count turns the ``cond`` into a select, and a batch holding a
    few-group lane and a many-group lane answers both as the scatter."""
    import jax

    cap = 8 * DENSE_GROUPS
    rng = np.random.default_rng(13)
    counts = [3, DENSE_GROUPS + 5, DENSE_GROUPS, 0]
    pages = [_gid_page(rng, cap, n, np.int64) for n in counts]
    gids = jnp.stack([g for g, _ in pages])
    cols = jnp.stack([c for _, c in pages])
    rows = jnp.zeros((len(counts), cap), dtype=jnp.int32)

    def lane(gid, group_rows, ngroups, col):
        return _hash_segment_reduce_impl(
            gid, group_rows, ngroups, (col,), (col < 0,), (col, col),
            ("sum", "min"), pallas="")[2]

    sums, mins = jax.jit(jax.vmap(lane))(
        gids, rows, jnp.asarray(counts, dtype=jnp.int32), cols)
    for i, (gid, col) in enumerate(pages):
        assert np.array_equal(sums[i], jax.ops.segment_sum(
            col, gid, num_segments=cap + 1)[:cap]), counts[i]
        assert np.array_equal(mins[i], jax.ops.segment_min(
            col, gid, num_segments=cap + 1)[:cap]), counts[i]


# ---------------------------------------------------------- operator oracle


def _sorted_rows(rows):
    return sorted(rows, key=lambda r: tuple(
        (v is None, 0 if v is None else v) for v in r))


def _drain(op):
    """Finish ``op`` and return its output rows, sorted."""
    op.finish()
    pages = []
    while not op.is_finished():
        p = op.get_output()
        if p is not None:
            pages.append(p.to_page())
    return _sorted_rows(Page.concat(pages).to_rows())


def _run_single(input_types, columns, group_channels, aggs,
                hash_grouping, page_rows=None):
    """Run a single-step aggregation over the columns split into pages."""
    n = len(columns[0])
    page_rows = page_rows or n
    # one pool per string column, shared across pages (the engine's
    # dictionary-stability contract)
    dicts = [Dictionary() if t.is_pooled else None for t in input_types]
    op = HashAggregationOperator(input_types, group_channels, aggs,
                                 "single", hash_grouping=hash_grouping)
    for lo in range(0, n, page_rows):
        chunk = [c[lo:lo + page_rows] for c in columns]
        page = Page.from_pylists(input_types, chunk, dicts)
        op.add_input(DevicePage.from_page(page))
    return _drain(op)


def _assert_rows_equal(a, b):
    assert len(a) == len(b)
    for ra, rb in zip(a, b):
        assert len(ra) == len(rb)
        for va, vb in zip(ra, rb):
            if isinstance(va, float) and va is not None and vb is not None:
                assert vb == pytest.approx(va, rel=1e-9), (ra, rb)
            else:
                assert va == vb, (ra, rb)


AGG_SUITE = [
    AggCall("count_star", None, None, T.BIGINT),
    AggCall("sum", 1, T.BIGINT, resolve_agg_type("sum", T.BIGINT)),
    AggCall("sum", 2, T.REAL, resolve_agg_type("sum", T.REAL)),
    AggCall("min", 1, T.BIGINT, T.BIGINT),
    AggCall("max", 2, T.REAL, T.REAL),
    AggCall("count", 2, T.REAL, T.BIGINT),
]
AGG_TYPES = [T.BIGINT, T.BIGINT, T.REAL]


def _payload(rng, nkeys):
    s1 = [int(v) if rng.random() > 0.1 else None
          for v in rng.integers(-1000, 1000, size=nkeys)]
    s2 = [float(np.float32(v)) if rng.random() > 0.1 else None
          for v in rng.normal(size=nkeys)]
    return s1, s2


@pytest.mark.parametrize("case", [
    "all_null", "single_group", "near_capacity", "mixed"])
def test_hash_vs_sort_single_key(case):
    rng = np.random.default_rng(hash(case) % 2**32)
    n = 700
    if case == "all_null":
        keys = [None] * n
    elif case == "single_group":
        keys = [42] * n
    elif case == "near_capacity":
        keys = [int(v) for v in np.arange(n) * 1_000_003]
    else:
        keys = [int(v) if rng.random() > 0.2 else None
                for v in rng.integers(0, 40, size=n)]
    s1, s2 = _payload(rng, n)
    cols = [keys, s1, s2]
    for page_rows in (n, 128):
        got = _run_single(AGG_TYPES, cols, [0], AGG_SUITE, True, page_rows)
        want = _run_single(AGG_TYPES, cols, [0], AGG_SUITE, False,
                           page_rows)
        _assert_rows_equal(got, want)


def test_hash_vs_sort_multi_key_with_strings():
    rng = np.random.default_rng(11)
    n = 500
    vt = T.varchar_type(8)
    types = [T.BIGINT, vt, T.BIGINT, T.REAL]
    k1 = [int(v) if rng.random() > 0.15 else None
          for v in rng.integers(0, 9, size=n)]
    k2 = [rng.choice(["aa", "bb", "cc", "dd"]) if rng.random() > 0.15
          else None for _ in range(n)]
    s1, s2 = _payload(rng, n)
    aggs = [
        AggCall("count_star", None, None, T.BIGINT),
        AggCall("sum", 2, T.BIGINT, resolve_agg_type("sum", T.BIGINT)),
        AggCall("min", 3, T.REAL, T.REAL),
        AggCall("max", 1, vt, vt),   # string min/max rides rank LUTs
    ]
    cols = [k1, k2, s1, s2]
    got = _run_single(types, cols, [0, 1], aggs, True, 128)
    want = _run_single(types, cols, [0, 1], aggs, False, 128)
    _assert_rows_equal(got, want)


def test_float_keys_fall_back_to_sort():
    """DOUBLE grouping keys are not hashable (no f64<->u64 bitcast on
    TPU): the operator must silently take the sort path and still be
    correct."""
    n = 200
    rng = np.random.default_rng(3)
    types = [T.DOUBLE, T.BIGINT]
    keys = [float(v) for v in rng.integers(0, 10, size=n)]
    s1 = [int(v) for v in rng.integers(0, 100, size=n)]
    aggs = [AggCall("sum", 1, T.BIGINT,
                    resolve_agg_type("sum", T.BIGINT))]
    op = HashAggregationOperator(types, [0], aggs, "single",
                                 hash_grouping=True)
    page = Page.from_pylists(types, [keys, s1])
    op.add_input(DevicePage.from_page(page))
    op.finish()
    out = op.get_output().to_page()
    assert op.path_counts["hash"] == 0 and op.path_counts["sort"] > 0
    assert out.num_rows == 10


def test_overflow_falls_back_to_sort_oracle(monkeypatch):
    """Exact-mode probe-budget overflow must transparently re-group via
    the sort path with identical results."""
    from functools import partial

    from trino_tpu.ops import aggregation as agg_mod
    from trino_tpu.ops import hashtable

    monkeypatch.setattr(
        agg_mod, "hash_group_ids",
        partial(hashtable.hash_group_ids, rounds=1))
    rng = np.random.default_rng(5)
    n = 900
    keys = [int(v) for v in np.arange(n) * 7919]  # all distinct
    s1, s2 = _payload(rng, n)
    cols = [keys, s1, s2]
    got = _run_single(AGG_TYPES, cols, [0], AGG_SUITE, True, 256)
    monkeypatch.undo()
    want = _run_single(AGG_TYPES, cols, [0], AGG_SUITE, False, 256)
    _assert_rows_equal(got, want)


def _paged_op(types, group, aggs, pages, hash_grouping):
    """A ``single`` aggregation fed ``pages`` of (columns, live mask or
    None); returns the operator, not yet drained."""
    op = HashAggregationOperator(types, group, aggs, "single",
                                 hash_grouping=hash_grouping)
    for columns, live in pages:
        dp = DevicePage.from_page(Page.from_pylists(types, columns))
        if live is not None:
            mask = np.zeros(dp.capacity, dtype=bool)
            mask[:len(live)] = live
            dp = DevicePage(dp.types, dp.cols, dp.nulls,
                            jnp.asarray(mask), dp.dictionaries)
        op.add_input(dp)
    return op


def test_keyless_aggregate_reduces_densely_and_equals_sort_path():
    """A global aggregate over several pages, one of them empty: every
    page and the merge count as ``dense`` (they are ``hash`` pages whose
    states took no scatter) and the answer is the sort path's."""
    rng = np.random.default_rng(29)
    types = [T.BIGINT, T.REAL]
    aggs = [AggCall("count_star", None, None, T.BIGINT),
            AggCall("sum", 0, T.BIGINT, resolve_agg_type("sum", T.BIGINT)),
            AggCall("min", 0, T.BIGINT, T.BIGINT),
            AggCall("max", 1, T.REAL, T.REAL)]

    def columns(n):
        return [[int(v) if rng.random() > 0.1 else None
                 for v in rng.integers(-500, 500, size=n)],
                [float(np.float32(v)) for v in rng.normal(size=n)]]

    pages = [(columns(300), None), (columns(200), np.zeros(200, bool)),
             (columns(700), None), (columns(90), None)]
    op = _paged_op(types, [], aggs, pages, True)
    paths = op.metrics()["grouping_paths"]
    assert paths == {"hash": len(pages), "dense": len(pages)}
    got = _drain(op)
    assert op.metrics()["grouping_paths"] == {
        "hash": len(pages) + 1, "dense": len(pages) + 1}
    want = _drain(_paged_op(types, [], aggs, pages, False))
    assert len(got) == 1
    _assert_rows_equal(got, want)


def test_many_group_stream_reports_no_dense_page():
    """More groups than the limit on every page and in the merge: the
    scatter branch, and ``grouping_paths`` has no ``dense`` entry."""
    rng = np.random.default_rng(31)
    types = [T.BIGINT, T.BIGINT]
    aggs = [AggCall("sum", 1, T.BIGINT, resolve_agg_type("sum", T.BIGINT)),
            AggCall("count_star", None, None, T.BIGINT)]
    ngroups = 3 * DENSE_GROUPS
    pages = [([[int(k) for k in rng.permutation(ngroups * 2) % ngroups],
               [int(v) for v in rng.integers(-99, 99, size=ngroups * 2)]],
              None) for _ in range(3)]
    op = _paged_op(types, [0], aggs, pages, True)
    got = _drain(op)
    assert op.metrics()["grouping_paths"] == {"hash": len(pages) + 1}
    want = _drain(_paged_op(types, [0], aggs, pages, False))
    assert len(got) == ngroups
    _assert_rows_equal(got, want)


# ----------------------------------------------------- partial/final chain


def _run_partial_final(input_types, columns, group_channels, aggs,
                       page_rows, adaptive=False, adaptive_min_rows=10**9,
                       adaptive_ratio=0.9):
    n = len(columns[0])
    partial = HashAggregationOperator(
        input_types, group_channels, aggs, "partial",
        adaptive_partial=adaptive, adaptive_min_rows=adaptive_min_rows,
        adaptive_ratio=adaptive_ratio)
    final_aggs = [AggCall(a.function, None, a.arg_type, a.output_type)
                  for a in aggs]
    inter_types = partial._intermediate_types()
    final = HashAggregationOperator(
        inter_types, list(range(len(group_channels))), final_aggs, "final")
    dicts = [Dictionary() if t.is_pooled else None for t in input_types]
    for lo in range(0, n, page_rows):
        chunk = [c[lo:lo + page_rows] for c in columns]
        page = Page.from_pylists(input_types, chunk, dicts)
        partial.add_input(DevicePage.from_page(page))
        while True:
            out = partial.get_output()
            if out is None:
                break
            final.add_input(out)
    partial.finish()
    while not partial.is_finished():
        out = partial.get_output()
        if out is not None:
            final.add_input(out)
    return partial, _drain(final)


def test_partial_final_hash_matches_single_sort():
    rng = np.random.default_rng(17)
    n = 1000
    keys = [int(v) if rng.random() > 0.2 else None
            for v in rng.integers(0, 37, size=n)]
    s1, s2 = _payload(rng, n)
    cols = [keys, s1, s2]
    _, got = _run_partial_final(AGG_TYPES, cols, [0], AGG_SUITE, 256)
    want = _run_single(AGG_TYPES, cols, [0], AGG_SUITE, False)
    _assert_rows_equal(got, want)


def test_adaptive_partial_switches_to_passthrough():
    """High-cardinality keys: the partial step must observe the
    non-reducing ratio, switch to pass-through, and final results must
    be unchanged."""
    rng = np.random.default_rng(23)
    n = 1200
    keys = [int(v) for v in rng.permutation(n * 50)[:n]]  # all distinct
    s1, s2 = _payload(rng, n)
    cols = [keys, s1, s2]
    partial, got = _run_partial_final(
        AGG_TYPES, cols, [0], AGG_SUITE, 256,
        adaptive=True, adaptive_min_rows=256, adaptive_ratio=0.5)
    assert partial.passthrough, "adaptive partial agg must have tripped"
    assert partial.path_counts["passthrough"] > 0
    want = _run_single(AGG_TYPES, cols, [0], AGG_SUITE, False)
    _assert_rows_equal(got, want)


def test_adaptive_partial_stays_on_for_reducing_input():
    rng = np.random.default_rng(29)
    n = 1200
    keys = [int(v) for v in rng.integers(0, 4, size=n)]  # 4 groups
    s1, s2 = _payload(rng, n)
    cols = [keys, s1, s2]
    partial, got = _run_partial_final(
        AGG_TYPES, cols, [0], AGG_SUITE, 256,
        adaptive=True, adaptive_min_rows=256, adaptive_ratio=0.5)
    assert not partial.passthrough
    assert partial.path_counts["passthrough"] == 0
    want = _run_single(AGG_TYPES, cols, [0], AGG_SUITE, False)
    _assert_rows_equal(got, want)


def test_adaptive_per_key_range_splits_skewed_stream():
    """'Partial Partial Aggregates': a skewed stream — one hot key
    carrying ~40% of rows plus an all-distinct tail — must flip only
    its COLD key ranges to pass-through (range-split mode), keep
    aggregating the hot range, and still produce the oracle's final
    rows."""
    rng = np.random.default_rng(31)
    n = 1536
    hot = rng.random(n) < 0.4
    uniq = rng.permutation(n * 50)[:n] + 100
    keys = [7 if h else int(u) for h, u in zip(hot, uniq)]
    s1, s2 = _payload(rng, n)
    cols = [keys, s1, s2]
    partial, got = _run_partial_final(
        AGG_TYPES, cols, [0], AGG_SUITE, 256,
        adaptive=True, adaptive_min_rows=512, adaptive_ratio=0.6)
    # mixed verdicts: the stream SPLIT instead of flipping wholesale
    assert partial._pass_buckets is not None
    assert not partial.passthrough
    assert partial.path_counts["range_split"] > 0
    m = partial.metrics()
    assert m["adaptive"].startswith("range-split")
    assert m["grouping_paths"]["range_split"] > 0
    want = _run_single(AGG_TYPES, cols, [0], AGG_SUITE, False)
    _assert_rows_equal(got, want)


def test_adaptive_single_bucket_keeps_legacy_whole_stream_decision():
    """adaptive_key_buckets=1 is the PR 1 behavior: one global
    verdict, never a range split."""
    rng = np.random.default_rng(37)
    n = 1200
    keys = [int(v) for v in rng.permutation(n * 50)[:n]]
    s1, s2 = _payload(rng, n)
    partial_ = HashAggregationOperator(
        AGG_TYPES, [0], AGG_SUITE, "partial", adaptive_partial=True,
        adaptive_min_rows=256, adaptive_ratio=0.5,
        adaptive_key_buckets=1)
    for lo in range(0, n, 256):
        chunk = [c[lo:lo + 256] for c in [keys, s1, s2]]
        partial_.add_input(DevicePage.from_page(
            Page.from_pylists(AGG_TYPES, chunk)))
        while partial_.get_output() is not None:
            pass
    assert partial_.passthrough
    assert partial_._pass_buckets is None


# ------------------------------------------- partials as wide as their groups


def _narrow_case(case):
    """(types, group_channels, aggs, pages): ``pages`` is a list of
    (columns, live) — ``live`` None keeps every row, else a bool mask."""
    rng = np.random.default_rng(sum(map(ord, case)))
    sum1 = AggCall("sum", 1, T.BIGINT, resolve_agg_type("sum", T.BIGINT))
    count = AggCall("count_star", None, None, T.BIGINT)
    types, group, aggs = [T.BIGINT, T.BIGINT], [0], [sum1, count]

    def page(keys):
        return ([list(keys), [int(v) for v in
                              rng.integers(-99, 99, size=len(keys))]],
                None)

    if case == "few_groups":
        pages = [page(rng.integers(0, 4, size=200)) for _ in range(3)]
    elif case == "pow2_groups":
        # exactly 32 groups a page: the partial keeps 32 lanes, not 64
        pages = [page(np.arange(300) % 32), page(np.arange(90) % 32 + 7)]
    elif case == "over_half":
        # 40 groups in 64 lanes: padded_size(40) is the page, no narrowing
        pages = [page(np.arange(60) % 40), page(np.arange(64) % 40 + 20)]
    elif case == "empty_page":
        cols, _ = page(rng.integers(0, 5, size=100))
        pages = [page(rng.integers(0, 5, size=100)),
                 (cols, np.zeros(100, dtype=bool)),
                 page(rng.integers(3, 9, size=100))]
    elif case == "keyless":
        group = []
        pages = [page(rng.integers(0, 9, size=150)) for _ in range(3)]
    elif case == "null_keys":
        pages = [page([int(k) if rng.random() > 0.3 else None
                       for k in rng.integers(0, 6, size=120)])
                 for _ in range(3)]
    elif case == "varchar_min":
        vt = T.varchar_type(8)
        types, group = [vt, vt], [0]
        aggs = [AggCall("min", 1, vt, vt), count]
        words = ["pear", "fig", "apple", "quince", "date", "lime"]
        pages = [([[str(rng.choice(["aa", "bb", "cc"])) if rng.random() > .2
                    else None for _ in range(130)],
                   [str(rng.choice(words)) if rng.random() > .2 else None
                    for _ in range(130)]], None) for _ in range(3)]
    else:
        raise AssertionError(case)
    return types, group, aggs, pages


def _page_groups(columns, live, group_channels):
    live = np.ones(len(columns[0]), bool) if live is None else live
    return len({tuple(columns[c][i] for c in group_channels)
                for i in np.flatnonzero(live)})


@pytest.mark.parametrize("case", [
    "few_groups", "pow2_groups", "over_half", "empty_page", "keyless",
    "null_keys", "varchar_min"])
def test_partials_are_as_wide_as_their_groups(case):
    """Step ``single`` on the hash path keeps each page's partial at
    ``padded_size(ngroups)`` lanes — the count rides the page's overflow
    read — and merges at the padded sum of what it kept; answers equal
    the sort oracle's."""
    types, group, aggs, pages = _narrow_case(case)
    answers = {}
    for hashed in (True, False):
        dicts = [Dictionary() if t.is_pooled else None for t in types]
        op = HashAggregationOperator(types, group, aggs, "single",
                                     hash_grouping=hashed)
        in_lanes = 0
        for columns, live in pages:
            dp = DevicePage.from_page(
                Page.from_pylists(types, columns, dicts))
            if live is not None:
                mask = np.zeros(dp.capacity, dtype=bool)
                mask[:len(live)] = live
                dp = DevicePage(dp.types, dp.cols, dp.nulls,
                                jnp.asarray(mask), dp.dictionaries)
            in_lanes += dp.capacity
            op.add_input(dp)
        kept = [p.capacity for p in op._partials]
        answers[hashed] = _drain(op)
        if not hashed:
            continue
        # every page and the merge have few groups: all reduce densely
        assert op.path_counts == {"hash": len(pages) + 1,
                                  "dense": len(pages) + 1, "sort": 0,
                                  "passthrough": 0, "range_split": 0}
        want = [padded_size(_page_groups(c, live, group))
                for c, live in pages]
        assert kept == want
        if case == "over_half":
            assert kept == [64, 64]          # as wide as the pages
        assert op.metrics()["partial_lanes"] == {
            "pages": len(pages), "in": in_lanes, "kept": sum(kept),
            "merge": padded_size(sum(kept))}
    _assert_rows_equal(answers[True], answers[False])


def test_final_step_merges_partials_of_unequal_width():
    """Step ``final`` over intermediate pages of q3's shape — unequal
    capacities, some with few groups (narrowed), some nearly full (kept
    as they are) — answers as the sort oracle does, and a memory context
    is charged the partials' narrow bytes, not their pages'."""
    from trino_tpu.exec.memory import QueryMemoryPool, device_page_bytes

    rng = np.random.default_rng(26)
    # sum(bigint) states: (sum, count)
    final_aggs = [AggCall("sum", None, T.BIGINT,
                          resolve_agg_type("sum", T.BIGINT))]
    inter = [T.BIGINT, T.BIGINT, T.BIGINT]
    # (capacity, live rows, distinct keys)
    shapes = [(2048, 1500, 9), (256, 250, 200), (4096, 3000, 700),
              (64, 64, 64), (1024, 10, 3)]
    pages = []
    for cap, rows, nkeys in shapes:
        keys = rng.permutation(np.arange(rows) % nkeys + (cap % 7))
        pages.append((cap, [[int(k) for k in keys],
                            [int(v) for v in rng.integers(-50, 50, rows)],
                            [int(v) for v in rng.integers(1, 4, rows)]]))
    answers = {}
    for hashed in (True, False):
        pool = QueryMemoryPool(1 << 30, spill_enabled=True)
        ctx = pool.create_context("agg")
        op = HashAggregationOperator(inter, [0], final_aggs, "final",
                                     memory_context=ctx,
                                     hash_grouping=hashed)
        for cap, columns in pages:
            op.add_input(DevicePage.from_page(
                Page.from_pylists(inter, columns), capacity=cap))
        if hashed:
            want = [padded_size(nkeys) for _, _, nkeys in shapes]
            assert [p.capacity for p in op._partials] == want
            assert want[1] == 256 and want[3] == 64   # not narrowed
            lane_bytes = 3 * (8 + 1) + 1   # 3 int64 + null masks + valid
            assert ctx.reserved == sum(want) * lane_bytes
            assert ctx.reserved == sum(map(device_page_bytes,
                                           op._partials))
            assert op.metrics()["partial_lanes"]["in"] == \
                sum(cap for cap, _ in pages)
        answers[hashed] = _drain(op)
        assert pool.reserved == 0
        if hashed:
            assert op.metrics()["partial_lanes"]["merge"] == \
                padded_size(sum(want))
    _assert_rows_equal(answers[True], answers[False])


def test_q1_sync_contract_has_no_page_trim():
    """q1 at tiny through the runner, tracing on: the aggregation reads
    the device once a page and once a merge (``agg_overflow``: overflow
    flag and group count together) and never to find the output's width
    (``page_trim``)."""
    from trino_tpu.connectors.tpch import TpchConnector
    from trino_tpu.runner import LocalQueryRunner
    from trino_tpu.sql.analyzer import Session

    runner = LocalQueryRunner(
        {"tpch": TpchConnector(page_rows=4096)},
        Session(catalog="tpch", schema="tiny"), desired_splits=8)
    res = runner.execute(
        "select l_returnflag, l_linestatus, sum(l_quantity), "
        "avg(l_extendedprice), count(*) from lineitem "
        "where l_shipdate <= date '1998-09-02' "
        "group by l_returnflag, l_linestatus order by 1, 2")
    assert len(res.rows) == 4
    spans = res.stats["trace"]
    root, = [s for s in spans if s["name"] == "statement"]
    lanes = [s["attrs"]["partial_lanes"] for s in spans
             if "partial_lanes" in s["attrs"]]
    assert len(lanes) == 1 and lanes[0]["pages"] > 1
    assert lanes[0]["kept"] == 16 * lanes[0]["pages"]
    assert lanes[0]["merge"] == padded_size(lanes[0]["kept"])
    by_why = root["attrs"]["host_sync_by_why"]
    assert "page_trim" not in by_why
    assert by_why["agg_overflow"][0] == lanes[0]["pages"] + 1


@pytest.mark.parametrize("sql,dense", [
    ("select l_returnflag, l_linestatus, sum(l_quantity), count(*) "
     "from lineitem group by l_returnflag, l_linestatus", True),
    ("select sum(l_extendedprice * l_discount) from lineitem "
     "where l_quantity < 24", True),
    ("select l_orderkey, sum(l_quantity) from lineitem "
     "group by l_orderkey", False),
])
def test_explain_analyze_says_how_many_pages_reduced_densely(sql, dense):
    """EXPLAIN ANALYZE's aggregation line carries ``grouping_paths``:
    every page (and the merge) of a few-group or keyless aggregation is
    ``dense``, none of a 15,000-group one."""
    import re

    from trino_tpu.connectors.tpch import TpchConnector
    from trino_tpu.runner import LocalQueryRunner
    from trino_tpu.sql.analyzer import Session

    runner = LocalQueryRunner(
        {"tpch": TpchConnector(page_rows=4096)},
        Session(catalog="tpch", schema="tiny"), desired_splits=8)
    text = "\n".join(str(r[0]) for r in
                     runner.execute("explain analyze " + sql).rows)
    line, = [ln for ln in text.splitlines()
             if "HashAggregationOperator" in ln]
    paths = dict(kv.split("=") for kv in
                 re.search(r"\[grouping ([^\]]+)\]", line).group(1).split())
    assert int(paths["hash"]) >= 2
    assert paths.get("dense") == (paths["hash"] if dense else None), line


# ------------------------------------------- hash against sort, as SQL ----

#: q18's grouping subquery without its HAVING (which leaves nothing at
#: micro): the aggregation itself, one group an order
Q18_GROUPS = ("select l_orderkey, sum(l_quantity) from lineitem "
              "group by l_orderkey")


@pytest.mark.parametrize("qid,witness,min_groups", [
    (1, None, 4),
    (18, Q18_GROUPS, 1001),
], ids=["q1", "q18"])
def test_sql_answers_the_same_hash_grouped_or_sorted(qid, witness,
                                                     min_groups):
    """TPC-H q1 (4 groups) and q18 (an aggregation over more than 1,000
    groups under a semijoin) through the planner give the same rows
    whether ``hash_grouping_enabled`` is on (the table) or off (the
    sort-based oracle)."""
    from trino_tpu.connectors.tpch import TpchConnector
    from trino_tpu.resources.tpch_queries import TPCH_QUERIES
    from trino_tpu.runner import LocalQueryRunner
    from trino_tpu.sql.analyzer import Session

    answers, groups = {}, {}
    for hashed in (True, False):
        session = Session(catalog="tpch", schema="micro")
        session.properties["hash_grouping_enabled"] = hashed
        runner = LocalQueryRunner({"tpch": TpchConnector(page_rows=4096)},
                                  session, desired_splits=4)
        answers[hashed] = runner.execute(TPCH_QUERIES[qid]).rows
        groups[hashed] = sorted(runner.execute(witness).rows) \
            if witness else answers[hashed]
    assert answers[True] == answers[False]
    assert groups[True] == groups[False]
    assert len(groups[True]) >= min_groups
