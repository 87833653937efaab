"""The join's expansion (``ops/join.py::_expand_matches_impl``): every
output lane's probe row from one histogram of ``cumsum(count)`` and one
prefix sum, where a binary search ran over every lane until PR 38.

The function is held to an independent NumPy reference on every live
lane and to the function it replaced — kept here as the oracle, the
search spelled out — on ALL lanes, dead ones included: the host's
protocol around it (the capacity guess, the overflow re-expansion, the
chunked units, LEFT / FULL / semi / anti) was written against those
bits. An expansion 64 times narrower than its page keeps the search
(``_SEARCH_WHEN_NARROWER``: the one shape test, PERF.md section 5's sweep),
so the cases stand on both sides of it. The lowering guard holds the
programs that call it to no ``while`` from there up.
"""

import zlib
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import export

import trino_tpu  # noqa: F401  (x64 on before any array exists)
from trino_tpu.ops import join as J


def _search_oracle(lo, count, out_cap: int):
    """``_expand_matches_impl`` as it was before PR 38."""
    off_end = jnp.cumsum(count)
    total = off_end[-1]
    j = jnp.arange(out_cap, dtype=jnp.int64)
    probe_idx = jnp.searchsorted(off_end, j, side="right")
    probe_idx = jnp.clip(probe_idx, 0, count.shape[0] - 1)
    start = off_end[probe_idx] - count[probe_idx]
    build_idx = lo[probe_idx] + (j - start)
    lane_valid = j < total
    return (probe_idx.astype(jnp.int32),
            jnp.clip(build_idx, 0, None).astype(jnp.int32), lane_valid)


def _numpy_reference(lo, count, out_cap: int):
    """(probe_idx, build_idx) of the first ``min(total, out_cap)`` lanes
    and the exact total: row ids repeated by their counts, and each
    row's ``lo`` plus the lane's offset within the row."""
    count = count.astype(np.int64)
    rows = np.repeat(np.arange(count.shape[0]), count)
    first = np.repeat(np.cumsum(count) - count, count)
    build = lo.astype(np.int64)[rows] + (np.arange(rows.shape[0]) - first)
    return rows[:out_cap], build[:out_cap], int(count.sum())


def _dealt(rng, rows, matches):
    """``matches`` candidates dealt to ``rows`` probe rows at random."""
    return np.bincount(rng.integers(0, rows, matches), minlength=rows)


def _case(name):
    """(count, out_cap) of one named page; ``lo`` is drawn by the test."""
    rng = np.random.default_rng(zlib.crc32(name.encode()))
    if name == "zeros_between_matches":
        count = np.zeros(512, dtype=np.int64)
        count[[3, 4, 90, 91, 92, 300, 511]] = [2, 1, 5, 1, 3, 7, 2]
        return count, 32
    if name == "leading_and_trailing_zeros":
        count = np.zeros(256, dtype=np.int64)
        count[100:140] = rng.integers(0, 3, 40)
        return count, 128
    if name == "all_zero_page":
        return np.zeros(1024, dtype=np.int64), 64
    if name == "one_match_a_row":
        return np.ones(4096, dtype=np.int64), 4096
    if name == "total_equals_out_cap":
        count = _dealt(rng, 2048, 1024)
        return count, 1024
    if name == "total_one_under_out_cap":
        return _dealt(rng, 2048, 1023), 1024
    if name == "overflow_total_over_out_cap":
        # the guess was too small: what the re-expansion starts from
        return _dealt(rng, 4096, 5000), 1024
    if name == "one_row_over_out_cap":
        count = _dealt(rng, 512, 40)
        count[17] = 3000
        return count, 2048
    if name == "last_row_over_out_cap":
        count = np.zeros(64, dtype=np.int64)
        count[63] = 500
        return count, 16
    if name == "fanout_40":
        return np.full(1024, 40, dtype=np.int64), 1 << 16
    if name == "narrow_cap_wide_page":
        # the semi join's late pages: 16 lanes against 262,144 rows
        return _dealt(rng, 262144, 11), 16
    if name == "wide_cap_narrow_page":
        return _dealt(rng, 16, 50000), 1 << 16
    # the search's side of the shape test, and its edge
    if name == "narrow_all_zero_page":
        return np.zeros(4096, dtype=np.int64), 64
    if name == "narrow_overflow":
        return _dealt(rng, 65536, 5000), 1024
    if name == "narrow_one_row_over_out_cap":
        count = _dealt(rng, 65536, 40)
        count[17] = 3000
        return count, 1024
    if name == "narrow_total_equals_out_cap":
        return _dealt(rng, 65536, 1024), 1024
    if name == "edge_search_side":
        return _dealt(rng, 64 * 128, 100), 128
    if name == "edge_histogram_side":
        return _dealt(rng, 64 * 128 - 1, 100), 128
    raise AssertionError(name)


CASES = ["zeros_between_matches", "leading_and_trailing_zeros",
         "all_zero_page", "one_match_a_row", "total_equals_out_cap",
         "total_one_under_out_cap", "overflow_total_over_out_cap",
         "one_row_over_out_cap", "last_row_over_out_cap", "fanout_40",
         "narrow_cap_wide_page", "wide_cap_narrow_page",
         "narrow_all_zero_page", "narrow_overflow",
         "narrow_one_row_over_out_cap", "narrow_total_equals_out_cap",
         "edge_search_side", "edge_histogram_side"]


def _inputs(name, dtype):
    count, out_cap = _case(name)
    rng = np.random.default_rng(38)
    lo = rng.integers(0, 1_500_000, count.shape[0])
    return lo.astype(dtype), count.astype(dtype), out_cap


def _ident(lo, out_cap):
    """A build whose rows arrived sorted, wide enough for every lane's
    sorted position, dead lanes' too: ``perm`` is the identity, so the
    expansion's arrival rows ARE the positions the oracles compute
    (a build that arrived in another order: ``test_join_build_index``)."""
    return jnp.arange(int(np.max(np.asarray(lo))) + out_cap + 1,
                      dtype=jnp.int32)


@pytest.mark.parametrize("dtype", [np.int32, np.int64],
                         ids=["int32", "int64"])
@pytest.mark.parametrize("name", CASES)
def test_live_lanes_equal_numpy_reference(name, dtype):
    lo, count, out_cap = _inputs(name, dtype)
    probe_idx, build_idx, lane_valid = J._expand_matches(
        jnp.asarray(lo), jnp.asarray(count), _ident(lo, out_cap),
        out_cap=out_cap)
    want_probe, want_build, total = _numpy_reference(lo, count, out_cap)
    live = min(total, out_cap)
    assert want_probe.shape[0] == live
    np.testing.assert_array_equal(np.asarray(lane_valid),
                                  np.arange(out_cap) < total)
    np.testing.assert_array_equal(np.asarray(probe_idx)[:live], want_probe)
    np.testing.assert_array_equal(np.asarray(build_idx)[:live], want_build)


@pytest.mark.parametrize("dtype", [np.int32, np.int64],
                         ids=["int32", "int64"])
@pytest.mark.parametrize("name", CASES)
def test_all_lanes_equal_the_search(name, dtype):
    """Dead lanes too: same dtypes, same bits as the replaced function."""
    lo, count, out_cap = _inputs(name, dtype)
    got = J._expand_matches(jnp.asarray(lo), jnp.asarray(count),
                            _ident(lo, out_cap), out_cap=out_cap)
    want = jax.jit(partial(_search_oracle, out_cap=out_cap))(
        jnp.asarray(lo), jnp.asarray(count))
    for g, w in zip(got, want):
        assert g.dtype == w.dtype and g.shape == w.shape
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))


def test_mixed_dtypes_as_the_direct_probe_gives_them():
    """``_probe_direct_counts`` hands int32 of both; the searches'
    ``hi - lo`` may come wider than ``lo``: any mix gives the bits."""
    lo, count, out_cap = _inputs("zeros_between_matches", np.int32)
    want = _search_oracle(jnp.asarray(lo), jnp.asarray(count), out_cap)
    for lo_t, count_t in ((np.int64, np.int32), (np.int32, np.int64)):
        got = J._expand_matches(jnp.asarray(lo.astype(lo_t)),
                                jnp.asarray(count.astype(count_t)),
                                _ident(lo, out_cap), out_cap=out_cap)
        for g, w in zip(got, want):
            assert g.dtype == w.dtype
            np.testing.assert_array_equal(np.asarray(g), np.asarray(w))


# -- under vmap, with the batched executor's axes ----------------------------
#
# ``exec/batched.py`` runs ``_expand_verified_impl`` / ``_semi_matched_impl``
# as jit(vmap(lane, in_axes=(0, None))): the probe page, ``lo`` and
# ``count`` stacked over the batch, the build's ``perm`` and key columns
# shared.

_BATCH = ["zeros_between_matches", "all_zero_page",
          "overflow_total_over_out_cap", "one_row_over_out_cap"]


def _batch(rows=512, out_cap=256, build=4096):
    """Four lanes of one shape cut from the named cases (counts clipped
    to the first ``rows`` rows), each with keys that verify on a row's
    first one or two candidates only."""
    rng = np.random.default_rng(7)
    los, counts, pkeys = [], [], []
    bkey = np.arange(build, dtype=np.int64) // 2
    for name in _BATCH:
        count = np.resize(_case(name)[0], rows).astype(np.int32)
        lo = rng.integers(0, build - int(count.max()) - 1,
                          rows).astype(np.int32)
        los.append(lo)
        counts.append(count)
        pkeys.append(bkey[lo])       # equal to the row's first candidate
    return (jnp.asarray(np.stack(los)), jnp.asarray(np.stack(counts)),
            jnp.asarray(np.stack(pkeys)), jnp.asarray(bkey), out_cap)


_PERM = jnp.arange(4096, dtype=jnp.int32)   # _batch's build, as it sorted


def test_expand_matches_under_vmap_equals_each_lane():
    lo, count, _, _, out_cap = _batch()
    got = jax.jit(jax.vmap(partial(J._expand_matches_impl,
                                   out_cap=out_cap),
                           in_axes=(0, 0, None)))(lo, count, _PERM)
    for b in range(lo.shape[0]):
        want = _search_oracle(lo[b], count[b], out_cap)
        for g, w in zip(got, want):
            assert g.dtype == w.dtype
            np.testing.assert_array_equal(np.asarray(g[b]), np.asarray(w))


def _verified_by_search(lo, count, pkey, bkey, out_cap):
    probe_idx, build_idx, keep = _search_oracle(lo, count, out_cap)
    return probe_idx, build_idx, keep & (pkey[probe_idx] == bkey[build_idx])


def test_expand_verified_under_the_batched_axes():
    lo, count, pkey, bkey, out_cap = _batch()

    def lane(batched, shared):
        lo, count, pkey = batched
        perm, bkeys = shared
        return J._expand_verified_impl(lo, count, perm, (pkey,), bkeys,
                                       out_cap=out_cap)

    got = jax.jit(jax.vmap(lane, in_axes=(0, None)))(
        (lo, count, pkey), (_PERM, (bkey,)))
    kept = 0
    for b in range(lo.shape[0]):
        want = _verified_by_search(lo[b], count[b], pkey[b], bkey,
                                   out_cap)
        for g, w in zip(got, want):
            np.testing.assert_array_equal(np.asarray(g[b]), np.asarray(w))
        kept += int(np.asarray(want[2]).sum())
    assert kept > 0


def test_semi_matched_under_the_batched_axes():
    lo, count, pkey, bkey, out_cap = _batch()
    rows = lo.shape[1]

    def lane(batched, shared):
        lo, count, pkey = batched
        perm, bkeys = shared
        return J._semi_matched_impl(lo, count, perm, (pkey,), bkeys,
                                    probe_cap=rows, out_cap=out_cap)

    got = np.asarray(jax.jit(jax.vmap(lane, in_axes=(0, None)))(
        (lo, count, pkey), (_PERM, (bkey,))))
    assert got.any()
    for b in range(lo.shape[0]):
        probe_idx, _, keep = _verified_by_search(
            lo[b], count[b], pkey[b], bkey, out_cap)
        want = np.zeros(rows, dtype=bool)
        want[np.asarray(probe_idx)[np.asarray(keep)]] = True
        np.testing.assert_array_equal(got[b], want)


# -- the lowering guard -------------------------------------------------------
#
# What the chip is handed for a q3-like page (65,536 probe rows against
# the 2^21-lane ``orders`` build): no loop. ``jax.export`` lowers for the
# TPU without its backend; the compile for a described v5e is
# ``tests/test_aot_lowering.py``'s.

_PAGE, _BUILD = 1 << 16, 1 << 21
_sds = jax.ShapeDtypeStruct


def _tpu_text(fn, *args):
    return export.export(jax.jit(fn), platforms=["tpu"])(*args).mlir_module()


def _q3_args():
    return (_sds((_PAGE,), jnp.int32), _sds((_PAGE,), jnp.int32),
            _sds((_BUILD,), jnp.int32),
            (_sds((_PAGE,), jnp.int64),), (_sds((_BUILD,), jnp.int64),))


#: the narrowest expansion of a 65,536-row page that takes the histogram
_FIRST_WIDE = 2 * _PAGE // J._SEARCH_WHEN_NARROWER


@pytest.mark.parametrize("out_cap", [_FIRST_WIDE, _PAGE, 1 << 20])
def test_expand_verified_lowers_without_a_loop(out_cap):
    text = _tpu_text(partial(J._expand_verified, out_cap=out_cap),
                     *_q3_args())
    assert "stablehlo.while" not in text
    assert "stablehlo.scatter" in text


@pytest.mark.parametrize("out_cap", [_FIRST_WIDE, _PAGE, 1 << 20])
def test_semi_matched_lowers_without_a_loop(out_cap):
    text = _tpu_text(partial(J._semi_matched, probe_cap=_PAGE,
                             out_cap=out_cap), *_q3_args())
    assert "stablehlo.while" not in text


@pytest.mark.parametrize("out_cap", [16, _FIRST_WIDE // 2])
def test_a_narrow_expansion_keeps_the_search(out_cap):
    """The shape test's other side: no scatter over the page's rows."""
    text = _tpu_text(partial(J._expand_matches, out_cap=out_cap),
                     *_q3_args()[:3])
    assert "stablehlo.while" in text
    assert "stablehlo.scatter" not in text


def test_the_guard_sees_the_search_it_guards_against():
    text = _tpu_text(partial(_search_oracle, out_cap=_PAGE),
                     *_q3_args()[:2])
    assert "stablehlo.while" in text
