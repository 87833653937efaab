"""A join expands a probe page at its matches' own width.

``LookupJoinOperator.add_input`` enqueues the lookup (candidate ranges,
the page's match total) and reads nothing; ``get_output`` reads the
total — one scalar a page, ``join_expand_total`` — and runs the
expansion at ``padded_size(total)`` lanes, or in row chunks where that
passes ``max_lanes``.  Every join type over four page shapes against a
numpy oracle, the output widths, the reads and the counters."""

import jax.numpy as jnp
import numpy as np
import pytest

from trino_tpu import types as T
from trino_tpu.block import DevicePage, padded_size
from trino_tpu.ops import join as J
from trino_tpu.telemetry import tracing

TYPES = [T.BIGINT, T.BIGINT]
PAGE = 512
JOIN_TYPES = ["inner", "left", "full", "semi", "anti"]


def _page(keys, payload, valid):
    cap = len(keys)
    return DevicePage(
        TYPES, [jnp.asarray(keys, dtype=jnp.int64),
                jnp.asarray(payload, dtype=jnp.int64)],
        [jnp.zeros(cap, dtype=bool), jnp.zeros(cap, dtype=bool)],
        jnp.asarray(valid, dtype=bool), [None, None])


def _publish(keys, payload):
    bridge = J.JoinBridge()
    build = J.HashBuilderOperator(TYPES, [0], bridge)
    cap = padded_size(len(keys))
    pad = cap - len(keys)
    build.add_input(_page(np.pad(keys, (0, pad)), np.pad(payload, (0, pad)),
                          np.arange(cap) < len(keys)))
    build.finish()
    build.get_output()
    return bridge


# -- the page shapes: (build keys, probe keys, probe valid, max_lanes) ------

def _sparse(rng):
    """A probe page 5 % valid (behind a selective filter), N : 1."""
    build = rng.permutation(4096)[:2048]
    probe = rng.integers(0, 4096, PAGE)
    return build, probe, rng.random(PAGE) < 0.05, None


def _full_n_to_1(rng):
    """Every lane holds a row and every row finds its one build row."""
    build = rng.permutation(1024)
    return build, rng.integers(0, 1024, PAGE), np.ones(PAGE, bool), None


def _no_match(rng):
    build = rng.permutation(1024)
    return build, rng.integers(5000, 6000, PAGE), np.ones(PAGE, bool), None


def _fan_out(rng):
    """Eight build rows a key: the page's 3,000-odd matches pass a lane
    budget of 256, so the expansion runs in row chunks."""
    build = np.repeat(np.arange(200), 8)
    return build, rng.integers(0, 260, PAGE), rng.random(PAGE) < 0.9, 256


SHAPES = {"sparse": _sparse, "full_n_to_1": _full_n_to_1,
          "no_match": _no_match, "fan_out": _fan_out}


def _oracle(join_type, bkeys, bpay, pkeys, ppay, pvalid):
    """The join's rows by numpy: each valid probe row against the build
    rows of its key, in the operator's output layout."""
    order = np.argsort(bkeys, kind="stable")
    skeys = bkeys[order]
    lo = np.searchsorted(skeys, pkeys, side="left")
    hi = np.searchsorted(skeys, pkeys, side="right")
    count = np.where(pvalid, hi - lo, 0)
    rows, hit = [], np.zeros(len(bkeys), bool)
    for p in np.nonzero(pvalid)[0]:
        probe = (int(pkeys[p]), int(ppay[p]))
        builds = order[lo[p]:lo[p] + count[p]]
        hit[builds] = True
        if join_type == "semi":
            rows.extend([probe] if len(builds) else [])
        elif join_type == "anti":
            rows.extend([] if len(builds) else [probe])
        else:
            rows.extend(probe + (int(bkeys[b]), int(bpay[b]))
                        for b in builds)
            if not len(builds) and join_type in ("left", "full"):
                rows.append(probe + (None, None))
    if join_type == "full":
        rows.extend((None, None, int(bkeys[b]), int(bpay[b]))
                    for b in np.nonzero(~hit)[0])
    return sorted(rows, key=repr), int(count.sum())


def _drain(op):
    pages = []
    while (p := op.get_output()) is not None:
        pages.append(p)
    return pages


def _case(shape, seed=11):
    rng = np.random.default_rng(seed)
    bkeys, pkeys, pvalid, max_lanes = SHAPES[shape](rng)
    bpay = np.arange(len(bkeys)) + 10_000
    ppay = np.arange(PAGE) + 20_000
    return bkeys, bpay, pkeys, ppay, pvalid, max_lanes


@pytest.mark.parametrize("shape", sorted(SHAPES))
@pytest.mark.parametrize("join_type", JOIN_TYPES)
def test_expansion_at_the_matches_width(join_type, shape):
    bkeys, bpay, pkeys, ppay, pvalid, max_lanes = _case(shape)
    want, total = _oracle(join_type, bkeys, bpay, pkeys, ppay, pvalid)
    op = J.LookupJoinOperator(TYPES, [0], _publish(bkeys, bpay), join_type,
                              max_lanes=max_lanes)
    op.add_input(_page(pkeys, ppay, pvalid))
    op.finish()
    pages = _drain(op)
    assert op.is_finished()
    got = sorted((r for p in pages for r in p.to_page().to_rows()),
                 key=repr)
    assert got == want
    m = op.metrics()
    assert m["expand_rows"] == total
    exact = padded_size(max(total, 16))
    if max_lanes is None:
        assert m["expand_lanes"] == exact
    else:
        assert exact > max_lanes < m["expand_lanes"]
    if join_type != "inner":
        return
    caps = [p.capacity for p in pages]
    if max_lanes is None:
        # the page's matches, padded: a full N : 1 page stays at its own
        # width, a 5 % page shrinks to its rows, an empty one to 16 lanes
        assert caps == [exact]
        assert exact <= PAGE
    else:
        assert len(caps) > 1 and max(caps) <= max_lanes


def test_add_input_reads_nothing_and_get_output_one_total_a_page():
    bkeys, bpay, pkeys, ppay, pvalid, _ = _case("sparse")
    op = J.LookupJoinOperator(TYPES, [0], _publish(bkeys, bpay), "inner")
    assert op.pipeline_depth == 4
    with tracing.Tracer().span("statement") as root:
        for i in range(4):
            assert op.needs_input()
            op.add_input(_page(np.roll(pkeys, i), ppay, pvalid))
        assert not op.needs_input()
        assert "host_syncs" not in root.attrs
        assert op.metrics()["expand_lanes"] == 0
        # the pipeline is full: the oldest page is expanded, the rest
        # wait for more input or its end
        assert op.get_output() is not None
        assert root.attrs["host_sync_by_why"]["join_expand_total"][0] == 1
        op.finish()
        assert len(_drain(op)) == 3
        assert root.attrs["host_syncs"] == 4
        assert set(root.attrs["host_sync_by_why"]) == {"join_expand_total"}


def test_counters_sum_matches_and_padded_totals():
    rng = np.random.default_rng(5)
    bkeys = rng.permutation(4096)[:2048]
    bpay = np.arange(2048)
    op = J.LookupJoinOperator(TYPES, [0], _publish(bkeys, bpay), "inner")
    totals, out_caps = [], []
    for share in (0.02, 0.3, 1.0, 0.0, 0.6):
        pkeys = rng.integers(0, 4096, PAGE)
        pvalid = rng.random(PAGE) < share
        totals.append(_oracle("inner", bkeys, bpay, pkeys,
                              np.arange(PAGE), pvalid)[1])
        op.add_input(_page(pkeys, np.arange(PAGE), pvalid))
        out_caps += [p.capacity for p in _drain(op)]
    op.finish()
    out_caps += [p.capacity for p in _drain(op)]
    want_caps = [padded_size(max(t, 16)) for t in totals]
    assert len(set(want_caps)) > 2
    assert out_caps == want_caps
    m = op.metrics()
    assert m["probe_lanes"] == 5 * PAGE
    assert m["expand_rows"] == sum(totals)
    assert m["expand_lanes"] == sum(want_caps)
