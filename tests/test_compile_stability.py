"""Compile-count stability: same-shape pages must not retrace.

Silent retracing (a jit cache key that varies page-to-page) is the
classic JAX perf bug — the engine would recompile per page and slide to
interpreter speed. Every hot-path kernel bumps a named counter in
``trino_tpu.jit_stats`` at TRACE time only, so after a warmup page the
total must stay flat across same-shape pages. The driver attributes
per-operator deltas into OperatorStats, surfacing them through EXPLAIN
ANALYZE.
"""

import numpy as np
import pytest

from trino_tpu import jit_stats
from trino_tpu import types as T
from trino_tpu.block import DevicePage, Page
from trino_tpu.ops.aggregation import AggCall, HashAggregationOperator, \
    resolve_agg_type


def _page(rng, n, nkeys=16):
    types = [T.BIGINT, T.BIGINT, T.REAL]
    cols = [[int(v) for v in rng.integers(0, nkeys, size=n)],
            [int(v) for v in rng.integers(-100, 100, size=n)],
            [float(np.float32(v)) for v in rng.normal(size=n)]]
    return types, DevicePage.from_page(Page.from_pylists(types, cols))


AGGS = [AggCall("count_star", None, None, T.BIGINT),
        AggCall("sum", 1, T.BIGINT, resolve_agg_type("sum", T.BIGINT)),
        AggCall("max", 2, T.REAL, T.REAL)]


@pytest.mark.parametrize("hash_grouping", [True, False])
def test_agg_same_shape_pages_do_not_retrace(hash_grouping):
    rng = np.random.default_rng(1)
    types, warm = _page(rng, 1000)
    op = HashAggregationOperator(types, [0], AGGS, "single",
                                 hash_grouping=hash_grouping)
    op.add_input(warm)  # warmup page pays all traces
    before = jit_stats.total()
    for _ in range(4):
        _, page = _page(rng, 1000)
        op.add_input(page)
    assert jit_stats.total() == before, (
        "same-shape pages retraced the aggregation path: "
        f"{jit_stats.counts()}")
    op.finish()
    assert op.get_output() is not None


def test_pages_on_either_side_of_the_dense_limit_share_one_program():
    """The reduce picks its branch on the device from the page's group
    count: a few-group page and a many-group page of one shape are one
    trace of each grouping program (the ``cond`` adds no cache key)."""
    from trino_tpu.ops.hashtable import DENSE_GROUPS

    rng = np.random.default_rng(4)
    types, few = _page(rng, 1000, nkeys=3)
    op = HashAggregationOperator(types, [0], AGGS, "single")
    op.add_input(few)
    names = ("hash_group_ids", "hash_segment_reduce")
    before = jit_stats.total_for(*names)
    for nkeys in (4 * DENSE_GROUPS, 2, 10**6):
        _, page = _page(rng, 1000, nkeys=nkeys)
        op.add_input(page)
    assert jit_stats.total_for(*names) == before, jit_stats.counts()
    assert op.path_counts["hash"] == 4 and op.path_counts["dense"] == 2
    op.finish()
    assert op.get_output() is not None


def test_keyless_aggregate_traces_its_own_entry_once():
    """A global aggregate builds no table: its group ids trace under the
    name ``keyless_group_ids``, once per page shape, and never touch the
    keyed entry's counter."""
    rng = np.random.default_rng(5)
    types, warm = _page(rng, 1000)

    def fresh():
        return HashAggregationOperator(types, [], AGGS, "single")

    op = fresh()
    op.add_input(warm)
    assert jit_stats.counts().get("keyless_group_ids", 0) >= 1
    before = jit_stats.counts()
    for op in (op, fresh()):
        for _ in range(3):
            _, page = _page(rng, 1000)
            op.add_input(page)
    after = jit_stats.counts()
    for name in ("keyless_group_ids", "hash_group_ids",
                 "hash_segment_reduce"):
        assert after.get(name, 0) == before.get(name, 0), name
    op.finish()
    assert op.get_output() is not None


def test_partial_passthrough_does_not_retrace():
    """The adaptive pass-through layout conversion is sort/jit-free; it
    must add zero traces once tripped."""
    rng = np.random.default_rng(2)
    types, warm = _page(rng, 1024, nkeys=10**9)
    op = HashAggregationOperator(types, [0], AGGS, "partial",
                                 adaptive_partial=True,
                                 adaptive_min_rows=64, adaptive_ratio=0.5)
    op.add_input(warm)
    assert op.passthrough
    before = jit_stats.total()
    for _ in range(3):
        _, page = _page(rng, 1024, nkeys=10**9)
        op.add_input(page)
    assert jit_stats.total() == before, jit_stats.counts()


def test_driver_attributes_compile_counts_and_explain_reports_them():
    """End-to-end: per-operator compile counts flow into Driver stats
    and the EXPLAIN ANALYZE rendering."""
    from trino_tpu.connectors.tpch import TpchConnector
    from trino_tpu.runner import LocalQueryRunner

    runner = LocalQueryRunner({"tpch": TpchConnector(page_rows=2048)})
    runner.session.catalog = "tpch"
    runner.session.schema = "micro"
    res = runner.execute(
        "EXPLAIN ANALYZE SELECT l_returnflag, count(*), sum(l_quantity) "
        "FROM lineitem GROUP BY l_returnflag")
    text = "\n".join(r[0] for r in res.rows)
    assert "compiles" in text
    # the aggregation operator line carries its compile count
    agg_lines = [ln for ln in text.splitlines()
                 if "HashAggregationOperator" in ln]
    assert agg_lines and all("compiles" in ln for ln in agg_lines)


def test_query_repeat_keeps_kernel_traces_flat():
    """Running the same query shape again must not re-trace the
    module-level grouping kernels (the jit caches are keyed on shapes +
    static config, not operator instances)."""
    rng = np.random.default_rng(3)
    types, warm = _page(rng, 512)

    def run_once():
        op = HashAggregationOperator(types, [0], AGGS, "single")
        for _ in range(2):
            _, page = _page(rng, 512)
            op.add_input(page)
        op.finish()
        return op.get_output()

    run_once()  # warmup
    grouping = ("hash_group_ids", "keyless_group_ids",
                "hash_segment_reduce", "sort_group_reduce",
                "segment_reduce_pallas")
    before = {k: v for k, v in jit_stats.counts().items() if k in grouping}
    run_once()
    after = {k: v for k, v in jit_stats.counts().items() if k in grouping}
    assert after == before, (before, after)
