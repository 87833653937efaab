"""MXU matmul join strategy (ops/matmul_join.py) vs the sorted-index
oracle, and the cost-model plumbing that selects it.

The matmul operator IS a LookupJoinOperator with the probe's candidate
lookup swapped for a blocked one-hot matmul, so every join type must
produce identical rows over adversarial distributions — dense and
sparse NDV, nulls, skew, dictionary-coded strings — and every
infeasible build must fall back to the inherited sorted-index probe
with the reason in metrics, still row-identical.
"""

import numpy as np
import pytest

from trino_tpu import jit_stats
from trino_tpu import types as T
from trino_tpu.block import DevicePage, Page
from trino_tpu.ops.join import (HashBuilderOperator, JoinBridge,
                                LookupJoinOperator)
from trino_tpu.ops.matmul_join import MatmulJoinOperator


def _run_join(op_cls, join_type, types_, build_cols, probe_cols,
              key_channels=(0,), page_rows=512, **kw):
    from trino_tpu.block import Dictionary

    bridge = JoinBridge()
    build = HashBuilderOperator(types_, list(key_channels), bridge)
    n_b = len(build_cols[0])
    # one pool per side, shared across its pages (the exchange-unified
    # contract); build and probe pools still DIFFER, so the remap seam
    # is exercised
    bdicts = [Dictionary() if t.is_pooled else None for t in types_]
    pdicts = [Dictionary() if t.is_pooled else None for t in types_]
    for lo in range(0, n_b, page_rows):
        build.add_input(DevicePage.from_page(Page.from_pylists(
            types_, [c[lo:lo + page_rows] for c in build_cols],
            bdicts)))
    build.finish()
    build.get_output()
    probe = op_cls(types_, list(key_channels), bridge, join_type, **kw)
    rows = []
    n_p = len(probe_cols[0])
    for lo in range(0, n_p, page_rows):
        probe.add_input(DevicePage.from_page(Page.from_pylists(
            types_, [c[lo:lo + page_rows] for c in probe_cols],
            pdicts)))
        while (p := probe.get_output()) is not None:
            rows.extend(p.to_page().to_rows())
    probe.finish()
    while not probe.is_finished():
        p = probe.get_output()
        if p is not None:
            rows.extend(p.to_page().to_rows())
    return sorted(rows, key=repr), probe


def _int_cols(rng, n, ndv, null_frac=0.0, skew=False):
    if skew:
        keys = (rng.zipf(1.8, n) % max(ndv, 1)).astype(int)
    else:
        keys = rng.integers(0, max(ndv, 1), n)
    k = [int(v) if rng.random() >= null_frac else None for v in keys]
    payload = [int(v) for v in rng.integers(0, 1000, n)]
    return [k, payload]


@pytest.mark.parametrize("join_type,ndv,null_frac,skew", [
    # every join type on the adversarial middle (skew + nulls) runs
    # tier-1; the dense/sparse NDV extremes ride the slow mark —
    # tier-1 budget
    ("inner", 150, 0.1, True),
    ("semi", 150, 0.1, True),
    ("anti", 150, 0.1, True),
    ("left", 150, 0.1, True),
    pytest.param("inner", 4, 0.0, False, marks=pytest.mark.slow),
    pytest.param("semi", 4, 0.0, False, marks=pytest.mark.slow),
    pytest.param("inner", 900, 0.05, False, marks=pytest.mark.slow),
    pytest.param("semi", 900, 0.05, False, marks=pytest.mark.slow),
])
def test_matmul_matches_sorted_index_oracle(join_type, ndv, null_frac,
                                            skew):
    rng = np.random.default_rng(ndv * 7 + len(join_type))
    types_ = [T.BIGINT, T.BIGINT]
    build_cols = _int_cols(rng, 768, ndv, null_frac)
    probe_cols = _int_cols(rng, 1024, int(ndv * 1.5) + 4, null_frac,
                           skew)
    want, _ = _run_join(LookupJoinOperator, join_type, types_,
                        build_cols, probe_cols)
    got, op = _run_join(MatmulJoinOperator, join_type, types_,
                        build_cols, probe_cols)
    assert op._fallback_reason is None, op._fallback_reason
    assert op.metrics()["strategy"] == "matmul"
    assert got == want


def test_matmul_string_keys_match_oracle():
    """Dictionary-coded keys: the probe remaps its pool into the
    build's (the inherited seam), and the codes ARE the dense domain —
    per-page pools differ on purpose."""
    rng = np.random.default_rng(5)
    types_ = [T.VARCHAR, T.BIGINT]
    vocab = [f"k{i:03d}" for i in range(60)]
    bk = [vocab[i] if rng.random() > 0.05 else None
          for i in rng.integers(0, 40, 900)]
    pk = [vocab[i] if rng.random() > 0.05 else None
          for i in rng.integers(0, 60, 1100)]
    bv = [int(v) for v in rng.integers(0, 100, 900)]
    pv = [int(v) for v in rng.integers(0, 100, 1100)]
    for jt in ("inner", "semi"):
        want, _ = _run_join(LookupJoinOperator, jt, types_, [bk, bv],
                            [pk, pv])
        got, op = _run_join(MatmulJoinOperator, jt, types_, [bk, bv],
                            [pk, pv])
        assert op._fallback_reason is None, op._fallback_reason
        assert got == want


@pytest.mark.parametrize("case,build_cols_fn,kw", [
    ("negative keys (u64 wrap)",
     lambda rng: _int_cols(rng, 400, 50), {}),
    ("range past max_key_range",
     lambda rng: [[0, 10_000_000], [1, 2]], {}),
    ("multi-key build", None, {}),
])
def test_infeasible_builds_fall_back_row_identical(case, build_cols_fn,
                                                   kw):
    rng = np.random.default_rng(9)
    types_ = [T.BIGINT, T.BIGINT]
    if case == "multi-key build":
        build_cols = _int_cols(rng, 300, 20)
        probe_cols = _int_cols(rng, 400, 25)
        keys = (0, 1)
    elif case.startswith("negative"):
        build_cols = build_cols_fn(rng)
        build_cols[0] = [None if v is None else v - 25
                         for v in build_cols[0]]
        probe_cols = _int_cols(rng, 500, 60)
        probe_cols[0] = [None if v is None else v - 30
                         for v in probe_cols[0]]
        keys = (0,)
    else:
        build_cols = build_cols_fn(rng)
        probe_cols = [[0, 5, 10_000_000], [7, 8, 9]]
        keys = (0,)
    want, _ = _run_join(LookupJoinOperator, "inner", types_,
                        build_cols, probe_cols, key_channels=keys)
    got, op = _run_join(MatmulJoinOperator, "inner", types_,
                        build_cols, probe_cols, key_channels=keys, **kw)
    assert op._fallback_reason is not None
    assert op.metrics()["strategy"] == "matmul->sorted-index"
    assert got == want


def test_matmul_probe_same_shape_pages_do_not_retrace():
    """Repeat probe pages of one shape must reuse the compiled one-hot
    matmul (the KERNEL_SIZING pow2 bucket keys the table width)."""
    rng = np.random.default_rng(3)
    types_ = [T.BIGINT, T.BIGINT]
    bridge = JoinBridge()
    build = HashBuilderOperator(types_, [0], bridge)
    build.add_input(DevicePage.from_page(Page.from_pylists(
        types_, _int_cols(rng, 512, 100))))
    build.finish()
    build.get_output()
    op = MatmulJoinOperator(types_, [0], bridge, "inner")
    for i in range(4):
        op.add_input(DevicePage.from_page(Page.from_pylists(
            types_, _int_cols(rng, 512, 120))))
        while op.get_output() is not None:
            pass
        if i == 0:
            before = jit_stats.total_for("matmul_join_probe",
                                         "matmul_join_build_table")
    assert jit_stats.total_for("matmul_join_probe",
                               "matmul_join_build_table") == before


# --------------------------------------------------------- cost model


def _tpch_runner(**props):
    from trino_tpu.connectors.tpch import TpchConnector
    from trino_tpu.runner import LocalQueryRunner
    from trino_tpu.sql.analyzer import Session

    s = Session(catalog="tpch", schema="micro")
    s.properties.update(props)
    return LocalQueryRunner({"tpch": TpchConnector(page_rows=4096)}, s)


JOIN_SQL = ("select c.c_custkey, o.o_orderkey from customer c "
            "join orders o on c.c_custkey = o.o_custkey")


def test_cost_rule_selects_matmul_only_in_win_region():
    """AUTOMATIC picks matmul exactly when the stats-estimated key
    range fits matmul_join_max_key_range: micro custkey (range 150)
    flips, the same join under a shrunken cap does not, and a
    wide-key join (o_orderkey range ~6000) never does."""
    r = _tpch_runner()
    plan = r.explain(JOIN_SQL)
    assert "strategy=matmul" in plan
    assert "key range 150" in plan
    # the estimate that picked it also reaches EXPLAIN's provenance
    assert "MatmulJoinStrategy" in plan

    narrow = _tpch_runner(matmul_join_max_key_range=64)
    assert "strategy=matmul" not in narrow.explain(JOIN_SQL)

    wide = ("select o.o_orderkey, l.l_quantity from orders o "
            "join lineitem l on o.o_orderkey = l.l_orderkey")
    assert "strategy=matmul" not in r.explain(wide)


def test_join_strategy_override_respected_both_ways():
    forced_off = _tpch_runner(join_strategy="SORTED_INDEX")
    assert "strategy=matmul" not in forced_off.explain(JOIN_SQL)
    wide = ("select o.o_orderkey, l.l_quantity from orders o "
            "join lineitem l on o.o_orderkey = l.l_orderkey")
    forced_on = _tpch_runner(join_strategy="MATMUL")
    plan = forced_on.explain(wide)
    assert "strategy=matmul" in plan and "forced by join_strategy" in plan
    # forcing matmul on an infeasible join still answers correctly:
    # the operator falls back per build (reason in EXPLAIN ANALYZE)
    want = sorted(_tpch_runner().execute(wide).rows)
    assert sorted(forced_on.execute(wide).rows) == want
    res = forced_on.execute("explain analyze " + wide)
    txt = "\n".join(x[0] for x in res.rows)
    assert "matmul->sorted-index" in txt


def test_matmul_join_end_to_end_sql_matches_sorted():
    """The full engine path: AUTOMATIC (matmul on micro) and forced
    SORTED_INDEX return identical rows, and EXPLAIN ANALYZE shows the
    strategy + estimate on the operator line."""
    auto = _tpch_runner()
    sorted_ = _tpch_runner(join_strategy="SORTED_INDEX")
    assert sorted(auto.execute(JOIN_SQL).rows) \
        == sorted(sorted_.execute(JOIN_SQL).rows)
    res = auto.execute("explain analyze " + JOIN_SQL)
    txt = "\n".join(x[0] for x in res.rows)
    assert "MatmulJoinOperator" in txt
    assert "strategy matmul" in txt
    assert "key range 150" in txt
