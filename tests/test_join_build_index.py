"""The join's build index (``ops/join.py``, since PR 48): one sort of (key,
row) — ``key_sorted`` and ``perm`` — over a build whose columns, null
masks and ``valid`` stay in arrival order and are read through ``perm``
at the lanes a probe page's matches take.

Every join type in every key mode is held to sqlite over builds that
arrive unsorted in several pages, with null keys on both sides, duplicate
keys, a key at the u64 sentinel (a bigint -1; two int32 -1 packed) on
both sides, and masked build lanes whose raw key equals a probe key (the
sentinel's among them: the sort ties them with the usable rows there and
only ``perm``'s sign tells them apart); so are an empty build, the hybrid
join's per-partition indexes and the batched executor's vmapped probe.
The structure is held too: the build's program is one sort and no gather,
and the spans say which side pays.
"""

import sqlite3

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from trino_tpu import types as T
from trino_tpu.block import DevicePage, Dictionary, Page
from trino_tpu.connectors.memory import MemoryConnector
from trino_tpu.exec.memory import QueryMemoryPool
from trino_tpu.ops import join as J
from trino_tpu.runner import LocalQueryRunner
from trino_tpu.sql.analyzer import Session

#: key mode -> (column types, key channels); the last column is a payload
MODES = {
    "single": ([T.BIGINT, T.BIGINT], (0,)),
    "packed": ([T.INTEGER, T.INTEGER, T.BIGINT], (0, 1)),
    "hashed": ([T.BIGINT, T.BIGINT, T.BIGINT], (0, 1)),
}
JOINS = ["inner", "left", "full", "semi", "anti", "semi_residual",
         "anti_residual"]


def _rows(mode, rng, n, key_hi):
    """``n`` rows (key..., payload): keys over [-1, key_hi) with
    duplicates, a tenth NULL, -1 — the u64 sentinel as a single bigint
    or as two packed int32 — several times."""
    _, kc = MODES[mode]
    out = []
    for _ in range(n):
        key = [int(rng.integers(-1, key_hi))]
        if len(kc) == 2:
            key.append(-1 if key[0] == -1 else int(rng.integers(0, 3)))
        if rng.random() < 0.1:
            key[int(rng.integers(0, len(kc)))] = None
        out.append(tuple(key) + (int(rng.integers(0, 5)),))
    out += [(-1,) * len(kc) + (7,)] * 3
    return [out[i] for i in rng.permutation(len(out))]


def _case(mode, seed=48):
    """(build rows, which of them are masked, probe rows): a fifth of
    the build's lanes are masked, rows with the sentinel key and rows
    with a key the probe asks for among them."""
    rng = np.random.default_rng(seed)
    build = _rows(mode, rng, 700, 60)
    probe = _rows(mode, rng, 500, 70)
    masked = rng.random(len(build)) < 0.2
    sentinel = [i for i, r in enumerate(build) if r[0] == -1]
    masked[sentinel[0]] = True
    masked[sentinel[1]] = False
    return build, masked, probe


def _page(types_, rows, masked=None):
    cols = [list(c) for c in zip(*rows)] if rows else [[] for _ in types_]
    page = DevicePage.from_page(Page.from_pylists(
        types_, cols, [Dictionary() if t.is_pooled else None
                       for t in types_]))
    if masked is None:
        return page
    keep = np.zeros(page.valid.shape[0], dtype=bool)
    keep[:len(rows)] = ~np.asarray(masked)
    return DevicePage(page.types, page.cols, page.nulls,
                      page.valid & jnp.asarray(keep), page.dictionaries)


def _residual(nprobe):
    """``probe.payload <> build.payload`` over the candidate lanes'
    combined rows (q21's ``l_suppkey <> l1.l_suppkey``)."""
    def fn(lanes):
        differ = lanes.cols[nprobe - 1] != lanes.cols[-1]
        return DevicePage(lanes.types, lanes.cols, lanes.nulls,
                          lanes.valid & differ, lanes.dictionaries)
    return fn


def _publish(mode, build, masked, page_rows=256, **builder):
    types_, kc = MODES[mode]
    bridge = J.JoinBridge()
    op = J.HashBuilderOperator(types_, list(kc), bridge, **builder)
    for lo in range(0, len(build), page_rows):
        op.add_input(_page(types_, build[lo:lo + page_rows],
                           masked[lo:lo + page_rows]))
    return bridge, op


def _finish(op):
    op.finish()
    op.get_output()


def _probe(mode, join, bridge, probe, page_rows=256):
    types_, kc = MODES[mode]
    kind = join.split("_")[0]
    op = J.LookupJoinOperator(
        types_, list(kc), bridge, kind,
        filter_fn=_residual(len(types_)) if "residual" in join else None)
    rows = []
    for lo in range(0, len(probe), page_rows):
        op.add_input(_page(types_, probe[lo:lo + page_rows]))
        while (p := op.get_output()) is not None:
            rows.extend(p.to_page().to_rows())
    op.finish()
    while not op.is_finished():
        if (p := op.get_output()) is not None:
            rows.extend(p.to_page().to_rows())
    return sorted(rows, key=repr), op


def _sqlite(mode, join, build, probe):
    """The join in sqlite over the build's unmasked rows."""
    _, kc = MODES[mode]
    names = [f"k{i}" for i in range(len(kc))] + ["v"]
    db = sqlite3.connect(":memory:")
    for table, rows in (("b", build), ("p", probe)):
        db.execute(f"create table {table} ({', '.join(names)})")
        db.executemany(f"insert into {table} values "
                       f"({', '.join('?' * len(names))})", rows)
    on = " and ".join(f"p.{k} = b.{k}" for k in names[:-1])
    if "residual" in join:
        on += " and p.v <> b.v"
    kind = join.split("_")[0]
    if kind in ("semi", "anti"):
        sql = (f"select p.* from p where {'not ' * (kind == 'anti')}"
               f"exists (select 1 from b where {on})")
    else:
        word = {"inner": "inner", "left": "left", "full": "full outer"}
        sql = f"select p.*, b.* from p {word[kind]} join b on {on}"
    return sorted(db.execute(sql).fetchall(), key=repr)


def _unmasked(build, masked):
    return [r for r, m in zip(build, masked) if not m]


@pytest.mark.parametrize("join", JOINS)
@pytest.mark.parametrize("mode", sorted(MODES))
def test_join_over_an_unsorted_masked_build_equals_sqlite(mode, join):
    build, masked, probe = _case(mode)
    bridge, op = _publish(mode, build, masked)
    _finish(op)
    b = bridge.build
    assert b.key_mode == mode
    assert op.metrics()["build_carried_cols"] == 0
    # the build lies as it arrived: its first page's payloads, in order
    np.testing.assert_array_equal(
        np.asarray(b.cols[-1])[:256], [r[-1] for r in build[:256]])
    got, probe_op = _probe(mode, join, bridge, probe)
    want = _sqlite(mode, join, _unmasked(build, masked), probe)
    assert got == want
    assert len(want) > 50
    m = probe_op.metrics()
    assert m["build_row_lanes"] == m["expand_lanes"] > 0


@pytest.mark.parametrize("mode", ["single", "packed"])
def test_a_masked_lane_at_the_sentinel_is_no_candidate(mode):
    """The sort ties the usable (-1) rows with the build's dead lanes,
    a masked lane whose raw key is -1 among them: the probe's -1 rows
    reach all of them by the searches, and ``perm``'s sign keeps the
    dead ones out (the raw keys alone would let the masked lane in)."""
    build, masked, probe = _case(mode)
    bridge, op = _publish(mode, build, masked)
    _finish(op)
    b = bridge.build
    assert b.direct is None
    assert b.direct_fallback == "key at the u64 sentinel"
    perm = np.asarray(b.perm)
    rows = np.where(perm < 0, ~perm, perm)
    assert sorted(rows) == list(range(perm.shape[0]))
    dead = ~np.asarray(b.valid)[rows]
    for c in b.key_channels:
        dead |= np.asarray(b.nulls[c])[rows]
    np.testing.assert_array_equal(perm < 0, dead)
    at_sentinel = np.asarray(b.key_sorted) == J._U64_SENTINEL
    assert (at_sentinel & (perm >= 0)).sum() >= 4    # the usable -1 rows
    assert (at_sentinel & (perm < 0)).sum() > 100    # tied with the dead
    got, _ = _probe(mode, "inner", bridge,
                    [r for r in probe if r[0] == -1])
    want = _sqlite(mode, "inner", _unmasked(build, masked),
                   [r for r in probe if r[0] == -1])
    assert got == want and len(want) >= 12


@pytest.mark.parametrize("join", JOINS)
def test_an_empty_build(join):
    _, _, probe = _case("single")
    bridge, op = _publish("single", [], [])
    _finish(op)
    assert op.metrics() == {"key_mode": "single", "build_lanes": 16,
                            "build_carried_cols": 0}
    got, _ = _probe("single", join, bridge, probe)
    assert got == _sqlite("single", join, [], probe)
    assert bool(got) == (join.split("_")[0] in ("left", "full", "anti"))


@pytest.mark.parametrize("join", ["inner", "left", "semi", "anti",
                                  "anti_residual"])
@pytest.mark.parametrize("mode", sorted(MODES))
def test_hybrid_partitions_index_their_pages_the_same_way(mode, join):
    """A build that went partitioned: the resident part and each cold
    partition's pass (``_build_side_from_spilled``) probe an index over
    pages that lie as they were parked."""
    build, masked, probe = _case(mode)
    pool = QueryMemoryPool(1 << 22, spill_enabled=True)
    ctx = pool.create_context("join-build")
    bridge, op = _publish(
        mode, build, masked, memory_context=ctx,
        hybrid={"fanout": 4, "max_depth": 3, "hint": None})
    with ctx.lock:
        assert op._revoke() > 0
    _finish(op)
    assert bridge.hybrid.spilled_build
    assert bridge.build.direct_fallback == "hybrid partitions"
    got, _ = _probe(mode, join, bridge, probe)
    assert got == _sqlite(mode, join, _unmasked(build, masked), probe)
    pool.close()


BATCHED = {
    "inner": "select f.v, d.w from f join d on f.k = d.k where f.v > %d",
    "left": "select f.v, d.w from f left join d on f.k = d.k "
            "where f.v > %d",
    "semi": "select v from f where k in (select k from d) and v > %d",
    "anti": "select v from f where k not in "
            "(select k from d where k is not null) and v > %d",
}


@pytest.mark.parametrize("join", sorted(BATCHED))
def test_the_batched_probe_reads_the_build_through_perm(join):
    """``exec/batched.py``: eight statements of one shape share one
    build, its ``perm`` beside its keys under ``in_axes=None``; the
    dimension arrives unsorted, with duplicates, NULL and -1 keys."""
    rng = np.random.default_rng(7)
    dim = [(int(k), int(w)) for w, k in enumerate(
        rng.permutation([-1, -1, 0, 1, 1, 1, 2, 3, 5, 8, 8, 13]))]
    fact = [(int(k), i) for i, k in enumerate(rng.integers(-2, 10, 96))]
    runner = LocalQueryRunner({"memory": MemoryConnector()},
                              Session(catalog="memory", schema="default"))
    db = sqlite3.connect(":memory:")
    for table, cols, rows in (("d", "k, w", dim), ("f", "k, v", fact)):
        rows = rows + [(None, 1000 + len(rows))]
        db.execute(f"create table {table} ({cols})")
        db.executemany(f"insert into {table} values (?, ?)", rows)
        runner.execute(
            f"create table {table} ({cols.replace(',', ' bigint,')} bigint)")
        runner.execute(f"insert into {table} values " + ", ".join(
            "(%s, %d)" % ("null" if k is None else k, x) for k, x in rows))
    burst = [BATCHED[join] % (i * 9) for i in range(8)]
    out = runner.execute_batch(burst)
    for sql, res in zip(burst, out):
        assert sorted(res.rows, key=repr) == sorted(
            db.execute(sql).fetchall(), key=repr)
    assert len(out[0].rows) > 10
    assert runner.query_cache.templates.dispositions.get(
        "join_stage_vmapped") == 1
    assert not runner.query_cache.templates.fallbacks


def _primitives(jaxpr):
    for eqn in jaxpr.eqns:
        yield eqn.primitive.name
        for value in eqn.params.values():
            for sub in value if isinstance(value, (list, tuple)) else [value]:
                inner = getattr(sub, "jaxpr", sub)
                if hasattr(inner, "eqns"):
                    yield from _primitives(inner)


@pytest.mark.parametrize("lanes", [16, 1 << 12])
def test_the_build_program_is_one_sort_and_no_gather(lanes):
    key = jax.ShapeDtypeStruct((lanes,), jnp.uint64)
    flag = jax.ShapeDtypeStruct((lanes,), jnp.bool_)
    names = list(_primitives(
        jax.make_jaxpr(J._build_sorted.jit)(key, flag, flag).jaxpr))
    assert names.count("sort") == 1
    assert not [n for n in names if "gather" in n or "scatter" in n
                or n == "dynamic_slice"]
    key_sorted, perm = jax.eval_shape(J._build_sorted.jit, key, flag, flag)
    assert (key_sorted.dtype, perm.dtype) == (jnp.uint64, jnp.int32)


def test_the_spans_say_which_side_pays():
    """The builder's span: ``build_carried_cols`` 0 beside
    ``build_lanes``; the join's: ``build_row_lanes``, the lanes it
    translated through ``perm`` — its expansions' lanes; EXPLAIN
    ANALYZE prints both."""
    runner = LocalQueryRunner({"memory": MemoryConnector()},
                              Session(catalog="memory", schema="default"))
    runner.execute("create table f (k bigint, v bigint)")
    runner.execute("create table d (k bigint, w bigint)")
    runner.execute("insert into f values " + ", ".join(
        "(%d, %d)" % (i % 7, i) for i in range(64)))
    runner.execute("insert into d values (3, 30), (1, 10), (5, 50), (1, 11)")
    sql = "select f.v, d.w from f join d on f.k = d.k"
    res = runner.execute(sql)
    assert len(res.rows) == 9 * 2 + 9 + 9
    spans = {s["name"]: s["attrs"] for s in res.stats["trace"]}
    build, join = spans["HashBuilderOperator"], spans["LookupJoinOperator"]
    assert build["build_carried_cols"] == 0 and build["build_lanes"] >= 4
    assert join["build_row_lanes"] == join["expand_lanes"] >= 36
    text = "\n".join(r[0] for r in runner.execute(
        "explain analyze " + sql).rows)
    assert f"[index {build['build_lanes']} lanes, 0 columns carried]" in text
    assert f", {join['build_row_lanes']} build rows through perm]" in text
