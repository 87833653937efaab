"""AOT TPU smoke tests: lowerings and real compiles for a described chip.

No test here executes on a TPU. Two strengths of check:

* ``jax.export`` lowers a jitted program for an EXPLICIT target platform
  without that platform's backend — sharded programs go through SPMD
  partitioning, Pallas kernels through the Mosaic *lowering*. It stops
  at MLIR and never calls the chip's compiler.
* the ``*_compiles_for_v5e`` tests hand the program to the chip's own
  compiler for a DESCRIBED (not attached) ``v5e:2x2`` topology, at the
  widths the served SF1 path runs (pages of 65,536 rows). The Pallas
  segment-reduce passed every ``jax.export`` case while Mosaic refused
  all six at compile (bool-vector reshape, i64 literals under x64,
  unproven slice alignment, i64 index-map results) — only a compile
  shows that.

The topology is described inside the module-scoped ``topo`` fixture —
never at import — so every xdist worker collects the same tests and only
the worker that runs this file loads the TPU library. Keep every
described-topology test in THIS file. A compile that passes is not a
chip run (that is ``chip_smoke.py``).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from jax import export
from jax.sharding import Mesh, NamedSharding, PartitionSpec, \
    SingleDeviceSharding

from trino_tpu import types as T

sds = jax.ShapeDtypeStruct

#: TpchConnector.page_rows of the served SF1 deployment
PAGE = 1 << 16


def _export_tpu(fn, *args):
    return export.export(fn, platforms=["tpu"])(*args)


# ------------------------------------------------- described v5e chip ----


@pytest.fixture(scope="module")
def topo():
    import os

    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without the chip (the next run would warn
    # and recompile): keep these compiles out of it
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield topo
    jax.config.update("jax_enable_compilation_cache", True)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def mesh4(topo):
    return Mesh(np.asarray(topo.devices[:4]), ("x",))


def _on(sharding, tree):
    """The pytree of shapes with ``sharding`` on every leaf."""
    return jax.tree_util.tree_map(
        lambda a: sds(a.shape, a.dtype, sharding=sharding), tree)


def _compile(fn, sharding, *shapes):
    return jax.jit(fn).lower(*_on(sharding, shapes)).compile()


@pytest.mark.parametrize("kind", ["sum", "min", "max"])
@pytest.mark.parametrize("dtype", [jnp.int32, jnp.float32])
def test_pallas_segment_reduce_compiles_for_v5e(kind, dtype, one_chip):
    """The compiled (interpret=False) kernel for every kind x dtype it
    claims, at the aggregation's per-page call: 65,536 rows into
    cap + 1 segments."""
    from trino_tpu.ops.pallas_kernels import _segment_reduce_pallas

    def fn(col, gid):
        return _segment_reduce_pallas(col, gid, PAGE + 1, kind,
                                      interpret=False)

    compiled = _compile(fn, one_chip, sds((PAGE,), dtype),
                        sds((PAGE,), jnp.int32))
    assert "tpu_custom_call" in compiled.as_text()


def test_pallas_segment_reduce_compiles_at_largest_table(one_chip):
    """The kernel keeps its whole output block in VMEM, so the group
    table it accepts is bounded (``_MAX_SEGMENTS``; larger reductions
    take ``jax.ops.segment_*``). The bound itself must compile."""
    from trino_tpu.ops.pallas_kernels import (_MAX_SEGMENTS,
                                              _segment_reduce_pallas)

    n = _MAX_SEGMENTS - 1

    def fn(col, gid):
        return _segment_reduce_pallas(col, gid, _MAX_SEGMENTS, "min",
                                      interpret=False)

    compiled = _compile(fn, one_chip, sds((n,), jnp.int32),
                        sds((n,), jnp.int32))
    print(compiled.memory_analysis())
    assert "tpu_custom_call" in compiled.as_text()


def _q1_grouping_shapes():
    """q1's grouping layout at one page: two pooled string keys (rank
    operands), eight int64 states plus one int32 state (a date min)."""
    key_ops = (sds((PAGE,), jnp.uint8), sds((PAGE,), jnp.uint64)) * 2
    key_raws = (sds((PAGE,), jnp.int32),) * 2
    states = (sds((PAGE,), jnp.int64),) * 8 + (sds((PAGE,), jnp.int32),)
    kinds = ("sum",) * 8 + ("min",)
    return key_ops, key_raws, states, kinds


def test_hash_grouping_compiles_for_v5e(one_chip):
    """Both programs of the hash grouping path; with ``pallas="tpu"``
    the reduce sorts by gid and takes the kernel for the int32 state."""
    from trino_tpu.ops.hashtable import (hash_group_ids,
                                         hash_segment_reduce)

    key_ops, key_raws, states, kinds = _q1_grouping_shapes()
    valid = sds((PAGE,), jnp.bool_)
    _compile(lambda k, v: hash_group_ids.jit(k, v, exact=True),
             one_chip, key_ops, valid)

    def reduce(gid, group_rows, ngroups, raws, key_nulls, cols):
        return hash_segment_reduce.jit(gid, group_rows, ngroups, raws,
                                       key_nulls, cols, kinds,
                                       pallas="tpu")

    i32 = sds((PAGE,), jnp.int32)
    compiled = _compile(reduce, one_chip, i32, i32, sds((), jnp.int32),
                        key_raws, (valid,) * 2, states)
    text = compiled.as_text()
    # one program, both reductions: the scatter branch keeps the kernel,
    # the page's group count picks the branch on the device
    assert "tpu_custom_call" in text and "conditional(" in text


def test_keyless_group_ids_compile_for_v5e_without_a_table(one_chip):
    """A global aggregate's group ids at a resident page's width: no
    probe loop and no scatter into a table."""
    from trino_tpu.ops.hashtable import hash_group_ids

    lanes = 1 << 18
    compiled = _compile(lambda v: hash_group_ids.jit((), v, exact=True),
                        one_chip, sds((lanes,), jnp.bool_))
    text = compiled.as_text()
    assert "while(" not in text and "scatter(" not in text


def test_sort_group_reduce_compiles_for_v5e(one_chip):
    from trino_tpu.ops.aggregation import _group_reduce

    key_ops, key_raws, states, kinds = _q1_grouping_shapes()

    def fn(ops, raws, cols, valid):
        return _group_reduce.jit(ops, raws, cols, valid, num_keys=2,
                                 num_states=len(cols), kinds=kinds,
                                 pallas="tpu")

    compiled = _compile(fn, one_chip, key_ops, key_raws, states,
                        sds((PAGE,), jnp.bool_))
    assert "tpu_custom_call" in compiled.as_text()


def test_q1_device_step_compiles_for_v5e(one_chip):
    """The fused q1 step at a full 65,536-row page (int64 states, x64
    on): no custom call, plain XLA."""
    from __graft_entry__ import q1_example_args

    step, args = q1_example_args()
    cols, nulls, valid, luts = jax.eval_shape(lambda: args)

    def widen(tree):
        return jax.tree_util.tree_map(
            lambda a: sds((PAGE,), a.dtype), tree)

    _compile(step, one_chip, widen(cols), widen(nulls), widen(valid),
             luts)


def test_join_kernels_compile_for_v5e(one_chip):
    """Sorted-index join at q3-SF1 sizes: 1.5 M ``orders`` build rows
    padded to 2^21 (the index alone is sorted, whatever the build's
    columns), one 65,536-row probe page."""
    from trino_tpu.ops.join import (_build_sorted, _expand_matches,
                                    _probe_counts)

    build = 1 << 21
    u64 = sds((build,), jnp.uint64)
    flag = sds((build,), jnp.bool_)
    _compile(_build_sorted.jit, one_chip, u64, flag, flag)
    _compile(_probe_counts.jit, one_chip, u64,
             sds((PAGE,), jnp.uint64), sds((PAGE,), jnp.bool_))
    _compile(lambda lo, count, perm: _expand_matches.jit(
        lo, count, perm, out_cap=2 * PAGE),
             one_chip, sds((PAGE,), jnp.int64), sds((PAGE,), jnp.int64),
             sds((build,), jnp.int32))


def test_fact_table_build_compiles_for_v5e(one_chip):
    """q21's builds at SF1: ``lineitem`` whole (6.0 M rows in 23 pages
    of 262,144 lanes, padded to 2^23): the index is (u64 key, int32
    row) a lane in and out and the sort's own buffers, whatever columns
    the residual ``l_suppkey <> l1.l_suppkey`` needs; sixteen times
    q3's build, and well inside the chip's 16 GB."""
    from trino_tpu.ops.join import _build_sorted

    build = 1 << 23
    u64 = sds((build,), jnp.uint64)
    flag = sds((build,), jnp.bool_)
    compiled = _compile(_build_sorted.jit, one_chip, u64, flag, flag)
    memory = compiled.memory_analysis()
    assert memory.temp_size_in_bytes + memory.argument_size_in_bytes \
        + memory.output_size_in_bytes < 1 << 30
    key_sorted, perm = compiled.out_info
    assert (key_sorted.dtype, perm.dtype) == (jnp.uint64, jnp.int32)


def test_join_expansion_compiles_without_a_loop_for_v5e(one_chip):
    """What the chip runs for a probe page's expansion (PR 38): the
    histogram's scatter and two prefix sums, no ``while`` — at a
    generated page of 65,536 rows and at a resident page of 262,144 rows
    expanded into 2^20 lanes. The semi join's narrow late pages (16
    lanes against 262,144 rows) keep the search, with no scatter over
    the page's rows."""
    from functools import partial

    from trino_tpu.ops.join import _expand_verified, _semi_matched

    build = sds((1 << 21,), jnp.int64)
    perm = sds((1 << 21,), jnp.int32)

    def expand(lo, count, perm, pk, bk, out_cap):
        return _expand_verified(lo, count, perm, (pk,), (bk,),
                                out_cap=out_cap)

    def semi(lo, count, perm, pk, bk, out_cap):
        return _semi_matched(lo, count, perm, (pk,), (bk,), lo.shape[0],
                             out_cap=out_cap)

    for fn, rows, out_cap in ((expand, PAGE, PAGE),
                              (expand, 1 << 18, 1 << 20),
                              (semi, 1 << 18, 1 << 18),
                              (semi, 1 << 18, 16)):
        idx = sds((rows,), jnp.int32)
        text = _compile(partial(fn, out_cap=out_cap), one_chip, idx, idx,
                        perm, sds((rows,), jnp.int64), build).as_text()
        assert (" while(" in text) == (out_cap == 16)


def test_two_column_key_join_compiles_for_v5e(one_chip):
    """q9's ``partsupp`` join at SF1 (``sf1_q9_join6``): the key is two
    bigints, so the build is ``hashed`` and has no direct-address table.
    The 323,326 joined rows are indexed at 2^21 lanes (their ten
    columns stay where they arrived), a resident ``partsupp`` page of
    262,144 rows probes the sorted index by two binary searches, and
    the expansion verifies both raw key columns where ``perm`` says
    they lie."""
    from functools import partial

    from trino_tpu.ops.join import (_build_sorted, _expand_verified,
                                    _probe_counts)

    build, rows = 1 << 21, 1 << 18
    u64 = sds((build,), jnp.uint64)
    flag = sds((build,), jnp.bool_)
    _compile(_build_sorted.jit, one_chip, u64, flag, flag)
    _compile(_probe_counts.jit, one_chip, u64,
             sds((rows,), jnp.uint64), sds((rows,), jnp.bool_))
    idx = sds((rows,), jnp.int32)
    key, bkey = sds((rows,), jnp.int64), sds((build,), jnp.int64)
    text = _compile(
        partial(lambda lo, count, perm, p0, p1, b0, b1, out_cap:
                _expand_verified(lo, count, perm, (p0, p1), (b0, b1),
                                 out_cap=out_cap), out_cap=rows),
        one_chip, idx, idx, sds((build,), jnp.int32), key, key, bkey,
        bkey).as_text()
    assert " while(" not in text


def test_join_expansion_at_the_matches_width_compiles_for_v5e(one_chip):
    """q9's chained joins at SF1 since a page is expanded at its
    matches' width: a resident 262,144-lane page of which a dynamic
    filter left ~14 k rows expands into 2^14 lanes (the histogram: no
    loop) against a build sorted at 2^19 lanes, and ``_finalize_join``
    gathers the page's six and the build's ten columns at that width —
    2^14 lanes out, also LEFT's 2^14 + 2^18."""
    from functools import partial

    from trino_tpu.ops.join import _expand_verified, _finalize_join

    rows, out_cap, build = 1 << 18, 1 << 14, 1 << 19
    idx = sds((rows,), jnp.int32)
    text = _compile(
        partial(lambda lo, count, perm, pk, bk, out_cap: _expand_verified(
            lo, count, perm, (pk,), (bk,), out_cap=out_cap),
            out_cap=out_cap),
        one_chip, idx, idx, sds((build,), jnp.int32),
        sds((rows,), jnp.int64), sds((build,), jnp.int64)).as_text()
    assert " while(" not in text
    pcols = (sds((rows,), jnp.int64),) * 5 + (sds((rows,), jnp.int32),)
    bcols = (sds((build,), jnp.int64),) * 8 + (sds((build,), jnp.int32),) * 2
    lane = sds((out_cap,), jnp.int32)
    for left in (False, True):
        compiled = _compile(
            partial(_finalize_join, left=left), one_chip, pcols,
            (sds((rows,), jnp.bool_),) * 6, sds((rows,), jnp.bool_), bcols,
            (sds((build,), jnp.bool_),) * 10, lane, lane,
            sds((out_cap,), jnp.bool_))
        out_cols, out_nulls, keep = compiled.out_info
        assert len(out_cols) == len(out_nulls) == 16
        assert keep.shape == (out_cap + (rows if left else 0),)


def test_direct_probe_compiles_for_v5e(one_chip):
    """The direct-address probe at q3-SF1 sizes: the ``orderkey`` build
    (2^20 sorted rows, 6.0 M codes: a table of 2^23 int32 offsets) and
    one coalesced probe page of 524,288 rows."""
    from trino_tpu.ops.join import (_build_direct_offsets, _key_span,
                                    _probe_direct_counts)

    build, kp, probe = 1 << 20, 1 << 23, 1 << 19
    u64 = sds((build,), jnp.uint64)
    span = sds((3,), jnp.uint64)
    perm = sds((build,), jnp.int32)
    _compile(_key_span, one_chip, u64, perm)
    _compile(lambda k, s: _build_direct_offsets.jit(k, s, kp=kp),
             one_chip, u64, span)
    _compile(_probe_direct_counts.jit, one_chip, sds((kp,), jnp.int32),
             span, sds((probe,), jnp.uint64), sds((probe,), jnp.bool_))


@pytest.mark.parametrize("form", ["table", "sorted", None])
def test_dynamic_filter_programs_compile_for_v5e(form, one_chip):
    """The dynamic filter at q9-SF1 sizes: a 2^21-lane build column
    whose keys span ``l_orderkey``'s 6.0 M codes (a table of 2^23
    bytes) and the mask of one resident ``lineitem`` page of 262,144
    lanes under each form of the value set."""
    from trino_tpu.exec.dynamic_filter import (_dynamic_filter_mask,
                                               _dynamic_filter_span,
                                               _dynamic_filter_table)

    build, kp, page = 1 << 21, 1 << 23, 1 << 18
    i64, i8 = jnp.int64, jnp.int8
    scalar = sds((), i64)
    if form == "table":
        flag = sds((build,), jnp.bool_)
        _compile(_dynamic_filter_span.jit, one_chip, sds((build,), i64),
                 flag, flag)
        _compile(lambda c, n, v, lo: _dynamic_filter_table.jit(
            c, n, v, lo, kp=kp), one_chip, sds((build,), i64), flag, flag,
            scalar)
    members = {"table": sds((kp,), i8), "sorted": sds((1 << 14,), i64),
               None: None}[form]
    compiled = _compile(
        lambda c, n, v, lo, hi, m, nan, p, s: _dynamic_filter_mask.jit(
            c, n, v, lo, hi, m, nan, p, s, form=form),
        one_chip, sds((page,), i64), sds((page,), jnp.bool_),
        sds((page,), jnp.bool_), scalar, scalar, members,
        sds((), jnp.bool_), scalar, scalar)
    # only the search loops: the table's test is a gather
    assert ("while" in compiled.as_text()) == (form == "sorted")


def test_sort_by_compiles_for_v5e(one_chip):
    """ORDER BY / TopN over a trimmed aggregation output (q3 at SF1
    sorts ~11,600 groups: 16,384 lanes) — two keys, chained single-key
    sorts."""
    from trino_tpu.ops.sort import _sorted_by

    n = 1 << 14
    key_ops = (sds((n,), jnp.uint8), sds((n,), jnp.uint64)) * 2
    cols = (sds((n,), jnp.int64), sds((n,), jnp.float64),
            sds((n,), jnp.int32))
    nulls = (sds((n,), jnp.bool_),) * 3

    def fn(k, c, nl, v):
        return _sorted_by.jit(k, c, nl, v, num_key_ops=len(k))

    _compile(fn, one_chip, key_ops, cols, nulls, sds((n,), jnp.bool_))


def test_resident_store_programs_compile_for_v5e(one_chip):
    """The memory connector's write-time programs at SF1 ``lineitem``'s
    shapes: 8 int64 + 8 int32 columns and their null masks, staged in
    2 x 262,144 lanes and cut into pages of 262,144."""
    from trino_tpu.connectors.memory import PAGE_ROWS, _kernels

    k = _kernels()

    def arrays(lanes):
        return (sds((lanes,), jnp.int64),) * 8 + \
            (sds((lanes,), jnp.int32),) * 8 + (sds((lanes,), jnp.bool_),) * 16

    valid = sds((PAGE_ROWS,), jnp.bool_)
    _compile(k.live_prefix, one_chip, valid)
    _compile(k.compact, one_chip, arrays(PAGE_ROWS), valid)
    _compile(k.stage_in, one_chip, arrays(2 * PAGE_ROWS),
             arrays(PAGE_ROWS), sds((), jnp.int32))
    _compile(lambda stage, fill: k.cut(stage, fill, PAGE_ROWS), one_chip,
             arrays(2 * PAGE_ROWS), sds((), jnp.int32))
    _compile(k.shift, one_chip, arrays(2 * PAGE_ROWS))


def test_device_exchange_compiles_for_v5e_mesh(mesh4):
    """The count and data collectives over the four described devices
    at ``(4, 65536)`` slabs: the data program must hold an all-to-all."""
    from trino_tpu.parallel.device_exchange import (_count_program,
                                                    _exchange_program)

    types_ = (T.BIGINT, T.BIGINT)
    rows = NamedSharding(mesh4, PartitionSpec("x"))
    whole = NamedSharding(mesh4, PartitionSpec())
    cols = _on(rows, tuple(sds((4, PAGE), jnp.int64) for _ in types_))
    nulls = _on(rows, tuple(sds((4, PAGE), jnp.bool_) for _ in types_))
    valid = sds((4, PAGE), jnp.bool_, sharding=rows)
    count = _count_program(mesh4, types_, (0,), 4, 4).jit
    count.lower(cols, nulls, valid, ()).compile()
    prog = _exchange_program(mesh4, types_, (0,), 4, 4, PAGE // 2).jit
    compiled = prog.lower(cols, nulls, valid, (),
                          sds((4,), jnp.int32, sharding=whole)).compile()
    assert "all-to-all" in compiled.as_text()


# ------------------------------------------- jax.export lowerings ----


def test_device_exchange_program_lowers_for_tpu():
    """The data all_to_all program (shard_map + collective) against an
    8-device TPU-platform lowering."""
    from trino_tpu.parallel.device_exchange import _exchange_program

    mesh = Mesh(np.asarray(jax.devices()[:8]), ("x",))
    types_ = (T.BIGINT, T.BIGINT)
    # .jit: the profiler wrapper keeps the raw jit product for
    # export (jax.export requires the jit callable itself)
    prog = _exchange_program(mesh, types_, (0,), 8, 8, 32).jit
    cap = 128
    cols = tuple(sds((8, cap), jnp.int64) for _ in types_)
    nulls = tuple(sds((8, cap), jnp.bool_) for _ in types_)
    ex = _export_tpu(prog, cols, nulls, sds((8, cap), jnp.bool_), (),
                     sds((8,), jnp.int32))  # the hot-partition mask
    assert "tpu" in ex.platforms


def test_count_program_lowers_for_tpu():
    """The count-first sizing collective (psum/pmax of histograms)."""
    from trino_tpu.parallel.device_exchange import _count_program

    mesh = Mesh(np.asarray(jax.devices()[:8]), ("x",))
    types_ = (T.BIGINT, T.BIGINT)
    prog = _count_program(mesh, types_, (0,), 8, 8).jit
    cap = 128
    cols = tuple(sds((8, cap), jnp.int64) for _ in types_)
    nulls = tuple(sds((8, cap), jnp.bool_) for _ in types_)
    ex = _export_tpu(prog, cols, nulls, sds((8, cap), jnp.bool_), ())
    assert "tpu" in ex.platforms


def test_q1_device_step_lowers_for_tpu():
    """The flagship fused filter+project+group-aggregate step — the
    program ``__graft_entry__.entry`` compiles on the real chip."""
    from __graft_entry__ import q1_example_args

    step, args = q1_example_args()
    ex = _export_tpu(jax.jit(step), *jax.eval_shape(lambda: args))
    assert "tpu" in ex.platforms
