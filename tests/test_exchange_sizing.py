"""Skew-adaptive device-exchange sizing (parallel/device_exchange.py).

The overflow protocol used to be a 2x cost cliff: lane overflow re-ran
the WHOLE all_to_all with doubled per_dest, so a skewed key distribution
paid the full shuffle twice or more. These tests pin the count-first
protocol: a 90%-of-rows-in-one-partition exchange completes with ZERO
doubling retries and exactly one data collective (exact mode), the
per-shape history pre-sizes repeat shapes without re-counting OR
recompiling (asserted via jit_stats), a stale history still completes
through the doubling backstop, and the skew stats surface identically on
the device and host paths through EXPLAIN ANALYZE.
"""

import jax
import numpy as np
import pytest

from trino_tpu import jit_stats
from trino_tpu import types as T
from trino_tpu.block import DevicePage, Page
from trino_tpu.parallel.device_exchange import (DeviceExchange,
                                                SIZING_HISTORY)

SIZING_KERNELS = ("device_exchange_program", "device_exchange_count")


@pytest.fixture(autouse=True)
def fresh_history():
    SIZING_HISTORY.reset()
    yield
    SIZING_HISTORY.reset()


def _skewed_exchange(sizing: str, n: int = 4, d: int = None,
                     rows_per_task: int = 1000, hot_frac: float = 0.9,
                     seed: int = 0,
                     threshold: float = 0.5) -> DeviceExchange:
    """Build + drain a DeviceExchange where ~hot_frac of all rows carry
    ONE key (=> one hot partition). Returns the collected exchange."""
    devs = jax.devices()
    d = n if d is None else d
    ex = DeviceExchange(n, devs[:d], sizing=sizing,
                        hot_split_threshold=threshold)
    ex.configure([T.BIGINT, T.BIGINT], [0])
    rng = np.random.default_rng(seed)
    for t in range(n):
        keys = np.where(rng.random(rows_per_task) < hot_frac, 7,
                        rng.integers(0, 10_000, rows_per_task))
        vals = rng.integers(0, 100, rows_per_task)
        p = Page.from_pylists([T.BIGINT, T.BIGINT],
                              [keys.tolist(), vals.tolist()])
        ex.add_page(t, DevicePage.from_page(p))
    ex.set_no_more_pages()
    # drain every partition (first pages() call triggers the collective)
    total = sum(pg.count() for part in range(n) for pg in ex.pages(part))
    assert total == n * rows_per_task
    return ex


def _partition_rows(ex: DeviceExchange, n: int):
    """Sorted (key, value) multiset per partition — the byte-equality
    surface: splitting may reorder rows across receiver slabs but must
    deliver the identical multiset to each consumer partition."""
    out = []
    for part in range(n):
        rows = []
        for pg in ex.pages(part):
            v = np.asarray(pg.valid)
            rows.extend(zip(np.asarray(pg.cols[0])[v].tolist(),
                            np.asarray(pg.cols[1])[v].tolist()))
        out.append(sorted(rows))
    return out


def test_exact_sizing_zero_retries_single_data_collective():
    before = DeviceExchange.total_collectives
    ex = _skewed_exchange("exact")
    assert ex.a2a_retries == 0
    assert ex.data_collectives == 1
    assert ex.count_collectives == 1
    assert DeviceExchange.total_collectives - before == 1
    s = ex.stats
    assert s["sizing_used"] == "exact"
    # 90% of 4000 rows in one of 4 partitions: skew ratio near 4 * 0.9
    assert s["skew_ratio"] > 2.5
    assert max(s["partition_rows"]) > 0.85 * s["rows"]
    assert s["per_dest"] >= s["observed_max_pair_rows"]
    assert s["bytes_moved"] > 0


def test_history_presizes_repeat_without_count_or_recompile():
    ex1 = _skewed_exchange("history", seed=1)
    assert ex1.count_collectives == 1  # unconfident: counted
    assert ex1.a2a_retries == 0
    traces_before = jit_stats.total_for(*SIZING_KERNELS)
    ex2 = _skewed_exchange("history", seed=1)
    # presized from history: no count pass, no doubling, and the data
    # program came straight from the lru_cache (zero new traces)
    assert ex2.count_collectives == 0
    assert ex2.a2a_retries == 0
    assert ex2.data_collectives == 1
    assert ex2.stats["sizing_used"] == "history"
    assert ex2.stats["per_dest"] == ex1.stats["per_dest"]
    assert jit_stats.total_for(*SIZING_KERNELS) == traces_before, (
        "history-presized repeat shape recompiled an exchange kernel")


def test_stale_history_recovers_via_backstop_and_relearns():
    """An undersized history presize must not wedge the exchange: the
    doubling backstop completes it, and the observation re-teaches the
    history so the NEXT run presizes correctly."""
    # teach the history a tiny load for this exchange shape
    ex_small = _skewed_exchange("history", rows_per_task=40,
                                hot_frac=0.0, seed=2)
    assert ex_small.a2a_retries == 0
    # same shape signature (types/keys/n/d), much bigger skewed load
    ex_big = _skewed_exchange("history", rows_per_task=4000, seed=3)
    assert ex_big.count_collectives == 0  # presized (stale)
    assert ex_big.a2a_retries >= 1        # backstop fired
    ex_next = _skewed_exchange("history", rows_per_task=4000, seed=3)
    assert ex_next.a2a_retries == 0       # history re-learned


@pytest.mark.parametrize("d", [1, 2])
def test_skew_with_fewer_devices_than_partitions(d):
    """The d<p carried-partition path under 90% skew: partitions split
    device slabs by carried id, sizing stays exact (zero retries), and
    every row lands in its hash partition."""
    import jax.numpy as jnp

    from trino_tpu.parallel.exchange import hash_partition_ids

    n = 4
    ex = _skewed_exchange("exact", n=n, d=d, rows_per_task=500)
    assert ex.d == d and ex.n == n
    assert ex.a2a_retries == 0
    assert ex.data_collectives == 1
    assert sum(ex.stats["partition_rows"]) == ex.stats["rows"]
    # routing correctness: rows of partition p hash to p
    for part in range(n):
        for pg in ex.pages(part):
            keys = np.asarray(pg.cols[0])[np.asarray(pg.valid)]
            if len(keys) == 0:
                continue
            got = np.asarray(hash_partition_ids(
                [jnp.asarray(keys).astype(jnp.int64).view(jnp.uint64)],
                n))
            assert (got == part).all()


def test_host_buffer_stats_parity():
    """The host path exposes the SAME stats surface (keys) the device
    path records, so EXPLAIN ANALYZE renders both identically."""
    from trino_tpu.ops.output import OutputBuffer

    buf = OutputBuffer(4)
    for p, rows in ((0, 90), (1, 5), (2, 5)):
        page = Page.from_pylists([T.BIGINT], [list(range(rows))])
        buf.enqueue(p, page)
    s = buf.stats
    assert s["kind"] == "host"
    assert s["rows"] == 100
    assert s["partition_rows"] == [90, 5, 5, 0]
    assert s["skew_ratio"] == 3.6
    ex = _skewed_exchange("exact", seed=4)
    assert set(s) <= set(ex.stats) | {"source_fragment"}


def test_explain_analyze_shows_exchange_skew_lines():
    """Acceptance surface: EXPLAIN ANALYZE shows per-exchange skew
    ratio, per_dest chosen, and retry count on the device path."""
    from trino_tpu.connectors.tpch import TpchConnector
    from trino_tpu.parallel.distributed import DistributedQueryRunner
    from trino_tpu.sql.analyzer import Session

    s = Session(catalog="tpch", schema="micro")
    s.properties["device_exchange"] = True
    s.properties["device_exchange_sizing"] = "exact"
    r = DistributedQueryRunner({"tpch": TpchConnector(page_rows=2048)}, s,
                               n_workers=3, desired_splits=8)
    res = r.execute(
        "EXPLAIN ANALYZE SELECT l_returnflag, count(*), sum(l_quantity) "
        "FROM lineitem GROUP BY l_returnflag")
    text = "\n".join(row[0] for row in res.rows)
    device_lines = [ln for ln in text.splitlines()
                    if "exchange [device]" in ln]
    assert device_lines, text
    for ln in device_lines:
        assert "skew" in ln and "per_dest=" in ln and "retries=" in ln
        assert "sizing=exact" in ln
    # host-side boundaries of the same query render the same shape
    assert any("exchange [host]" in ln for ln in text.splitlines())


def test_sizing_session_property_validates_and_normalizes():
    from trino_tpu.session_properties import set_property
    from trino_tpu.types import TrinoError

    props = {}
    set_property(props, "device_exchange_sizing", "EXACT")
    assert props["device_exchange_sizing"] == "exact"
    with pytest.raises(TrinoError):
        set_property(props, "device_exchange_sizing", "sometimes")
    set_property(props, "hot_partition_split_threshold", 0.8)
    assert props["hot_partition_split_threshold"] == 0.8
    with pytest.raises(TrinoError):
        set_property(props, "hot_partition_split_threshold", 1.5)
    set_property(props, "scale_writers_enabled", "true")
    assert props["scale_writers_enabled"] is True
    with pytest.raises(TrinoError):
        set_property(props, "rebalance_min_collectives", 0)


# ------------------------------------------ hot-partition splitting ----


def test_hot_split_byte_equal_and_spreads_receivers():
    """The acceptance witness: a 95%-hot-key exchange with splitting
    delivers the IDENTICAL per-partition row multisets as the unsplit
    path, but the hot partition's rows arrive over >= 2 receiver lanes
    and the max receiver-lane load (lane skew) collapses — with zero
    overflow retries and one data collective."""
    ex_split = _skewed_exchange("exact", hot_frac=0.95, seed=10,
                                threshold=0.5)
    SIZING_HISTORY.reset()
    ex_plain = _skewed_exchange("exact", hot_frac=0.95, seed=10,
                                threshold=1.0)
    n = 4
    assert _partition_rows(ex_split, n) == _partition_rows(ex_plain, n)
    s, p = ex_split.stats, ex_plain.stats
    assert s["splits"] == 1 and len(s["hot_partitions"]) == 1
    assert s["split_ways"] == ex_split.d
    hot = s["hot_partitions"][0]
    assert s["hot_spread"][hot] >= 2
    assert p["splits"] == 0 and p["hot_spread"] == {}
    # receiver-lane loads flatten; the DATA's partition skew stays put
    assert s["lane_skew_ratio"] < 1.5 < p["lane_skew_ratio"]
    assert s["skew_ratio"] == p["skew_ratio"] > 2.5
    # the split collective is also SMALLER: lanes sized to the spread
    # load, not the hot partition's full per-sender load
    assert s["per_dest"] < p["per_dest"]
    assert ex_split.a2a_retries == 0
    assert ex_split.data_collectives == 1
    assert ex_split.count_collectives == 1


def test_hot_split_engages_above_threshold_not_below():
    ex = _skewed_exchange("exact", hot_frac=0.95, seed=11,
                          threshold=0.97)
    assert ex.stats["splits"] == 0  # 95% < 97%: below threshold
    SIZING_HISTORY.reset()
    ex = _skewed_exchange("exact", hot_frac=0.95, seed=11,
                          threshold=0.5)
    assert ex.stats["splits"] == 1  # above: engaged
    SIZING_HISTORY.reset()
    # uniform keys: no partition crosses any sane threshold
    ex = _skewed_exchange("exact", hot_frac=0.0, seed=11, threshold=0.5)
    assert ex.stats["splits"] == 0


def test_hot_split_repeat_hits_program_cache():
    """History-presized repeats of a SPLIT exchange shape re-use the
    compiled program: the hot set rides as a traced mask (not a cache
    key), the hot decision comes from the history's remembered
    partition fractions, and jit-trace counters stay flat."""
    ex1 = _skewed_exchange("history", hot_frac=0.95, seed=12)
    assert ex1.stats["splits"] == 1
    assert ex1.count_collectives == 1  # unconfident: counted
    traces_before = jit_stats.total_for(*SIZING_KERNELS)
    ex2 = _skewed_exchange("history", hot_frac=0.95, seed=12)
    assert ex2.count_collectives == 0  # presized: no count pass
    assert ex2.a2a_retries == 0
    assert ex2.stats["splits"] == 1   # hot set remembered by shape
    assert ex2.stats["hot_partitions"] == ex1.stats["hot_partitions"]
    assert ex2.stats["per_dest"] == ex1.stats["per_dest"]
    assert jit_stats.total_for(*SIZING_KERNELS) == traces_before, (
        "split repeat shape recompiled an exchange kernel")
    assert _partition_rows(ex1, 4) == _partition_rows(ex2, 4)


def test_hot_split_with_fewer_devices_than_partitions():
    """d < n plus splitting: hot sub-buckets and carried-partition
    slab-splitting compose — every row still reaches the consumer of
    its ORIGINAL hash partition, exactly once."""
    import jax.numpy as jnp

    from trino_tpu.parallel.exchange import hash_partition_ids

    n, d = 4, 2
    ex = _skewed_exchange("exact", n=n, d=d, rows_per_task=500,
                          hot_frac=0.95, seed=13)
    assert ex.stats["splits"] == 1
    hot = ex.stats["hot_partitions"][0]
    assert ex.stats["hot_spread"][hot] == d
    assert ex.a2a_retries == 0
    for part in range(n):
        for pg in ex.pages(part):
            keys = np.asarray(pg.cols[0])[np.asarray(pg.valid)]
            if len(keys) == 0:
                continue
            got = np.asarray(hash_partition_ids(
                [jnp.asarray(keys).astype(jnp.int64).view(jnp.uint64)],
                n))
            assert (got == part).all()


# ------------------------------------------ scaled-writer rebalancer ----


def _feed(reb, hist, times):
    trail = []
    for _ in range(times):
        reb.observe(hist)
        trail.append(reb.assignment())
    return trail


def test_rebalancer_deterministic_under_fixed_seed():
    from trino_tpu.parallel.rebalancer import UniformPartitionRebalancer

    hist = [9000, 50, 40, 60, 30, 45, 55, 35]
    t1 = _feed(UniformPartitionRebalancer(8, 4, seed=42), hist, 6)
    t2 = _feed(UniformPartitionRebalancer(8, 4, seed=42), hist, 6)
    assert t1 == t2  # the FULL assignment history reproduces


def test_rebalancer_scales_hot_partition_and_does_not_flap():
    from trino_tpu.parallel.rebalancer import UniformPartitionRebalancer

    reb = UniformPartitionRebalancer(8, 4, min_collectives=2)
    hist = [9000, 50, 40, 60, 30, 45, 55, 35]
    trail = [reb.assignment()] + _feed(reb, hist, 10)
    # the hot logical partition ends up SCALED over >= 2 writer lanes
    assert len(trail[-1][0]) >= 2
    assert reb.stats()["scaled_partitions"] >= 1
    # stability: under a stationary distribution the assignment
    # converges and then stops changing (no flapping)
    assert trail[-1] == trail[-2] == trail[-3]
    changes = sum(1 for a, b in zip(trail, trail[1:]) if a != b)
    assert 1 <= changes <= 4
    # a balanced distribution never triggers a rebalance at all
    calm = UniformPartitionRebalancer(8, 4, min_collectives=2)
    assert _feed(calm, [100] * 8, 6)[-1] == calm.assignment()
    assert calm.rebalances == 0


def test_rebalancer_unscales_cooled_partition():
    """The reverse transition: a hot partition that SCALED over extra
    writer lanes releases them again once its load cools (same
    hysteresis window), and the flap guard holds — a stationary
    distribution, hot or cooled, converges and stays put."""
    from trino_tpu.parallel.rebalancer import UniformPartitionRebalancer

    reb = UniformPartitionRebalancer(8, 4, min_collectives=2)
    hot = [9000, 50, 40, 60, 30, 45, 55, 35]
    _feed(reb, hot, 10)
    scaled_lanes = len(reb.lanes_for(0))
    assert scaled_lanes >= 2
    stable_hot = reb.assignment()
    # keep feeding the SAME hot distribution: no un-scale (no flap)
    _feed(reb, hot, 6)
    assert reb.assignment() == stable_hot
    # the partition cools to the pack: lanes come back, one per
    # hysteresis window, down to a single lane
    cool = [50, 50, 40, 60, 30, 45, 55, 35]
    trail = _feed(reb, cool, 24)
    assert len(reb.lanes_for(0)) == 1
    # converged again: the cooled layout stops changing
    assert trail[-1] == trail[-2] == trail[-3]
    # determinism: an identical history reproduces the transitions
    reb2 = UniformPartitionRebalancer(8, 4, min_collectives=2)
    _feed(reb2, hot, 16)
    _feed(reb2, cool, 24)
    assert reb2.assignment() == reb.assignment()


def test_rebalancer_hysteresis_respects_min_collectives():
    from trino_tpu.parallel.rebalancer import UniformPartitionRebalancer

    reb = UniformPartitionRebalancer(8, 4, min_collectives=4)
    hist = [9000, 50, 40, 60, 30, 45, 55, 35]
    trail = _feed(reb, hist, 12)
    changes = [i for i, (a, b) in enumerate(zip(trail, trail[1:]))
               if a != b]
    # consecutive assignment changes are >= min_collectives apart
    assert all(b - a >= 4 for a, b in zip(changes, changes[1:]))


def test_partitioned_join_splits_hot_probe_and_matches_broadcast():
    """Acceptance, end to end: a PARTITIONED join whose probe side is
    90% one key ships RAW rows through the device exchange — the hot
    partition splits (EXPLAIN ANALYZE shows the splits=..x.. surface),
    zero overflow retries, and the result matches the broadcast plan
    (no exchange of probe rows at all — the unsplit oracle)."""
    from trino_tpu import types as TT
    from trino_tpu.connectors.memory import MemoryConnector
    from trino_tpu.parallel.distributed import DistributedQueryRunner
    from trino_tpu.sql.analyzer import Session

    rng = np.random.default_rng(31)
    keys = np.where(rng.random(6000) < 0.9, 7,
                    rng.integers(0, 300, 6000))
    conn = MemoryConnector()

    def runner(**props):
        s = Session(catalog="mem", schema="default")
        s.properties.update(props)
        return DistributedQueryRunner({"mem": conn}, s, n_workers=4,
                                      desired_splits=4)

    r = runner(join_distribution_type="PARTITIONED",
               device_exchange_sizing="exact")
    r.execute("create table z (k bigint, v bigint)")
    h = conn.metadata().get_table_handle("default", "z")
    cols = conn.metadata().get_columns(h)
    sink = conn.page_sink(h, cols)
    sink.append_page(Page.from_pylists(
        [TT.BIGINT, TT.BIGINT], [keys.tolist(), keys.tolist()]))
    sink.finish()
    r.execute("create table dim (k bigint, name bigint)")
    sink2 = conn.page_sink(
        conn.metadata().get_table_handle("default", "dim"),
        conn.metadata().get_columns(h))
    sink2.append_page(Page.from_pylists(
        [TT.BIGINT, TT.BIGINT],
        [list(range(300)) + [7], list(range(301))]))
    sink2.finish()
    sql = "select count(*) from z, dim where z.k = dim.k"
    res = r.execute("EXPLAIN ANALYZE " + sql)
    text = "\n".join(row[0] for row in res.rows)
    device_lines = [ln for ln in text.splitlines()
                    if "exchange [device]" in ln]
    assert any("splits=" in ln for ln in device_lines), text
    assert all("retries=0" in ln for ln in device_lines)
    got = r.execute(sql).rows
    want = runner(join_distribution_type="BROADCAST").execute(sql).rows
    assert got == want


def test_scaled_writer_ctas_correct_and_rebalances():
    """End-to-end: CTAS over a 90%-hot key with scale_writers_enabled
    routes rows through the rebalancing hash boundary — written rows
    identical to the unscaled plan, rebalancer engaged."""
    from trino_tpu import types as TT
    from trino_tpu.connectors.memory import MemoryConnector
    from trino_tpu.parallel.distributed import DistributedQueryRunner
    from trino_tpu.parallel.rebalancer import UniformPartitionRebalancer
    from trino_tpu.sql.analyzer import Session

    rng = np.random.default_rng(21)
    keys = np.where(rng.random(8000) < 0.9, 7,
                    rng.integers(0, 500, 8000))
    vals = rng.integers(0, 100, 8000)

    def run(scale):
        SIZING_HISTORY.reset()
        s = Session(catalog="mem", schema="default")
        s.properties["scale_writers_enabled"] = scale
        r = DistributedQueryRunner({"mem": MemoryConnector()}, s,
                                   n_workers=4, desired_splits=4)
        r.execute("create table z (k bigint, v bigint)")
        conn = r.metadata.connectors["mem"]
        h = conn.metadata().get_table_handle("default", "z")
        sink = conn.page_sink(h, conn.metadata().get_columns(h))
        sink.append_page(Page.from_pylists(
            [TT.BIGINT, TT.BIGINT], [keys.tolist(), vals.tolist()]))
        sink.finish()
        written = r.execute("create table out as select k, v from z")
        rows = sorted(r.execute("select k, v from out").rows)
        return written.rows, rows

    before = UniformPartitionRebalancer.total_rebalances
    count_off, rows_off = run(False)
    count_on, rows_on = run(True)
    assert count_on == count_off == [(8000,)]
    assert rows_on == rows_off
    assert UniformPartitionRebalancer.total_rebalances > before


def test_stale_history_overflow_shows_on_the_exchange_span():
    """A forced ``per_dest`` overflow inside a traced statement: the
    ``exchange`` span says the collective ran again (``a2a_retries``,
    every attempt's lanes in ``bytes_moved``) and that the doubled
    ``per_dest`` was a program to lower (``lowered``)."""
    from trino_tpu.parallel import device_exchange
    from trino_tpu.telemetry import tracing

    SIZING_HISTORY.reset()
    device_exchange._exchange_program.cache_clear()
    _skewed_exchange("history", rows_per_task=40, hot_frac=0.0, seed=2)
    tracer = tracing.Tracer()
    with tracer.span("statement") as root:
        ex = _skewed_exchange("history", rows_per_task=4000, seed=3)
    span, = [s for s in tracer.finished() if s["name"] == "exchange"]
    a = span["attrs"]
    assert span["parent_id"] == root.span_id
    assert a["a2a_retries"] == ex.a2a_retries >= 1
    assert a["data_collectives"] == a["a2a_retries"] + 1
    assert a["sizing_used"] == "history" and a["count_collectives"] == 0
    assert a["lowered"] >= a["a2a_retries"]
    # the attempts doubled up to the last one's per_dest (or the cap)
    attempts = [a["per_dest"] >> k for k in range(a["data_collectives"])]
    assert a["bytes_moved"] == sum(attempts) * 4 * 4 * a["lane_bytes"]
    by_why = root.attrs["host_sync_by_why"]
    assert by_why["exchange_overflow"][0] == a["data_collectives"]
    assert by_why["exchange_ready"][0] == a["data_collectives"]
    assert by_why["exchange_readback"][0] == 1
    assert root.attrs["lowerings_by_program"]["exchanged"][0] \
        >= a["a2a_retries"]
