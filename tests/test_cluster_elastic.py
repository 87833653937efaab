"""Elastic membership primitives: ledger, placement, autoscaler policy.

Pure-python units (no worker processes): the ClusterLedger's generation
monotonicity, topology-aware placement determinism (including exact
degeneration to the historical round-robin when topology carries no
signal), and the autoscaler's hysteresis/cooldown/bounds behavior.
Process-level elasticity (add/retire mid-query, chaos, the autoscaler
growing a live cluster under a burst) lives in test_chaos.py.
"""

from trino_tpu.parallel.autoscaler import Autoscaler
from trino_tpu.parallel.cluster import (NODE_ACTIVE, NODE_DRAINING,
                                        NODE_RETIRED, ClusterLedger,
                                        place_task)


class _W:
    def __init__(self, port):
        self.addr = ("127.0.0.1", port)


# -- ledger ------------------------------------------------------------


def test_ledger_generation_monotonic_over_churn():
    led = ClusterLedger()
    n1 = led.record_join(("127.0.0.1", 1), pid=11, reason="initial")
    n2 = led.record_join(("127.0.0.1", 2), pid=12, reason="initial")
    assert (n1.generation, n2.generation) == (1, 2)
    assert led.generation == 2
    led.mark_draining(n1.node_id)
    assert led.snapshot()[0].state == NODE_DRAINING
    assert led.record_retire(n1.node_id, "scale-down") is not None
    assert led.generation == 3
    # double-retire is a no-op, generation does not advance
    assert led.record_retire(n1.node_id) is None
    assert led.generation == 3
    n3 = led.record_join(("127.0.0.1", 3), pid=13, reason="heal")
    assert n3.generation == 4
    states = [n.state for n in led.snapshot()]
    assert states == [NODE_RETIRED, NODE_ACTIVE, NODE_ACTIVE]
    assert led.counts() == (3, 1)


# -- placement ---------------------------------------------------------


def test_place_task_degenerates_to_round_robin_without_topology():
    ws = [_W(1), _W(2), _W(3)]
    for t in range(7):
        assert place_task(t, 0, ws) is ws[t % 3]
        # no upstream signal at all (leaf scan)
        assert place_task(t, 0, ws, upstream_addrs=[]) is ws[t % 3]
        # upstream lives elsewhere entirely: still round-robin
        assert place_task(t, 0, ws,
                          upstream_addrs=[("10.0.0.9", 5)]) is ws[t % 3]


def test_place_task_prefers_upstream_holder():
    ws = [_W(1), _W(2), _W(3)]
    up = [("127.0.0.1", 2), ("127.0.0.1", 2), ("127.0.0.1", 3)]
    # worker 2 holds two of three producer tasks: every task index
    # prefers it (deterministically)
    for t in range(5):
        assert place_task(t, 0, ws, upstream_addrs=up) is ws[1]


def test_place_task_breaks_score_ties_round_robin():
    ws = [_W(1), _W(2), _W(3)]
    up = [("127.0.0.1", 1), ("127.0.0.1", 3)]
    # workers 1 and 3 tie: rotate between them by task index
    assert place_task(0, 0, ws, upstream_addrs=up) is ws[0]
    assert place_task(1, 0, ws, upstream_addrs=up) is ws[2]
    assert place_task(2, 0, ws, upstream_addrs=up) is ws[0]


def test_place_task_retry_rotates_full_candidate_list():
    ws = [_W(1), _W(2), _W(3)]
    up = [("127.0.0.1", 2)]
    # retry ignores stale affinity: the preferred node just failed
    assert place_task(0, 1, ws, upstream_addrs=up) is ws[1]
    assert place_task(0, 2, ws, upstream_addrs=up) is ws[2]


# -- autoscaler --------------------------------------------------------


def _mk(clock):
    return Autoscaler(clock=lambda: clock[0])


def test_autoscaler_hysteresis_and_doubling():
    clock = [0.0]
    a = _mk(clock)
    kw = dict(min_workers=2, max_workers=8, cooldown_s=10.0,
              up_queue_depth=1, down_idle_ticks=4)
    # one pressure tick is not enough (hysteresis)
    assert a.tick(size=2, queued=3, running=2, **kw) is None
    d = a.tick(size=2, queued=3, running=2, **kw)
    assert d == {"direction": "up", "from": 2, "to": 4,
                 "reason": "queued=3"}
    # cooldown: sustained pressure cannot fire again yet
    assert a.tick(size=4, queued=3, running=4, **kw) is None
    assert a.tick(size=4, queued=3, running=4, **kw) is None
    clock[0] = 11.0
    d = a.tick(size=4, queued=3, running=4, **kw)
    assert d["to"] == 8  # doubles, capped at max
    clock[0] = 22.0
    a.tick(size=8, queued=9, running=8, **kw)
    assert a.tick(size=8, queued=9, running=8, **kw) is None  # at max
    assert a.scale_ups == 2


def test_autoscaler_idle_scale_down_one_at_a_time():
    clock = [0.0]
    a = _mk(clock)
    kw = dict(min_workers=2, max_workers=8, cooldown_s=5.0,
              up_queue_depth=1, down_idle_ticks=3)
    for _ in range(2):
        assert a.tick(size=4, queued=0, running=0, **kw) is None
    d = a.tick(size=4, queued=0, running=0, **kw)
    assert d == {"direction": "down", "from": 4, "to": 3,
                 "reason": "idle 3 ticks"}
    # a busy (but unpressured) tick resets the idle streak
    clock[0] = 10.0
    a.tick(size=3, queued=0, running=0, **kw)
    a.tick(size=3, queued=0, running=1, **kw)  # reset
    a.tick(size=3, queued=0, running=0, **kw)
    a.tick(size=3, queued=0, running=0, **kw)
    assert a.tick(size=3, queued=0, running=0, **kw)["to"] == 2
    # never below min
    clock[0] = 20.0
    for _ in range(10):
        assert a.tick(size=2, queued=0, running=0, **kw) is None
    assert a.scale_downs == 2


def test_autoscaler_below_min_restores_immediately():
    a = _mk([0.0])
    d = a.tick(size=1, queued=0, running=0, min_workers=2,
               max_workers=8, cooldown_s=100.0, up_queue_depth=1,
               down_idle_ticks=4)
    assert d == {"direction": "up", "from": 1, "to": 2,
                 "reason": "below min_workers"}


def test_autoscaler_blocked_nodes_count_as_pressure():
    clock = [0.0]
    a = _mk(clock)
    kw = dict(min_workers=1, max_workers=4, cooldown_s=0.0,
              up_queue_depth=5, down_idle_ticks=4)
    a.tick(size=2, queued=0, running=1, blocked_nodes=1, **kw)
    d = a.tick(size=2, queued=0, running=1, blocked_nodes=1, **kw)
    assert d["direction"] == "up" and "blocked_nodes" in d["reason"]


def test_autoscaler_deterministic_replay():
    ticks = [dict(size=2, queued=q, running=r)
             for q, r in [(0, 0), (2, 1), (3, 2), (0, 1), (0, 0),
                          (0, 0), (0, 0), (0, 0)]]
    kw = dict(min_workers=1, max_workers=8, cooldown_s=0.0,
              up_queue_depth=1, down_idle_ticks=2)

    def run():
        clock = [0.0]
        a = _mk(clock)
        out = []
        for t in ticks:
            clock[0] += 1.0
            out.append(a.tick(**t, **kw))
        return out

    assert run() == run()
