"""``trace_reduce`` on a slice of a trace recorded on the chip.

``data/q3_slice.xplane.pb`` is 0.25 s of the device plane (``XLA Ops``,
``XLA Modules``) and the host plane of one ``sf1_q3_join`` statement traced
on a TPU v5 lite in PR 24, cut with the xplane protobuf bindings.  The
expected numbers were read straight from the protobuf, not through
``trace_reduce``: 784 operations whose union is 248,249,042,902 ps, one
program execution (``jit__expand_verified_impl``, 202,387,642,422 ps).
"""

import os

import pytest

from benchmark import trace_reduce

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data",
                    "q3_slice.xplane.pb")


def test_union_merges_overlaps():
    assert trace_reduce.union([(5, 7), (0, 2), (1, 3), (7, 8)]) == \
        [[0, 3], [5, 8]]


def test_short_names():
    assert trace_reduce.short_name("jit_f(123456)") == "jit_f"
    assert trace_reduce.short_name(
        "%while.5 = (u32[]{:T(128)}, s32[8]{0:T(1024)S(1)}) "
        "while((u32[]{:T(128)}) %tuple.82), condition=%c") == \
        "%while.5 while"
    assert trace_reduce.short_name(
        "%fusion.2 = u32[8]{0:T(1024)S(1)} fusion(u32[8]{0} %p), "
        "kind=kCustom") == "%fusion.2 fusion"


def test_recorded_tpu_slice():
    out = trace_reduce.reduce(trace_reduce.load(DATA))
    assert out["devices"] == 1
    assert out["launches"] == 1
    assert out["busy_s"] == pytest.approx(0.248249042902, rel=1e-6)
    programs = dict(out["device_ops"][:1])
    assert programs["jit__expand_verified_impl"] == \
        pytest.approx(0.202387642422, rel=1e-6)
    assert any(name.startswith("jit__expand_verified_impl/%while")
               for name, _ in out["device_ops"])
    # every idle second is labelled, and falls inside the statement's spans
    idle = sum(t for _, t in out["idle_gaps"])
    assert 0 < idle < out["span_s"] - out["busy_s"] + 1e-9
    assert all(label.endswith(" in runner.execute")
               for label, _ in out["idle_gaps"])


def test_no_device_plane_reads_nothing(tmp_path):
    class Line:
        name, events = "python", []

    class Plane:
        name, lines = "/host:CPU", [Line()]

    class Profile:
        planes = [Plane()]

    out = trace_reduce.reduce(Profile())
    assert out["busy_s"] is None and out["launches"] is None


# -- a hand-made trace of two devices with collectives ----------------------

class _Event:
    def __init__(self, start_ns, end_ns, name):
        self.start_ns, self.duration_ns, self.name = \
            start_ns, end_ns - start_ns, name


class _Line:
    def __init__(self, name, events):
        self.name, self.events = name, [_Event(*e) for e in events]


class _Plane:
    def __init__(self, name, lines):
        self.name, self.lines = name, [_Line(*ln) for ln in lines]


class _Profile:
    def __init__(self, planes):
        self.planes = planes


A2A = "%all-to-all.3 = (s64[4,64]{1,0}) all-to-all(s64[4,64]{1,0} %p.1)"
REDUCE_START = ("%all-reduce-start.1 = s32[4]{0} async-start(s32[4]{0} "
                "%p.2), calls=%all-reduce.1")
FUSION = "%fusion.7 = s64[256]{0} fusion(s64[256]{0} %p.3), kind=kLoop"
WHILE = "%while.2 = (s32[], s64[256]{0}) while((s32[]) %t.1), condition=%c"
MS = 1_000_000


def two_devices():
    """Device 0: a fusion (0-10 ms), an all-to-all (10-14 ms) inside a
    loop (8-20 ms) with a fusion overlapping its last millisecond
    (13-15 ms).  Device 1: an all-to-all (10-12 ms) alone and an
    asynchronous all-reduce's start (30-31 ms).  Listed second but
    numbered first, device 0 must come first in ``per_device``."""
    dev1 = _Plane("/device:TPU:1", [
        ("XLA Modules", [(10 * MS, 12 * MS, "jit_exchanged(1)"),
                         (30 * MS, 31 * MS, "jit_count(2)")]),
        ("XLA Ops", [(10 * MS, 12 * MS, A2A),
                     (30 * MS, 31 * MS, REDUCE_START)])])
    dev0 = _Plane("/device:TPU:0", [
        ("XLA Modules", [(0, 20 * MS, "jit_exchanged(1)")]),
        ("XLA Ops", [(0, 10 * MS, FUSION), (8 * MS, 20 * MS, WHILE),
                     (10 * MS, 14 * MS, A2A),
                     (13 * MS, 15 * MS, FUSION)])])
    host = _Plane("/host:CPU", [("python", [(0, 40 * MS, "runner.execute")])])
    return _Profile([dev1, host, dev0])


def test_collectives_are_told_by_opcode_or_result_name():
    assert trace_reduce.is_collective(A2A)
    assert trace_reduce.is_collective(REDUCE_START)
    assert not trace_reduce.is_collective(FUSION)
    assert not trace_reduce.is_collective(WHILE)
    assert not trace_reduce.is_collective("jit_exchanged(1)")


def test_two_planes_with_collectives():
    out = trace_reduce.reduce(two_devices())
    assert out["devices"] == 2
    assert [d["plane"] for d in out["per_device"]] == \
        ["/device:TPU:0", "/device:TPU:1"]
    dev0, dev1 = out["per_device"]
    assert dev0["busy_s"] == pytest.approx(0.020)
    assert dev1["busy_s"] == pytest.approx(0.003)
    assert out["busy_s"] == pytest.approx(0.0115)       # the mean
    assert (dev0["launches"], dev1["launches"]) == (1, 2)
    assert out["launches"] == 1.5
    assert dev0["collective_s"] == pytest.approx(0.004)
    assert dev1["collective_s"] == pytest.approx(0.003)
    assert out["collective_s"] == pytest.approx(0.0035)
    # device 0: the loop around the all-to-all is what holds it, the
    # fusion beside its last millisecond is overlap; device 1: all alone
    assert out["collective_exposed_s"] == pytest.approx(
        (0.003 + 0.003) / 2)
    assert out["device_ops"][-1] == [
        "<collectives: most less least over devices>",
        pytest.approx(0.001)]


class _Run:
    def __init__(self, trace, traced=2.0):
        self.trace, self._traced = trace, traced

    def statements_traced(self):
        return self._traced


def test_collective_readers():
    from benchmark.layer_metrics import (collective_exposed_pct,
                                         collective_ms)

    run = _Run(trace_reduce.reduce(two_devices()))
    assert collective_ms.read(run) == pytest.approx(3.5 / 2)
    assert collective_exposed_pct.read(run) == pytest.approx(
        100.0 * 3.0 / 3.5)
    # one chip, no collective: nothing to read, never a 0
    one = _Run(trace_reduce.reduce(trace_reduce.load(DATA)))
    assert one.trace["collective_s"] == 0.0
    assert collective_ms.read(one) is None
    assert collective_exposed_pct.read(one) is None
    assert collective_ms.read(_Run(None)) is None


def test_recorded_slice_keeps_its_values():
    """The keys ``reduce`` had before the collective ones read the same
    on the one-plane fixture (values of the parent's ``reduce``)."""
    out = trace_reduce.reduce(trace_reduce.load(DATA))
    assert out["busy_s"] == 0.248248898
    assert out["launches"] == 1.0
    assert out["span_s"] == 0.248253447
    assert out["device_ops"] == [
        ["jit__expand_verified_impl", 0.202387642],
        ["jit__expand_verified_impl/%while.7 while", 0.175939936],
        ["jit__expand_verified_impl/%fusion.32 fusion",
         0.13303965600000003],
        ["<no program>/%fusion.27 fusion", 0.054250185],
        ["jit__expand_verified_impl/%fusion.31 fusion", 0.042857393],
        ["<no program>/%fusion.26 fusion", 0.017989774]]
    assert out["idle_gaps"] == [
        ["<host idle> in runner.execute", 4.54899999999997e-06]]
    assert out["per_device"] == [{
        "plane": "/device:TPU:0", "busy_s": 0.248248898, "launches": 1,
        "collective_s": 0.0}]
