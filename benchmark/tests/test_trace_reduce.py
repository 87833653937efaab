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
