"""Reduction of a JAX profiler trace (``.xplane.pb``) to device busy and
idle time, program launches, the device operations that took most time
and the longest idle gaps labelled by what the host was doing.

Device planes are named ``/device:TPU:<n>``.  On each, the line
``XLA Ops`` holds one event per operation run and ``XLA Modules`` one per
program execution (a launch).  Collective operations (the exchange's
``all-to-all`` and the reductions that size it) are events of that line
too, told by their opcode or result name.  Host threads are lines of
``/host:CPU``; the benchmark's own spans
(``jax.profiler.TraceAnnotation``) are events there, beside the
runtime's own.
"""

from __future__ import annotations

import bisect
import glob
import os
import re
from collections import defaultdict

import numpy as np

DEVICE_PREFIX = "/device:TPU:"
HOST_PLANE = "/host:CPU"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
_OPCODE = re.compile(r"\s([a-z][a-z0-9-]*)\(")
#: HLO collectives, as an opcode (``all-to-all(``) or, where the
#: compiler made them asynchronous, as the result name of the
#: ``async-start`` / ``async-done`` pair (``%all-to-all-start.1``)
COLLECTIVES = ("all-to-all", "all-reduce", "all-gather", "reduce-scatter",
               "collective-permute", "collective-broadcast")


def find_xplane(trace_dir: str) -> str:
    files = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return files[-1]


def load(path: str):
    from jax.profiler import ProfileData

    return ProfileData.from_file(path)


def _events(line):
    return [(e.start_ns, e.start_ns + e.duration_ns, e.name)
            for e in line.events]


def union(intervals):
    """Sorted, merged ``[start, end)`` intervals."""
    merged = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            if e > merged[-1][1]:
                merged[-1][1] = e
        else:
            merged.append([s, e])
    return merged


def describe(profile) -> list:
    """Plane and line names with event counts: for a look by hand."""
    return [(p.name, ln.name, sum(1 for _ in ln.events))
            for p in profile.planes for ln in p.lines]


def _operation(name: str) -> tuple:
    """``(result, opcode)`` of an HLO operation's event name."""
    result, rest = name.split(" = ", 1)
    opcode = _OPCODE.search(" " + rest)
    return result, opcode.group(1) if opcode else ""


def short_name(name: str, limit: int = 96) -> str:
    """A program's name without its fingerprint, or an operation's
    result name and opcode without its operand list."""
    if " = " in name:                       # an HLO operation
        name = " ".join(_operation(name)).strip()
    elif name.endswith(")") and "(" in name:  # jit_f(1234567890)
        name = name[:name.rindex("(")]
    return name[:limit]


def is_collective(name: str) -> bool:
    """Whether an ``XLA Ops`` event is a collective operation."""
    if " = " not in name:
        return False
    result, opcode = _operation(name)
    return result.lstrip("%").startswith(COLLECTIVES) \
        or opcode.startswith(COLLECTIVES)


def collective_seconds(ops) -> tuple:
    """``(inside, exposed)`` seconds of one device's ``XLA Ops`` events:
    the union of its collective operations' intervals, and the part of
    it in which no other operation ran on that device.  An operation
    that spans a whole collective interval is what holds it (a loop, a
    call), not work that overlaps it, and does not count."""
    spans = np.array([(s, e) for s, e, _ in ops]).reshape(-1, 2)
    flags = np.array([is_collective(name) for _, _, name in ops], dtype=bool)
    inside = union(map(tuple, spans[flags]))
    if not inside:
        return 0.0, 0.0
    starts, ends = spans[~flags, 0], spans[~flags, 1]
    exposed = 0
    for cs, ce in inside:
        beside = (starts < ce) & (ends > cs) \
            & ~((starts <= cs) & (ends >= ce))
        covered = union(zip(np.maximum(starts[beside], cs),
                            np.minimum(ends[beside], ce)))
        exposed += (ce - cs) - sum(e - s for s, e in covered)
    return sum(e - s for s, e in inside) / 1e9, float(exposed) / 1e9


def reduce(profile, own_spans=("runner.execute", "client.execute"),
           top=10) -> dict:
    """See the module docstring.  Times are seconds.  ``busy_s`` is the
    mean over device planes of the union of their operation intervals;
    with no device plane (a CPU rehearsal) the device keys are None.
    ``collective_s`` / ``collective_exposed_s`` are the means over device
    planes of ``collective_seconds``; ``per_device`` has each plane's
    own ``busy_s``, ``launches`` and ``collective_s``, in the order of
    the planes' numbers.

    ``device_ops`` holds the programs that took most device time (the
    sum of their executions, by name, shapes together) and, as
    ``<program>/<operation>``, the single operations that did; a loop's
    time includes its body's operations, which are listed too.  With
    more than one device its last entry is the workers' skew: the most
    less the least time a device spent inside collectives.
    ``idle_gaps``, like ``device_ops``, are means over the devices."""
    device_planes, host_events = [], []
    for plane in sorted(profile.planes, key=lambda p: (len(p.name), p.name)):
        if plane.name.startswith(DEVICE_PREFIX):
            device_planes.append((plane.name, {
                ln.name: _events(ln) for ln in plane.lines
                if ln.name in (OPS_LINE, MODULES_LINE)}))
        elif plane.name == HOST_PLANE:
            for ln in plane.lines:
                host_events.extend(_events(ln))
    out = {"devices": len(device_planes), "busy_s": None, "launches": None,
           "device_ops": [], "idle_gaps": [], "span_s": None,
           "collective_s": None, "collective_exposed_s": None,
           "per_device": []}
    if not device_planes:
        return out
    busy, launches, span = [], 0, []
    collective, exposed = [], []
    program_time, op_time = defaultdict(float), defaultdict(float)
    gaps = []
    for plane_name, lines in device_planes:
        modules = sorted(lines.get(MODULES_LINE, ()))
        ops = lines.get(OPS_LINE) or modules
        merged = union((s, e) for s, e, _ in ops)
        busy.append(sum(e - s for s, e in merged) / 1e9)
        launches += len(modules)
        inside, alone = collective_seconds(lines.get(OPS_LINE, ()))
        collective.append(inside)
        exposed.append(alone)
        out["per_device"].append({
            "plane": plane_name, "busy_s": busy[-1],
            "launches": len(modules), "collective_s": inside})
        for s, e, name in modules:
            program_time[short_name(name)] += (e - s) / 1e9
        starts = [m[0] for m in modules]
        for s, e, name in lines.get(OPS_LINE, ()):
            i = bisect.bisect_right(starts, s) - 1
            program = short_name(modules[i][2]) \
                if i >= 0 and s < modules[i][1] else "<no program>"
            op_time[program + "/" + short_name(name)] += (e - s) / 1e9
        if merged:
            span.append((merged[-1][1] - merged[0][0]) / 1e9)
        gaps.extend((b[1], a[0]) for b, a in zip(merged, merged[1:]))
    out["busy_s"] = sum(busy) / len(busy)
    out["launches"] = launches / len(device_planes)
    out["span_s"] = max(span) if span else 0.0
    n = len(device_planes)
    out["collective_s"] = sum(collective) / n
    out["collective_exposed_s"] = sum(exposed) / n
    ranked = [sorted(d.items(), key=lambda kv: -kv[1])
              for d in (program_time, op_time)]
    out["device_ops"] = [[name, t / n] for name, t in
                         ranked[0][:top - top // 2] + ranked[1][:top // 2]]
    if n > 1:
        out["device_ops"][top - 1:] = [[
            "<collectives: most less least over devices>",
            max(collective) - min(collective)]]
    out["idle_gaps"] = [[label, t / n] for label, t in _label_gaps(
        gaps, host_events, own_spans, top)]
    return out


def _label_gaps(gaps, host_events, own_spans, top):
    """Idle seconds by label: the shortest host event that covers the
    gap's midpoint (the innermost thing the host was doing) and the
    benchmark's own span around it (``<no span>`` where none closed
    inside the trace: the profiler records a span when it ends, so a
    statement that outlasts the slice leaves none); a gap no host event
    covers is ``<host idle>``.  The 2,000 longest gaps are labelled one by one,
    the rest go under ``<short gaps>``."""
    if not gaps:
        return []
    gaps = sorted(gaps, key=lambda g: g[0] - g[1])
    labelled, rest = gaps[:2000], gaps[2000:]
    by_label = defaultdict(float)
    own = [ev for ev in host_events if ev[2] in own_spans]
    others = sorted(ev for ev in host_events if ev[2] not in own_spans)
    starts = [ev[0] for ev in others]
    for s, e in labelled:
        mid = (s + e) // 2
        outer = None
        for name in own_spans:              # innermost of ours first
            if any(hs <= mid <= he for hs, he, n in own if n == name):
                outer = name
                break
        hi = bisect.bisect_right(starts, mid)
        inner = None
        for hs, he, name in others[max(0, hi - 2000):hi]:
            if he >= mid and (inner is None or he - hs < inner[0]):
                inner = (he - hs, name)
        label = (short_name(inner[1]) if inner else "<host idle>") + \
            " in " + (outer or "<no span>")
        by_label[label] += (e - s) / 1e9
    if rest:
        by_label["<short gaps>"] += sum(e - s for s, e in rest) / 1e9
    return [[name, t] for name, t in sorted(
        by_label.items(), key=lambda kv: -kv[1])[:top]]
