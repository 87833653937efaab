"""Kernels layer: of the lanes the window's join probes ran over, the
share that held a row (the join operator spans' ``input_rows`` against
``probe_lanes``, the probe pages' widths summed).  A page a selective
filter masked and nobody trimmed costs every join above it its whole
width: 100 is full pages, 5 is a probe doing twenty times the work of
its rows.  None where the program's join spans count no lanes, or the
window ran no join."""

from benchmark.span_facts import per_statement


def _probes(key):
    def total(spans):
        return sum(s["attrs"].get(key, 0) for s in spans
                   if "probe_lanes" in s["attrs"])
    return total


def read(run):
    sums = [per_statement(run, _probes(key), _probes(key))
            for key in ("input_rows", "probe_lanes")]
    if None in sums:
        return None
    rows, lanes = (sum(values) for values in sums)
    return 100.0 * rows / lanes if lanes else None
