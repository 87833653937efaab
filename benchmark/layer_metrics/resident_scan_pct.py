"""Operators layer: of the bytes the window's scans handed to their
pipelines, the share that came from pages already on the device (the
scan operator spans' ``resident_bytes`` against ``uploaded_bytes``).
100 where every table scanned lives on the chip; anything less says a
scan fell back to host pages.  None where the program keeps no such
counter."""

from benchmark.span_facts import per_statement


def _total(key):
    def total(spans):
        return sum(s["attrs"].get(key, 0) for s in spans)
    return total


def read(run):
    sums = [per_statement(run, _total(key), _total(key))
            for key in ("resident_bytes", "uploaded_bytes")]
    if None in sums:
        return None
    resident, uploaded = (sum(values) for values in sums)
    handed = resident + uploaded
    return 100.0 * resident / handed if handed else None
