"""Operators layer: of the bytes the window's scans took from pages
already on a device, the share that lay on the device of the task that
scanned them (the scan operator spans' ``local_bytes`` against
``transferred_bytes``).  100 where every split was read by the task on
the chip that holds its pages; anything less says pages crossed from
one chip to another inside a scan.  None where the program keeps no
such counter (both read nothing), or nothing resident was scanned."""

from benchmark.layer_metrics.resident_scan_pct import _total
from benchmark.span_facts import per_statement


def read(run):
    sums = [per_statement(run, _total(key), _total(key))
            for key in ("local_bytes", "transferred_bytes")]
    if None in sums:
        return None
    local, transferred = (sum(values) for values in sums)
    scanned = local + transferred
    return 100.0 * local / scanned if scanned else None
