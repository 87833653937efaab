"""Kernels layer: of the page·filter applications in which the window's
scans tested a dynamic filter's value set, the share answered by the
filter's membership table — one gather over ``key - lo`` — instead of a
binary search over the sorted set (the scan operator spans'
``df_table_pages`` against ``df_member_pages``).  100 where every value
set's keys were integers in a range the table could cover; anything less
says a build fell back (float keys, a wide range, no memory, a spilled
build).  None where the program keeps no such counter, or the window
tested no value set."""

from benchmark.layer_metrics.resident_scan_pct import _total
from benchmark.span_facts import per_statement


def read(run):
    sums = [per_statement(run, _total(key), _total(key))
            for key in ("df_table_pages", "df_member_pages")]
    if None in sums:
        return None
    by_table, tested = (sum(values) for values in sums)
    return 100.0 * by_table / tested if tested else None
