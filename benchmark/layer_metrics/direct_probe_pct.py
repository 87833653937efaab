"""Kernels layer: of the probe pages the window's joins looked up, the
share answered from the build's direct-address table (the join operator
spans' ``direct_probe_pages`` against ``probe_pages``).  100 where every
build's keys spanned a range the table could hold; anything less says a
build fell back to the two binary searches over the sorted index (its
span's ``probe_fallback`` says why).  None where the program keeps no
such counter, or the window ran no join."""

from benchmark.layer_metrics.resident_scan_pct import _total
from benchmark.span_facts import per_statement


def read(run):
    sums = [per_statement(run, _total(key), _total(key))
            for key in ("direct_probe_pages", "probe_pages")]
    if None in sums:
        return None
    direct, probed = (sum(values) for values in sums)
    return 100.0 * direct / probed if probed else None
