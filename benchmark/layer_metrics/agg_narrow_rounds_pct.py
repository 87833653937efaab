"""Kernels layer: of the linear-probe rounds ``hash_group_ids`` ran for the
window's aggregations, the share run over a narrow buffer of the rows
still unresolved and not over the whole page (the aggregation operator
spans' ``probe_rounds_narrow`` against ``probe_rounds``).  0 where every
page resolved in its first round (a few groups a page); the higher, the
more of a long collision chain was paid by the few rows on it.  None
where the program's aggregations keep no such counter, or the window's
pages ran no probe round (keyless aggregates, partial steps)."""

from benchmark.layer_metrics.resident_scan_pct import _total
from benchmark.span_facts import per_statement


def read(run):
    sums = [per_statement(run, _total(key), _total(key))
            for key in ("probe_rounds_narrow", "probe_rounds")]
    if None in sums:
        return None
    narrow, rounds = (sum(values) for values in sums)
    return 100.0 * narrow / rounds if rounds else None
