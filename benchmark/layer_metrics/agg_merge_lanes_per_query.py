"""Operators layer: mean over the window's statements of the aggregation
operator spans' ``merge_lanes`` — lanes a statement sent through the
grouping a second time because an operator's groups did not fit one page:
the summed width of every merge of kept partials.  0 where each
aggregation kept one partial; None where the program's aggregations keep
no such counter."""

from benchmark.layer_metrics.resident_scan_pct import _total
from benchmark.span_facts import per_statement

_merge_lanes = _total("merge_lanes")


def _counted(spans):
    return sum("merge_lanes" in s["attrs"] for s in spans)


def read(run):
    values = per_statement(run, _merge_lanes, _merge_lanes)
    if not values or not any(per_statement(run, _counted, _counted)):
        return None
    return sum(values) / len(values)
