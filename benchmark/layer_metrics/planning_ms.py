"""Plan layer: median over the window's statements of their ``parse`` +
``plan`` + ``access_check`` + ``local_plan`` spans, a batch's shared ones
counted by the member's share.  What the served path pays: plan-cache
and template hits, then local planning."""

from statistics import median

from benchmark.span_facts import PLANNING, per_statement


def _planning_ms(spans):
    return sum((s["t1"] - s["t0"]) * 1e3 for s in spans
               if s["name"] in PLANNING)


def read(run):
    values = per_statement(run, _planning_ms, _planning_ms)
    return median(values) if values else None
