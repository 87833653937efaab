"""Operators layer: mean over the window's statements of the candidate
matches its joins put through a residual predicate (the join operator
spans' ``residual_rows``: each probe page's match total where the join
carries a predicate beside its key, as q21's ``l2.l_suppkey <>
l1.l_suppkey`` on its semi and its anti join).  Each such match is
expanded to a lane, gathered from both sides, run through the compiled
predicate and OR-ed back onto its probe row, where a join on the key
alone only counts it.  0 where the window's residual joins found no
candidate; None where no join span carries the attribute (a window
without such a join, or the parent of the PR that brought it)."""

from benchmark.span_facts import per_statement


def _residual_rows(spans):
    return sum(s["attrs"].get("residual_rows", 0) for s in spans)


def _residual_joins(spans):
    return sum("residual_rows" in s["attrs"] for s in spans)


def read(run):
    values = per_statement(run, _residual_rows, _residual_rows)
    if not values or not any(per_statement(run, _residual_joins,
                                           _residual_joins)):
        return None
    return sum(values) / len(values)
