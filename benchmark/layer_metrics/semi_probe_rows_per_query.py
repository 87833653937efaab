"""Plan layer: mean over the window's statements of the rows that
entered the probes of semi and anti joins (the join operator spans'
``input_rows`` where ``join_type`` is ``semi`` or ``anti``).  What the
plan decides: a filtering semi join placed above the joins it could have
filtered probes their whole output.  0 where the window's joins were of
other types; None where the program's join spans say no type."""

from benchmark.span_facts import per_statement


def _semi_rows(spans):
    return sum(s["attrs"].get("input_rows", 0) for s in spans
               if s["attrs"].get("join_type") in ("semi", "anti"))


def _typed(spans):
    return sum("join_type" in s["attrs"] for s in spans)


def read(run):
    values = per_statement(run, _semi_rows, _semi_rows)
    if not values or not any(per_statement(run, _typed, _typed)):
        return None
    return sum(values) / len(values)
