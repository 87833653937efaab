"""Plan layer: mean over the window's statements of the rows that
entered its join builds (the build operator spans' ``input_rows``; a
build span is one that says its ``key_mode``).  What the join order
decides: the side a join is built on is sorted, indexed and held for
the statement, so a plan that builds on a fact table reads in the
millions where its neighbour reads in the hundred thousands.  None
where the program's build spans say no key mode, or the window ran no
join."""

from benchmark.span_facts import per_statement


def _build_rows(spans):
    return sum(s["attrs"].get("input_rows", 0) for s in spans
               if "key_mode" in s["attrs"])


def _builds(spans):
    return sum("key_mode" in s["attrs"] for s in spans)


def read(run):
    values = per_statement(run, _build_rows, _build_rows)
    if not values or not any(per_statement(run, _builds, _builds)):
        return None
    return sum(values) / len(values)
