"""Kernels layer: of the lanes the window's joins expanded their probe
pages into, the share that held a match (the join operator spans'
``expand_rows``, each page's match total, against ``expand_lanes``, the
expansions' widths summed).  The expansion, its key verification and
every gather of the joined page run at that width, and so does whatever
the page is handed to: 100 is a page exactly as wide as its matches,
50–100 what padding to a power of two leaves, 20 a join that sized its
output from the probe page's width.  None where the program's join
spans count no such lanes (the parent of the PR that brought them), or
the window's joins expanded nothing."""

from benchmark.span_facts import per_statement


def _expansions(key):
    def total(spans):
        return sum(s["attrs"].get(key, 0) for s in spans
                   if "expand_lanes" in s["attrs"])
    return total


def read(run):
    sums = [per_statement(run, _expansions(key), _expansions(key))
            for key in ("expand_rows", "expand_lanes")]
    if None in sums:
        return None
    rows, lanes = (sum(values) for values in sums)
    return 100.0 * rows / lanes if lanes else None
