"""Plan layer, from outside: median round trip of ``EXPLAIN <instance>``
through the client after the window (parse, analyze, plan, optimize plus
one protocol round trip).  To be replaced by a span once the runner has one."""

from statistics import median


def read(run):
    return median(run.explain_ms) if run.explain_ms else None
