"""Protocol layer: median ``statement.queued`` span — submit to the
moment an executor thread of ``ProtocolServer`` takes the statement
(alone or as a member of an admission batch)."""

from benchmark.span_facts import median_ms


def read(run):
    return median_ms(run, "statement.queued")
