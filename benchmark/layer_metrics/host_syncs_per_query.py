"""Operators layer: mean over the window's statements of the root's
``host_syncs`` — blocking device-to-host reads at the sites
``tracing.host_sync`` wraps, a batch's counted by the member's share."""

from benchmark.span_facts import per_statement, root_counter


def read(run):
    total = root_counter("host_syncs")
    values = per_statement(run, total, total)
    return sum(values) / len(values) if values else None
