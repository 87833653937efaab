"""Operators layer: mean over the window's statements of the root's
``host_sync_s`` — wall seconds inside the blocking device-to-host reads
that ``tracing.host_sync`` wraps (the host waiting for the device, plus
the copy), a batch's counted by the member's share."""

from benchmark.span_facts import per_statement, root_counter


def read(run):
    total = root_counter("host_sync_s")
    values = per_statement(run, total, total)
    return sum(values) / len(values) if values else None
