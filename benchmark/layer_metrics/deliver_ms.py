"""Protocol layer: median ``statement.deliver`` span — the runner call
returned to the poll that served the final page (the client's poll sleep
and paging)."""

from benchmark.span_facts import median_ms


def read(run):
    return median_ms(run, "statement.deliver")
