"""Exchange layer: mean over the window's statements of the summed
lengths of their ``exchange`` spans (one a device collective, opened by
the consumer task that triggered it).  An exchange is a barrier: while
it runs on that one thread every other consumer of it waits, so the sum
is wall seconds of the statement and not four tasks' parallel seconds.
None where the program opens no such span (the parent of the PR that
brought it) or the window ran no device exchange."""

from benchmark.span_facts import window_statements


def window_exchanges(run):
    """``(exchange spans of the window's statements, statements)``, or
    None where the window has no such span."""
    statements = window_statements(run)
    if statements is None:
        return None
    spans = [s for _, tree, _, _ in statements for s in tree
             if s["name"] == "exchange"]
    return (spans, len(statements)) if spans else None


def seconds(span):
    return span["t1"] - span["t0"]


def read(run):
    found = window_exchanges(run)
    if found is None:
        return None
    spans, statements = found
    return sum(map(seconds, spans)) / statements
