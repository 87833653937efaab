"""What the span readers under ``layer_metrics/`` share: the window's
statement traces out of the program's own ring
(``trino_tpu.telemetry.tracing.RING``; spans are dicts stamped ``t0``/
``t1`` on ``time.perf_counter``, the clock of ``RunFacts``).

A program without the ring (the parent of the PR that brought it), a
ring that lost a statement of the window, or a run with tracing off all
read as None: the metric is then left out of the line.
"""

from statistics import median

PLANNING = ("parse", "plan", "access_check", "local_plan")


def window_statements(run):
    """``[(root, spans, batch_spans, batch_size)]`` for every statement
    whose ``statement`` root lies inside the window, or None.
    ``batch_spans`` is the tree of the batch the statement rode in
    (``batch.run``; empty for a statement served alone) and
    ``batch_size`` the number of members that share it."""
    try:
        from trino_tpu.telemetry import tracing
    except ImportError:
        return None
    ring = getattr(tracing, "RING", None)
    if ring is None:
        return None
    traces, lost = ring.since(run.window_open)
    if lost:
        return None
    statements, batches = [], {}
    for spans in traces:
        root = next((s for s in spans if s["parent_id"] is None), None)
        if root is None:
            continue
        if root["name"] == "batch.run":
            batches[root["span_id"]] = (spans, root)
        elif root["name"] == "statement" \
                and root["t0"] >= run.window_open \
                and root["t1"] <= run.window_close:
            statements.append((root, spans))
    out = []
    for root, spans in statements:
        batch_id = next((s["attrs"]["batch"] for s in spans
                         if s["attrs"].get("batch")), None)
        batch_spans, batch_root = batches.get(batch_id, ([], None))
        size = batch_root["attrs"].get("batch_size", 1) \
            if batch_root else 1
        out.append((root, spans, batch_spans, max(size, 1)))
    return out or None


def durations_ms(run, name):
    """The window's ``name`` spans (one per statement), in ms."""
    statements = window_statements(run)
    if statements is None:
        return None
    return [(s["t1"] - s["t0"]) * 1e3
            for _, spans, _, _ in statements
            for s in spans if s["name"] == name] or None


def median_ms(run, name):
    values = durations_ms(run, name)
    return median(values) if values else None


def per_statement(run, own, shared):
    """Per statement of the window: ``own(spans)`` over its tree plus
    its share (one over the batch size) of ``shared(batch_spans)``."""
    statements = window_statements(run)
    if statements is None:
        return None
    return [own(spans) + (shared(batch) / size if batch else 0.0)
            for _, spans, batch, size in statements]


def root_counter(key):
    """A reader of the statement root's counter ``key``."""
    def total(spans):
        return sum(s["attrs"].get(key, 0) for s in spans
                   if s["parent_id"] is None)
    return total
