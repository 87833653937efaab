"""Exchange layer: of the rows the window's exchanges delivered
(``rows``), the share whose receiver was the device that sent them
(``rows_stayed``: the diagonal of the collective's sender x receiver
counts, which the read-back has on the host) - rows that went through
the bucket sort and the ``all_to_all`` to arrive where they were.  25
is a uniform hash over four devices; 100 is an exchange that moved
nothing: one on a key its input was already partitioned on, or one
whose senders are a single task.  None where the program's ``exchange``
spans carry no ``rows_stayed`` (the parent of the PR that brought it),
or the window delivered no row."""

from benchmark.layer_metrics.exchange_s_per_query import window_exchanges


def read(run):
    found = window_exchanges(run)
    if found is None:
        return None
    attrs = [s["attrs"] for s in found[0] if "rows_stayed" in s["attrs"]]
    rows = sum(a.get("rows", 0) for a in attrs)
    return 100.0 * sum(a["rows_stayed"] for a in attrs) / rows \
        if rows else None
