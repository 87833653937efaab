"""Exchange layer: mean over the window's statements of the rows their
``exchange`` spans delivered (``rows``: the live lanes counted in what
every collective handed its consumers).  What the exchange planner
decides: each stage boundary sends its whole input through the bucket
sort and the ``all_to_all``, so a plan with a boundary on a key its
input is already partitioned on, or with a partial aggregation that
does not reduce, moves more rows for the same answer.  A count of data:
the same to the digit in every run of a cell.  None where the program
opens no ``exchange`` span or the window ran none."""

from benchmark.layer_metrics.exchange_s_per_query import window_exchanges


def read(run):
    found = window_exchanges(run)
    if found is None:
        return None
    spans, statements = found
    return sum(s["attrs"].get("rows", 0) for s in spans) / statements
