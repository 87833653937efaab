"""Exchange layer: of the bytes the window's exchanges sent through the
bucket sort and the ``all_to_all`` (``bytes_moved``: every attempt's
``d x d x per_dest`` lanes of ``lane_bytes``), the share that were rows
(``rows`` x ``lane_bytes``) — the rest is the padding of lanes sized to
a power of two over the fullest (sender, receiver) pair.  None where
the program opens no ``exchange`` span or the window moved nothing."""

from benchmark.layer_metrics.exchange_s_per_query import window_exchanges


def read(run):
    found = window_exchanges(run)
    if found is None:
        return None
    attrs = [s["attrs"] for s in found[0]]
    useful = sum(a.get("rows", 0) * a.get("lane_bytes", 0) for a in attrs)
    moved = sum(a.get("bytes_moved", 0) for a in attrs)
    return 100.0 * useful / moved if moved else None
