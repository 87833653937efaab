"""Exchange layer: of the window's ``exchange`` span seconds, the share
outside ``run_s`` — the part of the barrier in which no exchange program
runs and every consumer waits on the host: the slabs' assembly
(``assemble_s``), the sizing (``size_s``), the result's read-back and
its statistics (``readback_s``) and the slicing into pages
(``slice_s``).  100 x sum(duration - run_s) / sum(duration).  None
where the program opens no ``exchange`` span or the window ran none."""

from benchmark.layer_metrics.exchange_s_per_query import (seconds,
                                                          window_exchanges)


def read(run):
    found = window_exchanges(run)
    if found is None:
        return None
    exchange_s = sum(map(seconds, found[0]))
    run_s = sum(s["attrs"].get("run_s", 0.0) for s in found[0])
    return 100.0 * (exchange_s - run_s) / exchange_s if exchange_s else None
