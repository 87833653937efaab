"""Kernels layer, memory-bound: the least time the chip needs to read the
traced statements' referenced base columns once at the HBM peak
(``roofline.py``, ``peaks.json``), over the device's busy time in the
traced slice.  Statements partly inside the slice count by their share."""

from benchmark import roofline


def read(run):
    if not run.trace or not run.trace["busy_s"] or run.peaks is None:
        return None
    nbytes = sum(run.input_bytes[s.instance.template.name]
                 * run.traced_share(s) for s in run.finished)
    least = roofline.least_read_seconds(nbytes, run.peaks)
    return 100.0 * least / run.trace["busy_s"]
