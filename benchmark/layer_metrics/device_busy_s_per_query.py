"""Kernels layer: seconds in which an operation ran on the device in the
traced slice (union of intervals), over the statements traced."""


def read(run):
    n = run.statements_traced()
    if not run.trace or run.trace["busy_s"] is None or n <= 0:
        return None
    return run.trace["busy_s"] / n
