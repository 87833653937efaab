"""Exchange layer: milliseconds a device spends inside collective
operations (the exchange's ``all-to-all`` and the reductions that size
it; ``XLA Ops`` line, union of intervals, mean over the device planes)
in the traced slice, over the statements traced."""


def read(run):
    n = run.statements_traced()
    if not run.trace or not run.trace["collective_s"] or n <= 0:
        return None
    return run.trace["collective_s"] * 1e3 / n
