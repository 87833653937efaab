"""Operators layer: XLA program executions on the device plane of the
traced slice, over the statements traced."""


def read(run):
    n = run.statements_traced()
    if not run.trace or run.trace["launches"] is None or n <= 0:
        return None
    return run.trace["launches"] / n
