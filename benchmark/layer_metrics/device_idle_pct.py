"""Device: share of the traced slice in which no operation ran."""


def read(run):
    if not run.trace or run.trace["busy_s"] is None:
        return None
    span = run.trace_close - run.trace_open
    return 100.0 * (1.0 - run.trace["busy_s"] / span)
