"""Base-table rows the window's finished statements read, over the
window's length.  A statement reads the row counts of the tables its
template names (connector metadata; fixed per template, not an engine
counter).  All the work and all the time of the window."""


def read(run):
    rows = sum(run.rows_read[s.instance.template.name]
               for s in run.finished)
    return rows / run.window_s
