"""Process start to window open: JAX start-up, server start, warm-up
(compilation in a run that compiles)."""


def read(run):
    return run.setup_s
