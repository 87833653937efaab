"""95th percentile (nearest rank) of the same client-side seconds."""

from benchmark.stats import percentile


def read(run):
    return percentile([s.seconds for s in run.finished], 95)
