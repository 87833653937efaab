"""Exchange layer: the share of a device's time inside collective
operations in which that device ran no other operation (mean over the
device planes of the traced slice).  100 where the collectives are
synchronous: whatever is under 100 is overlap already won."""


def read(run):
    if not run.trace or not run.trace["collective_s"]:
        return None
    return 100.0 * run.trace["collective_exposed_s"] \
        / run.trace["collective_s"]
