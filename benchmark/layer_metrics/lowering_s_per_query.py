"""Kernels layer: mean over the window's statements of the root's
``lowering_s`` — seconds JAX spent tracing, lowering and compiling (or
fetching from the persistent cache) for the statement, summed over the
threads that did it.  A distributed statement's tasks lower on up to
four threads and Python's tracing holds the GIL, so this bounds the
statement's wall from above; a program's trace seconds also hold those
of the jitted functions traced inside it, which the listener hears
again under their own names.  None where the program keeps no such
counter."""

from benchmark.layer_metrics import lowerings_per_query


def read(run):
    return lowerings_per_query.read(run, key="lowering_s")
