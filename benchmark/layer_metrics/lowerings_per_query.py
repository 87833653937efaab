"""Kernels layer: mean over the window's statements of the root's
``lowerings`` — programs JAX lowered to an MLIR module for the statement
and asked the backend for (answered by a compile or by the persistent
cache), counted by the program's ``jax.monitoring`` listener on
whichever thread lowered; a batch's counted by the member's share.
Should be 0: jit's in-memory cache answers a warm statement.  None
where the program keeps no such counter (the parent of the PR that
brought it)."""

from benchmark.span_facts import per_statement, root_counter


def counted():
    """Whether the program under test counts lowerings at all: a root
    that lowered nothing has no counter either."""
    try:
        from trino_tpu.telemetry import tracing
    except ImportError:
        return False
    return hasattr(tracing, "lowering_line")


def read(run, key="lowerings"):
    if not counted():
        return None
    total = root_counter(key)
    values = per_statement(run, total, total)
    return sum(values) / len(values) if values else None
