"""Compile layer: XLA programs JAX asked its backend for while the
window was open (``jax.monitoring``).  Should be 0."""


def read(run):
    return run.compiles_in_window
