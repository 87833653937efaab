"""Where JAX's persistent compilation cache lives.

The engine compiles many small XLA programs (one per page shape x
kernel), so every entry point that runs queries keeps them on disk.
The directory is placed from OUTSIDE through ``JAX_COMPILATION_CACHE_DIR``
(JAX reads that variable itself; nothing is set in code then).  Unset,
the cache is ``<checkout>/.jax_cache`` — a fixed path derived from this
package's own location, because the path is part of the cache key: a
directory that moves between runs never hits.
"""

from __future__ import annotations

import os

_ENV = "JAX_COMPILATION_CACHE_DIR"
_CHECKOUT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def compile_cache_dir() -> str:
    """The directory in use: the environment's, else the in-checkout one."""
    return os.environ.get(_ENV) or os.path.join(_CHECKOUT, ".jax_cache")


def enable_compile_cache(min_compile_secs: float = 0.1) -> str:
    """Turn the persistent cache on for this process and return its
    directory.  Sub-second compiles persist too: a query is dozens of
    small programs, and re-compiling them dominates a cold start."""
    import jax

    if not os.environ.get(_ENV):
        jax.config.update("jax_compilation_cache_dir", compile_cache_dir())
    jax.config.update("jax_persistent_cache_min_compile_time_secs",
                      min_compile_secs)
    return compile_cache_dir()
