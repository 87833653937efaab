"""Process-wide jit trace (cache-miss) counters.

The classic JAX perf bug is silent retracing: a jitted kernel whose
cache key varies page-to-page recompiles forever and the engine slides
to interpreter speed. These counters make "same-shape pages do not
retrace" an assertable invariant: every jitted hot-path function bumps
a named counter INSIDE its traced body, so the bump executes exactly
once per cache miss (trace) and never on a cache hit.

The driver snapshots ``total()`` around each operator call and
attributes the delta to that operator's stats, which flow into EXPLAIN
ANALYZE (reference analog: the per-operator
``*CompilerStats`` / planner bytecode-compilation counters that
Trino exposes through OperatorStats metrics).
"""

from __future__ import annotations

import threading
from typing import Dict

_lock = threading.Lock()
_counts: Dict[str, int] = {}
_tls = threading.local()


def bump(name: str) -> None:
    """Record one trace of the named kernel. Call from INSIDE the
    jitted function body — the Python body only runs at trace time."""
    with _lock:
        _counts[name] = _counts.get(name, 0) + 1
    _tls.total = getattr(_tls, "total", 0) + 1


def counts() -> Dict[str, int]:
    with _lock:
        return dict(_counts)


def total() -> int:
    with _lock:
        return sum(_counts.values())


def total_for(*names: str) -> int:
    """Sum of the named counters (0 for never-traced kernels) — lets
    tests assert "this specific kernel did not retrace" without being
    perturbed by unrelated kernels tracing concurrently."""
    with _lock:
        return sum(_counts.get(n, 0) for n in names)


def thread_total() -> int:
    """Traces recorded on THIS thread. Tracing runs synchronously on
    the thread that called the jitted function, so snapshot deltas of
    this value attribute traces to the enclosing operator call exactly,
    even with concurrent task drivers (a global snapshot would charge
    thread A with thread B's traces)."""
    return getattr(_tls, "total", 0)


def reset() -> None:
    """Zero the counters (tests). Does NOT clear any jit cache: a
    kernel already compiled stays warm and will not re-bump."""
    with _lock:
        _counts.clear()
