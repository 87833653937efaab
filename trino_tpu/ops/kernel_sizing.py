"""Per-kernel-shape sizing history: remembered static capacities so
repeat shapes reuse compiled programs.

A batched burst's unified lane capacities (``exec/batched.py``) and the
hybrid join's partition fan-out (``ops/join.py``) are STATIC arguments
of jit'd programs.  A capacity derived freshly from each query's data
would drift run to run — padded_size buckets absorb most of it, but a
workload oscillating around a pow2 boundary would still alternate
between two compiled programs.  This history is the kernel-capacity
analog of ``parallel.device_exchange.ExchangeSizingHistory``: grow
IMMEDIATELY on a larger observation (an undersized capacity means a
fallback or a re-run; an oversized one only pads lanes), decay by EWMA
so a transient spike doesn't pin the capacity forever, and always emit
through ``padded_size`` so a stable workload re-lands on the identical
jit cache entry.
"""

from __future__ import annotations

import threading
from typing import Dict

from ..block import padded_size


class ShapeSizingHistory:
    """Process-wide remembered capacity per kernel shape key."""

    def __init__(self, alpha: float = 0.5):
        self.alpha = alpha
        self._lock = threading.Lock()
        self._ewma: Dict[tuple, float] = {}

    def suggest(self, key: tuple, need: int, minimum: int = 16) -> int:
        """The pow2-bucketed capacity for this shape: at least ``need``
        (exactness first), grown to the remembered level so a repeat
        shape whose need shrank a little keeps its compiled program.
        Records the observation."""
        with self._lock:
            prev = self._ewma.get(key)
            if prev is None or need >= prev:
                self._ewma[key] = float(need)
            else:
                self._ewma[key] = (self.alpha * need
                                   + (1 - self.alpha) * prev)
            remembered = int(round(self._ewma[key]))
        return padded_size(max(need, remembered, minimum))

    def reset(self) -> None:
        with self._lock:
            self._ewma.clear()


#: one history per process, like the jit caches it protects
KERNEL_SIZING = ShapeSizingHistory()
