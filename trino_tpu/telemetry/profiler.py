"""Compiled-program profiler: per-program trace/compile wall + XLA cost.

Reference analog: the reference engine's per-operator ``*CompilerStats``
and the JMX compiler MBeans — here applied to XLA programs.  ``jit_stats``
(PR 1) counts *that* a kernel traced; this registry records *what that
cost*: trace wall-time, compile wall-time, and the compiled program's
``cost_analysis()`` / ``memory_analysis()`` (flops, bytes accessed,
output/temp bytes), keyed by the same shape/cache keys the jit caches
use (``ProcessorCache``'s (types, IR) key for page processors, the
``_exchange_program`` lru key for collectives).

Mechanism: ``instrument(name, jitted)`` wraps a ``jax.jit`` product.
Disabled (the default), the wrapper forwards straight to the jitted
callable — one attribute check, no tracing-path work, nothing recorded
(the profiler is NEVER consulted inside traced code; qlint trace-purity
holds).  Enabled, the wrapper owns the program cache via the AOT API:
a registry miss pays ``.lower()`` (timed: trace wall) then
``.compile()`` (timed: compile wall), harvests the cost analyses, and
stores the compiled executable; hits call the stored executable
directly.  Exactly one compile per (name, key, signature) — repeat
shapes add ZERO registry entries, which is the assertable no-retrace
invariant at cost granularity.

Attribution: every profiled call folds its program's flops/bytes (and,
on a miss, compile wall) into THREAD-local accumulators; the Driver
snapshots deltas around operator calls exactly like the jit_stats
counters, so EXPLAIN ANALYZE VERBOSE renders per-operator
flops / bytes / compile-ms.

The wrapper keeps the raw jitted callable on ``.jit`` for AOT export
(``jax.export`` requires the jit product itself), and transparently
bypasses profiling when called with tracer arguments (a kernel invoked
inside another traced program must stage out inline, not execute).
"""

from __future__ import annotations

import inspect
import threading
import time
from typing import Dict, List, Optional, Tuple

__all__ = [
    "enabled", "enable", "profiling", "instrument", "snapshot",
    "compiled_programs", "totals", "thread_totals", "reset", "device_memory_stats",
    "ProfiledFunction",
]


class _State:
    """Module-global switch + registry. A single object so the hot-path
    check is one attribute load.  ``enabled`` is derived state:
    ``sticky`` (manual enable()) OR ``depth`` > 0 (active profiling()
    scopes, REFCOUNTED — a concurrent scope exiting must not clobber
    another scope still running on a different thread)."""

    __slots__ = ("enabled", "sticky", "depth", "lock", "entries",
                 "max_entries", "dropped")

    def __init__(self):
        self.enabled = False
        self.sticky = False
        self.depth = 0
        self.lock = threading.Lock()
        #: (name, key_extra, sig) -> _Entry
        self.entries: Dict[tuple, "_Entry"] = {}
        self.max_entries = 4096
        self.dropped = 0


_STATE = _State()
_tls = threading.local()


class _Entry:
    """One compiled program: its executable plus the recorded costs."""

    __slots__ = ("name", "key_repr", "compiled", "drop_pos", "drop_kw",
                 "compiles", "calls", "trace_ms", "compile_ms",
                 "execute_ms", "flops", "bytes_accessed", "output_bytes",
                 "temp_bytes", "argument_bytes", "code_bytes",
                 "fallbacks")

    def __init__(self, name: str, key_repr: str):
        self.name = name
        self.key_repr = key_repr
        self.compiled = None
        self.drop_pos: Tuple[int, ...] = ()
        self.drop_kw: Tuple[str, ...] = ()
        self.compiles = 0
        self.calls = 0
        self.trace_ms = 0.0
        self.compile_ms = 0.0
        self.execute_ms = 0.0
        self.flops = 0.0
        self.bytes_accessed = 0.0
        self.output_bytes = 0
        self.temp_bytes = 0
        self.argument_bytes = 0
        self.code_bytes = 0
        self.fallbacks = 0

    def to_dict(self) -> dict:
        return {
            "name": self.name, "key": self.key_repr,
            "compiles": self.compiles, "calls": self.calls,
            "trace_ms": round(self.trace_ms, 3),
            "compile_ms": round(self.compile_ms, 3),
            "execute_ms": round(self.execute_ms, 3),
            "flops": self.flops, "bytes_accessed": self.bytes_accessed,
            "output_bytes": self.output_bytes,
            "temp_bytes": self.temp_bytes,
            "argument_bytes": self.argument_bytes,
            "code_bytes": self.code_bytes,
            "fallbacks": self.fallbacks,
        }


# -- switch ----------------------------------------------------------------


def enabled() -> bool:
    return _STATE.enabled


def enable(on: bool = True):
    """Manual (sticky) switch: enable() keeps the profiler on until
    enable(False), independent of any profiling() scopes in flight."""
    with _STATE.lock:
        _STATE.sticky = bool(on)
        _STATE.enabled = _STATE.sticky or _STATE.depth > 0


class profiling:
    """Context manager enabling the profiler for a scope (EXPLAIN
    ANALYZE VERBOSE, the workers' ``profile`` RPC).  Scopes REFCOUNT:
    concurrent queries on different threads each hold a count, and the
    profiler only switches off when the last scope exits (a plain
    query's no-op scope can never clobber a profiled neighbor)."""

    def __init__(self, on: bool = True):
        self.on = bool(on)

    def __enter__(self):
        if self.on:
            with _STATE.lock:
                _STATE.depth += 1
                _STATE.enabled = True
        return self

    def __exit__(self, *exc):
        if self.on:
            with _STATE.lock:
                _STATE.depth = max(0, _STATE.depth - 1)
                _STATE.enabled = _STATE.sticky or _STATE.depth > 0
        return False


def reset():
    """Drop every registry entry and the thread accumulators (tests).
    Compiled executables held by entries are released; the underlying
    plain jit caches are untouched."""
    with _STATE.lock:
        _STATE.entries.clear()
        _STATE.dropped = 0
        _STATE.sticky = False
        _STATE.depth = 0
        _STATE.enabled = False
    for k in ("flops", "bytes", "compile_ms", "compiles"):
        setattr(_tls, k, 0.0)


# -- thread attribution ----------------------------------------------------


def thread_totals() -> Tuple[float, float, float, int]:
    """(flops, bytes_accessed, compile_ms, compiles) accumulated by
    profiled calls on THIS thread — the Driver snapshots deltas around
    operator calls to attribute program costs per operator (same
    mechanism as jit_stats.thread_total)."""
    return (getattr(_tls, "flops", 0.0), getattr(_tls, "bytes", 0.0),
            getattr(_tls, "compile_ms", 0.0),
            int(getattr(_tls, "compiles", 0)))


def _tls_add(flops: float, bytes_: float, compile_ms: float,
             compiles: int):
    _tls.flops = getattr(_tls, "flops", 0.0) + flops
    _tls.bytes = getattr(_tls, "bytes", 0.0) + bytes_
    _tls.compile_ms = getattr(_tls, "compile_ms", 0.0) + compile_ms
    _tls.compiles = int(getattr(_tls, "compiles", 0)) + compiles


# -- the wrapper -----------------------------------------------------------


def _abstract(leaf, value_scalars: bool):
    """Hashable cache-key token for one pytree leaf.  Arrays key by
    (shape, dtype) — the aval; python scalars are weak-typed 0-d inputs
    whose VALUE does not shape the program, so they key by type alone
    unless ``value_scalars`` (the no-signature structural path, where a
    positional static int could otherwise alias two programs)."""
    import numpy as np

    if hasattr(leaf, "shape") and hasattr(leaf, "dtype"):
        return ("a", tuple(leaf.shape), str(leaf.dtype))
    if isinstance(leaf, (bool, int, float, complex)) \
            and not value_scalars:
        return ("w", type(leaf).__name__)
    return ("h", leaf)  # raises TypeError when unhashable -> fallback


class ProfiledFunction:
    """Callable wrapper around one ``jax.jit`` product (see module
    docstring). ``key`` scopes the registry entries — per-instance jits
    (PageProcessor) and memoized builders (_exchange_program) pass
    their own cache key so same-shaped but different programs never
    alias."""

    __slots__ = ("name", "jit", "key_extra", "static_names", "_sig",
                 "_has_varargs")

    def __init__(self, name: str, jitted, key=None,
                 static_argnames: Tuple[str, ...] = ()):
        self.name = name
        self.jit = jitted
        self.key_extra = key
        self.static_names = tuple(static_argnames)
        try:
            self._sig = inspect.signature(jitted)
            self._has_varargs = any(
                p.kind is inspect.Parameter.VAR_POSITIONAL
                for p in self._sig.parameters.values())
        except (TypeError, ValueError):
            self._sig = None
            self._has_varargs = False

    # the disabled path must stay as close to a bare call as python
    # allows: one global attribute load, then straight through
    def __call__(self, *args, **kwargs):
        if not _STATE.enabled:
            return self.jit(*args, **kwargs)
        return self._profiled_call(args, kwargs)

    def lower(self, *args, **kwargs):
        """AOT passthrough (callers that lower explicitly)."""
        return self.jit.lower(*args, **kwargs)

    def clear_cache(self):
        """Passthrough to the jit product's cache clear, also dropping
        this wrapper's registry entries — tests that force a retrace
        must see the profiler recompile too."""
        with _STATE.lock:
            for k in [k for k in _STATE.entries
                      if k[0] == self.name and k[1] == self.key_extra]:
                del _STATE.entries[k]
        self.jit.clear_cache()

    # ------------------------------------------------------------------

    def _signature_key(self, args, kwargs):
        """(key, drop_pos, drop_kw) or None to fall back unprofiled.
        ``drop_*`` name the STATIC arguments, which the compiled
        executable must not receive again (they are baked into the
        program, not part of its input pytree)."""
        from jax.tree_util import tree_flatten

        if self._sig is not None and not self._has_varargs:
            try:
                bound = self._sig.bind(*args, **kwargs)
            except TypeError:
                return None
            statics = frozenset(self.static_names)
            parts: List[tuple] = []
            drop_pos: List[int] = []
            drop_kw: List[str] = []
            pos_names = list(self._sig.parameters)[:len(args)]
            for name, val in bound.arguments.items():
                if name in statics:
                    parts.append(("s", name, val))
                    if name in pos_names:
                        drop_pos.append(pos_names.index(name))
                    else:
                        drop_kw.append(name)
                else:
                    leaves, treedef = tree_flatten(val)
                    parts.append((name, treedef, tuple(
                        _abstract(x, value_scalars=False)
                        for x in leaves)))
            return tuple(parts), tuple(drop_pos), tuple(drop_kw)
        if self.static_names:
            return None  # statics but no signature: cannot drop safely
        leaves, treedef = tree_flatten((args, kwargs))
        return (("pos", treedef, tuple(
            _abstract(x, value_scalars=True) for x in leaves)),
            (), ())

    def _profiled_call(self, args, kwargs):
        import jax

        # a call with tracer arguments is INSIDE someone else's trace:
        # stage out inline, never execute/record here
        for leaf in jax.tree_util.tree_leaves((args, kwargs)):
            if isinstance(leaf, jax.core.Tracer):
                return self.jit(*args, **kwargs)
        try:
            keyed = self._signature_key(args, kwargs)
        except TypeError:
            keyed = None  # unhashable key component
        if keyed is None:
            return self.jit(*args, **kwargs)
        sig_key, drop_pos, drop_kw = keyed
        key = (self.name, self.key_extra, sig_key)
        st = _STATE
        with st.lock:
            entry = st.entries.get(key)
        if entry is None:
            entry = self._compile_entry(key, sig_key, drop_pos, drop_kw,
                                        args, kwargs)
            if entry is None:   # lower/compile failed: plain path
                return self.jit(*args, **kwargs)
        call_args = args if not drop_pos else tuple(
            a for i, a in enumerate(args) if i not in drop_pos)
        call_kwargs = kwargs if not drop_kw else {
            k: v for k, v in kwargs.items() if k not in drop_kw}
        t0 = time.perf_counter()
        try:
            out = entry.compiled(*call_args, **call_kwargs)
        except (TypeError, ValueError):
            # aval/pytree mismatch between our key and jax's notion:
            # record the fallback loudly and take the plain path
            with st.lock:
                entry.fallbacks += 1
            return self.jit(*args, **kwargs)
        dt = (time.perf_counter() - t0) * 1e3
        with st.lock:
            entry.calls += 1
            entry.execute_ms += dt
        _tls_add(entry.flops, entry.bytes_accessed, 0.0, 0)
        return out

    def _compile_entry(self, key, sig_key, drop_pos, drop_kw, args,
                       kwargs) -> Optional[_Entry]:
        """Registry miss: AOT lower (trace wall) + compile (compile
        wall) + cost harvest, exactly once per key. Compilation runs
        OUTSIDE the registry lock; a concurrent duplicate loses the
        store race and is discarded (its costs still count — both
        threads genuinely paid them)."""
        st = _STATE
        with st.lock:
            if len(st.entries) >= st.max_entries:
                st.dropped += 1
                return None
        entry = _Entry(self.name, _short_repr((self.key_extra, sig_key)))
        entry.drop_pos, entry.drop_kw = drop_pos, drop_kw
        try:
            t0 = time.perf_counter()
            lowered = self.jit.lower(*args, **kwargs)
            t1 = time.perf_counter()
            entry.compiled = lowered.compile()
            t2 = time.perf_counter()
        except Exception:  # qlint: ignore[taxonomy] profiler fallback is the designed aval-mismatch path; raising would fail the query for telemetry
            return None
        entry.compiles = 1
        entry.trace_ms = (t1 - t0) * 1e3
        entry.compile_ms = (t2 - t1) * 1e3
        _harvest_costs(entry)
        _tls_add(0.0, 0.0, entry.compile_ms, 1)
        with st.lock:
            cur = st.entries.get(key)
            if cur is not None:
                # lost the race: merge the duplicate's compile cost so
                # "compile seconds" stays an honest wall-time account
                cur.compiles += 1
                cur.trace_ms += entry.trace_ms
                cur.compile_ms += entry.compile_ms
                return cur
            st.entries[key] = entry
            return entry


def _short_repr(obj, limit: int = 160) -> str:
    r = repr(obj)
    return r if len(r) <= limit else r[:limit - 3] + "..."


def _harvest_costs(entry: _Entry):
    """cost_analysis()/memory_analysis() of a compiled executable into
    the entry; absent analyses (backend-dependent) leave zeros."""
    try:
        ca = entry.compiled.cost_analysis()
        if isinstance(ca, (list, tuple)):
            ca = ca[0] if ca else {}
        entry.flops = float(ca.get("flops", 0.0) or 0.0)
        entry.bytes_accessed = float(
            ca.get("bytes accessed", 0.0) or 0.0)
    except Exception:  # qlint: ignore[taxonomy] cost_analysis portability varies per backend; zeros are the contract
        pass
    try:
        ma = entry.compiled.memory_analysis()
        if ma is not None:
            entry.output_bytes = int(
                getattr(ma, "output_size_in_bytes", 0) or 0)
            entry.temp_bytes = int(
                getattr(ma, "temp_size_in_bytes", 0) or 0)
            entry.argument_bytes = int(
                getattr(ma, "argument_size_in_bytes", 0) or 0)
            entry.code_bytes = int(
                getattr(ma, "generated_code_size_in_bytes", 0) or 0)
    except Exception:  # qlint: ignore[taxonomy] memory_analysis portability varies per backend; zeros are the contract
        pass


def instrument(name: str, jitted, key=None,
               static_argnames: Tuple[str, ...] = ()
               ) -> ProfiledFunction:
    """Wrap one jit/pjit/shard_map/pallas product for the registry.
    ``name`` should match the kernel's ``jit_stats.bump`` name so the
    two surfaces join; ``key`` is the owning cache's key (processor IR
    key, exchange-program lru key) for per-instance programs."""
    return ProfiledFunction(name, jitted, key=key,
                            static_argnames=tuple(static_argnames))


# -- reporting -------------------------------------------------------------


def snapshot() -> List[dict]:
    """Every registry entry as a JSON-able dict, stable order (by name,
    then key) — the system.runtime.kernels rows."""
    with _STATE.lock:
        entries = list(_STATE.entries.values())
    return sorted((e.to_dict() for e in entries),
                  key=lambda d: (d["name"], d["key"]))


def compiled_programs() -> List[tuple]:
    """(name, compiled executable) of every registry entry — lets a
    caller inspect the programs themselves (``compiled.as_text()``)."""
    with _STATE.lock:
        return [(e.name, e.compiled) for e in _STATE.entries.values()]


def totals() -> dict:
    """Aggregate view: program count + summed compile/trace/cost."""
    out = {"programs": 0, "compiles": 0, "calls": 0, "trace_ms": 0.0,
           "compile_ms": 0.0, "execute_ms": 0.0, "flops": 0.0,
           "bytes_accessed": 0.0, "fallbacks": 0}
    with _STATE.lock:
        for e in _STATE.entries.values():
            out["programs"] += 1
            out["compiles"] += e.compiles
            out["calls"] += e.calls
            out["trace_ms"] += e.trace_ms
            out["compile_ms"] += e.compile_ms
            out["execute_ms"] += e.execute_ms
            out["flops"] += e.flops * max(e.calls, 1)
            out["bytes_accessed"] += e.bytes_accessed * max(e.calls, 1)
            out["fallbacks"] += e.fallbacks
    for k in ("trace_ms", "compile_ms", "execute_ms"):
        out[k] = round(out[k], 3)
    return out


def device_memory_stats() -> Optional[dict]:
    """Live/peak device memory summed over local devices, or None where
    the backend reports none (CPU).  Piggybacked on worker heartbeats
    beside the NodeMemoryPool snapshot (PR 4's transport pattern)."""
    try:
        import jax

        live = peak = limit = 0
        seen = False
        for d in jax.local_devices():
            ms = d.memory_stats()
            if not ms:
                continue
            seen = True
            live += int(ms.get("bytes_in_use", 0) or 0)
            peak += int(ms.get("peak_bytes_in_use", 0) or 0)
            limit += int(ms.get("bytes_limit", 0) or 0)
        if not seen:
            return None
        return {"live_bytes": live, "peak_bytes": peak,
                "limit_bytes": limit}
    except Exception:  # qlint: ignore[taxonomy] device memory_stats is best-effort per backend; None = not reported
        return None
