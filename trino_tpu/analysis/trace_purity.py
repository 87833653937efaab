"""trace-purity pass: no host side-effects reachable inside traced code.

Entry points are functions that jax stages out: ``@jax.jit`` /
``@partial(jax.jit, ...)`` / ``@partial(shard_map, ...)`` decorated
defs, and local functions passed into ``jax.jit(f)`` /
``shard_map(f, ...)`` / ``pl.pallas_call(kernel, ...)`` call forms
(the builder idiom of ``device_exchange._exchange_program``). From
every entry the pass walks resolved
call-graph edges and flags host effects at any reachable function:
span/metrics calls, lock acquisition, ``time.*``, file/socket/
subprocess IO, ``print``, host-RNG, and subscript stores into traced
parameters. The Python body of a jitted function runs only at trace
time, so any such effect silently fires once per compile instead of
once per call — or worse, holds a lock for the duration of a trace
(PR 6's "spans never open inside jit'd code" claim, now checked).

``jit_stats.bump`` is allowlisted: a trace-time counter is the
documented mechanism that makes "repeat shapes do not retrace"
assertable (one bump per cache miss, by design — see jit_stats.py).
"""

from __future__ import annotations

import ast
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Set, Tuple

from .core import (CallSite, Finding, FunctionInfo, ModuleInfo,
                   ProjectIndex, dotted_chain)

PASS_ID = "trace-purity"

#: decorator / call chains that stage a Python function out to XLA
_JIT_CHAINS = {"jax.jit", "jit", "jax.pmap", "pmap"}
#: batching transforms that WRAP the staged function — the traced body
#: is their first argument (``jax.jit(jax.vmap(f, ...))`` stages f)
_VMAP_CHAINS = {"jax.vmap", "vmap"}
_SHARD_CHAINS = {"shard_map", "jax.experimental.shard_map.shard_map"}
_PALLAS_SUFFIX = "pallas_call"
_PARTIAL_CHAINS = {"partial", "functools.partial"}

#: trace-time effects that are the designed mechanism, not a bug
_ALLOWED_CALLS = {"jit_stats.bump"}


@dataclass
class EntryInfo:
    """One staged-out function and how it was staged."""
    func: FunctionInfo
    kind: str                      # jit | shard_map | pallas
    static_params: Set[str] = field(default_factory=set)


def _static_names(call: ast.Call) -> Set[str]:
    for kw in call.keywords:
        if kw.arg in ("static_argnames", "static_argnums"):
            if isinstance(kw.value, (ast.Tuple, ast.List)):
                return {e.value for e in kw.value.elts
                        if isinstance(e, ast.Constant)
                        and isinstance(e.value, str)}
            if isinstance(kw.value, ast.Constant) \
                    and isinstance(kw.value.value, str):
                return {kw.value.value}
    return set()


def _stage_kind(chain: Optional[str]) -> Optional[str]:
    if chain is None:
        return None
    if chain in _JIT_CHAINS:
        return "jit"
    if chain in _SHARD_CHAINS or chain.split(".")[-1] == "shard_map":
        return "shard_map"
    if chain.split(".")[-1] == _PALLAS_SUFFIX:
        return "pallas"
    return None


def profiled_entries(index: ProjectIndex) -> Dict[str, List[str]]:
    """Kernel names registered with the compiled-program profiler
    (``telemetry.profiler.instrument("name", ...)`` call forms), keyed
    by name with the registering module(s) as values — the not-blind
    witness that the cost registry actually covers the engine's jit
    entry points (a renamed wrapper or dropped instrument() call would
    silently blind EXPLAIN ANALYZE VERBOSE and
    ``system.runtime.kernels``)."""
    out: Dict[str, List[str]] = {}
    # registration FACADES (round 17): a function whose body forwards
    # its own parameter as instrument()'s name — e.g. exec/batched.py
    # ``_batched_kernel(name, cfg, build_lane)`` wrapping every masked
    # agg/join kernel in ``instrument(name, jit(vmap(...)))``.  Calls
    # to such a facade with a CONSTANT name register that name: one-hop
    # dataflow, so the floor test still pins the literal kernel names
    # instead of going blind behind the helper.
    facades: Dict[str, int] = {}
    for mod_name in sorted(index.modules):
        mod = index.modules[mod_name]
        for node in ast.walk(mod.tree):
            if not isinstance(node, ast.FunctionDef):
                continue
            params = [a.arg for a in node.args.args]
            for call in ast.walk(node):
                if not isinstance(call, ast.Call):
                    continue
                chain = dotted_chain(call.func)
                if chain is None \
                        or chain.split(".")[-1] != "instrument":
                    continue
                if call.args and isinstance(call.args[0], ast.Name) \
                        and call.args[0].id in params:
                    facades[node.name] = params.index(call.args[0].id)
    for mod_name in sorted(index.modules):
        mod = index.modules[mod_name]
        # walk the whole module tree: most registrations are module-
        # level rebinds (`kernel = instrument("name", kernel, ...)`),
        # which live outside any FunctionInfo
        for node in ast.walk(mod.tree):
            if not isinstance(node, ast.Call):
                continue
            chain = dotted_chain(node.func)
            if chain is None:
                continue
            leaf = chain.split(".")[-1]
            pos = 0 if leaf == "instrument" else facades.get(leaf)
            if pos is None:
                continue
            if len(node.args) > pos \
                    and isinstance(node.args[pos], ast.Constant) \
                    and isinstance(node.args[pos].value, str):
                out.setdefault(node.args[pos].value,
                               []).append(mod_name)
    return out


def recording_sites(index: ProjectIndex) -> Dict[str, List[str]]:
    """Call sites of the history-based-statistics write path
    (``record_query`` / ``record_actuals`` on the runtime stats store),
    keyed by called chain with the calling function ids as values —
    the not-blind witness that actuals recording exists in the index
    AND (asserted in tests) stays outside every jit-reachable function:
    a store write that migrated inside traced code would fire once per
    compile instead of once per query, silently freezing history."""
    out: Dict[str, List[str]] = {}
    for mod_name in sorted(index.modules):
        mod = index.modules[mod_name]
        for qual in sorted(mod.functions):
            info = mod.functions[qual]
            for call in info.calls:
                last = call.chain.split(".")[-1]
                if last in ("record_query", "record_actuals"):
                    out.setdefault(call.chain, []).append(info.id)
    return out


def jit_reachable(index: ProjectIndex) -> Set[str]:
    """Every function id reachable from a staged-out entry point over
    resolved call edges — the set the trace-purity findings walk, and
    the set the stats-store write path must stay OUT of."""
    entries = jit_entries(index)
    reached: Set[str] = set()
    for fid in sorted(entries):
        stack = [fid]
        while stack:
            cur = stack.pop()
            if cur in reached:
                continue
            reached.add(cur)
            func = index.functions.get(cur)
            if func is None:
                continue
            for call in func.calls:
                if call.chain in _ALLOWED_CALLS:
                    continue
                if call.target and call.target in index.functions:
                    stack.append(call.target)
    return reached


def jit_entries(index: ProjectIndex) -> Dict[str, EntryInfo]:
    """Every staged-out function in the project, keyed by function id.
    Shared with the recompile pass (traced-branch detection needs the
    same entry set plus each entry's static parameter names)."""
    entries: Dict[str, EntryInfo] = {}

    def add(func: Optional[FunctionInfo], kind: str,
            statics: Set[str]):
        if func is not None and func.id not in entries:
            entries[func.id] = EntryInfo(func, kind, statics)

    for mod_name in sorted(index.modules):
        mod = index.modules[mod_name]
        for qual in sorted(mod.functions):
            info = mod.functions[qual]
            # decorator forms
            for dec in info.decorators:
                chain = index.decorator_chain(dec)
                kind = _stage_kind(chain)
                statics: Set[str] = set()
                if kind is None and isinstance(dec, ast.Call) \
                        and chain in _PARTIAL_CHAINS and dec.args:
                    kind = _stage_kind(dotted_chain(dec.args[0]))
                if kind is not None and isinstance(dec, ast.Call):
                    statics = _static_names(dec)
                if kind is not None:
                    add(info, kind, statics)
            # call forms: jax.jit(f) / shard_map(f, ...) /
            # pl.pallas_call(kernel, ...) / jax.jit(jax.vmap(f, ...))
            for call in info.calls:
                kind = _stage_kind(call.chain)
                if kind is None or not call.node.args:
                    continue
                staged = call.node.args[0]
                # unwrap batching transforms: the vmapped callable IS
                # the traced body (its Python code runs at trace time)
                while isinstance(staged, ast.Call) and staged.args \
                        and dotted_chain(staged.func) is not None \
                        and dotted_chain(staged.func).split(".")[-1] \
                        in {c.split(".")[-1] for c in _VMAP_CHAINS}:
                    staged = staged.args[0]
                arg_chain = dotted_chain(staged)
                if arg_chain is None:
                    continue
                target = index.resolve(mod, info, arg_chain)
                if target in index.functions:
                    add(index.functions[target], kind,
                        _static_names(call.node))
    return entries


# -- impurity tables -----------------------------------------------------

_IO_EXACT = {"open", "input"}
_IO_PREFIXES = ("os.", "socket.", "subprocess.", "shutil.", "io.")
_TELEMETRY_LASTS = {"span", "counter", "gauge", "histogram",
                    "gauge_fn", "observe"}


def _classify_call(chain: str) -> Optional[Tuple[str, str]]:
    """(rule, description) when the called chain is a host effect."""
    if chain in _ALLOWED_CALLS:
        return None
    parts = chain.split(".")
    last = parts[-1]
    if chain == "print":
        return "host-io", "print() runs once per trace, not per call"
    if parts[0] == "time":
        return "host-time", "time.* reads the host clock at trace time"
    if chain in _IO_EXACT or chain.startswith(_IO_PREFIXES):
        return "host-io", "file/socket/process IO inside traced code"
    if last == "acquire" or (len(parts) > 1
                             and "lock" in parts[-2].lower()):
        return "lock-in-trace", ("lock acquisition inside traced code "
                                 "(held for the whole trace, or never "
                                 "per-call)")
    if last in _TELEMETRY_LASTS and (
            "tracer" in parts or "metrics" in parts
            or parts[0] in ("tracer", "metrics")):
        return "telemetry-in-trace", ("span/metric call inside traced "
                                      "code fires per compile, not per "
                                      "query")
    if parts[0] in ("random",) or chain.startswith("np.random."):
        return "host-rng", "host RNG draws once at trace time"
    return None


def _with_lockish(stmt: ast.With) -> Optional[str]:
    for item in stmt.items:
        chain = dotted_chain(item.context_expr)
        if chain and "lock" in chain.split(".")[-1].lower():
            return chain
    return None


def _param_store_targets(func: FunctionInfo) -> List[ast.AST]:
    """Subscript stores into the function's own parameters —
    ``arr[i] = x`` on a traced array mutates a host buffer at trace
    time (jax arrays reject it; numpy ones silently bake one value
    in)."""
    params = set(func.params)
    hits: List[ast.AST] = []
    for node in ast.walk(func.node):
        targets = []
        if isinstance(node, ast.Assign):
            targets = node.targets
        elif isinstance(node, ast.AugAssign):
            targets = [node.target]
        for t in targets:
            if isinstance(t, ast.Subscript) \
                    and isinstance(t.value, ast.Name) \
                    and t.value.id in params:
                hits.append(t)
    return hits


def run(index: ProjectIndex) -> List[Finding]:
    entries = jit_entries(index)
    findings: List[Finding] = []
    # BFS per entry over resolved edges; remember one sample path root
    reached_via: Dict[str, str] = {}   # function id -> entry id
    order: List[str] = []
    for fid in sorted(entries):
        stack = [fid]
        while stack:
            cur = stack.pop()
            if cur in reached_via:
                continue
            reached_via[cur] = fid
            order.append(cur)
            func = index.functions.get(cur)
            if func is None:
                continue
            for call in func.calls:
                # an allowlisted call's own body is its business
                # (jit_stats.bump's counter lock is the mechanism)
                if call.chain in _ALLOWED_CALLS:
                    continue
                if call.target and call.target in index.functions:
                    stack.append(call.target)

    seen: Set[Tuple[str, str]] = set()
    for cur in order:
        func = index.functions.get(cur)
        if func is None:
            continue
        entry = entries[reached_via[cur]].func
        via = "" if cur == entry.id \
            else f" (reached from traced entry {entry.qualname})"
        for call in func.calls:
            hit = _classify_call(call.chain)
            if hit is None:
                continue
            rule, why = hit
            key = (cur, f"{rule}:{call.chain}")
            if key in seen:
                continue
            seen.add(key)
            findings.append(Finding(
                PASS_ID, rule, func.module, func.qualname, call.line,
                f"`{call.chain}()` inside traced code{via}: {why}",
                f"{call.chain}"))
        for node in ast.walk(func.node):
            if isinstance(node, ast.With):
                chain = _with_lockish(node)
                if chain is None:
                    continue
                key = (cur, f"lock-in-trace:{chain}")
                if key in seen:
                    continue
                seen.add(key)
                findings.append(Finding(
                    PASS_ID, "lock-in-trace", func.module,
                    func.qualname, node.lineno,
                    f"`with {chain}:` inside traced code{via}: the "
                    f"lock is held at trace time only",
                    f"with:{chain}"))
        if cur in entries:
            for t in _param_store_targets(func):
                name = t.value.id  # type: ignore[attr-defined]
                key = (cur, f"param-store:{name}")
                if key in seen:
                    continue
                seen.add(key)
                findings.append(Finding(
                    PASS_ID, "host-mutation", func.module,
                    func.qualname, t.lineno,
                    f"subscript store into traced parameter "
                    f"`{name}` mutates a host buffer at trace time",
                    f"store:{name}"))
    return findings
