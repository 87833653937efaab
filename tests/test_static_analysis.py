"""qlint analyzer tests: per-pass fixture snippets (one known-bad and
one known-good each) so the passes cannot silently go blind, plus the
tier-1 gate that runs every pass over ``trino_tpu/`` and fails on any
non-baselined finding.

The analysis package itself is pure stdlib ``ast``: it never imports
the code it analyses.
"""

import json
import os
import subprocess
import sys
import textwrap

import pytest

from trino_tpu.analysis import (PASSES, ProjectIndex, apply_baseline,
                                default_baseline_path, load_baseline,
                                run_passes)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PACKAGE = os.path.join(REPO, "trino_tpu")


def index_of(**sources):
    """Fixture index from {module_name: dedented source}."""
    return ProjectIndex.from_sources(
        {name: textwrap.dedent(src) for name, src in sources.items()})


def rules(findings):
    return {(f.pass_id, f.rule) for f in findings}


# -- trace-purity --------------------------------------------------------

def test_trace_purity_catches_span_inside_jit():
    idx = index_of(**{"pkg.kern": """
        import jax
        from functools import partial

        @partial(jax.jit, static_argnames=("n",))
        def kernel(x, n):
            with tracer.span("kernel"):
                return helper(x)

        def helper(x):
            print("tracing", x)
            return x
    """})
    found = run_passes(idx, ["trace-purity"])
    assert ("trace-purity", "telemetry-in-trace") in rules(found)
    # interprocedural: helper's print() reached through the call graph
    assert ("trace-purity", "host-io") in rules(found)
    assert any(f.qualname == "helper" for f in found)


def test_trace_purity_call_form_entry_and_lock():
    idx = index_of(**{"pkg.build": """
        import jax, time, threading

        _lock = threading.Lock()

        def build():
            def staged(x):
                with _lock:
                    t = time.time()
                return x
            return jax.jit(staged)
    """})
    found = run_passes(idx, ["trace-purity"])
    got = rules(found)
    assert ("trace-purity", "lock-in-trace") in got
    assert ("trace-purity", "host-time") in got


def test_trace_purity_clean_kernel_and_allowlisted_counter():
    idx = index_of(**{"pkg.ok": """
        import jax
        import jax.numpy as jnp
        from .. import jit_stats

        @jax.jit
        def kernel(x):
            jit_stats.bump("kernel")   # designed trace-time counter
            return jnp.sum(x * 2)

        def host_side():
            print("fine out here")
            import time
            time.sleep(0)
    """})
    assert run_passes(idx, ["trace-purity"]) == []


def test_trace_purity_sees_through_package_init_reexports():
    """A helper re-exported through a package __init__ must stay on
    the call graph: package-__init__ relative imports resolve against
    the package itself, not its parent."""
    idx = ProjectIndex.from_sources({
        "pkg.tel": textwrap.dedent("""
            import jax

            from .inner import span_helper

            @jax.jit
            def kernel(x):
                return span_helper(x)
        """),
        "pkg.tel.inner": textwrap.dedent("""
            def span_helper(x):
                print("host effect")
                return x
        """),
    }, packages=("pkg.tel",))
    found = run_passes(idx, ["trace-purity"])
    assert ("trace-purity", "host-io") in rules(found)


def test_bare_call_in_method_binds_module_level_not_sibling_method():
    """Python scoping: `helper()` inside C.m is the module-level
    helper, never the sibling method — a misresolution here fabricates
    false lock cycles / masks real host effects."""
    idx = index_of(**{"pkg.m": """
        import jax

        def helper(x):
            print("reached")
            return x

        class C:
            @jax.jit
            def m(self, x):
                return helper(x)

            def helper(self, x):
                return x
    """})
    found = run_passes(idx, ["trace-purity"])
    assert [f.qualname for f in found] == ["helper"]
    assert found[0].rule == "host-io"


def test_trace_purity_pragma_opt_out():
    idx = index_of(**{"pkg.cfg": """
        import jax, os

        @jax.jit
        def kernel(x):
            mode = os.environ.get("MODE", "")  # qlint: ignore[trace-purity] trace-static knob
            return x
    """})
    assert run_passes(idx, ["trace-purity"]) == []


# -- lock-order ----------------------------------------------------------

AB_BA = """
    import threading

    class Ledger:
        def __init__(self):
            self._lock = threading.Lock()

        def demote(self, pool: "Pool"):
            with self._lock:
                pool.reserve()

        def park(self):
            with self._lock:
                pass

    class Pool:
        def __init__(self):
            self._lock = threading.Lock()

        def reserve(self):
            with self._lock:
                pass

        def revoke(self, ledger: Ledger):
            with self._lock:
                ledger.park()
"""


def test_lock_order_catches_seeded_ab_ba_cycle():
    idx = index_of(**{"pkg.spill": AB_BA})
    found = run_passes(idx, ["lock-order"])
    cycles = [f for f in found if f.rule == "lock-cycle"]
    assert len(cycles) == 1
    assert "Ledger._lock" in cycles[0].message
    assert "Pool._lock" in cycles[0].message


def test_lock_order_consistent_order_is_clean():
    # same two locks, always acquired Ledger -> Pool: no cycle
    idx = index_of(**{"pkg.spill": """
        import threading

        class Ledger:
            def __init__(self):
                self._lock = threading.Lock()

            def demote(self, pool: "Pool"):
                with self._lock:
                    pool.reserve()

        class Pool:
            def __init__(self):
                self._lock = threading.Lock()

            def reserve(self):
                with self._lock:
                    pass
    """})
    assert run_passes(idx, ["lock-order"]) == []


def test_lock_order_nonblocking_acquire_breaks_no_cycle():
    # PR 5's demote_across pattern: the back-edge uses
    # acquire(blocking=False), which cannot deadlock
    idx = index_of(**{"pkg.spill": AB_BA.replace(
        "ledger.park()",
        "ledger._lock.acquire(blocking=False)")})
    found = run_passes(idx, ["lock-order"])
    assert [f for f in found if f.rule == "lock-cycle"] == []


def test_lock_order_self_deadlock_and_rlock_exemption():
    idx = index_of(**{"pkg.locks": """
        import threading

        class A:
            def __init__(self):
                self._lock = threading.Lock()

            def outer(self):
                with self._lock:
                    self.inner()

            def inner(self):
                with self._lock:
                    pass

        class B:
            def __init__(self):
                self._lock = threading.RLock()

            def outer(self):
                with self._lock:
                    with self._lock:
                        pass
    """})
    found = run_passes(idx, ["lock-order"])
    subs = {f.subject for f in found if f.rule == "self-deadlock"}
    assert "self:pkg.locks.A._lock" in subs          # Lock: deadlock
    assert not any("B._lock" in s for s in subs)     # RLock: reentrant


def test_lock_order_rpc_under_lock():
    idx = index_of(**{"pkg.srv": """
        import threading, subprocess

        _lock = threading.Lock()

        def ship(frames):
            with _lock:
                subprocess.run(["scp", "x"])
    """})
    found = run_passes(idx, ["lock-order"])
    assert ("lock-order", "lock-over-rpc") in rules(found)


# -- recompile -----------------------------------------------------------

def test_recompile_unhashable_arg():
    idx = index_of(**{"pkg.exch": """
        from functools import lru_cache

        @lru_cache(maxsize=8)
        def build_program(mesh, opts):
            return (mesh, opts)

        def run(mesh):
            return build_program(mesh, {"sizing": "exact"})
    """})
    found = run_passes(idx, ["recompile"])
    assert ("recompile", "unhashable-arg") in rules(found)


def test_recompile_traced_branch():
    idx = index_of(**{"pkg.kern": """
        import jax
        from functools import partial

        @partial(jax.jit, static_argnames=("exact",))
        def kernel(x, exact):
            if exact:            # static: fine
                pass
            if x > 0:            # traced: TracerBoolConversionError
                return x
            return -x
    """})
    found = run_passes(idx, ["recompile"])
    branches = [f for f in found if f.rule == "traced-branch"]
    assert len(branches) == 1
    assert "`x`" in branches[0].message


def test_recompile_static_accessors_are_clean():
    idx = index_of(**{"pkg.kern": """
        import jax

        @jax.jit
        def kernel(x, lut):
            if x.shape[0] > 128:   # shapes are static under jit
                pass
            if len(x.shape) == 2:
                pass
            if lut is None:        # pytree structure is static
                return x
            return x

        def run(mesh, key):
            return build(mesh, tuple(sorted(key)))
    """})
    assert run_passes(idx, ["recompile"]) == []


# -- session-props -------------------------------------------------------

SP_REG = """
    REGISTRY = {}

    def register(prop):
        REGISTRY[prop.name] = prop

    class SessionProperty:
        def __init__(self, name, type, default, description):
            self.name = name

    register(SessionProperty(
        "knob_used", "integer", 4, "read below"))
    register(SessionProperty(
        "knob_dead", "boolean", False, "never read"))
    register(SessionProperty(
        "knob_typo", "int", 0, "bad type vocab"))

    def value(session, name):
        return REGISTRY[name]

    def prop_value(props, name):
        return props.get(name)
"""


def test_session_props_dead_undeclared_and_bad_type():
    idx = index_of(**{
        "pkg.session_properties": SP_REG,
        "pkg.engine": """
            from . import session_properties as SP

            def plan(session):
                a = SP.value(session, "knob_used")
                b = SP.value(session, "knob_missing")
                return a, b
        """,
    })
    found = run_passes(idx, ["session-props"])
    by_rule = {}
    for f in found:
        by_rule.setdefault(f.rule, []).append(f)
    assert [f.subject for f in by_rule["dead-property"]] \
        == ["dead:knob_dead", "dead:knob_typo"]
    assert by_rule["undeclared-lookup"][0].subject \
        == "undeclared:knob_missing"
    assert by_rule["bad-type"][0].subject == "bad-type:knob_typo"


def test_session_props_every_declared_and_read_is_clean():
    idx = index_of(**{
        "pkg.session_properties": SP_REG.replace(
            '    register(SessionProperty(\n        "knob_dead"',
            '    _ = (lambda: None) or register(SessionProperty(\n'
            '        "knob_dead"').replace(
            '"knob_typo", "int"', '"knob_typo", "integer"'),
        "pkg.engine": """
            from . import session_properties as SP

            def plan(session):
                return (SP.value(session, "knob_used"),
                        SP.value(session, "knob_dead"),
                        SP.prop_value({}, "knob_typo"))
        """,
    })
    assert run_passes(idx, ["session-props"]) == []


# -- taxonomy ------------------------------------------------------------

def test_taxonomy_bare_raise_and_broad_swallow():
    idx = index_of(**{"pkg.parallel.worker": """
        def flush(resp):
            if not resp.get("ok"):
                raise RuntimeError("sink rejected")

        def loop():
            try:
                flush({})
            except Exception:
                pass
    """})
    found = run_passes(idx, ["taxonomy"])
    got = rules(found)
    assert ("taxonomy", "bare-raise") in got
    assert ("taxonomy", "broad-swallow") in got


def test_taxonomy_typed_raise_and_routed_handler_are_clean():
    idx = index_of(**{"pkg.parallel.worker": """
        from .fault import RemoteTaskError, serialize_failure

        def flush(resp):
            if not resp.get("ok"):
                raise RemoteTaskError("sink rejected", "INTERNAL")

        def loop(sock):
            try:
                flush({})
            except Exception as e:
                sock.send(serialize_failure(e))

        def reraise():
            try:
                flush({})
            except Exception:
                raise
    """})
    assert run_passes(idx, ["taxonomy"]) == []


def test_taxonomy_scoped_to_parallel_and_pragma():
    idx = index_of(**{
        # outside parallel/: not this pass's business
        "pkg.ops.sort": "def f():\n    raise RuntimeError('x')\n",
        # fault.py defines the vocabulary: exempt
        "pkg.parallel.fault": "def g():\n    raise RuntimeError('y')\n",
        "pkg.parallel.chaos": """
            def inject(task_id):
                raise RuntimeError(  # qlint: ignore[taxonomy] chaos-injected
                    f"injected failure for {task_id}")
        """,
    })
    assert run_passes(idx, ["taxonomy"]) == []


def test_taxonomy_covers_telemetry_and_cache():
    """Round 14 scope extension: telemetry/ and the serving cache are
    runtime paths too — an erased error type there silently disables a
    surface instead of reaching dispatch."""
    idx = index_of(**{
        "pkg.telemetry.metrics": """
            def scrape(fn):
                try:
                    return fn()
                except Exception:
                    return None
        """,
        "pkg.cache": """
            def lookup(key):
                raise RuntimeError("bad key")
        """,
        # fault.py-style exemption preserved
        "pkg.telemetry.fault": "def g():\n    raise RuntimeError('y')\n",
    })
    found = run_passes(idx, ["taxonomy"])
    assert ("taxonomy", "broad-swallow") in rules(found)
    assert ("taxonomy", "bare-raise") in rules(found)
    assert not any(f.module == "pkg.telemetry.fault" for f in found)


# -- blocked-protocol ----------------------------------------------------

def test_blocked_protocol_partial_channel_and_stale_token():
    idx = index_of(**{"pkg.chan": """
        class HalfChannel:
            def poll(self):
                return self._q.pop(0) if self._q else None

            def listen(self):
                return self._token

        class Source:
            def blocked_token(self):
                return self._chan.listen()   # no readiness re-check
    """})
    found = run_passes(idx, ["blocked-protocol"])
    got = rules(found)
    assert ("blocked-protocol", "channel-contract") in got
    assert ("blocked-protocol", "stale-token-park") in got
    contract = [f for f in found if f.rule == "channel-contract"]
    assert "at_end" in contract[0].message
    assert "has_page" in contract[0].message


def test_blocked_protocol_waker_under_lock():
    idx = index_of(**{"pkg.buf": """
        import threading

        class Buf:
            def __init__(self):
                self._lock = threading.Lock()
                self._listeners = []

            def enqueue(self, page):
                with self._lock:
                    self._pages.append(page)
                    for cb in self._listeners:
                        cb()      # fires under the state lock
    """})
    found = run_passes(idx, ["blocked-protocol"])
    assert ("blocked-protocol", "waker-under-lock") in rules(found)


def test_blocked_protocol_repo_idioms_are_clean():
    """The engine's own patterns pass: full quartet, snapshot-then-
    recheck blocked_token, collect-under-lock / fire-after-release."""
    idx = index_of(**{"pkg.ok": """
        import threading

        class Chan:
            def poll(self):
                return None

            def at_end(self):
                return True

            def has_page(self):
                return False

            def listen(self):
                return self._token

        class Source:
            def blocked_token(self):
                token = self._chan.listen()
                if self._chan.at_end() or self._chan.has_page():
                    return None
                return token

        class Buf:
            def __init__(self):
                self._lock = threading.Lock()
                self._listeners = []

            def _bump_locked(self):
                fired = list(self._listeners)
                self._listeners.clear()
                return fired

            def enqueue(self, page):
                with self._lock:
                    self._pages.append(page)
                    fired = self._bump_locked()
                for cb in fired:
                    cb()
    """})
    assert run_passes(idx, ["blocked-protocol"]) == []


# -- alias tracking (round 14 core) --------------------------------------

def test_alias_local_rebind_resolves_lock_identity():
    """`lk = self._lock; with lk:` used to scope the lock to the
    function (invisible); alias expansion recovers the class identity,
    so the self-routed re-acquire is a caught deadlock."""
    idx = index_of(**{"pkg.locks": """
        import threading

        class A:
            def __init__(self):
                self._lock = threading.Lock()

            def outer(self):
                lk = self._lock
                with lk:
                    self.inner()

            def inner(self):
                with self._lock:
                    pass
    """})
    found = run_passes(idx, ["lock-order"])
    assert any(f.rule == "self-deadlock"
               and "pkg.locks.A._lock" in f.subject for f in found)


def test_alias_rebound_name_never_unifies():
    """A name bound twice is NOT a must-alias: no finding may be
    fabricated from it."""
    idx = index_of(**{"pkg.locks": """
        import threading

        class A:
            def __init__(self):
                self._lock = threading.Lock()
                self._other = threading.RLock()

            def outer(self):
                lk = self._lock
                lk = self._other
                with lk:
                    self.inner()

            def inner(self):
                with self._lock:
                    pass
    """})
    assert run_passes(idx, ["lock-order"]) == []


def test_attr_types_resolve_cross_instance_calls():
    """`self.ledger.park()` resolves through the __init__-typed
    attribute — the old 3-part-chain dead end."""
    idx = index_of(**{"pkg.m": """
        import jax

        class Ledger:
            def park(self):
                print("host effect")

        class Ctx:
            def __init__(self, ledger: Ledger):
                self.ledger = ledger

            @jax.jit
            def kernel(self, x):
                self.ledger.park()
                return x
    """})
    found = run_passes(idx, ["trace-purity"])
    assert any(f.qualname == "Ledger.park" for f in found)


def test_attr_types_ambiguity_tombstones():
    """An attribute assigned two different types must resolve to
    NOTHING (no finding can be fabricated from a may-alias)."""
    idx = index_of(**{"pkg.m": """
        import jax

        class Ledger:
            def park(self):
                print("host effect")

        class Other:
            def park(self):
                return 1

        class Ctx:
            def __init__(self, ledger: Ledger, other: Other, flag):
                if flag:
                    self.dep = ledger
                else:
                    self.dep = other

            @jax.jit
            def kernel(self, x):
                self.dep.park()
                return x
    """})
    assert run_passes(idx, ["trace-purity"]) == []


def test_attr_types_untyped_rebind_tombstones():
    """An attribute rebound from an UNannotated name (or a lowercase
    factory call) is ambiguous — the earlier typed assignment must not
    survive, or a may-alias could fabricate findings."""
    idx = index_of(**{"pkg.m": """
        import jax

        class Ledger:
            def park(self):
                print("host effect")

        class Ctx:
            def __init__(self):
                self.dep = Ledger()

            def adopt(self, thing):
                self.dep = thing

            @jax.jit
            def kernel(self, x):
                self.dep.park()
                return x
    """})
    assert run_passes(idx, ["trace-purity"]) == []


def test_returned_attribute_accessor_names_the_lock():
    """`with ctx.lock():` where lock() returns self._lock acquires the
    target class's attribute — visible in the acquisition graph."""
    from trino_tpu.analysis.lock_order import build_lock_graph
    idx = index_of(**{"pkg.m": """
        import threading

        class Ctx:
            def __init__(self):
                self._lock = threading.Lock()

            def lock(self):
                return self._lock

        class Spiller:
            def __init__(self):
                self._lock = threading.Lock()

            def spill(self, ctx: Ctx):
                with self._lock:
                    with ctx.lock():
                        pass
    """})
    lg = build_lock_graph(idx)
    assert "pkg.m.Ctx._lock" in lg.graph.get("pkg.m.Spiller._lock", set())
    assert ("pkg.m.Spiller._lock", "pkg.m.Ctx._lock") \
        in lg.cross_instance_edges


# -- lock-order: cross-instance + parametric flow -------------------------

CROSS_AB_BA = """
    import threading

    class Ledger:
        def __init__(self):
            self._lock = threading.Lock()

        def demote(self, ctx: "Ctx"):
            with self._lock:
                spill_pages([], lock=ctx._lock)

        def park(self):
            with self._lock:
                pass

    def spill_pages(pages, lock=None):
        with lock:
            return pages

    class Ctx:
        def __init__(self, ledger: Ledger):
            self._lock = threading.Lock()
            self.ledger = ledger

        def finish(self):
            with self._lock:
                self.ledger.park()
"""


def test_lock_order_cross_instance_ab_ba_via_argument_flow():
    """The seeded cycle the OLD pass provably missed on both edges:
    the forward edge needs parametric lock flow (`lock=ctx._lock` into
    `with lock:` — the old pass scoped the param lock to
    spill_pages), the back edge needs typed-attribute resolution
    (`self.ledger.park()` — the old pass dropped 3-part chains)."""
    from trino_tpu.analysis.lock_order import build_lock_graph
    idx = index_of(**{"pkg.spill": CROSS_AB_BA})
    found = run_passes(idx, ["lock-order"])
    cycles = [f for f in found if f.rule == "lock-cycle"]
    assert len(cycles) == 1
    assert "Ledger._lock" in cycles[0].message
    assert "Ctx._lock" in cycles[0].message
    assert "cross-instance" in cycles[0].message
    lg = build_lock_graph(idx)
    assert ("pkg.spill.Ledger._lock", "pkg.spill.Ctx._lock") \
        in lg.cross_instance_edges


def test_lock_order_param_flow_nonblocking_stays_clean():
    """The same shape with a non-blocking try on the flowed lock (the
    demote_across idiom) must not cycle."""
    idx = index_of(**{"pkg.spill": CROSS_AB_BA.replace(
        "with lock:\n            return pages",
        "ok = lock.acquire(blocking=False)\n        return pages")})
    found = run_passes(idx, ["lock-order"])
    assert [f for f in found if f.rule == "lock-cycle"] == []


def test_lock_order_parametric_must_alias_self_deadlock():
    """Passing the HELD lock itself into a helper that blocking-
    acquires its parameter is a must-alias self-deadlock — provable
    only through argument flow."""
    idx = index_of(**{"pkg.m": """
        import threading

        class A:
            def __init__(self):
                self._lock = threading.Lock()

            def outer(self):
                with self._lock:
                    helper(self._lock)

        def helper(lock):
            lock.acquire()
    """})
    found = run_passes(idx, ["lock-order"])
    assert any(f.rule == "self-deadlock"
               and "flows through a call argument" in f.message
               for f in found)


def test_lock_order_direct_nested_two_instances_not_self_deadlock():
    """Hand-over-hand locking of TWO instances of one class directly
    nested in one body (`with self._lock: with other._lock:`) is
    ordered locking, not a self-cycle: structural id equality alone
    must not report — only identical source chains prove same-object."""
    idx = index_of(**{"pkg.m": """
        import threading

        class Pool:
            def __init__(self):
                self._lock = threading.Lock()

            def transfer(self, other: "Pool"):
                with self._lock:
                    with other._lock:
                        pass

            def reacquire(self):
                with self._lock:
                    with self._lock:
                        pass
    """})
    found = run_passes(idx, ["lock-order"])
    subs = [f for f in found if f.rule == "self-deadlock"]
    assert [f.qualname for f in subs] == ["Pool.reacquire"]


def test_lock_order_via_self_on_peer_lock_not_self_deadlock():
    """Holding a PEER instance's structurally-equal lock
    (`self.other._lock`) while self-calling a method that takes this
    instance's own lock is ordered locking — via_self alone must not
    report; both sides must be the instance's OWN attribute."""
    idx = index_of(**{"pkg.m": """
        import threading

        class Pool:
            def __init__(self, other: "Pool" = None):
                self._lock = threading.Lock()
                self.other = other

            def f(self):
                with self.other._lock:
                    self.park()

            def park(self):
                with self._lock:
                    pass
    """})
    found = run_passes(idx, ["lock-order"])
    assert [f for f in found if f.rule == "self-deadlock"] == []


def test_lock_order_alias_to_unlockish_name_still_acquires():
    """`lock = self._mu; with lock:` — the RAW name qualifies even
    when the canonical target's name doesn't look lockish; dropping it
    would lose lock-over-rpc/cycle detection the old pass had."""
    idx = index_of(**{"pkg.srv": """
        import threading, subprocess

        class S:
            def __init__(self):
                self._mu = threading.Lock()

            def ship(self):
                lock = self._mu
                with lock:
                    subprocess.run(["scp", "x"])
    """})
    found = run_passes(idx, ["lock-order"])
    assert ("lock-order", "lock-over-rpc") in rules(found)


def test_lock_order_param_flow_of_peer_lock_not_self_deadlock():
    """Handing a DIFFERENT instance's structurally-equal lock to a
    blocking helper while holding your own is a cross-instance
    hand-off: the must-alias claim requires the flowed argument's
    source chain to BE the held chain."""
    idx = index_of(**{"pkg.m": """
        import threading

        class A:
            def __init__(self):
                self._lock = threading.Lock()

            def transfer(self, other: "A"):
                with self._lock:
                    grab(other._lock)

        def grab(lock):
            lock.acquire()
    """})
    found = run_passes(idx, ["lock-order"])
    assert [f for f in found if f.rule == "self-deadlock"] == []


def test_lock_order_rebound_head_defeats_same_object_claim():
    """Two textually-identical chains whose head is REBOUND between
    the acquisitions (`ctx = self._next`) are not the same object —
    chain equality needs a non-rebindable head."""
    idx = index_of(**{"pkg.m": """
        import threading

        class Ctx:
            def __init__(self):
                self.lock = threading.Lock()
                self._next = None

        class W:
            def drain(self, ctx: Ctx):
                with ctx.lock:
                    ctx = ctx._next
                    with ctx.lock:
                        pass
    """})
    found = run_passes(idx, ["lock-order"])
    assert [f for f in found if f.rule == "self-deadlock"] == []


def test_lock_order_with_item_call_joins_the_graph():
    """A call made INSIDE a with-item expression (`with enter_chan():`)
    must reach the call graph — its transitive acquisitions close
    real AB-BA cycles."""
    idx = index_of(**{"pkg.m": """
        import threading

        LOCK_A = threading.Lock()
        LOCK_B = threading.Lock()

        def enter_chan():
            LOCK_B.acquire()
            return open("/dev/null")

        def forward():
            with LOCK_A:
                with enter_chan():
                    pass

        def backward():
            with LOCK_B:
                with LOCK_A:
                    pass
    """})
    found = run_passes(idx, ["lock-order"])
    assert any(f.rule == "lock-cycle" for f in found), \
        [f.render() for f in found]


def test_bind_args_respects_varargs_and_kwonly():
    """`helper(1, self._lock)` into `def helper(x, *args, lock=None)`
    puts the lock in *args at runtime — binding it to the kwonly
    `lock` would fabricate a must-alias self-deadlock."""
    idx = index_of(**{"pkg.m": """
        import threading

        class A:
            def __init__(self):
                self._lock = threading.Lock()

            def outer(self):
                with self._lock:
                    helper(1, self._lock)

        def helper(x, *args, lock=None):
            if lock is not None:
                lock.acquire()
    """})
    found = run_passes(idx, ["lock-order"])
    assert [f for f in found if f.rule == "self-deadlock"] == []


def test_lock_order_two_instances_same_class_not_conflated():
    """Structural identity must NOT turn two instances of one class
    into a self-cycle: a tree of Pools locking parent-then-child is
    fine."""
    idx = index_of(**{"pkg.m": """
        import threading

        class Pool:
            def __init__(self, parent: "Pool" = None):
                self._lock = threading.Lock()
                self.parent = parent

            def charge(self, other: "Pool"):
                with self._lock:
                    other.snapshot()

            def snapshot(self):
                with self._lock:
                    pass
    """})
    found = run_passes(idx, ["lock-order"])
    assert [f for f in found if f.rule == "self-deadlock"] == []


# -- cache-coherence -------------------------------------------------------

def test_cache_coherence_lru_session_read_min_collectives_class():
    """THE acceptance fixture: a session-property read inside an
    lru_cache'd builder whose key omits it fails the pass (the PR 5
    `min_collectives` bug class)."""
    idx = index_of(**{"pkg.exch": """
        from functools import lru_cache
        from .. import session_properties as SP

        @lru_cache(maxsize=8)
        def build_program(mesh, n):
            min_c = SP.prop_value({}, "rebalance_min_collectives")
            return (mesh, n, min_c)
    """})
    found = run_passes(idx, ["cache-coherence"])
    hits = [f for f in found if f.rule == "unkeyed-session-read"]
    assert len(hits) == 1
    assert "rebalance_min_collectives" in hits[0].message
    assert "build_program" in hits[0].message


def test_cache_coherence_memo_env_and_global_reads():
    idx = index_of(**{"pkg.progcache": """
        import os

        _MODE = "auto"

        def set_mode(m):
            global _MODE
            _MODE = m

        class Builder:
            def __init__(self):
                self._programs = {}

            def get(self, key):
                hit = self._programs.get(key)
                if hit is None:
                    flavor = os.environ.get("FLAVOR", "")
                    hit = self._programs[key] = (key, flavor, _MODE)
                return hit
    """})
    found = run_passes(idx, ["cache-coherence"])
    got = rules(found)
    assert ("cache-coherence", "unkeyed-env-read") in got
    assert ("cache-coherence", "unkeyed-global-read") in got
    env = [f for f in found if f.rule == "unkeyed-env-read"]
    assert "'FLAVOR'" in env[0].message


def test_cache_coherence_interprocedural_reach():
    """A helper the builder calls reads the property: flagged with the
    builder named (the read is reachable from memoized code)."""
    idx = index_of(**{"pkg.exch": """
        from functools import lru_cache
        from .. import session_properties as SP

        def pick_sizing():
            return SP.prop_value({}, "device_exchange_sizing")

        @lru_cache(maxsize=8)
        def build_program(mesh):
            return (mesh, pick_sizing())
    """})
    found = run_passes(idx, ["cache-coherence"])
    hits = [f for f in found if f.rule == "unkeyed-session-read"]
    assert len(hits) == 1
    assert "reached from cached builder build_program" in hits[0].message
    assert hits[0].qualname == "pick_sizing"


def test_cache_coherence_keyed_reads_are_clean():
    """Hoisting the read into the key (the canonical fix) and
    constant globals produce no findings; a caller reading props
    OUTSIDE the builder is the designed shape."""
    idx = index_of(**{"pkg.ok": """
        from functools import lru_cache
        from .. import session_properties as SP

        _CONST = 8

        @lru_cache(maxsize=8)
        def build_program(mesh, min_c):
            return (mesh, min_c, _CONST)

        def run(mesh, session):
            min_c = SP.value(session, "rebalance_min_collectives")
            return build_program(mesh, min_c)

        class Builder:
            def __init__(self):
                self._programs = {}

            def get(self, key, flavor):
                hit = self._programs.get((key, flavor))
                if hit is None:
                    hit = self._programs[(key, flavor)] = (key, flavor)
                return hit
    """})
    assert run_passes(idx, ["cache-coherence"]) == []


def test_cache_coherence_memo_read_in_key_is_coherent():
    """A memo builder whose env/session read flows INTO the memo key
    is coherent by construction — the read cannot leave get-or-build
    there, so the pass must recognize it in place (the lru fix of
    'hoist into the key' has no memo equivalent)."""
    idx = index_of(**{"pkg.ok": """
        import os

        class Builder:
            def __init__(self):
                self._programs = {}

            def get(self, key):
                flavor = os.environ.get("FLAVOR", "")
                k = (key, flavor)
                hit = self._programs.get(k)
                if hit is None:
                    hit = self._programs[k] = (key, flavor)
                return hit
    """})
    assert run_passes(idx, ["cache-coherence"]) == []


def test_cache_coherence_inline_key_read_and_aliased_container():
    """A read INLINE in the key expression, and a container reached
    through a local alias, are both keyed — coherent."""
    idx = index_of(**{"pkg.ok": """
        import os

        class B:
            def __init__(self):
                self._programs = {}

            def inline(self, key):
                hit = self._programs.get(
                    (key, os.environ.get("FLAVOR", "")))
                if hit is None:
                    self._programs[(key, "x")] = key
                return hit

        class C:
            def __init__(self):
                self._programs = {}

            def aliased(self, key):
                d = self._programs
                flavor = os.environ.get("FLAVOR", "")
                k = (key, flavor)
                hit = d.get(k)
                if hit is None:
                    hit = d[k] = (key, flavor)
                return hit
    """})
    assert run_passes(idx, ["cache-coherence"]) == []


def test_cache_coherence_global_container_not_its_own_input():
    """A lazily-initialized/resettable `global _CACHE` container is
    the cache itself, not an input missing from its own key."""
    idx = index_of(**{"pkg.m": """
        _CACHE = None

        def reset():
            global _CACHE
            _CACHE = None

        def get_prog(key):
            global _CACHE
            if _CACHE is None:
                _CACHE = {}
            v = _CACHE.get(key)
            if v is None:
                v = _CACHE[key] = (key,)
            return v
    """})
    assert run_passes(idx, ["cache-coherence"]) == []


def test_cache_coherence_rmw_accumulators_are_not_builders():
    """Refcounts/EWMAs (`d[k] = d.get(k, 0) + 1`) cache nothing: a
    session read beside one must not be flagged — tightening here is
    what keeps product code from contorting around the pass."""
    from trino_tpu.analysis.cache_coherence import cached_builders
    idx = index_of(**{"pkg.w": """
        from .. import session_properties as SP

        class W:
            def __init__(self):
                self._refs = {}

            def acquire(self, qid, session):
                self._refs[qid] = self._refs.get(qid, 0) + 1
                return SP.prop_value(session, "query_max_memory_bytes")
    """})
    assert run_passes(idx, ["cache-coherence"]) == []
    assert cached_builders(idx) == {}


def test_cache_coherence_pragma_opt_out():
    idx = index_of(**{"pkg.exch": """
        import os
        from functools import lru_cache

        @lru_cache(maxsize=8)
        def build(mesh):
            mode = os.environ.get("MODE", "")  # qlint: ignore[cache-coherence] trace-static
            return (mesh, mode)
    """})
    assert run_passes(idx, ["cache-coherence"]) == []


# -- resource-lifecycle ----------------------------------------------------

SPOOLY = """
    class SpoolCursor:
        def __init__(self, path):
            self.path = path

        def poll(self):
            return None

        def close(self):
            pass

        def __enter__(self):
            return self

        def __exit__(self, *exc):
            self.close()
"""


def test_resource_lifecycle_leak_and_conditional_close():
    idx = index_of(**{"pkg.spool": SPOOLY + """
    def leak(path):
        cur = SpoolCursor(path)
        return cur.poll()

    def racy(path):
        cur = SpoolCursor(path)
        page = cur.poll()
        cur.close()
        return page

    def dropped(path):
        SpoolCursor(path)
    """})
    found = run_passes(idx, ["resource-lifecycle"])
    by_rule = {}
    for f in found:
        by_rule.setdefault(f.rule, []).append(f)
    leaks = by_rule.get("leaked-closeable", [])
    assert any(f.qualname == "leak" for f in leaks)
    assert any(f.qualname == "dropped" for f in leaks)
    conds = by_rule.get("close-not-guaranteed", [])
    assert [f.qualname for f in conds] == ["racy"]


def test_resource_lifecycle_satisfied_shapes_are_clean():
    """with / finally / teardown-list registration / weakref.finalize /
    escape (return, self-store, container) all discharge the
    obligation — the engine's own idioms."""
    idx = index_of(**{"pkg.spool": SPOOLY + """
    import weakref

    def with_managed(path):
        with SpoolCursor(path) as cur:
            return cur.poll()

    def finally_closed(path):
        cur = SpoolCursor(path)
        try:
            return cur.poll()
        finally:
            cur.close()

    def registered(path, state):
        cur = SpoolCursor(path)
        state.channels.append(cur)

    def finalized(path):
        cur = SpoolCursor(path)
        weakref.finalize(cur, print, path)
        return cur

    def factory(path):
        return SpoolCursor(path)

    class Owner:
        def __init__(self, path):
            self._cur = SpoolCursor(path)

        def close(self):
            self._cur.close()
    """})
    assert run_passes(idx, ["resource-lifecycle"]) == []


def test_resource_lifecycle_factory_propagates():
    """A caller of a closeable FACTORY holds a closeable exactly as if
    it had called the constructor."""
    idx = index_of(**{"pkg.spool": SPOOLY + """
    def spool_channel(path):
        return SpoolCursor(path)

    def consumer(path):
        chan = spool_channel(path)
        chan.poll()
    """})
    found = run_passes(idx, ["resource-lifecycle"])
    assert any(f.rule == "leaked-closeable" and f.qualname == "consumer"
               for f in found)


def test_resource_lifecycle_open_builtin_and_pragma():
    idx = index_of(**{"pkg.io": """
    def bad(path):
        f = open(path)
        return f.read()

    def opted(path):
        f = open(path)  # qlint: ignore[resource-lifecycle] fd handed to C extension
        return f.read()

    def good(path):
        with open(path) as f:
            return f.read()
    """})
    found = run_passes(idx, ["resource-lifecycle"])
    assert [f.qualname for f in found] == ["bad"]


# -- pragma audit ----------------------------------------------------------

def test_pragma_audit_flags_bare_and_accepts_reasoned():
    idx = index_of(**{"pkg.m": """
        def f():
            x = 1  # qlint: ignore[taxonomy]
            y = 2  # qlint: ignore[trace-purity] deliberate trace-time read
            return x + y
    """})
    found = run_passes(idx, ["taxonomy"])
    bare = [f for f in found if f.pass_id == "pragma"]
    assert len(bare) == 1
    assert bare[0].rule == "missing-reason"
    assert "taxonomy" in bare[0].message


# -- framework plumbing --------------------------------------------------

def test_unknown_pass_rejected():
    idx = index_of(**{"pkg.m": "x = 1\n"})
    with pytest.raises(ValueError, match="unknown passes"):
        run_passes(idx, ["no-such-pass"])


def test_finding_keys_are_line_stable():
    src = """
        def flush(resp):
            raise RuntimeError("boom")
    """
    a = run_passes(index_of(**{"pkg.parallel.m": src}), ["taxonomy"])
    b = run_passes(index_of(**{"pkg.parallel.m": "\n\n\n" + textwrap.dedent(src)}),
                   ["taxonomy"])
    assert [f.key for f in a] == [f.key for f in b]
    assert a[0].line != b[0].line


def test_apply_baseline_splits_new_suppressed_stale():
    idx = index_of(**{"pkg.parallel.m": """
        def f():
            raise RuntimeError("a")

        def g():
            raise Exception("b")
    """})
    found = run_passes(idx, ["taxonomy"])
    assert len(found) == 2
    baseline = {found[0].key: "triaged", "gone:key": "stale"}
    new, suppressed, stale = apply_baseline(found, baseline)
    assert [f.key for f in new] == [found[1].key]
    assert [f.key for f in suppressed] == [found[0].key]
    assert stale == ["gone:key"]


# -- the tier-1 gate -----------------------------------------------------

@pytest.fixture(scope="module")
def repo_findings():
    index = ProjectIndex.from_package(PACKAGE)
    return index, run_passes(index)


def test_gate_repo_is_clean_modulo_baseline(repo_findings):
    """THE gate: every pass over trino_tpu/, zero non-baselined
    findings, no stale baseline entries (the baseline only shrinks)."""
    _index, findings = repo_findings
    baseline = load_baseline(default_baseline_path(PACKAGE))
    new, _suppressed, stale = apply_baseline(findings, baseline)
    assert not new, "non-baselined findings:\n" + "\n".join(
        f.render() for f in new)
    assert not stale, ("baseline entries that no longer fire "
                       "(remove them): " + ", ".join(stale))
    # the baseline may only shrink: at PR 7 every first-run finding
    # was fixed instead of baselined, so any growth is a regression
    assert len(baseline) <= 0, \
        "analysis_baseline.json grew — fix new findings instead"


def test_gate_passes_are_not_blind_on_the_real_repo(repo_findings):
    """The gate is only meaningful if the passes actually index the
    engine: staged-out entry points, locks, cached builders and the
    property registry must all be visible."""
    from trino_tpu.analysis.trace_purity import jit_entries
    from trino_tpu.analysis.recompile import _cached_functions
    from trino_tpu.analysis.session_props import (_declarations,
                                                  _registry_module)
    index, _ = repo_findings
    entries = jit_entries(index)
    assert len(entries) >= 15, sorted(entries)
    assert any(e.kind == "shard_map" for e in entries.values())
    assert "trino_tpu.parallel.device_exchange:_exchange_program.prog" \
        in entries
    # round 16: the vmapped batch entry (jax.jit(jax.vmap(_run, ...)))
    # must stay inside the trace-purity walk — the vmap unwrapping in
    # jit_entries is what keeps the batched path not-blind
    assert "trino_tpu.expr.compiler:PageProcessor._run" in entries
    # the join's direct-address table and probe and the per-key-range
    # adaptive kernels must be inside the trace-purity walk: all hot
    # jit'd code
    for entry in ("trino_tpu.ops.join:_build_direct_offsets",
                  "trino_tpu.ops.join:_probe_direct_counts",
                  "trino_tpu.ops.aggregation:_bucket_reduction_stats"):
        assert entry in entries, entry
    cached = _cached_functions(index)
    assert "trino_tpu.parallel.device_exchange:_exchange_program" \
        in cached
    declared = _declarations(_registry_module(index))
    assert len(declared) >= 30
    assert declared["retry_policy"][0] == "varchar"
    assert "page_rows" not in declared
    from trino_tpu.analysis.blocked_protocol import channel_classes
    chans = channel_classes(index)
    assert len(chans) >= 5, chans
    assert "trino_tpu.parallel.remote_exchange:RemoteExchangeChannel" \
        in chans
    assert "trino_tpu.parallel.spool:SpoolCursor" in chans
    # round 14: the cache-coherence pass must see the engine's caches
    # (lru program builders AND hand-rolled memo dicts) ...
    from trino_tpu.analysis.cache_coherence import cached_builders
    builders = cached_builders(index)
    assert len(builders) >= 10, sorted(builders)
    assert "trino_tpu.parallel.device_exchange:_exchange_program" \
        in builders
    assert builders[
        "trino_tpu.parallel.device_exchange:_exchange_program"].kind \
        == "lru"
    assert "trino_tpu.cache:ProcessorCache.get" in builders
    assert "trino_tpu.cache:QueryCache.parse" in builders
    assert "trino_tpu.exec.batched:_batched_kernel" in builders
    assert builders["trino_tpu.exec.batched:_batched_kernel"].kind \
        == "memo"
    # round 20: the HBO plan-exploration sites must stay visible.  The
    # optimizer's per-run region-estimate memo is a cached builder
    # (an unkeyed session/env read inside it would poison every
    # optimize() of the process) ...
    assert "trino_tpu.planner.memo:RuleContext.region_stats" in builders
    assert builders[
        "trino_tpu.planner.memo:RuleContext.region_stats"].kind == "memo"
    # ... and the broadcast-vs-partitioned DISTRIBUTION decision site
    # is indexed, including its history-flip counter call — a rename
    # would silently blind the cache-coherence walk to the decision
    vjoin = next(
        (f for f in index.iter_functions()
         if f.module == "trino_tpu.planner.exchanges"
         and f.qualname == "ExchangePlanner._v_JoinNode"), None)
    assert vjoin is not None
    assert any(c.chain.split(".")[-1] == "note_plan_flip"
               for c in vjoin.calls), \
        sorted(c.chain for c in vjoin.calls)
    # the plan-exploration session gates are declared with read sites
    # in the modules that enforce them
    assert declared["hbo_reorder_joins_enabled"][0] == "boolean"
    assert declared["hbo_distribution_enabled"][0] == "boolean"
    # ... the resource-lifecycle pass must see the closeables ...
    from trino_tpu.analysis.resource_lifecycle import (
        closeable_classes, closeable_factories)
    closeables = closeable_classes(index)
    assert len(closeables) >= 5, sorted(closeables)
    for cls in ("SpoolCursor", "_ChainedSpoolCursor",
                "RemoteExchangeChannel", "DiskSpiller",
                "QueryMemoryPool"):
        assert cls in closeables, cls
    factories = closeable_factories(index, closeables)
    assert "trino_tpu.parallel.spool:spool_channel" in factories
    assert "trino_tpu.parallel.spool:spool_task_cursor" in factories
    # ... and alias tracking must resolve CROSS-INSTANCE acquisition
    # edges on the real lock graph (the carried ROADMAP follow-on:
    # the old pass excluded these structurally)
    from trino_tpu.analysis.lock_order import build_lock_graph
    lg = build_lock_graph(index)
    assert lg.cross_instance_edges, "no cross-instance lock edges"
    assert ("trino_tpu.parallel.worker.WorkerServer._lock",
            "trino_tpu.exec.memory.NodeMemoryPool._lock") \
        in lg.cross_instance_edges, sorted(lg.cross_instance_edges)
    # the compiled-program profiler (round 11) must cover the jit
    # entry points: instrument() registrations are indexed by name so
    # a dropped wrapper can't silently blind EXPLAIN ANALYZE VERBOSE
    # or system.runtime.kernels
    from trino_tpu.analysis.trace_purity import profiled_entries
    profiled = profiled_entries(index)
    assert len(profiled) >= 15, sorted(profiled)
    for kernel in ("page_processor", "page_processor_batched",
                   "sort_by", "window_kernel",
                   "hash_group_ids", "hash_segment_reduce",
                   "sort_group_reduce", "join_build_sorted",
                   "join_probe_counts", "join_expand_matches",
                   "join_probe_direct", "join_direct_table",
                   "grouped_topn_kernel",
                   "device_exchange_program", "device_exchange_count",
                   "segment_reduce_pallas",
                   # round 17: masked agg/join lanes register through
                   # the _batched_kernel facade (jit(vmap(...)) wraps)
                   # — the facade-resolving walker must NOT go blind
                   "batched_agg_partial", "batched_agg_merge",
                   "batched_agg_finalize", "batched_join_probe",
                   "batched_join_expand", "batched_join_semi"):
        assert kernel in profiled, kernel
    assert all(m == "trino_tpu.exec.batched"
               for m in profiled["batched_agg_partial"])


def test_hbo_record_path_indexed_and_outside_jit(repo_findings):
    """History-based statistics (round 13): the stats-store write path
    must be VISIBLE to the index (not blind — a renamed record method
    would silently stop the check meaning anything) and every caller
    of it must be OUTSIDE the jit-reachable set: a store write that
    migrated inside traced code would fire once per compile instead of
    once per query, freezing history at trace-time values."""
    from trino_tpu.analysis.trace_purity import (jit_reachable,
                                                 recording_sites)
    index, _ = repo_findings
    sites = recording_sites(index)
    callers = {fid for fids in sites.values() for fid in fids}
    # the HboContext record facade calls record_query; the runners
    # call record/record_actuals — all must be indexed
    assert any("record_query" in chain for chain in sites), sites
    assert any("record_actuals" in chain for chain in sites), sites
    assert any(fid.startswith("trino_tpu.telemetry.stats_store:")
               for fid in callers), sorted(callers)
    reached = jit_reachable(index)
    inside = callers & reached
    assert not inside, (
        "stats-store write path reachable from jit-traced code: "
        + ", ".join(sorted(inside)))


# -- guarded-by ----------------------------------------------------------

def test_guarded_by_bare_write_from_timer_thread():
    """Known-bad: an attribute mutated under a lock on the main path
    but written bare from a Timer-thread callback."""
    idx = index_of(**{"pkg.srv": """
        import threading

        class Sweeper:
            def __init__(self):
                self._lock = threading.Lock()
                self.count = 0
                threading.Timer(5.0, self._tick).start()

            def _tick(self):
                self.count += 1      # bare write on the timer thread

            def bump(self):
                with self._lock:
                    self.count += 1

            def bump_again(self):
                with self._lock:
                    self.count += 1
    """})
    found = run_passes(idx, ["guarded-by"])
    assert ("guarded-by", "guarded-by") in rules(found)
    assert any(f.qualname == "Sweeper._tick" for f in found)
    # the message names the inferred guard and the guarded sites
    msg = next(f.message for f in found
               if f.qualname == "Sweeper._tick")
    assert "_lock" in msg and "timer" in msg


def test_guarded_by_interprocedural_lockset_is_clean():
    """A helper that mutates ONLY under callers that hold the lock
    inherits the lockset through the summary fixpoint — no finding."""
    idx = index_of(**{"pkg.srv": """
        import threading

        class Store:
            def __init__(self):
                self._lock = threading.Lock()
                self.total = 0
                threading.Thread(target=self._loop).start()

            def _loop(self):
                with self._lock:
                    self._merge(1)

            def record(self):
                with self._lock:
                    self._merge(2)

            def _merge(self, v):
                self.total += v      # guarded via every caller
    """})
    assert run_passes(idx, ["guarded-by"]) == []


def test_guarded_by_check_then_act_on_shared_dict():
    idx = index_of(**{"pkg.memo": """
        import threading

        class Memo:
            def __init__(self):
                self.memo = {}
                threading.Thread(target=self._sweep).start()

            def _sweep(self):
                for k in list(self.memo):
                    del self.memo[k]

            def get_or_build(self, k):
                if k not in self.memo:    # unlocked test-then-mutate
                    self.memo[k] = object()
                return self.memo[k]
    """})
    found = run_passes(idx, ["guarded-by"])
    assert ("guarded-by", "check-then-act") in rules(found)
    assert any(f.qualname == "Memo.get_or_build" for f in found)


def test_guarded_by_check_then_act_sees_tuple_unpack_store():
    """The body scan shares the site recorder's target predicate:
    a container store hidden inside a tuple unpack still counts."""
    idx = index_of(**{"pkg.memo": """
        import threading

        class Memo:
            def __init__(self):
                self.memo = {}
                self.other = 0
                threading.Thread(target=self._sweep).start()

            def _sweep(self):
                for k in list(self.memo):
                    del self.memo[k]

            def get_or_build(self, k):
                if k not in self.memo:
                    self.memo[k], self.other = (1, 2)
                return self.memo[k]
    """})
    found = run_passes(idx, ["guarded-by"])
    assert ("guarded-by", "check-then-act") in rules(found)


def test_guarded_by_locked_check_then_act_is_clean():
    idx = index_of(**{"pkg.memo": """
        import threading

        class Memo:
            def __init__(self):
                self.memo = {}
                self._lock = threading.Lock()
                threading.Thread(target=self._sweep).start()

            def _sweep(self):
                with self._lock:
                    self.memo.clear()

            def get_or_build(self, k):
                with self._lock:
                    if k not in self.memo:
                        self.memo[k] = object()
                    return self.memo[k]
    """})
    assert run_passes(idx, ["guarded-by"]) == []


def test_guarded_by_immutable_after_init_exempt():
    """Assigned solely in __init__ BEFORE the spawn: publication
    happens-before the thread — reads anywhere are clean. The same
    attribute assigned AFTER the spawn is the `init-race` rule: the
    spawned thread can run before the store lands."""
    clean = index_of(**{"pkg.a": """
        import threading

        class Ok:
            def __init__(self):
                self._lock = threading.Lock()
                self.config = {"a": 1}
                threading.Thread(target=self._loop).start()

            def _loop(self):
                return self.config.get("a")
    """})
    assert run_passes(clean, ["guarded-by"]) == []

    racy = index_of(**{"pkg.b": """
        import threading

        class Bad:
            def __init__(self):
                threading.Thread(target=self._loop).start()
                self.config = {"a": 1}     # spawned thread reads this

            def _loop(self):
                return self.config.get("a")
    """})
    found = run_passes(racy, ["guarded-by"])
    assert ("guarded-by", "init-race") in rules(found)
    assert any(f.qualname == "Bad.__init__" for f in found)
    # a post-spawn store the spawned thread never touches stays clean
    untouched = index_of(**{"pkg.c": """
        import threading

        class Meh:
            def __init__(self):
                threading.Thread(target=self._loop).start()
                self.unrelated = 3

            def _loop(self):
                return 1
    """})
    assert run_passes(untouched, ["guarded-by"]) == []


def test_guarded_by_single_entry_exempt():
    """Every site on ONE entry (the fetch loop owns its cursors):
    sequential within the thread — exempt even with a lock elsewhere
    in the class."""
    idx = index_of(**{"pkg.chan": """
        import threading

        class Channel:
            def __init__(self):
                self._lock = threading.Lock()
                self._queue = []
                self._cursor = 0
                threading.Thread(target=self._fetch).start()

            def _fetch(self):
                self._cursor += 1     # only this thread touches it
                with self._lock:
                    self._queue.append(self._cursor)

            def poll(self):
                with self._lock:
                    if self._queue:
                        return self._queue.pop()
    """})
    found = run_passes(idx, ["guarded-by"])
    assert not any("_cursor" in f.message for f in found), found


def test_guarded_by_pragma_opt_out():
    idx = index_of(**{"pkg.srv": """
        import threading

        class Gauge:
            def __init__(self):
                self._lock = threading.Lock()
                self.n = 0
                threading.Thread(target=self._loop).start()

            def _loop(self):
                self.n += 1  # qlint: ignore[guarded-by] monotonic gauge, torn reads acceptable

            def a(self):
                with self._lock:
                    self.n += 1

            def b(self):
                with self._lock:
                    self.n += 1
    """})
    assert run_passes(idx, ["guarded-by"]) == []


def test_guarded_by_condition_guards_like_a_lock():
    """`with self._cond:` (threading.Condition) is mutual exclusion —
    the construction site registers the identity past the lockish-name
    heuristic."""
    idx = index_of(**{"pkg.q": """
        import threading

        class Queue:
            def __init__(self):
                self._cond = threading.Condition()
                self.items = []
                threading.Thread(target=self._drain).start()

            def _drain(self):
                with self._cond:
                    if self.items:
                        self.items.pop()

            def offer(self, x):
                with self._cond:
                    self.items.append(x)
    """})
    assert run_passes(idx, ["guarded-by"]) == []


def test_guarded_by_sees_closure_self_in_nested_thread_target():
    """A nested def that captures the method's `self` (the per-task
    `run_one` shape) is attributed to the enclosing class — bare
    closure accesses cannot hide from the pass."""
    idx = index_of(**{"pkg.srv": """
        import threading

        class Pool:
            def __init__(self):
                self._lock = threading.Lock()
                self.slots = [1, 2]

            def swap(self, i):
                with self._lock:
                    self.slots[i] = 0

            def swap2(self, i):
                with self._lock:
                    self.slots[i] = 1

            def launch(self):
                def run_one(t):
                    return [s for s in self.slots if s]  # bare, closure
                threading.Thread(target=run_one, args=(0,)).start()
    """})
    found = run_passes(idx, ["guarded-by"])
    assert ("guarded-by", "guarded-by") in rules(found)
    assert any(f.qualname.endswith("run_one") for f in found), found


def test_thread_entry_kinds_taxonomy():
    """Every entry kind the index models: thread / timer / executor /
    rpc-handler / finalizer."""
    from trino_tpu.analysis.core import thread_entries
    idx = index_of(**{"pkg.m": """
        import threading
        import weakref
        from socketserver import BaseRequestHandler

        class H(BaseRequestHandler):
            def handle(self):
                pass

        class S:
            def __init__(self, pool):
                threading.Thread(target=self._loop).start()
                threading.Timer(1.0, self._tick).start()
                pool.submit(self._job)
                weakref.finalize(self, self._fin)

            def _loop(self): pass
            def _tick(self): pass
            def _job(self): pass
            def _fin(self): pass
    """})
    entries = thread_entries(idx)
    kinds = {e.func_id.split(":")[-1]: e.kind
             for e in entries.values()}
    assert kinds == {"S._loop": "thread", "S._tick": "timer",
                     "S._job": "executor", "S._fin": "finalizer",
                     "H.handle": "rpc-handler"}


def test_guarded_by_not_blind_on_the_real_repo(repo_findings):
    """The pass is only meaningful if it actually sees the engine's
    thread structure: the entry index, the guard inference and the
    named shared-state classes must all be populated."""
    from trino_tpu.analysis.core import thread_entries
    from trino_tpu.analysis.guarded_by import analyze
    index, _ = repo_findings
    entries = thread_entries(index)
    assert len(entries) >= 8, sorted(entries)
    mods = {e.func_id.split(":")[0] for e in entries.values()}
    assert len(mods) >= 4, sorted(mods)
    # the known thread-spawning modules must all contribute entries
    for mod in ("trino_tpu.exec.task_executor",
                "trino_tpu.parallel.process_runner",
                "trino_tpu.parallel.remote_exchange",
                "trino_tpu.parallel.worker",
                "trino_tpu.server.protocol"):
        assert mod in mods, sorted(mods)
    kinds = {e.kind for e in entries.values()}
    assert {"thread", "executor", "rpc-handler", "finalizer"} <= kinds
    analysis = analyze(index)
    assert len(analysis.guards) >= 10, sorted(analysis.guards)
    # the engine's known guarded families resolve to their locks
    assert analysis.guards[
        "trino_tpu.parallel.remote_exchange.RemoteExchangeChannel"
        "._queue"].endswith("RemoteExchangeChannel._lock")
    assert analysis.guards[
        "trino_tpu.parallel.process_runner.ProcessQueryRunner"
        ".workers"].endswith("ProcessQueryRunner._heal_lock")
    # the shared-state classes the pass exists for are indexed — a
    # rename that dropped them would blind the pass silently
    for probe in ("trino_tpu.parallel.worker._RetainedStream.frames",
                  "trino_tpu.server.protocol._QueryState.state",
                  "trino_tpu.exec.memory.HostSpillLedger"
                  ".resident_bytes"):
        assert probe in analysis.sites, probe
    assert analysis.guards[
        "trino_tpu.exec.memory.HostSpillLedger.resident_bytes"] \
        .endswith("HostSpillLedger._lock")


def test_guarded_by_sees_hybrid_join_partition_table(repo_findings):
    """The hybrid hash join's partition table mutates from whatever
    thread happens to hit the pool's revocation callback mid-reserve —
    exactly the shape the guarded-by pass exists for.  Reachability
    cannot see through the ``ctx._revoke_cb`` indirection, so the
    single-entry exemption (not a resolved guard) is the expected
    steady state; the floor pins what the pass DOES see: the class is
    indexed, and every post-init access of the partition-table family
    lexically holds ``HybridJoinState._lock``.  If the callback edge
    ever becomes visible, the exemption must flip to the real guard,
    never to a blind spot."""
    from trino_tpu.analysis.guarded_by import analyze

    index, _ = repo_findings
    analysis = analyze(index)
    base = "trino_tpu.ops.join.HybridJoinState."
    lock = base + "_lock"
    for attr in ("resident", "spilled_build", "spilled_probe",
                 "spilled_build_rows", "total_build_rows",
                 "demotions", "repartitions", "max_depth_seen"):
        ss = analysis.sites.get(base + attr)
        assert ss, f"guarded-by pass is blind to {base + attr}"
        post = [s for s in ss if not s.in_init]
        assert post, f"{attr}: no post-init sites indexed"
        for s in post:
            assert lock in s.lexical, (
                f"{attr} touched outside the partition-table lock at "
                f"{s.func_id}:{s.line}")
        guard = analysis.guards.get(base + attr)
        if guard is None:
            assert analysis.exempt.get(base + attr) == "single-entry", \
                (attr, analysis.exempt.get(base + attr))
        else:
            assert guard == lock, (attr, guard)


def test_nine_passes_registered():
    assert sorted(PASSES) == sorted([
        "trace-purity", "lock-order", "recompile", "session-props",
        "taxonomy", "blocked-protocol", "cache-coherence",
        "resource-lifecycle", "guarded-by"])


def test_analyzer_wall_clock_ratchet(monkeypatch):
    """The suite is a pre-commit gate, so a full fresh run (index + all
    nine passes + pragma audit) has to stay cheap. What keeps it so is
    structure, and structure is what is asserted: building the
    ``ProjectIndex`` parses every source file exactly once, and
    ``run_passes`` works on that one index — it parses nothing again
    and builds no second index. No clock is read."""
    import ast

    from trino_tpu.analysis import core

    parsed, built = [], []
    real_parse, real_init = ast.parse, ProjectIndex.__init__

    def counting_parse(source, filename="<unknown>", *a, **kw):
        parsed.append(filename)
        return real_parse(source, filename, *a, **kw)

    def counting_init(self, modules):
        built.append(len(modules))
        real_init(self, modules)

    monkeypatch.setattr(core.ast, "parse", counting_parse)
    monkeypatch.setattr(ProjectIndex, "__init__", counting_init)
    index = ProjectIndex.from_package(PACKAGE)
    files = sorted(
        os.path.join(root, fn)
        for root, dirs, fns in os.walk(PACKAGE)
        if "__pycache__" not in root
        and not os.path.relpath(root, PACKAGE).startswith("analysis")
        for fn in fns if fn.endswith(".py"))
    assert sorted(parsed) == files, "index parses each source file once"
    run_passes(index)
    assert len(parsed) == len(files), \
        f"run_passes parsed {len(parsed) - len(files)} sources again"
    assert built == [len(files)], "run_passes built a second index"


def test_cli_runs_clean_and_json(tmp_path):
    """`python -m trino_tpu.analysis` end to end: rc 0 on the clean
    tree, SARIF 2.1.0 shape, and rc 1 + stale reporting on a bad
    baseline."""
    env = dict(os.environ, PYTHONPATH=REPO)
    out = subprocess.run(
        [sys.executable, "-m", "trino_tpu.analysis", "--json", PACKAGE],
        capture_output=True, text=True, env=env, cwd=REPO, timeout=120)
    assert out.returncode == 0, out.stdout + out.stderr
    payload = json.loads(out.stdout)
    assert payload["version"] == "2.1.0"
    assert "sarif" in payload["$schema"]
    run = payload["runs"][0]
    assert run["tool"]["driver"]["name"] == "qlint"
    assert run["results"] == []
    props = run["properties"]
    assert props["new"] == []
    assert props["stale_baseline_keys"] == []
    assert sorted(props["passes"]) == sorted(PASSES)

    bad = tmp_path / "baseline.json"
    bad.write_text(json.dumps(
        {"findings": [{"key": "taxonomy:bare-raise:gone:f:raise",
                       "note": "stale"}]}))
    out = subprocess.run(
        [sys.executable, "-m", "trino_tpu.analysis", PACKAGE,
         "--baseline", str(bad)],
        capture_output=True, text=True, env=env, cwd=REPO, timeout=120)
    assert out.returncode == 1
    assert "STALE" in out.stdout


def test_cli_pass_selection(tmp_path):
    env = dict(os.environ, PYTHONPATH=REPO)
    out = subprocess.run(
        [sys.executable, "-m", "trino_tpu.analysis",
         "--passes", "session-props,taxonomy", "--json", PACKAGE],
        capture_output=True, text=True, env=env, cwd=REPO, timeout=120)
    assert out.returncode == 0, out.stdout + out.stderr
    payload = json.loads(out.stdout)
    assert payload["runs"][0]["properties"]["passes"] == \
        ["session-props", "taxonomy"]
    out = subprocess.run(
        [sys.executable, "-m", "trino_tpu.analysis",
         "--passes", "bogus", PACKAGE],
        capture_output=True, text=True, env=env, cwd=REPO, timeout=60)
    assert out.returncode == 2


def test_cli_changed_since(tmp_path):
    """Diff-aware pre-commit mode: full-index analysis, report
    filtered to files the git diff touched; SARIF results carry the
    same filter."""
    pkg = tmp_path / "pkg"
    (pkg / "parallel").mkdir(parents=True)
    (pkg / "__init__.py").write_text("")
    (pkg / "parallel" / "__init__.py").write_text("")
    (pkg / "parallel" / "a.py").write_text(
        "def fa():\n    raise RuntimeError('a')\n")
    (pkg / "parallel" / "b.py").write_text(
        "def fb():\n    raise RuntimeError('b')\n")

    def git(*args):
        out = subprocess.run(
            ["git", "-C", str(tmp_path), "-c", "user.name=t",
             "-c", "user.email=t@t", *args],
            capture_output=True, text=True, timeout=30)
        assert out.returncode == 0, out.stderr
        return out

    git("init", "-q")
    git("add", "-A")
    git("commit", "-qm", "seed")
    # touch ONLY a.py: its finding reports, b.py's is filtered out
    (pkg / "parallel" / "a.py").write_text(
        "def fa():\n    x = 1\n    raise RuntimeError('a')\n")

    # an UNTRACKED new module must be part of the changed set too: a
    # pre-commit gate that can't see files before `git add` is useless
    (pkg / "parallel" / "c.py").write_text(
        "def fc():\n    raise RuntimeError('c')\n")

    env = dict(os.environ, PYTHONPATH=REPO)
    out = subprocess.run(
        [sys.executable, "-m", "trino_tpu.analysis", str(pkg),
         "--no-baseline", "--changed-since", "HEAD"],
        capture_output=True, text=True, env=env, cwd=REPO, timeout=120)
    assert out.returncode == 1, out.stdout + out.stderr
    assert "pkg.parallel.a" in out.stdout
    assert "pkg.parallel.c" in out.stdout
    assert "pkg.parallel.b" not in out.stdout
    assert "changed-since HEAD" in out.stderr

    # the full run still sees both
    out = subprocess.run(
        [sys.executable, "-m", "trino_tpu.analysis", str(pkg),
         "--no-baseline"],
        capture_output=True, text=True, env=env, cwd=REPO, timeout=120)
    assert out.returncode == 1
    assert "pkg.parallel.a" in out.stdout
    assert "pkg.parallel.b" in out.stdout

    # a docs-only diff must exit 0 with an EXPLICIT no-analyzable-
    # changes note (distinguishable from an analyzed-and-clean run in
    # CI logs), even though the tree still has findings
    git("add", "-A")
    git("commit", "-qm", "tree with findings")
    (tmp_path / "NOTES.md").write_text("docs only\n")
    out = subprocess.run(
        [sys.executable, "-m", "trino_tpu.analysis", str(pkg),
         "--no-baseline", "--changed-since", "HEAD"],
        capture_output=True, text=True, env=env, cwd=REPO, timeout=120)
    assert out.returncode == 0, out.stdout + out.stderr
    assert "no analyzable changes" in out.stderr
    assert "touches no Python files" in out.stderr
