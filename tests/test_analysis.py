"""Static guardrails (poseidon_tpu/analysis): rule-by-rule fixtures, the
end-to-end run over the real package, and the HLO contract gates.

Layout mirrors the subsystem: (1) synthetic snippets prove each rule
FIRES on a known violation and stays quiet on the lock-disciplined twin;
(2) the whole package is linted against the checked-in baseline — the tree
must ship clean; (3) the checked-in per-model HLO contracts are recomputed
and diffed (the compile half of the gate, same counters CI runs)."""

import json
import os
import subprocess
import sys
import textwrap

import pytest

from poseidon_tpu.analysis import (Finding, filter_new, iter_python_files,
                                   load_baseline, pragma_suppressed,
                                   run_lints)
from poseidon_tpu.analysis import contracts as C
from poseidon_tpu.analysis import jit_hygiene, threads

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _thr(src: str):
    return threads.lint_file("synthetic.py", textwrap.dedent(src))


def _jit(src: str, path: str = "synthetic.py"):
    return jit_hygiene.lint_file(path, textwrap.dedent(src))


def _rules(findings):
    return {f.rule for f in findings}


# --------------------------------------------------------------------------- #
# THR: concurrency rules on fixture snippets
# --------------------------------------------------------------------------- #

RACY_COUNTER = """
    import threading

    class Racy:
        def __init__(self):
            self._lock = threading.Lock()
            self.count = 0
            self._t = threading.Thread(target=self._loop, daemon=True)
            self._t.start()

        def _loop(self):
            while True:
                self.count += 1

        def read(self):
            with self._lock:
                return self.count
"""

LOCKED_TWIN = """
    import threading

    class Disciplined:
        def __init__(self):
            self._lock = threading.Lock()
            self.count = 0
            self._t = threading.Thread(target=self._loop, daemon=True)
            self._t.start()

        def _loop(self):
            while True:
                with self._lock:
                    self.count += 1

        def read(self):
            with self._lock:
                return self.count
"""


def test_unlocked_counter_flagged_locked_twin_passes():
    racy = _thr(RACY_COUNTER)
    assert "THR004" in _rules(racy), racy
    assert [f.key for f in racy if f.rule == "THR004"] == ["count"]
    assert not _thr(LOCKED_TWIN)


def test_annotated_lock_declaration_recognized():
    """A lock declared with an annotated assignment in __init__ is a lock
    like any other — its regions must credit, not flag."""
    out = _thr("""
        import threading

        class AnnLocked:
            def __init__(self):
                self._lock: threading.Lock = threading.Lock()
                self.count = 0
                t = threading.Thread(target=self._loop, daemon=True)
                t.start()

            def _loop(self):
                with self._lock:
                    self.count += 1

            def read(self):
                with self._lock:
                    return self.count
    """)
    assert not out, out


def test_acquire_release_region_credits_the_lock():
    """The acquire/try/finally/release idiom holds the lock exactly like
    `with` — and a mutation AFTER the release is still outside it."""
    out = _thr("""
        import threading

        class AcqLocked:
            def __init__(self):
                self._lock = threading.Lock()
                self.count = 0
                t = threading.Thread(target=self._loop, daemon=True)
                t.start()

            def _loop(self):
                self._lock.acquire()
                try:
                    self.count += 1
                finally:
                    self._lock.release()

            def read(self):
                with self._lock:
                    return self.count
    """)
    assert not out, out
    out = _thr("""
        import threading

        class PostRelease:
            def __init__(self):
                self._lock = threading.Lock()
                self.count = 0
                t = threading.Thread(target=self._loop, daemon=True)
                t.start()

            def _loop(self):
                self._lock.acquire()
                self._lock.release()
                self.count += 1

            def read(self):
                with self._lock:
                    return self.count
    """)
    assert any(f.rule == "THR004" and f.key == "count" for f in out), out


def test_annotated_store_in_thread_body_flagged():
    """`self.count: int = v` in a thread entrypoint stores exactly like
    the plain spelling — an annotation must not hide the race."""
    out = _thr("""
        import threading

        class AnnStore:
            def __init__(self):
                self._lock = threading.Lock()
                self.count = 0
                t = threading.Thread(target=self._loop, daemon=True)
                t.start()

            def _loop(self):
                self.count: int = 99

            def read(self):
                with self._lock:
                    return self.count
    """)
    assert any(f.rule == "THR001" and f.key == "count" for f in out), out


def test_unbalanced_acquire_in_with_survives_with_exit():
    """An explicit .acquire() of a DIFFERENT lock inside a `with` body,
    released only after the with exits, keeps its credit across the exit
    — the with-exit pops its OWN lock by name, not the top of the stack."""
    out = _thr("""
        import threading

        class Handoff:
            def __init__(self):
                self._a = threading.Lock()
                self._b = threading.Lock()
                self.count = 0
                t = threading.Thread(target=self._loop, daemon=True)
                t.start()

            def _loop(self):
                with self._a:
                    self._b.acquire()
                self.count += 1
                self._b.release()

            def read(self):
                with self._b:
                    return self.count
    """)
    assert not out, out


def test_spawn_in_constructor_thread_body_flagged():
    """A thread target defined INSIDE __init__ runs after start() and
    races like any entrypoint; only non-thread init helpers keep the
    publish-before-start exemption."""
    out = _thr("""
        import threading

        class SpawnInCtor:
            def __init__(self):
                self._lock = threading.Lock()
                self.count = 0

                def _loop():
                    while True:
                        self.count += 1

                t = threading.Thread(target=_loop, daemon=True)
                t.start()
    """)
    assert any(f.rule == "THR004" and f.key == "count" for f in out), out


def test_mutation_under_disjoint_locks_flagged():
    """Writers under DIFFERENT locks don't exclude each other — the
    wrong-lock bug is THR006 even though every mutation is locked."""
    out = _thr("""
        import threading

        class WrongLock:
            def __init__(self):
                self._a = threading.Lock()
                self._b = threading.Lock()
                self.n = 0
                t = threading.Thread(target=self._loop, daemon=True)
                t.start()

            def _loop(self):
                with self._a:
                    self.n += 1

            def bump(self):
                with self._b:
                    self.n += 1
    """)
    assert any(f.rule == "THR006" and f.key == "n" for f in out), out


def test_known_race_flagged_general_mutation():
    """Assign-form (not +=) shared mutation -> THR001."""
    out = _thr("""
        import threading

        class Shared:
            def __init__(self):
                self._lock = threading.Lock()
                self.view = {}
                t = threading.Thread(target=self._poll, daemon=True)
                t.start()

            def _poll(self):
                self.view = {"fresh": True}

            def snapshot(self):
                with self._lock:
                    return dict(self.view)
    """)
    assert "THR001" in _rules(out), out


def test_caller_holds_lock_helper_not_flagged():
    """A private helper mutating state whose EVERY call site holds the
    lock inherits the lock (the _admit_locked pattern)."""
    out = _thr("""
        import threading

        class Svc:
            def __init__(self):
                self._lock = threading.Lock()
                self.members = set()
                t = threading.Thread(target=self._loop, daemon=True)
                t.start()

            def _admit_locked(self, w):
                self.members.add(w)

            def _loop(self):
                with self._lock:
                    self._admit_locked(1)

            def admit(self, w):
                with self._lock:
                    self._admit_locked(w)
    """)
    assert not out, out


def test_lock_order_cycle_detected():
    out = _thr("""
        import threading

        class TwoLocks:
            def __init__(self):
                self._a = threading.Lock()
                self._b = threading.Lock()
                t = threading.Thread(target=self._loop, daemon=True)
                t.start()

            def _loop(self):
                with self._a:
                    with self._b:
                        pass

            def other(self):
                with self._b:
                    with self._a:
                        pass
    """)
    cyc = [f for f in out if f.rule == "THR002"]
    assert cyc and "_a" in cyc[0].key and "_b" in cyc[0].key, out


def test_callback_does_not_inherit_registration_site_locks():
    """A method passed AS AN ARGUMENT runs whenever the callee decides,
    not under the locks held where it was registered — the callback edge
    must not feed caller-holds-lock inheritance."""
    out = _thr("""
        import threading

        class Dispatcher:
            def __init__(self):
                self._lock = threading.Lock()
                self.fired = 0
                t = threading.Thread(target=self._drain, daemon=True)
                t.start()

            def _drain(self):
                with self._lock:
                    retry(self._on_event)

            def _on_event(self):
                self.fired += 1
    """)
    assert any(f.rule == "THR004" and f.key == "fired" for f in out), out


def test_lock_order_cycle_detected_in_multi_item_with():
    """`with self._a, self._b:` must record the same _a -> _b order edge
    as the nested spelling."""
    out = _thr("""
        import threading

        class TwoLocks:
            def __init__(self):
                self._a = threading.Lock()
                self._b = threading.Lock()
                t = threading.Thread(target=self._loop, daemon=True)
                t.start()

            def _loop(self):
                with self._a, self._b:
                    pass

            def other(self):
                with self._b:
                    with self._a:
                        pass
    """)
    assert any(f.rule == "THR002" and "_a" in f.key and "_b" in f.key
               for f in out), out


def test_self_deadlock_on_plain_lock():
    out = _thr("""
        import threading

        class Re:
            def __init__(self):
                self._lock = threading.Lock()
                t = threading.Thread(target=self._loop, daemon=True)
                t.start()

            def _loop(self):
                with self._lock:
                    self._helper()

            def _helper(self):
                with self._lock:
                    pass
    """)
    assert any(f.rule == "THR002" and f.key == "self:_lock" for f in out), out


def test_rlock_reacquisition_not_flagged():
    """The re-entrant twin of test_self_deadlock_on_plain_lock: RLock
    (and default Condition) re-acquisition is legal and must stay quiet."""
    for ctor in ("RLock", "Condition"):
        out = _thr(f"""
            import threading

            class Re:
                def __init__(self):
                    self._lock = threading.{ctor}()
                    t = threading.Thread(target=self._loop, daemon=True)
                    t.start()

                def _loop(self):
                    with self._lock:
                        self._helper()

                def _helper(self):
                    with self._lock:
                        pass
        """)
        assert not [f for f in out if f.rule == "THR002"], (ctor, out)


def test_check_then_act_flagged():
    out = _thr("""
        import threading

        class CTA:
            def __init__(self):
                self._lock = threading.Lock()
                self.cache = {}
                t = threading.Thread(target=self._loop, daemon=True)
                t.start()

            def _loop(self):
                if "k" not in self.cache:
                    self.cache["k"] = 1

            def get(self):
                with self._lock:
                    return self.cache.get("k")
    """)
    assert "THR003" in _rules(out), out


def test_check_then_act_inside_init_exempt():
    """__init__ runs before any thread exists (publish-before-start), so
    a check-then-act there must stay quiet — only thread-target locals
    lose the exemption."""
    out = _thr("""
        import threading

        class C:
            def __init__(self, seed):
                self._lock = threading.Lock()
                self.stats = {}
                if seed not in self.stats:
                    self.stats[seed] = 0
                t = threading.Thread(target=self._loop, daemon=True)
                t.start()

            def _loop(self):
                with self._lock:
                    self.stats["n"] = 1
    """)
    assert not out, out


def test_check_then_act_on_public_attr_without_class_reader_flagged():
    """A PUBLIC attr is readable cross-object (the way server.py reads
    the batcher's counters), so a thread-side check-then-act must fire
    even when no method of the class itself reads it — the cta deferral
    out of THR001/THR004 must not drop it below THR003's bar."""
    out = _thr("""
        import threading

        class Cache:
            def __init__(self):
                self._lock = threading.Lock()
                self.cache = {}
                t = threading.Thread(target=self._loop, daemon=True)
                t.start()

            def _loop(self):
                if "k" not in self.cache:
                    self.cache["k"] = 1
    """)
    assert "THR003" in _rules(out), out


def test_jax_from_thread_flagged():
    out = _thr("""
        import threading
        import jax

        class BadWorker:
            def __init__(self):
                t = threading.Thread(target=self._loop, daemon=True)
                t.start()

            def _loop(self):
                jax.device_put(1)
    """)
    assert "THR005" in _rules(out), out


def test_mixed_discipline_flagged_without_thread():
    """THR006 needs no Thread construction — a lock-owning class whose
    attr is mutated both under and outside the lock is wrong somewhere."""
    out = _thr("""
        import threading

        class Mixed:
            def __init__(self):
                self._lock = threading.Lock()
                self.n = 0

            def locked_bump(self):
                with self._lock:
                    self.n += 1

            def unlocked_bump(self):
                self.n += 1
    """)
    assert "THR006" in _rules(out), out


def test_thread_target_nested_function_tracked():
    """The AsyncSnapshotWriter shape: Thread(target=<local fn>)."""
    out = _thr("""
        import threading

        class W:
            def __init__(self):
                self._lock = threading.Lock()
                self.last = None

            def submit(self):
                def _write():
                    self.last = "x"
                t = threading.Thread(target=_write, daemon=True)
                t.start()

            def read(self):
                with self._lock:
                    return self.last
    """)
    assert any(f.symbol == "W.submit._write" for f in out), out


def test_pragma_suppresses_in_place():
    src = textwrap.dedent(RACY_COUNTER).replace(
        "self.count += 1", "self.count += 1  # static-ok: THR004")
    out = [f for f in threads.lint_file("synthetic.py", src)
           if not pragma_suppressed(src.splitlines(), f)]
    assert not out, out


def test_def_level_pragma_suppresses_thr_rules():
    """'# static-ok: RULE' above a def blesses the whole function for
    ANY rule family, as the docs promise — not just the JIT rules."""
    import ast as ast_mod
    src = textwrap.dedent(RACY_COUNTER).replace(
        "    def _loop(self):",
        "    # static-ok: THR004\n    def _loop(self):")
    tree = ast_mod.parse(src)
    out = [f for f in threads.lint_file("synthetic.py", src, tree=tree)
           if not pragma_suppressed(src.splitlines(), f, tree=tree)]
    assert not out, out


# --------------------------------------------------------------------------- #
# JIT: hygiene rules on fixture snippets
# --------------------------------------------------------------------------- #

def test_host_sync_in_traced_function_flagged():
    out = _jit("""
        import jax
        import numpy as np

        def build():
            def step(x):
                y = x + 1
                return np.asarray(y).sum()
            return jax.jit(step)
    """)
    assert any(f.rule == "JIT101" and f.key == "np.asarray" for f in out), out


def test_item_in_decorated_jit_flagged():
    out = _jit("""
        import jax

        @jax.jit
        def step(x):
            return x.item()
    """)
    assert any(f.rule == "JIT101" and f.key == ".item()" for f in out), out


def test_traced_function_resolved_at_depth_and_reported_once():
    """jax.jit over a doubly-nested def resolves to the full qualname,
    and a sync in a nested def of a traced fn lands exactly ONE finding
    (under the innermost def, not doubled via descent)."""
    out = _jit("""
        import jax
        import numpy as np

        class A:
            def b(self):
                def c():
                    def d(x):
                        return np.asarray(x)
                    return jax.jit(d)
                return c
    """)
    hits = [f for f in out if f.rule == "JIT101"]
    assert [f.symbol for f in hits] == ["A.b.c.d"], out
    out = _jit("""
        import jax
        import numpy as np

        @jax.jit
        def step(x):
            def inner(y):
                return np.asarray(y)
            return inner(x)
    """)
    hits = [f for f in out if f.rule == "JIT101"]
    assert [f.symbol for f in hits] == ["step.inner"], out


_PALLAS_KERNEL_SRC = """
    import functools
    import numpy as np
    from jax.experimental import pallas as pl

    def _my_kernel(x_ref, o_ref, *, tile):
        offs = np.asarray(range(tile))        # static index math: fine
        o_ref[...] = x_ref[...] * {payload}

    def run(x):
        return pl.pallas_call(
            functools.partial(_my_kernel, tile=8),
            out_shape=x)(x)
"""


def test_pallas_kernel_body_np_static_math_not_flagged():
    """The carve-out: np.* inside a Pallas kernel body is trace-time
    constant math on static shapes — there is no device value to sync —
    so the host-sync rule must stay quiet there."""
    out = _jit(_PALLAS_KERNEL_SRC.format(payload="offs.sum()"))
    assert not [f for f in out if f.rule == "JIT101"], out


def test_pallas_kernel_body_real_sync_still_fires():
    """.item() (or device_get) inside a kernel body cannot lower at all —
    the kernel-body exemption must NOT blind the rule to it."""
    out = _jit(_PALLAS_KERNEL_SRC.format(payload="x_ref[0].item()"))
    hits = [f for f in out if f.rule == "JIT101" and f.key == ".item()"]
    assert [f.symbol for f in hits] == ["_my_kernel"], out
    assert "Pallas kernel body" in hits[0].message


def test_experimental_tracing_wrapper_still_linted():
    """The jax.experimental import branch (pallas detection) must not
    shadow TRACING_WRAPPERS resolution: a shard_map imported from
    jax.experimental.shard_map still traces its function."""
    out = _jit("""
        from jax.experimental.shard_map import shard_map

        @shard_map
        def step(x):
            return x.item()
    """)
    assert any(f.rule == "JIT101" and f.key == ".item()"
               and f.symbol == "step" for f in out), out


def test_pallas_kernel_detected_through_direct_reference():
    """pallas_call(kernel) without the functools.partial wrapper, via the
    bare-name import form."""
    out = _jit("""
        from jax.experimental.pallas import pallas_call

        def _k(x_ref, o_ref):
            o_ref[...] = x_ref[...].item()

        def run(x):
            return pallas_call(_k, out_shape=x)(x)
    """)
    assert any(f.rule == "JIT101" and f.symbol == "_k"
               and f.key == ".item()" for f in out), out


def test_bound_method_passed_to_jit_is_traced():
    """jax.jit(self._fwd) marks the sibling method traced — the serving
    executor traces its step exactly this way, so a Name-only resolver
    would blind JIT101 to a real in-repo traced function."""
    out = _jit("""
        import jax
        import numpy as np

        class Executor:
            def build(self):
                return jax.jit(self._fwd)

            def _fwd(self, x):
                return np.asarray(x).sum()
    """)
    hits = [f for f in out if f.rule == "JIT101"]
    assert [f.symbol for f in hits] == ["Executor._fwd"], out


def test_host_sync_in_window_flagged():
    """The window table keys on the engine's repo-relative path, so a
    synthetic engine.py exercises the real configuration."""
    out = _jit("""
        class Engine:
            def _dispatch_train_step(self, batch, rng):
                return float(self._helper(batch))

            def _helper(self, batch):
                import jax
                return jax.device_get(batch)
    """, path=os.path.join(REPO, "poseidon_tpu/runtime/engine.py"))
    assert any(f.rule == "JIT102" and f.key == "float()" for f in out), out
    assert any(f.rule == "JIT102" and f.key == "jax.device_get"
               for f in out), out


def test_stale_window_method_surfaces_instead_of_blinding_rule():
    """A WINDOW_METHODS entry that no longer resolves must itself be a
    finding (the JIT105 pattern) — the fixture above defines only
    _dispatch_train_step, so the other configured names must fire."""
    out = _jit("""
        class Engine:
            def _dispatch_train_step(self, batch, rng):
                return batch
    """, path=os.path.join(REPO, "poseidon_tpu/runtime/engine.py"))
    missing = {f.key for f in out
               if f.rule == "JIT102" and f.key.startswith("missing:")}
    assert "missing:Engine._next_batch" in missing, out
    # and the REAL engine resolves every configured name (no findings)
    from poseidon_tpu.analysis import run_lints
    real = run_lints([os.path.join(REPO, "poseidon_tpu/runtime/engine.py")],
                     rules=["JIT102"])
    assert not [f for f in real if f.key.startswith("missing:")], real


def test_retrace_hazard_jit_in_loop():
    out = _jit("""
        import jax

        def bench(xs):
            acc = 0
            for x in xs:
                acc += jax.jit(lambda v: v * 2)(x)
            return acc
    """)
    assert "JIT103" in _rules(out), out
    # stored wrapper outside the loop: deliberate, quiet
    ok = _jit("""
        import jax

        def bench(xs):
            f = jax.jit(lambda v: v * 2)
            return [f(x) for x in xs]
    """)
    assert "JIT103" not in _rules(ok), ok


def test_host_sync_in_control_flow_branch_functions_flagged():
    """fori_loop's body lives at args[2] and cond's false branch at
    args[2] — both trace, so both must be scanned."""
    out = _jit("""
        import jax
        import numpy as np

        def run(x):
            def body(i, acc):
                return acc + np.asarray(i)
            return jax.lax.fori_loop(0, 10, body, x)

        def pick(p, x):
            def t(v):
                return v
            def f(v):
                return np.asarray(v)
            return jax.lax.cond(p, t, f, x)
    """)
    assert {f.symbol for f in out if f.rule == "JIT101"} == \
        {"run.body", "pick.f"}, out


def test_plain_import_jax_numpy_does_not_blind_jax_checks():
    """`import jax.numpy` binds only the root name `jax` — it must not
    remap the 'jax' alias to jnp and hide jax.device_get host syncs."""
    out = _jit("""
        import jax
        import jax.numpy

        @jax.jit
        def step(x):
            return jax.device_get(x)
    """)
    assert any(f.rule == "JIT101" and f.key == "jax.device_get"
               for f in out), out


def test_f64_flagged_under_from_jax_import_numpy():
    out = _jit("""
        from jax import numpy as jnp

        def make():
            return jnp.zeros(3, dtype=jnp.float64)
    """)
    assert any(f.rule == "JIT104" for f in out), out


def test_f64_promotion_flagged():
    out = _jit("""
        import numpy as np

        def bad(x):
            return x.astype("float64") + np.zeros(3, dtype=np.float64)
    """)
    assert sum(1 for f in out if f.rule == "JIT104") == 2, out


def test_named_scope_recognized_as_bare_name_import():
    """`from jax import named_scope` + `with named_scope(...)` keeps the
    JIT105 contract satisfied — the matcher must not require the
    attribute-call spelling."""
    import ast as ast_mod
    names, _dyn = jit_hygiene._named_scope_strings(ast_mod.parse(
        textwrap.dedent("""
            from jax import named_scope

            def update(x):
                with named_scope("optimizer_update"):
                    return x
        """)))
    assert "optimizer_update" in names, names


def test_named_scope_contract_fires_when_scope_removed():
    """updates.py without its optimizer_update scope -> JIT105."""
    path = os.path.join(REPO, "poseidon_tpu/solvers/updates.py")
    out = _jit("def make_update_fn():\n    pass\n", path=path)
    assert any(f.rule == "JIT105" and f.key == "optimizer_update"
               for f in out), out
    # and the real module satisfies its table
    with open(path) as f:
        assert not _jit(f.read(), path=path)


_JIT106_FIXTURE = """
    import jax

    def apply(layers, params, x):
        def _body(p, b):
            {scope_site}
        for layer in layers:
            with jax.named_scope(layer.name):
                pass  # forward-only scope: the recompute escapes it
            x = jax.checkpoint(_body)(params, x)
        return x
"""


def test_jit106_checkpoint_body_without_scope_fires():
    """A checkpointed layer body with the named_scope OUTSIDE it: the ops
    XLA recomputes during backward carry no layer scope, so the remat
    planner's recompute cost would vanish into (unattributed)."""
    out = _jit(_JIT106_FIXTURE.format(scope_site="return b * p"),
               path=os.path.join(REPO, "poseidon_tpu/core/net.py"))
    assert any(f.rule == "JIT106" and f.key == "_body" for f in out), out


def test_jit106_quiet_twin_scope_inside_body():
    """Same fixture with the scope moved INSIDE the checkpointed body —
    quiet; and the rule stays scoped to REMAT_SCOPE_FILES (the identical
    defect in a file outside the table is not its business)."""
    good = _JIT106_FIXTURE.format(
        scope_site='with jax.named_scope("layer"):\n'
                   '                return b * p')
    out = _jit(good, path=os.path.join(REPO, "poseidon_tpu/core/net.py"))
    assert not [f for f in out if f.rule == "JIT106"], out
    elsewhere = _jit(_JIT106_FIXTURE.format(scope_site="return b * p"))
    assert not [f for f in elsewhere if f.rule == "JIT106"], elsewhere


def test_jit106_real_net_module_is_quiet():
    """The shipped core/net.py keeps its named_scope inside the
    checkpointed _body (the wiring the rule exists to protect)."""
    path = os.path.join(REPO, "poseidon_tpu/core/net.py")
    with open(path) as f:
        out = _jit(f.read(), path=path)
    assert not [x for x in out if x.rule == "JIT106"], out


# --------------------------------------------------------------------------- #
# end-to-end: the shipped tree is clean vs the shipped baseline
# --------------------------------------------------------------------------- #

def test_shipped_tree_has_no_new_findings():
    findings = run_lints()
    new = filter_new(findings, load_baseline())
    assert not new, "\n".join(f.render() for f in new)


def test_baseline_entries_still_fire():
    """A baseline entry whose finding no longer exists is stale — shrink
    the file (the grandfather list must never outlive its findings)."""
    live = {f.fingerprint for f in run_lints()}
    stale = [fp for fp in load_baseline() if fp not in live]
    assert not stale, f"stale baseline entries (delete them): {stale}"


def test_cli_exit_codes(tmp_path):
    env = dict(os.environ, PYTHONPATH=REPO)
    clean = subprocess.run(
        [sys.executable, "-m", "poseidon_tpu.analysis"],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=120)
    assert clean.returncode == 0, clean.stdout + clean.stderr

    racy = tmp_path / "racy.py"
    racy.write_text(textwrap.dedent(RACY_COUNTER))
    report = tmp_path / "report.json"
    dirty = subprocess.run(
        [sys.executable, "-m", "poseidon_tpu.analysis", str(racy),
         "--report", str(report)],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=120)
    assert dirty.returncode == 1, dirty.stdout + dirty.stderr
    assert "THR004" in dirty.stdout
    doc = json.loads(report.read_text())
    assert doc["new"] == 1 and doc["findings"]

    # usage errors exit 3 — NOT 2, which means a real contract violation
    typo = subprocess.run(
        [sys.executable, "-m", "poseidon_tpu.analysis",
         "--contracts", "lenett"],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=120)
    assert typo.returncode == 3, typo.stdout + typo.stderr
    assert "unknown model" in typo.stderr


def test_cli_no_fail_on_new_is_report_only(tmp_path):
    """--no-fail-on-new surveys findings without failing (e.g. from a
    pre-commit hook while triaging) — same output, exit 0."""
    from poseidon_tpu.analysis import __main__ as M
    racy = tmp_path / "racy.py"
    racy.write_text(textwrap.dedent(RACY_COUNTER))
    assert M.main([str(racy)]) == 1                       # default fails
    assert M.main(["--no-fail-on-new", str(racy)]) == 0


def test_cli_rejects_nonexistent_target_and_bad_flag_with_exit_3():
    """A typo'd path or flag must never read as '0 findings, clean' —
    and must not collide with exit 2 (contract violation) either."""
    env = dict(os.environ, PYTHONPATH=REPO)
    for argv in (["no_such_file.py"], ["--bogus"]):
        r = subprocess.run(
            [sys.executable, "-m", "poseidon_tpu.analysis"] + argv,
            cwd=REPO, env=env, capture_output=True, text=True, timeout=120)
        assert r.returncode == 3, (argv, r.stdout, r.stderr)


def test_cli_empty_contract_spec_is_a_usage_error():
    """--contracts ',' (or '' from an unset CI variable) must not run a
    gate over zero models and read as passing — exit 3 like any typo."""
    from poseidon_tpu.analysis import __main__ as M
    for spec in (",", ""):
        with pytest.raises(SystemExit) as e:
            M.main(["--contracts", spec])
        assert e.value.code == 3, spec


def test_missing_configured_script_target_surfaces_cfg001():
    """EXTRA_SCRIPT_TARGETS rot must surface as a finding, not silently
    shrink lint coverage (the WINDOW_METHODS pattern)."""
    from poseidon_tpu import analysis as A
    out = A.run_lints([os.path.join(A.REPO_ROOT, "scripts/gone.py")])
    assert [f.rule for f in out] == ["CFG001"], out
    # a --rules-restricted run (pre-commit hook style) must not filter
    # the infrastructure finding away and read as clean coverage
    out = A.run_lints([os.path.join(A.REPO_ROOT, "scripts/gone.py")],
                      rules=["THR001", "THR004"])
    assert [f.rule for f in out] == ["CFG001"], out


def test_cli_contract_infra_failure_exits_4_and_keeps_report(
        tmp_path, monkeypatch):
    """A crash while MEASURING contracts is exit 4 (not a lint 1 or a
    violation 2) and the already-complete lint report still lands."""
    from poseidon_tpu.analysis import __main__ as M
    from poseidon_tpu.analysis import contracts as C

    def boom(models):
        raise RuntimeError("simulated infra failure")

    monkeypatch.setattr(C, "check_all", boom)
    report = tmp_path / "r.json"
    rc = M.main(["--contracts", "lenet", "--report", str(report)])
    assert rc == 4
    doc = json.loads(report.read_text())
    assert "simulated infra failure" in doc["contracts_error"]


# --------------------------------------------------------------------------- #
# HLO contract gates
# --------------------------------------------------------------------------- #

def test_contract_diff_detects_synthetic_violation():
    """Pure-diff half: a regressed counter or lost donation is reported
    without any compilation."""
    golden = C.load_contract("googlenet")
    assert golden is not None, "missing checked-in googlenet contract"
    fresh = json.loads(json.dumps(golden))
    fresh["stablehlo"]["gradient_all_reduces"] = 120   # per-leaf regression
    diffs = C.diff_contracts(golden, fresh)
    assert diffs and "gradient_all_reduces" in diffs[0], diffs
    fresh = json.loads(json.dumps(golden))
    fresh["stablehlo"]["donated_buffers"] = 0
    don = [d for d in C.diff_contracts(golden, fresh) if "donat" in d]
    assert len(don) == 1, don      # one defect, one line — never doubled
    assert not C.diff_contracts(golden, golden)


def test_contract_device_count_mismatch_refuses_not_violates():
    """A golden measured on a different device count is NOT comparable:
    check_model refuses (ContractEnvironmentError -> CLI exit 4), never
    reporting the mismatch as a violation (exit 2)."""
    golden = C.load_contract("lenet")
    fresh = json.loads(json.dumps(golden))
    fresh["generated_with"]["n_devices"] = 1
    with pytest.raises(C.ContractEnvironmentError, match="not comparable"):
        C.check_model("lenet", fresh=fresh)


def test_contract_jax_version_mismatch_refuses_not_violates():
    """There is no cross-version "robust subset": a golden generated
    under another jax is not comparable, and the goldens on file are
    stamped with the jax this process runs — so the FULL comparison
    (optimized section included) is what test_hlo_contract_lenet makes."""
    import jax
    golden = C.load_contract("lenet")
    assert golden is not None and "optimized" in golden
    for m in C.MODELS:
        assert C.load_contract(m)["generated_with"]["jax"] == jax.__version__
    fresh = json.loads(json.dumps(golden))
    fresh["generated_with"]["jax"] = "999.0.0"
    with pytest.raises(C.ContractEnvironmentError, match="999.0.0"):
        C.check_model("lenet", fresh=fresh)
    fresh = json.loads(json.dumps(golden))
    fresh["optimized"]["fusion_count"] += 3
    assert any("optimized.fusion_count" in d
               for d in C.diff_contracts(golden, fresh))


def test_hlo_contract_lenet():
    """Fast lane: LeNet traces + CPU-compiles in seconds, so the full
    gate (stablehlo AND optimized sections) runs in every tier-1 sweep."""
    ok, diffs = C.check_model("lenet")
    assert ok, diffs


def test_contract_headline_numbers_are_pinned():
    """The golden FILES themselves carry the marquee invariants — a
    hand-edit that waters them down fails here without any compile."""
    alexnet = C.load_contract("alexnet")
    assert alexnet["nhwc"]["layout_transposes"] == 2      # fc6 pair only
    googlenet = C.load_contract("googlenet")
    # one sum a leaf in the lowered step (the 54 biases under the counter's
    # 256-element floor are not counted), no bucket: never more than the
    # leaves, and not the 11 buckets of PRs 4-58
    assert googlenet["config"]["param_leaves"] == 128
    assert googlenet["stablehlo"]["gradient_all_reduces"] == 74
    for m in C.MODELS:
        c = C.load_contract(m)
        assert c["stablehlo"]["f64_tensors"] == 0
        assert c["stablehlo"]["donated_buffers"] > 0
        assert c["generated_with"]["n_devices"] == 8


@pytest.mark.slow
def test_hlo_contract_alexnet():
    """Slow lane (~35s of tracing incl. the NHWC re-trace at 227 px):
    the tier-1 870s sweep budget can't afford it, so CI verifies it on
    every push via `scripts/check_static.py --contracts all` instead
    (the dedicated static-analysis step in tier1.yml)."""
    ok, diffs = C.check_model("alexnet")
    assert ok, diffs


@pytest.mark.slow
def test_hlo_contract_googlenet():
    """Slow lane (~25s of tracing); CI covers it via check_static
    --contracts all, same as alexnet."""
    ok, diffs = C.check_model("googlenet")
    assert ok, diffs


# --------------------------------------------------------------------------- #
# conftest thread sanitizer
# --------------------------------------------------------------------------- #

@pytest.mark.allow_thread_exceptions
def test_thread_excepthook_records():
    """The sanitizer's hook sees uncaught thread exceptions (this test
    carries the marker, so recording one must NOT fail it)."""
    import threading

    # the hook's globals ARE the conftest module namespace (tests/ is not
    # a package, so the module isn't importable by a stable name)
    _THREAD_ERRORS = threading.excepthook.__globals__["_THREAD_ERRORS"]
    n0 = len(_THREAD_ERRORS)

    def boom():
        raise RuntimeError("intentional sanitizer probe")

    t = threading.Thread(target=boom, daemon=True)
    t.start()
    t.join(2.0)
    assert len(_THREAD_ERRORS) == n0 + 1
    thread, msg = _THREAD_ERRORS[-1]
    # the OBJECT is recorded (idents get recycled across thread lifetimes)
    assert thread is t
    assert "intentional sanitizer probe" in msg


# --------------------------------------------------------------------------- #
# layering: the packages below runtime/ do not import from it
# --------------------------------------------------------------------------- #

# what the lower packages may take from poseidon_tpu.runtime: the logger
# and the span recorder (both jax-free leaves of runtime/); no named debt
# is left
_RUNTIME_ALLOWED = {
    "poseidon_tpu.runtime.metrics.log",
    "poseidon_tpu.runtime.spans.recorder",
}
_RUNTIME_DEBTS = set()
LOWER_PACKAGES = ["numeric.py", "config.py", "ops", "solvers", "proto",
                  "core", "data"]


def _imports_of(path: str, source: str = None,
                of: str = "poseidon_tpu.runtime"):
    """(line, dotted name) of everything ``path`` imports from the package
    ``of``, relative imports resolved, function-level imports included (an
    ``ast.walk`` sees every statement)."""
    import ast
    pkg = os.path.relpath(path, REPO)[:-3].split(os.sep)[:-1]
    if source is None:
        with open(path) as f:
            source = f.read()
    found = []
    for node in ast.walk(ast.parse(source)):
        if isinstance(node, ast.Import):
            names = [a.name for a in node.names]
        elif isinstance(node, ast.ImportFrom):
            base = pkg[:len(pkg) - (node.level - 1)] if node.level else []
            mod = ".".join(base + ([node.module] if node.module else []))
            names = [f"{mod}.{a.name}" for a in node.names]
        else:
            continue
        found += [(node.lineno, n) for n in names
                  if n == of or n.startswith(of + ".")]
    return found


@pytest.mark.parametrize("package", LOWER_PACKAGES)
def test_lower_packages_do_not_import_runtime(package):
    """numeric, config, ops, solvers, proto, core and data sit below
    runtime/: a knob resolved in runtime/ and read back from down here is
    how the autotuner put a second source under every decision."""
    files = iter_python_files([os.path.join(REPO, "poseidon_tpu", package)])
    assert files, package
    bad = []
    for path in files:
        rel = os.path.relpath(path, REPO)
        bad += [f"{rel}:{line} imports {name}"
                for line, name in _imports_of(path)
                if name not in _RUNTIME_ALLOWED
                and (rel, name) not in _RUNTIME_DEBTS]
    assert not bad, "\n".join(bad)


def test_layering_walk_sees_function_level_and_relative_imports():
    """The rule must fire on what it exists to stop: numeric.py reaching
    for the engine from inside a function, in every spelling."""
    path = os.path.join(REPO, "poseidon_tpu", "numeric.py")
    src = textwrap.dedent("""
        from .runtime.metrics import log
        import poseidon_tpu.runtime.engine
        def resolve():
            from .runtime.engine import Engine
            from .runtime import spans, compile_cache as cc
            from . import runtime
            from .ops import nn
    """)
    assert [n for _, n in _imports_of(path, src)] == [
        "poseidon_tpu.runtime.metrics.log",
        "poseidon_tpu.runtime.engine",
        "poseidon_tpu.runtime.engine.Engine",
        "poseidon_tpu.runtime.spans",
        "poseidon_tpu.runtime.compile_cache",
        "poseidon_tpu.runtime",
    ]
    deep = os.path.join(REPO, "poseidon_tpu", "ops", "nn.py")
    assert [n for _, n in _imports_of(
        deep, "def f():\n    from ..runtime.tools import x\n")] == [
        "poseidon_tpu.runtime.tools.x"]


def test_core_imports_models_only_from_the_layer_catalog():
    """core/ sits below models/. The layer catalog calls its layers'
    mathematics where it lives today (ROADMAP D21); ``Net``, the remat
    planner and the rest of core/ ask the layers and import no model."""
    files = iter_python_files([os.path.join(REPO, "poseidon_tpu", "core")])
    assert files
    bad = [f"{os.path.relpath(path, REPO)}:{line} imports {name}"
           for path in files if os.path.basename(path) != "layers.py"
           for line, name in _imports_of(path, of="poseidon_tpu.models")]
    assert not bad, "\n".join(bad)
    seen = _imports_of(
        os.path.join(REPO, "poseidon_tpu", "core", "net.py"),
        "def f():\n    from ..models.moe import GROUPED_MATMUL\n",
        of="poseidon_tpu.models")
    assert [n for _, n in seen] == ["poseidon_tpu.models.moe.GROUPED_MATMUL"]


# the layer types whose routes, stats sections, counters and FLOPs their
# classes state (``Layer.kernel_route`` / ``stats_sections`` /
# ``display_counters`` / ``forward_flops``)
_SELF_DESCRIBING = {"POOLING", "LRN", "ATTENTION", "KDA_SCAN", "SSD_SCAN",
                    "MOE", "SOFTMAX"}


def _type_switches(source: str):
    """(line, type name) of every comparison of a ``.TYPE`` attribute (or
    of a name bound to one in the same module, ``t = layer.TYPE``) with one
    of ``_SELF_DESCRIBING``, alone or in a tuple, list or set."""
    import ast
    tree = ast.parse(source)

    def is_type(node):
        return isinstance(node, ast.Attribute) and node.attr == "TYPE"

    aliases = {t.id for node in ast.walk(tree)
               if isinstance(node, ast.Assign) and is_type(node.value)
               for t in node.targets if isinstance(t, ast.Name)}
    found = []
    for node in ast.walk(tree):
        if not isinstance(node, ast.Compare):
            continue
        sides = [node.left] + list(node.comparators)
        if not any(is_type(x) or (isinstance(x, ast.Name)
                                  and x.id in aliases) for x in sides):
            continue
        for side in sides:
            items = side.elts if isinstance(
                side, (ast.Tuple, ast.List, ast.Set)) else [side]
            found += [(node.lineno, c.value) for c in items
                      if isinstance(c, ast.Constant)
                      and c.value in _SELF_DESCRIBING]
    return found


def test_no_module_switches_on_a_self_describing_layer_type():
    """What is true of one layer type lives in its class: outside
    core/layers.py nothing in the package asks a layer whether it is a
    POOLING, LRN, ATTENTION, KDA_SCAN, SSD_SCAN, MOE or SOFTMAX layer. (The
    graph-pattern passes match CONVOLUTION / RELU / INNER_PRODUCT / SLICE /
    SILU_GATE / HDF5_OUTPUT: shapes of the GRAPH, which no one layer
    knows.)"""
    files = iter_python_files([os.path.join(REPO, "poseidon_tpu")])
    bad = []
    for path in files:
        rel = os.path.relpath(path, REPO)
        if rel == os.path.join("poseidon_tpu", "core", "layers.py"):
            continue
        with open(path) as f:
            bad += [f"{rel}:{line} compares a layer's TYPE with {name!r}"
                    for line, name in _type_switches(f.read())]
    assert not bad, "\n".join(bad)
    # the rule fires on the spellings it exists to stop
    assert [n for _, n in _type_switches(textwrap.dedent("""
        def plan(net):
            for layer in net.layers:
                t = layer.TYPE
                if layer.TYPE == "POOLING" and layer.method == "MAX":
                    pass
                elif t in ("SOFTMAX", "SOFTMAX_LOSS"):
                    pass
                elif "MOE" != layer.TYPE or layer.TYPE == "CONVOLUTION":
                    pass
    """))] == ["POOLING", "SOFTMAX", "MOE"]

