"""Jit/dispatch-hygiene lint: host syncs, retrace hazards, f64, scopes.

The step pipeline (PR 5) only overlaps if nothing inside the dispatch
window forces a host<->device round-trip, and the attribution table (PR 7)
only stays honest if the named scopes it joins on survive refactors. Both
properties are lexical — so they are lintable:

- **JIT101 host-sync-in-traced**: an implicit host sync (``.item()``,
  ``np.asarray``/``np.array``, ``jax.device_get``,
  ``.block_until_ready()``, ``float()``/``int()`` on a computed value)
  inside a TRACED function — one decorated with / passed to ``jax.jit``,
  ``jax.grad``, ``jax.vmap``, ``jax.lax.scan`` etc., or nested in one.
  Inside a trace these either fail at trace time or, worse, silently
  constant-fold a device value into the compiled program.
- **JIT102 host-sync-in-window**: the same sync calls inside the engine's
  dispatch window — the configured method set below plus everything they
  reach intra-class and module-level helpers they call directly. A sync
  here serializes the pipelined loop (the regression class
  ``input_stall_ms_per_step`` measures after the fact; this catches it
  before).
- **JIT103 retrace-hazard**: ``jax.jit`` applied inside a loop body or to
  a ``lambda`` — each evaluation makes a fresh wrapper with an empty
  cache, so every call retraces; also jit ``static_argnums``/
  ``static_argnames`` functions whose parameter defaults are unhashable
  (list/dict/set) — the call fails or retraces per step.
- **JIT104 f64-promotion**: explicit float64 dtypes (``np.float64``,
  ``jnp.float64``, ``astype("float64")``, ``dtype=float``) — under
  ``jax_enable_x64=False`` these silently degrade to f32 with a warning
  at best; under x64 they double every byte of the buffer they touch.
- **JIT105 missing-named-scope**: the attribution spine's required
  ``jax.named_scope`` coverage (REQUIRED_SCOPES below). Removing one
  silently reclassifies that phase's device time into the
  ``(unattributed)`` residual row of the per-layer table.
- **JIT106 checkpoint-body-scope**: in REMAT_SCOPE_FILES, a local
  function handed to ``jax.checkpoint``/``jax.remat`` must itself
  contain a ``named_scope`` call. The HBM budget planner (core/remat.py)
  wraps chosen layers' forward bodies in ``jax.checkpoint``; the ops XLA
  RECOMPUTES during backward carry only the scopes inside the
  checkpointed body — a scope left outside it covers the forward pass
  and silently drops the recompute cost into ``(unattributed)``.

**Pallas kernel bodies** (functions passed — directly or through
``functools.partial`` — as the first argument of a ``pl.pallas_call``) are
traced too, so JIT101 covers them, with one carve-out: the ``np.*``
patterns are NOT flagged there. Inside a Mosaic kernel every value is a
Ref or a trace-time constant — ``np.asarray`` on static index math cannot
be a device sync because there is no device value to sync — while
``.item()`` / ``.block_until_ready()`` / ``jax.device_get`` remain real
defects (they cannot lower at all) and still fire.

Pure ``ast``; jax-free at import.
"""

from __future__ import annotations

import ast
from typing import Dict, List, Optional, Sequence, Set, Tuple

from . import Finding, pragma_on_line, relpath

# wrappers whose function argument is traced
TRACING_WRAPPERS = {"jit", "grad", "value_and_grad", "vmap", "pmap",
                    "checkpoint", "remat", "custom_vjp", "custom_jvp",
                    "shard_map", "scan", "while_loop", "fori_loop",
                    "cond", "eval_shape", "make_jaxpr"}

# method-call syncs: x.item(), x.block_until_ready()
SYNC_METHODS = {"item", "block_until_ready"}
# attribute-path syncs rooted at numpy / jax aliases
SYNC_NP_FUNCS = {"asarray", "array"}
SYNC_JAX_FUNCS = {"device_get"}

# The engine's dispatch window: between two hard-sync boundaries these are
# the only frames that run per step, so a host sync in any of them (or in
# what they reach) stalls the pipelined loop. Extend this table when the
# window grows new frames.
WINDOW_METHODS: Dict[str, Set[str]] = {
    "poseidon_tpu/runtime/engine.py": {
        "Engine._dispatch_train_step", "Engine._next_batch",
        "Engine._next_batch_stack", "Engine._absorb",
        "Engine._check_divergence"},
    "poseidon_tpu/runtime/metrics.py": {
        "AsyncScalarFetcher.put", "AsyncScalarFetcher.take_drained"},
    "poseidon_tpu/data/pipeline.py": {"DevicePrefetcher.__next__"},
}

# PR 7's attribution contract: these scope names must keep appearing in
# these modules (prefix match, so f-string suffixes like bucket indices
# are fine). core/net.py is special-cased: the per-layer scope is dynamic
# (jax.named_scope(layer.name)), so the rule requires at least one
# named_scope call with a non-literal argument there.
REQUIRED_SCOPES: Dict[str, Tuple[str, ...]] = {
    "poseidon_tpu/core/arena.py": ("arena_pack", "arena_unpack",
                                   "arena_views", "arena_grads"),
    "poseidon_tpu/solvers/updates.py": ("optimizer_update",),
    "poseidon_tpu/core/net.py": (),
}

# JIT106's scope: files where jax.checkpoint wraps attribution-scoped
# layer bodies (the remat planner's wiring). Extend when another module
# grows checkpointed per-layer forwards.
REMAT_SCOPE_FILES: Set[str] = {"poseidon_tpu/core/net.py"}


def _alias_map(tree: ast.Module) -> Dict[str, str]:
    """{local name: canonical root} for numpy / jax / jax.numpy imports."""
    out: Dict[str, str] = {}
    for n in ast.walk(tree):
        if isinstance(n, ast.Import):
            for a in n.names:
                root = a.name.split(".")[0]
                if root in ("numpy", "jax"):
                    if a.asname:
                        out[a.asname] = (
                            "np" if root == "numpy" else
                            ("jnp" if a.name == "jax.numpy" else
                             ("pallas" if a.name.startswith(
                                 "jax.experimental.pallas") else "jax")))
                    else:
                        # `import jax.numpy` binds only the ROOT name —
                        # mapping 'jax' to jnp would blind the
                        # jax.device_get checks
                        out[root] = "np" if root == "numpy" else "jax"
        elif isinstance(n, ast.ImportFrom) and n.module:
            root = n.module.split(".")[0]
            if root == "jax" and n.module == "jax.numpy":
                for a in n.names:
                    out.setdefault(a.asname or a.name, "jnp_member")
            elif n.module.startswith("jax.experimental"):
                for a in n.names:
                    if a.name == "pallas":     # from jax.experimental ...
                        out[a.asname or a.name] = "pallas"
                    elif a.name == "pallas_call":
                        out[a.asname or a.name] = "pallas_member"
                    elif a.name in TRACING_WRAPPERS:
                        # from jax.experimental.shard_map import shard_map:
                        # still a tracing wrapper — this branch must not
                        # shadow the plain-jax mapping below
                        out[a.asname or a.name] = "jax_member"
            elif root == "jax":
                for a in n.names:
                    if a.name in TRACING_WRAPPERS:
                        out[a.asname or a.name] = "jax_member"
                    elif a.name == "numpy":    # from jax import numpy as jnp
                        out[a.asname or a.name] = "jnp"
            elif root == "numpy":
                for a in n.names:
                    if a.name in SYNC_NP_FUNCS:
                        out[a.asname or a.name] = "np_member"
    return out


def _root_of(node) -> Optional[str]:
    while isinstance(node, ast.Attribute):
        node = node.value
    return node.id if isinstance(node, ast.Name) else None


def _is_const(node) -> bool:
    return isinstance(node, ast.Constant) or (
        isinstance(node, ast.UnaryOp) and _is_const(node.operand))


class _SyncFinder(ast.NodeVisitor):
    """Collect host-sync call sites within one function body.

    ``scalars`` additionally reports ``float()``/``int()`` on computed
    values — meaningful only in HOST code (the dispatch window), where
    they silently block on the device. In traced code they fail loudly at
    trace time, so flagging them there would only re-report what the
    first compile already screams about."""

    def __init__(self, aliases: Dict[str, str], scalars: bool = False,
                 descend: bool = True):
        self.aliases = aliases
        self.scalars = scalars
        self.descend = descend
        self.hits: List[Tuple[int, str]] = []

    def visit_Call(self, node: ast.Call) -> None:
        f = node.func
        if isinstance(f, ast.Attribute):
            if f.attr in SYNC_METHODS and not node.args:
                self.hits.append((node.lineno, f".{f.attr}()"))
            else:
                root = _root_of(f)
                canon = self.aliases.get(root or "", "")
                if canon == "np" and f.attr in SYNC_NP_FUNCS:
                    self.hits.append((node.lineno, f"np.{f.attr}"))
                elif canon == "jax" and f.attr in SYNC_JAX_FUNCS:
                    self.hits.append((node.lineno, f"jax.{f.attr}"))
        elif isinstance(f, ast.Name):
            if self.scalars and f.id in ("float", "int") and \
                    len(node.args) == 1 and not _is_const(node.args[0]):
                self.hits.append((node.lineno, f"{f.id}()"))
            elif self.aliases.get(f.id) == "np_member":
                self.hits.append((node.lineno, f.id))
        self.generic_visit(node)

    # JIT101 scans each nested def under its own qualname (the nesting
    # closure puts it in the traced set), so it must NOT also descend
    # here — the same sync would land twice under two fingerprints. The
    # JIT102 reachability walk never indexes nested defs, so it keeps
    # descending.
    def visit_FunctionDef(self, node):
        if self.descend:
            self.generic_visit(node)

    visit_AsyncFunctionDef = visit_FunctionDef


def _fn_pragma(lines: Sequence[str], node, rule: str) -> bool:
    """``# static-ok: RULE`` on (or just above) a ``def`` line suppresses
    the rule for the whole function — for designated sync points whose
    docstring already explains itself (``scalar_rows`` IS where the
    pipeline waits)."""
    return any(pragma_on_line(lines, ln, rule)
               for ln in (node.lineno, node.lineno - 1))


def _function_index(tree: ast.Module) -> Dict[str, ast.AST]:
    """{qualname: FunctionDef} with Class.method / fn.<local> nesting."""
    out: Dict[str, ast.AST] = {}

    def walk(node, prefix):
        for ch in ast.iter_child_nodes(node):
            if isinstance(ch, (ast.FunctionDef, ast.AsyncFunctionDef)):
                q = f"{prefix}{ch.name}"
                out[q] = ch
                walk(ch, q + ".")
            elif isinstance(ch, ast.ClassDef):
                walk(ch, f"{prefix}{ch.name}.")
            else:
                walk(ch, prefix)

    walk(tree, "")
    return out


def _traced_functions(tree: ast.Module, aliases: Dict[str, str],
                      index: Dict[str, ast.AST]) -> Set[str]:
    """Qualnames of functions that run under a jax trace: decorated with a
    tracing wrapper, passed to one by (local) name, or nested in one."""
    traced: Set[str] = set()
    by_node = {id(n): q for q, n in index.items()}

    def wrapper_name(func) -> Optional[str]:
        # jax.jit / jit / partial(jax.jit, ...) / functools.partial(jit)
        if isinstance(func, ast.Attribute):
            if func.attr in TRACING_WRAPPERS:
                root = _root_of(func)
                if aliases.get(root or "") in ("jax", "jnp") or \
                        root in ("lax", "jax"):
                    return func.attr
            return None
        if isinstance(func, ast.Name):
            if aliases.get(func.id) == "jax_member" or \
                    func.id in ("jit", "shard_map"):
                return func.id
        return None

    # decorators
    for q, node in index.items():
        for dec in getattr(node, "decorator_list", []):
            target = dec.func if isinstance(dec, ast.Call) else dec
            if isinstance(target, ast.Call):  # partial(jax.jit, ...)
                target = target.func
            if wrapper_name(target) is not None:
                traced.add(q)
            elif isinstance(dec, ast.Call) and any(
                    wrapper_name(a) for a in dec.args
                    if isinstance(a, (ast.Attribute, ast.Name))):
                traced.add(q)       # partial(jax.jit, ...) as a Call dec

    # call sites: jax.jit(f) where f is a Name resolving to a sibling def
    class V(ast.NodeVisitor):
        def __init__(self):
            self.scope: List[str] = []

        def visit_FunctionDef(self, node):
            self.scope.append(by_node.get(id(node), node.name))
            self.generic_visit(node)
            self.scope.pop()

        visit_AsyncFunctionDef = visit_FunctionDef

        def visit_Call(self, node):
            if wrapper_name(node.func) is not None:
                # fn position varies by wrapper: jit/scan at args[0],
                # while_loop cond/body at [0]/[1], cond branches at
                # [1]/[2], fori_loop body at [2]
                for arg in node.args[:3]:
                    if isinstance(arg, ast.Name):
                        # resolve innermost-scope-first; scope entries
                        # are already full qualnames, so each candidate
                        # is one enclosing qualname + the bare name
                        for enclosing in reversed(self.scope):
                            q = f"{enclosing}.{arg.id}"
                            if q in index:
                                traced.add(q)
                                break
                        else:
                            if arg.id in index:
                                traced.add(arg.id)
                    elif (isinstance(arg, ast.Attribute)
                          and isinstance(arg.value, ast.Name)
                          and arg.value.id == "self"):
                        # jax.jit(self._fwd): `self` binds to the class
                        # the enclosing method hangs off, so peel
                        # trailing qualname segments until a sibling
                        # matches (Class.method.local -> Class._fwd)
                        for enclosing in reversed(self.scope):
                            parts = enclosing.split(".")
                            hit = next(
                                (q for k in range(len(parts) - 1, 0, -1)
                                 if (q := ".".join(parts[:k] + [arg.attr]))
                                 in index), None)
                            if hit is not None:
                                traced.add(hit)
                                break
            self.generic_visit(node)

    V().visit(tree)
    # nesting closure: everything defined inside a traced function traces
    for q in list(index):
        for t in list(traced):
            if q.startswith(t + "."):
                traced.add(q)
    return traced


def _pallas_kernel_bodies(tree: ast.Module, aliases: Dict[str, str],
                          index: Dict[str, ast.AST]) -> Set[str]:
    """Qualnames of functions handed to ``pl.pallas_call`` as the kernel —
    directly, or wrapped in ``functools.partial(kernel, ...)`` (the
    repo's static-parameter idiom)."""
    bodies: Set[str] = set()

    def is_pallas_call(func) -> bool:
        if isinstance(func, ast.Attribute) and func.attr == "pallas_call":
            return aliases.get(_root_of(func) or "") == "pallas"
        return isinstance(func, ast.Name) and \
            aliases.get(func.id) == "pallas_member"

    for n in ast.walk(tree):
        if not (isinstance(n, ast.Call) and is_pallas_call(n.func)
                and n.args):
            continue
        k = n.args[0]
        if isinstance(k, ast.Call):            # functools.partial(kernel, …)
            f = k.func
            is_partial = (isinstance(f, ast.Name) and f.id == "partial") \
                or (isinstance(f, ast.Attribute) and f.attr == "partial")
            if is_partial and k.args:
                k = k.args[0]
        if isinstance(k, ast.Name) and k.id in index:
            bodies.add(k.id)
    return bodies


def _named_scope_strings(tree: ast.Module) -> Tuple[Set[str], bool]:
    """(literal/prefix scope names, saw a dynamic-arg named_scope call)."""
    names: Set[str] = set()
    dynamic = False
    for n in ast.walk(tree):
        if not (isinstance(n, ast.Call) and n.args and (
                (isinstance(n.func, ast.Attribute)
                 and n.func.attr == "named_scope")
                or (isinstance(n.func, ast.Name)
                    and n.func.id == "named_scope"))):
            continue
        a = n.args[0]
        if isinstance(a, ast.Constant) and isinstance(a.value, str):
            names.add(a.value)
        elif isinstance(a, ast.JoinedStr):
            if a.values and isinstance(a.values[0], ast.Constant):
                names.add(str(a.values[0].value))
            else:
                dynamic = True
        else:
            dynamic = True
    return names, dynamic


def lint_file(path: str, source: Optional[str] = None,
              tree: Optional[ast.Module] = None) -> List[Finding]:
    if source is None:
        with open(path, encoding="utf-8") as f:
            source = f.read()
    if tree is None:                 # run_lints hands in a shared parse
        try:
            tree = ast.parse(source)
        except SyntaxError:
            return []        # threads.py already reports THR000
    rel = relpath(path)
    lines = source.splitlines()
    aliases = _alias_map(tree)
    index = _function_index(tree)
    findings: List[Finding] = []

    # ---- JIT101: host sync inside traced functions -------------------- #
    pallas_bodies = _pallas_kernel_bodies(tree, aliases, index)
    for q in sorted(_traced_functions(tree, aliases, index)
                    | pallas_bodies):
        node = index[q]
        if _fn_pragma(lines, node, "JIT101"):
            continue
        body = ast.Module(body=list(node.body), type_ignores=[])
        sf = _SyncFinder(aliases, descend=False)
        sf.visit(body)
        in_kernel = q in pallas_bodies
        for line, what in sf.hits:
            if in_kernel and (what.startswith("np.")
                              or aliases.get(what) == "np_member"):
                # Mosaic kernel body: np.* on static index math is
                # trace-time constant folding, not a host sync — there is
                # no device value inside the kernel to sync on. The
                # method/jax syncs below stay flagged (they cannot lower).
                continue
            where = ("Pallas kernel body" if in_kernel
                     else "traced function")
            findings.append(Finding(
                rule="JIT101", path=rel, line=line, symbol=q, key=what,
                message=f"{what} inside {where} {q!r}: a host "
                        f"sync here either fails at trace time or "
                        f"constant-folds a device value into the "
                        f"compiled program"))

    # ---- JIT102: host sync inside the dispatch window ------------------ #
    window = WINDOW_METHODS.get(rel)
    if window:
        # a stale entry must SURFACE, not silently blind the rule (the
        # JIT105 pattern): a renamed window method with no finding here
        # would let host syncs ship unflagged forever after
        for q in sorted(window):
            if q not in index:
                findings.append(Finding(
                    rule="JIT102", path=rel, line=1, symbol="<module>",
                    key=f"missing:{q}",
                    message=f"configured dispatch-window method {q!r} no "
                            f"longer resolves — update WINDOW_METHODS or "
                            f"the host-sync gate goes blind for it"))
        reach: Set[str] = set()
        work = [q for q in window if q in index]
        while work:
            q = work.pop()
            if q in reach:
                continue
            reach.add(q)
            cls_prefix = q.rsplit(".", 1)[0] + "." if "." in q else ""
            for n in ast.walk(index[q]):
                if not isinstance(n, ast.Call):
                    continue
                callee = None
                if isinstance(n.func, ast.Attribute) and \
                        isinstance(n.func.value, ast.Name) and \
                        n.func.value.id == "self":
                    callee = cls_prefix + n.func.attr
                elif isinstance(n.func, ast.Name):
                    callee = n.func.id          # module-level helper
                if callee and callee in index and callee not in reach:
                    work.append(callee)
        for q in sorted(reach):
            node = index[q]
            if _fn_pragma(lines, node, "JIT102"):
                continue
            sf = _SyncFinder(aliases, scalars=True)
            sf.visit(ast.Module(body=list(node.body), type_ignores=[]))
            for line, what in sf.hits:
                findings.append(Finding(
                    rule="JIT102", path=rel, line=line, symbol=q, key=what,
                    message=f"{what} reachable inside the dispatch window "
                            f"(via {q!r}): a host sync here serializes "
                            f"the pipelined train loop"))

    # ---- JIT103: retrace hazards --------------------------------------- #
    class LoopJit(ast.NodeVisitor):
        def __init__(self):
            self.loops = 0

        def _jit_call(self, node) -> bool:
            f = node.func
            if isinstance(f, ast.Attribute) and f.attr == "jit" and \
                    aliases.get(_root_of(f) or "") == "jax":
                return True
            return isinstance(f, ast.Name) and aliases.get(f.id) == \
                "jax_member" and f.id == "jit"

        def visit_For(self, node):
            self.loops += 1
            self.generic_visit(node)
            self.loops -= 1

        visit_While = visit_For

        def visit_Call(self, node):
            # jax.jit(f)(x) — fresh wrapper built AND invoked in place:
            # inside a loop every iteration retraces (a stored wrapper,
            # or .lower()/.compile() AOT use, is deliberate and cached)
            if isinstance(node.func, ast.Call) and \
                    self._jit_call(node.func) and self.loops:
                findings.append(Finding(
                    rule="JIT103", path=rel, line=node.lineno,
                    symbol="<loop>", key="jit-in-loop",
                    message="jax.jit(f)(...) built and invoked inside a "
                            "loop body: each iteration makes a fresh "
                            "wrapper with an empty cache and retraces"))
            if self._jit_call(node):
                if self.loops and node.args and \
                        isinstance(node.args[0], ast.Lambda):
                    findings.append(Finding(
                        rule="JIT103", path=rel, line=node.lineno,
                        symbol="<lambda>", key="jit-lambda",
                        message="jax.jit over a lambda inside a loop: "
                                "the wrapper (and its trace cache) is "
                                "rebuilt every iteration"))
                for kw in node.keywords:
                    if kw.arg in ("static_argnums", "static_argnames"):
                        fn = node.args[0] if node.args else None
                        if isinstance(fn, ast.Name) and fn.id in index:
                            fdef = index[fn.id]
                            for d in getattr(fdef.args, "defaults", []):
                                if isinstance(d, (ast.List, ast.Dict,
                                                  ast.Set)):
                                    findings.append(Finding(
                                        rule="JIT103", path=rel,
                                        line=node.lineno, symbol=fn.id,
                                        key="unhashable-static",
                                        message="static arg with an "
                                                "unhashable (list/dict/"
                                                "set) default: every "
                                                "call re-traces or "
                                                "fails to hash"))
            self.generic_visit(node)

    LoopJit().visit(tree)

    # ---- JIT104: f64 promotion ----------------------------------------- #
    for n in ast.walk(tree):
        if isinstance(n, ast.Attribute) and n.attr == "float64" and \
                aliases.get(_root_of(n) or "") in ("np", "jnp"):
            findings.append(Finding(
                rule="JIT104", path=rel, line=n.lineno, symbol="<module>",
                key="float64",
                message="explicit float64 dtype: silently degrades to "
                        "f32 without x64 mode, doubles the buffer with "
                        "it"))
        elif isinstance(n, ast.Call) and isinstance(n.func, ast.Attribute) \
                and n.func.attr == "astype" and n.args and \
                isinstance(n.args[0], ast.Constant) and \
                n.args[0].value in ("float64", "f64", "double"):
            findings.append(Finding(
                rule="JIT104", path=rel, line=n.lineno, symbol="<module>",
                key="astype-f64",
                message="astype('float64'): accidental double-precision "
                        "promotion"))
        elif isinstance(n, ast.keyword) and n.arg == "dtype" and \
                isinstance(n.value, ast.Name) and n.value.id == "float":
            findings.append(Finding(
                rule="JIT104", path=rel, line=n.value.lineno,
                symbol="<module>", key="dtype-float",
                message="dtype=float is float64 on the host: an "
                        "accidental f64 wire into the traced program"))

    # ---- JIT105: required named_scope coverage ------------------------- #
    req = REQUIRED_SCOPES.get(rel)
    if req is not None:
        present, dynamic = _named_scope_strings(tree)
        if rel.endswith("core/net.py"):
            if not dynamic:
                findings.append(Finding(
                    rule="JIT105", path=rel, line=1, symbol="<module>",
                    key="layer-scope",
                    message="the per-layer jax.named_scope(layer.name) "
                            "wrapper is gone: per-layer device-time "
                            "attribution joins on it"))
        for name in req:
            if not any(p == name or p.startswith(name) for p in present):
                findings.append(Finding(
                    rule="JIT105", path=rel, line=1, symbol="<module>",
                    key=name,
                    message=f"required named_scope {name!r} missing: its "
                            f"device time falls into the attribution "
                            f"table's (unattributed) residual"))

    # ---- JIT106: checkpointed layer bodies keep their named_scope ------ #
    if rel in REMAT_SCOPE_FILES:
        def _has_named_scope(fdef) -> bool:
            return any(
                isinstance(c, ast.Call) and (
                    (isinstance(c.func, ast.Attribute)
                     and c.func.attr == "named_scope")
                    or (isinstance(c.func, ast.Name)
                        and c.func.id == "named_scope"))
                for c in ast.walk(fdef))

        for n in ast.walk(tree):
            if not (isinstance(n, ast.Call) and n.args):
                continue
            f = n.func
            is_ckpt = ((isinstance(f, ast.Attribute)
                        and f.attr in ("checkpoint", "remat")
                        and aliases.get(_root_of(f) or "") == "jax")
                       or (isinstance(f, ast.Name)
                           and f.id in ("checkpoint", "remat")
                           and aliases.get(f.id) == "jax_member"))
            if not is_ckpt or not isinstance(n.args[0], ast.Name):
                continue
            name = n.args[0].id
            # innermost-first resolution against the qualname index; a
            # name that resolves to no local def (e.g. a parameter) is
            # out of this rule's lexical reach
            cands = sorted((q for q in index
                            if q == name or q.endswith("." + name)),
                           key=len, reverse=True)
            if not cands:
                continue
            fdef = index[cands[0]]
            if not _has_named_scope(fdef):
                findings.append(Finding(
                    rule="JIT106", path=rel, line=n.lineno,
                    symbol=cands[0], key=name,
                    message=f"checkpointed body {name!r} has no "
                            f"named_scope inside it: the ops recomputed "
                            f"during backward carry only the scopes "
                            f"INSIDE the jax.checkpoint body, so the "
                            f"layer's recompute time falls into the "
                            f"attribution table's (unattributed) "
                            f"residual"))

    return findings


def required_scope_files() -> Sequence[str]:
    return tuple(REQUIRED_SCOPES)
