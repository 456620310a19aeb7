"""Per-layer device-time attribution from a ``jax.profiler`` trace.

The measurement ROADMAP item 2 is blocked on: which layers actually spend
the step's device time (AlexNet sits at 4.1% MFU and nobody can name the
top-3 sinks). The pipeline:

1. ``core/net.py`` wraps every layer's apply in ``jax.named_scope``, so
   each HLO instruction's ``op_name`` metadata carries the layer path —
   forward ops as ``.../jvp(conv1)/...``, backward ops as
   ``.../transpose(jvp(conv1))/...`` (autodiff preserves the scope). The
   arena/update phases (core/arena.py, solvers/updates.py) are scoped the
   same way.
2. A profiled step dumps an xplane protobuf, read with
   ``jax.profiler.ProfileData`` (``load_trace_events``) — the one trace
   reader of the repository; the benchmark's ``device_trace.py`` uses the
   same class.
3. Each op event joins back to its layer through the COMPILED module text
   (``compiled.as_text()``): instruction name -> op_name metadata ->
   layer scope (``hlo_scope_map``). This works identically on the CPU
   thunk runtime (events per HLO op on host threads) and the TPU device
   planes, because both name events after HLO instructions. The Engine
   publishes that map for the step it runs as stats section
   ``step_scopes`` (``step_scopes`` below), which is what the benchmark's
   per-pass and per-layer-type metrics join a device trace with.
4. ``attribute`` folds event durations into a per-layer table — fwd/bwd
   ms, %-of-traced-op-time, analytic FLOPs (``Net.cost_table``), arithmetic
   intensity, per-layer MFU against a peak — with an ``(unattributed)``
   residual row so coverage is honest: named rows + residual always sum
   to the traced op time.

Everything here is host-side postprocessing: nothing runs inside a timed
loop (``measure_then_trace`` pins the discipline — timing first, trace
capture after).
"""

from __future__ import annotations

import glob
import math
import os
import re
from typing import Dict, List, Optional, Sequence, Tuple

__all__ = [
    "load_trace_events", "trace_events_from_xspace", "hlo_scope_map",
    "step_scopes", "param_relayouts", "scope_of", "comm_axis_of",
    "attribute", "measure_then_trace",
]


# --------------------------------------------------------------------------- #
# trace loading (jax.profiler.ProfileData — the benchmark's reader too)
# --------------------------------------------------------------------------- #

def _flatten(profile) -> List[Dict]:
    """ProfileData -> op-level events ``[{name, dur_us, t0_us, plane, line,
    stats}]``, ``t0_us`` on the trace's own clock."""
    return [{"name": ev.name,
             "dur_us": ev.duration_ns / 1e3,
             "t0_us": ev.start_ns / 1e3,
             "plane": plane.name,
             "line": line.name,
             "stats": dict(ev.stats)}
            for plane in profile.planes
            for line in plane.lines
            for ev in line.events]


def trace_events_from_xspace(data: bytes) -> List[Dict]:
    """The events of one serialized XSpace (the bytes of an ``.xplane.pb``)."""
    from jax.profiler import ProfileData
    return _flatten(ProfileData.from_serialized_xspace(data))


def load_trace_events(trace_dir: str) -> List[Dict]:
    """Flatten a ``jax.profiler`` dump — the ``*.xplane.pb`` files of the
    newest run under ``trace_dir`` — into op-level events."""
    from jax.profiler import ProfileData
    runs = sorted(glob.glob(os.path.join(trace_dir, "plugins", "profile",
                                         "*")))
    run = runs[-1] if runs else trace_dir
    out: List[Dict] = []
    for pb in sorted(glob.glob(os.path.join(run, "*.xplane.pb"))):
        out.extend(_flatten(ProfileData.from_file(pb)))
    return out


# --------------------------------------------------------------------------- #
# HLO instruction -> layer scope (the join key)
# --------------------------------------------------------------------------- #

# transform wrappers that PRESERVE the scope they wrap (peel to the
# inside); anything else in wrapper(..) form — jit(fn), pjit(fn), named
# computation frames — is a CALL frame whose argument is a function name,
# not a scope, and must be dropped (jit(loss) is the traced function
# 'loss', not the layer 'loss')
_PEELABLE = frozenset({
    "jvp", "transpose", "vmap", "remat", "rematted_computation",
    "checkpoint", "custom_jvp", "custom_vjp", "custom_jvp_call",
    "custom_vjp_call",
})

_WRAP_OPEN = re.compile(r"^([\w.\-]+)\(")


def _scope_components(op_name: str) -> List[str]:
    """Path components with wrappers peeled — aware that a SLASHED scope
    name splits a wrapper across components: in
    'transpose(jvp(inception_3a/3x3))/conv', the wrapper opens in the
    'transpose(jvp(inception_3a' component and closes two components
    later, so per-component peeling (the old ``_peel``) mangled every
    wrapped GoogLeNet scope into 'jvp(inception_3a' + '3x3)' and the
    whole model fell into the residual row. Leading PEELABLE wrapper
    opens are stripped wherever they appear, call frames (jit(fn)) drop
    their component entirely, and trailing close-parens — ours or an
    enclosing component's — are shed."""
    comps: List[str] = []
    for comp in op_name.split("/"):
        while True:
            m = _WRAP_OPEN.match(comp)
            if not m:
                break
            if m.group(1) in _PEELABLE:
                comp = comp[m.end():]
            else:
                comp = ""       # call frame: not a scope, drop it
                break
        comp = comp.rstrip(")")
        if comp:
            comps.append(comp)
    return comps


def unit_residuals(jaxpr, plan, batch_args: Sequence[int] = ()
                   ) -> Tuple[List[Tuple[str, int, int]], int]:
    """What the units of ``plan`` (a ``core/remat.RematPlan``) hold from the
    forward pass of a traced gradient for their replays, read off what the
    replays read: ``(named, stored)``. A replay is a ``remat2`` equation of
    the backward pass (``differentiated``; a checkpoint inside a layer
    leaves one in the forward pass too, which is no replay).

    ``named`` is ``(name, unit, bytes)`` of every value a unit names
    (``checkpoint_name``, a name in ``plan.keep``) that a replay reads:
    what the unit KEEPS. A name nothing reads again costs nothing (jax
    stores no such value) and is not listed; a replay names what it makes
    again and keeps nothing. ``unit`` indexes ``plan.units``; the value's
    layer is read off its scope, as a device trace's instructions are.

    ``stored`` is the bytes of the other values the replays read that the
    forward pass made from the batch (``batch_args``: which of the jaxpr's
    arguments are the batch's): the units' stored inputs. A cotangent (made
    after the first replay) is none; a value made from the parameters alone
    (a cast weight) is left out, since the compiler may never write it; so
    is what a nested call hides. Both numbers are floors."""
    import jax
    from jax.extend.core import Var
    unit_of = {layer: at for at, unit in enumerate(plan.units)
               for layer in ((unit,) if isinstance(unit, str) else unit)}
    found: List[Tuple[str, int, int]] = []
    stored = 0

    def nbytes(var) -> int:
        return var.aval.size * var.aval.dtype.itemsize

    def replay(eqn) -> bool:
        return eqn.primitive.name == "remat2" \
            and eqn.params.get("differentiated", True)

    def walk(inner, from_batch) -> Dict:
        """-> {a value of this jaxpr that carries a name: its entry}."""
        nonlocal stored
        named: Dict = {}
        forward = set()                     # made before the first replay
        from_batch = set(from_batch)
        reads = {v for eqn in inner.eqns if replay(eqn)
                 for v in eqn.invars if isinstance(v, Var)}
        backward = False
        for eqn in inner.eqns:
            if replay(eqn):
                backward = True
                continue
            if not backward:
                forward.update(eqn.outvars)
            args = [v for v in eqn.invars if isinstance(v, Var)]
            if any(v in from_batch for v in args):
                from_batch.update(eqn.outvars)
            if eqn.primitive.name == "reduce_precision" \
                    and args and args[0] in named:
                # jax's guard between a kept value and its use, a no-op
                named[eqn.outvars[0]] = named.pop(args[0])
            if eqn.primitive.name == "name" \
                    and eqn.params["name"] in plan.keep:
                unit = next((unit_of[c] for c in _scope_components(
                    str(eqn.source_info.name_stack)) if c in unit_of), None)
                if unit is not None:
                    named[eqn.outvars[0]] = (eqn.params["name"], unit,
                                             nbytes(eqn.outvars[0]))
            for sub in jax.core.jaxprs_in_params(eqn.params):
                same = len(sub.invars) == len(eqn.invars)
                inside = walk(sub, [i for i, o in zip(sub.invars, eqn.invars)
                                    if isinstance(o, Var)
                                    and o in from_batch] if same else [])
                # a name given inside a call rides the call's result
                if len(sub.outvars) == len(eqn.outvars):
                    named.update((out, inside[o]) for o, out in zip(
                        sub.outvars, eqn.outvars)
                        if isinstance(o, Var) and o in inside)
        found.extend(entry for var, entry in named.items() if var in reads)
        stored += sum(
            nbytes(v) for v in reads
            if v in forward and v in from_batch and v not in named)
        return named

    top = jaxpr.jaxpr
    walk(top, [top.invars[i] for i in batch_args])
    return found, stored


# collective named scopes emitted by the comm machinery (spmd.py mesh
# collectives, the SSP boundary's delta buckets): each carries its mesh
# axis in the name, so a profiled step attributes comm time PER AXIS
# instead of lumping it into the residual row. Matched as whole path
# components.
COMM_SCOPE_RE = re.compile(
    r"^(grad_rs_bucket\d+|grad_ar_bucket\d+"
    r"|param_ag_bucket\d+|hist_ag_bucket\d+|delta_rs_bucket\d+"
    r"|delta_ar_bucket\d+|delta_ag_bucket\d+"
    r"|tp_fwd_[\w.\-]+|tp_dx_[\w.\-]+"
    r"|grad_tp_[\w.\-]+|grad_fused_[\w.\-]+)$")

_COMM_AXIS_PREFIX = (
    ("grad_rs_bucket", "fsdp"), ("param_ag_bucket", "fsdp"),
    ("hist_ag_bucket", "fsdp"), ("delta_rs_bucket", "fsdp"),
    ("delta_ag_bucket", "fsdp"), ("grad_ar_bucket", "data"),
    ("delta_ar_bucket", "data"),
    ("tp_fwd_", "tp"), ("tp_dx_", "tp"),
)


def comm_axis_of(scope: str) -> Optional[str]:
    """Mesh axis a comm scope's collective rides, or None for non-comm
    scopes. The hierarchical per-leaf psums carry the axis as a suffix
    (``grad_tp_<layer>_<param>_fsdp`` / ``_data``)."""
    for prefix, axis in _COMM_AXIS_PREFIX:
        if scope.startswith(prefix):
            return axis
    if scope.startswith(("grad_tp_", "grad_fused_")):
        if scope.endswith("_fsdp"):
            return "fsdp"
        if scope.endswith("_data"):
            return "data"
    return None


class _ScopeIndex:
    """The layer names and extra scopes of one module, prepared once for
    the lookups of every instruction: layers keyed by their first path
    component (longest first under a key), lookups memoized by op_name —
    a compiled step repeats a few hundred op_names over thousands of
    instructions."""

    def __init__(self, layer_names, extra_scopes=()):
        self._by_first: Dict[str, List[Tuple[Tuple[str, ...], str]]] = {}
        for name in layer_names:
            parts = tuple(name.split("/"))
            self._by_first.setdefault(parts[0], []).append((parts, name))
        for cands in self._by_first.values():
            cands.sort(key=lambda c: -len(c[0]))
        self._extra = tuple(sorted(extra_scopes))
        self._memo: Dict[str, Tuple[Optional[str], Optional[str]]] = {}

    def lookup(self, op_name: str):
        hit = self._memo.get(op_name)
        if hit is None:
            hit = self._memo[op_name] = self._find(op_name)
        return hit

    def _find(self, op_name: str):
        comps = tuple(_scope_components(op_name))
        best: Tuple[Tuple[str, ...], Optional[str]] = ((), None)
        for i, comp in enumerate(comps):
            for parts, name in self._by_first.get(comp, ()):
                if len(parts) <= len(best[0]):
                    break               # longest first: nothing better here
                if comps[i:i + len(parts)] == parts:
                    best = (parts, name)
                    break
        if best[1] is not None:
            return best[1], ("bwd" if "transpose(" in op_name else "fwd")
        for c in comps:
            if COMM_SCOPE_RE.match(c):
                return c, "misc"
        joined = "/".join(comps)
        for extra in self._extra:
            if extra in joined:
                return extra, "misc"
        return None, None


def scope_of(op_name: str, layer_names, extra_scopes=frozenset()):
    """(scope, phase) for one op_name metadata path, or (None, None).

    ``layer_names`` may contain '/' (GoogLeNet's inception blobs), so the
    peeled path components are matched against each layer's own component
    sequence — the layer of most components wins, then the earliest in the
    path. Phase is 'bwd' when the path went through an autodiff transpose,
    else 'fwd'; extra (non-layer) scopes — arena/update phases — report
    'misc', and the comm machinery's per-bucket/per-axis collective scopes
    (``COMM_SCOPE_RE``) are recognized unconditionally so comm time
    lands in named per-axis rows rather than the residual. For many
    lookups against one set of names, ``hlo_scope_map`` prepares the names
    once."""
    return _ScopeIndex(layer_names, extra_scopes).lookup(op_name)


_COMP_HDR = re.compile(r"^(?:ENTRY\s+)?%([\w.\-]+)\s*\(")
_INST = re.compile(r"^(ROOT\s+)?%([\w.\-]+)\s*=")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_CALLEE = re.compile(r"(?:calls|to_apply|body|condition)=%([\w.\-]+)")
_REF = re.compile(r"%([\w.\-]+)")


def hlo_scope_map(hlo_text: str, layer_names,
                  extra_scopes=frozenset()) -> Dict[str, Tuple[str, str]]:
    """Compiled-module text -> {instruction_name: (scope, phase)}.

    Trace events are named after HLO instructions (CPU thunks and TPU
    device lines alike), and instructions carry their source scope in
    ``op_name`` metadata; this is the whole join. Two wrinkles make it a
    small graph problem instead of one regex pass: XLA:CPU wraps
    multi-threaded kernels in metadata-less ``call``s to ``%parallel_*``
    computations, and parallelized fusion clones lose their own metadata
    — in both cases the scope lives on the instructions INSIDE the called
    computation. So: collect per-instruction direct scopes, then resolve
    call/fusion/while instructions through their callee computations
    (root's scope, else the members' majority) to a fixpoint.
    Instructions that still name no known scope are simply absent — they
    fall into the residual row. One pass over the text, the names prepared
    once (``_ScopeIndex``)."""
    return _resolve_scopes(hlo_text,
                           _ScopeIndex(layer_names, extra_scopes))[0]


# scopes outside the layer graph that a train step carries (core/arena.py,
# solvers/updates.py), and the type each reports in ``step_scopes``
STEP_EXTRA_SCOPES = {"optimizer_update": "update", "arena_pack": "arena",
                     "arena_unpack": "arena", "arena_views": "arena",
                     "arena_grads": "arena"}


def step_scopes(hlo_text: str, net) -> Dict:
    """The stats section ``step_scopes`` of one compiled train step:
    ``ops`` = {instruction: "<scope>|<phase>"} for every instruction that
    can execute as an operation of its own (fusion bodies are left out: a
    trace names the fusion), ``types`` = {scope: the layer's TYPE, or
    "update" / "arena" / "sync" for the scopes around the layer graph},
    ``recomputed`` = the mapped instructions that a ``jax.checkpoint``
    replays during backward (their phase reads ``bwd``, where they run:
    this list tells a forward op run a second time from backward's own
    products), and how many of those ``instructions`` are ``mapped``."""
    layer_types = {layer.name: layer.TYPE for layer in net.layers}
    resolved, comp_insts, fusion_bodies = _resolve_scopes(
        hlo_text, _ScopeIndex(layer_types, STEP_EXTRA_SCOPES))
    executed = [i for comp, insts in comp_insts.items()
                if comp not in fusion_bodies for i in insts]
    mapped = {i: resolved[i] for i in executed if i in resolved}
    types = {scope: (layer_types.get(scope) or STEP_EXTRA_SCOPES.get(scope)
                     or "sync")
             for scope in {scope for scope, _ in mapped.values()}}
    replayed = {m.group(2) for m in map(_INST.match, (
        line.strip() for line in hlo_text.splitlines()
        if "/rematted_computation/" in line)) if m}
    return {"ops": {i: f"{scope}|{phase}"
                    for i, (scope, phase) in mapped.items()},
            "types": types,
            "recomputed": sorted(i for i in mapped if i in replayed),
            "instructions": len(executed), "mapped": len(mapped)}


_ENTRY_COPY = re.compile(
    r"(?:ROOT\s+)?%([\w.\-]+) = (\w+)\[([\d,]*)\]\S* copy\(%([\w.\-]+)\)")


def param_relayouts(hlo_text: str, min_bytes: int = 1 << 20) -> Dict:
    """``{"copies": n, "mb": x}``: the ``copy`` instructions of the entry
    computation, of ``min_bytes`` or more, that read an entry parameter or
    whose result the root returns, and the megabytes they write. A train
    step's parameters and results are the weights and the solver's history,
    so such a copy is the compiler moving a leaf into the layout some
    consumer wants and back, every step: OLMoE's step had twelve of 537 MB
    around two expert stacks' Adam fusions until the weight gradient came in
    the stored orientation (PR 30). A fact of the compiled step (stats
    section ``compiled_step``), read from its text, never a time."""
    from .hlo_comm import _DTYPE_BYTES
    params, copies, returned = set(), [], set()
    entry = False
    for line in hlo_text.splitlines():
        if not line[:1].isspace():
            entry = line.startswith("ENTRY ")
            continue
        if not entry:
            continue
        ls = line.lstrip()
        if ls.startswith("ROOT "):
            returned.update(_REF.findall(ls))   # its own name among them
        if " parameter(" in ls:
            params.add(_INST.match(ls).group(2))
        m = _ENTRY_COPY.match(ls) if " copy(" in ls else None
        if m:
            name, dtype, dims, operand = m.groups()
            size = _DTYPE_BYTES.get(dtype, 4) * math.prod(
                int(d) for d in dims.split(",") if d)
            if size >= min_bytes:
                copies.append((name, operand, size))
    sizes = [size for name, operand, size in copies
             if operand in params or name in returned]
    return {"copies": len(sizes), "mb": round(sum(sizes) / 1e6, 1)}


def _resolve_scopes(hlo_text: str, index: "_ScopeIndex"):
    """``(resolved, comp_insts, fusion_bodies)``: {instruction: (scope,
    phase)}, {computation: [its instructions]} and the names of the
    computations that are bodies of ``fusion`` instructions."""
    resolved: Dict[str, Tuple[str, str]] = {}
    direct: Dict[str, Tuple[str, str]] = {}   # from own metadata only
    inst_callees: Dict[str, List[str]] = {}
    operand_users: Dict[str, List[str]] = {}  # operand -> [user insts]
    comp_insts: Dict[str, List[str]] = {}
    comp_root: Dict[str, str] = {}
    fusion_bodies = set()
    comp = None
    for line in hlo_text.splitlines():
        if line and not line[0].isspace():
            m = _COMP_HDR.match(line)
            comp = m.group(1) if m else comp
            continue
        ls = line.strip()
        m = _INST.match(ls)
        if not m:
            continue
        inst = m.group(2)
        rhs = ls.split("=", 1)[1]
        om = _OP_NAME.search(ls)
        if om and inst not in resolved:
            scope, phase = index.lookup(om.group(1))
            if scope is not None:
                resolved[inst] = direct[inst] = (scope, phase)
        callees = _CALLEE.findall(ls)
        if callees:
            inst_callees.setdefault(inst, []).extend(callees)
            if " fusion(" in rhs:
                fusion_bodies.update(callees)
        for ref in _REF.findall(rhs):
            operand_users.setdefault(ref, []).append(inst)
        if comp:
            comp_insts.setdefault(comp, []).append(inst)
            if m.group(1):
                comp_root[comp] = inst
    # one-hop neighbor inheritance: backend rewrites (the CPU layout pass
    # re-materializing a convolution) drop the op's own metadata but leave
    # it on the adjacent bitcast/copy — an unresolved instruction takes
    # the majority scope of its DIRECT-metadata users. One hop only, so
    # the residual row stays honest (no transitive flooding).
    for inst, users in operand_users.items():
        if inst in resolved:
            continue
        counts: Dict[Tuple[str, str], int] = {}
        for u in users:
            if u in direct:
                counts[direct[u]] = counts.get(direct[u], 0) + 1
        if counts:
            resolved[inst] = max(counts.items(), key=lambda kv: kv[1])[0]
    # fixpoint over the call graph (a parallel call wraps a fusion clone
    # wraps the fused computation — a few levels at most)
    for _ in range(8):
        cscope: Dict[str, Tuple[str, str]] = {}
        for c, insts in comp_insts.items():
            root = comp_root.get(c)
            if root in resolved:
                cscope[c] = resolved[root]
                continue
            counts: Dict[Tuple[str, str], int] = {}
            for i in insts:
                if i in resolved:
                    counts[resolved[i]] = counts.get(resolved[i], 0) + 1
            if counts:
                cscope[c] = max(counts.items(), key=lambda kv: kv[1])[0]
        changed = False
        for inst, callees in inst_callees.items():
            if inst in resolved:
                continue
            for c in callees:
                if c in cscope:
                    resolved[inst] = cscope[c]
                    changed = True
                    break
        if not changed:
            break
    # DOWNWARD inheritance: XLA:CPU's thunk registry names the CLONED
    # fusion instruction INSIDE a %parallel_* computation
    # ('copy_bitcast_fusion.2.clone' in %parallel_copy_bitcast_fusion.2),
    # which carries no metadata of its own — the upward fixpoint resolves
    # the CALLER, so push each called computation's caller scope down onto
    # its unresolved members (majority across call sites, a few levels)
    for _ in range(8):
        comp_counts: Dict[str, Dict[Tuple[str, str], int]] = {}
        for inst, callees in inst_callees.items():
            s = resolved.get(inst)
            if s is None:
                continue
            for c in callees:
                cc = comp_counts.setdefault(c, {})
                cc[s] = cc.get(s, 0) + 1
        changed = False
        for c, counts in comp_counts.items():
            s = max(counts.items(), key=lambda kv: kv[1])[0]
            for i in comp_insts.get(c, ()):
                if i not in resolved:
                    resolved[i] = s
                    changed = True
        if not changed:
            break
    # last-chance neighbor rescue, ONE snapshot pass: a backend-rewritten
    # instruction whose metadata is gone AND whose direct-metadata
    # neighbors are all metadata-less calls (the CPU layout pass
    # re-materializing a backward convolution between two parallel calls)
    # takes the majority scope of its RESOLVED operands/users. Snapshot
    # semantics — rescued instructions never feed further rescues — so
    # there is no transitive flooding and the residual row stays honest.
    snapshot = dict(resolved)
    inst_operands: Dict[str, List[str]] = {}
    for op, users in operand_users.items():
        for u in users:
            inst_operands.setdefault(u, []).append(op)
    for inst in {i for insts in comp_insts.values() for i in insts}:
        if inst in snapshot:
            continue
        counts = {}
        for nb in operand_users.get(inst, []) + inst_operands.get(inst, []):
            s = snapshot.get(nb)
            if s is not None:
                counts[s] = counts.get(s, 0) + 1
        if counts:
            resolved[inst] = max(counts.items(), key=lambda kv: kv[1])[0]
    return resolved, comp_insts, fusion_bodies


# --------------------------------------------------------------------------- #
# the attribution table
# --------------------------------------------------------------------------- #

RESIDUAL = "(unattributed)"


def attribute(events: Sequence[Dict], scope_map: Dict[str, Tuple[str, str]],
              cost_table: Optional[Dict[str, Dict]] = None,
              peak_flops: Optional[float] = None,
              steps: int = 1,
              tracer_overhead_ms: Optional[float] = None) -> Dict:
    """Fold trace events into the per-layer table.

    Only OP events enter the accounting: an event whose ``stats`` carry an
    ``hlo_op`` (the profiler's own op marker), whose name is a known
    instruction, or that sits on a device plane (TPU op lines carry the
    instruction name but not always the stat). Python/TraceMe/runtime
    housekeeping events are excluded from both numerator and denominator —
    the table answers "where does the traced op time go", and the residual
    row reports op time whose instruction metadata named no known scope.

    Accounting is SELF time: op events nest (a while op contains its body
    ops on the same thread line, a fusion its producers), so each event is
    billed its duration minus its direct op children's — flame-graph
    attribution, never double-counted. ``steps`` divides a multi-step
    trace down to per-step ms.

    ``tracer_overhead_ms``: on the CPU thunk runtime the tracer costs
    ~10 us PER OP EVENT, so a loopy op (pool backward's select-and-scatter
    runs one thunk per window) reads far slower traced than untraced. Pass
    ``traced_wall - untimed_wall`` here and the overhead is stripped
    uniformly per event before accounting (reported back as
    ``tracer_overhead_ms_stripped``). Leave None on TPU — device-plane
    events are hardware timings and carry no host tracer cost."""
    steps = max(1, int(steps))

    # 1) select op events, keyed for the scope join
    ops: List[Tuple] = []          # (plane, line, t0, dur, key, known)
    for ev in events:
        key = ev.get("stats", {}).get("hlo_op") or ev.get("name", "")
        if isinstance(key, bytes):
            key = key.decode("utf-8", "replace")
        known = key in scope_map
        if not known:
            # device event names sometimes decorate the instruction name
            # ('%fusion.3', an extra trailing '.<n>'); strip and retry
            # before consigning the event to the residual row
            alt = key.lstrip("%")
            if alt not in scope_map:
                alt = re.sub(r"\.\d+$", "", alt)
            if alt in scope_map:
                key, known = alt, True
        # TPU device planes also carry whole-step lines ("XLA Modules",
        # "Steps") whose events span the entire dispatch — counting those
        # as residual would halve coverage. Only the op line ("XLA Ops")
        # qualifies an unknown device event as op time.
        on_device_op_line = (
            str(ev.get("plane", "")).startswith("/device:")
            and "op" in str(ev.get("line", "")).lower())
        if not known and "hlo_op" not in ev.get("stats", {}) \
                and not on_device_op_line:
            continue                       # not an op event at all
        ops.append((ev.get("plane", ""), ev.get("line", ""),
                    float(ev.get("t0_us", 0.0)),
                    float(ev.get("dur_us", 0.0)), key, known))

    # 2) per thread line, subtract each op's direct op-children time
    self_us: List[float] = [0.0] * len(ops)
    children: List[int] = [0] * len(ops)   # direct op-children count
    by_line: Dict[Tuple, List[int]] = {}
    for i, op in enumerate(ops):
        by_line.setdefault((op[0], op[1]), []).append(i)
    for idxs in by_line.values():
        idxs.sort(key=lambda i: (ops[i][2], -ops[i][3]))
        stack: List[int] = []              # enclosing-op indices
        for i in idxs:
            _, _, t0, dur, _, _ = ops[i]
            while stack and t0 >= ops[stack[-1]][2] + ops[stack[-1]][3]:
                stack.pop()
            self_us[i] = dur
            if stack:
                self_us[stack[-1]] -= dur  # parent loses the child's time
                children[stack[-1]] += 1
            stack.append(i)

    # the tracer bills ~c per EVENT, and a child's bookkeeping lands in
    # its parent's self-time window — so debit each op c * (1 + its
    # direct children). This is what rescues the while-loop ops (one
    # thunk event per loop trip) from reading as the top sink.
    per_event_oh = 0.0
    if tracer_overhead_ms and ops:
        per_event_oh = max(tracer_overhead_ms, 0.0) * 1e3 / len(ops)

    per_scope: Dict[str, Dict[str, float]] = {}
    residual_us = 0.0
    residual_ops: Dict[str, float] = {}
    total_us = 0.0
    for (_, _, _t0, _dur, key, known), dur, nchild in zip(ops, self_us,
                                                          children):
        dur = max(dur - per_event_oh * (1 + nchild), 0.0)
        total_us += dur
        if not known:
            residual_us += dur
            residual_ops[key] = residual_ops.get(key, 0.0) + dur
            continue
        scope, phase = scope_map[key]
        row = per_scope.setdefault(scope, {"fwd": 0.0, "bwd": 0.0,
                                           "misc": 0.0})
        row[phase if phase in row else "misc"] += dur
    rows: List[Dict] = []
    for scope, acc in per_scope.items():
        tot_ms = (acc["fwd"] + acc["bwd"] + acc["misc"]) / 1e3 / steps
        row = {
            "layer": scope,
            "fwd_ms": round(acc["fwd"] / 1e3 / steps, 4),
            "bwd_ms": round(acc["bwd"] / 1e3 / steps, 4),
            "total_ms": round(tot_ms, 4),
            "pct_of_traced": round(100.0 * (acc["fwd"] + acc["bwd"] +
                                          acc["misc"]) / total_us, 2)
            if total_us else 0.0,
        }
        cost = (cost_table or {}).get(scope)
        if cost:
            row["flops"] = cost["flops"]
            row["intensity"] = cost["intensity"]
            if peak_flops and tot_ms > 0:
                row["mfu"] = round(cost["flops"] / (tot_ms / 1e3)
                                   / peak_flops, 4)
        rows.append(row)
    rows.sort(key=lambda r: -r["total_ms"])
    total_ms = total_us / 1e3 / steps
    res_ms = residual_us / 1e3 / steps
    coverage = 1.0 - (residual_us / total_us) if total_us else 0.0
    top_res = sorted(residual_ops.items(), key=lambda kv: -kv[1])[:5]
    return {
        "rows": rows,
        "residual": {
            "layer": RESIDUAL,
            "total_ms": round(res_ms, 4),
            "pct_of_traced": round(100.0 * residual_us / total_us, 2)
            if total_us else 0.0,
            "top_ops": [{"op": k, "ms": round(v / 1e3 / steps, 4)}
                        for k, v in top_res],
        },
        "total_ms": round(total_ms, 4),
        "coverage": round(coverage, 4),
        "top_sinks": [r["layer"] for r in rows[:3]],
        "op_events": len(ops),
        "tracer_overhead_ms_stripped": round(per_event_oh * len(ops) / 1e3,
                                             3),
    }


# --------------------------------------------------------------------------- #
# capture discipline: timing FIRST, trace capture AFTER
# --------------------------------------------------------------------------- #

def measure_then_trace(run_step, trace_dir: str, iters: int = 3) -> Dict:
    """Run the TIMED loop first (min-wall over ``iters`` calls, the
    estimator for one-sided noise), then capture exactly one traced step
    into ``trace_dir``. Profiler overhead can therefore never contaminate
    the reported step time (pinned by
    tests/test_attribution.py::test_trace_capture_stays_after_timing).

    ``run_step`` is a zero-arg callable that dispatches one step and
    blocks until it completes. Returns {"step_ms", "walls_ms"}."""
    import time as _time

    import jax

    walls = []
    for _ in range(max(1, iters)):
        t0 = _time.perf_counter()
        run_step()
        walls.append(_time.perf_counter() - t0)
    jax.profiler.start_trace(trace_dir)
    try:
        t0 = _time.perf_counter()
        run_step()
        traced_wall = _time.perf_counter() - t0
    finally:
        jax.profiler.stop_trace()
    return {"step_ms": round(min(walls) * 1e3, 4),
            "walls_ms": [round(w * 1e3, 3) for w in walls],
            # traced-vs-untraced gap = total tracer overhead; attribute()
            # strips it per event on host-traced (CPU) runs
            "traced_step_ms": round(traced_wall * 1e3, 4)}
