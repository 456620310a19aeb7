"""Measured HBM budget planner: per-layer activation remat as a policy.

Every perf lever so far (layout, arena, kernels, wire codec) attacks
time; this module attacks MEMORY — the axis that actually bounds the
per-chip batch, and through it MFU, on real TPUs. The mechanism follows
a cost-based optimizer's discipline (Caffe con Troll,
arXiv:1504.04343):
recomputation is a scheduler-level memory/compute trade (TensorFlow,
arXiv:1605.08695), so the choice of WHICH activations to drop is made
from measured numbers, not vibes:

- the analytic side is the ``act_bytes`` column of
  ``core/net.Net.cost_table`` — each layer's stored forward activation
  footprint, priced against its forward recompute FLOPs;
- the measured side is the compiled no-remat step's real
  ``compiled.memory_analysis()`` peak (the same call
  scripts/aot_tpu_check.py records per mesh arm), which anchors how many
  bytes actually need reclaiming to fit ``--hbm_budget_gb``.

:func:`plan_remat` closes the loop with a greedy cheapest-recompute-
per-byte knapsack: drop stored activations (cheapest recompute first)
until the deficit against the budget is covered. The resulting
:class:`RematPlan` rides ``build_train_step(remat_plan=)`` /
``build_spmd_train_step`` — ``core/net.Net.apply`` wraps the chosen
layers' bodies in ``jax.checkpoint`` with the ``named_scope`` INSIDE the
checkpointed function (the JIT106 contract: recomputed backward ops must
keep attributing to their layer, never the residual row) — and the
transformer family's ``remat`` flag generalizes to the policy enum below
riding the same plan.

Remat never changes the math: the recomputed forward replays the same
ops on the same inputs, so remat arms stay BITWISE equal to
stored-activation arms (tests/test_remat.py pins this through full
Engine steps and the dp/fsdp mesh).
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Dict, List, Optional, Sequence, Tuple

# The transformer-family remat policy enum ("riding the same plan"):
#   none              store every block's internals (no checkpoint)
#   dots_saveable     checkpoint blocks but keep matmul results — the
#                     measured default (recompute only the cheap
#                     elementwise/softmax tissue between dots)
#   nothing_saveable  checkpoint blocks saving only block inputs — the
#                     legacy remat=True behavior, maximal reclaim
#   auto              defer to the RematPlan's row
REMAT_POLICIES = ("none", "dots_saveable", "nothing_saveable", "auto")


def normalize_policy(value) -> str:
    """Fold the legacy bool flag and the enum spellings into one policy
    name. ``True`` folds to ``nothing_saveable`` — the legacy code wrapped
    blocks in bare ``jax.checkpoint``, whose default saves nothing, and the
    fold must preserve that graph exactly (the per-block gradient-parity
    anchors in test_transformer/test_moe pin it to within the old
    tolerances). ``False``/``None``/``""`` mean ``none``."""
    if value is None or value is False or value == "":
        return "none"
    if value is True:
        return "nothing_saveable"
    v = str(value).lower()
    if v not in REMAT_POLICIES:
        raise ValueError(
            f"unknown remat policy {value!r}; choose from {REMAT_POLICIES}")
    return v


def resolve_lm_policy(cfg_remat, plan_policy=None) -> str:
    """Resolve the transformer family's effective policy from the config
    flag and an (optional) plan row, refusing loudly on disagreement.

    ``False`` (the dataclass default) is treated as UNSET — a plan may
    enable remat under it. ``True`` and the string spellings are
    EXPLICIT: an explicit flag that contradicts a concrete plan value is
    a configuration error, never silently arbitrated. ``auto`` (either
    side) defers to the other; when both sides defer (or only ``auto``
    remains) the measured default ``dots_saveable`` applies."""
    plan = normalize_policy(plan_policy) if plan_policy is not None \
        else None
    explicit = cfg_remat is not None and cfg_remat is not False \
        and cfg_remat != ""
    cfg = normalize_policy(cfg_remat)
    if cfg == "auto":
        explicit = False
        cfg = "dots_saveable" if plan is None else plan
    if plan is None or plan == "auto":
        return "dots_saveable" if (plan == "auto" and not explicit) else cfg
    if explicit and cfg != plan:
        raise ValueError(
            f"remat policy conflict: config says {cfg!r} but the plan "
            f"says {plan!r} — drop the explicit flag (or set remat="
            f"'auto') to follow the plan, or retire the plan row")
    return plan if not explicit else cfg


def checkpoint_policy(name: str):
    """The jax checkpoint policy object for one enum member (None for
    ``nothing_saveable`` — jax.checkpoint's own default)."""
    import jax
    name = normalize_policy(name)
    if name in ("none", "auto"):
        raise ValueError(f"policy {name!r} does not name a checkpoint "
                         f"policy; resolve it first")
    if name == "dots_saveable":
        return jax.checkpoint_policies.dots_saveable
    return None                                   # nothing_saveable


def wrap_checkpoint(fn, policy_name: str):
    """``fn`` wrapped in jax.checkpoint under ``policy_name`` (identity
    for ``none``)."""
    import jax
    policy_name = normalize_policy(policy_name)
    if policy_name == "none":
        return fn
    pol = checkpoint_policy(policy_name)
    return jax.checkpoint(fn, policy=pol) if pol is not None \
        else jax.checkpoint(fn)


# --------------------------------------------------------------------------- #
# what a checkpointed unit keeps of its own making
# --------------------------------------------------------------------------- #

# The share of the device's memory limit (``default_budget_bytes``) that a
# COMPILED step may take while its units keep named values, where the user
# gave no ``--hbm_budget_gb``: PERF.md section 7 (49a) has the chip runs it
# was fixed from.
KEEP_SHARE = 0.88


def keep_rungs(made=None) -> List[Tuple[str, ...]]:
    """What a plan's units may keep (``RematPlan.keep``), most first, so
    that a unit's replay runs nothing expensive twice: the products of its
    gated FFN (``layers.FFN_SAVED``: gate and up, and the down product
    where a norm reads it), and the results of the Pallas forward kernels,
    which are the residuals of their own backward kernels.

        FFN + scan + flash  ->  scan + flash  ->  flash  ->  ()

    The order of giving up is by milliseconds a gigabyte on the v5e: an
    (S, F) product contracted over D returns D FLOPs a byte, 12 ms/GB at D
    2048 and 25 at D 3840 at the 155-166 TFLOP/s these scopes run (a down
    product, contracted over F, 72), so the FFN's go first; a scan's chunk
    states are large beside its forward (18-25 ms/GB), a flash kernel's
    output and row statistics small beside its (75 ms/GB), so the scans' go
    next. Of a program that makes the names ``made`` (None: any of them),
    the rungs that differ: ``[()]`` where it makes none. Which rung a step
    ends on is the Engine's to say (``_compile_step``): the first whose
    COMPILED step is within the budget, and a rung whose floor (the
    arguments + the units' stored inputs + its kept bytes, all read off the
    trace by the Engine's ``unit_residuals``) already exceeds it is passed
    over without a compile."""
    from ..ops.kda import SCAN_SAVED
    from ..ops.pallas_kernels import FLASH_SAVED
    from .layers import FFN_SAVED
    rungs: List[Tuple[str, ...]] = []
    for rung in (FFN_SAVED + SCAN_SAVED + FLASH_SAVED,
                 SCAN_SAVED + FLASH_SAVED, FLASH_SAVED, ()):
        rung = tuple(n for n in rung if made is None or n in made)
        if rung not in rungs:
            rungs.append(rung)
    return rungs


def checkpoint_unit(fn, keep: Sequence[str] = ()):
    """``fn`` as one of ``Net.apply``'s checkpointed units: it stores what
    it takes from outside and, of what it makes, the values named in
    ``keep`` (``checkpoint_name`` tags: a gated FFN's products and the
    Pallas forward kernels' results, :func:`keep_rungs`). With none it is
    bare ``jax.checkpoint``, the program it always was."""
    import jax
    if not keep:
        return jax.checkpoint(fn)
    return jax.checkpoint(
        fn, policy=jax.checkpoint_policies.save_only_these_names(*keep))


# --------------------------------------------------------------------------- #
# the plan
# --------------------------------------------------------------------------- #

@dataclass(frozen=True)
class RematPlan:
    """One resolved remat decision, computed at step-build time.

    ``layers`` names the Net-family layers whose forward bodies
    ``Net.apply`` wraps in ``jax.checkpoint``; ``lm_policy`` is the
    transformer family's block policy riding the same plan. The byte/
    FLOP fields record what the knapsack claimed so stats.yaml and the
    tuned store can say WHY these layers were chosen."""

    budget_bytes: int = 0               # the target (0 = no budget given)
    measured_peak_bytes: int = 0        # no-remat compiled peak (0 = n/a)
    layers: Tuple[str, ...] = ()
    # runs of consecutive ``layers`` that share ONE checkpoint (a block, a
    # head and its loss): only what such a run takes from outside is
    # stored. A layer in no segment is checkpointed alone.
    segments: Tuple[Tuple[str, ...], ...] = ()
    saved_bytes: int = 0                # analytic activation bytes dropped
    recompute_flops: float = 0.0        # analytic forward FLOPs re-paid
    lm_policy: str = "none"
    source: str = "analytic"            # analytic | measured | plan | flag
    # the named values every unit keeps through its backward instead of
    # replaying the kernel that wrote them (``keep_rungs``); none: a unit
    # keeps only what it takes from outside
    keep: Tuple[str, ...] = ()

    @property
    def units(self) -> Tuple:
        """What ``Net.apply(remat=)`` takes: the segments, then every
        other layer alone."""
        grouped = {n for seg in self.segments for n in seg}
        return self.segments + tuple(n for n in self.layers
                                     if n not in grouped)

    @property
    def apply_args(self) -> Dict:
        """``Net.apply``'s two keywords for this plan."""
        return {"remat": self.units, "remat_keep": self.keep}

    @property
    def active(self) -> bool:
        return bool(self.layers) or self.lm_policy != "none"

    def describe(self, kept: Optional[Dict] = None) -> str:
        """One line of the log; ``kept`` is the Engine's decision on what
        the units keep, once its step is compiled or loaded."""
        if not self.active:
            return "remat: off (fits the budget)"
        mb = self.saved_bytes / 2**20
        return (f"remat[{self.source}]: {len(self.layers)} layers"
                + (f" ({len(self.segments)} segments)"
                   if self.segments else "")
                + f", ~{mb:.1f} MiB reclaimed, "
                f"{self.recompute_flops / 1e6:.1f} MFLOP recompute"
                + (f", lm={self.lm_policy}"
                   if self.lm_policy != "none" else "")
                + (f"; units keep {'+'.join(self.keep) or 'nothing'}"
                   f" ({kept['kept_bytes'] / 2**20:.1f} MiB in "
                   f"{kept['kept_units']}"
                   + "".join(f", {name} {b / 2**20:.1f}" for name, b in
                             kept.get("kept_bytes_by_name", {}).items())
                   + f"): the step compiles at "
                   f"{kept['compiled_peak_bytes'] / 1e9:.2f} GB, held to "
                   f"{kept['held_to_bytes'] / 1e9:.2f}, "
                   f"{kept['compiles']} compile(s)" if kept else ""))

    def to_doc(self) -> Dict:
        return {"budget_bytes": int(self.budget_bytes),
                "measured_peak_bytes": int(self.measured_peak_bytes),
                "layers": list(self.layers),
                "segments": [list(seg) for seg in self.segments],
                "saved_bytes": int(self.saved_bytes),
                "recompute_flops": float(self.recompute_flops),
                "lm_policy": self.lm_policy,
                "source": self.source,
                "keep": list(self.keep)}

    @classmethod
    def from_doc(cls, doc: Dict) -> "RematPlan":
        return cls(budget_bytes=int(doc.get("budget_bytes", 0)),
                   measured_peak_bytes=int(doc.get("measured_peak_bytes",
                                                   0)),
                   layers=tuple(doc.get("layers", ())),
                   segments=tuple(tuple(seg)
                                  for seg in doc.get("segments", ())),
                   saved_bytes=int(doc.get("saved_bytes", 0)),
                   recompute_flops=float(doc.get("recompute_flops", 0.0)),
                   lm_policy=normalize_policy(doc.get("lm_policy",
                                                      "none")),
                   source=str(doc.get("source", "plan")),
                   keep=tuple(doc.get("keep", ())))


def resolve_entries(layer_names: Sequence[str], entries: Sequence[str]
                    ) -> Tuple[Tuple[str, ...], Tuple[Tuple[str, ...], ...]]:
    """``--remat``'s entries -> ``(layers, segments)`` of a plan.

    A plain entry is a layer name, checkpointed alone. An entry written
    ``/regex/`` names SEGMENTS: the regex is matched at the start of every
    layer name, and each maximal run of consecutive layers whose matched
    text is the same shares one checkpoint — ``/p\\d+_l\\d+_/`` is one
    segment per ``p<t>_l<i>_*`` block, ``/p\\d+_(?=head|nll)/`` one per
    pass's head and loss. Unknown names and a regex that matches nothing
    are refused."""
    import itertools
    import re
    known = set(layer_names)
    layers: List[str] = []
    segments: List[Tuple[str, ...]] = []
    for entry in entries:
        if not (len(entry) > 2 and entry[0] == entry[-1] == "/"):
            if entry not in known:
                raise ValueError(f"--remat names unknown layers: [{entry!r}]")
            layers.append(entry)
            continue
        pattern = re.compile(entry[1:-1])
        matched = [(name, pattern.match(name)) for name in layer_names]
        found = False
        for key, run in itertools.groupby(
                matched, key=lambda nm: nm[1].group(0) if nm[1] else None):
            if key is None:
                continue
            found = True
            names = tuple(name for name, _ in run)
            layers.extend(names)
            if len(names) > 1:
                segments.append(names)
        if not found:
            raise ValueError(f"--remat {entry} matches no layer")
    if len(set(layers)) != len(layers):
        raise ValueError("--remat names a layer more than once")
    return tuple(layers), tuple(segments)


def remat_candidates(net) -> List[str]:
    """Layer names eligible for per-layer checkpointing: layers that
    consume bottoms (a data source has nothing to recompute FROM — its
    top is the stored input either way) and produce a real top. Loss
    heads stay eligible but their scalar tops price at ~0 bytes, so the
    knapsack never wastes a pick on them."""
    out = []
    for layer in net.layers:
        if not layer.lp.bottom or not layer.lp.top:
            continue
        out.append(layer.name)
    return out


def plan_remat(cost_table: Dict[str, Dict], budget_bytes: int,
               peak_bytes: int,
               candidates: Optional[Sequence[str]] = None,
               lm_policy: str = "none",
               source: str = "analytic") -> RematPlan:
    """The greedy cheapest-recompute-per-byte knapsack.

    ``cost_table`` is ``net.cost_table()`` (the
    ``act_bytes`` + ``flops`` columns); ``peak_bytes`` is the NO-remat
    step's peak — measured via :func:`measured_peak_bytes` when a
    compile is affordable, else the analytic activation total. Layers
    drop (cheapest forward-recompute per reclaimed byte first) until
    the deficit ``peak_bytes - budget_bytes`` is covered or every
    candidate is spent.

    Edge semantics the unit tests pin: ``budget_bytes <= 0`` means
    maximal remat (every candidate drops — the "fit anywhere" request);
    a budget at or above the peak is a no-op identity plan. Lower
    budgets choose SUPERSETS of higher budgets' layers (the greedy
    order is fixed, so the plan is monotone in the budget)."""
    rows = []
    names = list(candidates) if candidates is not None \
        else list(cost_table)
    for name in names:
        row = cost_table.get(name)
        if not row:
            continue
        act = int(row.get("act_bytes", 0))
        if act <= 0:
            continue
        fwd_flops = float(row.get("flops", 0.0)) / 3.0   # table is 3x fwd
        rows.append((fwd_flops / act, name, act, fwd_flops))
    # fixed greedy order: cheapest recompute-per-byte first; name breaks
    # ties so the plan is deterministic across processes (the collective-
    # consistency property: every mesh participant must plan identically)
    rows.sort(key=lambda r: (r[0], r[1]))
    deficit = (float("inf") if budget_bytes <= 0
               else int(peak_bytes) - int(budget_bytes))
    chosen: List[str] = []
    saved = 0
    flops = 0.0
    for _, name, act, fwd in rows:
        if saved >= deficit:
            break
        chosen.append(name)
        saved += act
        flops += fwd
    return RematPlan(budget_bytes=max(0, int(budget_bytes)),
                     measured_peak_bytes=int(peak_bytes),
                     layers=tuple(chosen), saved_bytes=saved,
                     recompute_flops=flops,
                     lm_policy=normalize_policy(lm_policy), source=source)


# --------------------------------------------------------------------------- #
# the measured side
# --------------------------------------------------------------------------- #

def measured_peak_bytes(compiled) -> int:
    """The compiled step's peak live bytes from XLA's own buffer
    assignment: arguments + outputs + temps, minus the aliased (donated)
    overlap — the same ``memory_analysis()`` counters the AOT TPU
    evidence records. Returns 0 when the runtime reports nothing (older
    jaxlib / backends without the API)."""
    try:
        ma = compiled.memory_analysis()
    except Exception:                       # noqa: BLE001 — optional API
        return 0
    if ma is None:
        return 0
    total = 0
    for k in ("argument_size_in_bytes", "output_size_in_bytes",
              "temp_size_in_bytes"):
        total += int(getattr(ma, k, 0) or 0)
    total -= int(getattr(ma, "alias_size_in_bytes", 0) or 0)
    return max(0, total)


def default_budget_bytes(device=None, reserve_bytes: int = 0) -> int:
    """The default ``--hbm_budget_gb``: the device's own memory limit
    minus ``reserve_bytes`` (arena + optimizer state the caller knows
    about). Returns 0 when the backend publishes no memory stats (the
    CPU backend returns None) — callers must then pass an explicit
    budget. A backend that RAISES here is a real failure and propagates:
    silently planning against a zero budget would hide it."""
    import jax
    if device is None:
        device = jax.local_devices()[0]
    stats = device.memory_stats()
    if not stats:
        return 0
    limit = int(stats.get("bytes_limit", 0) or 0)
    return max(0, limit - int(reserve_bytes))


def plan_for_net_step(net, lowerable, example_args: tuple,
                      budget_bytes: int,
                      lm_policy: str = "none") -> RematPlan:
    """Compute a measured plan for one built (no-remat) train step:
    compile it, read the real ``memory_analysis()`` peak, and run the
    knapsack against the net's analytic activation column. The caller
    rebuilds the step with ``remat_plan=`` when the plan is active —
    remat is a trace-time property, so the no-remat compile is the
    price of measuring (paid once per job config; the tuned store
    memoizes the decision across processes)."""
    compiled = lowerable.lower(*example_args).compile()
    peak = measured_peak_bytes(compiled)
    table = net.cost_table()
    if peak <= 0:
        # no memory API: fall back to the analytic activation total so a
        # budget still produces a usable (if uncalibrated) plan
        peak = int(sum(r.get("act_bytes", 0) for r in table.values()))
        source = "analytic"
    else:
        source = "measured"
    return plan_remat(table, budget_bytes, peak,
                      candidates=remat_candidates(net),
                      lm_policy=lm_policy, source=source)
