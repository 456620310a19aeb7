"""HLO contract gates: checked-in per-model goldens for compiled invariants.

The perf PRs bought specific, countable properties of the compiled train
step — ~11 bucketed gradient psums on GoogLeNet instead of ~120 per-leaf
all-reduces (PR 4), exactly 2 NHWC layout transposes on AlexNet (the fc6
boundary pair, PR 3), donated param/state/batch buffers (PR 5), an
f64-free program — and until now they lived as assertions scattered
across tests that each compile their own subset. This module promotes
them to *contracts*: one JSON per model under ``evidence/hlo_contracts/``
recording the counters extracted from the lowered (StableHLO) and, where
a CPU compile is affordable, optimized-HLO text of one full data-parallel
optimizer step. The gate recomputes and diffs; ``refresh()`` rewrites the
goldens and prints the diff for review.

These static gates pin the compiled program's SHAPE on every CPU run —
the Julia->TPU/XLA argument (arXiv:1810.09868) that whole-program
ahead-of-time analysis is the natural fit for this regime. They say
nothing about speed; that takes the chip.

Compile-cost policy: tracing+lowering is seconds per model (the tier-1
gate level); full XLA CPU compiles are minutes on GoogLeNet, so the
``optimized`` section (fusion count) is recorded for LeNet only. The
NHWC layout half re-traces a mesh-free step via
``hlo_layout.net_transpose_report`` for AlexNet (the model the claim is
about; LeNet is single-channel and GoogLeNet's NHWC plan is pinned by
tests/test_layout_hlo.py). ROADMAP item 1's mesh work should EXTEND these
contracts with its planned collective schedule per (mesh, model).

Version drift: counters are exact goldens under the jax version that
generated them (recorded in ``generated_with``), and the repo is written
for one installation. Under any other jax the gate REFUSES (like a
device-count mismatch) instead of comparing a subset: upgrade jax and
``--refresh-contracts`` in the same change, reviewing the printed diff.
"""

from __future__ import annotations

import json
import os
import re
from typing import Dict, List, Optional, Sequence, Tuple

from . import REPO_ROOT

CONTRACT_DIR = os.path.join(REPO_ROOT, "evidence", "hlo_contracts")
MODELS = ("lenet", "alexnet", "googlenet")

# per-model build recipe: image/channels follow the cheapest configuration
# the existing suites already compile (tests/test_arena.py). The AlexNet
# NHWC half runs at the real 227 px: at toy sizes pool5 degenerates to
# 1x1 and the fc6 boundary pair it exists to pin folds away as bitcasts.
_SPECS = {
    # "mesh": lower the dp2xfsdp2xtp2 sharding-planner step and pin its
    # collective census against the planned schedule (parallel/spmd.py).
    # GoogLeNet skips it for compile budget — its schedule shape (conv
    # arena buckets + gathered-column classifier heads) is covered by
    # AlexNet.
    "lenet": {"image": 28, "channels": 1, "classes": 10,
              "optimized": True, "nhwc": False, "mesh": True},
    "alexnet": {"image": 67, "channels": 3, "classes": 10,
                "optimized": False, "nhwc": True, "nhwc_image": 227,
                "mesh": True},
    "googlenet": {"image": 224, "channels": 3, "classes": 10,
                  "optimized": False, "nhwc": False, "mesh": False},
}

_BATCH = 8          # one row per device on the 8-device virtual mesh

# the ops whose cross-participant divergence is a silent SPMD hang: a
# mesh member waiting in a collective its peers never entered (or
# entered with different groups/channels)
_COLLECTIVE_OP_RE = re.compile(
    r'"stablehlo\.(all_reduce|all_gather|reduce_scatter|all_to_all|'
    r'collective_permute|collective_broadcast)"')
_GROUPS_RE = re.compile(r"replica_groups\s*=\s*dense<([^>]*)>")
_CHANNEL_RE = re.compile(r"channel_handle<handle\s*=\s*(\d+)")
_DIM_RE = re.compile(r"(all_gather_dim|scatter_dimension|"
                     r"split_dimension|concat_dimension)\s*=\s*(\d+)")

_TENSOR_DTYPE_RE = re.compile(r"tensor<[0-9x]*([a-z][a-z0-9]*)>")


def collective_sequence(stablehlo: str) -> List[str]:
    """The ordered collective schedule of a lowered module: one
    normalized entry per collective op, in program order —
    ``op|replica_groups|dims|cN``. Channel ids are renumbered by first
    appearance (c0, c1, ...) so two participants' programs compare equal
    iff their schedules really match, even though jax's channel counter
    is process-global. This is the static form of the cross-participant
    contract: every mesh member must lower the IDENTICAL sequence, or
    some member ends up waiting in a collective its peers never enter —
    the silent-hang failure mode of multi-slice composition."""
    entries: List[str] = []
    chan_map: Dict[str, str] = {}
    for m in _COLLECTIVE_OP_RE.finditer(stablehlo):
        # attributes live between the op token and the body brace of the
        # same instruction; the next op's match bounds the slice
        end = stablehlo.find("({", m.end())
        nxt = _COLLECTIVE_OP_RE.search(stablehlo, m.end())
        stop = min(x for x in (end if end != -1 else len(stablehlo),
                               nxt.start() if nxt else len(stablehlo)))
        attrs = stablehlo[m.end():stop]
        g = _GROUPS_RE.search(attrs)
        groups = "".join((g.group(1) if g else "?").split())
        ch = _CHANNEL_RE.search(attrs)
        if ch:
            cid = chan_map.setdefault(ch.group(1), f"c{len(chan_map)}")
        else:
            cid = "c?"
        dims = ",".join(f"{k}={v}" for k, v in _DIM_RE.findall(attrs))
        entries.append(f"{m.group(1)}|{groups}|{dims}|{cid}")
    return entries


class ContractEnvironmentError(RuntimeError):
    """The measurement substrate does not match the golden's (wrong device
    count): the comparison is refused, not failed — CLI exit 4, never 2."""


def contract_path(model: str) -> str:
    return os.path.join(CONTRACT_DIR, f"{model}.json")


def _dtype_census(stablehlo: str) -> Dict[str, int]:
    out: Dict[str, int] = {}
    for m in _TENSOR_DTYPE_RE.finditer(stablehlo):
        out[m.group(1)] = out.get(m.group(1), 0) + 1
    return dict(sorted(out.items()))


def _fusion_count(optimized_hlo: str) -> int:
    return len(re.findall(r"\bfusion\(", optimized_hlo))


def _build_net(model: str):
    from ..core.net import Net
    from ..models import zoo
    spec = _SPECS[model]
    if model == "lenet":
        np_ = zoo.lenet(with_accuracy=False)
        shapes = zoo.lenet_shapes(_BATCH // 8)
    else:
        np_ = getattr(zoo, model)(num_classes=spec["classes"],
                                  with_accuracy=False)
        shapes = {"data": (_BATCH // 8, spec["channels"], spec["image"],
                           spec["image"]),
                  "label": (_BATCH // 8,)}
    return Net(np_, "TRAIN", source_shapes=shapes), spec


def ensure_virtual_mesh() -> None:
    """Pin the measurement substrate BEFORE jax initializes: the 8-device
    virtual CPU mesh every tier-1 suite runs on (tests/conftest.py). A
    contract measured on a different device count has different collective
    groups and is not comparable — if jax is already up with another
    count, check_model refuses the comparison (ContractEnvironmentError,
    CLI exit 4), never reporting it as a violation."""
    import sys
    if "jax" in sys.modules:
        return
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            flags + " --xla_force_host_platform_device_count=8").strip()


def build_contract(model: str) -> Dict:
    """Compile (on the current backend) and measure one model's contract.
    Slow path: seconds of tracing per model; LeNet additionally runs the
    CPU XLA compile for the optimized-HLO section."""
    ensure_virtual_mesh()
    import jax
    import jax.numpy as jnp
    import numpy as np

    from ..parallel import (CommConfig, build_train_step, init_train_state,
                            make_mesh)
    from ..proto.messages import SolverParameter
    from ..runtime.hlo_comm import count_gradient_all_reduces_stablehlo
    from ..runtime.hlo_layout import (count_layout_transposes,
                                      net_transpose_report)

    net, spec = _build_net(model)
    sp = SolverParameter(base_lr=0.01, lr_policy="fixed", momentum=0.9,
                         weight_decay=0.0005)
    mesh = make_mesh()
    n_dev = int(np.prod(list(mesh.shape.values())))
    params = net.init(jax.random.PRNGKey(0))
    cc = CommConfig()
    ts = build_train_step(net, sp, mesh, cc, donate=True, donate_batch=True)
    state = init_train_state(params, cc, n_dev)
    rs = np.random.RandomState(0)
    shape = (_BATCH, spec["channels"], spec["image"], spec["image"])
    batch = {"data": jnp.asarray(rs.randn(*shape).astype(np.float32)),
             "label": jnp.asarray(rs.randint(0, spec["classes"],
                                             size=(_BATCH,)))}
    lowered = ts.lowerable.lower(params, state, batch, jax.random.PRNGKey(7))
    txt = lowered.as_text()
    census = _dtype_census(txt)
    contract: Dict = {
        "model": model,
        "generated_with": {"jax": jax.__version__,
                           "backend": jax.default_backend(),
                           "n_devices": n_dev},
        "config": {"image": spec["image"], "channels": spec["channels"],
                   "batch": _BATCH, "num_classes": spec["classes"],
                   "conv_layout": net.conv_layout,
                   "param_leaves": len(jax.tree_util.tree_leaves(params)),
                   "donate": True, "donate_batch": True},
        "stablehlo": {
            # one sum a gradient leaf, issued where backward makes it
            # (PR 59: no buckets); the lowered count bounds the compiled
            # one from above, the compiler's combiner only ever merges.
            # Leaves under 256 elements (small biases) are not counted.
            "gradient_all_reduces": count_gradient_all_reduces_stablehlo(txt),
            # the PR-3 counter under the default (per-backend) layout
            "layout_transposes": count_layout_transposes(txt),
            # PR-5: params + solver state + batch buffers all donated
            "donated_buffers": txt.count("jax.buffer_donor"),
            "f64_tensors": census.get("f64", 0),
            "dtype_census": census,
        },
    }
    if spec["nhwc"]:
        from ..core.net import Net
        img = spec.get("nhwc_image", spec["image"])
        nhwc_net = Net(net.net_param, "TRAIN",
                       {"data": (2, spec["channels"], img, img),
                        "label": (2,)},
                       conv_layout="NHWC")
        rep = net_transpose_report(nhwc_net, sp, per_dev_batch=2,
                                   image=img)
        contract["nhwc"] = {
            "level": rep["level"],
            # the PR-3 headline: exactly the fc-boundary pair on AlexNet
            "layout_transposes": rep["layout_transposes"],
        }
    if spec.get("mesh"):
        # ROADMAP item 1's extension: the SPMD sharding planner's
        # collective schedule, pinned exactly like the arena's buckets.
        # dp2 x fsdp2 x tp2 uses all 8 virtual devices; counted on the
        # LOWERED program (combiner-proof: the chained buckets cannot
        # merge, and XLA never splits a collective).
        from ..runtime.hlo_comm import collective_census_stablehlo
        mtxt, plan, marena, mcfg, mnet, mcc = _lower_mesh_participant(model)
        census = collective_census_stablehlo(mtxt)
        # the planned schedule must be stated with the SAME CommConfig
        # the plan was built from, or planned-vs-lowered diffs for a
        # config reason rather than a lowering one
        sched = plan.collective_schedule(marena, mnet, comm=mcc)
        contract["collective_schedule"] = {
            "mesh": mcfg.describe(),
            "arena_buckets": (marena.n_buckets
                              if marena is not None else 0),
            "tp_modes": {l: d.mode
                         for l, d in sorted(plan.tp_layers.items())},
            "planned_counts": sched["counts"],
            "lowered_counts": census,
            "planned_matches_lowered": census == sched["counts"],
            # the full ordered schedule (op|groups|dims|channel): diffed
            # exactly under the recorded jax version, and the substrate
            # of the cross-participant consistency gate below
            "sequence": collective_sequence(mtxt),
        }
    # the HBM budget planner's contract surface (core/remat.py): the
    # analytic activation-bytes column the knapsack prices against, per
    # model. Pure shape math.
    from ..core import remat as remat_mod
    table = net.cost_table()
    zero_plan = remat_mod.plan_remat(
        table, 0, 0, candidates=remat_mod.remat_candidates(net),
        source="analytic")
    contract["memory"] = {
        "act_bytes_total": sum(int(r.get("act_bytes", 0))
                               for r in table.values()),
        "remat_candidates": len(remat_mod.remat_candidates(net)),
        # what the zero-budget maximal plan reclaims (bytes) — the
        # planner's full lever arm on this model
        "max_reclaim_bytes": int(zero_plan.saved_bytes),
    }
    if spec["optimized"]:
        compiled = lowered.compile()
        ctxt = compiled.as_text()
        from ..runtime.hlo_comm import count_gradient_all_reduces
        contract["optimized"] = {
            "gradient_all_reduces": count_gradient_all_reduces(ctxt),
            "layout_transposes": count_layout_transposes(ctxt),
            "fusion_count": _fusion_count(ctxt),
        }
        # real memory_analysis() peak — compiler output (exact only
        # under the recorded jax), riding the compile the optimized
        # section already paid; LeNet-only by the compile-cost policy
        contract["memory"]["measured_peak_bytes"] = \
            remat_mod.measured_peak_bytes(compiled)
    return contract


def _lower_mesh_participant(model: str):
    """Build + lower the dp2 x fsdp2 x tp2 sharded step EXACTLY as one
    mesh participant would — fresh Net, fresh plan, fresh trace — and
    return (stablehlo_text, plan, arena, mesh_config, net, comm_config).
    Called once by :func:`build_contract` and N times by
    :func:`collective_consistency` (each call IS one participant)."""
    ensure_virtual_mesh()
    import jax
    import jax.numpy as jnp
    import numpy as np

    from ..config import MeshConfig
    from ..core.net import Net
    from ..parallel import CommConfig, init_train_state
    from ..parallel.spmd import (ShardingPlan, build_spmd_train_step,
                                 named_mesh)
    from ..proto.messages import SolverParameter

    net, spec = _build_net(model)
    sp = SolverParameter(base_lr=0.01, lr_policy="fixed", momentum=0.9,
                         weight_decay=0.0005)
    cc = CommConfig()
    mcfg = MeshConfig(data=2, fsdp=2, tp=2)
    smesh = named_mesh(mcfg)
    n_dp = mcfg.data * mcfg.fsdp
    if model == "lenet":
        from ..models import zoo as _zoo
        mshapes = _zoo.lenet_shapes(_BATCH // n_dp)
    else:
        mshapes = {"data": (_BATCH // n_dp, spec["channels"],
                            spec["image"], spec["image"]),
                   "label": (_BATCH // n_dp,)}
    mnet = Net(net.net_param, "TRAIN", source_shapes=mshapes)
    plan = ShardingPlan.build(mnet, mcfg, cc)
    mts = build_spmd_train_step(mnet, sp, smesh, plan, cc, donate=False)
    mparams = mnet.init(jax.random.PRNGKey(0))
    mstate = init_train_state(mparams, cc, n_dp)
    rs = np.random.RandomState(0)
    shape = (_BATCH, spec["channels"], spec["image"], spec["image"])
    batch = {"data": jnp.asarray(rs.randn(*shape).astype(np.float32)),
             "label": jnp.asarray(rs.randint(0, spec["classes"],
                                             size=(_BATCH,)))}
    mlowered = mts.lowerable.lower(mparams, mstate, batch,
                                   jax.random.PRNGKey(7))
    return mlowered.as_text(), plan, mts.arena, mcfg, mnet, cc


def collective_consistency(models: Sequence[str] = ("lenet",),
                           participants: int = 2) -> Tuple[bool, Dict]:
    """The cross-participant collective gate: lower the sharded step
    ``participants`` times INDEPENDENTLY (fresh net, fresh planner state,
    fresh trace — what each process of a multi-process mesh, or each
    slice of ROADMAP item 4's cross-slice tier, would do on its own) and
    require the extracted collective sequences to be IDENTICAL: same ops
    in the same order, same replica groups, same dims, same normalized
    channel assignment. Any divergence is the mismatched-collective
    silent hang, caught at diff time instead of as a wedged pod."""
    report: Dict = {}
    ok = True
    for model in models:
        if not _SPECS.get(model, {}).get("mesh"):
            report[model] = {"ok": True, "skipped":
                             "no mesh spec for this model", "diffs": []}
            continue
        seqs = [collective_sequence(_lower_mesh_participant(model)[0])
                for _ in range(max(2, participants))]
        # a degenerate extraction must REFUSE, never vacuously pass: if
        # an MLIR printing change moves replica_groups out of the attr
        # slice, every entry degrades to 'op|?|...' and two genuinely
        # divergent participants would compare equal — the exact hang
        # this gate exists to catch. RuntimeError -> CLI exit 4 (infra).
        for p, seq in enumerate(seqs):
            bad = [e for e in seq if "|?|" in e]
            if not seq or bad:
                raise RuntimeError(
                    f"{model} participant {p}: collective sequence "
                    f"extraction degenerated ({'empty' if not seq else bad[0]!r}"
                    f") — the stablehlo printing no longer matches "
                    f"collective_sequence's attribute scan; fix the "
                    f"extractor before trusting this gate")
        diffs: List[str] = []
        base = seqs[0]
        for p, seq in enumerate(seqs[1:], start=1):
            if len(seq) != len(base):
                diffs.append(f"participant {p}: {len(seq)} collectives "
                             f"vs participant 0's {len(base)}")
            for i, (a, b) in enumerate(zip(base, seq)):
                if a != b:
                    diffs.append(f"participant {p} diverges at "
                                 f"collective #{i}: {a!r} vs {b!r}")
                    break       # first divergence per participant
        report[model] = {"ok": not diffs, "participants": len(seqs),
                         "sequence_len": len(base), "diffs": diffs}
        ok = ok and not diffs
    return ok, report


def load_contract(model: str) -> Optional[Dict]:
    path = contract_path(model)
    if not os.path.exists(path):
        return None
    with open(path) as f:
        return json.load(f)


def _environment_mismatch(golden: Dict, fresh: Dict) -> Optional[str]:
    """Why the two measurements are not comparable at all, or None."""
    g, f = golden.get("generated_with", {}), fresh.get("generated_with", {})
    if g.get("n_devices") != f.get("n_devices"):
        return (f"golden measured on {g.get('n_devices')} devices, this "
                f"process has {f.get('n_devices')} — collective groups are "
                f"not comparable (run under the 8-device virtual mesh, see "
                f"contracts.ensure_virtual_mesh)")
    if g.get("jax") != f.get("jax"):
        return (f"golden generated under jax {g.get('jax')!r}, running "
                f"{f.get('jax')!r} — counters are not comparable across "
                f"versions (--refresh-contracts under the installed jax)")
    return None


def diff_contracts(golden: Dict, fresh: Dict) -> List[str]:
    """Human-readable mismatches, empty when the contract holds. Pure —
    the unit tests feed it synthetic violations without compiling."""
    refusal = _environment_mismatch(golden, fresh)
    if refusal:
        return [refusal]
    diffs: List[str] = []
    for section in ("stablehlo", "nhwc", "collective_schedule",
                    "memory", "optimized"):
        gsec = golden.get(section)
        if gsec is None:
            continue
        if section == "optimized" and fresh.get(section) is None:
            diffs.append("optimized: section missing from measurement")
            continue
        for key, g in gsec.items():
            f = fresh.get(section, {}).get(key)
            if g is not None and g != f:
                diffs.append(
                    f"{section}.{key}: golden {g!r} != measured {f!r}")
    return diffs


def check_model(model: str,
                fresh: Optional[Dict] = None) -> Tuple[bool, List[str]]:
    golden = load_contract(model)
    if golden is None:
        return False, [f"no checked-in contract for {model!r} "
                       f"(run --refresh-contracts)"]
    fresh = fresh or build_contract(model)
    refusal = _environment_mismatch(golden, fresh)
    if refusal:
        raise ContractEnvironmentError(f"{model}: {refusal}")
    diffs = diff_contracts(golden, fresh)
    return not diffs, diffs


def check_all(models: Sequence[str] = MODELS) -> Tuple[bool, Dict]:
    report: Dict = {}
    ok = True
    for m in models:
        m_ok, diffs = check_model(m)
        report[m] = {"ok": m_ok, "diffs": diffs}
        ok = ok and m_ok
    return ok, report


def refresh(models: Sequence[str] = MODELS, out=print) -> None:
    """Rewrite the goldens, printing old->new for review — a contract
    change must be a decision, never an accident."""
    os.makedirs(CONTRACT_DIR, exist_ok=True)
    for m in models:
        fresh = build_contract(m)
        old = load_contract(m)
        if old is not None:
            for d in diff_contracts(old, fresh):
                out(f"  {m}: {d}")
        with open(contract_path(m), "w") as f:
            json.dump(fresh, f, indent=2)
            f.write("\n")
        out(f"refreshed {contract_path(m)}")
