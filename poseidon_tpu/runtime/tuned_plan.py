"""TunedPlan: one measured, persisted artifact for every policy knob.

The one chip A/B of July 2026 showed that hand-picked policies and HLO-level
proxies can invert on real hardware (NHWC "won" the transpose count yet ran
0.53x on the v5e; not re-measured on this code),
and the per-layer conv-strategy tuner (ops/conv_tune.py, PR 11) proved the
fix for ONE knob: measure short trials, persist the winner, memo-hit on the
next process. This module generalizes that mechanism to the whole policy
surface — Caffe con Troll's cost-based optimizer (arXiv:1504.04343) applied
to the repo's own knobs:

  conv_layout          internal activation layout (the "auto" per-backend
                       table becomes ONE MEASURED ROW of this plan)
  conv_strategy        per-layer conv lowering ("auto" = the PR-11 measured
                       per-layer store, riding this plan's cache dir)
  arena_bucket_mb      flat-arena gradient-collective bucket size
  mesh                 --mesh axis factorization of the available devices
  device_prefetch /    the step pipeline's input-prefetch depth and bounded
  max_in_flight        in-flight dispatch window
  steps_per_dispatch   optimizer steps per compiled dispatch (lax.scan)
  serve_buckets        the serving tier's batch bucket ladder
  remat / batch_size / the measured HBM budget pair (core/remat.py): at
  hbm_budget_gb        the job's own measured peak as the budget, does
                       checkpointing activations buy enough extra batch
                       to win on img/s?

One ``TunedPlan`` JSON per (model, backend, n_devices) lives in the
compile-cache tuned store (``runtime/compile_cache.load_tuned/save_tuned``,
namespace "plan") next to the AOT executables — the same restart economics:
a re-run with the same job config loads the winners instead of re-measuring.
Provenance (device kind, jax version, what was measured, when) is validated
at load time: a plan tuned on different hardware or a different jax refuses
to auto-load, loudly, and the built-in defaults apply.

Resolution precedence is strict and recorded per knob:

    explicit CLI flag  >  persisted TunedPlan  >  built-in default

``train``/``serve``/``bench_serve`` auto-load the matching plan at startup
(runtime/cli.py); the active resolution is published process-wide
(:func:`set_active_resolution`) so ``numeric.resolve_conv_layout``'s "auto"
branch reads the measured row, ``ops/conv_tune.py`` finds the per-layer
store, and the engine writes the provenance (sources + overrides) into
stats.yaml.

Trials are honest wall-clock measurements through the same hygiene the
bench harness uses: every arm warms before timing (first-call compile noise
never decides a winner) and candidates are timed in INTERLEAVED order-
alternating windows with a min-of-k estimator (host-load drift cannot bias
one arm — the ``bench.py pipeline_speedup`` idiom). The search always
includes the built-in default as a candidate and finishes with a composite
default-vs-tuned full-step A/B; a plan that measures slower than the
defaults is never shipped (the losing knobs revert, loudly).
"""

from __future__ import annotations

import os
import time
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, List, Optional, Tuple

from ..config import PipelineConfig
from .compile_cache import load_tuned, save_tuned, step_key, tuned_path
from .metrics import log

PLAN_NAMESPACE = "plan"
PLAN_VERSION = 1

# The built-in defaults every knob falls back to when neither a flag nor a
# plan covers it. The pipeline knobs read the PipelineConfig dataclass
# defaults so config.py stays the single source; the rest are the historic
# ad-hoc defaults this module collapses.
_PC = PipelineConfig()
BUILTIN_DEFAULTS: Dict[str, Any] = {
    "conv_layout": "auto",        # numeric.resolve_conv_layout's table
    "conv_strategy": "",          # legacy global conv_s2d policy
    "arena_bucket_mb": 4.0,
    "mesh": "",                   # flat data mesh over all devices
    "device_prefetch": _PC.device_prefetch,
    "max_in_flight": _PC.max_in_flight,
    "steps_per_dispatch": 1,
    "serve_buckets": "1,4,16,64",
    # LLM serving (serving/continuous.py): KV page size, the decode-batch
    # rung ladder, and the prompt-length prefill buckets
    "llm_page_size": 64,
    "llm_decode_rungs": "1,2,4,8",
    "llm_prompt_buckets": "16,64,256",
    "llm_replicas_tp": "",        # "RxT" replica×tp factorization; "" = auto
    # managed DCN delta wire dtype ('' = f32 byte-for-byte; bf16/f16/int8
    # compress with exact error feedback riding the comm residual)
    "wire_dtype": "",
    # measured HBM budget planner (core/remat.py): '' = no remat, 'auto'
    # = checkpoint per the budget knapsack; hbm_budget_gb 0 = no budget;
    # batch_size is the measured largest-admissible batch AT that budget
    # (informational — the prototxt owns the actual batch; 0 = unmeasured)
    "remat": "",
    "hbm_budget_gb": 0.0,
    "batch_size": 0,
}
TRAIN_KNOBS = ("conv_layout", "conv_strategy", "arena_bucket_mb", "mesh",
               "device_prefetch", "max_in_flight", "steps_per_dispatch",
               "wire_dtype", "remat", "hbm_budget_gb")


# --------------------------------------------------------------------------- #
# store: where plans live, how they are keyed, when they refuse to load
# --------------------------------------------------------------------------- #

def store_dir(cache_dir: Optional[str] = None) -> str:
    """The tuned-plan store directory: an explicit argument, else the
    compile-cache dir (plans live next to the AOT executables) — enabled
    or not, it resolves by the one rule in
    ``compile_cache.resolve_cache_dir``, so the ``tune`` -> ``train``
    auto-load round trip works with zero flags and nothing outside the
    checkout (or JAX_COMPILATION_CACHE_DIR) steers a run."""
    if cache_dir:
        return cache_dir
    from ..config import compile_cache_config
    from .compile_cache import resolve_cache_dir
    return compile_cache_config().cache_dir or resolve_cache_dir()


def plan_key(model: str, backend: str, n_devices: int) -> str:
    """Content key for one plan. Device kind and jax version are NOT in the
    key — they live in the provenance and are validated at load, so a
    mismatch is a LOUD refusal instead of a silent store miss."""
    return step_key(kind=PLAN_NAMESPACE, model=model.lower(),
                    backend=backend, n_devices=int(n_devices))


def plan_path(model: str, backend: str, n_devices: int,
              cache_dir: Optional[str] = None) -> str:
    return tuned_path(store_dir(cache_dir), PLAN_NAMESPACE,
                      plan_key(model, backend, n_devices))


def save_plan(doc: Dict, cache_dir: Optional[str] = None) -> Optional[str]:
    return save_tuned(store_dir(cache_dir), PLAN_NAMESPACE, doc["key"], doc)


def load_plan(model: str, backend: Optional[str] = None,
              n_devices: Optional[int] = None,
              cache_dir: Optional[str] = None) -> Optional[Dict]:
    """The persisted plan for (model, backend, n_devices), or None. A plan
    whose provenance names a different device kind or jax version REFUSES
    to load (loudly — measured winners do not transfer across
    hardware); any store-level failure is a
    clean miss (compile_cache.load_tuned logs torn entries)."""
    import jax
    backend = backend or jax.default_backend()
    n_devices = jax.device_count() if n_devices is None else n_devices
    doc = load_tuned(store_dir(cache_dir), PLAN_NAMESPACE,
                     plan_key(model, backend, n_devices))
    if doc is None:
        return None
    kind = jax.devices()[0].device_kind
    for fld, want in (("device_kind", kind),
                      ("jax_version", jax.__version__)):
        have = doc.get(fld)
        if have != want:
            log(f"[tuned_plan] REFUSING plan for {model!r}: {fld} "
                f"{have!r} != current {want!r} (tuned winners do not "
                f"transfer across hardware/toolchains — re-run "
                f"`python -m poseidon_tpu tune`); using built-in defaults")
            return None
    return doc


# --------------------------------------------------------------------------- #
# resolution: flag > plan > default, sources + overrides recorded
# --------------------------------------------------------------------------- #

@dataclass
class PlanResolution:
    """Per-knob resolved values with their source ("flag" | "plan" |
    "default"), plus the plan document (if any) and the store it came
    from. ``overridden`` names knobs where an explicit flag shadowed a
    persisted plan value — recorded in the provenance stats line so a
    stats.yaml always says which measured winners were NOT in effect."""

    values: Dict[str, Any] = field(default_factory=dict)
    sources: Dict[str, str] = field(default_factory=dict)
    doc: Optional[Dict] = None
    store: str = ""

    @property
    def overridden(self) -> List[str]:
        knobs = (self.doc or {}).get("knobs", {})
        return [k for k, src in sorted(self.sources.items())
                if src == "flag" and k in knobs
                and knobs[k] != self.values[k]]

    def provenance(self) -> Dict[str, Any]:
        out: Dict[str, Any] = {
            k: f"{self.values[k]} ({self.sources[k]})"
            for k in sorted(self.values)}
        if self.doc is not None:
            out["plan_key"] = self.doc.get("key")
            out["plan_model"] = self.doc.get("model")
            out["plan_measured_at"] = self.doc.get("measured_at")
            out["plan_device_kind"] = self.doc.get("device_kind")
            out["plan_jax_version"] = self.doc.get("jax_version")
        if self.overridden:
            out["overridden_by_flags"] = ",".join(self.overridden)
        return out

    def describe(self) -> str:
        head = ("plan " + str(self.doc.get("key"))[:12]
                if self.doc is not None else "no plan (defaults)")
        body = " ".join(f"{k}={self.values[k]}[{self.sources[k][0]}]"
                        for k in TRAIN_KNOBS if k in self.values)
        tail = (f" OVERRIDDEN: {','.join(self.overridden)}"
                if self.overridden else "")
        return f"{head}: {body}{tail}"


def resolve(doc: Optional[Dict], explicit: Dict[str, Any],
            knobs: Tuple[str, ...] = TRAIN_KNOBS,
            store: str = "") -> PlanResolution:
    """Fold the three layers into one resolution. ``explicit`` holds only
    the knobs the user actually set (CLI sentinel defaults keep unset
    flags out of it)."""
    res = PlanResolution(doc=doc, store=store)
    plan_knobs = (doc or {}).get("knobs", {})
    for k in knobs:
        if k in explicit and explicit[k] is not None:
            res.values[k], res.sources[k] = explicit[k], "flag"
        elif k in plan_knobs:
            res.values[k], res.sources[k] = plan_knobs[k], "plan"
        else:
            res.values[k], res.sources[k] = BUILTIN_DEFAULTS[k], "default"
    return res


# the process-wide active resolution: set by the CLI after auto-load, read
# by numeric.resolve_conv_layout (the measured "auto" row), conv_tune (the
# per-layer store location) and the engine (stats.yaml provenance section)
_active: Optional[PlanResolution] = None


def set_active_resolution(res: Optional[PlanResolution]) -> None:
    global _active
    _active = res


def active_resolution() -> Optional[PlanResolution]:
    return _active


def active_plan_value(knob: str) -> Optional[Any]:
    """The active resolution's value for ``knob`` IF it came from a
    measured plan (never a flag or default — callers consulting this want
    specifically the measured row)."""
    if _active is None or _active.sources.get(knob) != "plan":
        return None
    return _active.values.get(knob)


def active_store_dir() -> str:
    """Where the active plan was loaded from — ops/conv_tune.py falls back
    here so a plan-applied ``conv_strategy=auto`` memo-hits the per-layer
    winners the tune run persisted, even in a process that never enabled
    the compile cache. Empty unless a plan actually LOADED: a
    defaults-only resolution must not route conv_tune's store at the
    directory we merely looked in."""
    if _active is None or _active.doc is None:
        return ""
    return _active.store


def apply_training_resolution(res: PlanResolution) -> Dict[str, Any]:
    """Install the resolved values into the global policy/config state the
    training path reads (numeric policy for conv_layout/conv_strategy,
    PipelineConfig for the step-pipeline knobs) and publish the resolution.
    Returns the engine/CLI-level knobs the caller passes through
    explicitly: {arena_bucket_mb, mesh, steps_per_dispatch,
    device_prefetch, max_in_flight}. Used by cmd_train AND the parity
    test — applying a plan and passing the equivalent explicit flags must
    build bit-identical training runs."""
    from .. import config
    v = res.values
    config.set_policy(conv_layout=v["conv_layout"])
    if v["conv_strategy"]:
        config.set_policy(conv_strategy=v["conv_strategy"])
    config.set_pipeline_config(device_prefetch=int(v["device_prefetch"]),
                               max_in_flight=int(v["max_in_flight"]))
    # the managed DCN tier reads its wire dtype from ManagedCommConfig
    # (async_tier falls back to it when no explicit flag rode async_cfg);
    # NEVER returned to the caller — the compiled-tier CommConfig takes
    # the flag only, a plan value must not leak into compiled collectives
    config.set_managed_comm_config(wire_dtype=str(v.get("wire_dtype", "")))
    mesh = v["mesh"]
    if mesh and res.sources.get("mesh") == "plan":
        # plans are keyed by n_devices so this should never fire, but a
        # hand-edited/copied plan must degrade loudly, never SystemExit
        # deep in engine construction
        import jax
        from ..config import MeshConfig
        try:
            need = MeshConfig.parse(mesh).n_devices
        except ValueError as e:
            log(f"[tuned_plan] plan mesh {mesh!r} unparseable ({e}); "
                f"using the flat data mesh")
            mesh, res.values["mesh"], res.sources["mesh"] = "", "", "default"
        else:
            if need > jax.device_count():
                log(f"[tuned_plan] plan mesh {mesh!r} needs {need} devices, "
                    f"{jax.device_count()} available; using the flat data "
                    f"mesh")
                mesh, res.values["mesh"], res.sources["mesh"] = \
                    "", "", "default"
    set_active_resolution(res)
    return {"arena_bucket_mb": float(v["arena_bucket_mb"]),
            "mesh": mesh,
            "steps_per_dispatch": int(v["steps_per_dispatch"]),
            "device_prefetch": int(v["device_prefetch"]),
            "max_in_flight": int(v["max_in_flight"]),
            "remat": str(v.get("remat", "")),
            "hbm_budget_gb": float(v.get("hbm_budget_gb", 0.0))}


# --------------------------------------------------------------------------- #
# the measured-trial estimator (shared with ops/conv_tune.py)
# --------------------------------------------------------------------------- #

def interleaved_min_ms(fns: Dict[str, Callable[[], Any]],
                       windows: int = 4, iters: int = 3,
                       warmup: int = 2) -> Dict[str, float]:
    """Honest wall-clock per candidate: warm EVERY candidate ``warmup``
    times first (the first call pays trace+compile, the second can still
    pay one-time runtime work — neither may decide a winner), then time
    ``windows`` interleaved windows of ``iters`` calls each, alternating
    the candidate order per window (under cgroup throttling the first
    runner of a period gets the burst budget), and keep each candidate's
    MIN window — the robust estimator under one-sided noise (a window can
    be slowed by background load, never sped up). Returns {name: ms per
    call}."""
    order = list(fns)
    for name in order:
        for _ in range(max(1, warmup)):
            fns[name]()
    best = {name: float("inf") for name in order}
    for w in range(max(1, windows)):
        seq = order if w % 2 == 0 else list(reversed(order))
        for name in seq:
            t0 = time.perf_counter()
            for _ in range(max(1, iters)):
                fns[name]()
            best[name] = min(best[name],
                             (time.perf_counter() - t0) / max(1, iters))
    return {name: v * 1e3 for name, v in best.items()}


# --------------------------------------------------------------------------- #
# the search harness: `tune` (CLI + bench.py) lands here
# --------------------------------------------------------------------------- #

TUNE_MODELS = ("lenet", "alexnet", "googlenet")

# the engine-loop A/B net for the pipeline knobs (device_prefetch /
# max_in_flight act on the host<->device boundary, so they are measured
# through real Engine.train loops, not a bare compiled step)
_PIPE_NET = """
name: "tune_pipe"
layers { name: "src" type: MEMORY_DATA top: "data" top: "label"
  memory_data_param { batch_size: %d channels: 3 height: 20 width: 20 } }
layers { name: "conv1" type: CONVOLUTION bottom: "data" top: "conv1"
  convolution_param { num_output: 12 kernel_size: 3
    weight_filler { type: "xavier" } bias_filler { type: "constant" } } }
layers { name: "relu1" type: RELU bottom: "conv1" top: "conv1" }
layers { name: "pool1" type: POOLING bottom: "conv1" top: "pool1"
  pooling_param { pool: MAX kernel_size: 2 stride: 2 } }
layers { name: "ip1" type: INNER_PRODUCT bottom: "pool1" top: "ip1"
  inner_product_param { num_output: 10
    weight_filler { type: "xavier" } bias_filler { type: "constant" } } }
layers { name: "loss" type: SOFTMAX_LOSS bottom: "ip1" bottom: "label"
  top: "loss" }
"""

# the serving-ladder probe net when no deploy prototxt is supplied: ladder
# economics (pad waste vs compile slots) are shape-generic enough for a
# measured row, and the doc records that the probe was synthetic
_SERVE_NET = """
name: "tune_serve_synthetic"
input: "data"
input_dim: 1 input_dim: 3 input_dim: 24 input_dim: 24
layers { name: "conv1" type: CONVOLUTION bottom: "data" top: "conv1"
  convolution_param { num_output: 16 kernel_size: 3
    weight_filler { type: "xavier" } } }
layers { name: "relu1" type: RELU bottom: "conv1" top: "conv1" }
layers { name: "fc" type: INNER_PRODUCT bottom: "conv1" top: "fc"
  inner_product_param { num_output: 10 weight_filler { type: "xavier" } } }
layers { name: "prob" type: SOFTMAX bottom: "fc" top: "prob" }
"""


def search_space(smoke: bool, n_devices: int) -> Dict[str, List]:
    """The candidate sets per knob. Smoke keeps every measured knob at a
    2-point space (tier-1-safe); the full space is what a TPU re-tune
    sweeps. The built-in default is ALWAYS a candidate, so a winner can
    never measure worse than the default it replaces."""
    return {
        "conv_layout": ["NCHW", "NHWC"],
        "conv_strategy": ["", "auto"],
        "arena_bucket_mb": [1.0, 4.0] if smoke else [1.0, 4.0, 16.0],
        "steps_per_dispatch": [1] if smoke else [1, 4],
        "pipeline": ([(0, 1), (2, 2)] if smoke
                     else [(0, 1), (2, 2), (2, 4)]),
        "serve_buckets": (["1,4", "1,2,4"] if smoke
                          else ["1,4,16,64", "1,8,32,64", "1,2,8,32,64"]),
        "mesh": _mesh_candidates(n_devices, smoke),
        # LLM serving (serving/continuous.py): KV page size, decode-batch
        # rung ladder, replica x tp factorization — all measured against a
        # deep-overload burst through the continuous scheduler (the
        # offered-load operating point the bench's goodput curve saturates
        # at). The built-in default is always a candidate.
        "llm_page_size": [16, 64] if smoke else [16, 64, 128],
        "llm_decode_rungs": (["1,2,4,8", "1,4"] if smoke
                             else ["1,2,4,8", "1,4,8", "1,2,4,8,16"]),
        "llm_replicas_tp": _llm_factorizations(n_devices, smoke),
        # managed DCN wire dtype, measured over a throttled loopback link
        # (the f32 default is always a candidate — revert-if-losing)
        "wire_dtype": ["", "bf16"] if smoke else ["", "bf16", "f16", "int8"],
        # the (remat, batch_size) coordinate pair: at a fixed budget (the
        # no-remat default-batch measured peak) find the largest
        # admissible batch per remat policy, race on img/s ('' default
        # always a candidate — revert-if-losing)
        "remat_batch": ["", "auto"],
    }


def _llm_factorizations(n_devices: int, smoke: bool) -> List[str]:
    """Replica x tp candidates ("RxT") for the LLM fleet: all devices to
    replicas (throughput), or half to tp2 (larger models per replica,
    fewer rows in flight). Smoke keeps the single trivial arm (recorded,
    never a silent cap)."""
    if n_devices <= 1 or smoke:
        return ["1x1"]
    cands = [f"{n_devices}x1"]
    if n_devices % 2 == 0:
        cands.append(f"{n_devices // 2}x2")
    return cands


def _mesh_candidates(n_devices: int, smoke: bool) -> List[str]:
    if n_devices <= 1 or smoke:
        # one device has one factorization; smoke skips the (expensive)
        # spmd arms — both cases are recorded as the only candidate, never
        # a silent cap (the trial row says so)
        return [""]
    cands = [""]                      # flat data mesh (the default)
    if n_devices % 2 == 0:
        cands += [f"dp{n_devices // 2},fsdp2", f"dp{n_devices // 2},tp2"]
    return cands


def _model_setup(model: str, smoke: bool):
    """(net_param, source_shapes) for one tune target at a measurement-
    sized PER-DEVICE batch (trials measure RELATIVE knob cost; the tiny
    smoke shapes keep tier-1 honest and fast)."""
    from ..models import zoo
    if model == "lenet":
        batch = 8 if smoke else 64
        return zoo.lenet(with_accuracy=False), \
            {"data": (batch, 1, 28, 28), "label": (batch,)}
    if model == "alexnet":
        batch, image = (4, 67) if smoke else (32, 227)
        return zoo.alexnet(num_classes=1000, with_accuracy=False), \
            {"data": (batch, 3, image, image), "label": (batch,)}
    if model == "googlenet":
        batch = 2 if smoke else 16
        return zoo.googlenet(num_classes=1000, with_accuracy=False), \
            {"data": (batch, 3, 224, 224), "label": (batch,)}
    raise ValueError(f"unknown tune model {model!r}; choose from "
                     f"{TUNE_MODELS}")


def _build_step_arm(net_param, shapes, conv_layout: str, arena_mb: float,
                    scan_steps: int, mesh_spec: str,
                    conv_strategy: str = "", remat: str = "",
                    measure_peak: bool = False):
    """One measured arm: a compiled train step under one knob assignment,
    returned as a zero-arg blocked callable (state threads through a
    holder so successive calls are real successive steps). The callable's
    ``per_call_steps`` attribute normalizes scan arms to per-optimizer-
    step time.

    ``remat="auto"`` checkpoints every eligible layer (the zero-budget
    maximal plan — what the (remat, batch) stage races against the
    stored-activation default); any other non-empty ``remat`` is a
    comma-joined explicit layer list (the Engine ``--remat`` flag
    semantics — bench.py memory's budget-planned arm rides this).
    ``measure_peak=True`` additionally
    AOT-compiles the step and records its real ``memory_analysis()``
    peak as ``run.peak_bytes`` (a second compile — only the remat stage
    pays it)."""
    import jax
    import jax.numpy as jnp

    from .. import config
    from ..core import remat as remat_mod
    from ..core.net import Net
    from ..parallel import (CommConfig, build_train_step, init_train_state,
                            make_mesh)
    from ..proto.messages import SolverParameter

    with config.policy_scope(conv_layout=conv_layout):
        net = Net(net_param, phase="TRAIN", source_shapes=dict(shapes),
                  conv_strategy=conv_strategy or None)
    sp = SolverParameter(base_lr=0.01, lr_policy="fixed", momentum=0.9,
                         weight_decay=5e-4)
    # the knob under measurement: the gradient buckets' size (on one
    # device the step builds no buckets and every arm is the same program)
    comm = CommConfig(param_arena=True, arena_bucket_mb=float(arena_mb))
    nhwc = net.conv_layout == "NHWC"
    in_layout = "NHWC" if nhwc else "NCHW"
    rp = None
    if remat == "auto":
        from .attribution import layer_cost_table
        rp = remat_mod.plan_remat(
            layer_cost_table(net), 0, 0,
            candidates=remat_mod.remat_candidates(net), source="plan")
    elif remat:
        rp = remat_mod.RematPlan(
            layers=tuple(t.strip() for t in remat.split(",") if t.strip()),
            source="flag")
    if mesh_spec:
        from ..config import MeshConfig
        from ..parallel.spmd import ShardingPlan, named_mesh
        mesh_cfg = MeshConfig.parse(mesh_spec)
        mesh = named_mesh(mesh_cfg)
        plan = ShardingPlan.build(net, mesh_cfg, comm)
        ts = build_train_step(net, sp, mesh, comm, plan=plan,
                              input_layout=in_layout, remat_plan=rp)
        n_batch_dev = mesh_cfg.data * mesh_cfg.fsdp
    else:
        ts = build_train_step(net, sp, make_mesh(), comm,
                              scan_steps=scan_steps if scan_steps > 1
                              else None,
                              scan_reuse_batch=True, input_layout=in_layout,
                              remat_plan=rp)
        n_batch_dev = jax.device_count()
    params = net.init(jax.random.PRNGKey(0))
    state = init_train_state(params, comm, jax.device_count())
    # the prototxt batch contract: per-device rows in the net, global rows
    # on the wire (bench.py's _build semantics); NHWC arms feed channels-
    # last directly so the hot path carries zero entry transposes
    rows = int(shapes["data"][0]) * n_batch_dev
    chw = tuple(shapes["data"][1:])
    data_shape = (chw[1], chw[2], chw[0]) if nhwc else chw
    k1, k2 = jax.random.split(jax.random.PRNGKey(0))
    batch = {
        "data": jax.device_put(
            jax.random.uniform(k1, (rows,) + data_shape, jnp.float32),
            ts.batch_sharding),
        "label": jax.device_put(jax.random.randint(k2, (rows,), 0, 10),
                                ts.batch_sharding),
    }
    jax.block_until_ready(batch["data"])
    holder = {"params": params, "state": state}
    rng = jax.random.PRNGKey(1)

    def run():
        p, s, m = ts.step(holder["params"], holder["state"], batch, rng)
        holder["params"], holder["state"] = p, s
        jax.block_until_ready(m["loss"])

    run.per_call_steps = max(1, ts.scan_steps or 1)  # type: ignore
    run.global_rows = rows  # type: ignore
    if measure_peak:
        compiled = ts.lowerable.lower(params, state, batch, rng).compile()

        # the AOT compile does NOT seed the jit call cache, so timing
        # through ts.step would compile the same program a second time
        # (minutes per arm on the CPU proxy's conv models) — run the AOT
        # executable itself instead
        def run_aot():
            # the raw device_step returns (params, state, metrics, dumps)
            # — ts.step's wrapper strips the tail, the AOT call does not
            out = compiled(holder["params"], holder["state"], batch, rng)
            holder["params"], holder["state"] = out[0], out[1]
            jax.block_until_ready(out[2]["loss"])

        run_aot.per_call_steps = run.per_call_steps  # type: ignore
        run_aot.global_rows = rows  # type: ignore
        run_aot.peak_bytes = remat_mod.measured_peak_bytes(  # type: ignore
            compiled)
        return run_aot
    return run


def _measure_step_knob(net_param, shapes, current: Dict[str, Any],
                       knob: str, candidates: List, windows: int,
                       iters: int) -> Dict[str, float]:
    """Measure one step-level knob's candidates with every other knob held
    at its current best; ms are per OPTIMIZER step."""
    arms: Dict[str, Callable] = {}
    for cand in candidates:
        cfg = dict(current)
        cfg[knob] = cand
        arms[str(cand)] = _build_step_arm(
            net_param, shapes,
            conv_layout=cfg["conv_layout"],
            arena_mb=float(cfg["arena_bucket_mb"]),
            scan_steps=int(cfg["steps_per_dispatch"]),
            mesh_spec=cfg.get("mesh", ""),
            conv_strategy=cfg.get("conv_strategy", ""))
    raw = interleaved_min_ms(arms, windows=windows, iters=iters)
    return {name: round(raw[name] / arms[name].per_call_steps, 4)
            for name in raw}


def _measure_remat_batch(net_param, shapes, current: Dict[str, Any],
                         windows: int, iters: int,
                         max_doublings: int = 3) -> Dict[str, Any]:
    """The (remat, batch_size) coordinate pair at a FIXED byte budget.

    The budget is the no-remat default-batch step's measured
    ``memory_analysis()`` peak — i.e. "the HBM this job config already
    needs". Per remat policy ('' stored activations, 'auto' maximal
    checkpoint) the largest ADMISSIBLE batch is found by doubling from
    the default while the measured peak stays within the budget (at most
    ``max_doublings`` doublings — recorded, never a silent cap); the
    arms then race on img/s through ``interleaved_min_ms``. Remat wins
    only when dropping activations buys enough extra batch to beat the
    default's throughput — the revert-if-losing discipline.

    Returns {"remat", "batch_size", "hbm_budget_gb", "trial"}."""
    def make(policy: str, batch: int, measure_peak: bool):
        s = dict(shapes)
        s["data"] = (batch,) + tuple(shapes["data"][1:])
        s["label"] = (batch,)
        return _build_step_arm(
            net_param, s, current["conv_layout"],
            float(current["arena_bucket_mb"]), 1, "",
            current.get("conv_strategy", ""), remat=policy,
            measure_peak=measure_peak)

    base_batch = int(shapes["data"][0])
    probes: Dict[Tuple[str, int], Any] = {}
    probes[("", base_batch)] = make("", base_batch, True)
    budget = int(probes[("", base_batch)].peak_bytes)
    trial: Dict[str, Any] = {
        "budget_bytes": budget, "base_batch": base_batch,
        "max_doublings": max_doublings, "arms": {}}
    if budget <= 0:
        # no memory API on this backend: nothing to plan against — the
        # default wins by fiat, and the doc says why
        trial["note"] = ("memory_analysis() reported no peak; remat/"
                         "batch not measured on this backend")
        return {"remat": "", "batch_size": 0, "hbm_budget_gb": 0.0,
                "trial": trial}
    best: Dict[str, Tuple[int, Any]] = {}
    for policy in ("", "auto"):
        b, arm = base_batch, probes.get((policy, base_batch))
        if arm is None:
            arm = make(policy, base_batch, True)
        if arm.peak_bytes > budget and policy:  # remat arm at base batch
            # can only be <= the default's peak, but keep the guard honest
            trial["arms"][policy or "default"] = {
                "batch": base_batch, "peak_bytes": int(arm.peak_bytes),
                "admissible": False}
            continue
        for _ in range(max_doublings):
            nxt = make(policy, b * 2, True)
            if nxt.peak_bytes > budget:
                break
            b, arm = b * 2, nxt
        best[policy] = (b, arm)
        trial["arms"][policy or "default"] = {
            "batch": b, "peak_bytes": int(arm.peak_bytes),
            "admissible": True}
    fns = {(p or "default"): arm for p, (b, arm) in best.items()}
    raw = interleaved_min_ms(fns, windows=windows, iters=iters)
    imgs = {}
    for p, (b, arm) in best.items():
        name = p or "default"
        ms = raw[name] / arm.per_call_steps
        imgs[name] = arm.global_rows / max(ms, 1e-9) * 1e3  # img/s
        trial["arms"][name].update(step_ms=round(ms, 4),
                                   img_per_s=round(imgs[name], 1))
    winner = max(imgs, key=imgs.get)
    default_ips = imgs.get("default", 0.0)
    if winner != "default" and imgs[winner] <= default_ips:
        winner = "default"
    trial["winner"] = winner
    trial["speedup"] = round(imgs[winner] / max(default_ips, 1e-9), 4)
    policy = "" if winner == "default" else winner
    # the budget knob ships only with a winning remat row: a default win
    # must not make every later train run re-pay the measuring compile
    # for an identity plan (the trial row keeps budget_bytes either way)
    return {"remat": policy,
            "batch_size": int(best[policy][0]) if policy in best
            else base_batch,
            "hbm_budget_gb": (round(budget / 2**30, 6) if policy
                              else 0.0),
            "trial": trial}


def _measure_pipeline_knob(candidates: List[Tuple[int, int]], windows: int,
                           iters: int) -> Dict[str, float]:
    """Engine-loop wall per iteration for (device_prefetch, max_in_flight)
    candidates, through real Engine.train loops over a small MEMORY_DATA
    net (the knobs act on host blocking, which a bare compiled step cannot
    see). Interleaved windows, min per arm."""
    import tempfile

    import numpy as np

    from ..proto.messages import SolverParameter, load_net_from_string
    from .engine import Engine

    import shutil

    rs = np.random.RandomState(0)
    md = {"data": rs.randn(256, 3, 20, 20).astype(np.float32),
          "label": rs.randint(0, 10, 256)}
    net_param = load_net_from_string(_PIPE_NET % 8)
    engines: Dict[str, Any] = {}
    scratch = tempfile.mkdtemp(prefix="tune_pipe_")
    try:
        for pf, mif in candidates:
            sp = SolverParameter(train_net_param=net_param, base_lr=0.01,
                                 lr_policy="fixed", momentum=0.9, display=0,
                                 max_iter=0, random_seed=3)
            out_dir = os.path.join(scratch, f"{pf}_{mif}")
            os.makedirs(out_dir, exist_ok=True)
            eng = Engine(sp, memory_data=md, output_dir=out_dir,
                         device_prefetch=pf, max_in_flight=mif)
            eng._write_artifacts = lambda: None   # disk noise off the clock
            engines[f"{pf},{mif}"] = eng
        done = {name: 0 for name in engines}
        for name, eng in engines.items():        # warm: compile + fill
            eng.train(max_iter=2)
            done[name] = 2
        best = {name: float("inf") for name in engines}
        order = list(engines)
        for w in range(max(1, windows)):
            seq = order if w % 2 == 0 else list(reversed(order))
            for name in seq:
                eng = engines[name]
                t0 = time.perf_counter()
                eng.train(max_iter=done[name] + iters)
                best[name] = min(best[name],
                                 (time.perf_counter() - t0) / iters)
                done[name] += iters
        return {name: round(v * 1e3, 4) for name, v in best.items()}
    finally:
        for eng in engines.values():
            eng.close()
        shutil.rmtree(scratch, ignore_errors=True)


def _measure_serve_knob(candidates: List[str], windows: int, iters: int,
                        deploy: str = "") -> Dict[str, float]:
    """Mean request wall (ms) per bucket ladder: every ladder serves the
    same request-size sweep (1..max rows) through a warmed
    BucketedExecutor. Uses the deploy prototxt when given, else the
    synthetic probe net."""
    import numpy as np

    import jax

    from ..core.net import Net
    from ..proto.messages import load_net, load_net_from_string
    from ..serving.executor import BucketedExecutor, parse_buckets

    net_param = (load_net(deploy) if deploy
                 else load_net_from_string(_SERVE_NET))
    net = Net(net_param, "TEST")
    params = net.init(jax.random.PRNGKey(0))
    name = net.input_names[0]
    row_shape = tuple(net.blob_shapes[name][1:])
    max_rows = max(parse_buckets(spec)[-1] for spec in candidates)
    frames = np.random.RandomState(0).randn(
        max_rows, *row_shape).astype(np.float32)
    arms: Dict[str, Callable] = {}
    n_requests: Dict[str, int] = {}
    for spec in candidates:
        ex = BucketedExecutor(net, params, buckets=parse_buckets(spec))
        sizes = list(range(1, ex.max_batch + 1))
        n_requests[spec] = len(sizes)

        def run(ex=ex, sizes=sizes):
            for n in sizes:
                ex.infer({name: frames[:n]})

        arms[spec] = run
    raw = interleaved_min_ms(arms, windows=windows, iters=iters, warmup=1)
    return {spec: round(raw[spec] / n_requests[spec], 4) for spec in raw}


def _measure_llm_knob(arm_specs: Dict[str, Tuple[int, str, int, int]],
                      windows: int, iters: int) -> Dict[str, float]:
    """ms-per-generated-token at DEEP OVERLOAD for each LLM serving arm.

    ``arm_specs``: name -> (page_size, decode_rungs, replicas, tp). Every
    arm serves the same burst of concurrent generate requests (more
    requests than any rung holds, i.e. the saturated end of the offered-
    load curve — where the knob choice actually matters) through real
    :class:`ContinuousScheduler` instances over a tiny probe transformer;
    interleaved windows + min-of-k as everywhere else. tp > 1 arms build
    a (1,1,tp) named mesh per replica."""
    import threading

    import jax
    import numpy as np

    from ..config import MeshConfig
    from ..models.transformer import TransformerConfig, init_params
    from ..serving.continuous import GenerateExecutor, parse_rungs

    cfg = TransformerConfig(vocab_size=128, d_model=32, n_heads=4,
                            n_layers=2, max_seq=256)
    params = init_params(cfg, jax.random.PRNGKey(0))
    p_len, max_new, n_req = 8, 8, 12
    prompts = np.random.RandomState(0).randint(
        0, cfg.vocab_size, size=(n_req, p_len)).astype(np.int32)
    arms: Dict[str, Callable] = {}
    all_scheds = []
    for aname, (page, rungs, reps, tp) in arm_specs.items():
        scheds = []
        for _ in range(int(reps)):
            mesh_cfg = (MeshConfig(data=1, fsdp=1, tp=int(tp))
                        if int(tp) > 1 else None)
            ex = GenerateExecutor(
                cfg, params, page_size=int(page),
                decode_rungs=parse_rungs(rungs), prompt_buckets=(p_len,),
                max_seq_len=cfg.max_seq, default_max_new=max_new,
                mesh_cfg=mesh_cfg)
            scheds.append(ex.make_batcher(max_queue=n_req))
        all_scheds.extend(scheds)

        def run(scheds=scheds):
            errs: List[BaseException] = []

            def worker(i):
                try:
                    scheds[i % len(scheds)].submit(
                        {"prompt": prompts[i], "max_new": max_new},
                        timeout_s=120.0)
                except BaseException as e:  # noqa: BLE001 — surface below
                    errs.append(e)

            ts = [threading.Thread(target=worker, args=(i,))
                  for i in range(n_req)]
            for t in ts:
                t.start()
            for t in ts:
                t.join()
            if errs:
                raise errs[0]

        arms[aname] = run
    try:
        raw = interleaved_min_ms(arms, windows=windows, iters=iters,
                                 warmup=1)
    finally:
        for s in all_scheds:
            s.close(drain=False, timeout_s=5.0)
    per_tok = n_req * max_new
    return {name: round(raw[name] / per_tok, 4) for name in raw}


def _measure_wire_knob(candidates: List[str], windows: int, iters: int,
                       link_mbps: float = 8.0, side: int = 96,
                       clocks: int = 4, staleness: int = 0
                       ) -> Dict[str, float]:
    """Wall time of a fixed push/gate/refresh cadence per wire dtype over
    a THROTTLED loopback link (FaultProxy token bucket) — the operating
    point where wire compression pays its encode cost back. Each arm
    drives its own ParamService through its own throttled proxy with an
    :class:`AsyncSSPClient` configured for that dtype; interleaved
    windows + min-of-k as everywhere else. The sync point is the SERVICE
    side (poll the applied clock until every push landed): push() is
    asynchronous and a 1-worker gate never waits on its own clock, so
    only server-side apply bounds the throttled uplink transfer. The ''
    (f32, byte-for-byte) default is always a candidate, so a winner can
    never measure worse than the exact path it replaces."""
    import numpy as np

    from ..parallel.async_ssp import AsyncSSPClient, ParamService
    from .faults import FaultProxy, FaultRule

    rate_bps = link_mbps * 1e6 / 8.0
    params = {"fc": {"w": np.zeros((side, side), np.float32)}}
    arms: Dict[str, Callable] = {}
    closers = []
    for wd in candidates:
        svc = ParamService(params, n_workers=1)
        proxy = FaultProxy(("127.0.0.1", svc.port))
        # burst far below one frame, so transfer time tracks frame bytes
        proxy.add_rule(FaultRule(action="throttle", rate_bps=rate_bps,
                                 burst_bytes=8192))
        # no bandwidth budget: every push is a FULL flush (still wire-
        # compressed), so the arm measures the dtype's byte savings over
        # the throttled link, not the budget scheduler's deferral policy
        cli = AsyncSSPClient(0, proxy.addr, staleness, n_workers=1,
                             wire_dtype=wd)
        closers.append((cli, proxy, svc))
        rng = np.random.RandomState(11)
        state = {"clock": 0}

        def run(cli=cli, svc=svc, rng=rng, state=state):
            for _ in range(clocks):
                state["clock"] += 1
                cli.push({"fc": {"w": rng.randn(side, side)
                                 .astype(np.float32) * 1e-3}})
                cli.gate(state["clock"])
            deadline = time.monotonic() + 60.0
            while svc.clocks.get(0, -1) < state["clock"] - 1:
                if time.monotonic() > deadline:
                    raise TimeoutError("wire-knob arm: pushes not applied")
                time.sleep(0.001)

        arms[wd or "f32"] = run
    try:
        return interleaved_min_ms(arms, windows=windows, iters=iters,
                                  warmup=1)
    finally:
        for cli, proxy, svc in closers:
            cli.close()
            proxy.close()
            svc.close()


def _conv_strategy_rows(net_param, shapes, conv_layout: str,
                        cache_dir: str) -> Dict[str, Dict]:
    """Run the PR-11 per-layer conv tuner for this model (persisting the
    winners into THIS plan's store so a plan-applied conv_strategy="auto"
    memo-hits) and return the per-layer decision docs."""
    from .. import config
    from ..core.net import Net
    from ..ops import conv_tune

    saved = config.compile_cache_config().cache_dir
    config.set_compile_cache_config(cache_dir=cache_dir)
    try:
        with config.policy_scope(conv_layout=conv_layout):
            net = Net(net_param, phase="TRAIN", source_shapes=dict(shapes),
                      conv_strategy="auto")
        rows: Dict[str, Dict] = {}
        for layer in net.layers:
            if layer.TYPE != "CONVOLUTION":
                continue
            n, c, h, w = net.blob_shapes[layer.lp.bottom[0]]
            doc = conv_tune.resolve(       # memo hit: Net already measured
                layer.name, c, h, w, layer.kernel, layer.stride, layer.pad,
                layer.group, layer.params[0].shape[0], layer.run_layout, n,
                cache_dir=cache_dir)
            rows[layer.name] = {"winner": doc["winner"],
                                "source": doc.get("source"),
                                "timings_ms": doc.get("timings_ms", {})}
        return rows
    finally:
        config.set_compile_cache_config(cache_dir=saved)


def _builtin_layout(backend: str) -> str:
    """The pre-plan hardcoded per-backend row — the default arm every
    conv_layout trial measures against."""
    from ..numeric import resolve_conv_layout
    return resolve_conv_layout("auto", backend=backend, consult_plan=False)


def run_tune(model: str, *, smoke: bool = False, force: bool = False,
             cache_dir: Optional[str] = None, deploy: str = "",
             windows: Optional[int] = None, iters: Optional[int] = None,
             net_param=None, source_shapes=None,
             knobs: Optional[List[str]] = None) -> Dict[str, Any]:
    """The tune search: short measured trials over the policy space, one
    persisted TunedPlan with provenance. Returns ``{"doc", "source",
    "path", "store"}`` where source is "persisted" (memo-hit: a valid plan
    for this exact (model, backend, device kind, n_devices, jax version)
    already exists — re-measurement skipped) or "measured".

    ``net_param``/``source_shapes`` let tests tune a programmatic net under
    ``model`` as the plan name; ``knobs`` restricts the measured subset
    (restrictions are RECORDED in the doc's ``skipped`` map — never a
    silent cap)."""
    import jax

    backend = jax.default_backend()
    kind = jax.devices()[0].device_kind
    n_devices = jax.device_count()
    store = store_dir(cache_dir)
    key = plan_key(model, backend, n_devices)

    if not force:
        doc = load_plan(model, backend, n_devices, cache_dir=store)
        if doc is not None:
            log(f"[tune] {model}/{backend}: plan {key[:12]} already "
                f"persisted (measured {doc.get('measured_at')}); "
                f"memo-hit, skipping re-measurement (--force re-tunes)")
            return {"doc": doc, "source": "persisted", "store": store,
                    "path": tuned_path(store, PLAN_NAMESPACE, key)}

    t_start = time.perf_counter()
    if net_param is None:
        net_param, source_shapes = _model_setup(model, smoke)
    windows = windows if windows is not None else (2 if smoke else 4)
    iters = iters if iters is not None else (2 if smoke else 4)
    space = search_space(smoke, n_devices)
    wanted = list(knobs) if knobs else list(space)
    skipped = {k: "restricted by knobs argument"
               for k in space if k not in wanted}
    trials: Dict[str, Dict] = {}
    current: Dict[str, Any] = {
        "conv_layout": _builtin_layout(backend),
        "conv_strategy": "",
        "arena_bucket_mb": BUILTIN_DEFAULTS["arena_bucket_mb"],
        "steps_per_dispatch": BUILTIN_DEFAULTS["steps_per_dispatch"],
        "mesh": "",
    }
    default_cfg = dict(current)

    def note(knob, cands, timings, winner, source):
        trials[knob] = {"candidates": [str(c) for c in cands],
                        "timings_ms": timings, "winner": str(winner),
                        "source": source}
        ranked = ", ".join(f"{n}={timings[n]}ms"
                           for n in sorted(timings, key=timings.get))
        log(f"[tune] {model}.{knob}: -> {winner} [{source}]"
            + (f" ({ranked})" if ranked else ""))

    # ---- step-level knobs, greedy coordinate order ---------------------- #
    for knob, cands in (("conv_layout", space["conv_layout"]),
                        ("arena_bucket_mb", space["arena_bucket_mb"]),
                        ("steps_per_dispatch",
                         space["steps_per_dispatch"]),
                        ("mesh", space["mesh"])):
        if knob in skipped:
            continue
        if len(cands) == 1:
            current[knob] = cands[0]
            note(knob, cands, {}, cands[0],
                 "only-candidate" + (" (smoke skips the spmd arms)"
                                     if knob == "mesh" and n_devices > 1
                                     else ""))
            continue
        timings = _measure_step_knob(net_param, source_shapes, current,
                                     knob, cands, windows, iters)
        winner_s = min(timings, key=timings.get)
        current[knob] = next(c for c in cands if str(c) == winner_s)
        note(knob, cands, timings, current[knob], "measured")

    # ---- per-layer conv strategy (the PR-11 tuner, one plan row) -------- #
    if "conv_strategy" not in skipped:
        if any(lp.canonical_type() == "CONVOLUTION"
               for lp in net_param.layers):
            rows = _conv_strategy_rows(net_param, source_shapes,
                                       current["conv_layout"], store)
            current["conv_strategy"] = "auto"
            trials["conv_strategy"] = {
                "candidates": ["", "auto"], "winner": "auto",
                "source": "measured-per-layer", "per_layer": rows}
            log(f"[tune] {model}.conv_strategy: -> auto (per-layer: "
                + ", ".join(f"{k}={v['winner']}" for k, v in rows.items())
                + ")")
        else:
            skipped["conv_strategy"] = "model has no conv layers"

    # ---- composite default-vs-tuned full-step A/B ----------------------- #
    if any(current[k] != default_cfg[k] for k in default_cfg):
        from .. import config
        saved_cc = config.compile_cache_config().cache_dir
        if current["conv_strategy"]:
            # the tuned arm's Net(conv_strategy="auto") must memo-hit the
            # winners persisted above, not re-measure inside the A/B
            config.set_compile_cache_config(cache_dir=store)
        try:
            arms = {
                "default": _build_step_arm(
                    net_param, source_shapes, default_cfg["conv_layout"],
                    float(default_cfg["arena_bucket_mb"]),
                    int(default_cfg["steps_per_dispatch"]),
                    default_cfg["mesh"], default_cfg["conv_strategy"]),
                "tuned": _build_step_arm(
                    net_param, source_shapes, current["conv_layout"],
                    float(current["arena_bucket_mb"]),
                    int(current["steps_per_dispatch"]),
                    current["mesh"], current["conv_strategy"]),
            }
        finally:
            config.set_compile_cache_config(cache_dir=saved_cc)
        raw = interleaved_min_ms(arms, windows=max(windows, 3), iters=iters)
        d_ms = raw["default"] / arms["default"].per_call_steps
        t_ms = raw["tuned"] / arms["tuned"].per_call_steps
        ab = {"default_step_ms": round(d_ms, 4),
              "tuned_step_ms": round(t_ms, 4),
              "speedup": round(d_ms / max(t_ms, 1e-9), 4),
              "reverted": False}
        if ab["speedup"] < 1.0:
            # a cost-based optimizer never ships a plan it measured to be
            # slower than the defaults: revert the step knobs, keep the
            # losing measurement on record
            log(f"[tune] {model}: composite tuned arm measured "
                f"{ab['speedup']}x vs defaults — REVERTING step knobs to "
                f"built-in defaults (per-knob wins did not compose)")
            ab.update(raw_speedup=ab["speedup"], reverted=True, speedup=1.0)
            current.update(default_cfg)
    else:
        ab = {"speedup": 1.0,
              "note": "every measured winner equals the built-in default; "
                      "the arms are the same program"}

    # ---- engine-loop pipeline knobs ------------------------------------- #
    pf = BUILTIN_DEFAULTS["device_prefetch"]
    mif = BUILTIN_DEFAULTS["max_in_flight"]
    if "pipeline" not in skipped:
        timings = _measure_pipeline_knob(space["pipeline"], windows, iters)
        winner_s = min(timings, key=timings.get)
        pf, mif = (int(tok) for tok in winner_s.split(","))
        note("pipeline", space["pipeline"], timings, winner_s, "measured")

    # ---- serving bucket ladder ------------------------------------------ #
    serve_buckets = BUILTIN_DEFAULTS["serve_buckets"]
    if "serve_buckets" not in skipped:
        timings = _measure_serve_knob(space["serve_buckets"], windows,
                                      iters, deploy=deploy)
        serve_buckets = min(timings, key=timings.get)
        note("serve_buckets", space["serve_buckets"], timings,
             serve_buckets,
             "measured" + ("" if deploy else " (synthetic probe net)"))

    # ---- managed DCN wire dtype ----------------------------------------- #
    wire_dtype = str(BUILTIN_DEFAULTS["wire_dtype"])
    if "wire_dtype" not in skipped:
        cands = space["wire_dtype"]
        timings = _measure_wire_knob(cands, windows, iters)
        winner_s = min(timings, key=timings.get)
        wire_dtype = next(c for c in cands if (c or "f32") == winner_s)
        note("wire_dtype", [c or "f32" for c in cands], timings,
             wire_dtype or "f32",
             "measured (throttled loopback; f32 default always a "
             "candidate)")

    # ---- measured HBM budget: the (remat, batch_size) pair --------------- #
    # at the job config's own measured peak as the budget, does dropping
    # activations buy enough extra batch to win on img/s? ('' stored-
    # activation default always a candidate — revert-if-losing)
    remat = str(BUILTIN_DEFAULTS["remat"])
    tuned_batch = int(BUILTIN_DEFAULTS["batch_size"])
    hbm_gb = float(BUILTIN_DEFAULTS["hbm_budget_gb"])
    if "remat_batch" not in skipped:
        rb = _measure_remat_batch(net_param, source_shapes, current,
                                  windows, iters)
        remat, tuned_batch = rb["remat"], rb["batch_size"]
        hbm_gb = rb["hbm_budget_gb"]
        arms = rb["trial"].get("arms", {})
        note("remat_batch", list(arms),
             {n: a.get("step_ms", 0.0) for n, a in arms.items()
              if "step_ms" in a},
             f"{remat or 'default'}@batch{tuned_batch or '-'}",
             "measured (img/s at fixed measured-peak budget)")
        trials["remat_batch"].update(rb["trial"])  # the full per-arm rows

    # ---- LLM serving: page size, rung ladder, replica x tp --------------- #
    # greedy coordinate descent at the deep-overload operating point (the
    # saturated end of the offered-load curve bench.py serving_llm sweeps);
    # each later knob is measured under the earlier winners
    llm_page = int(BUILTIN_DEFAULTS["llm_page_size"])
    llm_rungs = str(BUILTIN_DEFAULTS["llm_decode_rungs"])
    llm_rt = str(BUILTIN_DEFAULTS["llm_replicas_tp"])
    if "llm_page_size" not in skipped:
        cands = space["llm_page_size"]
        timings = _measure_llm_knob(
            {str(p): (p, llm_rungs, 1, 1) for p in cands}, windows, iters)
        llm_page = int(min(timings, key=timings.get))
        note("llm_page_size", cands, timings, llm_page, "measured")
    if "llm_decode_rungs" not in skipped:
        cands = space["llm_decode_rungs"]
        timings = _measure_llm_knob(
            {r: (llm_page, r, 1, 1) for r in cands}, windows, iters)
        llm_rungs = min(timings, key=timings.get)
        note("llm_decode_rungs", cands, timings, llm_rungs, "measured")
    if "llm_replicas_tp" not in skipped:
        cands = space["llm_replicas_tp"]
        if len(cands) == 1:
            llm_rt = cands[0]
            note("llm_replicas_tp", cands, {}, llm_rt,
                 "only-candidate" + (" (smoke skips the fleet arms)"
                                     if smoke and n_devices > 1 else ""))
        else:
            specs = {}
            for c in cands:
                reps, tp = (int(t) for t in c.split("x"))
                specs[c] = (llm_page, llm_rungs, reps, tp)
            timings = _measure_llm_knob(specs, windows, iters)
            llm_rt = min(timings, key=timings.get)
            note("llm_replicas_tp", cands, timings, llm_rt, "measured")

    search_cost_s = round(time.perf_counter() - t_start, 2)
    doc = {
        "version": PLAN_VERSION,
        "model": model.lower(),
        "backend": backend,
        "device_kind": kind,
        "jax_version": jax.__version__,
        "n_devices": n_devices,
        "key": key,
        "smoke": smoke,
        "knobs": {
            "conv_layout": current["conv_layout"],
            "conv_strategy": current["conv_strategy"],
            "arena_bucket_mb": float(current["arena_bucket_mb"]),
            "steps_per_dispatch": int(current["steps_per_dispatch"]),
            "mesh": current["mesh"],
            "device_prefetch": int(pf),
            "max_in_flight": int(mif),
            "serve_buckets": serve_buckets,
            "llm_page_size": llm_page,
            "llm_decode_rungs": llm_rungs,
            # prompt buckets ride the defaults (prompt-length DISTRIBUTION
            # is workload data the probe net cannot stand in for)
            "llm_prompt_buckets": str(BUILTIN_DEFAULTS["llm_prompt_buckets"]),
            "llm_replicas_tp": llm_rt,
            "wire_dtype": wire_dtype,
            "remat": remat,
            "batch_size": tuned_batch,
            "hbm_budget_gb": hbm_gb,
        },
        "trials": trials,
        "ab": ab,
        "search_space": {k: [str(c) for c in v] for k, v in space.items()},
        "skipped": skipped,
        "search_cost_s": search_cost_s,
        "measured_at": time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime()),
    }
    path = save_plan(doc, cache_dir=store)
    log(f"[tune] {model}/{backend}/{kind}: plan {key[:12]} persisted to "
        f"{path} ({search_cost_s}s search"
        + (f", skipped: {skipped}" if skipped else "") + ")")
    return {"doc": doc, "source": "measured", "store": store, "path": path}
