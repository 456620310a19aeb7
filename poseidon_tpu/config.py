"""Global configuration: numeric policy and fault-tolerance policy.

The numeric policy (the TPU analog of Caffe's Dtype template parameter)
lives in ``poseidon_tpu.numeric`` and is re-exported here lazily: the
socket-tier processes (async-SSP workers spawned per host, the fault
proxy, a ParamService-only rank) import ``poseidon_tpu`` at startup, and
an eager ``import jax.numpy`` here would cost them multi-second process
startup that reads as silence to the service's liveness monitor. Anything
jax-side keeps its spelling — ``config.policy()``,
``from ..config import matmul_precision`` — and pays the jax import on
first touch, which for jax-side code has already happened.

The fault-tolerance policy (``FaultConfig``) is eager and dependency-free.
"""

from __future__ import annotations

import os
from dataclasses import dataclass

# Numeric-policy names re-exported from poseidon_tpu.numeric via the
# module __getattr__ below (PEP 562).
_NUMERIC_NAMES = frozenset({
    "Policy", "policy", "set_policy", "set_perf_policy", "policy_scope",
    "matmul_precision", "resolve_conv_layout",
})


def __getattr__(name):
    if name in _NUMERIC_NAMES:
        from . import numeric
        return getattr(numeric, name)
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")


@dataclass(frozen=True)
class MeshConfig:
    """Named-axis SPMD mesh request (``--mesh dp2,fsdp2,tp1``).

    Three axes, all data-independent mechanisms (parallel/spmd.py):
      ``data`` — classic data parallelism (replicated params, batch shards);
      ``fsdp`` — batch shards PLUS parameter-arena sharding: arena buckets
                 live 1/fsdp per device, gradients reduce-scatter, params
                 all-gather (the ZeRO trade);
      ``tp``   — tensor parallelism: FC layers take column/row weight
                 shards, activations reshard at planner-chosen points.
    Sizes of 1 deactivate an axis. Dependency-free (parsed before jax
    loads); ``parallel.spmd.named_mesh`` turns it into a jax Mesh."""

    data: int = 1
    fsdp: int = 1
    tp: int = 1
    # False = the replicated CONTROL arm on the same mesh (same batch
    # shards, same hierarchical reduction order, sharding mechanism off)
    # — the A/B the bitwise parity acceptance compares against. Spelled
    # ``--mesh dp2,fsdp2,replicated``.
    shard: bool = True

    _KEYS = (("dp", "data"), ("data", "data"), ("fsdp", "fsdp"),
             ("tp", "tp"))

    @classmethod
    def parse(cls, spec: str) -> "MeshConfig":
        """``"dp2,fsdp2,tp1"`` (any subset, any order) -> MeshConfig.
        Unknown axis names and repeated axes fail loudly; a trailing
        ``replicated`` token selects the control arm."""
        sizes = {}
        shard = True
        for part in filter(None, (p.strip() for p in spec.split(","))):
            if part == "replicated":
                shard = False
                continue
            for key, axis in cls._KEYS:
                if part.startswith(key) and part[len(key):].isdigit():
                    if axis in sizes:
                        raise ValueError(
                            f"--mesh {spec!r}: axis {axis!r} given twice")
                    sizes[axis] = int(part[len(key):])
                    break
            else:
                raise ValueError(
                    f"--mesh {spec!r}: cannot parse {part!r} (expected "
                    f"dpN / fsdpN / tpN or 'replicated', e.g. "
                    f"'dp2,fsdp2,tp1')")
        cfg = cls(shard=shard, **{k: v for k, v in sizes.items()})
        for name, size in (("data", cfg.data), ("fsdp", cfg.fsdp),
                           ("tp", cfg.tp)):
            if size < 1:
                raise ValueError(f"--mesh {spec!r}: {name} size must be "
                                 f">= 1, got {size}")
        return cfg

    @property
    def n_devices(self) -> int:
        return self.data * self.fsdp * self.tp

    @property
    def active(self) -> bool:
        """True when the request needs the SPMD planner (any sharding
        beyond plain data parallelism)."""
        return self.fsdp > 1 or self.tp > 1

    def describe(self) -> str:
        return (f"dp{self.data},fsdp{self.fsdp},tp{self.tp}"
                + ("" if self.shard else ",replicated"))


@dataclass
class FaultConfig:
    """Fault-tolerance policy for the host-driven async-SSP process tier.

    The reference is fail-fast (comm_bus.hpp:22-24: any connection error
    aborts the job); TPU pods preempt routinely, so the tier instead runs a
    liveness protocol: clients heartbeat on the push channel, the service
    evicts workers silent past the timeout (survivors' gates unblock), and
    clients reconnect with capped exponential backoff + full jitter,
    replaying un-acked flushes (the service dedups by per-worker sequence
    number, so a retried flush applies exactly once)."""

    # client -> service heartbeat cadence (sent when the push queue is idle)
    heartbeat_s: float = 1.0
    # service evicts a worker not heard from for this long; <= 0 disables
    # eviction (the reference's hang-forever gate semantics)
    liveness_timeout_s: float = 30.0
    # client gives up reconnecting (and surfaces permanent failure to the
    # training loop) after this long without a successful attempt
    reconnect_deadline_s: float = 30.0
    # backoff envelope: sleep ~ U(0, min(cap, base * 2**attempt))
    backoff_base_s: float = 0.05
    backoff_cap_s: float = 2.0


_fault = FaultConfig()


def fault_config() -> FaultConfig:
    return _fault


def set_fault_config(**kwargs) -> None:
    for k, v in kwargs.items():
        if not hasattr(_fault, k):
            raise AttributeError(k)
        setattr(_fault, k, v)


@dataclass
class ManagedCommConfig:
    """Managed-communication policy for the async-SSP DCN tier (SSPAggr:
    bandwidth-budgeted, magnitude-prioritized pushes,
    parallel/async_ssp.py).

    With a finite budget the client meters ACTUAL frame bytes on both
    channels through a token bucket; when a dense flush would overdraw
    it, only the top ``priority_frac`` of the delta by |value| ships now
    (TOPK index+value wire form) and the exact complement rides a local
    residual, force-flushed at every staleness+1 clock boundary — the
    SSP bound is preserved exactly via durable-clock gating. Budget
    <= 0 = unlimited: byte-for-byte the dense path."""

    # per-link bandwidth budget in Mbit/s (<= 0 disables managed mode)
    budget_mbps: float = 0.0
    # fraction of delta entries a budget-tight push ships, by |value|
    priority_frac: float = 0.1
    # adaptive cadence: back off payload frequency under congestion
    # (queue depth / bucket deficit), recover as the link drains
    adaptive: bool = False
    # wire dtype for DCN delta payloads ('' = f32, today's wire byte for
    # byte; 'bf16'/'f16'/'int8' compress with EXACT error feedback —
    # quantization error rides the managed-communication residual).
    # The tier's fallback when no --wire_dtype flag rode async_cfg.
    wire_dtype: str = ""


_managed_comm = ManagedCommConfig()


def managed_comm_config() -> ManagedCommConfig:
    return _managed_comm


def set_managed_comm_config(**kwargs) -> None:
    for k, v in kwargs.items():
        if not hasattr(_managed_comm, k):
            raise AttributeError(k)
        setattr(_managed_comm, k, v)


@dataclass
class FabricConfig:
    """Two-tier fabric policy (parallel/fabric.py): an SPMD slice as one
    elastic SSP worker. The intra-slice tier is the named dp/fsdp/tp mesh
    (synchronous, ICI-speed); the cross-slice tier is the async-SSP DCN
    protocol spoken by ONE leader process per slice. These knobs govern
    the slice-granular robustness machinery only — per-process async-SSP
    mode ignores them entirely."""

    # mirror the leader's oplog (clock, pending-as-sent, residual) into
    # the slice ledger after every push; False trades failover coverage
    # (a successor resumes from the service anchor only) for zero copies
    ledger_mirroring: bool = True
    # a slice that shrinks below this many live members retires instead
    # of re-cutting its inner data shard (1 = never auto-retire)
    min_members: int = 1
    # seconds a successor leader waits for the service to register the
    # dead leader's disconnect before re-dialing (0 = dial immediately;
    # the hello/admit path is idempotent either way)
    failover_grace_s: float = 0.0


_fabric = FabricConfig()


def fabric_config() -> FabricConfig:
    return _fabric


def set_fabric_config(**kwargs) -> None:
    for k, v in kwargs.items():
        if not hasattr(_fabric, k):
            raise AttributeError(k)
        setattr(_fabric, k, v)


@dataclass
class FleetConfig:
    """Serving-fleet policy (serving/fleet.py): how many replicas the
    front door fans out to, where they pin, and the health/reload knobs.
    Dependency-free (the serve CLI parses it before jax loads); replicas=1
    with no device pinning is byte-for-byte the single-engine PR-2 path."""

    # engines behind the front door, each its own executor + micro-batcher
    replicas: int = 1
    # comma-separated indices into jax.devices() to pin replicas to
    # ("" = round-robin over all local devices when replicas > 1)
    devices: str = ""
    # consecutive dispatch failures (or wedged-submit timeouts) before a
    # replica is marked DEAD and its queue reroutes
    failure_threshold: int = 1
    # rolling reload: how long one replica may take to drain before its
    # swap is skipped this pass
    drain_timeout_s: float = 30.0
    # how often the server refreshes the stats-registry "serving" section
    # for the metrics endpoint (<= 0 = only on stats-op reads)
    stats_refresh_s: float = 2.0


_fleet = FleetConfig()


def fleet_config() -> FleetConfig:
    return _fleet


def set_fleet_config(**kwargs) -> None:
    for k, v in kwargs.items():
        if not hasattr(_fleet, k):
            raise AttributeError(k)
        setattr(_fleet, k, v)


@dataclass
class PipelineConfig:
    """Step-pipeline policy for the training loop (runtime/engine.py).

    The serialized baseline loop device_puts each batch on the train
    thread, blocks on every step's metrics, and writes snapshots inline;
    these knobs run the host<->device boundary as a pipeline instead —
    device-side input prefetch, a bounded in-flight dispatch window, and
    background snapshot serialization. All three are numerics-neutral:
    the dispatched step sequence is identical, only host blocking moves
    (tests/test_pipeline_overlap.py pins bitwise parity).

    ``Engine.__init__`` reads these defaults for every knob its caller
    leaves at None; ``train``'s ``--device_prefetch`` / ``--max_in_flight``
    / ``--async_snapshot`` pass a value through and override them."""

    # host batches staged to device AHEAD of the step that consumes them
    # (data.pipeline.DevicePrefetcher depth); 0 disables the stage and the
    # train thread device_puts inline, the pre-pipeline behavior
    device_prefetch: int = 2
    # dispatches in flight before the loop blocks on the oldest one's
    # metrics (runtime/metrics.AsyncScalarFetcher window); 1 = the serial
    # loop. NaN detection lags by at most this many steps. The depth is
    # what the device runs on while the HOST is late: at 2 (the default
    # until PR 24) one queued step of 51-68 ms was all the cover, and host
    # stalls of 100-140 ms — a few per hundred seconds on a shared host,
    # none of them ours to remove — each cost 45-75 ms of idle chip, more
    # than the benchmark's bound on a whole 30 s run. At 4 the device has
    # three steps queued when the host falls behind.
    max_in_flight: int = 4
    # serialize mid-train snapshots on a background thread, from a host
    # copy taken at the sync point (runtime/checkpoint.AsyncSnapshotWriter)
    async_snapshot: bool = False


_pipeline = PipelineConfig()


def pipeline_config() -> PipelineConfig:
    return _pipeline


@dataclass
class CompileCacheConfig:
    """Fast-restart state (runtime/compile_cache.py): the directory
    ``enable_compile_cache`` resolved (JAX_COMPILATION_CACHE_DIR, else
    <checkout>/.jax_cache) and whether AOT-serialized step executables
    ride alongside the XLA cache in it. Empty cache_dir = not enabled in
    this process (a library embedder that never called the CLI entry
    points) — every compile is a full JIT."""

    # the enabled cache directory ("" = not enabled); the AOT step store
    # lives under <cache_dir>/aot
    cache_dir: str = ""
    # serialize/reload the compiled train-step executable itself (skips
    # tracing AND compilation on a key match; best-effort — any mismatch
    # falls back to jit + the persistent cache)
    aot_steps: bool = True


_compile_cache = CompileCacheConfig()


def compile_cache_config() -> CompileCacheConfig:
    return _compile_cache


def set_compile_cache_config(**kwargs) -> None:
    for k, v in kwargs.items():
        if not hasattr(_compile_cache, k):
            raise AttributeError(k)
        setattr(_compile_cache, k, v)


# the two libtpu flags async all-reduce fusion needs; checked INDEPENDENTLY
# (a user may have set either one explicitly, in either polarity)
_ASYNC_COLLECTIVE_FLAGS = (
    "xla_tpu_enable_async_collective_fusion_fuse_all_reduce",
    "xla_enable_async_all_reduce",
)
_TRUE_VALUES = ("true", "1")


def _flag_value(args: str, name: str):
    """The explicit value of ``--name=...`` in a LIBTPU_INIT_ARGS string:
    True / False when present, None when absent. Last occurrence wins
    (libtpu's own parse order)."""
    import re
    val = None
    for m in re.finditer(r"--%s=(\S+)" % re.escape(name), args):
        val = m.group(1).lower() in _TRUE_VALUES
    return val


def enable_tpu_async_collectives(check_backend: bool = True) -> bool:
    """Turn on libtpu's async collective fusion for all-reduce — OFF by
    default in libtpu, but it is the TPU backend's mechanism for running
    a gradient all-reduce beside compute: the collective is fused into an
    ``async_collective_fusion`` program whose DMA phases interleave with
    one backward conv/matmul. What the chip showed (four v5e chips,
    AlexNet 4 x 512 bf16; PERF.md section 6, PR 59): the flags make a
    collective asynchronous only where the program's dependencies leave
    the scheduler a reason to start it early. The default data-parallel
    step's 16 per-leaf psums compile to two SYNCHRONOUS all-reduces after
    the last backward op (``compiled_step.gradient_all_reduces`` 2,
    ``gradient_all_reduces_async`` 0; 4.27 ms a step exposed); the
    chained taps of ``CommConfig.dwbp_bucket_mb=0`` compile to 15, four of
    them (fc8, fc7, fc6, conv5) asynchronous and mid-backward. The AOT
    census this docstring used to cite (evidence/aot_tpu/dwbp.json: "6/6
    bucketed DWBP all-reduces fused with 18 compute ops") was of a 67-pixel
    AlexNet on an abstract v5e-8 and no timing; the bucketed step it stood
    for read 14 of 61 asynchronous and 3.31 ms exposed on the chip.
    libtpu's other data-parallel options (MaxText's set) change nothing in
    this program's compiled text and are not staged here.

    Each flag is checked INDEPENDENTLY against the existing
    ``LIBTPU_INIT_ARGS``: an explicitly-set flag is honored in either
    polarity and NEVER duplicated (appending ``--xla_enable_async_all_
    reduce=true`` after a user's explicit ``=false`` would hand libtpu a
    conflicting duplicate — and any explicit ``=false`` marks a deliberate
    baseline run, so nothing is appended at all). Only flags that are
    absent are appended, as ``=true``.

    Must run BEFORE libtpu initializes (i.e. before jax touches devices);
    returns True iff both flags are (or now are) enabled.
    ``check_backend=False`` skips the too-late detection (the table-driven
    tests run after jax initialized its CPU backend by construction)."""
    import os
    cur = os.environ.get("LIBTPU_INIT_ARGS", "")
    states = {name: _flag_value(cur, name)
              for name in _ASYNC_COLLECTIVE_FLAGS}
    if any(v is False for v in states.values()):
        # an explicit =false is a deliberate baseline run: honor it, append
        # nothing (a half-enabled pair would be a third config nobody asked
        # for — and appending =true after the user's =false would hand
        # libtpu a conflicting duplicate)
        return False
    missing = [n for n, v in states.items() if v is None]
    if not missing:
        return True  # both explicitly enabled already; nothing to append
    if check_backend:
        import sys
        if "jax" in sys.modules:
            try:  # passive check only — never triggers (or hangs on) init
                from jax._src import xla_bridge
                if xla_bridge._backends:
                    return False  # too late — libtpu read its flags at init
            except Exception:  # noqa: BLE001 — bridge internals moved
                pass
    add = " ".join(f"--{n}=true" for n in missing)
    os.environ["LIBTPU_INIT_ARGS"] = (cur + " " + add).strip()
    return True
