"""Engine: solver-driven training orchestration (CaffeEngine + Solver::Solve).

Mirrors the reference's control flow (caffe_engine.cpp:55-293,
solver.cpp:246-402) on top of the compiled SPMD step:

- resolve train/test nets from a SolverParameter (file or inline, shared-net
  phase filtering like Net::FilterNet)
- data pipelines per data layer, sharded per host, prefetching in background
- the hot loop: one pjit-compiled step per iteration (forward + backward +
  per-layer gradient collectives + update), with display / test / snapshot
  cadence from the solver prototxt
- metrics aggregated across the mesh inside the step (the net-output-PS-table
  analog) and flushed to CSV; stats YAML per run.

Batch-size semantics: the prototxt batch_size is PER-DEVICE (the reference's
per-worker meaning); the global batch is batch_size * num_devices. With the
default "mean" gradient reduction this behaves like single-worker Caffe at the
global batch size.
"""

from __future__ import annotations

import dataclasses
import os
import time
from collections import deque
from typing import Any, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..core.net import Net
from ..data.pipeline import (BatchPipeline, DevicePrefetcher,
                             build_phase_pipelines)
from ..data.workload import Shard
from ..parallel import (CommConfig, build_eval_step, build_ssp_train_step,
                        build_train_step, init_ssp_state, init_train_state,
                        make_mesh)
from ..parallel.trainer import TrainStep, comm_error_groups, stack_batches
from ..proto.messages import NetParameter, SolverParameter, load_net
from ..solvers.updates import learning_rate
from .checkpoint import (AsyncSnapshotWriter, latest_snapshot,
                         load_caffemodel, restore, snapshot, sweep_stale_tmp)
from .metrics import (AsyncScalarFetcher, MetricsServer, MetricsTable,
                      StatsRegistry, log)
from .spans import NULL_SPAN, recorder as span_recorder

# jax's monitoring event around every backend compile (or fetch from the
# compilation cache); its listeners are told the duration when it ends
_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


_CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"


def _record_compile(event: str, duration: float, fun_name: str = "",
                    **_) -> None:
    """A compile as a span on the host timeline, ending now, with the
    program's name: one inside a measured window is then on the timeline
    and names its gap, and one before the first step is on the start-up
    timeline under whichever of its spans is open."""
    if event == _COMPILE_EVENT:
        span_recorder.complete("compile", duration, "runtime",
                               {"program": fun_name} if fun_name else None)


def _count_cache_hit(event: str, **_) -> None:
    if event == _CACHE_HIT_EVENT:
        span_recorder.note(add=True, xla_cache_hits=1)


# one listener each per process, like the recorder they write to (no-ops
# once the start-up phase has closed, while that is disabled)
jax.monitoring.register_event_duration_secs_listener(_record_compile)
jax.monitoring.register_event_listener(_count_cache_hit)


class TrainingDivergedError(RuntimeError):
    """Raised when a watched training metric (loss) goes non-finite.

    Detection rides the async metrics drain (AsyncScalarFetcher), so the
    loop learns of the divergence at most ``max_in_flight`` dispatches
    after the step that produced it; ``iteration`` rewinds the report to
    the step whose metrics actually diverged."""

    def __init__(self, iteration: int, key: str, value: float):
        self.iteration = iteration
        self.key = key
        self.value = value
        super().__init__(
            f"training diverged: {key} = {value} at iteration {iteration} "
            f"(detected asynchronously, within the in-flight window)")


def resolve_nets(sp: SolverParameter):
    """Train NetParameter + list of test NetParameters, per the reference's
    precedence: train_net_param, train_net, net_param, net (solver.cpp)."""
    train: Optional[NetParameter] = None
    tests: List[NetParameter] = []
    if sp.train_net_param is not None:
        train = sp.train_net_param
    elif sp.train_net:
        train = load_net(sp.train_net)
    elif sp.net_param is not None:
        train = sp.net_param
    elif sp.net:
        train = load_net(sp.net)
    else:
        raise ValueError("solver specifies no train net")

    tests.extend(sp.test_net_param)
    for path in sp.test_net:
        tests.append(load_net(path))
    if not tests and sp.test_iter:
        # shared-net pattern: same NetParameter filtered by TEST phase
        tests.append(train)
    return train, tests


class Engine:
    def __init__(
        self,
        sp: SolverParameter,
        comm: Optional[CommConfig] = None,
        mesh=None,
        mesh_cfg=None,
        memory_data: Optional[Dict[str, np.ndarray]] = None,
        output_dir: str = ".",
        staleness: int = 0,
        sfb_auto: bool = False,
        steps_per_dispatch: int = 1,
        device_transform: bool = False,
        async_ssp: Optional[Dict] = None,
        device_prefetch: Optional[int] = None,
        max_in_flight: Optional[int] = None,
        async_snapshot: Optional[bool] = None,
        trace_out: Optional[str] = None,
        metrics_port: Optional[int] = None,
        hbm_budget_gb: Optional[float] = None,
        remat: Optional[str] = None,
    ):
        # the start-up span the timer `engine_build` is read off, closed at
        # the end of this function. Opened by hand and not by a decorator:
        # a decorator's frame lies under every call jax makes while it
        # lowers the fillers' programs, and moved them across the end of
        # one of the interpreter's 16 KiB frame-stack chunks: 1.5 s of
        # googlenet.lmdb's set-up on the chip (PERF.md, PR 34, finding 1)
        build = span_recorder.startup("engine_build").__enter__()
        self.sp = sp
        # step-pipeline knobs: explicit args win, else the global policy
        # (config.PipelineConfig)
        from ..config import pipeline_config
        _pc = pipeline_config()
        self.device_prefetch = int(_pc.device_prefetch
                                   if device_prefetch is None
                                   else device_prefetch)
        self.max_in_flight = max(1, int(_pc.max_in_flight
                                        if max_in_flight is None
                                        else max_in_flight))
        self.async_snapshot = bool(_pc.async_snapshot
                                   if async_snapshot is None
                                   else async_snapshot)
        # named SPMD mesh (--mesh dp2,fsdp2,tp1 -> config.MeshConfig):
        # the sharding planner (parallel/spmd.py) computes the per-layer
        # plan below, once the train net exists
        self.mesh_cfg = mesh_cfg
        self.plan = None
        if mesh_cfg is not None:
            # honored even when inactive (fsdp=tp=1): '--mesh dp2' means
            # TWO devices, not a silent fall-through to all of them
            if mesh is not None:
                raise ValueError("pass mesh or mesh_cfg, not both")
            from ..parallel.spmd import named_mesh
            mesh = named_mesh(mesh_cfg)
        self.mesh = mesh or make_mesh()
        self.n_dev = int(np.prod(list(self.mesh.shape.values())))
        self.comm = comm or CommConfig()
        if self.plan is None and mesh_cfg is not None and mesh_cfg.active \
                and self.comm.dcn_axis is not None:
            raise ValueError("--mesh and --dcn_slices do not compose")
        self.staleness = staleness
        self.output_dir = output_dir
        self.stats = StatsRegistry()
        self.rank = jax.process_index()
        self.world = jax.process_count()
        # --- telemetry spine ------------------------------------------- #
        # --trace_out enables the process-wide span recorder (the span
        # names are listed in runtime/spans.py) and dumps a Chrome
        # trace-event JSON at snapshot boundaries, when train() returns
        # and at close(). (--metrics_port is wired up BELOW, after the
        # async tier resolves this process's real rank.)
        self._trace_out: Optional[str] = None
        self._owns_span_recorder = False
        if trace_out:
            self._trace_out = (trace_out if os.path.isabs(trace_out)
                               else os.path.join(output_dir, trace_out))
            self._owns_span_recorder = not span_recorder.enabled
            # fresh ownership = fresh timeline: a previous engine's spans
            # (the recorder is process-global) must not ghost-prefix this
            # run's dump
            if self._owns_span_recorder:
                span_recorder.clear()
            span_recorder.enable()
        self._metrics_server: Optional[MetricsServer] = None
        self.metrics_port: Optional[int] = None
        self._metrics_port_arg = metrics_port
        # wait-free async-SSP process tier (runtime/async_tier.py): the
        # processes are INDEPENDENT jax runtimes (no jax.distributed world),
        # so rank/world come from the launcher env, the local mesh is this
        # process's own devices, and the only cross-process exchange is the
        # tier's parameter service
        self._async_cfg = async_ssp
        self._async_tier = None
        if async_ssp is not None:
            from .async_tier import env_world
            self.rank, self.world, _ = env_world()
        # --metrics_port: read-only HTTP endpoint for the stats registry
        # (text key=value, curl-able mid-run). Created only now that the
        # async tier has resolved the REAL rank: a fixed port is bound by
        # rank 0 alone — every worker of a multi-process job gets the same
        # CLI args, and N processes racing one port is EADDRINUSE, not
        # telemetry. Port 0 (ephemeral) binds on every rank.
        if self._metrics_port_arg is not None and \
                self._metrics_port_arg >= 0:
            if self._metrics_port_arg == 0 or self.rank == 0:
                try:
                    self._metrics_server = MetricsServer(
                        self.stats, port=self._metrics_port_arg)
                except OSError as e:
                    # an optional read-only endpoint must never abort a
                    # training run (a stale daemon holding the port is
                    # the operator's most likely EADDRINUSE)
                    log(f"WARNING: --metrics_port "
                        f"{self._metrics_port_arg} unavailable ({e}); "
                        f"training continues without the endpoint",
                        rank=self.rank)
                else:
                    self.metrics_port = self._metrics_server.port
                    # printed from EVERY rank that bound a server (the
                    # ADMITTED-line idiom): an ephemeral port nobody
                    # logged is an endpoint nobody can curl
                    log(f"metrics endpoint (rank {self.rank}): "
                        f"http://127.0.0.1:{self.metrics_port}/ "
                        f"(text key=value)")
        self.memory_data = memory_data
        # data assignment: launch-time (rank, world) for the fixed-world
        # tiers; the async tier re-keys it by the CURRENT member list via
        # reshard_data (an elastic joiner's rank sits OUTSIDE the launch
        # world, so it builds with the whole-range placeholder and the
        # tier reshards it at join, before the first batch is consumed)
        self._data_shard = (Shard(self.rank, self.world)
                            if self.rank < self.world else Shard(0, 1))
        # uint8 ingest + on-device (x - mean) * scale (the TPU-native split
        # of DataTransformer): train pipelines ship quarter-width bytes and
        # the normalization fuses into the compiled step (sync and SSP).
        self._device_transform = device_transform

        if self.comm.server_logic != "inc" and staleness == 0:
            log(f"WARNING: --server_logic {self.comm.server_logic} requires "
                f"--staleness > 0 (there is no server in the synchronous "
                f"step); training plain sync SGD", rank=self.rank)

        # iter_size (V2-prototxt gradient accumulation; the 2015 reference
        # predates it): K micro-batches' gradients accumulate inside the
        # compiled step before one update — batch_size B at iter_size K is
        # numerically equivalent to batch_size B*K (trainer.py, tested)
        self.iter_size = max(1, int(sp.iter_size))
        if self.iter_size > 1 and staleness > 0:
            log("WARNING: iter_size > 1 ignored under SSP staleness "
                "(increase batch_size instead)", rank=self.rank)
            self.iter_size = 1

        with span_recorder.startup("net_build"):
            train_param, test_params = resolve_nets(sp)

        # --- data pipelines for the train net ---------------------------- #
        self._train_param = train_param  # retained: reshard_data rebuilds
        self.train_pipelines, train_shapes = self._build_pipelines(
            train_param, "TRAIN")
        self._train_shapes = train_shapes  # per-device; remat probe scales
        # tops of a token source (HDF5 ids and targets): int32 whatever
        # their rank
        self._token_tops = frozenset(
            t for p in self.train_pipelines
            if getattr(getattr(p, "source", None), "tokens", False)
            for t in p.tops)
        with span_recorder.startup("net_build"):
            self.train_net = Net(train_param, "TRAIN",
                                 source_shapes=train_shapes)
            if self.mesh_cfg is not None and self.mesh_cfg.active:
                from ..parallel.spmd import ShardingPlan
                self.plan = ShardingPlan.build(
                    self.train_net, self.mesh_cfg, self.comm,
                    shard_params=self.mesh_cfg.shard,
                    enable_tp=self.mesh_cfg.shard)
        if self.plan is not None:
            log(f"sharding plan: {self.plan.describe()}", rank=self.rank)
            if self.iter_size > 1:
                log("WARNING: iter_size > 1 does not compose with --mesh "
                    "sharding yet; running iter_size=1", rank=self.rank)
                self.iter_size = 1
            if max(1, int(steps_per_dispatch)) > 1:
                log("WARNING: steps_per_dispatch ignored under --mesh "
                    "sharding", rank=self.rank)
                steps_per_dispatch = 1
        self._input_transform = self._make_input_transform()
        if self._device_transform and self._input_transform is None:
            log("WARNING: --device_transform requested but no train data "
                "layer is eligible (needs the native LMDB batcher, "
                "byte-backed records, and mean_value-style mean — a "
                "mean_file must stay host-side); using the host transform",
                rank=self.rank)

        self.test_nets: List[Net] = []
        self.test_pipelines: List[List[BatchPipeline]] = []
        for i, tp in enumerate(test_params):
            pipes, shapes = self._build_pipelines(tp, "TEST")
            with span_recorder.startup("net_build"):
                self.test_nets.append(Net(tp, "TEST", source_shapes=shapes))
            self.test_pipelines.append(pipes)

        if sfb_auto:
            # SACP cost-model strategy choice must land before step building:
            # build_*_train_step snapshots the strategy map eagerly. SFB is a
            # per-step backward-time exchange, so under SSP (local steps, no
            # per-step exchange) the auto picks stay DENSE instead.
            if staleness > 0 and self.comm.dcn_axis is None:
                log("sfb_auto: SFB does not compose with flat-mesh SSP "
                    "staleness; keeping DENSE delta sync for all layers "
                    "(on a two-tier mesh SFB rides the intra-slice tier)",
                    rank=self.rank)
            else:
                from ..parallel.strategies import auto_strategies
                self.comm.layer_strategies.update(
                    auto_strategies(self.train_net))

        # HDF5_OUTPUT in the TRAIN net (hdf5_output_layer.cpp): the step
        # additionally returns the dump bottoms; after every iteration the
        # file is rewritten with the latest batch — the reference's
        # overwrite-per-forward semantics. Must be known before step build.
        self._h5_train = [
            (l.lp.hdf5_output_param.file_name, list(l.lp.bottom))
            for l in self.train_net.layers if l.TYPE == "HDF5_OUTPUT"]
        if self._h5_train and staleness > 0:
            log("WARNING: HDF5_OUTPUT in the TRAIN net is not dumped "
                "under SSP staleness", rank=self.rank)
            self._h5_train = []

        # --- step pipeline eligibility ------------------------------------ #
        # Device-side input prefetch feeds the SINGLE-batch path: the
        # stacked paths (scan chunking, iter_size micro-batches) assemble
        # host batches in their own shapes and would desync the shared
        # pipeline order if a prefetcher were draining the same pipes.
        self._use_prefetch = (self.device_prefetch > 0
                              and self.iter_size == 1
                              and max(1, int(steps_per_dispatch)) == 1)
        if device_prefetch is not None and self.device_prefetch > 0 and \
                not self._use_prefetch:
            # warn only on an EXPLICIT request — the policy default (2)
            # silently stands down for stacked-batch runs
            log("WARNING: --device_prefetch disabled (iter_size > 1 or "
                "steps_per_dispatch > 1 use stacked host batches); the "
                "stacked transfer already amortizes the host->device "
                "boundary", rank=self.rank)
        # with a prefetcher handing the step a FRESH device batch every
        # iteration, donating the batch buffers lets XLA recycle the
        # previous step's allocation — steady state allocates no new
        # device batch buffers. CPU never honors donation (unimplemented)
        # yet the unhonored aliasing spec measurably slows the call path
        # (~10% on the 2-core bench box), so donate only where the
        # allocator actually recycles.
        donate_batch = self.donates_batch(
            self._use_prefetch, jax.default_backend(),
            set(train_shapes) - self._token_tops)
        self._donate_batch = donate_batch

        # --- measured HBM budget planner (core/remat.py) ------------------ #
        # --hbm_budget_gb fits the compiled train step's real
        # memory_analysis() peak under a byte budget by rematerializing
        # the cheapest-recompute activations (greedy knapsack against the
        # attribution table's act_bytes column); --remat either forces an
        # explicit layer list (skipping the measuring compile) or says
        # "auto" (plan against the budget) / "none" (off). The plan is
        # computed ONCE here, then rides build_train_step(remat_plan=);
        # what its units keep of their own making (the Pallas forward
        # kernels' results) is decided later, on the compiled step
        # (_compile_step).
        self.remat_plan = None
        self.hbm_budget_gb = hbm_budget_gb
        _want_plan = ((remat or "").strip().lower() not in ("", "none")
                      or (hbm_budget_gb is not None and hbm_budget_gb != 0))
        if _want_plan and staleness > 0:
            log("WARNING: --hbm_budget_gb/--remat are ignored under SSP "
                "staleness (the local-step path has no remat wiring yet)",
                rank=self.rank)
        elif _want_plan:
            with span_recorder.startup("net_build"):
                self.remat_plan = self._plan_remat(remat, hbm_budget_gb,
                                                   donate_batch)
        if self.remat_plan is not None and not self.remat_plan.active:
            self.remat_plan = None  # fits the budget: identity plan
        if self.remat_plan is not None:
            log(self.remat_plan.describe(), rank=self.rank)
            # stats.yaml says WHAT dropped and WHY (budget, measured
            # peak, claimed bytes)
            self.stats.set_section("remat", self.remat_plan.to_doc())

        with span_recorder.startup("step_build"):
            self._build_steps(sp, staleness, donate_batch, steps_per_dispatch)

        # --- state -------------------------------------------------------- #
        with span_recorder.startup("param_init"):
            seed = sp.random_seed if sp.random_seed >= 0 else 1
            self.rng = jax.random.PRNGKey(seed)
            self.params = self.train_net.init(
                jax.random.fold_in(self.rng, 0))
            self.err_groups = comm_error_groups(self.comm, self.mesh)
            if staleness > 0:
                # SSP groups = slices on a two-tier mesh, devices on a flat
                # one (the same granularity comm_error_groups computes)
                self.state = init_ssp_state(self.params, self.err_groups,
                                            self.comm)
            else:
                self.state = init_train_state(self.params, self.comm,
                                              self.err_groups,
                                              sp.solver_type)
            # to the point the leaves are ready: the device's tail of the
            # fills is this span's, not the step load's or the first step's
            # (0.1 s of googlenet.lmdb's set-up on the chip, PERF.md PR 34)
            jax.block_until_ready((self.params, self.state))
        # single-batch placement spec (test/eval batches and non-accumulated
        # train steps): the train step's input sharding minus the leading
        # [iter_size] micro-batch axis it gains under gradient accumulation
        from jax.sharding import NamedSharding, PartitionSpec
        spec = self.train_step.batch_sharding.spec
        if self.iter_size > 1:
            spec = PartitionSpec(*spec[1:])
        self._sample_sharding = NamedSharding(self.mesh, spec)
        self.metrics = MetricsTable("train")
        self.test_metrics = [MetricsTable(f"test_{i}")
                             for i in range(len(self.test_nets))]
        self.profile_steps = 0  # set >0 to capture an xplane trace
        # background snapshot serialization (--async_snapshot): the host
        # copy is still taken synchronously at the snapshot boundary (THE
        # sync point), but encode + write + atomic rename leave the loop
        self._snap_writer = AsyncSnapshotWriter() if self.async_snapshot \
            else None
        self._device_feed: Optional[DevicePrefetcher] = None
        # train batches dequeued so far: the producer threads number the
        # batches they make from 0 in the same order, so this is the
        # number the next one was made under (prefetch_wait's ``batch``)
        self._batches_taken = 0
        # display boundaries dispatched but not yet shown: (iteration, that
        # step's learning rate as a device scalar), oldest first
        self._displays: deque = deque()

        # fast restart (runtime/compile_cache.py): when a compile-cache
        # dir is configured, the single-step hot path resolves through the
        # AOT step-executable store on first dispatch — a restarted-or-new
        # worker whose program is the stored one (_aot_step_key: whatever
        # its seed, and its run's length where no schedule reads it) skips
        # tracing AND compilation entirely; a miss compiles once,
        # serializes for the next incarnation, and still rides the
        # persistent XLA cache. SSP local-step and HDF5-dump steps keep
        # the jit path (different call signatures).
        from ..config import compile_cache_config
        _ccc = compile_cache_config()
        self._aot_exec = None
        self._aot_failed = False
        # the AOT step store calls lowerable.lower(params, state, batch,
        # rng) and replays the executable with those four args; the spmd
        # step carries bound trailing (sharded multiplier) arguments the
        # replay would miss, so warm start stands down under a plan. A
        # jax.distributed world keeps the jit path too: one rank's
        # serialized executable is not another's.
        self._aot_enabled = (bool(_ccc.cache_dir) and _ccc.aot_steps
                             and staleness == 0 and not self._h5_train
                             and self.iter_size == 1
                             and self.plan is None
                             and jax.process_count() == 1)

        # what this run is, where a reader of stats.yaml will look for it:
        # the device as jax reports it, which kernel arm each layer took,
        # which layout plan the graph runs, which reader feeds each data layer
        dev = jax.local_devices()[0]
        self.stats.set_section("device", {
            "platform": dev.platform, "kind": dev.device_kind,
            "count": jax.device_count(),
            "processes": jax.process_count(),
            "jax": jax.__version__})
        self.stats.set_section("kernel_routes",
                               dict(self.train_net.kernel_routes))
        # which whole-graph plan the net took (asked for / resolved / why),
        # how many layers run channels-last and the boundaries it holds
        self.stats.set_section("conv_layout",
                               dict(self.train_net.layout_plan))
        if self.train_net.shared_params:
            # one leaf to the update, the clip and a snapshot, whatever
            # number of layers reads it
            self.stats.set_section("shared_params", {
                name: f"{d['owner']} x{d['uses']}" for name, d in
                self.train_net.shared_params.items()})
        # what the layers state of themselves (expert_share,
        # recurrent_state), and what a top they display counts for (_absorb)
        for section, facts in self.train_net.layer_facts().items():
            self.stats.set_section(section, facts)
        self._display_counters = self.train_net.display_counters()
        self.stats.set_section("data_reader", {
            p.tops[0]: ("native" if getattr(p, "native", None) is not None
                        else "python")
            for p in self.train_pipelines})
        self._placement_recorded = False

        self._h5_outputs = [
            [(l.lp.hdf5_output_param.file_name, list(l.lp.bottom))
             for l in net.layers if l.TYPE == "HDF5_OUTPUT"]
            for net in self.test_nets]
        self._h5_fetch = [
            (jax.jit(lambda p, b, _n=net: _n.apply(p, b, train=False,
                                                   keep_blobs=True).blobs)
             if any(outs) else None)
            for net, outs in zip(self.test_nets, self._h5_outputs)]

        # debug_info (solver.cpp:326,422; net.cpp ForwardDebugInfo/
        # UpdateDebugInfo): per-layer mean-|.| of activations, params, and
        # gradients, printed at display boundaries. Off the hot path — a
        # separate jitted pass that runs only when enabled.
        self._debug_fn = None
        if sp.debug_info and not sp.display:
            log("WARNING: debug_info needs a display cadence (display: N) "
                "to print; set display in the solver", rank=self.rank)
        elif sp.debug_info:
            def _debug(params, batch, rng):
                if self._input_transform is not None:
                    batch = self._input_transform(batch)
                out = self.train_net.apply(
                    params, batch, train=True, rng=rng, keep_blobs=True)
                grads = jax.grad(
                    lambda p: self.train_net.apply(
                        p, batch, train=True, rng=rng).loss)(params)
                stats = {}
                for name, v in out.blobs.items():
                    stats[f"blob\x00{name}"] = jnp.mean(jnp.abs(
                        v.astype(jnp.float32)))
                for lname, lp in params.items():
                    for pname, w in lp.items():
                        stats[f"param\x00{lname}/{pname}"] = jnp.mean(
                            jnp.abs(w.astype(jnp.float32)))
                        stats[f"grad\x00{lname}/{pname}"] = jnp.mean(
                            jnp.abs(grads[lname][pname].astype(jnp.float32)))
                return stats

            self._debug_fn = jax.jit(_debug)
        build.__exit__(None, None, None)
        self.stats.add_time("engine_build", build.dur_s)

    def _build_steps(self, sp: SolverParameter, staleness: int,
                     donate_batch: bool, steps_per_dispatch: int) -> None:
        """The train step (sync or SSP), the scan-chunk step and the eval
        steps of this job: ``__init__``'s ``step_build``."""
        # --- compiled steps ---------------------------------------------- #
        if staleness > 0:
            # SSP (ssp_consistency_controller.cpp): each device runs local
            # steps, reconciling every staleness+1 iters. The engine's view
            # of "the params" is the replicated anchor (what the PS holds).
            ssp_ts = build_ssp_train_step(self.train_net, sp, self.mesh,
                                          staleness, self.comm,
                                          input_transform=self._input_transform,
                                          donate_batch=donate_batch,
                                          plan=self.plan)
            raw_step = ssp_ts.step

            def _ssp_step(params, state, batch, rng):
                state, m = raw_step(state, batch, rng)
                return state.anchor_params, state, m

            self.train_step = TrainStep(
                step=_ssp_step, mesh=ssp_ts.mesh,
                batch_sharding=ssp_ts.batch_sharding,
                replicated=ssp_ts.replicated,
                # NOTE: the SSP lowerable has the 3-arg (state, batch, rng)
                # signature, not the wrapper's 4-arg one
                lowerable=ssp_ts.lowerable,
                arena=ssp_ts.arena)  # the boundary's delta buckets
        else:
            dump = sorted({b for _, bs in self._h5_train for b in bs})
            if dump and self.iter_size > 1:
                log("WARNING: iter_size > 1 ignored with HDF5_OUTPUT in "
                    "the TRAIN net (per-iteration dump semantics)",
                    rank=self.rank)
                self.iter_size = 1
            if dump and self.plan is not None:
                log("WARNING: HDF5_OUTPUT in the TRAIN net is not dumped "
                    "under --mesh sharding", rank=self.rank)
                dump = []
                self._h5_train = []
            # (_compile_step builds the step again with these where what
            # its units keep changes)
            self._train_step_args = dict(
                dump_blobs=dump, input_transform=self._input_transform,
                iter_size=self.iter_size, donate_batch=donate_batch,
                plan=self.plan)
            self.train_step = build_train_step(
                self.train_net, sp, self.mesh, self.comm,
                **self._train_step_args, remat_plan=self.remat_plan)

        # --- multi-step dispatch (scan chunks) ---------------------------- #
        # K optimizer steps per compiled dispatch: amortizes the runtime's
        # per-dispatch round-trip (whether it buys anything on a local
        # chip is ROADMAP S3's A/B).
        # The engine falls back to single steps near display/test/snapshot
        # boundaries so solver cadence semantics are exact.
        self.steps_per_dispatch = max(1, int(steps_per_dispatch))
        self._scan_step = None
        if self.steps_per_dispatch > 1:
            if staleness > 0:
                log("WARNING: steps_per_dispatch ignored under SSP "
                    "staleness (the SSP step already batches local steps)",
                    rank=self.rank)
                self.steps_per_dispatch = 1
            elif self._h5_train:
                log("WARNING: steps_per_dispatch ignored with HDF5_OUTPUT "
                    "in the TRAIN net (per-iteration dump semantics)",
                    rank=self.rank)
                self.steps_per_dispatch = 1
            else:
                self._scan_step = build_train_step(
                    self.train_net, sp, self.mesh, self.comm,
                    scan_steps=self.steps_per_dispatch,
                    input_transform=self._input_transform,
                    iter_size=self.iter_size,
                    remat_plan=self.remat_plan)
        self.eval_steps = [
            build_eval_step(n, self.mesh, dcn_axis=self.comm.dcn_axis,
                            plan=self.plan)
            for n in self.test_nets]

    # ---------------------------------------------------------------- #
    def _build_pipelines(self, net_param: NetParameter, phase: str,
                         shard: Optional[Shard] = None):
        # Each host produces only its addressable devices' rows; the pipeline
        # shards the record space across hosts (shared_file_system-style).
        # (the MESH's devices on this host: a mesh narrower than the host,
        # '--mesh dp1' on four chips, feeds that many and no more)
        local = sum(d.process_index == jax.process_index()
                    for d in self.mesh.devices.flat)
        with span_recorder.startup("pipeline_open", {"phase": phase}):
            return build_phase_pipelines(
                net_param, phase, batch_multiplier=local,
                shard=shard if shard is not None else self._data_shard,
                memory_data=self.memory_data,
                device_transform=(self._device_transform
                                  and phase == "TRAIN"))

    @staticmethod
    def donates_batch(use_prefetch: bool, backend: str,
                      float_tops) -> bool:
        """Whether the step donates its batch: only with a prefetcher
        handing it a fresh device batch every step, only where the
        allocator recycles (not the CPU), and only if some batch blob is
        not token ids. A batch of int32 ids and targets has nothing to
        alias — no result of the step is an integer array — so donating
        it only earned XLA's "Some donated buffers were not usable:
        int32[...]" at every token-model start."""
        return bool(use_prefetch and backend != "cpu" and float_tops)

    def _plan_remat(self, remat, hbm_budget_gb, donate_batch):
        """Resolve the remat decision for this job config (called once,
        before step building). Three spellings:

        - ``remat`` = comma-separated layer names, or ``/regex/`` entries
          that checkpoint runs of layers as one segment each
          (``remat.resolve_entries``): trust the operator, price the list
          against the attribution table, skip the measuring compile
          entirely (source="flag");
        - ``remat`` = "auto" and/or a budget: build the NO-remat step,
          compile it against abstract batch avals, read the real
          ``memory_analysis()`` peak, and run the knapsack
          (source="measured"; the no-remat compile is the price of
          measuring);
        - ``hbm_budget_gb`` < 0: auto-detect the device's own HBM limit
          (``default_budget_bytes``); refuses quietly on backends with
          no memory stats (the CPU proxy needs an explicit budget).
        """
        from ..core import remat as remat_mod
        table = self.train_net.cost_table()
        names = [s.strip() for s in str(remat or "").split(",")
                 if s.strip() and s.strip().lower() not in ("none",
                                                            "auto")]
        if names:
            layers, segments = remat_mod.resolve_entries(
                [l.name for l in self.train_net.layers], names)
            return remat_mod.RematPlan(
                budget_bytes=0,
                layers=layers, segments=segments,
                saved_bytes=sum(int(table.get(n, {}).get("act_bytes", 0))
                                for n in layers),
                recompute_flops=sum(
                    float(table.get(n, {}).get("flops", 0.0)) / 3.0
                    for n in layers),
                source="flag")
        if hbm_budget_gb is not None and hbm_budget_gb < 0:
            budget = remat_mod.default_budget_bytes()
            if budget <= 0:
                log("WARNING: --hbm_budget_gb auto needs device memory "
                    "stats (none on this backend); pass an explicit "
                    "budget — skipping remat planning", rank=self.rank)
                return None
        else:
            budget = int(float(hbm_budget_gb or 0) * 2**30)
        # the measuring probe: the SAME step config the engine is about
        # to build, minus remat, lowered against abstract avals (no
        # params materialize here — eval_shape carries the pytrees)
        probe = build_train_step(
            self.train_net, self.sp, self.mesh, self.comm,
            input_transform=self._input_transform,
            iter_size=self.iter_size, donate_batch=donate_batch,
            plan=self.plan)
        params_avals = jax.eval_shape(self.train_net.init,
                                      jax.random.PRNGKey(0))
        groups = comm_error_groups(self.comm, self.mesh)
        state_avals = jax.eval_shape(
            lambda p: init_train_state(p, self.comm, groups,
                                       self.sp.solver_type), params_avals)
        batch_avals = {}
        for k, s in self._train_shapes.items():
            g = (int(s[0]) * self.n_dev,) + tuple(int(d) for d in s[1:])
            if self.iter_size > 1:
                g = (self.iter_size,) + g
            # rank-1 source blobs are the data layers' label tops; a token
            # source's ids and targets are integers at rank 2 as well
            dt = jnp.int32 if len(s) == 1 or k in self._token_tops \
                else jnp.float32
            batch_avals[k] = jax.ShapeDtypeStruct(g, dt)
        return remat_mod.plan_for_net_step(
            self.train_net, probe.lowerable,
            (params_avals, state_avals, batch_avals,
             jax.random.PRNGKey(7)),
            budget)

    def reshard_data(self, shard: Shard) -> bool:
        """Re-key the TRAIN data assignment (elastic membership: the async
        tier calls this when the member list changes, with the shard from
        ``data/workload.member_shard``). Rebuilds the train pipelines —
        and the device prefetcher consuming them — against the new
        contiguous range; test pipelines keep the launch shard (eval is a
        fixed-world sweep). No-op when the shard is unchanged."""
        if shard == self._data_shard:
            return False
        old = self._data_shard
        if self._device_feed is not None:
            # the feed's worker thread consumes the pipelines being torn
            # down; stop it first, recreate it against the new ones below
            self._device_feed.close()
            self._device_feed = None
        for p in self.train_pipelines:
            p.close()
        self.train_pipelines, _ = self._build_pipelines(
            self._train_param, "TRAIN", shard=shard)
        self._batches_taken = 0     # new producers number from 0 again
        self._data_shard = shard
        if self._use_prefetch:
            self._device_feed = DevicePrefetcher(
                self.train_pipelines, self._sample_sharding,
                depth=self.device_prefetch)
        log(f"resharded data assignment: shard {old.index}/{old.count} -> "
            f"{shard.index}/{shard.count}", rank=self.rank)
        return True

    def _device_transform_specs(self) -> Dict[str, Dict[str, Any]]:
        """{top: {"mean_values", "scale"}} of the data layers whose
        normalization ``--device_transform`` moved into the step."""
        return {p.tops[0]: p.device_transform_spec
                for p in self.train_pipelines
                if getattr(p, "device_transform_spec", None) is not None}

    def _make_input_transform(self):
        """The device half of the uint8 ingest split: per data-layer
        (x - mean_values) * scale, traced into the compiled train step."""
        specs = self._device_transform_specs()
        if not specs:
            return None
        frozen = {top: (None if s["mean_values"] is None
                        else jnp.asarray(s["mean_values"], jnp.float32),
                        float(s["scale"]))
                  for top, s in specs.items()}

        def transform(batch):
            out = dict(batch)
            for top, (mean, scale) in frozen.items():
                if top not in out:
                    continue
                x = out[top].astype(jnp.float32)
                if mean is not None:
                    x = x - mean.reshape(1, -1, 1, 1)
                if scale != 1.0:
                    x = x * scale
                out[top] = x
            return out

        return transform

    def _next_batch(self, pipes: List[BatchPipeline]):
        from ..data.pipeline import place_batch
        batch: Dict[str, jax.Array] = {}
        for pipe in pipes:
            for k, v in next(pipe).items():
                batch[k] = place_batch(v, self._sample_sharding)
        return batch

    def _next_batch_stack(self, pipes: List[BatchPipeline], k: int,
                          sharding=None, lead_shape=None):
        """k host batches stacked to [k, ...] and placed in ONE transfer
        (the feeding side of steps_per_dispatch). ``lead_shape`` reshapes
        the leading axis, e.g. (chunk, iter_size) when scan chunking and
        gradient accumulation compose."""
        rows: List[Dict[str, np.ndarray]] = [{} for _ in range(k)]
        for pipe in pipes:
            for i in range(k):
                rows[i].update(next(pipe))
        if sharding is None:
            sharding = self._scan_step.batch_sharding
        return stack_batches(rows, sharding, lead_shape=lead_shape)

    # ---------------------------------------------------------------- #
    def _dispatch_train_step(self, batch, rng, it: Optional[int] = None):
        """One single-step dispatch, through the AOT warm-start path when
        configured (resolution is lazy: the store key needs the concrete
        batch shapes, which exist only once the first batch is drawn).
        ``it`` only labels the ``dispatch_execute`` span."""
        first = not self._placement_recorded
        # the process's first step ends the start-up phase (runtime/spans.py)
        starting = first and span_recorder.startup_open
        if first:
            # read BEFORE the dispatch: the step donates the batch
            sample = next(iter(batch.values()))
            batch_devs = sorted(sh.device.id
                                for sh in sample.addressable_shards)
        if self._aot_enabled and self._aot_exec is None \
                and not self._aot_failed:
            self._resolve_aot_step(batch, rng)
        if first and self._aot_exec is None:
            self._publish_step_scopes(
                None, self.stats.sections.get("compiled_step", {}).get(
                    "error", "the step runs through jit and there is no "
                    "executable to read: no compile-cache directory, SSP, "
                    "an HDF5 dump, iter_size > 1, a sharding plan or a "
                    "multi-process world"))
        tag = None if it is None else {"iter": it}
        if starting:
            first_step = span_recorder.startup("first_step", tag).__enter__()
        with span_recorder.span("dispatch_execute", "step", tag):
            if self._aot_exec is not None:
                # the lowerable's raw signature carries the (empty — AOT
                # is disabled under HDF5_OUTPUT) dump slot; keep the
                # step() wrapper's 3-tuple contract
                out = self._aot_exec(self.params, self.state, batch, rng)
                if isinstance(out, tuple) and len(out) > 3:
                    out = out[:3]
            else:
                out = self.train_step.step(self.params, self.state, batch,
                                           rng)
        if starting:
            self._end_startup(first_step, out)
        if first:
            self._record_placement(sample.shape, batch_devs, out[0])
        return out

    def _end_startup(self, first_step, out) -> None:
        """The process's first step has been dispatched: wait until its
        result is ready (the program's load onto the chip and the step's
        own run are the open span ``first_step``'s, once), then the
        recorder's start-up phase closes and its summary becomes stats
        section ``startup``, the step's route first (``jit`` when no
        executable was resolved)."""
        jax.block_until_ready(out)
        first_step.__exit__(None, None, None)
        doc = span_recorder.end_startup()
        self.stats.set_section("startup",
                               {"route": doc.pop("route", "jit"), **doc})

    def _publish_stalls(self) -> None:
        """While the span recorder is enabled, a ``train`` call ends with
        the stall ledger of the recorder's window (``spans.stall_ledger``:
        every late step since ``clear()`` with a cause) as stats section
        ``stalls``, beside what making it took (``summary_ms``)."""
        if not span_recorder.enabled:
            return
        t = time.perf_counter()
        doc = span_recorder.stalls(self.max_in_flight)
        doc["summary_ms"] = round((time.perf_counter() - t) * 1e3, 3)
        self.stats.set_section("stalls", doc)

    def _publish_step_scopes(self, text: Optional[str],
                             why: str = "") -> None:
        """Stats section ``step_scopes``: which layer and pass each
        instruction of the step that runs belongs to
        (``attribution.step_scopes``), so that a device trace of this run
        can be read by layer with no guess from shapes. The two maps stay
        in ``stats.snapshot()`` and out of the rendered documents. Without
        an executable's text the section is empty and says ``why``."""
        from .attribution import step_scopes
        doc = (step_scopes(text, self.train_net) if text is not None else
               {"ops": {}, "types": {}, "recomputed": [],
                "instructions": 0, "mapped": 0, "why": why[:500]})
        self.stats.set_section("step_scopes", doc,
                               snapshot_only=("ops", "types", "recomputed"))

    def _record_placement(self, batch_shape, batch_devs,
                          params) -> None:  # static-ok: JIT102
        """First dispatch only: where the work actually sits — the devices
        holding the batch's shards, and how many hold each parameter the
        step returned — into stats.yaml, so a multi-chip run can show it
        used every chip rather than assert it."""
        self._placement_recorded = True
        sharding = jax.tree_util.tree_leaves(params)[0].sharding
        self.stats.set_section("placement", {
            "batch_global_shape": "x".join(map(str, batch_shape)),
            "batch_shard_devices": ",".join(map(str, batch_devs)),
            "param_devices": len(sharding.device_set),
            "param_fully_replicated": bool(sharding.is_fully_replicated)})

    # one-time AOT resolution at the FIRST dispatch (key hashing over
    # static shapes/mesh ints), never steady-state:
    def _resolve_aot_step(self, batch, rng) -> None:  # static-ok: JIT102
        """Load — or compile + serialize — the step executable for this
        job's key (``_aot_step_key``: what reaches the traced program),
        under the start-up span ``step_load``, and publish stats section
        ``compiled_step``: what the step is and, read off the spans that
        closed under ``step_load``, where its seconds went."""
        with span_recorder.startup("step_load") as load:
            doc = self._load_or_compile_step(batch, rng, load)
        span_recorder.note(
            route=doc["source"], key=load.args.get("key", ""),
            pallas_custom_calls=doc.get("pallas_custom_calls", 0))
        # under the keys they have always had
        doc["phases"] = {
            phase: round(sum(load.children.get(n, 0.0) for n in names), 3)
            for phase, names in (
                ("load_s", ("step_key", "aot_read", "aot_unpack",
                            "aot_deserialize")),
                ("trace_lower_s", ("step_trace_lower",)),
                ("compile_s", ("step_compile",)),
                ("store_s", ("aot_store",)),
                ("text_s", ("step_text",)),
                ("scope_map_s", ("scope_map",)))}
        doc["seconds"] = round(load.dur_s, 3)
        self.stats.set_section("compiled_step", doc)

    def _load_or_compile_step(  # static-ok: JIT102
            self, batch, rng, load) -> Dict[str, Any]:
        """``_resolve_aot_step``'s work; returns the ``compiled_step``
        section without its timings. Best-effort: any failure pins the
        jit path for the rest of the run (which the persistent compile
        cache still accelerates). ``load`` is the open ``step_load`` span,
        which is told the key and the route."""
        startup = span_recorder.startup
        # which form the step took, readable without the HLO: where the
        # optimizer update runs and how many buckets of the flat buffer
        # are exchanged (the fsdp step's and the SSP boundary's; 0 in the
        # data-parallel step, which sums each gradient leaf on its own)
        arena = self.train_step.arena
        doc: Dict[str, Any] = {
            "source": "jit", "update_route": self.train_step.update_route,
            "grad_buckets": arena.n_buckets if arena is not None else 0}
        try:
            with startup("step_key"):
                from ..config import compile_cache_config
                from .attribution import param_relayouts
                from .compile_cache import (load_step_executable,
                                            load_step_note,
                                            save_step_executable)
                from .hlo_comm import gradient_all_reduce_census
                cfg = compile_cache_config()
                key = self._aot_step_key(batch)
            load.args["key"] = key[:12]
            exec_ = load_step_executable(cfg.cache_dir, key)
            source, stored = "loaded", "found"
            if exec_ is not None:
                # a loaded step is whatever was decided when it was stored
                kept = load_step_note(cfg.cache_dir, key).get(
                    "remat_keep") if self.remat_plan is not None else None
            else:
                source = "compiled"
                exec_, hits, kept = self._compile_step(batch, rng)
            if kept and self.remat_plan is not None:
                self.remat_plan = dataclasses.replace(
                    self.remat_plan, keep=tuple(kept["keep"]))
                log(self.remat_plan.describe(kept), rank=self.rank)
                doc["remat_keep"] = kept
                self.stats.set_section(
                    "remat", {**self.remat_plan.to_doc(), **kept})
            if source == "compiled":
                if hits:
                    # the XLA cache answered: trace paid, compile skipped;
                    # what it hands back is not re-serialized (see
                    # compile_cache.watch_cache_hits)
                    source, stored = "xla_cache", "no (xla cache hit)"
                else:
                    with startup("aot_store"):
                        stored = "yes" if save_step_executable(
                            cfg.cache_dir, key, exec_,
                            note={"remat_keep": kept} if kept else None
                        ) else "no (see log)"
            # the executable is good from here on, whatever the store did
            self._aot_exec = exec_
            log("aot warm start: " + {
                "loaded": "loaded serialized train step — trace and "
                          "compile skipped",
                "compiled": "compiled train step",
                "xla_cache": "train step answered by the XLA cache — "
                             "compile skipped"}[source]
                + f" (key {key[:12]}; in aot/: {stored})", rank=self.rank)
            # what the step that will run actually contains: the Pallas
            # custom calls the kernel routes promise (0 = interpreted or
            # routed to XLA), the gradient all-reduces and how many of
            # them the compiler made asynchronous, and the layout copies
            # between the step's parameters and its results
            doc.update(source=source, stored=stored)
            load.args["route"] = source
            with startup("step_text"):
                text = exec_.as_text()
                doc["pallas_custom_calls"] = text.count(
                    'custom_call_target="tpu_custom_call"')
                (doc["gradient_all_reduces"],
                 doc["gradient_all_reduces_async"]) = \
                    gradient_all_reduce_census(text)
                doc["param_relayouts"] = param_relayouts(text)
            with startup("scope_map"):
                self._publish_step_scopes(text)
        except Exception as e:  # noqa: BLE001 — warm start is best-effort
            # never silent: the reason goes to the log with its traceback
            # and into stats.yaml, where chip_smoke.py reads it
            import traceback
            self._aot_failed = self._aot_exec is None
            doc["error"] = f"{type(e).__name__}: {e}"[:500]
            log(f"aot warm start: {doc['error']}; "
                + ("using the jit path" if self._aot_failed else
                   "the executable is in use, its text could not be read")
                + "\n" + traceback.format_exc(), rank=self.rank)
            if self._aot_exec is not None:
                self._publish_step_scopes(None, doc["error"])
        return doc

    def _compile_step(self, batch, rng):  # static-ok: JIT102
        """Trace, lower and compile the train step -> (executable, whether
        the XLA cache answered its compile, what its units keep: None
        where no layer runs under a checkpoint).

        Under a remat plan the units first keep every name of
        ``remat.keep_rungs``' first rung: their gated FFNs' products and
        what their Pallas forward kernels wrote, so that the backward's
        replay runs none of them a second time. That costs memory, and a
        user who passes ``--remat`` wants memory first: the names stay only
        while the COMPILED step (``measured_peak_bytes``) is within
        ``--hbm_budget_gb``, or with none given ``KEEP_SHARE`` of the
        device's limit. Over it, or refused by the compiler, the FFNs'
        products go, then the scans' results, then the flash kernels', and
        the step is the one that keeps nothing. A rung that names nothing
        more than the next of what this program makes is passed over, and
        so is, without a compile, one whose floor is over the budget: what
        is certainly live as the backward starts, the step's arguments +
        the units' stored inputs + the rung's kept bytes, all read off the
        trace (``attribution.unit_residuals``) and taken as spread evenly
        over the mesh, the least a device can hold. So a cold start
        compiles at most four times and a net with no such value once; on
        a backend with no memory statistics, with no budget given, the
        names stay."""
        from ..core import remat as remat_mod
        from .attribution import unit_residuals
        from .compile_cache import watch_cache_hits
        startup = span_recorder.startup
        plan = self.remat_plan
        units = plan is not None and bool(plan.layers)

        def trace(keep):
            if units and keep != self.remat_plan.keep:
                self.remat_plan = dataclasses.replace(plan, keep=keep)
                self.train_step = build_train_step(
                    self.train_net, self.sp, self.mesh, self.comm,
                    **self._train_step_args, remat_plan=self.remat_plan)
            with startup("step_trace_lower"):
                traced = self.train_step.lowerable.trace(
                    self.params, self.state, batch, rng)
                return traced, traced.lower()

        traced, lowered = trace(remat_mod.keep_rungs()[0] if units else ())
        # what the units hold is read off the program, once; the rungs are
        # those that differ in what they keep of it
        n_lead = len(jax.tree_util.tree_leaves((self.params, self.state)))
        named, stored = unit_residuals(
            traced.jaxpr, self.remat_plan, range(
                n_lead, n_lead + len(jax.tree_util.tree_leaves(batch)))
        ) if units else ([], 0)
        arguments = sum(a.size * a.dtype.itemsize
                        for a in traced.jaxpr.in_avals)
        rungs = remat_mod.keep_rungs({name for name, _, _ in named})
        budget = int(self.hbm_budget_gb * 2**30) \
            if self.hbm_budget_gb and self.hbm_budget_gb > 0 \
            else int(remat_mod.KEEP_SHARE * remat_mod.default_budget_bytes())
        compiles, passed_over = 0, []
        while True:
            keep = rungs.pop(0)
            kept = [(n, u, b) for n, u, b in named if n in keep]
            by_name = {name: sum(b for n, _, b in kept if n == name)
                       for name in sorted({n for n, _, _ in kept})}
            floor = (arguments + stored + sum(by_name.values())) // self.n_dev
            if rungs and budget and floor > budget:
                passed_over.append("+".join(by_name))
                log(f"remat: the step whose units keep {passed_over[-1]} "
                    f"holds {floor / 1e9:.2f} GB as its backward starts "
                    f"({arguments / 1e9:.2f} of arguments, "
                    f"{stored / 1e9:.2f} of stored inputs, "
                    f"{sum(by_name.values()) / 1e9:.2f} kept), over its "
                    f"{budget / 1e9:.2f}: passed over without a compile",
                    rank=self.rank)
                continue
            if compiles or passed_over:
                traced, lowered = trace(keep)
            exec_, refused = None, ""
            try:
                with startup("step_compile"), watch_cache_hits() as hits:
                    exec_ = lowered.compile()
            except Exception as e:  # noqa: BLE001 — more than the chip holds
                if not rungs:
                    raise
                refused = f"{type(e).__name__}: {e}"[:200]
            compiles += 1
            if not units:
                return exec_, hits, None
            peak = remat_mod.measured_peak_bytes(exec_) if exec_ else 0
            doc = {"keep": list(by_name),
                   "kept_units": len({u for _, u, _ in kept}),
                   "kept_bytes": sum(by_name.values()),
                   "kept_bytes_by_name": by_name,
                   "floor_bytes": floor, "compiled_peak_bytes": peak,
                   "held_to_bytes": budget, "compiles": compiles,
                   "passed_over": list(passed_over)}
            if exec_ is not None and (not rungs or not budget
                                      or peak <= budget):
                return exec_, hits, doc
            log(f"remat: the step whose units keep {'+'.join(doc['keep'])}"
                f" ({doc['kept_bytes'] / 1e9:.2f} GB in "
                f"{doc['kept_units']}) "
                + (f"was refused ({refused})" if refused else
                   f"compiles at {peak / 1e9:.2f} GB, over its "
                   f"{budget / 1e9:.2f}") + ": keeping less",
                rank=self.rank)
            del exec_               # a compiled program holds device memory

    def _aot_step_key(self, batch) -> str:  # static-ok: JIT102
        """The AOT store's key for this job's train step. The rule: a part
        is in the key if and only if it reaches the traced program, so
        that a start whose program is the stored one loads it and any
        other misses (a stale load is worse than a slow start). The parts:

        - the program's own sources (``code_fingerprint``), the jax
          version, the backend, the device kind and count, the mesh;
        - the train net: its name, every compute layer's definition as
          parsed (a ratio, a window, an activation: same name and shapes,
          another program), the parameters' and the batch's shapes and
          dtypes, the activation layout, the remat units and the
          ``--hbm_budget_gb`` that what they keep is held to
          (``_compile_step``), the mean and scale that
          ``--device_transform`` moves into the step;
        - the numeric policy, the five lowering switches
          (``LOWERING_ENV``), the comm config, whether the batch is
          donated;
        - the solver fields the update and the rate are traced from, and
          ``max_iter`` only under a policy whose rate reads it
          (``HORIZON_POLICIES``: the horizon is a constant of the program
          there).

        NOT in the key, because no traced line reads them: ``random_seed``
        (the weights are made from it outside the step and ``rng`` is the
        step's fourth argument; tests/test_elasticity.py lowers steps under
        two seeds and compares the text), ``display``, the snapshot and
        test cadence, data sources and their host-side transforms, and
        ``max_iter`` under every other policy. So a new seed, a resume, a
        longer run and another dataset of the same shapes all load."""
        from ..config import policy
        from ..ops.pallas_kernels import LOWERING_ENV
        from ..solvers.updates import HORIZON_POLICIES
        from .compile_cache import code_fingerprint, step_key
        solver_fields = [
            "solver_type", "base_lr", "lr_policy", "gamma", "power",
            "stepsize", "stepvalue", "momentum", "momentum2",
            "weight_decay", "regularization_type", "delta",
            "clip_gradients", "iter_size"]
        if self.sp.lr_policy in HORIZON_POLICIES:
            solver_fields.append("max_iter")
        return step_key(
            kind="train_step",
            model=self.train_net.name or "net",
            # a caffemodel's weights ride a parsed layer as ``blobs``:
            # values, not program
            layers=[repr(dataclasses.replace(l.lp, blobs=[]))
                    for l in self.train_net.layers],
            params={l: {p: (list(v.shape), str(v.dtype))
                        for p, v in ps.items()}
                    for l, ps in self.params.items()},
            batch={k: (list(v.shape), str(v.dtype))
                   for k, v in batch.items()},
            mesh={k: int(v) for k, v in self.mesh.shape.items()},
            backend=jax.default_backend(),
            device_kind=jax.devices()[0].device_kind,
            n_devices=self.n_dev,
            jax_version=jax.__version__,
            code=code_fingerprint(),
            lowering_env={k: os.environ.get(k, "")
                          for k in LOWERING_ENV},
            numeric_policy=str(policy()),
            conv_layout=self.train_net.conv_layout,
            # --device_transform's mean and scale are constants of the step
            input_transform={
                top: (None if spec["mean_values"] is None
                      else spec["mean_values"].tolist(), spec["scale"])
                for top, spec in self._device_transform_specs().items()},
            solver={k: str(getattr(self.sp, k, None))
                    for k in solver_fields},
            comm=str(self.comm),
            donate_batch=self._donate_batch,
            # what runs under which checkpoint is part of the program, and
            # what its units keep follows from the program, the device and
            # the budget the user gave (_compile_step): the budget, not
            # the decision, so that a warm start needs no compile to know
            remat=self.remat_plan.units if self.remat_plan else (),
            hbm_budget_gb=float(self.hbm_budget_gb or 0))

    # ---------------------------------------------------------------- #
    def iteration(self) -> int:
        return int(self.state.it if self.staleness > 0
                   else self.state.solver.it)

    def restore_from(self, path: str):
        with span_recorder.startup("restore",
                                   {"file": os.path.basename(path)}):
            self._restore_from(path)

    def _restore_from(self, path: str) -> None:
        if path.endswith(".caffemodel"):
            self.params = load_caffemodel(path, self.train_net, self.params)
            if self.staleness > 0:
                self.state = init_ssp_state(self.params, self.err_groups,
                                            self.comm)
            log(f"Loaded weights from {path}", rank=self.rank)
        else:
            from .checkpoint import coerce_state
            params, state = restore(path)
            self.params, self.state = coerce_state(
                params, state, staleness=self.staleness,
                n_dev=self.err_groups, comm=self.comm)
            log(f"Restored solver state from {path} "
                f"(iter {self.iteration()})", rank=self.rank)

    def auto_resume(self) -> Optional[str]:
        """Restart-after-preemption without tracking filenames: sweep any
        stale snapshot tmp litter a killed predecessor left behind, find
        the newest ``<prefix>_iter_N.solverstate.npz`` under the solver's
        snapshot prefix, and restore it. Returns the restored path, or
        None when there is nothing to resume from (fresh start). Pairs
        with ``sp.snapshot`` cadence + the async tier's eviction/rejoin:
        a preempted worker relaunches with the same command line and
        continues from its last snapshot."""
        if not self.sp.snapshot_prefix:
            return None
        prefix = os.path.join(self.output_dir, self.sp.snapshot_prefix)
        removed = sweep_stale_tmp(prefix)
        if removed:
            log(f"auto-resume: swept {len(removed)} stale snapshot tmp "
                f"file(s): {', '.join(os.path.basename(r) for r in removed)}",
                rank=self.rank)
        path = latest_snapshot(prefix)
        if path is None:
            log(f"auto-resume: no snapshot under {prefix!r}; starting fresh",
                rank=self.rank)
            return None
        self.restore_from(path)
        return path

    def snapshot_now(self) -> Optional[str]:
        if not self.sp.snapshot_prefix:
            return None
        prefix = os.path.join(self.output_dir, self.sp.snapshot_prefix)
        if self._snap_writer is not None:
            model, statef = self._snap_writer.submit(
                prefix, self.train_net, self.params, self.state)
            log(f"Snapshotting (async) to {model} / {statef}",
                rank=self.rank)
            return statef
        model, statef = snapshot(prefix, self.train_net, self.params,
                                 self.state)
        log(f"Snapshotting to {model} / {statef}", rank=self.rank)
        return statef

    # ---------------------------------------------------------------- #
    def test(self, test_id: int = 0) -> Dict[str, float]:
        """Average metrics over test_iter batches (Solver::Test)."""
        net = self.test_nets[test_id]
        ev = self.eval_steps[test_id]
        iters = self.sp.test_iter[test_id] if test_id < len(self.sp.test_iter) \
            else 50
        acc: Dict[str, float] = {}
        h5_acc: Dict[str, list] = {}
        h5_specs = self._h5_outputs[test_id]
        multihost = jax.process_count() > 1
        for _ in range(iters):
            batch = self._next_batch(self.test_pipelines[test_id])
            if h5_specs:
                # one traced forward serves both metrics and dumped blobs
                blobs = self._h5_fetch[test_id](self.params, batch)
                m = {k: v for k, v in blobs.items()
                     if k in net.output_names and v.ndim == 0}
                for fname, bottoms in h5_specs:
                    for b in bottoms:
                        arr = blobs[b]
                        if multihost:
                            from jax.experimental import multihost_utils
                            arr = multihost_utils.process_allgather(
                                arr, tiled=True)
                        if self.rank == 0:
                            h5_acc.setdefault(f"{fname}\x00{b}", []).append(
                                np.asarray(arr))
            else:
                m = ev(self.params, batch)
            for k, v in m.items():
                acc[k] = acc.get(k, 0.0) + float(v)
        if h5_specs and self.rank == 0:
            self._write_h5_outputs(h5_acc)
        out = {k: v / iters for k, v in acc.items()}
        msg = ", ".join(f"{k} = {v:.4f}" for k, v in sorted(out.items()))
        log(f"    Test net #{test_id}: {msg}", rank=self.rank)
        self.test_metrics[test_id].accumulate(out)
        return out

    def _check_divergence(self, fetcher: AsyncScalarFetcher) -> None:
        """Abort on the first non-finite watched metric the async drain has
        seen. The report names the step that PRODUCED the bad value (the
        fetcher tags rows by iteration — the rewind), even though the loop
        has dispatched up to max_in_flight steps past it."""
        if fetcher.divergence is not None:
            it, key, value = fetcher.divergence
            raise TrainingDivergedError(it, key, value)

    def _absorb(self, rows, last: Dict[str, float]) -> Dict[str, float]:
        """Feed drained (iter, row) pairs into the metrics window; a
        display boundary that was waiting for its window's last step is
        shown the moment that step's row arrives, before any later row
        joins the window."""
        for row_it, row in rows:
            self.metrics.accumulate(row)
            last = row
            for top, counts in self._display_counters.items():
                if top in row:
                    for name, by in counts(row[top]).items():
                        self.stats.add(name, by)
            if self._displays and self._displays[0][0] == row_it + 1:
                self._display(*self._displays.popleft())
        return last

    def _display(self, it: int, lr_dev) -> None:  # static-ok: JIT102
        """One display boundary: the window's mean row, Caffe's log line,
        the live telemetry. Runs on the train thread when the boundary's
        last step has drained (``_absorb``), with the steps dispatched
        since then still on the device: a display does not wait for the
        device to run dry, and the device does not wait for a display.
        (The one device value read here, the rate, was dispatched behind
        that drained step and ahead of the next: it is there already.)"""
        row = self.metrics.flush_row(it)
        lr = float(lr_dev)
        extras = ", ".join(
            f"{k} = {v:.4f}" for k, v in sorted(row.items())
            if k not in ("iter", "time"))
        log(f"Iteration {it}, lr = {lr:.6g}, {extras}", rank=self.rank)
        # live telemetry rides the display cadence: gauges for the metrics
        # endpoint, plus the atomic stats.yaml write (a preempted run
        # keeps it)
        self.stats.set_gauge("iteration", it)
        self.stats.set_gauge("lr", lr)
        for k, v in row.items():
            if k not in ("iter", "time"):
                self.stats.set_gauge(f"train_{k}", round(v, 6))
        with span_recorder.span("telemetry_dump", "artifact", {"iter": it}):
            self._dump_live_telemetry()
        if self._async_tier is not None:
            # membership churn rides the display cadence, so
            # admissions/evictions are visible without log-grepping
            # (comm_stats.membership_counters)
            from .comm_stats import format_comm, format_membership
            log("    [membership] " + format_membership(
                self._async_tier.membership_counters()), rank=self.rank)
            # the per-link managed-communication bill rides the same
            # cadence: bytes on the wire, deferred fraction, measured
            # goodput, cadence backoffs — gauges feed stats.yaml + the
            # metrics endpoint
            cc = self._async_tier.comm_counters()
            if cc:
                log("    [comm] " + format_comm(cc), rank=self.rank)
                for k, v in cc.items():
                    self.stats.set_gauge(f"async_comm_{k}",
                                         round(float(v), 4))

    def train(self, max_iter: Optional[int] = None) -> Dict[str, float]:
        sp = self.sp
        max_iter = max_iter or sp.max_iter
        it = self.iteration()
        t_start = time.time()
        last: Dict[str, float] = {}
        # the dispatch window: device metrics drain to host floats on the
        # fetcher's thread; put() blocks only when max_in_flight dispatches
        # are un-materialized, so the loop runs ahead of the device by a
        # bounded number of steps instead of hard-syncing every iteration
        fetcher = AsyncScalarFetcher(self.max_in_flight)
        span_recorder.set_role("train")     # this thread's row of the timeline
        self._displays.clear()      # a run that raised may have left some
        if self._use_prefetch and self._device_feed is None:
            self._device_feed = DevicePrefetcher(
                self.train_pipelines, self._sample_sharding,
                depth=self.device_prefetch)
        if self._async_cfg is not None and self._async_tier is None:
            from .async_tier import AsyncSSPTier, FabricTier
            # two-tier fabric mode ("slice": True, --slice): this process
            # leads an SPMD slice and the DCN worker identity is the
            # SLICE id — membership, gates and the data shard below all
            # re-key to slice granularity (parallel/fabric.py)
            cfg = dict(self._async_cfg)
            tier_cls = FabricTier if cfg.pop("slice", False) else AsyncSSPTier
            self._async_tier = tier_cls(self.params, **cfg)
            # every worker starts from the service anchor: rank 0's view on
            # a fresh run, the surviving anchor (all applied clocks) when
            # this process is a preemption restart rejoining mid-job, and
            # the join-clock anchor for an elastic joiner admitted into a
            # live job
            self.params = jax.device_put(self._async_tier.resume_cache,
                                         self.train_step.replicated)
            # key the data assignment by the member list the join revealed
            # (a joiner built its pipelines with the placeholder shard;
            # everyone else no-ops unless the fleet already changed)
            self.reshard_data(self._async_tier.data_shard())
        # profiler window: skip a couple of warmup/compile steps
        profile_start = it + 2
        profiling = False

        if sp.test_interval and sp.test_initialization and self.test_nets:
            with span_recorder.startup("initial_test"):
                for i in range(len(self.test_nets)):
                    self.test(i)
                    self.test_metrics[i].flush_row(it)

        # until the process's first step is done, the loop's first turn is
        # on the start-up timeline (runtime/spans.py)
        starting = span_recorder.startup_open
        try:
            while it < max_iter:
                if sp.snapshot and it > 0 and it % sp.snapshot == 0:
                    # snapshot boundary = hard sync point: every in-flight
                    # step's metrics must be seen BEFORE persisting params,
                    # so a NaN that the drainer has not surfaced yet can
                    # never be snapshotted and then silently auto-resumed
                    with span_recorder.span("hard_sync", "sync",
                                            {"boundary": "snapshot"}):
                        last = self._absorb(fetcher.sync(), last)
                    self._check_divergence(fetcher)
                    with span_recorder.span("snapshot", "ckpt",
                                            {"iter": it}):
                        self.snapshot_now()
                    self._dump_span_timeline()
                if self.profile_steps and it == profile_start:
                    jax.profiler.start_trace(
                        os.path.join(self.output_dir, "profile"))
                    profiling = True

                # one iteration = one numbered step on the profiler's
                # timeline (--profile's window and, with the span recorder
                # on, any trace an outside profiler takes): the spans below
                # lie inside it, beside the device ops
                with (jax.profiler.StepTraceAnnotation("train", step_num=it)
                      if profiling or span_recorder.enabled else NULL_SPAN):
                    # how many steps may run before the next host-side
                    # boundary (display flush / debug pre-step / test /
                    # snapshot / profile); a full steps_per_dispatch chunk
                    # runs as ONE compiled dispatch
                    chunk = 1
                    if self._scan_step is not None:
                        room = max_iter - it
                        if sp.display:
                            d = sp.display - (it % sp.display)
                            room = min(room, d - 1 if self._debug_fn else d)
                        if sp.test_interval and self.test_nets:
                            room = min(room, sp.test_interval -
                                       (it % sp.test_interval))
                        if sp.snapshot:
                            room = min(room, sp.snapshot - (it % sp.snapshot))
                        if self.profile_steps and \
                                it < profile_start + self.profile_steps:
                            # single-step dispatches only until the trace
                            # window closes; afterwards chunking resumes
                            room = min(room, profile_start - it) \
                                if it < profile_start else 1
                        if room >= self.steps_per_dispatch:
                            chunk = self.steps_per_dispatch

                    if starting:
                        first_wait = span_recorder.startup(
                            "first_batch_wait", {"iter": it}).__enter__()
                    if chunk > 1:
                        t_in = time.perf_counter()
                        with span_recorder.span(
                                "prefetch_wait", "input",
                                {"iter": it, "chunk": chunk,
                                 "batch": self._batches_taken}):
                            batch = self._next_batch_stack(
                                self.train_pipelines, chunk * self.iter_size,
                                lead_shape=((chunk, self.iter_size)
                                            if self.iter_size > 1 else None))
                        self._batches_taken += chunk * self.iter_size
                        self.stats.add_time("input_stall",
                                            time.perf_counter() - t_in)
                        if starting:
                            first_wait.__exit__(None, None, None)
                        t0 = time.time()
                        # the scan step folds rng by global iteration
                        # internally (solver.it + offset): pass the session
                        # rng unfolded so a chunked run's per-step streams
                        # match single-step dispatch
                        tag = {"iter": it, "chunk": chunk}
                        if starting:
                            first_step = span_recorder.startup(
                                "first_step", tag).__enter__()
                        with span_recorder.span("dispatch", "step", tag):
                            self.params, self.state, m = self._scan_step.step(
                                self.params, self.state, batch, self.rng)
                        if starting:
                            self._end_startup(first_step, m)
                        it += chunk
                        at_display = bool(sp.display) and it % sp.display == 0
                    else:
                        t_in = time.perf_counter()
                        # ``batch`` is the number the producer threads made
                        # this batch under: producer_read -> producer_h2d ->
                        # prefetch_wait join on it, and on ``iter`` from here
                        with span_recorder.span(
                                "prefetch_wait", "input",
                                {"iter": it, "batch": self._batches_taken}):
                            if self.iter_size > 1:
                                # one optimizer step = iter_size stacked
                                # micro-batches
                                batch = self._next_batch_stack(
                                    self.train_pipelines, self.iter_size,
                                    sharding=self.train_step.batch_sharding)
                            elif self._device_feed is not None:
                                # the prefetch stage already placed this
                                # batch on device with the step's sharding;
                                # steady state this dequeue is instant and
                                # input_stall measures residual starvation
                                batch = next(self._device_feed)
                            else:
                                batch = self._next_batch(self.train_pipelines)
                        self._batches_taken += self.iter_size
                        self.stats.add_time("input_stall",
                                            time.perf_counter() - t_in)
                        if starting:
                            first_wait.__exit__(None, None, None)
                        at_display = bool(sp.display) and \
                            (it + 1) % sp.display == 0
                        if at_display and self._debug_fn:
                            # BEFORE the step, on the step's own inputs
                            # (pre-update params, this iteration's
                            # rng/batch) — the values Caffe's
                            # ForwardDebugInfo/UpdateDebugInfo report for
                            # iteration it+1. Under iter_size the debug pass
                            # reads the first micro-batch (one
                            # representative forward).
                            dbatch = ({k: v[0] for k, v in batch.items()}
                                      if self.iter_size > 1 else batch)
                            # the pass reads the device back anyway: show
                            # a boundary still pending first, so the log
                            # stays in step order
                            last = self._absorb(fetcher.sync(), last)
                            stats = self._debug_fn(
                                self.params, dbatch,
                                jax.random.fold_in(self.rng, it))
                            for key in sorted(stats):
                                kind, name = key.split("\x00")
                                log(f"    [debug] {kind:<5} {name}: "
                                    f"{float(stats[key]):.6g}", rank=self.rank)
                        t0 = time.time()
                        with span_recorder.span("dispatch", "step",
                                                {"iter": it}):
                            with span_recorder.span("dispatch_rng", "step",
                                                    {"iter": it}):
                                step_rng = jax.random.fold_in(self.rng, it)
                            result = self._dispatch_train_step(
                                batch, step_rng, it)
                        if self._h5_train:
                            self.params, self.state, m, dumps = result
                            self._write_train_h5(dumps)
                        else:
                            self.params, self.state, m = result
                        it += 1
                    # metrics stay device arrays on this thread: the
                    # fetcher's drainer materializes them to host floats
                    # off-thread, and put() blocks only when max_in_flight
                    # dispatches are still un-materialized — the bounded
                    # in-flight dispatch window (the span measures exactly
                    # the window backpressure wait)
                    with span_recorder.span("dispatch_window", "step",
                                            {"iter": it}):
                        fetcher.put(it - chunk, m)
                    self._check_divergence(fetcher)
                    starting = False
                    self.stats.add("train_iters", chunk)
                    self.stats.add_time("train_step", time.time() - t0)
                    if self._async_tier is not None:
                        self._async_tier.after_iters(self, chunk)

                    if at_display:  # same boundary: it has incremented since
                        # NOT a sync point: the boundary is shown when its
                        # last step's row drains (_absorb), at most
                        # max_in_flight - 1 dispatches from now. Waiting
                        # for it here would leave the device with nothing
                        # queued while the host writes a log line — once
                        # per `display` steps, and a different stretch of
                        # idle chip in every run. The rate is dispatched
                        # now and read then: behind the step just
                        # dispatched, ahead of the next.
                        self._displays.append(
                            (it, learning_rate(sp, jnp.asarray(it - 1))))
                    # absorb whatever the drainer finished — no display
                    # cadence needed to keep the metrics window bounded
                    last = self._absorb(fetcher.take_drained(), last)
                    if sp.test_interval and it % sp.test_interval == 0 and \
                            self.test_nets:
                        # test boundary = hard sync point too: never spend
                        # a full eval sweep on a model a still-draining NaN
                        # has already poisoned
                        with span_recorder.span("hard_sync", "sync",
                                                {"boundary": "test"}):
                            last = self._absorb(fetcher.sync(), last)
                        self._check_divergence(fetcher)
                        for i in range(len(self.test_nets)):
                            self.test(i)
                            self.test_metrics[i].flush_row(it)
                if profiling and it >= profile_start + self.profile_steps:
                    # after the step's annotation has closed, so the last
                    # profiled step is whole in the trace
                    jax.block_until_ready(m["loss"])
                    jax.profiler.stop_trace()
                    profiling = False
                    log(f"Wrote profiler trace to "
                        f"{os.path.join(self.output_dir, 'profile')}",
                        rank=self.rank)

            # tail iterations past the last display boundary
            with span_recorder.span("hard_sync", "sync",
                                    {"boundary": "final"}):
                last = self._absorb(fetcher.sync(), last)
            self._check_divergence(fetcher)
        finally:
            fetcher.close()
            if profiling:
                jax.profiler.stop_trace()
                log(f"Wrote profiler trace to "
                    f"{os.path.join(self.output_dir, 'profile')}",
                    rank=self.rank)
        if self._async_tier is not None:
            # flush the last clock + fold the final anchor into rank 0's
            # params BEFORE the after-train snapshot, so the snapshot holds
            # every worker's updates
            tier_stats = self._async_tier.finish(self)
            for k, v in tier_stats.items():
                self.stats.add(k, v)
            self._async_tier = None
        if sp.snapshot_after_train:
            with span_recorder.span("snapshot", "ckpt",
                                    {"boundary": "after_train"}):
                self.snapshot_now()
        if self._snap_writer is not None:
            # train() returning means the artifacts exist: join the last
            # background write (and surface its failure loudly)
            self._snap_writer.wait()
        self.stats.add_time("train_total", time.time() - t_start)
        self._publish_stalls()
        self._write_artifacts()
        written = self._dump_span_timeline()
        if written:
            log(f"Wrote span timeline to {written}", rank=self.rank)
        return last

    def _write_train_h5(self, dumps: Dict[str, jax.Array]):
        """Rewrite each TRAIN-net HDF5_OUTPUT file with the latest batch
        (hdf5_output_layer.cpp overwrites its datasets every Forward)."""
        import h5py
        host = {}
        multihost = jax.process_count() > 1
        for k, v in dumps.items():
            if multihost and not v.is_fully_addressable:
                from jax.experimental import multihost_utils
                v = multihost_utils.process_allgather(v, tiled=True)
            host[k] = np.asarray(v)
        if self.rank != 0:
            return
        for fname, bottoms in self._h5_train:
            path = os.path.join(self.output_dir, fname)
            os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
            with h5py.File(path, "w") as f:
                for b in bottoms:
                    f.create_dataset(b.replace("/", "_"), data=host[b])

    def _write_h5_outputs(self, h5_acc: Dict[str, list]):
        import h5py
        by_file: Dict[str, Dict[str, np.ndarray]] = {}
        for key, chunks in h5_acc.items():
            fname, blob = key.split("\x00")
            by_file.setdefault(fname, {})[blob.replace("/", "_")] = \
                np.concatenate(chunks)
        for fname, datasets in by_file.items():
            path = os.path.join(self.output_dir, fname)
            os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
            with h5py.File(path, "w") as f:
                for name, arr in datasets.items():
                    f.create_dataset(name, data=arr)
            log(f"HDF5 output -> {path}", rank=self.rank)

    # ---------------------------------------------------------------- #
    def _trace_out_path(self) -> Optional[str]:
        """This rank's span-timeline path: rank 0 writes the requested
        file, workers write a ``.rank<k>`` sibling (every process records
        its own timeline — async push/gate spans live on the workers, and
        an output_dir may be shared)."""
        if self._trace_out is None:
            return None
        if self.rank == 0:
            return self._trace_out
        base, ext = os.path.splitext(self._trace_out)
        return f"{base}.rank{self.rank}{ext or '.json'}"

    def _dump_live_telemetry(self):
        """Display-boundary telemetry flush: stats.yaml (atomic tmp +
        rename — a crashed/preempted run keeps everything through its
        last boundary, rank 0 only). Best-effort: a full disk or NFS blip
        at a display boundary must never abort a training run that could
        keep going (the exit-time writer retries the same path anyway).
        The span timeline is NOT written here: serializing the whole
        buffer (up to 65,536 events) inside every display interval stalled
        the loop it records; see ``_dump_span_timeline``."""
        if self.rank != 0:
            return
        try:
            self.stats.dump_yaml(os.path.join(self.output_dir, "stats.yaml"))
        except OSError as e:
            self._warn_telemetry_write(e)

    def _dump_span_timeline(self) -> Optional[str]:
        """Under --trace_out, write this rank's span timeline (atomic):
        at snapshot boundaries, when train() returns and at close() —
        where the loop stops anyway — so that a display boundary costs the
        same however many spans the run has recorded. Best-effort like
        ``_dump_live_telemetry``; returns the path written."""
        path = self._trace_out_path()
        if path is None:
            return None
        try:
            return span_recorder.dump(path)
        except OSError as e:
            self._warn_telemetry_write(e)
            return None

    def _warn_telemetry_write(self, e: OSError) -> None:
        if not getattr(self, "_telemetry_write_warned", False):
            self._telemetry_write_warned = True
            log(f"WARNING: telemetry write failed ({e}); training "
                f"continues, will retry at the next boundary",
                rank=self.rank)

    def _write_artifacts(self):
        if self.rank != 0:
            return
        # static per-layer comm accounting + comm/compute split estimate
        # (the stats.hpp bytes-per-clock analog, computed from shapes)
        from .comm_stats import comm_summary, layer_comm_table
        table = layer_comm_table(self.train_net, self.comm, self.mesh)
        iters = self.stats.counters.get("train_iters", 0)
        step_ms = (self.stats.timers.get("train_step", 0.0) / iters * 1e3
                   if iters else None)
        self.stats.set_section("comm", {
            "summary": comm_summary(table, step_ms),
            "per_layer": table,
        })
        mem = jax.local_devices()[0].memory_stats()
        if mem:  # the CPU backend publishes none
            self.stats.set_gauge("peak_bytes_in_use",
                                 int(mem.get("peak_bytes_in_use", 0)))
        name = self.train_net.name or "net"
        self.metrics.to_csv(os.path.join(self.output_dir,
                                         f"{name}_train_outputs.csv"))
        for i, tm in enumerate(self.test_metrics):
            if tm.rows:
                tm.to_csv(os.path.join(self.output_dir,
                                       f"{name}_test{i}_outputs.csv"))
        self.stats.dump_yaml(os.path.join(self.output_dir, "stats.yaml"))

    def close(self):
        # close EVERYTHING before surfacing any failure: a snapshot-write
        # error must not strand the prefetcher/pipeline worker threads,
        # and an aborted (diverged/interrupted) run must not leak the
        # async tier's sockets behind the skipped finish() protocol
        err: Optional[BaseException] = None
        if self._owns_span_recorder:
            # final timeline flush (every rank writes its own file), then
            # stand the recorder down (it is process-global; a later
            # engine without --trace_out must not keep paying for spans
            # nobody will dump)
            self._dump_span_timeline()
            span_recorder.disable()
            self._owns_span_recorder = False
        if self._metrics_server is not None:
            try:
                self._metrics_server.close()
            except Exception:  # noqa: BLE001 — teardown best effort
                pass
            self._metrics_server = None
        if self._snap_writer is not None:
            try:
                self._snap_writer.close()
            except BaseException as e:  # noqa: BLE001 — re-raised below
                err = e
        if self._async_tier is not None:
            for closer in (lambda: self._async_tier.client.close(),
                           lambda: (self._async_tier.service.close()
                                    if self._async_tier.service else None)):
                try:
                    closer()
                except Exception:  # noqa: BLE001 — teardown best effort
                    pass
            self._async_tier = None
        if self._device_feed is not None:
            # before the pipelines: the feed's worker consumes them
            self._device_feed.close()
            self._device_feed = None
        for p in self.train_pipelines:
            p.close()
        for pipes in self.test_pipelines:
            for p in pipes:
                p.close()
        if err is not None:
            raise err
