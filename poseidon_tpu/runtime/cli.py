"""CLI: the caffe_main-equivalent command registry — ALL brew commands live.

The reference's ``caffe_main <command>`` exposes train and device_query, with
test/time compiled out behind #if 0 (tools/caffe_main.cpp:49-350). Here every
command works: train, test, time, device_query, plus the dataset tools and the
feature extractor.

    python -m poseidon_tpu train --solver=examples/mnist/lenet_solver.prototxt
    python -m poseidon_tpu test --model=net.prototxt --weights=x.caffemodel --iterations=50
    python -m poseidon_tpu time --model=net.prototxt --iterations=50
    python -m poseidon_tpu device_query
    python -m poseidon_tpu convert_imageset|compute_image_mean|partition_data|extract_features ...
"""

from __future__ import annotations

import argparse
import os
import sys
import time as _time
from typing import List, Optional

import numpy as np


def cmd_device_query(args) -> int:
    import jax
    for d in jax.devices():
        print(f"device {d.id}: platform={d.platform} kind={d.device_kind} "
              f"process={d.process_index}")
    print(f"process_count={jax.process_count()} "
          f"local_devices={jax.local_device_count()}")
    return 0


def _engine_from_args(args, phase_nets=True):
    from ..parallel.strategies import CommConfig
    from ..proto.messages import load_solver
    from .engine import Engine

    import dataclasses
    sp = load_solver(args.solver)
    # --wire_dtype rides TWO tiers: the compiled collectives (CommConfig,
    # bf16/f16 only) and the managed DCN payload codec (async tier, which
    # also takes int8). int8 never enters the compiled config — the local
    # mesh stays at gradient dtype while the DCN frames compress.
    wd_flag = args.wire_dtype or None
    if wd_flag == "int8":
        if not getattr(args, "async_ssp", False):
            raise SystemExit(
                "--wire_dtype int8 is a managed-tier (async DCN) wire "
                "format; compiled collectives take bf16/f16")
        wd_flag = None
    comm = CommConfig(default_strategy=args.strategy,
                      reduce=args.grad_reduce,
                      topk_policy=getattr(args, "topk_policy", "magnitude"),
                      wire_dtype=wd_flag,
                      topk_block=getattr(args, "topk_block", 0) or None,
                      dwbp_bucket_mb=(
                          None if getattr(args, "dwbp_bucket_mb", -1.0) < 0
                          else args.dwbp_bucket_mb),
                      param_arena=(getattr(args, "param_arena", "true")
                                   == "true"),
                      arena_bucket_mb=args.arena_bucket_mb,
                      server_logic=getattr(args, "server_logic", "inc"),
                      adarev_init_step=getattr(args, "adarev_init_step", 0.1))
    if args.sfb_auto:
        # same config, default strategy reset (auto_strategies fills in SFB)
        comm = dataclasses.replace(comm, default_strategy="dense")
    mesh = None
    mesh_cfg = None
    mesh_spec = args.mesh
    if mesh_spec:
        from ..config import MeshConfig
        mesh_cfg = MeshConfig.parse(mesh_spec)
        if getattr(args, "dcn_slices", 0) > 1:
            raise SystemExit("--mesh and --dcn_slices do not compose: the "
                             "named mesh's axes carry the whole topology")
        import jax
        if mesh_cfg.n_devices > jax.device_count():
            raise SystemExit(
                f"--mesh {mesh_spec} needs {mesh_cfg.n_devices} devices; "
                f"{jax.device_count()} available")
    dcn_slices = getattr(args, "dcn_slices", 0)
    if dcn_slices > 1:
        # two-tier mesh: slices over the slow (DCN) axis, devices within a
        # slice over the fast (ICI) axis; TOPK layers compress inter-slice
        import jax
        from ..parallel import make_mesh
        n = jax.device_count()
        if n % dcn_slices:
            raise SystemExit(f"--dcn_slices {dcn_slices} does not divide "
                             f"{n} devices")
        mesh = make_mesh(axes=("dcn", "data"),
                         shape=(dcn_slices, n // dcn_slices))
        comm.dcn_axis = "dcn"
    staleness = getattr(args, "staleness", 0)
    async_cfg = None
    if getattr(args, "async_ssp", False):
        # the staleness bound belongs to the ASYNC tier; the local step
        # stays plain sync SGD on this process's own mesh
        async_cfg = {"staleness": staleness,
                     "sync_every": getattr(args, "async_sync_every", 1)}
        # fault-tolerance knobs: negative flag values mean "use the
        # FaultConfig defaults" (config.py) — only explicit settings ride
        for key, flag in (("heartbeat_s", "async_heartbeat_s"),
                          ("liveness_timeout_s",
                           "async_liveness_timeout_s"),
                          ("reconnect_deadline_s",
                           "async_reconnect_deadline_s"),
                          ("gate_timeout_s", "async_gate_timeout_s"),
                          ("first_gate_timeout_s",
                           "async_first_gate_timeout_s")):
            v = getattr(args, flag, -1.0)
            if v is not None and v >= 0:
                async_cfg[key] = v
        # managed communication (SSPAggr): negative budget = the
        # ManagedCommConfig default (off); 0 is an explicit "unlimited"
        v = getattr(args, "comm_budget_mbps", -1.0)
        if v is not None and v >= 0:
            async_cfg["comm_budget_mbps"] = v
        v = getattr(args, "comm_priority_frac", -1.0)
        if v is not None and v > 0:
            async_cfg["comm_priority_frac"] = v
        if getattr(args, "comm_adaptive", False):
            async_cfg["comm_adaptive"] = True
        # the managed DCN frames take the flag as given (int8 included);
        # unset, the tier falls back to ManagedCommConfig.wire_dtype
        if args.wire_dtype:
            async_cfg["comm_wire_dtype"] = args.wire_dtype
        # two-tier fabric: this process leads an SPMD slice and the DCN
        # worker identity is the slice id (runtime/async_tier.FabricTier;
        # needs the POSEIDON_SLICE_ID/POSEIDON_SLICE_SIZE env contract)
        if getattr(args, "slice", False):
            async_cfg["slice"] = True
        staleness = 0
    elif getattr(args, "slice", False):
        raise SystemExit("--slice composes the two-tier fabric on top of "
                         "the async tier; it requires --async_ssp")
    metrics_port = getattr(args, "metrics_port", -1)
    return Engine(sp, comm=comm, mesh=mesh, mesh_cfg=mesh_cfg,
                  output_dir=args.output_dir,
                  staleness=staleness, sfb_auto=args.sfb_auto,
                  steps_per_dispatch=args.steps_per_dispatch,
                  device_transform=getattr(args, "device_transform", False),
                  async_ssp=async_cfg,
                  device_prefetch=args.device_prefetch,
                  max_in_flight=args.max_in_flight,
                  async_snapshot=args.async_snapshot,
                  trace_out=getattr(args, "trace_out", "") or None,
                  metrics_port=metrics_port if metrics_port >= 0 else None,
                  hbm_budget_gb=args.hbm_budget_gb,
                  remat=args.remat or None)


def _enable_compile_cache_from_args(args) -> None:
    """Stage the fast-restart layers (persistent XLA compile cache + AOT
    step store) before any program is compiled. Shared by train/serve/
    bench_serve. The location is not a flag: it is
    ``JAX_COMPILATION_CACHE_DIR`` when set, else ``<checkout>/.jax_cache``
    (compile_cache.resolve_cache_dir)."""
    from .compile_cache import enable_compile_cache
    resolved = enable_compile_cache(
        aot_steps=getattr(args, "aot_steps", "true") == "true")
    from .metrics import log
    log(f"compile cache: persistent XLA cache at {resolved} "
        f"(aot_steps={getattr(args, 'aot_steps', 'true')})")


def cmd_train(args) -> int:
    from .spans import recorder
    # the start-up timeline's first span (runtime/spans.py): everything
    # this command does before it builds the Engine
    with recorder.startup("cli_setup"):
        from .cluster import init_distributed
        if getattr(args, "async_ssp", False):
            # async-SSP: the processes stay INDEPENDENT jax runtimes — no
            # jax.distributed world, no collective rendezvous; the only
            # cross-process channel is the tier's parameter service. The tier
            # reads the LOCAL launcher's env contract; a hostfile launch does
            # not set it, and silently degrading to N isolated full-data runs
            # would be worse than refusing.
            if args.hostfile and "POSEIDON_PROC_ID" not in os.environ:
                raise SystemExit(
                    "--async_ssp currently rides the launch_local env "
                    "contract (POSEIDON_PROC_ID/NUM_PROCS/COORDINATOR); for "
                    "a hostfile cluster, start each node under that env (see "
                    "scripts/launch.py) instead of --hostfile/--node_id")
        else:
            # FIRST: jax.distributed.initialize refuses to run once anything
            # has touched the backend
            init_distributed(
                hostfile=args.hostfile or None,
                node_id=args.node_id if args.node_id >= 0 else None)
        _enable_compile_cache_from_args(args)
        from .. import config
        if args.bf16:
            config.set_perf_policy()
        # the two graph-level requests Net reads from the numeric policy at
        # construction; every other knob reaches the Engine as an argument
        config.set_policy(conv_layout=args.conv_layout.upper())
        if args.conv_strategy:
            config.set_policy(conv_strategy=args.conv_strategy)
        with recorder.startup("backend_init"):
            # the program's own first touch of the backend; a caller that
            # touched it already (the benchmark's harness) leaves ~0 here
            import jax
            jax.local_devices()
    eng = _engine_from_args(args)
    eng.profile_steps = args.profile
    if args.snapshot == "auto":
        # engine-level auto-resume: sweep stale snapshot tmp litter a
        # killed predecessor left behind, then restore the newest
        # solverstate under the solver's snapshot prefix
        restored = eng.auto_resume()
        if restored is None and args.weights:
            # first run of an auto-resume launch still honors init weights
            eng.restore_from(args.weights)
    elif args.snapshot:
        eng.restore_from(args.snapshot)
    elif args.weights:
        eng.restore_from(args.weights)
    try:
        eng.train()
    finally:
        eng.close()
    return 0


def cmd_test(args) -> int:
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P
    from ..core.net import Net
    from ..data.pipeline import build_phase_pipelines
    from ..data.workload import Shard
    from ..parallel import build_eval_step, make_mesh
    from ..proto.messages import load_net
    from .checkpoint import load_caffemodel
    from .cluster import init_distributed

    init_distributed(hostfile=args.hostfile or None,
                     node_id=args.node_id if args.node_id >= 0 else None)
    net_param = load_net(args.model)
    mesh = make_mesh()
    rank, nproc = jax.process_index(), jax.process_count()
    # each host scores a DISJOINT shard of the record space and contributes
    # only its addressable devices' rows (Engine._build_pipelines semantics)
    pipes, shapes = build_phase_pipelines(
        net_param, "TEST", batch_multiplier=jax.local_device_count(),
        shard=Shard(rank, nproc))
    net = Net(net_param, "TEST", source_shapes=shapes)
    params = net.init(jax.random.PRNGKey(0))
    if args.weights:
        params = load_caffemodel(args.weights, net, params)
    ev = build_eval_step(net, mesh)
    sharding = NamedSharding(mesh, P("data"))
    acc = {}
    for _ in range(args.iterations):
        batch = {}
        for pipe in pipes:
            for k, v in next(pipe).items():
                if nproc > 1:
                    batch[k] = jax.make_array_from_process_local_data(
                        sharding, v)
                else:
                    batch[k] = jax.device_put(v, sharding)
        for k, v in ev(params, batch).items():
            acc[k] = acc.get(k, 0.0) + float(v)
    if rank == 0:
        for k in sorted(acc):
            print(f"{k}: {acc[k] / args.iterations:.4f}")
    for p in pipes:
        p.close()
    return 0


def cmd_time(args) -> int:
    """Per-layer forward timing + whole-graph forward/backward timing
    (the reference's `caffe time`, tools/caffe_main.cpp:256-328)."""
    import jax
    import jax.numpy as jnp
    from ..core.net import Net
    from ..proto.messages import load_net

    net_param = load_net(args.model)
    shapes = {}
    if net_param.input:
        net = Net(net_param, "TRAIN")
    else:
        # synthesize source shapes for data layers
        from ..core.net import filter_net
        from ..proto.messages import NetState
        from ..core.layers import DATA_SOURCE_TYPES
        for lp in filter_net(net_param, NetState(phase="TRAIN")):
            if lp.canonical_type() in DATA_SOURCE_TYPES:
                from ..data.pipeline import layer_batch_size
                b = layer_batch_size(lp) or args.batch_size
                chw, label_shape = None, ()
                src = (lp.data_param.source or lp.image_data_param.source
                       or lp.hdf5_data_param.source
                       or lp.window_data_param.source)
                if src:
                    # read one record for the true (C, H, W) — a synthesized
                    # 3x224x224 guess would mis-size every downstream layer
                    try:
                        from ..data.pipeline import build_source
                        from ..data.workload import Shard
                        s = build_source(lp, Shard(0, 1))
                        arr, lab = s.read(0)
                        chw = arr.shape
                        if s.tokens:    # a target per position
                            label_shape = tuple(lab.shape)
                    except Exception:
                        chw = None
                if chw is None:
                    c = lp.transform_param.crop_size or 224
                    chw = (3, c, c)
                if lp.transform_param.crop_size:
                    chw = (chw[0], lp.transform_param.crop_size,
                           lp.transform_param.crop_size)
                shapes[lp.top[0]] = (b,) + tuple(chw)
                if len(lp.top) > 1:
                    shapes[lp.top[1]] = (b,) + label_shape
        net = Net(net_param, "TRAIN", source_shapes=shapes)
    # the benchmark batch is whatever the model actually declares
    batch = net.blob_shapes[net.input_names[0]][0]

    rng = jax.random.PRNGKey(0)
    params = net.init(rng)
    inputs = {name: (jnp.zeros(net.blob_shapes[name], jnp.float32)
                     if len(net.blob_shapes[name]) > 1 else
                     jnp.zeros(net.blob_shapes[name], jnp.int32))
              for name in net.input_names}

    fwd = jax.jit(lambda p, x: net.apply(p, x, train=True,
                                         rng=jax.random.PRNGKey(1)).loss)
    grad = jax.jit(jax.grad(lambda p, x: net.apply(
        p, x, train=True, rng=jax.random.PRNGKey(1)).loss))

    jax.block_until_ready(fwd(params, inputs))  # compile
    t0 = _time.perf_counter()
    for _ in range(args.iterations):
        out = fwd(params, inputs)
    jax.block_until_ready(out)
    fwd_ms = (_time.perf_counter() - t0) / args.iterations * 1e3

    jax.block_until_ready(jax.tree_util.tree_leaves(grad(params, inputs))[0])
    t0 = _time.perf_counter()
    for _ in range(args.iterations):
        g = grad(params, inputs)
    jax.block_until_ready(jax.tree_util.tree_leaves(g)[0])
    fb_ms = (_time.perf_counter() - t0) / args.iterations * 1e3

    # Per-layer forward timing (the reference's per-layer breakdown,
    # caffe_main.cpp:256-328). Layers are timed in isolation, so totals can
    # differ from the fused whole-graph time — that fusion gap is itself
    # useful signal.
    if args.per_layer:
        from ..core.layers import ApplyCtx
        print(f"{'layer':<24}{'type':<22}{'fwd ms':>10}{'bwd ms':>10}")
        for layer in net.layers:
            bottoms = [jnp.zeros(net.blob_shapes[bname], jnp.float32)
                       for bname in layer.lp.bottom]
            lp_params = {pd.name: params[layer.name][pd.name]
                         for pd in layer.params} if layer.params else {}

            def run(ps, bs, _l=layer):
                ctx = ApplyCtx(train=True, rng=jax.random.PRNGKey(0))
                return _l.apply(ps, bs, ctx)

            def timed(fn, *fargs):
                jitted = jax.jit(fn)
                jax.block_until_ready(jitted(*fargs))
                t0 = _time.perf_counter()
                for _ in range(args.iterations):
                    out = jitted(*fargs)
                leaves = jax.tree_util.tree_leaves(out)
                jax.block_until_ready(leaves[0] if leaves else jnp.zeros(()))
                return (_time.perf_counter() - t0) / args.iterations * 1e3

            try:
                fwd_l = timed(run, lp_params, bottoms)
            except Exception as e:  # e.g. int-labeled losses fed zeros
                print(f"{layer.name:<24}{layer.TYPE:<22}{'skip':>10}"
                      f"{'skip':>10} ({e})")
                continue
            # per-layer backward: grad wrt params+bottoms of a scalarized
            # output (the reference's Backward timing, caffe_main.cpp:300+).
            # jax.grad re-runs the forward inside, so subtract fwd time to
            # report the backward alone like the reference does.
            try:
                def bwd(ps, bs, _l=layer):
                    out = run(ps, bs, _l=_l)
                    return sum(jnp.sum(o.astype(jnp.float32))
                               for o in jax.tree_util.tree_leaves(out))

                fb_l = timed(jax.grad(bwd, argnums=(0, 1)),
                             lp_params, bottoms)
                bwd_l = max(fb_l - fwd_l, 0.0)
                print(f"{layer.name:<24}{layer.TYPE:<22}{fwd_l:>10.3f}"
                      f"{bwd_l:>10.3f}")
            except Exception:  # non-differentiable layer (data/accuracy/...)
                print(f"{layer.name:<24}{layer.TYPE:<22}{fwd_l:>10.3f}"
                      f"{'-':>10}")

    # Static per-layer comm accounting over a hypothetical mesh — what each
    # strategy moves per step and what it saves vs dense (stats.hpp analog).
    if args.per_layer and args.comm_devices > 1:
        from ..parallel import CommConfig, auto_strategies
        from .comm_stats import comm_summary, layer_comm_table
        n = args.comm_devices
        slices = args.dcn_slices
        # purely static accounting — a {axis: size} shape dict models the
        # requested topology without needing that many physical devices
        wire = getattr(args, "wire_dtype", "") or None
        blockk = getattr(args, "topk_block", 0) or None
        if slices > 1:
            if n % slices:
                raise SystemExit(f"--dcn_slices {slices} does not divide "
                                 f"--comm_devices {n}")
            mesh_shape = {"dcn": slices, "data": n // slices}
            cc = CommConfig(dcn_axis="dcn", default_strategy=args.strategy,
                            wire_dtype=wire, topk_block=blockk)
        else:
            mesh_shape = {"data": n}
            cc = CommConfig(default_strategy=args.strategy,
                            wire_dtype=wire, topk_block=blockk)
        if args.sfb_auto:
            cc.layer_strategies.update(auto_strategies(net))
        table = layer_comm_table(net, cc, mesh_shape)
        print(f"\nComm bytes/step/device over {n} devices"
              + (f" ({slices} DCN slices)" if slices > 1 else "") + ":")
        print(f"{'layer':<24}{'strategy':<8}{'ici B':>12}{'dcn B':>12}"
              f"{'vs dense':>10}{'est ms':>9}")
        for lname, row in table.items():
            print(f"{lname:<24}{row['strategy']:<8}"
                  f"{row['ici_bytes_per_step']:>12}"
                  f"{row['dcn_bytes_per_step']:>12}"
                  f"{str(row['savings_vs_dense'] or '-'):>10}"
                  f"{row['est_comm_ms']:>9}")
        s = comm_summary(table, fb_ms)
        print(f"total: {s['total_bytes_per_step']} B/step/dev, "
              f"{s['savings_vs_dense'] or '-'}x vs dense, "
              f"est comm {s['est_comm_ms_per_step']} ms "
              f"({s.get('est_comm_fraction_if_unoverlapped', 0):.0%} of "
              f"measured step if unoverlapped)")

    print(f"Average Forward pass: {fwd_ms:.3f} ms")
    print(f"Average Forward-Backward: {fb_ms:.3f} ms")
    print(f"Throughput: {batch / (fb_ms / 1e3):.1f} images/s "
          f"(batch {batch})")
    return 0


# --------------------------------------------------------------------------- #
# serving tier (poseidon_tpu/serving/)
# --------------------------------------------------------------------------- #

_BENCH_SERVE_NET = """
name: "bench_serve_synthetic"
input: "data"
input_dim: 1 input_dim: 3 input_dim: 32 input_dim: 32
layers { name: "conv1" type: CONVOLUTION bottom: "data" top: "conv1"
  convolution_param { num_output: 16 kernel_size: 3
    weight_filler { type: "xavier" } } }
layers { name: "relu1" type: RELU bottom: "conv1" top: "conv1" }
layers { name: "pool1" type: POOLING bottom: "conv1" top: "pool1"
  pooling_param { pool: MAX kernel_size: 2 stride: 2 } }
layers { name: "fc" type: INNER_PRODUCT bottom: "pool1" top: "fc"
  inner_product_param { num_output: 10 weight_filler { type: "xavier" } } }
layers { name: "prob" type: SOFTMAX bottom: "fc" top: "prob" }
"""


def _build_serving_executor(model: str, weights: str, buckets: str,
                            device=None):
    """Shared by serve/bench_serve: deploy net (or the built-in synthetic
    one) + optional weights -> warmed BucketedExecutor, optionally pinned
    to one local device (the fleet's placement unit)."""
    from ..serving.executor import BucketedExecutor, parse_buckets
    bucket_sizes = parse_buckets(buckets)
    if model:
        return BucketedExecutor.from_files(model, weights or None,
                                           buckets=bucket_sizes,
                                           device=device)
    import jax
    from ..core.net import Net
    from ..proto.messages import load_net_from_string
    net = Net(load_net_from_string(_BENCH_SERVE_NET), "TEST")
    params = net.init(jax.random.PRNGKey(0))
    if weights:
        from ..serving.executor import load_serving_params
        params = load_serving_params(net, params, weights)
    return BucketedExecutor(net, params, buckets=bucket_sizes,
                            device=device)


def _resolve_fleet_devices(spec: str, n_replicas: int):
    """``--devices "0,2,3"`` -> the named jax devices; "" -> round-robin
    over every local device when the fleet has more than one replica (a
    single replica keeps the default device). Asking for an index that
    does not exist fails loudly — the make_mesh lesson: never silently
    truncate a placement request."""
    import jax
    local = jax.devices()
    if spec:
        try:
            idxs = [int(tok) for tok in spec.split(",") if tok.strip()]
        except ValueError:
            raise SystemExit(f"--devices {spec!r}: expected comma-separated "
                             f"device indices") from None
        bad = [i for i in idxs if i < 0 or i >= len(local)]
        if bad:
            raise SystemExit(f"--devices {spec!r}: no such device index "
                             f"{bad} (have {len(local)} local devices)")
        return [local[i] for i in idxs]
    if n_replicas <= 1:
        return []
    return list(local)


def build_serving_fleet(model: str, weights: str, buckets: str,
                        n_replicas: int, devices_spec: str = "",
                        max_delay_s: float = 0.005, max_queue: int = 64,
                        warm_async: bool = False, **manager_kw):
    """N warmed replicas under one :class:`ReplicaManager`, round-robin
    pinned across the resolved devices (replicas > devices is fine — CPU
    proxies and oversubscribed hosts still get N independent engines)."""
    from ..serving.fleet import ReplicaManager
    devices = _resolve_fleet_devices(devices_spec, n_replicas)

    def factory(device):
        return _build_serving_executor(model, weights, buckets,
                                       device=device)

    return ReplicaManager.build(factory, n_replicas, devices=devices,
                                warm_async=warm_async,
                                max_delay_s=max_delay_s,
                                max_queue=max_queue, **manager_kw)


LLM_PRESETS = ("tiny", "gpt_small")


def _build_generate_executor(preset: str, device=None):
    """A warmed paged-KV :class:`GenerateExecutor` over a named transformer
    preset. ``--generate`` serving has no snapshot format yet, so params
    are preset-initialized (the same smoke contract as an empty
    ``--weights`` on the CNN path)."""
    import jax
    from ..models.transformer import (TransformerConfig, gpt_small_config,
                                      init_params)
    from ..serving.continuous import DEFAULT_PROMPT_BUCKETS, GenerateExecutor

    if preset == "gpt_small":
        cfg = gpt_small_config(max_seq=512, remat=False)
    elif preset == "tiny":
        cfg = TransformerConfig(vocab_size=256, d_model=32, n_heads=4,
                                n_layers=2, d_ff=128, max_seq=128)
    else:
        raise SystemExit(
            f"--generate serves a transformer preset, not a deploy "
            f"prototxt; --model must be one of {'|'.join(LLM_PRESETS)} "
            f"(got {preset!r})")
    params = init_params(cfg, jax.random.PRNGKey(0))
    # a preset smaller than the default ladder drops the buckets it
    # cannot hold rather than refusing to serve
    buckets = tuple(b for b in DEFAULT_PROMPT_BUCKETS if b < cfg.max_seq)
    return GenerateExecutor(cfg, params, prompt_buckets=buckets,
                            device=device)


def _cmd_serve_generate(args) -> int:
    """The LLM branch of ``serve``: paged-KV continuous batching behind
    the same front door — ``generate`` wire op, streaming ``gen_chunk``
    frames, fleet routing/failover when ``--replicas > 1``."""
    import json
    import signal

    from ..config import fleet_config
    from ..serving.server import InferenceServer
    from .metrics import log

    _enable_compile_cache_from_args(args)
    if args.weights or args.watch:
        raise SystemExit("--generate serves preset-initialized params; "
                         "--weights/--watch have no LLM snapshot format "
                         "to load yet")
    replicas = max(1, getattr(args, "replicas", 1))
    fleet_mode = replicas > 1 or bool(getattr(args, "devices", ""))
    manager = None
    if fleet_mode:
        from ..serving.fleet import ReplicaManager
        devices = _resolve_fleet_devices(getattr(args, "devices", ""),
                                         replicas)

        def factory(device):
            return _build_generate_executor(args.model, device=device)

        manager = ReplicaManager.build(factory, replicas, devices=devices,
                                       max_queue=args.max_queue)
        ref = manager.reference_executor()
        log(f"serve: warmed {len(manager.replicas)} generate replicas "
            f"({args.model}, page_size={ref.page_size}, "
            f"rungs={ref.decode_rungs}, buckets={ref.prompt_buckets})")
    else:
        executor = _build_generate_executor(args.model)
        log(f"serve: warmed generate executor ({args.model}, "
            f"page_size={executor.page_size}, "
            f"rungs={executor.decode_rungs}, "
            f"buckets={executor.prompt_buckets})")
    if args.host not in ("127.0.0.1", "localhost", "::1"):
        log(f"serve: WARNING: binding {args.host!r} — the wire format is "
            f"pickled frames (arbitrary code execution for anyone who can "
            f"connect); serve only on loopback or a trusted network")
    metrics_port = getattr(args, "metrics_port", -1)
    server = InferenceServer(
        executor=None if fleet_mode else executor,
        fleet=manager,
        host=args.host, port=args.port, max_queue=args.max_queue,
        default_deadline_s=(args.deadline_ms / 1e3
                            if args.deadline_ms > 0 else None),
        stats_refresh_s=(fleet_config().stats_refresh_s
                         if fleet_mode or metrics_port >= 0 else 0.0))
    log(f"serve: listening on {server.host}:{server.port} (generate op"
        + (f", {replicas} replicas)" if fleet_mode else ")"))
    metrics_srv = None
    if metrics_port >= 0:
        from .metrics import MetricsServer
        server.stats_snapshot()
        metrics_srv = MetricsServer(server.stats, port=metrics_port)
        log(f"serve: metrics endpoint on "
            f"http://127.0.0.1:{metrics_srv.port}/")

    def _graceful(signum, frame):
        log(f"serve: signal {signum}; draining in-flight requests")
        server.request_stop()

    signal.signal(signal.SIGTERM, _graceful)
    signal.signal(signal.SIGINT, _graceful)
    try:
        server.wait_until_stopped()
    except KeyboardInterrupt:
        pass
    server.shutdown(drain=True)
    if metrics_srv is not None:
        metrics_srv.close()
    print(json.dumps({"serving_final_stats": server.stats_snapshot()}),
          flush=True)
    return 0


def cmd_serve(args) -> int:
    """Serve a trained snapshot over TCP: dynamic micro-batching, a
    shape-bucketed AOT compile cache, checkpoint hot-reload, and graceful
    drain on SIGTERM/SIGINT (exit 0, no request silently dropped).
    ``--replicas N`` puts a replica fleet behind the same front door:
    least-loaded routing, per-replica health/failover, rolling reload.
    ``--generate`` serves a transformer preset through the paged-KV
    continuous batcher instead (the ``generate`` wire op)."""
    import json
    import signal

    if getattr(args, "generate", False):
        return _cmd_serve_generate(args)

    from ..config import fleet_config
    from ..serving.reloader import CheckpointReloader, FleetReloader
    from ..serving.server import InferenceServer
    from .metrics import log

    # a serving replica's bucket warm-up is the same cold-start bill the
    # training tier pays: the persistent cache turns a restarted replica's
    # AOT bucket compiles into disk reads
    _enable_compile_cache_from_args(args)
    watch = args.watch
    if watch == "auto":
        # derive the snapshot prefix from the weights path:
        # out/snap/lenet_iter_500.solverstate.npz -> out/snap/lenet
        if args.weights and "_iter_" in args.weights:
            watch = args.weights.split("_iter_")[0]
        else:
            # refusing beats silently serving without the reloader the
            # operator asked for; checked BEFORE the (slow) bucket warm-up
            raise SystemExit(
                "--watch auto needs --weights pointing at a "
                "<prefix>_iter_N artifact to derive the prefix from; "
                "pass the snapshot prefix explicitly instead")
    # when --weights is itself a snapshot under the watch prefix, seed
    # the reloader with it so the first poll only swaps to something
    # strictly newer (never a redundant or backwards swap)
    serving_snap = (args.weights if watch and args.weights
                    and "_iter_" in args.weights
                    and args.weights.split("_iter_")[0] == watch
                    else None)
    replicas = max(1, getattr(args, "replicas", 1))
    fleet_mode = replicas > 1 or bool(getattr(args, "devices", ""))
    reloader = None
    if fleet_mode:
        manager = build_serving_fleet(
            args.model, args.weights, args.buckets, replicas,
            getattr(args, "devices", ""),
            max_delay_s=args.max_delay_ms / 1e3, max_queue=args.max_queue)
        ref = manager.reference_executor()
        log(f"serve: warmed {len(manager.replicas)} replicas, buckets "
            f"{ref.buckets} ({ref.net.name or 'net'}, "
            f"{ref.net.param_count()} params each)")
        if watch:
            reloader = FleetReloader(manager, watch, poll_s=args.poll_s,
                                     current_path=serving_snap)
    else:
        executor = _build_serving_executor(args.model, args.weights,
                                           args.buckets)
        log(f"serve: warmed buckets {executor.buckets} "
            f"({executor.net.name or 'net'}, "
            f"{executor.net.param_count()} params)")
        if watch:
            reloader = CheckpointReloader(executor, watch,
                                          poll_s=args.poll_s,
                                          current_path=serving_snap)
    if watch:
        log(f"serve: watching {watch!r} for newer snapshots "
            f"(every {args.poll_s}s)")
    if args.host not in ("127.0.0.1", "localhost", "::1"):
        log(f"serve: WARNING: binding {args.host!r} — the wire format is "
            f"pickled frames (arbitrary code execution for anyone who can "
            f"connect); serve only on loopback or a trusted network")
    metrics_port = getattr(args, "metrics_port", -1)
    server = InferenceServer(
        executor=None if fleet_mode else executor,
        fleet=manager if fleet_mode else None,
        host=args.host, port=args.port,
        max_delay_s=args.max_delay_ms / 1e3, max_queue=args.max_queue,
        default_deadline_s=(args.deadline_ms / 1e3
                            if args.deadline_ms > 0 else None),
        reloader=reloader,
        # the refresher keeps the registry section live for ANY metrics
        # endpoint (single-engine included — a once-seeded section would
        # read as a frozen server), and for the fleet health surface
        stats_refresh_s=(fleet_config().stats_refresh_s
                         if fleet_mode or metrics_port >= 0 else 0.0))
    log(f"serve: listening on {server.host}:{server.port}"
        + (f" ({replicas} replicas)" if fleet_mode else ""))
    metrics_srv = None
    if metrics_port >= 0:
        from .metrics import MetricsServer
        server.stats_snapshot()        # seed the section before first poll
        metrics_srv = MetricsServer(server.stats, port=metrics_port)
        log(f"serve: metrics endpoint on "
            f"http://127.0.0.1:{metrics_srv.port}/ (fleet health surface)")

    def _graceful(signum, frame):
        log(f"serve: signal {signum}; draining in-flight requests")
        # the handler only flips flags; the drain (thread joins) runs on
        # the main thread below — not signal-handler work
        server.request_stop()

    signal.signal(signal.SIGTERM, _graceful)
    signal.signal(signal.SIGINT, _graceful)
    try:
        server.wait_until_stopped()
    except KeyboardInterrupt:
        pass
    server.shutdown(drain=True)
    if metrics_srv is not None:
        metrics_srv.close()
    print(json.dumps({"serving_final_stats": server.stats_snapshot()}),
          flush=True)
    return 0


def run_serving_bench(executor, requests: int, concurrency: int, batch: int,
                      max_delay_ms: float = 5.0, max_queue: int = 64,
                      deadline_ms=None, fleet=None, offered_rps=None):
    """The in-process serving bench driver behind `bench_serve`: port-0
    server + the load generator, request sizes cycling 1..batch over the bucket ladder. Pass ``fleet`` (a
    ReplicaManager; ``executor=None``) to stand the whole fleet behind
    the front door, and ``offered_rps`` for the open-loop arrival-rate
    mode. Returns (run_load result, server stats snapshot)."""
    import numpy as np

    from ..serving.client import run_load
    from ..serving.server import InferenceServer

    # batching/admission knobs live on the REPLICAS in fleet mode (each
    # batcher was configured at build_serving_fleet time); passing them to
    # the server there would be a silent no-op
    server = (InferenceServer(fleet=fleet) if fleet is not None else
              InferenceServer(executor=executor,
                              max_delay_s=max_delay_ms / 1e3,
                              max_queue=max_queue))
    ref = executor if executor is not None else fleet.reference_executor()
    name = ref.input_names[0]
    row_shape = tuple(ref.net.blob_shapes[name][1:])
    max_rows = max(1, min(batch, ref.max_batch))
    frames = np.random.RandomState(0).randn(
        max_rows, *row_shape).astype(np.float32)

    def make_inputs(i):
        return {name: frames[: 1 + i % max_rows]}

    try:
        result = run_load(server.addr, make_inputs, n_requests=requests,
                          concurrency=concurrency, deadline_ms=deadline_ms,
                          offered_rps=offered_rps)
        stats = server.stats_snapshot()
    finally:
        server.shutdown()
    return result, stats


def cmd_bench_serve(args) -> int:
    """In-process serving latency microbenchmark: stand the server up on
    port 0, drive it with the shared load generator, print ONE JSON line
    (p50/p99/throughput + shed/fill telemetry). ``--replicas N`` benches
    the fleet path; ``--offered_rps R`` switches the generator to the
    open-loop arrival-rate mode (goodput-vs-offered-load measurable)."""
    import json

    _enable_compile_cache_from_args(args)
    replicas = max(1, getattr(args, "replicas", 1))
    offered = (args.offered_rps if getattr(args, "offered_rps", 0) > 0
               else None)
    if replicas > 1 or getattr(args, "devices", ""):
        fleet = build_serving_fleet(
            args.model, args.weights, args.buckets, replicas,
            getattr(args, "devices", ""),
            max_delay_s=args.max_delay_ms / 1e3, max_queue=args.max_queue)
        executor = None
    else:
        fleet = None
        executor = _build_serving_executor(args.model, args.weights,
                                           args.buckets)
    result, stats = run_serving_bench(
        executor, args.requests, args.concurrency, args.batch,
        max_delay_ms=args.max_delay_ms, max_queue=args.max_queue,
        deadline_ms=args.deadline_ms if args.deadline_ms > 0 else None,
        fleet=fleet, offered_rps=offered)
    if fleet is not None:
        result["replicas"] = replicas
        result["routing"] = stats["routing"]
        result["states"] = stats["states"]
        result["batches"] = stats["batches"]
        fills = [r.get("batch_fill") for r in stats["replicas"].values()
                 if r.get("batch_fill") is not None]
        result["batch_fill"] = (round(sum(fills) / len(fills), 4)
                                if fills else None)
    else:
        result["batch_fill"] = stats["batch_fill"]
        result["batches"] = stats["batches"]
        result["bucket_calls"] = stats["bucket_calls"]
    if not result.get("ok") or result.get("p99_ms") is None:
        # every request shed/errored: fail loudly, never a clean 0.0 line
        # (spread result FIRST — it carries an integer "error" counter that
        # must not clobber the diagnostic string)
        print(json.dumps({**result, "metric": "serving_p99_ms",
                          "value": 0.0, "unit": "ms",
                          "error_counts": result.get("error"),
                          "error": "no successful requests"}),
              flush=True)
        return 1
    print(json.dumps({"metric": "serving_p99_ms",
                      "value": result["p99_ms"],
                      "unit": "ms", **result}), flush=True)
    return 0


def cmd_convert_imageset(args) -> int:
    from .tools import convert_imageset
    convert_imageset(args.listfile, args.out_db, root_folder=args.root_folder,
                     resize_height=args.resize_height,
                     resize_width=args.resize_width, shuffle=args.shuffle,
                     gray=args.gray)
    return 0


def cmd_compute_image_mean(args) -> int:
    from .tools import compute_image_mean
    compute_image_mean(args.db, args.out_file)
    return 0


def cmd_partition_data(args) -> int:
    from .tools import partition_data
    partition_data(args.db, args.num_shards)
    return 0


def cmd_convert_db(args) -> int:
    from .tools import convert_db
    convert_db(args.src, args.out, args.backend)
    return 0


def cmd_extract_features(args) -> int:
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P
    from ..core.net import Net
    from ..data.pipeline import build_phase_pipelines
    from ..data.workload import Shard
    from ..parallel import make_mesh
    from ..proto.messages import load_net
    from .checkpoint import load_caffemodel
    from .cluster import init_distributed
    from .tools import extract_features

    init_distributed(hostfile=args.hostfile or None,
                     node_id=args.node_id if args.node_id >= 0 else None)
    rank, nproc = jax.process_index(), jax.process_count()
    net_param = load_net(args.model)
    # each process extracts a disjoint record shard and writes its own DBs —
    # the reference's per-(client,thread) LevelDB naming
    # (feature_extractor.cpp:43-80)
    pipes, shapes = build_phase_pipelines(net_param, "TEST", 1,
                                          shard=Shard(rank, nproc))
    net = Net(net_param, "TEST", source_shapes=shapes)
    params = net.init(jax.random.PRNGKey(0))
    if args.weights:
        params = load_caffemodel(args.weights, net, params)
    prefix = args.out_prefix if nproc == 1 else \
        f"{args.out_prefix}_client{rank}"
    # batches land with the train path's batch sharding (engine.py), not
    # defaulted onto device 0
    sharding = NamedSharding(make_mesh(), P("data"))
    extract_features(net, params, args.blobs.split(","), pipes[0],
                     args.num_batches, prefix, sharding=sharding)
    for p in pipes:
        p.close()
    return 0


def build_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(prog="poseidon_tpu",
                                description=__doc__.split("\n")[0])
    sub = p.add_subparsers(dest="command", required=True)

    t = sub.add_parser("train", help="train a model from a solver prototxt")
    t.add_argument("--solver", required=True)
    t.add_argument("--snapshot", default="",
                   help="resume from a .solverstate.npz, or 'auto' to pick "
                        "the newest one under the solver's snapshot_prefix")
    t.add_argument("--weights", default="",
                   help="finetune from a .caffemodel")
    t.add_argument("--output_dir", default=".")
    t.add_argument("--strategy", default="dense",
                   choices=["dense", "sfb", "topk"],
                   help="default gradient sync strategy")
    t.add_argument("--sfb-auto", action="store_true",
                   help="pick SFB per FC layer by cost model (SACP)")
    t.add_argument("--grad-reduce", default="mean", choices=["mean", "sum"])
    t.add_argument("--topk_policy", default="magnitude",
                   choices=["magnitude", "random", "fixed_order"],
                   help="which entries the TOPK budget sends (the server's "
                        "UpdateSortPolicy)")
    t.add_argument("--wire_dtype", default="",
                   choices=["", "f32", "bf16", "f16", "int8"],
                   help="reduced-precision gradient exchange: cast grads to "
                        "this dtype for every collective (DenseRowFloat16 "
                        "analog); with --async_ssp it also compresses the "
                        "managed DCN delta frames with exact error feedback "
                        "(int8 is DCN-only); empty = exchange at gradient "
                        "dtype")
    t.add_argument("--topk_block", type=int, default=0,
                   help="blocked top-k selection: pick top-k within blocks "
                        "of this many elements instead of one global sort "
                        "(row-granular, like the reference server); 0 = "
                        "global top-k")
    t.add_argument("--dwbp_bucket_mb", type=float, default=-1.0,
                   help="chain DWBP gradient psums into ~N-MB buckets so "
                        "each bucket stays a DISTINCT collective issued "
                        "mid-backward (the reference's per-blob sync-thread "
                        "structure, solver.cpp:419-449); 0 = one per blob, "
                        "negative = off (XLA's combiner decides)")
    t.add_argument("--param_arena", default="true",
                   choices=["true", "false"],
                   help="the flat parameter buffer of the two steps whose "
                        "state lives in one (ON by default): the fsdp-"
                        "sharded step of --mesh, which needs it, and the "
                        "--staleness boundary exchange, which sums "
                        "ceil(bytes/arena_bucket_mb) buckets instead of "
                        "one delta per leaf (false = one per leaf). The "
                        "synchronous data-parallel step packs nothing "
                        "under either value: it sums each gradient leaf "
                        "where backward makes it. Checkpoints are per "
                        "leaf either way")
    t.add_argument("--arena_bucket_mb", type=float, default=4.0,
                   help="bucket size in MB of the flat buffer's exchanges "
                        "(--mesh fsdp reduce-scatters, --staleness "
                        "boundary sums; DWBP-ordered exact element "
                        "ranges; <= 0 = one bucket per leaf)")
    t.add_argument("--hbm_budget_gb", type=float, default=0.0,
                   help="per-device HBM budget (GiB) for the measured "
                        "remat planner (core/remat.py): the no-remat "
                        "train step compiles once, its real "
                        "memory_analysis() peak is read, and a greedy "
                        "cheapest-recompute-per-byte knapsack drops "
                        "stored activations (jax.checkpoint on the "
                        "chosen layers) until the step fits. Negative = "
                        "auto-detect the device's own HBM limit; 0 = "
                        "off")
    t.add_argument("--remat", default="",
                   help="activation remat override: a comma-separated "
                        "layer list checkpoints exactly those layers "
                        "(no measuring compile); an entry written "
                        "/regex/ checkpoints each run of consecutive "
                        "layers whose names start with the same match "
                        "as ONE segment, storing only what the run takes "
                        "from outside (/p\\d+_l\\d+_/ = one per block of "
                        "a looped LM); 'auto' plans against "
                        "--hbm_budget_gb; empty or 'none' = off. A unit "
                        "also keeps what its Pallas forward kernels "
                        "(flash attention, the KDA / Gated DeltaNet scan) "
                        "wrote, so that the backward's replay runs none "
                        "of them twice, while the compiled step stays "
                        "within --hbm_budget_gb (none given: 88%% of the "
                        "device's memory); stats.yaml's remat section "
                        "says what was kept")
    t.add_argument("--bf16", action="store_true",
                   help="the documented bf16 training path: bfloat16 "
                        "compute (MXU-native) + the exact space-to-depth "
                        "stem rewrite; params/optimizer state/softmax "
                        "stats stay f32. Accuracy guardrail: the LeNet "
                        "loss-trajectory smoke must track f32 within "
                        "numeric.BF16_SMOKE_* (tests/test_kernels.py). "
                        "Default f32 matches Caffe numerics exactly")
    t.add_argument("--conv_strategy", default="",
                   choices=["", "direct", "im2col", "s2d"],
                   help="conv lowering strategy: a value forces one "
                        "strategy net-wide; empty = the global conv_s2d "
                        "policy (on under --bf16), with a 1-input-channel "
                        "conv lowered as im2col for the TPU")
    t.add_argument("--conv_layout", default="auto",
                   type=lambda s: s.lower(),
                   choices=["nchw", "nhwc", "auto"],
                   help="internal activation layout for the whole graph "
                        "(core/net.py plans conv/pool/LRN natively in it; "
                        "checkpoints stay canonical NCHW). 'auto' = the "
                        "per-backend table in numeric.resolve_conv_layout "
                        "(NHWC on tpu and gpu, NCHW on cpu); the run's log "
                        "([conv_layout]) and stats.yaml (conv_layout:) say "
                        "which plan it took")
    t.add_argument("--mesh", default="",
                   help="named SPMD mesh spec, e.g. 'dp2,fsdp2,tp1' "
                        "(axes: dp = data parallel, fsdp = sharded "
                        "parameter arena with reduce-scatter/all-gather "
                        "buckets, tp = tensor-parallel FC column/row "
                        "shards planned per layer); sizes of 1 "
                        "deactivate an axis. Empty = the flat data mesh")
    t.add_argument("--dcn_slices", type=int, default=0,
                   help="split devices into N slices on a slow (DCN) mesh "
                        "axis: dense sync intra-slice, TOPK-compressed "
                        "exchange inter-slice (managed comm / SSPAggr)")
    t.add_argument("--staleness", type=int, default=0,
                   help="SSP bound s: devices run local steps, reconciling "
                        "every s+1 iters (0 = synchronous, the reference's "
                        "recommended setting)")
    t.add_argument("--server_logic", default="inc",
                   choices=["inc", "adarevision"],
                   help="SSP anchor update rule: plain delta increment "
                        "(inc) or delay-corrected AdaGrad (the server's "
                        "adarevision_server_table_logic); needs --staleness")
    t.add_argument("--adarev_init_step", type=float, default=0.1,
                   help="adarevision server init_step_size; scales the SUM "
                        "of group updates (reduce is ignored — the server "
                        "applies every group's full update, the reference's "
                        "RowBatchInc semantics), so ~base_lr/n_groups is "
                        "the stable regime")
    t.add_argument("--async_ssp", action="store_true",
                   help="wait-free asynchronous SSP across launcher "
                        "processes (the Bösen execution model, "
                        "parallel/async_ssp.py): each process trains on "
                        "its LOCAL mesh, parameter increments stream to a "
                        "rank-0 service, reads gate on --staleness; no "
                        "jax.distributed world, no cross-process barrier")
    t.add_argument("--async_sync_every", type=int, default=1,
                   help="optimizer iterations per async-SSP flush clock")
    t.add_argument("--slice", action="store_true",
                   help="two-tier fabric (parallel/fabric.py): this "
                        "process LEADS an SPMD slice and the async-SSP "
                        "worker identity is the SLICE id — synchronous "
                        "dp/fsdp/tp math inside the slice, bounded-"
                        "staleness exchange between slices, admit/retire/"
                        "failover at slice granularity. Requires "
                        "--async_ssp plus the POSEIDON_SLICE_ID/"
                        "POSEIDON_SLICE_SIZE env contract; only the "
                        "slice leader (rank-in-slice 0) may run it")
    t.add_argument("--comm_budget_mbps", type=float, default=-1.0,
                   help="managed communication (SSPAggr): per-link "
                        "bandwidth budget in Mbit/s for the async-SSP "
                        "tier, metered as a token bucket over ACTUAL "
                        "frame bytes on both push and pull channels. A "
                        "tight budget switches to magnitude-prioritized "
                        "PARTIAL pushes (top --comm_priority_frac of the "
                        "delta by |value|, TOPK index+value wire form) "
                        "with the exact complement carried locally and "
                        "force-flushed every staleness+1 clocks; read "
                        "gates run on fully-flushed (durable) clocks so "
                        "the SSP bound is preserved exactly. <= 0 = "
                        "unlimited — byte-for-byte the dense path")
    t.add_argument("--comm_priority_frac", type=float, default=-1.0,
                   help="fraction of delta entries a budget-tight partial "
                        "push ships, ranked by |value| across the whole "
                        "update (default 0.1); negative = the "
                        "ManagedCommConfig default")
    t.add_argument("--comm_adaptive", action="store_true",
                   help="adaptive push cadence: under congestion (token-"
                        "bucket deficit or flushes queuing behind a slow "
                        "link) intermediate clocks ship as ~100-byte "
                        "ticks and the payload rides the next boundary "
                        "flush, recovering as the link drains "
                        "(cadence_backoffs counts escalations)")
    t.add_argument("--async_heartbeat_s", type=float, default=-1.0,
                   help="async-SSP client heartbeat cadence (liveness "
                        "signal when the flush queue is idle); negative = "
                        "FaultConfig default")
    t.add_argument("--async_liveness_timeout_s", type=float, default=-1.0,
                   help="async-SSP service evicts a worker silent this "
                        "long (survivors' gates unblock; 0 disables — the "
                        "reference's hang-forever semantics); negative = "
                        "FaultConfig default")
    t.add_argument("--async_reconnect_deadline_s", type=float, default=-1.0,
                   help="async-SSP client gives up reconnecting (and "
                        "surfaces permanent failure to the training loop) "
                        "after this long; negative = FaultConfig default")
    t.add_argument("--async_gate_timeout_s", type=float, default=-1.0,
                   help="async-SSP read-gate backstop per clock; negative "
                        "= tier default (120 s)")
    t.add_argument("--async_first_gate_timeout_s", type=float, default=-1.0,
                   help="async-SSP FIRST-clock gate backstop (covers "
                        "peers' initial multi-minute JIT compile); "
                        "negative = max(1800 s, 10x gate timeout)")
    t.add_argument("--hostfile", default="",
                   help="cluster hostfile ('<id> <ip> <port>' lines)")
    t.add_argument("--node_id", type=int, default=-1,
                   help="this process's hostfile id")
    t.add_argument("--steps_per_dispatch", type=int, default=1,
                   help="run K optimizer steps per compiled dispatch "
                        "(lax.scan): amortizes per-dispatch runtime "
                        "round-trip; falls back to single steps near "
                        "display/test/snapshot boundaries")
    t.add_argument("--device_prefetch", type=int, default=None,
                   help="device-side input prefetch depth: a background "
                        "stage device_puts the next N host batches with "
                        "the step's batch sharding while the current step "
                        "runs, and the batch buffers become donated step "
                        "inputs (no steady-state batch allocations); 0 "
                        "restores the inline device_put (default: the "
                        "PipelineConfig policy, 2)")
    t.add_argument("--max_in_flight", type=int, default=None,
                   help="bounded in-flight dispatch window: dispatch step "
                        "k+1 before step k's metrics are read, blocking "
                        "only when this many dispatches are un-"
                        "materialized; 1 = the serial loop. Loss display "
                        "and NaN detection lag by at most this many steps "
                        "(default: the PipelineConfig policy, 4)")
    t.add_argument("--async_snapshot", action="store_true", default=None,
                   help="serialize mid-train snapshots on a background "
                        "thread (host copy taken at the sync point; the "
                        "atomic tmp-rename protocol and auto-resume "
                        "semantics are unchanged; default: the "
                        "PipelineConfig policy, off)")
    t.add_argument("--aot_steps", default="true", choices=["true", "false"],
                   help="fast restart: besides the persistent XLA compile "
                        "cache (JAX_COMPILATION_CACHE_DIR, else "
                        "<checkout>/.jax_cache), serialize/reload the "
                        "compiled train-step executable itself under "
                        "<cache>/aot keyed by (model, shapes, mesh, code) — "
                        "a matching restart skips trace AND compile; false "
                        "keeps only the XLA cache")
    t.add_argument("--profile", type=int, default=0,
                   help="capture an xplane trace over N steps (from step 10)")
    t.add_argument("--trace_out", default="",
                   help="host-side span timeline: record dispatch/hard-"
                        "sync/snapshot/prefetch-stall and async-tier "
                        "push/pull/gate/admit spans and write Chrome "
                        "trace-event JSON here (relative to --output_dir), "
                        "refreshed atomically at every display boundary; "
                        "load in chrome://tracing or Perfetto")
    t.add_argument("--metrics_port", type=int, default=-1,
                   help="serve live training counters over HTTP on this "
                        "loopback port (0 = ephemeral, logged at startup): "
                        "curl it mid-run for text key=value — iteration, "
                        "loss, input_stall, membership churn; negative = "
                        "off")
    t.add_argument("--device_transform", action="store_true",
                   help="ship uint8 crops and apply (x - mean_value) * "
                        "scale on device (4x fewer host->device bytes; "
                        "needs the native batcher, mean_value-style mean)")
    t.set_defaults(fn=cmd_train)

    te = sub.add_parser("test", help="score a model")
    te.add_argument("--model", required=True)
    te.add_argument("--weights", default="")
    te.add_argument("--iterations", type=int, default=50)
    te.add_argument("--hostfile", default="")
    te.add_argument("--node_id", type=int, default=-1)
    te.set_defaults(fn=cmd_test)

    ti = sub.add_parser("time", help="benchmark model fwd/bwd")
    ti.add_argument("--model", required=True)
    ti.add_argument("--iterations", type=int, default=50)
    ti.add_argument("--batch_size", type=int, default=64)
    ti.add_argument("--per_layer", action="store_true",
                    help="also print per-layer forward times")
    ti.add_argument("--comm_devices", type=int, default=0,
                    help="with --per_layer: print static per-layer comm "
                         "bytes/savings over this many devices")
    ti.add_argument("--dcn_slices", type=int, default=0,
                    help="with --comm_devices: model a two-tier mesh with "
                         "this many DCN slices")
    ti.add_argument("--strategy", default="dense",
                    choices=["dense", "sfb", "topk"])
    ti.add_argument("--sfb-auto", action="store_true",
                    help="pick SFB per FC layer by cost model")
    ti.add_argument("--wire_dtype", default="",
                    choices=["", "f32", "bf16", "f16"],
                    help="bill the comm table at this wire width")
    ti.add_argument("--topk_block", type=int, default=0)
    ti.set_defaults(fn=cmd_time)

    dq = sub.add_parser("device_query", help="show accelerator info")
    dq.set_defaults(fn=cmd_device_query)

    sv = sub.add_parser(
        "serve", help="serve a trained snapshot over TCP (dynamic "
                      "micro-batching, bucketed AOT compile cache, "
                      "checkpoint hot-reload)")
    sv.add_argument("--model", required=True,
                    help="deploy-style prototxt (explicit input/input_dim)")
    sv.add_argument("--weights", default="",
                    help="a .caffemodel or .solverstate.npz to serve; "
                         "empty serves filler init (smoke mode)")
    sv.add_argument("--watch", default="",
                    help="snapshot prefix to poll for hot-reload (e.g. "
                         "out/snap/lenet), or 'auto' to derive it from "
                         "--weights' _iter_ naming")
    sv.add_argument("--host", default="127.0.0.1",
                    help="bind address; the protocol is pickle-framed and "
                         "UNAUTHENTICATED — loopback/trusted networks only")
    sv.add_argument("--port", type=int, default=0,
                    help="0 = ephemeral (printed at startup)")
    sv.add_argument("--buckets", default="",
                    help="batch bucket ladder; every bucket is AOT-"
                         "compiled at startup (no trace on a request). "
                         "Unset = serving.executor.DEFAULT_BUCKETS "
                         "(1,4,16,64)")
    sv.add_argument("--max_delay_ms", type=float, default=5.0,
                    help="micro-batcher flush deadline: a queued request "
                         "never waits longer than this for batch company")
    sv.add_argument("--max_queue", type=int, default=64,
                    help="admission bound; a full queue sheds explicitly")
    sv.add_argument("--deadline_ms", type=float, default=0.0,
                    help="default per-request deadline (0 = none)")
    sv.add_argument("--poll_s", type=float, default=1.0,
                    help="hot-reload watch cadence")
    sv.add_argument("--replicas", type=int, default=1,
                    help="serving replicas behind the one front door, "
                         "each its own bucketed executor + micro-batcher "
                         "(least-loaded routing, per-replica health, "
                         "rolling hot-reload); 1 = the single-engine path")
    sv.add_argument("--devices", default="",
                    help="comma-separated jax.devices() indices to pin "
                         "replicas to (e.g. '0,1,2'); empty round-robins "
                         "over all local devices when --replicas > 1")
    sv.add_argument("--metrics_port", type=int, default=-1,
                    help="serve live fleet health over HTTP on this port "
                         "(0 = ephemeral, printed at startup; the same "
                         "read-only endpoint as train's --metrics_port)")
    sv.add_argument("--generate", action="store_true",
                    help="LLM decode serving: --model names a transformer "
                         "preset (tiny|gpt_small) served through the "
                         "paged-KV continuous batcher — 'generate' wire "
                         "op with streaming gen_chunk frames; page size, "
                         "decode rungs and prompt buckets are the "
                         "constants of serving/continuous.py")
    sv.set_defaults(fn=cmd_serve)

    bs = sub.add_parser(
        "bench_serve", help="serving latency microbenchmark (in-process "
                            "server + load generator, ONE JSON line)")
    bs.add_argument("--model", default="",
                    help="deploy prototxt; empty uses a built-in synthetic "
                         "conv net")
    bs.add_argument("--weights", default="")
    bs.add_argument("--buckets", default="",
                    help="unset = serving.executor.DEFAULT_BUCKETS "
                         "(1,4,16,64)")
    bs.add_argument("--requests", type=int, default=200)
    bs.add_argument("--concurrency", type=int, default=4)
    bs.add_argument("--batch", type=int, default=8,
                    help="request sizes cycle 1..batch (exercises the "
                         "bucket ladder)")
    bs.add_argument("--max_delay_ms", type=float, default=5.0)
    bs.add_argument("--max_queue", type=int, default=64)
    bs.add_argument("--deadline_ms", type=float, default=0.0)
    bs.add_argument("--replicas", type=int, default=1,
                    help="bench the fleet path with this many replicas")
    bs.add_argument("--devices", default="",
                    help="device indices to pin the replicas to")
    bs.add_argument("--offered_rps", type=float, default=0.0,
                    help="open-loop mode: fixed arrival rate (req/s); "
                         "0 = closed loop")
    bs.set_defaults(fn=cmd_bench_serve)

    ci = sub.add_parser("convert_imageset", help="image list -> LMDB")
    ci.add_argument("listfile")
    ci.add_argument("out_db")
    ci.add_argument("--root_folder", default="")
    ci.add_argument("--resize_height", type=int, default=0)
    ci.add_argument("--resize_width", type=int, default=0)
    ci.add_argument("--shuffle", action="store_true")
    ci.add_argument("--gray", action="store_true")
    ci.set_defaults(fn=cmd_convert_imageset)

    cm = sub.add_parser("compute_image_mean", help="LMDB -> mean binaryproto")
    cm.add_argument("db")
    cm.add_argument("out_file")
    cm.set_defaults(fn=cmd_compute_image_mean)

    pd = sub.add_parser("partition_data", help="split LMDB into k shards")
    pd.add_argument("db")
    pd.add_argument("num_shards", type=int)
    pd.set_defaults(fn=cmd_partition_data)

    cd = sub.add_parser("convert_db", help="copy LevelDB<->LMDB")
    cd.add_argument("src")
    cd.add_argument("out")
    cd.add_argument("--backend", default="LMDB", choices=["LMDB", "LEVELDB"])
    cd.set_defaults(fn=cmd_convert_db)

    ef = sub.add_parser("extract_features",
                        help="dump named blobs to LMDBs")
    ef.add_argument("--model", required=True)
    ef.add_argument("--weights", default="")
    ef.add_argument("--blobs", required=True,
                    help="comma-separated blob names")
    ef.add_argument("--num_batches", type=int, default=10)
    ef.add_argument("--out_prefix", required=True)
    ef.add_argument("--hostfile", default="")
    ef.add_argument("--node_id", type=int, default=-1)
    ef.set_defaults(fn=cmd_extract_features)
    return p


def main(argv: Optional[List[str]] = None) -> int:
    args = build_parser().parse_args(argv)
    # async collective fusion must be staged into LIBTPU_INIT_ARGS before
    # any command initializes the backend (it is the DWBP-overlap mechanism
    # on TPU; a no-op on CPU runs — see config.enable_tpu_async_collectives)
    from .. import config as _config
    if not _config.enable_tpu_async_collectives() and \
            args.command == "train":
        from .metrics import log
        log("WARNING: async collective fusion NOT staged (an explicit "
            "=false in LIBTPU_INIT_ARGS, or the jax backend was already "
            "initialized before cli.main) — gradient all-reduces will not "
            "overlap backward compute on TPU")
    return args.fn(args)


if __name__ == "__main__":
    sys.exit(main())
