"""Sharded train/eval step builders: the compiled analog of Solver::ForwardBackward.

One call to the built ``train_step`` does what the reference spreads across
``Solver::ForwardBackward`` + per-layer DWBP sync threads + PS clock ticks
(solver.cpp:405-531): forward, backward with per-layer gradient collectives
(overlapped by XLA), optimizer update, all inside a single pjit-compiled
SPMD program over the mesh's "data" axis. Parameters and solver state are
replicated (the PS-table analog); batches are sharded on axis 0.

Also provides the SSP variant: with staleness s > 0, each device applies its
own updates locally for up to s steps between global reconciliations —
bounded-staleness semantics (ssp_consistency_controller.cpp) recast as
periodic local-SGD, since a compiled SPMD program has no asynchronous clock.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Callable, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax, shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..core.net import Net
from ..proto.messages import SolverParameter
from ..solvers.updates import SolverState, init_state, make_update_fn
from .strategies import (CommConfig, CommContext, DENSE, DENSE_FUSED, LOCAL,
                         SFB, TOPK, budget_topk_fraction, comm_salt,
                         topk_compress, wire_psum)


def param_mults(net: Net) -> Dict[str, Dict[str, tuple]]:
    return {
        lname: {p.name: (p.lr_mult, p.decay_mult) for p in defs}
        for lname, defs in net.param_defs.items()
    }


def refuse_layer_updates(net: Net, where: str) -> None:
    """A leaf its layer updates from the step's own statistics (MOE_ROUTER's
    selection bias) is one device's, one batch's: every device would balance
    its own loads and the replicas would part. Refused by name until the
    statistics are summed across the mesh (ROADMAP M2)."""
    if net.layer_updates:
        leaves = sorted("/".join(k) for k in net.layer_updates)
        raise ValueError(
            f"layer-updated leaves {leaves} run on one device with "
            f"iter_size 1 only, not under {where}")


class TrainState(NamedTuple):
    """Replicated per-step carry: solver state + managed-comm residuals.

    ``comm_error`` holds the error-feedback accumulators for TOPK-compressed
    layers (the SSPAggr analog: unsent gradient mass is delayed, not lost).
    Note the residual accumulates *per-device* gradient noise identically on
    every replica because it is computed from the post-psum view."""
    solver: SolverState
    comm_error: Dict


def init_comm_error(params, comm: Optional[CommConfig], n_dev: int) -> Dict:
    """Zero error-feedback residuals for every TOPK layer, stacked
    (n_dev, *shape): each device keeps its own residual (local gradients
    differ), sharded over the data axis."""
    comm = comm or CommConfig()
    return {
        lname: {k: jnp.zeros((n_dev,) + v.shape, v.dtype)
                for k, v in lparams.items()}
        for lname, lparams in params.items()
        if comm.strategy_for(lname) == TOPK}


def reconcile_comm_error(params, err: Dict, comm: Optional[CommConfig],
                         n_dev: int) -> Dict:
    """Adapt restored residuals to the current comm config: keep residuals
    for layers that are still TOPK (shape permitting), zero-init layers that
    became TOPK, drop the rest."""
    fresh = init_comm_error(params, comm, n_dev)
    out = {}
    for lname, zeros in fresh.items():
        old = err.get(lname, {})
        out[lname] = {
            k: old[k] if k in old and old[k].shape == z.shape else z
            for k, z in zeros.items()}
    return out


def init_train_state(params, comm: Optional[CommConfig] = None,
                     n_dev: int = 1, solver_type: str = "SGD") -> TrainState:
    return TrainState(solver=init_state(params, solver_type),
                      comm_error=init_comm_error(params, comm, n_dev))


@dataclass
class TrainStep:
    """Compiled training step + sharding info."""
    step: Callable  # (params, state, batch, rng) -> (params, state, metrics)
    mesh: Mesh
    batch_sharding: NamedSharding
    replicated: NamedSharding
    # The underlying jitted callable, for .lower()/.compile() introspection
    # (cost analysis, AOT). ``step`` may be a plain wrapper hiding those.
    lowerable: Optional[Callable] = None
    # Set when this step runs K optimizer steps per dispatch (lax.scan
    # inside the compiled program); batches then carry a leading [K] axis.
    scan_steps: Optional[int] = None
    # Set when this step accumulates gradients over K micro-batches per
    # optimizer step (SolverParameter.iter_size); batches carry a leading
    # [K] micro-batch axis (inside the scan axis, when both are set).
    iter_size: Optional[int] = None
    # Physical layout the step expects 4-D image inputs in ("NCHW" default;
    # "NHWC" when the caller feeds channels-last directly so an NHWC-planned
    # net's hot path carries zero entry transposes — see core/net.py).
    input_layout: str = "NCHW"
    # The arena layout (core/arena.py) of the two steps whose STATE lives
    # in the flat buffer: the sharding-planner step of parallel/spmd.py
    # (it shards the buffer over fsdp and says so in ``update_route``) and
    # the SSP step's boundary delta exchange. None for the data-parallel
    # step of ``build_train_step``, which sums each gradient leaf where
    # backward makes it and packs nothing (PR 59).
    arena: Optional[object] = None
    # Which form the optimizer update takes: "leaf" (the per-leaf rule on
    # the canonical leaves) or "flat_fsdp" (spmd.py's sharded flat buffer).
    update_route: str = "leaf"


def comm_error_groups(comm: Optional[CommConfig], mesh: Mesh) -> int:
    """How many independent TOPK residuals exist: one per device on a flat
    mesh (local gradients differ), one per DCN slice on a two-tier mesh (the
    residual is computed from the intra-slice-summed gradient, identical on
    every device of a slice). On a named SPMD mesh (parallel/spmd.py) tp
    replicas share one residual — their gradients are identical — so the
    count excludes the tp axis."""
    comm = comm or CommConfig()
    if comm.dcn_axis is not None:
        return mesh.shape[comm.dcn_axis]
    return int(np.prod([v for k, v in mesh.shape.items() if k != "tp"]))


def build_train_step(
    net: Net,
    sp: SolverParameter,
    mesh: Mesh,
    comm: Optional[CommConfig] = None,
    donate: bool = True,
    donate_batch: bool = False,
    dump_blobs: Optional[list] = None,
    scan_steps: Optional[int] = None,
    scan_reuse_batch: bool = False,
    input_transform: Optional[Callable] = None,
    iter_size: int = 1,
    input_layout: str = "NCHW",
    plan=None,
    remat_plan=None,
) -> TrainStep:
    """Compiled SPMD train step over ``mesh``.

    ``remat_plan`` (a ``core/remat.RematPlan``, from ``--hbm_budget_gb``
    or ``--remat``) wraps the named layers'
    forward bodies in ``jax.checkpoint`` inside ``Net.apply`` — stored
    activations drop until the step fits the HBM budget, at the cost of
    recomputing those layers' forwards during backward. Composes with
    the mesh planner and donation unchanged: remat changes
    what XLA's buffer assignment keeps live, never the math (remat arms
    are bitwise-equal to stored-activation arms).

    ``plan`` (a ``spmd.ShardingPlan``, from ``--mesh dp2,fsdp2,tp1``)
    routes the build to the sharding-planner step: arena buckets
    reduce-scatter over the fsdp axis, FC layers take the planned
    column/row tp shards, and the step's collective schedule is the
    plan's (parallel/spmd.py; the schedule is pinned by the
    ``collective_schedule`` HLO contract section). The flat data-parallel
    path below is unchanged when no plan is active. scan_steps /
    iter_size / dump_blobs do not compose with a plan yet — the builder
    rejects them loudly.

    ``input_layout="NHWC"`` declares that the caller feeds 4-D image blobs
    channels-last (after any ``input_transform``, which runs first); with
    an NHWC-planned net this removes the per-step entry transpose — the
    data plane ships HWC-native images as-is. Default "NCHW" keeps the
    Caffe feeding contract and costs one in-graph entry transpose per
    image input under an NHWC plan.

    With ``comm.dcn_axis`` set (two-tier mesh, e.g. axes ("dcn", "data")),
    DENSE/SFB collectives ride both axes jointly, while TOPK layers become
    hierarchical: dense psum inside each slice over the fast ICI axis, then
    magnitude top-k compressed exchange *between* slices over the slow DCN
    axis with per-slice error feedback — the SSPAggr analog
    (ssp_aggr_server_thread.cpp: full-rate intra-machine, budgeted
    prioritized bytes inter-machine).

    ``dump_blobs`` (HDF5_OUTPUT-in-TRAIN support, hdf5_output_layer.cpp):
    the step additionally returns those activation blobs, batch-sharded —
    the fourth element of the step's result tuple.

    ``scan_steps=K`` builds the multi-step-per-dispatch variant: the step
    takes batches with a leading [K] axis (stacked microbatches — see
    ``stack_batches``) and runs K full training steps inside one compiled
    program via ``lax.scan``, returning per-step metrics stacked [K]. One
    host->device dispatch then covers K optimizer steps, amortizing host
    and runtime dispatch latency — the TPU-native analog of keeping the
    solver loop hot instead of paying a host round-trip per iteration
    (the reference pays this per-iteration cost in Solver::Step,
    solver.cpp:405-531; on a multi-host runtime the round-trip can
    dominate). Incompatible with ``dump_blobs`` (stacking K
    copies of every activation would defeat the memory plan).

    ``scan_reuse_batch=True`` (benchmarking mode) drops the leading [K]
    batch axis and feeds the SAME batch to every scan iteration: per-step
    compute is shape-identical to training, parameters still evolve through
    the scan carry, but only one batch lives on device — this is what lets
    K grow large enough to amortize a multi-second runtime dispatch
    round-trip without K x 158 MB of stacked images.

    ``input_transform`` runs on the batch INSIDE the compiled step (per
    scan iteration in scan mode) — the device half of the data plane's
    uint8 split (pipeline.device_transform): (x - mean) * scale fuses into
    the first conv, and the host ships quarter-width bytes.

    ``iter_size=K`` (gradient accumulation — SolverParameter.iter_size, the
    V2-prototxt surface; Caffe accumulates K batches' gradients then
    normalizes by K in SGDSolver::Normalize): the step takes batches with a
    leading [K] micro-batch axis and runs the forward/backward K times via
    ``lax.scan`` (grad INSIDE the scan body, so activation memory stays at
    one micro-batch), averages the accumulated gradients, then syncs and
    updates ONCE. batch_size B at iter_size K is numerically equivalent to
    batch_size B*K (tested). There is no per-micro-batch backward exchange
    to tap (the DWBP/SFB structures are per-step mechanisms), so every
    DENSE, SFB and DENSE_FUSED layer gets one dense psum per accumulated
    leaf after the scan; TOPK compression still applies, on the
    accumulated gradient.

    ``donate_batch=True`` additionally donates the batch buffers: with a
    device-side input prefetch stage (``data.pipeline.DevicePrefetcher``)
    feeding fresh device arrays every step, donation lets XLA recycle the
    previous step's batch allocation, so steady-state training allocates
    no new device batch buffers. Callers that reuse a batch across calls
    (bench's ``scan_reuse_batch``) must keep the default False."""
    comm = comm or CommConfig()
    if plan is not None and plan.active:
        if scan_steps or iter_size > 1 or dump_blobs:
            raise ValueError(
                "--mesh (fsdp/tp sharding) does not compose with "
                "scan_steps / iter_size / dump_blobs yet; run those on "
                "the flat data mesh")
        from .spmd import build_spmd_train_step
        return build_spmd_train_step(
            net, sp, mesh, plan, comm, donate=donate,
            donate_batch=donate_batch, input_transform=input_transform,
            input_layout=input_layout, remat_plan=remat_plan)
    comm.wire_jnp_dtype()  # fail loudly on a bad wire_dtype string
    # layers whose forward bodies Net.apply wraps in jax.checkpoint
    _remat = (remat_plan.apply_args
              if remat_plan is not None and remat_plan.layers else {})
    axis = comm.axis
    dcn = comm.dcn_axis
    axes = comm.sync_axes  # (dcn, data) or (data,)
    update_fn = make_update_fn(sp, param_mults(net))
    n_total = int(np.prod([mesh.shape[a] for a in axes]))
    if n_total > 1 or iter_size > 1:
        refuse_layer_updates(net, f"{n_total} devices, iter_size "
                                  f"{iter_size}")

    for lname in net.param_defs:
        if comm.strategy_for(lname) == LOCAL:
            raise ValueError(
                f"layer {lname!r}: LOCAL (unsynced) params would diverge "
                f"across replicas while build_train_step declares them "
                f"replicated; use build_ssp_train_step for per-device "
                f"divergent parameters")

    # The data-parallel sum of DENSE gradients: each leaf is summed by the
    # tap in its own layer's backward (CommContext.tap_param), in whatever
    # layout the compiler keeps the leaf — no pack into flat buckets, no
    # gate chain, no unpack (PR 59: on four v5e chips those copies and
    # gates cost AlexNet 6.4 ms of a 40 ms step; merging small collectives
    # is the compiler's all-reduce combiner's job). An explicit
    # dwbp_bucket_mb keeps its chained taps; SFB/TOPK/DENSE_FUSED keep
    # their paths. ``param_arena`` decides nothing here: it belongs to the
    # two steps whose state lives in the flat buffer (parallel/spmd.py's
    # fsdp step, build_ssp_train_step's boundary exchange).
    ctx = CommContext(comm)

    if iter_size > 1:
        # no per-backward exchange exists under accumulation: every synced
        # leaf collapses to one dense post-accumulation psum — keep saying
        # so where a per-backward strategy was asked for
        sfb_layers = [l for l in net.param_defs
                      if comm.strategy_for(l) == SFB]
        what = []
        if sfb_layers:
            what.append(f"SFB layers {sfb_layers}")
        if comm.dwbp_bucket_mb is not None:
            what.append(f"dwbp_bucket_mb={comm.dwbp_bucket_mb}")
        if what:
            from ..runtime.metrics import log
            log(f"WARNING: iter_size={iter_size} accumulates gradients "
                f"before one dense post-accumulation psum per leaf for "
                f"{', '.join(what)}; per-backward comm strategies do not "
                f"apply to the accumulated step")

    topk_layers = [l for l in net.param_defs
                   if comm.strategy_for(l) == TOPK]
    fused_layers = [l for l in net.param_defs
                    if comm.strategy_for(l) == DENSE_FUSED]
    topk_fraction = budget_topk_fraction(net, comm)
    batch_spec = P(axes) if dcn else P(axis)
    # iter_size adds an unsharded leading [K] micro-batch axis
    step_batch_spec = (P(None, *batch_spec) if iter_size > 1
                       else batch_spec)
    err_spec = P(dcn) if dcn else P(axis)
    for b in (dump_blobs or ()):
        if len(net.blob_shapes.get(b, ())) < 1:
            raise ValueError(
                f"HDF5_OUTPUT bottom {b!r} is a scalar: per-sample dumping "
                f"needs a batch dimension (hdf5_output_layer.cpp requires "
                f"num()-shaped bottoms)")

    if iter_size > 1 and dump_blobs:
        raise ValueError("iter_size > 1 is incompatible with dump_blobs "
                         "(per-iteration HDF5 dump semantics)")

    def device_step(params, state: TrainState, batch, rng):
        flat_idx = lax.axis_index(axis)
        if dcn:
            flat_idx = flat_idx + mesh.shape[axis] * lax.axis_index(dcn)
        rng = jax.random.fold_in(rng, flat_idx)

        def loss_of(p, mb, lrng, lcomm):
            o = net.apply(p, mb, train=True, rng=lrng, comm=lcomm,
                          keep_blobs=bool(dump_blobs),
                          input_layout=input_layout, **_remat)
            return o.loss, o

        if iter_size > 1:
            # gradient accumulation: grad INSIDE the scan body so only one
            # micro-batch's activations are ever live; metrics stack [K]
            def accum_body(acc, xs):
                i, mb = xs
                if input_transform is not None:
                    mb = input_transform(mb)
                mrng = jax.random.fold_in(rng, i)
                g, o = jax.grad(lambda p: loss_of(p, mb, mrng, None),
                                has_aux=True)(params)
                acc = jax.tree_util.tree_map(jnp.add, acc, g)
                m = {"loss": o.loss}
                for name, val in o.outputs.items():
                    if val.ndim == 0:
                        m[name] = val.astype(jnp.float32)
                return acc, m

            grads, micro_ms = lax.scan(
                accum_body, jax.tree_util.tree_map(jnp.zeros_like, params),
                (jnp.arange(iter_size), batch))
            # Caffe's SGDSolver::Normalize: scale accumulated grads by 1/K
            grads = jax.tree_util.tree_map(lambda g: g / iter_size, grads)
            out_scalars = {k: jnp.mean(v) for k, v in micro_ms.items()}
            # post-accumulation sync: one sum a leaf for every layer the
            # per-backward taps would have handled (DENSE / SFB /
            # DENSE_FUSED)
            for lname in grads:
                if comm.strategy_for(lname) not in (LOCAL, TOPK):
                    for pname, g in grads[lname].items():
                        grads[lname][pname] = wire_psum(
                            g, axes, comm.reduce, comm.wire_dtype)
            out = None
        else:
            if input_transform is not None:
                batch = input_transform(batch)
            grads, out = jax.grad(lambda p: loss_of(p, batch, rng, ctx),
                                  has_aux=True)(params)
            out_scalars = {"loss": out.loss}
            for name, val in out.outputs.items():
                if val.ndim == 0:
                    out_scalars[name] = val.astype(jnp.float32)
            # DENSE_FUSED: one bulk psum after the whole backward — the
            # no-overlap baseline for the DWBP A/B.
            for lname in fused_layers:
                for pname, g in grads[lname].items():
                    grads[lname][pname] = wire_psum(g, axes, comm.reduce,
                                                    comm.wire_dtype)
        # Managed-comm tier: TOPK layers were left un-psummed by the tap;
        # compress the (residual-corrected) gradient, exchange only the
        # top-k entries, keep the remainder as next step's residual.
        new_errors = dict(state.comm_error)
        for lname in topk_layers:
            lerr = {}
            for pname, g in grads[lname].items():
                err = state.comm_error[lname][pname][0]  # unstack group dim
                if dcn:
                    # fast tier: dense sum inside the slice (cheap ICI, at
                    # wire width — pre-psum rounding here is the same
                    # unrecoverable trade as the dense tier's);
                    # slow tier: compressed exchange between slices
                    g = wire_psum(g, (axis,), "sum", comm.wire_dtype)
                sent, resid = topk_compress(g, topk_fraction, err,
                                            comm.topk_policy, state.solver.it,
                                            salt=comm_salt(lname, pname),
                                            block=comm.topk_block,
                                            wire=comm.wire_dtype)
                # sent is already wire-quantized, so the wire-dtype psum
                # operand cast is exact
                g_sync = wire_psum(sent, (dcn,) if dcn else (axis,), "sum",
                                   comm.wire_dtype)
                if comm.reduce == "mean":
                    g_sync = g_sync / n_total
                grads[lname][pname] = g_sync
                lerr[pname] = resid[None]
            new_errors[lname] = lerr
        new_params, new_solver = update_fn(
            params, grads, state.solver,
            out.updates if net.layer_updates else None)
        metrics = {name: lax.psum(val.astype(jnp.float32), axes) / n_total
                   for name, val in out_scalars.items()}
        dumps = ({b: out.blobs[b] for b in (dump_blobs or ())}
                 if out is not None else {})
        return new_params, TrainState(new_solver, new_errors), metrics, dumps

    if scan_steps:
        if dump_blobs:
            raise ValueError(
                "scan_steps is incompatible with dump_blobs: stacking "
                f"{scan_steps} copies of every dumped activation would "
                "defeat the memory plan")

        def device_multi_step(params, state, batches, rng):
            # fold by GLOBAL iteration (solver.it at dispatch + offset), so
            # the per-step rng stream is identical to single-step dispatches
            # (callers fold by iteration there) for ANY K and any chunk
            # boundary — dropout masks must not depend on dispatch grouping
            it0 = state.solver.it
            def body(carry, xs):
                p, s = carry
                if scan_reuse_batch:
                    i, batch = xs, batches
                else:
                    i, batch = xs
                p, s, m, _ = device_step(p, s, batch,
                                         jax.random.fold_in(rng, it0 + i))
                return (p, s), m
            xs = (jnp.arange(scan_steps) if scan_reuse_batch
                  else (jnp.arange(scan_steps), batches))
            (params, state), ms = lax.scan(body, (params, state), xs)
            return params, state, ms

        # leading [K] axis is unsharded; the per-step batch axis keeps the
        # single-step sharding. scan_reuse_batch feeds the SAME batch to
        # every scan iteration (per-step compute is shape-identical, params
        # still evolve through the carry) — the benchmarking mode that keeps
        # K large without K on-device batch copies.
        scan_batch_spec = (P(*step_batch_spec) if scan_reuse_batch
                           else P(None, *step_batch_spec))
        sharded = shard_map(
            device_multi_step,
            mesh=mesh,
            in_specs=(P(), TrainState(P(), err_spec), scan_batch_spec, P()),
            out_specs=(P(), TrainState(P(), err_spec), P()),
            check_vma=False,
        )
        argnums = (0, 1) if donate else ()
        if donate_batch:
            argnums = argnums + (2,)
        jitted = jax.jit(sharded, donate_argnums=argnums)
        return TrainStep(
            step=jitted,
            mesh=mesh,
            batch_sharding=NamedSharding(mesh, scan_batch_spec),
            replicated=NamedSharding(mesh, P()),
            lowerable=jitted,
            scan_steps=scan_steps,
            iter_size=iter_size if iter_size > 1 else None,
            input_layout=input_layout,
        )

    sharded = shard_map(
        device_step,
        mesh=mesh,
        in_specs=(P(), TrainState(P(), err_spec), step_batch_spec, P()),
        out_specs=(P(), TrainState(P(), err_spec), P(), batch_spec),
        check_vma=False,
    )
    argnums = (0, 1) if donate else ()
    if donate_batch:
        argnums = argnums + (2,)
    jitted = jax.jit(sharded, donate_argnums=argnums)
    if dump_blobs:
        step = jitted
    else:
        # callers without dumps keep the 3-tuple contract
        step = lambda p, s, b, r: jitted(p, s, b, r)[:3]  # noqa: E731
    return TrainStep(
        step=step,
        mesh=mesh,
        batch_sharding=NamedSharding(mesh, step_batch_spec),
        replicated=NamedSharding(mesh, P()),
        lowerable=jitted,
        iter_size=iter_size if iter_size > 1 else None,
        input_layout=input_layout,
    )


def stack_batches(host_batches, sharding=None, lead_shape=None):
    """Stack K host batches (dicts of arrays) into one [K, ...] pytree and
    place it in ONE host->device transfer — the feeding side of
    ``scan_steps``. K transfers of one batch each would re-pay transfer
    latency K times; one stacked transfer pays it once. ``lead_shape``
    reshapes the leading axis (e.g. (chunk, iter_size) when scan chunking
    and gradient accumulation compose); under multi-process the per-host
    stack is assembled into the global array via its sharding."""
    out = {}
    multihost = jax.process_count() > 1
    for k in host_batches[0]:
        stacked = np.stack([np.asarray(b[k]) for b in host_batches])
        if lead_shape is not None:
            stacked = stacked.reshape(tuple(lead_shape) + stacked.shape[1:])
        if sharding is None:
            out[k] = jnp.asarray(stacked)
        elif multihost:
            out[k] = jax.make_array_from_process_local_data(sharding, stacked)
        else:
            out[k] = jax.device_put(stacked, sharding)
    return out


def build_eval_step(net: Net, mesh: Mesh, axis: str = "data",
                    dcn_axis: Optional[str] = None, plan=None) -> Callable:
    """Test-phase forward returning cross-replica-averaged scalar outputs.

    With a ``plan`` (named SPMD mesh) the batch shards jointly over
    (data, fsdp) and tp replicas evaluate redundantly on replicated
    canonical params — eval never needs the sharded step."""
    if plan is not None and plan.active:
        axes = ("data", "fsdp")
        n_dev = plan.n_dp
        batch_spec = P(axes)

        def device_eval(params, batch):
            out = net.apply(params, batch, train=False)
            metrics = {}
            if out.loss.ndim == 0:
                metrics["loss"] = lax.psum(out.loss, axes) / n_dev
            for name, val in out.outputs.items():
                if val.ndim == 0:
                    metrics[name] = lax.psum(val.astype(jnp.float32),
                                             axes) / n_dev
            return metrics

        return jax.jit(shard_map(
            device_eval, mesh=mesh,
            in_specs=(P(), batch_spec), out_specs=P(), check_vma=False))
    axes = (dcn_axis, axis) if dcn_axis else (axis,)
    n_dev = int(np.prod([mesh.shape[a] for a in axes]))
    batch_spec = P(axes) if dcn_axis else P(axis)

    def device_eval(params, batch):
        out = net.apply(params, batch, train=False)
        metrics = {}
        if out.loss.ndim == 0:
            metrics["loss"] = lax.psum(out.loss, axes) / n_dev
        for name, val in out.outputs.items():
            if val.ndim == 0:
                metrics[name] = lax.psum(val.astype(jnp.float32), axes) / n_dev
        return metrics

    return jax.jit(shard_map(
        device_eval, mesh=mesh,
        in_specs=(P(), batch_spec), out_specs=P(), check_vma=False))


# --------------------------------------------------------------------------- #
# SSP (staleness > 0): bounded-staleness as periodic reconciliation
# --------------------------------------------------------------------------- #

class SSPState(NamedTuple):
    """Per-device divergent params (stacked on a leading device dim, sharded
    over the data axis) + the replicated anchor they diverged from.

    ``comm_error`` carries the error-feedback residual for TOPK layers whose
    *delta* exchange is compressed at sync boundaries (the SSPAggr
    composition: bounded staleness + bandwidth-managed communication,
    ssp_aggr_bg_worker.cpp). Same stacked-per-device layout as the params.

    ``adarev_server`` / ``adarev_gsum`` exist only under
    ``CommConfig.server_logic == "adarevision"``: the replicated server-side
    accumulators {layer: {param: {"z", "zmax"}}} (AdaRevisionRow, init 1)
    and each group's raw-gradient sum since its last sync (stacked per
    group, sharded — the client's un-sent oplog)."""
    local_params: Dict   # leaves: (n_dev, *shape), sharded on axis 0
    local_history: Dict  # momentum/adagrad history, same layout
    anchor_params: Dict  # leaves: (*shape,), replicated
    it: jax.Array
    comm_error: Dict     # TOPK residuals: (n_dev, *shape), sharded on axis 0
    adarev_server: Dict      # z/zmax accumulators, replicated ({} unless on)
    adarev_gsum: Dict        # (n_groups, *shape) raw grad sums, sharded


def build_ssp_train_step(
    net: Net,
    sp: SolverParameter,
    mesh: Mesh,
    staleness: int,
    comm: Optional[CommConfig] = None,
    input_transform: Optional[Callable] = None,
    donate_batch: bool = False,
    plan=None,
):
    """Staleness-s data parallelism (SSP, ssp_consistency_controller.cpp:37-161).

    Every device advances on purely local gradients; every (staleness+1) steps
    the accumulated deltas are summed across the mesh and folded into a common
    anchor — each replica's view is then at most s steps behind the aggregate,
    the SSP bound. This trades the reference's asynchronous clock machinery
    for a compiled, deterministic schedule with identical staleness semantics.

    Per-layer strategies compose at the sync boundary:
      DENSE — dense psum of the accumulated delta (default);
      TOPK  — magnitude top-k compression of the delta with error feedback
              (the SSPAggr pairing of staleness + bandwidth budget);
      LOCAL — never synchronized (the reference's LOCAL blob mode; replicas
              keep divergent copies, legal here unlike in the sync step).

    **Two-tier composition** (``comm.dcn_axis`` set): staleness moves to the
    slow (DCN) tier — each *slice* diverges for up to s steps and slices
    reconcile deltas every s+1 — while inside a slice the fast ICI tier syncs
    densely every step with in-backward taps. This is exactly the reference
    SSPAggr deployment (ssp_aggr_bg_worker.cpp:379-474: full-rate updates
    inside a machine, bounded-staleness bandwidth-managed bytes across).
    Intra-slice, DENSE/SFB ride the per-step backward-time exchange (so SFB
    *is* legal here, unlike flat SSP); TOPK/LOCAL/DENSE_FUSED gradients are
    dense-psummed intra-slice after backward. At the DCN sync boundary,
    non-LOCAL deltas are exchanged (TOPK-compressed where configured).

    On a flat mesh, SFB is rejected: it is a *backward-time* per-step factor
    exchange — under flat SSP there is no per-step exchange to ride on (the
    reference's SVB likewise drains sufficient vectors every iteration, i.e.
    it runs each FC layer at effective staleness 0).
    """
    import dataclasses
    refuse_layer_updates(net, "--staleness")
    if sp.solver_type == "ADAM" or sp.clip_gradients > 0:
        raise ValueError("solver_type ADAM and clip_gradients are not "
                         "supported under SSP staleness (per-device "
                         "histories carry one buffer, no global norm)")
    comm = comm or CommConfig()
    comm.wire_jnp_dtype()  # fail loudly on a bad wire_dtype string
    axis = comm.axis
    dcn = comm.dcn_axis
    # Named SPMD mesh (parallel/spmd.py): every (data, fsdp) device keeps
    # a divergent local copy (flat-mesh SSP semantics over both dp axes);
    # the boundary's arena delta exchange is resharded over fsdp —
    # reduce-scatter, psum the shard over data, all-gather back — so the
    # slow-tier bytes split by the fsdp size. tp does not compose with
    # SSP local steps (a tp-sharded layer needs its per-step psum).
    plan_fsdp = 1
    if plan is not None and plan.active:
        if plan.mesh_cfg.tp > 1:
            raise ValueError(
                "SSP staleness does not compose with tensor parallelism: "
                "tp layers exchange activations every step, which a "
                "local-step tier has no slot for; use --mesh dpN,fsdpN")
        if dcn is not None:
            raise ValueError("--mesh and --dcn_slices do not compose")
        if comm.server_logic == "adarevision":
            raise ValueError(
                "server_logic='adarevision' consumes per-leaf raw "
                "gradient sums and does not compose with the fsdp-"
                "sharded delta exchange")
        plan_fsdp = plan.mesh_cfg.fsdp
    update_fn = make_update_fn(sp, param_mults(net))
    period = staleness + 1
    # the tier that carries staleness: slices on a two-tier mesh, devices
    # on a flat one, every (data, fsdp) device on a named SPMD mesh
    if plan_fsdp > 1:
        group_axes: tuple = ("data", "fsdp")
        n_groups = plan.n_dp
        n_ici = 1
    else:
        group_axis = dcn if dcn else axis
        group_axes = (group_axis,)
        n_groups = mesh.shape[group_axis]
        n_ici = mesh.shape[axis] if dcn else 1
    n_total = n_groups * max(1, n_ici)

    for lname in net.param_defs:
        if comm.strategy_for(lname) == SFB and not dcn:
            raise ValueError(
                f"layer {lname!r}: SFB is a per-step backward-time exchange "
                f"and cannot compose with flat-mesh SSP local steps; use "
                f"DENSE or TOPK (delta compression), or a two-tier mesh "
                f"(comm.dcn_axis) where SFB rides the intra-slice tier")

    topk_layers = [l for l in net.param_defs
                   if comm.strategy_for(l) == TOPK]
    local_layers = {l for l in net.param_defs
                    if comm.strategy_for(l) == LOCAL}
    adarev = comm.server_logic == "adarevision"
    if comm.server_logic not in ("inc", "adarevision"):
        raise ValueError(f"unknown server_logic {comm.server_logic!r}")
    if adarev and topk_layers:
        raise ValueError(
            "server_logic='adarevision' does not compose with TOPK delta "
            "compression: the server logic consumes each group's RAW "
            "accumulated gradient (adarevision_server_table_logic.cpp "
            "applies -eta*u + (eta_old-eta)*g_bck per update), while TOPK "
            "rewrites the delta; pick one")
    topk_fraction = budget_topk_fraction(net, comm)
    # under dcn: strategies whose gradients the in-backward taps leave raw
    # and therefore need the explicit intra-slice psum after backward
    raw_ici_layers = [l for l in net.param_defs
                      if comm.strategy_for(l) in (TOPK, LOCAL, DENSE_FUSED)]
    ici_ctx = (CommContext(dataclasses.replace(comm, dcn_axis=None))
               if dcn else None)

    # Arena buckets for the SSP tier (flat mesh, "inc" server logic): the
    # boundary delta exchange is ceil(bytes/arena_bucket_mb) psums over
    # arena buckets instead of one per leaf. The local update is the
    # per-leaf rule, as in the synchronous step. TOPK (compressed deltas)
    # and LOCAL layers keep their per-leaf paths; adarevision consumes
    # per-leaf raw gradient sums and a two-tier mesh taps DENSE gradients
    # per-step intra-slice, so both exchange per leaf wholesale.
    dense_layers = [l for l in net.param_defs
                    if comm.strategy_for(l) == DENSE]
    arena = None
    if comm.param_arena and dense_layers and not adarev and not dcn:
        # fsdp-aligned buckets so the boundary reduce-scatter shards evenly
        arena = net.arena_layout(frozenset(dense_layers),
                                 comm.arena_bucket_mb,
                                 align=plan_fsdp)

    def device_step(ssp: SSPState, batch, rng):
        if plan_fsdp > 1:
            flat_idx = lax.axis_index("data") * plan_fsdp + \
                lax.axis_index("fsdp")
        else:
            flat_idx = lax.axis_index(axis)
            if dcn:
                flat_idx = flat_idx + \
                    mesh.shape[axis] * lax.axis_index(dcn)
        rng = jax.random.fold_in(rng, flat_idx)
        if input_transform is not None:
            batch = input_transform(batch)
        squeeze = lambda tree: jax.tree_util.tree_map(lambda x: x[0], tree)
        local = squeeze(ssp.local_params)
        history = squeeze(ssp.local_history)
        error = squeeze(ssp.comm_error)
        gsum = squeeze(ssp.adarev_gsum)

        def loss_fn(p):
            out = net.apply(p, batch, train=True, rng=rng, comm=ici_ctx)
            return out.loss, out

        grads, out = jax.grad(loss_fn, has_aux=True)(local)
        if dcn:
            # intra-slice dense tier for strategies the taps left raw
            for lname in raw_ici_layers:
                for pname, g in grads[lname].items():
                    grads[lname][pname] = wire_psum(
                        g, (axis,), comm.reduce, comm.wire_dtype)
        if adarev:
            # the client-side oplog: raw gradient mass accumulated since
            # this group's last sync (what Bösen clients send to the server)
            gsum = {ln: {pn: gsum[ln][pn] + grads[ln][pn]
                         for pn in grads[ln]}
                    for ln in gsum}
        new_local, new_solver = update_fn(
            local, grads, SolverState(it=ssp.it, history=history))

        do_sync = (new_solver.it % period) == 0
        scale = 1.0 / n_groups if comm.reduce == "mean" else 1.0
        eta0 = comm.adarev_init_step

        def adarev_apply(av, u_local, z, zmax):
            """The server's ApplyRowOpLog over this boundary's G arriving
            updates, applied in group order (adarevision_server_table_
            logic.cpp:52-175). g_bck — the gradient mass applied since the
            sender's snapshot — is 0 at boundary start (snapshots are taken
            at the previous boundary, when every group was sent the same
            version) and grows by each applied update within the boundary."""
            U = lax.all_gather(u_local, group_axis)  # (G, *shape)

            def body(carry, u):
                p, z_, zmax_, g_bck = carry
                eta_old = eta0 / jnp.sqrt(zmax_)
                z_ = z_ + u * (u + 2.0 * g_bck)
                zmax_ = jnp.maximum(zmax_, z_)
                eta = eta0 / jnp.sqrt(zmax_)
                p = p - eta * u + (eta_old - eta) * g_bck
                g_bck = g_bck + u
                return (p, z_, zmax_, g_bck), None

            (p_new, z_new, zmax_new, _), _ = lax.scan(
                body, (av, z, zmax, jnp.zeros_like(av)), U)
            return p_new, z_new, zmax_new

        def sync(args):
            l, anchor, err, server, gs = args
            merged, new_anchor, new_err = {}, {}, dict(err)
            new_server, new_gs = dict(server), dict(gs)
            if arena is not None:
                # bucketed DENSE delta exchange over the arena: the flat
                # delta's exact bucket ranges, one psum each — elementwise
                # identical to the per-leaf psums they replace. On an
                # fsdp mesh each bucket reduce-scatters over fsdp, psums
                # the shard over data, and all-gathers back: same sum,
                # slow-tier payload split by the fsdp size.
                flat_a = arena.pack(anchor)
                flat_delta = arena.pack(l) - flat_a
                summed = []
                for bi, b in enumerate(arena.split_buckets(flat_delta)):
                    if plan_fsdp > 1:
                        b, casted = ((b.astype(comm.wire_jnp_dtype()), True)
                                     if comm.wire_dtype else (b, False))
                        with jax.named_scope(f"delta_rs_bucket{bi}"):
                            b = lax.psum_scatter(b, "fsdp", tiled=True)
                        if mesh.shape["data"] > 1:
                            with jax.named_scope(f"delta_ar_bucket{bi}"):
                                b = lax.psum(b, "data")
                        with jax.named_scope(f"delta_ag_bucket{bi}"):
                            b = lax.all_gather(b, "fsdp", tiled=True)
                        if casted:
                            b = b.astype(jnp.float32)
                        summed.append(b)
                    else:
                        summed.append(wire_psum(b, group_axes, "sum",
                                                comm.wire_dtype))
                arena_merged = arena.unpack(
                    flat_a + scale * arena.join_buckets(summed))
            for lname, lp in l.items():
                if lname in local_layers:
                    # LOCAL blobs never cross the wire (blob.cpp LOCAL mode)
                    merged[lname] = lp
                    new_anchor[lname] = anchor[lname]
                    continue
                merged[lname], new_anchor[lname] = {}, {}
                is_topk = lname in topk_layers
                lerr = {}
                if adarev:
                    ls, lg = {}, {}
                    for pname, lv in lp.items():
                        m, z, zm = adarev_apply(
                            anchor[lname][pname], gs[lname][pname],
                            server[lname][pname]["z"],
                            server[lname][pname]["zmax"])
                        merged[lname][pname] = m
                        new_anchor[lname][pname] = m
                        ls[pname] = {"z": z, "zmax": zm}
                        lg[pname] = jnp.zeros_like(lv)  # oplog drained
                    new_server[lname], new_gs[lname] = ls, lg
                    continue
                for pname, lv in lp.items():
                    if arena is not None and arena.has(lname, pname):
                        m = arena_merged[lname][pname]
                        merged[lname][pname] = m
                        new_anchor[lname][pname] = m
                        continue
                    av = anchor[lname][pname]
                    delta = lv - av
                    if is_topk:
                        # rotation advances once per SYNC, not per local
                        # step — with ssp.it a gcd(period, n_slabs) > 1
                        # would skip slabs forever
                        sent, resid = topk_compress(
                            delta, topk_fraction, err[lname][pname],
                            comm.topk_policy, new_solver.it // period,
                            salt=comm_salt(lname, pname),
                            block=comm.topk_block, wire=comm.wire_dtype)
                        lerr[pname] = resid
                        delta = sent
                    m = av + scale * wire_psum(delta, group_axes, "sum",
                                               comm.wire_dtype)
                    merged[lname][pname] = m
                    new_anchor[lname][pname] = m
                if is_topk:
                    new_err[lname] = lerr
            return merged, new_anchor, new_err, new_server, new_gs

        new_local, new_anchor, new_error, new_server, gsum = lax.cond(
            do_sync, sync, lambda args: args,
            (new_local, ssp.anchor_params, error, ssp.adarev_server, gsum))
        axes_all = (("data", "fsdp") if plan_fsdp > 1
                    else (dcn, axis) if dcn else (axis,))
        metrics = {"loss": lax.psum(out.loss, axes_all) / n_total}
        for name, val in out.outputs.items():
            if val.ndim == 0:
                metrics[name] = lax.psum(val.astype(jnp.float32),
                                         axes_all) / n_total
        unsq = lambda tree: jax.tree_util.tree_map(lambda x: x[None], tree)
        return SSPState(unsq(new_local), unsq(new_solver.history),
                        new_anchor, new_solver.it, unsq(new_error),
                        new_server, unsq(gsum)), metrics

    if plan_fsdp > 1:
        g: object = ("data", "fsdp")
        batch_spec = P(("data", "fsdp"))
    else:
        g = group_axes[0]
        batch_spec = P((dcn, axis)) if dcn else P(axis)
    ssp_spec = SSPState(P(g), P(g), P(), P(), P(g), P(), P(g))
    sharded = shard_map(
        device_step, mesh=mesh,
        in_specs=(ssp_spec, batch_spec, P()),
        out_specs=(ssp_spec, P()),
        check_vma=False)
    jitted = jax.jit(sharded,
                     donate_argnums=(0, 1) if donate_batch else (0,))
    return TrainStep(
        step=jitted,
        mesh=mesh,
        batch_sharding=NamedSharding(mesh, batch_spec),
        replicated=NamedSharding(mesh, P()),
        lowerable=jitted,
        arena=arena,
    )


def init_adarev_state(params, comm: Optional[CommConfig],
                      n_groups: int) -> Tuple[Dict, Dict]:
    """(adarev_server, adarev_gsum) for server_logic='adarevision':
    z/zmax start at 1 (AdaRevisionRow ctor), gradient sums at 0."""
    comm = comm or CommConfig()
    if comm.server_logic != "adarevision":
        return {}, {}
    server = {
        lname: {pn: {"z": jnp.ones_like(v), "zmax": jnp.ones_like(v)}
                for pn, v in lparams.items()}
        for lname, lparams in params.items()
        if comm.strategy_for(lname) != LOCAL}
    gsum = {
        lname: {pn: jnp.zeros((n_groups,) + v.shape, v.dtype)
                for pn, v in lparams.items()}
        for lname, lparams in params.items()
        if comm.strategy_for(lname) != LOCAL}
    return server, gsum


def init_ssp_state(params, n_dev: int,
                   comm: Optional[CommConfig] = None) -> SSPState:
    stack = lambda tree: jax.tree_util.tree_map(
        lambda x: jnp.broadcast_to(x[None], (n_dev,) + x.shape), tree)
    zeros = jax.tree_util.tree_map(jnp.zeros_like, params)
    server, gsum = init_adarev_state(params, comm, n_dev)
    return SSPState(local_params=stack(params), local_history=stack(zeros),
                    anchor_params=params, it=jnp.zeros((), jnp.int32),
                    comm_error=init_comm_error(params, comm, n_dev),
                    adarev_server=server, adarev_gsum=gsum)
