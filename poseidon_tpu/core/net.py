"""Net: prototxt-defined DAG -> pure traced forward function.

The TPU-native counterpart of the reference's ``Net<Dtype>``
(``src/caffe/net.cpp``): builds the layer graph from a ``NetParameter`` with
phase filtering (``Net::FilterNet``, net.cpp:366), infers every blob shape,
collects parameter definitions, and exposes

    init(rng)                      -> params pytree
    apply(params, inputs, ...)     -> NetOutputs(loss, outputs, blobs)

``apply`` is pure and jit-able; backward is ``jax.grad(apply)`` — there is no
separate backward graph, no InsertSplits (multi-consumer blobs are natural in
a functional graph), and no PS-table plumbing (parameter placement is a
sharding annotation, handled in ``poseidon_tpu.parallel``).

**Layout plan** (round 6): when the policy (or the per-net override) selects
channels-last, the WHOLE graph is planned in NHWC at construction time —
every conv/pool/LRN runs natively channels-last, elementwise/concat/softmax
layers ride along (axis-remapped), and the plan converts back to canonical
NCHW only at genuine boundaries: the FC flatten, im2col columns, blob
export (``keep_blobs``/HDF5 dumps), and 4-D net outputs. Logical shapes
(``blob_shapes``), parameters, gradients and checkpoints stay canonical
NCHW/OIHW everywhere, so snapshots are layout-portable and the SFB /
DWBP taps always see one gradient layout. This replaces the round-3/5
per-op transpose shims whose boundary pairs did NOT cancel across
pool/LRN/concat seams (the 0.53x NHWC A/B).

The plan also fuses conv epilogues: an in-place ReLU that immediately
consumes a conv's top folds into the conv's epilogue (``ops/nn.conv2d``'s
``act``), so XLA emits one fused kernel per conv layer. The fold is exact —
``relu(conv + b)`` computed by the same formula — and phase-independent.
"""

from __future__ import annotations

import math
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..config import policy
from ..ops import nn as NN
from ..proto.messages import NetParameter, NetState, LayerParameter
from .blob import ParamDef
from .fillers import fill
from .remat import checkpoint_unit
from .layers import (ApplyCtx, DATA_SOURCE_TYPES, LAYOUT_AGNOSTIC,
                     LAYOUT_SPATIAL, Layer, create_layer)

Shape = Tuple[int, ...]


def filter_net(net_param: NetParameter, state: NetState) -> List[LayerParameter]:
    """Phase/level/stage filtering with the reference's include/exclude rules."""
    out = []
    for lp in net_param.layers:
        if lp.include and lp.exclude:
            raise ValueError(f"layer {lp.name!r}: specify include or exclude, not both")
        if lp.include:
            keep = any(r.matches(state) for r in lp.include)
        elif lp.exclude:
            keep = not any(r.matches(state) for r in lp.exclude)
        else:
            keep = True
        if keep:
            out.append(lp)
    return out


@jax.tree_util.register_dataclass
@dataclass
class NetOutputs:
    loss: jax.Array
    outputs: Dict[str, jax.Array]
    blobs: Dict[str, jax.Array] = field(default_factory=dict)
    # {layer: {param: next value}} of the layer-updated leaves
    # (``Net.layer_updates``): what the step stores in place of an
    # optimizer's result
    updates: Dict[str, Dict[str, jax.Array]] = field(default_factory=dict)


class Net:
    def __init__(
        self,
        net_param: NetParameter,
        phase: str = "TRAIN",
        source_shapes: Optional[Dict[str, Shape]] = None,
        level: int = 0,
        stages: Sequence[str] = (),
        conv_layout: Optional[str] = None,
        fuse_conv_epilogues: bool = True,
        conv_strategy: Optional[str] = None,
    ):
        self.net_param = net_param
        self.phase = phase
        self.state = NetState(phase=phase, level=level, stage=list(stages))
        self.name = net_param.name
        # The layout is a GRAPH-level choice, fixed at construction: the
        # per-net override wins, else the ambient numeric policy's default.
        # "auto" resolves through the per-backend table of
        # numeric.resolve_conv_layout (NHWC on the TPU and the GPU, NCHW on
        # the CPU). (Ops take explicit layout args; they no longer read
        # the policy.)
        from ..numeric import resolve_conv_layout
        asked = (conv_layout or policy().conv_layout or "NCHW").upper()
        backend = jax.default_backend() if asked == "AUTO" else None
        self.conv_layout = resolve_conv_layout(asked, backend)
        if self.conv_layout not in NN.LAYOUTS:
            raise ValueError(f"unknown conv_layout {self.conv_layout!r}")
        # what _plan_layouts logs and stats.yaml carries beside the plan:
        # what was asked for, and the backend that decided where it was left
        # to the table
        self._layout_asked = (("auto", backend) if backend
                              else (asked, "asked for"))
        self.fuse_conv_epilogues = fuse_conv_epilogues
        # Conv lowering strategy, also a graph-level request resolved at
        # construction: a value forces one strategy net-wide; "" keeps
        # the global conv_s2d policy.
        self.conv_strategy = (conv_strategy if conv_strategy is not None
                              else policy().conv_strategy) or ""
        if self.conv_strategy not in NN.CONV_STRATEGIES:
            raise ValueError(
                f"unknown conv_strategy {self.conv_strategy!r}; choose "
                f"from {NN.CONV_STRATEGIES}")

        selected = filter_net(net_param, self.state)
        self.source_layer_params: List[LayerParameter] = []
        self.layers: List[Layer] = []
        blob_shapes: Dict[str, Shape] = {}

        # Explicit net inputs (deploy-style nets).
        if net_param.input:
            dims = net_param.input_dim
            if len(dims) != 4 * len(net_param.input):
                raise ValueError("input_dim must have 4 entries per input")
            for i, name in enumerate(net_param.input):
                blob_shapes[name] = tuple(dims[4 * i:4 * i + 4])

        # Any supplied source shape is an external input (tops of data layers
        # when the net has them, or direct feeds for programmatic nets).
        source_shapes = dict(source_shapes or {})
        for name, shape in source_shapes.items():
            blob_shapes[name] = tuple(shape)
        for idx, lp in enumerate(selected):
            t = lp.canonical_type()
            if t in DATA_SOURCE_TYPES:
                self.source_layer_params.append(lp)
                for top in lp.top:
                    if top not in source_shapes:
                        raise ValueError(
                            f"data layer {lp.name!r}: shape for top {top!r} "
                            f"must be supplied via source_shapes")
                    blob_shapes[top] = tuple(source_shapes[top])
                continue
            layer = create_layer(lp, phase, idx)
            bottoms = []
            for b in lp.bottom:
                if b not in blob_shapes:
                    raise ValueError(f"layer {lp.name!r}: unknown bottom {b!r}")
                bottoms.append(blob_shapes[b])
            tops = layer.setup(bottoms)
            if len(tops) != len(lp.top):
                raise ValueError(
                    f"layer {lp.name!r}: produced {len(tops)} tops, "
                    f"declared {len(lp.top)}")
            for name, shape in zip(lp.top, tops):
                blob_shapes[name] = tuple(int(d) for d in shape)
            self.layers.append(layer)

        self.blob_shapes = blob_shapes
        seen = set(net_param.input)
        self.input_names: List[str] = list(net_param.input)
        for name in list(source_shapes) + [
                t for lp in self.source_layer_params for t in lp.top]:
            if name not in seen:
                seen.add(name)
                self.input_names.append(name)

        produced, consumed = [], set()
        for layer in self.layers:
            for b in layer.lp.bottom:
                consumed.add(b)
            for t in layer.lp.top:
                if t not in produced:
                    produced.append(t)
        # Layer-updated leaves: {(layer, param): the top that is its next
        # value}. Such a leaf is state the layer keeps outside the gradient
        # (a router's selection bias, balanced by the step's own loads);
        # its top is the step's business, not an output of the net.
        self.layer_updates: Dict[Tuple[str, str], str] = {
            (layer.name, pname): layer.lp.top[top]
            for layer in self.layers
            for pname, top in getattr(layer, "updates", {}).items()}
        self.output_names = [t for t in produced if t not in consumed
                             and t not in self.layer_updates.values()]

        # Cross-layer weight sharing (the reference's named params,
        # layer.hpp / net.cpp shared-blob machinery; what siamese nets use):
        # a non-empty ParamSpec.name binds a layer's blob to shared storage
        # owned by the first layer that declared the name. param_defs holds
        # OWNERS only, so the gradient pytree has one leaf per unique
        # parameter and autodiff sums the contributions of every sharer.
        self.param_defs: Dict[str, List[ParamDef]] = {}
        self._storage_of: Dict[Tuple[str, str], Tuple[str, str]] = {}
        shared_owner: Dict[str, Tuple[str, str, ParamDef]] = {}
        # {share name: {"owner": "<layer>/<blob>", "uses": layers bound to
        # it}}: what stats.yaml's ``shared_params`` section says
        self.shared_params: Dict[str, Dict] = {}
        for layer in self.layers:
            if not layer.params:
                continue
            owned: List[ParamDef] = []
            for i, pdef in enumerate(layer.params):
                share_name = layer.lp.param_spec(i).name
                if share_name and pdef.layer_updated:
                    raise ValueError(
                        f"layer {layer.name!r}: {pdef.name!r} is updated by "
                        f"its layer and cannot be shared ({share_name!r})")
                if share_name and share_name in shared_owner:
                    olayer, opname, odef = shared_owner[share_name]
                    spec = layer.lp.param_spec(i)
                    # V1 nets use the layer-level blob_share_mode list; V2
                    # nets carry share_mode on the ParamSpec itself.
                    mode = (layer.lp.blob_share_mode[i]
                            if i < len(layer.lp.blob_share_mode)
                            else spec.share_mode)
                    if (spec.lr_mult, spec.decay_mult) != (odef.lr_mult,
                                                           odef.decay_mult):
                        raise ValueError(
                            f"layer {layer.name!r}: shared param "
                            f"{share_name!r} lr/decay multipliers "
                            f"({spec.lr_mult}, {spec.decay_mult}) differ from "
                            f"owner {olayer!r}'s ({odef.lr_mult}, "
                            f"{odef.decay_mult})")
                    if mode == "PERMISSIVE":
                        if pdef.count != odef.count:
                            raise ValueError(
                                f"layer {layer.name!r}: shared param "
                                f"{share_name!r} count mismatch "
                                f"{pdef.count} vs {odef.count}")
                    elif pdef.shape != odef.shape:
                        raise ValueError(
                            f"layer {layer.name!r}: shared param "
                            f"{share_name!r} shape mismatch "
                            f"{pdef.shape} vs {odef.shape}")
                    self._storage_of[(layer.name, pdef.name)] = (olayer, opname)
                    self.shared_params[share_name]["uses"] += 1
                else:
                    if share_name:
                        shared_owner[share_name] = (layer.name, pdef.name, pdef)
                        self.shared_params[share_name] = {
                            "owner": f"{layer.name}/{pdef.name}", "uses": 1}
                    self._storage_of[(layer.name, pdef.name)] = (layer.name,
                                                                 pdef.name)
                    owned.append(pdef)
            if owned:
                self.param_defs[layer.name] = owned
        self._layer_by_name = {l.name: l for l in self.layers}
        # Static arena offset table (core/arena.py): every owner ParamDef in
        # DWBP order — REVERSE forward layer order, the order gradients
        # materialize during backward — so arena bucket 0 holds the leaves
        # whose gradients exist first and the bucketed sync preserves the
        # per-layer overlap structure. Computed here once; the trainer (and
        # anything re-deriving a layout) restricts it to the comm config's
        # arena-eligible layers via arena_layout().
        self._arena_order: List[Tuple[str, ParamDef]] = [
            (layer.name, pdef)
            for layer in reversed(self.layers)
            if layer.name in self.param_defs
            for pdef in self.param_defs[layer.name]]
        self._arena_layouts: Dict = {}
        if self.fuse_conv_epilogues:
            self._plan_epilogues()
        self._plan_layouts()
        self._plan_conv_strategies()
        self._plan_kernel_routes()
        self._plan_gated_products()

    # ------------------------------------------------------------------ #
    def arena_layout(self, include=None, bucket_mb: float = 4.0,
                     align: int = 1):
        """The flat-parameter-arena layout over this net's DWBP-ordered
        offset table, restricted to ``include`` layers (default: all param
        layers) and cut into ~``bucket_mb`` MB collective buckets, with
        bucket boundaries aligned to ``align`` elements (the SPMD mesh's
        fsdp shard count — parallel/spmd.py). Cached per
        (include, bucket_mb, align) so the trainer, tests and tools always
        agree on offsets. Returns None when nothing qualifies."""
        from .arena import build_arena
        inc = frozenset(self.param_defs) if include is None \
            else frozenset(include)
        key = (inc, bucket_mb, align)
        if key not in self._arena_layouts:
            self._arena_layouts[key] = build_arena(self._arena_order, inc,
                                                   bucket_mb, align=align)
        return self._arena_layouts[key]

    # ------------------------------------------------------------------ #
    def _plan_epilogues(self) -> None:
        """Fold each in-place ReLU that immediately consumes a conv's top
        into the conv's fused epilogue (bias + ReLU in one XLA kernel).
        Exact: identical formula, identical blob values (in-place ReLU
        already overwrites the blob, so downstream consumers see the
        activated values either way). Skipped when any layer touches the
        blob between the conv and the ReLU, or when the conv's own top
        carries a loss_weight (the pre-activation sum would change)."""
        for i, layer in enumerate(self.layers):
            if layer.TYPE != "CONVOLUTION" or len(layer.lp.top) != 1:
                continue
            if layer.lp.loss_weight:
                continue
            top = layer.lp.top[0]
            for nxt in self.layers[i + 1:]:
                if (nxt.TYPE == "RELU" and nxt.lp.bottom == [top]
                        and nxt.lp.top == [top]):
                    layer.fused_relu_slope = nxt.lp.relu_param.negative_slope
                    nxt.folded_into = layer.name
                    break
                if top in nxt.lp.bottom or top in nxt.lp.top:
                    break

    def _plan_gated_products(self) -> None:
        """Name the products of every gated unit (``layers.FFN_SAVED``), so
        that a checkpointed unit can keep them and replay none of them
        (``core/remat.keep_rungs``): ``ffn_in`` on each INNER_PRODUCT whose
        result a SILU_GATE reads, directly or as the halves of a SLICE (a
        dense gated FFN's gate and up, or the one product of both that is
        then cut in two), ``ffn_out`` on the INNER_PRODUCT that reads the
        SILU_GATE's result (the down product). Read off the net, not off
        names, so a mixer's gated output projection counts too. The name
        sits on the product, never on the slices: kept slices would cost a
        copy each. So a product of which the gate reads only a PART is not
        named (a Mamba-2 mixer's input projection, cut into z, xBC and dt:
        kept whole it is 139 MB a layer for z's 67, and Granite's step with
        those names compiles at 15.80 GB where 14.88 is allowed; compiler
        accounting, sandbox, PR 57). Outside a checkpoint that asks for it
        a name lowers to nothing."""
        maker: Dict[str, Layer] = {}        # blob -> the layer that wrote it
        whole: Dict[str, Layer] = {}        # a SLICE -> who made its bottom
        for layer in self.layers:
            made_by = [maker.get(b) for b in layer.lp.bottom]
            if layer.TYPE == "SLICE":
                whole[layer.name] = made_by[0]
            elif layer.TYPE == "SILU_GATE":
                for src in set(made_by):
                    if src is not None and src.TYPE == "SLICE" \
                            and set(src.lp.top) <= set(layer.lp.bottom):
                        src = whole[src.name]
                    if src is not None and src.TYPE == "INNER_PRODUCT":
                        src.saved_as = "ffn_in"
            elif layer.TYPE == "INNER_PRODUCT" and made_by[0] is not None \
                    and made_by[0].TYPE == "SILU_GATE":
                layer.saved_as = "ffn_out"
            for top in layer.lp.top:
                maker[top] = layer

    def _plan_layouts(self) -> None:
        """Assign each layer's run layout and each external input's entry
        layout. Under NCHW this is the identity plan. Under NHWC: spatial
        layers (conv/pool/LRN) run channels-last natively, agnostic layers
        propagate whatever layout their 4-D bottoms arrived in, and
        canonical layers (FC flatten, im2col, dropout rng, unknown types)
        force the genuine NCHW boundary. The walk mirrors ``apply``'s, so
        apply can replay it to know every blob's physical layout at every
        program point (in-place chains may re-layout a name mid-net).

        Which plan the net took, why, and what it holds is logged in one
        line, like every other routing decision, and kept as
        ``layout_plan`` (stats.yaml's ``conv_layout`` section): the layers
        that run channels-last of those that touch a 4-D blob, and the
        boundary conversions ``apply`` will make (a 4-D bottom that arrives
        in the other layout, ``<blob>-><layer>``; a 4-D output exported
        canonical, ``<blob>->out``). A net without a 4-D blob counts 0."""
        from ..runtime.metrics import log
        self.input_layouts: Dict[str, str] = {}
        nhwc = self.conv_layout == "NHWC"
        for name in self.input_names:
            four_d = len(self.blob_shapes[name]) == 4
            self.input_layouts[name] = "NHWC" if (nhwc and four_d) else "NCHW"
        cur = dict(self.input_layouts)
        four_d_layers = channels_last = 0
        boundaries: List[str] = []
        converted: set = set()      # apply's cache: (blob, layout) it holds
        for layer in self.layers:
            b4 = [b for b in layer.lp.bottom
                  if len(self.blob_shapes[b]) == 4]
            t4 = [t for t in layer.lp.top if len(self.blob_shapes[t]) == 4]
            four_d_layers += bool(b4 or t4)
            if not nhwc:
                continue
            if layer.LAYOUT_KIND == LAYOUT_SPATIAL:
                run = "NHWC"
            elif layer.LAYOUT_KIND == LAYOUT_AGNOSTIC:
                run = ("NHWC" if b4 and all(cur.get(b, "NCHW") == "NHWC"
                                            for b in b4) else "NCHW")
            else:
                run = "NCHW"
            layer.run_layout = run
            channels_last += run == "NHWC"
            for b in b4:
                if cur.get(b, "NCHW") != run and (b, run) not in converted:
                    converted.add((b, run))
                    boundaries.append(f"{b}->{layer.name}")
            for t in t4:
                cur[t] = run
                converted -= {(t, "NCHW"), (t, "NHWC")}
        boundaries += [f"{o}->out" for o in self.output_names
                       if len(self.blob_shapes[o]) == 4
                       and cur.get(o, "NCHW") == "NHWC"]
        asked, why = self._layout_asked
        self.layout_plan: Dict[str, object] = {
            "asked": asked, "resolved": self.conv_layout, "why": why,
            "layers_with_4d_blob": four_d_layers,
            "channels_last_layers": channels_last,
            "boundary_conversions": len(boundaries),
            "boundaries": ", ".join(boundaries) or "none",
            # a caller that feeds canonical batches (the Engine) pays one
            # transpose for each of these at the step's entry
            "inputs_channels_last": sum(
                v == "NHWC" for v in self.input_layouts.values())}
        head = f"{self.conv_layout} ({why})"
        if asked == "auto":
            head = f"auto -> {head}"
        if not four_d_layers:
            what = "no 4-D blob, nothing to plan"
        elif not nhwc:
            what = (f"{four_d_layers} layers with a 4-D blob run "
                    f"canonical, no boundary")
        else:
            what = (f"{channels_last} of {four_d_layers} layers with a 4-D "
                    f"blob run channels-last, {len(boundaries)} boundary "
                    f"conversion(s)"
                    + (f": {', '.join(boundaries)}" if boundaries else ""))
        log(f"[conv_layout] {head}: {what}")

    def _plan_conv_strategies(self) -> None:
        """Resolve each conv layer's lowering strategy. "" leaves the
        global-policy behavior (layer.conv_strategy stays None); a
        concrete strategy is assigned net-wide."""
        req = self.conv_strategy
        convs = [l for l in self.layers if l.TYPE == "CONVOLUTION"]
        if not req:
            self._route_one_channel_convs(convs)
            return
        for layer in convs:
            layer.conv_strategy = req

    def _route_one_channel_convs(self, convs) -> None:
        """No strategy asked for, lowering for the TPU: a conv over ONE
        input channel goes through patches + GEMM. libtpu 0.0.34 does not
        finish compiling that conv's backward as a direct convolution at
        f32 HIGHEST — examples/mnist LeNet, batch 64, ran past 600 s on
        the v5e host inside `.compile()`; as im2col the whole step compiles
        in 36 s and trains (PR 21, on the chip). Same sums, other order.
        Logged per layer like every other routing decision."""
        from ..ops.pallas_kernels import _interpret_default
        from ..runtime.metrics import log
        if not convs or _interpret_default():
            return
        for layer in convs:
            if self.blob_shapes[layer.lp.bottom[0]][1] == 1:
                layer.conv_strategy = "im2col"
                log(f"[conv_strategy] {layer.name}: 1 input channel -> "
                    f"im2col (the direct conv does not finish compiling "
                    f"for the TPU)")

    def _bottom_shapes(self, layer: Layer) -> List[Shape]:
        return [self.blob_shapes[b] for b in layer.lp.bottom]

    def _plan_kernel_routes(self) -> None:
        """Which arm — which XLA formulation or which kernel — each layer
        that has more than one lowers to, as the layer says
        (``Layer.kernel_route``), logged once per layer. The routing is by
        platform and shape, which is legitimate; what is not is a run that
        cannot say which arm it took."""
        from ..runtime.metrics import log
        itemsize = jnp.dtype(policy().compute_dtype).itemsize
        self.kernel_routes: Dict[str, str] = {}
        for layer in self.layers:
            route = layer.kernel_route(self._bottom_shapes(layer), itemsize)
            if route is None:
                continue
            what, arm, note = route
            if arm == "pallas" and note:
                # the orientation and block the pool / LRN kernels run with
                arm, note = f"{arm} ({note})", ""
            self.kernel_routes[layer.name] = f"{what}={arm}"
            log(f"[kernel_route] {layer.name}: {what} -> {arm}"
                + (f" ({note})" if note else ""))

    def layer_facts(self) -> Dict[str, Dict[str, Dict]]:
        """{section of stats.yaml: {layer: its facts}} as the layers state
        them (``Layer.stats_sections``): ``expert_share``, which of the
        router's experts a MOE layer holds; ``recurrent_state``, a scan's
        states' shape, its chunk and what its backward keeps of them."""
        itemsize = jnp.dtype(policy().compute_dtype).itemsize
        out: Dict[str, Dict[str, Dict]] = {}
        for layer in self.layers:
            for section, facts in layer.stats_sections(
                    self._bottom_shapes(layer), itemsize).items():
                out.setdefault(section, {})[layer.name] = facts
        return out

    def display_counters(self) -> Dict[str, object]:
        """{a displayed top: its value of one step -> {counter: increment}}
        over every layer (``Layer.display_counters``)."""
        return {top: count for layer in self.layers
                for top, count in layer.display_counters(
                    self._bottom_shapes(layer)).items()}

    def cost_table(self, dtype_bytes: int = 4) -> Dict[str, Dict]:
        """{layer: {flops, bytes, act_bytes, intensity}} for one train step
        (fwd+bwd), from blob/param shapes — the analytic model the
        attribution table's FLOPs column joins from (XLA's cost_analysis
        reports only the whole-module total). Each layer states its forward
        FLOPs (``Layer.forward_flops``): conv/FC exact MAC counts,
        pool/LRN/elementwise per-element op estimates — they exist to rank
        sinks and compute intensity, not to be a simulator. Backward = dW +
        dX = 2x forward. Bytes = activations in + out + params, x3 for the
        backward's re-reads and gradient writes.

        ``act_bytes`` is the layer's STORED forward activation footprint —
        the top blobs autodiff keeps live until the backward pass consumes
        them. It is the per-layer column core/remat.py's budget knapsack
        ranks against recompute FLOPs; an in-place top (same name as a
        bottom) still counts once, matching what the trace stores."""
        out: Dict[str, Dict] = {}
        for layer in self.layers:
            bots = self._bottom_shapes(layer)
            tops = [self.blob_shapes[t] for t in layer.lp.top]
            defs = self.param_defs.get(layer.name, [])
            out_elems = sum(math.prod(t) for t in tops)
            moved = sum(math.prod(b) for b in bots) + out_elems \
                + sum(p.count for p in defs)
            flops = 3.0 * layer.forward_flops(bots, tops, defs)
            bytes_ = 3.0 * moved * dtype_bytes
            out[layer.name] = {
                "flops": flops,
                "bytes": bytes_,
                "act_bytes": int(out_elems) * int(dtype_bytes),
                "intensity": round(flops / bytes_, 3) if bytes_ else None,
            }
        return out

    def conv_strategy_plan(self) -> Dict[str, Optional[str]]:
        """{conv layer name: resolved strategy} — what bench/tests print."""
        return {l.name: l.conv_strategy for l in self.layers
                if l.TYPE == "CONVOLUTION"}

    def _layer_params(self, params, layer: Layer,
                      comm=None) -> Dict[str, jax.Array]:
        """Resolve a layer's param dict through the sharing bindings."""
        out = {}
        for pdef in layer.params:
            olayer, opname = self._storage_of[(layer.name, pdef.name)]
            arr = params[olayer][opname]
            if arr.shape != pdef.shape:
                if arr.size == pdef.count:
                    # PERMISSIVE share: same count, different shape
                    arr = arr.reshape(pdef.shape)
                elif comm is not None and getattr(
                        comm, "is_tp_leaf", lambda *_: False)(
                            layer.name, pdef.name):
                    # tensor-parallel shard (parallel/spmd.py): the
                    # layer's comm hook consumes the local slice as-is
                    pass
                else:
                    raise ValueError(
                        f"layer {layer.name!r} param {pdef.name!r}: got "
                        f"shape {tuple(arr.shape)} for defined shape "
                        f"{tuple(pdef.shape)} — size mismatch with no "
                        f"tensor-parallel plan covering this leaf")
            out[pdef.name] = arr
        return out

    # ------------------------------------------------------------------ #
    def init(self, rng: jax.Array) -> Dict[str, Dict[str, jax.Array]]:
        params: Dict[str, Dict[str, jax.Array]] = {}
        for li, (lname, defs) in enumerate(sorted(self.param_defs.items())):
            lparams = {}
            for pi, pdef in enumerate(defs):
                key = jax.random.fold_in(jax.random.fold_in(rng, li), pi)
                lparams[pdef.name] = fill(key, pdef)
            params[lname] = lparams
        return params

    def param_count(self) -> int:
        return sum(p.count for defs in self.param_defs.values() for p in defs)

    # ------------------------------------------------------------------ #
    def _remat_units(self, remat) -> Dict[int, Tuple[Layer, ...]]:
        """{index of a unit's first layer: its layers} for ``apply``'s
        ``remat``: names checked, a segment's layers consecutive and in one
        layout, no layer in two units."""
        index = {l.name: i for i, l in enumerate(self.layers)}
        units: Dict[int, Tuple[Layer, ...]] = {}
        seen = set()
        for unit in remat or ():
            names = (unit,) if isinstance(unit, str) else tuple(unit)
            unknown = sorted(set(names) - set(index))
            if unknown:
                raise ValueError(f"remat names unknown layers: {unknown}")
            first = index[names[0]]
            layers = tuple(self.layers[first:first + len(names)])
            if tuple(l.name for l in layers) != names:
                raise ValueError(f"remat segment {names}: its layers do not "
                                 f"follow one another in the net")
            if seen & set(names):
                raise ValueError(f"remat names {sorted(seen & set(names))} "
                                 f"twice")
            if len({l.run_layout for l in layers}) > 1:
                raise ValueError(f"remat segment {names} crosses a layout "
                                 f"boundary of the NHWC plan")
            seen.update(names)
            units[first] = layers
        return units

    # ------------------------------------------------------------------ #
    def apply(
        self,
        params: Dict[str, Dict[str, jax.Array]],
        inputs: Dict[str, jax.Array],
        train: Optional[bool] = None,
        rng: Optional[jax.Array] = None,
        comm=None,
        keep_blobs: bool = False,
        input_layout: str = "NCHW",
        remat=None,
        remat_keep: Tuple[str, ...] = (),
    ) -> NetOutputs:
        """``input_layout`` names the physical layout of the CALLER's 4-D
        input blobs ("NCHW" default — the Caffe contract). Under an NHWC
        plan, feeding "NHWC" directly (images are naturally HWC; the bench
        generates device-side) makes the hot path transpose-free; feeding
        canonical NCHW costs exactly one entry transpose per image input.
        Outputs and ``keep_blobs`` are ALWAYS canonical NCHW — export,
        HDF5 dumps and debug tooling never see the internal layout.

        ``remat`` names what runs under ``jax.checkpoint`` (usually a
        ``core/remat.RematPlan.units``): each item a layer name, or a tuple
        of CONSECUTIVE layer names that share one checkpoint (a segment: a
        transformer block, a head and its loss). What a unit takes from
        outside stays stored as the checkpoint's input; everything it
        makes is dropped after forward and recomputed during backward. A
        chain of one-layer units stores every link; a segment stores only
        its ends. ``remat_keep`` (a plan's ``keep``) names what a unit
        stores of its own making besides: the results of its Pallas forward
        kernels, which their backward kernels read, so that the replay
        runs no kernel a second time. The wrap changes WHAT IS STORED,
        never the math."""
        if train is None:
            train = self.phase == "TRAIN"
        if comm is not None:
            # reset the comm context's per-trace state (DWBP chain tokens)
            getattr(comm, "begin", lambda: None)()
        ctx = ApplyCtx(train=train, rng=rng, comm=comm)
        # physical layout of every blob at the CURRENT program point (an
        # in-place chain may re-layout a name mid-net); mirrors the
        # planner's walk in _plan_layouts
        cur_layout: Dict[str, str] = {}
        blobs: Dict[str, jax.Array] = {}
        for name, val in inputs.items():
            want = self.input_layouts.get(name, "NCHW")
            if getattr(val, "ndim", 0) == 4:
                val = NN.to_layout(val, input_layout, want)
            blobs[name] = val
            cur_layout[name] = want
        converted: Dict[Tuple[str, str], jax.Array] = {}

        def bottom_in(name: str, want: str) -> jax.Array:
            v = blobs[name]
            have = cur_layout.get(name, "NCHW")
            if getattr(v, "ndim", 0) != 4 or have == want:
                return v
            key = (name, want)
            if key not in converted:
                converted[key] = NN.to_layout(v, have, want)
            return converted[key]

        unit_at = self._remat_units(remat)
        loss = jnp.zeros((), jnp.float32)
        outputs: Dict[str, jax.Array] = {}
        at = 0
        while at < len(self.layers):
            checkpointed = at in unit_at
            unit = unit_at[at] if checkpointed else (self.layers[at],)
            at += len(unit)
            # layer-scoped HLO metadata: xplane trace events carry the layer
            # name, so one profiled step attributes device time per layer
            # (no per-layer recompiles — the `time --per_layer` alternative
            # on compile-expensive runtimes); autodiff preserves the scope,
            # so backward ops attribute too (transpose(jvp(<name>)) paths —
            # runtime/attribution.py joins both back). Bottom layout
            # conversions sit INSIDE the scope: a boundary transpose bills
            # to the layer that demanded it, not to the residual row.
            if checkpointed:
                # remat (core/remat.py): checkpoint the unit's body — what
                # it takes from outside and its params stay stored as the
                # checkpoint's inputs, its tops recompute during backward.
                # The named_scope sits INSIDE the checkpointed function
                # (the JIT106 contract): the recomputed ops must keep
                # attributing to their layer, not the residual row. ctx
                # (rng/comm) is closed over, not differentiated — the
                # recompute replays the same dropout masks and the comm
                # taps' custom_vjp rules fire once, in backward order.
                made: set = set()
                taken: Dict[Tuple[str, str], jax.Array] = {}
                for layer in unit:
                    with jax.named_scope(layer.name):
                        for b in layer.lp.bottom:
                            if b not in made:
                                taken[(b, layer.run_layout)] = bottom_in(
                                    b, layer.run_layout)
                    made.update(layer.lp.top)
                lparams = [self._layer_params(params, layer, comm)
                           if layer.params else {} for layer in unit]

                def _body(lps_, taken_, _unit=unit):
                    local: Dict[str, jax.Array] = {}
                    all_tops = []
                    for layer_, lp_ in zip(_unit, lps_):
                        with jax.named_scope(layer_.name):
                            tops_ = layer_.apply(lp_, [
                                local[b] if b in local
                                else taken_[(b, layer_.run_layout)]
                                for b in layer_.lp.bottom], ctx)
                        local.update(zip(layer_.lp.top, tops_))
                        all_tops.append(tops_)
                    return all_tops

                unit_tops = checkpoint_unit(_body, remat_keep)(lparams,
                                                               taken)
            else:
                layer = unit[0]
                with jax.named_scope(layer.name):
                    bottoms = [bottom_in(b, layer.run_layout)
                               for b in layer.lp.bottom]
                    unit_tops = [layer.apply(
                        self._layer_params(params, layer, comm)
                        if layer.params else {},
                        bottoms, ctx)]
            for layer, tops in zip(unit, unit_tops):
                weights = layer.loss_weights(len(tops))
                for name, val, w in zip(layer.lp.top, tops, weights):
                    blobs[name] = val
                    cur_layout[name] = layer.run_layout
                    converted.pop((name, "NCHW"), None)
                    converted.pop((name, "NHWC"), None)
                    if w:
                        # Caffe sums the whole top blob into the objective
                        # when a loss_weight is set on a non-scalar top
                        # (net.cpp) — layout-invariant, so the sum needs no
                        # conversion.
                        loss = loss + w * jnp.sum(val.astype(jnp.float32))

        def canonical(name: str) -> jax.Array:
            v = blobs[name]
            if getattr(v, "ndim", 0) != 4:
                return v
            return NN.to_layout(v, cur_layout.get(name, "NCHW"), "NCHW")

        for name in self.output_names:
            outputs[name] = canonical(name)
        updates: Dict[str, Dict[str, jax.Array]] = {}
        for (lname, pname), top in self.layer_updates.items():
            updates.setdefault(lname, {})[pname] = jax.lax.stop_gradient(
                blobs[top])
        return NetOutputs(
            loss=loss, outputs=outputs,
            blobs={k: canonical(k) for k in blobs} if keep_blobs else {},
            updates=updates)

    # ------------------------------------------------------------------ #
    def load_weights(self, params, layer_weights: Dict[str, List[np.ndarray]],
                     strict: bool = False):
        """CopyTrainedLayersFrom (net.cpp): merge {layer: [blob arrays]} by
        name/order; unknown layers ignored unless strict."""
        new_params = {k: dict(v) for k, v in params.items()}
        for lname, arrays in layer_weights.items():
            layer = self._layer_by_name.get(lname)
            if layer is None or not layer.params:
                if strict:
                    raise KeyError(f"no such param layer {lname!r}")
                continue
            # Caffe serializes EVERY layer's blobs, shared ones included
            # (Layer::ToProto); route each blob to its owning storage.
            defs = layer.params
            if len(arrays) != len(defs):
                raise ValueError(
                    f"{lname}: {len(arrays)} blobs in file, {len(defs)} in net")
            for pdef, arr in zip(defs, arrays):
                arr = np.asarray(arr, np.float32)
                if int(arr.size) != pdef.count:
                    raise ValueError(
                        f"{lname}/{pdef.name}: count mismatch "
                        f"{arr.size} vs {pdef.count}")
                olayer, opname = self._storage_of[(lname, pdef.name)]
                oshape = next(d.shape for d in self.param_defs[olayer]
                              if d.name == opname)
                new_params[olayer][opname] = jnp.asarray(arr.reshape(oshape))
        return new_params

    def export_weights(self, params) -> Dict[str, List[np.ndarray]]:
        """Every param layer's blobs, shared ones included (Caffe's
        serialization shape: sharers repeat the shared array)."""
        out: Dict[str, List[np.ndarray]] = {}
        for layer in self.layers:
            if layer.params:
                out[layer.name] = [
                    np.asarray(self._layer_params(params, layer)[p.name])
                    for p in layer.params]
        return out
