"""The layer catalog: all reference layer types as shape-inferring, pure ops.

Mirrors the capability of the 40-type catalog in
``/root/reference/src/caffe/layers/`` + ``src/caffe/layer_factory.cpp`` while
being functional: a layer is (setup: bottom shapes -> top shapes + ParamDefs,
apply: params x bottoms -> tops). Backward never appears — it is derived by
``jax.grad`` over the whole net — so the per-layer ``Backward_{cpu,gpu}``
kernels of the reference have no analog here by design.

Data-producing layers (DATA, IMAGE_DATA, HDF5_DATA, WINDOW_DATA, MEMORY_DATA)
are *sources*: inside the traced graph their tops are external inputs; the
actual IO lives in ``poseidon_tpu.data`` (host side, prefetched). DUMMY_DATA is
generated in-graph from its fillers.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.ad_checkpoint import checkpoint_name

from ..ops import elementwise as E
from ..ops import losses as L
from ..ops import nn as NN
from ..proto.messages import FillerParameter, LayerParameter
from .blob import ParamDef
from .fillers import fill

Shape = Tuple[int, ...]

LOSS_TYPES = {
    "SOFTMAX_LOSS", "EUCLIDEAN_LOSS", "HINGE_LOSS", "INFOGAIN_LOSS",
    "MULTINOMIAL_LOGISTIC_LOSS", "SIGMOID_CROSS_ENTROPY_LOSS",
    "CONTRASTIVE_LOSS", "EXIT_LOSS", "WEIGHTED_MEAN_LOSS",
}
DATA_SOURCE_TYPES = {"DATA", "IMAGE_DATA", "HDF5_DATA", "WINDOW_DATA", "MEMORY_DATA"}

# Layout contract classes for the net-level channels-last plan (core/net.py):
#   "spatial"   — has a native NHWC implementation (conv/pool/LRN); runs in
#                 the planned layout with zero boundary transposes.
#   "agnostic"  — elementwise / structural; correct in ANY layout (axis-
#                 remapped where the op names a channel axis). Propagates
#                 its input layout.
#   "canonical" — the op's semantics are tied to Caffe's NCHW ordering
#                 (FC flatten, im2col columns, MVN axes, ...); the planner
#                 inserts a layout conversion at this GENUINE boundary.
LAYOUT_SPATIAL = "spatial"
LAYOUT_AGNOSTIC = "agnostic"
LAYOUT_CANONICAL = "canonical"


def _elems(shapes) -> int:
    return sum(math.prod(s) for s in shapes)


def _remap_axis(axis: int, layout: str, ndim: int) -> int:
    """Map a Caffe NCHW-semantics axis onto the physical layout."""
    if layout != "NHWC" or ndim != 4:
        return axis
    return {0: 0, 1: 3, 2: 1, 3: 2}[axis]


class ApplyCtx:
    """Per-call context threaded through Layer.apply."""

    def __init__(self, train: bool, rng: Optional[jax.Array] = None, comm=None):
        self.train = train
        self.rng = rng
        self.comm = comm  # parallel.strategies.CommContext or None

    def layer_rng(self, index: int) -> Optional[jax.Array]:
        if self.rng is None:
            return None
        return jax.random.fold_in(self.rng, index)


class Layer:
    TYPE = "NONE"
    # layout contract class (see module docstring constants); the safe
    # default is canonical — an unknown op never silently consumes NHWC
    LAYOUT_KIND = LAYOUT_CANONICAL

    def __init__(self, lp: LayerParameter, phase: str, index: int = 0):
        self.lp = lp
        self.phase = phase
        self.index = index
        self.params: List[ParamDef] = []
        # physical layout this layer runs in; assigned by the net-level
        # layout planner (core/net.py), "NCHW" outside an NHWC plan
        self.run_layout = "NCHW"

    @property
    def name(self) -> str:
        return self.lp.name

    def default_loss_weight(self) -> float:
        return 1.0 if self.TYPE in LOSS_TYPES else 0.0

    def loss_weights(self, n_tops: int) -> List[float]:
        lw = list(self.lp.loss_weight)
        if not lw:
            # Only top[0] of a loss layer carries loss by default (e.g.
            # SOFTMAX_LOSS's optional second top is the prob blob).
            return [self.default_loss_weight() if i == 0 else 0.0
                    for i in range(n_tops)]
        if len(lw) != n_tops:
            raise ValueError(f"{self.name}: loss_weight arity mismatch")
        return lw

    def _param(self, name: str, shape: Shape, filler: FillerParameter,
               blob_index: int) -> ParamDef:
        spec = self.lp.param_spec(blob_index)
        return ParamDef(name=name, shape=shape, filler=filler,
                        lr_mult=spec.lr_mult, decay_mult=spec.decay_mult)

    # -- protocol ---------------------------------------------------------- #
    def setup(self, bottom_shapes: List[Shape]) -> List[Shape]:
        raise NotImplementedError

    def apply(self, params: Dict[str, jax.Array], bottoms: List[jax.Array],
              ctx: ApplyCtx) -> List[jax.Array]:
        raise NotImplementedError

    # -- what a layer says of itself: ``Net`` asks every layer and switches
    # -- on no type; a type's fact sits beside the ``apply`` it is a fact of
    def kernel_route(self, bottom_shapes: List[Shape], itemsize: int
                     ) -> Optional[Tuple[str, str, str]]:
        """(what, arm, note): the kernel or XLA formulation ``apply`` lowers
        to on these bottoms at this compute itemsize, from the SAME route
        function the op consults at trace time (``Net.kernel_routes``).
        None: one lowering, nothing to report."""
        return None

    def stats_sections(self, bottom_shapes: List[Shape], itemsize: int
                       ) -> Dict[str, Dict]:
        """{section of stats.yaml: this layer's facts} (``Net.layer_facts``)."""
        return {}

    def display_counters(self, bottom_shapes: List[Shape]
                         ) -> Dict[str, Callable[[float], Dict[str, float]]]:
        """{a scalar top a display carries: its value of one step -> the
        {counter: increment} the Engine adds for that step}."""
        return {}

    def forward_flops(self, bottom_shapes: List[Shape],
                      top_shapes: List[Shape], defs: List[ParamDef]) -> float:
        """Forward FLOPs of one step from the shapes and the parameter
        definitions this layer OWNS (``Net.cost_table``); by default an
        elementwise estimate, one op an element of the larger side."""
        return float(max(_elems(bottom_shapes), _elems(top_shapes)))


# --------------------------------------------------------------------------- #
# Parametric layers
# --------------------------------------------------------------------------- #

def _resolve_hw(single, h, w, default=None, *, what="", layer=""):
    """Caffe's size-resolution rule with its CHECKs (conv/pooling LayerSetUp):
    either the square `single` value or BOTH h and w; required unless a
    default exists."""
    if h or w:
        if single:
            raise ValueError(
                f"layer {layer!r}: specify {what} as one size OR "
                f"{what}_h/{what}_w, not both")
        if not (h and w):
            raise ValueError(
                f"layer {layer!r}: both {what}_h and {what}_w are required "
                f"for non-square {what}")
        return int(h), int(w)
    if single:
        return int(single), int(single)
    if default is None:
        raise ValueError(f"layer {layer!r}: {what} must be specified")
    return default, default


class ConvolutionLayer(Layer):
    TYPE = "CONVOLUTION"
    LAYOUT_KIND = LAYOUT_SPATIAL

    def __init__(self, lp: LayerParameter, phase: str, index: int = 0):
        super().__init__(lp, phase, index)
        # fused epilogue: set by the net-level plan when an in-place ReLU
        # immediately consumes this conv's top (one XLA kernel per conv)
        self.fused_relu_slope: Optional[float] = None
        # per-layer lowering strategy: resolved by the net-level plan
        # (measured under conv_strategy="auto"); None = the legacy global
        # conv_s2d policy decides inside ops/nn.conv2d
        self.conv_strategy: Optional[str] = None

    def setup(self, bottom_shapes):
        cp = self.lp.convolution_param
        n, c, h, w = bottom_shapes[0]
        self.kernel = _resolve_hw(cp.kernel_size, cp.kernel_h, cp.kernel_w,
                                  what="kernel", layer=self.name)
        self.stride = _resolve_hw(cp.stride, cp.stride_h, cp.stride_w, 1,
                                  what="stride", layer=self.name)
        self.pad = _resolve_hw(cp.pad, cp.pad_h, cp.pad_w, 0,
                               what="pad", layer=self.name)
        self.group = cp.group
        self.bias_term = cp.bias_term
        if c % self.group or cp.num_output % self.group:
            raise ValueError(f"{self.name}: channels not divisible by group")
        wshape = (cp.num_output, c // self.group, *self.kernel)
        self.params = [self._param("w", wshape, cp.weight_filler, 0)]
        if self.bias_term:
            self.params.append(
                self._param("b", (cp.num_output,), cp.bias_filler, 1))
        oh = NN.conv_out_size(h, self.kernel[0], self.stride[0], self.pad[0])
        ow = NN.conv_out_size(w, self.kernel[1], self.stride[1], self.pad[1])
        return [(n, cp.num_output, oh, ow)] * len(self.lp.top)

    def apply(self, params, bottoms, ctx):
        w = params["w"]
        b = params.get("b") if self.bias_term else None
        if ctx.comm is not None:
            # taps see the CANONICAL (OIHW) weight — the layout plan never
            # reshapes params, so DWBP/SFB gradients stay layout-portable
            w = ctx.comm.tap_param(self.name, "w", w)
            if b is not None:
                b = ctx.comm.tap_param(self.name, "b", b)
        act = "relu" if self.fused_relu_slope is not None else None
        return [NN.conv2d(x, w, b, self.stride, self.pad, self.group,
                          layout=self.run_layout, act=act,
                          act_slope=self.fused_relu_slope or 0.0,
                          strategy=self.conv_strategy)
                for x in bottoms]

    def forward_flops(self, bottom_shapes, top_shapes, defs):
        if not defs or len(defs[0].shape) != 4:
            return super().forward_flops(bottom_shapes, top_shapes, defs)
        k, cg, r, s = defs[0].shape             # exact MACs, x2 for mul+add
        n, _, ho, wo = top_shapes[0]
        return 2.0 * n * ho * wo * k * cg * r * s


# The names a gated unit's products carry (``checkpoint_name``): a product a
# SILU_GATE reads, and the product that reads the SILU_GATE. ``Net`` says
# which layers those are (``_plan_gated_products``); a checkpointed unit may
# keep them (``core/remat.keep_rungs``) so that its replay runs no FFN matmul
# a second time.
FFN_SAVED = ("ffn_in", "ffn_out")


class InnerProductLayer(Layer):
    TYPE = "INNER_PRODUCT"
    saved_as: Optional[str] = None      # one of FFN_SAVED, by Net's plan

    def setup(self, bottom_shapes):
        ip = self.lp.inner_product_param
        shape = bottom_shapes[0]
        self.axis = ip.axis if ip.axis >= 0 else len(shape) + ip.axis
        if not 1 <= self.axis < len(shape):
            raise ValueError(f"{self.name}: inner_product axis {ip.axis} "
                             f"outside a bottom of shape {shape}")
        # axes before `axis` are kept (Caffe: M_ = count(0, axis)); the rest
        # fold into one product. axis 1 is the classic (N, K) flatten.
        self.lead = tuple(shape[:self.axis])
        k = int(np.prod(shape[self.axis:]))
        self.bias_term = ip.bias_term
        self.params = [self._param("w", (ip.num_output, k), ip.weight_filler, 0)]
        if self.bias_term:
            self.params.append(self._param("b", (ip.num_output,), ip.bias_filler, 1))
        return [self.lead + (ip.num_output,)]

    def apply(self, params, bottoms, ctx):
        w = params["w"]
        b = params.get("b") if self.bias_term else None
        x = bottoms[0]
        if self.axis != 1:
            x = x.reshape(-1, w.shape[1])
        y = None
        if ctx.comm is not None:
            # SFB hook: the comm context may supply a sufficient-factor
            # custom-vjp matmul for this layer (SURVEY §2.3; the reference's
            # ComputeGradientFromSV path, inner_product_layer.cpp:126).
            y = ctx.comm.inner_product(self.name, x, w, b)
            if y is None:
                w = ctx.comm.tap_param(self.name, "w", w)
                if b is not None:
                    b = ctx.comm.tap_param(self.name, "b", b)
        if y is None:
            y = NN.inner_product(x, w, b)
        if self.saved_as:
            # on the product itself: what reads it may be slices of it
            y = checkpoint_name(y, self.saved_as)
        return [y if self.axis == 1 else y.reshape(self.lead + y.shape[-1:])]

    def forward_flops(self, bottom_shapes, top_shapes, defs):
        if not defs:
            return super().forward_flops(bottom_shapes, top_shapes, defs)
        wcount = max((p.count for p in defs if len(p.shape) == 2),
                     default=sum(p.count for p in defs))
        return 2.0 * bottom_shapes[0][0] * wcount


# --------------------------------------------------------------------------- #
# Token-model layers: blobs are (batch, sequence, feature). Each is a thin
# wrapper over a function of models/ or ops/ (imported at apply time: a CNN
# never pays for them).
# --------------------------------------------------------------------------- #

def _tap_all(ctx, name, params):
    """Every param of a layer through the comm context's gradient tap."""
    if ctx.comm is None:
        return params
    return {k: ctx.comm.tap_param(name, k, v) for k, v in params.items()}


class EmbedLayer(Layer):
    """ids (N, S) -> (N, S, D): Caffe's Embed, a row lookup (no bias)."""
    TYPE = "EMBED"

    def setup(self, bottom_shapes):
        ep = self.lp.embed_param
        if ep.input_dim <= 0 or ep.num_output <= 0:
            raise ValueError(f"{self.name}: embed_param needs input_dim and "
                             f"num_output")
        self.params = [self._param("w", (ep.input_dim, ep.num_output),
                                   ep.weight_filler, 0)]
        return [tuple(bottom_shapes[0]) + (ep.num_output,)]

    def apply(self, params, bottoms, ctx):
        from ..config import policy
        w = _tap_all(ctx, self.name, params)["w"]
        ids = bottoms[0].astype(jnp.int32)
        # gather, then cast: the rows looked up, not the whole table
        return [w[ids].astype(policy().compute_dtype)]


class RMSNormLayer(Layer):
    """x * rsqrt(mean(x^2) + eps) * g over the last axis; gain only (filled
    with ones), statistics in f32. With ``num_heads`` the last axis is that
    many heads side by side: each is normalised over its own dims and ONE
    gain of a head's width serves them all (a per-head QK-norm). With
    ``num_groups`` it is that many groups of channels side by side: each is
    normalised over its own channels under a gain of the WHOLE width
    (Mamba-2's gated norm with more than one group of B / C)."""
    TYPE = "RMS_NORM"

    def setup(self, bottom_shapes):
        ones = FillerParameter(type="constant", value=1.0)
        width = bottom_shapes[0][-1]
        self.heads = self.lp.rms_norm_param.num_heads
        self.groups = self.lp.rms_norm_param.num_groups
        if self.heads < 0 or (self.heads and width % self.heads):
            raise ValueError(f"{self.name}: {self.heads} heads do not split "
                             f"a last axis of {width}")
        if self.groups < 0 or (self.groups and (
                width % self.groups or self.heads)):
            raise ValueError(
                f"{self.name}: {self.groups} groups do not split a last "
                f"axis of {width}, or num_heads is set beside them (a gain "
                f"a head's width OR one of the whole width)")
        self.params = [self._param(
            "g", (width // self.heads if self.heads else width,), ones, 0)]
        return [bottom_shapes[0]]

    def apply(self, params, bottoms, ctx):
        from ..models.transformer import rms_norm
        g = _tap_all(ctx, self.name, params)["g"]
        x, eps = bottoms[0], self.lp.rms_norm_param.eps
        if self.heads or self.groups:
            split = x.reshape(x.shape[:-1] + (self.heads or self.groups, -1))
            if self.groups:             # the gain's own slice a group
                g = g.reshape(split.shape[-2:])
            return [rms_norm(split, g, eps).reshape(x.shape)]
        return [rms_norm(x, g, eps)]


class AttentionLayer(Layer):
    """Bottoms q (N, S, D), k and v (N, S, Dkv) -> (N, S, D): q split into
    ``num_heads`` heads, k and v into ``num_kv_heads`` of the same width
    (grouped-query attention; unset: as many as q, Dkv = D), rotate-half
    RoPE on the first ``rotary_dims`` of every q and k head (unset: the
    whole head; ``rope: false``: no positions at all), causal
    softmax(q k^T / sqrt(Dh)) v (``scale`` > 0: softmax(scale q k^T) v, a
    model's own multiplier; 0, the default, is 1 / sqrt(Dh) and leaves every
    net that does not set it the lowered step it had) over every earlier
    token or, with a
    ``window``, over the last that many (t - window < s <= t), heads
    merged. Any whole number of query heads may share a key-value head (8
    do in a 32 / 4 layer). It normalises nothing: a QK-norm is the layers
    before it.

    Latent attention's two widths: with ``value_head_dim`` v's heads are
    that wide and the top is (N, S, num_heads * value_head_dim); a FOURTH
    bottom (N, S, Ds) is one key part every head shares: each key head is
    its own Dh - Ds dims of k followed by it (k is then (N, S,
    num_kv_heads * (Dh - Ds))).

    ``rotary_shared`` (with the fourth bottom): the positions are on that
    shared part, rotated ONCE a token before the heads take it, and on the
    last Ds dims of every q head, the dims that meet it in the scores;
    k's own dims and the rest of q pass as they come (DeepSeek-V3's
    decoupled rotary part: GLM-4.7-Flash's 192 + 64). ``rotary_dims`` is
    then Ds or unset.

    ``rope_factor`` > 1: the frequencies are YaRN's blend of theta's and
    theta's divided by it (``models/transformer.rope_frequencies``, with
    ``rope_original_positions``, ``rope_beta_fast``, ``rope_beta_slow``);
    1, the default, is plain theta."""
    TYPE = "ATTENTION"

    def setup(self, bottom_shapes):
        ap = self.lp.attention_param
        shared = bottom_shapes[3][-1] if len(bottom_shapes) == 4 else 0
        if len(bottom_shapes) not in (3, 4) \
                or any(len(b) != 3 for b in bottom_shapes) \
                or any(b[:2] != bottom_shapes[0][:2] for b in bottom_shapes) \
                or (not shared and not ap.value_head_dim
                    and bottom_shapes[1] != bottom_shapes[2]):
            raise ValueError(f"{self.name}: ATTENTION takes q (N, S, D) and "
                             f"k, v of one (N, S, Dkv) shape (v of its own "
                             f"with value_head_dim; a fourth bottom "
                             f"(N, S, Ds) is a key part all heads share), "
                             f"got {bottom_shapes}")
        d, d_k, d_v = (b[-1] for b in bottom_shapes[:3])
        if ap.num_heads <= 0 or d % ap.num_heads or (d // ap.num_heads) % 2:
            raise ValueError(f"{self.name}: {ap.num_heads} heads do not "
                             f"split D={d} into even head sizes")
        d_head = d // ap.num_heads
        n_kv = ap.num_kv_heads or ap.num_heads
        self.v_head = ap.value_head_dim or d_head
        if ap.num_heads % n_kv or d_k != n_kv * (d_head - shared) \
                or d_v != n_kv * self.v_head or not 0 <= shared < d_head \
                or ap.value_head_dim < 0:
            latent = f" (of which {shared} shared) and values of " \
                f"{self.v_head}" if shared or ap.value_head_dim else ""
            raise ValueError(f"{self.name}: {n_kv} key-value heads of "
                             f"{d_head}{latent} need k, v of width "
                             f"{n_kv * (d_head - shared)}, "
                             f"{n_kv * self.v_head} and a whole number of "
                             f"query heads each; got {d_k}, {d_v} and "
                             f"{ap.num_heads} query heads")
        if ap.rotary_dims % 2 or not 0 <= ap.rotary_dims <= d_head:
            raise ValueError(f"{self.name}: rotary_dims {ap.rotary_dims} "
                             f"is not an even part of a head of {d_head}")
        if not ap.rope and (ap.rotary_dims or ap.rotary_shared):
            raise ValueError(f"{self.name}: rope false (no positions) and "
                             f"rotary_dims {ap.rotary_dims} / rotary_shared "
                             f"{ap.rotary_shared} contradict each other")
        if ap.rotary_shared and (not shared or shared % 2
                                 or ap.rotary_dims not in (0, shared)):
            raise ValueError(f"{self.name}: rotary_shared rotates the fourth "
                             f"bottom (an even width, got {shared}) and as "
                             f"many last dims of every q head; rotary_dims "
                             f"{ap.rotary_dims} is neither that nor unset")
        if ap.window < 0:
            raise ValueError(f"{self.name}: window {ap.window} is negative "
                             f"(0 = every earlier token)")
        if ap.scale < 0:
            raise ValueError(f"{self.name}: scale {ap.scale} is negative "
                             f"(0 = 1 / sqrt(Dh))")
        if ap.rope_factor < 1 or (ap.rope_factor > 1 and not (
                ap.rope and ap.rope_original_positions > 0
                and ap.rope_beta_fast > ap.rope_beta_slow > 0)):
            raise ValueError(f"{self.name}: rope_factor {ap.rope_factor} "
                             f"(1 = plain theta) needs rope, original "
                             f"positions and rope_beta_fast > "
                             f"rope_beta_slow > 0")
        return [tuple(bottom_shapes[0][:2]) + (ap.num_heads * self.v_head,)]

    def apply(self, params, bottoms, ctx):
        from ..models.transformer import Yarn, rope_attention
        ap = self.lp.attention_param
        theta = ap.rope_theta if ap.rope_factor == 1.0 else Yarn(
            ap.rope_theta, ap.rope_factor, ap.rope_original_positions,
            ap.rope_beta_fast, ap.rope_beta_slow)
        return [rope_attention(*bottoms[:3], n_heads=ap.num_heads,
                               rope_theta=theta,
                               n_kv_heads=ap.num_kv_heads,
                               rotary_dims=ap.rotary_dims,
                               window=ap.window, rope=ap.rope,
                               k_shared=bottoms[3] if len(bottoms) == 4
                               else None, scale=ap.scale or None,
                               rotary_shared=ap.rotary_shared)]

    def kernel_route(self, bottom_shapes, itemsize):
        from ..ops.pallas_kernels import attention_route
        ap = self.lp.attention_param
        _, s, d = bottom_shapes[0]
        d_head = d // ap.num_heads
        arm, note = attention_route(s, s, d_head, itemsize, window=ap.window,
                                    dv=ap.value_head_dim or None)
        if arm == "pallas_flash":
            # the tiles each flash kernel runs with and the live / visited
            # programs of its grid: stats.yaml carries them
            arm, note = f"{arm} ({note})", ""
        if ap.num_kv_heads and ap.num_kv_heads != ap.num_heads:
            # which way the key-value heads reach their query heads
            arm += (f"; {ap.num_kv_heads} kv heads repeated x"
                    f"{ap.num_heads // ap.num_kv_heads}")
        if arm.startswith("dense") and 0 < ap.window < s:
            arm += f"; window {ap.window} as a dense mask"
        if not ap.rope:
            arm += "; no positions"
        if arm.startswith("dense") and ap.value_head_dim:
            arm += f"; d {d_head}/{ap.value_head_dim}"
        if len(bottom_shapes) == 4:
            # latent attention: the one key part all heads share
            arm += ("; k_pe rotated once, joined x" if ap.rotary_shared
                    else "; k_pe repeated x") \
                + f"{ap.num_kv_heads or ap.num_heads}"
        if ap.rope_factor > 1:
            arm += f"; yarn x{ap.rope_factor:g}"
        return "attention", arm, note


class MoELayer(Layer):
    """Bottom (N, S, D) -> top-k token-choice experts, dropless. The router
    scores ``num_experts``; this layer holds ``num_held`` of them from
    ``held_first`` on (unset: all) and adds nothing for a token whose
    expert is elsewhere: one rank's share of an expert-parallel layer, the
    shares summing to the whole. Blobs: gate and up (G, F, D), down
    (G, D, F) over the G held experts, and
    - ``router_hidden`` 0: router (E, D) first. Tops: the output; the
      load-balancing and router z losses (scalars, weighted by the
      prototxt's ``loss_weight``);
    - ``router_hidden`` > 0, ``score_func`` "sigmoid" or a SECOND BOTTOM:
      no router here; the second bottom is the gates (N, S, E) of the
      MOE_ROUTER layer before it (each token's ``top_k`` weights, zero
      elsewhere). Tops: the output.
    Then optionally the step's own routing as up to four more scalars:
    assignments at the fullest HELD expert over their mean, assignments to
    a held expert that no expert computed (0: nothing is dropped), the
    share of all assignments that fell on a held expert, and the share of
    the held experts' live rows' gate pre-activations that are <= 0 (what a
    ReLU gate zeroes). An expert is down(act(gate x) * (up x)),
    ``activation`` "silu" (the default) or "relu"; with ``activation``
    "relu2" it is UNGATED, down(relu(up x)^2): the layer then has no gate
    blob (two stacks, up and down), and the fourth scalar counts the up
    pre-activations <= 0 (what the squared ReLU zeroes)."""
    TYPE = "MOE"

    def setup(self, bottom_shapes):
        mp = self.lp.moe_param
        n, s, d = bottom_shapes[0]
        if not 0 < mp.top_k <= mp.num_experts or mp.expert_width <= 0:
            raise ValueError(f"{self.name}: moe_param needs num_experts >= "
                             f"top_k > 0 and expert_width")
        from ..models.moe import EXPERT_ACTS, UNGATED_ACT
        if mp.activation not in EXPERT_ACTS:
            raise ValueError(
                f"{self.name}: activation {mp.activation!r} is none of "
                f"{EXPERT_ACTS} (silu, relu: a gated unit of three stacks; "
                f"relu2: the ungated squared ReLU of two)")
        self.ungated = mp.activation == UNGATED_ACT
        self.gated = mp.router_hidden > 0 or mp.score_func == "sigmoid" \
            or len(bottom_shapes) == 2
        self.n_fixed = 1 if self.gated else 3
        if not self.n_fixed <= len(self.lp.top) <= self.n_fixed + 4:
            raise ValueError(
                f"{self.name}: MOE has {self.n_fixed} to {self.n_fixed + 4} "
                f"tops (output, " + ("" if self.gated else "balance loss, "
                                     "z loss, ")
                + f"[load max/mean[, dropped[, held share[, gate zero "
                f"share]]]]), got {len(self.lp.top)}")
        self.gate_zeros = len(self.lp.top) == self.n_fixed + 4
        e, f = mp.num_experts, mp.expert_width
        self.held = mp.num_held or e
        if mp.held_first < 0 or mp.held_first + self.held > e:
            raise ValueError(f"{self.name}: experts {mp.held_first}.."
                             f"{mp.held_first + self.held - 1} are not among "
                             f"the {e} the router scores")
        want = [(n, s, d), (n, s, e)] if self.gated else [(n, s, d)]
        if [tuple(b) for b in bottom_shapes] != want:
            raise ValueError(f"{self.name}: MOE with router_hidden "
                             f"{mp.router_hidden}, score_func "
                             f"{mp.score_func!r} takes bottoms {want}, got "
                             f"{bottom_shapes}")
        g = self.held
        self.params = [] if self.gated else [
            self._param("router", (e, d), mp.weight_filler, 0)]
        at = len(self.params)
        stacks = ([] if self.ungated else [("gate", (g, f, d))]) \
            + [("up", (g, f, d)), ("down", (g, d, f))]
        self.params += [
            self._param(name, shape, mp.weight_filler, at + i)
            for i, (name, shape) in enumerate(stacks)]
        return [(n, s, d)] + [()] * (len(self.lp.top) - 1)

    def default_loss_weight(self) -> float:
        return 0.0

    def apply(self, params, bottoms, ctx):
        from ..models.moe import moe_dropless, moe_gated
        mp = self.lp.moe_param
        p = _tap_all(ctx, self.name, params)
        x = bottoms[0]
        n, s, d = x.shape
        flat = x.reshape(n * s, d)
        how = (mp.top_k, mp.held_first, mp.activation, self.gate_zeros)
        if self.gated:
            y, sizes = moe_gated(
                flat, bottoms[1].reshape(n * s, mp.num_experts),
                p.get("gate"), p["up"], p["down"], *how)
            losses = []
        else:
            y, lb, z, sizes = moe_dropless(
                flat, p["router"], p.get("gate"), p["up"], p["down"], *how)
            losses = [lb, z]
        y, *zero_share = y if self.gate_zeros else (y,)
        tops = [y.reshape(n, s, d)] + losses
        sizes = lax.stop_gradient(sizes).astype(jnp.float32)
        total = float(n * s * mp.top_k)
        if self.held == mp.num_experts:
            stats = [jnp.max(sizes) * mp.num_experts / total,
                     total - jnp.sum(sizes), jnp.sum(sizes) / total]
        else:
            here = sizes[mp.held_first:mp.held_first + self.held]
            stats = [jnp.max(here) * self.held
                     / jnp.maximum(jnp.sum(here), 1.0),
                     jnp.zeros((), jnp.float32), jnp.sum(here) / total]
        return tops + (stats + zero_share)[:len(self.lp.top) - self.n_fixed]

    def _held_plan(self, bottom_shapes):
        from ..models.moe import held_rows_plan
        n, s, _ = bottom_shapes[0]
        mp = self.lp.moe_param
        return held_rows_plan(n * s * mp.top_k, self.held, mp.num_experts)

    def kernel_route(self, bottom_shapes, itemsize):
        from ..models.moe import GROUPED_MATMUL
        arm = GROUPED_MATMUL
        held = self._held_plan(bottom_shapes)
        if held:
            # the row work runs in chunks of the sorted assignments, as many
            # trips as the live rows need (stats.yaml: held_chunk_trips)
            arm += f"; held rows: chunks of {held[0]} of {held[2]}"
        if self.lp.moe_param.activation != "silu":
            arm += f"; act={self.lp.moe_param.activation}"
        if self.ungated:
            arm += "; ungated"
        return "grouped_matmul", arm, "sorted by expert, dropless"

    def stats_sections(self, bottom_shapes, itemsize):
        mp = self.lp.moe_param
        return {"expert_share": {"held_first": mp.held_first,
                                 "num_held": self.held,
                                 "router_num_experts": mp.num_experts}}

    def display_counters(self, bottom_shapes):
        # held rows in chunks and the held share displayed: the share says
        # how many trips the step's held arm made
        from ..models.moe import held_share_counts
        held = self._held_plan(bottom_shapes)
        if not held or len(self.lp.top) < self.n_fixed + 3:
            return {}
        return {self.lp.top[self.n_fixed + 2]: held_share_counts(*held)}


class MoERouterLayer(Layer):
    """A router that is a layer of its own, so that its time has a scope
    and it may score another blob than the experts compute on. Three forms,
    by ``moe_param``, the first two keeping a selection bias they balance
    themselves:

    - ``router_hidden`` > 0 (``models/moe.mlp_router``, top-1): bottoms the
      normed hidden state (N, S, D) and, in every layer but the first, the
      router state (N, S, R) of the layer before. Tops: this layer's router
      state (N, S, R) f32; the gates; the bias's next value. Blobs: down
      (R, D), mix (R,) (only with the second bottom), w1, w2 (R, R), w3
      (E, R), bias (E,).
    - ``score_func`` "sigmoid" (``models/moe.sigmoid_router``, any top-k):
      one bottom, the normed hidden state. Tops: the gates; the bias's next
      value; optionally that value's largest magnitude, a scalar a display
      carries. Blobs: w (E, D), bias (E,).
    - ``score_func`` "softmax" and no ``router_hidden``
      (``models/moe.softmax_router``, any top-k): one bottom, the state the
      router scores. Tops: the gates; the load-balancing and router z
      losses (scalars, weighted by the prototxt's ``loss_weight``). Blob:
      w (E, D). No bias, nothing layer-updated.

    The gates (N, S, E) f32 hold each token's chosen experts' weights and
    zero elsewhere (what the MOE layer after it takes). ``bias`` is a
    LAYER-UPDATED leaf (``updates``): the step takes its next value from
    the top after the gates and no gradient, optimizer, decay or clip
    touches it; its rule's step is ``bias_update_rate``."""
    TYPE = "MOE_ROUTER"

    def setup(self, bottom_shapes):
        mp = self.lp.moe_param
        n, s, d = bottom_shapes[0]
        e, r = mp.num_experts, mp.router_hidden
        zero = FillerParameter(type="constant", value=0.0)
        self.sigmoid = mp.score_func == "sigmoid"
        self.plain = mp.score_func == "softmax" and not r
        if mp.score_func not in ("softmax", "sigmoid"):
            raise ValueError(f"{self.name}: score_func {mp.score_func!r} is "
                             f"neither softmax nor sigmoid")
        if self.plain:
            if not 0 < mp.top_k <= e or len(bottom_shapes) != 1 \
                    or len(self.lp.top) != 3:
                raise ValueError(
                    f"{self.name}: a softmax MOE_ROUTER needs num_experts "
                    f">= top_k > 0, takes (N, S, D) and has 3 tops (gates, "
                    f"balance loss, z loss); got {bottom_shapes}, "
                    f"{len(self.lp.top)} tops")
            self.params = [self._param("w", (e, d), mp.weight_filler, 0)]
            return [(n, s, e), (), ()]
        if self.sigmoid:
            if not 0 < mp.top_k <= e or r or len(bottom_shapes) != 1 \
                    or len(self.lp.top) not in (2, 3):
                raise ValueError(
                    f"{self.name}: a sigmoid MOE_ROUTER needs num_experts >= "
                    f"top_k > 0 and no router_hidden, takes (N, S, D) and "
                    f"has 2 or 3 tops (gates, the bias's next value[, its "
                    f"largest magnitude]); got {bottom_shapes}, "
                    f"{len(self.lp.top)} tops")
            shapes = [("w", (e, d), mp.weight_filler), ("bias", (e,), zero)]
            tops = [(n, s, e), (e,)] + [()] * (len(self.lp.top) - 2)
        else:
            if e <= 0 or r <= 0 or mp.top_k != 1:
                raise ValueError(f"{self.name}: an MLP MOE_ROUTER needs "
                                 f"num_experts, router_hidden and top_k 1")
            self.mixes = len(bottom_shapes) == 2
            if len(bottom_shapes) > 2 or len(self.lp.top) != 3 or (
                    self.mixes and tuple(bottom_shapes[1]) != (n, s, r)):
                raise ValueError(
                    f"{self.name}: MOE_ROUTER takes (N, S, D) and "
                    f"optionally (N, S, {r}), and has 3 tops; got "
                    f"{bottom_shapes}, {len(self.lp.top)} tops")
            shapes = [("down", (r, d), mp.weight_filler)] \
                + ([("mix", (r,), zero)] if self.mixes else []) \
                + [("w1", (r, r), mp.weight_filler),
                   ("w2", (r, r), mp.weight_filler),
                   ("w3", (e, r), mp.weight_filler), ("bias", (e,), zero)]
            tops = [(n, s, r), (n, s, e), (e,)]
        self.updates = {"bias": tops.index((e,))}   # param -> the top it is
        self.params = [self._param(name, shape, filler, i)
                       for i, (name, shape, filler) in enumerate(shapes)]
        self.params[-1] = dataclasses.replace(self.params[-1],
                                              layer_updated=True)
        return tops

    def default_loss_weight(self) -> float:
        return 0.0

    def apply(self, params, bottoms, ctx):
        from ..models.moe import mlp_router, sigmoid_router, softmax_router
        mp = self.lp.moe_param
        p = _tap_all(ctx, self.name, params)
        n, s, d = bottoms[0].shape
        flat = bottoms[0].reshape(n * s, d)
        if self.plain:
            gates, lb, z = softmax_router(flat, p["w"], mp.top_k)
            return [gates.reshape(n, s, -1), lb, z]
        if self.sigmoid:
            gates, bias = sigmoid_router(
                flat, p["w"], p["bias"], mp.top_k, mp.route_scale,
                mp.bias_update_rate)
            return [gates.reshape(n, s, -1), bias,
                    jnp.max(jnp.abs(bias))][:len(self.lp.top)]
        r, gates, bias = mlp_router(
            flat, bottoms[1].reshape(n * s, -1) if self.mixes else None,
            p["down"], p.get("mix"), p["w1"], p["w2"], p["w3"], p["bias"],
            mp.bias_update_rate)
        return [r.reshape(n, s, -1), gates.reshape(n, s, -1), bias]


# CCA (compressed convolutional attention, arXiv:2510.04476): what happens
# to q and k in the latent between their projections and the attention.

def _shift_tokens(x, by: int = 1):
    """x (N, S, ...) -> x moved ``by`` positions later, zeros in front
    (``by`` < 0: earlier, zeros behind)."""
    if by == 0:
        return x
    rest = [(0, 0)] * (x.ndim - 2)
    if by < 0:
        return jnp.pad(x[:, -by:], [(0, 0), (0, -by)] + rest)
    return jnp.pad(x[:, :x.shape[1] - by], [(0, 0), (by, 0)] + rest)


class TokenShiftLayer(Layer):
    """(N, S, ...) -> the same with every token's row replaced by the row
    ``offset`` positions on, top(t) = bottom(t + offset), zeros where that
    falls outside the sequence: -1, the default, is the token before's
    (zeros at position 0); 1 the next token's (zeros at the last). A second
    top marks the rows that are real: (N, S) f32, 1 where t + offset lies
    inside the sequence and 0 on the filled ones — what WEIGHTED_MEAN_LOSS
    takes to leave them out of a mean."""
    TYPE = "TOKEN_SHIFT"

    def setup(self, bottom_shapes):
        self.offset = self.lp.token_shift_param.offset
        n, s = bottom_shapes[0][:2]
        if len(self.lp.top) not in (1, 2) or not 0 < abs(self.offset) < s:
            raise ValueError(f"{self.name}: TOKEN_SHIFT has 1 or 2 tops and "
                             f"a non-zero offset inside the sequence of {s}; "
                             f"got {len(self.lp.top)} tops, offset "
                             f"{self.offset}")
        return [bottom_shapes[0], (n, s)][:len(self.lp.top)]

    def apply(self, params, bottoms, ctx):
        x = bottoms[0]
        tops = [_shift_tokens(x, -self.offset)]
        if len(self.lp.top) == 2:
            tops.append(_shift_tokens(jnp.ones(x.shape[:2], jnp.float32),
                                      -self.offset))
        return tops


class _CCALayer(Layer):
    def _heads(self, bottom_shapes):
        cp = self.lp.cca_param
        (n, s, lq), (_, _, lk) = bottom_shapes[:2]
        if cp.num_heads % cp.num_kv_heads or lq % cp.num_heads \
                or lk * cp.num_heads != lq * cp.num_kv_heads \
                or any(tuple(b) != tuple(bottom_shapes[i % 2])
                       for i, b in enumerate(bottom_shapes)):
            raise ValueError(
                f"{self.name}: {self.TYPE} takes q (N, S, H d) and k "
                f"(N, S, G d) with H = {cp.num_heads}, G = "
                f"{cp.num_kv_heads}; got {bottom_shapes}")
        self.h, self.g, self.d = cp.num_heads, cp.num_kv_heads, \
            lq // cp.num_heads
        return [tuple(bottom_shapes[0]), tuple(bottom_shapes[1])]


class CCAConvLayer(_CCALayer):
    """Bottoms q~ (N, S, H d), k~ (N, S, G d) -> q_c, k_c of the same
    shapes: the two causal convolutions over the sequence of c = [q~, k~].
    Depthwise, ``time0`` taps: c1_t = sum_j dw[j] * c_{t-j} + dw_b per
    channel. Then grouped, ``time1`` taps, one group a head (H + G groups
    of d channels): c2_t[g] = sum_j gw[j, g] c1_{t-j}[g] + gw_b[g], gw[j, g]
    a (d, d) matrix (out, in). Blobs: dw (time0, C), dw_b (C), gw
    (time1, H + G, d, d), gw_b (C)."""
    TYPE = "CCA_CONV"

    def setup(self, bottom_shapes):
        tops = self._heads(bottom_shapes)
        cp = self.lp.cca_param
        c, groups = (self.h + self.g) * self.d, self.h + self.g
        zero = FillerParameter(type="constant", value=0.0)
        self.params = [
            self._param("dw", (cp.time0, c), cp.weight_filler, 0),
            self._param("dw_b", (c,), zero, 1),
            self._param("gw", (cp.time1, groups, self.d, self.d),
                        cp.weight_filler, 2),
            self._param("gw_b", (c,), zero, 3)]
        return tops

    def apply(self, params, bottoms, ctx):
        from ..config import matmul_precision, policy
        p = _tap_all(ctx, self.name, params)
        cp = self.lp.cca_param
        q, k = bottoms
        n, s, lq = q.shape
        c = jnp.concatenate([q, k], axis=-1)
        dt = policy().compute_dtype
        c32 = c.astype(jnp.float32)
        c1 = sum(p["dw"][j] * _shift_tokens(c32, j)
                 for j in range(cp.time0)) + p["dw_b"]
        c1 = c1.astype(dt).reshape(n, s, self.h + self.g, self.d)
        # the taps side by side on the contracted axis: one (time1 d) x d
        # product a group
        taps = jnp.concatenate([_shift_tokens(c1, j)
                                for j in range(cp.time1)], axis=-1)
        w = jnp.concatenate([p["gw"][j] for j in range(cp.time1)],
                            axis=-1).astype(dt)            # (G, d, time1 d)
        c2 = jnp.einsum("nsgi,goi->nsgo", taps, w,
                        precision=matmul_precision(),
                        preferred_element_type=jnp.float32)
        c2 = (c2.reshape(n, s, -1) + p["gw_b"]).astype(c.dtype)
        return [c2[..., :lq], c2[..., lq:]]


class CCAQKMeanLayer(_CCALayer):
    """Bottoms q~, k~, q_c, k_c -> q, k: the q-k mean joins the convolved
    latent. Per query head h of key-value group h // (H / G): m_q[h] =
    (q~[h] + k~[h // (H / G)]) / 2; per group, m_k[g] = the mean of its
    query heads' m_q. q = q_c + m_q, k = k_c + m_k."""
    TYPE = "CCA_QKMEAN"

    def setup(self, bottom_shapes):
        if len(bottom_shapes) != 4:
            raise ValueError(f"{self.name}: CCA_QKMEAN takes q~, k~, q_c, "
                             f"k_c")
        return self._heads(bottom_shapes)

    def apply(self, params, bottoms, ctx):
        q0, k0, qc, kc = bottoms
        n, s, _ = q0.shape
        per = self.h // self.g
        q4 = q0.astype(jnp.float32).reshape(n, s, self.g, per, self.d)
        k4 = k0.astype(jnp.float32).reshape(n, s, self.g, 1, self.d)
        m_q = (q4 + k4) / 2
        m_k = jnp.mean(m_q, axis=3)
        return [(qc.astype(jnp.float32) + m_q.reshape(q0.shape))
                .astype(qc.dtype),
                (kc.astype(jnp.float32) + m_k.reshape(k0.shape))
                .astype(kc.dtype)]


class CCAQKNormLayer(_CCALayer):
    """Bottoms q, k -> each head over its d dims divided by its L2 norm and
    times sqrt(d) (x * rsqrt(mean(x^2) + eps), statistics in f32); k's
    heads also times ``tau`` (G,), a learned temperature per key-value
    head, 1 at start."""
    TYPE = "CCA_QKNORM"

    def setup(self, bottom_shapes):
        tops = self._heads(bottom_shapes)
        ones = FillerParameter(type="constant", value=1.0)
        self.params = [self._param("tau", (self.g,), ones, 0)]
        return tops

    def apply(self, params, bottoms, ctx):
        tau = _tap_all(ctx, self.name, params)["tau"]
        eps = self.lp.cca_param.eps

        def unit(x, heads, scale=None):
            x32 = x.astype(jnp.float32).reshape(x.shape[:2] + (heads, self.d))
            y = x32 * lax.rsqrt(jnp.mean(x32 * x32, -1, keepdims=True) + eps)
            if scale is not None:
                y = y * scale.astype(jnp.float32)[:, None]
            return y.reshape(x.shape).astype(x.dtype)

        return [unit(bottoms[0], self.h), unit(bottoms[1], self.g, tau)]


# The recurrent-state layers (KDA, arXiv:2510.26692): what happens to q, k
# and v between their projections and the scan, the decay's form, the scan.

class ShortConvLayer(Layer):
    """(N, S, C) -> the same shape: a causal depthwise convolution over the
    sequence, ``kernel_size`` taps a channel, y_t = sum_j w[j] * x_{t-j}
    (zeros before a sequence's start: no state crosses it; nothing resets
    inside a sequence), then SiLU. Taps and sum in f32. Blob: w
    (kernel_size, C) and, with ``bias_term`` (Mamba-2's
    ``mamba_conv_bias``), b (C,) added before the SiLU; without it, the
    default, the layer and its lowered step are what every net had. A
    sibling of CCA_CONV, which is that layer's two-bottom form with biases
    and a grouped second convolution."""
    TYPE = "SHORT_CONV"

    def setup(self, bottom_shapes):
        kp = self.lp.kda_param
        if len(bottom_shapes) != 1 or len(bottom_shapes[0]) != 3 \
                or kp.kernel_size <= 0:
            raise ValueError(f"{self.name}: SHORT_CONV takes (N, S, C) and "
                             f"a kernel_size > 0, got {bottom_shapes}")
        self.params = [self._param(
            "w", (kp.kernel_size, bottom_shapes[0][-1]), kp.weight_filler,
            0)]
        if kp.bias_term:
            self.params.append(self._param(
                "b", (bottom_shapes[0][-1],), kp.bias_filler, 1))
        return [bottom_shapes[0]]

    def apply(self, params, bottoms, ctx):
        @jax.checkpoint         # a gradient keeps x and w, no f32 copy
        def conv(x, w, *b):
            taps, s = w.shape[0], x.shape[1]
            w = w.astype(jnp.float32)
            # the zeros before the start once, in x's type; tap j reads the
            # window that ends j tokens back
            early = jnp.pad(x, [(0, 0), (taps - 1, 0), (0, 0)])
            y = sum(w[j] * early[:, taps - 1 - j:taps - 1 - j + s]
                    .astype(jnp.float32) for j in range(taps))
            if b:
                y = y + b[0].astype(jnp.float32)
            return jax.nn.silu(y).astype(x.dtype)

        p = _tap_all(ctx, self.name, params)
        return [conv(bottoms[0], p["w"], *([p["b"]] if "b" in p else []))]


def _split_heads(name, what, shape, heads):
    if len(shape) != 3 or heads <= 0 or shape[-1] % heads:
        raise ValueError(f"{name}: {what} takes (N, S, H d) with H = "
                         f"{heads} heads, got {shape}")
    return shape[-1] // heads


class L2NormLayer(Layer):
    """(N, S, H d) -> each of ``num_heads`` heads divided by its L2 norm,
    x * rsqrt(sum x^2 + eps) over its own d dims, in f32. No blob."""
    TYPE = "L2_NORM"

    def setup(self, bottom_shapes):
        _split_heads(self.name, self.TYPE, bottom_shapes[0],
                     self.lp.kda_param.num_heads)
        return [bottom_shapes[0]]

    def apply(self, params, bottoms, ctx):
        kp = self.lp.kda_param

        @jax.checkpoint         # a gradient keeps x, no f32 copy
        def unit(x):
            x32 = x.astype(jnp.float32).reshape(
                x.shape[:2] + (kp.num_heads, -1))
            y = x32 * lax.rsqrt(jnp.sum(x32 * x32, -1, keepdims=True)
                                + kp.eps)
            return y.reshape(x.shape).astype(x.dtype)

        return [unit(bottoms[0])]


class KDADecayLayer(Layer):
    """(N, S, H d) -> the log-decay g = -exp(A_log) softplus(x + dt_bias),
    <= 0, one a head AND channel (d = 1, a bottom of (N, S, H): ONE a
    head, Gated DeltaNet's), f32 whatever the compute policy (its
    cumulative sums are the scan's exponents). Blobs: A_log (H,), dt_bias
    (H d). A second top, optional: the mean of exp(g) over the step, a
    scalar a display carries (does the state forget?). A third and a
    fourth, optional (Mamba-2's SSD_SCAN, whose write is dt x B^T, takes the
    step itself as well): dt = softplus(x + dt_bias) in the bottom's shape,
    f32, and its mean over the step, a scalar. With one or two tops the
    layer and its lowered step are what every net had."""
    TYPE = "KDA_DECAY"

    def setup(self, bottom_shapes):
        kp = self.lp.kda_param
        _split_heads(self.name, self.TYPE, bottom_shapes[0], kp.num_heads)
        if not 1 <= len(self.lp.top) <= 4:
            raise ValueError(f"{self.name}: KDA_DECAY has 1 to 4 tops (g[, "
                             f"the mean decay[, dt[, the mean dt]]])")
        self.params = [
            self._param("A_log", (kp.num_heads,), FillerParameter(
                type="log_of_uniform", min=kp.a_min, max=kp.a_max), 0),
            self._param("dt_bias", (bottom_shapes[0][-1],), FillerParameter(
                type="inv_softplus_log_uniform", min=kp.dt_min,
                max=kp.dt_max), 1)]
        return [bottom_shapes[0], (), bottom_shapes[0], ()][:len(self.lp.top)]

    def default_loss_weight(self) -> float:
        return 0.0

    def apply(self, params, bottoms, ctx):
        p = _tap_all(ctx, self.name, params)
        heads = self.lp.kda_param.num_heads
        with_step = len(self.lp.top) > 2

        @jax.checkpoint         # a gradient keeps x and the two blobs
        def decay(x, a_log, dt_bias):
            x = x.astype(jnp.float32)
            step = jax.nn.softplus(x + dt_bias.astype(jnp.float32))
            rate = jnp.exp(a_log.astype(jnp.float32))
            g = -(step.reshape(x.shape[:2] + (heads, -1))
                  * rate[:, None]).reshape(x.shape)
            return (g, step) if with_step else g

        out = decay(bottoms[0], p["A_log"], p["dt_bias"])
        g, *step = out if with_step else (out,)
        tops = [g, jnp.mean(jnp.exp(lax.stop_gradient(g)))]
        tops += [t for s in step for t in (s, jnp.mean(lax.stop_gradient(s)))]
        return tops[:len(self.lp.top)]


class KDAScanLayer(Layer):
    """The recurrent-state layer. Bottoms q, k (N, S, H d_k), v
    (N, S, H d_v), g (N, S, H d_k) (a decay a channel) or (N, S, H) (one a
    head), beta (N, S, H) -> o (N, S, H d_v): per head a state (d_k, d_v),
    zero at a sequence's start,
    S_t = (I - beta k k^T) Diag(exp(g)) S_{t-1} + beta k v^T,
    o_t = S_t^T q_t d_k^-0.5 (``ops/kda.kda_scan``: chunks of the sequence,
    f32 state; which arm runs follows from g's shape, the two widths and the
    backend, and ``Net`` logs it). beta may pass 1 (up to 2: a write with a
    negative eigenvalue along k). Nothing resets the state inside a
    sequence. A second top, optional: the share of the step's (token, head)
    writes with beta > 1, a scalar a display carries."""
    TYPE = "KDA_SCAN"

    def setup(self, bottom_shapes):
        h = self.lp.kda_param.num_heads
        if len(bottom_shapes) != 5 or len(self.lp.top) not in (1, 2):
            raise ValueError(f"{self.name}: KDA_SCAN takes q, k, v, g, beta "
                             f"and has 1 or 2 tops (o[, the share of beta "
                             f"> 1])")
        q, k, v, g, beta = (tuple(b) for b in bottom_shapes)
        d_k = _split_heads(self.name, self.TYPE, q, h)
        _split_heads(self.name, self.TYPE, v, h)
        self.per_head = g == q[:2] + (h,) and d_k != 1
        if q != k or not (g == q or self.per_head) or v[:2] != q[:2] \
                or beta != q[:2] + (h,):
            raise ValueError(
                f"{self.name}: KDA_SCAN takes q, k of one (N, S, H d_k) "
                f"shape, g of that shape or (N, S, {h}), v (N, S, H d_v) "
                f"and beta (N, S, {h}); got {bottom_shapes}")
        return [v] + [()] * (len(self.lp.top) - 1)

    def apply(self, params, bottoms, ctx):
        from ..ops.kda import kda_scan
        h = self.lp.kda_param.num_heads
        q, k, v, g, beta = bottoms
        heads = lambda x: x.reshape(x.shape[:2] + (h, -1))
        o = kda_scan(heads(q), heads(k), heads(v),
                     g if self.per_head else heads(g), beta)
        tops = [o.reshape(v.shape).astype(v.dtype)]
        if len(self.lp.top) == 2:
            tops.append(jnp.mean(
                (lax.stop_gradient(beta) > 1).astype(jnp.float32)))
        return tops

    def _widths(self, bottom_shapes):       # heads, a head's d_k and d_v
        h = self.lp.kda_param.num_heads
        return h, bottom_shapes[0][2] // h, bottom_shapes[2][2] // h

    def kernel_route(self, bottom_shapes, itemsize):
        from ..ops.kda import kda_route
        h, d_k, d_v = self._widths(bottom_shapes)
        # the route's note names the arm and, where it is not pallas, why
        return "kda", kda_route(bottom_shapes[0][1], d_k, d_v, h, itemsize,
                                per_head=self.per_head)[1], ""

    def stats_sections(self, bottom_shapes, itemsize):
        from ..ops.kda import kda_chunk, state_bytes
        n, s, _ = bottom_shapes[0]
        h, d_k, d_v = self._widths(bottom_shapes)
        chunk = kda_chunk(s)
        facts = {"heads": h, "d_k": d_k, "d_v": d_v, "chunk": chunk or 1,
                 "chunks": s // (chunk or 1),
                 "saved_state_bytes": state_bytes(
                     n, s, h, d_k, d_v, self.per_head, itemsize)
                 if chunk else 0}
        if self.per_head:           # one decay a head (a channel: unsaid)
            facts["decay"] = "head"
        return {"recurrent_state": facts}


class SSDScanLayer(Layer):
    """Mamba-2's selective scan. Bottoms x (N, S, H P), dt and a = dt A
    (N, S, H) f32 (KDA_DECAY's third and first tops), B and C
    (N, S, G N_state): ``num_groups`` (G; 1, the default, is what every net
    had) groups side by side, head h of the ``num_heads`` reading group
    h // (H / G) -> y (N, S, H P): per head a state (P, N_state), zero at a
    sequence's start,
    H_t = exp(a_t) H_{t-1} + dt_t x_t B_t^T, y_t = H_t C_t + D x_t
    (``ops/ssd.ssd_scan``: chunks of the sequence, one C B^T grid a chunk
    for a group's heads, f32 state; which arm runs follows from the shape
    and the backend, and ``Net`` logs it). Blob: D (H,), the skip, filled
    with ones. Nothing resets the state inside a sequence."""
    TYPE = "SSD_SCAN"

    def setup(self, bottom_shapes):
        h, g = self.lp.kda_param.num_heads, self.lp.kda_param.num_groups
        if len(bottom_shapes) != 5:
            raise ValueError(f"{self.name}: SSD_SCAN takes x, dt, a, B, C")
        x, dt, a, b, c = (tuple(t) for t in bottom_shapes)
        _split_heads(self.name, self.TYPE, x, h)
        if dt != x[:2] + (h,) or a != dt or b != c or len(b) != 3 \
                or b[:2] != x[:2] or g < 1 or h % g or b[2] % g:
            raise ValueError(
                f"{self.name}: SSD_SCAN takes x (N, S, H P), dt and a "
                f"(N, S, {h}) and B, C of one (N, S, G N_state) shape, "
                f"num_groups {g} dividing the heads and B's width; got "
                f"{bottom_shapes}")
        self.params = [self._param(
            "D", (h,), FillerParameter(type="constant", value=1.0), 0)]
        return [x]

    def apply(self, params, bottoms, ctx):
        from ..ops.ssd import ssd_scan
        x, dt, a, b, c = bottoms
        kp = self.lp.kda_param
        heads = x.reshape(x.shape[:2] + (kp.num_heads, -1))
        if kp.num_groups > 1:
            b, c = (t.reshape(t.shape[:2] + (kp.num_groups, -1))
                    for t in (b, c))
        y = ssd_scan(heads, dt, a, b, c,
                     _tap_all(ctx, self.name, params)["D"])
        return [y.reshape(x.shape).astype(x.dtype)]

    def _widths(self, bottom_shapes):   # heads, P, N_state, groups
        kp = self.lp.kda_param
        return (kp.num_heads, bottom_shapes[0][2] // kp.num_heads,
                bottom_shapes[3][2] // kp.num_groups, kp.num_groups)

    def kernel_route(self, bottom_shapes, itemsize):
        from ..ops.ssd import ssd_route
        h, p, n_state, g = self._widths(bottom_shapes)
        # the note names the arm and, chunked on a shape the kernels
        # refuse, the reason; with more than one group, ``groups=<G>``
        return "ssd_scan", ssd_route(bottom_shapes[0][1], h, p, n_state,
                                     itemsize, g)[1], ""

    def stats_sections(self, bottom_shapes, itemsize):
        from ..ops import ssd
        # a state (P, N_state) a head, B and C shared by a group's heads
        n, s, _ = bottom_shapes[0]
        h, p, n_state, g = self._widths(bottom_shapes)
        chunk = ssd.scan_chunk(s, h, p, n_state, g)
        facts = {
            "heads": h, "d_k": n_state, "d_v": p, "chunk": chunk or 1,
            "chunks": s // (chunk or 1),
            "saved_state_bytes": ssd.state_bytes(n, s, h, p, n_state, g),
            "decay": "head"}
        if g > 1:                       # one group: unsaid, as it was
            facts["groups"] = g
        return {"recurrent_state": facts}


class SiLUGateLayer(Layer):
    """Bottoms gate, up of one shape -> silu(gate) * up: the gate of a dense
    gated FFN whose three projections are per-token INNER_PRODUCTs."""
    TYPE = "SILU_GATE"

    def setup(self, bottom_shapes):
        if len(bottom_shapes) != 2 or bottom_shapes[0] != bottom_shapes[1]:
            raise ValueError(f"{self.name}: SILU_GATE takes gate and up of "
                             f"one shape, got {bottom_shapes}")
        return [bottom_shapes[0]]

    def apply(self, params, bottoms, ctx):
        return [jax.nn.silu(bottoms[0]) * bottoms[1]]


# The residual stream of hyper-connections (``ops/hyper.py``): n residual
# states of the hidden size side by side, (N, S, n C).

class _HyperLayer(Layer):
    """What the five stream layers share: ``hyper_param.streams`` and the
    check that a stream blob (N, S, n C) is n whole hidden states."""

    def _n(self) -> int:
        n = self.lp.hyper_param.streams
        if n < 1:
            raise ValueError(f"{self.name}: hyper_param.streams {n} < 1")
        return n

    def _stream(self, shape) -> Tuple[int, int, int, int]:
        """(N, S, n, C) of a stream blob's shape."""
        n = self._n()
        if len(shape) != 3 or shape[-1] % n:
            raise ValueError(f"{self.name}: a stream is (N, S, n C) with n "
                             f"= {n}, got {tuple(shape)}")
        return shape[0], shape[1], n, shape[-1] // n

    def _coef(self, stream_shape, coef_shape):
        b, s, n, _ = self._stream(stream_shape)
        if tuple(coef_shape) != (b, s, n * (n + 2)):
            raise ValueError(f"{self.name}: the coefficients of an HC_MAP "
                             f"are (N, S, n (n + 2)) = "
                             f"{(b, s, n * (n + 2))}, got {coef_shape}")


class HCStartLayer(_HyperLayer):
    """(N, S, C) -> the stream (N, S, n C): every stream a copy."""
    TYPE = "HC_START"

    def setup(self, bottom_shapes):
        return [tuple(bottom_shapes[0][:-1])
                + (bottom_shapes[0][-1] * self._n(),)]

    def apply(self, params, bottoms, ctx):
        from ..ops.hyper import hc_start
        return [hc_start(bottoms[0], self._n())]


class HCEndLayer(_HyperLayer):
    """The stream (N, S, n C) -> (N, S, C): the streams' sum."""
    TYPE = "HC_END"

    def setup(self, bottom_shapes):
        b, s, _, c = self._stream(bottom_shapes[0])
        return [(b, s, c)]

    def apply(self, params, bottoms, ctx):
        from ..ops.hyper import hc_end
        return [hc_end(bottoms[0], self._n())]


class HCMapLayer(_HyperLayer):
    """The stream (N, S, n C) -> a sub-layer's coefficients a token,
    (N, S, n (n + 2)) f32: p (n) for HC_READ, q (n) and the doubly
    stochastic M (n n) for HC_WRITE (``ops/hyper.hc_map``); then three
    scalars a display carries: what the Sinkhorn iterations leave (the
    largest |rowsum - 1| or |colsum - 1| of the step's tokens), the mean of
    p and the mean of q. Blobs: phi_pre, phi_post (n, n C), phi_res
    (n n, n C) from ``weight_filler``; b_pre (n,) = logit(1 / n), b_post
    (n,) = 0 (q = 1), b_res (n, n) = ``RES_DIAG`` on the diagonal (the mix
    starts near the identity); a_pre, a_post, a_res (1,) = ``SCALE``."""
    TYPE = "HC_MAP"
    SCALE, RES_DIAG = 0.01, 4.0

    def setup(self, bottom_shapes):
        hp = self.lp.hyper_param
        b, s, n, c = self._stream(bottom_shapes[0])
        if len(self.lp.top) != 4 or hp.sinkhorn_iters < 0 or hp.clamp <= 0:
            raise ValueError(f"{self.name}: HC_MAP has 4 tops "
                             f"(coefficients, res_err, pre_mean, "
                             f"post_mean), sinkhorn_iters >= 0 and clamp "
                             f"> 0; got {len(self.lp.top)} tops")
        const = lambda v: FillerParameter(type="constant", value=v)
        shapes = [
            ("phi_pre", (n, n * c), hp.weight_filler),
            ("phi_post", (n, n * c), hp.weight_filler),
            ("phi_res", (n * n, n * c), hp.weight_filler),
            ("b_pre", (n,), const(-math.log(n - 1.0) if n > 1 else 30.0)),
            ("b_post", (n,), const(0.0)),
            ("b_res", (n, n), FillerParameter(type="diagonal",
                                              value=self.RES_DIAG)),
            ("a_pre", (1,), const(self.SCALE)),
            ("a_post", (1,), const(self.SCALE)),
            ("a_res", (1,), const(self.SCALE))]
        self.params = [self._param(name, shape, filler, i)
                       for i, (name, shape, filler) in enumerate(shapes)]
        return [(b, s, n * (n + 2)), (), (), ()]

    def apply(self, params, bottoms, ctx):
        from ..ops.hyper import hc_map
        hp = self.lp.hyper_param
        return list(hc_map(bottoms[0], _tap_all(ctx, self.name, params),
                           self._n(), hp.sinkhorn_iters, hp.eps, hp.clamp))

    def forward_flops(self, bottom_shapes, top_shapes, defs):
        b, s, n, c = self._stream(bottom_shapes[0])
        # the projection, the statistic, and the loop's 4 n n a pass
        return float(b * s) * (2.0 * n * c * n * (n + 2) + 2.0 * n * c
                               + 4.0 * n * n
                               * self.lp.hyper_param.sinkhorn_iters)


class HCReadLayer(_HyperLayer):
    """Bottoms the stream (N, S, n C) and an HC_MAP's coefficients ->
    (N, S, C): a sub-layer's input, sum_j p_j X_j."""
    TYPE = "HC_READ"

    def setup(self, bottom_shapes):
        if len(bottom_shapes) != 2:
            raise ValueError(f"{self.name}: HC_READ takes the stream and "
                             f"its coefficients, got {bottom_shapes}")
        self._coef(*bottom_shapes)
        b, s, _, c = self._stream(bottom_shapes[0])
        return [(b, s, c)]

    def apply(self, params, bottoms, ctx):
        from ..ops.hyper import hc_read
        return [hc_read(bottoms[0], bottoms[1], self._n())]

    def forward_flops(self, bottom_shapes, top_shapes, defs):
        return 2.0 * _elems(bottom_shapes[:1])


class HCWriteLayer(_HyperLayer):
    """Bottoms the stream (N, S, n C), a sub-layer's output (N, S, C) and
    the HC_MAP's coefficients -> the stream after it,
    X'_i = sum_j M_ij X_j + q_i y."""
    TYPE = "HC_WRITE"

    def setup(self, bottom_shapes):
        if len(bottom_shapes) != 3:
            raise ValueError(f"{self.name}: HC_WRITE takes the stream, the "
                             f"sub-layer's output and the coefficients, "
                             f"got {bottom_shapes}")
        b, s, _, c = self._stream(bottom_shapes[0])
        self._coef(bottom_shapes[0], bottom_shapes[2])
        if tuple(bottom_shapes[1]) != (b, s, c):
            raise ValueError(f"{self.name}: the sub-layer's output is "
                             f"{(b, s, c)}, got {bottom_shapes[1]}")
        return [tuple(bottom_shapes[0])]

    def apply(self, params, bottoms, ctx):
        from ..ops.hyper import hc_write
        return [hc_write(bottoms[0], bottoms[1], bottoms[2], self._n())]

    def forward_flops(self, bottom_shapes, top_shapes, defs):
        return 2.0 * (self._n() + 1) * _elems(bottom_shapes[:1])


# --------------------------------------------------------------------------- #
# Vision layers
# --------------------------------------------------------------------------- #

class PoolingLayer(Layer):
    TYPE = "POOLING"
    LAYOUT_KIND = LAYOUT_SPATIAL

    def setup(self, bottom_shapes):
        pp = self.lp.pooling_param
        n, c, h, w = bottom_shapes[0]
        if pp.global_pooling:
            self.kernel = (h, w)
            self.stride = (1, 1)
            self.pad = (0, 0)
        else:
            self.kernel = _resolve_hw(pp.kernel_size, pp.kernel_h,
                                      pp.kernel_w, what="kernel",
                                      layer=self.name)
            self.stride = _resolve_hw(pp.stride, pp.stride_h, pp.stride_w, 1,
                                      what="stride", layer=self.name)
            self.pad = _resolve_hw(pp.pad, pp.pad_h, pp.pad_w, 0,
                                   what="pad", layer=self.name)
        self.method = pp.pool
        oh = NN.pool_out_size(h, self.kernel[0], self.stride[0], self.pad[0])
        ow = NN.pool_out_size(w, self.kernel[1], self.stride[1], self.pad[1])
        return [(n, c, oh, ow)]

    def apply(self, params, bottoms, ctx):
        x = bottoms[0]
        lay = self.run_layout
        if self.method == "MAX":
            return [NN.max_pool(x, self.kernel, self.stride, self.pad, lay)]
        if self.method == "AVE":
            return [NN.ave_pool(x, self.kernel, self.stride, self.pad, lay)]
        if self.method == "STOCHASTIC":
            return [NN.stochastic_pool(x, self.kernel, self.stride, self.pad,
                                       ctx.layer_rng(self.index), ctx.train,
                                       lay)]
        raise ValueError(f"unknown pool method {self.method}")

    def kernel_route(self, bottom_shapes, itemsize):
        # the backward's arm: a TRAIN net's MAX and AVE pools have one
        if self.phase != "TRAIN" or self.method not in ("MAX", "AVE"):
            return None
        return ("pool_bwd",) + tuple(NN.pool_bwd_route(
            self.kernel, self.stride, self.pad, self.method.lower(),
            bottom_shapes[0], itemsize))

    def forward_flops(self, bottom_shapes, top_shapes, defs):
        ksz = max(1, int(self.lp.pooling_param.kernel_size))
        return float(_elems(top_shapes)) * ksz * ksz


class LRNLayer(Layer):
    TYPE = "LRN"
    LAYOUT_KIND = LAYOUT_SPATIAL

    def setup(self, bottom_shapes):
        lp = self.lp.lrn_param
        self.local_size = lp.local_size
        self.alpha = lp.alpha
        self.beta = lp.beta
        self.region = lp.norm_region
        self.k = lp.k
        return [bottom_shapes[0]]

    def apply(self, params, bottoms, ctx):
        x = bottoms[0]
        if self.region == "ACROSS_CHANNELS":
            # on real TPU this takes the fused Pallas kernel (one VMEM pass);
            # XLA formulation elsewhere — identical numerics either way
            from ..ops.pallas_kernels import maybe_lrn_fused
            return [maybe_lrn_fused(x, self.local_size, self.alpha,
                                    self.beta, self.k,
                                    layout=self.run_layout)]
        return [NN.lrn_within_channel(x, self.local_size, self.alpha,
                                      self.beta, self.run_layout)]

    def kernel_route(self, bottom_shapes, itemsize):
        if self.region != "ACROSS_CHANNELS":
            return None
        from ..ops.pallas_kernels import lrn_route
        n, c, h, w = bottom_shapes[0]
        return ("lrn",) + tuple(lrn_route(h * w, c, n, itemsize))

    def forward_flops(self, bottom_shapes, top_shapes, defs):
        return float(_elems(bottom_shapes)) \
            * (2 * max(1, int(self.local_size)) + 4)


class Im2colLayer(Layer):
    TYPE = "IM2COL"

    def setup(self, bottom_shapes):
        cp = self.lp.convolution_param
        n, c, h, w = bottom_shapes[0]
        self.kernel = _resolve_hw(cp.kernel_size, cp.kernel_h, cp.kernel_w,
                                  what="kernel", layer=self.name)
        self.stride = _resolve_hw(cp.stride, cp.stride_h, cp.stride_w, 1,
                                  what="stride", layer=self.name)
        self.pad = _resolve_hw(cp.pad, cp.pad_h, cp.pad_w, 0,
                               what="pad", layer=self.name)
        oh = NN.conv_out_size(h, self.kernel[0], self.stride[0], self.pad[0])
        ow = NN.conv_out_size(w, self.kernel[1], self.stride[1], self.pad[1])
        return [(n, c * self.kernel[0] * self.kernel[1], oh, ow)]

    def apply(self, params, bottoms, ctx):
        return [NN.im2col(bottoms[0], self.kernel, self.stride, self.pad)]


# --------------------------------------------------------------------------- #
# Neuron layers (shape-preserving elementwise)
# --------------------------------------------------------------------------- #

class _NeuronLayer(Layer):
    LAYOUT_KIND = LAYOUT_AGNOSTIC

    def setup(self, bottom_shapes):
        return [bottom_shapes[0]]


class ReLULayer(_NeuronLayer):
    TYPE = "RELU"

    def __init__(self, lp: LayerParameter, phase: str, index: int = 0):
        super().__init__(lp, phase, index)
        # set by the net-level epilogue-fusion pass: this in-place ReLU was
        # folded into the producing conv's epilogue; apply is then identity
        # (the bottom already holds the activated values)
        self.folded_into: Optional[str] = None

    def apply(self, params, bottoms, ctx):
        if self.folded_into is not None:
            return [bottoms[0]]
        return [E.relu(bottoms[0], self.lp.relu_param.negative_slope)]


class SigmoidLayer(_NeuronLayer):
    TYPE = "SIGMOID"

    def apply(self, params, bottoms, ctx):
        return [E.sigmoid(bottoms[0])]


class TanHLayer(_NeuronLayer):
    TYPE = "TANH"

    def apply(self, params, bottoms, ctx):
        return [E.tanh(bottoms[0])]


class BNLLLayer(_NeuronLayer):
    TYPE = "BNLL"

    def apply(self, params, bottoms, ctx):
        return [E.bnll(bottoms[0])]


class AbsValLayer(_NeuronLayer):
    TYPE = "ABSVAL"

    def apply(self, params, bottoms, ctx):
        return [E.absval(bottoms[0])]


class PowerLayer(_NeuronLayer):
    TYPE = "POWER"

    def apply(self, params, bottoms, ctx):
        pp = self.lp.power_param
        return [E.power(bottoms[0], pp.power, pp.scale, pp.shift)]


class ThresholdLayer(_NeuronLayer):
    TYPE = "THRESHOLD"

    def apply(self, params, bottoms, ctx):
        return [E.threshold(bottoms[0], self.lp.threshold_param.threshold)]


class DropoutLayer(_NeuronLayer):
    TYPE = "DROPOUT"
    # the bernoulli mask is drawn over x.shape, so the element<->mask
    # assignment would depend on the physical layout; canonical keeps the
    # rng stream layout-portable (bit-identical train steps either way).
    # CNN dropout sits on FC/post-global-pool blobs where the conversion
    # is degenerate (XLA folds it to a bitcast), so this costs nothing.
    LAYOUT_KIND = LAYOUT_CANONICAL

    def apply(self, params, bottoms, ctx):
        return [E.dropout(bottoms[0], self.lp.dropout_param.dropout_ratio,
                          ctx.layer_rng(self.index), ctx.train)]


# --------------------------------------------------------------------------- #
# Structural layers
# --------------------------------------------------------------------------- #

class FlattenLayer(Layer):
    TYPE = "FLATTEN"

    def setup(self, bottom_shapes):
        n = bottom_shapes[0][0]
        return [(n, int(np.prod(bottom_shapes[0][1:])))]

    def apply(self, params, bottoms, ctx):
        return [E.flatten(bottoms[0])]


class ConcatLayer(Layer):
    TYPE = "CONCAT"
    LAYOUT_KIND = LAYOUT_AGNOSTIC  # axis-remapped under NHWC

    def setup(self, bottom_shapes):
        self.axis = self.lp.concat_param.concat_dim
        out = list(bottom_shapes[0])
        out[self.axis] = sum(s[self.axis] for s in bottom_shapes)
        return [tuple(out)]

    def apply(self, params, bottoms, ctx):
        axis = _remap_axis(self.axis, self.run_layout, bottoms[0].ndim)
        return [E.concat(bottoms, axis)]


class SliceLayer(Layer):
    TYPE = "SLICE"
    LAYOUT_KIND = LAYOUT_AGNOSTIC  # axis-remapped under NHWC

    def setup(self, bottom_shapes):
        sp = self.lp.slice_param
        self.axis = sp.slice_dim
        self.points = list(sp.slice_point)
        n_top = len(self.lp.top)
        size = bottom_shapes[0][self.axis]
        if self.points:
            bounds = [0] + self.points + [size]
        else:
            if size % n_top != 0:
                raise ValueError(
                    f"layer {self.lp.name!r}: cannot slice axis of size "
                    f"{size} into {n_top} equal tops")
            bounds = [i * (size // n_top) for i in range(n_top + 1)]
        shapes = []
        for i in range(n_top):
            s = list(bottom_shapes[0])
            s[self.axis] = bounds[i + 1] - bounds[i]
            shapes.append(tuple(s))
        return shapes

    def apply(self, params, bottoms, ctx):
        axis = _remap_axis(self.axis, self.run_layout, bottoms[0].ndim)
        return E.slice_blob(bottoms[0], axis, self.points, len(self.lp.top))


class SplitLayer(Layer):
    TYPE = "SPLIT"
    LAYOUT_KIND = LAYOUT_AGNOSTIC

    def setup(self, bottom_shapes):
        return [bottom_shapes[0]] * len(self.lp.top)

    def apply(self, params, bottoms, ctx):
        return [bottoms[0]] * len(self.lp.top)


class EltwiseLayer(Layer):
    TYPE = "ELTWISE"
    LAYOUT_KIND = LAYOUT_AGNOSTIC

    def setup(self, bottom_shapes):
        return [bottom_shapes[0]]

    def apply(self, params, bottoms, ctx):
        ep = self.lp.eltwise_param
        return [E.eltwise(bottoms, ep.operation, ep.coeff)]


class MVNLayer(_NeuronLayer):
    TYPE = "MVN"

    def apply(self, params, bottoms, ctx):
        mp = self.lp.mvn_param
        return [E.mvn(bottoms[0], mp.normalize_variance, mp.across_channels,
                      layout=self.run_layout)]


class SilenceLayer(Layer):
    TYPE = "SILENCE"
    LAYOUT_KIND = LAYOUT_AGNOSTIC  # discards its bottoms; any layout is fine

    def setup(self, bottom_shapes):
        return []

    def apply(self, params, bottoms, ctx):
        return []


class SoftmaxLayer(Layer):
    TYPE = "SOFTMAX"
    LAYOUT_KIND = LAYOUT_AGNOSTIC  # channel-axis remapped under NHWC

    def setup(self, bottom_shapes):
        return [bottom_shapes[0]]

    def apply(self, params, bottoms, ctx):
        axis = _remap_axis(1, self.run_layout, bottoms[0].ndim)
        return [L.softmax(bottoms[0], axis=axis)]

    def forward_flops(self, bottom_shapes, top_shapes, defs):
        return 5.0 * _elems(bottom_shapes)


class ArgMaxLayer(Layer):
    TYPE = "ARGMAX"

    def setup(self, bottom_shapes):
        ap = self.lp.argmax_param
        n = bottom_shapes[0][0]
        return [(n, 2 if ap.out_max_val else 1, ap.top_k, 1)]

    def apply(self, params, bottoms, ctx):
        ap = self.lp.argmax_param
        return [L.argmax(bottoms[0], ap.top_k, ap.out_max_val)]


# --------------------------------------------------------------------------- #
# Losses and metrics
# --------------------------------------------------------------------------- #

class _ScalarTopLayer(Layer):
    def setup(self, bottom_shapes):
        return [()]


class SoftmaxLossLayer(Layer):
    TYPE = "SOFTMAX_LOSS"

    def setup(self, bottom_shapes):
        if len(self.lp.top) >= 2:
            return [(), bottom_shapes[0]]
        return [()]

    def apply(self, params, bottoms, ctx):
        axis = self.lp.softmax_param.axis
        if axis not in (1, -1, bottoms[0].ndim - 1):
            raise ValueError(f"{self.name}: softmax axis {axis} is neither "
                             f"1 nor the last axis")
        if axis != 1 and bottoms[0].ndim > 2:
            # classes last: (N, S, V) logits against (N, S) targets
            axis, loss = -1, L.softmax_loss_last_axis(*bottoms[:2])
        else:
            axis, loss = 1, L.softmax_loss(*bottoms[:2])
        if len(self.lp.top) >= 2:
            return [loss, L.softmax(bottoms[0], axis=axis)]
        return [loss]

    forward_flops = SoftmaxLayer.forward_flops


class SoftmaxNLLLayer(Layer):
    """Logits (N, S, V), targets (N, S) -> -log softmax(logits)[target] at
    every position, (N, S) f32. Not a loss of its own: what EXIT_LOSS
    weights, one per pass."""
    TYPE = "SOFTMAX_NLL"

    def setup(self, bottom_shapes):
        if len(bottom_shapes) != 2 or \
                tuple(bottom_shapes[0][:-1]) != tuple(bottom_shapes[1]):
            raise ValueError(f"{self.name}: SOFTMAX_NLL takes logits "
                             f"(..., V) and targets (...), got "
                             f"{bottom_shapes}")
        return [tuple(bottom_shapes[1])]

    def apply(self, params, bottoms, ctx):
        return [L.softmax_nll_last_axis(*bottoms)]


class ExitLossLayer(Layer):
    """Bottoms: T per-position losses (N, S), one per pass of a looped LM,
    then the T - 1 exit-gate logits (N, S, 1) of every pass but the last.
    Top 0: the exit-probability-weighted loss less ``entropy_weight`` x the
    exit distribution's entropy (``ops/losses.exit_weighted_loss``);
    optionally T more scalar tops, the mean exit mass of each pass (they
    sum to 1)."""
    TYPE = "EXIT_LOSS"

    def setup(self, bottom_shapes):
        n = len(bottom_shapes)
        self.passes = (n + 1) // 2
        if n % 2 != 1 or len(self.lp.top) not in (1, 1 + self.passes) or \
                any(tuple(s) != tuple(bottom_shapes[0])
                    for s in bottom_shapes[:self.passes]):
            raise ValueError(
                f"{self.name}: EXIT_LOSS takes T losses of one shape and "
                f"T - 1 gates, and has 1 or 1 + T tops; got {n} bottoms, "
                f"{len(self.lp.top)} tops")
        return [()] * len(self.lp.top)

    def apply(self, params, bottoms, ctx):
        loss, p = L.exit_weighted_loss(
            bottoms[:self.passes], bottoms[self.passes:],
            self.lp.exit_loss_param.entropy_weight)
        mass = lax.stop_gradient(jnp.mean(p.reshape(self.passes, -1), axis=1))
        return [loss] + [mass[t] for t in range(len(self.lp.top) - 1)]


class WeightedMeanLossLayer(_ScalarTopLayer):
    """Per-position losses (N, S) and weights (N, S) -> sum(loss x weight) /
    sum(weight), a scalar in f32: with TOKEN_SHIFT's second top as the
    weights, the mean over the positions that have a target (a prediction
    module's S - 1 of S), the others left out and not given a made-up one.
    The weights take no gradient."""
    TYPE = "WEIGHTED_MEAN_LOSS"

    def setup(self, bottom_shapes):
        if len(bottom_shapes) != 2 \
                or tuple(bottom_shapes[0]) != tuple(bottom_shapes[1]):
            raise ValueError(f"{self.name}: WEIGHTED_MEAN_LOSS takes losses "
                             f"and weights of one shape, got {bottom_shapes}")
        return [()]

    def apply(self, params, bottoms, ctx):
        per, w = (b.astype(jnp.float32) for b in bottoms)
        w = lax.stop_gradient(w)
        return [jnp.sum(per * w) / jnp.sum(w)]


class EuclideanLossLayer(_ScalarTopLayer):
    TYPE = "EUCLIDEAN_LOSS"

    def apply(self, params, bottoms, ctx):
        return [L.euclidean_loss(bottoms[0], bottoms[1])]


class HingeLossLayer(_ScalarTopLayer):
    TYPE = "HINGE_LOSS"

    def apply(self, params, bottoms, ctx):
        return [L.hinge_loss(bottoms[0], bottoms[1],
                             self.lp.hinge_loss_param.norm)]


class MultinomialLogisticLossLayer(_ScalarTopLayer):
    TYPE = "MULTINOMIAL_LOGISTIC_LOSS"

    def apply(self, params, bottoms, ctx):
        return [L.multinomial_logistic_loss(bottoms[0], bottoms[1])]


class SigmoidCrossEntropyLossLayer(_ScalarTopLayer):
    TYPE = "SIGMOID_CROSS_ENTROPY_LOSS"

    def apply(self, params, bottoms, ctx):
        return [L.sigmoid_cross_entropy_loss(bottoms[0], bottoms[1])]


class InfogainLossLayer(_ScalarTopLayer):
    TYPE = "INFOGAIN_LOSS"

    def setup(self, bottom_shapes):
        src = self.lp.infogain_loss_param.source
        if len(bottom_shapes) >= 3:
            self.H = None  # provided as third bottom
        elif src:
            from ..proto.wire import read_blob_file
            if src.endswith(".npy"):
                self.H = np.load(src).astype(np.float32)
            else:
                self.H = read_blob_file(src).reshape(-1)
            dim = int(np.prod(bottom_shapes[0][1:]))
            self.H = np.asarray(self.H, np.float32).reshape(dim, dim)
        else:
            raise ValueError(f"{self.name}: infogain needs a source or 3rd bottom")
        return [()]

    def apply(self, params, bottoms, ctx):
        H = bottoms[2] if len(bottoms) >= 3 else jnp.asarray(self.H)
        if H.ndim > 2:
            H = H.reshape(H.shape[-2], H.shape[-1]) if H.shape[-1] == H.shape[-2] \
                else H.reshape(int(H.size ** 0.5), -1)
        return [L.infogain_loss(bottoms[0], bottoms[1], H)]


class ContrastiveLossLayer(_ScalarTopLayer):
    TYPE = "CONTRASTIVE_LOSS"

    def apply(self, params, bottoms, ctx):
        return [L.contrastive_loss(bottoms[0], bottoms[1], bottoms[2],
                                   self.lp.contrastive_loss_param.margin)]


class AccuracyLayer(_ScalarTopLayer):
    TYPE = "ACCURACY"

    def apply(self, params, bottoms, ctx):
        return [L.accuracy(bottoms[0], bottoms[1],
                           self.lp.accuracy_param.top_k)]


# --------------------------------------------------------------------------- #
# Data layers
# --------------------------------------------------------------------------- #

class _SourceLayer(Layer):
    """Tops are provided externally by the data pipeline (host side)."""

    def setup(self, bottom_shapes):
        raise RuntimeError(f"{self.TYPE} tops must come from the data pipeline")

    def apply(self, params, bottoms, ctx):
        raise RuntimeError(f"{self.TYPE} is not applied in-graph")


class DataLayer(_SourceLayer):
    TYPE = "DATA"


class ImageDataLayer(_SourceLayer):
    TYPE = "IMAGE_DATA"


class HDF5DataLayer(_SourceLayer):
    TYPE = "HDF5_DATA"


class WindowDataLayer(_SourceLayer):
    TYPE = "WINDOW_DATA"


class MemoryDataLayer(_SourceLayer):
    TYPE = "MEMORY_DATA"


class DummyDataLayer(Layer):
    TYPE = "DUMMY_DATA"

    def setup(self, bottom_shapes):
        dp = self.lp.dummy_data_param
        n_top = len(self.lp.top)

        def dim(values, i):
            if len(values) == 1:
                return values[0]
            return values[i]

        self.shapes = [
            (dim(dp.num, i), dim(dp.channels, i), dim(dp.height, i),
             dim(dp.width, i))
            for i in range(n_top)
        ]
        fillers = dp.data_filler or [FillerParameter()]
        self.fillers = [fillers[i] if len(fillers) > 1 else fillers[0]
                        for i in range(n_top)]
        return list(self.shapes)

    def apply(self, params, bottoms, ctx):
        outs = []
        rng = ctx.layer_rng(self.index)
        for i, (shape, f) in enumerate(zip(self.shapes, self.fillers)):
            pdef = ParamDef(name=f"top{i}", shape=shape, filler=f)
            key = (jax.random.fold_in(rng, i) if rng is not None
                   else jax.random.PRNGKey(i))
            outs.append(fill(key, pdef))
        return outs


class HDF5OutputLayer(Layer):
    TYPE = "HDF5_OUTPUT"
    # no in-graph compute; the engine dumps its bottoms from the
    # canonicalized blobs dict (Net.apply keep_blobs converts to NCHW)
    LAYOUT_KIND = LAYOUT_AGNOSTIC

    def setup(self, bottom_shapes):
        return []

    def apply(self, params, bottoms, ctx):
        # Side-effecting IO cannot live in the traced graph; the engine dumps
        # the bottoms of HDF5_OUTPUT layers from the blobs dict after each step
        # (runtime/engine.py), mirroring hdf5_output_layer.cpp.
        return []


REGISTRY: Dict[str, type] = {
    cls.TYPE: cls
    for cls in [
        ConvolutionLayer, InnerProductLayer, EmbedLayer, RMSNormLayer,
        AttentionLayer, MoELayer, MoERouterLayer, TokenShiftLayer,
        CCAConvLayer, CCAQKMeanLayer, CCAQKNormLayer, ShortConvLayer,
        L2NormLayer, KDADecayLayer, KDAScanLayer, SSDScanLayer, PoolingLayer,
        LRNLayer,
        Im2colLayer, ReLULayer, SigmoidLayer, TanHLayer, BNLLLayer,
        AbsValLayer, PowerLayer, ThresholdLayer, DropoutLayer, FlattenLayer,
        ConcatLayer, SliceLayer, SplitLayer, EltwiseLayer, MVNLayer,
        SilenceLayer, SoftmaxLayer, ArgMaxLayer, SoftmaxLossLayer,
        SiLUGateLayer, HCStartLayer, HCEndLayer, HCMapLayer, HCReadLayer,
        HCWriteLayer, SoftmaxNLLLayer, ExitLossLayer, WeightedMeanLossLayer,
        EuclideanLossLayer, HingeLossLayer, MultinomialLogisticLossLayer,
        SigmoidCrossEntropyLossLayer, InfogainLossLayer, ContrastiveLossLayer,
        AccuracyLayer, DataLayer, ImageDataLayer, HDF5DataLayer,
        WindowDataLayer, MemoryDataLayer, DummyDataLayer, HDF5OutputLayer,
    ]
}


def create_layer(lp: LayerParameter, phase: str, index: int) -> Layer:
    t = lp.canonical_type()
    if t not in REGISTRY:
        raise ValueError(f"layer {lp.name!r}: unsupported type {t}")
    return REGISTRY[t](lp, phase, index)
