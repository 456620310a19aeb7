"""Static layout tests: the net-level NHWC plan is transpose-free inside.

Round 6 replaced the per-op transpose shims (round 3/5: transpose at every
op boundary and hope XLA cancels the pairs — it measurably did not across
pool/LRN/concat seams, the 0.53x NHWC A/B) with a net-level layout plan:
the whole graph runs channels-last and converts only at genuine
boundaries. These tests pin that claim on the COMPILER INPUT (StableHLO of
the lowered program): the layout transposes our program asks for must sit
only at the FC-flatten boundaries — never one pair per spatial op.

The count is taken at the StableHLO level via ``runtime/hlo_layout.py``
because the CPU backend's optimized HLO materializes its own conv
canonicalization transposes for every conv GRADIENT regardless of our
plan (~77 for the NCHW AlexNet step); the TPU-compiler (optimized-HLO)
version of this check is ``scripts/aot_tpu_check.py`` section ``nhwc``,
AOT against an abstract v5e.

Reference anchor: the cuDNN NCHW-native layers this policy replaces
(src/caffe/layers/cudnn_conv_layer.cpp); the TPU-first design instead
plans XLA's preferred channels-last layout and keeps the public interface
NCHW.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from poseidon_tpu.core.net import Net
from poseidon_tpu.models import zoo
from poseidon_tpu.ops import nn
from poseidon_tpu.runtime import hlo_layout as HL


def _stablehlo_of(fn, *args):
    return jax.jit(fn).lower(*args).as_text()


# --------------------------------------------------------------------------- #
# op-level: the native NHWC chain emits ZERO transposes at the compiler input
# --------------------------------------------------------------------------- #

def test_native_nhwc_chain_has_zero_transposes():
    """conv -> (fused relu) -> lrn -> pool -> conv, built natively NHWC
    with canonical OIHW weights: not a single transpose reaches the
    compiler — there are no shims left to cancel."""
    rs = np.random.RandomState(0)
    x = jnp.asarray(rs.randn(4, 31, 31, 3).astype(np.float32))
    w1 = jnp.asarray(rs.randn(16, 3, 3, 3).astype(np.float32))
    b1 = jnp.asarray(rs.randn(16).astype(np.float32))
    w2 = jnp.asarray(rs.randn(32, 16, 3, 3).astype(np.float32))
    b2 = jnp.asarray(rs.randn(32).astype(np.float32))

    def chain(x, w1, b1, w2, b2):
        y = nn.conv2d(x, w1, b1, (2, 2), (1, 1), layout="NHWC", act="relu")
        y = nn.lrn_across_channels(y, 5, 1e-4, 0.75, layout="NHWC")
        y = nn.max_pool(y, (3, 3), (2, 2), (0, 0), layout="NHWC")
        return nn.conv2d(y, w2, b2, (1, 1), (1, 1), layout="NHWC")

    txt = _stablehlo_of(chain, x, w1, b1, w2, b2)
    assert HL.count_layout_transposes(txt) == 0, HL.layout_report(txt)


def test_native_nhwc_chain_backward_has_zero_transposes():
    """Same property through the VJP: conv/pool/LRN gradients stay
    channels-last (jax's conv transpose rules juggle dimension numbers,
    not transposes)."""
    rs = np.random.RandomState(0)
    x = jnp.asarray(rs.randn(2, 15, 15, 3).astype(np.float32))
    w = jnp.asarray(rs.randn(8, 3, 3, 3).astype(np.float32))
    b = jnp.asarray(rs.randn(8).astype(np.float32))

    def loss(x, w, b):
        y = nn.conv2d(x, w, b, (1, 1), (1, 1), layout="NHWC", act="relu")
        y = nn.lrn_across_channels(y, 3, 1e-4, 0.75, layout="NHWC")
        y = nn.max_pool(y, (3, 3), (2, 2), (0, 0), layout="NHWC")
        return jnp.sum(y ** 2)

    txt = _stablehlo_of(jax.grad(loss, argnums=(0, 1, 2)), x, w, b)
    assert HL.count_layout_transposes(txt) == 0, HL.layout_report(txt)


# --------------------------------------------------------------------------- #
# net-level: full optimizer steps, layout transposes only at FC boundaries
# --------------------------------------------------------------------------- #

def _net(model, layout, image, batch):
    return Net(getattr(zoo, model)(num_classes=10, with_accuracy=False),
               "TRAIN", {"data": (batch, 3, image, image),
                         "label": (batch,)}, conv_layout=layout)


def _alexnet(layout, image=227, batch=2):
    return _net("alexnet", layout, image, batch)


def test_alexnet_nhwc_train_step_le_2_layout_transposes():
    """The acceptance bound: one full AlexNet optimizer step planned NHWC
    and fed NHWC keeps <= 2 layout transposes — the fc6 flatten boundary's
    forward + backward pair and NOTHING else (the shim design carried one
    surviving pair per pool/LRN seam)."""
    net = _alexnet("NHWC")
    rep = HL.net_transpose_report(net, per_dev_batch=2, image=227)
    assert rep["layout_transposes"] <= 2, rep
    # and each of them is the pool5 <-> fc6 boundary (256-channel 6x6)
    for t in rep["layout_transpose_shapes"]:
        assert sorted(t["shape"])[-1] == 256, rep


def test_alexnet_transpose_count_is_depth_independent():
    """The regression the ISSUE targets: under the old shim the count grew
    with every spatial op (one pair per pool/LRN seam). Net-level planning
    makes it a function of the BOUNDARY count only — AlexNet has 5 convs,
    3 pools, 2 LRNs and still exactly one convert site."""
    rep = HL.net_transpose_report(_alexnet("NHWC"), per_dev_batch=2,
                                  image=227)
    n_spatial_ops = 5 + 3 + 2
    assert rep["layout_transposes"] < n_spatial_ops, rep


def test_googlenet_nhwc_transposes_only_at_fc_boundaries():
    """GoogLeNet has THREE genuine FC boundaries (main head's global pool
    is degenerate 1x1; two aux heads flatten real 4x4x128 blobs): <= 2
    layout transposes per boundary, zero anywhere in the 9-inception
    conv/pool/concat body."""
    rep = HL.net_transpose_report(_net("googlenet", "NHWC", 224, 1),
                                  per_dev_batch=1, image=224)
    n_boundaries = 3  # loss3/classifier + two aux-head FCs
    assert rep["layout_transposes"] <= 2 * n_boundaries, rep
    # every surviving transpose is at an FC flatten (4x4x128 aux or the
    # degenerate 1x1x1024 main head) — none inside the inception body
    for t in rep["layout_transpose_shapes"]:
        assert max(t["shape"]) in (128, 1024), rep


@pytest.mark.parametrize("model,image,batch", [
    ("alexnet", 227, 2), ("googlenet", 224, 1)])
def test_nchw_plan_has_zero_layout_transposes(model, image, batch):
    """The canonical plan is the identity: no layout machinery leaks in
    (GoogLeNet: the inception fan-out, CONCAT and three FC flattens too)."""
    rep = HL.net_transpose_report(_net(model, "NCHW", image, batch),
                                  per_dev_batch=batch, image=image)
    assert rep["layout_transposes"] == 0, rep


def test_nhwc_plan_fed_canonical_costs_exactly_one_entry_transpose():
    """Feeding the Caffe NCHW contract into an NHWC-planned net costs one
    entry transpose per image input on top of the boundary pair — the
    documented fallback, not a regression."""
    net = _alexnet("NHWC")
    from poseidon_tpu.proto.messages import SolverParameter
    step = HL.build_plain_step(net, SolverParameter(
        base_lr=0.01, lr_policy="fixed", momentum=0.9), input_layout="NCHW")
    params, state, _, rng = HL.step_avals(net, 2, 227)
    batch = {"data": jax.ShapeDtypeStruct((2, 3, 227, 227), jnp.float32),
             "label": jax.ShapeDtypeStruct((2,), jnp.int32)}
    txt = jax.jit(step).lower(params, state, batch, rng).as_text()
    n = HL.count_layout_transposes(txt)
    # lower bound is the LIVE positive control for the parser: if a jax
    # upgrade changes the textual transpose form, every <= N assertion in
    # this file would pass vacuously — this program is GUARANTEED to carry
    # the entry transpose, so a zero count means the regex went blind
    assert 1 <= n <= 3, HL.layout_report(txt)


# --------------------------------------------------------------------------- #
# parser unit coverage
# --------------------------------------------------------------------------- #

def test_parser_reads_both_program_levels():
    hlo = ("  %t = f32[4,6,6,256]{3,2,1,0} transpose(%p), "
           "dimensions={0,3,1,2}\n"
           "  %u = f32[4,1,1,256]{3,2,1,0} transpose(%q), "
           "dimensions={0,3,1,2}\n")
    shlo = ("    %1 = stablehlo.transpose %0, dims = [0, 3, 1, 2] : "
            "(tensor<4x6x6x256xf32>) -> tensor<4x256x6x6xf32>\n")
    ops = HL.parse_transposes(hlo)
    assert len(ops) == 2
    assert ops[0].is_layout            # real 6x6x256 layout change
    assert not ops[1].nontrivial       # degenerate (N,1,1,C): a bitcast
    assert HL.count_layout_transposes(hlo) == 1
    assert HL.count_layout_transposes(shlo) == 1


@pytest.mark.parametrize("layout", ["NCHW", "NHWC"])
def test_report_carries_level_and_plan(layout):
    net = _alexnet(layout, image=67)
    rep = HL.net_transpose_report(net, per_dev_batch=2, image=67)
    assert rep["level"] == "stablehlo"
    assert rep["conv_layout"] == layout
