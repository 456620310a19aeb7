"""Layout parity: the NHWC plan is a performance policy, never a numerics one.

Acceptance criteria of the round-6 layout PR: with ``conv_layout="NHWC"``,
every spatial layer op and one full optimizer step of AlexNet and
GoogLeNet match the NCHW path on CPU within float tolerance (params and
grads compared in CANONICAL NCHW), and snapshots written under either
layout load under the other. Everything here runs mesh-free (plain jit /
grad) so the CPU tier stays independent of shard_map availability.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from poseidon_tpu.core.net import Net
from poseidon_tpu.models import zoo
from poseidon_tpu.proto.messages import (
    ConcatParameter, ConvolutionParameter, EltwiseParameter, LayerParameter,
    LRNParameter, MVNParameter, NetParameter, PoolingParameter,
    SliceParameter, SolverParameter)

jtu = jax.tree_util


def _tree_close(a, b, rtol=1e-5, atol=1e-6, msg=""):
    la, lb = jtu.tree_leaves(a), jtu.tree_leaves(b)
    assert len(la) == len(lb)
    for x, y in zip(la, lb):
        np.testing.assert_allclose(np.asarray(x), np.asarray(y),
                                   rtol=rtol, atol=atol, err_msg=msg)


def _both_layouts(net_param, shapes, inputs, train=True, rng_seed=7):
    """(outputs, param grads) under each layout, same canonical params."""
    rng = jax.random.PRNGKey(rng_seed)
    results = {}
    params = None
    for layout in ("NCHW", "NHWC"):
        net = Net(net_param, "TRAIN" if train else "TEST", shapes,
                  conv_layout=layout)
        if params is None:
            params = net.init(jax.random.PRNGKey(0))

        def loss_fn(p):
            out = net.apply(p, inputs, train=train, rng=rng)
            return out.loss, out.outputs

        if params:
            (loss, outs), grads = jax.value_and_grad(
                loss_fn, has_aux=True)(params)
        else:
            loss, outs = loss_fn(params)
            grads = {}
        results[layout] = (loss, outs, grads)
    return results


def _single_layer_net(layer_lp, shapes, loss_bottom=None, label_shape=None):
    """Wrap one layer in a net with a loss so grads flow; shapes name the
    external inputs."""
    layers = [layer_lp]
    if loss_bottom is not None:
        from poseidon_tpu.models.zoo import ip, softmax_loss
        layers += [ip("fc", loss_bottom, "fc", 5),
                   softmax_loss("loss", ["fc", "label"])]
    return NetParameter(name="t", layers=layers)


RS = np.random.RandomState(42)


def _img(shape):
    return jnp.asarray(RS.randn(*shape).astype(np.float32))


# --------------------------------------------------------------------------- #
# per-layer-type parity (each spatial/structural op through the planner)
# --------------------------------------------------------------------------- #

def _layer_case(name):
    """(extra layer stack, input shape) per layer type under test; every
    case is conv -> <layer> -> fc/loss so the op under test runs inside a
    genuinely NHWC-planned region with grads flowing through it."""
    C = ConvolutionParameter
    conv = LayerParameter(
        name="conv", type="CONVOLUTION", bottom=["data"], top=["conv"],
        convolution_param=C(num_output=8, kernel_size=3, pad=1,
                            weight_filler=zoo.xavier(),
                            bias_filler=zoo.constant(0.1)))
    if name == "conv_group":
        lp = LayerParameter(
            name="op", type="CONVOLUTION", bottom=["conv"], top=["op"],
            convolution_param=C(num_output=8, kernel_size=3, pad=1, group=2,
                                weight_filler=zoo.xavier(),
                                bias_filler=zoo.constant(0.0)))
    elif name == "pool_max":
        lp = LayerParameter(
            name="op", type="POOLING", bottom=["conv"], top=["op"],
            pooling_param=PoolingParameter(pool="MAX", kernel_size=3,
                                           stride=2, pad=1))
    elif name == "pool_ave":
        lp = LayerParameter(
            name="op", type="POOLING", bottom=["conv"], top=["op"],
            pooling_param=PoolingParameter(pool="AVE", kernel_size=3,
                                           stride=2, pad=1))
    elif name == "pool_global":
        lp = LayerParameter(
            name="op", type="POOLING", bottom=["conv"], top=["op"],
            pooling_param=PoolingParameter(pool="AVE", global_pooling=True))
    elif name == "lrn_across":
        lp = LayerParameter(
            name="op", type="LRN", bottom=["conv"], top=["op"],
            lrn_param=LRNParameter(local_size=5, alpha=1e-4, beta=0.75))
    elif name == "lrn_within":
        lp = LayerParameter(
            name="op", type="LRN", bottom=["conv"], top=["op"],
            lrn_param=LRNParameter(local_size=3, alpha=1e-4, beta=0.75,
                                   norm_region="WITHIN_CHANNEL"))
    elif name == "mvn":
        lp = LayerParameter(
            name="op", type="MVN", bottom=["conv"], top=["op"],
            mvn_param=MVNParameter(normalize_variance=True,
                                   across_channels=False))
    elif name == "eltwise":
        return None  # multi-bottom; built in its own test
    else:
        raise KeyError(name)
    return [conv, lp]


@pytest.mark.parametrize("case", [
    "conv_group", "pool_max", "pool_ave", "pool_global",
    "lrn_across", "lrn_within", "mvn",
])
def test_layer_type_parity(case):
    layers = _layer_case(case)
    from poseidon_tpu.models.zoo import ip, softmax_loss
    np_ = NetParameter(name="t", layers=layers + [
        ip("fc", "op", "fc", 5), softmax_loss("loss", ["fc", "label"])])
    shapes = {"data": (2, 4, 9, 9), "label": (2,)}
    inputs = {"data": _img((2, 4, 9, 9)),
              "label": jnp.asarray(RS.randint(0, 5, (2,)))}
    r = _both_layouts(np_, shapes, inputs)
    _tree_close(r["NCHW"][0], r["NHWC"][0], msg=f"{case}: loss")
    _tree_close(r["NCHW"][2], r["NHWC"][2], rtol=1e-4, atol=1e-5,
                msg=f"{case}: grads")


def test_concat_slice_eltwise_softmax_parity():
    """The structural seams the old shim stranded transposes across:
    slice on channels -> eltwise -> concat -> in-graph SOFTMAX on a 4-D
    blob, all inside the NHWC region."""
    from poseidon_tpu.models.zoo import conv as zconv, ip, softmax_loss
    layers = [
        zconv("conv", "data", "conv", 8, 3, pad=1),
        LayerParameter(name="sl", type="SLICE", bottom=["conv"],
                       top=["s1", "s2"],
                       slice_param=SliceParameter(slice_dim=1)),
        LayerParameter(name="ew", type="ELTWISE", bottom=["s1", "s2"],
                       top=["ew"],
                       eltwise_param=EltwiseParameter(operation="SUM",
                                                      coeff=[0.5, 2.0])),
        LayerParameter(name="cat", type="CONCAT", bottom=["ew", "s1"],
                       top=["cat"],
                       concat_param=ConcatParameter(concat_dim=1)),
        LayerParameter(name="sm", type="SOFTMAX", bottom=["cat"],
                       top=["sm"]),
        ip("fc", "sm", "fc", 5),
        softmax_loss("loss", ["fc", "label"]),
    ]
    np_ = NetParameter(name="t", layers=layers)
    shapes = {"data": (2, 4, 7, 7), "label": (2,)}
    inputs = {"data": _img((2, 4, 7, 7)),
              "label": jnp.asarray(RS.randint(0, 5, (2,)))}
    r = _both_layouts(np_, shapes, inputs)
    _tree_close(r["NCHW"][0], r["NHWC"][0], msg="loss")
    _tree_close(r["NCHW"][2], r["NHWC"][2], rtol=1e-4, atol=1e-5,
                msg="grads")


def test_dropout_rng_is_layout_portable():
    """Dropout masks must not depend on the physical layout (the layer is
    planned canonical precisely for this) — train-mode losses match
    BITWISE across plans for the same rng."""
    from poseidon_tpu.models.zoo import conv as zconv, dropout, ip, \
        softmax_loss
    layers = [
        zconv("conv", "data", "conv", 8, 3, pad=1),
        dropout("drop", "conv", 0.5),
        ip("fc", "conv", "fc", 5),
        softmax_loss("loss", ["fc", "label"]),
    ]
    np_ = NetParameter(name="t", layers=layers)
    shapes = {"data": (2, 4, 7, 7), "label": (2,)}
    inputs = {"data": _img((2, 4, 7, 7)),
              "label": jnp.asarray(RS.randint(0, 5, (2,)))}
    r = _both_layouts(np_, shapes, inputs, train=True)
    assert float(r["NCHW"][0]) == float(r["NHWC"][0])


# --------------------------------------------------------------------------- #
# full-net optimizer-step parity (the acceptance bar)
# --------------------------------------------------------------------------- #

def _one_step(net, params, batch, input_layout="NCHW"):
    from poseidon_tpu.parallel.trainer import param_mults
    from poseidon_tpu.solvers.updates import init_state, make_update_fn
    sp = SolverParameter(base_lr=0.01, lr_policy="fixed", momentum=0.9,
                         weight_decay=5e-4)
    update = make_update_fn(sp, param_mults(net))

    @jax.jit
    def step(p, s, b):
        def loss_fn(pp):
            return net.apply(pp, b, train=True, rng=jax.random.PRNGKey(3),
                             input_layout=input_layout).loss
        g = jax.grad(loss_fn)(p)
        return update(p, g, s)

    return step(params, init_state(params), batch)


@pytest.mark.parametrize("model,image,batch", [
    ("alexnet", 67, 2),
    pytest.param("googlenet", 224, 1, marks=pytest.mark.slow),
])
def test_full_net_optimizer_step_parity(model, image, batch):
    """One full momentum+weight-decay optimizer step under each plan:
    updated params (canonical layout by construction) must agree within
    float tolerance. AlexNet runs at a reduced image size to keep the CPU
    tier fast; GoogLeNet (224 required by its pooling tree) is the slow-
    marked heavyweight variant."""
    np_ = getattr(zoo, model)(num_classes=10, with_accuracy=False)
    shapes = {"data": (batch, 3, image, image), "label": (batch,)}
    batch_arrs = {"data": _img(shapes["data"]),
                  "label": jnp.asarray(RS.randint(0, 10, (batch,)))}
    out = {}
    params = None
    for layout in ("NCHW", "NHWC"):
        net = Net(np_, "TRAIN", shapes, conv_layout=layout)
        if params is None:
            params = net.init(jax.random.PRNGKey(0))
        out[layout], _ = _one_step(net, params, batch_arrs)
    _tree_close(out["NCHW"], out["NHWC"], rtol=1e-4, atol=1e-6,
                msg=f"{model}: params after one step")


def test_nhwc_fed_input_matches_canonical_feed():
    """Feeding channels-last directly (the transpose-free hot path) is the
    same computation as feeding the Caffe NCHW contract."""
    np_ = zoo.alexnet(num_classes=10, with_accuracy=False)
    shapes = {"data": (2, 3, 67, 67), "label": (2,)}
    net = Net(np_, "TRAIN", shapes, conv_layout="NHWC")
    params = net.init(jax.random.PRNGKey(0))
    x = _img((2, 3, 67, 67))
    lbl = jnp.asarray(RS.randint(0, 10, (2,)))
    rng = jax.random.PRNGKey(5)
    l_nchw = net.apply(params, {"data": x, "label": lbl}, train=True,
                       rng=rng).loss
    l_nhwc = net.apply(params, {"data": jnp.transpose(x, (0, 2, 3, 1)),
                                "label": lbl}, train=True, rng=rng,
                       input_layout="NHWC").loss
    assert float(l_nchw) == float(l_nhwc)


def test_keep_blobs_and_outputs_are_canonical():
    """Blob export is a genuine boundary: every 4-D blob coming out of an
    NHWC-planned net is canonical NCHW with its logical shape."""
    np_ = zoo.alexnet(num_classes=10, with_accuracy=False)
    shapes = {"data": (2, 3, 67, 67), "label": (2,)}
    net = Net(np_, "TRAIN", shapes, conv_layout="NHWC")
    params = net.init(jax.random.PRNGKey(0))
    out = net.apply(params, {"data": _img((2, 3, 67, 67)),
                             "label": jnp.asarray([0, 1])},
                    train=False, keep_blobs=True)
    for name, blob in out.blobs.items():
        if getattr(blob, "ndim", 0) == 4:
            assert tuple(blob.shape) == net.blob_shapes[name], name


# --------------------------------------------------------------------------- #
# snapshots / weights are layout-portable
# --------------------------------------------------------------------------- #

def test_weights_roundtrip_across_layouts(tmp_path):
    """Params are canonical under either plan: weights exported by an
    NHWC-planned net load into an NCHW-planned net (and back) with
    identical forward results — snapshots never encode the layout."""
    np_ = zoo.alexnet(num_classes=10, with_accuracy=False)
    shapes = {"data": (2, 3, 67, 67), "label": (2,)}
    nets = {lay: Net(np_, "TRAIN", shapes, conv_layout=lay)
            for lay in ("NCHW", "NHWC")}
    params = nets["NHWC"].init(jax.random.PRNGKey(1))
    blobs = nets["NHWC"].export_weights(params)
    restored = nets["NCHW"].load_weights(nets["NCHW"].init(
        jax.random.PRNGKey(2)), blobs)
    _tree_close(params, restored)
    inputs = {"data": _img((2, 3, 67, 67)),
              "label": jnp.asarray(RS.randint(0, 10, (2,)))}
    rng = jax.random.PRNGKey(9)
    l1 = nets["NHWC"].apply(params, inputs, train=True, rng=rng).loss
    l2 = nets["NCHW"].apply(restored, inputs, train=True, rng=rng).loss
    np.testing.assert_allclose(float(l1), float(l2), rtol=1e-5)


def test_snapshot_roundtrip_across_layouts(tmp_path):
    """The runtime snapshot files written under one plan restore under the
    other (checkpoints stay NCHW-canonical)."""
    from poseidon_tpu.parallel.trainer import init_train_state
    from poseidon_tpu.runtime.checkpoint import restore, snapshot
    np_ = zoo.lenet(with_accuracy=False)
    shapes = {"data": (2, 1, 28, 28), "label": (2,)}
    net_a = Net(np_, "TRAIN", shapes, conv_layout="NHWC")
    params = net_a.init(jax.random.PRNGKey(0))
    state = init_train_state(params)
    _, state_path = snapshot(str(tmp_path / "snap"), net_a, params, state)
    loaded_params, _ = restore(state_path)
    net_b = Net(np_, "TRAIN", shapes, conv_layout="NCHW")
    inputs = {"data": _img((2, 1, 28, 28)),
              "label": jnp.asarray([1, 2])}
    l_a = net_a.apply(params, inputs, train=False).loss
    l_b = net_b.apply(jtu.tree_map(jnp.asarray, loaded_params), inputs,
                      train=False).loss
    np.testing.assert_allclose(float(l_a), float(l_b), rtol=1e-6)


# --------------------------------------------------------------------------- #
# fused conv epilogues
# --------------------------------------------------------------------------- #

def test_epilogue_fusion_is_exact_and_optional():
    """conv->in-place-relu folds into the conv epilogue; the fold is
    BITWISE identical to the unfused net (same formula), in both layouts."""
    np_ = zoo.alexnet(num_classes=10, with_accuracy=False)
    shapes = {"data": (2, 3, 67, 67), "label": (2,)}
    inputs = {"data": _img((2, 3, 67, 67)),
              "label": jnp.asarray(RS.randint(0, 10, (2,)))}
    rng = jax.random.PRNGKey(4)
    for layout in ("NCHW", "NHWC"):
        fused = Net(np_, "TRAIN", shapes, conv_layout=layout)
        plain = Net(np_, "TRAIN", shapes, conv_layout=layout,
                    fuse_conv_epilogues=False)
        assert any(l.fused_relu_slope is not None for l in fused.layers)
        assert all(l.fused_relu_slope is None for l in plain.layers
                   if l.TYPE == "CONVOLUTION")
        params = fused.init(jax.random.PRNGKey(0))
        lf = fused.apply(params, inputs, train=True, rng=rng).loss
        lp = plain.apply(params, inputs, train=True, rng=rng).loss
        assert float(lf) == float(lp), layout


def test_epilogue_fusion_skips_non_inplace_and_loss_weighted():
    """Guards: a ReLU writing a DIFFERENT top keeps the conv's own blob
    pre-activation (no fold); a loss_weight on the conv top reads the
    pre-activation sum (no fold)."""
    from poseidon_tpu.models.zoo import ip, softmax_loss
    C = ConvolutionParameter
    conv = LayerParameter(
        name="conv", type="CONVOLUTION", bottom=["data"], top=["conv"],
        convolution_param=C(num_output=4, kernel_size=3,
                            weight_filler=zoo.xavier(),
                            bias_filler=zoo.constant(0.0)))
    relu_out = LayerParameter(name="relu", type="RELU", bottom=["conv"],
                              top=["act"])
    np_ = NetParameter(name="t", layers=[
        conv, relu_out, ip("fc", "act", "fc", 3),
        softmax_loss("loss", ["fc", "label"])])
    net = Net(np_, "TRAIN", {"data": (2, 2, 7, 7), "label": (2,)})
    assert net._layer_by_name["conv"].fused_relu_slope is None

    conv_lw = LayerParameter(
        name="conv", type="CONVOLUTION", bottom=["data"], top=["conv"],
        loss_weight=[0.1],
        convolution_param=C(num_output=4, kernel_size=3,
                            weight_filler=zoo.xavier(),
                            bias_filler=zoo.constant(0.0)))
    relu_in = LayerParameter(name="relu", type="RELU", bottom=["conv"],
                             top=["conv"])
    np2 = NetParameter(name="t2", layers=[
        conv_lw, relu_in, ip("fc", "conv", "fc", 3),
        softmax_loss("loss", ["fc", "label"])])
    net2 = Net(np2, "TRAIN", {"data": (2, 2, 7, 7), "label": (2,)})
    assert net2._layer_by_name["conv"].fused_relu_slope is None


def test_conv_scale_shift_epilogue():
    """The BN-folded inference epilogue: y = (conv+b)*scale + shift, per
    output channel, fused into the conv call — same numbers as the
    explicit elementwise chain, both layouts."""
    from poseidon_tpu.ops import nn as NN
    rs = np.random.RandomState(3)
    x = jnp.asarray(rs.randn(2, 3, 9, 9).astype(np.float32))
    w = jnp.asarray(rs.randn(6, 3, 3, 3).astype(np.float32))
    b = jnp.asarray(rs.randn(6).astype(np.float32))
    scale = jnp.asarray(rs.rand(6).astype(np.float32) + 0.5)
    shift = jnp.asarray(rs.randn(6).astype(np.float32))
    base = NN.conv2d(x, w, b, (1, 1), (1, 1))
    want = jnp.maximum(base * scale.reshape(1, -1, 1, 1)
                       + shift.reshape(1, -1, 1, 1), 0)
    got = NN.conv2d(x, w, b, (1, 1), (1, 1), scale=scale, shift=shift,
                    act="relu")
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-6, atol=1e-6)
    xt = jnp.transpose(x, (0, 2, 3, 1))
    got_nhwc = NN.conv2d(xt, w, b, (1, 1), (1, 1), layout="NHWC",
                         scale=scale, shift=shift, act="relu")
    np.testing.assert_allclose(
        np.asarray(jnp.transpose(got_nhwc, (0, 3, 1, 2))),
        np.asarray(want), rtol=1e-5, atol=1e-5)


def test_s2d_stem_rewrite_parity_nhwc():
    """The space-to-depth stem rewrite stays exact under the NHWC plan
    (its channel flattening order matches the canonical kernel rewrite)."""
    from poseidon_tpu import config
    from poseidon_tpu.ops import nn as NN
    rs = np.random.RandomState(11)
    x = jnp.asarray(rs.randn(2, 3, 19, 19).astype(np.float32))
    w = jnp.asarray(rs.randn(8, 3, 5, 5).astype(np.float32))
    b = jnp.asarray(rs.randn(8).astype(np.float32))
    ref = NN.conv2d(x, w, b, (2, 2), (1, 1))
    with config.policy_scope(conv_s2d=True):
        got_nchw = NN.conv2d(x, w, b, (2, 2), (1, 1))
        got_nhwc = NN.conv2d(jnp.transpose(x, (0, 2, 3, 1)), w, b,
                             (2, 2), (1, 1), layout="NHWC")
    np.testing.assert_allclose(np.asarray(got_nchw), np.asarray(ref),
                               rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(
        np.asarray(jnp.transpose(got_nhwc, (0, 3, 1, 2))),
        np.asarray(ref), rtol=1e-4, atol=1e-5)


def test_conv_layout_auto_resolves_per_backend():
    """``conv_layout="auto"`` resolves at Net construction: NHWC on TPU
    since PR 55 (conv1's weight gradient leaves the top of AlexNet's step
    on the v5e: PERF.md section 6; the July-2026 chip A/B's 0.53x predates
    the LRN and pool kernels that run the same blocks under both plans),
    NHWC on GPU (tensor-core native), NCHW on CPU / unknown backends;
    explicit overrides pass through untouched."""
    from poseidon_tpu.numeric import resolve_conv_layout

    assert resolve_conv_layout("auto", backend="tpu") == "NHWC"
    assert resolve_conv_layout("auto", backend="gpu") == "NHWC"
    assert resolve_conv_layout("auto", backend="cpu") == "NCHW"
    assert resolve_conv_layout("auto", backend="something_else") == "NCHW"
    assert resolve_conv_layout("NHWC", backend="tpu") == "NHWC"
    assert resolve_conv_layout("nchw", backend="gpu") == "NCHW"

    # a Net built under "auto" lands on this backend's resolved layout
    # (the suite runs on CPU -> NCHW) and still trains/applies
    np_ = NetParameter(name="auto_net", layers=[
        LayerParameter(name="c", type="CONVOLUTION", bottom=["data"],
                       top=["c"],
                       convolution_param=ConvolutionParameter(
                           num_output=4, kernel_size=3)),
    ], input=["data"], input_dim=[2, 3, 8, 8])
    net = Net(np_, "TEST", conv_layout="auto")
    assert net.conv_layout == resolve_conv_layout("auto")
    assert net.conv_layout in ("NCHW", "NHWC")

    # the ambient policy accepts "auto" too
    from poseidon_tpu import config
    with config.policy_scope(conv_layout="auto"):
        net2 = Net(np_, "TEST")
        assert net2.conv_layout == resolve_conv_layout("auto")
