import jax
import jax.numpy as jnp
import numpy as np
import pytest

from poseidon_tpu.core.net import Net, filter_net
from poseidon_tpu.models import zoo
from poseidon_tpu.proto import load_net_from_string
from poseidon_tpu.proto.messages import NetState


def _batch(shapes, rng):
    data = rng.randn(*shapes["data"]).astype(np.float32)
    label = rng.randint(0, 10, size=shapes["label"])
    return {"data": jnp.asarray(data), "label": jnp.asarray(label)}


def test_lenet_shapes_and_forward(rng_np):
    net = Net(zoo.lenet(), phase="TRAIN", source_shapes=zoo.lenet_shapes(4))
    assert net.blob_shapes["conv1"] == (4, 20, 24, 24)
    assert net.blob_shapes["pool1"] == (4, 20, 12, 12)
    assert net.blob_shapes["conv2"] == (4, 50, 8, 8)
    assert net.blob_shapes["ip1"] == (4, 500)
    assert net.blob_shapes["ip2"] == (4, 10)
    params = net.init(jax.random.PRNGKey(0))
    assert params["conv1"]["w"].shape == (20, 1, 5, 5)
    assert params["ip1"]["w"].shape == (500, 800)
    out = net.apply(params, _batch(zoo.lenet_shapes(4), rng_np),
                    rng=jax.random.PRNGKey(1))
    assert out.loss.shape == ()
    assert float(out.loss) == pytest.approx(np.log(10), rel=0.3)


def test_phase_filtering():
    net_param = zoo.lenet(with_accuracy=True)
    train = filter_net(net_param, NetState(phase="TRAIN"))
    test = filter_net(net_param, NetState(phase="TEST"))
    train_names = [l.name for l in train]
    test_names = [l.name for l in test]
    assert "accuracy" not in train_names
    assert "accuracy" in test_names


def test_grad_flows_everywhere(rng_np):
    net = Net(zoo.lenet(with_accuracy=False), phase="TRAIN",
              source_shapes=zoo.lenet_shapes(2))
    params = net.init(jax.random.PRNGKey(0))
    batch = _batch(zoo.lenet_shapes(2), rng_np)

    def loss_fn(p):
        return net.apply(p, batch, rng=jax.random.PRNGKey(0)).loss

    grads = jax.grad(loss_fn)(params)
    for lname, lg in grads.items():
        for pname, g in lg.items():
            assert np.isfinite(np.asarray(g)).all(), (lname, pname)
            assert np.abs(np.asarray(g)).sum() > 0, (lname, pname)


def test_inplace_layers(rng_np):
    # relu1 writes its bottom in place (top == bottom), the Caffe idiom.
    net = Net(zoo.cifar10_quick(), phase="TRAIN",
              source_shapes=zoo.cifar10_shapes(2))
    params = net.init(jax.random.PRNGKey(0))
    out = net.apply(params, _batch(zoo.cifar10_shapes(2), rng_np),
                    rng=jax.random.PRNGKey(1), keep_blobs=True)
    assert np.asarray(out.blobs["pool1"]).min() >= 0  # post-relu view


def test_deploy_net_with_input_decl(rng_np):
    net_param = load_net_from_string("""
    name: "deploy"
    input: "data"
    input_dim: 2 input_dim: 3 input_dim: 8 input_dim: 8
    layers { name: "conv" type: CONVOLUTION bottom: "data" top: "conv"
      convolution_param { num_output: 4 kernel_size: 3
        weight_filler { type: "xavier" } } }
    layers { name: "prob" type: SOFTMAX bottom: "conv" top: "prob" }
    """)
    net = Net(net_param, phase="TEST")
    assert net.blob_shapes["prob"] == (2, 4, 6, 6)
    params = net.init(jax.random.PRNGKey(0))
    x = jnp.asarray(rng_np.randn(2, 3, 8, 8).astype(np.float32))
    out = net.apply(params, {"data": x})
    np.testing.assert_allclose(
        np.asarray(out.outputs["prob"]).sum(axis=1), 1.0, rtol=1e-5)


def test_cifar10_full_builds_and_steps():
    """cifar10_full (pool-before-relu, WITHIN_CHANNEL LRN, decay 250 ip):
    builds, one train step moves params, loss ~ ln(10)."""
    import jax
    from poseidon_tpu.parallel import (CommConfig, build_train_step,
                                       init_train_state, make_mesh)
    from poseidon_tpu.proto.messages import SolverParameter

    net = Net(zoo.cifar10_full(), phase="TRAIN",
              source_shapes=zoo.cifar10_shapes(2))
    assert net.layers[3].lp.lrn_param.norm_region == "WITHIN_CHANNEL"
    sp = SolverParameter(base_lr=0.001, lr_policy="fixed", momentum=0.9,
                         weight_decay=0.004)
    ts = build_train_step(net, sp, make_mesh(), CommConfig(), donate=False)
    params = net.init(jax.random.PRNGKey(0))
    rs = np.random.RandomState(0)
    batch = {"data": jnp.asarray(rs.rand(16, 3, 32, 32).astype(np.float32)),
             "label": jnp.asarray(rs.randint(0, 10, size=(16,)))}
    p, s, m = ts.step(params, init_train_state(params), batch,
                      jax.random.PRNGKey(1))
    assert float(m["loss"]) == pytest.approx(np.log(10), rel=0.3)
    assert np.abs(np.asarray(p["ip1"]["w"]) -
                  np.asarray(params["ip1"]["w"])).max() > 0


def test_googlenet_trains_multidevice():
    """GoogLeNet end-to-end on the 8-device mesh: aux heads (0.3 loss
    weights, train_test.prototxt parity) contribute to the total loss and
    all three heads report; one SGD step moves the deepest inception params.
    bf16 compute keeps the 224x224 CPU run tractable."""
    import jax
    from poseidon_tpu.config import policy_scope
    from poseidon_tpu.parallel import (CommConfig, build_train_step,
                                       init_train_state, make_mesh)
    from poseidon_tpu.proto.messages import SolverParameter

    net = Net(zoo.googlenet(num_classes=16), phase="TRAIN",
              source_shapes=zoo.googlenet_shapes(1))
    sp = SolverParameter(base_lr=0.01, lr_policy="fixed", momentum=0.9)
    mesh = make_mesh()
    with policy_scope(compute_dtype=jnp.bfloat16):
        ts = build_train_step(net, sp, mesh, CommConfig(), donate=False)
        params = net.init(jax.random.PRNGKey(0))
        w0 = np.asarray(params["inception_5b/1x1"]["w"])
        rs = np.random.RandomState(0)
        batch = {
            "data": jnp.asarray(rs.rand(8, 3, 224, 224).astype(np.float32)),
            "label": jnp.asarray(rs.randint(0, 16, size=(8,))),
        }
        p, s, m = ts.step(params, init_train_state(params), batch,
                          jax.random.PRNGKey(1))
    # total loss = main + 0.3*aux1 + 0.3*aux2 (all finite, all reported)
    assert np.isfinite(float(m["loss"]))
    assert {"loss1/loss", "loss2/loss", "loss3"} <= set(m), sorted(m)
    want = (float(m["loss3"]) + 0.3 * float(m["loss1/loss"])
            + 0.3 * float(m["loss2/loss"]))
    assert float(m["loss"]) == pytest.approx(want, rel=0.05)
    # ~ln(16) at init
    assert float(m["loss3"]) == pytest.approx(np.log(16), rel=0.4)
    assert np.abs(np.asarray(p["inception_5b/1x1"]["w"]) - w0).max() > 0


def test_googlenet_builds():
    net = Net(zoo.googlenet(num_classes=100), phase="TRAIN",
              source_shapes=zoo.googlenet_shapes(2))
    assert net.blob_shapes["inception_3a/output"] == (2, 256, 28, 28)
    assert net.blob_shapes["inception_5b/output"] == (2, 1024, 7, 7)
    assert net.blob_shapes["pool5/7x7_s1"] == (2, 1024, 1, 1)
    # three losses in TRAIN phase
    loss_layers = [l for l in net.layers if l.TYPE == "SOFTMAX_LOSS"]
    assert len(loss_layers) == 3


def test_alexnet_builds():
    net = Net(zoo.alexnet(), phase="TRAIN",
              source_shapes=zoo.alexnet_shapes(2))
    assert net.blob_shapes["pool5"] == (2, 256, 6, 6)
    assert net.param_count() > 60_000_000  # AlexNet ~61M params


def test_weight_export_import_roundtrip(rng_np):
    net = Net(zoo.lenet(), phase="TRAIN", source_shapes=zoo.lenet_shapes(2))
    params = net.init(jax.random.PRNGKey(0))
    exported = net.export_weights(params)
    params2 = net.init(jax.random.PRNGKey(42))
    params3 = net.load_weights(params2, exported)
    for l in exported:
        for pd, arr in zip(net.param_defs[l], exported[l]):
            np.testing.assert_array_equal(np.asarray(params3[l][pd.name]), arr)


def test_caffemodel_wire_roundtrip(rng_np, tmp_path):
    from poseidon_tpu.proto.wire import decode_caffemodel, encode_caffemodel
    net = Net(zoo.lenet(), phase="TRAIN", source_shapes=zoo.lenet_shapes(2))
    params = net.init(jax.random.PRNGKey(0))
    blob = encode_caffemodel("LeNet", net.export_weights(params))
    decoded = decode_caffemodel(blob)
    assert set(decoded) == set(net.param_defs)
    np.testing.assert_allclose(
        decoded["conv1"][0].reshape(20, 1, 5, 5),
        np.asarray(params["conv1"]["w"]), rtol=1e-6)


def test_shared_weights_siamese():
    """Caffe's named-param sharing (siamese pattern): two branches share conv
    weights via `param:` names; gradients flow through both uses."""
    from poseidon_tpu.proto.messages import load_net_from_string
    net_param = load_net_from_string("""
    name: "siamese"
    layers { name: "ip_a" type: INNER_PRODUCT bottom: "xa" top: "fa"
      param: "shared_w" param: "shared_b"
      inner_product_param { num_output: 6 weight_filler { type: "xavier" } } }
    layers { name: "ip_b" type: INNER_PRODUCT bottom: "xb" top: "fb"
      param: "shared_w" param: "shared_b"
      inner_product_param { num_output: 6 weight_filler { type: "xavier" } } }
    layers { name: "loss" type: CONTRASTIVE_LOSS
      bottom: "fa" bottom: "fb" bottom: "sim" top: "loss"
      contrastive_loss_param { margin: 1.0 } }
    """)
    shapes = {"xa": (4, 3), "xb": (4, 3), "sim": (4,)}
    net = Net(net_param, "TRAIN", source_shapes=shapes)
    # only the owner layer holds storage
    assert "ip_a" in net.param_defs and "ip_b" not in net.param_defs
    params = net.init(jax.random.PRNGKey(0))
    assert set(params) == {"ip_a"}

    rs = np.random.RandomState(0)
    batch = {"xa": jnp.asarray(rs.randn(4, 3).astype(np.float32)),
             "xb": jnp.asarray(rs.randn(4, 3).astype(np.float32)),
             "sim": jnp.asarray(np.array([1, 0, 1, 0], np.float32))}
    out = net.apply(params, batch, keep_blobs=True)
    # both branches used the same weights
    w = np.asarray(params["ip_a"]["w"])
    np.testing.assert_allclose(
        np.asarray(out.blobs["fb"]),
        np.asarray(batch["xb"]) @ w.T + np.asarray(params["ip_a"]["b"]),
        rtol=1e-5)

    # gradient accumulates from BOTH branches: zeroing one branch's input
    # changes the shared-weight gradient
    def loss_fn(p, b):
        return net.apply(p, b).loss

    g_both = jax.grad(loss_fn)(params, batch)
    batch_zero_b = dict(batch, xb=jnp.zeros_like(batch["xb"]))
    g_one = jax.grad(loss_fn)(params, batch_zero_b)
    assert np.abs(np.asarray(g_both["ip_a"]["w"])).sum() > 0
    assert not np.allclose(np.asarray(g_both["ip_a"]["w"]),
                           np.asarray(g_one["ip_a"]["w"]))

    # round trip: caffemodel export contains BOTH layers' blobs (Caffe's
    # serialization), and loading routes sharer blobs back to owner storage
    exported = net.export_weights(params)
    assert set(exported) == {"ip_a", "ip_b"}
    np.testing.assert_array_equal(exported["ip_a"][0], exported["ip_b"][0])
    reloaded = net.load_weights(net.init(jax.random.PRNGKey(9)), exported)
    np.testing.assert_array_equal(np.asarray(reloaded["ip_a"]["w"]), w)


# --------------------------------------------------------------------------- #
# token-model layers: (batch, sequence, feature) blobs
# --------------------------------------------------------------------------- #

def _token_net(extra=""):
    from poseidon_tpu.proto.messages import load_net_from_string as load
    return Net(load("""
        layers { name: "embed" type: EMBED bottom: "tokens" top: "x"
                 embed_param { input_dim: 97 num_output: 32
                               weight_filler { type: "gaussian" std: 0.5 } } }
        layers { name: "norm" type: RMS_NORM bottom: "x" top: "a"
                 param { decay_mult: 0 } }
        layers { name: "q" type: INNER_PRODUCT bottom: "a" top: "q"
                 inner_product_param { num_output: 32 bias_term: false axis: 2
                     weight_filler { type: "gaussian" std: 0.2 } } }
        layers { name: "att" type: ATTENTION bottom: "q" bottom: "q"
                 bottom: "a" top: "att" attention_param { num_heads: 4 } }
        layers { name: "moe" type: MOE bottom: "att" top: "m" top: "bal"
                 top: "z" loss_weight: 0 loss_weight: 0.01 loss_weight: 0.001
                 moe_param { num_experts: 4 top_k: 2 expert_width: 16
                     weight_filler { type: "gaussian" std: 0.2 } } }
        layers { name: "head" type: INNER_PRODUCT bottom: "m" top: "logits"
                 inner_product_param { num_output: 97 axis: 2
                     weight_filler { type: "gaussian" std: 0.2 } } }
        layers { name: "loss" type: SOFTMAX_LOSS bottom: "logits"
                 bottom: "targets" top: "loss" softmax_param { axis: -1 } }
        """ + extra), "TRAIN",
        source_shapes={"tokens": (3, 8), "targets": (3, 8)})


@pytest.mark.parametrize("blob,shape", [
    ("x", (3, 8, 32)), ("a", (3, 8, 32)), ("q", (3, 8, 32)),
    ("att", (3, 8, 32)), ("m", (3, 8, 32)), ("bal", ()), ("z", ()),
    ("logits", (3, 8, 97)), ("loss", ())])
def test_token_layer_shapes(blob, shape):
    assert _token_net().blob_shapes[blob] == shape


@pytest.mark.parametrize("layer,pname,shape,decay", [
    ("embed", "w", (97, 32), 1.0), ("norm", "g", (32,), 0.0),
    ("q", "w", (32, 32), 1.0), ("moe", "router", (4, 32), 1.0),
    ("moe", "gate", (4, 16, 32), 1.0), ("moe", "up", (4, 16, 32), 1.0),
    ("moe", "down", (4, 32, 16), 1.0), ("head", "w", (97, 32), 1.0),
    ("head", "b", (97,), 1.0)])
def test_token_layer_params(layer, pname, shape, decay):
    net = _token_net()
    pdef = next(p for p in net.param_defs[layer] if p.name == pname)
    assert (pdef.shape, pdef.decay_mult) == (shape, decay)
    if layer == "norm":     # gains start at one whatever the prototxt says
        assert float(net.init(jax.random.PRNGKey(0))["norm"]["g"][0]) == 1.0


def test_token_net_forward_pieces(rng_np):
    """Each token layer against the arithmetic written out: the lookup, the
    norm, a per-token product (axis 2, with a bias), the loss over the last
    axis; the net's loss adds the two weighted MOE tops."""
    net = _token_net()
    params = net.init(jax.random.PRNGKey(1))
    tok = jnp.asarray(rng_np.randint(0, 97, size=(3, 8)))
    tgt = jnp.asarray(rng_np.randint(0, 97, size=(3, 8)))
    out = net.apply(params, {"tokens": tok, "targets": tgt}, train=True,
                    keep_blobs=True)
    b = {k: np.asarray(v, np.float64) for k, v in out.blobs.items()}
    emb = np.asarray(params["embed"]["w"], np.float64)
    np.testing.assert_allclose(b["x"], emb[np.asarray(tok)], rtol=1e-6)
    rms = np.sqrt((b["x"] ** 2).mean(-1, keepdims=True) + 1e-5)
    np.testing.assert_allclose(b["a"], b["x"] / rms, rtol=1e-5)
    np.testing.assert_allclose(
        b["logits"], b["m"] @ np.asarray(params["head"]["w"], np.float64).T
        + np.asarray(params["head"]["b"], np.float64), rtol=1e-4, atol=1e-5)
    lse = np.log(np.exp(b["logits"]).sum(-1))
    picked = np.take_along_axis(b["logits"], np.asarray(tgt)[..., None], -1)
    np.testing.assert_allclose(b["loss"], (lse - picked[..., 0]).mean(),
                               rtol=1e-5)
    np.testing.assert_allclose(
        float(out.loss), b["loss"] + 0.01 * b["bal"] + 0.001 * b["z"],
        rtol=1e-5)
    assert net.kernel_routes == {"att": "attention=dense",
                                 "moe": "grouped_matmul=ragged_dot"}


def test_attention_is_causal_and_positional(rng_np):
    """A change to a later token leaves every earlier position's attention
    output untouched (causal); rolling the sequence changes position 0's
    (rotary positions are absolute in q.k only through their difference, so
    a roll moves which keys precede it)."""
    net = _token_net()
    params = net.init(jax.random.PRNGKey(2))
    tok = rng_np.randint(0, 97, size=(3, 8))
    tgt = jnp.zeros((3, 8), jnp.int32)
    run = lambda t: np.asarray(net.apply(  # noqa: E731
        params, {"tokens": jnp.asarray(t), "targets": tgt}, train=True,
        keep_blobs=True).blobs["att"])
    base = run(tok)
    later = tok.copy()
    later[:, 5] = (later[:, 5] + 1) % 97
    moved = run(later)
    np.testing.assert_array_equal(moved[:, :5], base[:, :5])
    assert np.abs(moved[:, 5:] - base[:, 5:]).max() > 1e-4


def test_inner_product_axis_default_still_flattens():
    """axis 1 (the default) is the classic flatten: a 4-D bottom becomes
    (N, K), and its weight is (M, C*H*W)."""
    from poseidon_tpu.proto.messages import load_net_from_string as load
    net = Net(load("""layers { name: "ip" type: INNER_PRODUCT bottom: "data"
        top: "ip" inner_product_param { num_output: 5 } }"""), "TRAIN",
        source_shapes={"data": (2, 3, 4, 4)})
    assert net.blob_shapes["ip"] == (2, 5)
    assert net.param_defs["ip"][0].shape == (5, 48)
