import os

import pytest

from poseidon_tpu.proto.messages import ParamSpec

from poseidon_tpu.proto import (
    load_net_from_string, load_solver_from_string, parse,
)
from poseidon_tpu.proto.messages import load_net, load_solver

REF = "/root/reference"

LENET_SNIPPET = """
name: "TestNet"
layers {
  name: "conv1"
  type: CONVOLUTION
  bottom: "data"
  top: "conv1"
  blobs_lr: 1
  blobs_lr: 2
  convolution_param {
    num_output: 20
    kernel_size: 5
    stride: 1
    weight_filler { type: "xavier" }
    bias_filler { type: "constant" }
  }
}
layers {
  name: "relu1"
  type: RELU
  bottom: "conv1"
  top: "conv1"
}
"""


def test_parse_v1_layers():
    net = load_net_from_string(LENET_SNIPPET)
    assert net.name == "TestNet"
    assert len(net.layers) == 2
    c = net.layers[0]
    assert c.canonical_type() == "CONVOLUTION"
    assert c.convolution_param.num_output == 20
    assert c.convolution_param.kernel_size == 5
    assert c.convolution_param.weight_filler.type == "xavier"
    assert c.blobs_lr == [1, 2]
    assert c.param_spec(0).lr_mult == 1
    assert c.param_spec(1).lr_mult == 2
    assert net.layers[1].canonical_type() == "RELU"


def test_parse_v2_layer_format():
    net = load_net_from_string("""
    layer {
      name: "fc"
      type: "InnerProduct"
      bottom: "x" top: "y"
      param { lr_mult: 1 decay_mult: 1 }
      param { lr_mult: 2 decay_mult: 0 }
      inner_product_param { num_output: 10 }
    }
    """)
    fc = net.layers[0]
    assert fc.canonical_type() == "INNER_PRODUCT"
    assert fc.param_spec(1).lr_mult == 2
    assert fc.param_spec(1).decay_mult == 0


def test_parse_solver():
    sp = load_solver_from_string("""
    net: "train_val.prototxt"
    base_lr: 0.01
    lr_policy: "step"
    gamma: 0.1
    stepsize: 100000
    display: 20
    max_iter: 450000
    momentum: 0.9
    weight_decay: 0.0005
    solver_mode: GPU
    solver_type: NESTEROV
    test_iter: 1000
    test_interval: 1000
    random_seed: 7
    """)
    assert sp.base_lr == pytest.approx(0.01)
    assert sp.lr_policy == "step"
    assert sp.solver_type == "NESTEROV"
    assert sp.solver_mode == "GPU"
    assert sp.test_iter == [1000]
    assert sp.random_seed == 7


def test_comments_strings_escapes():
    node = parse('a: 1 # comment\nb: "hi \\"there\\"" c: -1.5e-3 d: true')
    assert node.get("a") == 1
    assert node.get("b") == 'hi "there"'
    assert node.get("c") == pytest.approx(-0.0015)
    assert node.get("d") is True


@pytest.mark.skipif(not os.path.isdir(REF), reason="reference not mounted")
@pytest.mark.parametrize("relpath", [
    "examples/mnist/lenet_train_test.prototxt",
    "examples/cifar10/cifar10_quick_train_test.prototxt",
    "models/bvlc_alexnet/train_val.prototxt",
    "models/bvlc_googlenet/train_test.prototxt",
    "models/bvlc_reference_caffenet/train_val.prototxt",
])
def test_parse_reference_model_zoo(relpath):
    path = os.path.join(REF, relpath)
    if not os.path.exists(path):
        pytest.skip(f"{relpath} not in reference")
    net = load_net(path)
    assert net.layers, relpath
    for lp in net.layers:
        lp.canonical_type()  # every layer type must resolve


@pytest.mark.skipif(not os.path.isdir(REF), reason="reference not mounted")
@pytest.mark.parametrize("relpath", [
    "examples/mnist/lenet_solver.prototxt",
    "examples/cifar10/cifar10_quick_solver.prototxt",
    "models/bvlc_alexnet/solver.prototxt",
    "models/bvlc_googlenet/quick_solver.prototxt",
])
def test_parse_reference_solvers(relpath):
    path = os.path.join(REF, relpath)
    if not os.path.exists(path):
        pytest.skip(f"{relpath} not in reference")
    sp = load_solver(path)
    assert sp.base_lr > 0


def test_to_prototxt_roundtrip():
    from poseidon_tpu.models import zoo
    from poseidon_tpu.proto.messages import net_to_prototxt
    from poseidon_tpu.core.net import Net
    for build_fn, shapes_fn in [(zoo.lenet, zoo.lenet_shapes),
                                (zoo.googlenet, zoo.googlenet_shapes)]:
        net_param = build_fn()
        text = net_to_prototxt(net_param)
        reparsed = load_net_from_string(text)
        assert [l.name for l in reparsed.layers] == \
            [l.name for l in net_param.layers]
        # the round-tripped net must build to identical blob shapes
        a = Net(net_param, "TRAIN", shapes_fn(2))
        b = Net(reparsed, "TRAIN", shapes_fn(2))
        assert a.blob_shapes == b.blob_shapes
        # enum identifiers must be unquoted (Caffe's parser requires it);
        # default-valued fields (e.g. pool: MAX) are correctly omitted
        assert 'type: CONVOLUTION' in text
        assert 'type: "CONVOLUTION"' not in text
    assert 'pool: AVE' in text  # googlenet's non-default pooling survives


# --------------------------------------------------------------------------- #
# V0 legacy format upgrade (upgrade_proto.cpp:15-506)
# --------------------------------------------------------------------------- #

V0_NET = """
name: "V0Net"
layers {
  layer {
    name: "mnist" type: "data" source: "train_db" batchsize: 8
    scale: 0.00390625 cropsize: 24 mirror: true meanfile: "mean.bp"
  }
  top: "data" top: "label"
}
layers {
  layer { name: "pad1" type: "padding" pad: 2 }
  bottom: "data" top: "pad1"
}
layers {
  layer {
    name: "conv1" type: "conv" num_output: 6 kernelsize: 5 stride: 1
    group: 2 biasterm: true
    weight_filler { type: "xavier" }
    blobs_lr: 1.0 blobs_lr: 2.0 weight_decay: 1.0 weight_decay: 0.0
  }
  bottom: "pad1" top: "conv1"
}
layers { layer { name: "relu1" type: "relu" } bottom: "conv1" top: "conv1" }
layers {
  layer { name: "pool1" type: "pool" pool: MAX kernelsize: 2 stride: 2 }
  bottom: "conv1" top: "pool1"
}
layers {
  layer { name: "drop" type: "dropout" dropout_ratio: 0.3 }
  bottom: "pool1" top: "pool1"
}
layers {
  layer { name: "norm" type: "lrn" local_size: 3 alpha: 0.0001 beta: 0.5 }
  bottom: "pool1" top: "norm"
}
layers {
  layer { name: "ip1" type: "innerproduct" num_output: 10
          weight_filler { type: "gaussian" std: 0.01 } }
  bottom: "norm" top: "ip1"
}
layers {
  layer { name: "loss" type: "softmax_loss" }
  bottom: "ip1" bottom: "label" top: "loss"
}
"""


def test_v0_net_upgrades():
    net = load_net_from_string(V0_NET)
    types = [l.type for l in net.layers]
    # padding layer is deleted, its pad folded into conv1
    assert "padding" not in " ".join(types)
    assert types == ["DATA", "CONVOLUTION", "RELU", "POOLING", "DROPOUT",
                     "LRN", "INNER_PRODUCT", "SOFTMAX_LOSS"]
    conv = net.layers[1]
    assert conv.name == "conv1"
    assert conv.bottom == ["data"]          # rewired past the padding layer
    assert conv.convolution_param.pad == 2  # folded from the padding layer
    assert conv.convolution_param.num_output == 6
    assert conv.convolution_param.kernel_size == 5
    assert conv.convolution_param.group == 2
    assert conv.convolution_param.weight_filler.type == "xavier"
    assert conv.blobs_lr == [1.0, 2.0]
    assert conv.weight_decay == [1.0, 0.0]
    data = net.layers[0]
    assert data.data_param.source == "train_db"
    assert data.data_param.batch_size == 8
    # V0 scale/cropsize/mirror/meanfile land in transform_param
    assert data.transform_param.scale == pytest.approx(0.00390625)
    assert data.transform_param.crop_size == 24
    assert data.transform_param.mirror is True
    assert data.transform_param.mean_file == "mean.bp"
    pool = net.layers[3]
    assert pool.pooling_param.pool == "MAX"
    assert pool.pooling_param.kernel_size == 2
    assert net.layers[4].dropout_param.dropout_ratio == pytest.approx(0.3)
    assert net.layers[5].lrn_param.local_size == 3
    assert net.layers[6].inner_product_param.num_output == 10
    # the upgraded net must actually build and run shape inference
    from poseidon_tpu.core.net import Net
    built = Net(net, "TRAIN", source_shapes={"data": (8, 2, 24, 24),
                                             "label": (8,)})
    assert built.blob_shapes["conv1"] == (8, 6, 24, 24)


def test_v0_unknown_field_raises():
    from poseidon_tpu.proto.prototxt import PrototxtError
    bad = """
    layers { layer { name: "x" type: "conv" num_output: 2 bogus_field: 1 }
             bottom: "data" top: "x" }
    """
    with pytest.raises(PrototxtError, match="bogus_field"):
        load_net_from_string(bad)


def test_v1_data_transform_migration():
    net = load_net_from_string("""
    layers {
      name: "d" type: DATA top: "data" top: "label"
      data_param { source: "db" batch_size: 4 scale: 0.5 crop_size: 12
                   mirror: true }
    }
    layers { name: "s" type: SILENCE bottom: "data" }
    layers { name: "s2" type: SILENCE bottom: "label" }
    """)
    t = net.layers[0].transform_param
    assert t.scale == 0.5 and t.crop_size == 12 and t.mirror is True


TOKEN_LAYERS = """
layer { name: "embed" type: "Embed" bottom: "tokens" top: "x"
        embed_param { input_dim: 512 num_output: 64
                      weight_filler { type: "gaussian" std: 0.02 } } }
layers { name: "n" type: RMS_NORM bottom: "x" top: "a"
         param { decay_mult: 0 } rms_norm_param { eps: 1e-6 } }
layers { name: "q" type: INNER_PRODUCT bottom: "a" top: "q"
         inner_product_param { num_output: 64 bias_term: false axis: 2 } }
layers { name: "att" type: ATTENTION bottom: "q" bottom: "q" bottom: "q"
         top: "att" attention_param { num_heads: 4 rope_theta: 50000 } }
layers { name: "moe" type: MOE bottom: "att" top: "m" top: "bal" top: "z"
         loss_weight: 0 loss_weight: 0.01 loss_weight: 0.001
         moe_param { num_experts: 8 top_k: 2 expert_width: 32 } }
layers { name: "loss" type: SOFTMAX_LOSS bottom: "m" bottom: "targets"
         top: "loss" softmax_param { axis: -1 } }
layer { name: "act" type: "SiLUGate" bottom: "q" bottom: "q" top: "act" }
layers { name: "shared" type: INNER_PRODUCT bottom: "a" top: "s"
         param { name: "q_w" } param { name: "q_b" decay_mult: 0 }
         inner_product_param { num_output: 1 axis: 2 } }
layers { name: "nll" type: SOFTMAX_NLL bottom: "m" bottom: "targets"
         top: "nll" }
layer { name: "exit" type: "ExitLoss" bottom: "nll" bottom: "nll"
        bottom: "s" top: "exit" top: "mass1" top: "mass2"
        exit_loss_param { entropy_weight: 0.1 } }
layers { name: "conv" type: SHORT_CONV bottom: "q" top: "qc"
         kda_param { kernel_size: 3
                     weight_filler { type: "uniform" min: -0.5 max: 0.5 } } }
layer { name: "l2" type: "L2Norm" bottom: "qc" top: "qn"
        kda_param { num_heads: 4 eps: 1e-5 } }
layers { name: "decay" type: KDA_DECAY bottom: "q" top: "g" top: "g_mean"
         kda_param { num_heads: 4 a_max: 8 dt_min: 0.01 } }
layer { name: "scan" type: "KDAScan" bottom: "qn" bottom: "qn" bottom: "q"
        bottom: "g" bottom: "beta" top: "o" kda_param { num_heads: 4 } }
layers { name: "mla" type: ATTENTION bottom: "q" bottom: "k" bottom: "v"
         bottom: "kpe" top: "lat"
         attention_param { num_heads: 4 rope: false value_head_dim: 8 } }
"""


@pytest.mark.parametrize("layer,ctype,field,want", [
    ("embed", "EMBED", "embed_param.input_dim", 512),
    ("embed", "EMBED", "embed_param.weight_filler.std", 0.02),
    ("n", "RMS_NORM", "rms_norm_param.eps", 1e-6),
    ("q", "INNER_PRODUCT", "inner_product_param.axis", 2),
    ("q", "INNER_PRODUCT", "inner_product_param.bias_term", False),
    ("att", "ATTENTION", "attention_param.num_heads", 4),
    ("att", "ATTENTION", "attention_param.rope_theta", 50000.0),
    ("moe", "MOE", "moe_param.top_k", 2),
    ("moe", "MOE", "moe_param.expert_width", 32),
    ("moe", "MOE", "moe_param.num_experts", 8),
    ("moe", "MOE", "loss_weight", [0.0, 0.01, 0.001]),
    ("loss", "SOFTMAX_LOSS", "softmax_param.axis", -1),
    ("act", "SILU_GATE", "bottom", ["q", "q"]),
    ("shared", "INNER_PRODUCT", "param", [
        ParamSpec(name="q_w"), ParamSpec(name="q_b", decay_mult=0.0)]),
    ("nll", "SOFTMAX_NLL", "top", ["nll"]),
    ("exit", "EXIT_LOSS", "exit_loss_param.entropy_weight", 0.1),
    ("exit", "EXIT_LOSS", "top", ["exit", "mass1", "mass2"]),
    ("conv", "SHORT_CONV", "kda_param.kernel_size", 3),
    ("conv", "SHORT_CONV", "kda_param.weight_filler.min", -0.5),
    ("l2", "L2_NORM", "kda_param.eps", 1e-5),
    ("decay", "KDA_DECAY", "kda_param.a_max", 8.0),
    ("decay", "KDA_DECAY", "kda_param.dt_min", 0.01),
    ("decay", "KDA_DECAY", "top", ["g", "g_mean"]),
    ("scan", "KDA_SCAN", "bottom", ["qn", "qn", "q", "g", "beta"]),
    ("scan", "KDA_SCAN", "kda_param.num_heads", 4),
    ("mla", "ATTENTION", "attention_param.value_head_dim", 8),
    ("mla", "ATTENTION", "bottom", ["q", "k", "v", "kpe"]),
])
def test_parse_token_layers(layer, ctype, field, want):
    """The token model's layer types and fields, in the V1 and the V2
    spelling, and back out through net_to_prototxt unchanged."""
    from poseidon_tpu.proto.messages import net_to_prototxt
    net = load_net_from_string(TOKEN_LAYERS)
    for n in (net, load_net_from_string(net_to_prototxt(net))):
        lp = next(l for l in n.layers if l.name == layer)
        assert lp.canonical_type() == ctype
        got = lp
        for part in field.split("."):
            got = getattr(got, part)
        assert got == want


def test_parse_adam_solver_fields():
    sp = load_solver_from_string(
        'net: "x" solver_type: ADAM momentum: 0.9 momentum2: 0.95 '
        'delta: 1e-8 clip_gradients: 1.0 lr_policy: "cosine"')
    assert (sp.solver_type, sp.momentum2, sp.clip_gradients) == \
        ("ADAM", 0.95, 1.0)
    defaults = load_solver_from_string('net: "x"')
    assert defaults.clip_gradients < 0 and defaults.momentum2 == 0.999
