"""Model zoo: programmatic builders for the reference's benchmark networks.

The reference ships these as prototxt (``models/bvlc_alexnet``,
``models/bvlc_googlenet``, ``examples/mnist``, ``examples/cifar10``). Here the
same architectures are constructed programmatically as ``NetParameter``s (the
public, well-known LeNet / CIFAR-10-quick / AlexNet / GoogLeNet definitions);
``to_prototxt`` round-trips them to text for zoo compatibility. Each builder
takes the batch size so the same definition serves train/test/bench shapes.

Beside them, the token models that train through the same ``Net`` (each
builder with no arguments writes its whole published model; the committed
``examples/lm/*_train.prototxt`` are the cut their headers state):
``olmoe``, ``ouro``, ``zaya1``, ``trinity_mini``, ``kimi_linear``,
``smallthinker``, ``olmo_hybrid``, ``granite_hybrid``, ``glm_flash``
(GLM-4.7-Flash: latent attention whose rotary part rotates in every layer
and a multi-token-prediction module that shares the embedding and the
head), ``xing4`` (Xing4.0-29B-A4B: ``glm_flash``'s block on a residual
stream of four hidden states, manifold-constrained hyper-connections, with
YaRN's rotary frequencies) and ``nemotron_h`` (NVIDIA-Nemotron-3-Nano-30B-A3B:
one sub-layer a layer by a pattern string, Mamba-2 with eight groups of
B / C, ungated squared-ReLU experts).
"""

from __future__ import annotations

import math
from typing import Dict, List, Optional, Tuple

from ..proto.messages import (  # noqa: F401
    net_to_prototxt as to_prototxt,
    AccuracyParameter, ConvolutionParameter, DropoutParameter, FillerParameter,
    InnerProductParameter, LayerParameter, LRNParameter, NetParameter,
    NetStateRule, ParamSpec, PoolingParameter,
)


def gaussian(std: float) -> FillerParameter:
    return FillerParameter(type="gaussian", std=std)


def constant(value: float = 0.0) -> FillerParameter:
    return FillerParameter(type="constant", value=value)


def xavier() -> FillerParameter:
    return FillerParameter(type="xavier")


def conv(
    name: str, bottom: str, top: str, num_output: int, kernel: int,
    stride: int = 1, pad: int = 0, group: int = 1,
    weight_filler: Optional[FillerParameter] = None,
    bias_value: float = 0.0,
    lr: Tuple[float, float] = (1.0, 2.0),
    decay: Tuple[float, float] = (1.0, 0.0),
) -> LayerParameter:
    return LayerParameter(
        name=name, type="CONVOLUTION", bottom=[bottom], top=[top],
        blobs_lr=list(lr), weight_decay=list(decay),
        convolution_param=ConvolutionParameter(
            num_output=num_output, kernel_size=kernel, stride=stride, pad=pad,
            group=group, weight_filler=weight_filler or xavier(),
            bias_filler=constant(bias_value)))


def ip(
    name: str, bottom: str, top: str, num_output: int,
    weight_filler: Optional[FillerParameter] = None,
    bias_value: float = 0.0,
    lr: Tuple[float, float] = (1.0, 2.0),
    decay: Tuple[float, float] = (1.0, 0.0),
) -> LayerParameter:
    return LayerParameter(
        name=name, type="INNER_PRODUCT", bottom=[bottom], top=[top],
        blobs_lr=list(lr), weight_decay=list(decay),
        inner_product_param=InnerProductParameter(
            num_output=num_output, weight_filler=weight_filler or xavier(),
            bias_filler=constant(bias_value)))


def pool(name: str, bottom: str, top: str, method: str, kernel: int,
         stride: int, pad: int = 0) -> LayerParameter:
    return LayerParameter(
        name=name, type="POOLING", bottom=[bottom], top=[top],
        pooling_param=PoolingParameter(pool=method, kernel_size=kernel,
                                       stride=stride, pad=pad))


def relu(name: str, blob: str) -> LayerParameter:
    return LayerParameter(name=name, type="RELU", bottom=[blob], top=[blob])


def lrn(name: str, bottom: str, top: str, local_size: int = 5,
        alpha: float = 1e-4, beta: float = 0.75,
        norm_region: str = "ACROSS_CHANNELS") -> LayerParameter:
    return LayerParameter(
        name=name, type="LRN", bottom=[bottom], top=[top],
        lrn_param=LRNParameter(local_size=local_size, alpha=alpha, beta=beta,
                               norm_region=norm_region))


def dropout(name: str, blob: str, ratio: float = 0.5) -> LayerParameter:
    return LayerParameter(name=name, type="DROPOUT", bottom=[blob], top=[blob],
                          dropout_param=DropoutParameter(dropout_ratio=ratio))


def softmax_loss(name: str, bottoms: List[str], top: str = "loss") -> LayerParameter:
    return LayerParameter(name=name, type="SOFTMAX_LOSS", bottom=bottoms,
                          top=[top])


def accuracy(name: str, bottoms: List[str], top: str = "accuracy",
             top_k: int = 1, test_only: bool = True) -> LayerParameter:
    lp = LayerParameter(name=name, type="ACCURACY", bottom=bottoms, top=[top],
                        accuracy_param=AccuracyParameter(top_k=top_k))
    if test_only:
        lp.include = [NetStateRule(phase="TEST")]
    return lp


# --------------------------------------------------------------------------- #
# LeNet (examples/mnist) — the minimum end-to-end slice of SURVEY.md §7.2
# --------------------------------------------------------------------------- #

def lenet(with_accuracy: bool = True) -> NetParameter:
    layers = [
        conv("conv1", "data", "conv1", 20, 5, lr=(1, 2), decay=(1, 0)),
        pool("pool1", "conv1", "pool1", "MAX", 2, 2),
        conv("conv2", "pool1", "conv2", 50, 5),
        pool("pool2", "conv2", "pool2", "MAX", 2, 2),
        ip("ip1", "pool2", "ip1", 500),
        relu("relu1", "ip1"),
        ip("ip2", "ip1", "ip2", 10),
        softmax_loss("loss", ["ip2", "label"]),
    ]
    if with_accuracy:
        layers.insert(-1, accuracy("accuracy", ["ip2", "label"]))
    return NetParameter(name="LeNet", layers=layers)


def lenet_shapes(batch: int) -> Dict[str, tuple]:
    return {"data": (batch, 1, 28, 28), "label": (batch,)}


# --------------------------------------------------------------------------- #
# CIFAR-10 quick (examples/cifar10)
# --------------------------------------------------------------------------- #

def cifar10_quick(with_accuracy: bool = True) -> NetParameter:
    layers = [
        conv("conv1", "data", "conv1", 32, 5, pad=2, weight_filler=gaussian(1e-4)),
        pool("pool1", "conv1", "pool1", "MAX", 3, 2),
        relu("relu1", "pool1"),
        conv("conv2", "pool1", "conv2", 32, 5, pad=2, weight_filler=gaussian(0.01)),
        relu("relu2", "conv2"),
        pool("pool2", "conv2", "pool2", "AVE", 3, 2),
        conv("conv3", "pool2", "conv3", 64, 5, pad=2, weight_filler=gaussian(0.01)),
        relu("relu3", "conv3"),
        pool("pool3", "conv3", "pool3", "AVE", 3, 2),
        ip("ip1", "pool3", "ip1", 64, weight_filler=gaussian(0.1)),
        ip("ip2", "ip1", "ip2", 10, weight_filler=gaussian(0.1)),
        softmax_loss("loss", ["ip2", "label"]),
    ]
    if with_accuracy:
        layers.insert(-1, accuracy("accuracy", ["ip2", "label"]))
    return NetParameter(name="CIFAR10_quick", layers=layers)


def cifar10_full(with_accuracy: bool = True) -> NetParameter:
    """examples/cifar10/cifar10_full_train_test.prototxt: the deeper CIFAR
    config — pool-before-relu stem, WITHIN_CHANNEL LRNs, heavy ip decay."""
    layers = [
        conv("conv1", "data", "conv1", 32, 5, pad=2,
             weight_filler=gaussian(1e-4)),
        pool("pool1", "conv1", "pool1", "MAX", 3, 2),
        relu("relu1", "pool1"),
        lrn("norm1", "pool1", "norm1", local_size=3, alpha=5e-5, beta=0.75,
            norm_region="WITHIN_CHANNEL"),
        conv("conv2", "norm1", "conv2", 32, 5, pad=2,
             weight_filler=gaussian(0.01)),
        relu("relu2", "conv2"),
        pool("pool2", "conv2", "pool2", "AVE", 3, 2),
        lrn("norm2", "pool2", "norm2", local_size=3, alpha=5e-5, beta=0.75,
            norm_region="WITHIN_CHANNEL"),
        conv("conv3", "norm2", "conv3", 64, 5, pad=2,
             weight_filler=gaussian(0.01), lr=(1, 1), decay=(1, 0)),
        relu("relu3", "conv3"),
        pool("pool3", "conv3", "pool3", "AVE", 3, 2),
        ip("ip1", "pool3", "ip1", 10, weight_filler=gaussian(0.01),
           decay=(250.0, 0.0)),
        softmax_loss("loss", ["ip1", "label"]),
    ]
    if with_accuracy:
        layers.insert(-1, accuracy("accuracy", ["ip1", "label"]))
    return NetParameter(name="CIFAR10_full", layers=layers)


def cifar10_shapes(batch: int) -> Dict[str, tuple]:
    return {"data": (batch, 3, 32, 32), "label": (batch,)}


# --------------------------------------------------------------------------- #
# AlexNet (models/bvlc_alexnet) — the FC-heavy SFB benchmark model
# --------------------------------------------------------------------------- #

def alexnet(num_classes: int = 1000, with_accuracy: bool = True) -> NetParameter:
    layers = [
        conv("conv1", "data", "conv1", 96, 11, stride=4,
             weight_filler=gaussian(0.01)),
        relu("relu1", "conv1"),
        lrn("norm1", "conv1", "norm1"),
        pool("pool1", "norm1", "pool1", "MAX", 3, 2),
        conv("conv2", "pool1", "conv2", 256, 5, pad=2, group=2,
             weight_filler=gaussian(0.01), bias_value=0.1),
        relu("relu2", "conv2"),
        lrn("norm2", "conv2", "norm2"),
        pool("pool2", "norm2", "pool2", "MAX", 3, 2),
        conv("conv3", "pool2", "conv3", 384, 3, pad=1,
             weight_filler=gaussian(0.01)),
        relu("relu3", "conv3"),
        conv("conv4", "conv3", "conv4", 384, 3, pad=1, group=2,
             weight_filler=gaussian(0.01), bias_value=0.1),
        relu("relu4", "conv4"),
        conv("conv5", "conv4", "conv5", 256, 3, pad=1, group=2,
             weight_filler=gaussian(0.01), bias_value=0.1),
        relu("relu5", "conv5"),
        pool("pool5", "conv5", "pool5", "MAX", 3, 2),
        ip("fc6", "pool5", "fc6", 4096, weight_filler=gaussian(0.005),
           bias_value=0.1),
        relu("relu6", "fc6"),
        dropout("drop6", "fc6", 0.5),
        ip("fc7", "fc6", "fc7", 4096, weight_filler=gaussian(0.005),
           bias_value=0.1),
        relu("relu7", "fc7"),
        dropout("drop7", "fc7", 0.5),
        ip("fc8", "fc7", "fc8", num_classes, weight_filler=gaussian(0.01)),
        softmax_loss("loss", ["fc8", "label"]),
    ]
    if with_accuracy:
        layers.insert(-1, accuracy("accuracy", ["fc8", "label"]))
    return NetParameter(name="AlexNet", layers=layers)


def alexnet_shapes(batch: int) -> Dict[str, tuple]:
    return {"data": (batch, 3, 227, 227), "label": (batch,)}


# --------------------------------------------------------------------------- #
# GoogLeNet (models/bvlc_googlenet) — the conv-heavy dense-psum benchmark model
# --------------------------------------------------------------------------- #

def _inception(name: str, bottom: str, c1: int, c3r: int, c3: int,
               c5r: int, c5: int, cp: int) -> Tuple[List[LayerParameter], str]:
    """One inception module; returns (layers, output blob name)."""
    n = f"inception_{name}"
    ls = [
        conv(f"{n}/1x1", bottom, f"{n}/1x1", c1, 1,
             weight_filler=xavier(), bias_value=0.2),
        relu(f"{n}/relu_1x1", f"{n}/1x1"),
        conv(f"{n}/3x3_reduce", bottom, f"{n}/3x3_reduce", c3r, 1,
             weight_filler=xavier(), bias_value=0.2),
        relu(f"{n}/relu_3x3_reduce", f"{n}/3x3_reduce"),
        conv(f"{n}/3x3", f"{n}/3x3_reduce", f"{n}/3x3", c3, 3, pad=1,
             weight_filler=xavier(), bias_value=0.2),
        relu(f"{n}/relu_3x3", f"{n}/3x3"),
        conv(f"{n}/5x5_reduce", bottom, f"{n}/5x5_reduce", c5r, 1,
             weight_filler=xavier(), bias_value=0.2),
        relu(f"{n}/relu_5x5_reduce", f"{n}/5x5_reduce"),
        conv(f"{n}/5x5", f"{n}/5x5_reduce", f"{n}/5x5", c5, 5, pad=2,
             weight_filler=xavier(), bias_value=0.2),
        relu(f"{n}/relu_5x5", f"{n}/5x5"),
        pool(f"{n}/pool", bottom, f"{n}/pool", "MAX", 3, 1, pad=1),
        conv(f"{n}/pool_proj", f"{n}/pool", f"{n}/pool_proj", cp, 1,
             weight_filler=xavier(), bias_value=0.2),
        relu(f"{n}/relu_pool_proj", f"{n}/pool_proj"),
        LayerParameter(
            name=f"{n}/output", type="CONCAT",
            bottom=[f"{n}/1x1", f"{n}/3x3", f"{n}/5x5", f"{n}/pool_proj"],
            top=[f"{n}/output"]),
    ]
    return ls, f"{n}/output"


def _aux_head(tag: str, bottom: str, num_classes: int) -> List[LayerParameter]:
    p = f"loss{tag}"
    return [
        pool(f"{p}/ave_pool", bottom, f"{p}/ave_pool", "AVE", 5, 3),
        conv(f"{p}/conv", f"{p}/ave_pool", f"{p}/conv", 128, 1,
             weight_filler=xavier(), bias_value=0.2),
        relu(f"{p}/relu_conv", f"{p}/conv"),
        ip(f"{p}/fc", f"{p}/conv", f"{p}/fc", 1024,
           weight_filler=xavier(), bias_value=0.2),
        relu(f"{p}/relu_fc", f"{p}/fc"),
        dropout(f"{p}/drop_fc", f"{p}/fc", 0.7),
        ip(f"{p}/classifier", f"{p}/fc", f"{p}/classifier", num_classes,
           weight_filler=xavier()),
        LayerParameter(
            name=f"{p}/loss", type="SOFTMAX_LOSS",
            bottom=[f"{p}/classifier", "label"], top=[f"{p}/loss"],
            loss_weight=[0.3], include=[NetStateRule(phase="TRAIN")]),
    ]


def googlenet(num_classes: int = 1000, with_accuracy: bool = True,
              aux_heads: bool = True) -> NetParameter:
    layers: List[LayerParameter] = [
        conv("conv1/7x7_s2", "data", "conv1/7x7_s2", 64, 7, stride=2, pad=3,
             weight_filler=xavier(), bias_value=0.2),
        relu("conv1/relu_7x7", "conv1/7x7_s2"),
        pool("pool1/3x3_s2", "conv1/7x7_s2", "pool1/3x3_s2", "MAX", 3, 2),
        lrn("pool1/norm1", "pool1/3x3_s2", "pool1/norm1"),
        conv("conv2/3x3_reduce", "pool1/norm1", "conv2/3x3_reduce", 64, 1,
             weight_filler=xavier(), bias_value=0.2),
        relu("conv2/relu_3x3_reduce", "conv2/3x3_reduce"),
        conv("conv2/3x3", "conv2/3x3_reduce", "conv2/3x3", 192, 3, pad=1,
             weight_filler=xavier(), bias_value=0.2),
        relu("conv2/relu_3x3", "conv2/3x3"),
        lrn("conv2/norm2", "conv2/3x3", "conv2/norm2"),
        pool("pool2/3x3_s2", "conv2/norm2", "pool2/3x3_s2", "MAX", 3, 2),
    ]
    cur = "pool2/3x3_s2"

    cfgs = {
        "3a": (64, 96, 128, 16, 32, 32),
        "3b": (128, 128, 192, 32, 96, 64),
        "4a": (192, 96, 208, 16, 48, 64),
        "4b": (160, 112, 224, 24, 64, 64),
        "4c": (128, 128, 256, 24, 64, 64),
        "4d": (112, 144, 288, 32, 64, 64),
        "4e": (256, 160, 320, 32, 128, 128),
        "5a": (256, 160, 320, 32, 128, 128),
        "5b": (384, 192, 384, 48, 128, 128),
    }
    for tag in ("3a", "3b"):
        ls, cur = _inception(tag, cur, *cfgs[tag])
        layers += ls
    layers.append(pool("pool3/3x3_s2", cur, "pool3/3x3_s2", "MAX", 3, 2))
    cur = "pool3/3x3_s2"
    for tag in ("4a", "4b", "4c", "4d", "4e"):
        ls, cur = _inception(tag, cur, *cfgs[tag])
        layers += ls
        if aux_heads and tag == "4a":
            layers += _aux_head("1", cur, num_classes)
        if aux_heads and tag == "4d":
            layers += _aux_head("2", cur, num_classes)
    layers.append(pool("pool4/3x3_s2", cur, "pool4/3x3_s2", "MAX", 3, 2))
    cur = "pool4/3x3_s2"
    for tag in ("5a", "5b"):
        ls, cur = _inception(tag, cur, *cfgs[tag])
        layers += ls
    layers += [
        pool("pool5/7x7_s1", cur, "pool5/7x7_s1", "AVE", 7, 1),
        dropout("pool5/drop_7x7_s1", "pool5/7x7_s1", 0.4),
        ip("loss3/classifier", "pool5/7x7_s1", "loss3/classifier", num_classes,
           weight_filler=xavier()),
        softmax_loss("loss3/loss3", ["loss3/classifier", "label"], "loss3"),
    ]
    if with_accuracy:
        layers.insert(-1, accuracy("loss3/top-1", ["loss3/classifier", "label"]))
    return NetParameter(name="GoogleNet", layers=layers)


def googlenet_shapes(batch: int) -> Dict[str, tuple]:
    return {"data": (batch, 3, 224, 224), "label": (batch,)}


ZOO = {
    "lenet": (lenet, lenet_shapes),
    "cifar10_quick": (cifar10_quick, cifar10_shapes),
    "alexnet": (alexnet, alexnet_shapes),
    "googlenet": (googlenet, googlenet_shapes),
}


# --------------------------------------------------------------------------- #
# CaffeNet (models/bvlc_reference_caffenet) — AlexNet variant with
# pool-before-norm ordering; also the backbone of the reference's R-CNN
# (models/bvlc_reference_rcnn_ilsvrc13) and flickr-style finetuning models.
# --------------------------------------------------------------------------- #

def caffenet(num_classes: int = 1000, with_accuracy: bool = True,
             classifier_name: str = "fc8") -> NetParameter:
    layers = [
        conv("conv1", "data", "conv1", 96, 11, stride=4,
             weight_filler=gaussian(0.01)),
        relu("relu1", "conv1"),
        pool("pool1", "conv1", "pool1", "MAX", 3, 2),
        lrn("norm1", "pool1", "norm1"),
        conv("conv2", "norm1", "conv2", 256, 5, pad=2, group=2,
             weight_filler=gaussian(0.01), bias_value=1.0),
        relu("relu2", "conv2"),
        pool("pool2", "conv2", "pool2", "MAX", 3, 2),
        lrn("norm2", "pool2", "norm2"),
        conv("conv3", "norm2", "conv3", 384, 3, pad=1,
             weight_filler=gaussian(0.01)),
        relu("relu3", "conv3"),
        conv("conv4", "conv3", "conv4", 384, 3, pad=1, group=2,
             weight_filler=gaussian(0.01), bias_value=1.0),
        relu("relu4", "conv4"),
        conv("conv5", "conv4", "conv5", 256, 3, pad=1, group=2,
             weight_filler=gaussian(0.01), bias_value=1.0),
        relu("relu5", "conv5"),
        pool("pool5", "conv5", "pool5", "MAX", 3, 2),
        ip("fc6", "pool5", "fc6", 4096, weight_filler=gaussian(0.005),
           bias_value=1.0),
        relu("relu6", "fc6"),
        dropout("drop6", "fc6", 0.5),
        ip("fc7", "fc6", "fc7", 4096, weight_filler=gaussian(0.005),
           bias_value=1.0),
        relu("relu7", "fc7"),
        dropout("drop7", "fc7", 0.5),
        ip(classifier_name, "fc7", classifier_name, num_classes,
           weight_filler=gaussian(0.01)),
        softmax_loss("loss", [classifier_name, "label"]),
    ]
    if with_accuracy:
        layers.insert(-1, accuracy("accuracy", [classifier_name, "label"]))
    return NetParameter(name="CaffeNet", layers=layers)


def caffenet_shapes(batch: int) -> Dict[str, tuple]:
    return {"data": (batch, 3, 227, 227), "label": (batch,)}


def rcnn_ilsvrc13(num_classes: int = 200) -> NetParameter:
    """R-CNN detection head (models/bvlc_reference_rcnn_ilsvrc13): CaffeNet
    backbone scoring warped window crops; trains from WINDOW_DATA."""
    net = caffenet(num_classes=num_classes, with_accuracy=True,
                   classifier_name="fc-rcnn")
    net.name = "R-CNN-ilsvrc13"
    return net


def finetune_flickr_style(num_classes: int = 20) -> NetParameter:
    """Finetuning recipe (models/finetune_flickr_style upstream): CaffeNet
    with a fresh, faster-learning classifier layer."""
    net = caffenet(num_classes=num_classes, with_accuracy=True,
                   classifier_name="fc8_flickr")
    for lp in net.layers:
        if lp.name == "fc8_flickr":
            lp.blobs_lr = [10.0, 20.0]  # fresh head learns 10x faster
    net.name = "FlickrStyleCaffeNet"
    return net


ZOO.update({
    "caffenet": (caffenet, caffenet_shapes),
    "rcnn_ilsvrc13": (rcnn_ilsvrc13, caffenet_shapes),
    "finetune_flickr_style": (finetune_flickr_style, caffenet_shapes),
})


# --------------------------------------------------------------------------- #
# OLMoE (arXiv:2409.02060): a token model as layers of the Net. The
# published 1B-7B sizes are the defaults; tests pass small ones.
# --------------------------------------------------------------------------- #

def olmoe(batch: int = 2, source: str = "examples/lm/olmoe_tokens.txt",
          n_layers: int = 16, hidden: int = 2048, heads: int = 16,
          experts: int = 64, top_k: int = 8, expert_width: int = 1024,
          vocab: int = 50304, rope_theta: float = 10000.0,
          eps: float = 1e-5, init_std: float = 0.02,
          balance_weight: float = 0.01, z_weight: float = 0.001,
          name: str = "OLMoE-1B-7B") -> NetParameter:
    """Pre-norm block with QK-norm over the full width, rotate-half RoPE,
    top-k dropless SiLU-gated experts; RMSNorm gains carry decay_mult 0,
    every matrix decay_mult 1 (AdamW's decoupled decay, solvers/updates.py).
    The data layer's tops are ``tokens`` and ``targets``, both (N, S)."""
    from ..proto.messages import (AttentionParameter, EltwiseParameter,
                                  EmbedParameter, HDF5DataParameter,
                                  MoEParameter, RMSNormParameter,
                                  SoftmaxParameter)
    w = gaussian(init_std)
    layers: List[LayerParameter] = [LayerParameter(
        name="tokens", type="HDF5_DATA", top=["tokens", "targets"],
        hdf5_data_param=HDF5DataParameter(source=source, batch_size=batch))]

    def norm(lname, bottom, top):
        layers.append(LayerParameter(
            name=lname, type="RMS_NORM", bottom=[bottom], top=[top],
            param=[ParamSpec(lr_mult=1.0, decay_mult=0.0)],
            rms_norm_param=RMSNormParameter(eps=eps)))

    def proj(lname, bottom, top, n_out):
        layers.append(LayerParameter(
            name=lname, type="INNER_PRODUCT", bottom=[bottom], top=[top],
            inner_product_param=InnerProductParameter(
                num_output=n_out, bias_term=False, axis=2, weight_filler=w)))

    def add(lname, a, b, top):
        layers.append(LayerParameter(
            name=lname, type="ELTWISE", bottom=[a, b], top=[top],
            eltwise_param=EltwiseParameter(operation="SUM")))

    layers.append(LayerParameter(
        name="embed", type="EMBED", bottom=["tokens"], top=["x0"],
        embed_param=EmbedParameter(input_dim=vocab, num_output=hidden,
                                   weight_filler=w)))
    x = "x0"
    for i in range(n_layers):
        p = f"l{i}_"
        norm(p + "attn_norm", x, p + "a")
        for t in "qkv":
            proj(p + t, p + "a", p + t, hidden)
        norm(p + "q_norm", p + "q", p + "qn")
        norm(p + "k_norm", p + "k", p + "kn")
        layers.append(LayerParameter(
            name=p + "attn", type="ATTENTION",
            bottom=[p + "qn", p + "kn", p + "v"], top=[p + "att"],
            attention_param=AttentionParameter(
                num_heads=heads, rope_theta=rope_theta)))
        proj(p + "o", p + "att", p + "ao", hidden)
        add(p + "res1", x, p + "ao", p + "h")
        norm(p + "ffn_norm", p + "h", p + "u")
        layers.append(LayerParameter(
            name=p + "moe", type="MOE", bottom=[p + "u"],
            top=[p + "m", p + "balance_loss", p + "z_loss",
                 p + "expert_load", p + "dropped"],
            loss_weight=[0.0, balance_weight, z_weight, 0.0, 0.0],
            moe_param=MoEParameter(num_experts=experts, top_k=top_k,
                                   expert_width=expert_width,
                                   weight_filler=w)))
        add(p + "res2", p + "h", p + "m", p + "y")
        x = p + "y"
    norm("final_norm", x, "xf")
    proj("lm_head", "xf", "logits", vocab)
    layers.append(LayerParameter(
        name="lm_loss", type="SOFTMAX_LOSS", bottom=["logits", "targets"],
        top=["lm_loss"], softmax_param=SoftmaxParameter(axis=-1)))
    return NetParameter(name=name, layers=layers)


def ouro(batch: int = 1, source: str = "examples/lm/ouro_tokens.txt",
         n_layers: int = 48, passes: int = 4, hidden: int = 2048,
         heads: int = 16, ffn_width: int = 5632, vocab: int = 49152,
         rope_theta: float = 1e6, eps: float = 1e-6, init_std: float = 0.02,
         entropy_weight: float = 0.1,
         name: str = "Ouro-2.6B") -> NetParameter:
    """A looped LM (arXiv:2510.25741): ONE stack of ``n_layers`` sandwich-
    norm blocks (RMSNorm before and after attention and the SiLU-gated FFN)
    applied ``passes`` times with the same weights. Pass t's layers are
    ``p<t>_l<i>_<what>``; every blob of pass 1 owns its storage under a
    name (``param { name: "l<i>_<what>_w" }``) that the later passes bind
    to, so a weight is one leaf to the update, the clip and a snapshot.
    Each pass ends in the shared final norm, whose output feeds the shared
    head, the shared one-unit exit gate (all passes but the last) and the
    next pass; EXIT_LOSS weights the passes' per-token losses by the exit
    distribution. Gains carry decay_mult 0, matrices 1, as ``olmoe``."""
    from ..proto.messages import (AttentionParameter, EltwiseParameter,
                                  EmbedParameter, ExitLossParameter,
                                  HDF5DataParameter, RMSNormParameter)
    w = gaussian(init_std)
    layers: List[LayerParameter] = [LayerParameter(
        name="tokens", type="HDF5_DATA", top=["tokens", "targets"],
        hdf5_data_param=HDF5DataParameter(source=source, batch_size=batch))]

    def norm(lname, shared, bottom, top):
        layers.append(LayerParameter(
            name=lname, type="RMS_NORM", bottom=[bottom], top=[top],
            param=[ParamSpec(name=shared + "_g", lr_mult=1.0,
                             decay_mult=0.0)],
            rms_norm_param=RMSNormParameter(eps=eps)))

    def proj(lname, shared, bottom, top, n_out, bias=False):
        spec = [ParamSpec(name=shared + "_w")]
        if bias:
            spec.append(ParamSpec(name=shared + "_b", decay_mult=0.0))
        layers.append(LayerParameter(
            name=lname, type="INNER_PRODUCT", bottom=[bottom], top=[top],
            param=spec, inner_product_param=InnerProductParameter(
                num_output=n_out, bias_term=bias, axis=2, weight_filler=w)))

    def add(lname, a, b, top):
        layers.append(LayerParameter(
            name=lname, type="ELTWISE", bottom=[a, b], top=[top],
            eltwise_param=EltwiseParameter(operation="SUM")))

    layers.append(LayerParameter(
        name="embed", type="EMBED", bottom=["tokens"], top=["h0"],
        embed_param=EmbedParameter(input_dim=vocab, num_output=hidden,
                                   weight_filler=w)))
    x = "h0"
    for t in range(1, passes + 1):
        for i in range(n_layers):
            p, sh = f"p{t}_l{i}_", f"l{i}_"
            norm(p + "attn_norm", sh + "attn_norm", x, p + "a")
            for c in "qkv":
                proj(p + c, sh + c, p + "a", p + c, hidden)
            layers.append(LayerParameter(
                name=p + "attn", type="ATTENTION",
                bottom=[p + "q", p + "k", p + "v"], top=[p + "att"],
                attention_param=AttentionParameter(
                    num_heads=heads, rope_theta=rope_theta)))
            proj(p + "o", sh + "o", p + "att", p + "ao", hidden)
            norm(p + "attn_out_norm", sh + "attn_out_norm", p + "ao",
                 p + "aon")
            add(p + "res1", x, p + "aon", p + "h")
            norm(p + "ffn_norm", sh + "ffn_norm", p + "h", p + "m")
            proj(p + "ffn_gate", sh + "ffn_gate", p + "m", p + "fg",
                 ffn_width)
            proj(p + "ffn_up", sh + "ffn_up", p + "m", p + "fu", ffn_width)
            layers.append(LayerParameter(
                name=p + "ffn_act", type="SILU_GATE",
                bottom=[p + "fg", p + "fu"], top=[p + "fa"]))
            proj(p + "ffn_down", sh + "ffn_down", p + "fa", p + "fd", hidden)
            norm(p + "ffn_out_norm", sh + "ffn_out_norm", p + "fd",
                 p + "fdn")
            add(p + "res2", p + "h", p + "fdn", p + "y")
            x = p + "y"
        p = f"p{t}_"
        norm(p + "final_norm", "final_norm", x, p + "hn")
        x = p + "hn"
        proj(p + "head", "head", x, p + "logits", vocab)
        layers.append(LayerParameter(
            name=p + "nll", type="SOFTMAX_NLL",
            bottom=[p + "logits", "targets"], top=[p + "nll"]))
        if t < passes:      # the last pass takes what mass is left
            proj(p + "gate", "gate", x, p + "gate", 1, bias=True)
    layers.append(LayerParameter(
        name="exit_loss", type="EXIT_LOSS",
        bottom=[f"p{t}_nll" for t in range(1, passes + 1)]
        + [f"p{t}_gate" for t in range(1, passes)],
        top=["exit_loss"] + [f"exit_mass_p{t}"
                             for t in range(1, passes + 1)],
        exit_loss_param=ExitLossParameter(entropy_weight=entropy_weight)))
    return NetParameter(name=name, layers=layers)


def zaya1(batch: int = 1, source: str = "examples/lm/zaya1_tokens.txt",
          n_layers: int = 40, hidden: int = 2048, heads: int = 8,
          kv_heads: int = 2, head_dim: int = 128, experts: int = 16,
          held: int = 0, held_first: int = 0, expert_width: int = 2048,
          router_hidden: int = 256, vocab: int = 262272,
          rope_theta: float = 5e6, rotary: float = 0.5, time0: int = 2,
          time1: int = 2, eps: float = 1e-5, init_std: float = 0.02,
          name: str = "ZAYA1-8B") -> NetParameter:
    """ZAYA1 (arXiv:2511.17127): every layer is CCA attention
    (arXiv:2510.04476) and a top-1 MoE behind an MLP router, both pre-norm.

    CCA: q~ (``heads`` x ``head_dim``) and k~ (``kv_heads`` x ``head_dim``)
    are projections of the normed state into a latent narrower than the
    model; v is two half-width projections side by side, the second of the
    token BEFORE (``l<i>_cca_shift``); [q~, k~] pass two causal
    convolutions over the sequence (``l<i>_cca_conv``), take the q-k mean
    (``l<i>_cca_qkmean``) and an L2 norm per head with a learned
    temperature on k (``l<i>_cca_qknorm``); grouped-query ATTENTION with
    rotary positions on ``rotary`` of a head; ``l<i>_o`` leaves the latent.

    MoE: ``l<i>_router`` (MOE_ROUTER) scores all ``experts`` from the
    normed state and the router state of the layer before, and keeps a
    selection bias it balances itself; ``l<i>_moe`` holds ``held`` of the
    experts from ``held_first`` on (0 = all): with fewer than all, the
    net is one rank's share of an expert-parallel model. The embedding's
    table is the head's (one leaf, ``tok_w``). Gains, biases, ``tau`` and
    the router's ``mix`` carry decay_mult 0, every matrix 1."""
    from ..proto.messages import (AttentionParameter, CCAParameter,
                                  ConcatParameter, EltwiseParameter,
                                  EmbedParameter, HDF5DataParameter,
                                  MoEParameter, RMSNormParameter)
    w = gaussian(init_std)
    lq, lk = heads * head_dim, kv_heads * head_dim
    layers: List[LayerParameter] = [LayerParameter(
        name="tokens", type="HDF5_DATA", top=["tokens", "targets"],
        hdf5_data_param=HDF5DataParameter(source=source, batch_size=batch))]
    no_decay = ParamSpec(lr_mult=1.0, decay_mult=0.0)

    def norm(lname, bottom, top):
        layers.append(LayerParameter(
            name=lname, type="RMS_NORM", bottom=[bottom], top=[top],
            param=[no_decay], rms_norm_param=RMSNormParameter(eps=eps)))

    def proj(lname, bottom, top, n_out, spec=()):
        layers.append(LayerParameter(
            name=lname, type="INNER_PRODUCT", bottom=[bottom], top=[top],
            param=list(spec), inner_product_param=InnerProductParameter(
                num_output=n_out, bias_term=False, axis=2, weight_filler=w)))

    def add(lname, a, b, top):
        layers.append(LayerParameter(
            name=lname, type="ELTWISE", bottom=[a, b], top=[top],
            eltwise_param=EltwiseParameter(operation="SUM")))

    def cca(lname, kind, bottoms, tops, spec=()):
        layers.append(LayerParameter(
            name=lname, type=kind, bottom=bottoms, top=tops,
            param=list(spec), cca_param=CCAParameter(
                num_heads=heads, num_kv_heads=kv_heads, time0=time0,
                time1=time1, eps=eps, weight_filler=w)))

    tied = [ParamSpec(name="tok_w")]
    layers.append(LayerParameter(
        name="embed", type="EMBED", bottom=["tokens"], top=["x0"],
        param=tied, embed_param=EmbedParameter(
            input_dim=vocab, num_output=hidden, weight_filler=w)))
    x = "x0"
    for i in range(n_layers):
        p = f"l{i}_"
        norm(p + "attn_norm", x, p + "a")
        proj(p + "q", p + "a", p + "q", lq)
        proj(p + "k", p + "a", p + "k", lk)
        proj(p + "v1", p + "a", p + "v1", lk // 2)
        layers.append(LayerParameter(
            name=p + "cca_shift", type="TOKEN_SHIFT", bottom=[p + "a"],
            top=[p + "as"]))
        proj(p + "v2", p + "as", p + "v2", lk // 2)
        layers.append(LayerParameter(
            name=p + "cca_vcat", type="CONCAT",
            bottom=[p + "v1", p + "v2"], top=[p + "v"],
            concat_param=ConcatParameter(concat_dim=2)))
        cca(p + "cca_conv", "CCA_CONV", [p + "q", p + "k"],
            [p + "qc", p + "kc"],
            [ParamSpec(), no_decay, ParamSpec(), no_decay])
        cca(p + "cca_qkmean", "CCA_QKMEAN",
            [p + "q", p + "k", p + "qc", p + "kc"], [p + "qm", p + "km"])
        cca(p + "cca_qknorm", "CCA_QKNORM", [p + "qm", p + "km"],
            [p + "qn", p + "kn"], [no_decay])
        layers.append(LayerParameter(
            name=p + "attn", type="ATTENTION",
            bottom=[p + "qn", p + "kn", p + "v"], top=[p + "att"],
            attention_param=AttentionParameter(
                num_heads=heads, rope_theta=rope_theta,
                num_kv_heads=kv_heads,
                rotary_dims=int(round(rotary * head_dim)))))
        proj(p + "o", p + "att", p + "ao", hidden)
        add(p + "res1", x, p + "ao", p + "h")
        norm(p + "moe_norm", p + "h", p + "u")
        moe = dict(num_experts=experts, top_k=1, expert_width=expert_width,
                   router_hidden=router_hidden, weight_filler=w)
        first = i == 0            # no router state before the first layer
        layers.append(LayerParameter(
            name=p + "router", type="MOE_ROUTER",
            bottom=[p + "u"] + ([] if first else [f"l{i - 1}_r"]),
            top=[p + "r", p + "gates", p + "bias_next"],
            param=[ParamSpec()] + ([] if first else [no_decay])
            + [ParamSpec(), ParamSpec(), ParamSpec(), no_decay],
            moe_param=MoEParameter(**moe)))
        layers.append(LayerParameter(
            name=p + "moe", type="MOE", bottom=[p + "u", p + "gates"],
            top=[p + "m", p + "expert_load", p + "dropped",
                 p + "held_share"],
            moe_param=MoEParameter(num_held=held, held_first=held_first,
                                   **moe)))
        add(p + "res2", p + "h", p + "m", p + "y")
        x = p + "y"
    norm("final_norm", x, "xf")
    proj("lm_head", "xf", "logits", vocab, tied)
    layers.append(LayerParameter(
        name="lm_nll", type="SOFTMAX_NLL", bottom=["logits", "targets"],
        top=["nll"]))
    # the mean over positions: the exit-weighted loss of ONE pass
    layers.append(LayerParameter(
        name="lm_loss", type="EXIT_LOSS", bottom=["nll"], top=["lm_loss"]))
    return NetParameter(name=name, layers=layers)


def trinity_mini(batch: int = 1,
                 source: str = "examples/lm/trinity_mini_tokens.txt",
                 n_layers: int = 32, dense_layers: int = 2,
                 hidden: int = 2048, heads: int = 32, kv_heads: int = 4,
                 head_dim: int = 128, window: int = 2048,
                 global_every: int = 4, first_global: int = -1,
                 dense_width: int = 6144,
                 experts: int = 128, top_k: int = 8, held: int = 0,
                 held_first: int = 0, expert_width: int = 1024,
                 shared_width: int = 1024, route_scale: float = 2.826,
                 bias_update_rate: float = 0.001, vocab: int = 200192,
                 rope_theta: float = 10000.0, eps: float = 1e-5,
                 init_std: float = 0.02,
                 name: str = "Trinity-Mini") -> NetParameter:
    """Trinity-Mini (config.json of arcee-ai/Trinity-Mini, ``afmoe``):
    every layer is gated grouped-query attention and an FFN, each between a
    norm before and a norm after it (four RMSNorms a layer):

        h = x + N2(Attn(N1(x)));  y = h + N4(FFN(N3(h)))

    Attention: q and a gate g (``heads`` x ``head_dim``), k and v
    (``kv_heads`` x ``head_dim``) project the normed state; q and k take an
    RMSNorm over each head's dims with one gain a projection
    (``l<i>_q_norm``, ``l<i>_k_norm``); layer ``first_global`` (unset:
    ``global_every`` - 1, as published) and every ``global_every``-th after
    it is GLOBAL (``l<i>_attn_global``: every earlier token, no positions
    at all), the others WINDOW layers (``l<i>_attn_window``: the last
    ``window`` tokens, rotate-half rotary positions); the merged
    heads times sigmoid(g) (``l<i>_gate_sig``, ``l<i>_gate_mul``) leave
    through ``l<i>_o``.

    FFN: the first ``dense_layers`` layers a SiLU-gated MLP of
    ``dense_width``; the others ``l<i>_router`` (MOE_ROUTER: sigmoid scores
    over all ``experts``, the ``top_k`` of score + selection bias chosen,
    weighed by the unbiased scores over their sum times ``route_scale``,
    the bias balanced by the layer at ``bias_update_rate``, its largest
    magnitude a top that every display carries), ``l<i>_moe``
    holding ``held`` of the experts from ``held_first`` on (0 = all: with
    fewer the net is one rank's share of an expert-parallel model) and an
    always-on shared expert (``l<i>_shared_*``) added unweighted.

    The embedding's rows are scaled by sqrt(``hidden``) (``embed_scale``);
    the head is untied. Gains and the selection bias carry decay_mult 0,
    every matrix 1."""
    from ..proto.messages import (AttentionParameter, EltwiseParameter,
                                  EmbedParameter, HDF5DataParameter,
                                  MoEParameter, PowerParameter,
                                  RMSNormParameter)
    w = gaussian(init_std)
    lq, lk = heads * head_dim, kv_heads * head_dim
    if first_global < 0:
        first_global = global_every - 1
    layers: List[LayerParameter] = [LayerParameter(
        name="tokens", type="HDF5_DATA", top=["tokens", "targets"],
        hdf5_data_param=HDF5DataParameter(source=source, batch_size=batch))]
    no_decay = ParamSpec(lr_mult=1.0, decay_mult=0.0)

    def norm(lname, bottom, top, per_head=0):
        layers.append(LayerParameter(
            name=lname, type="RMS_NORM", bottom=[bottom], top=[top],
            param=[no_decay], rms_norm_param=RMSNormParameter(
                eps=eps, num_heads=per_head)))

    def proj(lname, bottom, top, n_out):
        layers.append(LayerParameter(
            name=lname, type="INNER_PRODUCT", bottom=[bottom], top=[top],
            inner_product_param=InnerProductParameter(
                num_output=n_out, bias_term=False, axis=2, weight_filler=w)))

    def eltwise(lname, a, b, top, operation="SUM"):
        layers.append(LayerParameter(
            name=lname, type="ELTWISE", bottom=[a, b], top=[top],
            eltwise_param=EltwiseParameter(operation=operation)))

    def gated_mlp(p, bottom, top, width):
        proj(p + "gate", bottom, p + "g", width)
        proj(p + "up", bottom, p + "u", width)
        layers.append(LayerParameter(
            name=p + "act", type="SILU_GATE", bottom=[p + "g", p + "u"],
            top=[p + "a"]))
        proj(p + "down", p + "a", top, hidden)

    layers.append(LayerParameter(
        name="embed", type="EMBED", bottom=["tokens"], top=["x_rows"],
        embed_param=EmbedParameter(input_dim=vocab, num_output=hidden,
                                   weight_filler=w)))
    layers.append(LayerParameter(
        name="embed_scale", type="POWER", bottom=["x_rows"], top=["x0"],
        power_param=PowerParameter(scale=float(hidden) ** 0.5)))
    x = "x0"
    for i in range(n_layers):
        p = f"l{i}_"
        norm(p + "attn_norm", x, p + "a")
        proj(p + "q", p + "a", p + "q", lq)
        proj(p + "k", p + "a", p + "k", lk)
        proj(p + "v", p + "a", p + "v", lk)
        proj(p + "g", p + "a", p + "g", lq)
        norm(p + "q_norm", p + "q", p + "qn", heads)
        norm(p + "k_norm", p + "k", p + "kn", kv_heads)
        is_global = i >= first_global \
            and (i - first_global) % global_every == 0
        layers.append(LayerParameter(
            name=p + ("attn_global" if is_global else "attn_window"),
            type="ATTENTION", bottom=[p + "qn", p + "kn", p + "v"],
            top=[p + "att"], attention_param=AttentionParameter(
                num_heads=heads, rope_theta=rope_theta,
                num_kv_heads=kv_heads, rope=not is_global,
                window=0 if is_global else window)))
        layers.append(LayerParameter(
            name=p + "gate_sig", type="SIGMOID", bottom=[p + "g"],
            top=[p + "gs"]))
        eltwise(p + "gate_mul", p + "att", p + "gs", p + "ag", "PROD")
        proj(p + "o", p + "ag", p + "ao", hidden)
        norm(p + "attn_out_norm", p + "ao", p + "aon")
        eltwise(p + "res1", x, p + "aon", p + "h")
        norm(p + "ffn_norm", p + "h", p + "u")
        if i < dense_layers:
            gated_mlp(p + "ffn_", p + "u", p + "f", dense_width)
        else:
            moe = dict(num_experts=experts, top_k=top_k,
                       expert_width=expert_width, score_func="sigmoid",
                       route_scale=route_scale,
                       bias_update_rate=bias_update_rate, weight_filler=w)
            layers.append(LayerParameter(
                name=p + "router", type="MOE_ROUTER", bottom=[p + "u"],
                top=[p + "gates", p + "bias_next", p + "bias_max_abs"],
                param=[ParamSpec(), no_decay],
                moe_param=MoEParameter(**moe)))
            layers.append(LayerParameter(
                name=p + "moe", type="MOE", bottom=[p + "u", p + "gates"],
                top=[p + "m", p + "expert_load", p + "dropped",
                     p + "held_share"],
                moe_param=MoEParameter(num_held=held, held_first=held_first,
                                       **moe)))
            gated_mlp(p + "shared_", p + "u", p + "s", shared_width)
            eltwise(p + "moe_sum", p + "m", p + "s", p + "f")
        norm(p + "ffn_out_norm", p + "f", p + "fn")
        eltwise(p + "res2", p + "h", p + "fn", p + "y")
        x = p + "y"
    norm("final_norm", x, "xf")
    proj("lm_head", "xf", "logits", vocab)
    layers.append(LayerParameter(
        name="lm_nll", type="SOFTMAX_NLL", bottom=["logits", "targets"],
        top=["nll"]))
    # the mean over positions: the exit-weighted loss of ONE pass
    layers.append(LayerParameter(
        name="lm_loss", type="EXIT_LOSS", bottom=["nll"], top=["lm_loss"]))
    return NetParameter(name=name, layers=layers)


def smallthinker(batch: int = 1,
                 source: str = "examples/lm/smallthinker_21b_tokens.txt",
                 n_layers: int = 52, hidden: int = 2560, heads: int = 28,
                 kv_heads: int = 4, head_dim: int = 128, window: int = 4096,
                 global_every: int = 4, first_global: int = 0,
                 experts: int = 64, top_k: int = 6, held: int = 0,
                 held_first: int = 0, expert_width: int = 768,
                 vocab: int = 151936, rope_theta: float = 1.5e6,
                 eps: float = 1e-6, init_std: float = 0.02,
                 balance_weight: float = 0.01, z_weight: float = 0.001,
                 name: str = "SmallThinker-21BA3B") -> NetParameter:
    """SmallThinker-21BA3B-Instruct (config.json of
    PowerInfer/SmallThinker-21BA3B-Instruct, ``smallthinker``;
    arXiv:2507.20984): every layer is a pre-norm block of grouped-query
    attention and a MoE whose router reads the PRE-attention state:

        a = N1(x);  gates = Router(a);  h = x + Attn(a) W_o
        y = h + MoE(N2(h), gates)

    Attention: q (``heads`` x ``head_dim``, wider than ``hidden``), k and v
    (``kv_heads`` x ``head_dim``) project the normed state, no bias, no
    QK-norm, no gate; layer ``first_global`` and every ``global_every``-th
    after it is GLOBAL (``l<i>_attn_global``: every earlier token, no
    positions at all), the others WINDOW layers (``l<i>_attn_window``: the
    last ``window`` tokens, rotate-half rotary positions): the published
    ``sliding_window_layout`` and ``rope_layout`` [0, 1, 1, 1] a period.

    MoE: ``l<i>_router`` (MOE_ROUTER, the plain softmax form) scores
    ``l<i>_a`` over all ``experts``, chooses the ``top_k`` largest logits
    and weighs them by the softmax over the chosen; its balance and z
    losses are tops with ``balance_weight`` and ``z_weight``. ``l<i>_moe``
    takes the post-attention normed state and those gates, holds ``held``
    of the experts from ``held_first`` on (0 = all: with fewer the net is
    one rank's share of an expert-parallel model) and runs ReGLU experts,
    down(relu(gate u) * (up u)); its last top is the share of the held
    experts' gate pre-activations that are <= 0. No shared expert, no
    dense layer.

    The head is untied. Gains carry decay_mult 0, every matrix 1."""
    from ..proto.messages import (AttentionParameter, EltwiseParameter,
                                  EmbedParameter, HDF5DataParameter,
                                  MoEParameter, RMSNormParameter)
    w = gaussian(init_std)
    lq, lk = heads * head_dim, kv_heads * head_dim
    layers: List[LayerParameter] = [LayerParameter(
        name="tokens", type="HDF5_DATA", top=["tokens", "targets"],
        hdf5_data_param=HDF5DataParameter(source=source, batch_size=batch))]

    def norm(lname, bottom, top):
        layers.append(LayerParameter(
            name=lname, type="RMS_NORM", bottom=[bottom], top=[top],
            param=[ParamSpec(lr_mult=1.0, decay_mult=0.0)],
            rms_norm_param=RMSNormParameter(eps=eps)))

    def proj(lname, bottom, top, n_out):
        layers.append(LayerParameter(
            name=lname, type="INNER_PRODUCT", bottom=[bottom], top=[top],
            inner_product_param=InnerProductParameter(
                num_output=n_out, bias_term=False, axis=2, weight_filler=w)))

    def add(lname, a, b, top):
        layers.append(LayerParameter(
            name=lname, type="ELTWISE", bottom=[a, b], top=[top],
            eltwise_param=EltwiseParameter(operation="SUM")))

    layers.append(LayerParameter(
        name="embed", type="EMBED", bottom=["tokens"], top=["x0"],
        embed_param=EmbedParameter(input_dim=vocab, num_output=hidden,
                                   weight_filler=w)))
    x = "x0"
    for i in range(n_layers):
        p = f"l{i}_"
        norm(p + "attn_norm", x, p + "a")
        moe = dict(num_experts=experts, top_k=top_k,
                   expert_width=expert_width, weight_filler=w)
        layers.append(LayerParameter(
            name=p + "router", type="MOE_ROUTER", bottom=[p + "a"],
            top=[p + "gates", p + "balance_loss", p + "z_loss"],
            loss_weight=[0.0, balance_weight, z_weight],
            moe_param=MoEParameter(**moe)))
        proj(p + "q", p + "a", p + "q", lq)
        proj(p + "k", p + "a", p + "k", lk)
        proj(p + "v", p + "a", p + "v", lk)
        is_global = i >= first_global \
            and (i - first_global) % global_every == 0
        layers.append(LayerParameter(
            name=p + ("attn_global" if is_global else "attn_window"),
            type="ATTENTION", bottom=[p + "q", p + "k", p + "v"],
            top=[p + "att"], attention_param=AttentionParameter(
                num_heads=heads, rope_theta=rope_theta,
                num_kv_heads=kv_heads, rope=not is_global,
                window=0 if is_global else window)))
        proj(p + "o", p + "att", p + "ao", hidden)
        add(p + "res1", x, p + "ao", p + "h")
        norm(p + "ffn_norm", p + "h", p + "u")
        layers.append(LayerParameter(
            name=p + "moe", type="MOE", bottom=[p + "u", p + "gates"],
            top=[p + "m", p + "expert_load", p + "dropped",
                 p + "held_share", p + "gate_zero_share"],
            moe_param=MoEParameter(num_held=held, held_first=held_first,
                                   activation="relu", **moe)))
        add(p + "res2", p + "h", p + "m", p + "y")
        x = p + "y"
    norm("final_norm", x, "xf")
    proj("lm_head", "xf", "logits", vocab)
    layers.append(LayerParameter(
        name="lm_nll", type="SOFTMAX_NLL", bottom=["logits", "targets"],
        top=["nll"]))
    # the mean over positions: the exit-weighted loss of ONE pass
    layers.append(LayerParameter(
        name="lm_loss", type="EXIT_LOSS", bottom=["nll"], top=["lm_loss"]))
    return NetParameter(name=name, layers=layers)


def kimi_linear(batch: int = 1,
                source: str = "examples/lm/kimi_linear_tokens.txt",
                n_layers: int = 27, dense_layers: int = 1,
                hidden: int = 2304, heads: int = 32, head_dim: int = 128,
                conv_taps: int = 4, full_every: int = 4,
                kv_rank: int = 512, nope_dim: int = 128, rope_dim: int = 64,
                v_dim: int = 128, dense_width: int = 9216,
                experts: int = 256, top_k: int = 8, held: int = 0,
                held_first: int = 0, expert_width: int = 1024,
                shared_width: int = 1024, route_scale: float = 2.446,
                bias_update_rate: float = 0.001, vocab: int = 163840,
                eps: float = 1e-5, init_std: float = 0.02,
                name: str = "Kimi-Linear-48B-A3B") -> NetParameter:
    """Kimi-Linear-48B-A3B (config.json of
    moonshotai/Kimi-Linear-48B-A3B-Instruct, ``kimi_linear``;
    arXiv:2510.26692): every layer is a token mixer and an FFN, pre-norm,

        h = x + Mix(N1(x));  y = h + FFN(N2(h))

    Layer ``full_every`` (1-indexed) and every ``full_every``-th after it
    mixes by latent attention (MLA), the others by the gated delta rule
    with a per-channel decay (KDA).

    KDA: q, k, v (``heads`` x ``head_dim``) project the normed state
    (``l<i>_kda_{q,k,v}``), each passes a causal depthwise convolution of
    ``conv_taps`` taps and SiLU (``l<i>_kda_conv_*``), q and k an L2 norm
    per head (``l<i>_kda_l2_*``); the log-decay, one a head and channel,
    is -exp(A_log) softplus(W_up W_down a + dt_bias) (``l<i>_kda_decay*``,
    whose second top ``l<i>_decay_mean`` every display carries), the write
    strength sigmoid(W_b a) a head (``l<i>_kda_beta*``); the recurrence
    (``l<i>_kda_scan``: a state of ``head_dim`` x ``head_dim`` a head);
    its output takes an RMSNorm per head with one gain
    (``l<i>_kda_onorm``), times sigmoid of a low-rank gate
    (``l<i>_kda_ogate*``), and leaves through ``l<i>_kda_o``. No
    positions.

    MLA: q (``heads`` x (``nope_dim`` + ``rope_dim``)) from the normed
    state (``l<i>_mla_q``); one projection to a latent of ``kv_rank`` and a
    ``rope_dim``-wide key part that all heads share (``l<i>_mla_kva``,
    split by ``l<i>_mla_kva_split``); the normed latent
    (``l<i>_mla_kvnorm``) gives each head ``nope_dim`` of key
    (``l<i>_mla_kvb_k``) and ``v_dim`` of value (``l<i>_mla_kvb_v``: the
    published one matrix, its rows sorted into keys and values); causal
    attention with NO positions over keys [own part ; shared part] and the
    narrower values (``l<i>_mla_attn``); ``l<i>_mla_o``.

    FFN: the first ``dense_layers`` layers a SiLU-gated MLP of
    ``dense_width``; the others a sigmoid router with a selection bias
    over ``experts`` (``l<i>_router``), ``l<i>_moe`` holding ``held`` of
    the experts from ``held_first`` on (0 = all: with fewer the net is one
    rank's share of an expert-parallel model) and an always-on shared
    expert (``l<i>_shared_*``) added unweighted, as ``trinity_mini``'s.

    The head is untied. Gains, A_log, dt_bias and the selection bias carry
    decay_mult 0, every matrix and the convolutions' taps 1."""
    from ..proto.messages import (AttentionParameter, EltwiseParameter,
                                  EmbedParameter, HDF5DataParameter,
                                  KDAParameter, MoEParameter,
                                  RMSNormParameter, SliceParameter)
    w = gaussian(init_std)
    width = heads * head_dim
    layers: List[LayerParameter] = [LayerParameter(
        name="tokens", type="HDF5_DATA", top=["tokens", "targets"],
        hdf5_data_param=HDF5DataParameter(source=source, batch_size=batch))]
    no_decay = ParamSpec(lr_mult=1.0, decay_mult=0.0)

    def norm(lname, bottom, top, per_head=0):
        layers.append(LayerParameter(
            name=lname, type="RMS_NORM", bottom=[bottom], top=[top],
            param=[no_decay], rms_norm_param=RMSNormParameter(
                eps=eps, num_heads=per_head)))

    def proj(lname, bottom, top, n_out):
        layers.append(LayerParameter(
            name=lname, type="INNER_PRODUCT", bottom=[bottom], top=[top],
            inner_product_param=InnerProductParameter(
                num_output=n_out, bias_term=False, axis=2, weight_filler=w)))

    def eltwise(lname, a, b, top, operation="SUM"):
        layers.append(LayerParameter(
            name=lname, type="ELTWISE", bottom=[a, b], top=[top],
            eltwise_param=EltwiseParameter(operation=operation)))

    def sigmoid(lname, bottom, top):
        layers.append(LayerParameter(name=lname, type="SIGMOID",
                                     bottom=[bottom], top=[top]))

    def gated_mlp(p, bottom, top, n_mid):
        proj(p + "gate", bottom, p + "g", n_mid)
        proj(p + "up", bottom, p + "u", n_mid)
        layers.append(LayerParameter(
            name=p + "act", type="SILU_GATE", bottom=[p + "g", p + "u"],
            top=[p + "a"]))
        proj(p + "down", p + "a", top, hidden)

    def kda(p, a, top):
        kp = dict(num_heads=heads)
        taps = FillerParameter(type="uniform", min=-conv_taps ** -0.5,
                               max=conv_taps ** -0.5)
        for t in "qkv":
            proj(p + "kda_" + t, a, p + t + "p", width)
            layers.append(LayerParameter(
                name=p + "kda_conv_" + t, type="SHORT_CONV",
                bottom=[p + t + "p"], top=[p + t + "c"],
                kda_param=KDAParameter(kernel_size=conv_taps,
                                       weight_filler=taps)))
        for t in "qk":
            layers.append(LayerParameter(
                name=p + "kda_l2_" + t, type="L2_NORM", bottom=[p + t + "c"],
                top=[p + t + "n"], kda_param=KDAParameter(**kp)))
        proj(p + "kda_decay_down", a, p + "fd", head_dim)
        proj(p + "kda_decay_up", p + "fd", p + "fu", width)
        layers.append(LayerParameter(
            name=p + "kda_decay", type="KDA_DECAY", bottom=[p + "fu"],
            top=[p + "gdec", p + "decay_mean"], param=[no_decay, no_decay],
            kda_param=KDAParameter(**kp)))
        proj(p + "kda_beta", a, p + "bl", heads)
        sigmoid(p + "kda_beta_sig", p + "bl", p + "beta")
        layers.append(LayerParameter(
            name=p + "kda_scan", type="KDA_SCAN",
            bottom=[p + "qn", p + "kn", p + "vc", p + "gdec", p + "beta"],
            top=[p + "so"], kda_param=KDAParameter(**kp)))
        norm(p + "kda_onorm", p + "so", p + "son", heads)
        proj(p + "kda_ogate_down", a, p + "ogd", head_dim)
        proj(p + "kda_ogate_up", p + "ogd", p + "ogu", width)
        sigmoid(p + "kda_ogate_sig", p + "ogu", p + "ogs")
        eltwise(p + "kda_ogate_mul", p + "son", p + "ogs", p + "sog", "PROD")
        proj(p + "kda_o", p + "sog", top, hidden)

    def mla(p, a, top):
        proj(p + "mla_q", a, p + "q", heads * (nope_dim + rope_dim))
        proj(p + "mla_kva", a, p + "kva", kv_rank + rope_dim)
        layers.append(LayerParameter(
            name=p + "mla_kva_split", type="SLICE", bottom=[p + "kva"],
            top=[p + "c", p + "kpe"],
            slice_param=SliceParameter(slice_dim=2, slice_point=[kv_rank])))
        norm(p + "mla_kvnorm", p + "c", p + "cn")
        proj(p + "mla_kvb_k", p + "cn", p + "kn", heads * nope_dim)
        proj(p + "mla_kvb_v", p + "cn", p + "v", heads * v_dim)
        layers.append(LayerParameter(
            name=p + "mla_attn", type="ATTENTION",
            bottom=[p + "q", p + "kn", p + "v", p + "kpe"], top=[p + "att"],
            attention_param=AttentionParameter(
                num_heads=heads, rope=False, value_head_dim=v_dim)))
        proj(p + "mla_o", p + "att", top, hidden)

    layers.append(LayerParameter(
        name="embed", type="EMBED", bottom=["tokens"], top=["x0"],
        embed_param=EmbedParameter(input_dim=vocab, num_output=hidden,
                                   weight_filler=w)))
    x = "x0"
    for i in range(n_layers):
        p = f"l{i}_"
        norm(p + "attn_norm", x, p + "a")
        (mla if (i + 1) % full_every == 0 else kda)(p, p + "a", p + "ao")
        eltwise(p + "res1", x, p + "ao", p + "h")
        norm(p + "ffn_norm", p + "h", p + "u")
        if i < dense_layers:
            gated_mlp(p + "ffn_", p + "u", p + "f", dense_width)
        else:
            moe = dict(num_experts=experts, top_k=top_k,
                       expert_width=expert_width, score_func="sigmoid",
                       route_scale=route_scale,
                       bias_update_rate=bias_update_rate, weight_filler=w)
            layers.append(LayerParameter(
                name=p + "router", type="MOE_ROUTER", bottom=[p + "u"],
                top=[p + "gates", p + "bias_next", p + "bias_max_abs"],
                param=[ParamSpec(), no_decay],
                moe_param=MoEParameter(**moe)))
            layers.append(LayerParameter(
                name=p + "moe", type="MOE", bottom=[p + "u", p + "gates"],
                top=[p + "m", p + "expert_load", p + "dropped",
                     p + "held_share"],
                moe_param=MoEParameter(num_held=held, held_first=held_first,
                                       **moe)))
            gated_mlp(p + "shared_", p + "u", p + "s", shared_width)
            eltwise(p + "moe_sum", p + "m", p + "s", p + "f")
        eltwise(p + "res2", p + "h", p + "f", p + "y")
        x = p + "y"
    norm("final_norm", x, "xf")
    proj("lm_head", "xf", "logits", vocab)
    layers.append(LayerParameter(
        name="lm_nll", type="SOFTMAX_NLL", bottom=["logits", "targets"],
        top=["nll"]))
    # the mean over positions: the exit-weighted loss of ONE pass
    layers.append(LayerParameter(
        name="lm_loss", type="EXIT_LOSS", bottom=["nll"], top=["lm_loss"]))
    return NetParameter(name=name, layers=layers)


def glm_flash(batch: int = 1, n_layers: int = 47, held: int = 0,
              vocab: int = 154880, mtp: int = 1,
              source: str = "examples/lm/glm_4_7_flash_tokens.txt",
              dense_layers: int = 1, hidden: int = 2048, heads: int = 20,
              q_rank: int = 768, kv_rank: int = 512, nope_dim: int = 192,
              rope_dim: int = 64, v_dim: int = 256, dense_width: int = 10240,
              experts: int = 64, top_k: int = 4, held_first: int = 0,
              expert_width: int = 1536, shared_width: int = 1536,
              route_scale: float = 1.8, bias_update_rate: float = 0.001,
              rope_theta: float = 1e6, eps: float = 1e-5,
              mtp_weight: float = 0.3, init_std: float = 0.02,
              name: str = "GLM-4.7-Flash", streams: int = 0,
              sinkhorn_iters: int = 20, hc_eps: float = 1e-6,
              hc_clamp: float = 30.0, attn_scale: float = 0.0,
              rope_factor: float = 1.0, rope_original_positions: int = 4096,
              rope_beta_fast: float = 32.0, rope_beta_slow: float = 1.0
              ) -> NetParameter:
    """GLM-4.7-Flash (config.json of zai-org/GLM-4.7-Flash,
    ``glm4_moe_lite``, 30B-A3B; the DeepSeek-V3 block, arXiv:2412.19437):
    every layer is latent attention and an FFN, pre-norm,

        h = x + MLA(N1(x));  y = h + FFN(N2(h))

    MLA: a query latent of ``q_rank`` (``l<i>_mla_qa``) takes an RMSNorm
    (``l<i>_mla_qnorm``) and gives ``heads`` heads of [``nope_dim`` ;
    ``rope_dim``] (``l<i>_mla_qb``); one projection to a latent of
    ``kv_rank`` and a ``rope_dim``-wide key part that all heads share
    (``l<i>_mla_kva``, split by ``l<i>_mla_kva_split``); the normed latent
    (``l<i>_mla_kvnorm``) gives each head ``nope_dim`` of key
    (``l<i>_mla_kvb_k``) and ``v_dim`` of value (``l<i>_mla_kvb_v``: the
    published one matrix, its rows sorted into keys and values); causal
    attention over keys [own part ; shared part] with rotate-half rotary
    positions on the SHARED part, turned once a token, and on the last
    ``rope_dim`` dims of every q head, nothing else (``l<i>_mla_attn``,
    ``rotary_shared``); ``l<i>_mla_o``.

    FFN: the first ``dense_layers`` layers a SiLU-gated MLP of
    ``dense_width``; the others a sigmoid router with a selection bias
    over ``experts`` (``l<i>_router``: the ``top_k`` of score + bias
    chosen, weighed by the unbiased scores over their sum times
    ``route_scale``, the bias balanced by the layer), ``l<i>_moe`` holding
    ``held`` of the experts from ``held_first`` on (0 = all: with fewer the
    net is one rank's share of an expert-parallel model) and an always-on
    shared expert (``l<i>_shared_*``) added unweighted, as
    ``trinity_mini``'s and ``kimi_linear``'s.

    The head is untied (``final_norm``, ``lm_head``, ``lm_nll``,
    ``lm_loss``). With ``mtp`` 1 (0: none), a multi-token-prediction module
    of depth 1 (DeepSeek-V3 section 2.2) stands between the last layer and
    the head: the embedding of token t+1 (``mtp_embed``: the main table,
    looked up with the targets) and the last layer's output BEFORE the
    final norm take an RMSNorm each (``mtp_enorm``, ``mtp_hnorm``), side by
    side (``mtp_cat``, the embedding first) through ``mtp_eh``
    (2 ``hidden`` -> ``hidden``) into one more sparse layer of its own
    weights (``mtp_mla_*``, ``mtp_router``, ``mtp_moe``, ``mtp_shared_*``)
    at the same positions; after the main head, ``mtp_snorm`` and
    ``mtp_head`` (the main head's matrix) give the logits of token t+2,
    ``mtp_shift`` the targets one on with the last position marked,
    ``mtp_nll`` and ``mtp_loss`` the mean over the S - 1 positions that
    have a second-next token, weighted ``mtp_weight`` in the objective.
    ``embed`` / ``mtp_embed`` share ``tok_w`` and ``lm_head`` / ``mtp_head``
    share ``head_w``: one array each, its gradient the sum of both users'.
    The main head between the module's block and the module's head makes
    them two ``/mtp_/`` remat units.

    ``attn_scale`` (0: 1 / sqrt(head)) is what multiplies the scores;
    ``rope_factor`` > 1 asks ATTENTION for YaRN's blended frequencies
    (``rope_original_positions``, ``rope_beta_fast``, ``rope_beta_slow``).

    ``streams`` n > 0 (0: the plain residual above, the net every caller
    had): the residual state is a STREAM of n hidden states side by side
    (hyper-connections with the manifold-constrained mapping,
    ``ops/hyper.py``). ``hc_start`` copies the embedding to the n streams;
    each sub-layer S of a block (``a``: attention, ``f``: the FFN, the
    shared expert inside it) maps the stream to its coefficients
    (``<p>hc_S_map``: one statistic, three projections, two sigmoids, the
    n x n mix through ``sinkhorn_iters`` Sinkhorn iterations), reads its
    input (``<p>hc_S_read``, into the sub-layer's norm) and writes the
    stream after it (``<p>hc_S_write``) where the plain block has
    ``<p>res1`` / ``<p>res2``; ``hc_end`` sums the streams before the final
    norm (and the module's ``mtp_hnorm``). The module's block runs on a
    stream of its own (``mtp_hc_start``, ``mtp_hc_end``). The mappings
    carry decay_mult 0.

    Gains and the selection biases carry decay_mult 0, every matrix 1."""
    from ..proto.messages import (AttentionParameter, ConcatParameter,
                                  EltwiseParameter, EmbedParameter,
                                  HDF5DataParameter, HyperParameter,
                                  MoEParameter, RMSNormParameter,
                                  SliceParameter, TokenShiftParameter)
    if mtp not in (0, 1):
        raise ValueError(f"glm_flash: mtp {mtp} is neither 0 nor 1 (the "
                         f"published depth)")
    w = gaussian(init_std)
    layers: List[LayerParameter] = [LayerParameter(
        name="tokens", type="HDF5_DATA", top=["tokens", "targets"],
        hdf5_data_param=HDF5DataParameter(source=source, batch_size=batch))]
    no_decay = ParamSpec(lr_mult=1.0, decay_mult=0.0)

    def norm(lname, bottom, top):
        layers.append(LayerParameter(
            name=lname, type="RMS_NORM", bottom=[bottom], top=[top],
            param=[no_decay], rms_norm_param=RMSNormParameter(eps=eps)))

    def proj(lname, bottom, top, n_out, spec=()):
        layers.append(LayerParameter(
            name=lname, type="INNER_PRODUCT", bottom=[bottom], top=[top],
            param=list(spec), inner_product_param=InnerProductParameter(
                num_output=n_out, bias_term=False, axis=2, weight_filler=w)))

    def add(lname, a, b, top):
        layers.append(LayerParameter(
            name=lname, type="ELTWISE", bottom=[a, b], top=[top],
            eltwise_param=EltwiseParameter(operation="SUM")))

    def embed(lname, bottom, top):
        layers.append(LayerParameter(
            name=lname, type="EMBED", bottom=[bottom], top=[top],
            param=[ParamSpec(name="tok_w")] if mtp else [],
            embed_param=EmbedParameter(input_dim=vocab, num_output=hidden,
                                       weight_filler=w)))

    def gated_mlp(p, bottom, top, n_mid):
        proj(p + "gate", bottom, p + "g", n_mid)
        proj(p + "up", bottom, p + "u", n_mid)
        layers.append(LayerParameter(
            name=p + "act", type="SILU_GATE", bottom=[p + "g", p + "u"],
            top=[p + "a"]))
        proj(p + "down", p + "a", top, hidden)

    def hyper(lname, kind, bottoms, tops):
        layers.append(LayerParameter(
            name=lname, type=kind, bottom=bottoms, top=tops,
            param=[no_decay] * 9 if kind == "HC_MAP" else [],
            hyper_param=HyperParameter(
                streams=streams, sinkhorn_iters=sinkhorn_iters, eps=hc_eps,
                clamp=hc_clamp, weight_filler=w)))

    def sublayer_in(p, s, x, top):
        """The stream ``x`` -> sub-layer ``s``'s input: its mapping's
        coefficients (and what a display shows of them), then the read."""
        hyper(f"{p}hc_{s}_map", "HC_MAP", [x],
              [f"{p}{s}_coef"] + [f"{p}hc_{s}_{what}" for what in (
                  "res_err", "pre_mean", "post_mean")])
        hyper(f"{p}hc_{s}_read", "HC_READ", [x, f"{p}{s}_coef"], [top])

    def block(p, x, sparse):
        if streams:
            sublayer_in(p, "a", x, p + "ah")
        norm(p + "attn_norm", p + "ah" if streams else x, p + "a")
        proj(p + "mla_qa", p + "a", p + "cq", q_rank)
        norm(p + "mla_qnorm", p + "cq", p + "cqn")
        proj(p + "mla_qb", p + "cqn", p + "q", heads * (nope_dim + rope_dim))
        proj(p + "mla_kva", p + "a", p + "kva", kv_rank + rope_dim)
        layers.append(LayerParameter(
            name=p + "mla_kva_split", type="SLICE", bottom=[p + "kva"],
            top=[p + "c", p + "kpe"],
            slice_param=SliceParameter(slice_dim=2, slice_point=[kv_rank])))
        norm(p + "mla_kvnorm", p + "c", p + "cn")
        proj(p + "mla_kvb_k", p + "cn", p + "kn", heads * nope_dim)
        proj(p + "mla_kvb_v", p + "cn", p + "v", heads * v_dim)
        layers.append(LayerParameter(
            name=p + "mla_attn", type="ATTENTION",
            bottom=[p + "q", p + "kn", p + "v", p + "kpe"], top=[p + "att"],
            attention_param=AttentionParameter(
                num_heads=heads, rope_theta=rope_theta,
                value_head_dim=v_dim, rotary_shared=True, scale=attn_scale,
                rope_factor=rope_factor,
                rope_original_positions=rope_original_positions,
                rope_beta_fast=rope_beta_fast,
                rope_beta_slow=rope_beta_slow)))
        proj(p + "mla_o", p + "att", p + "ao", hidden)
        if streams:
            hyper(p + "hc_a_write", "HC_WRITE",
                  [x, p + "ao", p + "a_coef"], [p + "h"])
            sublayer_in(p, "f", p + "h", p + "fh")
        else:
            add(p + "res1", x, p + "ao", p + "h")
        norm(p + "ffn_norm", p + "fh" if streams else p + "h", p + "u")
        if not sparse:
            gated_mlp(p + "ffn_", p + "u", p + "f", dense_width)
        else:
            moe = dict(num_experts=experts, top_k=top_k,
                       expert_width=expert_width, score_func="sigmoid",
                       route_scale=route_scale,
                       bias_update_rate=bias_update_rate, weight_filler=w)
            layers.append(LayerParameter(
                name=p + "router", type="MOE_ROUTER", bottom=[p + "u"],
                top=[p + "gates", p + "bias_next", p + "bias_max_abs"],
                param=[ParamSpec(), no_decay],
                moe_param=MoEParameter(**moe)))
            layers.append(LayerParameter(
                name=p + "moe", type="MOE", bottom=[p + "u", p + "gates"],
                top=[p + "m", p + "expert_load", p + "dropped",
                     p + "held_share"],
                moe_param=MoEParameter(num_held=held, held_first=held_first,
                                       **moe)))
            gated_mlp(p + "shared_", p + "u", p + "s", shared_width)
            add(p + "moe_sum", p + "m", p + "s", p + "f")
        if streams:
            hyper(p + "hc_f_write", "HC_WRITE",
                  [p + "h", p + "f", p + "f_coef"], [p + "y"])
        else:
            add(p + "res2", p + "h", p + "f", p + "y")
        return p + "y"

    def residual_state(p, h, run):
        """``run`` on the residual state that starts as ``h``: the hidden
        state itself, or the stream between its start and its end."""
        if not streams:
            return run(h)
        hyper(p + "hc_start", "HC_START", [h], [p + "xs"])
        hyper(p + "hc_end", "HC_END", [run(p + "xs")], [p + "xe"])
        return p + "xe"

    def trunk(x):
        for i in range(n_layers):
            x = block(f"l{i}_", x, sparse=i >= dense_layers)
        return x

    embed("embed", "tokens", "x0")
    x = residual_state("", "x0", trunk)
    if mtp:
        # token t+1 is the targets' token t: no shift before the lookup
        embed("mtp_embed", "targets", "mtp_e")
        norm("mtp_enorm", "mtp_e", "mtp_en")
        norm("mtp_hnorm", x, "mtp_hn")
        layers.append(LayerParameter(
            name="mtp_cat", type="CONCAT", bottom=["mtp_en", "mtp_hn"],
            top=["mtp_eh_in"], concat_param=ConcatParameter(concat_dim=2)))
        proj("mtp_eh", "mtp_eh_in", "mtp_z", hidden)
        z = residual_state("mtp_", "mtp_z",
                           lambda h: block("mtp_", h, sparse=True))
    head = [ParamSpec(name="head_w")] if mtp else []
    norm("final_norm", x, "xf")
    proj("lm_head", "xf", "logits", vocab, head)
    layers.append(LayerParameter(
        name="lm_nll", type="SOFTMAX_NLL", bottom=["logits", "targets"],
        top=["nll"]))
    # the mean over positions: the exit-weighted loss of ONE pass
    layers.append(LayerParameter(
        name="lm_loss", type="EXIT_LOSS", bottom=["nll"], top=["lm_loss"]))
    if mtp:
        norm("mtp_snorm", z, "mtp_zf")
        proj("mtp_head", "mtp_zf", "mtp_logits", vocab, head)
        layers.append(LayerParameter(
            name="mtp_shift", type="TOKEN_SHIFT", bottom=["targets"],
            top=["mtp_targets", "mtp_real"],
            token_shift_param=TokenShiftParameter(offset=1)))
        layers.append(LayerParameter(
            name="mtp_nll", type="SOFTMAX_NLL",
            bottom=["mtp_logits", "mtp_targets"], top=["mtp_nll_pos"]))
        layers.append(LayerParameter(
            name="mtp_loss", type="WEIGHTED_MEAN_LOSS",
            bottom=["mtp_nll_pos", "mtp_real"], top=["mtp_loss"],
            loss_weight=[mtp_weight]))
    return NetParameter(name=name, layers=layers)


def xing4(batch: int = 1, n_layers: int = 40, held: int = 0,
          vocab: int = 131072, mtp: int = 1,
          source: str = "examples/lm/xing4_0_29b_a4b_tokens.txt",
          dense_layers: int = 2, hidden: int = 3584, heads: int = 32,
          q_rank: int = 768, kv_rank: int = 512, nope_dim: int = 128,
          rope_dim: int = 64, v_dim: int = 128, dense_width: int = 9216,
          experts: int = 64, top_k: int = 4, held_first: int = 0,
          expert_width: int = 1024, shared_width: int = 1024,
          route_scale: float = 2.0, rope_theta: float = 10000.0,
          eps: float = 1e-6, streams: int = 4, sinkhorn_iters: int = 20,
          hc_eps: float = 1e-6, hc_clamp: float = 30.0,
          rope_factor: float = 64.0, rope_original_positions: int = 4096,
          rope_beta_fast: float = 32.0, rope_beta_slow: float = 1.0,
          mscale_all_dim: float = 1.0, **rest) -> NetParameter:
    """Xing4.0-29B-A4B (config.json of XingChen-AGI/Xing4.0-29B-A4B,
    ``xing4_0``): ``glm_flash``'s block — latent attention in every layer
    (32 heads of [128 ; 64] against values of 128, the 64-wide shared key
    part and every q head's last 64 dims rotating), ``dense_layers``
    leading dense layers, then sigmoid top-4 routers over 64 experts with a
    shared expert, the prediction module — on a residual state of
    ``streams`` = 4 hidden states (``glm_flash``'s ``streams``:
    manifold-constrained hyper-connections, a read, a write and a 4 x 4
    Sinkhorn-projected mix a token and sub-layer), with YaRN's rotary
    frequencies (``rope_factor`` 64 over 4,096 positions) and the scores'
    scale (0.1 ``mscale_all_dim`` ln(``rope_factor``) + 1)^2 / sqrt(192)
    (YaRN's mscale squared, the DeepSeek-V3 form; cos and sin take none).
    With no arguments the whole published model: 40 layers, 2 of them
    dense, 64 experts, 131,072 rows, the module. ``rest`` goes to
    ``glm_flash`` (``bias_update_rate``, ``mtp_weight``, ``init_std``)."""
    mscale = 0.1 * mscale_all_dim * math.log(rope_factor) + 1.0 \
        if rope_factor > 1 else 1.0
    return glm_flash(
        batch=batch, n_layers=n_layers, held=held, vocab=vocab, mtp=mtp,
        source=source, dense_layers=dense_layers, hidden=hidden, heads=heads,
        q_rank=q_rank, kv_rank=kv_rank, nope_dim=nope_dim, rope_dim=rope_dim,
        v_dim=v_dim, dense_width=dense_width, experts=experts, top_k=top_k,
        held_first=held_first, expert_width=expert_width,
        shared_width=shared_width, route_scale=route_scale,
        rope_theta=rope_theta, eps=eps, streams=streams,
        sinkhorn_iters=sinkhorn_iters, hc_eps=hc_eps, hc_clamp=hc_clamp,
        attn_scale=mscale * mscale / math.sqrt(nope_dim + rope_dim),
        rope_factor=rope_factor,
        rope_original_positions=rope_original_positions,
        rope_beta_fast=rope_beta_fast, rope_beta_slow=rope_beta_slow,
        **{"name": "Xing4.0-29B-A4B", **rest})


def olmo_hybrid(batch: int = 1,
                source: str = "examples/lm/olmo_hybrid_7b_tokens.txt",
                n_layers: int = 32, hidden: int = 3840, heads: int = 30,
                heads_held: int = 0,
                key_head_dim: int = 96, value_head_dim: int = 192,
                conv_taps: int = 4, full_every: int = 4,
                attn_head_dim: int = 128, ffn_width: int = 11008,
                vocab: int = 100352, eps: float = 1e-6,
                init_std: float = 0.02,
                name: str = "Olmo-Hybrid-7B") -> NetParameter:
    """Olmo-Hybrid-7B (config.json of allenai/Olmo-Hybrid-7B,
    ``olmo_hybrid``): every layer is a token mixer and a dense FFN in the
    family's (Olmo 2 / 3) REORDERED norm, an RMSNorm on each sublayer's
    OUTPUT and none on its input,

        h = x + N_a(Mix(x));  y = h + N_f(FFN(h))
        FFN(h) = W_d (SiLU(W_g h) * W_u h)           no bias anywhere

    a final RMSNorm before the untied head. Layer ``full_every``
    (1-indexed) and every ``full_every``-th after it mixes by full
    attention, the others by Gated DeltaNet (``layer_types`` = (linear,
    linear, linear, full) x 8).

    Linear layer (``l<i>_gdn_*``), per head of d_k ``key_head_dim`` and d_v
    ``value_head_dim``, per token t:

        q, k, v = SiLU(conv4(W_q x)), SiLU(conv4(W_k x)), SiLU(conv4(W_v x))
        q = q / |q|_2 * d_k^-1/2 ;  k = k / |k|_2         per head, f32
        beta_t = 2 sigmoid(W_b x_t)                       one a head, (0, 2)
        g_t    = -exp(A_log) softplus(W_a x_t + dt_bias)  ONE a head, <= 0
        Sbar_t = exp(g_t) S_{t-1}
        S_t    = Sbar_t + beta_t k_t (v_t - Sbar_t^T k_t)^T    S_0 = 0, f32
        o_t    = S_t^T q_t
        Mix(x) = W_o [RMSNorm_dv(o_t) * SiLU(W_z x_t)]    one d_v-wide gain

    as layers: ``l<i>_gdn_{q,k,v}`` project x, ``l<i>_gdn_conv_*`` the
    causal depthwise convolutions and their SiLU, ``l<i>_gdn_l2_{q,k}``
    (q's scale is the scan's), ``l<i>_gdn_a`` and ``l<i>_gdn_decay`` (whose
    second top ``l<i>_decay_mean`` every display carries: the mean exp(g),
    does the state forget?), ``l<i>_gdn_b``, ``l<i>_gdn_beta_sig`` and
    ``l<i>_gdn_beta`` (POWER: the doubling; ``linear_allow_neg_eigval``),
    ``l<i>_gdn_scan`` (KDA_SCAN with g of (N, S, H); its second top
    ``l<i>_beta_over_one`` is the share of writes with beta > 1),
    ``l<i>_gdn_onorm``, ``l<i>_gdn_z`` and ``l<i>_gdn_gate`` (SILU_GATE),
    ``l<i>_gdn_o``.

    Full layer (``l<i>_attn_*``): q = N_q(W_q x), k = N_k(W_k x) with an
    RMSNorm over ALL the held heads' channels (one gain vector each), v =
    W_v x, causal softmax at ``attn_head_dim``^-1/2 with NO positions
    (``rope_theta`` is null in the source), ``l<i>_attn_o``.

    ``heads_held`` of the ``heads`` heads of BOTH mixers (0 = all): with
    fewer the net is one chip's share of a model whose layers are shared
    by heads (WHICH heads decides no shape, so the net does not ask). Every projection of both
    mixers then emits, and W_o consumes, the held heads' columns only; the
    partial W_o result goes on into N_a and the residual as it is — nothing
    stands in for the other chips or their all-reduce, and the whole-vector
    QK-norm's mean square runs over the held channels. The FFN stays whole.

    Gains, A_log and dt_bias carry decay_mult 0, every matrix and the
    convolutions' taps 1."""
    from ..proto.messages import (AttentionParameter, EltwiseParameter,
                                  EmbedParameter, HDF5DataParameter,
                                  KDAParameter, PowerParameter,
                                  RMSNormParameter)
    held = heads_held or heads
    if not 0 < held <= heads:
        raise ValueError(f"olmo_hybrid: {held} heads held of {heads}")
    if held < heads:
        name = f"{name} ({held} of {heads} heads)"
    w = gaussian(init_std)
    no_decay = ParamSpec(lr_mult=1.0, decay_mult=0.0)
    layers: List[LayerParameter] = [LayerParameter(
        name="tokens", type="HDF5_DATA", top=["tokens", "targets"],
        hdf5_data_param=HDF5DataParameter(source=source, batch_size=batch))]

    def norm(lname, bottom, top, per_head=0):
        layers.append(LayerParameter(
            name=lname, type="RMS_NORM", bottom=[bottom], top=[top],
            param=[no_decay], rms_norm_param=RMSNormParameter(
                eps=eps, num_heads=per_head)))

    def proj(lname, bottom, top, n_out):
        layers.append(LayerParameter(
            name=lname, type="INNER_PRODUCT", bottom=[bottom], top=[top],
            inner_product_param=InnerProductParameter(
                num_output=n_out, bias_term=False, axis=2, weight_filler=w)))

    def add(lname, a, b, top):
        layers.append(LayerParameter(
            name=lname, type="ELTWISE", bottom=[a, b], top=[top],
            eltwise_param=EltwiseParameter(operation="SUM")))

    def silu_gate(lname, gate, up, top):
        layers.append(LayerParameter(
            name=lname, type="SILU_GATE", bottom=[gate, up], top=[top]))

    def gdn(p, x, top):
        kp = dict(num_heads=held)
        taps = FillerParameter(type="uniform", min=-conv_taps ** -0.5,
                               max=conv_taps ** -0.5)
        for t, d in (("q", key_head_dim), ("k", key_head_dim),
                     ("v", value_head_dim)):
            proj(p + "gdn_" + t, x, p + t + "p", held * d)
            layers.append(LayerParameter(
                name=p + "gdn_conv_" + t, type="SHORT_CONV",
                bottom=[p + t + "p"], top=[p + t + "c"],
                kda_param=KDAParameter(kernel_size=conv_taps,
                                       weight_filler=taps)))
        for t in "qk":
            layers.append(LayerParameter(
                name=p + "gdn_l2_" + t, type="L2_NORM", bottom=[p + t + "c"],
                top=[p + t + "n"], kda_param=KDAParameter(**kp)))
        proj(p + "gdn_a", x, p + "al", held)
        layers.append(LayerParameter(
            name=p + "gdn_decay", type="KDA_DECAY", bottom=[p + "al"],
            top=[p + "gdec", p + "decay_mean"], param=[no_decay, no_decay],
            kda_param=KDAParameter(**kp)))
        proj(p + "gdn_b", x, p + "bl", held)
        layers.append(LayerParameter(name=p + "gdn_beta_sig", type="SIGMOID",
                                     bottom=[p + "bl"], top=[p + "bs"]))
        layers.append(LayerParameter(
            name=p + "gdn_beta", type="POWER", bottom=[p + "bs"],
            top=[p + "beta"], power_param=PowerParameter(scale=2.0)))
        layers.append(LayerParameter(
            name=p + "gdn_scan", type="KDA_SCAN",
            bottom=[p + "qn", p + "kn", p + "vc", p + "gdec", p + "beta"],
            top=[p + "so", p + "beta_over_one"],
            kda_param=KDAParameter(**kp)))
        norm(p + "gdn_onorm", p + "so", p + "son", held)
        proj(p + "gdn_z", x, p + "z", held * value_head_dim)
        silu_gate(p + "gdn_gate", p + "z", p + "son", p + "sog")
        proj(p + "gdn_o", p + "sog", top, hidden)

    def attn(p, x, top):
        for t in "qkv":
            proj(p + "attn_" + t, x, p + t, held * attn_head_dim)
        norm(p + "attn_qnorm", p + "q", p + "qn")
        norm(p + "attn_knorm", p + "k", p + "kn")
        layers.append(LayerParameter(
            name=p + "attn_sdpa", type="ATTENTION",
            bottom=[p + "qn", p + "kn", p + "v"], top=[p + "att"],
            attention_param=AttentionParameter(num_heads=held, rope=False)))
        proj(p + "attn_o", p + "att", top, hidden)

    layers.append(LayerParameter(
        name="embed", type="EMBED", bottom=["tokens"], top=["x0"],
        embed_param=EmbedParameter(input_dim=vocab, num_output=hidden,
                                   weight_filler=w)))
    x = "x0"
    for i in range(n_layers):
        p = f"l{i}_"
        (attn if (i + 1) % full_every == 0 else gdn)(p, x, p + "mo")
        norm(p + "mix_norm", p + "mo", p + "mn")
        add(p + "res1", x, p + "mn", p + "h")
        proj(p + "ffn_gate", p + "h", p + "fg", ffn_width)
        proj(p + "ffn_up", p + "h", p + "fu", ffn_width)
        silu_gate(p + "ffn_act", p + "fg", p + "fu", p + "fa")
        proj(p + "ffn_down", p + "fa", p + "fo", hidden)
        norm(p + "ffn_norm", p + "fo", p + "fn")
        add(p + "res2", p + "h", p + "fn", p + "y")
        x = p + "y"
    norm("final_norm", x, "xf")
    proj("lm_head", "xf", "logits", vocab)
    layers.append(LayerParameter(
        name="lm_nll", type="SOFTMAX_NLL", bottom=["logits", "targets"],
        top=["nll"]))
    # the mean over positions: the exit-weighted loss of ONE pass
    layers.append(LayerParameter(
        name="lm_loss", type="EXIT_LOSS", bottom=["nll"], top=["lm_loss"]))
    return NetParameter(name=name, layers=layers)


def granite_hybrid(batch: int = 1,
                   source: str = "examples/lm/granite_h_micro_tokens.txt",
                   layers: int = 40, vocab_rows: int = 100352,
                   hidden: int = 2048, attn_every: int = 10,
                   attn_at: int = 5, heads: int = 32, kv_heads: int = 8,
                   ssd_heads: int = 64, ssd_head_dim: int = 64,
                   state: int = 128, conv_taps: int = 4,
                   ffn_width: int = 8192, eps: float = 1e-5,
                   embedding_multiplier: float = 12.0,
                   attention_multiplier: float = 0.015625,
                   residual_multiplier: float = 0.22,
                   logits_scaling: float = 8.0, init_std: float = 0.02,
                   name: str = "Granite-4.0-H-Micro") -> NetParameter:
    """Granite-4.0-H-Micro (config.json of ibm-granite/granite-4.0-h-micro,
    ``granitemoehybrid`` with no experts): 40 pre-norm layers, nine Mamba-2
    mixers to one attention mixer (``layer_types``: attention at layers 5,
    15, 25, 35; a layer i mixes by attention where i % ``attn_every`` ==
    ``attn_at``), a SwiGLU MLP in every layer, four scalar multipliers, a
    table tied to the head, no bias but the convolution's:

        h_0 = 12 E[ids]
        u = h + 0.22 Mix(N1(h));  h' = u + 0.22 MLP(N2(u))
        MLP(y) = W_out (SiLU(a) * b),  [a, b] = W_in y
        logits = N_f(h_L) E^T / 8

    Mamba-2 layer (``l<i>_ssd_*``), H ``ssd_heads`` heads of P
    ``ssd_head_dim``, a state of N ``state`` a head, ONE group of B / C:

        [z, xBC, dt~] = W_in y                 (H P | H P + 2 N | H)
        [x, B, C] = SiLU(conv4(xBC) + b_conv)  causal, depthwise
        dt = softplus(dt~ + dt_bias);  a = -exp(A_log) dt      one a head
        H_t = exp(a_t) H_{t-1} + dt_t x_t B_t^T;  y_t = H_t C_t + D x_t
        Mix = W_out N_g(y * SiLU(z))           gate, THEN one norm over H P

    as layers: ``l<i>_ssd_in`` (one INNER_PRODUCT) and ``l<i>_ssd_in_split``
    (SLICE), ``l<i>_ssd_conv`` (SHORT_CONV with ``bias_term``) and
    ``l<i>_ssd_conv_split``, ``l<i>_ssd_decay`` (KDA_DECAY with four tops:
    a, ``l<i>_ssd_decay_mean``, dt, ``l<i>_ssd_dt_mean``; the two means are
    scalars every display carries), ``l<i>_ssd_scan`` (SSD_SCAN, blob D),
    ``l<i>_ssd_gate`` (SILU_GATE), ``l<i>_ssd_onorm``, ``l<i>_ssd_out``.

    Attention layer (``l<i>_attn_*``): ``heads`` query and ``kv_heads``
    key-value heads of hidden / heads, NO positions, causal
    softmax(``attention_multiplier`` q k^T) v (ATTENTION's ``scale``).

    The multipliers are layers the Net has: POWER's scale on the embedding
    (``embed_scale``) and on the final norm's result (``lm_scale``: a
    division by 8 commutes with the tied product and is exact in any
    float), ELTWISE SUM's coeff on the residuals. ``layers`` of the 40 (the
    first that many: 10 is one period) and ``vocab_rows`` of the 100,352
    rows of the table give one pipeline stage's cut.

    Gains, A_log, dt_bias, D and the convolution's bias carry decay_mult 0,
    every matrix and the convolution's taps 1."""
    from ..proto.messages import (AttentionParameter, EltwiseParameter,
                                  EmbedParameter, HDF5DataParameter,
                                  KDAParameter, PowerParameter,
                                  RMSNormParameter, SliceParameter)
    if layers != 40 or vocab_rows != 100352:
        name = f"{name} ({layers} of 40 layers, {vocab_rows} rows)"
    w = gaussian(init_std)
    no_decay = ParamSpec(lr_mult=1.0, decay_mult=0.0)
    tied = [ParamSpec(name="tok_w")]
    net: List[LayerParameter] = [LayerParameter(
        name="tokens", type="HDF5_DATA", top=["tokens", "targets"],
        hdf5_data_param=HDF5DataParameter(source=source, batch_size=batch))]

    def norm(lname, bottom, top):
        net.append(LayerParameter(
            name=lname, type="RMS_NORM", bottom=[bottom], top=[top],
            param=[no_decay], rms_norm_param=RMSNormParameter(eps=eps)))

    def proj(lname, bottom, top, n_out, spec=()):
        net.append(LayerParameter(
            name=lname, type="INNER_PRODUCT", bottom=[bottom], top=[top],
            param=list(spec), inner_product_param=InnerProductParameter(
                num_output=n_out, bias_term=False, axis=2, weight_filler=w)))

    def split(lname, bottom, tops, points):
        net.append(LayerParameter(
            name=lname, type="SLICE", bottom=[bottom], top=list(tops),
            slice_param=SliceParameter(slice_dim=2,
                                       slice_point=list(points))))

    def scaled(lname, bottom, top, by):
        net.append(LayerParameter(
            name=lname, type="POWER", bottom=[bottom], top=[top],
            power_param=PowerParameter(scale=by)))

    def residual(lname, a, b, top):
        net.append(LayerParameter(
            name=lname, type="ELTWISE", bottom=[a, b], top=[top],
            eltwise_param=EltwiseParameter(
                operation="SUM", coeff=[1.0, residual_multiplier])))

    def silu_gate(lname, gate, up, top):
        net.append(LayerParameter(
            name=lname, type="SILU_GATE", bottom=[gate, up], top=[top]))

    def mamba(p, y, top):
        inner = ssd_heads * ssd_head_dim
        kp = dict(num_heads=ssd_heads)
        taps = FillerParameter(type="uniform", min=-conv_taps ** -0.5,
                               max=conv_taps ** -0.5)
        proj(p + "ssd_in", y, p + "zxd", 2 * inner + 2 * state + ssd_heads)
        split(p + "ssd_in_split", p + "zxd", [p + "z", p + "xbc", p + "dtr"],
              [inner, 2 * inner + 2 * state])
        net.append(LayerParameter(
            name=p + "ssd_conv", type="SHORT_CONV", bottom=[p + "xbc"],
            top=[p + "xbcc"], param=[ParamSpec(), no_decay],
            kda_param=KDAParameter(kernel_size=conv_taps, weight_filler=taps,
                                   bias_term=True, bias_filler=taps)))
        split(p + "ssd_conv_split", p + "xbcc", [p + "xs", p + "B", p + "C"],
              [inner, inner + state])
        net.append(LayerParameter(
            name=p + "ssd_decay", type="KDA_DECAY", bottom=[p + "dtr"],
            top=[p + "a", p + "ssd_decay_mean", p + "dt",
                 p + "ssd_dt_mean"], param=[no_decay, no_decay],
            kda_param=KDAParameter(**kp)))
        net.append(LayerParameter(
            name=p + "ssd_scan", type="SSD_SCAN",
            bottom=[p + "xs", p + "dt", p + "a", p + "B", p + "C"],
            top=[p + "sy"], param=[no_decay], kda_param=KDAParameter(**kp)))
        silu_gate(p + "ssd_gate", p + "z", p + "sy", p + "sg")
        norm(p + "ssd_onorm", p + "sg", p + "sn")
        proj(p + "ssd_out", p + "sn", top, hidden)

    def attention(p, y, top):
        d_head = hidden // heads
        for t, n in (("q", heads), ("k", kv_heads), ("v", kv_heads)):
            proj(p + "attn_" + t, y, p + t, n * d_head)
        net.append(LayerParameter(
            name=p + "attn_sdpa", type="ATTENTION",
            bottom=[p + "q", p + "k", p + "v"], top=[p + "att"],
            attention_param=AttentionParameter(
                num_heads=heads, num_kv_heads=kv_heads, rope=False,
                scale=attention_multiplier)))
        proj(p + "attn_o", p + "att", top, hidden)

    net.append(LayerParameter(
        name="embed", type="EMBED", bottom=["tokens"], top=["x0"],
        param=tied, embed_param=EmbedParameter(
            input_dim=vocab_rows, num_output=hidden, weight_filler=w)))
    scaled("embed_scale", "x0", "h0", embedding_multiplier)
    h = "h0"
    for i in range(layers):
        p = f"l{i}_"
        norm(p + "norm1", h, p + "n1")
        (attention if i % attn_every == attn_at else mamba)(
            p, p + "n1", p + "mo")
        residual(p + "res1", h, p + "mo", p + "u")
        norm(p + "norm2", p + "u", p + "n2")
        proj(p + "ffn_in", p + "n2", p + "fab", 2 * ffn_width)
        split(p + "ffn_split", p + "fab", [p + "fa", p + "fb"], [ffn_width])
        silu_gate(p + "ffn_act", p + "fa", p + "fb", p + "fg")
        proj(p + "ffn_out", p + "fg", p + "fo", hidden)
        residual(p + "res2", p + "u", p + "fo", p + "y")
        h = p + "y"
    norm("final_norm", h, "xf")
    scaled("lm_scale", "xf", "xs", 1.0 / logits_scaling)
    proj("lm_head", "xs", "logits", vocab_rows, tied)
    net.append(LayerParameter(
        name="lm_nll", type="SOFTMAX_NLL", bottom=["logits", "targets"],
        top=["nll"]))
    # the mean over positions: the exit-weighted loss of ONE pass
    net.append(LayerParameter(
        name="lm_loss", type="EXIT_LOSS", bottom=["nll"], top=["lm_loss"]))
    return NetParameter(name=name, layers=net)


NEMOTRON_PATTERN = "MEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEMEM*EMEMEMEME"


def nemotron_h(batch: int = 1, pattern: str = NEMOTRON_PATTERN,
               held: int = 0, held_first: int = 0,
               vocab_rows: int = 131072,
               source: str = "examples/lm/nemotron_3_nano_30b_a3b_tokens"
                             ".txt",
               hidden: int = 2688, ssd_heads: int = 64,
               ssd_head_dim: int = 64, state: int = 128, groups: int = 8,
               conv_taps: int = 4, heads: int = 32, kv_heads: int = 2,
               head_dim: int = 128, experts: int = 128, top_k: int = 6,
               expert_width: int = 1856, shared_width: int = 3712,
               route_scale: float = 2.5, bias_update_rate: float = 0.001,
               eps: float = 1e-5, init_std: float = 0.02,
               init_layers: int = 52,
               name: str = "NVIDIA-Nemotron-3-Nano-30B-A3B") -> NetParameter:
    """NVIDIA-Nemotron-3-Nano-30B-A3B (config.json of
    nvidia/NVIDIA-Nemotron-3-Nano-30B-A3B-BF16, ``nemotron_h``): every layer
    is ONE sub-layer behind one norm, which one by its letter in ``pattern``
    (``hybrid_override_pattern``: ``M`` Mamba-2, ``E`` mixture of experts,
    ``*`` attention; the published 52 are 23 / 23 / 6), a final norm, an
    untied head, no bias but the convolution's:

        h_0 = E[ids];  h' = h + Sub_i(RMSNorm(h));  logits = W_head N_f(h_L)

    ``M`` (``l<i>_ssd_*``), H ``ssd_heads`` heads of P ``ssd_head_dim`` (an
    inner width of H P, NOT ``expand`` x hidden), a state of N ``state`` a
    head, G ``groups`` groups of B / C, head h reading group h // (H / G):

        [z | xBC | dt~] = W_in y               (H P | H P + 2 G N | H)
        [x | B | C] = SiLU(conv4(xBC) + b_conv)    causal, depthwise
        dt = softplus(dt~ + dt_bias);  a = -exp(A_log) dt      one a head
        H_t = exp(a_t) H_{t-1} + dt_t x_t B_{t,g(h)}^T
        y_t = H_t C_{t,g(h)} + D_h x_t
        Sub = W_out N_G(y * SiLU(z))   gate THEN norm; N_G: RMS over each
                                       group's H P / G channels, one gain
                                       of H P

    as layers: ``l<i>_ssd_in``, ``l<i>_ssd_in_split``, ``l<i>_ssd_conv``
    (SHORT_CONV with ``bias_term``), ``l<i>_ssd_conv_split``,
    ``l<i>_ssd_decay`` (KDA_DECAY, four tops: a, ``l<i>_ssd_decay_mean``,
    dt, ``l<i>_ssd_dt_mean``), ``l<i>_ssd_scan`` (SSD_SCAN with
    ``num_groups``), ``l<i>_ssd_gate``, ``l<i>_ssd_onorm`` (RMS_NORM with
    ``num_groups``), ``l<i>_ssd_out``.

    ``*`` (``l<i>_attn_{q,k,v,sdpa,o}``): ``heads`` query and ``kv_heads``
    key-value heads of ``head_dim`` (q and o wider than the hidden state),
    causal softmax(q k^T / sqrt(head_dim)) v, NO positions.

    ``E`` (``l<i>_moe_*``): ``l<i>_moe_router`` (MOE_ROUTER: sigmoid scores
    over all ``experts`` in f32, the ``top_k`` of score + selection bias
    chosen, weighed by the unbiased scores over their sum times
    ``route_scale``, the bias balanced by the layer at
    ``bias_update_rate``), ``l<i>_moe_experts`` (MOE with ``activation``
    "relu2": UNGATED experts W2 relu(W1 y)^2 of ``expert_width``, two
    stacks, holding ``held`` of them from ``held_first`` on; 0 = all: with
    fewer the net is one rank's share of an expert-parallel model; its
    scalar tops are the held load, the dropped assignments, the held share
    and the share of pre-activations the ReLU zeroes) and the shared expert
    W2_s relu(W1_s y)^2 of ``shared_width`` as INNER_PRODUCT
    (``l<i>_moe_shared_up``) -> RELU (``_shared_relu``) -> POWER 2
    (``_shared_sq``) -> INNER_PRODUCT (``_shared_down``), added unweighted
    (``l<i>_moe_sum``).

    ``pattern`` (any string of the three letters: the first nine published,
    ``MEMEM*EME``, are the benchmark's cut) and ``vocab_rows`` of the
    131,072 rows of the table AND of the head give one rank's cut.
    Out-projections (``ssd_out``, ``attn_o``, ``moe_shared_down``) are
    initialised std / sqrt(2 ``init_layers``) (``rescale_prenorm_residual``
    at the PUBLISHED depth, whatever the cut); the experts' two stacks share
    the MOE layer's one filler. Gains, A_log, dt_bias, D, the convolution's
    bias and the selection bias carry decay_mult 0, every matrix and the
    convolution's taps 1."""
    from ..proto.messages import (AttentionParameter, EltwiseParameter,
                                  EmbedParameter, HDF5DataParameter,
                                  KDAParameter, MoEParameter, PowerParameter,
                                  RMSNormParameter, SliceParameter)
    if set(pattern) - set("ME*") or not pattern:
        raise ValueError(f"nemotron_h: pattern {pattern!r} is no string of "
                         f"M (Mamba-2), E (experts) and * (attention)")
    if pattern != NEMOTRON_PATTERN or vocab_rows != 131072 or held:
        name = (f"{name} (layers {pattern}, {held or experts} of {experts} "
                f"experts, {vocab_rows} rows)")
    w = gaussian(init_std)
    w_out = gaussian(init_std / math.sqrt(2 * init_layers))
    no_decay = ParamSpec(lr_mult=1.0, decay_mult=0.0)
    net: List[LayerParameter] = [LayerParameter(
        name="tokens", type="HDF5_DATA", top=["tokens", "targets"],
        hdf5_data_param=HDF5DataParameter(source=source, batch_size=batch))]

    def norm(lname, bottom, top, in_groups=0):
        net.append(LayerParameter(
            name=lname, type="RMS_NORM", bottom=[bottom], top=[top],
            param=[no_decay], rms_norm_param=RMSNormParameter(
                eps=eps, num_groups=in_groups)))

    def proj(lname, bottom, top, n_out, filler=w):
        net.append(LayerParameter(
            name=lname, type="INNER_PRODUCT", bottom=[bottom], top=[top],
            inner_product_param=InnerProductParameter(
                num_output=n_out, bias_term=False, axis=2,
                weight_filler=filler)))

    def split(lname, bottom, tops, points):
        net.append(LayerParameter(
            name=lname, type="SLICE", bottom=[bottom], top=list(tops),
            slice_param=SliceParameter(slice_dim=2,
                                       slice_point=list(points))))

    def add(lname, a, b, top):
        net.append(LayerParameter(
            name=lname, type="ELTWISE", bottom=[a, b], top=[top],
            eltwise_param=EltwiseParameter(operation="SUM")))

    def mamba(p, y, top):
        inner, bc = ssd_heads * ssd_head_dim, groups * state
        kp = dict(num_heads=ssd_heads)
        taps = FillerParameter(type="uniform", min=-conv_taps ** -0.5,
                               max=conv_taps ** -0.5)
        proj(p + "ssd_in", y, p + "zxd", 2 * inner + 2 * bc + ssd_heads)
        split(p + "ssd_in_split", p + "zxd", [p + "z", p + "xbc", p + "dtr"],
              [inner, 2 * inner + 2 * bc])
        net.append(LayerParameter(
            name=p + "ssd_conv", type="SHORT_CONV", bottom=[p + "xbc"],
            top=[p + "xbcc"], param=[ParamSpec(), no_decay],
            kda_param=KDAParameter(kernel_size=conv_taps, weight_filler=taps,
                                   bias_term=True, bias_filler=taps)))
        split(p + "ssd_conv_split", p + "xbcc", [p + "xs", p + "B", p + "C"],
              [inner, inner + bc])
        net.append(LayerParameter(
            name=p + "ssd_decay", type="KDA_DECAY", bottom=[p + "dtr"],
            top=[p + "a", p + "ssd_decay_mean", p + "dt",
                 p + "ssd_dt_mean"], param=[no_decay, no_decay],
            kda_param=KDAParameter(**kp)))
        net.append(LayerParameter(
            name=p + "ssd_scan", type="SSD_SCAN",
            bottom=[p + "xs", p + "dt", p + "a", p + "B", p + "C"],
            top=[p + "sy"], param=[no_decay],
            kda_param=KDAParameter(num_groups=groups, **kp)))
        net.append(LayerParameter(
            name=p + "ssd_gate", type="SILU_GATE", bottom=[p + "z", p + "sy"],
            top=[p + "sg"]))
        norm(p + "ssd_onorm", p + "sg", p + "sn", groups)
        proj(p + "ssd_out", p + "sn", top, hidden, w_out)

    def attention(p, y, top):
        for t, n in (("q", heads), ("k", kv_heads), ("v", kv_heads)):
            proj(p + "attn_" + t, y, p + t, n * head_dim)
        net.append(LayerParameter(
            name=p + "attn_sdpa", type="ATTENTION",
            bottom=[p + "q", p + "k", p + "v"], top=[p + "att"],
            attention_param=AttentionParameter(
                num_heads=heads, num_kv_heads=kv_heads, rope=False)))
        proj(p + "attn_o", p + "att", top, hidden, w_out)

    def sparse(p, y, top):
        moe = dict(num_experts=experts, top_k=top_k,
                   expert_width=expert_width, score_func="sigmoid",
                   route_scale=route_scale,
                   bias_update_rate=bias_update_rate, weight_filler=w)
        net.append(LayerParameter(
            name=p + "moe_router", type="MOE_ROUTER", bottom=[y],
            top=[p + "gates", p + "bias_next", p + "bias_max_abs"],
            param=[ParamSpec(), no_decay], moe_param=MoEParameter(**moe)))
        net.append(LayerParameter(
            name=p + "moe_experts", type="MOE", bottom=[y, p + "gates"],
            top=[p + "m", p + "expert_load", p + "dropped",
                 p + "held_share", p + "act_zero_share"],
            moe_param=MoEParameter(num_held=held, held_first=held_first,
                                   activation="relu2", **moe)))
        proj(p + "moe_shared_up", y, p + "su", shared_width)
        net.append(LayerParameter(
            name=p + "moe_shared_relu", type="RELU", bottom=[p + "su"],
            top=[p + "sr"]))
        net.append(LayerParameter(
            name=p + "moe_shared_sq", type="POWER", bottom=[p + "sr"],
            top=[p + "sq"], power_param=PowerParameter(power=2.0)))
        proj(p + "moe_shared_down", p + "sq", p + "sd", hidden, w_out)
        add(p + "moe_sum", p + "m", p + "sd", top)

    net.append(LayerParameter(
        name="embed", type="EMBED", bottom=["tokens"], top=["h0"],
        embed_param=EmbedParameter(
            input_dim=vocab_rows, num_output=hidden, weight_filler=w)))
    h = "h0"
    sub = {"M": mamba, "E": sparse, "*": attention}
    for i, letter in enumerate(pattern):
        p = f"l{i}_"
        norm(p + "norm", h, p + "n")
        sub[letter](p, p + "n", p + "o")
        add(p + "res", h, p + "o", p + "y")
        h = p + "y"
    norm("final_norm", h, "xf")
    proj("lm_head", "xf", "logits", vocab_rows)
    net.append(LayerParameter(
        name="lm_nll", type="SOFTMAX_NLL", bottom=["logits", "targets"],
        top=["nll"]))
    # the mean over positions: the exit-weighted loss of ONE pass
    net.append(LayerParameter(
        name="lm_loss", type="EXIT_LOSS", bottom=["nll"], top=["lm_loss"]))
    return NetParameter(name=name, layers=net)
