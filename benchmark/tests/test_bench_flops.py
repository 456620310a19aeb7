"""Required FLOPs and the prototxt reader, against numbers worked by hand."""

import json
import os

import pytest

import caffe_proto
import flops
from conftest import BENCH_DIR, ROOT


def records(config: str, phase: str = "TRAIN", batch: int = 1):
    with open(os.path.join(BENCH_DIR, "configs", f"{config}.json")) as f:
        cfg = json.load(f)
    with open(os.path.join(BENCH_DIR, cfg["net"])) as f:
        net = caffe_proto.parse(f.read())
    data = caffe_proto.data_layer(net, phase)
    crop = data["crop_size"]
    recs = caffe_proto.infer(
        caffe_proto.phase_layers(net, phase),
        {data["tops"][0]: (batch, 3, crop, crop), data["tops"][1]: (batch,)})
    return cfg, data, recs


def test_alexnet_layers_by_hand():
    _, data, recs = records("bvlc_alexnet")
    per_layer = flops.required_flops_per_image(recs, data["tops"])
    by_name = {r["name"]: r for r in recs}
    # conv1: 96 filters of 3x11x11 at stride 4 over 227 -> 55x55; fed by the
    # input blob, so forward + weight gradient only
    assert by_name["conv1"]["top_shapes"][0] == (1, 96, 55, 55)
    conv1_macs = 55 * 55 * 96 * 3 * 11 * 11
    assert per_layer["conv1"] == 2 * conv1_macs * 2 == 421_660_800 * 1.0
    # conv2: two groups, 48 input channels each, 5x5 pad 2 over 27x27
    assert by_name["conv2"]["bottom_shapes"][0] == (1, 96, 27, 27)
    assert per_layer["conv2"] == 2 * (27 * 27 * 256 * 48 * 25) * 3
    # fc6: 256 x 6 x 6 = 9216 inputs -> 4096, all three passes
    assert by_name["fc6"]["bottom_shapes"][0] == (1, 256, 6, 6)
    assert per_layer["fc6"] == 2 * (9216 * 4096) * 3 == 226_492_416 * 1.0
    assert sorted(per_layer) == ["conv1", "conv2", "conv3", "conv4", "conv5",
                                 "fc6", "fc7", "fc8"]


def test_net_totals():
    # AlexNet's forward multiply-accumulates per image, layer by layer from
    # the published table (conv1..conv5, fc6..fc8): 724.4 M in all
    _, data, recs = records("bvlc_alexnet")
    macs = {r["name"]: flops.layer_macs_per_image(r) for r in recs}
    assert sum(macs.values()) == (105_415_200 + 223_948_800 + 149_520_384
                                  + 112_140_288 + 74_760_192 + 37_748_736
                                  + 16_777_216 + 4_096_000)
    total = sum(flops.required_flops_per_image(recs, data["tops"]).values())
    assert total == 6 * sum(macs.values()) - 2 * macs["conv1"]
    assert 4.1e9 < total < 4.2e9
    _, data, recs = records("bvlc_googlenet")
    total = sum(flops.required_flops_per_image(recs, data["tops"]).values())
    macs = sum(flops.layer_macs_per_image(r) for r in recs)
    # Szegedy et al. give ~1.5 G multiply-adds for the main trunk; the two
    # auxiliary heads of the TRAIN net add ~0.09 G
    assert 1.55e9 < macs < 1.65e9
    assert 9.3e9 < total < 9.8e9


@pytest.mark.parametrize("config", ["bvlc_alexnet", "bvlc_googlenet"])
def test_parameter_count_is_the_published_one(config):
    cfg, _, recs = records(config)
    count = 0
    for r in recs:
        if r["type"] == "CONVOLUTION":
            c_in = r["bottom_shapes"][0][1] // r["group"]
            count += r["num_output"] * (c_in * r["kernel"][0] * r["kernel"][1]
                                        + 1)
        elif r["type"] == "INNERPRODUCT":
            fan_in = 1
            for d in r["bottom_shapes"][0][1:]:
                fan_in *= d
            count += r["num_output"] * (fan_in + 1)
    assert count == cfg["sizes"]["parameters"]


def test_pooling_rounds_up_like_caffe():
    _, _, recs = records("bvlc_googlenet")
    by_name = {r["name"]: r for r in recs}
    # 112 -> ceil((112 - 3) / 2) + 1 = 56 (floor would give 55)
    assert by_name["pool1/3x3_s2"]["top_shapes"][0] == (1, 64, 56, 56)
    assert by_name["pool5/7x7_s1"]["top_shapes"][0] == (1, 1024, 1, 1)
    assert by_name["loss1/ave_pool"]["top_shapes"][0] == (1, 512, 4, 4)
    # a padded stride-1 3x3 pool keeps the size: the clipped last window
    assert by_name["inception_3a/pool"]["top_shapes"][0] == (1, 192, 28, 28)


def test_train_phase_has_three_weighted_heads_test_phase_one():
    _, _, train = records("bvlc_googlenet", "TRAIN")
    _, _, test = records("bvlc_googlenet", "TEST")
    weights = lambda recs: [r["loss_weight"] for r in recs
                            if r["type"] == "SOFTMAXLOSS"]
    assert weights(train) == [0.3, 0.3, 1.0]
    assert weights(test) == [1.0]


def test_parser_reads_nested_repeated_and_commented_fields():
    node = caffe_proto.parse('''
        name: "n"  # a comment
        layers { name: "a" type: RELU bottom: "x" bottom: "y"
                 include { phase: TEST } }
        layers { name: "b" type: "InnerProduct"
                 inner_product_param { num_output: 7 } }''')
    a, b = node.all("layers")
    assert a.all("bottom") == ["x", "y"] and a.one("top") is None
    assert caffe_proto.layer_type(b) == "INNERPRODUCT"
    assert b.one("inner_product_param").one("num_output") == 7
    assert [l.one("name") for l in caffe_proto.phase_layers(node, "TRAIN")] \
        == ["b"]
    with pytest.raises(ValueError):
        caffe_proto.parse("layers { name: 1")
    with pytest.raises(NotImplementedError, match="ELTWISE"):
        caffe_proto.infer(caffe_proto.parse(
            'layers { name: "e" type: ELTWISE bottom: "x" top: "y" }'
        ).all("layers"), {"x": (1, 2)})


@pytest.mark.parametrize("config", ["bvlc_alexnet", "bvlc_googlenet"])
def test_configuration_copies_still_match_the_examples(config):
    """The benchmark runs its own copies, so that an edit to examples/ can
    not change what is measured; a drift is worth knowing about."""
    with open(os.path.join(BENCH_DIR, "configs", f"{config}.json")) as f:
        cfg = json.load(f)
    for copy, original in cfg["copied_from"].items():
        with open(os.path.join(BENCH_DIR, copy)) as a, \
                open(os.path.join(ROOT, original)) as b:
            assert a.read() == b.read(), (copy, original)
