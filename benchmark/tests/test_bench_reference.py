"""The plain reference against the program's TEST-phase forward at tiny
widths, in float32 (tight: only summation order differs) and under the bf16
policy the cells run in (the reference's own tolerance)."""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import caffe_proto
from conftest import BENCH_DIR
from reference import caffe_net
from runners.caffe_train import tiny_net


# gaussian(0.01) weights leave AlexNet's predictions near zero: scale them up
# so that every layer matters; GoogLeNet's xavier weights already do
WEIGHT_SCALE = {"bvlc_alexnet": 3.0, "bvlc_googlenet": 1.0}


def both_forwards(config: str, images: int = 2):
    from poseidon_tpu.core.net import Net
    from poseidon_tpu.proto.messages import load_net_from_string
    with open(os.path.join(BENCH_DIR, "configs", f"{config}.json")) as f:
        cfg = json.load(f)
    with open(os.path.join(BENCH_DIR, cfg["net"])) as f:
        text = tiny_net(f.read(), cfg["cpu_tiny"], cfg["classes"])
    node = caffe_proto.parse(text)
    data = caffe_proto.data_layer(node, "TEST")
    crop = data["crop_size"]
    shapes = {"data": (images, 3, crop, crop), "label": (images,)}
    records = caffe_proto.infer(caffe_proto.phase_layers(node, "TEST"),
                                shapes)
    fed = sorted({r["bottoms"][0] for r in records
                  if r["type"] == "SOFTMAXLOSS"})
    key = jax.random.PRNGKey(0)
    inputs = {"data": 64.0 * jax.random.normal(key, shapes["data"]),
              "label": jax.random.randint(key, shapes["label"], 0,
                                          cfg["classes"], jnp.int32)}
    net = Net(load_net_from_string(text), "TEST", source_shapes=shapes)
    params = net.init(jax.random.PRNGKey(1))
    params = jax.tree.map(       # and biases off zero
        lambda p: p * WEIGHT_SCALE[config]
        + 0.05 * jax.random.normal(key, p.shape), params)
    out = jax.jit(lambda p, x: net.apply(p, x, train=False,
                                         keep_blobs=True))(params, inputs)
    ref = jax.jit(lambda w, x: caffe_net.forward(records, w, x))(
        net.export_weights(params), inputs)
    return out, ref, fed


def rel_l2(a, b):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.linalg.norm(a - b) / np.linalg.norm(b))


@pytest.mark.parametrize("config", ["bvlc_alexnet", "bvlc_googlenet"])
def test_reference_equals_program_in_float32(config):
    out, ref, fed = both_forwards(config)
    assert fed and float(ref["loss"]) > 0
    for name in fed:
        assert rel_l2(out.blobs[name], ref["predictions"][name]) < 1e-4, name
    assert abs(float(out.loss) - float(ref["loss"])) \
        < 1e-5 * abs(float(ref["loss"])) + 1e-5


def test_reference_holds_the_bf16_path_to_its_tolerance():
    from poseidon_tpu import config as program_config
    from poseidon_tpu.numeric import policy
    saved = {k: getattr(policy(), k) for k in ("compute_dtype", "conv_s2d")}
    program_config.set_perf_policy()
    try:
        out, ref, fed = both_forwards("bvlc_alexnet")
    finally:
        program_config.set_policy(**saved)
    tol = caffe_net.TOLERANCE["bf16"]
    for name in fed:
        err = rel_l2(out.blobs[name], ref["predictions"][name])
        # bf16 is visible (not a float32 run in disguise) and inside the bound
        assert 1e-4 < err < tol["prediction_rel_l2"], (name, err)
    assert abs(float(out.loss) - float(ref["loss"])) \
        < tol["loss_rel"] * abs(float(ref["loss"]))


def test_reference_notices_a_dropped_layer():
    """The tolerance is tight enough that leaving part of the mathematics
    out fails: drop the LRN layers from the reference's side."""
    out, ref, fed = both_forwards("bvlc_alexnet")
    import reference.caffe_net as mod
    real = mod._lrn
    mod._lrn = lambda x, rec: x
    try:
        _, without_lrn, _ = both_forwards("bvlc_alexnet")
    finally:
        mod._lrn = real
    err = rel_l2(out.blobs[fed[0]], without_lrn["predictions"][fed[0]])
    assert err > mod.TOLERANCE["bf16"]["prediction_rel_l2"]
