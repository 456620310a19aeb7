"""The CNN configurations' ``Net`` against the benchmark's plain reference
(benchmark/reference/caffe_net.py and benchmark/caffe_proto.py, loaded from
where they lie: one file each, no second copy): the TEST-phase forward of
BVLC AlexNet and GoogLeNet from the benchmark's own prototxts, in f32 and
under the ``--bf16`` policy, within the reference's own ``TOLERANCE``; then
one-layer geometries, forward and the input's gradient. Every case runs
under both whole-graph plans (``Net(conv_layout=)``: the default NCHW and
the channels-last plan, which PR 44 measured faster on the chip): the
reference knows neither, so it holds both to Caffe's numbers."""

import importlib.util
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from poseidon_tpu.config import policy_scope
from poseidon_tpu.core.net import Net
from poseidon_tpu.proto.messages import load_net_from_string

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _load(name, *path):
    spec = importlib.util.spec_from_file_location(
        name, os.path.join(ROOT, "benchmark", *path))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


ref = _load("caffe_net_reference", "reference", "caffe_net.py")
caffe_proto = _load("caffe_proto_reader", "caffe_proto.py")

# what `train --bf16` sets (numeric.set_perf_policy), and Caffe-parity f32
POLICIES = {"f32": dict(compute_dtype=jnp.float32, conv_s2d=False),
            "bf16": dict(compute_dtype=jnp.bfloat16, conv_s2d=True)}


def _rel_l2(got, want):
    a, b = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return float(np.linalg.norm(a - b) / max(np.linalg.norm(b), 1e-30))


def _he_scaled(params, seed):
    """He-scaled weights and small biases in place of the prototxt's
    gaussian(0.01) fillers, under which every prediction is the same
    number whatever a layer in the middle does; the classifiers a fiftieth
    of that, so that the logits stay near 1 and the loss near ln(classes)."""
    for i, (lname, lp) in enumerate(sorted(params.items())):
        key = jax.random.PRNGKey(1000 * seed + i)
        fan_in = int(np.prod(lp["w"].shape[1:]))
        head = lp["w"].shape[0] in (10, 1000) and lp["w"].ndim == 2
        lp["w"] = jax.random.normal(key, lp["w"].shape) * np.sqrt(
            2.0 / fan_in) * (0.02 if head else 1.0)
        if "b" in lp:
            lp["b"] = 0.1 * jax.random.normal(jax.random.fold_in(key, 1),
                                              lp["b"].shape)
    return params


def _seeded_params(net, seed):
    return _he_scaled(net.init(jax.random.PRNGKey(seed)), seed)


LAYOUTS = ["NCHW", "NHWC"]
# the reference's side of a case: float32 whatever the program's policy and
# plan, so computed once per net and seed (weights and inputs follow both)
_WANT = {}


def _inputs(shapes, classes, seed, pixels):
    key = jax.random.PRNGKey(seed)
    return {"data": pixels * jax.random.normal(key, shapes["data"],
                                               jnp.float32),
            "label": jax.random.randint(jax.random.fold_in(key, 1),
                                        shapes["label"], 0, classes,
                                        jnp.int32)}


def _evaluate(forward, inputs, grad_of):
    """``forward(inputs) -> (loss, predictions)`` and, with ``grad_of``,
    the loss's gradient in that input, fetched to the host."""
    out = dict(zip(("loss", "predictions"), jax.jit(forward)(inputs)))
    if grad_of:
        out["grad"] = jax.jit(jax.grad(lambda v: forward(
            {**inputs, grad_of: v})[0]))(inputs[grad_of])
    return jax.device_get(out)


def _both(text, shapes, classes, precision, layout, seed, pixels=64.0,
          grad_of=None):
    """The program's TEST-phase forward beside the reference's, on weights
    and inputs made from ``seed`` (mean-subtracted pixels of scale
    ``pixels``, as the benchmark feeds them)."""
    inputs = _inputs(shapes, classes, seed, pixels)
    node = caffe_proto.parse(text)
    records = caffe_proto.infer(caffe_proto.phase_layers(node, "TEST"),
                                shapes)
    fed = sorted({r["bottoms"][0] for r in records
                  if r["type"] == "SOFTMAXLOSS"})
    with policy_scope(**POLICIES[precision]):
        net = Net(load_net_from_string(text), "TEST", source_shapes=shapes,
                  conv_layout=layout)
        params = _seeded_params(net, seed)

        def program(x):
            out = net.apply(params, x, train=False, keep_blobs=True)
            return out.loss, {k: out.blobs[k] for k in fed}

        got = _evaluate(program, inputs, grad_of)
    if (text, seed) not in _WANT:
        weights = net.export_weights(params)

        def reference(x):
            out = ref.forward(records, weights, x)
            return out["loss"], out["predictions"]

        _WANT[text, seed] = _evaluate(reference, inputs, grad_of)
    return got, _WANT[text, seed], fed


# --------------------------------------------------------------------------- #
# the two configurations, from the benchmark's own prototxts
# --------------------------------------------------------------------------- #

def _googlenet_cut(text):
    """GoogLeNet's stem, inception_3a and a classifier on its output: the
    whole net at 224 x 1 is minutes of CPU compile for program plus
    reference in two precisions. Both LRNs, the ceil-mode pools, the
    four-branch fan-out and its CONCAT stay; the head is loss3's (7 x 7 AVE
    pool, dropout, inner product, loss) moved up. (A whole-plane AVE pool
    of these 784 values sums in the activation dtype: in bf16 on the CPU
    the predictions were 41% off; ROADMAP D18.)"""
    preamble, *blocks = re.split(r"(?m)^(?=layers \{)", text)
    # by position up to inception_3a (both DATA layers share one name: an
    # Engine's TRAIN net reads the first), by name for the head
    names = [re.search(r'name: "([^"]+)"', b)[1] for b in blocks]
    by_name = dict(zip(names, blocks))
    pool = by_name["pool5/7x7_s1"].replace(
        'bottom: "inception_5b/output"', 'bottom: "inception_3a/output"')
    # 28 x 28 here, not pool5's 7 x 7: the same 49-tap window, stepped by 7
    pool = pool.replace("kernel_size: 7", "kernel_size: 7 stride: 7")
    assert "inception_3a" in pool and "stride: 7" in pool
    return preamble + "".join(
        blocks[:names.index("inception_3a/output") + 1] + [pool]
        + [by_name[n] for n in ("pool5/drop_7x7_s1", "loss3/classifier",
                                "loss3/loss3")])


CONFIGS = {
    # AlexNet whole, at the image the CPU can afford (pool5 leaves 1 x 1)
    "alexnet": ("bvlc_alexnet", 67, 2, None),
    "googlenet": ("bvlc_googlenet", 224, 1, _googlenet_cut),
}


@pytest.fixture(scope="module")
def config_run():
    done = {}

    def run(model, precision, layout):
        if (model, precision, layout) not in done:
            folder, image, batch, cut = CONFIGS[model]
            with open(os.path.join(ROOT, "benchmark", "configs", folder,
                                   "train_val.prototxt")) as f:
                text = f.read()
            if cut:
                text = cut(text)
            shapes = {"data": (batch, 3, image, image), "label": (batch,)}
            done[model, precision, layout] = _both(
                text, shapes, 1000, precision, layout, seed=7)
        return done[model, precision, layout]

    return run


@pytest.mark.parametrize("layout", LAYOUTS)
@pytest.mark.parametrize("precision", ["f32", "bf16"])
@pytest.mark.parametrize("model", sorted(CONFIGS))
def test_config_loss_matches_reference(config_run, model, precision, layout):
    got, want, _ = config_run(model, precision, layout)
    assert np.isfinite(got["loss"]) and want["loss"] > 1.0
    assert abs(float(got["loss"]) - float(want["loss"])) <= \
        ref.TOLERANCE[precision]["loss_rel"] * abs(float(want["loss"]))


@pytest.mark.parametrize("layout", LAYOUTS)
@pytest.mark.parametrize("precision", ["f32", "bf16"])
@pytest.mark.parametrize("model", sorted(CONFIGS))
def test_config_predictions_match_reference(config_run, model, precision,
                                            layout):
    got, want, fed = config_run(model, precision, layout)
    assert fed == {"alexnet": ["fc8"],
                   "googlenet": ["loss3/classifier"]}[model]
    for k in fed:
        assert np.std(want["predictions"][k]) > 1e-3     # not a constant
        assert _rel_l2(got["predictions"][k], want["predictions"][k]) <= \
            ref.TOLERANCE[precision]["prediction_rel_l2"], k


# --------------------------------------------------------------------------- #
# one-layer geometries: data -> <layer> -> inner product -> loss
# --------------------------------------------------------------------------- #

_FILLERS = 'weight_filler { type: "xavier" } bias_filler { type: "constant" }'
LAYER_CASES = {
    "conv_group": ((2, 8, 9, 9), f"""type: CONVOLUTION
        convolution_param {{ num_output: 12 kernel_size: 3 pad: 1 stride: 2
                            group: 4 {_FILLERS} }}"""),
    # 10 -> ceil((10 + 2 - 3) / 2) + 1 = 6 rows: the last window hangs over
    # the far edge, and AVE divides by the window clipped to the padding
    "pool_max_ceil": ((2, 5, 10, 10), """type: POOLING
        pooling_param { pool: MAX kernel_size: 3 stride: 2 pad: 1 }"""),
    "pool_ave_clipped": ((2, 5, 10, 10), """type: POOLING
        pooling_param { pool: AVE kernel_size: 3 stride: 2 pad: 1 }"""),
    "pool_ave_whole_plane": ((2, 6, 7, 7), """type: POOLING
        pooling_param { pool: AVE global_pooling: true }"""),
    "lrn_across": ((2, 16, 6, 6), """type: LRN
        lrn_param { local_size: 5 alpha: 0.0001 beta: 0.75 }"""),
    "inner_product_4d": ((2, 4, 5, 5), None),
}


def _layer_net(body):
    op = "" if body is None else f"""
layers {{ name: "op" bottom: "data" top: "op" {body} }}"""
    return f"""name: "one_layer"{op}
layers {{ name: "fc" type: INNER_PRODUCT
         bottom: "{'data' if body is None else 'op'}" top: "fc"
         inner_product_param {{ num_output: 10 {_FILLERS} }} }}
layers {{ name: "loss" type: SOFTMAX_LOSS bottom: "fc" bottom: "label"
         top: "loss" }}
"""


@pytest.mark.parametrize("layout", LAYOUTS)
@pytest.mark.parametrize("precision", ["f32", "bf16"])
@pytest.mark.parametrize("case", sorted(LAYER_CASES))
def test_layer_forward_and_input_grad_match_reference(case, precision,
                                                      layout):
    shape, body = LAYER_CASES[case]
    shapes = {"data": shape, "label": shape[:1]}
    # pixels of a size at which the LRN's alpha = 1e-4 bends the curve and
    # one layer's sums stay far from bf16's range
    got, want, fed = _both(_layer_net(body), shapes, 10, precision, layout,
                           seed=3, pixels=8.0, grad_of="data")
    tol = ref.TOLERANCE[precision]
    assert fed == ["fc"]
    assert abs(float(got["loss"]) - float(want["loss"])) <= \
        tol["loss_rel"] * abs(float(want["loss"]))
    assert _rel_l2(got["predictions"]["fc"], want["predictions"]["fc"]) <= \
        tol["prediction_rel_l2"]
    assert got["grad"].shape == shape and np.linalg.norm(want["grad"]) > 0
    assert _rel_l2(got["grad"], want["grad"]) <= tol["prediction_rel_l2"]
