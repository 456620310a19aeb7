"""Plain reference: the TEST-phase forward of a Caffe prototxt net.

A straightforward float32 ``jax.lax`` / ``jax.numpy`` reading of the layer
semantics in BVLC Caffe's ``src/caffe/layers/*.cpp``: no kernels, no layout
plan, no fused epilogues, no bf16. It shares nothing with
``poseidon_tpu.core.layers`` / ``poseidon_tpu.ops``: shapes and geometry come
from the benchmark's own prototxt reader (caffe_proto.py), weights arrive as
Caffe blobs (``{layer: [weight, bias]}``, conv weight OIHW, inner-product
weight [out, in]).

Departures from Caffe, all exact in the TEST phase: DROPOUT is the identity
(Caffe scales in TRAIN only); ACCURACY layers are skipped (they feed no
loss).
"""

from __future__ import annotations

from typing import Dict, List

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

# How far the program's TEST-phase forward may sit from this reference, by
# the precision the traffic mix runs in. The program computes convolutions
# and matrix products from bfloat16 inputs (8 bits of mantissa: 2^-9 = 0.2%
# rounding per input) and accumulates in float32; through AlexNet's 8 and
# GoogLeNet's 22 weighted layers the errors add up like a random walk, and
# what was seen on the v5e at full width (PR 22, PERF.md section 6) is well
# under 1% of the predictions' norm. The bound on the predictions is 3%: a
# forward that drops part of the arithmetic, or computes in 8-bit floats
# (2^-4 = 6% per input), fails it. The bound on the loss is 1%: the program
# hands its loss back rounded to bfloat16, and half a ULP at 6.9 is 0.23%.
# For float32 ("f32", Precision.HIGHEST in the program) only the order of
# summation differs.
TOLERANCE = {
    "bf16": {"prediction_rel_l2": 0.03, "loss_rel": 0.01},
    "f32": {"prediction_rel_l2": 1e-4, "loss_rel": 1e-5},
}


def _conv(x, blobs, rec):
    y = lax.conv_general_dilated(
        x, blobs[0], window_strides=rec["stride"],
        padding=[(p, p) for p in rec["pad"]],
        dimension_numbers=("NCHW", "OIHW", "NCHW"),
        feature_group_count=rec["group"],
        precision=lax.Precision.HIGHEST)
    if rec["bias"]:
        y = y + blobs[1].reshape(1, -1, 1, 1)
    return y


def _inner_product(x, blobs, rec):
    y = jnp.matmul(x.reshape(x.shape[0], -1), blobs[0].T,
                   precision=lax.Precision.HIGHEST)
    return y + blobs[1] if rec["bias"] else y


def _lrn(x, rec):
    """Across channels: x / (k + alpha/n * sum over n neighbours of x^2)^beta
    (lrn_layer.cpp)."""
    n = rec["local_size"]
    sq = jnp.pad(x * x, ((0, 0), (n // 2, n - 1 - n // 2), (0, 0), (0, 0)))
    window = sum(sq[:, i:i + x.shape[1]] for i in range(n))
    return x / (rec["k"] + rec["alpha"] / n * window) ** rec["beta"]


def _pool(x, rec):
    (kh, kw), (sh, sw), (ph, pw) = rec["kernel"], rec["stride"], rec["pad"]
    h, w = x.shape[2:]
    oh, ow = rec["out"]
    # Caffe's pooled size is rounded up: the last window may hang over the
    # far edge, so pad that side by whatever the window grid needs
    far = (max(0, (oh - 1) * sh + kh - h - ph),
           max(0, (ow - 1) * sw + kw - w - pw))
    pads = ((0, 0), (0, 0), (ph, far[0]), (pw, far[1]))
    dims, strides = (1, 1, kh, kw), (1, 1, sh, sw)
    if rec["method"] == "MAX":
        return lax.reduce_window(x, -jnp.inf, lax.max, dims, strides, pads)
    if rec["method"] != "AVE":
        raise NotImplementedError(f"pooling method {rec['method']}")
    total = lax.reduce_window(x, 0.0, lax.add, dims, strides, pads)
    # Caffe divides by the window clipped to the PADDED extent, computed
    # before clipping to the image (pooling_layer.cpp: pool_size)
    def extent(size, k, s, p, o):
        start = np.arange(o) * s - p
        return np.minimum(start + k, size + p) - start
    count = np.outer(extent(h, kh, sh, ph, oh), extent(w, kw, sw, pw, ow))
    return total / jnp.asarray(count, x.dtype)


def _softmax_loss(logits, label):
    """Mean over the batch of -log max(softmax(logits)[label], FLT_MIN)
    (softmax_loss_layer.cpp, [N, C] predictions)."""
    logp = jax.nn.log_softmax(logits.reshape(logits.shape[0], -1), axis=1)
    picked = jnp.take_along_axis(logp, label.reshape(-1, 1).astype(jnp.int32),
                                 axis=1)
    floor = float(np.log(np.finfo(np.float32).tiny))
    return -jnp.mean(jnp.maximum(picked, floor))


def forward(records: List[dict], weights: Dict[str, list],
            inputs: Dict[str, jax.Array]) -> dict:
    """Run ``records`` (caffe_proto.infer of the TEST-phase layers) on
    ``inputs``. Returns ``{"loss": weighted sum of the loss layers,
    "predictions": {blob: what each loss layer was fed}}``."""
    with jax.default_matmul_precision("highest"):
        blobs = {k: (v.astype(jnp.float32) if v.ndim > 1 else v)
                 for k, v in inputs.items()}
        loss = jnp.zeros((), jnp.float32)
        predictions = {}
        for rec in records:
            kind = rec["type"]
            x = blobs[rec["bottoms"][0]]
            w = [jnp.asarray(b, jnp.float32)
                 for b in weights.get(rec["name"], [])]
            if kind == "CONVOLUTION":
                y = _conv(x, w, rec)
            elif kind == "INNERPRODUCT":
                y = _inner_product(x, w, rec)
            elif kind == "RELU":
                y = jnp.where(x > 0, x, rec["negative_slope"] * x)
            elif kind == "LRN":
                y = _lrn(x, rec)
            elif kind == "POOLING":
                y = _pool(x, rec)
            elif kind == "DROPOUT":
                y = x
            elif kind == "CONCAT":
                y = jnp.concatenate([blobs[b] for b in rec["bottoms"]],
                                    axis=rec["axis"])
            elif kind == "SOFTMAXLOSS":
                y = _softmax_loss(x, blobs[rec["bottoms"][1]])
                loss = loss + rec["loss_weight"] * y
                predictions[rec["bottoms"][0]] = x
            elif kind == "ACCURACY":
                continue
            else:
                raise NotImplementedError(kind)
            blobs[rec["tops"][0]] = y
        return {"loss": loss, "predictions": predictions}
