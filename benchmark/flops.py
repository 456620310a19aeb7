"""Required FLOPs of one training step, from the prototxt's shapes.

Required = what the model's mathematics needs, not what a compiler emitted:
2 FLOPs per multiply-accumulate of every CONVOLUTION and INNER_PRODUCT layer
of the TRAIN net, three times over — forward, weight gradient, data gradient
— less the data gradient of a layer fed by an input blob, which nobody needs.
Recomputation, layout copies, pooling, LRN, bias and loss count as zero: they
are overhead against the matrix units' peak, which is what MFU measures.
"""

from __future__ import annotations

import math
from typing import Dict, Iterable, List


def layer_macs_per_image(rec: dict) -> int:
    """Multiply-accumulates of one forward pass of ``rec`` (a record of
    caffe_proto.infer) for ONE image; 0 for layers without a matrix product."""
    if rec["type"] == "CONVOLUTION":
        _, c_in, _, _ = rec["bottom_shapes"][0]
        _, c_out, oh, ow = rec["top_shapes"][0]
        kh, kw = rec["kernel"]
        return oh * ow * c_out * (c_in // rec["group"]) * kh * kw
    if rec["type"] == "INNERPRODUCT":
        return math.prod(rec["bottom_shapes"][0][1:]) * rec["num_output"]
    return 0


def required_flops_per_image(records: List[dict],
                             input_blobs: Iterable[str]) -> Dict[str, float]:
    """{layer: FLOPs per image per training step}; the net's is the sum."""
    inputs = set(input_blobs)
    out: Dict[str, float] = {}
    for rec in records:
        macs = layer_macs_per_image(rec)
        if not macs:
            continue
        passes = 2 if set(rec["bottoms"]) & inputs else 3
        out[rec["name"]] = 2.0 * macs * passes
    return out
