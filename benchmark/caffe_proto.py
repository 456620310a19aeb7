"""The benchmark's own reading of a Caffe prototxt: text -> fields, phase
filter, blob shapes.

Kept apart from ``poseidon_tpu.proto`` and ``poseidon_tpu.core`` on purpose:
the required-FLOP count (flops.py) and the plain reference
(reference/caffe_net.py) must not move when the program's parser or shape
inference does. Only what the benchmark's configurations use is understood;
anything else raises by name rather than being guessed.
"""

from __future__ import annotations

import math
import re
from typing import Dict, List, Tuple

Shape = Tuple[int, ...]

_TOKEN = re.compile(r'#[^\n]*|"(?:[^"\\]|\\.)*"|[{}:]|[^\s{}:#"]+')


class Node(list):
    """One prototxt message: (key, value) pairs in file order. A value is a
    str, an int, a float or a nested Node; a repeated field repeats."""

    def all(self, key: str) -> list:
        return [v for k, v in self if k == key]

    def one(self, key: str, default=None):
        vals = self.all(key)
        return vals[-1] if vals else default


def _scalar(tok: str):
    if tok.startswith('"'):
        return tok[1:-1]
    for cast in (int, float):
        try:
            return cast(tok)
        except ValueError:
            pass
    return tok                      # an enum name or true/false


def parse(text: str) -> Node:
    toks = [t for t in _TOKEN.findall(text) if not t.startswith("#")]
    pos = 0

    def block(closing: bool) -> Node:
        nonlocal pos
        node = Node()
        while pos < len(toks):
            key = toks[pos]
            pos += 1
            if key == "}":
                if not closing:
                    raise ValueError("prototxt: unmatched '}'")
                return node
            if pos < len(toks) and toks[pos] == ":":
                pos += 1
            if pos >= len(toks):
                raise ValueError(f"prototxt: field {key!r} has no value")
            if toks[pos] == "{":
                pos += 1
                node.append((key, block(True)))
            else:
                node.append((key, _scalar(toks[pos])))
                pos += 1
        if closing:
            raise ValueError("prototxt: missing '}'")
        return node

    return block(False)


def layer_type(layer: Node) -> str:
    """CONVOLUTION, INNERPRODUCT, SOFTMAXLOSS ... for V1 enums and V2 strings
    alike ("InnerProduct", INNER_PRODUCT -> INNERPRODUCT)."""
    t = re.sub(r"[^A-Z]", "", str(layer.one("type", "")).upper())
    return {"SOFTMAXWITHLOSS": "SOFTMAXLOSS"}.get(t, t)


def phase_layers(net: Node, phase: str) -> List[Node]:
    """The net's layers that exist in ``phase`` (include/exclude rules on
    phase only, which is all the benchmark's nets use)."""
    out = []
    for layer in net.all("layers") + net.all("layer"):
        inc = [r.one("phase") for r in layer.all("include")]
        exc = [r.one("phase") for r in layer.all("exclude")]
        if (inc and phase not in inc) or phase in exc:
            continue
        out.append(layer)
    return out


def _hw(p: Node, name: str, default=None) -> Tuple[int, int]:
    both = p.one(name if name != "kernel" else "kernel_size")
    h, w = p.one(f"{name}_h", both), p.one(f"{name}_w", both)
    if h is None or w is None:
        if default is None:
            raise ValueError(f"prototxt: no {name} given")
        return default, default
    return int(h), int(w)


def conv_geometry(layer: Node) -> dict:
    p = layer.one("convolution_param", Node())
    return {"num_output": int(p.one("num_output")),
            "kernel": _hw(p, "kernel"), "stride": _hw(p, "stride", 1),
            "pad": _hw(p, "pad", 0), "group": int(p.one("group", 1)),
            "bias": str(p.one("bias_term", "true")) == "true"}


def pool_geometry(layer: Node, in_hw: Tuple[int, int]) -> dict:
    p = layer.one("pooling_param", Node())
    if str(p.one("global_pooling", "false")) == "true":
        kernel, stride, pad = tuple(in_hw), (1, 1), (0, 0)
    else:
        kernel, stride = _hw(p, "kernel"), _hw(p, "stride", 1)
        pad = _hw(p, "pad", 0)
    out = []
    for size, k, s, q in zip(in_hw, kernel, stride, pad):
        # Caffe rounds pooled sizes UP, then drops a last window that would
        # start inside the padding (pooling_layer.cpp)
        o = int(math.ceil((size + 2 * q - k) / s)) + 1
        if q and (o - 1) * s >= size + q:
            o -= 1
        out.append(o)
    return {"method": str(p.one("pool", "MAX")), "kernel": kernel,
            "stride": stride, "pad": pad, "out": tuple(out)}


def infer(layers: List[Node], inputs: Dict[str, Shape]) -> List[dict]:
    """Walk ``layers`` (already phase-filtered) from the ``inputs`` blob
    shapes and return one record per non-data layer: name, type, bottoms,
    tops, the bottom and top shapes, and the layer's geometry."""
    shapes: Dict[str, Shape] = dict(inputs)
    out = []
    for layer in layers:
        kind = layer_type(layer)
        bottoms, tops = layer.all("bottom"), layer.all("top")
        if kind == "DATA":
            continue
        bshapes = [shapes[b] for b in bottoms]
        rec = {"name": layer.one("name"), "type": kind, "bottoms": bottoms,
               "tops": tops, "bottom_shapes": bshapes}
        if kind == "CONVOLUTION":
            g = conv_geometry(layer)
            n, c, h, w = bshapes[0]
            oh = (h + 2 * g["pad"][0] - g["kernel"][0]) // g["stride"][0] + 1
            ow = (w + 2 * g["pad"][1] - g["kernel"][1]) // g["stride"][1] + 1
            rec.update(g)
            tshapes = [(n, g["num_output"], oh, ow)]
        elif kind == "INNERPRODUCT":
            p = layer.one("inner_product_param", Node())
            rec.update(num_output=int(p.one("num_output")),
                       bias=str(p.one("bias_term", "true")) == "true")
            tshapes = [(bshapes[0][0], rec["num_output"])]
        elif kind == "POOLING":
            n, c, h, w = bshapes[0]
            rec.update(pool_geometry(layer, (h, w)))
            tshapes = [(n, c) + rec["out"]]
        elif kind == "LRN":
            p = layer.one("lrn_param", Node())
            region = str(p.one("norm_region", "ACROSS_CHANNELS"))
            if region != "ACROSS_CHANNELS":
                raise NotImplementedError(f"LRN norm_region {region}")
            rec.update(local_size=int(p.one("local_size", 5)),
                       alpha=float(p.one("alpha", 1.0)),
                       beta=float(p.one("beta", 0.75)),
                       k=float(p.one("k", 1.0)))
            tshapes = [bshapes[0]]
        elif kind == "RELU":
            p = layer.one("relu_param", Node())
            rec.update(negative_slope=float(p.one("negative_slope", 0.0)))
            tshapes = [bshapes[0]]
        elif kind == "DROPOUT":
            tshapes = [bshapes[0]]
        elif kind == "CONCAT":
            p = layer.one("concat_param", Node())
            axis = int(p.one("axis", p.one("concat_dim", 1)))
            joined = list(bshapes[0])
            joined[axis] = sum(s[axis] for s in bshapes)
            rec.update(axis=axis)
            tshapes = [tuple(joined)]
        elif kind == "SOFTMAXLOSS":
            weights = layer.all("loss_weight")
            rec.update(loss_weight=float(weights[0]) if weights else 1.0)
            tshapes = [()]
        elif kind == "ACCURACY":
            tshapes = [()] * len(tops)
        else:
            raise NotImplementedError(
                f"layer {rec['name']!r}: type {layer.one('type')} is not one "
                f"the benchmark's reference understands")
        rec["top_shapes"] = tshapes
        shapes.update(zip(tops, tshapes))
        out.append(rec)
    return out


def data_layer(net: Node, phase: str) -> dict:
    """The phase's DATA layer: its tops, batch size and crop."""
    for layer in phase_layers(net, phase):
        if layer_type(layer) == "DATA":
            tp = layer.one("transform_param", Node())
            return {"tops": layer.all("top"),
                    "batch_size": int(layer.one("data_param").one(
                        "batch_size")),
                    "crop_size": int(tp.one("crop_size", 0))}
    raise ValueError(f"net has no DATA layer in phase {phase}")
