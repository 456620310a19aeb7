"""Elementwise / neuron ops and structural ops (concat, slice, eltwise, MVN...).

Replaces the reference's neuron layers (``src/caffe/layers/{relu,sigmoid,tanh,
bnll,absval,power,threshold,dropout}_layer.*``) and structural layers with pure
functions; XLA fuses these into adjacent convs/GEMMs so they cost no extra HBM
round-trips.
"""

from __future__ import annotations

from typing import List, Optional, Sequence

import jax
import jax.numpy as jnp


def relu(x, negative_slope: float = 0.0):
    if negative_slope == 0.0:
        return jnp.maximum(x, 0)
    return jnp.where(x > 0, x, negative_slope * x)


def sigmoid(x):
    return jax.nn.sigmoid(x)


def tanh(x):
    return jnp.tanh(x)


def bnll(x):
    # y = x > 0 ? x + log(1 + exp(-x)) : log(1 + exp(x))   (bnll_layer.cpp)
    return jnp.where(x > 0, x, 0) + jnp.log1p(jnp.exp(-jnp.abs(x)))


def absval(x):
    return jnp.abs(x)


def power(x, power_: float, scale: float, shift: float):
    base = shift + scale * x
    if power_ == 1.0:
        return base
    if power_ == 2.0:       # a square is a product: no pow, exact at 0
        return base * base
    return base ** power_


def threshold(x, t: float):
    return (x > t).astype(x.dtype)


def dropout(x, ratio: float, rng: jax.Array, train: bool):
    if not train or ratio == 0.0:
        return x
    keep = 1.0 - ratio
    mask = jax.random.bernoulli(rng, keep, x.shape)
    return jnp.where(mask, x / keep, 0).astype(x.dtype)


def flatten(x):
    return x.reshape(x.shape[0], -1)


def concat(xs: Sequence[jax.Array], axis: int):
    return jnp.concatenate(xs, axis=axis)


def slice_blob(x, axis: int, slice_points: Optional[List[int]], num_out: int):
    if slice_points:
        bounds = [0] + list(slice_points) + [x.shape[axis]]
    else:
        size = x.shape[axis]
        if size % num_out != 0:
            raise ValueError(f"slice: {size} not divisible into {num_out}")
        step = size // num_out
        bounds = [i * step for i in range(num_out + 1)]
    return [jax.lax.slice_in_dim(x, bounds[i], bounds[i + 1], axis=axis)
            for i in range(len(bounds) - 1)]


def eltwise(xs: Sequence[jax.Array], operation: str, coeffs: Sequence[float]):
    if operation == "PROD":
        y = xs[0]
        for x in xs[1:]:
            y = y * x
        return y
    if operation == "SUM":
        if not coeffs:
            coeffs = [1.0] * len(xs)
        y = None
        for c, x in zip(coeffs, xs):
            term = x if c == 1.0 else c * x
            y = term if y is None else y + term
        return y
    if operation == "MAX":
        y = xs[0]
        for x in xs[1:]:
            y = jnp.maximum(y, x)
        return y
    raise ValueError(f"unknown eltwise op {operation!r}")


def mvn(x, normalize_variance: bool, across_channels: bool, eps: float = 1e-10,
        layout: str = "NCHW"):
    # mvn_layer.cpp: normalize over (C,H,W) if across_channels else (H,W),
    # per sample; eps added to sqrt(var). across_channels reduces every
    # non-batch axis, so only the spatial-only variant is layout-sensitive.
    if across_channels:
        axes = (1, 2, 3)
    else:
        axes = (1, 2) if layout == "NHWC" else (2, 3)
    mean = jnp.mean(x, axis=axes, keepdims=True)
    centered = x - mean
    if not normalize_variance:
        return centered
    var = jnp.mean(x * x, axis=axes, keepdims=True) - mean * mean
    return centered / (jnp.sqrt(jnp.maximum(var, 0)) + eps)
