"""Parameter initialization matching the reference's filler semantics.

Reference: ``include/caffe/filler.hpp`` — constant, uniform, gaussian (with
optional sparsity), positive_unitball, xavier. Xavier draws
Uniform(-s, s) with s = sqrt(3 / fan_in), fan_in = count / num.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from ..proto.messages import FillerParameter
from .blob import ParamDef


def fill(rng: jax.Array, pdef: ParamDef, dtype=jnp.float32) -> jax.Array:
    f: FillerParameter = pdef.filler
    shape = pdef.shape
    t = f.type
    if t == "constant":
        return jnp.full(shape, f.value, dtype)
    if t == "uniform":
        return jax.random.uniform(rng, shape, dtype, minval=f.min, maxval=f.max)
    if t == "gaussian":
        x = f.mean + f.std * jax.random.normal(rng, shape, dtype)
        if f.sparse >= 0:
            # Bernoulli mask with non-zero probability sparse / fan_out per
            # column, mirroring the reference's sparse gaussian filler.
            k_mask = jax.random.split(rng)[0]
            prob = min(1.0, f.sparse / max(1, shape[0]))
            mask = jax.random.bernoulli(k_mask, prob, shape)
            x = jnp.where(mask, x, 0.0)
        return x
    if t == "positive_unitball":
        x = jax.random.uniform(rng, shape, dtype)
        flat = x.reshape(shape[0], -1)
        flat = flat / jnp.sum(flat, axis=1, keepdims=True)
        return flat.reshape(shape)
    if t == "xavier":
        scale = (3.0 / pdef.fan_in) ** 0.5
        return jax.random.uniform(rng, shape, dtype, minval=-scale, maxval=scale)
    if t == "diagonal":
        # ``value`` on the diagonal of a square matrix, 0 off it (the bias of
        # a hyper-connection's mix: near the identity after the projection)
        return f.value * jnp.eye(shape[-2], shape[-1], dtype=dtype)
    if t == "log_of_uniform":
        # the log of a uniform draw in [min, max] (a decay rate's A_log)
        return jnp.log(jax.random.uniform(rng, shape, dtype, minval=f.min,
                                          maxval=f.max))
    if t == "inv_softplus_log_uniform":
        # x with softplus(x) log-uniform in [min, max] (a step size's bias)
        step = jnp.exp(jax.random.uniform(
            rng, shape, dtype, minval=jnp.log(f.min), maxval=jnp.log(f.max)))
        return step + jnp.log(-jnp.expm1(-step))
    raise ValueError(f"unknown filler type {t!r}")
