"""The residual STREAM of hyper-connections (arXiv:2409.19606) with the
manifold-constrained mapping of arXiv:2512.24880: ``n`` residual states of
the hidden size C a token, side by side along the lanes, X (B, S, n C) in
the activations' dtype (stream j is lanes j C .. (j + 1) C: C a multiple of
128 keeps every stream whole vregs). Per sub-layer F:

    u = vec(X) / sqrt(mean(vec(X)^2) + eps)        one statistic, no gain
    p = sigmoid(a_pre (u Phi_pre) + b_pre)                         (n)
    q = 2 sigmoid(a_post (u Phi_post) + b_post)                    (n)
    M = exp(clip(a_res mat(u Phi_res) + B_res, -clamp, clamp))     (n, n)
    ``iters`` times:  M <- M / (rowsum(M) + eps);  M <- M / (colsum(M) + eps)
    h = sum_j p_j X_j;   y = F(norm(h));   X'_i = sum_j M_ij X_j + q_i y

What the layout is for. The stream is the step's largest activation and
every function here is bound by its bytes, so each makes ONE pass over it:
``hc_map`` reads X once for the statistic and once as the (n (n + 2), n C)
projection's operand (u Phi = (X Phi) r with r the token's one statistic,
so nothing normalised is ever written); ``hc_read`` and ``hc_write`` are one
fusion each. The Sinkhorn loop is 2 ``iters`` reductions over an axis of n:
it runs with the TOKENS along the lanes, M as (n, n, T), where a row or
column sum is n - 1 adds of whole vregs (with the n x n minor it would fill
n of 128 lanes). Its result is transposed once, 24 numbers a token, to the
token-major (T, n (n + 2)) the two stream passes broadcast from along the
lanes. The mapping's arithmetic is f32 (the projection accumulates in f32
from operands in the compute type); the stream is read and written in the
activations' dtype, its sums in f32. Autodiff gives every backward: the
loop's is ``iters`` small fusions on (n, n, T).
"""

from __future__ import annotations

from typing import Dict, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from ..config import matmul_precision, policy


def sinkhorn(m: jax.Array, iters: int, eps: float) -> jax.Array:
    """m (n, n, ...) positive -> the same after ``iters`` times [divide each
    row by its sum + eps, then each column by its sum + eps]: axis 0 is the
    row index i, axis 1 the column index j, whatever follows rides along."""
    for _ in range(iters):
        m = m / (jnp.sum(m, axis=1, keepdims=True) + eps)
        m = m / (jnp.sum(m, axis=0, keepdims=True) + eps)
    return m


def hc_map(x: jax.Array, w: Dict[str, jax.Array], n: int, iters: int,
           eps: float, clamp: float) -> Tuple[jax.Array, jax.Array,
                                              jax.Array, jax.Array]:
    """x (B, S, n C) -> (coef (B, S, n (n + 2)) f32: a token's p (n), q (n)
    and M (n n, row-major: M_ij at 2 n + i n + j); and three scalars of this
    call's tokens, no gradient: the largest |rowsum(M) - 1| or
    |colsum(M) - 1|, the mean of p, the mean of q). ``w``: phi_pre, phi_post
    (n, n C), phi_res (n n, n C), b_pre, b_post (n,), b_res (n, n), a_pre,
    a_post, a_res (1,)."""
    b, s, width = x.shape
    p = policy()
    phi = jnp.concatenate([w["phi_pre"], w["phi_post"], w["phi_res"]], 0)
    flat = x.reshape(b * s, width)
    # (n (n + 2), T): the tokens along the lanes from here to the transpose
    z = lax.dot_general(phi.astype(p.compute_dtype),
                        flat.astype(p.compute_dtype),
                        (((1,), (1,)), ((), ())),
                        precision=matmul_precision(),
                        preferred_element_type=jnp.float32)
    x32 = flat.astype(jnp.float32)
    z = z * lax.rsqrt(jnp.mean(x32 * x32, axis=-1) + eps)[None]
    f32 = lambda name: w[name].astype(jnp.float32)
    pre = jax.nn.sigmoid(f32("a_pre") * z[:n] + f32("b_pre")[:, None])
    post = 2.0 * jax.nn.sigmoid(
        f32("a_post") * z[n:2 * n] + f32("b_post")[:, None])
    logits = f32("a_res") * z[2 * n:].reshape(n, n, -1) \
        + f32("b_res")[:, :, None]
    mix = sinkhorn(jnp.exp(jnp.clip(logits, -clamp, clamp)), iters, eps)
    coef = jnp.concatenate([pre, post, mix.reshape(n * n, -1)], 0)
    off = lax.stop_gradient(mix)
    err = jnp.maximum(jnp.max(jnp.abs(jnp.sum(off, 1) - 1.0)),
                      jnp.max(jnp.abs(jnp.sum(off, 0) - 1.0)))
    return (coef.T.reshape(b, s, -1), err,
            lax.stop_gradient(jnp.mean(pre)),
            lax.stop_gradient(jnp.mean(post)))


def _streams(x: jax.Array, n: int):
    c = x.shape[-1] // n
    return [x[..., j * c:(j + 1) * c].astype(jnp.float32) for j in range(n)]


def hc_start(h: jax.Array, n: int) -> jax.Array:
    """h (B, S, C) -> (B, S, n C): every stream a copy of it."""
    return jnp.concatenate([h] * n, axis=-1)


def hc_end(x: jax.Array, n: int) -> jax.Array:
    """x (B, S, n C) -> (B, S, C): the streams' sum (in f32)."""
    return sum(_streams(x, n)).astype(x.dtype)


def hc_read(x: jax.Array, coef: jax.Array, n: int) -> jax.Array:
    """h = sum_j p_j X_j: x (B, S, n C), coef from ``hc_map`` -> (B, S, C)."""
    return sum(coef[..., j:j + 1] * xj
               for j, xj in enumerate(_streams(x, n))).astype(x.dtype)


def hc_write(x: jax.Array, y: jax.Array, coef: jax.Array, n: int
             ) -> jax.Array:
    """X'_i = sum_j M_ij X_j + q_i y: x (B, S, n C), y (B, S, C), coef from
    ``hc_map`` -> (B, S, n C)."""
    streams, y32 = _streams(x, n), y.astype(jnp.float32)
    at = lambda k: coef[..., k:k + 1]
    return jnp.concatenate(
        [(sum(at(2 * n + i * n + j) * xj for j, xj in enumerate(streams))
          + at(n + i) * y32).astype(x.dtype) for i in range(n)], axis=-1)
