"""Scaled dot-product attention with an online-softmax block accumulator.

The reference predates transformers (SURVEY §5: no attention op), but
long-context support is first-class in this framework: these primitives are
the single-device building blocks that ``parallel/sequence.py`` distributes
via ring ppermute or all-to-all head exchange.

The block accumulator is the flash/ring-attention recurrence: for key/value
blocks arriving one at a time, maintain (acc, m, l) with

    m'   = max(m, rowmax(S))
    p    = exp(S - m')
    l'   = l * exp(m - m') + rowsum(p)
    acc' = acc * exp(m - m') + p @ V

and finalize with acc / l. Matmul inputs run in the global compute policy
(bfloat16 feeds the MXU, which accumulates in f32 internally); softmax
statistics and the block accumulators are always float32.
"""

from __future__ import annotations

from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax import lax

from ..config import matmul_precision, policy

NEG_INF = -1e30


def attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
              causal: bool = False, scale: Optional[float] = None,
              bias: Optional[jax.Array] = None,
              window: Optional[int] = None) -> jax.Array:
    """Reference attention. q,k,v: (B, H, S, D) -> (B, H, Sq, D).
    ``window`` (causal): position t attends to s with t - window < s <= t."""
    p = policy()
    if scale is None:
        scale = q.shape[-1] ** -0.5
    s = lax.dot_general(
        q.astype(p.compute_dtype), k.astype(p.compute_dtype),
        (((3,), (3,)), ((0, 1), (0, 1))),
        precision=matmul_precision()) * scale
    s = s.astype(jnp.float32)  # softmax statistics always accumulate in f32
    if bias is not None:
        s = s + bias
    if causal:
        sq, sk = s.shape[-2], s.shape[-1]
        mask = jnp.tril(jnp.ones((sq, sk), bool), k=sk - sq)
        if window:
            mask = mask & ~jnp.tril(jnp.ones((sq, sk), bool),
                                    k=sk - sq - window)
        s = jnp.where(mask, s, NEG_INF)
    w = jax.nn.softmax(s, axis=-1)
    return lax.dot_general(
        w.astype(p.compute_dtype), v.astype(p.compute_dtype),
        (((3,), (2,)), ((0, 1), (0, 1))),
        precision=matmul_precision()).astype(q.dtype)


class BlockAcc(NamedTuple):
    acc: jax.Array  # (B, H, Sq, D) f32
    m: jax.Array    # (B, H, Sq)    f32 running rowmax
    l: jax.Array    # (B, H, Sq)    f32 running denom


def init_block_acc(batch, heads, sq, d) -> BlockAcc:
    return BlockAcc(
        acc=jnp.zeros((batch, heads, sq, d), jnp.float32),
        m=jnp.full((batch, heads, sq), NEG_INF, jnp.float32),
        l=jnp.zeros((batch, heads, sq), jnp.float32),
    )


def block_attend(state: BlockAcc, q, k, v, scale: float,
                 bias: Optional[jax.Array] = None) -> BlockAcc:
    """Fold one K/V block into the online-softmax accumulator."""
    p = policy()
    s = lax.dot_general(
        q.astype(p.compute_dtype), k.astype(p.compute_dtype),
        (((3,), (3,)), ((0, 1), (0, 1))),
        precision=matmul_precision()) * scale
    if bias is not None:
        s = s + bias
    s = s.astype(jnp.float32)
    m_new = jnp.maximum(state.m, jnp.max(s, axis=-1))
    # when an entire row is masked so far, keep exp() at zero
    alpha = jnp.exp(state.m - m_new)
    probs = jnp.exp(s - m_new[..., None])
    l_new = state.l * alpha + jnp.sum(probs, axis=-1)
    pv = lax.dot_general(
        probs.astype(p.compute_dtype), v.astype(p.compute_dtype),
        (((3,), (2,)), ((0, 1), (0, 1))),
        precision=matmul_precision()).astype(jnp.float32)
    acc_new = state.acc * alpha[..., None] + pv
    return BlockAcc(acc=acc_new, m=m_new, l=l_new)


def finalize_block_acc(state: BlockAcc, dtype) -> jax.Array:
    l = jnp.where(state.l == 0, 1.0, state.l)
    return (state.acc / l[..., None]).astype(dtype)
