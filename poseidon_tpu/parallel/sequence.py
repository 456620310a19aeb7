"""Sequence/context parallelism: ring attention + all-to-all (Ulysses) style.

Long sequences are sharded across a mesh axis; two exchange strategies cover
the design space the way SFB-vs-dense covers gradients:

**Ring attention** (blockwise attention over a ppermute ring): each device
holds a contiguous (B, H, S/n, D) slice of Q, K, V. K/V blocks rotate around
the ring; every device folds each arriving block into the online-softmax
accumulator (ops/attention.py). Comm is O(S/n * D) per step over n steps and
rides ICI neighbor links; compute overlaps the rotation since XLA schedules
the next ppermute alongside the current block matmul. Causal masking is
applied at block granularity from the rotating source-shard index.

**All-to-all (Ulysses)**: one all_to_all re-shards from sequence-sharded to
head-sharded, each device runs dense attention for its H/n heads over the
FULL sequence, and a second all_to_all restores sequence sharding. Two
collective hops total — cheaper than the ring when heads >= devices and the
full-sequence scores fit in HBM.

Both are exact: tests check they match full attention on the gathered
sequence to float tolerance, under jit + shard_map on the virtual mesh.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax

from ..ops.attention import (NEG_INF, attention, block_attend,
                             finalize_block_acc, init_block_acc)


def ring_attention(q: jax.Array, k: jax.Array, v: jax.Array, axis: str,
                   *, causal: bool = False,
                   scale: Optional[float] = None) -> jax.Array:
    """Blockwise ring attention inside shard_map; q,k,v: (B, H, S_local, D)
    sequence-sharded along `axis`. Returns the local output block.

    On TPU with cleanly-tiling chunks, dispatches to the Pallas
    ring_flash_attention (per-chunk flash kernels, O(S_local) HBM); the lax
    formulation below is the portable fallback."""
    from ..ops.pallas_kernels import _interpret_default, pick_block
    if pick_block(q.shape[-2]) is not None and not _interpret_default():
        return ring_flash_attention(q, k, v, axis, causal, scale)
    n = lax.psum(1, axis)
    my = lax.axis_index(axis)
    if scale is None:
        scale = q.shape[-1] ** -0.5
    b, h, s_local, d = q.shape
    perm = [(i, (i + 1) % n) for i in range(n)]

    def step(carry, i):
        state, kb, vb = carry
        src = (my - i) % n  # which global block this k/v slice is
        if causal:
            # block-level mask: future blocks fully masked; the diagonal
            # block gets the in-block causal triangle.
            within = jnp.tril(jnp.ones((s_local, s_local), bool))
            bias = jnp.where(
                src < my, 0.0,
                jnp.where(src == my,
                          jnp.where(within, 0.0, NEG_INF),
                          NEG_INF))
            bias = jnp.broadcast_to(bias, (b, h, s_local, s_local))
        else:
            bias = None
        state = block_attend(state, q, kb, vb, scale, bias)
        kb = lax.ppermute(kb, axis, perm)
        vb = lax.ppermute(vb, axis, perm)
        return (state, kb, vb), None

    init = (init_block_acc(b, h, s_local, d), k, v)
    (state, _, _), _ = lax.scan(step, init, jnp.arange(n))
    return finalize_block_acc(state, q.dtype)


# --------------------------------------------------------------------------- #
# Ring attention through the Pallas flash kernels (O(S_local) HBM per device)
# --------------------------------------------------------------------------- #

def _chunk_mode(my, src, causal: bool):
    """+1 = K/V chunk strictly in the past (all live), 0 = diagonal chunk
    (in-chunk causal triangle), -1 = future chunk (fully masked).
    Non-causal: always +1."""
    if not causal:
        return jnp.int32(1)
    return jnp.where(src < my, jnp.int32(1),
                     jnp.where(src == my, jnp.int32(0), jnp.int32(-1)))


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def ring_flash_attention(q, k, v, axis: str, causal: bool = False,
                         scale: Optional[float] = None,
                         block: Optional[int] = None,
                         interpret: Optional[bool] = None):
    """Exact ring attention where every chunk runs through the Pallas flash
    kernels: K/V rotate via ppermute; each arriving chunk's (out, lse) merge
    by logsumexp weighting — never more than one (S_local, S_local) score
    TILE in VMEM, O(S_local) HBM. ``block`` None: each kernel takes the
    tile rule's blocks for the chunk length (``flash_blocks``), as plain
    self-attention does. The backward re-rotates K/V and runs the
    flash backward (one sweep, or two: ``_flash_bwd``) per chunk with the
    GLOBAL logsumexp; dK/dV
    accumulators travel the ring WITH their chunk, arriving home after the
    full rotation."""
    out, _ = _ring_flash_fwd_impl(q, k, v, axis, causal, scale, block,
                                  interpret)
    return out


def _ring_merge(acc, m, l, o_c, lse_c):
    """Fold one chunk's normalized output + lse into the running merge:
    final = sum_c o_c * exp(lse_c) / sum_c exp(lse_c), computed stably."""
    m_new = jnp.maximum(m, lse_c)
    alpha = jnp.exp(m - m_new)           # rescale old accumulator
    w = jnp.exp(lse_c - m_new)           # weight of the new chunk
    acc = acc * alpha[..., None] + o_c.astype(jnp.float32) * w[..., None]
    l = l * alpha + w
    return acc, m_new, l


def _ring_flash_fwd_impl(q, k, v, axis, causal, scale, block, interpret):
    from ..ops.pallas_kernels import _flash_fwd, _interpret_default
    if scale is None:
        scale = q.shape[-1] ** -0.5
    if interpret is None:
        interpret = _interpret_default()
    n = lax.psum(1, axis)
    my = lax.axis_index(axis)
    b, h, s_local, d = q.shape
    perm = [(i, (i + 1) % n) for i in range(n)]

    def step(carry, i):
        acc, m, l, kb, vb = carry
        src = (my - i) % n
        mode = _chunk_mode(my, src, causal)

        def live(_):
            return _flash_fwd(q, kb, vb, scale, causal, block, block,
                              interpret, mode=mode)

        def dead(_):
            # future chunk under causal: zero weight in the merge; skip the
            # kernel entirely (about half the ring's launches)
            return (jnp.zeros(q.shape, q.dtype),
                    jnp.full(q.shape[:-1], NEG_INF, jnp.float32))

        o_c, lse_c = lax.cond(mode >= 0, live, dead, None)
        acc, m, l = _ring_merge(acc, m, l, o_c, lse_c)
        kb = lax.ppermute(kb, axis, perm)
        vb = lax.ppermute(vb, axis, perm)
        return (acc, m, l, kb, vb), None

    acc0 = jnp.zeros((b, h, s_local, d), jnp.float32)
    m0 = jnp.full((b, h, s_local), NEG_INF, jnp.float32)
    l0 = jnp.zeros((b, h, s_local), jnp.float32)
    (acc, m, l, _, _), _ = lax.scan(step, (acc0, m0, l0, k, v),
                                    jnp.arange(n))
    lsafe = jnp.where(l == 0, 1.0, l)
    out = (acc / lsafe[..., None]).astype(q.dtype)
    lse_global = m + jnp.log(lsafe)      # log sum_c exp(lse_c)
    return out, lse_global


def _ring_flash_vjp_fwd(q, k, v, axis, causal, scale, block, interpret):
    out, lse = _ring_flash_fwd_impl(q, k, v, axis, causal, scale, block,
                                    interpret)
    return out, (q, k, v, out, lse)


def _ring_flash_vjp_bwd(axis, causal, scale, block, interpret, res, g):
    from ..ops.pallas_kernels import _flash_bwd, _interpret_default
    q, k, v, out, lse = res
    if scale is None:
        scale = q.shape[-1] ** -0.5
    if interpret is None:
        interpret = _interpret_default()
    n = lax.psum(1, axis)
    my = lax.axis_index(axis)
    perm = [(i, (i + 1) % n) for i in range(n)]
    # delta is global: rowsum over the FULL key dimension = rowsum(dO * O)
    delta = jnp.sum(g.astype(jnp.float32) * out.astype(jnp.float32), axis=-1)

    def step(carry, i):
        dq_acc, kb, vb, dkb, dvb = carry
        src = (my - i) % n
        mode = _chunk_mode(my, src, causal)

        def live(_):
            # global lse/delta make each chunk's p the GLOBAL probability
            # slice, so per-chunk dq/dk/dv sum to the exact full gradients
            return _flash_bwd(
                q, kb, vb, out, lse, g, scale, causal, block, block,
                interpret, mode=mode, delta=delta)

        def dead(_):
            return (jnp.zeros(q.shape, q.dtype), jnp.zeros(kb.shape, k.dtype),
                    jnp.zeros(vb.shape, v.dtype))

        dq_c, dk_c, dv_c = lax.cond(mode >= 0, live, dead, None)
        dq_acc = dq_acc + dq_c.astype(jnp.float32)
        # dK/dV ride the ring with their chunk; after n steps they are home
        dkb = lax.ppermute(dkb + dk_c.astype(jnp.float32), axis, perm)
        dvb = lax.ppermute(dvb + dv_c.astype(jnp.float32), axis, perm)
        kb = lax.ppermute(kb, axis, perm)
        vb = lax.ppermute(vb, axis, perm)
        return (dq_acc, kb, vb, dkb, dvb), None

    zeros = jnp.zeros(k.shape, jnp.float32)
    (dq, _, _, dk, dv), _ = lax.scan(
        step, (jnp.zeros(q.shape, jnp.float32), k, v, zeros, zeros),
        jnp.arange(n))
    return dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype)


ring_flash_attention.defvjp(_ring_flash_vjp_fwd, _ring_flash_vjp_bwd)


def ulysses_attention(q: jax.Array, k: jax.Array, v: jax.Array, axis: str,
                      *, causal: bool = False,
                      scale: Optional[float] = None) -> jax.Array:
    """All-to-all sequence parallelism inside shard_map; q,k,v:
    (B, H, S_local, D) with H divisible by the axis size. Returns the local
    sequence block of the output."""
    n = lax.psum(1, axis)
    b, h, s_local, d = q.shape
    if h % n:
        raise ValueError(f"heads ({h}) must divide by axis size ({n})")

    def seq_to_heads(x):
        # (B, H, S/n, D) -> (B, H/n, S, D). Tiled all_to_all splits the head
        # axis across devices and concatenates sequence blocks in source-
        # device order, which IS global sequence order.
        return lax.all_to_all(x, axis, split_axis=1, concat_axis=2, tiled=True)

    def heads_to_seq(x):
        # inverse: (B, H/n, S, D) -> (B, H, S/n, D), heads restored to global
        # order since device j contributed head group j.
        return lax.all_to_all(x, axis, split_axis=2, concat_axis=1, tiled=True)

    qh, kh, vh = seq_to_heads(q), seq_to_heads(k), seq_to_heads(v)
    # full-sequence attention on the local head group: Pallas flash kernel
    # when the global sequence tiles cleanly, dense fallback otherwise
    from ..ops.pallas_kernels import maybe_flash_attention
    out = maybe_flash_attention(qh, kh, vh, causal=causal, scale=scale)
    return heads_to_seq(out)
