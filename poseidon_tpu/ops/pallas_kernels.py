"""Pallas TPU kernels for the hot ops.

``flash_attention``: blockwise attention entirely in VMEM — never
materializes the (S, S) score matrix in HBM. Grid is (batch*heads,
query-blocks); each program streams key/value blocks through the
online-softmax recurrence (the same math as ops/attention.py's BlockAcc, here
per 128-row tile). The backward pass is likewise Pallas and O(S) in HBM: the
dq and dk/dv kernels below recompute scores blockwise from the saved
(out, logsumexp) residuals, wired up via ``defvjp``.

``lrn_fused`` / ``lrn_fused_bwd``: cross-channel LRN in one VMEM pass per
(H*W)-tile, forward and analytic backward, in both layouts. The default
path on TPU (``lrn_route``; ``POSEIDON_PALLAS_LRN=0`` opts back out) with
the XLA formulation on the CPU test mesh and beyond the VMEM tiling cap.

Kernels run in interpret mode on the CPU test mesh so it exercises the same
code path; any backend other than tpu/cpu is refused (``_interpret_default``).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..config import matmul_precision
from .attention import NEG_INF


# The A/B switches the ops read at TRACE time (ROADMAP D3): each changes the
# lowered program without changing any argument, so whatever keys a compiled
# program by its inputs (the engine's AOT step store) must fold them in.
LOWERING_ENV = ("POSEIDON_POOL_BWD", "POSEIDON_PALLAS_LRN",
                "POSEIDON_LRN_BWD", "POSEIDON_FORCE_PALLAS")


def _interpret_default() -> bool:
    """Compile the Mosaic kernels on TPU, interpret them on the CPU test
    mesh — and refuse anything else: a backend that is neither (a plug-in
    platform, a GPU) must not silently run every kernel through the
    interpreter and report the result as a device run."""
    # POSEIDON_FORCE_PALLAS=1 compiles the real Mosaic kernels even when
    # the RUNTIME backend is not TPU — the AOT-for-TPU-target path
    # (scripts/aot_tpu_check.py), where default_backend() is cpu but the
    # compile target is the chip
    import os
    if os.environ.get("POSEIDON_FORCE_PALLAS") == "1":
        return False
    backend = jax.default_backend()
    if backend not in ("tpu", "cpu"):
        raise RuntimeError(
            f"Pallas TPU kernels on backend {backend!r}: only 'tpu' "
            f"(compiled) and 'cpu' (interpreted, tests) are supported")
    return backend == "cpu"


@functools.lru_cache(maxsize=None)
def _log_route_once(msg: str) -> None:
    """Trace-time routing decisions of ops that no Net constructs (the LM
    attention path), logged once per distinct decision."""
    from ..runtime.metrics import log
    log(msg)


def _cdiv(a: int, b: int) -> int:
    return -(-a // b)


# --------------------------------------------------------------------------- #
# Flash attention
# --------------------------------------------------------------------------- #

def _causal_mask(s, qi, kj, block_q, block_k, mode=None):
    """Self-attention: mask by absolute tile position. Chunked (ring) mode:
    ``mode`` is a traced scalar describing how the K/V chunk aligns with the
    Q rows' chunk — +1 chunk strictly past (all live), 0 diagonal (in-chunk
    triangle), -1 future (all masked)."""
    rows = qi * block_q + lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 0)
    cols = kj * block_k + lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 1)
    if mode is None:
        return jnp.where(rows >= cols, s, NEG_INF)
    live = (mode > 0) | ((mode == 0) & (rows >= cols))
    return jnp.where(live, s, NEG_INF)


def _flash_fwd_kernel(*refs, scale: float, causal: bool, block_q: int,
                      block_k: int, n_kb: int, chunk_mode: bool):
    """Grid (bh, q_blocks, k_blocks); only one (block_q, d) Q tile and one
    (block_k, d) K/V tile are VMEM-resident at a time. The online-softmax
    state persists in scratch across the innermost (k-block) grid dimension.
    Also emits the per-row logsumexp, which the O(S)-memory backward kernels
    consume (flash attention paper's L = m + log l).

    ``chunk_mode`` (ring attention): a leading SMEM scalar describes the
    chunk alignment for causal masking (see _causal_mask)."""
    if chunk_mode:
        mode_ref, q_ref, k_ref, v_ref, o_ref, lse_ref, \
            acc_ref, m_ref, l_ref = refs
        mode = mode_ref[0]
    else:
        q_ref, k_ref, v_ref, o_ref, lse_ref, acc_ref, m_ref, l_ref = refs
        mode = None
    qi = pl.program_id(1)
    kj = pl.program_id(2)

    @pl.when(kj == 0)
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)
        m_ref[:] = jnp.full_like(m_ref, NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)

    # causal self-attention: blocks entirely above the diagonal contribute
    # nothing (static skip); chunked liveness is dynamic, handled by the mask
    block_live = True if (not causal or chunk_mode) else \
        (kj * block_k <= qi * block_q + block_q - 1)

    @pl.when(block_live)
    def _update():
        q = q_ref[0].astype(jnp.float32)         # (block_q, d)
        k_blk = k_ref[0].astype(jnp.float32)     # (block_k, d)
        v_blk = v_ref[0].astype(jnp.float32)
        s = jnp.dot(q, k_blk.T, preferred_element_type=jnp.float32,
            precision=matmul_precision()) * scale
        if causal:
            s = _causal_mask(s, qi, kj, block_q, block_k, mode)
        m_prev = m_ref[:, 0]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new[:, None])
        l_ref[:, 0] = l_ref[:, 0] * alpha + jnp.sum(p, axis=-1)
        acc_ref[:] = acc_ref[:] * alpha[:, None] + jnp.dot(
            p, v_blk, preferred_element_type=jnp.float32,
            precision=matmul_precision())
        m_ref[:, 0] = m_new

    @pl.when(kj == n_kb - 1)
    def _finalize():
        l = l_ref[:, 0]
        lsafe = jnp.where(l == 0, 1.0, l)
        o_ref[0] = (acc_ref[:] / lsafe[:, None]).astype(o_ref.dtype)
        # lse layout is (bh, s, 1): a (block_q, 1) tile keeps the minor dim
        # equal to the full array dim, which Mosaic's tiling rules require
        # for block_q < 128 (the (1, 1, block_q) layout only lowered with
        # full-length 128 tiles)
        lse_ref[0] = (m_ref[:, 0] + jnp.log(lsafe))[:, None]


def _check_blocks(s, block_q, block_k):
    block_q = min(block_q, s)
    block_k = min(block_k, s)
    if s % block_q or s % block_k:
        raise ValueError(f"seq len {s} must divide by blocks "
                         f"({block_q}, {block_k})")
    return block_q, block_k


def _flash_fwd(q, k, v, scale: float, causal: bool, block_q: int,
               block_k: int, interpret: bool, mode=None):
    """mode (traced int32 scalar) selects chunked causal masking for ring
    attention; None = plain self-attention."""
    b, h, s, d = q.shape
    bh = b * h
    q3 = q.reshape(bh, s, d)
    k3 = k.reshape(bh, s, d)
    v3 = v.reshape(bh, s, d)
    block_q, block_k = _check_blocks(s, block_q, block_k)
    n_kb = s // block_k
    grid = (bh, s // block_q, n_kb)
    chunk = mode is not None
    in_specs = [
        pl.BlockSpec((1, block_q, d), lambda i, j, kk: (i, j, 0),
                     memory_space=pltpu.VMEM),
        pl.BlockSpec((1, block_k, d), lambda i, j, kk: (i, kk, 0),
                     memory_space=pltpu.VMEM),
        pl.BlockSpec((1, block_k, d), lambda i, j, kk: (i, kk, 0),
                     memory_space=pltpu.VMEM),
    ]
    args = [q3, k3, v3]
    if chunk:
        in_specs.insert(0, pl.BlockSpec(memory_space=pltpu.SMEM))
        args.insert(0, jnp.asarray(mode, jnp.int32).reshape(1))
    out, lse = pl.pallas_call(
        functools.partial(_flash_fwd_kernel, scale=scale, causal=causal,
                          block_q=block_q, block_k=block_k, n_kb=n_kb,
                          chunk_mode=chunk),
        name="flash_fwd",
        out_shape=(jax.ShapeDtypeStruct((bh, s, d), q.dtype),
                   jax.ShapeDtypeStruct((bh, s, 1), jnp.float32)),
        grid=grid,
        in_specs=in_specs,
        out_specs=(
            pl.BlockSpec((1, block_q, d), lambda i, j, kk: (i, j, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, block_q, 1), lambda i, j, kk: (i, j, 0),
                         memory_space=pltpu.VMEM),
        ),
        scratch_shapes=[
            pltpu.VMEM((block_q, d), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(*args)
    return out.reshape(b, h, s, d), lse.reshape(b, h, s)


def _row_ref(ref):
    """(block_q,) row statistics from a (1, block_q, 1) lse/delta tile."""
    return ref[0, :, 0]


# --------------------------------------------------------------------------- #
# Flash attention backward: O(S) memory, two sweeps (flash attention paper)
# --------------------------------------------------------------------------- #

def _flash_dq_kernel(*refs, scale: float, causal: bool, block_q: int,
                     block_k: int, n_kb: int, chunk_mode: bool):
    """Grid (bh, q_blocks, k_blocks): accumulate dQ for one Q tile across all
    K/V tiles. p is recomputed from Q,K and the saved logsumexp — the score
    matrix never exists outside one VMEM tile."""
    if chunk_mode:
        mode_ref, q_ref, k_ref, v_ref, g_ref, lse_ref, delta_ref, \
            dq_ref, dq_acc = refs
        mode = mode_ref[0]
    else:
        q_ref, k_ref, v_ref, g_ref, lse_ref, delta_ref, dq_ref, dq_acc = refs
        mode = None
    qi = pl.program_id(1)
    kj = pl.program_id(2)

    @pl.when(kj == 0)
    def _init():
        dq_acc[:] = jnp.zeros_like(dq_acc)

    block_live = True if (not causal or chunk_mode) else \
        (kj * block_k <= qi * block_q + block_q - 1)

    @pl.when(block_live)
    def _update():
        q = q_ref[0].astype(jnp.float32)
        k_blk = k_ref[0].astype(jnp.float32)
        v_blk = v_ref[0].astype(jnp.float32)
        g = g_ref[0].astype(jnp.float32)
        lse = _row_ref(lse_ref)                   # (block_q,)
        delta = _row_ref(delta_ref)               # (block_q,)
        s = jnp.dot(q, k_blk.T, preferred_element_type=jnp.float32,
            precision=matmul_precision()) * scale
        if causal:
            s = _causal_mask(s, qi, kj, block_q, block_k, mode)
        p = jnp.exp(s - lse[:, None])             # masked entries -> 0
        dp = jnp.dot(g, v_blk.T, preferred_element_type=jnp.float32,
            precision=matmul_precision())
        ds = p * (dp - delta[:, None]) * scale
        dq_acc[:] = dq_acc[:] + jnp.dot(
            ds, k_blk, preferred_element_type=jnp.float32,
            precision=matmul_precision())

    @pl.when(kj == n_kb - 1)
    def _finalize():
        dq_ref[0] = dq_acc[:].astype(dq_ref.dtype)


def _flash_dkv_kernel(*refs, scale: float, causal: bool, block_q: int,
                      block_k: int, n_qb: int, chunk_mode: bool):
    """Grid (bh, k_blocks, q_blocks): accumulate dK and dV for one K/V tile
    across all Q tiles."""
    if chunk_mode:
        mode_ref, q_ref, k_ref, v_ref, g_ref, lse_ref, delta_ref, \
            dk_ref, dv_ref, dk_acc, dv_acc = refs
        mode = mode_ref[0]
    else:
        q_ref, k_ref, v_ref, g_ref, lse_ref, delta_ref, \
            dk_ref, dv_ref, dk_acc, dv_acc = refs
        mode = None
    kj = pl.program_id(1)
    qi = pl.program_id(2)

    @pl.when(qi == 0)
    def _init():
        dk_acc[:] = jnp.zeros_like(dk_acc)
        dv_acc[:] = jnp.zeros_like(dv_acc)

    block_live = True if (not causal or chunk_mode) else \
        (qi * block_q + block_q - 1 >= kj * block_k)

    @pl.when(block_live)
    def _update():
        q = q_ref[0].astype(jnp.float32)
        k_blk = k_ref[0].astype(jnp.float32)
        v_blk = v_ref[0].astype(jnp.float32)
        g = g_ref[0].astype(jnp.float32)
        lse = _row_ref(lse_ref)
        delta = _row_ref(delta_ref)
        s = jnp.dot(q, k_blk.T, preferred_element_type=jnp.float32,
            precision=matmul_precision()) * scale
        if causal:
            s = _causal_mask(s, qi, kj, block_q, block_k, mode)
        p = jnp.exp(s - lse[:, None])             # (block_q, block_k)
        dv_acc[:] = dv_acc[:] + jnp.dot(
            p.T, g, preferred_element_type=jnp.float32,
            precision=matmul_precision())
        dp = jnp.dot(g, v_blk.T, preferred_element_type=jnp.float32,
            precision=matmul_precision())
        ds = p * (dp - delta[:, None]) * scale
        dk_acc[:] = dk_acc[:] + jnp.dot(
            ds.T, q, preferred_element_type=jnp.float32,
            precision=matmul_precision())

    @pl.when(qi == n_qb - 1)
    def _finalize():
        dk_ref[0] = dk_acc[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[:].astype(dv_ref.dtype)


def _flash_bwd(q, k, v, out, lse, g, scale: float, causal: bool,
               block_q: int, block_k: int, interpret: bool, mode=None,
               delta=None):
    """mode: see _flash_fwd. ``delta`` (rowsum(dO*O), global) may be passed
    in by the ring backward, whose O is the merged global output."""
    b, h, s, d = q.shape
    bh = b * h
    block_q, block_k = _check_blocks(s, block_q, block_k)
    n_qb, n_kb = s // block_q, s // block_k
    if delta is None:
        # delta_i = rowsum(dO * O): one O(S*D) elementwise pass, XLA-fused
        delta = jnp.sum(g.astype(jnp.float32) * out.astype(jnp.float32),
                        axis=-1)                   # (b, h, s)
    r3 = lambda x: x.reshape(bh, s, x.shape[-1])
    q3, k3, v3, g3 = r3(q), r3(k), r3(v), r3(g)
    lse3 = lse.reshape(bh, s, 1)
    delta3 = delta.reshape(bh, s, 1)
    chunk = mode is not None
    mode_arg = [jnp.asarray(mode, jnp.int32).reshape(1)] if chunk else []
    smem = [pl.BlockSpec(memory_space=pltpu.SMEM)] if chunk else []

    qspec = pl.BlockSpec((1, block_q, d), lambda i, j, kk: (i, j, 0),
                         memory_space=pltpu.VMEM)
    kspec = pl.BlockSpec((1, block_k, d), lambda i, j, kk: (i, kk, 0),
                         memory_space=pltpu.VMEM)
    rowq = pl.BlockSpec((1, block_q, 1), lambda i, j, kk: (i, j, 0),
                        memory_space=pltpu.VMEM)

    dq = pl.pallas_call(
        functools.partial(_flash_dq_kernel, scale=scale, causal=causal,
                          block_q=block_q, block_k=block_k, n_kb=n_kb,
                          chunk_mode=chunk),
        name="flash_bwd_dq",
        out_shape=jax.ShapeDtypeStruct((bh, s, d), q.dtype),
        grid=(bh, n_qb, n_kb),
        in_specs=smem + [qspec, kspec, kspec, qspec, rowq, rowq],
        out_specs=qspec,
        scratch_shapes=[pltpu.VMEM((block_q, d), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(*mode_arg, q3, k3, v3, g3, lse3, delta3)

    # swapped grid: (bh, k_blocks, q_blocks)
    qspec_t = pl.BlockSpec((1, block_q, d), lambda i, j, kk: (i, kk, 0),
                           memory_space=pltpu.VMEM)
    kspec_t = pl.BlockSpec((1, block_k, d), lambda i, j, kk: (i, j, 0),
                           memory_space=pltpu.VMEM)
    rowq_t = pl.BlockSpec((1, block_q, 1), lambda i, j, kk: (i, kk, 0),
                          memory_space=pltpu.VMEM)
    dk, dv = pl.pallas_call(
        functools.partial(_flash_dkv_kernel, scale=scale, causal=causal,
                          block_q=block_q, block_k=block_k, n_qb=n_qb,
                          chunk_mode=chunk),
        name="flash_bwd_dkv",
        out_shape=(jax.ShapeDtypeStruct((bh, s, d), k.dtype),
                   jax.ShapeDtypeStruct((bh, s, d), v.dtype)),
        grid=(bh, n_kb, n_qb),
        in_specs=smem + [qspec_t, kspec_t, kspec_t, qspec_t, rowq_t, rowq_t],
        out_specs=(kspec_t, kspec_t),
        scratch_shapes=[pltpu.VMEM((block_k, d), jnp.float32),
                        pltpu.VMEM((block_k, d), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(*mode_arg, q3, k3, v3, g3, lse3, delta3)

    rs = lambda x: x.reshape(b, h, s, d)
    return rs(dq), rs(dk), rs(dv)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def flash_attention(q, k, v, causal: bool = False,
                    scale: Optional[float] = None, block_q: int = 128,
                    block_k: int = 128, interpret: Optional[bool] = None):
    """Pallas blockwise attention; (B, H, S, D) -> (B, H, S, D)."""
    if scale is None:
        scale = q.shape[-1] ** -0.5
    if interpret is None:
        interpret = _interpret_default()
    out, _ = _flash_fwd(q, k, v, scale, causal, block_q, block_k, interpret)
    return out


def _flash_vjp_fwd(q, k, v, causal, scale, block_q, block_k, interpret):
    if scale is None:
        scale = q.shape[-1] ** -0.5
    if interpret is None:
        interpret = _interpret_default()
    out, lse = _flash_fwd(q, k, v, scale, causal, block_q, block_k, interpret)
    return out, (q, k, v, out, lse)


def _flash_vjp_bwd(causal, scale, block_q, block_k, interpret, res, g):
    q, k, v, out, lse = res
    if scale is None:
        scale = q.shape[-1] ** -0.5
    if interpret is None:
        interpret = _interpret_default()
    return _flash_bwd(q, k, v, out, lse, g, scale, causal, block_q, block_k,
                      interpret)


flash_attention.defvjp(_flash_vjp_fwd, _flash_vjp_bwd)


def pick_block(s: int) -> Optional[int]:
    """Largest clean tile height for a sequence length, MXU/VPU-aligned.

    Mosaic only needs the block's second-minor dim to be a multiple of the
    8-row f32 sublane tile, so non-power-of-two sequence lengths that a
    128/64/32 block cannot divide (s=48, s=136, ...) still tile with a
    smaller aligned block — falling back to None there routed perfectly
    kernelable shapes onto the dense O(S^2) op."""
    return next((bs for bs in (128, 64, 32, 16, 8) if s % bs == 0), None)


def attention_route(s: int, sk: Optional[int] = None):
    """``(arm, note)`` for one attention geometry — THE routing decision:
    ``maybe_flash_attention`` takes it at trace time and ``Net`` logs it
    per ATTENTION layer at construction. ``"pallas_flash"`` when the
    sequence tiles cleanly (divisible by a 128/64/32-row block,
    self-attention lengths); ``"dense"`` on the CPU test mesh (the kernel
    would run in interpret-mode emulation — strictly slower than the dense
    op it replaces) and for shapes the kernel does not tile."""
    block = pick_block(s)
    if _interpret_default():
        return "dense", "cpu backend"
    if sk is not None and sk != s:
        return "dense", "cross-attention lengths"
    if block is None:
        return "dense", f"no aligned block divides S={s}"
    return "pallas_flash", f"block {block}"


def maybe_flash_attention(q, k, v, causal: bool = False,
                          scale: Optional[float] = None) -> jax.Array:
    """Attention through :func:`attention_route`'s arm — and say which,
    once per shape. The training entry point for models/transformer.py
    (both blocks) and the Ulysses head-parallel path."""
    from .attention import attention
    s = q.shape[-2]
    arm, note = attention_route(s, k.shape[-2])
    where = f"[kernel_route] attention S={s} D={q.shape[-1]}"
    if arm == "pallas_flash":
        block = pick_block(s)
        _log_route_once(f"{where}: pallas flash, block {block}")
        return flash_attention(q, k, v, causal, scale, block, block)
    _log_route_once(f"{where}: dense ({note})")
    return attention(q, k, v, causal=causal, scale=scale)


# --------------------------------------------------------------------------- #
# Fused cross-channel LRN
# --------------------------------------------------------------------------- #

def _lrn_kernel(x_ref, o_ref, *, local_size: int, alpha: float, beta: float,
                k: float, channels: int, channel_axis: int = 0):
    """One LRN tile. ``channel_axis`` selects the block orientation:
    0 = (C, T) channels x spatial tile (NCHW), 1 = (T, C) spatial tile x
    channels (NHWC — the channel window then runs over the MINOR axis,
    matching the net-level channels-last plan so the kernel needs no
    operand layout change at its custom-call boundary)."""
    x = x_ref[0].astype(jnp.float32)
    pre = (local_size - 1) // 2
    sq = x * x
    pads = [(0, 0), (0, 0)]
    pads[channel_axis] = (pre, local_size - pre - 1)
    padded = jnp.pad(sq, pads)
    windowed = jnp.zeros_like(sq)
    for dc in range(local_size):
        windowed = windowed + lax.slice_in_dim(padded, dc, dc + channels,
                                               axis=channel_axis)
    scale = k + (alpha / local_size) * windowed
    o_ref[0] = (x * scale ** (-beta)).astype(o_ref.dtype)


class LRNTileError(ValueError):
    """No VMEM-legal spatial tiling exists for this channel count."""


def _lrn_tile(hw: int, want: int, channels: int) -> tuple:
    """(tile, padded_hw): a lane-legal spatial tiling. Mosaic requires the
    block's minor dim to be a multiple of 128 OR the full array dim, and
    one-tile-per-image VMEM-OOMs at GoogLeNet's norm2 scale (192 x 3136
    bf16 + temps = 24.6 MB vs the 16 MB scoped limit — caught by the AOT
    Mosaic gate, evidence/aot_tpu). Preference order, by the cost model:

    1. the FULL spatial extent when its working set fits VMEM (always
       layout-legal, zero pad/copy overhead — padding to lane multiples
       measured +32% est. cycles on AlexNet's norms);
    2. otherwise a 128-multiple tile with the extent padded up and the
       pad sliced off after. LRN windows run over CHANNELS only, so zero
       spatial padding is inert (scale = k > 0).

    Raises :class:`LRNTileError` when the VMEM budget caps the tile below
    128 lanes (channels > ~2560): emitting a 128-wide block anyway would
    exceed the scoped VMEM limit at Mosaic compile time, so callers must
    fall back to the XLA formulation instead (``lrn_fused`` does)."""
    # ~8 f32 temps of (C, tile) live on the kernel stack (x, g, sq,
    # padded, windowed, scale, r, out); stay under ~10 MB of the 16 MB
    # scoped VMEM
    budget = 10 * 2 ** 20
    if channels * hw * 4 * 8 <= budget:
        return hw, hw
    cap = budget // (channels * 4 * 8)
    if cap < 128:
        raise LRNTileError(
            f"fused LRN: {channels} channels leave a VMEM tile budget of "
            f"{cap} < 128 lanes (~8 f32 temps of (C, tile) must fit "
            f"{budget >> 20} MB); use the XLA formulation for channel "
            f"counts above ~{budget // (4 * 8 * 128)}")
    want = max(128, (min(want, cap) // 128) * 128)
    padded = -(-hw // want) * want
    return want, padded


def lrn_tile_feasible(hw: int, channels: int) -> bool:
    """Whether a VMEM-legal tiling exists (see ``_lrn_tile``)."""
    try:
        _lrn_tile(hw, 512, channels)
        return True
    except LRNTileError:
        return False


def _lrn_shape(x, layout: str):
    """(n, c, hw, reshape-to-3d, restore-from-3d) for either layout; the
    3-D view keeps channels on the axis the kernel's block expects (major
    for NCHW, MINOR for NHWC — channels-last stays channels-last through
    the custom-call boundary, no operand relayout)."""
    if layout == "NHWC":
        n, h, w, c = x.shape
        return (n, c, h * w,
                lambda a: a.reshape(n, h * w, c),
                lambda a: a.reshape(n, h, w, c))
    n, c, h, w = x.shape
    return (n, c, h * w,
            lambda a: a.reshape(n, c, h * w),
            lambda a: a.reshape(n, c, h, w))


def _lrn_specs(c: int, tile: int, layout: str):
    if layout == "NHWC":
        return pl.BlockSpec((1, tile, c), lambda i, j: (i, j, 0),
                            memory_space=pltpu.VMEM), 1
    return pl.BlockSpec((1, c, tile), lambda i, j: (i, 0, j),
                        memory_space=pltpu.VMEM), 0


def _lrn_pad3(x2, hw: int, hw_p: int, layout: str):
    if hw_p == hw:
        return x2
    pad = [(0, 0)] * 3
    pad[1 if layout == "NHWC" else 2] = (0, hw_p - hw)
    return jnp.pad(x2, pad)


def _lrn_crop3(out, n: int, c: int, hw: int, layout: str):
    if layout == "NHWC":
        return lax.slice(out, (0, 0, 0), (n, hw, c))
    return lax.slice(out, (0, 0, 0), (n, c, hw))


def _lrn_fused_fwd_impl(x, local_size: int, alpha: float, beta: float,
                        k: float, tile: int, interpret: Optional[bool],
                        layout: str = "NCHW"):
    if interpret is None:
        interpret = _interpret_default()
    n, c, hw, to3, from3 = _lrn_shape(x, layout)
    tile, hw_p = _lrn_tile(hw, tile, c)
    x2 = _lrn_pad3(to3(x), hw, hw_p, layout)
    spec, caxis = _lrn_specs(c, tile, layout)
    out_shape = ((n, hw_p, c) if layout == "NHWC" else (n, c, hw_p))
    out = pl.pallas_call(
        functools.partial(_lrn_kernel, local_size=local_size, alpha=alpha,
                          beta=beta, k=k, channels=c, channel_axis=caxis),
        name="lrn_fwd",
        out_shape=jax.ShapeDtypeStruct(out_shape, x.dtype),
        grid=(n, hw_p // tile),
        in_specs=[spec],
        out_specs=spec,
        interpret=interpret,
    )(x2)
    return from3(_lrn_crop3(out, n, c, hw, layout))


@functools.partial(jax.custom_vjp, nondiff_argnums=(1, 2, 3, 4, 5, 6, 7))
def _lrn_fused_cvjp(x, local_size: int, alpha: float, beta: float,
                    k: float, tile: int, interpret: Optional[bool],
                    layout: str):
    return _lrn_fused_fwd_impl(x, local_size, alpha, beta, k, tile,
                               interpret, layout)


def lrn_fused(x, local_size: int, alpha: float, beta: float, k: float = 1.0,
              tile: int = 512, interpret: Optional[bool] = None,
              layout: str = "NCHW"):
    """Fused LRN: one VMEM pass per spatial tile, forward and analytic
    backward. ``layout`` selects the block orientation — x is (N, C, H, W)
    under NCHW, (N, H, W, C) under NHWC (the net-level channels-last plan
    feeds this directly; no layout round-trip at the custom-call
    boundary).

    Channel counts whose VMEM working set admits no 128-lane tile
    (> ~2560 channels, see ``_lrn_tile``) fall back to the XLA
    formulation — same numbers, no Mosaic scoped-VMEM blowup."""
    n, c, hw, _, _ = _lrn_shape(x, layout)
    if not lrn_tile_feasible(hw, c):
        from .nn import lrn_across_channels
        return lrn_across_channels(x, local_size, alpha, beta, k, layout)
    return _lrn_fused_cvjp(x, local_size, alpha, beta, k, tile, interpret,
                           layout)


def _lrn_bwd_kernel(x_ref, g_ref, o_ref, *, local_size: int, alpha: float,
                    beta: float, k: float, channels: int,
                    channel_axis: int = 0):
    """One-pass LRN backward (the analytic Caffe gradient,
    lrn_layer.cpp CrossChannelBackward):

        dx_i = g_i * scale_i^-beta
               - (2*alpha*beta/n) * x_i * sum_{j: i in win(j)} g_j*y_j/scale_j

    where g_j*y_j/scale_j = g_j * x_j * scale_j^(-beta-1). The transpose
    window is the forward window mirrored (pad (post, pre) instead of
    (pre, post)). Everything stays in one VMEM tile — the round-5 cycle
    attribution put the recompute-through-XLA backward at ~2/3 of the LRN
    layers' 29%-of-step cost (evidence/aot_tpu/layer_cycles.json).
    ``channel_axis``: see ``_lrn_kernel``."""
    x = x_ref[0].astype(jnp.float32)
    g = g_ref[0].astype(jnp.float32)
    pre = (local_size - 1) // 2
    post = local_size - pre - 1
    sq = x * x
    fwd_pads = [(0, 0), (0, 0)]
    fwd_pads[channel_axis] = (pre, post)
    padded = jnp.pad(sq, fwd_pads)
    windowed = jnp.zeros_like(sq)
    for dc in range(local_size):
        windowed = windowed + lax.slice_in_dim(padded, dc, dc + channels,
                                               axis=channel_axis)
    scale = k + (alpha / local_size) * windowed
    r = g * x * scale ** (-beta - 1.0)
    bwd_pads = [(0, 0), (0, 0)]
    bwd_pads[channel_axis] = (post, pre)
    rp = jnp.pad(r, bwd_pads)
    rsum = jnp.zeros_like(r)
    for dc in range(local_size):
        rsum = rsum + lax.slice_in_dim(rp, dc, dc + channels,
                                       axis=channel_axis)
    dx = g * scale ** (-beta) - (2.0 * alpha * beta / local_size) * x * rsum
    o_ref[0] = dx.astype(o_ref.dtype)


def lrn_fused_bwd(x, g, local_size: int, alpha: float, beta: float,
                  k: float = 1.0, tile: int = 512,
                  interpret: Optional[bool] = None, layout: str = "NCHW"):
    """Fused LRN backward: dx from (x, g) in one VMEM pass per tile."""
    if interpret is None:
        interpret = _interpret_default()
    n, c, hw, to3, from3 = _lrn_shape(x, layout)
    tile, hw_p = _lrn_tile(hw, tile, c)
    x2 = _lrn_pad3(to3(x), hw, hw_p, layout)
    g2 = _lrn_pad3(to3(g), hw, hw_p, layout)
    spec, caxis = _lrn_specs(c, tile, layout)
    out_shape = ((n, hw_p, c) if layout == "NHWC" else (n, c, hw_p))
    out = pl.pallas_call(
        functools.partial(_lrn_bwd_kernel, local_size=local_size,
                          alpha=alpha, beta=beta, k=k, channels=c,
                          channel_axis=caxis),
        name="lrn_bwd",
        out_shape=jax.ShapeDtypeStruct(out_shape, x.dtype),
        grid=(n, hw_p // tile),
        in_specs=[spec, spec],
        out_specs=spec,
        interpret=interpret,
    )(x2, g2)
    return from3(_lrn_crop3(out, n, c, hw, layout))


def _lrn_fused_vjp_fwd(x, local_size, alpha, beta, k, tile, interpret,
                       layout):
    return _lrn_fused_fwd_impl(x, local_size, alpha, beta, k, tile,
                               interpret, layout), x


def _lrn_fused_vjp_bwd(local_size, alpha, beta, k, tile, interpret, layout,
                       x, g):
    if interpret is None:
        interpret = _interpret_default()
    if interpret:
        # off-TPU: the differentiable XLA formulation (interpret-mode
        # Pallas emulation would only slow the CPU mesh down)
        from .nn import lrn_across_channels
        _, vjp = jax.vjp(
            lambda x_: lrn_across_channels(x_, local_size, alpha, beta, k,
                                           layout),
            x)
        return vjp(g)
    return (lrn_fused_bwd(x, g, local_size, alpha, beta, k, tile,
                          interpret, layout),)


_lrn_fused_cvjp.defvjp(_lrn_fused_vjp_fwd, _lrn_fused_vjp_bwd)


def lrn_route(hw: int, channels: int):
    """``(arm, note)`` for one ACROSS_CHANNELS LRN geometry — THE routing
    decision: ``maybe_lrn_fused`` takes it at trace time and ``Net`` logs
    it per layer at construction. ``"pallas"`` on TPU (the fused fwd+bwd
    kernels, either layout); ``"xla"`` on the CPU test mesh
    (interpret-mode emulation is strictly slower than the op it
    replaces) and for channel counts beyond the VMEM tiling cap. Same
    numerics either way. ``POSEIDON_PALLAS_LRN`` forces an arm for A/B:
    ``0`` = XLA on TPU, ``1`` = the (interpreted) kernels on CPU."""
    import os
    env = os.environ.get("POSEIDON_PALLAS_LRN", "")
    if env == "0":
        return "xla", "POSEIDON_PALLAS_LRN=0"
    if _interpret_default() and env != "1":
        return "xla", "cpu backend"
    if not lrn_tile_feasible(hw, channels):
        return "xla", f"no VMEM-legal tile for {channels} channels"
    return "pallas", ""


def maybe_lrn_fused(x, local_size: int, alpha: float, beta: float,
                    k: float = 1.0, layout: str = "NCHW"):
    """ACROSS_CHANNELS LRN through :func:`lrn_route`'s arm. The NCHW block
    puts channels major, the NHWC entry keeps channels minor: no transpose
    in the program, though on the v5e the compiler still copies between
    its own channel-minor activation layout and the kernel's row-major
    operands (1-1.5 ms each at AlexNet's norm1). One traced run each there
    (PR 24): AlexNet's device step is 68.9 ms with the kernels and 75.95
    through XLA, GoogLeNet's 50.6 and 50.4 (ROADMAP S6)."""
    from .nn import lrn_across_channels
    _, c, hw, _, _ = _lrn_shape(x, layout)
    if lrn_route(hw, c)[0] == "pallas":
        return lrn_fused(x, local_size, alpha, beta, k, layout=layout)
    return lrn_across_channels(x, local_size, alpha, beta, k, layout)
