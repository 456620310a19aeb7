"""Pallas TPU kernels for the hot ops.

``flash_attention``: blockwise attention entirely in VMEM — never
materializes the (S, S) score matrix in HBM. Grid is (batch*heads,
query-blocks, key-blocks); the online-softmax recurrence (the same math as
ops/attention.py's BlockAcc) runs per (block_q, block_k) tile, sized by
``flash_blocks`` from the sequence length, the head width and the operand
itemsize. The backward pass is likewise Pallas and O(S) in HBM: one sweep
over the (K block, Q block) pairs recomputes the scores blockwise from the
saved (out, logsumexp) residuals and makes dK, dV and, summed into a head's
resident rows, dQ (the paper's two sweeps where those rows do not fit VMEM),
wired up via ``defvjp``.

``lrn_fused`` / ``lrn_fused_bwd``: cross-channel LRN, forward and analytic
backward, in the orientation the compiled step holds the activation in
(``_lrn_tile``). The default on TPU (``lrn_route``; ``POSEIDON_PALLAS_LRN=0``
opts out), the XLA formulation on the CPU mesh and beyond the VMEM cap.

``maxpool_bwd``: max-pool backward in one pass in the activation dtype,
in the same orientation with the window axes leading (``_pool_plan``). The
default for MAX pooling on TPU (``nn.pool_bwd_route``), the tap-sum on the
CPU mesh, select-and-scatter for AVE pooling.

Kernels run in interpret mode on the CPU test mesh so it exercises the same
code path; any backend other than tpu/cpu is refused (``_interpret_default``).
"""

from __future__ import annotations

import functools
import math
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.ad_checkpoint import checkpoint_name
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

_NT = (((1,), (1,)), ((), ()))      # a @ b.T: both contract their minor dim
_NN = (((1,), (0,)), ((), ()))      # a @ b
_TN = (((0,), (0,)), ((), ()))      # a.T @ b: both contract their major dim


def _dot(a, b, dims):
    """One MXU product of two tiles, accumulated in f32."""
    return lax.dot_general(a, b, dims, preferred_element_type=jnp.float32,
                           precision=matmul_precision())


def _operand_dtype(dtype):
    """What a tile is handed to the MXU as. Under the bf16 policy (DEFAULT
    precision) the dtype it arrives in: a bf16 x bf16 product accumulated in
    f32 is exact, an up-cast buys nothing. Under the f32 policy (HIGHEST)
    f32, as ever."""
    if matmul_precision() == lax.Precision.HIGHEST:
        return jnp.float32
    return dtype


_BLOCK_LADDER = (1024, 512, 256, 128, 64, 32, 16, 8)
# What Mosaic may use of VMEM for one flash program (128 MiB on the v5e, of
# which a kernel gets 16 unless it asks), and the three quarters of that
# the tile rule fills with what it can count; the rest is the compiler's
# own (the iota, compare and cast temporaries of the masked path).
_FLASH_VMEM_LIMIT = 32 * 2 ** 20
_FLASH_VMEM_BUDGET = 24 * 2 ** 20
# The backward's single sweep asks for twice both, half the chip's VMEM: at
# 1024 x 1024 beside a head's dQ rows it counts 32 MiB at S 8,192 x 128, 40
# at 16,384 x 128 and 44 at 8,192 x 256 (``_flash_vmem_bytes``).
_SWEEP_VMEM_LIMIT = 2 * _FLASH_VMEM_LIMIT
_SWEEP_VMEM_BUDGET = 2 * _FLASH_VMEM_BUDGET
# f32 score-shaped (block_q, block_k) temporaries a program's body names:
# s and p forward; s, p, dp and ds in either of the two backward sweeps; in
# the single sweep (``"bwd"``: the dK/dV sweep that sums dQ too) a fifth,
# the transposed ds in the operand dtype that two products read. An upper
# bound: Mosaic reuses their buffers (1024 x 1024 compiles for the v5e
# inside 16 MiB in the three older kernels, not inside 8).
_SCORE_TEMPS = {"fwd": 2, "dq": 4, "dkv": 4, "bwd": 5}


def pick_block(s: int) -> Optional[int]:
    """Largest clean tile height for a sequence length, MXU/VPU-aligned:
    the one-dimensional half of the tile rule (``flash_blocks`` pairs two
    such heights under a VMEM budget), and None where the kernels do not
    tile at all.

    Mosaic only needs the block's second-minor dim to be a multiple of the
    8-row f32 sublane tile, so non-power-of-two sequence lengths that no
    block of 32 rows or more divides (s=48, s=136, ...) still tile with a
    smaller aligned block — falling back to None there routed perfectly
    kernelable shapes onto the dense O(S^2) op."""
    return next((bs for bs in _BLOCK_LADDER if s % bs == 0), None)


def _flash_vmem_bytes(kernel: str, block_q: int, block_k: int, d: int,
                      itemsize: int, dv: Optional[int] = None,
                      s: int = 0) -> int:
    """Live VMEM of one program: the score-shaped f32 temporaries, the
    operand and result tiles (double-buffered by the pipeline) and the f32
    accumulators. ``d`` is the width of a q / k head, ``dv`` of a v head
    (None: the same). The single sweep (``"bwd"``) is the dK/dV sweep plus
    one head's whole dQ, ``s`` rows: the f32 sums and the result's block,
    double-buffered like any other (4.2 + 4.2 MB at 8,192 x 128 bf16)."""
    dv = d if dv is None else dv
    scores = _SCORE_TEMPS[kernel] * block_q * block_k * 4
    dkv = (d + dv, 2 * (d + dv),                  # q, dO | k, v, dk, dv |
           block_k * (d + dv))                    # two accs
    q_cols, k_cols, acc = {
        "fwd": (d + dv, d + dv, block_q * dv),    # q, o | k, v | acc
        "dq": (2 * d + dv, d + dv, block_q * d),  # q, dO, dq | k, v | dq_acc
        "dkv": dkv, "bwd": dkv,
    }[kernel]
    tiles = 2 * itemsize * (q_cols * block_q + k_cols * block_k)
    rows = s * d * (4 + 2 * itemsize) if kernel == "bwd" else 0
    return scores + tiles + acc * 4 + rows


def _fits_vmem(kernel: str, block_q: int, block_k: int, d: int,
               itemsize: int, dv: Optional[int], s: int) -> bool:
    """Whether a program's count is inside its kernel's budget."""
    budget = _SWEEP_VMEM_BUDGET if kernel == "bwd" else _FLASH_VMEM_BUDGET
    return _flash_vmem_bytes(kernel, block_q, block_k, d, itemsize, dv,
                             s) <= budget


def flash_blocks(kernel: str, s: int, d: int, itemsize: int,
                 dv: Optional[int] = None):
    """``(block_q, block_k)`` for one of the flash kernels (``"fwd"``; the
    backward's single sweep ``"bwd"``, or its two, ``"dq"`` and ``"dkv"``)
    — THE tile rule, a function of the sequence
    length, the head widths (``d`` of q and k, ``dv`` of v: None = the
    same) and the operand itemsize alone: the largest
    aligned blocks dividing S whose live tiles fit the VMEM budget. A grid
    step costs about 0.35 us on the v5e whatever it computes, ten times
    the arithmetic of a 128 x 128 x 128 block, so a program is given as
    much work as VMEM holds (1024 x 1024 at S=4096, D=128); of two
    choices with equal work per program the wider K/V block wins (fewer
    rescalings of the running softmax state per score). Lengths no large
    block divides (S = 48, 136, ...) tile as they always did. None: no
    aligned block divides S, or (``"bwd"``) a head's dQ rows leave no tile
    room in the budget (S 65,536 x 128): the backward is then the two
    sweeps (``_single_sweep_blocks``)."""
    best = None
    for bq in _BLOCK_LADDER:
        for bk in _BLOCK_LADDER:
            if s % bq or s % bk or not _fits_vmem(kernel, bq, bk, d,
                                                  itemsize, dv, s):
                continue
            if best is None or (bq * bk, bk) > (best[0] * best[1], best[1]):
                best = (bq, bk)
    return best


def _causal_mask(s, qi, kj, block_q, block_k, mode=None, transposed=False,
                 window=None):
    """Self-attention: mask by absolute tile position. Chunked (ring) mode:
    ``mode`` is a traced scalar describing how the K/V chunk aligns with the
    Q rows' chunk — +1 chunk strictly past (all live), 0 diagonal (in-chunk
    triangle), -1 future (all masked). ``transposed``: ``s`` is the
    (block_k, block_q) tile of the dK/dV sweep, keys down the rows.
    ``window``: row t also loses the columns at or before t - window."""
    shape = (block_k, block_q) if transposed else (block_q, block_k)
    rows = qi * block_q + lax.broadcasted_iota(
        jnp.int32, shape, 1 if transposed else 0)
    cols = kj * block_k + lax.broadcasted_iota(
        jnp.int32, shape, 0 if transposed else 1)
    if window is not None:
        return jnp.where((rows >= cols) & (rows - cols < window), s, NEG_INF)
    if mode is None:
        return jnp.where(rows >= cols, s, NEG_INF)
    live = (mode > 0) | ((mode == 0) & (rows >= cols))
    return jnp.where(live, s, NEG_INF)


def _on_live_blocks(update, causal: bool, chunk_mode: bool, qi, kj,
                    block_q: int, block_k: int, window=None,
                    in_range=None) -> None:
    """Run ``update(masked)`` where block (qi, kj) has anything to add.
    Causal self-attention knows that from the grid position: a block wholly
    below the diagonal needs no mask, one the diagonal crosses is masked
    element by element, one wholly above it is skipped (its operands are
    not fetched either: ``_last_live_k`` / ``_first_live_q``). With a
    ``window`` the live blocks are a band: one wholly at or before the
    band's lower edge is skipped as well, one the edge crosses is masked.
    ``in_range``: the windowed dK/dV sweep's Q block lies inside the
    sequence (its grid may step past the end). Ring attention's chunk
    alignment is a traced scalar: every block takes the masked path."""
    if not causal:
        update(False)
    elif chunk_mode:
        update(True)
    else:
        first_row, last_row = qi * block_q, qi * block_q + block_q - 1
        first_col, last_col = kj * block_k, kj * block_k + block_k - 1
        if window is None:
            pl.when(last_col <= first_row)(lambda: update(False))
            pl.when((first_col <= last_row) & (last_col > first_row))(
                lambda: update(True))
            return
        # row - col lies in [first_row - last_col, last_row - first_col]
        # over the block, and has to lie in [0, window)
        inside = (last_col <= first_row) & (last_row - first_col < window)
        live = (first_col <= last_row) & (first_row - last_col < window)
        if in_range is not None:
            live = live & in_range
        pl.when(inside & live)(lambda: update(False))
        pl.when(live & jnp.logical_not(inside))(lambda: update(True))


def _last_live_k(qi, block_q: int, block_k: int):
    """Causal self-attention: the last K/V block a Q block attends to."""
    return (qi * block_q + block_q - 1) // block_k


def _first_live_q(kj, block_q: int, block_k: int):
    """Causal self-attention: the first Q block that attends to a K/V block."""
    return (kj * block_k) // block_q


def _at_least_0(x):
    return max(x, 0) if isinstance(x, int) else jnp.maximum(x, 0)


def _first_live_k(qi, block_q: int, block_k: int, window: int):
    """Windowed self-attention: the first K/V block a Q block attends to
    (the one that holds column first_row - window + 1)."""
    return _at_least_0(qi * block_q - window + 1) // block_k


def _last_live_q(kj, block_q: int, block_k: int, window: int):
    """Windowed self-attention: the last Q block that attends to a K/V
    block (the one that holds row last_col + window - 1), which may lie past
    the sequence's end: callers cap it."""
    return (kj * block_k + block_k + window - 2) // block_q


def _band_steps(s: int, block_q: int, block_k: int, window: int,
                over_q: bool = False) -> int:
    """Windowed self-attention: the innermost grid extent, the most live
    blocks any one outer block has — K/V blocks a Q block (the forward and
    the dQ sweep), or with ``over_q`` Q blocks a K/V block (the dK/dV
    sweep). The grid then starts each outer block at its first live block
    and visits nothing before the band."""
    n_qb, n_kb = s // block_q, s // block_k
    if over_q:
        return max(min(n_qb - 1, _last_live_q(kj, block_q, block_k, window))
                   - _first_live_q(kj, block_q, block_k) + 1
                   for kj in range(n_kb))
    return max(_last_live_k(qi, block_q, block_k)
               - _first_live_k(qi, block_q, block_k, window) + 1
               for qi in range(n_qb))


def flash_grid_programs(s: int, block_q: int, block_k: int, causal: bool,
                        window: Optional[int] = None, over_q: bool = False):
    """(live, visited) programs per head of one flash grid. A visited block
    that is not live runs no body and names the operand tile already
    resident, so it costs one empty grid step. With a ``window`` the grid
    is the band's (``_band_steps``; ``over_q``: the dK/dV sweep's)."""
    n_qb, n_kb = s // block_q, s // block_k
    if not causal:
        return n_qb * n_kb, n_qb * n_kb
    if window is None:
        live = sum(min(n_kb, _last_live_k(qi, block_q, block_k) + 1)
                   for qi in range(n_qb))
        return live, n_qb * n_kb
    live = sum(_last_live_k(qi, block_q, block_k)
               - _first_live_k(qi, block_q, block_k, window) + 1
               for qi in range(n_qb))
    outer = n_kb if over_q else n_qb
    return live, outer * _band_steps(s, block_q, block_k, window, over_q)


def _flash_fwd_kernel(*refs, scale: float, causal: bool, block_q: int,
                      block_k: int, n_kb: int, chunk_mode: bool, op_dtype,
                      window=None):
    """Grid (bh, q_blocks, k_blocks); only one (block_q, d) Q tile and one
    (block_k, d) K/V tile are VMEM-resident at a time. The online-softmax
    state persists in f32 scratch across the innermost (k-block) grid
    dimension. Also emits the per-row logsumexp, which the O(S)-memory
    backward kernels consume (flash attention paper's L = m + log l).

    ``chunk_mode`` (ring attention): a leading SMEM scalar describes the
    chunk alignment for causal masking (see _causal_mask).

    ``window``: the innermost grid dimension has ``n_kb`` = the band's
    steps and starts at the Q block's first live K/V block."""
    if chunk_mode:
        mode_ref, q_ref, k_ref, v_ref, o_ref, lse_ref, \
            acc_ref, m_ref, l_ref = refs
        mode = mode_ref[0]
    else:
        q_ref, k_ref, v_ref, o_ref, lse_ref, acc_ref, m_ref, l_ref = refs
        mode = None
    qi = pl.program_id(1)
    step = kj = pl.program_id(2)
    if window is not None:
        kj = _first_live_k(qi, block_q, block_k, window) + step

    @pl.when(step == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    def update(masked: bool):
        q = q_ref[0].astype(op_dtype)             # (block_q, d)
        k_blk = k_ref[0].astype(op_dtype)         # (block_k, d)
        v_blk = v_ref[0].astype(op_dtype)
        s = _dot(q, k_blk, _NT) * scale           # f32 from here on
        if masked:
            s = _causal_mask(s, qi, kj, block_q, block_k, mode,
                             window=window)
        m_prev = m_ref[...]                       # (block_q, 1)
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)
        l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=-1, keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha + _dot(
            p.astype(op_dtype), v_blk, _NN)
        m_ref[...] = m_new

    _on_live_blocks(update, causal, chunk_mode, qi, kj, block_q, block_k,
                    window)

    @pl.when(step == n_kb - 1)
    def _finalize():
        l = l_ref[...]
        lsafe = jnp.where(l == 0, 1.0, l)
        o_ref[0] = (acc_ref[...] / lsafe).astype(o_ref.dtype)
        # this kernel (and the dQ sweep, where it runs) wants the row
        # statistics as a column beside its (block_q, block_k) scores:
        # (bh, s, 1), whose (block_q, 1) tile is legal at any block height.
        # The backward's sweep over transposed scores reads them as rows
        # (_flash_bwd).
        lse_ref[0] = m_ref[...] + jnp.log(lsafe)


def _blocks_for(kernel: str, s: int, d: int, itemsize: int, block_q, block_k,
                dv=None):
    """The caller's blocks, or the tile rule's for this kernel."""
    if block_q is None or block_k is None:
        blocks = flash_blocks(kernel, s, d, itemsize, dv)
        if blocks is None:
            raise ValueError(f"no aligned block divides seq len {s}")
        return blocks
    block_q, block_k = min(block_q, s), min(block_k, s)
    if s % block_q or s % block_k:
        raise ValueError(f"seq len {s} must divide by blocks "
                         f"({block_q}, {block_k})")
    return block_q, block_k


def _operand_view(x, heads: Optional[int]):
    """``(x3, H')``: an operand as the kernels address it, ``(B', S, H'·D)``
    of which program ``i`` takes the lane block ``i % H'`` of batch row
    ``i // H'``. Head-major ``(B, H, S, D)`` (``heads`` None) is the case
    ``B' = B·H, H' = 1``; token-major ``(B, S, H·D)``, the form a projection
    leaves its result in, is itself with ``H' = heads``."""
    if heads is not None:
        return x, heads
    b, h, s, d = x.shape
    return x.reshape(b * h, s, d), 1


def _tile_at(hp: int):
    """``at(i, row)``: the block index of program ``i``'s ``(1, block, D)``
    tile at row block ``row`` of a ``(B', S, H'·D)`` operand."""
    if hp == 1:
        return lambda i, row: (i, row, 0)
    return lambda i, row: (i // hp, row, i % hp)


def _kv_block_map(clamp: bool, block_q: int, block_k: int, window=None,
                  at=_tile_at(1)):
    """Index map of a K/V tile on the (bh, q_blocks, k_blocks) grid; ``at``
    places a row block in the operand (``_tile_at``).
    ``clamp`` (causal self-attention): a block above the diagonal names the
    last live K/V tile again, which is resident, so no copy is issued.
    ``window``: the grid's last dimension counts from the Q block's first
    live K/V block."""
    if window is not None:
        return lambda i, j, kk: at(
            i, jnp.minimum(_first_live_k(j, block_q, block_k, window) + kk,
                           _last_live_k(j, block_q, block_k)))
    if clamp:
        return lambda i, j, kk: at(
            i, jnp.minimum(kk, _last_live_k(j, block_q, block_k)))
    return lambda i, j, kk: at(i, kk)


def _compiler_params(single_sweep: bool = False):
    """``single_sweep``: the grid's second dimension carries a sum too (dQ
    over the K blocks) and may not be split or reordered (one TensorCore a
    v5e chip, so nothing is lost), and the head's dQ rows want the larger
    limit."""
    return pltpu.CompilerParams(
        dimension_semantics=("parallel",
                             "arbitrary" if single_sweep else "parallel",
                             "arbitrary"),
        vmem_limit_bytes=_SWEEP_VMEM_LIMIT if single_sweep
        else _FLASH_VMEM_LIMIT)


def _flash_fwd(q, k, v, scale: float, causal: bool, block_q: Optional[int],
               block_k: Optional[int], interpret: bool, mode=None,
               window: Optional[int] = None, heads: Optional[int] = None):
    """q, k, v head-major ``(B, H, S, D)``, or with ``heads`` token-major
    ``(B, S, heads·D)`` as the projections leave them: one grid
    ``(B·H, q_blocks, k_blocks)`` either way, a head being a lane block in
    the index maps (``_operand_view``). Returns the output in the operands'
    form and the logsumexp ``(B, H, S)``.

    mode (traced int32 scalar) selects chunked causal masking for ring
    attention; None = plain self-attention. Blocks of None: the tile rule's
    (``flash_blocks``). ``window`` (causal self-attention, below S): the
    grid covers the band alone."""
    (q3, hp), (k3, _), (v3, _) = (_operand_view(x, heads) for x in (q, k, v))
    bp, s, _ = q3.shape
    d, dv = q3.shape[-1] // hp, v3.shape[-1] // hp  # v heads: their own width
    bh = bp * hp
    block_q, block_k = _blocks_for("fwd", s, d, q.dtype.itemsize, block_q,
                                   block_k, dv)
    n_kb = s // block_k
    extra = {}
    if window is not None:
        n_kb = _band_steps(s, block_q, block_k, window)
        extra = {"window": window}
    grid = (bh, s // block_q, n_kb)
    at = _tile_at(hp)
    chunk = mode is not None
    kmap = _kv_block_map(causal and not chunk, block_q, block_k, window, at)
    qmap = lambda i, j, kk: at(i, j)
    rowmap = lambda i, j, kk: (i, j, 0)
    in_specs = [
        pl.BlockSpec((1, block_q, d), qmap, memory_space=pltpu.VMEM),
        pl.BlockSpec((1, block_k, d), kmap, memory_space=pltpu.VMEM),
        pl.BlockSpec((1, block_k, dv), kmap, memory_space=pltpu.VMEM),
    ]
    args = [q3, k3, v3]
    if chunk:
        in_specs.insert(0, pl.BlockSpec(memory_space=pltpu.SMEM))
        args.insert(0, jnp.asarray(mode, jnp.int32).reshape(1))
    out, lse = pl.pallas_call(
        functools.partial(_flash_fwd_kernel, scale=scale, causal=causal,
                          block_q=block_q, block_k=block_k, n_kb=n_kb,
                          chunk_mode=chunk,
                          op_dtype=_operand_dtype(q.dtype), **extra),
        name="flash_fwd",
        out_shape=(jax.ShapeDtypeStruct((bp, s, hp * dv), q.dtype),
                   jax.ShapeDtypeStruct((bh, s, 1), jnp.float32)),
        grid=grid,
        in_specs=in_specs,
        out_specs=(
            pl.BlockSpec((1, block_q, dv), qmap, memory_space=pltpu.VMEM),
            pl.BlockSpec((1, block_q, 1), rowmap, memory_space=pltpu.VMEM),
        ),
        scratch_shapes=[
            pltpu.VMEM((block_q, dv), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
        ],
        compiler_params=_compiler_params(),
        interpret=interpret,
    )(*args)
    h = heads or q.shape[1]
    if heads is None:
        out = out.reshape(q.shape[:-1] + (dv,))
    return out, lse.reshape(bh // h, h, s)


# --------------------------------------------------------------------------- #
# Flash attention backward: O(S) memory. One sweep over the (K block, Q block)
# pairs where a head's dQ rows stay in VMEM beside the tiles, else the flash
# attention paper's two
# --------------------------------------------------------------------------- #

def _flash_dq_kernel(*refs, scale: float, causal: bool, block_q: int,
                     block_k: int, n_kb: int, chunk_mode: bool, op_dtype,
                     window=None):
    """Grid (bh, q_blocks, k_blocks): accumulate dQ for one Q tile across all
    K/V tiles: the first of the two sweeps, run where a head's dQ does not
    fit beside the dK/dV sweep's tiles. p is recomputed from Q,K and the
    saved logsumexp — the score
    matrix never exists outside one VMEM tile. The softmax scale on dS is
    applied once, to the f32 accumulator."""
    if chunk_mode:
        mode_ref, q_ref, k_ref, v_ref, g_ref, lse_ref, delta_ref, \
            dq_ref, dq_acc = refs
        mode = mode_ref[0]
    else:
        q_ref, k_ref, v_ref, g_ref, lse_ref, delta_ref, dq_ref, dq_acc = refs
        mode = None
    qi = pl.program_id(1)
    step = kj = pl.program_id(2)
    if window is not None:                  # the band's grid: see the forward
        kj = _first_live_k(qi, block_q, block_k, window) + step

    @pl.when(step == 0)
    def _init():
        dq_acc[...] = jnp.zeros_like(dq_acc)

    def update(masked: bool):
        q = q_ref[0].astype(op_dtype)
        k_blk = k_ref[0].astype(op_dtype)
        v_blk = v_ref[0].astype(op_dtype)
        g = g_ref[0].astype(op_dtype)
        s = _dot(q, k_blk, _NT) * scale
        if masked:
            s = _causal_mask(s, qi, kj, block_q, block_k, mode,
                             window=window)
        p = jnp.exp(s - lse_ref[0])               # masked entries -> 0
        dp = _dot(g, v_blk, _NT)
        ds = p * (dp - delta_ref[0])              # lse, delta: (block_q, 1)
        dq_acc[...] = dq_acc[...] + _dot(ds.astype(op_dtype), k_blk, _NN)

    _on_live_blocks(update, causal, chunk_mode, qi, kj, block_q, block_k,
                    window)

    @pl.when(step == n_kb - 1)
    def _finalize():
        dq_ref[0] = (dq_acc[...] * scale).astype(dq_ref.dtype)


def _flash_dkv_kernel(*refs, scale: float, causal: bool, block_q: int,
                      block_k: int, n_qb: int, chunk_mode: bool, op_dtype,
                      window=None, q_blocks=None, n_kb=None):
    """Grid (bh, k_blocks, q_blocks): accumulate dK and dV for one K/V tile
    across all Q tiles. The scores are computed TRANSPOSED, keys down the
    rows ((block_k, block_q) = K Q^T), so that P^T dO and dS^T Q are plain
    products of the tile as it lies and nothing score-shaped goes through a
    transpose; the row statistics then broadcast down the sublanes from a
    lane-dense (1, block_q) tile.

    ``window``: the innermost grid dimension has ``n_qb`` = the band's
    steps and starts at the K/V block's first live Q block; ``q_blocks`` is
    then how many Q blocks the sequence has (a step may lie past them).

    ``n_kb`` (the single sweep: how many K blocks the grid has): the pair's
    share of dQ, dS K, is taken from the same transposed dS by one more
    product and summed into the pair's rows of a scratch that holds the
    head's whole dQ, (S, d) f32: zeroed at the head's first program, written
    out once, scaled, at its last, to a result block that is the head's
    whole slab. A Q block's shares arrive in the order of the K blocks, the
    dQ sweep's own order. The scores are then recomputed ONCE a backward."""
    refs = list(refs)
    mode = refs.pop(0)[0] if chunk_mode else None
    q_ref, k_ref, v_ref, g_ref, lse_ref, delta_ref, dk_ref, dv_ref = refs[:8]
    if n_kb is None:
        dk_acc, dv_acc = refs[8:]
    else:
        dq_ref, dk_acc, dv_acc, dq_acc = refs[8:]
    kj = pl.program_id(1)
    step = qi = pl.program_id(2)
    in_range = None
    if window is not None:
        qi = _first_live_q(kj, block_q, block_k) + step
        in_range = qi < q_blocks

    @pl.when(step == 0)
    def _init():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    if n_kb is not None:
        @pl.when((step == 0) & (kj == 0))
        def _init_head():
            dq_acc[...] = jnp.zeros_like(dq_acc)

    def update(masked: bool):
        q = q_ref[0].astype(op_dtype)
        k_blk = k_ref[0].astype(op_dtype)
        v_blk = v_ref[0].astype(op_dtype)
        g = g_ref[0].astype(op_dtype)
        st = _dot(k_blk, q, _NT) * scale          # (block_k, block_q)
        if masked:
            st = _causal_mask(st, qi, kj, block_q, block_k, mode,
                              transposed=True, window=window)
        pt = jnp.exp(st - lse_ref[0, 0])          # lse, delta: (1, block_q)
        dv_acc[...] = dv_acc[...] + _dot(pt.astype(op_dtype), g, _NN)
        dpt = _dot(v_blk, g, _NT)
        dst = (pt * (dpt - delta_ref[0, 0])).astype(op_dtype)
        dk_acc[...] = dk_acc[...] + _dot(dst, q, _NN)
        if n_kb is not None:
            rows = pl.ds(pl.multiple_of(qi * block_q, block_q), block_q)
            dq_acc[rows, :] = dq_acc[rows, :] + _dot(dst, k_blk, _TN)

    _on_live_blocks(update, causal, chunk_mode, qi, kj, block_q, block_k,
                    window, in_range)

    @pl.when(step == n_qb - 1)
    def _finalize():
        dk_ref[0] = (dk_acc[...] * scale).astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[...].astype(dv_ref.dtype)

    if n_kb is not None:
        @pl.when((step == n_qb - 1) & (kj == n_kb - 1))
        def _finalize_head():
            dq_ref[0] = (dq_acc[...] * scale).astype(dq_ref.dtype)


def _single_sweep_blocks(s: int, d: int, itemsize: int, block_q, block_k,
                         dv=None):
    """The single-sweep backward's blocks (the rule's, or the caller's), or
    None where a head's dQ rows do not fit in VMEM beside them: the
    residency rule, read off the same count the tile rule reads. None is
    the two sweeps."""
    if block_q is None or block_k is None:
        return flash_blocks("bwd", s, d, itemsize, dv)
    blocks = _blocks_for("bwd", s, d, itemsize, block_q, block_k, dv)
    return blocks if _fits_vmem("bwd", *blocks, d, itemsize, dv, s) else None


def _flash_bwd(q, k, v, out, lse, g, scale: float, causal: bool,
               block_q: Optional[int], block_k: Optional[int],
               interpret: bool, mode=None, delta=None,
               window: Optional[int] = None, heads: Optional[int] = None):
    """mode, blocks, window, heads: see _flash_fwd; out and g come in the
    operands' form, lse is (B, H, S)
    and dq, dk, dv leave in the operands' form. One sweep where a head's dQ
    stays resident (``_single_sweep_blocks``: every shape a cell runs), else
    the dQ sweep and the dK/dV sweep, each with the rule's tiles for it.
    ``delta`` (rowsum(dO*O), global, (B, H, S)) may be passed in by the ring
    backward, whose O is the merged global output."""
    hp = heads or 1
    d, d_v = q.shape[-1] // hp, v.shape[-1] // hp   # v, out, g: a v head's
    if delta is None:
        # delta_i = rowsum(dO * O): one O(S*D) elementwise pass, XLA-fused
        if heads is None:
            delta = jnp.sum(g.astype(jnp.float32) * out.astype(jnp.float32),
                            axis=-1)                   # (b, h, s)
        else:
            # the sum over a head's lanes as a product with the heads'
            # 0 / 1 lane masks, which leaves it (b, h, s) as the kernels
            # want it: (b, s, h, d_v) is another layout under the (8, 128)
            # tiling, and a copy of dO's size. A product of two bf16 values
            # is exact in f32 and the f32 policy's passes keep it so. Behind
            # a barrier: the matmul that makes dO would take dO * O into its
            # fusion and write it out in f32 for this one to read
            lanes = np.kron(np.eye(hp, dtype=np.float32),
                            np.ones(d_v, np.float32))
            delta = jnp.einsum(
                "bsl,hl->bhs", lax.optimization_barrier(g).astype(jnp.float32)
                * out.astype(jnp.float32), lanes,
                precision=lax.Precision.HIGHEST)
    (q3, _), (k3, _), (v3, _), (g3, _) = (
        _operand_view(x, heads) for x in (q, k, v, g))
    bp, s, _ = q3.shape
    bh = bp * hp
    chunk = mode is not None
    mode_arg = [jnp.asarray(mode, jnp.int32).reshape(1)] if chunk else []
    smem = [pl.BlockSpec(memory_space=pltpu.SMEM)] if chunk else []
    op_dtype = _operand_dtype(q.dtype)
    clamp = causal and not chunk
    vmem = functools.partial(pl.BlockSpec, memory_space=pltpu.VMEM)
    at = _tile_at(hp)
    itemsize = q.dtype.itemsize
    single = _single_sweep_blocks(s, d, itemsize, block_q, block_k, d_v)

    if single is None:
        # dQ sweep: grid (bh, q_blocks, k_blocks), the forward's; the row
        # statistics a (block_q, 1) column a Q block
        bq, bk = _blocks_for("dq", s, d, itemsize, block_q, block_k, d_v)
        qmap = lambda i, j, kk: at(i, j)
        kmap = _kv_block_map(clamp, bq, bk, window, at)
        qspec, gspec = vmem((1, bq, d), qmap), vmem((1, bq, d_v), qmap)
        kspec, vspec = vmem((1, bk, d), kmap), vmem((1, bk, d_v), kmap)
        rowq = vmem((1, bq, 1), lambda i, j, kk: (i, j, 0))
        n_kb, extra = s // bk, {}
        if window is not None:
            n_kb = _band_steps(s, bq, bk, window)
            extra = {"window": window}
        dq = pl.pallas_call(
            functools.partial(_flash_dq_kernel, scale=scale, causal=causal,
                              block_q=bq, block_k=bk, n_kb=n_kb,
                              chunk_mode=chunk, op_dtype=op_dtype, **extra),
            name="flash_bwd_dq",
            out_shape=jax.ShapeDtypeStruct(q3.shape, q.dtype),
            grid=(bh, s // bq, n_kb),
            in_specs=smem + [qspec, kspec, vspec, gspec, rowq, rowq],
            out_specs=qspec,
            scratch_shapes=[pltpu.VMEM((bq, d), jnp.float32)],
            compiler_params=_compiler_params(),
            interpret=interpret,
        )(*mode_arg, q3, k3, v3, g3, lse.reshape(bh, s, 1),
          delta.reshape(bh, s, 1))

    # dK/dV sweep: swapped grid (bh, k_blocks, q_blocks); the row statistics
    # one lane-dense (1, block_q) row per Q block. The single sweep is this
    # grid with the head's dQ as a third result
    bq, bk = single or _blocks_for("dkv", s, d, itemsize, block_q, block_k,
                                   d_v)
    n_qb = steps = s // bq
    extra = {}
    if window is not None:
        # the grid's last dimension counts from the K/V block's first live
        # Q block; past the band's last (or the sequence's) it names that
        # one again
        steps = _band_steps(s, bq, bk, window, over_q=True)
        extra = {"window": window, "q_blocks": n_qb}
        qblk = lambda j, kk: jnp.minimum(
            _first_live_q(j, bq, bk) + kk,
            jnp.minimum(_last_live_q(j, bq, bk, window), n_qb - 1))
    elif clamp:
        # a Q block above the diagonal names the first live one again
        qblk = lambda j, kk: jnp.maximum(kk, _first_live_q(j, bq, bk))
    else:
        qblk = lambda j, kk: kk
    qmap_t = lambda i, j, kk: at(i, qblk(j, kk))
    kmap_t = lambda i, j, kk: at(i, j)
    qspec_t, gspec_t = vmem((1, bq, d), qmap_t), vmem((1, bq, d_v), qmap_t)
    kspec_t, vspec_t = vmem((1, bk, d), kmap_t), vmem((1, bk, d_v), kmap_t)
    rowq_t = vmem((1, 1, 1, bq), lambda i, j, kk: (i, qblk(j, kk), 0, 0))
    out_shape = [jax.ShapeDtypeStruct(k3.shape, k.dtype),
                 jax.ShapeDtypeStruct(v3.shape, v.dtype)]
    out_specs = [kspec_t, vspec_t]
    scratch = [pltpu.VMEM((bk, d), jnp.float32),
               pltpu.VMEM((bk, d_v), jnp.float32)]
    if single:
        # the head's whole dQ: a block whose index is constant over the
        # head's programs, so it is written back when the head changes
        extra["n_kb"] = s // bk
        out_shape.append(jax.ShapeDtypeStruct(q3.shape, q.dtype))
        out_specs.append(vmem((1, s, d), lambda i, j, kk: at(i, 0)))
        scratch.append(pltpu.VMEM((s, d), jnp.float32))
    dk, dv, *dq_rows = pl.pallas_call(
        functools.partial(_flash_dkv_kernel, scale=scale, causal=causal,
                          block_q=bq, block_k=bk, n_qb=steps,
                          chunk_mode=chunk, op_dtype=op_dtype, **extra),
        name="flash_bwd" if single else "flash_bwd_dkv",
        out_shape=out_shape,
        grid=(bh, s // bk, steps),
        in_specs=smem + [qspec_t, kspec_t, vspec_t, gspec_t, rowq_t, rowq_t],
        out_specs=out_specs,
        scratch_shapes=scratch,
        compiler_params=_compiler_params(single_sweep=bool(single)),
        interpret=interpret,
    )(*mode_arg, q3, k3, v3, g3, lse.reshape(bh, n_qb, 1, bq),
      delta.reshape(bh, n_qb, 1, bq))
    if single:
        dq, = dq_rows

    # head-major: back to the caller's four axes (a reshape of nothing on
    # the token-major form)
    return dq.reshape(q.shape), dk.reshape(k.shape), dv.reshape(v.shape)


def _band_window(window: Optional[int], causal: bool, s: int):
    """The window the kernels are built with: None where it masks nothing
    (``window >= S`` is the causal kernel, traced as it always was)."""
    if not window or window >= s:
        return None
    if not causal:
        raise ValueError("a window needs causal attention")
    return int(window)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8, 9))
def flash_attention(q, k, v, causal: bool = False,
                    scale: Optional[float] = None,
                    block_q: Optional[int] = None,
                    block_k: Optional[int] = None,
                    interpret: Optional[bool] = None,
                    window: Optional[int] = None,
                    heads: Optional[int] = None):
    """Pallas blockwise attention; (B, H, S, D) -> (B, H, S, Dv), or with
    ``heads`` token-major (B, S, heads·D) -> (B, S, heads·Dv): the same
    kernels on the same grid, a head addressed as a lane block
    (``_operand_view``), gradients in the operands' form. With no
    blocks given each kernel takes ``flash_blocks``' tiles;
    a given (block_q, block_k) is used by all. ``window`` (causal):
    token t attends to s with t - window < s <= t; the kernels then
    run, fetch and visit the band's blocks alone."""
    out, _ = _flash_vjp_fwd(q, k, v, causal, scale, block_q, block_k,
                            interpret, window, heads)
    return out


FLASH_SAVED = ("flash_out", "flash_lse")   # the forward kernel's two results


def _flash_scale(q, scale, heads):
    if scale is not None:
        return scale
    return (q.shape[-1] // (heads or 1)) ** -0.5


def _flash_vjp_fwd(q, k, v, causal, scale, block_q, block_k, interpret,
                   window=None, heads=None):
    if interpret is None:
        interpret = _interpret_default()
    out, lse = _flash_fwd(q, k, v, _flash_scale(q, scale, heads), causal,
                          block_q, block_k, interpret,
                          window=_band_window(window, causal, q.shape[-2]),
                          heads=heads)
    # what the kernel wrote is what its backward needs: named, so that a
    # checkpoint whose policy asks for these keeps them and its replay runs
    # no second forward (core/remat.py); elsewhere a name is the identity
    # and lowers to nothing
    out, lse = map(checkpoint_name, (out, lse), FLASH_SAVED)
    return out, (q, k, v, out, lse)


def _flash_vjp_bwd(causal, scale, block_q, block_k, interpret, window, heads,
                   res, g):
    q, k, v, out, lse = res
    if interpret is None:
        interpret = _interpret_default()
    return _flash_bwd(q, k, v, out, lse, g, _flash_scale(q, scale, heads),
                      causal, block_q, block_k, interpret,
                      window=_band_window(window, causal, q.shape[-2]),
                      heads=heads)


flash_attention.defvjp(_flash_vjp_fwd, _flash_vjp_bwd)


TOKEN_MAJOR = "operands token-major (B,S,HxD)"


def flash_operand_form(s: int, d: int, dv: Optional[int] = None):
    """``(token_major, note)``: the form the flash kernels take the
    projections' results in — a function of the shape alone. Token-major
    ``(B, S, H·D)`` as they lie, where a head is whole vregs of lanes
    (``d`` and ``dv`` multiples of 128) and the sequence tiles: no split /
    merge transpose then stands between a projection and a kernel.
    Head-major ``(B, H, S, D)`` elsewhere: a lane block narrower than a
    vreg is not a legal tile."""
    for width in (d, d if dv is None else dv):
        if width % 128:
            # no ": " inside: the note is a stats.yaml leaf
            return False, (f"operands head-major (Dh {width}, not "
                           f"lane-aligned)")
    if pick_block(s) is None:
        return False, f"operands head-major (S {s} does not tile)"
    return True, TOKEN_MAJOR


def attention_route(s: int, sk: int, d: int, itemsize: int,
                    causal: bool = True, window: Optional[int] = None,
                    dv: Optional[int] = None,
                    token_major: Optional[bool] = None):
    """``(arm, note)`` for one attention geometry — THE routing decision:
    ``maybe_flash_attention`` takes it at trace time and ``Net`` logs it
    per ATTENTION layer at construction, for Q and K/V lengths ``s`` and
    ``sk``, head width ``d`` and operand ``itemsize``. ``"pallas_flash"`` when the
    sequence tiles cleanly (an aligned block divides it, self-attention
    lengths), the note then stating each kernel's ``block_q x block_k`` from
    ``flash_blocks`` and the live / visited programs of its grid per head
    (``fwd ..., bwd ...`` where the backward is the single sweep, ``fwd ...,
    dq ..., dkv ...`` where a head's dQ rows do not fit and it is the two;
    with a ``window`` below S: the band's, and the note says so) and the
    operands' form (``flash_operand_form``'s for a caller that holds the
    projections' results, an ATTENTION layer; ``token_major``: the form a
    caller handed them over in);
    ``"dense"`` on the CPU test mesh (the kernel would run in
    interpret-mode emulation — strictly slower than the dense op it
    replaces) and for shapes the kernel does not tile."""
    if _interpret_default():
        return "dense", "cpu backend"
    if sk != s:
        return "dense", "cross-attention lengths"
    if pick_block(s) is None:
        return "dense", f"no aligned block divides S={s}"
    window = _band_window(window, causal, s)
    # the backward that will run: one sweep where a head's dQ rows stay
    # resident, the two sweeps' kernels where they do not
    single = flash_blocks("bwd", s, d, itemsize, dv) is not None
    parts = []
    for kernel in ("fwd", "bwd") if single else ("fwd", "dq", "dkv"):
        bq, bk = flash_blocks(kernel, s, d, itemsize, dv)
        live, visited = flash_grid_programs(s, bq, bk, causal, window,
                                            over_q=kernel in ("dkv", "bwd"))
        parts.append(f"{kernel} {bq}x{bk} {live}/{visited}")
    by_rule, form = flash_operand_form(s, d, dv)
    if token_major not in (None, by_rule):
        form = TOKEN_MAJOR if token_major \
            else "operands head-major (the caller's)"
    return "pallas_flash", ", ".join(parts) + \
        "; block_q x block_k, live/visited programs a head" + \
        (f"; window {window}: the band's grid" if window else "") + \
        (f"; flash d {d}/{dv}" if dv not in (None, d) else "") + \
        f"; {form}"


def maybe_flash_attention(q, k, v, causal: bool = False,
                          scale: Optional[float] = None,
                          window: Optional[int] = None,
                          heads: Optional[int] = None) -> jax.Array:
    """Attention through :func:`attention_route`'s arm — and say which,
    once per shape. The training entry point for models/transformer.py
    (both blocks) and the Ulysses head-parallel path. Operands and result
    head-major ``(B, H, S, D)``, or with ``heads`` token-major
    ``(B, S, heads·D)``, the form ``flash_operand_form`` names for the
    caller that holds the projections' results (``rope_attention``)."""
    from .attention import attention
    s = q.shape[-2]
    d, dv = (t.shape[-1] // (heads or 1) for t in (q, v))
    arm, note = attention_route(s, k.shape[-2], d, q.dtype.itemsize, causal,
                                window, dv, token_major=heads is not None)
    where = f"[kernel_route] attention S={s} D={d}" \
        + (f"/{dv}" if dv != d else "")
    if arm == "pallas_flash":
        _log_route_once(f"{where}: pallas flash, {note}")
        return flash_attention(q, k, v, causal, scale, window=window,
                               heads=heads)
    _log_route_once(f"{where}: dense ({note})")
    if heads is not None:
        # the dense op is head-major: the CPU test mesh's arm
        b = q.shape[0]
        q, k, v = (t.reshape(b, s, heads, -1).swapaxes(1, 2)
                   for t in (q, k, v))
    out = attention(q, k, v, causal=causal, scale=scale, window=window)
    return out if heads is None else out.swapaxes(1, 2).reshape(b, s, -1)


# --------------------------------------------------------------------------- #
# Fused cross-channel LRN
# --------------------------------------------------------------------------- #

# About what one operand block holds: the backward's x, g and dx, each
# double-buffered, stay well under the 16 MiB of scoped VMEM a kernel gets.
_LRN_BLOCK_BYTES = 2 ** 20
# What one trip of the in-kernel loop works on, in f32 vregs of 8 x 128: a
# quarter of the 64 (x, g, the window sum and the powers are live together).
_LRN_PIECE_VREGS = 16


def _channel_axis(batch: int) -> int:
    """Where the channels of a pixels-leading CNN operand go — the ONE rule
    the LRN and the pooling kernels share, so that norm1 -> pool1 and
    norm2 -> pool2 hand over in one orientation. 1 = batch-minor ``(...,
    C, N)``: batch on the lanes, channels on the sublanes, where the
    per-device batch fills the lanes (a multiple of 128). 2 =
    channel-minor ``(..., N, C)`` below that. Those are the layouts the
    TPU compiler keeps a CNN's activations in at such a batch, so the
    logical transposes around a kernel are bitcasts in the compiled step."""
    return 1 if batch % 128 == 0 else 2


class LRNTileError(ValueError):
    """No VMEM-legal block exists for this channel count."""


def _lrn_tile(hw: int, channels: int, batch: int, itemsize: int,
              want: Optional[int] = None) -> tuple:
    """``(channel_axis, block, rows)``: how one LRN geometry is handed to
    the kernels — a function of the shape alone, as ``flash_blocks`` is.

    The operand is the logical transpose with the pixels leading.
    ``channel_axis`` 1 is ``(HW, C, N)``, batch-minor: batch on the lanes,
    channels on the sublanes, taken where the per-device batch fills the
    lanes (a multiple of 128). ``channel_axis`` 2 is ``(HW, N, C)``,
    channel-minor, below that. Those are the layouts the TPU compiler keeps
    a CNN's activations in on either side of the call at such a batch, so
    the transpose is a bitcast in the compiled step and no relayout copy
    stands at the kernel's boundary (tests/test_aot_tpu.py counts them).

    ``block`` = (T, second, minor) is the BlockSpec: T pixels (``want``, or
    about 1 MB an operand), the minor two dims the array's own or, for a
    batch too large for that, a lane- / sublane-exact tile of it — nothing
    is ever padded or cropped. ``rows`` is how many of the T pixels one
    trip of the in-kernel loop takes, so that its f32 temporaries stay in
    registers; T is a multiple of it.

    Raises :class:`LRNTileError` beyond ~2560 channels, where the f32
    temporaries of even a one-pixel, 128-wide slab outgrow the scoped
    VMEM: callers fall back to the XLA formulation (``lrn_fused`` does)."""
    # ~8 f32 temporaries of a (C, 128) slab (x, g, x^2, the window sum,
    # scale, two powers, dx) within 10 MB of the 16 MB scoped VMEM
    budget = 10 * 2 ** 20
    if channels * 128 * 4 * 8 > budget:
        raise LRNTileError(
            f"fused LRN: one pixel of {channels} channels x 128 needs "
            f"{channels * 128 * 4 * 8 >> 20} MB of f32 temporaries, over "
            f"{budget >> 20} MB of scoped VMEM; use the XLA formulation "
            f"for channel counts above ~{budget // (4 * 8 * 128)}")
    channel_axis = _channel_axis(batch)
    if channel_axis == 1:
        second, rows = channels, 1
        minor = max(128, min(batch, _LRN_BLOCK_BYTES
                             // (channels * itemsize) // 128 * 128))
    else:
        minor = channels
        lane_tiles = _cdiv(channels, 128)           # vregs a row of C fills
        # as many images as fill a piece, in whole bf16 sublane tiles
        fit = max(16, _LRN_PIECE_VREGS // lane_tiles * 8 // 16 * 16)
        second = min(batch, fit)
        rows = max(1, _LRN_PIECE_VREGS // (_cdiv(second, 8) * lane_tiles))
    if want is None:
        want = max(1, _LRN_BLOCK_BYTES // (second * minor * itemsize))
    t = max(rows, min(want, _cdiv(hw, rows) * rows) // rows * rows)
    return channel_axis, (t, second, minor), rows


def lrn_tile_feasible(hw: int, channels: int) -> bool:
    """Whether a VMEM-legal block exists (see ``_lrn_tile``)."""
    try:
        _lrn_tile(hw, channels, 128, 4)
        return True
    except LRNTileError:
        return False


def _lrn_window(v, size: int, pre: int, axis: int):
    """sum of v[c - pre : c - pre + size] over the channel ``axis``, zeros
    beyond its ends: ``size - 1`` rotations, each masked where it wrapped."""
    c = v.shape[axis]
    idx = lax.broadcasted_iota(jnp.int32, v.shape, axis)
    out = v
    for d in range(-pre, size - pre):
        if d == 0 or abs(d) >= c:
            continue
        shifted = pltpu.roll(v, (-d) % c, axis)       # shifted[c] = v[c + d]
        inside = (idx < c - d) if d > 0 else (idx >= -d)
        out = out + jnp.where(inside, shifted, 0.0)
    return out


def _lrn_kernel(*refs, local_size: int, alpha: float, beta: float, k: float,
                channel_axis: int, rows: int):
    """LRN forward (``x_ref, o_ref``) or the analytic Caffe backward
    (``x_ref, g_ref, o_ref``; lrn_layer.cpp CrossChannelBackward) on one
    (T, C, N) or (T, N, C) block — ``channel_axis`` 1 or 2, the
    orientation rule's (``_lrn_tile``), not the net's layout plan:

        y_i  = x_i * scale_i^-beta
        dx_i = g_i * scale_i^-beta
               - (2*alpha*beta/n) * x_i * sum_{j: i in win(j)} g_j*y_j/scale_j

    The transpose window is the forward window mirrored. The block is
    worked through ``rows`` pixels (and, batch-minor, 128 lanes) at a
    time, each piece loaded, finished and stored before the next, so no
    whole-block f32 temporary exists; one power a pass, the second as the
    first over ``scale``."""
    x_ref, o_ref = refs[0], refs[-1]
    g_ref = refs[1] if len(refs) == 3 else None
    t, _, minor = x_ref.shape
    # batch-minor: one pixel's (C, N) slab is taken 128 lanes at a time
    slabs = minor // 128 if channel_axis == 1 else 1
    pre = (local_size - 1) // 2
    post = local_size - pre - 1

    def piece(i, carry):
        if channel_axis == 1:
            at = (pl.ds(i // slabs * rows, rows), slice(None),
                  pl.ds(pl.multiple_of(i % slabs * 128, 128), 128))
        else:
            at = (pl.ds(i * rows, rows), slice(None), slice(None))
        x = x_ref[at].astype(jnp.float32)
        scale = k + (alpha / local_size) * _lrn_window(
            x * x, local_size, pre, channel_axis)
        # scale^-beta. Where the result is rounded to bf16 the power is
        # written out: on the v5e exp(log) is 1.7x faster than `**` and up
        # to 6e-5 off, a 64th of a bf16 ulp; an f32 result keeps `**` (1e-6)
        p = (scale ** -beta if o_ref.dtype == jnp.float32
             else jnp.exp(-beta * jnp.log(scale)))
        if g_ref is None:
            out = x * p
        else:
            g = g_ref[at].astype(jnp.float32)
            gp = g * p
            rsum = _lrn_window(gp * x / scale, local_size, post,
                               channel_axis)
            out = gp - (2.0 * alpha * beta / local_size) * x * rsum
        o_ref[at] = out.astype(o_ref.dtype)
        return carry

    lax.fori_loop(0, t // rows * slabs, piece, 0)


def _lrn_shape(x, layout: str):
    """(n, c, hw) of an activation in the net's logical ``layout``."""
    if layout == "NHWC":
        n, h, w, c = x.shape
    else:
        n, c, h, w = x.shape
    return n, c, h * w


def _lrn_call(operands, local_size: int, alpha: float, beta: float, k: float,
              tile: Optional[int], interpret: Optional[bool], layout: str):
    """One LRN kernel over ``operands`` — (x,) forward, (x, g) backward —
    in the orientation and blocks ``_lrn_tile`` gives their shape. The
    transposes in and out are logical: in the compiled step they are
    bitcasts wherever the rule's orientation is the compiler's own."""
    if interpret is None:
        interpret = _interpret_default()
    x = operands[0]
    n, c, hw = _lrn_shape(x, layout)
    channel_axis, block, rows = _lrn_tile(hw, c, n, x.dtype.itemsize, tile)
    # logical (N, C, HW) or (N, HW, C) -> pixels leading, channels where
    # the rule wants them
    axes = {("NCHW", 1): (2, 1, 0), ("NCHW", 2): (2, 0, 1),
            ("NHWC", 1): (1, 2, 0), ("NHWC", 2): (1, 0, 2)}[
                layout, channel_axis]
    flat = (n, hw, c) if layout == "NHWC" else (n, c, hw)
    ops3 = [a.reshape(flat).transpose(axes) for a in operands]
    spec = pl.BlockSpec(
        block, (lambda i, j: (i, j, 0)) if channel_axis == 2
        else (lambda i, j: (i, 0, j)), memory_space=pltpu.VMEM)
    out = pl.pallas_call(
        functools.partial(_lrn_kernel, local_size=local_size, alpha=alpha,
                          beta=beta, k=k, channel_axis=channel_axis,
                          rows=rows),
        name="lrn_fwd" if len(operands) == 1 else "lrn_bwd",
        out_shape=jax.ShapeDtypeStruct(ops3[0].shape, x.dtype),
        grid=(_cdiv(hw, block[0]), _cdiv(n, block[3 - channel_axis])),
        in_specs=[spec] * len(operands),
        out_specs=spec,
        interpret=interpret,
    )(*ops3)
    back = tuple(axes.index(a) for a in range(3))
    return out.transpose(back).reshape(x.shape)


@functools.partial(jax.custom_vjp, nondiff_argnums=(1, 2, 3, 4, 5, 6, 7))
def _lrn_fused_cvjp(x, local_size: int, alpha: float, beta: float,
                    k: float, tile: Optional[int],
                    interpret: Optional[bool], layout: str):
    return _lrn_call((x,), local_size, alpha, beta, k, tile, interpret,
                     layout)


def lrn_fused(x, local_size: int, alpha: float, beta: float, k: float = 1.0,
              tile: Optional[int] = None, interpret: Optional[bool] = None,
              layout: str = "NCHW"):
    """Fused LRN, forward and analytic backward. ``layout`` is the net's
    logical one — x is (N, C, H, W) under NCHW, (N, H, W, C) under NHWC;
    the orientation the kernel runs in follows the shape alone
    (``_lrn_tile``), and ``tile`` (pixels a block) is for tests.

    Channel counts with no VMEM-legal block (> ~2560, see ``_lrn_tile``)
    fall back to the XLA formulation — same numbers, no Mosaic
    scoped-VMEM blowup."""
    n, c, hw = _lrn_shape(x, layout)
    if not lrn_tile_feasible(hw, c):
        from .nn import lrn_across_channels
        return lrn_across_channels(x, local_size, alpha, beta, k, layout)
    return _lrn_fused_cvjp(x, local_size, alpha, beta, k, tile, interpret,
                           layout)


def lrn_fused_bwd(x, g, local_size: int, alpha: float, beta: float,
                  k: float = 1.0, tile: Optional[int] = None,
                  interpret: Optional[bool] = None, layout: str = "NCHW"):
    """Fused LRN backward: dx from (x, g) in one pass."""
    return _lrn_call((x, g), local_size, alpha, beta, k, tile, interpret,
                     layout)


def _lrn_fused_vjp_fwd(x, local_size, alpha, beta, k, tile, interpret,
                       layout):
    return _lrn_call((x,), local_size, alpha, beta, k, tile, interpret,
                     layout), x


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


def lrn_route(hw: int, channels: int, batch: Optional[int] = None,
              itemsize: int = 4):
    """``(arm, note)`` for one ACROSS_CHANNELS LRN geometry — THE routing
    decision: ``maybe_lrn_fused`` takes it at trace time and ``Net`` logs
    it per layer at construction. ``"pallas"`` on TPU (the fused fwd+bwd
    kernels), with the orientation and block the per-device ``batch``
    gives them in the note; ``"xla"`` on the CPU test mesh
    (interpret-mode emulation is strictly slower than the op it
    replaces) and for channel counts beyond the VMEM cap. Same
    numerics either way. ``POSEIDON_PALLAS_LRN`` forces an arm for A/B:
    ``0`` = XLA on TPU, ``1`` = the (interpreted) kernels on CPU."""
    import os
    env = os.environ.get("POSEIDON_PALLAS_LRN", "")
    if env == "0":
        return "xla", "POSEIDON_PALLAS_LRN=0"
    if _interpret_default() and env != "1":
        return "xla", "cpu backend"
    if not lrn_tile_feasible(hw, channels):
        return "xla", f"no VMEM-legal block for {channels} channels"
    if batch is None:
        return "pallas", ""
    channel_axis, block, _ = _lrn_tile(hw, channels, batch, itemsize)
    orient = ("batch-minor HWxCxN" if channel_axis == 1
              else "channel-minor HWxNxC")
    return "pallas", "%s, block %dx%dx%d" % (orient, *block)


def maybe_lrn_fused(x, local_size: int, alpha: float, beta: float,
                    k: float = 1.0, layout: str = "NCHW"):
    """ACROSS_CHANNELS LRN through :func:`lrn_route`'s arm. The kernels
    take their operand pixels-leading, batch-minor where the batch fills
    the lanes and channel-minor below — what the v5e's compiler holds the
    neighbouring convolutions' and pools' arrays in at such a batch — so
    the step has no relayout copy at their boundary (until PR 33 they took
    row-major (N, C, HW) and each call cost a 1-1.5 ms copy per operand
    at AlexNet's norm1)."""
    from .nn import lrn_across_channels
    n, c, hw = _lrn_shape(x, layout)
    if lrn_route(hw, c, n, x.dtype.itemsize)[0] == "pallas":
        return lrn_fused(x, local_size, alpha, beta, k, layout=layout)
    return lrn_across_channels(x, local_size, alpha, beta, k, layout)


# --------------------------------------------------------------------------- #
# Max-pool backward
# --------------------------------------------------------------------------- #

# What Mosaic may use of VMEM for one pool-backward program (128 MiB on the
# v5e) and what the block rule fills of it with the buffers it can count.
_POOL_VMEM_LIMIT = 64 * 2 ** 20
_POOL_VMEM_BUDGET = 40 * 2 ** 20
# f32 vregs of 8 x 128 one chunk of windows may hold per live array (the
# running max, its tap, the tap being read, the cotangent): 4 x 14 of 64
_POOL_CHUNK_VREGS = 14
# below this many bytes of dx a program the route keeps XLA's op
_POOL_MIN_BLOCK_BYTES = 256 * 1024


class PoolTileError(ValueError):
    """No VMEM-legal block exists for this pooling geometry, or none worth
    a kernel (``maxpool_bwd_note``); the message is the route's note."""


class _PoolPlan(NamedTuple):
    """How one max-pool backward geometry is handed to the kernel."""
    channel_axis: int       # _channel_axis: 1 (H, W, C, N), 2 (H, W, N, C)
    out: tuple              # (OH, OW)
    rows: int               # dx rows a program owns (TH, a multiple of sh)
    g_rows: int             # window rows whose first tap row is among them
    halo: tuple             # (g above, g below, x above, x below) in rows
    tile: tuple             # (second, minor) extent of every block
    chunk: int              # windows of a row worked on at a time
    chunks: int
    width: int              # columns of the staged row, pads included


def _pool_plan(h: int, w: int, c: int, n: int, kernel, stride, pad,
               itemsize: int, rows: Optional[int] = None) -> _PoolPlan:
    """The orientation, block and in-kernel chunk of one geometry — a
    function of the shape alone, as ``_lrn_tile`` is; ``rows`` (dx rows a
    block) is for tests.

    The operand is the logical transpose with the window axes leading,
    ``(H, W, C, N)`` or ``(H, W, N, C)`` by ``_channel_axis``. A block is
    ``rows`` rows of all of W by ONE register tile of the two minor dims
    (16 x 128 at two bytes, 8 x 128 at four; a dim no multiple of its tile
    is taken whole): the window never crosses the minor dims, so the f32
    staging a program needs is that of one tile. ``rows`` is the largest
    count, evened over the blocks, whose buffers fit ``_POOL_VMEM_BUDGET``.
    Raises :class:`PoolTileError` where not even ``stride`` rows do."""
    from .nn import pool_out_size
    (kh, kw), (sh, sw), (ph, pw) = kernel, stride, pad
    oh, ow = pool_out_size(h, kh, sh, ph), pool_out_size(w, kw, sw, pw)
    channel_axis = _channel_axis(n)
    second, minor = (c, n) if channel_axis == 1 else (n, c)
    sub = 32 // itemsize                      # sublanes of one packed tile
    ts = sub if second % sub == 0 else second
    tm = 128 if minor % 128 == 0 else minor
    # f32 vregs one position of a tile fills, and the bytes it takes staged
    # (f32) and as an operand block
    vregs = _cdiv(ts, 8) * _cdiv(tm, 128)
    staged, held = vregs * 4096, _cdiv(ts, sub) * _cdiv(tm, 128) * 4096
    chunks = _cdiv(ow, max(1, _POOL_CHUNK_VREGS // vregs))
    chunk = _cdiv(ow, chunks)
    width = max(pw + w, (chunks * chunk - 1) * sw + kw)
    # window rows above / below a block's own that reach into it, and the
    # input rows those reach beyond it
    g_up, g_dn = (kh - 1 - ph) // sh, _cdiv(ph, sh)
    x_up, x_dn = g_up * sh + ph, max(0, (g_dn - 1) * sh + kh - ph)

    def vmem(g_rows):
        th = g_rows * sh
        x_blk = (th + x_up + x_dn) * w * held
        g_blk = (g_rows + g_up + g_dn) * ow * held
        return (2 * (x_blk + g_blk + th * w * held)     # double-buffered
                # x staged as f32 and the f32 accumulator
                + 2 * (th + x_up + x_dn) * width * staged
                + (g_rows + g_up + g_dn) * chunks * chunk * staged)

    most = _cdiv(h, sh)
    if rows is not None:
        g_rows = max(1, min(most, rows // sh))
    else:
        g_rows = most
        while g_rows > 1 and vmem(g_rows) > _POOL_VMEM_BUDGET:
            g_rows -= 1
        # the same number of blocks, evened out
        g_rows = _cdiv(most, _cdiv(most, g_rows))
    if vmem(g_rows) > _POOL_VMEM_BUDGET:
        raise PoolTileError(
            f"no VMEM-legal block: {g_rows * sh} rows of {w} x ({ts} x {tm})"
            f" need {vmem(g_rows) >> 20} MB, over "
            f"{_POOL_VMEM_BUDGET >> 20} MB")
    return _PoolPlan(channel_axis, (oh, ow), g_rows * sh, g_rows,
                     (g_up, g_dn, x_up, x_dn), (ts, tm), chunk, chunks, width)


def _maxpool_bwd_kernel(x_ref, g_ref, dx_ref, xs, gs, acc, *, kernel, stride,
                        pad, shape, plan: _PoolPlan):
    """dx rows ``[i * rows, (i + 1) * rows)`` of one minor tile.

    ``x_ref`` / ``g_ref`` hold those rows' halo too: every window that
    touches the block and every input row those windows cover, rows beyond
    the array unspecified. They are staged once as f32 — input rows and
    columns beyond the array as -inf (Caffe's pad and the ceil-mode edge
    are masked taps, never a padded copy), window rows and columns beyond
    the output as 0 — so that every window of the block is the same code:
    per chunk of windows the max and the FIRST tap that reaches it
    (Caffe's ``>`` rule, as ``nn._pool_max_args``), then the cotangent
    added at that tap's position of the f32 accumulator, the taps of one
    row that land ``stride`` columns apart summed in registers first. Rows
    of the accumulator outside the block collect their windows' share and
    are dropped: the program that owns them computes it again. One
    rounding, on the way out.

    Written in ``lax`` primitives with the window's rows as (unrolled)
    loops: the step traces and lowers one of these per MAX pool on every
    start that the AOT store does not answer, and ``jnp``'s operators cost
    three times the seconds there."""
    (kh, kw), (sh, sw), (ph, pw) = kernel, stride, pad
    h, w = shape
    oh, ow = plan.out
    g_up, g_dn, x_up, x_dn = plan.halo
    th, gt, wc = plan.rows, plan.g_rows, plan.chunk
    f32, i32 = jnp.float32, jnp.int32
    i = pl.program_id(0)
    row0 = lax.sub(lax.mul(i, th), x_up)       # the array row of xs[0]
    win0 = lax.sub(lax.mul(i, gt), g_up)       # the window row of gs[0]
    tile = plan.tile
    # Mosaic pads a window at the array's far end only: the first block's
    # windows start at row 0, its rows above the array are these
    x_off, g_off = lax.max(lax.neg(row0), 0), lax.max(lax.neg(win0), 0)

    x_pad = [(c, n) for c, n in ((0, pw), (pw + w, plan.width - pw - w))
             if n]
    g_pad = gs.shape[1] - ow

    def outside(first, r, extent):
        at = lax.add(first, r)
        return lax.bitwise_or(lax.lt(at, 0), lax.ge(at, extent))

    def filled(n, value):
        return lax.full((n,) + tile, value, f32)

    def stage_x(r, carry):
        row = lax.convert_element_type(
            x_ref[lax.max(lax.sub(r, x_off), 0)], f32)
        xs[r, pl.ds(pw, w)] = lax.select(
            lax.broadcast(outside(row0, r, h), row.shape),
            filled(w, -jnp.inf), row)
        for c, n in x_pad:
            xs[r, pl.ds(c, n)] = filled(n, -jnp.inf)
        acc[r] = filled(plan.width, 0.0)
        return carry

    def stage_g(r, carry):
        row = lax.convert_element_type(
            g_ref[lax.max(lax.sub(r, g_off), 0)], f32)
        gs[r, pl.ds(0, ow)] = lax.select(
            lax.broadcast(outside(win0, r, oh), row.shape),
            filled(ow, 0.0), row)
        if g_pad:
            gs[r, pl.ds(ow, g_pad)] = filled(g_pad, 0.0)
        return carry

    lax.fori_loop(0, th + x_up + x_dn, stage_x, 0)
    lax.fori_loop(0, gt + g_up + g_dn, stage_g, 0)

    def cols(col, first, n):
        start = lax.add(lax.mul(col, sw), first)
        return pl.ds(start, n, stride=sw) if sw > 1 else pl.ds(start, n)

    def window_chunk(j, carry):
        r = lax.div(j, plan.chunks)
        col = lax.mul(lax.rem(j, plan.chunks), wc)
        top = lax.mul(r, sh)                   # the window row's first tap row
        shape_ = (wc,) + tile

        def tap(dh, dw):
            return lax.broadcast(lax.add(lax.mul(dh, kw), dw), shape_)

        def argmax_row(dh, best):
            mx, arg = best
            for dw in range(kw):
                v = xs[lax.add(top, dh), cols(col, dw, wc)]
                better = lax.gt(v, mx)
                mx = lax.select(better, v, mx)
                arg = lax.select(better, tap(dh, dw), arg)
            return mx, arg

        _, arg = lax.fori_loop(
            0, kh, argmax_row,
            (filled(wc, -jnp.inf), lax.full(shape_, 0, i32)), unroll=True)
        g = gs[r, pl.ds(col, wc)]
        zero = filled(wc, 0.0)

        def scatter_row(dh, carry_):
            # the taps of this row that land on one column class (dw = first,
            # first + stride, ...) reach the accumulator as one sum
            for first in range(min(sw, kw)):
                dws = range(first, kw, sw)
                sums = None
                for m, dw in enumerate(dws):
                    hit = lax.select(lax.eq(arg, tap(dh, dw)), g, zero)
                    # window c's tap lands m columns of the class further on
                    lead, trail = m, len(dws) - 1 - m
                    parts = ([filled(lead, 0.0)] * bool(lead) + [hit]
                             + [filled(trail, 0.0)] * bool(trail))
                    if len(parts) > 1:
                        hit = lax.concatenate(parts, 0)
                    sums = hit if sums is None else lax.add(sums, hit)
                at = (lax.add(top, dh), cols(col, first, wc + len(dws) - 1))
                acc[at] = lax.add(acc[at], sums)
            return carry_

        lax.fori_loop(0, kh, scatter_row, 0, unroll=True)
        return carry

    lax.fori_loop(0, (gt + g_up + g_dn) * plan.chunks, window_chunk, 0)

    def write(r, carry):
        dx_ref[r] = lax.convert_element_type(
            acc[lax.add(r, x_up), pl.ds(pw, w)], dx_ref.dtype)
        return carry

    lax.fori_loop(0, th, write, 0)


def maxpool_bwd(x, g, kernel, stride, pad, layout: str = "NCHW",
                rows: Optional[int] = None,
                interpret: Optional[bool] = None):
    """Max-pool backward in one pass: dx from the pool's input ``x`` and the
    cotangent ``g`` of its output, in ``x.dtype``, overlapping windows
    summed in f32 and rounded once, the first maximum of a window taking
    its cotangent. ``layout`` is the net's logical one; the orientation and
    block follow the shape alone (``_pool_plan``), ``rows`` is for tests.

    Each program owns a block of dx rows, so no two write one row; x is
    read once plus the windows' reach beyond a block, g once plus a row or
    two, dx written once. The transposes in and out are logical: bitcasts
    in the compiled step wherever the rule's orientation is the
    compiler's own.

    The call goes through an inlined ``jit``: layers of one geometry
    (GoogLeNet's inception_4b / 4c / 4d) trace the kernel once."""
    if interpret is None:
        interpret = _interpret_default()
    return _maxpool_bwd(x, g, tuple(kernel), tuple(stride), tuple(pad),
                        layout, rows, interpret)


@functools.partial(jax.jit, static_argnums=(2, 3, 4, 5, 6, 7), inline=True)
def _maxpool_bwd(x, g, kernel, stride, pad, layout, rows, interpret):
    if layout == "NHWC":
        (n, h, w, c), lead = x.shape, {1: (1, 2, 3, 0), 2: (1, 2, 0, 3)}
    else:
        (n, c, h, w), lead = x.shape, {1: (2, 3, 1, 0), 2: (2, 3, 0, 1)}
    plan = _pool_plan(h, w, c, n, kernel, stride, pad, x.dtype.itemsize,
                      rows)
    axes = lead[plan.channel_axis]
    g_up, g_dn, x_up, x_dn = plan.halo
    th, gt, (ts, tm) = plan.rows, plan.g_rows, plan.tile
    oh, ow = plan.out
    blocks = _cdiv(h, th)
    x4, g4 = x.transpose(axes), g.transpose(axes)

    def haloed(rows_, up, dn, extent, cols):
        # every dim by element, so that a block may start `up` rows above
        # its own; the rows beyond the array's end (as far as the last
        # block's window reaches) are never fetched
        size = rows_ + up + dn
        reach = max((blocks - 1) * rows_ - up, 0) + size
        return pl.BlockSpec(
            (pl.Element(size, (0, max(0, reach - extent))),
             pl.Element(cols), pl.Element(ts), pl.Element(tm)),
            lambda i, j, k: (lax.max(lax.sub(lax.mul(i, rows_), up), 0), 0,
                             lax.mul(j, ts),
                             lax.mul(k, tm) if tm == 128 else 0))

    staged = (th + x_up + x_dn, plan.width, ts, tm)
    out = pl.pallas_call(
        functools.partial(_maxpool_bwd_kernel, kernel=kernel, stride=stride,
                          pad=pad, shape=(h, w), plan=plan),
        name="maxpool_bwd",
        out_shape=jax.ShapeDtypeStruct(x4.shape, x.dtype),
        grid=(blocks, x4.shape[2] // ts, x4.shape[3] // tm),
        in_specs=[haloed(th, x_up, x_dn, h, w),
                  haloed(gt, g_up, g_dn, oh, ow)],
        out_specs=pl.BlockSpec((th, w, ts, tm),
                               lambda i, j, k: (i, 0, j, k)),
        scratch_shapes=[
            pltpu.VMEM(staged, jnp.float32),
            pltpu.VMEM((gt + g_up + g_dn, plan.chunks * plan.chunk, ts, tm),
                       jnp.float32),
            pltpu.VMEM(staged, jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",) * 3,
            vmem_limit_bytes=_POOL_VMEM_LIMIT),
        interpret=interpret,
    )(x4, g4)
    return out.transpose(tuple(axes.index(a) for a in range(4)))


def maxpool_bwd_note(shape, kernel, stride, pad, itemsize: int,
                     floor: bool = True) -> str:
    """How :func:`maxpool_bwd` would take the per-device logical
    ``(N, C, H, W)`` input ``shape`` — ``"batch-minor HxWxCxN, block
    14x55x16x128"``. Raises :class:`PoolTileError` with the reason where
    the kernel should not run (``nn.pool_bwd_route`` then keeps
    select-and-scatter): no VMEM-legal block, or a dx block under
    ``_POOL_MIN_BLOCK_BYTES`` — every program is then start-up and the
    kernel no faster than XLA's op (0.20 against 0.23 ms at GoogLeNet's
    7 x 7 x 832 x 128, the only such geometry of the benchmark; PERF.md,
    PR 35), while each call costs its lowering at every start (``floor``
    False, the forced arm's, leaves that second rule out)."""
    n, c, h, w = shape
    plan = _pool_plan(h, w, c, n, kernel, stride, pad, itemsize)
    block_bytes = math.prod((min(plan.rows, h), w, *plan.tile)) * itemsize
    if floor and block_bytes < _POOL_MIN_BLOCK_BYTES:
        raise PoolTileError("a dx block of %d KB is all per-program overhead"
                            % (block_bytes >> 10))
    orient = ("batch-minor HxWxCxN" if plan.channel_axis == 1
              else "channel-minor HxWxNxC")
    return "%s, block %dx%dx%dx%d" % (orient, plan.rows, w, *plan.tile)
