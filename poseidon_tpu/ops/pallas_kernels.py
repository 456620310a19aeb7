"""Pallas TPU kernels for the hot ops.

``flash_attention``: blockwise attention entirely in VMEM — never
materializes the (S, S) score matrix in HBM. Grid is (batch*heads,
query-blocks, key-blocks); the online-softmax recurrence (the same math as
ops/attention.py's BlockAcc) runs per (block_q, block_k) tile, sized by
``flash_blocks`` from the sequence length, the head width and the operand
itemsize. The backward pass is likewise Pallas and O(S) in HBM: the
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

_NT = (((1,), (1,)), ((), ()))      # a @ b.T: both contract their minor dim
_NN = (((1,), (0,)), ((), ()))      # a @ b


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
# f32 score-shaped (block_q, block_k) temporaries a program's body names:
# s and p forward; s, p, dp and ds in either backward sweep. An upper
# bound: Mosaic reuses their buffers (1024 x 1024 compiles for the v5e
# inside 16 MiB in all three kernels, not inside 8).
_SCORE_TEMPS = {"fwd": 2, "dq": 4, "dkv": 4}


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
                      itemsize: int) -> int:
    """Live VMEM of one program: the score-shaped f32 temporaries, the
    operand and result tiles (double-buffered by the pipeline) and the f32
    accumulators."""
    scores = _SCORE_TEMPS[kernel] * block_q * block_k * 4
    q_tiles, k_tiles, acc_rows = {
        "fwd": (2, 2, block_q),           # q, o | k, v | acc
        "dq": (3, 2, block_q),            # q, dO, dq | k, v | dq_acc
        "dkv": (2, 4, 2 * block_k),       # q, dO | k, v, dk, dv | two accs
    }[kernel]
    tiles = 2 * itemsize * d * (q_tiles * block_q + k_tiles * block_k)
    return scores + tiles + acc_rows * d * 4


def flash_blocks(kernel: str, s: int, d: int, itemsize: int):
    """``(block_q, block_k)`` for one of the three flash kernels (``"fwd"``,
    ``"dq"``, ``"dkv"``) — THE tile rule, a function of the sequence
    length, the head width and the operand itemsize alone: the largest
    aligned blocks dividing S whose live tiles fit the VMEM budget. A grid
    step costs about 0.35 us on the v5e whatever it computes, ten times
    the arithmetic of a 128 x 128 x 128 block, so a program is given as
    much work as VMEM holds (1024 x 1024 at S=4096, D=128); of two
    choices with equal work per program the wider K/V block wins (fewer
    rescalings of the running softmax state per score). Lengths no large
    block divides (S = 48, 136, ...) tile as they always did. None: no
    aligned block divides S."""
    best = None
    for bq in _BLOCK_LADDER:
        for bk in _BLOCK_LADDER:
            if s % bq or s % bk or _flash_vmem_bytes(
                    kernel, bq, bk, d, itemsize) > _FLASH_VMEM_BUDGET:
                continue
            if best is None or (bq * bk, bk) > (best[0] * best[1], best[1]):
                best = (bq, bk)
    return best


def _causal_mask(s, qi, kj, block_q, block_k, mode=None, transposed=False):
    """Self-attention: mask by absolute tile position. Chunked (ring) mode:
    ``mode`` is a traced scalar describing how the K/V chunk aligns with the
    Q rows' chunk — +1 chunk strictly past (all live), 0 diagonal (in-chunk
    triangle), -1 future (all masked). ``transposed``: ``s`` is the
    (block_k, block_q) tile of the dK/dV sweep, keys down the rows."""
    shape = (block_k, block_q) if transposed else (block_q, block_k)
    rows = qi * block_q + lax.broadcasted_iota(
        jnp.int32, shape, 1 if transposed else 0)
    cols = kj * block_k + lax.broadcasted_iota(
        jnp.int32, shape, 0 if transposed else 1)
    if mode is None:
        return jnp.where(rows >= cols, s, NEG_INF)
    live = (mode > 0) | ((mode == 0) & (rows >= cols))
    return jnp.where(live, s, NEG_INF)


def _on_live_blocks(update, causal: bool, chunk_mode: bool, qi, kj,
                    block_q: int, block_k: int) -> None:
    """Run ``update(masked)`` where block (qi, kj) has anything to add.
    Causal self-attention knows that from the grid position: a block wholly
    below the diagonal needs no mask, one the diagonal crosses is masked
    element by element, one wholly above it is skipped (its operands are
    not fetched either: ``_last_live_k`` / ``_first_live_q``). Ring
    attention's chunk alignment is a traced scalar: every block takes the
    masked path."""
    if not causal:
        update(False)
    elif chunk_mode:
        update(True)
    else:
        first_row, last_row = qi * block_q, qi * block_q + block_q - 1
        first_col, last_col = kj * block_k, kj * block_k + block_k - 1
        pl.when(last_col <= first_row)(lambda: update(False))
        pl.when((first_col <= last_row) & (last_col > first_row))(
            lambda: update(True))


def _last_live_k(qi, block_q: int, block_k: int):
    """Causal self-attention: the last K/V block a Q block attends to."""
    return (qi * block_q + block_q - 1) // block_k


def _first_live_q(kj, block_q: int, block_k: int):
    """Causal self-attention: the first Q block that attends to a K/V block."""
    return (kj * block_k) // block_q


def flash_grid_programs(s: int, block_q: int, block_k: int, causal: bool):
    """(live, visited) programs per head of one flash grid. A visited block
    that is not live runs no body and names the operand tile already
    resident, so it costs one empty grid step."""
    n_qb, n_kb = s // block_q, s // block_k
    if not causal:
        return n_qb * n_kb, n_qb * n_kb
    live = sum(min(n_kb, _last_live_k(qi, block_q, block_k) + 1)
               for qi in range(n_qb))
    return live, n_qb * n_kb


def _flash_fwd_kernel(*refs, scale: float, causal: bool, block_q: int,
                      block_k: int, n_kb: int, chunk_mode: bool, op_dtype):
    """Grid (bh, q_blocks, k_blocks); only one (block_q, d) Q tile and one
    (block_k, d) K/V tile are VMEM-resident at a time. The online-softmax
    state persists in f32 scratch across the innermost (k-block) grid
    dimension. Also emits the per-row logsumexp, which the O(S)-memory
    backward kernels consume (flash attention paper's L = m + log l).

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
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    def update(masked: bool):
        q = q_ref[0].astype(op_dtype)             # (block_q, d)
        k_blk = k_ref[0].astype(op_dtype)         # (block_k, d)
        v_blk = v_ref[0].astype(op_dtype)
        s = _dot(q, k_blk, _NT) * scale           # f32 from here on
        if masked:
            s = _causal_mask(s, qi, kj, block_q, block_k, mode)
        m_prev = m_ref[...]                       # (block_q, 1)
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)
        l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=-1, keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha + _dot(
            p.astype(op_dtype), v_blk, _NN)
        m_ref[...] = m_new

    _on_live_blocks(update, causal, chunk_mode, qi, kj, block_q, block_k)

    @pl.when(kj == n_kb - 1)
    def _finalize():
        l = l_ref[...]
        lsafe = jnp.where(l == 0, 1.0, l)
        o_ref[0] = (acc_ref[...] / lsafe).astype(o_ref.dtype)
        # this kernel and the dQ sweep want the row statistics as a column
        # beside their (block_q, block_k) scores: (bh, s, 1), whose
        # (block_q, 1) tile is legal at any block height. The dK/dV sweep
        # reads them as rows (_flash_bwd).
        lse_ref[0] = m_ref[...] + jnp.log(lsafe)


def _blocks_for(kernel: str, q, block_q, block_k):
    """The caller's blocks, or the tile rule's for this kernel."""
    s, d = q.shape[-2:]
    if block_q is None or block_k is None:
        blocks = flash_blocks(kernel, s, d, q.dtype.itemsize)
        if blocks is None:
            raise ValueError(f"no aligned block divides seq len {s}")
        return blocks
    block_q, block_k = min(block_q, s), min(block_k, s)
    if s % block_q or s % block_k:
        raise ValueError(f"seq len {s} must divide by blocks "
                         f"({block_q}, {block_k})")
    return block_q, block_k


def _kv_block_map(clamp: bool, block_q: int, block_k: int):
    """Index map of a K/V tile on the (bh, q_blocks, k_blocks) grid.
    ``clamp`` (causal self-attention): a block above the diagonal names the
    last live K/V tile again, which is resident, so no copy is issued."""
    if clamp:
        return lambda i, j, kk: (
            i, jnp.minimum(kk, _last_live_k(j, block_q, block_k)), 0)
    return lambda i, j, kk: (i, kk, 0)


def _compiler_params():
    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", "arbitrary"),
        vmem_limit_bytes=_FLASH_VMEM_LIMIT)


def _flash_fwd(q, k, v, scale: float, causal: bool, block_q: Optional[int],
               block_k: Optional[int], interpret: bool, mode=None):
    """mode (traced int32 scalar) selects chunked causal masking for ring
    attention; None = plain self-attention. Blocks of None: the tile rule's
    (``flash_blocks``)."""
    b, h, s, d = q.shape
    bh = b * h
    q3 = q.reshape(bh, s, d)
    k3 = k.reshape(bh, s, d)
    v3 = v.reshape(bh, s, d)
    block_q, block_k = _blocks_for("fwd", q, block_q, block_k)
    n_kb = s // block_k
    grid = (bh, s // block_q, n_kb)
    chunk = mode is not None
    kmap = _kv_block_map(causal and not chunk, block_q, block_k)
    qmap = lambda i, j, kk: (i, j, 0)
    in_specs = [
        pl.BlockSpec((1, block_q, d), qmap, memory_space=pltpu.VMEM),
        pl.BlockSpec((1, block_k, d), kmap, memory_space=pltpu.VMEM),
        pl.BlockSpec((1, block_k, d), kmap, memory_space=pltpu.VMEM),
    ]
    args = [q3, k3, v3]
    if chunk:
        in_specs.insert(0, pl.BlockSpec(memory_space=pltpu.SMEM))
        args.insert(0, jnp.asarray(mode, jnp.int32).reshape(1))
    out, lse = pl.pallas_call(
        functools.partial(_flash_fwd_kernel, scale=scale, causal=causal,
                          block_q=block_q, block_k=block_k, n_kb=n_kb,
                          chunk_mode=chunk,
                          op_dtype=_operand_dtype(q.dtype)),
        name="flash_fwd",
        out_shape=(jax.ShapeDtypeStruct((bh, s, d), q.dtype),
                   jax.ShapeDtypeStruct((bh, s, 1), jnp.float32)),
        grid=grid,
        in_specs=in_specs,
        out_specs=(
            pl.BlockSpec((1, block_q, d), qmap, memory_space=pltpu.VMEM),
            pl.BlockSpec((1, block_q, 1), qmap, memory_space=pltpu.VMEM),
        ),
        scratch_shapes=[
            pltpu.VMEM((block_q, d), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
        ],
        compiler_params=_compiler_params(),
        interpret=interpret,
    )(*args)
    return out.reshape(b, h, s, d), lse.reshape(b, h, s)


# --------------------------------------------------------------------------- #
# Flash attention backward: O(S) memory, two sweeps (flash attention paper)
# --------------------------------------------------------------------------- #

def _flash_dq_kernel(*refs, scale: float, causal: bool, block_q: int,
                     block_k: int, n_kb: int, chunk_mode: bool, op_dtype):
    """Grid (bh, q_blocks, k_blocks): accumulate dQ for one Q tile across all
    K/V tiles. p is recomputed from Q,K and the saved logsumexp — the score
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
    kj = pl.program_id(2)

    @pl.when(kj == 0)
    def _init():
        dq_acc[...] = jnp.zeros_like(dq_acc)

    def update(masked: bool):
        q = q_ref[0].astype(op_dtype)
        k_blk = k_ref[0].astype(op_dtype)
        v_blk = v_ref[0].astype(op_dtype)
        g = g_ref[0].astype(op_dtype)
        s = _dot(q, k_blk, _NT) * scale
        if masked:
            s = _causal_mask(s, qi, kj, block_q, block_k, mode)
        p = jnp.exp(s - lse_ref[0])               # masked entries -> 0
        dp = _dot(g, v_blk, _NT)
        ds = p * (dp - delta_ref[0])              # lse, delta: (block_q, 1)
        dq_acc[...] = dq_acc[...] + _dot(ds.astype(op_dtype), k_blk, _NN)

    _on_live_blocks(update, causal, chunk_mode, qi, kj, block_q, block_k)

    @pl.when(kj == n_kb - 1)
    def _finalize():
        dq_ref[0] = (dq_acc[...] * scale).astype(dq_ref.dtype)


def _flash_dkv_kernel(*refs, scale: float, causal: bool, block_q: int,
                      block_k: int, n_qb: int, chunk_mode: bool, op_dtype):
    """Grid (bh, k_blocks, q_blocks): accumulate dK and dV for one K/V tile
    across all Q tiles. The scores are computed TRANSPOSED, keys down the
    rows ((block_k, block_q) = K Q^T), so that P^T dO and dS^T Q are plain
    products of the tile as it lies and nothing score-shaped goes through a
    transpose; the row statistics then broadcast down the sublanes from a
    lane-dense (1, block_q) tile."""
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
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    def update(masked: bool):
        q = q_ref[0].astype(op_dtype)
        k_blk = k_ref[0].astype(op_dtype)
        v_blk = v_ref[0].astype(op_dtype)
        g = g_ref[0].astype(op_dtype)
        st = _dot(k_blk, q, _NT) * scale          # (block_k, block_q)
        if masked:
            st = _causal_mask(st, qi, kj, block_q, block_k, mode,
                              transposed=True)
        pt = jnp.exp(st - lse_ref[0, 0])          # lse, delta: (1, block_q)
        dv_acc[...] = dv_acc[...] + _dot(pt.astype(op_dtype), g, _NN)
        dpt = _dot(v_blk, g, _NT)
        dst = pt * (dpt - delta_ref[0, 0])
        dk_acc[...] = dk_acc[...] + _dot(dst.astype(op_dtype), q, _NN)

    _on_live_blocks(update, causal, chunk_mode, qi, kj, block_q, block_k)

    @pl.when(qi == n_qb - 1)
    def _finalize():
        dk_ref[0] = (dk_acc[...] * scale).astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[...].astype(dv_ref.dtype)


def _flash_bwd(q, k, v, out, lse, g, scale: float, causal: bool,
               block_q: Optional[int], block_k: Optional[int],
               interpret: bool, mode=None, delta=None):
    """mode, blocks: see _flash_fwd (the rule sizes the two sweeps apart).
    ``delta`` (rowsum(dO*O), global) may be passed in by the ring backward,
    whose O is the merged global output."""
    b, h, s, d = q.shape
    bh = b * h
    if delta is None:
        # delta_i = rowsum(dO * O): one O(S*D) elementwise pass, XLA-fused
        delta = jnp.sum(g.astype(jnp.float32) * out.astype(jnp.float32),
                        axis=-1)                   # (b, h, s)
    r3 = lambda x: x.reshape(bh, s, x.shape[-1])
    q3, k3, v3, g3 = r3(q), r3(k), r3(v), r3(g)
    chunk = mode is not None
    mode_arg = [jnp.asarray(mode, jnp.int32).reshape(1)] if chunk else []
    smem = [pl.BlockSpec(memory_space=pltpu.SMEM)] if chunk else []
    op_dtype = _operand_dtype(q.dtype)
    clamp = causal and not chunk
    vmem = functools.partial(pl.BlockSpec, memory_space=pltpu.VMEM)

    # dQ sweep: grid (bh, q_blocks, k_blocks), the forward's
    bq, bk = _blocks_for("dq", q, block_q, block_k)
    qmap = lambda i, j, kk: (i, j, 0)
    qspec = vmem((1, bq, d), qmap)
    kspec = vmem((1, bk, d), _kv_block_map(clamp, bq, bk))
    rowq = vmem((1, bq, 1), qmap)
    dq = pl.pallas_call(
        functools.partial(_flash_dq_kernel, scale=scale, causal=causal,
                          block_q=bq, block_k=bk, n_kb=s // bk,
                          chunk_mode=chunk, op_dtype=op_dtype),
        name="flash_bwd_dq",
        out_shape=jax.ShapeDtypeStruct((bh, s, d), q.dtype),
        grid=(bh, s // bq, s // bk),
        in_specs=smem + [qspec, kspec, kspec, qspec, rowq, rowq],
        out_specs=qspec,
        scratch_shapes=[pltpu.VMEM((bq, d), jnp.float32)],
        compiler_params=_compiler_params(),
        interpret=interpret,
    )(*mode_arg, q3, k3, v3, g3, lse.reshape(bh, s, 1),
      delta.reshape(bh, s, 1))

    # dK/dV sweep: swapped grid (bh, k_blocks, q_blocks); the row statistics
    # one lane-dense (1, block_q) row per Q block
    bq, bk = _blocks_for("dkv", q, block_q, block_k)
    n_qb = s // bq
    if clamp:
        # a Q block above the diagonal names the first live one again
        qblk = lambda j, kk: jnp.maximum(kk, _first_live_q(j, bq, bk))
    else:
        qblk = lambda j, kk: kk
    qspec_t = vmem((1, bq, d), lambda i, j, kk: (i, qblk(j, kk), 0))
    kspec_t = vmem((1, bk, d), lambda i, j, kk: (i, j, 0))
    rowq_t = vmem((1, 1, 1, bq), lambda i, j, kk: (i, qblk(j, kk), 0, 0))
    dk, dv = pl.pallas_call(
        functools.partial(_flash_dkv_kernel, scale=scale, causal=causal,
                          block_q=bq, block_k=bk, n_qb=n_qb,
                          chunk_mode=chunk, op_dtype=op_dtype),
        name="flash_bwd_dkv",
        out_shape=(jax.ShapeDtypeStruct((bh, s, d), k.dtype),
                   jax.ShapeDtypeStruct((bh, s, d), v.dtype)),
        grid=(bh, s // bk, n_qb),
        in_specs=smem + [qspec_t, kspec_t, kspec_t, qspec_t, rowq_t, rowq_t],
        out_specs=(kspec_t, kspec_t),
        scratch_shapes=[pltpu.VMEM((bk, d), jnp.float32),
                        pltpu.VMEM((bk, d), jnp.float32)],
        compiler_params=_compiler_params(),
        interpret=interpret,
    )(*mode_arg, q3, k3, v3, g3, lse.reshape(bh, n_qb, 1, bq),
      delta.reshape(bh, n_qb, 1, bq))

    rs = lambda x: x.reshape(b, h, s, d)
    return rs(dq), rs(dk), rs(dv)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def flash_attention(q, k, v, causal: bool = False,
                    scale: Optional[float] = None,
                    block_q: Optional[int] = None,
                    block_k: Optional[int] = None,
                    interpret: Optional[bool] = None):
    """Pallas blockwise attention; (B, H, S, D) -> (B, H, S, D). With no
    blocks given each of the three kernels takes ``flash_blocks``' tiles;
    a given (block_q, block_k) is used by all three."""
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


def attention_route(s: int, sk: int, d: int, itemsize: int,
                    causal: bool = True):
    """``(arm, note)`` for one attention geometry — THE routing decision:
    ``maybe_flash_attention`` takes it at trace time and ``Net`` logs it
    per ATTENTION layer at construction, for Q and K/V lengths ``s`` and
    ``sk``, head width ``d`` and operand ``itemsize``. ``"pallas_flash"`` when the
    sequence tiles cleanly (an aligned block divides it, self-attention
    lengths), the note then stating each kernel's ``block_q x block_k`` from
    ``flash_blocks`` and the live / visited programs of its grid per head;
    ``"dense"`` on the CPU test mesh (the kernel would run in
    interpret-mode emulation — strictly slower than the dense op it
    replaces) and for shapes the kernel does not tile."""
    if _interpret_default():
        return "dense", "cpu backend"
    if sk != s:
        return "dense", "cross-attention lengths"
    if pick_block(s) is None:
        return "dense", f"no aligned block divides S={s}"
    parts = []
    for kernel in ("fwd", "dq", "dkv"):
        bq, bk = flash_blocks(kernel, s, d, itemsize)
        live, visited = flash_grid_programs(s, bq, bk, causal)
        parts.append(f"{kernel} {bq}x{bk} {live}/{visited}")
    return "pallas_flash", ", ".join(parts) + \
        "; block_q x block_k, live/visited programs a head"


def maybe_flash_attention(q, k, v, causal: bool = False,
                          scale: Optional[float] = None) -> jax.Array:
    """Attention through :func:`attention_route`'s arm — and say which,
    once per shape. The training entry point for models/transformer.py
    (both blocks) and the Ulysses head-parallel path."""
    from .attention import attention
    s, d = q.shape[-2:]
    arm, note = attention_route(s, k.shape[-2], d, q.dtype.itemsize, causal)
    where = f"[kernel_route] attention S={s} D={d}"
    if arm == "pallas_flash":
        _log_route_once(f"{where}: pallas flash, {note}")
        return flash_attention(q, k, v, causal, scale)
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
