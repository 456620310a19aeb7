"""The chunked gated delta rule (``ops/kda.py`` has the mathematics) as two
Pallas (Mosaic) kernels: forward and backward, at head widths that are
multiples of 128 (``kda_refusal`` names what they do not take), each with
two arms for the chunk-local parts: a decay a CHANNEL (g (N, S, H d_k):
``_local`` / ``_local_pullback``, everything below about sub-blocks) and
ONE decay a head (g (N, S, H), Gated DeltaNet: ``_local_head`` /
``_local_head_pullback``, whose ratios are one number a token pair on the
(R, R) grid, so a and b are one product and one mask; ``ops/kda.kda_scan``
pads such a scan's 96 / 192 lanes to 128 / 256 on the way in; the arm earns
its lines against g broadcast over the key lanes into the per-channel arm,
which the same cell ran 2.5% slower end to end: PERF.md, PR 48). One program
owns ``m`` chunks of 64 tokens of ONE head of ONE sequence; the grid's last
axis walks
the sequence (forward from the first chunk, backward from the last) and the
head's f32 state — its gradient in the backward — stays in a VMEM scratch
from one program to the next. Everything a chunk computes without the state
(the cumulative sums, the decay ratios, A, B, the unit-lower inverse, w, u)
is made and used inside the program and never visits HBM; the backward
rebuilds it with the forward's own code and pulls back through it by hand.

Operands are read in place: the layer's blobs are (N, S, H d), so the block
(1, m 64, d) at (b, c, h) is a head's chunks with no transposed copy; beta
(N, S, H) comes as a (1, m 64, H) block of which the program takes its
column.

Inside a program the chunk-local work runs on TILES of two chunks (one where
a program has one chunk): 128 rows, with every (rows, rows) matrix block
diagonal a chunk, so that the products fill the MXU's 128 rows. The state is
kept transposed, (d_v, d_k): what scales it a chunk, exp(G_C), is a row over
d_k, and every product with it contracts the operands' minor dimensions.

The stability rule is ``ops/kda.py``'s: no ratio is formed with exp(-G).
Inside a sub-block of 16 tokens every ratio is exp(G_i - G_j) element by
element (offset by offset: row i against row i - o, o = 0..15, a sublane
rotation); between sub-blocks it is exp(G_i - r) exp(r - G_j) with r the
cumulative sum at the end of the sub-block before i's. The cumulative sum is
a product with a lower-triangular matrix of ones. The unit-lower inverse is
block forward substitution: row by row inside the 16 x 16 diagonal blocks,
on the vector unit with a tile's eight blocks side by side in the lanes
(the MXU waits on nothing else meanwhile: the kernels are bound by their
f32 products), then by doubling (the inverse of a 2s block from those of its
two s blocks: D - D N D, N the block's lower-left quarter) as products.
Every product here is f32 at HIGHEST precision.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .kda import SCAN_SAVED
from .pallas_kernels import _compiler_params

CHUNK = 64                 # tokens a chunk: the only one the kernels take
_SUB = 16                  # tokens a sub-block: ratios inside it elementwise
_HI = lax.Precision.HIGHEST
_F32 = jnp.float32


def kda_blocks(s: int, d_k: int, d_v: int, heads: int = 1,
               itemsize: int = 2) -> Optional[int]:
    """Chunks a program (m), of the shape and the VMEM budget alone: the
    largest of 4, 2, 1 that divides S / 64 and with which the backward
    kernel (the larger) fits half the kernels' VMEM limit: its blocks,
    double-buffered (q, k, v, d_o and dq, dk, dv in the operands' type; g, dg
    and the saved states f32; beta's H columns), the f32 parts of a tile of
    two chunks, and the state's gradient. None where the kernels cannot
    take the shape (``kda_refusal`` says which): 64 does not divide S, or
    a head's width is no multiple of 128."""
    if _shape_refusal(s, d_k, d_v):
        return None
    budget = _compiler_params().vmem_limit_bytes // 2
    for m in (4, 2, 1):
        if (s // CHUNK) % m:
            continue
        rows = m * CHUNK
        a_row = (4 * d_k + 3 * d_v) * itemsize + 8 * d_k + 4 * heads + 4
        blocks = 2 * (rows * a_row + m * d_k * d_v * 4)
        parts = min(rows, 2 * CHUNK) * (12 * d_k + 8 * d_v + 12 * 128) * 4
        if blocks + parts + 2 * d_k * d_v * 4 <= budget:
            return m
    return None


def _shape_refusal(s: int, d_k: int, d_v: int) -> str:
    if s % CHUNK:
        return f"{CHUNK} does not divide S={s}"
    if d_k % 128 or d_v % 128:
        return (f"heads of {d_k} / {d_v} are no lane blocks of (N, S, H d) "
                f"(multiples of 128)")
    return ""


def kda_refusal(s: int, d_k: int, d_v: int, heads: int = 1,
                itemsize: int = 2) -> str:
    """Why ``kda_blocks`` is None for a shape, '' where it is not: what
    ``kda_route`` tells ``Net``'s log when a scan runs ``chunked``."""
    return _shape_refusal(s, d_k, d_v) or (
        "" if kda_blocks(s, d_k, d_v, heads, itemsize)
        else "no block fits the kernels' VMEM budget")


def _mm(a, b, ca: int, cb: int):
    """a, b f32 2-D, contracted over a's dimension ``ca`` and b's ``cb``."""
    return lax.dot_general(a, b, (((ca,), (cb,)), ((), ())), precision=_HI,
                           preferred_element_type=_F32)


def _roll(x, shift: int):
    """Rows move down by ``shift`` (row i takes row i - shift's)."""
    return pltpu.roll(x, shift % x.shape[0], 0) if shift else x


def _grid_masks(r: int):
    row = lax.broadcasted_iota(jnp.int32, (r, r), 0)
    col = lax.broadcasted_iota(jnp.int32, (r, r), 1)
    same = (row // CHUNK) == (col // CHUNK)
    return row, col, same


def _off_blocks(r: int):
    """(lo, c0) of every sub-block row that has sub-blocks of its own chunk
    to its left."""
    return [(lo, lo // CHUNK * CHUNK) for lo in range(0, r, _SUB)
            if lo % CHUNK]


def _between(k, gc, lo: int, c0: int):
    """The two factors of the ratios between sub-block row ``lo`` and the
    sub-blocks of its chunk before it: down (16, d_k) = exp(G_i - r), eu
    (R, d_k) = exp(r - G_j) on rows c0 <= j < lo and 0 elsewhere."""
    rows = lax.broadcasted_iota(jnp.int32, (k.shape[0], 1), 0)
    ref = gc[lo - 1:lo]
    down = jnp.exp(gc[lo:lo + _SUB] - ref)
    eu = jnp.where((rows >= c0) & (rows < lo),
                   jnp.exp(jnp.minimum(ref - gc, 0.0)), 0.0)
    return down, eu


def _chunk_ends(gc):
    """(R, d_k): every row holds its chunk's last cumulative sum."""
    return jnp.concatenate(
        [jnp.broadcast_to(gc[c + CHUNK - 1:c + CHUNK], (CHUNK, gc.shape[1]))
         for c in range(0, gc.shape[0], CHUNK)], 0)


def _sub_block_inverses(low):
    """(I + L_b)^-1 of the eight 16 x 16 diagonal blocks of ``low`` (128,
    128) by forward substitution, row by row, on the vector unit, the eight
    blocks side by side in the lanes -> (128, 128), the inverses block
    diagonal. Row i of an inverse is e_i - L_b[i, :i] times the rows above
    it; the rows not yet made are the identity's, whose coefficients are
    zero."""
    r = low.shape[0]
    n = r // _SUB
    row, col, _ = _grid_masks(r)
    blocks = (row // _SUB) == (col // _SUB)
    turned = jnp.where(blocks, low, 0.0).T
    coef = turned[:_SUB]                 # (16, R): [j, 16 b + i] = L_b[i, j]
    for b in range(1, n):
        coef = coef + turned[b * _SUB:(b + 1) * _SUB]
    lane = lax.broadcasted_iota(jnp.int32, (_SUB, r), 1) % _SUB
    sub = lax.broadcasted_iota(jnp.int32, (_SUB, r), 0)
    t = jnp.where(lane == sub, 1.0, 0.0)     # (16, R): [j, 16 b + c]
    for i in range(1, _SUB):
        # L_b[i, j] in every lane of block b: lane i of each group of 16,
        # spread to the group's lanes above it and below it
        up = down = jnp.where(lane == i, coef, 0.0)
        for s in (1, 2, 4, 8):
            up = up + pltpu.roll(up, s, 1)
            down = down + pltpu.roll(down, r - s, 1)
        above = jnp.sum(jnp.where(lane >= i, up, down) * t, 0, keepdims=True)
        t = jnp.where(sub == i, t - above, t)
    return jnp.where(blocks, jnp.concatenate([t] * n, 0), 0.0)


def _local(q, k, v, g, beta):
    """A tile's chunk-local parts. q, k, g (R, d_k), v (R, d_v), beta (R, 1)
    f32, R = 64 or 128 rows (one or two chunks) -> a dict: gc, a (strictly
    lower), b (lower), t = (I + Diag(beta) a)^-1, all (R, R) block diagonal a
    chunk; w, u, kg, qg, krev, erev = exp(G_C - G); gl."""
    r, d_k = q.shape
    row, col, same = _grid_masks(r)
    gc = _mm(jnp.where(same & (col <= row), 1.0, 0.0), g, 1, 0)
    sub = row % _SUB
    a = jnp.zeros((r, r), _F32)
    b = jnp.zeros((r, r), _F32)
    for o in range(_SUB):
        x = _roll(k, o)
        if o:
            x = x * jnp.exp(jnp.minimum(gc - _roll(gc, o), 0.0))
        hit = (col == row - o) & (sub >= o)
        b = jnp.where(hit, jnp.sum(q * x, -1, keepdims=True), b)
        if o:
            a = jnp.where(hit, jnp.sum(k * x, -1, keepdims=True), a)
    rows_a = [jnp.zeros((_SUB, r), _F32)] * (r // _SUB)
    rows_b = list(rows_a)
    for lo, c0 in _off_blocks(r):
        down, eu = _between(k, gc, lo, c0)
        both = _mm(jnp.concatenate([k[lo:lo + _SUB] * down,
                                    q[lo:lo + _SUB] * down], 0),
                   k * eu, 1, 1)
        rows_a[lo // _SUB], rows_b[lo // _SUB] = both[:_SUB], both[_SUB:]
    a = a + jnp.concatenate(rows_a, 0)
    b = b + jnp.concatenate(rows_b, 0)
    return _solved(q, k, v, beta, gc, a, b)


def _unit_lower_inverse(low):
    """(I + low)^-1 of a tile's (R, R) strictly lower matrix, block diagonal
    a chunk: forward substitution inside the 16 x 16 diagonal blocks (one
    chunk a program: from the 2 x 2 blocks), then by doubling, the inverse
    of a 2s block from those of its two s blocks, as products."""
    r = low.shape[0]
    row, col, _ = _grid_masks(r)
    if r == 2 * CHUNK:
        t, s = _sub_block_inverses(low), _SUB
    else:       # one chunk a program: the doubling from the 2 x 2 blocks up
        t = jnp.where(row == col, 1.0, 0.0) \
            - jnp.where(row // 2 == col // 2, low, 0.0)
        s = 2
    while s < CHUNK:
        quarter = jnp.where((row // (2 * s) == col // (2 * s))
                            & (row // s != col // s), low, 0.0)
        t = t - _mm(t, _mm(quarter, t, 1, 0), 1, 0)
        s *= 2
    return t


def _solved(q, k, v, beta, gc, a, b, **more):
    """What follows a and b in a tile's chunk-local parts, for gc (R, d_k)
    (a decay a channel) or (R, 1) (one a head): the system solved against
    Diag(beta) [K exp(G) | V], and the decayed operands."""
    d_k = q.shape[1]
    t = _unit_lower_inverse(beta * a)
    decay = jnp.exp(gc)
    kg = k * decay
    solved = _mm(t, beta * jnp.concatenate([kg, v], 1), 1, 0)
    gl = _chunk_ends(gc)
    erev = jnp.exp(gl - gc)
    return dict(gc=gc, a=a, b=b, t=t, w=solved[:, :d_k], u=solved[:, d_k:],
                decay=decay, kg=kg, qg=q * decay, erev=erev, krev=k * erev,
                gl=gl, **more)


def _chunk_decay(gl, state):
    """exp(G_C) of one chunk as a row over the state's (d_v, d_k) lanes:
    gl (64, d_k) a channel or (64, 1) a head (the column spread along the
    lanes BEFORE its first row is taken: Mosaic broadcasts along one of
    sublanes and lanes at a time)."""
    return jnp.exp(jnp.broadcast_to(gl, (gl.shape[0], state.shape[1]))[:1])


def _as_row(column, row, col):
    """(R, 1) -> (1, R), on the vector unit (the diagonal of its
    broadcast)."""
    return jnp.sum(jnp.where(row == col, column, 0.0), 0, keepdims=True)


def _as_column(line, row, col):
    """(1, R) -> (R, 1)."""
    return jnp.sum(jnp.where(row == col, line, 0.0), 1, keepdims=True)


def _local_head(q, k, v, g, beta):
    """``_local`` with ONE decay a head: g (R, 1). Every ratio is one
    number a token pair, exp(G_i - G_j) with i >= j on the (R, R) grid (at
    most 1), so a and b are one product and one mask: no offsets, no
    sub-block factors. gc, decay, erev and gl come back (R, 1); ``ratio`` is
    kept for the pullback."""
    r = q.shape[0]
    row, col, same = _grid_masks(r)
    lower = same & (col <= row)
    gc = jnp.sum(jnp.where(lower, _as_row(g, row, col), 0.0), 1,
                 keepdims=True)
    ratio = jnp.where(lower, jnp.exp(jnp.minimum(
        gc - _as_row(gc, row, col), 0.0)), 0.0)
    both = _mm(jnp.concatenate([k, q], 0), k, 1, 1)          # (2R, R)
    a = jnp.where(col < row, both[:r] * ratio, 0.0)
    return _solved(q, k, v, beta, gc, a, both[r:] * ratio, ratio=ratio)


def _solved_pullback(k, v, beta, p, d_w, d_u, d_qg, d_krev):
    """The pullback through ``_solved``'s products, shared by both decays:
    -> (d_a, d_beta (R, 1), d_v, d_kg, and d_q, d_k, d_gc as far as the
    decayed operands give them; d_gc per channel, (R, d_k))."""
    d_k = k.shape[1]
    row, col, same = _grid_masks(k.shape[0])
    # through [w | u] = t rhs and t = (I + beta a)^-1
    kv = jnp.concatenate([p["kg"], v], 1)
    d_rhs = _mm(p["t"], jnp.concatenate([d_w, d_u], 1), 0, 0)
    # d t = d_solved rhs^T and d (I + beta a) = -t^T d_t t^T, as one product
    d_m = jnp.where(same & (col < row),
                    -_mm(d_rhs, jnp.concatenate([p["w"], p["u"]], 1), 1, 1),
                    0.0)
    d_beta = jnp.sum(d_m * p["a"], -1, keepdims=True) \
        + jnp.sum(d_rhs * kv, -1, keepdims=True)
    d_kg = beta * d_rhs[:, :d_k]
    # through kg, qg, krev
    back = d_krev * p["krev"]
    return (beta * d_m, d_beta, beta * d_rhs[:, d_k:],
            d_qg * p["decay"], d_kg * p["decay"] + d_krev * p["erev"],
            d_kg * p["kg"] + d_qg * p["qg"] - back, back)


def _local_head_pullback(q, k, v, beta, p, d_w, d_u, d_qg, d_b, d_krev,
                         d_gl):
    """The pullback of ``_local_head`` by hand; d_gl (R, 1) = every row its
    chunk's d last * last -> dq, dk, dv and dg, dbeta as ROWS (1, R)."""
    r = q.shape[0]
    row, col, same = _grid_masks(r)
    d_a, d_beta, d_v, d_q, d_k_, d_gc, back = _solved_pullback(
        k, v, beta, p, d_w, d_u, d_qg, d_krev)
    d_gc = jnp.sum(d_gc, 1, keepdims=True)                   # (R, 1)
    back = jnp.sum(back, 1, keepdims=True)
    ends = jnp.concatenate(
        [jnp.broadcast_to(jnp.sum(back[c:c + CHUNK], 0, keepdims=True),
                          (CHUNK, 1)) for c in range(0, r, CHUNK)], 0)
    rows = lax.broadcasted_iota(jnp.int32, (r, 1), 0)
    d_gc = d_gc + jnp.where(rows % CHUNK == CHUNK - 1, ends + d_gl, 0.0)
    # through a = (k k^T) ratio, b = (q k^T) ratio, ratio = exp(G_i - G_j)
    moved = d_a * p["a"] + d_b * p["b"]
    d_gc = d_gc + jnp.sum(moved, 1, keepdims=True) \
        - _as_column(jnp.sum(moved, 0, keepdims=True), row, col)
    d_scores = jnp.concatenate([d_a, d_b], 0) * jnp.concatenate(
        [p["ratio"], p["ratio"]], 0)                         # (2R, R)
    along = _mm(d_scores, k, 1, 0)                           # rows i
    d_k_ = d_k_ + along[:r] \
        + _mm(d_scores, jnp.concatenate([k, q], 0), 0, 0)    # rows j
    d_q = d_q + along[r:]
    # through the cumulative sum: a reverse cumulative sum inside the chunk
    d_g = jnp.sum(jnp.where(same & (col <= row), d_gc, 0.0), 0,
                  keepdims=True)
    return d_q, d_k_, d_v, d_g, _as_row(d_beta, row, col)


def _local_pullback(q, k, v, beta, p, d_w, d_u, d_qg, d_b, d_krev, d_gl):
    """The pullback of ``_local`` by hand. ``p``: its parts; d_w, d_qg,
    d_krev (R, d_k), d_u (R, d_v), d_b (R, R) masked to b's support, d_gl
    (R, d_k) = every row its chunk's d last * last -> dq, dk, dv, dg and
    dbeta as a ROW (1, R)."""
    r, d_k = q.shape
    row, col, same = _grid_masks(r)
    gc = p["gc"]
    d_a, d_beta, d_v, d_q, d_k_, d_gc, back = _solved_pullback(
        k, v, beta, p, d_w, d_u, d_qg, d_krev)
    d_beta = _as_row(d_beta, row, col)
    ends = jnp.concatenate(
        [jnp.broadcast_to(jnp.sum(back[c:c + CHUNK], 0, keepdims=True),
                          (CHUNK, d_k)) for c in range(0, r, CHUNK)], 0)
    rows = lax.broadcasted_iota(jnp.int32, (r, 1), 0)
    d_gc = d_gc + jnp.where(rows % CHUNK == CHUNK - 1, ends + d_gl, 0.0)
    # through a and b inside a sub-block, offset by offset
    sub = row % _SUB
    for o in range(_SUB):
        hit = (col == row - o) & (sub >= o)
        db = jnp.sum(jnp.where(hit, d_b, 0.0), -1, keepdims=True)
        kr = _roll(k, o)
        if not o:
            d_q = d_q + db * k
            d_k_ = d_k_ + db * q
            continue
        da = jnp.sum(jnp.where(hit, d_a, 0.0), -1, keepdims=True)
        e = jnp.exp(jnp.minimum(gc - _roll(gc, o), 0.0))
        x = kr * e
        d_q = d_q + db * x
        ce = (da * k + db * q) * e
        tg = ce * kr
        d_k_ = d_k_ + da * x + _roll(ce, -o)
        d_gc = d_gc + tg - _roll(tg, -o)
    # through a and b between sub-blocks
    zero = jnp.zeros((_SUB, d_k), _F32)
    dk_rows = [zero] * (r // _SUB)
    dq_rows, dg_rows = list(dk_rows), list(dk_rows)
    for lo, c0 in _off_blocks(r):
        down, eu = _between(k, gc, lo, c0)
        up = k * eu
        kd, qd = k[lo:lo + _SUB] * down, q[lo:lo + _SUB] * down
        dab = jnp.concatenate([d_a[lo:lo + _SUB], d_b[lo:lo + _SUB]], 0)
        d_down = _mm(dab, up, 1, 0)
        d_kd, d_qd = d_down[:_SUB], d_down[_SUB:]
        d_up = _mm(dab, jnp.concatenate([kd, qd], 0), 0, 0)
        i = lo // _SUB
        dk_rows[i], dq_rows[i] = d_kd * down, d_qd * down
        dg_rows[i] = d_kd * kd + d_qd * qd
        d_k_ = d_k_ + d_up * eu
        d_gc = d_gc - d_up * up
    d_k_ = d_k_ + jnp.concatenate(dk_rows, 0)
    d_q = d_q + jnp.concatenate(dq_rows, 0)
    d_gc = d_gc + jnp.concatenate(dg_rows, 0)
    # through the cumulative sum: a reverse cumulative sum inside the chunk
    d_g = _mm(jnp.where(same & (col <= row), 1.0, 0.0), d_gc, 0, 0)
    return d_q, d_k_, d_v, d_g, d_beta


def _column(beta_ref, rows):
    """This program's head's column of the (1, rows, H) beta block."""
    beta = beta_ref[0].astype(_F32)
    lane = lax.broadcasted_iota(jnp.int32, beta.shape, 1)
    return jnp.sum(jnp.where(lane == pl.program_id(1), beta, 0.0), -1,
                   keepdims=True)


def _tile_operands(refs, rs):
    return tuple(ref[0, rs, :].astype(_F32) for ref in refs)


def _tile_parts(refs, g_ref, g_head, beta, rs):
    """A tile's operands and chunk-local parts: ``g_head`` None for a decay
    a channel (g read from its (1, rows, d_k) block), this head's (rows, 1)
    column for one decay a head."""
    q, k, v = _tile_operands(refs, rs)
    if g_head is None:
        g, = _tile_operands((g_ref,), rs)
        return q, k, v, _local(q, k, v, g, beta[rs])
    return q, k, v, _local_head(q, k, v, g_head[rs], beta[rs])


def _fwd_kernel(q_ref, k_ref, v_ref, g_ref, beta_ref, o_ref, *rest,
                scale: float, m: int, tile: int, per_head: bool):
    """Grid (B, H, S / (m 64)), the last axis in order. ``rest``: the saved
    states' block (1, 1, m, d_v, d_k) where the backward wants them, then
    the state scratch (d_v, d_k). ``per_head``: g comes as beta does, a
    (1, rows, H) block of which the program takes its column."""
    state = rest[-1]
    saved = rest[0] if len(rest) == 2 else None

    @pl.when(pl.program_id(2) == 0)
    def _():
        state[...] = jnp.zeros_like(state)

    r = tile * CHUNK
    beta = _column(beta_ref, m * CHUNK)
    g_head = _column(g_ref, m * CHUNK) if per_head else None
    for ti in range(m // tile):
        rs = slice(ti * r, (ti + 1) * r)
        _, _, _, p = _tile_parts((q_ref, k_ref, v_ref), g_ref, g_head, beta,
                                 rs)
        st = state[...]
        wrote, read = [], []
        for c in range(tile):
            cs = slice(c * CHUNK, (c + 1) * CHUNK)
            if saved is not None:
                saved[0, 0, ti * tile + c] = st
            both = _mm(jnp.concatenate([p["w"][cs], p["qg"][cs]], 0),
                       st, 1, 1)
            wrote.append(p["u"][cs] - both[:CHUNK])
            read.append(both[CHUNK:])
            st = st * _chunk_decay(p["gl"][cs], st) \
                + _mm(wrote[-1], p["krev"][cs], 0, 0)
        state[...] = st
        o = scale * (jnp.concatenate(read, 0)
                     + _mm(p["b"], jnp.concatenate(wrote, 0), 1, 0))
        o_ref[0, rs, :] = o.astype(o_ref.dtype)


def _bwd_kernel(q_ref, k_ref, v_ref, g_ref, beta_ref, saved, do_ref,
                dq_ref, dk_ref, dv_ref, dg_ref, dbeta_ref, d_state, *,
                scale: float, m: int, tile: int, per_head: bool):
    """The same grid walked from the sequence's last program (the index
    maps), and inside a program from its last chunk; ``d_state`` (d_v, d_k)
    carries the state's gradient."""
    @pl.when(pl.program_id(2) == 0)
    def _():
        d_state[...] = jnp.zeros_like(d_state)

    r = tile * CHUNK
    beta = _column(beta_ref, m * CHUNK)
    g_head = _column(g_ref, m * CHUNK) if per_head else None
    row, col, same = _grid_masks(r)
    for ti in reversed(range(m // tile)):
        rs = slice(ti * r, (ti + 1) * r)
        q, k, v, p = _tile_parts((q_ref, k_ref, v_ref), g_ref, g_head, beta,
                                 rs)
        d_out = scale * do_ref[0, rs, :].astype(_F32)
        chunks = [slice(c * CHUNK, (c + 1) * CHUNK) for c in range(tile)]
        states = [saved[0, 0, ti * tile + c] for c in range(tile)]
        wrote = jnp.concatenate(
            [p["u"][cs] - _mm(p["w"][cs], st, 1, 1)
             for cs, st in zip(chunks, states)], 0)
        d_wrote_b = _mm(p["b"], d_out, 0, 0)
        d_b = jnp.where(same & (col <= row), _mm(d_out, wrote, 1, 1), 0.0)
        ds = d_state[...]
        d_w, d_u, d_qg, d_krev, d_gl = ([None] * tile for _ in range(5))
        for c in reversed(range(tile)):
            cs, st = chunks[c], states[c]
            d_u[c] = d_wrote_b[cs] + _mm(p["krev"][cs], ds, 1, 1)
            both = _mm(jnp.concatenate([d_u[c], d_out[cs]], 0), st, 1, 0)
            d_w[c], d_qg[c] = -both[:CHUNK], both[CHUNK:]
            d_krev[c] = _mm(wrote[cs], ds, 1, 0)
            last = jnp.exp(p["gl"][cs][:1])
            # d last: a channel's (1, d_k), or the whole state's (1, 1)
            d_last = jnp.sum(st * ds, 0, keepdims=True)
            if per_head:
                d_last = jnp.sum(d_last, 1, keepdims=True)
            d_gl[c] = jnp.broadcast_to(d_last * last,
                                       (CHUNK, last.shape[1]))
            ds = ds * _chunk_decay(p["gl"][cs], ds) + _mm(
                jnp.concatenate([d_out[cs], d_u[c]], 0),
                jnp.concatenate([p["qg"][cs], -p["w"][cs]], 0), 0, 0)
        d_state[...] = ds
        cat = lambda xs: jnp.concatenate(xs, 0)
        pullback = _local_head_pullback if per_head else _local_pullback
        d_q, d_k, d_v, d_g, d_beta = pullback(
            q, k, v, beta[rs], p, cat(d_w), cat(d_u), cat(d_qg), d_b,
            cat(d_krev), cat(d_gl))
        dq_ref[0, rs, :] = d_q.astype(dq_ref.dtype)
        dk_ref[0, rs, :] = d_k.astype(dk_ref.dtype)
        dv_ref[0, rs, :] = d_v.astype(dv_ref.dtype)
        if per_head:            # a row, as d beta
            dg_ref[0, 0, 0, :, rs] = d_g
        else:
            dg_ref[0, rs, :] = d_g.astype(dg_ref.dtype)
        dbeta_ref[0, 0, 0, :, rs] = d_beta


def _geometry(q, v, m):
    b, s, h, d_k = q.shape
    d_v = v.shape[-1]
    rows = m * CHUNK
    grid = (b, h, s // rows)
    tile = 2 if m % 2 == 0 else 1
    return b, s, h, d_k, d_v, rows, grid, tile


def _specs(rows, d_k, d_v, h, n, reverse: bool):
    """Block specs of (q | k | g), v, beta and the saved states; ``reverse``
    walks the sequence from its last program."""
    at = (lambda c: n - 1 - c) if reverse else (lambda c: c)
    vmem = pltpu.VMEM
    head = lambda d: pl.BlockSpec((1, rows, d), lambda b, i, c: (b, at(c), i),
                                  memory_space=vmem)
    beta = pl.BlockSpec((1, rows, h), lambda b, i, c: (b, at(c), 0),
                        memory_space=vmem)
    saved = pl.BlockSpec((1, 1, rows // CHUNK, d_v, d_k),
                         lambda b, i, c: (b, i, at(c), 0, 0),
                         memory_space=vmem)
    return head(d_k), head(d_v), beta, saved


def _forward(q, k, v, g, beta, scale, m, interpret, with_states: bool):
    """-> o (B, S, H, d_v) in v's type and, ``with_states``, the f32 state
    at every chunk's start (B, H, S / 64, d_v, d_k) (transposed)."""
    b, s, h, d_k, d_v, rows, grid, tile = _geometry(q, v, m)
    flat = lambda x: x.reshape(b, s, -1)
    qs, vs, bs, ss = _specs(rows, d_k, d_v, h, grid[2], False)
    per_head = g.ndim == 3
    out_shape = [jax.ShapeDtypeStruct((b, s, h * d_v), v.dtype)]
    out_specs = [vs]
    if with_states:
        out_shape.append(jax.ShapeDtypeStruct(
            (b, h, s // CHUNK, d_v, d_k), _F32))
        out_specs.append(ss)
    out = pl.pallas_call(
        functools.partial(_fwd_kernel, scale=scale, m=m, tile=tile,
                          per_head=per_head),
        name="gdn_scan_fwd" if per_head else "kda_scan_fwd", grid=grid,
        in_specs=[qs, qs, vs, bs if per_head else qs, bs],
        out_specs=out_specs, out_shape=out_shape,
        scratch_shapes=[pltpu.VMEM((d_v, d_k), _F32)],
        compiler_params=_compiler_params(), interpret=interpret,
    )(flat(q), flat(k), flat(v), flat(g), beta)
    o = out[0].reshape(b, s, h, d_v)
    return (o, out[1]) if with_states else o


def _backward(q, k, v, g, beta, states, d_o, scale, m, interpret):
    b, s, h, d_k, d_v, rows, grid, tile = _geometry(q, v, m)
    n = grid[2]
    flat = lambda x: x.reshape(b, s, -1)
    qs, vs, bs, ss = _specs(rows, d_k, d_v, h, n, True)
    per_head = g.ndim == 3
    line = pl.BlockSpec(            # one number a token, written as a row
        (1, 1, 1, 1, rows), lambda b_, i, c: (b_, i, n - 1 - c, 0, 0),
        memory_space=pltpu.VMEM)
    lines = jax.ShapeDtypeStruct((b, h, n, 1, rows), _F32)
    d_q, d_k_, d_v_, d_g, d_beta = pl.pallas_call(
        functools.partial(_bwd_kernel, scale=scale, m=m, tile=tile,
                          per_head=per_head),
        name="gdn_scan_bwd" if per_head else "kda_scan_bwd", grid=grid,
        in_specs=[qs, qs, vs, bs if per_head else qs, bs, ss, vs],
        out_specs=[qs, qs, vs, line if per_head else qs, line],
        out_shape=[jax.ShapeDtypeStruct((b, s, h * d_k), q.dtype),
                   jax.ShapeDtypeStruct((b, s, h * d_k), k.dtype),
                   jax.ShapeDtypeStruct((b, s, h * d_v), v.dtype),
                   lines if per_head
                   else jax.ShapeDtypeStruct((b, s, h * d_k), g.dtype),
                   lines],
        scratch_shapes=[pltpu.VMEM((d_v, d_k), _F32)],
        compiler_params=_compiler_params(), interpret=interpret,
    )(flat(q), flat(k), flat(v), flat(g), beta, states, flat(d_o))
    token_major = lambda x, like: x.reshape(b, h, s).swapaxes(1, 2).astype(
        like.dtype)
    return (d_q.reshape(q.shape), d_k_.reshape(k.shape),
            d_v_.reshape(v.shape),
            token_major(d_g, g) if per_head else d_g.reshape(g.shape),
            token_major(d_beta, beta))


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7))
def kda_scan_pallas(q, k, v, g, beta, scale: float, m: int,
                    interpret: bool):
    """``ops/kda.kda_scan``'s Pallas arm: q, k (B, S, H, d_k), g the same
    (a decay a channel) or (B, S, H) f32 (one a head), v (B, S, H, d_v),
    beta (B, S, H) -> o (B, S, H, d_v) in v's type; ``m`` chunks of 64 a
    program (``kda_blocks``). Both widths multiples of 128 (``kda_scan``
    pads a per-head scan's lanes to them)."""
    return _forward(q, k, v, g, beta, scale, m, interpret, False)


def _vjp_fwd(q, k, v, g, beta, scale, m, interpret):
    o, states = _forward(q, k, v, g, beta, scale, m, interpret, True)
    # named as the flash kernel's results are (pallas_kernels._flash_vjp_fwd)
    o, states = map(checkpoint_name, (o, states), SCAN_SAVED)
    return o, (q, k, v, g, beta, states)


def _vjp_bwd(scale, m, interpret, res, d_o):
    return _backward(*res, d_o, scale, m, interpret)


kda_scan_pallas.defvjp(_vjp_fwd, _vjp_bwd)
