"""Mamba-2's chunked selective scan (``ops/ssd.py`` has the mathematics) as
two Pallas (Mosaic) kernels, ``ssd_scan_fwd`` and ``ssd_scan_bwd``.

The grid is (sequence, chunk, program of heads), the programs innermost: a
program owns ONE chunk of Q tokens (256, or 128 where 256 does not divide S)
of up to eight heads, and the f32 states of ALL heads — their gradients in
the backward — stay in one VMEM scratch (H P, N) from a sequence's first
chunk to its last (the backward walks the chunks from the last: the index
maps). What no head owns is made once a program and serves the heads it
holds: the (Q, Q) grid C B^T, and in the backward its gradient, summed over
the program's heads before the two products that turn it into d C and d B.
d B and d C are (S, N) blocks that the programs of one chunk ACCUMULATE in
VMEM (the block's index does not move while the program axis runs), so no
(H, S, N) array ever exists.

B and C may come in G groups, (B, S, G N) along the lanes, head h reading
group h // (H / G) (``n_groups``; Nemotron-H has 8 for 64 heads, Granite
1). A program's heads all lie in ONE group (``ssd_refusal`` names the
shapes where they would not), so the same kernels serve: the B / C block's
index map picks the program's group, and the d B / d C blocks accumulate
over the H / (G hp) programs that share a group and start afresh at the
first program of the next. With G = 1 the index maps and the program are
what they were.

Operands are read in place: x is (B, S, H P) as the convolution leaves it,
and a program's heads are ``W`` = heads x P of its lanes. Inside, the work
runs a LANE BLOCK of 128 at a time: two heads of 64 side by side (one of
128, four of 32). Each head brings its own (Q, Q) mask of ratios
exp(L_i - L_j) (i >= j: every exponent <= 0) and its own dt; the products
with x and d y take the lane block whole, the other head's lanes zeroed, so
nothing is sliced or padded inside a vreg. The state of a lane block is
(128, N): its heads' (P, N) states stacked.

What is one number a token and head (dt, the cumulative sums L made outside,
and the two gradients d dt, d L) travels as ROWS, (B, H / hp, hp, S) with a
program's block (hp, Q): a head's row is a sublane, its column form is the
diagonal of its broadcast (vector unit). Lane-segment sums (a head's 64 of
a block's 128 lanes) are products with a 0 / 1 selector of 8 rows.

A product runs the MXU passes its operands' types need (``_mm``, by dtype
alone). f32 x f32 at HIGHEST is six bf16 passes: each operand's high,
middle and low eight bits (``split3``), the six pairings that matter. x,
B, C and d y stay in the type they ARRIVE in up to the product, and a bf16
operand has no middle and no low part, so beside it an f32 operand (a
state, the masked grid, d y times its decay: made here) needs its three
parts once each, THREE passes, and two bf16 operands ONE: the same terms
HIGHEST would sum, less those that multiply by zero. Nothing is rounded
that did not arrive rounded; f32 operands run the six passes as before.
``_products`` is the account, ``mxu_passes`` its sum, and the route's note
says what share of six passes a product a run's kernels make.
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

_HI = lax.Precision.HIGHEST
_F32 = jnp.float32
_BF16 = jnp.bfloat16
_LANES = 128


def heads_a_program(heads: int, p: int) -> int:
    """Heads a program holds: eight (a row tile of the per-head numbers),
    or all of them, rounded up to whole lane blocks, where there are
    fewer."""
    per_block = max(1, _LANES // p)
    return min(8, -(-heads // per_block) * per_block)


def padded_heads(heads: int, p: int) -> int:
    """The heads the kernels run: whole programs (zero heads appended: a
    zero x and a zero dt write nothing and read nothing)."""
    hp = heads_a_program(heads, p)
    return -(-heads // hp) * hp


def ssd_refusal(s: int, heads: int, p: int, n: int, groups: int = 1) -> str:
    """Why the kernels do not take a shape, '' where they do. ``groups``:
    the groups of B / C the heads share (a program's heads read ONE)."""
    if s % 128:
        return f"neither 256 nor 128 divides S={s}"
    if p not in (16, 32, 64, 128):
        return f"heads of {p} are no whole part of a lane block of 128"
    if n % _LANES:
        return f"a state of {n} a head is no multiple of 128 lanes"
    if groups > 1 and (heads % groups
                       or (heads // groups) % heads_a_program(heads, p)):
        return (f"{heads} heads in {groups} groups of B / C are no whole "
                f"programs of {heads_a_program(heads, p)} heads a group: a "
                f"program would span two groups")
    if padded_heads(heads, p) * p * n * 4 \
            > _compiler_params().vmem_limit_bytes // 4:
        return "the heads' states do not fit the kernels' VMEM budget"
    return ""


def ssd_blocks(s: int, heads: int, p: int, n: int,
               groups: int = 1) -> Optional[int]:
    """Tokens a chunk (Q) of the kernels, None where they refuse the shape
    (``ssd_refusal`` says why)."""
    if ssd_refusal(s, heads, p, n, groups):
        return None
    return 256 if s % 256 == 0 else 128


def split3(a):
    """f32 ``a`` as three bf16 parts, hi + mid + lo == a: 8 + 8 + 8 bits of
    its 24, each subtraction exact. Bit for bit down to |a| = 2^-102; a
    smaller number's parts under 2^-126, the smallest normal one, are
    flushed (here as in the MXU), which is all the sum is then short by."""
    hi = a.astype(_BF16)
    rest = a - hi.astype(_F32)
    mid = rest.astype(_BF16)
    return hi, mid, (rest - mid.astype(_F32)).astype(_BF16)


def _mm(a, b, ca: int, cb: int):
    """a, b 2-D, contracted over a's dimension ``ca`` and b's ``cb`` into
    f32, in the MXU passes their types need (the module's account): bf16
    operands go as they are, an f32 one beside a bf16 one as its
    ``split3`` parts, the smallest first; two f32 operands (anything not
    bf16 is cast) at HIGHEST."""
    dot = functools.partial(
        lax.dot_general, dimension_numbers=(((ca,), (cb,)), ((), ())),
        preferred_element_type=_F32)
    short_a, short_b = a.dtype == _BF16, b.dtype == _BF16
    if short_a and short_b:
        return dot(a, b)
    if short_a or short_b:
        hi, mid, lo = (dot(a, part) if short_a else dot(part, b)
                       for part in split3(b if short_a else a))
        return lo + mid + hi
    return dot(a.astype(_F32), b.astype(_F32), precision=_HI)


def _products(q: int, p: int, n: int, heads: int):
    """-> (forward's, backward's) Q-row products of one program (a chunk of
    ``heads`` heads): (product, FLOP, how many of its two operands ARRIVE,
    in the operands' type; the others are f32 made here). A lane block's
    products take its 128 lanes whole, a head's the other heads' lanes
    zeroed. Not listed: the backward's 8-row selector products (two a lane
    block, one a head), under 3% of its MACs, which follow the same rule."""
    blocks = heads * p // _LANES
    grid, state, head = 2 * q * q * n, 2 * q * n * _LANES, 2 * q * q * _LANES
    return (
        (("C B^T", grid, 2),
         ("C . state", blocks * state, 1),
         ("(grid ratio dt) . x_j", heads * head, 1),
         ("(x write)^T . B", blocks * state, 1)),
        (("C B^T", grid, 2),
         ("(d y decay) . state, (x write) . d state", 2 * blocks * state, 0),
         ("B . d state, C . state, (d y decay)^T . C", 3 * blocks * state, 1),
         ("d y_j . x^T", heads * head, 2),
         ("(grid ratio dt)^T . d y_j", heads * head, 1),
         ("d grid . B, d grid^T . C", 2 * grid, 1)))


def mxu_passes(q: int, p: int, n: int, heads: int, dtype):
    """-> (forward, backward) FLOP x MXU passes of one program's products
    with x, B, C and d y in ``dtype``: ``_mm``'s rule over ``_products``
    (six, three or one pass by the operands that arrive, where they arrive
    in bf16; six whatever arrives in f32)."""
    passes = (6, 3, 1) if jnp.dtype(dtype) == _BF16 else (6, 6, 6)
    return tuple(sum(flop * passes[arrive] for _, flop, arrive in rows)
                 for rows in _products(q, p, n, heads))


def _masks(q: int):
    row = lax.broadcasted_iota(jnp.int32, (q, q), 0)
    col = lax.broadcasted_iota(jnp.int32, (q, q), 1)
    return col <= row, row == col, col == q - 1


def _head(lr_ref, dtr_ref, h: int, lower, eye, end):
    """One head's chunk-local numbers from its two rows: L and dt as rows
    (1, Q) and columns (Q, 1), the (Q, Q) ratios exp(L_i - L_j) on i >= j
    (0 above), exp(L_Q) (1, 1), and exp(L_Q - L_j) as a column. A column
    is a reduction of the row's broadcast over the (Q, Q) grid (the
    diagonal; the last lane for L_Q in every row): Mosaic moves no single
    lane to the sublanes."""
    column = lambda line, at: jnp.sum(jnp.where(at, line, 0.0), 1,
                                      keepdims=True)
    l_row, dt_row = lr_ref[0, 0, h:h + 1, :], dtr_ref[0, 0, h:h + 1, :]
    l_col, dt_col = column(l_row, eye), column(dt_row, eye)
    ratio = jnp.where(lower, jnp.exp(jnp.minimum(l_col - l_row, 0.0)), 0.0)
    l_end = column(l_row, end)                   # (Q, 1), every row L_Q
    return dict(l_col=l_col, dt_row=dt_row, dt_col=dt_col, ratio=ratio,
                keep=jnp.exp(l_end[:1]), rev=jnp.exp(l_end - l_col))


def _block_heads(lr_ref, dtr_ref, k: int, p: int, q: int, masks):
    """The heads of lane block ``k`` and what they spread over its lanes and
    its state's rows: ``decay`` exp(L_i), ``rev`` exp(L_Q - L_j) and
    ``write`` = rev dt, each (Q, 128) with a head's column in its own lanes;
    ``keep`` exp(L_Q) (128, 1) in its own rows."""
    per_block = max(1, _LANES // p)
    lane = lax.broadcasted_iota(jnp.int32, (q, _LANES), 1) // p
    sub = lax.broadcasted_iota(jnp.int32, (_LANES, 1), 0) // p
    heads = [_head(lr_ref, dtr_ref, k * per_block + j, *masks)
             for j in range(per_block)]
    spread = lambda cols: functools.reduce(
        lambda acc, jc: jnp.where(lane == jc[0], jc[1], acc),
        enumerate(cols), jnp.zeros((q, _LANES), _F32))
    keep = functools.reduce(
        lambda acc, jh: jnp.where(sub == jh[0], jh[1]["keep"], acc),
        enumerate(heads), jnp.zeros((_LANES, 1), _F32))
    return heads, lane, sub, dict(
        decay=spread([jnp.exp(h["l_col"]) for h in heads]),
        rev=spread([h["rev"] for h in heads]),
        write=spread([h["rev"] * h["dt_col"] for h in heads]), keep=keep)


def _state_rows(width: int):
    """-> k -> the rows of the all-heads scratch that lane block ``k`` of
    this program's group owns (read ``program_id`` outside any ``when``)."""
    base = pl.multiple_of(pl.program_id(2) * width, _LANES)
    return lambda k: pl.ds(base + k * _LANES, _LANES)


def _fwd_kernel(x_ref, dtr_ref, lr_ref, b_ref, c_ref, d_ref, y_ref, *rest,
                p: int):
    """Grid (B, S / Q, H / hp), the chunks in order. ``rest``: the saved
    states' block (1, 1, W, N) where the backward wants them, then the
    all-heads state scratch (H P, N)."""
    state = rest[-1]
    saved = rest[0] if len(rest) == 2 else None
    q, width = x_ref.shape[1:]
    rows = _state_rows(width)

    @pl.when(pl.program_id(1) == 0)
    def _():
        for k in range(width // _LANES):
            state[rows(k), :] = jnp.zeros(
                (_LANES, state.shape[1]), _F32)

    masks = _masks(q)
    bs, cs = b_ref[0], c_ref[0]             # as they arrived, like x below
    grid = _mm(cs, bs, 1, 1)
    for k in range(width // _LANES):
        ls = slice(k * _LANES, (k + 1) * _LANES)
        heads, lane, _, spread = _block_heads(lr_ref, dtr_ref, k, p, q,
                                              masks)
        xs = x_ref[0, :, ls]
        xb = xs.astype(_F32)
        st = state[rows(k), :]
        y = spread["decay"] * _mm(cs, st, 1, 1) + d_ref[:, ls] * xb
        for j, h in enumerate(heads):
            y = y + _mm(grid * h["ratio"] * h["dt_row"],
                        jnp.where(lane == j, xs, 0.0), 1, 0)
        y_ref[0, :, ls] = y.astype(y_ref.dtype)
        if saved is not None:
            saved[0, 0, ls, :] = st
        state[rows(k), :] = spread["keep"] * st \
            + _mm(xb * spread["write"], bs, 0, 0)


def _bwd_kernel(x_ref, dtr_ref, lr_ref, b_ref, c_ref, d_ref, saved, dy_ref,
                dx_ref, db_ref, dc_ref, ddt_ref, dl_ref, dd_ref, d_state, *,
                p: int, share: int = 0):
    """The same grid with the chunks from the last (the index maps);
    ``d_state`` (H P, N) carries the states' gradients. d dt and d L leave
    as rows, d D as a chunk's partial sums over tokens. ``share``: how many
    programs in a row read one group of B / C and sum into one d B / d C
    block (0: all of them, one group)."""
    q, width = x_ref.shape[1:]
    rows = _state_rows(width)

    @pl.when(pl.program_id(1) == 0)
    def _():
        for k in range(width // _LANES):
            d_state[rows(k), :] = jnp.zeros(
                (_LANES, d_state.shape[1]), _F32)

    masks = _masks(q)
    bs, cs = b_ref[0], c_ref[0]         # as they arrived, like x and d y
    grid = _mm(cs, bs, 1, 1)
    # the two 0 / 1 selectors, exact in the operands' type
    ones = jnp.ones((8, q), x_ref.dtype)
    last = lax.broadcasted_iota(jnp.int32, (1, q), 1) == q - 1
    # row r of ``pick`` picks head r's lanes of a lane block
    pick = jnp.where(
        lax.broadcasted_iota(jnp.int32, (8, _LANES), 1) // p
        == lax.broadcasted_iota(jnp.int32, (8, _LANES), 0), 1.0, 0.0
        ).astype(x_ref.dtype)
    d_grid = jnp.zeros((q, q), _F32)
    d_b = jnp.zeros(bs.shape, _F32)
    d_c = jnp.zeros(cs.shape, _F32)
    per_block = max(1, _LANES // p)
    for k in range(width // _LANES):
        ls = slice(k * _LANES, (k + 1) * _LANES)
        heads, lane, sub, spread = _block_heads(lr_ref, dtr_ref, k, p, q,
                                                masks)
        xs, dys = x_ref[0, :, ls], dy_ref[0, :, ls]
        xb, dyb = xs.astype(_F32), dys.astype(_F32)
        st = saved[0, 0, ls, :]
        ds = d_state[rows(k), :]
        dyd = dyb * spread["decay"]
        d_c = d_c + _mm(dyd, st, 1, 0)
        wrote = _mm(bs, ds, 1, 1)                    # d (x rev dt), (Q, 128)
        d_b = d_b + _mm(xb * spread["write"], ds, 1, 0)
        # a head's sums over its own lanes, as rows (8, Q)
        seg_l = _mm(pick, dyd * _mm(cs, st, 1, 1), 1, 1)
        seg_u = _mm(pick, wrote * xb * spread["rev"], 1, 1)
        carried = jnp.sum(ds * st, 1, keepdims=True)             # (128, 1)
        dxb = d_ref[:, ls] * dyb + wrote * spread["write"]
        for j, h in enumerate(heads):
            dyj = jnp.where(lane == j, dys, 0.0)
            dm = _mm(dyj, xs, 1, 1) * h["ratio"]     # d (E G dt) on i >= j
            scaled = dm * h["dt_row"]
            d_grid = d_grid + scaled
            dxb = dxb + _mm(grid * h["ratio"] * h["dt_row"], dyj, 0, 0)
            moved = scaled * grid                    # d M * M
            u_row = seg_u[j:j + 1]
            v_row = u_row * h["dt_row"]
            d_end = h["keep"] * jnp.sum(jnp.where(sub == j, carried, 0.0),
                                        0, keepdims=True)
            head = k * per_block + j
            ddt_ref[0, 0, head:head + 1, :] = \
                jnp.sum(dm * grid, 0, keepdims=True) + u_row
            dl_ref[0, 0, head:head + 1, :] = \
                _mm(ones, moved, 1, 1)[:1] \
                - jnp.sum(moved, 0, keepdims=True) + seg_l[j:j + 1] - v_row \
                + jnp.where(last, jnp.sum(v_row, 1, keepdims=True) + d_end,
                            0.0)
        dx_ref[0, :, ls] = dxb.astype(dx_ref.dtype)
        dd_ref[0, 0, :, ls] = jnp.sum(dyb * xb, 0, keepdims=True)
        d_state[rows(k), :] = spread["keep"] * ds \
            + _mm(dyd, cs, 0, 0)
    d_c = d_c + _mm(d_grid, bs, 1, 0)
    d_b = d_b + _mm(d_grid, cs, 0, 0)

    if share == 1:              # a group a program: nothing to sum over
        db_ref[0] = d_b
        dc_ref[0] = d_c
        return
    # this program's place among those that share its d B / d C block
    at = lambda: pl.program_id(2) % share if share else pl.program_id(2)

    @pl.when(at() == 0)
    def _():
        db_ref[0] = d_b
        dc_ref[0] = d_c

    @pl.when(at() != 0)
    def _():
        db_ref[0] += d_b
        dc_ref[0] += d_c


def _params():
    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "arbitrary", "arbitrary"),
        vmem_limit_bytes=_compiler_params().vmem_limit_bytes)


def _rows(t, hp: int):
    """(B, S, H) -> (B, H / hp, hp, S) f32: a head's numbers as a row."""
    b, s, h = t.shape
    return t.astype(_F32).swapaxes(1, 2).reshape(b, h // hp, hp, s)


def _columns(t, heads: int):
    """(B, H / hp, hp, S) -> (B, S, heads): back, the zero heads dropped."""
    b, g, hp, s = t.shape
    return t.reshape(b, g * hp, s).swapaxes(1, 2)[..., :heads]


def _operands(x, dt, a, d, q):
    """The kernels' operands of x (B, S, H, P), dt, a (B, S, H), d (H,):
    x (B, S, H' P), dt and the cumulative sums of a inside a chunk as rows,
    d spread over each head's lanes (1, H' P); H' the padded heads."""
    b, s, h, p = x.shape
    hp, wide = heads_a_program(h, p), padded_heads(h, p)
    pad = lambda t, axis: jnp.pad(
        t, [(0, wide - h if i == axis else 0) for i in range(t.ndim)])
    cum = jnp.cumsum(a.astype(_F32).reshape(b, s // q, q, h), 2)
    return (pad(x, 2).reshape(b, s, wide * p),
            _rows(pad(dt, 2), hp), _rows(pad(cum.reshape(b, s, h), 2), hp),
            jnp.repeat(pad(d.astype(_F32), 0), p)[None], hp, wide)


def _specs(q, width, n, hp, n_chunks, reverse: bool, share: int = 0):
    """``share``: the programs in a row that read one group of B / C (0:
    one group for all, block 0 of the lanes)."""
    at = (lambda c: n_chunks - 1 - c) if reverse else (lambda c: c)
    group = (lambda g: g // share) if share else (lambda g: 0)
    vmem = pltpu.VMEM
    lanes = pl.BlockSpec((1, q, width), lambda b, c, g: (b, at(c), g),
                         memory_space=vmem)
    rows = pl.BlockSpec((1, 1, hp, q), lambda b, c, g: (b, g, 0, at(c)),
                        memory_space=vmem)
    shared = pl.BlockSpec((1, q, n), lambda b, c, g: (b, at(c), group(g)),
                          memory_space=vmem)
    skip = pl.BlockSpec((1, width), lambda b, c, g: (0, g),
                        memory_space=vmem)
    saved = pl.BlockSpec((1, 1, width, n),
                         lambda b, c, g: (b, at(c), g, 0), memory_space=vmem)
    return lanes, rows, shared, skip, saved, at


def _groups(b_, c_, h: int, hp: int):
    """b, c (B, S, N) or (B, S, G, N) -> (both laid (B, S, G N) along the
    lanes, N, the programs in a row that share a group: 0 with one group)."""
    n = b_.shape[-1]
    if b_.ndim == 3:
        return b_, c_, n, 0
    flat = lambda t: t.reshape(t.shape[:2] + (-1,))
    return flat(b_), flat(c_), n, h // (b_.shape[2] * hp)


def _forward(x, dt, a, b_, c_, d, q, interpret, with_states: bool):
    """-> y (B, S, H, P) in x's type and, ``with_states``, the f32 state at
    every chunk's start (B, S / Q, H' P, N)."""
    b, s, h, p = x.shape
    xs, dtr, lr, skip_d, hp, wide = _operands(x, dt, a, d, q)
    b_, c_, n, share = _groups(b_, c_, h, hp)
    width, n_chunks = hp * p, s // q
    lanes, rows, shared, skip, saved, _ = _specs(q, width, n, hp, n_chunks,
                                                 False, share)
    out_shape = [jax.ShapeDtypeStruct(xs.shape, x.dtype)]
    out_specs = [lanes]
    if with_states:
        out_shape.append(jax.ShapeDtypeStruct(
            (b, n_chunks, wide * p, n), _F32))
        out_specs.append(saved)
    out = pl.pallas_call(
        functools.partial(_fwd_kernel, p=p), name="ssd_scan_fwd",
        grid=(b, n_chunks, wide // hp),
        in_specs=[lanes, rows, rows, shared, shared, skip],
        out_specs=out_specs, out_shape=out_shape,
        scratch_shapes=[pltpu.VMEM((wide * p, n), _F32)],
        compiler_params=_params(), interpret=interpret,
    )(xs, dtr, lr, b_, c_, skip_d)
    y = out[0].reshape(b, s, wide, p)[:, :, :h]
    return (y, out[1]) if with_states else y


def _backward(x, dt, a, b_, c_, d, states, d_y, q, interpret):
    b, s, h, p = x.shape
    xs, dtr, lr, skip_d, hp, wide = _operands(x, dt, a, d, q)
    b_shape, c_shape = b_.shape, c_.shape
    b_, c_, n, share = _groups(b_, c_, h, hp)
    d_ys = jnp.pad(d_y, [(0, 0), (0, 0), (0, wide - h), (0, 0)]).reshape(
        xs.shape)
    width, n_chunks = hp * p, s // q
    lanes, rows, shared, skip, saved, at = _specs(q, width, n, hp, n_chunks,
                                                  True, share)
    partial = pl.BlockSpec((1, 1, 1, width),
                           lambda b, c, g: (b, at(c), 0, g),
                           memory_space=pltpu.VMEM)
    f32 = lambda shape: jax.ShapeDtypeStruct(shape, _F32)
    d_x, d_b, d_c, d_dt, d_l, d_d = pl.pallas_call(
        functools.partial(_bwd_kernel, p=p, share=share),
        name="ssd_scan_bwd",
        grid=(b, n_chunks, wide // hp),
        in_specs=[lanes, rows, rows, shared, shared, skip, saved, lanes],
        out_specs=[lanes, shared, shared, rows, rows, partial],
        out_shape=[jax.ShapeDtypeStruct(xs.shape, x.dtype),
                   f32(b_.shape), f32(c_.shape), f32(dtr.shape),
                   f32(lr.shape), f32((b, n_chunks, 1, wide * p))],
        scratch_shapes=[pltpu.VMEM((wide * p, n), _F32)],
        compiler_params=_params(), interpret=interpret,
    )(xs, dtr, lr, b_, c_, skip_d, states, d_ys)
    # through the cumulative sum: a reverse cumulative sum inside the chunk
    d_l = _columns(d_l, h).reshape(b, n_chunks, q, h)
    d_a = jnp.flip(jnp.cumsum(jnp.flip(d_l, 2), 2), 2).reshape(b, s, h)
    d_d = jnp.sum(d_d, (0, 1, 2)).reshape(wide, p).sum(1)[:h]
    return (d_x.reshape(b, s, wide, p)[:, :, :h],
            _columns(d_dt, h).astype(dt.dtype), d_a.astype(a.dtype),
            d_b.astype(b_.dtype).reshape(b_shape),
            d_c.astype(c_.dtype).reshape(c_shape), d_d.astype(d.dtype))


@functools.partial(jax.custom_vjp, nondiff_argnums=(6, 7))
def ssd_scan_pallas(x, dt, a, b, c, d, q: int, interpret: bool):
    """``ops/ssd.ssd_scan``'s Pallas arm: x (B, S, H, P), dt and a
    (B, S, H), b and c (B, S, N) or (B, S, G, N), d (H,) -> y (B, S, H, P)
    in x's type; ``q`` tokens a chunk (``ssd_blocks``)."""
    return _forward(x, dt, a, b, c, d, q, interpret, False)


def _vjp_fwd(x, dt, a, b, c, d, q, interpret):
    y, states = _forward(x, dt, a, b, c, d, q, interpret, True)
    # named as the delta rule's and the flash kernel's results are
    y, states = map(checkpoint_name, (y, states), SCAN_SAVED)
    return y, (x, dt, a, b, c, d, states)


def _vjp_bwd(q, interpret, res, d_y):
    return _backward(*res, d_y, q, interpret)


ssd_scan_pallas.defvjp(_vjp_fwd, _vjp_bwd)
