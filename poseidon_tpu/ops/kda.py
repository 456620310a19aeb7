"""The gated delta rule as a chunked scan, forward and a hand-written
backward, with a decay a head AND channel (KDA, arXiv:2510.26692) or ONE a
head (Gated DeltaNet, arXiv:2412.06464).

Per head, a state S (d_k, d_v) (the two widths need not be equal), S_0 = 0,
and per token t a query and a key q_t, k_t (d_k), a value v_t (d_v), a
log-decay g_t <= 0 (d_k, one a CHANNEL; or a scalar, one a HEAD: g comes as
(B, S, H, d_k) or as (B, S, H)) and a write strength beta_t in (0, 2) (past
1 the write's eigenvalue 1 - beta along k is negative; the unit-lower
system below is the same in form):

    Sbar_t = Diag(exp(g_t)) S_{t-1}
    S_t    = Sbar_t + beta_t k_t (v_t - Sbar_t^T k_t)^T
    o_t    = S_t^T q_t * scale

``kda_recurrence`` is that, token by token (what the tests hold the scan
to). ``kda_scan`` computes the same in chunks of C tokens. With G the
inclusive cumulative sum of g inside a chunk, u_t = beta_t (v_t - Sbar_t^T
k_t) the value each token really writes, and S the state at the chunk's
start:

    A_ij = sum_c k_ic k_jc exp(G_ic - G_jc)        j <  i
    B_ij = sum_c q_ic k_jc exp(G_ic - G_jc)        j <= i
    (I + Diag(beta) A) U = Diag(beta) (V - (K * exp(G)) S)
    O  = scale ((Q * exp(G)) S + B U)
    S' = Diag(exp(G_C)) S + (K * exp(G_C - G))^T U

Everything of a chunk that does not need S is computed for a GROUP of
chunks at once (``_chunk_parts``: A, its triangular system solved against
Diag(beta) [V | K exp(G)] by forward substitution written as products, B
and the three decayed operands; ``_GROUP``
tokens a group, so that these f32 parts hold a group's share of HBM and not
the sequence's); a ``lax.scan`` over the group's chunks carries S through
three products a chunk, inside a ``lax.scan`` over the groups.

Strong decay: exp(-G_j) overflows f32 once a channel has forgotten more
than e^-88 inside a chunk, so no decay ratio is ever formed as a product
with exp(-G). Every ratio is exp(G_i - G_j) with i >= j (at most 1): inside
a sub-block of ``_SUB`` tokens element by element, between sub-blocks as a
product of two factors that are each at most 1, exp(G_i - r) and
exp(r - G_j) with r the cumulative sum at the end of the sub-block before
i's (so G_i <= r <= G_j).

One decay a head (``_chunk_parts_head``): the ratio of a token pair is ONE
number, exp(G_i - G_j), so A and B are (K K^T) and (Q K^T) times a C x C
grid of ratios formed element by element, each with i >= j (at most 1, no
exp(-G)). The sub-block rule is not needed there: it exists because a
per-channel ratio inside a PRODUCT over channels has to be split into two
factors, and a grid of scalars is never split.

The backward (``custom_vjp``) keeps the five operands and ONE state a chunk
a head (d_k x d_v f32), recomputes a group's parts and walks its chunks
backwards with the transposed three products, group by group from the last;
the derivative of the chunk-local parts is autodiff's. The state, the
cumulative sums and the triangular system are f32 at HIGHEST precision
whatever the compute policy.

Which arm takes which, chosen by ``kda_route`` from what the code can see
(the decay's shape, the widths, the backend; no switch):

- ``pallas`` (``ops/kda_pallas.py``'s two kernels, each with an arm for
  either decay: a program owns ``kda_blocks``'s m chunks of a head, the
  state stays in VMEM from a sequence's first chunk to its last, the
  chunk-local parts and their pullback never visit HBM): a backend that
  compiles Mosaic, 64 divides S, and either a decay a CHANNEL with d_k and
  d_v both multiples of 128 (a head is a lane block of (N, S, H d), read in
  place), or one decay a HEAD at any widths, q, k and v padded with zero
  lanes to multiples of 128 on the way in (Gated DeltaNet's 96 / 192 run
  at 128 / 256: one padded copy of each operand, a quarter of the lanes
  and 44% of the state's products wasted, o sliced back);
- ``chunked`` (the ``jax.numpy`` form below, which is also what the kernels
  are read beside): everything else that has a chunk — a decay a channel
  at widths that are no lane multiples, a chunk shorter than 64, the CPU
  mesh. ``kda_route``'s note says which of these it was;
- ``recurrence``: no chunk divides S.

``kda_recurrence`` is the oracle of all of them.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.ad_checkpoint import checkpoint_name

_SUB = 16                  # tokens a sub-block: ratios inside it elementwise
_GROUP = 1024              # tokens whose chunk-local parts live at once
_HI = lax.Precision.HIGHEST


def kda_chunk(s: int) -> Optional[int]:
    """Tokens a chunk for a sequence of ``s`` — the chunk rule, of the shape
    alone: 64 (the family's) where it divides S, else the largest multiple
    of the sub-block below it that does, else None (no chunked form)."""
    return next((c for c in (64, 48, 32, 16) if s % c == 0), None)


def state_bytes(batch: int, s: int, heads: int, d_k: int, d_v: int,
                per_head: bool = False, itemsize: int = 2) -> int:
    """What the backward keeps of the recurrence: one f32 state a chunk a
    head a sequence, at the widths the arm that runs keeps it (the Pallas
    arm's padded lanes where it pads)."""
    if _pallas_blocks(s, d_k, d_v, heads, itemsize, per_head):
        d_k, d_v = _kernel_widths(d_k, d_v, per_head)
    return batch * heads * (s // kda_chunk(s)) * d_k * d_v * 4


def kda_route(s: int, d_k: Optional[int] = None, d_v: Optional[int] = None,
              heads: int = 1, itemsize: int = 2, per_head: bool = False):
    """``(arm, note)`` for a sequence length, a head's widths and the
    decay's shape (``per_head``: one g a head, not a channel), as ``Net``
    logs it — THE routing decision, of the backend and the shape alone:
    ``pallas`` (``ops/kda_pallas.py``) where the backend compiles Mosaic, 64
    divides S and the widths are multiples of 128 or, with one decay a
    head, are padded to them (``kda_blocks``: the chunks a program); else
    the ``jax.numpy`` chunked form below, the note saying WHY the kernels
    did not take it; else the recurrence."""
    c = kda_chunk(s)
    if c is None:
        return "recurrence", f"token by token (no chunk divides S={s})"
    m = _pallas_blocks(s, d_k, d_v, heads, itemsize, per_head)
    if m:
        padded = _lanes(d_k), _lanes(d_v)
        return "pallas", (
            f"pallas (C {c} x {m}, {s // c} chunks, f32 state in VMEM"
            + (", one decay a head" if per_head else "")
            + (f", lanes {d_k} / {d_v} padded to {padded[0]} / {padded[1]}"
               if padded != (d_k, d_v) else "") + ")")
    why = _pallas_refusal(s, d_k, d_v, heads, itemsize, per_head)
    return "chunked", (f"chunked C {c}, {s // c} chunks, f32 state"
                       + (", one decay a head" if per_head else "")
                       + (f"; not pallas: {why}" if why else ""))


def _lanes(d: int) -> int:
    """A head's width rounded up to whole lane blocks of 128."""
    return -(-d // 128) * 128


def _kernel_widths(d_k, d_v, per_head):
    """The widths the kernels would run a head at: its own or, with one
    decay a head, padded to lane blocks (a zero channel of q and k adds
    nothing to a score and leaves its row of the state zero; a zero column
    of v is a zero column of o). A decay a CHANNEL is not padded: Kimi's
    widths are lane blocks already, and nothing else runs that arm."""
    return (_lanes(d_k), _lanes(d_v)) if per_head else (d_k, d_v)


def _pallas_refusal(s, d_k, d_v, heads, itemsize, per_head) -> str:
    """Why the Pallas arm does not take a shape ('' where no widths were
    given): the kernels' own refusal (``kda_pallas.kda_refusal``), else the
    backend."""
    if not (d_k and d_v):
        return ""
    from .kda_pallas import kda_refusal
    from .pallas_kernels import _interpret_default
    return kda_refusal(s, *_kernel_widths(d_k, d_v, per_head), heads,
                       itemsize) or (
        "this backend would interpret the kernels"
        if _interpret_default() else "")


def _pallas_blocks(s, d_k, d_v, heads, itemsize,
                   per_head: bool = False) -> Optional[int]:
    """Chunks a program of the Pallas arm, None where it does not run: no
    widths given, a shape the kernels cannot take, or a backend that would
    interpret them."""
    if not (d_k and d_v):
        return None
    from .kda_pallas import kda_blocks
    from .pallas_kernels import _interpret_default
    m = kda_blocks(s, *_kernel_widths(d_k, d_v, per_head), heads, itemsize)
    return m if m and not _interpret_default() else None


def _pallas_scan(q, k, v, g, beta, scale, m, interpret):
    """The Pallas arm at the widths the kernels run (``_kernel_widths``):
    zero lanes appended to q, k, v on the way in, o sliced back."""
    from .kda_pallas import kda_scan_pallas
    d_k, d_v = q.shape[-1], v.shape[-1]
    wide_k, wide_v = _kernel_widths(d_k, d_v, g.ndim == 3)
    if (wide_k, wide_v) == (d_k, d_v):      # lane blocks as they come
        return kda_scan_pallas(q, k, v, g, beta, scale, m, interpret)
    wide = lambda x, to: jnp.pad(
        x, [(0, 0)] * 3 + [(0, to - x.shape[-1])])
    o = kda_scan_pallas(wide(q, wide_k), wide(k, wide_k), wide(v, wide_v),
                        g, beta, scale, m, interpret)
    return o[..., :d_v]


def _mm(a, b, spec):
    return jnp.einsum(spec, a, b, precision=_HI,
                      preferred_element_type=jnp.float32)


def kda_recurrence(q, k, v, g, beta, scale: Optional[float] = None):
    """q, k (B, S, H, d_k), g the same or (B, S, H) (one a head), v
    (B, S, H, d_v), beta (B, S, H) -> o (B, S, H, d_v) f32: the recurrence
    as written, a ``lax.scan`` over t."""
    scale = q.shape[-1] ** -0.5 if scale is None else scale
    q, k, v, g, beta = (x.astype(jnp.float32) for x in (q, k, v, g, beta))
    g = g[..., None] if g.ndim == 3 else g
    b, _, h, d_k = q.shape

    def step(state, xs):
        q_t, k_t, v_t, g_t, b_t = xs               # (B, H, ...)
        sbar = jnp.exp(g_t)[..., None] * state
        u = b_t[..., None] * (v_t - _mm(sbar, k_t, "bhkv,bhk->bhv"))
        state = sbar + k_t[..., None] * u[..., None, :]
        return state, _mm(state, q_t, "bhkv,bhk->bhv") * scale

    s0 = jnp.zeros((b, h, d_k, v.shape[-1]), jnp.float32)
    _, o = lax.scan(step, s0, tuple(x.swapaxes(0, 1)
                                    for x in (q, k, v, g, beta)))
    return o.swapaxes(0, 1)


@jax.checkpoint
def _within_sub_blocks(q, k, gc):
    """The decayed scores of token pairs inside ONE sub-block, ratio by
    ratio: q, k, gc (..., n_sub, _SUB, d_k) -> (A, B) (..., n_sub, _SUB,
    _SUB), j < i and j <= i. Under ``jax.checkpoint``: the (_SUB, _SUB,
    d_k) ratios are never a residual."""
    i = jnp.arange(q.shape[-2])
    lower = i[:, None] >= i[None, :]
    diff = gc[..., :, None, :] - gc[..., None, :, :]       # G_i - G_j
    ratio = jnp.where(lower[..., None],
                      jnp.exp(jnp.where(lower[..., None], diff, 0.0)), 0.0)
    kd = k[..., None, :, :] * ratio                        # k_j exp(G_i - G_j)
    a = jnp.sum(k[..., :, None, :] * kd, -1)
    b = jnp.sum(q[..., :, None, :] * kd, -1)
    return jnp.where(i[:, None] > i[None, :], a, 0.0), b


def _unit_lower_inverse(low):
    """(I + L)^-1 for strictly lower triangular L (..., C, C), by forward
    substitution, exactly as one would by hand and with nothing but
    products: row by row inside each ``_SUB`` x ``_SUB`` diagonal block
    (row i of the inverse is e_i - L[i, :i] times the rows above it), then
    block row by block row, T_ij = -T_ii (L_i,<i T_<i,j). (A library
    triangular solve of 64 x 64 systems was a third of the scan's time on
    the v5e; the series (I - L)(I + L^2)(I + L^4).. is all products too
    but cancels catastrophically where keys repeat.)"""
    c = low.shape[-1]
    n_sub = c // _SUB
    blocks = low.reshape(low.shape[:-2] + (n_sub, _SUB, n_sub, _SUB))
    diag = jnp.stack([blocks[..., b, :, b, :] for b in range(n_sub)], -3)
    eye = jnp.eye(_SUB, dtype=jnp.float32)
    rows = [jnp.broadcast_to(eye[0], diag.shape[:-2] + (_SUB,))]
    for i in range(1, _SUB):
        above = jnp.stack(rows, -2)                      # (.., i, _SUB)
        rows.append(eye[i] - _mm(diag[..., i, :i], above,
                                 "...j,...jk->...k"))
    inner = jnp.stack(rows, -2)                          # (.., n_sub, S, S)
    done = [jnp.concatenate(
        [inner[..., 0, :, :],
         jnp.zeros(low.shape[:-2] + (_SUB, c - _SUB), jnp.float32)], -1)]
    for b in range(1, n_sub):
        lo = b * _SUB
        left = _mm(low[..., lo:lo + _SUB, :lo],
                   jnp.concatenate(done, -2)[..., :lo],
                   "...ij,...jk->...ik")
        done.append(jnp.concatenate(
            [-_mm(inner[..., b, :, :], left, "...ij,...jk->...ik"),
             inner[..., b, :, :],
             jnp.zeros(low.shape[:-2] + (_SUB, c - lo - _SUB),
                       jnp.float32)], -1))
    return jnp.concatenate(done, -2)


def _chunk_parts(q, k, v, g, beta):
    """All that a chunk computes without the state, for the N chunks of a
    group at once. q, k, g (B, H, N, C, d_k), v (B, H, N, C, d_v), beta
    (B, H, N, C)
    f32 -> w (C, d_k) and u (C, d_v): the system solved against
    Diag(beta) K exp(G) and Diag(beta) V; qg = Q exp(G); b = B; krev =
    K exp(G_C - G); last = exp(G_C) (d_k)."""
    c, d_k = q.shape[-2:]
    n_sub = c // _SUB
    gc = jnp.cumsum(g, axis=-2)
    sub = lambda x: x.reshape(x.shape[:-2] + (n_sub, _SUB, x.shape[-1]))
    a_in, b_in = _within_sub_blocks(sub(q), sub(k), sub(gc))
    rows_a, rows_b = [], []
    for blk in range(n_sub):
        lo = blk * _SUB
        pieces_a, pieces_b = [], []
        if blk:
            ref = gc[..., lo - 1:lo, :]            # G at the end of blk - 1
            down = jnp.exp(gc[..., lo:lo + _SUB, :] - ref)      # <= 1
            up = k[..., :lo, :] * jnp.exp(ref - gc[..., :lo, :])  # <= |k|
            pieces_a.append(_mm(k[..., lo:lo + _SUB, :] * down, up,
                                "...ic,...jc->...ij"))
            pieces_b.append(_mm(q[..., lo:lo + _SUB, :] * down, up,
                                "...ic,...jc->...ij"))
        pad = jnp.zeros(q.shape[:-2] + (_SUB, c - lo - _SUB), jnp.float32)
        rows_a.append(jnp.concatenate(
            pieces_a + [a_in[..., blk, :, :], pad], -1))
        rows_b.append(jnp.concatenate(
            pieces_b + [b_in[..., blk, :, :], pad], -1))
    a = jnp.concatenate(rows_a, -2)                # (.., C, C) strictly lower
    b = jnp.concatenate(rows_b, -2)                # (.., C, C) lower
    decay = jnp.exp(gc)
    kg = k * decay
    solved = _mm(_unit_lower_inverse(beta[..., None] * a),
                 beta[..., None] * jnp.concatenate([kg, v], -1),
                 "...ij,...jk->...ik")
    last = gc[..., -1:, :]
    return (solved[..., :d_k], solved[..., d_k:], q * decay, b,
            k * jnp.exp(last - gc), jnp.exp(last[..., 0, :]))


def _chunk_parts_head(q, k, v, g, beta):
    """``_chunk_parts`` with ONE decay a head: g (B, H, N, C). Every ratio
    is exp(G_i - G_j), i >= j, one number a token pair on the C x C grid
    (at most 1; never a product with exp(-G)), so A and B are one product
    and one mask each. ``last`` comes back (.., 1): it scales the whole
    state."""
    c, d_k = q.shape[-2:]
    gc = jnp.cumsum(g, axis=-1)
    i = jnp.arange(c)
    lower = i[:, None] >= i[None, :]
    ratio = jnp.where(lower, jnp.exp(jnp.where(
        lower, gc[..., :, None] - gc[..., None, :], 0.0)), 0.0)
    both = _mm(jnp.concatenate([k, q], -2), k, "...ic,...jc->...ij")
    a = jnp.where(i[:, None] > i[None, :], both[..., :c, :] * ratio, 0.0)
    b = both[..., c:, :] * ratio
    decay = jnp.exp(gc)[..., None]
    kg = k * decay
    solved = _mm(_unit_lower_inverse(beta[..., None] * a),
                 beta[..., None] * jnp.concatenate([kg, v], -1),
                 "...ij,...jk->...ik")
    last = gc[..., -1:]
    return (solved[..., :d_k], solved[..., d_k:], q * decay, b,
            k * jnp.exp(last - gc)[..., None], jnp.exp(last))


def _group(s: int, chunk: int) -> int:
    """Chunks a group: the chunk-local parts are computed for ``_GROUP``
    tokens at a time (what they hold of HBM is a group's, not the
    sequence's), the largest such number of chunks that divides S / C."""
    n = s // chunk
    return next(g for g in range(max(1, _GROUP // chunk), 0, -1)
                if n % g == 0)


def _split(x, chunk, group):
    """(B, S, H, ...) -> (S / (G C), B, H, G, C, ...): groups of G chunks
    leading, in x's own type."""
    b, s, h = x.shape[:3]
    x = x.reshape((b, s // (group * chunk), group, chunk, h) + x.shape[3:])
    return jnp.moveaxis(x, (1, 4), (0, 2))


def _merge(x):
    """(S / (G C), B, H, G, C, ...) -> (B, S, H, ...)."""
    n, b, h, g, c = x.shape[:5]
    return jnp.moveaxis(x, (0, 2), (1, 4)).reshape(
        (b, n * g * c, h) + x.shape[5:])


def _parts_of(q, k, v, g, beta):
    """One group's operands (B, H, G, C, ..) in their own types -> its
    chunk-local parts, chunks leading (G, B, H, ...). g (.., d_k) a channel
    or (.., 1) a head."""
    f32 = lambda x: x.astype(jnp.float32)
    if g.shape[-1] == 1:        # one decay a head (d_k 1: the same thing)
        parts = _chunk_parts_head(f32(q), f32(k), f32(v), f32(g[..., 0]),
                                  f32(beta[..., 0]))
    else:
        parts = _chunk_parts(f32(q), f32(k), f32(v), f32(g),
                             f32(beta[..., 0]))
    return tuple(jnp.moveaxis(p, 2, 0) for p in parts)


def _operands(q, k, v, g, beta, chunk):
    group = _group(q.shape[1], chunk)
    return tuple(_split(x, chunk, group)
                 for x in (q, k, v, g, beta[..., None]))


def _forward(q, k, v, g, beta, scale, chunk):
    """-> (o (B, S, H, d_v) in v's type, the f32 state at every chunk's
    start (S / (G C), G, B, H, d_k, d_v))."""
    def step(state, xs):
        w, u, qg, b, krev, last = xs
        wrote = u - _mm(w, state, "bhck,bhkv->bhcv")
        o = scale * (_mm(qg, state, "bhck,bhkv->bhcv")
                     + _mm(b, wrote, "bhij,bhjv->bhiv"))
        new = last[..., None] * state + _mm(krev, wrote, "bhck,bhcv->bhkv")
        return new, (o.astype(v.dtype), state)

    def group(state, operands):
        state, (o, states) = lax.scan(step, state, _parts_of(*operands))
        return state, (jnp.moveaxis(o, 0, 2), states)

    s0 = jnp.zeros((q.shape[0], q.shape[2], q.shape[-1], v.shape[-1]),
                   jnp.float32)
    _, (o, states) = lax.scan(group, s0, _operands(q, k, v, g, beta, chunk))
    return _merge(o), states


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6))
def _kda_chunked(q, k, v, g, beta, scale, chunk):
    return _forward(q, k, v, g, beta, scale, chunk)[0]


SCAN_SAVED = ("scan_out", "scan_states")   # either arm's forward results


def _kda_fwd(q, k, v, g, beta, scale, chunk):
    o, states = _forward(q, k, v, g, beta, scale, chunk)
    # named as the flash kernel's results are (pallas_kernels._flash_vjp_fwd)
    o, states = map(checkpoint_name, (o, states), SCAN_SAVED)
    return o, (q, k, v, g, beta, states)


def _kda_bwd(scale, chunk, res, d_o):
    q, k, v, g, beta, states = res
    operands = _operands(q, k, v, g, beta, chunk)
    d_og = jnp.moveaxis(_split(d_o, chunk, _group(q.shape[1], chunk)), 3, 1)

    def step(d_state, xs):
        (w, u, qg, b, krev, last), state, d_out = xs
        wrote = u - _mm(w, state, "bhck,bhkv->bhcv")
        d_out = scale * d_out.astype(jnp.float32)
        d_wrote = _mm(b, d_out, "bhij,bhiv->bhjv") \
            + _mm(krev, d_state, "bhck,bhkv->bhcv")
        d_parts = (-_mm(d_wrote, state, "bhcv,bhkv->bhck"), d_wrote,
                   _mm(d_out, state, "bhcv,bhkv->bhck"),
                   _mm(d_out, wrote, "bhiv,bhjv->bhij"),
                   _mm(wrote, d_state, "bhcv,bhkv->bhck"),
                   # last is (d_k) a channel, (1) a head: the whole state's
                   jnp.sum(state * d_state, -1) if last.shape[-1] > 1
                   else jnp.sum(state * d_state, (-2, -1))[..., None])
        d_prev = _mm(qg, d_out, "bhck,bhcv->bhkv") \
            + last[..., None] * d_state \
            - _mm(w, d_wrote, "bhck,bhcv->bhkv")
        return d_prev, d_parts

    def group(d_state, xs):
        ops, states_g, d_out_g = xs
        parts, pull = jax.vjp(_parts_of, *ops)
        d_state, d_parts = lax.scan(step, d_state,
                                    (parts, states_g, d_out_g), reverse=True)
        return d_state, pull(d_parts)

    _, grads = lax.scan(group, jnp.zeros_like(states[0, 0]),
                        (operands, states, d_og), reverse=True)
    d_q, d_k, d_v, d_g, d_beta = (_merge(x) for x in grads)
    return d_q, d_k, d_v, d_g, d_beta[..., 0]


_kda_chunked.defvjp(_kda_fwd, _kda_bwd)


def kda_scan(q, k, v, g, beta, scale: Optional[float] = None,
             chunk: Optional[int] = None):
    """q, k (B, S, H, d_k), g the same (a decay a channel) or (B, S, H) (one
    a head), v (B, S, H, d_v), beta (B, S, H) -> o (B, S, H, d_v) in v's
    type. ``chunk``: tokens a chunk (None: ``kda_chunk``'s; a multiple of
    the sub-block that divides S). Where no chunk divides S the
    token-by-token recurrence runs."""
    scale = q.shape[-1] ** -0.5 if scale is None else float(scale)
    per_head = g.ndim == 3
    m = None if chunk else _pallas_blocks(
        q.shape[1], q.shape[-1], v.shape[-1], q.shape[2], q.dtype.itemsize,
        per_head)
    if m:
        return _pallas_scan(q, k, v, g, beta, scale, m, False)
    chunk = kda_chunk(q.shape[1]) if chunk is None else chunk
    if chunk is None:
        return kda_recurrence(q, k, v, g, beta, scale).astype(v.dtype)
    if q.shape[1] % chunk or chunk % _SUB:
        raise ValueError(f"chunk {chunk} is not a multiple of {_SUB} that "
                         f"divides S={q.shape[1]}")
    return _kda_chunked(q, k, v, g[..., None] if per_head else g, beta,
                        scale, int(chunk))
