"""Mamba-2's selective scan (state-space duality, arXiv:2405.21060) with B
and C shared by the heads of a GROUP: the recurrence, its chunked form and
the backward.

Per head h a state H (P, N), zero at a sequence's start, and per token t a
value x_t (P), a step dt_t > 0 and a log-decay a_t = dt_t A_h <= 0 (one
number a head), and a write key B_t and a read key C_t (N) a GROUP of
heads: with G groups (``n_groups``) head h reads group g(h) = h // (H / G),
so G = 1 (Granite-4.0-H) is one B and one C for ALL heads and G = 8
(Nemotron-H) one for every eight of 64:

    H_t = exp(a_t) H_{t-1} + dt_t x_t B_{t,g(h)}^T
    y_t = H_t C_{t,g(h)} + D_h x_t

b and c come as (B, S, N) (one group: what every caller had) or as
(B, S, G, N). The groups share nothing, so the ``jax.numpy`` arms run a
group's heads as the one-group form under ``jax.vmap`` (``_by_group``) and
the one-group program is what it was; the kernels index a program's B / C
block by its group (``ops/ssd_pallas.py``).

No delta term (nothing reads the state back before the write: the gated
delta rule of ``ops/kda.py`` solves a unit-lower system a chunk, this has
none), the write is dt x B^T, the skip D x.

``ssd_recurrence`` is that, token by token (what the tests hold every arm
to). ``ssd_scan`` computes the same in chunks of Q tokens (256, the
published ``mamba_chunk_size``, where it divides S). With L_i the inclusive
cumulative sum of a inside a chunk (every exponent below is <= 0, so no
ratio is ever formed with exp(-L)) and H the state at the chunk's start:

    Y_i = sum_{j<=i} exp(L_i - L_j) (C_i . B_j) dt_j x_j
          + exp(L_i) H C_i + D x_i
    H'  = exp(L_Q) H + sum_j exp(L_Q - L_j) dt_j x_j B_j^T

``C_i . B_j`` is ONE (Q, Q) grid a chunk for all heads of a group; a head
brings its own mask of ratios and its own dt x.

The backward (``custom_vjp``) is the chunk-local ``jax.vjp`` with the
state's pullback carried in reverse: the forward keeps its operands and ONE
f32 state a chunk a head (P x N), the backward walks the chunks from the
last, rebuilds a chunk with the forward's own code (``_chunk_step``) and
pulls (d y of the chunk, d state at its end) back to the chunk's operands
and the state at its start. d B and d C are sums over a GROUP's heads (the
einsums' own), d dt collects from the write and, through a = dt A outside,
from the decay's exponent, d D is a sum over tokens. The steps, the
cumulative sums, every ratio and the carried state are f32, products at
HIGHEST precision, whatever the compute policy (the kernels sum the same
terms in the MXU passes that do not multiply by the zero low parts of a
bf16 operand: ``ops/ssd_pallas.py``).

Which arm runs, chosen by ``ssd_route`` from the shape and the backend (no
switch):

- ``pallas`` (``ops/ssd_pallas.py``: ``ssd_scan_fwd`` / ``ssd_scan_bwd``,
  a program owns one chunk of up to eight heads, two heads of 64 a lane
  block, the states stay in VMEM across the sequence, the (Q, Q) grid is
  made once a program and the heads' masks never visit HBM): a backend that
  compiles Mosaic, a chunk of 256 or 128 divides S, P divides 128, N is
  a multiple of 128 and, with more than one group, a group's heads fill
  whole programs (no program spans two groups);
- ``chunked`` (the ``jax.numpy`` form below, which the kernels are read
  beside): every other shape with a chunk, and the CPU mesh; the note says
  which of these it was;
- ``recurrence``: no chunk divides S.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.ad_checkpoint import checkpoint_name

from .kda import SCAN_SAVED

_HI = lax.Precision.HIGHEST
_F32 = jnp.float32


def ssd_chunk(s: int) -> Optional[int]:
    """Tokens a chunk for a sequence of ``s``: 256 (``mamba_chunk_size``)
    where it divides S, else the largest power of two from 128 down to 8
    that does, else None (no chunked form)."""
    return next((q for q in (256, 128, 64, 32, 16, 8) if s % q == 0), None)


def _pallas_chunk(s, heads, p, n, groups: int = 1) -> Optional[int]:
    """The chunk the Pallas arm runs, None where it does not: a shape the
    kernels refuse, or a backend that would interpret them."""
    from .pallas_kernels import _interpret_default
    from .ssd_pallas import ssd_blocks
    q = ssd_blocks(s, heads, p, n, groups)
    return q if q and not _interpret_default() else None


def scan_chunk(s: int, heads: int, p: int, n: int,
               groups: int = 1) -> Optional[int]:
    """Tokens a chunk of the arm that runs a shape here (the kernels' where
    they take it, else ``ssd_chunk``'s; None: the recurrence)."""
    return _pallas_chunk(s, heads, p, n, groups) or ssd_chunk(s)


def state_bytes(batch: int, s: int, heads: int, p: int, n: int,
                groups: int = 1) -> int:
    """What the backward keeps of the recurrence: one f32 state a chunk a
    head a sequence (the Pallas arm's zero heads where it pads)."""
    q = scan_chunk(s, heads, p, n, groups)
    if _pallas_chunk(s, heads, p, n, groups):
        from .ssd_pallas import padded_heads
        heads = padded_heads(heads, p)
    return batch * heads * (s // q) * p * n * 4 if q else 0


def ssd_route(s: int, heads: int, p: int, n: int, itemsize: int = 4,
              groups: int = 1):
    """``(arm, note)`` for a sequence length and a scan's widths, as ``Net``
    logs it — THE routing decision, of the backend and the shape alone:
    ``pallas`` with the chunk and the heads a program, else ``chunked`` with
    the reason the kernels did not take it (a program that would span two
    of ``groups`` groups of B / C among them), else ``recurrence``. With
    more than one group the note ends ``; groups=<G>``.
    ``itemsize``: the bytes of the compute type x, B and C arrive in, from
    which the Pallas arm's note says what share of six MXU passes a product
    its forward / backward kernels run (``ssd_pallas.mxu_passes``)."""
    from .pallas_kernels import _interpret_default
    from .ssd_pallas import heads_a_program, mxu_passes, ssd_refusal
    said = f"; groups={groups}" if groups > 1 else ""
    q = _pallas_chunk(s, heads, p, n, groups)
    if q:
        hp = heads_a_program(heads, p)
        six = mxu_passes(q, p, n, hp, _F32)
        ran = mxu_passes(q, p, n, hp,
                         jnp.bfloat16 if itemsize == 2 else _F32)
        return "pallas", (
            f"pallas (Q {q}, {s // q} chunks, {hp} "
            f"heads a program, {max(1, 128 // p)} a lane block, one C B^T "
            f"grid a program, f32 states in VMEM, passes "
            f"{ran[0] / six[0]:.2f} / {ran[1] / six[1]:.2f} of six a "
            f"product){said}")
    q = ssd_chunk(s)
    if q is None:
        return "recurrence", (f"token by token (no chunk divides S={s})"
                              f"{said}")
    why = ssd_refusal(s, heads, p, n, groups) or (
        "this backend would interpret the kernels"
        if _interpret_default() else "")
    return "chunked", (f"chunked Q {q}, {s // q} chunks, f32 state, one "
                       f"C B^T grid a chunk"
                       + (f"; not pallas: {why}" if why else "") + said)


def _mm(a, b, spec):
    return jnp.einsum(spec, a, b, precision=_HI,
                      preferred_element_type=_F32)


def _by_group(scan, x, dt, a, b, c, d):
    """``scan`` (a one-group form: b, c (B, S, N)) over the G groups of b,
    c (B, S, G, N): head h = g H / G + j is head j of group g, the groups
    share nothing, so each runs as the one-group form (``jax.vmap`` over the
    group axis; d B and d C then sum over a group's heads alone)."""
    g = b.shape[2]
    if x.shape[2] % g:
        raise ValueError(f"{x.shape[2]} heads do not split into {g} groups "
                         f"of B / C")

    def split(t, axis):
        return t.reshape(t.shape[:axis] + (g, -1) + t.shape[axis + 1:])

    y = jax.vmap(scan, in_axes=(2, 2, 2, 2, 2, 0), out_axes=2)(
        split(x, 2), split(dt, 2), split(a, 2), b, c, split(d, 0))
    return y.reshape(x.shape)


def ssd_recurrence(x, dt, a, b, c, d):
    """x (B, S, H, P), dt and a (B, S, H), b and c (B, S, N) or
    (B, S, G, N), d (H,) -> y (B, S, H, P) f32: the recurrence as written,
    a ``lax.scan`` over t."""
    if b.ndim == 4:
        return _by_group(ssd_recurrence, x, dt, a, b, c, d)
    x, dt, a, b, c, d = (t.astype(_F32) for t in (x, dt, a, b, c, d))

    def step(state, xs):
        x_t, dt_t, a_t, b_t, c_t = xs
        # exp(a) H as H + expm1(a) H: no bias of exp near 1 compounds over
        # the thousand tokens a slow head keeps its state
        state = state + (jnp.expm1(a_t)[..., None, None] * state
                         + (dt_t[..., None] * x_t)[..., None]
                         * b_t[:, None, None, :])
        return state, _mm(state, c_t, "bhpn,bn->bhp") + d[:, None] * x_t

    s0 = jnp.zeros(x.shape[:1] + x.shape[2:] + b.shape[-1:], _F32)
    _, y = lax.scan(step, s0, tuple(t.swapaxes(0, 1)
                                    for t in (x, dt, a, b, c)))
    return y.swapaxes(0, 1)


def _chunk_step(state, x, dt, a, b, c):
    """One chunk of every head of ONE group. state (B, H, P, N) f32 at its
    start; x (B, Q, H, P), dt and a (B, Q, H), b and c (B, Q, N) in their
    own types -> (y (B, Q, H, P) f32 without the skip, the state at its
    end)."""
    x, dt, a, b, c = (t.astype(_F32) for t in (x, dt, a, b, c))
    q = x.shape[1]
    lt = jnp.cumsum(a, 1).swapaxes(1, 2)                   # (B, H, Q)
    dtt = dt.swapaxes(1, 2)
    grid = _mm(c, b, "bin,bjn->bij")                       # all heads' scores
    i = jnp.arange(q)
    lower = i[:, None] >= i[None, :]
    ratio = jnp.where(lower, jnp.exp(jnp.where(
        lower, lt[..., :, None] - lt[..., None, :], 0.0)), 0.0)
    y = _mm(grid[:, None] * ratio * dtt[:, :, None, :], x,
            "bhij,bjhp->bihp")
    y = y + jnp.exp(lt).swapaxes(1, 2)[..., None] \
        * _mm(c, state, "bin,bhpn->bihp")
    rev = (jnp.exp(lt[..., -1:] - lt) * dtt).swapaxes(1, 2)    # (B, Q, H)
    new = jnp.exp(lt[..., -1])[..., None, None] * state \
        + _mm(x * rev[..., None], b, "bjhp,bjn->bhpn")
    return y, new


def _chunks(t, q):
    """(B, S, ...) -> (S / Q, B, Q, ...): chunks leading."""
    return jnp.moveaxis(t.reshape((t.shape[0], -1, q) + t.shape[2:]), 1, 0)


def _merge(t):
    """(S / Q, B, Q, ...) -> (B, S, ...)."""
    t = jnp.moveaxis(t, 0, 1)
    return t.reshape((t.shape[0], -1) + t.shape[3:])


def _forward(x, dt, a, b, c, d, q):
    """-> (y (B, S, H, P) in x's type, the f32 state at every chunk's start
    (S / Q, B, H, P, N))."""
    def step(state, xs):
        y, new = _chunk_step(state, *xs)
        return new, (y, state)

    s0 = jnp.zeros(x.shape[:1] + x.shape[2:] + b.shape[-1:], _F32)
    _, (y, states) = lax.scan(step, s0,
                              tuple(_chunks(t, q) for t in (x, dt, a, b, c)))
    y = _merge(y) + d.astype(_F32)[:, None] * x.astype(_F32)
    return y.astype(x.dtype), states


@functools.partial(jax.custom_vjp, nondiff_argnums=(6,))
def _ssd_chunked(x, dt, a, b, c, d, q):
    return _forward(x, dt, a, b, c, d, q)[0]


def _ssd_fwd(x, dt, a, b, c, d, q):
    y, states = _forward(x, dt, a, b, c, d, q)
    # named as the delta rule's and the flash kernel's results are
    y, states = map(checkpoint_name, (y, states), SCAN_SAVED)
    return y, (x, dt, a, b, c, d, states)


def _ssd_bwd(q, res, d_y):
    x, dt, a, b, c, d, states = res
    d_y = d_y.astype(_F32)

    def step(d_state, xs):
        state, d_out, *operands = xs
        _, pull = jax.vjp(_chunk_step, state, *operands)
        d_prev, *grads = pull((d_out, d_state))
        return d_prev, grads

    _, grads = lax.scan(
        step, jnp.zeros_like(states[0]),
        (states, _chunks(d_y, q)) + tuple(_chunks(t, q)
                                          for t in (x, dt, a, b, c)),
        reverse=True)
    d_x, d_dt, d_a, d_b, d_c = (_merge(g) for g in grads)
    d_x = d_x.astype(_F32) + d.astype(_F32)[:, None] * d_y
    d_d = jnp.sum(d_y * x.astype(_F32), (0, 1, 3))
    return tuple(g.astype(t.dtype) for g, t in zip(
        (d_x, d_dt, d_a, d_b, d_c, d_d), (x, dt, a, b, c, d)))


_ssd_chunked.defvjp(_ssd_fwd, _ssd_bwd)


def ssd_scan(x, dt, a, b, c, d, chunk: Optional[int] = None):
    """x (B, S, H, P), dt and a = dt A (B, S, H) f32, b and c (B, S, N) (one
    group for all heads) or (B, S, G, N) (head h reads group h // (H / G)),
    d (H,) -> y (B, S, H, P) in x's type. ``chunk``: tokens a chunk of the
    ``jax.numpy`` form (None: the route's arm, ``ssd_route``). Where no
    chunk divides S the token-by-token recurrence runs."""
    s, h, p = x.shape[1:]
    n = b.shape[-1]
    groups = b.shape[2] if b.ndim == 4 else 1
    if chunk is None:
        q = _pallas_chunk(s, h, p, n, groups)
        if q:
            from .ssd_pallas import ssd_scan_pallas
            return ssd_scan_pallas(x, dt, a, b, c, d, q, False)
        chunk = ssd_chunk(s)
    if chunk is None:
        return ssd_recurrence(x, dt, a, b, c, d).astype(x.dtype)
    if s % chunk:
        raise ValueError(f"chunk {chunk} does not divide S={s}")
    if b.ndim == 4:
        return _by_group(
            lambda *one: _ssd_chunked(*one, int(chunk)), x, dt, a, b, c, d)
    return _ssd_chunked(x, dt, a, b, c, d, int(chunk))
