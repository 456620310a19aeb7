"""Mixture-of-experts transformer — expert parallelism over an "expert" axis.

Completes the framework's parallelism set (dp/sp/tp/pp/ep). The reference's
distributed substrate is a parameter server moving dense gradients
(SURVEY §2.2); expert parallelism has no 2015 analog — it exists here because
the mandate makes large-scale distributed training first-class. The design is
the standard TPU MoE recipe (Switch/GShard): top-1 routing with a fixed
per-source capacity so every shape is static, dispatch/combine as einsums
against a one-hot dispatch tensor, and ONE pair of `lax.all_to_all`
collectives per MoE layer to move tokens to their experts and back. Token
dropping (over-capacity) is a masked select, not control flow — XLA sees a
fixed program.

Gradient flow: the router learns through the gate probability that scales
each expert's output (straight-through top-1, Switch §2.2 of the paper
family); dropped tokens pass through the residual only. The all_to_all
transpose routes expert-weight cotangents back to the owning rank, so expert
grads arrive summed over the expert-axis group with no explicit collective;
replicated-leaf grads need the usual psum (done OUTSIDE the differentiated
region — see build_dp_tp_train_step's note on psum transposition).

Losses are normalized by the STATIC global token count so the cross-device
reduction is a plain psum (exact, order-independent)."""

from __future__ import annotations

from dataclasses import dataclass
from functools import partial
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax, shard_map
from jax.sharding import Mesh, PartitionSpec as P

from ..config import matmul_precision, policy
from ..proto.messages import SolverParameter
from ..solvers.updates import SolverState, make_update_fn
from .transformer import (TransformerConfig, _dense, _layer_norm,
                          attention_sublayer, embed_tokens, lm_head,
                          transformer_mults)


@dataclass(frozen=True)
class MoEConfig:
    base: TransformerConfig
    n_experts: int = 8
    # tokens each SOURCE shard may send to each expert; 0 = auto from
    # capacity_factor (even-load tokens * factor, rounded up)
    capacity: int = 0
    capacity_factor: float = 1.25
    aux_weight: float = 0.01


def resolved_capacity(cfg: MoEConfig, n_tokens: int) -> int:
    if cfg.capacity:
        return cfg.capacity
    return int(np.ceil(n_tokens / cfg.n_experts * cfg.capacity_factor))


def init_moe_params(cfg: MoEConfig, rng: jax.Array) -> Dict:
    """Like transformer.init_params but each block's dense FFN is replaced
    by a router ``wg`` (E, D) and per-expert stacks ``w1e`` (E, F, D) /
    ``w2e`` (E, D, F); the leading E axis is what shards over "expert"."""
    b = cfg.base

    def dense(key, fan_in, shape):
        return (jax.random.normal(key, shape, jnp.float32)
                * (1.0 / np.sqrt(fan_in)))

    keys = jax.random.split(rng, 4 + 8 * b.n_layers)
    params: Dict = {
        "embed": {"w": dense(keys[0], 1, (b.vocab_size, b.d_model)) * 0.02},
        "pos": {"w": dense(keys[1], 1, (b.max_seq, b.d_model)) * 0.02},
        "head": {"w": dense(keys[2], b.d_model, (b.vocab_size, b.d_model))},
        "ln_f": {"g": jnp.ones((b.d_model,)), "b": jnp.zeros((b.d_model,))},
    }
    for i in range(b.n_layers):
        k = keys[4 + 8 * i:4 + 8 * (i + 1)]
        params[f"block{i}"] = {
            "wqkv": dense(k[0], b.d_model, (3 * b.d_model, b.d_model)),
            "wo": dense(k[1], b.d_model, (b.d_model, b.d_model)),
            "wg": dense(k[2], b.d_model, (cfg.n_experts, b.d_model)),
            "w1e": dense(k[3], b.d_model,
                         (cfg.n_experts, b.d_ff, b.d_model)),
            "w2e": dense(k[4], b.d_ff, (cfg.n_experts, b.d_model, b.d_ff)),
            "ln1_g": jnp.ones((b.d_model,)),
            "ln1_b": jnp.zeros((b.d_model,)),
            "ln2_g": jnp.ones((b.d_model,)),
            "ln2_b": jnp.zeros((b.d_model,)),
        }
    return params


def _experts_apply(w1e, w2e, toks):
    """toks (E_local, N, D) through each local expert's gelu FFN."""
    def one(w1, w2, t):
        return _dense(jax.nn.gelu(_dense(t, w1)), w2)
    return jax.vmap(one)(w1e, w2e, toks)


def moe_ffn(x: jax.Array, wg: jax.Array, w1e: jax.Array, w2e: jax.Array,
            cfg: MoEConfig, *, expert_axis: Optional[str] = None,
            n_expert_ranks: int = 1) -> Tuple[jax.Array, jax.Array]:
    """Top-1 switch FFN over flat tokens x (T, D) -> (y (T, D), aux loss).

    With ``expert_axis``, ``w1e``/``w2e`` hold only this rank's
    E/n_expert_ranks experts and tokens move over the mesh: dispatch einsum
    -> all_to_all (tokens to owning rank) -> local expert FFNs ->
    all_to_all back -> combine einsum. Without it, all experts are local
    and the same code skips the exchange — the single-device reference the
    parity test checks against."""
    t_local, d = x.shape
    n_exp = cfg.n_experts
    cap = resolved_capacity(cfg, t_local)

    logits = _dense(x, wg).astype(jnp.float32)      # (T, E)
    gates = jax.nn.softmax(logits, axis=-1)
    e_star = jnp.argmax(gates, axis=-1)             # (T,)
    gate = jnp.take_along_axis(gates, e_star[:, None], axis=1)[:, 0]
    onehot = jax.nn.one_hot(e_star, n_exp, dtype=jnp.float32)
    # position of each token in its expert's queue; beyond-capacity drops
    pos = jnp.cumsum(onehot, axis=0) * onehot - 1.0
    keep = (pos >= 0) & (pos < cap)                 # (T, E)
    slot = jax.nn.one_hot(jnp.clip(pos, 0, cap - 1).astype(jnp.int32),
                          cap, dtype=jnp.float32)   # (T, E, C)
    disp = slot * keep[..., None]                   # 0/1 dispatch tensor
    comb = disp * gate[:, None, None]               # gate-weighted combine

    xd = jnp.einsum("tec,td->ecd", disp.astype(x.dtype), x)  # (E, C, D)
    if expert_axis is not None:
        e_local = n_exp // n_expert_ranks
        xd = xd.reshape(n_expert_ranks, e_local, cap, d)
        # rank r keeps its expert slice from every source rank; after the
        # exchange axis 0 indexes the SOURCE rank
        xd = lax.all_to_all(xd, expert_axis, split_axis=0, concat_axis=0)
        toks = xd.transpose(1, 0, 2, 3).reshape(e_local,
                                                n_expert_ranks * cap, d)
        out = _experts_apply(w1e, w2e, toks).astype(x.dtype)
        out = out.reshape(e_local, n_expert_ranks, cap, d) \
            .transpose(1, 0, 2, 3)
        out = lax.all_to_all(out, expert_axis, split_axis=0, concat_axis=0)
        out = out.reshape(n_exp, cap, d)
    else:
        out = _experts_apply(w1e, w2e, xd).astype(x.dtype)
    y = jnp.einsum("tec,ecd->td", comb.astype(x.dtype), out)

    # Switch load-balancing loss: n_exp * sum_e fraction_e * mean_gate_e
    frac = jnp.mean(onehot, axis=0)
    mean_gate = jnp.mean(gates, axis=0)
    aux = cfg.aux_weight * n_exp * jnp.sum(frac * mean_gate)
    return y, aux


# --------------------------------------------------------------------------- #
# Token-choice top-k, dropless (OLMoE / Mixtral-style): what the MOE layer of
# core/layers.py wraps, beside the switch path above.
# --------------------------------------------------------------------------- #

GROUPED_MATMUL = "ragged_dot"   # the arm every MOE layer takes (kernel_routes)


def topk_route(logits: jax.Array, top_k: int):
    """Router logits (T, E) f32 -> (probs (T, E), weights (T, k), experts
    (T, k)): softmax over all experts in f32, the k largest kept, their
    weights as they are (OLMoE's ``norm_topk_prob`` false)."""
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    weights, experts = lax.top_k(probs, top_k)
    return probs, weights, experts


def router_losses(logits: jax.Array, probs: jax.Array, sizes: jax.Array):
    """(load-balancing loss, router z loss), unweighted, from the router's
    logits and probabilities (T, E) and the assignments per expert (E,).
    Load balancing is E * sum_e f_e P_e with f_e the fraction of tokens that
    have expert e among their k (the f_e sum to k) and P_e the mean router
    probability; z is mean(logsumexp(logits)^2)."""
    n_tok, n_exp = logits.shape
    f = sizes.astype(jnp.float32) / n_tok
    lb = n_exp * jnp.sum(f * jnp.mean(probs, axis=0))
    z = jnp.mean(jax.nn.logsumexp(logits.astype(jnp.float32), axis=-1) ** 2)
    return lb, z


def _grouped(x, w, group_sizes):
    """Rows of x (M, K), sorted by group, times their group's w[g] (N, K),
    weights as Caffe stores them (out x in) -> (M, N). The weights reach
    ``lax.ragged_dot`` as (G, K, N): the transpose rides the cast to the
    compute dtype, and contracting the stored layout's last axis instead
    (``ragged_dot_general``) ran this product at 26 TFLOP/s on the v5e
    where this form runs at 132 (PR 25, PERF.md)."""
    return _grouped_cast(x.astype(policy().compute_dtype), w, group_sizes)


@jax.custom_vjp
def _grouped_cast(xc, w, group_sizes):
    return lax.ragged_dot(xc, jnp.swapaxes(w.astype(xc.dtype), 1, 2),
                          group_sizes, precision=matmul_precision())


def _grouped_fwd(xc, w, group_sizes):
    return _grouped_cast(xc, w, group_sizes), (xc, w, group_sizes)


# dW[g] = dy_g^T x_g: the ragged dimension (the sorted rows) contracts, and
# with dy as the left operand the result is (G, N, K)
_DW_DIMS = lax.RaggedDotDimensionNumbers(
    dot_dimension_numbers=(((0,), (0,)), ((), ())),
    lhs_ragged_dimensions=[0], rhs_group_dimensions=[])


def _grouped_bwd(res, dy):
    """The backward written out for the weight gradient's sake. dx = dy
    times the stored (G, N, K) weights, the product autodiff makes too.
    dw = dy_g^T x_g per group, born (G, N, K) as the stack is stored:
    autodiff's x_g^T dy_g is (G, K, N), and on the v5e the compiler then ran
    ``gate``'s and ``up``'s Adam fusions in the transposed layout, behind
    six 537 MB relayout copies of weight and moments a stack (PR 30,
    PERF.md). Same products, same kernel, same precision: operands in the
    compute dtype, dw cast to the stack's dtype where the forward cast's
    transpose did it."""
    xc, w, group_sizes = res
    prec = matmul_precision()
    dx = lax.ragged_dot(dy, w.astype(xc.dtype), group_sizes, precision=prec)
    dw = lax.ragged_dot_general(dy, xc, group_sizes, _DW_DIMS,
                                precision=prec)
    return dx, dw.astype(w.dtype), None


_grouped_cast.defvjp(_grouped_fwd, _grouped_bwd)


def expert_sizes(experts: jax.Array, n_exp: int):
    """(T, k) chosen experts -> (the same flat (T*k,), assignments per
    expert (E,) int32)."""
    flat_e = experts.reshape(-1)
    return flat_e, jnp.zeros((n_exp,), jnp.int32).at[flat_e].add(1)


# Rows of the sorted assignments are handed to the grouped matmuls in
# multiples of this many: the MXU's 128 rows, what the prefix rung of the
# held arm is rounded up to.
_ROW_TILE = 128


def held_row_ladder(rows: int, n_held: int, n_exp: int):
    """The static prefix lengths ("rungs") over which ``expert_ffn``'s held
    arm may run its row work, shortest first; the last is always all
    ``rows`` = T k sorted assignments. A rule of the shapes and nothing
    else: the first rung is TWICE the even share of a rank that holds
    ``n_held`` of ``n_exp`` experts, rounded up to ``_ROW_TILE``; where
    that is all the rows or more (half the experts held: ZAYA1) the ladder
    is the single full rung and no conditional is traced. (A middle rung at
    four times the even share was tried for Trinity-Mini, whose first MoE
    layer holds 35-40% of the assignments: the step then compiles at 15.23
    GB, over the 85% it is sized by. PERF.md, PR 37.)"""
    first = -(-2 * rows * n_held // n_exp // _ROW_TILE) * _ROW_TILE
    return (first, rows) if first < rows else (rows,)


def _combine(out, order, weights, dtype):
    """All T k sorted rows ``out`` (T*k, D) back to their tokens by the
    inverse permutation, a token's k summed with its ``weights`` (T, k) in
    f32 and handed back as ``dtype``."""
    t, top_k = weights.shape
    back = jnp.zeros_like(order).at[order].set(jnp.arange(t * top_k))
    y = jnp.sum(out[back].reshape(t, top_k, -1).astype(jnp.float32)
                * weights[..., None], axis=1)
    return y.astype(dtype)


def _held_rows(x, weights, here, order, sizes, gate, up, down, rows):
    """The held arm's row work over the first ``rows`` of the sorted
    assignments (``order``: the held experts' rows first, by expert; absent
    ones behind them), as an f32-summed (T, D) in x's dtype. ``sizes`` are
    the held experts' (G,), ``here`` (T*k,) says which assignments fell on
    one. Exact whenever the live rows, ``sum(sizes)``, number at most
    ``rows``.

    ``rows`` = T k is the whole sort: the inverse permutation brings every
    assignment's result back to its token and the k are summed over an
    axis. A shorter prefix touches nothing of T k rows times a feature
    width: P rows are gathered, multiplied, and scatter-added, weighted,
    into the tokens' f32 sums (the same k terms in another order)."""
    t, d = x.shape
    top_k = weights.shape[1]
    # rows past the last group belong to no expert: what a grouped
    # matmul leaves there is masked on the way in (so is their
    # cotangent) and on the way out
    live = (jnp.arange(rows) < jnp.sum(sizes))[:, None]

    def grouped(rows_, w):
        return jnp.where(live, _grouped(jnp.where(live, rows_, 0), w,
                                        sizes), 0)

    full = rows == t * top_k
    head = order if full else order[:rows]
    tok = head // top_k
    xs = x[tok]                                     # (rows, D)
    h = jax.nn.silu(grouped(xs, gate)) * grouped(xs, up)
    out = grouped(h, down)                          # (rows, D)
    weights = weights * here.reshape(t, top_k)
    if full:
        return _combine(out, order, weights, x.dtype)
    y = jnp.zeros((t, d), jnp.float32).at[tok].add(
        out.astype(jnp.float32) * weights.reshape(-1)[head][:, None])
    return y.astype(x.dtype)


# A rung's body as the ladder calls it: a function of its own in the
# program, so that the layers of one shape, the forward's and the backward's
# conditionals share ONE trace and one lowering of each rung (on the chip's
# host the ladder lowers in +0.9 s over the parent's arm this way, +2.9 s
# traced in place; the compiler inlines the calls: PERF.md, PR 37)
_held_rows_jit = jax.jit(_held_rows, static_argnums=8)


def expert_ffn(x: jax.Array, weights: jax.Array, flat_e: jax.Array,
               sizes: jax.Array, gate: jax.Array, up: jax.Array,
               down: jax.Array, held_first: int = 0):
    """The experts' part of a dropless MoE, shared by every router: x
    (T, D), each token's k router ``weights`` (T, k), its experts flat
    (T*k,) and ``sizes`` (E,), the assignments per expert over ALL E the
    router scores (``expert_sizes``).
    The T*k assignments are sorted by expert, each projection is ONE grouped
    matmul over the sorted rows (``lax.ragged_dot``) and a token's k results
    are summed with its weights.

    The stacks hold experts ``held_first .. held_first + G - 1``, G =
    ``gate.shape[0]``. G = E is a layer that owns every expert it routes
    to. G < E is one rank's share of an expert-parallel layer: assignments
    to an absent expert sort behind the held ones, lie outside every group
    of the grouped matmuls (no work) and add ZERO to y, so that the shares
    of the ranks sum to the whole layer's output.

    The held arm's row work (gather, masks, activation, combine) runs over
    a PREFIX of the sorted rows chosen at run time by the live count:
    ``held_row_ladder`` gives the static lengths from T k, G and E alone
    (twice the even share, then everything), a ``lax.cond`` takes the
    shortest rung that holds every live row, and the full rung is the
    overflow path that keeps the layer dropless for any routing. With one
    rung (G / E >= 1/2) no conditional is traced. The ladder's derivative
    is written out (``_held_ladder``, a ``custom_vjp``): the forward saves
    its inputs and the backward differentiates the rung taken alone, since
    autodiff through the conditional keeps the residuals of both rungs."""
    t, d = x.shape
    top_k = weights.shape[1]
    n_exp, n_held = sizes.shape[0], gate.shape[0]
    if n_held == n_exp:
        order = jnp.argsort(flat_e, stable=True)    # assignments by expert
        xs = x[order // top_k]                      # (T*k, D) sorted rows
        h = jax.nn.silu(_grouped(xs, gate, sizes)) * _grouped(xs, up, sizes)
        return _combine(_grouped(h, down, sizes), order, weights, x.dtype)
    local = flat_e - held_first
    here = (local >= 0) & (local < n_held)
    order = jnp.argsort(jnp.where(here, local, n_held), stable=True)
    sizes = sizes[held_first:held_first + n_held]
    rungs = held_row_ladder(t * top_k, n_held, n_exp)
    if len(rungs) == 1:
        return _held_rows(x, weights, here, order, sizes, gate, up, down,
                          rungs[0])
    return _held_ladder(rungs, x, weights, gate, up, down, here, order,
                        sizes)


def _take_rung(rungs, sizes, branch, *operands):
    """``branch(rows)(*operands)`` for the shortest rung that holds every
    live row."""
    prefix, full = rungs
    return lax.cond(jnp.sum(sizes) <= prefix, branch(prefix), branch(full),
                    *operands)


@partial(jax.custom_vjp, nondiff_argnums=(0,))
def _held_ladder(rungs, x, weights, gate, up, down, here, order, sizes):
    """``_held_rows`` over the rung the live count picks. Its derivative is
    written out for the memory's sake: autodiff through the conditional
    keeps BOTH rungs' residuals (zeros for the one not taken), and
    Trinity-Mini's step at the cell's batch then no longer fits the chip
    (PERF.md, PR 37). The forward saves what is live anyway; the backward
    takes the same rung and differentiates it alone, its forward once more
    inside the branch (prefix-sized where the prefix rung was taken)."""
    return _take_rung(rungs, sizes,
                      lambda rows: lambda *a: _held_rows_jit(*a, rows),
                      x, weights, here, order, sizes, gate, up, down)


def _held_ladder_fwd(rungs, x, weights, gate, up, down, here, order, sizes):
    return (_held_ladder(rungs, x, weights, gate, up, down, here, order,
                         sizes),
            (x, weights, gate, up, down, here, order, sizes))


def _held_ladder_bwd(rungs, res, dy):
    *primals, here, order, sizes = res

    def branch(rows):
        def bwd(dy, *primals):
            return jax.vjp(lambda x, weights, gate, up, down: _held_rows_jit(
                x, weights, here, order, sizes, gate, up, down, rows),
                *primals)[1](dy)
        return bwd

    return _take_rung(rungs, sizes, branch, dy, *primals) + (None,) * 3


_held_ladder.defvjp(_held_ladder_fwd, _held_ladder_bwd)


def moe_dropless(x: jax.Array, router: jax.Array, gate: jax.Array,
                 up: jax.Array, down: jax.Array, top_k: int,
                 held_first: int = 0):
    """Top-k token-choice MoE over flat tokens x (T, D), no capacity: every
    token is computed by all k of its experts whatever the load.

    router (E, D) scores all E experts; gate, up (G, F, D) and down
    (G, D, F) are the G experts held here (``expert_ffn``; G = E: all).
    Returns (y (T, D), load-balancing loss, z loss, assignments per expert
    (E,) int32)."""
    n_exp = router.shape[0]
    # the router runs in f32 whatever the policy: top-k is discontinuous,
    # and a bf16 logit flips which experts a token gets
    logits = lax.dot_general(x.astype(jnp.float32), router,
                             (((1,), (1,)), ((), ())),
                             precision=lax.Precision.HIGHEST)
    probs, weights, experts = topk_route(logits, top_k)
    flat_e, sizes = expert_sizes(experts, n_exp)
    lb, z = router_losses(logits, probs, sizes)
    y = expert_ffn(x, weights, flat_e, sizes, gate, up, down, held_first)
    return y, lb, z, sizes


def moe_gated(x: jax.Array, gates: jax.Array, gate: jax.Array, up: jax.Array,
              down: jax.Array, top_k: int, held_first: int = 0):
    """``moe_dropless`` behind a router that is a layer of its own
    (``mlp_router``): ``gates`` (T, E) f32 hold each token's k chosen
    experts' weights and zero elsewhere. Returns (y, assignments per expert
    (E,) int32)."""
    weights, experts = lax.top_k(gates, top_k)
    flat_e, sizes = expert_sizes(experts, gates.shape[1])
    return expert_ffn(x, weights, flat_e, sizes, gate, up, down,
                      held_first), sizes


def sigmoid_router(h: jax.Array, w: jax.Array, bias: jax.Array, top_k: int,
                   route_scale: float, rate: float):
    """A sigmoid router with a selection bias (the DeepSeek-V3 family's,
    no group limit) over flat tokens h (T, D), in f32 whatever the policy
    (top-k is discontinuous):

        s = sigmoid(h w^T)                       (T, E)
        chosen = the top_k largest of s + bias   the bias chooses,
        weight_e = s_e / sum_chosen s * scale    it does not weigh

    ``bias`` (E,) takes no gradient; it is balanced by the step's own
    loads as ``mlp_router``'s is: bias_e + rate * sign(T k / E - n_e), n_e
    the assignments to e (``rate``: the layer's ``bias_update_rate``).
    Returns (gates (T, E) = the weights at the chosen experts and zero
    elsewhere, the bias's next value)."""
    f32 = jnp.float32
    s = jax.nn.sigmoid(lax.dot_general(
        h.astype(f32), w.astype(f32), (((1,), (1,)), ((), ())),
        precision=lax.Precision.HIGHEST))
    bias = lax.stop_gradient(bias.astype(f32))
    _, experts = lax.top_k(s + bias, top_k)
    chosen = jnp.sum(jax.nn.one_hot(experts, s.shape[1], dtype=f32), axis=1)
    gates = s * chosen
    gates = gates / jnp.sum(gates, axis=-1, keepdims=True)
    mean_load = s.shape[0] * top_k / s.shape[1]
    return gates * route_scale, bias + rate * jnp.sign(
        mean_load - jnp.sum(chosen, axis=0))


def mlp_router(h: jax.Array, r_prev: Optional[jax.Array], down: jax.Array,
               mix: jax.Array, w1: jax.Array, w2: jax.Array, w3: jax.Array,
               bias: jax.Array, rate: float):
    """ZAYA1's router (arXiv:2511.17127) over flat tokens h (T, D), in f32
    whatever the policy (top-1 is discontinuous):

        r = h down^T + mix * r_prev              (T, R); r_prev: the layer
                                                  before's r, None = zero
        s = w3 gelu(w2 gelu(w1 r))               (T, E)
        p = softmax(s);  e(t) = argmax_e (p_e + bias_e)

    ``bias`` (E,) takes no gradient; it is balanced by the step's own
    loads: bias_e + rate * sign(T / E - n_e), n_e the tokens that chose e
    and ``rate`` the layer's ``bias_update_rate``.
    Returns (r, gates (T, E) = p at the chosen expert and zero elsewhere,
    the bias's next value)."""
    f32, hi = jnp.float32, lax.Precision.HIGHEST

    def mm(a, w):                                   # a (T, in), w (out, in)
        return lax.dot_general(a, w.astype(f32), (((1,), (1,)), ((), ())),
                               precision=hi)

    r = mm(h.astype(f32), down)
    if r_prev is not None:
        r = r + mix.astype(f32) * r_prev.astype(f32)
    s = mm(jax.nn.gelu(mm(jax.nn.gelu(mm(r, w1), approximate=False), w2),
                       approximate=False), w3)
    p = jax.nn.softmax(s, axis=-1)
    bias = lax.stop_gradient(bias.astype(f32))
    n_exp = p.shape[1]
    chosen = jax.nn.one_hot(jnp.argmax(p + bias, axis=-1), n_exp, dtype=f32)
    load = jnp.sum(chosen, axis=0)
    bias_next = bias + rate * jnp.sign(p.shape[0] / n_exp - load)
    return r, p * chosen, bias_next


def moe_forward(params: Dict, cfg: MoEConfig, tokens: jax.Array,
                *, expert_axis: Optional[str] = None,
                n_expert_ranks: int = 1) -> Tuple[jax.Array, jax.Array]:
    """tokens (B, S) -> (logits (B, S, V), summed aux loss). Entry/exit
    scaffold (embed/pos, final ln + head) is shared with the dense model;
    ``cfg.base.remat`` checkpoints each MoE block like every other path."""
    b_sz, s = tokens.shape
    bcfg = cfg.base
    x = embed_tokens(params, tokens)

    def moe_block(x, blk):
        x = attention_sublayer(bcfg, x, blk)
        h = _layer_norm(x, blk["ln2_g"], blk["ln2_b"])
        y, aux = moe_ffn(h.reshape(b_sz * s, bcfg.d_model), blk["wg"],
                         blk["w1e"], blk["w2e"], cfg,
                         expert_axis=expert_axis,
                         n_expert_ranks=n_expert_ranks)
        return x + y.reshape(b_sz, s, bcfg.d_model).astype(x.dtype), aux

    if bcfg.remat:
        # drop the dispatch/combine tensors (O(T x E x C)) and attention
        # internals from the stored residuals, like the dense paths do
        moe_block = jax.checkpoint(moe_block)
    aux_total = jnp.zeros((), jnp.float32)
    for i in range(bcfg.n_layers):
        x, aux = moe_block(x, params[f"block{i}"])
        aux_total = aux_total + aux
    return lm_head(params, x), aux_total


def ep_param_specs(params: Dict, expert_axis: str = "expert") -> Dict:
    """Expert stacks split on their leading E axis; everything else
    (attention, router, embeddings, head, norms) replicated."""
    return {lname: {leaf: (P(expert_axis) if leaf in ("w1e", "w2e")
                           else P())
                    for leaf in lp}
            for lname, lp in params.items()}


def build_dp_ep_train_step(cfg: MoEConfig, sp: SolverParameter, mesh: Mesh,
                           params: Dict, data_axis: str = "data",
                           expert_axis: str = "expert",
                           donate: bool = True):
    """Training step over a 2-D (data x expert) mesh. The batch shards over
    BOTH axes (every device works distinct tokens); expert stacks shard
    over ``expert_axis``; each MoE layer runs one all_to_all out and one
    back within the expert-axis group.

    Losses are local-sum / STATIC global token count, so: replicated-leaf
    grads psum over both axes; expert-leaf grads arrive already summed over
    the expert group (all_to_all transpose) and psum over ``data_axis``
    only. Both psums sit outside the differentiated region."""
    n_exp_ranks = dict(zip(mesh.axis_names, mesh.devices.shape))[expert_axis]
    n_data = dict(zip(mesh.axis_names, mesh.devices.shape))[data_axis]
    if cfg.n_experts % n_exp_ranks:
        raise ValueError(f"n_experts={cfg.n_experts} not divisible by "
                         f"{n_exp_ranks} expert ranks")
    specs = ep_param_specs(params, expert_axis)
    n_dev = n_exp_ranks * n_data

    def device_step(p, state: SolverState, tokens, targets, rng):
        b_local, s_len = tokens.shape
        inv_total = 1.0 / float(b_local * s_len * n_dev)

        def loss_fn(pp):
            logits, aux = moe_forward(pp, cfg, tokens,
                                      expert_axis=expert_axis,
                                      n_expert_ranks=n_exp_ranks)
            logp = jax.nn.log_softmax(logits, axis=-1)
            picked = jnp.take_along_axis(logp, targets[..., None], axis=-1)
            # local sums over the static GLOBAL normalizers: cross-device
            # psum then reconstructs the exact global mean
            return -jnp.sum(picked) * inv_total + aux / float(n_dev)

        loss, grads = jax.value_and_grad(loss_fn)(p)
        grads = {lname: {leaf: (lax.psum(g, data_axis)
                                if leaf in ("w1e", "w2e")
                                else lax.psum(lax.psum(g, data_axis),
                                              expert_axis))
                         for leaf, g in lg.items()}
                 for lname, lg in grads.items()}
        upd = make_update_fn(sp, transformer_mults(p))
        new_params, new_state = upd(p, grads, state)
        metrics = {"loss": lax.psum(lax.psum(loss, data_axis), expert_axis)}
        return new_params, new_state, metrics

    state_spec = SolverState(it=P(), history=specs)
    sharded = shard_map(
        device_step, mesh=mesh,
        in_specs=(specs, state_spec, P((data_axis, expert_axis)),
                  P((data_axis, expert_axis)), P()),
        out_specs=(specs, state_spec, P()),
        check_vma=False)
    return jax.jit(sharded, donate_argnums=(0, 1) if donate else ())
