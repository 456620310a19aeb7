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
# multiples of this many: the MXU's 128 rows, what a chunk of the held arm
# is rounded up to.
_ROW_TILE = 128


# A trip of the held arm's loop costs two sums of its rows into (T, D) f32
# and a read-add-write of the three stacks' gradient sums whatever its
# length: 2.5-3.5 ms on the v5e at Kimi-Linear's and Trinity's shapes, the
# row work of some 8,000 rows (PERF.md, PR 43), and about 5 ms at
# SmallThinker's (T 16,384 x 2,560) since its sums run on the MXU
# (``held_sum_on_mxu``; 14 ms before: PERF.md, PR 53). No chunk is shorter.
_CHUNK_FLOOR = 8192


def held_chunk_rows(rows: int, n_held: int, n_exp: int) -> int:
    """The chunk length P of ``expert_ffn``'s held arm: how many of the
    ``rows`` = T k sorted assignments one trip of its loop takes. A rule of
    the shapes and nothing else: the EVEN share of a rank that holds
    ``n_held`` of ``n_exp`` experts, no less than ``_CHUNK_FLOOR`` and no
    more than all the rows, in whole ``_ROW_TILE``s (8,192 of
    Kimi-Linear's 65,536, where the even share is 2,048; 16,384 of
    Trinity-Mini's 131,072; 8,192 of ZAYA1's 16,384). Half the even share,
    the even share and twice it were measured at the three cells' shapes
    before the floor was set (PERF.md, PR 43)."""
    even = -(-rows * n_held // n_exp)
    return -(-min(max(even, _CHUNK_FLOOR), rows) // _ROW_TILE) * _ROW_TILE


# Widths D at which XLA's scatter-add of rows into a (T, D) f32 array runs
# off its fast path on the v5e: it costs 0.4 us (2,560) and 1.8 us (5,120)
# a row of the TARGET, whatever the rows added, where the twelve other
# widths read from 512 to 4,096 pay 0.01-0.2 (7.5 / 31.4 ms for 8,192 rows
# into 16,384 x 2,560 / x 5,120 against 1.65 into x 2,048 and 2.0 into
# x 2,816; PERF.md, PR 53). No formula of the width tells them apart.
_SCATTER_CLIFFS = frozenset({2560, 5120})


def held_sum_on_mxu(width: int) -> bool:
    """How a trip of ``expert_ffn``'s held arm sums its chunk's rows into
    the (T, D) f32 carry: on the MXU (``_tile_sum``) where XLA's
    scatter-add is off its fast path at D = ``width``
    (``_SCATTER_CLIFFS``: SmallThinker's 2,560, 9.9 ms a sum of 24,576 rows
    against 5.0), by the scatter-add at every other width, where the two
    cost the same to a tenth or the scatter less (Trinity's 2,048: 2.1 ms
    a sum in the layer against 2.8; Kimi-Linear's 2,304). A rule of the
    shapes and nothing else, as the chunk is."""
    return width in _SCATTER_CLIFFS


def _combine(out, order, weights, dtype):
    """All T k sorted rows ``out`` (T*k, D) back to their tokens by the
    inverse permutation, a token's k summed with its ``weights`` (T, k) in
    f32 and handed back as ``dtype``."""
    t, top_k = weights.shape
    back = jnp.zeros_like(order).at[order].set(jnp.arange(t * top_k))
    y = jnp.sum(out[back].reshape(t, top_k, -1).astype(jnp.float32)
                * weights[..., None], axis=1)
    return y.astype(dtype)


# What an expert is, by its ``act``: "silu" / "relu", the gated unit
# down(act(gate x) * (up x)) of three stacks; "relu2", the UNGATED squared
# ReLU down(relu(up x)^2) of two (``gate`` is None: Nemotron-H's expert).
EXPERT_ACTS = ("silu", "relu", "relu2")
UNGATED_ACT = "relu2"


def _act(a, act: str):
    """The activation on an expert's pre-activation ``a``: the gate's in a
    gated unit (act(a) * b), the whole unit's where there is no gate."""
    if act == UNGATED_ACT:
        return jnp.square(jax.nn.relu(a))
    return jax.nn.silu(a) if act == "silu" else jax.nn.relu(a)


def _hidden(grouped, xs, gate, up, act: str):
    """(a, h) of rows ``xs`` in the straight-line arms: the pre-activation
    the activation reads and the unit's hidden rows, ``grouped(rows, w)``
    being the arm's grouped matmul. With a gate a = gate x and h = act(a) *
    (up x); without (``gate`` None) a = up x and h = act(a)."""
    if gate is None:
        a = grouped(xs, up)
        return a, _act(a, act)
    a = grouped(xs, gate)
    return a, _act(a, act) * grouped(xs, up)


def expert_ffn(x: jax.Array, weights: jax.Array, flat_e: jax.Array,
               sizes: jax.Array, gate: jax.Array, up: jax.Array,
               down: jax.Array, held_first: int = 0, act: str = "silu",
               gate_zeros: bool = False):
    """The experts' part of a dropless MoE, shared by every router: x
    (T, D), each token's k router ``weights`` (T, k), its experts flat
    (T*k,) and ``sizes`` (E,), the assignments per expert over ALL E the
    router scores (``expert_sizes``).
    The T*k assignments are sorted by expert, each projection is ONE grouped
    matmul over the sorted rows (``lax.ragged_dot``) and a token's k results
    are summed with its weights.

    The stacks hold experts ``held_first .. held_first + G - 1``, G =
    ``up.shape[0]``. G = E is a layer that owns every expert it routes
    to. G < E is one rank's share of an expert-parallel layer: assignments
    to an absent expert sort behind the held ones, lie outside every group
    of the grouped matmuls (no work) and add ZERO to y, so that the shares
    of the ranks sum to the whole layer's output.

    The held arm's row work (gather, grouped matmuls, masks, activation,
    combine) runs in chunks of P = ``held_chunk_rows(T k, G, E)`` sorted
    rows under a loop whose trip count is the live rows' (``_held_chunks``):
    ``ceil(live / P)`` trips, up to ``ceil(T k / P)``, so the cost follows
    the share of the assignments this rank really holds and the layer is
    dropless for any routing. No array of T k rows times a feature width
    exists; what is left of T k are the sort and its vectors of scalars.
    A trip's rows are summed into (T, D) f32 on the MXU (``_tile_sum``) or
    by XLA's scatter-add, whichever the width D runs faster
    (``held_sum_on_mxu``): the same f32 terms either way.
    Where two chunks at most hold every row (half the experts held: ZAYA1)
    there is nothing for a loop to skip, and the rows run as straight-line
    code (``_held_rows``): a rule of the shapes, as the chunk is.

    An expert is down(act(gate x) * (up x)), ``act`` one of
    ``EXPERT_ACTS``; with ``act`` "relu2" it has NO gate (``gate`` None):
    down(relu(up x)^2), through every arm below (``_hidden``; the held
    arm's pullback then has d up = 2 relu(a) d h on the live rows and no
    d gate). With ``gate_zeros`` the result is (y, the share of the held
    experts' LIVE rows' pre-activations — the gate's, or up's where there is
    no gate — that are <= 0, an f32 scalar without a gradient): what a ReLU
    zeroes, counted where the pre-activation is at hand."""
    if (gate is None) != (act == UNGATED_ACT):
        raise ValueError(
            f"expert_ffn: act {act!r} of {EXPERT_ACTS} "
            + ("is the ungated expert's and takes no gate stack"
               if gate is not None else "is a gated unit's and needs one"))
    top_k = weights.shape[1]
    n_exp, n_held = sizes.shape[0], up.shape[0]
    if n_held == n_exp:
        order = jnp.argsort(flat_e, stable=True)    # assignments by expert
        xs = x[order // top_k]                      # (T*k, D) sorted rows
        a, h = _hidden(lambda rows, w: _grouped(rows, w, sizes), xs, gate,
                       up, act)
        y = _combine(_grouped(h, down, sizes), order, weights, x.dtype)
        out = (y, jnp.sum(a <= 0, dtype=jnp.int32)) if gate_zeros else y
    else:
        local = flat_e - held_first
        here = (local >= 0) & (local < n_held)
        order = jnp.argsort(jnp.where(here, local, n_held), stable=True)
        sizes = sizes[held_first:held_first + n_held]
        chunk = held_chunk_rows(order.shape[0], n_held, n_exp)
        if held_rows_loop(order.shape[0], chunk):
            out = _held_chunks(chunk, x, weights, gate, up, down, order,
                               sizes, act, gate_zeros)
        else:
            out = _held_rows(x, weights, here, order, sizes, gate, up, down,
                             act, gate_zeros)
    if not gate_zeros:
        return out
    # every arm counts; the live rows are the held experts' assignments
    y, zeros = out
    return y, zeros.astype(jnp.float32) / jnp.maximum(
        jnp.sum(sizes).astype(jnp.float32) * up.shape[1], 1.0)


def held_rows_loop(rows: int, chunk: int) -> bool:
    """Whether the held arm's rows run under the loop: from three chunks
    on. With one or two (ZAYA1: 8 of 16 experts held, 40% of the rows live
    in the mean and up to 70%) the loop has at most one trip to skip and
    pays for it: its cell ran 3.9% slower and its step compiled 1.27 GB
    larger than with the straight-line rows (PERF.md, PR 43)."""
    return -(-rows // chunk) > 2


def held_rows_plan(rows: int, n_held: int, n_exp: int
                   ) -> Optional[Tuple[int, int, int]]:
    """What ``expert_ffn`` does with the ``rows`` = T k assignments of a
    layer holding ``n_held`` of ``n_exp`` experts, for who names or counts
    it: (the chunk a trip of its loop takes; twice the even share of the
    rows, in whole row tiles; T k), or None where the rows run as
    straight-line code (``held_rows_loop``)."""
    chunk = held_chunk_rows(rows, n_held, n_exp)
    if not held_rows_loop(rows, chunk):
        return None
    twice = -(-2 * rows * n_held // (n_exp * _ROW_TILE))
    return chunk, twice * _ROW_TILE, rows


def held_share_counts(chunk: int, twice_even: int, rows: int):
    """``_trips`` read from the host: from the held share a layer of this
    ``held_rows_plan`` displays for one step to what that step's held arm
    did, as increments of stats.yaml's counters. The live rows are the
    share times T k, exactly, and the loop ran the chunks that hold them;
    ``held_prefix_hits`` is a fact of the routing: live rows at most twice
    the even share."""
    def counts(share: float) -> Dict[str, float]:
        live = round(share * rows)
        trips = -(-live // chunk)
        return {"held_chunk_trips": trips, "held_rows_run": trips * chunk,
                "held_rows_live": live, "held_layer_steps": 1,
                "held_prefix_hits": live <= twice_even}
    return counts


def _held_rows(x, weights, here, order, sizes, gate, up, down,
               act: str = "silu", gate_zeros: bool = False):
    """The held arm's row work over ALL T k sorted assignments, as
    straight-line code that autodiff differentiates: every row gathered
    and multiplied, each assignment's result brought back to its token by
    the inverse permutation and the k summed over an axis. ``here`` (T*k,)
    says which assignments fell on a held expert. With ``gate_zeros``:
    (y, the live rows' gate pre-activations <= 0, an int32 count)."""
    t, top_k = weights.shape
    # rows past the last group belong to no expert: what a grouped
    # matmul leaves there is masked on the way in (so is their
    # cotangent) and on the way out
    live = (jnp.arange(t * top_k) < jnp.sum(sizes))[:, None]

    def grouped(rows_, w):
        return jnp.where(live, _grouped(jnp.where(live, rows_, 0), w,
                                        sizes), 0)

    tok = order // top_k
    xs = x[tok]                                     # (T*k, D)
    a, h = _hidden(grouped, xs, gate, up, act)
    out = grouped(h, down)                          # (T*k, D)
    weights = weights * here.reshape(t, top_k)
    y = _combine(out, order, weights, x.dtype)
    if not gate_zeros:
        return y
    return y, jnp.sum((a <= 0) & live, dtype=jnp.int32)


def _chunk(i, chunk, act, x, weights, order, sizes, ends, gate_t, up_t):
    """Trip ``i`` of the held arm as far as the activation: rows
    [i P, i P + P) of the sorted assignments ``order`` (padded to a whole
    number of chunks), the held experts' groups clipped to them (``ends``:
    the running sum of ``sizes``), and x's rows through ``gate_t`` and
    ``up_t`` (G, D, F), already in the compute dtype (``gate_t`` None: no
    gate, ``b`` None). Returns (grouped,
    groups, tok, head, xs, a, b, h, live): ``grouped(rows, w)`` is this
    chunk's grouped matmul with w (G, K, N); rows at or past the live count
    (``live`` (P, 1) false) belong to no expert, and what a grouped matmul
    leaves there is masked on the way in and on the way out."""
    lo = i * chunk
    head = lax.dynamic_slice(order, (lo,), (chunk,))
    groups = (jnp.clip(ends, lo, lo + chunk)
              - jnp.clip(ends - sizes, lo, lo + chunk))
    live = (lo + jnp.arange(chunk) < ends[-1])[:, None]
    prec = matmul_precision()

    def grouped(rows, w):
        return jnp.where(live, lax.ragged_dot(
            jnp.where(live, rows, 0), w, groups, precision=prec), 0)

    tok = head // weights.shape[1]
    xs = x[tok].astype(up_t.dtype)                  # (P, D)
    if gate_t is None:
        a, b = grouped(xs, up_t), None
        return grouped, groups, tok, head, xs, a, b, _act(a, act), live
    a, b = grouped(xs, gate_t), grouped(xs, up_t)
    return grouped, groups, tok, head, xs, a, b, _act(a, act) * b, live


def _tile_sum(rows, scale, tok, live, t):
    """A chunk's rows summed by token on the MXU: (T, D) f32 whose row
    ``tok[p]`` holds the sum of ``scale[p] * rows[p]`` over the ``live``
    rows p (rows (P, D), scale (P,) f32 or None for 1, live (P, 1)); dead
    rows, wherever they point, add nothing.

    The P positions are brought into token-TILE order (``tok // tile``, tile
    = ``_ROW_TILE`` tokens or T where T is smaller; dead rows last, outside
    every group; the order inside a tile is free), the rows gathered in that
    order, and ONE ``ragged_dot_general`` with the ragged dimension
    contracted (``_DW_DIMS``) multiplies each tile's rows by their one-hot
    columns ``tok % tile``, which carry ``scale``: (T / tile, tile, D). A
    one-hot column is exact and at ``Precision.HIGHEST`` the f32 products
    and sums are f32's, so the result is the serial ``.at[tok].add``'s to
    f32 rounding. Both operands are f32: the chip's grouped-matmul kernel
    takes no f32 columns beside bf16 rows, and bf16 columns cannot carry an
    f32 weight in one product (three bf16 slabs, three products, ran slower
    than this one: PERF.md, PR 53)."""
    f32 = jnp.float32
    tile = min(_ROW_TILE, t)
    n_tiles = -(-t // tile)
    key = jnp.where(live[:, 0], tok // tile, n_tiles)
    by_tile = jnp.argsort(key)
    in_tile = jnp.sum(key[:, None] == jnp.arange(n_tiles), axis=0,
                      dtype=jnp.int32)
    cols = jax.nn.one_hot(tok[by_tile] % tile, tile, dtype=f32)  # (P, tile)
    if scale is not None:
        cols = cols * scale[by_tile][:, None]
    y = lax.ragged_dot_general(cols, rows[by_tile].astype(f32), in_tile,
                               _DW_DIMS, precision=lax.Precision.HIGHEST)
    return y.reshape(n_tiles * tile, -1)[:t]


def _trips(order, sizes, chunk):
    """(``order`` padded with zeros to a whole number of chunks, so that a
    trip's slice is always in range; the held experts' running sizes; the
    trip count ceil(live / P), a value of the run)."""
    ends = jnp.cumsum(sizes)
    pad = -order.shape[0] % chunk
    return jnp.pad(order, (0, pad)), ends, -(-ends[-1] // chunk)


# The loops are functions of their own in the program, so that the layers
# of one shape share ONE trace and one lowering of each (the compiler
# inlines the calls). The compute dtype is an argument because the policy
# is read while tracing and is no part of a jitted function's key.
@partial(jax.jit, static_argnums=(0, 1, 2, 3))
def _held_chunks_fwd(chunk, cdtype, act, gate_zeros, x, weights, order,
                     sizes, gate, up, down):
    f32 = jnp.float32
    on_mxu = held_sum_on_mxu(x.shape[1])
    order, ends, n = _trips(order, sizes, chunk)
    # the stacks' casts and transposes are made once a pass, not once a trip
    gate_t, up_t, down_t = (
        None if w is None else jnp.swapaxes(w.astype(cdtype), 1, 2)
        for w in (gate, up, down))

    def trip(i, y):
        grouped, _, tok, head, _, a, _, h, live = _chunk(
            i, chunk, act, x, weights, order, sizes, ends, gate_t, up_t)
        out = grouped(h, down_t)                    # (P, D)
        if on_mxu:
            return y + _tile_sum(out, weights.reshape(-1)[head], tok, live,
                                 x.shape[0]), a, live
        return y.at[tok].add(out.astype(f32)
                             * weights.reshape(-1)[head][:, None]), a, live

    if not gate_zeros:
        y = lax.fori_loop(0, n, lambda i, y: trip(i, y)[0],
                          jnp.zeros(x.shape, f32))
        return y.astype(x.dtype)

    def counting(i, carry):
        y, a, live = trip(i, carry[0])
        return y, carry[1] + jnp.sum((a <= 0) & live, dtype=jnp.int32)

    y, zeros = lax.fori_loop(0, n, counting, (
        jnp.zeros(x.shape, f32), jnp.zeros((), jnp.int32)))
    return y.astype(x.dtype), zeros


@partial(jax.jit, static_argnums=(0, 1, 2))
def _held_chunks_bwd(chunk, cdtype, act, x, weights, order, sizes, gate, up,
                     down, dy):
    f32 = jnp.float32
    on_mxu = held_sum_on_mxu(x.shape[1])
    order, ends, n = _trips(order, sizes, chunk)
    gate_c, up_c, down_c = (None if w is None else w.astype(cdtype)
                            for w in (gate, up, down))
    gate_t, up_t = (None if w is None else jnp.swapaxes(w, 1, 2)
                    for w in (gate_c, up_c))
    prec = matmul_precision()

    def trip(i, carry):
        dx, dweights, dgate, dup, ddown = carry
        grouped, groups, tok, head, xs, a, b, h, live = _chunk(
            i, chunk, act, x, weights, order, sizes, ends, gate_t, up_t)

        def dw(dys, rows):                          # (G, N, K), as stored
            return lax.ragged_dot_general(dys, rows, groups, _DW_DIMS,
                                          precision=prec).astype(f32)

        dyr = dy[tok]                               # (P, D)
        w = weights.reshape(-1)[head][:, None]
        # y's row is w (h down^T): its pullback onto h is w (dy down), and
        # onto w the product of h with the same dy down, so the chunk's
        # ``out`` is not computed again
        u = grouped(dyr.astype(cdtype), down_c).astype(f32)     # (P, F)
        # a dead row's u is zero, so it adds nothing where the padding
        # points
        dweights = dweights.at[head].add(
            jnp.sum(h.astype(f32) * u, axis=-1))
        ddown = ddown + dw((dyr.astype(f32) * w).astype(cdtype), h)
        dh = u * w
        if gate is None:                # relu(a)^2: d a = 2 relu(a) d h
            da = (2 * jnp.maximum(a.astype(f32), 0) * dh).astype(cdtype)
            dup = dup + dw(da, xs)
            dxs = grouped(da, up_c).astype(f32)
        else:
            a32, b32 = a.astype(f32), b.astype(f32)
            if act == "silu":
                s = jax.nn.sigmoid(a32)
                da = (dh * b32 * s * (1 + a32 * (1 - s))).astype(cdtype)
                db = (dh * a32 * s).astype(cdtype)
            else:                       # relu: nothing passes a gate <= 0
                da = jnp.where(a32 > 0, dh * b32, 0).astype(cdtype)
                db = (dh * jnp.maximum(a32, 0)).astype(cdtype)
            dgate, dup = dgate + dw(da, xs), dup + dw(db, xs)
            dxs = (grouped(da, gate_c).astype(f32)
                   + grouped(db, up_c).astype(f32))
        dx = (dx + _tile_sum(dxs, None, tok, live, x.shape[0]) if on_mxu
              else dx.at[tok].add(dxs))
        return dx, dweights, dgate, dup, ddown

    dx, dweights, dgate, dup, ddown = lax.fori_loop(0, n, trip, (
        jnp.zeros(x.shape, f32), jnp.zeros(order.shape, f32),
        *(None if w is None else jnp.zeros(w.shape, f32)
          for w in (gate, up, down))))
    # a stack's gradient is rounded to the compute dtype, as the cotangent
    # of its cast is, and behind a barrier: the narrow copy is then what
    # lives until the update, where the compiler would keep the f32 sums
    # and round them there (0.8 GB of Trinity-Mini's step: PR 43)
    narrow = lax.optimization_barrier(tuple(
        None if g is None else g.astype(cdtype)
        for g in (dgate, dup, ddown)))
    return (dx.astype(x.dtype),
            dweights[:weights.size].reshape(weights.shape)
            .astype(weights.dtype),
            *(None if g is None else g.astype(w.dtype)
              for g, w in zip(narrow, (gate, up, down))))


@partial(jax.custom_vjp, nondiff_argnums=(0, 8, 9))
def _held_chunks(chunk, x, weights, gate, up, down, order, sizes,
                 act="silu", gate_zeros=False):
    """The held arm's row work: y (T, D) in x's dtype, the f32 sum of every
    live assignment's weighted expert output, computed ``chunk`` sorted rows
    a trip. ``order`` (T*k,) holds the held experts' rows first, by expert
    (absent ones behind them), ``sizes`` (G,) the held experts' counts.
    With ``gate_zeros``: (y, the live rows' gate pre-activations <= 0, an
    int32 count the forward's trips add up).

    A loop whose trip count is a value of the run has no reverse
    derivative, so it is written out: the forward saves its inputs alone,
    and the backward makes the same trips, each recomputing its chunk's
    activations, adding its rows' dx and dweights into (T, D) and (T k,) f32
    sums (dx as the forward adds y's rows: ``held_sum_on_mxu``; dweights by
    a scatter-add of scalars) and its share of the stacks' gradients into
    f32 sums in the STORED (G, N, K) orientation (``_DW_DIMS``: PR 30)."""
    return _held_chunks_fwd(chunk, jnp.dtype(policy().compute_dtype), act,
                            gate_zeros, x, weights, order, sizes, gate, up,
                            down)


def _held_chunks_vjp_fwd(chunk, x, weights, gate, up, down, order, sizes,
                         act, gate_zeros):
    return (_held_chunks(chunk, x, weights, gate, up, down, order, sizes,
                         act, gate_zeros),
            (x, weights, gate, up, down, order, sizes))


def _held_chunks_vjp_bwd(chunk, act, gate_zeros, res, dy):
    x, weights, gate, up, down, order, sizes = res
    return _held_chunks_bwd(chunk, jnp.dtype(policy().compute_dtype), act, x,
                            weights, order, sizes, gate, up, down,
                            dy[0] if gate_zeros else dy) + (None, None)


_held_chunks.defvjp(_held_chunks_vjp_fwd, _held_chunks_vjp_bwd)


def moe_dropless(x: jax.Array, router: jax.Array, gate: jax.Array,
                 up: jax.Array, down: jax.Array, top_k: int,
                 held_first: int = 0, act: str = "silu",
                 gate_zeros: bool = False):
    """Top-k token-choice MoE over flat tokens x (T, D), no capacity: every
    token is computed by all k of its experts whatever the load.

    router (E, D) scores all E experts; gate, up (G, F, D) and down
    (G, D, F) are the G experts held here (``expert_ffn``; G = E: all;
    ``gate`` None with ``act`` "relu2", the ungated expert).
    Returns (y (T, D), load-balancing loss, z loss, assignments per expert
    (E,) int32); y is ``expert_ffn``'s (y, share) pair with ``gate_zeros``."""
    n_exp = router.shape[0]
    # the router runs in f32 whatever the policy: top-k is discontinuous,
    # and a bf16 logit flips which experts a token gets
    logits = lax.dot_general(x.astype(jnp.float32), router,
                             (((1,), (1,)), ((), ())),
                             precision=lax.Precision.HIGHEST)
    probs, weights, experts = topk_route(logits, top_k)
    flat_e, sizes = expert_sizes(experts, n_exp)
    lb, z = router_losses(logits, probs, sizes)
    y = expert_ffn(x, weights, flat_e, sizes, gate, up, down, held_first,
                   act, gate_zeros)
    return y, lb, z, sizes


def moe_gated(x: jax.Array, gates: jax.Array, gate: jax.Array, up: jax.Array,
              down: jax.Array, top_k: int, held_first: int = 0,
              act: str = "silu", gate_zeros: bool = False):
    """``moe_dropless`` behind a router that is a layer of its own
    (``mlp_router``, ``sigmoid_router``, ``softmax_router``): ``gates``
    (T, E) f32 hold each token's k chosen experts' weights and zero
    elsewhere. Returns (y, assignments per expert (E,) int32); y is
    ``expert_ffn``'s (y, share) pair with ``gate_zeros``."""
    weights, experts = lax.top_k(gates, top_k)
    flat_e, sizes = expert_sizes(experts, gates.shape[1])
    return expert_ffn(x, weights, flat_e, sizes, gate, up, down,
                      held_first, act, gate_zeros), sizes


def softmax_router(h: jax.Array, w: jax.Array, top_k: int):
    """A plain softmax router as a layer of its own, over flat tokens h
    (T, D) that need not be what the experts compute on, in f32 whatever
    the policy (top-k is discontinuous):

        r = h w^T                                (T, E)
        chosen = the top_k largest of r
        weight_e = softmax over the chosen of r

    (the softmax applied AFTER the choice: the k weights sum to 1).
    Returns (gates (T, E) = the weights at the chosen experts and zero
    elsewhere; the load-balancing and z losses of ``router_losses``,
    unweighted, over the softmax of ALL E logits, as ``moe_dropless``
    computes them)."""
    f32 = jnp.float32
    logits = lax.dot_general(h.astype(f32), w.astype(f32),
                             (((1,), (1,)), ((), ())),
                             precision=lax.Precision.HIGHEST)
    top, experts = lax.top_k(logits, top_k)
    picked = jax.nn.one_hot(experts, logits.shape[1], dtype=f32)  # (T,k,E)
    gates = jnp.sum(picked * jax.nn.softmax(top, axis=-1)[..., None], axis=1)
    _, sizes = expert_sizes(experts, logits.shape[1])
    lb, z = router_losses(logits, jax.nn.softmax(logits, axis=-1), sizes)
    return gates, lb, z


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
