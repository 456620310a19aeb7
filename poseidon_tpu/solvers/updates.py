"""Caffe-exact optimizer update rules as pure, jit-able transforms.

Spec: ``/root/reference/src/caffe/solver.cpp``
- LR policies fixed/step/exp/inv/poly    (GetLearningRate, solver.cpp:758-790)
- SGD:      g' = g + decay*reg(w); h = m*h + local_lr*g'; w -= h
            (ComputeUpdateValue, solver.cpp:815-900)
- Nesterov: h' = m*h + local_lr*g'; w -= (1+m)*h' - m*h     (solver.cpp:1013)
- AdaGrad:  h += g'^2; w -= local_lr * g' / (sqrt(h)+delta) (solver.cpp:1240)
- Adam:     Caffe's fields (momentum = beta1, momentum2 = beta2, delta = eps),
            bias-corrected, with DECOUPLED weight decay (AdamW, what token
            models train with — not Caffe's coupled L2): m = b1 m + (1-b1) g;
            v = b2 v + (1-b2) g^2; w -= local_lr * ((m/(1-b1^t)) /
            (sqrt(v/(1-b2^t)) + delta) + local_decay * w), t = iter + 1.
            Two f32 moments per parameter: ``history`` is {"m": tree,
            "v": tree}.
Regularization: L2 adds decay*w to the gradient, L1 adds decay*sign(w);
local_lr = base_rate * lr_mult, local_decay = weight_decay * decay_mult.
``clip_gradients`` > 0 (Caffe's ClipGradients, solver.cpp) scales every
gradient by clip / ||g|| when the global L2 norm over ALL parameters
exceeds it, before regularization; off (the default) leaves the rule's
arithmetic untouched.

Iteration is carried as a traced scalar so the whole update compiles into the
training step; LR schedules use only XLA-friendly math.
"""

from __future__ import annotations

from typing import Dict, NamedTuple

import jax
import jax.numpy as jnp

from ..proto.messages import SolverParameter


# the policies whose rate reads ``max_iter``: there the run's length is a
# constant of the traced step (the AOT store keys on it, runtime/engine.py)
HORIZON_POLICIES = ("poly", "cosine")


def learning_rate(sp: SolverParameter, it: jax.Array) -> jax.Array:
    it = it.astype(jnp.float32)
    policy = sp.lr_policy
    base = jnp.float32(sp.base_lr)
    if policy == "fixed":
        return base
    if policy == "step":
        current_step = jnp.floor(it / sp.stepsize)
        return base * jnp.power(sp.gamma, current_step)
    if policy == "exp":
        return base * jnp.power(sp.gamma, it)
    if policy == "inv":
        return base * jnp.power(1.0 + sp.gamma * it, -sp.power)
    if policy == "poly":
        return base * jnp.power(1.0 - it / sp.max_iter, sp.power)
    if policy == "sigmoid":
        return base * (1.0 / (1.0 + jnp.exp(-sp.gamma * (it - sp.stepsize))))
    if policy == "multistep":
        # number of stepvalues passed so far
        steps = jnp.asarray(sp.stepvalue, jnp.float32)
        current_step = jnp.sum(it >= steps).astype(jnp.float32)
        return base * jnp.power(sp.gamma, current_step)
    if policy == "cosine":
        # linear warm-up over `stepsize` iterations, then a half cosine
        # from base_lr down to gamma * base_lr at max_iter
        frac = jnp.clip((it - sp.stepsize) / max(1, sp.max_iter - sp.stepsize),
                        0.0, 1.0)
        decayed = sp.gamma + (1.0 - sp.gamma) * 0.5 * (1.0 + jnp.cos(
            jnp.pi * frac))
        warm = jnp.minimum(1.0, (it + 1.0) / max(1, sp.stepsize))
        return base * warm * decayed
    raise ValueError(f"unknown lr_policy {policy!r}")


class SolverState(NamedTuple):
    it: jax.Array           # current iteration (traced scalar, int32)
    # momentum / accumulated squared grads, a tree like params; under ADAM
    # {"m": tree, "v": tree}
    history: Dict


def _adam(sp: SolverParameter, w, g, m, v, local_rate, local_decay, t):
    """One AdamW step on a leaf."""
    b1, b2 = sp.momentum, sp.momentum2
    m_new = b1 * m + (1.0 - b1) * g
    v_new = b2 * v + (1.0 - b2) * (g * g)
    m_hat = m_new / (1.0 - jnp.power(jnp.float32(b1), t))
    v_hat = v_new / (1.0 - jnp.power(jnp.float32(b2), t))
    step = local_rate * (m_hat / (jnp.sqrt(v_hat) + sp.delta)
                         + local_decay * w)
    return (w - step).astype(w.dtype), m_new, v_new


def clip_scale(sp: SolverParameter, *grad_trees):
    """Caffe's ClipGradients: None when off, else the factor (<= 1) that
    brings the global L2 norm of every gradient in ``grad_trees`` down to
    ``clip_gradients``."""
    if sp.clip_gradients <= 0:
        return None
    sumsq = sum(jnp.sum(jnp.square(g.astype(jnp.float32)))
                for g in jax.tree_util.tree_leaves(grad_trees))
    norm = jnp.sqrt(sumsq)
    return jnp.where(norm > sp.clip_gradients,
                     sp.clip_gradients / norm, 1.0)


def _regularized(g, w, local_decay: float, reg_type: str):
    if local_decay == 0.0:
        return g
    if reg_type == "L2":
        return g + local_decay * w
    if reg_type == "L1":
        return g + local_decay * jnp.sign(w)
    raise ValueError(f"unknown regularization_type {reg_type!r}")


def _without(tree, taken):
    """``tree`` ({layer: {param: x}}) less the leaves ``taken`` names."""
    return {lname: {p: x for p, x in leaves.items()
                    if p not in taken.get(lname, ())}
            for lname, leaves in tree.items()}


def _leafwise_update(sp: SolverParameter, mults, rate, params, grads,
                     history, it=None, scale=None, layer_updates=None):
    """One optimizer step over a per-leaf tree: one elementwise fusion per
    leaf, in whatever layout the compiler keeps it. ``it`` (ADAM's bias
    correction) and ``scale`` (the clip factor) are the caller's: both
    span the whole parameter set, which ``params`` need not be.
    ``layer_updates`` ({layer: {param: next value}}): leaves their layer
    updates itself. They take that value; no rule, decay or clip touches
    them and their history stays as it is."""
    if layer_updates:
        new_params, new_hist = _leafwise_update(
            sp, mults, rate, _without(params, layer_updates), grads,
            history, it, scale)
        trees = [(new_hist[k], history[k]) for k in ("m", "v")] \
            if sp.solver_type == "ADAM" else [(new_hist, history)]
        for lname, leaves in layer_updates.items():
            for pname, value in leaves.items():
                new_params[lname][pname] = value.astype(
                    params[lname][pname].dtype)
                for new, old in trees:
                    new[lname][pname] = old[lname][pname]
        return new_params, new_hist
    solver_type = sp.solver_type
    if solver_type == "ADAM":
        t = (it + 1).astype(jnp.float32)
        new_params, new_m, new_v = {}, {}, {}
        for lname, lparams in params.items():
            new_params[lname], new_m[lname], new_v[lname] = {}, {}, {}
            for pname, w in lparams.items():
                g = grads[lname][pname].astype(jnp.float32)
                if scale is not None:
                    g = g * scale
                lr_mult, decay_mult = mults[lname][pname]
                new_params[lname][pname], new_m[lname][pname], \
                    new_v[lname][pname] = _adam(
                        sp, w, g, history["m"][lname][pname],
                        history["v"][lname][pname], rate * lr_mult,
                        sp.weight_decay * decay_mult, t)
        return new_params, {"m": new_m, "v": new_v}
    momentum = sp.momentum
    weight_decay = sp.weight_decay
    reg_type = sp.regularization_type
    delta = sp.delta
    new_params = {}
    new_hist = {}
    for lname, lparams in params.items():
        new_params[lname] = {}
        new_hist[lname] = {}
        for pname, w in lparams.items():
            g = grads[lname][pname]
            lr_mult, decay_mult = mults[lname][pname]
            local_rate = rate * lr_mult
            local_decay = weight_decay * decay_mult
            h = history[lname][pname]
            g = g.astype(jnp.float32)
            if scale is not None:
                g = g * scale
            g = _regularized(g, w, local_decay, reg_type)
            if solver_type == "SGD":
                h_new = momentum * h + local_rate * g
                step = h_new
            elif solver_type == "NESTEROV":
                h_new = momentum * h + local_rate * g
                step = (1.0 + momentum) * h_new - momentum * h
            elif solver_type == "ADAGRAD":
                h_new = h + g * g
                step = local_rate * g / (jnp.sqrt(h_new) + delta)
            else:
                raise ValueError(f"unknown solver_type {solver_type!r}")
            new_params[lname][pname] = (w - step).astype(w.dtype)
            new_hist[lname][pname] = h_new
    return new_params, new_hist


def make_update_fn(sp: SolverParameter, mults: Dict[str, Dict[str, tuple]]):
    """Build update(params, grads, state) -> (params, state).

    ``mults`` maps layer -> param name -> (lr_mult, decay_mult), from the
    net's ParamDefs (the reference's blobs_lr / weight_decay lists).
    """
    def update(params, grads, state: SolverState, layer_updates=None):
        # scoped so one profiled step attributes the whole optimizer pass
        # as "optimizer_update" instead of leaking per-leaf fusions into
        # the attribution residual (runtime/attribution.py)
        with jax.named_scope("optimizer_update"):
            rate = learning_rate(sp, state.it)
            if layer_updates:           # outside the clip's norm
                grads = _without(grads, layer_updates)
            new_params, new_hist = _leafwise_update(
                sp, mults, rate, params, grads, state.history, state.it,
                clip_scale(sp, grads), layer_updates)
            return new_params, SolverState(it=state.it + 1, history=new_hist)

    return update


def make_flat_update_rule(sp: SolverParameter):
    """The update rule over a FLAT f32 buffer, for the one step whose
    parameters live in one: the fsdp-sharded step of parallel/spmd.py,
    which feeds each device its 1/fsdp shard of the buffer and of the
    per-leaf multipliers expanded to vectors (``ArenaLayout.mult_vectors``).
    fused(flat_w, flat_g, flat_h, rate, lr_vec, decay_vec) ->
    (flat_w', flat_h'): the arithmetic of ``_leafwise_update`` in the same
    order, bit-identical to it (tests/test_arena.py). SGD, NESTEROV and
    ADAGRAD; that step refuses ADAM and the clip."""
    solver_type = sp.solver_type
    momentum = sp.momentum
    reg_type = sp.regularization_type
    delta = sp.delta
    if solver_type not in ("SGD", "NESTEROV", "ADAGRAD"):
        raise ValueError(f"no flat update rule for solver_type "
                         f"{solver_type!r}")
    if reg_type not in ("L2", "L1"):
        raise ValueError(f"unknown regularization_type {reg_type!r}")

    def fused(flat_w, flat_g, flat_h, rate, lr_vec, decay_vec):
        local_rate = rate * lr_vec
        g = flat_g.astype(jnp.float32)
        reg = flat_w if reg_type == "L2" else jnp.sign(flat_w)
        # the elementwise form of _regularized's local_decay == 0 skip:
        # untouched gradient where the segment's decay is zero
        g = jnp.where(decay_vec == 0.0, g, g + decay_vec * reg)
        if solver_type == "SGD":
            h_new = momentum * flat_h + local_rate * g
            step = h_new
        elif solver_type == "NESTEROV":
            h_new = momentum * flat_h + local_rate * g
            step = (1.0 + momentum) * h_new - momentum * flat_h
        else:  # ADAGRAD
            h_new = flat_h + g * g
            step = local_rate * g / (jnp.sqrt(h_new) + delta)
        return (flat_w - step).astype(flat_w.dtype), h_new

    return fused


def init_state(params, solver_type: str = "SGD") -> SolverState:
    """Zero history shaped for ``solver_type``: one tree like ``params``,
    or ADAM's two moments."""
    zeros = lambda: jax.tree_util.tree_map(jnp.zeros_like, params)  # noqa: E731
    history = {"m": zeros(), "v": zeros()} if solver_type == "ADAM" \
        else zeros()
    return SolverState(it=jnp.zeros((), jnp.int32), history=history)
