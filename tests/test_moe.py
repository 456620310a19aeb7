"""MoE transformer: expert parallelism over a (data x expert) mesh."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from poseidon_tpu.models.moe import (
    MoEConfig, build_dp_ep_train_step, init_moe_params, moe_ffn, moe_forward)
from poseidon_tpu.models.transformer import (
    TransformerConfig, lm_loss, transformer_mults)
from poseidon_tpu.parallel.mesh import make_mesh
from poseidon_tpu.proto.messages import SolverParameter
from poseidon_tpu.solvers.updates import init_state, make_update_fn

from conftest import pattern_batch

BASE = TransformerConfig(vocab_size=32, d_model=32, n_heads=2, n_layers=2,
                         d_ff=64, max_seq=32)
CFG = MoEConfig(base=BASE, n_experts=8, capacity=16, aux_weight=0.0)
B, S = 8, 16  # global batch/seq; mesh (data=2, expert=4) -> 16 tokens/device


def _pattern_batch(rs, b, s):
    return pattern_batch(rs, b, s, BASE.vocab_size)


def test_dp_ep_matches_single_device_gradstep():
    """With capacity high enough that nothing drops, expert-parallel
    routing over all_to_all must equal the all-experts-local reference:
    the exchange is a relayout of the same token->expert assignment."""
    sp = SolverParameter(base_lr=0.05, lr_policy="fixed")
    params = init_moe_params(CFG, jax.random.PRNGKey(1))
    rs = np.random.RandomState(2)
    tokens, targets = _pattern_batch(rs, B, S)

    mesh = make_mesh(axes=("data", "expert"), shape=(2, 4))
    step = build_dp_ep_train_step(CFG, sp, mesh, params, donate=False)
    p_ep, _, m = step(params, init_state(params), tokens, targets,
                      jax.random.PRNGKey(0))

    # reference: same math, all experts local, capacity covering the full
    # global batch (neither side drops, so capacities need not match)
    cfg_ref = dataclasses.replace(CFG, capacity=B * S)

    def loss_fn(p):
        logits, aux = moe_forward(p, cfg_ref, tokens)
        return lm_loss(logits, targets) + aux

    loss, grads = jax.value_and_grad(loss_fn)(params)
    upd = make_update_fn(sp, transformer_mults(params))
    p_ref, _ = upd(params, grads, init_state(params))

    assert float(m["loss"]) == pytest.approx(float(loss), rel=1e-4)
    for lname in p_ref:
        for k in p_ref[lname]:
            np.testing.assert_allclose(
                np.asarray(p_ep[lname][k]), np.asarray(p_ref[lname][k]),
                rtol=2e-3, atol=2e-5, err_msg=f"{lname}/{k}")


def test_aux_loss_value_with_flat_router():
    """With wg = 0 the gates are uniform (1/E) and every argmax lands on
    expert 0, so frac = (1,0,..), mean_gate = 1/E and the switch aux loss
    reduces to exactly aux_weight per MoE layer."""
    cfg = dataclasses.replace(CFG, aux_weight=0.01)
    params = init_moe_params(cfg, jax.random.PRNGKey(3))
    for i in range(BASE.n_layers):
        params[f"block{i}"]["wg"] = jnp.zeros_like(params[f"block{i}"]["wg"])
    rs = np.random.RandomState(4)
    tokens, _ = _pattern_batch(rs, 2, 8)
    _, aux = moe_forward(params, cfg, tokens)
    assert float(aux) == pytest.approx(0.01 * BASE.n_layers, rel=1e-5)


def test_capacity_drops_tokens():
    """Tokens beyond an expert's capacity contribute zero output (they ride
    the residual only) — the fixed-shape analog of a dispatch queue."""
    rs = np.random.RandomState(5)
    t, d, e, cap = 6, 8, 4, 2
    x = jnp.asarray(np.abs(rs.randn(t, d)).astype(np.float32))
    wg = jnp.zeros((e, d), jnp.float32).at[0].set(10.0)  # all -> expert 0
    w1e = jnp.asarray(rs.randn(e, 16, d).astype(np.float32))
    w2e = jnp.asarray(rs.randn(e, d, 16).astype(np.float32))
    cfg = MoEConfig(base=BASE, n_experts=e, capacity=cap, aux_weight=0.0)
    y, _ = moe_ffn(x, wg, w1e, w2e, cfg)
    y = np.asarray(y)
    assert np.abs(y[:cap]).sum() > 0
    np.testing.assert_array_equal(y[cap:], np.zeros_like(y[cap:]))


def test_dp_ep_converges():
    """The expert-parallel step must actually train (the router gradient
    flows through the gate scale, the expert grads through all_to_all)."""
    cfg = dataclasses.replace(CFG, aux_weight=0.01)
    sp = SolverParameter(base_lr=0.1, lr_policy="fixed", momentum=0.9)
    mesh = make_mesh(axes=("data", "expert"), shape=(2, 4))
    p = init_moe_params(cfg, jax.random.PRNGKey(6))
    step = build_dp_ep_train_step(cfg, sp, mesh, p, donate=False)
    s = init_state(p)
    rs = np.random.RandomState(7)
    tokens, targets = _pattern_batch(rs, B, S)
    first = last = None
    for it in range(60):
        p, s, m = step(p, s, tokens, targets, jax.random.PRNGKey(it))
        last = float(m["loss"])
        first = first if first is not None else last
    assert last < 0.3 * first, (first, last)


def test_moe_remat_gradients_match():
    """cfg.base.remat must be honored by moe_forward (checkpointed blocks)
    without changing values or gradients."""
    cfg_r = dataclasses.replace(
        CFG, base=dataclasses.replace(BASE, remat=True), aux_weight=0.01)
    cfg_n = dataclasses.replace(CFG, aux_weight=0.01)
    params = init_moe_params(cfg_n, jax.random.PRNGKey(8))
    rs = np.random.RandomState(9)
    tokens, targets = _pattern_batch(rs, 2, 8)

    def loss(p, cfg):
        logits, aux = moe_forward(p, cfg, tokens)
        return lm_loss(logits, targets) + aux

    l0, g0 = jax.value_and_grad(loss)(params, cfg_n)
    l1, g1 = jax.value_and_grad(loss)(params, cfg_r)
    assert float(l0) == pytest.approx(float(l1), rel=1e-6)
    for lname in g0:
        for k in g0[lname]:
            np.testing.assert_allclose(
                np.asarray(g0[lname][k]), np.asarray(g1[lname][k]),
                rtol=1e-5, atol=1e-7, err_msg=f"{lname}/{k}")


# --------------------------------------------------------------------------- #
# expert_ffn's held arm: the ladder of prefix lengths against the full rung
# --------------------------------------------------------------------------- #

E_ALL, G_HELD, D_X, F_X = 16, 2, 32, 16
TK = 512                        # T * k assignments in every case below
P0 = 128                        # twice the even share, TK * 2 * 2 / 16


def _routing(top_k, live, held_first, seed=0):
    """(x, weights, flat_e, sizes, gate, up, down) with exactly ``live`` of
    the TK assignments on a held expert, spread over the held ones."""
    from poseidon_tpu.models.moe import expert_sizes
    rs = np.random.RandomState(seed)
    t = TK // top_k
    held = np.arange(held_first, held_first + G_HELD)
    absent = np.setdiff1d(np.arange(E_ALL), held)
    experts = rs.choice(absent, size=TK)
    experts[rs.permutation(TK)[:live]] = rs.choice(held, size=live)
    flat_e, sizes = expert_sizes(jnp.asarray(experts.reshape(t, top_k)),
                                 E_ALL)
    f32 = jnp.float32
    return (jnp.asarray(rs.randn(t, D_X), f32),
            jnp.asarray(rs.rand(t, top_k) + 0.1, f32), flat_e, sizes,
            jnp.asarray(rs.randn(G_HELD, F_X, D_X) * 0.3, f32),
            jnp.asarray(rs.randn(G_HELD, F_X, D_X) * 0.3, f32),
            jnp.asarray(rs.randn(G_HELD, D_X, F_X) * 0.3, f32))


def _value_and_grads(args, held_first, dtype):
    """y and the gradients of sum(y * cot) with respect to x, weights, gate,
    up, down, under jit, grad and jax.checkpoint as a layer's remat unit
    runs it."""
    from poseidon_tpu.config import policy_scope
    from poseidon_tpu.models.moe import expert_ffn
    x, weights, flat_e, sizes, gate, up, down = args
    cot = jnp.asarray(np.random.RandomState(1).randn(*x.shape), jnp.float32)

    def f(x, weights, gate, up, down):
        y = jax.checkpoint(lambda *a: expert_ffn(
            a[0].astype(dtype), a[1], flat_e, sizes, *a[2:],
            held_first=held_first))(x, weights, gate, up, down)
        return jnp.sum(y.astype(jnp.float32) * cot), y

    with policy_scope(compute_dtype=dtype):
        (_, y), grads = jax.jit(jax.value_and_grad(
            f, argnums=(0, 1, 2, 3, 4), has_aux=True))(
                x, weights, gate, up, down)
    return (y,) + grads


def _rel(a, b):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.linalg.norm(a - b) / max(np.linalg.norm(b), 1e-30))


LADDER_CASES = {          # (top-k, live rows, held_first)
    "no_live_row": (8, 0, 0), "below_the_rung": (8, 50, 0),
    "at_the_rung": (8, P0, 0), "one_over_the_rung": (8, P0 + 1, 0),
    "twice_the_rung": (8, 2 * P0, 0),
    "every_row_live": (8, TK, 0), "held_first_6": (8, 90, 6),
    "held_first_14_over": (8, 300, 14), "top_1": (1, 100, 0),
    "top_1_over": (1, 200, 3),
}


@pytest.mark.parametrize("dtype", ["f32", "bf16"])
@pytest.mark.parametrize("case", sorted(LADDER_CASES))
def test_held_ladder_equals_the_full_rung(case, dtype, monkeypatch):
    """The ladder (prefix rung at twice the even share, full rung on
    overflow) against the full rung alone: y and every gradient. A k-term
    f32 sum in another order: 1e-6 relative in f32, a bf16 ulp's share
    under the bf16 policy. Over the rung the ladder runs the full rung's own
    equations (the test below), compiled inside a conditional: the compiler
    fuses them otherwise, so equal to rounding and not to the bit."""
    from poseidon_tpu.models import moe
    top_k, live, held_first = LADDER_CASES[case]
    assert moe.held_row_ladder(TK, G_HELD, E_ALL) == (P0, TK)
    args = _routing(top_k, live, held_first)
    assert int(jnp.sum(args[3][held_first:held_first + G_HELD])) == live
    dt = {"f32": jnp.float32, "bf16": jnp.bfloat16}[dtype]
    got = _value_and_grads(args, held_first, dt)
    monkeypatch.setattr(moe, "held_row_ladder", lambda rows, g, e: (rows,))
    want = _value_and_grads(args, held_first, dt)
    tol = {"f32": 1e-6, "bf16": 4e-3}[dtype]
    for name, a, b in zip(("y", "dx", "dweights", "dgate", "dup", "ddown"),
                          got, want):
        assert a.dtype == b.dtype and a.shape == b.shape, name
        assert _rel(a, b) <= tol, (name, _rel(a, b))
        if live and name != "dweights":
            assert np.any(np.asarray(a, np.float32)), name


def test_held_ladder_is_a_rule_of_the_shapes():
    """Twice the even share, rounded up to the row tile; a single full rung
    (no conditional) from half the experts held."""
    from poseidon_tpu.models.moe import held_row_ladder
    assert held_row_ladder(131072, 16, 128) == (32768, 131072)   # Trinity
    assert held_row_ladder(16384, 8, 16) == (16384,)             # ZAYA1
    assert held_row_ladder(1000, 1, 16) == (128, 1000)
    assert held_row_ladder(1024, 3, 16) == (384, 1024)
    assert held_row_ladder(512, 4, 16) == (256, 512)
    assert held_row_ladder(256, 7, 16) == (256,)


def test_full_rung_of_the_ladder_is_the_held_arm_alone():
    """One two-branch conditional on the live count; the branch taken on
    overflow is, equation for equation, what a single-rung layer traces
    (ZAYA1's program, the parent's held arm)."""
    from poseidon_tpu.models import moe
    x, weights, flat_e, sizes, gate, up, down = _routing(8, 50, 0)
    jaxpr = jax.make_jaxpr(lambda *a: moe.expert_ffn(
        a[0], a[1], flat_e, sizes, *a[2:]))(x, weights, gate, up, down)
    conds = [e for e in _eqns(jaxpr.jaxpr) if e.primitive.name == "cond"]
    assert len(conds) == 1 and len(conds[0].params["branches"]) == 2
    here = flat_e < G_HELD
    order = jnp.argsort(jnp.where(here, flat_e, G_HELD), stable=True)
    alone = jax.make_jaxpr(lambda *a: moe._held_rows(*a, TK))(
        x, weights, here, order, sizes[:G_HELD], gate, up, down)
    # each branch is ONE call of the rung's body (a jitted function, so
    # that every layer and pass shares its trace and lowering)
    full, prefix = ([e.params["jaxpr"].jaxpr for e in b.jaxpr.eqns]
                    for b in conds[0].params["branches"])   # False, True
    assert len(full) == len(prefix) == 1
    assert str(full[0]) == str(alone.jaxpr)
    assert str(prefix[0]) != str(alone.jaxpr)


def _eqns(jaxpr):
    for eqn in jaxpr.eqns:
        yield eqn
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _eqns(sub)


def _avals(jaxpr):
    for eqn in _eqns(jaxpr):
        for v in list(eqn.invars) + list(eqn.outvars):
            if hasattr(v, "aval") and hasattr(v.aval, "shape"):
                yield v.aval


@pytest.mark.parametrize("pass_", ["forward", "gradient"])
def test_prefix_rung_holds_no_array_of_all_the_rows(pass_):
    """The prefix branch's jaxpr (and its gradient's) holds no array of T k
    rows with a trailing feature axis: what is left of T k are vectors of
    scalars (the sort key, ``order``, ``here``) and (T, k) weights. The full
    rung, traced the same way, does hold them."""
    from poseidon_tpu.models import moe
    x, weights, flat_e, sizes, gate, up, down = _routing(8, 50, 0)
    here = flat_e < G_HELD
    order = jnp.argsort(jnp.where(here, flat_e, G_HELD), stable=True)

    def wide(rows):
        def f(x, weights, gate, up, down):
            return jnp.sum(moe._held_rows(x, weights, here, order,
                                          sizes[:G_HELD], gate, up, down,
                                          rows).astype(jnp.float32))
        fn = f if pass_ == "forward" else jax.grad(f, argnums=(0, 1, 2, 3, 4))
        jaxpr = jax.make_jaxpr(fn)(x, weights, gate, up, down).jaxpr
        return [a.shape for a in _avals(jaxpr) if len(a.shape) >= 2
                and a.shape[-1] in (D_X, F_X)
                and int(np.prod(a.shape[:-1])) == TK]

    assert wide(P0) == []
    assert (TK, D_X) in wide(TK)
