"""Solver update math vs hand-computed Caffe semantics."""

import jax.numpy as jnp
import numpy as np
import pytest

from poseidon_tpu.proto.messages import SolverParameter
from poseidon_tpu.solvers.updates import (
    init_state, learning_rate, make_update_fn)


def _mults():
    return {"l": {"w": (1.0, 1.0)}}


def _pack(x):
    return {"l": {"w": jnp.asarray(x, jnp.float32)}}


def test_lr_policies():
    sp = SolverParameter(base_lr=0.1, lr_policy="step", gamma=0.5, stepsize=10)
    assert float(learning_rate(sp, jnp.asarray(0))) == pytest.approx(0.1)
    assert float(learning_rate(sp, jnp.asarray(9))) == pytest.approx(0.1)
    assert float(learning_rate(sp, jnp.asarray(10))) == pytest.approx(0.05)
    assert float(learning_rate(sp, jnp.asarray(25))) == pytest.approx(0.025)

    sp = SolverParameter(base_lr=0.1, lr_policy="inv", gamma=1e-4, power=0.75)
    assert float(learning_rate(sp, jnp.asarray(100))) == pytest.approx(
        0.1 * (1 + 1e-4 * 100) ** -0.75, rel=1e-5)

    sp = SolverParameter(base_lr=0.1, lr_policy="poly", power=2.0, max_iter=100)
    assert float(learning_rate(sp, jnp.asarray(50))) == pytest.approx(
        0.1 * 0.25, rel=1e-5)

    sp = SolverParameter(base_lr=0.1, lr_policy="exp", gamma=0.99)
    assert float(learning_rate(sp, jnp.asarray(10))) == pytest.approx(
        0.1 * 0.99 ** 10, rel=1e-5)

    sp = SolverParameter(base_lr=0.1, lr_policy="multistep", gamma=0.1,
                         stepvalue=[5, 8])
    assert float(learning_rate(sp, jnp.asarray(6))) == pytest.approx(0.01)
    assert float(learning_rate(sp, jnp.asarray(9))) == pytest.approx(0.001)


def test_sgd_momentum_weight_decay():
    sp = SolverParameter(base_lr=0.1, lr_policy="fixed", momentum=0.9,
                         weight_decay=0.01, solver_type="SGD")
    update = make_update_fn(sp, _mults())
    w = np.array([1.0, -2.0], np.float32)
    g = np.array([0.5, 0.25], np.float32)
    params, state = _pack(w), init_state(_pack(w))
    params, state = update(params, _pack(g), state)
    # h = 0.9*0 + 0.1*(g + 0.01*w); w -= h
    h = 0.1 * (g + 0.01 * w)
    np.testing.assert_allclose(np.asarray(params["l"]["w"]), w - h, rtol=1e-6)
    # second step: momentum kicks in
    params, state = update(params, _pack(g), state)
    w1 = w - h
    h2 = 0.9 * h + 0.1 * (g + 0.01 * w1)
    np.testing.assert_allclose(np.asarray(params["l"]["w"]), w1 - h2, rtol=1e-6)


def test_sgd_l1_regularization():
    sp = SolverParameter(base_lr=0.1, lr_policy="fixed", momentum=0.0,
                         weight_decay=0.01, regularization_type="L1")
    update = make_update_fn(sp, _mults())
    w = np.array([1.0, -2.0, 0.0], np.float32)
    g = np.zeros(3, np.float32)
    params, state = update(_pack(w), _pack(g), init_state(_pack(w)))
    expect = w - 0.1 * 0.01 * np.sign(w)
    np.testing.assert_allclose(np.asarray(params["l"]["w"]), expect, rtol=1e-6)


def test_nesterov():
    sp = SolverParameter(base_lr=0.1, lr_policy="fixed", momentum=0.9,
                         solver_type="NESTEROV")
    update = make_update_fn(sp, _mults())
    w = np.array([1.0], np.float32)
    g = np.array([1.0], np.float32)
    params, state = update(_pack(w), _pack(g), init_state(_pack(w)))
    # h' = 0.1; step = 1.9*0.1 - 0.9*0 = 0.19
    np.testing.assert_allclose(np.asarray(params["l"]["w"]), [1.0 - 0.19],
                               rtol=1e-6)


def test_adagrad():
    sp = SolverParameter(base_lr=0.1, lr_policy="fixed", solver_type="ADAGRAD",
                         delta=1e-8)
    update = make_update_fn(sp, _mults())
    w = np.array([1.0], np.float32)
    g = np.array([2.0], np.float32)
    params, state = update(_pack(w), _pack(g), init_state(_pack(w)))
    # h = 4; step = 0.1 * 2 / (2 + 1e-8) = 0.1
    np.testing.assert_allclose(np.asarray(params["l"]["w"]), [0.9], rtol=1e-5)
    params, state = update(params, _pack(g), state)
    # h = 8; step = 0.1*2/sqrt(8)
    np.testing.assert_allclose(np.asarray(params["l"]["w"]),
                               [0.9 - 0.2 / np.sqrt(8)], rtol=1e-5)


def test_lr_mult_decay_mult():
    sp = SolverParameter(base_lr=0.1, lr_policy="fixed", weight_decay=0.01)
    mults = {"l": {"w": (2.0, 0.0)}}
    update = make_update_fn(sp, mults)
    w = np.array([1.0], np.float32)
    g = np.array([1.0], np.float32)
    params, _ = update(_pack(w), _pack(g), init_state(_pack(w)))
    # lr doubled, decay zeroed
    np.testing.assert_allclose(np.asarray(params["l"]["w"]), [1.0 - 0.2],
                               rtol=1e-6)


# --------------------------------------------------------------------------- #
# ADAM (decoupled weight decay), clip_gradients, the cosine policy
# --------------------------------------------------------------------------- #

def _numpy_adamw(w, g, m, v, t, lr, b1, b2, eps, wd):
    """Hand-written AdamW (Loshchilov & Hutter), float64."""
    m = b1 * m + (1 - b1) * g
    v = b2 * v + (1 - b2) * g * g
    m_hat, v_hat = m / (1 - b1 ** t), v / (1 - b2 ** t)
    return w - lr * (m_hat / (np.sqrt(v_hat) + eps) + wd * w), m, v


@pytest.mark.parametrize("clip", [-1.0, 1.0])
def test_adam_matches_numpy_adamw(clip):
    """Three steps over two leaves, one with decay_mult 0 (a norm gain) and
    lr_mult 2; with the clip on, the gradient's global norm (over BOTH
    leaves) is 5x the threshold, so every gradient is scaled by 1/5."""
    sp = SolverParameter(base_lr=4e-4, lr_policy="fixed", momentum=0.9,
                         momentum2=0.95, delta=1e-8, weight_decay=0.1,
                         solver_type="ADAM", clip_gradients=clip)
    mults = {"a": {"w": (1.0, 1.0)}, "n": {"g": (2.0, 0.0)}}
    update = make_update_fn(sp, mults)
    rs = np.random.RandomState(0)
    w = {"a": {"w": rs.randn(4, 3)}, "n": {"g": 1.0 + rs.randn(3)}}
    params = {l: {k: jnp.asarray(x, jnp.float32) for k, x in lp.items()}
              for l, lp in w.items()}
    state = init_state(params, "ADAM")
    assert set(state.history) == {"m", "v"}
    m = {l: {k: np.zeros_like(x) for k, x in lp.items()} for l, lp in w.items()}
    v = {l: {k: np.zeros_like(x) for k, x in lp.items()} for l, lp in w.items()}
    for t in (1, 2, 3):
        g = {"a": {"w": rs.randn(4, 3)}, "n": {"g": rs.randn(3)}}
        norm = np.sqrt(sum((x ** 2).sum() for lp in g.values()
                           for x in lp.values()))
        g = {l: {k: x * 5.0 / norm for k, x in lp.items()}
             for l, lp in g.items()}          # global norm exactly 5
        scale = min(1.0, clip / 5.0) if clip > 0 else 1.0
        grads = {l: {k: jnp.asarray(x, jnp.float32) for k, x in lp.items()}
                 for l, lp in g.items()}
        params, state = update(params, grads, state)
        for l, lp in w.items():
            for k in lp:
                lr_mult, decay_mult = mults[l][k]
                w[l][k], m[l][k], v[l][k] = _numpy_adamw(
                    w[l][k], g[l][k] * scale, m[l][k], v[l][k], t,
                    4e-4 * lr_mult, 0.9, 0.95, 1e-8, 0.1 * decay_mult)
                np.testing.assert_allclose(params[l][k], w[l][k],
                                           rtol=2e-6, atol=1e-7)
                np.testing.assert_allclose(state.history["m"][l][k],
                                           m[l][k], rtol=1e-5, atol=1e-8)
                np.testing.assert_allclose(state.history["v"][l][k],
                                           v[l][k], rtol=1e-5, atol=1e-10)
    assert int(state.it) == 3


def test_clip_gradients_sgd_and_off_is_identity():
    """The clip is any solver's: SGD's step shrinks by clip / ||g||; a norm
    under the threshold, like no threshold, leaves the step bit for bit."""
    w = np.array([1.0, -2.0], np.float32)
    g = np.array([3.0, 4.0], np.float32)          # norm 5
    steps = {}
    for clip in (-1.0, 10.0, 1.0):
        sp = SolverParameter(base_lr=0.1, lr_policy="fixed", momentum=0.0,
                             clip_gradients=clip)
        p, _ = make_update_fn(sp, _mults())(_pack(w), _pack(g),
                                            init_state(_pack(w)))
        steps[clip] = np.asarray(p["l"]["w"])
    np.testing.assert_array_equal(steps[-1.0], steps[10.0])
    np.testing.assert_allclose(steps[1.0], w - 0.1 * g / 5.0, rtol=1e-6)


def test_cosine_policy_warms_up_then_decays():
    sp = SolverParameter(base_lr=4e-4, lr_policy="cosine", gamma=0.1,
                         stepsize=10, max_iter=110)
    lr = lambda it: float(learning_rate(sp, jnp.asarray(it)))  # noqa: E731
    assert lr(0) == pytest.approx(4e-5)           # 1/10 of the way up
    assert lr(9) == pytest.approx(4e-4)
    assert lr(60) == pytest.approx(4e-4 * (0.1 + 0.9 * 0.5), rel=1e-5)
    assert lr(110) == pytest.approx(4e-5, rel=1e-5)
    assert lr(500) == pytest.approx(4e-5, rel=1e-5)
