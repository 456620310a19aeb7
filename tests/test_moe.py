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
# expert_ffn's held arm: chunks of the sorted rows under a loop whose trip
# count is the live rows', against the straight-line arm over all T k rows
# --------------------------------------------------------------------------- #

E_ALL, G_HELD, D_X, F_X = 16, 2, 32, 16
TK = 512                        # T * k assignments in every case below
P0 = 128                        # a chunk: the even share TK * 2 / 16, up to
#                                 a row tile, with the floor lowered to it


@pytest.fixture
def short_chunks(monkeypatch):
    """The rule's floor (8,192 rows) lowered to a row tile, so that
    ``expert_ffn`` itself cuts these 512 rows into four chunks."""
    from poseidon_tpu.models import moe
    monkeypatch.setattr(moe, "_CHUNK_FLOOR", P0)
    assert moe.held_chunk_rows(TK, G_HELD, E_ALL) == P0


@pytest.fixture
def sums_on_mxu(monkeypatch):
    """The table of ``held_sum_on_mxu`` holds these cases' width, so that
    the loop's trips sum their rows on the MXU (``_tile_sum``). The rule is
    read while tracing and is no part of the jitted loops' keys: their
    traces are dropped before and after."""
    from poseidon_tpu.models import moe
    monkeypatch.setattr(moe, "_SCATTER_CLIFFS", frozenset({D_X}))
    loops = (moe._held_chunks_fwd, moe._held_chunks_bwd)
    for f in loops:
        f.clear_cache()
    yield
    for f in loops:
        f.clear_cache()


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


def _sorted_by_held_expert(flat_e, held_first, n_held):
    local = flat_e - held_first
    here = (local >= 0) & (local < n_held)
    return here, jnp.argsort(jnp.where(here, local, n_held), stable=True)


def _straight_line(x, weights, flat_e, sizes, gate, up, down, held_first=0):
    """The held arm with no loop, the reference the chunks are held to: ALL
    T k sorted rows gathered, multiplied (rows past the last group masked on
    the way in and out), brought back to their tokens by the inverse
    permutation and the k summed in f32. Autodiff differentiates it."""
    from poseidon_tpu.models import moe
    n_held = gate.shape[0]
    t, top_k = weights.shape
    here, order = _sorted_by_held_expert(flat_e, held_first, n_held)
    sizes = sizes[held_first:held_first + n_held]
    live = (jnp.arange(t * top_k) < jnp.sum(sizes))[:, None]

    def grouped(rows, w):
        return jnp.where(live, moe._grouped(jnp.where(live, rows, 0), w,
                                            sizes), 0)

    xs = x[order // top_k]
    h = jax.nn.silu(grouped(xs, gate)) * grouped(xs, up)
    return moe._combine(grouped(h, down), order,
                        weights * here.reshape(t, top_k), x.dtype)


def _value_and_grads(ffn, args, held_first, dtype):
    """y and the gradients of sum(y * cot) with respect to x, weights, gate,
    up, down, under jit, grad and jax.checkpoint as a layer's remat unit
    runs it."""
    from poseidon_tpu.config import policy_scope
    x, weights, flat_e, sizes, gate, up, down = args
    cot = jnp.asarray(np.random.RandomState(1).randn(*x.shape), jnp.float32)

    def f(x, weights, gate, up, down):
        y = jax.checkpoint(lambda *a: ffn(
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


LADDER_CASES = {          # (top-k, live rows, held_first); P0 rows a chunk
    "no_live_row": (8, 0, 0), "below_a_chunk": (8, 50, 0),
    "exactly_a_chunk": (8, P0, 0), "a_chunk_and_a_row": (8, P0 + 1, 0),
    "two_chunks": (8, 2 * P0, 0), "three_chunks_and_a_row": (8, 3 * P0 + 1, 0),
    "every_row_live": (8, TK, 0), "held_first_6": (8, 90, 6),
    # two experts of about 150 rows: the second chunk splits both groups
    "held_first_14_splits_a_group": (8, 300, 14), "top_1": (1, 100, 0),
    "top_1_over": (1, 200, 3), "top_1_every_row_live": (1, TK, 0),
}


@pytest.mark.parametrize("dtype", ["f32", "bf16"])
@pytest.mark.parametrize("case", sorted(LADDER_CASES))
def test_held_chunks_equal_the_straight_line_arm(case, dtype, short_chunks):
    """The chunked loop against the straight-line arm over all T k rows: y
    and every gradient. In f32 the same terms in another order: 1e-6
    relative. Under the bf16 policy y is the straight line's to the bit;
    the gradients are not: the loop keeps in f32 what autodiff rounds to
    bf16 (the activation's derivative, the sum of a row's two dx products
    and of a token's k), pulls y's row back through ``dy down`` where
    autodiff recomputes ``h down^T``, and sums a stack's gradient over the
    chunks in f32 before it is rounded to bf16 once. Within a bf16 ulp of
    the straight line, and as near the f32 result as the straight line is
    (to a quarter: both round a few times in bf16)."""
    from poseidon_tpu.models import moe
    top_k, live, held_first = LADDER_CASES[case]
    args = _routing(top_k, live, held_first)
    assert int(jnp.sum(args[3][held_first:held_first + G_HELD])) == live
    dt = {"f32": jnp.float32, "bf16": jnp.bfloat16}[dtype]
    got = _value_and_grads(moe.expert_ffn, args, held_first, dt)
    want = _value_and_grads(_straight_line, args, held_first, dt)
    exact = want if dtype == "f32" else _value_and_grads(
        _straight_line, args, held_first, jnp.float32)
    tol = {"f32": 1e-6, "bf16": 2.0 ** -7}[dtype]
    for name, a, b, c in zip(("y", "dx", "dweights", "dgate", "dup", "ddown"),
                             got, want, exact):
        assert a.dtype == b.dtype and a.shape == b.shape, name
        assert _rel(a, b) <= tol, (name, _rel(a, b))
        assert _rel(a, c) <= 1.25 * _rel(b, c) + 1e-6, name
        assert bool(np.any(np.asarray(a, np.float32))) == bool(live), name


@pytest.mark.parametrize("dtype", ["f32", "bf16"])
@pytest.mark.parametrize("case", sorted(LADDER_CASES))
def test_held_chunks_that_sum_on_the_mxu_equal_the_straight_line_arm(
        case, dtype, short_chunks, sums_on_mxu):
    """The same ladder with the trips' two (T, D) sums on the MXU: y and
    every gradient against the straight-line arm, to the same tolerances
    (the sums are the scatter-add's terms in another order)."""
    test_held_chunks_equal_the_straight_line_arm(case, dtype, short_chunks)


def test_held_sum_is_a_rule_of_the_width(short_chunks):
    """``held_sum_on_mxu``: the MXU at the widths where XLA's scatter-add
    was read off its fast path (SmallThinker's 2,560 among them), the
    scatter-add at every other (Trinity's 2,048, Kimi-Linear's 2,304,
    these tests' 32), where a trip's forward scatters into (T, D) as it
    did."""
    from poseidon_tpu.models import moe
    assert all(moe.held_sum_on_mxu(d) for d in (2560, 5120))
    assert not any(moe.held_sum_on_mxu(d) for d in (
        D_X, 512, 1024, 2048, 2304, 3072, 4096))
    inside = list(_inside_whiles(_held_jaxpr("forward")))
    assert [e.invars[0].aval.shape for e in inside
            if e.primitive.name == "scatter-add"] == [(TK // 8, D_X)]
    assert not [e for e in inside if e.primitive.name == "ragged_dot_general"
                and e.outvars[0].aval.shape == (1, TK // 8, D_X)]


def test_held_chunk_is_a_rule_of_the_shapes():
    """The even share of the T k assignments, no less than the floor and no
    more than all the rows, in whole row tiles: the three cells' shapes and
    the small ones."""
    from poseidon_tpu.models.moe import held_chunk_rows
    assert held_chunk_rows(65536, 8, 256) == 8192      # Kimi-Linear: floor
    assert held_chunk_rows(131072, 16, 128) == 16384   # Trinity: even share
    assert held_chunk_rows(16384, 8, 16) == 8192       # ZAYA1: both
    assert held_chunk_rows(196608, 16, 128) == 24576   # Trinity, 3 sequences
    assert held_chunk_rows(100000, 3, 16) == 18816     # 18,750 up to a tile
    assert held_chunk_rows(8192, 1, 16) == 8192        # every row: one chunk
    assert held_chunk_rows(1000, 1, 16) == 1024        # one chunk, padded
    assert held_chunk_rows(512, 4, 16) == 512
    assert held_chunk_rows(100, 15, 16) == 128


def test_two_chunks_at_most_run_as_straight_line_rows():
    """The loop from three chunks on: with one or two (the rule's own
    floor at these 512 rows; ZAYA1's 8 of 16 experts at the cell's shape)
    ``expert_ffn`` traces, equation for equation, the straight-line arm
    this file holds the chunks to, and no loop."""
    from poseidon_tpu.models import moe
    assert [moe.held_rows_loop(r, c) for r, c in (
        (65536, 8192), (131072, 16384), (16384, 8192), (16385, 8192),
        (512, 512), (512, 128))] == [True, True, False, True, False, True]
    assert not moe.held_rows_loop(TK, moe.held_chunk_rows(TK, G_HELD, E_ALL))
    x, weights, flat_e, sizes, gate, up, down = _routing(8, 50, 0)
    got, want = (jax.make_jaxpr(lambda *a: f(
        a[0], a[1], flat_e, sizes, *a[2:]))(x, weights, gate, up, down)
        for f in (moe.expert_ffn, _straight_line))
    assert str(got) == str(want)
    assert "while" not in [e.primitive.name for e in _eqns(got.jaxpr)]


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


def _held_jaxpr(pass_, ffn=None):
    from poseidon_tpu.models import moe
    x, weights, flat_e, sizes, gate, up, down = _routing(8, 50, 0)

    def f(x, weights, gate, up, down):
        return jnp.sum((ffn or moe.expert_ffn)(
            x, weights, flat_e, sizes, gate, up, down).astype(jnp.float32))

    fn = f if pass_ == "forward" else jax.grad(f, argnums=(0, 1, 2, 3, 4))
    return jax.make_jaxpr(fn)(x, weights, gate, up, down).jaxpr


def _wide(jaxpr):
    """Shapes of T k rows times a feature width anywhere in the jaxpr."""
    return [a.shape for a in _avals(jaxpr) if len(a.shape) >= 2
            and a.shape[-1] in (D_X, F_X)
            and int(np.prod(a.shape[:-1])) == TK]


@pytest.mark.parametrize("pass_", ["forward", "gradient"])
def test_held_arm_is_one_loop_over_chunks(pass_, short_chunks):
    """One ``while`` a pass and no conditional: the forward's loop in the
    forward's jaxpr; the gradient's holds the backward's and, until dead
    code goes, the forward's beside it. Neither holds an array of T k rows
    with a trailing feature axis: what is left of T k are vectors of scalars
    (the sort key, ``order``, the weights' gradient) and (T, k) weights. The
    straight-line arm, traced the same way, does hold them."""
    jaxpr = _held_jaxpr(pass_)
    names = [e.primitive.name for e in _eqns(jaxpr)]
    assert "cond" not in names
    assert names.count("while") == {"forward": 1, "gradient": 2}[pass_]
    # the trip count is a value of the run: no loop is unrolled or scanned
    assert "scan" not in names
    assert _wide(jaxpr) == []
    assert (TK, D_X) in _wide(_held_jaxpr(pass_, _straight_line))


@pytest.mark.parametrize("pass_", ["forward", "gradient"])
def test_stacks_are_cast_outside_the_loop(pass_, short_chunks):
    """The stacks reach the loop's body in the compute dtype, cast and
    transposed once a pass: no equation inside a ``while`` converts or
    transposes an array of a stack's size."""
    from poseidon_tpu.config import policy_scope
    with policy_scope(compute_dtype=jnp.bfloat16):
        jaxpr = _held_jaxpr(pass_)
    stack = G_HELD * F_X * D_X
    casts = {"inside": [], "outside": []}

    def walk(jaxpr, where):
        for eqn in jaxpr.eqns:
            name = eqn.primitive.name
            # to the compute dtype: a chunk's share of a stack's gradient
            # goes the other way, to the f32 sum, inside the loop
            if name in ("convert_element_type", "transpose") and any(
                    int(np.prod(v.aval.shape)) == stack
                    and len(v.aval.shape) == 3
                    and v.aval.dtype == jnp.bfloat16 for v in eqn.outvars):
                casts[where].append(name)
            for sub in jax.core.jaxprs_in_params(eqn.params):
                walk(sub, "inside" if name == "while" else where)

    walk(jaxpr, "outside")
    assert casts["inside"] == []
    # forward: three stacks cast and transposed; the backward's casts serve
    # both orientations
    assert casts["outside"].count("convert_element_type") >= 3
    assert casts["outside"].count("transpose") >= 3


def test_arm_that_holds_every_expert_is_untouched():
    """G = E (OLMoE): the sort, one gather of all T k rows, three grouped
    matmuls and the inverse permutation, equation for equation; no loop."""
    from poseidon_tpu.models import moe
    x, weights, flat_e, sizes, *_ = _routing(8, 50, 0)
    rs = np.random.RandomState(3)
    gate, up = (jnp.asarray(rs.randn(E_ALL, F_X, D_X), jnp.float32)
                for _ in range(2))
    down = jnp.asarray(rs.randn(E_ALL, D_X, F_X), jnp.float32)

    def parents(x, weights, gate, up, down):
        order = jnp.argsort(flat_e, stable=True)
        xs = x[order // weights.shape[1]]
        h = jax.nn.silu(moe._grouped(xs, gate, sizes)) \
            * moe._grouped(xs, up, sizes)
        return moe._combine(moe._grouped(h, down, sizes), order, weights,
                            x.dtype)

    def ours(x, weights, gate, up, down):
        return moe.expert_ffn(x, weights, flat_e, sizes, gate, up, down)

    got, want = (jax.make_jaxpr(f)(x, weights, gate, up, down)
                 for f in (ours, parents))
    assert str(got) == str(want)
    assert "while" not in [e.primitive.name for e in _eqns(got.jaxpr)]


# --------------------------------------------------------------------------- #
# The chunk's rows summed into (T, D) on the MXU (``_tile_sum``) against the
# serial ``.at[tok].add`` it took the place of, kept here as the reference.
# --------------------------------------------------------------------------- #


def _scatter_sum(rows, scale, tok, live, t):
    """What a trip added to its (T, D) f32 carry before the MXU sum: every
    row times its f32 scale, scatter-added by token; a dead row adds zero
    where it points."""
    return jnp.zeros((t, rows.shape[1]), jnp.float32).at[tok].add(
        rows.astype(jnp.float32) * jnp.where(live, scale[:, None], 0))


def _front(flat, first, at, total, rs):
    """A permutation of ``total`` assignments with ``flat`` at positions
    ``at ..``, drawn from ``first`` (those allowed before the rest)."""
    rest = np.setdiff1d(first, flat)
    rs.shuffle(rest)
    head = np.concatenate([rest[:at], flat, rest[at:]])
    tail = np.setdiff1d(np.arange(total), head)
    rs.shuffle(tail)
    return np.concatenate([head, tail])


def _sum_case(case):
    """(T, k, chunk P, the sorted assignments ``order`` (T k,), live rows,
    the token the case is about or None)."""
    rs = np.random.RandomState(5)
    every = np.arange(512)
    if case == "token_whole_in_one_chunk":      # its 8 rows inside chunk 0
        return 64, 8, 128, _front(np.arange(40, 48), every, 17, 512, rs), \
            300, 5
    if case == "token_split_over_two_chunks":   # 4 rows each side of row 128
        return 64, 8, 128, _front(np.arange(40, 48), every, 124, 512, rs), \
            300, 5
    if case == "one_tile_holds_a_chunks_live_rows":
        # T = 256 is two tiles; chunk 0 holds tokens under 128 alone, and the
        # 60 live rows of chunk 1 are tokens from 128 on
        low = rs.permutation(256)[:128]
        high = 256 + rs.permutation(256)
        return 256, 2, 128, np.concatenate(
            [low, high[:60], np.setdiff1d(every, np.concatenate(
                [low, high[:60]]))]), 188, None
    if case == "dead_rows_and_padding":
        # P = 384: the second trip holds 16 live rows, 112 dead ones with
        # tokens of their own, and 256 rows of ``_trips``' padding (token 0)
        return 64, 8, 384, rs.permutation(512), 400, 0
    if case == "t_below_a_tile":
        return 24, 4, 128, rs.permutation(96), 96, None
    assert case == "t_not_a_multiple_of_a_tile"     # 200 = 128 + 72
    return 200, 2, 128, rs.permutation(400), 391, 199


SUM_CASES = ("token_whole_in_one_chunk", "token_split_over_two_chunks",
             "one_tile_holds_a_chunks_live_rows", "dead_rows_and_padding",
             "t_below_a_tile", "t_not_a_multiple_of_a_tile")


@pytest.mark.parametrize("dtype", ["f32", "bf16"])
@pytest.mark.parametrize("case", SUM_CASES)
def test_tile_sum_equals_the_scatter_add(case, dtype):
    """The loop's trips over ``_trips``' padded order, each trip's rows
    summed into the carry by ``_tile_sum`` and by the scatter-add: the same
    f32 terms (a row in the compute dtype times its f32 weight) in another
    order, 1e-6 relative in f32 and in bf16 rows alike. Dead rows hold
    numbers here (the loop masks them to zero) and must add nothing."""
    from poseidon_tpu.models import moe
    t, top_k, chunk, order, n_live, token = _sum_case(case)
    dt = {"f32": jnp.float32, "bf16": jnp.bfloat16}[dtype]
    rs = np.random.RandomState(9)
    weights = jnp.asarray(rs.rand(t * top_k) + 0.1, jnp.float32)
    padded, ends, n = moe._trips(jnp.asarray(order, jnp.int32),
                                 jnp.asarray([n_live], jnp.int32), chunk)
    assert int(n) == -(-n_live // chunk) and padded.shape[0] % chunk == 0
    got, want = (jnp.zeros((t, D_X), jnp.float32),) * 2
    exact = np.zeros((t, D_X))
    for i in range(int(n)):
        head = padded[i * chunk:(i + 1) * chunk]
        tok = head // top_k
        live = (i * chunk + jnp.arange(chunk) < ends[-1])[:, None]
        rows = jnp.asarray(rs.randn(chunk, D_X), dt)
        got = got + jax.jit(moe._tile_sum, static_argnums=4)(
            rows, weights[head], tok, live, t)
        want = want + _scatter_sum(rows, weights[head], tok, live, t)
        np.add.at(exact, np.asarray(tok), np.asarray(rows, np.float64)
                  * np.asarray(jnp.where(live, weights[head][:, None], 0),
                               np.float64))
        if case == "one_tile_holds_a_chunks_live_rows":
            tiles = set(np.asarray(tok)[np.asarray(live[:, 0])] // 128)
            assert tiles == {i}
    assert got.dtype == jnp.float32 and got.shape == (t, D_X)
    assert _rel(got, want) <= 1e-6, _rel(got, want)
    assert _rel(got, exact) <= 1e-6
    if token is not None:       # the row the case is about, not the norm
        assert _rel(got[token], exact[token]) <= 1e-6
        assert np.any(exact[token])
    if case.startswith("token_"):
        at = np.flatnonzero(np.asarray(order) // top_k == token)
        assert len(at) == top_k
        assert len(set(at // chunk)) == (1 if "whole" in case else 2)


def _inside_whiles(jaxpr):
    """The equations inside every ``while`` of the jaxpr, bodies within
    bodies included."""
    for eqn in _eqns(jaxpr):
        if eqn.primitive.name == "while":
            for sub in jax.core.jaxprs_in_params(eqn.params):
                yield from _eqns(sub)


@pytest.mark.parametrize("pass_", ["forward", "gradient"])
def test_no_scatter_add_into_t_by_d_under_the_loop(pass_, short_chunks,
                                                   sums_on_mxu):
    """Where the sums run on the MXU (``held_sum_on_mxu``), inside the
    ``while`` of either pass a chunk's rows reach the (T, D) f32 carry
    through a grouped product with the ragged dimension contracted,
    (T / tile, tile, D), and a plain add: no ``scatter-add`` has a (T, D)
    operand. The only scatter left there is the weights' gradient's, (T k,)
    scalars, in the backward's loop. No array of T k rows times a feature
    width appears."""
    jaxpr = _held_jaxpr(pass_)
    t = TK // 8
    inside = list(_inside_whiles(jaxpr))
    scattered = [e.invars[0].aval.shape for e in inside
                 if e.primitive.name.startswith("scatter")]
    assert scattered == {"forward": [], "gradient": [(TK,)]}[pass_]
    sums = [e for e in inside if e.primitive.name == "ragged_dot_general"
            and e.outvars[0].aval.shape == (1, t, D_X)]
    # the forward's sum; the gradient's jaxpr holds the forward's loop too
    assert len(sums) == {"forward": 1, "gradient": 2}[pass_]
    assert all(e.outvars[0].aval.dtype == jnp.float32
               and set(e.params["precision"]) == {jax.lax.Precision.HIGHEST}
               for e in sums)
    assert _wide(jaxpr) == []
    # the reference of this file does scatter (T, D) rows
    ref = jax.make_jaxpr(lambda r, s, k: _scatter_sum(
        r, s, k, jnp.ones((P0, 1), bool), t))(
            jnp.zeros((P0, D_X)), jnp.zeros((P0,)), jnp.zeros((P0,), int))
    assert [e.invars[0].aval.shape for e in _eqns(ref.jaxpr)
            if e.primitive.name == "scatter-add"] == [(t, D_X)]
