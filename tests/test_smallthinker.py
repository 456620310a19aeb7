"""SmallThinker-21BA3B's block as layers of the Net against its plain
reference (benchmark/reference/smallthinker.py, loaded from there: one file,
no second copy), at a small size on the CPU with seeded weights: logits, the
loss with both router losses, every gradient and one AdamW step; the router
that scores the PRE-attention state; the four expert shares summing to the
uncut layer; the ReLU arm of ``expert_ffn`` in its three forms against
autodiff of the plain form; window / NoPE-global by layer; the share of gate
pre-activations a ReLU zeroes; the example prototxts."""

import importlib.util
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from poseidon_tpu.core.net import Net
from poseidon_tpu.models import moe, zoo
from poseidon_tpu.proto.messages import load_net_from_string

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_spec = importlib.util.spec_from_file_location(
    "smallthinker_reference",
    os.path.join(ROOT, "benchmark", "reference", "smallthinker.py"))
ref = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(ref)

# one whole period (global, window, window, window), as the cell
L, E, K, HELD, W = 4, 16, 3, 4, 16
SIZES = dict(n_layers=L, hidden=64, heads=8, kv_heads=2, head_dim=16,
             window=W, experts=E, top_k=K, expert_width=32, vocab=128)
CFG = {"num_hidden_layers": L, "num_attention_heads": 8,
       "num_key_value_heads": 2, "head_dim": 16, "num_experts": E,
       "num_experts_per_tok": K, "sliding_window_size": W,
       "sliding_window_layout": [0, 1, 1, 1], "rms_norm_eps": 1e-6,
       "rope_theta": 1.5e6, "balance_weight": 0.01, "z_weight": 0.001}
N, S = 2, 64
REMAT = [r"/l\d+_/", r"/lm_/"]   # what the example solver's header names


def build(held=HELD, held_first=0, n=N, s=S, **kw):
    # through the text form: what a user's prototxt goes through
    text = zoo.to_prototxt(zoo.smallthinker(
        batch=n, held=held, held_first=held_first, **{**SIZES, **kw}))
    return Net(load_net_from_string(text), "TRAIN",
               source_shapes={"tokens": (n, s), "targets": (n, s)})


def batch_of(n=N, s=S, seed=5):
    key = jax.random.PRNGKey(seed)
    return {"tokens": jax.random.randint(key, (n, s), 0, SIZES["vocab"]),
            "targets": jax.random.randint(jax.random.fold_in(key, 1),
                                          (n, s), 0, SIZES["vocab"])}


def seeded(net, seed=3):
    """Fresh weights, then the gains moved off 1 so that a gain in the wrong
    place shows, and the router's matrix larger, so that its choices are not
    all near-ties."""
    params = net.init(jax.random.PRNGKey(seed))
    for i, (lname, lp) in enumerate(sorted(params.items())):
        for j, (pname, w) in enumerate(sorted(lp.items())):
            noise = jax.random.normal(jax.random.PRNGKey(100 + 31 * i + j),
                                      w.shape)
            if pname == "g":
                lp[pname] = 1.0 + 0.2 * noise
            elif lname.endswith("_router"):
                lp[pname] = 0.5 * noise
    return params


@pytest.fixture(scope="module")
def model():
    net = build()
    return net, seeded(net), batch_of()


def rel(a, b):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.linalg.norm(a - b) / max(np.linalg.norm(b), 1e-30))


def chosen_of(gates):
    """(N, S, E) gates -> each token's experts, ascending (N, S, K)."""
    g = np.asarray(gates)
    assert np.all((g > 0).sum(-1) == K)
    return np.sort(np.argsort(-g, -1, kind="stable")[..., :K], -1)


def test_leaves_scopes_and_routes(model):
    net, params, _ = model
    # embed, head, final norm; per layer 2 norms, q k v o, router, 3 stacks
    assert sum(len(v) for v in params.values()) == 3 + L * 10
    assert not net.shared_params and not net.layer_updates
    assert params["l1_moe"]["gate"].shape == (HELD, 32, 64)
    assert params["l1_router"]["w"].shape == (E, 64)
    assert params["l0_q"]["w"].shape == (8 * 16, 64)     # q wider than D
    assert net.layer_facts()["expert_share"]["l3_moe"] == {
        "held_first": 0, "num_held": HELD, "router_num_experts": E}
    types = {l.name: l.TYPE for l in net.layers}
    assert [n for n, t in types.items() if t == "ATTENTION"] == [
        "l0_attn_global", "l1_attn_window", "l2_attn_window",
        "l3_attn_window"]
    assert types["l1_router"] == "MOE_ROUTER" and types["l1_moe"] == "MOE"
    # the router reads the PRE-attention state, the experts the post- one
    by_name = {l.name: l.lp for l in net.layers}
    assert by_name["l1_router"].bottom == ["l1_a"] \
        and by_name["l1_q"].bottom == ["l1_a"] \
        and by_name["l1_moe"].bottom == ["l1_u", "l1_gates"]
    assert net.kernel_routes["l0_attn_global"] == (
        "attention=dense; 2 kv heads repeated x4; no positions")
    assert net.kernel_routes["l1_attn_window"] == (
        "attention=dense; 2 kv heads repeated x4; window 16 as a dense mask")
    assert net.kernel_routes["l1_moe"] == "grouped_matmul=ragged_dot; act=relu"


def test_published_defaults_are_window_4096_and_nope_global_by_layer():
    """``zoo.smallthinker()`` as published: 52 layers, every fourth from
    layer 0 global without positions, the others window 4096 with rotary
    positions at theta 1.5e6; 28 / 4 heads of 128; 64 ReGLU experts of 768,
    top-6 by a softmax router on the pre-attention state; no bias, no
    shared expert."""
    net = zoo.smallthinker()
    att = [l for l in net.layers if l.type == "ATTENTION"]
    assert len(att) == 52
    for i, l in enumerate(att):
        ap = l.attention_param
        assert (ap.num_heads, ap.num_kv_heads, ap.rope_theta) \
            == (28, 4, 1.5e6)
        if i % 4 == 0:
            assert l.name == f"l{i}_attn_global" and ap.window == 0 \
                and not ap.rope
        else:
            assert l.name == f"l{i}_attn_window" and ap.window == 4096 \
                and ap.rope
    widths = {l.name: l.inner_product_param.num_output for l in net.layers
              if l.type == "INNER_PRODUCT"}
    assert (widths["l0_q"], widths["l0_k"], widths["l0_v"], widths["l0_o"],
            widths["lm_head"]) == (3584, 512, 512, 2560, 151936)
    assert not any(l.inner_product_param.bias_term for l in net.layers
                   if l.type == "INNER_PRODUCT")
    routers = [l for l in net.layers if l.type == "MOE_ROUTER"]
    moes = [l for l in net.layers if l.type == "MOE"]
    assert len(routers) == len(moes) == 52
    assert all((l.moe_param.num_experts, l.moe_param.top_k,
                l.moe_param.score_func, l.moe_param.router_hidden,
                list(l.loss_weight)) == (64, 6, "softmax", 0,
                                         [0.0, 0.01, 0.001])
               for l in routers)
    assert all((l.moe_param.expert_width, l.moe_param.activation,
                l.moe_param.num_held) == (768, "relu", 0) for l in moes)
    assert not any("shared" in l.name for l in net.layers)


def test_net_matches_reference_forward(model):
    """f32 against f32: the same products summed in another order. The
    program's experts ARE the reference's own top-k (seeded router matrices
    keep the choices off near-ties), so every layer's routed part, counts
    and router losses compare as they are."""
    net, params, batch = model
    out = jax.jit(lambda p, b: net.apply(p, b, train=True,
                                         keep_blobs=True))(params, batch)
    weights = net.export_weights(params)
    want_loss, want = ref.loss(CFG, weights, batch["tokens"],
                               batch["targets"], held=range(HELD))
    tol = ref.TOLERANCE["f32"]
    assert rel(out.blobs["logits"], want["logits"]) < tol["logits_rel_l2"]
    assert abs(float(out.loss) - float(want_loss)) \
        < tol["loss_rel"] * float(want_loss)
    aux = sum(0.01 * float(out.outputs[f"l{i}_balance_loss"])
              + 0.001 * float(out.outputs[f"l{i}_z_loss"]) for i in range(L))
    assert aux > 0.05
    np.testing.assert_allclose(float(out.loss),
                               float(out.outputs["lm_loss"]) + aux, rtol=1e-6)
    for i in range(L):
        np.testing.assert_array_equal(
            chosen_of(out.blobs[f"l{i}_gates"]),
            np.sort(np.asarray(want["choice"][i]), -1))
        # the softmax over the chosen: a token's weights sum to 1
        np.testing.assert_allclose(
            np.asarray(out.blobs[f"l{i}_gates"]).sum(-1), 1.0, rtol=1e-5)
        counts = np.asarray(want["counts"][i])
        assert counts.sum() == N * S * K
        assert 0 < counts[:HELD].sum() < N * S * K
        np.testing.assert_allclose(out.outputs[f"l{i}_held_share"],
                                   counts[:HELD].sum() / (N * S * K),
                                   rtol=1e-6)
        np.testing.assert_allclose(
            out.outputs[f"l{i}_expert_load"],
            counts[:HELD].max() * HELD / counts[:HELD].sum(), rtol=1e-6)
        assert float(out.outputs[f"l{i}_dropped"]) == 0.0
        np.testing.assert_allclose(out.outputs[f"l{i}_balance_loss"],
                                   want["balance"][i], rtol=1e-5)
        np.testing.assert_allclose(out.outputs[f"l{i}_z_loss"],
                                   want["z"][i], rtol=1e-5)
        np.testing.assert_allclose(out.outputs[f"l{i}_gate_zero_share"],
                                   want["gate_zero_share"][i], rtol=1e-5)
        assert 0.2 < float(out.outputs[f"l{i}_gate_zero_share"]) < 0.8
        assert rel(out.blobs[f"l{i}_m"], want["routed"][i]) < 2e-5


def test_net_matches_reference_gradients(model):
    """Every leaf's gradient: relative L2 under 5e-5 (f32 summation order
    through four blocks of backward)."""
    net, params, batch = model
    got = jax.jit(jax.grad(
        lambda p: net.apply(p, batch, train=True).loss))(params)
    weights = {k: [jnp.asarray(b) for b in v] for k, v in
               net.export_weights(params).items() if k in params}
    want = jax.jit(jax.grad(lambda w: ref.loss(
        CFG, w, batch["tokens"], batch["targets"],
        held=range(HELD))[0]))(weights)
    n = 0
    for lname, leaves in want.items():
        names = [p.name for p in net._layer_by_name[lname].params]
        for pname, g in zip(names, leaves):
            assert np.linalg.norm(np.asarray(g)) > 0, (lname, pname)
            assert rel(got[lname][pname], g) < 5e-5, (lname, pname)
            n += 1
    assert n == sum(len(v) for v in params.values())


@pytest.mark.parametrize("top", ["balance_loss", "z_loss"])
def test_the_router_s_gradient_reaches_the_pre_attention_norm(model, top):
    """The router scores ``l<i>_a``: a router loss's gradient reaches
    ``l<i>_attn_norm``'s gain and the router's matrix, and NOT the
    post-attention norm's gain nor the attention's own projections (which
    a router inside MOE, reading ``l<i>_u``, would reach)."""
    net, params, batch = model
    got = jax.jit(jax.grad(lambda p: net.apply(
        p, batch, train=True).outputs[f"l2_{top}"]))(params)
    assert np.linalg.norm(np.asarray(got["l2_attn_norm"]["g"])) > 0
    assert np.linalg.norm(np.asarray(got["l2_router"]["w"])) > 0
    for lname in ("l2_ffn_norm", "l2_q", "l2_o", "l2_moe"):
        for g in got[lname].values():
            assert not np.any(np.asarray(g)), lname


def test_one_train_step_matches_the_reference_s(model):
    """One whole step as the runner's ``step_check`` compares it: the
    program's gradient through the solver's own update (ADAM + decay + the
    clip) against ``train_step``."""
    from poseidon_tpu.proto.messages import SolverParameter
    from poseidon_tpu.solvers.updates import init_state, make_update_fn
    net, params, batch = model
    sp = SolverParameter(solver_type="ADAM", base_lr=4e-3, lr_policy="fixed",
                         momentum=0.9, momentum2=0.95, delta=1e-8,
                         weight_decay=0.1, clip_gradients=0.05)
    mults = {l.name: {p.name: (p.lr_mult, p.decay_mult) for p in l.params}
             for l in net.layers if l.name in params}
    grads = jax.grad(lambda p: net.apply(p, batch, train=True).loss)(params)
    new, _ = make_update_fn(sp, mults)(params, grads,
                                       init_state(params, "ADAM"))
    owned = {l.name: l.params for l in net.layers if l.name in params}
    opt = {"rate": {n: [sp.base_lr * p.lr_mult for p in ps]
                    for n, ps in owned.items()},
           "decay": {n: [sp.weight_decay * p.decay_mult for p in ps]
                     for n, ps in owned.items()},
           "clip": sp.clip_gradients, "b1": 0.9, "b2": 0.95, "eps": 1e-8}
    want = jax.jit(lambda w: ref.train_step(
        CFG, w, batch["tokens"], batch["targets"], opt,
        held=range(HELD)))(net.export_weights(params))
    assert float(want["grad_norm"]) > sp.clip_gradients      # the clip is on
    for lname, blobs in want["change"].items():
        for pdef, change in zip(owned[lname], blobs):
            moved = np.asarray(new[lname][pdef.name]) \
                - np.asarray(params[lname][pdef.name])
            assert rel(moved, change) < 2e-3, (lname, pdef.name)


def test_the_four_shares_add_up_to_the_uncut_layer():
    """One layer cut into four shares of 4 experts: the shares' outputs sum
    to the uncut reference's layer and to the program's with all 16 held.
    There is no shared expert: nothing is counted twice, the plain sum is
    the layer."""
    whole = build(held=0, n_layers=1)
    params = seeded(whole)
    batch = batch_of()
    cfg = {**CFG, "num_hidden_layers": 1}
    stacks, share_of = params["l0_moe"], 4
    routed, held_share = [], []
    for first in range(0, E, share_of):
        net = build(held=share_of, held_first=first, n_layers=1)
        share = {**params, "l0_moe": {k: v[first:first + share_of]
                                      for k, v in stacks.items()}}
        out = jax.jit(lambda p, b, net=net: net.apply(
            p, b, train=True, keep_blobs=True))(share, batch)
        want = ref.forward(cfg, net.export_weights(share), batch["tokens"],
                           held=range(first, first + share_of))
        assert rel(out.blobs["l0_m"], want["routed"][0]) < 2e-5
        routed.append(np.asarray(out.blobs["l0_m"]))
        held_share.append(float(out.outputs["l0_held_share"]))
    uncut = ref.forward(cfg, whole.export_weights(params), batch["tokens"])
    assert rel(sum(routed), uncut["routed"][0]) < 2e-5
    full = jax.jit(lambda p, b: whole.apply(p, b, train=True,
                                            keep_blobs=True))(params, batch)
    assert rel(full.blobs["l0_m"], uncut["routed"][0]) < 2e-5
    assert float(full.outputs["l0_held_share"]) == 1.0
    # every assignment falls in exactly one share
    np.testing.assert_allclose(sum(held_share), 1.0, rtol=1e-6)
    assert np.asarray(uncut["counts"][0]).sum() == N * S * K


# --------------------------------------------------------------------------- #
# expert_ffn's ReLU arm in its three forms
# --------------------------------------------------------------------------- #

ARMS = {"all_held": 16, "straight_line_share": 8, "chunk_loop_share": 4}


def _ffn_case(n_held, seed=0):
    """128 tokens, top-4 of 16 experts (512 sorted rows); a few of every
    expert's gate units are EXACTLY zero rows, so their pre-activation is
    exactly 0 at every token."""
    key = jax.random.PRNGKey(seed)
    t, d, f, n_exp, top_k, first = 128, 32, 24, 16, 4, 4
    first = 0 if n_held == n_exp else first
    ks = jax.random.split(key, 6)
    x = jax.random.normal(ks[0], (t, d))
    weights = jax.nn.softmax(jax.random.normal(ks[1], (t, top_k)), -1)
    experts = jnp.argsort(jax.random.uniform(ks[2], (t, n_exp)),
                          -1)[:, :top_k]
    gate = 0.3 * jax.random.normal(ks[3], (n_held, f, d))
    gate = gate.at[:, ::5].set(0.0)
    up = 0.3 * jax.random.normal(ks[4], (n_held, f, d))
    down = 0.3 * jax.random.normal(ks[5], (n_held, d, f))
    return x, weights, experts, gate, up, down, first


def _plain(x, weights, experts, gate, up, down, first):
    """down_e(relu(gate_e x) * (up_e x)) of every held expert on every
    token, weighed: the form autodiff differentiates."""
    y = jnp.zeros_like(x)
    for row in range(gate.shape[0]):
        w = jnp.sum(weights * (experts == first + row), -1)
        a = x @ gate[row].T
        y = y + w[:, None] * ((jax.nn.relu(a) * (x @ up[row].T))
                              @ down[row].T)
    return y


def _run_ffn(args, gate_zeros=False):
    x, weights, experts, gate, up, down, first = args
    flat_e, sizes = moe.expert_sizes(experts, 16)
    return moe.expert_ffn(x, weights, flat_e, sizes, gate, up, down, first,
                          "relu", gate_zeros)


@pytest.fixture
def short_chunks(monkeypatch):
    monkeypatch.setattr(moe, "_CHUNK_FLOOR", 128)


@pytest.mark.parametrize("arm", sorted(ARMS))
def test_relu_arm_equals_autodiff_of_the_plain_form(arm, short_chunks):
    """Value and the gradient of every operand, the hand-written pullback
    of the chunk loop included, with gate pre-activations that are exactly
    0 (where ReLU's derivative is taken as 0 on both sides)."""
    args = _ffn_case(ARMS[arm])
    rows, chunk = 512, moe.held_chunk_rows(512, ARMS[arm], 16)
    assert (ARMS[arm] < 16 and moe.held_rows_loop(rows, chunk)) \
        == (arm == "chunk_loop_share")
    a = args[0] @ args[3][0].T
    assert np.all(np.asarray(a)[:, ::5] == 0.0)
    cot = jax.random.normal(jax.random.PRNGKey(9), args[0].shape)

    def scalar(f):
        return lambda *diff: jnp.sum(f((*diff[:2], args[2], *diff[2:],
                                        args[6])) * cot)

    diff = (args[0], args[1], *args[3:6])
    got_y, got = jax.value_and_grad(scalar(_run_ffn), (0, 1, 2, 3, 4))(*diff)
    want_y, want = jax.value_and_grad(
        scalar(lambda a: _plain(*a)), (0, 1, 2, 3, 4))(*diff)
    np.testing.assert_allclose(got_y, want_y, rtol=1e-5)
    for name, g, w in zip(("x", "weights", "gate", "up", "down"), got, want):
        assert np.linalg.norm(np.asarray(w)) > 0, name
        assert rel(g, w) < 1e-5, name
    # the exactly-zero gate units take no gradient from either side
    assert not np.any(np.asarray(got[2])[:, ::5])


@pytest.mark.parametrize("arm", sorted(ARMS))
def test_gate_zero_share_is_the_direct_count(arm, short_chunks):
    """``expert_ffn(..., gate_zeros=True)``: the share of the held experts'
    live rows' gate pre-activations that are <= 0, against a count over
    (token, chosen held expert) pairs; the exact zeros count."""
    args = _ffn_case(ARMS[arm], seed=1)
    x, _, experts, gate, _, _, first = args
    y, share = jax.jit(lambda x: _run_ffn((x, *args[1:]), True))(x)
    np.testing.assert_allclose(y, _run_ffn(args), rtol=1e-4, atol=1e-5)
    zeros = rows = 0
    for row in range(gate.shape[0]):
        took = np.asarray(experts == first + row).any(-1)
        a = np.asarray(x @ gate[row].T)[took]
        zeros += int((a <= 0).sum())
        rows += a.size
    assert rows > 0 and float(share) == pytest.approx(zeros / rows, rel=1e-6)
    assert float(share) > 0.5           # a fifth exactly 0, half the rest
    # no gradient flows through the count
    g = jax.grad(lambda x: _run_ffn((x, *args[1:]), True)[1])(x)
    assert not np.any(np.asarray(g))


def test_silu_stays_the_default_and_an_unknown_activation_is_refused():
    from poseidon_tpu.proto.messages import MoEParameter
    assert MoEParameter().activation == "silu" and moe.EXPERT_ACTS == (
        "silu", "relu", "relu2")        # PR 64: the ungated squared ReLU
    text = zoo.to_prototxt(zoo.smallthinker(batch=1, n_layers=1, **{
        k: v for k, v in SIZES.items() if k != "n_layers"}))
    assert text.count('activation: "relu"') == 1
    with pytest.raises(ValueError, match="activation 'gelu'"):
        Net(load_net_from_string(text.replace('"relu"', '"gelu"')), "TRAIN",
            source_shapes={"tokens": (1, S), "targets": (1, S)})


def test_window_layers_see_the_window_and_the_global_layer_everything(model):
    """Perturb token t: the global layer 0's attention output moves
    everywhere from t on; a window layer's reach is W. Layer 0 reads the
    embedding alone, so its reach is exact; one layer of the other kind is
    built for the window's."""
    _, _, batch = model
    t = 9
    other = dict(batch, tokens=batch["tokens"].at[:, t].set(
        (batch["tokens"][:, t] + 1) % SIZES["vocab"]))
    for first_global, name in ((0, "l0_attn_global"), (1, "l0_attn_window")):
        net = build(n_layers=1, first_global=first_global)
        assert [l.name for l in net.layers if l.TYPE == "ATTENTION"] == [name]
        params = seeded(net)
        run = jax.jit(lambda b, net=net, params=params: net.apply(
            params, b, train=True, keep_blobs=True).blobs["l0_att"])
        x, y = np.asarray(run(batch)), np.asarray(run(other))
        np.testing.assert_array_equal(x[:, :t], y[:, :t])
        assert np.any(x[:, t] != y[:, t])
        if first_global:
            np.testing.assert_array_equal(x[:, t + W:], y[:, t + W:])
            assert np.any(x[:, t + W - 1] != y[:, t + W - 1])
        else:
            assert np.any(x[:, -1] != y[:, -1])


def _job(tmp_path, max_iter):
    import h5py
    from poseidon_tpu.proto.messages import load_solver
    rs = np.random.RandomState(7)
    stream = rs.randint(0, SIZES["vocab"], S + 1).astype(np.int32)
    with h5py.File(tmp_path / "tokens.h5", "w") as h:
        h["data"] = np.tile(stream[:-1], (8, 1))
        h["label"] = np.tile(stream[1:], (8, 1))
    (tmp_path / "tokens.txt").write_text(str(tmp_path / "tokens.h5") + "\n")
    (tmp_path / "net.prototxt").write_text(zoo.to_prototxt(zoo.smallthinker(
        batch=N, source=str(tmp_path / "tokens.txt"), held=HELD, **SIZES)))
    (tmp_path / "solver.prototxt").write_text(
        f'net: "{tmp_path / "net.prototxt"}"\nsolver_type: ADAM\n'
        f'base_lr: 0.004\nlr_policy: "fixed"\nmomentum: 0.9\n'
        f'momentum2: 0.95\ndelta: 1e-8\nweight_decay: 0.1\n'
        f'clip_gradients: 1.0\nmax_iter: {max_iter}\ndisplay: 1\n'
        f'snapshot: 0\nsnapshot_after_train: false\n'
        f'snapshot_prefix: "snap/st"\nrandom_seed: 3\n')
    return load_solver(str(tmp_path / "solver.prototxt"))


def test_engine_trains_it_and_remat_changes_nothing(tmp_path):
    """Through ``Engine.train`` as the ``train`` command runs it: three
    steps with one checkpoint a layer and one for the head (the solver
    header's ``--remat``) leave the very weights three steps without
    leave; the display rows carry every router's losses and every MOE
    layer's routing, the gate-zero share among them."""
    from poseidon_tpu.parallel.mesh import make_mesh
    from poseidon_tpu.runtime.engine import Engine

    def finish(out, remat=None):
        eng = Engine(_job(tmp_path, max_iter=3), output_dir=str(out),
                     remat=remat, mesh=make_mesh(1))
        try:
            eng.train()
            return jax.tree_util.tree_map(np.asarray, eng.params), \
                eng.remat_plan, eng.metrics.rows
        finally:
            eng.close()

    whole, _, rows = finish(tmp_path / "whole")
    remat, plan, _ = finish(tmp_path / "remat", remat=",".join(REMAT))
    assert len(plan.segments) == L + 1
    for a, b in zip(jax.tree_util.tree_leaves(whole),
                    jax.tree_util.tree_leaves(remat)):
        np.testing.assert_array_equal(a, b)
    for i in range(L):
        for top in ("balance_loss", "z_loss", "expert_load", "dropped",
                    "held_share", "gate_zero_share"):
            assert f"l{i}_{top}" in rows[-1], (i, top)
        assert rows[-1][f"l{i}_dropped"] == 0.0
        assert 0.0 < rows[-1][f"l{i}_gate_zero_share"] < 1.0
    assert rows[-1]["loss"] < rows[0]["loss"]


@pytest.mark.parametrize("name", ["train", "solver"])
def test_example_prototxts_are_the_zoo_s_and_the_benchmark_s(name):
    """examples/lm/smallthinker_21b_*.prototxt: the net is what
    `zoo.smallthinker` writes at the cut its header states, and the
    benchmark's copies (what the cell runs) are the same bytes."""
    example = os.path.join(ROOT, "examples", "lm",
                           f"smallthinker_21b_{name}.prototxt")
    copy = os.path.join(ROOT, "benchmark", "configs", "smallthinker_21b",
                        f"{name}.prototxt")
    with open(example) as a, open(copy) as b:
        text = a.read()
        assert text == b.read()
    if name == "train":
        m = re.search(r"zoo\.smallthinker\(batch=1, n_layers=(\d+), "
                      r"held=(\d+), vocab=(\d+)\)", text)
        depth, held, vocab = (int(x) for x in m.groups())
        body = "".join(l for l in text.splitlines(True)
                       if not l.startswith("#"))
        assert body == zoo.to_prototxt(zoo.smallthinker(
            batch=1, n_layers=depth, held=held, vocab=vocab))
        assert (depth, held, vocab) == (4, 16, 151936 // 8)
        net = load_net_from_string(body)
        windows = [(l.attention_param.window, l.attention_param.rope)
                   for l in net.layers if l.type == "ATTENTION"]
        assert windows == [(0, False)] + [(4096, True)] * 3
    else:
        assert "--remat '" + ",".join(REMAT) + "'" in text
        assert "--seq_len 16384" in text and "--vocab 18992" in text
