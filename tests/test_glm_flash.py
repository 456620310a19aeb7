"""GLM-4.7-Flash's block and its prediction module as layers of the Net
against their plain reference (benchmark/reference/glm_flash.py, loaded from
there: one file, no second copy), at a small size on the CPU with seeded
weights: main and module logits, both losses, every gradient and one whole
train step; the embedding's and the head's gradients as the sums of their
two users'; the token shift and the mean over the S - 1 positions that have
a second-next token; the expert shares summing to the whole layer with the
shared expert counted ONCE; the planted faults the reference can write down;
what the whole published model counts; the example prototxts."""

import importlib.util
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from poseidon_tpu.core.net import Net
from poseidon_tpu.models import zoo
from poseidon_tpu.proto.messages import load_net_from_string

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_spec = importlib.util.spec_from_file_location(
    "glm_reference",
    os.path.join(ROOT, "benchmark", "reference", "glm_flash.py"))
ref = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(ref)

# the dense layer, two sparse layers and the module's sparse block
L, DENSE, E, K, HELD = 3, 1, 16, 4, 8
SIZES = dict(n_layers=L, hidden=64, heads=4, q_rank=24, kv_rank=32,
             nope_dim=12, rope_dim=4, v_dim=16, dense_width=96, experts=E,
             top_k=K, expert_width=32, shared_width=32, vocab=128)
CFG = {"num_hidden_layers": L, "num_dense_layers": DENSE, "num_heads": 4,
       "q_lora_rank": 24, "kv_lora_rank": 32, "qk_nope_head_dim": 12,
       "qk_rope_head_dim": 4, "v_head_dim": 16, "num_experts": E,
       "num_experts_per_tok": K, "route_scale": 1.8, "rope_theta": 1e6,
       "rms_norm_eps": 1e-5, "mtp_layers": 1, "mtp_weight": 0.3}
N, S = 2, 48
RATE = 0.001
SPARSE = [f"l{i}_" for i in range(DENSE, L)] + ["mtp_"]


def build(held=HELD, held_first=0, n=N, s=S, **kw):
    # through the text form: what a user's prototxt goes through
    text = zoo.to_prototxt(zoo.glm_flash(
        batch=n, held=held, held_first=held_first, **{**SIZES, **kw}))
    return Net(load_net_from_string(text), "TRAIN",
               source_shapes={"tokens": (n, s), "targets": (n, s)})


def batch_of(n=N, s=S, seed=5):
    """A stream cut into data and label as the token file is: the targets
    are the tokens one on."""
    stream = jax.random.randint(jax.random.PRNGKey(seed), (n, s + 1), 0,
                                SIZES["vocab"])
    return {"tokens": stream[:, :-1], "targets": stream[:, 1:]}


def seeded(net, seed=3):
    """Fresh weights, then everything a fresh model has at a trivial value
    moved off it; the routers' matrices larger, so that their choices are
    not all near-ties; the other matrices larger, so that the scores depend
    on the positions."""
    params = net.init(jax.random.PRNGKey(seed))
    for i, (lname, lp) in enumerate(sorted(params.items())):
        for j, (pname, w) in enumerate(sorted(lp.items())):
            noise = jax.random.normal(jax.random.PRNGKey(100 + 31 * i + j),
                                      w.shape)
            if pname == "g":
                lp[pname] = 1.0 + 0.2 * noise
            elif pname == "bias":
                lp[pname] = 0.02 * noise
            elif lname.endswith("_router"):
                lp[pname] = 0.5 * noise
            else:
                lp[pname] = noise / np.sqrt(w.shape[-1])
    return params


def owned(net, params):
    """{layer: [blobs]} of the OWNERS, as the reference takes them: the
    module's embedding and head are the main model's arrays."""
    return {l.name: [params[l.name][p.name] for p in l.params]
            for l in net.layers if l.name in params}


@pytest.fixture(scope="module")
def model():
    net = build()
    return net, seeded(net), batch_of()


def rel(a, b):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.linalg.norm(a - b) / max(np.linalg.norm(b), 1e-30))


def test_leaves_scopes_shares_and_routes(model):
    net, params, _ = model
    # embed, head, final norm; a block: 2 norms, 5 projections (kvb as two)
    # and 2 latent gains; the dense layer's 3; a sparse block's router 2, 3
    # stacks, shared 3; the module's 2 norms, W_eh and head norm
    assert sum(len(v) for v in params.values()) \
        == 3 + (L + 1) * (2 + 8) + DENSE * 3 + L * 8 + 4
    # the embedding and the head: one array each, two users each
    assert net.shared_params == {
        "tok_w": {"owner": "embed/w", "uses": 2},
        "head_w": {"owner": "lm_head/w", "uses": 2}}
    assert "mtp_embed" not in params and "mtp_head" not in params
    assert params["l1_moe"]["gate"].shape == (HELD, 32, 64)
    assert params["l0_mla_qb"]["w"].shape == (4 * 16, 24)
    assert params["mtp_mla_kva"]["w"].shape == (32 + 4, 64)
    assert params["mtp_eh"]["w"].shape == (64, 128)
    assert net.layer_updates == {
        (p + "router", "bias"): p + "bias_next" for p in SPARSE}
    types = {l.name: l.TYPE for l in net.layers}
    assert [n for n, t in types.items() if t == "ATTENTION"] \
        == [f"l{i}_mla_attn" for i in range(L)] + ["mtp_mla_attn"]
    assert types["mtp_shift"] == "TOKEN_SHIFT" \
        and types["mtp_loss"] == "WEIGHTED_MEAN_LOSS" \
        and types["mtp_cat"] == "CONCAT" and types["mtp_embed"] == "EMBED"
    assert net.kernel_routes["l0_mla_attn"] == (
        "attention=dense; d 16/16; k_pe rotated once, joined x4")
    # both losses are outputs of their own: a display shows them apart
    assert {"lm_loss", "mtp_loss"} <= set(net.output_names)
    # the main head stands between the module's block and the module's
    # head: two runs of mtp_* layers, so --remat /mtp_/ makes two units
    from poseidon_tpu.core.remat import resolve_entries
    _, segments = resolve_entries([l.name for l in net.layers],
                                  [r"/l\d+_/", "/mtp_/", "/lm_/"])
    assert len(segments) == L + 2 + 1
    mtp = [seg for seg in segments if seg[0].startswith("mtp_")]
    assert [seg[0] for seg in mtp] == ["mtp_embed", "mtp_snorm"] \
        and mtp[1][-1] == "mtp_loss"


def test_route_on_the_chip_at_the_published_heads(monkeypatch):
    monkeypatch.setenv("POSEIDON_FORCE_PALLAS", "1")
    net = build(n=1, s=256, heads=2, nope_dim=192, rope_dim=64, v_dim=256,
                n_layers=1, mtp=0)
    route = net.kernel_routes["l0_mla_attn"]
    assert route.startswith("attention=pallas_flash (fwd 256x256 1/1") \
        and route.endswith("; operands token-major (B,S,HxD)); k_pe "
                           "rotated once, joined x2"), route


def test_net_matches_reference_forward(model):
    """f32 against f32: flash-free attention with the shared part appended
    to every head against the masked softmax with the shared part explicit,
    the same products in another order; both heads, both losses."""
    net, params, batch = model
    out = jax.jit(lambda p, b: net.apply(p, b, train=True,
                                         keep_blobs=True))(params, batch)
    weights = owned(net, params)
    want_loss, want = ref.loss(CFG, weights, batch["tokens"],
                               batch["targets"], held=range(HELD))
    tol = ref.TOLERANCE["f32"]
    assert rel(out.blobs["logits"], want["logits"]) < tol["logits_rel_l2"]
    assert rel(out.blobs["mtp_logits"], want["mtp_logits"]) \
        < tol["mtp_logits_rel_l2"]
    for mine, theirs in ((out.loss, want_loss),
                         (out.outputs["lm_loss"], want["lm_loss"]),
                         (out.outputs["mtp_loss"], want["mtp_loss"])):
        assert abs(float(mine) - float(theirs)) \
            < tol["loss_rel"] * float(theirs)
    assert float(out.loss) == pytest.approx(
        float(out.outputs["lm_loss"]) + 0.3 * float(out.outputs["mtp_loss"]),
        rel=1e-6)
    for at, p in enumerate(SPARSE):
        g = np.asarray(out.blobs[p + "gates"])
        np.testing.assert_array_equal(
            np.sort(np.argsort(-g, -1, kind="stable")[..., :K], -1),
            np.sort(np.asarray(want["choice"][at]), -1))
        np.testing.assert_allclose(g.sum(-1), 1.8, rtol=1e-5)
        counts = np.asarray(want["counts"][at])
        np.testing.assert_allclose(out.outputs[p + "held_share"],
                                   counts[:HELD].sum() / (N * S * K),
                                   rtol=1e-6)
        np.testing.assert_allclose(
            out.updates[p + "router"]["bias"],
            ref.next_bias(weights[p + "router"][-1], counts, RATE),
            rtol=0, atol=1e-7)
        assert rel(out.blobs[p + "m"], want["routed"][at]) < 3e-4
        assert rel(out.blobs[p + "s"], want["shared"][at]) < 3e-4


def test_net_matches_reference_gradients(model):
    """Every leaf's gradient: relative L2 under 1e-4 (f32 summation order
    through four blocks of backward). The selection bias takes none; the
    embedding's and the head's are over both users."""
    net, params, batch = model
    got = jax.jit(jax.grad(
        lambda p: net.apply(p, batch, train=True).loss))(params)
    want = jax.jit(jax.grad(lambda w: ref.loss(
        CFG, w, batch["tokens"], batch["targets"],
        held=range(HELD))[0]))(owned(net, params))
    n = 0
    for lname, leaves in want.items():
        names = [p.name for p in net._layer_by_name[lname].params]
        for pname, g in zip(names, leaves):
            if pname == "bias":
                assert not np.any(np.asarray(g)) \
                    and not np.any(np.asarray(got[lname][pname]))
                continue
            assert np.linalg.norm(np.asarray(g)) > 0, (lname, pname)
            assert rel(got[lname][pname], g) < 1e-4, (lname, pname)
            n += 1
    assert n == sum(len(v) for v in params.values()) - len(SPARSE)


@pytest.mark.parametrize("shared", ["embed", "lm_head"])
def test_a_shared_array_s_gradient_is_the_sum_of_its_two_users(model,
                                                               shared):
    """Unbind the module's user (the same net with ``mtp_embed`` /
    ``mtp_head`` owning arrays of their own, filled with the shared one's
    values): the shared array's gradient is the sum of the two gradients,
    and neither is zero."""
    net, params, batch = model
    user = {"embed": "mtp_embed", "lm_head": "mtp_head"}[shared]
    share = {"embed": "tok_w", "lm_head": "head_w"}[shared]
    text = zoo.to_prototxt(zoo.glm_flash(batch=N, held=HELD, **SIZES))
    first, second, rest = text.split(f'name: "{share}"')   # its two users
    apart = Net(load_net_from_string(
        first + f'name: "{share}_main"' + second + f'name: "{share}_mtp"'
        + rest), "TRAIN",
        source_shapes={"tokens": (N, S), "targets": (N, S)})
    assert not set(apart.shared_params) & {share}
    both = {**params, user: {"w": params[shared]["w"]}}
    grad = lambda net_, p: jax.jit(jax.grad(          # noqa: E731
        lambda q: net_.apply(q, batch, train=True).loss))(p)
    summed, parts = grad(net, params), grad(apart, both)
    assert np.linalg.norm(parts[user]["w"]) > 0 \
        and np.linalg.norm(parts[shared]["w"]) > 0
    assert rel(summed[shared]["w"],
               parts[shared]["w"] + parts[user]["w"]) < 1e-5


def test_one_train_step_matches_the_reference_s(model):
    """One whole step as the runner's ``step_check`` compares it: the
    program's gradient through the solver's own update (ADAM + decay + the
    clip, the biases outside all three) against ``train_step``."""
    from poseidon_tpu.proto.messages import SolverParameter
    from poseidon_tpu.solvers.updates import init_state, make_update_fn
    net, params, batch = model
    sp = SolverParameter(solver_type="ADAM", base_lr=4e-3, lr_policy="fixed",
                         momentum=0.9, momentum2=0.95, delta=1e-8,
                         weight_decay=0.1, clip_gradients=0.05)
    mults = {l.name: {p.name: (p.lr_mult, p.decay_mult) for p in l.params}
             for l in net.layers if l.name in params}

    def loss_and_updates(p):
        out = net.apply(p, batch, train=True)
        return out.loss, out.updates

    (loss, updates), grads = jax.value_and_grad(loss_and_updates,
                                                has_aux=True)(params)
    new, _ = make_update_fn(sp, mults)(params, grads,
                                       init_state(params, "ADAM"), updates)
    leaves = {l.name: l.params for l in net.layers if l.name in params}
    opt = {"rate": {n: [sp.base_lr * p.lr_mult for p in ps]
                    for n, ps in leaves.items()},
           "decay": {n: [sp.weight_decay * p.decay_mult for p in ps]
                     for n, ps in leaves.items()},
           "clip": sp.clip_gradients, "b1": 0.9, "b2": 0.95, "eps": 1e-8,
           "bias_rate": RATE}
    want = jax.jit(lambda w: ref.train_step(
        CFG, w, batch["tokens"], batch["targets"], opt, held=range(HELD),
        remat=True, q_block=16))(owned(net, params))
    assert float(want["grad_norm"]) > sp.clip_gradients      # the clip is on
    assert abs(float(loss) - float(want["loss"])) < 1e-5 * float(loss)
    assert ref.router_names(want["change"]) == [p + "router" for p in SPARSE]
    for lname, blobs in want["change"].items():
        for pdef, change in zip(leaves[lname], blobs):
            moved = np.asarray(new[lname][pdef.name]) \
                - np.asarray(params[lname][pdef.name])
            if pdef.name == "bias":
                np.testing.assert_allclose(moved, change, rtol=0, atol=1e-7)
            else:
                # Adam's first step is the gradient's sign: in a leaf of
                # a few hundred numbers one near-zero entry is seen
                assert rel(moved, change) < (
                    5e-3 if change.size >= 2 ** 12 else 0.1), \
                    (lname, pdef.name)
    assert mults["mtp_enorm"] == {"g": (1.0, 0.0)} \
        and mults["mtp_eh"] == {"w": (1.0, 1.0)}


def test_token_shift_marks_the_tail_and_the_mean_leaves_it_out():
    """TOKEN_SHIFT with offset 1: top(t) = bottom(t + 1), zeros and mark 0
    at the last position; offset -1 (the default) is the look-back it always
    was. WEIGHTED_MEAN_LOSS over the mark is the mean over S - 1 positions,
    whatever stands at the last."""
    text = """
layers { name: "next" type: TOKEN_SHIFT bottom: "x" top: "nx" top: "real"
         token_shift_param { offset: 1 } }
layers { name: "before" type: TOKEN_SHIFT bottom: "x" top: "bx" }
layers { name: "two" type: TOKEN_SHIFT bottom: "ids" top: "ids2"
         top: "real2" token_shift_param { offset: 2 } }
layers { name: "mean" type: WEIGHTED_MEAN_LOSS bottom: "per" bottom: "real"
         top: "mean" loss_weight: 0.5 }
"""
    n, s = 2, 6
    net = Net(load_net_from_string(text), "TRAIN", source_shapes={
        "x": (n, s, 3), "ids": (n, s), "per": (n, s)})
    x = jnp.arange(n * s * 3, dtype=jnp.float32).reshape(n, s, 3) + 1.0
    ids = jnp.arange(n * s, dtype=jnp.int32).reshape(n, s) + 1
    per = jnp.asarray(np.random.RandomState(0).rand(n, s), jnp.float32)
    out = net.apply({}, {"x": x, "ids": ids, "per": per}, keep_blobs=True)
    b = out.blobs
    np.testing.assert_array_equal(b["nx"][:, :-1], x[:, 1:])
    np.testing.assert_array_equal(b["nx"][:, -1], 0)
    np.testing.assert_array_equal(b["real"], [[1] * (s - 1) + [0]] * n)
    np.testing.assert_array_equal(b["bx"][:, 1:], x[:, :-1])
    np.testing.assert_array_equal(b["bx"][:, 0], 0)
    np.testing.assert_array_equal(b["ids2"][:, :-2], ids[:, 2:])
    assert b["ids2"].dtype == jnp.int32
    np.testing.assert_array_equal(b["real2"], [[1] * (s - 2) + [0, 0]] * n)
    want = float(jnp.mean(per[:, :-1]))
    assert float(b["mean"]) == pytest.approx(want, rel=1e-6)
    assert float(out.loss) == pytest.approx(0.5 * want, rel=1e-6)
    # the last position's value does not matter, and takes no gradient
    g = jax.grad(lambda p: net.apply(
        {}, {"x": x, "ids": ids, "per": p}).loss)(per)
    np.testing.assert_array_equal(g[:, -1], 0)
    np.testing.assert_allclose(g[:, :-1], 0.5 / (n * (s - 1)), rtol=1e-6)
    for broken, match in (
            (text.replace("offset: 1", "offset: 0"), "non-zero offset"),
            (text.replace('top: "real"', 'top: "real" top: "z"'),
             "TOKEN_SHIFT has 1 or 2 tops")):
        with pytest.raises(ValueError, match=match):
            Net(load_net_from_string(broken), "TRAIN", source_shapes={
                "x": (n, s, 3), "ids": (n, s), "per": (n, s)})


def test_the_module_s_loss_is_over_s_minus_one_positions(model):
    """The module's targets are the second-next tokens; what stands in the
    targets' LAST position's successor (there is none) cannot matter: the
    loss is the reference's mean over S - 1 positions, and changing the
    last main target moves the module's loss only through the embedding it
    feeds, never as a target."""
    net, params, batch = model
    out = net.apply(params, batch, train=True, keep_blobs=True)
    nll = np.asarray(out.blobs["mtp_nll_pos"])
    assert float(out.outputs["mtp_loss"]) == pytest.approx(
        float(nll[:, :-1].mean()), rel=1e-6)
    np.testing.assert_array_equal(out.blobs["mtp_targets"][:, :-1],
                                  batch["targets"][:, 1:])
    np.testing.assert_array_equal(out.blobs["mtp_real"][:, -1], 0)


def test_the_shares_add_up_with_the_shared_expert_counted_once():
    """One sparse layer (behind the dense one) cut into 2 shares of 8
    experts, the module off: the shares' ROUTED parts plus the shared expert
    ONCE equal the uncut reference's layer and the program's with all 16
    held."""
    whole = build(held=0, n_layers=2, mtp=0)
    params = seeded(whole)
    batch = batch_of()
    cfg = {**CFG, "num_hidden_layers": 2, "mtp_layers": 0}
    stacks, share_of = params["l1_moe"], HELD
    routed, shared = [], []
    for first in range(0, E, share_of):
        net = build(held=share_of, held_first=first, n_layers=2, mtp=0)
        share = {**params, "l1_moe": {k: v[first:first + share_of]
                                      for k, v in stacks.items()}}
        out = jax.jit(lambda p, b, net=net: net.apply(
            p, b, train=True, keep_blobs=True))(share, batch)
        want = ref.forward(cfg, owned(net, share), batch["tokens"],
                           held=range(first, first + share_of))
        assert rel(out.blobs["l1_m"], want["routed"][0]) < 1e-4
        assert rel(out.blobs["l1_f"],
                   want["routed"][0] + want["shared"][0]) < 1e-4
        routed.append(np.asarray(out.blobs["l1_m"]))
        shared.append(np.asarray(out.blobs["l1_s"]))
    for other in shared[1:]:
        np.testing.assert_array_equal(shared[0], other)
    uncut = ref.forward(cfg, owned(whole, params), batch["tokens"])
    layer = uncut["routed"][0] + uncut["shared"][0]
    assert rel(sum(routed) + shared[0], layer) < 1e-4
    # and NOT the plain sum of the shares' outputs
    assert rel(sum(routed) + sum(shared), layer) > 0.1
    full = jax.jit(lambda p, b: whole.apply(p, b, train=True,
                                            keep_blobs=True))(params, batch)
    assert rel(full.blobs["l1_f"], layer) < 1e-4
    assert float(full.outputs["l1_held_share"]) == 1.0
    assert not whole.shared_params            # no module: nothing to share


@pytest.mark.parametrize("fault", ref.FAULTS)
def test_each_planted_fault_is_another_function(model, fault):
    """The reference's written-down faults each move what they are there to
    move, far past the f32 limits, and leave the rest as it was."""
    net, params, batch = model
    weights = owned(net, params)
    run = lambda **how: ref.loss(CFG, weights, batch["tokens"],  # noqa: E731
                                 batch["targets"], held=range(HELD), **how)
    (right, a), (wrong, b) = run(), run(fault=fault)
    main = rel(b["logits"], a["logits"])
    module = rel(b["mtp_logits"], a["mtp_logits"])
    if fault in ("rope_on_head_start", "k_pe_unrotated"):
        assert main > 0.05 and module > 0.05
    elif fault == "head_not_shared":
        assert main == 0.0 and module > 0.5
    elif fault == "mtp_target_next":
        assert main == module == 0.0
        assert abs(float(b["mtp_loss"]) - float(a["mtp_loss"])) > 0.01
    else:                                         # mtp_weight_zero
        assert float(wrong) == pytest.approx(float(a["lm_loss"]), rel=1e-6)
    assert abs(float(wrong) - float(right)) > 1e-3 * float(right)


def test_positions_and_causal_reach(model):
    """Perturb token t: nothing before t moves anywhere (attention is
    causal, and the module's look-ahead is through its input alone: logits'
    at t - 1 read the embedding of token t). And the positions are real:
    the same tokens one place later give other logits."""
    net, params, batch = model
    t = 30
    other = {k: v.at[:, t].set((v[:, t] + 1) % SIZES["vocab"])
             for k, v in batch.items()}
    run = jax.jit(lambda b: net.apply(params, b, train=True,
                                      keep_blobs=True).blobs)
    a, b = run(batch), run({"tokens": other["tokens"],
                            "targets": batch["targets"]})
    for blob in ("l0_att", "logits", "mtp_logits"):
        x, y = np.asarray(a[blob]), np.asarray(b[blob])
        np.testing.assert_array_equal(x[:, :t], y[:, :t])
        assert np.any(x[:, t] != y[:, t]) and np.any(x[:, -1] != y[:, -1])
    # the targets feed the module's input at their own position on
    c = run({"tokens": batch["tokens"], "targets": other["targets"]})
    x, y = np.asarray(a["mtp_logits"]), np.asarray(c["mtp_logits"])
    np.testing.assert_array_equal(x[:, :t], y[:, :t])
    assert np.any(x[:, t] != y[:, t])
    np.testing.assert_array_equal(a["logits"], c["logits"])
    # a sequence of ONE repeated token: every value row is the same, so the
    # attention's result is too, whatever the scores
    same = {k: jnp.full_like(v, 7) for k, v in batch.items()}
    att = np.asarray(run(same)["l0_att"])
    assert np.allclose(att[:, 1:], att[:, :-1], atol=1e-6)
    # and the positions are real: two earlier tokens change places, and a
    # later row of the FIRST layer's attention moves (without positions it
    # is a function of the set of earlier tokens, whatever their order)
    tok = batch["tokens"]
    swapped = dict(batch, tokens=tok.at[:, 3].set(tok[:, 5])
                   .at[:, 5].set(tok[:, 3]))
    assert bool(jnp.any(tok[:, 3] != tok[:, 5]))
    u, v = a["l0_att"], run(swapped)["l0_att"]
    assert rel(np.asarray(v)[:, 10:], np.asarray(u)[:, 10:]) > 1e-4


def test_layers_refuse_what_they_cannot_mean():
    text = zoo.to_prototxt(zoo.glm_flash(batch=N, **SIZES))

    def broken(old, new, match):
        assert old in text
        with pytest.raises(ValueError, match=match):
            Net(load_net_from_string(text.replace(old, new, 1)), "TRAIN",
                source_shapes={"tokens": (N, S), "targets": (N, S)})

    broken("    rotary_shared: true\n",
           "    rotary_shared: true\n    rotary_dims: 2\n",
           "rotary_dims 2 is neither that nor unset")
    broken("    rotary_shared: true\n",
           "    rotary_shared: true\n    rope: false\n",
           "contradict each other")
    # without the shared part the keys are too narrow for the heads
    broken('  bottom: "l0_kpe"\n  top: "l0_att"', '  top: "l0_att"',
           "need k, v of width 64, 64")
    with pytest.raises(ValueError, match="mtp 2 is neither 0 nor 1"):
        zoo.glm_flash(mtp=2)


def test_the_whole_published_model_counts():
    """``zoo.glm_flash()`` with no arguments writes the whole model: 47
    layers (one dense, 46 sparse) and one module, 64 experts all held,
    154,880 rows, 30.59 B parameters; the cut is the issue's 706,518,848."""
    net_param = zoo.glm_flash()
    by_type = {}
    for l in net_param.layers:
        by_type.setdefault(l.type, []).append(l)
    assert len(by_type["ATTENTION"]) == 47 + 1
    assert len(by_type["MOE"]) == 46 + 1
    assert all(l.moe_param.num_experts == 64 and l.moe_param.num_held == 0
               and l.moe_param.top_k == 4 and l.moe_param.expert_width == 1536
               and l.moe_param.route_scale == 1.8
               and l.moe_param.score_func == "sigmoid"
               for l in by_type["MOE"] + by_type["MOE_ROUTER"])
    assert all(l.attention_param.rotary_shared
               and l.attention_param.num_heads == 20
               and l.attention_param.value_head_dim == 256
               and l.attention_param.rope_theta == 1e6
               for l in by_type["ATTENTION"])
    assert [l.embed_param.input_dim for l in by_type["EMBED"]] \
        == [154880, 154880]

    def count(**cut):
        net = Net(zoo.glm_flash(**cut), "TRAIN",
                  source_shapes={"tokens": (1, 64), "targets": (1, 64)})
        return net.param_count()

    attention = 1_572_864 + 3_932_160 + 1_179_648 + 4_587_520 + 10_485_760 \
        + 768 + 512
    assert attention == 21_759_232
    sparse = attention + 131_136 + 64 * 9_437_184 + 9_437_184 + 4_096
    module = 4_096 + 8_388_608 + sparse + 2_048
    whole = (attention + 62_914_560 + 4_096) + 46 * sparse \
        + 2 * 154_880 * 2048 + 2_048 + module
    assert count() == whole == 30_587_100_096
    assert count(n_layers=5, held=8, vocab=19360) == 706_518_848
    assert count(n_layers=5, held=8, vocab=19360, mtp=0) == 591_294_976


@pytest.mark.parametrize("name", ["train", "solver"])
def test_example_prototxts_are_the_zoo_s_and_the_benchmark_s(name):
    """examples/lm/glm_4_7_flash_*.prototxt: the net is what
    `zoo.glm_flash` writes at the cut its header states, and the
    benchmark's copies (what the cell runs) are the same bytes."""
    example = os.path.join(ROOT, "examples", "lm",
                           f"glm_4_7_flash_{name}.prototxt")
    copy = os.path.join(ROOT, "benchmark", "configs", "glm_4_7_flash",
                        f"{name}.prototxt")
    with open(example) as a, open(copy) as b:
        text = a.read()
        assert text == b.read()
    if name == "train":
        m = re.search(r"zoo\.glm_flash\(batch=1, n_layers=(\d+), "
                      r"held=(\d+), vocab=(\d+), mtp=(\d+)\)", text)
        depth, held, vocab, mtp = (int(x) for x in m.groups())
        body = "".join(l for l in text.splitlines(True)
                       if not l.startswith("#"))
        assert body == zoo.to_prototxt(zoo.glm_flash(
            batch=1, n_layers=depth, held=held, vocab=vocab, mtp=mtp))
        assert (depth, held, vocab, mtp) == (5, 8, 154880 // 8, 1)
        net = load_net_from_string(body)
        widths = {l.name: l.inner_product_param.num_output
                  for l in net.layers if l.type == "INNER_PRODUCT"}
        assert widths["l0_mla_qa"] == 768 \
            and widths["l0_mla_qb"] == 20 * 256 \
            and widths["l3_mla_kva"] == 512 + 64 \
            and widths["mtp_mla_kvb_k"] == 20 * 192 \
            and widths["mtp_mla_kvb_v"] == 20 * 256 \
            and widths["l0_ffn_gate"] == 10240 \
            and widths["mtp_eh"] == 2048 \
            and widths["lm_head"] == widths["mtp_head"] == 19360
        loss = next(l for l in net.layers if l.name == "mtp_loss")
        assert loss.loss_weight == [0.3]
    else:
        assert "--remat '/l\\d+_/,/mtp_/,/lm_/'" in text
