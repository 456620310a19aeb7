"""Xing4.0-29B-A4B's block on its residual stream (manifold-constrained
hyper-connections) as layers of the Net against their plain reference
(benchmark/reference/xing4.py, loaded from there: one file, no second copy),
at a small size on the CPU with EVERY leaf drawn at random (the mappings'
scales of order 1: at their initial values the mix is doubly stochastic
after one iteration and the dynamic part 1% of the logits): logits, loss and
every gradient, with and without the prediction module; one whole train
step; the expert shares summing to the whole layer THROUGH the stream's
write with the shared expert counted once; what the Sinkhorn iterations
leave; one stream with p = q = M = 1 against the plain-residual block;
YaRN's frequencies against the closed form; the planted faults; what the
whole published model counts; the example prototxts."""

import importlib.util
import math
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
    "xing_reference", os.path.join(ROOT, "benchmark", "reference", "xing4.py"))
ref = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(ref)

# the dense layer, two sparse layers (and, with it, the module's block)
L, DENSE, E, K, HELD, STREAMS = 3, 1, 16, 4, 8, 4
SCALING = {"factor": 64.0, "original_max_position_embeddings": 4096,
           "beta_fast": 32.0, "beta_slow": 1.0, "mscale": 1.0,
           "mscale_all_dim": 1.0, "type": "yarn"}
SIZES = dict(n_layers=L, dense_layers=DENSE, hidden=64, heads=4, q_rank=24,
             kv_rank=32, nope_dim=8, rope_dim=8, v_dim=16, dense_width=96,
             experts=E, top_k=K, expert_width=32, shared_width=32, vocab=128)
CFG = {"num_hidden_layers": L, "num_dense_layers": DENSE, "num_heads": 4,
       "q_lora_rank": 24, "kv_lora_rank": 32, "qk_nope_head_dim": 8,
       "qk_rope_head_dim": 8, "v_head_dim": 16, "num_experts": E,
       "num_experts_per_tok": K, "route_scale": 2.0, "rope_theta": 1e4,
       "rope_scaling": SCALING, "rms_norm_eps": 1e-6, "hc_mult": STREAMS,
       "hc_sinkhorn_iters": 20, "hc_eps": 1e-6, "hc_clamp": 30.0,
       "mtp_layers": 1, "mtp_weight": 0.3}
N, S = 2, 48
RATE = 0.001
MAPS = ("phi_pre", "phi_post", "phi_res", "b_pre", "b_post", "b_res",
        "a_pre", "a_post", "a_res")


def build(held=HELD, held_first=0, n=N, s=S, generator=zoo.xing4, **kw):
    # through the text form: what a user's prototxt goes through
    text = zoo.to_prototxt(generator(
        batch=n, held=held, held_first=held_first, **{**SIZES, **kw}))
    return Net(load_net_from_string(text), "TRAIN",
               source_shapes={"tokens": (n, s), "targets": (n, s)})


def batch_of(n=N, s=S, seed=5):
    stream = jax.random.randint(jax.random.PRNGKey(seed), (n, s + 1), 0,
                                SIZES["vocab"])
    return {"tokens": stream[:, :-1], "targets": stream[:, 1:]}


def seeded(net, seed=3):
    """Fresh weights, then EVERY leaf moved to a random value: gains around
    1, the routers' matrices large enough that their choices are no
    near-ties, the mappings' scales of order 1 and their biases spread, so
    that the mix a token is far from the identity and depends on the
    token."""
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
            elif pname.startswith("a_"):
                lp[pname] = 1.0 + 0.3 * noise
            elif pname.startswith("b_"):
                lp[pname] = w + 0.5 * noise
            else:
                lp[pname] = noise / np.sqrt(w.shape[-1])
    return params


def owned(net, params):
    """{layer: [blobs]} of the OWNERS, as the reference takes them."""
    return {l.name: [params[l.name][p.name] for p in l.params]
            for l in net.layers if l.name in params}


def cfg_of(mtp):
    return dict(CFG, mtp_layers=mtp)


def dead(lname, pname):
    """The mapping leaves whose gradient is zero BY CONSTRUCTION (rounding
    noise and eps apart): a stream's first read (its n states are copies, so
    h is a multiple of one of them and the norm after it takes the multiple
    out) and a stream's last mix (only the streams' SUM is read after it,
    and the mix's columns sum to 1)."""
    first, last = ("l0_hc_a_map", "mtp_hc_a_map"), \
        (f"l{L - 1}_hc_f_map", "mtp_hc_f_map")
    return (lname in first and pname.endswith("_pre")) \
        or (lname in last and pname.endswith("_res"))


@pytest.fixture(scope="module", params=[0, 1], ids=["no_module", "module"])
def model(request):
    net = build(mtp=request.param)
    return net, seeded(net), batch_of(), request.param


def rel(a, b):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.linalg.norm(a - b) / max(np.linalg.norm(b), 1e-30))


def test_leaves_scopes_and_units(model):
    net, params, _, mtp = model
    blocks = L + mtp
    # a mapping is nine leaves of n (n + 2) (n C + 1) + 3 numbers
    assert all([p.name for p in net._layer_by_name[f"l0_hc_{s}_map"].params]
               == list(MAPS) for s in "af")
    per_map = sum(int(np.prod(v.shape))
                  for v in params["l1_hc_f_map"].values())
    assert per_map == STREAMS * (STREAMS + 2) * (STREAMS * 64 + 1) + 3
    types = {l.name: l.TYPE for l in net.layers}
    stream_layers = [n for n, t in types.items() if t.startswith("HC_")]
    assert len(stream_layers) == blocks * 6 + 2 * (1 + mtp)
    assert types["hc_start"] == "HC_START" and types["hc_end"] == "HC_END"
    assert "l0_res1" not in types and "l2_res2" not in types
    # a mapping takes no weight decay
    assert {p.decay_mult for p in net._layer_by_name["l2_hc_a_map"].params} \
        == {0.0}
    # what a display shows of every sub-layer
    assert {f"l{i}_hc_{s}_{what}" for i in range(L) for s in "af"
            for what in ("res_err", "pre_mean", "post_mean")} \
        <= set(net.output_names)
    assert net.kernel_routes["l0_mla_attn"] == (
        "attention=dense; d 16/16; k_pe rotated once, joined x4; yarn x64")
    # one checkpoint a layer: the stream is what crosses
    from poseidon_tpu.core.remat import resolve_entries
    _, segments = resolve_entries([l.name for l in net.layers],
                                  [r"/l\d+_/", "/lm_/"])
    layer_units = [seg for seg in segments if re.match(r"l\d+_", seg[0])]
    assert [seg[0] for seg in layer_units] \
        == [f"l{i}_hc_a_map" for i in range(L)] \
        and all(seg[-1].endswith("_hc_f_write") for seg in layer_units)


def test_net_matches_reference_forward(model):
    """f32 against f32: the streams side by side along the lanes and the
    Sinkhorn loop with the tokens along them against an (n, C) array and an
    (n, n) matrix a token; the same products in another order."""
    net, params, batch, mtp = model
    out = jax.jit(lambda p, b: net.apply(p, b, train=True,
                                         keep_blobs=True))(params, batch)
    weights = owned(net, params)
    want_loss, want = ref.loss(cfg_of(mtp), weights, batch["tokens"],
                               batch["targets"], held=range(HELD))
    tol = ref.TOLERANCE["f32"]
    assert rel(out.blobs["logits"], want["logits"]) < tol["logits_rel_l2"]
    assert abs(float(out.loss) - float(want_loss)) \
        < tol["loss_rel"] * float(want_loss)
    if mtp:
        assert rel(out.blobs["mtp_logits"], want["mtp_logits"]) \
            < tol["logits_rel_l2"]
        assert float(out.loss) == pytest.approx(
            float(out.outputs["lm_loss"])
            + 0.3 * float(out.outputs["mtp_loss"]), rel=1e-6)
    sparse = [f"l{i}_" for i in range(DENSE, L)] + ["mtp_"] * mtp
    for at, p in enumerate(sparse):
        g = np.asarray(out.blobs[p + "gates"])
        np.testing.assert_array_equal(
            np.sort(np.argsort(-g, -1, kind="stable")[..., :K], -1),
            np.sort(np.asarray(want["choice"][at]), -1))
        np.testing.assert_allclose(g.sum(-1), 2.0, rtol=1e-5)
        assert rel(out.blobs[p + "m"], want["routed"][at]) < 3e-4
        assert rel(out.blobs[p + "y"].reshape(N, S, STREAMS, -1),
                   want["stream"][at]) < 3e-4
    # what every display shows of a sub-layer, in the order they run
    subs = [f"l{i}_hc_{s}_" for i in range(L) for s in "af"] \
        + [f"mtp_hc_{s}_" for s in "af"] * mtp
    for what in ("res_err", "pre_mean", "post_mean"):
        got = np.array([float(out.outputs[p + what]) for p in subs])
        np.testing.assert_allclose(got, np.asarray(want[what]), rtol=2e-3,
                                   atol=1e-6)
    # the mix is far from the identity on these weights (scales of order 1
    # on a diagonal of e^4: 20 iterations leave a few percent on the rows,
    # the columns being divided last; a fresh model's reads under 1e-6)
    assert max(float(out.outputs[p + "res_err"]) for p in subs) < 0.1
    mix = np.asarray(out.blobs["l1_f_coef"])[..., 2 * STREAMS:]
    assert np.abs(mix.reshape(N, S, STREAMS, STREAMS)
                  - np.eye(STREAMS)).max() > 0.3


def test_net_matches_reference_gradients(model):
    """Every leaf's gradient, the mappings' nine a sub-layer among them:
    relative L2 under 2e-4 (f32 summation order through the blocks' backward
    and 20 Sinkhorn iterations' twice a layer)."""
    net, params, batch, mtp = model
    got = jax.jit(jax.grad(
        lambda p: net.apply(p, batch, train=True).loss))(params)
    want = jax.jit(jax.grad(lambda w: ref.loss(
        cfg_of(mtp), w, batch["tokens"], batch["targets"],
        held=range(HELD))[0]))(owned(net, params))
    n = 0
    for lname, leaves in want.items():
        names = [p.name for p in net._layer_by_name[lname].params]
        for pname, g in zip(names, leaves):
            if pname == "bias":
                assert not np.any(np.asarray(g)) \
                    and not np.any(np.asarray(got[lname][pname]))
                continue
            n += 1
            if dead(lname, pname):
                # both sides read noise, a thousandth of a live leaf's
                assert max(np.linalg.norm(np.asarray(g)), np.linalg.norm(
                    np.asarray(got[lname][pname]))) < 1e-3, (lname, pname)
                continue
            # (a stream's first MIX is all but dead as well: on n copies
            # of one state it is the row sums, 1 to what the loop leaves)
            assert np.linalg.norm(np.asarray(g)) > 1e-5, (lname, pname)
            assert rel(got[lname][pname], g) < 2e-4, (lname, pname)
    assert n == sum(len(v) for v in params.values()) - (L - DENSE + mtp)


def test_one_train_step_matches_the_reference_s():
    """One whole step as the runner's ``step_check`` compares it: the
    program's gradient through the solver's own update (ADAM + decay + the
    clip, the biases outside all three, the mappings without decay) against
    ``train_step``."""
    from poseidon_tpu.proto.messages import SolverParameter
    from poseidon_tpu.solvers.updates import init_state, make_update_fn
    net = build(mtp=0)
    params, batch = seeded(net), batch_of()
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
        cfg_of(0), w, batch["tokens"], batch["targets"], opt,
        held=range(HELD), remat=True, q_block=16))(owned(net, params))
    assert float(want["grad_norm"]) > sp.clip_gradients      # the clip is on
    assert abs(float(loss) - float(want["loss"])) < 1e-5 * float(loss)
    for lname, blobs in want["change"].items():
        for pdef, change in zip(leaves[lname], blobs):
            moved = np.asarray(new[lname][pdef.name]) \
                - np.asarray(params[lname][pdef.name])
            if pdef.name == "bias":
                np.testing.assert_allclose(moved, change, rtol=0, atol=1e-7)
            elif not dead(lname, pdef.name):
                # Adam's first step is the gradient's sign: in a leaf of
                # a few numbers one near-zero entry is seen
                assert rel(moved, change) < (
                    5e-3 if change.size >= 2 ** 12 else 0.1), \
                    (lname, pdef.name)
    assert mults["l0_hc_a_map"]["phi_res"] == (1.0, 0.0)


def test_the_shares_add_up_through_the_write():
    """Two shares of the first sparse layer (experts 0..7 and 8..15 of 16):
    their routed parts plus the shared expert ONCE, through the stream's
    write with the layer's own coefficients, are the uncut reference layer's
    stream; each share alone is not."""
    from poseidon_tpu.ops.hyper import hc_write
    whole = build(held=0, mtp=0)
    params, batch = seeded(whole), batch_of()
    p = f"l{DENSE}_"
    _, want = ref.loss(cfg_of(0), owned(whole, params), batch["tokens"],
                       batch["targets"])
    parts = []
    for first in (0, HELD):
        net = build(held=HELD, held_first=first, mtp=0)
        mine = {name: dict(lp) for name, lp in params.items()}
        for name in mine:
            if name.endswith("_moe"):
                mine[name] = {k: v[first:first + HELD]
                              for k, v in mine[name].items()}
        out = net.apply(mine, batch, train=True, keep_blobs=True)
        parts.append(out.blobs)
    a, b = parts
    np.testing.assert_array_equal(a[p + "h"], b[p + "h"])
    assert rel(a[p + "m"] + b[p + "m"], want["routed"][0]) < 3e-4
    assert rel(a[p + "s"], want["shared"][0]) < 3e-4
    summed = hc_write(a[p + "h"], a[p + "m"] + b[p + "m"] + a[p + "s"],
                      a[p + "f_coef"], STREAMS)
    uncut = np.asarray(want["stream"][0]).reshape(N, S, -1)
    assert rel(summed, uncut) < 3e-4
    assert rel(a[p + "y"], uncut) > 1e-2
    # counted twice, the shared expert shows
    twice = hc_write(a[p + "h"], a[p + "f"] + b[p + "f"], a[p + "f_coef"],
                     STREAMS)
    assert rel(twice, uncut) > 1e-2


def test_the_mix_is_doubly_stochastic_to_what_the_counter_reads():
    """R's rows and columns sum to 1 within ``res_err``'s reading, p lies
    in (0, 1) and q in (0, 2); fewer iterations leave more."""
    from poseidon_tpu.ops.hyper import hc_map
    net = build(mtp=0)
    params = seeded(net)["l1_hc_a_map"]
    x = jax.random.normal(jax.random.PRNGKey(9), (N, S, STREAMS * 64))
    coef, err, pre_mean, post_mean = hc_map(x, params, STREAMS, 20, 1e-6,
                                            30.0)
    n = STREAMS
    mix = np.asarray(coef[..., 2 * n:]).reshape(N, S, n, n)
    worst = max(np.abs(mix.sum(-1) - 1).max(), np.abs(mix.sum(-2) - 1).max())
    assert worst == pytest.approx(float(err), rel=1e-5) and worst < 0.1
    np.testing.assert_allclose(mix.sum(-2), 1.0, atol=1e-5)   # columns: last
    assert (mix > 0).all()
    p, q = np.asarray(coef[..., :n]), np.asarray(coef[..., n:2 * n])
    assert 0 < p.min() and p.max() < 1 and 0 < q.min() and q.max() < 2
    assert float(pre_mean) == pytest.approx(p.mean(), rel=1e-5) \
        and float(post_mean) == pytest.approx(q.mean(), rel=1e-5)
    _, one, _, _ = hc_map(x, params, n, 1, 1e-6, 30.0)
    assert float(one) > 10 * float(err)
    # at its initial values a mapping reads p = 1 / n, q = 1 and a mix
    # within 4% of the identity
    fresh = net.init(jax.random.PRNGKey(0))["l1_hc_a_map"]
    coef, _, pre_mean, post_mean = hc_map(x, fresh, n, 20, 1e-6, 30.0)
    assert float(pre_mean) == pytest.approx(1 / n, abs=1e-3) \
        and float(post_mean) == pytest.approx(1.0, abs=1e-3)
    mix = np.asarray(coef[..., 2 * n:]).reshape(N, S, n, n)
    assert np.abs(mix - np.eye(n)).max() < 0.06 and float(_) < 1e-3


def test_one_stream_forced_to_one_is_the_plain_residual_block():
    """n = 1 with p = q = M = 1 (the scales 0, b_pre large, b_post and
    B_res 0, no iteration): the net is ``zoo.glm_flash``'s block at the same
    widths, scale and frequencies, to rounding."""
    import inspect
    attn = dict(rope_factor=64.0,
                attn_scale=(0.1 * math.log(64) + 1) ** 2 / math.sqrt(16))
    plain = build(generator=zoo.glm_flash, mtp=0, rope_theta=1e4, eps=1e-6,
                  route_scale=2.0, **attn)
    one = build(mtp=0, streams=1, sinkhorn_iters=0)
    assert inspect.signature(zoo.glm_flash).parameters["streams"].default == 0
    params = seeded(plain)
    forced = {name: dict(lp) for name, lp in params.items()}
    for name, lp in one.init(jax.random.PRNGKey(0)).items():
        if name.endswith("_map"):
            forced[name] = {k: jnp.zeros_like(v) for k, v in lp.items()}
            forced[name]["b_pre"] = jnp.full((1,), 30.0)
    assert set(forced) == set(one.init(jax.random.PRNGKey(0)))
    batch = batch_of()
    a = plain.apply(params, batch, train=True, keep_blobs=True)
    b = one.apply(forced, batch, train=True, keep_blobs=True)
    assert float(b.outputs["l1_hc_f_pre_mean"]) == 1.0 \
        and float(b.outputs["l1_hc_f_post_mean"]) == 1.0 \
        and float(b.outputs["l1_hc_f_res_err"]) == 0.0
    assert rel(b.blobs["logits"], a.blobs["logits"]) < 1e-5
    assert float(b.loss) == pytest.approx(float(a.loss), rel=1e-6)


def test_yarn_frequencies_against_the_closed_form():
    from poseidon_tpu.models.transformer import (Yarn, rope_frequencies,
                                                 rope_tables)
    theta, rot, factor, positions = 10000.0, 64, 64.0, 4096
    got = rope_frequencies(rot, Yarn(theta, factor, positions, 32.0, 1.0))
    pair = lambda b: rot * math.log(positions / (2 * math.pi * b)) \
        / (2 * math.log(theta))                               # noqa: E731
    low, high = math.floor(pair(32)), math.ceil(pair(1))
    assert (low, high) == (10, 23) and got.shape == (32,)
    for i in range(32):
        f = theta ** (-2 * i / rot)
        m = 1 - min(1.0, max(0.0, (i - low) / (high - low)))
        assert got[i] == pytest.approx(f / factor * (1 - m) + f * m,
                                       rel=1e-12)
    plain = rope_frequencies(rot, theta)
    np.testing.assert_array_equal(got[:11], plain[:11])       # fast: kept
    np.testing.assert_allclose(got[23:], plain[23:] / 64, rtol=1e-12)
    assert (np.diff(got) < 0).all()
    # the reference's own
    np.testing.assert_allclose(
        ref.yarn_frequencies(rot, theta, SCALING), got, rtol=1e-12)
    # factor 1 is plain theta, to the bit, and so are the tables
    np.testing.assert_array_equal(
        rope_frequencies(rot, Yarn(theta, 1.0, positions, 32.0, 1.0)), plain)
    for a, b in zip(rope_tables(16, rot, theta),
                    rope_tables(16, rot, Yarn(theta, 1.0))):
        np.testing.assert_array_equal(a, b)
    assert ref.softmax_scale(192, SCALING) == pytest.approx(0.14468, rel=1e-4)


@pytest.fixture(scope="module")
def faultless():
    """The reference on the randomly drawn model with the module, once:
    -> (run(**how), its loss and outputs with no fault)."""
    net = build(mtp=1)
    params, batch = seeded(net), batch_of()
    weights = owned(net, params)
    run = jax.jit(lambda fault=None: ref.loss(
        cfg_of(1), weights, batch["tokens"], batch["targets"],
        held=range(HELD), fault=fault), static_argnames="fault")
    return run, run()


@pytest.mark.parametrize("fault", ref.FAULTS)
def test_each_planted_fault_is_another_function(faultless, fault):
    """Each wrong program the reference can write down moves the logits of
    the randomly drawn model by far more than the f32 limit."""
    run, (_, right) = faultless
    _, wrong = run(fault=fault)
    assert rel(wrong["logits"], right["logits"]) \
        > 20 * ref.TOLERANCE["f32"]["logits_rel_l2"]


def test_averaging_the_streams_at_the_end_is_the_same_function(faultless):
    """The one planted fault no output can show: x_L meets only RMSNorms,
    which take the factor 1 / n out again (eps apart)."""
    run, (a, right) = faultless
    b, wrong = run(fault=ref.SAME_FUNCTION[0])
    assert rel(wrong["logits"], right["logits"]) < 1e-5 \
        and rel(wrong["mtp_logits"], right["mtp_logits"]) < 1e-5 \
        and float(b) == pytest.approx(float(a), rel=1e-6)


def test_layers_refuse_what_they_cannot_mean():
    shapes = {"tokens": (1, 8), "targets": (1, 8)}
    three = zoo.to_prototxt(zoo.xing4(**{**SIZES, "n_layers": 1, "mtp": 0,
                                         "streams": 3}))
    with pytest.raises(ValueError, match="a stream is"):
        # the end of a stream of three, handed one hidden state of 64
        Net(load_net_from_string(three.replace(
            'bottom: "l0_y"\n  top: "xe"', 'bottom: "x0"\n  top: "xe"')),
            "TRAIN", source_shapes=shapes)
    text = zoo.to_prototxt(zoo.xing4(**{**SIZES, "n_layers": 1, "mtp": 0}))
    with pytest.raises(ValueError, match="rope_factor"):
        Net(load_net_from_string(text.replace("rope_factor: 64.0",
                                              "rope_factor: 0.5")), "TRAIN",
            source_shapes=shapes)
    with pytest.raises(ValueError, match="the coefficients"):
        Net(load_net_from_string(text.replace(
            'bottom: "l0_a_coef"\n  top: "l0_ah"',
            'bottom: "x0"\n  top: "l0_ah"')), "TRAIN", source_shapes=shapes)
    with pytest.raises(ValueError, match="HC_MAP has 4 tops"):
        # the mapping without its display scalars
        Net(load_net_from_string(text.replace(
            '  top: "l0_hc_a_res_err"\n  top: "l0_hc_a_pre_mean"\n'
            '  top: "l0_hc_a_post_mean"\n', "")), "TRAIN",
            source_shapes=shapes)


def test_a_fresh_mapping_is_the_configuration_s_assumed_init():
    # ``assumed.e``: p = 1 / n, q = 1, the mix near the identity, the
    # dynamic part 1% of the logits; the layer's constants, no option
    net = build(n_layers=1, dense_layers=1, mtp=0)
    fresh = net.init(jax.random.PRNGKey(0))["l0_hc_f_map"]
    for kind in ("pre", "post", "res"):
        assert float(fresh["a_" + kind][0]) == pytest.approx(0.01)
    np.testing.assert_array_equal(fresh["b_res"], 4.0 * np.eye(STREAMS))
    np.testing.assert_allclose(jax.nn.sigmoid(fresh["b_pre"]), 1 / STREAMS,
                               rtol=1e-6)
    np.testing.assert_array_equal(fresh["b_post"], 0.0)
    assert 0.015 < float(jnp.std(fresh["phi_res"])) < 0.025


def test_the_whole_published_model_counts():
    """`zoo.xing4()` with no arguments is the published model: 40 layers,
    2 of them dense, 64 experts, 131,072 rows, the module; the cut the
    example's header states is the benchmark's."""
    text = zoo.to_prototxt(zoo.xing4())
    net = load_net_from_string(text)
    names = [l.name for l in net.layers]
    assert sum(1 for n in names if re.fullmatch(r"l\d+_mla_attn", n)) == 40
    assert sum(1 for n in names if re.fullmatch(r"l\d+_router", n)) == 38 \
        and "l1_ffn_gate" in names and "l2_router" in names
    moe = next(l for l in net.layers if l.name == "l2_moe").moe_param
    assert (moe.num_experts, moe.num_held, moe.top_k, moe.expert_width) \
        == (64, 0, 4, 1024)
    attn = next(l for l in net.layers if l.name == "l0_mla_attn") \
        .attention_param
    assert attn.scale == pytest.approx(0.14468, rel=1e-4) \
        and attn.rope_factor == 64 and attn.rotary_shared \
        and attn.num_heads == 32 and attn.value_head_dim == 128

    def count(**cut):
        built = Net(zoo.xing4(**cut), "TRAIN",
                    source_shapes={"tokens": (1, 64), "targets": (1, 64)})
        return built.param_count()

    attention = 2_752_512 + 4_718_592 + 2_064_384 + 4_194_304 + 14_680_064 \
        + 768 + 512
    mapping = 344_091
    assert attention == 28_411_136
    dense = attention + 99_090_432 + 2 * 3584 + 2 * mapping
    sparse = attention + 229_440 + 64 * 11_010_048 + 11_010_048 + 2 * 3584 \
        + 2 * mapping
    assert dense == 128_196_918
    module = 2 * 3584 + 2 * 3584 * 3584 + sparse + 3584
    table = 2 * 131_072 * 3584
    assert 2 * dense + 38 * sparse + table + 3584 == 29_505_505_264
    assert count() == 29_505_505_264 + module == 30_276_195_174
    cut = dict(n_layers=5, dense_layers=1, held=8, vocab=16384)
    assert count(mtp=0, **cut) == 759_346_446
    assert count(mtp=1, **cut) == 913_473_668


@pytest.mark.parametrize("name", ["train", "solver"])
def test_example_prototxts_are_the_zoo_s_and_the_benchmark_s(name):
    """examples/lm/xing4_0_29b_a4b_*.prototxt: the net is what `zoo.xing4`
    writes at the cut its header states, and the benchmark's copies (what
    the cell runs) are the same bytes."""
    example = os.path.join(ROOT, "examples", "lm",
                           f"xing4_0_29b_a4b_{name}.prototxt")
    copy = os.path.join(ROOT, "benchmark", "configs", "xing4_0_29b_a4b",
                        f"{name}.prototxt")
    with open(example) as a, open(copy) as b:
        text = a.read()
        assert text == b.read()
    if name == "train":
        m = re.search(r"zoo\.xing4\(batch=1, n_layers=(\d+), "
                      r"dense_layers=(\d+), held=(\d+), vocab=(\d+), "
                      r"mtp=(\d+)\)", text)
        depth, dense, held, vocab, mtp = (int(x) for x in m.groups())
        body = "".join(l for l in text.splitlines(True)
                       if not l.startswith("#"))
        assert body == zoo.to_prototxt(zoo.xing4(
            batch=1, n_layers=depth, dense_layers=dense, held=held,
            vocab=vocab, mtp=mtp))
        assert (depth, dense, held, vocab, mtp) == (5, 1, 8, 131072 // 8, 0)
        net = load_net_from_string(body)
        widths = {l.name: l.inner_product_param.num_output
                  for l in net.layers if l.type == "INNER_PRODUCT"}
        assert widths["l0_mla_qa"] == 768 \
            and widths["l0_mla_qb"] == 32 * 192 \
            and widths["l3_mla_kva"] == 512 + 64 \
            and widths["l4_mla_kvb_k"] == 32 * 128 \
            and widths["l4_mla_kvb_v"] == 32 * 128 \
            and widths["l0_ffn_gate"] == 9216 \
            and widths["l2_shared_up"] == 1024 \
            and widths["lm_head"] == 16384
        maps = [l for l in net.layers if l.type == "HC_MAP"]
        assert len(maps) == 10 and all(
            (l.hyper_param.streams, l.hyper_param.sinkhorn_iters,
             l.hyper_param.eps, l.hyper_param.clamp) == (4, 20, 1e-6, 30.0)
            for l in maps)
    else:
        assert "--remat '/l\\d+_/,/lm_/'" in text
