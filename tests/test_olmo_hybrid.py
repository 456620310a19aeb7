"""Olmo-Hybrid's block as layers of the Net against its plain reference
(benchmark/reference/olmo_hybrid.py, loaded from there: one file, no second
copy), at a small size on the CPU with seeded weights: the per-head scan
against the token-by-token recurrence AND against the per-channel arm with g
broadcast (values, five gradients, widths that are no multiple of 8, beta
1.9, g from -1e-4 to -20 a token); the Pallas per-head arm, interpreted, at
96 / 192 in padded lanes; logits, loss, every gradient and one
whole train step; the head shares summing to the whole mixer; what the run
says it ran; the parameter count of the published-width cut; the example
prototxts."""

import importlib.util
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from poseidon_tpu.core.net import Net
from poseidon_tpu.models import zoo
from poseidon_tpu.ops import kda
from poseidon_tpu.proto.messages import load_net_from_string

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_spec = importlib.util.spec_from_file_location(
    "olmo_hybrid_reference",
    os.path.join(ROOT, "benchmark", "reference", "olmo_hybrid.py"))
ref = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(ref)

# one whole period: linear, linear, linear, full; heads of 12 / 24 and 16
L, H = 4, 4
SIZES = dict(n_layers=L, hidden=64, heads=H, key_head_dim=12,
             value_head_dim=24, attn_head_dim=16, ffn_width=96, vocab=128)
TYPES = ["linear", "linear", "linear", "full"]
CFG = {"num_hidden_layers": L, "layer_types": TYPES, "num_heads": H,
       "rms_norm_eps": 1e-6}
N, S = 2, 128                    # two chunks of 64 a sequence
LINEAR = [0, 1, 2]


def build(n=N, s=S, **kw):
    # through the text form: what a user's prototxt goes through
    text = zoo.to_prototxt(zoo.olmo_hybrid(batch=n, **{**SIZES, **kw}))
    return Net(load_net_from_string(text), "TRAIN",
               source_shapes={"tokens": (n, s), "targets": (n, s)})


def batch_of(n=N, s=S, seed=5):
    key = jax.random.PRNGKey(seed)
    return {"tokens": jax.random.randint(key, (n, s), 0, SIZES["vocab"]),
            "targets": jax.random.randint(jax.random.fold_in(key, 1),
                                          (n, s), 0, SIZES["vocab"])}


def seeded(net, seed=3):
    """Fresh weights, then everything a fresh model has at a trivial value
    moved off it: gains off 1; the decay's and the write strength's
    projections larger, so that both depend on the token and beta passes 1
    for about half the writes."""
    params = net.init(jax.random.PRNGKey(seed))
    for i, (lname, lp) in enumerate(sorted(params.items())):
        for j, (pname, w) in enumerate(sorted(lp.items())):
            noise = jax.random.normal(jax.random.PRNGKey(100 + 31 * i + j),
                                      w.shape)
            if pname == "g":
                lp[pname] = 1.0 + 0.2 * noise
            elif lname.endswith(("_gdn_a", "_gdn_b", "_gdn_z")):
                lp[pname] = 0.5 * noise
            elif lname == "embed":      # a unit-RMS state into layer 0:
                lp[pname] = noise       # its L2 norms divide by no 0.1
            elif pname == "w" and "_conv_" not in lname:
                lp[pname] = 0.1 * noise
    return params


@pytest.fixture(scope="module")
def model():
    net = build()
    return net, seeded(net), batch_of()


def rel(a, b):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.linalg.norm(a - b) / max(np.linalg.norm(b), 1e-30))


# --------------------------------------------------------------------------- #
# the per-head scan against the recurrence and the per-channel arm
# --------------------------------------------------------------------------- #

DECAYS = {"weak": (-1e-4, -1e-3), "mild": (-0.05, -1.0),
          "strong": (-3.0, -20.0)}
GRADS = ("dq", "dk", "dv", "dg", "dbeta")


def operands(seed, decay, b=2, s=128, h=3, d_k=12, d_v=20):
    """Heads of 12 / 20 (no multiple of 8, d_k != d_v), beta 1.9 on every
    write, g drawn between the regime's two ends, one a head."""
    r = np.random.RandomState(seed)
    q, k = r.randn(2, b, s, h, d_k)
    q /= np.linalg.norm(q, axis=-1, keepdims=True)
    k /= np.linalg.norm(k, axis=-1, keepdims=True)
    lo, hi = DECAYS[decay]
    g = -np.exp(r.uniform(np.log(-lo), np.log(-hi), size=(b, s, h)))
    beta = np.full((b, s, h), 1.9)
    return [jnp.asarray(x, jnp.float32)
            for x in (q, k, r.randn(b, s, h, d_v), g, beta)]


def _channel(q, k, v, g, beta):
    """The per-channel arm on the same numbers: g broadcast to d_k."""
    return kda.kda_scan(q, k, v, jnp.broadcast_to(g[..., None], q.shape),
                        beta)


@pytest.mark.parametrize("decay", sorted(DECAYS))
@pytest.mark.parametrize("what", ("o",) + GRADS)
def test_per_head_scan_equals_recurrence_and_per_channel_arm(what, decay):
    """Two chunks of 64, values and each of the five gradients, f32 to
    rounding, at beta 1.9 (the eigenvalue along k is -0.9) under weak, mild
    and strong decay. ``strong``: a head's log-decay sums below -150 inside
    a chunk, where exp(-G) overflows f32: every value stays finite and
    equal (d g, of the order of exp(g) there, to 1e-4 of its norm)."""
    args = operands(0, decay)
    if decay == "strong":
        assert float(jnp.min(jnp.sum(
            args[3].reshape(2, 2, 64, 3), 2))) < -150
    co = jnp.asarray(np.random.RandomState(1).randn(2, 128, 3, 20),
                     jnp.float32)
    fns = (kda.kda_scan, kda.kda_recurrence, _channel)
    if what == "o":
        got, want, chan = (jax.jit(f)(*args) for f in fns)
    else:
        i = GRADS.index(what)
        got, want, chan = (jax.jit(jax.grad(
            lambda *a, f=f: jnp.sum(f(*a) * co), argnums=i))(*args)
            for f in fns)
    for x in (got, chan):
        assert np.all(np.isfinite(np.asarray(x))) and x.shape == want.shape
        assert rel(x, want) < (1e-4 if what == "dg" else 3e-5)


def test_per_head_scan_takes_the_chunk_rule_and_two_sequences():
    """S 96 runs chunks of 48; a sequence's state does not reach the next
    sequence of the batch."""
    q, k, v, g, beta = operands(2, "mild", b=2, s=96)
    assert kda.kda_chunk(96) == 48
    both = kda.kda_scan(q, k, v, g, beta)
    alone = kda.kda_scan(q[1:], k[1:], v[1:], g[1:], beta[1:])
    np.testing.assert_allclose(both[1:], alone, rtol=1e-6, atol=1e-6)
    assert rel(both, kda.kda_recurrence(q, k, v, g, beta)) < 3e-5


@pytest.fixture(scope="module")
def pallas_against_recurrence():
    """{decay: (the Pallas arm's, the recurrence's)} at the PUBLISHED head
    widths, 96 / 192, through ``_pallas_scan`` (lanes padded to 128 / 256,
    the kernels interpreted): B = 1, S = 256 = two programs of two chunks,
    H = 2, beta 1.9."""
    out = {}
    for decay in ("weak", "strong"):
        args = operands(0, decay, b=1, s=256, h=2, d_k=96, d_v=192)
        co = jnp.asarray(np.random.RandomState(1).randn(1, 256, 2, 192),
                         jnp.float32)

        def both(f):
            o, pull = jax.vjp(f, *args)
            return dict(zip(("o",) + GRADS, (o,) + pull(co)))

        out[decay] = tuple(jax.jit(lambda f=f: both(f))() for f in (
            lambda *a: kda._pallas_scan(*a, 96 ** -0.5, 2, True),
            kda.kda_recurrence))
    return out


@pytest.mark.parametrize("decay", ["weak", "strong"])
@pytest.mark.parametrize("what", ("o",) + GRADS)
def test_pallas_per_head_scan_equals_the_recurrence(
        what, decay, pallas_against_recurrence):
    """The two kernels' per-head arm (``_local_head`` and its pullback by
    hand) against the token-by-token recurrence and autodiff through it, f32
    to rounding, at heads of 96 / 192 in padded lanes: the zero lanes add
    nothing, and o and every gradient come back at the published widths."""
    got, want = pallas_against_recurrence[decay]
    assert got[what].shape == want[what].shape
    assert np.all(np.isfinite(np.asarray(got[what])))
    assert rel(got[what], want[what]) < (1e-4 if what == "dg" else 3e-5)


def test_pallas_per_head_scan_one_chunk_a_program_and_bf16_operands():
    """m = 1 (the doubling from the 2 x 2 blocks up) and bf16 q, k, v with
    an f32 g: o in v's type, equal to the chunked form's on the same
    operands."""
    q, k, v, g, beta = operands(3, "mild", b=2, s=192, h=2, d_k=96, d_v=192)
    q, k, v = (x.astype(jnp.bfloat16) for x in (q, k, v))
    got = jax.jit(lambda *a: kda._pallas_scan(*a, 96 ** -0.5, 1, True))(
        q, k, v, g, beta)
    want = kda.kda_scan(q, k, v, g, beta)
    assert got.dtype == jnp.bfloat16 and got.shape == want.shape
    assert rel(got.astype(jnp.float32), want.astype(jnp.float32)) < 1e-2


def test_route_names_the_arm_and_why(monkeypatch):
    """``kda_route`` decides from the decay's shape, the widths and the
    backend, and says why a shape runs ``chunked``."""
    arm, note = kda.kda_route(8192, 96, 192, 15, 2, per_head=True)
    assert (arm, note) == ("chunked", (
        "chunked C 64, 128 chunks, f32 state, one decay a head; not pallas: "
        "this backend would interpret the kernels"))
    arm, note = kda.kda_route(8192, 96, 192, 15, 2)
    assert arm == "chunked" and "heads of 96 / 192 are no lane blocks" in note
    arm, note = kda.kda_route(8160, 96, 192, 15, 2, per_head=True)
    assert arm == "chunked" and "64 does not divide S=8160" in note  # C 48
    monkeypatch.setenv("POSEIDON_FORCE_PALLAS", "1")
    assert kda.kda_route(8192, 96, 192, 15, 2, per_head=True) == (
        "pallas", "pallas (C 64 x 4, 128 chunks, f32 state in VMEM, one "
        "decay a head, lanes 96 / 192 padded to 128 / 256)")
    # a decay a channel is not padded: Kimi's widths are lane blocks
    assert kda.kda_route(8192, 96, 192, 15, 2)[0] == "chunked"
    assert kda.kda_route(8192, 128, 128, 32, 2)[0] == "pallas"
    assert kda.kda_route(8192, 128, 128, 32, 2, per_head=True) == (
        "pallas", "pallas (C 64 x 4, 128 chunks, f32 state in VMEM, one "
        "decay a head)")
    assert kda.kda_route(100, 96, 192)[0] == "recurrence"


# --------------------------------------------------------------------------- #
# the whole net against the reference
# --------------------------------------------------------------------------- #

def test_leaves_scopes_and_routes(model):
    net, params, _ = model
    # embed, head, final norm; per layer 2 norms + 3 FFN; linear: q k v z o
    # a b, 3 convs, (A_log, dt_bias), out-norm; full: q k v o + 2 gains
    assert sum(len(v) for v in params.values()) \
        == 3 + L * 5 + 3 * 13 + 6
    assert params["l0_gdn_q"]["w"].shape == (H * 12, 64)
    assert params["l0_gdn_v"]["w"].shape == (H * 24, 64)
    assert params["l0_gdn_decay"]["A_log"].shape == (H,)
    assert params["l0_gdn_decay"]["dt_bias"].shape == (H,)     # ONE a head
    assert params["l0_gdn_onorm"]["g"].shape == (24,)          # one d_v gain
    assert params["l3_attn_qnorm"]["g"].shape == (H * 16,)     # whole vector
    types = {l.name: l.TYPE for l in net.layers}
    assert [n for n, t in types.items() if t == "KDA_SCAN"] \
        == [f"l{i}_gdn_scan" for i in LINEAR]
    assert [n for n, t in types.items() if t == "ATTENTION"] \
        == ["l3_attn_sdpa"]
    assert types["l0_gdn_beta"] == "POWER" \
        and types["l0_gdn_gate"] == "SILU_GATE" \
        and types["l0_gdn_decay"] == "KDA_DECAY"
    for i in LINEAR:
        assert net.kernel_routes[f"l{i}_gdn_scan"] == (
            "kda=chunked C 64, 2 chunks, f32 state, one decay a head; not "
            "pallas: this backend would interpret the kernels")
    assert net.kernel_routes["l3_attn_sdpa"] == "attention=dense; no positions"
    assert net.layer_facts()["recurrent_state"] == {
        f"l{i}_gdn_scan": {"heads": H, "d_k": 12, "d_v": 24, "chunk": 64,
                           "chunks": 2, "decay": "head", "saved_state_bytes":
                           N * H * 2 * 12 * 24 * 4} for i in LINEAR}
    mults = {l.name: {p.name: (p.lr_mult, p.decay_mult) for p in l.params}
             for l in net.layers if l.name in params}
    assert mults["l0_gdn_decay"] == {"A_log": (1.0, 0.0),
                                     "dt_bias": (1.0, 0.0)}
    assert mults["l0_gdn_conv_q"] == {"w": (1.0, 1.0)}
    assert mults["l0_mix_norm"] == {"g": (1.0, 0.0)}


def test_net_matches_reference_forward(model):
    """f32 against f32: the chunked per-head scan against the
    token-by-token recurrence, dense attention against the masked softmax,
    the same products in another order; and the two counters a display
    carries."""
    net, params, batch = model
    out = jax.jit(lambda p, b: net.apply(p, b, train=True,
                                         keep_blobs=True))(params, batch)
    weights = net.export_weights(params)
    want_loss, want = ref.loss(CFG, weights, batch["tokens"],
                               batch["targets"])
    tol = ref.TOLERANCE["f32"]
    assert rel(out.blobs["logits"], want["logits"]) < tol["logits_rel_l2"]
    assert abs(float(out.loss) - float(want_loss)) \
        < tol["loss_rel"] * float(want_loss)
    for at, i in enumerate(LINEAR):
        np.testing.assert_allclose(out.outputs[f"l{i}_decay_mean"],
                                   want["decay_mean"][at], rtol=1e-5)
        over = float(out.outputs[f"l{i}_beta_over_one"])
        np.testing.assert_allclose(over, want["beta_over_one"][at],
                                   atol=2 / (N * S * H))
        assert 0.2 < over < 0.8                 # both sides of 1 are run
        assert float(jnp.max(out.blobs[f"l{i}_beta"])) > 1.5
    assert "l3_decay_mean" not in out.outputs           # the full layer
    for i in range(L):
        assert rel(out.blobs[f"l{i}_mo"], want["mixed"][i]) < 3e-4


def test_net_matches_reference_gradients(model):
    """Every leaf's gradient: relative L2 under 1e-3 (f32 summation order
    through four blocks of backward and the scan's own backward against
    autodiff of the recurrence; the worst leaves read 2e-4 to 4.4e-4, all
    behind a linear layer's q and k: their gradient passes the L2 norm's
    pullback, d - y (y . d), a difference of near-equal terms; v's path
    and the full layer's read under 3e-5)."""
    net, params, batch = model
    got = jax.jit(jax.grad(
        lambda p: net.apply(p, batch, train=True).loss))(params)
    weights = {k: [jnp.asarray(b) for b in v] for k, v in
               net.export_weights(params).items() if k in params}
    want = jax.jit(jax.grad(lambda w: ref.loss(
        CFG, w, batch["tokens"], batch["targets"])[0]))(weights)
    n = 0
    for lname, leaves in want.items():
        names = [p.name for p in net._layer_by_name[lname].params]
        for pname, g in zip(names, leaves):
            assert np.linalg.norm(np.asarray(g)) > 0, (lname, pname)
            assert rel(got[lname][pname], g) < 1e-3, (lname, pname)
            n += 1
    assert n == sum(len(v) for v in params.values())


def test_one_train_step_matches_the_reference_s(model):
    """One whole step as the runner's ``step_check`` compares it: the
    program's gradient through the solver's own update (ADAM + decay + the
    clip) against ``train_step`` (under its ``remat``: the recurrence in
    blocks of tokens)."""
    from poseidon_tpu.proto.messages import SolverParameter
    from poseidon_tpu.solvers.updates import init_state, make_update_fn
    net, params, batch = model
    sp = SolverParameter(solver_type="ADAM", base_lr=4e-3, lr_policy="fixed",
                         momentum=0.9, momentum2=0.95, delta=1e-8,
                         weight_decay=0.1, clip_gradients=0.05)
    mults = {l.name: {p.name: (p.lr_mult, p.decay_mult) for p in l.params}
             for l in net.layers if l.name in params}
    loss, grads = jax.value_and_grad(
        lambda p: net.apply(p, batch, train=True).loss)(params)
    new, _ = make_update_fn(sp, mults)(params, grads,
                                       init_state(params, "ADAM"), {})
    owned = {l.name: l.params for l in net.layers if l.name in params}
    opt = {"rate": {n: [sp.base_lr * p.lr_mult for p in ps]
                    for n, ps in owned.items()},
           "decay": {n: [sp.weight_decay * p.decay_mult for p in ps]
                     for n, ps in owned.items()},
           "clip": sp.clip_gradients, "b1": 0.9, "b2": 0.95, "eps": 1e-8}
    want = jax.jit(lambda w: ref.train_step(
        CFG, w, batch["tokens"], batch["targets"], opt, remat=True,
        t_block=16))(net.export_weights(params))
    assert float(want["grad_norm"]) > sp.clip_gradients      # the clip is on
    assert abs(float(loss) - float(want["loss"])) < 1e-5 * float(loss)
    for lname, blobs in want["change"].items():
        for pdef, change in zip(owned[lname], blobs):
            moved = np.asarray(new[lname][pdef.name]) \
                - np.asarray(params[lname][pdef.name])
            # Adam's first step is the gradient's sign: an entry whose
            # gradient is within the two gradients' 2e-4 of zero moves
            # between -rate and +rate (one such in 6,144 reads 0.012)
            assert rel(moved, change) < (
                3e-2 if change.size >= 2 ** 12 else 0.15), (lname, pdef.name)


# --------------------------------------------------------------------------- #
# a share of the heads
# --------------------------------------------------------------------------- #

@pytest.mark.parametrize("layer", [0, 3], ids=["gdn", "attention"])
def test_the_shares_add_up(layer, model):
    """The outputs Mix(x) (BEFORE N_a) of the shares (2, 0) and (2, 2) of
    the 4 heads sum to the 4-head layer's, for a Gated DeltaNet mixer and
    for ATTENTION + W_o on normed q and k, in program and reference alike:
    each share's net is ``zoo.olmo_hybrid(heads_held=2)`` (which heads
    decides no shape) and takes its heads' rows of the whole model's
    weights (``ref.head_share``).

    The ONE exception, stated here as in ISSUE 48: the whole-vector
    QK-norm's mean square runs over the HELD channels in a share (a
    deployment would exchange one scalar a token to complete it), so a
    share's normed q and k differ from the whole model's slices. The
    attention case therefore hands every share the whole layer's statistic:
    its q and k gains are scaled per TOKEN — not expressible as weights —
    so the test compares ATTENTION + W_o on the whole model's normed q, k
    cut by heads, which is what "on normed q and k" means."""
    whole_net, params, batch = model
    out = jax.jit(lambda p, b: whole_net.apply(
        p, b, train=True, keep_blobs=True))(params, batch)
    weights = whole_net.export_weights(params)
    p = f"l{layer}_"
    x = out.blobs["x0"] if layer == 0 else out.blobs[f"l{layer - 1}_y"]
    total_net = jnp.zeros_like(out.blobs[p + "mo"])
    total_ref = jnp.zeros_like(total_net)
    for first in (0, 2):
        share = build(heads_held=2)
        assert share.net_param.name == "Olmo-Hybrid-7B (2 of 4 heads)"
        w = ref.head_share(weights, H, 2, first)
        sp = {l.name: {pd.name: jnp.asarray(b) for pd, b in
                       zip(l.params, w[l.name])}
              for l in share.layers if l.name in w}
        assert {k: {n: v.shape for n, v in lp.items()}
                for k, lp in sp.items()} == {
            k: {n: v.shape for n, v in lp.items()}
            for k, lp in jax.eval_shape(
                share.init, jax.random.PRNGKey(0)).items()}
        if layer == 0:
            got = jax.jit(lambda p_, b: share.apply(
                p_, b, train=True, keep_blobs=True))(sp, batch)
            total_net += got.blobs[p + "mo"]
            total_ref += ref.forward(
                dict(CFG, num_heads=2), w, batch["tokens"],
                upto=1)["mixed"][0]
        else:
            # the whole model's normed q, k and its v, cut to the share's
            # heads; then the share's own ATTENTION layer and W_o
            cut = lambda b: out.blobs[p + b][..., first * 16:(first + 2) * 16]
            att = share._layer_by_name[p + "attn_sdpa"]
            from poseidon_tpu.core.layers import ApplyCtx
            o = att.apply({}, [cut("qn"), cut("kn"), cut("v")],
                          ApplyCtx(train=True))[0]
            total_net += o @ sp[p + "attn_o"]["w"].T
            s = x.shape[1]
            heads = lambda y: y.reshape(s, 2, 16)
            total_ref += jnp.stack([ref.attention(
                heads(cut("qn")[n]), heads(cut("kn")[n]), heads(cut("v")[n]))
                for n in range(N)]) @ jnp.asarray(w[p + "attn_o"][0]).T
    assert rel(total_net, out.blobs[p + "mo"]) < 1e-5
    assert rel(total_ref, out.blobs[p + "mo"]) < 3e-4


def test_share_bounds_are_refused():
    for kw in (dict(heads_held=5), dict(heads_held=-1)):
        with pytest.raises(ValueError, match="heads held of 4"):
            zoo.olmo_hybrid(**{**SIZES, **kw})


def test_layers_refuse_what_they_cannot_mean():
    text = zoo.to_prototxt(zoo.olmo_hybrid(batch=1, **SIZES))

    def broken(old, new, match):
        assert text.count(old) >= 1
        with pytest.raises(ValueError, match=match):
            Net(load_net_from_string(text.replace(old, new, 1)), "TRAIN",
                source_shapes={"tokens": (1, S), "targets": (1, S)})

    broken('  top: "l0_beta_over_one"\n',
           '  top: "l0_beta_over_one"\n  top: "l0_third"\n',
           "has 1 or 2 tops")
    broken('  bottom: "l0_gdec"\n', '  bottom: "l0_vc"\n',
           "g of that shape or")


# --------------------------------------------------------------------------- #
# the published sizes
# --------------------------------------------------------------------------- #

def count(net):
    shapes = jax.eval_shape(net.init, jax.random.PRNGKey(0))
    return sum(int(np.prod(x.shape)) for x in jax.tree.leaves(shapes))


def test_parameter_count_of_the_published_width_cut():
    """What configs/olmo_hybrid_7b.json's ``reduced_how`` states, from the
    net the program builds: 4 layers (one period), 15 of 30 heads, an
    eighth of the vocabulary; and the whole model's."""
    shapes = {"tokens": (1, 8192), "targets": (1, 8192)}
    cut = Net(zoo.olmo_hybrid(n_layers=4, heads_held=15, vocab=12544),
              "TRAIN", source_shapes=shapes)
    assert count(cut) == 766_241_946
    per = jax.eval_shape(cut.init, jax.random.PRNGKey(0))
    size = lambda pre: sum(int(np.prod(x.shape)) for k, v in per.items()
                           if k.startswith(pre) for x in jax.tree.leaves(v))
    assert size("l0_gdn_") == 44_375_262 and size("l3_attn_") == 29_495_040
    assert size("l0_ffn_") == 126_812_160 + 3840
    whole = Net(zoo.olmo_hybrid(), "TRAIN", source_shapes=shapes)
    per = jax.eval_shape(whole.init, jax.random.PRNGKey(0))
    assert size("l0_gdn_") == 88_750_332 and size("l3_attn_") == 58_990_080
    assert len([l for l in whole.layers if l.TYPE == "KDA_SCAN"]) == 24
    assert len([l for l in whole.layers if l.TYPE == "ATTENTION"]) == 8
    assert count(whole) == 7_430_870_688


@pytest.mark.parametrize("name", ["train", "solver"])
def test_example_prototxts_are_the_zoo_s_and_the_benchmark_s(name):
    """examples/lm/olmo_hybrid_7b_*.prototxt: the net is what
    `zoo.olmo_hybrid` writes at the cut its header states, and the
    benchmark's copies (what the cell runs) are the same bytes."""
    example = os.path.join(ROOT, "examples", "lm",
                           f"olmo_hybrid_7b_{name}.prototxt")
    copy = os.path.join(ROOT, "benchmark", "configs", "olmo_hybrid_7b",
                        f"{name}.prototxt")
    with open(example) as a, open(copy) as b:
        text = a.read()
        assert text == b.read()
    if name == "train":
        m = re.search(r"zoo\.olmo_hybrid\(batch=1, n_layers=(\d+), "
                      r"heads_held=(\d+), vocab=(\d+)\)", text)
        depth, held, vocab = (int(x) for x in m.groups())
        body = "".join(l for l in text.splitlines(True)
                       if not l.startswith("#"))
        assert body == zoo.to_prototxt(zoo.olmo_hybrid(
            batch=1, n_layers=depth, heads_held=held, vocab=vocab))
        assert (depth, held, vocab) == (4, 15, 100352 // 8)
        net = load_net_from_string(body)
        assert [l.name for l in net.layers if l.type == "KDA_SCAN"] \
            == [f"l{i}_gdn_scan" for i in LINEAR]
        widths = {l.name: l.inner_product_param.num_output
                  for l in net.layers if l.type == "INNER_PRODUCT"}
        assert widths["l0_gdn_q"] == 15 * 96 \
            and widths["l0_gdn_v"] == 15 * 192 \
            and widths["l0_gdn_a"] == 15 and widths["l3_attn_q"] == 15 * 128 \
            and widths["l0_ffn_gate"] == 11008 and widths["lm_head"] == 12544
    else:
        assert "--remat '/l\\d+_/,/lm_/'" in text
