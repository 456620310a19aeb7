"""Kimi-Linear's block as layers of the Net against its plain reference
(benchmark/reference/kimi_linear.py, loaded from there: one file, no second
copy), at a small size on the CPU with seeded weights: the chunked scan
against the token-by-token recurrence (values, five gradients, strong
decay, several chunks, two sequences); the short convolution against a
written-out loop; logits, loss, every gradient and one whole train step;
the expert shares summing to the whole layer with the shared expert counted
ONCE; what the run says it ran; the example prototxts."""

import functools
import importlib.util
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from poseidon_tpu.core.net import Net
from poseidon_tpu.models import zoo
from poseidon_tpu.ops import kda, kda_pallas
from poseidon_tpu.proto.messages import load_net_from_string

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_spec = importlib.util.spec_from_file_location(
    "kimi_reference",
    os.path.join(ROOT, "benchmark", "reference", "kimi_linear.py"))
ref = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(ref)

# 1 dense + a whole period (KDA + dense; KDA, KDA, MLA, KDA with MoE)
L, DENSE, E, K, HELD = 5, 1, 16, 4, 8
SIZES = dict(n_layers=L, dense_layers=DENSE, hidden=64, heads=4, head_dim=16,
             kv_rank=32, nope_dim=16, rope_dim=8, v_dim=16, dense_width=96,
             experts=E, top_k=K, expert_width=32, shared_width=32, vocab=128)
CFG = {"num_hidden_layers": L, "num_dense_layers": DENSE,
       "layer_types": ["kda", "kda", "kda", "mla", "kda"], "num_heads": 4,
       "kv_lora_rank": 32, "qk_nope_head_dim": 16, "num_experts": E,
       "num_experts_per_tok": K, "route_scale": 2.446, "rms_norm_eps": 1e-5}
N, S = 2, 128                    # two chunks of 64 a sequence
RATE = 0.001
MOE_LAYERS = list(range(DENSE, L))
KDA_LAYERS = [0, 1, 2, 4]


def build(held=HELD, held_first=0, n=N, s=S, **kw):
    # through the text form: what a user's prototxt goes through
    text = zoo.to_prototxt(zoo.kimi_linear(
        batch=n, held=held, held_first=held_first, **{**SIZES, **kw}))
    return Net(load_net_from_string(text), "TRAIN",
               source_shapes={"tokens": (n, s), "targets": (n, s)})


def batch_of(n=N, s=S, seed=5):
    key = jax.random.PRNGKey(seed)
    return {"tokens": jax.random.randint(key, (n, s), 0, SIZES["vocab"]),
            "targets": jax.random.randint(jax.random.fold_in(key, 1),
                                          (n, s), 0, SIZES["vocab"])}


def seeded(net, seed=3):
    """Fresh weights, then everything a fresh model has at a trivial value
    moved off it; the router's matrix larger, so that its choices are not
    all near-ties; the low-rank pairs larger, so that the decay and the
    gate depend on the token."""
    params = net.init(jax.random.PRNGKey(seed))
    for i, (lname, lp) in enumerate(sorted(params.items())):
        for j, (pname, w) in enumerate(sorted(lp.items())):
            noise = jax.random.normal(jax.random.PRNGKey(100 + 31 * i + j),
                                      w.shape)
            if pname == "g":
                lp[pname] = 1.0 + 0.2 * noise
            elif pname == "bias":
                lp[pname] = 0.02 * noise
            elif "_decay_" in lname:       # a decay that still remembers
                lp[pname] = 0.1 * noise
            elif lname.endswith("_router") or "_ogate_" in lname \
                    or lname.endswith("_beta"):
                lp[pname] = 0.5 * noise
    return params


@pytest.fixture(scope="module")
def model():
    net = build()
    return net, seeded(net), batch_of()


def rel(a, b):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.linalg.norm(a - b) / max(np.linalg.norm(b), 1e-30))


# --------------------------------------------------------------------------- #
# the chunked scan against the recurrence
# --------------------------------------------------------------------------- #

def operands(seed, b=2, s=128, h=2, d_k=16, d_v=8, strong=False):
    r = np.random.RandomState(seed)
    q, k = r.randn(2, b, s, h, d_k)
    q /= np.linalg.norm(q, axis=-1, keepdims=True)
    k /= np.linalg.norm(k, axis=-1, keepdims=True)
    g = -np.exp(r.uniform(-4, 1, size=(b, s, h, d_k)))
    if strong:      # some channels forget e^-3 and more a token
        g = 4 * g - 3.0 * (r.rand(b, s, h, d_k) < 0.5)
    beta = 1 / (1 + np.exp(-r.randn(b, s, h)))
    return [jnp.asarray(x, jnp.float32)
            for x in (q, k, r.randn(b, s, h, d_v), g, beta)]


@pytest.mark.parametrize("strong", [False, True], ids=["mild", "strong"])
@pytest.mark.parametrize("what", ["o", "dq", "dk", "dv", "dg", "dbeta"])
def test_chunked_scan_equals_the_recurrence(what, strong):
    """Two chunks of 64, values and each of the five gradients, f32 to
    rounding. ``strong``: a channel's log-decay sums below -150 inside a
    chunk, where exp(-G) overflows f32: every value stays finite and
    equal."""
    args = operands(0, strong=strong)
    if strong:
        per_chunk = jnp.sum(args[3].reshape(2, 2, 64, 2, 16), 2)
        assert float(jnp.min(per_chunk)) < -150
    co = jnp.asarray(np.random.RandomState(1).randn(2, 128, 2, 8),
                     jnp.float32)
    if what == "o":
        got, want = jax.jit(kda.kda_scan)(*args), kda.kda_recurrence(*args)
    else:
        i = ("dq", "dk", "dv", "dg", "dbeta").index(what)
        got, want = (jax.jit(jax.grad(lambda *a, f=f: jnp.sum(f(*a) * co),
                                      argnums=i))(*args)
                     for f in (kda.kda_scan, kda.kda_recurrence))
    assert np.all(np.isfinite(np.asarray(got)))
    assert got.shape == want.shape and rel(got, want) < 2e-5


@pytest.mark.parametrize("s,chunk", [(64, 64), (192, 64), (96, 48),
                                     (80, 16), (200, None)])
def test_chunk_rule_and_lengths(s, chunk):
    """A sequence of one, three chunks, a shorter chunk where 64 does not
    divide S, and none at all (the recurrence itself runs)."""
    assert kda.kda_chunk(s) == chunk
    args = operands(2, b=1, s=s)
    assert rel(kda.kda_scan(*args), kda.kda_recurrence(*args)) < 2e-5
    route = kda.kda_route(s)
    assert route == (("chunked", f"chunked C {chunk}, {s // chunk} chunks, "
                      f"f32 state") if chunk else
                     ("recurrence", f"token by token (no chunk divides "
                      f"S={s})"))


def test_sequences_of_a_batch_do_not_share_state():
    """Sequence 0 alone and beside another sequence: bit-equal output and
    gradients; the other sequence's operands take no gradient from it."""
    args = operands(3)
    alone = [x[:1] for x in args]

    def first(*a):
        return jnp.sum(kda.kda_scan(*a)[0] ** 2)

    np.testing.assert_array_equal(kda.kda_scan(*args)[:1],
                                  kda.kda_scan(*alone))
    both = jax.grad(first, argnums=(0, 1, 2, 3, 4))(*args)
    for g, g_alone in zip(both, jax.grad(first, argnums=(0, 1, 2, 3, 4))(
            *alone)):
        np.testing.assert_allclose(g[:1], g_alone, rtol=1e-6, atol=1e-9)
        assert not np.any(np.asarray(g[1:]))


def test_backward_keeps_one_state_a_chunk():
    """What the custom backward keeps: the five operands and ONE (d_k, d_v)
    f32 state a chunk a head; the (16, 16, d_k) decay ratios of a sub-block
    are no residual."""
    args = operands(4, b=1, s=256, h=2, d_k=16, d_v=8)
    _, res = jax.eval_shape(
        lambda *a: kda._kda_fwd(*a, 0.25, 64), *args)
    assert [r.shape for r in res[:5]] == [a.shape for a in args]
    # (groups, chunks a group, B, H, d_k, d_v)
    assert res[5].shape == (1, 4, 1, 2, 16, 8) \
        and res[5].dtype == jnp.float32
    assert kda.state_bytes(1, 256, 2, 16, 8) == 4 * 2 * 16 * 8 * 4
    # at the cell's shape: 2 MB a chunk a sequence
    assert kda.state_bytes(1, 64, 32, 128, 128) == 2 * 2 ** 20


def test_scan_under_one_checkpoint_is_replayed_once():
    """Under the traffic's one checkpoint a layer the gradient's jaxpr
    holds the scan over groups (and inside it the scan over a group's
    chunks) three times: the forward, its ONE replay (which keeps the
    states) and the backward walk — not a second replay."""
    args = operands(5, b=1, s=128)

    def loss(*a):
        return jnp.sum(jax.checkpoint(kda.kda_scan)(*a))

    text = str(jax.make_jaxpr(jax.grad(loss, argnums=(0, 1, 2, 3, 4)))(*args))
    assert text.count("scan[") == 2 * 3, text.count("scan[")


@pytest.mark.parametrize("what", ["o", "grads"])
def test_groups_of_chunks_carry_the_state(what, monkeypatch):
    """Four chunks as four groups of one and as two of two: the state and
    its cotangent cross the groups' boundaries."""
    args = operands(6, b=1, s=256)
    want = kda.kda_recurrence(*args) if what == "o" else jax.grad(
        lambda *a: jnp.sum(kda.kda_recurrence(*a) ** 2),
        argnums=(0, 1, 2, 3, 4))(*args)
    for tokens in (64, 128):
        monkeypatch.setattr(kda, "_GROUP", tokens)
        assert kda._group(256, 64) == tokens // 64
        got = kda.kda_scan(*args) if what == "o" else jax.grad(
            lambda *a: jnp.sum(kda.kda_scan(*a) ** 2),
            argnums=(0, 1, 2, 3, 4))(*args)
        for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
            assert rel(a, b) < 2e-5


# --------------------------------------------------------------------------- #
# the scan's Pallas arm (ops/kda_pallas.py), its kernels interpreted
# --------------------------------------------------------------------------- #

GRADS = ("dq", "dk", "dv", "dg", "dbeta")


def pallas_scan(m=2):
    return lambda *a: kda_pallas.kda_scan_pallas(
        *a, a[0].shape[-1] ** -0.5, m, True)


def values_and_grads(f, args, co):
    """o and the five gradients of sum(o * co), one compile."""
    o, pull = jax.vjp(f, *args)
    return dict(zip(("o",) + GRADS, (o,) + pull(co.astype(o.dtype))))


@pytest.fixture(scope="module")
def pallas_against_recurrence():
    """{strong: (the Pallas arm's, the recurrence's)} at the cell's head
    width: B = 2, S = 256 = two programs of two chunks, H = 2, d 128."""
    out = {}
    for strong in (False, True):
        args = operands(0, s=256, d_k=128, d_v=128, strong=strong)
        if strong:
            per_chunk = jnp.sum(args[3].reshape(2, 4, 64, 2, 128), 2)
            assert float(jnp.min(per_chunk)) < -150
        co = jnp.asarray(np.random.RandomState(1).randn(2, 256, 2, 128),
                         jnp.float32)
        out[strong] = tuple(
            jax.jit(functools.partial(values_and_grads, f))(args, co)
            for f in (pallas_scan(), kda.kda_recurrence))
    return out


@pytest.mark.parametrize("strong", [False, True], ids=["mild", "strong"])
@pytest.mark.parametrize("what", ("o",) + GRADS)
def test_pallas_scan_equals_the_recurrence(what, strong,
                                           pallas_against_recurrence):
    """The two kernels (forward with the states it saves; backward with the
    chunk-local pullback by hand) against the token-by-token recurrence and
    autodiff through it, f32 to rounding; ``strong``: a channel's log-decay
    sums below -150 inside a chunk and every value stays finite."""
    got, want = pallas_against_recurrence[strong]
    assert np.all(np.isfinite(np.asarray(got[what])))
    assert got[what].shape == want[what].shape
    assert rel(got[what], want[what]) < 2e-5


def test_pallas_scan_is_the_chunked_form_on_bf16_operands():
    """q, k, v as the compute policy hands them (bf16; g and beta f32): the
    two implementations of the chunked form cast up the same values, so
    they differ by f32 rounding and o's own bf16 rounding alone; the
    gradients come back in the operands' types."""
    args = operands(7, b=1, s=128, d_k=128, d_v=128)
    args = [x.astype(jnp.bfloat16) for x in args[:3]] + args[3:]
    co = jnp.asarray(np.random.RandomState(2).randn(1, 128, 2, 128),
                     jnp.float32)
    got, want = (values_and_grads(f, args, co)
                 for f in (pallas_scan(), kda.kda_scan))
    for name, x in zip(("o",) + GRADS, args[2:3] + args):
        assert got[name].dtype == want[name].dtype == x.dtype
        assert got[name].shape == want[name].shape
        # one bf16 ulp where a value sits on a rounding boundary
        assert rel(got[name].astype(jnp.float32),
                   want[name].astype(jnp.float32)) < 3e-3, name
    for name in ("dg", "dbeta"):
        assert rel(got[name], want[name]) < 2e-4, name


def test_pallas_sequences_of_a_batch_do_not_share_state():
    """The state scratch is zeroed at every sequence's first program:
    sequence 0 alone and beside another, bit-equal output and gradients;
    the other sequence's operands take no gradient from it."""
    args = operands(3, s=128, d_k=128, d_v=128)
    alone = [x[:1] for x in args]
    f = pallas_scan()

    def first(*a):
        return jnp.sum(f(*a)[0] ** 2)

    np.testing.assert_array_equal(f(*args)[:1], f(*alone))
    both = jax.grad(first, argnums=(0, 1, 2, 3, 4))(*args)
    for g, g_alone in zip(both, jax.grad(first, argnums=(0, 1, 2, 3, 4))(
            *alone)):
        np.testing.assert_array_equal(g[:1], g_alone)
        assert not np.any(np.asarray(g[1:]))


def test_pallas_backward_keeps_one_state_a_chunk_and_replays_once():
    """What the Pallas arm's backward keeps is what ``state_bytes`` says:
    the five operands and ONE (d_v, d_k) f32 state a chunk a head; under the
    traffic's one checkpoint a layer the gradient holds the forward kernel
    twice (the forward and its ONE replay, which saves the states) and the
    backward kernel once."""
    args = operands(4, b=1, s=256, d_k=128, d_v=128)
    _, res = jax.eval_shape(
        lambda *a: kda_pallas._vjp_fwd(*a, 0.25, 2, True), *args)
    assert [r.shape for r in res[:5]] == [a.shape for a in args]
    assert res[5].shape == (1, 2, 4, 128, 128) \
        and res[5].dtype == jnp.float32
    assert res[5].size * 4 == kda.state_bytes(1, 256, 2, 128, 128)

    def loss(*a):
        return jnp.sum(jax.checkpoint(pallas_scan())(*a))

    text = str(jax.make_jaxpr(jax.grad(loss, argnums=(0, 1, 2, 3, 4)))(*args))
    assert text.count("name=kda_scan_fwd") == 2, text.count("kda_scan_fwd")
    assert text.count("name=kda_scan_bwd") == 1


@pytest.mark.parametrize("s,m", [(192, 1), (384, 2), (256, 4)])
def test_pallas_chunks_a_program(s, m):
    """One chunk a program (a tile of 64 rows), several (tiles of 128, the
    state and its gradient crossing tiles and programs), and the rule where
    S / 64 is no multiple of the four it would like: 3 chunks -> 1 a
    program, 6 -> 2, 4 -> 4."""
    assert kda_pallas.kda_blocks(s, 128, 128) == m
    args = operands(8, b=1, s=s, h=1, d_k=128, d_v=128)
    co = jnp.asarray(np.random.RandomState(3).randn(1, s, 1, 128),
                     jnp.float32)
    got, want = (values_and_grads(f, args, co)
                 for f in (pallas_scan(m), kda.kda_recurrence))
    for name in ("o",) + GRADS:
        assert rel(got[name], want[name]) < 2e-5, name


def test_pallas_blocks_rule():
    """Of the shape and the VMEM budget alone: none where 64 does not
    divide S or a width is no multiple of 128; fewer chunks a program where
    wide heads' blocks would pass half the kernels' VMEM limit."""
    assert kda_pallas.kda_blocks(8192, 128, 128, 32) == 4
    assert kda_pallas.kda_blocks(96, 128, 128) is None
    assert kda_pallas.kda_blocks(128, 16, 128) is None
    assert kda_pallas.kda_blocks(128, 128, 64) is None
    assert kda_pallas.kda_blocks(8192, 512, 512, 32) == 2
    assert kda_pallas.kda_blocks(8192, 1024, 1024, 32) is None


def test_route_by_backend_and_shape(monkeypatch):
    """The Pallas arm where Mosaic compiles (here: the AOT-for-the-chip
    switch) and the kernels take the shape; the ``jax.numpy`` arms'
    notes letter for letter everywhere else, each with WHY the kernels
    did not take the shape (PR 48: the note names the reason)."""
    old = ("chunked", "chunked C 64, 128 chunks, f32 state")
    assert kda.kda_route(8192) == old
    assert kda.kda_route(8192, 128, 128, 32) == (            # the CPU mesh
        "chunked", old[1] + "; not pallas: this backend would interpret "
        "the kernels")
    monkeypatch.setenv("POSEIDON_FORCE_PALLAS", "1")
    assert kda.kda_route(8192, 128, 128, 32) == (
        "pallas", "pallas (C 64 x 4, 128 chunks, f32 state in VMEM)")
    assert kda.kda_route(192, 128, 128, 2) == (
        "pallas", "pallas (C 64 x 1, 3 chunks, f32 state in VMEM)")
    assert kda.kda_route(8192) == old                         # no widths
    assert kda.kda_route(8192, 16, 16, 4) == (               # the tiny nets
        "chunked", old[1] + "; not pallas: heads of 16 / 16 are no lane "
        "blocks of (N, S, H d) (multiples of 128)")
    assert kda.kda_route(96, 128, 128, 2) == (
        "chunked", "chunked C 48, 2 chunks, f32 state; not pallas: 64 does "
        "not divide S=96")
    assert kda.kda_route(200, 128, 128, 2)[0] == "recurrence"
    net = build(n=1, s=128, heads=2, head_dim=128, v_dim=128, nope_dim=128,
                rope_dim=64)
    for i in KDA_LAYERS:
        assert net.kernel_routes[f"l{i}_kda_scan"] == (
            "kda=pallas (C 64 x 2, 2 chunks, f32 state in VMEM)")
    assert net.layer_facts()["recurrent_state"]["l0_kda_scan"] == {
        "heads": 2, "d_k": 128, "d_v": 128, "chunk": 64, "chunks": 2,
        "saved_state_bytes": 2 * 2 * 128 * 128 * 4}


def test_short_conv_is_the_written_out_loop():
    """SHORT_CONV against an explicit loop over positions and taps: zeros
    before the sequence's start, nothing from the sequence beside it."""
    lp = load_net_from_string(
        'layers { name: "c" type: SHORT_CONV bottom: "x" top: "y" '
        'kda_param { kernel_size: 4 weight_filler { type: "uniform" '
        'min: -0.5 max: 0.5 } } }').layers[0]
    from poseidon_tpu.core.layers import ApplyCtx, create_layer
    layer = create_layer(lp, "TRAIN", 0)
    assert layer.setup([(2, 9, 6)]) == [(2, 9, 6)]
    assert [p.shape for p in layer.params] == [(4, 6)]
    r = np.random.RandomState(0)
    x, w = r.randn(2, 9, 6), r.uniform(-0.5, 0.5, (4, 6))
    got = layer.apply({"w": jnp.asarray(w, jnp.float32)},
                      [jnp.asarray(x, jnp.float32)], ApplyCtx(True))[0]
    want = np.zeros_like(x)
    for n in range(2):
        for t in range(9):
            for j in range(4):
                if t - j >= 0:
                    want[n, t] += w[j] * x[n, t - j]
    want = want / (1 + np.exp(-want))
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(
        ref.short_conv(jnp.asarray(x[0], jnp.float32),
                       jnp.asarray(w, jnp.float32)), want[0], rtol=1e-5,
        atol=1e-6)


def test_decay_fillers_and_form():
    """A_log = log U(1, 16) a head, dt_bias the inverse softplus of a step
    in [1e-3, 1e-1] a channel; g = -exp(A_log) softplus(x + dt_bias) <= 0 in
    f32 under the bf16 policy too; the second top is the mean decay."""
    net = build()
    params = net.init(jax.random.PRNGKey(0))
    p = params["l0_kda_decay"]
    assert p["A_log"].shape == (4,) and p["dt_bias"].shape == (64,)
    rate, step = np.exp(p["A_log"]), np.log1p(np.exp(p["dt_bias"]))
    assert 1 <= rate.min() and rate.max() <= 16
    assert 1e-3 * 0.999 <= step.min() and step.max() <= 1e-1 * 1.001
    out = net.apply(params, batch_of(), train=True, keep_blobs=True)
    g = np.asarray(out.blobs["l0_gdec"])
    assert g.dtype == np.float32 and g.max() <= 0
    np.testing.assert_allclose(out.outputs["l0_decay_mean"],
                               np.exp(g).mean(), rtol=1e-6)
    assert 0.2 < float(out.outputs["l0_decay_mean"]) < 1.0
    assert "l3_decay_mean" not in out.outputs           # the MLA layer


# --------------------------------------------------------------------------- #
# the whole net against the reference
# --------------------------------------------------------------------------- #

def test_leaves_scopes_and_routes(model):
    net, params, _ = model
    # embed, head, final norm; per layer 2 norms; KDA: q k v o, 3 convs, 2
    # decay + (A_log, dt_bias), beta, out-norm, 2 gate; MLA: q kva norm kvb x2
    # o; dense: 3; MoE: router 2, 3 stacks, shared 3
    assert sum(len(v) for v in params.values()) \
        == 3 + L * 2 + 4 * 15 + 6 + DENSE * 3 + (L - DENSE) * 8
    assert not net.shared_params
    assert params["l1_moe"]["gate"].shape == (HELD, 32, 64)
    assert params["l0_kda_onorm"]["g"].shape == (16,)    # one head's dims
    assert params["l3_mla_q"]["w"].shape == (4 * 24, 64)
    assert params["l3_mla_kva"]["w"].shape == (32 + 8, 64)
    assert net.layer_updates == {
        (f"l{i}_router", "bias"): f"l{i}_bias_next" for i in MOE_LAYERS}
    types = {l.name: l.TYPE for l in net.layers}
    assert [n for n, t in types.items() if t == "KDA_SCAN"] \
        == [f"l{i}_kda_scan" for i in KDA_LAYERS]
    assert [n for n, t in types.items() if t == "ATTENTION"] \
        == ["l3_mla_attn"]
    assert types["l0_kda_conv_q"] == "SHORT_CONV" \
        and types["l0_kda_l2_k"] == "L2_NORM" \
        and types["l0_kda_decay"] == "KDA_DECAY" \
        and types["l0_kda_onorm"] == "RMS_NORM" \
        and types["l3_mla_kva_split"] == "SLICE"
    for i in KDA_LAYERS:
        assert net.kernel_routes[f"l{i}_kda_scan"] \
            == ("kda=chunked C 64, 2 chunks, f32 state; not pallas: heads "
                "of 16 / 16 are no lane blocks of (N, S, H d) (multiples "
                "of 128)")
    assert net.kernel_routes["l3_mla_attn"] == (
        "attention=dense; no positions; d 24/16; k_pe repeated x4")
    assert net.layer_facts()["recurrent_state"] == {
        f"l{i}_kda_scan": {"heads": 4, "d_k": 16, "d_v": 16, "chunk": 64,
                           "chunks": 2, "saved_state_bytes":
                           N * 4 * 2 * 16 * 16 * 4} for i in KDA_LAYERS}


def test_mla_route_on_the_chip(monkeypatch):
    monkeypatch.setenv("POSEIDON_FORCE_PALLAS", "1")
    net = build(n=1, s=256, heads=2, nope_dim=128, rope_dim=64, v_dim=128,
                head_dim=32)
    route = net.kernel_routes["l3_mla_attn"]
    assert route.startswith("attention=pallas_flash (fwd 256x256 1/1") \
        and route.endswith("; flash d 192/128; operands head-major (Dh 192, "
                           "not lane-aligned)); no positions; "
                           "k_pe repeated x2"), route


def test_net_matches_reference_forward(model):
    """f32 against f32: the chunked scan against the token-by-token
    recurrence, flash-free attention against the masked softmax, the same
    products in another order."""
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
    for i in KDA_LAYERS:
        np.testing.assert_allclose(out.outputs[f"l{i}_decay_mean"],
                                   want["decay_mean"][i], rtol=1e-5)
    for at, i in enumerate(MOE_LAYERS):
        g = np.asarray(out.blobs[f"l{i}_gates"])
        np.testing.assert_array_equal(
            np.sort(np.argsort(-g, -1, kind="stable")[..., :K], -1),
            np.sort(np.asarray(want["choice"][at]), -1))
        np.testing.assert_allclose(g.sum(-1), 2.446, rtol=1e-5)
        counts = np.asarray(want["counts"][at])
        np.testing.assert_allclose(out.outputs[f"l{i}_held_share"],
                                   counts[:HELD].sum() / (N * S * K),
                                   rtol=1e-6)
        np.testing.assert_allclose(
            out.updates[f"l{i}_router"]["bias"],
            ref.next_bias(weights[f"l{i}_router"][-1], counts, RATE),
            rtol=0, atol=1e-7)
        assert rel(out.blobs[f"l{i}_m"], want["routed"][at]) < 3e-4
        assert rel(out.blobs[f"l{i}_s"], want["shared"][at]) < 3e-4


def test_net_matches_reference_gradients(model):
    """Every leaf's gradient: relative L2 under 1e-4 (f32 summation order
    through five blocks of backward and the scan's own backward against
    autodiff of the recurrence). The selection bias takes none."""
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
            if pname == "bias":
                assert not np.any(np.asarray(g)) \
                    and not np.any(np.asarray(got[lname][pname]))
                continue
            assert np.linalg.norm(np.asarray(g)) > 0, (lname, pname)
            assert rel(got[lname][pname], g) < 1e-4, (lname, pname)
            n += 1
    assert n == sum(len(v) for v in params.values()) - len(MOE_LAYERS)


def test_one_train_step_matches_the_reference_s(model):
    """One whole step as the runner's ``step_check`` compares it: the
    program's gradient through the solver's own update (ADAM + decay + the
    clip, the biases outside all three) against ``train_step`` (under its
    ``remat``: the recurrence in blocks of tokens)."""
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
    owned = {l.name: l.params for l in net.layers if l.name in params}
    opt = {"rate": {n: [sp.base_lr * p.lr_mult for p in ps]
                    for n, ps in owned.items()},
           "decay": {n: [sp.weight_decay * p.decay_mult for p in ps]
                     for n, ps in owned.items()},
           "clip": sp.clip_gradients, "b1": 0.9, "b2": 0.95, "eps": 1e-8,
           "bias_rate": RATE}
    want = jax.jit(lambda w: ref.train_step(
        CFG, w, batch["tokens"], batch["targets"], opt, held=range(HELD),
        remat=True, t_block=16))(net.export_weights(params))
    assert float(want["grad_norm"]) > sp.clip_gradients      # the clip is on
    assert abs(float(loss) - float(want["loss"])) < 1e-5 * float(loss)
    for lname, blobs in want["change"].items():
        for pdef, change in zip(owned[lname], blobs):
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
    # no decay on gains, A_log, dt_bias; decay on the taps
    assert mults["l0_kda_decay"] == {"A_log": (1.0, 0.0),
                                     "dt_bias": (1.0, 0.0)}
    assert mults["l0_kda_conv_q"] == {"w": (1.0, 1.0)}


def test_the_shares_add_up_with_the_shared_expert_counted_once():
    """One MoE layer (behind the dense one) cut into 4 shares of 4 experts:
    the four shares' ROUTED parts plus the shared expert ONCE equal the
    uncut reference's layer and the program's with all 16 held."""
    whole = build(held=0, n_layers=2)
    params = seeded(whole)
    batch = batch_of()
    cfg = {**CFG, "num_hidden_layers": 2}
    stacks, share_of = params["l1_moe"], 4
    routed, shared = [], []
    for first in range(0, E, share_of):
        net = build(held=share_of, held_first=first, n_layers=2)
        share = {**params, "l1_moe": {k: v[first:first + share_of]
                                      for k, v in stacks.items()}}
        out = jax.jit(lambda p, b, net=net: net.apply(
            p, b, train=True, keep_blobs=True))(share, batch)
        want = ref.forward(cfg, net.export_weights(share), batch["tokens"],
                           held=range(first, first + share_of))
        assert rel(out.blobs["l1_m"], want["routed"][0]) < 1e-4
        assert rel(out.blobs["l1_f"],
                   want["routed"][0] + want["shared"][0]) < 1e-4
        routed.append(np.asarray(out.blobs["l1_m"]))
        shared.append(np.asarray(out.blobs["l1_s"]))
    for other in shared[1:]:
        np.testing.assert_array_equal(shared[0], other)
    uncut = ref.forward(cfg, whole.export_weights(params), batch["tokens"])
    layer = uncut["routed"][0] + uncut["shared"][0]
    assert rel(sum(routed) + shared[0], layer) < 1e-4
    # and NOT the plain sum of the shares' outputs
    assert rel(sum(routed) + sum(shared), layer) > 0.1
    full = jax.jit(lambda p, b: whole.apply(p, b, train=True,
                                            keep_blobs=True))(params, batch)
    assert rel(full.blobs["l1_f"], layer) < 1e-4
    assert float(full.outputs["l1_held_share"]) == 1.0


def test_no_positions_and_causal_reach(model):
    """Perturb token t: nothing before t moves anywhere (the convolution,
    the recurrence and the attention are causal); the KDA layer's and the
    MLA layer's outputs move at t and after."""
    net, params, batch = model
    t = 70                                   # in the second chunk
    other = dict(batch, tokens=batch["tokens"].at[:, t].set(
        (batch["tokens"][:, t] + 1) % SIZES["vocab"]))
    run = jax.jit(lambda b: net.apply(params, b, train=True,
                                      keep_blobs=True).blobs)
    a, b = run(batch), run(other)
    for blob in ("l0_so", "l3_att", "logits"):
        x, y = np.asarray(a[blob]), np.asarray(b[blob])
        np.testing.assert_array_equal(x[:, :t], y[:, :t])
        assert np.any(x[:, t] != y[:, t]) and np.any(x[:, -1] != y[:, -1])


def test_layers_refuse_what_they_cannot_mean():
    text = zoo.to_prototxt(zoo.kimi_linear(batch=N, **SIZES))

    def broken(old, new, match):
        assert old in text
        with pytest.raises(ValueError, match=match):
            Net(load_net_from_string(text.replace(old, new, 1)), "TRAIN",
                source_shapes={"tokens": (N, S), "targets": (N, S)})

    broken("    value_head_dim: 16\n", "    value_head_dim: 12\n",
           "values of 12 need")
    broken('  bottom: "l0_beta"\n', '  bottom: "l0_bl"\n  bottom: "l0_bl"\n',
           "KDA_SCAN takes q, k, v, g, beta")
    broken("    num_heads: 4\n", "    num_heads: 5\n", "H = 5 heads")


@pytest.mark.parametrize("name", ["train", "solver"])
def test_example_prototxts_are_the_zoo_s_and_the_benchmark_s(name):
    """examples/lm/kimi_linear_*.prototxt: the net is what
    `zoo.kimi_linear` writes at the cut its header states, and the
    benchmark's copies (what the cell runs) are the same bytes."""
    example = os.path.join(ROOT, "examples", "lm",
                           f"kimi_linear_{name}.prototxt")
    copy = os.path.join(ROOT, "benchmark", "configs", "kimi_linear_48b",
                        f"{name}.prototxt")
    with open(example) as a, open(copy) as b:
        text = a.read()
        assert text == b.read()
    if name == "train":
        m = re.search(r"zoo\.kimi_linear\(batch=1, n_layers=(\d+), "
                      r"held=(\d+), vocab=(\d+)\)", text)
        depth, held, vocab = (int(x) for x in m.groups())
        body = "".join(l for l in text.splitlines(True)
                       if not l.startswith("#"))
        assert body == zoo.to_prototxt(zoo.kimi_linear(
            batch=1, n_layers=depth, held=held, vocab=vocab))
        assert (depth, held, vocab) == (5, 8, 163840 // 8)
        net = load_net_from_string(body)
        routers = [l for l in net.layers if l.type == "MOE_ROUTER"]
        assert len(routers) == 4 and all(
            l.moe_param.bias_update_rate == 0.001
            and l.moe_param.score_func == "sigmoid"
            and l.moe_param.top_k == 8 and l.moe_param.num_experts == 256
            and l.moe_param.route_scale == 2.446 for l in routers)
        assert [l.name for l in net.layers if l.type == "KDA_SCAN"] \
            == [f"l{i}_kda_scan" for i in KDA_LAYERS]
        widths = {l.name: l.inner_product_param.num_output
                  for l in net.layers if l.type == "INNER_PRODUCT"}
        assert widths["l0_kda_q"] == 4096 and widths["l3_mla_q"] == 32 * 192 \
            and widths["l3_mla_kva"] == 512 + 64 \
            and widths["l0_ffn_gate"] == 9216 \
            and widths["l0_kda_decay_down"] == 128 \
            and widths["lm_head"] == 20480
    else:
        assert "--remat '/l\\d+_/,/lm_/'" in text
