"""Granite-4.0-H-Micro's block as layers of the Net against its plain
reference (benchmark/reference/granite_hybrid.py, loaded from there: one
file, no second copy), at a small size on the CPU with seeded weights:
Mamba-2's chunked scan and the Pallas kernels, interpreted, against the
token-by-token recurrence (values and six gradients, strong and weak decay,
heads that fill no whole program); logits, loss, the display's counters and
every gradient with the four multipliers at their published values; the
convolution's bias, ATTENTION's own scale, the tied table's two gradients,
the vocabulary's eight shares; what the run says it ran; both parameter
counts; the example prototxts."""

import importlib.util
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from poseidon_tpu.core.net import Net
from poseidon_tpu.models import zoo
from poseidon_tpu.ops import ssd, ssd_pallas
from poseidon_tpu.proto.messages import load_net_from_string

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_spec = importlib.util.spec_from_file_location(
    "granite_hybrid_reference",
    os.path.join(ROOT, "benchmark", "reference", "granite_hybrid.py"))
ref = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(ref)

# one whole period: five mamba, attention, four mamba; hidden 64, attention
# heads of 8 (8 / 2), 16 scan heads of 8 with a state of 16
L = 10
SIZES = dict(layers=L, vocab_rows=128, hidden=64, heads=8, kv_heads=2,
             ssd_heads=16, ssd_head_dim=8, state=16, ffn_width=96)
TYPES = ["mamba"] * 5 + ["attention"] + ["mamba"] * 4
MAMBA = [i for i, t in enumerate(TYPES) if t == "mamba"]
CFG = {"num_hidden_layers": L, "layer_types": TYPES, "mamba_n_heads": 16,
       "mamba_d_state": 16, "num_attention_heads": 8,
       "num_key_value_heads": 2, "rms_norm_eps": 1e-5,
       "embedding_multiplier": 12, "attention_multiplier": 0.015625,
       "residual_multiplier": 0.22, "logits_scaling": 8}
N, S = 2, 48                      # three chunks of 16 a sequence


def build(n=N, s=S, **kw):
    # through the text form: what a user's prototxt goes through
    text = zoo.to_prototxt(zoo.granite_hybrid(batch=n, **{**SIZES, **kw}))
    return Net(load_net_from_string(text), "TRAIN",
               source_shapes={"tokens": (n, s), "targets": (n, s)})


def batch_of(n=N, s=S, seed=5):
    key = jax.random.PRNGKey(seed)
    return {"tokens": jax.random.randint(key, (n, s), 0, SIZES["vocab_rows"]),
            "targets": jax.random.randint(jax.random.fold_in(key, 1),
                                          (n, s), 0, SIZES["vocab_rows"])}


def seeded(net, seed=3):
    """Fresh weights, then everything a fresh model has at a trivial value
    moved off it: gains and the skip D off 1, matrices large enough that the
    step and the gate depend on the token."""
    params = net.init(jax.random.PRNGKey(seed))
    for i, (lname, lp) in enumerate(sorted(params.items())):
        for j, (pname, w) in enumerate(sorted(lp.items())):
            noise = jax.random.normal(jax.random.PRNGKey(100 + 31 * i + j),
                                      w.shape)
            if pname in ("g", "D"):
                lp[pname] = 1.0 + 0.2 * noise
            elif lname == "embed":
                lp[pname] = 0.1 * noise
            elif pname == "w" and not lname.endswith("_ssd_conv"):
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
# the scan against the recurrence
# --------------------------------------------------------------------------- #

# dt A a token: about -8 (the state forgets everything), -0.3, -1e-3
DECAYS = {"strong": (2.0, 3.0, 5.0), "mild": (-2.0, 1.0, 4.0),
          "weak": (-7.0, 0.9, 1.1)}
NAMES = ("x", "dt", "B", "C", "A_log", "D")


def operands(seed, decay, b, s, h, p, n):
    shift, lo, hi = DECAYS[decay]
    r = np.random.RandomState(seed)
    dt = np.log1p(np.exp(r.randn(b, s, h) + shift))
    ops = (r.randn(b, s, h, p), dt, 0.5 * r.randn(b, s, n),
           0.5 * r.randn(b, s, n), np.log(r.uniform(lo, hi, size=h)),
           1.0 + 0.3 * r.randn(h), r.randn(b, s, h, p))
    return [jnp.asarray(t, jnp.float32) for t in ops]


def value_and_grads(scan, x, dt, b, c, a_log, d, weight):
    def total(x, dt, b, c, a_log, d):
        y = scan(x, dt, -jnp.exp(a_log) * dt, b, c, d)
        return jnp.sum(y.astype(jnp.float32) * weight)
    return jax.value_and_grad(total, argnums=tuple(range(6)))(
        x, dt, b, c, a_log, d)


def close(got, want, decay):
    assert rel(got[0], want[0]) < 1e-4
    for name, g, w in zip(NAMES, got[1], want[1]):
        assert np.linalg.norm(np.asarray(w)) > 0, name
        # under strong decay d A_log is a sum of terms near f32's floor
        limit = 5e-3 if (name, decay) == ("A_log", "strong") else 2e-4
        assert rel(g, w) < limit, (name, rel(g, w))


@pytest.mark.parametrize("decay", sorted(DECAYS))
def test_chunked_scan_equals_the_recurrence(decay):
    """S = 80 in five chunks of 16, 5 heads of 12 with a state of 20 (no
    multiple of 8 anywhere): y and all six gradients."""
    ops = operands(1, decay, 2, 80, 5, 12, 20)
    assert float(jnp.mean(-jnp.exp(ops[4]) * ops[1])) < 0
    close(value_and_grads(ssd.ssd_scan, *ops),
          value_and_grads(ssd.ssd_recurrence, *ops), decay)


def test_scan_takes_the_chunk_rule_and_falls_back_to_the_recurrence():
    assert [ssd.ssd_chunk(s) for s in (8192, 384, 80, 37)] \
        == [256, 128, 16, None]
    ops = operands(2, "mild", 1, 37, 3, 8, 16)[:6]
    x, dt, b, c, a_log, d = ops
    a = -jnp.exp(a_log) * dt
    np.testing.assert_allclose(
        ssd.ssd_scan(x, dt, a, b, c, d),
        ssd.ssd_recurrence(x, dt, a, b, c, d), rtol=1e-6, atol=1e-6)
    with pytest.raises(ValueError, match="does not divide"):
        ssd.ssd_scan(x, dt, a, b, c, d, chunk=16)


# (heads, P, S): 3 heads of 32 fill no lane block (padded to 4, one program);
# 10 heads of 64 fill no second program (8 a program, padded to 16), two
# chunks of 128
PALLAS = {"h3_p32": (3, 32, 256), "h10_p64": (10, 64, 256)}


@pytest.mark.parametrize("decay", ["strong", "weak"])
@pytest.mark.parametrize("shape", sorted(PALLAS))
def test_pallas_kernels_interpreted_equal_the_recurrence(shape, decay):
    h, p, s = PALLAS[shape]
    q = 256 if shape == "h3_p32" else 128
    assert ssd_pallas.padded_heads(h, p) > h
    ops = operands(3, decay, 1, s, h, p, 128)
    scan = lambda *t: ssd_pallas.ssd_scan_pallas(*t, q, True)
    got = value_and_grads(scan, *ops)
    close(got, value_and_grads(ssd.ssd_recurrence, *ops), decay)
    close(got, value_and_grads(
        lambda *t: ssd.ssd_scan(*t, chunk=64), *ops), decay)


def test_pallas_kernels_take_bf16_operands_and_keep_states():
    x, dt, b, c, a_log, d, _ = operands(4, "mild", 1, 256, 4, 64, 128)
    a = -jnp.exp(a_log) * dt
    half = lambda t: t.astype(jnp.bfloat16)
    y, states = ssd_pallas._forward(half(x), dt, a, half(b), half(c), d,
                                    256, True, True)
    assert y.dtype == jnp.bfloat16 and states.shape == (1, 1, 256, 128)
    assert float(jnp.max(jnp.abs(states))) == 0     # the first chunk's start
    want = ssd.ssd_recurrence(half(x), dt, a, half(b), half(c), d)
    assert rel(y.astype(jnp.float32), want) < 4e-3      # y's own rounding


def test_split3_is_exact_over_the_kernels_range():
    """hi + mid + lo == a bit for bit: the ratios exp(L_i - L_j) down to
    exp(-70), states up to 1e4, zeros, both signs. Below 2^-102 a part
    falls under the smallest normal number and is flushed, on this CPU as
    in the MXU: the sum is then short by less than that number."""
    r = np.random.RandomState(7)
    f32 = lambda t: np.asarray(t, np.float32)
    values = np.concatenate([
        f32(np.exp(-r.uniform(0, 70, 50000))), f32(1e4 * r.randn(50000)),
        f32(-np.exp(-r.uniform(0, 70, 50000))), f32(r.randn(50000)),
        np.zeros(8, np.float32), f32([1.0, -1.0, 2.0 ** -102, 3e38])])
    tiny = f32(np.exp(-r.uniform(70, 110, 50000)) * np.sign(r.randn(50000)))

    def back(a):
        parts = jax.jit(ssd_pallas.split3)(jnp.asarray(a))
        assert all(t.dtype == jnp.bfloat16 for t in parts)
        hi, mid, lo = (np.asarray(t.astype(jnp.float32)) for t in parts)
        return (hi + mid) + lo

    assert np.array_equal(back(values), values)
    assert np.max(np.abs(back(tiny).astype(np.float64) - tiny)) <= 2.0 ** -126


# (heads, P, S, Q): heads of 64 with padding (10 -> 16, two programs a chunk,
# two chunks); heads of 128, one a lane block
SHORT = {"h10_p64": (10, 64, 256, 128), "h3_p128": (3, 128, 256, 256)}


@pytest.mark.parametrize("decay", ["strong", "weak"])
@pytest.mark.parametrize("shape", sorted(SHORT))
def test_bf16_operands_sum_what_the_six_pass_products_sum(shape, decay):
    """The kernels on bf16 x, B, C, d y (one and three passes a product)
    against the SAME values handed in as f32 (six passes, the parent's
    products): what leaves in f32 (the states, d dt, d a, d D) to 1e-5
    (d a under strong decay to 1e-4: sums of terms near f32's floor, as in
    ``close``); what leaves in the operands' type (y, d x, d B, d C) equal
    to the f32 instance's after ITS rounding to bf16, but for a few
    neighbours one unit in the last place apart (or, where an element is
    what large terms cancel to, the f32 sums' own last place)."""
    h, p, s, q = SHORT[shape]
    x, dt, b, c, a_log, d, d_y = operands(5, decay, 1, s, h, p, 128)
    a = -jnp.exp(a_log) * dt
    short = [t.astype(jnp.bfloat16) for t in (x, b, c, d_y)]

    def both(x, b, c, d_y):
        y, states = ssd_pallas._forward(x, dt, a, b, c, d, q, True, True)
        return (y, states) + ssd_pallas._backward(
            x, dt, a, b, c, d, states, d_y, q, True)

    got = both(*short)
    want = both(*(t.astype(jnp.float32) for t in short))
    for name, g, w in zip(("y", "states", "dx", "ddt", "da", "dB", "dC",
                           "dD"), got, want):
        assert float(jnp.max(jnp.abs(w))) > 0 or name == "states", name
        if g.dtype == jnp.float32:
            limit = 1e-4 if (name, decay) == ("da", "strong") else 1e-5
            assert w.dtype == jnp.float32 and rel(g, w) < limit, name
            continue
        assert g.dtype == jnp.bfloat16 and w.dtype == jnp.float32, name
        g, w = (np.asarray(t.astype(jnp.bfloat16).astype(jnp.float32))
                for t in (g, w))
        assert np.mean(g != w) < 1e-3, (name, np.mean(g != w))
        assert np.all(np.abs(g - w) <= np.abs(w) * 2.0 ** -7
                      + 1e-6 * np.max(np.abs(w))), name


def test_mxu_passes_count_the_products_the_types_need(monkeypatch):
    """FLOP x passes at Granite's shape (Q 256, P 64, N 128): a lane block
    more of a program, a program of eight heads, 256 programs a call; and
    the route's note carries the share of six passes a product."""
    def counts(dtype):
        two, four, eight = (ssd_pallas.mxu_passes(256, 64, 128, heads, dtype)
                            for heads in (2, 4, 8))
        return ([round((b - a) / 1e6) for a, b in zip(two, four)],
                [round(256 * t / 1e9) for t in eight])

    assert counts(jnp.float32) == ([302, 654], [335, 747])
    assert counts(jnp.bfloat16) == ([151, 310], [159, 348])
    assert counts(jnp.float16) == counts(jnp.float32)   # cast, not split
    # the same four lane blocks as four heads of 128: no lanes zeroed, half
    # the products a head
    assert [round(t / 1e6) for t in ssd_pallas.mxu_passes(
        256, 128, 128, 4, jnp.bfloat16)] == [419, 1091]
    monkeypatch.setenv("POSEIDON_FORCE_PALLAS", "1")
    assert ssd.ssd_route(8192, 64, 64, 128, 2)[1].endswith(
        "f32 states in VMEM, passes 0.47 / 0.47 of six a product)")
    assert ssd.ssd_route(8192, 64, 64, 128, 4)[1].endswith(
        "f32 states in VMEM, passes 1.00 / 1.00 of six a product)")


def test_route_names_the_arm_and_why(monkeypatch):
    assert ssd.ssd_route(8192, 64, 64, 128) == (
        "chunked", "chunked Q 256, 32 chunks, f32 state, one C B^T grid a "
        "chunk; not pallas: this backend would interpret the kernels")
    assert ssd.ssd_route(37, 4, 8, 16)[0] == "recurrence"
    monkeypatch.setenv("POSEIDON_FORCE_PALLAS", "1")
    assert ssd.ssd_route(8192, 64, 64, 128) == (
        "pallas", "pallas (Q 256, 32 chunks, 8 heads a program, 2 a lane "
        "block, one C B^T grid a program, f32 states in VMEM, passes 1.00 / "
        "1.00 of six a product)")
    assert ssd.ssd_route(384, 64, 64, 128)[1].startswith("pallas (Q 128, 3")
    for shape, why in (((48, 16, 8, 16), "neither 256 nor 128 divides S=48"),
                       ((256, 16, 8, 128), "heads of 8 are no whole part"),
                       ((256, 4, 64, 16), "a state of 16 a head is no")):
        arm, note = ssd.ssd_route(*shape)
        assert arm == "chunked" and f"not pallas: {why}" in note
    assert ssd.state_bytes(1, 8192, 64, 64, 128) == 32 * 64 * 64 * 128 * 4


# --------------------------------------------------------------------------- #
# the Net against the reference
# --------------------------------------------------------------------------- #

def test_leaves_scopes_and_routes(model):
    net, params, _ = model
    # table, final norm; per layer 2 norms + 2 FFN; mamba: in, conv (w, b),
    # (A_log, dt_bias), D, out-norm, out; attention: q k v o
    assert sum(len(v) for v in params.values()) \
        == 2 + L * 4 + 9 * 8 + 4
    assert "lm_head" not in params or not params["lm_head"]    # tied
    inner = 16 * 8
    assert params["l0_ssd_in"]["w"].shape == (2 * inner + 2 * 16 + 16, 64)
    assert params["l0_ssd_conv"]["w"].shape == (4, inner + 32)
    assert params["l0_ssd_conv"]["b"].shape == (inner + 32,)
    assert params["l0_ssd_decay"]["A_log"].shape == (16,)
    assert params["l0_ssd_decay"]["dt_bias"].shape == (16,)
    assert params["l0_ssd_scan"]["D"].shape == (16,)
    assert params["l0_ssd_onorm"]["g"].shape == (inner,)   # ONE whole norm
    assert params["l5_attn_k"]["w"].shape == (2 * 8, 64)
    types = {l.name: l.TYPE for l in net.layers}
    assert [n for n, t in types.items() if t == "SSD_SCAN"] \
        == [f"l{i}_ssd_scan" for i in MAMBA]
    assert [n for n, t in types.items() if t == "ATTENTION"] \
        == ["l5_attn_sdpa"]
    assert types["embed_scale"] == types["lm_scale"] == "POWER" \
        and types["l0_ssd_decay"] == "KDA_DECAY" \
        and types["l0_ssd_gate"] == "SILU_GATE"
    for i in MAMBA:
        assert net.kernel_routes[f"l{i}_ssd_scan"] == (
            "ssd_scan=chunked Q 16, 3 chunks, f32 state, one C B^T grid a "
            "chunk; not pallas: neither 256 nor 128 divides S=48")
    assert net.kernel_routes["l5_attn_sdpa"] == \
        "attention=dense; 2 kv heads repeated x4; no positions"
    assert net.layer_facts()["recurrent_state"] == {
        f"l{i}_ssd_scan": {"heads": 16, "d_k": 16, "d_v": 8, "chunk": 16,
                           "chunks": 3, "decay": "head", "saved_state_bytes":
                           N * 16 * 3 * 8 * 16 * 4} for i in MAMBA}
    mults = {l.name: {p.name: (p.lr_mult, p.decay_mult) for p in l.params}
             for l in net.layers if l.name in params}
    assert mults["l0_ssd_decay"] == {"A_log": (1.0, 0.0),
                                     "dt_bias": (1.0, 0.0)}
    assert mults["l0_ssd_conv"] == {"w": (1.0, 1.0), "b": (1.0, 0.0)}
    assert mults["l0_ssd_scan"] == {"D": (1.0, 0.0)}
    # every scope the configuration's patterns have to find
    scopes = "\n".join(types)
    for part in ("ssd_in", "ssd_conv", "ssd_decay", "ssd_scan", "ssd_gate",
                 "ssd_onorm", "ssd_out", "ffn_in", "ffn_act", "ffn_out",
                 "res1", "res2"):
        assert re.search(rf"^l0_{part}$", scopes, re.M), part
    for part in "qkvo":
        assert f"l5_attn_{part}" in types


def test_net_matches_reference_forward(model):
    """f32 against f32, the four multipliers at their published values:
    the chunked scan against the token-by-token recurrence, dense attention
    at its own scale against the masked softmax; and the two counters a
    display carries."""
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
    for at, i in enumerate(MAMBA):
        np.testing.assert_allclose(out.outputs[f"l{i}_ssd_decay_mean"],
                                   want["decay_mean"][at], rtol=1e-5)
        np.testing.assert_allclose(out.outputs[f"l{i}_ssd_dt_mean"],
                                   want["dt_mean"][at], rtol=1e-5)
        assert 0.0 < float(out.outputs[f"l{i}_ssd_decay_mean"]) < 1.0
    assert "l5_ssd_decay_mean" not in out.outputs       # the attention layer
    assert rel(out.blobs[f"l{L - 1}_y"], want["state"]) < 2e-4


def test_net_matches_reference_gradients(model):
    """Every leaf's gradient, relative L2 under 1e-3: the scan's backward
    (the chunk-local pullback, the state's carried in reverse) against
    autodiff of the recurrence; the convolution's bias gets its own; the
    table's is the sum of the lookup's and the head's."""
    net, params, batch = model
    got = jax.jit(jax.grad(
        lambda p: net.apply(p, batch, train=True).loss))(params)
    weights = {k: [jnp.asarray(b) for b in v] for k, v in
               net.export_weights(params).items() if params.get(k)}
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
    assert float(jnp.linalg.norm(got["l0_ssd_conv"]["b"])) > 0


def test_the_table_s_gradient_is_the_lookup_s_and_the_head_s(model):
    """Untied in the reference (one table looked up x 12, another under the
    head / 8): the Net's gradient of the ONE table is the sum of the two."""
    net, params, batch = model
    got = jax.jit(jax.grad(
        lambda p: net.apply(p, batch, train=True).loss))(params)
    weights = {k: [jnp.asarray(b) for b in v] for k, v in
               net.export_weights(params).items() if params.get(k)}

    def untied(lookup, head):
        h0 = CFG["embedding_multiplier"] * lookup[batch["tokens"]]
        return ref.loss(CFG, {**weights, "embed": [head]}, batch["tokens"],
                        batch["targets"], states=h0)[0]

    table = weights["embed"][0]
    d_lookup, d_head = jax.jit(jax.grad(untied, argnums=(0, 1)))(table,
                                                                 table)
    assert float(jnp.linalg.norm(d_lookup)) > 0 \
        and float(jnp.linalg.norm(d_head)) > 0
    assert rel(got["embed"]["w"], d_lookup + d_head) < 1e-3
    assert rel(got["embed"]["w"], d_head) > 1e-2          # both are needed


def test_the_vocabulary_s_eight_shares_give_the_whole_logits(model):
    """A chip that holds an eighth of the table's rows computes an eighth of
    the logits' columns: the eight shares side by side are the whole
    reference's logits (each fed the whole model's h_0: the lookup of a row
    another share holds is that share's)."""
    net, params, batch = model
    weights = net.export_weights(params)
    whole = ref.forward(CFG, weights, batch["tokens"])["logits"]
    h0 = CFG["embedding_multiplier"] \
        * jnp.asarray(weights["embed"][0])[batch["tokens"]]
    parts = [ref.forward(CFG, share, batch["tokens"], states=h0)["logits"]
             for share in ref.vocabulary_shares(weights, 8)]
    assert parts[0].shape[-1] * 8 == whole.shape[-1]
    np.testing.assert_allclose(jnp.concatenate(parts, -1), whole,
                               rtol=1e-6, atol=1e-6)


def test_attention_takes_a_scale_of_its_own():
    """ATTENTION ``scale`` against the dense op of the reference, at a scale
    that is not 1 / sqrt(Dh); 0 is 1 / sqrt(Dh)."""
    from poseidon_tpu.models.transformer import rope_attention
    r = np.random.RandomState(0)
    q, k, v = (jnp.asarray(r.randn(1, 32, w), jnp.float32)
               for w in (64, 16, 16))
    for scale, expect in ((0.4, 0.4), (None, 8 ** -0.5)):
        got = rope_attention(q, k, v, n_heads=8, n_kv_heads=2, rope=False,
                             scale=scale)[0]
        want = ref.attention(q[0].reshape(32, 8, 8), k[0].reshape(32, 2, 8),
                             v[0].reshape(32, 2, 8), expect)
        assert rel(got, want) < 1e-5
    text = zoo.to_prototxt(zoo.granite_hybrid(**SIZES))
    assert "scale: 0.015625" in text
    assert "scale" not in zoo.to_prototxt(zoo.olmo_hybrid(
        n_layers=4, hidden=64, heads=4, key_head_dim=12, value_head_dim=24,
        attn_head_dim=16, ffn_width=96, vocab=128)).split("ATTENTION")[1] \
        .split("layer")[0]


def test_layers_refuse_what_they_cannot_mean():
    text = zoo.to_prototxt(zoo.granite_hybrid(batch=N, **SIZES))
    shapes = {"tokens": (N, S), "targets": (N, S)}
    for bad, why in (
            (text.replace('bottom: "l0_dt"', 'bottom: "l0_xs"', 1),
             "SSD_SCAN takes x"),           # dt of another width than heads
            (text.replace("scale: 0.015625", "scale: -1.0"),
             "scale -1.0 is negative")):
        assert bad != text
        with pytest.raises(ValueError, match=why):
            Net(load_net_from_string(bad), "TRAIN", source_shapes=shapes)


def test_both_parameter_counts():
    """The published 40 layers and whole table (the catalog's "about 3.2B")
    and the benchmark's cut, to the unit, from the layers' shapes alone."""
    def count(**kw):
        net = Net(load_net_from_string(zoo.to_prototxt(
            zoo.granite_hybrid(**kw))), "TRAIN",
            source_shapes={"tokens": (1, 8192), "targets": (1, 8192)})
        owned = {}
        for l in net.layers:
            for i, pd in enumerate(l.params):
                key = l.lp.param_spec(i).name or (l.name, pd.name)
                owned[key] = int(np.prod(pd.shape))
        return sum(owned.values()), net

    mamba = 2048 * 8512 + 5 * 4352 + 3 * 64 + 4096 + 4096 * 2048
    attention = 2 * 2048 * 2048 + 2 * 512 * 2048
    mlp = 2048 * 16384 + 8192 * 2048
    assert (mamba, attention, mlp) == (25847232, 10485760, 50331648)
    whole, net = count()
    assert whole == 36 * mamba + 4 * attention + 40 * (mlp + 4096) \
        + 100352 * 2048 + 2048 == 3191396096
    assert [l.name for l in net.layers if l.TYPE == "ATTENTION"] \
        == [f"l{i}_attn_sdpa" for i in (5, 15, 25, 35)]
    cut, net = count(layers=10, vocab_rows=12544)
    assert cut == 9 * mamba + attention + 10 * (mlp + 4096) \
        + 12544 * 2048 + 2048 == 772160448
    assert net.kernel_routes["l5_attn_sdpa"].startswith("attention=")


@pytest.mark.parametrize("name", ["train", "solver"])
def test_example_prototxts_are_the_zoo_s_and_the_benchmark_s(name):
    """examples/lm/granite_h_micro_*.prototxt: the net is what
    `zoo.granite_hybrid` writes at the cut its header states, and the
    benchmark's copies (what the cell runs) are the same bytes."""
    example = os.path.join(ROOT, "examples", "lm",
                           f"granite_h_micro_{name}.prototxt")
    copy = os.path.join(ROOT, "benchmark", "configs", "granite_4_0_h_micro",
                        f"{name}.prototxt")
    with open(example) as a, open(copy) as b:
        text = a.read()
        assert text == b.read()
    if name == "train":
        m = re.search(r"zoo\.granite_hybrid\(batch=1, layers=(\d+), "
                      r"vocab_rows=(\d+)\)", text)
        depth, rows = (int(x) for x in m.groups())
        body = "".join(l for l in text.splitlines(True)
                       if not l.startswith("#"))
        assert body == zoo.to_prototxt(zoo.granite_hybrid(
            batch=1, layers=depth, vocab_rows=rows))
        assert (depth, rows) == (10, 100352 // 8)
        net = load_net_from_string(body)
        assert [l.name for l in net.layers if l.type == "SSD_SCAN"] \
            == [f"l{i}_ssd_scan" for i in MAMBA]
        widths = {l.name: l.inner_product_param.num_output
                  for l in net.layers if l.type == "INNER_PRODUCT"}
        assert widths["l0_ssd_in"] == 8512 and widths["l0_ssd_out"] == 2048 \
            and widths["l5_attn_q"] == 2048 and widths["l5_attn_k"] == 512 \
            and widths["l0_ffn_in"] == 16384 and widths["lm_head"] == 12544
    else:
        assert "--remat '/l\\d+_/,/lm_/'" in text
