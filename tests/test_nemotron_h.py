"""NVIDIA-Nemotron-3-Nano-30B-A3B's layers as layers of the Net against the
plain reference (benchmark/reference/nemotron_h.py, loaded from there: one
file, no second copy), at a small size on the CPU with seeded weights: the
selective scan with G groups of B / C through its three arms (values and six
gradients; a program that would span two groups refused by name; one group
what it was, to the bit); the ungated squared-ReLU expert through
``expert_ffn``'s three arms against a dense loop (the gated arms' programs
the parent's); the grouped RMS_NORM; logits, loss and every gradient on a
pattern with all three letters; a sparse layer's shares summing to the whole
layer; what the run says it ran; the parameter counts; the example
prototxts."""

import hashlib
import importlib.util
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from poseidon_tpu.core.net import Net
from poseidon_tpu.models import moe, zoo
from poseidon_tpu.ops import ssd, ssd_pallas
from poseidon_tpu.proto.messages import (KDAParameter, MoEParameter,
                                         RMSNormParameter, load_net,
                                         load_net_from_string)

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_spec = importlib.util.spec_from_file_location(
    "nemotron_h_reference",
    os.path.join(ROOT, "benchmark", "reference", "nemotron_h.py"))
ref = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(ref)

# all three letters, two of the two that come in numbers; hidden 64, 16 scan
# heads of 8 in 4 groups with a state of 16, attention 8 / 2 heads of 16 (q
# wider than the hidden state), 16 experts of 24, 3 a token, a shared one of 40
PATTERN = "ME*ME"
SIZES = dict(pattern=PATTERN, vocab_rows=128, hidden=64, ssd_heads=16,
             ssd_head_dim=8, state=16, groups=4, heads=8, kv_heads=2,
             head_dim=16, experts=16, top_k=3, expert_width=24,
             shared_width=40, init_layers=len(PATTERN))
CFG = {"pattern": PATTERN, "mamba_num_heads": 16, "ssm_state_size": 16,
       "n_groups": 4, "num_attention_heads": 8, "num_key_value_heads": 2,
       "head_dim": 16, "num_experts": 16, "num_experts_per_tok": 3,
       "routed_scaling_factor": 2.5, "held_first": 0, "norm_eps": 1e-5,
       "bias_update_rate": 0.001}
N, S = 2, 48                      # three chunks of 16 a sequence


def build(n=N, s=S, **kw):
    # through the text form: what a user's prototxt goes through
    text = zoo.to_prototxt(zoo.nemotron_h(batch=n, **{**SIZES, **kw}))
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
    step, the gate and the routing depend on the token, a selection bias
    that chooses."""
    params = net.init(jax.random.PRNGKey(seed))
    for i, (lname, lp) in enumerate(sorted(params.items())):
        for j, (pname, w) in enumerate(sorted(lp.items())):
            noise = jax.random.normal(jax.random.PRNGKey(100 + 31 * i + j),
                                      w.shape)
            if pname in ("g", "D"):
                lp[pname] = 1.0 + 0.2 * noise
            elif pname == "bias":
                lp[pname] = 0.05 * noise
            elif pname in ("up", "down") or (
                    pname == "w" and not lname.endswith("_ssd_conv")):
                lp[pname] = 0.1 * noise
    return params


def exported(net, params):
    return {l.name: [params[l.name][p.name] for p in l.params]
            for l in net.layers if params.get(l.name)}


@pytest.fixture(scope="module")
def model():
    net = build()
    return net, seeded(net), batch_of()


def rel(a, b):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.linalg.norm(a - b) / max(np.linalg.norm(b), 1e-30))


# --------------------------------------------------------------------------- #
# the scan with groups of B / C against the recurrence
# --------------------------------------------------------------------------- #

NAMES = ("x", "dt", "B", "C", "A_log", "D")


def operands(seed, b, s, h, p, n, g, shift=-2.0):
    """dt A about -0.3 a token; b, c (B, S, G, N)."""
    r = np.random.RandomState(seed)
    dt = np.log1p(np.exp(r.randn(b, s, h) + shift))
    ops = (r.randn(b, s, h, p), dt, 0.5 * r.randn(b, s, g, n),
           0.5 * r.randn(b, s, g, n), np.log(r.uniform(1.0, 4.0, size=h)),
           1.0 + 0.3 * r.randn(h), r.randn(b, s, h, p))
    return [jnp.asarray(t, jnp.float32) for t in ops]


def value_and_grads(scan, x, dt, b, c, a_log, d, weight):
    def total(x, dt, b, c, a_log, d):
        y = scan(x, dt, -jnp.exp(a_log) * dt, b, c, d)
        return jnp.sum(y.astype(jnp.float32) * weight)
    return jax.value_and_grad(total, argnums=tuple(range(6)))(
        x, dt, b, c, a_log, d)


def close(got, want, limit=2e-4):
    assert rel(got[0], want[0]) < 1e-4
    for name, g, w in zip(NAMES, got[1], want[1]):
        assert np.linalg.norm(np.asarray(w)) > 0, name
        assert rel(g, w) < limit, (name, rel(g, w))


def written_out(x, dt, a, b, c, d):
    """The grouped recurrence with no ``vmap`` and no reshape of the heads:
    every head's own group's keys gathered, token by token."""
    per_group = x.shape[2] // b.shape[2]
    b_h, c_h = (jnp.repeat(t, per_group, 2) for t in (b, c))  # (B, S, H, N)

    def step(state, xs):
        x_t, dt_t, a_t, b_t, c_t = xs
        state = jnp.exp(a_t)[..., None, None] * state \
            + (dt_t[..., None] * x_t)[..., None] * b_t[:, :, None, :]
        return state, jnp.einsum("bhpn,bhn->bhp", state, c_t) \
            + d[:, None] * x_t

    s0 = jnp.zeros(x.shape[:1] + x.shape[2:] + b.shape[-1:], jnp.float32)
    _, y = jax.lax.scan(step, s0, tuple(t.swapaxes(0, 1)
                                        for t in (x, dt, a, b_h, c_h)))
    return y.swapaxes(0, 1)


@pytest.mark.parametrize("groups", [1, 2, 8])
def test_recurrence_and_chunked_scan_read_a_head_s_own_group(groups):
    """S = 80 in five chunks of 16, 8 heads of 12 with a state of 20: the
    recurrence against the loop written out with every head's group
    gathered, the chunked form against the recurrence, y and six gradients
    (d B and d C are then sums over a group's heads alone)."""
    ops = operands(1, 2, 80, 8, 12, 20, groups)
    want = value_and_grads(written_out, *ops)
    close(value_and_grads(ssd.ssd_recurrence, *ops), want)
    close(value_and_grads(ssd.ssd_scan, *ops), want)
    assert want[1][2].shape == (2, 80, groups, 20)


# (groups, heads, P): eight groups of eight heads of 16 (Nemotron's 64 / 8: a
# group a program); two groups of sixteen heads of 32 (two programs share a
# group's d B / d C block, the next group's start afresh); one group handed
# in the grouped form
PALLAS = {"g8": (8, 64, 16), "g2": (2, 32, 32), "g1": (1, 16, 64)}


@pytest.mark.parametrize("shape", sorted(PALLAS))
def test_pallas_kernels_interpreted_take_groups(shape):
    g, h, p = PALLAS[shape]
    assert not ssd_pallas.ssd_refusal(256, h, p, 128, g)
    assert ssd_pallas.padded_heads(h, p) == h
    ops = operands(3, 1, 256, h, p, 128, g)
    scan = lambda *t: ssd_pallas.ssd_scan_pallas(*t, 128, True)
    got = value_and_grads(scan, *ops)
    close(got, value_and_grads(ssd.ssd_recurrence, *ops))
    close(got, value_and_grads(lambda *t: ssd.ssd_scan(*t, chunk=64), *ops))


def test_one_group_is_granite_s_program_to_the_bit():
    """b, c (B, S, 1, N) through the kernels = b, c (B, S, N) through them,
    bit for bit, forward and the six gradients: the index maps pick block 0
    either way; and the layer hands one group over in the old form."""
    ops = operands(4, 1, 256, 16, 64, 128, 1)
    flat = [t[:, :, 0] if i in (2, 3) else t for i, t in enumerate(ops)]
    scan = lambda *t: ssd_pallas.ssd_scan_pallas(*t, 128, True)
    got, want = value_and_grads(scan, *ops), value_and_grads(scan, *flat)
    assert np.array_equal(got[0], want[0])
    for g, w in zip(got[1], want[1]):
        assert np.array_equal(np.asarray(g).reshape(w.shape), w)
    # the chunked form under vmap over ONE group: the same sums
    chunked = lambda *t: ssd.ssd_scan(*t, chunk=64)
    got, want = value_and_grads(chunked, *ops), value_and_grads(chunked, *flat)
    close((got[0], [g.reshape(w.shape) for g, w in zip(got[1], want[1])]),
          want, 1e-6)


def test_a_program_over_two_groups_is_refused_by_name(monkeypatch):
    # 8 heads of 64 are ONE program; two groups of four would share it
    why = ssd_pallas.ssd_refusal(256, 8, 64, 128, 2)
    assert "a program would span two groups" in why \
        and why.startswith("8 heads in 2 groups of B / C")
    assert ssd_pallas.ssd_blocks(256, 8, 64, 128, 2) is None
    assert ssd_pallas.ssd_blocks(256, 8, 64, 128, 1) == 256
    assert "no whole programs" in ssd_pallas.ssd_refusal(256, 12, 64, 128, 8)
    monkeypatch.setenv("POSEIDON_FORCE_PALLAS", "1")
    arm, note = ssd.ssd_route(256, 8, 64, 128, 4, 2)
    assert arm == "chunked" and "not pallas: 8 heads in 2 groups" in note \
        and note.endswith("; groups=2")
    # the published shape: 64 heads of 64 in 8 groups, a group a program
    arm, note = ssd.ssd_route(8192, 64, 64, 128, 2, 8)
    assert arm == "pallas" and note.endswith(
        "passes 0.47 / 0.47 of six a product); groups=8")
    assert ssd.ssd_route(8192, 64, 64, 128, 2) \
        == ssd.ssd_route(8192, 64, 64, 128, 2, 1)       # one group: unsaid
    assert ssd.state_bytes(1, 8192, 64, 64, 128, 8) \
        == 32 * 64 * 64 * 128 * 4
    # and the refused shape runs the chunked arm
    ops = operands(6, 1, 256, 8, 64, 128, 2)
    close(value_and_grads(ssd.ssd_scan, *ops),
          value_and_grads(ssd.ssd_recurrence, *ops))


# --------------------------------------------------------------------------- #
# the ungated expert through expert_ffn's arms
# --------------------------------------------------------------------------- #

def dense_loop(x, weights, experts, gate, up, down, held_first, act):
    """Every token through every held expert, weighed by its router weight
    where it chose that expert."""
    y = jnp.zeros_like(x)
    for j in range(up.shape[0]):
        w = jnp.sum(jnp.where(experts == held_first + j, weights, 0.0), 1)
        a = x @ up[j].T
        if gate is None:
            h = jnp.square(jax.nn.relu(a))
        else:
            h = (jax.nn.silu if act == "silu" else jax.nn.relu)(
                x @ gate[j].T) * a
        y = y + w[:, None] * (h @ down[j].T)
    return y


def routed(seed, t, d, f, n_exp, top_k, n_held):
    r = np.random.RandomState(seed)
    f32 = lambda a: jnp.asarray(a, jnp.float32)
    experts = jnp.asarray(np.stack(
        [r.permutation(n_exp)[:top_k] for _ in range(t)]), jnp.int32)
    return (f32(r.randn(t, d)), f32(r.uniform(0.1, 1.0, (t, top_k))),
            experts, f32(0.3 * r.randn(n_held, f, d)),
            f32(0.3 * r.randn(n_held, f, d)),
            f32(0.3 * r.randn(n_held, d, f)))


# (experts, held, held_first, the chunk rule's floor): every expert held;
# half held, two chunks, straight-line rows; a quarter held from the
# fourth on, the loop whose trip count is the live rows'
ARMS = {"all": (8, 8, 0, None), "rows": (8, 4, 0, None),
        "chunks": (16, 4, 4, 128)}


@pytest.mark.parametrize("arm", sorted(ARMS))
def test_ungated_expert_equals_the_dense_loop_in_every_arm(arm, monkeypatch):
    n_exp, n_held, first, floor = ARMS[arm]
    if floor:
        monkeypatch.setattr(moe, "_CHUNK_FLOOR", floor)
    t, top_k = 256, 4
    x, weights, experts, _, up, down = routed(7, t, 32, 24, n_exp, top_k,
                                              n_held)
    plan = moe.held_rows_plan(t * top_k, n_held, n_exp)
    assert (plan is not None) == (arm == "chunks")
    flat_e, sizes = moe.expert_sizes(experts, n_exp)

    def program(x, weights, up, down):
        y, zeros = moe.expert_ffn(x, weights, flat_e, sizes, None, up, down,
                                  first, "relu2", True)
        return jnp.sum(y * jnp.cos(jnp.arange(y.size).reshape(y.shape))), \
            (y, zeros)

    def reference(x, weights, up, down):
        y = dense_loop(x, weights, experts, None, up, down, first, "relu2")
        return jnp.sum(y * jnp.cos(jnp.arange(y.size).reshape(y.shape))), y

    with jax.default_matmul_precision("highest"):
        (_, (y, zeros)), got = jax.value_and_grad(
            program, argnums=(0, 1, 2, 3), has_aux=True)(x, weights, up, down)
        (_, want_y), want = jax.value_and_grad(
            reference, argnums=(0, 1, 2, 3), has_aux=True)(
                x, weights, up, down)
    assert rel(y, want_y) < 1e-5
    for name, g, w in zip(("x", "weights", "up", "down"), got, want):
        assert np.linalg.norm(np.asarray(w)) > 0 and rel(g, w) < 1e-5, name
    # the counter: the share of the live rows' up pre-activations <= 0
    here = (experts >= first) & (experts < first + n_held)
    pre = jnp.einsum("td,efd->tef", x, up)              # (T, held, F)
    chose = jnp.any(experts[:, :, None] == first + jnp.arange(n_held), 1)
    want_zeros = jnp.sum((pre <= 0) & chose[..., None]) \
        / (jnp.sum(here) * up.shape[1])
    assert abs(float(zeros) - float(want_zeros)) < 1e-6
    assert 0.3 < float(zeros) < 0.7


def test_an_act_and_a_gate_that_do_not_belong_together_are_refused():
    x, weights, experts, gate, up, down = routed(8, 16, 8, 8, 4, 2, 4)
    flat_e, sizes = moe.expert_sizes(experts, 4)
    with pytest.raises(ValueError, match="takes no gate stack"):
        moe.expert_ffn(x, weights, flat_e, sizes, gate, up, down,
                       act="relu2")
    with pytest.raises(ValueError, match="a gated unit's and needs one"):
        moe.expert_ffn(x, weights, flat_e, sizes, None, up, down, act="silu")
    assert moe.EXPERT_ACTS == ("silu", "relu", "relu2")
    net = zoo.nemotron_h(batch=1, **SIZES)
    for layer in net.layers:
        if layer.type == "MOE":
            layer.moe_param.activation = "gelu"
    with pytest.raises(ValueError, match="relu2: the ungated squared ReLU"):
        Net(net, "TRAIN", source_shapes={"tokens": (1, 16),
                                         "targets": (1, 16)})


# sha256 of the gradient's jaxpr, its length: taken on the PARENT of PR 64
# (commit 86976b2), equal on the change. tests/test_token_step_text.py holds
# the straight-line arms of the gated unit; this holds the LOOP (the arm the
# six held cells run), with the zero counter as SmallThinker has it.
GATED_LOOP = {
    "silu": ("e0d1467f2dd02785f8aade2b9f3de9a993bbfadfe9221dbe34da90c7ff6f"
             "ce05", 244945),
    "relu": ("126035cd5d86444d6a8919d6acf243c3908712eb86cb8ebac7dcec2e1472"
             "e949", 247055),
}


@pytest.mark.parametrize("act", sorted(GATED_LOOP))
def test_the_gated_loop_traces_to_the_parent_s_program(act, monkeypatch):
    monkeypatch.setenv("POSEIDON_FORCE_PALLAS", "1")
    monkeypatch.setattr(moe, "_CHUNK_FLOOR", 128)
    n, s = 1, 256
    proto = zoo.trinity_mini(
        batch=n, n_layers=3, dense_layers=1, hidden=64, heads=4, kv_heads=2,
        head_dim=16, window=32, first_global=2, dense_width=96, experts=16,
        top_k=4, held=2, expert_width=32, shared_width=32, vocab=128)
    for layer in proto.layers:
        if layer.type == "MOE":
            layer.moe_param.activation = act
            layer.top.append(layer.name + "_gz")
    net = Net(load_net_from_string(zoo.to_prototxt(proto)), "TRAIN",
              source_shapes={"tokens": (n, s), "targets": (n, s)})
    params = jax.eval_shape(net.init, jax.random.PRNGKey(0))
    batch = {k: jax.ShapeDtypeStruct((n, s), jnp.int32)
             for k in ("tokens", "targets")}
    text = re.sub(r"0x[0-9a-f]+", "0x", str(jax.make_jaxpr(jax.grad(
        lambda p, b: net.apply(p, b, train=True).loss))(params, batch)))
    assert (hashlib.sha256(text.encode()).hexdigest(), len(text)) \
        == GATED_LOOP[act]


# --------------------------------------------------------------------------- #
# the layers' new fields
# --------------------------------------------------------------------------- #

def test_grouped_rms_norm_normalises_each_group_under_one_whole_gain():
    text = """
      input: "x" input_dim: 2 input_dim: 5 input_dim: 1 input_dim: 24
      layers { name: "n" type: RMS_NORM bottom: "x" top: "y"
               rms_norm_param { eps: 1e-5 num_groups: 4 } }"""
    net = Net(load_net_from_string(text), "TRAIN")
    params = net.init(jax.random.PRNGKey(0))
    assert params["n"]["g"].shape == (24,)          # NOT a group's width
    params["n"]["g"] = jnp.linspace(0.5, 1.5, 24)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 5, 1, 24)) \
        * jnp.repeat(jnp.array([1.0, 10.0, 0.1, 3.0]), 6)
    y = net.apply(params, {"x": x}, train=False, keep_blobs=True).blobs["y"]
    split = np.asarray(x, np.float64).reshape(2, 5, 1, 4, 6)
    want = split / np.sqrt((split ** 2).mean(-1, keepdims=True) + 1e-5)
    np.testing.assert_allclose(
        y, want.reshape(2, 5, 1, 24) * np.linspace(0.5, 1.5, 24), rtol=2e-6)
    assert rel(y, ref.rms_norm(x, params["n"]["g"], 1e-5, 4)) < 1e-6
    both = text.replace("num_groups: 4", "num_groups: 4 num_heads: 2")
    with pytest.raises(ValueError, match="num_heads is set beside them"):
        Net(load_net_from_string(both), "TRAIN")
    with pytest.raises(ValueError, match="5 groups do not split"):
        Net(load_net_from_string(text.replace("groups: 4", "groups: 5")),
            "TRAIN")


def test_new_fields_at_their_defaults_are_not_written():
    """An accepted configuration's prototxt stays byte for byte: a field at
    its default leaves no text, and comes back as the default."""
    assert (KDAParameter().num_groups, RMSNormParameter().num_groups,
            MoEParameter().activation) == (1, 0, "silu")
    text = zoo.to_prototxt(zoo.granite_hybrid(
        batch=1, layers=2, vocab_rows=128, hidden=64, heads=2, kv_heads=1,
        ssd_heads=4, ssd_head_dim=8, state=16, ffn_width=32))
    assert "num_groups" not in text and "activation" not in text
    back = load_net_from_string(text)
    scan = next(l for l in back.layers if l.type == "SSD_SCAN")
    assert scan.kda_param.num_groups == 1
    assert zoo.to_prototxt(back) == text
    text = zoo.to_prototxt(zoo.nemotron_h(batch=1, **SIZES))
    assert text.count("num_groups: 4") == 2 * PATTERN.count("M") \
        and text.count('activation: "relu2"') == PATTERN.count("E")
    assert zoo.to_prototxt(load_net_from_string(text)) == text


# --------------------------------------------------------------------------- #
# the Net against the reference
# --------------------------------------------------------------------------- #

def test_leaves_scopes_and_routes(model):
    net, params, _ = model
    inner, bc = 16 * 8, 4 * 16
    assert params["l0_ssd_in"]["w"].shape == (2 * inner + 2 * bc + 16, 64)
    assert params["l0_ssd_conv"]["w"].shape == (4, inner + 2 * bc)
    assert params["l0_ssd_onorm"]["g"].shape == (inner,)
    assert params["l2_attn_q"]["w"].shape == (8 * 16, 64)   # wider than 64
    assert params["l2_attn_k"]["w"].shape == (2 * 16, 64)
    assert sorted(params["l1_moe_experts"]) == ["down", "up"]   # no gate
    assert params["l1_moe_experts"]["up"].shape == (16, 24, 64)
    assert params["l1_moe_router"]["w"].shape == (16, 64)
    assert params["l1_moe_shared_up"]["w"].shape == (40, 64)
    types = {l.name: l.TYPE for l in net.layers}
    for i, letter in enumerate(PATTERN):
        kind = {"M": "ssd_scan", "E": "moe_experts", "*": "attn_sdpa"}
        assert f"l{i}_{kind[letter]}" in types
        assert types[f"l{i}_norm"] == "RMS_NORM" \
            and types[f"l{i}_res"] == "ELTWISE"
    assert [types[f"l1_moe_shared_{t}"] for t in ("up", "relu", "sq",
                                                   "down")] \
        == ["INNER_PRODUCT", "RELU", "POWER", "INNER_PRODUCT"]
    assert net.kernel_routes["l0_ssd_scan"] == (
        "ssd_scan=chunked Q 16, 3 chunks, f32 state, one C B^T grid a "
        "chunk; not pallas: neither 256 nor 128 divides S=48; groups=4")
    assert net.kernel_routes["l1_moe_experts"] == \
        "grouped_matmul=ragged_dot; act=relu2; ungated"
    assert net.kernel_routes["l2_attn_sdpa"] == \
        "attention=dense; 2 kv heads repeated x4; no positions"
    assert net.layer_facts()["recurrent_state"]["l3_ssd_scan"] == {
        "heads": 16, "d_k": 16, "d_v": 8, "chunk": 16, "chunks": 3,
        "decay": "head", "groups": 4,
        "saved_state_bytes": N * 16 * 3 * 8 * 16 * 4}
    assert sorted(net.layer_facts()["recurrent_state"]) \
        == ["l0_ssd_scan", "l3_ssd_scan"]
    mults = {l.name: {p.name: (p.lr_mult, p.decay_mult) for p in l.params}
             for l in net.layers if l.params}
    assert mults["l1_moe_router"] == {"w": (1.0, 1.0), "bias": (1.0, 0.0)}
    assert mults["l0_ssd_conv"] == {"w": (1.0, 1.0), "b": (1.0, 0.0)}
    assert mults["l1_moe_experts"] == {"up": (1.0, 1.0), "down": (1.0, 1.0)}


def test_net_equals_the_reference_logits_loss_counters_and_gradients(model):
    """f32 on the CPU, the same products in another order: logits and loss
    to 2e-5, every leaf's gradient to 2e-4 of its norm (the selection biases
    take none on either side)."""
    net, params, batch = model
    weights = exported(net, params)

    def program(p):
        out = net.apply(p, batch, train=True, keep_blobs=True)
        return out.loss, out

    with jax.default_matmul_precision("highest"):
        (total, out), grads = jax.value_and_grad(program, has_aux=True)(
            params)
    (want_total, want), want_grads = jax.value_and_grad(
        lambda w: ref.loss(CFG, w, batch["tokens"], batch["targets"]),
        has_aux=True)(weights)
    assert rel(out.blobs["logits"], want["logits"]) < 2e-5
    assert abs(float(total) - float(want_total)) < 2e-5 * float(want_total)
    scalars = {k: float(v) for k, v in out.blobs.items() if v.ndim == 0}
    for j, i in enumerate(i for i, t in enumerate(PATTERN) if t == "M"):
        assert abs(scalars[f"l{i}_ssd_decay_mean"]
                   - float(want["decay_mean"][j])) < 1e-5
        assert abs(scalars[f"l{i}_ssd_dt_mean"]
                   - float(want["dt_mean"][j])) < 1e-5
    for i in (i for i, t in enumerate(PATTERN) if t == "E"):
        assert scalars[f"l{i}_held_share"] == 1.0      # every expert held
        assert 0.2 < scalars[f"l{i}_act_zero_share"] < 0.8
    got_grads = exported(net, grads)
    checked = 0
    for name, blobs in want_grads.items():
        for j, w in enumerate(blobs):
            if name.endswith("_moe_router") and j == 1:
                assert not np.any(np.asarray(w))        # the bias: none
                continue
            assert np.linalg.norm(np.asarray(w)) > 0, (name, j)
            assert rel(got_grads[name][j], w) < 2e-4, (name, j)
            checked += 1
    # embed, head, final norm; a norm a layer; M: in, conv x2, decay x2, D,
    # onorm, out; *: q k v o; E: router w, up, down, shared up, down
    assert checked == 3 + len(PATTERN) + 2 * 8 + 4 + 2 * 5


def test_the_shares_of_a_sparse_layer_sum_to_the_whole_layer(model):
    """Four ranks of four experts each: the routed parts the four shares
    give on ONE input, program and reference alike, sum to the uncut
    layer's routed part, and with the shared expert counted once to the
    whole sub-layer."""
    net, params, batch = model
    weights = exported(net, params)
    whole = ref.forward(CFG, weights, batch["tokens"], upto=2)
    total = np.zeros(np.asarray(whole["routed"]).shape)
    for first in range(0, 16, 4):
        share = build(held=4, held_first=first)
        cut = {name: dict(lp) for name, lp in params.items()}
        for name in ("l1_moe_experts", "l4_moe_experts"):
            cut[name] = {k: v[first:first + 4]
                         for k, v in params[name].items()}
        with jax.default_matmul_precision("highest"):
            out = share.apply(cut, batch, train=False, keep_blobs=True)
        # layer 1's input is the same on every rank (layer 0 has no experts)
        part = ref.forward({**CFG, "held_first": first}, exported(share, cut),
                           batch["tokens"], upto=2)
        assert rel(out.blobs["l1_m"], part["routed"]) < 2e-5
        assert rel(out.blobs["l1_sd"], whole["shared"]) < 2e-5
        assert 0.0 < float(out.blobs["l1_held_share"]) < 1.0
        total += np.asarray(out.blobs["l1_m"], np.float64)
    assert rel(total, whole["routed"]) < 2e-5
    assert rel(total + np.asarray(whole["shared"]),
               np.asarray(whole["routed"]) + np.asarray(whole["shared"])) \
        < 2e-5


def test_reference_train_step_moves_the_biases_by_the_sign_rule(model):
    net, params, batch = model
    weights = exported(net, params)
    opt = {"rate": {n: [1e-3] * len(b) for n, b in weights.items()},
           "decay": {n: [0.0] * len(b) for n, b in weights.items()},
           "clip": 1.0, "b1": 0.9, "b2": 0.95, "eps": 1e-8}
    out = ref.train_step(CFG, weights, batch["tokens"], batch["targets"],
                         opt)
    counts = np.asarray(out["counts"])
    assert counts.shape == (2, 16) and np.all(counts.sum(1) == N * S * 3)
    even = N * S * 3 / 16
    for name, n_e in zip(("l1_moe_router", "l4_moe_router"), counts):
        np.testing.assert_allclose(out["change"][name][1],
                                   0.001 * np.sign(even - n_e))
    # the program's next bias: the same rule on its own counts
    got = net.apply(params, batch, train=True, keep_blobs=True).blobs
    np.testing.assert_allclose(
        got["l1_bias_next"] - params["l1_moe_router"]["bias"],
        out["change"]["l1_moe_router"][1], atol=1e-7)


# --------------------------------------------------------------------------- #
# the published model, the cut, the example files
# --------------------------------------------------------------------------- #

def count(net_param, s=64):
    net = Net(net_param, "TRAIN", source_shapes={"tokens": (1, s),
                                                 "targets": (1, s)})
    shapes = jax.eval_shape(net.init, jax.random.PRNGKey(0))
    by_layer = {name: sum(int(np.prod(v.shape)) for v in lp.values())
                for name, lp in shapes.items()}
    biases = sum(int(np.prod(lp["bias"].shape)) for name, lp in shapes.items()
                 if name.endswith("_moe_router"))
    return by_layer, sum(by_layer.values()) - biases, biases


def test_parameter_counts_of_the_cut_and_of_the_published_model():
    by_layer, trained, biases = count(zoo.nemotron_h(
        batch=1, pattern="MEMEM*EME", held=8, vocab_rows=16384))
    layer = lambda i: sum(v for k, v in by_layer.items()
                          if k.startswith(f"l{i}_"))
    assert layer(0) == 38_744_896                      # Mamba-2
    assert layer(1) - 128 == 100_125_312               # sparse, 8 held
    assert layer(5) == 23_399_040                      # attention
    assert by_layer["embed"] + by_layer["lm_head"] \
        + by_layer["final_norm"] == 88_083_072
    # ISSUE 64's count, to the unit; beside it the four selection biases,
    # layer-updated leaves that no optimizer state stands behind
    assert (trained, biases) == (666_962_944, 512)
    _, trained, biases = count(zoo.nemotron_h(batch=1))
    assert (trained, biases) == (31_577_937_344, 23 * 128)


def test_example_prototxts_are_the_generator_s_and_the_benchmark_s():
    text = zoo.to_prototxt(zoo.nemotron_h(
        batch=2, pattern="MEMEM*EME", held=8, vocab_rows=16384))
    for name in ("train", "solver"):
        with open(os.path.join(
                ROOT, "examples", "lm",
                f"nemotron_3_nano_30b_a3b_{name}.prototxt")) as f:
            example = f.read()
        with open(os.path.join(
                ROOT, "benchmark", "configs", "nemotron_3_nano_30b_a3b",
                f"{name}.prototxt")) as f:
            assert f.read() == example, name
        if name == "train":
            body = example[example.index("name: "):]
            assert body == text
    load_net(os.path.join(ROOT, "examples", "lm",
                          "nemotron_3_nano_30b_a3b_train.prototxt"))
