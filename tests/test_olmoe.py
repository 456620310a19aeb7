"""OLMoE as layers of the Net against its plain reference (tests/olmoe_ref.py,
a copy of benchmark/reference/olmoe.py): logits, the three losses and every
parameter's gradient on seeded weights, at a small size on the CPU."""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import olmoe_ref as ref
from poseidon_tpu.config import policy_scope
from poseidon_tpu.core.net import Net
from poseidon_tpu.models import zoo
from poseidon_tpu.models.moe import _grouped, moe_dropless
from poseidon_tpu.proto.messages import load_net_from_string

SIZES = dict(n_layers=2, hidden=64, heads=4, experts=8, top_k=2,
             expert_width=32, vocab=512)
CFG = {"num_hidden_layers": 2, "num_attention_heads": 4,
       "num_experts_per_tok": 2, "rms_norm_eps": 1e-5, "rope_theta": 10000.0}
N, S = 2, 64


@pytest.fixture(scope="module")
def model():
    # through the text form: what a user's prototxt goes through
    text = zoo.to_prototxt(zoo.olmoe(batch=N, **SIZES))
    net = Net(load_net_from_string(text), "TRAIN",
              source_shapes={"tokens": (N, S), "targets": (N, S)})
    params = net.init(jax.random.PRNGKey(3))
    # norm gains off 1.0, so that a gain in the wrong place shows
    for i, (lname, lp) in enumerate(sorted(params.items())):
        if "g" in lp:
            lp["g"] = 1.0 + 0.2 * jax.random.normal(
                jax.random.PRNGKey(100 + i), lp["g"].shape)
    key = jax.random.PRNGKey(5)
    batch = {"tokens": jax.random.randint(key, (N, S), 0, SIZES["vocab"]),
             "targets": jax.random.randint(jax.random.fold_in(key, 1),
                                           (N, S), 0, SIZES["vocab"])}
    return net, params, batch


def rel(a, b):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.linalg.norm(a - b) / max(np.linalg.norm(b), 1e-30))


def test_net_matches_reference_forward(model):
    """f32 against f32: both sides sum the same products in another order,
    so 2e-4 of relative L2 on the logits (1e-5 on the losses) is summation
    noise with room; a bf16 matmul (1e-2), a renormalised top-2 (weights
    x 3) or a dropped assignment (half a token's FFN) is far outside it."""
    net, params, batch = model
    out = jax.jit(lambda p, b: net.apply(p, b, train=True,
                                         keep_blobs=True))(params, batch)
    weights = net.export_weights(params)
    want_total, parts = ref.loss(CFG, weights, batch["tokens"],
                                 batch["targets"])
    want = ref.forward(CFG, weights, batch["tokens"])
    tol = ref.TOLERANCE["f32"]
    assert rel(out.blobs["logits"], want["logits"]) < tol["logits_rel_l2"]
    assert abs(float(out.loss) - float(want_total)) \
        < tol["loss_rel"] * float(want_total)
    assert abs(float(out.outputs["lm_loss"]) - float(parts["lm"])) \
        < tol["loss_rel"] * float(parts["lm"])
    for i in range(2):
        np.testing.assert_allclose(out.outputs[f"l{i}_balance_loss"],
                                   want["balance"][i], rtol=1e-5)
        np.testing.assert_allclose(out.outputs[f"l{i}_z_loss"],
                                   want["z"][i], rtol=1e-5)
        # the step's own routing: fullest expert over the mean, none dropped
        load = np.asarray(want["tokens_per_expert"][i], np.float64)
        np.testing.assert_allclose(out.outputs[f"l{i}_expert_load"],
                                   load.max() / load.mean(), rtol=1e-6)
        assert float(out.outputs[f"l{i}_dropped"]) == 0.0


def test_net_matches_reference_gradients(model):
    """Every parameter's gradient, leaf by leaf: relative L2 under 1e-5
    (f32 summation order through two blocks of backward; the loosest leaf
    measured 5.4e-7). A wrong RoPE sign, a norm after the head split or a
    gradient through the top-k mask each put some leaf at 1e-1 or more."""
    net, params, batch = model
    got = jax.jit(jax.grad(
        lambda p: net.apply(p, batch, train=True).loss))(params)
    names = {l.name: [p.name for p in l.params] for l in net.layers
             if l.params}

    def ref_loss(weights):
        return ref.loss(CFG, weights, batch["tokens"], batch["targets"])[0]

    weights = {k: [jnp.asarray(b) for b in v]
               for k, v in net.export_weights(params).items()}
    want = jax.jit(jax.grad(ref_loss))(weights)
    checked, worst = 0, 0.0
    for lname, pnames in names.items():
        for pname, w in zip(pnames, want[lname]):
            r = rel(got[lname][pname], w)
            assert r < 1e-5, (lname, pname, r)
            checked, worst = checked + 1, max(worst, r)
    print(f"loosest leaf: {worst:.2e}")
    # embed, 2 x (8 attention-side leaves + 4 MOE blobs), final norm, head
    assert checked == sum(len(v) for v in params.values()) == 27


def test_skewed_routing_stays_dropless():
    """Every token to the same two experts: with no capacity nothing is
    dropped, so the result still equals the dense computation."""
    t, d, e, f, k = 96, 32, 8, 16, 2
    keys = jax.random.split(jax.random.PRNGKey(11), 5)
    x = jax.random.normal(keys[0], (t, d)).at[:, 0].set(5.0)
    router = jnp.zeros((e, d)).at[3, 0].set(10.0).at[6, 0].set(8.0)
    gate, up = (0.3 * jax.random.normal(kk, (e, f, d)) for kk in keys[1:3])
    down = 0.3 * jax.random.normal(keys[3], (e, d, f))
    y, lb, z, sizes = jax.jit(
        lambda *a: moe_dropless(*a, top_k=k))(x, router, gate, up, down)
    with jax.default_matmul_precision("highest"):
        y_ref, lb_ref, z_ref, load = ref.moe(x, router, gate, up, down, k)
    assert np.asarray(sizes).tolist() == [0, 0, 0, t, 0, 0, t, 0]
    assert np.asarray(load).tolist() == np.asarray(sizes).tolist()
    assert rel(y, y_ref) < 1e-5
    np.testing.assert_allclose(lb, lb_ref, rtol=1e-6)
    np.testing.assert_allclose(z, z_ref, rtol=1e-6)


# rows per group of the sorted assignments, by case: even load, every row to
# the two experts of ``test_skewed_routing_stays_dropless``, a group between
# two others with no row at all
_GROUP_ROWS = {"even": [24] * 8,
               "skewed": [0, 0, 0, 96, 0, 0, 96, 0],
               "empty_group": [10, 0, 25, 5, 0, 70, 1, 81]}


def _grouped_plain(x, w, group_sizes):
    """What ``_grouped`` computes, as a dense einsum per group over the
    stored (N, K) weights (every row against every group, all but its own
    masked out), for autodiff to differentiate."""
    rows = jnp.repeat(jnp.arange(w.shape[0]), group_sizes,
                      total_repeat_length=x.shape[0])
    mine = jax.nn.one_hot(rows, w.shape[0], dtype=x.dtype)
    return jnp.einsum("mk,gnk,mg->mn", x, w, mine,
                      precision=jax.lax.Precision.HIGHEST)


@pytest.mark.parametrize("compute", ["f32", "bf16"])
@pytest.mark.parametrize("stack", ["gate", "down"])
@pytest.mark.parametrize("case", sorted(_GROUP_ROWS))
def test_grouped_backward_matches_autodiff_of_the_plain_form(case, stack,
                                                             compute):
    """``_grouped``'s backward is written out (the weight gradient born in
    the stored orientation). At f32 it equals autodiff of the plain form
    to 1e-6 in dx and dw; under the bf16 policy it stays inside the
    tolerance ``test_bf16_policy_stays_near_reference`` uses. And dw is a
    gradient OF the stack: its shape, its dtype."""
    sizes = jnp.asarray(_GROUP_ROWS[case], jnp.int32)
    m, d, f = int(sizes.sum()), 32, 16
    k_in, n_out = (d, f) if stack == "gate" else (f, d)
    keys = jax.random.split(jax.random.PRNGKey(17), 3)
    x = jax.random.normal(keys[0], (m, k_in))
    w = 0.3 * jax.random.normal(keys[1], (8, n_out, k_in))
    ct = jax.random.normal(keys[2], (m, n_out))

    def grads(fn):
        return jax.jit(jax.grad(
            lambda x, w: jnp.sum(fn(x, w, sizes).astype(jnp.float32) * ct),
            argnums=(0, 1)))(x, w)

    want_dx, want_dw = grads(_grouped_plain)
    if compute == "f32":
        got_dx, got_dw = grads(_grouped)
        tol = 1e-6
    else:
        with policy_scope(compute_dtype=jnp.bfloat16):
            got_dx, got_dw = grads(_grouped)
        tol = ref.TOLERANCE["bf16"]["logits_rel_l2"]
    assert got_dw.shape == w.shape and got_dw.dtype == w.dtype
    assert got_dx.shape == x.shape and got_dx.dtype == x.dtype
    assert rel(got_dx, want_dx) < tol and rel(got_dw, want_dw) < tol
    # a group with no row has no gradient, exactly
    empty = np.asarray(sizes) == 0
    assert not np.asarray(got_dw)[empty].any()


def test_expert_stacks_are_transposed_in_the_forward_only():
    """Lowered, not compiled: the StableHLO of ``jax.grad(moe_dropless)``
    under the bf16 policy transposes a rank-3 array with E in front exactly
    three times, the forward's (G, K, N) views of ``gate``, ``up`` and
    ``down``. Autodiff of that forward made nine: three views again for dx,
    and each weight gradient born (G, K, N) and turned back, which on the
    v5e became twelve 537 MB relayout copies around two stacks' Adam
    fusions (PR 30)."""
    t, d, e, f, k = 96, 32, 8, 16, 2
    keys = jax.random.split(jax.random.PRNGKey(11), 5)
    x = jax.random.normal(keys[0], (t, d))
    router = jax.random.normal(keys[4], (e, d))
    gate, up = (0.3 * jax.random.normal(kk, (e, f, d)) for kk in keys[1:3])
    down = 0.3 * jax.random.normal(keys[3], (e, d, f))

    def loss(x, router, gate, up, down):
        y, lb, z, _ = moe_dropless(x, router, gate, up, down, top_k=k)
        return jnp.sum(y.astype(jnp.float32) ** 2) + lb + z

    with policy_scope(compute_dtype=jnp.bfloat16):
        text = jax.jit(jax.grad(loss, argnums=(0, 1, 2, 3, 4))).lower(
            x, router, gate, up, down).as_text()
    stacks = re.findall(
        rf"stablehlo\.transpose [^\n]*: \(tensor<{e}x(\d+)x(\d+)x(\w+)>\)",
        text)
    assert sorted(stacks) == sorted([(str(f), str(d), "bf16")] * 2
                                    + [(str(d), str(f), "bf16")]), stacks


def test_bf16_policy_stays_near_reference(model):
    """The --bf16 policy on the CPU: inside the tolerance the chip run's
    `correct` uses, and outside the f32 one (the tolerance can tell the two
    precisions apart)."""
    net, params, batch = model
    with policy_scope(compute_dtype=jnp.bfloat16):
        out = jax.jit(lambda p, b: net.apply(
            p, b, train=False, keep_blobs=True))(params, batch)
    want = ref.forward(CFG, net.export_weights(params), batch["tokens"])
    r = rel(out.blobs["logits"], want["logits"])
    assert ref.TOLERANCE["f32"]["logits_rel_l2"] < r \
        < ref.TOLERANCE["bf16"]["logits_rel_l2"]
