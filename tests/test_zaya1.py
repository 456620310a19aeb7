"""ZAYA1's block as layers of the Net against its plain reference
(benchmark/reference/zaya1.py, loaded from there: one file, no second copy),
at a small size on the CPU with seeded weights: logits, loss and every
gradient; the two expert shares summing to the whole layer; the causal
convolutions and shift; the tied table's gradient; grouped-query flash
attention against the dense op; and the router's selection bias, a leaf its
layer updates, through Engine steps of ADAM + decay + clip."""

import importlib.util
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from poseidon_tpu.core.net import Net
from poseidon_tpu.models import zoo
from poseidon_tpu.parallel.mesh import make_mesh
from poseidon_tpu.proto.messages import load_net_from_string

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_spec = importlib.util.spec_from_file_location(
    "zaya1_reference",
    os.path.join(ROOT, "benchmark", "reference", "zaya1.py"))
ref = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(ref)

L, E, HELD = 2, 8, 4
SIZES = dict(n_layers=L, hidden=64, heads=4, kv_heads=2, head_dim=16,
             experts=E, expert_width=32, router_hidden=16, vocab=128)
CFG = {"num_hidden_layers": L, "num_attention_heads": 4,
       "num_key_value_heads": 2, "num_experts": E, "rms_norm_eps": 1e-5,
       "rope_theta": 5e6, "rotary_dims": 8}
N, S = 2, 64
RATE = 0.001
REMAT = [r"/l\d+_/", r"/lm_/"]   # what the example solver's header names


def build(held=HELD, held_first=0, n=N, s=S, tied=True, **kw):
    # through the text form: what a user's prototxt goes through
    text = zoo.to_prototxt(zoo.zaya1(batch=n, held=held,
                                     held_first=held_first,
                                     **{**SIZES, **kw}))
    if not tied:
        text = text.replace('  param {\n    name: "tok_w"\n  }\n', "")
    return Net(load_net_from_string(text), "TRAIN",
               source_shapes={"tokens": (n, s), "targets": (n, s)})


def batch_of(n=N, s=S, seed=5):
    key = jax.random.PRNGKey(seed)
    return {"tokens": jax.random.randint(key, (n, s), 0, SIZES["vocab"]),
            "targets": jax.random.randint(jax.random.fold_in(key, 1),
                                          (n, s), 0, SIZES["vocab"])}


def seeded(net, seed=3):
    """Fresh weights, then everything a fresh model has at a trivial value
    moved off it, so that a gain, a bias, ``tau``, the router's ``mix`` or
    the selection bias in the wrong place shows; the router's matrices
    larger, so that its choices are not all near-ties."""
    params = net.init(jax.random.PRNGKey(seed))
    for i, (lname, lp) in enumerate(sorted(params.items())):
        for j, (pname, w) in enumerate(sorted(lp.items())):
            key = jax.random.PRNGKey(100 + 31 * i + j)
            noise = jax.random.normal(key, w.shape)
            if pname in ("g", "tau"):
                lp[pname] = 1.0 + 0.2 * noise
            elif pname in ("dw_b", "gw_b", "mix"):
                lp[pname] = 0.3 * noise
            elif pname == "bias":
                lp[pname] = 0.02 * noise
            elif lname.endswith("_router") or lname.endswith("_cca_conv"):
                lp[pname] = 0.5 * noise
    return params


@pytest.fixture(scope="module")
def model():
    net = build()
    return net, seeded(net), batch_of()


def rel(a, b):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.linalg.norm(a - b) / max(np.linalg.norm(b), 1e-30))


def test_leaves_shares_and_routes(model):
    net, params, _ = model
    # embed (tied with the head); per layer 2 gains, q k v1 v2 o, 4 conv
    # blobs, tau, 3 stacks, the router's 5 (6 behind the first layer)
    assert sum(len(v) for v in params.values()) \
        == 1 + L * (2 + 5 + 4 + 1 + 3 + 5) + (L - 1) + 1
    assert net.shared_params == {"tok_w": {"owner": "embed/w", "uses": 2}}
    assert "lm_head" not in params and params["l0_moe"]["gate"].shape \
        == (HELD, 32, 64) and params["l0_router"]["w3"].shape == (E, 16)
    assert net.layer_updates == {("l0_router", "bias"): "l0_bias_next",
                                 ("l1_router", "bias"): "l1_bias_next"}
    assert "l0_bias_next" not in net.output_names
    assert net.layer_facts()["expert_share"]["l1_moe"] == {
        "held_first": 0, "num_held": HELD, "router_num_experts": E}
    assert net.kernel_routes["l0_attn"].endswith("2 kv heads repeated x2")


def test_net_matches_reference_forward(model):
    """f32 against f32: the same products summed in another order, so 2e-4
    of relative L2 on the logits and 1e-5 on the loss are summation noise
    with room. A key-value head read by the wrong query heads, the shifted
    half of v on the wrong head, rotary positions over the whole head, a
    missing tau or the q-k mean of another group each move the logits by
    1e-2 or more (tried by hand while writing this)."""
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
    assert float(out.outputs["lm_loss"]) == float(out.loss)
    for i in range(L):
        chosen = np.argmax(np.asarray(out.blobs[f"l{i}_gates"]), -1)
        np.testing.assert_array_equal(chosen, want["choice"][i])
        counts = np.asarray(want["counts"][i])
        # the routing is not degenerate: several experts, held and absent
        assert (counts > 0).sum() >= 4 and 0 < counts[:HELD].sum() < N * S
        np.testing.assert_allclose(out.outputs[f"l{i}_held_share"],
                                   counts[:HELD].sum() / (N * S), rtol=1e-6)
        np.testing.assert_allclose(
            out.outputs[f"l{i}_expert_load"],
            counts[:HELD].max() * HELD / counts[:HELD].sum(), rtol=1e-6)
        assert float(out.outputs[f"l{i}_dropped"]) == 0.0
        np.testing.assert_allclose(
            out.updates[f"l{i}_router"]["bias"],
            ref.next_bias(weights[f"l{i}_router"][-1], counts, RATE),
            rtol=0, atol=1e-7)
        assert rel(out.blobs[f"l{i}_m"], want["moe"][i]) < 2e-5


def test_net_matches_reference_gradients(model):
    """Every leaf's gradient: relative L2 under 2e-5 (f32 summation order
    through two blocks of backward); the tied table's is one leaf. The
    selection bias takes none, on either side."""
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
            assert rel(got[lname][pname], g) < 2e-5, (lname, pname)
            n += 1
    assert n == sum(len(v) for v in params.values()) - L


def test_the_two_shares_add_up_to_the_whole_layer():
    """One layer's MoE output with experts 0..3 held plus that with 4..7
    equals the uncut reference's (nothing is computed on both, so a plain
    sum), and equals the program's with all eight."""
    whole = build(held=0, n_layers=1)
    params = seeded(whole)
    batch = batch_of()
    stacks = params["l0_moe"]
    blobs = {}
    for first in (0, HELD):
        net = build(held=HELD, held_first=first, n_layers=1)
        share = {**params, "l0_moe": {k: v[first:first + HELD]
                                      for k, v in stacks.items()}}
        out = jax.jit(lambda p, b, net=net: net.apply(
            p, b, train=True, keep_blobs=True))(share, batch)
        blobs[first] = out.blobs
        want = ref.forward({**CFG, "num_hidden_layers": 1},
                           net.export_weights(share), batch["tokens"],
                           held=range(first, first + HELD))
        assert rel(out.blobs["l0_m"], want["moe"][0]) < 2e-5
    uncut = ref.forward({**CFG, "num_hidden_layers": 1},
                        whole.export_weights(params), batch["tokens"])
    both = np.asarray(blobs[0]["l0_m"]) + np.asarray(blobs[HELD]["l0_m"])
    assert rel(both, uncut["moe"][0]) < 2e-5
    # a token is computed by exactly one of the two shares
    zero = [np.all(np.asarray(blobs[f]["l0_m"]) == 0, -1) for f in (0, HELD)]
    np.testing.assert_array_equal(zero[0], ~zero[1])
    full = jax.jit(lambda p, b: whole.apply(p, b, train=True,
                                            keep_blobs=True))(params, batch)
    assert rel(full.blobs["l0_m"], both) < 2e-5
    assert float(full.outputs["l0_held_share"]) == 1.0


def test_convolutions_and_shift_do_not_look_ahead(model):
    """Perturb token t: nothing the latent layers or the whole block make
    before t moves (the convolutions and the shift reach one position
    back, never forward), and what they make AT t + 1 does."""
    net, params, batch = model
    t = 23
    other = dict(batch, tokens=batch["tokens"].at[:, t].set(
        (batch["tokens"][:, t] + 1) % SIZES["vocab"]))
    run = jax.jit(lambda b: net.apply(params, b, train=True,
                                      keep_blobs=True).blobs)
    a, b = run(batch), run(other)
    for blob in ("l0_as", "l0_v", "l0_qc", "l0_kc", "l0_qn", "l0_kn",
                 "l0_att", "l1_r", "l1_y", "logits"):
        x, y = np.asarray(a[blob]), np.asarray(b[blob])
        np.testing.assert_array_equal(x[:, :t], y[:, :t], err_msg=blob)
        assert np.any(x[:, t + 1] != y[:, t + 1]), blob
    # the shift is exactly one position; layer 0's two convolutions reach
    # one position back each, so t shows at t, t + 1, t + 2 and no further
    np.testing.assert_array_equal(np.asarray(a["l0_as"])[:, 1:],
                                  np.asarray(a["l0_a"])[:, :-1])
    assert not np.any(np.asarray(a["l0_as"])[:, 0])
    for blob in ("l0_qc", "l0_kc"):
        assert np.any(np.asarray(a[blob])[:, t] != np.asarray(b[blob])[:, t])
        assert np.any(np.asarray(a[blob])[:, t + 2]
                      != np.asarray(b[blob])[:, t + 2])
        np.testing.assert_array_equal(np.asarray(a[blob])[:, t + 3:],
                                      np.asarray(b[blob])[:, t + 3:])


def test_tied_table_gradient_is_lookup_plus_head(model):
    """The embedding's table is the head's: one leaf, two uses of different
    layer types (a gather and a matmul). Its gradient is the sum of the
    two leaves' gradients of the same net untied at the same weights."""
    net, params, batch = model
    tied = jax.jit(jax.grad(
        lambda p: net.apply(p, batch, train=True).loss))(params)
    loose = build(tied=False)
    assert not loose.shared_params
    both = {**params, "lm_head": {"w": params["embed"]["w"]}}
    g = jax.jit(jax.grad(
        lambda p: loose.apply(p, batch, train=True).loss))(both)
    assert rel(tied["embed"]["w"],
               np.asarray(g["embed"]["w"]) + np.asarray(g["lm_head"]["w"])) \
        < 1e-6
    assert np.linalg.norm(np.asarray(g["embed"]["w"])) > 0 \
        and np.linalg.norm(np.asarray(g["lm_head"]["w"])) > 0


@pytest.mark.parametrize("what", ["forward", "dq", "dk", "dv"])
def test_grouped_query_flash_matches_dense(what):
    """8 query / 2 key-value heads with rotary positions on half a head
    through the flash kernels (interpret mode) against the dense op: the
    forward and all three gradients, k's and v's summed over the four
    query heads that read them."""
    from poseidon_tpu.models import transformer as tr
    from poseidon_tpu.ops import pallas_kernels as pk
    from poseidon_tpu.ops.attention import attention
    b, s, h, g, d = 1, 256, 8, 2, 32
    key = jax.random.PRNGKey(11)
    q = jax.random.normal(key, (b, s, h * d))
    k = jax.random.normal(jax.random.fold_in(key, 1), (b, s, g * d))
    v = jax.random.normal(jax.random.fold_in(key, 2), (b, s, g * d))
    co = jax.random.normal(jax.random.fold_in(key, 3), (b, s, h * d))

    def through(flash):
        def att(q_, k_, v_, causal, scale=None, window=None):
            if flash:
                return pk.flash_attention(q_, k_, v_, causal, scale, 64, 64,
                                          True)
            return attention(q_, k_, v_, causal=causal, scale=scale)

        def f(q_, k_, v_):
            old, tr.maybe_flash_attention = tr.maybe_flash_attention, att
            try:
                return tr.rope_attention(q_, k_, v_, h, 5e6, g, d // 2)
            finally:
                tr.maybe_flash_attention = old
        return f

    if what == "forward":
        got, want = through(True)(q, k, v), through(False)(q, k, v)
    else:
        i = ("dq", "dk", "dv").index(what)
        got, want = (jax.grad(lambda *a, f=f: jnp.sum(f(*a) * co),
                              argnums=i)(q, k, v)
                     for f in (through(True), through(False)))
    assert got.shape == want.shape and rel(got, want) < 2e-5
    # against the reference's attention too: the group a query head reads
    if what == "forward":
        one = ref.attention(
            ref.rope(q[0].reshape(s, h, d), 5e6, d // 2),
            ref.rope(k[0].reshape(s, g, d), 5e6, d // 2),
            v[0].reshape(s, g, d), h // g)
        assert rel(got[0], one) < 2e-5


def test_attention_refuses_heads_that_do_not_fit():
    text = zoo.to_prototxt(zoo.zaya1(batch=N, **SIZES)).replace(
        "rope_theta: 5000000.0\n    num_kv_heads: 2",
        "rope_theta: 5000000.0\n    num_kv_heads: 4")
    with pytest.raises(ValueError, match="4 key-value heads of 16 need"):
        Net(load_net_from_string(text), "TRAIN",
            source_shapes={"tokens": (N, S), "targets": (N, S)})


# --------------------------------------------------------------------------- #
# the layer-updated leaf through the Engine: ADAM + decay + clip
# --------------------------------------------------------------------------- #

LR, WD, CLIP = 4e-3, 0.1, 0.05


def _job(tmp_path, max_iter, snapshot=0):
    import h5py
    from poseidon_tpu.proto.messages import load_solver
    rs = np.random.RandomState(7)
    stream = rs.randint(0, SIZES["vocab"], S + 1).astype(np.int32)
    with h5py.File(tmp_path / "tokens.h5", "w") as h:
        h["data"] = np.tile(stream[:-1], (8, 1))
        h["label"] = np.tile(stream[1:], (8, 1))
    (tmp_path / "tokens.txt").write_text(str(tmp_path / "tokens.h5") + "\n")
    (tmp_path / "net.prototxt").write_text(zoo.to_prototxt(zoo.zaya1(
        batch=N, source=str(tmp_path / "tokens.txt"), held=HELD, **SIZES)))
    (tmp_path / "solver.prototxt").write_text(
        f'net: "{tmp_path / "net.prototxt"}"\nsolver_type: ADAM\n'
        f'base_lr: {LR}\nlr_policy: "fixed"\nmomentum: 0.9\n'
        f'momentum2: 0.95\ndelta: 1e-8\nweight_decay: {WD}\n'
        f'clip_gradients: {CLIP}\nmax_iter: {max_iter}\ndisplay: 1\n'
        f'snapshot: {snapshot}\nsnapshot_after_train: false\n'
        f'snapshot_prefix: "snap/zaya"\nrandom_seed: 3\n')
    batch = {"tokens": jnp.tile(stream[:-1], (N, 1)),
             "targets": jnp.tile(stream[1:], (N, 1))}
    return load_solver(str(tmp_path / "solver.prototxt")), batch


def test_selection_bias_follows_the_sign_rule_step_for_step(tmp_path):
    """Three Engine steps of ADAM with weight decay and a clip that is on:
    after each, every router's bias is the reference's rule applied to the
    reference's OWN counts on the weights the step started from (so the
    bias steers the next step's choices in both). No optimizer, decay or
    clip touches it: its moments stay zero, its values are whole multiples
    of the rate, and the clip's norm is taken without it. The other leaves
    train."""
    from poseidon_tpu.runtime.engine import Engine
    sp, batch = _job(tmp_path, max_iter=3)
    eng = Engine(sp, output_dir=str(tmp_path / "out"), mesh=make_mesh(1))
    try:
        net = eng.train_net
        moved = 0
        for step in range(1, 4):
            before = jax.tree_util.tree_map(np.asarray, eng.params)
            weights = net.export_weights(eng.params)
            counts = ref.forward(CFG, weights, batch["tokens"],
                                 held=range(HELD))["counts"]
            eng.train(max_iter=step)
            after = jax.tree_util.tree_map(np.asarray, eng.params)
            for i in range(L):
                want = ref.next_bias(before[f"l{i}_router"]["bias"],
                                     counts[i], RATE)
                np.testing.assert_allclose(after[f"l{i}_router"]["bias"],
                                           want, rtol=0, atol=1e-7)
                moved += int(np.sum(after[f"l{i}_router"]["bias"]
                                    != before[f"l{i}_router"]["bias"]))
            assert np.any(after["l0_router"]["w1"]
                          != before["l0_router"]["w1"])
        assert moved >= 3 * L * (E - 2)
        hist = jax.tree_util.tree_map(np.asarray, eng.state.solver.history)
        for i in range(L):
            bias = after[f"l{i}_router"]["bias"]
            np.testing.assert_allclose(bias / RATE, np.round(bias / RATE),
                                       atol=1e-3)
            assert np.max(np.abs(bias)) <= 3 * RATE + 1e-7
            for moment in ("m", "v"):
                assert not np.any(hist[moment][f"l{i}_router"]["bias"])
                assert np.any(hist[moment][f"l{i}_router"]["w3"])
        sections = eng.stats.snapshot()["sections"]
        assert sections["expert_share"]["l0_moe"]["num_held"] == HELD
    finally:
        eng.close()


def test_clip_norm_and_update_leave_the_bias_out():
    """``make_update_fn`` with a layer-updated leaf: the clip's global norm
    is the other leaves' (a huge gradient on the bias changes nothing), the
    leaf takes the layer's value and its history does not move."""
    from poseidon_tpu.proto.messages import SolverParameter
    from poseidon_tpu.solvers.updates import init_state, make_update_fn
    sp = SolverParameter(solver_type="ADAM", base_lr=0.1, lr_policy="fixed",
                         momentum=0.9, momentum2=0.95, delta=1e-8,
                         weight_decay=0.5, clip_gradients=1.0)
    params = {"r": {"w": jnp.ones((4,)), "bias": jnp.full((3,), 0.25)}}
    mults = {"r": {"w": (1.0, 1.0), "bias": (1.0, 1.0)}}
    grads = {"r": {"w": jnp.full((4,), 2.0), "bias": jnp.full((3,), 1e6)}}
    state = init_state(params, "ADAM")
    nxt = {"r": {"bias": jnp.asarray([0.251, 0.249, 0.25])}}
    update = make_update_fn(sp, mults)
    got, new_state = update(params, grads, state, nxt)
    alone, _ = update({"r": {"w": params["r"]["w"]}},
                      {"r": {"w": grads["r"]["w"]}},
                      init_state({"r": {"w": params["r"]["w"]}}, "ADAM"))
    np.testing.assert_array_equal(got["r"]["w"], alone["r"]["w"])
    np.testing.assert_array_equal(got["r"]["bias"], nxt["r"]["bias"])
    assert not np.any(np.asarray(new_state.history["m"]["r"]["bias"]))
    assert np.any(np.asarray(new_state.history["m"]["r"]["w"]))


def test_remat_flag_and_snapshot_carry_the_bias_bit_for_bit(tmp_path):
    """The `--remat` the solver's header names gives the stored arm's
    parameters bit for bit, the bias among them (its next value is a top
    of a checkpointed layer); a snapshot holds the bias like any leaf and
    resumes bit for bit."""
    from poseidon_tpu.runtime.engine import Engine

    def finish(out, restore=None, remat=None):
        sp, _ = _job(tmp_path, max_iter=4, snapshot=2)
        eng = Engine(sp, output_dir=str(out), remat=remat,
                     mesh=make_mesh(1))
        try:
            if restore:
                eng.restore_from(restore)
            eng.train()
            return jax.tree_util.tree_map(np.asarray, eng.params), \
                eng.remat_plan
        finally:
            eng.close()

    whole, _ = finish(tmp_path / "whole")
    remat, plan = finish(tmp_path / "remat", remat=",".join(REMAT))
    assert len(plan.segments) == L + 1
    snap = tmp_path / "whole" / "snap" / "zaya_iter_2.solverstate.npz"
    with np.load(snap) as z:
        assert any("l1_router" in k and "bias" in k for k in z.files)
    resumed, _ = finish(tmp_path / "resumed", restore=str(snap))
    assert np.any(whole["l1_router"]["bias"])
    for other in (remat, resumed):
        for a, b in zip(jax.tree_util.tree_leaves(whole),
                        jax.tree_util.tree_leaves(other)):
            np.testing.assert_array_equal(a, b)


@pytest.mark.parametrize("how", ["devices", "mesh", "staleness"])
def test_layer_updated_leaf_is_refused_beyond_one_device(tmp_path, how):
    """Every device would balance its own loads: refused by name, as ADAM
    is under --mesh and --staleness."""
    from poseidon_tpu.config import MeshConfig
    from poseidon_tpu.runtime.engine import Engine
    sp, _ = _job(tmp_path, max_iter=1)
    sp.solver_type, sp.clip_gradients = "SGD", 0.0
    kw = {"devices": dict(mesh=make_mesh(2)),
          "mesh": dict(mesh_cfg=MeshConfig(data=1, fsdp=2, tp=1)),
          "staleness": dict(mesh=make_mesh(2), staleness=1)}[how]
    with pytest.raises(ValueError, match="l0_router/bias"):
        Engine(sp, output_dir=str(tmp_path / "out"), **kw).close()


@pytest.mark.parametrize("name", ["train", "solver"])
def test_example_prototxts_are_the_zoo_s_and_the_benchmark_s(name):
    """examples/lm/zaya1_8b_*.prototxt: the net is what `zoo.zaya1` writes
    at the cut its header states, and the benchmark's copies (what the
    cell runs) are the same bytes."""
    import re
    example = os.path.join(ROOT, "examples", "lm", f"zaya1_8b_{name}.prototxt")
    copy = os.path.join(ROOT, "benchmark", "configs", "zaya1_8b",
                        f"{name}.prototxt")
    with open(example) as a, open(copy) as b:
        text = a.read()
        assert text == b.read()
    if name == "train":
        m = re.search(r"zoo\.zaya1\(batch=1, n_layers=(\d+), held=(\d+), "
                      r"vocab=(\d+)\)", text)
        depth, held, vocab = (int(x) for x in m.groups())
        body = "".join(l for l in text.splitlines(True)
                       if not l.startswith("#"))
        assert body == zoo.to_prototxt(zoo.zaya1(
            batch=1, n_layers=depth, held=held, vocab=vocab))
        assert depth >= 4 and held == 8 and vocab == 262272 // 8
    else:
        assert "--remat '" + ",".join(REMAT) + "'" in text
