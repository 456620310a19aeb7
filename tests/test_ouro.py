"""Ouro's looped block as layers of the Net against its plain reference
(benchmark/reference/ouro.py, loaded from there: one file, no second copy):
every pass's logits, the exit distribution, the loss and every OWNER leaf's
gradient on seeded weights, at a small size on the CPU; then the shared
leaves through one Engine step of ADAM + clip, a remat arm and a snapshot."""

import importlib.util
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from poseidon_tpu.config import policy_scope
from poseidon_tpu.core.net import Net
from poseidon_tpu.core.remat import resolve_entries
from poseidon_tpu.models import zoo
from poseidon_tpu.proto.messages import load_net_from_string

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_spec = importlib.util.spec_from_file_location(
    "ouro_reference", os.path.join(ROOT, "benchmark", "reference", "ouro.py"))
ref = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(ref)

L, T = 2, 4
SIZES = dict(n_layers=L, passes=T, hidden=64, heads=4, ffn_width=96,
             vocab=512)
CFG = {"num_hidden_layers": L, "total_ut_steps": T, "num_attention_heads": 4,
       "rms_norm_eps": 1e-6, "rope_theta": 1e6}
N, S = 2, 64
BETA = 0.1
# what the example solver's header tells a user to pass to --remat
REMAT = [r"/p\d+_l\d+_/", r"/p\d+_(?=head|nll)/"]


def build(n=N, s=S):
    # through the text form: what a user's prototxt goes through
    text = zoo.to_prototxt(zoo.ouro(batch=n, entropy_weight=BETA, **SIZES))
    return Net(load_net_from_string(text), "TRAIN",
               source_shapes={"tokens": (n, s), "targets": (n, s)})


@pytest.fixture(scope="module")
def model():
    net = build()
    params = net.init(jax.random.PRNGKey(3))
    # norm gains off 1.0 and a gate bias off 0, so that a gain or a bias in
    # the wrong place shows
    for i, (lname, lp) in enumerate(sorted(params.items())):
        for pname in ("g", "b"):
            if pname in lp:
                lp[pname] = (pname == "g") + 0.2 * jax.random.normal(
                    jax.random.PRNGKey(100 + i), lp[pname].shape)
    key = jax.random.PRNGKey(5)
    batch = {"tokens": jax.random.randint(key, (N, S), 0, SIZES["vocab"]),
             "targets": jax.random.randint(jax.random.fold_in(key, 1),
                                           (N, S), 0, SIZES["vocab"])}
    return net, params, batch


def rel(a, b):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.linalg.norm(a - b) / max(np.linalg.norm(b), 1e-30))


def test_one_leaf_per_shared_weight(model):
    net, params, _ = model
    # embed; L x (4 gains + q k v o + gate up down); final norm, head, gate w b
    assert sum(len(v) for v in params.values()) == 1 + L * 11 + 4
    assert all(k == "embed" or k.startswith("p1_") for k in params)
    shared = net.shared_params
    assert shared["l1_ffn_down_w"] == {"owner": "p1_l1_ffn_down/w", "uses": T}
    assert shared["head_w"]["uses"] == T and shared["gate_b"]["uses"] == T - 1


def test_net_matches_reference_forward(model):
    """f32 against f32: both sides sum the same products in another order,
    so 2e-4 of relative L2 on each pass's logits (1e-5 on the loss) is
    summation noise with room; a bf16 matmul (1e-2), a norm before the
    residual add where it belongs after, weights not shared between passes
    or an exit distribution that skips a pass each move a later pass's
    logits or the loss by 1e-1 or more."""
    net, params, batch = model
    out = jax.jit(lambda p, b: net.apply(p, b, train=True,
                                         keep_blobs=True))(params, batch)
    weights = net.export_weights(params)
    want_total, parts = ref.loss(CFG, weights, batch["tokens"],
                                 batch["targets"], BETA)
    want = ref.forward(CFG, weights, batch["tokens"])
    tol = ref.TOLERANCE["f32"]
    for t in range(T):
        assert rel(out.blobs[f"p{t + 1}_logits"], want["logits"][t]) \
            < tol["logits_rel_l2"], t
        np.testing.assert_allclose(out.outputs[f"exit_mass_p{t + 1}"],
                                   parts["exit_mass"][t], rtol=1e-5)
        np.testing.assert_allclose(
            np.mean(out.blobs[f"p{t + 1}_nll"]), parts["ce"][t], rtol=1e-5)
    for t in range(T - 1):
        np.testing.assert_allclose(out.blobs[f"p{t + 1}_gate"][..., 0],
                                   want["gates"][t], rtol=1e-4, atol=1e-6)
    mass = sum(float(out.outputs[f"exit_mass_p{t + 1}"]) for t in range(T))
    assert abs(mass - 1.0) < 1e-6
    np.testing.assert_allclose(np.sum(want["exit_p"], 0), 1.0, rtol=1e-6)
    # the passes differ, and later passes are not the first one again
    assert rel(want["logits"][1], want["logits"][0]) > 1e-2
    assert abs(float(out.loss) - float(want_total)) \
        < tol["loss_rel"] * float(want_total)
    assert float(out.outputs["exit_loss"]) == float(out.loss)


def test_net_matches_reference_gradients(model):
    """Every OWNER leaf's gradient, shared ones included (a shared leaf's is
    the sum over its four uses): relative L2 under 1e-5 (f32 summation
    order through eight block applications of backward). A gradient taken
    from one pass only is off by 1e-1 or more."""
    net, params, batch = model
    got = jax.jit(jax.grad(
        lambda p: net.apply(p, batch, train=True).loss))(params)
    owners = {l.name: [p.name for p in l.params] for l in net.layers
              if l.name in params}

    def ref_loss(weights):
        return ref.loss(CFG, weights, batch["tokens"], batch["targets"],
                        BETA)[0]

    weights = {k: [jnp.asarray(b) for b in v]
               for k, v in net.export_weights(params).items() if k in owners}
    want = jax.jit(jax.grad(ref_loss))(weights)
    checked, worst = 0, 0.0
    for lname, pnames in owners.items():
        for pname, w in zip(pnames, want[lname]):
            r = rel(got[lname][pname], w)
            assert r < 1e-5, (lname, pname, r)
            checked, worst = checked + 1, max(worst, r)
    print(f"loosest leaf: {worst:.2e}")
    assert checked == sum(len(v) for v in params.values()) == 27


def test_remat_segments_are_bitwise_the_stored_arm(model):
    """One checkpoint per block application and per head-and-loss: the loss
    and every gradient equal the stored arm's bit for bit (remat moves WHEN
    an activation is computed, never what)."""
    net, params, batch = model
    layers, segments = resolve_entries([l.name for l in net.layers], REMAT)
    assert len(segments) == T * L + T and len(segments[0]) == 15
    assert segments[-1] == (f"p{T}_head", f"p{T}_nll")
    assert set(layers) == {n for seg in segments for n in seg}

    def arm(remat):
        return jax.jit(jax.value_and_grad(
            lambda p: net.apply(p, batch, train=True, remat=remat).loss))(
                params)

    (l0, g0), (l1, g1) = arm(None), arm(segments)
    assert float(l0) == float(l1)
    for a, b in zip(jax.tree_util.tree_leaves(g0),
                    jax.tree_util.tree_leaves(g1)):
        assert np.array_equal(a, b)


@pytest.mark.parametrize("entries, why", [
    (["/nothing_/"], "matches no layer"),
    (["no_such_layer"], "unknown layers"),
    ([r"/p\d+_l\d+_/", "p1_l0_q"], "more than once"),
])
def test_remat_entries_are_refused_loudly(model, entries, why):
    with pytest.raises(ValueError, match=why):
        resolve_entries([l.name for l in model[0].layers], entries)


def test_a_segment_must_follow_the_net(model):
    net, params, batch = model
    with pytest.raises(ValueError, match="do not follow"):
        net.apply(params, batch, remat=[("p1_l0_q", "p1_l0_v")])


def test_bf16_policy_stays_near_reference(model):
    """The --bf16 policy on the CPU, at width 64: inside the rehearsal's
    tolerance and outside the f32 one, while float8 matmul inputs (the
    nearest precision below) fall outside it: the tolerance tells the
    precisions on both sides of it apart. The published widths' limit is
    tighter (the reference file says from which two chip readings)."""
    net, params, batch = model
    with policy_scope(compute_dtype=jnp.bfloat16):
        out = jax.jit(lambda p, b: net.apply(
            p, b, train=False, keep_blobs=True))(params, batch)
    weights = net.export_weights(params)
    want = ref.forward(CFG, weights, batch["tokens"])["logits"]
    low = ref.forward(CFG, weights, batch["tokens"],
                      round_to=jnp.float8_e4m3fn)["logits"]
    tol = ref.TOLERANCE_TINY          # width 64: the rehearsal's limits
    for t in range(T):
        r = rel(out.blobs[f"p{t + 1}_logits"], want[t])
        assert tol["f32"]["logits_rel_l2"] < r < tol["bf16"]["logits_rel_l2"]
        assert rel(low[t], want[t]) > tol["bf16"]["logits_rel_l2"]
    assert ref.TOLERANCE["bf16"]["logits_rel_l2"] \
        < tol["bf16"]["logits_rel_l2"]


# --------------------------------------------------------------------------- #
# the shared leaves through the Engine: ADAM + clip, remat, a snapshot
# --------------------------------------------------------------------------- #

LR, WD, CLIP = 4e-4, 0.1, 0.5


def _job(tmp_path, max_iter, snapshot=0):
    """A token file whose records are all ONE sequence (a snapshot does not
    carry the data cursor), the zoo's net over it and an ADAM solver."""
    import h5py
    from poseidon_tpu.proto.messages import load_solver
    rs = np.random.RandomState(7)
    stream = rs.randint(0, SIZES["vocab"], S + 1).astype(np.int32)
    with h5py.File(tmp_path / "tokens.h5", "w") as h:
        h["data"] = np.tile(stream[:-1], (8, 1))
        h["label"] = np.tile(stream[1:], (8, 1))
    (tmp_path / "tokens.txt").write_text(str(tmp_path / "tokens.h5") + "\n")
    (tmp_path / "net.prototxt").write_text(zoo.to_prototxt(zoo.ouro(
        batch=N, source=str(tmp_path / "tokens.txt"), entropy_weight=BETA,
        **SIZES)))
    (tmp_path / "solver.prototxt").write_text(
        f'net: "{tmp_path / "net.prototxt"}"\nsolver_type: ADAM\n'
        f'base_lr: {LR}\nlr_policy: "fixed"\nmomentum: 0.9\n'
        f'momentum2: 0.95\ndelta: 1e-8\nweight_decay: {WD}\n'
        f'clip_gradients: {CLIP}\nmax_iter: {max_iter}\ndisplay: 1\n'
        f'snapshot: {snapshot}\nsnapshot_after_train: false\n'
        f'snapshot_prefix: "snap/ouro"\nrandom_seed: 3\n')
    batch = {"tokens": jnp.tile(stream[:-1], (N, 1)),
             "targets": jnp.tile(stream[1:], (N, 1))}
    return load_solver(str(tmp_path / "solver.prototxt")), batch


def test_engine_adam_clip_step_equals_reference_adamw(tmp_path):
    """One Engine step of ADAM + clip_gradients: a shared leaf is clipped
    and updated ONCE, with the sum of its four uses' gradients — equal to
    AdamW by hand on the reference's gradients, the global norm taken over
    the owner leaves (a norm that counted a shared leaf four times would
    scale every step by another factor; Adam's first step is lr * g / (|g|
    + eps), so the comparison is on the moments, which are linear in g)."""
    from poseidon_tpu.runtime.engine import Engine
    sp, batch = _job(tmp_path, max_iter=1)
    eng = Engine(sp, output_dir=str(tmp_path / "out"))
    try:
        before = jax.tree_util.tree_map(np.asarray, eng.params)
        mults = {l.name: [(p.lr_mult, p.decay_mult) for p in l.params]
                 for l in eng.train_net.layers if l.name in before}
        weights = {k: [jnp.asarray(b) for b in v] for k, v in
                   eng.train_net.export_weights(eng.params).items()
                   if k in before}
        eng.train()
        after = jax.tree_util.tree_map(np.asarray, eng.params)
        hist = jax.tree_util.tree_map(np.asarray, eng.state.solver.history)
        assert set(after) == set(before) and int(eng.state.solver.it) == 1
    finally:
        eng.close()
    grads = jax.jit(jax.grad(lambda w: ref.loss(
        CFG, w, batch["tokens"], batch["targets"], BETA)[0]))(weights)
    norm = float(np.sqrt(sum(float(np.sum(np.asarray(g, np.float64) ** 2))
                             for gs in grads.values() for g in gs)))
    assert norm > CLIP                 # the clip is on in this step
    scale = CLIP / norm
    for lname, gs in grads.items():
        names = [p.name for p in
                 eng.train_net._layer_by_name[lname].params]
        for pname, g, (lr_mult, decay_mult) in zip(names, gs, mults[lname]):
            g = np.asarray(g, np.float64) * scale
            w = before[lname][pname].astype(np.float64)
            m, v = 0.1 * g, 0.05 * g * g
            step = (m / 0.1) / (np.sqrt(v / 0.05) + 1e-8) \
                + WD * decay_mult * w
            np.testing.assert_allclose(hist["m"][lname][pname], m,
                                       rtol=2e-4, atol=1e-9)
            np.testing.assert_allclose(hist["v"][lname][pname], v,
                                       rtol=4e-4, atol=1e-14)
            # where |g| is far above eps the step is lr * sign(g) + decay
            big = np.abs(g) > 1e-5
            np.testing.assert_allclose(
                after[lname][pname][big],
                (w - LR * lr_mult * step)[big], rtol=1e-5, atol=1e-7)


def _finish(sp, out, restore=None, remat=None):
    from poseidon_tpu.runtime.engine import Engine
    eng = Engine(sp, output_dir=str(out), remat=remat)
    try:
        if restore:
            eng.restore_from(restore)
            assert eng.iteration() == 3
        eng.train()
        plan = eng.remat_plan
        sections = eng.stats.snapshot()["sections"]
        return jax.tree_util.tree_map(
            np.asarray, (eng.params, eng.state.solver.history,
                         eng.state.solver.it)), plan, sections
    finally:
        eng.close()


def test_engine_remat_flag_and_snapshot_resume_bit_for_bit(tmp_path):
    """Through the Engine: the `--remat` the solver's header names gives
    the stored arm's parameters and moments bit for bit; a snapshot of the
    shared-leaf ADAM run holds each weight once and resumes bit for bit."""
    sp, _ = _job(tmp_path, max_iter=6, snapshot=3)
    whole, _, sections = _finish(sp, tmp_path / "whole")
    assert sections["shared_params"]["l0_q_w"] == f"p1_l0_q/w x{T}"
    assert "remat" not in sections
    remat, plan, sections = _finish(sp, tmp_path / "remat",
                                    remat=",".join(REMAT))
    assert len(plan.segments) == T * L + T and plan.source == "flag"
    assert len(sections["remat"]["segments"]) == len(plan.segments)
    snap = tmp_path / "whole" / "snap" / "ouro_iter_3.solverstate.npz"
    with np.load(snap) as z:
        held = [k for k in z.files if "ffn_down" in k and "l1_" in k]
    # the weight and its two moments, once each: no p2_ / p3_ / p4_ copy
    assert len(held) == 3 and all("p1_l1_ffn_down" in k for k in held), held
    resumed, _, _ = _finish(sp, tmp_path / "resumed", restore=str(snap))
    assert int(whole[2]) == int(remat[2]) == int(resumed[2]) == 6
    for other in (remat, resumed):
        for a, b in zip(jax.tree_util.tree_leaves(whole),
                        jax.tree_util.tree_leaves(other)):
            np.testing.assert_array_equal(a, b)


def test_remat_auto_probe_types_token_blobs_as_integers(tmp_path):
    """`--remat auto`'s probe lowers the step against abstract batches typed
    as the source gives them: ids and targets are int32 at rank 2. (Typed
    f32, the EMBED lookup's cast hid it; a layer that indexes with its
    bottom as given would not lower.)"""
    from poseidon_tpu.core import remat as remat_mod
    from poseidon_tpu.runtime.engine import Engine
    sp, _ = _job(tmp_path, max_iter=1)
    seen = {}
    real = remat_mod.plan_for_net_step

    def spy(net, lowerable, example_args, budget, **kw):
        seen.update({k: v.dtype for k, v in example_args[2].items()})
        return real(net, lowerable, example_args, budget, **kw)

    remat_mod.plan_for_net_step = spy
    try:
        eng = Engine(sp, output_dir=str(tmp_path / "out"), remat="auto",
                     hbm_budget_gb=1e-6)
        eng.close()
    finally:
        remat_mod.plan_for_net_step = real
    assert seen == {"tokens": jnp.int32, "targets": jnp.int32}
    assert eng.remat_plan is not None and eng.remat_plan.source == "measured"


@pytest.mark.parametrize("prefetch, backend, float_tops, want", [
    (True, "tpu", {"data"}, True),          # an image batch, as before
    (True, "tpu", set(), False),            # ids and targets only
    (True, "cpu", {"data"}, False),
    (False, "tpu", {"data"}, False),
])
def test_a_token_batch_is_not_donated(prefetch, backend, float_tops, want):
    from poseidon_tpu.runtime.engine import Engine
    assert Engine.donates_batch(prefetch, backend, float_tops) is want


def test_token_engine_has_nothing_to_donate(tmp_path):
    from poseidon_tpu.runtime.engine import Engine
    sp, _ = _job(tmp_path, max_iter=1)
    eng = Engine(sp, output_dir=str(tmp_path / "out"))
    try:
        assert eng._token_tops == {"tokens", "targets"}
        assert not Engine.donates_batch(True, "tpu",
                                        set(eng._train_shapes)
                                        - eng._token_tops)
    finally:
        eng.close()


@pytest.mark.parametrize("name", ["train", "solver"])
def test_example_prototxts_are_the_zoo_s_and_the_benchmark_s(name):
    """examples/lm/ouro_2_6b_*.prototxt: the net is what `zoo.ouro` writes
    at the depth its header states, and the benchmark's copies (what the
    cell runs) are the same bytes."""
    import re
    example = os.path.join(ROOT, "examples", "lm", f"ouro_2_6b_{name}.prototxt")
    copy = os.path.join(ROOT, "benchmark", "configs", "ouro_2_6b",
                        f"{name}.prototxt")
    with open(example) as a, open(copy) as b:
        text = a.read()
        assert text == b.read()
    if name == "train":
        depth = int(re.search(r"batch=1, n_layers=(\d+)\)", text).group(1))
        body = "".join(l for l in text.splitlines(True)
                       if not l.startswith("#"))
        assert body == zoo.to_prototxt(zoo.ouro(batch=1, n_layers=depth))
        assert f"depth cut 48 -> {depth} " in text
    else:
        assert "--remat '" + ",".join(REMAT) + "'" in text
