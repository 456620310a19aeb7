"""Trinity-Mini's block as layers of the Net against its plain reference
(benchmark/reference/trinity.py, loaded from there: one file, no second
copy), at a small size on the CPU with seeded weights: logits, loss and
every gradient; the expert shares summing to the whole layer with the shared
expert counted ONCE; window and global layers (positions only in the
former); the per-head QK-norm; the sigmoid router's selection bias through
Engine steps of ADAM + decay + clip; the example prototxts."""

import importlib.util
import os
import re

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
    "trinity_reference",
    os.path.join(ROOT, "benchmark", "reference", "trinity.py"))
ref = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(ref)

# 1 dense + a whole period (window, window, window, global), as the cell
L, DENSE, E, K, HELD, W = 5, 1, 16, 4, 8, 16
SIZES = dict(n_layers=L, dense_layers=DENSE, hidden=64, heads=8, kv_heads=2,
             head_dim=16, window=W, first_global=4, dense_width=96,
             experts=E, top_k=K,
             expert_width=32, shared_width=32, vocab=128)
CFG = {"num_hidden_layers": L, "num_dense_layers": DENSE,
       "num_attention_heads": 8, "num_key_value_heads": 2, "num_experts": E,
       "num_experts_per_tok": K, "route_scale": 2.826, "sliding_window": W,
       "layer_types": ["sliding_attention"] * 4 + ["full_attention"],
       "rms_norm_eps": 1e-5, "rope_theta": 10000.0}
N, S = 2, 64
RATE = 0.001
MOE_LAYERS = list(range(DENSE, L))
REMAT = [r"/l\d+_/", r"/lm_/"]   # what the example solver's header names


def build(held=HELD, held_first=0, n=N, s=S, **kw):
    # through the text form: what a user's prototxt goes through
    text = zoo.to_prototxt(zoo.trinity_mini(
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
    moved off it, so that a gain or the selection bias in the wrong place
    shows; the router's matrix larger, so that its choices are not all
    near-ties."""
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
    # embed, head, final norm; per layer 4 norms, q k v g o, 2 qk gains;
    # dense: 3; MoE: router 2, 3 stacks, shared 3
    assert sum(len(v) for v in params.values()) \
        == 3 + L * 11 + DENSE * 3 + (L - DENSE) * 8
    assert not net.shared_params
    assert params["l1_moe"]["gate"].shape == (HELD, 32, 64)
    assert params["l1_router"]["w"].shape == (E, 64)
    assert params["l0_q_norm"]["g"].shape == (16,)       # one head's dims
    assert params["l0_attn_norm"]["g"].shape == (64,)
    assert "l0_router" not in params and "l0_ffn_gate" in params
    assert net.layer_updates == {
        (f"l{i}_router", "bias"): f"l{i}_bias_next" for i in MOE_LAYERS}
    assert net.layer_facts()["expert_share"]["l4_moe"] == {
        "held_first": 0, "num_held": HELD, "router_num_experts": E}
    types = {l.name: l.TYPE for l in net.layers}
    # window and global told apart by name, every new scope typed
    assert [n for n, t in types.items() if t == "ATTENTION"] == [
        "l0_attn_window", "l1_attn_window", "l2_attn_window",
        "l3_attn_window", "l4_attn_global"]
    assert types["l1_router"] == "MOE_ROUTER" \
        and types["l1_shared_act"] == "SILU_GATE" \
        and types["l0_ffn_act"] == "SILU_GATE" \
        and types["l0_gate_sig"] == "SIGMOID" \
        and types["l0_gate_mul"] == "ELTWISE" \
        and types["embed_scale"] == "POWER"
    assert net.kernel_routes["l0_attn_window"] == (
        "attention=dense; 2 kv heads repeated x4; window 16 as a dense mask")
    assert net.kernel_routes["l4_attn_global"] == (
        "attention=dense; 2 kv heads repeated x4; no positions")


def test_kernel_route_names_the_band_on_the_chip(monkeypatch):
    monkeypatch.setenv("POSEIDON_FORCE_PALLAS", "1")
    net = build(n=1, s=256, window=64)
    assert net.kernel_routes["l0_attn_window"].startswith(
        "attention=pallas_flash (fwd 256x256 1/1")
    # every ATTENTION layer's route names its operands' form: narrow heads
    # head-major, whole vregs of lanes token-major
    assert all("; operands head-major (Dh " in r and "not lane-aligned)" in r
               for n, r in net.kernel_routes.items() if "_attn_" in n)
    net = build(n=1, s=256, window=64, head_dim=128, hidden=128, heads=2,
                kv_heads=1)
    route = net.kernel_routes["l0_attn_window"]
    assert "window 64: the band's grid; operands token-major (B,S,HxD))" \
        in route and route.endswith("1 kv heads repeated x2")
    assert "; operands token-major (B,S,HxD))" \
        in net.kernel_routes["l4_attn_global"]
    assert "window" not in net.kernel_routes["l4_attn_global"] \
        and net.kernel_routes["l4_attn_global"].endswith("no positions")


def test_net_matches_reference_forward(model):
    """f32 against f32: the same products summed in another order. The
    program's experts ARE the reference's own top-k (seeded router matrices
    keep the choices off near-ties), so every MoE layer's routed part, its
    counts and the biases' next values compare as they are."""
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
    for at, i in enumerate(MOE_LAYERS):
        np.testing.assert_array_equal(
            chosen_of(out.blobs[f"l{i}_gates"]),
            np.sort(np.asarray(want["choice"][at]), -1))
        # weights: the unbiased scores over their sum, times the scale
        np.testing.assert_allclose(
            np.asarray(out.blobs[f"l{i}_gates"]).sum(-1), 2.826, rtol=1e-5)
        counts = np.asarray(want["counts"][at])
        assert counts.sum() == N * S * K
        assert (counts > 0).sum() >= 8 and 0 < counts[:HELD].sum() < N * S * K
        np.testing.assert_allclose(out.outputs[f"l{i}_held_share"],
                                   counts[:HELD].sum() / (N * S * K),
                                   rtol=1e-6)
        np.testing.assert_allclose(
            out.outputs[f"l{i}_expert_load"],
            counts[:HELD].max() * HELD / counts[:HELD].sum(), rtol=1e-6)
        assert float(out.outputs[f"l{i}_dropped"]) == 0.0
        np.testing.assert_allclose(
            out.updates[f"l{i}_router"]["bias"],
            ref.next_bias(weights[f"l{i}_router"][-1], counts, RATE),
            rtol=0, atol=1e-7)
        assert rel(out.blobs[f"l{i}_m"], want["routed"][at]) < 2e-5
        assert rel(out.blobs[f"l{i}_s"], want["shared"][at]) < 2e-5


def test_net_matches_reference_gradients(model):
    """Every leaf's gradient: relative L2 under 5e-5 (f32 summation order
    through five blocks of backward). The selection bias takes none, on
    either side."""
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
            assert rel(got[lname][pname], g) < 5e-5, (lname, pname)
            n += 1
    assert n == sum(len(v) for v in params.values()) - len(MOE_LAYERS)


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

    (_, updates), grads = jax.value_and_grad(loss_and_updates,
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
        CFG, w, batch["tokens"], batch["targets"], opt,
        held=range(HELD)))(net.export_weights(params))
    assert float(want["grad_norm"]) > sp.clip_gradients      # the clip is on
    for lname, blobs in want["change"].items():
        for pdef, change in zip(owned[lname], blobs):
            moved = np.asarray(new[lname][pdef.name]) \
                - np.asarray(params[lname][pdef.name])
            if pdef.name == "bias":
                np.testing.assert_allclose(moved, change, rtol=0, atol=1e-7)
                assert np.sum(moved != 0) >= E - 2
            else:
                assert rel(moved, change) < 2e-3, (lname, pdef.name)


def test_the_shares_add_up_with_the_shared_expert_counted_once():
    """One MoE layer (behind the dense one) cut into shares of 4 experts:
    the four shares' ROUTED parts plus the shared expert ONCE equal the
    uncut reference's layer and the program's with all 16 held; every share
    carries the same shared part, so a plain sum of the shares' outputs
    would count it four times."""
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
        assert rel(out.blobs["l1_m"], want["routed"][0]) < 2e-5
        assert rel(out.blobs["l1_f"],
                   want["routed"][0] + want["shared"][0]) < 2e-5
        routed.append(np.asarray(out.blobs["l1_m"]))
        shared.append(np.asarray(out.blobs["l1_s"]))
    for other in shared[1:]:
        np.testing.assert_array_equal(shared[0], other)
    uncut = ref.forward(cfg, whole.export_weights(params), batch["tokens"])
    layer = uncut["routed"][0] + uncut["shared"][0]
    assert rel(sum(routed) + shared[0], layer) < 2e-5
    # and NOT the plain sum of the shares' outputs
    assert rel(sum(routed) + sum(shared), layer) > 0.1
    full = jax.jit(lambda p, b: whole.apply(p, b, train=True,
                                            keep_blobs=True))(params, batch)
    assert rel(full.blobs["l1_f"], layer) < 2e-5
    assert float(full.outputs["l1_held_share"]) == 1.0
    # the shares' held shares add up to every assignment
    counts = np.asarray(uncut["counts"][0])
    assert counts.sum() == N * S * K


def test_window_layers_see_the_window_and_global_layers_everything(model):
    """Perturb token t: a window layer's attention output moves at t ..
    t + W - 1 and nowhere else (nothing before t, nothing from t + W on);
    the global layer's moves everywhere after t. Layer 0 reads the
    embedding alone, so its reach is exact."""
    net, params, batch = model
    t = 9
    other = dict(batch, tokens=batch["tokens"].at[:, t].set(
        (batch["tokens"][:, t] + 1) % SIZES["vocab"]))
    run = jax.jit(lambda b: net.apply(params, b, train=True,
                                      keep_blobs=True).blobs)
    a, b = run(batch), run(other)
    x, y = np.asarray(a["l0_att"]), np.asarray(b["l0_att"])
    np.testing.assert_array_equal(x[:, :t], y[:, :t])
    np.testing.assert_array_equal(x[:, t + W:], y[:, t + W:])
    for at in (t, t + 1, t + W - 1):
        assert np.any(x[:, at] != y[:, at]), at
    x, y = np.asarray(a["l4_att"]), np.asarray(b["l4_att"])
    np.testing.assert_array_equal(x[:, :t], y[:, :t])
    assert np.any(x[:, -1] != y[:, -1])
    np.testing.assert_array_equal(np.asarray(a["logits"])[:, :t],
                                  np.asarray(b["logits"])[:, :t])


def test_global_layers_have_no_positions():
    """``rope: false``: moving a (q, k, v) triple of the past to another
    past position changes nothing at the last query of a global layer, and
    does in a window layer's (rotary positions)."""
    from poseidon_tpu.models import transformer as tr
    key = jax.random.PRNGKey(2)
    b, s, h, g, d = 1, 16, 4, 2, 8
    q = jax.random.normal(key, (b, s, h * d))
    k = jax.random.normal(jax.random.fold_in(key, 1), (b, s, g * d))
    v = jax.random.normal(jax.random.fold_in(key, 2), (b, s, g * d))
    swap = np.arange(s)
    swap[[2, 7]] = [7, 2]

    def last(rope, k_, v_):
        return np.asarray(tr.rope_attention(q, k_, v_, h, 1e4, g,
                                            rope=rope))[:, -1]

    np.testing.assert_allclose(last(False, k, v),
                               last(False, k[:, swap], v[:, swap]),
                               rtol=1e-5, atol=1e-6)
    assert rel(last(True, k, v), last(True, k[:, swap], v[:, swap])) > 1e-3


def test_per_head_norm_shares_one_gain(model):
    net, params, batch = model
    out = jax.jit(lambda p, b: net.apply(p, b, train=True,
                                         keep_blobs=True))(params, batch)
    q, qn = np.asarray(out.blobs["l0_q"]), np.asarray(out.blobs["l0_qn"])
    g = np.asarray(params["l0_q_norm"]["g"])
    heads = q.reshape(N, S, 8, 16)
    want = heads / np.sqrt((heads ** 2).mean(-1, keepdims=True) + 1e-5) * g
    np.testing.assert_allclose(qn, want.reshape(N, S, -1), rtol=2e-5,
                               atol=2e-6)


@pytest.mark.parametrize("what", ["forward", "dq", "dk", "dv"])
def test_windowed_grouped_query_flash_matches_dense(what):
    """8 query / 2 key-value heads under a window through the flash kernels
    (interpret mode) against the dense op and the reference's attention:
    the forward and all three gradients, k's and v's summed over the four
    query heads that read them."""
    from poseidon_tpu.models import transformer as tr
    from poseidon_tpu.ops import pallas_kernels as pk
    from poseidon_tpu.ops.attention import attention
    b, s, h, g, d, window = 1, 256, 8, 2, 32, 100
    key = jax.random.PRNGKey(11)
    q = jax.random.normal(key, (b, s, h * d))
    k = jax.random.normal(jax.random.fold_in(key, 1), (b, s, g * d))
    v = jax.random.normal(jax.random.fold_in(key, 2), (b, s, g * d))
    co = jax.random.normal(jax.random.fold_in(key, 3), (b, s, h * d))

    def through(flash):
        def att(q_, k_, v_, causal, scale=None, window=None):
            if flash:
                return pk.flash_attention(q_, k_, v_, causal, scale, 64, 64,
                                          True, window)
            return attention(q_, k_, v_, causal=causal, scale=scale,
                             window=window)

        def f(q_, k_, v_):
            old, tr.maybe_flash_attention = tr.maybe_flash_attention, att
            try:
                return tr.rope_attention(q_, k_, v_, h, 1e4, g,
                                         window=window)
            finally:
                tr.maybe_flash_attention = old
        return f

    if what == "forward":
        got, want = through(True)(q, k, v), through(False)(q, k, v)
        one = ref.attention(ref.rope(q[0].reshape(s, h, d), 1e4),
                            ref.rope(k[0].reshape(s, g, d), 1e4),
                            v[0].reshape(s, g, d), h // g, window)
        assert rel(got[0], one) < 2e-5
    else:
        i = ("dq", "dk", "dv").index(what)
        got, want = (jax.grad(lambda *a, f=f: jnp.sum(f(*a) * co),
                              argnums=i)(q, k, v)
                     for f in (through(True), through(False)))
    assert got.shape == want.shape and rel(got, want) < 2e-5


def test_layers_refuse_what_they_cannot_mean():
    text = zoo.to_prototxt(zoo.trinity_mini(batch=N, **SIZES))

    def broken(old, new, match):
        assert old in text
        with pytest.raises(ValueError, match=match):
            Net(load_net_from_string(text.replace(old, new, 1)), "TRAIN",
                source_shapes={"tokens": (N, S), "targets": (N, S)})

    broken("    rope: false\n", "    rope: false\n    rotary_dims: 8\n",
           "rope false")
    broken("    window: 16\n", "    window: -1\n", "window -1 is negative")
    broken('    score_func: "sigmoid"\n', '    score_func: "tanh"\n',
           "neither softmax nor sigmoid")
    broken("    num_kv_heads: 2\n", "    num_kv_heads: 3\n",
           "3 key-value heads of 16 need")
    broken("    num_heads: 8\n  }\n", "    num_heads: 5\n  }\n",
           "5 heads do not split")


# --------------------------------------------------------------------------- #
# the layer-updated leaf's second user, top-k > 1, through the Engine
# --------------------------------------------------------------------------- #

LR, WD, CLIP = 4e-3, 0.1, 0.05


def _job(tmp_path, max_iter, snapshot=0, held=HELD, n=N, **sizes):
    import h5py
    from poseidon_tpu.proto.messages import load_solver
    rs = np.random.RandomState(7)
    stream = rs.randint(0, SIZES["vocab"], S + 1).astype(np.int32)
    with h5py.File(tmp_path / "tokens.h5", "w") as h:
        h["data"] = np.tile(stream[:-1], (8, 1))
        h["label"] = np.tile(stream[1:], (8, 1))
    (tmp_path / "tokens.txt").write_text(str(tmp_path / "tokens.h5") + "\n")
    (tmp_path / "net.prototxt").write_text(zoo.to_prototxt(zoo.trinity_mini(
        batch=n, source=str(tmp_path / "tokens.txt"), held=held,
        **{**SIZES, **sizes})))
    (tmp_path / "solver.prototxt").write_text(
        f'net: "{tmp_path / "net.prototxt"}"\nsolver_type: ADAM\n'
        f'base_lr: {LR}\nlr_policy: "fixed"\nmomentum: 0.9\n'
        f'momentum2: 0.95\ndelta: 1e-8\nweight_decay: {WD}\n'
        f'clip_gradients: {CLIP}\nmax_iter: {max_iter}\ndisplay: 1\n'
        f'snapshot: {snapshot}\nsnapshot_after_train: false\n'
        f'snapshot_prefix: "snap/trinity"\nrandom_seed: 3\n')
    batch = {"tokens": jnp.tile(stream[:-1], (n, 1)),
             "targets": jnp.tile(stream[1:], (n, 1))}
    return load_solver(str(tmp_path / "solver.prototxt")), batch


def test_held_row_ladder_is_named_and_counted(tmp_path, monkeypatch):
    """2 of 16 experts held, top-2 of 256 tokens: the MOE layers'
    ``kernel_routes`` note names the chunks the held rows run in, and
    ``stats.yaml`` counts, from every step's displayed held shares, the
    trips the held arms made (``held_chunk_trips``), the rows those ran
    (``held_rows_run``) and the live rows among them (``held_rows_live``;
    live / run is how full the chunks were), beside the layer-steps
    displayed and those whose live rows number at most twice the even share
    (counters ``held_layer_steps`` / ``held_prefix_hits``). Routings on each side of that, forced through
    the routers' biases: a step with no assignment on a held expert (no
    trip), one with every assignment on one (every chunk, nothing dropped),
    back, and one the routers choose themselves (a chunk partly filled).
    Half the experts held: two chunks hold every row, the rows run as
    straight-line code: no note, no counter."""
    from poseidon_tpu.models import moe
    from poseidon_tpu.runtime.engine import Engine
    from poseidon_tpu.runtime.metrics import read_stats_yaml
    # the rule's floor (8,192 rows) lowered to a row tile: these 512 rows
    # are then cut at the even share, as the cells' are
    monkeypatch.setattr(moe, "_CHUNK_FLOOR", 128)
    assert "held rows" not in build().kernel_routes["l1_moe"]
    assert build().display_counters() == {}
    n, k = 4, 2
    sp, _ = _job(tmp_path, max_iter=4, held=2, n=n, top_k=k)
    chunk, prefix, rows = 128, 128, n * S * k

    def stats():
        doc = read_stats_yaml(str(tmp_path / "out" / "stats.yaml"))
        done = {c: float(v) for c, v in doc["counters"].items()}
        return ([round(done["held_rows_live"]
                       / max(done["held_rows_run"], 1.0), 6)]
                + [done[c] for c in
                   ("held_layer_steps", "held_prefix_hits",
                    "held_chunk_trips", "held_rows_run")])

    eng = Engine(sp, output_dir=str(tmp_path / "out"), mesh=make_mesh(1))

    def bias(*offsets):          # onto the routers' biases at experts 0, 1
        params = dict(eng.params)
        for i in MOE_LAYERS:
            router = params[f"l{i}_router"]
            params[f"l{i}_router"] = dict(router, bias=router["bias"].at[
                :2].set(jnp.asarray(offsets, jnp.float32)))
        eng.params = params

    try:
        routes = eng.stats.snapshot()["sections"]["kernel_routes"]
        for i in MOE_LAYERS:
            assert routes[f"l{i}_moe"] == (
                f"grouped_matmul=ragged_dot; held rows: chunks of {chunk} "
                f"of {rows}")
        assert sorted(eng.train_net.display_counters()) == [
            f"l{i}_held_share" for i in MOE_LAYERS]
        assert moe.held_rows_plan(rows, 2, E) == (chunk, prefix, rows)
        for offsets, share, want in (
                ((-10.0, -10.0), 0.0, [0.0, 4.0, 4.0, 0.0, 0.0]),
                ((10.0, 10.0), 1.0, [1.0, 8.0, 4.0, 16.0, 2048.0]),
                ((-10.0, -10.0), 0.0, [1.0, 12.0, 8.0, 16.0, 2048.0])):
            bias(*offsets)
            eng.train(max_iter=eng.iteration() + 1)
            row = eng.metrics.rows[-1]
            for i in MOE_LAYERS:
                assert row[f"l{i}_held_share"] == share
                assert row[f"l{i}_dropped"] == 0.0
            assert np.isfinite(row["loss"])
            assert stats() == want
        # the routers' own choice: the two held experts get some of the
        # assignments, so a layer's trips end in a chunk partly filled
        bias(0.0, 0.0)
        eng.train(max_iter=eng.iteration() + 1)
        row = eng.metrics.rows[-1]
        live = [round(row[f"l{i}_held_share"] * rows) for i in MOE_LAYERS]
        assert all(v < rows for v in live) and any(
            v % chunk for v in live)
        trips = [-(-v // chunk) for v in live]
        fill, steps, within, made, run = stats()
        assert (steps, within) == (16.0, 8.0 + sum(
            v <= prefix for v in live))
        assert (made, run) == (16.0 + sum(trips),
                               2048.0 + chunk * sum(trips))
        assert fill == round((2048.0 + sum(live)) / run, 6) and fill < 1.0
    finally:
        eng.close()


def test_selection_bias_follows_the_sign_rule_step_for_step(tmp_path):
    """Three Engine steps of ADAM with weight decay and a clip that is on:
    after each, every router's bias is the reference's rule applied to the
    reference's OWN counts (8 of 16 by a sigmoid: the bias steers the next
    step's choices in both). No optimizer, decay or clip touches it; the
    displays carry the routing and the biases' largest magnitude."""
    from poseidon_tpu.runtime.engine import Engine
    sp, batch = _job(tmp_path, max_iter=3)
    eng = Engine(sp, output_dir=str(tmp_path / "out"), mesh=make_mesh(1))
    try:
        net = eng.train_net
        for step in range(1, 4):
            before = jax.tree_util.tree_map(np.asarray, eng.params)
            counts = ref.forward(CFG, net.export_weights(eng.params),
                                 batch["tokens"], held=range(HELD))["counts"]
            eng.train(max_iter=step)
            after = jax.tree_util.tree_map(np.asarray, eng.params)
            for at, i in enumerate(MOE_LAYERS):
                want = ref.next_bias(before[f"l{i}_router"]["bias"],
                                     counts[at], RATE)
                np.testing.assert_allclose(after[f"l{i}_router"]["bias"],
                                           want, rtol=0, atol=1e-7)
            assert np.any(after["l1_router"]["w"] != before["l1_router"]["w"])
        hist = jax.tree_util.tree_map(np.asarray, eng.state.solver.history)
        for i in MOE_LAYERS:
            bias = after[f"l{i}_router"]["bias"]
            np.testing.assert_allclose(bias / RATE, np.round(bias / RATE),
                                       atol=1e-3)
            assert 0 < np.max(np.abs(bias)) <= 3 * RATE + 1e-7
            for moment in ("m", "v"):
                assert not np.any(hist[moment][f"l{i}_router"]["bias"])
                assert np.any(hist[moment][f"l{i}_router"]["w"])
        row = eng.metrics.rows[-1]
        for i in MOE_LAYERS:
            assert 0.0 < row[f"l{i}_held_share"] < 1.0
            assert row[f"l{i}_dropped"] == 0.0 and row[f"l{i}_expert_load"] >= 1
        sections = eng.stats.snapshot()["sections"]
        assert sections["expert_share"]["l1_moe"]["num_held"] == HELD
        assert sections["kernel_routes"]["l4_attn_global"].endswith(
            "no positions")
        for i in MOE_LAYERS:        # display 1: the last step's own value
            np.testing.assert_allclose(
                row[f"l{i}_bias_max_abs"],
                np.max(np.abs(after[f"l{i}_router"]["bias"])), rtol=1e-6)
    finally:
        eng.close()


@pytest.mark.parametrize("how", ["devices", "mesh", "staleness"])
def test_sigmoid_router_s_bias_is_refused_beyond_one_device(tmp_path, how):
    from poseidon_tpu.config import MeshConfig
    from poseidon_tpu.runtime.engine import Engine
    sp, _ = _job(tmp_path, max_iter=1)
    sp.solver_type, sp.clip_gradients = "SGD", 0.0
    kw = {"devices": dict(mesh=make_mesh(2)),
          "mesh": dict(mesh_cfg=MeshConfig(data=1, fsdp=2, tp=1)),
          "staleness": dict(mesh=make_mesh(2), staleness=1)}[how]
    with pytest.raises(ValueError, match="l1_router/bias"):
        Engine(sp, output_dir=str(tmp_path / "out"), **kw).close()


def test_remat_flag_gives_the_stored_arm_bit_for_bit(tmp_path):
    from poseidon_tpu.runtime.engine import Engine

    def finish(out, remat=None):
        sp, _ = _job(tmp_path, max_iter=3)
        eng = Engine(sp, output_dir=str(out), remat=remat, mesh=make_mesh(1))
        try:
            eng.train()
            return jax.tree_util.tree_map(np.asarray, eng.params), \
                eng.remat_plan
        finally:
            eng.close()

    whole, _ = finish(tmp_path / "whole")
    remat, plan = finish(tmp_path / "remat", remat=",".join(REMAT))
    assert len(plan.segments) == L + 1
    assert np.any(whole["l4_router"]["bias"])
    for a, b in zip(jax.tree_util.tree_leaves(whole),
                    jax.tree_util.tree_leaves(remat)):
        np.testing.assert_array_equal(a, b)


@pytest.mark.parametrize("name", ["train", "solver"])
def test_example_prototxts_are_the_zoo_s_and_the_benchmark_s(name):
    """examples/lm/trinity_mini_*.prototxt: the net is what
    `zoo.trinity_mini` writes at the cut its header states, and the
    benchmark's copies (what the cell runs) are the same bytes."""
    example = os.path.join(ROOT, "examples", "lm",
                           f"trinity_mini_{name}.prototxt")
    copy = os.path.join(ROOT, "benchmark", "configs", "trinity_mini",
                        f"{name}.prototxt")
    with open(example) as a, open(copy) as b:
        text = a.read()
        assert text == b.read()
    if name == "train":
        m = re.search(r"zoo\.trinity_mini\(batch=1, n_layers=(\d+), "
                      r"dense_layers=(\d+), first_global=(\d+), "
                      r"held=(\d+), vocab=(\d+)\)", text)
        depth, dense, first, held, vocab = (int(x) for x in m.groups())
        body = "".join(l for l in text.splitlines(True)
                       if not l.startswith("#"))
        assert body == zoo.to_prototxt(zoo.trinity_mini(
            batch=1, n_layers=depth, dense_layers=dense,
            first_global=first, held=held, vocab=vocab))
        assert (depth, dense, first, held, vocab) \
            == (5, 1, 4, 16, 200192 // 8)
        # the rate is the configuration's load_balance_coeff, and the
        # prototxt's routers carry it (the field's default states it)
        net = load_net_from_string(body)
        routers = [l for l in net.layers if l.type == "MOE_ROUTER"]
        assert len(routers) == 4 and all(
            l.moe_param.bias_update_rate == 0.001
            and l.moe_param.score_func == "sigmoid"
            and l.moe_param.top_k == 8 and l.moe_param.num_experts == 128
            and l.moe_param.route_scale == 2.826 for l in routers)
        windows = [l.attention_param.window for l in net.layers
                   if l.type == "ATTENTION"]
        assert windows == [2048, 2048, 2048, 2048, 0]
    else:
        assert "--remat '" + ",".join(REMAT) + "'" in text
