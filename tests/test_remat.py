"""The HBM budget planner's regression surface (core/remat.py).

Four properties, pinned at tier-1 cost:

1. **Knapsack semantics** — zero budget means maximal remat, a budget at
   or above the peak is the identity plan, and lower budgets choose
   SUPERSETS of higher budgets' layers (monotone in the budget; the
   greedy order is fixed so every mesh participant plans identically).
2. **Bitwise parity** — remat changes what XLA's buffer assignment keeps
   live, never the math. Checkpointed arms must equal stored-activation
   arms bit for bit: through bare train steps, through full Engine runs
   (same seed, same data), through the dp2 x fsdp2 sharded step, and per
   transformer checkpoint policy.
3. **Plan resolution** — the legacy bool folds to the enum, explicit
   config vs concrete plan disagreement refuses loudly (never silently
   arbitrated), and ``auto`` defers.
4. **Tuner integration** — the (remat, batch_size) stage persists and
   memo-hits; a default win must not ship a budget knob that would make
   later trains re-pay the measuring compile.
"""

import os
import re

import jax
import numpy as np
import pytest

from poseidon_tpu.core import remat as remat_mod
from poseidon_tpu.core.net import Net
from poseidon_tpu.core.remat import (RematPlan, normalize_policy,
                                     plan_remat, resolve_lm_policy,
                                     wrap_checkpoint)
from poseidon_tpu.models import zoo
from poseidon_tpu.parallel import (CommConfig, build_train_step,
                                   init_train_state, make_mesh)
from poseidon_tpu.proto.messages import SolverParameter

N_DEV = 8
SP = SolverParameter(base_lr=0.01, lr_policy="fixed", momentum=0.9,
                     weight_decay=0.0005)


@pytest.fixture(scope="module", autouse=True)
def _release_compiled_steps():
    yield
    jax.clear_caches()


def _tree_equal(a, b, what=""):
    la = jax.tree_util.tree_leaves(a)
    lb = jax.tree_util.tree_leaves(b)
    assert len(la) == len(lb)
    for i, (x, y) in enumerate(zip(la, lb)):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y),
                                      err_msg=f"{what} leaf {i}")


# --------------------------------------------------------------------------- #
# policy enum + resolution
# --------------------------------------------------------------------------- #

def test_normalize_policy_folds_legacy_bools():
    assert normalize_policy(False) == "none"
    assert normalize_policy(None) == "none"
    assert normalize_policy("") == "none"
    # True folds to jax.checkpoint's own default so the legacy bool keeps
    # its exact graph (the seed wrapped blocks in bare jax.checkpoint)
    assert normalize_policy(True) == "nothing_saveable"
    assert normalize_policy("NOTHING_SAVEABLE") == "nothing_saveable"
    with pytest.raises(ValueError, match="unknown remat policy"):
        normalize_policy("everything_saveable")


def test_resolve_lm_policy_conflict_refuses_loudly():
    # explicit config flag vs a concrete contradicting plan: error, not
    # silent arbitration
    with pytest.raises(ValueError, match="remat policy conflict"):
        resolve_lm_policy("nothing_saveable", "dots_saveable")
    # agreement passes through
    assert resolve_lm_policy("dots_saveable",
                             "dots_saveable") == "dots_saveable"
    # unset config follows the plan; auto defers; both-defer -> measured
    # default
    assert resolve_lm_policy(False, "nothing_saveable") == \
        "nothing_saveable"
    assert resolve_lm_policy("auto", "none") == "none"
    assert resolve_lm_policy("auto", None) == "dots_saveable"
    assert resolve_lm_policy(False, None) == "none"


# --------------------------------------------------------------------------- #
# the knapsack
# --------------------------------------------------------------------------- #

_TABLE = {
    # flops column is the attribution table's 3x-forward convention
    "cheap_big": {"act_bytes": 1000, "flops": 300.0},    # 0.1 flop/byte
    "mid": {"act_bytes": 500, "flops": 1500.0},          # 1 flop/byte
    "dear_small": {"act_bytes": 100, "flops": 3000.0},   # 10 flop/byte
    "scalar_head": {"act_bytes": 0, "flops": 9.0},       # never picked
}


def test_zero_budget_is_maximal_remat():
    plan = plan_remat(_TABLE, 0, 1600)
    assert set(plan.layers) == {"cheap_big", "mid", "dear_small"}
    assert plan.saved_bytes == 1600
    assert plan.active


def test_budget_at_or_above_peak_is_identity():
    plan = plan_remat(_TABLE, 1600, 1600)
    assert plan.layers == ()
    assert not plan.active
    assert plan_remat(_TABLE, 10**9, 1600).layers == ()


def test_greedy_order_is_cheapest_recompute_per_byte():
    # deficit 400: cheap_big alone (1000 bytes reclaimed) covers it
    plan = plan_remat(_TABLE, 1200, 1600)
    assert plan.layers == ("cheap_big",)
    assert plan.saved_bytes == 1000
    assert plan.recompute_flops == pytest.approx(100.0)  # 300 / 3


def test_budget_monotonicity_supersets():
    peak = 1600
    prev: set = set()
    for budget in (peak, 1200, 600, 100, 0):
        layers = set(plan_remat(_TABLE, budget, peak).layers)
        assert layers >= prev, (budget, layers, prev)
        prev = layers
    assert prev == {"cheap_big", "mid", "dear_small"}


def test_plan_doc_roundtrip():
    plan = plan_remat(_TABLE, 1200, 1600, lm_policy="dots_saveable",
                      source="measured")
    back = RematPlan.from_doc(plan.to_doc())
    assert back == plan


# --------------------------------------------------------------------------- #
# bitwise parity: bare step, Engine, dp2 x fsdp2
# --------------------------------------------------------------------------- #

def _lenet_setup(per_dev=2):
    net = Net(zoo.lenet(with_accuracy=False), phase="TRAIN",
              source_shapes=zoo.lenet_shapes(per_dev))
    rows = per_dev * N_DEV
    rs = np.random.RandomState(0)
    batch = {"data": rs.randn(rows, 1, 28, 28).astype(np.float32),
             "label": rs.randint(0, 10, size=(rows,))}
    return net, batch


def _run_steps(net, batch, remat_plan, n_steps=3):
    comm = CommConfig(param_arena=True)
    ts = build_train_step(net, SP, make_mesh(), comm,
                          remat_plan=remat_plan)
    p = net.init(jax.random.PRNGKey(0))
    s = init_train_state(p, comm, N_DEV)
    for i in range(n_steps):
        p, s, m = ts.step(p, s, batch, jax.random.fold_in(
            jax.random.PRNGKey(7), i))
    return p, s, m


def test_lenet_step_bitwise_parity_under_max_remat():
    net, batch = _lenet_setup()
    plan = plan_remat(net.cost_table(), 0, 0,
                      candidates=remat_mod.remat_candidates(net))
    assert plan.active
    p0, s0, m0 = _run_steps(net, batch, None)
    p1, s1, m1 = _run_steps(net, batch, plan)
    _tree_equal(p0, p1, "params")
    _tree_equal(s0, s1, "state")
    np.testing.assert_array_equal(np.asarray(m0["loss"]),
                                  np.asarray(m1["loss"]))


def test_unknown_remat_layer_refuses_loudly():
    net, batch = _lenet_setup()
    with pytest.raises(ValueError, match="unknown"):
        _run_steps(net, batch, RematPlan(layers=("not_a_layer",),
                                         source="flag"), n_steps=1)


def test_engine_bitwise_parity_with_remat_flag(tmp_path):
    """Full Engine runs (same seed, same MEMORY_DATA): the --remat flag
    arm's final params equal the stored-activation arm's bit for bit."""
    from poseidon_tpu.proto.messages import load_net_from_string
    from poseidon_tpu.runtime.engine import Engine

    net_txt = """
name: "SmallNet"
layers {
  name: "mnist" type: MEMORY_DATA top: "data" top: "label"
  memory_data_param { batch_size: 8 channels: 1 height: 12 width: 12 }
}
layers {
  name: "conv1" type: CONVOLUTION bottom: "data" top: "conv1"
  convolution_param { num_output: 8 kernel_size: 3
    weight_filler { type: "xavier" } bias_filler { type: "constant" } }
}
layers { name: "relu1" type: RELU bottom: "conv1" top: "conv1" }
layers {
  name: "ip1" type: INNER_PRODUCT bottom: "conv1" top: "ip1"
  inner_product_param { num_output: 5
    weight_filler { type: "xavier" } bias_filler { type: "constant" } }
}
layers { name: "loss" type: SOFTMAX_LOSS bottom: "ip1" bottom: "label"
  top: "loss" }
"""
    rs = np.random.RandomState(0)
    md = {"data": rs.randn(64, 1, 12, 12).astype(np.float32),
          "label": rs.randint(0, 5, size=64)}
    finals = {}
    for arm, remat in (("stored", None), ("remat", "conv1,ip1")):
        sp = SolverParameter(train_net_param=load_net_from_string(net_txt),
                             base_lr=0.05, lr_policy="fixed", momentum=0.9,
                             weight_decay=0.0005, display=0, max_iter=8,
                             random_seed=3)
        out_dir = tmp_path / arm
        out_dir.mkdir()
        eng = Engine(sp, memory_data=md, output_dir=str(out_dir),
                     remat=remat)
        try:
            eng.train()
            finals[arm] = jax.device_get(eng.params)
            if remat:
                assert eng.remat_plan is not None
                assert eng.remat_plan.source == "flag"
                assert set(eng.remat_plan.layers) == {"conv1", "ip1"}
        finally:
            eng.close()
    _tree_equal(finals["stored"], finals["remat"], "engine params")


def test_spmd_dp2_fsdp2_bitwise_parity():
    from poseidon_tpu.config import MeshConfig
    from poseidon_tpu.parallel.spmd import (ShardingPlan,
                                            build_spmd_train_step,
                                            named_mesh)

    cfg = MeshConfig.parse("dp2,fsdp2")
    mesh = named_mesh(cfg)
    comm = CommConfig(param_arena=True)
    net = Net(zoo.lenet(with_accuracy=False), phase="TRAIN",
              source_shapes=zoo.lenet_shapes(4))
    plan = ShardingPlan.build(net, cfg, comm)
    rplan = plan_remat(net.cost_table(), 0, 0,
                       candidates=remat_mod.remat_candidates(net))
    rs = np.random.RandomState(0)
    batch = {"data": rs.randn(16, 1, 28, 28).astype(np.float32),
             "label": rs.randint(0, 10, size=(16,))}
    finals = {}
    for arm, rp in (("stored", None), ("remat", rplan)):
        ts = build_spmd_train_step(net, SP, mesh, plan, comm,
                                   donate=False, remat_plan=rp)
        p = net.init(jax.random.PRNGKey(0))
        s = init_train_state(p, comm, plan.n_dp)
        for i in range(2):
            p, s, m = ts.step(p, s, batch, jax.random.fold_in(
                jax.random.PRNGKey(5), i))
        finals[arm] = jax.device_get(p)
    _tree_equal(finals["stored"], finals["remat"], "spmd params")


def test_transformer_per_policy_loss_parity():
    """GPT-small-pattern block stack (CPU-sized): every checkpoint policy
    produces the bitwise-identical LOSS (the forward replay is the same
    program). Gradients are allclose, not bitwise: the rematerialized
    backward is a structurally different graph, so XLA's fusion reorders
    reductions by ULPs — unlike the CNN per-layer checkpoint arms, whose
    backward parity stays exact (pinned above)."""
    import jax.numpy as jnp
    from poseidon_tpu.models.transformer import (TransformerConfig,
                                                 forward, init_params,
                                                 lm_loss)

    cfg = TransformerConfig(vocab_size=128, d_model=64, n_heads=4,
                            n_layers=2, d_ff=128, max_seq=32, remat=False)
    params = init_params(cfg, jax.random.PRNGKey(0))
    toks = jax.random.randint(jax.random.PRNGKey(1), (2, 32), 0, 128)
    tgts = jax.random.randint(jax.random.PRNGKey(2), (2, 32), 0, 128)

    def run(policy):
        def loss(p):
            return lm_loss(forward(p, cfg, toks, remat_policy=policy),
                           tgts)
        return jax.jit(jax.value_and_grad(loss))(params)

    base_l, base_g = run("none")
    for policy in ("dots_saveable", "nothing_saveable"):
        l, g = run(policy)
        np.testing.assert_array_equal(np.asarray(base_l), np.asarray(l),
                                      err_msg=policy)
        for i, (x, y) in enumerate(zip(jax.tree_util.tree_leaves(base_g),
                                       jax.tree_util.tree_leaves(g))):
            np.testing.assert_allclose(
                np.asarray(x), np.asarray(y), rtol=1e-5, atol=1e-6,
                err_msg=f"grads[{policy}] leaf {i}")


def test_wrap_checkpoint_identity_for_none():
    fn = lambda x: x * 2  # noqa: E731
    assert wrap_checkpoint(fn, "none") is fn
    assert wrap_checkpoint(fn, "dots_saveable") is not fn


# --------------------------------------------------------------------------- #
# the measured side
# --------------------------------------------------------------------------- #

def test_measured_peak_api_and_remat_arm_stay_bounded():
    """``memory_analysis()`` reports a real peak for both arms, and the
    maximal-remat arm's peak stays within 10% of the no-remat arm's on
    toy LeNet. Direction is deliberately NOT asserted here: on the CPU
    proxy the buffer arena is conv-scratch-dominated and a toy model's
    checkpoint can land a few KiB either side. What this DOES catch is a
    remat wiring bug that doubles buffers or breaks the measurement
    API."""
    import jax.numpy as jnp

    net, batch = _lenet_setup()
    comm = CommConfig(param_arena=True)
    full_plan = remat_mod.plan_remat(
        net.cost_table(), 0, 0,
        candidates=remat_mod.remat_candidates(net), source="plan")
    assert full_plan.active
    p = net.init(jax.random.PRNGKey(0))
    s = init_train_state(p, comm, N_DEV)
    args = (p, s, {k: jnp.asarray(v) for k, v in batch.items()},
            jax.random.PRNGKey(7))
    peaks = []
    for rp in (None, full_plan):
        ts = build_train_step(net, SP, make_mesh(), comm, remat_plan=rp)
        peaks.append(remat_mod.measured_peak_bytes(
            ts.lowerable.lower(*args).compile()))
    base, full = peaks
    assert base > 0, "memory_analysis() returned no peak"
    assert full > 0
    assert abs(full - base) / base < 0.10


def test_plan_for_net_step_measured_source():
    net, batch = _lenet_setup()
    comm = CommConfig(param_arena=True)
    ts = build_train_step(net, SP, make_mesh(), comm)
    p = net.init(jax.random.PRNGKey(0))
    s = init_train_state(p, comm, N_DEV)
    import jax.numpy as jnp
    args = (p, s, {k: jnp.asarray(v) for k, v in batch.items()},
            jax.random.PRNGKey(7))
    tight = remat_mod.plan_for_net_step(net, ts.lowerable, args, 1)
    assert tight.source == "measured"
    assert tight.measured_peak_bytes > 0
    assert tight.active          # 1-byte budget cannot fit: must remat
    roomy = remat_mod.plan_for_net_step(net, ts.lowerable, args, 10**12)
    assert not roomy.active      # fits: identity plan


# --------------------------------------------------------------------------- #
# what a unit keeps: the Pallas forward kernels' named results (PR 49) and its
# gated FFN's products (PR 57)
# --------------------------------------------------------------------------- #

from poseidon_tpu.core.layers import FFN_SAVED                  # noqa: E402
from poseidon_tpu.ops.kda import SCAN_SAVED                     # noqa: E402
from poseidon_tpu.ops.pallas_kernels import FLASH_SAVED         # noqa: E402
from poseidon_tpu.proto.messages import load_net_from_string   # noqa: E402

UNITS = "/l\\d+_/,/lm_/"            # the token cells' --remat: a layer a unit
KEEPS = {"replayed": (), "flash": FLASH_SAVED,
         "all": SCAN_SAVED + FLASH_SAVED,                   # PR 49's first
         "ffn": FFN_SAVED + SCAN_SAVED + FLASH_SAVED}       # keep_rungs' first


def _hybrid(n=1, s=256, source="tokens.txt", **kw):
    """Two layers: a Gated DeltaNet scan (l0), then full ATTENTION (l1)."""
    return zoo.olmo_hybrid(batch=n, source=source, **{**dict(
        n_layers=2, full_every=2, hidden=128, heads=2, heads_held=1,
        key_head_dim=16, value_head_dim=32, attn_head_dim=128, ffn_width=64,
        vocab=128), **kw})


def _hybrid_net(n=1, s=256, **kw):
    return Net(load_net_from_string(zoo.to_prototxt(_hybrid(n, s, **kw))),
               "TRAIN", source_shapes={"tokens": (n, s), "targets": (n, s)})


def _unit_plan(net, keep=()):
    layers, segments = remat_mod.resolve_entries(
        [l.name for l in net.layers], UNITS.split(","))
    return RematPlan(layers=layers, segments=segments, keep=tuple(keep),
                     source="flag")


def _token_avals(net, n=1, s=256):
    import jax.numpy as jnp
    return (jax.eval_shape(net.init, jax.random.PRNGKey(0)),
            {k: jax.ShapeDtypeStruct((n, s), jnp.int32)
             for k in ("tokens", "targets")})


@pytest.mark.parametrize("kernel, forward", [("flash", "name=flash_fwd"),
                                            ("scan", "name=gdn_scan_fwd")])
@pytest.mark.parametrize("arm", sorted(KEEPS))
def test_a_unit_that_keeps_a_kernel_s_results_runs_it_once(
        arm, kernel, forward, monkeypatch):
    """The gradient's jaxpr of the two-layer net under one checkpoint a
    layer, lowered for the TPU: a unit's Pallas forward kernel stands twice
    (the forward, the backward's replay) where its results are not kept and
    once where they are; the backward kernels once either way."""
    monkeypatch.setenv("POSEIDON_FORCE_PALLAS", "1")
    net = _hybrid_net()
    plan = _unit_plan(net, KEEPS[arm])
    jaxpr = jax.make_jaxpr(jax.grad(lambda p, b: net.apply(
        p, b, train=True, **plan.apply_args).loss))(*_token_avals(net))
    text = str(jaxpr)
    kept = set(FLASH_SAVED if kernel == "flash" else SCAN_SAVED) \
        <= set(KEEPS[arm])
    assert text.count(forward) == (1 if kept else 2)
    assert len(re.findall(r"name=flash_bwd\b", text)) == 1
    assert text.count("name=gdn_scan_bwd") == 1
    # what the program names under its units, as the Engine reads it
    from poseidon_tpu.runtime.attribution import unit_residuals
    n_params = len(jax.tree.leaves(net.param_defs))
    named, stored = unit_residuals(
        jaxpr, plan, batch_args=range(n_params, n_params + 2))
    # the units' stored inputs: the residual stream (1, 256, 128) into each
    # layer and into the head, f32; the tokens and targets are arguments
    assert stored == 3 * 256 * 128 * 4
    assert {n for n, _, _ in named} == set(KEEPS[arm])
    if arm == "ffn":
        # a layer's gate, up and down (its norm reads the down product),
        # the linear layer's z and gated output projection besides
        assert sorted((n, u) for n, u, _ in named if n in FFN_SAVED) == \
            [("ffn_in", 0)] * 3 + [("ffn_in", 1)] * 2 \
            + [("ffn_out", 0)] * 2 + [("ffn_out", 1)]
    assert {u for n, u, _ in named if n in FLASH_SAVED} <= {1}
    assert {u for n, u, _ in named if n in SCAN_SAVED} <= {0}
    if arm == "all":
        sizes = {n: b for n, _, b in named}
        # o (1, 256, 1 head of 128 lanes) and lse (1, 1, 256) in f32; the
        # scan's o at its padded 128 lanes and 4 chunk states of 128 x 128
        assert sizes == {"flash_out": 256 * 128 * 4, "flash_lse": 256 * 4,
                         "scan_out": 256 * 128 * 4,
                         "scan_states": 4 * 128 * 128 * 4}


def test_keep_rungs_follow_what_the_program_makes():
    """The order of giving up: the scans' results first, the flash
    kernels' last, then nothing; a rung that keeps no more than the next
    of what the program makes is no rung."""
    every = SCAN_SAVED + FLASH_SAVED
    first = FFN_SAVED + every
    assert remat_mod.keep_rungs() == [first, every, FLASH_SAVED, ()]
    assert remat_mod.keep_rungs(set(every)) == [every, FLASH_SAVED, ()]
    assert remat_mod.keep_rungs(set(FLASH_SAVED)) == [FLASH_SAVED, ()]
    assert remat_mod.keep_rungs(set(SCAN_SAVED)) == [SCAN_SAVED, ()]
    assert remat_mod.keep_rungs(set()) == [()]
    plan = RematPlan(layers=("a",), keep=FLASH_SAVED, source="flag")
    assert RematPlan.from_doc(plan.to_doc()) == plan
    assert plan.apply_args == {"remat": ("a",), "remat_keep": FLASH_SAVED}


@pytest.mark.parametrize("made, rungs", [
    # all three families: the FFN's products go first, then the scans'
    (FFN_SAVED + SCAN_SAVED + FLASH_SAVED,
     [FFN_SAVED + SCAN_SAVED + FLASH_SAVED, SCAN_SAVED + FLASH_SAVED,
      FLASH_SAVED, ()]),
    # two
    (FFN_SAVED + FLASH_SAVED, [FFN_SAVED + FLASH_SAVED, FLASH_SAVED, ()]),
    (FFN_SAVED + SCAN_SAVED, [FFN_SAVED + SCAN_SAVED, SCAN_SAVED, ()]),
    # one: a down product that nothing reads again is not made
    (FFN_SAVED, [FFN_SAVED, ()]),
    (("ffn_in",), [("ffn_in",), ()]),
    (SCAN_SAVED, [SCAN_SAVED, ()]),
    # none
    ((), [()]),
], ids=["ffn+scan+flash", "ffn+flash", "ffn+scan", "ffn", "ffn_in", "scan",
        "none"])
def test_keep_rungs_with_the_ffn_names(made, rungs):
    """``keep_rungs(made)`` for programs that make all three families of
    names, two, one, none: FFN + scan + flash -> scan + flash -> flash ->
    (), each of what the program makes, no rung twice."""
    got = remat_mod.keep_rungs(set(made))
    assert got == rungs and len(set(got)) == len(got)


def _replayed_products(jaxpr) -> list:
    """The scopes of the ``dot_general``s that the replays (the ``remat2``
    equations) of a traced gradient run of the FORWARD pass: jax marks
    those ``rematted_computation/<layer>``; a replay's backward products
    carry the layer's scope alone."""
    found = []

    def walk(inner, replay):
        for eqn in inner.eqns:
            scope = str(eqn.source_info.name_stack)
            if replay and eqn.primitive.name == "dot_general" \
                    and "rematted_computation/" in scope:
                found.append(scope.rsplit("/", 1)[-1])
            for sub in jax.core.jaxprs_in_params(eqn.params):
                walk(sub, replay or eqn.primitive.name == "remat2")

    walk(jaxpr.jaxpr, False)
    return found


@pytest.mark.parametrize("arm", sorted(KEEPS))
def test_a_unit_that_keeps_its_ffn_products_replays_none(arm):
    """The traced gradient of the two-layer net, one checkpoint a layer:
    the replay of a unit that keeps the FFN names runs no ``dot_general``
    of a gate, an up or a down product (nor of the linear mixer's z and
    gated output projection, which the same rule names); every other arm
    replays each of them once, and all arms replay the other products (q,
    k, v, the head)."""
    net = _hybrid_net()
    plan = _unit_plan(net, KEEPS[arm])
    jaxpr = jax.make_jaxpr(jax.grad(lambda p, b: net.apply(
        p, b, train=True, **plan.apply_args).loss))(*_token_avals(net))
    replayed = _replayed_products(jaxpr)
    gated = sorted(f"l{i}_ffn_{p}" for i in (0, 1)
                   for p in ("gate", "up", "down")) \
        + ["l0_gdn_o", "l0_gdn_z"]
    assert {l.name for l in net.layers if getattr(l, "saved_as", None)} \
        == set(gated)
    assert sorted(n for n in replayed if n in gated) == \
        ([] if arm == "ffn" else sorted(gated))
    assert {"l0_gdn_q", "l0_gdn_k", "l0_gdn_v", "lm_head"} <= set(replayed)


def _grads(net, params, batch, plan):
    args = plan.apply_args if plan is not None else {}
    return jax.jit(jax.value_and_grad(lambda p: net.apply(
        p, batch, train=True, **args).loss))(params)


@pytest.mark.parametrize("arm", ["replayed", "all", "ffn"])
def test_loss_and_every_gradient_bitwise_across_the_arms(arm):
    """Stored (no checkpoint) against replayed and kept, a case a rung that
    the CPU's program tells apart: the loss and every leaf's gradient, bit
    for bit (the chunked scan names its results and the gated FFNs their
    products; attention is the dense op, so ``flash`` is ``replayed``)."""
    import jax.numpy as jnp
    net = _hybrid_net(s=128)
    params = net.init(jax.random.PRNGKey(3))
    key = jax.random.PRNGKey(5)
    batch = {"tokens": jax.random.randint(key, (1, 128), 0, 128),
             "targets": jax.random.randint(jax.random.fold_in(key, 1),
                                           (1, 128), 0, 128)}
    want = _grads(net, params, batch, None)
    got = _grads(net, params, batch, _unit_plan(net, KEEPS[arm]))
    assert float(jnp.abs(want[0])) > 0
    _tree_equal(want, got, arm)


@pytest.mark.parametrize("arm", ["replayed", "kept"])
def test_flash_kernel_under_a_unit_bitwise(arm):
    """The flash kernels interpreted, under one unit with the projection
    that follows them: output and the three gradients equal the stored
    arm's bit for bit, kept or replayed."""
    import jax.numpy as jnp
    from poseidon_tpu.ops.pallas_kernels import flash_attention
    q, k, v, w = (jax.random.normal(jax.random.PRNGKey(i), shape)
                  for i, shape in enumerate(
                      [(1, 2, 64, 16)] * 3 + [(16, 16)]))

    def body(q, k, v):
        return jnp.tanh(flash_attention(q, k, v, True, interpret=True) @ w)

    def run(f):
        return jax.jit(jax.value_and_grad(
            lambda *a: jnp.sum(f(*a) ** 2), argnums=(0, 1, 2)))(q, k, v)

    unit = remat_mod.checkpoint_unit(
        body, FLASH_SAVED if arm == "kept" else ())
    text = str(jax.make_jaxpr(jax.grad(
        lambda *a: jnp.sum(unit(*a) ** 2), argnums=(0, 1, 2)))(q, k, v))
    assert text.count("name=flash_fwd") == (1 if arm == "kept" else 2)
    _tree_equal(run(body), run(unit), arm)


def _token_engine(tmp_path, **kw):
    """The two-layer net through the Engine, a sequence a device: HDF5
    tokens, ADAM with a clip, one checkpoint a layer where ``remat`` says
    so."""
    import h5py

    from poseidon_tpu.proto.messages import load_solver
    from poseidon_tpu.runtime.engine import Engine
    tmp_path.mkdir(parents=True, exist_ok=True)
    stream = np.random.RandomState(7).randint(0, 128, 129).astype(np.int32)
    with h5py.File(tmp_path / "tokens.h5", "w") as h:
        h["data"] = np.tile(stream[:-1], (2 * N_DEV, 1))
        h["label"] = np.tile(stream[1:], (2 * N_DEV, 1))
    (tmp_path / "tokens.txt").write_text(str(tmp_path / "tokens.h5") + "\n")
    (tmp_path / "net.prototxt").write_text(zoo.to_prototxt(_hybrid(
        s=128, source=str(tmp_path / "tokens.txt"), hidden=64,
        attn_head_dim=16)))
    (tmp_path / "solver.prototxt").write_text(
        f'net: "{tmp_path / "net.prototxt"}"\nsolver_type: ADAM\n'
        f'base_lr: 0.004\nlr_policy: "fixed"\nmomentum: 0.9\n'
        f'momentum2: 0.95\nweight_decay: 0.1\nclip_gradients: 1.0\n'
        f'max_iter: 3\ndisplay: 0\nsnapshot: 0\n'
        f'snapshot_after_train: false\nsnapshot_prefix: "snap/x"\n'
        f'random_seed: 3\n')
    return Engine(load_solver(str(tmp_path / "solver.prototxt")),
                  output_dir=str(tmp_path), **kw)


def _lowered_text(eng) -> str:
    batch = eng._next_batch(eng.train_pipelines)
    return eng.train_step.lowerable.lower(
        eng.params, eng.state, batch, eng.rng).as_text()


@pytest.fixture(scope="module")
def engine_arms(tmp_path_factory):
    """name -> (loss after 3 steps, parameters, stats sections, the lowered
    text of the step the Engine ended on) of the Engine's arms, one cache
    directory: stored (no checkpoint); replayed (``--remat`` through jit:
    no compiled step to measure, so the units keep nothing, the parent's
    program); kept (``--remat``, no budget: on a backend with no memory
    statistics the names stay); warm (the same job again: loads the kept
    step); tight (``--hbm_budget_gb`` too small for anything). The cache
    is this fixture's while it runs (``conftest.jax_cache_env`` is a
    test's)."""
    from conftest import _set_jax_cache_dir

    from poseidon_tpu import config
    from poseidon_tpu.runtime.compile_cache import enable_compile_cache
    tmp_path = tmp_path_factory.mktemp("arms")
    before = jax.config.jax_compilation_cache_dir
    arms = {}
    with pytest.MonkeyPatch.context() as patch:
        patch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "cc"))
        _set_jax_cache_dir(str(tmp_path / "cc"))
        enable_compile_cache()
        try:
            for name, kw in (
                    ("stored", {}), ("replayed", {"remat": UNITS}),
                    ("kept", {"remat": UNITS}), ("warm", {"remat": UNITS}),
                    ("tight", {"remat": UNITS, "hbm_budget_gb": 1e-6})):
                eng = _token_engine(tmp_path / name, **kw)
                try:
                    eng._aot_enabled = name != "replayed"
                    loss = eng.train()["loss"]
                    arms[name] = (loss, jax.device_get(eng.params),
                                  dict(eng.stats.sections),
                                  _lowered_text(eng))
                finally:
                    eng.close()
        finally:
            config.set_compile_cache_config(cache_dir="", aot_steps=True)
            _set_jax_cache_dir(before)
    return arms


@pytest.mark.parametrize("arm", ["replayed", "kept", "warm", "tight"])
def test_engine_arms_bitwise_and_counted(arm, engine_arms):
    """Through Engine steps: every arm's loss and updated parameters equal
    the stored arm's bit for bit, and ``stats.yaml: remat`` and the facts
    line's ``compiled_step`` say what the units keep, in how many bytes
    and units, the compiled peak, what it was held to, and the compiles
    the decision took (a loaded step: what was decided when stored)."""
    loss, params, sections, text = engine_arms[arm]
    assert loss == engine_arms["stored"][0]
    _tree_equal(engine_arms["stored"][1], params, arm)
    doc = sections["remat"]
    if arm == "replayed":
        assert doc["keep"] == [] and "compiled_step" not in sections
        return
    assert sections["compiled_step"]["remat_keep"] == {
        k: doc[k] for k in ("keep", "kept_units", "kept_bytes",
                            "kept_bytes_by_name", "floor_bytes",
                            "compiled_peak_bytes", "held_to_bytes",
                            "compiles", "passed_over")}
    # (tight: the XLA cache may answer with the replayed arm's program,
    # which is the same program)
    assert sections["compiled_step"]["source"] in {
        "warm": ("loaded",), "kept": ("compiled",),
        "tight": ("compiled", "xla_cache")}[arm]
    assert doc["compiled_peak_bytes"] > 0
    if arm == "tight":
        # nothing fits: the parent's program, text for text
        assert (doc["keep"], doc["kept_units"], doc["kept_bytes"]) == \
            ([], 0, 0)
        # the arguments alone are over it: both rungs that keep something
        # are passed over, and the one compile is the last rung's
        assert doc["compiles"] == 1 and doc["held_to_bytes"] == 1073
        assert doc["passed_over"] == [
            "ffn_in+ffn_out+scan_out+scan_states", "scan_out+scan_states"]
        assert doc["floor_bytes"] > 1073 and doc["kept_bytes_by_name"] == {}
        assert text == engine_arms["replayed"][3]
        assert text != engine_arms["kept"][3]
    else:
        # the scan's o (1, 128, 1, 32) and two chunk states (16 x 32), f32;
        # two layers' gate and up (128 x 64) and the linear layer's z
        # (128 x 32); two down products and the gated output projection
        # (128 x 64), which the norms behind them read again
        assert doc["keep"] == sorted(FFN_SAVED + SCAN_SAVED)
        assert (doc["kept_units"], doc["compiles"]) == (2, 1)
        assert doc["kept_bytes_by_name"] == {
            "ffn_in": (4 * 64 + 32) * 128 * 4, "ffn_out": 3 * 64 * 128 * 4,
            "scan_out": 128 * 32 * 4, "scan_states": 2 * 16 * 32 * 4}
        assert doc["kept_bytes"] == sum(doc["kept_bytes_by_name"].values())
        assert doc["floor_bytes"] > doc["kept_bytes"] // N_DEV
        assert doc["passed_over"] == []
        assert doc["held_to_bytes"] == 0       # no statistics, no budget
        assert doc == engine_arms["kept"][2]["remat"]


@pytest.mark.parametrize("case", ["falls", "passed_over"])
def test_compile_step_under_a_fake_budget(case, tmp_path, jax_cache_env,
                                          engine_arms, monkeypatch):
    """``Engine._compile_step`` with the compiler's accounting faked.
    ``falls``: the first rung's step (the FFN names) compiles over the
    budget, the next (the scan names, PR 49's first) within it: two
    compiles, the second rung's names. ``passed_over``: a budget between
    the two rungs' floors (arguments + stored inputs + kept bytes, a
    device's even share): the first rung is not compiled at all."""
    from poseidon_tpu.runtime.compile_cache import enable_compile_cache
    enable_compile_cache()
    kept = engine_arms["kept"][2]["remat"]
    ffn = sum(b for n, b in kept["kept_bytes_by_name"].items()
              if n in FFN_SAVED)
    peaks = []

    def fake_peak(compiled):
        peaks.append(2**31 if case == "falls" and not peaks else 1)
        return peaks[-1]

    monkeypatch.setattr(remat_mod, "measured_peak_bytes", fake_peak)
    budget = 2**30 if case == "falls" \
        else kept["floor_bytes"] - ffn // (2 * N_DEV)
    eng = _token_engine(tmp_path, remat=UNITS, hbm_budget_gb=budget / 2**30)
    try:
        loss = eng.train()["loss"]
        doc = eng.stats.sections["remat"]
    finally:
        eng.close()
    assert loss == engine_arms["stored"][0]
    assert doc["keep"] == sorted(SCAN_SAVED) and doc["kept_units"] == 1
    assert doc["kept_bytes_by_name"] == {
        n: kept["kept_bytes_by_name"][n] for n in sorted(SCAN_SAVED)}
    assert doc["held_to_bytes"] == budget
    assert doc["floor_bytes"] == kept["floor_bytes"] - ffn // N_DEV
    if case == "falls":
        assert (doc["compiles"], doc["passed_over"]) == (2, [])
        assert peaks == [2**31, 1] and doc["compiled_peak_bytes"] == 1
    else:
        assert (doc["compiles"], doc["passed_over"]) == (
            1, ["ffn_in+ffn_out+scan_out+scan_states"])
        assert peaks == [1]


def test_stats_yaml_carries_the_kept_counter(tmp_path, jax_cache_env):
    """What a reader of the run's ``stats.yaml`` finds."""
    from poseidon_tpu.runtime.compile_cache import enable_compile_cache
    from poseidon_tpu.runtime.metrics import read_stats_yaml
    enable_compile_cache()
    eng = _token_engine(tmp_path, remat=UNITS)
    try:
        eng.train(max_iter=1)
        eng.stats.dump_yaml(str(tmp_path / "stats.yaml"))
    finally:
        eng.close()
    doc = read_stats_yaml(str(tmp_path / "stats.yaml"))   # leaves: strings
    assert doc["remat"]["keep"] == str(sorted(FFN_SAVED + SCAN_SAVED))
    assert doc["remat"]["kept_units"] == "2"
    by_name = {k: int(v)
               for k, v in doc["remat"]["kept_bytes_by_name"].items()}
    assert by_name["scan_out"] + by_name["scan_states"] \
        == 128 * 32 * 4 + 2 * 16 * 32 * 4
    assert int(doc["remat"]["kept_bytes"]) == sum(by_name.values())
    assert doc["remat"]["passed_over"] == "[]"
    assert int(doc["remat"]["compiled_peak_bytes"]) > 0
    assert doc["compiled_step"]["remat_keep"]["compiles"] == "1"


@pytest.mark.parametrize("kernel", ["flash", "scan"])
def test_no_checkpoint_lowers_to_the_same_text_without_the_tags(
        kernel, monkeypatch):
    """Outside a checkpoint that asks for it a name is the identity and
    lowers to nothing: the gradient of the two-layer net with NO unit
    lowers to the text it has with ``checkpoint_name`` taken out of the
    three modules (the Pallas arms as lowered for the TPU, outside the
    Mosaic payloads, and the ``jax.numpy`` scan the CPU runs)."""
    from poseidon_tpu.ops import kda, kda_pallas, pallas_kernels
    platforms = None
    if kernel == "flash":
        monkeypatch.setenv("POSEIDON_FORCE_PALLAS", "1")
        platforms = ("tpu",)
    net = _hybrid_net()

    def lowered():
        text = jax.jit(jax.grad(lambda p, b: net.apply(
            p, b, train=True).loss)).trace(*_token_avals(net)).lower(
                lowering_platforms=platforms).as_text()
        # private functions are numbered as a process meets them, and a
        # Mosaic payload (its debug locations) is not the same bytes twice
        text = re.sub(r"@(\w+?)_\d+\b", r"@\1", text)
        return re.sub(r'backend_config = "[^"]*"', "", text)

    tagged = lowered()
    for module in (kda, kda_pallas, pallas_kernels):
        monkeypatch.setattr(module, "checkpoint_name", lambda x, name: x)
    assert ("tpu_custom_call" in tagged) == (kernel == "flash")
    assert tagged == lowered()
