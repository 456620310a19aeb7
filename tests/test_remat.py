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
    from poseidon_tpu.runtime.attribution import layer_cost_table
    plan = plan_remat(layer_cost_table(net), 0, 0,
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
    from poseidon_tpu.runtime.attribution import layer_cost_table

    cfg = MeshConfig.parse("dp2,fsdp2")
    mesh = named_mesh(cfg)
    comm = CommConfig(param_arena=True)
    net = Net(zoo.lenet(with_accuracy=False), phase="TRAIN",
              source_shapes=zoo.lenet_shapes(4))
    plan = ShardingPlan.build(net, cfg, comm)
    rplan = plan_remat(layer_cost_table(net), 0, 0,
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

    from poseidon_tpu.runtime.attribution import layer_cost_table

    net, batch = _lenet_setup()
    comm = CommConfig(param_arena=True)
    full_plan = remat_mod.plan_remat(
        layer_cost_table(net), 0, 0,
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
