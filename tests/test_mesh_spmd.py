"""SPMD sharding planner (parallel/spmd.py): mesh, plan, parity, census.

The acceptance pins (ISSUE 10 / ROADMAP item 1):
- make_mesh fails loudly (no silent truncation; balanced multi-axis
  default);
- every DENSE leaf gets a placement, the fsdp shard ranges cover the
  padded arena disjointly, and SFB/TOPK layers opt out of tp;
- LeNet under dp2,fsdp2 is BITWISE identical to the replicated control
  on the same mesh (the hierarchical reduce-scatter -> all-reduce order
  matches the control's psum -> psum association exactly); dp2,tp2
  agrees to float-associativity tolerance (a sharded contraction
  re-associates its reduction);
- the sharded-state (ZeRO) layout computes the same numbers with 1/fsdp
  persistent arena bytes per device;
- the lowered collective census equals the planned schedule (the same
  comparison the checked-in HLO contracts gate in CI);
- snapshots stay canonical per-leaf: a dp2,fsdp2 run's snapshot restores
  bit-identically into a replicated run.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from poseidon_tpu.config import MeshConfig
from poseidon_tpu.core.net import Net
from poseidon_tpu.models import zoo
from poseidon_tpu.parallel import (CommConfig, build_ssp_train_step,
                                   init_ssp_state, init_train_state,
                                   make_mesh)
from poseidon_tpu.parallel.mesh import balanced_shape
from poseidon_tpu.parallel.spmd import (COL, ROW, ShardingPlan,
                                        build_spmd_train_step,
                                        fsdp_shard_ranges, named_mesh,
                                        shard_train_state,
                                        sharded_state_avals,
                                        unshard_train_state)
from poseidon_tpu.parallel.strategies import SFB, TOPK
from poseidon_tpu.proto.messages import SolverParameter
from poseidon_tpu.runtime.hlo_comm import collective_census_stablehlo

pytestmark = pytest.mark.mesh

N_DEV = 8
BATCH = 16


@pytest.fixture(scope="module", autouse=True)
def _release_compiled_steps():
    """This module compiles ~a dozen distinct SPMD step variants; drop
    them from jax's global caches at module teardown so the rest of the
    tier-1 sweep doesn't carry their executables as resident ballast."""
    yield
    jax.clear_caches()

SP = SolverParameter(base_lr=0.01, lr_policy="fixed", momentum=0.9,
                     weight_decay=0.0005)


def _lenet(n_dp):
    return Net(zoo.lenet(with_accuracy=False), phase="TRAIN",
               source_shapes=zoo.lenet_shapes(BATCH // n_dp))


def _batch(rng):
    return {
        "data": jnp.asarray(rng.randn(BATCH, 1, 28, 28).astype(np.float32)),
        "label": jnp.asarray(rng.randint(0, 10, size=(BATCH,))),
    }


def _tree_equal(a, b, what=""):
    assert set(a) == set(b)
    for l in a:
        for k in a[l]:
            np.testing.assert_array_equal(
                np.asarray(a[l][k]), np.asarray(b[l][k]),
                err_msg=f"{what} {l}/{k}")


def _tree_close(a, b, what=""):
    assert set(a) == set(b)
    for l in a:
        for k in a[l]:
            np.testing.assert_allclose(
                np.asarray(a[l][k]), np.asarray(b[l][k]),
                rtol=2e-6, atol=1e-7, err_msg=f"{what} {l}/{k}")


# Two arms of one step agree BITWISE while the update has one rounding per
# element (momentum 0: h' = rate * (g + decay * w)): that pins what the
# arms are built to share, the association order of the gradient sums.
# With momentum the update is m*h + rate*g, and XLA:CPU contracts that
# into an FMA one way in the fusion over a flat fsdp shard and the other
# way in the per-leaf fusions of the replicated arm, so history differs by
# 1 ulp from the second step (the first has h = 0) and parameters by at
# most 1.5e-8 after three steps, 2.7e-8 after the Engine's eight (3 ulps
# at their magnitude, 0.1-0.5). Not ours to order; _tree_close says so.
_PARITY = pytest.mark.parametrize(
    "momentum,same", [(0.0, _tree_equal), (0.9, _tree_close)],
    ids=["plain_sgd_bitwise", "momentum_ulps"])


def _sp(momentum):
    return SolverParameter(base_lr=0.01, lr_policy="fixed",
                           momentum=momentum, weight_decay=0.0005)


def _run(net, mesh, plan, comm, params, batch, rng, n_steps=3, sp=SP):
    ts = build_spmd_train_step(net, sp, mesh, plan, comm, donate=False)
    p, s = params, init_train_state(params, comm, plan.n_dp)
    for i in range(n_steps):
        p, s, m = ts.step(p, s, batch, jax.random.fold_in(rng, i))
    return ts, p, s, m


# --------------------------------------------------------------------------- #
# make_mesh footguns (satellite: no silent truncation, balanced default)
# --------------------------------------------------------------------------- #

def test_make_mesh_rejects_too_many_devices():
    assert jax.device_count() == N_DEV
    with pytest.raises(ValueError, match="only 8 exist"):
        make_mesh(num_devices=16)
    with pytest.raises(ValueError, match="must be positive"):
        make_mesh(num_devices=0)


def test_make_mesh_balanced_multi_axis_default():
    m = make_mesh(axes=("a", "b"))
    assert tuple(m.shape.values()) == (4, 2)       # not the old (8, 1)
    m3 = make_mesh(axes=("a", "b", "c"))
    assert tuple(m3.shape.values()) == (2, 2, 2)
    assert balanced_shape(12, 2) == (4, 3)
    assert balanced_shape(7, 2) == (7, 1)


def test_make_mesh_shape_mismatch_is_loud():
    with pytest.raises(ValueError, match="needs 6 devices, have 8"):
        make_mesh(axes=("a", "b"), shape=(3, 2))
    with pytest.raises(ValueError, match="2 dims for 1 axes"):
        make_mesh(axes=("a",), shape=(4, 2))


def test_mesh_config_parse():
    cfg = MeshConfig.parse("dp2,fsdp2,tp1")
    assert (cfg.data, cfg.fsdp, cfg.tp) == (2, 2, 1)
    assert cfg.n_devices == 4 and cfg.active and cfg.shard
    assert not MeshConfig.parse("dp4").active
    assert not MeshConfig.parse("dp2,fsdp2,replicated").shard
    with pytest.raises(ValueError, match="cannot parse"):
        MeshConfig.parse("dp2,zz3")
    with pytest.raises(ValueError, match="given twice"):
        MeshConfig.parse("dp2,dp4")


# --------------------------------------------------------------------------- #
# planner unit contracts
# --------------------------------------------------------------------------- #

def test_every_dense_leaf_gets_a_placement():
    net = _lenet(4)
    plan = ShardingPlan.build(net, MeshConfig(data=2, fsdp=2, tp=1),
                              CommConfig())
    for lname, defs in net.param_defs.items():
        for pdef in defs:
            assert (lname, pdef.name) in plan.leaf_plan, (lname, pdef.name)
            assert plan.leaf_plan[(lname, pdef.name)].placement == \
                "arena_fsdp"


def test_planner_megatron_pairing_on_lenet():
    """ip1 -> relu1 (in-place) -> ip2 becomes the COL(sharded-out) -> ROW
    pair with the resharding point at the ROW psum."""
    net = _lenet(4)
    plan = ShardingPlan.build(net, MeshConfig(data=2, fsdp=1, tp=2),
                              CommConfig())
    assert plan.tp_layers["ip1"].mode == COL
    assert not plan.tp_layers["ip1"].gather
    assert plan.tp_layers["ip2"].mode == ROW
    assert "ip1" in plan.sharded_blobs
    assert plan.leaf_plan[("ip1", "w")].spec == \
        jax.sharding.PartitionSpec("tp", None)
    assert plan.leaf_plan[("ip2", "w")].spec == \
        jax.sharding.PartitionSpec(None, "tp")


def test_tp_opt_out_for_sfb_topk_layers():
    net = _lenet(4)
    comm = CommConfig(layer_strategies={"ip1": SFB, "ip2": TOPK})
    plan = ShardingPlan.build(net, MeshConfig(data=2, fsdp=1, tp=2), comm)
    assert plan.tp_layers == {}
    for lname in ("ip1", "ip2"):
        for pdef in net.param_defs[lname]:
            lp = plan.leaf_plan[(lname, pdef.name)]
            assert lp.placement == "replicated"
            assert lp.spec == jax.sharding.PartitionSpec()


def test_fsdp_shard_ranges_cover_disjointly():
    net = _lenet(4)
    for f, bucket_mb in ((2, 0.05), (4, 0.3), (8, 4.0)):
        layout = net.arena_layout(bucket_mb=bucket_mb, align=f)
        ranges = fsdp_shard_ranges(layout, f)
        assert len(ranges) == f
        seen = np.zeros(layout.padded_total, np.int32)
        for dev_ranges in ranges:
            assert len(dev_ranges) == layout.n_buckets
            for lo, hi in dev_ranges:
                seen[lo:hi] += 1
        assert (seen == 1).all()        # disjoint cover, no gaps
        assert layout.padded_total % f == 0


def test_fsdp_without_arena_is_rejected():
    net = _lenet(4)
    with pytest.raises(ValueError, match="rides the flat parameter arena"):
        ShardingPlan.build(net, MeshConfig(data=2, fsdp=2, tp=1),
                           CommConfig(param_arena=False))


@pytest.mark.parametrize("tier", ["mesh", "staleness"])
@pytest.mark.parametrize("what,fields", [
    ("ADAM", dict(solver_type="ADAM")),
    ("clip_gradients", dict(clip_gradients=1.0))])
def test_adam_and_clip_refused_by_name(tier, what, fields):
    """The fsdp-sharded step and the SSP step carry one history buffer
    and no global norm: ADAM and the clip are refused when the step is
    built, by name, never trained as plain SGD."""
    sp = SolverParameter(base_lr=0.01, lr_policy="fixed", momentum=0.9,
                         **fields)
    cfg = MeshConfig.parse("dp2,fsdp2")
    mesh, net, comm = named_mesh(cfg), _lenet(4), CommConfig()
    with pytest.raises(ValueError, match=what) as e:
        if tier == "mesh":
            build_spmd_train_step(net, sp, mesh,
                                  ShardingPlan.build(net, cfg, comm), comm)
        else:
            build_ssp_train_step(net, sp, make_mesh(), 1, comm)
    assert "ADAM" in str(e.value) and "clip_gradients" in str(e.value)


# --------------------------------------------------------------------------- #
# parity: sharded vs replicated control on the SAME mesh
# --------------------------------------------------------------------------- #

@_PARITY
def test_lenet_fsdp_parity(rng_np, momentum, same):
    """dp2,fsdp2 sharded arm == replicated arm, params AND history,
    across 3 steps — reduce-scatter + shard-psum reduces in the same
    association order as the control's hierarchical psums (bitwise
    without momentum; see _PARITY for what momentum adds)."""
    sp = _sp(momentum)
    cfg = MeshConfig.parse("dp2,fsdp2")
    mesh = named_mesh(cfg)
    net = _lenet(4)
    comm = CommConfig()
    params = net.init(jax.random.PRNGKey(0))
    batch, rng = _batch(rng_np), jax.random.PRNGKey(7)
    _, p1, s1, m1 = _run(net, mesh,
                         ShardingPlan.build(net, cfg, comm),
                         comm, params, batch, rng, sp=sp)
    _, p2, s2, m2 = _run(net, mesh,
                         ShardingPlan.build(net, cfg, comm,
                                            shard_params=False),
                         comm, params, batch, rng, sp=sp)
    np.testing.assert_allclose(float(m1["loss"]), float(m2["loss"]),
                               rtol=0 if momentum == 0 else 1e-6)
    same(p1, p2, "params")
    same(s1.solver.history, s2.solver.history, "history")


def test_lenet_tp_parity(rng_np):
    """dp2,tp2 (COL ip1 -> ROW ip2) vs the tp-off control on the same
    mesh: loss and params agree to float-associativity tolerance — the
    sharded contraction necessarily re-associates its K/M reductions, so
    bitwise is not achievable (unlike fsdp)."""
    cfg = MeshConfig.parse("dp2,tp2")
    mesh = named_mesh(cfg)
    net = _lenet(2)
    comm = CommConfig()
    params = net.init(jax.random.PRNGKey(0))
    batch, rng = _batch(rng_np), jax.random.PRNGKey(7)
    plan_tp = ShardingPlan.build(net, cfg, comm)
    assert plan_tp.tp_layers            # the pairing actually engaged
    _, p1, _, m1 = _run(net, mesh, plan_tp, comm, params, batch, rng)
    _, p2, _, m2 = _run(net, mesh,
                        ShardingPlan.build(net, cfg, comm,
                                           enable_tp=False),
                        comm, params, batch, rng)
    np.testing.assert_allclose(float(m1["loss"]), float(m2["loss"]),
                               rtol=1e-6)
    for l in p1:
        for k in p1[l]:
            np.testing.assert_allclose(
                np.asarray(p1[l][k]), np.asarray(p2[l][k]),
                rtol=1e-5, atol=1e-7, err_msg=f"{l}/{k}")


@_PARITY
def test_sharded_state_matches_canonical(rng_np, momentum, same):
    """The ZeRO layout (params+momentum living 1/fsdp per device, param
    all-gather in the prologue) computes the canonical step's numbers
    (bitwise without momentum; see _PARITY), and each device's persistent
    arena shard is exactly padded_total/fsdp elements."""
    sp = _sp(momentum)
    cfg = MeshConfig.parse("dp2,fsdp2")
    mesh = named_mesh(cfg)
    net = _lenet(4)
    comm = CommConfig()
    params = net.init(jax.random.PRNGKey(0))
    batch, rng = _batch(rng_np), jax.random.PRNGKey(7)
    plan = ShardingPlan.build(net, cfg, comm)
    ts, p1, s1, m1 = _run(net, mesh, plan, comm, params, batch, rng,
                          sp=sp)

    ts2 = build_spmd_train_step(net, sp, mesh, plan, comm, donate=False,
                                sharded_state=True)
    st = shard_train_state(params, init_train_state(params, comm, 4),
                           ts2.arena, mesh, plan)
    for sh in st.flat_w.addressable_shards:
        assert sh.data.shape == (ts2.arena.padded_total // 2,)
    for i in range(3):
        st, m2 = ts2.step(st, batch, jax.random.fold_in(rng, i))
    p2, s2 = unshard_train_state(st, ts2.arena, plan)
    np.testing.assert_allclose(float(m1["loss"]), float(m2["loss"]),
                               rtol=0 if momentum == 0 else 1e-6)
    same(p1, p2, "params")
    same(s1.solver.history, s2.solver.history, "history")


def test_sharded_state_avals_lower(rng_np):
    """AOT entry (scripts/aot_tpu_check.py --sections mesh): lowering the
    sharded-state step from ShapeDtypeStruct avals works, and the
    program's per-device argument footprint carries the 1/fsdp arena."""
    cfg = MeshConfig.parse("dp2,fsdp2")
    mesh = named_mesh(cfg)
    net = _lenet(4)
    comm = CommConfig()
    plan = ShardingPlan.build(net, cfg, comm)
    ts = build_spmd_train_step(net, SP, mesh, plan, comm, donate=False,
                               sharded_state=True)
    st = sharded_state_avals(net, ts.arena, plan, mesh)
    from jax.sharding import NamedSharding, PartitionSpec as P
    bspec = NamedSharding(mesh, P(("data", "fsdp")))
    batch = {"data": jax.ShapeDtypeStruct((BATCH, 1, 28, 28), jnp.float32,
                                          sharding=bspec),
             "label": jax.ShapeDtypeStruct((BATCH,), jnp.int32,
                                           sharding=bspec)}
    rng = jax.ShapeDtypeStruct((2,), jnp.uint32,
                               sharding=NamedSharding(mesh, P()))
    txt = ts.lowerable.lower(st, batch, rng).as_text()
    census = collective_census_stablehlo(txt)
    sched = plan.collective_schedule(ts.arena, net, sharded_state=True)
    assert census == sched["counts"]


# --------------------------------------------------------------------------- #
# collective census == planned schedule (the contract gate's comparison)
# --------------------------------------------------------------------------- #

@pytest.mark.parametrize("spec,comm_kw", [
    ("dp2,fsdp2", {}),
    ("dp2,tp2", {}),
    ("dp2,fsdp2,tp2", {}),
    # non-default strategies must be stated too (TOPK compressed psum,
    # SFB factor gathers, arena-off in-backward taps)
    ("dp2,tp2", {"layer_strategies": {"ip2": TOPK}}),
    ("dp2,fsdp2", {"layer_strategies": {"ip1": SFB}}),
    ("dp2,tp2", {"param_arena": False}),
])
def test_collective_census_matches_plan(rng_np, spec, comm_kw):
    cfg = MeshConfig.parse(spec)
    mesh = named_mesh(cfg)
    net = _lenet(cfg.data * cfg.fsdp)
    comm = CommConfig(**comm_kw)
    params = net.init(jax.random.PRNGKey(0))
    plan = ShardingPlan.build(net, cfg, comm)
    ts = build_spmd_train_step(net, SP, mesh, plan, comm, donate=False)
    state = init_train_state(params, comm, plan.n_dp)
    txt = ts.lowerable.lower(params, state, _batch(rng_np),
                             jax.random.PRNGKey(1)).as_text()
    census = collective_census_stablehlo(txt)
    sched = plan.collective_schedule(ts.arena, net, comm=comm)
    assert census == sched["counts"], (census, sched["counts"])
    if cfg.fsdp > 1 and not comm_kw:
        assert sched["counts"]["reduce_scatter"] == ts.arena.n_buckets


def test_size_mismatch_without_tp_plan_is_loud():
    """A wrong-size leaf on a run with no tp plan covering it must fail
    at param resolution, not silently broadcast (the tp-shard escape
    hatch is plan-gated)."""
    net = _lenet(N_DEV)
    params = net.init(jax.random.PRNGKey(0))
    params["ip1"]["b"] = jnp.zeros((1,), jnp.float32)   # wrong size
    x = {"data": jnp.zeros((2, 1, 28, 28)), "label": jnp.zeros((2,),
                                                               jnp.int32)}
    with pytest.raises(ValueError, match="no tensor-parallel plan"):
        net.apply(params, x, train=False)


# --------------------------------------------------------------------------- #
# snapshot portability: canonical per-leaf across meshes
# --------------------------------------------------------------------------- #

def test_snapshot_portable_to_replicated_run(rng_np, tmp_path):
    """A dp2,fsdp2 run's snapshot restores bit-identically (canonical
    per-leaf trees), and a flat replicated data-parallel step consumes
    the restored state directly — cross-mesh portability."""
    from poseidon_tpu.parallel import build_train_step
    from poseidon_tpu.runtime.checkpoint import restore, snapshot

    cfg = MeshConfig.parse("dp2,fsdp2")
    mesh = named_mesh(cfg)
    net = _lenet(4)
    comm = CommConfig()
    params = net.init(jax.random.PRNGKey(0))
    batch, rng = _batch(rng_np), jax.random.PRNGKey(7)
    plan = ShardingPlan.build(net, cfg, comm)
    _, p1, s1, _ = _run(net, mesh, plan, comm, params, batch, rng,
                        n_steps=2)
    prefix = str(tmp_path / "lenet")
    _, statef = snapshot(prefix, net, p1, s1)
    rparams, rstate = restore(statef)
    _tree_equal(p1, rparams, "restored params")
    _tree_equal(s1.solver.history, rstate.solver.history, "restored hist")
    assert int(rstate.solver.it) == 2

    # restored state drives a REPLICATED flat-mesh run (different net
    # instance, different mesh) without conversion
    flat_mesh = make_mesh()
    net2 = _lenet(N_DEV)
    ts2 = build_train_step(net2, SP, flat_mesh, comm, donate=False)
    p2, s2, m2 = ts2.step(rparams, rstate, batch,
                          jax.random.fold_in(rng, 2))
    assert np.isfinite(float(m2["loss"]))


# --------------------------------------------------------------------------- #
# engine / CLI acceptance arm
# --------------------------------------------------------------------------- #

@_PARITY
def test_engine_mesh_cli_vs_replicated(tmp_path, momentum, same):
    """The acceptance criterion end to end: an Engine run under
    ``--mesh dp2,fsdp2`` produces the final params of the
    ``--mesh dp2,fsdp2,replicated`` control run (bitwise without
    momentum; see _PARITY)."""
    import sys
    sys.path.insert(0, str(__import__("pathlib").Path(__file__).parent))
    from test_runtime import _memory_data, _write_mnistish_prototxt
    from poseidon_tpu.proto.messages import load_solver
    from poseidon_tpu.runtime.engine import Engine

    sp = load_solver(_write_mnistish_prototxt(tmp_path, max_iter=8))
    sp.test_interval = 0
    sp.momentum = momentum
    finals = {}
    for spec in ("dp2,fsdp2", "dp2,fsdp2,replicated"):
        eng = Engine(sp, mesh_cfg=MeshConfig.parse(spec),
                     memory_data=_memory_data(),
                     output_dir=str(tmp_path / spec.replace(",", "_")))
        try:
            eng.train()
            finals[spec] = {l: {k: np.asarray(v)
                                for k, v in lp.items()}
                            for l, lp in eng.params.items()}
            assert eng.plan is not None
            assert eng.plan.shard_params == (spec == "dp2,fsdp2")
        finally:
            eng.close()
    same(finals["dp2,fsdp2"], finals["dp2,fsdp2,replicated"], "engine")


# --------------------------------------------------------------------------- #
# SSP tier on the named mesh
# --------------------------------------------------------------------------- #

def test_ssp_fsdp_delta_exchange(rng_np):
    """SSP staleness on a dp2,fsdp2 mesh: the boundary arena delta
    exchange reshards over fsdp (reduce-scatter / all-gather in the
    lowered program) and the run converges like the flat-mesh tier."""
    cfg = MeshConfig.parse("dp2,fsdp2")
    mesh = named_mesh(cfg)
    net = _lenet(4)
    comm = CommConfig()
    plan = ShardingPlan.build(net, cfg, comm)
    params = net.init(jax.random.PRNGKey(0))
    ts = build_ssp_train_step(net, SP, mesh, 1, comm, plan=plan)
    txt = ts.lowerable.lower(
        init_ssp_state(params, plan.n_dp, comm), _batch(rng_np),
        jax.random.PRNGKey(0)).as_text()
    census = collective_census_stablehlo(txt)
    assert census["reduce_scatter"] >= 1
    assert census["all_gather"] >= 1
    st = init_ssp_state(params, plan.n_dp, comm)
    b = _batch(rng_np)
    losses = []
    for i in range(6):
        st, m = ts.step(st, b, jax.random.PRNGKey(i))
        losses.append(float(m["loss"]))
    assert losses[-1] < losses[0]


def test_comm_scopes_attribute_per_axis():
    """The spmd collective scopes are recognized as named attribution
    rows (never residual) and map to their mesh axis."""
    from poseidon_tpu.runtime import attribution as A
    layers = {"conv1", "ip1"}
    for scope, axis in (("grad_rs_bucket0", "fsdp"),
                        ("grad_ar_bucket3", "data"),
                        ("param_ag_bucket1", "fsdp"),
                        ("hist_ag_bucket0", "fsdp"),
                        ("delta_ar_bucket2", "data"),
                        ("delta_rs_bucket0", "fsdp"),
                        ("tp_fwd_ip1", "tp"),
                        ("tp_dx_ip1", "tp"),
                        ("grad_tp_ip1_w_fsdp", "fsdp"),
                        ("grad_tp_ip1_w_data", "data")):
        got = A.scope_of(f"jit(step)/{scope}/psum", layers)
        assert got == (scope, "misc"), (scope, got)
        assert A.comm_axis_of(scope) == axis, scope
    # layer scopes still win over comm detection, and unknowns stay None
    assert A.scope_of("jit(step)/jvp(ip1)/dot", layers) == ("ip1", "fwd")
    assert A.comm_axis_of("optimizer_update") is None


def test_ssp_rejects_tp():
    cfg = MeshConfig.parse("dp2,tp2")
    mesh = named_mesh(cfg)
    net = _lenet(2)
    plan = ShardingPlan.build(net, cfg, CommConfig())
    with pytest.raises(ValueError, match="tensor parallelism"):
        build_ssp_train_step(net, SP, mesh, 1, CommConfig(), plan=plan)
