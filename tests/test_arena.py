"""The arena (core/arena.py): gradient buckets where there is a collective.

The arena gives DENSE f32 leaves a static DWBP-ordered offset table. With
more than one device on the sync axes, the step packs their GRADIENTS into
ceil(bytes/arena_bucket_mb) bucket buffers, sums each with one collective
and slices the sums back to leaves; parameters and solver history never
enter the flat buffer, and the update is the per-leaf rule. On one device
there is no arena. Everything here pins those contracts:

- one device: the step holds no ``arena_*`` scope and equals a mesh-free
  per-leaf reference (grad + ``make_update_fn``) bit for bit;
- several devices: the bucketed step computes the per-leaf-collective
  step's numbers on CPU: full LeNet steps are bit-identical end to end,
  and full AlexNet/GoogLeNet steps agree to <= 1 ulp (XLA may pick a
  different cross-replica reduction order for a bucketed all-reduce than
  for a tiny per-leaf psum) — for every solver rule, both numeric
  policies, wire dtypes, gradient accumulation, scan dispatch, and SSP;
- the compiled data-parallel program carries at most
  ceil(total_grad_bytes / arena_bucket_mb) gradient all-reduces instead of
  one per leaf; and
- the flat update rule the fsdp-sharded step keeps (parallel/spmd.py) is
  bit-identical to the per-leaf rule.
"""

import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from poseidon_tpu import config
from poseidon_tpu.core.net import Net
from poseidon_tpu.models import zoo
from poseidon_tpu.parallel import (CommConfig, build_ssp_train_step,
                                   build_train_step, init_ssp_state,
                                   init_train_state, make_mesh)
from poseidon_tpu.proto.messages import SolverParameter
from poseidon_tpu.runtime.hlo_comm import count_gradient_all_reduces

N_DEV = 8
BATCH = 16


@pytest.fixture(scope="module")
def mesh():
    assert jax.device_count() == N_DEV
    return make_mesh()


@pytest.fixture(scope="module")
def mesh1():
    return make_mesh(1)


@pytest.fixture(scope="module")
def lenet_net():
    return Net(zoo.lenet(with_accuracy=False), phase="TRAIN",
               source_shapes=zoo.lenet_shapes(BATCH // N_DEV))


def _batch(rng):
    return {
        "data": jnp.asarray(rng.randn(BATCH, 1, 28, 28).astype(np.float32)),
        "label": jnp.asarray(rng.randint(0, 10, size=(BATCH,))),
    }


def _assert_tree_equal(a, b, msg=""):
    for l in a:
        for k in a[l]:
            np.testing.assert_array_equal(
                np.asarray(a[l][k]), np.asarray(b[l][k]),
                err_msg=f"{msg} {l}/{k}")
    assert set(a) == set(b)


def _ab_step(net, sp, mesh, comm, params, batch, rng, n_steps=1):
    """(step under test, per-leaf reference) after n_steps from the same
    start. On several devices: gradient buckets against per-leaf
    collectives (``param_arena`` on / off). On ONE device: the built step,
    which must hold no arena at all, against a mesh-free grad +
    ``make_update_fn`` step on that device's share of the batch."""
    import dataclasses
    solver_type = sp.solver_type
    if mesh.size == 1:
        from poseidon_tpu.runtime.hlo_layout import build_plain_step
        batch = {k: v[:BATCH // N_DEV] for k, v in batch.items()}
        ts = build_train_step(net, sp, mesh, comm, donate=False)
        assert ts.arena is None and ts.update_route == "leaf"
        state = init_train_state(params, comm, 1, solver_type)
        assert "arena_" not in ts.lowerable.lower(
            params, state, batch, rng).as_text(debug_info=True)
        p, s = params, state
        for i in range(n_steps):
            p, s, m = ts.step(p, s, batch, jax.random.fold_in(rng, i))
        plain = jax.jit(build_plain_step(net, sp))
        rp, rs = params, state.solver
        for i in range(n_steps):
            # the built step folds the device's index (0) into the rng
            rp, rs = plain(rp, rs, batch, jax.random.fold_in(
                jax.random.fold_in(rng, i), 0))
        return [(p, s, m), (rp, state._replace(solver=rs), m)]
    out = []
    for arena_on in (True, False):
        cc = dataclasses.replace(comm, param_arena=arena_on)
        ts = build_train_step(net, sp, mesh, cc, donate=False)
        assert (ts.arena is not None) == arena_on
        p, s = params, init_train_state(params, cc, N_DEV, solver_type)
        for i in range(n_steps):
            p, s, m = ts.step(p, s, batch, jax.random.fold_in(rng, i))
        out.append((p, s, m))
    return out


@pytest.fixture(params=[N_DEV, 1], ids=["dev8", "dev1"])
def any_mesh(request, mesh, mesh1):
    return mesh if request.param == N_DEV else mesh1


# --------------------------------------------------------------------------- #
# offset table / views unit behavior
# --------------------------------------------------------------------------- #

def test_offset_table_is_dwbp_ordered(lenet_net):
    """Slots run in REVERSE forward layer order (the order gradients
    materialize in backward), contiguously from offset 0."""
    layout = lenet_net.arena_layout()
    layer_order = [l.name for l in lenet_net.layers
                   if l.name in lenet_net.param_defs]
    seen = [s.layer for s in layout.slots]
    # first slot belongs to the LAST param layer
    assert seen[0] == layer_order[-1]
    assert seen[-1] == layer_order[0]
    off = 0
    for s in layout.slots:
        assert s.offset == off
        off += s.size
    assert layout.total == off == lenet_net.param_count()


def test_pack_unpack_roundtrip_and_views_grad(lenet_net):
    """unpack(pack(t)) == t bit-for-bit, and the views custom-vjp delivers
    the cotangent PACKED: grad of sum(leaf * const) wrt the bucket buffers
    equals the packed consts — including leaves that SPAN bucket
    boundaries (tiny bucket_mb forces spanning)."""
    layout = lenet_net.arena_layout(bucket_mb=0.037)  # ~9.2k elems/bucket
    assert layout.n_buckets == math.ceil(
        layout.total_bytes() / (0.037 * 1e6))
    params = lenet_net.init(jax.random.PRNGKey(0))
    flat = layout.pack(params)
    assert flat.shape == (layout.total,)
    _assert_tree_equal(layout.unpack(flat), params, "roundtrip")

    rs = np.random.RandomState(1)
    consts = jax.tree_util.tree_map(
        lambda v: jnp.asarray(rs.randn(*v.shape).astype(np.float32)), params)

    def f(*bufs):
        tree = layout.views(*bufs)
        return sum(jnp.vdot(tree[l][k], consts[l][k])
                   for l in tree for k in tree[l])

    grads = jax.grad(f, argnums=tuple(range(layout.n_buckets)))(
        *layout.split_buckets(flat))
    np.testing.assert_array_equal(
        np.asarray(layout.join_buckets(list(grads))),
        np.asarray(layout.pack(consts)))


def test_residual_merge_partition(lenet_net):
    layout = lenet_net.arena_layout(include=frozenset({"conv1", "ip2"}))
    params = lenet_net.init(jax.random.PRNGKey(0))
    excl = layout.residual(params)
    assert set(excl) == {"conv2", "ip1"}
    _assert_tree_equal(layout.merge(layout.unpack(layout.pack(params)),
                                    excl), params, "partition")


def test_non_f32_leaf_fails_loudly(lenet_net):
    layout = lenet_net.arena_layout()
    params = lenet_net.init(jax.random.PRNGKey(0))
    params["conv1"]["w"] = params["conv1"]["w"].astype(jnp.bfloat16)
    with pytest.raises(TypeError, match="f32-homogeneous"):
        layout.pack(params)


# --------------------------------------------------------------------------- #
# flat update rule (the fsdp-sharded step's) == per-leaf rule, bit for bit
# --------------------------------------------------------------------------- #

@pytest.mark.parametrize("solver_type,reg", [
    ("SGD", "L2"), ("SGD", "L1"), ("NESTEROV", "L2"), ("ADAGRAD", "L2")])
def test_fused_update_matches_leafwise(lenet_net, solver_type, reg, rng_np):
    """make_flat_update_rule over the packed buffer with the layout's
    multiplier vectors == make_update_fn per leaf, including mixed lr/decay
    multipliers and the zero-decay skip."""
    from poseidon_tpu.parallel.trainer import param_mults
    from poseidon_tpu.solvers.updates import (init_state,
                                              make_flat_update_rule,
                                              make_update_fn)
    sp = SolverParameter(base_lr=0.02, lr_policy="fixed", momentum=0.9,
                         weight_decay=0.0005, solver_type=solver_type,
                         regularization_type=reg)
    layout = lenet_net.arena_layout()
    params = lenet_net.init(jax.random.PRNGKey(0))
    grads = jax.tree_util.tree_map(
        lambda v: jnp.asarray(rng_np.randn(*v.shape).astype(np.float32)),
        params)
    state = init_state(params)
    # two per-leaf steps (nonzero history exercises the momentum term)
    update = make_update_fn(sp, param_mults(lenet_net))
    p1, s1 = update(params, grads, state)
    p1, s1 = update(p1, grads, s1)

    from poseidon_tpu.solvers.updates import learning_rate
    fused = make_flat_update_rule(sp)
    lr_vec, decay_vec = map(jnp.asarray,
                            layout.mult_vectors(sp.weight_decay))
    fw, fh = layout.pack(params), layout.pack(state.history)
    for it in range(2):
        rate = learning_rate(sp, jnp.asarray(it, jnp.int32))
        fw, fh = fused(fw, layout.pack(grads), fh, rate, lr_vec, decay_vec)
    _assert_tree_equal(layout.unpack(fw), p1, "params")
    _assert_tree_equal(layout.unpack(fh), s1.history, "history")


# --------------------------------------------------------------------------- #
# full-step bit-exactness: the built step vs the per-leaf reference
# --------------------------------------------------------------------------- #

@pytest.mark.parametrize("solver_type", ["SGD", "NESTEROV", "ADAGRAD"])
def test_lenet_step_bitexact(any_mesh, lenet_net, rng_np, solver_type):
    """SGD+momentum+L2 (the acceptance pin, and Caffe's default) is BIT
    identical to the per-leaf reference: bucketed against per-leaf
    collectives on eight devices, the arena-free step against a mesh-free
    grad + update on one. Nesterov/AdaGrad run the same per-leaf rule, but
    their multi-term step expressions give XLA's FMA contraction freedom
    that can differ between two programs' fusion shapes — those pin to
    ~1 ulp instead."""
    sp = SolverParameter(base_lr=0.01, lr_policy="fixed", momentum=0.9,
                         weight_decay=0.0005, solver_type=solver_type)
    params = lenet_net.init(jax.random.PRNGKey(0))
    (p1, s1, m1), (p2, s2, m2) = _ab_step(
        lenet_net, sp, any_mesh, CommConfig(), params, _batch(rng_np),
        jax.random.PRNGKey(7), n_steps=3)
    assert float(m1["loss"]) == float(m2["loss"])
    if solver_type == "SGD":
        _assert_tree_equal(p1, p2, solver_type)
        _assert_tree_equal(s1.solver.history, s2.solver.history, "history")
    else:
        for l in p1:
            for k in p1[l]:
                np.testing.assert_allclose(
                    np.asarray(p1[l][k]), np.asarray(p2[l][k]),
                    rtol=1e-6, atol=1e-8, err_msg=f"{solver_type} {l}/{k}")


def test_adam_clip_mixed_leaf_sizes_matches_leafwise(
        any_mesh, lenet_net, rng_np, monkeypatch):
    """The OLMoE shape in small: ADAM with ``clip_gradients`` on a net
    whose ip1 weight is over the arena's leaf cap (it keeps its per-leaf
    gradient tap) while every other leaf is arena-sized. The step is
    ``_leafwise_update`` alone over ALL leaves either way, the clip's norm
    spanning every gradient: eight devices agree with per-leaf collectives
    to ~1 ulp (ADAM's multi-term step, as for Nesterov), one device holds
    no arena and equals the mesh-free reference bit for bit."""
    from poseidon_tpu.core import arena as arena_mod
    monkeypatch.setattr(arena_mod, "MAX_LEAF_ELEMENTS", 100_000)
    assert not arena_mod.fits_arena(lenet_net.param_defs["ip1"])
    sp = SolverParameter(base_lr=1e-3, lr_policy="fixed", momentum=0.9,
                         momentum2=0.95, weight_decay=0.1,
                         solver_type="ADAM", clip_gradients=0.5)
    params = lenet_net.init(jax.random.PRNGKey(0))
    if any_mesh.size > 1:
        ts = build_train_step(lenet_net, sp, any_mesh, CommConfig(),
                              donate=False)
        assert ts.arena.layers == set(lenet_net.param_defs) - {"ip1"}
    (p1, s1, m1), (p2, s2, m2) = _ab_step(
        lenet_net, sp, any_mesh, CommConfig(), params, _batch(rng_np),
        jax.random.PRNGKey(7), n_steps=3)
    assert float(m1["loss"]) == float(m2["loss"])
    assert set(s1.solver.history) == {"m", "v"}
    for a, b in zip(jax.tree_util.tree_leaves((p1, s1.solver.history)),
                    jax.tree_util.tree_leaves((p2, s2.solver.history))):
        if any_mesh.size == 1:
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        else:
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-6, atol=1e-9)
    # the clip engaged: the raw gradient norm is well over the threshold
    assert float(jnp.sqrt(sum(
        jnp.sum(jnp.square(a - b)) for a, b in zip(
            jax.tree_util.tree_leaves(p1),
            jax.tree_util.tree_leaves(params))))) > 0


def test_lenet_wire_dtype_and_sum_reduce_bitexact(mesh, lenet_net, rng_np):
    sp = SolverParameter(base_lr=0.01, lr_policy="fixed", momentum=0.9)
    params = lenet_net.init(jax.random.PRNGKey(0))
    for comm in (CommConfig(wire_dtype="bf16"), CommConfig(reduce="sum")):
        (p1, _, _), (p2, _, _) = _ab_step(
            lenet_net, sp, mesh, comm, params, _batch(rng_np),
            jax.random.PRNGKey(7))
        _assert_tree_equal(p1, p2, str(comm.wire_dtype))


def test_iter_size_rides_arena_buckets(mesh, lenet_net, rng_np):
    """Gradient accumulation: the post-accumulation sync goes through the
    arena buckets (bit-identical to the per-leaf dense psums), and the
    compiled program carries the bucketed collective count, not
    one-per-leaf — the former 'per-backward comm strategies do not apply'
    warning path."""
    sp = SolverParameter(base_lr=0.01, lr_policy="fixed", momentum=0.9,
                         weight_decay=0.0005)
    params = lenet_net.init(jax.random.PRNGKey(0))
    b = _batch(rng_np)
    stacked = {k: jnp.stack([v, v]) for k, v in b.items()}
    import dataclasses
    comm = CommConfig(arena_bucket_mb=0.05)
    outs = []
    for arena_on in (True, False):
        cc = dataclasses.replace(comm, param_arena=arena_on)
        ts = build_train_step(lenet_net, sp, mesh, cc, iter_size=2,
                              donate=False)
        p, s, m = ts.step(params, init_train_state(params, cc, N_DEV),
                          stacked, jax.random.PRNGKey(7))
        outs.append((ts, p))
    _assert_tree_equal(outs[0][1], outs[1][1], "iter_size")
    ts = outs[0][0]
    hlo = ts.lowerable.lower(params, init_train_state(params, comm, N_DEV),
                             stacked, jax.random.PRNGKey(7)) \
        .compile().as_text()
    bound = math.ceil(ts.arena.total_bytes() / (0.05 * 1e6))
    n = count_gradient_all_reduces(hlo)
    assert 1 <= n <= bound, (n, bound)


def test_scan_steps_bitexact(any_mesh, lenet_net, rng_np):
    """Two steps inside one dispatch (lax.scan) follow the same rule: on
    eight devices buckets == per-leaf collectives, on one device no arena
    and the numbers of two single dispatches to rounding (XLA:CPU fuses a
    while-loop body's reductions differently from a straight-line step)."""
    sp = SolverParameter(base_lr=0.01, lr_policy="fixed", momentum=0.9)
    params = lenet_net.init(jax.random.PRNGKey(0))
    b = _batch(rng_np)
    rng = jax.random.PRNGKey(7)
    import dataclasses
    outs = []
    if any_mesh.size == 1:
        b = {k: v[:BATCH // N_DEV] for k, v in b.items()}
        stacked = {k: jnp.stack([v, v]) for k, v in b.items()}
        cc = CommConfig()
        ts = build_train_step(lenet_net, sp, any_mesh, cc, scan_steps=2,
                              donate=False)
        state = init_train_state(params, cc, 1)
        assert ts.arena is None and "arena_" not in ts.lowerable.lower(
            params, state, stacked, rng).as_text(debug_info=True)
        outs.append(ts.step(params, state, stacked, rng)[0])
        one = build_train_step(lenet_net, sp, any_mesh, cc, donate=False)
        p, s = params, state
        for i in range(2):
            p, s, _ = one.step(p, s, b, jax.random.fold_in(rng, i))
        outs.append(p)
    else:
        stacked = {k: jnp.stack([v, v]) for k, v in b.items()}
        for arena_on in (True, False):
            cc = dataclasses.replace(CommConfig(), param_arena=arena_on)
            ts = build_train_step(lenet_net, sp, any_mesh, cc, scan_steps=2,
                                  donate=False)
            p, s, m = ts.step(params, init_train_state(params, cc, N_DEV),
                              stacked, rng)
            outs.append(p)
    if any_mesh.size == 1:
        for a, bb in zip(jax.tree_util.tree_leaves(outs[0]),
                         jax.tree_util.tree_leaves(outs[1])):
            np.testing.assert_allclose(np.asarray(a), np.asarray(bb),
                                       rtol=1e-4, atol=1e-7)
    else:
        _assert_tree_equal(outs[0], outs[1], "scan")


def test_ssp_arena_bitexact(mesh, lenet_net, rng_np):
    """SSP: fused local update + bucketed boundary delta exchange, across a
    sync boundary, bit-identical local params AND anchor."""
    sp = SolverParameter(base_lr=0.01, lr_policy="fixed", momentum=0.9,
                         weight_decay=0.0005)
    params = lenet_net.init(jax.random.PRNGKey(0))
    b = _batch(rng_np)
    copy = lambda t: jax.tree_util.tree_map(jnp.array, t)  # noqa: E731
    states = []
    for arena_on in (True, False):
        import dataclasses
        cc = dataclasses.replace(CommConfig(arena_bucket_mb=0.05),
                                 param_arena=arena_on)
        ts = build_ssp_train_step(lenet_net, sp, mesh, 1, cc)
        assert (ts.arena is not None) == arena_on
        s = init_ssp_state(copy(params), N_DEV, cc)
        for i in range(4):  # crosses two sync boundaries at staleness 1
            s, m = ts.step(s, b, jax.random.PRNGKey(i))
        states.append(s)
    _assert_tree_equal(states[0].anchor_params, states[1].anchor_params,
                       "anchor")
    for a, bb in zip(jax.tree_util.tree_leaves(states[0].local_params),
                     jax.tree_util.tree_leaves(states[1].local_params)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(bb))


def test_dwbp_bucket_request_takes_precedence(mesh, lenet_net):
    """An explicit dwbp_bucket_mb (per-backward chained taps) disables the
    arena on the per-step path — the two bucketing mechanisms never
    double-psum."""
    sp = SolverParameter(base_lr=0.01, lr_policy="fixed")
    ts = build_train_step(lenet_net, sp, mesh, CommConfig(dwbp_bucket_mb=0),
                          donate=False)
    assert ts.arena is None


# --------------------------------------------------------------------------- #
# AlexNet / GoogLeNet: both numeric policies
# --------------------------------------------------------------------------- #

def _model_net_and_batch(model, image, batch):
    np_ = getattr(zoo, model)(num_classes=10, with_accuracy=False)
    shapes = {"data": (batch // N_DEV, 3, image, image),
              "label": (batch // N_DEV,)}
    net = Net(np_, "TRAIN", source_shapes=shapes)
    rs = np.random.RandomState(0)
    b = {"data": jnp.asarray(rs.randn(batch, 3, image, image)
                             .astype(np.float32)),
         "label": jnp.asarray(rs.randint(0, 10, size=(batch,)))}
    return net, b


def _model_bitexact(mesh, model, image, batch, compute_dtype,
                    check_collectives=False):
    """One full SGD+momentum+L2 optimizer step, arena vs per-leaf: equal
    loss and params equal to <= 1 ulp. (The update RULE is bit-identical —
    pinned by test_fused_update_matches_leafwise and the LeNet full-step
    tests — but at net scale XLA may pick a different cross-replica
    reduction order for a 4 MB bucketed all-reduce than for a 10-element
    per-leaf psum, so individual elements can land 1 ulp apart: the
    observed worst case is 1/5.9M elements at 7e-11 absolute.) Optionally
    also pins the compiled program's gradient all-reduce count against the
    ceil(bytes/bucket) bound — ONE AOT compile serves both the count and
    the run."""
    import dataclasses
    net, b = _model_net_and_batch(model, image, batch)
    sp = SolverParameter(base_lr=0.01, lr_policy="fixed", momentum=0.9,
                         weight_decay=0.0005)
    params = net.init(jax.random.PRNGKey(0))
    rng = jax.random.PRNGKey(7)
    results = []
    with config.policy_scope(compute_dtype=compute_dtype):
        for arena_on in (True, False):
            cc = dataclasses.replace(CommConfig(), param_arena=arena_on)
            ts = build_train_step(net, sp, mesh, cc, donate=False)
            state = init_train_state(params, cc, N_DEV)
            compiled = ts.lowerable.lower(params, state, b, rng).compile()
            if arena_on and check_collectives:
                bound = math.ceil(ts.arena.total_bytes() /
                                  (cc.arena_bucket_mb * 1e6))
                n = count_gradient_all_reduces(compiled.as_text())
                assert 1 <= n <= bound, (n, bound)
            # the AOT executable returns the un-wrapped 4-tuple (the jitted
            # fn's dumps slot rides along)
            p, s, m = compiled(params, state, b, rng)[:3]
            results.append((p, s, m))
    (p1, s1, m1), (p2, s2, m2) = results
    assert float(m1["loss"]) == float(m2["loss"])
    for tree1, tree2, what in ((p1, p2, "params"),
                               (s1.solver.history, s2.solver.history,
                                "history")):
        for l in tree1:
            for k in tree1[l]:
                np.testing.assert_allclose(
                    np.asarray(tree1[l][k]), np.asarray(tree2[l][k]),
                    rtol=1e-5, atol=1e-9,
                    err_msg=f"{model} {what} {l}/{k}")


def test_alexnet_step_bitexact_f32(mesh):
    _model_bitexact(mesh, "alexnet", 67, N_DEV, jnp.float32,
                    check_collectives=True)


@pytest.mark.slow
def test_alexnet_step_bitexact_bf16(mesh):
    # fast-lane bf16 coverage lives in test_lenet_bf16_policy_bitexact;
    # the AlexNet bf16 compile is a ~minute of CPU XLA
    _model_bitexact(mesh, "alexnet", 67, N_DEV, jnp.bfloat16)


def test_lenet_bf16_policy_bitexact(any_mesh, lenet_net, rng_np):
    """bf16-compute policy, fast lane: the built step vs the per-leaf
    reference, bit-identical (params stay f32; activations/matmuls run
    bfloat16)."""
    sp = SolverParameter(base_lr=0.01, lr_policy="fixed", momentum=0.9,
                         weight_decay=0.0005)
    params = lenet_net.init(jax.random.PRNGKey(0))
    with config.policy_scope(compute_dtype=jnp.bfloat16):
        (p1, s1, m1), (p2, s2, m2) = _ab_step(
            lenet_net, sp, any_mesh, CommConfig(), params, _batch(rng_np),
            jax.random.PRNGKey(7), n_steps=2)
    assert float(m1["loss"]) == float(m2["loss"])
    _assert_tree_equal(p1, p2, "bf16")
    _assert_tree_equal(s1.solver.history, s2.solver.history, "bf16 hist")


def test_googlenet_bucketed_collective_count(mesh):
    """The acceptance pin, fast-lane half: the data-parallel GoogLeNet
    train step carries <= ceil(total_grad_bytes / arena_bucket_mb)
    gradient all-reduces — ~120 per-leaf psums collapse to ~11 bucketed
    ones at 4 MB (GoogLeNet's ~120-leaf swarm is exactly why the arena
    exists). Counted on the LOWERED program (tracing is seconds; a full
    GoogLeNet XLA CPU compile is minutes): lowering count is an upper
    bound on the compiled count, since XLA merges but never splits
    all-reduces. The compiled-text count (and arena-vs-per-leaf step
    parity, both numeric policies) is pinned by the slow-marked tests
    below and on smaller nets by test_iter_size_rides_arena_buckets /
    the AlexNet f32 test."""
    net, b = _model_net_and_batch("googlenet", 224, N_DEV)
    sp = SolverParameter(base_lr=0.01, lr_policy="fixed", momentum=0.9,
                         weight_decay=0.0005)
    params = net.init(jax.random.PRNGKey(0))
    cc = CommConfig()
    ts = build_train_step(net, sp, mesh, cc, donate=False)
    assert ts.arena is not None
    n_leaves = len(jax.tree_util.tree_leaves(params))
    assert n_leaves > 100  # the many-small-tensor regime this PR targets
    bound = math.ceil(ts.arena.total_bytes() / (cc.arena_bucket_mb * 1e6))
    assert ts.arena.n_buckets == bound
    state = init_train_state(params, cc, N_DEV)
    rng = jax.random.PRNGKey(7)
    from poseidon_tpu.runtime.hlo_comm import (
        count_gradient_all_reduces_stablehlo)
    txt = ts.lowerable.lower(params, state, b, rng).as_text()
    n = count_gradient_all_reduces_stablehlo(txt)
    assert 1 <= n <= bound, (n, bound)
    assert n < n_leaves / 4, (n, n_leaves)


@pytest.mark.slow
def test_googlenet_step_bitexact_f32(mesh):
    """Slow-lane half of the acceptance pin: compiled-text collective
    count within the bucket bound + arena-vs-per-leaf step parity."""
    _model_bitexact(mesh, "googlenet", 224, N_DEV, jnp.float32,
                    check_collectives=True)


@pytest.mark.slow
def test_googlenet_step_bitexact_bf16(mesh):
    _model_bitexact(mesh, "googlenet", 224, N_DEV, jnp.bfloat16)
