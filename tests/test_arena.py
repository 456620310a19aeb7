"""The arena (core/arena.py) and the data-parallel step that no longer uses it.

The arena gives f32 leaves a static DWBP-ordered offset table in one flat
buffer, for the two steps whose STATE lives there: the fsdp-sharded step
(parallel/spmd.py) and the SSP tier's boundary delta exchange. The
synchronous data-parallel step of ``build_train_step`` packs nothing since
PR 59: each DENSE gradient is summed by the tap in its own layer's backward.
Everything here pins those contracts:

- the offset table, pack / unpack / views, and the flat update rule the
  fsdp-sharded step keeps, bit for bit against the per-leaf rule;
- one device: the step holds no ``arena_*`` scope and equals a mesh-free
  per-leaf reference (grad + ``make_update_fn``) bit for bit;
- several devices: the step holds no ``arena_*`` / ``grad_sync_bucket``
  scope and no 1-D bucket buffer, whatever ``param_arena`` says, and
  computes the numbers of the plain reference (``jax.grad``, one
  ``lax.psum`` a leaf AFTER backward, ``make_update_fn``) — for every
  solver rule, both numeric policies, wire dtypes, gradient accumulation
  and scan dispatch; its compiled program carries at most one gradient
  all-reduce a leaf (fewer where the compiler's combiner merges);
- SSP: the bucketed boundary exchange (which still packs) against
  per-leaf psums, ``param_arena`` on / off.
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
from poseidon_tpu.runtime.hlo_comm import (
    count_gradient_all_reduces, count_gradient_all_reduces_stablehlo)

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


def _plain_dp_step(net, sp, mesh, comm):
    """The plain data-parallel reference: ``jax.grad`` on each device's
    shard, ONE ``lax.psum`` a leaf after the whole backward (at the wire
    dtype, mean or sum as ``comm.reduce`` says), ``make_update_fn``. No
    tap, no context, nothing of ``build_train_step``."""
    from jax import lax, shard_map
    from jax.sharding import PartitionSpec as P

    from poseidon_tpu.parallel.strategies import wire_psum
    from poseidon_tpu.parallel.trainer import param_mults
    from poseidon_tpu.solvers.updates import make_update_fn
    update = make_update_fn(sp, param_mults(net))
    axes = comm.sync_axes
    n = mesh.size

    def device_step(params, solver, batch, rng):
        rng = jax.random.fold_in(rng, lax.axis_index(comm.axis))

        def loss_fn(p):
            return net.apply(p, batch, train=True, rng=rng).loss

        loss, grads = jax.value_and_grad(loss_fn)(params)
        grads = jax.tree_util.tree_map(
            lambda g: wire_psum(g, axes, comm.reduce, comm.wire_dtype),
            grads)
        params, solver = update(params, grads, solver)
        return params, solver, {"loss": lax.psum(loss, axes) / n}

    return jax.jit(shard_map(
        device_step, mesh=mesh, in_specs=(P(), P(), P(comm.axis), P()),
        out_specs=(P(), P(), P()), check_vma=False))


def _assert_no_arena(ts, *args):
    """The built data-parallel step packs nothing: no arena layout, no
    ``arena_*`` / ``grad_sync_bucket`` scope in its lowering."""
    assert ts.arena is None and ts.update_route == "leaf"
    text = ts.lowerable.lower(*args).as_text(debug_info=True)
    assert "arena_" not in text and "grad_sync_bucket" not in text
    return text


def _ab_step(net, sp, mesh, comm, params, batch, rng, n_steps=1,
             param_arena=True):
    """(step under test, reference) after n_steps from the same start. On
    several devices: the built step — under ``param_arena``, which must
    decide nothing there — against the plain ``jax.grad`` + ``lax.psum``
    reference (``_plain_dp_step``). On ONE device: the built step against
    a mesh-free grad + ``make_update_fn`` step on that device's share of
    the batch. Neither holds an arena."""
    import dataclasses
    solver_type = sp.solver_type
    if mesh.size == 1:
        from poseidon_tpu.runtime.hlo_layout import build_plain_step
        batch = {k: v[:BATCH // N_DEV] for k, v in batch.items()}
        ts = build_train_step(net, sp, mesh, comm, donate=False)
        state = init_train_state(params, comm, 1, solver_type)
        _assert_no_arena(ts, params, state, batch, rng)
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
    cc = dataclasses.replace(comm, param_arena=param_arena)
    ts = build_train_step(net, sp, mesh, cc, donate=False)
    state = init_train_state(params, cc, N_DEV, solver_type)
    _assert_no_arena(ts, params, state, batch, rng)
    p, s = params, state
    for i in range(n_steps):
        p, s, m = ts.step(p, s, batch, jax.random.fold_in(rng, i))
    plain = _plain_dp_step(net, sp, mesh, cc)
    rp, rs = params, state.solver
    for i in range(n_steps):
        rp, rs, rm = plain(rp, rs, batch, jax.random.fold_in(rng, i))
    return [(p, s, m), (rp, state._replace(solver=rs), rm)]


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
    identical to the reference: the in-backward taps against the plain
    grad + one psum a leaf after backward on eight devices, the step
    against a mesh-free grad + update on one. Nesterov/AdaGrad run the same
    per-leaf rule, but
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


@pytest.mark.parametrize("param_arena", [True, False])
def test_adam_clip_matches_plain_reference(any_mesh, lenet_net, rng_np,
                                           param_arena):
    """The OLMoE shape in small: ADAM with ``clip_gradients``, the clip's
    norm spanning every gradient. The step is ``_leafwise_update`` over
    ALL leaves: eight devices agree with the plain grad + psum reference
    to ~1 ulp (ADAM's multi-term step, as for Nesterov), one device equals
    the mesh-free reference bit for bit; ``param_arena`` decides nothing
    (no leaf is packed, so no leaf is too large to pack: the cap that
    ``core/arena.fits_arena`` held went with the buckets, PR 59)."""
    sp = SolverParameter(base_lr=1e-3, lr_policy="fixed", momentum=0.9,
                         momentum2=0.95, weight_decay=0.1,
                         solver_type="ADAM", clip_gradients=0.5)
    params = lenet_net.init(jax.random.PRNGKey(0))
    (p1, s1, m1), (p2, s2, m2) = _ab_step(
        lenet_net, sp, any_mesh, CommConfig(), params, _batch(rng_np),
        jax.random.PRNGKey(7), n_steps=3, param_arena=param_arena)
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


@pytest.mark.parametrize("comm", [
    CommConfig(wire_dtype="bf16"), CommConfig(reduce="sum"),
    CommConfig(wire_dtype="bf16", param_arena=False)],
    ids=["bf16_wire", "sum", "bf16_wire_no_arena_flag"])
def test_lenet_wire_dtype_and_sum_reduce_bitexact(mesh, lenet_net, rng_np,
                                                  comm):
    """The same precision on the wire as the plain reference: f32 sums in
    f32, ``wire_dtype`` casts the operand and nothing else, ``reduce``
    keeps its meaning."""
    sp = SolverParameter(base_lr=0.01, lr_policy="fixed", momentum=0.9)
    params = lenet_net.init(jax.random.PRNGKey(0))
    (p1, _, _), (p2, _, _) = _ab_step(
        lenet_net, sp, mesh, comm, params, _batch(rng_np),
        jax.random.PRNGKey(7), param_arena=comm.param_arena)
    _assert_tree_equal(p1, p2, str(comm.wire_dtype))


def _psums(jaxpr, in_scan=False, out=None):
    """(inside a scan?, operand shape) of every psum in a jaxpr."""
    out = [] if out is None else out
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "psum":
            out.extend((in_scan, v.aval.shape) for v in eqn.invars)
        for sub in jax.core.jaxprs_in_params(eqn.params):
            _psums(sub, in_scan or eqn.primitive.name == "scan", out)
    return out


@pytest.mark.parametrize("param_arena", [True, False])
def test_iter_size_sums_each_leaf_once_after_the_scan(mesh, lenet_net,
                                                      rng_np, param_arena):
    """Gradient accumulation: nothing is summed across devices inside the
    micro-batch scan, and after it every leaf is summed exactly once, in
    its own shape (no bucket buffer); the step computes the numbers of the
    plain reference that accumulates in a Python loop and psums each leaf
    (to rounding: XLA:CPU fuses a while-loop body's reductions differently
    from straight-line code); the compiled program carries at most one
    gradient all-reduce a leaf."""
    from jax import lax, shard_map
    from jax.sharding import PartitionSpec as P

    from poseidon_tpu.parallel.trainer import param_mults
    from poseidon_tpu.solvers.updates import make_update_fn
    sp = SolverParameter(base_lr=0.01, lr_policy="fixed", momentum=0.9,
                         weight_decay=0.0005)
    params = lenet_net.init(jax.random.PRNGKey(0))
    b = _batch(rng_np)
    b2 = {k: jnp.roll(v, 3, axis=0) for k, v in b.items()}
    stacked = {k: jnp.stack([b[k], b2[k]]) for k in b}
    cc = CommConfig(arena_bucket_mb=0.05, param_arena=param_arena)
    rng = jax.random.PRNGKey(7)
    ts = build_train_step(lenet_net, sp, mesh, cc, iter_size=2,
                          donate=False)
    state = init_train_state(params, cc, N_DEV)
    _assert_no_arena(ts, params, state, stacked, rng)
    leaf_shapes = sorted(v.shape for v in jax.tree_util.tree_leaves(params))
    psums = _psums(jax.make_jaxpr(ts.lowerable)(
        params, state, stacked, rng).jaxpr)
    assert not [shape for in_scan, shape in psums if in_scan]
    assert sorted(shape for _, shape in psums if shape) == leaf_shapes
    p, s, m = ts.step(params, state, stacked, rng)

    update = make_update_fn(sp, param_mults(lenet_net))

    def device_step(params, solver, batches):
        grads = jax.tree_util.tree_map(jnp.zeros_like, params)
        for i in range(2):
            mb = {k: v[i] for k, v in batches.items()}
            g = jax.grad(lambda q: lenet_net.apply(
                q, mb, train=True, rng=rng).loss)(params)
            grads = jax.tree_util.tree_map(jnp.add, grads, g)
        grads = jax.tree_util.tree_map(
            lambda g: lax.psum(g / 2, "data") / N_DEV, grads)
        return update(params, grads, solver)

    want, _ = jax.jit(shard_map(
        device_step, mesh=mesh, in_specs=(P(), P(), P(None, "data")),
        out_specs=(P(), P()), check_vma=False))(params, state.solver,
                                                stacked)
    for l in want:
        for k in want[l]:
            np.testing.assert_allclose(
                np.asarray(p[l][k]), np.asarray(want[l][k]),
                rtol=1e-5, atol=1e-7, err_msg=f"iter_size {l}/{k}")
    hlo = ts.lowerable.lower(params, state, stacked, rng).compile().as_text()
    n = count_gradient_all_reduces(hlo)
    assert 1 <= n <= len(leaf_shapes), (n, len(leaf_shapes))


def test_scan_steps_bitexact(any_mesh, lenet_net, rng_np):
    """Two steps inside one dispatch (lax.scan) follow the same rule on
    eight devices and on one: no arena, and the numbers of two single
    dispatches to rounding (XLA:CPU fuses a while-loop body's reductions
    differently from a straight-line step)."""
    sp = SolverParameter(base_lr=0.01, lr_policy="fixed", momentum=0.9)
    params = lenet_net.init(jax.random.PRNGKey(0))
    b = _batch(rng_np)
    rng = jax.random.PRNGKey(7)
    n = any_mesh.size
    if n == 1:
        b = {k: v[:BATCH // N_DEV] for k, v in b.items()}
    stacked = {k: jnp.stack([v, v]) for k, v in b.items()}
    cc = CommConfig()
    ts = build_train_step(lenet_net, sp, any_mesh, cc, scan_steps=2,
                          donate=False)
    state = init_train_state(params, cc, n)
    _assert_no_arena(ts, params, state, stacked, rng)
    got = ts.step(params, state, stacked, rng)[0]
    one = build_train_step(lenet_net, sp, any_mesh, cc, donate=False)
    p, s = params, state
    for i in range(2):
        p, s, _ = one.step(p, s, b, jax.random.fold_in(rng, i))
    for a, bb in zip(jax.tree_util.tree_leaves(got),
                     jax.tree_util.tree_leaves(p)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(bb),
                                   rtol=1e-4, atol=1e-7)


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


def test_dwbp_bucket_request_keeps_its_chained_taps(mesh, lenet_net,
                                                    rng_np):
    """An explicit dwbp_bucket_mb keeps its path untouched: per-blob
    chained taps cannot merge (the chain would cycle), so the compiled step
    carries one gradient all-reduce a leaf, where the default's plain taps
    leave the compiler's combiner free to merge them into fewer."""
    sp = SolverParameter(base_lr=0.01, lr_policy="fixed")
    params = lenet_net.init(jax.random.PRNGKey(0))
    n_leaves = len(jax.tree_util.tree_leaves(params))
    counts = {}
    for name, cc in (("chained", CommConfig(dwbp_bucket_mb=0)),
                     ("plain", CommConfig())):
        ts = build_train_step(lenet_net, sp, mesh, cc, donate=False)
        assert ts.arena is None
        counts[name] = count_gradient_all_reduces(ts.lowerable.lower(
            params, init_train_state(params, cc, N_DEV), _batch(rng_np),
            jax.random.PRNGKey(7)).compile().as_text(), min_payload_bytes=40)
    assert counts["chained"] == n_leaves, counts
    assert 1 <= counts["plain"] <= n_leaves, counts


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
    """One full SGD+momentum+L2 optimizer step, the built step against the
    plain grad + psum reference: equal loss and params equal to <= 1 ulp.
    (The update RULE is bit-identical — pinned by
    test_fused_update_matches_leafwise and the LeNet full-step tests — but
    at net scale XLA may pick a different cross-replica reduction order
    for an all-reduce its combiner merged than for a per-leaf psum, so
    individual elements can land 1 ulp apart.) Optionally also pins the
    compiled program: at most one gradient all-reduce a leaf, no 1-D
    bucket buffer — ONE AOT compile serves both the count and the run."""
    import re
    net, b = _model_net_and_batch(model, image, batch)
    sp = SolverParameter(base_lr=0.01, lr_policy="fixed", momentum=0.9,
                         weight_decay=0.0005)
    params = net.init(jax.random.PRNGKey(0))
    rng = jax.random.PRNGKey(7)
    cc = CommConfig()
    with config.policy_scope(compute_dtype=compute_dtype):
        ts = build_train_step(net, sp, mesh, cc, donate=False)
        state = init_train_state(params, cc, N_DEV)
        assert ts.arena is None
        compiled = ts.lowerable.lower(params, state, b, rng).compile()
        if check_collectives:
            text = compiled.as_text()
            assert "arena_" not in text and "grad_sync_bucket" not in text
            leaves = jax.tree_util.tree_leaves(params)
            n = count_gradient_all_reduces(text)
            assert 1 <= n <= len(leaves), (n, len(leaves))
            # no flat buffer: every 1-D f32 array is bias-sized
            widest_bias = max(v.size for v in leaves if v.ndim == 1)
            assert max(int(m) for m in re.findall(
                r"f32\[(\d+)\]", text)) <= widest_bias
        # the AOT executable returns the un-wrapped 4-tuple (the jitted
        # fn's dumps slot rides along)
        p1, s1, m1 = compiled(params, state, b, rng)[:3]
        p2, h2, m2 = _plain_dp_step(net, sp, mesh, cc)(
            params, state.solver, b, rng)
    assert float(m1["loss"]) == float(m2["loss"])
    for tree1, tree2, what in ((p1, p2, "params"),
                               (s1.solver.history, h2.history, "history")):
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


def test_googlenet_one_sum_a_leaf_and_no_bucket(mesh):
    """The many-small-tensor regime the buckets were written for, on the
    route that replaced them: the data-parallel GoogLeNet step's LOWERED
    program (tracing is seconds; a full GoogLeNet XLA CPU compile is
    minutes) holds exactly one gradient all-reduce a leaf, each in its
    leaf's own shape, no ``arena_*`` / ``grad_sync_bucket`` scope and no
    4 MB buffer. The lowering count is an upper bound on the compiled
    count: XLA's combiner merges all-reduces and never splits one (the
    compiled text, and step parity in both numeric policies, are pinned by
    the slow-marked tests below and on smaller nets by the LeNet and
    AlexNet tests above)."""
    net, b = _model_net_and_batch("googlenet", 224, N_DEV)
    sp = SolverParameter(base_lr=0.01, lr_policy="fixed", momentum=0.9,
                         weight_decay=0.0005)
    params = net.init(jax.random.PRNGKey(0))
    cc = CommConfig()
    ts = build_train_step(net, sp, mesh, cc, donate=False)
    leaves = jax.tree_util.tree_leaves(params)
    assert len(leaves) > 100  # the many-small-tensor regime
    state = init_train_state(params, cc, N_DEV)
    txt = _assert_no_arena(ts, params, state, b, jax.random.PRNGKey(7))
    assert "tensor<1000000xf32>" not in txt
    # no leaf is a scalar; the metrics' psums are
    assert min(v.size for v in leaves) >= 2
    n = count_gradient_all_reduces_stablehlo(txt, min_elements=2)
    assert n == len(leaves), (n, len(leaves))


@pytest.mark.slow
def test_googlenet_step_bitexact_f32(mesh):
    """Slow-lane half: compiled-text collective count within one a leaf,
    and parity with the plain grad + psum reference."""
    _model_bitexact(mesh, "googlenet", 224, N_DEV, jnp.float32,
                    check_collectives=True)


@pytest.mark.slow
def test_googlenet_step_bitexact_bf16(mesh):
    _model_bitexact(mesh, "googlenet", 224, N_DEV, jnp.bfloat16)
