"""Parallel strategies on the 8-device virtual CPU mesh.

Core invariants:
- dense (DWBP-tap) DP training on N devices == single-device training on the
  concatenated batch with summed gradients (exact parity).
- SFB produces bit-equal gradients to dense for FC layers.
- top-k compressed sync keeps replicas consistent.
- SSP staleness s: replicas may diverge between syncs, reconcile every s+1.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from poseidon_tpu.core.net import Net
from poseidon_tpu.models import zoo
from poseidon_tpu.parallel import (
    CommConfig, SFB, auto_strategies, build_eval_step, build_ssp_train_step,
    build_train_step, init_ssp_state, init_train_state, make_mesh)
from poseidon_tpu.proto.messages import SolverParameter
from poseidon_tpu.solvers.updates import init_state, make_update_fn
from poseidon_tpu.parallel.trainer import param_mults

N_DEV = 8
BATCH = 16  # global batch; 2 per device


@pytest.fixture(scope="module")
def mesh():
    assert jax.device_count() == N_DEV, "conftest must provide 8 cpu devices"
    return make_mesh()


@pytest.fixture(scope="module")
def lenet_net():
    return Net(zoo.lenet(with_accuracy=False), phase="TRAIN",
               source_shapes=zoo.lenet_shapes(BATCH // N_DEV))


def _global_batch(rng):
    return {
        "data": jnp.asarray(rng.randn(BATCH, 1, 28, 28).astype(np.float32)),
        "label": jnp.asarray(rng.randint(0, 10, size=(BATCH,))),
    }


def _single_device_reference(net, sp, params, batch, n_steps, rng_np):
    """Sum of per-shard mean-gradients == what dense DP computes."""
    update = make_update_fn(sp, param_mults(net))
    state = init_state(params)
    shard = BATCH // N_DEV

    for step in range(n_steps):
        def loss_fn(p):
            total = 0.0
            for d in range(N_DEV):
                sl = {k: v[d * shard:(d + 1) * shard] for k, v in batch.items()}
                total = total + net.apply(p, sl, train=True,
                                          rng=jax.random.PRNGKey(99)).loss
            return total
        grads = jax.grad(loss_fn)(params)
        params, state = update(params, grads, state)
    return params


def test_dense_dp_matches_single_device(mesh, lenet_net, rng_np):
    sp = SolverParameter(base_lr=0.01, lr_policy="fixed", momentum=0.9,
                         weight_decay=0.0005)
    params = lenet_net.init(jax.random.PRNGKey(0))
    batch = _global_batch(rng_np)

    # Use a fixed rng fed identically; dropout-free net so rng is inert.
    ts = build_train_step(lenet_net, sp, mesh, CommConfig(reduce="sum"),
                          donate=False)
    # ONE step: the re-layout contract at its strongest — sharded and
    # single-device params agree to f32 epsilon (measured 3e-8).
    p1, s1, _ = ts.step(params, init_train_state(params), batch,
                        jax.random.PRNGKey(99))
    want1 = _single_device_reference(lenet_net, sp, params, batch, 1, rng_np)
    for l in want1:
        for k in want1[l]:
            np.testing.assert_allclose(
                np.asarray(p1[l][k]), np.asarray(want1[l][k]),
                rtol=1e-5, atol=1e-6, err_msg=f"step1 {l}/{k}")

    p, s = params, init_train_state(params)
    for _ in range(3):
        p, s, metrics = ts.step(p, s, batch, jax.random.PRNGKey(99))
    want = _single_device_reference(lenet_net, sp, params, batch, 3, rng_np)
    for l in want:
        for k in want[l]:
            # Over multiple steps exactness is unattainable for ANY two
            # valid schedules: psum tree-reduction order differs from the
            # sequential host sum by ~1 ulp, and max-pool's argmax can flip
            # on a near-tie once params differ by epsilon, re-routing one
            # window's gradient entirely (observed: 1/500 conv1 weights at
            # 8e-4 after 3 momentum steps; step 1 is at 3e-8).
            np.testing.assert_allclose(
                np.asarray(p[l][k]), np.asarray(want[l][k]),
                rtol=2e-2, atol=1.5e-3, err_msg=f"{l}/{k}")


@pytest.mark.parametrize("param_arena", [True, False])
def test_multi_device_step_packs_nothing(mesh, lenet_net, rng_np,
                                         param_arena):
    """The route of PR 59, read in the COMPILED eight-device step: each
    DENSE gradient is summed where backward makes it, so the program holds
    no ``arena_*`` / ``grad_sync_bucket`` scope, no 1-D f32 buffer wider
    than a bias (a bucket of LeNet's 431,080 parameters was one), and at
    most one gradient all-reduce a leaf — fewer where the compiler's
    combiner merges — whatever ``param_arena`` says."""
    import re

    from poseidon_tpu.runtime.hlo_comm import gradient_all_reduce_census
    sp = SolverParameter(base_lr=0.01, lr_policy="fixed", momentum=0.9,
                         weight_decay=0.0005)
    params = lenet_net.init(jax.random.PRNGKey(0))
    comm = CommConfig(param_arena=param_arena)
    ts = build_train_step(lenet_net, sp, mesh, comm, donate=False)
    assert ts.arena is None and ts.update_route == "leaf"
    text = ts.lowerable.lower(
        params, init_train_state(params, comm, N_DEV), _global_batch(rng_np),
        jax.random.PRNGKey(1)).compile().as_text()
    assert "arena_" not in text and "grad_sync_bucket" not in text
    leaves = jax.tree_util.tree_leaves(params)
    widest_bias = max(v.size for v in leaves if v.ndim == 1)
    assert max(int(n) for n in re.findall(r"f32\[(\d+)\]", text)) \
        <= widest_bias
    n, n_async = gradient_all_reduce_census(text, min_payload_bytes=40)
    assert 1 <= n <= len(leaves) and 0 <= n_async <= n, (n, n_async)


def test_dense_dp_mean_matches_single_device_on_whole_batch(mesh, lenet_net,
                                                            rng_np):
    """``reduce="mean"`` (the default): after N steps the eight-device
    step's parameters equal ONE device's on the concatenated batch — the
    mean of eight equal shards' mean gradients is the whole batch's — to
    the tolerances of ``test_dense_dp_matches_single_device``."""
    from poseidon_tpu.runtime.hlo_layout import build_plain_step
    sp = SolverParameter(base_lr=0.01, lr_policy="fixed", momentum=0.9,
                         weight_decay=0.0005)
    params = lenet_net.init(jax.random.PRNGKey(0))
    batch = _global_batch(rng_np)
    ts = build_train_step(lenet_net, sp, mesh, CommConfig(), donate=False)
    whole = Net(zoo.lenet(with_accuracy=False), phase="TRAIN",
                source_shapes=zoo.lenet_shapes(BATCH))
    plain = jax.jit(build_plain_step(whole, sp))
    p, s = params, init_train_state(params)
    wp, ws = params, init_state(params)
    for step, (rtol, atol) in enumerate(
            [(1e-5, 1e-6), (2e-2, 1.5e-3), (2e-2, 1.5e-3)]):
        p, s, _ = ts.step(p, s, batch, jax.random.PRNGKey(99))
        wp, ws = plain(wp, ws, batch, jax.random.PRNGKey(99))
        for l in wp:
            for k in wp[l]:
                np.testing.assert_allclose(
                    np.asarray(p[l][k]), np.asarray(wp[l][k]),
                    rtol=rtol, atol=atol, err_msg=f"step {step + 1} {l}/{k}")


def test_sfb_matches_dense(mesh, lenet_net, rng_np):
    sp = SolverParameter(base_lr=0.01, lr_policy="fixed", momentum=0.9)
    params = lenet_net.init(jax.random.PRNGKey(0))
    batch = _global_batch(rng_np)

    dense = build_train_step(lenet_net, sp, mesh, CommConfig(), donate=False)
    sfb = build_train_step(
        lenet_net, sp, mesh,
        CommConfig(layer_strategies={"ip1": SFB, "ip2": SFB}), donate=False)

    mk = init_train_state
    p1, s1, m1 = dense.step(params, mk(params), batch, jax.random.PRNGKey(7))
    p2, s2, m2 = sfb.step(params, mk(params), batch, jax.random.PRNGKey(7))
    assert float(m1["loss"]) == pytest.approx(float(m2["loss"]), rel=1e-6)
    for l in p1:
        for k in p1[l]:
            np.testing.assert_allclose(
                np.asarray(p1[l][k]), np.asarray(p2[l][k]),
                rtol=1e-4, atol=1e-7, err_msg=f"{l}/{k}")


def test_dense_fused_matches_dense(mesh, lenet_net, rng_np):
    """The no-overlap A/B baseline (one bulk psum after backward) must be
    numerically identical to the in-backward DWBP taps — same psums, just
    scheduled at the end."""
    from poseidon_tpu.parallel import DENSE_FUSED
    sp = SolverParameter(base_lr=0.01, lr_policy="fixed", momentum=0.9)
    params = lenet_net.init(jax.random.PRNGKey(0))
    batch = _global_batch(rng_np)
    dense = build_train_step(lenet_net, sp, mesh, CommConfig(), donate=False)
    fused = build_train_step(
        lenet_net, sp, mesh,
        CommConfig(default_strategy=DENSE_FUSED), donate=False)
    p1, _, m1 = dense.step(params, init_train_state(params), batch,
                           jax.random.PRNGKey(7))
    p2, _, m2 = fused.step(params, init_train_state(params), batch,
                           jax.random.PRNGKey(7))
    assert float(m1["loss"]) == pytest.approx(float(m2["loss"]), rel=1e-6)
    for l in p1:
        for k in p1[l]:
            np.testing.assert_allclose(
                np.asarray(p1[l][k]), np.asarray(p2[l][k]),
                rtol=1e-5, atol=1e-7, err_msg=f"{l}/{k}")


def test_adarevision_matches_server_formula(mesh, lenet_net, rng_np):
    """server_logic='adarevision' must reproduce the reference server's
    update rule exactly (adarevision_server_table_logic.cpp:52-175): for
    each group's accumulated gradient u applied in group order,
    z += u*(u + 2*g_bck); zmax = max(zmax, z); delta = -eta*u +
    (eta_old - eta)*g_bck with eta = eta0/sqrt(zmax); g_bck accumulates
    the within-boundary updates (snapshots are boundary-aligned here, so
    g_bck starts at 0 each sync). Verified against a NumPy replica fed the
    per-shard gradients."""
    eta0 = 0.05
    comm = CommConfig(server_logic="adarevision", adarev_init_step=eta0)
    sp = SolverParameter(base_lr=0.01, lr_policy="fixed", momentum=0.0,
                         weight_decay=0.0)
    params = lenet_net.init(jax.random.PRNGKey(0))
    batch = _global_batch(rng_np)
    ts = build_ssp_train_step(lenet_net, sp, mesh, staleness=0, comm=comm)

    # per-shard raw gradients + numpy copies BEFORE the step: the jitted
    # step donates its state, whose anchor aliases `params`
    shard = BATCH // N_DEV
    u = []
    for d in range(N_DEV):
        sl = {k: v[d * shard:(d + 1) * shard] for k, v in batch.items()}
        u.append(jax.device_get(jax.grad(
            lambda p: lenet_net.apply(p, sl, train=True,
                                      rng=jax.random.PRNGKey(9)).loss)(params)))
    params0 = jax.device_get(params)

    state = init_ssp_state(params, N_DEV, comm)
    state, m = ts.step(state, batch, jax.random.PRNGKey(9))
    for l in params0:
        for k in params0[l]:
            av = np.asarray(params0[l][k], np.float64)
            z = np.ones_like(av)
            zmax = np.ones_like(av)
            g_bck = np.zeros_like(av)
            for d in range(N_DEV):
                ug = np.asarray(u[d][l][k], np.float64)
                eta_old = eta0 / np.sqrt(zmax)
                z = z + ug * (ug + 2.0 * g_bck)
                zmax = np.maximum(zmax, z)
                eta = eta0 / np.sqrt(zmax)
                av = av - eta * ug + (eta_old - eta) * g_bck
                g_bck = g_bck + ug
            np.testing.assert_allclose(
                np.asarray(state.anchor_params[l][k]), av,
                rtol=2e-4, atol=1e-6, err_msg=f"{l}/{k}")
            # locals refreshed from the server at the boundary
            np.testing.assert_array_equal(
                np.asarray(state.local_params[l][k][0]),
                np.asarray(state.anchor_params[l][k]))


def test_adarevision_converges_under_staleness(mesh, lenet_net, rng_np):
    """adarevision + staleness: the delay-corrected server keeps replicas
    consistent at boundaries and the loss goes down."""
    # eta0 scales the SUM of group updates (the server applies every
    # client's u in full — the same sum semantics that made PMLS retune lr
    # per cluster size); ~base_lr/n_groups is the stable regime
    comm = CommConfig(server_logic="adarevision", adarev_init_step=0.005)
    sp = SolverParameter(base_lr=0.01, lr_policy="fixed", momentum=0.9)
    params = lenet_net.init(jax.random.PRNGKey(0))
    ts = build_ssp_train_step(lenet_net, sp, mesh, staleness=1, comm=comm)
    state = init_ssp_state(params, N_DEV, comm)
    batch = _global_batch(rng_np)  # fixed batch: a learnable objective
    losses = []
    for i in range(40):
        state, m = ts.step(state, batch, jax.random.PRNGKey(i))
        losses.append(float(m["loss"]))
    # the trajectory saw-tooths (local preview vs anchor reset); judge the
    # envelope, not adjacent steps
    assert min(losses[-6:]) < 0.1, losses
    # oplog drains at every boundary (staleness 1 -> sync on even its)
    for lname, lp in state.adarev_gsum.items():
        for pname, v in lp.items():
            assert np.isfinite(np.asarray(v)).all()
    z = state.adarev_server["ip2"]["w"]["zmax"]
    assert float(jnp.min(z)) >= 1.0  # AdaRevisionRow init, monotone max


def test_adarevision_rejects_topk():
    from poseidon_tpu.parallel import TOPK
    net = Net(zoo.lenet(with_accuracy=False), phase="TRAIN",
              source_shapes=zoo.lenet_shapes(2))
    comm = CommConfig(server_logic="adarevision",
                      layer_strategies={"ip1": TOPK})
    sp = SolverParameter(base_lr=0.01, lr_policy="fixed")
    with pytest.raises(ValueError, match="adarevision"):
        build_ssp_train_step(net, sp, make_mesh(), staleness=1, comm=comm)


def test_iter_size_matches_big_batch(mesh, rng_np):
    """Gradient accumulation (SolverParameter.iter_size, Caffe's V2
    surface): batch_size B at iter_size K must equal batch_size B*K — same
    samples, same mean gradient, same momentum trajectory. Sample-to-device
    assignment differs between the two layouts, but under reduce='mean'
    every sample contributes 1/(B*K) either way."""
    K = 4
    sp = SolverParameter(base_lr=0.01, lr_policy="fixed", momentum=0.9)
    small = Net(zoo.lenet(with_accuracy=False), phase="TRAIN",
                source_shapes=zoo.lenet_shapes(BATCH // N_DEV))
    big = Net(zoo.lenet(with_accuracy=False), phase="TRAIN",
              source_shapes=zoo.lenet_shapes(BATCH * K // N_DEV))
    params = small.init(jax.random.PRNGKey(0))
    data = rng_np.randn(BATCH * K, 1, 28, 28).astype(np.float32)
    labels = rng_np.randint(0, 10, size=(BATCH * K,)).astype(np.int32)

    ts_acc = build_train_step(small, sp, mesh, CommConfig(), donate=False,
                              iter_size=K)
    assert ts_acc.iter_size == K
    ts_big = build_train_step(big, sp, mesh, CommConfig(), donate=False)
    b_acc = {"data": jnp.asarray(data.reshape(K, BATCH, 1, 28, 28)),
             "label": jnp.asarray(labels.reshape(K, BATCH))}
    b_big = {"data": jnp.asarray(data), "label": jnp.asarray(labels)}

    pa, sa = params, init_train_state(params)
    pb, sb = params, init_train_state(params)
    for _ in range(2):  # two steps: momentum history must match too
        pa, sa, ma = ts_acc.step(pa, sa, b_acc, jax.random.PRNGKey(7))
        pb, sb, mb = ts_big.step(pb, sb, b_big, jax.random.PRNGKey(7))
    assert float(ma["loss"]) == pytest.approx(float(mb["loss"]), rel=1e-5)
    for l in pa:
        for k in pa[l]:
            np.testing.assert_allclose(
                np.asarray(pa[l][k]), np.asarray(pb[l][k]),
                rtol=1e-4, atol=1e-6, err_msg=f"{l}/{k}")


def test_iter_size_composes_with_topk(mesh, lenet_net, rng_np):
    """TOPK compression applies to the ACCUMULATED gradient under
    iter_size; replicas stay consistent and the error residual carries."""
    from poseidon_tpu.parallel import TOPK
    sp = SolverParameter(base_lr=0.01, lr_policy="fixed", momentum=0.9)
    comm = CommConfig(layer_strategies={"ip1": TOPK}, topk_fraction=0.05)
    params = lenet_net.init(jax.random.PRNGKey(0))
    ts = build_train_step(lenet_net, sp, mesh, comm, donate=False,
                          iter_size=2)
    batch = {"data": jnp.asarray(rng_np.randn(2, BATCH, 1, 28, 28)
                                 .astype(np.float32)),
             "label": jnp.asarray(rng_np.randint(0, 10, size=(2, BATCH))
                                  .astype(np.int32))}
    p, s = params, init_train_state(params, comm, N_DEV)
    for _ in range(3):
        p, s, m = ts.step(p, s, batch, jax.random.PRNGKey(7))
    assert np.isfinite(float(m["loss"]))
    # residual is nonzero (something was withheld) and params are finite
    resid = s.comm_error["ip1"]["w"]
    assert float(jnp.abs(resid).sum()) > 0


def test_dwbp_bucketed_matches_dense(mesh, lenet_net, rng_np):
    """Chained (bucketed) DWBP taps are an ORDERING change only: the psums
    are gated on chain tokens, never rescaled — parameters after a step must
    match plain dense bit-for-bit (the gate is the identity for any finite
    token), and the compiled program must keep the buckets' collectives
    DISTINCT (the whole point: round 3 showed the combiner merges unchained
    taps into one all-reduce)."""
    sp = SolverParameter(base_lr=0.01, lr_policy="fixed", momentum=0.9)
    params = lenet_net.init(jax.random.PRNGKey(0))
    batch = _global_batch(rng_np)
    dense = build_train_step(lenet_net, sp, mesh, CommConfig(), donate=False)
    # bucket 0 MB = one chain stage per parameter (per-blob granularity)
    chained = build_train_step(lenet_net, sp, mesh,
                               CommConfig(dwbp_bucket_mb=0), donate=False)
    p1, _, m1 = dense.step(params, init_train_state(params), batch,
                           jax.random.PRNGKey(7))
    p2, _, m2 = chained.step(params, init_train_state(params), batch,
                             jax.random.PRNGKey(7))
    assert float(m1["loss"]) == pytest.approx(float(m2["loss"]), rel=1e-6)
    for l in p1:
        for k in p1[l]:
            np.testing.assert_array_equal(
                np.asarray(p1[l][k]), np.asarray(p2[l][k]),
                err_msg=f"{l}/{k}")

    # distinctness: the chained program must carry MORE gradient all-reduces
    # than the unchained one (whose taps the combiner merges into ~1)
    def n_all_reduce(ts):
        hlo = ts.lowerable.lower(params, init_train_state(params), batch,
                                 jax.random.PRNGKey(7)).compile().as_text()
        return sum(line.count(" all-reduce(") + line.count(" all-reduce-start(")
                   for line in hlo.splitlines())

    n_dense, n_chained = n_all_reduce(dense), n_all_reduce(chained)
    # lenet has 4 param layers x (w, b) = 8 taps; metrics psums add a couple
    assert n_chained > n_dense, (n_dense, n_chained)
    assert n_chained >= 8


def test_dwbp_bucket_grouping(mesh, lenet_net, rng_np):
    """A large bucket budget must group taps: strictly fewer collectives
    than per-blob chaining, while still matching dense numerically."""
    sp = SolverParameter(base_lr=0.01, lr_policy="fixed", momentum=0.9)
    params = lenet_net.init(jax.random.PRNGKey(0))
    batch = _global_batch(rng_np)

    def n_all_reduce(cfg):
        ts = build_train_step(lenet_net, sp, mesh, cfg, donate=False)
        hlo = ts.lowerable.lower(params, init_train_state(params), batch,
                                 jax.random.PRNGKey(7)).compile().as_text()
        return sum(line.count(" all-reduce(") + line.count(" all-reduce-start(")
                   for line in hlo.splitlines())

    per_blob = n_all_reduce(CommConfig(dwbp_bucket_mb=0))
    bucketed = n_all_reduce(CommConfig(dwbp_bucket_mb=1.0))
    assert bucketed < per_blob, (bucketed, per_blob)


@pytest.mark.parametrize("param_arena", [True, False])
def test_sfb_topk_layers_keep_their_paths_beside_dense_taps(
        mesh, lenet_net, rng_np, param_arena):
    """SFB and TOPK layers keep their custom comm paths beside the DENSE
    layers' per-leaf taps, whatever ``param_arena`` says (it decides
    nothing in this step, PR 59): a mixed-strategy step equals the
    reference built from the parts — ``jax.grad`` on each shard, a plain
    ``lax.psum`` mean for the DENSE leaves AND for the SFB layer (the
    factor exchange reconstructs the same global gradient), the TOPK layer
    through ``topk_compress`` on the raw local gradient — to f32
    rounding, TOPK error-feedback residuals included."""
    from jax import lax, shard_map
    from jax.sharding import PartitionSpec as P

    from poseidon_tpu.parallel.strategies import comm_salt, topk_compress
    from poseidon_tpu.parallel.trainer import param_mults
    from poseidon_tpu.solvers.updates import make_update_fn
    sp = SolverParameter(base_lr=0.01, lr_policy="fixed", momentum=0.9,
                         weight_decay=0.0005)
    params = lenet_net.init(jax.random.PRNGKey(0))
    batch = _global_batch(rng_np)
    comm = CommConfig(layer_strategies={"ip1": SFB, "conv2": "topk"},
                      topk_fraction=0.1, param_arena=param_arena)
    ts = build_train_step(lenet_net, sp, mesh, comm, donate=False)
    assert ts.arena is None
    p, s = params, init_train_state(params, comm, N_DEV)
    for i in range(2):
        p, s, m = ts.step(p, s, batch, jax.random.PRNGKey(i))

    update = make_update_fn(sp, param_mults(lenet_net))

    def device_step(params, solver, err, batch, rng):
        rng = jax.random.fold_in(rng, lax.axis_index("data"))
        grads = jax.grad(lambda q: lenet_net.apply(
            q, batch, train=True, rng=rng).loss)(params)
        new_err = {}
        for l in grads:
            for k, g in grads[l].items():
                if l == "conv2":
                    sent, resid = topk_compress(
                        g, 0.1, err[l][k][0], "magnitude", solver.it,
                        salt=comm_salt(l, k))
                    grads[l][k] = lax.psum(sent, "data") / N_DEV
                    new_err.setdefault(l, {})[k] = resid[None]
                else:
                    grads[l][k] = lax.psum(g, "data") / N_DEV
        params, solver = update(params, grads, solver)
        return params, solver, new_err

    ref = jax.jit(shard_map(
        device_step, mesh=mesh,
        in_specs=(P(), P(), P("data"), P("data"), P()),
        out_specs=(P(), P(), P("data")), check_vma=False))
    s0 = init_train_state(params, comm, N_DEV)
    rp, rs, rerr = params, s0.solver, s0.comm_error
    for i in range(2):
        rp, rs, rerr = ref(rp, rs, rerr, batch, jax.random.PRNGKey(i))
    for l in p:
        for k in p[l]:
            np.testing.assert_allclose(
                np.asarray(p[l][k]), np.asarray(rp[l][k]),
                rtol=1e-4, atol=1e-6, err_msg=f"{l}/{k}")
    for l in s.comm_error:
        for k in s.comm_error[l]:
            np.testing.assert_allclose(
                np.asarray(s.comm_error[l][k]), np.asarray(rerr[l][k]),
                rtol=1e-4, atol=1e-6, err_msg=f"err {l}/{k}")


def test_auto_strategies_picks_sfb_for_big_fc():
    net = Net(zoo.alexnet(), phase="TRAIN",
              source_shapes=zoo.alexnet_shapes(32))
    strats = auto_strategies(net)
    # fc6: 4096x9216 weight vs batch 32: SFB clearly wins
    assert strats.get("fc6") == SFB
    assert strats.get("fc7") == SFB


def test_topk_sync_keeps_replicas_consistent(mesh, lenet_net, rng_np):
    sp = SolverParameter(base_lr=0.01, lr_policy="fixed")
    params = lenet_net.init(jax.random.PRNGKey(0))
    batch = _global_batch(rng_np)
    cc = CommConfig(default_strategy="topk", topk_fraction=0.1)
    ts = build_train_step(lenet_net, sp, mesh, cc, donate=False)
    p, s = params, init_train_state(params, cc, N_DEV)
    for _ in range(2):
        p, s, m = ts.step(p, s, batch, jax.random.PRNGKey(3))
    # params replicated => no NaNs, finite, and training moved
    w = np.asarray(p["conv1"]["w"])
    assert np.isfinite(w).all()
    assert np.abs(w - np.asarray(params["conv1"]["w"])).max() > 0


def test_topk_error_feedback_preserves_convergence(mesh, lenet_net, rng_np):
    """TOPK@10% must land within a modest margin of dense training after N
    steps — the error-feedback guarantee (delayed, not lost). Also exercises
    comm_error across snapshot/restore mid-run."""
    sp = SolverParameter(base_lr=0.01, lr_policy="fixed", momentum=0.9)
    params = lenet_net.init(jax.random.PRNGKey(0))
    batch = _global_batch(rng_np)
    n_iters = 14

    dense = build_train_step(lenet_net, sp, mesh, CommConfig(), donate=False)
    p, s = params, init_train_state(params)
    for i in range(n_iters):
        p, s, m_dense = dense.step(p, s, batch, jax.random.PRNGKey(i))

    cc = CommConfig(default_strategy="topk", topk_fraction=0.1)
    ts = build_train_step(lenet_net, sp, mesh, cc, donate=False)
    p, s = params, init_train_state(params, cc, N_DEV)
    for i in range(n_iters // 2):
        p, s, m = ts.step(p, s, batch, jax.random.PRNGKey(i))

    # mid-run snapshot/restore roundtrip must preserve the residuals exactly
    from poseidon_tpu.runtime.checkpoint import restore, snapshot
    import tempfile, os
    with tempfile.TemporaryDirectory() as d:
        _, state_path = snapshot(os.path.join(d, "tk"), lenet_net, p, s)
        p2, s2 = restore(state_path)
        for l, lp_ in s.comm_error.items():
            for k in lp_:
                np.testing.assert_array_equal(
                    np.asarray(s2.comm_error[l][k]), np.asarray(lp_[k]))
    for i in range(n_iters // 2, n_iters):
        p2, s2, m_topk = ts.step(p2, s2, batch, jax.random.PRNGKey(i))

    start = float(np.log(10))
    d_loss, t_loss = float(m_dense["loss"]), float(m_topk["loss"])
    assert d_loss < 0.5 * start
    # within half of dense's progress despite sending only 10% of entries
    assert t_loss < d_loss + 0.5 * (start - d_loss), \
        f"topk {t_loss} vs dense {d_loss}"


def test_eval_step(mesh, rng_np):
    net = Net(zoo.lenet(with_accuracy=True), phase="TEST",
              source_shapes=zoo.lenet_shapes(BATCH // N_DEV))
    params = net.init(jax.random.PRNGKey(0))
    ev = build_eval_step(net, mesh)
    metrics = ev(params, _global_batch(rng_np))
    assert 0.0 <= float(metrics["accuracy"]) <= 1.0
    assert float(metrics["loss"]) == pytest.approx(np.log(10), rel=0.3)


def test_ssp_bounded_staleness(mesh, lenet_net, rng_np):
    sp = SolverParameter(base_lr=0.01, lr_policy="fixed", momentum=0.9)
    params = lenet_net.init(jax.random.PRNGKey(0))
    batch = _global_batch(rng_np)
    staleness = 2
    ts = build_ssp_train_step(lenet_net, sp, mesh, staleness)
    st = init_ssp_state(params, N_DEV)
    for i in range(1, 7):
        st, m = ts.step(st, batch, jax.random.PRNGKey(i))
        local = np.asarray(st.local_params["conv1"]["w"])
        spread = np.abs(local - local[0:1]).max()
        if i % (staleness + 1) == 0:
            # just synced: all replicas identical
            assert spread == 0.0, f"iter {i}"
        else:
            # replicas allowed to drift between syncs
            assert np.isfinite(local).all()
    assert np.isfinite(float(m["loss"]))


def test_ssp_converges_close_to_sync(mesh, lenet_net, rng_np):
    """SSP s=2 must track synchronous training: after N iters on a fixed
    batch, its loss lands within a small margin of the s=0 loss (the bounded
    -staleness convergence claim, ssp_consistency_controller.cpp)."""
    sp = SolverParameter(base_lr=0.01, lr_policy="fixed", momentum=0.9)
    params = lenet_net.init(jax.random.PRNGKey(0))
    batch = _global_batch(rng_np)
    n_iters = 9  # multiple of period so the final iter is a sync point

    sync_ts = build_train_step(lenet_net, sp, mesh, CommConfig(),
                               donate=False)
    p, s = params, init_train_state(params)
    for i in range(n_iters):
        p, s, m_sync = sync_ts.step(p, s, batch, jax.random.PRNGKey(i))

    ssp_ts = build_ssp_train_step(lenet_net, sp, mesh, staleness=2)
    st = init_ssp_state(params, N_DEV)
    for i in range(n_iters):
        st, m_ssp = ssp_ts.step(st, batch, jax.random.PRNGKey(i))

    sync_loss, ssp_loss = float(m_sync["loss"]), float(m_ssp["loss"])
    start_loss = float(np.log(10))
    # both should have made real progress, and SSP shouldn't lag sync by more
    # than a third of the progress sync made
    assert sync_loss < 0.8 * start_loss
    assert ssp_loss < sync_loss + 0.35 * (start_loss - sync_loss), \
        f"ssp {ssp_loss} vs sync {sync_loss}"


def test_ssp_topk_composition(mesh, lenet_net, rng_np):
    """SSP + TOPK (the SSPAggr pairing): deltas are compressed at sync
    boundaries, residuals carry error feedback, replicas stay consistent."""
    sp = SolverParameter(base_lr=0.01, lr_policy="fixed", momentum=0.9)
    params = lenet_net.init(jax.random.PRNGKey(0))
    batch = _global_batch(rng_np)
    cc = CommConfig(default_strategy="topk", topk_fraction=0.1)
    w0 = np.asarray(params["conv1"]["w"])  # copy before donation eats params
    ts = build_ssp_train_step(lenet_net, sp, mesh, staleness=1, comm=cc)
    st = init_ssp_state(params, N_DEV, cc)
    assert "conv1" in st.comm_error
    for i in range(1, 5):
        st, m = ts.step(st, batch, jax.random.PRNGKey(i))
        local = np.asarray(st.local_params["conv1"]["w"])
        if i % 2 == 0:  # sync point: replicas identical again
            assert np.abs(local - local[0:1]).max() == 0.0, f"iter {i}"
    # error feedback holds the unsent delta mass (non-zero after a sync)
    err = np.asarray(st.comm_error["conv1"]["w"])
    assert np.abs(err).max() > 0
    assert np.isfinite(float(m["loss"]))
    # params moved
    assert np.abs(np.asarray(st.anchor_params["conv1"]["w"]) - w0).max() > 0


def test_ssp_rejects_sfb(mesh, lenet_net):
    sp = SolverParameter(base_lr=0.01, lr_policy="fixed")
    cc = CommConfig(layer_strategies={"ip1": SFB})
    with pytest.raises(ValueError, match="SFB"):
        build_ssp_train_step(lenet_net, sp, mesh, staleness=1, comm=cc)


# --------------------------------------------------------------------------- #
# Two-tier (ici x dcn) mesh: dense intra-slice + managed comm inter-slice
# --------------------------------------------------------------------------- #

@pytest.fixture(scope="module")
def two_tier_mesh():
    return make_mesh(axes=("dcn", "data"), shape=(2, 4))


def _two_tier_cc(**kw):
    return CommConfig(dcn_axis="dcn", **kw)


def test_two_tier_dense_matches_flat(mesh, two_tier_mesh, lenet_net, rng_np):
    """Dense sync over a (2,4) mesh == dense sync over the flat 8-mesh:
    psum over both axes touches the same 8 gradients."""
    sp = SolverParameter(base_lr=0.01, lr_policy="fixed", momentum=0.9)
    params = lenet_net.init(jax.random.PRNGKey(0))
    batch = _global_batch(rng_np)

    flat = build_train_step(lenet_net, sp, mesh, CommConfig(), donate=False)
    tier = build_train_step(lenet_net, sp, two_tier_mesh, _two_tier_cc(),
                            donate=False)
    p1, s1, m1 = flat.step(params, init_train_state(params), batch,
                           jax.random.PRNGKey(7))
    p2, s2, m2 = tier.step(params, init_train_state(params), batch,
                           jax.random.PRNGKey(7))
    assert float(m1["loss"]) == pytest.approx(float(m2["loss"]), rel=1e-5)
    for l in p1:
        for k in p1[l]:
            np.testing.assert_allclose(
                np.asarray(p1[l][k]), np.asarray(p2[l][k]),
                rtol=1e-4, atol=1e-6, err_msg=f"{l}/{k}")


def test_two_tier_sfb_matches_dense(two_tier_mesh, lenet_net, rng_np):
    """SFB factor gathers ride both axes: bit-comparable to two-tier dense."""
    sp = SolverParameter(base_lr=0.01, lr_policy="fixed", momentum=0.9)
    params = lenet_net.init(jax.random.PRNGKey(0))
    batch = _global_batch(rng_np)
    dense = build_train_step(lenet_net, sp, two_tier_mesh, _two_tier_cc(),
                             donate=False)
    sfb = build_train_step(
        lenet_net, sp, two_tier_mesh,
        _two_tier_cc(layer_strategies={"ip1": SFB, "ip2": SFB}),
        donate=False)
    p1, _, m1 = dense.step(params, init_train_state(params), batch,
                           jax.random.PRNGKey(7))
    p2, _, m2 = sfb.step(params, init_train_state(params), batch,
                         jax.random.PRNGKey(7))
    assert float(m1["loss"]) == pytest.approx(float(m2["loss"]), rel=1e-6)
    for l in p1:
        for k in p1[l]:
            np.testing.assert_allclose(
                np.asarray(p1[l][k]), np.asarray(p2[l][k]),
                rtol=1e-4, atol=1e-7, err_msg=f"{l}/{k}")


def test_two_tier_topk_consistent_and_converges(two_tier_mesh, lenet_net,
                                                rng_np):
    """Hierarchical managed comm: dense intra-slice psum + TOPK inter-slice.
    Params stay replicated across ALL devices (both slices applied the same
    compressed exchange), residuals are per-slice, and training converges."""
    from poseidon_tpu.parallel import comm_error_groups
    sp = SolverParameter(base_lr=0.01, lr_policy="fixed", momentum=0.9)
    params = lenet_net.init(jax.random.PRNGKey(0))
    w0 = np.asarray(params["conv1"]["w"])
    batch = _global_batch(rng_np)
    cc = _two_tier_cc(default_strategy="topk", topk_fraction=0.25)
    groups = comm_error_groups(cc, two_tier_mesh)
    assert groups == 2  # one residual per slice, not per device
    ts = build_train_step(lenet_net, sp, two_tier_mesh, cc, donate=False)
    p, s = params, init_train_state(params, cc, groups)
    losses = []
    for i in range(12):
        p, s, m = ts.step(p, s, batch, jax.random.PRNGKey(i))
        losses.append(float(m["loss"]))
    # replicas consistent: out_specs P() would fail to rebuild a replicated
    # array if devices disagreed; also check values are finite and moved
    w = np.asarray(p["conv1"]["w"])
    assert np.isfinite(w).all() and np.abs(w - w0).max() > 0
    # per-slice residuals differ (slices saw different data) and are nonzero
    err = np.asarray(s.comm_error["conv1"]["w"])
    assert err.shape[0] == 2
    assert np.abs(err).max() > 0
    assert np.abs(err[0] - err[1]).max() > 0
    # error feedback preserves convergence despite 75% of entries delayed
    assert losses[-1] < 0.5 * losses[0], losses


def test_two_tier_engine_end_to_end(tmp_path_factory, rng_np):
    """Engine + two-tier mesh: the --dcn_slices path."""
    from poseidon_tpu.runtime.engine import Engine

    tmp_path = tmp_path_factory.mktemp("two_tier")
    from tests.test_runtime import _memory_data, _write_mnistish_prototxt
    from poseidon_tpu.proto.messages import load_solver
    solver_path = _write_mnistish_prototxt(tmp_path, max_iter=25)
    sp = load_solver(solver_path)
    mesh = make_mesh(axes=("dcn", "data"), shape=(2, 4))
    cc = _two_tier_cc(default_strategy="topk", topk_fraction=0.25)
    eng = Engine(sp, comm=cc, mesh=mesh, memory_data=_memory_data(),
                 output_dir=str(tmp_path))
    try:
        last = eng.train()
        assert last["loss"] < 0.6, f"two-tier did not converge: {last}"
        out = eng.test(0)
        assert out["accuracy"] > 0.8
    finally:
        eng.close()


@pytest.mark.parametrize("policy", ["magnitude", "random", "fixed_order"])
def test_topk_policies(mesh, lenet_net, rng_np, policy):
    """UpdateSortPolicy parity (configs.hpp:27-33): every selection policy
    keeps replicas consistent, populates residuals, and still trains."""
    sp = SolverParameter(base_lr=0.01, lr_policy="fixed", momentum=0.9)
    params = lenet_net.init(jax.random.PRNGKey(0))
    batch = _global_batch(rng_np)
    cc = CommConfig(default_strategy="topk", topk_fraction=0.1,
                    topk_policy=policy)
    ts = build_train_step(lenet_net, sp, mesh, cc, donate=False)
    p, s = params, init_train_state(params, cc, N_DEV)
    losses = []
    for i in range(6):
        p, s, m = ts.step(p, s, batch, jax.random.PRNGKey(i))
        losses.append(float(m["loss"]))
    assert np.isfinite(losses).all()
    assert losses[-1] < losses[0], losses  # still learning under the budget
    assert np.abs(np.asarray(s.comm_error["conv1"]["w"])).max() > 0


def test_topk_fixed_order_covers_all_entries():
    """fixed_order rotation sends every entry exactly once per cycle."""
    from poseidon_tpu.parallel.strategies import topk_compress
    g = jnp.arange(1.0, 11.0)
    err = jnp.zeros(10)
    seen = np.zeros(10, bool)
    for step in range(5):  # fraction 0.2 -> slabs of 2 -> 5-step cycle
        sent, err_new = topk_compress(g, 0.2, jnp.zeros(10),
                                      "fixed_order", step)
        nz = np.asarray(sent) != 0
        assert nz.sum() == 2
        assert not (seen & nz).any()  # no entry twice in a cycle
        seen |= nz
    assert seen.all()


def test_bandwidth_budget_derives_topk_fraction(lenet_net):
    from poseidon_tpu.parallel.strategies import budget_topk_fraction
    cc = CommConfig(default_strategy="topk", bandwidth_budget_mb=0.1)
    frac = budget_topk_fraction(lenet_net, cc)
    total = lenet_net.param_count()
    assert frac == pytest.approx(0.1e6 / 8.0 / total, rel=1e-6)
    # no budget -> configured fraction
    assert budget_topk_fraction(lenet_net, CommConfig()) == 0.01


# --------------------------------------------------------------------------- #
# Reduced-precision wire (DenseRowFloat16 analog) + blocked top-k
# --------------------------------------------------------------------------- #

def test_wire_dtype_bf16_converges_close_to_f32(mesh, lenet_net, rng_np):
    """bf16 gradient exchange must track full-precision training closely —
    the DenseRowFloat16 trade (dense_row_float16.hpp:10-16), compiled."""
    sp = SolverParameter(base_lr=0.01, lr_policy="fixed", momentum=0.9)
    params = lenet_net.init(jax.random.PRNGKey(0))
    batch = _global_batch(rng_np)
    n_iters = 10

    f32 = build_train_step(lenet_net, sp, mesh, CommConfig(), donate=False)
    p1, s1 = params, init_train_state(params)
    for i in range(n_iters):
        p1, s1, m1 = f32.step(p1, s1, batch, jax.random.PRNGKey(i))

    cc = CommConfig(wire_dtype="bf16")
    bw = build_train_step(lenet_net, sp, mesh, cc, donate=False)
    p2, s2 = params, init_train_state(params, cc, N_DEV)
    for i in range(n_iters):
        p2, s2, m2 = bw.step(p2, s2, batch, jax.random.PRNGKey(i))

    start = float(np.log(10))
    l1, l2 = float(m1["loss"]), float(m2["loss"])
    assert l1 < 0.7 * start
    # within a third of full-precision progress despite half-width wire
    assert l2 < l1 + 0.33 * (start - l1), f"bf16 wire {l2} vs f32 {l1}"
    for l in p1:
        for k in p1[l]:
            np.testing.assert_allclose(
                np.asarray(p1[l][k]), np.asarray(p2[l][k]),
                rtol=0.1, atol=5e-3, err_msg=f"{l}/{k}")


def test_wire_dtype_lowers_bf16_collectives(mesh, lenet_net, rng_np):
    """The compiled step must actually carry bf16 operands into the
    collectives (not cast after): check the lowered module text."""
    sp = SolverParameter(base_lr=0.01, lr_policy="fixed")
    params = lenet_net.init(jax.random.PRNGKey(0))
    batch = _global_batch(rng_np)
    cc = CommConfig(wire_dtype="bf16")
    ts = build_train_step(lenet_net, sp, mesh, cc, donate=False)
    state = init_train_state(params, cc, N_DEV)
    text = ts.lowerable.lower(params, state, batch,
                              jax.random.PRNGKey(0)).as_text()
    assert "bf16" in text
    # the f32 build has no bf16 anywhere (compute dtype is f32 in tests)
    ts0 = build_train_step(lenet_net, sp, mesh, CommConfig(), donate=False)
    t0 = ts0.lowerable.lower(params, init_train_state(params), batch,
                             jax.random.PRNGKey(0)).as_text()
    assert "bf16" not in t0


def test_wire_dtype_sfb_and_topk(mesh, lenet_net, rng_np):
    """wire_dtype composes with SFB (factors gathered at bf16) and TOPK
    (values quantized into the error-feedback residual)."""
    sp = SolverParameter(base_lr=0.01, lr_policy="fixed", momentum=0.9)
    params = lenet_net.init(jax.random.PRNGKey(0))
    batch = _global_batch(rng_np)
    cc = CommConfig(wire_dtype="bf16",
                    layer_strategies={"ip1": SFB, "ip2": SFB,
                                      "conv1": "topk", "conv2": "topk"},
                    topk_fraction=0.2)
    ts = build_train_step(lenet_net, sp, mesh, cc, donate=False)
    p, s = params, init_train_state(params, cc, N_DEV)
    losses = []
    for i in range(8):
        p, s, m = ts.step(p, s, batch, jax.random.PRNGKey(i))
        losses.append(float(m["loss"]))
    assert np.isfinite(losses).all()
    assert losses[-1] < losses[0], losses


def test_wire_dtype_ssp(mesh, lenet_net, rng_np):
    """wire_dtype applies to the SSP delta exchange at sync boundaries."""
    from poseidon_tpu.parallel import build_ssp_train_step, init_ssp_state
    sp = SolverParameter(base_lr=0.01, lr_policy="fixed", momentum=0.9)
    params = lenet_net.init(jax.random.PRNGKey(0))
    batch = _global_batch(rng_np)
    cc = CommConfig(wire_dtype="bf16")
    ts = build_ssp_train_step(lenet_net, sp, mesh, staleness=1, comm=cc)
    s = init_ssp_state(params, N_DEV, cc)
    losses = []
    for i in range(8):
        s, m = ts.step(s, batch, jax.random.PRNGKey(i))
        losses.append(float(m["loss"]))
    assert np.isfinite(losses).all()
    assert losses[-1] < losses[0], losses


def test_blocked_topk_matches_global_budget():
    """Blocked selection keeps >= the global-k budget, selects the per-block
    maxima, and feeds the complement into the residual."""
    from poseidon_tpu.parallel.strategies import topk_compress
    rng = np.random.RandomState(0)
    g = jnp.asarray(rng.randn(1000).astype(np.float32))
    err = jnp.zeros(1000, jnp.float32)
    sent, resid = topk_compress(g, 0.01, err, "magnitude", block=100)
    nz = np.asarray(sent) != 0
    # ceil(10/10) = 1 per block x 10 blocks = 10 entries
    assert nz.sum() == 10
    # each block's winner is that block's max-|g| entry
    ga = np.asarray(g).reshape(10, 100)
    for b in range(10):
        w = np.abs(ga[b]).argmax()
        assert nz.reshape(10, 100)[b, w]
    np.testing.assert_allclose(np.asarray(sent + resid), np.asarray(g),
                               rtol=1e-6)


def test_blocked_topk_nondivisible_and_training(mesh, lenet_net, rng_np):
    """Padding path (size not a multiple of block) + end-to-end training."""
    from poseidon_tpu.parallel.strategies import topk_compress
    g = jnp.asarray(np.random.RandomState(1).randn(103).astype(np.float32))
    sent, resid = topk_compress(g, 0.1, jnp.zeros(103), "magnitude",
                                block=25)
    np.testing.assert_allclose(np.asarray(sent + resid), np.asarray(g),
                               rtol=1e-6)
    assert (np.asarray(sent) != 0).sum() >= 10

    sp = SolverParameter(base_lr=0.01, lr_policy="fixed", momentum=0.9)
    params = lenet_net.init(jax.random.PRNGKey(0))
    batch = _global_batch(rng_np)
    cc = CommConfig(default_strategy="topk", topk_fraction=0.1,
                    topk_block=256)
    ts = build_train_step(lenet_net, sp, mesh, cc, donate=False)
    p, s = params, init_train_state(params, cc, N_DEV)
    losses = []
    for i in range(10):
        p, s, m = ts.step(p, s, batch, jax.random.PRNGKey(i))
        losses.append(float(m["loss"]))
    assert np.isfinite(losses).all()
    assert losses[-1] < losses[0], losses


def test_random_topk_decorrelated_across_layers():
    """Same-shaped tensors in different layers must select different random
    subsets (the per-table independence of the reference's Random policy)."""
    from poseidon_tpu.parallel.strategies import comm_salt, topk_compress
    g = jnp.ones(1000)
    err = jnp.zeros(1000)
    s1, _ = topk_compress(g, 0.05, err, "random", step=3,
                          salt=comm_salt("conv1", "w"))
    s2, _ = topk_compress(g, 0.05, err, "random", step=3,
                          salt=comm_salt("conv2", "w"))
    nz1 = np.flatnonzero(np.asarray(s1))
    nz2 = np.flatnonzero(np.asarray(s2))
    assert not np.array_equal(nz1, nz2)


# --------------------------------------------------------------------------- #
# SSP x two-tier mesh: staleness on the DCN tier, dense ICI tier every step
# (the SSPAggr deployment: full-rate intra-machine, managed inter-machine)
# --------------------------------------------------------------------------- #

def test_ssp_two_tier_slices_sync_on_boundary(two_tier_mesh, lenet_net,
                                              rng_np):
    """With staleness on the DCN tier, the two slices diverge between syncs
    and reconcile exactly at the boundary; devices inside a slice see the
    same slice-local params throughout."""
    sp = SolverParameter(base_lr=0.01, lr_policy="fixed", momentum=0.9)
    params = lenet_net.init(jax.random.PRNGKey(0))
    batch = _global_batch(rng_np)
    cc = _two_tier_cc()
    ts = build_ssp_train_step(lenet_net, sp, two_tier_mesh, staleness=1,
                              comm=cc)
    st = init_ssp_state(params, 2, cc)  # 2 slices
    for i in range(1, 5):
        st, m = ts.step(st, batch, jax.random.PRNGKey(i))
        local = np.asarray(st.local_params["conv1"]["w"])  # (2, ...)
        diverged = np.abs(local[0] - local[1]).max()
        if i % 2 == 0:  # sync boundary: slices reconciled
            assert diverged == 0.0, f"iter {i}: slices differ by {diverged}"
        else:           # mid-period: slices have diverged (different shards)
            assert diverged > 0.0, f"iter {i}: slices did not diverge"
    assert np.isfinite(float(m["loss"]))


def test_ssp_two_tier_with_sfb_and_topk(two_tier_mesh, lenet_net, rng_np):
    """The full SSPAggr composition: SFB FC layers ride the per-step ICI
    tier, conv layers TOPK-compress their deltas across the DCN tier, all
    under staleness 1 — and training still converges."""
    sp = SolverParameter(base_lr=0.01, lr_policy="fixed", momentum=0.9)
    params = lenet_net.init(jax.random.PRNGKey(0))
    batch = _global_batch(rng_np)
    cc = _two_tier_cc(layer_strategies={"ip1": SFB, "ip2": SFB,
                                        "conv1": "topk", "conv2": "topk"},
                      topk_fraction=0.2)
    ts = build_ssp_train_step(lenet_net, sp, two_tier_mesh, staleness=1,
                              comm=cc)
    st = init_ssp_state(params, 2, cc)
    assert "conv1" in st.comm_error and "ip1" not in st.comm_error
    losses = []
    for i in range(10):
        st, m = ts.step(st, batch, jax.random.PRNGKey(i))
        losses.append(float(m["loss"]))
    assert np.isfinite(losses).all()
    assert losses[-1] < losses[0], losses
    # TOPK residuals hold unsent delta mass after a sync
    assert np.abs(np.asarray(st.comm_error["conv1"]["w"])).max() > 0


def test_ssp_two_tier_staleness0_matches_sync(two_tier_mesh, lenet_net,
                                              rng_np):
    """staleness=0 over the two-tier mesh must equal the fully-synchronous
    two-tier step: every step reconciles, so no divergence survives."""
    sp = SolverParameter(base_lr=0.01, lr_policy="fixed", momentum=0.9)
    params = lenet_net.init(jax.random.PRNGKey(0))
    batch = _global_batch(rng_np)
    cc = _two_tier_cc()
    sync = build_train_step(lenet_net, sp, two_tier_mesh, cc, donate=False)
    p1, s1 = params, init_train_state(params, cc, 2)
    ssp = build_ssp_train_step(lenet_net, sp, two_tier_mesh, staleness=0,
                               comm=cc)
    st = init_ssp_state(params, 2, cc)
    for i in range(3):
        p1, s1, m1 = sync.step(p1, s1, batch, jax.random.PRNGKey(9))
        st, m2 = ssp.step(st, batch, jax.random.PRNGKey(9))
    assert float(m1["loss"]) == pytest.approx(float(m2["loss"]), rel=1e-4)
    for l in p1:
        for k in p1[l]:
            np.testing.assert_allclose(
                np.asarray(p1[l][k]), np.asarray(st.anchor_params[l][k]),
                rtol=1e-3, atol=1e-5, err_msg=f"{l}/{k}")


def test_ssp_resume_across_topologies(mesh, two_tier_mesh, lenet_net,
                                      rng_np):
    """A flat-mesh SSP snapshot (8 per-device groups) resumes onto the
    two-tier mesh (2 per-slice groups): coerce_state re-seeds the local
    replicas from the anchor at the stored iteration."""
    from poseidon_tpu.runtime.checkpoint import coerce_state
    sp = SolverParameter(base_lr=0.01, lr_policy="fixed", momentum=0.9)
    params = lenet_net.init(jax.random.PRNGKey(0))
    batch = _global_batch(rng_np)

    flat_cc = CommConfig()
    ts = build_ssp_train_step(lenet_net, sp, mesh, staleness=1, comm=flat_cc)
    st = init_ssp_state(params, N_DEV, flat_cc)
    for i in range(4):
        st, _ = ts.step(st, batch, jax.random.PRNGKey(i))

    tt_cc = _two_tier_cc(default_strategy="topk", topk_fraction=0.2)
    p2, st2 = coerce_state(st.anchor_params, st, staleness=1, n_dev=2,
                           comm=tt_cc)
    assert jax.tree_util.tree_leaves(st2.local_params)[0].shape[0] == 2
    assert int(st2.it) == 4  # iteration survives the topology change
    ts2 = build_ssp_train_step(lenet_net, sp, two_tier_mesh, staleness=1,
                               comm=tt_cc)
    losses = []
    for i in range(4):
        st2, m = ts2.step(st2, batch, jax.random.PRNGKey(10 + i))
        losses.append(float(m["loss"]))
    assert np.isfinite(losses).all()
    assert losses[-1] < losses[0] + 0.05  # keeps converging after resume


def test_blocked_topk_honors_budget_from_below():
    """The blocked path never exceeds the k budget; when k < n_blocks it
    falls back to exact global selection (budget contract, SSPAggr's
    bandwidth bound)."""
    from poseidon_tpu.parallel.strategies import topk_compress
    rng = np.random.RandomState(0)
    g = jnp.asarray(rng.randn(10000).astype(np.float32))
    err = jnp.zeros(10000, jnp.float32)
    # k = 100, blocks of 100 -> 100 blocks, kb = 1 -> exactly 100 sent
    sent, _ = topk_compress(g, 0.01, err, "magnitude", block=100)
    assert (np.asarray(sent) != 0).sum() == 100
    # k = 10 < 100 blocks -> global fallback, exactly 10 sent (not 100)
    sent2, _ = topk_compress(g, 0.001, err, "magnitude", block=100)
    assert (np.asarray(sent2) != 0).sum() == 10
    # global fallback picks the true global top-10
    top10 = np.argsort(-np.abs(np.asarray(g)))[:10]
    assert set(np.flatnonzero(np.asarray(sent2))) == set(top10)


def test_wire_dtype_f16_converges(mesh, lenet_net, rng_np):
    """f16 wire (the reference's actual DenseRowFloat16 dtype): narrower
    exponent than bf16, still converges at LeNet scale with mean reduce
    (overflow at extreme device counts is the documented trade)."""
    sp = SolverParameter(base_lr=0.01, lr_policy="fixed", momentum=0.9)
    params = lenet_net.init(jax.random.PRNGKey(0))
    batch = _global_batch(rng_np)
    cc = CommConfig(wire_dtype="f16")
    ts = build_train_step(lenet_net, sp, mesh, cc, donate=False)
    p, s = params, init_train_state(params, cc, N_DEV)
    losses = []
    for i in range(8):
        p, s, m = ts.step(p, s, batch, jax.random.PRNGKey(i))
        losses.append(float(m["loss"]))
    assert np.isfinite(losses).all()
    assert losses[-1] < 0.8 * losses[0], losses
