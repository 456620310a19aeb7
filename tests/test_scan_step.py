"""Multi-step-per-dispatch (scan_steps): K optimizer steps in one program.

A runtime's per-dispatch round-trip can dwarf the device step, so the
trainer has a lax.scan-over-steps mode. Invariant: scan_steps=K runs the
same math as K sequential single-step dispatches fed the same microbatches
and rng stream — equal up to compilation-order float rounding (the two
programs fuse differently), so parameters are compared at tight tolerance,
not bit equality.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from poseidon_tpu.core.net import Net
from poseidon_tpu.models import zoo
from poseidon_tpu.parallel import (
    CommConfig, SFB, TOPK, build_train_step, init_train_state, make_mesh,
    stack_batches)
from poseidon_tpu.proto.messages import SolverParameter

N_DEV = 8
BATCH = 16
K = 4


@pytest.fixture(scope="module")
def mesh():
    assert jax.device_count() == N_DEV
    return make_mesh()


@pytest.fixture(scope="module")
def net():
    return Net(zoo.lenet(with_accuracy=False), phase="TRAIN",
               source_shapes=zoo.lenet_shapes(BATCH // N_DEV))


def _batches(rng, k=K):
    return [{
        "data": rng.randn(BATCH, 1, 28, 28).astype(np.float32),
        "label": rng.randint(0, 10, size=(BATCH,)),
    } for _ in range(k)]


def _sp():
    return SolverParameter(base_lr=0.01, lr_policy="fixed", momentum=0.9,
                           weight_decay=0.0005)


@pytest.mark.parametrize("comm_kw", [
    {},
    {"layer_strategies": {"ip1": SFB}},
    {"layer_strategies": {"ip2": TOPK}, "topk_fraction": 0.25},
])
def test_scan_matches_sequential(mesh, net, rng_np, comm_kw):
    comm = CommConfig(**comm_kw)
    params = net.init(jax.random.PRNGKey(0))
    batches = _batches(rng_np)
    rng = jax.random.PRNGKey(7)

    # sequential single-step dispatches, rng folded per step like scan does
    ts1 = build_train_step(net, _sp(), mesh, comm, donate=False)
    p, s = params, init_train_state(params, comm, N_DEV)
    losses = []
    for i, b in enumerate(batches):
        b = {k: jnp.asarray(v) for k, v in b.items()}
        p, s, m = ts1.step(p, s, b, jax.random.fold_in(rng, i))
        losses.append(float(m["loss"]))

    tsk = build_train_step(net, _sp(), mesh, comm, donate=False,
                           scan_steps=K)
    assert tsk.scan_steps == K
    stacked = stack_batches(batches, tsk.batch_sharding)
    assert stacked["data"].shape == (K, BATCH, 1, 28, 28)
    pk, sk, mk = tsk.step(params, init_train_state(params, comm, N_DEV),
                          stacked, rng)

    assert mk["loss"].shape == (K,)
    np.testing.assert_allclose(np.asarray(mk["loss"]), losses, rtol=1e-6)
    # same math, but scan-compiled vs per-step-compiled programs may fuse
    # (and so round) differently — tight tolerance, not bit equality
    jax.tree.map(
        lambda a, b: np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=5e-4, atol=1e-6),
        p, pk)
    np.testing.assert_array_equal(np.asarray(s.solver.it),
                                  np.asarray(sk.solver.it))


def test_scan_on_two_tier_mesh(net, rng_np):
    from jax.sharding import Mesh
    devs = np.array(jax.devices()).reshape(2, 4)
    mesh = Mesh(devs, ("dcn", "data"))
    comm = CommConfig(dcn_axis="dcn",
                      layer_strategies={"ip2": TOPK}, topk_fraction=0.25)
    params = net.init(jax.random.PRNGKey(0))
    from poseidon_tpu.parallel import comm_error_groups
    tsk = build_train_step(net, _sp(), mesh, comm, donate=False,
                           scan_steps=K)
    stacked = stack_batches(_batches(rng_np), tsk.batch_sharding)
    state0 = init_train_state(params, comm, comm_error_groups(comm, mesh))
    pk, sk, mk = tsk.step(params, state0, stacked, jax.random.PRNGKey(7))
    assert mk["loss"].shape == (K,)
    assert np.isfinite(np.asarray(mk["loss"])).all()
    assert int(sk.solver.it) == K


def test_scan_rejects_dump_blobs(mesh, net):
    with pytest.raises(ValueError, match="scan_steps"):
        build_train_step(net, _sp(), mesh, CommConfig(), scan_steps=2,
                         dump_blobs=["ip1"])


def test_scan_reuse_batch_matches_repeated_batch(mesh, net, rng_np):
    """scan_reuse_batch=True == scan over K copies of the same batch: same
    final params, same per-step losses, one on-device batch."""
    comm = CommConfig()
    params = net.init(jax.random.PRNGKey(0))
    one = _batches(rng_np, k=1)[0]
    rng = jax.random.PRNGKey(7)

    tsk = build_train_step(net, _sp(), mesh, comm, donate=False,
                           scan_steps=K)
    stacked = stack_batches([one] * K, tsk.batch_sharding)
    pk, sk, mk = tsk.step(params, init_train_state(params, comm, N_DEV),
                          stacked, rng)

    tsr = build_train_step(net, _sp(), mesh, comm, donate=False,
                           scan_steps=K, scan_reuse_batch=True)
    single = {k: jax.device_put(jnp.asarray(v), tsr.batch_sharding)
              for k, v in one.items()}
    assert single["data"].shape == (BATCH, 1, 28, 28)  # no [K] axis
    pr, sr, mr = tsr.step(params, init_train_state(params, comm, N_DEV),
                          single, rng)

    assert mr["loss"].shape == (K,)
    np.testing.assert_allclose(np.asarray(mr["loss"]),
                               np.asarray(mk["loss"]), rtol=1e-6)
    jax.tree.map(
        lambda a, b: np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=5e-4, atol=1e-6),
        pr, pk)
    # params actually evolved (it's K optimizer steps, not one)
    assert int(sr.solver.it) == K
