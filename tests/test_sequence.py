"""Ring attention / all-to-all sequence parallelism vs full attention."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import shard_map
from jax.sharding import PartitionSpec as P

from poseidon_tpu.ops.attention import attention
from poseidon_tpu.parallel.mesh import make_mesh
from poseidon_tpu.parallel.sequence import (ring_attention,
                                            ring_flash_attention,
                                            ulysses_attention)

N_DEV = 8
B, H, S, D = 2, 8, 64, 16  # S sharded into 8 blocks of 8


@pytest.fixture(scope="module")
def mesh():
    return make_mesh(axes=("seq",))


@pytest.fixture(scope="module")
def qkv(rng_np=None):
    rs = np.random.RandomState(0)
    mk = lambda: jnp.asarray(rs.randn(B, H, S, D).astype(np.float32) * 0.5)
    return mk(), mk(), mk()


def _sharded(mesh, fn, causal):
    wrapped = shard_map(
        functools.partial(fn, axis="seq", causal=causal),
        mesh=mesh,
        in_specs=(P(None, None, "seq"), P(None, None, "seq"),
                  P(None, None, "seq")),
        out_specs=P(None, None, "seq"),
        check_vma=False)
    return jax.jit(wrapped)


@pytest.mark.parametrize("causal", [False, True])
def test_ring_attention_matches_full(mesh, qkv, causal):
    q, k, v = qkv
    want = attention(q, k, v, causal=causal)
    got = _sharded(mesh, ring_attention, causal)(q, k, v)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-4, atol=2e-5)


@pytest.mark.parametrize("causal", [False, True])
def test_ulysses_attention_matches_full(mesh, qkv, causal):
    q, k, v = qkv
    want = attention(q, k, v, causal=causal)
    got = _sharded(mesh, ulysses_attention, causal)(q, k, v)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-4, atol=2e-5)


def _sharded_flash(mesh, causal, block=8):
    wrapped = shard_map(
        lambda q, k, v: ring_flash_attention(q, k, v, "seq", causal, None,
                                             block, True),
        mesh=mesh,
        in_specs=(P(None, None, "seq"), P(None, None, "seq"),
                  P(None, None, "seq")),
        out_specs=P(None, None, "seq"),
        check_vma=False)
    return jax.jit(wrapped)


@pytest.fixture(scope="module")
def qkv_long():
    """S=128 over 8 devices: chunks of 16 rows."""
    rs = np.random.RandomState(1)
    mk = lambda: jnp.asarray(rs.randn(B, H, 128, D).astype(np.float32) * 0.5)
    return mk(), mk(), mk()


# (sequence fixture, block): one block per chunk (as ever); a chunk of TWO
# blocks, so a diagonal chunk has a dead block and a live one that needs
# no mask and the kernels' chunk_mode must mask both alike; and the tile
# rule's own choice for the chunk length. Eight devices under causal meet
# all three alignments: past (+1), diagonal (0) and future (-1) chunks.
RING_CASES = [("qkv", 8), ("qkv_long", 8), ("qkv_long", None)]


@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("which, block", RING_CASES)
def test_ring_flash_attention_matches_full(mesh, request, which, block,
                                           causal):
    """Ring exchange with per-chunk Pallas flash kernels + lse merge."""
    q, k, v = request.getfixturevalue(which)
    want = attention(q, k, v, causal=causal)
    got = _sharded_flash(mesh, causal, block)(q, k, v)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-4, atol=2e-5)


def _assert_grads_match(loss_full, loss_other, qkv):
    g_full = jax.grad(loss_full, argnums=(0, 1, 2))(*qkv)
    g_other = jax.grad(loss_other, argnums=(0, 1, 2))(*qkv)
    for gf, go, name in zip(g_full, g_other, "qkv"):
        np.testing.assert_allclose(np.asarray(go), np.asarray(gf),
                                   rtol=5e-3, atol=5e-4, err_msg=name)


@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("which, block", RING_CASES)
def test_ring_flash_attention_gradients_match(mesh, request, which, block,
                                              causal):
    """The ring-level custom VJP (dk/dv accumulators riding the ring) vs the
    dense reference gradients."""
    ring = _sharded_flash(mesh, causal, block)
    _assert_grads_match(
        lambda q, k, v: jnp.sum(attention(q, k, v, causal=causal) ** 2),
        lambda q, k, v: jnp.sum(ring(q, k, v) ** 2),
        request.getfixturevalue(which))


@pytest.mark.parametrize("causal", [False, True])
def test_ulysses_attention_gradients_match(mesh, qkv, causal):
    """Head-parallel attention through ``maybe_flash_attention`` (the dense
    arm on this mesh) between its two all_to_alls, against the dense op."""
    uly = _sharded(mesh, ulysses_attention, causal)
    _assert_grads_match(
        lambda q, k, v: jnp.sum(attention(q, k, v, causal=causal) ** 2),
        lambda q, k, v: jnp.sum(uly(q, k, v) ** 2), qkv)


def test_ring_attention_gradients_match(mesh, qkv):
    ring = _sharded(mesh, ring_attention, True)
    _assert_grads_match(
        lambda q, k, v: jnp.sum(attention(q, k, v, causal=True) ** 2),
        lambda q, k, v: jnp.sum(ring(q, k, v) ** 2), qkv)
