"""The window arm of the three flash kernels (interpret mode on the CPU):
forward and both backward sweeps against the dense masked op, the band's
grid against a brute-force count, and ``window >= S`` against the causal
kernel it has to BE."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from poseidon_tpu.ops import pallas_kernels as pk
from poseidon_tpu.ops.attention import attention

S, D, BLOCK = 64, 16, 16


def _qkv(seed=0, s=S, heads=2):
    keys = jax.random.split(jax.random.PRNGKey(seed), 4)
    return [jax.random.normal(k, (1, heads, s, D), jnp.float32)
            for k in keys]


def _dense_masked(q, k, v, window):
    """Written out here, apart from ops/attention: softmax over the band."""
    s = q.shape[-2]
    t, u = np.arange(s)[:, None], np.arange(s)[None, :]
    mask = (u <= t) & (t - u < window)
    scores = jnp.einsum("bhqd,bhkd->bhqk", q, k) / np.sqrt(q.shape[-1])
    probs = jax.nn.softmax(jnp.where(mask, scores, -jnp.inf), -1)
    return jnp.einsum("bhqk,bhkd->bhqd", probs, v)


# below, equal to, above and not a multiple of the tile; one key; S - 1
WINDOWS = [1, 5, BLOCK - 1, BLOCK, BLOCK + 1, 2 * BLOCK, 2 * BLOCK + 7,
           S - 1]


@pytest.mark.parametrize("window", WINDOWS)
def test_window_kernels_match_dense_masked(window):
    q, k, v, g = _qkv()

    def flash(q, k, v):
        return pk.flash_attention(q, k, v, True, None, BLOCK, BLOCK, True,
                                  window)

    out, vjp = jax.vjp(flash, q, k, v)
    want, want_vjp = jax.vjp(lambda *a: _dense_masked(*a, window), q, k, v)
    np.testing.assert_allclose(out, want, rtol=2e-5, atol=2e-5)
    for got, ref in zip(vjp(g), want_vjp(g)):
        np.testing.assert_allclose(got, ref, rtol=2e-4, atol=2e-5)


@pytest.mark.parametrize("blocks", [(8, 32), (32, 8), (16, 64), (64, 16)])
def test_window_kernels_with_unequal_tiles(blocks):
    q, k, v, g = _qkv(1)
    window = 20

    def flash(q, k, v):
        return pk.flash_attention(q, k, v, True, None, *blocks, True, window)

    out, vjp = jax.vjp(flash, q, k, v)
    want, want_vjp = jax.vjp(lambda *a: _dense_masked(*a, window), q, k, v)
    np.testing.assert_allclose(out, want, rtol=2e-5, atol=2e-5)
    for got, ref in zip(vjp(g), want_vjp(g)):
        np.testing.assert_allclose(got, ref, rtol=2e-4, atol=2e-5)


@pytest.mark.parametrize("window", [S, S + 1, 4 * S, 0, None])
def test_window_at_or_past_the_sequence_is_the_causal_kernel(window):
    q, k, v, g = _qkv(2)

    def run(w):
        f = lambda q, k, v: pk.flash_attention(     # noqa: E731
            q, k, v, True, None, BLOCK, BLOCK, True, w)
        out, vjp = jax.vjp(f, q, k, v)
        return (out,) + vjp(g), str(jax.make_jaxpr(f)(q, k, v))

    (got, got_text), (want, want_text) = run(window), run(None)
    assert got_text == want_text            # the same program, not a twin
    for a, b in zip(got, want):
        assert np.array_equal(np.asarray(a), np.asarray(b))


def test_dense_arm_takes_the_same_window():
    q, k, v, _ = _qkv(3)
    for window in (1, 7, BLOCK, S - 1):
        np.testing.assert_allclose(
            attention(q, k, v, causal=True, window=window),
            _dense_masked(q, k, v, window), rtol=2e-5, atol=2e-5)
    assert np.array_equal(
        np.asarray(attention(q, k, v, causal=True, window=S)),
        np.asarray(attention(q, k, v, causal=True)))


def _brute(s, bq, bk, window):
    """Blocks with at least one (t, u), u <= t < u + window: every key
    block between a row's first and last visible key."""
    return {(t // bq, kb) for t in range(s)
            for kb in range(max(0, t - window + 1) // bk, t // bk + 1)}


@pytest.mark.parametrize("s,bq,bk,window", [
    (64, 16, 16, 16), (64, 16, 16, 17), (64, 16, 16, 1), (64, 8, 32, 20),
    (64, 32, 8, 20), (128, 16, 16, 40), (128, 64, 16, 33), (64, 16, 16, 63),
    (8192, 1024, 1024, 2048), (8192, 1024, 512, 2048), (8192, 512, 1024, 2048),
])
def test_flash_grid_programs_against_brute_force(s, bq, bk, window):
    live = _brute(s, bq, bk, window)
    for over_q in (False, True):
        got_live, visited = pk.flash_grid_programs(s, bq, bk, True, window,
                                                   over_q=over_q)
        assert got_live == len(live)
        axis = 1 if over_q else 0
        outer = s // (bk if over_q else bq)
        most = max(sum(1 for b in live if b[axis] == i)
                   for i in range(outer))
        assert visited == outer * most
        assert visited >= got_live


def test_the_issue_s_count_at_the_cell_s_shape():
    # S 8192, W 2048, 1024 x 1024 tiles: 21 live of the 36 the causal
    # kernel has, 3 steps a Q block
    assert pk.flash_grid_programs(8192, 1024, 1024, True, 2048) == (21, 24)
    assert pk.flash_grid_programs(8192, 1024, 1024, True) == (36, 64)
    # a window of the whole sequence is no window: the causal kernel
    assert pk._band_window(8192, True, 8192) is None


def test_attention_route_names_the_band(monkeypatch):
    monkeypatch.setenv("POSEIDON_FORCE_PALLAS", "1")
    arm, note = pk.attention_route(8192, 8192, 128, 2, True, 2048)
    assert arm == "pallas_flash" and "window 2048" in note
    causal = pk.attention_route(8192, 8192, 128, 2, True)[1]
    assert "window" not in causal
    assert pk.attention_route(8192, 8192, 128, 2, True, 8192)[1] == causal
