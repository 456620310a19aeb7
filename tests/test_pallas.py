"""Pallas kernels (interpret mode on CPU) vs reference ops."""

import functools
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from poseidon_tpu.ops.attention import attention
from poseidon_tpu.ops.nn import lrn_across_channels
from poseidon_tpu.ops.pallas_kernels import flash_attention, lrn_fused

B, H, S, D = 2, 3, 128, 32


@pytest.fixture(scope="module")
def qkv():
    rs = np.random.RandomState(0)
    mk = lambda: jnp.asarray(rs.randn(B, H, S, D).astype(np.float32) * 0.3)
    return mk(), mk(), mk()


def _dense_f32(q, k, v, causal):
    f32 = lambda t: t.astype(jnp.float32)
    return attention(f32(q), f32(k), f32(v), causal=causal)


def _rel_l2(got, want):
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    return float(np.linalg.norm(got - want) / np.linalg.norm(want))


# (block_q, block_k) of the three kernels: equal, rectangular either way
# (S=128 in blocks of 32 and 64 has dead blocks on both sides of the
# diagonal and live ones that need no mask), a full-sequence tile, and
# None = what the tile rule picks for this geometry
BLOCKS = [(32, 32), (32, 64), (64, 32), (128, 128), (None, None)]


@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("blocks", BLOCKS)
def test_flash_attention_matches_reference(qkv, causal, blocks):
    q, k, v = qkv
    want = attention(q, k, v, causal=causal)
    got = flash_attention(q, k, v, causal, None, *blocks)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-4, atol=2e-5)


@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("blocks", BLOCKS)
def test_flash_attention_gradients(qkv, causal, blocks):
    """The Pallas dq/dk/dv kernels (O(S) memory, recompute-from-lse) against
    the dense reference VJP, across block shapes incl. full-sequence tiles."""
    q, k, v = qkv

    def loss_ref(q, k, v):
        return jnp.sum(attention(q, k, v, causal=causal) ** 2)

    def loss_flash(q, k, v):
        return jnp.sum(flash_attention(q, k, v, causal, None, *blocks) ** 2)

    gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    for a, b, name in zip(gr, gf, "qkv"):
        np.testing.assert_allclose(np.asarray(b), np.asarray(a),
                                   rtol=5e-3, atol=5e-4, err_msg=name)


@pytest.mark.parametrize("blocks", [(32, 64), (64, 32), (None, None)])
def test_flash_attention_bf16_operands_against_f32_dense(qkv, blocks):
    """Under the bf16 policy the tiles reach the MXU as bf16 (p and dS cast
    for their products too) while the softmax statistics and every
    accumulator stay f32: forward and all three gradients within 1% relative
    L2 of the dense op computed in f32 from the same bf16 inputs (bf16
    carries 8 bits; measured 0.3-0.6%, on the v5e 0.5-0.6% at S=1024)."""
    from poseidon_tpu.config import policy_scope
    q, k, v = (t.astype(jnp.bfloat16) for t in qkv)
    g = (q + v) * 0.5                                # some cotangent, bf16
    want, vjp = jax.vjp(lambda *a: _dense_f32(*a, True), q, k, v)
    want_g = vjp(g.astype(jnp.float32))
    with policy_scope(compute_dtype=jnp.bfloat16):
        got, vjp = jax.vjp(
            lambda *a: flash_attention(*a, True, None, *blocks), q, k, v)
        got_g = vjp(g)
    assert got.dtype == jnp.bfloat16
    assert _rel_l2(got, want) < 0.01
    for a, b, name in zip(got_g, want_g, "qkv"):
        assert a.dtype == jnp.bfloat16
        assert _rel_l2(a, b) < 0.01, name


def test_flash_attention_grad_under_jit_and_vmapless_batch(qkv):
    """Backward works inside jit (the training-path usage)."""
    q, k, v = qkv
    f = jax.jit(jax.grad(
        lambda q_, k_, v_: flash_attention(q_, k_, v_, True, None, 32, 32)
        .sum(), argnums=(0, 1, 2)))
    gq, gk, gv = f(q, k, v)
    for g in (gq, gk, gv):
        assert np.isfinite(np.asarray(g)).all()


def test_pick_block_non_power_of_two_lengths():
    """Non-power-of-two sequence lengths must tile with the largest
    ALIGNED block that divides them (Mosaic needs the second-minor block
    dim to be a multiple of the 8-row f32 sublane tile), not fall back to
    None — s=48 tiles at 16, s=136 at 8; only unaligned lengths refuse."""
    from poseidon_tpu.ops.pallas_kernels import pick_block
    assert pick_block(4096) == 1024
    assert pick_block(1024) == 1024
    assert pick_block(384) == 128     # 3 * 128
    assert pick_block(96) == 32
    assert pick_block(48) == 16       # used to fall back to None
    assert pick_block(136) == 8       # 17 * 8
    assert pick_block(24) == 8
    assert pick_block(100) is None    # 4 mod 8: no aligned block exists
    assert pick_block(7) is None


@pytest.mark.parametrize("s", [48, 136])
def test_flash_runs_at_the_small_block_rungs(s):
    """The rule's own tiles for the odd lengths (16 x 16, 8 x 8), forward
    and gradients."""
    rs = np.random.RandomState(3)
    q = jnp.asarray(rs.randn(1, 2, s, 16).astype(np.float32))
    loss = lambda f: lambda q_: jnp.sum(f(q_, q_, q_) ** 2)
    flash = lambda a, b, c: flash_attention(a, b, c, True)
    dense = lambda a, b, c: attention(a, b, c, causal=True)
    np.testing.assert_allclose(np.asarray(flash(q, q, q)),
                               np.asarray(dense(q, q, q)),
                               rtol=2e-4, atol=2e-5)
    np.testing.assert_allclose(np.asarray(jax.grad(loss(flash))(q)),
                               np.asarray(jax.grad(loss(dense))(q)),
                               rtol=5e-3, atol=5e-4)


# (kernel, S, D, itemsize) -> (block_q, block_k): the cell's geometry first
@pytest.mark.parametrize("kernel", ["fwd", "dq", "dkv", "bwd"])
@pytest.mark.parametrize("s, d, itemsize, want", [
    (4096, 128, 2, (1024, 1024)),      # olmoe.l1.pack4k: bf16[32,4096,128]
    (4096, 128, 4, (1024, 1024)),
    (2048, 64, 2, (1024, 1024)),
    (1536, 128, 2, (512, 512)),        # 3 * 512
    (384, 64, 2, (128, 128)),
    (128, 32, 4, (128, 128)),
    (48, 16, 4, (16, 16)),
    (136, 16, 4, (8, 8)),
    (100, 16, 4, None),
])
def test_flash_blocks_rule(kernel, s, d, itemsize, want):
    """The tile rule is a function of (S, D, itemsize) alone, and what it
    picks fits its own VMEM budget."""
    from poseidon_tpu.ops import pallas_kernels as PK
    assert PK.flash_blocks(kernel, s, d, itemsize) == want
    if want is not None:
        assert PK._fits_vmem(kernel, *want, d, itemsize, None, s)
        assert PK._FLASH_VMEM_BUDGET < PK._FLASH_VMEM_LIMIT \
            and PK._SWEEP_VMEM_BUDGET < PK._SWEEP_VMEM_LIMIT


def test_flash_blocks_shrink_when_the_budget_binds():
    """f32 operands 256 wide leave the two backward sweeps (four
    score-shaped temporaries) no room for 1024 x 1024; the forward (two)
    keeps it. Of two halvings the one that keeps the K/V block wide wins,
    until the dK/dV sweep's four K-side tiles and two accumulators weigh
    more. The single sweep counts a fifth temporary and the head's dQ rows
    against twice the budget: f32 operands 256 wide keep 1024 x 1024 at S
    4,096 and not at 8,192."""
    from poseidon_tpu.ops.pallas_kernels import flash_blocks
    assert flash_blocks("fwd", 4096, 256, 4) == (1024, 1024)
    assert flash_blocks("dq", 4096, 256, 4) == (512, 1024)
    assert flash_blocks("dkv", 4096, 256, 4) == (512, 1024)
    assert flash_blocks("dkv", 4096, 384, 4) == (1024, 512)
    assert flash_blocks("bwd", 4096, 256, 4) == (1024, 1024)
    assert flash_blocks("bwd", 8192, 256, 4) == (512, 1024)
    assert flash_blocks("bwd", 16384, 256, 2) == (1024, 512)


@pytest.mark.parametrize("s, bq, bk, causal, want", [
    (4096, 1024, 1024, True, (10, 16)),
    (4096, 512, 512, True, (36, 64)),
    (4096, 128, 128, True, (528, 1024)),
    (128, 32, 64, True, (6, 8)),
    (128, 64, 32, True, (6, 8)),
    (128, 32, 64, False, (8, 8)),
])
def test_flash_grid_programs(s, bq, bk, causal, want):
    """(live, visited) programs per head, and the clamped index maps: a
    dead block names the last live K/V tile (first live Q tile) again."""
    from poseidon_tpu.ops import pallas_kernels as PK
    assert PK.flash_grid_programs(s, bq, bk, causal) == want
    if causal:
        live = [(qi, kj) for qi in range(s // bq) for kj in range(s // bk)
                if kj * bk <= qi * bq + bq - 1]
        assert len(live) == want[0]
        for qi in range(s // bq):
            assert PK._last_live_k(qi, bq, bk) == max(
                kj for q_, kj in live if q_ == qi)
        for kj in range(s // bk):
            assert PK._first_live_q(kj, bq, bk) == min(
                qi for qi, k_ in live if k_ == kj)


def test_attention_route_states_tiles_and_programs(monkeypatch):
    """Lowering for the TPU, the note names each kernel's tiles and the
    live / visited programs of its grid; on the CPU mesh the route is the
    dense op."""
    from poseidon_tpu.ops.pallas_kernels import attention_route
    monkeypatch.setattr(jax, "default_backend", lambda: "cpu")
    assert attention_route(4096, 4096, 128, 2) == ("dense", "cpu backend")
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    arm, note = attention_route(4096, 4096, 128, 2)
    assert arm == "pallas_flash"
    assert note == ("fwd 1024x1024 10/16, bwd 1024x1024 10/16; "
                    "block_q x block_k, live/visited programs a head; "
                    "operands token-major (B,S,HxD)")
    assert ": " not in note          # it is a stats.yaml leaf
    assert attention_route(4096, 2048, 128, 2)[0] == "dense"
    assert attention_route(100, 100, 16, 4) == (
        "dense", "no aligned block divides S=100")


def test_maybe_flash_routing(qkv):
    """Off-TPU, routing must use the dense op (interpret-mode Pallas would
    be an emulation slowdown) — bit-identical to attention(). On a real TPU
    (POSEIDON_TEST_TPU=1 runs), routing takes the Mosaic-compiled flash
    kernel instead — numerically close, not bitwise."""
    from poseidon_tpu.ops.pallas_kernels import maybe_flash_attention
    q, k, v = qkv
    got = maybe_flash_attention(q, k, v, causal=True)
    want = attention(q, k, v, causal=True)
    if jax.default_backend() == "tpu":
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-4, atol=2e-5)
    else:
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_lrn_fused_matches_reference():
    rs = np.random.RandomState(1)
    x = jnp.asarray(rs.randn(2, 16, 8, 8).astype(np.float32))
    want = lrn_across_channels(x, 5, 1e-4, 0.75)
    got = lrn_fused(x, 5, 1e-4, 0.75)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-6)


def test_lrn_fused_gradient_matches_reference():
    """The recompute VJP: grad through the Pallas forward must equal grad
    through the XLA formulation (it literally recomputes through it)."""
    rs = np.random.RandomState(2)
    x = jnp.asarray(rs.randn(2, 16, 6, 6).astype(np.float32))
    g_ref = jax.grad(
        lambda x_: jnp.sum(lrn_across_channels(x_, 5, 1e-4, 0.75) ** 2))(x)
    g_fused = jax.grad(
        lambda x_: jnp.sum(lrn_fused(x_, 5, 1e-4, 0.75) ** 2))(x)
    np.testing.assert_allclose(np.asarray(g_fused), np.asarray(g_ref),
                               rtol=1e-5, atol=1e-6)


def test_lrn_fused_bwd_kernel_matches_analytic():
    """The one-pass Pallas backward (interpret mode here, Mosaic on chip)
    must reproduce the autodiff gradient of the XLA formulation — the
    analytic Caffe gradient with the mirrored transpose window
    (lrn_layer.cpp CrossChannelBackward)."""
    from poseidon_tpu.ops.pallas_kernels import lrn_fused_bwd
    rs = np.random.RandomState(4)
    x = jnp.asarray(rs.randn(2, 16, 6, 6).astype(np.float32))
    g = jnp.asarray(rs.randn(2, 16, 6, 6).astype(np.float32))
    _, vjp = jax.vjp(
        lambda x_: lrn_across_channels(x_, 5, 1e-4, 0.75), x)
    (want,) = vjp(g)
    got = lrn_fused_bwd(x, g, 5, 1e-4, 0.75, interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-6)


def test_lrn_fused_bwd_kernel_even_window():
    """Asymmetric (even) local_size exercises the mirrored pre/post pads."""
    from poseidon_tpu.ops.pallas_kernels import lrn_fused_bwd
    rs = np.random.RandomState(5)
    x = jnp.asarray(rs.randn(1, 12, 4, 4).astype(np.float32))
    g = jnp.asarray(rs.randn(1, 12, 4, 4).astype(np.float32))
    _, vjp = jax.vjp(
        lambda x_: lrn_across_channels(x_, 4, 2e-4, 0.9, 1.5), x)
    (want,) = vjp(g)
    got = lrn_fused_bwd(x, g, 4, 2e-4, 0.9, 1.5, interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-6)


def test_maybe_lrn_fused_routing():
    """Whichever arm ``lrn_route`` picks for this backend (the XLA
    formulation on the CPU mesh, the compiled Pallas kernels on the chip)
    computes the same LRN: bitwise on CPU, where both are the XLA op;
    1.9e-6 relative on the v5e (PR 21 chip run)."""
    from poseidon_tpu.ops.pallas_kernels import maybe_lrn_fused
    rs = np.random.RandomState(3)
    x = jnp.asarray(rs.randn(1, 8, 5, 5).astype(np.float32))
    want = lrn_across_channels(x, 5, 1e-4, 0.75)
    got = maybe_lrn_fused(x, 5, 1e-4, 0.75)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-6)


def test_lrn_fused_nhwc_entry_matches_nchw():
    """The NHWC kernel entry (net-level channels-last plan): channels on
    the MINOR axis inside the block, no layout round-trip at the
    custom-call boundary — same numbers as the NCHW kernel."""
    rs = np.random.RandomState(6)
    x = rs.randn(2, 16, 8, 8).astype(np.float32)
    xt = jnp.asarray(np.transpose(x, (0, 2, 3, 1)).copy())
    want = np.asarray(lrn_fused(jnp.asarray(x), 5, 1e-4, 0.75))
    got = np.asarray(lrn_fused(xt, 5, 1e-4, 0.75, layout="NHWC"))
    np.testing.assert_allclose(np.transpose(got, (0, 3, 1, 2)), want,
                               rtol=1e-5, atol=1e-6)


def test_lrn_fused_bwd_nhwc_matches_analytic():
    from poseidon_tpu.ops.pallas_kernels import lrn_fused_bwd
    rs = np.random.RandomState(7)
    x = rs.randn(2, 16, 6, 6).astype(np.float32)
    g = rs.randn(2, 16, 6, 6).astype(np.float32)
    _, vjp = jax.vjp(
        lambda x_: lrn_across_channels(x_, 5, 1e-4, 0.75), jnp.asarray(x))
    (want,) = vjp(jnp.asarray(g))
    got = lrn_fused_bwd(jnp.asarray(np.transpose(x, (0, 2, 3, 1)).copy()),
                        jnp.asarray(np.transpose(g, (0, 2, 3, 1)).copy()),
                        5, 1e-4, 0.75, interpret=True, layout="NHWC")
    np.testing.assert_allclose(
        np.transpose(np.asarray(got), (0, 3, 1, 2)), np.asarray(want),
        rtol=1e-5, atol=1e-6)


def test_lrn_tile_rejects_vmem_busting_channel_counts():
    """Advisor finding (round 6): at channels > ~2560 the VMEM budget caps
    the spatial tile below 128 lanes; _lrn_tile must refuse (clear error)
    instead of emitting a block that exceeds the scoped-VMEM limit at
    Mosaic compile time."""
    import pytest as _pytest
    from poseidon_tpu.ops.pallas_kernels import (LRNTileError, _lrn_tile,
                                                 lrn_tile_feasible)
    # comfortably feasible: the AlexNet/GoogLeNet norms
    assert lrn_tile_feasible(55 * 55, 96)
    assert lrn_tile_feasible(56 * 56, 192)
    # the cap boundary: budget/(4*8*128) = 2560 channels
    assert lrn_tile_feasible(128 * 128, 2560)
    assert not lrn_tile_feasible(128 * 128, 2561)
    assert not lrn_tile_feasible(128 * 128, 4096)
    with _pytest.raises(LRNTileError, match="XLA formulation"):
        _lrn_tile(128 * 128, 4096, 128, 4)


def test_lrn_fused_falls_back_to_xla_above_tile_cap():
    """lrn_fused at 4096 channels (no legal tile) must silently take the
    XLA formulation — same numbers, forward and gradient, no Mosaic
    blowup."""
    rs = np.random.RandomState(8)
    # hw must exceed the budget's full-extent fit (hw > ~80 at 4096ch) so
    # the tiler is actually consulted — and then refuses (cap 80 < 128)
    x = jnp.asarray(rs.randn(1, 4096, 12, 12).astype(np.float32))
    want = lrn_across_channels(x, 5, 1e-4, 0.75)
    got = lrn_fused(x, 5, 1e-4, 0.75)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    g_want = jax.grad(
        lambda x_: jnp.sum(lrn_across_channels(x_, 5, 1e-4, 0.75) ** 2))(x)
    g_got = jax.grad(
        lambda x_: jnp.sum(lrn_fused(x_, 5, 1e-4, 0.75) ** 2))(x)
    np.testing.assert_allclose(np.asarray(g_got), np.asarray(g_want),
                               rtol=1e-5, atol=1e-6)


# --------------------------------------------------------------------------- #
# token-major operands: (B, S, H·D) as the projections leave them, a head a
# lane block in the kernels' index maps, against head-major (B, H, S, D)
# --------------------------------------------------------------------------- #

def _token_major(t):
    """(B, H, S, D) -> (B, S, H·D)."""
    b, h, s, d = t.shape
    return t.swapaxes(1, 2).reshape(b, s, h * d)


# name: (S, heads, kv heads, Dh, Dv, causal, window, blocks, dtype)
TOKEN_MAJOR_CASES = {
    "causal": (128, 3, 3, 32, 32, True, None, (32, 64), jnp.float32),
    "full": (128, 3, 3, 32, 32, False, None, (64, 32), jnp.float32),
    "rule_blocks": (128, 2, 2, 128, 128, True, None, (None, None),
                    jnp.float32),
    "window": (128, 2, 2, 16, 16, True, 40, (16, 32), jnp.float32),
    "window_unequal": (64, 2, 2, 16, 16, True, 20, (32, 8), jnp.float32),
    # two head widths, both whole vregs of lanes
    "two_widths": (128, 2, 2, 256, 128, True, None, (64, 32), jnp.float32),
    "values_wider": (128, 2, 2, 128, 256, True, 48, (32, 64), jnp.float32),
    # grouped query: k and v repeated to the query heads outside the kernel
    "grouped": (128, 4, 2, 128, 128, True, None, (64, 64), jnp.float32),
    "grouped_window": (128, 8, 2, 32, 32, True, 50, (32, 32), jnp.float32),
    "bf16": (128, 2, 2, 128, 128, True, None, (64, 64), jnp.bfloat16),
    "bf16_window": (128, 4, 1, 128, 128, True, 40, (32, 64), jnp.bfloat16),
    "small_block": (64, 2, 2, 128, 128, True, None, (8, 8), jnp.float32),
    "small_block_window": (64, 3, 3, 16, 16, True, 12, (8, 16),
                           jnp.float32),
}


@pytest.mark.parametrize("what", ["out", "dq", "dk", "dv"])
@pytest.mark.parametrize("case", sorted(TOKEN_MAJOR_CASES))
def test_token_major_operands_match_head_major(case, what):
    """The three kernels (interpret mode) on (B, S, H·D) operands with
    ``heads`` against the same kernels on (B, H, S, D): the output and all
    three gradients, the key-value heads' gradients summed over the query
    heads that read them where they are repeated."""
    from poseidon_tpu.models.transformer import _repeat_lanes
    s, h, g, d, dv, causal, window, blocks, dtype = TOKEN_MAJOR_CASES[case]
    rs = np.random.RandomState(sum(map(ord, case)))
    mk = lambda n, w: jnp.asarray(
        rs.randn(2, n, s, w).astype(np.float32) * 0.3).astype(dtype)
    q, k, v, co = mk(h, d), mk(g, d), mk(g, dv), mk(h, dv)

    def head_major(q, k, v):
        k, v = (jnp.repeat(t, h // g, axis=1) for t in (k, v))
        out = flash_attention(q, k, v, causal, None, *blocks, True, window)
        return _token_major(out)

    def token_major(q, k, v):
        q, k, v = (_token_major(t) for t in (q, k, v))
        if g != h:
            k, v = (_repeat_lanes(t, g, h // g) for t in (k, v))
        return flash_attention(q, k, v, causal, None, *blocks, True, window,
                               h)

    if what == "out":
        got, want = token_major(q, k, v), head_major(q, k, v)
        assert got.shape == (2, s, h * dv) and got.dtype == dtype
    else:
        i = ("dq", "dk", "dv").index(what)
        loss = lambda f: lambda *a: jnp.sum(
            (f(*a) * _token_major(co)).astype(jnp.float32))
        got, want = (jax.grad(loss(f), argnums=i)(q, k, v)
                     for f in (token_major, head_major))
        assert got.shape == (q, k, v)[i].shape and got.dtype == dtype
    tol = 2e-2 if dtype == jnp.bfloat16 else 2e-6
    assert _rel_l2(got, want) < tol


def test_token_major_operands_against_the_dense_op(qkv):
    """And against the dense op directly, forward and backward."""
    q, k, v = qkv
    dense = lambda *a: _token_major(attention(*a, causal=True))
    flash = lambda *a: flash_attention(
        *(_token_major(t) for t in a), True, None, 32, 64, True, None, H)
    np.testing.assert_allclose(flash(q, k, v), dense(q, k, v), rtol=2e-4,
                               atol=2e-5)
    gr, gf = (jax.grad(lambda *a, f=f: jnp.sum(f(*a) ** 2),
                       argnums=(0, 1, 2))(q, k, v) for f in (dense, flash))
    for a, b in zip(gr, gf):
        np.testing.assert_allclose(b, a, rtol=5e-3, atol=5e-4)


@pytest.mark.parametrize("s,d,dv,want", [
    (8192, 128, None, (True, "operands token-major (B,S,HxD)")),
    (4096, 128, 128, (True, "operands token-major (B,S,HxD)")),
    (16384, 256, 128, (True, "operands token-major (B,S,HxD)")),
    (8192, 192, 128,
     (False, "operands head-major (Dh 192, not lane-aligned)")),
    (8192, 128, 64, (False, "operands head-major (Dh 64, not lane-aligned)")),
    (256, 64, None, (False, "operands head-major (Dh 64, not lane-aligned)")),
    (100, 128, None, (False, "operands head-major (S 100 does not tile)")),
])
def test_operand_form_rule(s, d, dv, want):
    """Token-major where a head is whole vregs of lanes and the sequence
    tiles, head-major elsewhere: by the shape alone."""
    from poseidon_tpu.ops.pallas_kernels import flash_operand_form
    assert flash_operand_form(s, d, dv) == want


def test_attention_route_names_the_operand_form(monkeypatch):
    from poseidon_tpu.ops.pallas_kernels import attention_route
    monkeypatch.setenv("POSEIDON_FORCE_PALLAS", "1")
    note = lambda *a, **kw: attention_route(*a, **kw)[1]
    assert note(8192, 8192, 128, 2).endswith(
        "; operands token-major (B,S,HxD)")
    assert note(8192, 8192, 192, 2, dv=128).endswith(
        "; flash d 192/128; operands head-major (Dh 192, not lane-aligned)")
    assert note(2048, 2048, 64, 2).endswith(
        "; operands head-major (Dh 64, not lane-aligned)")
    # a caller that hands the operands over head-major says so
    assert note(8192, 8192, 128, 2, token_major=False).endswith(
        "; operands head-major (the caller's)")
    assert ": " not in note(8192, 8192, 192, 2, dv=128)  # a stats.yaml leaf


def test_maybe_flash_routing_with_token_major_operands(qkv):
    """Off-TPU a token-major caller gets the dense op on its heads split
    out: bit-identical to attention() merged back."""
    from poseidon_tpu.ops.pallas_kernels import maybe_flash_attention
    q, k, v = qkv
    got = maybe_flash_attention(*(_token_major(t) for t in (q, k, v)),
                                causal=True, heads=H)
    np.testing.assert_array_equal(
        got, _token_major(attention(q, k, v, causal=True)))


# --------------------------------------------------------------------------- #
# the backward's single sweep (dQ summed inside the dK/dV kernel) against the
# dense op, and against the two sweeps it replaces wherever a head's dQ rows
# stay resident (every shape here: a test forces the two through the rule)
# --------------------------------------------------------------------------- #

# name: (S, heads, Dh, Dv, causal, window, blocks, token-major, dtype)
SWEEP_CASES = {
    "equal_tiles": (128, 3, 32, 32, True, None, (32, 32), False, jnp.float32),
    "wide_k": (128, 3, 32, 32, True, None, (32, 64), False, jnp.float32),
    "wide_q": (128, 3, 32, 32, True, None, (64, 32), False, jnp.float32),
    "one_tile": (128, 3, 32, 32, True, None, (128, 128), False, jnp.float32),
    "rule_tiles": (128, 3, 32, 32, True, None, (None, None), False,
                   jnp.float32),
    "not_causal": (128, 3, 32, 32, False, None, (32, 64), False,
                   jnp.float32),
    "not_causal_wide_q": (128, 3, 32, 32, False, None, (64, 32), False,
                          jnp.float32),
    "s48": (48, 2, 16, 16, True, None, (None, None), False, jnp.float32),
    "s136": (136, 2, 16, 16, True, None, (None, None), False, jnp.float32),
    "window": (128, 2, 16, 16, True, 40, (16, 32), False, jnp.float32),
    "window_unequal": (64, 2, 16, 16, True, 20, (32, 8), False, jnp.float32),
    # the cells' windows, on tiles of 512 and 1024 (one head of 8)
    "window_1024": (2048, 1, 8, 8, True, 1024, (512, 512), False,
                    jnp.float32),
    "window_2048": (4096, 1, 8, 8, True, 2048, (1024, 1024), False,
                    jnp.bfloat16),
    # the cells' head widths, in the form flash_operand_form sends each
    "d128": (128, 2, 128, 128, True, None, (64, 32), True, jnp.bfloat16),
    "d128_window": (128, 2, 128, 128, True, 48, (32, 64), True,
                    jnp.bfloat16),
    "d192_128": (128, 2, 192, 128, True, None, (32, 64), False,
                 jnp.bfloat16),
    "d256": (128, 2, 256, 256, True, None, (64, 64), True, jnp.bfloat16),
    "d64": (128, 2, 64, 64, True, None, (64, 32), False, jnp.bfloat16),
    "d128_f32": (64, 2, 128, 128, True, None, (8, 8), True, jnp.float32),
}


def _ulp(x, dtype):
    """The unit in the last place of ``dtype`` at magnitude ``x``."""
    bits = jnp.finfo(dtype).nmant
    return 2.0 ** (np.floor(np.log2(x)) - bits)


@pytest.fixture(scope="module")
def sweeps():
    """dq, dk, dv of every case: the single sweep's, the two sweeps' (the
    residency rule patched to refuse) and the dense op's in f32."""
    from poseidon_tpu.ops import pallas_kernels as PK

    def grads(case):
        s, h, d, dv, causal, window, blocks, lanes, dtype = SWEEP_CASES[case]
        rs = np.random.RandomState(sum(map(ord, case)))
        mk = lambda w: jnp.asarray(
            rs.randn(2, h, s, w).astype(np.float32) * 0.3).astype(dtype)
        q, k, v, co = mk(d), mk(d), mk(dv), mk(dv)
        form = _token_major if lanes else (lambda t: t)

        def flash(q, k, v):
            out = flash_attention(form(q), form(k), form(v), causal, None,
                                  *blocks, True, window, h if lanes else None)
            return jnp.sum((out * form(co)).astype(jnp.float32))

        def dense(q, k, v):
            out = attention(*(t.astype(jnp.float32) for t in (q, k, v)),
                            causal=causal, window=window)
            return jnp.sum(out * co.astype(jnp.float32))

        single = jax.grad(flash, argnums=(0, 1, 2))(q, k, v)
        with pytest.MonkeyPatch.context() as patch:
            patch.setattr(PK, "_single_sweep_blocks", lambda *a: None)
            two = jax.grad(flash, argnums=(0, 1, 2))(q, k, v)
        return single, two, jax.grad(dense, argnums=(0, 1, 2))(q, k, v)

    return functools.lru_cache(maxsize=None)(grads)


@pytest.mark.parametrize("what", ["dq", "dk", "dv"])
@pytest.mark.parametrize("sweep", ["single", "two"])
@pytest.mark.parametrize("case", sorted(SWEEP_CASES))
def test_backward_sweeps_against_the_dense_op(sweeps, case, sweep, what):
    """Either backward (interpret mode) against the dense f32 op's VJP at
    the file's tolerances: f32 operands as
    ``test_flash_attention_gradients``, bf16 operands within 1% relative
    L2 as ``test_flash_attention_bf16_operands_against_f32_dense``."""
    i = ("dq", "dk", "dv").index(what)
    single, two, want = sweeps(case)
    got = (single if sweep == "single" else two)[i]
    dtype = SWEEP_CASES[case][-1]
    assert got.dtype == dtype and got.shape == want[i].shape
    if dtype == jnp.bfloat16:
        assert _rel_l2(got, want[i]) < 0.01
    else:
        np.testing.assert_allclose(np.asarray(got), np.asarray(want[i]),
                                   rtol=5e-3, atol=5e-4)


@pytest.mark.parametrize("case", sorted(SWEEP_CASES))
def test_single_sweep_is_the_two_sweeps(sweeps, case):
    """dK and dV are the dK/dV sweep's own, bit for bit. dQ's shares are
    summed in the dQ sweep's order, one product's operands the other way
    round: equal to f32 rounding of the sums, which a bf16 result shows as
    at most one unit in its last place (counted at a thousandth of the
    largest entry or above: below that a sum's own rounding is the
    entry)."""
    single, two, _ = sweeps(case)
    dtype = SWEEP_CASES[case][-1]
    for a, b in zip(single[1:], two[1:]):
        np.testing.assert_array_equal(np.asarray(a, np.float32),
                                      np.asarray(b, np.float32))
    a, b = (np.asarray(t, np.float32) for t in (single[0], two[0]))
    if dtype == jnp.bfloat16:
        at = np.maximum(np.maximum(np.abs(a), np.abs(b)),
                        1e-3 * np.abs(b).max())
        assert np.all(np.abs(a - b) <= _ulp(at, dtype))
    else:
        np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-6)


# a head's dQ rows beside the tiles, in MiB as ``_flash_vmem_bytes`` counts
# them, at the ten token cells' ATTENTION geometries (S, Dh, Dv; bf16)
CELL_GEOMETRIES = {
    "olmoe": (4096, 128, 128, 28), "ouro": (8192, 128, 128, 32),
    "zaya1": (8192, 128, 128, 32), "trinity": (8192, 128, 128, 32),
    "smallthinker": (16384, 128, 128, 40), "olmo_hybrid": (8192, 128, 128, 32),
    "glm_flash": (8192, 256, 256, 44), "kimi_linear": (8192, 192, 128, 37),
    "xing4": (8192, 192, 128, 37), "granite_h": (8192, 64, 64, 26),
}


@pytest.mark.parametrize("cell", sorted(CELL_GEOMETRIES))
def test_dq_rows_stay_resident_at_the_cells_geometries(cell):
    """The residency rule is the tile rule's own count: at every cell's
    geometry the single sweep keeps the dK/dV sweep's 1024 x 1024 tiles
    with the head's dQ rows beside them, inside the budget."""
    from poseidon_tpu.ops import pallas_kernels as PK
    s, d, dv, mib = CELL_GEOMETRIES[cell]
    assert PK.flash_blocks("bwd", s, d, 2, dv) == (1024, 1024) \
        == PK.flash_blocks("dkv", s, d, 2, dv)
    assert PK._single_sweep_blocks(s, d, 2, None, None, dv) == (1024, 1024)
    count = PK._flash_vmem_bytes("bwd", 1024, 1024, d, 2, dv, s)
    assert count == mib * 2 ** 20 <= PK._SWEEP_VMEM_BUDGET \
        < PK._SWEEP_VMEM_LIMIT
    rows = s * d * (4 + 2 * 2)              # f32 sums + the result, twice
    assert count - PK._flash_vmem_bytes("dkv", 1024, 1024, d, 2, dv) \
        == rows + 1024 * 1024 * 4           # and the fifth temporary


@pytest.mark.parametrize("s, d, itemsize, blocks, want", [
    (32768, 128, 2, (None, None), (512, 1024)),  # rows 32 MiB: smaller tiles
    (65536, 128, 2, (None, None), None),         # rows 64 MiB: the two sweeps
    (32768, 256, 2, (None, None), None),
    (16384, 128, 4, (None, None), (512, 1024)),   # f32: rows 24 MiB
    (65536, 128, 2, (128, 128), None),           # a caller's tiles likewise
    (8192, 128, 2, (128, 256), (128, 256)),
])
def test_dq_rows_that_do_not_fit_leave_the_two_sweeps(s, d, itemsize, blocks,
                                                      want):
    from poseidon_tpu.ops import pallas_kernels as PK
    assert PK._single_sweep_blocks(s, d, itemsize, *blocks) == want
    if blocks == (None, None):
        assert PK.flash_blocks("bwd", s, d, itemsize) == want
        # the two sweeps' own tiles do not depend on the rows
        assert PK.flash_blocks("dkv", s, d, itemsize) == (1024, 1024)


def test_backward_launches_one_kernel_or_two(monkeypatch):
    """What is traced: ``flash_bwd`` alone where the rows stay resident,
    ``flash_bwd_dq`` and ``flash_bwd_dkv`` where the rule refuses, and in
    ring attention's chunk mode (a traced alignment) the same choice."""
    from poseidon_tpu.ops import pallas_kernels as PK
    q = jax.ShapeDtypeStruct((1, 2, 128, 32), jnp.float32)
    row = jax.ShapeDtypeStruct((1, 2, 128), jnp.float32)
    mode = jax.ShapeDtypeStruct((), jnp.int32)

    def names(**kw):
        f = lambda q, lse, mode: PK._flash_bwd(
            q, q, q, q, lse, q, 1.0, True, 32, 64, True,
            **({"mode": mode, "delta": lse} if kw.get("chunk") else {}))
        text = str(jax.make_jaxpr(f)(q, row, mode))
        return sorted(set(re.findall(r"name=(flash_\w+)", text)))

    assert names() == names(chunk=True) == ["flash_bwd"]
    monkeypatch.setattr(PK, "_SWEEP_VMEM_BUDGET", 2 ** 16)
    assert names() == names(chunk=True) == ["flash_bwd_dkv", "flash_bwd_dq"]


def test_attention_route_names_the_backward_that_runs(monkeypatch):
    """``fwd ..., bwd ...`` where the single sweep runs, the three-kernel
    form where a head's dQ rows do not fit; either way the words
    ``benchmark/lm_trace.window_visited_over_live`` reads."""
    import re
    from poseidon_tpu.ops import pallas_kernels as PK
    monkeypatch.setenv("POSEIDON_FORCE_PALLAS", "1")
    arm, note = PK.attention_route(16384, 16384, 128, 2, window=4096)
    assert arm == "pallas_flash" and note == (
        "fwd 1024x1024 70/80, bwd 1024x1024 70/80; block_q x block_k, "
        "live/visited programs a head; window 4096: the band's grid; "
        "operands token-major (B,S,HxD)")
    assert re.findall(r"\b(\d+)/(\d+)\b", note) == [("70", "80")] * 2
    assert PK.attention_route(65536, 65536, 128, 2)[1].startswith(
        "fwd 1024x1024 2080/4096, dq 1024x1024 2080/4096, "
        "dkv 1024x1024 2080/4096; block_q x block_k")
    assert PK.attention_route(8192, 8192, 192, 2, dv=128)[1] == (
        "fwd 1024x1024 36/64, bwd 1024x1024 36/64; block_q x block_k, "
        "live/visited programs a head; flash d 192/128; "
        "operands head-major (Dh 192, not lane-aligned)")
