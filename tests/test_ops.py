"""Golden-value numerics tests: XLA ops vs naive-numpy Caffe semantics."""

import numpy as np
import pytest

import caffe_ref as ref
from poseidon_tpu.ops import elementwise as E
from poseidon_tpu.ops import losses as L
from poseidon_tpu.ops import nn as NN


@pytest.mark.parametrize("k,s,p,h", [
    (2, 2, 0, 8), (3, 2, 0, 7), (3, 2, 1, 8), (5, 3, 2, 13), (3, 1, 1, 6),
])
def test_max_pool_matches_caffe(rng_np, k, s, p, h):
    x = rng_np.randn(2, 3, h, h).astype(np.float32)
    got = np.asarray(NN.max_pool(x, (k, k), (s, s), (p, p)))
    want = ref.max_pool(x, k, s, p)
    assert got.shape == want.shape
    np.testing.assert_allclose(got, want, rtol=1e-6)


@pytest.mark.parametrize("k,s,p,h", [
    (2, 2, 0, 8), (3, 2, 0, 7), (3, 2, 1, 8), (5, 3, 2, 13), (3, 1, 1, 6),
])
def test_ave_pool_matches_caffe(rng_np, k, s, p, h):
    x = rng_np.randn(2, 3, h, h).astype(np.float32)
    got = np.asarray(NN.ave_pool(x, (k, k), (s, s), (p, p)))
    want = ref.ave_pool(x, k, s, p)
    assert got.shape == want.shape
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("group", [1, 2])
def test_conv_matches_caffe(rng_np, group):
    x = rng_np.randn(2, 4, 9, 9).astype(np.float32)
    w = rng_np.randn(6, 4 // group, 3, 3).astype(np.float32)
    b = rng_np.randn(6).astype(np.float32)
    got = np.asarray(NN.conv2d(x, w, b, (2, 2), (1, 1), group))
    want = ref.conv2d(x, w, b, 2, 1, group)
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-5)


@pytest.mark.parametrize("group", [1, 2])
def test_conv_nhwc_layout_matches_nchw(rng_np, group):
    """Native NHWC (TPU-preferred) conv: channels-last activations with
    the SAME canonical OIHW weight, same numbers, forward and backward
    (the net-level layout plan's per-op contract)."""
    import jax
    x = rng_np.randn(2, 4, 9, 9).astype(np.float32)
    xt = np.transpose(x, (0, 2, 3, 1)).copy()
    w = rng_np.randn(6, 4 // group, 3, 3).astype(np.float32)
    b = rng_np.randn(6).astype(np.float32)

    def loss_nchw(args, *, _g=group):
        xx, ww, bb = args
        return NN.conv2d(xx, ww, bb, (2, 2), (1, 1), _g).sum()

    def loss_nhwc(args, *, _g=group):
        xx, ww, bb = args
        return NN.conv2d(xx, ww, bb, (2, 2), (1, 1), _g,
                         layout="NHWC").sum()

    y1 = np.asarray(NN.conv2d(x, w, b, (2, 2), (1, 1), group))
    g1 = jax.grad(loss_nchw)((x, w, b))
    y2 = np.asarray(NN.conv2d(xt, w, b, (2, 2), (1, 1), group,
                              layout="NHWC"))
    g2 = jax.grad(loss_nhwc)((xt, w, b))
    np.testing.assert_allclose(y1, np.transpose(y2, (0, 3, 1, 2)),
                               rtol=1e-5, atol=1e-5)
    gx1, gw1, gb1 = g1
    gx2, gw2, gb2 = g2
    np.testing.assert_allclose(np.asarray(gx1),
                               np.transpose(np.asarray(gx2), (0, 3, 1, 2)),
                               rtol=1e-4, atol=1e-5, err_msg="x")
    # weight/bias grads are CANONICAL in either layout — the whole point
    np.testing.assert_allclose(np.asarray(gw1), np.asarray(gw2),
                               rtol=1e-4, atol=1e-5, err_msg="w")
    np.testing.assert_allclose(np.asarray(gb1), np.asarray(gb2),
                               rtol=1e-4, atol=1e-5, err_msg="b")


def test_lrn_across_channels(rng_np):
    x = rng_np.randn(2, 8, 5, 5).astype(np.float32)
    got = np.asarray(NN.lrn_across_channels(x, 5, 1e-4, 0.75))
    want = ref.lrn_across(x, 5, 1e-4, 0.75)
    np.testing.assert_allclose(got, want, rtol=1e-5)


def test_pool_lrn_nhwc_layout_matches_nchw(rng_np):
    """Native channels-last pooling/LRN/stochastic-pool (the net-level
    NHWC plan runs these with zero boundary transposes — round 3's per-op
    shim left pool/LRN NCHW and every transpose survived, the 1.9x
    anomaly): identical numbers either way, forward and backward."""
    import jax
    x = rng_np.randn(2, 8, 9, 9).astype(np.float32)
    xt = np.transpose(x, (0, 2, 3, 1)).copy()
    xpos = np.abs(x) + 0.1
    xpos_t = np.transpose(xpos, (0, 2, 3, 1)).copy()

    fns = {
        "max": lambda a, lay: NN.max_pool(a, (3, 3), (2, 2), (1, 1), lay),
        "ave": lambda a, lay: NN.ave_pool(a, (3, 3), (2, 2), (1, 1), lay),
        "lrn": lambda a, lay: NN.lrn_across_channels(a, 5, 1e-4, 0.75,
                                                     1.0, lay),
        "lrn_w": lambda a, lay: NN.lrn_within_channel(a, 3, 1e-4, 0.75,
                                                      lay),
        "gap": lambda a, lay: NN.global_ave_pool(a, lay),
    }
    for k, f in fns.items():
        o1 = np.asarray(f(x, "NCHW"))
        o2 = np.asarray(f(xt, "NHWC"))
        np.testing.assert_allclose(o1, np.transpose(o2, (0, 3, 1, 2)),
                                   rtol=1e-5, atol=1e-6, err_msg=k)
    sp1 = np.asarray(NN.stochastic_pool(xpos, (3, 3), (3, 3), (0, 0),
                                        None, True, "NCHW"))
    sp2 = np.asarray(NN.stochastic_pool(xpos_t, (3, 3), (3, 3), (0, 0),
                                        None, True, "NHWC"))
    np.testing.assert_allclose(sp1, np.transpose(sp2, (0, 3, 1, 2)),
                               rtol=1e-5, atol=1e-6, err_msg="stochastic")
    for k in ("max", "lrn"):
        g1 = jax.grad(lambda a, _f=fns[k]: _f(a, "NCHW").sum())(x)
        g2 = jax.grad(lambda a, _f=fns[k]: _f(a, "NHWC").sum())(xt)
        np.testing.assert_allclose(
            np.asarray(g1), np.transpose(np.asarray(g2), (0, 3, 1, 2)),
            rtol=1e-5, atol=1e-6, err_msg=f"grad:{k}")


def test_lrn_within_channel(rng_np):
    x = rng_np.randn(2, 3, 7, 7).astype(np.float32)
    got = np.asarray(NN.lrn_within_channel(x, 3, 5e-5, 0.75))
    want = ref.lrn_within(x, 3, 5e-5, 0.75)
    np.testing.assert_allclose(got, want, rtol=1e-5)


def test_softmax_loss_matches_caffe(rng_np):
    logits = rng_np.randn(4, 10).astype(np.float32)
    labels = rng_np.randint(0, 10, size=(4,))
    got = float(L.softmax_loss(logits, labels))
    want = ref.softmax_loss(logits, labels)
    assert got == pytest.approx(want, rel=1e-5)


def test_softmax_loss_spatial(rng_np):
    logits = rng_np.randn(2, 5, 3, 3).astype(np.float32)
    labels = rng_np.randint(0, 5, size=(2, 3, 3))
    got = float(L.softmax_loss(logits, labels))
    want = ref.softmax_loss(logits, labels)
    assert got == pytest.approx(want, rel=1e-5)


def test_euclidean_loss(rng_np):
    a = rng_np.randn(4, 3).astype(np.float32)
    b = rng_np.randn(4, 3).astype(np.float32)
    assert float(L.euclidean_loss(a, b)) == pytest.approx(
        ((a - b) ** 2).sum() / 8.0, rel=1e-6)


def test_hinge_loss(rng_np):
    s = rng_np.randn(3, 5).astype(np.float32)
    y = np.array([1, 0, 4])
    m = s.copy()
    m[np.arange(3), y] *= -1
    m = np.maximum(0, 1 + m)
    assert float(L.hinge_loss(s, y, "L1")) == pytest.approx(m.sum() / 3, rel=1e-6)
    assert float(L.hinge_loss(s, y, "L2")) == pytest.approx(
        (m * m).sum() / 3, rel=1e-6)


def test_accuracy_topk(rng_np):
    s = np.array([[0.1, 0.9, 0.0], [0.8, 0.1, 0.1]], np.float32)
    y = np.array([1, 2])
    assert float(L.accuracy(s, y, 1)) == pytest.approx(0.5)
    assert float(L.accuracy(s, y, 2)) == pytest.approx(0.5)
    assert float(L.accuracy(s, y, 3)) == pytest.approx(1.0)


def test_sigmoid_ce(rng_np):
    x = rng_np.randn(3, 4).astype(np.float32)
    t = rng_np.rand(3, 4).astype(np.float32)
    want = (np.maximum(x, 0) - x * t + np.log1p(np.exp(-np.abs(x)))).sum() / 3
    assert float(L.sigmoid_cross_entropy_loss(x, t)) == pytest.approx(want, rel=1e-5)


def test_contrastive_loss(rng_np):
    a = rng_np.randn(4, 6).astype(np.float32)
    b = rng_np.randn(4, 6).astype(np.float32)
    y = np.array([1, 0, 1, 0], np.float32)
    d2 = ((a - b) ** 2).sum(1)
    want = (np.where(y > 0, d2, np.maximum(1.0 - d2, 0))).sum() / 8
    assert float(L.contrastive_loss(a, b, y, 1.0)) == pytest.approx(want, rel=1e-5)


def test_bnll_power_threshold(rng_np):
    x = rng_np.randn(3, 4).astype(np.float32) * 3
    np.testing.assert_allclose(
        np.asarray(E.bnll(x)), np.log1p(np.exp(-np.abs(x))) + np.maximum(x, 0),
        rtol=1e-5)
    np.testing.assert_allclose(
        np.asarray(E.power(x, 2.0, 0.5, 1.0)), (1.0 + 0.5 * x) ** 2, rtol=1e-5)
    np.testing.assert_allclose(
        np.asarray(E.threshold(x, 0.5)), (x > 0.5).astype(np.float32))


def test_mvn(rng_np):
    x = rng_np.randn(2, 3, 4, 4).astype(np.float32)
    got = np.asarray(E.mvn(x, True, False))
    for i in range(2):
        for c in range(3):
            sl = x[i, c]
            want = (sl - sl.mean()) / (np.sqrt((sl ** 2).mean() - sl.mean() ** 2) + 1e-10)
            np.testing.assert_allclose(got[i, c], want, rtol=1e-4, atol=1e-5)


def test_eltwise_and_slice(rng_np):
    a = rng_np.randn(2, 4).astype(np.float32)
    b = rng_np.randn(2, 4).astype(np.float32)
    np.testing.assert_allclose(np.asarray(E.eltwise([a, b], "SUM", [2.0, -1.0])),
                               2 * a - b, rtol=1e-6)
    np.testing.assert_allclose(np.asarray(E.eltwise([a, b], "MAX", [])),
                               np.maximum(a, b))
    parts = E.slice_blob(a, 1, [1, 3], 3)
    assert [p.shape[1] for p in parts] == [1, 2, 1]


def test_im2col_shape(rng_np):
    x = rng_np.randn(2, 3, 8, 8).astype(np.float32)
    out = np.asarray(NN.im2col(x, (3, 3), (2, 2), (1, 1)))
    assert out.shape == (2, 27, 4, 4)


def test_dropout_scaling(rng_np):
    import jax
    x = np.ones((1000,), np.float32)
    y = np.asarray(E.dropout(x, 0.4, jax.random.PRNGKey(0), True))
    kept = y[y > 0]
    np.testing.assert_allclose(kept, 1.0 / 0.6, rtol=1e-5)
    assert abs(len(kept) / 1000 - 0.6) < 0.08
    np.testing.assert_allclose(np.asarray(E.dropout(x, 0.4, None, False)), x)


@pytest.mark.parametrize("c,k,s,p,h", [
    (3, 11, 4, 0, 227),   # AlexNet conv1
    (3, 7, 2, 3, 49),     # GoogLeNet conv1 shape family (reduced spatial)
    (1, 5, 2, 1, 17),     # k not divisible by s, odd sizes
    (4, 4, 4, 2, 19),     # k == s with padding
])
def test_conv_space_to_depth_exact(rng_np, c, k, s, p, h):
    """The s2d stem rewrite is the identical sum re-bracketed: forward and
    backward must match the direct conv to float tolerance."""
    import jax
    from poseidon_tpu.config import policy_scope
    x = rng_np.randn(2, c, h, h).astype(np.float32)
    w = rng_np.randn(8, c, k, k).astype(np.float32)
    b = rng_np.randn(8).astype(np.float32)

    def loss(args):
        xx, ww, bb = args
        return (NN.conv2d(xx, ww, bb, (s, s), (p, p), 1) ** 2).sum()

    y1 = np.asarray(NN.conv2d(x, w, b, (s, s), (p, p), 1))
    g1 = jax.grad(loss)((x, w, b))
    with policy_scope(conv_s2d=True):
        y2 = np.asarray(NN.conv2d(x, w, b, (s, s), (p, p), 1))
        g2 = jax.grad(loss)((x, w, b))
    assert y1.shape == y2.shape
    np.testing.assert_allclose(y2, y1, rtol=1e-5, atol=5e-5)
    # grads re-bracket ~k*k*O-term float sums; tolerance covers order noise
    for a, c_, name in zip(g1, g2, "xwb"):
        np.testing.assert_allclose(np.asarray(c_), np.asarray(a),
                                   rtol=1e-3, atol=3e-4, err_msg=name)


def test_s2d_real_stems_parity_and_perf_config_default(rng_np):
    """The bf16 perf config (numeric.set_perf_policy — what
    ``train --bf16`` runs) flips conv_s2d ON; this pins the rewrite at the
    REAL stem configurations. f32 parity is checked at float-sum-rebracket
    tolerance against the direct conv1 formulation for both stems:
    AlexNet conv1 (96x3x11x11 / s4 / p0 @ 227) and GoogLeNet conv1
    (64x3x7x7 / s2 / p3 @ 224)."""
    import jax.numpy as jnp
    from poseidon_tpu import config
    from poseidon_tpu.config import policy_scope

    # the perf config's defaults, restored by hand (set_perf_policy has no
    # scope form — it is the bench/CLI entry point)
    saved = (config.policy().compute_dtype, config.policy().conv_s2d)
    try:
        config.set_perf_policy()
        assert config.policy().compute_dtype == jnp.bfloat16
        assert config.policy().conv_s2d is True
    finally:
        config.set_policy(compute_dtype=saved[0], conv_s2d=saved[1])

    stems = [
        ("alexnet_conv1", 96, 11, 4, 0, 227),
        ("googlenet_conv1", 64, 7, 2, 3, 224),
    ]
    for name, o, k, s, p, h in stems:
        x = rng_np.randn(1, 3, h, h).astype(np.float32)
        w = (rng_np.randn(o, 3, k, k).astype(np.float32) / k)
        b = rng_np.randn(o).astype(np.float32)
        y_direct = np.asarray(NN.conv2d(x, w, b, (s, s), (p, p), 1))
        with policy_scope(conv_s2d=True):
            y_s2d = np.asarray(NN.conv2d(x, w, b, (s, s), (p, p), 1))
        assert y_direct.shape == y_s2d.shape, name
        np.testing.assert_allclose(y_s2d, y_direct, rtol=1e-5, atol=1e-5,
                                   err_msg=name)


def test_conv_space_to_depth_skips_many_channel_convs(rng_np):
    """The rewrite must only fire on lane-starved stems (C <= 4)."""
    import jax.numpy as jnp
    from poseidon_tpu.ops.nn import _s2d_applicable
    from poseidon_tpu.config import policy_scope
    x8 = jnp.zeros((1, 8, 9, 9))
    x3 = jnp.zeros((1, 3, 9, 9))
    w8 = jnp.zeros((4, 8, 3, 3))
    w3 = jnp.zeros((4, 3, 3, 3))
    with policy_scope(conv_s2d=True):
        assert not _s2d_applicable(x8, w8, (2, 2), 1, "NCHW")  # enough lanes
        assert not _s2d_applicable(x3, w3, (1, 1), 1, "NCHW")  # stride 1
        assert not _s2d_applicable(x3, w3, (2, 2), 3, "NCHW")  # grouped
        assert _s2d_applicable(x3, w3, (2, 2), 1, "NCHW")
        # NHWC: the channel count is read off the minor axis
        import jax.numpy as _jnp
        assert _s2d_applicable(_jnp.zeros((1, 9, 9, 3)), w3, (2, 2), 1,
                               "NHWC")
        assert not _s2d_applicable(_jnp.zeros((1, 9, 9, 8)), w8, (2, 2), 1,
                                   "NHWC")
    assert not _s2d_applicable(x3, w3, (2, 2), 1, "NCHW")      # knob off
