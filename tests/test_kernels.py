"""Kernel parity + conv-strategy + bf16 guardrails (the MFU-sink PR).

Three contracts, each pinned against the formulation it replaces:

- **pool backward**: the custom VJP's three arms (the vectorized tap-sum of
  the CPU mesh; select-and-scatter in f32, the TPU route of PR 24 and still
  AVE pooling's; the one-pass Pallas max-pool kernel of PR 35, interpreted
  here, in both operand orientations and with block edges inside windows)
  must match each other — f32 tolerance and bf16, both layouts,
  first-max-wins ties bitwise — and Caffe's own backward loop at the
  benchmark configurations' real geometries, summing overlaps in f32 under
  bf16;
- **LRN**: Pallas fwd+bwd parity vs the XLA formulation in both layouts
  (f32 + bf16) and the routing defaults (XLA off-TPU, Pallas on TPU,
  ``POSEIDON_PALLAS_LRN=0`` opt-out, VMEM-cap fallback);
- **conv strategy**: direct/im2col/s2d lowering parity (fwd + dx/dw, both
  layouts), per-layer measured resolution with persistence through the
  compile-cache tuned store, and the ``--bf16`` LeNet smoke training to a
  loss within ``numeric.BF16_SMOKE_*`` of the f32 run.
"""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from poseidon_tpu import config
from poseidon_tpu.config import policy_scope
from poseidon_tpu.ops import nn as NN

N_DEV = 8


@pytest.fixture()
def rng_np():
    return np.random.RandomState(0)


@pytest.fixture()
def pool_env(monkeypatch):
    def force(strategy):
        monkeypatch.setenv("POSEIDON_POOL_BWD", strategy)
    return force


POOL_GEOMS = [
    ((3, 3), (2, 2), (0, 0), 9),    # AlexNet-style overlapping pool
    ((3, 3), (2, 2), (1, 1), 8),    # padded + ceil-mode clamp
    ((2, 2), (2, 2), (0, 0), 8),    # LeNet non-overlapping
    ((5, 5), (3, 3), (2, 2), 11),   # larger window, uneven coverage
    ((3, 3), (1, 1), (1, 1), 7),    # stride 1 (the LRN-within path)
]


def _pool_grad(fn, x, k, s, p, layout):
    f = lambda x_: jnp.sum(fn(x_, k, s, p, layout).astype(jnp.float32) ** 2)
    return np.asarray(jax.grad(f)(x))


# (method, arm): the Pallas arm is MAX pooling's only (AVE stays on sas)
POOL_ARMS = [("max", "taps"), ("ave", "taps"), ("max", "pallas")]


@pytest.mark.parametrize("method,arm", POOL_ARMS)
@pytest.mark.parametrize("layout", ["NCHW", "NHWC"])
@pytest.mark.parametrize("geom", POOL_GEOMS)
def test_pool_bwd_strategies_match_reference(rng_np, pool_env, method, arm,
                                             layout, geom):
    """The tap-sum backward and the (interpreted) Pallas kernel ==
    select-and-scatter."""
    k, s, p, h = geom
    fn = NN.max_pool if method == "max" else NN.ave_pool
    x = rng_np.randn(2, 5, h, h).astype(np.float32)
    if layout == "NHWC":
        x = np.transpose(x, (0, 2, 3, 1)).copy()
    x = jnp.asarray(x)
    pool_env("sas")
    ref = _pool_grad(fn, x, k, s, p, layout)
    pool_env(arm)
    shape = (2, 5, h, h)
    assert NN.pool_bwd_route(k, s, p, method, shape)[0] == arm
    got = _pool_grad(fn, x, k, s, p, layout)
    np.testing.assert_allclose(got, ref, rtol=1e-5, atol=1e-5,
                               err_msg=f"{method}/{layout}/{arm}")


@pytest.mark.parametrize("method,arm", POOL_ARMS)
def test_pool_bwd_bf16(rng_np, pool_env, method, arm):
    """bf16 activations: the tap-sum and the Pallas kernel track the
    reference within bf16 resolution (all recompute and accumulate in
    f32)."""
    fn = NN.max_pool if method == "max" else NN.ave_pool
    x = jnp.asarray(rng_np.randn(2, 4, 9, 9).astype(np.float32)).astype(
        jnp.bfloat16)
    pool_env("sas")
    ref = _pool_grad(fn, x, (3, 3), (2, 2), (0, 0), "NCHW").astype(
        np.float32)
    pool_env(arm)
    got = _pool_grad(fn, x, (3, 3), (2, 2), (0, 0), "NCHW").astype(
        np.float32)
    np.testing.assert_allclose(got, ref, rtol=0.05, atol=0.1,
                               err_msg=f"{method}/{arm}")


@pytest.mark.parametrize("value", [1.0, 0.0], ids=["ones", "relu_zeros"])
@pytest.mark.parametrize("arm", ["taps", "pallas"])
def test_pool_bwd_first_max_wins_ties(pool_env, arm, value):
    """Constant input — ones, or the zeros a ReLU leaves in front of a
    pool: EVERY window position ties, so any argmax divergence from
    Caffe's first-wins `>`-update rule shows up bitwise."""
    x = jnp.full((1, 3, 8, 8), value, jnp.float32)
    w = jnp.arange(1.0, 1.0 + 3 * 5 * 5).reshape(1, 3, 5, 5)

    def grad(x_):
        return np.asarray(jax.grad(lambda a: jnp.sum(NN.max_pool(
            a, (3, 3), (2, 2), (1, 1), "NCHW") * w))(x_))

    pool_env("sas")
    ref = grad(x)
    pool_env(arm)
    np.testing.assert_array_equal(grad(x), ref)
    assert np.count_nonzero(ref) == 3 * 5 * 5      # one winner a window


def test_pool_bwd_strategy_routing(monkeypatch):
    from poseidon_tpu.ops.nn import POOL_TAPS_CAP, pool_bwd_route
    monkeypatch.delenv("POSEIDON_POOL_BWD", raising=False)
    # CPU-mesh default: taps (the CPU thunk-runtime win); the backend is
    # pinned so the suite says the same thing when it runs on the chip
    monkeypatch.setattr("poseidon_tpu.ops.pallas_kernels._interpret_default",
                        lambda: True)
    assert pool_bwd_route((3, 3)) == ("taps", "cpu backend")
    # a global pool's window exceeds the taps cap: the reference arm
    # (select-and-scatter degenerates to a broadcast there anyway)
    assert pool_bwd_route((9, 9))[0] == "sas"
    assert 9 * 9 > POOL_TAPS_CAP
    # lowering for the TPU: select-and-scatter, whatever the window (the
    # v5e's table in PERF.md, PR 24, has no geometry where taps wins)
    monkeypatch.setattr("poseidon_tpu.ops.pallas_kernels._interpret_default",
                        lambda: False)
    for kernel in ((2, 2), (3, 3), (5, 5), (7, 7), (9, 9)):
        assert pool_bwd_route(kernel)[0] == "sas"
    assert pool_bwd_route((3, 3)) == ("sas", "")
    # explicit override always wins
    monkeypatch.setenv("POSEIDON_POOL_BWD", "taps")
    assert pool_bwd_route((3, 3)) == ("taps", "POSEIDON_POOL_BWD=taps")
    # the Pallas arm is the rule's, not the switch's: without the layer's
    # geometry the switch cannot put it anywhere
    monkeypatch.setenv("POSEIDON_POOL_BWD", "pallas")
    assert pool_bwd_route((3, 3)) == ("sas", "")


S2, S1P1 = ((2, 2), (0, 0)), ((1, 1), (1, 1))


ROUTE_CASES = [
    # MAX pooling lowered for the TPU: the kernel, in the orientation the
    # per-device batch gives (AlexNet's pool1 at 512 and 32 images a chip,
    # GoogLeNet's ceil-mode pool1 and an inception pool at 128, LeNet's)
    ("max", (3, 3), *S2, (512, 96, 55, 55), 2, "tpu",
     ("pallas", "batch-minor HxWxCxN, block 14x55x16x128")),
    ("max", (3, 3), *S2, (32, 96, 55, 55), 2, "tpu",
     ("pallas", "channel-minor HxWxNxC, block 14x55x16x96")),
    ("max", (3, 3), *S2, (128, 64, 112, 112), 2, "tpu", "pallas"),
    ("max", (3, 3), *S1P1, (128, 512, 14, 14), 2, "tpu", "pallas"),
    # a dx block too small to be worth a program (GoogLeNet's 5a / 5b)
    ("max", (3, 3), *S1P1, (128, 832, 7, 7), 2, "tpu",
     ("sas", "a dx block of 196 KB is all per-program overhead")),
    ("max", (2, 2), *S2, (64, 20, 24, 24), 4, "tpu", "pallas"),
    # AVE, global and oversized windows, a missing geometry: sas
    ("ave", (5, 5), (3, 3), (0, 0), (128, 512, 14, 14), 2, "tpu",
     ("sas", "")),
    ("ave", (7, 7), (1, 1), (0, 0), (128, 1024, 7, 7), 2, "tpu",
     ("sas", "")),
    ("max", (9, 9), (1, 1), (0, 0), (128, 64, 9, 9), 2, "tpu",
     ("sas", "window above 64 taps")),
    ("max", (3, 3), *S2, None, 2, "tpu", ("sas", "")),
    # a row of W too wide for any block: sas, and the note says why
    ("max", (3, 3), *S2, (128, 64, 8, 40000), 2, "tpu",
     ("sas", "no VMEM-legal block: 2 rows of 40000 x (16 x 128) need 5938 "
             "MB, over 40 MB")),
    # the CPU mesh: taps whatever the layer
    ("max", (3, 3), *S2, (512, 96, 55, 55), 2, "cpu",
     ("taps", "cpu backend")),
    ("ave", (5, 5), (3, 3), (0, 0), (128, 512, 14, 14), 2, "cpu",
     ("taps", "cpu backend")),
]


@pytest.mark.parametrize(
    "case", ROUTE_CASES,
    ids=lambda c: f"{c[0]}{c[1][0]}s{c[2][0]}p{c[3][0]}-{c[6]}-"
                  + ("none" if c[4] is None else "x".join(map(str, c[4]))))
def test_pool_bwd_route_is_the_layers_and_the_shapes(monkeypatch, case):
    """`pool_bwd_route` with the layer's method, window and per-device
    shape: the Pallas kernel for MAX pooling lowered for the TPU wherever a
    block fits, select-and-scatter for AVE / global / oversized windows,
    taps on the CPU mesh."""
    method, kernel, stride, pad, shape, itemsize, backend, want = case
    monkeypatch.delenv("POSEIDON_POOL_BWD", raising=False)
    monkeypatch.setattr("poseidon_tpu.ops.pallas_kernels._interpret_default",
                        lambda: backend == "cpu")
    got = NN.pool_bwd_route(kernel, stride, pad, method, shape, itemsize)
    assert (got[0] if isinstance(want, str) else got) == want


def test_interpret_default_refuses_unknown_backends(monkeypatch):
    """tpu compiles, cpu interprets, anything else is refused — never
    silently interpreted and reported as a device run."""
    from poseidon_tpu.ops import pallas_kernels as PK
    monkeypatch.delenv("POSEIDON_FORCE_PALLAS", raising=False)
    for backend, want in (("tpu", False), ("cpu", True)):
        monkeypatch.setattr(jax, "default_backend", lambda b=backend: b)
        assert PK._interpret_default() is want
    monkeypatch.setattr(jax, "default_backend", lambda: "gpu")
    with pytest.raises(RuntimeError, match="only 'tpu'"):
        PK._interpret_default()


def test_net_logs_and_records_kernel_routes(capsys, monkeypatch):
    """Which arm each pool backward / LRN takes is decided by the same
    function the op consults at trace time, logged once per layer at Net
    construction and kept on the net (the engine writes it to
    stats.yaml): taps on the CPU mesh, the Pallas kernel with its
    orientation and block for a MAX pool lowered for the TPU."""
    from poseidon_tpu.core.net import Net
    from poseidon_tpu.models import zoo
    from poseidon_tpu.ops import pallas_kernels as PK
    monkeypatch.delenv("POSEIDON_POOL_BWD", raising=False)
    monkeypatch.delenv("POSEIDON_PALLAS_LRN", raising=False)
    monkeypatch.setattr(PK, "_interpret_default", lambda: True)  # CPU mesh
    net = Net(zoo.alexnet(num_classes=10), "TRAIN",
              source_shapes={"data": (2, 3, 67, 67), "label": (2,)})
    assert net.kernel_routes == {
        "norm1": "lrn=xla", "pool1": "pool_bwd=taps", "norm2": "lrn=xla",
        "pool2": "pool_bwd=taps", "pool5": "pool_bwd=taps"}
    assert "[kernel_route] pool1: pool_bwd -> taps" in capsys.readouterr().out
    monkeypatch.setattr(PK, "_interpret_default", lambda: False)  # for TPU
    net = Net(zoo.alexnet(num_classes=10), "TRAIN",
              source_shapes={"data": (16, 3, 131, 131), "label": (16,)})
    assert net.kernel_routes == {
        "norm1": "lrn=pallas (channel-minor HWxNxC, block 168x16x96)",
        "pool1": "pool_bwd=pallas (channel-minor HxWxNxC, block 32x31x8x96)",
        "norm2": "lrn=pallas (channel-minor HWxNxC, block 64x16x256)",
        "pool2": "pool_bwd=pallas (channel-minor HxWxNxC, "
                 "block 16x15x8x128)",
        "pool5": "pool_bwd=sas"}
    out = capsys.readouterr().out
    assert ("[kernel_route] pool1: pool_bwd -> pallas (channel-minor "
            "HxWxNxC, block 32x31x8x96)") in out
    # 16 x 256 x 7 x 7: the rule says why it kept XLA's op
    assert ("[kernel_route] pool5: pool_bwd -> sas (a dx block of 196 KB "
            "is all per-program overhead)") in out
    assert ("[kernel_route] norm1: lrn -> pallas (channel-minor HWxNxC, "
            "block 168x16x96)") in out


@pytest.mark.parametrize("backend", ["tpu", "cpu"])
def test_googlenet_names_an_arm_for_every_pooling_layer(monkeypatch,
                                                        backend):
    """GoogLeNet at the benchmark cell's 128 images a chip: eleven of its
    thirteen MAX pools (four 3x3 s2 in ceil mode, seven 3x3 s1 p1 inside
    the inception modules) take the Pallas kernel batch-minor when lowered
    for the TPU; the two 3x3 s1 p1 on 7 x 7 (a dx block of 196 KB: all
    per-program overhead) and the three AVE pools (5x5 s3 twice, the 7x7
    head) stay on select-and-scatter; the CPU mesh takes the tap-sum for
    all sixteen."""
    from poseidon_tpu.core.net import Net
    from poseidon_tpu.models import zoo
    from poseidon_tpu.ops import pallas_kernels as PK
    monkeypatch.delenv("POSEIDON_POOL_BWD", raising=False)
    monkeypatch.setattr(PK, "_interpret_default", lambda: backend == "cpu")
    with policy_scope(compute_dtype=jnp.bfloat16):
        net = Net(zoo.googlenet(), "TRAIN",
                  source_shapes={"data": (128, 3, 224, 224),
                                 "label": (128,)})
    pools = {k: v for k, v in net.kernel_routes.items()
             if v.startswith("pool_bwd=")}
    assert len(pools) == 16
    ave = {"loss1/ave_pool", "loss2/ave_pool", "pool5/7x7_s1"}
    if backend == "cpu":
        assert set(pools.values()) == {"pool_bwd=taps"}
        return
    small = {"inception_5a/pool", "inception_5b/pool"}    # 7 x 7: too small
    assert {k for k, v in pools.items() if v == "pool_bwd=sas"} == ave | small
    assert all(v.startswith("pool_bwd=pallas (batch-minor HxWxCxN, block ")
               for k, v in pools.items() if k not in ave | small)
    assert pools["pool1/3x3_s2"] == (
        "pool_bwd=pallas (batch-minor HxWxCxN, block 6x112x16x128)")
    assert pools["inception_4e/pool"] == (
        "pool_bwd=pallas (batch-minor HxWxCxN, block 14x14x16x128)")


def test_one_channel_conv_takes_im2col_when_lowering_for_tpu(capsys,
                                                            monkeypatch):
    """libtpu 0.0.34 does not finish compiling LeNet's 1-input-channel
    conv1 backward as a direct conv at f32 HIGHEST (PR 21, on the chip):
    lowering for the TPU routes such a conv through im2col, says so, and
    leaves every other conv — and the CPU mesh, and an explicit
    --conv_strategy — alone."""
    from poseidon_tpu.core.net import Net
    from poseidon_tpu.models import zoo
    from poseidon_tpu.ops import pallas_kernels as PK

    def plan(**kw):
        return Net(zoo.lenet(with_accuracy=False), "TRAIN",
                   zoo.lenet_shapes(2), **kw).conv_strategy_plan()

    monkeypatch.setattr(PK, "_interpret_default", lambda: True)   # CPU mesh
    assert plan() == {"conv1": None, "conv2": None}
    monkeypatch.setattr(PK, "_interpret_default", lambda: False)  # for TPU
    capsys.readouterr()
    assert plan() == {"conv1": "im2col", "conv2": None}
    assert "[conv_strategy] conv1: 1 input channel -> im2col" in \
        capsys.readouterr().out
    assert plan(conv_strategy="direct") == {"conv1": "direct",
                                            "conv2": "direct"}


# the pooling geometries the benchmark's two configurations hold (AlexNet's
# three; GoogLeNet's ceil-mode stride-2, its stride-1 inception pools and
# its AVE heads), at small N and C
REAL_POOL_GEOMS = [
    ("max", (3, 3), (2, 2), (0, 0), 55),
    ("max", (3, 3), (2, 2), (0, 0), 27),
    ("max", (3, 3), (2, 2), (0, 0), 13),
    ("max", (3, 3), (2, 2), (0, 0), 112),   # ceil mode: 112 -> 56
    ("max", (3, 3), (2, 2), (0, 0), 56),    # ceil mode: 56 -> 28
    ("max", (3, 3), (2, 2), (0, 0), 28),    # ceil mode: 28 -> 14
    ("max", (3, 3), (2, 2), (0, 0), 14),    # ceil mode: 14 -> 7
    ("max", (3, 3), (1, 1), (1, 1), 28),
    ("max", (3, 3), (1, 1), (1, 1), 14),
    ("max", (3, 3), (1, 1), (1, 1), 7),
    ("ave", (5, 5), (3, 3), (0, 0), 14),
    ("ave", (7, 7), (1, 1), (0, 0), 7),
]


def _caffe_pool_bwd(x, g, k, s, p, method):
    """pooling_layer.cpp's Backward_cpu in numpy (NCHW): the first max of
    a window takes its cotangent, AVE divides by the window clipped to the
    padded extent; everything summed in f32."""
    n, c, h, w = x.shape
    dx = np.zeros(x.shape, np.float32)
    for i in range(g.shape[2]):
        for j in range(g.shape[3]):
            hs, ws = i * s[0] - p[0], j * s[1] - p[1]
            he, we = min(hs + k[0], h + p[0]), min(ws + k[1], w + p[1])
            size = (he - hs) * (we - ws)
            hs, ws, he, we = max(hs, 0), max(ws, 0), min(he, h), min(we, w)
            if method == "ave":
                dx[:, :, hs:he, ws:we] += g[:, :, i:i + 1, j:j + 1] / size
                continue
            first = x[:, :, hs:he, ws:we].reshape(n, c, -1).argmax(-1)
            r, q = np.divmod(first, we - ws)
            np.add.at(dx, (np.arange(n)[:, None], np.arange(c)[None, :],
                           hs + r, ws + q), g[:, :, i, j])
    return dx


def _geom_id(g):
    return f"{g[0]}{g[1][0]}s{g[2][0]}p{g[3][0]}on{g[4]}"


@pytest.mark.parametrize("arm", ["sas", "taps", "pallas"])
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("geom", REAL_POOL_GEOMS, ids=_geom_id)
def test_pool_bwd_routes_match_caffe_at_real_geometries(
        rng_np, pool_env, arm, dtype, geom):
    """Every formulation a route lowers (the Pallas kernel for MAX pooling
    on the TPU, interpreted here; select-and-scatter in f32 for AVE there;
    the tap-sum for the CPU), run on the CPU mesh, against Caffe's own
    backward loop."""
    method, k, s, p, h = geom
    pool_env(arm)
    if arm == "pallas" and method == "ave":
        arm = "sas"                      # the rule keeps AVE pooling on sas
    assert NN.pool_bwd_route(k, s, p, method, (2, 3, h, h))[0] == arm
    fn = NN.max_pool if method == "max" else NN.ave_pool
    x = jnp.asarray(rng_np.randn(2, 3, h, h), dtype)
    y, vjp = jax.vjp(lambda x_: fn(x_, k, s, p, "NCHW"), x)
    g = jnp.asarray(rng_np.randn(*y.shape), dtype)
    got = vjp(g)[0]
    assert got.dtype == x.dtype
    want = _caffe_pool_bwd(np.asarray(x, np.float32),
                           np.asarray(g, np.float32), k, s, p, method)
    # bf16: ONE rounding of the f32 sum (2**-8), not one per contribution
    tol = 1e-5 if dtype == "float32" else 2.0 ** -7
    np.testing.assert_allclose(np.asarray(got, np.float32), want,
                               rtol=tol, atol=tol)


@pytest.mark.parametrize("orient", ["batch_minor", "channel_minor"])
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("geom",
                         [g for g in REAL_POOL_GEOMS if g[0] == "max"],
                         ids=_geom_id)
def test_maxpool_bwd_kernel_matches_caffe_in_both_orientations(
        rng_np, orient, dtype, geom):
    """The kernel alone (interpreted) at every MAX geometry of the two
    configurations, channels cut for the CPU: in both operand orientations
    (a per-device batch of 128 is batch-minor, below it channel-minor),
    with three or more blocks of rows, so that windows straddle block edges
    and their halo rows decide, the ceil-mode row and column and Caffe's
    pad as masked taps, and behind a ReLU, whose zeros tie: Caffe's first
    maximum must win in every window, also one cut by a block edge."""
    from poseidon_tpu.ops import pallas_kernels as PK
    _, k, s, p, h = geom
    # (second, minor) tiles of 2 x 128 and 16 x 3: in bf16 the first is
    # staged widened to f32, the second as packed 32-bit words
    n, c = (128, 2) if orient == "batch_minor" else (16, 3)
    oh = NN.pool_out_size(h, k[0], s[0], p[0])
    rows = min(4 if h > 56 else 8, max(1, -(-h // 3) // s[0]) * s[0])
    plan = PK._pool_plan(h, h, c, n, k, s, p, jnp.dtype(dtype).itemsize,
                         rows)
    assert plan.channel_axis == (1 if orient == "batch_minor" else 2)
    assert plan.rows == rows and -(-h // rows) >= 2
    x = jnp.asarray(np.maximum(rng_np.randn(n, c, h, h), 0), dtype)
    g = jnp.asarray(rng_np.randn(n, c, oh, oh), dtype)
    got = PK.maxpool_bwd(x, g, k, s, p, rows=rows, interpret=True)
    assert got.dtype == x.dtype and got.shape == x.shape
    want = _caffe_pool_bwd(np.asarray(x, np.float32),
                           np.asarray(g, np.float32), k, s, p, "max")
    tol = 1e-5 if dtype == "float32" else 2.0 ** -7
    np.testing.assert_allclose(np.asarray(got, np.float32), want,
                               rtol=tol, atol=tol)
    # NHWC is the same kernel behind another logical transpose
    if h <= 14:
        nhwc = PK.maxpool_bwd(x.transpose(0, 2, 3, 1),
                              g.transpose(0, 2, 3, 1), k, s, p,
                              layout="NHWC", rows=rows, interpret=True)
        np.testing.assert_array_equal(
            np.asarray(nhwc.transpose(0, 3, 1, 2), np.float32),
            np.asarray(got, np.float32))


@pytest.mark.parametrize("method,arm", [
    ("max", "sas"), ("max", "taps"), ("ave", "sas"), ("ave", "taps"),
    ("max", "pallas"), ("max", "pallas_halo")])
def test_pool_bwd_bf16_sums_overlaps_in_f32(pool_env, method, arm):
    """Input position (2, 2) of a 3x3 s2 pool lies in four windows. With
    cotangents 256, 1, 1, 1 a bf16 accumulator stays at 256 (257 is no
    bf16 number); the f32 sum 259 rounds once, to 260 — in every arm, and
    in the Pallas kernel also where a block edge runs through the four
    windows (rows 0-1 and 2-3 in different programs)."""
    x = np.zeros((2, 16, 5, 5), np.float32)
    x[:, :, 2, 2] = 1.0                     # the max of all four windows
    g = np.broadcast_to(np.array([[256.0, 1.0], [1.0, 1.0]], np.float32),
                        (2, 16, 2, 2))
    fn = NN.max_pool if method == "max" else NN.ave_pool
    scale = 1.0 if method == "max" else 9.0      # undo AVE's exact / 9
    xb, gb = jnp.asarray(x, jnp.bfloat16), jnp.asarray(g * scale,
                                                       jnp.bfloat16)
    if arm == "pallas_halo":
        from poseidon_tpu.ops import pallas_kernels as PK
        dx = PK.maxpool_bwd(xb, gb, (3, 3), (2, 2), (0, 0), rows=2,
                            interpret=True)
    else:
        pool_env(arm)
        _, vjp = jax.vjp(lambda x_: fn(x_, (3, 3), (2, 2), (0, 0), "NCHW"),
                         xb)
        dx = vjp(gb)[0]
    assert dx.dtype == jnp.bfloat16
    assert np.all(np.asarray(dx[:, :, 2, 2], np.float32) == 260.0)


def test_pool_bwd_under_jit_and_in_net(rng_np, pool_env):
    """The custom VJP composes with jit and a whole-net backward: LeNet
    gradients under taps == under the reference arm."""
    from poseidon_tpu.core.net import Net
    from poseidon_tpu.models import zoo
    net = Net(zoo.lenet(with_accuracy=False), phase="TRAIN",
              source_shapes=zoo.lenet_shapes(4))
    params = net.init(jax.random.PRNGKey(0))
    batch = {"data": jnp.asarray(rng_np.randn(4, 1, 28, 28)
                                 .astype(np.float32)),
             "label": jnp.asarray(rng_np.randint(0, 10, size=(4,)))}

    def loss(p):
        return net.apply(p, batch, rng=jax.random.PRNGKey(1)).loss

    grads = {}
    for strategy in ("sas", "taps"):
        pool_env(strategy)
        jax.clear_caches()     # the strategy is read at trace time
        grads[strategy] = jax.jit(jax.grad(loss))(params)
    for lname in grads["sas"]:
        for pname in grads["sas"][lname]:
            np.testing.assert_allclose(
                np.asarray(grads["taps"][lname][pname]),
                np.asarray(grads["sas"][lname][pname]),
                rtol=1e-5, atol=1e-6, err_msg=f"{lname}/{pname}")


# --------------------------------------------------------------------------- #
# LRN
# --------------------------------------------------------------------------- #

def test_lrn_routing_defaults(monkeypatch):
    """CPU mesh (mocked, so the suite says the same on the chip): XLA
    formulation, POSEIDON_PALLAS_LRN=1 forces the interpreted kernels. TPU
    (mocked): Pallas by default, POSEIDON_PALLAS_LRN=0 opts out."""
    from poseidon_tpu.ops import pallas_kernels as PK
    x = jnp.ones((1, 4, 4, 4), jnp.float32)
    calls = []
    monkeypatch.setattr(PK, "lrn_fused",
                        lambda *a, **kw: calls.append("pallas") or x)
    monkeypatch.delenv("POSEIDON_PALLAS_LRN", raising=False)
    monkeypatch.setattr(PK, "_interpret_default", lambda: True)
    PK.maybe_lrn_fused(x, 5, 1e-4, 0.75)          # CPU: XLA
    assert calls == [] and PK.lrn_route(16, 4) == ("xla", "cpu backend")
    monkeypatch.setenv("POSEIDON_PALLAS_LRN", "1")
    assert PK.lrn_route(16, 4) == ("pallas", "")  # forced, interpreted
    monkeypatch.delenv("POSEIDON_PALLAS_LRN")
    monkeypatch.setattr(PK, "_interpret_default", lambda: False)
    PK.maybe_lrn_fused(x, 5, 1e-4, 0.75)          # "TPU": Pallas default
    assert calls == ["pallas"]
    monkeypatch.setenv("POSEIDON_PALLAS_LRN", "0")
    PK.maybe_lrn_fused(x, 5, 1e-4, 0.75)          # opt-out honored
    assert calls == ["pallas"]
    assert PK.lrn_route(16, 4) == ("xla", "POSEIDON_PALLAS_LRN=0")


@pytest.mark.parametrize("layout", ["NCHW", "NHWC"])
def test_lrn_fwd_bwd_parity_f32(rng_np, layout):
    """Pallas LRN fwd + analytic bwd kernels (interpret mode) vs the XLA
    formulation, through the custom-VJP gradient path."""
    from poseidon_tpu.ops.pallas_kernels import lrn_fused, lrn_fused_bwd
    from poseidon_tpu.ops.nn import lrn_across_channels
    x = rng_np.randn(2, 16, 5, 5).astype(np.float32)
    if layout == "NHWC":
        x = np.transpose(x, (0, 2, 3, 1)).copy()
    xj = jnp.asarray(x)
    want = np.asarray(lrn_across_channels(xj, 5, 1e-4, 0.75, 1.0, layout))
    got = np.asarray(lrn_fused(xj, 5, 1e-4, 0.75, 1.0, layout=layout))
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)
    f_ref = lambda x_: jnp.sum(
        lrn_across_channels(x_, 5, 1e-4, 0.75, 1.0, layout) ** 2)
    dref = np.asarray(jax.grad(f_ref)(xj))
    g = jax.grad(lambda x_: jnp.sum(
        lrn_across_channels(x_, 5, 1e-4, 0.75, 1.0, layout) ** 2))(xj)
    # the standalone analytic backward kernel, driven by the same upstream
    # cotangent the squared-sum loss produces
    y = lrn_across_channels(xj, 5, 1e-4, 0.75, 1.0, layout)
    dk = np.asarray(lrn_fused_bwd(xj, 2.0 * y, 5, 1e-4, 0.75, 1.0,
                                  interpret=True, layout=layout))
    np.testing.assert_allclose(dk, dref, rtol=1e-4, atol=1e-5)
    assert g.shape == xj.shape


@pytest.mark.parametrize("layout", ["NCHW", "NHWC"])
def test_lrn_parity_bf16(rng_np, layout):
    from poseidon_tpu.ops.pallas_kernels import lrn_fused
    from poseidon_tpu.ops.nn import lrn_across_channels
    x = rng_np.randn(2, 16, 5, 5).astype(np.float32)
    if layout == "NHWC":
        x = np.transpose(x, (0, 2, 3, 1)).copy()
    xb = jnp.asarray(x).astype(jnp.bfloat16)
    want = np.asarray(lrn_across_channels(xb, 5, 1e-4, 0.75, 1.0,
                                          layout)).astype(np.float32)
    got = np.asarray(lrn_fused(xb, 5, 1e-4, 0.75, 1.0,
                               layout=layout)).astype(np.float32)
    np.testing.assert_allclose(got, want, rtol=0.05, atol=0.05)


@pytest.mark.parametrize("layout", ["NCHW", "NHWC"])
@pytest.mark.parametrize("local_size,k", [(5, 1.0), (4, 1.5)])
def test_lrn_analytic_xla_bwd_matches_autodiff(rng_np, monkeypatch, layout,
                                               local_size, k):
    """The XLA fallback's analytic custom-VJP backward (what CPU runs by
    default now) == plain autodiff through the forward, odd AND even
    windows, both layouts."""
    from poseidon_tpu.ops.nn import lrn_across_channels
    x = rng_np.randn(2, 16, 4, 4).astype(np.float32)
    if layout == "NHWC":
        x = np.transpose(x, (0, 2, 3, 1)).copy()
    xj = jnp.asarray(x)
    f = lambda x_: jnp.sum(
        lrn_across_channels(x_, local_size, 2e-4, 0.75, k, layout) ** 2)
    monkeypatch.setenv("POSEIDON_LRN_BWD", "autodiff")
    want = np.asarray(jax.grad(f)(xj))
    monkeypatch.delenv("POSEIDON_LRN_BWD")
    got = np.asarray(jax.grad(f)(xj))
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-6)


# the four norm layers of the benchmark's CNN cells at the cells' batch a
# chip, GoogLeNet's published batch 32, and a batch that fills no lane tile
_LRN_RULE = [
    # n, c, hw, itemsize -> channel_axis, block, rows
    ((512, 96, 3025, 2), (1, (10, 96, 512), 1)),
    ((512, 256, 729, 2), (1, (4, 256, 512), 1)),
    ((128, 64, 3136, 2), (1, (64, 64, 128), 1)),
    ((128, 192, 3136, 2), (1, (21, 192, 128), 1)),
    ((32, 64, 3136, 2), (2, (256, 32, 64), 4)),
    ((32, 192, 3136, 2), (2, (84, 32, 192), 2)),
    ((200, 96, 729, 4), (2, (21, 128, 96), 1)),
    ((4096, 256, 729, 2), (1, (1, 256, 2048), 1)),
]


@pytest.mark.parametrize("geometry,want", _LRN_RULE)
def test_lrn_tile_rule(geometry, want, monkeypatch):
    """Orientation and block come from the shape alone: batch-minor where
    the batch fills the lanes, channel-minor below; about 1 MB an operand
    block; the minor two dims the array's own or an exact tile of them.
    The route's note says which."""
    from poseidon_tpu.ops import pallas_kernels as PK
    n, c, hw, itemsize = geometry
    got = PK._lrn_tile(hw, c, n, itemsize)
    assert got == want
    axis, (t, second, minor), rows = got
    assert t % rows == 0 and t * second * minor * itemsize <= 2 ** 20
    assert (second, minor) == ((c, min(n, minor)) if axis == 1
                               else (min(n, second), c))
    monkeypatch.delenv("POSEIDON_PALLAS_LRN", raising=False)
    monkeypatch.setattr(PK, "_interpret_default", lambda: False)
    assert PK.lrn_route(hw, c, n, itemsize) == (
        "pallas", "%s, block %dx%dx%d" % (
            "batch-minor HWxCxN" if axis == 1 else "channel-minor HWxNxC",
            t, second, minor))


@pytest.mark.parametrize("local_size", [3, 5])
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("n,c,h,w", [
    # batch 128 -> batch-minor, batch 4 -> channel-minor; 55 x 55 = 3025
    # pixels in blocks of 16 leave a partial last block, as do 5 x 7
    (128, 8, 55, 55),
    (128, 64, 5, 7), (128, 96, 5, 7), (128, 192, 5, 7), (128, 256, 5, 7),
    (4, 64, 55, 55), (4, 96, 55, 55), (4, 192, 55, 55), (4, 256, 55, 55)])
def test_lrn_each_orientation_matches_xla(rng_np, n, c, h, w, dtype,
                                          local_size):
    """Both orientations of the one kernel body, forward and analytic
    backward, against ``lrn_across_channels`` and its VJP."""
    from poseidon_tpu.ops import pallas_kernels as PK
    from poseidon_tpu.ops.nn import lrn_across_channels
    assert PK._lrn_tile(h * w, c, n, 4, 16)[0] == (1 if n == 128 else 2)
    assert (h * w) % 16
    x32 = jnp.asarray(rng_np.randn(n, c, h, w).astype(np.float32) * 8)
    g32 = jnp.asarray(rng_np.randn(n, c, h, w).astype(np.float32))
    x, g = x32.astype(dtype), g32.astype(dtype)
    ref = lambda x_: lrn_across_channels(x_, local_size, 1e-4, 0.75, 1.0)
    want, vjp = jax.vjp(ref, x)
    # interpreted on the CPU mesh, compiled under POSEIDON_TEST_TPU=1
    got = PK.lrn_fused(x, local_size, 1e-4, 0.75, 1.0, tile=16)
    dgot = PK.lrn_fused_bwd(x, g, local_size, 1e-4, 0.75, 1.0, tile=16)
    assert got.dtype == x.dtype and dgot.dtype == x.dtype
    f32 = lambda a: np.asarray(a.astype(jnp.float32))
    if dtype == "float32":
        np.testing.assert_allclose(f32(got), f32(want), rtol=1e-5, atol=1e-6)
        np.testing.assert_allclose(f32(dgot), f32(vjp(g)[0]),
                                   rtol=1e-4, atol=1e-5)
    else:
        np.testing.assert_allclose(f32(got), f32(want), rtol=0.05, atol=0.05)
        np.testing.assert_allclose(f32(dgot), f32(vjp(g)[0]),
                                   rtol=0.05, atol=0.05)


def test_lrn_vmem_cap_falls_back_with_grad():
    """Beyond the ~2560-channel tile cap, lrn_fused silently takes the
    XLA formulation — forward AND backward stay usable."""
    from poseidon_tpu.ops.pallas_kernels import lrn_fused, lrn_tile_feasible
    assert not lrn_tile_feasible(81, 4096)
    x = jnp.ones((1, 4096, 9, 9), jnp.float32)
    y = lrn_fused(x, 5, 1e-4, 0.75)
    g = jax.grad(lambda x_: jnp.sum(lrn_fused(x_, 5, 1e-4, 0.75) ** 2))(x)
    assert y.shape == x.shape and g.shape == x.shape


# --------------------------------------------------------------------------- #
# conv strategies
# --------------------------------------------------------------------------- #

@pytest.mark.parametrize("layout", ["NCHW", "NHWC"])
@pytest.mark.parametrize("strategy", ["im2col", "s2d"])
def test_conv_strategy_parity(rng_np, layout, strategy):
    """Every lowering computes the direct conv's numbers (fwd, dx, dw)."""
    x = rng_np.randn(2, 3, 13, 13).astype(np.float32)
    w = rng_np.randn(8, 3, 3, 3).astype(np.float32)
    b = rng_np.randn(8).astype(np.float32)
    if layout == "NHWC":
        x = np.transpose(x, (0, 2, 3, 1)).copy()
    x, w, b = jnp.asarray(x), jnp.asarray(w), jnp.asarray(b)
    args = ((2, 2), (1, 1), 1)

    def run(s):
        y = NN.conv2d(x, w, b, *args, layout=layout, strategy=s)
        f = lambda x_, w_: jnp.sum(
            NN.conv2d(x_, w_, b, *args, layout=layout, strategy=s) ** 2)
        dx, dw = jax.grad(f, argnums=(0, 1))(x, w)
        return map(np.asarray, (y, dx, dw))

    for got, want in zip(run(strategy), run("direct")):
        np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-4)


def test_conv_strategy_inapplicable_falls_back(rng_np):
    """Grouped conv: im2col/s2d cannot lower it — conv2d silently takes
    direct, and the candidate filter never offers them."""
    x = jnp.asarray(rng_np.randn(1, 4, 8, 8).astype(np.float32))
    w = jnp.asarray(rng_np.randn(8, 2, 3, 3).astype(np.float32))
    want = NN.conv2d(x, w, None, (1, 1), (1, 1), 2, strategy="direct")
    for s in ("im2col", "s2d"):
        got = NN.conv2d(x, w, None, (1, 1), (1, 1), 2, strategy=s)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
        assert not NN.conv_strategy_applicable(s, x, w, (1, 1), 2, "NCHW")


def test_conv2d_rejects_unresolved_auto(rng_np):
    """"auto" named the per-layer micro-tuner's request; conv2d never
    lowered it and still refuses it, now as any other unknown name."""
    x = jnp.zeros((1, 3, 8, 8), jnp.float32)
    w = jnp.zeros((4, 3, 3, 3), jnp.float32)
    with pytest.raises(ValueError, match="unknown strategy 'auto'"):
        NN.conv2d(x, w, None, (1, 1), (0, 0), strategy="auto")


def test_net_conv_strategy_plumbing():
    """Net-level resolution: a forced strategy lands on every conv layer
    and the net traces and runs under it; no request leaves the layers to
    the global conv_s2d policy; an unknown name ("auto" is one since the
    micro-tuner went) is refused at construction."""
    from poseidon_tpu.core.net import Net
    from poseidon_tpu.models import zoo
    shapes = zoo.lenet_shapes(4)
    net = Net(zoo.lenet(with_accuracy=False), "TRAIN", shapes,
              conv_strategy="im2col")
    assert net.conv_strategy_plan() == {"conv1": "im2col",
                                        "conv2": "im2col"}
    params = net.init(jax.random.PRNGKey(0))
    out = net.apply(params, {
        "data": jnp.zeros(shapes["data"], jnp.float32),
        "label": jnp.zeros(shapes["label"], jnp.int32)},
        rng=jax.random.PRNGKey(1))
    assert np.isfinite(float(out.loss))
    # default: layers carry None (the global conv_s2d policy rules)
    net0 = Net(zoo.lenet(with_accuracy=False), "TRAIN", shapes)
    assert set(net0.conv_strategy_plan().values()) == {None}
    for unknown in ("winograd", "auto"):
        with pytest.raises(ValueError, match="conv_strategy"):
            Net(zoo.lenet(with_accuracy=False), "TRAIN", shapes,
                conv_strategy=unknown)


# --------------------------------------------------------------------------- #
# the documented --bf16 path: loss-trajectory guardrail
# --------------------------------------------------------------------------- #

def _train_lenet_losses(rng_np, iters):
    """LeNet overfitting a fixed 4-batch cycle (random labels memorize
    reliably at this lr; fresh batches every step would just bounce)."""
    from poseidon_tpu.core.net import Net
    from poseidon_tpu.models import zoo
    from poseidon_tpu.parallel import (CommConfig, build_train_step,
                                       init_train_state, make_mesh)
    from poseidon_tpu.proto.messages import SolverParameter
    batch_n = 16
    net = Net(zoo.lenet(with_accuracy=False), "TRAIN",
              zoo.lenet_shapes(batch_n // N_DEV))
    sp = SolverParameter(base_lr=0.005, lr_policy="fixed", momentum=0.9,
                         weight_decay=0.0005)
    cc = CommConfig()
    ts = build_train_step(net, sp, make_mesh(), cc, donate=False)
    params = net.init(jax.random.PRNGKey(0))
    state = init_train_state(params, cc, N_DEV)
    data = rng_np.randn(4, batch_n, 1, 28, 28).astype(np.float32)
    labels = rng_np.randint(0, 10, size=(4, batch_n))
    losses = []
    for i in range(iters):
        batch = {"data": jnp.asarray(data[i % 4]),
                 "label": jnp.asarray(labels[i % 4])}
        params, state, m = ts.step(params, state, batch,
                                   jax.random.fold_in(jax.random.PRNGKey(1),
                                                      i))
        losses.append(float(m["loss"]))
    return losses


def test_bf16_lenet_smoke_within_documented_tolerance():
    """The --bf16 acceptance guardrail: identical data/seeds, f32 vs the
    bf16 perf policy; the end-of-smoke loss level must sit inside the
    documented numeric.BF16_SMOKE_* band. Catches any kernel that starts
    accumulating below f32 where it must not."""
    from poseidon_tpu.numeric import (BF16_SMOKE_ATOL, BF16_SMOKE_ITERS,
                                      BF16_SMOKE_RTOL)
    f32 = _train_lenet_losses(np.random.RandomState(7), BF16_SMOKE_ITERS)
    with policy_scope(compute_dtype=jnp.bfloat16, conv_s2d=True):
        bf16 = _train_lenet_losses(np.random.RandomState(7),
                                   BF16_SMOKE_ITERS)
    assert all(np.isfinite(bf16)), "bf16 run diverged"
    f32_tail = float(np.mean(f32[-5:]))
    bf16_tail = float(np.mean(bf16[-5:]))
    tol = BF16_SMOKE_RTOL * abs(f32_tail) + BF16_SMOKE_ATOL
    assert abs(bf16_tail - f32_tail) <= tol, (
        f"bf16 tail loss {bf16_tail:.4f} drifted beyond the documented "
        f"band from f32 {f32_tail:.4f} (tol {tol:.4f})")
    # and training actually made progress in both arms
    assert f32_tail < float(np.mean(f32[:3]))
    assert bf16_tail < float(np.mean(bf16[:3]))
