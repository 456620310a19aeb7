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


# --------------------------------------------------------------------------- #
# value heads of their own width (latent attention: 192-wide scores over
# 128-wide values)
# --------------------------------------------------------------------------- #

def _qkv_two_widths(seed, s, d, dv, heads=2):
    keys = jax.random.split(jax.random.PRNGKey(seed), 4)
    widths = (d, d, dv, dv)
    return [jax.random.normal(k, (1, heads, s, w), jnp.float32)
            for k, w in zip(keys, widths)]


@pytest.mark.parametrize("what", ["out", "dq", "dk", "dv"])
@pytest.mark.parametrize("s,d,dv,blocks", [
    (256, 192, 128, (128, 64)),      # the configuration's widths
    (64, 24, 16, (16, 32)),
    (64, 16, 24, (32, 16)),          # and values wider than keys
])
def test_flash_kernels_take_two_head_widths(what, s, d, dv, blocks):
    """The three kernels (interpret mode) against the XLA arm, causal: q and
    k of one width, v, the output and its cotangent of another; dq and dk
    come back at the first, dv at the second."""
    q, k, v, g = _qkv_two_widths(3, s, d, dv)
    flash = lambda q, k, v: pk.flash_attention(q, k, v, True, None, *blocks,
                                               True)
    dense = lambda q, k, v: attention(q, k, v, causal=True)
    if what == "out":
        got, want = flash(q, k, v), dense(q, k, v)
        assert got.shape == (1, 2, s, dv)
    else:
        i = ("dq", "dk", "dv").index(what)
        got, want = (jax.grad(lambda *a, f=f: jnp.sum(f(*a) * g),
                              argnums=i)(q, k, v) for f in (flash, dense))
        assert got.shape == (q, k, v)[i].shape
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-5)


def test_tile_rule_and_route_with_two_widths(monkeypatch):
    """``flash_blocks`` counts both widths: equal widths give the rule's old
    answer whatever way they are passed; at 8,192 x 192 / 128 in bf16 all
    three kernels keep 1024 x 1024; the route's note names the widths."""
    for kernel in ("fwd", "dq", "dkv", "bwd"):
        for s, d in ((4096, 128), (8192, 128), (2048, 64), (136, 32)):
            assert pk.flash_blocks(kernel, s, d, 2) \
                == pk.flash_blocks(kernel, s, d, 2, d)
            assert pk._flash_vmem_bytes(kernel, 512, 256, d, 2, s=s) \
                == pk._flash_vmem_bytes(kernel, 512, 256, d, 2, d, s)
        assert pk.flash_blocks(kernel, 8192, 192, 2, 128) == (1024, 1024)
    # the widths are counted: f32 operands at 192 / 128 no longer fit the
    # dK/dV sweep's 1024 x 1024 (they do at 128 / 128), nor at 192 / 256
    # the single sweep's (they do at 192 / 128)
    assert pk.flash_blocks("dkv", 8192, 192, 4, 128) == (512, 1024)
    assert pk.flash_blocks("dkv", 8192, 128, 4) == (1024, 1024)
    assert pk.flash_blocks("bwd", 8192, 192, 4, 256) == (512, 1024)
    assert pk.flash_blocks("bwd", 8192, 192, 4, 128) == (1024, 1024)
    monkeypatch.setenv("POSEIDON_FORCE_PALLAS", "1")
    arm, note = pk.attention_route(8192, 8192, 192, 2, dv=128)
    assert arm == "pallas_flash" and note.endswith(
        "; flash d 192/128; operands head-major (Dh 192, not lane-aligned)")
    assert "flash d" not in pk.attention_route(8192, 8192, 128, 2, dv=128)[1]


def test_shared_key_part_is_joined_to_every_head():
    """``rope_attention`` with a fourth operand: each key head is its own
    dims followed by the shared part; equal to attention over keys built by
    hand, and the shared part's gradient is the sum over heads."""
    from poseidon_tpu.models.transformer import rope_attention
    key = jax.random.PRNGKey(7)
    b, s, h, own, shared, dv = 1, 16, 4, 6, 2, 4
    q = jax.random.normal(key, (b, s, h * (own + shared)))
    k = jax.random.normal(jax.random.fold_in(key, 1), (b, s, h * own))
    v = jax.random.normal(jax.random.fold_in(key, 2), (b, s, h * dv))
    kpe = jax.random.normal(jax.random.fold_in(key, 3), (b, s, shared))
    got = rope_attention(q, k, v, n_heads=h, rope=False, k_shared=kpe)
    assert got.shape == (b, s, h * dv)
    full = jnp.concatenate([k.reshape(b, s, h, own), jnp.broadcast_to(
        kpe[:, :, None], (b, s, h, shared))], -1)
    heads = lambda t: t.reshape(b, s, h, -1).swapaxes(1, 2)
    want = attention(heads(q), full.swapaxes(1, 2), heads(v), causal=True)
    np.testing.assert_allclose(got, want.swapaxes(1, 2).reshape(b, s, -1),
                               rtol=1e-5, atol=1e-6)


# --------------------------------------------------------------------------- #
# rope_attention where a head is whole vregs of lanes: q, k and v stay
# (B, S, H·Dh) from the projections to the kernels (rotation, key-value
# repeat and shared key part along the lanes), against the head-major route
# --------------------------------------------------------------------------- #

# name: (heads, kv heads, Dh, Dv, rotary_dims, rope, window, shared)
LANE_CASES = {
    "mha": (2, 2, 128, 128, 0, True, 0, 0),
    "no_positions": (2, 2, 128, 128, 0, False, 0, 0),
    "partial_rotary": (2, 1, 128, 128, 64, True, 0, 0),
    "grouped": (4, 2, 128, 128, 0, True, 0, 0),
    "grouped_window": (4, 1, 128, 128, 0, True, 24, 0),
    "grouped_window_no_positions": (4, 2, 128, 128, 0, False, 24, 0),
    "values_wider": (2, 1, 128, 256, 32, True, 0, 0),
    "shared_key_part": (2, 2, 256, 128, 64, True, 0, 64),
}


@pytest.mark.parametrize("kernels", ["dense", "flash"])
@pytest.mark.parametrize("case", sorted(LANE_CASES))
def test_rope_attention_lanes_route_matches_head_major(case, kernels,
                                                       monkeypatch):
    """``rope_attention`` takes the token-major route at lane-aligned head
    widths (``flash_operand_form``); turned off, the same call takes the
    head-major one: outputs and the gradients of every operand agree, with
    the dense op and with the interpreted kernels under both."""
    from poseidon_tpu.models import transformer as tr
    h, g, d, dv, rot, rope, window, shared = LANE_CASES[case]
    b, s = 2, 64
    key = jax.random.split(jax.random.PRNGKey(len(case)), 5)
    q = jax.random.normal(key[0], (b, s, h * d))
    k = jax.random.normal(key[1], (b, s, g * (d - shared)))
    v = jax.random.normal(key[2], (b, s, g * dv))
    co = jax.random.normal(key[3], (b, s, h * dv))
    kpe = jax.random.normal(key[4], (b, s, shared)) if shared else None
    forms = []
    if kernels == "flash":
        def att(q_, k_, v_, causal, scale=None, window=None, heads=None):
            forms.append(heads)
            return pk.flash_attention(q_, k_, v_, causal, scale, 32, 16,
                                      True, window, heads)
        monkeypatch.setattr(tr, "maybe_flash_attention", att)

    def run():
        f = lambda q_, k_, v_, kpe_: tr.rope_attention(      # noqa: E731
            q_, k_, v_, h, 1e4, g, rot, window, rope, kpe_)
        out, vjp = jax.vjp(f, q, k, v, kpe)
        return (out,) + vjp(co)[:4 if shared else 3]

    assert tr.flash_operand_form(s, d, dv)[0]
    got = run()
    monkeypatch.setattr(tr, "flash_operand_form",
                        lambda *a: (False, "operands head-major (test)"))
    want = run()
    if kernels == "flash":              # both routes were taken
        assert h in forms and None in forms
    assert got[0].shape == (b, s, h * dv)
    for a, w in zip(got, want):
        assert a.shape == w.shape
        np.testing.assert_allclose(a, w, rtol=2e-5, atol=2e-5)


def test_rope_attention_keeps_narrow_heads_head_major(monkeypatch):
    """Heads that are not whole vregs of lanes (192 / 128, 64) go to the
    kernels head-major, as they always did."""
    from poseidon_tpu.models import transformer as tr
    seen = []

    def att(q_, k_, v_, causal, scale=None, window=None, heads=None):
        seen.append((q_.shape, heads))
        return attention(q_, k_, v_, causal=causal, scale=scale,
                         window=window)
    monkeypatch.setattr(tr, "maybe_flash_attention", att)
    x = jnp.ones((1, 32, 2 * 192))
    tr.rope_attention(x, x[..., :2 * 128], x[..., :2 * 128], 2,
                      rotary_dims=64, k_shared=x[..., :64])
    tr.rope_attention(x[..., :128], x[..., :128], x[..., :128], 2)
    assert seen == [((1, 2, 32, 192), None), ((1, 2, 32, 64), None)]


@pytest.mark.parametrize("s,n,d,rot", [
    (256, 2, 128, 128),     # two table rows of 128 positions
    (384, 3, 128, 64),      # partial rotary, three
    (192, 2, 128, 128),     # 128 does not divide S: periods of 64
    (136, 1, 256, 32),      # periods of 8
    (64, 4, 128, 128),      # one period: the low table alone
])
def test_rope_along_the_lanes_is_apply_rope(s, n, d, rot):
    """``_rope_lanes`` on (B, S, n·Dh), its cos / sin from the angle sums
    of a (S / P, n·Dh) and a (P, n·Dh) table, against ``apply_rope`` on
    (B, n, S, Dh) with the (S, Dh) tables: values to f32 rounding, and the
    hand-written backward (the rotation the other way) against autodiff's."""
    from poseidon_tpu.models import transformer as tr
    b = 2
    x = jax.random.normal(jax.random.PRNGKey(s), (b, s, n * d))
    g = jax.random.normal(jax.random.PRNGKey(s + 1), (b, s, n * d))
    cos, sin = tr.rope_tables(s, rot, 1e4)

    def by_head(x_):
        t = x_.reshape(b, s, n, d).swapaxes(1, 2)
        t = jnp.concatenate([tr.apply_rope(t[..., :rot], cos, sin),
                             t[..., rot:]], axis=-1)
        return t.swapaxes(1, 2).reshape(b, s, n * d)

    want, want_vjp = jax.vjp(by_head, x)
    got, got_vjp = jax.vjp(lambda x_: tr._rope_lanes(x_, n, rot, 1e4), x)
    np.testing.assert_allclose(got, want, rtol=0, atol=2e-6)
    np.testing.assert_allclose(got_vjp(g)[0], want_vjp(g)[0], rtol=0,
                               atol=2e-6)
    # bf16 in, bf16 out: the same rounding of the same f32 values
    xb = x.astype(jnp.bfloat16)
    assert tr._rope_lanes(xb, n, rot, 1e4).dtype == jnp.bfloat16
    np.testing.assert_allclose(
        tr._rope_lanes(xb, n, rot, 1e4).astype(jnp.float32),
        by_head(xb).astype(jnp.float32), rtol=0, atol=2e-2)


# --------------------------------------------------------------------------- #
# rotary_shared (latent attention's decoupled positions): the shared key
# part turns once a token, q's heads turn their LAST dims; against a
# reference written head by head
# --------------------------------------------------------------------------- #

# name: (heads, Dh, Dv, shared = rotating dims, S)
ROTARY_SHARED_CASES = {
    "head_major": (4, 24, 16, 8, 32),          # narrow heads: transposed
    "token_major": (2, 256, 256, 64, 64),      # GLM's 192 + 64 / 256
    "token_major_values_narrow": (3, 128, 128, 32, 192),   # periods of 64
}


def _per_head_rotary_shared(q, k, v, kpe, h, theta):
    """Head by head, nothing shared in code with ``rope_attention``: the
    angle of position t and pair (j, j + R/2) is t theta^(-2j/R)."""
    b, s, _ = q.shape
    r = kpe.shape[-1]
    ang = np.arange(s)[:, None] * theta ** (-np.arange(0, r, 2) / r)
    cos, sin = jnp.asarray(np.cos(ang), q.dtype), jnp.asarray(np.sin(ang),
                                                              q.dtype)

    def turn(x):                                   # (B, S, R)
        a, c = x[..., :r // 2], x[..., r // 2:]
        return jnp.concatenate([a * cos - c * sin, c * cos + a * sin], -1)

    d, own, dv = q.shape[-1] // h, k.shape[-1] // h, v.shape[-1] // h
    outs = []
    for i in range(h):
        qh = q[..., i * d:(i + 1) * d]
        qh = jnp.concatenate([qh[..., :d - r], turn(qh[..., d - r:])], -1)
        kh = jnp.concatenate([k[..., i * own:(i + 1) * own], turn(kpe)], -1)
        scores = jnp.einsum("bqd,bkd->bqk", qh, kh) / np.sqrt(d)
        scores = jnp.where(np.tril(np.ones((s, s), bool)), scores, -jnp.inf)
        outs.append(jax.nn.softmax(scores, -1) @ v[..., i * dv:(i + 1) * dv])
    return jnp.concatenate(outs, -1)


@pytest.mark.parametrize("kernels", ["dense", "flash"])
@pytest.mark.parametrize("case", sorted(ROTARY_SHARED_CASES))
def test_rotary_shared_equals_a_per_head_reference(case, kernels,
                                                   monkeypatch):
    """The rotation on the shared key part and on the matching tail of
    every q head, on the head-major and the token-major route, with the
    dense op and the interpreted kernels: outputs and all four operands'
    gradients (the shared part's is the sum over heads, through one
    rotation) equal the per-head reference's."""
    from poseidon_tpu.models import transformer as tr
    h, d, dv, shared, s = ROTARY_SHARED_CASES[case]
    b, theta = 2, 1e6
    key = jax.random.split(jax.random.PRNGKey(len(case)), 5)
    q = jax.random.normal(key[0], (b, s, h * d))
    k = jax.random.normal(key[1], (b, s, h * (d - shared)))
    v = jax.random.normal(key[2], (b, s, h * dv))
    kpe = jax.random.normal(key[3], (b, s, shared))
    co = jax.random.normal(key[4], (b, s, h * dv))
    forms = []
    if kernels == "flash":
        def att(q_, k_, v_, causal, scale=None, window=None, heads=None):
            forms.append(heads)
            return pk.flash_attention(q_, k_, v_, causal, scale, 32, 16,
                                      True, window, heads)
        monkeypatch.setattr(tr, "maybe_flash_attention", att)
    assert tr.flash_operand_form(s, d, dv)[0] == case.startswith("token")
    got, vjp = jax.vjp(lambda *ops: tr.rope_attention(
        *ops[:3], n_heads=h, rope_theta=theta, k_shared=ops[3],
        rotary_shared=True), q, k, v, kpe)
    want, want_vjp = jax.vjp(lambda *ops: _per_head_rotary_shared(
        *ops, h, theta), q, k, v, kpe)
    if kernels == "flash":
        assert forms == [h if case.startswith("token") else None]
    np.testing.assert_allclose(got, want, rtol=3e-5, atol=3e-5)
    for a, w in zip(vjp(co), want_vjp(co)):
        assert a.shape == w.shape
        np.testing.assert_allclose(a, w, rtol=3e-5, atol=3e-5)


def test_rotary_shared_is_not_the_first_dims_rotation():
    """What a net got before ``rotary_shared`` from ``rotary_dims`` = the
    shared width (a head's FIRST dims and every key head's, the shared
    part appended unrotated) is another function: the option is not a
    spelling of it."""
    from poseidon_tpu.models import transformer as tr
    x = jax.random.normal(jax.random.PRNGKey(0), (1, 32, 2 * 24))
    kpe = x[..., :8] * 0.5
    ops = (x, x[..., :2 * 16], x[..., :2 * 16])
    a = tr.rope_attention(*ops, 2, 1e4, rotary_dims=8, k_shared=kpe)
    b = tr.rope_attention(*ops, 2, 1e4, k_shared=kpe, rotary_shared=True)
    assert float(jnp.max(jnp.abs(a - b))) > 1e-2


@pytest.mark.parametrize("s,n,d,rot,at", [
    (256, 20, 256, 64, 192),    # GLM's q: the last 64 of 256, 20 heads
    (192, 2, 128, 32, 96),      # periods of 64
    (64, 1, 64, 64, 0),         # the shared part itself: one head, all of it
    (136, 3, 128, 32, 40),      # a rotating part in a head's middle
])
def test_rope_along_the_lanes_at_an_offset(s, n, d, rot, at):
    """``_rope_lanes`` with ``at``: the ``rot`` dims of every head from dim
    ``at`` on rotate, the others pass; values and the hand-written backward
    against ``apply_rope`` on the slice."""
    from poseidon_tpu.models import transformer as tr
    b = 2
    x = jax.random.normal(jax.random.PRNGKey(s), (b, s, n * d))
    g = jax.random.normal(jax.random.PRNGKey(s + 1), (b, s, n * d))
    cos, sin = tr.rope_tables(s, rot, 1e6)

    def by_head(x_):
        t = x_.reshape(b, s, n, d).swapaxes(1, 2)
        t = jnp.concatenate([t[..., :at],
                             tr.apply_rope(t[..., at:at + rot], cos, sin),
                             t[..., at + rot:]], axis=-1)
        return t.swapaxes(1, 2).reshape(b, s, n * d)

    want, want_vjp = jax.vjp(by_head, x)
    got, got_vjp = jax.vjp(
        lambda x_: tr._rope_lanes(x_, n, rot, 1e6, 1, at), x)
    np.testing.assert_allclose(got, want, rtol=0, atol=2e-6)
    np.testing.assert_allclose(got_vjp(g)[0], want_vjp(g)[0], rtol=0,
                               atol=2e-6)
