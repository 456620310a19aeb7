"""Transformer LM family — the long-context flagship (beyond the reference).

The reference is a 2015 CNN framework; this model family exists because
long-context and distributed are first-class here. A GPT-style decoder built
from the framework's own pieces: ``ops/attention.py`` (or the Pallas flash
kernel) for compute, ``parallel/sequence.py`` for sequence parallelism, the
Caffe-exact solvers for updates. Parameters are a plain pytree like Net's, so
checkpoints/metrics reuse the runtime unchanged.

``build_dp_sp_train_step`` shards batch over the "data" axis and sequence
over the "seq" axis of one 2-D mesh: gradients psum over BOTH axes (every
device holds a full replica of the params), activations of the attention ring
rotate along "seq" only.
"""

from __future__ import annotations

import functools
import math
from dataclasses import dataclass
from typing import Dict, NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax, shard_map
from jax.sharding import Mesh, PartitionSpec as P

from ..config import matmul_precision, policy
from ..core.remat import resolve_lm_policy, wrap_checkpoint
from ..ops.pallas_kernels import (flash_operand_form,
                                  maybe_flash_attention)
from ..parallel.sequence import ring_attention
from ..proto.messages import SolverParameter
from ..solvers.updates import SolverState, make_update_fn


@dataclass(frozen=True)
class TransformerConfig:
    vocab_size: int = 256
    d_model: int = 128
    n_heads: int = 4
    n_layers: int = 2
    d_ff: int = 512
    max_seq: int = 1024
    # rematerialize block activations in the backward pass (jax.checkpoint):
    # HBM drops from O(layers x S x D) stored activations to O(S x D) per
    # live block — the lever that lets long sequences fit. A policy enum
    # (core/remat.REMAT_POLICIES): "none" | "dots_saveable" (keep matmul
    # results, recompute the cheap tissue between them — the measured
    # default) | "nothing_saveable" (save only block inputs, maximal
    # reclaim) | "auto" (follow the RematPlan's row). The legacy
    # bools still work: True means dots_saveable, False means unset.
    remat: "bool | str" = False

    def n_params(self) -> int:
        """Parameter count (embeddings + blocks + head), for FLOPs/MFU."""
        d, f, v, L = self.d_model, self.d_ff, self.vocab_size, self.n_layers
        block = 4 * d * d + 2 * d * f + 4 * d  # qkv+o, ffn, 2 layernorms
        return v * d + self.max_seq * d + v * d + 2 * d + L * block


def gpt_small_config(max_seq: int = 1024,
                     remat: "bool | str" = True) -> "TransformerConfig":
    """The GPT-2-small shape (768d x 12L x 12h) — the LM family's
    performance identity config (round-4 verdict item 4: a model worth
    measuring, not the zoo-default toy). vocab 32768 keeps the embedding
    matmul on MXU tile boundaries (50257 pads to the same tiles with 35%
    waste); with the untied head this totals ~136M params (n_params())."""
    return TransformerConfig(vocab_size=32768, d_model=768, n_heads=12,
                             n_layers=12, d_ff=3072, max_seq=max_seq,
                             remat=remat)


def init_params(cfg: TransformerConfig, rng: jax.Array) -> Dict:
    def dense(key, fan_in, shape):
        return (jax.random.normal(key, shape, jnp.float32)
                * (1.0 / np.sqrt(fan_in)))

    keys = jax.random.split(rng, 4 + 6 * cfg.n_layers)
    params: Dict = {
        "embed": {"w": dense(keys[0], 1, (cfg.vocab_size, cfg.d_model)) * 0.02},
        "pos": {"w": dense(keys[1], 1, (cfg.max_seq, cfg.d_model)) * 0.02},
        "head": {"w": dense(keys[2], cfg.d_model,
                            (cfg.vocab_size, cfg.d_model))},
        "ln_f": {"g": jnp.ones((cfg.d_model,)), "b": jnp.zeros((cfg.d_model,))},
    }
    for i in range(cfg.n_layers):
        k = keys[4 + 6 * i:4 + 6 * (i + 1)]
        params[f"block{i}"] = {
            "wqkv": dense(k[0], cfg.d_model, (3 * cfg.d_model, cfg.d_model)),
            "wo": dense(k[1], cfg.d_model, (cfg.d_model, cfg.d_model)),
            "w1": dense(k[2], cfg.d_model, (cfg.d_ff, cfg.d_model)),
            "w2": dense(k[3], cfg.d_ff, (cfg.d_model, cfg.d_ff)),
            "ln1_g": jnp.ones((cfg.d_model,)),
            "ln1_b": jnp.zeros((cfg.d_model,)),
            "ln2_g": jnp.ones((cfg.d_model,)),
            "ln2_b": jnp.zeros((cfg.d_model,)),
        }
    return params


def _layer_norm(x, g, b, eps=1e-5):
    x32 = x.astype(jnp.float32)
    mu = jnp.mean(x32, axis=-1, keepdims=True)
    var = jnp.mean((x32 - mu) ** 2, axis=-1, keepdims=True)
    return ((x32 - mu) * lax.rsqrt(var + eps) * g + b).astype(x.dtype)


def _dense(x, w):
    p = policy()
    return lax.dot_general(
        x.astype(p.compute_dtype), w.astype(p.compute_dtype),
        (((x.ndim - 1,), (1,)), ((), ())),
        precision=matmul_precision())


# --------------------------------------------------------------------------- #
# The modern block's pieces (RMSNorm, rotary positions, QK-norm): what the
# token layers of core/layers.py wrap, beside the GPT-2 pieces above.
# --------------------------------------------------------------------------- #

def rms_norm(x: jax.Array, g: jax.Array, eps: float = 1e-5) -> jax.Array:
    """x * rsqrt(mean(x^2) + eps) * g over the last axis; statistics and
    the gain in f32, the result back in x's dtype."""
    x32 = x.astype(jnp.float32)
    var = jnp.mean(x32 * x32, axis=-1, keepdims=True)
    return (x32 * lax.rsqrt(var + eps) * g.astype(jnp.float32)) \
        .astype(x.dtype)


class Yarn(NamedTuple):
    """YaRN's blended rotary frequencies in the DeepSeek-V3 form (a
    ``rope_scaling`` block of type "yarn"): what ``rope_frequencies`` takes
    in plain theta's place. Hashable: it rides ``_rope_lanes``' static
    arguments."""
    theta: float
    factor: float
    original_positions: int = 4096
    beta_fast: float = 32.0
    beta_slow: float = 1.0


def rope_frequencies(rot: int, theta) -> np.ndarray:
    """The ``rot / 2`` rotary frequencies of a head, on the host (float64).
    ``theta`` a number: f_i = theta^(-2i/rot), nothing else. ``theta`` a
    :class:`Yarn`: with c(b) = rot ln(P / (2 pi b)) / (2 ln theta) the pair
    that makes b turns over the P original positions, low = floor(c(
    beta_fast)), high = ceil(c(beta_slow)) (both inside 0 .. rot/2 - 1),
    m_i = 1 - clip((i - low) / (high - low), 0, 1), pair i turns by
    f_i m_i + (f_i / factor) (1 - m_i): the fast pairs as they were, the
    slow ones ``factor`` times slower, a linear blend between. cos and sin
    take no factor (a model's mscale belongs to the scores' scale). A
    ``factor`` of 1 is plain theta."""
    yarn = isinstance(theta, Yarn)
    inv = 1.0 / (theta.theta if yarn else theta) ** (np.arange(0, rot, 2)
                                                     / rot)
    if not yarn or theta.factor == 1:
        return inv

    def pair(turns):
        return rot * math.log(theta.original_positions
                              / (2 * math.pi * turns)) \
            / (2 * math.log(theta.theta))

    low = max(math.floor(pair(theta.beta_fast)), 0)
    high = min(math.ceil(pair(theta.beta_slow)), rot // 2 - 1)
    keep = 1.0 - np.clip((np.arange(rot // 2) - low)
                         / max(high - low, 1e-3), 0.0, 1.0)
    return inv / theta.factor * (1.0 - keep) + inv * keep


def rope_tables(seq: int, d_head: int, theta=10000.0):
    """(cos, sin), each (seq, d_head) f32, of the rotate-half convention:
    frequency i serves dims i and i + d_head/2. ``theta``: a number, or a
    :class:`Yarn` for its blended frequencies (``rope_frequencies``)."""
    inv = rope_frequencies(d_head, theta)                    # host numpy
    ang = np.arange(seq)[:, None] * inv[None, :]
    ang = np.concatenate([ang, ang], axis=-1)
    return (jnp.asarray(np.cos(ang), jnp.float32),
            jnp.asarray(np.sin(ang), jnp.float32))


def apply_rope(x: jax.Array, cos: jax.Array, sin: jax.Array) -> jax.Array:
    """Rotate-half RoPE on x (B, H, S, Dh): x*cos + rotate_half(x)*sin,
    in f32, back in x's dtype."""
    x32 = x.astype(jnp.float32)
    half = x.shape[-1] // 2
    rot = jnp.concatenate([-x32[..., half:], x32[..., :half]], axis=-1)
    return (x32 * cos + rot * sin).astype(x.dtype)


def rope_attention(q: jax.Array, k: jax.Array, v: jax.Array, n_heads: int,
                   rope_theta=10000.0, n_kv_heads: int = 0,
                   rotary_dims: int = 0, window: int = 0,
                   rope: bool = True,
                   k_shared: Optional[jax.Array] = None,
                   scale: Optional[float] = None,
                   rotary_shared: bool = False) -> jax.Array:
    """q (B, S, D), k and v (B, S, Dkv) -> (B, S, D): q split into
    ``n_heads`` heads, k and v into ``n_kv_heads`` of the same width (0 =
    ``n_heads``, Dkv = D), rotary positions on q and k, causal
    softmax(q k^T / sqrt(Dh)) v, heads merged. With fewer key-value heads
    query head h reads key-value head h // (n_heads / n_kv_heads): k and v
    are repeated to the query heads before the kernel (their gradients sum
    in autodiff).
    ``rotary_dims`` (0 = the whole head): only the first that many dims of
    a head rotate, the rest pass; ``rope`` false: nothing rotates, the
    layer has no positions. ``rope_theta``: a number, or a :class:`Yarn`
    for its blended frequencies (``rope_frequencies``). ``window`` (0 = none): token t attends to s with
    t - window < s <= t. ``scale`` (None: 1 / sqrt(Dh)): what multiplies
    q k^T. The Pallas flash kernel where the sequence
    tiles (``maybe_flash_attention``), the dense op elsewhere.

    Where a head is whole vregs of lanes (``flash_operand_form``: widths
    that are multiples of 128) nothing here moves a head: q, k and v stay
    (B, S, H·Dh) through the rotation and the repeat
    (``_rope_attention_lanes``), the kernels read them where the projections
    left them and write (B, S, H·Dv) where the out-projection reads it.
    Elsewhere q, k and v are transposed to (B, H, S, Dh) first and the
    result back, each an HBM copy of the activation on the TPU.

    v's heads may have a width of their own (Dv / n_kv: the result is then
    (B, S, n_heads * that)). ``k_shared`` (B, S, Ds): a key part that every
    head shares; k's heads are then d_head - Ds wide and each key head is
    its own dims followed by it, repeated to the heads before the kernel
    as key-value heads are. ``rotary_shared``: the positions are on that
    shared part, rotated once a token (Ds lanes) before the heads take it,
    and on the LAST Ds dims of every q head; nothing else rotates (on the
    head-major form, Xing4.0's 192 / 128, after the head split; on the
    lanes form where q lies)."""
    b, s, d = q.shape
    d_head = d // n_heads
    n_kv = n_kv_heads or n_heads
    rot = rotary_dims or d_head
    if rotary_shared:
        rot = k_shared.shape[-1]
    if flash_operand_form(s, d_head, v.shape[-1] // n_kv)[0]:
        return _rope_attention_lanes(q, k, v, n_heads, n_kv, rot,
                                     rope_theta if rope else None, window,
                                     k_shared, scale, rotary_shared)

    def heads(t, n):
        return t.reshape(b, s, n, -1).swapaxes(1, 2)

    cos, sin = rope_tables(s, rot, rope_theta) if rope else (None, None)

    def rotate(t):
        if not rope:
            return t
        if rot == d_head:
            return apply_rope(t, cos, sin)
        return jnp.concatenate([apply_rope(t[..., :rot], cos, sin),
                                t[..., rot:]], axis=-1)

    def key_heads(t):
        t = heads(t, n_kv)
        if k_shared is None:
            return t
        return jnp.concatenate([t, jnp.broadcast_to(
            k_shared[:, None], (b, n_kv, s, k_shared.shape[-1]))], axis=-1)

    if rotary_shared:
        # the one shared part turns before the heads take it; of q the dims
        # that meet it, a head's last
        k_shared = apply_rope(k_shared, cos, sin)
        q = heads(q, n_heads)
        q = jnp.concatenate([q[..., :d_head - rot], apply_rope(
            q[..., d_head - rot:], cos, sin)], axis=-1)
        k = key_heads(k)
    else:
        q, k = rotate(heads(q, n_heads)), rotate(key_heads(k))
    v = heads(v, n_kv)
    if n_kv != n_heads:
        k, v = (jnp.repeat(t, n_heads // n_kv, axis=1) for t in (k, v))
    att = maybe_flash_attention(q, k, v, causal=True, window=window,
                                scale=scale)
    return att.swapaxes(1, 2).reshape(b, s, n_heads * v.shape[-1])


@functools.partial(jax.custom_vjp, nondiff_argnums=(1, 2, 3, 4, 5))
def _rope_lanes(x: jax.Array, n: int, rot: int, theta,
                turn: int = 1, at: int = 0) -> jax.Array:
    """``apply_rope`` on x (B, S, n·Dh) as it lies, its ``n`` heads side by
    side along the lanes and the ``rot`` dims of each from dim ``at`` on
    (0: its first) rotating, by
    ``turn`` (1, or -1: the other way, which is the rotation's transpose
    and so its backward). Nothing is reshaped to (B, S, n, Dh), which under
    the TPU's (8, 128) tiling is another layout and a copy of x each way:
    a dim's rotate-half partner is ``rot / 2`` lanes to its right or left.
    ``theta`` is what ``rope_frequencies`` takes: a number, or a ``Yarn``.

    The tables are the problem of this form: (S, Dh) ones do not broadcast
    along the lanes of n heads, and (S, n·Dh) ones are n times the constant
    and the traffic. So position t = hi·P + lo (P = 128 where it divides S)
    and cos / sin (t f) come from the angle sums of two tables with all the
    heads' lanes, (S / P, n·Dh) and (P, n·Dh), a few hundred KB each, which
    broadcast over the rows of x seen as (B, S / P, P, n·Dh): a bitcast,
    the row tiles stay whole. The products are f32, as the tables were."""
    b, s, width = x.shape
    d_head, half = width // n, rot // 2
    period = math.gcd(s, 128)
    inv = rope_frequencies(rot, theta)                       # host numpy
    # a head's lanes: frequency i serves dims i and i + rot/2, none past
    # the rotating dims (angle 0: cos 1, sin 0, the dim passes);
    # rotate_half's sign goes with the sine
    rest = [np.zeros(at), np.zeros(d_head - rot - at)]
    freq = np.tile(np.concatenate([rest[0], inv, inv, rest[1]]), n)
    sign = np.tile(np.concatenate([rest[0], -np.ones(half), np.ones(half),
                                   rest[1]]), n) * turn
    table = lambda fn, pos, by=1.0: jnp.asarray(
        fn(pos[:, None] * freq) * by, jnp.float32)
    hi, lo = np.arange(0, s, period), np.arange(period)
    cos_hi, sin_hi = (table(fn, hi)[:, None] for fn in (np.cos, np.sin))
    cos_lo, sin_lo = table(np.cos, lo), table(np.sin, lo)
    cos_lo_s, sin_lo_s = table(np.cos, lo, sign), table(np.sin, lo, sign)
    # between two barriers: the projection's matmul must not take a
    # product with a table into its own fusion (it would write it out in
    # f32, twice x's bytes, for the fusion below to read back), and the
    # compiler must not move the reshapes inward: against (B, S, n·Dh) the
    # tables do not broadcast, and it builds each at x's size instead
    x = lax.optimization_barrier(x).reshape(b, s // period, period, width)
    first = lax.broadcasted_iota(jnp.int32, (1, 1, 1, width), 3) % d_head
    # the lanes whose partner is to their right: the rotating dims' first
    # half (a dim outside them meets sine 0 whichever it is handed)
    first = first < half if not at else (first >= at) & (first < at + half)
    right = jnp.roll(x, half, axis=-1)                       # x[l - half]
    partner = jnp.where(first, jnp.roll(right, -rot, axis=-1), right)
    x32, p32 = x.astype(jnp.float32), partner.astype(jnp.float32)
    # x cos(hi + lo) + partner sin(hi + lo), each product starting from x
    # or its partner: a sum of tables alone would be an (S, n·Dh) f32 array
    # for the compiler to build once a layer and keep
    out = (x32 * cos_hi) * cos_lo - (x32 * sin_hi) * sin_lo \
        + (p32 * sin_hi) * cos_lo_s + (p32 * cos_hi) * sin_lo_s
    return lax.optimization_barrier(out.astype(x.dtype)).reshape(b, s, width)


def _rope_lanes_fwd(x, n, rot, theta, turn, at):
    return _rope_lanes(x, n, rot, theta, turn, at), None


def _rope_lanes_bwd(n, rot, theta, turn, at, _, g):
    return (_rope_lanes(g, n, rot, theta, -turn, at),)


_rope_lanes.defvjp(_rope_lanes_fwd, _rope_lanes_bwd)


def _repeat_lanes(t: jax.Array, n: int, times: int) -> jax.Array:
    """(B, S, n·Dh) -> (B, S, n·times·Dh): each of the ``n`` heads side by
    side along the lanes ``times`` times over, head h of the result being
    head h // times (``jnp.repeat`` along the head axis, without one)."""
    d_head = t.shape[-1] // n
    return jnp.concatenate(
        [t[..., i * d_head:(i + 1) * d_head]
         for i in range(n) for _ in range(times)], axis=-1)


def _rope_attention_lanes(q, k, v, n_heads: int, n_kv: int, rot: int,
                          theta: Optional[float], window: int, k_shared,
                          scale: Optional[float] = None,
                          rotary_shared: bool = False):
    """``rope_attention`` where a head is whole vregs of lanes: q, k and v
    stay (B, S, n·Dh) from the projections to the kernels, which address a
    head as a lane block, and the result is (B, S, n_heads·Dv) as the
    out-projection reads it. ``theta`` None: no positions.

    A shared key part is joined to the heads ALONG THE LANES: the kernels'
    k is written once, each head's own dims followed by the shared part,
    one concatenate of lane slices as ``_repeat_lanes``' is (its transpose,
    the backward, sums the heads' slices of dk into the shared part's
    gradient in the same pass). The write itself stays: the kernels read a
    key head as one lane block of k (a K index map that reads the shared
    part from its own array would save it, ROADMAP M4). With
    ``rotary_shared`` (GLM-4.7-Flash: 20 heads of [192 ; 64] / 256) that
    part rotates first, ``rot`` lanes a token, and q's heads turn their
    last ``rot`` dims where they lie; otherwise q and the joined k turn
    their heads' first ``rot`` dims."""
    if rotary_shared:
        k_shared = _rope_lanes(k_shared, 1, rot, theta)
        q = _rope_lanes(q, n_heads, rot, theta, 1,
                        q.shape[-1] // n_heads - rot)
    if k_shared is not None:
        own = k.shape[-1] // n_kv
        k = jnp.concatenate(
            [part for i in range(n_kv)
             for part in (k[..., i * own:(i + 1) * own], k_shared)], axis=-1)
    if theta is not None and not rotary_shared:
        q, k = _rope_lanes(q, n_heads, rot, theta), \
            _rope_lanes(k, n_kv, rot, theta)
    if n_kv != n_heads:
        k, v = (_repeat_lanes(t, n_kv, n_heads // n_kv) for t in (k, v))
    return maybe_flash_attention(q, k, v, causal=True, window=window,
                                 heads=n_heads, scale=scale)


def attention_sublayer(cfg: TransformerConfig, x: jax.Array, blk: Dict,
                       *, seq_axis: Optional[str] = None) -> jax.Array:
    """ln1 -> fused qkv -> (flash | ring) attention -> wo residual. Shared
    by the dense block and the MoE block (models/moe.py), which differ only
    in their FFN sublayer."""
    b, s, _ = x.shape
    h = _layer_norm(x, blk["ln1_g"], blk["ln1_b"])
    qkv = _dense(h, blk["wqkv"])  # (B, S, 3*D)
    d_head = cfg.d_model // cfg.n_heads
    qkv = qkv.reshape(b, s, 3, cfg.n_heads, d_head)
    q, k, v = (qkv[:, :, j].swapaxes(1, 2) for j in range(3))  # (B,H,S,Dh)
    if seq_axis is None:
        # Pallas flash kernel when the sequence tiles cleanly (O(S)
        # memory, never materializes S x S scores in HBM)
        att = maybe_flash_attention(q, k, v, causal=True)
    else:
        att = ring_attention(q, k, v, seq_axis, causal=True)
    att = att.swapaxes(1, 2).reshape(b, s, cfg.d_model)
    return x + _dense(att, blk["wo"]).astype(x.dtype)


def ffn_sublayer(x: jax.Array, blk: Dict) -> jax.Array:
    """ln2 -> gelu FFN -> residual. Shared by the dense block and the
    KV-cached decode block (models/generate.py)."""
    h = _layer_norm(x, blk["ln2_g"], blk["ln2_b"])
    ff = _dense(jax.nn.gelu(_dense(h, blk["w1"])), blk["w2"])
    return x + ff.astype(x.dtype)


def block_forward(cfg: TransformerConfig, x: jax.Array, blk: Dict,
                  *, seq_axis: Optional[str] = None) -> jax.Array:
    """One decoder block: attention sublayer + gelu FFN residual. The
    single definition of the block math — forward() and the pipeline path
    both call it (the tp path differs structurally via its f/g
    collectives)."""
    return ffn_sublayer(attention_sublayer(cfg, x, blk, seq_axis=seq_axis),
                        blk)


def embed_tokens(params: Dict, tokens: jax.Array,
                 pos_offset: jax.Array | int = 0) -> jax.Array:
    """Token + positional embedding — the model-entry scaffold shared by
    the dense and MoE forwards."""
    positions = pos_offset + jnp.arange(tokens.shape[-1])
    return params["embed"]["w"][tokens] + params["pos"]["w"][positions]


def lm_head(params: Dict, x: jax.Array) -> jax.Array:
    """Final layer norm + vocabulary projection (f32 logits) — the
    model-exit scaffold shared by the dense and MoE forwards."""
    x = _layer_norm(x, params["ln_f"]["g"], params["ln_f"]["b"])
    return _dense(x, params["head"]["w"]).astype(jnp.float32)


def forward(params: Dict, cfg: TransformerConfig, tokens: jax.Array,
            *, seq_axis: Optional[str] = None,
            pos_offset: jax.Array | int = 0,
            remat_policy: Optional[str] = None) -> jax.Array:
    """tokens (B, S_local) -> logits (B, S_local, V). With ``seq_axis``,
    attention runs as a ring over that mesh axis; everything else is local.

    ``remat_policy`` is a plan-side override (the RematPlan's row); it
    resolves against ``cfg.remat`` via
    ``core/remat.resolve_lm_policy`` — an explicit config flag that
    contradicts a concrete plan value refuses loudly."""
    x = embed_tokens(params, tokens, pos_offset)

    def block(x, blk):
        return block_forward(cfg, x, blk, seq_axis=seq_axis)

    # policy-driven checkpoint: dots_saveable keeps matmul results and
    # recomputes the elementwise/softmax tissue; nothing_saveable keeps
    # only each block's input (scores, probabilities, ffn intermediates
    # all recompute during backward)
    block = wrap_checkpoint(block, resolve_lm_policy(cfg.remat,
                                                     remat_policy))
    for i in range(len([k for k in params if k.startswith("block")])):
        x = block(x, params[f"block{i}"])
    return lm_head(params, x)


def lm_loss(logits: jax.Array, targets: jax.Array) -> jax.Array:
    logp = jax.nn.log_softmax(logits, axis=-1)
    picked = jnp.take_along_axis(logp, targets[..., None], axis=-1)[..., 0]
    return -jnp.mean(picked)


def transformer_mults(params) -> Dict:
    return {lname: {p: (1.0, 1.0 if p.startswith("w") else 0.0)
                    for p in lp}
            for lname, lp in params.items()}


def build_dp_sp_train_step(cfg: TransformerConfig, sp: SolverParameter,
                           mesh: Mesh, data_axis: str = "data",
                           seq_axis: str = "seq", donate: bool = True):
    """Training step over a 2-D (data x seq) mesh.

    tokens/targets come in (B_global, S_global); each device sees
    (B/data, S/seq). The causal shift happens host-side (targets =
    tokens[:, 1:]); gradients psum over both axes; params stay replicated.
    """
    def device_step(params, state: SolverState, tokens, targets, rng):
        seq_ix = lax.axis_index(seq_axis)
        s_local = tokens.shape[1]

        def loss_fn(p):
            logits = forward(p, cfg, tokens, seq_axis=seq_axis,
                             pos_offset=seq_ix * s_local)
            return lm_loss(logits, targets)

        loss, grads = jax.value_and_grad(loss_fn)(params)
        grads = jax.tree_util.tree_map(
            lambda g: lax.pmean(lax.pmean(g, data_axis), seq_axis), grads)
        upd = make_update_fn(sp, transformer_mults(params))
        new_params, new_state = upd(params, grads, state)
        metrics = {"loss": lax.pmean(lax.pmean(loss, data_axis), seq_axis)}
        return new_params, new_state, metrics

    sharded = shard_map(
        device_step, mesh=mesh,
        in_specs=(P(), P(), P(data_axis, seq_axis), P(data_axis, seq_axis),
                  P()),
        out_specs=(P(), P(), P()),
        check_vma=False)
    return jax.jit(sharded, donate_argnums=(0, 1) if donate else ())


# --------------------------------------------------------------------------- #
# Tensor parallelism (Megatron-style): dp x tp over a ("data", "model") mesh
# --------------------------------------------------------------------------- #


def _check_tp_divisibility(cfg: TransformerConfig, mesh: Mesh,
                           tp_axis: str) -> None:
    n_tp = dict(zip(mesh.axis_names, mesh.devices.shape))[tp_axis]
    if cfg.n_heads % n_tp or cfg.d_ff % n_tp:
        raise ValueError(
            f"n_heads={cfg.n_heads} and d_ff={cfg.d_ff} must both divide "
            f"by the {n_tp} tensor-parallel ranks of axis {tp_axis!r}")


def make_fg_ops(tp_axis: str):
    """Megatron's conjugate collective pair as custom_vjps. ``f`` is
    identity-forward / psum-backward (placed at each column-parallel
    region's input); ``g`` is psum-forward / identity-backward (placed
    after each row-parallel matmul). A raw lax.psum must not sit in the
    differentiated path: its autodiff transpose is another psum, which
    multiplies an already-replicated cotangent by the rank count
    (measured: 4x per crossed psum on a 4-way tp mesh)."""

    @jax.custom_vjp
    def f_op(x):
        return x

    def _f_fwd(x):
        return x, None

    def _f_bwd(_, g):
        return (lax.psum(g, tp_axis),)

    f_op.defvjp(_f_fwd, _f_bwd)

    @jax.custom_vjp
    def g_op(x):
        return lax.psum(x, tp_axis)

    def _g_fwd(x):
        return lax.psum(x, tp_axis), None

    def _g_bwd(_, ct):
        return (ct,)

    g_op.defvjp(_g_fwd, _g_bwd)
    return f_op, g_op


def tp_block_forward(cfg: TransformerConfig, x: jax.Array, blk: Dict,
                     f_op, g_op, *,
                     seq_axis: Optional[str] = None) -> jax.Array:
    """One decoder block with tensor-parallel weights: this rank's head
    slices + FFN columns, partial outputs restored by ``g_op``'s psum.
    Shared by the dp x tp step and the 3-D dp x pp x tp step. With
    ``seq_axis``, attention over this rank's heads runs as a ring over
    that mesh axis (the long-context Megatron + sequence-parallel combo:
    heads split over tp, K/V chunks rotate over sp — the two compose
    orthogonally because the ring never crosses heads)."""
    b, s, _ = x.shape
    dh = cfg.d_model // cfg.n_heads
    h = f_op(_layer_norm(x, blk["ln1_g"], blk["ln1_b"]))
    qkv = _dense(h, blk["wqkv"])          # (B, S, Hl*3*dh)
    hl = qkv.shape[-1] // (3 * dh)        # local heads on this rank
    qkv = qkv.reshape(b, s, hl, 3, dh)
    q, k, v = (qkv[:, :, :, j].swapaxes(1, 2) for j in range(3))
    if seq_axis is None:
        att = maybe_flash_attention(q, k, v, causal=True)
    else:
        att = ring_attention(q, k, v, seq_axis, causal=True)
    att = att.swapaxes(1, 2).reshape(b, s, hl * dh)
    # row-parallel wo: partial product, summed across ranks
    part = _dense(att, blk["wo"])
    x = x + g_op(part).astype(x.dtype)
    h = f_op(_layer_norm(x, blk["ln2_g"], blk["ln2_b"]))
    ff_part = _dense(jax.nn.gelu(_dense(h, blk["w1"])), blk["w2"])
    return x + g_op(ff_part).astype(x.dtype)


def to_tp_layout(params: Dict, cfg: TransformerConfig) -> Dict:
    """Rearrange each block's fused qkv weight from [q-heads; k-heads;
    v-heads] row order to HEAD-major [(q,k,v) of head 0; (q,k,v) of head 1;
    ...]: a contiguous row split over the "model" axis then gives every
    rank the full q/k/v of its own heads (the Megatron column-parallel
    layout). All other leaves are unchanged; ``from_tp_layout`` inverts."""
    dh = cfg.d_model // cfg.n_heads
    out = {k: dict(v) for k, v in params.items()}
    for lname, lp in out.items():
        if lname.startswith("block"):
            w = lp["wqkv"].reshape(3, cfg.n_heads, dh, cfg.d_model)
            lp["wqkv"] = jnp.transpose(w, (1, 0, 2, 3)).reshape(
                3 * cfg.d_model, cfg.d_model)
    return out


def from_tp_layout(params: Dict, cfg: TransformerConfig) -> Dict:
    dh = cfg.d_model // cfg.n_heads
    out = {k: dict(v) for k, v in params.items()}
    for lname, lp in out.items():
        if lname.startswith("block"):
            w = lp["wqkv"].reshape(cfg.n_heads, 3, dh, cfg.d_model)
            lp["wqkv"] = jnp.transpose(w, (1, 0, 2, 3)).reshape(
                3 * cfg.d_model, cfg.d_model)
    return out


def tp_param_specs(params: Dict, tp_axis: str = "model") -> Dict:
    """PartitionSpec pytree mirroring ``params`` (in TP layout): attention
    qkv and FFN w1 column-split, wo and w2 row-split, everything else
    (embedding, positions, head, layer norms) replicated."""
    specs: Dict = {}
    for lname, lp in params.items():
        if lname.startswith("block"):
            specs[lname] = {
                "wqkv": P(tp_axis, None),   # head-major rows (to_tp_layout)
                "wo": P(None, tp_axis),     # input dim is head-major
                "w1": P(tp_axis, None),
                "w2": P(None, tp_axis),
                "ln1_g": P(), "ln1_b": P(), "ln2_g": P(), "ln2_b": P(),
            }
        else:
            specs[lname] = {k: P() for k in lp}
    return specs


def build_dp_tp_train_step(cfg: TransformerConfig, sp: SolverParameter,
                           mesh: Mesh, params: Dict,
                           data_axis: str = "data",
                           tp_axis: str = "model",
                           seq_axis: Optional[str] = None,
                           donate: bool = True,
                           remat_policy: Optional[str] = None):
    """Training step over a 2-D (data x model) mesh — Megatron-style tensor
    parallelism built on XLA collectives instead of hand-written NCCL
    groups (the reference's distributed substrate, SURVEY §2.3; TP itself
    is beyond the 2015 reference, first-class here per the long-context /
    distributed mandate).

    Per block, each tp rank holds n_heads/T full (q,k,v) head slices
    (column-parallel wqkv in head-major layout — ``to_tp_layout``), runs
    attention on its own heads, and contributes a partial output through
    its wo row shard; one psum over ``tp_axis`` restores the replicated
    residual stream. The FFN splits the same way (w1 columns, w2 rows, one
    psum). Embedding/positions/head/layer-norms stay replicated; the
    residual stream is replicated on every rank, so the loss is too.

    Gradient flow uses Megatron's f/g conjugate operators: ``g`` is the
    forward psum after each row-parallel matmul (its autodiff backward is
    the identity — every rank receives the full cotangent), and ``f`` is
    an identity-forward / psum-backward custom_vjp at each column-parallel
    region's INPUT, so the cotangent reaching the replicated residual
    stream is the full sum over ranks, not a per-rank partial. With both
    in place every replicated leaf's gradient is bit-identical on all tp
    ranks (no post-hoc psum — a naive one double-counts the residual-path
    contributions, which are computed in full on every rank), and each
    sharded leaf's gradient is complete locally. Everything then pmeans
    over ``data_axis``. Pass params through ``to_tp_layout`` first
    (``params`` is used for the spec pytree only — the step still takes
    params positionally); the sharding is published via
    ``tp_param_specs``.

    With ``seq_axis`` this becomes dp x sp x tp (the long-context 3-D
    combo): tokens additionally shard over ``seq_axis``, each rank's local
    heads attend via the sequence ring, and gradients pmean over the seq
    axis too (it is a second data-like axis for every leaf — tp-sharded
    leaves are replicated across it, replicated leaves' f/g-summed grads
    differ per seq shard)."""
    specs = tp_param_specs(params, tp_axis)
    _check_tp_divisibility(cfg, mesh, tp_axis)
    f_op, g_op = make_fg_ops(tp_axis)

    def block_tp(x, blk):
        return tp_block_forward(cfg, x, blk, f_op, g_op, seq_axis=seq_axis)

    lm_policy = resolve_lm_policy(cfg.remat, remat_policy)

    def forward_tp(p, tokens, pos_offset):
        x = embed_tokens(p, tokens, pos_offset)
        blk_fn = wrap_checkpoint(block_tp, lm_policy)
        for i in range(cfg.n_layers):
            x = blk_fn(x, p[f"block{i}"])
        return lm_head(p, x)

    def device_step(p, state: SolverState, tokens, targets, rng):
        if seq_axis is None:
            pos_offset = 0
        else:
            pos_offset = lax.axis_index(seq_axis) * tokens.shape[1]

        def loss_fn(pp):
            return lm_loss(forward_tp(pp, tokens, pos_offset), targets)

        loss, grads = jax.value_and_grad(loss_fn)(p)
        # replicated leaves' grads are already full on every tp rank (the
        # f/g operators did the cross-rank sums in backward); sharded
        # leaves' grads are complete locally — the data-like axes remain
        def sync(g):
            g = lax.pmean(g, data_axis)
            return g if seq_axis is None else lax.pmean(g, seq_axis)
        grads = jax.tree_util.tree_map(sync, grads)
        upd = make_update_fn(sp, transformer_mults(p))
        new_params, new_state = upd(p, grads, state)
        metrics = {"loss": sync(loss)}
        return new_params, new_state, metrics

    tok_spec = (P(data_axis) if seq_axis is None
                else P(data_axis, seq_axis))
    state_spec = SolverState(it=P(), history=specs)
    sharded = shard_map(
        device_step, mesh=mesh,
        in_specs=(specs, state_spec, tok_spec, tok_spec, P()),
        out_specs=(specs, state_spec, P()),
        check_vma=False)
    return jax.jit(sharded, donate_argnums=(0, 1) if donate else ())


# --------------------------------------------------------------------------- #
# Pipeline parallelism (GPipe-style): dp x pp over a ("data", "stage") mesh
# --------------------------------------------------------------------------- #


def to_pp_layout(params: Dict, cfg: TransformerConfig) -> Dict:
    """Stack the per-block leaves along a leading layer axis so a contiguous
    split over the "stage" mesh axis gives each stage its run of layers:
    ``{"block0": {...}, "block1": {...}}`` becomes ``{"blocks": {leaf:
    [n_layers, ...]}}``. Embed/pos/head/ln_f pass through (replicated; only
    the first/last stage's copies carry gradient). ``from_pp_layout``
    inverts."""
    out = {k: dict(v) for k, v in params.items() if not k.startswith("block")}
    names = sorted((k for k in params if k.startswith("block")),
                   key=lambda k: int(k[len("block"):]))
    out["blocks"] = {
        leaf: jnp.stack([params[n][leaf] for n in names])
        for leaf in params[names[0]]}
    return out


def from_pp_layout(params: Dict, cfg: TransformerConfig) -> Dict:
    out = {k: dict(v) for k, v in params.items() if k != "blocks"}
    n_layers = next(iter(params["blocks"].values())).shape[0]
    for i in range(n_layers):
        out[f"block{i}"] = {leaf: v[i] for leaf, v in params["blocks"].items()}
    return out


def pp_param_specs(params: Dict, stage_axis: str = "stage",
                   tp_axis: Optional[str] = None) -> Dict:
    """PartitionSpec pytree for the PP layout: stacked block leaves split on
    the layer axis over ``stage_axis``, everything else replicated. With
    ``tp_axis``, block weights additionally split tensor-parallel (columns
    for wqkv/w1, rows for wo/w2 — the 3-D dp x pp x tp layout)."""
    if tp_axis is None:
        return {lname: {leaf: (P(stage_axis) if lname == "blocks" else P())
                        for leaf in lp}
                for lname, lp in params.items()}
    tp_spec = {"wqkv": P(stage_axis, tp_axis),
               "wo": P(stage_axis, None, tp_axis),
               "w1": P(stage_axis, tp_axis),
               "w2": P(stage_axis, None, tp_axis)}
    return {lname: {leaf: (tp_spec.get(leaf, P(stage_axis))
                           if lname == "blocks" else P())
                    for leaf in lp}
            for lname, lp in params.items()}


def build_dp_pp_train_step(cfg: TransformerConfig, sp: SolverParameter,
                           mesh: Mesh, params: Dict, microbatches: int,
                           data_axis: str = "data",
                           stage_axis: str = "stage",
                           tp_axis: Optional[str] = None,
                           donate: bool = True,
                           remat_policy: Optional[str] = None):
    """Training step over a 2-D (data x stage) mesh — GPipe-style pipeline
    parallelism as ONE differentiable compiled program, not a scheduler.
    Where a CUDA framework hand-writes a 1F1B schedule with per-stage
    threads and NCCL send/recv (the reference's per-layer comm threads are
    the closest analog, solver.cpp's DWBP), here the forward schedule is a
    ``lax.scan`` over microbatch ticks with a ``ppermute`` ring shifting
    activations stage->stage+1, and the BACKWARD pipeline falls out of
    autodiff: the transpose of the scan runs the ticks in reverse and the
    transpose of each ppermute is the reverse rotation, so the cotangents
    ride the ring backwards with no scheduler code at all.

    Layers split contiguously over ``stage_axis`` (stacked leaves,
    ``to_pp_layout``); each stage scans over its local run. The local batch
    splits into ``microbatches`` microbatches; tick t ingests microbatch t
    at stage 0 (embedding) and retires one at the last stage (final LN +
    head + summed token loss) once the pipe fills. SPMD means every stage
    executes the ingest/egress code with masked selects — the embed/head
    FLOPs are spent on every stage but only stage 0 / stage S-1 keep the
    result, the standard SPMD-pipeline trade. Activation memory is GPipe's
    (all live ticks), cut by per-tick remat when ``cfg.remat``.

    Gradients: block grads are stage-local by construction (cotangents
    arrive over the reversed ring); the masked selects zero every other
    stage's embed/head/ln_f grads, so one explicit psum over ``stage_axis``
    (outside the differentiated region — a raw psum inside it transposes to
    another psum and over-counts) restores the replicated leaves, then
    everything pmeans over ``data_axis``. The per-device loss scalar stays
    un-psum'd inside ``loss_fn`` for the same reason; the metric sums
    across stages afterwards. Requires n_layers % n_stages == 0 and
    local batch % microbatches == 0.

    With ``tp_axis`` this becomes the standard 3-D recipe (dp x pp x tp):
    each stage's blocks run ``tp_block_forward`` (this rank's head slices /
    FFN columns, f/g conjugate collectives over ``tp_axis``), so pass
    params through ``to_pp_layout(to_tp_layout(...))``. The grad sync is
    unchanged: block grads stay local (tp-sharded leaves complete per rank,
    per-stage ln leaves bit-identical across tp ranks via f/g), non-block
    leaves still psum over ``stage_axis`` only — they are computed in full
    on every tp rank, so a tp psum would over-count."""
    n_stage = dict(zip(mesh.axis_names, mesh.devices.shape))[stage_axis]
    n_layers = next(iter(params["blocks"].values())).shape[0]
    if n_layers % n_stage:
        raise ValueError(f"n_layers={n_layers} not divisible by "
                         f"{n_stage} pipeline stages")
    specs = pp_param_specs(params, stage_axis, tp_axis)
    if tp_axis is None:
        def stage_block(h, blk):
            return block_forward(cfg, h, blk)
    else:
        _check_tp_divisibility(cfg, mesh, tp_axis)
        f_op, g_op = make_fg_ops(tp_axis)

        def stage_block(h, blk):
            return tp_block_forward(cfg, h, blk, f_op, g_op)

    def device_step(p, state: SolverState, tokens, targets, rng):
        stage = lax.axis_index(stage_axis)
        b_local, s_len = tokens.shape
        m = microbatches
        if b_local % m:
            raise ValueError(f"local batch {b_local} not divisible by "
                             f"{m} microbatches")
        bm = b_local // m
        tok_mb = tokens.reshape(m, bm, s_len)
        tgt_mb = targets.reshape(m, bm, s_len)
        n_tokens = float(m * bm * s_len)
        perm = [(i, (i + 1) % n_stage) for i in range(n_stage)]

        def tick(pp, x, t):
            # ingest (kept by stage 0 only): embed microbatch t
            toks = lax.dynamic_index_in_dim(
                tok_mb, jnp.clip(t, 0, m - 1), 0, keepdims=False)
            fresh = (pp["embed"]["w"][toks]
                     + pp["pos"]["w"][jnp.arange(s_len)])
            x = jnp.where(stage == 0, fresh, x)
            # this stage's run of layers
            def body(h, blk):
                return stage_block(h, blk), None
            x, _ = lax.scan(body, x, pp["blocks"])
            # egress (kept by the last stage once the pipe is full):
            # microbatch t - (n_stage - 1) retires at tick t
            out_idx = t - (n_stage - 1)
            h = _layer_norm(x, pp["ln_f"]["g"], pp["ln_f"]["b"])
            logits = _dense(h, pp["head"]["w"]).astype(jnp.float32)
            tgt = lax.dynamic_index_in_dim(
                tgt_mb, jnp.clip(out_idx, 0, m - 1), 0, keepdims=False)
            logp = jax.nn.log_softmax(logits, axis=-1)
            picked = jnp.take_along_axis(logp, tgt[..., None], axis=-1)
            valid = (out_idx >= 0) & (stage == n_stage - 1)
            loss = jnp.where(valid, -jnp.sum(picked) / n_tokens, 0.0)
            return lax.ppermute(x, stage_axis, perm), loss

        tick_fn = wrap_checkpoint(tick, resolve_lm_policy(cfg.remat,
                                                          remat_policy))

        def loss_fn(pp):
            def tick_p(x, t):
                return tick_fn(pp, x, t)
            x0 = jnp.zeros((bm, s_len, cfg.d_model), jnp.float32)
            _, losses = lax.scan(tick_p, x0, jnp.arange(m + n_stage - 1))
            # per-device scalar: zero except on the last stage (see above)
            return jnp.sum(losses)

        loss, grads = jax.value_and_grad(loss_fn)(p)
        grads = {lname: {leaf: (g if lname == "blocks"
                                else lax.psum(g, stage_axis))
                         for leaf, g in lg.items()}
                 for lname, lg in grads.items()}
        grads = jax.tree_util.tree_map(
            lambda g: lax.pmean(g, data_axis), grads)
        upd = make_update_fn(sp, transformer_mults(p))
        new_params, new_state = upd(p, grads, state)
        metrics = {"loss": lax.pmean(lax.psum(loss, stage_axis), data_axis)}
        return new_params, new_state, metrics

    state_spec = SolverState(it=P(), history=specs)
    sharded = shard_map(
        device_step, mesh=mesh,
        in_specs=(specs, state_spec, P(data_axis), P(data_axis), P()),
        out_specs=(specs, state_spec, P()),
        check_vma=False)
    return jax.jit(sharded, donate_argnums=(0, 1) if donate else ())
