"""Plain reference for Ouro's looped block (arXiv:2510.25741, "Scaling Latent
Reasoning via Looped Language Models"; config.json of ByteDance/Ouro-2.6B):
forward, the exit-weighted loss and, through ``jax.grad`` of ``loss``, every
gradient. Straightforward ``jax.numpy`` in float32 under
``jax.default_matmul_precision("highest")``: dense attention, a Python loop
over the passes, no kernel, no remat, nothing imported from the program.
With x the token ids, L layers and T passes:

    h(0) = E[x]
    for t = 1..T, from u = h(t-1), for each layer i = 1..L, THE SAME WEIGHTS
    IN EVERY PASS:
        a = RMSNorm_i1(u);  q, k, v = W_q a, W_k a, W_v a      (no bias)
        o = W_o Attn(RoPE(q), RoPE(k), v)      (causal, rotate-half, no
                                                QK-norm, scale 1/sqrt(Dh))
        u = u + RMSNorm_i2(o)                  (sandwich norm)
        m = RMSNorm_i3(u);  f = W_d( silu(W_g m) * (W_u m) )
        u = u + RMSNorm_i4(f)
    h(t) = RMSNorm_final(u)        (head, gate and pass t+1 all read it)
    logits(t) = W_head h(t);  lambda_t = sigmoid(w_gate . h(t) + b_gate)
    p_t = lambda_t prod_{j<t} (1 - lambda_j)  (t < T);
    p_T = prod_{j<T} (1 - lambda_j)                      (sums to 1)
    loss = mean over tokens of [ sum_t p_t CE(logits(t), target)
                                 - entropy_weight * H(p) ],
    H(p) = -sum_t p_t log p_t

The gate of the last pass enters neither the loss nor a later pass and is
not computed. Packed documents attend across their boundaries (no
intra-document mask). ``q_block`` computes the attention of ``q_block``
queries at a time, each against every key up to its own position (the same
dense softmax rows, fewer of them in memory at once: an 8,192 x 8,192 score
matrix per head is 4.3 GB over 16 heads); ``last`` keeps the logits of the
last ``last`` positions only. ``round_to`` rounds every matmul input to a
narrower type and back: the reading that shows a tolerance can tell
precisions apart, never used for ``correct``.

Weights come as ``{layer name: [blobs]}`` (what ``Net.export_weights``
gives) under the names of the configuration's prototxt, read from the FIRST
pass's layers, which own them: ``embed``, ``p1_l<i>_{attn_norm,q,k,v,o,
attn_out_norm,ffn_norm,ffn_gate,ffn_up,ffn_down,ffn_out_norm}``,
``p1_final_norm``, ``p1_head``, ``p1_gate`` (w (1, D), b (1,)); matrices are
(out, in).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

# program against reference, per numeric policy of the program's forward, at
# the PUBLISHED widths (what the chip run's `correct` uses). f32: both sides
# sum the same products in another order (1e-6 a step, 28 block applications
# deep at the cell's depth). bf16: the program rounds every matmul input and
# every activation to 8 bits of mantissa. OLMoE's cell holds 3%, but there a
# token near a routing tie takes another expert than the reference's; this
# model has no discontinuity, every block's output passes an RMSNorm before
# it joins the residual and every pass ends in one, so a pass's error does
# not compound into the next. Two readings on the v5e set the limit (PERF.md,
# PR 29): the program under bf16 read 0.22-0.27% at every pass over the
# seeds tried, and this reference with its matmul inputs rounded to float8
# (e4m3, the nearest precision below: `round_to`) read 1.6-2.2%. 0.8% is 3 x
# the largest of the first and half the smallest of the second: OLMoE's 3%
# would have passed float8. The losses agreed to 1e-5 under bf16.
TOLERANCE = {
    "f32": {"logits_rel_l2": 2e-4, "loss_rel": 1e-5},
    "bf16": {"logits_rel_l2": 8e-3, "loss_rel": 1e-3},
}
# at the widths of a CPU rehearsal (hidden 64, 64 positions; tests and
# --cpu-tiny only) the same two readings are 1.0-2.0% and 27%: a logit is a
# sum of 64 products, not 2048, and attention averages 64 values, not 8192
TOLERANCE_TINY = {
    "f32": TOLERANCE["f32"],
    "bf16": {"logits_rel_l2": 5e-2, "loss_rel": 5e-3},
}


def rms_norm(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * g


def rope(x, theta):
    """x (S, H, Dh): rotate-half rotary positions."""
    s, _, dh = x.shape
    inv = 1.0 / theta ** (jnp.arange(0, dh, 2, dtype=jnp.float32) / dh)
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * inv[None, :]
    ang = jnp.concatenate([ang, ang], -1)[:, None, :]        # (S, 1, Dh)
    rot = jnp.concatenate([-x[..., dh // 2:], x[..., :dh // 2]], -1)
    return x * jnp.cos(ang) + rot * jnp.sin(ang)


def attention(q, k, v, heads, theta, q_block=None):
    """One sequence: q, k, v (S, D) -> (S, D), causal."""
    s, d = q.shape
    dh = d // heads
    q = rope(q.reshape(s, heads, dh), theta)
    k = rope(k.reshape(s, heads, dh), theta)
    v = v.reshape(s, heads, dh)
    q_block = q_block or s

    def rows(start):
        qb = jax.lax.dynamic_slice_in_dim(q, start, q_block, 0)
        scores = jnp.einsum("qhd,khd->hqk", qb, k) / jnp.sqrt(jnp.float32(dh))
        mask = (start + jnp.arange(q_block))[:, None] >= jnp.arange(s)[None]
        probs = jax.nn.softmax(jnp.where(mask, scores, -jnp.inf), -1)
        return jnp.einsum("hqk,khd->qhd", probs, v)

    out = jax.lax.map(rows, jnp.arange(0, s, q_block))
    return out.reshape(s, d)


def forward(cfg, weights, tokens, targets=None, last=None, q_block=None,
            round_to=None):
    """tokens (N, S) int -> {"logits" (T, N, last or S, V), "gates"
    (T - 1, N, S) exit-gate logits, "exit_p" (T, N, S), and with
    ``targets`` "ce" (T, N, S), each pass's cross-entropy at every
    position}. ``cfg``: num_hidden_layers, total_ut_steps,
    num_attention_heads, rms_norm_eps, rope_theta."""
    with jax.default_matmul_precision("highest"):
        eps, heads = cfg["rms_norm_eps"], cfg["num_attention_heads"]
        passes = cfg["total_ut_steps"]

        def f32(name):
            return [jnp.asarray(b, jnp.float32) for b in weights[name]]

        def rnd(x):
            return x if round_to is None \
                else x.astype(round_to).astype(jnp.float32)

        def mm(x, name):                 # x (.., in) by a (out, in) matrix
            return rnd(x) @ rnd(f32(name)[0]).T

        u = f32("embed")[0][tokens]                           # (N, S, D)
        logits, gates, ce = [], [], []
        for t in range(1, passes + 1):
            for i in range(cfg["num_hidden_layers"]):
                l = f"p1_l{i}_"          # pass 1's layers own the weights
                a = rms_norm(u, f32(l + "attn_norm")[0], eps)
                q, k, v = mm(a, l + "q"), mm(a, l + "k"), mm(a, l + "v")
                att = jax.lax.map(             # one sequence at a time
                    lambda qkv: attention(*qkv, heads, cfg["rope_theta"],
                                          q_block), (q, k, v))
                o = mm(att, l + "o")
                u = u + rms_norm(o, f32(l + "attn_out_norm")[0], eps)
                m = rms_norm(u, f32(l + "ffn_norm")[0], eps)
                f = mm(jax.nn.silu(mm(m, l + "ffn_gate"))
                       * mm(m, l + "ffn_up"), l + "ffn_down")
                u = u + rms_norm(f, f32(l + "ffn_out_norm")[0], eps)
            u = rms_norm(u, f32("p1_final_norm")[0], eps)     # h(t)
            full = mm(u, "p1_head")                           # (N, S, V)
            logits.append(full if last is None else full[:, -last:])
            if targets is not None:
                ce.append(-jnp.take_along_axis(
                    jax.nn.log_softmax(full, -1), targets[..., None],
                    -1)[..., 0])
            if t < passes:
                gates.append(mm(u, "p1_gate")[..., 0] + f32("p1_gate")[1][0])
        gates = jnp.stack(gates)
        lam = jax.nn.sigmoid(gates)
        stay = jnp.cumprod(1.0 - lam, 0)          # prod_{j<=t} (1-lambda_j)
        before = jnp.concatenate([jnp.ones_like(stay[:1]), stay[:-1]])
        exit_p = jnp.concatenate([lam * before, stay[-1:]])
        out = {"logits": jnp.stack(logits), "gates": gates,
               "exit_p": exit_p}
        if targets is not None:
            out["ce"] = jnp.stack(ce)
        return out


def loss(cfg, weights, tokens, targets, entropy_weight=0.1, **how):
    """-> (total, {"ce" (T,) each pass's mean cross-entropy, "entropy" mean
    H(p), "exit_mass" (T,) mean p_t, "logits"}); ``how`` is ``forward``'s
    ``last`` / ``q_block`` / ``round_to``."""
    out = forward(cfg, weights, tokens, targets, **how)
    ce, p = out["ce"], out["exit_p"]
    entropy = -jnp.sum(jnp.where(p > 0, p * jnp.log(p), 0.0), 0)
    total = jnp.mean(jnp.sum(p * ce, 0) - entropy_weight * entropy)
    return total, {"ce": jnp.mean(ce, (1, 2)), "entropy": jnp.mean(entropy),
                   "exit_mass": jnp.mean(p, (1, 2)),
                   "logits": out["logits"]}
