"""Plain reference for the OLMoE block (arXiv:2409.02060; config.json of
allenai/OLMoE-1B-7B-0125-Instruct): forward, loss and, through ``jax.grad``
of ``loss``, every gradient. Straightforward ``jax.numpy`` in float32 under
``jax.default_matmul_precision("highest")``: every expert is computed densely
for EVERY token and masked by the top-k — no sort, no grouped matmul, no
kernel, no arena, nothing imported from the program. Per token ``x``:

    a = RMSNorm(x);  q = RMSNorm_q(W_q a), k = RMSNorm_k(W_k a)  (over the
        full width, before the head split);  v = W_v a
    h = x + W_o Attn(RoPE(q), RoPE(k), v)            (causal, rotate-half)
    u = RMSNorm(h);  p = softmax_f32(W_r u);  T = the k largest p
    y = h + sum_{e in T} p_e W_down,e( silu(W_gate,e u) * (W_up,e u) )
    loss = CE(W_head RMSNorm(y_last), targets)
           + balance_weight * E * sum_e f_e P_e
           + z_weight * mean(logsumexp(W_r u)^2)        (both per layer)

``f_e`` is the fraction of tokens that have expert e among their k (the f_e
sum to k, as in the paper's public implementation), ``P_e`` the mean of p_e.

Departures from the paper, each deliberate: the top-k weights are NOT
renormalised (``norm_topk_prob`` false, as the published config has it);
packed documents attend across their boundaries (no intra-document mask, as
OLMoE trained); no dropout (the paper uses none); the experts of one layer
run one after another (a ``lax.scan`` over the expert axis) only so that the
dense (tokens x experts x width) intermediates fit beside the weights at
the published widths — the sums are the same.

Weights come as ``{layer name: [blobs]}`` (what ``Net.export_weights``
gives), under the names of the configuration's prototxt: ``embed``,
``l<i>_{attn_norm,q,k,v,q_norm,k_norm,o,ffn_norm,moe}``, ``final_norm``,
``lm_head``; matrices are (out, in), the MOE blobs router (E, D), gate and
up (E, F, D), down (E, D, F).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

# program against reference, per numeric policy of the program's forward.
# f32: both sides sum the same products in another order (1e-6 a step, a few
# dozen steps deep). bf16: the program rounds every matmul input and every
# activation to 8 bits of mantissa (2^-9 = 0.2% a rounding, a dozen roundings
# deep, partly cancelling), and a token whose 8th and 9th router
# probabilities are closer than that rounding takes another expert than the
# reference's; 3% holds both, while a dropped expert (1/8 of a token's FFN
# output), a renormalised top-k (weights x 4-8) or f16-width statistics
# each move the logits by 10% or more.
TOLERANCE = {
    "f32": {"logits_rel_l2": 2e-4, "loss_rel": 1e-5},
    "bf16": {"logits_rel_l2": 3e-2, "loss_rel": 5e-3},
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


def attention(q, k, v, heads, theta):
    """One sequence: q, k, v (S, D) -> (S, D), causal."""
    s, d = q.shape
    dh = d // heads
    q = rope(q.reshape(s, heads, dh), theta)
    k = rope(k.reshape(s, heads, dh), theta)
    v = v.reshape(s, heads, dh)
    scores = jnp.einsum("qhd,khd->hqk", q, k) / jnp.sqrt(jnp.float32(dh))
    mask = jnp.tril(jnp.ones((s, s), bool))
    probs = jax.nn.softmax(jnp.where(mask, scores, -jnp.inf), -1)
    return jnp.einsum("hqk,khd->qhd", probs, v).reshape(s, d)


def moe(u, router, gate, up, down, top_k):
    """u (T, D) -> (y (T, D), balance loss, z loss, tokens per expert)."""
    n_exp = router.shape[0]
    logits = u @ router.T                                     # (T, E)
    p = jax.nn.softmax(logits, -1)
    kth = jnp.sort(p, -1)[:, n_exp - top_k]                   # k-th largest
    chosen = p >= kth[:, None]                                # (T, E) top-k
    w = jnp.where(chosen, p, 0.0)                             # not renormed

    def one_expert(y, e):
        g_e, u_e, d_e, w_e = e
        hid = jax.nn.silu(u @ g_e.T) * (u @ u_e.T)            # every token
        return y + w_e[:, None] * (hid @ d_e.T), None

    y, _ = jax.lax.scan(one_expert, jnp.zeros_like(u),
                        (gate, up, down, w.T))
    f = jnp.mean(chosen.astype(jnp.float32), 0)               # sums to k
    balance = n_exp * jnp.sum(f * jnp.mean(p, 0))
    z = jnp.mean(jax.nn.logsumexp(logits, -1) ** 2)
    return y, balance, z, jnp.sum(chosen, 0)


def forward(cfg, weights, tokens):
    """tokens (N, S) int -> {"logits" (N, S, V), "balance" [per layer],
    "z" [per layer], "tokens_per_expert" [per layer]}. ``cfg``:
    num_hidden_layers, num_attention_heads, num_experts_per_tok,
    rms_norm_eps, rope_theta."""
    with jax.default_matmul_precision("highest"):
        eps, heads = cfg["rms_norm_eps"], cfg["num_attention_heads"]
        f32 = lambda name: [jnp.asarray(b, jnp.float32)     # noqa: E731
                            for b in weights[name]]
        x = f32("embed")[0][tokens]                           # (N, S, D)
        n, s, d = x.shape
        out = {"balance": [], "z": [], "tokens_per_expert": []}
        for i in range(cfg["num_hidden_layers"]):
            l = f"l{i}_"
            a = rms_norm(x, f32(l + "attn_norm")[0], eps)
            q = rms_norm(a @ f32(l + "q")[0].T, f32(l + "q_norm")[0], eps)
            k = rms_norm(a @ f32(l + "k")[0].T, f32(l + "k_norm")[0], eps)
            v = a @ f32(l + "v")[0].T
            att = jax.lax.map(                 # one sequence at a time
                lambda qkv: attention(*qkv, heads, cfg["rope_theta"]),
                (q, k, v))
            h = x + att @ f32(l + "o")[0].T
            u = rms_norm(h, f32(l + "ffn_norm")[0], eps)
            y, bal, z, load = moe(u.reshape(n * s, d), *f32(l + "moe"),
                                  cfg["num_experts_per_tok"])
            x = h + y.reshape(n, s, d)
            out["balance"].append(bal)
            out["z"].append(z)
            out["tokens_per_expert"].append(load)
        xf = rms_norm(x, f32("final_norm")[0], eps)
        out["logits"] = xf @ f32("lm_head")[0].T
        return out


def loss(cfg, weights, tokens, targets, balance_weight=0.01,
         z_weight=0.001):
    """-> (total, {"lm", "balance" (summed over layers, unweighted), "z"})."""
    out = forward(cfg, weights, tokens)
    logp = jax.nn.log_softmax(out["logits"], -1)
    lm = -jnp.mean(jnp.take_along_axis(logp, targets[..., None], -1))
    bal, z = sum(out["balance"]), sum(out["z"])
    return lm + balance_weight * bal + z_weight * z, \
        {"lm": lm, "balance": bal, "z": z}
