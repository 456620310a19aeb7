"""Plain reference for ZAYA1-8B's block (config.json of Zyphra/ZAYA1-8B;
arXiv:2510.04476, Compressed Convolutional Attention; arXiv:2511.17127, the
ZAYA1 report): forward, the next-token loss and, through ``jax.grad`` of
``loss``, every gradient. Straightforward ``jax.numpy`` in float32 under
``jax.default_matmul_precision("highest")``: dense attention in blocks of
queries, a Python loop over experts, no kernel, no sort, nothing imported
from the program (``remat`` wraps a layer, a block of queries, an expert and
a sequence's head in ``jax.checkpoint``: the same arithmetic, so that the
gradient of two sequences of 8,192 at the published widths fits one chip). Per sequence x (S, D), with H query and G
key-value heads of d, per = H / G, R the router's width, E experts:

    x = E_tok[ids]
    for each layer:
      h = RMSNorm(x)
      -- CCA --
      q~ = h W_q (S, H d);  k~ = h W_k (S, G d)
      v  = [h W_v1 , shift(h) W_v2]        shift(h)_t = h_{t-1}, 0 at t = 0:
                                           kv head 0 this token's values,
                                           head 1 the token before's
      c  = [q~ , k~];  c1_t = sum_j dw[j] * c_{t-j} + dw_b   (depthwise,
                                           time0 taps, causal)
      c2_t[g] = sum_j gw[j, g] c1_{t-j}[g] + gw_b[g]         (one group a
                                           head, H + G groups, time1 taps)
      (q_c, k_c) = split(c2)
      m_q[h] = (q~[h] + k~[h // per]) / 2;  m_k[g] = mean of its per m_q
      q = q_c + m_q;  k = k_c + m_k
      q = q * rsqrt(mean_d(q^2) + eps)      (= q / ||q|| * sqrt(d))
      k = k * rsqrt(mean_d(k^2) + eps) * tau[g]
      rotate-half RoPE on the first rotary_dims of every q and k head
      o = softmax(q k^T / sqrt(d)) v, causal, query head h reads kv head
          h // per;  x = x + o W_o
      -- MoE behind the MLP router --
      u = RMSNorm(x)
      r = u W_r + gamma * r_prev            (r_prev of the layer before;
                                            the first layer has no gamma)
      s = W_3 gelu(W_2 gelu(W_1 r))  (exact gelu);  p = softmax(s)  (E)
      e(t) = argmax_e (p_e + b_e)           b: selection bias, no gradient
      y_t = p_{e(t)} W_down[e] (silu(W_gate[e] u_t) * W_up[e] u_t)
            where e(t) is HELD, zero elsewhere;  x = x + y
    logits = RMSNorm(x) E_tok^T             (tied);  loss = mean NLL

``held`` is the set of expert ids whose weights ``weights`` carries, in
ascending order (stack row i is expert held[i]); None = all E. A token whose
expert is not held adds nothing: the shares of disjoint ``held`` sets sum to
the whole layer's output. The balancing rule (``next_bias``) is the step's:
b_e + rate * sign(T / E - n_e), n_e the tokens that chose e, held or not.

``choice`` (one (N, S) int array a layer) hands the experts the PROGRAM
chose to this reference, so that a near-tie that rounding flips shows as a
count (``route_flips``: where the reference's own argmax differs) and not
as a logit error; without it the reference routes by itself. ``q_block``
computes the attention of that many queries at a time; ``last`` keeps the
logits of the last ``last`` positions; ``round_to`` rounds every matmul
input to a narrower type and back (the gradient passes straight through the
rounding; ``round_when``, a traced bool, switches it inside one compiled
program): the reading that shows a tolerance can tell precisions apart,
never used for ``correct``.

``train_step`` is one whole step of the solver on this model, as plainly:
``jax.grad`` of ``loss``, the global-norm clip, AdamW (``adamw_step``), the
balancing rule on the selection biases, and returns every blob's CHANGE.

Weights come as ``{layer name: [blobs]}`` (``Net.export_weights``) under
the prototxt's names: ``embed``, ``l<i>_{attn_norm,q,k,v1,v2,cca_conv
[dw (time0, C), dw_b, gw (time1, H + G, d, d) (out, in), gw_b],
cca_qknorm [tau], o, moe_norm, router [down, (mix,) w1, w2, w3, bias],
moe [gate (G', F, D), up, down (G', D, F)]}``, ``final_norm``; matrices
are (out, in).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

# program against reference at the PUBLISHED widths (the chip run's
# `correct`), per numeric policy of the program. f32: the same products in
# another order. bf16, every limit from readings on the v5e (PERF.md, PR 31):
# - logits_rel_l2, update_cosine: two readings each, the program under bf16
#   over its seeds (0.0022-0.0044; 0.974-0.977) and this reference with its
#   matmul inputs rounded to float8 e4m3, the nearest precision below
#   (0.0108-0.043; 0.877-0.878). The limit lies between them.
# - loss_rel, step_loss_rel, update_norm_rel: the precision hardly moves
#   them (float8 reads among bf16's seeds), so the limit is about three
#   times the largest bf16 reading (7.2e-5 of 16 runs; 2.6e-5; 0.033, a
#   router's 256-number mix vector whose gradient is small beside AdamW's
#   eps; every leaf outside the routers within 0.003).
# - bias_margin: a selection bias is compared where its expert's count lies
#   further than this share of the step's tokens from the even split; the
#   one bias that differed in two runs lay 0.00024 (4 tokens) from it.
TOLERANCE = {
    "f32": {"logits_rel_l2": 2e-4, "loss_rel": 1e-5,
            "step_loss_rel": 1e-5, "update_norm_rel": 1e-3,
            "update_cosine": 0.999, "cosine_from": 2 ** 16,
            "bias_margin": 0.0},
    "bf16": {"logits_rel_l2": 9e-3, "loss_rel": 2.5e-4,
             "step_loss_rel": 1e-4, "update_norm_rel": 0.1,
             "update_cosine": 0.93, "cosine_from": 2 ** 16,
             "bias_margin": 0.01},
}
# at a CPU rehearsal's widths (hidden 64, 64 positions, 128 tokens a step) a
# logit is a sum of 64 products, not 2048: bf16 reads 1-3% there, a handful
# of the 128 tokens change expert, and the routers' gradients are small
# beside AdamW's eps. The rehearsal shows that the check runs, not how close
# the program comes.
TOLERANCE_TINY = {
    "f32": dict(TOLERANCE["f32"], cosine_from=2 ** 6),
    "bf16": {"logits_rel_l2": 6e-2, "loss_rel": 5e-3,
             "step_loss_rel": 5e-3, "update_norm_rel": 0.5,
             "update_cosine": 0.8, "cosine_from": 2 ** 10,
             "bias_margin": 0.04},
}


def rms_norm(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * g


def shift(x, by=1):
    """x (S, ...) -> moved ``by`` positions later, zeros in front."""
    if by == 0:
        return x
    return jnp.concatenate([jnp.zeros_like(x[:by]), x[:-by]], 0)


def rope(x, theta, dims):
    """x (S, heads, d): rotate-half rotary positions on the first ``dims``
    of every head (frequency i serves dims i and i + dims / 2)."""
    s = x.shape[0]
    inv = 1.0 / theta ** (jnp.arange(0, dims, 2, dtype=jnp.float32) / dims)
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * inv[None, :]
    ang = jnp.concatenate([ang, ang], -1)[:, None, :]
    part = x[..., :dims]
    rot = jnp.concatenate([-part[..., dims // 2:], part[..., :dims // 2]],
                          -1)
    return jnp.concatenate([part * jnp.cos(ang) + rot * jnp.sin(ang),
                            x[..., dims:]], -1)


def attention(q, k, v, per, q_block=None, ckpt=lambda f: f):
    """One sequence: q (S, H, d), k, v (S, G, d) -> (S, H d), causal, query
    head h reading key-value head h // per."""
    s, h, d = q.shape
    q_block = q_block or s
    kv_of = jnp.arange(h) // per

    def rows(start):
        qb = jax.lax.dynamic_slice_in_dim(q, start, q_block, 0)
        scores = jnp.einsum("qhd,khd->hqk", qb, k[:, kv_of]) \
            / jnp.sqrt(jnp.float32(d))
        mask = (start + jnp.arange(q_block))[:, None] >= jnp.arange(s)[None]
        probs = jax.nn.softmax(jnp.where(mask, scores, -jnp.inf), -1)
        return jnp.einsum("hqk,khd->qhd", probs, v[:, kv_of])

    return jax.lax.map(ckpt(rows),
                       jnp.arange(0, s, q_block)).reshape(s, h * d)


def next_bias(bias, counts, rate):
    """The balancing rule: ``counts`` (E,) tokens per expert of one step."""
    counts = jnp.asarray(counts, jnp.float32)
    return bias + rate * jnp.sign(jnp.sum(counts) / counts.shape[0] - counts)


def forward(cfg, weights, tokens, targets=None, held=None, last=None,
            q_block=None, round_to=None, choice=None, remat=False,
            round_when=None):
    """tokens (N, S) int -> {"logits" (N, last or S, V); "counts" (L, E)
    tokens per expert by this reference's own argmax; "choice" (L, N, S)
    that argmax; "route_flips" (L,) positions where a handed-over
    ``choice`` differs from it (zeros without one); "moe" (L, N, S, D) each
    layer's MoE output; and with ``targets`` "nll" (N, S)}. ``cfg``:
    num_hidden_layers, num_attention_heads, num_key_value_heads,
    num_experts (what the router scores), rms_norm_eps, rope_theta,
    rotary_dims."""
    with jax.default_matmul_precision("highest"):
        eps = cfg["rms_norm_eps"]
        n_h, n_g = cfg["num_attention_heads"], cfg["num_key_value_heads"]
        n_exp, per = cfg["num_experts"], n_h // n_g
        held = list(range(n_exp)) if held is None else sorted(held)
        ckpt = jax.checkpoint if remat else (lambda f: f)

        def f32(blobs):
            return [jnp.asarray(b, jnp.float32) for b in blobs]

        def rnd(x):
            if round_to is None:
                return x
            r = x.astype(round_to).astype(jnp.float32)
            if round_when is not None:
                r = jnp.where(round_when, r, x)
            return x + jax.lax.stop_gradient(r - x)   # straight through

        def mm(x, w):                    # x (.., in) by an (out, in) matrix
            return rnd(x) @ rnd(w).T

        def cca(w, hs):                  # one sequence (S, D) -> (S, D)
            s = hs.shape[0]
            q0, k0 = mm(hs, w["q"][0]), mm(hs, w["k"][0])
            v = jnp.concatenate([mm(hs, w["v1"][0]),
                                 mm(shift(hs), w["v2"][0])], -1)
            dw, dw_b, gw, gw_b = w["cca_conv"]
            c = jnp.concatenate([q0, k0], -1)
            c1 = sum(dw[j] * shift(c, j) for j in range(dw.shape[0])) + dw_b
            d = gw.shape[-1]
            c1g = c1.reshape(s, n_h + n_g, d)
            c2 = sum(jnp.einsum("sgi,goi->sgo", rnd(shift(c1g, j)),
                                rnd(gw[j]))
                     for j in range(gw.shape[0])).reshape(s, -1) + gw_b
            qc, kc = c2[:, :n_h * d], c2[:, n_h * d:]
            m_q = (q0.reshape(s, n_g, per, d)
                   + k0.reshape(s, n_g, 1, d)) / 2
            m_k = jnp.mean(m_q, 2)
            q = qc.reshape(s, n_h, d) + m_q.reshape(s, n_h, d)
            k = kc.reshape(s, n_g, d) + m_k
            tau = w["cca_qknorm"][0]
            q = q * jax.lax.rsqrt(jnp.mean(q * q, -1, keepdims=True) + eps)
            k = k * jax.lax.rsqrt(jnp.mean(k * k, -1, keepdims=True)
                                  + eps) * tau[:, None]
            q = rope(q, cfg["rope_theta"], cfg["rotary_dims"])
            k = rope(k, cfg["rope_theta"], cfg["rotary_dims"])
            att = attention(rnd(q), rnd(k), rnd(v.reshape(s, n_g, d)), per,
                            q_block, ckpt)
            return mm(att, w["o"][0])

        def expert(u, gate, up, dn):     # every token through one expert
            return mm(jax.nn.silu(mm(u, gate)) * mm(u, up), dn)

        def layer(w, x, r_prev, handed):
            """-> (x after the layer, its router state, counts (E,), its
            own argmax (N, S), flips against ``handed``, the MoE output)."""
            h = rms_norm(x, w["attn_norm"][0], eps)
            x = x + jax.lax.map(lambda hs: cca(w, hs), h)
            u = rms_norm(x, w["moe_norm"][0], eps)
            rw = w["router"]
            down, (w1, w2, w3, bias) = rw[0], rw[-4:]
            r = u @ down.T                  # the router is never rounded
            if r_prev is not None:
                r = r + rw[1] * r_prev
            gelu = lambda a: jax.nn.gelu(a, approximate=False)  # noqa: E731
            p = jax.nn.softmax(gelu(gelu(r @ w1.T) @ w2.T) @ w3.T, -1)
            own = jnp.argmax(p + jax.lax.stop_gradient(bias), -1)  # (N, S)
            e = own if handed is None else handed
            p_e = jnp.take_along_axis(p, e[..., None], -1)    # (N, S, 1)
            gate, up, dn = w["moe"]
            y = jnp.zeros_like(x)
            for row, which in enumerate(held):      # every token, masked
                y = y + jnp.where((e == which)[..., None],
                                  ckpt(expert)(u, gate[row], up[row],
                                               dn[row]), 0.0)
            y = p_e * y
            return (x + y, r, jnp.sum(jax.nn.one_hot(own, n_exp), (0, 1)),
                    own, jnp.sum(e != own), y)

        emb = f32(weights["embed"])[0]
        x = emb[tokens]                                       # (N, S, D)
        r_prev = None
        counts, choices, flips, moe_out = [], [], [], []
        for i in range(cfg["num_hidden_layers"]):
            pre = f"l{i}_"
            w = {name[len(pre):]: f32(blobs)
                 for name, blobs in weights.items() if name.startswith(pre)}
            handed = None if choice is None else jnp.asarray(choice[i])
            x, r_prev, n_e, own, flipped, y = ckpt(layer)(w, x, r_prev,
                                                          handed)
            counts.append(n_e)
            choices.append(own)
            flips.append(flipped)
            moe_out.append(y)
        xf = rms_norm(x, f32(weights["final_norm"])[0], eps)

        def head(seq):                   # one sequence: the vocabulary is
            xs, tgt = seq                # wide, (S, V) at a time
            full = mm(xs, emb)
            kept = full if last is None else full[-last:]
            if tgt is None:
                return kept, None
            return kept, -jnp.take_along_axis(
                jax.nn.log_softmax(full, -1), tgt[:, None], -1)[:, 0]

        logits, nll = jax.lax.map(ckpt(head), (xf, targets))
        out = {"logits": logits, "counts": jnp.stack(counts),
               "choice": jnp.stack(choices),
               "route_flips": jnp.stack(flips), "moe": jnp.stack(moe_out)}
        if targets is not None:
            out["nll"] = nll
        return out


def loss(cfg, weights, tokens, targets, **how):
    """-> (mean next-token NLL, forward's dict); ``how`` is ``forward``'s
    ``held`` / ``last`` / ``q_block`` / ``round_to`` / ``round_when`` /
    ``choice`` / ``remat``."""
    out = forward(cfg, weights, tokens, targets, **how)
    return jnp.mean(out["nll"]), out


def cosine_lr(it, base, warm, total, floor):
    """The solver's ``cosine`` policy at iteration ``it`` (0 the first):
    linear warm-up over ``warm`` iterations, ``base * (it + 1) / warm``,
    then half a cosine from ``base`` down to ``floor * base`` at ``total``."""
    import math
    frac = min(1.0, max(0.0, (it - warm) / max(1, total - warm)))
    return base * min(1.0, (it + 1.0) / max(1, warm)) \
        * (floor + (1.0 - floor) * 0.5 * (1.0 + math.cos(math.pi * frac)))


def adamw_step(w, g, m, v, t, rate, decay, b1, b2, eps):
    """One AdamW step on one blob, ``t`` = 1 the first: -> (w', m', v')."""
    m = b1 * m + (1.0 - b1) * g
    v = b2 * v + (1.0 - b2) * g * g
    step = (m / (1.0 - b1 ** t)) / (jnp.sqrt(v / (1.0 - b2 ** t)) + eps)
    return w - rate * (step + decay * w), m, v


def train_step(cfg, weights, tokens, targets, opt, **how):
    """The FIRST step of training from ``weights``: the mean loss over
    every position and its gradient (``jax.grad`` of ``loss``), the gradient
    scaled down to a global L2 norm of ``opt["clip"]`` where it is larger,
    AdamW from zero moments on every blob, and the balancing rule on the
    routers' selection biases (the LAST blob of every ``*_router``: no
    gradient, optimizer, decay or clip; not in the clip's norm).
    ``opt``: ``rate`` and ``decay`` as {layer: [a number a blob]} (the
    step's learning rate x the blob's lr_mult, the weight decay x its
    decay_mult), ``clip``, ``b1``, ``b2``, ``eps``, ``bias_rate``.
    -> {"loss", "counts" (L, E), "grad_norm", "change": {layer: [w' - w]}}"""
    biases = [name for name in weights if name.endswith("_router")]

    def trained(w):                      # the biases enter as constants
        return {name: blobs[:-1] if name in biases else list(blobs)
                for name, blobs in w.items()}

    def objective(some):
        whole = {name: blobs + [weights[name][-1]] if name in biases
                 else blobs for name, blobs in some.items()}
        total, out = loss(cfg, whole, tokens, targets, **how)
        return total, out["counts"]

    (total, counts), grads = jax.value_and_grad(objective, has_aux=True)(
        trained({k: [jnp.asarray(b, jnp.float32) for b in v]
                 for k, v in weights.items()}))
    norm = jnp.sqrt(sum(jnp.sum(g * g) for g in jax.tree.leaves(grads)))
    scale = jnp.where(norm > opt["clip"], opt["clip"] / norm, 1.0)
    change = {}
    for name, blobs in grads.items():
        change[name] = []
        for j, g in enumerate(blobs):
            w = jnp.asarray(weights[name][j], jnp.float32)
            new, _, _ = adamw_step(
                w, g * scale, 0.0, 0.0, 1, opt["rate"][name][j],
                opt["decay"][name][j], opt["b1"], opt["b2"], opt["eps"])
            change[name].append(new - w)
    for i, name in enumerate(sorted(biases, key=lambda n: int(n[1:-7]))):
        bias = jnp.asarray(weights[name][-1], jnp.float32)
        change[name].append(
            next_bias(bias, counts[i], opt["bias_rate"]) - bias)
    return {"loss": total, "counts": counts, "grad_norm": norm,
            "change": change}
