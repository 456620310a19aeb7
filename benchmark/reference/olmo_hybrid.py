"""Plain reference for Olmo-Hybrid-7B's block (config.json of
allenai/Olmo-Hybrid-7B, ``model_type: olmo_hybrid``; what config.json does
not say is under ``assumed`` in configs/olmo_hybrid_7b.json): forward, the
next-token loss and, through ``jax.grad`` of ``loss``, every gradient.
Straightforward ``jax.numpy`` in float32 under
``jax.default_matmul_precision("highest")``: Gated DeltaNet TOKEN BY TOKEN
(a ``lax.scan`` over t, no chunks, no triangular system), attention as a
full masked softmax in blocks of queries, no kernel, nothing imported from
the program (``remat`` wraps a layer, ``t_block`` tokens of the recurrence,
a block of queries and a sequence's head in ``jax.checkpoint``: the same
arithmetic, so that the gradient of a sequence of 8,192 at the published
widths fits one chip). Per sequence x (S, D), H heads HELD of the model's:

    x = E_tok[ids]
    for each layer i:   h = x + N_a(Mix_i(x));  x = h + N_f(FFN(h))
        N_*: RMSNorm (eps, own gain) on the sublayer's OUTPUT, none on its
        input;  FFN(h) = (silu(h W_g) * (h W_u)) W_d
    linear layer (layer_types[i] "linear"), Gated DeltaNet, d_k != d_v:
      q~ = x W_q, k~ = x W_k (S, H d_k);  v~ = x W_v (S, H d_v)
      c'_t = silu(sum_j w[j] * c~_{t-j})   per channel, 4 taps, zeros
                                           before the sequence's start
      q = q' / sqrt(sum q'^2 + 1e-6) per head, k likewise; v = v'
      g_t = -exp(A_log_h) softplus(x_t W_a + dt_bias_h)     (H) ONE a head
      beta_t = 2 sigmoid(x_t W_b)                           (H) in (0, 2)
      S_0 = 0 (d_k, d_v) a head;  Sbar_t = exp(g_t) S_{t-1}
      S_t = Sbar_t + beta_t k_t (v_t - Sbar_t^T k_t)^T
      o_t = S_t^T q_t d_k^-0.5
      Mix = (RMSNorm_dv(o) * gain * silu(x W_z)) W_o
    full layer ("full"): q = N_q(x W_q), k = N_k(x W_k): RMSNorm over ALL
      the held heads' channels (one gain vector each); v = x W_v
      Mix = softmax_causal(q k^T / sqrt(d)) v  W_o        NO positions
    logits = N_final(x) W_head^T (untied);  loss = mean NLL

A share of the heads: ``weights`` carries the held heads' columns of W_q,
W_k, W_v, W_a, W_b, W_z (rows of the (out, in) matrices), their taps, A_log,
dt_bias and QK gains, and the held heads' ROWS of W_o's input (its columns);
``cfg["num_heads"]`` is how many. The partial W_o result goes on into N_a as
it is: nothing stands in for the other chips. ``head_share`` cuts a share's
weights out of a model's with more heads (the tests' "the shares add up").
The embedding and the head come at the vocabulary's slice.

``q_block`` computes the attention of that many queries at a time; ``last``
keeps the logits of the last ``last`` positions. Two controls show that a
tolerance can tell precisions apart, never used for ``correct``:
``round_to`` rounds every matmul input (and q, k, v before the recurrence
and the attention) to a narrower type and back, the gradient passing
straight through (``round_when``, a traced bool, switches it inside one
compiled program); ``delta_rule``'s ``state_round`` rounds the
recurrence's STATE after every token.

``train_step`` is one whole step of the solver on this model, as plainly:
``jax.grad`` of ``loss``, the global-norm clip, AdamW (``adamw_step``), and
returns every blob's CHANGE.

Weights come as ``{layer name: [blobs]}`` under the prototxt's names:
``embed``; a linear layer's ``l<i>_gdn_{q,k,v}``, ``l<i>_gdn_conv_{q,k,v}
[w (taps, H d)]``, ``l<i>_gdn_a``, ``l<i>_gdn_decay [A_log (H), dt_bias
(H)]``, ``l<i>_gdn_b``, ``l<i>_gdn_onorm [gain (d_v)]``, ``l<i>_gdn_z``,
``l<i>_gdn_o``; a full layer's ``l<i>_attn_{q,k,v,o}``, ``l<i>_attn_qnorm``,
``l<i>_attn_knorm``; every layer's ``l<i>_mix_norm``,
``l<i>_ffn_{gate,up,down}``, ``l<i>_ffn_norm``; ``final_norm``,
``lm_head``; matrices are (out, in).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

# program against reference at the PUBLISHED widths (the chip run's
# `correct`), per numeric policy of the program. f32: the same products in
# another order. bf16: every limit from readings on the v5e (PERF.md, PR 48,
# section 6 has them seed by seed): the program under bf16; this reference
# with its matmul inputs rounded to float8 e4m3, the nearest precision
# below; and the recurrence with its state rounded to bf16 after every
# token. Each control fails at least one limit:
# - logits_rel_l2 (trained weights, the last 512 positions of one sequence
#   of 8,192): bf16 0.0163-0.0199 over 21 seeds, float8 0.323-0.388: 0.03
#   between, 1.5 times the program's largest (fresh seeds read higher) and a
#   tenth of the control's smallest. The bf16 STATE does not show in the logits here (it
#   read 0.0126-0.0177, at or below the program's own bf16 noise: the norm
#   on every sublayer's output passes a mixer's relative error on 1 : 1, and
#   a head that keeps 0.7-0.9 of its state a token forgets a rounding in a
#   few tokens), so the state is held where it shows:
# - scan_rel_l2: the LAST linear layer's recurrence alone, the program's
#   scan with o left in f32 against ``delta_rule``, both on the layer's own
#   operand blobs (bf16 q, k, v, f32 g, beta at the trained weights, the
#   whole sequence): the program's f32 state 4.8e-6-3.3e-5 over 8 seeds, the
#   bf16 state 0.00232-0.0060 over 21: 3e-4 between, nine times the
#   program's largest reading and an eighth of the control's smallest. (With o rounded to bf16 as the layer
#   hands it on, the program read 0.00166 on every seed: the cast, not the
#   state.)
# - update_cosine (the worst leaf of 2**16 numbers or more, on every seed
#   the last linear layer's q projection: its gradient passes the L2 norm's
#   pullback): bf16 0.9105-0.9384, float8 0.161-0.246: 0.7 between.
# - gate_cosine (the leaves that only the scan's d g and d beta feed, W_a +
#   A_log + dt_bias and W_b of all linear layers, each group ONE vector; the
#   worse group; they lie under cosine_from, and a norm cannot see a sign):
#   bf16 0.946-0.954 over 8 seeds, float8 0.280-0.357 (its better group up
#   to 0.399): 0.6 between.
# - update_norm_rel: the precision hardly moves it (bf16 0.0006-0.0047,
#   float8 0.0008-0.0226): between the reading and 1, which a state left
#   unchanged reads, with the more room above.
# - loss_rel: NOT a limit under bf16 (None), a fact: the precision hardly
#   moves it (bf16 0.5e-5-16.9e-5, float8 4e-5-300e-5: float8 reads among
#   bf16's seeds) and the accepted cells' 2.5e-4 would leave the program's
#   largest reading 1.5 times of room, not three (its first reading, 3.5e-5,
#   had seven): the 8 cycled sequences are memorised inside the window, so
#   the trained logits are sharp and the loss on an unseen sequence is noisy.
# - step_loss_rel: NOT a limit under bf16 (None), as in the accepted token
#   cells: 0-9.6e-5 on fresh weights, float8 3.7e-4-1.3e-3. A fact.
TOLERANCE = {
    "f32": {"logits_rel_l2": 2e-4, "scan_rel_l2": 1e-4, "loss_rel": 1e-5,
            "step_loss_rel": 1e-5, "update_norm_rel": 1e-3,
            "update_cosine": 0.999, "gate_cosine": 0.99,
            "cosine_from": 2 ** 16},
    "bf16": {"logits_rel_l2": 0.03, "scan_rel_l2": 3e-4, "loss_rel": None,
             "step_loss_rel": None, "update_norm_rel": 0.1,
             "update_cosine": 0.7, "gate_cosine": 0.6,
             "cosine_from": 2 ** 16},
}
# at a CPU rehearsal's widths a logit is a sum of 64 products and a head's
# q, k are L2 norms over 4 rounded numbers (bf16 read 0.027 and 0.094 on two
# seeds, float8 0.42 and 1.25; the recurrence alone, o in f32, 2.8e-7 over
# 128 tokens, its bf16 state 0.0029; gate_cosine 0.83-0.85, float8 -0.03).
# The rehearsal shows that the check runs, not how close the program comes.
TOLERANCE_TINY = {
    "f32": dict(TOLERANCE["f32"], cosine_from=2 ** 6),
    "bf16": {"logits_rel_l2": 0.2, "scan_rel_l2": 3e-4, "loss_rel": 5e-3,
             "step_loss_rel": 5e-3, "update_norm_rel": 0.5,
             "update_cosine": 0.7, "gate_cosine": 0.5,
             "cosine_from": 2 ** 10},
}
L2_EPS = 1e-6
BETA_MAX = 2.0          # linear_allow_neg_eigval: beta in (0, 2)


def narrowed(x, dtype):
    """x rounded to ``dtype`` and back to float32. The barrier keeps the two
    casts: a compiler that is allowed excess precision drops the bare pair
    (the v5e's did: PR 48's first chip run read a float8 control that moved
    nothing in the first step)."""
    return jax.lax.optimization_barrier(x.astype(dtype)).astype(jnp.float32)


def rms_norm(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * g


def short_conv(x, w):
    """x (S, C), w (taps, C): y_t = silu(sum_j w[j] x_{t-j}), zeros before
    the sequence's start — a written-out loop over the taps."""
    s = x.shape[0]
    y = jnp.zeros_like(x)
    for j in range(w.shape[0]):
        y = y + w[j] * jnp.concatenate(
            [jnp.zeros((j, x.shape[1]), x.dtype), x[:s - j]], 0)
    return jax.nn.silu(y)


def delta_rule(q, k, v, g, beta, t_block=None, ckpt=lambda f: f,
               state_round=lambda s: s):
    """One sequence, token by token: q, k (S, H, d_k), v (S, H, d_v), g and
    beta (S, H) -> (S, H, d_v). ``t_block``: the scan over t is cut into
    scans of that many tokens, each under ``ckpt`` (what a gradient keeps
    is then a state a block and the states of ONE block)."""
    s, h, d_k = q.shape
    t_block = t_block or s

    def token(state, xs):
        q_t, k_t, v_t, g_t, b_t = xs
        sbar = jnp.exp(g_t)[:, None, None] * state
        u = b_t[:, None] * (v_t - jnp.einsum("hkv,hk->hv", sbar, k_t))
        state = state_round(sbar + k_t[..., None] * u[:, None, :])
        return state, jnp.einsum("hkv,hk->hv", state, q_t) * d_k ** -0.5

    def block(state, xs):
        return jax.lax.scan(token, state, xs)

    blocks = tuple(x.reshape((s // t_block, t_block) + x.shape[1:])
                   for x in (q, k, v, g, beta))
    _, o = jax.lax.scan(ckpt(block),
                        jnp.zeros((h, d_k, v.shape[-1]), jnp.float32), blocks)
    return o.reshape((s,) + o.shape[2:])


def attention(q, k, v, q_block=None, ckpt=lambda f: f):
    """One sequence: q, k, v (S, H, d) -> (S, H d), position t attending to
    s <= t: a dense mask, no positions."""
    s, h, d = q.shape
    q_block = q_block or s

    def rows(start):
        qb = jax.lax.dynamic_slice_in_dim(q, start, q_block, 0)
        scores = jnp.einsum("qhd,khd->hqk", qb, k) / jnp.sqrt(jnp.float32(d))
        t = (start + jnp.arange(q_block))[:, None]
        probs = jax.nn.softmax(
            jnp.where(jnp.arange(s)[None] <= t, scores, -jnp.inf), -1)
        return jnp.einsum("hqk,khd->qhd", probs, v)

    return jax.lax.map(ckpt(rows), jnp.arange(0, s, q_block)).reshape(s, -1)


def head_share(weights, heads: int, held: int, first: int):
    """The weights of heads ``first`` .. ``first + held - 1`` out of a
    model's with ``heads`` heads, under the same names: the held heads'
    rows of every (out, in) projection that EMITS heads, their taps, A_log,
    dt_bias and QK-norm gains, and the held heads' columns of the two
    W_o. Everything else (FFN, norms over the hidden state, the one d_v-wide
    out-norm gain, embedding, head) is every share's own whole copy."""
    def cut(x, axis):
        d = x.shape[axis] // heads
        return jax.lax.slice_in_dim(x, first * d, (first + held) * d,
                                    axis=axis)

    out = {}
    for name, blobs in weights.items():
        part = name.split("_", 1)[1] if name[0] == "l" else name
        if part in ("gdn_q", "gdn_k", "gdn_v", "gdn_a", "gdn_b", "gdn_z",
                    "attn_q", "attn_k", "attn_v", "gdn_decay", "attn_qnorm",
                    "attn_knorm"):
            out[name] = [cut(b, 0) for b in blobs]
        elif part in ("gdn_conv_q", "gdn_conv_k", "gdn_conv_v", "gdn_o",
                      "attn_o"):
            out[name] = [cut(b, 1) for b in blobs]
        else:
            out[name] = list(blobs)
    return out


def forward(cfg, weights, tokens, targets=None, last=None, q_block=None,
            round_to=None, remat=False, round_when=None, t_block=None,
            upto=None):
    """tokens (N, S) int -> {"logits" (N, last or S, V); "decay_mean" and
    "beta_over_one" (one a linear layer: the mean exp(g) and the share of
    beta > 1); "mixed" (L, N, S, D): every layer's Mix(x) BEFORE N_a, the
    part that the shares of a layer's heads sum to; and with ``targets``
    "nll" (N, S)}. ``cfg``: num_hidden_layers, layer_types ("linear" /
    "full" a layer that is run), num_heads (held), rms_norm_eps."""
    with jax.default_matmul_precision("highest"):
        eps = cfg["rms_norm_eps"]
        n_h = cfg["num_heads"]
        ckpt = jax.checkpoint if remat else (lambda f: f)
        if remat and t_block is None:
            t_block = next(b for b in (128, 64, 32, 16, 8, 4, 2, 1)
                           if tokens.shape[1] % b == 0)

        def f32(blobs):
            return [jnp.asarray(b, jnp.float32) for b in blobs]

        def straight_through(x, r):
            return x + jax.lax.stop_gradient(r - x)

        def rnd(x):
            if round_to is None:
                return x
            r = narrowed(x, round_to)
            if round_when is not None:
                r = jnp.where(round_when, r, x)
            return straight_through(x, r)

        def mm(x, w):                    # x (.., in) by an (out, in) matrix
            return rnd(x) @ rnd(w).T

        def unit(x):                     # an L2 norm over each head's dims
            return x * jax.lax.rsqrt(jnp.sum(x * x, -1, keepdims=True)
                                     + L2_EPS)

        def linear(w, x):                # one sequence (S, D) -> (S, D)
            s = x.shape[0]
            heads = lambda y: y.reshape(s, n_h, -1)
            q, k, v = (heads(short_conv(mm(x, w["gdn_" + t][0]),
                                        w["gdn_conv_" + t][0]))
                       for t in "qkv")
            a_log, dt_bias = w["gdn_decay"]
            g = -jnp.exp(a_log) * jax.nn.softplus(
                mm(x, w["gdn_a"][0]) + dt_bias)
            beta = BETA_MAX * jax.nn.sigmoid(mm(x, w["gdn_b"][0]))
            o = delta_rule(rnd(unit(q)), rnd(unit(k)), rnd(v), g, beta,
                           t_block if remat else None, ckpt)
            o = rms_norm(o, w["gdn_onorm"][0], eps).reshape(s, -1)
            gate = jax.nn.silu(mm(x, w["gdn_z"][0]))
            return (mm(o * gate, w["gdn_o"][0]), jnp.mean(jnp.exp(g)),
                    jnp.mean((beta > 1.0).astype(jnp.float32)))

        def full(w, x):
            s = x.shape[0]
            q = rms_norm(mm(x, w["attn_q"][0]), w["attn_qnorm"][0], eps)
            k = rms_norm(mm(x, w["attn_k"][0]), w["attn_knorm"][0], eps)
            v = mm(x, w["attn_v"][0])
            o = attention(*(rnd(y).reshape(s, n_h, -1) for y in (q, k, v)),
                          q_block, ckpt)
            return mm(o, w["attn_o"][0]), jnp.float32(0), jnp.float32(0)

        def layer(i, w, x):
            mix = linear if cfg["layer_types"][i] == "linear" else full
            mixed, decay, over = jax.vmap(lambda one: mix(w, one))(x)
            h = x + rms_norm(mixed, w["mix_norm"][0], eps)
            f = mm(jax.nn.silu(mm(h, w["ffn_gate"][0]))
                   * mm(h, w["ffn_up"][0]), w["ffn_down"][0])
            return (h + rms_norm(f, w["ffn_norm"][0], eps), mixed,
                    jnp.mean(decay), jnp.mean(over))

        x = f32(weights["embed"])[0][tokens]                    # (N, S, D)
        mixes, decays, overs = [], [], []
        n_layers = cfg["num_hidden_layers"] if upto is None else upto
        for i in range(n_layers):
            pre = f"l{i}_"
            w = {name[len(pre):]: f32(blobs)
                 for name, blobs in weights.items() if name.startswith(pre)}
            x, mixed, decay, over = ckpt(
                lambda w, x, i=i: layer(i, w, x))(w, x)
            mixes.append(mixed)
            if cfg["layer_types"][i] == "linear":
                decays.append(decay)
                overs.append(over)
        out = {"mixed": jnp.stack(mixes), "decay_mean": jnp.stack(decays),
               "beta_over_one": jnp.stack(overs), "state": x}
        if upto is not None:
            return out
        xf = rms_norm(x, f32(weights["final_norm"])[0], eps)
        w_head = f32(weights["lm_head"])[0]

        def head(seq):                   # one sequence: the vocabulary is
            xs, tgt = seq                # wide, (S, V) at a time
            whole = mm(xs, w_head)
            kept = whole if last is None else whole[-last:]
            if tgt is None:
                return kept, None
            return kept, -jnp.take_along_axis(
                jax.nn.log_softmax(whole, -1), tgt[:, None], -1)[:, 0]

        out["logits"], nll = jax.lax.map(ckpt(head), (xf, targets))
        if targets is not None:
            out["nll"] = nll
        return out


def loss(cfg, weights, tokens, targets, **how):
    """-> (mean next-token NLL, forward's dict); ``how`` is ``forward``'s
    ``last`` / ``q_block`` / ``round_to`` / ``round_when`` / ``t_block`` /
    ``remat``."""
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
    AdamW from zero moments on every blob. ``opt``: ``rate`` and ``decay``
    as {layer: [a number a blob]} (the step's learning rate x the blob's
    lr_mult, the weight decay x its decay_mult), ``clip``, ``b1``, ``b2``,
    ``eps``. -> {"loss", "grad_norm", "change": {layer: [w' - w]}}"""
    start = {k: [jnp.asarray(b, jnp.float32) for b in v]
             for k, v in weights.items()}
    total, grads = jax.value_and_grad(
        lambda w: loss(cfg, w, tokens, targets, **how)[0])(start)
    norm = jnp.sqrt(sum(jnp.sum(g * g) for g in jax.tree.leaves(grads)))
    scale = jnp.where(norm > opt["clip"], opt["clip"] / norm, 1.0)
    change = {}
    for name, blobs in grads.items():
        change[name] = []
        for j, g in enumerate(blobs):
            new, _, _ = adamw_step(
                start[name][j], g * scale, 0.0, 0.0, 1, opt["rate"][name][j],
                opt["decay"][name][j], opt["b1"], opt["b2"], opt["eps"])
            change[name].append(new - start[name][j])
    return {"loss": total, "grad_norm": norm, "change": change}
