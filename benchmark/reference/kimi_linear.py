"""Plain reference for Kimi-Linear-48B-A3B's block (config.json of
moonshotai/Kimi-Linear-48B-A3B-Instruct, ``model_type: kimi_linear``;
arXiv:2510.26692; what config.json does not say is under ``assumed`` in
configs/kimi_linear_48b.json): forward, the next-token loss and, through
``jax.grad`` of ``loss``, every gradient. Straightforward ``jax.numpy`` in
float32 under ``jax.default_matmul_precision("highest")``: the gated delta
rule TOKEN BY TOKEN (a ``lax.scan`` over t, no chunks, no triangular
system), latent attention as a full masked softmax in blocks of queries, a
Python loop over experts, no kernel, no sort, nothing imported from the
program (``remat`` wraps a layer, ``t_block`` tokens of the recurrence, a
block of queries, an expert and a sequence's head in ``jax.checkpoint``:
the same arithmetic, so that the gradient of two sequences of 8,192 at the
published widths fits one chip). Per sequence x (S, D), H heads, E experts
of which k a token:

    x = E_tok[ids]
    for each layer i:   h = x + Mix_i(N1(x));  x = h + FFN_i(N2(h))
                                                  N*: RMSNorm, own gain
    KDA layer (layer_types[i] "kda"), a = N1(x), d = d_k = d_v:
      q~ = a W_q, k~ = a W_k, v~ = a W_v                      (S, H d)
      c'_t = silu(sum_j w[j] * c~_{t-j})   per channel, 4 taps, zeros
                                           before the sequence's start  (a)
      q = q' / sqrt(sum_d q'^2 + 1e-6) per head, k likewise; v = v'     (b)
      g_t = -exp(A_log_h) softplus(W_fu (W_fd a_t) + dt_bias)   (H, d)  (c)
      beta_t = sigmoid(W_b a_t)                                  (H)
      S_0 = 0 (d_k, d_v) a head;  Sbar_t = Diag(exp g_t) S_{t-1}
      S_t = Sbar_t + beta_t k_t (v_t - Sbar_t^T k_t)^T
      o_t = S_t^T q_t d^-0.5                                            (d)
      Mix = (RMSNorm_d(o) * gain * sigmoid(W_gu (W_gd a))) W_o          (e)
    MLA layer ("mla"): q = a W_q (S, H, 192);  [c ; k_pe] = a W_kva
      (512 + 64);  k_nope = N_c(c) W_kvb_k, v = N_c(c) W_kvb_v (S, H, 128)
      k_h = [k_nope_h ; k_pe], NO rotation anywhere (mla_use_nope)     (f)
      Mix = softmax_causal(q k^T / sqrt(192)) v  W_o
    dense layer (the first ``num_dense_layers``):
      FFN = (silu(u W_gate) * (u W_up)) W_down
    MoE layer:                                                         (g)
      s = sigmoid(u W_r) (E) f32, never rounded
      chosen = the k largest of s + b      b: selection bias, no gradient
      w_e = route_scale * s_e / sum_{chosen} s   for chosen e
      FFN = sum_{chosen e that is HELD} w_e E_e(u) + Shared(u)
    logits = N_f(x) W_head^T (untied);  loss = mean NLL

``held`` is the set of expert ids whose weights ``weights`` carries, in
ascending order (stack row i is expert held[i]); None = all E. An assignment
to an expert that is not held adds nothing: the routed parts of disjoint
``held`` sets sum to the whole layer's routed output, and the shared expert
is in EVERY share's output — whoever sums shares counts it once
(``forward``'s "routed" is the part to sum). The balancing rule
(``next_bias``) is the step's: b_e + rate * sign(T k / E - n_e), n_e the
assignments to e over all E, held or not.

``choice`` (one (N, S, k) int array a MoE layer) hands the experts the
PROGRAM chose to this reference (``route_flips`` counts the handed
assignments its own top-k does not have); ``q_block`` computes the
attention of that many queries at a time; ``last`` keeps the logits of the
last ``last`` positions. Two controls show that a tolerance can tell
precisions apart, never used for ``correct``: ``round_to`` rounds every
matmul input (and q, k, v before the recurrence and the attention) to a
narrower type and back, the gradient passing straight through
(``round_when``, a traced bool, switches it inside one compiled program);
``state_dtype`` rounds the recurrence's STATE to that type after every
token.

``train_step`` is one whole step of the solver on this model, as plainly:
``jax.grad`` of ``loss``, the global-norm clip, AdamW (``adamw_step``), the
balancing rule on the selection biases, and returns every blob's CHANGE.

Weights come as ``{layer name: [blobs]}`` under the prototxt's names:
``embed``, ``l<i>_{attn_norm, ffn_norm}``; a KDA layer's
``l<i>_kda_{q,k,v}``, ``l<i>_kda_conv_{q,k,v} [w (taps, H d)]``,
``l<i>_kda_decay_{down,up}``, ``l<i>_kda_decay [A_log (H), dt_bias (H d)]``,
``l<i>_kda_beta``, ``l<i>_kda_onorm [gain (d)]``,
``l<i>_kda_ogate_{down,up}``, ``l<i>_kda_o``; an MLA layer's
``l<i>_mla_{q, kva, kvnorm, kvb_k, kvb_v, o}``; a dense layer's
``l<i>_ffn_{gate,up,down}``, a MoE layer's ``l<i>_router [w (E, D), bias]``,
``l<i>_moe [gate (G', F, D), up, down (G', D, F)]`` and
``l<i>_shared_{gate,up,down}``; ``final_norm``, ``lm_head``; matrices are
(out, in).

Departures from the published description (the configuration's
``departures`` says the same): W_kvb is held as two matrices, its key rows
and its value rows (a permutation of the published matrix's rows); the
output gate's up-projection has no bias.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

# program against reference at the PUBLISHED widths (the chip run's
# `correct`), per numeric policy of the program. f32: the same products in
# another order. bf16, every limit from readings on the v5e (PERF.md, PR 41,
# section 6 has them seed by seed): the program under bf16; this reference
# with its matmul inputs rounded to float8 e4m3, the nearest precision below;
# and this reference with the recurrence's state rounded to bf16 after every
# token. Each control has to fail at least one limit:
# - logits_rel_l2 (trained weights, the last 512 positions of one sequence
#   of 8,192, the program's experts handed over): bf16 0.0107-0.0126 over
#   nine seeds, the bf16 state 0.0218-0.0268, float8 0.0321-0.0371: 0.017
#   between the program's largest and the nearer control's smallest, a third
#   of room on either side. (Three times Trinity's bf16 reading: the logits
#   pass four recurrences of 8,192 steps whose q, k, v arrive rounded.)
# - update_cosine (the worst leaf of 2**16 numbers or more, in every run a
#   router's (256, 2304) matrix: fresh sigmoid scores are near-ties, so the
#   step's free-running top-8 differs between bf16 and f32 inputs): bf16
#   0.851-0.879, float8 0.707-0.734: 0.81 between, Trinity's. (The bf16
#   state does not move the first step: it fails by the logits alone.)
# - loss_rel, update_norm_rel: the precision hardly moves them (float8 reads
#   among bf16's seeds). loss_rel 0.3e-5-8.4e-5: the accepted cells' 2.5e-4
#   (three times of room). update_norm_rel 0.0013-0.0035 (a 128-number head
#   gain): between the reading and 1, which a state left unchanged reads,
#   with the more room above.
# - step_loss_rel: NOT a limit under bf16 (None), as in Trinity's cell: the
#   first step's loss on fresh weights read 1.1e-5-5.3e-5 and float8 up to
#   1.4e-4; the loss is held to loss_rel on the trained weights. A fact.
# - bias_margin, bias_compared_share: Trinity's (a selection bias is
#   compared where its expert's count lies further than a tenth of the even
#   split from it: 890-920 of 1,024 were, none wrong).
TOLERANCE = {
    "f32": {"logits_rel_l2": 2e-4, "loss_rel": 1e-5,
            "step_loss_rel": 1e-5, "update_norm_rel": 1e-3,
            "update_cosine": 0.999, "cosine_from": 2 ** 16,
            "bias_margin": 0.0, "bias_compared_share": 0.25},
    "bf16": {"logits_rel_l2": 0.017, "loss_rel": 2.5e-4,
             "step_loss_rel": None, "update_norm_rel": 0.1,
             "update_cosine": 0.81, "cosine_from": 2 ** 16,
             "bias_margin": 0.1, "bias_compared_share": 0.25},
}
# at a CPU rehearsal's widths a logit is a sum of 64 products and a handful
# of the tokens change an expert. The rehearsal shows that the check runs,
# not how close the program comes.
TOLERANCE_TINY = {
    "f32": dict(TOLERANCE["f32"], cosine_from=2 ** 6),
    "bf16": {"logits_rel_l2": 6e-2, "loss_rel": 5e-3,
             "step_loss_rel": 5e-3, "update_norm_rel": 0.5,
             "update_cosine": 0.7, "cosine_from": 2 ** 10,
             "bias_margin": 0.5, "bias_compared_share": 0.05},
}
L2_EPS = 1e-6


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
    """One sequence, token by token: q, k, g (S, H, d_k), v (S, H, d_v),
    beta (S, H) -> (S, H, d_v). ``t_block``: the scan over t is cut into
    scans of that many tokens, each under ``ckpt`` (what a gradient keeps
    is then a state a block and the states of ONE block)."""
    s, h, d_k = q.shape
    t_block = t_block or s

    def token(state, xs):
        q_t, k_t, v_t, g_t, b_t = xs
        sbar = jnp.exp(g_t)[..., None] * state
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
    """One sequence: q, k (S, H, d), v (S, H, d_v) -> (S, H d_v), position
    t attending to s <= t: a dense mask."""
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


def next_bias(bias, counts, rate):
    """The balancing rule: ``counts`` (E,) assignments per expert of one
    step."""
    counts = jnp.asarray(counts, jnp.float32)
    return bias + rate * jnp.sign(jnp.sum(counts) / counts.shape[0] - counts)


def forward(cfg, weights, tokens, targets=None, held=None, last=None,
            q_block=None, round_to=None, choice=None, remat=False,
            round_when=None, state_dtype=None, t_block=None):
    """tokens (N, S) int -> {"logits" (N, last or S, V); "counts" (M, E)
    assignments per expert by this reference's own top-k, one row a MoE
    layer; "choice" (M, N, S, k) that top-k; "route_flips" (M,); "routed"
    (M, N, S, D) each MoE layer's routed part and "shared" (M, N, S, D) its
    shared expert's; and with ``targets`` "nll" (N, S)}. ``cfg``:
    num_hidden_layers, num_dense_layers, layer_types ("kda" / "mla" a layer
    that is run), num_heads, kv_lora_rank, qk_nope_head_dim, num_experts
    (what the router scores), num_experts_per_tok, route_scale,
    rms_norm_eps."""
    with jax.default_matmul_precision("highest"):
        eps = cfg["rms_norm_eps"]
        n_h = cfg["num_heads"]
        n_exp, top_k = cfg["num_experts"], cfg["num_experts_per_tok"]
        held = list(range(n_exp)) if held is None else sorted(held)
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
            r = x.astype(round_to).astype(jnp.float32)
            if round_when is not None:
                r = jnp.where(round_when, r, x)
            return straight_through(x, r)

        def state_round(s):
            if state_dtype is None:
                return s
            # reduce_precision, not a cast there and back: a compiler that
            # is allowed excess precision drops the pair of casts
            kind = jnp.finfo(state_dtype)
            return straight_through(s, jax.lax.reduce_precision(
                s, exponent_bits=kind.nexp, mantissa_bits=kind.nmant))

        def mm(x, w):                    # x (.., in) by an (out, in) matrix
            return rnd(x) @ rnd(w).T

        def mlp(u, gate, up, dn):        # a SiLU-gated MLP, any width
            return mm(jax.nn.silu(mm(u, gate)) * mm(u, up), dn)

        def unit(x):                     # an L2 norm over each head's dims
            return x * jax.lax.rsqrt(jnp.sum(x * x, -1, keepdims=True)
                                     + L2_EPS)

        def kda(w, a):                   # one sequence (S, D) -> (S, D)
            s = a.shape[0]
            heads = lambda x: x.reshape(s, n_h, -1)
            q, k, v = (heads(short_conv(mm(a, w["kda_" + t][0]),
                                        w["kda_conv_" + t][0]))
                       for t in "qkv")
            a_log, dt_bias = w["kda_decay"]
            g = -jnp.exp(a_log)[:, None] * heads(jax.nn.softplus(
                mm(mm(a, w["kda_decay_down"][0]), w["kda_decay_up"][0])
                + dt_bias))
            beta = jax.nn.sigmoid(mm(a, w["kda_beta"][0]))
            o = delta_rule(rnd(unit(q)), rnd(unit(k)), rnd(v), g, beta,
                           t_block if remat else None, ckpt, state_round)
            gate = jax.nn.sigmoid(mm(mm(a, w["kda_ogate_down"][0]),
                                     w["kda_ogate_up"][0]))
            o = rms_norm(o, w["kda_onorm"][0], eps).reshape(s, -1)
            return mm(o * gate, w["kda_o"][0]), jnp.mean(jnp.exp(g))

        def mla(w, a):
            s = a.shape[0]
            rank, nope = cfg["kv_lora_rank"], cfg["qk_nope_head_dim"]
            q = mm(a, w["mla_q"][0]).reshape(s, n_h, -1)
            kva = mm(a, w["mla_kva"][0])
            c = rms_norm(kva[:, :rank], w["mla_kvnorm"][0], eps)
            k_pe = jnp.broadcast_to(kva[:, None, rank:],
                                    (s, n_h, kva.shape[1] - rank))
            k = jnp.concatenate(
                [mm(c, w["mla_kvb_k"][0]).reshape(s, n_h, nope), k_pe], -1)
            v = mm(c, w["mla_kvb_v"][0]).reshape(s, n_h, -1)
            o = attention(rnd(q), rnd(k), rnd(v), q_block, ckpt)
            return mm(o, w["mla_o"][0]), jnp.float32(0)

        def moe(w, u, handed):
            """-> (routed part, shared part, counts (E,), own top-k
            (N, S, k), flips against ``handed``)."""
            w_r, bias = w["router"]
            s = jax.nn.sigmoid(u @ w_r.T)        # the router: never rounded
            _, own = jax.lax.top_k(s + jax.lax.stop_gradient(bias), top_k)
            e = own if handed is None else handed            # (N, S, k)
            picked = jnp.sum(jax.nn.one_hot(e, n_exp), -2)   # (N, S, E) 0/1
            gates = s * picked
            gates = cfg["route_scale"] * gates \
                / jnp.sum(gates, -1, keepdims=True)
            gate, up, dn = w["moe"]
            routed = jnp.zeros_like(u)
            for row, which in enumerate(held):      # every token, weighed
                routed = routed + gates[..., which, None] * ckpt(mlp)(
                    u, gate[row], up[row], dn[row])
            shared = mlp(u, w["shared_gate"][0], w["shared_up"][0],
                         w["shared_down"][0])
            mine = jnp.sum(jax.nn.one_hot(own, n_exp), -2)
            flips = jnp.sum(picked * (1.0 - mine))
            return routed, shared, jnp.sum(mine, (0, 1)), own, flips

        def layer(i, w, x, handed):
            mix = kda if cfg["layer_types"][i] == "kda" else mla
            a = rms_norm(x, w["attn_norm"][0], eps)
            mixed, decay = jax.vmap(lambda one: mix(w, one))(a)
            h = x + mixed
            u = rms_norm(h, w["ffn_norm"][0], eps)
            if i < cfg["num_dense_layers"]:
                f = mlp(u, w["ffn_gate"][0], w["ffn_up"][0],
                        w["ffn_down"][0])
                extra = None
            else:
                routed, shared, n_e, own, flips = moe(w, u, handed)
                f = routed + shared
                extra = (n_e, own, flips, routed, shared)
            return h + f, extra, jnp.mean(decay)

        x = f32(weights["embed"])[0][tokens]                    # (N, S, D)
        per_moe, decays = [], []
        for i in range(cfg["num_hidden_layers"]):
            pre = f"l{i}_"
            w = {name[len(pre):]: f32(blobs)
                 for name, blobs in weights.items() if name.startswith(pre)}
            at = len(per_moe)
            handed = None if choice is None or i < cfg["num_dense_layers"] \
                else jnp.asarray(choice[at])
            x, extra, decay = ckpt(
                lambda w, x, handed, i=i: layer(i, w, x, handed))(
                    w, x, handed)
            decays.append(decay)
            if extra is not None:
                per_moe.append(extra)
        xf = rms_norm(x, f32(weights["final_norm"])[0], eps)
        w_head = f32(weights["lm_head"])[0]

        def head(seq):                   # one sequence: the vocabulary is
            xs, tgt = seq                # wide, (S, V) at a time
            full = mm(xs, w_head)
            kept = full if last is None else full[-last:]
            if tgt is None:
                return kept, None
            return kept, -jnp.take_along_axis(
                jax.nn.log_softmax(full, -1), tgt[:, None], -1)[:, 0]

        logits, nll = jax.lax.map(ckpt(head), (xf, targets))
        names = ("counts", "choice", "route_flips", "routed", "shared")
        out = {"logits": logits, "decay_mean": jnp.stack(decays)}
        out.update({name: jnp.stack([m[j] for m in per_moe])
                    for j, name in enumerate(names) if per_moe})
        if targets is not None:
            out["nll"] = nll
        return out


def loss(cfg, weights, tokens, targets, **how):
    """-> (mean next-token NLL, forward's dict); ``how`` is ``forward``'s
    ``held`` / ``last`` / ``q_block`` / ``round_to`` / ``round_when`` /
    ``state_dtype`` / ``t_block`` / ``choice`` / ``remat``."""
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


def router_names(weights):
    """The MoE layers' routers, in layer order."""
    return sorted((n for n in weights if n.endswith("_router")),
                  key=lambda n: int(n[1:-7]))


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
    -> {"loss", "counts" (M, E), "grad_norm", "change": {layer: [w' - w]}}"""
    biases = router_names(weights)

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
    for i, name in enumerate(biases):
        bias = jnp.asarray(weights[name][-1], jnp.float32)
        change[name].append(
            next_bias(bias, counts[i], opt["bias_rate"]) - bias)
    return {"loss": total, "counts": counts, "grad_norm": norm,
            "change": change}
