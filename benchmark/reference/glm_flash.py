"""Plain reference for GLM-4.7-Flash's block and its prediction module
(config.json of zai-org/GLM-4.7-Flash, ``model_type: glm4_moe_lite``,
30B-A3B; the DeepSeek-V3 block and its multi-token prediction of depth 1,
arXiv:2412.19437 sections 2.1 and 2.2; what config.json does not say is
under ``assumed`` in configs/glm_4_7_flash.json): forward, BOTH losses and,
through ``jax.grad`` of ``loss``, every gradient. Straightforward
``jax.numpy`` in float32 under ``jax.default_matmul_precision("highest")``:
latent attention as a full masked softmax in blocks of queries with the
shared key part EXPLICIT (one ``k_pe`` a token, broadcast in the score, so
that it cannot inherit the program's layout), a Python loop over experts, no
kernel, no sort, nothing imported from the program (``remat`` wraps a layer,
a block of queries, an expert and a sequence's head in ``jax.checkpoint``:
the same arithmetic, so that the gradient of two sequences of 8,192 at the
published widths fits one chip). Per sequence x (S, D), H heads, E experts
of which k a token, tok(t) the sequence's ids and ``targets``(t) = tok(t+1):

    x_0 = Emb[tok]
    for each layer i:   h = x + MLA_i(N1(x));  x = h + FFN_i(N2(h))
                                                  N*: RMSNorm, own gain
    MLA, a = N1(x):
      c_q = N_q(a W_qa) (768);  q = c_q W_qb, H heads of [q_nope (192) ;
      q_rope (64)];  [c_kv (512) ; k_pe (64)] = a W_kva;  c = N_c(c_kv)
      k_nope_h (192) = c W_kvb_k,  v_h (256) = c W_kvb_v                (a)
      R_t: rotate-half rotary positions over the WHOLE 64 (theta 1e6):
        frequency j serves dims j and j + 32                            (b)
      score_h(t, s) = (q_nope_h(t) . k_nope_h(s)
                       + R_t q_rope_h(t) . R_s k_pe(s)) / sqrt(256)
      MLA = [softmax_{s <= t}(score_h) v_h]_h W_o        (H 256 -> D)
    dense layer (the first ``num_dense_layers``):
      FFN = (silu(u W_gate) * (u W_up)) W_down
    sparse layer:
      s = sigmoid(u W_r) (E) f32, never rounded
      chosen = the k largest of s + b      b: selection bias, no gradient
      w_e = route_scale * s_e / sum_{chosen} s   for chosen e
      FFN = sum_{chosen e that is HELD} w_e E_e(u) + Shared(u)
    logits = N_f(x_L) W_head^T (untied)
    prediction module (``num_nextn_predict_layers`` 1), x_L BEFORE N_f:
      z(t) = [N_e(Emb[tok(t+1)]) ; N_h(x_L(t))] W_eh^T  (2 D -> D)    (c)
      z' = one more sparse layer of its own weights on z, positions t  (d)
      logits'(t) = N_s(z'(t)) W_head^T     Emb, W_head: the arrays above
    loss = mean_t CE(logits(t), tok(t+1))
           + mtp_weight * mean_{t < S-1} CE(logits'(t), tok(t+2))
    The last position has no second-next token inside the sequence and is
    left out of the second mean (not given a made-up target).

``held`` is the set of expert ids whose weights ``weights`` carries, in
ascending order (stack row i is expert held[i]); None = all E. An assignment
to an expert that is not held adds nothing: the routed parts of disjoint
``held`` sets sum to the whole layer's routed output, and the shared expert
is in EVERY share's output — whoever sums shares counts it once
(``forward``'s "routed" is the part to sum). The balancing rule
(``next_bias``) is the step's: b_e + rate * sign(T k / E - n_e), n_e the
assignments to e over all E, held or not.

``choice`` (one (N, S, k) int array a sparse layer, the module's last) hands
the experts the PROGRAM chose to this reference (``route_flips`` counts the
handed assignments its own top-k does not have); ``q_block`` computes the
attention of that many queries at a time; ``last`` keeps the logits of the
last ``last`` positions. One control shows that a tolerance can tell
precisions apart, never used for ``correct``: ``round_to`` rounds every
matmul input (and q, k, v before the attention) to a narrower type and back,
the gradient passing straight through (``round_when``, a traced bool,
switches it inside one compiled program). ``fault`` plants one of
``FAULTS`` — a wrong program written down, for the tests that show that
each is caught by a limit.

``train_step`` is one whole step of the solver on this model, as plainly:
``jax.grad`` of ``loss``, the global-norm clip, AdamW (``adamw_step``), the
balancing rule on the selection biases, and returns every blob's CHANGE.

Weights come as ``{layer name: [blobs]}`` under the prototxt's names, a
block's under its prefix (``l<i>_``, the module's ``mtp_``):
``<p>{attn_norm, ffn_norm}``, ``<p>mla_{qa, qnorm, qb, kva, kvnorm, kvb_k,
kvb_v, o}``; a dense layer's ``<p>ffn_{gate,up,down}``, a sparse layer's
``<p>router [w (E, D), bias]``, ``<p>moe [gate (G', F, D), up, down
(G', D, F)]`` and ``<p>shared_{gate,up,down}``; ``embed``, ``final_norm``,
``lm_head``; the module's ``mtp_{enorm, hnorm, eh, snorm}``. There is no
``mtp_embed`` and no ``mtp_head``: the module reads ``embed`` and
``lm_head``, and their gradients are the sums over both users. Matrices are
(out, in).

Departures from the published description (the configuration's
``departures`` says the same): (a) W_kvb is held as two matrices, its key
rows and its value rows (a permutation of the published matrix's rows);
(b) rotate-half pairing inside the 64 rotary dims where the published code
pairs neighbours (a fixed permutation of 64 rows of W_qb and of W_kva);
(c) in W_eh's input the embedding comes first (a column permutation of the
other order); (d) the module's block is a sparse layer as layers 1-46 are
and runs the same positions.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

# program against reference at the PUBLISHED widths (the chip run's
# `correct`), per numeric policy of the program. f32: the same products in
# another order. bf16: every limit from readings on the v5e over fifteen
# seeds (PERF.md, PR 56, section 6): the program under bf16, and this
# reference with its matmul inputs rounded to float8 e4m3, the nearest
# precision below, which has to fail at least one limit (it fails four):
# - logits_rel_l2, mtp_logits_rel_l2 (trained weights, the last 512
#   positions of one sequence of 8,192, the program's experts handed over):
#   bf16 0.0020-0.0030 and 0.0020-0.0028, float8 0.0087-0.0162 and
#   0.0085-0.0163: 0.006 between the program's largest and the control's
#   smallest, twice the one and 0.7 of the other. (Under Trinity's
#   0.004-0.005: no gate, QK-norm or post-norm between the attention and
#   the logits, and heads twice as wide.)
# - update_cosine (the worst leaf of 2**16 numbers or more, in every run a
#   router's (64, 2048) matrix: fresh sigmoid scores are near-ties, so the
#   step's free-running top-4 differs between bf16 and f32 inputs): bf16
#   0.858-0.944, float8 0.528-0.726: 0.79 between, half way (Trinity's and
#   Kimi's 0.81 would leave the program's lowest seed 0.05).
# - group_cosine (the leaves only this configuration's mechanisms feed, each
#   group as one vector, ``glm_train.new_leaves``; the worst group in every
#   run the module's own leaves): bf16 0.9839-0.9900, float8 0.9255-0.9416:
#   0.96 between. A wrong rotation reads under 0.1 in two groups at the
#   rehearsal's sizes (benchmark/tests/test_bench_glm.py).
# - loss_rel, update_norm_rel: the precision hardly moves them. loss_rel
#   (the MAIN loss) 1.1e-6-5.4e-5 (float8 9.9e-6-4.0e-4): the accepted
#   cells' 2.5e-4, twelve times the first reading. update_norm_rel
#   0.0043-0.0168 (a router's matrix; float8 0.017-0.057): 0.1, between the
#   reading and 1, which a state left unchanged reads, with the more room
#   above.
# - mtp_loss_rel: NOT a limit under bf16 (None; a fact in ``compared``). The
#   first reading, 3.1e-5, left the accepted 2.5e-4 eight times of room; the
#   sixth seed read 2.0e-4 (the other fourteen 2.5e-7-5.7e-5; float8 3.3e-5-
#   2.0e-4: the precision does not move it), and a limit that one seed in
#   fifteen comes within a fifth of fails some later PR's check on no fault.
#   The module's loss is held by the first-loss band (both cross-entropies
#   are in it), by mtp_logits_rel_l2 and by group_cosine's ``mtp_module``
#   group, the direction of the update of every leaf only that loss feeds:
#   wrong targets read 0.20 there, lambda 0 0.005 (the rehearsal's sizes).
# - step_loss_rel: NOT a limit under bf16 (None), as in Trinity's and
#   Kimi's cells: the first step's loss on fresh weights read 2.6e-5-4.7e-5;
#   the losses are held on the trained weights. A fact.
# - bias_margin, bias_compared_share: Trinity's (a selection bias is
#   compared where its expert's count lies further than a tenth of the even
#   split from it: 308-317 of 320 were, none wrong).
TOLERANCE = {
    "f32": {"logits_rel_l2": 2e-4, "mtp_logits_rel_l2": 2e-4,
            "loss_rel": 1e-5, "mtp_loss_rel": 1e-5,
            "step_loss_rel": 1e-5, "update_norm_rel": 1e-3,
            "update_cosine": 0.999, "group_cosine": 0.999,
            "cosine_from": 2 ** 16,
            "bias_margin": 0.0, "bias_compared_share": 0.25},
    "bf16": {"logits_rel_l2": 0.006, "mtp_logits_rel_l2": 0.006,
             "loss_rel": 2.5e-4, "mtp_loss_rel": None,
             "step_loss_rel": None, "update_norm_rel": 0.1,
             "update_cosine": 0.79, "group_cosine": 0.96,
             "cosine_from": 2 ** 16,
             "bias_margin": 0.1, "bias_compared_share": 0.25},
}
# at a CPU rehearsal's widths a logit is a sum of 32 products and a handful
# of the tokens change an expert. The rehearsal shows that the check runs
# and that each planted fault is caught, not how close the program comes.
TOLERANCE_TINY = {
    "f32": dict(TOLERANCE["f32"], cosine_from=2 ** 6),
    "bf16": {"logits_rel_l2": 6e-2, "mtp_logits_rel_l2": 6e-2,
             "loss_rel": 5e-3, "mtp_loss_rel": 5e-3,
             "step_loss_rel": 5e-3, "update_norm_rel": 0.5,
             "update_cosine": 0.7, "group_cosine": 0.7,
             "cosine_from": 2 ** 10,
             "bias_margin": 0.5, "bias_compared_share": 0.05},
}
# wrong programs, written down: what each changes is in ``forward``
FAULTS = ("rope_on_head_start", "k_pe_unrotated", "mtp_target_next",
          "mtp_weight_zero", "head_not_shared")


def rms_norm(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * g


def rotate(x, theta):
    """x (S, ..., R) at positions 0 .. S-1 -> the same, every pair (j,
    j + R/2) turned by t * theta^(-2j / R): rotate-half over the whole R."""
    s, r = x.shape[0], x.shape[-1]
    inv = theta ** (-jnp.arange(0, r, 2, dtype=jnp.float32) / r)
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * inv[None]   # (S, R/2)
    ang = ang.reshape((s,) + (1,) * (x.ndim - 2) + (r // 2,))
    a, b = x[..., :r // 2], x[..., r // 2:]
    return jnp.concatenate([a * jnp.cos(ang) - b * jnp.sin(ang),
                            b * jnp.cos(ang) + a * jnp.sin(ang)], -1)


def attention(q_nope, q_rope, k_nope, k_pe, v, q_block=None,
              ckpt=lambda f: f):
    """One sequence: q_nope, k_nope (S, H, dn), q_rope (S, H, dr), k_pe
    (S, dr) — ONE a token, every head's — v (S, H, dv) -> (S, H dv),
    position t attending to s <= t: a dense mask; the scores' scale is
    1 / sqrt(dn + dr)."""
    s, h, dn = q_nope.shape
    q_block = q_block or s
    scale = 1.0 / jnp.sqrt(jnp.float32(dn + q_rope.shape[-1]))

    def rows(start):
        qn = jax.lax.dynamic_slice_in_dim(q_nope, start, q_block, 0)
        qr = jax.lax.dynamic_slice_in_dim(q_rope, start, q_block, 0)
        scores = (jnp.einsum("qhd,khd->hqk", qn, k_nope)
                  + jnp.einsum("qhd,kd->hqk", qr, k_pe)) * scale
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
            round_when=None, fault=None):
    """tokens (N, S) int -> {"logits" (N, last or S, V); "counts" (M, E)
    assignments per expert by this reference's own top-k, one row a sparse
    layer, the module's block last; "choice" (M, N, S, k) that top-k;
    "route_flips" (M,); "routed" (M, N, S, D) each sparse layer's routed
    part and "shared" (M, N, S, D) its shared expert's; and with ``targets``
    "nll" (N, S), "mtp_logits" (N, last or S, V) and "mtp_nll" (N, S - 1)}.
    ``cfg``: num_hidden_layers, num_dense_layers, num_heads, q_lora_rank,
    kv_lora_rank, qk_nope_head_dim, qk_rope_head_dim, v_head_dim,
    num_experts (what the router scores), num_experts_per_tok, route_scale,
    rope_theta, rms_norm_eps, mtp_layers (0 or 1)."""
    assert fault is None or fault in FAULTS, fault
    with jax.default_matmul_precision("highest"):
        eps, theta = cfg["rms_norm_eps"], cfg["rope_theta"]
        n_h, rank = cfg["num_heads"], cfg["kv_lora_rank"]
        nope, rope = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"]
        n_exp, top_k = cfg["num_experts"], cfg["num_experts_per_tok"]
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
            return x + jax.lax.stop_gradient(r - x)  # straight through

        def mm(x, w):                    # x (.., in) by an (out, in) matrix
            return rnd(x) @ rnd(w).T

        def mlp(u, gate, up, dn):        # a SiLU-gated MLP, any width
            return mm(jax.nn.silu(mm(u, gate)) * mm(u, up), dn)

        def mla(w, a):                   # one sequence (S, D) -> (S, D)
            s = a.shape[0]
            c_q = rms_norm(mm(a, w["mla_qa"][0]), w["mla_qnorm"][0], eps)
            q = mm(c_q, w["mla_qb"][0]).reshape(s, n_h, nope + rope)
            kva = mm(a, w["mla_kva"][0])
            c = rms_norm(kva[:, :rank], w["mla_kvnorm"][0], eps)
            k_pe = kva[:, rank:]                               # (S, rope)
            k_nope = mm(c, w["mla_kvb_k"][0]).reshape(s, n_h, nope)
            v = mm(c, w["mla_kvb_v"][0]).reshape(s, n_h, -1)
            q_nope, q_rope = q[..., :nope], q[..., nope:]
            if fault == "rope_on_head_start":
                # the FIRST ``rope`` dims of every q and key head turn (the
                # rotation a head without a rotary part of its own gets),
                # not the rotary parts
                q_nope, k_nope = (jnp.concatenate(
                    [rotate(t[..., :rope], theta), t[..., rope:]], -1)
                    for t in (q_nope, k_nope))
                k_pe_t = k_pe
            elif fault == "k_pe_unrotated":
                q_rope, k_pe_t = rotate(q_rope, theta), k_pe
            else:
                q_rope, k_pe_t = rotate(q_rope, theta), rotate(k_pe, theta)
            o = attention(rnd(q_nope), rnd(q_rope), rnd(k_nope),
                          rnd(k_pe_t), rnd(v), q_block, ckpt)
            return mm(o, w["mla_o"][0])

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

        def layer(sparse, w, x, handed):
            a = rms_norm(x, w["attn_norm"][0], eps)
            h = x + jax.vmap(lambda one: mla(w, one))(a)
            u = rms_norm(h, w["ffn_norm"][0], eps)
            if not sparse:
                return h + mlp(u, w["ffn_gate"][0], w["ffn_up"][0],
                               w["ffn_down"][0]), None
            routed, shared, n_e, own, flips = moe(w, u, handed)
            return h + routed + shared, (n_e, own, flips, routed, shared)

        def under(prefix):
            return {name[len(prefix):]: f32(blobs)
                    for name, blobs in weights.items()
                    if name.startswith(prefix)}

        per_moe = []

        def block(prefix, sparse, x):
            handed = None if choice is None or not sparse \
                else jnp.asarray(choice[len(per_moe)])
            x, extra = ckpt(lambda w, x, handed: layer(sparse, w, x, handed))(
                under(prefix), x, handed)
            if extra is not None:
                per_moe.append(extra)
            return x

        table = f32(weights["embed"])[0]
        x = table[tokens]                                       # (N, S, D)
        for i in range(cfg["num_hidden_layers"]):
            x = block(f"l{i}_", i >= cfg["num_dense_layers"], x)
        w_head = f32(weights["lm_head"])[0]

        def head(seq):                   # one sequence: the vocabulary is
            xs, tgt, matrix = seq        # wide, (S, V) at a time
            full = mm(xs, matrix)
            kept = full if last is None else full[-last:]
            if tgt is None:
                return kept, None
            return kept, -jnp.take_along_axis(
                jax.nn.log_softmax(full, -1), tgt[:, None], -1)[:, 0]

        def heads(xf, tgt, matrix):
            return jax.lax.map(
                ckpt(lambda seq: head(seq + (matrix,))), (xf, tgt))

        logits, nll = heads(rms_norm(x, f32(weights["final_norm"])[0], eps),
                            targets, w_head)
        out = {"logits": logits}
        if targets is not None:
            out["nll"] = nll
        if cfg.get("mtp_layers") and targets is not None:
            # token t+1 is ``targets``(t); token t+2 is ``targets``(t+1),
            # which the last position does not have
            z_in = jnp.concatenate(
                [rms_norm(table[targets], f32(weights["mtp_enorm"])[0], eps),
                 rms_norm(x, f32(weights["mtp_hnorm"])[0], eps)], -1)
            z = block("mtp_", True, mm(z_in, f32(weights["mtp_eh"])[0]))
            if fault == "head_not_shared":
                # another matrix in the shared one's place: its rows turned
                matrix = jnp.roll(w_head, 1, 0)
            else:
                matrix = w_head
            second = jnp.concatenate(
                [targets[:, 1:], jnp.zeros_like(targets[:, :1])], 1)
            if fault == "mtp_target_next":
                second = targets         # token t+1 once more
            out["mtp_logits"], mtp_nll = heads(
                rms_norm(z, f32(weights["mtp_snorm"])[0], eps), second,
                matrix)
            out["mtp_nll"] = mtp_nll[:, :-1]
        names = ("counts", "choice", "route_flips", "routed", "shared")
        out.update({name: jnp.stack([m[j] for m in per_moe])
                    for j, name in enumerate(names) if per_moe})
        return out


def loss(cfg, weights, tokens, targets, **how):
    """-> (mean next-token NLL + mtp_weight x the module's mean over the
    S - 1 positions with a second-next token, forward's dict with
    "lm_loss" and "mtp_loss" beside); ``how`` is ``forward``'s ``held`` /
    ``last`` / ``q_block`` / ``round_to`` / ``round_when`` / ``choice`` /
    ``remat`` / ``fault``."""
    out = forward(cfg, weights, tokens, targets, **how)
    out["lm_loss"] = total = jnp.mean(out["nll"])
    if "mtp_nll" in out:
        out["mtp_loss"] = jnp.mean(out["mtp_nll"])
        if how.get("fault") != "mtp_weight_zero":
            total = total + cfg["mtp_weight"] * out["mtp_loss"]
    return total, out


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
    """The sparse layers' routers in the order they run: the layers', then
    the module's."""
    return sorted((n for n in weights if n.endswith("_router")),
                  key=lambda n: (n.startswith("mtp_"),
                                 int(n[1:-7]) if n[0] == "l" else 0))


def train_step(cfg, weights, tokens, targets, opt, **how):
    """The FIRST step of training from ``weights``: the loss over every
    position and its gradient (``jax.grad`` of ``loss``), the gradient
    scaled down to a global L2 norm of ``opt["clip"]`` where it is larger,
    AdamW from zero moments on every blob, and the balancing rule on the
    routers' selection biases (the LAST blob of every ``*_router``: no
    gradient, optimizer, decay or clip; not in the clip's norm).
    ``opt``: ``rate`` and ``decay`` as {layer: [a number a blob]} (the
    step's learning rate x the blob's lr_mult, the weight decay x its
    decay_mult), ``clip``, ``b1``, ``b2``, ``eps``, ``bias_rate``.
    -> {"loss", "lm_loss", "mtp_loss", "counts" (M, E), "grad_norm",
    "change": {layer: [w' - w]}}"""
    biases = router_names(weights)

    def trained(w):                      # the biases enter as constants
        return {name: blobs[:-1] if name in biases else list(blobs)
                for name, blobs in w.items()}

    def objective(some):
        whole = {name: blobs + [weights[name][-1]] if name in biases
                 else blobs for name, blobs in some.items()}
        total, out = loss(cfg, whole, tokens, targets, **how)
        return total, (out["counts"], out["lm_loss"],
                       out.get("mtp_loss", jnp.float32(0)))

    (total, (counts, lm, mtp)), grads = jax.value_and_grad(
        objective, has_aux=True)(
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
    return {"loss": total, "lm_loss": lm, "mtp_loss": mtp, "counts": counts,
            "grad_norm": norm, "change": change}
