"""Plain reference for SmallThinker-21BA3B-Instruct's block (config.json of
PowerInfer/SmallThinker-21BA3B-Instruct, ``model_type: smallthinker``,
arXiv:2507.20984; what config.json does not say is under ``assumed`` in
configs/smallthinker_21b.json): forward, the loss (next-token cross-entropy
plus the routers' balance and z losses) and, through ``jax.grad`` of
``loss``, every gradient. Straightforward ``jax.numpy`` in float32 under
``jax.default_matmul_precision("highest")``: dense attention in blocks of
queries under a dense mask, a Python loop over experts in which every held
expert computes every token, no kernel, no sort, no chunks, nothing imported
from the program (``remat`` wraps a layer, a block of queries, an expert and
a sequence's head in ``jax.checkpoint``: the same arithmetic, so that the
gradient of a sequence of 16,384 at the published widths fits one chip).
Per sequence x (S, D), H query and G key-value heads of d, per = H / G, E
experts of which k a token, W the window:

    x = E_tok[ids]
    for each layer i:
      a = N1(x)                                   N*: RMSNorm, own gain
      r = a W_r  (E)  f32, never rounded          the router reads the
      chosen = the k largest of r                 PRE-attention state
      w_e = softmax over the chosen of r          (the k weights sum to 1)
      q = a W_q (S, H, d);  k = a W_k, v = a W_v (S, G, d)   no bias, no norm
      window layer (sliding_window_layout[i] 1): rotate-half RoPE on q and
          k, t attends to s with t - W < s <= t
      global layer (0): no positions at all, s <= t
      o = softmax(q k^T / sqrt(d)) v, query head j reading kv head j // per
      h = x + o W_o
      u = N2(h)
      f = sum_{chosen e that is HELD} w_e W_down_e (relu(W_gate_e u) * (W_up_e u))
      x = h + f
    logits = N_f(x) W_head^T  (untied)
    loss = mean NLL + sum_i (balance_weight * lb_i + z_weight * z_i)
      lb_i = E * sum_e f_e P_e,  f_e = (assignments to e) / T (the f_e sum
             to k), P_e = the mean over the T tokens of softmax(r)_e over ALL
             E; z_i = mean(logsumexp(r)^2); T = every token of the batch

``held`` is the set of expert ids whose weights ``weights`` carries, in
ascending order (stack row i is expert held[i]); None = all E. An assignment
to an expert that is not held adds nothing (its term is left out): the
outputs of disjoint ``held`` sets sum to the whole layer's (``forward``'s
"routed"); there is no shared expert, so nothing is counted twice. The
router's losses are over all E whatever is held.

``choice`` (one (N, S, k) int array a layer) hands the experts the PROGRAM
chose to this reference, so that a near-tie that rounding flips shows as a
count (``route_flips``) and not as a logit error; the weights are then the
softmax of the reference's own logits at the handed experts and the
balance loss counts the handed assignments. Without it the reference routes
by itself. ``q_block`` computes the attention of that many queries at a
time; ``last`` keeps the logits of the last ``last`` positions; ``round_to``
rounds every matmul input to a narrower type and back (the gradient passes
straight through; ``round_when``, a traced bool, switches it inside one
compiled program): the reading that shows a tolerance can tell precisions
apart, never used for ``correct``.

``train_step`` is one whole step of the solver on this model, as plainly:
``jax.grad`` of ``loss``, the global-norm clip, AdamW (``adamw_step``), and
returns every blob's CHANGE.

Weights come as ``{layer name: [blobs]}`` (``Net.export_weights``) under the
prototxt's names: ``embed``, ``l<i>_{attn_norm, router, q, k, v, o,
ffn_norm}``, ``l<i>_moe [gate (G', F, D), up, down (G', D, F)]``,
``final_norm``, ``lm_head``; matrices are (out, in).

Departures from the published description: the "secondary experts" that the
catalog's ``described_as`` mentions have no key in config.json and none run;
the auxiliary losses are the repo's (``assumed.aux_losses``), not the
model's own recipe, which config.json does not give.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

# program against reference at the PUBLISHED widths (the chip run's
# `correct`), per numeric policy of the program. f32: the same products in
# another order. bf16, every limit from readings on the v5e (PERF.md, PR 45):
# the program under bf16 over its seeds, and this reference with its matmul
# inputs rounded to float8 e4m3, the nearest precision below, which has to
# fail at least one (it fails logits_rel_l2, and no other):
# - logits_rel_l2: bf16 0.0020-0.0025 over seven seeds, float8
#   0.0088-0.0103: 4.5e-3 between, a factor of two from either (PRs 53 and
#   63, fourteen runs: bf16 0.0023-0.0030, float8 0.0097-0.0130).
# - update_cosine, the direction of the WHOLE update, every leaf as one
#   vector (ISSUE 63; PERF.md 53a and section 6, PR 63): bf16 0.9951-0.9968
#   on eleven seeds of twelve and 0.9756 on one (3053000007), whose batch
#   has two frequent token ids (515 and 405 of 16,384 tokens) with their 6th
#   and 7th router scores in layer 0 nearer (0.0003, 0.0008) than bf16's
#   error of a score (0.003 rms): layer 0's router reads a function of the
#   id alone, so every token of such an id changes an expert at once, and
#   the free-running top-6 differs for 579 tokens there against 121-274 on
#   the other seeds. The precision hardly moves the number (float8
#   0.9914-0.9959, on that seed 0.9920: the router is never rounded), so it
#   has no upper reading from the control; a state left unchanged reads 0.
#   The limit stays PR 45's 0.93, three times the worst seed's gap.
# - leaf_cosine, the least cosine of ONE leaf of 2**16 numbers or more (what
#   update_cosine was until ISSUE 63: a router's matrix (64, 2560) or a held
#   stack of layer 3 in most seeds): bf16 0.9722-0.9874 on eleven seeds of
#   twelve, 0.9052 on 3053000007 (l0_router; then l1_k 0.9228, l0_q 0.9375);
#   PR 45's seven seeds read 0.9715-0.9849; float8 0.9488-0.9768, no upper
#   reading either. Adam's first change of a number is the rate times its
#   gradient's sign, so a leaf of the wrong sign reads -1 and one left
#   unmoved 0: 0.8, twice the worst seed's gap from 1 and five times nearer
#   to it than an unmoved leaf.
# - loss_rel, update_norm_rel: the precision hardly moves them either.
#   loss_rel 0.4e-5-2.1e-5: the accepted cells' 2.5e-4 (12 times of room).
#   update_norm_rel 0.0014-0.0048 (a held stack of layer 2 or 3; float8
#   0.0028-0.0038): 0.1, between the reading and 1, which a state left
#   unchanged reads, with the more room above.
# - step_loss_rel: NOT a limit under bf16 (None), as in trinity.py: the
#   first step's loss on fresh weights read 0.1e-4-1.9e-4 (float8
#   0.5e-4-1.4e-4); the loss is held to loss_rel on the trained weights. The
#   number stays among the facts. There are no selection biases.
TOLERANCE = {
    "f32": {"logits_rel_l2": 2e-4, "loss_rel": 1e-5,
            "step_loss_rel": 1e-5, "update_norm_rel": 1e-3,
            "update_cosine": 0.999, "leaf_cosine": 0.999,
            "cosine_from": 2 ** 16},
    "bf16": {"logits_rel_l2": 4.5e-3, "loss_rel": 2.5e-4,
             "step_loss_rel": None, "update_norm_rel": 0.1,
             "update_cosine": 0.93, "leaf_cosine": 0.8,
             "cosine_from": 2 ** 16},
}
# at a CPU rehearsal's widths (hidden 64, 64 positions, 128 tokens a step) a
# logit is a sum of 64 products, not 2560, and a handful of tokens change an
# expert. The rehearsal shows that the check runs, not how close the program
# comes.
TOLERANCE_TINY = {
    "f32": dict(TOLERANCE["f32"], cosine_from=2 ** 6),
    "bf16": {"logits_rel_l2": 6e-2, "loss_rel": 5e-3,
             "step_loss_rel": 5e-3, "update_norm_rel": 0.5,
             "update_cosine": 0.7, "leaf_cosine": 0.5,
             "cosine_from": 2 ** 10},
}


def rms_norm(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * g


def rope(x, theta):
    """x (S, heads, d): rotate-half rotary positions over the whole head
    (frequency i serves dims i and i + d / 2)."""
    s, _, d = x.shape
    inv = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * inv[None, :]
    ang = jnp.concatenate([ang, ang], -1)[:, None, :]
    rot = jnp.concatenate([-x[..., d // 2:], x[..., :d // 2]], -1)
    return x * jnp.cos(ang) + rot * jnp.sin(ang)


def attention(q, k, v, per, window=None, q_block=None, ckpt=lambda f: f):
    """One sequence: q (S, H, d), k, v (S, G, d) -> (S, H d), query head h
    reading key-value head h // per, position t attending to s <= t and,
    with a ``window``, s > t - window: a dense mask."""
    s, h, d = q.shape
    q_block = q_block or s
    kv_of = jnp.arange(h) // per

    def rows(start):
        qb = jax.lax.dynamic_slice_in_dim(q, start, q_block, 0)
        scores = jnp.einsum("qhd,khd->hqk", qb, k[:, kv_of]) \
            / jnp.sqrt(jnp.float32(d))
        t = (start + jnp.arange(q_block))[:, None]
        u = jnp.arange(s)[None]
        mask = u <= t
        if window is not None:
            mask = mask & (u > t - window)
        probs = jax.nn.softmax(jnp.where(mask, scores, -jnp.inf), -1)
        return jnp.einsum("hqk,khd->qhd", probs, v[:, kv_of])

    return jax.lax.map(ckpt(rows),
                       jnp.arange(0, s, q_block)).reshape(s, h * d)


def is_global(cfg, i):
    return cfg["sliding_window_layout"][i] == 0


def forward(cfg, weights, tokens, targets=None, held=None, last=None,
            q_block=None, round_to=None, choice=None, remat=False,
            round_when=None):
    """tokens (N, S) int -> {"logits" (N, last or S, V); "counts" (L, E)
    assignments per expert by this reference's own top-k, one row a layer;
    "choice" (L, N, S, k) that top-k; "route_flips" (L,) assignments of a
    handed-over ``choice`` that it does not have (zeros without one);
    "routed" (L, N, S, D) each layer's experts' output; "balance", "z" (L,)
    the routers' unweighted losses; "gate_zero_share" (L,) the share of the
    held experts' gate pre-activations <= 0 over the assignments they
    computed; and with ``targets`` "nll" (N, S)}.
    ``cfg``: num_hidden_layers, num_attention_heads, num_key_value_heads,
    head_dim, num_experts (what the router scores), num_experts_per_tok,
    sliding_window_size, sliding_window_layout (0 global without positions,
    1 window with them, a layer that is run), rms_norm_eps, rope_theta."""
    with jax.default_matmul_precision("highest"):
        eps = cfg["rms_norm_eps"]
        n_h, n_g = cfg["num_attention_heads"], cfg["num_key_value_heads"]
        n_exp, top_k, per = cfg["num_experts"], \
            cfg["num_experts_per_tok"], n_h // n_g
        d = cfg["head_dim"]
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

        def expert(u, gate, up, dn):     # ReGLU -> (output, gate pre-act.)
            a = mm(u, gate)
            return mm(jax.nn.relu(a) * mm(u, up), dn), a

        def attend(w, a, window, rotary):    # one sequence (S, D) -> (S, D)
            s = a.shape[0]
            q = mm(a, w["q"][0]).reshape(s, n_h, d)
            k = mm(a, w["k"][0]).reshape(s, n_g, d)
            v = mm(a, w["v"][0]).reshape(s, n_g, d)
            if rotary:
                q, k = rope(q, cfg["rope_theta"]), rope(k, cfg["rope_theta"])
            o = attention(rnd(q), rnd(k), rnd(v), per, window, q_block, ckpt)
            return mm(o, w["o"][0])

        def route(w, a, handed):
            """-> (gates (N, S, E), counts (E,), own top-k, flips, balance
            loss, z loss): the router on the pre-attention state."""
            r = a @ w["router"][0].T             # the router: never rounded
            _, own = jax.lax.top_k(r, top_k)
            e = own if handed is None else handed            # (N, S, k)
            picked = jax.nn.one_hot(e, n_exp)                # (N, S, k, E)
            chosen = jnp.take_along_axis(r, e, -1)           # (N, S, k)
            gates = jnp.sum(picked * jax.nn.softmax(chosen, -1)[..., None],
                            -2)
            mine = jnp.sum(jax.nn.one_hot(own, n_exp), -2)
            flips = jnp.sum(jnp.sum(picked, -2) * (1.0 - mine))
            tokens_ = r.shape[0] * r.shape[1]
            f = jnp.sum(picked, (0, 1, 2)) / tokens_
            balance = n_exp * jnp.sum(
                f * jnp.mean(jax.nn.softmax(r, -1), (0, 1)))
            z = jnp.mean(jax.nn.logsumexp(r, -1) ** 2)
            return gates, jnp.sum(mine, (0, 1)), own, flips, balance, z

        def experts(w, u, gates):
            gate, up, dn = w["moe"]
            routed = jnp.zeros_like(u)
            zeros = rows = 0.0
            for row, which in enumerate(held):      # every token, weighed
                out, a = ckpt(expert)(u, gate[row], up[row], dn[row])
                routed = routed + gates[..., which, None] * out
                took = gates[..., which] > 0                 # (N, S)
                zeros = zeros + jnp.sum((a <= 0) & took[..., None])
                rows = rows + jnp.sum(took) * a.shape[-1]
            return routed, zeros / jnp.maximum(rows, 1.0)

        def layer(i, w, x, handed):
            glob = is_global(cfg, i)
            a = rms_norm(x, w["attn_norm"][0], eps)
            gates, n_e, own, flips, balance, z = route(w, a, handed)
            att = jax.lax.map(
                lambda one: attend(w, one, None if glob
                                   else cfg["sliding_window_size"],
                                   not glob), a)
            h = x + att
            routed, zero_share = experts(
                w, rms_norm(h, w["ffn_norm"][0], eps), gates)
            return h + routed, (n_e, own, flips, routed, balance, z,
                                jax.lax.stop_gradient(zero_share))

        x = f32(weights["embed"])[0][tokens]                # (N, S, D)
        per_layer = []
        for i in range(cfg["num_hidden_layers"]):
            pre = f"l{i}_"
            w = {name[len(pre):]: f32(blobs)
                 for name, blobs in weights.items() if name.startswith(pre)}
            handed = None if choice is None else jnp.asarray(choice[i])
            x, extra = ckpt(lambda w, x, handed, i=i: layer(i, w, x, handed))(
                w, x, handed)
            per_layer.append(extra)
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
        names = ("counts", "choice", "route_flips", "routed", "balance", "z",
                 "gate_zero_share")
        out = {"logits": logits}
        out.update({name: jnp.stack([m[j] for m in per_layer])
                    for j, name in enumerate(names)})
        if targets is not None:
            out["nll"] = nll
        return out


def loss(cfg, weights, tokens, targets, **how):
    """-> (mean next-token NLL + the weighted balance and z losses of every
    layer's router (``cfg``: balance_weight, z_weight), forward's dict);
    ``how`` is ``forward``'s ``held`` / ``last`` / ``q_block`` / ``round_to``
    / ``round_when`` / ``choice`` / ``remat``."""
    out = forward(cfg, weights, tokens, targets, **how)
    return jnp.mean(out["nll"]) + cfg["balance_weight"] * jnp.sum(
        out["balance"]) + cfg["z_weight"] * jnp.sum(out["z"]), out


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
    """The FIRST step of training from ``weights``: the loss over every
    position and its gradient (``jax.grad`` of ``loss``), the gradient
    scaled down to a global L2 norm of ``opt["clip"]`` where it is larger,
    AdamW from zero moments on every blob.
    ``opt``: ``rate`` and ``decay`` as {layer: [a number a blob]} (the
    step's learning rate x the blob's lr_mult, the weight decay x its
    decay_mult), ``clip``, ``b1``, ``b2``, ``eps``.
    -> {"loss", "counts" (L, E), "grad_norm", "change": {layer: [w' - w]}}"""
    def objective(w):
        total, out = loss(cfg, w, tokens, targets, **how)
        return total, out["counts"]

    (total, counts), grads = jax.value_and_grad(objective, has_aux=True)(
        {k: [jnp.asarray(b, jnp.float32) for b in v]
         for k, v in weights.items()})
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
    return {"loss": total, "counts": counts, "grad_norm": norm,
            "change": change}
