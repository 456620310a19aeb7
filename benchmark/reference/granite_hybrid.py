"""Plain reference for Granite-4.0-H-Micro's block (config.json of
ibm-granite/granite-4.0-h-micro, ``model_type: granitemoehybrid`` with no
experts; what config.json does not say is under ``assumed`` in
configs/granite_4_0_h_micro.json): forward, the next-token loss and, through
``jax.grad`` of ``loss``, every gradient. Straightforward ``jax.numpy`` in
float32 under ``jax.default_matmul_precision("highest")``: Mamba-2's
recurrence TOKEN BY TOKEN (a ``lax.scan`` over t, no chunks, no kernels),
attention as a dense masked softmax in blocks of queries, nothing imported
from the program (``remat`` wraps a layer, ``t_block`` tokens of the
recurrence, a block of queries and a sequence's head in ``jax.checkpoint``:
the same arithmetic, so that the gradient of a sequence of 8,192 at the
published widths fits one chip). Per sequence, h (S, D):

    h_0 = embedding_multiplier * E[ids]
    every layer:  u = h + residual_multiplier * Mix(N1(h))
                  h' = u + residual_multiplier * MLP(N2(u))
        N*: RMSNorm (eps, own gain) on the sublayer's INPUT
        MLP(y) = (silu(a) * b) W_out,  [a, b] = y W_in
    mamba layer (layer_types[i] "mamba"), H heads of P, a state of N a head,
    ONE group of B / C for all heads:
      [z, xBC, dt~] = y W_inproj             widths H P | H P + 2 N | H
      xBC'_t = silu(sum_j w[j] xBC_{t-j} + b_conv)   per channel, 4 taps,
                                         zeros before the sequence's start
      [x, B, C] = xBC'                       H P | N | N
      dt_t = softplus(dt~_t + dt_bias) (H);  a_t = -exp(A_log) dt_t  (<= 0)
      H_0 = 0 (P, N) a head;  H_t = exp(a_t) H_{t-1} + dt_t x_t B_t^T
      y_t = H_t C_t + D x_t
      Mix = N_g(y * silu(z)) W_outproj       the gate FIRST, then one
                                         RMSNorm over all H P channels
    attention layer ("attention"): q of ``num_attention_heads``, k and v of
      ``num_key_value_heads`` heads (query head h reads key-value head
      h // (heads / kv heads)), NO positions,
      Mix = softmax_causal(attention_multiplier * q k^T) v  W_o
    logits = N_final(h_L) E^T / logits_scaling  (E the embedding's table);
    loss = mean NLL

The table comes at the vocabulary's slice (``embed`` has the rows held).
``vocabulary_shares`` cuts the table's rows into equal shares: the logits of
the shares, side by side, are the whole table's (the tests' share test).

``q_block`` computes the attention of that many queries at a time; ``last``
keeps the logits of the last ``last`` positions. Two controls show that a
tolerance can tell precisions apart, never used for ``correct``:
``round_to`` rounds every matmul input (and x, B, C before the recurrence,
q, k, v before the attention) to a narrower type and back, the gradient
passing straight through (``round_when``, a traced bool, switches it inside
one compiled program); ``ssd``'s ``state_round`` rounds the recurrence's
STATE after every token.

``train_step`` is one whole step of the solver on this model, as plainly:
``jax.grad`` of ``loss``, the global-norm clip, AdamW (``adamw_step``), and
returns every blob's CHANGE.

Weights come as ``{layer name: [blobs]}`` under the prototxt's names:
``embed``; a mamba layer's ``l<i>_ssd_in``, ``l<i>_ssd_conv [w (taps, C), b
(C)]``, ``l<i>_ssd_decay [A_log (H), dt_bias (H)]``, ``l<i>_ssd_scan [D
(H)]``, ``l<i>_ssd_onorm``, ``l<i>_ssd_out``; an attention layer's
``l<i>_attn_{q,k,v,o}``; every layer's ``l<i>_norm1``, ``l<i>_norm2``,
``l<i>_ffn_in``, ``l<i>_ffn_out``; ``final_norm``; matrices are (out, in).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

# program against reference at the PUBLISHED widths (the chip run's
# `correct`), per numeric policy of the program. f32: the same products in
# another order. bf16: readings on the v5e (my chip runs, PR 54; PERF.md
# section 6 has them seed by seed: ten seeds of the first round, fourteen
# more of the review round, calls 69 and 71) of the program under bf16 and of two
# controls in the nearest precision below: this reference with its matmul
# inputs rounded to float8 e4m3, and the recurrence with its state (and the
# state's cotangent) rounded to bf16 after every token. Each control fails
# the limits it is there for at least twice over; program | control; limit:
# - update_cosine (the worst leaf of 2**16 numbers or more, on every seed a
#   mamba layer's input projection): bf16 0.9845-0.9849 on twenty-four seeds,
#   float8 0.8119-0.8166: 0.94 between (1 - cosine: 0.0155 | 0.06 | 0.184).
#   This is the limit that holds the matmuls to bf16.
# - group_cosine (the first update of the leaves ONLY the scan's gradients
#   feed, `runners/granite_train.scan_leaves`, the nine layers' as one
#   vector, worst of five groups; Adam's first change is the gradient's
#   sign, so 1 - cosine is twice the share of flipped signs): bf16
#   0.9762-0.9866 on fourteen seeds (by group: d_a 0.986-0.997, d_dt
#   0.976-0.997, d_D 0.983-0.997, d_BC 0.982-0.987, d_x 0.988), float8
#   0.7557-0.8015 (d_dt or d_BC): 0.93 between (1 - cosine: 0.024 | 0.07 |
#   0.198). A gradient of the scan with the wrong sign reads -1 in its group
#   and passes every other limit of the step.
# - scan_rel_l2 (the LAST mamba layer's recurrence alone, no skip, in f32,
#   against `ssd` on the program's own operands): 7.5e-6-3.3e-5 on fourteen
#   seeds | bf16 state 0.0090-0.175: 3e-4 between, nine times the program's
#   largest reading and a thirtieth of the control's smallest. (The skip
#   hardly matters on trained operands: with `D x` in y the same fourteen read
#   7.9e-6-3.2e-5, `D x` being 0.08-0.74 of y's norm. With `ssd`'s decay written exp(a) H the SAME program read
#   4e-5-2.4e-4 on four seeds: the chip's exp near 1 has a bias that a slow
#   head compounds over a thousand tokens, 6.9e-4 from NumPy float64 where
#   `H + expm1(a) H` reads 5.6e-7 and the kernels 2.3e-6; see ``ssd``.)
# - scan_grad_rel_l2 (the same call's backward under one seeded cotangent:
#   the routed scan's six gradients against ``jax.grad`` of `ssd`, each on
#   its own norm, the WORST, which is d a on thirteen seeds): 1.0e-5-5.5e-5
#   on fourteen seeds (d dt 1.3e-5-2.8e-5, d C 8e-6-3.2e-5, d x and d B 7e-7-1.8e-5,
#   d D 3e-7) | bf16 state 0.033-0.65 (d a; its d C 0.012-0.12, d dt
#   0.0028-0.016, d B 0.0017-0.0054, d x 4.3e-4-1.3e-3): 3e-4 between, five
#   times the program's largest and a hundredth of the control's smallest;
#   every gradient of the control but d D (which no state reaches) lies
#   above it on every seed. This holds ``ssd_scan_bwd`` at 8,192 x 64 x 64 x
#   128.
# - logits_rel_l2 (trained weights, the last 512 positions of one sequence
#   of 8,192): bf16 0.0017-0.0038; float8 0.0042-0.041, which OVERLAPS the
#   program's seeds (the state is 12 x the embedding plus 0.22 x every
#   sublayer: a rounding inside a sublayer reaches the logits at a fifth of
#   its size, and how sharp the trained logits are varies by seed), so this
#   limit cannot lie between and has no lower-precision reading behind it:
#   0.01, 2.6 times the program's largest reading, can tell a forward that
#   is wrong in kind, not a precision; update_cosine holds the precision.
# - update_norm_rel: the precision hardly moves it (bf16 0.0056-0.0187,
#   float8 0.0075-0.0198): held against 1, which a leaf left unchanged
#   reads, with the more room above the reading (0.1: five times the
#   largest). `benchmark/tests/test_bench_granite.py` plants both faults
#   (and a d a of the wrong sign, and a loss over half the positions) at
#   the rehearsal's sizes and sees `correct: false`.
# - loss_rel, step_loss_rel: NOT limits under bf16 (None), facts, as in the
#   accepted token cells: bf16 1.2e-5 / 1.8e-6, float8 2.4e-5 (the logits
#   are nearly flat, so no precision moves the loss).
TOLERANCE = {
    "f32": {"logits_rel_l2": 2e-4, "scan_rel_l2": 1e-4,
            "scan_grad_rel_l2": 3e-4, "loss_rel": 1e-5,
            "step_loss_rel": 1e-5, "update_norm_rel": 1e-3,
            "update_cosine": 0.999, "group_cosine": 0.99,
            "cosine_from": 2 ** 16},
    "bf16": {"logits_rel_l2": 0.01, "scan_rel_l2": 3e-4,
             "scan_grad_rel_l2": 3e-4, "loss_rel": None,
             "step_loss_rel": None, "update_norm_rel": 0.1,
             "update_cosine": 0.94, "group_cosine": 0.93,
             "cosine_from": 2 ** 16},
}
# at a CPU rehearsal's widths a logit is a sum of 64 products: the rehearsal
# shows that the check runs, not how close the program comes.
TOLERANCE_TINY = {
    "f32": dict(TOLERANCE["f32"], cosine_from=2 ** 6),
    "bf16": {"logits_rel_l2": 0.2, "scan_rel_l2": 3e-4,
             "scan_grad_rel_l2": 3e-4, "loss_rel": 5e-3,
             "step_loss_rel": 5e-3, "update_norm_rel": 0.5,
             "update_cosine": 0.6, "group_cosine": 0.6,
             "cosine_from": 2 ** 10},
}


def narrowed(x, dtype):
    """x rounded to ``dtype`` and back to float32. The barrier keeps the two
    casts: a compiler that is allowed excess precision drops the bare
    pair."""
    return jax.lax.optimization_barrier(x.astype(dtype)).astype(jnp.float32)


def rms_norm(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * g


def short_conv(x, w, b):
    """x (S, C), w (taps, C), b (C): y_t = silu(sum_j w[j] x_{t-j} + b),
    zeros before the sequence's start — a written-out loop over the taps."""
    s = x.shape[0]
    y = jnp.zeros_like(x) + b
    for j in range(w.shape[0]):
        y = y + w[j] * jnp.concatenate(
            [jnp.zeros((j, x.shape[1]), x.dtype), x[:s - j]], 0)
    return jax.nn.silu(y)


def ssd(x, dt, a, b, c, d, t_block=None, ckpt=lambda f: f,
        state_round=lambda s: s):
    """One sequence, token by token: x (S, H, P), dt and a (S, H), b and c
    (S, N), d (H) -> (S, H, P). ``t_block``: the scan over t is cut into
    scans of that many tokens, each under ``ckpt`` (what a gradient keeps
    is then a state a block and the states of ONE block)."""
    s, h, p = x.shape
    t_block = t_block or s

    def token(state, xs):
        x_t, dt_t, a_t, b_t, c_t = xs
        # exp(a) H as H + expm1(a) H: a slow head (a about -1e-3) keeps its
        # state a thousand tokens, and whatever bias exp has near 1 (the
        # v5e's read 1e-4 of such a state, against the chunked form's ONE
        # exp of a sum) would compound over them; expm1 near 0 has none
        state = state_round(
            state + (jnp.expm1(a_t)[:, None, None] * state
                     + (dt_t[:, None] * x_t)[..., None] * b_t[None, None, :]))
        # the read at HIGHEST whoever calls (the chip's default would round
        # the f32 state to bf16 on its way into the product)
        return state, jnp.einsum("hpn,n->hp", state, c_t,
                                 precision="highest") + d[:, None] * x_t

    def block(state, xs):
        return jax.lax.scan(token, state, xs)

    blocks = tuple(t.reshape((s // t_block, t_block) + t.shape[1:])
                   for t in (x, dt, a, b, c))
    _, y = jax.lax.scan(ckpt(block),
                        jnp.zeros((h, p, b.shape[-1]), jnp.float32), blocks)
    return y.reshape((s,) + y.shape[2:])


def attention(q, k, v, scale, q_block=None, ckpt=lambda f: f):
    """One sequence: q (S, H, d), k and v (S, Hkv, d) -> (S, H d), position
    t attending to s <= t: a dense mask, no positions, query head h on
    key-value head h // (H / Hkv)."""
    s, h, d = q.shape
    k, v = (jnp.repeat(t, h // t.shape[1], 1) for t in (k, v))
    q_block = q_block or s

    def rows(start):
        qb = jax.lax.dynamic_slice_in_dim(q, start, q_block, 0)
        scores = jnp.einsum("qhd,khd->hqk", qb, k) * scale
        t = (start + jnp.arange(q_block))[:, None]
        probs = jax.nn.softmax(
            jnp.where(jnp.arange(s)[None] <= t, scores, -jnp.inf), -1)
        return jnp.einsum("hqk,khd->qhd", probs, v)

    return jax.lax.map(ckpt(rows), jnp.arange(0, s, q_block)).reshape(s, -1)


def vocabulary_shares(weights, shares: int):
    """The model's weights ``shares`` times, each with one equal share of
    the table's rows (in order) and everything else whole."""
    table = weights["embed"][0]
    rows = table.shape[0] // shares
    return [{**weights, "embed": [table[i * rows:(i + 1) * rows]]}
            for i in range(shares)]


def forward(cfg, weights, tokens, targets=None, last=None, q_block=None,
            round_to=None, remat=False, round_when=None, t_block=None,
            upto=None, state_round=lambda s: s, states=None):
    """tokens (N, S) int -> {"logits" (N, last or S, V); "decay_mean" and
    "dt_mean" (one a mamba layer: the means of exp(a) and of dt); "state"
    (N, S, D) after the last layer run; and with ``targets`` "nll" (N, S)}.
    ``cfg``: the configuration's own keys — num_hidden_layers, layer_types
    (a layer that is run), mamba_n_heads, mamba_d_state,
    num_attention_heads, num_key_value_heads, rms_norm_eps and the four
    multipliers. ``upto``: stop after that many layers (no head).
    ``states``: h_0 given (N, S, D) instead of the lookup (the share test
    and the scan's check feed a state)."""
    with jax.default_matmul_precision("highest"):
        eps = cfg["rms_norm_eps"]
        n_h, n_state = cfg["mamba_n_heads"], cfg["mamba_d_state"]
        heads, kv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
        res = cfg["residual_multiplier"]
        ckpt = jax.checkpoint if remat else (lambda f: f)
        if remat and t_block is None:
            t_block = next(b for b in (128, 64, 32, 16, 8, 4, 2, 1)
                           if tokens.shape[1] % b == 0)

        def f32(blobs):
            return [jnp.asarray(b, jnp.float32) for b in blobs]

        def rnd(x):
            if round_to is None:
                return x
            r = narrowed(x, round_to)
            if round_when is not None:
                r = jnp.where(round_when, r, x)
            return x + jax.lax.stop_gradient(r - x)    # straight through

        def mm(x, w):                    # x (.., in) by an (out, in) matrix
            return rnd(x) @ rnd(w).T

        def mamba(w, y):                 # one sequence (S, D) -> (S, D)
            s = y.shape[0]
            zxd = mm(y, w["ssd_in"][0])
            inner = (zxd.shape[1] - 2 * n_state - n_h) // 2
            z, xbc, dtr = (zxd[:, :inner], zxd[:, inner:-n_h], zxd[:, -n_h:])
            xbc = short_conv(xbc, *w["ssd_conv"])
            x, b, c = (xbc[:, :inner], xbc[:, inner:inner + n_state],
                       xbc[:, inner + n_state:])
            a_log, dt_bias = w["ssd_decay"]
            dt = jax.nn.softplus(dtr + dt_bias)
            a = -jnp.exp(a_log) * dt
            o = ssd(rnd(x).reshape(s, n_h, -1), dt, a, rnd(b), rnd(c),
                    w["ssd_scan"][0], t_block if remat else None, ckpt,
                    state_round).reshape(s, -1)
            o = rms_norm(o * jax.nn.silu(z), w["ssd_onorm"][0], eps)
            return (mm(o, w["ssd_out"][0]), jnp.mean(jnp.exp(a)),
                    jnp.mean(dt))

        def attend(w, y):
            s = y.shape[0]
            q, k, v = (rnd(mm(y, w["attn_" + t][0])) for t in "qkv")
            o = attention(q.reshape(s, heads, -1), k.reshape(s, kv, -1),
                          v.reshape(s, kv, -1), cfg["attention_multiplier"],
                          q_block, ckpt)
            return mm(o, w["attn_o"][0]), jnp.float32(0), jnp.float32(0)

        def layer(i, w, h):
            mix = mamba if cfg["layer_types"][i] == "mamba" else attend
            n1 = rms_norm(h, w["norm1"][0], eps)
            mixed, decay, step = jax.vmap(lambda one: mix(w, one))(n1)
            u = h + res * mixed
            ab = mm(rms_norm(u, w["norm2"][0], eps), w["ffn_in"][0])
            half = ab.shape[-1] // 2
            f = mm(jax.nn.silu(ab[..., :half]) * ab[..., half:],
                   w["ffn_out"][0])
            return u + res * f, jnp.mean(decay), jnp.mean(step)

        table = f32(weights["embed"])[0]
        h = cfg["embedding_multiplier"] * table[tokens] if states is None \
            else jnp.asarray(states, jnp.float32)
        decays, steps = [], []
        n_layers = cfg["num_hidden_layers"] if upto is None else upto
        for i in range(n_layers):
            pre = f"l{i}_"
            w = {name[len(pre):]: f32(blobs)
                 for name, blobs in weights.items() if name.startswith(pre)}
            h, decay, step = ckpt(lambda w, h, i=i: layer(i, w, h))(w, h)
            if cfg["layer_types"][i] == "mamba":
                decays.append(decay)
                steps.append(step)
        out = {"decay_mean": jnp.stack(decays) if decays else jnp.zeros(0),
               "dt_mean": jnp.stack(steps) if steps else jnp.zeros(0),
               "state": h}
        if upto is not None:
            return out
        xf = rms_norm(h, f32(weights["final_norm"])[0], eps)

        def head(seq):                   # one sequence: the vocabulary is
            xs, tgt = seq                # wide, (S, V) at a time
            whole = mm(xs, table) / cfg["logits_scaling"]
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
    ``remat`` / ``state_round``."""
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
    every position and its gradient (``jax.grad`` of ``loss``; the table's
    is the sum of the lookup's and the head's), the gradient scaled down to
    a global L2 norm of ``opt["clip"]`` where it is larger, AdamW from zero
    moments on every blob. ``opt``: ``rate`` and ``decay`` as {layer: [a
    number a blob]} (the step's learning rate x the blob's lr_mult, the
    weight decay x its decay_mult), ``clip``, ``b1``, ``b2``, ``eps``.
    -> {"loss", "grad_norm", "change": {layer: [w' - w]}}"""
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
