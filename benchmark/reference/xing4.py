"""Plain reference for Xing4.0-29B-A4B's block, its residual STREAM and its
prediction module (config.json of XingChen-AGI/Xing4.0-29B-A4B,
``model_type: xing4_0``, 29B-A4B: the DeepSeek-V3 block, arXiv:2412.19437,
on manifold-constrained hyper-connections, arXiv:2512.24880 over
arXiv:2409.19606, with YaRN's rotary frequencies; what config.json does not
say is under ``assumed`` in configs/xing4_0_29b_a4b.json): forward, the
losses and, through ``jax.grad`` of ``loss``, every gradient.
Straightforward ``jax.numpy`` in float32 under
``jax.default_matmul_precision("highest")``: the stream an EXPLICIT (n, C)
array a token, the Sinkhorn projection a Python loop of ``hc_sinkhorn_iters``
row and column divisions on an (n, n) matrix a token (so that it cannot
inherit the program's layout, which keeps the streams side by side along the
lanes and runs the loop with the tokens along them), latent attention as a
full masked softmax in blocks of queries with the shared key part explicit,
a Python loop over experts, no kernel, no sort, nothing imported from the
program (``remat`` wraps a layer, a block of queries, an expert and a
sequence's head in ``jax.checkpoint``: the same arithmetic, so that the
gradient of a sequence of 8,192 at the published widths fits one chip). Per
token t, n streams of C, H heads, E experts of which k a token:

    X_j = Emb[tok]  for j = 1..n                                       (c)
    each layer, two sub-layers F (latent attention, then the FFN), each
    with its own mapping Phi_pre, Phi_post (n C -> n), Phi_res (n C -> n n),
    b_pre, b_post (n), B_res (n, n), scalars a_pre, a_post, a_res:
      u = vec(X) / sqrt(mean(vec(X)^2) + hc_eps)      vec: stream-major
      p = sigmoid(a_pre (u Phi_pre) + b_pre)
      q = 2 sigmoid(a_post (u Phi_post) + b_post)
      M = exp(clip(a_res mat(u Phi_res) + B_res, -30, 30))    mat: row-major
      20 times:  M <- M / (rowsum(M) + hc_eps);  M <- M / (colsum(M) + hc_eps)
      h = sum_j p_j X_j;  y = F(N(h));  X'_i = sum_j M_ij X_j + q_i y
                                                   N: RMSNorm, own gain
    MLA, a = N1(h):
      c_q = N_q(a W_qa) (768);  q = c_q W_qb, H heads of [q_nope (128) ;
      q_rope (64)];  [c_kv (512) ; k_pe (64)] = a W_kva;  c = N_c(c_kv)
      k_nope_h (128) = c W_kvb_k,  v_h (128) = c W_kvb_v
      R_t: rotate-half rotary positions over the 64, YaRN's frequencies:
        f_i = theta^(-2i/64), g_i = f_i / factor,
        c(b) = 64 ln(P / (2 pi b)) / (2 ln theta), low = floor(c(beta_fast)),
        high = ceil(c(beta_slow)), m_i = 1 - clip((i - low) / (high - low),
        0, 1); pair i (dims i and i + 32) turns by t (g_i (1 - m_i) + f_i m_i)
      score_h(t, s) = (q_nope_h(t) . k_nope_h(s) + R_t q_rope_h(t) . R_s
                       k_pe(s)) mscale^2 / sqrt(192),
                       mscale = 0.1 mscale_all_dim ln(factor) + 1
      MLA = [softmax_{s <= t}(score_h) v_h]_h W_o        (H 128 -> C)
    dense layer (the first ``num_dense_layers``):
      FFN = (silu(u W_gate) * (u W_up)) W_down
    sparse layer:
      s = sigmoid(u W_r) (E) f32, never rounded
      chosen = the k largest of s + b      b: selection bias, no gradient
      w_e = route_scale * s_e / sum_{chosen} s   for chosen e
      FFN = sum_{chosen e that is HELD} w_e E_e(u) + Shared(u)
    x_L = sum_j X_j;  logits = N_f(x_L) W_head^T (untied)
    prediction module (``mtp_layers`` 1), x_L BEFORE N_f:
      z(t) = [N_e(Emb[tok(t+1)]) ; N_h(x_L(t))] W_eh^T  (2 C -> C)
      Z_j = z for j = 1..n; one more sparse layer of its own weights and
      mappings on Z; z' = sum_j Z_j; logits'(t) = N_s(z'(t)) W_head^T
    loss = mean_t CE(logits(t), tok(t+1))
           + mtp_weight * mean_{t < S-1} CE(logits'(t), tok(t+2))

``held`` is the set of expert ids whose weights ``weights`` carries, in
ascending order (stack row i is expert held[i]); None = all E. An assignment
to an expert that is not held adds nothing: the routed parts of disjoint
``held`` sets sum to the whole layer's routed output, and the shared expert
is in EVERY share's output — whoever sums shares counts it once, and the
stream's write is linear in y, so the sum goes through it. The balancing
rule (``next_bias``) is the step's: b_e + rate * sign(T k / E - n_e).

``choice`` (one (N, S, k) int array a sparse layer, the module's last) hands
the experts the PROGRAM chose to this reference; ``q_block`` computes the
attention of that many queries at a time; ``last`` keeps the logits of the
last ``last`` positions. ``round_to`` rounds every matmul input (the
mappings' projections among them, and q, k, v before the attention) to a
narrower type and back, the gradient passing straight through
(``round_when``, a traced bool, switches it inside one compiled program): a
control, never used for ``correct``. ``fault`` plants one of ``FAULTS`` — a
wrong program written down, for the tests that show that each is caught.
With the mappings at their INITIAL values (``assumed.e``) most of them
cannot show: M is doubly stochastic after one iteration by symmetry and the
dynamic part is 1% of the logits, so the tests draw every leaf at random
(``a_*`` of order 1) before they plant one.

Weights come as ``{layer name: [blobs]}`` under the prototxt's names, a
block's under its prefix (``l<i>_``, the module's ``mtp_``):
``<p>{attn_norm, ffn_norm}``, ``<p>mla_{qa, qnorm, qb, kva, kvnorm, kvb_k,
kvb_v, o}``, ``<p>hc_{a,f}_map [phi_pre (n, n C), phi_post, phi_res (n n,
n C), b_pre, b_post, b_res (n, n), a_pre (1,), a_post, a_res]``; a dense
layer's ``<p>ffn_{gate,up,down}``, a sparse layer's ``<p>router [w (E, D),
bias]``, ``<p>moe [gate (G', F, D), up, down (G', D, F)]`` and
``<p>shared_{gate,up,down}``; ``embed``, ``final_norm``, ``lm_head``; the
module's ``mtp_{enorm, hnorm, eh, snorm}``. Matrices are (out, in).

Departures from the published description (the configuration's
``departures`` says the same): W_kvb is held as two matrices, its key rows
and its value rows (a permutation of the published matrix's rows);
rotate-half pairing inside the 64 rotary dims where the published code may
pair neighbours (a fixed permutation of 64 rows of W_qb and of W_kva); in
W_eh's input the embedding comes first; the module's block is a sparse layer
at the same positions on a stream of its own.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

# program against reference at the PUBLISHED widths (the chip run's
# `correct`), per numeric policy of the program. f32: the same products in
# another order. bf16: every limit from readings on the v5e, set over the
# first five seeds and read again on eight more (my chip runs, PR 60, calls
# A, B, E, F, G and H, the last the seed the driver's check refused,
# 1458287010; PERF.md section 6): the program under bf16, and this
# reference with its matmul inputs rounded to float8 e4m3, the nearest
# precision below, which has to fail at least one limit (it fails every one
# it is judged by but, on one seed of thirteen, the loss's).
# - logits_rel_l2 (trained weights, the last 512 positions of one sequence of
#   8,192, the program's experts handed over): bf16 0.0025-0.0041, float8
#   0.0139-0.0285 (thirteen seeds; call F's three read the highest and call
#   H's the lowest on both sides: 5.6-7.0 times apart in every run): 0.006
#   between the program's largest and the control's smallest, 1.5 times the
#   one and under half of the other (GLM's limit).
# - update_cosine (the worst leaf of 2**16 numbers or more, in every run a
#   router's (64, 3584) matrix on near-tied fresh scores): bf16 0.817-0.885
#   (0.817 in call H, 0.848 the lowest of the twelve before it), float8
#   0.426-0.597: 0.75 between, 0.07 under the one and 0.15 over the other.
# - group_cosine (the leaves only this configuration's mechanisms feed, each
#   group as one vector, ``xing_train.new_leaves``; the limit is over
#   ``hc_post``, ``q_rotary_rows``, ``k_shared_rows``, the worst in every run
#   a rotary group): bf16 0.9861-0.9911, float8 0.8914-0.9249: 0.96 between.
#   ``hc_pre`` and ``hc_res`` are FACTS (0.807-0.960 and 0.968-0.995: on a
#   fresh model what reaches them is under what a bf16 stream rounds away,
#   ``xing_train.FACT_GROUPS``).
# - stream_rel_l2, stream_grad_rel_l2 (``xing_train.stream_check``: the
#   program's mapping, read, write and end, forward and backward, on seeded
#   operands that carry signal): bf16 0.00175-0.0019 and 0.0030-0.0056 (the
#   worst of d X, d y, the three matrices' and ``d_small``: the biases' and
#   the scales' gradients, 27 numbers, as ONE vector. With the three scales
#   a vector of their own it read 0.0027-0.0055 on twelve seeds and 0.0152
#   on the thirteenth, call H's, where the three sums over 8,192 tokens
#   land near zero: the measure's fault, not the program's; 21 seeds on
#   the chip, call I, those thirteen among them), the reference with its
#   stream STORED in float8 0.0132-0.0171 and 0.0369-0.0436: 0.005 and 0.01
#   between.
# - attention_rel_l2, attention_grad_rel_l2 (``xing_train.attention_check``:
#   the ATTENTION layer itself, flash kernels, YaRN's angles and the scale,
#   on seeded operands of order 1): bf16 0.00238-0.00239 and 0.0038-0.0042;
#   the reference on operands rounded to float8 reads some ten times that
#   (0.0436-0.0438 and 0.052-0.053 on the chip, calls E and F): 0.008 and
#   0.012 between.
# - res_err_rel (what 20 Sinkhorn iterations leave, the program's ten
#   sub-layers against the reference's on the trained weights): 0.0011-
#   0.0035 over twelve seeds. No precision moves it: it tells one loop from
#   another, so its upper reading is this reference with its loop cut to
#   ONE iteration (``one_iteration_res_err_rel``, a row of ``compared`` in
#   every run): 13.5-14.8 (calls F and G, five seeds). 0.05 between, fourteen
#   times the largest reading and 270 times under the other.
# - loss_rel: 2e-7-7.1e-5 (first reading 6.9e-5); float8 3.9e-4-1.2e-3 on
#   twelve seeds and 3.9e-5 on the thirteenth (``control_float8_loss_rel``;
#   calls A, B, E, F, G and H): a mean over 8,192 positions can land on the
#   reference's under any precision, so this control does NOT break its
#   limit in every run (it broke five others in that one). The accepted
#   cells' 2.5e-4, 3.6 times the first reading.
# - update_norm_rel: the precision hardly moves it. 0.0026-0.0100 (an
#   expert stack or a router's matrix): 0.1, between the reading and 1,
#   which a state left unchanged reads, with the more room above. Every
#   leaf but a mapping's.
# - mapping_norm_rel (the mappings' leaves by KIND, each kind's live leaves
#   as one vector; the limit is over ``hc_post``): 1.6e-5-5.7e-4 (calls F
#   and G); 0.1 as above. ``hc_pre`` and ``hc_res`` are facts here too
#   (0.018-0.176 and 0.004-0.151), and NO mapping leaf is held alone: after the clip
#   their gradients lie at Adam's eps (1e-8), where the first change is NOT
#   lr sqrt(n) whatever its direction. Against the f32 reference a leaf
#   alone read up to 0.90 (a phi_pre), 0.34 (a b_res), 0.32 (a phi_res),
#   0.59 (an a_pre), 3.1 (an a_res) and 0.11 (a b_post) over calls E to G:
#   under a per-leaf 0.1 every run would read not correct. What is held of
#   every live mapping leaf is that it MOVED (``mapping_unmoved`` 0 of 81; a
#   name's scales, one number each, count as one leaf).
# - step_loss_rel: NOT a limit under bf16 (None), as in GLM's, Trinity's and
#   Kimi's cells: 2e-5-1.4e-4 on fresh weights; the losses are held on the
#   trained weights. A fact.
# - bias_margin, bias_compared_share: Trinity's and GLM's (245-251 of 256
#   selection biases were compared, none wrong).
TOLERANCE = {
    "f32": {"logits_rel_l2": 2e-4, "loss_rel": 1e-5,
            "res_err_rel": 1e-2,
            "stream_rel_l2": 2e-5, "stream_grad_rel_l2": 2e-4,
            "attention_rel_l2": 2e-5, "attention_grad_rel_l2": 2e-4,
            "step_loss_rel": 1e-5, "update_norm_rel": 1e-3,
            "mapping_norm_rel": 1e-3,
            "update_cosine": 0.999, "group_cosine": 0.999,
            "cosine_from": 2 ** 16,
            "bias_margin": 0.0, "bias_compared_share": 0.25},
    "bf16": {"logits_rel_l2": 0.006, "loss_rel": 2.5e-4,
             "res_err_rel": 0.05,
             "stream_rel_l2": 0.005, "stream_grad_rel_l2": 0.01,
             "attention_rel_l2": 0.008, "attention_grad_rel_l2": 0.012,
             "step_loss_rel": None, "update_norm_rel": 0.1,
             "mapping_norm_rel": 0.1,
             "update_cosine": 0.75, "group_cosine": 0.96,
             "cosine_from": 2 ** 16,
             "bias_margin": 0.1, "bias_compared_share": 0.25},
}
# at a CPU rehearsal's widths a logit is a sum of 32 products and a handful
# of the tokens change an expert. The rehearsal shows that the check runs
# and that each planted fault is caught, not how close the program comes.
TOLERANCE_TINY = {
    "f32": dict(TOLERANCE["f32"], cosine_from=2 ** 6),
    "bf16": {"logits_rel_l2": 6e-2, "loss_rel": 5e-3,
             "res_err_rel": 0.2,
             "stream_rel_l2": 0.01, "stream_grad_rel_l2": 0.02,
             "attention_rel_l2": 0.01, "attention_grad_rel_l2": 0.03,
             "step_loss_rel": 5e-3, "update_norm_rel": 0.5,
             "mapping_norm_rel": 0.5,
             "update_cosine": 0.7, "group_cosine": 0.7,
             "cosine_from": 2 ** 10,
             "bias_margin": 0.5, "bias_compared_share": 0.05},
}
# wrong programs, written down: what each changes is in ``forward``
FAULTS = ("sinkhorn_one_iter", "columns_first", "write_without_two",
          "read_unnormalised", "end_first_stream", "plain_theta",
          "scale_without_mscale")
# written down as well, and NOT another function: the streams averaged
# instead of summed at the end. x_L meets nothing but RMSNorms (final_norm,
# the module's mtp_hnorm), which take a factor 1 / n out again, eps apart:
# no comparison of outputs can tell the two, and a test says so.
SAME_FUNCTION = ("streams_averaged",)


def rms_norm(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * g


def yarn_frequencies(rot, theta, scaling=None):
    """The rot / 2 frequencies of a rotary part of ``rot`` dims: plain
    theta's, or with ``scaling`` (config.json's ``rope_scaling`` block of
    type "yarn") the blend above. Host floats."""
    plain = [theta ** (-2.0 * i / rot) for i in range(rot // 2)]
    if not scaling or scaling["factor"] == 1:
        return plain

    def pair(turns):
        return rot * math.log(scaling["original_max_position_embeddings"]
                              / (2 * math.pi * turns)) / (2 * math.log(theta))

    low = max(math.floor(pair(scaling["beta_fast"])), 0)
    high = min(math.ceil(pair(scaling["beta_slow"])), rot // 2 - 1)
    out = []
    for i, f in enumerate(plain):
        keep = 1.0 - min(1.0, max(0.0, (i - low) / max(high - low, 1e-3)))
        out.append(f / scaling["factor"] * (1.0 - keep) + f * keep)
    return out


def softmax_scale(d_head, scaling=None):
    """1 / sqrt(d_head), times YaRN's mscale squared (the DeepSeek-V3
    form: cos and sin take no factor where mscale = mscale_all_dim)."""
    scale = 1.0 / math.sqrt(d_head)
    if scaling and scaling["factor"] > 1:
        mscale = 0.1 * scaling["mscale_all_dim"] \
            * math.log(scaling["factor"]) + 1.0
        scale *= mscale * mscale
    return scale


def rotate(x, freqs):
    """x (S, ..., R) at positions 0 .. S-1 -> the same, every pair (j,
    j + R/2) turned by t * freqs[j]: rotate-half over the whole R."""
    s, r = x.shape[0], x.shape[-1]
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] \
        * jnp.asarray(freqs, jnp.float32)[None]                 # (S, R/2)
    ang = ang.reshape((s,) + (1,) * (x.ndim - 2) + (r // 2,))
    a, b = x[..., :r // 2], x[..., r // 2:]
    return jnp.concatenate([a * jnp.cos(ang) - b * jnp.sin(ang),
                            b * jnp.cos(ang) + a * jnp.sin(ang)], -1)


def attention(q_nope, q_rope, k_nope, k_pe, v, scale, q_block=None,
              ckpt=lambda f: f):
    """One sequence: q_nope, k_nope (S, H, dn), q_rope (S, H, dr), k_pe
    (S, dr) — ONE a token, every head's — v (S, H, dv) -> (S, H dv),
    position t attending to s <= t: a dense mask."""
    s = q_nope.shape[0]
    q_block = q_block or s

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


def sinkhorn(m, iters, eps, columns_first=False):
    """m (..., n, n) -> ``iters`` times rows then columns divided by their
    sums + eps: a Python loop, the matrix a token as it is written."""
    for _ in range(iters):
        for axis in ((-2, -1) if columns_first else (-1, -2)):
            m = m / (jnp.sum(m, axis, keepdims=True) + eps)
    return m


def stream_mapping(w, xs, n, iters, hc_eps, clamp, mm=None, fault=None):
    """A sub-layer's coefficients: xs (..., n, C), ``w`` the mapping's nine
    blobs -> p (..., n), q (..., n), M (..., n, n). ``mm``: the matmul
    (x (.., in) by an (out, in) matrix; ``forward``'s rounds its inputs
    under the float8 control)."""
    mm = mm or (lambda x, m: x @ m.T)
    phi_pre, phi_post, phi_res, b_pre, b_post, b_res, \
        a_pre, a_post, a_res = w
    flat = xs.reshape(xs.shape[:-2] + (-1,))
    u = flat / jnp.sqrt(jnp.mean(flat * flat, -1, keepdims=True) + hc_eps)
    # (a fault: the read's mapping taken from the state as it comes)
    p = jax.nn.sigmoid(a_pre * mm(
        flat if fault == "read_unnormalised" else u, phi_pre) + b_pre)
    q = jax.nn.sigmoid(a_post * mm(u, phi_post) + b_post) \
        * (1.0 if fault == "write_without_two" else 2.0)
    logits = a_res * mm(u, phi_res).reshape(flat.shape[:-1] + (n, n)) + b_res
    m = sinkhorn(jnp.exp(jnp.clip(logits, -clamp, clamp)), iters, hc_eps,
                 fault == "columns_first")
    return p, q, m


def stream_sublayer(cfg, w, xs, y, stream_dtype=None, fault=None):
    """ONE sub-layer's passes over the stream with its output GIVEN, for the
    comparison that holds the program's stream functions on operands of its
    own (``xing_train.stream_check``): xs (N, S, n, C), y (N, S, C), ``w``
    the mapping's nine blobs -> (coefficients (N, S, n (n + 2)): p, q, M
    row-major; h = sum_j p_j X_j (N, S, C); X' (N, S, n, C)). ``stream_dtype``:
    a control, the stream STORED in that type (xs and y rounded on the way
    in, h and X' on the way out, the gradient straight through)."""
    def stored(t):
        if stream_dtype is None:
            return t
        return t + jax.lax.stop_gradient(
            t.astype(stream_dtype).astype(jnp.float32) - t)

    with jax.default_matmul_precision("highest"):
        n = cfg["hc_mult"]
        w = [jnp.asarray(b, jnp.float32) for b in w]
        xs, y = stored(jnp.asarray(xs, jnp.float32)), \
            stored(jnp.asarray(y, jnp.float32))
        p, q, m = stream_mapping(
            w, xs, n, 1 if fault == "sinkhorn_one_iter"
            else cfg["hc_sinkhorn_iters"], cfg["hc_eps"], cfg["hc_clamp"],
            fault=fault)
        h = jnp.einsum("...j,...jc->...c", p, xs)
        out = jnp.einsum("...ij,...jc->...ic", m, xs) \
            + q[..., :, None] * y[..., None, :]
        coef = jnp.concatenate([p, q, m.reshape(m.shape[:-2] + (-1,))], -1)
        return coef, stored(h), stored(out)


def forward(cfg, weights, tokens, targets=None, held=None, last=None,
            q_block=None, round_to=None, choice=None, remat=False,
            round_when=None, fault=None):
    """tokens (N, S) int -> {"logits" (N, last or S, V); "counts" (M, E)
    assignments per expert by this reference's own top-k, one row a sparse
    layer, the module's block last; "choice" (M, N, S, k) that top-k;
    "route_flips" (M,); "routed" (M, N, S, C) each sparse layer's routed
    part, "shared" (M, N, S, C) its shared expert's and "stream" (M, N, S,
    n, C) the stream after it; "res_err" (2 layers,) what the Sinkhorn
    iterations leave a sub-layer, "pre_mean", "post_mean" the same way; and
    with ``targets`` "nll" (N, S), and with a module "mtp_logits" and
    "mtp_nll" (N, S - 1)}. ``cfg``: num_hidden_layers, num_dense_layers,
    num_heads, q_lora_rank, kv_lora_rank, qk_nope_head_dim,
    qk_rope_head_dim, v_head_dim, num_experts (what the router scores),
    num_experts_per_tok, route_scale, rope_theta, rope_scaling (the yarn
    block or None), rms_norm_eps, hc_mult, hc_sinkhorn_iters, hc_eps,
    hc_clamp, mtp_layers (0 or 1), mtp_weight; optionally scale_head_dim,
    the head width the scores' scale is taken from where it is not this
    net's own (a rehearsal that cuts the heads keeps the published scale)."""
    assert fault is None or fault in FAULTS + SAME_FUNCTION, fault
    with jax.default_matmul_precision("highest"):
        eps = cfg["rms_norm_eps"]
        n_h, rank = cfg["num_heads"], cfg["kv_lora_rank"]
        nope, rope = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"]
        n_exp, top_k = cfg["num_experts"], cfg["num_experts_per_tok"]
        n, hc_eps, clamp = cfg["hc_mult"], cfg["hc_eps"], cfg["hc_clamp"]
        iters = 1 if fault == "sinkhorn_one_iter" \
            else cfg["hc_sinkhorn_iters"]
        scaling = cfg.get("rope_scaling")
        freqs = yarn_frequencies(
            rope, cfg["rope_theta"],
            None if fault == "plain_theta" else scaling)
        scale = softmax_scale(
            cfg.get("scale_head_dim", nope + rope),
            None if fault == "scale_without_mscale" else scaling)
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

        def mapping(w, xs):
            return stream_mapping(w, xs, n, iters, hc_eps, clamp, mm, fault)

        stats = []                       # a sub-layer: (res_err, p, q means)

        def sublayer(w_map, xs, norm_g, fn):
            """xs (N, S, n, C) -> X' through one sub-layer ``fn``."""
            p, q, m = mapping(w_map, xs)
            h = jnp.einsum("...j,...jc->...c", p, xs)
            y = fn(rms_norm(h, norm_g, eps))
            off = jax.lax.stop_gradient(m)
            err = jnp.maximum(jnp.max(jnp.abs(jnp.sum(off, -1) - 1.0)),
                              jnp.max(jnp.abs(jnp.sum(off, -2) - 1.0)))
            out = jnp.einsum("...ij,...jc->...ic", m, xs) \
                + q[..., :, None] * y[..., None, :]
            return out, y, (err, jnp.mean(p), jnp.mean(q))

        def mla(w, a):                   # one sequence (S, C) -> (S, C)
            s = a.shape[0]
            c_q = rms_norm(mm(a, w["mla_qa"][0]), w["mla_qnorm"][0], eps)
            q = mm(c_q, w["mla_qb"][0]).reshape(s, n_h, nope + rope)
            kva = mm(a, w["mla_kva"][0])
            c = rms_norm(kva[:, :rank], w["mla_kvnorm"][0], eps)
            k_pe = kva[:, rank:]                               # (S, rope)
            k_nope = mm(c, w["mla_kvb_k"][0]).reshape(s, n_h, nope)
            v = mm(c, w["mla_kvb_v"][0]).reshape(s, n_h, -1)
            q_nope, q_rope = q[..., :nope], q[..., nope:]
            o = attention(rnd(q_nope), rnd(rotate(q_rope, freqs)),
                          rnd(k_nope), rnd(rotate(k_pe, freqs)), rnd(v),
                          scale, q_block, ckpt)
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

        def layer(sparse, w, xs, handed):
            xs, _, first = sublayer(
                w["hc_a_map"], xs, w["attn_norm"][0],
                lambda a: jax.vmap(lambda one: mla(w, one))(a))
            if not sparse:
                xs, _, second = sublayer(
                    w["hc_f_map"], xs, w["ffn_norm"][0],
                    lambda u: mlp(u, w["ffn_gate"][0], w["ffn_up"][0],
                                  w["ffn_down"][0]))
                return xs, (first, second), None
            kept = {}

            def ffn(u):
                routed, shared, n_e, own, flips = moe(w, u, handed)
                kept.update(routed=routed, shared=shared, rest=(n_e, own,
                                                                flips))
                return routed + shared

            xs, _, second = sublayer(w["hc_f_map"], xs, w["ffn_norm"][0],
                                     ffn)
            return xs, (first, second), kept["rest"] + (
                kept["routed"], kept["shared"], xs)

        def under(prefix):
            return {name[len(prefix):]: f32(blobs)
                    for name, blobs in weights.items()
                    if name.startswith(prefix)}

        per_moe = []

        def block(prefix, sparse, xs):
            handed = None if choice is None or not sparse \
                else jnp.asarray(choice[len(per_moe)])
            xs, subs, extra = ckpt(
                lambda w, xs, handed: layer(sparse, w, xs, handed))(
                under(prefix), xs, handed)
            stats.extend(subs)
            if extra is not None:
                per_moe.append(extra)
            return xs

        def start(h):                    # (N, S, C) -> n copies
            return jnp.repeat(h[..., None, :], n, axis=-2)

        def end(xs):                     # the streams summed
            if fault == "end_first_stream":
                return xs[..., 0, :]     # one stream, not their sum
            total = jnp.sum(xs, -2)
            return total / n if fault == "streams_averaged" else total

        table = f32(weights["embed"])[0]
        xs = start(table[tokens])                            # (N, S, n, C)
        for i in range(cfg["num_hidden_layers"]):
            xs = block(f"l{i}_", i >= cfg["num_dense_layers"], xs)
        x = end(xs)
        w_head = f32(weights["lm_head"])[0]

        def head(seq):                   # one sequence: the vocabulary is
            xs_, tgt, matrix = seq       # wide, (S, V) at a time
            full = mm(xs_, matrix)
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
            z = end(block("mtp_", True,
                          start(mm(z_in, f32(weights["mtp_eh"])[0]))))
            second = jnp.concatenate(
                [targets[:, 1:], jnp.zeros_like(targets[:, :1])], 1)
            out["mtp_logits"], mtp_nll = heads(
                rms_norm(z, f32(weights["mtp_snorm"])[0], eps), second,
                w_head)
            out["mtp_nll"] = mtp_nll[:, :-1]
        names = ("counts", "choice", "route_flips", "routed", "shared",
                 "stream")
        out.update({name: jnp.stack([m[j] for m in per_moe])
                    for j, name in enumerate(names) if per_moe})
        out.update({name: jnp.stack([s[j] for s in stats])
                    for j, name in enumerate(
                        ("res_err", "pre_mean", "post_mean"))})
        return out


def loss(cfg, weights, tokens, targets, **how):
    """-> (mean next-token NLL + mtp_weight x the module's mean over the
    S - 1 positions with a second-next token, forward's dict with
    "lm_loss" and, with a module, "mtp_loss" beside); ``how`` is
    ``forward``'s ``held`` / ``last`` / ``q_block`` / ``round_to`` /
    ``round_when`` / ``choice`` / ``remat`` / ``fault``."""
    out = forward(cfg, weights, tokens, targets, **how)
    out["lm_loss"] = total = jnp.mean(out["nll"])
    if "mtp_nll" in out:
        out["mtp_loss"] = jnp.mean(out["mtp_nll"])
        total = total + cfg["mtp_weight"] * out["mtp_loss"]
    return total, out


def cosine_lr(it, base, warm, total, floor):
    """The solver's ``cosine`` policy at iteration ``it`` (0 the first):
    linear warm-up over ``warm`` iterations, ``base * (it + 1) / warm``,
    then half a cosine from ``base`` down to ``floor * base`` at ``total``."""
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
    -> {"loss", "lm_loss", "counts" (M, E), "grad_norm",
    "change": {layer: [w' - w]}}"""
    biases = router_names(weights)

    def trained(w):                      # the biases enter as constants
        return {name: blobs[:-1] if name in biases else list(blobs)
                for name, blobs in w.items()}

    def objective(some):
        whole = {name: blobs + [weights[name][-1]] if name in biases
                 else blobs for name, blobs in some.items()}
        total, out = loss(cfg, whole, tokens, targets, **how)
        return total, (out["counts"], out["lm_loss"])

    (total, (counts, lm)), grads = jax.value_and_grad(
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
    return {"loss": total, "lm_loss": lm, "counts": counts,
            "grad_norm": norm, "change": change}
