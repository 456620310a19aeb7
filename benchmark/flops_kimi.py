"""Required operations of Kimi-Linear's training step as ONE of 32 chips
that share each layer sees it (KDA layers: the gated delta rule behind a
short convolution; one latent-attention layer in four; a leading dense
layer, MoE layers of which this rank holds ``num_experts`` of
``router_num_experts``, a shared expert, an untied head), from the
configuration's published sizes: the yardstick ``mfu_required`` and the
cell's per-layer shares divide by. Same conventions as ``flops_trinity``;
checked against hand counts in tests/.

Per token, forward multiply-accumulates. A KDA layer: the q, k, v and o
projections (4 D W, W = H d), the decay's and the output gate's low-rank
pairs (2 (D d + d W)), the write strength's (D H), and the recurrence's OWN
work, 3 H d_k d_v (the decayed state times k, the rank-one write, the state
times q) whatever computes it: the chunked form's extra products (the
intra-chunk scores, the triangular system), the convolutions, norms and
gates count zero. An MLA layer: q (D H (nope + rope)), the latent and the
shared key part (D (rank + rope)), keys and values out of the latent (rank H
(nope + v)), o (H v D), and the attention over half the square at (nope +
rope) + v a (query, key) pair a head. A dense layer: 3 D I. A MoE layer: the
router (D E); the routed experts at an EVEN split, held / E of a token's k
experts (3 D F each); the shared expert (3 D F). Once: the head (D V). Times
2 FLOPs, times 3 passes (forward, and backward's two products). Every
recomputation counts as zero.
"""

from __future__ import annotations

PASSES = 3          # forward + backward's two products per matmul
FLOPS_PER_MAC = 2


def layers_run(cfg: dict) -> dict:
    """{"dense", "moe", "kda", "mla"}: how many layers of each kind the
    configuration RUNS (``layers_run``)."""
    run = cfg["layers_run"]
    kinds = run["layer_types"]
    assert len(kinds) == cfg["num_hidden_layers"] == run["dense"] + run["moe"]
    return {"dense": run["dense"], "moe": run["moe"],
            "kda": kinds.count("kda"), "mla": kinds.count("mla")}


def kda_sizes(cfg: dict) -> tuple:
    """(H, d): the recurrence's heads and a head's width (d_k = d_v)."""
    lin = cfg["linear_attn_config"]
    return lin["num_heads"], lin["head_dim"]


def required_macs_per_token(cfg: dict, seq_len: int) -> dict:
    """Forward multiply-accumulates per token, summed over layers, from the
    keys of the model's config.json (``num_experts`` = held here,
    ``router_num_experts`` = what the router scores)."""
    dm, f = cfg["hidden_size"], cfg["moe_intermediate_size"]
    h, d = kda_sizes(cfg)
    w = h * d
    n = layers_run(cfg)
    heads = cfg["num_attention_heads"]
    qk = cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]
    rank, v = cfg["kv_lora_rank"], cfg["v_head_dim"]
    e = cfg["router_num_experts"]
    return {
        "kda_projections": n["kda"] * (4 * dm * w + 2 * (dm * d + d * w)
                                       + dm * h),
        "kda_recurrence": n["kda"] * 3 * h * d * d,
        "mla_projections": n["mla"] * (
            dm * heads * qk + dm * (rank + cfg["qk_rope_head_dim"])
            + rank * heads * (cfg["qk_nope_head_dim"] + v) + heads * v * dm),
        # scores at qk, values at v, over half the square a token
        "mla_attention": n["mla"] * heads * (qk + v) * seq_len // 2,
        "dense_ffn": n["dense"] * 3 * dm * cfg["intermediate_size"],
        "router": n["moe"] * dm * e,
        "experts": n["moe"] * cfg["num_experts_per_token"] * 3 * dm * f
        * cfg["num_experts"] // e,
        "shared_expert": n["moe"] * cfg["num_shared_experts"] * 3 * dm * f,
        "head": dm * cfg["vocab_size"],
    }


def required_flops_per_token(cfg: dict, seq_len: int) -> dict:
    """Training FLOPs per token by part, and their ``total``."""
    parts = {k: v * FLOPS_PER_MAC * PASSES
             for k, v in required_macs_per_token(cfg, seq_len).items()}
    parts["total"] = sum(parts.values())
    return parts


def expert_flops_per_assignment(cfg: dict) -> int:
    """Training FLOPs of ONE token through ONE routed expert (3 D F, three
    passes)."""
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"] \
        * FLOPS_PER_MAC * PASSES


def flash_attention_step(cfg: dict, batch: int, seq_len: int,
                         itemsize: int = 2) -> dict:
    """What the MLA layers' flash kernels of ONE training step require:
    ``flops`` — forward's two products (scores at nope + rope, values at v)
    and backward's four (dV and dP at v, dQ and dK at nope + rope) over half
    the square, so 3 x ((nope + rope) + v) multiply-accumulates a live pair
    a head; the backward's recomputed scores and remat's second forward
    count as zero. ``bytes`` — q read and o written, k and v read ONCE by
    the forward; q, o, do, k, v read and dq, dk, dv written by the backward;
    a key at its own width, H nope + rope (the shared part once: repeating it
    to the heads, as one arm does, is not required)."""
    heads = cfg["num_attention_heads"]
    nope, rope, v = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                     cfg["v_head_dim"])
    n = layers_run(cfg)["mla"]
    pairs = batch * seq_len * seq_len // 2
    tokens = batch * seq_len * itemsize
    q_w, k_w, v_w = heads * (nope + rope), heads * nope + rope, heads * v
    return {"flops": n * pairs * heads * 3 * (nope + rope + v)
            * FLOPS_PER_MAC,
            # fwd: q, k, v, o; bwd: q, k, v, o, do, dq, dk, dv
            "bytes": n * tokens * (3 * q_w + 3 * k_w + 6 * v_w)}


def kda_scan_step(cfg: dict, batch: int, seq_len: int, itemsize: int = 2,
                  decay_itemsize: int = 4) -> dict:
    """What the KDA layers' recurrences of ONE training step require,
    whatever implements them: ``flops`` — 3 passes of 3 H d_k d_v
    multiply-accumulates a token; ``bytes`` — q, k, v, beta and o at the
    compute type's size and the log-decay at f32's, read or written once,
    and their gradients once."""
    h, d = kda_sizes(cfg)
    n = layers_run(cfg)["kda"]
    tokens = batch * seq_len
    per_token = 4 * h * d * itemsize + h * d * decay_itemsize + h * itemsize
    return {"flops": n * tokens * PASSES * 3 * h * d * d * FLOPS_PER_MAC,
            "bytes": n * tokens * 2 * per_token}
