"""Required operations of GLM-4.7-Flash's training step as ONE of 8 chips
that share each layer sees it (latent attention in every block; a leading
dense layer, sparse layers of which this rank holds ``n_routed_experts`` of
``router_num_experts``, a shared expert; an untied head; a prediction module
of depth 1: W_eh, one more sparse block, a second pass of the head), from
the configuration's published sizes: the yardstick ``mfu_required`` and the
cell's per-layer shares divide by. Same conventions as ``flops_kimi`` and
``flops_trinity``; checked against hand counts in tests/.

Per token, forward multiply-accumulates. A block's latent attention: the
query latent and its heads (D r_q + r_q H (nope + rope)), the key-value
latent and the shared key part (D (r_kv + rope)), keys and values out of the
latent (r_kv H (nope + v)), o (H v D), and the attention over half the
square at (nope + rope) + v a (query, key) pair a head. The dense layer:
3 D I. A sparse block: the router (D E); the routed experts at an EVEN
split, held / E of a token's k experts (3 D F each); the shared expert
(3 D F). The head (D V) once, and once more for the module at the S - 1
positions of S that have a second-next token; the module's W_eh (2 D D).
Times 2 FLOPs, times 3 passes (forward, and backward's two products). The
rotation, norms, gates, the shared part's hand-over to the heads and every
recomputation count as zero.
"""

from __future__ import annotations

PASSES = 3          # forward + backward's two products per matmul
FLOPS_PER_MAC = 2


def layers_run(cfg: dict) -> dict:
    """{"dense", "moe", "mtp", "blocks", "sparse"}: how many layers of each
    kind the configuration RUNS (``layers_run``); ``blocks`` have latent
    attention (the module's included), ``sparse`` of them a router."""
    run = cfg["layers_run"]
    assert cfg["num_hidden_layers"] == run["dense"] + run["moe"]
    return {**{k: run[k] for k in ("dense", "moe", "mtp")},
            "blocks": run["dense"] + run["moe"] + run["mtp"],
            "sparse": run["moe"] + run["mtp"]}


def required_macs_per_token(cfg: dict, seq_len: int) -> dict:
    """Forward multiply-accumulates per token, summed over layers, from the
    keys of the model's config.json (``n_routed_experts`` = held here,
    ``router_num_experts`` = what the router scores)."""
    dm, f = cfg["hidden_size"], cfg["moe_intermediate_size"]
    n = layers_run(cfg)
    heads = cfg["num_attention_heads"]
    nope, rope, v = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                     cfg["v_head_dim"])
    r_q, r_kv = cfg["q_lora_rank"], cfg["kv_lora_rank"]
    e = cfg["router_num_experts"]
    return {
        "mla_projections": n["blocks"] * (
            dm * r_q + r_q * heads * (nope + rope) + dm * (r_kv + rope)
            + r_kv * heads * (nope + v) + heads * v * dm),
        # scores at nope + rope, values at v, over half the square a token
        "mla_attention": n["blocks"] * heads * (nope + rope + v)
        * seq_len // 2,
        "dense_ffn": n["dense"] * 3 * dm * cfg["intermediate_size"],
        "router": n["sparse"] * dm * e,
        "experts": n["sparse"] * cfg["num_experts_per_tok"] * 3 * dm * f
        * cfg["n_routed_experts"] // e,
        "shared_expert": n["sparse"] * cfg["n_shared_experts"] * 3 * dm * f,
        "head": dm * cfg["vocab_size"],
        # the module's pass over the S - 1 positions with a target
        "mtp_head": n["mtp"] * dm * cfg["vocab_size"] * (seq_len - 1)
        // seq_len,
        "mtp_eh": n["mtp"] * 2 * dm * dm,
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
    """What the six blocks' flash kernels of ONE training step require:
    ``flops`` — forward's two products (scores at nope + rope, values at v)
    and backward's four (dV and dP at v, dQ and dK at nope + rope) over half
    the square, so 3 x ((nope + rope) + v) multiply-accumulates a live pair
    a head; the backward's recomputed scores and remat's second forward
    count as zero. ``bytes`` — q read and o written, k and v read ONCE by
    the forward; q, o, do, k, v read and dq, dk, dv written by the backward;
    a key at its own width, H nope + rope (the shared part once: writing it
    into every head's lanes, as the program does, is not required)."""
    heads = cfg["num_attention_heads"]
    nope, rope, v = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                     cfg["v_head_dim"])
    n = layers_run(cfg)["blocks"]
    pairs = batch * seq_len * seq_len // 2
    tokens = batch * seq_len * itemsize
    q_w, k_w, v_w = heads * (nope + rope), heads * nope + rope, heads * v
    return {"flops": n * pairs * heads * 3 * (nope + rope + v)
            * FLOPS_PER_MAC,
            # fwd: q, k, v, o; bwd: q, k, v, o, do, dq, dk, dv
            "bytes": n * tokens * (3 * q_w + 3 * k_w + 6 * v_w)}
