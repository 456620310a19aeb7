"""Required operations of ZAYA1's training step as ONE rank of an
expert-parallel pair sees it (CCA attention in a latent of H d, a top-1 MoE
of which this rank holds ``num_experts`` of ``router_num_experts``, a tied
head), from the configuration's published sizes: the yardstick
``mfu_required`` and the cell's per-layer shares divide by. Same conventions
as ``flops_lm`` / ``flops_looplm``; checked against hand counts in tests/.

Per token and layer, forward multiply-accumulates: the q, k, v1, v2 and o
projections (D (Lq + Lk + Lk) + Lq D with Lq = H d, Lk = G d); the grouped
convolution ((H + G) groups of d x d, ``cca_time1`` taps); causal attention
at the latent's width, scores and values together (S x Lq: the masked half
is not required); the router (D R + 2 R^2 + R E); the experts at an EVEN
split, held / E of a token's one expert (3 D F) — a constant, so the metric
moves 1:1 with throughput whatever the step's own routing. Once: the head
(D x V). Times 2 FLOPs, times 3 passes (forward, and backward's two
products). The lookup, norms, the depthwise convolution, shift, mean, L2
norm, rotary positions, softmaxes, routing and every recomputation count as
zero.
"""

from __future__ import annotations

PASSES = 3          # forward + backward's two products per matmul
FLOPS_PER_MAC = 2


def latent(cfg: dict) -> tuple:
    """(Lq, Lk, d): the widths of q and of k / v in the latent."""
    d = cfg["head_dim"]
    return cfg["num_attention_heads"] * d, cfg["num_key_value_heads"] * d, d


def required_macs_per_token(cfg: dict, seq_len: int) -> dict:
    """Forward multiply-accumulates per token, summed over layers, from the
    keys of the model's config.json (``num_experts`` = held here,
    ``router_num_experts`` = what the router scores)."""
    dm, f, r = cfg["hidden_size"], cfg["moe_intermediate_size"], \
        cfg["router_hidden_size"]
    lq, lk, d = latent(cfg)
    layers, e = cfg["num_hidden_layers"], cfg["router_num_experts"]
    groups = cfg["num_attention_heads"] + cfg["num_key_value_heads"]
    return {
        "projections": layers * (dm * (lq + 2 * lk) + lq * dm),
        "conv": layers * groups * d * d * cfg["cca_time1"],
        "attention": layers * seq_len * lq,
        "router": layers * (dm * r + 2 * r * r + r * e),
        "experts": layers * cfg["num_experts_per_tok"] * 3 * dm * f
        * cfg["num_experts"] // e,
        "head": dm * cfg["vocab_size"],
    }


def required_flops_per_token(cfg: dict, seq_len: int) -> dict:
    """Training FLOPs per token by part, and their ``total``."""
    parts = {k: v * FLOPS_PER_MAC * PASSES
             for k, v in required_macs_per_token(cfg, seq_len).items()}
    parts["total"] = sum(parts.values())
    return parts


def expert_flops_per_assignment(cfg: dict) -> int:
    """Training FLOPs of ONE token through ONE expert (3 D F, three
    passes): what ``held_moe_flops_util`` multiplies by the assignments the
    step really routed to the held experts."""
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"] \
        * FLOPS_PER_MAC * PASSES


def flash_attention_step(cfg: dict, batch: int, seq_len: int,
                         itemsize: int = 2) -> dict:
    """What the causal flash-attention kernels of ONE training step require
    at H query / G key-value heads: ``flops`` — forward's two products and
    backward's four (dV, dP, dQ, dK) over the unmasked half at the query
    width; the backward's recomputed scores and remat's second forward
    count as zero. ``bytes`` — q read and o written, k and v read ONCE at
    their own width by the forward; q, o, do, k, v read and dq, dk, dv
    written by the backward (k and v repeated to the query heads, as one
    arm does, is not required)."""
    lq, lk, _ = latent(cfg)
    layers = cfg["num_hidden_layers"]
    one_product = batch * seq_len * seq_len // 2 * lq * FLOPS_PER_MAC
    wide = batch * seq_len * lq * itemsize
    narrow = batch * seq_len * lk * itemsize
    return {"flops": layers * 6 * one_product,
            "bytes": layers * ((2 + 4) * wide + (2 + 4) * narrow)}
