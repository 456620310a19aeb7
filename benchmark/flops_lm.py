"""Required operations of a token-model training step, from the
configuration's published sizes (the yardstick ``mfu_required`` and the
per-layer shares divide by; kept with the benchmark, checked against hand
counts in tests/).

Per token and forward pass, multiply-accumulates of: the q, k, v and o
projections (4 x D^2); causal attention, scores and values together
(S x D at sequence length S: the masked half is not required); the router
(D x E); the ``k`` experts a token is routed to, three projections each
(k x 3 x D x F); the head (D x V). Times 2 FLOPs, times 3 passes (forward,
and the two products of backward). The embedding lookup, norms, rotary
positions, softmaxes, the routing itself (top-k, sort, gather, scatter) and
any recomputation count as zero.
"""

from __future__ import annotations

PASSES = 3          # forward + backward's two products per matmul
FLOPS_PER_MAC = 2


def required_macs_per_token(cfg: dict, seq_len: int) -> dict:
    """Forward multiply-accumulates per token and LAYER-SUMMED part, from
    the keys of the model's published config.json."""
    d, f = cfg["hidden_size"], cfg["intermediate_size"]
    layers = cfg["num_hidden_layers"]
    return {
        "projections": layers * 4 * d * d,
        "attention": layers * seq_len * d,
        "router": layers * d * cfg["num_experts"],
        "experts": layers * cfg["num_experts_per_tok"] * 3 * d * f,
        "head": d * cfg["vocab_size"],
    }


def required_flops_per_token(cfg: dict, seq_len: int) -> dict:
    """Training FLOPs per token by part, and their ``total``."""
    parts = {k: v * FLOPS_PER_MAC * PASSES
             for k, v in required_macs_per_token(cfg, seq_len).items()}
    parts["total"] = sum(parts.values())
    return parts


def flash_attention_step(cfg: dict, batch: int, seq_len: int,
                         itemsize: int = 2) -> dict:
    """What the causal flash-attention kernels of ONE training step require
    (all layers): ``flops`` — forward's two products and backward's four
    (dV, dP, dQ, dK) over the unmasked half, the backward's recomputed
    scores counting as zero; ``bytes`` — q, k, v read and o written by the
    forward, q, k, v, o, do read and dq, dk, dv written by the backward,
    each once at ``itemsize`` (the log-sum-exp rows are 1/head_dim of
    that and left out)."""
    d = cfg["hidden_size"]
    layers = cfg["num_hidden_layers"]
    one_product = batch * seq_len * seq_len // 2 * d * FLOPS_PER_MAC
    tensor = batch * seq_len * d * itemsize
    return {"flops": layers * 6 * one_product,
            "bytes": layers * (4 + 8) * tensor}
