"""Required operations of a looped LM's training step (one stack of layers
applied ``total_ut_steps`` times with the same weights, a head after every
pass), from the configuration's published sizes: the yardstick
``mfu_required`` and the cell's per-layer shares divide by. Same conventions
as ``flops_lm``; checked against hand counts in tests/.

Per token and forward pass, multiply-accumulates of ONE application of one
layer: the q, k, v and o projections (4 x D^2); causal attention, scores
and values together (S x D at sequence length S: the masked half is not
required); the gated FFN's three projections (3 x D x F). Times layers x
passes. Each pass's head (D x V) and each exit gate (D; the last pass has
none). Times 2 FLOPs, times 3 passes (forward, and the two products of
backward). The embedding lookup, norms, rotary positions, softmaxes, the
SiLU gate, the exit distribution and every recomputation count as zero:
weight reuse shares parameters, not work, and what remat replays is not
required.
"""

from __future__ import annotations

PASSES = 3          # forward + backward's two products per matmul
FLOPS_PER_MAC = 2


def required_macs_per_token(cfg: dict, seq_len: int) -> dict:
    """Forward multiply-accumulates per token, summed over layers and
    passes, from the keys of the model's published config.json."""
    d, f = cfg["hidden_size"], cfg["intermediate_size"]
    loops = cfg["total_ut_steps"]
    applications = cfg["num_hidden_layers"] * loops
    return {
        "projections": applications * 4 * d * d,
        "attention": applications * seq_len * d,
        "ffn": applications * 3 * d * f,
        "heads": loops * d * cfg["vocab_size"],
        "gates": (loops - 1) * d,
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
    (every application of every layer): ``flops`` — forward's two products
    and backward's four (dV, dP, dQ, dK) over the unmasked half, the
    backward's recomputed scores and remat's second forward counting as
    zero; ``bytes`` — q, k, v read and o written by the forward, q, k, v,
    o, do read and dq, dk, dv written by the backward, each once at
    ``itemsize`` (the log-sum-exp rows are 1/head_dim of that and left
    out)."""
    d = cfg["hidden_size"]
    applications = cfg["num_hidden_layers"] * cfg["total_ut_steps"]
    one_product = batch * seq_len * seq_len // 2 * d * FLOPS_PER_MAC
    tensor = batch * seq_len * d * itemsize
    return {"flops": applications * 6 * one_product,
            "bytes": applications * (4 + 8) * tensor}
