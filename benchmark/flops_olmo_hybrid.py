"""Required operations of Olmo-Hybrid's training step as ONE of the chips
that share each layer's HEADS sees it (linear layers: Gated DeltaNet behind a
short convolution; one full-attention layer in four; a dense SiLU FFN in
every layer, whole on every chip; an untied head over the vocabulary's
slice), from the configuration's published sizes: the yardstick
``mfu_required`` and the cell's per-layer shares divide by. Same conventions
as ``flops_kimi``; checked against hand counts in tests/.

Per token, forward multiply-accumulates, H the heads HELD (the
configuration's ``linear_num_*_heads`` / ``num_attention_heads``). A linear
layer: the q and k projections (2 D H d_k), v, the output gate z and o (3 D
H d_v), the decay's and the write strength's (2 D H), and the recurrence's
OWN work, 3 H d_k d_v (the decayed state times k, the rank-one write, the
state times q) whatever computes it: the chunked form's extra products (the
intra-chunk scores, the triangular system), the convolutions, norms and
gates count zero. A full layer: q, k, v, o (4 D H d) and the attention over
half the square at 2 d a (query, key) pair a head. Every layer: the FFN, 3 D
I. Once: the head (D V). Times 2 FLOPs, times 3 passes (forward, and
backward's two products). Padding, every recomputation and the lanes a
kernel wastes count as zero.
"""

from __future__ import annotations

PASSES = 3          # forward + backward's two products per matmul
FLOPS_PER_MAC = 2


def layers_run(cfg: dict) -> dict:
    """{"linear", "full"}: how many layers of each kind the configuration
    RUNS (``layers_run``)."""
    kinds = cfg["layers_run"]["layer_types"]
    assert len(kinds) == cfg["num_hidden_layers"]
    return {"linear": kinds.count("linear"), "full": kinds.count("full")}


def gdn_sizes(cfg: dict) -> tuple:
    """(H, d_k, d_v): the recurrence's held heads and a head's widths."""
    assert cfg["linear_num_key_heads"] == cfg["linear_num_value_heads"]
    return (cfg["linear_num_key_heads"], cfg["linear_key_head_dim"],
            cfg["linear_value_head_dim"])


def attention_sizes(cfg: dict) -> tuple:
    """(H, d): the full layers' held heads and a head's width."""
    assert cfg["num_attention_heads"] == cfg["num_key_value_heads"]
    return cfg["num_attention_heads"], cfg["attention_head_dim"]


def required_macs_per_token(cfg: dict, seq_len: int) -> dict:
    """Forward multiply-accumulates per token, summed over layers, from the
    keys of the model's config.json (head counts = held here)."""
    dm = cfg["hidden_size"]
    h, d_k, d_v = gdn_sizes(cfg)
    heads, d = attention_sizes(cfg)
    n = layers_run(cfg)
    return {
        "gdn_projections": n["linear"] * dm * h * (2 * d_k + 3 * d_v + 2),
        "gdn_recurrence": n["linear"] * 3 * h * d_k * d_v,
        "attention_projections": n["full"] * 4 * dm * heads * d,
        # scores and values at d each, over half the square a token
        "attention": n["full"] * heads * 2 * d * seq_len // 2,
        "ffn": cfg["num_hidden_layers"] * 3 * dm * cfg["intermediate_size"],
        "head": dm * cfg["vocab_size"],
    }


def required_flops_per_token(cfg: dict, seq_len: int) -> dict:
    """Training FLOPs per token by part, and their ``total``."""
    parts = {k: v * FLOPS_PER_MAC * PASSES
             for k, v in required_macs_per_token(cfg, seq_len).items()}
    parts["total"] = sum(parts.values())
    return parts


def flash_attention_step(cfg: dict, batch: int, seq_len: int,
                         itemsize: int = 2) -> dict:
    """What the full layers' flash kernels of ONE training step require:
    ``flops`` — forward's two products and backward's four over half the
    square, 3 x 2 d multiply-accumulates a live pair a head; the backward's
    recomputed scores and remat's second forward count as zero. ``bytes`` —
    q, k, v read and o written ONCE by the forward; q, k, v, o, do read and
    dq, dk, dv written by the backward, each H d wide."""
    heads, d = attention_sizes(cfg)
    n = layers_run(cfg)["full"]
    pairs = batch * seq_len * seq_len // 2
    tokens = batch * seq_len * itemsize
    return {"flops": n * pairs * heads * 3 * 2 * d * FLOPS_PER_MAC,
            # fwd: q, k, v, o; bwd: q, k, v, o, do, dq, dk, dv
            "bytes": n * tokens * 12 * heads * d}


def gdn_scan_step(cfg: dict, batch: int, seq_len: int, itemsize: int = 2,
                  decay_itemsize: int = 4) -> dict:
    """What the linear layers' recurrences of ONE training step require at
    the published 96 / 192, whatever implements them: ``flops`` — 3 passes
    of 3 H d_k d_v multiply-accumulates a token; ``bytes`` — q, k (d_k), v,
    o (d_v) and beta (1) a head at the compute type's size and the
    log-decay, ONE a head, at f32's, read or written once, and their
    gradients once."""
    h, d_k, d_v = gdn_sizes(cfg)
    n = layers_run(cfg)["linear"]
    tokens = batch * seq_len
    per_token = h * ((2 * d_k + 2 * d_v + 1) * itemsize + decay_itemsize)
    return {"flops": n * tokens * PASSES * 3 * h * d_k * d_v * FLOPS_PER_MAC,
            "bytes": n * tokens * 2 * per_token}
