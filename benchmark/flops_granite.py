"""Required operations of Granite-4.0-H-Micro's training step as ONE pipeline
stage sees it (Mamba-2 layers behind a short convolution; one attention
layer in ten; a SwiGLU MLP in every layer; a tied head over the vocabulary's
slice), from the configuration's published sizes: the yardstick
``mfu_required`` and the cell's per-layer shares divide by. Same conventions
as ``flops_olmo_hybrid``; checked against hand counts in tests/.

Per token, forward multiply-accumulates. A mamba layer: the input projection
(D x (2 H P + 2 N + H)), the output projection (H P x D) and the
recurrence's OWN work at the published 64 x 64 x 128, 2 H P N (the rank-one
write dt x B^T into every head's state and the read H C) whatever computes
it: the chunked form's products (the C B^T grid, the masked products with
x), the decay of the state, the convolution, the skip, norms and gates count
zero. The attention layer: q, o (2 D H d), k, v (2 D Hkv d) and the
attention over half the square at 2 d a (query, key) pair a query head.
Every layer: the MLP, 3 D I. Once: the head (D V). Times 2 FLOPs, times 3
passes (forward, and backward's two products). Padding, every recomputation
and the lanes a kernel wastes count as zero, so a later kernel change cannot
make the yardstick stale.
"""

from __future__ import annotations

PASSES = 3          # forward + backward's two products per matmul
FLOPS_PER_MAC = 2


def layers_run(cfg: dict) -> dict:
    """{"mamba", "attention"}: how many layers of each kind the
    configuration RUNS (``layers_run``)."""
    kinds = cfg["layers_run"]["layer_types"]
    assert len(kinds) == cfg["num_hidden_layers"]
    return {"mamba": kinds.count("mamba"),
            "attention": kinds.count("attention")}


def ssd_sizes(cfg: dict) -> tuple:
    """(H, P, N): the scan's heads, a head's width and its state."""
    assert cfg["mamba_n_groups"] == 1
    assert cfg["mamba_n_heads"] * cfg["mamba_d_head"] \
        == cfg["mamba_expand"] * cfg["hidden_size"]
    return cfg["mamba_n_heads"], cfg["mamba_d_head"], cfg["mamba_d_state"]


def attention_sizes(cfg: dict) -> tuple:
    """(H, Hkv, d): query heads, key-value heads and a head's width."""
    heads = cfg["num_attention_heads"]
    return heads, cfg["num_key_value_heads"], cfg["hidden_size"] // heads


def required_macs_per_token(cfg: dict, seq_len: int) -> dict:
    """Forward multiply-accumulates per token, summed over layers, from the
    keys of the model's config.json."""
    dm = cfg["hidden_size"]
    h, p, n_state = ssd_sizes(cfg)
    heads, kv, d = attention_sizes(cfg)
    n = layers_run(cfg)
    return {
        "ssd_projections": n["mamba"] * dm * (3 * h * p + 2 * n_state + h),
        "ssd_recurrence": n["mamba"] * 2 * h * p * n_state,
        "attention_projections": n["attention"] * 2 * dm * (heads + kv) * d,
        # scores and values at d each, over half the square a token
        "attention": n["attention"] * heads * 2 * d * seq_len // 2,
        "ffn": cfg["num_hidden_layers"] * 3 * dm
        * cfg["shared_intermediate_size"],
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
    """What the attention layers' flash kernels of ONE training step
    require: ``flops`` — forward's two products and backward's four over
    half the square, 3 x 2 d multiply-accumulates a live pair a QUERY head;
    the backward's recomputed scores and remat's second forward count as
    zero. ``bytes`` — q and o (H d wide) and k, v (Hkv d wide: a key-value
    head is read once whatever repeats it) read or written ONCE by the
    forward; q, o, do, dq (H d) and k, v, dk, dv (Hkv d) by the backward."""
    heads, kv, d = attention_sizes(cfg)
    n = layers_run(cfg)["attention"]
    pairs = batch * seq_len * seq_len // 2
    tokens = batch * seq_len * itemsize
    return {"flops": n * pairs * heads * 3 * 2 * d * FLOPS_PER_MAC,
            # fwd: q, o | k, v; bwd: q, o, do, dq | k, v, dk, dv
            "bytes": n * tokens * (6 * heads + 6 * kv) * d}


def ssd_scan_step(cfg: dict, batch: int, seq_len: int, itemsize: int = 2,
                  step_itemsize: int = 4) -> dict:
    """What the mamba layers' recurrences of ONE training step require at
    the published 64 x 64 x 128, whatever implements them: ``flops`` — 3
    passes of 2 H P N multiply-accumulates a token (the write and the read;
    chunk products, padding and recomputation count zero); ``bytes`` — x and
    y (H P) and B, C (N each, ONE group) at the compute type's size, dt and
    the log-decay a (H each) at f32's, read or written once, and their
    gradients once."""
    h, p, n_state = ssd_sizes(cfg)
    n = layers_run(cfg)["mamba"]
    tokens = batch * seq_len
    per_token = (2 * h * p + 2 * n_state) * itemsize + 2 * h * step_itemsize
    return {"flops": n * tokens * PASSES * 2 * h * p * n_state
            * FLOPS_PER_MAC,
            "bytes": n * tokens * 2 * per_token}
