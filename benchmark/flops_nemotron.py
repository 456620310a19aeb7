"""Required operations of NVIDIA-Nemotron-3-Nano-30B-A3B's training step as
ONE of 16 chips that share each layer sees it (one sub-layer a layer by the
pattern's letter: Mamba-2 with G groups of B / C behind a short convolution;
attention; a sparse layer of which this rank holds ``n_routed_experts`` of
``router_num_experts`` UNGATED experts, and a shared one; an untied head over
the vocabulary's slice), from the configuration's published sizes: the
yardstick ``mfu_required`` and the cell's per-layer shares divide by. Same
conventions as ``flops_granite`` and ``flops_glm``; checked against hand
counts in tests/.

Per token, forward multiply-accumulates. A Mamba-2 layer: the input
projection (D x (2 H P + 2 G N + H)), the output projection (H P x D) and
the recurrence's OWN work at the published 64 x 64 x 128, 2 H P N (the
rank-one write dt x B^T into every head's state and the read H C: a head
reads ONE group, so the groups change the bytes and not the operations)
whatever computes it: the chunked form's products (the C B^T grid a group,
the masked products with x), the decay of the state, the convolution, the
skip, norms and gates count zero. The attention layer: q, o (2 D H d), k, v
(2 D Hkv d) and the attention over half the square at 2 d a (query, key)
pair a query head. A sparse layer: the router (D E); the routed experts at
an EVEN split, held / E of a token's k experts, TWO products each (2 D F:
there is no gate matrix); the shared expert (2 D F_s). Once: the head (D V).
Times 2 FLOPs, times 3 passes (forward, and backward's two products).
Padding, every recomputation and the lanes a kernel wastes count as zero, so
a later kernel change cannot make the yardstick stale.
"""

from __future__ import annotations

PASSES = 3          # forward + backward's two products per matmul
FLOPS_PER_MAC = 2


def layers_run(cfg: dict) -> dict:
    """{"mamba", "sparse", "attention"}: how many layers of each kind the
    configuration RUNS (``layers_run.pattern``: M, E, *)."""
    pattern = cfg["layers_run"]["pattern"]
    assert len(pattern) == cfg["num_hidden_layers"]
    return {"mamba": pattern.count("M"), "sparse": pattern.count("E"),
            "attention": pattern.count("*")}


def ssd_sizes(cfg: dict) -> tuple:
    """(H, P, N, G): the scan's heads, a head's width, its state and the
    groups of B / C."""
    return (cfg["mamba_num_heads"], cfg["mamba_head_dim"],
            cfg["ssm_state_size"], cfg["n_groups"])


def attention_sizes(cfg: dict) -> tuple:
    """(H, Hkv, d): query heads, key-value heads and a head's width."""
    return (cfg["num_attention_heads"], cfg["num_key_value_heads"],
            cfg["head_dim"])


def required_macs_per_token(cfg: dict, seq_len: int) -> dict:
    """Forward multiply-accumulates per token, summed over layers, from the
    keys of the model's config.json (``n_routed_experts`` = held here,
    ``router_num_experts`` = what the router scores)."""
    dm = cfg["hidden_size"]
    h, p, n_state, g = ssd_sizes(cfg)
    heads, kv, d = attention_sizes(cfg)
    n, e = layers_run(cfg), cfg["router_num_experts"]
    return {
        "ssd_projections": n["mamba"] * dm
        * (3 * h * p + 2 * g * n_state + h),
        "ssd_recurrence": n["mamba"] * 2 * h * p * n_state,
        "attention_projections": n["attention"] * 2 * dm * (heads + kv) * d,
        # scores and values at d each, over half the square a token
        "attention": n["attention"] * heads * 2 * d * seq_len // 2,
        "router": n["sparse"] * dm * e,
        "experts": n["sparse"] * cfg["num_experts_per_tok"] * 2 * dm
        * cfg["moe_intermediate_size"] * cfg["n_routed_experts"] // e,
        "shared_expert": n["sparse"] * cfg["n_shared_experts"] * 2 * dm
        * cfg["moe_shared_expert_intermediate_size"],
        "head": dm * cfg["vocab_size"],
    }


def required_flops_per_token(cfg: dict, seq_len: int) -> dict:
    """Training FLOPs per token by part, and their ``total``."""
    parts = {k: v * FLOPS_PER_MAC * PASSES
             for k, v in required_macs_per_token(cfg, seq_len).items()}
    parts["total"] = sum(parts.values())
    return parts


def expert_flops_per_assignment(cfg: dict) -> int:
    """Training FLOPs of ONE token through ONE routed expert (2 D F: up and
    down, no gate; three passes)."""
    return 2 * cfg["hidden_size"] * cfg["moe_intermediate_size"] \
        * FLOPS_PER_MAC * PASSES


def flash_attention_step(cfg: dict, batch: int, seq_len: int,
                         itemsize: int = 2) -> dict:
    """What the attention layer's flash kernels of ONE training step
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
    """What the Mamba-2 layers' recurrences of ONE training step require at
    the published 64 x 64 x 128 in 8 groups, whatever implements them:
    ``flops`` — 3 passes of 2 H P N multiply-accumulates a token (the write
    and the read; chunk products, padding and recomputation count zero);
    ``bytes`` — x and y (H P) and B, C (G N each: EVERY group's keys) at the
    compute type's size, dt and the log-decay a (H each) at f32's, read or
    written once, and their gradients once."""
    h, p, n_state, g = ssd_sizes(cfg)
    n = layers_run(cfg)["mamba"]
    tokens = batch * seq_len
    per_token = (2 * h * p + 2 * g * n_state) * itemsize \
        + 2 * h * step_itemsize
    return {"flops": n * tokens * PASSES * 2 * h * p * n_state
            * FLOPS_PER_MAC,
            "bytes": n * tokens * 2 * per_token}
