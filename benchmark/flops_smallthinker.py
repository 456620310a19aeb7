"""Required operations of SmallThinker-21BA3B's training step as ONE of four
chips that share each layer sees it (28 / 4-head attention over a window or
over everything, a softmax router over ``router_num_experts``, ReGLU experts
of which this rank holds ``moe_num_primary_experts``, an untied head), from
the configuration's published sizes: the yardstick ``mfu_required`` and the
cell's per-layer shares divide by. Same conventions as ``flops_trinity``;
checked against a hand count in tests/.

Per token, forward multiply-accumulates. Every layer: the q, k, v and o
projections (D (Lq + 2 Lk) + Lq D, Lq = H d, Lk = G d: q and o are wider
than the hidden state); attention, scores and values together, over the key
positions the mask leaves — in a window layer the BAND, W (W + 1) / 2 +
(S - W) W positions a sequence, in a global layer half the square — at the
query width; the router (D E); the routed experts at an EVEN split, held / E
of a token's k experts (3 D F each) — a constant, so the metric moves 1:1
with throughput whatever the step's own routing. Once: the head (D V). Times
2 FLOPs, times 3 passes (forward, and backward's two products). The lookup,
the norms, rotary positions, softmaxes, routing, the router's two losses and
every recomputation count as zero.
"""

from __future__ import annotations

PASSES = 3          # forward + backward's two products per matmul
FLOPS_PER_MAC = 2


def widths(cfg: dict) -> tuple:
    """(Lq, Lk): the widths of q and of k / v."""
    d = cfg["head_dim"]
    return cfg["num_attention_heads"] * d, cfg["num_key_value_heads"] * d


def key_positions(seq_len: int, window=None) -> int:
    """Unmasked (query, key) pairs of one sequence: the band under a window,
    half the square without one (the convention of the other token cells)."""
    if window is None or window >= seq_len:
        return seq_len * seq_len // 2
    return window * (window + 1) // 2 + (seq_len - window) * window


def layers_run(cfg: dict) -> dict:
    """{"window", "global"}: how many layers of each kind the configuration
    RUNS (``layers_run.layout``: 1 a window layer, 0 a global one; the
    configuration's ``sliding_window_layout`` is the published list)."""
    layout = cfg["layers_run"]["layout"]
    assert len(layout) == cfg["num_hidden_layers"]
    return {"window": layout.count(1), "global": layout.count(0)}


def required_macs_per_token(cfg: dict, seq_len: int) -> dict:
    """Forward multiply-accumulates per token, summed over layers, from the
    keys of the model's config.json (``moe_num_primary_experts`` = held
    here, ``router_num_experts`` = what the router scores)."""
    dm, f = cfg["hidden_size"], cfg["moe_ffn_hidden_size"]
    lq, lk = widths(cfg)
    n = layers_run(cfg)
    layers, e = cfg["num_hidden_layers"], cfg["router_num_experts"]
    band = key_positions(seq_len, cfg["sliding_window_size"])
    return {
        "projections": layers * (dm * (lq + 2 * lk) + lq * dm),
        # 2 products (scores, values) x Lq x key positions a token
        "window_attention": n["window"] * 2 * lq * band // seq_len,
        "global_attention": n["global"] * 2 * lq
        * key_positions(seq_len) // seq_len,
        "router": layers * dm * e,
        "experts": layers * cfg["moe_num_active_primary_experts"] * 3 * dm
        * f * cfg["moe_num_primary_experts"] // e,
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
    passes): what ``st_held_moe_flops_util`` multiplies by the assignments
    the step really routed to the held experts."""
    return 3 * cfg["hidden_size"] * cfg["moe_ffn_hidden_size"] \
        * FLOPS_PER_MAC * PASSES


def flash_attention_step(cfg: dict, batch: int, seq_len: int,
                         itemsize: int = 2) -> dict:
    """What the flash-attention kernels of ONE training step require, the
    window layers' and the global layers' apart ({"window": {...},
    "global": {...}}): ``flops`` — forward's two products and backward's
    four (dV, dP, dQ, dK) over the UNMASKED positions at the query width
    (the band in a window layer); the backward's recomputed scores and
    remat's second forward count as zero. ``bytes`` — q read and o written,
    k and v read ONCE at their own width by the forward; q, o, do, k, v read
    and dq, dk, dv written by the backward (k and v repeated to the query
    heads, as the program's arm does, is not required)."""
    lq, lk = widths(cfg)
    n = layers_run(cfg)
    wide = batch * seq_len * lq * itemsize
    narrow = batch * seq_len * lk * itemsize
    out = {}
    for kind, window in (("window", cfg["sliding_window_size"]),
                         ("global", None)):
        one_product = batch * key_positions(seq_len, window) * lq \
            * FLOPS_PER_MAC
        out[kind] = {"flops": n[kind] * 6 * one_product,
                     "bytes": n[kind] * ((2 + 4) * wide + (2 + 4) * narrow)}
    return out
