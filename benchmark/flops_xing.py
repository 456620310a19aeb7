"""Required operations of Xing4.0-29B-A4B's training step as ONE of 8 chips
that share each layer sees it (a residual STREAM of ``hc_mult`` hidden
states with a mapping, a read and a write a sub-layer; latent attention in
every block; a leading dense layer, sparse layers of which this rank holds
``n_routed_experts`` of ``router_num_experts``, a shared expert; an untied
head), from the configuration's published sizes: the yardstick
``mfu_required`` and the cell's per-layer shares divide by. Same conventions
as ``flops_glm``; checked against hand counts in tests/.

Per token, forward multiply-accumulates. A block's latent attention, dense
layer, router, experts at an EVEN split, shared expert and the head as
``flops_glm`` counts them. The stream, a sub-layer (two a block): the
mapping's projection (n C x n (n + 2)), the read (n C) and the write
(n (n + 1) C: the mix and the output's share). Times 2 FLOPs, times 3 passes
(forward, and backward's two products). The statistic, the sigmoids, the
Sinkhorn iterations, the rotation, norms, gates and every recomputation
count as zero.
"""

from __future__ import annotations

PASSES = 3          # forward + backward's two products per matmul
FLOPS_PER_MAC = 2
SUBLAYERS = 2       # a block's: latent attention, the FFN


def layers_run(cfg: dict) -> dict:
    """{"dense", "moe", "mtp", "blocks", "sparse"}: how many layers of each
    kind the configuration RUNS (``layers_run``)."""
    run = cfg["layers_run"]
    assert cfg["num_hidden_layers"] == run["dense"] + run["moe"]
    assert cfg["num_nextn_predict_layers"] == run["mtp"]
    return {**{k: run[k] for k in ("dense", "moe", "mtp")},
            "blocks": run["dense"] + run["moe"] + run["mtp"],
            "sparse": run["moe"] + run["mtp"]}


def required_macs_per_token(cfg: dict, seq_len: int) -> dict:
    """Forward multiply-accumulates per token, summed over layers, from the
    keys of the model's config.json (``n_routed_experts`` = held here,
    ``router_num_experts`` = what the router scores)."""
    dm, f = cfg["hidden_size"], cfg["moe_intermediate_size"]
    n = layers_run(cfg)
    heads, streams = cfg["num_attention_heads"], cfg["hc_mult"]
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
        # the stream: projection + read + write, a sub-layer
        "hc": n["blocks"] * SUBLAYERS * streams * dm
        * (streams * (streams + 2) + 1 + streams + 1),
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
    """What the blocks' flash kernels of ONE training step require, as
    ``flops_glm.flash_attention_step`` counts it: forward's two products and
    backward's four over half the square; q read and o written, k and v
    read ONCE by the forward; q, o, do, k, v read and dq, dk, dv written by
    the backward; a key at its own width, H nope + rope."""
    heads = cfg["num_attention_heads"]
    nope, rope, v = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                     cfg["v_head_dim"])
    n = layers_run(cfg)["blocks"]
    pairs = batch * seq_len * seq_len // 2
    tokens = batch * seq_len * itemsize
    q_w, k_w, v_w = heads * (nope + rope), heads * nope + rope, heads * v
    return {"flops": n * pairs * heads * 3 * (nope + rope + v)
            * FLOPS_PER_MAC,
            "bytes": n * tokens * (3 * q_w + 3 * k_w + 6 * v_w)}


def hc_stream_step(cfg: dict, batch: int, seq_len: int,
                   itemsize: int = 2) -> dict:
    """What the stream's passes of ONE training step MUST move and compute,
    whatever implements them. ``bytes``, a sub-layer forward: two reads and
    one write of the stream (T, n, C) — one read for the mapping and the
    read, which one pass over a token's row can share; one read and one
    write for X -> X' — and one write and one read of (T, C) (h out, y in),
    in the stream's dtype; remat's replay the same; the backward twice that
    (it reads what the forward read and its cotangents, and writes the
    cotangents of what the forward read): four forwards' worth a sub-layer.
    ``flops``: the required count above (projection, read, write; three
    passes)."""
    streams, dm = cfg["hc_mult"], cfg["hidden_size"]
    n = layers_run(cfg)["blocks"] * SUBLAYERS
    tokens = batch * seq_len
    forward = (3 * streams * dm + 2 * dm) * tokens * itemsize
    macs = streams * dm * (streams * (streams + 2) + 1 + streams + 1)
    return {"flops": n * tokens * macs * FLOPS_PER_MAC * PASSES,
            "bytes": n * 4 * forward}
