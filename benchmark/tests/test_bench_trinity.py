"""The Trinity cell's yardstick: ``flops_trinity`` against hand counts, the
configuration against the catalog row and its copies, the traffic file, each
of the cell's readers on a hand-made ``layers`` dict (and on a
program without what it reads), the plain reference's router, window and
shares against NumPy, the runner's ``step_check`` on right and wrong steps,
its refusal of a program from before the model, and the ``--cpu-tiny``
rehearsal of ``trinity.e16of128.pack8k`` end to end."""

import json
import os
import types

import numpy as np
import pytest

import flops_trinity
import tokengen
from conftest import BENCH_DIR, ROOT
from layer_metrics import (attention_gate_ms_per_step,
                           global_attention_ms_per_step,
                           global_flash_attention_roofline,
                           shared_expert_ms_per_step,
                           router_ms_per_step,
                           attention_glue_ms_per_step,
                           head_ms_per_step,
                           held_assignment_share,
                           held_dropped_assignments,
                           held_load_max_over_mean,
                           held_moe_flops_util,
                           held_moe_ms_per_step,
                           held_share_layer_max,
                           recompute_ms_per_step,
                           tokens_per_s_per_chip,
                           window_attention_ms_per_step,
                           window_flash_attention_roofline,
                           window_visited_over_live_programs)
from test_bench_run import BENCH, declared, run_cell

CELL = "trinity.e16of128.pack8k"
with open(os.path.join(BENCH_DIR, "configs", "trinity_mini.json")) as f:
    CFG = json.load(f)
with open(os.path.join(BENCH_DIR, "cells", CELL + ".json")) as f:
    OWN = json.load(f)
DEPTH, BATCH = CFG["num_hidden_layers"], OWN["batch_per_chip"]
V, S, W = 200192 // 8, 8192, 2048

# config.json of arcee-ai/Trinity-Mini as the model-configs catalog
# (architectures.jsonl) holds it
CATALOG = {
    "global_attn_every_n_layers": 4, "head_dim": 128, "hidden_act": "silu",
    "hidden_size": 2048, "intermediate_size": 6144,
    "layer_types": (["sliding_attention"] * 3 + ["full_attention"]) * 8,
    "load_balance_coeff": 0.001, "max_position_embeddings": 131072,
    "model_type": "afmoe", "moe_intermediate_size": 1024,
    "mup_enabled": True, "n_group": 1, "num_attention_heads": 32,
    "num_dense_layers": 2, "num_expert_groups": 1, "num_experts": 128,
    "num_experts_per_tok": 8, "num_hidden_layers": 32,
    "num_key_value_heads": 4, "num_limited_groups": 1,
    "num_shared_experts": 1, "rms_norm_eps": 1e-05, "rope_scaling": None,
    "rope_theta": 10000, "route_norm": True, "route_scale": 2.826,
    "score_func": "sigmoid", "sliding_window": 2048,
    "tie_word_embeddings": False, "topk_group": 1, "use_grouped_mm": True,
    "vocab_size": 200192}
REDUCED = {"num_hidden_layers": 32, "num_experts": 128, "vocab_size": 200192}


@pytest.mark.parametrize("key", sorted(CATALOG))
def test_configuration_equals_the_catalog_row(key):
    """Every key as published; the depth, the experts HELD and the rows of
    the vocabulary, and only those, are reduced, and no width among them."""
    if key in REDUCED:
        assert sorted(CFG["reduced"]) == sorted(REDUCED)
        assert CFG["published"][key] == CATALOG[key] == REDUCED[key]
        assert CFG[key] < CATALOG[key]
    else:
        assert CFG[key] == CATALOG[key]


def test_the_cut_is_the_issue_s():
    assert (DEPTH, CFG["num_experts"], CFG["router_num_experts"],
            CFG["vocab_size"]) == (5, 16, 128, V) and V == 25024
    run = CFG["layers_run"]
    assert (run["dense"], run["moe"]) == (1, 4) and run["layer_types"] == \
        ["sliding_attention"] * 4 + ["full_attention"]
    for section in ("assumed", "departures", "deployment", "reduced_how",
                    "what_the_cut_changes"):
        assert CFG[section]
    for key in ("a_block", "b_qk_norm", "c_positions", "d_gate", "e_router",
                "f_shared_expert", "g_balancing", "h_mup", "optimizer",
                "initialisation", "packing", "source"):
        assert CFG["assumed"][key], key
    assert "8 chips share each layer" in CFG["deployment"]
    assert sorted(CFG["reduced_how"]) == sorted(REDUCED)


def test_configuration_arithmetic():
    """The sizes the configuration file and ISSUE 36 argue from."""
    d, f, i, e = 2048, 1024, 6144, 128
    attention = d * (4096 + 512 + 512 + 4096) + 4096 * d
    gains = 4 * d + 2 * 128
    expert = 3 * d * f
    moe_layer = attention + gains + 16 * expert + expert + e * d + e
    dense_layer = attention + gains + 3 * d * i
    assert attention == 27_262_976 and expert == 6_291_456
    assert round(dense_layer / 1e6, 1) == 65.0
    assert round(moe_layer / 1e6, 1) == 134.5
    total = 2 * V * d + d + dense_layer + 4 * moe_layer
    assert total == 705_474_304
    assert f"{total:,} parameters" in CFG["reduced_how"]["num_hidden_layers"]
    assert round(16 * total / 1e9, 1) == 11.3
    assert round(12 * total / 1e9, 2) == 8.47        # the step's arguments
    two_periods = total + 4 * moe_layer
    assert round(two_periods / 1e9, 2) == 1.24 \
        and round(12 * two_periods / 1e9, 1) == 14.9


BAND = W * (W + 1) // 2 + (S - W) * W


@pytest.mark.parametrize("part,macs", [
    ("projections", 5 * (2048 * (4096 + 1024) + 4096 * 2048)),
    ("gate", 5 * 2048 * 4096),
    ("window_attention", 4 * 2 * 4096 * BAND // S),
    ("global_attention", 4096 * S),
    ("dense_ffn", 3 * 2048 * 6144),
    ("router", 4 * 2048 * 128),
    ("experts", 4 * 8 * 6_291_456 * 16 // 128),
    ("shared_expert", 4 * 6_291_456),
    ("head", 2048 * V)])
def test_required_macs_against_hand_counts(part, macs):
    assert flops_trinity.required_macs_per_token(CFG, S)[part] == macs


def test_required_flops_and_shares():
    """ISSUE 36's hand count: 738 MFLOP forward, 2.2 GFLOP a token;
    attention (projections, gate, kernels) about 62%, the head 14%, the
    held routed experts 7%."""
    assert BAND == 14_681_088 and flops_trinity.key_positions(S) == S * S // 2
    assert flops_trinity.key_positions(S, S) == S * S // 2
    macs = flops_trinity.required_macs_per_token(CFG, S)
    assert round(2 * sum(macs.values()) / 1e6) == 738
    one = flops_trinity.required_flops_per_token(CFG, S)
    assert round(one["total"] / 1e9, 1) == 2.2
    share = lambda *parts: round(  # noqa: E731
        100 * sum(one[p] for p in parts) / one["total"])
    assert share("projections", "gate", "window_attention",
                 "global_attention") == 62
    assert share("head") == 14 and share("experts") == 7
    assert share("shared_expert") == 7 and share("dense_ffn") == 10
    # the attention parts ARE what the flash kernels are asked for
    flash = flops_trinity.flash_attention_step(CFG, BATCH, S)
    assert flash["window"]["flops"] == 4 * 6 * BATCH * BAND * 4096 * 2
    assert abs(flash["window"]["flops"]
               - one["window_attention"] * S * BATCH) < 1e-6 * one["total"]
    assert flash["global"]["flops"] == one["global_attention"] * S * BATCH
    assert flash["global"]["bytes"] == BATCH * S * 2 * (6 * 4096 + 6 * 512)
    assert flash["window"]["bytes"] == 4 * flash["global"]["bytes"]
    # the band is 44% of the triangle: counted over the triangle the window
    # kernels' share of their roofline would read 2.3 times too high
    assert round(100 * BAND / (S * S // 2)) == 44
    assert flops_trinity.expert_flops_per_assignment(CFG) == 6 * 6_291_456


def test_copies_match_their_originals():
    for copy, original in CFG["copied_from"].items():
        with open(os.path.join(BENCH_DIR, copy)) as a, \
                open(os.path.join(ROOT, original)) as b:
            assert a.read() == b.read(), (copy, original)
    with open(os.path.join(BENCH_DIR, CFG["net"])) as f:
        net = f.read()
    assert net.count("type: ATTENTION") == DEPTH
    assert net.count("type: MOE\n") == net.count("type: MOE_ROUTER") == 4
    assert net.count("window: 2048") == 4 and net.count("rope: false") == 1
    assert net.count("num_held: 16") == 4 \
        and net.count("num_experts: 128") == 8 and net.count("top_k: 8") == 8
    assert net.count("num_kv_heads: 4") == DEPTH
    assert net.count("route_scale: 2.826") == 8
    # per-head norms: one gain of 128 for q's 32 heads, one for k's 4
    assert net.count("    num_heads: 32\n  }\n") >= DEPTH


def test_traffic_is_packed8k_over_an_eighth():
    with open(os.path.join(BENCH_DIR, "traffic", "packed8k_ep8.json")) as f:
        traffic = json.load(f)
    with open(os.path.join(BENCH_DIR, "traffic", "packed8k_slice.json")) as f:
        sibling = json.load(f)
    mix = traffic["documents"]
    assert (traffic["seq_len"], traffic["steps_in_file"], traffic["display"],
            traffic["runner"], traffic["precision"]) == \
        (8192, 8, 4, "trinity_train", "bf16")
    # the warm-up, the window and the flags are packed8k_slice's
    for key in ("argv", "display", "settle_displays", "trace_steps",
                "seq_len", "steps_in_file", "feed", "window"):
        assert traffic[key] == sibling[key], key
    assert (mix["doc_len_median"], mix["doc_len_sigma"], mix["doc_len_min"],
            mix["doc_len_max"], mix["zipf_exponent"],
            mix["end_of_text_id"]) == (512, 1.2, 16, 8192, 1.0, 0)
    big = 3_000_000_019                      # over 2**31, as the driver's
    a = tokengen.packed_sequences(big, 2, 8192, V, mix)
    flat, nxt = a["data"].reshape(-1), a["label"].reshape(-1)
    assert np.array_equal(flat[1:], nxt[:-1])           # packed end to end
    assert 0 <= flat.min() and flat.max() < V           # ids over the slice
    with open(os.path.join(ROOT, "examples", "lm",
                           "trinity_mini_solver.prototxt")) as f:
        header = f.read()
    flag = next(a for a in traffic["argv"] if a.startswith("--remat="))
    assert "--remat '" + flag[len("--remat="):] + "'" in header


def test_the_window_sits_where_the_schedule_puts_it():
    """Where the window sits in the routers' history is where the cell's
    spread comes from (PERF.md, PR 39): the solver's own fields and the
    traffic's warm-up give every step's rate. The window opens 24 steps
    into the solver's linear rise and closes inside it, so it trains at a
    quarter of the peak rate or more and never at the peak. An edit of
    either file that moves the window fails here, and has its own series to
    bring."""
    import importlib
    import re
    with open(os.path.join(BENCH_DIR, CFG["solver"])) as f:
        text = f.read()
    assert 'lr_policy: "cosine"' in text
    base, warm, total, floor = (
        float(re.search(rf"(?m)^{key}: (\S+)$", text)[1])
        for key in ("base_lr", "stepsize", "max_iter", "gamma"))
    with open(os.path.join(BENCH_DIR, "traffic", "packed8k_ep8.json")) as f:
        traffic = json.load(f)
    # Engine.train to 1, to `display`, `settle_displays` displays, one more
    opens = (2 + traffic["settle_displays"]) * traffic["display"]
    # a step of the cell takes 0.5 s or more: 2 x --seconds steps at most
    closes = opens + 2 * BENCH["run_seconds"]
    cosine_lr = importlib.import_module("reference.trinity").cosine_lr
    rates = [cosine_lr(it, base, warm, total, floor) for it in range(closes)]
    assert opens == 24 and closes <= warm
    assert rates[opens] == pytest.approx(1.0e-4)
    assert 0.25 * base <= min(rates[opens:]) and max(rates[opens:]) < base
    # what Adam has moved a router's weight by when the window opens
    assert sum(rates[:opens]) == pytest.approx(1.2e-3)


# --------------------------------------------------------------------------- #
# the cell's readers on a hand-made run
# --------------------------------------------------------------------------- #
#   two steps; times in ns
OPS = [("fusion q.1 bf16[8]", 0.0, 10.0),              # l0_q fwd
       ("pallas-call flashw.2 bf16[8]", 10.0, 8.0),    # l0_attn_window bwd
       ("pallas-call flashg.3 bf16[8]", 20.0, 20.0),   # l4_attn_global bwd
       ("fusion rope.4 bf16[8]", 40.0, 4.0),           # l0_attn_window fwd
       ("fusion moe.5 bf16[8]", 50.0, 30.0),           # l1_moe bwd
       ("fusion router.6 f32[8]", 80.0, 8.0),          # l1_router fwd
       ("fusion head.7 bf16[8]", 90.0, 12.0),          # lm_head bwd
       ("fusion nll.8 f32[8]", 102.0, 2.0),            # lm_nll fwd
       ("fusion gate.9 bf16[8]", 104.0, 6.0),          # l0_gate_mul fwd
       ("fusion g.10 bf16[8]", 110.0, 2.0),            # l0_g bwd
       ("fusion shared.11 bf16[8]", 112.0, 14.0),      # l1_shared_up bwd
       ("fusion merge.12 bf16[8]", 126.0, 2.0)]        # l4_attn_global fwd
SCOPES = {"ops": {"q.1": "l0_q|fwd", "flashw.2": "l0_attn_window|bwd",
                  "flashg.3": "l4_attn_global|bwd",
                  "rope.4": "l0_attn_window|fwd", "moe.5": "l1_moe|bwd",
                  "router.6": "l1_router|fwd", "head.7": "lm_head|bwd",
                  "nll.8": "lm_nll|fwd", "gate.9": "l0_gate_mul|fwd",
                  "g.10": "l0_g|bwd", "shared.11": "l1_shared_up|bwd",
                  "merge.12": "l4_attn_global|fwd"},
          "recomputed": ["moe.5", "rope.4"],
          "types": {"l0_q": "INNER_PRODUCT", "l0_attn_window": "ATTENTION",
                    "l4_attn_global": "ATTENTION", "l1_moe": "MOE",
                    "l1_router": "MOE_ROUTER", "lm_head": "INNER_PRODUCT",
                    "lm_nll": "SOFTMAX_NLL", "l0_gate_mul": "ELTWISE",
                    "l0_g": "INNER_PRODUCT",
                    "l1_shared_up": "INNER_PRODUCT"}}
PEAKS = {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e11}
ROUTES = [
    "attention=pallas_flash (fwd 1024x1024 21/24, dq 1024x1024 21/24, dkv "
    "1024x1024 21/24; block_q x block_k, live/visited programs a head; "
    "window 2048: the band's grid); 4 kv heads repeated x8",
    "attention=pallas_flash (fwd 1024x1024 36/64, dq 1024x1024 36/64, dkv "
    "1024x1024 36/64; block_q x block_k, live/visited programs a head); 4 "
    "kv heads repeated x8; no positions", "grouped_matmul=ragged_dot"]


def small_run(scopes=SCOPES, lm=True):
    run = {"trace": {"steps": 2, "spans": [], "async": {},
                     "devices": {"0": OPS}},
           "steps": 10, "batch_per_chip": 2, "window_s": 4.0,
           "peak_flops_per_s": PEAKS["bf16_flops_per_s"],
           "stats": {"sections": {"step_scopes": scopes} if scopes else {}}}
    if lm:
        run["lm"] = {"seq_len": 8192,
                     "scopes": CFG["scopes"], "peaks": PEAKS,
                     "kernel_routes": ROUTES,
                     "flash_per_step": {
                         "window": {"flops": 2e3, "bytes": 100.0},
                         "global": {"flops": 1e3, "bytes": 500.0}},
                     "flops_per_assignment": 10.0,
                     "assignments_per_step": 1000,
                     "held_share": [0.1, 0.125, 0.15],
                     "held_share_by_layer": {
                         "l1_held_share": [0.12, 0.2, 0.23],
                         "l2_held_share": [0.08, 0.05, 0.07]},
                     "traced_held_share": [0.25],
                     "expert_load": [1.2, 1.4], "dropped": [0.0, 0.0]}
    return run


READERS = [
    (window_attention_ms_per_step, 6e-6),          # (8 + 4) ns / 2
    (global_attention_ms_per_step, 11e-6),         # (20 + 2) / 2
    # flops-bound: 2e3 / 1e12 = 2 ns against 4 ns of kernel a step
    (window_flash_attention_roofline, 100 * 2e-9 / 4e-9),
    # bytes-bound: 500 / 1e11 = 5 ns against 10 ns of kernel a step
    (global_flash_attention_roofline, 100 * 5e-9 / 10e-9),
    (window_visited_over_live_programs, 72 / 63),
    (attention_glue_ms_per_step, 3e-6),    # (4 + 2) / 2
    (attention_gate_ms_per_step, 4e-6),            # (6 + 2) / 2
    (router_ms_per_step, 4e-6),
    (shared_expert_ms_per_step, 7e-6),
    (held_moe_ms_per_step, 15e-6),
    # the TRACED steps' 0.25 x 1000 assignments x 10 FLOPs over 15 ns x 1e12
    (held_moe_flops_util, 100 * 2.5e3 / (15e-9 * 1e12)),
    (held_assignment_share, 12.5),
    (held_load_max_over_mean, 1.3),
    (held_dropped_assignments, 0.0),
    (held_share_layer_max, 23.0),          # l1's third display
    (head_ms_per_step, 7e-6),              # (12 + 2) / 2
    (recompute_ms_per_step, 17e-6),        # (30 + 4) ns / 2
    (tokens_per_s_per_chip, 10 * 2 * 8192 / 4.0),
]


@pytest.mark.parametrize("reader, want", READERS)
def test_each_reader_on_a_hand_made_run(reader, want):
    assert reader.reduce(small_run()) == pytest.approx(want)


@pytest.mark.parametrize("reader", [r for r, _ in READERS])
def test_each_reader_finds_nothing_on_a_program_without_it(reader):
    """A program or a run without what the reader reads: no map, no ``lm``
    section, no trace — None, and nothing raised. (Which CELLS report a
    metric is its ``workloads`` list's to say, not the reader's: no reader
    looks for a cell's name.)"""
    assert reader.reduce(small_run(scopes=None, lm=False)) is None
    if reader is not recompute_ms_per_step:   # reads the map alone
        assert reader.reduce(small_run(lm=False)) is None
    counters = (held_assignment_share, tokens_per_s_per_chip,
                held_load_max_over_mean,
                held_dropped_assignments,
                held_share_layer_max,
                window_visited_over_live_programs)
    if reader not in counters:                # those need no trace
        assert reader.reduce(dict(small_run(), trace=None)) is None


def test_visited_over_live_reads_the_dense_arm_as_nothing():
    run = small_run()
    run["lm"]["kernel_routes"] = [
        "attention=dense; 4 kv heads repeated x8; window 16 as a dense mask"]
    assert window_visited_over_live_programs.reduce(run) is None


# --------------------------------------------------------------------------- #
# the plain reference against NumPy, piece by piece
# --------------------------------------------------------------------------- #

def tiny_weights(seed=0, d=16, h=4, g=2, dh=4, e=8, f=12, i=20, v=32,
                 held=range(8), kinds=("sliding_attention",
                                       "sliding_attention",
                                       "full_attention")):
    import jax
    keys = iter(jax.random.split(jax.random.PRNGKey(seed), 200))
    mat = lambda *shape: 0.5 * jax.random.normal(next(keys), shape)  # noqa
    gain = lambda n: [1.0 + 0.1 * jax.random.normal(next(keys), (n,))]  # noqa
    w = {"embed": [mat(v, d) / 4], "final_norm": gain(d),
         "lm_head": [mat(v, d)]}
    n = len(list(held))
    for at in range(len(kinds)):
        l = f"l{at}_"
        for name in ("attn_norm", "attn_out_norm", "ffn_norm",
                     "ffn_out_norm"):
            w[l + name] = gain(d)
        w[l + "q"], w[l + "g"] = [mat(h * dh, d)], [mat(h * dh, d)]
        w[l + "k"], w[l + "v"] = [mat(g * dh, d)], [mat(g * dh, d)]
        w[l + "q_norm"], w[l + "k_norm"] = gain(dh), gain(dh)
        w[l + "o"] = [mat(d, h * dh)]
        if at == 0:
            w[l + "ffn_gate"], w[l + "ffn_up"] = [mat(i, d)], [mat(i, d)]
            w[l + "ffn_down"] = [mat(d, i)]
        else:
            w[l + "router"] = [mat(e, d), 0.05 * mat(e)]
            w[l + "moe"] = [mat(n, f, d), mat(n, f, d), mat(n, d, f)]
            w[l + "shared_gate"], w[l + "shared_up"] = [mat(f, d)], \
                [mat(f, d)]
            w[l + "shared_down"] = [mat(d, f)]
    cfg = {"num_hidden_layers": len(kinds), "num_dense_layers": 1,
           "layer_types": list(kinds), "num_attention_heads": h,
           "num_key_value_heads": g, "num_experts": e,
           "num_experts_per_tok": 3, "route_scale": 2.826,
           "sliding_window": 5, "rms_norm_eps": 1e-5, "rope_theta": 1e4}
    return cfg, w


def test_reference_router_and_experts_against_a_numpy_loop():
    """The reference's MoE sublayer token by token in NumPy float64: sigmoid
    scores, the top-3 of score + bias, weights from the UNBIASED scores
    over their sum times the scale, the shared expert unweighted."""
    import reference.trinity as ref
    cfg, w = tiny_weights()
    f64 = lambda a: np.asarray(a, np.float64)  # noqa: E731
    tokens = np.arange(6)[None]
    got = ref.forward(cfg, w, tokens)
    # layer 1's input u, from the reference's own pieces up to it
    one = ref.forward({**cfg, "num_hidden_layers": 1,
                       "layer_types": cfg["layer_types"][:1]}, w, tokens)
    assert "counts" not in one                 # a dense layer routes nothing
    w_r, bias = (f64(a) for a in w["l1_router"])
    gate, up, dn = (f64(a) for a in w["l1_moe"])
    silu = lambda a: a / (1 + np.exp(-a))      # noqa: E731
    # recover u from the routed part's definition: run layer 1's front here
    import jax
    import jax.numpy as jnp
    with jax.default_matmul_precision("highest"):
        x = jnp.asarray(w["embed"][0])[tokens] * 4.0
        pre = {k[3:]: [jnp.asarray(b) for b in v] for k, v in w.items()
               if k.startswith("l0_")}
        a = ref.rms_norm(x, pre["attn_norm"][0], 1e-5)[0]
        s = a.shape[0]
        q = ref.rope(ref.rms_norm((a @ pre["q"][0].T).reshape(s, 4, 4),
                                  pre["q_norm"][0], 1e-5), 1e4)
        k = ref.rope(ref.rms_norm((a @ pre["k"][0].T).reshape(s, 2, 4),
                                  pre["k_norm"][0], 1e-5), 1e4)
        o = ref.attention(q, k, (a @ pre["v"][0].T).reshape(s, 2, 4), 2, 5)
        att = (o * jax.nn.sigmoid(a @ pre["g"][0].T)) @ pre["o"][0].T
        h = x[0] + ref.rms_norm(att, pre["attn_out_norm"][0], 1e-5)
        u0 = ref.rms_norm(h, pre["ffn_norm"][0], 1e-5)
        f = (jax.nn.silu(u0 @ pre["ffn_gate"][0].T)
             * (u0 @ pre["ffn_up"][0].T)) @ pre["ffn_down"][0].T
        x1 = h + ref.rms_norm(f, pre["ffn_out_norm"][0], 1e-5)
    # ... then layer 1 in NumPy, float64
    l1 = {k[3:]: [f64(b) for b in v] for k, v in w.items()
          if k.startswith("l1_")}
    rms = lambda t, g_: t / np.sqrt((t * t).mean(-1, keepdims=True)  # noqa
                                    + 1e-5) * g_
    x1 = f64(x1)
    a1 = rms(x1, l1["attn_norm"][0])
    q1 = rms((a1 @ l1["q"][0].T).reshape(6, 4, 4), l1["q_norm"][0])
    k1 = rms((a1 @ l1["k"][0].T).reshape(6, 2, 4), l1["k_norm"][0])
    q1, k1 = f64(ref.rope(jnp.asarray(q1, jnp.float32), 1e4)), \
        f64(ref.rope(jnp.asarray(k1, jnp.float32), 1e4))
    v1 = (a1 @ l1["v"][0].T).reshape(6, 2, 4)
    o1 = np.zeros((6, 4, 4))
    for t in range(6):
        for head in range(4):
            lo = max(0, t - 5 + 1)                 # the window: 5 keys
            sc = k1[lo:t + 1, head // 2] @ q1[t, head] / 2.0
            p = np.exp(sc - sc.max())
            o1[t, head] = (p / p.sum()) @ v1[lo:t + 1, head // 2]
    att1 = (o1.reshape(6, 16) / (1 + np.exp(-(a1 @ l1["g"][0].T)))) \
        @ l1["o"][0].T
    h1 = x1 + rms(att1, l1["attn_out_norm"][0])
    u = rms(h1, l1["ffn_norm"][0])
    score = 1 / (1 + np.exp(-(u @ w_r.T)))
    chosen = np.argsort(-(score + bias), -1, kind="stable")[:, :3]
    routed = np.zeros((6, 16))
    for t in range(6):
        total = score[t, chosen[t]].sum()
        for e in chosen[t]:
            routed[t] += 2.826 * score[t, e] / total * (
                dn[e] @ (silu(gate[e] @ u[t]) * (up[e] @ u[t])))
    shared = (silu(u @ l1["shared_gate"][0].T)
              * (u @ l1["shared_up"][0].T)) @ l1["shared_down"][0].T
    np.testing.assert_array_equal(
        np.sort(np.asarray(got["choice"])[0, 0], -1), np.sort(chosen, -1))
    np.testing.assert_allclose(np.asarray(got["routed"])[0, 0], routed,
                               rtol=5e-4, atol=1e-5)
    np.testing.assert_allclose(np.asarray(got["shared"])[0, 0], shared,
                               rtol=5e-4, atol=1e-5)
    np.testing.assert_array_equal(
        np.asarray(got["counts"])[0],
        np.bincount(chosen.reshape(-1), minlength=8))


def test_reference_shares_add_up_and_a_handed_choice_counts_flips():
    """The routed parts of disjoint shares sum to the whole layer's; the
    shared expert is in every share, to be counted once; a handed-over
    choice that differs in one assignment is one flip."""
    import jax
    import reference.trinity as ref
    cfg, w = tiny_weights()
    tokens = jax.random.randint(jax.random.PRNGKey(4), (2, 16), 0, 32)
    whole = ref.forward(cfg, w, tokens)
    cut = lambda lo, hi: {k: ([a[lo:hi] for a in v]  # noqa: E731
                              if k.endswith("_moe") else v)
                          for k, v in w.items()}
    two = {**cfg, "num_hidden_layers": 2,
           "layer_types": cfg["layer_types"][:2]}   # layer 1: the same input
    parts = [ref.forward(two, cut(lo, lo + 2), tokens,
                         held=range(lo, lo + 2)) for lo in range(0, 8, 2)]
    np.testing.assert_allclose(
        sum(np.asarray(p["routed"][0]) for p in parts),
        np.asarray(whole["routed"][0]), rtol=1e-5, atol=1e-6)
    for p in parts:
        np.testing.assert_array_equal(np.asarray(p["shared"][0]),
                                      np.asarray(whole["shared"][0]))
    assert not np.any(np.asarray(whole["route_flips"]))
    choice = np.array(whole["choice"])              # (2 MoE layers, N, S, k)
    own = set(choice[1, 0, 3])
    choice[1, 0, 3, 0] = next(e for e in range(8) if e not in own)
    moved = ref.forward(cfg, w, tokens, choice=choice)
    assert list(np.asarray(moved["route_flips"])) == [0, 1]
    assert np.any(np.asarray(moved["routed"][1])[0, 3]
                  != np.asarray(whole["routed"][1])[0, 3])
    np.testing.assert_array_equal(np.asarray(moved["routed"][1])[1],
                                  np.asarray(whole["routed"][1])[1])


def test_reference_balancing_rule_counts_assignments():
    import reference.trinity as ref
    bias = np.array([0.0, 0.002, -0.001, 0.0], np.float32)
    got = ref.next_bias(bias, [20, 0, 10, 10], 0.001)   # mean 10
    np.testing.assert_allclose(got, [-0.001, 0.003, -0.001, 0.0], atol=1e-9)


def _tiny_step(seed=0):
    import jax
    cfg, w = tiny_weights(seed)
    tokens = jax.random.randint(jax.random.PRNGKey(4), (2, 16), 0, 32)
    targets = jax.random.randint(jax.random.PRNGKey(5), (2, 16), 0, 32)
    opt = {"rate": {n: [1e-3 * (1 + j) for j in range(len(b))]
                    for n, b in w.items()},
           "decay": {n: [0.1 if np.ndim(a) > 1 else 0.0 for a in b]
                     for n, b in w.items()},
           "clip": 0.5, "b1": 0.9, "b2": 0.95, "eps": 1e-8,
           "bias_rate": 0.001}
    return cfg, w, tokens, targets, opt


def test_reference_train_step_is_grad_clip_adamw_and_the_sign_rule():
    import jax
    import reference.trinity as ref
    cfg, w, tokens, targets, opt = _tiny_step()
    got = jax.device_get(ref.train_step(cfg, w, tokens, targets, opt))
    (total, out), grads = jax.value_and_grad(
        lambda some: ref.loss(cfg, some, tokens, targets), has_aux=True)(w)
    grads = jax.device_get(grads)
    assert float(got["loss"]) == pytest.approx(float(total), rel=1e-6)
    routers = ref.router_names(w)
    assert routers == ["l1_router", "l2_router"]
    for n in routers:                   # the bias takes no gradient
        assert not np.any(grads[n][-1])
    norm = np.sqrt(sum(float(np.sum(np.square(g, dtype=np.float64)))
                       for b in grads.values() for g in b))
    assert norm > opt["clip"]
    assert float(got["grad_norm"]) == pytest.approx(norm, rel=1e-5)
    for n, blobs in w.items():
        for j, a in enumerate(blobs[:-1] if n in routers else blobs):
            g = grads[n][j].astype(np.float64) * opt["clip"] / norm
            want = -opt["rate"][n][j] * (
                g / (np.abs(g) + 1e-8)
                + opt["decay"][n][j] * np.asarray(a, np.float64))
            np.testing.assert_allclose(got["change"][n][j], want,
                                       rtol=2e-4, atol=2e-7, err_msg=n)
    counts = np.asarray(out["counts"])
    assert counts.sum(1).tolist() == [2 * 16 * 3] * 2   # assignments
    for at, n in enumerate(routers):
        np.testing.assert_allclose(
            got["change"][n][-1],
            0.001 * np.sign(counts[at].sum() / 8 - counts[at]), atol=1e-8)
    again = jax.device_get(ref.train_step(
        cfg, w, tokens, targets, opt, remat=True, q_block=4,
        round_to=jax.numpy.float8_e4m3fn, round_when=False))
    for a, b in zip(jax.tree.leaves(again["change"]),
                    jax.tree.leaves(got["change"])):
        # (an element whose gradient is of AdamW's eps steps by its size)
        np.testing.assert_allclose(a, b, rtol=1e-4, atol=2e-5)
    low = jax.device_get(ref.train_step(
        cfg, w, tokens, targets, opt, remat=True, q_block=4,
        round_to=jax.numpy.float8_e4m3fn, round_when=True))
    assert abs(float(low["loss"]) - float(total)) > 1e-4 * float(total)


@pytest.mark.parametrize("fault, limit", [
    (None, None), ("bias_still", "bias_wrong"), ("half_a_leaf", "norm"),
    ("other_loss", "loss"), ("wrong_way", "cosine")])
def test_step_check_tells_a_wrong_step(fault, limit):
    """``step_check`` on a step that IS the reference's passes; one whose
    selection biases never moved, whose expert stack moved half as far or
    the wrong way, or whose loss is another batch's does not."""
    import jax
    import reference.trinity as ref
    from runners import trinity_train
    cfg, w, tokens, targets, opt = _tiny_step()
    w = jax.device_get(w)
    took = jax.device_get(ref.train_step(cfg, w, tokens, targets, opt))
    change = {n: [np.array(a) for a in b] for n, b in took["change"].items()}
    loss = float(took["loss"])
    if fault == "bias_still":
        for n in change:
            if n.endswith("_router"):
                change[n][-1] *= 0
    elif fault == "half_a_leaf":
        change["l2_moe"][0] *= 0.5
    elif fault == "wrong_way":
        change["l2_moe"][2] *= -1
    elif fault == "other_loss":
        loss *= 1.01
    job = {"config": dict(CFG, reference_positions=4), "tiny": True,
           "traffic": {"precision": "f32"}}
    model = {"num_hidden_layers": 3, "num_attention_heads": 4,
             "num_key_value_heads": 2, "router_num_experts": 8,
             "num_experts": 8, "num_experts_per_tok": 3,
             "route_scale": 2.826, "sliding_window": 5,
             "rms_norm_eps": 1e-5, "rope_theta": 1e4,
             "load_balance_coeff": 0.001,
             "layers_run": {"dense": 1, "moe": 2,
                            "layer_types": cfg["layer_types"]}}
    facts, ok = trinity_train.step_check(job, model, 16, {
        "before": w, "change": change, "loss": loss,
        "batch": {"tokens": np.asarray(tokens),
                  "targets": np.asarray(targets)},
        "opt": dict({k: v for k, v in opt.items() if k != "bias_rate"},
                    first_rate=1e-3)})
    assert ok is (fault is None), facts
    tol = facts["tolerance"]
    assert (facts["bias_wrong"] > 0) is (limit == "bias_wrong")
    assert (facts["update_norm_rel"] > tol["update_norm_rel"]) \
        is (limit == "norm")
    assert (facts["loss_rel"] > tol["step_loss_rel"]) is (limit == "loss")
    assert (facts["update_cosine"] < tol["update_cosine"]) \
        is (limit == "cosine")


def test_runner_refuses_a_program_from_before_the_model(monkeypatch, capsys):
    """The driver hands the parent this PR's benchmark files: the runner
    looks in the program for the fields it needs and exits 2 at once,
    before jax is touched."""
    import runners.trinity_train as runner
    from poseidon_tpu.proto import messages
    runner.refuse_old_program(CELL)           # this program: fine
    monkeypatch.setattr(messages, "AttentionParameter",
                        lambda: types.SimpleNamespace(num_heads=1))
    with pytest.raises(SystemExit) as stop:
        runner.refuse_old_program(CELL)
    err = capsys.readouterr().err
    assert stop.value.code == 2 and "attention_param.window" in err
    monkeypatch.undo()
    monkeypatch.setattr(messages, "MoEParameter",
                        lambda: types.SimpleNamespace(num_held=0,
                                                      held_first=0))
    with pytest.raises(SystemExit) as stop:
        runner.refuse_old_program(CELL)
    assert stop.value.code == 2 and "score_func" in capsys.readouterr().err


@pytest.mark.parametrize("trace", [0, 1])
def test_cpu_tiny_rehearsal_of_the_trinity_cell(trace):
    done = run_cell("--workload", CELL, "--seed", "3000000019", "--seconds",
                    "1", "--trace", str(trace), "--cpu-tiny")
    assert done.returncode == 0, done.stderr[-3000:]
    lines = done.stdout.strip().splitlines()
    line, facts = json.loads(lines[-1]), json.loads(lines[-2])["facts"]
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] >= 4 and line["device"]["platform"] == "cpu"
    assert all(facts["checks"].values()), facts["checks"]
    check = facts["reference"]
    assert check["logits_rel_l2"] < check["tolerance"]["logits_rel_l2"]
    assert check["logits_rel_l2"] < check["lower_precision_rel_l2"]
    assert len(check["route_flips"]) == 4          # one count a MoE layer
    step = facts["step_reference"]
    assert step["sequences"] == BATCH and step["bias_wrong"] == 0
    assert step["bias_of"] == 4 * 128
    assert step["bias_moved"] > step["bias_of"] // 2
    assert step["loss_rel"] < step["tolerance"]["step_loss_rel"]
    assert step["update_norm_rel"] < step["tolerance"]["update_norm_rel"]
    assert step["lower_precision_update_cosine"] < step["update_cosine"]
    assert facts["token_file"]["documents"] > 10     # end-of-text is in play
    assert facts["kernel_routes"] == [
        "attention=dense; 4 kv heads repeated x8; no positions",
        "attention=dense; 4 kv heads repeated x8; window 16 as a dense mask",
        # what the program prints since PR 43: the held rows run in chunks
        # (``; held rows: chunks of P of T k`` on the chip); at these cut
        # sizes two chunks hold every row and the note says nothing
        "grouped_matmul=ragged_dot"]
    assert facts["remat_segments"] == DEPTH + 1
    assert facts["shared_params"] == {}
    assert facts["expert_share"]["l1_moe"] == {
        "held_first": 0, "num_held": 16, "router_num_experts": 128}
    share = facts["held_assignment_share"]
    assert share["first_display"] and share["last_display"] \
        and 0.0 < share["min"] <= share["max"] < 1.0
    assert facts["selection_bias_max_abs"]["last_display"][0] > 0
    # the ladder's regime over the window: every MoE layer's own share per
    # display, and the Engine's layer-step counts differenced over it
    assert sorted(share["per_layer"]) == [f"l{i}_held_share"
                                          for i in range(1, DEPTH)]
    assert max(max(v) for v in share["per_layer"].values()) >= share["max"]
    rungs = share["window_prefix"]
    # counted where a layer's held rows run in chunks (PR 43): every
    # layer-step on the chip, none at these cut sizes
    assert rungs["held_layer_steps"] in (0, (DEPTH - 1) * line["attempted"]) \
        and 0 <= rungs["held_prefix_hits"] <= rungs["held_layer_steps"]
    names = set(line["metrics"])
    if trace:
        # all of the cell's per-layer metrics but those that need a chip's
        # peaks, its memory statistics or its Pallas kernels
        assert names == declared("per_layer", CELL) - {
            "busy_flops_util", "peak_hbm_gb", "held_moe_flops_util",
            "window_flash_attention_roofline",
            "global_flash_attention_roofline",
            "window_visited_over_live_programs"}
        m = {k: v["value"] for k, v in line["metrics"].items()}
        assert m["scope_coverage"] >= 95.0
        parts = ("window_attention_ms_per_step",
                 "global_attention_ms_per_step",
                 "attention_gate_ms_per_step", "router_ms_per_step",
                 "shared_expert_ms_per_step", "held_moe_ms_per_step",
                 "head_ms_per_step")
        assert all(m[k] > 0 for k in parts)
        # on the CPU the whole ATTENTION layer is glue (no Pallas call)
        assert m["attention_glue_ms_per_step"] == pytest.approx(
            m["window_attention_ms_per_step"]
            + m["global_attention_ms_per_step"])
        assert sum(m[k] for k in parts) \
            < m["fwd_ms_per_step"] + m["bwd_ms_per_step"]
        assert 0 < m["held_assignment_share"] \
            <= m["held_share_layer_max"] < 100
    else:
        assert names == declared("end_to_end", CELL) - {"mfu_required"}
        assert line["metrics"]["images_per_s_per_chip"]["value"] == \
            pytest.approx(facts["tokens_per_s_per_chip"] / facts["seq_len"])


def test_new_entries_follow_the_contract():
    cell = next(w for w in BENCH["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == \
        ("trinity_mini", "packed8k_ep8", 1)
    assert "1 dense + 4 of 30 MoE layers" in cell["why"] \
        and f"{BATCH} x 8192" in cell["why"]
    config = next(c for c in BENCH["configs"] if c["name"] == "trinity_mini")
    assert config["reduced"] == CFG["reduced"] == [
        "num_hidden_layers", "num_experts", "vocab_size"]
    assert config["source"] == CFG["source"] \
        and config["file"] == "benchmark/configs/trinity_mini.json"
    mine = [m for m in BENCH["per_layer"]
            if CELL in m.get("workloads", ())]
    # every reader tested above is declared for this cell, under the name
    # the cells that share the measurement share (ISSUE 50)
    assert {r.__name__.rsplit(".", 1)[-1] for r, _ in READERS} \
        <= {m["name"] for m in mine}
    for text in (cell["why"], config["why"], config["source"],
                 *(m["layer"] for m in mine)):
        assert 1 <= len(text) <= 200 and text.isascii() \
            and text.isprintable(), text
    layers = {m["layer"] for m in BENCH["per_layer"]
              if CELL not in m.get("workloads", ())}
    for m in mine:
        assert set(m) == {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        assert m["moves"] in ("mfu_required", "images_per_s_per_chip") \
            and m["layer"] in layers
    assert "85%" in OWN["why"]
