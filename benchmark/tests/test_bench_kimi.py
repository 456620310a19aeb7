"""The Kimi-Linear cell's yardstick: ``flops_kimi`` against hand counts, the
configuration against the catalog row and its copies, the traffic file, each
of the cell's readers on a hand-made ``layers`` dict (and on a program
without what it reads), the plain reference's recurrence, convolution and
latent attention against NumPy loops and its two controls, the runner's
``compared`` rows and its refusal of a program from before the model, and
the ``--cpu-tiny`` rehearsal of ``kimi_linear.e8of256.pack8k`` end to end."""

import importlib
import json
import os
import types

import numpy as np
import pytest

import flops_kimi
import tokengen
from conftest import BENCH_DIR, ROOT
from layer_metrics import (attention_glue_ms_per_step, attention_ms_per_step,
                           delta_glue_ms_per_step, delta_ms_per_step,
                           delta_scan_ms_per_step, delta_scan_roofline,
                           flash_attention_roofline, head_ms_per_step,
                           held_assignment_share, held_dropped_assignments,
                           held_load_max_over_mean, held_moe_flops_util,
                           held_moe_ms_per_step, held_share_layer_max,
                           recompute_ms_per_step, router_ms_per_step,
                           shared_expert_ms_per_step, tokens_per_s_per_chip)
from test_bench_run import BENCH, declared, run_cell

CELL = "kimi_linear.e8of256.pack8k"
with open(os.path.join(BENCH_DIR, "configs", "kimi_linear_48b.json")) as f:
    CFG = json.load(f)
with open(os.path.join(BENCH_DIR, "cells", CELL + ".json")) as f:
    OWN = json.load(f)
with open(os.path.join(BENCH_DIR, "traffic", "packed8k_ep32.json")) as f:
    TRAFFIC = json.load(f)
DEPTH, BATCH = CFG["num_hidden_layers"], OWN["batch_per_chip"]
V, S = 163840 // 8, 8192
_CATALOG_FILE = "/opt/skills/guides/model-configs/architectures.jsonl"
_rows = []
if os.path.exists(_CATALOG_FILE):
    with open(_CATALOG_FILE) as f:
        _rows = [json.loads(l) for l in f if l.strip()]
# config.json of moonshotai/Kimi-Linear-48B-A3B-Instruct as the
# model-configs catalog (architectures.jsonl) holds it
CATALOG = {
    "first_k_dense_replace": 1, "head_dim": 72, "hidden_act": "silu",
    "hidden_size": 2304, "intermediate_size": 9216, "kv_lora_rank": 512,
    "linear_attn_config": {
        "full_attn_layers": [4, 8, 12, 16, 20, 24, 27], "head_dim": 128,
        "kda_layers": [1, 2, 3, 5, 6, 7, 9, 10, 11, 13, 14, 15, 17, 18, 19,
                       21, 22, 23, 25, 26],
        "num_heads": 32, "short_conv_kernel_size": 4},
    "mla_use_nope": True, "model_max_length": 1048576,
    "model_type": "kimi_linear", "moe_intermediate_size": 1024,
    "moe_layer_freq": 1, "moe_renormalize": True,
    "moe_router_activation_func": "sigmoid", "num_attention_heads": 32,
    "num_expert_group": 1, "num_experts": 256, "num_experts_per_token": 8,
    "num_hidden_layers": 27, "num_key_value_heads": 32,
    "num_nextn_predict_layers": 0, "num_shared_experts": 1,
    "q_lora_rank": None, "qk_nope_head_dim": 128, "qk_rope_head_dim": 64,
    "rms_norm_eps": 1e-05, "rope_scaling": None, "rope_theta": 10000,
    "routed_scaling_factor": 2.446, "tie_word_embeddings": False,
    "topk_group": 1, "use_grouped_topk": True, "v_head_dim": 128,
    "vocab_size": 163840}
REDUCED = {"num_hidden_layers": 27, "num_experts": 256, "vocab_size": 163840}


def test_the_catalog_row_is_the_one_copied_here():
    row = [r for r in _rows if r["name"] == "Kimi-Linear-48B-A3B-Instruct"]
    if not row:
        pytest.skip("no model-configs catalog on this machine")
    assert row[0]["config"] == CATALOG
    assert row[0]["source_url"] == CFG["source"]


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
            CFG["vocab_size"]) == (5, 8, 256, V) and V == 20480
    run = CFG["layers_run"]
    assert (run["dense"], run["moe"]) == (1, 4) \
        and run["layer_types"] == ["kda", "kda", "kda", "mla", "kda"]
    # layers 1-5 as published (1-indexed lists)
    lin = CFG["linear_attn_config"]
    assert [("mla" if i in lin["full_attn_layers"] else "kda")
            for i in range(1, 6)] == run["layer_types"]
    assert all(i in lin["kda_layers"] for i in (1, 2, 3, 5))
    for section in ("assumed", "departures", "deployment", "reduced_how",
                    "what_the_cut_changes", "published", "precision",
                    "scopes", "cpu_tiny"):
        assert CFG[section], section
    for key in ("a_short_conv", "b_l2_norm", "c_q_scale", "d_decay",
                "e_decay_init", "f_beta", "g_out_norm", "h_mla_nope",
                "i_balancing", "j_recipe", "k_packing", "l_end_of_text",
                "source"):
        assert CFG["assumed"][key], key
    assert "32 chips share each layer" in CFG["deployment"]
    assert "f32" in CFG["precision"]["recurrence"] \
        and "bf16" in CFG["precision"]["recurrence"]
    assert sorted(CFG["reduced_how"]) == sorted(REDUCED)
    assert "two matrices" in CFG["departures"]


def test_configuration_arithmetic():
    """The sizes the configuration file and ISSUE 41 argue from."""
    d, w, h, hd, f, i, e = 2304, 4096, 32, 128, 1024, 9216, 256
    kda = 4 * d * w + 3 * 4 * w + 2 * (d * hd + hd * w) + d * h \
        + h + w + hd
    mla = d * 32 * 192 + d * (512 + 64) + 512 + 512 * 32 * 256 + w * d
    expert = 3 * d * f
    moe = e * d + e + expert + 8 * expert
    assert (kda, mla, expert, moe, 3 * d * i) == (
        39_514_272, 29_114_880, 7_077_888, 64_291_072, 63_700_992)
    layers = (kda + 3 * d * i + 2 * d) + 3 * (kda + moe + 2 * d) \
        + (mla + moe + 2 * d)
    assert layers == 508_060_288
    total = layers + 2 * V * d + d
    assert total == 602_434_432
    how = CFG["reduced_how"]["num_hidden_layers"]
    assert f"{total:,} parameters" in how and f"{kda:,}" in how \
        and f"{mla:,}" in how
    assert round(16 * total / 1e9, 2) == 9.64
    assert round(12 * total / 1e9, 2) == 7.23        # the step's arguments
    sixteen = total + 4 * 8 * expert
    assert round(sixteen / 1e6, 1) == 828.9 \
        and round(16 * sixteen / 1e9, 1) == 13.3


@pytest.mark.parametrize("part,macs", [
    ("kda_projections", 4 * 39_460_864),
    ("kda_recurrence", 4 * 1_572_864),
    ("mla_projections", 29_114_368),
    ("mla_attention", S // 2 * 32 * (192 + 128)),
    ("dense_ffn", 63_700_992),
    ("router", 4 * 589_824),
    ("experts", 4 * 1_769_472),
    ("shared_expert", 4 * 7_077_888),
    ("head", 2304 * V)])
def test_required_macs_against_hand_counts(part, macs):
    assert flops_kimi.required_macs_per_token(CFG, S)[part] == macs


def test_required_flops_and_shares():
    macs = flops_kimi.required_macs_per_token(CFG, S)
    flops = flops_kimi.required_flops_per_token(CFG, S)
    total = sum(macs.values())
    assert round(total / 1e6) == 384 and flops["total"] == 6 * total
    assert round(flops["total"] / 1e9, 2) == 2.30
    share = lambda *parts: round(100 * sum(macs[p] for p in parts) / total)
    assert share("kda_projections", "kda_recurrence") == 43
    assert share("mla_projections", "mla_attention") == 19     # 18.5
    assert share("dense_ffn") == 17 and share("head") == 12
    assert share("router", "experts", "shared_expert") == 10
    assert round(100 * macs["experts"] / total, 1) == 1.8
    assert flops_kimi.expert_flops_per_assignment(CFG) == 6 * 7_077_888
    flash = flops_kimi.flash_attention_step(CFG, 2, S)
    assert flash["flops"] == 2 * (S * S // 2) * 32 * 3 * 320 * 2
    assert flash["bytes"] == 2 * S * 2 * (3 * 32 * 192 + 3 * (32 * 128 + 64)
                                          + 6 * 32 * 128)
    scan = flops_kimi.kda_scan_step(CFG, 2, S)
    assert scan["flops"] == 4 * 2 * S * 3 * 1_572_864 * 2
    assert scan["bytes"] == 4 * 2 * S * 2 * (4 * 4096 * 2 + 4096 * 4 + 64)
    # the recurrence is bound by its bytes: 7.9 ms of HBM a step against
    # 3.1 ms of the MXU
    assert round(1e3 * scan["bytes"] / 819e9, 1) == 7.9
    assert round(1e3 * scan["flops"] / 197e12, 1) == 3.1


def test_copies_match_their_originals():
    for copy, original in CFG["copied_from"].items():
        with open(os.path.join(BENCH_DIR, copy)) as a, \
                open(os.path.join(ROOT, original)) as b:
            assert a.read() == b.read(), (copy, original)
    with open(os.path.join(BENCH_DIR, CFG["net"])) as f:
        net = f.read()
    assert net.count("type: KDA_SCAN") == 4 \
        and net.count("type: ATTENTION") == 1
    assert net.count("type: SHORT_CONV") == 12 \
        and net.count("type: L2_NORM") == 8 \
        and net.count("type: KDA_DECAY") == 4
    assert net.count("type: MOE\n") == net.count("type: MOE_ROUTER") == 4
    assert net.count("rope: false") == 1 \
        and net.count("value_head_dim: 128") == 1
    assert net.count("num_held: 8") == 4 \
        and net.count("num_experts: 256") == 8 and net.count("top_k: 8") == 8
    assert net.count("route_scale: 2.446") == 8


def test_traffic_is_packed8k_ep8_s_over_this_slice():
    with open(os.path.join(BENCH_DIR, "traffic", "packed8k_ep8.json")) as f:
        sibling = json.load(f)
    mix = TRAFFIC["documents"]
    assert (TRAFFIC["seq_len"], TRAFFIC["steps_in_file"], TRAFFIC["display"],
            TRAFFIC["runner"], TRAFFIC["precision"]) == \
        (8192, 8, 4, "kimi_train", "bf16")
    # the warm-up's shape, the window and the feed are packed8k_ep8's
    for key in ("display", "trace_steps", "seq_len", "steps_in_file", "feed",
                "window", "precision"):
        assert TRAFFIC[key] == sibling[key], key
    assert TRAFFIC["argv"] == sibling["argv"]
    assert {k: v for k, v in mix.items() if k != "why"} == \
        {k: v for k, v in sibling["documents"].items() if k != "why"}
    big = 3_000_000_019                      # over 2**31, as the driver's
    a = tokengen.packed_sequences(big, 2, 8192, V, mix)
    flat, nxt = a["data"].reshape(-1), a["label"].reshape(-1)
    assert np.array_equal(flat[1:], nxt[:-1])           # packed end to end
    assert 0 <= flat.min() and flat.max() < V           # ids over the slice
    with open(os.path.join(ROOT, "examples", "lm",
                           "kimi_linear_solver.prototxt")) as f:
        header = f.read()
    flag = next(a for a in TRAFFIC["argv"] if a.startswith("--remat="))
    assert "--remat '" + flag[len("--remat="):] + "'" in header


def test_the_remat_flag_is_one_checkpoint_a_layer():
    """The traffic's flag against the net's layer names: one segment a
    layer (mixer and FFN together), one for the head; every layer in one."""
    from poseidon_tpu.core.remat import resolve_entries
    from poseidon_tpu.proto.messages import load_net
    names = [l.name for l in load_net(
        os.path.join(BENCH_DIR, CFG["net"])).layers]
    flag = next(a for a in TRAFFIC["argv"] if a.startswith("--remat="))
    layers, segments = resolve_entries(names, flag[len("--remat="):]
                                       .split(","))
    assert [(s[0], s[-1]) for s in segments] == [
        (f"l{i}_attn_norm", f"l{i}_res2") for i in range(5)] \
        + [("lm_head", "lm_loss")]
    assert set(names) - set(layers) == {"tokens", "embed", "final_norm"}


# --------------------------------------------------------------------------- #
# the cell's readers on a hand-made run
# --------------------------------------------------------------------------- #
#   two steps; times in ns
OPS = [("fusion q.1 bf16[8]", 0.0, 10.0),              # l0_kda_q fwd
       ("fusion scan.2 f32[8]", 10.0, 40.0),           # l0_kda_scan bwd
       ("pallas-call flash.3 bf16[8]", 50.0, 20.0),    # l3_mla_attn bwd
       ("fusion conv.4 bf16[8]", 70.0, 4.0),           # l0_kda_conv_q fwd
       ("fusion moe.5 bf16[8]", 80.0, 30.0),           # l1_moe bwd
       ("fusion router.6 f32[8]", 110.0, 8.0),         # l1_router fwd
       ("fusion head.7 bf16[8]", 120.0, 12.0),         # lm_head bwd
       ("fusion nll.8 f32[8]", 132.0, 2.0),            # lm_nll fwd
       ("fusion onorm.9 bf16[8]", 134.0, 6.0),         # l0_kda_onorm fwd
       ("fusion split.10 bf16[8]", 140.0, 2.0),        # l3_mla_kva_split bwd
       ("fusion shared.11 bf16[8]", 142.0, 14.0),      # l1_shared_up bwd
       ("fusion merge.12 bf16[8]", 156.0, 2.0)]        # l3_mla_attn fwd
SCOPES = {"ops": {"q.1": "l0_kda_q|fwd", "scan.2": "l0_kda_scan|bwd",
                  "flash.3": "l3_mla_attn|bwd",
                  "conv.4": "l0_kda_conv_q|fwd", "moe.5": "l1_moe|bwd",
                  "router.6": "l1_router|fwd", "head.7": "lm_head|bwd",
                  "nll.8": "lm_nll|fwd", "onorm.9": "l0_kda_onorm|fwd",
                  "split.10": "l3_mla_kva_split|bwd",
                  "shared.11": "l1_shared_up|bwd",
                  "merge.12": "l3_mla_attn|fwd"},
          "recomputed": ["scan.2", "conv.4"],
          "types": {"l0_kda_q": "INNER_PRODUCT", "l0_kda_scan": "KDA_SCAN",
                    "l3_mla_attn": "ATTENTION",
                    "l0_kda_conv_q": "SHORT_CONV", "l1_moe": "MOE",
                    "l1_router": "MOE_ROUTER", "lm_head": "INNER_PRODUCT",
                    "lm_nll": "SOFTMAX_NLL", "l0_kda_onorm": "RMS_NORM",
                    "l3_mla_kva_split": "SLICE",
                    "l1_shared_up": "INNER_PRODUCT"}}
PEAKS = {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e11}


def small_run(scopes=SCOPES, lm=True):
    run = {"trace": {"steps": 2, "spans": [], "async": {},
                     "devices": {"0": OPS}},
           "steps": 10, "batch_per_chip": 2, "window_s": 4.0,
           "peak_flops_per_s": PEAKS["bf16_flops_per_s"],
           "stats": {"sections": {"step_scopes": scopes} if scopes else {}}}
    if lm:
        run["lm"] = {"seq_len": 8192,
                     "scopes": CFG["scopes"], "peaks": PEAKS,
                     "flash_per_step": {"flops": 2e3, "bytes": 100.0},
                     "delta_scan_per_step": {"flops": 1e3, "bytes": 500.0},
                     "flops_per_assignment": 10.0,
                     "assignments_per_step": 1000,
                     "held_share": [0.02, 0.03, 0.04],
                     "held_share_by_layer": {
                         "l1_held_share": [0.01, 0.05, 0.06],
                         "l2_held_share": [0.03, 0.01, 0.02]},
                     "traced_held_share": [0.25],
                     "expert_load": [1.2, 1.4], "dropped": [0.0, 0.0]}
    return run


READERS = [
    (delta_ms_per_step, 30e-6),                      # (10 + 40 + 4 + 6) ns / 2
    (delta_scan_ms_per_step, 20e-6),
    # bytes-bound: 500 / 1e11 = 5 ns against 20 ns of scan a step
    (delta_scan_roofline, 100 * 5e-9 / 20e-9),
    (delta_glue_ms_per_step, 5e-6),                  # (4 + 6) / 2
    (attention_ms_per_step, 11e-6),            # (20 + 2) / 2
    # flops-bound: 2e3 / 1e12 = 2 ns against 10 ns of kernel a step
    (flash_attention_roofline, 100 * 2e-9 / 10e-9),
    (attention_glue_ms_per_step, 2e-6),                  # (2 + 2) / 2
    (router_ms_per_step, 4e-6),
    (shared_expert_ms_per_step, 7e-6),
    (held_moe_ms_per_step, 15e-6),
    # the TRACED steps' 0.25 x 1000 assignments x 10 FLOPs over 15 ns x 1e12
    (held_moe_flops_util, 100 * 2.5e3 / (15e-9 * 1e12)),
    (held_assignment_share, 3.0),
    (held_load_max_over_mean, 1.3),
    (held_dropped_assignments, 0.0),
    (held_share_layer_max, 6.0),              # l1's third display
    (head_ms_per_step, 7e-6),                 # (12 + 2) / 2
    (recompute_ms_per_step, 22e-6),           # (40 + 4) ns / 2
    (tokens_per_s_per_chip, 10 * 2 * 8192 / 4.0),
]
COUNTERS = (held_assignment_share, tokens_per_s_per_chip,
            held_load_max_over_mean, held_dropped_assignments,
            held_share_layer_max)


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
    if reader not in COUNTERS:                # those need no trace
        assert reader.reduce(dict(small_run(), trace=None)) is None
    # the program's map without this model's scopes (the parent's): the
    # time readers find nothing under their patterns and read 0 or nothing
    if reader in (delta_scan_roofline, flash_attention_roofline):
        bare = small_run(scopes={"ops": {"q.1": "l0_q|fwd"},
                                 "types": {"l0_q": "INNER_PRODUCT"}})
        assert reader.reduce(bare) is None


# --------------------------------------------------------------------------- #
# the plain reference
# --------------------------------------------------------------------------- #

ref = importlib.import_module("reference.kimi_linear")


def test_reference_imports_nothing_of_the_program():
    with open(os.path.join(BENCH_DIR, "reference", "kimi_linear.py")) as f:
        text = f.read()
    assert "import poseidon" not in text and "from poseidon" not in text
    assert 'default_matmul_precision("highest")' in text
    assert "jax.lax.scan(token" in text            # token by token
    for word in ("solve_triangular", "cumsum", "pallas"):
        assert word not in text, word


def test_reference_recurrence_conv_and_controls_against_numpy():
    import jax.numpy as jnp
    r = np.random.RandomState(0)
    s, h, d = 24, 2, 4
    q, k, v = r.randn(3, s, h, d)
    g = -np.exp(r.randn(s, h, d) - 1)
    beta = 1 / (1 + np.exp(-r.randn(s, h)))
    want, state = np.zeros((s, h, d)), np.zeros((h, d, d))
    for t in range(s):
        for j in range(h):
            sbar = np.exp(g[t, j])[:, None] * state[j]
            u = beta[t, j] * (v[t, j] - sbar.T @ k[t, j])
            state[j] = sbar + np.outer(k[t, j], u)
            want[t, j] = state[j].T @ q[t, j] * d ** -0.5
    f32 = lambda x: jnp.asarray(x, jnp.float32)
    args = [f32(x) for x in (q, k, v, g, beta)]
    np.testing.assert_allclose(ref.delta_rule(*args), want, rtol=2e-5,
                               atol=1e-6)
    # in blocks of tokens under a checkpoint: the same numbers
    import jax
    np.testing.assert_allclose(
        ref.delta_rule(*args, t_block=8, ckpt=jax.checkpoint), want,
        rtol=2e-5, atol=1e-6)
    # the state rounded to bf16 after every token: another result
    low = ref.delta_rule(*args, state_round=lambda x: x.astype(
        jnp.bfloat16).astype(jnp.float32))
    err = np.abs(np.asarray(low) - want).max() / np.abs(want).max()
    assert 1e-4 < err < 5e-2
    # the convolution: zeros before the start, tap j reads t - j
    x, w = r.randn(9, 3), r.randn(4, 3)
    conv = np.zeros_like(x)
    for t in range(9):
        for j in range(4):
            if t >= j:
                conv[t] += w[j] * x[t - j]
    np.testing.assert_allclose(ref.short_conv(f32(x), f32(w)),
                               conv / (1 + np.exp(-conv)), rtol=1e-5,
                               atol=1e-6)
    # attention: causal, keys wider than values
    qa, ka, va = r.randn(6, 2, 6), r.randn(6, 2, 6), r.randn(6, 2, 4)
    got = np.asarray(ref.attention(f32(qa), f32(ka), f32(va), q_block=3))
    for t in range(6):
        for j in range(2):
            sc = ka[:t + 1, j] @ qa[t, j] / np.sqrt(6)
            p = np.exp(sc - sc.max())
            np.testing.assert_allclose(
                got[t, 4 * j:4 * j + 4], (p / p.sum()) @ va[:t + 1, j],
                rtol=1e-4, atol=1e-5)


def test_reference_balancing_rule_counts_assignments():
    import jax.numpy as jnp
    bias = ref.next_bias(jnp.zeros(4), [9.0, 1.0, 5.0, 5.0], 0.001)
    np.testing.assert_allclose(bias, [-0.001, 0.001, 0.0, 0.0])
    assert ref.cosine_lr(0, 4e-4, 100, 20000, 0.1) == pytest.approx(4e-6)


def test_compared_rows_say_what_decided():
    import runners.kimi_train as runner
    tol = ref.TOLERANCE["bf16"]
    rows = runner.compared(
        {"tolerance": tol, "loss_program": 10.0, "loss_reference": 10.001,
         "logits_rel_l2": 0.012, "lower_precision_rel_l2": 0.04},
        {"loss_rel": 1e-5, "update_norm_rel": 0.01, "update_cosine": 0.9,
         "bias_wrong": 0, "bias_compared": 900, "bias_of": 1024,
         "lower_precision_update_cosine": 0.7},
        {"logits_rel_l2": 0.03})
    by = {r["name"]: r for r in rows}
    assert all(r["holds"] for r in rows if r["limit"] is not None)
    assert by["step_loss_rel"]["limit"] is None \
        and by["step_loss_rel"]["decides_correct"] is False
    assert by["logits_rel_l2"]["decides_correct"] \
        and not by["control_bf16_state_logits_rel_l2"]["decides_correct"]
    assert by["loss_rel"]["value"] == pytest.approx(1e-4, rel=1e-3)
    assert [r["name"] for r in rows if r["name"].startswith("control_")] == [
        "control_float8_logits_rel_l2", "control_float8_update_cosine",
        "control_bf16_state_logits_rel_l2"]


def test_runner_refuses_a_program_from_before_the_model(monkeypatch, capsys):
    """The driver hands the parent this PR's benchmark files: the runner
    looks in the program for the fields it needs and exits 2 at once,
    before jax is touched."""
    import runners.kimi_train as runner
    from poseidon_tpu.proto import messages
    runner.refuse_old_program(CELL)           # this program: fine

    def parent_layer():
        return types.SimpleNamespace(
            attention_param=types.SimpleNamespace(num_heads=1, window=0))
    monkeypatch.setattr(messages, "LayerParameter", parent_layer)
    with pytest.raises(SystemExit) as stop:
        runner.refuse_old_program(CELL)
    err = capsys.readouterr().err
    assert stop.value.code == 2 and "attention_param.value_head_dim" in err \
        and "kda_param.num_heads" in err


def test_runner_reuses_trinity_s_checks_and_leaves_them_as_they_were():
    import runners.kimi_train as runner
    import runners.trinity_train as trinity
    theirs = trinity.reference_sizes
    with runner.kimi_sizes():
        assert trinity.reference_sizes is runner.reference_sizes
    assert trinity.reference_sizes is theirs
    model = {k: CFG[k] for k in runner.MODEL_KEYS}
    sizes = runner.reference_sizes(CFG, model)
    assert sizes["layer_types"] == ["kda", "kda", "kda", "mla", "kda"] \
        and sizes["num_experts"] == 256 and sizes["route_scale"] == 2.446 \
        and sizes["num_heads"] == 32 and sizes["kv_lora_rank"] == 512


@pytest.mark.parametrize("trace", [0, 1])
def test_cpu_tiny_rehearsal_of_the_kimi_cell(trace):
    done = run_cell("--workload", CELL, "--seed", "3000000019", "--seconds",
                    "2", "--trace", str(trace), "--cpu-tiny")
    assert done.returncode == 0, done.stderr[-3000:]
    lines = done.stdout.strip().splitlines()
    line, facts = json.loads(lines[-1]), json.loads(lines[-2])["facts"]
    assert all(facts["checks"].values()), facts["checks"]
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] >= 4 and line["device"]["platform"] == "cpu"
    check = facts["reference"]
    assert check["logits_rel_l2"] < check["tolerance"]["logits_rel_l2"]
    assert check["logits_rel_l2"] < check["lower_precision_rel_l2"]
    assert len(check["route_flips"]) == 4          # one count a MoE layer
    step = facts["step_reference"]
    assert step["bias_wrong"] == 0 and step["bias_of"] == 4 * 256
    assert step["update_norm_rel"] < step["tolerance"]["update_norm_rel"]
    assert step["lower_precision_update_cosine"] < step["update_cosine"]
    # what was compared, each beside its limit, LAST in the facts line
    assert list(facts)[-1] == "compared"
    decided = [r for r in facts["compared"] if r["decides_correct"]]
    assert len(decided) >= 5 and all(r["holds"] for r in decided)
    assert facts["state_control"]["logits_rel_l2"] > 0
    assert facts["kernel_routes"] == [
        "attention=dense; no positions; d 6/4; k_pe repeated x32",
        # what the program prints since PR 43 (``; held rows: chunks of P of
        # T k`` on the chip; at these cut sizes two chunks hold every row and
        # the note says nothing) and PR 47 (why the scan is not the kernel)
        "grouped_matmul=ragged_dot",
        "kda=chunked C 64, 2 chunks, f32 state; not pallas: heads of 4 / 4 "
        "are no lane blocks of (N, S, H d) (multiples of 128)"]
    assert facts["remat_segments"] == DEPTH + 1
    assert facts["expert_share"]["l1_moe"] == {
        "held_first": 0, "num_held": 8, "router_num_experts": 256}
    assert sorted(facts["recurrent_state"]) == [
        f"l{i}_kda_scan" for i in (0, 1, 2, 4)]
    assert facts["recurrent_state"]["l0_kda_scan"]["chunk"] == 64
    assert sorted(facts["decay_mean"]) == [
        f"l{i}_decay_mean" for i in (0, 1, 2, 4)]
    share = facts["held_assignment_share"]
    assert 0.0 <= share["min"] <= share["max"] < 1.0
    assert sorted(share["per_layer"]) == [f"l{i}_held_share"
                                          for i in range(1, DEPTH)]
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
            "delta_scan_roofline", "flash_attention_roofline"}
        m = {k: v["value"] for k, v in line["metrics"].items()}
        assert m["scope_coverage"] >= 95.0
        parts = ("delta_ms_per_step", "attention_ms_per_step",
                 "router_ms_per_step", "shared_expert_ms_per_step",
                 "held_moe_ms_per_step", "head_ms_per_step")
        assert all(m[k] > 0 for k in parts)
        assert m["delta_scan_ms_per_step"] + m["delta_glue_ms_per_step"] \
            < m["delta_ms_per_step"]
        # on the CPU the whole ATTENTION layer is glue (no Pallas call)
        assert m["attention_glue_ms_per_step"] >= m["attention_ms_per_step"]
        assert sum(m[k] for k in parts) \
            < m["fwd_ms_per_step"] + m["bwd_ms_per_step"]
        assert 0 <= m["held_assignment_share"] \
            <= m["held_share_layer_max"] < 100
    else:
        assert names == declared("end_to_end", CELL) - {"mfu_required"}
        assert line["metrics"]["images_per_s_per_chip"]["value"] == \
            pytest.approx(facts["tokens_per_s_per_chip"] / facts["seq_len"])


def test_new_entries_follow_the_contract():
    cell = next(w for w in BENCH["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) \
        == ("kimi_linear_48b", "packed8k_ep32", 1)
    assert "layers 1-5 of 27" in cell["why"] \
        and f"{BATCH} x 8192" in cell["why"]
    config = next(c for c in BENCH["configs"]
                  if c["name"] == "kimi_linear_48b")
    assert config["reduced"] == CFG["reduced"] == [
        "num_hidden_layers", "num_experts", "vocab_size"]
    assert config["source"] == CFG["source"] \
        and config["file"] == "benchmark/configs/kimi_linear_48b.json"
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
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        assert len(f.read()) < 64 * 1024
