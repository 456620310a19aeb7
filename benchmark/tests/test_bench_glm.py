"""The GLM-4.7-Flash cell's yardstick: ``flops_glm`` against hand counts, the
configuration against the catalog row and its copies, the traffic file, each
of the cell's readers on a hand-made ``layers`` dict (and on a program
without what it reads), the plain reference's rotation and latent attention
against NumPy loops, the runner's groups of new leaves, its ``compared``
rows and its refusal of a program from before the model, the ``--cpu-tiny``
rehearsal of ``glm_flash.e8of64.pack8k`` end to end, and five faults planted
in the program, each of which has to read ``correct: false``."""

import importlib
import json
import os

import numpy as np
import pytest

import flops_glm
import tokengen
from conftest import BENCH_DIR, ROOT
from layer_metrics import (attention_glue_ms_per_step,
                           attention_ms_per_step,
                           flash_attention_roofline, head_ms_per_step,
                           held_assignment_share,
                           held_dropped_assignments,
                           held_load_max_over_mean,
                           held_moe_flops_util, held_moe_ms_per_step,
                           mla_proj_ms_per_step, mtp_loss_over_main,
                           mtp_ms_per_step, recompute_ms_per_step,
                           router_ms_per_step,
                           shared_expert_ms_per_step,
                           tokens_per_s_per_chip)
from test_bench_run import BENCH, declared, entries_of, run_cell

CELL = "glm_flash.e8of64.pack8k"
with open(os.path.join(BENCH_DIR, "configs", "glm_4_7_flash.json")) as f:
    CFG = json.load(f)
with open(os.path.join(BENCH_DIR, "cells", CELL + ".json")) as f:
    OWN = json.load(f)
with open(os.path.join(BENCH_DIR, "traffic", "packed8k_ep8_mtp.json")) as f:
    TRAFFIC = json.load(f)
DEPTH, BATCH = CFG["num_hidden_layers"], OWN["batch_per_chip"]
V, S = 154880 // 8, 8192
_CATALOG_FILE = "/opt/skills/guides/model-configs/architectures.jsonl"
_rows = []
if os.path.exists(_CATALOG_FILE):
    with open(_CATALOG_FILE) as f:
        _rows = [json.loads(l) for l in f if l.strip()]
# config.json of zai-org/GLM-4.7-Flash as the model-configs catalog
# (architectures.jsonl) holds it
CATALOG = {
    "attention_bias": False, "hidden_act": "silu", "hidden_size": 2048,
    "intermediate_size": 10240, "max_position_embeddings": 202752,
    "model_type": "glm4_moe_lite", "moe_intermediate_size": 1536,
    "topk_method": "noaux_tc", "norm_topk_prob": True,
    "num_attention_heads": 20, "n_group": 1, "topk_group": 1,
    "n_routed_experts": 64, "n_shared_experts": 1,
    "routed_scaling_factor": 1.8, "num_experts_per_tok": 4,
    "first_k_dense_replace": 1, "num_hidden_layers": 47,
    "num_key_value_heads": 20, "num_nextn_predict_layers": 1,
    "partial_rotary_factor": 1, "rms_norm_eps": 1e-05, "rope_scaling": None,
    "rope_theta": 1000000, "tie_word_embeddings": False, "q_lora_rank": 768,
    "kv_lora_rank": 512, "qk_nope_head_dim": 192, "qk_rope_head_dim": 64,
    "v_head_dim": 256, "vocab_size": 154880}
REDUCED = {"num_hidden_layers": 47, "n_routed_experts": 64,
           "vocab_size": 154880}


def test_the_catalog_row_is_the_one_copied_here():
    row = [r for r in _rows if r["name"] == "GLM-4.7-Flash"]
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
    assert (DEPTH, CFG["n_routed_experts"], CFG["router_num_experts"],
            CFG["vocab_size"]) == (5, 8, 64, V) and V == 19360
    run = CFG["layers_run"]
    assert (run["dense"], run["moe"], run["mtp"]) == (1, 4, 1)
    assert flops_glm.layers_run(CFG) == {"dense": 1, "moe": 4, "mtp": 1,
                                         "blocks": 6, "sparse": 5}
    for section in ("assumed", "departures", "deployment", "reduced_how",
                    "what_the_cut_changes", "published", "precision",
                    "scopes", "cpu_tiny", "first_loss_why"):
        assert CFG[section], section
    assert sorted(CFG["assumed"]) == [
        "a_mtp_weight", "b_eh_order", "c_mtp_block", "d_rotary_pairing",
        "e_balancing", "f_recipe", "g_packing", "h_end_of_text", "source"]
    assert CFG["mtp_loss_weight"] == 0.3 and "0.3" in CFG["assumed"][
        "a_mtp_weight"]
    assert "8 chips share each layer" in CFG["deployment"]
    assert sorted(CFG["reduced_how"]) == sorted(REDUCED)
    assert "two matrices" in CFG["departures"]
    assert sorted(CFG["precision"]) == [
        "compute", "losses", "master_weights_and_moments", "rotation",
        "router", "softmax_statistics"]
    assert CFG["published"]["parameters"] == 30_587_100_096


def test_configuration_arithmetic():
    """The sizes the configuration file and ISSUE 56 argue from."""
    d, h, f, i, e = 2048, 20, 1536, 10240, 64
    attention = d * 768 + 768 * h * 256 + d * 576 + 512 * h * 448 \
        + h * 256 * d + 768 + 512
    expert = 3 * d * f
    sparse = attention + (e * d + e) + 8 * expert + expert + 2 * d
    dense = attention + 3 * d * i + 2 * d
    module = 2 * d + 2 * d * d + sparse + d
    assert (attention, expert, dense, sparse, module) == (
        21_759_232, 9_437_184, 84_677_888, 106_829_120, 115_223_872)
    total = dense + 4 * sparse + 2 * V * d + d + module
    assert total == 706_518_848
    how = CFG["reduced_how"]["num_hidden_layers"]
    for number in (total, attention, dense, 4 * sparse, module,
                   total - module):
        assert f"{number:,}" in how, number
    assert round(16 * total / 1e9, 2) == 11.30
    assert round(12 * total / 1e9, 2) == 8.48        # the step's arguments
    assert round(16 * (total - module) / 1e9, 2) == 9.46


@pytest.mark.parametrize("part,macs", [
    ("mla_projections", 6 * 21_757_952),
    ("mla_attention", 6 * (S // 2) * 20 * (256 + 256)),
    ("dense_ffn", 62_914_560),
    ("router", 5 * 131_072),
    ("experts", 5 * 4 * 9_437_184 // 8),
    ("shared_expert", 5 * 9_437_184),
    ("head", 2048 * V),
    ("mtp_head", 2048 * V * (S - 1) // S),
    ("mtp_eh", 2 * 2048 * 2048)])
def test_required_macs_against_hand_counts(part, macs):
    assert flops_glm.required_macs_per_token(CFG, S)[part] == macs


def test_required_flops_and_shares():
    macs = flops_glm.required_macs_per_token(CFG, S)
    flops = flops_glm.required_flops_per_token(CFG, S)
    total = sum(macs.values())
    assert round(total / 1e6) == 604 and flops["total"] == 6 * total
    assert round(flops["total"] * S / 1e12, 1) == 29.7     # a sequence
    share = lambda *parts: round(100 * sum(macs[p] for p in parts) / total)
    assert share("mla_attention") == 42 and share("mla_projections") == 22
    assert share("mla_attention", "mla_projections") == 63
    assert share("head", "mtp_head") == 13 and share("dense_ffn") == 10
    assert share("shared_expert") == 8 and share("experts") == 4
    assert share("mtp_eh") == 1
    # the module as a whole: its block (a sixth of the attention, a fifth
    # of the sparse parts), its head pass and W_eh
    module = (macs["mla_attention"] + macs["mla_projections"]) / 6 \
        + (macs["router"] + macs["experts"] + macs["shared_expert"]) / 5 \
        + macs["mtp_head"] + macs["mtp_eh"]
    assert round(100 * module / total) == 21
    assert flops_glm.expert_flops_per_assignment(CFG) == 6 * 9_437_184
    flash = flops_glm.flash_attention_step(CFG, 2, S)
    assert flash["flops"] == 6 * 2 * (S * S // 2) * 20 * 3 * 512 * 2
    assert flash["bytes"] == 6 * 2 * S * 2 * (
        3 * 20 * 256 + 3 * (20 * 192 + 64) + 6 * 20 * 256)
    # the kernels are bound by their FLOPs: 126 ms of the MXU a step
    # against 14 ms of HBM
    assert round(1e3 * flash["flops"] / 197e12) == 126
    assert round(1e3 * flash["bytes"] / 819e9) == 14


def test_copies_match_their_originals():
    for copy, original in CFG["copied_from"].items():
        with open(os.path.join(BENCH_DIR, copy)) as a, \
                open(os.path.join(ROOT, original)) as b:
            assert a.read() == b.read(), (copy, original)
    with open(os.path.join(BENCH_DIR, CFG["net"])) as f:
        net = f.read()
    assert net.count("type: ATTENTION") == 6 \
        and net.count("rotary_shared: true") == 6 \
        and net.count("value_head_dim: 256") == 6 \
        and net.count("rope_theta: 1000000.0") == 6
    assert net.count("type: MOE\n") == net.count("type: MOE_ROUTER") == 5
    assert net.count("num_held: 8") == 5 \
        and net.count("num_experts: 64") == 10 and net.count("top_k: 4") == 10
    assert net.count("route_scale: 1.8") == 10
    assert net.count('name: "tok_w"') == net.count('name: "head_w"') == 2
    assert net.count("type: TOKEN_SHIFT") == 1 \
        and net.count("type: WEIGHTED_MEAN_LOSS") == 1 \
        and net.count("loss_weight: 0.3") == 1


def test_traffic_is_packed8k_ep8_s_with_the_module_s_checkpoints():
    with open(os.path.join(BENCH_DIR, "traffic", "packed8k_ep8.json")) as f:
        sibling = json.load(f)
    mix = TRAFFIC["documents"]
    assert (TRAFFIC["seq_len"], TRAFFIC["steps_in_file"], TRAFFIC["display"],
            TRAFFIC["runner"], TRAFFIC["precision"],
            TRAFFIC["settle_displays"]) == \
        (8192, 8, 4, "glm_train", "bf16", 4)
    # the warm-up's shape, the window and the feed are packed8k_ep8's
    for key in ("display", "trace_steps", "seq_len", "steps_in_file", "feed",
                "window", "precision", "settle_displays"):
        assert TRAFFIC[key] == sibling[key], key
    assert TRAFFIC["argv"][:-1] == sibling["argv"][:-1]
    assert TRAFFIC["argv"][-1] == r"--remat=/l\d+_/,/mtp_/,/lm_/"
    assert {k: v for k, v in mix.items() if k != "why"} == \
        {k: v for k, v in sibling["documents"].items() if k != "why"}
    big = 3_000_000_019                      # over 2**31, as the driver's
    a = tokengen.packed_sequences(big, 2, 8192, V, mix)
    flat, nxt = a["data"].reshape(-1), a["label"].reshape(-1)
    assert np.array_equal(flat[1:], nxt[:-1])           # packed end to end
    assert 0 <= flat.min() and flat.max() < V           # ids over the slice
    with open(os.path.join(ROOT, "examples", "lm",
                           "glm_4_7_flash_solver.prototxt")) as f:
        header = f.read()
    flag = TRAFFIC["argv"][-1]
    assert "--remat '" + flag[len("--remat="):] + "'" in header


def test_the_remat_flag_is_one_checkpoint_a_layer_and_two_for_the_module():
    """The traffic's flag against the net's layer names: one segment a
    layer, the module's block, the head, the module's head; every layer but
    the entry and the final norm in one."""
    from poseidon_tpu.core.remat import resolve_entries
    from poseidon_tpu.proto.messages import load_net
    names = [l.name for l in load_net(
        os.path.join(BENCH_DIR, CFG["net"])).layers]
    flag = TRAFFIC["argv"][-1]
    layers, segments = resolve_entries(names, flag[len("--remat="):]
                                       .split(","))
    assert sorted((s[0], s[-1]) for s in segments) == sorted(
        [(f"l{i}_attn_norm", f"l{i}_res2") for i in range(5)]
        + [("mtp_embed", "mtp_res2"), ("mtp_snorm", "mtp_loss"),
           ("lm_head", "lm_loss")])
    assert set(names) - set(layers) == {"tokens", "embed", "final_norm"}


# --------------------------------------------------------------------------- #
# the cell's readers on a hand-made run
# --------------------------------------------------------------------------- #
#   two steps; times in ns
OPS = [("fusion qb.1 bf16[8]", 0.0, 10.0),             # l0_mla_qb fwd
       ("fusion rope.2 bf16[8]", 10.0, 6.0),           # l0_mla_attn fwd
       ("pallas-call flash.3 bf16[8]", 20.0, 40.0),    # l0_mla_attn bwd
       ("pallas-call flash.4 bf16[8]", 60.0, 20.0),    # mtp_mla_attn fwd
       ("fusion split.5 bf16[8]", 80.0, 2.0),          # l1_mla_kva_split bwd
       ("fusion qnorm.6 bf16[8]", 82.0, 4.0),          # mtp_mla_qnorm fwd
       ("fusion moe.7 bf16[8]", 90.0, 30.0),           # l1_moe bwd
       ("fusion moe.8 bf16[8]", 120.0, 10.0),          # mtp_moe fwd
       ("fusion router.9 f32[8]", 130.0, 8.0),         # l1_router fwd
       ("fusion head.10 bf16[8]", 140.0, 12.0),        # lm_head bwd
       ("fusion head.11 bf16[8]", 152.0, 14.0),        # mtp_head bwd
       ("fusion nll.12 f32[8]", 166.0, 2.0),           # mtp_nll fwd
       ("fusion shared.13 bf16[8]", 170.0, 16.0),      # mtp_shared_up bwd
       ("fusion eh.14 bf16[8]", 186.0, 4.0)]           # mtp_eh fwd
SCOPES = {"ops": {"qb.1": "l0_mla_qb|fwd", "rope.2": "l0_mla_attn|fwd",
                  "flash.3": "l0_mla_attn|bwd", "flash.4": "mtp_mla_attn|fwd",
                  "split.5": "l1_mla_kva_split|bwd",
                  "qnorm.6": "mtp_mla_qnorm|fwd", "moe.7": "l1_moe|bwd",
                  "moe.8": "mtp_moe|fwd", "router.9": "l1_router|fwd",
                  "head.10": "lm_head|bwd", "head.11": "mtp_head|bwd",
                  "nll.12": "mtp_nll|fwd", "shared.13": "mtp_shared_up|bwd",
                  "eh.14": "mtp_eh|fwd"},
          "recomputed": ["rope.2", "flash.4"],
          "types": {"l0_mla_qb": "INNER_PRODUCT", "l0_mla_attn": "ATTENTION",
                    "mtp_mla_attn": "ATTENTION", "l1_mla_kva_split": "SLICE",
                    "mtp_mla_qnorm": "RMS_NORM", "l1_moe": "MOE",
                    "mtp_moe": "MOE", "l1_router": "MOE_ROUTER",
                    "lm_head": "INNER_PRODUCT", "mtp_head": "INNER_PRODUCT",
                    "mtp_nll": "SOFTMAX_NLL",
                    "mtp_shared_up": "INNER_PRODUCT",
                    "mtp_eh": "INNER_PRODUCT"}}
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
                     "flash_per_step": {"flops": 6e3, "bytes": 100.0},
                     "flops_per_assignment": 10.0,
                     "assignments_per_step": 1000,
                     "held_share": [0.12, 0.13, 0.14],
                     "traced_held_share": [0.25],
                     "expert_load": [1.2, 1.4], "dropped": [0.0, 0.0],
                     "mtp_loss_over_main": [1.01, 1.03]}
    return run


READERS = [
    # (10 + 6 + 40 + 20 + 2 + 4) ns / 2 steps
    (attention_ms_per_step, 41e-6),
    # flops-bound: 6e3 / 1e12 = 6 ns against (40 + 20) / 2 ns of kernel
    (flash_attention_roofline, 100 * 6e-9 / 30e-9),
    (attention_glue_ms_per_step, 6e-6),       # (6 + 2 + 4) / 2
    (mla_proj_ms_per_step, 5e-6),
    # every mtp_* scope: 20 + 4 + 10 + 14 + 2 + 16 + 4
    (mtp_ms_per_step, 35e-6),
    (head_ms_per_step, 14e-6),                # (12 + 14 + 2) / 2
    (mtp_loss_over_main, 1.02),
    (held_moe_ms_per_step, 20e-6),
    # the TRACED steps' 0.25 x 1000 assignments x 10 FLOPs over 20 ns x 1e12
    (held_moe_flops_util, 100 * 2.5e3 / (20e-9 * 1e12)),
    (shared_expert_ms_per_step, 8e-6),
    (router_ms_per_step, 4e-6),
    (held_assignment_share, 13.0),            # the window's displays, in %
    (held_load_max_over_mean, 1.3),
    (held_dropped_assignments, 0.0),
    (recompute_ms_per_step, 13e-6),           # (6 + 20) ns / 2
    (tokens_per_s_per_chip, 10 * 2 * 8192 / 4.0),
]
COUNTERS = (tokens_per_s_per_chip, held_assignment_share,
            held_load_max_over_mean, held_dropped_assignments,
            mtp_loss_over_main)


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
    if reader not in COUNTERS:                    # those need no trace
        assert reader.reduce(dict(small_run(), trace=None)) is None
    # the program's map without this model's scopes (the parent's): the
    # roofline finds no kernel time under its pattern and reads nothing
    if reader is flash_attention_roofline:
        bare = small_run(scopes={"ops": {"qb.1": "l0_q|fwd"},
                                 "types": {"l0_q": "INNER_PRODUCT"}})
        assert reader.reduce(bare) is None


# --------------------------------------------------------------------------- #
# the plain reference
# --------------------------------------------------------------------------- #

ref = importlib.import_module("reference.glm_flash")


def test_reference_imports_nothing_of_the_program():
    with open(os.path.join(BENCH_DIR, "reference", "glm_flash.py")) as f:
        text = f.read()
    assert "import poseidon" not in text and "from poseidon" not in text
    assert 'default_matmul_precision("highest")' in text
    assert '"qhd,kd->hqk"' in text       # the shared part: one a token
    for word in ("pallas", "checkpoint_name", "ragged"):
        assert word not in text, word
    for letter in "abcd":
        assert f"({letter})" in text.split("Departures")[1]


def test_reference_rotation_and_attention_against_numpy():
    import jax.numpy as jnp
    r = np.random.RandomState(0)
    f32 = lambda x: jnp.asarray(x, jnp.float32)
    # rotate: pair (j, j + R/2) by t theta^(-2j / R), any axes between
    s, heads, rot, theta = 7, 3, 6, 1e6
    x = r.randn(s, heads, rot)
    want = np.zeros_like(x)
    for t in range(s):
        for j in range(rot // 2):
            a = t * theta ** (-2 * j / rot)
            c, sn = np.cos(a), np.sin(a)
            want[t, :, j] = x[t, :, j] * c - x[t, :, j + rot // 2] * sn
            want[t, :, j + rot // 2] = x[t, :, j + rot // 2] * c \
                + x[t, :, j] * sn
    np.testing.assert_allclose(ref.rotate(f32(x), theta), want, rtol=1e-5,
                               atol=1e-6)
    np.testing.assert_allclose(ref.rotate(f32(x[:, 0]), theta), want[:, 0],
                               rtol=1e-5, atol=1e-6)
    # attention: causal, ONE shared key part a token, keys wider than
    # neither part alone
    dn, dr, dv = 5, 2, 4
    qn, kn = r.randn(s, heads, dn), r.randn(s, heads, dn)
    qr, kpe, v = r.randn(s, heads, dr), r.randn(s, dr), r.randn(s, heads, dv)
    got = np.asarray(ref.attention(*(f32(a) for a in (qn, qr, kn, kpe, v)),
                                   q_block=7))
    for t in range(s):
        for j in range(heads):
            sc = (kn[:t + 1, j] @ qn[t, j] + kpe[:t + 1] @ qr[t, j]) \
                / np.sqrt(dn + dr)
            p = np.exp(sc - sc.max())
            np.testing.assert_allclose(
                got[t, dv * j:dv * j + dv], (p / p.sum()) @ v[:t + 1, j],
                rtol=1e-4, atol=1e-5)


def test_reference_balancing_rule_and_router_order():
    import jax.numpy as jnp
    bias = ref.next_bias(jnp.zeros(4), [9.0, 1.0, 5.0, 5.0], 0.001)
    np.testing.assert_allclose(bias, [-0.001, 0.001, 0.0, 0.0])
    assert ref.cosine_lr(0, 4e-4, 100, 20000, 0.1) == pytest.approx(4e-6)
    names = {"mtp_router": 0, "l10_router": 0, "l2_router": 0, "embed": 0}
    assert ref.router_names(names) == ["l2_router", "l10_router",
                                       "mtp_router"]


def test_new_leaves_are_taken_by_pattern_blob_and_rows():
    import runners.glm_train as runner
    model = {"qk_nope_head_dim": 3, "qk_rope_head_dim": 1,
             "num_attention_heads": 2, "kv_lora_rank": 2}
    groups = runner.new_leaves(model)
    assert sorted(groups) == ["embed_and_head", "k_shared_rows", "mtp_module",
                              "q_rotary_rows"]
    assert groups["q_rotary_rows"][0][2] == [3, 7]     # each head's last dim
    assert groups["k_shared_rows"][0][2] == [2]
    ones = lambda *shape: np.ones(shape)
    change = {"l0_mla_qb": [ones(8, 2)], "mtp_mla_qb": [ones(8, 2)],
              "l0_mla_kva": [ones(3, 4)], "mtp_mla_kva": [ones(3, 4)],
              "embed": [ones(5, 4)], "lm_head": [ones(5, 4)],
              "mtp_eh": [ones(4, 8)], "mtp_router": [ones(6, 4), ones(6)],
              "l1_router": [ones(6, 4), ones(6)]}
    other = {k: [b.copy() for b in v] for k, v in change.items()}
    # a wrong sign in ONE head's rotary row of ONE block
    other["l0_mla_qb"][0][7] *= -1
    # the module's selection bias is no leaf of the optimizer: left out
    other["mtp_router"][1] *= -1
    got = runner.group_cosines(change, other, groups)
    assert got["q_rotary_rows"] == pytest.approx(0.5)   # 2 of 8 numbers
    assert all(got[g] == pytest.approx(1.0) for g in (
        "k_shared_rows", "embed_and_head", "mtp_module"))


def test_compared_rows_say_what_decided():
    import runners.glm_train as runner
    tol = ref.TOLERANCE["bf16"]
    rows = runner.compared(
        {"tolerance": tol, "logits_rel_l2": 0.005,
         "mtp_logits_rel_l2": 0.004, "loss_rel": 1e-5, "mtp_loss_rel": 2e-5,
         "lower_precision_rel_l2": 0.04, "lower_precision_mtp_rel_l2": 0.04},
        {"loss_rel": 1e-5, "update_norm_rel": 0.01, "update_cosine": 0.9,
         "group_cosine": 0.98, "bias_wrong": 0, "bias_compared": 250,
         "bias_of": 320, "lower_precision_update_cosine": 0.7,
         "lower_precision_group_cosine": 0.8},
        (1.001, 0.98, 1.02))
    by = {}
    for r in rows:
        by.setdefault(r["name"], r)
    assert all(r["holds"] for r in rows if r["limit"] is not None)
    # under bf16 two losses are facts: the first step's and the module's
    for fact in ("step_loss_rel", "mtp_loss_rel"):
        assert by[fact]["limit"] is None \
            and by[fact]["decides_correct"] is False
    assert ref.TOLERANCE_TINY["bf16"]["mtp_loss_rel"] is not None
    decided = {r["name"] for r in rows if r["decides_correct"]}
    assert decided == {
        "first_loss_over_expected", "logits_rel_l2", "mtp_logits_rel_l2",
        "loss_rel", "update_norm_rel", "update_cosine", "group_cosine",
        "bias_wrong", "bias_compared_share"}
    assert [r["name"] for r in rows if r["name"].startswith("control_")] == [
        "control_float8_logits_rel_l2", "control_float8_mtp_logits_rel_l2",
        "control_float8_update_cosine", "control_float8_group_cosine"]


def test_first_loss_expectation_counts_both_cross_entropies():
    import math
    import runners.glm_train as runner
    model = {"vocab_size": V, "hidden_size": 2048, "mtp_loss_weight": 0.3,
             "layers_run": CFG["layers_run"]}
    want = runner.expected_first_loss(CFG, model)
    assert want["part"] == pytest.approx(math.log(19360) + 0.4096)
    assert want["total"] == pytest.approx(1.3 * want["part"])
    assert "13.36" in CFG["first_loss_why"] \
        and round(want["total"], 2) == 13.36


def test_runner_refuses_a_program_from_before_the_model(monkeypatch, capsys):
    """The driver hands the parent this PR's benchmark files: the runner
    looks in the program for what it needs and exits 2 at once, before jax
    is touched."""
    import runners.glm_train as runner
    from poseidon_tpu.models import zoo
    runner.refuse_old_program(CELL)           # this program: fine
    monkeypatch.delattr(zoo, "glm_flash")     # the parent's zoo
    with pytest.raises(SystemExit) as stop:
        runner.refuse_old_program(CELL)
    err = capsys.readouterr().err
    assert stop.value.code == 2 and "zoo.glm_flash" in err


@pytest.mark.parametrize("trace", [0, 1])
def test_cpu_tiny_rehearsal_of_the_glm_cell(trace):
    done = run_cell("--workload", CELL, "--seed", "3000000019", "--seconds",
                    "2", "--trace", str(trace), "--cpu-tiny")
    assert done.returncode == 0, done.stderr[-3000:]
    lines = done.stdout.strip().splitlines()
    line, facts = json.loads(lines[-1]), json.loads(lines[-2])["facts"]
    assert all(facts["checks"].values()), facts["checks"]
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] >= 4 and line["device"]["platform"] == "cpu"
    check = facts["reference"]
    for key in ("logits_rel_l2", "mtp_logits_rel_l2", "loss_rel",
                "mtp_loss_rel"):
        assert check[key] < check["tolerance"][key], key
    assert check["logits_rel_l2"] < check["lower_precision_rel_l2"]
    assert check["mtp_logits_rel_l2"] < check["lower_precision_mtp_rel_l2"]
    assert len(check["route_flips"]) == 5          # the module's block last
    step = facts["step_reference"]
    assert step["routers"] == [f"l{i}_router" for i in range(1, 5)] \
        + ["mtp_router"]
    assert sorted(step["group_cosines"]) == [
        "embed_and_head", "k_shared_rows", "mtp_module", "q_rotary_rows"]
    assert step["group_cosine"] == min(step["group_cosines"].values()) \
        >= step["tolerance"]["group_cosine"]
    assert step["bias_wrong"] == 0 and step["bias_of"] == 5 * 64
    assert step["update_norm_rel"] < step["tolerance"]["update_norm_rel"]
    assert step["lower_precision_update_cosine"] < step["update_cosine"]
    assert 0.9 < step["mtp_loss_over_main"] < 1.1
    if trace:
        assert facts["stalls"]["steps"] >= 4
    else:
        assert facts["stalls"] is None
    # what was compared, each beside its limit, LAST in the facts line
    assert list(facts)[-1] == "compared"
    decided = [r for r in facts["compared"] if r["decides_correct"]]
    assert {"logits_rel_l2", "mtp_logits_rel_l2", "loss_rel", "mtp_loss_rel",
            "update_cosine", "group_cosine", "bias_compared_share"} \
        <= {r["name"] for r in decided}
    assert all(r["holds"] for r in decided)
    assert facts["kernel_routes"] == [
        "attention=dense; d 8/8; k_pe rotated once, joined x20",
        "grouped_matmul=ragged_dot"]
    assert facts["remat_segments"] == DEPTH + 2 + 1
    assert facts["shared_params"] == {"tok_w": "embed/w x2",
                                      "head_w": "lm_head/w x2"}
    assert sorted(facts["expert_share"]) == [
        f"l{i}_moe" for i in range(1, 5)] + ["mtp_moe"]
    assert facts["first_loss"] == pytest.approx(
        facts["first_loss_expected"], rel=0.01)
    parts = facts["loss_parts"]
    assert len(parts["lm_loss"]) == len(parts["mtp_loss"]) >= 1
    names = set(line["metrics"])
    if trace:
        # all of the cell's per-layer metrics but those that need a chip's
        # peaks, its memory statistics or its Pallas kernels
        assert names == declared("per_layer", CELL) - {
            "busy_flops_util", "peak_hbm_gb", "flash_attention_roofline",
            "held_moe_flops_util"}
        m = {k: v["value"] for k, v in line["metrics"].items()}
        assert m["scope_coverage"] >= 95.0
        parts = ("attention_ms_per_step", "held_moe_ms_per_step",
                 "shared_expert_ms_per_step", "router_ms_per_step",
                 "head_ms_per_step")
        assert all(m[k] > 0 for k in parts)
        assert m["attention_glue_ms_per_step"] \
            + m["mla_proj_ms_per_step"] \
            == pytest.approx(m["attention_ms_per_step"])  # all dense
        assert sum(m[k] for k in parts) \
            < m["fwd_ms_per_step"] + m["bwd_ms_per_step"]
        assert 0 < m["mtp_ms_per_step"] \
            < m["fwd_ms_per_step"] + m["bwd_ms_per_step"]
        assert m["recompute_ms_per_step"] < m["bwd_ms_per_step"]
        assert 0.9 < m["mtp_loss_over_main"] < 1.1
        assert m["held_dropped_assignments"] == 0.0
    else:
        assert names == declared("end_to_end", CELL) - {"mfu_required"}
        assert line["metrics"]["images_per_s_per_chip"]["value"] == \
            pytest.approx(facts["tokens_per_s_per_chip"] / facts["seq_len"])


# a program with ONE fault planted, run through the harness's own entry at
# the rehearsal's sizes: {fault: (what is planted before run.py starts, the
# rows of ``compared`` of which at least one has to break)}
_PLANTED = {
    # the rotation on the FIRST 64 dims of every head (what rotary_dims
    # alone gave a net before ``rotary_shared``), the rotary parts left
    "rope_on_head_start": ("""
from poseidon_tpu.models import transformer
honest = transformer.rope_attention
def faulty(q, k, v, n_heads, rope_theta=10000.0, n_kv_heads=0, rotary_dims=0,
           window=0, rope=True, k_shared=None, scale=None,
           rotary_shared=False):
    rot = k_shared.shape[-1] if rotary_shared else rotary_dims
    return honest(q, k, v, n_heads, rope_theta, n_kv_heads, rot, window,
                  rope, k_shared, scale)
transformer.rope_attention = faulty
""", {"group_cosine", "update_cosine"}),
    # q's rotary part rotates, the shared key part does not
    "k_pe_unrotated": ("""
from poseidon_tpu.models import transformer
honest = transformer.apply_rope
def faulty(x, cos, sin):        # the shared part alone is (B, S, R)
    return x if x.ndim == 3 else honest(x, cos, sin)
transformer.apply_rope = faulty
""", {"group_cosine", "update_cosine"}),
    # the module's targets are the next tokens once more, not the
    # second-next
    "mtp_target_next": ("""
from poseidon_tpu.core import layers
honest = layers.TokenShiftLayer.apply
def faulty(self, params, bottoms, ctx):
    tops = honest(self, params, bottoms, ctx)
    return [bottoms[0]] + tops[1:] if self.offset == 1 else tops
layers.TokenShiftLayer.apply = faulty
""", {"mtp_loss_rel", "group_cosine"}),
    # lambda 0: the module's loss is shown and not trained on
    "mtp_weight_zero": ("""
from poseidon_tpu.core import layers
layers.WeightedMeanLossLayer.loss_weights = lambda self, n_tops: [0.0]
""", {"first_loss_over_expected", "update_norm_rel", "group_cosine"}),
    # the module's head pass through another matrix than the main head's
    "head_not_shared": ("""
import jax.numpy as jnp
from poseidon_tpu.core import net
honest = net.Net._layer_params
def faulty(self, params, layer, comm=None):
    out = honest(self, params, layer, comm)
    if layer.name == "mtp_head":
        out = {"w": jnp.roll(out["w"], 1, 0)}
    return out
net.Net._layer_params = faulty
""", {"mtp_logits_rel_l2", "group_cosine"}),
}


@pytest.mark.parametrize("fault", sorted(_PLANTED))
def test_a_planted_fault_reads_not_correct(fault, tmp_path):
    """Each mechanism this configuration brought is held by a limit: with
    its fault planted in the PROGRAM the harness has to print ``correct:
    false`` and name a row that broke."""
    plant, must_break = _PLANTED[fault]
    script = tmp_path / "run_faulty.py"
    script.write_text(f"""
import os, runpy, sys
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["JAX_COMPILATION_CACHE_DIR"] = {str(tmp_path / "cache")!r}
sys.path[:0] = [{BENCH_DIR!r}, {ROOT!r}]
{plant}
runpy.run_path(os.path.join({BENCH_DIR!r}, "run.py"), run_name="__main__")
""")
    done = run_cell("--workload", CELL, "--seed", "3000000023", "--seconds",
                    "1", "--trace", "0", "--cpu-tiny", script=str(script))
    assert done.returncode == 0, done.stderr[-3000:]
    lines = done.stdout.strip().splitlines()
    line, facts = json.loads(lines[-1]), json.loads(lines[-2])["facts"]
    assert line["correct"] is False
    broke = {r["name"] for r in facts["compared"]
             if r["decides_correct"] and not r["holds"]}
    assert must_break <= broke, (broke, facts["checks"])


def test_new_entries_follow_the_contract():
    cell = next(w for w in BENCH["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) \
        == ("glm_4_7_flash", "packed8k_ep8_mtp", 1)
    assert "layers 0-4 of 47" in cell["why"] \
        and f"{BATCH} x 8192" in cell["why"] and "2,048 tokens" in cell["why"]
    config = next(c for c in BENCH["configs"] if c["name"] == "glm_4_7_flash")
    assert config["reduced"] == CFG["reduced"] \
        == ["num_hidden_layers", "n_routed_experts", "vocab_size"]
    assert config["source"] == CFG["source"] \
        and config["file"] == "benchmark/configs/glm_4_7_flash.json"
    mine = entries_of(CELL, [r for r, _ in READERS])
    for text in (cell["why"], config["why"], config["source"],
                 *(m["layer"] for m in mine)):
        assert 1 <= len(text) <= 200 and text.isascii() \
            and text.isprintable(), text
    layers = {m["layer"] for m in BENCH["per_layer"]
              if CELL not in m.get("workloads", ())}
    for m in mine:
        assert set(m) == {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        assert m["moves"] == "mfu_required" and m["layer"] in layers
        if m["name"].endswith("_roofline"):
            assert m["unit"] == "%" and m["better"] == "higher"
    assert BATCH == 2 and "85%" in OWN["why"] and "13.50 GB" in OWN["why"] \
        and "15.45 GB" in OWN["why"]
    assert len(BENCH["per_layer"]) <= 128 and len(BENCH["workloads"]) <= 24
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        assert len(f.read()) < 64 * 1024
