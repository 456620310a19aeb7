"""The Xing4.0-29B-A4B cell's yardstick: ``flops_xing`` against hand counts,
the configuration against the catalog row and its copies, the traffic file,
each of the cell's readers on a hand-made ``layers`` dict (and on a program
without what it reads), the plain reference's Sinkhorn loop and YaRN
frequencies against NumPy loops, the runner's groups of new leaves and the
leaves it leaves out, its ``compared`` rows and its refusal of a program
from before the model, the ``--cpu-tiny`` rehearsal of ``xing4.e8of64.hc4``
end to end, and six faults planted in the program, each of which has to break a limit
of the mechanism checks (whose operands are all drawn at random)."""

import importlib
import json
import math
import os

import numpy as np
import pytest

import flops_xing
import tokengen
from conftest import BENCH_DIR, ROOT
from layer_metrics import (attention_glue_ms_per_step,
                           attention_ms_per_step,
                           flash_attention_roofline,
                           hc_map_ms_per_step, hc_ms_per_step,
                           hc_res_err, hc_stream_roofline,
                           head_ms_per_step,
                           held_assignment_share,
                           held_dropped_assignments,
                           held_load_max_over_mean,
                           held_moe_flops_util,
                           held_moe_ms_per_step,
                           mla_proj_ms_per_step,
                           recompute_ms_per_step,
                           router_ms_per_step,
                           shared_expert_ms_per_step,
                           tokens_per_s_per_chip)
from test_bench_run import BENCH, declared, entries_of, run_cell

CELL = "xing4.e8of64.hc4"
NAME = "xing4_0_29b_a4b"
with open(os.path.join(BENCH_DIR, "configs", NAME + ".json")) as f:
    CFG = json.load(f)
with open(os.path.join(BENCH_DIR, "cells", CELL + ".json")) as f:
    OWN = json.load(f)
with open(os.path.join(BENCH_DIR, "traffic", "packed_ep8_hc4.json")) as f:
    TRAFFIC = json.load(f)
DEPTH, BATCH = CFG["num_hidden_layers"], OWN["batch_per_chip"]
V, S = 131072 // 8, 8192
_CATALOG_FILE = "/opt/skills/guides/model-configs/architectures.jsonl"
_rows = []
if os.path.exists(_CATALOG_FILE):
    with open(_CATALOG_FILE) as f:
        _rows = [json.loads(l) for l in f if l.strip()]
# config.json of XingChen-AGI/Xing4.0-29B-A4B as the model-configs catalog
# (architectures.jsonl) holds it
CATALOG = {
    "attention_bias": False, "ep_size": 1, "first_k_dense_replace": 2,
    "hidden_act": "silu", "hidden_size": 3584, "intermediate_size": 9216,
    "kv_lora_rank": 512, "max_position_embeddings": 262144,
    "model_type": "xing4_0", "moe_intermediate_size": 1024,
    "moe_layer_freq": 1, "n_group": 1, "n_routed_experts": 64,
    "n_shared_experts": 1, "norm_topk_prob": True, "num_attention_heads": 32,
    "num_experts_per_tok": 4, "num_hidden_layers": 40,
    "num_key_value_heads": 32, "num_nextn_predict_layers": 1, "hc_mult": 4,
    "hc_sinkhorn_iters": 20, "hc_eps": 1e-06, "mhc_h_res_clamp_min": -30,
    "mhc_h_res_clamp_max": 30, "q_lora_rank": 768, "qk_nope_head_dim": 128,
    "qk_rope_head_dim": 64, "rms_norm_eps": 1e-06, "rope_theta": 10000,
    "rope_scaling": {"beta_fast": 32, "beta_slow": 1, "factor": 64,
                     "mscale": 1, "mscale_all_dim": 1,
                     "original_max_position_embeddings": 4096,
                     "type": "yarn"},
    "routed_scaling_factor": 2, "scoring_func": "sigmoid",
    "tie_word_embeddings": False, "topk_group": 1, "topk_method": "noaux_tc",
    "v_head_dim": 128, "vocab_size": 131072}
REDUCED = {"num_hidden_layers": 40, "n_routed_experts": 64,
           "vocab_size": 131072, "num_nextn_predict_layers": 1}


def test_the_catalog_row_is_the_one_copied_here():
    row = [r for r in _rows if r["name"] == "Xing4.0-29B-A4B"]
    if not row:
        pytest.skip("no model-configs catalog on this machine")
    assert row[0]["config"] == CATALOG
    assert row[0]["source_url"] == CFG["source"]


@pytest.mark.parametrize("key", sorted(CATALOG))
def test_configuration_equals_the_catalog_row(key):
    """Every key as published; the depth, the experts HELD, the rows of the
    vocabulary and the module, and only those, are reduced, and no width
    among them."""
    if key in REDUCED:
        assert sorted(CFG["reduced"]) == sorted(REDUCED)
        assert CFG["published"][key] == CATALOG[key] == REDUCED[key]
        assert CFG[key] < CATALOG[key]
    else:
        assert CFG[key] == CATALOG[key]


def test_the_cut_is_the_issue_s():
    assert (DEPTH, CFG["n_routed_experts"], CFG["router_num_experts"],
            CFG["vocab_size"], CFG["num_nextn_predict_layers"]) \
        == (5, 8, 64, V, 0) and V == 16384
    run = CFG["layers_run"]
    assert (run["dense"], run["moe"], run["mtp"]) == (1, 4, 0)
    assert flops_xing.layers_run(CFG) == {"dense": 1, "moe": 4, "mtp": 0,
                                          "blocks": 5, "sparse": 4}
    for section in ("assumed", "departures", "deployment", "reduced_how",
                    "what_the_cut_changes", "published", "precision",
                    "scopes", "cpu_tiny", "first_loss_why"):
        assert CFG[section], section
    assert [k[0] for k in sorted(CFG["assumed"]) if k != "source"] \
        == list("abcdefghijkl")
    assert "8 chips share each layer" in CFG["deployment"]
    assert sorted(CFG["reduced_how"]) == sorted(REDUCED)
    assert "two matrices" in CFG["departures"]
    assert {"stream", "mappings", "master_weights_and_moments"} \
        <= set(CFG["precision"])
    assert CFG["published"]["parameters"] == 30_276_195_174 \
        and CFG["published"]["parameters_without_module"] == 29_505_505_264
    assert CFG["hc_clamp"] == CFG["mhc_h_res_clamp_max"] \
        == -CFG["mhc_h_res_clamp_min"]
    # the leaves nothing feeds are named where the cut is described
    assert "l4_hc_f_map" in CFG["what_the_cut_changes"] \
        and "l0_hc_a_map" in CFG["what_the_cut_changes"]


def test_configuration_arithmetic():
    """The sizes the configuration file and ISSUE 60 argue from."""
    d, h, f, i, e, n = 3584, 32, 1024, 9216, 64, 4
    attention = d * 768 + 768 * h * 192 + d * 576 + 512 * h * 256 \
        + h * 128 * d + 768 + 512
    mapping = n * (n + 2) * (n * d + 1) + 3
    expert = 3 * d * f
    sparse = attention + (e * d + e) + 8 * expert + expert + 2 * d \
        + 2 * mapping
    dense = attention + 3 * d * i + 2 * d + 2 * mapping
    module = 2 * d + 2 * d * d + sparse + d
    assert (attention, mapping, expert, dense, sparse, module) == (
        28_411_136, 344_091, 11_010_048, 128_196_918, 128_426_358,
        154_127_222)
    total = dense + 4 * sparse + 2 * V * d + d
    assert total == 759_346_446
    how = CFG["reduced_how"]
    for number in (total, attention, mapping, dense, sparse, 4 * sparse):
        assert f"{number:,}" in how["num_hidden_layers"], number
    for number in (module, total + module):
        assert f"{number:,}" in how["num_nextn_predict_layers"], number
    assert round(16 * total / 1e9, 2) == 12.15
    assert round(12 * total / 1e9, 2) == 9.11        # the step's arguments
    assert round(16 * (total + module) / 1e9, 1) == 14.6


@pytest.mark.parametrize("part,macs", [
    ("mla_projections", 5 * 28_409_856),
    ("mla_attention", 5 * 32 * 320 * 4096),
    ("dense_ffn", 99_090_432),
    ("router", 4 * 229_376),
    ("experts", 4 * 4 * 11_010_048 // 8),
    ("shared_expert", 4 * 11_010_048),
    ("head", 3584 * 16384),
    # ten sub-layers: projection 24, read 1, write 5, a value of the stream
    ("hc", 10 * 14336 * 30),
])
def test_required_macs_against_hand_counts(part, macs):
    assert flops_xing.required_macs_per_token(CFG, S)[part] == macs


def test_required_flops_and_shares():
    per = flops_xing.required_flops_per_token(CFG, S)
    assert per["total"] == sum(v for k, v in per.items() if k != "total") \
        == 3_485_122_560
    share = {k: 100.0 * v / per["total"] for k, v in per.items()}
    assert [round(share[k], 1) for k in (
        "mla_attention", "mla_projections", "dense_ffn", "head",
        "shared_expert", "experts", "hc")] \
        == [36.1, 24.5, 17.1, 10.1, 7.6, 3.8, 0.7]
    for text in ("36%", "24.5%", "17%", "10%", "7.6%", "3.8%", "0.7%"):
        assert text in CFG["what_the_cut_changes"], text
    assert flops_xing.expert_flops_per_assignment(CFG) == 6 * 11_010_048
    flash = flops_xing.flash_attention_step(CFG, 1, S)
    assert flash["flops"] == 5 * 32 * 3 * 320 * 2 * S * S // 2
    assert flash["bytes"] == 5 * S * 2 * (3 * 6144 + 3 * 4160 + 6 * 4096)
    # the stream: four forwards' worth a sub-layer, (3 n C + 2 C) values a
    # token each
    stream = flops_xing.hc_stream_step(CFG, 1, S)
    assert stream["bytes"] == 10 * 4 * (3 * 14336 + 2 * 3584) * S * 2 \
        == 32_883_343_360
    assert stream["flops"] == per["hc"] * S
    assert "32.9 GB" in CFG["what_the_cut_changes"]
    # bound by bytes on the v5e: 40 ms against 1 ms of FLOPs
    assert stream["bytes"] / 819e9 > 30 * stream["flops"] / 197e12


def test_copies_match_their_originals():
    for copy, original in CFG["copied_from"].items():
        with open(os.path.join(BENCH_DIR, copy)) as a, \
                open(os.path.join(ROOT, original)) as b:
            assert a.read() == b.read(), (copy, original)
    with open(os.path.join(BENCH_DIR, CFG["net"])) as f:
        net = f.read()
    assert net.count("type: ATTENTION") == 5 \
        and net.count("rotary_shared: true") == 5 \
        and net.count("value_head_dim: 128") == 5 \
        and net.count("rope_theta: 10000.0") == 0 \
        and net.count("rope_factor: 64.0") == 5 \
        and net.count("\n    scale: 0.1446796") == 5
    assert net.count("type: MOE\n") == net.count("type: MOE_ROUTER") == 4
    assert net.count("num_held: 8") == 4 \
        and net.count("num_experts: 64") == 8 and net.count("top_k: 4") == 8
    assert net.count("route_scale: 2.0") == 8
    assert net.count("type: HC_MAP") == net.count("type: HC_READ") \
        == net.count("type: HC_WRITE") == 10
    assert net.count("type: HC_START") == net.count("type: HC_END") == 1
    assert net.count("type: ELTWISE") == 4          # the shared experts' sums
    assert "TOKEN_SHIFT" not in net and "mtp_" not in net.split("layers")[1]


def test_traffic_is_packed8k_ep8_mtp_s_without_the_module_s_checkpoints():
    with open(os.path.join(BENCH_DIR, "traffic",
                           "packed8k_ep8_mtp.json")) as f:
        sibling = json.load(f)
    mix = TRAFFIC["documents"]
    assert (TRAFFIC["seq_len"], TRAFFIC["steps_in_file"], TRAFFIC["display"],
            TRAFFIC["runner"], TRAFFIC["precision"],
            TRAFFIC["settle_displays"], TRAFFIC["name"]) == \
        (8192, 8, 4, "xing_train", "bf16", 4, "packed_ep8_hc4")
    for key in ("display", "trace_steps", "seq_len", "steps_in_file", "feed",
                "window", "precision", "settle_displays"):
        assert TRAFFIC[key] == sibling[key], key
    assert TRAFFIC["argv"][:-1] == sibling["argv"][:-1]
    assert TRAFFIC["argv"][-1] == r"--remat=/l\d+_/,/lm_/"
    assert {k: v for k, v in mix.items() if k != "why"} == \
        {k: v for k, v in sibling["documents"].items() if k != "why"}
    big = 3_000_000_019                      # over 2**31, as the driver's
    a = tokengen.packed_sequences(big, 2, 8192, V, mix)
    flat, nxt = a["data"].reshape(-1), a["label"].reshape(-1)
    assert np.array_equal(flat[1:], nxt[:-1])           # packed end to end
    assert 0 <= flat.min() and flat.max() < V           # ids over the slice
    with open(os.path.join(ROOT, "examples", "lm",
                           NAME + "_solver.prototxt")) as f:
        header = f.read()
    flag = TRAFFIC["argv"][-1]
    assert "--remat '" + flag[len("--remat="):] + "'" in header


def test_the_remat_flag_is_one_checkpoint_a_layer():
    """The traffic's flag against the net's layer names: one segment a
    layer, from its first mapping to its last write, and the head; the
    stream's start and end, the embedding and the final norm in none."""
    from poseidon_tpu.core.remat import resolve_entries
    from poseidon_tpu.proto.messages import load_net
    names = [l.name for l in load_net(
        os.path.join(BENCH_DIR, CFG["net"])).layers]
    flag = TRAFFIC["argv"][-1]
    layers, segments = resolve_entries(names, flag[len("--remat="):]
                                       .split(","))
    assert sorted((s[0], s[-1]) for s in segments) == sorted(
        [(f"l{i}_hc_a_map", f"l{i}_hc_f_write") for i in range(5)]
        + [("lm_head", "lm_loss")])
    assert set(names) - set(layers) == {"tokens", "embed", "hc_start",
                                        "hc_end", "final_norm"}


# --------------------------------------------------------------------------- #
# the cell's readers on a hand-made run
# --------------------------------------------------------------------------- #
#   two steps; times in ns
OPS = [("fusion qb.1 bf16[8]", 0.0, 10.0),             # l0_mla_qb fwd
       ("fusion rope.2 bf16[8]", 10.0, 6.0),           # l0_mla_attn fwd
       ("pallas-call flash.3 bf16[8]", 20.0, 40.0),    # l0_mla_attn bwd
       ("pallas-call flash.4 bf16[8]", 60.0, 20.0),    # l2_mla_attn fwd
       ("fusion split.5 bf16[8]", 80.0, 2.0),          # l1_mla_kva_split bwd
       ("fusion qnorm.6 bf16[8]", 82.0, 4.0),          # l2_mla_qnorm fwd
       ("fusion moe.7 bf16[8]", 90.0, 30.0),           # l1_moe bwd
       ("fusion moe.8 bf16[8]", 120.0, 10.0),          # l2_moe fwd
       ("fusion router.9 f32[8]", 130.0, 8.0),         # l1_router fwd
       ("fusion head.10 bf16[8]", 140.0, 12.0),        # lm_head bwd
       ("fusion nll.11 f32[8]", 152.0, 2.0),           # lm_nll fwd
       ("fusion shared.12 bf16[8]", 160.0, 16.0),      # l2_shared_up bwd
       ("fusion map.13 f32[8]", 180.0, 24.0),          # l0_hc_a_map bwd
       ("fusion read.14 bf16[8]", 204.0, 6.0),         # l1_hc_f_read fwd
       ("fusion write.15 bf16[8]", 210.0, 10.0),       # l1_hc_f_write fwd
       ("fusion start.16 bf16[8]", 220.0, 4.0),        # hc_start fwd
       ("fusion end.17 bf16[8]", 224.0, 2.0)]          # hc_end bwd
SCOPES = {"ops": {"qb.1": "l0_mla_qb|fwd", "rope.2": "l0_mla_attn|fwd",
                  "flash.3": "l0_mla_attn|bwd", "flash.4": "l2_mla_attn|fwd",
                  "split.5": "l1_mla_kva_split|bwd",
                  "qnorm.6": "l2_mla_qnorm|fwd", "moe.7": "l1_moe|bwd",
                  "moe.8": "l2_moe|fwd", "router.9": "l1_router|fwd",
                  "head.10": "lm_head|bwd", "nll.11": "lm_nll|fwd",
                  "shared.12": "l2_shared_up|bwd",
                  "map.13": "l0_hc_a_map|bwd", "read.14": "l1_hc_f_read|fwd",
                  "write.15": "l1_hc_f_write|fwd", "start.16": "hc_start|fwd",
                  "end.17": "hc_end|bwd"},
          "recomputed": ["rope.2", "flash.4", "write.15"],
          "types": {"l0_mla_qb": "INNER_PRODUCT", "l0_mla_attn": "ATTENTION",
                    "l2_mla_attn": "ATTENTION", "l1_mla_kva_split": "SLICE",
                    "l2_mla_qnorm": "RMS_NORM", "l1_moe": "MOE",
                    "l2_moe": "MOE", "l1_router": "MOE_ROUTER",
                    "lm_head": "INNER_PRODUCT", "lm_nll": "SOFTMAX_NLL",
                    "l2_shared_up": "INNER_PRODUCT",
                    "l0_hc_a_map": "HC_MAP", "l1_hc_f_read": "HC_READ",
                    "l1_hc_f_write": "HC_WRITE", "hc_start": "HC_START",
                    "hc_end": "HC_END"}}
PEAKS = {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e11}


def small_run(scopes=SCOPES, lm=True):
    run = {"trace": {"steps": 2, "spans": [], "async": {},
                     "devices": {"0": OPS}},
           "steps": 10, "batch_per_chip": 1, "window_s": 4.0,
           "peak_flops_per_s": PEAKS["bf16_flops_per_s"],
           "stats": {"sections": {"step_scopes": scopes} if scopes else {}}}
    if lm:
        run["lm"] = {"seq_len": 8192,
                     "scopes": CFG["scopes"], "peaks": PEAKS,
                     "flash_per_step": {"flops": 6e3, "bytes": 100.0},
                     "hc_stream_per_step": {"flops": 10.0, "bytes": 1200.0},
                     "flops_per_assignment": 10.0,
                     "assignments_per_step": 1000,
                     "held_share": [0.12, 0.13, 0.14],
                     "traced_held_share": [0.25],
                     "expert_load": [1.2, 1.4], "dropped": [0.0, 0.0],
                     "hc_res_err": [2e-5, 4e-5, 3e-5]}
    return run


READERS = [
    # every hc scope: (24 + 6 + 10 + 4 + 2) ns / 2 steps
    (hc_ms_per_step, 23e-6),
    (hc_map_ms_per_step, 12e-6),
    # bytes-bound: 1200 / 1e11 = 12 ns against (24 + 6 + 10) / 2 ns
    (hc_stream_roofline, 100 * 12e-9 / 20e-9),
    (hc_res_err, 4e-5),
    # (10 + 6 + 40 + 20 + 2 + 4) ns / 2 steps
    (attention_ms_per_step, 41e-6),
    # flops-bound: 6e3 / 1e12 = 6 ns against (40 + 20) / 2 ns of kernel
    (flash_attention_roofline, 100 * 6e-9 / 30e-9),
    (attention_glue_ms_per_step, 6e-6),      # (6 + 2 + 4) / 2
    (mla_proj_ms_per_step, 5e-6),
    (head_ms_per_step, 7e-6),                # (12 + 2) / 2
    (held_moe_ms_per_step, 20e-6),
    # the TRACED steps' 0.25 x 1000 assignments x 10 FLOPs over 20 ns x 1e12
    (held_moe_flops_util, 100 * 2.5e3 / (20e-9 * 1e12)),
    (shared_expert_ms_per_step, 8e-6),
    (router_ms_per_step, 4e-6),
    (held_assignment_share, 13.0),           # the window's displays, in %
    (held_load_max_over_mean, 1.3),
    (held_dropped_assignments, 0.0),
    (recompute_ms_per_step, 18e-6),          # (6 + 20 + 10) ns / 2
    (tokens_per_s_per_chip, 10 * 1 * 8192 / 4.0),
]
COUNTERS = (held_assignment_share, held_load_max_over_mean,
            held_dropped_assignments, hc_res_err, tokens_per_s_per_chip)


@pytest.mark.parametrize("reader, want", READERS)
def test_each_reader_on_a_hand_made_run(reader, want):
    assert reader.reduce(small_run()) == pytest.approx(want)


@pytest.mark.parametrize("reader", [r for r, _ in READERS])
def test_each_reader_finds_nothing_on_a_program_without_it(reader):
    """A program or a run without what the reader reads: no map, no ``lm``
    section, no trace — None, and nothing raised."""
    assert reader.reduce(small_run(scopes=None, lm=False)) is None
    if reader is not recompute_ms_per_step:  # reads the map alone
        assert reader.reduce(small_run(lm=False)) is None
    if reader not in COUNTERS:                    # those need no trace
        assert reader.reduce(dict(small_run(), trace=None)) is None
    # the program's map without this model's scopes (the parent's): a
    # roofline finds no time under its pattern and reads nothing
    if reader in (flash_attention_roofline, hc_stream_roofline):
        bare = small_run(scopes={"ops": {"qb.1": "l0_q|fwd"},
                                 "types": {"l0_q": "INNER_PRODUCT"}})
        assert reader.reduce(bare) is None


# --------------------------------------------------------------------------- #
# the plain reference
# --------------------------------------------------------------------------- #

ref = importlib.import_module("reference.xing4")


def test_reference_imports_nothing_of_the_program():
    with open(os.path.join(BENCH_DIR, "reference", "xing4.py")) as f:
        text = f.read()
    assert "import poseidon" not in text and "from poseidon" not in text \
        and "import reference" not in text and "from reference" not in text
    assert 'default_matmul_precision("highest")' in text


def test_reference_sinkhorn_and_yarn_against_numpy():
    import jax.numpy as jnp
    rng = np.random.default_rng(0)
    m = np.exp(rng.standard_normal((3, 4, 4)))
    want = m.copy()
    for _ in range(20):
        for t in range(3):
            for i in range(4):
                want[t, i] = want[t, i] / (want[t, i].sum() + 1e-6)
            for j in range(4):
                want[t, :, j] = want[t, :, j] / (want[t, :, j].sum() + 1e-6)
    got = np.asarray(ref.sinkhorn(jnp.asarray(m, jnp.float32), 20, 1e-6))
    np.testing.assert_allclose(got, want, rtol=2e-5)
    np.testing.assert_allclose(got.sum(-2), 1.0, atol=1e-5)   # columns last
    other = np.asarray(ref.sinkhorn(jnp.asarray(m, jnp.float32), 1, 1e-6,
                                    columns_first=True))
    np.testing.assert_allclose(other.sum(-1), 1.0, atol=1e-5)  # rows last
    # YaRN: the closed form of ISSUE 60 at the published numbers
    scaling = CFG["rope_scaling"]
    freqs = ref.yarn_frequencies(64, 10000.0, scaling)
    c = lambda b: 64 * math.log(4096 / (2 * math.pi * b)) \
        / (2 * math.log(10000.0))                             # noqa: E731
    assert (math.floor(c(32)), math.ceil(c(1))) == (10, 23)
    for i, f in enumerate(freqs):
        plain = 10000.0 ** (-2 * i / 64)
        keep = 1 - min(1.0, max(0.0, (i - 10) / 13))
        assert f == pytest.approx(plain / 64 * (1 - keep) + plain * keep)
    assert freqs[:11] == ref.yarn_frequencies(64, 10000.0)[:11]
    assert ref.softmax_scale(192, scaling) == pytest.approx(
        (0.1 * math.log(64) + 1) ** 2 / math.sqrt(192))
    assert ref.softmax_scale(192) == pytest.approx(1 / math.sqrt(192))
    # the rotation turns pair (j, j + R/2) by t * freqs[j]
    x = rng.standard_normal((5, 8)).astype(np.float32)
    fr = [1.0, 0.5, 0.25, 0.125]
    rot = np.asarray(ref.rotate(jnp.asarray(x), fr))
    for t in range(5):
        for j in range(4):
            a, b = x[t, j], x[t, j + 4]
            ang = t * fr[j]
            np.testing.assert_allclose(
                [rot[t, j], rot[t, j + 4]],
                [a * math.cos(ang) - b * math.sin(ang),
                 b * math.cos(ang) + a * math.sin(ang)], atol=1e-5)


def test_reference_balancing_rule_and_router_order():
    import jax.numpy as jnp
    bias = ref.next_bias(jnp.zeros(4), [9.0, 1.0, 5.0, 5.0], 0.001)
    np.testing.assert_allclose(bias, [-0.001, 0.001, 0.0, 0.0])
    assert ref.cosine_lr(0, 4e-4, 100, 20000, 0.1) == pytest.approx(4e-6)
    names = {"mtp_router": 0, "l10_router": 0, "l2_router": 0, "embed": 0}
    assert ref.router_names(names) == ["l2_router", "l10_router",
                                       "mtp_router"]
    assert set(ref.FAULTS) & set(ref.SAME_FUNCTION) == set() \
        and len(ref.FAULTS) == 7


def test_new_leaves_leave_out_what_nothing_feeds():
    import runners.xing_train as runner
    model = {"qk_nope_head_dim": 3, "qk_rope_head_dim": 1,
             "num_attention_heads": 2, "kv_lora_rank": 2,
             "num_hidden_layers": 2}
    dead = runner.dead_leaves(model)
    assert dead == {"l0_hc_a_map": (0, 3, 6, 2, 5, 8),
                    "l1_hc_f_map": (2, 5, 8)}
    blobs = runner.MAP_BLOBS
    assert [blobs[j] for j in dead["l1_hc_f_map"]] \
        == ["phi_res", "b_res", "a_res"]
    groups = runner.new_leaves(model)
    assert sorted(groups) == ["hc_post", "hc_pre", "hc_res",
                              "k_shared_rows", "q_rotary_rows"]
    assert groups["q_rotary_rows"][0][2] == [3, 7]     # each head's last dim
    assert groups["k_shared_rows"][0][2] == [2]
    # four mappings of nine blobs, nine of them dead
    assert [len(groups[g]) for g in ("hc_pre", "hc_post", "hc_res")] \
        == [9, 12, 6]
    ones = lambda *shape: np.ones(shape)               # noqa: E731
    maps = {f"l{i}_hc_{s}_map": [ones(2, 4), ones(2, 4), ones(4, 4), ones(2),
                                 ones(2), ones(2, 2), ones(1), ones(1),
                                 ones(1)] for i in range(2) for s in "af"}
    change = {"l0_mla_qb": [ones(8, 2)], "l1_mla_qb": [ones(8, 2)],
              "l0_mla_kva": [ones(3, 4)], "l1_mla_kva": [ones(3, 4)], **maps}
    other = {k: [b.copy() for b in v] for k, v in change.items()}
    other["l0_mla_qb"][0][7] *= -1       # ONE head's rotary row, ONE block
    other["l0_hc_a_map"][2] *= -1        # a dead leaf: in no group
    other["l1_hc_f_map"][5] *= -1        # a dead leaf: in no group
    got = runner.group_cosines(change, other, groups)
    assert got["q_rotary_rows"] == pytest.approx(0.5)   # 2 of 8 numbers
    assert all(got[g] == pytest.approx(1.0) for g in (
        "hc_pre", "hc_post", "hc_res", "k_shared_rows"))
    other["l1_hc_a_map"][2] *= -1        # a live mix: 16 of its 42 numbers
    got = runner.group_cosines(change, other, groups)
    assert got["hc_res"] < 0.3 and got["hc_pre"] == pytest.approx(1.0)


def test_compared_rows_say_what_decided():
    import runners.xing_train as runner
    tol = ref.TOLERANCE["bf16"]
    rows = runner.compared(
        {"tolerance": tol, "logits_rel_l2": 0.004, "loss_rel": 1e-5,
         "res_err_rel": 0.002, "one_iteration_res_err_rel": 9.0,
         "stream_rel_l2": 0.002,
         "stream_grad_rel_l2": 0.01, "attention_rel_l2": 0.003,
         "attention_grad_rel_l2": 0.01, "lower_precision_rel_l2": 0.04,
         "lower_precision_loss_rel": 7e-4,
         "stream_control": {"stream_rel_l2": 0.02,
                            "stream_grad_rel_l2": 0.1},
         "attention_control": {"attention_rel_l2": 0.03,
                               "attention_grad_rel_l2": 0.05}},
        {"loss_rel": 1e-5, "update_norm_rel": 0.01, "update_cosine": 0.9,
         "mapping_norm_rel": 0.01, "mapping_unmoved": 0,
         "group_cosine": 0.98, "bias_wrong": 0, "bias_compared": 200,
         "bias_of": 256, "lower_precision_update_cosine": 0.7,
         "lower_precision_group_cosine": 0.8},
        (1.001, 0.97, 1.03))
    by = {}
    for r in rows:
        by.setdefault(r["name"], r)
    assert all(r["holds"] for r in rows if r["limit"] is not None)
    # under bf16 the first step's loss is a fact
    assert by["step_loss_rel"]["limit"] is None \
        and by["step_loss_rel"]["decides_correct"] is False
    decided = {r["name"] for r in rows if r["decides_correct"]}
    assert decided == {
        "first_loss_over_expected", "logits_rel_l2", "loss_rel",
        "res_err_rel", "stream_rel_l2", "stream_grad_rel_l2",
        "attention_rel_l2", "attention_grad_rel_l2",
        "update_norm_rel", "mapping_norm_rel", "mapping_unmoved",
        "update_cosine", "group_cosine", "bias_wrong",
        "bias_compared_share"}
    assert [r["name"] for r in rows if r["name"].startswith("control_")] == [
        "control_float8_logits_rel_l2", "control_one_iteration_res_err_rel",
        "control_float8_loss_rel", "control_float8_stream_rel_l2",
        "control_float8_stream_grad_rel_l2",
        "control_float8_attention_rel_l2",
        "control_float8_attention_grad_rel_l2",
        "control_float8_update_cosine", "control_float8_group_cosine"]


def test_first_loss_expectation():
    import runners.xing_train as runner
    model = {"vocab_size": V, "hidden_size": 3584}
    want = runner.expected_first_loss(CFG, model)
    assert want == pytest.approx(math.log(16384) + 0.7168)
    assert "10.42" in CFG["first_loss_why"] and round(want, 2) == 10.42


def test_runner_refuses_a_program_from_before_the_model(monkeypatch, capsys):
    """The driver hands the parent this PR's benchmark files: the runner
    looks in the program for what it needs and exits 2 at once, before jax
    is touched."""
    import runners.xing_train as runner
    from poseidon_tpu.models import zoo
    runner.refuse_old_program(CELL)           # this program: fine
    monkeypatch.delattr(zoo, "xing4")         # the parent's zoo
    with pytest.raises(SystemExit) as stop:
        runner.refuse_old_program(CELL)
    err = capsys.readouterr().err
    assert stop.value.code == 2 and "zoo.xing4" in err


def test_worst_rels_takes_a_fact_into_neither_worst():
    import runners.xing_train as runner
    want = {"out": np.ones(4), "d_x": np.ones(4), "alone_d_a": np.ones(1)}
    side = {"out": want["out"] * 1.01, "d_x": want["d_x"] * 1.02,
            "alone_d_a": want["alone_d_a"] * 2.0}
    fwd, bwd, rels = runner.worst_rels(side, want, ("out",))
    assert (fwd, bwd) == (pytest.approx(0.01), pytest.approx(0.02))
    assert rels["alone_d_a"] == pytest.approx(1.0)


@pytest.mark.parametrize("trace", [0, 1])
def test_cpu_tiny_rehearsal_of_the_xing_cell(trace):
    done = run_cell("--workload", CELL, "--seed", "3000000019", "--seconds",
                    "2", "--trace", str(trace), "--cpu-tiny")
    assert done.returncode == 0, done.stderr[-3000:]
    lines = done.stdout.strip().splitlines()
    line, facts = json.loads(lines[-1]), json.loads(lines[-2])["facts"]
    assert all(facts["checks"].values()), facts["checks"]
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] >= 4 and line["device"]["platform"] == "cpu"
    check = facts["reference"]
    for key in ("logits_rel_l2", "loss_rel"):
        assert check[key] < check["tolerance"][key], key
    assert check["logits_rel_l2"] < check["lower_precision_rel_l2"]
    assert len(check["route_flips"]) == 4
    assert check["res_err_program"] < 1e-3 > check["res_err_reference"]
    assert check["res_err_rel"] < check["tolerance"]["res_err_rel"]
    for key in ("stream_rel_l2", "stream_grad_rel_l2", "attention_rel_l2",
                "attention_grad_rel_l2"):
        assert check[key] < check["tolerance"][key], key
        assert check[key] < check[key.split("_")[0] + "_control"][key]
    # the biases' and the scales' gradients are held as ONE vector; what
    # each reads alone is a fact and decides nothing
    import runners.xing_train as runner
    rels = check["stream_rels"]
    assert sorted(k for k in rels if k.startswith("d_")) == [
        "d_phi_post", "d_phi_pre", "d_phi_res", "d_small", "d_x", "d_y"]
    assert sorted(k for k in rels if k.startswith("alone_")) == sorted(
        "alone_d_" + b for b in runner.MAP_BLOBS if not b.startswith("phi_"))
    assert check["stream_grad_rel_l2"] == max(
        v for k, v in rels.items() if k.startswith("d_"))
    step = facts["step_reference"]
    assert step["routers"] == [f"l{i}_router" for i in range(1, 5)]
    assert sorted(step["group_cosines"]) == [
        "hc_post", "hc_pre", "hc_res", "k_shared_rows", "q_rotary_rows"]
    # the read's and the mix's leaves are facts on a fresh model
    assert step["group_cosine"] == min(
        v for g, v in step["group_cosines"].items()
        if g not in ("hc_pre", "hc_res")) \
        >= step["tolerance"]["group_cosine"]
    assert step["dead_leaves"] == 9
    assert step["bias_wrong"] == 0 and step["bias_of"] == 4 * 64
    assert step["update_norm_rel"] < step["tolerance"]["update_norm_rel"]
    # the mappings: the write's leaves as one vector beside a limit, the
    # read's and the mix's facts; all 90 - 9 live leaves moved
    assert step["mapping_norm_rel"] == step["mapping_norm_rels"]["hc_post"] \
        < step["tolerance"]["mapping_norm_rel"]
    assert sorted(step["mapping_norm_rels"]) == ["hc_post", "hc_pre",
                                                 "hc_res"]
    assert (step["mapping_unmoved"], step["mapping_live"]) == (0, 81)
    assert check["one_iteration_res_err_rel"] \
        > check["tolerance"]["res_err_rel"]
    assert step["lower_precision_update_cosine"] < step["update_cosine"]
    if trace:
        assert facts["stalls"]["steps"] >= 4
    else:
        assert facts["stalls"] is None
    # what was compared, each beside its limit, LAST in the facts line
    assert list(facts)[-1] == "compared"
    decided = [r for r in facts["compared"] if r["decides_correct"]]
    assert {"logits_rel_l2", "loss_rel", "update_cosine", "group_cosine",
            "bias_compared_share"} <= {r["name"] for r in decided}
    assert all(r["holds"] for r in decided)
    assert facts["kernel_routes"] == [
        "attention=dense; d 24/8; k_pe rotated once, joined x32; yarn x64",
        "grouped_matmul=ragged_dot"]
    assert facts["remat_segments"] == DEPTH + 1
    assert sorted(facts["expert_share"]) == [
        f"l{i}_moe" for i in range(1, 5)]
    assert facts["first_loss"] == pytest.approx(
        facts["first_loss_expected"], rel=0.01)
    # a fresh model's stream: p = 1 / n, q = 1, the mix doubly stochastic
    stream = facts["stream"]
    assert stream["res_err_max"] < 1e-3
    assert stream["pre_mean"][0] == pytest.approx(0.25, abs=0.01) \
        and stream["post_mean"][0] == pytest.approx(1.0, abs=0.02)
    names = set(line["metrics"])
    if trace:
        # all of the cell's per-layer metrics but those that need a chip's
        # peaks, its memory statistics or its Pallas kernels
        assert names == declared("per_layer", CELL) - {
            "busy_flops_util", "peak_hbm_gb", "flash_attention_roofline",
            "held_moe_flops_util", "hc_stream_roofline"}
        m = {k: v["value"] for k, v in line["metrics"].items()}
        assert m["scope_coverage"] >= 95.0
        parts = ("attention_ms_per_step", "held_moe_ms_per_step",
                 "shared_expert_ms_per_step", "router_ms_per_step",
                 "head_ms_per_step", "hc_ms_per_step")
        assert all(m[k] > 0 for k in parts)
        assert m["attention_glue_ms_per_step"] \
            + m["mla_proj_ms_per_step"] \
            == pytest.approx(m["attention_ms_per_step"])  # all dense
        assert sum(m[k] for k in parts) \
            < m["fwd_ms_per_step"] + m["bwd_ms_per_step"]
        assert 0 < m["hc_map_ms_per_step"] < m["hc_ms_per_step"]
        assert m["recompute_ms_per_step"] < m["bwd_ms_per_step"]
        assert m["held_dropped_assignments"] == 0.0
        assert m["hc_res_err"] == stream["res_err_max"]
    else:
        assert names == declared("end_to_end", CELL) - {"mfu_required"}
        assert line["metrics"]["images_per_s_per_chip"]["value"] == \
            pytest.approx(facts["tokens_per_s_per_chip"] / facts["seq_len"])


# A fresh model cannot show most of these faults in what it computes: at
# their initial values (the configuration's assumed.e_init) the mix is
# doubly stochastic after ONE iteration by symmetry, the dynamic part is 1%
# of the logits and the streams are all but copies of one state; and a
# fresh model's attention scores are all but zero (std-0.02 projections), so
# its softmax is flat whatever multiplies the scores and wherever the
# positions turn. The runner's ``stream_check`` and ``attention_check`` hold
# the mechanisms on operands of their own, every leaf and operand drawn at
# random at order 1 (the mappings' scales among them), at the run's own
# sizes: that is where each fault has to show.
# a program with ONE fault planted, run through the harness's own entry at
# the rehearsal's sizes: {fault: (what is planted before run.py starts, the
# rows of ``compared`` of which at least one has to break)}
_PLANTED = {
    "sinkhorn_one_iter": ("""
from poseidon_tpu.ops import hyper
honest = hyper.sinkhorn
hyper.sinkhorn = lambda m, iters, eps: honest(m, 1, eps)
""", {"stream_rel_l2", "stream_grad_rel_l2"}),
    # ("columns normalised before rows", the reference's ``columns_first``,
    # is NOT planted here: both orders converge to the one doubly stochastic
    # scaling of the matrix, so after 20 iterations they differ by what the
    # loop leaves and no more, 2e-5 of the mix on a draw that converges and
    # under 1% on one that does not (a dominant diagonal of e^4 to e^6):
    # under every bf16 limit. tests/test_xing4.py shows it in f32.)
    # q = sigmoid(.), not 2 sigmoid(.)
    "write_without_two": ("""
from poseidon_tpu.ops import hyper
honest = hyper.hc_write
hyper.hc_write = lambda x, y, coef, n: honest(x, 0.5 * y, coef, n)
""", {"stream_rel_l2", "stream_grad_rel_l2"}),
    # the read's mapping from the state as it comes: the statistic left out
    "read_unnormalised": ("""
import jax, jax.numpy as jnp
from poseidon_tpu.ops import hyper
honest = hyper.hc_map
def faulty(x, w, n, iters, eps, clamp):
    coef, err, pre, post = honest(x, w, n, iters, eps, clamp)
    raw = jnp.einsum("bsc,jc->bsj", x.astype(jnp.float32),
                     w["phi_pre"].astype(jnp.float32))
    wrong = jax.nn.sigmoid(w["a_pre"] * raw + w["b_pre"])
    return jnp.concatenate([wrong, coef[..., n:]], -1), err, pre, post
hyper.hc_map = faulty
""", {"stream_rel_l2", "stream_grad_rel_l2"}),
    # the end takes ONE stream, not their sum (their MEAN is the same
    # function: the final norm takes the factor out, reference/xing4.py
    # SAME_FUNCTION)
    "end_first_stream": ("""
from poseidon_tpu.ops import hyper
hyper.hc_end = lambda x, n: x[..., :x.shape[-1] // n]
""", {"stream_rel_l2", "stream_grad_rel_l2"}),
    # NOT one of the issue's seven: the step leaves the mix's leaves as
    # they were (no gradient reaches them). On a fresh model neither size
    # nor direction of their change can be held (``FACT_GROUPS``); that
    # they moved can
    "mix_left_untrained": ("""
import jax
from poseidon_tpu.ops import hyper
honest = hyper.hc_map
hyper.hc_map = lambda x, w, *a: honest(x, dict(w, **{
    k: jax.lax.stop_gradient(w[k]) for k in ("phi_res", "a_res")}), *a)
""", {"mapping_unmoved"}),
    "plain_theta": ("""
from poseidon_tpu.models import transformer
honest = transformer.rope_frequencies
transformer.rope_frequencies = lambda rot, theta: honest(
    rot, getattr(theta, "theta", theta))
""", {"attention_rel_l2", "attention_grad_rel_l2"}),
    "scale_without_mscale": ("""
from poseidon_tpu.models import transformer
honest = transformer.rope_attention
def faulty(*args, **kwargs):
    kwargs["scale"] = None
    return honest(*args, **kwargs)
transformer.rope_attention = faulty
""", {"attention_rel_l2", "attention_grad_rel_l2"}),
}


def _run_planted(plant, tmp_path, seed):
    script = tmp_path / "run_planted.py"
    script.write_text(f"""
import os, runpy, sys
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["JAX_COMPILATION_CACHE_DIR"] = {str(tmp_path / "cache")!r}
sys.path[:0] = [{BENCH_DIR!r}, {ROOT!r}]
{plant}
runpy.run_path(os.path.join({BENCH_DIR!r}, "run.py"), run_name="__main__")
""")
    done = run_cell("--workload", CELL, "--seed", seed, "--seconds",
                    "1", "--trace", "0", "--cpu-tiny", script=str(script))
    assert done.returncode == 0, done.stderr[-3000:]
    lines = done.stdout.strip().splitlines()
    return json.loads(lines[-1]), json.loads(lines[-2])["facts"]


@pytest.mark.parametrize("fault", sorted(_PLANTED))
def test_a_planted_fault_reads_not_correct(fault, tmp_path):
    """Each mechanism this configuration brought is held by a limit: with
    its fault planted in the PROGRAM the harness has to print ``correct:
    false`` and name a row that broke."""
    plant, may_break = _PLANTED[fault]
    line, facts = _run_planted(plant, tmp_path, "3000000023")
    assert line["correct"] is False
    broke = {r["name"] for r in facts["compared"]
             if r["decides_correct"] and not r["holds"]}
    assert broke & may_break, (broke, facts["compared"])


def test_new_entries_follow_the_contract():
    """Found by NAME: a later PR's entries may follow them."""
    cell = next(w for w in BENCH["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) \
        == (NAME, "packed_ep8_hc4", 1)
    assert "layers 0, 2-5 of 40" in cell["why"] \
        and f"{BATCH} x 8192" in cell["why"] and "33 GB" in cell["why"]
    config = next(c for c in BENCH["configs"] if c["name"] == NAME)
    assert config["reduced"] == CFG["reduced"] == [
        "num_hidden_layers", "n_routed_experts", "vocab_size",
        "num_nextn_predict_layers"]
    assert config["source"] == CFG["source"] \
        and config["file"] == f"benchmark/configs/{NAME}.json"
    # ISSUE 63 made the room PR 60 lacked: its tokens a second are among them
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
    assert BATCH == 1 and "85%" in OWN["why"]
    assert len(BENCH["per_layer"]) <= 128 and len(BENCH["workloads"]) <= 24
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        assert len(f.read()) < 64 * 1024
