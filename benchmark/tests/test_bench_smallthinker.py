"""The SmallThinker cell's yardstick: ``flops_smallthinker`` against a count
by hand, the configuration against the catalog row and its copies, the
traffic file, each of the cell's readers on a hand-made ``layers`` dict (and
on a program without what it reads), the plain reference against NumPy at a
toy size, the runner's ``compared`` rows, its own ``step_check``'s reason,
its refusal of a program from before the model, and the ``--cpu-tiny``
rehearsal of ``smallthinker.e16of64.pack16k`` end to end."""

import importlib
import json
import math
import os
import types

import numpy as np
import pytest

import flops_smallthinker
from conftest import BENCH_DIR, ROOT
from layer_metrics import (attention_glue_ms_per_step,
                           st_gate_zero_share,
                           global_attention_ms_per_step,
                           global_flash_attention_roofline,
                           head_ms_per_step, held_assignment_share,
                           held_dropped_assignments,
                           held_load_max_over_mean,
                           held_moe_flops_util, held_moe_ms_per_step,
                           recompute_ms_per_step, router_ms_per_step,
                           tokens_per_s_per_chip,
                           window_attention_ms_per_step,
                           window_flash_attention_roofline,
                           window_visited_over_live_programs)
from test_bench_run import BENCH, STALLS, declared, run_cell

CELL = "smallthinker.e16of64.pack16k"
with open(os.path.join(BENCH_DIR, "configs", "smallthinker_21b.json")) as f:
    CFG = json.load(f)
with open(os.path.join(BENCH_DIR, "cells", CELL + ".json")) as f:
    OWN = json.load(f)
with open(os.path.join(BENCH_DIR, "traffic", "packed16k_ep4.json")) as f:
    TRAFFIC = json.load(f)
DEPTH, BATCH = CFG["num_hidden_layers"], OWN["batch_per_chip"]
V, S = 151936 // 8, 16384
_CATALOG_FILE = "/opt/skills/guides/model-configs/architectures.jsonl"
_rows = []
if os.path.exists(_CATALOG_FILE):
    with open(_CATALOG_FILE) as f:
        _rows = [json.loads(l) for l in f if l.strip()]
_LAYOUT = [0, 1, 1, 1] * 13
# config.json of PowerInfer/SmallThinker-21BA3B-Instruct as the
# model-configs catalog (architectures.jsonl) holds it
CATALOG = {
    "head_dim": 128, "hidden_size": 2560, "max_position_embeddings": 16384,
    "model_name": "smallthinker_21b_instruct", "moe_ffn_hidden_size": 768,
    "moe_num_active_primary_experts": 6, "moe_num_primary_experts": 64,
    "moe_primary_router_apply_softmax": True, "norm_topk_prob": True,
    "num_attention_heads": 28, "num_hidden_layers": 52,
    "num_key_value_heads": 4, "rms_norm_eps": 1e-06, "rope_layout": _LAYOUT,
    "rope_scaling": None, "rope_theta": 1500000,
    "sliding_window_layout": _LAYOUT, "sliding_window_size": 4096,
    "tie_word_embeddings": False, "vocab_size": 151936}
REDUCED = {"num_hidden_layers": 52, "moe_num_primary_experts": 64,
           "vocab_size": 151936}


def test_the_catalog_row_is_the_one_copied_here():
    if not _rows:
        pytest.skip("no catalog on this machine")
    row = next(r for r in _rows
               if r["name"] == "SmallThinker-21BA3B-Instruct")
    assert row["config"] == CATALOG and row["source_url"] == CFG["source"]


@pytest.mark.parametrize("key", sorted(CATALOG))
def test_configuration_equals_the_catalog_row(key):
    """Every key of the published config.json is in the file under the
    same name with the same value, but the three ``reduced`` names."""
    if key in REDUCED:
        assert key in CFG["reduced"] and CFG[key] != CATALOG[key] \
            and CFG["published"][key] == CATALOG[key] == REDUCED[key]
    else:
        assert CFG[key] == CATALOG[key]


def test_the_cut_is_the_issue_s():
    assert sorted(CFG["reduced"]) == sorted(REDUCED)
    assert (CFG["num_hidden_layers"], CFG["moe_num_primary_experts"],
            CFG["router_num_experts"], CFG["vocab_size"]) == (4, 16, 64, V)
    # one whole period at the published 1 : 3, global first
    assert CFG["layers_run"]["layout"] == _LAYOUT[:4] == [0, 1, 1, 1]
    assert "4 chips share each layer" in CFG["deployment"]
    assert "1,536 assignments" in CFG["what_the_cut_changes"]
    # no width is reduced
    for key in ("hidden_size", "moe_ffn_hidden_size", "head_dim",
                "num_attention_heads", "num_key_value_heads",
                "moe_num_active_primary_experts", "sliding_window_size"):
        assert key not in CFG["reduced"]
    assert CFG["assumed"]["aux_losses"]["balance_weight"] == 0.01 \
        and CFG["assumed"]["aux_losses"]["z_weight"] == 0.001
    assert set(CFG["assumed"]) >= {"a_block", "b_bias", "c_positions",
                                   "d_window", "e_router", "f_experts",
                                   "g_optimizer", "h_init", "packing"}


def test_configuration_arithmetic():
    """The parameter count the file states, by hand."""
    d, lq, lk, f = 2560, 28 * 128, 4 * 128, 768
    attention = 2 * d * lq + 2 * d * lk
    assert attention == 20_971_520
    layer = attention + 64 * d + 2 * d + 16 * 3 * d * f
    assert layer == 115_512_320
    total = 4 * layer + 2 * V * d + d
    assert total == 559_290_880
    assert "559,290,880" in CFG["reduced_how"]["num_hidden_layers"]
    assert round(total * 16 / 1e9, 2) == 8.95


@pytest.mark.parametrize("part,macs", [
    ("projections", 4 * 20_971_520),
    # the band: 4096 x 4097 / 2 + 12288 x 4096 pairs a sequence, two
    # products at the query width, three layers
    ("window_attention", 3 * 2 * 3584 * 58_722_304 // 16384),
    ("global_attention", 2 * 3584 * 8192),
    ("router", 4 * 2560 * 64),
    ("experts", 4 * 6 * 5_898_240 // 4),
    ("head", 2560 * 18992)])
def test_required_macs_against_hand_counts(part, macs):
    assert flops_smallthinker.required_macs_per_token(CFG, S)[part] == macs


def test_required_flops_and_shares():
    macs = flops_smallthinker.required_macs_per_token(CFG, S)
    total = sum(macs.values())
    assert total == 304_343_680                        # 304.3M a token
    flops = flops_smallthinker.required_flops_per_token(CFG, S)
    assert flops["total"] == 6 * total
    assert round(flops["total"] * S / 1e12, 1) == 29.9     # TFLOP a step
    share = {k: v / total for k, v in macs.items()}
    assert round(100 * share["projections"], 1) == 27.6
    assert round(100 * (share["window_attention"]
                        + share["global_attention"]), 1) == 44.6
    assert round(100 * share["experts"], 1) == 11.6
    assert round(100 * share["head"], 1) == 16.0
    # the band is 44% of the causal triangle at S 16,384, W 4096
    band = flops_smallthinker.key_positions(S, 4096)
    assert band == 58_722_304 and round(100 * band / (S * S // 2)) == 44
    assert flops_smallthinker.key_positions(4096, 4096) == 4096 * 4096 // 2
    assert flops_smallthinker.expert_flops_per_assignment(CFG) \
        == 6 * 5_898_240
    flash = flops_smallthinker.flash_attention_step(CFG, 1, S)
    assert flash["window"]["flops"] == 3 * 6 * 2 * 58_722_304 * 3584
    assert flash["global"]["flops"] == 6 * 2 * (S * S // 2) * 3584
    assert flash["global"]["bytes"] == 6 * S * 2 * (3584 + 512)


def test_copies_match_their_originals():
    for copy, original in CFG["copied_from"].items():
        with open(os.path.join(BENCH_DIR, copy)) as a, \
                open(os.path.join(ROOT, original)) as b:
            assert a.read() == b.read(), copy
    with open(os.path.join(BENCH_DIR, CFG["net"])) as f:
        net = f.read()
    assert CFG["paths"]["train_source"] in net
    # every size the rehearsal cuts is in the prototxt
    for field, cuts in CFG["cpu_tiny"]["prototxt_fields"].items():
        for size in cuts:
            assert f"{field}: {size}\n" in net, (field, size)


def test_traffic_is_packed8k_ep8_s_at_the_published_positions():
    with open(os.path.join(BENCH_DIR, "traffic", "packed8k_ep8.json")) as f:
        theirs = json.load(f)
    assert TRAFFIC["seq_len"] == CFG["max_position_embeddings"] == S
    assert TRAFFIC["runner"] == "smallthinker_train"
    same = ("feed", "precision", "argv", "display", "steps_in_file",
            "trace_steps", "settle_displays", "window")
    assert {k: TRAFFIC[k] for k in same} == {k: theirs[k] for k in same}
    docs = TRAFFIC["documents"]
    assert (docs["doc_len_median"], docs["doc_len_sigma"],
            docs["doc_len_min"], docs["doc_len_max"], docs["zipf_exponent"],
            docs["end_of_text_id"]) == (512, 1.2, 16, S, 1.0, 0)
    assert "--bf16" in TRAFFIC["argv"] \
        and "--remat=/l\\d+_/,/lm_/" in TRAFFIC["argv"]


# --------------------------------------------------------------------------- #
# the cell's readers on a hand-made run
# --------------------------------------------------------------------------- #
#   two steps; times in ns
OPS = [("fusion q.1 bf16[8]", 0.0, 10.0),               # l0_q fwd
       ("pallas-call flash.2 bf16[8]", 10.0, 40.0),     # l0_attn_global bwd
       ("pallas-call flash.3 bf16[8]", 50.0, 20.0),     # l1_attn_window bwd
       ("fusion repeat.4 bf16[8]", 70.0, 4.0),          # l1_attn_window fwd
       ("fusion moe.5 bf16[8]", 80.0, 30.0),            # l1_moe bwd
       ("fusion router.6 f32[8]", 110.0, 8.0),          # l1_router fwd
       ("fusion head.7 bf16[8]", 120.0, 12.0),          # lm_head bwd
       ("fusion nll.8 f32[8]", 132.0, 2.0),             # lm_nll fwd
       ("fusion merge.9 bf16[8]", 134.0, 6.0)]          # l0_attn_global fwd
SCOPES = {"ops": {"q.1": "l0_q|fwd", "flash.2": "l0_attn_global|bwd",
                  "flash.3": "l1_attn_window|bwd",
                  "repeat.4": "l1_attn_window|fwd", "moe.5": "l1_moe|bwd",
                  "router.6": "l1_router|fwd", "head.7": "lm_head|bwd",
                  "nll.8": "lm_nll|fwd", "merge.9": "l0_attn_global|fwd"},
          "recomputed": ["flash.3", "repeat.4"],
          "types": {"l0_q": "INNER_PRODUCT", "l0_attn_global": "ATTENTION",
                    "l1_attn_window": "ATTENTION", "l1_moe": "MOE",
                    "l1_router": "MOE_ROUTER", "lm_head": "INNER_PRODUCT",
                    "lm_nll": "SOFTMAX_NLL"}}
PEAKS = {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e11}
ROUTES = ["attention=pallas_flash (fwd 1024x1024 136/256, dq 1024x1024 "
          "136/256, dkv 1024x1024 136/256; block_q x block_k, live/visited "
          "programs a head); 4 kv heads repeated x7; no positions",
          "attention=pallas_flash (fwd 1024x1024 70/80, dq 1024x1024 70/80, "
          "dkv 1024x1024 70/80; block_q x block_k, live/visited programs a "
          "head; window 4096: the band's grid); 4 kv heads repeated x7",
          "grouped_matmul=ragged_dot; held rows: chunks of 24576 of 98304; "
          "act=relu"]


def small_run(scopes=SCOPES, lm=True):
    run = {"trace": {"steps": 2, "spans": [], "async": {},
                     "devices": {"0": OPS}},
           "steps": 10, "batch_per_chip": 1, "window_s": 4.0,
           "peak_flops_per_s": PEAKS["bf16_flops_per_s"],
           "stats": {"sections": {"step_scopes": scopes} if scopes else {}}}
    if lm:
        run["lm"] = {"seq_len": S,
                     "scopes": CFG["scopes"], "peaks": PEAKS,
                     "flash_per_step": {
                         "window": {"flops": 2e3, "bytes": 100.0},
                         "global": {"flops": 1e3, "bytes": 500.0}},
                     "flops_per_assignment": 10.0,
                     "assignments_per_step": 1000,
                     "kernel_routes": ROUTES,
                     "held_share": [0.2, 0.3, 0.4],
                     "traced_held_share": [0.25],
                     "gate_zero_share": [0.5, 0.54],
                     "expert_load": [1.2, 1.4], "dropped": [0.0, 0.0]}
    return run


READERS = [
    (window_attention_ms_per_step, 12e-6),       # (20 + 4) ns / 2
    (global_attention_ms_per_step, 23e-6),       # (40 + 6) / 2
    # flops-bound: 2e3 / 1e12 = 2 ns against 10 ns of kernel a step
    (window_flash_attention_roofline, 100 * 2e-9 / 10e-9),
    # bytes-bound: 500 / 1e11 = 5 ns against 20 ns of kernel a step
    (global_flash_attention_roofline, 100 * 5e-9 / 20e-9),
    (window_visited_over_live_programs, 80 / 70),
    (attention_glue_ms_per_step, 5e-6),          # (4 + 6) / 2
    (router_ms_per_step, 4e-6),
    (held_moe_ms_per_step, 15e-6),
    # the TRACED steps' 0.25 x 1000 assignments x 10 FLOPs over 15 ns x 1e12
    (held_moe_flops_util, 100 * 2.5e3 / (15e-9 * 1e12)),
    (held_assignment_share, 30.0),
    (held_load_max_over_mean, 1.3),
    (held_dropped_assignments, 0.0),
    (st_gate_zero_share, 52.0),
    (head_ms_per_step, 7e-6),                    # (12 + 2) / 2
    (recompute_ms_per_step, 12e-6),              # (20 + 4) ns / 2
    (tokens_per_s_per_chip, 10 * 1 * S / 4.0),
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
    assert reader.reduce({}) is None


def test_the_flash_kernels_floor_is_their_flops_at_the_cell_s_shapes():
    """What the roofline shares divide: at the cell's shapes the required
    FLOPs over the v5e's peak are the floor of the kernels' time (the band:
    7.6 TFLOP a step over 197 TFLOP/s = 38 ms against 0.5 ms of bytes), so a
    share reads 100 only at the matrix peak itself."""
    with open(os.path.join(BENCH_DIR, "peaks.json")) as f:
        v5e = json.load(f)["TPU v5 lite"]
    flash = flops_smallthinker.flash_attention_step(CFG, 1, S)
    for kind in ("window", "global"):
        flop_s = flash[kind]["flops"] / v5e["bf16_flops_per_s"]
        byte_s = flash[kind]["bytes"] / v5e["hbm_bytes_per_s"]
        assert flop_s > 10 * byte_s and 0.02 < flop_s < 0.06


# --------------------------------------------------------------------------- #
# the reference, the runner
# --------------------------------------------------------------------------- #

def test_reference_imports_nothing_of_the_program():
    with open(os.path.join(BENCH_DIR, "reference", "smallthinker.py")) as f:
        text = f.read()
    assert "poseidon" not in text.split('"""', 2)[2]
    assert "ragged" not in text and "argsort" not in text


def test_reference_router_and_reglu_against_numpy():
    """One layer at a toy size in NumPy loops: top-k of the logits, the
    softmax over the chosen, ReGLU experts (absent ones left out), the
    balance and z losses over all E."""
    import jax
    ref = importlib.import_module("reference.smallthinker")
    rs = np.random.RandomState(0)
    n, s, d, e, k, f, h, g, dh = 1, 8, 8, 4, 2, 6, 2, 1, 4
    cfg = {"num_hidden_layers": 1, "num_attention_heads": h,
           "num_key_value_heads": g, "head_dim": dh, "num_experts": e,
           "num_experts_per_tok": k, "sliding_window_size": 4,
           "sliding_window_layout": [0], "rms_norm_eps": 1e-6,
           "rope_theta": 1.5e6, "balance_weight": 0.01, "z_weight": 0.001}
    held = [1, 2]
    w = {"embed": [rs.randn(16, d)], "l0_attn_norm": [np.ones(d)],
         "l0_router": [rs.randn(e, d)], "l0_q": [rs.randn(h * dh, d) * .3],
         "l0_k": [rs.randn(g * dh, d) * .3], "l0_v": [rs.randn(g * dh, d)],
         "l0_o": [rs.randn(d, h * dh) * .3], "l0_ffn_norm": [np.ones(d)],
         "l0_moe": [rs.randn(2, f, d), rs.randn(2, f, d), rs.randn(2, d, f)],
         "final_norm": [np.ones(d)], "lm_head": [rs.randn(16, d)]}
    w = {a: [np.asarray(b, np.float32) for b in bs] for a, bs in w.items()}
    tokens = rs.randint(0, 16, (n, s))
    out = jax.device_get(ref.forward(cfg, w, tokens, held=held))

    def norm(x):
        return x / np.sqrt((x * x).mean(-1, keepdims=True) + 1e-6)

    x = w["embed"][0][tokens][0].astype(np.float64)
    a = norm(x)
    r = a @ w["l0_router"][0].T.astype(np.float64)
    q = (a @ w["l0_q"][0].T).reshape(s, h, dh)
    kk = (a @ w["l0_k"][0].T).reshape(s, g, dh)
    v = (a @ w["l0_v"][0].T).reshape(s, g, dh)
    att = np.zeros((s, h, dh))
    for t in range(s):                      # global, no positions
        for j in range(h):
            sc = kk[:t + 1, 0] @ q[t, j] / np.sqrt(dh)
            p = np.exp(sc - sc.max())
            att[t, j] = (p / p.sum()) @ v[:t + 1, 0]
    hid = x + att.reshape(s, -1) @ w["l0_o"][0].T
    u = norm(hid)
    routed, counts = np.zeros((s, d)), np.zeros(e)
    for t in range(s):
        top = np.argsort(-r[t], kind="stable")[:k]
        p = np.exp(r[t, top] - r[t, top].max())
        p /= p.sum()
        for which, weight in zip(top, p):
            counts[which] += 1
            if which in held:
                row = held.index(which)
                gate, up, dn = (w["l0_moe"][i][row] for i in range(3))
                routed[t] += weight * (
                    (np.maximum(u[t] @ gate.T, 0) * (u[t] @ up.T)) @ dn.T)
    np.testing.assert_allclose(out["routed"][0][0], routed, rtol=2e-4,
                               atol=2e-5)
    np.testing.assert_array_equal(out["counts"][0], counts)
    probs = np.exp(r - r.max(-1, keepdims=True))
    probs /= probs.sum(-1, keepdims=True)
    assert out["balance"][0] == pytest.approx(
        e * ((counts / s) * probs.mean(0)).sum(), rel=1e-5)
    lse = np.log(np.exp(r).sum(-1))
    assert out["z"][0] == pytest.approx((lse ** 2).mean(), rel=1e-5)


def test_expected_first_loss_counts_both_router_losses():
    import runners.smallthinker_train as runner
    model = {k: CFG[k] for k in runner.MODEL_KEYS}
    want = math.log(V) + 0.512 + 4 * (0.01 * 6 + 0.001
                                      * (math.log(64) + 0.512) ** 2)
    assert runner.expected_first_loss(CFG, model) == pytest.approx(want)
    assert 10.6 < want < 10.8 and "10.69" in CFG["first_loss_why"]
    assert CFG["first_loss_band"] == [0.97, 1.08]


def test_compared_rows_say_what_decided():
    import runners.smallthinker_train as runner
    tol = {"logits_rel_l2": 8e-3, "loss_rel": 2.5e-4, "step_loss_rel": None,
           "update_norm_rel": 0.1, "update_cosine": 0.93, "leaf_cosine": 0.8}
    rows = runner.compared(
        {"tolerance": tol, "loss_program": 10.001, "loss_reference": 10.0,
         "logits_rel_l2": 4e-3, "lower_precision_rel_l2": 1.5e-2},
        {"loss_rel": 3e-5, "update_norm_rel": 0.01, "update_cosine": 0.985,
         "leaf_cosine_min": 0.905, "lower_precision_update_cosine": 0.97})
    by = {r["name"]: r for r in rows}
    assert [r["name"] for r in rows if r["decides_correct"]] == [
        "logits_rel_l2", "loss_rel", "update_norm_rel", "update_cosine",
        "leaf_cosine_min"]
    assert all(r["holds"] for r in rows if r["decides_correct"])
    assert by["step_loss_rel"]["holds"] is None       # a fact under bf16
    assert by["loss_rel"]["value"] == pytest.approx(1e-4, rel=1e-3)
    # the one control row is the one a float8 step breaks on the chip; its
    # update's cosine reads no lower than bf16's and is a row no more
    assert [r["name"] for r in rows if r["name"].startswith("control_")] == [
        "control_float8_logits_rel_l2"]
    assert all(r["holds"] for r in rows if r["name"].startswith("control_"))


def _changes(rng, flip=(), still=(), noisy=()):
    """Two steps' changes of four leaves as Adam's first step makes them
    (the rate times a sign): a held stack of 2**22 numbers, two matrices of
    2**16 (a router's among them) and a norm's gain of 64. ``noisy``: a
    share of a leaf's signs drawn anew on one side."""
    sizes = {"l0_moe": 2 ** 22, "l0_router": 2 ** 16, "l0_q": 2 ** 16,
             "l0_attn_norm": 64}
    want = {k: [1e-3 * np.sign(rng.standard_normal(n)).astype(np.float32)]
            for k, n in sizes.items()}
    got = {k: [v[0].copy()] for k, v in want.items()}
    for k in flip:
        got[k][0] *= -1
    for k in still:
        got[k][0] *= 0
    for k, share in dict(noisy).items():
        redrawn = rng.random(sizes[k]) < share
        got[k][0][redrawn] *= np.sign(rng.standard_normal(redrawn.sum()))
    return got, want


@pytest.mark.parametrize("fault, breaks", [
    # sound: a router's matrix reads 0.905 (PERF.md 53a's seed), the rest 0.98
    ({"noisy": (("l0_router", 0.095), ("l0_moe", 0.02), ("l0_q", 0.02))},
     set()),
    ({"flip": ("l0_router",)}, {"leaf_cosine"}),     # a leaf of the wrong sign
    ({"flip": ("l0_moe",)}, {"leaf_cosine", "cosine"}),
    ({"still": ("l0_q",)}, {"leaf_cosine", "norm_rel"}),    # a leaf not moved
    ({"still": ("l0_attn_norm",)}, {"norm_rel"}),   # under ``cosine_from``
    ({"still": ("l0_moe", "l0_router", "l0_q", "l0_attn_norm")},
     {"leaf_cosine", "cosine", "norm_rel"}),        # the state left unchanged
])
def test_the_step_comparison_takes_the_whole_update_and_the_least_leaf(
        fault, breaks):
    import runners.smallthinker_train as runner
    import reference.smallthinker as ref
    tol = ref.TOLERANCE["bf16"]
    got, want = _changes(np.random.default_rng(63), **fault)
    read = runner.compare_changes(got, want, tol["cosine_from"])
    assert read["leaves"] == 4
    broke = {k for k, holds in (
        ("cosine", read["cosine"] >= tol["update_cosine"]),
        ("leaf_cosine", read["leaf_cosine"] >= tol["leaf_cosine"]),
        ("norm_rel", read["norm_rel"] <= tol["update_norm_rel"]))
        if not holds}
    assert broke == breaks, read
    if not breaks:
        # the whole update is the steady number: the noisy small leaf is a
        # sixty-sixth of it
        assert read["leaf_cosine"] == pytest.approx(0.905, abs=0.01)
        assert read["cosine"] == pytest.approx(0.979, abs=0.005)
        assert read["worst_by_cosine"][0]["leaf"] == "l0_router[0]"


def test_runner_refuses_a_program_from_before_the_model(monkeypatch, capsys):
    """The driver hands the parent this PR's benchmark files: the runner
    looks in the program for what it needs and exits 2 at once, before jax
    is touched."""
    import runners.smallthinker_train as runner
    from poseidon_tpu.models import moe
    from poseidon_tpu.proto import messages
    runner.refuse_old_program(CELL)           # this program: fine

    import runners.trinity_train as trinity

    def parent_moe():                 # every field but this PR's
        return types.SimpleNamespace(**{f: 0 for f in trinity.MOE_FIELDS})
    monkeypatch.setattr(messages, "MoEParameter", parent_moe)
    monkeypatch.delattr(moe, "softmax_router")
    with pytest.raises(SystemExit) as stop:
        runner.refuse_old_program(CELL)
    err = capsys.readouterr().err
    assert stop.value.code == 2 and "moe_param.activation" in err \
        and "softmax_router" in err


def test_runner_reuses_trinity_s_check_and_leaves_it_as_it_was():
    import runners.smallthinker_train as runner
    import runners.trinity_train as trinity
    theirs = trinity.reference_sizes
    with runner.smallthinker_sizes():
        assert trinity.reference_sizes is runner.reference_sizes
    assert trinity.reference_sizes is theirs
    model = {k: CFG[k] for k in runner.MODEL_KEYS}
    sizes = runner.reference_sizes(CFG, model)
    assert sizes["sliding_window_layout"] == [0, 1, 1, 1] \
        and sizes["num_experts"] == 64 and sizes["num_experts_per_tok"] == 6 \
        and sizes["balance_weight"] == 0.01 and sizes["rope_theta"] == 1.5e6
    # its own step_check: every router has ONE blob, its matrix, which
    # trinity_train.step_check would take for a selection bias and skip
    with open(os.path.join(BENCH_DIR, CFG["net"])) as f:
        assert "bias_next" not in f.read()
    assert runner.step_check is not trinity.step_check


@pytest.mark.parametrize("trace", [0, 1])
def test_cpu_tiny_rehearsal_of_the_smallthinker_cell(trace):
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
    assert len(check["route_flips"]) == DEPTH        # one count a layer
    step = facts["step_reference"]
    # embed, head, final norm; a layer: 2 norms, q k v o, router, 3 stacks
    assert step["leaves_compared"] == 3 + DEPTH * 10
    assert step["update_norm_rel"] < step["tolerance"]["update_norm_rel"]
    assert step["leaf_cosine_min"] <= step["update_cosine"] <= 1.0
    assert step["lower_precision_leaf_cosine_min"] < step["leaf_cosine_min"]
    # what was compared, each beside its limit, LAST in the facts line
    assert list(facts)[-1] == "compared"
    decided = [r for r in facts["compared"] if r["decides_correct"]]
    assert len(decided) >= 4 and all(r["holds"] for r in decided)
    assert facts["kernel_routes"] == [
        "attention=dense; 4 kv heads repeated x7; no positions",
        "attention=dense; 4 kv heads repeated x7; window 16 as a dense mask",
        "grouped_matmul=ragged_dot; act=relu"]
    assert facts["remat_segments"] == DEPTH + 1
    assert facts["expert_share"]["l1_moe"] == {
        "held_first": 0, "num_held": 16, "router_num_experts": 64}
    assert facts["first_loss"] == pytest.approx(
        facts["first_loss_expected"], rel=0.02)
    share = facts["held_assignment_share"]
    assert 0.0 < share["min"] <= share["max"] < 1.0
    assert sorted(share["per_layer"]) == [f"l{i}_held_share"
                                          for i in range(DEPTH)]
    zero = facts["gate_zero_share"]
    assert all(0.3 < v < 0.7 for v in zero["per_display"])
    assert sorted(zero["per_layer_last_display"]) == [
        f"l{i}_gate_zero_share" for i in range(DEPTH)]
    assert 5.0 < facts["router_losses"]["balance_first_display"][0] < 8.0
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
                 "global_attention_ms_per_step", "router_ms_per_step",
                 "held_moe_ms_per_step", "head_ms_per_step")
        assert all(m[k] > 0 for k in parts)
        # on the CPU the whole ATTENTION layers are glue (no Pallas call)
        assert m["attention_glue_ms_per_step"] == pytest.approx(
            m["window_attention_ms_per_step"]
            + m["global_attention_ms_per_step"])
        assert sum(m[k] for k in parts) \
            < m["fwd_ms_per_step"] + m["bwd_ms_per_step"]
        assert m["held_dropped_assignments"] == 0.0
        assert m["st_gate_zero_share"] == pytest.approx(
            100 * sum(zero["per_display"]) / len(zero["per_display"]))
        assert m["held_assignment_share"] == pytest.approx(
            100 * share["mean"])
    else:
        assert names == declared("end_to_end", CELL) - {"mfu_required"}
        assert line["metrics"]["images_per_s_per_chip"]["value"] == \
            pytest.approx(facts["tokens_per_s_per_chip"] / facts["seq_len"])


def test_new_entries_follow_the_contract():
    cell = next(w for w in BENCH["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) \
        == ("smallthinker_21b", "packed16k_ep4", 1)
    assert "layers 0-3 of 52" in cell["why"] \
        and f"{BATCH} x 16384" in cell["why"]
    config = next(c for c in BENCH["configs"]
                  if c["name"] == "smallthinker_21b")
    assert config["reduced"] == CFG["reduced"] == [
        "num_hidden_layers", "moe_num_primary_experts", "vocab_size"]
    assert config["source"] == CFG["source"] \
        and config["file"] == "benchmark/configs/smallthinker_21b.json"
    # the entries that list the cell, by membership; the stall ledger's
    # seven are test_bench_stalls.py's
    mine = [m for m in BENCH["per_layer"]
            if CELL in m.get("workloads", ()) and m["name"] not in STALLS]
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
        assert m["moves"] == "mfu_required" and m["layer"] in layers
        if m["name"].endswith("_roofline"):
            assert m["unit"] == "%" and m["better"] == "higher"
    assert "85%" in OWN["why"] and BATCH == 1
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        assert len(f.read()) < 64 * 1024
