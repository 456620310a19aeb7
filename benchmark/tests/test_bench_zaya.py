"""The ZAYA cell's yardstick: ``flops_zaya`` against hand counts, the
configuration against the catalog row and its copies, the traffic file, each
of the cell's twelve readers on a hand-made ``layers`` dict (and on a program
without what it reads), the plain reference's router, balancing rule and
expert shares against NumPy, the runner's refusal of a program from before
the model, and the ``--cpu-tiny`` rehearsal of ``zaya1.e8of16.pack8k`` end
to end."""

import json
import os
import types

import numpy as np
import pytest

import flops_zaya
import tokengen
from conftest import BENCH_DIR, ROOT
from layer_metrics import (flash_attention_roofline, cca_mix_ms_per_step,
                           cca_ms_per_step, held_assignment_share,
                           held_dropped_assignments,
                           held_load_max_over_mean,
                           held_moe_flops_util, held_moe_ms_per_step,
                           router_ms_per_step, head_ms_per_step,
                           recompute_ms_per_step,
                           tokens_per_s_per_chip)
from test_bench_run import BENCH, STALLS, declared, run_cell

CELL = "zaya1.e8of16.pack8k"
with open(os.path.join(BENCH_DIR, "configs", "zaya1_8b.json")) as f:
    CFG = json.load(f)
with open(os.path.join(BENCH_DIR, "cells", CELL + ".json")) as f:
    OWN = json.load(f)
DEPTH, BATCH = CFG["num_hidden_layers"], OWN["batch_per_chip"]
V = 262272 // 8

# config.json of Zyphra/ZAYA1-8B as the model-configs catalog
# (architectures.jsonl) holds it; layer_types is 40 x "hybrid"
CATALOG = {
    "attention_bias": False, "cca_time0": 2, "cca_time1": 2, "head_dim": 128,
    "hidden_act": "silu", "hidden_size": 2048,
    "layer_types": ["hybrid"] * 40, "lm_head_bias": False,
    "max_position_embeddings": 131072, "model_type": "zaya",
    "moe_intermediate_size": 2048, "num_attention_heads": 8,
    "num_experts": 16, "num_experts_per_tok": 1, "num_hidden_layers": 40,
    "num_key_value_heads": 2, "partial_rotary_factor": 0.5,
    "rms_norm_eps": 1e-05,
    "rope_parameters": {
        "hybrid": {"partial_rotary_factor": 0.5, "rope_theta": 5000000,
                   "rope_type": "default"},
        "hybrid_sliding": {"partial_rotary_factor": 0.5, "rope_theta": 10000,
                           "rope_type": "default"},
        "rope_type": "default"},
    "router_hidden_size": 256, "sliding_window": None,
    "tie_word_embeddings": True, "vocab_size": 262272}
REDUCED = {"num_hidden_layers": 40, "num_experts": 16, "vocab_size": 262272}


@pytest.mark.parametrize("key", sorted(CATALOG))
def test_configuration_equals_the_catalog_row(key):
    """Every key as published; the depth, the experts HELD and the rows of
    the tied table, and only those, are reduced, and no width among them."""
    if key in REDUCED:
        assert sorted(CFG["reduced"]) == sorted(REDUCED)
        assert CFG["published"][key] == CATALOG[key] == REDUCED[key]
        assert CFG[key] < CATALOG[key]
    else:
        assert CFG[key] == CATALOG[key]


def test_the_cut_is_the_issue_s():
    assert DEPTH >= 4 and CFG["num_experts"] == 8 \
        and CFG["router_num_experts"] == 16 and CFG["vocab_size"] == V == 32784
    for section in ("assumed", "departures", "deployment", "reduced_how"):
        assert CFG[section]
    for key in ("cca_convolutions", "cca_value_shift", "cca_qk_mean",
                "cca_qk_norm", "router", "balancing", "not_modelled",
                "optimizer", "initialisation", "packing"):
        assert CFG["assumed"][key], key
    assert "0.001" in CFG["assumed"]["balancing"]
    assert sorted(CFG["reduced_how"]) == sorted(REDUCED)


def test_configuration_arithmetic():
    """The sizes the configuration file and ISSUE 31 argue from."""
    d, f, r, e = 2048, 2048, 256, 16
    expert = 3 * d * f
    proj = d * (1024 + 256 + 128 + 128) + 1024 * d
    conv = 2 * 1280 + 1280 + 2 * 10 * 128 * 128 + 1280
    router = d * r + r + 2 * r * r + e * r + e
    layer = 8 * expert + proj + conv + router + 2 * d + 2
    assert expert == 12_582_912 and proj == 5_242_880
    assert round(conv / 1e6, 2) == 0.33 and round(router / 1e6, 2) == 0.66
    assert round(layer / 1e6, 1) == 106.9
    table = V * d
    assert round(table / 1e6, 1) == 67.1
    total = {n: table + d + n * layer - r for n in (4, 5, 6, 7)}
    assert [round(16 * total[n] / 1e9, 1) for n in (4, 5, 6, 7)] \
        == [7.9, 9.6, 11.3, 13.0]
    assert f"{total[DEPTH]:,} parameters" in \
        CFG["reduced_how"]["num_hidden_layers"]
    whole = 40 * (16 * expert + layer - 8 * expert) + 262272 * d
    assert round(whole / 1e9, 2) == 8.84 and round(16 * whole / 1e9) == 141


@pytest.mark.parametrize("part,macs", [
    ("projections", DEPTH * 5_242_880),
    ("conv", DEPTH * 327_680),
    ("attention", DEPTH * 8192 * 1024),
    ("router", DEPTH * 659_456),
    ("experts", DEPTH * 12_582_912 // 2),
    ("head", 2048 * V)])
def test_required_macs_against_hand_counts(part, macs):
    assert flops_zaya.required_macs_per_token(CFG, 8192)[part] == macs


def test_required_flops_and_shares():
    """ISSUE 31's hand count at six layers: 192.6M MACs = 1.156 GFLOP a
    token, 65% in the layers, 35% in the head."""
    six = flops_zaya.required_flops_per_token(
        {**CFG, "num_hidden_layers": 6}, 8192)
    assert round(six["total"] / 6 / 1e6, 1) == 192.6
    assert round(six["total"] / 1e9, 3) == 1.156
    assert round(100 * six["head"] / six["total"]) == 35
    one = flops_zaya.required_flops_per_token(CFG, 8192)
    assert one["total"] == 6 * (DEPTH * 20_910_080 + 2048 * V)
    # the attention part IS what the flash kernels are asked for
    flash = flops_zaya.flash_attention_step(CFG, BATCH, 8192)
    assert flash["flops"] == one["attention"] * 8192 * BATCH
    assert flash["bytes"] == DEPTH * BATCH * 8192 * 2 * (6 * 1024 + 6 * 256)
    assert flops_zaya.expert_flops_per_assignment(CFG) == 6 * 12_582_912
    # an even split: half a token's expert, whatever the step routed
    assert one["experts"] == DEPTH * 6 * 12_582_912 // 2


def test_copies_match_their_originals():
    for copy, original in CFG["copied_from"].items():
        with open(os.path.join(BENCH_DIR, copy)) as a, \
                open(os.path.join(ROOT, original)) as b:
            assert a.read() == b.read(), (copy, original)
    with open(os.path.join(BENCH_DIR, CFG["net"])) as f:
        net = f.read()
    assert net.count("type: ATTENTION") == net.count("type: MOE\n") \
        == net.count("type: MOE_ROUTER") == net.count("type: CCA_CONV") \
        == DEPTH
    assert net.count('name: "tok_w"') == 2
    assert net.count("num_held: 8") == DEPTH \
        and net.count("num_experts: 16") == 2 * DEPTH
    assert net.count("num_kv_heads: 2") == 4 * DEPTH \
        and net.count("rotary_dims: 64") == DEPTH


def test_traffic_is_packed8k_over_the_slice():
    with open(os.path.join(BENCH_DIR, "traffic", "packed8k_slice.json")) as f:
        traffic = json.load(f)
    mix = traffic["documents"]
    assert (traffic["seq_len"], traffic["steps_in_file"], traffic["display"],
            traffic["runner"], traffic["precision"]) == \
        (8192, 8, 4, "zaya_train", "bf16")
    assert traffic["settle_displays"] == 4      # the window opens at step 24
    assert (mix["doc_len_median"], mix["doc_len_sigma"], mix["doc_len_min"],
            mix["doc_len_max"], mix["zipf_exponent"],
            mix["end_of_text_id"]) == (512, 1.2, 16, 8192, 1.0, 0)
    big = 3_000_000_019                      # over 2**31, as the driver's
    a = tokengen.packed_sequences(big, 2, 8192, V, mix)
    flat, nxt = a["data"].reshape(-1), a["label"].reshape(-1)
    assert np.array_equal(flat[1:], nxt[:-1])           # packed end to end
    assert 0 <= flat.min() and flat.max() < V           # ids over the slice
    gaps = np.diff(np.flatnonzero(flat == 0)) - 1       # whole documents
    assert 16 <= gaps.min() and gaps.max() <= 8192
    # the remat flags are the ones the example solver's header names
    with open(os.path.join(ROOT, "examples", "lm",
                           "zaya1_8b_solver.prototxt")) as f:
        header = f.read()
    flag = next(a for a in traffic["argv"] if a.startswith("--remat="))
    assert "--remat '" + flag[len("--remat="):] + "'" in header


# --------------------------------------------------------------------------- #
# the twelve readers on a hand-made run
# --------------------------------------------------------------------------- #
#   two steps; times in ns
OPS = [("fusion q.1 bf16[8]", 0.0, 10.0),              # l0_q fwd
       ("fusion conv.2 bf16[8]", 10.0, 6.0),           # l0_cca_conv bwd
       ("pallas-call flash.3 bf16[8]", 20.0, 20.0),    # l1_attn bwd
       ("fusion rope.4 bf16[8]", 40.0, 4.0),           # l1_attn fwd
       ("fusion moe.5 bf16[8]", 50.0, 30.0),           # l0_moe bwd
       ("fusion router.6 f32[8]", 80.0, 8.0),          # l1_router fwd
       ("fusion head.7 bf16[8]", 90.0, 12.0),          # lm_head bwd
       ("fusion nll.8 f32[8]", 102.0, 2.0),            # lm_nll fwd
       ("fusion norm.9 bf16[8]", 104.0, 2.0),          # l0_moe_norm fwd
       ("fusion embed.10 bf16[8]", 106.0, 2.0)]        # embed fwd
SCOPES = {"ops": {"q.1": "l0_q|fwd", "conv.2": "l0_cca_conv|bwd",
                  "flash.3": "l1_attn|bwd", "rope.4": "l1_attn|fwd",
                  "moe.5": "l0_moe|bwd", "router.6": "l1_router|fwd",
                  "head.7": "lm_head|bwd", "nll.8": "lm_nll|fwd",
                  "norm.9": "l0_moe_norm|fwd", "embed.10": "embed|fwd"},
          "recomputed": ["moe.5", "rope.4"],
          "types": {"l0_q": "INNER_PRODUCT", "l0_cca_conv": "CCA_CONV",
                    "l1_attn": "ATTENTION", "l0_moe": "MOE",
                    "l1_router": "MOE_ROUTER", "lm_head": "INNER_PRODUCT",
                    "lm_nll": "SOFTMAX_NLL", "l0_moe_norm": "RMS_NORM",
                    "embed": "EMBED"}}
PEAKS = {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e11}


def small_run(scopes=SCOPES, lm=True):
    run = {"trace": {"steps": 2, "spans": [], "async": {},
                     "devices": {"0": OPS}},
           "steps": 10, "batch_per_chip": 2, "window_s": 4.0,
           "peak_flops_per_s": PEAKS["bf16_flops_per_s"],
           "stats": {"sections": {"step_scopes": scopes} if scopes else {}}}
    if lm:
        run["lm"] = {"seq_len": 8192, "scopes": CFG["scopes"],
                     "peaks": PEAKS,
                     "flash_per_step": {"flops": 2e3, "bytes": 100.0},
                     "flops_per_assignment": 10.0,
                     "assignments_per_step": 1000,
                     "held_share": [0.4, 0.5, 0.6],
                     "traced_held_share": [0.25],
                     "expert_load": [1.2, 1.4], "dropped": [0.0, 0.0]}
    return run


@pytest.mark.parametrize("reader, want", [
    (cca_ms_per_step, 20e-6),                 # (10 + 6 + 20 + 4) ns / 2
    (cca_mix_ms_per_step, 5e-6),              # conv 6 + attention glue 4
    # flops-bound: 2e3 / 1e12 = 2 ns against 10 ns of kernel a step
    (flash_attention_roofline, 100 * 2e-9 / 10e-9),
    (held_moe_ms_per_step, 15e-6),
    # the TRACED steps' 0.25 x 1000 assignments x 10 FLOPs over 15 ns x 1e12
    (held_moe_flops_util, 100 * 2.5e3 / (15e-9 * 1e12)),
    (router_ms_per_step, 4e-6),
    (head_ms_per_step, 7e-6),            # (12 + 2) / 2
    (held_assignment_share, 50.0),
    (tokens_per_s_per_chip, 10 * 2 * 8192 / 4.0),
    (held_load_max_over_mean, 1.3),
    (held_dropped_assignments, 0.0),
    (recompute_ms_per_step, 17e-6),      # (30 + 4) ns / 2
])
def test_each_reader_on_a_hand_made_run(reader, want):
    assert reader.reduce(small_run()) == pytest.approx(want)


@pytest.mark.parametrize("reader", [
    cca_ms_per_step, cca_mix_ms_per_step, flash_attention_roofline,
    held_moe_ms_per_step, held_moe_flops_util, router_ms_per_step,
    head_ms_per_step, held_assignment_share,
    tokens_per_s_per_chip, held_load_max_over_mean,
    held_dropped_assignments, recompute_ms_per_step])
def test_each_reader_finds_nothing_on_a_program_without_it(reader):
    """A program or a run without what the reader reads: no map, no ``lm``
    section, no trace — None, and nothing raised. (Which CELLS report a
    metric is its ``workloads`` list's to say, not the reader's: no reader
    looks for a cell's name.)"""
    assert reader.reduce(small_run(scopes=None, lm=False)) is None
    if reader is not recompute_ms_per_step:   # reads the map alone
        assert reader.reduce(small_run(lm=False)) is None
    counters = (held_assignment_share, tokens_per_s_per_chip,
                held_load_max_over_mean, held_dropped_assignments)
    if reader not in counters:                # those need no trace
        assert reader.reduce(dict(small_run(), trace=None)) is None


# --------------------------------------------------------------------------- #
# the plain reference against NumPy, piece by piece
# --------------------------------------------------------------------------- #

def tiny_weights(seed=0, layers=2, d=16, h=4, g=2, dh=4, r=8, e=8, f=12,
                 v=32, held=range(8)):
    import jax
    keys = iter(jax.random.split(jax.random.PRNGKey(seed), 200))
    mat = lambda *shape: 0.5 * jax.random.normal(next(keys), shape)  # noqa
    gain = lambda n: [1.0 + 0.1 * jax.random.normal(next(keys), (n,))]  # noqa
    c = (h + g) * dh
    w = {"embed": [mat(v, d)], "final_norm": gain(d)}
    for i in range(layers):
        l = f"l{i}_"
        w[l + "attn_norm"], w[l + "moe_norm"] = gain(d), gain(d)
        w[l + "q"], w[l + "k"] = [mat(h * dh, d)], [mat(g * dh, d)]
        w[l + "v1"], w[l + "v2"] = [mat(g * dh // 2, d)], \
            [mat(g * dh // 2, d)]
        w[l + "cca_conv"] = [mat(2, c), mat(c), mat(2, h + g, dh, dh), mat(c)]
        w[l + "cca_qknorm"] = gain(g)
        w[l + "o"] = [mat(d, h * dh)]
        w[l + "router"] = [mat(r, d)] + ([mat(r)] if i else []) \
            + [mat(r, r), mat(r, r), mat(e, r), 0.05 * mat(e)]
        n = len(list(held))
        w[l + "moe"] = [mat(n, f, d), mat(n, f, d), mat(n, d, f)]
    cfg = {"num_hidden_layers": layers, "num_attention_heads": h,
           "num_key_value_heads": g, "num_experts": e, "rms_norm_eps": 1e-5,
           "rope_theta": 5e6, "rotary_dims": dh // 2}
    return cfg, w


def test_reference_router_and_experts_against_a_numpy_loop():
    """The reference's MoE sublayer token by token in NumPy float64: the
    router's exact-GELU MLP, the biased argmax, and the chosen expert's
    gated FFN times its UNRENORMALISED probability. A one-layer net whose
    CCA adds nothing (W_o = 0) and whose embedding rows are the states, so
    that the sublayer's input is known: u = RMSNorm(row), gain 1."""
    import math

    import reference.zaya1 as ref
    cfg, w = tiny_weights(layers=1)
    f64 = lambda a: np.asarray(a, np.float64)  # noqa: E731
    rows = np.random.RandomState(0).randn(6, 16)
    w = {**w, "l0_o": [0 * w["l0_o"][0]],
         "l0_moe_norm": [np.ones(16, np.float32)],
         "embed": [np.concatenate([rows, np.zeros((26, 16))])
                   .astype(np.float32)]}
    got = ref.forward(cfg, w, np.arange(6)[None])
    down, w1, w2, w3, bias = (f64(a) for a in w["l0_router"])
    gate, up, dn = (f64(a) for a in w["l0_moe"])
    gelu = np.vectorize(lambda a: 0.5 * a * (1 + math.erf(a / math.sqrt(2))))
    rows = f64(w["embed"][0])[:6]
    u = rows / np.sqrt((rows * rows).mean(-1, keepdims=True) + 1e-5)
    s = gelu(gelu(u @ down.T @ w1.T) @ w2.T) @ w3.T
    p = np.exp(s - s.max(-1, keepdims=True))
    p /= p.sum(-1, keepdims=True)
    e = np.argmax(p + bias, -1)
    want = np.zeros((6, 16))
    for t in range(6):
        act = gate[e[t]] @ u[t]
        act = act / (1 + np.exp(-act)) * (up[e[t]] @ u[t])
        want[t] = p[t, e[t]] * (dn[e[t]] @ act)
    np.testing.assert_array_equal(np.asarray(got["choice"])[0, 0], e)
    np.testing.assert_allclose(np.asarray(got["moe"])[0, 0], want,
                               rtol=2e-4, atol=1e-6)
    np.testing.assert_array_equal(np.asarray(got["counts"])[0],
                                  np.bincount(e, minlength=8))


def test_reference_shares_add_up_and_a_handed_choice_counts_flips():
    import jax
    import reference.zaya1 as ref
    cfg, w = tiny_weights()
    tokens = jax.random.randint(jax.random.PRNGKey(4), (2, 16), 0, 32)
    whole = ref.forward(cfg, w, tokens)
    lo = {k: ([a[:4] for a in v] if k.endswith("_moe") else v)
          for k, v in w.items()}
    hi = {k: ([a[4:] for a in v] if k.endswith("_moe") else v)
          for k, v in w.items()}
    one = {**cfg, "num_hidden_layers": 1}     # layer 0: the same input
    a = ref.forward(one, lo, tokens, held=range(4))["moe"][0]
    b = ref.forward(one, hi, tokens, held=range(4, 8))["moe"][0]
    np.testing.assert_allclose(np.asarray(a) + np.asarray(b),
                               np.asarray(whole["moe"][0]), rtol=1e-5,
                               atol=1e-7)
    assert not np.any(np.asarray(whole["route_flips"]))
    # hand over another choice for one token of layer 1: one flip, there
    choice = np.array(whole["choice"])
    choice[1, 0, 3] = (choice[1, 0, 3] + 1) % 8
    moved = ref.forward(cfg, w, tokens, choice=choice)
    assert list(np.asarray(moved["route_flips"])) == [0, 1]
    assert np.any(np.asarray(moved["moe"][1])[0, 3]
                  != np.asarray(whole["moe"][1])[0, 3])
    np.testing.assert_array_equal(np.asarray(moved["moe"][1])[1],
                                  np.asarray(whole["moe"][1])[1])


def test_reference_balancing_rule():
    import reference.zaya1 as ref
    bias = np.array([0.0, 0.002, -0.001, 0.0], np.float32)
    got = ref.next_bias(bias, [10, 0, 5, 5], 0.001)    # mean 5
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
    """``train_step`` against the same step written out in NumPy from
    ``jax.grad`` of ``loss``: the clip is on (the gradient's norm is above
    it), a blob's rate and decay are its own, the first AdamW step from zero
    moments is rate x (g / (|g| + eps) + decay x w), and the selection
    biases move by the sign rule on the step's counts and by nothing else.
    Remat, and rounding that is switched off, change nothing."""
    import jax
    import reference.zaya1 as ref
    cfg, w, tokens, targets, opt = _tiny_step()
    got = jax.device_get(ref.train_step(cfg, w, tokens, targets, opt))
    (total, out), grads = jax.value_and_grad(
        lambda some: ref.loss(cfg, some, tokens, targets), has_aux=True)(w)
    grads = jax.device_get(grads)
    assert float(got["loss"]) == pytest.approx(float(total), rel=1e-6)
    routers = [n for n in w if n.endswith("_router")]
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
            # w' - w in f32: half an ulp of a weight of order 1
            np.testing.assert_allclose(got["change"][n][j], want,
                                       rtol=2e-4, atol=2e-7, err_msg=n)
    counts = np.asarray(out["counts"])
    for i, n in enumerate(sorted(routers)):
        np.testing.assert_allclose(
            got["change"][n][-1],
            0.001 * np.sign(counts[i].sum() / 8 - counts[i]), atol=1e-8)
    again = jax.device_get(ref.train_step(
        cfg, w, tokens, targets, opt, remat=True, q_block=4,
        round_to=jax.numpy.float8_e4m3fn, round_when=False))
    for a, b in zip(jax.tree.leaves(again["change"]),
                    jax.tree.leaves(got["change"])):
        # (an element whose gradient is of AdamW's eps steps by its size)
        np.testing.assert_allclose(a, b, rtol=1e-4, atol=5e-6)
    low = jax.device_get(ref.train_step(
        cfg, w, tokens, targets, opt, remat=True, q_block=4,
        round_to=jax.numpy.float8_e4m3fn, round_when=True))
    assert abs(float(low["loss"]) - float(total)) > 1e-4 * float(total)
    assert float(low["grad_norm"]) > 0     # straight through the rounding


@pytest.mark.parametrize("fault, limit", [
    (None, None), ("bias_still", "bias_wrong"), ("half_a_leaf", "norm"),
    ("other_loss", "loss"), ("wrong_way", "cosine")])
def test_step_check_tells_a_wrong_step(fault, limit):
    """``step_check`` on a step that IS the reference's passes; one whose
    selection biases never moved, whose expert stack moved half as far or
    the wrong way, or whose loss is another batch's does not."""
    import jax
    import reference.zaya1 as ref
    from runners import zaya_train
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
        change["l1_moe"][0] *= 0.5
    elif fault == "wrong_way":
        change["l1_moe"][2] *= -1
    elif fault == "other_loss":
        loss *= 1.01
    job = {"config": dict(CFG, rope_parameters={"hybrid": {
               "rope_theta": cfg["rope_theta"]}}, reference_positions=4),
           "tiny": True, "traffic": {"precision": "f32"}}
    model = {"num_hidden_layers": 2, "num_attention_heads": 4,
             "num_key_value_heads": 2, "router_num_experts": 8,
             "num_experts": 8, "rms_norm_eps": 1e-5, "head_dim": 4,
             "partial_rotary_factor": 0.5}
    facts, ok = zaya_train.step_check(job, model, 16, {
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
    """The driver may hand the parent this PR's benchmark files: the runner
    looks in the program for the layer types and MoE fields it needs and
    exits 2 at once, before jax is touched."""
    import runners.zaya_train as runner
    from poseidon_tpu.core import layers
    from poseidon_tpu.proto import messages
    runner.refuse_old_program(CELL)           # this program: fine
    monkeypatch.setattr(layers, "REGISTRY", {
        k: v for k, v in layers.REGISTRY.items() if k != "MOE_ROUTER"})
    with pytest.raises(SystemExit) as stop:
        runner.refuse_old_program(CELL)
    assert stop.value.code == 2 and "MOE_ROUTER" in capsys.readouterr().err
    monkeypatch.undo()
    monkeypatch.setattr(messages, "MoEParameter",
                        lambda: types.SimpleNamespace(num_experts=0))
    with pytest.raises(SystemExit) as stop:
        runner.refuse_old_program(CELL)
    assert stop.value.code == 2 and "num_held" in capsys.readouterr().err


@pytest.mark.parametrize("trace", [0, 1])
def test_cpu_tiny_rehearsal_of_the_zaya_cell(trace):
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
    assert len(check["route_flips"]) == DEPTH
    # the Engine's own first step against the reference's train_step
    step = facts["step_reference"]
    assert step["sequences"] == BATCH and step["bias_wrong"] == 0
    assert step["bias_moved"] > step["bias_of"] // 2
    assert step["loss_rel"] < step["tolerance"]["step_loss_rel"]
    assert step["update_norm_rel"] < step["tolerance"]["update_norm_rel"]
    assert step["lower_precision_update_cosine"] < step["update_cosine"]
    assert facts["token_file"]["documents"] > 10     # end-of-text is in play
    assert facts["kernel_routes"] == [
        "attention=dense; 2 kv heads repeated x4", "grouped_matmul=ragged_dot"]
    assert facts["remat_segments"] == DEPTH + 1
    assert facts["shared_params"] == {"tok_w": "embed/w x2"}
    assert facts["expert_share"]["l0_moe"] == {
        "held_first": 0, "num_held": 8, "router_num_experts": 16}
    share = facts["held_assignment_share"]
    assert share["first_display"] and share["last_display"] \
        and 0.0 < share["min"] <= share["max"] < 1.0
    names = set(line["metrics"])
    if trace:
        # all of the cell's per-layer metrics but those that need a chip's
        # peaks, its memory statistics or its Pallas kernels
        assert names == declared("per_layer", CELL) - {
            "busy_flops_util", "peak_hbm_gb", "held_moe_flops_util",
            "flash_attention_roofline"}
        m = {k: v["value"] for k, v in line["metrics"].items()}
        assert m["scope_coverage"] >= 95.0
        parts = ("cca_ms_per_step", "cca_mix_ms_per_step",
                 "held_moe_ms_per_step", "router_ms_per_step",
                 "head_ms_per_step")
        assert all(m[k] > 0 for k in parts)
        assert m["cca_mix_ms_per_step"] < m["cca_ms_per_step"]
        assert sum(m[k] for k in parts if k != "cca_mix_ms_per_step") \
            < m["fwd_ms_per_step"] + m["bwd_ms_per_step"]
        assert 0 < m["held_assignment_share"] < 100
    else:
        assert names == declared("end_to_end", CELL) - {"mfu_required"}
        assert line["metrics"]["images_per_s_per_chip"]["value"] == \
            pytest.approx(facts["tokens_per_s_per_chip"] / facts["seq_len"])


def test_new_entries_follow_the_contract():
    cell = next(w for w in BENCH["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == \
        ("zaya1_8b", "packed8k_slice", 1)
    assert "step time follows" in cell["why"]     # the load is the router's
    assert f"{DEPTH} of 40 layers" in cell["why"] \
        and f"{BATCH} x 8192" in cell["why"]
    config = next(c for c in BENCH["configs"] if c["name"] == "zaya1_8b")
    assert config["reduced"] == CFG["reduced"] == [
        "num_hidden_layers", "num_experts", "vocab_size"]
    assert config["source"] == CFG["source"] \
        and config["file"] == "benchmark/configs/zaya1_8b.json"
    # the entries that list the cell, by membership; the stall ledger's
    # seven are test_bench_stalls.py's
    mine = [m for m in BENCH["per_layer"]
            if CELL in m.get("workloads", ()) and m["name"] not in STALLS]
    assert mine
    # the contract's limits of form on every line of text this PR adds
    # (the driver refused a 203-character `why` before any run)
    for text in (cell["why"], config["why"], config["source"],
                 *(m["layer"] for m in mine)):
        assert 1 <= len(text) <= 200 and text.isascii() \
            and text.isprintable(), text
    for m in mine:
        assert set(m) == {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        assert m["moves"] == "mfu_required"
        assert os.path.exists(os.path.join(
            BENCH_DIR, "layer_metrics", m["name"] + ".py"))
    assert "85%" in OWN["why"]
