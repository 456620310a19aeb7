"""The token-model cell's yardstick: ``flops_lm`` against hand counts, the
plain reference against a NumPy loop, the token generator, the configuration
against the catalog row and its copies, and the ``--cpu-tiny`` rehearsal of
``olmoe.l1.pack4k`` end to end."""

import json
import os

import numpy as np
import pytest

import flops_lm
import tokengen
from conftest import BENCH_DIR, ROOT
from test_bench_run import BENCH, declared, run_cell

with open(os.path.join(BENCH_DIR, "configs", "olmoe_1b_7b.json")) as f:
    CFG = json.load(f)

# config.json of allenai/OLMoE-1B-7B-0125-Instruct as the model-configs
# catalog (architectures.jsonl) holds it
CATALOG = {"attention_bias": False, "clip_qkv": None, "hidden_act": "silu",
           "hidden_size": 2048, "intermediate_size": 1024,
           "max_position_embeddings": 4096, "model_type": "olmoe",
           "norm_topk_prob": False, "num_attention_heads": 16,
           "num_experts": 64, "num_experts_per_tok": 8,
           "num_hidden_layers": 16, "num_key_value_heads": 16,
           "rms_norm_eps": 1e-05, "rope_scaling": None, "rope_theta": 10000,
           "tie_word_embeddings": False, "vocab_size": 50304}


@pytest.mark.parametrize("key", sorted(CATALOG))
def test_configuration_equals_the_catalog_row(key):
    """Every key as published; the depth, and only the depth, is reduced."""
    if key in CFG["reduced"]:
        assert CFG["reduced"] == ["num_hidden_layers"] and CFG[key] == 1
    else:
        assert CFG[key] == CATALOG[key]


def test_configuration_arithmetic():
    """The sizes the configuration file argues from."""
    d, f, e, v = 2048, 1024, 64, 50304
    experts, attn, router = e * 3 * d * f, 4 * d * d, e * d
    assert experts == 402_653_184 and attn == 16_777_216
    layer, vocab = experts + attn + router, 2 * v * d
    assert round(layer / 1e6, 1) == 419.6 and round(vocab / 1e6, 1) == 206.0
    assert round(16 * (layer + vocab) / 1e9, 1) == 10.0      # one layer
    assert round(16 * (2 * layer + vocab) / 1e9, 1) == 16.7  # two do not fit


@pytest.mark.parametrize("part,macs", [
    ("projections", 4 * 2048 * 2048), ("attention", 4096 * 2048),
    ("router", 2048 * 64), ("experts", 8 * 3 * 2048 * 1024),
    ("head", 2048 * 50304)])
def test_required_macs_against_hand_counts(part, macs):
    assert flops_lm.required_macs_per_token(CFG, 4096)[part] == macs


def test_required_flops_and_shares():
    one = flops_lm.required_flops_per_token(CFG, 4096)
    assert round(one["total"] / 1e9, 2) == 1.07            # GFLOP a token
    assert round(one["total"] * 8192 / 1e12, 1) == 8.8     # TFLOP a step
    assert round(100 * one["experts"] / one["total"]) == 28
    assert round(100 * one["head"] / one["total"]) == 58
    full = flops_lm.required_flops_per_token(
        {**CFG, "num_hidden_layers": 16}, 4096)
    assert round(100 * full["experts"] / full["total"]) == 61
    assert round(100 * full["head"] / full["total"]) == 8
    # the attention part IS what the flash kernels are asked for
    flash = flops_lm.flash_attention_step(CFG, 2, 4096)
    assert flash["flops"] == one["attention"] * 8192
    assert flash["bytes"] == 12 * 2 * 4096 * 2048 * 2


def test_copies_match_their_originals():
    for copy, original in CFG["copied_from"].items():
        with open(os.path.join(BENCH_DIR, copy)) as a, \
                open(os.path.join(ROOT, original)) as b:
            assert a.read() == b.read(), (copy, original)
    # the program's tests import their own copy of the reference
    with open(os.path.join(BENCH_DIR, "reference", "olmoe.py")) as a, \
            open(os.path.join(ROOT, "tests", "olmoe_ref.py")) as b:
        assert a.read() == b.read()


def test_token_file_is_seeded_packed_and_zipf():
    with open(os.path.join(BENCH_DIR, "traffic", "packed4k.json")) as f:
        mix = json.load(f)["documents"]
    big = 3_000_000_019                      # over 2**31, as the driver's
    a = tokengen.packed_sequences(big, 4, 4096, 50304, mix)
    b = tokengen.packed_sequences(big, 4, 4096, 50304, mix)
    c = tokengen.packed_sequences(big + 1, 4, 4096, 50304, mix)
    assert np.array_equal(a["data"], b["data"])
    assert not np.array_equal(a["data"], c["data"])
    assert a["data"].dtype == a["label"].dtype == np.int32
    assert a["data"].shape == a["label"].shape == (4, 4096)
    flat, nxt = a["data"].reshape(-1), a["label"].reshape(-1)
    assert np.array_equal(flat[1:], nxt[:-1])           # packed end to end
    assert 0 <= flat.min() and flat.max() < 50304       # and no padding id
    eot = flat == mix["end_of_text_id"]
    gaps = np.diff(np.flatnonzero(eot)) - 1             # whole documents
    assert set(gaps.tolist()) <= set(a["doc_lengths"])
    assert 16 <= gaps.min() and gaps.max() <= 4096
    # Zipf(1): rank 1 about twice rank 2, the head far over the tail
    counts = np.bincount(flat[~eot], minlength=50304)
    assert 1.5 < counts[0] / counts[1] < 2.7
    assert counts[:10].sum() > 20 * counts[25000:25010].sum()


def test_reference_against_a_numpy_loop():
    """The plain reference's block, token by token and expert by expert in
    NumPy float64 at toy size (the arithmetic of its docstring, with loops
    where it has einsums)."""
    import jax
    import reference.olmoe as ref
    rs = np.random.RandomState(0)
    s, d, heads, e, k, f, v = 6, 8, 2, 4, 2, 4, 11
    dh = d // heads
    cfg = {"num_hidden_layers": 1, "num_attention_heads": heads,
           "num_experts_per_tok": k, "rms_norm_eps": 1e-5,
           "rope_theta": 10000.0}
    w = {"embed": [rs.randn(v, d)], "final_norm": [1 + .1 * rs.randn(d)],
         "lm_head": [rs.randn(v, d)],
         "l0_moe": [rs.randn(e, d), .5 * rs.randn(e, f, d),
                    .5 * rs.randn(e, f, d), .5 * rs.randn(e, d, f)]}
    for n in ("attn_norm", "q_norm", "k_norm", "ffn_norm"):
        w["l0_" + n] = [1 + .1 * rs.randn(d)]
    for n in "qkvo":
        w["l0_" + n] = [.5 * rs.randn(d, d)]
    tokens = rs.randint(0, v, size=(1, s))
    targets = rs.randint(0, v, size=(1, s))

    def norm(x, g):
        return x / np.sqrt((x * x).mean() + 1e-5) * g

    def rope(x, pos):
        out = np.empty_like(x)
        for i in range(dh // 2):
            ang = pos / 10000.0 ** (2 * i / dh)
            a, b = x[i], x[i + dh // 2]
            out[i] = a * np.cos(ang) - b * np.sin(ang)
            out[i + dh // 2] = b * np.cos(ang) + a * np.sin(ang)
        return out

    x = w["embed"][0][tokens[0]]
    a = np.stack([norm(t, w["l0_attn_norm"][0]) for t in x])
    q = np.stack([norm(w["l0_q"][0] @ t, w["l0_q_norm"][0]) for t in a])
    kk = np.stack([norm(w["l0_k"][0] @ t, w["l0_k_norm"][0]) for t in a])
    vv = np.stack([w["l0_v"][0] @ t for t in a])
    att = np.zeros((s, d))
    for h in range(heads):
        sl = slice(h * dh, (h + 1) * dh)
        for i in range(s):
            qi = rope(q[i, sl], i)
            sc = np.array([qi @ rope(kk[j, sl], j) / np.sqrt(dh)
                           for j in range(i + 1)])
            p = np.exp(sc - sc.max())
            p /= p.sum()
            att[i, sl] = sum(p[j] * vv[j, sl] for j in range(i + 1))
    hid = x + att @ w["l0_o"][0].T
    router, gate, up, down = w["l0_moe"]
    y, probs, chosen, lse = np.zeros((s, d)), [], np.zeros(e), []
    for i in range(s):
        u = norm(hid[i], w["l0_ffn_norm"][0])
        logit = router @ u
        lse.append(np.log(np.exp(logit).sum()))
        p = np.exp(logit - lse[-1])
        probs.append(p)
        for ex in np.argsort(p)[-k:]:                # not renormalised
            g = gate[ex] @ u
            y[i] += p[ex] * (down[ex] @ (g / (1 + np.exp(-g)) * (up[ex] @ u)))
            chosen[ex] += 1
    out = hid + y
    logits = np.stack([w["lm_head"][0] @ norm(t, w["final_norm"][0])
                       for t in out])
    lm = np.mean([np.log(np.exp(r).sum()) - r[t]
                  for r, t in zip(logits, targets[0])])
    balance = e * np.sum(chosen / s * np.mean(probs, 0))
    z = np.mean(np.square(lse))

    got = ref.forward(cfg, w, tokens)
    total, parts = jax.jit(lambda: ref.loss(cfg, w, tokens, targets))()
    np.testing.assert_allclose(got["logits"][0], logits, rtol=2e-4, atol=2e-5)
    np.testing.assert_allclose(parts["lm"], lm, rtol=1e-5)
    np.testing.assert_allclose(parts["balance"], balance, rtol=1e-5)
    np.testing.assert_allclose(parts["z"], z, rtol=1e-5)
    np.testing.assert_allclose(total, lm + 0.01 * balance + 0.001 * z,
                               rtol=1e-5)
    assert np.asarray(got["tokens_per_expert"][0]).tolist() == chosen.tolist()


@pytest.mark.parametrize("trace", [0, 1])
def test_cpu_tiny_rehearsal_of_the_token_cell(trace):
    cell = "olmoe.l1.pack4k"
    done = run_cell("--workload", cell, "--seed", "3000000019", "--seconds",
                    "1", "--trace", str(trace), "--cpu-tiny")
    assert done.returncode == 0, done.stderr[-3000:]
    lines = done.stdout.strip().splitlines()
    line, facts = json.loads(lines[-1]), json.loads(lines[-2])["facts"]
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] >= 4 and line["device"]["platform"] == "cpu"
    assert all(facts["checks"].values()), facts["checks"]
    assert facts["reference"]["logits_rel_l2"] \
        < facts["reference"]["tolerance"]["logits_rel_l2"]
    assert facts["token_file"]["documents"] > 10     # end-of-text is in play
    assert facts["kernel_routes"] == {"l0_attn": "attention=dense",
                                      "l0_moe": "grouped_matmul=ragged_dot"}
    names = set(line["metrics"])
    if trace:
        # all of the cell's per-layer metrics but those that need a chip's
        # peaks, its memory statistics or its Pallas kernels
        assert names == declared("per_layer", cell) - {
            "busy_flops_util", "peak_hbm_gb", "moe_flops_util",
            "flash_attention_roofline"}
        m = {k: v["value"] for k, v in line["metrics"].items()}
        assert m["scope_coverage"] >= 95.0 and m["dropped_tokens"] == 0.0
        assert m["moe_ms_per_step"] > 0 and m["attention_ms_per_step"] > 0
        assert 0 < m["head_ms_per_step"] < m["fwd_ms_per_step"] \
            + m["bwd_ms_per_step"]
        assert m["expert_load_max_over_mean"] >= 1.0
        assert m["tokens_per_s_per_chip"] > 0
    else:
        # every end-to-end metric but the one a CPU has no peak for; a
        # token is not an image, so the sample is one sequence
        assert names == declared("end_to_end", cell) - {"mfu_required"}
        assert line["metrics"]["images_per_s_per_chip"]["value"] == \
            pytest.approx(facts["tokens_per_s_per_chip"] / facts["seq_len"])


def test_new_entries_follow_the_contract():
    cell = next(w for w in BENCH["workloads"] if w["name"] == "olmoe.l1.pack4k")
    assert (cell["config"], cell["traffic"], cell["chips"]) == \
        ("olmoe_1b_7b", "packed4k", 1)
    # images_per_s_per_chip counts sequences here, and the cell says so
    assert "sequences/s/chip" in cell["why"] and len(cell["why"]) <= 200
    config = next(c for c in BENCH["configs"] if c["name"] == "olmoe_1b_7b")
    assert config["reduced"] == CFG["reduced"] and config["source"] == \
        CFG["source"]
    for m in BENCH["per_layer"]:
        if m.get("workloads") == ["olmoe.l1.pack4k"]:
            assert m["moves"] == "mfu_required"
            assert os.path.exists(os.path.join(
                BENCH_DIR, "layer_metrics", m["name"] + ".py"))
