"""The looped-LM cell's yardstick: ``flops_looplm`` against hand counts, the
configuration against the catalog row and its copies, each of the cell's
seven readers on a hand-made ``layers`` dict (and on a program without what
it reads), the plain reference's exit distribution against a NumPy loop, and
the ``--cpu-tiny`` rehearsal of ``ouro.loop4.pack8k`` end to end."""

import json
import os

import numpy as np
import pytest

import flops_looplm
import tokengen
from conftest import BENCH_DIR, ROOT
from layer_metrics import (exit_heads_ms_per_step, exit_mass_last_pass,
                           ffn_flops_util, ffn_ms_per_step,
                           attention_ms_per_step,
                           flash_attention_roofline,
                           recompute_ms_per_step)
from test_bench_run import BENCH, STALLS, declared, run_cell

CELL = "ouro.loop4.pack8k"
with open(os.path.join(BENCH_DIR, "configs", "ouro_2_6b.json")) as f:
    CFG = json.load(f)
DEPTH = CFG["num_hidden_layers"]

# config.json of ByteDance/Ouro-2.6B as the model-configs catalog
# (architectures.jsonl) holds it; layer_types is 48 x "full_attention"
CATALOG = {"head_dim": 128, "hidden_act": "silu", "hidden_size": 2048,
           "intermediate_size": 5632,
           "layer_types": ["full_attention"] * 48,
           "max_position_embeddings": 65536, "max_window_layers": 48,
           "model_type": "ouro", "num_attention_heads": 16,
           "num_hidden_layers": 48, "num_key_value_heads": 16,
           "rms_norm_eps": 1e-06, "rope_scaling": None,
           "rope_theta": 1000000, "sliding_window": None,
           "tie_word_embeddings": False, "total_ut_steps": 4,
           "early_exit_threshold": 1, "use_sliding_window": False,
           "vocab_size": 49152}


@pytest.mark.parametrize("key", sorted(CATALOG))
def test_configuration_equals_the_catalog_row(key):
    """Every key as published; the depth, and only the depth, is reduced."""
    if key in CFG["reduced"]:
        assert CFG["reduced"] == ["num_hidden_layers"] and CFG[key] < 48
    else:
        assert CFG[key] == CATALOG[key]


def test_configuration_arithmetic():
    """The sizes the configuration file argues from."""
    d, f, v = 2048, 5632, 49152
    layer = 4 * d * d + 3 * d * f + 4 * d
    vocab = 2 * v * d + d + d + 1              # embed, head, final norm, gate
    assert round(layer / 1e6, 1) == 51.4 and round(vocab / 1e6, 1) == 201.3
    assert round((vocab + 6 * layer) / 1e6, 1) == 509.7
    assert round(16 * (vocab + DEPTH * layer) / 1e9, 1) == \
        {6: 8.2, 7: 9.0}[DEPTH]
    assert round(16 * (vocab + 48 * layer) / 1e9) == 43     # the whole model


@pytest.mark.parametrize("part,macs", [
    ("projections", 4 * DEPTH * 4 * 2048 * 2048),
    ("attention", 4 * DEPTH * 8192 * 2048),
    ("ffn", 4 * DEPTH * 3 * 2048 * 5632),
    ("heads", 4 * 2048 * 49152), ("gates", 3 * 2048)])
def test_required_macs_against_hand_counts(part, macs):
    assert flops_looplm.required_macs_per_token(CFG, 8192)[part] == macs


def test_required_flops_and_shares():
    six = flops_looplm.required_flops_per_token(
        {**CFG, "num_hidden_layers": 6}, 8192)
    # ISSUE 29's hand count: 68.2M MACs a token and application, x 24, + 4
    # heads of 100.7M, x 2 x 3
    per_app = 4 * 2048 ** 2 + 8192 * 2048 + 3 * 2048 * 5632
    assert round(per_app / 1e6, 1) == 68.2
    assert six["total"] == 6 * (24 * per_app + 4 * 2048 * 49152 + 3 * 2048)
    assert round(six["total"] / 1e9, 1) == 12.2            # GFLOP a token
    assert round(six["total"] * 8192 / 1e12) == 100        # TFLOP a step
    assert round(100 * six["heads"] / six["total"]) == 20
    assert round(100 * six["ffn"] / six["total"]) == 41
    assert round(100 * six["attention"] / six["total"]) == 20
    full = flops_looplm.required_flops_per_token(
        {**CFG, "num_hidden_layers": 48}, 8192)
    assert round(100 * full["heads"] / full["total"]) == 3
    # the attention part IS what the flash kernels are asked for
    one = flops_looplm.required_flops_per_token(CFG, 8192)
    flash = flops_looplm.flash_attention_step(CFG, 1, 8192)
    assert flash["flops"] == one["attention"] * 8192
    assert flash["bytes"] == 4 * DEPTH * 12 * 8192 * 2048 * 2


def test_copies_match_their_originals():
    for copy, original in CFG["copied_from"].items():
        with open(os.path.join(BENCH_DIR, copy)) as a, \
                open(os.path.join(ROOT, original)) as b:
            assert a.read() == b.read(), (copy, original)
    with open(os.path.join(BENCH_DIR, CFG["net"])) as f:
        net = f.read()
    assert net.count('type: ATTENTION') == 4 * DEPTH
    assert net.count('name: "head_w"') == 4 and 'name: "p4_gate"' not in net


def test_traffic_is_packed8k_as_stated():
    with open(os.path.join(BENCH_DIR, "traffic", "packed8k.json")) as f:
        traffic = json.load(f)
    mix = traffic["documents"]
    assert (traffic["seq_len"], traffic["steps_in_file"],
            traffic["display"], traffic["runner"]) == \
        (8192, 8, 4, "looplm_train")
    assert (mix["doc_len_median"], mix["doc_len_sigma"], mix["doc_len_min"],
            mix["doc_len_max"], mix["zipf_exponent"],
            mix["end_of_text_id"]) == (512, 1.2, 16, 8192, 1.0, 0)
    big = 3_000_000_019                      # over 2**31, as the driver's
    a = tokengen.packed_sequences(big, 2, 8192, 49152, mix)
    flat, nxt = a["data"].reshape(-1), a["label"].reshape(-1)
    assert np.array_equal(flat[1:], nxt[:-1])           # packed end to end
    assert 0 <= flat.min() and flat.max() < 49152
    gaps = np.diff(np.flatnonzero(flat == 0)) - 1       # whole documents
    assert 16 <= gaps.min() and gaps.max() <= 8192
    # the remat flags are the ones the example solver's header names
    with open(os.path.join(ROOT, "examples", "lm",
                           "ouro_2_6b_solver.prototxt")) as f:
        header = f.read()
    flag = next(a for a in traffic["argv"] if a.startswith("--remat="))
    assert "--remat '" + flag[len("--remat="):] + "'" in header


# --------------------------------------------------------------------------- #
# the seven readers on a hand-made run
# --------------------------------------------------------------------------- #
#   0    10   20   30   40   50   60   70   80   90  100 ns, two steps
OPS = [("fusion ffn.1 bf16[8]", 0.0, 10.0),            # p1_l0_ffn_gate fwd
       ("fusion ffn.2 bf16[8]", 10.0, 10.0),           # p2_l0_ffn_down, replay
       ("pallas-call flash.3 bf16[8]", 20.0, 20.0),    # p1_l0_attn bwd
       ("fusion rope.4 bf16[8]", 40.0, 4.0),           # p1_l0_attn, replay
       ("fusion head.5 bf16[8]", 50.0, 30.0),          # p3_head bwd
       ("fusion exit.6 f32[8]", 80.0, 6.0),            # exit_loss fwd
       ("fusion norm.7 bf16[8]", 90.0, 2.0)]           # p1_l0_ffn_norm fwd
SCOPES = {"ops": {"ffn.1": "p1_l0_ffn_gate|fwd", "ffn.2": "p2_l0_ffn_down|bwd",
                  "flash.3": "p1_l0_attn|bwd", "rope.4": "p1_l0_attn|bwd",
                  "head.5": "p3_head|bwd", "exit.6": "exit_loss|fwd",
                  "norm.7": "p1_l0_ffn_norm|fwd"},
          "types": {"p1_l0_ffn_gate": "INNER_PRODUCT",
                    "p2_l0_ffn_down": "INNER_PRODUCT",
                    "p1_l0_attn": "ATTENTION", "p3_head": "INNER_PRODUCT",
                    "exit_loss": "EXIT_LOSS", "p1_l0_ffn_norm": "RMS_NORM"},
          "recomputed": ["ffn.2", "rope.4"]}
PEAKS = {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e11}


def small_run(scopes=SCOPES, lm=True):
    run = {"trace": {"steps": 2, "spans": [], "async": {},
                     "devices": {"0": OPS}},
           "peak_flops_per_s": PEAKS["bf16_flops_per_s"],
           "stats": {"sections": {"step_scopes": scopes} if scopes else {}}}
    if lm:
        run["lm"] = {"scopes": CFG["scopes"], "peaks": PEAKS,
                     "flops_per_step": {"ffn": 4e3},
                     "flash_per_step": {"flops": 2e3, "bytes": 100.0},
                     "exit_mass": [[0.5, 0.25, 0.125, 0.125],
                                   [0.4, 0.3, 0.2, 0.1]]}
    return run


@pytest.mark.parametrize("reader, want", [
    (ffn_ms_per_step, 10e-6),                 # (10 + 10) ns / 2 steps
    (ffn_flops_util, 100 * 4e3 / (10e-9 * 1e12)),
    (attention_ms_per_step, 12e-6),      # (20 + 4) / 2
    # flops-bound: 2e3 / 1e12 = 2 ns against 10 ns of kernel a step
    (flash_attention_roofline, 100 * 2e-9 / 10e-9),
    (exit_heads_ms_per_step, 18e-6),          # (30 + 6) / 2
    (recompute_ms_per_step, 7e-6),            # (10 + 4) / 2
    (exit_mass_last_pass, 11.25),
])
def test_each_reader_on_a_hand_made_run(reader, want):
    assert reader.reduce(small_run()) == pytest.approx(want)


@pytest.mark.parametrize("reader", [
    ffn_ms_per_step, ffn_flops_util, attention_ms_per_step,
    flash_attention_roofline, exit_heads_ms_per_step,
    recompute_ms_per_step, exit_mass_last_pass])
def test_each_reader_finds_nothing_on_a_program_without_it(reader):
    """The parent's program: no map at all, a map without ``recomputed``,
    another runner's ``lm`` section, no trace — None, and nothing raised."""
    old_map = {k: v for k, v in SCOPES.items() if k != "recomputed"}
    assert reader.reduce(small_run(scopes=None, lm=False)) is None
    assert reader.reduce(small_run(scopes=old_map, lm=False)) is None
    other = small_run(scopes=old_map, lm=False)
    other["lm"] = {"seq_len": 4096, "peaks": PEAKS,
                   "scopes": {"head": "lm_head"},
                   "flops_per_step": {}, "flash_per_step": {}}
    if reader is not attention_ms_per_step:   # by TYPE where no part is named
        assert reader.reduce(other) is None
    no_trace = dict(small_run(), trace=None)
    if reader is not exit_mass_last_pass:     # a counter, not a trace
        assert reader.reduce(no_trace) is None


def test_reference_exit_distribution_against_a_numpy_loop():
    """The plain reference's exit probabilities and loss from its own
    logits, gate by gate in NumPy float64."""
    import jax
    import jax.numpy as jnp
    import reference.ouro as ref
    cfg = {"num_hidden_layers": 1, "total_ut_steps": 3,
           "num_attention_heads": 2, "rms_norm_eps": 1e-6, "rope_theta": 1e6}
    d, f, v, s = 16, 24, 32, 8
    keys = iter(jax.random.split(jax.random.PRNGKey(0), 32))
    mat = lambda *shape: 0.3 * jax.random.normal(next(keys), shape)  # noqa
    gain = lambda: [1.0 + 0.1 * jax.random.normal(next(keys), (d,))]  # noqa
    w = {"embed": [mat(v, d)], "p1_final_norm": gain(),
         "p1_head": [mat(v, d)], "p1_gate": [mat(1, d), mat(1)]}
    for name in ("attn_norm", "attn_out_norm", "ffn_norm", "ffn_out_norm"):
        w["p1_l0_" + name] = gain()
    for name, shape in (("q", (d, d)), ("k", (d, d)), ("v", (d, d)),
                        ("o", (d, d)), ("ffn_gate", (f, d)),
                        ("ffn_up", (f, d)), ("ffn_down", (d, f))):
        w["p1_l0_" + name] = [mat(*shape)]
    tokens = jax.random.randint(next(keys), (2, s), 0, v)
    targets = jax.random.randint(next(keys), (2, s), 0, v)
    out = ref.forward(cfg, w, tokens, targets)
    total, parts = ref.loss(cfg, w, tokens, targets, 0.1)
    lam = 1.0 / (1.0 + np.exp(-np.asarray(out["gates"], np.float64)))
    logits = np.asarray(out["logits"], np.float64)
    want = 0.0
    for n in range(2):
        for i in range(s):
            p = [lam[0, n, i], (1 - lam[0, n, i]) * lam[1, n, i],
                 (1 - lam[0, n, i]) * (1 - lam[1, n, i])]
            np.testing.assert_allclose(out["exit_p"][:, n, i], p, rtol=1e-5)
            ce = [np.log(np.exp(logits[t, n, i]).sum())
                  - logits[t, n, i, int(targets[n, i])] for t in range(3)]
            want += sum(a * b for a, b in zip(p, ce)) \
                + 0.1 * sum(a * np.log(a) for a in p)
    assert float(total) == pytest.approx(want / (2 * s), rel=1e-5)
    assert float(jnp.sum(parts["exit_mass"])) == pytest.approx(1.0)


@pytest.mark.parametrize("trace", [0, 1])
def test_cpu_tiny_rehearsal_of_the_looped_cell(trace):
    done = run_cell("--workload", CELL, "--seed", "3000000019", "--seconds",
                    "1", "--trace", str(trace), "--cpu-tiny")
    assert done.returncode == 0, done.stderr[-3000:]
    lines = done.stdout.strip().splitlines()
    line, facts = json.loads(lines[-1]), json.loads(lines[-2])["facts"]
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] >= 4 and line["device"]["platform"] == "cpu"
    assert all(facts["checks"].values()), facts["checks"]
    check = facts["reference"]
    assert check["logits_rel_l2"] < check["tolerance"]["logits_rel_l2"] \
        < check["lower_precision_rel_l2"]
    assert len(check["logits_rel_l2_per_pass"]) == 4
    assert facts["token_file"]["documents"] > 10     # end-of-text is in play
    assert facts["kernel_routes"] == ["attention=dense"]
    assert facts["remat_segments"] == 4 * DEPTH + 4
    assert facts["shared_params"] == 11 * DEPTH + 4
    assert abs(sum(facts["exit_mass"][-1]) - 1.0) < 1e-3
    names = set(line["metrics"])
    if trace:
        # all of the cell's per-layer metrics but those that need a chip's
        # peaks, its memory statistics or its Pallas kernels
        assert names == declared("per_layer", CELL) - {
            "busy_flops_util", "peak_hbm_gb", "ffn_flops_util",
            "flash_attention_roofline"}
        m = {k: v["value"] for k, v in line["metrics"].items()}
        assert m["scope_coverage"] >= 95.0
        parts = ("ffn_ms_per_step", "attention_ms_per_step",
                 "exit_heads_ms_per_step", "recompute_ms_per_step")
        assert all(m[k] > 0 for k in parts)
        assert m["recompute_ms_per_step"] < m["bwd_ms_per_step"]
        assert 0 < m["exit_mass_last_pass"] < 100
    else:
        assert names == declared("end_to_end", CELL) - {"mfu_required"}
        assert line["metrics"]["images_per_s_per_chip"]["value"] == \
            pytest.approx(facts["tokens_per_s_per_chip"] / facts["seq_len"])


def test_new_entries_follow_the_contract():
    cell = next(w for w in BENCH["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == \
        ("ouro_2_6b", "packed8k", 1)
    assert "sequences/s/chip" in cell["why"] and len(cell["why"]) <= 200
    assert f"{DEPTH} of 48 layers" in cell["why"]
    config = next(c for c in BENCH["configs"] if c["name"] == "ouro_2_6b")
    assert config["reduced"] == CFG["reduced"] == ["num_hidden_layers"]
    assert config["source"] == CFG["source"]
    assert len([w for w in BENCH["workloads"] if w["chips"] == 4]) == 1
    # the entries that list the cell, by membership; the stall ledger's
    # seven are test_bench_stalls.py's
    mine = [m for m in BENCH["per_layer"]
            if CELL in m.get("workloads", ()) and m["name"] not in STALLS]
    assert len(mine) == 8        # tokens_per_s_per_chip joined (ISSUE 50)
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
    with open(os.path.join(BENCH_DIR, "cells", CELL + ".json")) as f:
        own = json.load(f)
    assert own["batch_per_chip"] == 1 and "85%" in own["why"]
