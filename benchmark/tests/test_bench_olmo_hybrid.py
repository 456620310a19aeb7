"""The Olmo-Hybrid cell's yardstick: ``flops_olmo_hybrid`` against a count by
hand, the configuration against the catalog row and its copies, the traffic
file, each of the cell's readers on a hand-made ``layers`` dict (and on a
program without what it reads), the plain reference against NumPy at a toy
size, the runner's ``compared`` rows, its refusal of a program from before
the model, and the ``--cpu-tiny`` rehearsal of ``olmo_hybrid.p1.pack8k`` end
to end."""

import importlib
import json
import os

import numpy as np
import pytest

import flops_olmo_hybrid
from conftest import BENCH_DIR, ROOT
from layer_metrics import (delta_glue_ms_per_step, delta_ms_per_step,
                           delta_scan_ms_per_step, delta_scan_roofline,
                           attention_ms_per_step,
                           flash_attention_roofline, ffn_flops_util,
                           recompute_ms_per_step)
from test_bench_run import BENCH, STALLS, declared, run_cell

CELL = "olmo_hybrid.p1.pack8k"
with open(os.path.join(BENCH_DIR, "configs", "olmo_hybrid_7b.json")) as f:
    CFG = json.load(f)
with open(os.path.join(BENCH_DIR, "cells", CELL + ".json")) as f:
    OWN = json.load(f)
with open(os.path.join(BENCH_DIR, "traffic", "packed8k_heads.json")) as f:
    TRAFFIC = json.load(f)
DEPTH, BATCH = CFG["num_hidden_layers"], OWN["batch_per_chip"]
S = 8192
_CATALOG_FILE = "/opt/skills/guides/model-configs/architectures.jsonl"
_rows = []
if os.path.exists(_CATALOG_FILE):
    with open(_CATALOG_FILE) as f:
        _rows = [json.loads(l) for l in f if l.strip()]
# config.json of allenai/Olmo-Hybrid-7B as the model-configs catalog
# (architectures.jsonl) holds it
CATALOG = {
    "model_type": "olmo_hybrid", "vocab_size": 100352, "hidden_size": 3840,
    "intermediate_size": 11008, "num_hidden_layers": 32,
    "num_attention_heads": 30, "num_key_value_heads": 30,
    "hidden_act": "silu", "max_position_embeddings": 65536,
    "attention_bias": False, "rms_norm_eps": 1e-06,
    "tie_word_embeddings": False,
    "layer_types": (["linear_attention"] * 3 + ["full_attention"]) * 8,
    "linear_num_key_heads": 30, "linear_num_value_heads": 30,
    "linear_key_head_dim": 96, "linear_value_head_dim": 192,
    "linear_conv_kernel_dim": 4, "linear_allow_neg_eigval": True,
    "rope_parameters": {"rope_theta": None}}
REDUCED = {"num_hidden_layers": 4, "num_attention_heads": 15,
           "num_key_value_heads": 15, "linear_num_key_heads": 15,
           "linear_num_value_heads": 15, "vocab_size": 12544}


def test_the_catalog_row_is_the_one_copied_here():
    if not _rows:
        pytest.skip("no catalog on this machine")
    row = next(r for r in _rows if r["name"] == "Olmo-Hybrid-7B")
    assert row["config"] == CATALOG and row["source_url"] == CFG["source"]


@pytest.mark.parametrize("key", sorted(CATALOG))
def test_configuration_equals_the_catalog_row(key):
    """Every key of the source under the same name; a key that differs is in
    ``reduced`` and its published value in ``published``."""
    if key in REDUCED:
        assert key in CFG["reduced"] and CFG[key] == REDUCED[key] \
            and CFG["published"][key] == CATALOG[key]
    else:
        assert CFG[key] == CATALOG[key]


def test_the_cut_is_the_issue_s():
    assert sorted(CFG["reduced"]) == sorted(REDUCED)
    assert CFG["layers_run"]["layer_types"] == ["linear"] * 3 + ["full"]
    assert CFG["heads_run"] == {"held": 15, "first": 0, "of": 30,
                                "why": CFG["heads_run"]["why"]}
    assert CFG["attention_head_dim"] == 3840 // 30
    # no width in `reduced`
    assert not any(k.endswith(("_dim", "_rank", "_size")) and k != "vocab_size"
                   for k in CFG["reduced"])
    assert "766,241,946" in CFG["reduced_how"]["total"]
    for key in ("a_block_order", "b_qk_norm", "c_no_positions",
                "d_short_conv", "e_l2_norm", "f_q_scale", "g_decay", "h_beta",
                "i_out_norm", "j_recipe", "k_packing", "l_end_of_text"):
        assert key in CFG["assumed"]


@pytest.mark.parametrize("part,macs", [
    # 3 linear layers: q, k 2 x 3840 x 1440; v, z, o 3 x 3840 x 2880; a, b
    ("gdn_projections", 3 * (2 * 5_529_600 + 3 * 11_059_200 + 2 * 57_600)),
    ("gdn_recurrence", 3 * 3 * 15 * 96 * 192),
    ("attention_projections", 4 * 3840 * 1920),
    ("attention", 15 * 256 * 4096),
    ("ffn", 4 * 3 * 3840 * 11008),
    ("head", 3840 * 12544)])
def test_required_macs_against_hand_counts(part, macs):
    assert flops_olmo_hybrid.required_macs_per_token(CFG, S)[part] == macs


def test_required_flops_and_shares():
    """ISSUE 48's arithmetic: 736.2M MACs = 4.42 GFLOP a token, 36.2 TFLOP a
    step; FFN 68.9%, Gated DeltaNet 18.4%, attention 6.1%, head 6.5%."""
    macs = flops_olmo_hybrid.required_macs_per_token(CFG, S)
    total = sum(macs.values())
    assert total == 736_181_760
    flops = flops_olmo_hybrid.required_flops_per_token(CFG, S)
    assert flops["total"] == 6 * total
    assert flops["total"] * S == pytest.approx(36.18e12, rel=1e-3)
    share = lambda *parts: sum(macs[p] for p in parts) / total
    assert share("ffn") == pytest.approx(0.689, abs=1e-3)
    assert share("gdn_projections", "gdn_recurrence") == pytest.approx(
        0.184, abs=1e-3)
    assert share("attention_projections", "attention") == pytest.approx(
        0.061, abs=1e-3)
    assert share("head") == pytest.approx(0.065, abs=1e-3)


def test_the_scan_s_floor_is_its_bytes_and_the_flash_kernels_their_flops():
    """What the two roofline shares divide, at the cell's shapes on the
    v5e: the recurrence's required FLOPs take 0.62 ms at the matrix peak and
    its bytes 1.04 ms at memory speed (bytes-bound); the flash kernels 3.9
    ms of FLOPs against 0.46 ms of bytes."""
    with open(os.path.join(BENCH_DIR, "peaks.json")) as f:
        v5e = json.load(f)["TPU v5 lite"]
    scan = flops_olmo_hybrid.gdn_scan_step(CFG, 1, S)
    assert scan["flops"] == 3 * S * 3 * 3 * 15 * 96 * 192 * 2
    assert scan["bytes"] == 3 * S * 2 * 15 * ((96 * 2 + 192 * 2 + 1) * 2 + 4)
    assert scan["bytes"] / v5e["hbm_bytes_per_s"] \
        > scan["flops"] / v5e["bf16_flops_per_s"]
    flash = flops_olmo_hybrid.flash_attention_step(CFG, 1, S)
    assert flash["flops"] == S * S // 2 * 15 * 3 * 256 * 2
    assert flash["flops"] / v5e["bf16_flops_per_s"] \
        > 5 * flash["bytes"] / v5e["hbm_bytes_per_s"]


def test_copies_match_their_originals():
    for copy, original in CFG["copied_from"].items():
        with open(os.path.join(BENCH_DIR, copy)) as a, \
                open(os.path.join(ROOT, original)) as b:
            assert a.read() == b.read(), copy
    with open(os.path.join(BENCH_DIR, "reference", "olmo_hybrid.py")) as f:
        text = f.read()
    assert "poseidon_tpu" not in text.replace("poseidon_tpu train", "")


def test_traffic_is_packed8k_ep32_s_without_the_settling():
    with open(os.path.join(BENCH_DIR, "traffic", "packed8k_ep32.json")) as f:
        kimi = json.load(f)
    same = ("feed", "precision", "argv", "display", "seq_len",
            "steps_in_file", "trace_steps", "window")
    assert {k: TRAFFIC[k] for k in same} == {k: kimi[k] for k in same}
    docs = dict(TRAFFIC["documents"], why=None)
    assert docs == dict(kimi["documents"], why=None)
    assert "settle_displays" not in TRAFFIC
    assert TRAFFIC["runner"] == "olmo_hybrid_train"


# --------------------------------------------------------------------------- #
# the cell's readers on a hand-made run
# --------------------------------------------------------------------------- #
#   two steps; times in ns
OPS = [("fusion q.1 bf16[8]", 0.0, 10.0),               # l0_gdn_q fwd
       ("fusion scan.2 f32[8]", 10.0, 40.0),            # l0_gdn_scan bwd
       ("fusion conv.3 bf16[8]", 50.0, 6.0),            # l0_gdn_conv_q fwd
       ("pallas-call flash.4 bf16[8]", 60.0, 20.0),     # l3_attn_sdpa bwd
       ("fusion qnorm.5 bf16[8]", 80.0, 4.0),           # l3_attn_qnorm fwd
       ("fusion ffn.6 bf16[8]", 90.0, 30.0),            # l0_ffn_gate bwd
       ("fusion head.7 bf16[8]", 120.0, 12.0),          # lm_head bwd
       ("fusion nll.8 f32[8]", 132.0, 2.0),             # lm_nll fwd
       ("fusion norm.9 bf16[8]", 134.0, 2.0)]           # l0_mix_norm fwd
SCOPES = {"ops": {"q.1": "l0_gdn_q|fwd", "scan.2": "l0_gdn_scan|bwd",
                  "conv.3": "l0_gdn_conv_q|fwd",
                  "flash.4": "l3_attn_sdpa|bwd",
                  "qnorm.5": "l3_attn_qnorm|fwd", "ffn.6": "l0_ffn_gate|bwd",
                  "head.7": "lm_head|bwd", "nll.8": "lm_nll|fwd",
                  "norm.9": "l0_mix_norm|fwd"},
          "recomputed": ["scan.2", "conv.3"],
          "types": {"l0_gdn_q": "INNER_PRODUCT", "l0_gdn_scan": "KDA_SCAN",
                    "l0_gdn_conv_q": "SHORT_CONV",
                    "l3_attn_sdpa": "ATTENTION", "l3_attn_qnorm": "RMS_NORM",
                    "l0_ffn_gate": "INNER_PRODUCT",
                    "lm_head": "INNER_PRODUCT", "lm_nll": "SOFTMAX_NLL",
                    "l0_mix_norm": "RMS_NORM"}}
PEAKS = {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e11}


def small_run(scopes=SCOPES, lm=True):
    run = {"trace": {"steps": 2, "spans": [], "async": {},
                     "devices": {"0": OPS}},
           "steps": 10, "batch_per_chip": 1, "window_s": 4.0,
           "peak_flops_per_s": PEAKS["bf16_flops_per_s"],
           "stats": {"sections": {"step_scopes": scopes} if scopes else {}}}
    if lm:
        run["lm"] = {"seq_len": S,
                     "scopes": CFG["scopes"], "peaks": PEAKS,
                     "flops_per_step": {"ffn": 3e3},
                     "flash_per_step": {"flops": 1e3, "bytes": 10.0},
                     "delta_scan_per_step": {"flops": 1e3, "bytes": 500.0}}
    return run


READERS = [
    (delta_ms_per_step, 28e-6),                       # (10 + 40 + 6) ns / 2
    (delta_scan_ms_per_step, 20e-6),
    # bytes-bound: 500 / 1e11 = 5 ns against 20 ns of scan a step
    (delta_scan_roofline, 100 * 5e-9 / 20e-9),
    (delta_glue_ms_per_step, 3e-6),
    (attention_ms_per_step, 12e-6),              # (20 + 4) / 2
    # flops-bound: 1e3 / 1e12 = 1 ns against 10 ns of kernel a step
    (flash_attention_roofline, 100 * 1e-9 / 10e-9),
    # 3e3 FLOPs over the FFN scopes' 15 ns a step x 1e12
    (ffn_flops_util, 100 * 3e3 / (15e-9 * 1e12)),
    (recompute_ms_per_step, 23e-6),              # (40 + 6) / 2
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


# --------------------------------------------------------------------------- #
# the reference, the runner
# --------------------------------------------------------------------------- #

def test_reference_delta_rule_against_numpy():
    """Gated DeltaNet token by token, written out in NumPy float64: one
    decay a head, d_k != d_v, beta past 1; and the state control rounds."""
    import jax.numpy as jnp
    ref = importlib.import_module("reference.olmo_hybrid")
    r = np.random.RandomState(0)
    s, h, d_k, d_v = 24, 2, 3, 5
    q, k = r.randn(2, s, h, d_k)
    k /= np.linalg.norm(k, axis=-1, keepdims=True)
    v = r.randn(s, h, d_v)
    g = -np.exp(r.uniform(-3, 0, (s, h)))
    beta = r.uniform(0.2, 1.95, (s, h))
    want = np.zeros((s, h, d_v))
    for i in range(h):
        state = np.zeros((d_k, d_v))
        for t in range(s):
            state = np.exp(g[t, i]) * state
            state = state + beta[t, i] * np.outer(
                k[t, i], v[t, i] - state.T @ k[t, i])
            want[t, i] = state.T @ q[t, i] * d_k ** -0.5
    args = [jnp.asarray(x, jnp.float32) for x in (q, k, v, g, beta)]
    got = ref.delta_rule(*args)
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-5)
    np.testing.assert_allclose(ref.delta_rule(*args, t_block=8), got,
                               rtol=1e-6, atol=1e-6)
    assert ref.BETA_MAX == 2.0


def test_compared_rows_say_what_decided():
    import runners.olmo_hybrid_train as runner
    tol = {"logits_rel_l2": 0.03, "scan_rel_l2": 2.1e-3, "loss_rel": None,
           "step_loss_rel": None, "update_norm_rel": 0.1,
           "update_cosine": 0.7, "gate_cosine": 0.5}
    rows = runner.compared(
        {"tolerance": tol, "loss_program": 10.001, "loss_reference": 10.0,
         "logits_rel_l2": 0.017, "lower_precision_rel_l2": 0.34,
         "scan_rel_l2": 1.66e-3, "state_control": {"scan_rel_l2": 8e-3}},
        {"loss_rel": 3e-5, "update_norm_rel": 0.01, "update_cosine": 0.93,
         "lower_precision_update_cosine": 0.23, "gate_cosine": 0.9,
         "lower_precision_gate_cosine": 0.2}, (1.021, 0.96, 1.04))
    by = {r["name"]: r for r in rows}
    assert [r["name"] for r in rows if r["decides_correct"]] == [
        "first_loss_over_expected", "first_loss_over_expected",
        "logits_rel_l2", "scan_rel_l2", "update_norm_rel", "update_cosine",
        "gate_cosine"]
    assert all(r["holds"] for r in rows if r["decides_correct"])
    assert by["step_loss_rel"]["holds"] is None       # facts under bf16
    assert by["loss_rel"]["holds"] is None
    assert by["loss_rel"]["value"] == pytest.approx(1e-4, rel=1e-3)
    assert [r["name"] for r in rows if r["name"].startswith("control_")] == [
        "control_float8_logits_rel_l2", "control_float8_update_cosine",
        "control_float8_gate_cosine", "control_bf16_state_scan_rel_l2"]
    assert all(r["holds"] for r in rows if r["name"].startswith("control_"))


@pytest.mark.parametrize("group,leaf", [("d_g", "l1_gdn_a"),
                                        ("d_g", "l2_gdn_decay"),
                                        ("d_beta", "l0_gdn_b")])
def test_gate_cosines_see_a_sign_that_a_norm_cannot(group, leaf):
    """Adam's first change of a leaf is lr sign(gradient): its norm is the
    same whatever the sign of the scan's d g or d beta, so the leaves behind
    them are held to a direction, each group's as one vector."""
    import runners.olmo_hybrid_train as runner
    rng = np.random.default_rng(0)
    step = {f"l{i}_gdn_{name}": [np.sign(rng.standard_normal(shape))
                                 for shape in shapes]
            for i in range(3) for name, shapes in (
                ("a", [(5, 64)]), ("b", [(5, 64)]), ("decay", [(5,), (5,)]),
                ("q", [(20, 64)]))}
    assert runner.gate_cosines(step, step) == pytest.approx(
        {"d_g": 1.0, "d_beta": 1.0})
    flipped = dict(step)
    flipped[leaf] = [-b for b in step[leaf]]
    got = runner.gate_cosines(flipped, step)
    other = "d_beta" if group == "d_g" else "d_g"
    assert got[other] == pytest.approx(1.0) and got[group] < 0.999
    if leaf.endswith(("_a", "_b")):           # a third of the group's numbers
        assert got[group] < 0.4
    for name in step:                         # and no norm moved
        assert all(np.linalg.norm(a) == np.linalg.norm(b)
                   for a, b in zip(flipped[name], step[name]))


def test_runner_refuses_a_program_from_before_the_model(monkeypatch, capsys):
    """The driver hands the parent this PR's benchmark files: the runner
    looks in the program for what it needs and exits 2 at once, before jax
    is touched."""
    import runners.olmo_hybrid_train as runner
    from poseidon_tpu.models import zoo
    runner.refuse_old_program(CELL)           # this program: fine
    monkeypatch.delattr(zoo, "olmo_hybrid")   # the parent's zoo
    with pytest.raises(SystemExit) as stop:
        runner.refuse_old_program(CELL)
    err = capsys.readouterr().err
    assert stop.value.code == 2 and "zoo.olmo_hybrid" in err


@pytest.mark.parametrize("trace", [0, 1])
def test_cpu_tiny_rehearsal_of_the_olmo_hybrid_cell(trace):
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
    assert check["scan_layer"] == "l2_gdn_scan"
    assert check["scan_rel_l2"] < check["tolerance"]["scan_rel_l2"] \
        < check["state_control"]["scan_rel_l2"]
    step = facts["step_reference"]
    assert step["update_norm_rel"] < step["tolerance"]["update_norm_rel"]
    assert step["lower_precision_update_cosine"] < step["update_cosine"]
    # what was compared, each beside its limit, LAST in the facts line
    assert list(facts)[-1] == "compared"
    decided = [r for r in facts["compared"] if r["decides_correct"]]
    assert len(decided) >= 4 and all(r["holds"] for r in decided)
    assert facts["kernel_routes"] == [
        "attention=dense; no positions",
        "kda=chunked C 64, 2 chunks, f32 state, one decay a head; not "
        "pallas: this backend would interpret the kernels"]
    assert facts["remat_segments"] == DEPTH + 1
    assert sorted(facts["recurrent_state"]) == [
        f"l{i}_gdn_scan" for i in range(3)]
    assert facts["recurrent_state"]["l0_gdn_scan"]["decay"] == "head"
    assert sorted(facts["decay_mean"]) == [
        f"l{i}_decay_mean" for i in range(3)]
    assert sorted(facts["beta_over_one"]) == [
        f"l{i}_beta_over_one" for i in range(3)]
    assert facts["first_loss"] == pytest.approx(
        facts["first_loss_expected"], rel=0.02)
    names = set(line["metrics"])
    if trace:
        # all of the cell's per-layer metrics but those that need a chip's
        # peaks, its memory statistics or its Pallas kernels
        assert names == declared("per_layer", CELL) - {
            "busy_flops_util", "peak_hbm_gb", "delta_scan_roofline",
            "flash_attention_roofline", "ffn_flops_util"}
        m = {k: v["value"] for k, v in line["metrics"].items()}
        assert m["scope_coverage"] >= 95.0
        parts = ("delta_ms_per_step", "attention_ms_per_step")
        assert all(m[k] > 0 for k in parts)
        assert m["delta_scan_ms_per_step"] + m["delta_glue_ms_per_step"] \
            < m["delta_ms_per_step"]
        assert sum(m[k] for k in parts) \
            < m["fwd_ms_per_step"] + m["bwd_ms_per_step"]
        assert m["recompute_ms_per_step"] < m["bwd_ms_per_step"]
    else:
        assert names == declared("end_to_end", CELL) - {"mfu_required"}
        assert line["metrics"]["images_per_s_per_chip"]["value"] == \
            pytest.approx(facts["tokens_per_s_per_chip"] / facts["seq_len"])


def test_new_entries_follow_the_contract():
    cell = next(w for w in BENCH["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) \
        == ("olmo_hybrid_7b", "packed8k_heads", 1)
    assert "layers 0-3 of 32" in cell["why"] \
        and f"{BATCH} x 8192" in cell["why"] and "15 of 30" in cell["why"]
    config = next(c for c in BENCH["configs"]
                  if c["name"] == "olmo_hybrid_7b")
    assert config["reduced"] == CFG["reduced"]
    assert config["source"] == CFG["source"] \
        and config["file"] == "benchmark/configs/olmo_hybrid_7b.json"
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
    # the driver's contract for BENCHMARK.json (the builder's instructions,
    # "per_layer: 1 to 128 metrics"; a file outside its limits is refused
    # before a single run). PR 48 could declare 8 of ISSUE 48's 13 under that
    # cap; since ISSUE 50 the head, the FFN's milliseconds and tokens/s are
    # declared too (the two counters stay facts of the run)
    assert len(mine) == 11 and len(BENCH["per_layer"]) <= 128
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        assert len(f.read()) < 64 * 1024
