"""The Granite cell's yardstick: ``flops_granite`` against a count by hand,
the configuration against the catalog row and its copies, the traffic file,
each of the cell's readers on a hand-made ``layers`` dict (and on a program
without what it reads), the plain reference's recurrence against NumPy at a
toy size, the runner's ``compared`` rows and first-loss expectation, its
refusal of a program from before the model, and the ``--cpu-tiny`` rehearsal
of ``granite_h.p1.pack8k`` end to end. Nothing here pins the per-layer
list's count or its end: a later cell appends to both."""

import importlib
import json
import math
import os

import numpy as np
import pytest

import flops_granite
from conftest import BENCH_DIR, ROOT
from layer_metrics import (attention_ms_per_step, ffn_flops_util,
                           ffn_ms_per_step, flash_attention_roofline,
                           head_ms_per_step, recompute_ms_per_step,
                           ssd_decay_mean, ssd_glue_ms_per_step,
                           ssd_ms_per_step, ssd_scan_ms_per_step,
                           ssd_scan_roofline, tokens_per_s_per_chip)
from test_bench_run import BENCH, declared, entries_of, run_cell

CELL = "granite_h.p1.pack8k"
with open(os.path.join(BENCH_DIR, "configs",
                       "granite_4_0_h_micro.json")) as f:
    CFG = json.load(f)
with open(os.path.join(BENCH_DIR, "cells", CELL + ".json")) as f:
    OWN = json.load(f)
with open(os.path.join(BENCH_DIR, "traffic", "packed8k_period.json")) as f:
    TRAFFIC = json.load(f)
DEPTH, BATCH = CFG["num_hidden_layers"], OWN["batch_per_chip"]
S = 8192
_CATALOG_FILE = "/opt/skills/guides/model-configs/architectures.jsonl"
_rows = []
if os.path.exists(_CATALOG_FILE):
    with open(_CATALOG_FILE) as f:
        _rows = [json.loads(l) for l in f if l.strip()]
# config.json of ibm-granite/granite-4.0-h-micro as the model-configs catalog
# (architectures.jsonl) holds it
CATALOG = {
    "attention_bias": False, "attention_multiplier": 0.015625,
    "embedding_multiplier": 12, "hidden_act": "silu", "hidden_size": 2048,
    "intermediate_size": 8192,
    "layer_types": (["mamba"] * 5 + ["attention"] + ["mamba"] * 4) * 4,
    "logits_scaling": 8, "mamba_chunk_size": 256, "mamba_conv_bias": True,
    "mamba_d_conv": 4, "mamba_d_head": 64, "mamba_d_state": 128,
    "mamba_expand": 2, "mamba_n_groups": 1, "mamba_n_heads": 64,
    "mamba_proj_bias": False, "max_position_embeddings": 131072,
    "model_type": "granitemoehybrid", "normalization_function": "rmsnorm",
    "num_attention_heads": 32, "num_experts_per_tok": 0,
    "num_hidden_layers": 40, "num_key_value_heads": 8,
    "num_local_experts": 0, "position_embedding_type": "nope",
    "residual_multiplier": 0.22, "rms_norm_eps": 1e-05,
    "rope_scaling": None, "rope_theta": 10000,
    "shared_intermediate_size": 8192, "tie_word_embeddings": True,
    "vocab_size": 100352}
REDUCED = {"num_hidden_layers": 10, "vocab_size": 12544}


def test_the_catalog_row_is_the_one_copied_here():
    if not _rows:
        pytest.skip("no catalog on this machine")
    row = next(r for r in _rows if r["name"] == "granite-4.0-h-micro")
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
    assert CFG["layers_run"]["layer_types"] == CATALOG["layer_types"][:10]
    # no width in `reduced`
    assert not any(k.endswith(("_dim", "_rank", "_size")) and k != "vocab_size"
                   for k in CFG["reduced"])
    assert "772,160,448" in CFG["reduced_how"]["total"]
    assert CFG["published"]["parameters"] == 3_191_396_096
    assert "four pipeline stages" in CFG["deployment"]
    for key in ("a_block_order", "b_in_proj", "c_short_conv",
                "d_step_and_decay", "e_skip", "f_gate_then_norm",
                "g_attention", "h_multipliers", "i_packing", "j_init"):
        assert key in CFG["assumed"]
    for key in ("precision", "what_the_cut_changes", "scopes", "cpu_tiny"):
        assert CFG[key]


@pytest.mark.parametrize("part,macs", [
    # 9 mamba layers: in_proj 2048 x 8512, out_proj 4096 x 2048
    ("ssd_projections", 9 * (17_432_576 + 8_388_608)),
    ("ssd_recurrence", 9 * 2 * 64 * 64 * 128),
    ("attention_projections", 2 * 4_194_304 + 2 * 1_048_576),
    ("attention", 32 * 128 * 4096),
    ("ffn", 10 * 50_331_648),
    ("head", 2048 * 12544)])
def test_required_macs_against_hand_counts(part, macs):
    assert flops_granite.required_macs_per_token(CFG, S)[part] == macs


def test_required_flops_and_shares():
    """ISSUE 54's arithmetic: 798M MACs = 4.79 GFLOP a token, 39.2 TFLOP a
    step; MLP 63%, Mamba-2 30%, attention 3.4%, head 3.2%."""
    macs = flops_granite.required_macs_per_token(CFG, S)
    total = sum(macs.values())
    assert total == 798_097_408
    flops = flops_granite.required_flops_per_token(CFG, S)
    assert flops["total"] == 6 * total
    assert flops["total"] * S == pytest.approx(39.23e12, rel=1e-3)
    share = lambda *parts: sum(macs[p] for p in parts) / total
    assert share("ffn") == pytest.approx(0.631, abs=1e-3)
    assert share("ssd_projections", "ssd_recurrence") == pytest.approx(
        0.303, abs=1e-3)
    assert share("attention_projections", "attention") == pytest.approx(
        0.034, abs=1e-3)
    assert share("head") == pytest.approx(0.032, abs=1e-3)


def test_the_scan_s_floor_is_its_bytes_and_the_flash_kernels_their_flops():
    """What the two roofline shares divide, at the cell's shapes on the
    v5e: the nine recurrences' required FLOPs take 2.4 ms at the matrix peak
    and their bytes 3.1 ms at memory speed (bytes-bound); the flash kernels
    2.1 ms of FLOPs against 0.5 ms of bytes."""
    with open(os.path.join(BENCH_DIR, "peaks.json")) as f:
        v5e = json.load(f)["TPU v5 lite"]
    scan = flops_granite.ssd_scan_step(CFG, 1, S)
    assert scan["flops"] == 9 * S * 3 * 2 * 64 * 64 * 128 * 2
    assert scan["bytes"] == 9 * S * 2 * ((2 * 4096 + 2 * 128) * 2
                                         + 2 * 64 * 4)
    assert scan["bytes"] / v5e["hbm_bytes_per_s"] \
        > scan["flops"] / v5e["bf16_flops_per_s"]
    flash = flops_granite.flash_attention_step(CFG, 1, S)
    assert flash["flops"] == S * S // 2 * 32 * 3 * 128 * 2
    assert flash["bytes"] == S * 2 * (6 * 32 + 6 * 8) * 64
    assert flash["flops"] / v5e["bf16_flops_per_s"] \
        > 3 * flash["bytes"] / v5e["hbm_bytes_per_s"]


def test_copies_match_their_originals():
    for copy, original in CFG["copied_from"].items():
        with open(os.path.join(BENCH_DIR, copy)) as a, \
                open(os.path.join(ROOT, original)) as b:
            assert a.read() == b.read(), copy
    with open(os.path.join(BENCH_DIR, "reference",
                           "granite_hybrid.py")) as f:
        text = f.read()
    assert "poseidon_tpu" not in text


def test_traffic_is_packed8k_heads_under_its_own_name():
    with open(os.path.join(BENCH_DIR, "traffic", "packed8k_heads.json")) as f:
        olmo = json.load(f)
    same = ("feed", "precision", "argv", "display", "seq_len",
            "steps_in_file", "trace_steps", "window")
    assert {k: TRAFFIC[k] for k in same} == {k: olmo[k] for k in same}
    docs = dict(TRAFFIC["documents"], why=None)
    assert docs == dict(olmo["documents"], why=None)
    assert "settle_displays" not in TRAFFIC
    assert TRAFFIC["runner"] == "granite_train" \
        and TRAFFIC["name"] == "packed8k_period"


# --------------------------------------------------------------------------- #
# the cell's readers on a hand-made run
# --------------------------------------------------------------------------- #
#   two steps; times in ns
OPS = [("fusion in.1 bf16[8]", 0.0, 10.0),              # l0_ssd_in fwd
       ("pallas-call scan.2 f32[8]", 10.0, 40.0),       # l0_ssd_scan bwd
       ("fusion conv.3 bf16[8]", 50.0, 6.0),            # l0_ssd_conv fwd
       ("pallas-call flash.4 bf16[8]", 60.0, 20.0),     # l5_attn_sdpa bwd
       ("fusion q.5 bf16[8]", 80.0, 4.0),               # l5_attn_q fwd
       ("fusion ffn.6 bf16[8]", 90.0, 30.0),            # l0_ffn_in bwd
       ("fusion head.7 bf16[8]", 120.0, 12.0),          # lm_head bwd
       ("fusion nll.8 f32[8]", 132.0, 2.0),             # lm_nll fwd
       ("fusion norm.9 bf16[8]", 134.0, 2.0)]           # l0_norm1 fwd
SCOPES = {"ops": {"in.1": "l0_ssd_in|fwd", "scan.2": "l0_ssd_scan|bwd",
                  "conv.3": "l0_ssd_conv|fwd", "flash.4": "l5_attn_sdpa|bwd",
                  "q.5": "l5_attn_q|fwd", "ffn.6": "l0_ffn_in|bwd",
                  "head.7": "lm_head|bwd", "nll.8": "lm_nll|fwd",
                  "norm.9": "l0_norm1|fwd"},
          "recomputed": ["in.1", "conv.3"],
          "types": {"l0_ssd_in": "INNER_PRODUCT", "l0_ssd_scan": "SSD_SCAN",
                    "l0_ssd_conv": "SHORT_CONV", "l5_attn_sdpa": "ATTENTION",
                    "l5_attn_q": "INNER_PRODUCT",
                    "l0_ffn_in": "INNER_PRODUCT", "lm_head": "INNER_PRODUCT",
                    "lm_nll": "SOFTMAX_NLL", "l0_norm1": "RMS_NORM"}}
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
                     "ssd_scan_per_step": {"flops": 1e3, "bytes": 500.0},
                     "ssd_decay_mean": [0.9, 0.8, 0.7]}
    return run


READERS = [
    (ssd_ms_per_step, 28e-6),                         # (10 + 40 + 6) ns / 2
    (ssd_scan_ms_per_step, 20e-6),
    # bytes-bound: 500 / 1e11 = 5 ns against 20 ns of scan a step
    (ssd_scan_roofline, 100 * 5e-9 / 20e-9),
    (ssd_glue_ms_per_step, 3e-6),
    (attention_ms_per_step, 12e-6),                   # (20 + 4) / 2
    # flops-bound: 1e3 / 1e12 = 1 ns against 10 ns of kernel a step
    (flash_attention_roofline, 100 * 1e-9 / 10e-9),
    (ffn_ms_per_step, 15e-6),
    # 3e3 FLOPs over the MLP scopes' 15 ns a step x 1e12
    (ffn_flops_util, 100 * 3e3 / (15e-9 * 1e12)),
    (head_ms_per_step, 7e-6),                         # (12 + 2) / 2
    (recompute_ms_per_step, 8e-6),                    # (10 + 6) / 2
    (ssd_decay_mean, 0.8),
    (tokens_per_s_per_chip, 10 * S / 4.0),
]
MAP_ONLY = (recompute_ms_per_step,)            # reads the map alone


@pytest.mark.parametrize("reader, want", READERS)
def test_each_reader_on_a_hand_made_run(reader, want):
    assert reader.reduce(small_run()) == pytest.approx(want)


@pytest.mark.parametrize("reader", [r for r, _ in READERS])
def test_each_reader_finds_nothing_on_a_program_without_it(reader):
    """A program or a run without what the reader reads (the parent has no
    SSD_SCAN, so no such scope and no ``ssd_*`` key): no map, no ``lm``
    section, no trace — None, and nothing raised."""
    assert reader.reduce(small_run(scopes=None, lm=False)) is None
    if reader not in MAP_ONLY:
        assert reader.reduce(small_run(lm=False)) is None
    assert reader.reduce({}) is None


# --------------------------------------------------------------------------- #
# the reference, the runner
# --------------------------------------------------------------------------- #

def test_reference_recurrence_against_numpy():
    """Mamba-2 token by token, written out in NumPy float64: B and C shared
    by the heads, the skip; blocks of tokens change nothing; the state
    control rounds."""
    import jax.numpy as jnp
    ref = importlib.import_module("reference.granite_hybrid")
    r = np.random.RandomState(0)
    s, h, p, n = 24, 3, 4, 5
    x, dt = r.randn(s, h, p), r.uniform(0.01, 0.5, (s, h))
    a = -dt * r.uniform(1, 16, h)
    b, c, d = r.randn(s, n), r.randn(s, n), r.randn(h)
    want = np.zeros((s, h, p))
    for i in range(h):
        state = np.zeros((p, n))
        for t in range(s):
            state = np.exp(a[t, i]) * state \
                + dt[t, i] * np.outer(x[t, i], b[t])
            want[t, i] = state @ c[t] + d[i] * x[t, i]
    args = [jnp.asarray(t, jnp.float32) for t in (x, dt, a, b, c, d)]
    got = ref.ssd(*args)
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-5)
    np.testing.assert_allclose(ref.ssd(*args, t_block=8), got,
                               rtol=1e-6, atol=1e-6)
    low = ref.ssd(*args, state_round=lambda st: ref.narrowed(
        st, jnp.bfloat16))
    assert 1e-4 < np.linalg.norm(low - got) / np.linalg.norm(got) < 0.1


def test_compared_rows_say_what_decided():
    from runners.token_checks import compared
    tol = {"logits_rel_l2": 0.05, "scan_rel_l2": 3e-4,
           "scan_grad_rel_l2": 1e-3, "loss_rel": None,
           "step_loss_rel": None, "update_norm_rel": 0.1,
           "update_cosine": 0.7, "group_cosine": 0.8}
    rows = compared(
        tol, (1.001, 0.99, 1.01),
        [("logits_rel_l2", 0.017), ("scan_rel_l2", 1e-5),
         ("scan_grad_rel_l2", 2e-3), ("loss_rel", 1e-4)],
        {"loss_rel": 3e-5, "update_norm_rel": 0.01, "update_cosine": 0.93,
         "group_cosine": 0.9, "group_cosines": {"d_a": 0.9, "d_D": 0.95}},
        [("float8_update_cosine", 0.23, "<", "update_cosine"),
         ("bf16_state_scan_rel_l2", 8e-3, ">", "scan_rel_l2")])
    by = {r["name"]: r for r in rows}
    assert [r["name"] for r in rows if r["decides_correct"]] == [
        "first_loss_over_expected", "first_loss_over_expected",
        "logits_rel_l2", "scan_rel_l2", "scan_grad_rel_l2",
        "update_norm_rel", "update_cosine", "group_cosine"]
    assert [r["name"] for r in rows
            if r["decides_correct"] and not r["holds"]] \
        == ["scan_grad_rel_l2"]
    assert by["step_loss_rel"]["holds"] is None       # facts under bf16
    assert by["loss_rel"]["holds"] is None
    assert [r["name"] for r in rows if r["name"].startswith("control_")] == [
        "control_float8_update_cosine", "control_bf16_state_scan_rel_l2"]
    assert all(r["holds"] for r in rows if r["name"].startswith("control_"))


def test_grouped_cosines_take_leaves_by_suffix_blob_and_channels():
    """Every layer's part of a group as ONE vector; a group's sign flipped
    reads -1 whatever the other groups do."""
    import runners.granite_train as runner
    from runners.token_checks import grouped_cosines
    model = {"mamba_n_heads": 4, "mamba_d_head": 2, "mamba_d_state": 3}
    groups = runner.scan_leaves(model)
    assert groups["d_BC"] == [("_ssd_conv", 0, (8, 14)),
                              ("_ssd_conv", 1, (8, 14))]
    r = np.random.RandomState(1)
    step = {f"l{i}_{name}": [r.randn(*shape) for shape in shapes]
            for i in (0, 2) for name, shapes in (
                ("ssd_decay", [(4,), (4,)]), ("ssd_scan", [(4,)]),
                ("ssd_conv", [(4, 14), (14,)]), ("ssd_in", [(30, 16)]))}
    same = grouped_cosines(step, step, groups)
    assert sorted(same) == ["d_BC", "d_D", "d_a", "d_dt", "d_x"]
    assert all(v == pytest.approx(1.0) for v in same.values())
    flipped = {k: [b.copy() for b in v] for k, v in step.items()}
    for i in (0, 2):
        flipped[f"l{i}_ssd_decay"][0] *= -1           # A_log alone
        flipped[f"l{i}_ssd_conv"][0][:, 8:] *= -1      # the taps of B and C
    got = grouped_cosines(flipped, step, groups)
    assert got["d_a"] == pytest.approx(-1.0)
    assert all(got[k] == pytest.approx(1.0) for k in ("d_dt", "d_D", "d_x"))
    assert -1.0 < got["d_BC"] < 0.0                    # the bias kept its sign


def test_first_loss_expectation_divides_the_logits_by_eight():
    """ln V + 0.02^2 x 2048 / 64 / 2: nearly flat logits. Without the
    division the expectation would sit 4.3% higher, outside the band."""
    import runners.granite_train as runner
    want = runner.expected_first_loss(CFG, CFG)
    assert want == pytest.approx(math.log(12544) + 0.0064, abs=1e-9)
    low, high = CFG["first_loss_band"]
    undivided = math.log(12544) + 0.02 ** 2 * 2048 / 2
    assert undivided > high * want and low * want < math.log(12544)


def test_runner_refuses_a_program_from_before_the_model(monkeypatch, capsys):
    """The driver hands the parent this PR's benchmark files: the runner
    looks in the program for what it needs and exits 2 at once, before jax
    is touched."""
    import runners.granite_train as runner
    from poseidon_tpu.models import zoo
    runner.refuse_old_program(CELL)           # this program: fine
    monkeypatch.delattr(zoo, "granite_hybrid")    # the parent's zoo
    with pytest.raises(SystemExit) as stop:
        runner.refuse_old_program(CELL)
    err = capsys.readouterr().err
    assert stop.value.code == 2 and "zoo.granite_hybrid" in err


@pytest.mark.parametrize("trace", [0, 1])
def test_cpu_tiny_rehearsal_of_the_granite_cell(trace):
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
    assert check["scan_layer"] == "l9_ssd_scan"
    assert check["scan_rel_l2"] < check["tolerance"]["scan_rel_l2"]
    assert check["scan_rel_l2"] < check["state_control"]["scan_rel_l2"]
    # the scan's backward, held twice: its six gradients against jax.grad
    # of the recurrence, and the first update of the leaves only it feeds
    assert sorted(check["scan_grads_rel_l2"]) == [
        "d_B", "d_C", "d_D", "d_a", "d_dt", "d_x"]
    assert check["scan_grad_rel_l2"] == max(
        check["scan_grads_rel_l2"].values())
    assert check["scan_grad_rel_l2"] \
        < check["tolerance"]["scan_grad_rel_l2"] \
        < check["state_control"]["scan_grad_rel_l2"]
    assert check["scan_skip_norm_over_y"] > 0.0
    step = facts["step_reference"]
    assert sorted(step["group_cosines"]) == [
        "d_BC", "d_D", "d_a", "d_dt", "d_x"]
    assert step["group_cosine"] == min(step["group_cosines"].values()) \
        >= step["tolerance"]["group_cosine"]
    # a traced run's stall ledger, totals only (the recorder is on)
    if trace:
        assert facts["stalls"]["steps"] >= 4 \
            and "lost_ms_by_cause" in facts["stalls"]
    else:
        assert facts["stalls"] is None
    assert step["update_norm_rel"] < step["tolerance"]["update_norm_rel"]
    assert step["lower_precision_update_cosine"] < step["update_cosine"]
    # what was compared, each beside its limit, LAST in the facts line
    assert list(facts)[-1] == "compared"
    decided = [r for r in facts["compared"] if r["decides_correct"]]
    assert {"scan_rel_l2", "scan_grad_rel_l2", "update_cosine",
            "group_cosine"} <= {r["name"] for r in decided}
    assert all(r["holds"] for r in decided)
    assert facts["kernel_routes"] == [
        "attention=dense; 8 kv heads repeated x4; no positions",
        "ssd_scan=chunked Q 128, 1 chunks, f32 state, one C B^T grid a "
        "chunk; not pallas: heads of 2 are no whole part of a lane block "
        "of 128"]
    assert facts["remat_segments"] == DEPTH + 1
    mamba = [i for i in range(10) if i != 5]
    assert sorted(facts["recurrent_state"]) == [
        f"l{i}_ssd_scan" for i in mamba]
    assert sorted(facts["decay_mean"]) == [
        f"l{i}_ssd_decay_mean" for i in mamba]
    assert sorted(facts["dt_mean"]) == [f"l{i}_ssd_dt_mean" for i in mamba]
    assert facts["first_loss"] == pytest.approx(
        facts["first_loss_expected"], rel=0.005)
    names = set(line["metrics"])
    if trace:
        # all of the cell's per-layer metrics but those that need a chip's
        # peaks, its memory statistics or its Pallas kernels
        assert names == declared("per_layer", CELL) - {
            "busy_flops_util", "peak_hbm_gb", "ssd_scan_roofline",
            "flash_attention_roofline", "ffn_flops_util"}
        m = {k: v["value"] for k, v in line["metrics"].items()}
        assert m["scope_coverage"] >= 95.0
        parts = ("ssd_ms_per_step", "attention_ms_per_step",
                 "ffn_ms_per_step", "head_ms_per_step")
        assert all(m[k] > 0 for k in parts)
        assert m["ssd_scan_ms_per_step"] + m["ssd_glue_ms_per_step"] \
            < m["ssd_ms_per_step"]
        assert sum(m[k] for k in parts) \
            < m["fwd_ms_per_step"] + m["bwd_ms_per_step"]
        assert m["recompute_ms_per_step"] < m["bwd_ms_per_step"]
        assert 0.0 < m["ssd_decay_mean"] < 1.0
    else:
        assert names == declared("end_to_end", CELL) - {"mfu_required"}
        assert line["metrics"]["images_per_s_per_chip"]["value"] == \
            pytest.approx(facts["tokens_per_s_per_chip"] / facts["seq_len"])


# a program with ONE fault planted, run through the harness's own entry at
# the rehearsal's sizes: {fault: (what is planted before run.py starts, the
# rows of ``compared`` that have to break)}
_PLANTED = {
    # the scan's backward hands d a back with the wrong sign: every leaf it
    # feeds lies under ``cosine_from`` and Adam's first change has the same
    # norm whatever its direction
    "d_a_sign": ("""
from poseidon_tpu.ops import ssd
honest = ssd._ssd_bwd
def faulty(q, res, d_y):
    g = list(honest(q, res, d_y))
    g[2] = -g[2]
    return tuple(g)
ssd._ssd_chunked.defvjp(ssd._ssd_fwd, faulty)
""", {"scan_grad_rel_l2", "group_cosine"}),
    # the solver leaves one leaf (the tied table) as it was
    "leaf_unchanged": ("""
from poseidon_tpu.solvers import updates
honest = updates._adam
def faulty(sp, w, g, m, v, *rest):
    new, m, v = honest(sp, w, g, m, v, *rest)
    return (w if w.shape == (512, 64) else new), m, v
updates._adam = faulty
""", {"update_norm_rel"}),
    # the loss counts the first half of every sequence's positions
    "half_the_positions": ("""
import jax.numpy as jnp
from poseidon_tpu.core import layers
honest = layers.SoftmaxNLLLayer.apply
def faulty(self, params, bottoms, ctx):
    (nll,) = honest(self, params, bottoms, ctx)
    return [nll * (jnp.arange(nll.shape[1]) < nll.shape[1] // 2)]
layers.SoftmaxNLLLayer.apply = faulty
""", {"first_loss_over_expected"}),
}


@pytest.mark.parametrize("fault", sorted(_PLANTED))
def test_a_planted_fault_reads_not_correct(fault, tmp_path):
    """The limits that have no lower-precision reading behind them
    (``update_norm_rel``, the first-loss band) and the scan's backward are
    held against the fault each is there for: the harness has to print
    ``correct: false`` and name the row that broke."""
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
        == ("granite_4_0_h_micro", "packed8k_period", 1)
    assert "layers 0-9 of 40" in cell["why"] \
        and f"{BATCH} x 8192" in cell["why"] and "sequences/s" in cell["why"]
    config = next(c for c in BENCH["configs"]
                  if c["name"] == "granite_4_0_h_micro")
    assert config["reduced"] == CFG["reduced"] \
        == ["num_hidden_layers", "vocab_size"]
    assert config["source"] == CFG["source"] \
        and config["file"] == "benchmark/configs/granite_4_0_h_micro.json"
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
    assert BATCH == 1
    assert len(BENCH["per_layer"]) <= 128 and len(BENCH["workloads"]) <= 24
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        assert len(f.read()) < 64 * 1024
