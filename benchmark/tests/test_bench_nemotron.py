"""The Nemotron cell's yardstick: ``flops_nemotron`` against a count by hand,
the configuration against the catalog row and its copies, the traffic file,
the cell's reader files (one import each) and its own reader on a hand-made
``layers`` dict, the plain reference's grouped recurrence against NumPy at a
toy size, the runner's refusal of a program from before the model, the
``--cpu-tiny`` rehearsal of ``nemotron_h.e8of128.pack8k`` end to end and
three planted faults. Nothing here pins the per-layer list's count or its
end: a later cell appends to both."""

import ast
import importlib
import json
import os

import numpy as np
import pytest

import flops_nemotron
from conftest import BENCH_DIR, ROOT
from test_bench_run import BENCH, declared, run_cell

CELL = "nemotron_h.e8of128.pack8k"
NAME = "nemotron_3_nano_30b_a3b"
with open(os.path.join(BENCH_DIR, "configs", NAME + ".json")) as f:
    CFG = json.load(f)
with open(os.path.join(BENCH_DIR, "cells", CELL + ".json")) as f:
    OWN = json.load(f)
with open(os.path.join(BENCH_DIR, "traffic", "packed8k_ep16.json")) as f:
    TRAFFIC = json.load(f)
BATCH, S = OWN["batch_per_chip"], 8192
_CATALOG_FILE = "/opt/skills/guides/model-configs/architectures.jsonl"
REDUCED = {"num_hidden_layers": (9, 52), "n_routed_experts": (8, 128),
           "vocab_size": (16384, 131072)}
# the measurements this cell shares with accepted cells, each under a name
# of its own whose reader file is one import of the shared reader
SHARED = ("tokens_per_s_per_chip", "ssd_ms_per_step", "ssd_scan_ms_per_step",
          "ssd_scan_roofline", "ssd_glue_ms_per_step", "ssd_decay_mean",
          "attention_ms_per_step", "flash_attention_roofline",
          "head_ms_per_step", "held_moe_ms_per_step", "held_moe_flops_util",
          "router_ms_per_step", "shared_expert_ms_per_step",
          "held_assignment_share", "held_load_max_over_mean",
          "held_dropped_assignments", "recompute_ms_per_step")
MINE = tuple(f"nemotron_{name}" for name in SHARED) \
    + ("nemotron_act_zero_share",)


def test_configuration_holds_every_number_of_the_catalog_row():
    """Every key of the source under the same name; a number that differs is
    in ``reduced`` and its published value in ``published``."""
    if not os.path.exists(_CATALOG_FILE):
        pytest.skip("no catalog on this machine")
    with open(_CATALOG_FILE) as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "NVIDIA-Nemotron-3-Nano-30B-A3B-BF16")
    assert row["source_url"] == CFG["source"]
    for key, value in row["config"].items():
        if key in REDUCED:
            assert (CFG[key], CFG["published"][key]) == REDUCED[key] \
                and value == REDUCED[key][1], key
        else:
            assert CFG[key] == value, key
    assert CFG["hybrid_override_pattern"].startswith(
        CFG["layers_run"]["pattern"])


def test_the_cut_is_the_issue_s():
    assert sorted(CFG["reduced"]) == sorted(REDUCED)
    assert CFG["layers_run"]["pattern"] == "MEMEM*EME"
    assert CFG["router_num_experts"] == 128 \
        and CFG["num_experts_per_tok"] == 6
    # no width in `reduced`
    assert not any(k.endswith(("_dim", "_rank", "_size")) and k != "vocab_size"
                   for k in CFG["reduced"])
    assert "666,962,944" in CFG["reduced_how"]["total"]
    assert CFG["published"]["parameters"] == 31_577_937_344
    assert "16 chips share each layer" in CFG["deployment"] \
        and "FIRST" in CFG["deployment"]
    for key in ("a_inner_width", "b_no_positions", "c_projection_order",
                "d_gate_then_norm", "e_selection_bias", "f_init",
                "g_recipe_and_packing", "h_chunk_size"):
        assert key in CFG["assumed"]
    for key in ("precision", "what_the_cut_changes", "scopes", "cpu_tiny"):
        assert CFG[key]


@pytest.mark.parametrize("name", ["train", "solver"])
def test_the_benchmark_s_prototxts_are_the_examples(name):
    """Drift: the benchmark runs its own copies."""
    copy = CFG["net" if name == "train" else "solver"]
    with open(os.path.join(BENCH_DIR, copy)) as f:
        mine = f.read()
    with open(os.path.join(ROOT, CFG["copied_from"][copy])) as f:
        assert f.read() == mine
    if name == "train":
        assert CFG["paths"]["train_source"] in mine
        for field, values in CFG["cpu_tiny"]["prototxt_fields"].items():
            for value in values:
                assert f"{field}: {value}\n" in mine, (field, value)
    else:
        flags = " ".join(a for a in TRAFFIC["argv"] if a.startswith("--")
                         and "{" not in a).replace("=", " '", 1) + "'"
        assert "--bf16" in mine and flags.split(" ", 1)[1] in mine


def test_the_traffic_is_the_issue_s():
    assert (TRAFFIC["runner"], TRAFFIC["seq_len"], TRAFFIC["display"],
            TRAFFIC["settle_displays"], TRAFFIC["trace_steps"],
            TRAFFIC["steps_in_file"]) == ("nemotron_train", 8192, 4, 4, 4, 8)
    mix = TRAFFIC["documents"]
    assert (mix["doc_len_median"], mix["doc_len_sigma"], mix["doc_len_min"],
            mix["doc_len_max"], mix["zipf_exponent"],
            mix["end_of_text_id"]) == (512, 1.2, 16, 8192, 1.0, 0)
    assert "--remat=/l\\d+_/,/lm_/" in TRAFFIC["argv"] and BATCH == 2


@pytest.mark.parametrize("part,macs", [
    # 4 Mamba-2 layers: in_proj 2688 x 10304, out_proj 4096 x 2688
    ("ssd_projections", 4 * (27_697_152 + 11_010_048)),
    ("ssd_recurrence", 4 * 2 * 64 * 64 * 128),
    ("attention_projections", 2 * 11_010_048 + 2 * 688_128),
    ("attention", 32 * 2 * 128 * 4096),
    ("router", 4 * 2688 * 128),
    # 6 of a token's experts, 8 of 128 held at an even split, TWO products
    ("experts", 4 * 6 * 2 * 2688 * 1856 * 8 // 128),
    ("shared_expert", 4 * 2 * 2688 * 3712),
    ("head", 2688 * 16384)])
def test_required_macs_against_hand_counts(part, macs):
    assert flops_nemotron.required_macs_per_token(CFG, S)[part] == macs


def test_required_flops_and_shares():
    """ISSUE 64's arithmetic: 2.1 GFLOP a token; Mamba-2 about 44%, the
    sparse layers 27% (shared expert 22, held experts 4), attention 16%, the
    head 12.5%."""
    macs = flops_nemotron.required_macs_per_token(CFG, S)
    total = sum(macs.values())
    flops = flops_nemotron.required_flops_per_token(CFG, S)
    assert flops["total"] == 6 * total == pytest.approx(2.137e9, rel=2e-3)
    share = lambda *parts: sum(macs[p] for p in parts) / total
    assert share("ssd_projections", "ssd_recurrence") == pytest.approx(
        0.447, abs=2e-3)
    assert share("router", "experts", "shared_expert") == pytest.approx(
        0.270, abs=2e-3)
    assert share("shared_expert") == pytest.approx(0.224, abs=2e-3)
    assert share("experts") == pytest.approx(0.042, abs=2e-3)
    assert share("attention_projections", "attention") == pytest.approx(
        0.160, abs=2e-3)
    assert share("head") == pytest.approx(0.124, abs=2e-3)
    assert flops_nemotron.expert_flops_per_assignment(CFG) \
        == 2 * 2688 * 1856 * 6


def test_the_scan_s_floor_is_its_bytes_with_every_group_s_keys_counted():
    with open(os.path.join(BENCH_DIR, "peaks.json")) as f:
        v5e = json.load(f)["TPU v5 lite"]
    scan = flops_nemotron.ssd_scan_step(CFG, BATCH, S)
    assert scan["flops"] == 4 * BATCH * S * 3 * 2 * 64 * 64 * 128 * 2
    assert scan["bytes"] == 4 * BATCH * S * 2 * (
        (2 * 4096 + 2 * 8 * 128) * 2 + 2 * 64 * 4)
    assert scan["bytes"] / v5e["hbm_bytes_per_s"] \
        > scan["flops"] / v5e["bf16_flops_per_s"]
    flash = flops_nemotron.flash_attention_step(CFG, BATCH, S)
    assert flash["flops"] == BATCH * S * S // 2 * 32 * 3 * 2 * 128 * 2
    assert flash["bytes"] == BATCH * S * 2 * (6 * 32 + 6 * 2) * 128
    assert flash["flops"] / v5e["bf16_flops_per_s"] \
        > flash["bytes"] / v5e["hbm_bytes_per_s"]


def test_reference_recurrence_reads_a_head_s_own_group():
    """``reference/nemotron_h.ssd`` against a NumPy loop at a toy size: 4
    heads in 2 groups."""
    ref = importlib.import_module("reference.nemotron_h")
    r = np.random.RandomState(0)
    s, h, p, g, n = 12, 4, 3, 2, 5
    x, dt = r.randn(s, h, p), np.log1p(np.exp(r.randn(s, h)))
    a, d = -dt * r.uniform(0.5, 2.0, h), r.randn(h)
    b, c = r.randn(s, g, n), r.randn(s, g, n)
    want, state = np.zeros((s, h, p)), np.zeros((h, p, n))
    for t in range(s):
        for k in range(h):
            state[k] = np.exp(a[t, k]) * state[k] \
                + dt[t, k] * np.outer(x[t, k], b[t, k // 2])
            want[t, k] = state[k] @ c[t, k // 2] + d[k] * x[t, k]
    got = ref.ssd(*(np.asarray(t, np.float32) for t in (x, dt, a, b, c, d)))
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)


def test_new_entries_follow_the_contract():
    cell = next(w for w in BENCH["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) \
        == (NAME, "packed8k_ep16", 1)
    assert "layers 0-8 of 52" in cell["why"] \
        and f"{BATCH} x 8192" in cell["why"] and "8 of 128" in cell["why"]
    config = next(c for c in BENCH["configs"] if c["name"] == NAME)
    assert config["reduced"] == CFG["reduced"] == list(REDUCED)
    assert config["source"] == CFG["source"] \
        and config["file"] == f"benchmark/configs/{NAME}.json"
    by_name = {m["name"]: m for m in BENCH["per_layer"]}
    assert {m["name"] for m in BENCH["per_layer"]
            if CELL in m.get("workloads", ())} == set(MINE)
    assert len(MINE) <= 22
    for text in (cell["why"], config["why"], config["source"]):
        assert 1 <= len(text) <= 200 and text.isascii() \
            and text.isprintable(), text
    for name in MINE:
        m = by_name[name]
        assert set(m) == {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"} and m["workloads"] == [CELL]
        assert m["moves"] == "mfu_required"
        if name.endswith("_roofline"):
            assert m["unit"] == "%" and m["better"] == "higher"
    for shared in SHARED:                # the shared entry's own fields
        twin = by_name[shared]
        assert {k: by_name[f"nemotron_{shared}"][k] for k in (
            "unit", "better", "source", "layer", "moves")} \
            == {k: twin[k] for k in ("unit", "better", "source", "layer",
                                     "moves")}, shared
        assert CELL not in twin.get("workloads", [CELL + "?"])
    assert len(BENCH["per_layer"]) <= 128 and len(BENCH["workloads"]) <= 24
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        assert len(f.read()) < 64 * 1024


@pytest.mark.parametrize("shared", SHARED)
def test_a_shared_measurement_s_reader_is_one_import(shared):
    """A docstring and ONE import of the shared reader's ``reduce``: no
    arithmetic copied, the same function object."""
    path = os.path.join(BENCH_DIR, "layer_metrics", f"nemotron_{shared}.py")
    with open(path) as f:
        tree = ast.parse(f.read())
    assert ast.get_docstring(tree) and len(tree.body) == 2
    imp = tree.body[1]
    assert isinstance(imp, ast.ImportFrom) \
        and imp.module == f"layer_metrics.{shared}" \
        and [a.name for a in imp.names] == ["reduce"]
    mine = importlib.import_module(f"layer_metrics.nemotron_{shared}")
    assert mine.reduce is importlib.import_module(
        f"layer_metrics.{shared}").reduce


def test_the_cell_s_own_reader_and_a_program_without_the_counter():
    reader = importlib.import_module("layer_metrics.nemotron_act_zero_share")
    assert reader.reduce({"lm": {"act_zero_share": [0.5, 0.25]}}) == 37.5
    # the parent's program publishes no such top: left out, never 0
    assert reader.reduce({"lm": {"act_zero_share": []}}) is None
    assert reader.reduce({"lm": {}}) is None and reader.reduce({}) is None


def test_the_runner_refuses_a_program_from_before_the_model(monkeypatch,
                                                            capsys):
    from poseidon_tpu.models import zoo
    runner = importlib.import_module("runners.nemotron_train")
    runner.refuse_old_program(CELL)           # this program: fine
    monkeypatch.delattr(zoo, "nemotron_h")    # the parent's zoo
    with pytest.raises(SystemExit) as stop:
        runner.refuse_old_program(CELL)
    err = capsys.readouterr().err
    assert stop.value.code == 2 and "zoo.nemotron_h" in err


@pytest.mark.parametrize("trace", [0, 1])
def test_cpu_tiny_rehearsal_of_the_nemotron_cell(trace):
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
    assert (check["scan_layer"], check["routed_layer"]) \
        == ("l7_ssd_scan", "l1_moe_experts")
    assert check["scan_rel_l2"] < check["tolerance"]["scan_rel_l2"] \
        < check["state_control"]["scan_rel_l2"]
    assert sorted(check["scan_grads_rel_l2"]) == [
        "d_B", "d_C", "d_D", "d_a", "d_dt", "d_x"]
    assert check["scan_grad_rel_l2"] \
        < check["tolerance"]["scan_grad_rel_l2"] \
        < check["state_control"]["scan_grad_rel_l2"]
    assert check["routed_rel_l2"] < check["lower_precision_routed_rel_l2"]
    assert check["routed_tokens_held"] > 0
    step = facts["step_reference"]
    assert sorted(step["group_cosines"]) == [
        "d_BC", "d_D", "d_a", "d_dt", "d_x"]
    assert step["group_cosine"] == min(step["group_cosines"].values()) \
        >= step["tolerance"]["group_cosine"]
    assert step["leaf_cosine_min"] <= step["update_cosine"]
    assert len(step["reference_held_share"]) == 4
    assert list(facts)[-1] == "compared"
    decided = [r for r in facts["compared"] if r["decides_correct"]]
    assert {"scan_rel_l2", "scan_grad_rel_l2", "routed_rel_l2",
            "update_cosine", "leaf_cosine_min", "group_cosine"} \
        <= {r["name"] for r in decided}
    assert all(r["holds"] for r in decided)
    assert facts["kernel_routes"] == [
        "attention=dense; 2 kv heads repeated x16; no positions",
        "grouped_matmul=ragged_dot; act=relu2; ungated",
        "ssd_scan=chunked Q 128, 1 chunks, f32 state, one C B^T grid a "
        "chunk; not pallas: heads of 2 are no whole part of a lane block "
        "of 128; groups=8"]
    assert facts["remat_segments"] == 9 + 1
    assert sorted(facts["recurrent_state"]) == [
        f"l{i}_ssd_scan" for i in (0, 2, 4, 7)]
    assert all(v["groups"] == 8 for v in facts["recurrent_state"].values())
    assert sorted(facts["expert_share"]) == [
        f"l{i}_moe_experts" for i in (1, 3, 6, 8)]
    assert facts["first_loss"] == pytest.approx(
        facts["first_loss_expected"], rel=0.01)
    names = set(line["metrics"])
    if trace:
        # all of the cell's per-layer metrics but those that need a chip's
        # peaks, its memory statistics or its Pallas kernels
        assert names == declared("per_layer", CELL) - {
            "busy_flops_util", "peak_hbm_gb", "nemotron_ssd_scan_roofline",
            "nemotron_flash_attention_roofline",
            "nemotron_held_moe_flops_util"}
        m = {k: v["value"] for k, v in line["metrics"].items()}
        assert m["scope_coverage"] >= 95.0
        parts = ("nemotron_ssd_ms_per_step", "nemotron_attention_ms_per_step",
                 "nemotron_held_moe_ms_per_step",
                 "nemotron_shared_expert_ms_per_step",
                 "nemotron_router_ms_per_step", "nemotron_head_ms_per_step")
        assert all(m[k] > 0 for k in parts)
        assert m["nemotron_ssd_scan_ms_per_step"] \
            + m["nemotron_ssd_glue_ms_per_step"] \
            < m["nemotron_ssd_ms_per_step"]
        assert sum(m[k] for k in parts) \
            < m["fwd_ms_per_step"] + m["bwd_ms_per_step"]
        assert 0.0 < m["nemotron_ssd_decay_mean"] < 1.0
        assert 30.0 < m["nemotron_act_zero_share"] < 70.0
        assert 0.0 < m["nemotron_held_assignment_share"] < 100.0
        assert m["nemotron_held_dropped_assignments"] == 0.0
    else:
        assert names == declared("end_to_end", CELL) - {"mfu_required"}
        assert line["metrics"]["images_per_s_per_chip"]["value"] == \
            pytest.approx(facts["tokens_per_s_per_chip"] / facts["seq_len"])


# a program with ONE fault planted, run through the harness's own entry at
# the rehearsal's sizes: {fault: (what is planted before run.py starts, the
# rows of ``compared`` that have to break)}
_PLANTED = {
    # the scan's backward sums d B over ALL heads, as one group's does, and
    # hands every group the mean: what a kernel whose d B / d C blocks kept
    # accumulating across groups would leave
    "d_B_over_all_groups": ("""
import jax.numpy as jnp
from poseidon_tpu.ops import ssd
honest = ssd._by_group
def faulty(scan, x, dt, a, b, c, d):
    import jax
    @jax.custom_vjp
    def smear(b):
        return b
    smear.defvjp(lambda b: (b, None), lambda _, g: (
        jnp.broadcast_to(jnp.mean(g, 2, keepdims=True), g.shape),))
    return honest(scan, x, dt, a, smear(b), c, d)
ssd._by_group = faulty
""", {"scan_grad_rel_l2"}),
    # the expert is relu(up x), not its square
    "expert_not_squared": ("""
import jax
from poseidon_tpu.models import moe
honest = moe._act
moe._act = lambda a, act: jax.nn.relu(a) if act == "relu2" \\
    else honest(a, act)
""", {"routed_rel_l2"}),
    # the solver leaves the table and the head as they were
    "leaf_unchanged": ("""
from poseidon_tpu.solvers import updates
honest = updates._adam
def faulty(sp, w, g, m, v, *rest):
    new, m, v = honest(sp, w, g, m, v, *rest)
    return (w if w.shape == (512, 64) else new), m, v
updates._adam = faulty
""", {"update_norm_rel"}),
}


@pytest.mark.parametrize("fault", sorted(_PLANTED))
def test_a_planted_fault_reads_not_correct(fault, tmp_path):
    """The grouped backward, the ungated unit and the limit that has no
    lower-precision reading behind it, each held against the fault it is
    there for: the harness has to print ``correct: false`` and name the row
    that broke."""
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
