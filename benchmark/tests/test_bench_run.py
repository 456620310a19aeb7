"""The one command end to end: the --cpu-tiny rehearsal of an ``lmdb`` and a
``resident`` cell (what runs on the chip, at cut widths, on the CPU and
labelled so), and the ways it must refuse to run."""

import json
import os
import shutil
import subprocess
import sys

import pytest

from conftest import BENCH_DIR, ROOT

with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
    BENCH = json.load(f)


def run_cell(*args, cwd=ROOT, script=os.path.join(BENCH_DIR, "run.py")):
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    return subprocess.run([sys.executable, script, *args], cwd=cwd, env=env,
                          capture_output=True, text=True, timeout=600)


# the stall ledger's seven readings (ISSUE 51), which every token cell lists
# since ISSUE 63 and which move the throughput, not ``mfu_required``: a cell's
# own test file leaves them to test_bench_stalls.py
STALLS = frozenset((
    "stall_lost_share", "stall_host_ms_per_step", "stall_device_ms_per_step",
    "stalls_per_1k_steps", "stall_longest_ms", "stall_unnamed_share",
    "host_freeze_ms_per_step"))


def declared(kind: str, cell: str) -> set:
    return {m["name"] for m in BENCH[kind]
            if "workloads" not in m or cell in m["workloads"]}


def entries_of(cell: str, readers) -> list:
    """The ``per_layer`` entries of the reader modules a cell's own file
    tests, each found by NAME and listing the cell by MEMBERSHIP (under the
    name the cells that share the measurement share, ISSUES 50 and 63);
    what else lists the cell is the stall ledger's."""
    by_name = {m["name"]: m for m in BENCH["per_layer"]}
    tested = {r.__name__.rsplit(".", 1)[-1] for r in readers}
    mine = [by_name[name] for name in sorted(tested)]
    assert all(cell in m["workloads"] for m in mine)
    assert {m["name"] for m in BENCH["per_layer"]
            if cell in m.get("workloads", ())} - tested == STALLS
    return mine


def assert_setup_is_accounted_for(stdout: str, m: dict,
                                  layers_path: str) -> None:
    """``setup_s`` has a name for every stretch (ISSUE 50): what the harness
    does before the program, the program's start-up up to the end of its
    first step, what the harness does after it. The three big program spans
    are metrics of their own; the rest of the program's stretch (the other
    top-level spans and the gaps between them) is what ``startup_coverage``
    and the timeline say of it. The ends are read on two clocks that start
    within a second of each other (the OS's process start, ``run.py``'s
    first line)."""
    facts_line = json.loads(stdout.strip().splitlines()[-2])
    with open(layers_path) as f:
        layers = json.load(f)
    # the runtime's start of the chips is taken out of the end-to-end
    # ``setup_s`` (PR 63) and reported beside it; the readers' account is of
    # the runner's whole reading, top of ``run.py`` -> window
    backend = facts_line["backend_start_s"]
    assert 0 < backend == layers["backend_start_s"] == m["backend_start_s"]
    assert backend < m["setup_before_program_s"]
    assert facts_line["end_to_end"]["setup_s"] == layers["setup_s"] - backend
    setup_s = layers["setup_s"]
    startup = layers["stats"]["sections"]["startup"]
    named = (m["setup_before_program_s"], m["engine_build_s"],
             m["step_load_s"], m["first_step_run_s"],
             m["setup_after_first_step_s"])
    rest = startup["stretch_s"] - sum(named[1:4])
    assert all(v >= 0 for v in named) and rest > -0.05
    assert sum(named) + rest == pytest.approx(setup_s, abs=1.5)
    # the second end holds the warm-up's timed display, and no more than
    # what lies between the program's start and the window
    timed = facts_line["facts"]["step_s_warmup"] * 4
    assert timed < named[4] < setup_s - named[0]


@pytest.mark.parametrize("cell,trace", [
    ("alexnet.lmdb", 0), ("alexnet.lmdb", 1),
    ("alexnet.resident", 0), ("alexnet.dp4.resident", 1)])
def test_cpu_tiny_rehearsal(cell, trace, tmp_path):
    chips = next(w["chips"] for w in BENCH["workloads"] if w["name"] == cell)
    kept = str(tmp_path / "layers.json")
    done = run_cell("--workload", cell, "--seed", "3", "--seconds", "1",
                    "--trace", str(trace), "--cpu-tiny",
                    *(("--keep-layers", kept) if trace else ()))
    assert done.returncode == 0, done.stderr[-3000:]
    line = json.loads(done.stdout.strip().splitlines()[-1])
    keys = {"correct", "attempted", "failed", "metrics", "device"}
    assert set(line) == (keys | {"breakdown"} if trace else keys)
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] >= 4
    assert line["device"]["platform"] == "cpu"       # never passes for a chip
    assert line["device"]["count"] == chips
    names = set(line["metrics"])
    for m in line["metrics"].values():
        assert set(m) == {"value", "unit"} and isinstance(m["value"], float)
    if trace:
        # every per-layer metric of the cell but the ones that need a chip's
        # peak or its memory statistics
        assert names == declared("per_layer", cell) - {"busy_flops_util",
                                                       "peak_hbm_gb"}
        assert line["metrics"]["compiles_in_window"]["value"] == 0.0
        assert 0 < line["device"]["busy_s"] <= line["device"]["window_s"]
        assert 0 < len(line["breakdown"]["device_ops"]) <= 10
        assert 0 < len(line["breakdown"]["idle_gaps"]) <= 5
        spans = {name for name, _ in line["breakdown"]["idle_gaps"]}
        assert spans - {"unattributed"}, "no gap met an engine span"
        assert_setup_is_accounted_for(
            done.stdout, {k: v["value"] for k, v in line["metrics"].items()},
            kept)
    else:
        # no peak FLOP/s for a CPU: the MFU is left out, not made up
        assert names == declared("end_to_end", cell) - {"mfu_required"}
        assert line["metrics"]["images_per_s_per_chip"]["value"] > 0
        assert line["metrics"]["setup_s"]["value"] > 0
        facts_line = json.loads(done.stdout.strip().splitlines()[-2])
        assert line["metrics"]["setup_s"]["value"] \
            == facts_line["end_to_end"]["setup_s"]
        assert facts_line["backend_start_s"] > 0


def test_refuses_to_measure_without_a_tpu():
    done = run_cell("--workload", "alexnet.lmdb", "--seed", "0",
                    "--seconds", "1", "--trace", "0")
    assert done.returncode == 2
    assert "REFUSING" in done.stderr and "tpu" in done.stderr
    assert '"metrics"' not in done.stdout


def test_unknown_cell_and_missing_files_fail_by_name(tmp_path):
    done = run_cell("--workload", "nope", "--cpu-tiny")
    assert done.returncode == 2 and "'nope'" in done.stderr
    # a checkout that holds only BENCHMARK.json and the benchmark's paths:
    # cells are found, the program is not
    bare = tmp_path / "bare"
    shutil.copytree(BENCH_DIR, bare / "benchmark",
                    ignore=shutil.ignore_patterns(".work", "__pycache__"))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), bare)
    script = str(bare / "benchmark" / "run.py")
    done = run_cell("--workload", "alexnet.lmdb", "--cpu-tiny", cwd=bare,
                    script=script)
    assert done.returncode == 2 and "poseidon_tpu" in done.stderr
    assert done.stdout == ""
    # a cell that names a traffic mix / a configuration nobody brought
    doc = json.loads((bare / "BENCHMARK.json").read_text())
    doc["workloads"][0]["traffic"] = "bursty"
    doc["configs"][1]["file"] = "benchmark/configs/gone.json"
    (bare / "BENCHMARK.json").write_text(json.dumps(doc))
    done = run_cell("--workload", doc["workloads"][0]["name"], "--cpu-tiny",
                    cwd=bare, script=script)
    assert done.returncode == 2
    assert "traffic mix 'bursty'" in done.stderr
    assert "benchmark/traffic/bursty.json" in done.stderr
    done = run_cell("--workload", "googlenet.lmdb", "--cpu-tiny", cwd=bare,
                    script=script)
    assert done.returncode == 2
    assert "configuration 'bvlc_googlenet'" in done.stderr
    assert "benchmark/configs/gone.json" in done.stderr


def test_unknown_device_kind_is_an_error():
    import device
    assert device.peaks("TPU v5 lite")["bf16_flops_per_s"] == 197e12
    for kind in ("TPU v9", "cpu", "comment"):
        with pytest.raises(KeyError, match="no peak rates on record"):
            device.peaks(kind)


def test_run_py_names_no_cell_configuration_traffic_or_metric():
    with open(os.path.join(BENCH_DIR, "run.py")) as f:
        text = f.read()
    names = [x["name"] for k in ("configs", "workloads", "end_to_end",
                                 "per_layer") for x in BENCH[k]]
    names += [w["traffic"] for w in BENCH["workloads"]]
    assert not [n for n in names if n in text]


def test_every_declared_name_has_its_file():
    for w in BENCH["workloads"]:
        assert os.path.exists(os.path.join(BENCH_DIR, "traffic",
                                           f"{w['traffic']}.json"))
    for c in BENCH["configs"]:
        assert os.path.exists(os.path.join(ROOT, c["file"]))
    for m in BENCH["per_layer"]:
        assert os.path.exists(os.path.join(BENCH_DIR, "layer_metrics",
                                           f"{m['name']}.py")), m["name"]
        assert m["moves"] in {e["name"] for e in BENCH["end_to_end"]}


def test_entries_follow_the_contract():
    """What the driver refuses before any run, for EVERY entry (the cells'
    own test files check what each brought, never how many entries the
    benchmark has or in which order: later PRs append)."""
    cells = [w["name"] for w in BENCH["workloads"]]
    configs = [c["name"] for c in BENCH["configs"]]
    metrics = [m["name"] for k in ("end_to_end", "per_layer")
               for m in BENCH[k]]
    for names in (cells, configs, metrics):
        assert len(names) == len(set(names))
    assert len({c["file"] for c in BENCH["configs"]}) == len(configs)
    assert len({(w["config"], w["traffic"])
                for w in BENCH["workloads"]}) == len(cells)
    assert {w["config"] for w in BENCH["workloads"]} == set(configs)
    four = [w for w in BENCH["workloads"] if w["chips"] == 4]
    assert all(w["chips"] in (1, 4) for w in BENCH["workloads"]) \
        and len(four) <= max(1, len(cells) // 4)
    for w in BENCH["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
    for c in BENCH["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert c["file"].startswith(BENCH["paths"][0] + "/")
    keys = {"name", "unit", "better", "source"}
    for kind, more in (("end_to_end", {"bound"}),
                       ("per_layer", {"layer", "moves"})):
        for m in BENCH[kind]:
            assert keys | more <= set(m) <= keys | more | {"workloads"}, m
            assert m["better"] in ("lower", "higher")
            assert set(m.get("workloads", ())) <= set(cells)
            if kind == "per_layer":
                # a metric's cells report the end-to-end metric it moves
                assert all(m["moves"] in declared("end_to_end", cell)
                           for cell in m.get("workloads", cells))
    for text in [x[k] for kind in ("workloads", "configs", "per_layer")
                 for x in BENCH[kind] for k in ("why", "layer", "source")
                 if k in x and isinstance(x[k], str)]:
        assert 1 <= len(text) <= 200 and text.isascii() \
            and text.isprintable(), text
    assert any(m["name"] == "setup_s" for m in BENCH["end_to_end"])
    assert all(m["bound"] <= 0.1 for m in BENCH["end_to_end"])
