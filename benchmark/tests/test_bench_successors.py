"""One name per measurement (ISSUE 50, again ISSUE 63): ``BENCHMARK.json``'s
``per_layer`` says each measurement once, under a successor name with a
``workloads`` list, and every value a cell reports under a successor equals,
to the last bit, what the same ``layers`` dict gave under the cell's old name.

The ``layers`` are recorded: ``data/layers/<cell>.json.gz`` is what the
runner handed the readers in one ``--cpu-tiny --trace 1`` rehearsal of the
cell (``run.py --keep-layers``), with a v5e's peaks put in so that the
rooflines and utilisations have something to divide by.
``data/parent_values.json`` is what the readers of the cell's PARENT
returned on those dicts (``parent_values.py``): for the eleven cells ISSUE 50
folded, PR 50's parent; for the three ISSUE 63 folded (Granite, GLM, Xing4),
PR 63's (``data/renames.json`` ``parents`` says which). Where a copy of a
parent is unpacked at ``.parent/`` and lists a cell's metrics under the
recorded names, its readers are run again, live, and have to agree with the
table. ``data/renames.json`` ``renames`` is the rename table: successor ->
{cell: the name the cell reported it under on its parent; null where the cell
joins}. ``data/names_pr62.json`` is every cell's list of names on PR 63's
parent.

Every entry is found by NAME and every cell in a list by MEMBERSHIP: a later
PR that appends a cell, a configuration and entries of its own turns nothing
here red (``test_an_appended_cell_and_entry_turn_nothing_red``)."""

import ast
import copy
import gzip
import importlib
import json
import os
import subprocess
import sys

import pytest

from conftest import BENCH_DIR, ROOT
from test_bench_run import STALLS     # the three cells of ISSUE 63 join them

DATA = os.path.join(BENCH_DIR, "tests", "data")
with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
    BENCH = json.load(f)
with open(os.path.join(DATA, "renames.json")) as f:
    TABLE = json.load(f)
RENAMES, RETIRED = TABLE["renames"], TABLE["retired"]
with open(os.path.join(DATA, "parent_values.json")) as f:
    PARENT_VALUES = json.load(f)
with open(os.path.join(DATA, "names_pr62.json")) as f:
    NAMES_PR62 = json.load(f)
CELLS = [w["name"] for w in BENCH["workloads"]]
RECORDED = sorted(PARENT_VALUES)           # the cells with a record
FOLDED_63 = ("granite_h.p1.pack8k", "glm_flash.e8of64.pack8k",
             "xing4.e8of64.hc4")
PARENT = os.path.join(ROOT, ".parent")
SETUP_ENDS = ("setup_before_program_s", "setup_after_first_step_s")
# entries younger than the eleven older cells' record (PRs 50 and 51): no
# value of their parent to hold them to
YOUNGER = set(SETUP_ENDS) | set(STALLS) | {"h2d_land_ms_per_batch",
                                           "h2d_gb_per_s"}
# what ``run.py`` takes out of ``setup_s`` since PR 63's second round: EVERY
# cell reports it, and no record of a parent holds it
BACKEND = "backend_start_s"
YOUNGER.add(BACKEND)


def applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def names_of(bench: dict, cell: str) -> list:
    return [m["name"] for m in bench["per_layer"] if applies(m, cell)]


def recorded(cell: str) -> dict:
    with gzip.open(os.path.join(DATA, "layers", cell + ".json.gz"),
                   "rt") as f:
        return json.load(f)


def old_name(metric: str, cell: str):
    """The name under which ``cell`` reported ``metric`` on its parent; None
    where the table says that it joins."""
    return RENAMES.get(metric, {}).get(cell, metric)


def live_parent_values(cell: str):
    """What ``.parent/``'s readers return on the cell's recorded layers, if
    a parent is unpacked there and it is the one the record is of (it lists
    the cell's metrics under the recorded names); else None."""
    path = os.path.join(PARENT, "BENCHMARK.json")
    if not os.path.exists(path):
        return None
    with open(path) as f:
        theirs = json.load(f)
    if set(names_of(theirs, cell)) != set(PARENT_VALUES[cell]):
        return None
    live = subprocess.run(
        [sys.executable, os.path.join(BENCH_DIR, "tests", "parent_values.py"),
         os.path.join(PARENT, "benchmark"),
         os.path.join(DATA, "layers", cell + ".json.gz"), cell],
        capture_output=True, text=True, check=True).stdout
    return json.loads(live.strip().splitlines()[-1])


def check_cell(bench: dict, cell: str) -> dict:
    """Every value ``cell`` reports under ``bench``'s names on its recorded
    layers against its parent's under the old names; -> what joined and
    what is younger than the record."""
    layers, want = recorded(cell), PARENT_VALUES[cell]
    read, joined, younger = set(), [], []
    for name in names_of(bench, cell):
        got = importlib.import_module(f"layer_metrics.{name}").reduce(layers)
        old = old_name(name, cell)
        if old is None:
            joined.append(name)
            assert got is not None, name     # a cell that joins reads it
        elif old not in want:
            younger.append(name)             # nothing to hold it to
        else:
            read.add(old)
            assert got == want[old], (name, old)      # to the last bit
    # nothing the parent reported in this cell went unread but the retired
    assert set(want) - read == {n for n in RETIRED if n in want}
    assert sum(want[old] is not None for old in read) >= len(read) - 5
    return {"joined": set(joined), "younger": set(younger)}


JOINED = {
    "ouro.loop4.pack8k": {"tokens_per_s_per_chip"},
    "olmo_hybrid.p1.pack8k": {"tokens_per_s_per_chip", "head_ms_per_step",
                              "ffn_ms_per_step"},
    "granite_h.p1.pack8k": set(STALLS),
    "glm_flash.e8of64.pack8k": set(STALLS) | {"held_assignment_share"},
    "xing4.e8of64.hc4": set(STALLS) | {"held_assignment_share",
                                       "tokens_per_s_per_chip"},
}


@pytest.mark.parametrize("cell", RECORDED)
def test_every_value_equals_the_parent_s_on_the_same_layers(cell):
    live = live_parent_values(cell)
    assert live is None or live == PARENT_VALUES[cell]
    found = check_cell(BENCH, cell)
    assert found["joined"] == JOINED.get(cell, set())
    assert found["younger"] <= ({BACKEND} if cell in FOLDED_63 else YOUNGER)


@pytest.mark.parametrize("cell", sorted(NAMES_PR62))
def test_a_cell_lists_what_it_listed_on_pr_63_s_parent(cell):
    """The eleven older cells print exactly the names they printed before,
    plus ``backend_start_s`` (every cell's) and nothing else; the three
    folded ones each old name's successor and what they join."""
    then, now = NAMES_PR62[cell], names_of(BENCH, cell)
    assert len(set(now)) == len(now)
    now.remove(BACKEND)
    if cell not in FOLDED_63:
        assert sorted(now) == sorted(then)
        return
    succ = {old: s for s, by in RENAMES.items()
            for c, old in by.items() if c == cell and old}
    assert set(now) == {succ.get(n, n) for n in then} | JOINED[cell]
    assert len(now) == len(then) + len(JOINED[cell])
    assert {n for n in then if n.startswith(("granite_", "glm_", "xing_"))} \
        == set(succ)


def test_the_rename_table_accounts_for_every_old_name():
    by_name = {m["name"]: m for m in BENCH["per_layer"]}
    assert len(by_name) == len(BENCH["per_layer"]) <= 128
    for successor, by_cell in RENAMES.items():
        listed = by_name[successor]["workloads"]      # found by name
        assert set(by_cell) <= set(listed), successor     # by membership
        assert listed == sorted(listed, key=CELLS.index), successor
    old = {n for by_cell in RENAMES.values() for n in by_cell.values() if n}
    # an old name is gone unless it is the successor's own
    assert old & set(by_name) <= set(RENAMES)
    assert not set(RETIRED) & set(by_name)
    # ISSUE 63: all 38 names with a cell's prefix, 32 folded and 6 renamed
    prefixed = {n for n in old
                if n.startswith(("granite_", "glm_", "xing_"))}
    assert len(prefixed) == 38 and not prefixed & set(by_name)
    assert not [n for n in by_name
                if n.startswith(("granite_", "glm_", "xing_"))]
    assert RENAMES["mla_proj_ms_per_step"] == {
        "glm_flash.e8of64.pack8k": "glm_mla_proj_ms_per_step",
        "xing4.e8of64.hc4": "xing_mla_proj_ms_per_step"}
    for name in SETUP_ENDS:
        assert by_name[name] == {
            "name": name, "unit": "s", "better": "lower",
            "source": {"setup_before_program_s": "program_span",
                       "setup_after_first_step_s": "host_clock"}[name],
            "layer": "entry", "moves": "setup_s"}


def test_an_appended_cell_and_entry_turn_nothing_red():
    """What the next ``model_config`` PR does to the file: a cell, a
    configuration and entries of its own at the lists' ends. Every rule
    above holds on that file as on this one."""
    bench = copy.deepcopy(BENCH)
    bench["workloads"].append(dict(bench["workloads"][-1], name="dummy.c1",
                                   config="dummy", traffic="dummy_mix"))
    bench["configs"].append(dict(bench["configs"][-1], name="dummy"))
    for name in ("dummy_attention_ms_per_step", "dummy_own_ms_per_step"):
        bench["per_layer"].append(
            {"name": name, "unit": "ms", "better": "lower",
             "source": "device_trace", "layer": "kernels",
             "moves": "mfu_required", "workloads": ["dummy.c1"]})
    for cell in RECORDED:
        assert names_of(bench, cell) == names_of(BENCH, cell)
        check_cell(bench, cell)
    assert names_of(bench, "dummy.c1")[-2:] == [
        "dummy_attention_ms_per_step", "dummy_own_ms_per_step"]


def test_entries_follow_the_contract():
    ends = {m["name"] for m in BENCH["end_to_end"]}
    assert 1 <= len(BENCH["per_layer"]) <= 128
    for m in BENCH["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source",
                                          "layer", "moves"}, m
        assert len(m["name"]) <= 64 and m["moves"] in ends
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
        assert set(m.get("workloads", CELLS)) <= set(CELLS)
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        assert len(f.read()) < 64 * 1024


def test_one_reader_file_an_entry_and_no_two_alike():
    """``layer_metrics/`` holds one file per entry and nothing else; no
    reader asks whose run it reads (``is_ours``), and no two are the same
    code — with one trace module, twins that differed only by their gate
    would be."""
    folder = os.path.join(BENCH_DIR, "layer_metrics")
    files = sorted(f for f in os.listdir(folder) if f != "__pycache__")
    assert files == sorted(m["name"] + ".py" for m in BENCH["per_layer"])
    bodies = {}
    for name in files:
        with open(os.path.join(folder, name)) as f:
            source = f.read()
        assert "is_ours" not in source, name
        tree = ast.parse(source)
        if isinstance(tree.body[0], ast.Expr):           # the docstring
            tree.body = tree.body[1:]
        bodies.setdefault(ast.dump(tree), []).append(name)
    assert [names for names in bodies.values() if len(names) > 1] == []
    modules = sorted(f for f in os.listdir(BENCH_DIR) if f.endswith("_trace.py"))
    assert modules == ["device_trace.py", "lm_trace.py", "scope_trace.py"]


@pytest.mark.parametrize("part", ["router", "shared_expert", "head", "ffn",
                                  "delta", "delta_scan", "delta_glue",
                                  "held_moe", "attention_gate"])
def test_a_part_the_configuration_does_not_name_is_left_out(part):
    """The reader finds what is cell-specific in the run: the part's pattern
    under ``run["lm"]["scopes"]``; a run that names no such part gives None,
    never 0."""
    name = "attention_gate_ms_per_step" if part == "attention_gate" \
        else f"{part}_ms_per_step"
    reader = importlib.import_module(f"layer_metrics.{name}")
    layers = recorded("olmoe.l1.pack4k")
    assert set(layers["lm"]["scopes"]) == {"head", "why"}
    got = reader.reduce(layers)
    assert (got is None) == (part != "head")
    del layers["lm"]["scopes"]
    assert reader.reduce(layers) is None
