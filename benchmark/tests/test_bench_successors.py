"""One name per measurement (ISSUE 50): ``BENCHMARK.json``'s ``per_layer`` says
each measurement once, under a successor name with a ``workloads`` list, and
every value a cell reports under a successor equals, to the last bit, what
the same ``layers`` dict gave under the cell's old name.

The ``layers`` are recorded: ``data/layers/<cell>.json.gz`` is what the
runner handed the readers in one ``--cpu-tiny --trace 1`` rehearsal of the
cell (``run.py --keep-layers``), with a v5e's peaks put in so that the
rooflines and utilisations have something to divide by.
``data/parent_values.json`` is what the PARENT's readers returned on those
dicts (``parent_values.py``); where a copy of the parent is unpacked at
``.parent/`` its readers are run again, live, and have to agree with the
table. ``data/renames.json`` is the rename table: successor -> {cell: the
name the cell reported it under before; null where the cell joins}."""

import ast
import gzip
import importlib
import json
import os
import subprocess
import sys

import pytest

from conftest import BENCH_DIR, ROOT

DATA = os.path.join(BENCH_DIR, "tests", "data")
with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
    BENCH = json.load(f)
with open(os.path.join(DATA, "renames.json")) as f:
    TABLE = json.load(f)
RENAMES, RETIRED = TABLE["renames"], TABLE["retired"]
with open(os.path.join(DATA, "parent_values.json")) as f:
    PARENT_VALUES = json.load(f)
CELLS = [w["name"] for w in BENCH["workloads"]]
PARENT = os.path.join(ROOT, ".parent", "benchmark")
# per-layer metrics a cell, ISSUE 50's table
COUNTS = dict(zip(CELLS, (39, 30, 39, 32, 38, 38, 42, 48, 48, 46, 41)))
SETUP_ENDS = ("setup_before_program_s", "setup_after_first_step_s")


def applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def recorded(cell: str) -> dict:
    with gzip.open(os.path.join(DATA, "layers", cell + ".json.gz"),
                   "rt") as f:
        return json.load(f)


def old_name(metric: str, cell: str):
    """The name under which ``cell`` reported ``metric`` on the parent;
    None where it did not (a cell that joins, a new metric)."""
    if metric in SETUP_ENDS:
        return None
    return RENAMES[metric][cell] if metric in RENAMES else metric


@pytest.mark.parametrize("cell", CELLS)
def test_every_value_equals_the_parent_s_on_the_same_layers(cell):
    layers, want = recorded(cell), PARENT_VALUES[cell]
    if os.path.isdir(os.path.join(PARENT, "layer_metrics")):
        live = subprocess.run(
            [sys.executable, os.path.join(BENCH_DIR, "tests",
                                          "parent_values.py"), PARENT,
             os.path.join(DATA, "layers", cell + ".json.gz"), cell],
            capture_output=True, text=True, check=True).stdout
        assert json.loads(live.strip().splitlines()[-1]) == want
    mine = [m["name"] for m in BENCH["per_layer"] if applies(m, cell)]
    assert len(mine) == COUNTS[cell]
    read, joined = set(), []
    for name in mine:
        got = importlib.import_module(f"layer_metrics.{name}").reduce(layers)
        old = old_name(name, cell)
        if old is None:
            joined.append(name)
            assert got is not None, name     # a cell that joins reads it
            continue
        read.add(old)
        assert got == want[old], (name, old)      # to the last bit
    # nothing the parent reported in this cell went unread but the retired
    assert set(want) - read == {n for n in RETIRED if n in want}
    assert sum(want[old] is not None for old in read) >= len(read) - 5
    assert set(joined) - set(SETUP_ENDS) == {
        "ouro.loop4.pack8k": {"tokens_per_s_per_chip"},
        "olmo_hybrid.p1.pack8k": {"tokens_per_s_per_chip",
                                  "head_ms_per_step", "ffn_ms_per_step"},
    }.get(cell, set())


def test_the_rename_table_accounts_for_every_name_of_the_parent():
    parent = {name for by_cell in PARENT_VALUES.values() for name in by_cell}
    now = {m["name"] for m in BENCH["per_layer"]}
    gone = parent - now
    renamed = {old for by_cell in RENAMES.values()
               for old in by_cell.values() if old}
    assert gone == (renamed - now) | set(RETIRED)
    assert now - parent == (set(RENAMES) - parent) | set(SETUP_ENDS)
    assert len(parent) == 128 and len(now) == 76 <= 80
    by_name = {m["name"]: m for m in BENCH["per_layer"]}
    for successor, by_cell in RENAMES.items():
        assert by_name[successor]["workloads"] == [
            c for c in CELLS if c in by_cell], successor
    for name in SETUP_ENDS:
        assert by_name[name] == {
            "name": name, "unit": "s", "better": "lower",
            "source": {"setup_before_program_s": "program_span",
                       "setup_after_first_step_s": "host_clock"}[name],
            "layer": "entry", "moves": "setup_s"}
    assert [m["name"] for m in BENCH["per_layer"][-2:]] == list(SETUP_ENDS)


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
