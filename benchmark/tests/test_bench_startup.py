"""The per-layer metrics under ``setup_s`` (ISSUE 34): thirteen readers of the
program's stats section ``startup`` (and a fourteenth, ``step_key_s``, for
what the first chip runs showed the thirteen leave out of ``step_load_s``). Each gives ``None`` on a run whose
program has no such section (the parent of the PR that added it), so the
metric is left out of the line, and the right number on a hand-made section;
each has its entry in BENCHMARK.json, for every cell."""

import importlib
import json
import os

import pytest

from conftest import BENCH_DIR, ROOT

with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
    BENCH = json.load(f)

# one warm start and one cold start, as the program summarises them
LOADED = {
    "route": "loaded", "key": "0123456789ab", "coverage": 0.9712,
    "stretch_s": 44.0, "events_dropped": 0, "compiles": 31,
    "compile_s": 1.25, "xla_cache_hits": 31,
    "aot_bytes_on_disk": 152_000_000, "aot_bytes_serialized": 715_000_000,
    "pallas_custom_calls": 112,
    "timeline": {"cli_setup": {"at_s": 9.1, "dur_s": 0.4},
                 "engine_build": {"at_s": 9.6, "dur_s": 4.5},
                 "first_batch_wait": {"at_s": 14.1, "dur_s": 0.01},
                 "step_load": {"at_s": 14.2, "dur_s": 37.0},
                 "first_step": {"at_s": 51.2, "dur_s": 1.9}},
    "spans": {"cli_setup": 0.4, "backend_init": 0.001, "engine_build": 4.5,
              "net_build": 1.5, "pipeline_open": 0.25, "step_build": 0.05,
              "param_init": 2.5, "compile": 1.25, "first_batch_wait": 0.01,
              "step_load": 36.77, "step_key": 0.02, "aot_read": 0.5,
              "aot_unpack": 2.5, "aot_deserialize": 32.0, "step_text": 1.5,
              "scope_map": 0.25, "first_step": 1.9}}
COMPILED = {
    **LOADED, "route": "compiled", "xla_cache_hits": 0,
    "spans": {"engine_build": 5.0, "param_init": 3.0, "pipeline_open": 0.25,
              "compile": 130.0, "step_load": 160.02, "step_key": 0.02,
              "step_trace_lower": 27.5, "step_compile": 127.0,
              "aot_store": 4.0, "aot_serialize": 1.0, "aot_pack": 2.5,
              "aot_write": 0.5, "step_text": 1.25, "scope_map": 0.25,
              "first_step": 2.0}}

WANT = {   # metric: (unit, source, layer, on LOADED, on COMPILED)
    "aot_load_s": ("s", "program_span", "entry", 35.0, 0.0),
    "aot_deserialize_s": ("s", "program_span", "entry", 32.0, 0.0),
    "step_trace_lower_s": ("s", "program_span", "entry", 0.0, 27.5),
    "step_compile_s": ("s", "program_span", "entry", 0.0, 127.0),
    "step_text_s": ("s", "program_span", "entry", 1.75, 1.5),
    "aot_hit": ("count", "program_counter", "entry", 1.0, 0.0),
    "aot_serialized_mb": ("MB", "program_counter", "entry", 715.0, 715.0),
    "engine_build_s": ("s", "program_span", "train_loop", 4.5, 5.0),
    "param_init_s": ("s", "program_span", "graph", 2.5, 3.0),
    "pipeline_open_s": ("s", "program_span", "input", 0.25, 0.25),
    "first_step_run_s": ("s", "program_span", "device", 1.9, 2.0),
    "setup_other_compile_s": ("s", "program_counter", "entry", 1.25, 1.25),
    "startup_coverage": ("%", "program_span", "entry", 97.12, 97.12),
    "step_key_s": ("s", "program_span", "entry", 0.02, 0.02),
}


def run_with(section=None) -> dict:
    sections = {"compiled_step": {"seconds": 37.0, "source": "loaded"}}
    if section is not None:
        sections["startup"] = section
    return {"steps": 8, "spans": [], "stats": {"sections": sections}}


def reader(name: str):
    return importlib.import_module(f"layer_metrics.{name}")


@pytest.mark.parametrize("name", sorted(WANT))
def test_reader_leaves_the_metric_out_without_the_section(name):
    assert reader(name).reduce(run_with()) is None
    # nor does a run dict of another runner's making raise
    assert reader(name).reduce({"stats": {"sections": {}}}) is None


@pytest.mark.parametrize("name", sorted(WANT))
def test_reader_reads_the_section(name):
    *_, loaded, compiled = WANT[name]
    got = reader(name).reduce(run_with(LOADED))
    assert isinstance(got, float) and got == pytest.approx(loaded)
    got = reader(name).reduce(run_with(COMPILED))
    assert isinstance(got, float) and got == pytest.approx(compiled)
    # the jit path resolves no executable: no route's span, no bytes
    bare = {"route": "jit", "coverage": 0.95, "compile_s": 0.0,
            "spans": {"engine_build": 1.0}}
    got = reader(name).reduce(run_with(bare))
    assert isinstance(got, float) and got >= 0.0


def test_the_parts_sum_to_the_step_load():
    """``step_load_s`` = key + load + trace/lower + compile + store + text,
    whichever route."""
    for section in (LOADED, COMPILED):
        run = run_with(section)
        parts = sum(reader(n).reduce(run) for n in (
            "step_key_s", "aot_load_s", "step_trace_lower_s",
            "step_compile_s", "step_text_s")) \
            + section["spans"].get("aot_store", 0.0)
        assert parts == pytest.approx(section["spans"]["step_load"],
                                      rel=0.01)


def test_new_entries_follow_the_contract():
    mine = [m for m in BENCH["per_layer"] if m["name"] in WANT]
    assert [m["name"] for m in mine] == list(WANT)
    for m in mine:
        unit, source, layer, *_ = WANT[m["name"]]
        # every cell: the six keys of the other all-cell entries, no list
        assert m == {"name": m["name"], "unit": unit,
                     "better": "higher" if m["name"] in (
                         "aot_hit", "startup_coverage") else "lower",
                     "source": source, "layer": layer, "moves": "setup_s"}
        assert os.path.exists(os.path.join(
            BENCH_DIR, "layer_metrics", m["name"] + ".py"))
    layers = {m["layer"] for m in BENCH["per_layer"] if m not in mine}
    assert {m["layer"] for m in mine} <= layers
    assert any(m["name"] == "setup_s" for m in BENCH["end_to_end"])


# --------------------------------------------------------------------------- #
# PR 63, second round: the runtime's start of the chips leaves ``setup_s``
# --------------------------------------------------------------------------- #

@pytest.mark.parametrize("run,want", [
    ({"backend_start_s": 6.5, "setup_s": 16.5}, 6.5),
    ({"backend_start_s": 0.0}, 0.0),
    ({"setup_s": 16.5}, None),          # a parent's layers: left out
    ({}, None)])
def test_backend_start_is_what_run_py_handed(run, want):
    got = reader("backend_start_s").reduce(run)
    assert got == want and (want is None or isinstance(got, float))


def test_backend_start_s_entry_is_every_cell_s():
    entry = next(m for m in BENCH["per_layer"]
                 if m["name"] == "backend_start_s")
    assert entry == {"name": "backend_start_s", "unit": "s",
                     "better": "lower", "source": "host_clock",
                     "layer": "device", "moves": "setup_s"}
    assert any(m["layer"] == "device" and m is not entry
               for m in BENCH["per_layer"])


def test_require_times_the_one_call_that_starts_the_backend(monkeypatch):
    """``device.require`` clocks ``jax.devices()`` alone: not the import of
    jax before it, nothing after it."""
    import time
    import types

    import device as device_mod

    class Dev:
        platform, device_kind = "cpu", "cpu"

    def devices():
        time.sleep(0.05)
        return [Dev()]

    monkeypatch.setitem(__import__("sys").modules, "jax",
                        types.SimpleNamespace(devices=devices))
    monkeypatch.setattr(device_mod, "BACKEND_START_S", 0.0)
    t = time.perf_counter()
    info = device_mod.require(1, cpu_rehearsal=True)
    whole = time.perf_counter() - t
    assert info == {"platform": "cpu", "kind": "cpu", "count": 1}
    assert 0.05 <= device_mod.BACKEND_START_S <= whole
