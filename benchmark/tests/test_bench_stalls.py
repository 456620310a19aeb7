"""The per-layer metrics of the train loop's stall ledger and of a batch's
landing on the chip (ISSUE 51): seven readers of the program's stats section
``stalls`` and two of the span ``producer_h2d_land``. Each gives ``None`` on a
run whose program has no such section or span (the parent of the PR that
added them), so the metric is left out of the line; the right number on a
hand-made run; 0 and never a division by zero where nothing was lost; and
each has its entry in BENCHMARK.json, found by name."""

import importlib
import json
import os

import pytest

from conftest import BENCH_DIR, ROOT

with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
    BENCH = json.load(f)

TRAIN_CELLS = [
    "alexnet.lmdb", "googlenet.lmdb", "olmoe.l1.pack4k", "ouro.loop4.pack8k",
    "zaya1.e8of16.pack8k", "trinity.e16of128.pack8k",
    "kimi_linear.e8of256.pack8k", "smallthinker.e16of64.pack16k",
    "olmo_hybrid.p1.pack8k",
    # ISSUE 63: their runners hand the ledger too
    "granite_h.p1.pack8k", "glm_flash.e8of64.pack8k", "xing4.e8of64.hc4"]
LMDB_CELLS = ["alexnet.lmdb", "googlenet.lmdb"]

# a window of 1,000 steps at 29.5 ms that lost 310 ms in five stalls
LEDGER = {
    "pace_ms": 29.5, "steps": 1000, "window_ms": 31000.0, "stalls": 5,
    "lost_ms": 310.0,
    "lost_ms_by_cause": {"input": 120.0, "device": 60.0, "freeze": 90.0,
                         "unnamed": 40.0},
    "longest_ms": 95.5, "freeze_ms": 250.0, "events_dropped": 0,
    "summary_ms": 11.0,
    "worst": {"412": {"at_ms": 12100.0, "interval_ms": 125.0,
                      "lost_ms": 95.5, "cause": "freeze",
                      "train": "dispatch_window iter=416",
                      "reader": "producer_queue_full batch=419"}}}
QUIET = {"pace_ms": 29.5, "steps": 1000, "window_ms": 29470.5, "stalls": 0,
         "lost_ms": 0.0, "lost_ms_by_cause": {}, "longest_ms": 0.0,
         "freeze_ms": 0.0, "events_dropped": 0, "summary_ms": 9.0,
         "worst": {}}
# every stall of the window was the chip's own: nothing lost on the host
DEVICE_ONLY = {**QUIET, "stalls": 2, "lost_ms": 50.0, "longest_ms": 30.0,
               "lost_ms_by_cause": {"device": 50.0}}
# fewer than three completions: the program could make no ledger
EMPTY = {**QUIET, "steps": 0, "pace_ms": 0.0, "window_ms": 0.0}


def span(name, ts, dur, batch, nbytes=None, thread="prefetcher"):
    args = {"batch": batch}
    if nbytes is not None:
        args["bytes"] = nbytes
    return {"name": name, "cat": "input", "ph": "X", "ts": ts, "dur": dur,
            "pid": 1, "tid": 7, "args": args, "thread": thread}


# two batches of 316.6 MB: 7 ms of calls + 13 ms until they landed, and 8 + 17
SPANS = [span("producer_h2d", 0.0, 7000.0, 0, 316_600_000),
         span("producer_h2d_land", 7000.0, 13000.0, 0, 316_600_000),
         span("producer_h2d", 30000.0, 8000.0, 1, 316_600_000),
         span("producer_h2d_land", 38000.0, 17000.0, 1, 316_600_000),
         span("producer_queue_full", 55000.0, 2000.0, 1),
         {"name": "step_done", "cat": "step", "ph": "i", "s": "t",
          "ts": 60000.0, "pid": 1, "tid": 9, "args": {"iter": 0},
          "thread": "drainer"}]


def run_with(section=None, spans=()):
    sections = {"startup": {"route": "loaded"}}
    if section is not None:
        sections["stalls"] = section
    return {"steps": 1000, "window_s": 31.0, "spans": list(spans),
            "stats": {"sections": sections}}


WANT = {   # metric: (unit, better, layer, cells, LEDGER, QUIET, DEVICE_ONLY)
    "stall_lost_share": ("%", "lower", "train_loop", TRAIN_CELLS,
                         1.0, 0.0, 100.0 * 50.0 / 29470.5),
    "stall_host_ms_per_step": ("ms", "lower", "train_loop", TRAIN_CELLS,
                               0.25, 0.0, 0.0),
    "stall_device_ms_per_step": ("ms", "lower", "train_loop", TRAIN_CELLS,
                                 0.06, 0.0, 0.05),
    "stalls_per_1k_steps": ("count", "lower", "train_loop", TRAIN_CELLS,
                            5.0, 0.0, 2.0),
    "stall_longest_ms": ("ms", "lower", "train_loop", TRAIN_CELLS,
                         95.5, 0.0, 30.0),
    "stall_unnamed_share": ("%", "lower", "train_loop", TRAIN_CELLS,
                            16.0, 0.0, 0.0),
    "host_freeze_ms_per_step": ("ms", "lower", "train_loop", TRAIN_CELLS,
                                0.25, 0.0, 0.0),
}
SPAN_WANT = {   # metric: (unit, better, layer, cells, on SPANS)
    "h2d_land_ms_per_batch": ("ms", "lower", "input", LMDB_CELLS, 15.0),
    "h2d_gb_per_s": ("GB/s", "higher", "input", LMDB_CELLS,
                     (0.3166 / 0.020 + 0.3166 / 0.025) / 2),
}


def reader(name):
    assert os.path.exists(os.path.join(BENCH_DIR, "layer_metrics",
                                       name + ".py"))
    return importlib.import_module(f"layer_metrics.{name}").reduce


@pytest.mark.parametrize("name", sorted(WANT))
def test_a_ledger_reader_on_a_hand_made_run(name):
    *_, full, quiet, device_only = WANT[name]
    reduce = reader(name)
    assert reduce(run_with(LEDGER, SPANS)) == pytest.approx(full)
    # zero lost: 0, never a division by zero
    assert reduce(run_with(QUIET)) == quiet == 0.0
    assert reduce(run_with(DEVICE_ONLY)) == pytest.approx(device_only)
    # a program without the section, without stats, with an empty ledger
    assert reduce(run_with(None, SPANS)) is None
    assert reduce({"spans": SPANS, "steps": 1000}) is None
    assert reduce({"spans": [], "stats": None}) is None
    assert reduce(run_with(EMPTY)) is None


@pytest.mark.parametrize("name", sorted(SPAN_WANT))
def test_a_landing_reader_on_a_hand_made_run(name):
    want = SPAN_WANT[name][-1]
    reduce = reader(name)
    assert reduce(run_with(LEDGER, SPANS)) == pytest.approx(want)
    # the parent's run: producer_h2d alone, no landing span
    parent = [{k: v for k, v in e.items() if k != "thread"}
              for e in SPANS if e["name"] != "producer_h2d_land"]
    assert reduce(run_with(None, parent)) is None
    assert reduce(run_with(LEDGER, [])) is None
    assert reduce({"spans": None}) is None and reduce({}) is None


def test_the_rate_takes_only_batches_with_both_spans_and_their_bytes():
    reduce = reader("h2d_gb_per_s")
    # batch 1's landing fell outside the window; a span without bytes
    cut = [e for e in SPANS
           if not (e["name"] == "producer_h2d_land"
                   and e["args"]["batch"] == 1)]
    assert reduce(run_with(None, cut)) == pytest.approx(0.3166 / 0.020)
    bare = [span("producer_h2d", 0.0, 7000.0, 0),
            span("producer_h2d_land", 7000.0, 13000.0, 0)]
    assert reduce(run_with(None, bare)) is None
    still = [span("producer_h2d", 0.0, 0.0, 0, 24),
             span("producer_h2d_land", 0.0, 0.0, 0, 24)]
    assert reduce(run_with(None, still)) is None       # no time: no rate


def test_the_accepted_host_span_readers_take_events_that_say_their_thread():
    """``thread`` is one key more on an event: the readers that index
    ``name`` / ``ts`` / ``dur`` / ``args`` read what they read without it."""
    bare = [{k: v for k, v in e.items() if k != "thread"} for e in SPANS]
    for name in ("h2d_ms_per_batch", "producer_idle_share",
                 "slowest_step_over_median", "gc_pause_ms_per_step",
                 "input_wait_share", "producer_ms_per_batch",
                 "host_dispatch_ms_per_step", "window_wait_ms_per_step",
                 "telemetry_ms_per_display"):
        reduce = reader(name)
        assert reduce(run_with(None, SPANS)) == reduce(run_with(None, bare))
    assert reader("h2d_ms_per_batch")(run_with(None, SPANS)) == 7.5


def test_the_nine_entries_follow_the_contract():
    by_name = {m["name"]: m for m in BENCH["per_layer"]}
    cells = {w["name"] for w in BENCH["workloads"]}
    ends = {m["name"] for m in BENCH["end_to_end"]}
    layers = {m["layer"] for m in BENCH["per_layer"]
              if m["name"] not in WANT and m["name"] not in SPAN_WANT}
    for name, (unit, better, layer, where, *_) in {**WANT,
                                                   **SPAN_WANT}.items():
        m = by_name[name]                       # found by name, not by place
        assert {k: v for k, v in m.items() if k != "workloads"} == {
            "name": name, "unit": unit, "better": better,
            "source": "program_span", "layer": layer,
            "moves": "images_per_s_per_chip"}
        # a cell in a list by membership, not by the list's end
        assert set(where) <= set(m["workloads"]) <= cells
        assert m["moves"] in ends
        assert layer in layers                  # a layer the benchmark names
        assert len(name) <= 64 and name.replace("_", "").isalnum()
        assert 1 <= len(unit) <= 16 and " " not in unit
    # the two cells whose window is the benchmark's own loop are in no list
    assert not {"alexnet.resident", "alexnet.dp4.resident"} & set(TRAIN_CELLS)
    # every cell of the lists reports the end-to-end metric they move
    moved = next(m for m in BENCH["end_to_end"]
                 if m["name"] == "images_per_s_per_chip")
    assert set(moved.get("workloads", cells)) >= set(TRAIN_CELLS)
    assert len({m["name"] for m in BENCH["per_layer"]}) \
        == len(BENCH["per_layer"]) <= 128
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        assert len(f.read()) < 64 * 1024


def test_the_accepted_entries_are_still_there():
    """Nothing that was there is retired by the nine: PR 50's names and the
    nine, each found by name wherever it stands."""
    names = {m["name"] for m in BENCH["per_layer"]}
    assert names >= {
        "stall_lost_share", "stall_host_ms_per_step",
        "stall_device_ms_per_step", "stalls_per_1k_steps",
        "stall_longest_ms", "stall_unnamed_share", "host_freeze_ms_per_step",
        "h2d_land_ms_per_batch", "h2d_gb_per_s",
        "setup_before_program_s", "setup_after_first_step_s",
        "stall_share", "slowest_step_over_median", "compiles_in_window",
        "h2d_ms_per_batch"}
