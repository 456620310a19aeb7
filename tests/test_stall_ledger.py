"""The train loop's stall ledger (ISSUE 51): ``runtime/spans.py``'s
``stall_ledger`` on synthetic timelines, whose thread an event is, the
heartbeat that records ``host_freeze``, ``producer_h2d_land``, and what a
run with the recorder disabled does not do."""

import json
import subprocess
import sys
import threading

import numpy as np
import pytest

from poseidon_tpu.runtime import spans as S
from poseidon_tpu.runtime.spans import SpanRecorder, recorder as global_rec

P, DEPTH, STEPS = 0.020, 4, 40        # pace, max_in_flight, steps a timeline
PW, DISPATCH, GAP = 0.0002, 0.001, 0.00005
OLD_KEYS = {"name", "cat", "ph", "ts", "pid", "tid"}


@pytest.fixture
def clean_recorder():
    global_rec.disable()
    global_rec.clear()
    if global_rec.startup_open:
        global_rec.end_startup()
    yield global_rec
    global_rec.disable()
    global_rec.clear()


def timeline(late_at=None, late_by=0.0, seen_late=None, host=None,
             freeze=None, every_step_frozen=0.0):
    """The recorder's events of ``STEPS`` steps at pace ``P`` with the loop
    ``DEPTH - 1`` dispatches ahead: the drainer's ``step_done`` instants
    (from ``late_at`` on every completion ``late_by`` later; ``seen_late``
    = (iter, seconds) moves that one completion alone) and the train
    thread's spans, one host step a pace: ``prefetch_wait``, ``dispatch``
    with its children, ``dispatch_window``, a sliver in no span. ``host``
    = {host step: {"pw" | "dw" | "gap": extra seconds}}; ``freeze`` = one
    ``host_freeze`` (start, seconds); ``every_step_frozen`` = seconds of
    one in every host step (a call that holds the interpreter)."""
    events = []
    for i in range(STEPS):
        at = (i + 1) * P
        if late_at is not None and i >= late_at:
            at += late_by
        if seen_late is not None and i == seen_late[0]:
            at += seen_late[1]
        events.append(("step_done", "step", at, None, 2, {"iter": i},
                       "drainer"))
    t = 0.0
    for s in range(STEPS):
        extra = (host or {}).get(s, {})

        def put(name, cat, dur, args):
            events.append((name, cat, t, dur, 1, args, "train"))

        it = s + DEPTH - 1
        put("prefetch_wait", "input", PW + extra.get("pw", 0.0),
            {"iter": it, "batch": it})
        t += PW + extra.get("pw", 0.0)
        put("dispatch", "step", DISPATCH, {"iter": it})
        put("dispatch_rng", "step", 0.0002, {"iter": it})
        events.append(("dispatch_execute", "step", t + 0.0002, 0.0007, 1,
                       {"iter": it}, "train"))
        t += DISPATCH
        dw = P - PW - DISPATCH - GAP + extra.get("dw", 0.0)
        put("dispatch_window", "step", dw, {"iter": it + 1})
        if every_step_frozen:
            events.append(("host_freeze", "runtime", t + 0.001,
                           every_step_frozen, 3, {"cpu_ms": 100.0},
                           "heartbeat"))
        t += dw + GAP + extra.get("gap", 0.0)
    if freeze is not None:
        events.append(("host_freeze", "runtime", freeze[0], freeze[1], 3,
                       {"cpu_ms": 0.1}, "heartbeat"))
    return events


CASES = {
    # name: (timeline's arguments, stalls, cause, lost milliseconds)
    "an even pace gives none": ({}, 0, None, 0.0),
    "a completion seen late and made up within the window loses nothing": (
        dict(seen_late=(20, 0.015)), 0, None, 0.0),
    "seen two paces late and made up by two empty intervals": (
        dict(seen_late=(20, 0.045)), 0, None, 0.0),
    "the train thread held in prefetch_wait is input": (
        dict(late_at=20, late_by=0.050, host={20: {"pw": 0.050}}),
        1, "input", 50.0),
    "waiting in dispatch_window through a long interval is device": (
        dict(late_at=20, late_by=0.060, host={20: {"dw": 0.060}}),
        1, "device", 60.0),
    "a host_freeze over the excess is freeze whatever else is open": (
        dict(late_at=20, late_by=0.060, host={20: {"dw": 0.060}},
             freeze=(20 * P + 0.001, 0.058)), 1, "freeze", 60.0),
    "the train thread in no span is unnamed": (
        dict(late_at=20, late_by=0.040, host={20: {"gap": 0.040}}),
        1, "unnamed", 40.0),
    "a delay one queue before the late completion is still found": (
        dict(late_at=23, late_by=0.050, host={20: {"pw": 0.050}}),
        1, "input", 50.0),
    "a freeze one queue before it too": (
        dict(late_at=23, late_by=0.030, host={20: {"gap": 0.030}},
             freeze=(20 * P + 0.019, 0.029)), 1, "freeze", 30.0),
    "a freeze that every step has does not make a stall its own": (
        dict(late_at=20, late_by=0.030, host={20: {"pw": 0.030}},
             every_step_frozen=0.009), 1, "input", 30.0),
    "an excess under the threshold is no stall": (
        dict(late_at=20, late_by=0.0015, host={20: {"pw": 0.0015}}),
        0, None, 0.0),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_stall_ledger_on_a_synthetic_timeline(case):
    kwargs, stalls, cause, lost_ms = CASES[case]
    doc = S.stall_ledger(timeline(**kwargs), DEPTH)
    assert doc["steps"] == STEPS and doc["pace_ms"] == pytest.approx(20.0)
    assert doc["window_ms"] == pytest.approx(
        (STEPS - 1) * 20.0 + kwargs.get("late_by", 0.0) * 1e3)
    assert doc["stalls"] == stalls
    assert doc["lost_ms"] == pytest.approx(lost_ms, abs=1.0)
    assert doc["freeze_ms"] == pytest.approx(
        kwargs["freeze"][1] * 1e3 if "freeze" in kwargs
        else kwargs.get("every_step_frozen", 0.0) * 1e3 * STEPS)
    if not stalls:
        assert doc["lost_ms_by_cause"] == {} and doc["worst"] == {} \
            and doc["longest_ms"] == 0.0
        return
    assert doc["lost_ms_by_cause"] == {cause: doc["lost_ms"]}
    assert doc["longest_ms"] == doc["lost_ms"]
    (it, row), = doc["worst"].items()
    assert it == kwargs["late_at"] and row["cause"] == cause
    assert row["lost_ms"] == doc["lost_ms"]
    assert row["interval_ms"] == pytest.approx(20.0 + lost_ms, abs=1.0)
    # whose span was open at the interval's midpoint, with its identifiers
    if cause == "unnamed":
        assert "train" not in row         # in none: that is the finding
    else:
        name, *ids = row["train"].split()
        assert name in S._STALL_CATEGORY and any(
            i.startswith("iter=") for i in ids)
    assert "drainer" not in row           # the drainer records no span


def test_stall_ledger_keeps_the_worst_and_sums_by_cause():
    host = {s: {"pw": 0.010 + 0.001 * s} for s in range(4, 40, 2)}
    events = timeline(host=host)
    # every delayed host step's completion and all after it come later
    shift, moved = 0.0, []
    for e in events:
        if e[0] == "step_done":
            shift += host.get(e[5]["iter"], {}).get("pw", 0.0)
            e = e[:2] + (e[2] + shift,) + e[3:]
        moved.append(e)
    doc = S.stall_ledger(moved, DEPTH, dropped=7)
    assert doc["stalls"] == len(host) == 18 and doc["events_dropped"] == 7
    assert len(doc["worst"]) == S.WORST_STALLS
    lost = [row["lost_ms"] for row in doc["worst"].values()]
    assert lost == sorted(lost, reverse=True)
    assert doc["longest_ms"] == lost[0] == pytest.approx(48.0, abs=1.0)
    assert set(doc["lost_ms_by_cause"]) == {"input"}
    assert doc["lost_ms"] == pytest.approx(
        sum(v["pw"] for v in host.values()) * 1e3, abs=1.0)


def test_the_steps_of_one_scan_chunk_dispatch_are_one_completion():
    events = []
    for d in range(12):                  # 12 dispatches of 4 steps each
        at = (d + 1) * 0.080 + (0.100 if d >= 6 else 0.0)
        for i in range(4):
            events.append(("step_done", "step", at + i * 1e-6, None, 2,
                           {"iter": 4 * d + i, "dispatch": 4 * d},
                           "drainer"))
    doc = S.stall_ledger(events, 2)
    assert doc["steps"] == 48 and doc["pace_ms"] == pytest.approx(80.0,
                                                                  abs=0.01)
    assert doc["stalls"] == 1 and doc["lost_ms"] == pytest.approx(100.0,
                                                                  abs=0.1)
    assert list(doc["worst"]) == [27]    # the dispatch's last step
    assert doc["worst"][27]["cause"] == "unnamed"    # no train thread seen


def test_too_few_completions_give_an_empty_ledger():
    doc = S.stall_ledger(timeline()[:2], DEPTH)
    assert doc["stalls"] == 0 and doc["window_ms"] == 0.0 \
        and doc["steps"] == 2


# --------------------------------------------------------------------------- #
# whose thread
# --------------------------------------------------------------------------- #

def test_every_event_of_a_named_thread_says_whose_it_is(tmp_path):
    rec = SpanRecorder()
    rec.enable()
    try:
        def work():
            with rec.span("producer_read", "input", {"batch": 0}):
                pass
            rec.instant("mark", "input")

        for name in ("reader", None):
            t = threading.Thread(target=work, name=name)
            t.start()
            t.join()
        with rec.span("before", "step"):
            pass
        rec.set_role("train")
        with rec.span("dispatch", "step", {"iter": 0}):
            pass
    finally:
        rec.disable()
    events = [e for e in rec.trace_events() if e["name"] != "host_freeze"]
    by_thread = {}
    for e in events:
        by_thread.setdefault(e.get("thread"), []).append(e)
        assert OLD_KEYS <= set(e) and ("dur" in e) == (e["ph"] == "X")
        assert e["ph"] in ("X", "i")             # no M record among them
    assert [e["name"] for e in by_thread["reader"]] == ["producer_read",
                                                        "mark"]
    assert [e["name"] for e in by_thread["train"]] == ["dispatch"]
    # a thread nobody named, and this one before it said: no key at all
    assert sorted(e["name"] for e in by_thread[None]) == [
        "before", "mark", "producer_read"]
    assert by_thread["reader"][0]["args"] == {"batch": 0}
    with open(rec.dump(str(tmp_path / "spans.json"))) as f:
        doc = json.load(f)["traceEvents"]
    rows = {(e["tid"], e["args"]["name"]) for e in doc if e["ph"] == "M"}
    assert all(e["name"] == "thread_name" for e in doc if e["ph"] == "M")
    assert {name for _, name in rows} >= {"reader", "train"}
    assert (by_thread["reader"][0]["tid"], "reader") in rows
    assert [e for e in doc if e["ph"] != "M" and e["name"] != "host_freeze"
            ] == events


def test_the_package_s_training_threads_are_made_with_their_roles():
    from poseidon_tpu.data.pipeline import DevicePrefetcher
    from poseidon_tpu.runtime.metrics import AsyncScalarFetcher

    class Pipe:
        def __iter__(self):
            return self

        def __next__(self):
            return {"data": np.zeros((2, 3), np.float32)}

    fetcher = AsyncScalarFetcher(2)
    feed = DevicePrefetcher([Pipe()], None, depth=1, passthrough=False)
    try:
        assert fetcher._thread.name == "drainer"
        assert feed._thread.name == "prefetcher"
    finally:
        feed.close()
        fetcher.close()


# --------------------------------------------------------------------------- #
# the heartbeat
# --------------------------------------------------------------------------- #

def _beating():
    return [t for t in threading.enumerate() if t.name == "heartbeat"]


def test_enable_starts_the_heartbeat_and_disable_joins_it():
    rec = SpanRecorder()
    before = len(_beating())
    rec.enable()
    # a new recorder is in its start-up phase, whose spans account for all
    # of its time already: the heartbeat waits for the phase to close
    assert rec.startup_open and len(_beating()) == before
    rec.end_startup()
    assert len(_beating()) == before + 1
    rec.enable()                                   # idempotent
    assert len(_beating()) == before + 1
    beat = rec._heartbeat
    rec.disable()
    assert len(_beating()) == before and not beat._thread.is_alive()
    rec.disable()                                  # and so is this
    rec.enable()                                   # a second life
    assert len(_beating()) == before + 1
    rec.disable()
    assert len(_beating()) == before


def test_a_process_that_never_enabled_has_no_heartbeat():
    code = ("import threading\n"
            "from poseidon_tpu.runtime.spans import recorder, span\n"
            "with span('dispatch', 'step'):\n"
            "    pass\n"
            "recorder.instant('step_done', 'step', {'iter': 0})\n"
            "print([t.name for t in threading.enumerate()])\n")
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, check=True).stdout
    assert json.loads(out.strip().replace("'", '"')) == ["MainThread"]


def test_the_late_wake_rule_on_an_injected_clock():
    rec = SpanRecorder()
    rec.enabled = True                   # record; no thread, no real sleep
    beat = S._Heartbeat(rec)
    assert not beat._thread.is_alive()
    quiet = (1.000, 10, 1, 500.0)
    # on time, and late by less than the threshold: nothing
    beat.beat(50.000, 50.0001, quiet, (1.001, 11, 1, 500.0))
    beat.beat(50.000, 50.0079, quiet, (1.001, 11, 1, 500.0))
    assert rec.trace_events() == []
    # 30 ms late: the span lies from the wake asked for to the wake got
    beat.beat(50.002, 50.032, (1.001, 11, 1, 500.0),
              (1.0011, 14, 3, 20500.0))
    (ev,) = rec.trace_events()
    assert (ev["name"], ev["cat"], ev["ph"]) == ("host_freeze", "runtime",
                                                 "X")
    assert ev["dur"] == pytest.approx(30_000.0, abs=1.0)
    assert ev["ts"] == pytest.approx(rec._epoch_us + 50.002e6, abs=1.0)
    assert ev["args"] == {"ms": 30.0, "cpu_ms": pytest.approx(0.1),
                          "nivcsw": 3, "majflt": 2, "throttled_us": 20000.0}
    # exactly at the threshold is not over it; no cpu.stat, no such key
    beat.threshold_s = 0.03125
    beat.beat(64.0, 64.03125, quiet, (1.0311, 14, 3, None))
    assert len(rec.trace_events()) == 1
    beat.threshold_s = 0.005
    beat.beat(64.0, 64.03125, (1.0311, 14, 3, None), (1.0311, 14, 3, None))
    assert len(rec.trace_events()) == 2
    assert "throttled_us" not in rec.trace_events()[1]["args"]


def test_an_on_time_wake_reads_the_clocks_alone(monkeypatch):
    """The loop itself on an injected clock: 200 wakes, the 70th 30 ms
    late. The counters are read at the start, on every 64th wake and on the
    late one; the freeze's ``cpu_ms`` is still the stretch's own."""
    rec = SpanRecorder()
    rec.enabled = True
    beat = S._Heartbeat(rec)
    read_at, sleeps = [], []

    class Clock:
        now, cpu = 100.0, 5.0

        @classmethod
        def perf_counter(cls):
            return cls.now

        @classmethod
        def process_time(cls):
            return cls.cpu

        @classmethod
        def sleep(cls, s):
            sleeps.append(s)
            late = 0.030 if len(sleeps) == 70 else 0.0
            cls.now += s + late
            cls.cpu += 0.001 + late / 10      # 1 ms a period; 3 ms frozen
            if len(sleeps) == 200:
                beat._stop.set()

    def readings(cpu_stat):
        read_at.append(len(sleeps))
        return Clock.cpu, 10 + len(sleeps), 1, None

    monkeypatch.setattr(S, "time", Clock)
    monkeypatch.setattr(S, "_readings", readings)
    monkeypatch.setattr(S, "_open_cpu_stat", lambda: None)
    beat._loop()
    assert sleeps == [beat.period_s] * 200
    assert read_at == [0, 64, 70, 128, 192]
    (ev,) = rec.trace_events()
    assert ev["dur"] == pytest.approx(30_000.0, abs=1.0)
    # the CPU time of the late sleep alone, the switches since wake 64
    assert ev["args"] == {"ms": 30.0, "cpu_ms": pytest.approx(4.0),
                          "nivcsw": 6, "majflt": 0}


def test_the_heartbeat_reads_this_machine_s_counters():
    import os
    assert S._readings(None)[3] is None      # no cpu.stat: no reading of it
    cpu_stat = S._open_cpu_stat()            # cgroup v2's, or None
    try:
        cpu, nivcsw, majflt, throttled = S._readings(cpu_stat)
        assert cpu > 0 and nivcsw >= 0 and majflt >= 0
        assert (throttled is None) == (cpu_stat is None)
        assert throttled is None or throttled >= 0
    finally:
        if cpu_stat is not None:
            os.close(cpu_stat)


# --------------------------------------------------------------------------- #
# producer_h2d_land, and the Engine's section
# --------------------------------------------------------------------------- #

class _Pipe:
    def __iter__(self):
        return self

    def __next__(self):
        return {"data": np.zeros((2, 3), np.float32),
                "label": np.zeros((2,), np.int32)}


def test_the_landing_is_a_span_when_enabled_and_no_wait_when_disabled(
        clean_recorder, monkeypatch):
    import jax
    from poseidon_tpu.data import pipeline
    waited = []
    ready = jax.block_until_ready
    monkeypatch.setattr(jax, "block_until_ready",
                        lambda x: waited.append(x) or ready(x))
    feed = pipeline.DevicePrefetcher([_Pipe()], None, passthrough=True)
    next(feed)                            # batch 0, the recorder disabled
    assert waited == [] and clean_recorder.trace_events() == []
    clean_recorder.enable()
    clean_recorder.set_role("train")
    try:
        taken = [next(feed) for _ in range(2 * pipeline.LAND_EVERY)]
    finally:
        clean_recorder.disable()
        clean_recorder._roles.__dict__.pop("role", None)
    # every LAND_EVERY-th batch is waited for under the span, the batch
    # before it under none (the sampled copy gets the link to itself), and
    # no other
    every = pipeline.LAND_EVERY
    assert [w["data"] is taken[i]["data"] for w, i in
            zip(waited, (every - 2, every - 1, 2 * every - 2,
                         2 * every - 1))] == [True] * 4 == \
        [set(w) == {"data", "label"} for w in waited]
    spans = {}
    for e in clean_recorder.trace_events():
        if e["name"].startswith("producer_h2d"):
            spans.setdefault(e["name"], {})[e["args"]["batch"]] = e
    assert sorted(spans["producer_h2d"]) == list(
        range(1, 2 * pipeline.LAND_EVERY + 1))
    assert sorted(spans["producer_h2d_land"]) == [
        pipeline.LAND_EVERY, 2 * pipeline.LAND_EVERY]
    land = spans["producer_h2d_land"][pipeline.LAND_EVERY]
    h2d = spans["producer_h2d"][pipeline.LAND_EVERY]
    assert land["cat"] == "input" and land["thread"] == "train" \
        and land["tid"] == threading.get_ident()
    assert land["args"] == h2d["args"] == {"batch": pipeline.LAND_EVERY,
                                           "bytes": 32}
    assert land["ts"] >= h2d["ts"] + h2d["dur"] - 1.0


def test_the_prefetcher_records_the_landing_on_its_own_thread(
        clean_recorder):
    from poseidon_tpu.data.pipeline import DevicePrefetcher
    clean_recorder.enable()
    feed = DevicePrefetcher([_Pipe()], None, depth=1, passthrough=False)
    try:
        next(feed)
        next(feed)
    finally:
        feed.close()
        clean_recorder.disable()
    land = [e for e in clean_recorder.trace_events()
            if e["name"] == "producer_h2d_land"]
    assert [e["args"]["batch"] for e in land] == [0]
    assert land[0]["thread"] == "prefetcher"
    assert land[0]["tid"] != threading.get_ident()


SMALLNET = """
name: "LedgerNet"
layers {
  name: "src" type: MEMORY_DATA top: "data" top: "label"
  memory_data_param { batch_size: 8 channels: 1 height: 12 width: 12 }
}
layers {
  name: "ip1" type: INNER_PRODUCT bottom: "data" top: "ip1"
  inner_product_param { num_output: 5
    weight_filler { type: "xavier" } bias_filler { type: "constant" } }
}
layers { name: "loss" type: SOFTMAX_LOSS bottom: "ip1" bottom: "label"
  top: "loss" }
"""


def _engine(tmp_path, **kw):
    from poseidon_tpu.proto.messages import (SolverParameter,
                                             load_net_from_string)
    from poseidon_tpu.runtime.engine import Engine
    rs = np.random.RandomState(0)
    sp = SolverParameter(train_net_param=load_net_from_string(SMALLNET),
                         base_lr=0.01, lr_policy="fixed", display=4,
                         max_iter=12, random_seed=3)
    return Engine(sp, memory_data={
        "data": rs.randn(64, 1, 12, 12).astype(np.float32),
        "label": rs.randint(0, 5, 64)}, output_dir=str(tmp_path), **kw)


SECTION = {"pace_ms", "steps", "window_ms", "stalls", "lost_ms",
           "lost_ms_by_cause", "longest_ms", "freeze_ms", "events_dropped",
           "worst", "summary_ms"}


def test_a_traced_train_publishes_the_ledger_and_names_its_threads(
        tmp_path, clean_recorder):
    from poseidon_tpu.runtime.metrics import read_stats_yaml
    eng = _engine(tmp_path, trace_out="spans.json")
    try:
        assert _beating()
        eng.train()
        events = clean_recorder.trace_events()
        doc = eng.stats.snapshot()["sections"]["stalls"]
    finally:
        eng.close()
    assert not _beating()                 # the Engine stood the recorder down
    assert set(doc) == SECTION and doc["steps"] == 12
    assert doc["window_ms"] > 0 and doc["pace_ms"] > 0
    assert doc["lost_ms"] == pytest.approx(
        sum(doc["lost_ms_by_cause"].values()), abs=0.01)
    assert len(doc["worst"]) == min(doc["stalls"], S.WORST_STALLS)
    whose = {}
    for e in events:
        whose.setdefault(e["name"], set()).add(e.get("thread"))
    for name in ("prefetch_wait", "dispatch", "dispatch_rng",
                 "dispatch_execute", "dispatch_window", "hard_sync",
                 "telemetry_dump", "producer_h2d", "producer_h2d_land"):
        assert whose[name] == {"train"}, (name, whose[name])
    assert whose["producer_read"] == {"reader"}
    # a step's row is ingested by the drainer, or by the train thread when
    # the dispatch had already finished (the CPU's inline path)
    assert whose["step_done"] <= {"drainer", "train"}
    assert whose.get("host_freeze", {"heartbeat"}) == {"heartbeat"}
    # and the file a person reads has the section, key by key
    on_disk = read_stats_yaml(str(tmp_path / "stats.yaml"))["stalls"]
    assert set(on_disk) == SECTION and on_disk["steps"] == "12"


def test_an_untraced_train_has_no_heartbeat_no_landing_wait_no_section(
        tmp_path, clean_recorder, monkeypatch):
    import jax
    waited = []
    ready = jax.block_until_ready
    monkeypatch.setattr(jax, "block_until_ready",
                        lambda x: waited.append(x) or ready(x))
    eng = _engine(tmp_path)
    try:
        eng.train()
        assert not _beating()
        sections = eng.stats.snapshot()["sections"]
    finally:
        eng.close()
    assert "stalls" not in sections and "placement" in sections
    assert not any(isinstance(x, dict) and "data" in x for x in waited)
    assert clean_recorder.trace_events() == []
    assert "stalls:" not in (tmp_path / "stats.yaml").read_text()
