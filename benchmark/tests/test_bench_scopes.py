"""The readers that join a run with what the program says about itself: the
device trace with the step's op -> (layer, pass) map (``scope_trace``), and
the measured window with the spans of the program's own threads
(``host_spans``). On hand-made input, on the trace recorded on the v5e, and
end to end in the CPU rehearsal."""

import gzip
import json
import os
import subprocess
import sys

import pytest

import device_trace as dt
import host_spans
import scope_trace
from conftest import BENCH_DIR, ROOT
from layer_metrics import (bwd_ms_per_step, fwd_ms_per_step,
                           gc_pause_ms_per_step, h2d_ms_per_batch,
                           matmul_ms_per_step, pool_bwd_ms_per_step,
                           producer_idle_share, producer_ms_per_batch,
                           scope_coverage, slowest_step_over_median,
                           telemetry_ms_per_display, update_ms_per_step)

HERE = os.path.dirname(os.path.abspath(__file__))
DEVICE_READERS = (scope_coverage, fwd_ms_per_step, bwd_ms_per_step,
                  update_ms_per_step, pool_bwd_ms_per_step,
                  matmul_ms_per_step)
HOST_READERS = (producer_ms_per_batch, h2d_ms_per_batch, producer_idle_share,
                gc_pause_ms_per_step, telemetry_ms_per_display,
                slowest_step_over_median)


def test_the_instruction_is_the_labels_second_word_or_the_whole_name():
    assert scope_trace.instruction(
        "pallas-call maxpool_bwd.4 f32[512,256,27,27]") == "maxpool_bwd.4"
    assert scope_trace.instruction(
        "fusion fusion.31 (f32[512,256,13,13], bf16[256])") == "fusion.31"
    assert scope_trace.instruction("dot_general.13") == "dot_general.13"


#   0    10   20   30   40   50   60   70   80   90  100 ns, two steps
#   |-- while.1 ----------------|         |-fusion.2|
#     |conv.1|  |ar.1------|                   |kernel.3---|  (not in the map)
OPS = [("while while.1 f32[8]", 0.0, 50.0), ("convolution conv.1 f32[8]", 2.0, 8.0),
       ("all-reduce ar.1 f32[8]", 20.0, 20.0), ("fusion fusion.2 f32[8]", 70.0, 20.0),
       ("pallas-call kernel.3 f32[8]", 90.0, 10.0)]
SCOPES = {"ops": {"while.1": "pool1|bwd", "conv.1": "conv1|fwd",
                  "ar.1": "grad_sync_bucket0|misc",
                  "fusion.2": "optimizer_update|misc"},
          "types": {"pool1": "POOLING", "conv1": "CONVOLUTION",
                    "grad_sync_bucket0": "sync",
                    "optimizer_update": "update"}}


def small_run(scopes=SCOPES, devices=None):
    return {"trace": {"steps": 2, "spans": [], "async": {},
                      "devices": devices or {"0": OPS}},
            "stats": {"sections": {"step_scopes": scopes} if scopes else {}}}


def test_parts_and_residual_partition_the_busy_time_by_hand():
    run = small_run()
    per = 1e6 * 2                    # ns -> ms per step, one chip, two steps
    split = scope_trace.parts(run)
    assert split["busy_ms"] == pytest.approx(80.0 / per)
    assert split["busy_ms"] == pytest.approx(
        dt.busy_seconds(run["trace"]["devices"]) * 1e3 / 2)
    # the while shell keeps its own 22 ns: 50 less the two operations in it
    assert split["by"] == {
        ("net", "POOLING", "bwd"): pytest.approx(22.0 / per),
        ("net", "CONVOLUTION", "fwd"): pytest.approx(8.0 / per),
        ("sync", "", "misc"): pytest.approx(20.0 / per),
        ("update", "", "misc"): pytest.approx(20.0 / per)}
    assert split["unmapped_ops"] == {
        "pallas-call kernel.3 f32[8]": pytest.approx(10.0 / per)}
    s = scope_trace.summary(run)
    assert s["fwd_ms"] + s["bwd_ms"] + s["update_ms"] + s["sync_ms"] \
        + s["unmapped_ms"] == pytest.approx(s["busy_ms"])
    assert scope_coverage.reduce(run) == pytest.approx(100 * 70 / 80)
    assert fwd_ms_per_step.reduce(run) == pytest.approx(8.0 / per)
    assert bwd_ms_per_step.reduce(run) == pytest.approx(22.0 / per)
    assert update_ms_per_step.reduce(run) == pytest.approx(20.0 / per)
    assert pool_bwd_ms_per_step.reduce(run) == pytest.approx(22.0 / per)
    assert matmul_ms_per_step.reduce(run) == pytest.approx(8.0 / per)
    # a second chip that ran half as much: the mean over chips
    two = small_run(devices={"0": OPS,
                             "1": [("convolution conv.1 f32[8]", 0.0, 4.0)]})
    assert fwd_ms_per_step.reduce(two) == pytest.approx((8.0 + 4.0) / 2 / per)


def test_no_map_or_no_trace_leaves_every_device_metric_out():
    """What the parent of the PR that added the map gives: no section, or
    (its jit path) a section with no ops. Nothing raises."""
    for run in (small_run(scopes=None),
                small_run(scopes={"ops": {}, "types": {}, "why": "jit"}),
                dict(small_run(), trace=None),
                dict(small_run(), stats=None)):
        assert all(m.reduce(run) is None for m in DEVICE_READERS)
        assert scope_trace.summary(run) is None
    # a map that holds none of the kind: the metric is 0, not absent
    none_pooled = small_run(scopes={"ops": {"conv.1": "conv1|fwd"},
                                    "types": {"conv1": "CONVOLUTION"}})
    assert pool_bwd_ms_per_step.reduce(none_pooled) == 0.0
    assert update_ms_per_step.reduce(none_pooled) == 0.0


# --------------------------------------------------------------------------- #
# the recorded trace: one step of alexnet.dp4.resident on four v5e chips
# (test_bench_trace.py says how it was cut), under a hand-made map
# --------------------------------------------------------------------------- #

@pytest.fixture(scope="module")
def recorded(tmp_path_factory):
    path = tmp_path_factory.mktemp("trace") / "one_step.xplane.pb"
    with gzip.open(os.path.join(
            HERE, "data", "v5e_dp4_alexnet_one_step.xplane.pb.gz")) as f:
        path.write_bytes(f.read())
    return dict(dt.load(str(path), "tpu", "benchmark_align"), steps=1,
                spans=[])


def test_recorded_trace_parts_and_residual_sum_to_the_self_time(recorded):
    labels = {op[0] for ops in recorded["devices"].values() for op in ops}
    ops, types = {}, {"pool2": "POOLING", "conv_any": "CONVOLUTION",
                      "grad_sync_bucket0": "sync",
                      "optimizer_update": "update"}
    # by hand, for this trace only: the three f32 pool-backward kernels (PR
    # 22 read them off their shapes), every all-reduce, every convolution
    # fusion; everything else stays outside the map
    for label in labels:
        inst = scope_trace.instruction(label)
        if dt.is_pallas(label) and "f32[512," in label:
            ops[inst] = "pool2|bwd"
        elif dt.is_collective(label):
            ops[inst] = "grad_sync_bucket0|misc"
        elif label.startswith("fusion convolution"):
            ops[inst] = "conv_any|fwd"
    run = {"trace": recorded,
           "stats": {"sections": {"step_scopes": {"ops": ops,
                                                  "types": types}}}}
    split = scope_trace.parts(run)
    s = scope_trace.summary(run)
    busy_ms = dt.busy_seconds(recorded["devices"]) * 1e3
    # self times partition the busy time: the op line nests, it never
    # overlaps sideways
    assert split["busy_ms"] == pytest.approx(busy_ms, rel=1e-9)
    assert s["fwd_ms"] + s["bwd_ms"] + s["update_ms"] + s["sync_ms"] \
        + s["unmapped_ms"] == pytest.approx(busy_ms, rel=1e-9)
    # the three kernels of PR 22's breakdown: 151.3 + 120.9 + 77.5 ms
    assert pool_bwd_ms_per_step.reduce(run) == pytest.approx(349.8, abs=0.3)
    top = dict(dt.top_ops(recorded["devices"]["0"], 10))
    assert pool_bwd_ms_per_step.reduce(run) == pytest.approx(1e3 * sum(
        v for k, v in top.items() if dt.is_pallas(k) and "f32[512," in k),
        rel=1e-3)
    assert s["sync_ms"] == pytest.approx(
        (3316192 + 3312743 + 3346238 + 3332552) / 4 / 1e6)
    assert 0 < scope_coverage.reduce(run) < 100
    assert update_ms_per_step.reduce(run) == 0.0


# --------------------------------------------------------------------------- #
# the host readers on canned spans
# --------------------------------------------------------------------------- #

def span(name, ts, dur=None, tid=1, **args):
    e = {"name": name, "ts": float(ts), "tid": tid, "args": args,
         "ph": "i" if dur is None else "X"}
    if dur is not None:
        e["dur"] = float(dur)
    return e


def test_host_readers_on_canned_spans():
    READER, PREFETCHER, TRAIN, DRAINER = 7, 8, 1, 9
    spans = [
        span("producer_read", 0, 400e3, READER, batch=0),
        span("producer_read", 500e3, 600e3, READER, batch=1),
        span("producer_queue_full", 1100e3, 250e3, READER, batch=1),
        # the prefetcher's full queue is not the reader's
        span("producer_queue_full", 0, 900e3, PREFETCHER, batch=0),
        span("producer_h2d", 400e3, 30e3, PREFETCHER, batch=0, bytes=8),
        span("producer_h2d", 1100e3, 50e3, PREFETCHER, batch=1, bytes=8),
        span("dispatch", 0, 1e3, TRAIN, iter=0),
        span("gc_pause", 10e3, 44e3, TRAIN, generation=2, collected=5),
        span("gc_pause", 900e3, 1e3, READER, generation=0, collected=0),
        span("telemetry_dump", 1000e3, 3e3, TRAIN, iter=4),
        span("telemetry_dump", 2000e3, 5e3, TRAIN, iter=8),
    ] + [span("step_done", ts, None, DRAINER, iter=i)
         for i, ts in enumerate((0, 500e3, 1000e3, 1544e3, 2044e3))]
    run = {"spans": spans, "steps": 5, "window_s": 2.5}
    assert producer_ms_per_batch.reduce(run) == pytest.approx(500.0)
    assert h2d_ms_per_batch.reduce(run) == pytest.approx(40.0)
    assert producer_idle_share.reduce(run) == pytest.approx(10.0)
    assert gc_pause_ms_per_step.reduce(run) == pytest.approx(9.0)
    assert telemetry_ms_per_display.reduce(run) == pytest.approx(4.0)
    # gaps 500 500 544 500: the +44 ms pace shows as 1.088
    assert slowest_step_over_median.reduce(run) == pytest.approx(544 / 500)
    assert host_spans.mean_ms(run, "dispatch") == 1.0

    # the recorder was on and nothing of the kind happened: 0, not absent
    quiet = dict(run, spans=[e for e in spans if e["name"] not in (
        "gc_pause", "producer_queue_full")])
    assert gc_pause_ms_per_step.reduce(quiet) == 0.0
    assert producer_idle_share.reduce(quiet) == 0.0

    # a program without these spans (the parent records dispatch and the
    # waits only), or no spans at all: every metric left out
    for old in (dict(run, spans=[span("dispatch", 0, 1e3, TRAIN, iter=0),
                                 span("prefetch_wait", 0, 1e3, TRAIN)]),
                dict(run, spans=[])):
        assert all(m.reduce(old) is None for m in HOST_READERS)


# --------------------------------------------------------------------------- #
# end to end: the CPU rehearsal prints all twelve
# --------------------------------------------------------------------------- #

def test_cpu_rehearsal_prints_all_twelve_and_the_parts_sum():
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    done = subprocess.run(
        [sys.executable, os.path.join(BENCH_DIR, "run.py"), "--workload",
         "alexnet.lmdb", "--seed", "5", "--seconds", "1", "--trace", "1",
         "--cpu-tiny"], cwd=ROOT, env=env, capture_output=True, text=True,
        timeout=600)
    assert done.returncode == 0, done.stderr[-3000:]
    line = json.loads(done.stdout.strip().splitlines()[-1])
    assert line["device"]["platform"] == "cpu"
    values = {k: v["value"] for k, v in line["metrics"].items()}
    twelve = [m.__name__.split(".")[-1] for m in DEVICE_READERS + HOST_READERS]
    assert set(twelve) <= set(values)
    assert 0 < values["scope_coverage"] <= 100
    assert values["fwd_ms_per_step"] > 0 and values["bwd_ms_per_step"] > 0
    assert values["matmul_ms_per_step"] > 0
    assert values["producer_ms_per_batch"] > 0
    assert values["slowest_step_over_median"] >= 1.0
    assert values["gc_pause_ms_per_step"] >= 0.0
    # what the coverage reader says beside its number: the identity
    said = next(ln for ln in done.stderr.splitlines()
                if ln.startswith("[scope_trace]"))
    parts = json.loads(said.split("ms/step ")[1].split(";")[0]
                       .replace("'", '"'))
    assert parts["fwd_ms"] + parts["bwd_ms"] + parts["update_ms"] \
        + parts["sync_ms"] + parts["unmapped_ms"] == pytest.approx(
            parts["busy_ms"], rel=1e-2)
