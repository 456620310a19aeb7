"""The reduction from device operations to busy time, idle gaps, collective
overlap and the breakdown: on intervals small enough to work by hand, and on
the small trace recorded on the v5e that is kept beside this file."""

import json
import os

import pytest

import device_trace as dt

HERE = os.path.dirname(os.path.abspath(__file__))

#   0    10   20   30   40   50   60   70   80   90  100
#   |-- while ------------------|         |-fusion-|
#     |conv|  |all-reduce|                      |ar.2----|
OPS = [("while", 0.0, 50.0), ("conv", 2.0, 8.0), ("all-reduce", 20.0, 20.0),
       ("fusion", 70.0, 20.0), ("all-reduce.2", 85.0, 15.0)]


def test_union_gaps_total():
    busy = dt.union(OPS)
    assert busy == [(0.0, 50.0), (70.0, 100.0)]
    assert dt.total(busy) == 80.0
    assert dt.gaps(busy) == [(50.0, 70.0)]
    assert dt.union([]) == [] and dt.gaps([]) == []


def test_the_traced_window_is_cut_where_a_counted_step_begins():
    """The traced window counts what the chip ran from the first counted
    step on (ISSUE 50): a step's start is the start of the first operation
    that ran exactly once in each step; what ran before the cut goes, on
    every chip, asynchronous spans too, and the window and the busy time
    follow. An operation in a loop, whose count follows the data, is no
    mark."""
    step = [("rng", 0.0, 1.0), ("body", 2.0, 1.0), ("body", 4.0, 1.0),
            ("conv", 6.0, 3.0)]
    ops = [(name, start + 10.0 * k, dur)
           for k in range(3) for name, start, dur in step]
    ops.insert(2, ("body", 3.0, 0.5))          # one more trip in step 0
    assert dt.step_starts(ops, 3) == [0.0, 10.0, 20.0]
    late = [(name, start + 0.5, dur) for name, start, dur in ops]
    trace = {"devices": {"0": ops, "1": late},
             "async": {"0": [("copy-start", 5.0, 20.0),
                             ("copy-start.2", 21.0, 2.0)]}}
    cut, opens = dt.since_step(trace, 3, 2)
    assert opens == 20.0
    assert cut["devices"] == {"0": ops[-4:], "1": late[-4:]}
    assert cut["async"] == {"0": [("copy-start.2", 21.0, 2.0)]}
    assert dt.window(cut["devices"]) == (20.0, 29.5)
    assert dt.window(trace["devices"]) == (0.0, 29.5)       # left as it was
    with pytest.raises(RuntimeError, match="exactly once"):
        dt.step_starts(ops, 4)


def test_self_time_and_leaves():
    assert dt.self_times(OPS) == [22.0, 8.0, 20.0, 20.0, 15.0]
    assert [op[0] for op in dt.leaves(OPS)] == [
        "conv", "all-reduce", "fusion", "all-reduce.2"]
    top = dt.top_ops(OPS + [("conv", 200.0, 30.0)], 2)
    assert top == [["conv", 38e-9], ["while", 22e-9]]


def test_collective_time_and_its_exposed_part():
    coll, exposed = dt.collective_time(OPS)
    # 20 inside the while shell, where no other leaf runs, + 15 of which
    # the fusion covers 85..90
    assert coll == 35.0
    assert exposed == 35.0 - 5.0
    assert dt.collective_time([("fusion", 0.0, 5.0)]) == (0.0, 0.0)
    # an asynchronous collective lasts from its start to its done: the op
    # line shows two instants, the async line the span between them
    ops = [("all-reduce-start ar.1 f32[4]", 0.0, 1.0),
           ("fusion fusion.2 f32[4]", 1.0, 6.0),
           ("all-reduce-done ar.1 f32[4]", 7.0, 3.0)]
    spans = [("all-reduce-start ar.1 f32[4]", 0.0, 10.0)]
    assert dt.collective_time(ops, spans) == (10.0, 4.0)


def test_labels_from_hlo_text():
    text = ('%tpu_custom_call.10 = f32[512,256,27,27]{3,2,1,0:T(8,128)} '
            'custom-call(f32[512,256,27,27]{3,2,1,0:T(8,128)} %copy.222), '
            'custom_call_target="tpu_custom_call", operand_layout_constr={}')
    assert dt.label(text) == \
        "pallas-call tpu_custom_call.10 f32[512,256,27,27]"
    assert dt.is_pallas(dt.label(text))
    fusion = ('%fusion.31 = (f32[512,256,13,13]{3,2,1,0:T(8,128)}, '
              'bf16[256]{0:T(256)(128)(2,1)S(1)}) fusion(bf16[2]{0} %b), '
              'kind=kLoop, calls=%fused_computation.57')
    assert dt.label(fusion) == \
        "fusion fusion.31 (f32[512,256,13,13], bf16[256])"
    # an operand that is a kernel's result does not make a kernel
    convert = ('%convert.9 = bf16[8]{0} convert(f32[8]{0} '
               '%tpu_custom_call.10)')
    assert not dt.is_pallas(dt.label(convert))
    assert dt.label("dot_general.13") == "dot_general.13"   # XLA:CPU
    assert dt.is_collective("all-reduce.10")
    assert dt.is_collective(dt.label(
        "%all-reduce-start.2 = f32[4]{0} all-reduce-start(f32[4]{0} %x)"))
    assert not dt.is_collective(dt.label(fusion))


def test_overlap_of_interval_lists():
    assert dt.overlap([(0, 10), (20, 30)], [(5, 25)]) == 10
    assert dt.overlap([(0, 10)], [(10, 20)]) == 0


def test_gaps_are_named_by_the_span_over_their_midpoint():
    spans = [{"name": "train", "start_ns": 0.0, "dur_ns": 1000.0},
             {"name": "prefetch_wait", "start_ns": 55.0, "dur_ns": 10.0}]
    idle = [(50.0, 70.0), (100.0, 104.0), (2000.0, 2001.0)]
    assert dt.attribute_gaps(idle, spans, 5) == [
        ["prefetch_wait", 20e-9], ["train", 4e-9], ["unattributed", 1e-9]]
    assert len(dt.attribute_gaps(idle, spans, 2)) == 2


def test_summary_of_a_window_over_two_chips():
    trace = {"steps": 2, "spans": [], "async": {},
             "devices": {"0": OPS, "1": [("conv", 10.0, 30.0)]}}
    s = dt.summarize(trace)
    assert s["window_s"] == 100e-9
    assert s["busy_s"] == (80.0 + 30.0) / 2 / 1e9
    assert s["breakdown"]["idle_gaps"] == [["unattributed", 20e-9]]
    with pytest.raises(RuntimeError, match="no device operation"):
        dt.summarize({"devices": {}, "spans": [], "steps": 1})


def test_layer_metric_readers_on_the_same_window():
    from layer_metrics import (busy_flops_util, collective_exposed_ms_per_step,
                               collective_ms_per_step, device_idle_share,
                               device_ops_per_step, pallas_share)
    run = {"trace": {"steps": 2, "spans": [], "async": {}, "devices": {
        "0": OPS + [("pallas-call tpu_custom_call.3 f32[8]", 60.0, 5.0)]}},
        "flops_per_image": 1e3, "batch_per_chip": 4,
        "peak_flops_per_s": 1e12}
    assert device_idle_share.reduce(run) == pytest.approx(15.0)
    assert device_ops_per_step.reduce(run) == 5 / 2
    assert pallas_share.reduce(run) == pytest.approx(100 * 5 / 85)
    assert collective_ms_per_step.reduce(run) == pytest.approx(35e-6 / 2)
    assert collective_exposed_ms_per_step.reduce(run) == \
        pytest.approx(30e-6 / 2)
    # 4 images x 1e3 FLOPs per step over 42.5 ns busy per step at 1 TFLOP/s
    assert busy_flops_util.reduce(run) == pytest.approx(
        100 * 4e3 / (42.5e-9 * 1e12))
    empty = dict(run, trace=None)
    assert all(m.reduce(empty) is None for m in (
        busy_flops_util, collective_ms_per_step, device_idle_share,
        device_ops_per_step, pallas_share))


def test_span_readers():
    from layer_metrics import (host_dispatch_ms_per_step, input_wait_share,
                               window_wait_ms_per_step)
    spans = [{"name": "dispatch", "ph": "X", "dur": 2000.0},
             {"name": "dispatch", "ph": "X", "dur": 4000.0},
             {"name": "dispatch_window", "ph": "X", "dur": 9000.0},
             {"name": "hard_sync", "ph": "X", "dur": 1000.0},
             {"name": "prefetch_wait", "ph": "X", "dur": 50000.0}]
    run = {"spans": spans, "steps": 2, "window_s": 1.0}
    assert host_dispatch_ms_per_step.reduce(run) == 3.0
    assert window_wait_ms_per_step.reduce(run) == 5.0
    assert input_wait_share.reduce(run) == pytest.approx(5.0)
    assert input_wait_share.reduce(dict(run, spans=[])) is None


# --------------------------------------------------------------------------- #
# the recorded trace: one step of alexnet.dp4.resident on four v5e chips
# (PR 22, chip call of 2026-09-26), cut from the run's xplane.pb to the op
# lines of the four device planes between 2 ms before the third step's first
# operation and 2 ms before the fourth's, plus the host's alignment event;
# event statistics dropped, names and times untouched.
# --------------------------------------------------------------------------- #

@pytest.fixture(scope="module")
def recorded(tmp_path_factory):
    import gzip
    path = tmp_path_factory.mktemp("trace") / "one_step.xplane.pb"
    with gzip.open(os.path.join(
            HERE, "data", "v5e_dp4_alexnet_one_step.xplane.pb.gz")) as f:
        path.write_bytes(f.read())
    return dict(dt.load(str(path), "tpu", "benchmark_align"), steps=1,
                spans=[])


def sweep_busy(ops):
    """Busy time another way: walk the sorted interval ends with a count of
    the operations open."""
    ends = sorted([(s, 1) for _, s, d in ops] + [(s + d, -1) for _, s, d in ops],
                  key=lambda e: (e[0], -e[1]))     # opens before closes
    busy, open_, since = 0.0, 0, None
    for t, step in ends:
        if open_ == 0 and step == 1:
            since = t
        open_ += step
        if open_ == 0:
            busy += t - since
    return busy


def test_recorded_trace_planes_lines_and_labels(recorded):
    assert sorted(recorded["devices"]) == ["0", "1", "2", "3"]
    assert [len(recorded["devices"][c]) for c in "0123"] == \
        [1168, 1164, 1164, 1164]
    # the profiler wrote asynchronous spans for chip 0 only
    assert {c: len(v) for c, v in recorded["async"].items()} == {"0": 314}
    assert recorded["align_ns"] == 152035464.0
    for ops in recorded["devices"].values():
        kernels = [op[0] for op in ops if dt.is_pallas(op[0])]
        assert len(kernels) == 7            # 3 pool backward, 2 LRN fwd+bwd
        assert all(k.startswith("pallas-call shard_map.") for k in kernels)
        assert sum(dt.is_collective(op[0]) for op in ops) == 47
    assert ("all-reduce all-reduce.456 f32[1000000]"
            in {op[0] for op in recorded["devices"]["0"]})


def test_recorded_trace_busy_idle_and_breakdown(recorded):
    for ops in recorded["devices"].values():
        assert dt.total(dt.union(ops)) == pytest.approx(sweep_busy(ops))
    s = dt.summarize(recorded)
    assert s["window_s"] == pytest.approx(0.490036834, rel=1e-9)
    assert s["busy_s"] == pytest.approx(0.48867718925, rel=1e-9)
    top = s["breakdown"]["device_ops"]
    assert len(top) == 10
    assert top[0] == ["pallas-call shard_map.438 f32[512,256,27,27]",
                      pytest.approx(0.151345237)]
    assert [n.split(" ")[0] for n, _ in top[:4]] == \
        ["pallas-call", "pallas-call", "pallas-call", "reduce-window"]
    # the longest gap is the one between two steps, where the host sits in
    # the runner's window wait
    gaps = dt.gaps(dt.union(recorded["devices"]["0"]))
    longest = max(gaps, key=lambda g: g[1] - g[0])
    assert longest == (1133293669.0, 1134585003.0)
    spans = [{"name": "dispatch_window", "start_ns": 1133000000.0,
              "dur_ns": 2000000.0}]
    assert dt.attribute_gaps(gaps, spans, 5)[0] == \
        ["dispatch_window", pytest.approx(0.001291334)]


def test_recorded_trace_collectives_and_readers(recorded):
    from layer_metrics import (collective_exposed_ms_per_step,
                               collective_ms_per_step, device_idle_share,
                               device_ops_per_step, pallas_share)
    ops = recorded["devices"]["0"]
    total, exposed = dt.collective_time(ops, recorded["async"]["0"])
    # 47 synchronous all-reduces of 4 MB buckets, ~70 us each: nothing else
    # runs on the chip while one does
    assert total == exposed == 3316192.0
    run = {"trace": recorded}
    assert collective_ms_per_step.reduce(run) == pytest.approx(
        (3316192 + 3312743 + 3346238 + 3332552) / 4 / 1e6)
    assert collective_exposed_ms_per_step.reduce(run) == \
        collective_ms_per_step.reduce(run)
    assert device_ops_per_step.reduce(run) == (1161 + 1155 + 1161 + 1156) / 4
    assert device_idle_share.reduce(run) == pytest.approx(
        100 * (1 - 0.48867718925 / 0.490036834))
    assert 73 < pallas_share.reduce(run) < 74
