"""Observability spine tests (ISSUE 7): per-layer attribution, span
timeline, and the live metrics endpoint.

- the trace loader (``jax.profiler.ProfileData``) reads a canned XSpace
  built with the shared varint helpers;
- a canned trace fixture attributes to a stable table: named rows, the
  honest residual row, self-time nesting, the FLOPs join;
- ``jax.named_scope`` layer names survive jit+compile on CPU for LeNet
  forward AND backward (the whole join hangs on this);
- the span recorder's dump is valid Chrome trace-event JSON, the engine's
  --trace_out timeline carries dispatch/hard-sync/snapshot/prefetch
  spans, and a real 2-worker async exchange records push/pull/gate/admit;
- enabling spans costs <2% of a CPU LeNet step, and trace capture stays
  AFTER the timed loop (runtime/attribution.measure_then_trace);
- --metrics_port serves the live registry mid-train; stats.yaml lands
  atomically at every display boundary.
"""

import glob
import json
import os
import threading
import time
import urllib.request

import numpy as np
import pytest

from poseidon_tpu.data.varint import write_varint
from poseidon_tpu.runtime import attribution as A
from poseidon_tpu.runtime.metrics import MetricsServer, StatsRegistry
from poseidon_tpu.runtime.spans import SpanRecorder, recorder as global_rec


# --------------------------------------------------------------------------- #
# canned xplane: a tiny protobuf writer (wire format only, test-local)
# --------------------------------------------------------------------------- #

def _tag(out, fno, wt):
    write_varint(out, (fno << 3) | wt)


def _bytes_field(out, fno, payload: bytes):
    _tag(out, fno, 2)
    write_varint(out, len(payload))
    out.extend(payload)


def _varint_field(out, fno, v: int):
    _tag(out, fno, 0)
    write_varint(out, v)


def _map_entry(fno_key: int, key: int, val: bytes) -> bytes:
    out = bytearray()
    _varint_field(out, 1, key)
    _bytes_field(out, 2, val)
    return bytes(out)


def _canned_xspace() -> bytes:
    """One plane, one line, two events: metadata-named 'dot.7' with an
    hlo_op stat, and 'misc.1' with no stat (residual candidate)."""
    emeta1 = bytearray()
    _varint_field(emeta1, 1, 7)
    _bytes_field(emeta1, 2, b"dot.7")
    emeta2 = bytearray()
    _varint_field(emeta2, 1, 8)
    _bytes_field(emeta2, 2, b"misc.1")
    smeta = bytearray()
    _varint_field(smeta, 1, 3)
    _bytes_field(smeta, 2, b"hlo_op")

    stat = bytearray()                       # XStat: hlo_op = "dot.7"
    _varint_field(stat, 1, 3)
    _bytes_field(stat, 5, b"dot.7")

    ev1 = bytearray()                        # XEvent
    _varint_field(ev1, 1, 7)                 # metadata_id
    _varint_field(ev1, 2, 1_000_000)         # offset_ps
    _varint_field(ev1, 3, 2_500_000)         # duration_ps = 2.5 us
    _bytes_field(ev1, 4, bytes(stat))
    ev2 = bytearray()
    _varint_field(ev2, 1, 8)
    _varint_field(ev2, 2, 5_000_000)
    _varint_field(ev2, 3, 1_000_000)

    line = bytearray()                       # XLine
    _bytes_field(line, 2, b"thread-0")
    _varint_field(line, 3, 123)              # timestamp_ns
    _bytes_field(line, 4, bytes(ev1))
    _bytes_field(line, 4, bytes(ev2))

    plane = bytearray()                      # XPlane
    _bytes_field(plane, 2, b"/host:CPU")
    _bytes_field(plane, 3, bytes(line))
    _bytes_field(plane, 4, _map_entry(4, 7, bytes(emeta1)))
    _bytes_field(plane, 4, _map_entry(4, 8, bytes(emeta2)))
    _bytes_field(plane, 5, _map_entry(5, 3, bytes(smeta)))

    space = bytearray()                      # XSpace
    _bytes_field(space, 1, bytes(plane))
    return bytes(space)


def test_xplane_parser_roundtrips_canned_space():
    """The same canned bytes the wire walker was checked on, through the
    one reader that is left (``jax.profiler.ProfileData``)."""
    e1, e2 = A.trace_events_from_xspace(_canned_xspace())
    assert e1["plane"] == e2["plane"] == "/host:CPU"
    assert e1["line"] == e2["line"] == "thread-0"
    assert e1["name"] == "dot.7"
    assert e1["dur_us"] == pytest.approx(2.5)          # 2_500_000 ps
    # line timestamp 123 ns + offset 1_000_000 ps, on the trace's clock
    assert e1["t0_us"] == pytest.approx((123 + 1000) / 1e3)
    assert e1["stats"] == {"hlo_op": "dot.7"}
    assert e2["name"] == "misc.1"
    assert e2["t0_us"] == pytest.approx((123 + 5000) / 1e3)
    assert e2["stats"] == {}


def test_load_trace_events_reads_canned_xplane(tmp_path):
    run = tmp_path / "plugins" / "profile" / "2026_01_01"
    run.mkdir(parents=True)
    (run / "host.xplane.pb").write_bytes(_canned_xspace())
    evs = A.load_trace_events(str(tmp_path))
    assert len(evs) == 2
    assert evs[0]["name"] == "dot.7"
    assert evs[0]["dur_us"] == pytest.approx(2.5)
    assert evs[0]["stats"]["hlo_op"] == "dot.7"
    assert evs == A.trace_events_from_xspace(_canned_xspace())


# --------------------------------------------------------------------------- #
# the canned-table contract
# --------------------------------------------------------------------------- #

def _ev(name, t0, dur, line="t0", hlo=True, plane="p"):
    return {"name": name, "t0_us": t0, "dur_us": dur, "plane": plane,
            "line": line, "stats": {"hlo_op": name} if hlo else {}}


def test_canned_trace_attributes_to_stable_table():
    scope_map = {"dot.1": ("conv1", "fwd"), "dot.2": ("conv1", "bwd"),
                 "fusion.1": ("ip1", "fwd")}
    events = [
        _ev("dot.1", 0, 100),
        _ev("dot.2", 200, 300),
        _ev("fusion.1", 600, 100),
        _ev("mystery.9", 800, 100),          # -> residual
        {"name": "python_noise", "t0_us": 0, "dur_us": 99999,
         "plane": "p", "line": "t9", "stats": {}},   # excluded entirely
    ]
    out = A.attribute(events, scope_map,
                      cost_table={"conv1": {"flops": 4e9, "bytes": 1e6,
                                            "intensity": 4000.0}},
                      peak_flops=1e12)
    by_name = {r["layer"]: r for r in out["rows"]}
    assert by_name["conv1"]["fwd_ms"] == pytest.approx(0.1)
    assert by_name["conv1"]["bwd_ms"] == pytest.approx(0.3)
    assert by_name["conv1"]["flops"] == 4e9
    assert by_name["conv1"]["mfu"] == pytest.approx(4e9 / 0.4e-3 / 1e12,
                                                    rel=1e-3)
    assert by_name["ip1"]["total_ms"] == pytest.approx(0.1)
    # residual row is honest: named + residual == total
    assert out["residual"]["total_ms"] == pytest.approx(0.1)
    assert out["total_ms"] == pytest.approx(0.6)
    assert out["coverage"] == pytest.approx(5 / 6, abs=1e-3)
    assert out["residual"]["top_ops"][0]["op"] == "mystery.9"
    # rows sorted by total desc -> top sinks
    assert out["top_sinks"][0] == "conv1"


def test_attribute_self_time_never_double_counts_nesting():
    """A while op containing its body ops on the same line is billed only
    its SELF time (flame-graph accounting)."""
    scope_map = {"while.1": ("pool1", "bwd"), "body.1": ("pool1", "bwd"),
                 "other.1": ("conv1", "fwd")}
    events = [
        _ev("while.1", 0, 1000),             # parent
        _ev("body.1", 100, 600),             # nested child
        _ev("other.1", 2000, 500),           # disjoint
    ]
    out = A.attribute(events, scope_map)
    assert out["total_ms"] == pytest.approx(1.5)  # 1000 + 500, not 1600+500
    by_name = {r["layer"]: r for r in out["rows"]}
    assert by_name["pool1"]["bwd_ms"] == pytest.approx(1.0)


def test_attribute_normalizes_decorated_device_event_names():
    """TPU device events sometimes decorate instruction names ('%fusion.3',
    an extra trailing '.<n>'); the join must strip and retry before
    consigning them to the residual row."""
    scope_map = {"fusion.3": ("conv1", "fwd")}
    events = [
        {"name": "%fusion.3", "t0_us": 0, "dur_us": 100,
         "plane": "/device:TPU:0", "line": "XLA Ops", "stats": {}},
        {"name": "fusion.3.7", "t0_us": 200, "dur_us": 100,
         "plane": "/device:TPU:0", "line": "XLA Ops", "stats": {}},
    ]
    out = A.attribute(events, scope_map)
    assert out["coverage"] == pytest.approx(1.0)
    assert out["rows"][0]["layer"] == "conv1"
    assert out["rows"][0]["fwd_ms"] == pytest.approx(0.2)


def test_attribute_ignores_device_module_and_step_lines():
    """TPU device planes carry whole-step 'XLA Modules'/'Steps' lines
    whose events span the entire dispatch; only the op line may feed the
    denominator, or coverage halves on perfectly-named programs."""
    scope_map = {"dot.1": ("conv1", "fwd")}
    events = [
        {"name": "dot.1", "t0_us": 0, "dur_us": 100,
         "plane": "/device:TPU:0", "line": "XLA Ops", "stats": {}},
        {"name": "unknown.9", "t0_us": 200, "dur_us": 50,
         "plane": "/device:TPU:0", "line": "XLA Ops", "stats": {}},
        {"name": "jit_train_step", "t0_us": 0, "dur_us": 10_000,
         "plane": "/device:TPU:0", "line": "XLA Modules", "stats": {}},
        {"name": "step 3", "t0_us": 0, "dur_us": 10_000,
         "plane": "/device:TPU:0", "line": "Steps", "stats": {}},
    ]
    out = A.attribute(events, scope_map)
    assert out["total_ms"] == pytest.approx(0.15)
    assert out["residual"]["total_ms"] == pytest.approx(0.05)
    assert out["coverage"] == pytest.approx(100 / 150, abs=1e-3)


def test_attribute_strips_tracer_overhead_per_event():
    scope_map = {"a.1": ("l1", "fwd"), "b.1": ("l2", "fwd")}
    events = [_ev("a.1", 0, 100), _ev("b.1", 200, 100)]
    out = A.attribute(events, scope_map, tracer_overhead_ms=0.1)
    # 0.1 ms across 2 events = 50 us each
    by_name = {r["layer"]: r for r in out["rows"]}
    assert by_name["l1"]["total_ms"] == pytest.approx(0.05)
    assert out["tracer_overhead_ms_stripped"] == pytest.approx(0.1)


def test_scope_of_peels_autodiff_wrappers_and_slashed_names():
    layers = {"conv1", "inception_3a/1x1"}
    assert A.scope_of("jit(f)/jit(main)/jvp(conv1)/dot", layers) == \
        ("conv1", "fwd")
    assert A.scope_of("jit(f)/transpose(jvp(conv1))/dot", layers) == \
        ("conv1", "bwd")
    assert A.scope_of("jit(f)/jvp(inception_3a)/1x1/conv", layers) == \
        ("inception_3a/1x1", "fwd")
    # what jax ACTUALLY emits for a slashed layer name: the wrapper opens
    # and closes in DIFFERENT '/'-components — per-component peeling used
    # to mangle this into 'jvp(inception_3a' + '1x1)' and every wrapped
    # GoogLeNet op fell into the residual row
    assert A.scope_of("jit(f)/jvp(inception_3a/1x1)/conv", layers) == \
        ("inception_3a/1x1", "fwd")
    assert A.scope_of("jit(f)/transpose(jvp(inception_3a/1x1))/conv",
                      layers) == ("inception_3a/1x1", "bwd")
    assert A.scope_of("jit(f)/arena_pack/concatenate", layers,
                      {"arena_pack"}) == ("arena_pack", "misc")
    assert A.scope_of("jit(f)/unrelated/op", layers) == (None, None)
    # a call frame whose function name collides with a layer must still
    # NOT attribute (jit(conv1) is the traced function, not the layer)
    assert A.scope_of("jit(conv1)/add", layers) == (None, None)


# --------------------------------------------------------------------------- #
# named scopes survive jit (LeNet fwd + bwd on CPU)
# --------------------------------------------------------------------------- #

def _lenet_net(batch=4):
    from poseidon_tpu.core.net import Net
    from poseidon_tpu.models import zoo
    return Net(zoo.lenet(with_accuracy=False), "TRAIN",
               source_shapes=zoo.lenet_shapes(batch))


def test_named_scopes_survive_jit_lenet_fwd_bwd():
    import jax

    net = _lenet_net()
    params = net.init(jax.random.PRNGKey(0))
    inputs = {"data": np.zeros((4, 1, 28, 28), np.float32),
              "label": np.zeros((4,), np.int32)}

    def loss(p):
        return net.apply(p, inputs, train=True,
                         rng=jax.random.PRNGKey(1)).loss

    txt = jax.jit(jax.grad(loss)).lower(params).compile().as_text()
    smap = A.hlo_scope_map(txt, {layer.name for layer in net.layers})
    phases = {}
    for scope, phase in smap.values():
        phases.setdefault(scope, set()).add(phase)
    # every parameterized layer appears, forward AND backward
    for lname in ("conv1", "conv2", "ip1", "ip2"):
        assert lname in phases, f"{lname} missing from compiled metadata"
        assert "fwd" in phases[lname], f"{lname}: no forward ops"
        assert "bwd" in phases[lname], f"{lname}: no backward ops"


def test_real_cpu_trace_attributes_lenet(tmp_path):
    """End-to-end smoke on the REAL profiler: one traced LeNet grad step
    parses into a table whose named rows carry most of the op time."""
    import jax

    net = _lenet_net(8)
    params = net.init(jax.random.PRNGKey(0))
    inputs = {"data": np.random.RandomState(0).randn(
        8, 1, 28, 28).astype(np.float32),
        "label": np.zeros((8,), np.int32)}

    def loss(p):
        return net.apply(p, inputs, train=True,
                         rng=jax.random.PRNGKey(1)).loss

    compiled = jax.jit(jax.grad(loss)).lower(params).compile()

    def run():
        jax.block_until_ready(
            jax.tree_util.tree_leaves(compiled(params))[0])

    timing = A.measure_then_trace(run, str(tmp_path), iters=2)
    events = A.load_trace_events(str(tmp_path))
    if not events:
        pytest.skip("profiler produced no parseable trace on this box")
    smap = A.hlo_scope_map(compiled.as_text(),
                           {layer.name for layer in net.layers})
    out = A.attribute(
        events, smap, cost_table=net.cost_table(),
        tracer_overhead_ms=max(
            timing["traced_step_ms"] - timing["step_ms"], 0.0))
    assert out["total_ms"] > 0
    assert out["coverage"] > 0.5, (out["coverage"],
                                   out["residual"]["top_ops"])
    named = {r["layer"] for r in out["rows"]}
    assert "conv2" in named or "ip1" in named


def test_layer_cost_table_conv_and_fc_flops():
    net = _lenet_net(4)
    table = net.cost_table()
    # conv1: 20 filters of 1x5x5 over 24x24 outputs, batch 4, x3 fwd+bwd
    assert table["conv1"]["flops"] == pytest.approx(
        3 * 2 * 4 * 24 * 24 * 20 * 25)
    # ip1: 500 x (50*4*4) weights, batch 4
    assert table["ip1"]["flops"] == pytest.approx(
        3 * 2 * 4 * 500 * 50 * 4 * 4)
    assert table["conv1"]["intensity"] > 1.0


# --------------------------------------------------------------------------- #
# spans: Chrome JSON validity, overhead, capture-after-timing
# --------------------------------------------------------------------------- #

def test_span_dump_is_valid_chrome_trace_json(tmp_path):
    rec = SpanRecorder()
    rec.enable()
    with rec.span("dispatch", "step", {"iter": 3}):
        with rec.span("inner", "step"):
            pass
    rec.instant("marker", "sync")
    rec.disable()       # takes its collector hook out of gc.callbacks
    path = rec.dump(str(tmp_path / "spans.json"))
    with open(path) as f:
        doc = json.load(f)
    evs = doc["traceEvents"]
    assert {e["name"] for e in evs} - {"gc_pause"} == {"dispatch", "inner",
                                                       "marker"}
    for e in evs:
        assert e["ph"] in ("X", "i")
        assert isinstance(e["ts"], (int, float))
        assert "pid" in e and "tid" in e
        if e["ph"] == "X":
            assert e["dur"] >= 0
    by = {e["name"]: e for e in evs}
    assert by["dispatch"]["args"] == {"iter": 3}
    # no tmp litter left behind (atomic rename)
    assert not glob.glob(str(tmp_path / "*.tmp.*"))


def test_span_overhead_under_two_percent_of_lenet_step():
    """The <2% guard: per-span cost (enabled) x spans-per-engine-step must
    stay under 2% of a real CPU LeNet step, and the DISABLED path must be
    sub-microsecond (it lives permanently in the hot loop)."""
    import jax

    net = _lenet_net(8)
    params = net.init(jax.random.PRNGKey(0))
    inputs = {"data": np.zeros((8, 1, 28, 28), np.float32),
              "label": np.zeros((8,), np.int32)}

    def loss(p):
        return net.apply(p, inputs, train=True,
                         rng=jax.random.PRNGKey(1)).loss

    compiled = jax.jit(jax.grad(loss)).lower(params).compile()
    jax.block_until_ready(jax.tree_util.tree_leaves(compiled(params))[0])
    t0 = time.perf_counter()
    for _ in range(5):
        out = compiled(params)
    jax.block_until_ready(jax.tree_util.tree_leaves(out)[0])
    step_s = (time.perf_counter() - t0) / 5

    rec = SpanRecorder()
    n = 2000

    def span_cost():
        t0 = time.perf_counter()
        for i in range(n):
            with rec.span("dispatch", "step"):
                pass
        return (time.perf_counter() - t0) / n

    disabled = min(span_cost() for _ in range(3))
    rec.end_startup()   # past the start-up phase the heartbeat runs too
    rec.enable()        # with jax imported: the TraceAnnotation is entered
    try:
        assert rec._heartbeat._thread.is_alive()
        enabled = min(span_cost() for _ in range(3))
    finally:
        rec.disable()
    # the engine hot loop wears at most ~8 spans per step (prefetch_wait,
    # dispatch, dispatch_window, boundary syncs, async push/pull/gate)
    assert enabled * 8 < 0.02 * step_s, (
        f"span overhead {enabled * 8 * 1e6:.1f}us/step vs "
        f"2% of step = {0.02 * step_s * 1e6:.1f}us")
    assert disabled < 5e-6, f"disabled span path costs {disabled * 1e6:.2f}us"


def test_trace_capture_stays_after_timing(tmp_path, monkeypatch):
    """measure_then_trace runs EVERY timed step before the profiler ever
    starts — attribution can never contaminate the timed loop."""
    import jax

    order = []
    monkeypatch.setattr(jax.profiler, "start_trace",
                        lambda *a, **k: order.append("trace_start"))
    monkeypatch.setattr(jax.profiler, "stop_trace",
                        lambda: order.append("trace_stop"))
    timing = A.measure_then_trace(lambda: order.append("step"),
                                  str(tmp_path), iters=3)
    assert order == ["step"] * 3 + ["trace_start", "step", "trace_stop"]
    assert timing["step_ms"] >= 0


# --------------------------------------------------------------------------- #
# engine wiring: --trace_out timeline + stats.yaml at display boundaries
# --------------------------------------------------------------------------- #

SMALLNET = """
name: "ObsNet"
layers {
  name: "src" type: MEMORY_DATA top: "data" top: "label"
  memory_data_param { batch_size: 8 channels: 1 height: 12 width: 12 }
}
layers {
  name: "conv1" type: CONVOLUTION bottom: "data" top: "conv1"
  convolution_param { num_output: 8 kernel_size: 3
    weight_filler { type: "xavier" } bias_filler { type: "constant" } }
}
layers { name: "relu1" type: RELU bottom: "conv1" top: "conv1" }
layers {
  name: "ip1" type: INNER_PRODUCT bottom: "conv1" top: "ip1"
  inner_product_param { num_output: 5
    weight_filler { type: "xavier" } bias_filler { type: "constant" } }
}
layers { name: "loss" type: SOFTMAX_LOSS bottom: "ip1" bottom: "label"
  top: "loss" }
"""


def _solver(max_iter=8, display=2, **kw):
    from poseidon_tpu.proto.messages import (SolverParameter,
                                             load_net_from_string)
    return SolverParameter(train_net_param=load_net_from_string(SMALLNET),
                           base_lr=0.01, lr_policy="fixed", momentum=0.9,
                           display=display, max_iter=max_iter,
                           random_seed=3, **kw)


def _md(n=64):
    rs = np.random.RandomState(0)
    return {"data": rs.randn(n, 1, 12, 12).astype(np.float32),
            "label": rs.randint(0, 5, n)}


@pytest.fixture
def clean_recorder():
    global_rec.clear()
    yield global_rec
    global_rec.disable()
    global_rec.clear()


def test_engine_trace_out_records_hot_path_spans(tmp_path, clean_recorder):
    from poseidon_tpu.runtime.engine import Engine

    eng = Engine(_solver(max_iter=6, display=2,
                         snapshot=3, snapshot_prefix="snap/obs"),
                 memory_data=_md(), output_dir=str(tmp_path),
                 trace_out="spans.json")
    try:
        eng.train()
    finally:
        eng.close()
    path = tmp_path / "spans.json"
    assert path.exists()
    with open(path) as f:
        doc = json.load(f)
    names = {e["name"] for e in doc["traceEvents"]}
    for want in ("prefetch_wait", "dispatch", "dispatch_window",
                 "hard_sync", "snapshot"):
        assert want in names, f"{want} span missing from {sorted(names)}"
    # boundary args distinguish the sync kinds; a display boundary is
    # none of them (it is shown when its last step drains, with the next
    # steps already dispatched) and leaves its telemetry_dump instead
    bounds = {e["args"]["boundary"] for e in doc["traceEvents"]
              if e["name"] == "hard_sync"}
    assert bounds == {"snapshot", "final"}
    dumps = sorted(e["args"]["iter"] for e in doc["traceEvents"]
                   if e["name"] == "telemetry_dump")
    assert dumps == [2, 4, 6]
    # stats.yaml landed too (display boundary), atomically
    assert (tmp_path / "stats.yaml").exists()
    assert not glob.glob(str(tmp_path / "stats.yaml.tmp.*"))


def test_stats_yaml_written_at_display_boundary_not_only_exit(tmp_path):
    """The crash-safety satellite: stats.yaml exists after the FIRST
    display boundary even though the run is still mid-flight (end-of-run
    artifact writing is disabled to prove it)."""
    from poseidon_tpu.runtime.engine import Engine

    eng = Engine(_solver(max_iter=4, display=2), memory_data=_md(),
                 output_dir=str(tmp_path))
    eng._write_artifacts = lambda: None          # no exit-time write
    try:
        eng.train()
    finally:
        eng.close()
    stats = (tmp_path / "stats.yaml").read_text()
    assert "counters:" in stats
    assert "train_iters" in stats
    assert "gauges:" in stats and "iteration" in stats
    assert not glob.glob(str(tmp_path / "stats.yaml.tmp.*"))


# --------------------------------------------------------------------------- #
# async tier: push/pull/gate/admit spans from a real 2-worker exchange
# --------------------------------------------------------------------------- #

def test_async_two_worker_run_records_push_pull_gate_admit_spans(
        tmp_path, clean_recorder):
    from poseidon_tpu.parallel.async_ssp import AsyncSSPClient, ParamService

    clean_recorder.enable()
    params = {"fc": {"w": np.zeros((2, 2), np.float32)}}
    svc = ParamService(params, n_workers=2, liveness_timeout_s=0.0)
    clients = []
    try:
        for w in range(2):
            cli = AsyncSSPClient(w, ("127.0.0.1", svc.port), staleness=0,
                                 n_workers=2, heartbeat_s=0.1)
            cli.join()
            clients.append(cli)

        def worker(cli):
            for _ in range(3):
                clock = cli.push(
                    {"fc": {"w": np.ones((2, 2), np.float32)}})
                cli.refresh()
                cli.gate(clock + 1, timeout_s=20.0)

        threads = [threading.Thread(target=worker, args=(c,))
                   for c in clients]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=30.0)
        for c in clients:
            c.mark_done()
    finally:
        for c in clients:
            c.close()
        svc.close()
    path = clean_recorder.dump(str(tmp_path / "async_spans.json"))
    with open(path) as f:
        doc = json.load(f)
    by_cat = {}
    for e in doc["traceEvents"]:
        by_cat.setdefault(e["cat"], set()).add(e["name"])
    assert "async" in by_cat
    for want in ("async_push", "async_pull", "async_admit"):
        assert want in by_cat["async"], by_cat["async"]
    # both workers pushed under span cover
    pushers = {e["args"]["worker"] for e in doc["traceEvents"]
               if e["name"] == "async_push"}
    assert pushers == {0, 1}


# --------------------------------------------------------------------------- #
# metrics endpoint
# --------------------------------------------------------------------------- #

def _get(url: str) -> str:
    with urllib.request.urlopen(url, timeout=5) as r:
        return r.read().decode()


def test_metrics_server_serves_registry_contents():
    reg = StatsRegistry()
    reg.add("train_iters", 42)
    reg.add_time("train_step", 1.25)
    reg.set_gauge("iteration", 42)
    reg.set_section("comm", {"summary": {"total_bytes_per_step": 128}})
    srv = MetricsServer(reg, port=0)
    try:
        body = _get(f"http://127.0.0.1:{srv.port}/")
        assert "train_iters=42" in body
        assert "iteration=42" in body
        assert "train_step_sec=1.25" in body
        assert "comm.summary.total_bytes_per_step=128" in body
        # live: a later add is visible on the next poll
        reg.add("train_iters", 1)
        assert "train_iters=43" in _get(f"http://127.0.0.1:{srv.port}/")
    finally:
        srv.close()


def test_metrics_port_serves_live_counters_mid_train(tmp_path):
    """The acceptance pin: curl the endpoint WHILE train() is running and
    see counters advancing."""
    from poseidon_tpu.runtime.engine import Engine

    eng = Engine(_solver(max_iter=400, display=2), memory_data=_md(),
                 output_dir=str(tmp_path), metrics_port=0)
    assert eng.metrics_port and eng.metrics_port > 0
    url = f"http://127.0.0.1:{eng.metrics_port}/"
    seen_mid_train = []
    t = threading.Thread(target=lambda: eng.train(), daemon=True)
    t.start()
    try:
        deadline = time.time() + 60.0
        while time.time() < deadline:
            body = _get(url)
            for ln in body.splitlines():
                if ln.startswith("train_iters="):
                    v = float(ln.split("=")[1])
                    if 0 < v < 400:     # strictly MID-train
                        seen_mid_train.append(v)
            if seen_mid_train:
                break
            time.sleep(0.02)
        assert seen_mid_train, "endpoint never showed mid-train counters"
        body = _get(url)
        assert "input_stall_sec=" in body
    finally:
        t.join(timeout=120.0)
        eng.close()


# --------------------------------------------------------------------------- #
# serving stats growth (executor bucket fill + reloader counters)
# --------------------------------------------------------------------------- #

@pytest.mark.serving
def test_executor_bucket_fill_and_stats_op_growth():
    import jax
    from poseidon_tpu.core.net import Net
    from poseidon_tpu.proto.messages import load_net_from_string
    from poseidon_tpu.serving.executor import BucketedExecutor
    from poseidon_tpu.serving.server import InferenceServer

    deploy = """
name: "obs_deploy"
input: "data"
input_dim: 1 input_dim: 1 input_dim: 8 input_dim: 8
layers { name: "ip" type: INNER_PRODUCT bottom: "data" top: "ip"
  inner_product_param { num_output: 4 weight_filler { type: "xavier" } } }
"""
    net = Net(load_net_from_string(deploy), "TEST")
    ex = BucketedExecutor(net, net.init(jax.random.PRNGKey(0)),
                          buckets=(2, 4))
    ex.infer({"data": np.zeros((1, 1, 8, 8), np.float32)})   # 1/2 fill
    ex.infer({"data": np.zeros((4, 1, 8, 8), np.float32)})   # 4/4 fill
    fill = ex.bucket_fill()
    assert fill[2] == pytest.approx(0.5)
    assert fill[4] == pytest.approx(1.0)
    srv = InferenceServer(ex)
    try:
        snap = srv.stats_snapshot()
        assert snap["executor_bucket_fill"][2] == pytest.approx(0.5)
        assert snap["reloader"] is None      # none attached -> explicit
    finally:
        srv.shutdown()
