"""One named timeline for a training step (ISSUE 23).

- device half: the Engine publishes its compiled step's instruction ->
  (layer, pass) map as stats section ``step_scopes`` for every source of
  the executable; the build is one pass; it changes no arithmetic;
- host half: every thread that can hold a step up records spans
  (``runtime/spans.py`` lists them), one step's spans join on
  (``batch``, ``iter``), and under the profiler the same spans lie in the
  xplane's host plane, per thread, with ``train`` steps numbered;
- what a disabled recorder costs: nothing allocated, no collector hook;
- ``--trace_out`` no longer re-serializes the span buffer inside a display
  interval;
- set-up (ISSUE 34): until the first step is done the recorder keeps the
  spans of category ``startup`` whatever its state, the Engine summarises
  them once into stats section ``startup``, and ``compiled_step.phases`` /
  ``.seconds`` and the timer ``engine_build`` are read off those spans.
"""

import gc
import glob
import os
import subprocess
import sys
import threading
import time

import numpy as np
import pytest

from poseidon_tpu.runtime import attribution as A
from poseidon_tpu.runtime import spans as spans_mod
from poseidon_tpu.runtime.spans import SpanRecorder, recorder as global_rec

NET = """
name: "TimelineNet"
layers {
  name: "src" type: MEMORY_DATA top: "data" top: "label"
  memory_data_param { batch_size: 8 channels: 4 height: 12 width: 12 }
}
layers {
  name: "conv1" type: CONVOLUTION bottom: "data" top: "conv1"
  convolution_param { num_output: 8 kernel_size: 3
    weight_filler { type: "xavier" } bias_filler { type: "constant" } }
}
layers { name: "relu1" type: RELU bottom: "conv1" top: "conv1" }
layers { name: "norm1" type: LRN bottom: "conv1" top: "norm1"
  lrn_param { local_size: 3 alpha: 0.0001 beta: 0.75 } }
layers { name: "pool1" type: POOLING bottom: "norm1" top: "pool1"
  pooling_param { pool: MAX kernel_size: 2 stride: 2 } }
layers {
  name: "ip1" type: INNER_PRODUCT bottom: "pool1" top: "ip1"
  inner_product_param { num_output: 5
    weight_filler { type: "xavier" } bias_filler { type: "constant" } }
}
layers { name: "loss" type: SOFTMAX_LOSS bottom: "ip1" bottom: "label"
  top: "loss" }
"""


def _solver(max_iter=2, display=2, **kw):
    from poseidon_tpu.proto.messages import (SolverParameter,
                                             load_net_from_string)
    return SolverParameter(train_net_param=load_net_from_string(NET),
                           base_lr=0.01, lr_policy="fixed", momentum=0.9,
                           display=display, max_iter=max_iter,
                           random_seed=3, **kw)


def _md(n=64):
    rs = np.random.RandomState(0)
    return {"data": rs.randn(n, 4, 12, 12).astype(np.float32),
            "label": rs.randint(0, 5, n)}


def _engine(out, **kw):
    from poseidon_tpu.runtime.engine import Engine
    solver = {k: kw.pop(k) for k in ("max_iter", "display", "snapshot",
                                     "snapshot_prefix") if k in kw}
    return Engine(_solver(**solver), memory_data=_md(), output_dir=str(out),
                  **kw)


@pytest.fixture
def clean_recorder():
    global_rec.disable()
    global_rec.clear()
    if global_rec.startup_open:     # whichever test of the process is first
        global_rec.end_startup()
    yield global_rec
    global_rec.disable()
    global_rec.clear()


# --------------------------------------------------------------------------- #
# A. the step's op -> (layer, pass) map
# --------------------------------------------------------------------------- #

def _train(out, **kw):
    eng = _engine(out, **kw)
    try:
        last = eng.train()
    finally:
        eng.close()
    return eng, last


def test_engine_publishes_step_scopes_for_every_source(tmp_path,
                                                       jax_cache_env):
    """compiled, loaded and xla_cache steps all publish the same map: the
    text of the executable that runs carries the scopes whichever way the
    Engine came by it."""
    import shutil

    from poseidon_tpu.runtime.compile_cache import enable_compile_cache

    cache = enable_compile_cache()
    seen = {}
    for run in ("compiled", "loaded", "xla_cache"):
        if run == "xla_cache":      # XLA cache warm, aot/ empty
            shutil.rmtree(os.path.join(cache, "aot"))
        eng, last = _train(tmp_path / run)
        step = eng.stats.sections["compiled_step"]
        assert step["source"] == run and "error" not in step
        seen[run] = (eng.stats.snapshot()["sections"]["step_scopes"],
                     last["loss"])
    scopes, loss = seen["compiled"]
    assert scopes["mapped"] == len(scopes["ops"]) > 0
    assert scopes["instructions"] >= scopes["mapped"]
    assert scopes["types"]["pool1"] == "POOLING"
    assert scopes["types"]["norm1"] == "LRN"
    assert scopes["types"]["optimizer_update"] == "update"
    assert {v.split("|")[1] for v in scopes["ops"].values()} == {
        "fwd", "bwd", "misc"}
    for run in ("loaded", "xla_cache"):
        assert seen[run][0]["ops"] == scopes["ops"], run
        assert seen[run][0]["types"] == scopes["types"], run
        assert seen[run][1] == loss          # and the same arithmetic


@pytest.mark.parametrize("arm", ["default_routes", "tpu_routes",
                                 "tpu_routes_sas"])
def test_pool_and_lrn_instructions_map_to_their_layer_and_pass(
        arm, monkeypatch):
    """Every instruction whose own metadata names the pool or the LRN
    layer lands on that layer with the pass its path says — for the arms
    the CPU routes to, and for the TPU's (the Pallas max-pool backward
    and LRN kernels, interpreted here and custom calls on the chip: same
    scopes, same join — `pool_bwd_ms_per_step` reads the kernel where it
    read the scatter; select-and-scatter, the A/B arm and AVE pooling's)."""
    import re

    import jax

    from poseidon_tpu.core.net import Net
    from poseidon_tpu.proto.messages import load_net_from_string

    pool_arm = {"tpu_routes": "pallas", "tpu_routes_sas": "sas"}.get(arm)
    if pool_arm:
        monkeypatch.setenv("POSEIDON_POOL_BWD", pool_arm)
        monkeypatch.setenv("POSEIDON_PALLAS_LRN", "1")
    net = Net(load_net_from_string(NET), "TRAIN",
              source_shapes={"data": (4, 4, 12, 12), "label": (4,)})
    if pool_arm:
        assert net.kernel_routes["norm1"] == (
            "lrn=pallas (channel-minor HWxNxC, block 112x4x8)")
        assert net.kernel_routes["pool1"].split(" ")[0] == (
            f"pool_bwd={pool_arm}")
        assert set(net.kernel_routes) == {"norm1", "pool1"}
    params = net.init(jax.random.PRNGKey(0))
    inputs = {"data": np.ones((4, 4, 12, 12), np.float32),
              "label": np.zeros((4,), np.int32)}

    def loss(p):
        return net.apply(p, inputs, train=True,
                         rng=jax.random.PRNGKey(1)).loss

    text = jax.jit(jax.grad(loss)).lower(params).compile().as_text()
    doc = A.step_scopes(text, net)
    named = 0
    for inst, op_name in re.findall(
            r'%([\w.\-]+) = [^\n]*op_name="([^"]*)"', text):
        for layer in ("pool1", "norm1"):
            if f"({layer})" in op_name and inst in doc["ops"]:
                want = "bwd" if "transpose(" in op_name else "fwd"
                assert doc["ops"][inst] == f"{layer}|{want}", (inst, op_name)
                named += 1
    assert named >= 4       # both layers, both passes
    passes = {(v.split("|")[0], v.split("|")[1])
              for v in doc["ops"].values()}
    assert {("pool1", "fwd"), ("pool1", "bwd"), ("norm1", "fwd"),
            ("norm1", "bwd")} <= passes


def test_scope_map_build_is_one_pass():
    """4,000 instructions x 150 slashed layer names in well under the
    0.5 s the Engine may spend on it at step resolve (the old build
    re-sorted every layer name for every instruction)."""
    layers = [f"inception_{i}/branch_{j}" for i in range(15)
              for j in range(10)]
    lines = ["HloModule synthetic", "", "ENTRY %main (p: f32[8]) -> f32[8] {",
             "  %p = f32[8]{0} parameter(0)"]
    prev = "p"
    for n in range(4000):
        layer = layers[n % len(layers)]
        path = (f"jit(step)/transpose(jvp({layer}))/mul" if n % 2
                else f"jit(step)/jvp({layer})/add")
        lines.append(f'  %op.{n} = f32[8]{{0}} multiply(%{prev}, %p), '
                     f'metadata={{op_name="{path}"}}')
        prev = f"op.{n}"
    lines += [f"  ROOT %out = f32[8]{{0}} copy(%{prev})", "}"]
    text = "\n".join(lines)
    t0 = time.perf_counter()
    smap = A.hlo_scope_map(text, layers, A.STEP_EXTRA_SCOPES)
    took = time.perf_counter() - t0
    assert took < 0.5, f"{took:.3f} s"
    assert smap["op.0"] == (layers[0], "fwd")
    assert smap["op.3999"] == (layers[3999 % 150], "bwd")
    assert len(smap) >= 4000


# a hand-made optimized module: %w is copied on its way in (2 MB), %h on its
# way out (4 MB); %small is under the floor, %mid is neither a parameter's
# copy nor returned, and the copy inside the fusion body is no instruction
# that runs on its own
_RELAYOUT_TEXT = """HloModule jit_step, is_scheduled=true

%fused_computation (param_0: f32[512,1024]) -> f32[512,1024] {
  %param_0 = f32[512,1024]{1,0} parameter(0)
  ROOT %copy.9 = f32[512,1024]{0,1} copy(%param_0)
}

ENTRY %main (w: f32[512,1024], h: f32[1024,1024], b: f32[1024]) -> (f32[512,1024], f32[1024,1024], f32[1024]) {
  %w = f32[512,1024]{1,0:T(8,128)} parameter(0)
  %h = f32[1024,1024]{1,0:T(8,128)} parameter(1)
  %b = f32[1024]{0} parameter(2)
  %copy.1 = f32[512,1024]{0,1:T(8,128)} copy(%w), metadata={op_name="params"}
  %fusion.1 = f32[512,1024]{0,1:T(8,128)} fusion(%copy.1), kind=kLoop, calls=%fused_computation
  %copy.2 = f32[512,1024]{1,0:T(8,128)} copy(%fusion.1)
  %add.1 = f32[1024,1024]{0,1:T(8,128)} add(%h, %h)
  %copy.3 = f32[1024,1024]{0,1:T(8,128)} copy(%add.1)
  %copy.4 = f32[1024,1024]{1,0:T(8,128)} copy(%copy.3)
  %copy.5 = f32[1024]{0} copy(%b)
  %copy-start.1 = (f32[1024]{0}, f32[1024]{0:S(1)}, u32[]) copy-start(%b)
  ROOT %tuple.1 = (f32[512,1024]{1,0}, f32[1024,1024]{1,0}, f32[1024]{0}) tuple(%copy.2, %copy.4, %copy.5)
}
"""


@pytest.mark.parametrize("floor, want", [
    # copy.1 (reads %w), copy.2 and copy.4 (returned): 2.1 + 2.1 + 4.2 MB;
    # copy.3 feeds neither, copy.5 is 4 KB, copy.9 is inside a fusion
    (1 << 20, {"copies": 3, "mb": 8.4}),
    (3 << 20, {"copies": 1, "mb": 4.2}),
    (1, {"copies": 4, "mb": 8.4}),
    (1 << 30, {"copies": 0, "mb": 0.0}),
])
def test_param_relayouts_counts_copies_at_the_steps_boundary(floor, want):
    assert A.param_relayouts(_RELAYOUT_TEXT, floor) == want


def test_param_relayouts_of_one_parameter_copy():
    """One copy of one entry parameter and nothing else: one copy, its
    megabytes (the result's: what the copy writes)."""
    text = """ENTRY %main (w: bf16[64,2048,1024]) -> bf16[64,2048,1024] {
  %w = bf16[64,2048,1024]{2,1,0:T(8,128)(2,1)} parameter(0)
  %copy.7 = bf16[64,2048,1024]{1,2,0:T(8,128)(2,1)} copy(%w), sharding={replicated}
  ROOT %negate.1 = bf16[64,2048,1024]{1,2,0:T(8,128)(2,1)} negate(%copy.7)
}
"""
    assert A.param_relayouts(text) == {"copies": 1, "mb": 268.4}


def test_engine_publishes_param_relayouts_of_the_step_that_runs(
        tmp_path, jax_cache_env):
    """The fact sits beside ``update_route`` and ``grad_buckets`` in stats
    section ``compiled_step`` and in ``stats.yaml``; a two-layer net's step
    copies no parameter of a megabyte (it has none)."""
    from poseidon_tpu.runtime.compile_cache import enable_compile_cache
    from poseidon_tpu.runtime.metrics import read_stats_yaml

    enable_compile_cache()
    eng, _ = _train(tmp_path)
    step = eng.stats.sections["compiled_step"]
    assert step["source"] == "compiled" and "error" not in step
    assert step["param_relayouts"] == {"copies": 0, "mb": 0.0}
    assert step["update_route"] == "leaf" and "grad_buckets" in step
    doc = read_stats_yaml(str(tmp_path / "stats.yaml"))
    # read back as written: every leaf a string
    assert doc["compiled_step"]["param_relayouts"] == {"copies": "0",
                                                       "mb": "0.0"}


def test_map_changes_no_arithmetic_and_jit_path_says_why(tmp_path,
                                                         jax_cache_env):
    """The step whose text is read and mapped computes bitwise the loss of
    the step nobody looks at; the jit path (no executable to read)
    publishes an empty map and the reason."""
    from poseidon_tpu.runtime.compile_cache import enable_compile_cache

    plain, last_plain = _train(tmp_path / "jit", max_iter=3)
    empty = plain.stats.snapshot()["sections"]["step_scopes"]
    assert empty["ops"] == {} and empty["mapped"] == 0
    assert "jit" in empty["why"]

    enable_compile_cache()
    mapped, last_mapped = _train(tmp_path / "aot", max_iter=3)
    assert mapped.stats.snapshot()["sections"]["step_scopes"]["mapped"] > 0
    assert last_mapped["loss"] == last_plain["loss"]        # bitwise


def test_compiled_step_phases_sum_and_maps_stay_out_of_stats_yaml(
        tmp_path, jax_cache_env):
    from poseidon_tpu.runtime.compile_cache import enable_compile_cache
    from poseidon_tpu.runtime.metrics import read_stats_yaml

    enable_compile_cache()
    eng, _ = _train(tmp_path)
    step = eng.stats.sections["compiled_step"]
    assert set(step["phases"]) == {"load_s", "trace_lower_s", "compile_s",
                                   "store_s", "text_s", "scope_map_s"}
    assert sum(step["phases"].values()) == pytest.approx(step["seconds"],
                                                         abs=0.01)
    assert step["phases"]["compile_s"] > 0
    assert step["phases"]["scope_map_s"] < 0.5
    assert eng.stats.timers["engine_build"] > 0
    # the file rewritten at every display boundary carries the counts, not
    # the maps; the snapshot a program reads carries both
    doc = read_stats_yaml(str(tmp_path / "stats.yaml"))
    assert set(doc["step_scopes"]) == {"instructions", "mapped"}
    assert int(doc["step_scopes"]["mapped"]) > 0
    assert "ops" in eng.stats.snapshot()["sections"]["step_scopes"]
    assert "step_scopes.mapped=" in eng.stats.render_text()
    assert "step_scopes.ops" not in eng.stats.render_text()


@pytest.fixture
def location_flags():
    import jax
    saved = (jax.config.jax_include_full_tracebacks_in_locations,
             jax.config.jax_traceback_in_locations_limit)
    yield
    jax.config.update("jax_include_full_tracebacks_in_locations", saved[0])
    jax.config.update("jax_traceback_in_locations_limit", saved[1])
    jax.clear_caches()


def _lower_lrn_for_tpu(monkeypatch):
    """The LRN kernels, forward and backward, lowered for the TPU from
    inside a named scope (the Mosaic modules are built here, on the CPU;
    nothing compiles)."""
    import jax
    import jax.numpy as jnp

    from poseidon_tpu.ops.pallas_kernels import lrn_fused

    monkeypatch.setenv("POSEIDON_FORCE_PALLAS", "1")

    def step(x):
        with jax.named_scope("norm1"):
            return lrn_fused(x, 5, 1e-4, 0.75).sum()

    jax.clear_caches()
    x = jax.ShapeDtypeStruct((8, 96, 55, 55), jnp.float32)
    return jax.jit(jax.value_and_grad(step)).trace(x).lower(
        lowering_platforms=("tpu",))


def test_cache_setting_keeps_kernel_payload_stable_and_scopes_in_op_names(
        tmp_path, jax_cache_env, location_flags, monkeypatch):
    """What ``enable_compile_cache`` sets about source locations has two
    jobs: a Pallas kernel's serialized payload must not depend on the
    caller's stack (PR 21: a warm process missed the cache on the v5e),
    and the named scopes must survive into ``op_name`` (PR 21's first
    answer, ``jax_include_full_tracebacks_in_locations=False``, cut them
    off: the step's map would be empty on the chip)."""
    from poseidon_tpu.runtime.compile_cache import enable_compile_cache

    enable_compile_cache()

    def deeper():
        return _lower_lrn_for_tpu(monkeypatch)

    here, there = _lower_lrn_for_tpu(monkeypatch), deeper()
    assert here.as_text() == there.as_text()
    text = here.as_text(debug_info=True)
    assert "transpose(jvp(norm1))" in text
    # the kernels' fixed names reach the custom call
    assert 'kernel_name = "lrn_fwd"' in text
    assert 'kernel_name = "lrn_bwd"' in text


# --------------------------------------------------------------------------- #
# B. a span wherever a thread can hold a step up
# --------------------------------------------------------------------------- #

def _by_name(events):
    out = {}
    for e in events:
        out.setdefault(e["name"], []).append(e)
    return out


def test_two_batch_train_yields_every_span_and_the_chain_joins(
        tmp_path, clean_recorder):
    eng = _engine(tmp_path, max_iter=2, display=2, trace_out="spans.json")
    try:
        eng.train()
        gc.collect()                 # a collector run while the recorder is on
        # the reader ran ahead, filled its queue and blocked: the span of
        # that wait closes once the queue has room again
        next(eng.train_pipelines[0])
        deadline = time.time() + 10.0
        while time.time() < deadline and not any(
                e["name"] == "producer_queue_full"
                for e in clean_recorder.trace_events()):
            time.sleep(0.01)
    finally:
        eng.close()
    spans = _by_name(clean_recorder.trace_events())
    want_args = {
        "producer_read": {"batch"}, "producer_queue_full": {"batch"},
        "producer_h2d": {"batch", "bytes"},
        "prefetch_wait": {"iter", "batch"}, "dispatch": {"iter"},
        "dispatch_rng": {"iter"}, "dispatch_execute": {"iter"},
        "dispatch_window": {"iter"}, "step_done": {"iter"},
        "gc_pause": {"generation", "collected"},
        "telemetry_dump": {"iter"}, "hard_sync": {"boundary"}}
    for name, keys in want_args.items():
        assert name in spans, f"{name} missing from {sorted(spans)}"
        assert set(spans[name][0]["args"]) == keys, name
    assert "compile" in spans and spans["compile"][0]["dur"] > 0
    assert spans["compile"][0]["cat"] == spans["gc_pause"][0]["cat"] \
        == "runtime"
    assert spans["step_done"][0]["ph"] == "i"
    rows = 8 * eng.n_dev            # the prototxt's batch is per device
    assert spans["producer_h2d"][0]["args"]["bytes"] == \
        rows * 4 * 12 * 12 * 4 + rows * 4
    # one step's chain: producer_read -> producer_h2d -> prefetch_wait join
    # on batch; prefetch_wait -> dispatch (and its children) -> step_done
    # join on iter
    for it in (0, 1):
        wait = next(e for e in spans["prefetch_wait"]
                    if e["args"]["iter"] == it)
        batch = wait["args"]["batch"]
        assert batch == it
        read = next(e for e in spans["producer_read"]
                    if e["args"]["batch"] == batch)
        h2d = next(e for e in spans["producer_h2d"]
                   if e["args"]["batch"] == batch)
        disp = next(e for e in spans["dispatch"] if e["args"]["iter"] == it)
        done = next(e for e in spans["step_done"] if e["args"]["iter"] == it)
        assert read["ts"] <= h2d["ts"] <= disp["ts"] <= done["ts"]
        for child in ("dispatch_rng", "dispatch_execute"):
            c = next(e for e in spans[child] if e["args"]["iter"] == it)
            assert disp["ts"] <= c["ts"]
            assert c["ts"] + c["dur"] <= disp["ts"] + disp["dur"] + 1.0
    # the reader works on a thread of its own
    assert spans["producer_read"][0]["tid"] != spans["dispatch"][0]["tid"]
    # nothing new took a name the existing readers sum
    assert len(spans["dispatch"]) == 2 and len(spans["prefetch_wait"]) == 2


def test_threaded_prefetcher_records_h2d_and_full_queue_on_its_thread(
        clean_recorder):
    from poseidon_tpu.data.pipeline import DevicePrefetcher

    class Pipe:
        def __iter__(self):
            return self

        def __next__(self):
            return {"data": np.zeros((2, 3), np.float32)}

    clean_recorder.enable()
    feed = DevicePrefetcher([Pipe()], None, depth=1, passthrough=False)
    try:
        deadline = time.time() + 10.0
        taken = 0
        while time.time() < deadline and taken < 3:
            time.sleep(0.05)        # let the worker fill the queue and block
            next(feed)
            taken += 1
    finally:
        feed.close()
    spans = _by_name(clean_recorder.trace_events())
    h2d = sorted(e["args"]["batch"] for e in spans["producer_h2d"])
    assert h2d[:3] == [0, 1, 2]
    assert spans["producer_h2d"][0]["args"]["bytes"] == 24
    full = spans["producer_queue_full"]
    assert {e["tid"] for e in full} == {spans["producer_h2d"][0]["tid"]}
    assert full[0]["tid"] != threading.get_ident()
    assert full[0]["dur"] > 10_000          # it really waited (~50 ms)


def test_gc_pause_is_registered_only_while_enabled():
    rec = SpanRecorder()
    assert rec._on_gc not in gc.callbacks
    rec.enable()
    rec.enable()                            # idempotent
    assert gc.callbacks.count(rec._on_gc) == 1
    gc.collect()
    rec.disable()
    assert rec._on_gc not in gc.callbacks
    pauses = [e for e in rec.trace_events() if e["name"] == "gc_pause"]
    assert pauses and pauses[-1]["args"]["generation"] == 2
    assert pauses[-1]["dur"] > 0
    before = len(rec.trace_events())
    gc.collect()                            # disabled: nobody listens
    assert len(rec.trace_events()) == before


def test_spans_imports_without_jax():
    """The socket tier records spans from processes that never pay the
    jax import; the annotation is taken only where jax already is."""
    code = ("import sys, gc\n"
            "from poseidon_tpu.runtime.spans import recorder\n"
            "import poseidon_tpu.data.pipeline, poseidon_tpu.runtime.metrics\n"
            "recorder.enable()\n"
            "with recorder.span('x', 'y', {'k': 1}):\n"
            "    gc.collect()\n"
            "recorder.instant('i')\n"
            "recorder.disable()\n"
            "assert 'jax' not in sys.modules, 'jax was imported'\n"
            "print(sorted({e['name'] for e in recorder.trace_events()}))\n")
    env = dict(os.environ, PYTHONPATH=os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    done = subprocess.run([sys.executable, "-c", code], env=env,
                          capture_output=True, text=True, timeout=120)
    assert done.returncode == 0, done.stderr[-2000:]
    assert done.stdout.strip() == "['gc_pause', 'i', 'x']"


def test_disabled_recorder_allocates_nothing_in_the_producer_loop(
        clean_recorder, monkeypatch):
    """Recorder off (every untraced run): the producer loop builds no span
    object and no args dict — ``span()`` hands back the one shared no-op."""
    from poseidon_tpu.data.pipeline import BatchPipeline
    from poseidon_tpu.proto.messages import load_net_from_string

    def boom(*a, **k):
        raise AssertionError("a span was built while the recorder is off")

    monkeypatch.setattr(spans_mod._Span, "__init__", boom)
    calls = []
    real = clean_recorder.span
    monkeypatch.setattr(clean_recorder, "span",
                        lambda *a, **k: calls.append(a) or real(*a, **k))
    lp = load_net_from_string(NET).layers[0]
    pipe = BatchPipeline(lp, "TRAIN", 8, memory_data=_md(), prefetch=1)
    try:
        for _ in range(4):
            assert next(pipe)["data"].shape == (8, 4, 12, 12)
    finally:
        pipe.close()
    assert calls, "the producer loop never reached its span calls"
    assert all(a[2] is None for a in calls), calls[:3]    # no args dict
    assert all(real(*a) is spans_mod.NULL_SPAN for a in calls)
    assert clean_recorder.trace_events() == []


# --------------------------------------------------------------------------- #
# C. one clock: the spans in the xplane's host plane, per thread
# --------------------------------------------------------------------------- #

def test_spans_and_numbered_steps_lie_in_the_xplane_host_plane(
        tmp_path, clean_recorder):
    import jax
    from jax.profiler import ProfileData

    eng = _engine(tmp_path / "out", max_iter=4, display=2,
                  max_in_flight=2)
    clean_recorder.enable()
    try:
        eng.train(max_iter=2)               # compile outside the trace
        jax.profiler.start_trace(str(tmp_path / "trace"))
        try:
            eng.train(max_iter=4)
        finally:
            jax.profiler.stop_trace()
    finally:
        eng.close()
    (pb,) = glob.glob(str(tmp_path / "trace" / "plugins" / "profile" / "*"
                          / "*.xplane.pb"))
    lines = []            # per thread line: names of the program's events
    steps = []
    ours = {e["name"] for e in clean_recorder.trace_events()}
    for plane in ProfileData.from_file(pb).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            names = set()
            for ev in line.events:
                if ev.name == "train":
                    steps.append(dict(ev.stats).get("step_num"))
                elif ev.name in ours:
                    names.add(ev.name)
            if names:
                lines.append(names)
    assert sorted(steps) == [2, 3]
    train_line = next(names for names in lines if "dispatch" in names)
    assert {"prefetch_wait", "dispatch_rng", "dispatch_execute",
            "dispatch_window", "hard_sync"} <= train_line
    reader_line = next(names for names in lines
                       if "producer_read" in names)
    assert "dispatch" not in reader_line        # a thread line of its own
    assert any("step_done" in names for names in lines)


# --------------------------------------------------------------------------- #
# repair: --trace_out at display boundaries
# --------------------------------------------------------------------------- #

def test_trace_out_is_not_rewritten_inside_display_intervals(
        tmp_path, clean_recorder, monkeypatch):
    """The timeline is written where the loop stops anyway (snapshot
    boundaries, train() return, close()), never at a display boundary:
    what a display interval pays no longer grows with the spans the run
    has recorded."""
    eng = _engine(tmp_path, max_iter=8, display=2, snapshot=4,
                  snapshot_prefix="snap/t", trace_out="spans.json")
    dumps = []
    real_dump = clean_recorder.dump
    monkeypatch.setattr(
        clean_recorder, "dump",
        lambda path: dumps.append(eng.stats.counters["train_iters"])
        or real_dump(path))
    try:
        eng.train()
    finally:
        eng.close()
    # iteration counts at which the buffer was serialized: the snapshot
    # boundary at 4, the end of train(), close() — and none of the four
    # display boundaries (2, 4, 6, 8) on its own
    assert dumps == [4, 8, 8]
    dump_spans = [e for e in clean_recorder.trace_events()
                  if e["name"] == "telemetry_dump"]
    assert len(dump_spans) == 4
    assert (tmp_path / "spans.json").exists()
    assert (tmp_path / "stats.yaml").exists()


# --------------------------------------------------------------------------- #
# E. set-up: the start-up phase of the recorder, and stats section `startup`
# --------------------------------------------------------------------------- #

STARTUP_NET = """
name: "StartupNet"
layers {
  name: "src" type: DATA top: "data" top: "label"
  data_param { source: "%s" batch_size: 8 backend: LMDB }
  transform_param { scale: 0.00390625 }
}
layers {
  name: "conv1" type: CONVOLUTION bottom: "data" top: "conv1"
  convolution_param { num_output: 8 kernel_size: 5 stride: 2
    weight_filler { type: "xavier" } bias_filler { type: "constant" } }
}
layers { name: "relu1" type: RELU bottom: "conv1" top: "conv1" }
layers { name: "pool1" type: POOLING bottom: "conv1" top: "pool1"
  pooling_param { pool: MAX kernel_size: 2 stride: 2 } }
layers {
  name: "ip1" type: INNER_PRODUCT bottom: "pool1" top: "ip1"
  inner_product_param { num_output: 10
    weight_filler { type: "xavier" } bias_filler { type: "constant" } }
}
layers { name: "loss" type: SOFTMAX_LOSS bottom: "ip1" bottom: "label"
  top: "loss" }
"""

STARTUP_SOLVER = """
net: "%s"
base_lr: 0.01
lr_policy: "fixed"
momentum: 0.9
display: 4
max_iter: 11
random_seed: 3
snapshot: 0
snapshot_after_train: false
"""


@pytest.fixture(scope="module")
def startup_runs(tmp_path_factory):
    """The user's ``train`` command twice in this process, each with the
    start-up phase opened afresh, against ONE compile-cache directory: a
    cold run (plain) and a warm one (``--trace_out``). What each left:
    the ``startup`` and ``compiled_step`` sections, the phase's events,
    stats.yaml, the state of the recorder after the run, the dumped
    timeline."""
    import json

    from conftest import _set_jax_cache_dir
    from poseidon_tpu.runtime import cli
    from poseidon_tpu.runtime.engine import Engine
    from poseidon_tpu.runtime.metrics import read_stats_yaml

    root = tmp_path_factory.mktemp("startup")
    lmdb = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "examples", "mnist", "mnist_train_lmdb")
    (root / "net.prototxt").write_text(STARTUP_NET % lmdb)
    (root / "solver.prototxt").write_text(
        STARTUP_SOLVER % (root / "net.prototxt"))
    built = []
    real_init = Engine.__init__

    def keeping(self, *args, **kwargs):
        built.append(self)
        real_init(self, *args, **kwargs)

    import jax
    before = jax.config.jax_compilation_cache_dir
    runs = {}
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("JAX_COMPILATION_CACHE_DIR", str(root / "cc"))
        _set_jax_cache_dir(str(root / "cc"))
        mp.setattr(Engine, "__init__", keeping)
        for run, extra in (("cold", []),
                           ("warm", ["--trace_out", "spans.json"])):
            global_rec.disable()
            global_rec.clear()
            global_rec._begin_startup()     # a second `train` in one process
            out = root / run
            assert cli.main(["train", "--solver",
                             str(root / "solver.prototxt"),
                             "--output_dir", str(out)] + extra) == 0
            snap = built[-1].stats.snapshot()
            window = global_rec.trace_events()
            phase = global_rec.trace_events(startup=True)
            runs[run] = {
                "sections": snap["sections"], "timers": snap["timers_sec"],
                "events": phase[:len(phase) - len(window)],
                "window": window,
                "yaml": read_stats_yaml(str(out / "stats.yaml")),
                "enabled_after": global_rec.enabled,
                "open_after": global_rec.startup_open,
                "null_after": global_rec.span("x") is spans_mod.NULL_SPAN}
        with open(root / "warm" / "spans.json") as f:
            runs["warm"]["dump"] = json.load(f)
    _set_jax_cache_dir(before)
    global_rec.clear()
    return runs


BOTH = pytest.mark.parametrize("run, route", [("cold", "compiled"),
                                              ("warm", "loaded")])
TOP_LEVEL = ["cli_setup", "engine_build", "first_batch_wait", "step_load",
             "first_step"]


@BOTH
def test_startup_section_says_the_route_and_counts(startup_runs, run, route):
    sec = startup_runs[run]["sections"]["startup"]
    assert sec["route"] == route
    assert sec["route"] == \
        startup_runs[run]["sections"]["compiled_step"]["source"]
    assert len(sec["key"]) == 12 and int(sec["key"], 16) >= 0
    assert sec["events_dropped"] == 0 and sec["pallas_custom_calls"] == 0
    # the step's bytes, written on the cold run and read back on the warm
    assert sec["aot_bytes_serialized"] > sec["aot_bytes_on_disk"] > 0
    assert sec["aot_bytes_serialized"] == startup_runs["cold"]["sections"][
        "startup"]["aot_bytes_serialized"]
    # parameter init, rng and the rest compile outside the step's load
    # (in a process of its own; here jit's own caches may hold them all,
    # and on the second run do), into a cache directory that starts empty
    assert sec["compiles"] >= 0 and sec["compile_s"] >= 0
    assert sec["compiles"] <= startup_runs["cold"]["sections"]["startup"][
        "compiles"]
    assert sec["xla_cache_hits"] == 0
    assert startup_runs[run]["open_after"] is False


@BOTH
def test_startup_timeline_is_ordered_and_covers_the_stretch(
        startup_runs, run, route):
    sec = startup_runs[run]["sections"]["startup"]
    assert list(sec["timeline"]) == TOP_LEVEL
    at = [row["at_s"] for row in sec["timeline"].values()]
    assert at == sorted(at) and at[0] >= 0
    ends = [row["at_s"] + row["dur_s"] for row in sec["timeline"].values()]
    assert all(b >= a - 0.002 for a, b in zip(ends, at[1:]))   # no overlap
    assert 0.9 <= sec["coverage"] <= 1.0
    assert sec["stretch_s"] == pytest.approx(ends[-1] - at[0], abs=0.005)
    assert sec["spans"]["first_step"] > 0
    want = {"compiled": {"step_trace_lower", "step_compile", "aot_store",
                         "aot_serialize", "aot_pack", "aot_write"},
            "loaded": {"aot_read", "aot_unpack", "aot_deserialize"}}
    assert want[route] <= set(sec["spans"])
    assert not want["loaded" if route == "compiled" else "compiled"] \
        & set(sec["spans"])
    assert {"backend_init", "net_build", "pipeline_open", "step_build",
            "param_init", "step_key", "step_text",
            "scope_map"} <= set(sec["spans"])
    # the step's own compile is an event of the phase, inside its load
    assert ("compile" in sec["spans"]) or route == "loaded"


@BOTH
def test_every_startup_span_lies_inside_its_parent(startup_runs, run, route):
    events = startup_runs[run]["events"]
    by_name = _by_name(events)
    parents = {"backend_init": "cli_setup", "net_build": "engine_build",
               "pipeline_open": "engine_build", "step_build": "engine_build",
               "param_init": "engine_build", "step_key": "step_load",
               "step_text": "step_load", "scope_map": "step_load",
               "aot_read": "step_load", "aot_unpack": "step_load",
               "aot_deserialize": "step_load",
               "step_trace_lower": "step_load", "step_compile": "step_load",
               "aot_store": "step_load", "aot_serialize": "aot_store",
               "aot_pack": "aot_store", "aot_write": "aot_store"}
    checked = 0
    for e in events:
        parent = (e.get("args") or {}).get("parent")
        if e["name"] in TOP_LEVEL:
            assert parent is None, e
            continue
        if e["name"] != "compile":
            assert parent == parents[e["name"]], e
        outer = by_name[parent][0]      # the containers are there once
        assert len(by_name[parent]) == 1
        # a compile's start is its end less the duration jax reports, on
        # another clock: half a millisecond of grace
        assert outer["ts"] - 500.0 <= e["ts"]
        assert e["ts"] + e["dur"] <= outer["ts"] + outer["dur"] + 1.0
        checked += 1
    assert checked >= 12
    assert by_name["first_step"][0]["args"] == {"iter": 0}
    assert by_name["first_batch_wait"][0]["args"] == {"iter": 0}
    load = by_name["step_load"][0]["args"]
    assert load == {"route": route, "key": startup_runs[run]["sections"][
        "startup"]["key"]}
    assert all(e["args"].get("program") for e in by_name.get("compile", ()))
    assert ("compile" in by_name) or route == "loaded"


@BOTH
def test_compiled_step_timings_are_read_off_the_step_load_spans(
        startup_runs, run, route):
    events = startup_runs[run]["events"]
    load = _by_name(events)["step_load"][0]
    kids = [e for e in events
            if (e.get("args") or {}).get("parent") == "step_load"
            and e["name"] != "compile"]
    # the children tile the load: within 1% (half a millisecond on a step
    # this small, whose warm load is 30 ms)
    assert sum(e["dur"] for e in kids) == pytest.approx(
        load["dur"], rel=0.01, abs=500.0)
    step = startup_runs[run]["sections"]["compiled_step"]
    assert step["seconds"] == pytest.approx(load["dur"] / 1e6, abs=0.0006)
    assert list(step["phases"]) == ["load_s", "trace_lower_s", "compile_s",
                                    "store_s", "text_s", "scope_map_s"]
    assert sum(step["phases"].values()) == pytest.approx(
        step["seconds"], rel=0.01, abs=0.004)
    spans = startup_runs[run]["sections"]["startup"]["spans"]
    assert step["phases"]["compile_s"] == spans.get("step_compile", 0.0)
    assert step["phases"]["store_s"] == spans.get("aot_store", 0.0)
    assert (step["phases"]["compile_s"] > 0) == (route == "compiled")
    assert (step["phases"]["load_s"] > spans["step_key"]) \
        == (route == "loaded")
    assert startup_runs[run]["timers"]["engine_build"] == pytest.approx(
        spans["engine_build"], abs=0.0006)


@BOTH
def test_stats_yaml_of_a_plain_train_carries_the_startup_section(
        startup_runs, run, route):
    doc = startup_runs[run]["yaml"]["startup"]
    assert doc["route"] == route
    assert list(doc["timeline"]) == TOP_LEVEL
    for row in doc["timeline"].values():
        assert float(row["at_s"]) >= 0 and float(row["dur_s"]) >= 0
    for key in ("key", "coverage", "stretch_s", "compiles", "compile_s",
                "xla_cache_hits", "events_dropped", "aot_bytes_on_disk",
                "aot_bytes_serialized", "pallas_custom_calls"):
        assert key in doc, key
    assert float(doc["spans"]["engine_build"]) > 0


def test_recorder_is_as_before_once_the_first_step_is_done(startup_runs):
    """A plain run: after step 0 the recorder is disabled again, its window
    holds nothing of the ten steps that followed, no start-up event belongs
    to a later step, and ``span()`` hands out the shared null span."""
    cold = startup_runs["cold"]
    assert cold["enabled_after"] is False and cold["null_after"] is True
    assert cold["window"] == []
    assert cold["sections"]["startup"]["spans"]["first_step"] > 0
    assert {e["args"]["iter"] for e in cold["events"]
            if "iter" in (e.get("args") or {})} == {0}
    assert len([e for e in cold["events"] if e["name"] == "first_step"]) == 1
    assert len(cold["events"]) < 100            # a few dozen, not per step


def test_trace_out_timeline_holds_the_startup_spans_on_the_steps_clock(
        startup_runs):
    """Under ``--trace_out`` the Engine clears the recorder when it takes
    it and the phase's spans are still in the dump, ahead of the window and
    on its clock: ``first_step`` ends before iteration 1 is dispatched."""
    events = startup_runs["warm"]["dump"]["traceEvents"]
    by_name = _by_name(events)
    for name in TOP_LEVEL + ["backend_init", "param_init", "aot_deserialize",
                             "step_text"]:
        assert by_name[name][0]["cat"] == "startup", name
    first = by_name["first_step"][0]
    next_dispatch = next(e for e in by_name["dispatch"]
                         if e["args"]["iter"] == 1)
    own_dispatch = next(e for e in by_name["dispatch"]
                        if e["args"]["iter"] == 0)
    assert own_dispatch["ts"] <= first["ts"]
    assert first["ts"] + first["dur"] <= next_dispatch["ts"]
    assert by_name["cli_setup"][0]["ts"] < by_name["engine_build"][0]["ts"] \
        < first["ts"]
    assert len(by_name["dispatch"]) == 11 and len(by_name["first_step"]) == 1
    assert startup_runs["warm"]["dump"]["metadata"][
        "dropped_startup_spans"] == 0
    # the phase's compiles are there once
    assert len([e for e in by_name.get("compile", ())
                if e["ts"] < first["ts"]]) == len(
        [e for e in startup_runs["warm"]["events"] if e["name"] == "compile"])


def test_startup_cap_drops_and_counts():
    rec = SpanRecorder()
    rec.startup_cap = 4
    for i in range(7):
        with rec.startup("net_build", {"i": i}):
            pass
    assert rec.startup_dropped == 3
    assert len(rec.trace_events(startup=True)) == 4
    doc = rec.end_startup()
    assert doc["events_dropped"] == 3
    assert doc["timeline"]["net_build"]["n"] == 4
    assert SpanRecorder.startup_cap == 512


@pytest.mark.parametrize("state, kept", [
    ("open", "startup"), ("closed_disabled", None),
    ("closed_enabled", "window")])
def test_startup_span_always_times_and_is_kept_by_the_state(state, kept):
    """A start-up span is the timer of its region for every Engine of a
    process, so it times whatever the recorder's state; where the event goes
    is the state's: the phase while it is open, afterwards the window of an
    enabled recorder, else nowhere."""
    rec = SpanRecorder()
    if state != "open":
        rec.end_startup()
    if state == "closed_enabled":
        rec.enable()
    try:
        with rec.startup("engine_build") as outer:
            with rec.startup("param_init", {"iter": 0}) as inner:
                time.sleep(0.002)
            rec.complete("compile", 0.001, "runtime", {"program": "jit(f)"})
    finally:
        rec.disable()
    assert inner.dur_s >= 0.002 and outer.dur_s >= inner.dur_s
    assert outer.children == {"param_init": inner.dur_s}
    assert inner.args == {"iter": 0, "parent": "engine_build"}
    window = rec.trace_events()
    phase = [e for e in rec.trace_events(startup=True) if e not in window]
    names = {"startup": [e["name"] for e in phase],
             "window": [e["name"] for e in window], None: []}[kept]
    assert names == (["param_init", "compile", "engine_build"] if kept
                     else [])
    assert (phase == []) == (kept != "startup")
    assert (window == []) == (kept != "window")
    if kept == "startup":
        assert phase[1]["args"] == {"program": "jit(f)",
                                    "parent": "engine_build"}
        # a compile keeps the category its reporter gave it, so that it
        # is no row of the timeline and no named time of its own
        assert [e["cat"] for e in phase] == ["startup", "runtime", "startup"]
    # the hot path's span() is what it was: the shared null span unless the
    # recorder is enabled, whatever the phase
    assert rec.span("dispatch") is spans_mod.NULL_SPAN


def test_startup_summary_counts_compiles_outside_the_step_load():
    rec = SpanRecorder()
    with rec.startup("engine_build"):
        time.sleep(0.003)
        rec.complete("compile", 0.002, "runtime", {"program": "jit(init)"})
    time.sleep(0.002)                           # a caller's gap, and in
    # it a compile on a thread with no start-up span open (a harness's own)
    rec.complete("compile", 0.002, "runtime", {"program": "jit(theirs)"})
    with rec.startup("step_load"):
        time.sleep(0.003)
        rec.complete("compile", 0.002, "runtime", {"program": "jit(step)"})
    rec.note(add=True, xla_cache_hits=1)
    rec.note(route="compiled")
    with rec.startup("first_step", {"iter": 0}):
        time.sleep(0.001)
    doc = rec.end_startup()
    assert doc["compiles"] == 2 and doc["compile_s"] == pytest.approx(
        0.004, abs=1e-6)
    assert doc["spans"]["compile"] == pytest.approx(0.006, abs=1e-6)
    assert doc["xla_cache_hits"] == 1 and doc["route"] == "compiled"
    assert list(doc["timeline"]) == ["engine_build", "step_load",
                                     "first_step"]
    # the gap is not named: a compile no span of the program owns is no
    # row of the timeline and no named time
    assert 0.5 < doc["coverage"] < 0.9
    # once closed, facts and counts are not taken any more
    rec.note(route="x")
    rec.note(add=True, xla_cache_hits=1)
    assert rec.startup_open is False and rec.end_startup()["route"] == \
        "compiled" and rec.end_startup()["xla_cache_hits"] == 1


def test_exception_inside_a_startup_span_leaves_no_open_parent():
    rec = SpanRecorder()
    with pytest.raises(ValueError):
        with rec.startup("engine_build"):
            leaked = rec.startup("net_build")
            leaked.__enter__()          # never closed: the build raised
            raise ValueError("bad prototxt")
    with rec.startup("engine_build") as again:
        pass
    assert again.parent is None and "parent" not in again.args
    # `Engine.__init__` opens its span by hand; one a failed build left open
    # is no parent of the next build's, and closing it late harms nothing
    stale = rec.startup("engine_build").__enter__()
    rec.startup("net_build").__enter__()
    with rec.startup("engine_build") as third:
        with rec.startup("net_build") as inner:
            pass
    assert third.parent is None and inner.parent is third
    stale.__exit__(None, None, None)
    assert rec._open_startup_spans() == []
