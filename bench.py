"""Benchmark harness: training throughput on TPU.

Prints ONE JSON line:
  {"metric": ..., "value": N, "unit": ..., "vs_baseline": N, ...extras}

Baseline anchor (BASELINE.md): PMLS-Caffe trained AlexNet/ILSVRC12 to 56.5%
top-1 in ~1 day on 8x K20 (docs/performance.md:19). K20-era Caffe ran AlexNet
at ~200 images/s/GPU forward+backward (batch 256); the 8-node PMLS cluster
therefore sustained O(1.6k) images/s aggregate. vs_baseline is measured
images/s/chip divided by 200 (per-device parity with one K20 worker of the
reference cluster). GoogLeNet (docs/performance.md:40, quick_solver batch 32,
~4x speedup over single-machine Caffe ≈ 120 images/s/GPU-equivalent) is
reported in extras.

Contract:
- one process: the bench goes straight to ``jax.devices()`` and holds the
  chip itself (a chip belongs to one process at a time);
- the backend must be a TPU (never a silent CPU fallback); CPU runs must be
  requested explicitly via POSEIDON_BENCH_CPU=1 (smoke testing) and are
  labeled as such;
- a failure emits the ONE structured JSON line with an "error" field and
  value 0.0 — never a number carried over from an earlier run;
- an unknown ``device_kind`` has no peak FLOP/s: that is an error, not a
  default;
- extras include an MFU estimate from XLA's own cost analysis and a
  DWBP-overlap A/B (per-layer in-backward psums vs one fused end-of-backward
  sync).

ROADMAP S1 replaces this file with one runner over a table of cells; until
then `chip_smoke.py` is the proof that the system runs on the chip.
"""

from __future__ import annotations

import json
import os
import sys
import time

import numpy as np

BASELINE_IMAGES_PER_SEC_PER_DEVICE = 200.0   # PMLS-Caffe AlexNet on one K20
GOOGLENET_BASELINE_PER_DEVICE = 120.0        # ~4x single-GPU Caffe, 8 workers
_REPO = os.path.dirname(os.path.abspath(__file__))
# Every completed section checkpoints here, so a run killed at its time
# limit still leaves the finished sections' numbers on disk.
PARTIAL_PATH = os.path.join(_REPO, "evidence", "bench_partial.json")

# Peak bf16 FLOPs/s per chip by device kind (public specs).
PEAK_FLOPS = {
    "TPU v4": 275e12,
    "TPU v5 lite": 197e12,
    "TPU v5e": 197e12,
    "TPU v5p": 459e12,
    "TPU v6 lite": 918e12,
    "TPU v6e": 918e12,
}


def peak_flops(kind: str) -> float:
    """An unknown device kind is an error: a utilization against a guessed
    peak is not a measurement."""
    if kind not in PEAK_FLOPS:
        raise KeyError(f"no peak FLOP/s on record for device kind {kind!r}; "
                       f"add it to bench.PEAK_FLOPS with its source")
    return PEAK_FLOPS[kind]


def emit(payload: dict) -> None:
    print(json.dumps(payload), flush=True)


def fail(error: str, probe: dict | None = None,
         extras: dict | None = None) -> None:
    payload = {
        "metric": "alexnet_ilsvrc12_train_images_per_sec_per_chip",
        "value": 0.0,
        "unit": "images/s/chip",
        "vs_baseline": 0.0,
        "error": error,
    }
    if probe:
        payload["probe"] = probe
    if extras:
        payload["partial"] = extras
    emit(payload)
    sys.exit(1)


def checkpoint_partial(extras: dict, section: str) -> None:
    """Persist completed sections' numbers immediately (atomic rename), so
    the slowest section hanging cannot erase the ones that finished."""
    try:
        os.makedirs(os.path.dirname(PARTIAL_PATH), exist_ok=True)
        doc = {"sections_done": extras.get("_sections_done", []) + [section],
               "written_at": time.strftime("%Y-%m-%dT%H:%M:%SZ",
                                           time.gmtime()),
               **{k: v for k, v in extras.items() if not k.startswith("_")}}
        extras["_sections_done"] = doc["sections_done"]
        tmp = PARTIAL_PATH + ".tmp"
        with open(tmp, "w") as f:
            json.dump(doc, f, indent=1)
        os.replace(tmp, PARTIAL_PATH)
    except Exception as e:  # noqa: BLE001 — checkpointing must never kill a run
        print(f"[bench] partial checkpoint failed: {e}", file=sys.stderr,
              flush=True)


def _trace_meta(model: str, scan_steps, batch: dict, backend: str,
                device_kind: str) -> dict:
    """What a captured trace actually contains — stamped into extras AND
    written as trace_meta.json next to the xplane dump, so a trace pulled
    off a box weeks later still says what model/shape/backend it was."""
    return {
        "model": model,
        "scan_steps": scan_steps,
        "batch_shape": {k: list(map(int, np.shape(v)))
                        for k, v in batch.items()},
        "backend": backend,
        "device_kind": device_kind,
        "captured_at": time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime()),
    }


def _write_trace_meta(trace_dir: str, meta: dict) -> None:
    try:
        os.makedirs(trace_dir, exist_ok=True)
        with open(os.path.join(trace_dir, "trace_meta.json"), "w") as f:
            json.dump(meta, f, indent=1)
    except OSError as e:
        print(f"[bench] trace_meta write failed: {e}", file=sys.stderr,
              flush=True)


def probe_backend() -> dict:
    """The backend as jax reports it, in THIS process (which then holds
    the chip). A backend that cannot initialize raises."""
    import jax
    devs = jax.devices()
    return {"platform": devs[0].platform,
            "device_kind": devs[0].device_kind, "n": len(devs)}


def require_tpu(what: str, fail_fn) -> dict:
    """Shared gate of every mode: POSEIDON_BENCH_CPU=1 is the explicit,
    labeled CPU smoke; otherwise anything but a TPU is refused."""
    probe = probe_backend()
    if os.environ.get("POSEIDON_BENCH_CPU", "") == "1":
        probe["smoke"] = True
    elif probe["platform"] != "tpu":
        fail_fn(f"refusing to report {probe['platform']!r} as a TPU {what} "
                f"(set POSEIDON_BENCH_CPU=1 for an explicit CPU smoke run)",
                probe)
    return probe


def _build(model: str, per_dev_batch: int, image: int, classes: int,
           strategy_overrides=None, scan_steps: int | None = None,
           scan_reuse: bool = False, param_arena: bool = True,
           return_net: bool = False):
    import functools

    import jax
    import jax.numpy as jnp
    from poseidon_tpu.core.net import Net
    from poseidon_tpu.models import zoo
    from poseidon_tpu.parallel import (CommConfig, build_train_step,
                                      init_train_state, make_mesh)
    from poseidon_tpu.proto.messages import SolverParameter

    n_dev = jax.device_count()
    mesh = make_mesh()
    if model == "alexnet":
        net_param = zoo.alexnet(num_classes=classes, with_accuracy=False)
        chw = (3, image, image)
    elif model == "lenet":
        # the attribution ladder's smallest rung (and the overhead-guard
        # model): MNIST shapes, classes fixed by the architecture
        net_param = zoo.lenet(with_accuracy=False)
        chw = (1, 28, 28)
        classes = 10
    else:
        net_param = zoo.googlenet(num_classes=classes, with_accuracy=False)
        chw = (3, image, image)
    shapes = {"data": (per_dev_batch,) + chw,
              "label": (per_dev_batch,)}
    net = Net(net_param, phase="TRAIN", source_shapes=shapes)
    # Under the NHWC plan (policy conv_layout at net construction) the
    # step consumes channels-last batches directly — the synthetic
    # generator below emits them that way, so the timed program carries
    # ZERO entry transposes (real data is HWC-native anyway).
    nhwc = net.conv_layout == "NHWC"
    sp = SolverParameter(base_lr=0.01, lr_policy="step", gamma=0.1,
                         stepsize=100000, momentum=0.9, weight_decay=5e-4)
    # POSEIDON_BENCH_DWBP_BUCKET_MB >= 0 chains the DWBP taps into ~N-MB
    # buckets (distinct mid-backward collectives; 0 = per-blob) — see
    # parallel/strategies.py:_chained_sync_tap. Meaningful only on multi-
    # device meshes; a 1-chip TPU program has no collectives either way.
    bucket_env = os.environ.get("POSEIDON_BENCH_DWBP_BUCKET_MB", "")
    bucket_mb = float(bucket_env) if bucket_env else -1.0
    # POSEIDON_BENCH_ARENA_BUCKET_MB sizes the flat-arena gradient buckets
    # (param_arena=False builds the per-leaf baseline for the arena A/B;
    # an explicit DWBP bucket request also takes the per-leaf tap path)
    arena_mb = float(os.environ.get("POSEIDON_BENCH_ARENA_BUCKET_MB", "4"))
    comm = CommConfig(layer_strategies=dict(strategy_overrides or {}),
                      dwbp_bucket_mb=bucket_mb if bucket_mb >= 0 else None,
                      param_arena=param_arena, arena_bucket_mb=arena_mb)
    ts = build_train_step(net, sp, mesh, comm, donate=True,
                          scan_steps=scan_steps, scan_reuse_batch=scan_reuse,
                          input_layout="NHWC" if nhwc else "NCHW")
    params = net.init(jax.random.PRNGKey(0))
    state = init_train_state(params, comm, n_dev)
    batch = per_dev_batch * n_dev
    lead = ((scan_steps, batch) if scan_steps and not scan_reuse
            else (batch,))
    data_shape = (chw[1], chw[2], chw[0]) if nhwc else chw
    sharding = {"data": ts.batch_sharding, "label": ts.batch_sharding}

    # synthetic inputs are generated ON DEVICE: the timed path must measure
    # the training step, not host->device transfer of random bytes (input
    # feeding is benched separately: scripts/bench_dataplane.py for decode,
    # the microbench h2d section for the link)
    @functools.partial(jax.jit, out_shardings=sharding)
    def gen():
        k1, k2 = jax.random.split(jax.random.PRNGKey(0))
        return {"data": jax.random.uniform(
                    k1, lead + data_shape, jnp.float32),
                "label": jax.random.randint(k2, lead, 0, classes)}

    batch_arrs = gen()
    jax.block_until_ready(batch_arrs["data"])
    if return_net:
        return ts, params, state, batch_arrs, net
    return ts, params, state, batch_arrs


def _time_step(ts, params, state, batch, iters: int):
    """Wall time per OPTIMIZER step. With a scan-mode TrainStep each
    dispatch covers ts.scan_steps optimizer steps."""
    import jax
    rng = jax.random.PRNGKey(1)
    params, state, m = ts.step(params, state, batch, rng)  # compile+warmup
    jax.block_until_ready(m["loss"])
    t0 = time.perf_counter()
    for _ in range(iters):
        params, state, m = ts.step(params, state, batch, rng)
    jax.block_until_ready(m["loss"])
    dt = time.perf_counter() - t0
    return dt / iters / (ts.scan_steps or 1), params, state, m


def _time_dispatch_walls(ts, params, state, batch, dispatches: int,
                         warmup: int = 2):
    """Per-dispatch wall times, each individually blocked. The MIN wall is
    the robust estimator under one-sided dispatch noise (a dispatch can be
    late, never early): K-vs-2K differencing over AVERAGED walls failed
    because jitter spikes swamped the device-time difference.

    ``warmup`` dispatches run un-timed first (>= 2): the first call pays
    trace+compile, and the SECOND can still pay one-time runtime work
    (autotuned executable upload, allocator growth) — round 5's
    16368 ms googlenet "overhead" was compile-adjacent time caught in one
    series of the K-vs-2K differencing because only one variant was warm."""
    import jax
    rng = jax.random.PRNGKey(1)
    for _ in range(max(1, warmup)):
        params, state, m = ts.step(params, state, batch, rng)
        jax.block_until_ready(m["loss"])
    walls = []
    for _ in range(dispatches):
        t0 = time.perf_counter()
        params, state, m = ts.step(params, state, batch, rng)
        jax.block_until_ready(m["loss"])
        walls.append(time.perf_counter() - t0)
    return walls, params, state, m


def _dispatch_roundtrip_ms(iters: int = 12) -> float:
    """Round-trip latency of one tiny dispatch+block — the per-step tax a
    single-step-per-dispatch loop pays on this runtime (scan_steps
    amortizes it)."""
    import jax
    import jax.numpy as jnp
    bump = jax.jit(lambda v: v + 1.0)
    v = bump(jnp.zeros((8, 128), jnp.float32))
    jax.block_until_ready(v)
    t0 = time.perf_counter()
    for _ in range(iters):
        v = bump(v)
        jax.block_until_ready(v)
    return (time.perf_counter() - t0) / iters * 1e3


_PIPELINE_AB_NET = """
name: "pipe_ab"
layers { name: "src" type: MEMORY_DATA top: "data" top: "label"
  memory_data_param { batch_size: %d channels: 3 height: 24 width: 24 } }
layers { name: "conv1" type: CONVOLUTION bottom: "data" top: "conv1"
  convolution_param { num_output: 16 kernel_size: 3
    weight_filler { type: "xavier" } bias_filler { type: "constant" } } }
layers { name: "relu1" type: RELU bottom: "conv1" top: "conv1" }
layers { name: "pool1" type: POOLING bottom: "conv1" top: "pool1"
  pooling_param { pool: MAX kernel_size: 2 stride: 2 } }
layers { name: "ip1" type: INNER_PRODUCT bottom: "pool1" top: "ip1"
  inner_product_param { num_output: 10
    weight_filler { type: "xavier" } bias_filler { type: "constant" } } }
layers { name: "loss" type: SOFTMAX_LOSS bottom: "ip1" bottom: "label"
  top: "loss" }
"""


def _pipeline_ab(iters: int, per_dev_batch: int = 16) -> dict:
    """Pipelined-vs-serial A/B of the ENGINE loop itself (the tentpole of
    the step pipeline): the serial arm device_puts each batch inline and
    drains every step's metrics before dispatching the next
    (device_prefetch=0, max_in_flight=1 — the fully serial baseline); the
    pipelined arm stages batches to device in the
    background and runs the bounded in-flight dispatch window. Both train
    the same MEMORY_DATA conv net through real BatchPipelines, so host
    feeding is on the measured path — exactly what the pipeline hides.
    Returns {pipeline_speedup, *_step_ms, input_stall_ms_per_step,
    steps_in_flight}.

    Calibration: on CPU the pipeline is structurally ~neutral (there is
    no host->device link to hide, the prefetch stage runs in passthrough
    mode, and CPU dispatch is effectively synchronous), so the smoke's
    speedup measures ~1.0 +- the box's noise floor; the real win needs an
    accelerator backend, where the prefetch thread overlaps the transfer
    and the in-flight window hides the dispatch round-trip (not measured
    on the chip yet — ROADMAP S3)."""
    import jax
    from poseidon_tpu.proto.messages import (SolverParameter,
                                             load_net_from_string)
    from poseidon_tpu.runtime.engine import Engine

    net_param = load_net_from_string(_PIPELINE_AB_NET % per_dev_batch)
    rs = np.random.RandomState(0)
    md = {"data": rs.randn(512, 3, 24, 24).astype(np.float32),
          "label": rs.randint(0, 10, 512)}
    out: dict = {}

    def _mk(device_prefetch, max_in_flight):
        import tempfile
        sp = SolverParameter(train_net_param=net_param, base_lr=0.01,
                             lr_policy="fixed", momentum=0.9, display=0,
                             max_iter=0, random_seed=3)
        eng = Engine(sp, memory_data=md,
                     output_dir=tempfile.mkdtemp(prefix="pipe_ab_"),
                     device_prefetch=device_prefetch,
                     max_in_flight=max_in_flight)
        # every timed window is one train() call; its end-of-train
        # artifact writes (stats.yaml + CSV) are disk noise inside the
        # perf window — suppress them for the A/B engines only
        eng._write_artifacts = lambda: None
        return eng

    serial = _mk(0, 1)
    piped = _mk(int(os.environ.get("POSEIDON_BENCH_DEVICE_PREFETCH", "2")),
                int(os.environ.get("POSEIDON_BENCH_MAX_IN_FLIGHT", "2")))
    try:
        # warmup: compile + pipeline fill; steady-state stall only below
        # (the fill/compile-window waits must not contaminate the metric)
        serial.train(max_iter=3)
        piped.train(max_iter=3)
        stall0 = {e: e.stats.timers.get("input_stall", 0.0)
                  for e in (serial, piped)}
        n0 = {e: e.stats.counters.get("train_iters", 0.0)
              for e in (serial, piped)}
        # INTERLEAVED windows + min: both arms sample the same host-load
        # epochs (a drifting box cannot bias one arm), and the noise is
        # one-sided (a window can be slowed by background load, never
        # sped up), so min() is each arm's clean run — the same
        # estimator as the dispatch walls
        windows = int(os.environ.get("POSEIDON_BENCH_PIPELINE_WINDOWS",
                                     "12"))
        dts = {serial: [], piped: []}
        done = 3
        for w in range(windows):
            # alternate which arm goes first: under cgroup CPU throttling
            # the first runner of a period systematically gets the burst
            # budget, which would bias a fixed order by a few percent
            order = (serial, piped) if w % 2 == 0 else (piped, serial)
            for eng in order:
                t0 = time.perf_counter()
                eng.train(max_iter=done + iters)
                dts[eng].append((time.perf_counter() - t0) / iters)
            done += iters

        def _stall(eng):
            n = max(eng.stats.counters.get("train_iters", 0.0) - n0[eng], 1)
            return (eng.stats.timers.get("input_stall", 0.0)
                    - stall0[eng]) / n

        serial_s, piped_s = min(dts[serial]), min(dts[piped])
        # the headline ratio is the MEDIAN of paired per-window ratios:
        # pairing cancels epoch drift that min/min cannot (each arm's min
        # may come from different epochs), and the median rejects the
        # occasional throttled window outright
        ratios = sorted(a / b for a, b in zip(dts[serial], dts[piped]))
        speedup = ratios[len(ratios) // 2] if len(ratios) % 2 else \
            0.5 * (ratios[len(ratios) // 2 - 1] + ratios[len(ratios) // 2])
        serial_stall, piped_stall = _stall(serial), _stall(piped)
        in_flight = piped.stats.counters.get("steps_in_flight", 0.0)
    finally:
        serial.close()
        piped.close()
    out["pipeline_serial_step_ms"] = round(serial_s * 1e3, 3)
    out["pipeline_step_ms"] = round(piped_s * 1e3, 3)
    out["pipeline_speedup"] = round(speedup, 4)
    out["input_stall_ms_per_step"] = round(piped_stall * 1e3, 3)
    out["input_stall_serial_ms_per_step"] = round(serial_stall * 1e3, 3)
    out["steps_in_flight"] = in_flight
    return out


# --------------------------------------------------------------------------- #
# cold-start A/B: cache-cold vs cache-warm restart (elasticity economics)
# --------------------------------------------------------------------------- #

_COLDSTART_NET = """
name: "coldstart_ab"
layers { name: "src" type: MEMORY_DATA top: "data" top: "label"
  memory_data_param { batch_size: 16 channels: 3 height: 24 width: 24 } }
layers { name: "conv1" type: CONVOLUTION bottom: "data" top: "conv1"
  convolution_param { num_output: 24 kernel_size: 5
    weight_filler { type: "xavier" } bias_filler { type: "constant" } } }
layers { name: "relu1" type: RELU bottom: "conv1" top: "conv1" }
layers { name: "pool1" type: POOLING bottom: "conv1" top: "pool1"
  pooling_param { pool: MAX kernel_size: 2 stride: 2 } }
layers { name: "conv2" type: CONVOLUTION bottom: "pool1" top: "conv2"
  convolution_param { num_output: 32 kernel_size: 3
    weight_filler { type: "xavier" } bias_filler { type: "constant" } } }
layers { name: "relu2" type: RELU bottom: "conv2" top: "conv2" }
layers { name: "ip1" type: INNER_PRODUCT bottom: "conv2" top: "ip1"
  inner_product_param { num_output: 10
    weight_filler { type: "xavier" } bias_filler { type: "constant" } } }
layers { name: "loss" type: SOFTMAX_LOSS bottom: "ip1" bottom: "label"
  top: "loss" }
"""

def _step_flops(ts, params, state, batch) -> float:
    """XLA's own FLOP count for the compiled train step."""
    import jax
    rng = jax.random.PRNGKey(1)
    lowerable = ts.lowerable or ts.step
    compiled = lowerable.lower(params, state, batch, rng).compile()
    ca = compiled.cost_analysis()
    if isinstance(ca, (list, tuple)):
        ca = ca[0]
    return float(ca["flops"])


def main() -> None:
    bench_t0 = time.perf_counter()
    cpu_ok = os.environ.get("POSEIDON_BENCH_CPU", "") == "1"

    from poseidon_tpu import config
    # stage the async-collective flags before backend init (multi-chip
    # gradient all-reduces fuse with backward compute; no-op on one chip)
    config.enable_tpu_async_collectives()
    probe = require_tpu("number", fail)

    import jax
    import jax.numpy as jnp

    # POSEIDON_BENCH_PRNG=rbg swaps threefry for the TPU-cheap rbg
    # generator (dropout mask generation rides the step's critical path)
    prng = os.environ.get("POSEIDON_BENCH_PRNG", "")
    if prng:
        jax.config.update("jax_default_prng_impl", prng)

    # THE bf16 perf config (numeric.set_perf_policy): MXU-native bfloat16
    # compute + the exact space-to-depth stem rewrite, both on by default.
    config.set_perf_policy()

    n_dev = jax.device_count()
    per_dev_batch = int(os.environ.get("POSEIDON_BENCH_BATCH", "256"))
    image = int(os.environ.get("POSEIDON_BENCH_IMAGE", "227"))
    classes = int(os.environ.get("POSEIDON_BENCH_CLASSES", "1000"))
    iters = int(os.environ.get("POSEIDON_BENCH_ITERS", "20"))
    # GoogLeNet runs fixed 224x224 (its pooling tree needs it), so it is on
    # by default only on real accelerators — CPU smoke must opt in
    with_googlenet = os.environ.get("POSEIDON_BENCH_GOOGLENET",
                                    "0" if cpu_ok else "1") == "1"
    with_ab = os.environ.get("POSEIDON_BENCH_AB", "1") == "1"
    trace_dir = os.environ.get("POSEIDON_BENCH_TRACE", "")
    kind = jax.devices()[0].device_kind
    # no utilization on the CPU smoke: there is no peak to divide by
    peak = None if probe["platform"] == "cpu" else peak_flops(kind)

    extras: dict = {"backend": jax.default_backend(), "device_kind": kind,
                    "n_devices": n_dev}
    if prng:
        extras["prng_impl"] = prng
    # extras stop once the budget is spent so the headline JSON line always
    # lands within the driver's patience, even with slow first compiles
    budget_s = float(os.environ.get("POSEIDON_BENCH_BUDGET_S", "900"))

    def budget_left(section: str) -> bool:
        if time.perf_counter() - bench_t0 < budget_s:
            return True
        extras.setdefault("skipped_over_budget", []).append(section)
        return False

    # POSEIDON_BENCH_LAYOUT=NHWC takes the headline with the channels-last
    # internal conv layout (use when the layout A/B showed it wins — the
    # evidence capture escalates to this automatically)
    layout = os.environ.get("POSEIDON_BENCH_LAYOUT", "")
    if layout:
        config.set_policy(conv_layout=layout)
        extras["conv_layout"] = layout
    # The space-to-depth stem rewrite rides the bf16 perf config by default
    # (set_perf_policy above; conv1's 3 input channels are lane-starved on
    # the MXU); POSEIDON_BENCH_S2D=0 opts back out for a direct-conv1 run.
    s2d = os.environ.get("POSEIDON_BENCH_S2D", "1") == "1"
    if not s2d:
        config.set_policy(conv_s2d=False)
    extras["conv_s2d"] = s2d

    # K optimizer steps per dispatch: the runtime's per-dispatch round-trip
    # must not masquerade as step time. Timing at
    # K and 2K and differencing cancels the round-trip exactly; it is
    # reported separately as dispatch overhead. K must be large enough that
    # K x device_step dwarfs the round-trip NOISE (the xplane put the real
    # AlexNet device step at ~34 ms vs 1-2 s of jittery overhead, so K=16
    # differencing failed); batch reuse (scan_reuse_batch) keeps one batch
    # on device regardless of K, making K=64 affordable.
    scan_reuse = os.environ.get("POSEIDON_BENCH_SCAN_REUSE", "1") == "1"
    scan = max(1, int(os.environ.get("POSEIDON_BENCH_SCAN",
                                     "2" if cpu_ok else "64")))
    if scan_reuse:
        extras["scan_batch_reuse"] = True

    def _device_step_s(model, batch_sz, img, overrides=None,
                       dispatches=4):
        """(device_step_s, overhead_s, per_step_flops, ts_k, params, state,
        batch, metrics) via two-K differencing. The 2K program is built,
        timed, and freed BEFORE the K program so their stacked synthetic
        batches (the 2K one is ~5 GB at AlexNet defaults) never coexist on
        device. Per-step FLOPs are derived from the K-vs-2K cost-analysis
        ratio because XLA counts a while(scan) body ONCE regardless of trip
        count — dividing by K would be wrong under that convention."""
        ts_b, p_b, s_b, b_b = _build(model, batch_sz, img, classes,
                                     overrides, scan_steps=2 * scan,
                                     scan_reuse=scan_reuse)
        fl_b = _step_flops(ts_b, p_b, s_b, b_b)
        walls_b, p_b, s_b, m_b = _time_dispatch_walls(ts_b, p_b, s_b, b_b,
                                                      dispatches)
        del ts_b, p_b, s_b, b_b
        ts_a, p_a, s_a, b_a = _build(model, batch_sz, img, classes,
                                     overrides, scan_steps=scan,
                                     scan_reuse=scan_reuse)
        fl_a = _step_flops(ts_a, p_a, s_a, b_a)
        walls_a, p_a, s_a, m_a = _time_dispatch_walls(ts_a, p_a, s_a, b_a,
                                                      dispatches)
        # min-wall differencing: dispatch noise is one-sided (late, never
        # early), so min(walls) is each program's cleanest dispatch
        disp_a, disp_b = min(walls_a), min(walls_b)
        step_a = disp_a / scan           # per-step wall incl. overhead/K
        dev = (disp_b - disp_a) / scan
        differencing_ok = dev > 0
        floor_s = extras.get("dispatch_roundtrip_floor_ms", 0.0) / 1e3
        if differencing_ok:
            overhead = max(disp_a - scan * dev, 0.0)
            # plausibility cross-check against the independently measured
            # tiny-dispatch round-trip: an "overhead" orders of magnitude
            # above that floor (round 3's googlenet_dispatch_overhead_ms:
            # 16368) means the K-vs-2K difference under-estimated the device
            # step — flag it so the derived img/s is read with suspicion
            if overhead > max(1.0, 20.0 * floor_s):
                extras.setdefault("dispatch_overhead_implausible",
                                  {})[model] = round(overhead, 3)
        else:
            # noise swamped the difference (2K not slower than K — the
            # noise is one-sided, so one of the two mins is a jitter
            # victim). Clamp the negative delta to the measured
            # roundtrip floor: the device step is estimated as the K wall
            # minus the floor (never the raw wall, which would fold runtime
            # overhead into img/s), the reported overhead IS the floor
            # (explicitly flagged, not a silent 0.0), and the noisier of
            # the two wall series is recorded so the JSON says WHICH
            # timing to distrust.
            dev = max(disp_a - floor_s, 0.2 * disp_a) / scan
            overhead = floor_s
            spread = lambda ws: (max(ws) - min(ws)) / max(min(ws), 1e-9)  # noqa: E731
            extras.setdefault("dispatch_overhead_is_floor", {})[model] = True
            extras.setdefault("dispatch_noisy_timing", {})[model] = {
                "noisy": "2k" if spread(walls_b) >= spread(walls_a) else "k",
                "k_spread": round(spread(walls_a), 3),
                "2k_spread": round(spread(walls_b), 3)}
        # sanity invariant (round-5 verdict: googlenet overhead 16368 ms >
        # the dispatch itself): the overhead estimate must satisfy
        # 0 <= overhead < the K-dispatch wall — anything outside is a
        # differencing artifact, clamped and flagged, never reported raw
        if not 0.0 <= overhead < disp_a:
            extras.setdefault("dispatch_overhead_clamped", {})[model] = \
                round(overhead * 1e3, 3)
            overhead = min(max(overhead, 0.0), max(floor_s, 0.0),
                           0.5 * disp_a)
        assert 0.0 <= overhead < max(disp_a, 1e-12), (overhead, disp_a)
        # raw dispatch walls so a failed differencing is diagnosable from
        # the JSON alone (is 2K genuinely not slower, or just noisy?)
        extras.setdefault("dispatch_walls_ms", {})[model] = {
            "k": [round(w * 1e3, 1) for w in walls_a],
            "2k": [round(w * 1e3, 1) for w in walls_b]}
        if not (fl_a and fl_b):
            per_step_flops, convention = fl_a, "unknown"
        elif fl_b / fl_a > 1.5:
            per_step_flops, convention = fl_a / scan, "trip_scaled"
        else:
            per_step_flops, convention = fl_a, "body_once"
        return {"dev": dev, "overhead": overhead,
                "flops": per_step_flops, "flops_convention": convention,
                "differencing_ok": differencing_ok,
                "ts": ts_a, "params": p_a, "state": s_a, "batch": b_a,
                "metrics": m_a}

    try:
        extras["dispatch_roundtrip_floor_ms"] = round(_dispatch_roundtrip_ms(), 2)
        # ---- AlexNet (the headline number) --------------------------------
        from poseidon_tpu.parallel import SFB
        r = _device_step_s("alexnet", per_dev_batch, image,
                           {"fc6": SFB, "fc7": SFB},
                           dispatches=max(3, iters // 5))
        step_s, overhead_s, flops = r["dev"], r["overhead"], r["flops"]
        ts, params, state, batch, m = (r["ts"], r["params"], r["state"],
                                       r["batch"], r["metrics"])
        extras["dispatch_overhead_ms"] = round(overhead_s * 1e3, 3)
        extras["scan_steps_per_dispatch"] = scan
        if not r["differencing_ok"]:
            # the headline then contains overhead/K of runtime round-trip
            extras["dispatch_differencing_failed"] = True
        if flops and r["flops_convention"] == "unknown":
            extras["flops_convention_unverified"] = True
        if trace_dir:
            # capture the xplane AFTER the timed loop so profiler overhead
            # never contaminates the headline number or the A/B ratios
            jax.profiler.start_trace(trace_dir)
            params, state, m = ts.step(params, state, batch,
                                       jax.random.PRNGKey(2))
            jax.block_until_ready(m["loss"])
            jax.profiler.stop_trace()
            extras["trace_dir"] = trace_dir
            # self-describing capture: what was traced rides with the trace
            extras["trace_meta"] = _trace_meta(
                "alexnet", scan, batch, jax.default_backend(), kind)
            _write_trace_meta(trace_dir, extras["trace_meta"])
        images_per_sec = per_dev_batch * n_dev / step_s
        per_device = images_per_sec / n_dev
        # cost_analysis() flops are PER DEVICE under SPMD sharding
        extras["alexnet_step_flops_per_device"] = flops
        if peak:
            extras["alexnet_mfu"] = round(flops / step_s / peak, 4)
        extras["alexnet_step_ms"] = round(step_s * 1e3, 3)
        extras["alexnet_loss"] = float(np.asarray(m["loss"]).ravel()[-1])
        checkpoint_partial(extras, "alexnet")

        def _device_est(wall_per_step_s, tag):
            """Per-step device time for a sibling program: same-K wall minus
            the measured per-dispatch overhead share (the overhead is a
            property of the runtime link, not the program). If the overhead
            estimate swallows >80% of the sibling's wall time the subtraction
            is no longer trustworthy — keep a 20%-of-wall floor and flag the
            A/B so a noisy overhead can't fabricate absurd speedups."""
            est = wall_per_step_s - overhead_s / scan
            floor = 0.2 * wall_per_step_s
            if est < floor:
                extras[f"{tag}_overhead_dominated"] = True
                return floor
            return est

        # ---- DWBP overlap A/B: in-backward psums vs one fused sync --------
        if with_ab and n_dev > 1 and budget_left("dwbp_ab"):
            from poseidon_tpu.parallel import DENSE_FUSED
            fused_overrides = {"fc6": SFB, "fc7": SFB}
            ts2, p2, s2, b2 = _build(
                "alexnet", per_dev_batch, image, classes,
                {**{l: DENSE_FUSED for l in params}, **fused_overrides},
                scan_steps=scan, scan_reuse=scan_reuse)
            fused_s, *_ = _time_step(ts2, p2, s2, b2, max(3, iters // 5))
            fused_s = _device_est(fused_s, "dwbp_ab")
            extras["dwbp_overlap_speedup"] = round(fused_s / step_s, 4)
            extras["fused_sync_step_ms"] = round(fused_s * 1e3, 3)
            del ts2, p2, s2, b2
            checkpoint_partial(extras, "dwbp_ab")

        # ---- Conv layout A/B: NCHW vs net-level NHWC plan -----------------
        if os.environ.get("POSEIDON_BENCH_LAYOUT_AB", "1") == "1" and \
                not layout and budget_left("layout_ab"):
            with config.policy_scope(conv_layout="NHWC"):
                ts3, p3, s3, b3 = _build(
                    "alexnet", per_dev_batch, image, classes,
                    {"fc6": SFB, "fc7": SFB}, scan_steps=scan,
                    scan_reuse=scan_reuse)
                nhwc_s, p3, s3, _m3 = _time_step(ts3, p3, s3, b3,
                                                 max(3, iters // 5))
            nhwc_s = _device_est(nhwc_s, "nhwc_ab")
            extras["nhwc_step_ms"] = round(nhwc_s * 1e3, 3)
            extras["nhwc_speedup"] = round(step_s / nhwc_s, 4)
            # compiler-verifiable cleanliness: layout transposes in the
            # program we hand XLA (StableHLO from .lower() — tracing only,
            # no second multi-minute compile of the already-timed step;
            # the optimized-HLO count for the TPU compiler is captured by
            # scripts/aot_tpu_check.py --sections nhwc). The net-level plan
            # converts only at the FC boundary, so this should be ~2; the
            # old per-op shim carried one pair per pool/LRN seam (the
            # 0.53x round-3 anomaly this A/B keeps guarding).
            try:
                from poseidon_tpu.runtime.hlo_layout import (
                    count_layout_transposes)
                txt = ts3.lowerable.lower(
                    p3, s3, b3, jax.random.PRNGKey(1)).as_text()
                extras["nhwc_transposes_in_hlo"] = count_layout_transposes(txt)
                extras["nhwc_transposes_level"] = "stablehlo"
            except Exception as e:  # noqa: BLE001 — evidence, not headline
                extras["nhwc_transposes_in_hlo"] = f"error: {e}"
            del ts3, p3, s3, b3
            checkpoint_partial(extras, "layout_ab")

        # ---- Stem space-to-depth A/B: conv1 uses 3 of 128 MXU lanes -------
        # s2d now rides the headline (perf config); the A/B builds the
        # OTHER variant so the guard keeps measuring. s2d_speedup stays
        # oriented ">1 = the rewrite wins" either way.
        if os.environ.get("POSEIDON_BENCH_S2D_AB", "1") == "1" and \
                budget_left("s2d_ab"):
            with config.policy_scope(conv_s2d=not s2d):
                ts5, p5, s5, b5 = _build(
                    "alexnet", per_dev_batch, image, classes,
                    {"fc6": SFB, "fc7": SFB}, scan_steps=scan,
                    scan_reuse=scan_reuse)
                other_s, *_ = _time_step(ts5, p5, s5, b5, max(3, iters // 5))
            other_s = _device_est(other_s, "s2d_ab")
            on_s, off_s = (step_s, other_s) if s2d else (other_s, step_s)
            extras["s2d_step_ms"] = round(on_s * 1e3, 3)
            extras["s2d_off_step_ms"] = round(off_s * 1e3, 3)
            extras["s2d_speedup"] = round(off_s / on_s, 4)
            del ts5, p5, s5, b5
            checkpoint_partial(extras, "s2d_ab")

        # ---- Step-pipeline A/B: prefetch + in-flight window vs serial -----
        if os.environ.get("POSEIDON_BENCH_PIPELINE_AB", "1") == "1" and \
                budget_left("pipeline_ab"):
            extras.update(_pipeline_ab(
                int(os.environ.get("POSEIDON_BENCH_PIPELINE_ITERS",
                                   "30" if cpu_ok else "50"))))
            checkpoint_partial(extras, "pipeline_ab")

        # ---- TOPK selection cost at fc6 scale: global vs blocked ----------
        if os.environ.get("POSEIDON_BENCH_TOPK",
                          "0" if cpu_ok else "1") == "1" and \
                budget_left("topk_cost"):
            from poseidon_tpu.parallel.strategies import topk_compress
            fc6_n = int(os.environ.get("POSEIDON_BENCH_TOPK_N",
                                       str(4096 * 9216)))  # fc6 = 37.7M
            frac = 0.01
            g = jnp.asarray(np.random.RandomState(3)
                            .randn(fc6_n).astype(np.float32))
            err0 = jnp.zeros_like(g)

            def _time_compress(fn):
                s, e = fn(g, err0)
                jax.block_until_ready(s)
                t0 = time.perf_counter()
                for _ in range(5):
                    s, e = fn(g, e)
                jax.block_until_ready(s)
                return (time.perf_counter() - t0) / 5 * 1e3

            glob = jax.jit(lambda gg, ee: topk_compress(gg, frac, ee))
            blk = jax.jit(lambda gg, ee: topk_compress(gg, frac, ee,
                                                       block=4096))
            extras["topk_global_ms"] = round(_time_compress(glob), 3)
            extras["topk_blocked_ms"] = round(_time_compress(blk), 3)
            extras["topk_blocked_speedup"] = round(
                extras["topk_global_ms"] /
                max(extras["topk_blocked_ms"], 1e-9), 2)
            del g, err0
            checkpoint_partial(extras, "topk")

        # ---- Transformer LM (long-context flagship; beyond-reference) -----
        # The LM performance identity: GPT-2-small shape (~136M params at
        # vocab 32768, untied head) so tokens/s and MFU are anchored to a
        # model worth measuring. MFU follows the 6*P*T convention; XLA's
        # executed-flops count (includes remat recompute) is lm_hfu.
        if os.environ.get("POSEIDON_BENCH_LM",
                          "0" if cpu_ok else "1") == "1" and \
                budget_left("lm"):
            from poseidon_tpu.models.transformer import (
                TransformerConfig, build_dp_sp_train_step, gpt_small_config,
                init_params)
            from poseidon_tpu.parallel import make_mesh
            from poseidon_tpu.solvers.updates import init_state
            from poseidon_tpu.proto.messages import SolverParameter as SP

            lm_seq = int(os.environ.get("POSEIDON_BENCH_LM_SEQ", "1024"))
            lm_batch = int(os.environ.get("POSEIDON_BENCH_LM_BATCH", "8"))
            lm_preset = os.environ.get("POSEIDON_BENCH_LM_PRESET",
                                       "gpt_small")
            if lm_preset == "tiny":     # CPU smoke only — never a headline
                lm_cfg = TransformerConfig(
                    vocab_size=512, d_model=64, n_heads=2, n_layers=2,
                    d_ff=128, max_seq=lm_seq, remat=True)
            else:
                lm_cfg = gpt_small_config(max_seq=lm_seq)
            lm_mesh = make_mesh(axes=("data", "seq"), shape=(n_dev, 1))
            lm_step = build_dp_sp_train_step(
                lm_cfg, SP(base_lr=0.01, lr_policy="fixed", momentum=0.9),
                lm_mesh, donate=False)
            lp = init_params(lm_cfg, jax.random.PRNGKey(0))
            ls = init_state(lp)
            rs2 = np.random.RandomState(1)
            toks = jnp.asarray(rs2.randint(
                0, lm_cfg.vocab_size, size=(lm_batch * n_dev, lm_seq),
                dtype=np.int32))
            tgts = jnp.asarray(rs2.randint(
                0, lm_cfg.vocab_size, size=(lm_batch * n_dev, lm_seq),
                dtype=np.int32))
            # ONE compile: the AOT executable supplies cost analysis AND
            # runs the timing loop (calling lm_step would jit-compile the
            # same 12-layer remat program a second time)
            lm_exec = lm_step.lower(lp, ls, toks, tgts,
                                    jax.random.PRNGKey(1)).compile()
            lm_flops = 0.0
            try:
                lm_ca = lm_exec.cost_analysis()
                if isinstance(lm_ca, (list, tuple)):
                    lm_ca = lm_ca[0]
                lm_flops = float(lm_ca.get("flops", 0.0))
            except Exception:  # noqa: BLE001
                pass
            lp, ls, lm_m = lm_exec(lp, ls, toks, tgts, jax.random.PRNGKey(1))
            jax.block_until_ready(lm_m["loss"])
            t0 = time.perf_counter()
            lm_iters = max(3, iters // 4)
            for _ in range(lm_iters):
                lp, ls, lm_m = lm_exec(lp, ls, toks, tgts,
                                       jax.random.PRNGKey(2))
            jax.block_until_ready(lm_m["loss"])
            lm_dt = (time.perf_counter() - t0) / lm_iters
            extras["lm_tokens_per_sec_per_chip"] = round(
                lm_batch * lm_seq / lm_dt, 1)
            n_par = lm_cfg.n_params()
            model_flops = 6.0 * n_par * lm_batch * lm_seq  # the MFU convention

            def _lm_rates(dt):
                # MFU uses the 6*P*T convention; the executed-flops number
                # (which under remat counts the backward's forward
                # recompute, ~8*P*T) is reported separately as HFU
                if peak:
                    extras["lm_mfu"] = round(model_flops / dt / peak, 4)
                    if lm_flops:
                        extras["lm_hfu"] = round(lm_flops / dt / peak, 4)

            # the LM step is one dispatch per step; correct for the measured
            # per-dispatch runtime round-trip to estimate the device rate
            lm_dev_dt = lm_dt - overhead_s
            if 0 < lm_dev_dt < lm_dt:
                extras["lm_tokens_per_sec_per_chip_device"] = round(
                    lm_batch * lm_seq / lm_dev_dt, 1)
                _lm_rates(lm_dev_dt)
            else:
                _lm_rates(lm_dt)
            extras["lm_config"] = {
                "preset": lm_preset, "params": n_par,
                "d_model": lm_cfg.d_model, "n_layers": lm_cfg.n_layers,
                "n_heads": lm_cfg.n_heads, "vocab": lm_cfg.vocab_size,
                "batch_per_chip": lm_batch, "seq": lm_seq, "remat": True}
            if lm_flops:
                extras["lm_step_flops_per_device"] = lm_flops
                extras["lm_flops_vs_6pt"] = round(lm_flops / model_flops, 3)
            extras["lm_seq"] = lm_seq
            extras["lm_loss"] = float(lm_m["loss"])
            del lp, ls
            checkpoint_partial(extras, "lm")

        # ---- GoogLeNet ----------------------------------------------------
        if with_googlenet and budget_left("googlenet"):
            g_batch = int(os.environ.get("POSEIDON_BENCH_GOOGLENET_BATCH",
                                         "128"))
            # GoogLeNet's pooling tree needs the real 224 input (the anchor
            # config, models/bvlc_googlenet); tiny smoke sizes break it
            g_image = 224
            # 4+ dispatches: min-wall differencing needs at least one clean
            # dispatch per program; 3 was the weakest config in the round-3
            # capture
            rg = _device_step_s("googlenet", g_batch, g_image,
                                dispatches=max(4, iters // 5))
            g_step_s, gflops, mg = rg["dev"], rg["flops"], rg["metrics"]
            extras["googlenet_dispatch_overhead_ms"] = round(
                rg["overhead"] * 1e3, 3)
            if not rg["differencing_ok"]:
                extras["googlenet_differencing_failed"] = True
            g_per_device = g_batch / g_step_s
            extras["googlenet_images_per_sec_per_chip"] = round(g_per_device, 2)
            extras["googlenet_vs_baseline"] = round(
                g_per_device / GOOGLENET_BASELINE_PER_DEVICE, 3)
            extras["googlenet_loss"] = float(
                np.asarray(mg["loss"]).ravel()[-1])
            if gflops and peak:
                extras["googlenet_mfu"] = round(gflops / g_step_s / peak, 4)
            checkpoint_partial(extras, "googlenet")

            # ---- Flat-arena A/B: packed buckets + fused update vs the ----
            # per-leaf swarm (~120 leaves = ~120 collectives + tiny update
            # fusions — the flagged GoogLeNet MFU gap). The headline above
            # already runs the arena; this builds the per-leaf baseline.
            ts_g = rg["ts"]
            if ts_g.arena is not None:
                extras["arena_buckets"] = ts_g.arena.n_buckets
                extras["arena_param_bytes"] = ts_g.arena.total_bytes()
                try:
                    # gradient all-reduces in the COMPILED program — must
                    # be <= ceil(total_grad_bytes / arena_bucket_mb); 0 on
                    # a single chip (no collectives at all)
                    from poseidon_tpu.runtime.hlo_comm import (
                        count_gradient_all_reduces)
                    g_hlo = ts_g.lowerable.lower(
                        rg["params"], rg["state"], rg["batch"],
                        jax.random.PRNGKey(1)).compile().as_text()
                    extras["arena_collectives_in_hlo"] = \
                        count_gradient_all_reduces(g_hlo)
                except Exception as e:  # noqa: BLE001 — evidence, not headline
                    extras["arena_collectives_in_hlo"] = f"error: {e}"
            # gated on the headline actually RUNNING the arena (an explicit
            # POSEIDON_BENCH_DWBP_BUCKET_MB disables it): without the gate
            # this block would label a per-leaf-vs-per-leaf comparison as
            # the arena A/B
            if ts_g.arena is not None and \
                    os.environ.get("POSEIDON_BENCH_ARENA_AB", "1") == "1" \
                    and budget_left("arena_ab"):
                del rg, ts_g
                ts6, p6, s6, b6 = _build(
                    "googlenet", g_batch, g_image, classes,
                    scan_steps=scan, scan_reuse=scan_reuse,
                    param_arena=False)
                leaf_s, *_ = _time_step(ts6, p6, s6, b6, max(3, iters // 5))
                leaf_s = _device_est(leaf_s, "arena_ab")
                extras["arena_step_ms"] = round(g_step_s * 1e3, 3)
                extras["per_leaf_step_ms"] = round(leaf_s * 1e3, 3)
                extras["arena_speedup"] = round(leaf_s / g_step_s, 4)
                del ts6, p6, s6, b6
                checkpoint_partial(extras, "arena_ab")
    except Exception as e:  # noqa: BLE001
        import traceback
        fail(f"{type(e).__name__}: {e} | "
             f"{traceback.format_exc().strip().splitlines()[-1]}", probe,
             extras)
        return

    payload = {
        "metric": "alexnet_ilsvrc12_train_images_per_sec_per_chip",
        "value": round(per_device, 2),
        "unit": "images/s/chip",
        "vs_baseline": round(per_device / BASELINE_IMAGES_PER_SEC_PER_DEVICE,
                             3),
        **{k: v for k, v in extras.items() if not k.startswith("_")},
    }
    emit(payload)


# --------------------------------------------------------------------------- #
# serving mode: `python bench.py serving`
# --------------------------------------------------------------------------- #

SERVING_P99_TARGET_MS = 50.0   # vs_baseline anchor: an interactive-serving
#                                p99 budget; vs_baseline = target / measured
#                                (>1 means under budget), same
#                                higher-is-better orientation as the
#                                training metric.


def serving_main() -> None:
    """Serving latency microbenchmark: in-process InferenceServer (port 0)
    driven by serving/client.py's load generator. Emits the same ONE-JSON-
    line contract as the training bench — {"metric", "value", "unit",
    "vs_baseline", ...extras} — with p50/p99/throughput/shed/batch-fill.

    Env knobs: POSEIDON_BENCH_CPU=1 (explicit CPU smoke, labeled),
    POSEIDON_BENCH_SERVE_REQUESTS/_CONCURRENCY/_BATCH/_BUCKETS,
    POSEIDON_BENCH_SERVE_MODEL/_WEIGHTS (deploy prototxt + snapshot; the
    default is the CLI's built-in synthetic conv net)."""
    cpu_ok = os.environ.get("POSEIDON_BENCH_CPU", "") == "1"

    def fail_serving(error: str, probe: dict | None = None) -> None:
        payload = {"metric": "serving_p99_ms", "value": 0.0, "unit": "ms",
                   "vs_baseline": 0.0, "error": error}
        if probe:
            payload["probe"] = probe
        emit(payload)
        sys.exit(1)

    probe = require_tpu("serving number", fail_serving)

    n_requests = int(os.environ.get("POSEIDON_BENCH_SERVE_REQUESTS", "400"))
    concurrency = int(os.environ.get("POSEIDON_BENCH_SERVE_CONCURRENCY", "8"))
    batch = int(os.environ.get("POSEIDON_BENCH_SERVE_BATCH", "8"))
    buckets = os.environ.get("POSEIDON_BENCH_SERVE_BUCKETS", "1,4,16,64")
    model = os.environ.get("POSEIDON_BENCH_SERVE_MODEL", "")
    weights = os.environ.get("POSEIDON_BENCH_SERVE_WEIGHTS", "")

    try:
        from poseidon_tpu.runtime.cli import (_build_serving_executor,
                                              run_serving_bench)

        t0 = time.perf_counter()
        executor = _build_serving_executor(model, weights, buckets)
        warm_s = time.perf_counter() - t0
        result, stats = run_serving_bench(
            executor, n_requests, concurrency, batch,
            max_queue=max(64, concurrency * 8))
    except Exception as e:  # noqa: BLE001 — one JSON line on every path
        import traceback
        fail_serving(f"{type(e).__name__}: {e} | "
                     f"{traceback.format_exc().strip().splitlines()[-1]}",
                     probe)
        return

    if not result.get("ok") or result.get("p99_ms") is None:
        # a run where every request shed/errored must FAIL loudly, not
        # report value 0.0 as if it were a fast success
        fail_serving(
            f"no successful requests (ok={result.get('ok')}, "
            f"shed={result.get('shed')}, errors={result.get('error')})",
            probe)
        return
    p99 = result.get("p99_ms") or 0.0
    emit({
        "metric": "serving_p99_ms",
        "value": p99,
        "unit": "ms",
        "vs_baseline": round(SERVING_P99_TARGET_MS / p99, 3) if p99 else 0.0,
        "p50_ms": result.get("p50_ms"),
        "mean_ms": result.get("mean_ms"),
        "throughput_rps": result.get("throughput_rps"),
        "requests": n_requests,
        "concurrency": concurrency,
        "shed": result.get("shed"),
        "errors": result.get("error"),
        "batch_fill": stats.get("batch_fill"),
        "batches": stats.get("batches"),
        "bucket_calls": stats.get("bucket_calls"),
        "aot_warm_s": round(warm_s, 3),
        "platform": probe.get("platform"),
        "cpu_smoke": cpu_ok,
    })

    if os.environ.get("POSEIDON_BENCH_FLEET", "1") != "0":
        try:
            fleet_main(probe)
        except Exception as e:  # noqa: BLE001 — one JSON line on every path
            import traceback
            emit({"metric": "fleet_goodput_rps", "value": 0.0,
                  "unit": "req/s", "vs_baseline": 0.0,
                  "error": f"{type(e).__name__}: {e} | "
                           f"{traceback.format_exc().strip().splitlines()[-1]}"})


# the fleet A/B's synthetic deploy net: heavier than the bench_serve one so
# a request's dispatch (GIL-free XLA compute) dominates the Python/socket
# overhead — otherwise the 1-vs-N comparison measures the front door, not
# the replicas
FLEET_BENCH_NET = """
name: "fleet_synthetic"
input: "data"
input_dim: 1 input_dim: 3 input_dim: 48 input_dim: 48
layers { name: "conv1" type: CONVOLUTION bottom: "data" top: "conv1"
  convolution_param { num_output: 48 kernel_size: 3 pad: 1
    weight_filler { type: "xavier" } } }
layers { name: "relu1" type: RELU bottom: "conv1" top: "conv1" }
layers { name: "conv2" type: CONVOLUTION bottom: "conv1" top: "conv2"
  convolution_param { num_output: 48 kernel_size: 3 pad: 1
    weight_filler { type: "xavier" } } }
layers { name: "relu2" type: RELU bottom: "conv2" top: "conv2" }
layers { name: "pool" type: POOLING bottom: "conv2" top: "pool"
  pooling_param { pool: MAX kernel_size: 2 stride: 2 } }
layers { name: "fc" type: INNER_PRODUCT bottom: "pool" top: "fc"
  inner_product_param { num_output: 64 weight_filler { type: "xavier" } } }
layers { name: "prob" type: SOFTMAX bottom: "fc" top: "prob" }
"""


def fleet_main(probe: dict) -> None:
    """Fleet A/B: goodput-vs-offered-load curves for 1 vs N replicas
    behind the same front door (serving/fleet.ReplicaManager), driven by
    the OPEN-LOOP load generator at 3 offered-load points anchored to the
    single replica's measured closed-loop capacity C (0.6C under load,
    1.5C past saturation, 3.0C deep overload). Emits the BENCH-schema
    lines ``fleet_goodput_rps`` (vs_baseline = N-replica / 1-replica
    goodput at the top point — the fleet scaling acceptance) and
    ``fleet_p99_ms`` (vs_baseline = 1-replica / N-replica p99 there).

    Env knobs: POSEIDON_BENCH_FLEET=0 skips, POSEIDON_BENCH_FLEET_REPLICAS
    (default 3), POSEIDON_BENCH_FLEET_SECONDS per point (default 2.5),
    POSEIDON_BENCH_FLEET_MODEL/_WEIGHTS (deploy prototxt override)."""
    import numpy as np

    import jax
    from poseidon_tpu.core.net import Net
    from poseidon_tpu.proto.messages import load_net_from_string
    from poseidon_tpu.serving.client import run_load
    from poseidon_tpu.serving.executor import BucketedExecutor
    from poseidon_tpu.serving.fleet import ReplicaManager
    from poseidon_tpu.serving.server import InferenceServer

    n_repl = int(os.environ.get("POSEIDON_BENCH_FLEET_REPLICAS", "3"))
    duration = float(os.environ.get("POSEIDON_BENCH_FLEET_SECONDS", "2.5"))
    model = os.environ.get("POSEIDON_BENCH_FLEET_MODEL", "")
    weights = os.environ.get("POSEIDON_BENCH_FLEET_WEIGHTS", "")
    buckets = (1, 4, 8)
    rows = 4                     # every request = one bucket-4 dispatch
    deadline_ms = 400.0          # the goodput SLO: late answers don't count
    concurrency = 96             # open-loop workers (>> offered x latency;
    #                              under deep overload a blocked worker means
    #                              a late fire, which closes the loop)

    if model:
        # warm=False: this executor only donates net/params to the replica
        # fleet — warming it would pay a full per-bucket AOT compile for
        # executables nobody ever dispatches
        base = BucketedExecutor.from_files(model, weights or None,
                                           buckets=buckets, warm=False)
        net, params = base.net, base._params
    else:
        net = Net(load_net_from_string(FLEET_BENCH_NET), "TEST")
        params = net.init(jax.random.PRNGKey(0))
    devs = jax.devices()

    def make_fleet(n: int) -> ReplicaManager:
        # pin round-robin across local devices (on the CPU proxy that is
        # one device — concurrency still comes from N flush threads
        # dispatching GIL-free XLA executions)
        exs = [BucketedExecutor(net, params, buckets=buckets,
                                device=devs[i % len(devs)])
               for i in range(n)]
        # batching/admission knobs belong to the replicas' batchers (the
        # fleet-mode server ignores its own): tight flush deadline, deep
        # admission queue so overload turns into deadline misses and
        # sheds, not instant refusals
        return ReplicaManager(exs, devices=[str(devs[i % len(devs)])
                                            for i in range(n)],
                              max_delay_s=0.002, max_queue=128)

    name = net.input_names[0]
    row_shape = tuple(net.blob_shapes[name][1:])
    frame = np.random.RandomState(0).randn(rows,
                                           *row_shape).astype(np.float32)

    def mk(i):
        return {name: frame}

    def drive(n_replicas: int, points) -> dict:
        fleet = make_fleet(n_replicas)
        server = InferenceServer(fleet=fleet)
        arm = {"replicas": n_replicas, "points": {}}
        try:
            if points is None:
                # closed-loop capacity probe: what ONE replica sustains
                # probed at enough closed-loop workers to saturate the
                # micro-batcher (packing raises capacity vs a serial
                # probe); the curve itself uses a larger OPEN-loop pool
                # purely to keep arrivals on schedule under overload
                cap = run_load(server.addr, mk, n_requests=400,
                               concurrency=24)
                arm["capacity_rps"] = cap["throughput_rps"]
                # floor at 1 req/s: a pathologically slow model must not
                # produce an offered point of 0 (run_load refuses it)
                points = [max(1.0, round(cap["throughput_rps"] * f, 1))
                          for f in (0.6, 1.5, 3.0)]
            arm["offered_points_rps"] = points
            for rps in points:
                n = max(80, int(rps * duration))
                r = run_load(server.addr, mk, n_requests=n,
                             concurrency=concurrency,
                             deadline_ms=deadline_ms, offered_rps=rps)
                arm["points"][str(rps)] = {
                    k: r.get(k) for k in
                    ("goodput_rps", "p50_ms", "p99_ms", "ok", "shed",
                     "deadline", "error", "late_fires", "achieved_rps")}
        finally:
            server.shutdown()
        return arm

    one = drive(1, None)
    many = drive(n_repl, one["offered_points_rps"])
    top = str(one["offered_points_rps"][-1])
    g1 = one["points"][top]["goodput_rps"] or 0.0
    gN = many["points"][top]["goodput_rps"] or 0.0
    speedup = round(gN / g1, 3) if g1 else 0.0
    cfg = {
        "cpu_proxy": probe["platform"] != "tpu",
        "platform": probe.get("platform"),
        "replicas": n_repl,
        "request_rows": rows,
        "deadline_ms": deadline_ms,
        "duration_s_per_point": duration,
        "offered_points_rps": one["offered_points_rps"],
    }
    emit({"metric": "fleet_goodput_rps", "value": gN, "unit": "req/s",
          "vs_baseline": speedup, "goodput_speedup_at_top_offered": speedup,
          **cfg, "one_replica": one, "fleet": many})
    p99_1 = one["points"][top]["p99_ms"] or 0.0
    p99_N = many["points"][top]["p99_ms"] or 0.0
    emit({"metric": "fleet_p99_ms", "value": p99_N, "unit": "ms",
          "vs_baseline": round(p99_1 / p99_N, 3) if p99_N else 0.0,
          **cfg,
          "one_replica_p99_ms": p99_1,
          "curve_one": {k: v["p99_ms"] for k, v in one["points"].items()},
          "curve_fleet": {k: v["p99_ms"] for k, v in many["points"].items()}})


# --------------------------------------------------------------------------- #
# serving_llm mode: `python bench.py serving_llm`
# --------------------------------------------------------------------------- #

LLM_EVIDENCE_PATH = os.path.join(_REPO, "evidence", "serving_llm.json")

# request mix for the A/B: mostly-short generations with a heavy tail.
# Static batching pays the max of the batch (every slot rides until the
# longest sequence drains) while continuous batching backfills freed
# slots the same step — a homogeneous mix would hide exactly the
# straggler waste iteration-level scheduling exists to reclaim.
LLM_LEN_CYCLE = (2, 64, 2, 2, 2, 2, 2, 2)


def serving_llm_main(argv: list | None = None) -> None:
    """LLM decode serving bench: goodput-vs-offered-load curves for a
    replica fleet of paged-KV continuous batchers behind the socket front
    door, plus the continuous-vs-static A/B at deep overload.

    Open-loop points anchor to the continuous fleet's measured closed-loop
    capacity C (0.6C / 1.5C / 3.0C); the static control arm (same pool,
    same deadlines, gang admission instead of iteration-level) is driven
    at the 3.0C point only — that is where slot reclamation matters and
    where the acceptance (continuous >= 2x static goodput) is judged.
    Goodput is generated tokens/s over ACCEPTED requests (sheds and
    deadline misses earn zero), p99 over accepted only.

    Emits BENCH lines ``llm_goodput_tps``, ``llm_p99_ms`` and
    ``continuous_vs_static_speedup``; writes evidence/serving_llm.json.

    Env knobs: POSEIDON_BENCH_CPU=1 (explicit CPU proxy, labeled),
    POSEIDON_BENCH_LLM_REPLICAS (3), POSEIDON_BENCH_LLM_SECONDS per point
    (2.5), POSEIDON_BENCH_LLM_MAXNEW (16), POSEIDON_BENCH_LLM_PROMPT (12),
    POSEIDON_BENCH_LLM_DEADLINE_MS (2000), POSEIDON_BENCH_LLM_GPT_SMALL=1
    (force the GPT-small config even off-TPU)."""
    del argv
    cpu_ok = os.environ.get("POSEIDON_BENCH_CPU", "") == "1"

    def fail_llm(error: str, probe: dict | None = None) -> None:
        payload = {"metric": "llm_goodput_tps", "value": 0.0,
                   "unit": "tok/s", "vs_baseline": 0.0, "error": error}
        if probe:
            payload["probe"] = probe
        emit(payload)
        sys.exit(1)

    probe = require_tpu("LLM serving number", fail_llm)

    import jax
    from poseidon_tpu.models.transformer import (TransformerConfig,
                                                 gpt_small_config,
                                                 init_params)
    from poseidon_tpu.serving.client import run_load
    from poseidon_tpu.serving.continuous import GenerateExecutor
    from poseidon_tpu.serving.fleet import ReplicaManager
    from poseidon_tpu.serving.server import InferenceServer

    n_repl = int(os.environ.get("POSEIDON_BENCH_LLM_REPLICAS", "3"))
    duration = float(os.environ.get("POSEIDON_BENCH_LLM_SECONDS", "2.5"))
    max_new = int(os.environ.get("POSEIDON_BENCH_LLM_MAXNEW", "64"))
    p_len = int(os.environ.get("POSEIDON_BENCH_LLM_PROMPT", "12"))
    # the goodput SLO (same role as fleet_main's 400ms): an answer later
    # than this earns nothing — SLO-goodput is where iteration-level
    # scheduling wins, because static batching's queue wait blows the
    # budget long before its raw throughput ceiling does
    deadline_ms = float(os.environ.get("POSEIDON_BENCH_LLM_DEADLINE_MS",
                                       "400"))
    concurrency = 64             # open-loop workers (see fleet_main)

    on_tpu = probe["platform"] == "tpu"
    if on_tpu or os.environ.get("POSEIDON_BENCH_LLM_GPT_SMALL") == "1":
        model_name = "gpt_small"
        cfg = gpt_small_config(max_seq=512, remat=False)
        page_size, rungs, buckets = 64, (1, 2, 4, 8), (16, 64)
        max_seq_len = 512
    else:
        # CPU proxy: the model must be small enough that the FIXED
        # per-step cost (dispatch, page-table build) dominates per-row
        # matmul. On the accelerator a decode step is bandwidth-bound —
        # its cost barely moves with occupancy, which is exactly why an
        # idle slot is waste. CPU matmul instead scales with rows, and a
        # compute-bound proxy would price static batching's idle slots
        # at zero, hiding the effect being measured.
        model_name = "cpu_proxy_tiny"
        cfg = TransformerConfig(vocab_size=256, d_model=32, n_heads=4,
                                n_layers=2, d_ff=128, max_seq=128)
        page_size, rungs, buckets = 16, (1, 2, 4, 8), (16,)
        max_seq_len = 80
    params = init_params(cfg, jax.random.PRNGKey(0))
    devs = jax.devices()

    rs = np.random.RandomState(0)
    prompts = rs.randint(0, cfg.vocab_size, (32, p_len)).astype(np.int32)

    def mk(i):
        return {"prompt": prompts[i % len(prompts)],
                "max_new": min(max_new, LLM_LEN_CYCLE[i % len(LLM_LEN_CYCLE)])}

    ab_duration = float(os.environ.get("POSEIDON_BENCH_LLM_AB_SECONDS",
                                       "6"))

    def drive(mode: str, points, durations=None, probe_only=False,
              use_deadline=True) -> dict:
        exs = []
        for i in range(n_repl):
            ex = GenerateExecutor(cfg, params, page_size=page_size,
                                  decode_rungs=rungs,
                                  prompt_buckets=buckets,
                                  max_seq_len=max_seq_len,
                                  default_max_new=max_new,
                                  device=devs[i % len(devs)])
            ex.scheduler_mode = mode
            exs.append(ex)
        fleet = ReplicaManager(exs, devices=[str(devs[i % len(devs)])
                                             for i in range(n_repl)],
                               max_delay_s=0.002, max_queue=128)
        server = InferenceServer(fleet=fleet)
        arm = {"mode": mode, "replicas": n_repl, "points": {}}
        try:
            if points is None or probe_only:
                # probe at the open-loop worker-pool size: the batcher's
                # capacity depends on occupancy, and a shallow closed-loop
                # pool would under-fill the rungs and anchor the curve to
                # a fictitiously low C. No deadline: this measures the raw
                # sustainable rate, stragglers fully paid.
                cap = run_load(server.addr, mk, n_requests=200,
                               concurrency=concurrency, op="generate")
                arm["capacity_rps"] = cap["throughput_rps"]
                arm["capacity_tps"] = cap["goodput_tps"]
                if probe_only:
                    points = []
                else:
                    points = [max(1.0, round(cap["throughput_rps"] * f, 1))
                              for f in (0.6, 1.5, 3.0)]
            arm["offered_points_rps"] = points
            if durations is None:
                # the deep-overload point runs longer: the static arm's
                # queue collapse needs several SLO-widths of steady state
                # before its goodput stops depending on the window edge
                durations = [duration] * (len(points) - 1) + [ab_duration]
            for rps, secs in zip(points, durations):
                n = max(40, int(rps * secs))
                r = run_load(server.addr, mk, n_requests=n,
                             concurrency=concurrency,
                             deadline_ms=deadline_ms if use_deadline
                             else None,
                             offered_rps=rps, op="generate")
                arm["points"][str(rps)] = {
                    k: r.get(k) for k in
                    ("goodput_tps", "tokens", "goodput_rps", "p50_ms",
                     "p99_ms", "ok", "shed", "deadline", "error",
                     "late_fires", "achieved_rps")}
        finally:
            server.shutdown()
        # retirement must have freed every page — a leak here means lost
        # serving capacity that compounds forever in a real deployment
        arm["pools_all_free"] = all(ex.pool.all_free() for ex in exs)
        return arm

    cont = drive("continuous", None)
    top = str(cont["offered_points_rps"][-1])

    # A/B anchor: DEEP OVERLOAD IS RELATIVE TO THE STATIC ARM (3x its
    # own measured capacity), and the A/B runs WITHOUT the per-request
    # SLO. With a deadline, the comparison is bistable around the SLO
    # cliff: queue wait eats the budget and the deadline kills every
    # straggler in BOTH arms, so the slot waste continuous batching
    # exists to reclaim has already been shed at the front door and the
    # arms converge. Deadline-free deep overload pins each arm at its
    # saturated service rate — goodput IS sustainable capacity, and the
    # delta isolates iteration-level slot reclamation. The SLO machinery
    # is still measured where it behaves monotonically: the curve above.
    c_static = drive("static", [], probe_only=True)["capacity_rps"]
    r_ab = max(1.0, round(c_static * 4.0, 1))
    ab_static = drive("static", [r_ab], [ab_duration],
                      use_deadline=False)
    ab_cont = drive("continuous", [r_ab], [ab_duration],
                    use_deadline=False)

    g = ab_cont["points"][str(r_ab)]["goodput_tps"] or 0.0
    gs = ab_static["points"][str(r_ab)]["goodput_tps"] or 0.0
    speedup = round(g / gs, 3) if gs else 0.0
    cfg_extras = {
        "cpu_proxy": not on_tpu,
        "platform": probe.get("platform"),
        "model": model_name,
        "replicas": n_repl,
        "prompt_len": p_len,
        "max_new_cycle": [min(max_new, x) for x in LLM_LEN_CYCLE],
        "page_size": page_size,
        "decode_rungs": list(rungs),
        "deadline_ms": deadline_ms,
        "duration_s_per_point": duration,
        "ab_duration_s": ab_duration,
        "offered_points_rps": cont["offered_points_rps"],
        "static_capacity_rps": c_static,
        "ab_offered_rps": r_ab,
    }
    g_top = cont["points"][top]["goodput_tps"] or 0.0
    emit({"metric": "llm_goodput_tps", "value": g_top, "unit": "tok/s",
          "vs_baseline": speedup,
          "continuous_vs_static_at_ab_point": speedup,
          **cfg_extras, "continuous": cont,
          "ab_static": ab_static, "ab_continuous": ab_cont})
    p99 = cont["points"][top]["p99_ms"] or 0.0
    p99_s = ab_static["points"][str(r_ab)]["p99_ms"] or 0.0
    p99_c = ab_cont["points"][str(r_ab)]["p99_ms"] or 0.0
    emit({"metric": "llm_p99_ms", "value": p99, "unit": "ms",
          "vs_baseline": round(p99_s / p99_c, 3) if p99_c else 0.0,
          **cfg_extras, "ab_static_p99_ms": p99_s,
          "ab_continuous_p99_ms": p99_c,
          "curve_continuous": {k: v["p99_ms"]
                               for k, v in cont["points"].items()}})
    emit({"metric": "continuous_vs_static_speedup", "value": speedup,
          "unit": "x", "vs_baseline": speedup, **cfg_extras,
          "continuous_goodput_tps": g, "static_goodput_tps": gs,
          "pools_all_free": cont["pools_all_free"]
          and ab_static["pools_all_free"] and ab_cont["pools_all_free"]})

    doc = {"written_at": time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime()),
           "config": cfg_extras, "continuous": cont,
           "ab_static": ab_static, "ab_continuous": ab_cont,
           "llm_goodput_tps": g_top, "llm_p99_ms": p99,
           "continuous_vs_static_speedup": speedup}
    os.makedirs(os.path.dirname(LLM_EVIDENCE_PATH), exist_ok=True)
    tmp = LLM_EVIDENCE_PATH + ".tmp"
    with open(tmp, "w") as f:
        json.dump(doc, f, indent=2, sort_keys=True)
        f.write("\n")
    os.replace(tmp, LLM_EVIDENCE_PATH)


# --------------------------------------------------------------------------- #
# attribution mode: `python bench.py attribution [--model alexnet]`
# --------------------------------------------------------------------------- #

ATTR_COVERAGE_TARGET = 0.90       # named-layer rows must cover this much
ATTR_MODELS = ("lenet", "alexnet", "googlenet")
# named scopes OUTSIDE the layer graph (core/arena.py, solvers/updates.py,
# parallel/strategies.py) — attributed by name, never residual
ATTR_EXTRA_SCOPES = frozenset({
    "arena_pack", "arena_unpack", "arena_views", "arena_grads",
    "optimizer_update", "grad_sync"})


def _attr_one(model: str, per_dev_batch: int, iters: int, classes: int,
              peak: float | None, trace_keep: str) -> dict:
    """One model's attribution: build + ONE compile (timing, trace capture,
    cost analysis and the HLO-text scope join all reuse it), timed loop
    FIRST, one traced step AFTER (runtime/attribution.measure_then_trace),
    then the xplane -> per-layer table."""
    import shutil
    import tempfile

    import jax
    from poseidon_tpu.runtime import attribution as A

    image = {"lenet": 28, "googlenet": 224}.get(
        model, int(os.environ.get("POSEIDON_BENCH_IMAGE", "227")))
    ts, params, state, batch, net = _build(
        model, per_dev_batch, image, classes, scan_steps=None,
        return_net=True)
    rng = jax.random.PRNGKey(1)
    low = ts.lowerable or ts.step
    compiled = low.lower(params, state, batch, rng).compile()
    hlo_text = compiled.as_text()
    step_flops = 0.0
    try:
        ca = compiled.cost_analysis()
        if isinstance(ca, (list, tuple)):
            ca = ca[0]
        step_flops = float(ca.get("flops", 0.0))
    except Exception:  # noqa: BLE001 — evidence, not headline
        pass

    holder = {"params": params, "state": state}

    def run_step():
        # rebind: donated buffers mean last step's params are consumed
        # (the lowerable's raw signature may carry the empty dump slot)
        out = compiled(holder["params"], holder["state"], batch, rng)
        holder["params"], holder["state"], m = out[:3]
        jax.block_until_ready(m["loss"])

    conv_plan = {k: v for k, v in net.conv_strategy_plan().items() if v}
    if conv_plan:
        print(f"[bench] {model} conv strategies: "
              + ", ".join(f"{k}={v}" for k, v in conv_plan.items()),
              file=sys.stderr, flush=True)

    trace_dir = trace_keep or tempfile.mkdtemp(prefix=f"attr_{model}_")
    try:
        timing = A.measure_then_trace(run_step, trace_dir, iters=iters)
        meta = _trace_meta(model, None, batch, jax.default_backend(),
                           jax.devices()[0].device_kind)
        _write_trace_meta(trace_dir, meta)
        events = A.load_trace_events(trace_dir)
    finally:
        if not trace_keep:
            shutil.rmtree(trace_dir, ignore_errors=True)
    scope_map = A.hlo_scope_map(hlo_text,
                                {layer.name for layer in net.layers},
                                ATTR_EXTRA_SCOPES)
    # CPU proxy correction: the host tracer bills ~10 us per op event,
    # which makes loopy ops (pool backward's one-thunk-per-window
    # select-and-scatter) read catastrophically slower traced than
    # untraced; strip the measured traced-vs-untraced gap per event.
    # TPU device-plane events are hardware timings — no correction.
    overhead_ms = (None if peak else
                   max(timing["traced_step_ms"] - timing["step_ms"], 0.0))
    result = A.attribute(events, scope_map,
                         cost_table=A.layer_cost_table(net),
                         peak_flops=peak,
                         tracer_overhead_ms=overhead_ms)
    # comm time per mesh axis: the spmd/arena collective scopes
    # (grad_rs_bucket<i> on fsdp, grad_ar_bucket<i>/grad_sync_bucket<i>
    # on data, tp_* on tp) carry their axis in the name — attribute it
    # instead of leaving collectives in the residual row
    comm_by_axis: dict = {}
    for r in result["rows"]:
        ax = A.comm_axis_of(r["layer"])
        if ax:
            comm_by_axis[ax] = round(
                comm_by_axis.get(ax, 0.0) + r["total_ms"], 4)
    doc = {
        "comm_ms_by_axis": comm_by_axis,
        "conv_strategy_plan": conv_plan,
        "model": model,
        "per_device_batch": per_dev_batch,
        "step_ms_timed": timing["step_ms"],
        "step_flops_per_device": step_flops,
        "trace_events": len(events),
        "trace_meta": meta,
        **result,
    }
    if peak and timing["step_ms"] > 0 and step_flops:
        doc["step_mfu"] = round(
            step_flops / (timing["step_ms"] / 1e3) / peak, 4)
    print(A.format_table(result, title=f"== {model} (batch {per_dev_batch}"
                                       f"/device, {timing['step_ms']} ms "
                                       f"timed step) =="),
          file=sys.stderr, flush=True)
    return doc


def attribution_main(argv: list) -> None:
    """`bench.py attribution`: the per-layer device-time table ROADMAP
    item 2 needs — ms / FLOPs / arithmetic intensity / %-of-traced-op-time per named
    layer, residual row for honesty, top-3 sinks flagged. Emits the ONE
    JSON line (metric = worst named coverage across models) and writes the
    full tables to --out. A CPU run is clearly labeled as proxy timings;
    the same command runs unchanged on the chip."""
    import argparse

    ap = argparse.ArgumentParser(prog="bench.py attribution")
    ap.add_argument("--model", default="all",
                    choices=ATTR_MODELS + ("all",))
    ap.add_argument("--iters", type=int, default=0,
                    help="timed steps before the traced one (0 = 3 on "
                         "cpu, 10 on tpu)")
    ap.add_argument("--batch", type=int, default=0,
                    help="per-device batch (0 = per-model default)")
    ap.add_argument("--out", default=os.path.join(_REPO, "chiprun_out",
                                                  "attribution.json"))
    ap.add_argument("--trace_dir", default="",
                    help="keep raw profiler dumps under <dir>/<model> "
                         "(default: temp, deleted after parsing)")
    args = ap.parse_args(argv)

    def fail_attr(error: str, probe: dict | None = None) -> None:
        payload = {"metric": "attribution_named_coverage", "value": 0.0,
                   "unit": "fraction", "vs_baseline": 0.0, "error": error}
        if probe:
            payload["probe"] = probe
        emit(payload)
        sys.exit(1)

    # attribution is evidence, not the throughput headline: a CPU run
    # (thunk-runtime op events attribute the same way) is labeled as
    # proxy; the same command runs unchanged on the chip
    on_accel = probe_backend()["platform"] == "tpu"
    import jax

    from poseidon_tpu import config
    config.set_perf_policy()
    # per-layer measured conv strategy rides the attribution run by
    # default: the choices print with their micro-run times, and the
    # winner documents persist (evidence/conv_tune unless a compile-cache
    # dir is already configured) so a second run skips re-measurement.
    # POSEIDON_BENCH_CONV_STRATEGY: ''=legacy, or direct/im2col/s2d.
    conv_strategy = os.environ.get("POSEIDON_BENCH_CONV_STRATEGY", "auto")
    if conv_strategy:
        config.set_policy(conv_strategy=conv_strategy)
        if not config.compile_cache_config().cache_dir:
            config.set_compile_cache_config(
                cache_dir=os.path.join(_REPO, "evidence", "conv_tune"))
    kind = jax.devices()[0].device_kind
    peak = peak_flops(kind) if on_accel else None
    models = ATTR_MODELS if args.model == "all" else (args.model,)
    iters = args.iters or (10 if on_accel else 3)
    classes = int(os.environ.get("POSEIDON_BENCH_CLASSES", "1000"))
    defaults = ({"lenet": 64, "alexnet": 256, "googlenet": 128}
                if on_accel else
                {"lenet": 64, "alexnet": 16, "googlenet": 8})

    docs: dict = {}
    try:
        for model in models:
            docs[model] = _attr_one(
                model, args.batch or defaults[model], iters, classes, peak,
                os.path.join(args.trace_dir, model) if args.trace_dir
                else "")
    except Exception as e:  # noqa: BLE001 — one JSON line on every path
        import traceback
        fail_attr(f"{type(e).__name__}: {e} | "
                  f"{traceback.format_exc().strip().splitlines()[-1]}")
        return

    out_doc = {"backend": jax.default_backend(), "device_kind": kind,
               "coverage_target": ATTR_COVERAGE_TARGET, "models": docs}
    if not on_accel:
        out_doc["proxy"] = ("cpu-backend timings (thunk-runtime op "
                            "events); per-layer MFU needs a TPU run of "
                            "this same command")
    try:
        os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
        tmp = args.out + ".tmp"
        with open(tmp, "w") as f:
            json.dump(out_doc, f, indent=1)
        os.replace(tmp, args.out)
    except OSError as e:
        print(f"[bench] attribution out write failed: {e}", file=sys.stderr,
              flush=True)

    # the sink ranking as its own BENCH line, so "which named row eats the
    # step" is tracked across rounds in the BENCH stream, not just in
    # evidence JSON: top-3 named rows by self time + their share of traced
    # op time, per model; headline value = the top-3 combined share on the
    # largest model measured (the lift target — it FALLS as kernels land)
    sinks = {}
    for m, d in docs.items():
        tot = d["total_ms"] or 1.0
        sinks[m] = [{"row": r["layer"], "self_ms": r["total_ms"],
                     "share": round(r["total_ms"] / tot, 4)}
                    for r in d["rows"][:3]]
    head = next((m for m in ("googlenet", "alexnet") if m in docs),
                next(iter(docs)))
    emit({
        "metric": "top_self_time_sinks",
        "value": round(sum(s["share"] for s in sinks[head]), 4),
        "unit": "fraction_of_traced_self_time",
        "vs_baseline": 1.0,
        "model": head,
        "backend": jax.default_backend(),
        "cpu_proxy": not on_accel,
        "sinks": sinks,
    })

    coverage = min(d["coverage"] for d in docs.values())
    emit({
        "metric": "attribution_named_coverage",
        "value": round(coverage, 4),
        "unit": "fraction",
        "vs_baseline": round(coverage / ATTR_COVERAGE_TARGET, 3),
        "backend": jax.default_backend(),
        "device_kind": kind,
        "cpu_proxy": not on_accel,
        "out": args.out,
        "models": {m: {"coverage": d["coverage"],
                       "step_ms": d["step_ms_timed"],
                       "top_sinks": d["top_sinks"],
                       "residual_pct": d["residual"]["pct_of_traced"]}
                   for m, d in docs.items()},
    })


# --------------------------------------------------------------------------- #
# mesh mode: `python bench.py mesh` — replicated vs fsdp vs tp A/B
# --------------------------------------------------------------------------- #

def mesh_main(argv: list) -> None:
    """`bench.py mesh`: the sharding planner's A/B (ROADMAP item 1).

    For AlexNet, time one optimizer step under {replicated, fsdp, tp}
    arms on the SAME device count and record each arm's lowered
    collective census against the planned schedule, plus the fsdp arm's
    per-device persistent state bytes (sharded-state layout) vs
    replicated. For the GPT-small LM, lower the dp2 x tp4 step
    (models/transformer.py) and diff its census against the comm bill on
    record in evidence/aot_tpu/lm_gpt_small.json. CPU runs are labeled
    proxy — step times need a run on the chip; the census and byte
    counts are backend-independent."""
    import argparse
    import time as _t

    ap = argparse.ArgumentParser(prog="bench.py mesh")
    ap.add_argument("--iters", type=int, default=3)
    ap.add_argument("--batch", type=int, default=0,
                    help="global batch (0 = 8 on cpu, 256 on tpu)")
    ap.add_argument("--image", type=int, default=0,
                    help="AlexNet image size (0 = 67 on cpu, 227 on tpu)")
    ap.add_argument("--out", default=os.path.join(_REPO, "evidence",
                                                  "mesh_ab.json"))
    args = ap.parse_args(argv)

    # the mesh A/B is structural evidence (census + bytes) plus proxy
    # step times: off the chip it wants the 8-device virtual CPU mesh (the
    # flag shapes only the host platform, and must precede backend init)
    os.environ.setdefault("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in os.environ["XLA_FLAGS"]:
        os.environ["XLA_FLAGS"] = (
            os.environ["XLA_FLAGS"]
            + " --xla_force_host_platform_device_count=8").strip()
    on_accel = probe_backend()["platform"] == "tpu"
    import jax

    import jax.numpy as jnp
    from poseidon_tpu.config import MeshConfig
    from poseidon_tpu.core.net import Net
    from poseidon_tpu.models import zoo
    from poseidon_tpu.parallel import CommConfig, init_train_state
    from poseidon_tpu.parallel.spmd import (ShardingPlan,
                                            build_spmd_train_step,
                                            named_mesh, shard_train_state)
    from poseidon_tpu.proto.messages import SolverParameter
    from poseidon_tpu.runtime.hlo_comm import collective_census_stablehlo

    image = args.image or (227 if on_accel else 67)
    batch = args.batch or (256 if on_accel else 8)
    sp = SolverParameter(base_lr=0.01, lr_policy="fixed", momentum=0.9,
                         weight_decay=0.0005)
    comm = CommConfig()
    rs = np.random.RandomState(0)
    doc: dict = {"backend": jax.default_backend(),
                 "cpu_proxy": not on_accel,
                 "alexnet": {}, "image": image, "global_batch": batch}

    arms = (("replicated", "dp2,fsdp2", dict(shard_params=False)),
            ("fsdp2", "dp2,fsdp2", {}),
            ("tp2", "dp2,tp2", {}))
    for arm, spec, plan_kw in arms:
        cfg = MeshConfig.parse(spec)
        mesh = named_mesh(cfg)
        n_dp = cfg.data * cfg.fsdp
        net = Net(zoo.alexnet(num_classes=1000, with_accuracy=False),
                  phase="TRAIN",
                  source_shapes={"data": (batch // n_dp, 3, image, image),
                                 "label": (batch // n_dp,)})
        plan = ShardingPlan.build(net, cfg, comm, **plan_kw)
        ts = build_spmd_train_step(net, sp, mesh, plan, comm,
                                   donate=False)
        params = net.init(jax.random.PRNGKey(0))
        state = init_train_state(params, comm, plan.n_dp)
        feed = {"data": jnp.asarray(rs.randn(batch, 3, image, image)
                                    .astype(np.float32)),
                "label": jnp.asarray(rs.randint(0, 1000, size=(batch,)))}
        rng = jax.random.PRNGKey(1)
        lowered = ts.lowerable.lower(params, state, feed, rng)
        census = collective_census_stablehlo(lowered.as_text())
        sched = plan.collective_schedule(ts.arena, net, comm=comm)
        p, s = params, state
        walls = []
        for i in range(max(1, args.iters) + 1):   # first call compiles
            t0 = _t.perf_counter()
            p, s, m = ts.step(p, s, feed, jax.random.fold_in(rng, i))
            jax.block_until_ready(m["loss"])
            walls.append(_t.perf_counter() - t0)
        row = {"mesh": spec, "plan": plan.describe(),
               "step_ms": round(min(walls[1:]) * 1e3, 2),
               "images_per_s": round(batch / min(walls[1:]), 1),
               "lowered_census": census,
               "planned_counts": sched["counts"],
               "census_matches_plan": census == sched["counts"]}
        if arm == "fsdp2":
            # persistent per-device param+grad+momentum bytes, sharded-
            # state layout vs the replicated tree (the ZeRO footprint)
            ts_sh = build_spmd_train_step(net, sp, mesh, plan, comm,
                                          donate=False,
                                          sharded_state=True)
            st = shard_train_state(params, state, ts_sh.arena, mesh, plan)
            shard_bytes = sum(
                sh.data.nbytes
                for arr in (st.flat_w, st.flat_h)
                for sh in arr.addressable_shards[:1])
            full_bytes = 2 * 4 * ts_sh.arena.total
            row["arena_state_bytes_per_device"] = shard_bytes
            row["arena_state_bytes_replicated"] = full_bytes
            row["arena_state_fraction"] = round(
                shard_bytes / full_bytes, 4)
        doc["alexnet"][arm] = row
        print(f"[mesh] alexnet/{arm}: {row['step_ms']} ms, census "
              f"{census} (plan match: {row['census_matches_plan']})",
              file=sys.stderr, flush=True)

    # GPT-small dp2 x tp4: the comm bill already on record
    try:
        from poseidon_tpu import config as pconfig
        from poseidon_tpu.models.transformer import (
            build_dp_tp_train_step, gpt_small_config, init_params,
            to_tp_layout)
        from poseidon_tpu.parallel import make_mesh
        from poseidon_tpu.runtime.hlo_comm import (measured_comm_summary,
                                                   parse_collectives)
        from poseidon_tpu.solvers.updates import init_state
        mesh8 = make_mesh(8, axes=("data", "model"), shape=(2, 4))
        seq = 1024 if on_accel else 128
        gbatch = 16 if on_accel else 4
        cfg_lm = gpt_small_config(max_seq=seq)
        with pconfig.policy_scope(compute_dtype=jnp.bfloat16):
            lp = to_tp_layout(init_params(cfg_lm, jax.random.PRNGKey(0)),
                              cfg_lm)
            step = build_dp_tp_train_step(cfg_lm, sp, mesh8, lp,
                                          donate=False)
            ls = init_state(lp)
            toks = jnp.asarray(rs.randint(0, cfg_lm.vocab_size,
                                          (gbatch, seq), dtype=np.int32))
            txt = step.lower(lp, ls, toks, toks,
                             jax.random.PRNGKey(1)).as_text()
        lm_census = collective_census_stablehlo(txt)
        lm_row: dict = {"mesh": "dp2,tp4", "seq": seq,
                        "global_batch": gbatch,
                        "lowered_census": lm_census}
        ref_path = os.path.join(_REPO, "evidence", "aot_tpu",
                                "lm_gpt_small.json")
        if os.path.exists(ref_path):
            with open(ref_path) as fh:
                ref = json.load(fh)
            lm_row["aot_reference_dp2_tp4"] = \
                ref.get("dp2_tp4", {}).get("collectives_by_kind")
        doc["gpt_small_dp2_tp4"] = lm_row
        print(f"[mesh] gpt_small dp2,tp4: {lm_census}", file=sys.stderr,
              flush=True)
    except Exception as e:  # noqa: BLE001 — LM leg is evidence, not gate
        doc["gpt_small_dp2_tp4"] = {"error": f"{type(e).__name__}: {e}"}

    try:
        os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
        tmp = args.out + ".tmp"
        with open(tmp, "w") as fh:
            json.dump(doc, fh, indent=1)
        os.replace(tmp, args.out)
    except OSError as e:
        print(f"[bench] mesh out write failed: {e}", file=sys.stderr,
              flush=True)

    fsdp = doc["alexnet"].get("fsdp2", {})
    all_match = all(r.get("census_matches_plan")
                    for r in doc["alexnet"].values())
    emit({
        "metric": "mesh_arena_state_fraction",
        "value": fsdp.get("arena_state_fraction", 0.0),
        "unit": "fraction_of_replicated",
        "vs_baseline": (0.5 / fsdp["arena_state_fraction"]
                        if fsdp.get("arena_state_fraction") else 0.0),
        "census_matches_plan": all_match,
        "cpu_proxy": not on_accel,
        "out": args.out,
        "alexnet": {a: {"step_ms": r.get("step_ms"),
                        "census": r.get("lowered_census")}
                    for a, r in doc["alexnet"].items()},
    })
    if not all_match:
        sys.exit(1)


# --------------------------------------------------------------------------- #
# tune mode: `python bench.py tune` — the measured autotuner + its A/B
# --------------------------------------------------------------------------- #

def tune_main(argv: list) -> None:
    """`bench.py tune`: run the measured autotuner (runtime/tuned_plan.py,
    ROADMAP item 5) for one model and report its composite A/B — the full
    train step under the TunedPlan's winners vs the same step under the
    built-in defaults. Emits the BENCH lines ``tuned_vs_default_speedup``
    (>= 1.0 by construction: the default config is always a candidate and
    a composite loss reverts the plan, on record) and
    ``tune_search_cost_s``, with the measured search space + any skipped
    knobs logged in full — no silent caps. Writes the plan to
    evidence/tuned_plans/<model>_<backend>.json; the canonical store copy
    (what train/serve auto-load) lands via compile_cache keying. CPU runs
    are labeled proxy; the same command tunes on the chip."""
    import argparse

    ap = argparse.ArgumentParser(prog="bench.py tune")
    ap.add_argument("--model", default="lenet",
                    choices=("lenet", "alexnet", "googlenet"))
    ap.add_argument("--full", action="store_true",
                    help="force the full search space (default: full on "
                         "accelerators, smoke on the CPU proxy)")
    ap.add_argument("--force", action="store_true",
                    help="re-measure even if a matching plan is persisted")
    ap.add_argument("--cache_dir", default="",
                    help="plan store override (default: the tuned_plan "
                         "store_dir resolution)")
    ap.add_argument("--windows", type=int, default=0)
    ap.add_argument("--iters", type=int, default=0)
    ap.add_argument("--out", default="")
    args = ap.parse_args(argv)

    def fail_tune(error: str, probe: dict | None = None) -> None:
        payload = {"metric": "tuned_vs_default_speedup", "value": 0.0,
                   "unit": "x", "vs_baseline": 0.0, "error": error}
        if probe:
            payload["probe"] = probe
        emit(payload)
        sys.exit(1)

    # a CPU run is labeled proxy; the plan it persists is keyed and
    # provenanced to the CPU backend, so it can never leak into a TPU
    # run's resolution
    probe = probe_backend()
    on_accel = probe["platform"] == "tpu"
    smoke = not (on_accel or args.full)

    try:
        from poseidon_tpu.runtime.tuned_plan import run_tune
        result = run_tune(args.model, smoke=smoke, force=args.force,
                          cache_dir=args.cache_dir or None,
                          windows=args.windows or None,
                          iters=args.iters or None)
    except Exception as e:  # noqa: BLE001 — one JSON line on every path
        import traceback
        fail_tune(f"{type(e).__name__}: {e} | "
                  f"{traceback.format_exc().strip().splitlines()[-1]}",
                  probe)
        return

    doc = result["doc"]
    ab = doc.get("ab", {})
    out_path = args.out or os.path.join(
        _REPO, "evidence", "tuned_plans",
        f"{doc['model']}_{doc['backend']}.json")
    try:
        os.makedirs(os.path.dirname(out_path), exist_ok=True)
        tmp = out_path + ".tmp"
        with open(tmp, "w") as f:
            json.dump({"source": result["source"],
                       "store_path": result["path"], **doc}, f, indent=1)
        os.replace(tmp, out_path)
    except OSError as e:
        print(f"[bench] tuned plan evidence write failed: {e}",
              file=sys.stderr, flush=True)

    speedup = float(ab.get("speedup", 1.0))
    emit({
        "metric": "tuned_vs_default_speedup",
        "value": round(speedup, 4),
        "unit": "x",
        "vs_baseline": round(speedup, 4),
        "cpu_proxy": not on_accel,
        "model": doc["model"],
        "backend": doc["backend"],
        "device_kind": doc["device_kind"],
        "n_devices": doc["n_devices"],
        "smoke_space": doc.get("smoke"),
        "memo_hit": result["source"] == "persisted",
        "knobs": doc["knobs"],
        "ab": ab,
        "search_space": doc.get("search_space"),
        "skipped_knobs": doc.get("skipped", {}),
        "plan_store_path": result["path"],
        "out": out_path,
    })
    emit({
        "metric": "tune_search_cost_s",
        # a memo-hit run measured nothing THIS run; the persisted doc's
        # cost is reported alongside so the line stays honest either way
        "value": (0.0 if result["source"] == "persisted"
                  else doc.get("search_cost_s", 0.0)),
        "unit": "s",
        "vs_baseline": 1.0,
        "cpu_proxy": not on_accel,
        "model": doc["model"],
        "memo_hit": result["source"] == "persisted",
        "persisted_search_cost_s": doc.get("search_cost_s"),
    })


# --------------------------------------------------------------------------- #
# memory mode: `python bench.py memory` — peak-bytes vs step-time under remat
# --------------------------------------------------------------------------- #

def memory_main(argv: list) -> None:
    """`bench.py memory`: the HBM budget planner's peak-bytes-vs-step-time
    sweep. For the CNN models the no-remat step's real
    ``memory_analysis()`` peak anchors a tight budget (``--budget_frac``
    of it); the planner's knapsack (core/remat.plan_remat) picks layers
    and the planned step is compiled and re-measured. Emits
    ``remat_peak_bytes_ratio`` (planned peak / no-remat peak),
    ``remat_step_overhead_frac`` (planned step ms / no-remat ms - 1) and
    ``max_batch_at_budget`` (largest doubled batch whose maximal-remat
    step still fits the no-remat base peak). For gpt_small the sweep is
    per checkpoint policy (none / dots_saveable / nothing_saveable) over
    the block stack instead of per layer. CPU runs are labeled proxy
    (gpt_small additionally drops to a proxy shape, recorded in the
    payload); the same command measures on the chip. Evidence lands in
    evidence/memory/<model>_<backend>.json."""
    import argparse

    ap = argparse.ArgumentParser(prog="bench.py memory")
    ap.add_argument("--model", default="googlenet",
                    choices=("alexnet", "googlenet", "gpt_small"))
    ap.add_argument("--batch", type=int, default=0,
                    help="per-device batch override (0 = mode default)")
    ap.add_argument("--budget_frac", type=float, default=0.6,
                    help="tight budget as a fraction of the no-remat peak")
    ap.add_argument("--full", action="store_true",
                    help="force full-size shapes (default: full on "
                         "accelerators, smoke on the CPU proxy)")
    ap.add_argument("--windows", type=int, default=3)
    ap.add_argument("--iters", type=int, default=3)
    ap.add_argument("--max_doublings", type=int, default=3)
    ap.add_argument("--out", default="")
    args = ap.parse_args(argv)

    def fail_mem(error: str, probe: dict | None = None) -> None:
        payload = {"metric": "remat_peak_bytes_ratio", "value": 0.0,
                   "unit": "x", "vs_baseline": 0.0, "error": error}
        if probe:
            payload["probe"] = probe
        emit(payload)
        sys.exit(1)

    probe = probe_backend()
    on_accel = probe["platform"] == "tpu"
    import jax
    smoke = not (on_accel or args.full)

    common = {"cpu_proxy": not on_accel, "model": args.model,
              "backend": jax.default_backend(),
              "device_kind": jax.devices()[0].device_kind,
              "smoke_shapes": smoke}
    doc: dict = dict(common)
    try:
        if args.model == "gpt_small":
            results = _memory_sweep_lm(args, smoke, doc)
        else:
            results = _memory_sweep_cnn(args, smoke, doc)
    except Exception as e:  # noqa: BLE001 — one JSON line on every path
        import traceback
        fail_mem(f"{type(e).__name__}: {e} | "
                 f"{traceback.format_exc().strip().splitlines()[-1]}",
                 probe)
        return

    out_path = args.out or os.path.join(
        _REPO, "evidence", "memory",
        f"{args.model}_{common['backend']}.json")
    try:
        os.makedirs(os.path.dirname(out_path), exist_ok=True)
        tmp = out_path + ".tmp"
        with open(tmp, "w") as f:
            json.dump(doc, f, indent=1)
        os.replace(tmp, out_path)
    except OSError as e:
        print(f"[bench] memory evidence write failed: {e}",
              file=sys.stderr, flush=True)

    for metric, value, unit, extras in results:
        emit({"metric": metric, "value": value, "unit": unit,
              "vs_baseline": value, **common, **extras, "out": out_path})


def _memory_sweep_cnn(args, smoke: bool, doc: dict) -> list:
    """CNN arm of `bench.py memory`: no-remat baseline vs the budget-
    planned step vs maximal remat, all real compiled-step measurements
    through the tune stage's arm builder."""
    from poseidon_tpu.core import remat as remat_mod
    from poseidon_tpu.core.net import Net
    from poseidon_tpu.runtime.attribution import layer_cost_table
    from poseidon_tpu.runtime.tuned_plan import (BUILTIN_DEFAULTS,
                                                 _build_step_arm,
                                                 _model_setup,
                                                 interleaved_min_ms)

    net_param, shapes = _model_setup(args.model, smoke)
    if args.batch:
        shapes["data"] = (args.batch,) + tuple(shapes["data"][1:])
        shapes["label"] = (args.batch,)
    arena = float(BUILTIN_DEFAULTS["arena_bucket_mb"])

    def make(remat: str, batch: int | None = None):
        s = dict(shapes)
        if batch is not None:
            s["data"] = (batch,) + tuple(shapes["data"][1:])
            s["label"] = (batch,)
        return _build_step_arm(net_param, s, "", arena, 1, "",
                               remat=remat, measure_peak=True)

    base = make("")
    peak0 = int(base.peak_bytes)
    if peak0 <= 0:
        raise RuntimeError("memory_analysis() reported no peak on this "
                           "backend; nothing to plan against")
    budget = int(peak0 * args.budget_frac)
    net = Net(net_param, phase="TRAIN", source_shapes=dict(shapes))
    plan = remat_mod.plan_remat(
        layer_cost_table(net), budget, peak0,
        candidates=remat_mod.remat_candidates(net), source="measured")
    planned = make(",".join(plan.layers))
    full = make("auto")

    arms = {"default": base, "planned": planned, "full_remat": full}
    raw = interleaved_min_ms(arms, windows=args.windows, iters=args.iters)
    ms = {k: raw[k] / arms[k].per_call_steps for k in raw}
    peaks = {k: int(arms[k].peak_bytes) for k in arms}

    # largest doubled batch the maximal-remat step fits in the no-remat
    # base peak — activations scale with batch, params don't, so this is
    # the planner's batch-autoscaling headroom in one number
    base_batch = int(shapes["data"][0])
    b = base_batch
    if int(full.peak_bytes) <= peak0:
        for _ in range(args.max_doublings):
            nxt = make("auto", batch=b * 2)
            if int(nxt.peak_bytes) > peak0:
                break
            b *= 2
    doc.update({
        "budget_frac": args.budget_frac, "budget_bytes": budget,
        "base_batch": base_batch, "max_doublings": args.max_doublings,
        "plan": plan.to_doc(),
        "arms": {k: {"peak_bytes": peaks[k], "step_ms": round(ms[k], 4)}
                 for k in arms},
        "max_batch_at_budget": b,
    })
    ratio = peaks["planned"] / peak0
    overhead = ms["planned"] / max(ms["default"], 1e-9) - 1.0
    detail = {"budget_frac": args.budget_frac,
              "planned_layers": len(plan.layers), "arms": doc["arms"]}
    return [
        ("remat_peak_bytes_ratio", round(ratio, 4), "x", detail),
        ("remat_step_overhead_frac", round(overhead, 4), "frac", detail),
        ("max_batch_at_budget", b, "rows/device",
         {"base_batch": base_batch, "max_doublings": args.max_doublings}),
    ]


def _memory_sweep_lm(args, smoke: bool, doc: dict) -> list:
    """LM arm of `bench.py memory`: gpt_small fwd+bwd per checkpoint
    policy. The policy enum replaces the CNN per-layer knapsack — block
    stacks trade whole tiers of saveables, not individual layers."""
    import jax
    import jax.numpy as jnp
    from poseidon_tpu.core import remat as remat_mod
    from poseidon_tpu.models.transformer import (TransformerConfig,
                                                 forward, gpt_small_config,
                                                 init_params, lm_loss)
    from poseidon_tpu.runtime.tuned_plan import interleaved_min_ms

    if smoke:
        # proxy shape: same block anatomy, CPU-sized — labeled in the doc
        cfg = TransformerConfig(vocab_size=2048, d_model=256, n_heads=8,
                                n_layers=6, d_ff=1024, max_seq=256,
                                remat=False)
        bsz, seq = 2, 256
    else:
        cfg = gpt_small_config(max_seq=1024, remat=False)
        bsz, seq = 8, 1024
    doc["shape"] = {"vocab": cfg.vocab_size, "d_model": cfg.d_model,
                    "n_layers": cfg.n_layers, "n_heads": cfg.n_heads,
                    "d_ff": cfg.d_ff, "batch": bsz, "seq": seq,
                    "proxy_shape": smoke}

    params = init_params(cfg, jax.random.PRNGKey(0))
    toks = jax.random.randint(jax.random.PRNGKey(1), (bsz, seq), 0,
                              cfg.vocab_size)
    tgts = jax.random.randint(jax.random.PRNGKey(2), (bsz, seq), 0,
                              cfg.vocab_size)

    def make(policy: str, b: jax.Array, t: jax.Array):
        def loss(p, bb, tt):
            return lm_loss(forward(p, cfg, bb, remat_policy=policy), tt)
        step = jax.jit(jax.value_and_grad(loss))
        peak = remat_mod.measured_peak_bytes(
            step.lower(params, b, t).compile())

        def run():
            l, g = step(params, b, t)
            jax.block_until_ready(l)

        run.per_call_steps = 1  # type: ignore
        run.peak_bytes = peak  # type: ignore
        return run

    policies = ("none", "dots_saveable", "nothing_saveable")
    arms = {p: make(p, toks, tgts) for p in policies}
    peaks = {p: int(arms[p].peak_bytes) for p in policies}
    if peaks["none"] <= 0:
        raise RuntimeError("memory_analysis() reported no peak on this "
                           "backend; nothing to plan against")
    raw = interleaved_min_ms(arms, windows=args.windows, iters=args.iters)
    ms = {p: raw[p] for p in raw}

    # batch autoscaling headroom: doubled batches under nothing_saveable
    # against the none-policy base peak
    b, budget = bsz, peaks["none"]
    for _ in range(args.max_doublings):
        k1, k2 = jax.random.split(jax.random.PRNGKey(3))
        nb = jax.random.randint(k1, (b * 2, seq), 0, cfg.vocab_size)
        nt = jax.random.randint(k2, (b * 2, seq), 0, cfg.vocab_size)
        probe = jax.jit(jax.value_and_grad(
            lambda p, bb, tt: lm_loss(
                forward(p, cfg, bb, remat_policy="nothing_saveable"), tt)))
        pk = remat_mod.measured_peak_bytes(
            probe.lower(params, nb, nt).compile())
        if pk > budget:
            break
        b *= 2
    doc.update({
        "arms": {p: {"peak_bytes": peaks[p], "step_ms": round(ms[p], 4)}
                 for p in policies},
        "max_batch_at_budget": b, "base_batch": bsz,
        "max_doublings": args.max_doublings,
    })
    ratio = peaks["nothing_saveable"] / peaks["none"]
    overhead = ms["nothing_saveable"] / max(ms["none"], 1e-9) - 1.0
    detail = {"arms": doc["arms"]}
    return [
        ("remat_peak_bytes_ratio", round(ratio, 4), "x", detail),
        ("remat_step_overhead_frac", round(overhead, 4), "frac", detail),
        ("max_batch_at_budget", b, "rows",
         {"base_batch": bsz, "max_doublings": args.max_doublings}),
    ]


# --------------------------------------------------------------------------- #
# comms mode: `python bench.py comms` — dense vs managed over a throttled link
# --------------------------------------------------------------------------- #

def comms_main(argv: list | None = None) -> None:
    """A/B the async-SSP DCN tier's managed communication (SSPAggr) over a
    deterministically throttled link: the same clock/push/gate/refresh
    cadence runs once with dense flushes and once with a bandwidth budget
    matching the link (magnitude-prioritized partial pushes, residual
    full-flush at every staleness boundary), through a FaultProxy
    ``throttle`` rule. Emits ``managed_comm_speedup`` (dense wall /
    managed wall, >1 = managed wins) and ``managed_comm_deferred_fraction``
    BENCH lines. Pure socket tier — no accelerator involved, so the run is
    labeled a CPU proxy either way; real DCN (cross-slice links) is not
    measured."""
    import argparse

    import numpy as np

    from poseidon_tpu.parallel.async_ssp import AsyncSSPClient, ParamService
    from poseidon_tpu.runtime.faults import FaultProxy, FaultRule

    ap = argparse.ArgumentParser(prog="bench.py comms")
    ap.add_argument("--param_kb", type=int, default=1024,
                    help="dense flush size in KiB (default 1 MiB)")
    ap.add_argument("--link_mbps", type=float, default=0.0,
                    help="throttled link rate in Mbit/s (both directions); "
                         "0 = auto: measure this host's unthrottled "
                         "push-pathway capacity and throttle to 1/16 of it, "
                         "so the operating point tracks the machine instead "
                         "of a hardcoded rate")
    ap.add_argument("--clocks", type=int, default=6)
    ap.add_argument("--staleness", type=int, default=2)
    ap.add_argument("--priority_frac", type=float, default=0.05)
    ap.add_argument("--wire_kb", type=int, default=64,
                    help="dense flush size in KiB for the wire-codec grid "
                         "arms (smaller than --param_kb: the grid sweeps "
                         "6 codec x dtype arms over the same link)")
    ap.add_argument("--wire_clocks", type=int, default=4)
    args = ap.parse_args(argv)

    side = int(max(16, (args.param_kb * 256) ** 0.5))  # side^2 f32 = kb
    params = {"fc": {"w": np.zeros((side, side), np.float32)}}

    # ---- wire-codec grid arm: push-dominant cadence, service-side sync -- #
    # (push() is asynchronous and a 1-worker gate never waits on its own
    # clock, so only the server's applied clock bounds the throttled
    # uplink transfer)
    from poseidon_tpu.proto.wire import (reset_wire_stats, set_wire_codec,
                                         wire_stats)

    def run_wire_arm(codec_on: bool, wd: str, link_mbps: float,
                     wire_side: int, clocks: int) -> dict:
        wparams = {"fc": {"w": np.zeros((wire_side, wire_side),
                                        np.float32)}}
        set_wire_codec(codec_on)
        reset_wire_stats()
        svc = ParamService(wparams, n_workers=1)
        proxy = FaultProxy(("127.0.0.1", svc.port))
        if link_mbps > 0:
            rate = link_mbps * 1e6 / 8.0
            # burst far below one frame: transfer time tracks frame bytes
            proxy.add_rule(FaultRule(action="throttle", rate_bps=rate,
                                     burst_bytes=8192))
        cli = AsyncSSPClient(0, proxy.addr, 0, n_workers=1, wire_dtype=wd)
        rng = np.random.RandomState(23)
        try:
            t0 = time.monotonic()
            for c in range(clocks):
                cli.push({"fc": {"w": rng.randn(wire_side, wire_side)
                                 .astype(np.float32) * 1e-3}})
                cli.gate(c + 1)
            deadline = time.monotonic() + 120.0
            while svc.clocks.get(0, -1) < clocks - 1:
                if time.monotonic() > deadline:
                    raise TimeoutError("wire arm: pushes not applied")
                time.sleep(0.0002)
            wall = time.monotonic() - t0
            counters = cli.comm_counters()
            ws = wire_stats()
        finally:
            cli.close()
            proxy.close()
            svc.close()
            set_wire_codec(True)
        logical = clocks * wire_side * wire_side * 4  # f32 update bytes
        sent = counters["bytes_sent"]
        saved = counters.get("wire_bytes_saved", 0.0)
        return {
            "wall_s": round(wall, 4),
            "logical_mb": round(logical / 1e6, 3),
            "bytes_sent": sent,
            "effective_mbps": round(logical * 8 / wall / 1e6, 3),
            "wire_compression_ratio": round((sent + saved) / sent, 3)
            if sent else 1.0,
            "wire_encode_ms": round(ws["encode_ns"] / 1e6, 3),
            "wire_decode_ms": round(ws["decode_ns"] / 1e6, 3),
            "codec_frames": ws["frames_encoded"],
            "pickle_frames": ws["pickle_frames_sent"],
            "transfer_ms": round(sent / (link_mbps * 1e6 / 8.0) * 1e3, 3)
            if link_mbps > 0 else 0.0,
        }

    # resolve the link: explicit flag, else 1/16 of the measured
    # unthrottled capacity of the very pathway the arms drive (client
    # encode -> loopback -> server decode+apply), so the throttle anchors
    # to the machine, never to a magic constant
    wire_side = int(max(16, (args.wire_kb * 256) ** 0.5))
    capacity_mbps = None
    link_mbps = args.link_mbps
    if link_mbps <= 0:
        probe = run_wire_arm(True, "", 0.0, wire_side, args.wire_clocks)
        capacity_mbps = probe["effective_mbps"]
        link_mbps = max(1.0, capacity_mbps / 16.0)
    rate_bps = link_mbps * 1e6 / 8.0

    def run_arm(managed: bool) -> dict:
        svc = ParamService(params, n_workers=1)
        proxy = FaultProxy(("127.0.0.1", svc.port))
        proxy.add_rule(FaultRule(action="throttle", rate_bps=rate_bps,
                                 burst_bytes=int(rate_bps / 8)))
        cli = AsyncSSPClient(
            0, proxy.addr, args.staleness, n_workers=1,
            budget_mbps=link_mbps if managed else None,
            priority_frac=args.priority_frac)
        rng = np.random.RandomState(17)
        t0 = time.monotonic()
        try:
            for c in range(args.clocks):
                delta = {"fc": {"w": rng.randn(side, side)
                                .astype(np.float32) * 1e-3}}
                cli.push(delta)
                cli.gate(c + 1)
                if (c + 1) % (args.staleness + 1) == 0:
                    cli.refresh()       # anchor pull at the SSP boundary
            cli.mark_done()
            wall = time.monotonic() - t0
            return {"wall_s": round(wall, 3),
                    "final_anchor_sum": float(svc.anchor["fc"]["w"].sum()),
                    **cli.comm_counters()}
        finally:
            cli.close()
            proxy.close()
            svc.close()

    dense = run_arm(managed=False)
    managed = run_arm(managed=True)
    speedup = (dense["wall_s"] / managed["wall_s"]
               if managed["wall_s"] else 0.0)
    cfg = {
        "cpu_proxy": True,  # socket tier on loopback; real DCN not measured
        "link_mbps": round(link_mbps, 3),
        "link_auto": args.link_mbps <= 0,
        "capacity_mbps": capacity_mbps,
        "param_kb": args.param_kb,
        "clocks": args.clocks,
        "staleness": args.staleness,
        "priority_frac": args.priority_frac,
    }
    emit({"metric": "managed_comm_speedup", "value": round(speedup, 3),
          "unit": "x", "vs_baseline": round(speedup, 3), **cfg,
          "dense": dense, "managed": managed})
    # the companion line carries the SAME run parameters so round-over-
    # round tracking can tell configurations apart; the fraction is
    # informational (its "good" direction depends on the budget config),
    # so vs_baseline rides the speedup the deferral bought
    emit({"metric": "managed_comm_deferred_fraction",
          "value": round(managed.get("deferred_fraction", 0.0), 4),
          "unit": "fraction", "vs_baseline": round(speedup, 3), **cfg})

    # ---- wire codec x dtype grid over the SAME throttled link ----------- #
    # every arm pushes the identical f32 update stream; "effective
    # throughput" is logical f32 bytes delivered per second, so a dtype
    # arm wins exactly by what compression + codec framing buy on the wire
    grid = [("pickle", ""), ("pickle", "bf16"), ("codec", ""),
            ("codec", "bf16"), ("codec", "f16"), ("codec", "int8")]
    wire = {}
    for framing, wd in grid:
        arm = f"{framing}-{wd or 'f32'}"
        wire[arm] = run_wire_arm(framing == "codec", wd, link_mbps,
                                 wire_side, args.wire_clocks)
    wcfg = {"cpu_proxy": True, "link_mbps": round(link_mbps, 3),
            "link_auto": args.link_mbps <= 0, "capacity_mbps": capacity_mbps,
            "wire_kb": args.wire_kb, "wire_clocks": args.wire_clocks}
    base = wire["pickle-f32"]
    for arm, r in wire.items():
        ratio = round(r["effective_mbps"] / base["effective_mbps"], 3) \
            if base["effective_mbps"] else 0.0
        emit({"metric": "wire_encode_ms", "value": r["wire_encode_ms"],
              "unit": "ms", "vs_baseline": ratio, "arm": arm, **wcfg})
        emit({"metric": "wire_decode_ms", "value": r["wire_decode_ms"],
              "unit": "ms", "vs_baseline": ratio, "arm": arm, **wcfg})
        emit({"metric": "wire_compression_ratio",
              "value": r["wire_compression_ratio"], "unit": "x",
              "vs_baseline": ratio, "arm": arm, **wcfg})
    # the acceptance pair: codec+bf16 effective throughput over the
    # pickle/f32 dense path on the same link, and the codec's own
    # (de)serialization cost as a fraction of throttled transfer time
    best = wire["codec-bf16"]
    speed = (best["effective_mbps"] / base["effective_mbps"]
             if base["effective_mbps"] else 0.0)
    overhead = ((best["wire_encode_ms"] + best["wire_decode_ms"])
                / best["transfer_ms"] if best["transfer_ms"] else 0.0)
    emit({"metric": "wire_codec_speedup", "value": round(speed, 3),
          "unit": "x", "vs_baseline": round(speed, 3), **wcfg,
          "arms": wire})
    emit({"metric": "wire_codec_overhead_fraction",
          "value": round(overhead, 4), "unit": "fraction",
          "vs_baseline": round(speed, 3), **wcfg})


def fabric_main(argv: list | None = None) -> None:
    """A/B the two-tier fabric's DCN bill against the flat per-process
    tier on the same loopback service: the SAME global cadence (every
    participant gates, pushes, and the clock barrier waits for the apply)
    runs once with one DCN client per PROCESS and once with one client
    per SLICE leader (parallel/fabric.SliceWorker, ledger mirroring on) —
    the fabric's thesis is that intra-slice aggregation rides ICI, so the
    DCN tier carries slices, not processes. Emits ``fabric_vs_flat_step_ms``
    (fabric per-clock wall; vs_baseline = flat/fabric, >1 = fabric wins)
    and ``fabric_chaos_recovery_s`` (leader links severed mid-run ->
    failover -> next push applied). Pure socket tier on loopback, so both
    lines are CPU proxies; real DCN is not measured."""
    import argparse

    import numpy as np

    from poseidon_tpu.parallel.async_ssp import AsyncSSPClient, ParamService
    from poseidon_tpu.parallel.fabric import SliceWorker
    from poseidon_tpu.runtime.faults import FaultProxy

    ap = argparse.ArgumentParser(prog="bench.py fabric")
    ap.add_argument("--param_kb", type=int, default=256,
                    help="dense flush size in KiB per DCN participant")
    ap.add_argument("--clocks", type=int, default=8)
    ap.add_argument("--staleness", type=int, default=1)
    ap.add_argument("--slices", type=int, default=2)
    ap.add_argument("--procs_per_slice", type=int, default=2)
    args = ap.parse_args(argv)

    side = int(max(16, (args.param_kb * 256) ** 0.5))
    params = {"fc": {"w": np.zeros((side, side), np.float32)}}
    opts = dict(heartbeat_s=0.1, backoff_base_s=0.01, backoff_cap_s=0.1)

    def _delta(rng):
        return {"fc": {"w": rng.randn(side, side).astype(np.float32)
                       * 1e-3}}

    def _drain(svc, clock, n, deadline_s=60.0):
        t0 = time.monotonic()
        while any(svc.clocks[w] < clock for w in range(n)):
            if time.monotonic() - t0 > deadline_s:
                raise RuntimeError(f"clock {clock} never applied")
            time.sleep(0.001)

    def run_flat() -> float:
        n = args.slices * args.procs_per_slice
        svc = ParamService(params, n_workers=n)
        clients = [AsyncSSPClient(w, ("127.0.0.1", svc.port),
                                  args.staleness, n_workers=n, **opts)
                   for w in range(n)]
        rng = np.random.RandomState(7)
        try:
            t0 = time.monotonic()
            for c in range(args.clocks):
                for cli in clients:
                    cli.gate(c)
                    cli.push(_delta(rng))
                _drain(svc, c, n)
            wall = time.monotonic() - t0
            for cli in clients:
                cli.mark_done()
            return wall
        finally:
            for cli in clients:
                cli.close()
            svc.close()

    def run_fabric() -> float:
        svc = ParamService(params, n_workers=args.slices)
        workers = [SliceWorker(s, list(range(args.procs_per_slice)),
                               ("127.0.0.1", svc.port), args.staleness,
                               n_slices=args.slices, client_opts=opts)
                   for s in range(args.slices)]
        rng = np.random.RandomState(7)
        try:
            t0 = time.monotonic()
            for c in range(args.clocks):
                for w in workers:
                    w.gate(c)
                    w.push(_delta(rng))
                _drain(svc, c, args.slices)
            wall = time.monotonic() - t0
            for w in workers:
                w.mark_done()
            return wall
        finally:
            for w in workers:
                w.close()
            svc.close()

    def run_chaos() -> float:
        """Leader links severed mid-run; the clock runs from the cut to
        the successor's next push being APPLIED — reconnect, floor
        re-derivation, oplog replay and the fresh flush, end to end."""
        svc = ParamService(params, n_workers=1, liveness_timeout_s=0.0)
        proxy = FaultProxy(("127.0.0.1", svc.port))
        w = SliceWorker(0, [0, 1], proxy.addr, args.staleness,
                        n_slices=1,
                        client_opts=dict(opts, reconnect_deadline_s=10.0))
        rng = np.random.RandomState(7)
        try:
            w.push(_delta(rng))
            _drain(svc, 0, 1)
            t0 = time.monotonic()
            proxy.sever_group({0})
            if w.fail_member(0) != "failover":
                raise RuntimeError("leader kill did not fail over")
            w.push(_delta(rng))
            _drain(svc, 1, 1)
            recovery = time.monotonic() - t0
            w.mark_done()
            return recovery
        finally:
            w.close()
            proxy.close()
            svc.close()

    flat_wall = run_flat()
    fabric_wall = run_fabric()
    recovery_s = run_chaos()
    speedup = flat_wall / fabric_wall if fabric_wall else 0.0
    cfg = {
        "cpu_proxy": True,  # loopback socket tier; real DCN not measured
        "param_kb": args.param_kb,
        "clocks": args.clocks,
        "staleness": args.staleness,
        "slices": args.slices,
        "procs_per_slice": args.procs_per_slice,
    }
    emit({"metric": "fabric_vs_flat_step_ms",
          "value": round(fabric_wall / args.clocks * 1e3, 3),
          "unit": "ms", "vs_baseline": round(speedup, 3), **cfg,
          "flat_step_ms": round(flat_wall / args.clocks * 1e3, 3)})
    # recovery is informational (no baseline exists for it yet), so
    # vs_baseline rides the step A/B the slice-granular tier bought
    emit({"metric": "fabric_chaos_recovery_s",
          "value": round(recovery_s, 3), "unit": "s",
          "vs_baseline": round(speedup, 3), **cfg})


if __name__ == "__main__":
    if len(sys.argv) > 1 and sys.argv[1] == "serving":
        serving_main()
    elif len(sys.argv) > 1 and sys.argv[1] == "serving_llm":
        serving_llm_main(sys.argv[2:])
    elif len(sys.argv) > 1 and sys.argv[1] == "attribution":
        attribution_main(sys.argv[2:])
    elif len(sys.argv) > 1 and sys.argv[1] == "mesh":
        mesh_main(sys.argv[2:])
    elif len(sys.argv) > 1 and sys.argv[1] == "comms":
        comms_main(sys.argv[2:])
    elif len(sys.argv) > 1 and sys.argv[1] == "fabric":
        fabric_main(sys.argv[2:])
    elif len(sys.argv) > 1 and sys.argv[1] == "tune":
        tune_main(sys.argv[2:])
    elif len(sys.argv) > 1 and sys.argv[1] == "memory":
        memory_main(sys.argv[2:])
    else:
        main()
