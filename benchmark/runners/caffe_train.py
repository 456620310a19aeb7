"""Runner: one Caffe-prototxt training job, driven through the program's own
``train`` command.

What is taken from the program (the benchmark's contact surface, listed in
PERF.md): ``config.enable_tpu_async_collectives``; ``runtime.cli.main`` up to
the ``Engine`` it builds; ``Engine.train / close / params / state / rng /
stats / metrics / max_in_flight / train_step.batch_sharding /
train_net.export_weights`` and — for the ``resident`` feed only — the private
``Engine._dispatch_train_step``; ``runtime.spans.recorder``;
``proto.messages.load_net`` + ``core.net.Net.apply`` for the TEST-phase
forward of the reference check; and the dataset writers datagen.py names.
Everything else — inputs, clocks, FLOPs, the reference, the trace reduction —
is the benchmark's own.
"""

from __future__ import annotations

import collections
import importlib
import math
import os
import re
import shutil
import time

import caffe_proto
import datagen
import device as device_mod
import device_trace
import flops

ALIGN = "benchmark_align"     # one host event on both clocks, see trace_window


# --------------------------------------------------------------------------- #
# the job's files: cut copies of the configuration's prototxts
# --------------------------------------------------------------------------- #

def cut_fields(text: str, set_: dict, drop=()) -> str:
    """Top-level ``key: value`` edits of a solver prototxt."""
    for key in drop:
        text = re.sub(rf"(?m)^{key}:.*\n", "", text)
    for key, val in set_.items():
        if isinstance(val, str) and val not in ("true", "false"):
            val = f'"{val}"'
        text, n = re.subn(rf"(?m)^{key}:.*$", f"{key}: {val}", text)
        if not n:
            text += f"{key}: {val}\n"
    return text


def tiny_net(text: str, cut: dict, classes: int) -> str:
    """--cpu-tiny only: the same layers with widths, crop and batch cut to
    what a CPU test can run; the class count stays, so the first-loss check
    runs unchanged."""
    def width(m):
        n = int(m[1])
        return f"num_output: {n if n == classes else max(4, n // cut['width_divisor'])}"
    text = re.sub(r"num_output: (\d+)", width, text)
    if "crop_size" in cut:
        text = re.sub(r"crop_size: \d+", f"crop_size: {cut['crop_size']}",
                      text)
    return text


def write_job_files(job: dict, work: str, data: dict, batch: int):
    cfg, traffic = job["config"], job["traffic"]
    with open(os.path.join(job["bench_dir"], cfg["net"])) as f:
        net = f.read()
    net = net.replace(cfg["paths"]["train_source"], data["train"])
    net = net.replace(cfg["paths"]["mean_file"], data["mean"])
    net = re.sub(r"batch_size: \d+", f"batch_size: {batch}", net)
    if job["tiny"]:
        net = tiny_net(net, cfg["cpu_tiny"], cfg["classes"])
    net_path = os.path.join(work, "net.prototxt")
    with open(net_path, "w") as f:
        f.write(net)
    with open(os.path.join(job["bench_dir"], cfg["solver"])) as f:
        solver = cut_fields(
            f.read(),
            {"net": net_path, "display": traffic["display"], "snapshot": 0,
             "snapshot_after_train": "false", "snapshot_prefix": "snap/x",
             "random_seed": job["seed"]},
            drop=("test_iter", "test_interval", "test_initialization"))
    solver_path = os.path.join(work, "solver.prototxt")
    with open(solver_path, "w") as f:
        f.write(solver)
    return net_path, solver_path


# --------------------------------------------------------------------------- #
# the system under test
# --------------------------------------------------------------------------- #

def build_engine(argv: list):
    """Run the user's ``train`` command up to the point where it would call
    ``Engine.train``, and hand back the Engine it built: whatever
    ``cmd_train`` does before training (cache, policy, plan, restore) is
    done by ``cmd_train`` itself, today and after any later PR."""
    from poseidon_tpu.runtime import cli
    from poseidon_tpu.runtime.engine import Engine
    built = []
    real_train, real_close = Engine.train, Engine.close
    Engine.train = lambda self, *a, **k: built.append(self) or {}
    Engine.close = lambda self: None
    try:
        rc = cli.main(argv)
    finally:
        Engine.train, Engine.close = real_train, real_close
    if rc != 0 or len(built) != 1:
        raise RuntimeError(f"`{' '.join(argv)}` returned {rc} and built "
                           f"{len(built)} engines")
    return built[0]


class LmdbFeed:
    """The whole user path: every step is ``Engine.train``'s own. The
    benchmark's clock is also read at every display boundary, where the
    Engine has just read that window's losses back from the device and
    hands the row to its metrics table (for stall_share)."""

    def __init__(self, eng):
        self.eng = eng
        self.it = eng.iteration()
        self.stamps = []
        flush_row = eng.metrics.flush_row

        def stamped(iteration):
            self.stamps.append(time.perf_counter())
            return flush_row(iteration)

        eng.metrics.flush_row = stamped

    def steps(self, n: int) -> dict:
        from poseidon_tpu.runtime.engine import TrainingDivergedError
        eng, rows = self.eng, len(self.eng.metrics.rows)
        done = n
        self.stamps = [time.perf_counter()]
        try:
            last = eng.train(max_iter=self.it + n)
        except TrainingDivergedError as e:
            done, last = max(0, e.iteration - self.it), {}
        self.step_s = (time.perf_counter() - self.stamps[0]) / n
        self.it += n
        losses = [r["loss"] for r in eng.metrics.rows[rows:]]
        if "loss" in last:
            losses.append(last["loss"])
        return {"attempted": n, "failed": n - done, "losses": losses,
                "stamps": self.stamps}


class ResidentFeed:
    """The same Engine's resolved step executable on batches that already
    live on the device: no reader, no transform, no host-to-device copy, no
    display or artifact work. The step donates its batch on a TPU, so each
    step gets a device-to-device copy of one of the master batches (for
    AlexNet at 512 images 0.32 GB, ~1 ms of a ~0.5 s step at HBM speed)
    rather than a second program compiled without donation."""

    def __init__(self, eng, shapes: dict, classes: int, masters: int,
                 display: int, seed: int):
        import jax
        import jax.numpy as jnp
        from poseidon_tpu.runtime.spans import recorder
        self.eng, self.display, self.span = eng, display, recorder.span
        self.it = eng.iteration()
        sharding = eng.train_step.batch_sharding
        shard = {k: sharding for k in shapes}

        def make(key):
            out = {}
            for i, (name, shape) in enumerate(sorted(shapes.items())):
                k = jax.random.fold_in(key, i)
                if len(shape) == 1:     # the label top
                    out[name] = jax.random.randint(
                        k, shape, 0, classes, jnp.int32)
                else:                   # mean-subtracted pixels
                    out[name] = 64.0 * jax.random.normal(k, shape,
                                                         jnp.float32)
            return out

        make = jax.jit(make, out_shardings=shard)
        self.masters = [make(jax.random.PRNGKey(seed * 1000 + i))
                        for i in range(masters)]
        self.copy = jax.jit(lambda b: {k: jnp.copy(v) for k, v in b.items()},
                            out_shardings=shard)
        jax.block_until_ready(self.copy(self.masters[0]))

    def steps(self, n: int) -> dict:
        import jax
        eng, span = self.eng, self.span
        pending = collections.deque()
        losses = []
        stamps = [time.perf_counter()]

        def drain(keep: int) -> None:
            while len(pending) > keep:
                losses.append(float(pending.popleft()))

        for i in range(n):
            it = self.it + i
            with span("resident_copy", "input", {"iter": it}):
                batch = self.copy(self.masters[i % len(self.masters)])
            with span("dispatch", "step", {"iter": it}):
                eng.params, eng.state, m = eng._dispatch_train_step(
                    batch, jax.random.fold_in(eng.rng, it))
            pending.append(m["loss"])
            # the Engine's own bound: on return from its window at most
            # max_in_flight - 1 dispatches are un-materialized
            with span("dispatch_window", "step", {"iter": it}):
                drain(eng.max_in_flight - 1)
            if (i + 1) % self.display == 0 or i + 1 == n:
                with span("hard_sync", "sync", {"boundary": "display"}):
                    drain(0)
                stamps.append(time.perf_counter())
        self.it += n
        self.step_s = (stamps[-1] - stamps[0]) / n
        bad = sum(1 for v in losses if not math.isfinite(v))
        return {"attempted": n, "failed": bad, "losses": losses,
                "stamps": stamps}


# --------------------------------------------------------------------------- #
# windows
# --------------------------------------------------------------------------- #

class CompileCounter:
    """Programs compiled or fetched from the compilation cache while armed
    (jax's own monitoring event around every backend compile)."""
    EVENT = "/jax/core/compile/backend_compile_duration"

    def __enter__(self):
        import jax.monitoring
        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)
        return self

    def _on(self, event: str, duration: float, **_) -> None:
        if event == self.EVENT:
            self.count += 1

    def __exit__(self, *exc):
        import jax.monitoring
        jax.monitoring.unregister_event_duration_listener(self._on)
        return False


# How long a cell runs under the profiler BEFORE the steps that count, in whole
# multiples of its ``trace_steps`` and in the same ``Engine.train`` call. A
# process's first profiler session stalls every host thread at once for
# 0.18-0.30 s somewhere in its first 0.7 s (PERF.md, section 5,
# ``alexnet.lmdb``, has the readings): a cell whose steps are shorter than
# that reads the stall as its own idle time unless it has passed.
LEAD_SECONDS = 1.0


def trace_window(feed, steps: int, platform: str, trace_dir: str) -> dict:
    """A short window of its own under the profiler: ONE call of the feed
    for the uncounted lead steps (``LEAD_SECONDS`` at the pace of the feed's
    last call, the measured window's) and the ``steps`` that every reader
    sees, so that the counted steps run where the measured window's do, deep
    inside a call, with the loop ahead of the device. The span recorder's
    clock (perf_counter) and the profiler's meet in one event: an instant
    span and a ``TraceAnnotation`` of the same name, taken back to back. The
    trace is cut where the chip began the first counted step
    (``device_trace.since_step``), the recorder's spans with it
    (``rows_from``: where the counted steps' display rows start in
    ``Engine.metrics.rows``)."""
    import jax.profiler
    from poseidon_tpu.runtime.spans import recorder
    shutil.rmtree(trace_dir, ignore_errors=True)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0     # the Python tracer's events are not read
    opts.host_tracer_level = 1
    recorder.clear()
    rows = feed.eng.metrics.rows
    rows_before = len(rows)
    lead = steps * max(1, math.ceil(LEAD_SECONDS / (steps * feed.step_s)))
    jax.profiler.start_trace(trace_dir, profiler_options=opts)
    try:
        recorder.instant(ALIGN)
        with jax.profiler.TraceAnnotation(ALIGN):
            pass
        feed.steps(lead + steps)
    finally:
        jax.profiler.stop_trace()
    trace = device_trace.load(device_trace.newest_xplane(trace_dir),
                              platform, ALIGN)
    events = recorder.trace_events()
    mark = next((e["ts"] for e in events if e["name"] == ALIGN), None)
    align_ns = trace.pop("align_ns")
    counted, opens_ns = device_trace.since_step(trace, lead + steps, lead)
    spans = []
    if mark is not None and align_ns is not None:
        ends_by = (opens_ns - align_ns) / 1e3 + mark     # recorder's clock, us
        spans = [{"name": e["name"], "dur_ns": e["dur"] * 1e3,
                  "start_ns": align_ns + (e["ts"] - mark) * 1e3}
                 for e in events
                 if e.get("ph") == "X" and e["ts"] + e["dur"] >= ends_by]
    # the lead steps' display rows are the first ones of the call
    lead_rows = (len(rows) - rows_before) * lead // (lead + steps)
    return dict(counted, steps=steps, lead_steps=lead, spans=spans,
                rows_from=rows_before + lead_rows)


# --------------------------------------------------------------------------- #
# correctness
# --------------------------------------------------------------------------- #

def reference_check(job: dict, eng, net_path: str, net_node, n_images: int):
    """The program's TEST-phase forward against the configuration's plain
    reference, on ``n_images`` seeded images and the Engine's current
    weights. Returns the comparison's numbers and whether they pass."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from poseidon_tpu.core.net import Net
    from poseidon_tpu.proto.messages import load_net

    ref_mod = importlib.import_module(f"reference.{job['config']['reference']}")
    data = caffe_proto.data_layer(net_node, "TEST")
    crop = data["crop_size"]
    image_top, label_top = data["tops"]
    shapes = {image_top: (n_images, 3, crop, crop), label_top: (n_images,)}
    key = jax.random.PRNGKey(job["seed"] + 7919)
    inputs = {
        image_top: 64.0 * jax.random.normal(key, shapes[image_top],
                                            jnp.float32),
        label_top: jax.random.randint(jax.random.fold_in(key, 1),
                                      shapes[label_top], 0,
                                      job["config"]["classes"], jnp.int32)}
    records = caffe_proto.infer(caffe_proto.phase_layers(net_node, "TEST"),
                                shapes)
    fed = sorted({r["bottoms"][0] for r in records
                  if r["type"] == "SOFTMAXLOSS"})

    test_net = Net(load_net(net_path), "TEST", source_shapes=shapes)

    def program(params, x):
        out = test_net.apply(params, x, train=False, keep_blobs=True)
        return {"loss": out.loss,
                "predictions": {k: out.blobs[k] for k in fed}}

    # single-device arrays for both sides, whatever mesh the Engine trains on
    dev = jax.local_devices()[0]
    host = jax.tree.map(np.asarray, eng.params)
    params = jax.device_put(host, dev)
    weights = eng.train_net.export_weights(host)   # Caffe's blob layout
    inputs = jax.device_put(inputs, dev)
    got = jax.device_get(jax.jit(program)(params, inputs))
    want = jax.device_get(jax.jit(
        lambda w, x: ref_mod.forward(records, w, x))(weights, inputs))

    tol = ref_mod.TOLERANCE[job["traffic"]["precision"]]
    facts = {"loss_program": float(got["loss"]),
             "loss_reference": float(want["loss"]), "prediction_rel_l2": {}}
    ok = math.isfinite(facts["loss_program"]) and abs(
        facts["loss_program"] - facts["loss_reference"]) <= \
        tol["loss_rel"] * abs(facts["loss_reference"])
    for k in fed:
        a = np.asarray(got["predictions"][k], np.float64)
        b = np.asarray(want["predictions"][k], np.float64)
        rel = float(np.linalg.norm(a - b) / max(np.linalg.norm(b), 1e-30))
        facts["prediction_rel_l2"][k] = rel
        ok = ok and rel <= tol["prediction_rel_l2"]
    return facts, ok


# --------------------------------------------------------------------------- #
# the run
# --------------------------------------------------------------------------- #

def run(job: dict) -> dict:
    clock = time.perf_counter
    cfg, traffic, cell = job["config"], job["traffic"], job["cell"]
    chips, tiny = int(cell["chips"]), job["tiny"]
    batch = cfg["cpu_tiny"]["batch_per_chip"] if tiny \
        else int(cell["batch_per_chip"])
    display = int(traffic["display"])

    # libtpu reads the async-collective flags when the backend starts
    from poseidon_tpu import config as program_config
    program_config.enable_tpu_async_collectives()
    dev = device_mod.require(chips, cpu_rehearsal=tiny)
    peak = None if tiny else device_mod.peaks(dev["kind"])["bf16_flops_per_s"]

    work = os.path.join(job["work_dir"], cell["name"])
    os.makedirs(work, exist_ok=True)
    side = cfg["cpu_tiny"]["record_side"] if tiny else cfg["record"]["side"]
    data = datagen.build_lmdb(
        os.path.join(work, "data"), seed=job["seed"], side=side,
        records=int(traffic["lmdb_batches"]) * batch * chips,
        channels=cfg["record"]["channels"], classes=cfg["classes"])
    net_path, solver_path = write_job_files(job, work, data, batch)
    with open(net_path) as f:
        net_node = caffe_proto.parse(f.read())

    # the benchmark's own reading of the job: input shapes, required FLOPs
    train_data = caffe_proto.data_layer(net_node, "TRAIN")
    crop = train_data["crop_size"]
    image_top, label_top = train_data["tops"]
    train_records = caffe_proto.infer(
        caffe_proto.phase_layers(net_node, "TRAIN"),
        {image_top: (batch, cfg["record"]["channels"], crop, crop),
         label_top: (batch,)})
    flops_per_image = sum(flops.required_flops_per_image(
        train_records, train_data["tops"]).values())
    loss_weight = sum(r["loss_weight"] for r in train_records
                      if r["type"] == "SOFTMAXLOSS")

    out_dir = os.path.join(work, "out")
    argv = [a.format(solver=solver_path, output_dir=out_dir)
            for a in traffic["argv"]]
    eng = build_engine(argv)
    try:
        from poseidon_tpu.runtime.spans import recorder
        # warm-up, all of it set-up: the first step compiles or loads the
        # step; the next ones reach a display boundary; a last clean display
        # window gives the step time that sizes the measured window
        first_loss = eng.train(max_iter=1).get("loss", float("nan"))
        eng.train(max_iter=display)
        t = clock()
        eng.train(max_iter=2 * display)
        step_s = (clock() - t) / display
        if traffic["feed"] == "resident":
            g = batch * chips
            feed = ResidentFeed(
                eng, {image_top: (g, cfg["record"]["channels"], crop, crop),
                      label_top: (g,)},
                cfg["classes"], int(traffic["masters"]), display,
                job["seed"])
            t = clock()
            feed.steps(display)
            step_s = (clock() - t) / display
        else:
            feed = LmdbFeed(eng)
        n_steps = display * max(1, round(job["seconds"] / (display * step_s)))
        if job["trace"]:
            recorder.enable()
            recorder.clear()

        # ---- the measured window: opens and closes on a hard sync ------- #
        with CompileCounter() as compiles:
            t0 = clock()
            window = feed.steps(n_steps)
            seconds = clock() - t0
        setup_s = t0 - job["t_start"]
        window_spans = recorder.trace_events() if job["trace"] else []
        after = eng.stats.snapshot()
        memory_peak = device_mod.memory_peak_bytes()

        trace = None
        if job["trace"]:
            trace = trace_window(feed, int(traffic["trace_steps"]),
                                 dev["platform"],
                                 os.path.join(work, "trace"))
            recorder.disable()
            if job.get("keep_trace"):
                shutil.copytree(os.path.join(work, "trace"),
                                job["keep_trace"], dirs_exist_ok=True)
            shutil.rmtree(os.path.join(work, "trace"), ignore_errors=True)

        # ---- correct? (outside every timed region) ----------------------- #
        ref_facts, ref_ok = reference_check(
            job, eng, net_path, net_node,
            cfg["cpu_tiny"]["reference_images"] if tiny
            else int(cfg["reference_images"]))
    finally:
        eng.close()

    place = after["sections"].get("placement", {})
    # labels are uniform and fresh weights know nothing of them, so the
    # first loss cannot sit under ln(classes) x the loss weights; how far
    # over it the fillers may put it is the configuration's to say
    want_first = math.log(cfg["classes"]) * loss_weight
    low, high = cfg["first_loss_over_uniform"]
    checks = {
        "losses_finite": bool(window["losses"]) and all(
            math.isfinite(v) for v in window["losses"]),
        "first_loss": low * want_first <= first_loss <= high * want_first,
        "no_compile_in_window": compiles.count == 0,
        "batch_on_every_chip": len(set(str(place.get(
            "batch_shard_devices", "")).split(","))) == chips
        and int(place.get("param_devices", 0)) == chips,
        "reference": ref_ok,
        "no_failed_step": window["failed"] == 0,
    }
    # images of all steps COMPLETED in the window over the window's seconds:
    # it opened and closed on a hard sync, never on an enqueue. (A median
    # over the display intervals was tried and is worse: the lmdb loop runs
    # in two paces, 1.99 s or 2.035 s per display interval, and a median
    # flips between them; PR 22, PERF.md section 6.)
    images_per_s_per_chip = (window["attempted"] - window["failed"]) \
        * batch / seconds
    intervals = [b - a for a, b in zip(window["stamps"],
                                       window["stamps"][1:])]
    end_to_end = {"images_per_s_per_chip": images_per_s_per_chip,
                  "setup_s": setup_s}
    if peak:
        end_to_end["mfu_required"] = \
            100.0 * images_per_s_per_chip * flops_per_image / peak
    facts = {"first_loss": first_loss, "first_loss_uniform": want_first,
             "window_losses": window["losses"][-3:], "reference": ref_facts,
             "checks": checks, "steps": window["attempted"],
             "window_s": seconds, "step_s_warmup": step_s,
             "display_intervals_s": intervals,
             "batch_per_chip": batch, "flops_per_image": flops_per_image,
             "lmdb_built": data["built"],
             "compiled_step": after["sections"].get("compiled_step", {}),
             "placement": place}
    return {
        "correct": all(checks.values()),
        "attempted": window["attempted"], "failed": window["failed"],
        "device": dict(dev, memory_peak_bytes=memory_peak),
        "end_to_end": end_to_end,
        "facts": facts,
        # what the per-layer readers (layer_metrics/*.py) reduce
        "layers": {"steps": window["attempted"], "window_s": seconds,
                   "setup_s": setup_s,
                   "batch_per_chip": batch,
                   "flops_per_image": flops_per_image,
                   "peak_flops_per_s": peak,
                   "compiles_in_window": compiles.count,
                   "display_intervals_s": intervals,
                   "spans": window_spans, "stats": after,
                   "memory_peak_bytes": memory_peak,
                   "trace": trace},
    }
