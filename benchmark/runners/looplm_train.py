"""Runner: one looped-LM training job (a prototxt whose stack of EMBED /
RMS_NORM / INNER_PRODUCT / ATTENTION / SILU_GATE layers is applied several
times with shared weights, SOFTMAX_NLL per pass, EXIT_LOSS over them),
driven through the program's own ``train`` command exactly as ``lm_train``
drives OLMoE's, whose pieces (and ``caffe_train``'s) it reuses: the token
file, ``build_engine``, ``LmdbFeed``, ``CompileCounter``, ``trace_window``.

What differs from ``lm_train`` is the model's: the keys read from its
config.json, the first-loss band (cross-entropy plus the exit distribution's
entropy bonus), the reference check (every pass's logits at the LAST
``reference_positions`` positions of one whole-length sequence against the
whole context; ``reference/ouro.py``), the exit masses the EXIT_LOSS layer
publishes per display, and no routing to check for dropped tokens.

The per-layer readers get the keys ``lm_train`` hands them, ONE SEQUENCE as
the sample; ``lm`` holds what this cell's readers add (``scopes``: the
layer-name patterns of the FFN and of the exit heads; ``exit_mass``).
"""

from __future__ import annotations

import importlib
import math
import os
import re
import shutil
import sys
import time

import device as device_mod
import flops_looplm
import tokengen
from runners.caffe_train import (CompileCounter, LmdbFeed, build_engine,
                                 cut_fields, trace_window)
from runners.lm_train import document_mix

# the keys of the model's published config.json the benchmark computes from
MODEL_KEYS = ("hidden_size", "intermediate_size", "num_attention_heads",
              "num_key_value_heads", "num_hidden_layers", "total_ut_steps",
              "vocab_size", "rms_norm_eps", "rope_theta")
# --cpu-tiny only: where each cut size sits in the prototxt
TINY_FIELDS = {"hidden_size": ("num_output",),
               "vocab_size": ("num_output", "input_dim"),
               "num_attention_heads": ("num_heads",),
               "intermediate_size": ("num_output",)}
LAYER_TYPES = ("SILU_GATE", "SOFTMAX_NLL", "EXIT_LOSS")


def write_job_files(job: dict, work: str, source: str, batch: int,
                    model: dict):
    cfg, traffic = job["config"], job["traffic"]
    with open(os.path.join(job["bench_dir"], cfg["net"])) as f:
        net = f.read()
    net = net.replace(cfg["paths"]["train_source"], source)
    net = re.sub(r"batch_size: \d+", f"batch_size: {batch}", net)
    if job["tiny"]:
        for key, fields in TINY_FIELDS.items():
            for field in fields:
                net = re.sub(rf"\b{field}: {cfg[key]}\b",
                             f"{field}: {model[key]}", net)
    net_path = os.path.join(work, "net.prototxt")
    with open(net_path, "w") as f:
        f.write(net)
    with open(os.path.join(job["bench_dir"], cfg["solver"])) as f:
        solver = cut_fields(
            f.read(),
            {"net": net_path, "display": traffic["display"], "snapshot": 0,
             "snapshot_after_train": "false", "snapshot_prefix": "snap/x",
             "random_seed": job["seed"]})
    solver_path = os.path.join(work, "solver.prototxt")
    with open(solver_path, "w") as f:
        f.write(solver)
    return net_path, solver_path


def expected_first_loss(cfg: dict, model: dict) -> tuple:
    """(lowest, highest) first loss of fresh weights — the configuration's
    ``first_loss_why``: every pass's cross-entropy is ln V + var / 2 (the
    exit weights sum to 1), less ``entropy_weight`` x H(p) with H between 0
    and ln T."""
    var = cfg["init_std"] ** 2 * model["hidden_size"]    # of a logit
    ce = math.log(model["vocab_size"]) + var / 2
    bonus = cfg["assumed"]["entropy_weight"] \
        * math.log(model["total_ut_steps"])
    return ce - bonus, ce


def reference_check(job: dict, params: dict, net_path: str, model: dict,
                    seq: int):
    """The program's forward (the run's numeric policy) against the plain
    reference on ONE seeded whole-length sequence and the trained weights
    (``params``, still on the device): every pass's logits at the last
    ``reference_positions`` positions against the whole context (the
    reference's attention ``reference_positions`` queries at a time), and
    the loss over every position. Called with the Engine closed and its
    solver state dropped. Also what the reference gives with its matmul
    inputs rounded to ``reference_lower_precision``, the nearest precision
    below the run's: a fact, outside ``correct``, that has to lie outside
    the tolerance."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from poseidon_tpu.core.net import Net
    from poseidon_tpu.proto.messages import load_net

    cfg = job["config"]
    ref = importlib.import_module(f"reference.{cfg['reference']}")
    last = min(int(cfg["reference_positions"]), seq)
    made = tokengen.packed_sequences(job["seed"] + 7919, 1, seq,
                                     model["vocab_size"], document_mix(job))
    tokens, targets = jnp.asarray(made["data"]), jnp.asarray(made["label"])
    net = Net(load_net(net_path), "TRAIN",
              source_shapes={"tokens": (1, seq), "targets": (1, seq)})
    passes = model["total_ut_steps"]

    def program(p, tok, tgt):
        out = net.apply(p, {"tokens": tok, "targets": tgt}, train=False,
                        keep_blobs=True)
        return {"loss": out.loss, "logits": jnp.stack([
            out.blobs[f"p{t}_logits"][:, -last:]
            for t in range(1, passes + 1)])}

    def host(out):
        return {"loss": float(out["loss"]),
                "logits": np.asarray(out["logits"], np.float32)}

    got = host(jax.jit(program)(params, tokens, targets))
    # the same device arrays under the reference's names and blob order
    weights = {l.name: [params[l.name][p.name] for p in l.params]
               for l in net.layers if l.name in params}
    sizes = {k: model[k] for k in MODEL_KEYS}
    beta = cfg["assumed"]["entropy_weight"]

    def reference(w, tok, tgt, round_to=None):
        total, parts = ref.loss(sizes, w, tok, tgt, beta, last=last,
                                q_block=last, round_to=round_to)
        return {"loss": total, "logits": parts["logits"]}

    want = host(jax.jit(reference)(weights, tokens, targets))
    low = host(jax.jit(lambda *a: reference(
        *a, round_to=getattr(jnp, cfg["reference_lower_precision"])))(
            weights, tokens, targets))

    def rel(a, b):
        return [float(np.linalg.norm((x - y).astype(np.float64))
                      / max(np.linalg.norm(y.astype(np.float64)), 1e-30))
                for x, y in zip(a, b)]

    tol = (ref.TOLERANCE_TINY if job["tiny"] else ref.TOLERANCE)[
        job["traffic"]["precision"]]
    per_pass = rel(got["logits"], want["logits"])
    facts = {"loss_program": got["loss"], "loss_reference": want["loss"],
             "logits_rel_l2": max(per_pass), "logits_rel_l2_per_pass": per_pass,
             "lower_precision": cfg["reference_lower_precision"],
             "lower_precision_rel_l2": max(rel(low["logits"],
                                               want["logits"])),
             "lower_precision_loss": low["loss"],
             "sequences": 1, "positions": last, "context": seq,
             "tolerance": tol}
    ok = math.isfinite(got["loss"]) \
        and max(per_pass) <= tol["logits_rel_l2"] and \
        abs(got["loss"] - want["loss"]) <= tol["loss_rel"] * abs(want["loss"])
    return facts, ok


def run(job: dict) -> dict:
    clock = time.perf_counter
    cfg, traffic, cell = job["config"], job["traffic"], job["cell"]
    chips, tiny = int(cell["chips"]), job["tiny"]
    from poseidon_tpu.core.layers import REGISTRY
    missing = [t for t in LAYER_TYPES if t not in REGISTRY]
    if missing:           # a program from before the model: fail at once
        print(f"[benchmark] REFUSING: this program has no layer type "
              f"{missing}; it cannot run {cell['name']!r}. Nothing was "
              f"measured.", file=sys.stderr)
        raise SystemExit(2)
    model = {k: cfg[k] for k in MODEL_KEYS}
    if tiny:
        model.update(cfg["cpu_tiny"]["sizes"])
    batch = cfg["cpu_tiny"]["batch_per_chip"] if tiny \
        else int(cell["batch_per_chip"])
    seq = cfg["cpu_tiny"]["seq_len"] if tiny else int(traffic["seq_len"])
    display = int(traffic["display"])
    passes = model["total_ut_steps"]

    # as the `train` command does before the backend starts (libtpu reads
    # the async-collective flags then), so the step is the user's step
    from poseidon_tpu import config as program_config
    program_config.enable_tpu_async_collectives()
    dev = device_mod.require(chips, cpu_rehearsal=tiny)
    peaks = None if tiny else device_mod.peaks(dev["kind"])
    peak = peaks["bf16_flops_per_s"] if peaks else None

    work = os.path.join(job["work_dir"], cell["name"])
    os.makedirs(work, exist_ok=True)
    data = tokengen.build_token_file(
        os.path.join(work, "data"), seed=job["seed"],
        sequences=int(traffic["steps_in_file"]) * batch * chips,
        seq_len=seq, vocab=model["vocab_size"], mix=document_mix(job))
    net_path, solver_path = write_job_files(job, work, data["source"],
                                            batch, model)

    # the benchmark's own reading of the job: required FLOPs
    per_token = flops_looplm.required_flops_per_token(model, seq)
    flops_per_sequence = per_token["total"] * seq
    want_first = expected_first_loss(cfg, model)

    out_dir = os.path.join(work, "out")
    argv = [a.format(solver=solver_path, output_dir=out_dir)
            for a in traffic["argv"]]
    eng = build_engine(argv)
    try:
        from poseidon_tpu.runtime.spans import recorder
        # warm-up, all of it set-up (see traffic["warm_up"])
        t = clock()
        first_loss = eng.train(max_iter=1).get("loss", float("nan"))
        first_step_s = clock() - t
        eng.train(max_iter=display)
        t = clock()
        eng.train(max_iter=2 * display)
        step_s = (clock() - t) / display
        feed = LmdbFeed(eng)
        n_steps = display * max(1, round(job["seconds"] / (display * step_s)))
        if job["trace"]:
            recorder.enable()
            recorder.clear()

        # ---- the measured window: opens and closes on a hard sync ------- #
        rows_before = len(eng.metrics.rows)
        with CompileCounter() as compiles:
            t0 = clock()
            window = feed.steps(n_steps)
            seconds = clock() - t0
        setup_s = t0 - job["t_start"]
        window_spans = recorder.trace_events() if job["trace"] else []
        after = eng.stats.snapshot()
        memory_peak = device_mod.memory_peak_bytes()
        rows = eng.metrics.rows[rows_before:]

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

    finally:
        eng.close()
    # ---- correct? (outside every timed region; the Engine's Adam moments
    # leave the device first, its weights stay for the check) ------------- #
    params, eng.params, eng.state = eng.params, None, None
    del eng, feed
    ref_facts, ref_ok = reference_check(job, params, net_path, model, seq)

    # the exit distribution, as the EXIT_LOSS layer publishes it per display
    mass = [[r[f"exit_mass_p{t}"] for t in range(1, passes + 1)]
            for r in rows if f"exit_mass_p{passes}" in r]
    place = after["sections"].get("placement", {})
    low, high = cfg["first_loss_band"]
    checks = {
        "losses_finite": bool(window["losses"]) and all(
            math.isfinite(v) for v in window["losses"]),
        "first_loss": low * want_first[0] <= first_loss
        <= high * want_first[1],
        "no_compile_in_window": compiles.count == 0,
        "batch_on_every_chip": len(set(str(place.get(
            "batch_shard_devices", "")).split(","))) == chips
        and int(place.get("param_devices", 0)) == chips,
        "reference": ref_ok,
        "no_failed_step": window["failed"] == 0,
        "exit_mass_sums_to_one": bool(mass) and all(
            abs(sum(m) - 1.0) < 1e-3 and min(m) >= 0.0 for m in mass),
    }
    # per chip: ``batch`` is the sequences ONE chip takes a step
    sequences_per_s = (window["attempted"] - window["failed"]) * batch \
        / seconds
    intervals = [b - a for a, b in zip(window["stamps"],
                                       window["stamps"][1:])]
    # the sample of images_per_s_per_chip is here ONE SEQUENCE, as in
    # olmoe.l1.pack4k
    end_to_end = {"setup_s": setup_s,
                  "images_per_s_per_chip": sequences_per_s}
    if peak:
        end_to_end["mfu_required"] = \
            100.0 * sequences_per_s * flops_per_sequence / peak
    sections = after["sections"]
    facts = {"first_loss": first_loss, "first_loss_expected": want_first,
             "window_losses": window["losses"][-3:], "reference": ref_facts,
             "checks": checks, "steps": window["attempted"],
             "window_s": seconds, "step_s_warmup": step_s,
             "first_step_s": first_step_s,
             "display_intervals_s": intervals,
             "batch_per_chip": batch, "seq_len": seq,
             "tokens_per_s_per_chip": sequences_per_s * seq,
             "flops_per_token": per_token, "token_file": data,
             "exit_mass": mass[-3:],
             "kernel_routes": sorted(set(
                 sections.get("kernel_routes", {}).values())),
             "compiled_step": sections.get("compiled_step", {}),
             "remat": {k: v for k, v in sections.get("remat", {}).items()
                       if k not in ("layers", "segments")},
             "remat_segments": len(sections.get("remat", {}).get(
                 "segments", ())),
             "shared_params": len(sections.get("shared_params", {})),
             "placement": place}
    return {
        "correct": all(checks.values()),
        "attempted": window["attempted"], "failed": window["failed"],
        "device": dict(dev, memory_peak_bytes=memory_peak),
        "end_to_end": end_to_end,
        "facts": facts,
        # what the per-layer readers (layer_metrics/*.py) reduce: the keys
        # caffe_train hands them, one sequence as the sample, plus "lm"
        "layers": {"steps": window["attempted"], "window_s": seconds,
                   "setup_s": setup_s,
                   "batch_per_chip": batch,
                   "flops_per_image": flops_per_sequence,
                   "peak_flops_per_s": peak,
                   "compiles_in_window": compiles.count,
                   "display_intervals_s": intervals,
                   "spans": window_spans, "stats": after,
                   "memory_peak_bytes": memory_peak,
                   "trace": trace,
                   "lm": {"seq_len": seq,
                          "flops_per_step": {
                              k: v * seq * batch
                              for k, v in per_token.items()},
                          "flash_per_step":
                              flops_looplm.flash_attention_step(
                                  model, batch, seq),
                          "peaks": peaks,
                          "scopes": cfg["scopes"],
                          "exit_mass": mass}},
    }
