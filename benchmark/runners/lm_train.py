"""Runner: one token-model training job (a prototxt of EMBED / RMS_NORM /
ATTENTION / MOE layers), driven through the program's own ``train`` command
like ``caffe_train``'s jobs, whose pieces it reuses: ``build_engine`` (the
user's command up to ``Engine.train``), ``LmdbFeed`` (every step is
``Engine.train``'s own, whatever the data layer reads), ``CompileCounter``
and ``trace_window``.

What is taken from the program beyond that runner's list: the scalar tops
of the ``MOE`` layers in ``Engine.metrics.rows`` (``*_expert_load``,
``*_dropped``: the step's own routing, per display) and ``stats`` section
``kernel_routes``. Inputs (``tokengen``), FLOPs (``flops_lm``), the plain
reference (``reference/olmoe.py``) and every clock are the benchmark's own.

The per-layer readers get the same ``layers`` keys as from ``caffe_train``,
with ONE SEQUENCE as the sample (``flops_per_image`` = required FLOPs of a
sequence, ``batch_per_chip`` = sequences a step), so the existing readers
work unedited; ``lm`` holds what the readers of this cell add.
"""

from __future__ import annotations

import importlib
import math
import os
import re
import shutil
import time

import device as device_mod
import flops_lm
import tokengen
from runners.caffe_train import (CompileCounter, LmdbFeed, build_engine,
                                 cut_fields, trace_window)

# the keys of the model's published config.json the benchmark computes from
MODEL_KEYS = ("hidden_size", "intermediate_size", "num_attention_heads",
              "num_key_value_heads", "num_experts", "num_experts_per_tok",
              "num_hidden_layers", "vocab_size", "rms_norm_eps", "rope_theta")
# --cpu-tiny only: where each cut size sits in the prototxt
TINY_FIELDS = {"hidden_size": ("num_output",),
               "vocab_size": ("num_output", "input_dim"),
               "num_attention_heads": ("num_heads",),
               "num_experts": ("num_experts",),
               "num_experts_per_tok": ("top_k",),
               "intermediate_size": ("expert_width",)}


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


def document_mix(job: dict) -> dict:
    mix = dict(job["traffic"]["documents"])
    if job["tiny"]:         # documents cut with the sequence
        mix.update(job["config"]["cpu_tiny"]["documents"])
    return mix


def expected_first_loss(cfg: dict, model: dict) -> tuple:
    """(lowest, highest) first loss of fresh weights: their cross-entropy
    plus the two weighted auxiliary terms, the balance term between
    uniform routing (E * sum f P = k) and every token on the same k
    experts (= E) — the configuration's ``first_loss_why``."""
    var = cfg["init_std"] ** 2 * model["hidden_size"]    # of a logit
    lm = math.log(model["vocab_size"]) + var / 2
    weight = cfg["assumed"]["balance_loss_weight"]
    z = cfg["assumed"]["router_z_loss_weight"] \
        * (math.log(model["num_experts"]) + var / 2) ** 2
    layers = model["num_hidden_layers"]
    return (lm + layers * (weight * model["num_experts_per_tok"] + z),
            lm + layers * (weight * model["num_experts"] + z))


def reference_check(job: dict, params: dict, net_path: str,
                    model: dict, seq: int, n_seq: int):
    """The program's forward (the run's numeric policy) against the plain
    reference on ``n_seq`` seeded sequences and the trained weights
    (``params``, still on the device), whole sequences at every position.
    Called with the Engine closed and its solver state dropped: the
    reference's dense experts need the room the Adam moments held."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from poseidon_tpu.core.net import Net
    from poseidon_tpu.proto.messages import load_net

    ref = importlib.import_module(f"reference.{job['config']['reference']}")
    mix = document_mix(job)
    made = tokengen.packed_sequences(job["seed"] + 7919, n_seq, seq,
                                     model["vocab_size"], mix)
    tokens, targets = jnp.asarray(made["data"]), jnp.asarray(made["label"])
    net = Net(load_net(net_path), "TRAIN",
              source_shapes={"tokens": (n_seq, seq), "targets": (n_seq, seq)})

    def program(p, tok, tgt):
        out = net.apply(p, {"tokens": tok, "targets": tgt}, train=False,
                        keep_blobs=True)
        return {"loss": out.loss, "logits": out.blobs["logits"]}

    got = jax.jit(program)(params, tokens, targets)
    got = {"loss": float(got["loss"]),
           "logits": np.asarray(got["logits"], np.float32)}
    # the same device arrays under the reference's names and blob order
    weights = {l.name: [params[l.name][p.name] for p in l.params]
               for l in net.layers if l.params}
    cfg = {k: model[k] for k in MODEL_KEYS}
    assumed = job["config"]["assumed"]

    def reference(w, tok, tgt):
        total, _ = ref.loss(cfg, w, tok, tgt, assumed["balance_loss_weight"],
                            assumed["router_z_loss_weight"])
        return {"loss": total, "logits": ref.forward(cfg, w, tok)["logits"]}

    want = jax.jit(reference)(weights, tokens, targets)
    want = {"loss": float(want["loss"]),
            "logits": np.asarray(want["logits"], np.float32)}
    tol = ref.TOLERANCE[job["traffic"]["precision"]]
    rel = float(np.linalg.norm((got["logits"] - want["logits"])
                               .astype(np.float64))
                / max(np.linalg.norm(want["logits"].astype(np.float64)),
                      1e-30))
    facts = {"loss_program": got["loss"], "loss_reference": want["loss"],
             "logits_rel_l2": rel, "sequences": n_seq, "positions": seq,
             "tolerance": tol}
    ok = math.isfinite(got["loss"]) and rel <= tol["logits_rel_l2"] and \
        abs(got["loss"] - want["loss"]) <= tol["loss_rel"] * abs(want["loss"])
    return facts, ok


def run(job: dict) -> dict:
    clock = time.perf_counter
    cfg, traffic, cell = job["config"], job["traffic"], job["cell"]
    chips, tiny = int(cell["chips"]), job["tiny"]
    model = {k: cfg[k] for k in MODEL_KEYS}
    if tiny:
        model.update(cfg["cpu_tiny"]["sizes"])
    batch = cfg["cpu_tiny"]["batch_per_chip"] if tiny \
        else int(cell["batch_per_chip"])
    seq = cfg["cpu_tiny"]["seq_len"] if tiny else int(traffic["seq_len"])
    display = int(traffic["display"])

    # as the `train` command does before the backend starts (libtpu reads
    # the async-collective flags then), so the step is the user's step
    from poseidon_tpu import config as program_config
    program_config.enable_tpu_async_collectives()
    dev = device_mod.require(chips, cpu_rehearsal=tiny)
    peaks = None if tiny else device_mod.peaks(dev["kind"])
    peak = peaks["bf16_flops_per_s"] if peaks else None

    work = os.path.join(job["work_dir"], cell["name"])
    os.makedirs(work, exist_ok=True)
    mix = document_mix(job)
    data = tokengen.build_token_file(
        os.path.join(work, "data"), seed=job["seed"],
        sequences=int(traffic["steps_in_file"]) * batch * chips,
        seq_len=seq, vocab=model["vocab_size"], mix=mix)
    net_path, solver_path = write_job_files(job, work, data["source"],
                                            batch, model)

    # the benchmark's own reading of the job: required FLOPs
    per_token = flops_lm.required_flops_per_token(model, seq)
    flops_per_sequence = per_token["total"] * seq
    want_first = expected_first_loss(cfg, model)

    out_dir = os.path.join(work, "out")
    argv = [a.format(solver=solver_path, output_dir=out_dir)
            for a in traffic["argv"]]
    eng = build_engine(argv)
    try:
        from poseidon_tpu.runtime.spans import recorder
        # warm-up, all of it set-up (see traffic["warm_up"])
        first_loss = eng.train(max_iter=1).get("loss", float("nan"))
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
    # ---- correct? (outside every timed region; the Engine's 5 GB of Adam
    # moments leave the device first, its weights stay for the check) ----- #
    params, eng.params, eng.state = eng.params, None, None
    del eng, feed
    ref_facts, ref_ok = reference_check(
        job, params, net_path, model, seq,
        cfg["cpu_tiny"]["batch_per_chip"] if tiny
        else int(cfg["reference_sequences"]))

    # the step's own routing, as the MOE layers publish it per display
    load = [v for r in rows for k, v in r.items()
            if k.endswith("_expert_load")]
    dropped = [v for r in rows for k, v in r.items() if k.endswith("_dropped")]
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
        "no_dropped_token": bool(dropped) and max(dropped) == 0.0,
    }
    # per chip: ``batch`` is the sequences ONE chip takes a step
    sequences_per_s = (window["attempted"] - window["failed"]) * batch \
        / seconds
    intervals = [b - a for a, b in zip(window["stamps"],
                                       window["stamps"][1:])]
    # the driver wants every end-to-end metric of every cell: the sample of
    # images_per_s_per_chip is here ONE SEQUENCE (as for the readers below)
    end_to_end = {"setup_s": setup_s,
                  "images_per_s_per_chip": sequences_per_s}
    if peak:
        end_to_end["mfu_required"] = \
            100.0 * sequences_per_s * flops_per_sequence / peak
    facts = {"first_loss": first_loss, "first_loss_expected": want_first,
             "window_losses": window["losses"][-3:], "reference": ref_facts,
             "checks": checks, "steps": window["attempted"],
             "window_s": seconds, "step_s_warmup": step_s,
             "display_intervals_s": intervals,
             "batch_per_chip": batch, "seq_len": seq,
             "tokens_per_s_per_chip": sequences_per_s * seq,
             "flops_per_token": per_token, "token_file": data,
             "expert_load_max_over_mean": load[-3:],
             "kernel_routes": after["sections"].get("kernel_routes", {}),
             "compiled_step": after["sections"].get("compiled_step", {}),
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
                          "flash_per_step": flops_lm.flash_attention_step(
                              model, batch, seq),
                          "peaks": peaks,
                          "scopes": cfg["scopes"],
                          "expert_load": load, "dropped": dropped}},
    }
