"""Runner: one Kimi-Linear training job as ONE of 32 chips that share each
layer (a prototxt of EMBED / RMS_NORM / INNER_PRODUCT / SHORT_CONV / L2_NORM
/ KDA_DECAY / KDA_SCAN / SIGMOID / ELTWISE / SLICE / ATTENTION with value
heads of their own width and a shared key part / SILU_GATE / MOE_ROUTER with
a sigmoid score / MOE layers, the MOE layers holding part of the experts
their routers score), driven through the program's own ``train`` command
exactly as ``trinity_train`` drives its model, whose pieces (and
``zaya_train``'s, ``lm_train``'s and ``caffe_train``'s) it reuses: the token
file, ``build_engine``, ``LmdbFeed``, ``CompileCounter``, ``trace_window``,
``write_job_files``, ``first_step`` and BOTH comparisons that decide
``correct`` (``trinity_train.reference_check`` on the trained weights,
``trinity_train.step_check`` on the timed path's first step), against
``reference/kimi_linear.py``.

What is this file's own, and why: ``MODEL_KEYS`` / ``reference_sizes`` (the
model's keys; ``trinity_train``'s two checks read ``reference_sizes`` as a
global of their module, and an accepted benchmark file is not this PR's to
edit, so ``kimi_sizes`` swaps it in for the length of a call); ``run`` (a
module-level ``MODEL_KEYS`` and ``flops_trinity`` are written into
``trinity_train.run``); the second control
(``state_control``: the reference with its recurrence's state rounded to
bf16 after every token has to lie outside a limit, as the float8 control
does); the KDA layers' mean decay per display; and ``compared``, every
number that decided ``correct`` beside its limit, in the facts line.

The per-layer readers get the keys ``lm_train`` hands them, ONE SEQUENCE as
the sample; ``lm`` holds what the token cells' readers add, under the keys
every token runner shares (``lm_trace``: ``scopes``, the configuration's
layer-name patterns by part; the required work; the display rows' series).
"""

from __future__ import annotations

import contextlib
import importlib
import math
import os
import shutil
import sys
import time

import device as device_mod
import flops_kimi
import tokengen
from runners import trinity_train
from runners.caffe_train import (CompileCounter, LmdbFeed, build_engine,
                                 trace_window)
from runners.lm_train import document_mix
from runners.zaya_train import (expected_first_loss, first_step,
                                write_job_files)

# the keys of the model's config.json the benchmark computes from
MODEL_KEYS = ("hidden_size", "intermediate_size", "moe_intermediate_size",
              "num_attention_heads", "kv_lora_rank", "qk_nope_head_dim",
              "qk_rope_head_dim", "v_head_dim", "linear_attn_config",
              "num_experts", "router_num_experts", "num_experts_per_token",
              "num_shared_experts", "num_hidden_layers", "vocab_size",
              "rms_norm_eps", "routed_scaling_factor", "bias_update_rate",
              "layers_run")
NEW_FIELDS = {"attention_param": ("value_head_dim",),
              "kda_param": ("num_heads", "kernel_size")}


def refuse_old_program(cell: str) -> None:
    """A program from before the model: fail at once, exit 2."""
    trinity_train.refuse_old_program(cell)
    from poseidon_tpu.proto import messages
    layer = messages.LayerParameter()
    missing = [f"{param}.{f}" for param, fields in NEW_FIELDS.items()
               for f in fields if not hasattr(getattr(layer, param, None), f)]
    if missing:
        print(f"[benchmark] REFUSING: this program has no {missing}; it "
              f"cannot run {cell!r}. Nothing was measured.", file=sys.stderr)
        raise SystemExit(2)


def reference_sizes(cfg: dict, model: dict) -> dict:
    return {"num_hidden_layers": model["num_hidden_layers"],
            "num_dense_layers": model["layers_run"]["dense"],
            "layer_types": model["layers_run"]["layer_types"],
            "num_heads": model["linear_attn_config"]["num_heads"],
            "kv_lora_rank": model["kv_lora_rank"],
            "qk_nope_head_dim": model["qk_nope_head_dim"],
            "num_experts": model["router_num_experts"],
            "num_experts_per_tok": model["num_experts_per_token"],
            "route_scale": model["routed_scaling_factor"],
            "rms_norm_eps": model["rms_norm_eps"]}


@contextlib.contextmanager
def kimi_sizes():
    """``trinity_train``'s checks with this model's ``reference_sizes``."""
    theirs = trinity_train.reference_sizes
    trinity_train.reference_sizes = reference_sizes
    try:
        yield
    finally:
        trinity_train.reference_sizes = theirs


def state_control(job: dict, params: dict, net_path: str, model: dict,
                  seq: int) -> dict:
    """The second control: the reference on the trained weights with its
    recurrence's state in f32 (free-running) and rounded to bf16 after every
    token. How far the two lie apart is what a program that kept its state
    in bf16 would read against the reference; it has to lie outside the
    logits' limit."""
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
    weights = {l.name: [params[l.name][p.name] for p in l.params]
               for l in net.layers if l.name in params}
    sizes = reference_sizes(cfg, model)

    def logits(w, state_dtype):
        total, out = ref.loss(sizes, w, tokens, targets,
                              held=range(model["num_experts"]), last=last,
                              q_block=last, state_dtype=state_dtype)
        return out["logits"], total

    want, want_loss = jax.jit(lambda w: logits(w, None))(weights)
    low, low_loss = jax.jit(lambda w: logits(w, jnp.bfloat16))(weights)
    want, low = (np.asarray(x, np.float64) for x in (want, low))
    return {"state": "bfloat16",
            "logits_rel_l2": float(np.linalg.norm(low - want)
                                   / max(np.linalg.norm(want), 1e-30)),
            "loss_rel": abs(float(low_loss) - float(want_loss))
            / abs(float(want_loss))}


def compared(ref_facts: dict, step_facts: dict, state: dict) -> list:
    """Every number that decided ``correct`` beside its limit, and the two
    controls beside the limit they have to break."""
    tol = ref_facts["tolerance"]
    loss_rel = abs(ref_facts["loss_program"] - ref_facts["loss_reference"]) \
        / abs(ref_facts["loss_reference"])
    rows = [("logits_rel_l2", ref_facts["logits_rel_l2"], "<=",
             tol["logits_rel_l2"]),
            ("loss_rel", loss_rel, "<=", tol["loss_rel"]),
            ("step_loss_rel", step_facts["loss_rel"], "<=",
             tol["step_loss_rel"]),
            ("update_norm_rel", step_facts["update_norm_rel"], "<=",
             tol["update_norm_rel"]),
            ("update_cosine", step_facts["update_cosine"], ">=",
             tol["update_cosine"]),
            ("bias_wrong", step_facts["bias_wrong"], "<=", 0),
            ("bias_compared_share",
             step_facts["bias_compared"] / max(1, step_facts["bias_of"]),
             ">=", tol["bias_compared_share"]),
            ("control_float8_logits_rel_l2",
             ref_facts["lower_precision_rel_l2"], ">", tol["logits_rel_l2"]),
            ("control_float8_update_cosine",
             step_facts["lower_precision_update_cosine"], "<",
             tol["update_cosine"]),
            ("control_bf16_state_logits_rel_l2", state["logits_rel_l2"],
             ">", tol["logits_rel_l2"])]
    ops = {"<=": lambda a, b: a <= b, ">=": lambda a, b: a >= b,
           ">": lambda a, b: a > b, "<": lambda a, b: a < b}
    return [{"name": name, "value": value, "must_be": op, "limit": limit,
             "holds": None if limit is None else bool(ops[op](value, limit)),
             "decides_correct": not name.startswith("control_")
             and limit is not None}
            for name, value, op, limit in rows]


def run(job: dict) -> dict:
    clock = time.perf_counter
    cfg, traffic, cell = job["config"], job["traffic"], job["cell"]
    chips, tiny = int(cell["chips"]), job["tiny"]
    refuse_old_program(cell["name"])
    model = {k: cfg[k] for k in MODEL_KEYS}
    if tiny:
        model.update(cfg["cpu_tiny"]["sizes"])
    # the names trinity_train's checks read
    model["num_experts_per_tok"] = model["num_experts_per_token"]
    model["load_balance_coeff"] = model["bias_update_rate"]
    batch = cfg["cpu_tiny"]["batch_per_chip"] if tiny \
        else int(cell["batch_per_chip"])
    seq = cfg["cpu_tiny"]["seq_len"] if tiny else int(traffic["seq_len"])
    display = int(traffic["display"])
    moe_layers = model["layers_run"]["moe"]

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
    net_path, solver_path = write_job_files(job, work, data["source"], batch)

    # the benchmark's own reading of the job: required FLOPs
    per_token = flops_kimi.required_flops_per_token(model, seq)
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
        step = first_step(eng, cfg)
        first_loss, first_step_s = step["loss"], clock() - t
        eng.train(max_iter=display)
        settle = display * max(1, int(traffic["settle_displays"]))
        eng.train(max_iter=display + settle)
        t = clock()
        eng.train(max_iter=2 * display + settle)
        step_s = (clock() - t) / display
        feed = LmdbFeed(eng)
        n_steps = display * max(1, round(job["seconds"] / (display * step_s)))
        if job["trace"]:
            recorder.enable()
            recorder.clear()

        # ---- the measured window: opens and closes on a hard sync ------- #
        rows_before = len(eng.metrics.rows)
        counted_before = eng.stats.snapshot()["counters"]
        with CompileCounter() as compiles:
            t0 = clock()
            window = feed.steps(n_steps)
            seconds = clock() - t0
        setup_s = t0 - job["t_start"]
        window_spans = recorder.trace_events() if job["trace"] else []
        after = eng.stats.snapshot()
        memory_peak = device_mod.memory_peak_bytes()
        warm_rows = eng.metrics.rows[:rows_before]
        rows = eng.metrics.rows[rows_before:]

        trace, traced_rows = None, []
        if job["trace"]:
            trace = trace_window(feed, int(traffic["trace_steps"]),
                                 dev["platform"],
                                 os.path.join(work, "trace"))
            recorder.disable()
            traced_rows = eng.metrics.rows[trace["rows_from"]:]
            if job.get("keep_trace"):
                shutil.copytree(os.path.join(work, "trace"),
                                job["keep_trace"], dirs_exist_ok=True)
            shutil.rmtree(os.path.join(work, "trace"), ignore_errors=True)

    finally:
        eng.close()
    # ---- correct? (outside every timed region; the Engine's Adam moments
    # leave the device first, its weights stay for the checks) ------------ #
    params, eng.params, eng.state = eng.params, None, None
    del eng, feed
    with kimi_sizes():
        ref_facts, ref_ok = trinity_train.reference_check(
            job, params, net_path, model, seq)
        state_facts = state_control(job, params, net_path, model, seq)
        del params              # the device is the reference's own now
        step_facts, step_ok = trinity_train.step_check(job, model, seq, step)
    del step

    def per_display(some_rows, suffix):
        return [sum(vals) / len(vals) for vals in (
            [v for k, v in r.items() if k.endswith(suffix)]
            for r in some_rows) if vals]

    def per_layer(some_rows, suffix):
        """{a layer's top: its value in every display that has it}"""
        tops = sorted({k for r in some_rows for k in r if k.endswith(suffix)})
        return {top: [r[top] for r in some_rows if top in r] for top in tops}

    held_share = per_display(rows, "_held_share")
    held_by_layer = per_layer(rows, "_held_share")
    # which rung each of the WINDOW's MoE layer-steps took: the Engine counts
    # them step by step (cumulative; differenced over the window here)
    held_prefix = {k: after["counters"].get(k, 0) - counted_before.get(k, 0)
                   for k in ("held_prefix_hits", "held_layer_steps")}
    load = per_display(rows, "_expert_load")
    decay = per_layer(rows, "_decay_mean")
    bias_max = [max(vals) for vals in (
        [v for k, v in r.items() if k.endswith("_bias_max_abs")]
        for r in rows) if vals]
    dropped = [v for r in rows for k, v in r.items()
               if k.endswith("_dropped")]
    place = after["sections"].get("placement", {})
    low, high = cfg["first_loss_band"]
    checks = {
        "losses_finite": bool(window["losses"]) and all(
            math.isfinite(v) for v in window["losses"]),
        "first_loss": low * want_first <= first_loss <= high * want_first,
        "no_compile_in_window": compiles.count == 0,
        "batch_on_every_chip": len(set(str(place.get(
            "batch_shard_devices", "")).split(","))) == chips
        and int(place.get("param_devices", 0)) == chips,
        "reference": ref_ok,
        "step_reference": step_ok,
        "no_failed_step": window["failed"] == 0,
        "no_dropped_token": bool(dropped) and max(dropped) == 0.0,
        "held_share_published": len(held_share) >= 2
        and all(0.0 <= s <= 1.0 for s in held_share),
        "biases_published": len(bias_max) >= 2 and all(
            0.0 <= b < 1.0 for b in bias_max),
        "decay_published": len(decay) == model["layers_run"][
            "layer_types"].count("kda") and all(
            0.0 < v < 1.0 for vals in decay.values() for v in vals),
    }
    # per chip: ``batch`` is the sequences ONE chip takes a step
    sequences_per_s = (window["attempted"] - window["failed"]) * batch \
        / seconds
    intervals = [b - a for a, b in zip(window["stamps"],
                                       window["stamps"][1:])]
    # the sample of images_per_s_per_chip is here ONE SEQUENCE, as in the
    # other token cells
    end_to_end = {"setup_s": setup_s,
                  "images_per_s_per_chip": sequences_per_s}
    if peak:
        end_to_end["mfu_required"] = \
            100.0 * sequences_per_s * flops_per_sequence / peak
    sections = after["sections"]
    routes = sorted(set(sections.get("kernel_routes", {}).values()))
    facts = {"first_loss": first_loss, "first_loss_expected": want_first,
             "window_losses": window["losses"][-3:], "reference": ref_facts,
             "state_control": state_facts,
             "step_reference": step_facts,
             "checks": checks, "steps": window["attempted"],
             "window_s": seconds, "step_s_warmup": step_s,
             "first_step_s": first_step_s,
             "display_intervals_s": intervals,
             "batch_per_chip": batch, "seq_len": seq,
             "tokens_per_s_per_chip": sequences_per_s * seq,
             "flops_per_token": per_token, "token_file": data,
             "held_assignment_share": {
                 "warm_up": per_display(warm_rows, "_held_share"),
                 "min": min(held_share, default=None),
                 "max": max(held_share, default=None),
                 "mean": sum(held_share) / max(1, len(held_share)),
                 "per_display": held_share,
                 "warm_up_per_layer": per_layer(warm_rows, "_held_share"),
                 "per_layer": held_by_layer,
                 "window_prefix": held_prefix},
             "held_expert_load_max_over_mean": {
                 "first_display": load[:1], "last_display": load[-1:],
                 "max": max(load, default=None)},
             "selection_bias_max_abs": {
                 "first_display": bias_max[:1],
                 "last_display": bias_max[-1:]},
             "decay_mean": {top: vals[-1:] for top, vals in decay.items()},
             "kernel_routes": routes,
             "expert_share": sections.get("expert_share", {}),
             "recurrent_state": sections.get("recurrent_state", {}),
             "compiled_step": sections.get("compiled_step", {}),
             "remat": {k: v for k, v in sections.get("remat", {}).items()
                       if k not in ("layers", "segments")},
             "remat_segments": len(sections.get("remat", {}).get(
                 "segments", ())),
             "placement": place,
             # LAST in the line: what was compared, each beside its limit
             "compared": compared(ref_facts, step_facts, state_facts)}
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
                          "flash_per_step": flops_kimi.flash_attention_step(
                              model, batch, seq),
                          "delta_scan_per_step": flops_kimi.kda_scan_step(
                              model, batch, seq),
                          "flops_per_assignment":
                              flops_kimi.expert_flops_per_assignment(model),
                          "assignments_per_step": moe_layers * seq
                          * batch * model["num_experts_per_token"],
                          "peaks": peaks,
                          "scopes": cfg["scopes"],
                          "kernel_routes": routes,
                          "held_share": held_share, "expert_load": load,
                          "held_share_by_layer": held_by_layer,
                          "dropped": dropped,
                          # the routing of the steps the profiler saw
                          "traced_held_share": per_display(
                              traced_rows, "_held_share")}},
    }
