"""Runner: one SmallThinker-21BA3B training job as ONE of four chips that
share each layer (a prototxt of EMBED / RMS_NORM / INNER_PRODUCT / ATTENTION
with a window or without positions / MOE_ROUTER in its plain softmax form,
reading the pre-attention state / MOE layers with ReGLU experts that hold
part of the experts their routers score / ELTWISE), driven through the
program's own ``train`` command exactly as ``trinity_train`` drives its
model, whose pieces (and ``zaya_train``'s, ``lm_train``'s and
``caffe_train``'s) it reuses: the token file, ``build_engine``,
``LmdbFeed``, ``CompileCounter``, ``trace_window``, ``write_job_files``,
``first_step`` and ``trinity_train.reference_check`` on the trained weights,
against ``reference/smallthinker.py``.

What is this file's own, and why: ``MODEL_KEYS`` / ``reference_sizes`` (the
model's keys; ``trinity_train.reference_check`` reads ``reference_sizes`` as
a global of its module, and an accepted benchmark file is not this PR's to
edit, so ``smallthinker_sizes`` swaps it in for the length of a call, as
``kimi_train`` does); ``step_check`` (``trinity_train``'s reads a selection
bias as every router's LAST blob, skips it in the leaf comparison and
stacks the biases' moves: this model's routers have one blob, their matrix,
and no bias, so that check would skip the very leaf the new router arm
trains and fail on an empty stack; this one compares EVERY leaf, with the
same numbers: loss, worst norm, worst cosine, the float8 control);
``expected_first_loss`` (the two auxiliary terms); ``run`` (the model's
keys, ``flops_smallthinker``, the gate-zero share per display); and ``compared``, every number that decided ``correct``
beside its limit, last in the facts line.

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
import flops_smallthinker
import tokengen
from runners import trinity_train
from runners.caffe_train import (CompileCounter, LmdbFeed, build_engine,
                                 trace_window)
from runners.lm_train import document_mix
from runners.zaya_train import first_step, write_job_files

# the keys of the model's config.json the benchmark computes from
MODEL_KEYS = ("hidden_size", "moe_ffn_hidden_size", "num_attention_heads",
              "num_key_value_heads", "head_dim", "moe_num_primary_experts",
              "router_num_experts", "moe_num_active_primary_experts",
              "num_hidden_layers", "vocab_size", "rms_norm_eps", "rope_theta",
              "sliding_window_size", "layers_run")


def refuse_old_program(cell: str) -> None:
    """A program from before the model: fail at once, exit 2."""
    trinity_train.refuse_old_program(cell)
    from poseidon_tpu.models import moe
    from poseidon_tpu.proto.messages import MoEParameter
    missing = [name for name, there in (
        ("moe_param.activation", hasattr(MoEParameter(), "activation")),
        ("models/moe.softmax_router", hasattr(moe, "softmax_router")))
        if not there]
    if missing:
        print(f"[benchmark] REFUSING: this program has no {missing}; it "
              f"cannot run {cell!r}. Nothing was measured.", file=sys.stderr)
        raise SystemExit(2)


def reference_sizes(cfg: dict, model: dict) -> dict:
    aux = cfg["assumed"]["aux_losses"]
    return {"num_hidden_layers": model["num_hidden_layers"],
            "num_attention_heads": model["num_attention_heads"],
            "num_key_value_heads": model["num_key_value_heads"],
            "head_dim": model["head_dim"],
            "num_experts": model["router_num_experts"],
            "num_experts_per_tok": model["moe_num_active_primary_experts"],
            "sliding_window_size": model["sliding_window_size"],
            "sliding_window_layout": model["layers_run"]["layout"],
            "rms_norm_eps": model["rms_norm_eps"],
            "rope_theta": model["rope_theta"],
            "balance_weight": aux["balance_weight"],
            "z_weight": aux["z_weight"]}


@contextlib.contextmanager
def smallthinker_sizes():
    """``trinity_train.reference_check`` with this model's
    ``reference_sizes``."""
    theirs = trinity_train.reference_sizes
    trinity_train.reference_sizes = reference_sizes
    try:
        yield
    finally:
        trinity_train.reference_sizes = theirs


def expected_first_loss(cfg: dict, model: dict) -> float:
    """Fresh weights know nothing of the targets: ln V + var / 2 with var
    the variance of a logit (a unit-RMS state against a row of the
    std-``init_std`` head), plus a layer's two weighted router losses: the
    balance loss of a fresh router is k (E sum_e f_e / E, the f_e summing to
    k), its z loss (ln E + var / 2)^2 — the configuration's
    ``first_loss_why``."""
    aux = cfg["assumed"]["aux_losses"]
    half_var = cfg["init_std"] ** 2 * model["hidden_size"] / 2
    router = aux["balance_weight"] * model["moe_num_active_primary_experts"] \
        + aux["z_weight"] * (math.log(model["router_num_experts"])
                             + half_var) ** 2
    return math.log(model["vocab_size"]) + half_var \
        + model["num_hidden_layers"] * router


def compare_changes(got: dict, other: dict, cosine_from: int) -> dict:
    """Two steps' changes ({layer: [blobs]}), ``got`` against ``other``:
    ``norm_rel``, how far the norms of the two changes of a leaf lie from
    each other, the worst leaf's (a leaf left unchanged reads 1, one moved
    double reads 1); ``cosine``, the direction of the WHOLE update, every
    leaf of both as one vector; ``leaf_cosine``, the least cosine of any
    ONE leaf of ``cosine_from`` numbers or more, the routers' matrices among
    them. Adam's first change of a number is the rate times its gradient's
    sign, so a leaf's cosine is 1 - 2 x the share of its numbers whose sign
    the two sides disagree on, and a leaf whose gradient is small beside
    bf16's noise reads low by its nature (PERF.md 53a: a router's matrix at
    0.905 on one seed of five where the whole update read alike on all):
    the whole update is the steady number and carries the close limit, the
    least leaf a wide one that a leaf of the wrong sign (-1) or one left
    unmoved still breaks."""
    import numpy as np
    rows, dot, sq_a, sq_b = [], 0.0, 0.0, 0.0
    for name, blobs in other.items():
        for j, b in enumerate(blobs):
            a = got[name][j].astype(np.float64).ravel()
            b = b.astype(np.float64).ravel()
            na, nb, ab = np.linalg.norm(a), np.linalg.norm(b), float(a @ b)
            dot, sq_a, sq_b = dot + ab, sq_a + na * na, sq_b + nb * nb
            rows.append({"leaf": f"{name}[{j}]", "numbers": b.size,
                         "norm_rel": float(abs(na - nb) / max(nb, 1e-30)),
                         "cosine": float(ab / max(na * nb, 1e-300))
                         if b.size >= cosine_from else None})
    by_norm = sorted(rows, key=lambda r: -r["norm_rel"])
    by_cosine = sorted((r for r in rows if r["cosine"] is not None),
                       key=lambda r: r["cosine"])
    return {"norm_rel": by_norm[0]["norm_rel"],
            "cosine": float(dot / max(math.sqrt(sq_a * sq_b), 1e-300)),
            "leaf_cosine": by_cosine[0]["cosine"] if by_cosine else 1.0,
            "leaves": len(rows),
            "worst_by_norm": by_norm[:6], "worst_by_cosine": by_cosine[:6]}


def step_check(job: dict, model: dict, seq: int, step: dict):
    """The Engine's own compiled step against the reference's: ``step``
    holds the seeded weights (``before``), the change the run's FIRST step
    made to every leaf (``change``), that step's loss, its batch and the
    solver's numbers, all on the host. The reference takes the same step in
    f32 (``train_step``, free-running: the step publishes no expert
    choice), and once more with its matmul inputs rounded to
    ``reference_lower_precision``, which has to lie outside a limit.
    Decided by: the loss (where the tolerance has a limit for it: under
    bf16 it is a fact only) and ``compare_changes``' three numbers."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    cfg = job["config"]
    ref = importlib.import_module(f"reference.{cfg['reference']}")
    opt = dict(step["opt"])
    first_rate = opt.pop("first_rate")
    sizes = reference_sizes(cfg, model)
    held = range(model["num_experts"])
    low_type = getattr(jnp, cfg["reference_lower_precision"])
    q_block = min(seq, int(cfg["reference_positions"]))

    @jax.jit
    def reference(w, tok, tgt, low):
        return ref.train_step(sizes, w, tok, tgt, opt, held=held,
                              q_block=q_block, remat=True,
                              round_to=low_type, round_when=low)

    tokens = jnp.asarray(step["batch"]["tokens"])
    targets = jnp.asarray(step["batch"]["targets"])
    clock, took = time.perf_counter, {}
    t = clock()
    weights = jax.device_put(step["before"])
    want = jax.device_get(reference(weights, tokens, targets, False))
    took["reference_s"] = clock() - t         # with the step's compile
    t = clock()
    low = jax.device_get(reference(weights, tokens, targets, True))
    took["lower_precision_s"] = clock() - t
    del weights
    t = clock()
    tol = (ref.TOLERANCE_TINY if job["tiny"] else ref.TOLERANCE)[
        job["traffic"]["precision"]]

    program = compare_changes(step["change"], want["change"],
                              tol["cosine_from"])
    control = compare_changes(low["change"], want["change"],
                              tol["cosine_from"])
    loss_rel = abs(step["loss"] - float(want["loss"])) \
        / abs(float(want["loss"]))
    counts = np.asarray(want["counts"])                       # (L, E)
    facts = {"loss_program": step["loss"],
             "loss_reference": float(want["loss"]),
             "loss_rel": loss_rel,
             "update_norm_rel": program["norm_rel"],
             "update_cosine": program["cosine"],
             "leaf_cosine_min": program["leaf_cosine"],
             "leaves_compared": program["leaves"],
             "worst_by_norm": program["worst_by_norm"],
             "worst_by_cosine": program["worst_by_cosine"],
             "reference_held_share": [
                 float(c[:model["num_experts"]].sum() / c.sum())
                 for c in counts],
             "grad_norm_reference": float(want["grad_norm"]),
             "first_rate": first_rate,
             "lower_precision": cfg["reference_lower_precision"],
             "lower_precision_loss_rel": abs(
                 float(low["loss"]) - float(want["loss"]))
             / abs(float(want["loss"])),
             "lower_precision_update_norm_rel": control["norm_rel"],
             "lower_precision_update_cosine": control["cosine"],
             "lower_precision_leaf_cosine_min": control["leaf_cosine"],
             "lower_precision_worst_by_cosine": control["worst_by_cosine"][:2],
             "sequences": int(tokens.shape[0]), "context": seq,
             "seconds": dict(took, compare_s=clock() - t),
             "tolerance": tol}
    ok = math.isfinite(step["loss"]) \
        and (tol["step_loss_rel"] is None
             or loss_rel <= tol["step_loss_rel"]) \
        and program["norm_rel"] <= tol["update_norm_rel"] \
        and program["cosine"] >= tol["update_cosine"] \
        and program["leaf_cosine"] >= tol["leaf_cosine"]
    return facts, ok


def compared(ref_facts: dict, step_facts: dict) -> list:
    """Every number that decided ``correct`` beside its limit, and the
    float8 control beside the limit it has to break: the logits' (a float8
    STEP moves the update's direction no further than bf16 does, PERF.md
    53a, so its cosines are facts under ``step_reference``, not rows)."""
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
            ("leaf_cosine_min", step_facts["leaf_cosine_min"], ">=",
             tol["leaf_cosine"]),
            ("control_float8_logits_rel_l2",
             ref_facts["lower_precision_rel_l2"], ">", tol["logits_rel_l2"])]
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
    # the names trinity_train.reference_check reads
    model["num_experts"] = model["moe_num_primary_experts"]
    model["num_experts_per_tok"] = model["moe_num_active_primary_experts"]
    model["layers_run"] = dict(model["layers_run"], dense=0)
    batch = cfg["cpu_tiny"]["batch_per_chip"] if tiny \
        else int(cell["batch_per_chip"])
    seq = cfg["cpu_tiny"]["seq_len"] if tiny else int(traffic["seq_len"])
    display = int(traffic["display"])
    layers = model["num_hidden_layers"]

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
    per_token = flops_smallthinker.required_flops_per_token(model, seq)
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
    # leave the device first, its weights stay for the check) ------------- #
    params, eng.params, eng.state = eng.params, None, None
    del eng, feed
    with smallthinker_sizes():
        ref_facts, ref_ok = trinity_train.reference_check(
            job, params, net_path, model, seq)
    del params                  # the device is the reference's own now
    step_facts, step_ok = step_check(job, model, seq, step)
    del step

    # the step's own routing, as the layers publish it per display: one
    # mean over the layers a display
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
    gate_zero = per_display(rows, "_gate_zero_share")
    # the chunk loop's trips over the WINDOW's MoE layer-steps: the Engine
    # counts them step by step (cumulative; differenced over the window)
    held_loop = {k: after["counters"].get(k, 0) - counted_before.get(k, 0)
                 for k in ("held_chunk_trips", "held_rows_run",
                           "held_rows_live", "held_layer_steps")}
    load = per_display(rows, "_expert_load")
    balance = per_display(rows, "_balance_loss")
    z_loss = per_display(rows, "_z_loss")
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
        "router_losses_published": len(balance) >= 2 and len(z_loss) >= 2
        and all(math.isfinite(v) and v > 0.0 for v in balance + z_loss),
        "gate_zero_share_published": len(gate_zero) >= 2
        and all(0.0 < s < 1.0 for s in gate_zero),
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
                 "window_loop": held_loop},
             "held_expert_load_max_over_mean": {
                 "first_display": load[:1], "last_display": load[-1:],
                 "max": max(load, default=None)},
             "gate_zero_share": {
                 "warm_up": per_display(warm_rows, "_gate_zero_share"),
                 "per_display": gate_zero,
                 "per_layer_last_display": {
                     top: vals[-1:] for top, vals in per_layer(
                         rows, "_gate_zero_share").items()}},
             "router_losses": {"balance_first_display": balance[:1],
                               "balance_last_display": balance[-1:],
                               "z_first_display": z_loss[:1],
                               "z_last_display": z_loss[-1:]},
             "kernel_routes": routes,
             "expert_share": sections.get("expert_share", {}),
             "compiled_step": sections.get("compiled_step", {}),
             "remat": {k: v for k, v in sections.get("remat", {}).items()
                       if k not in ("layers", "segments")},
             "remat_segments": len(sections.get("remat", {}).get(
                 "segments", ())),
             "placement": place,
             # LAST in the line: what was compared, each beside its limit
             "compared": compared(ref_facts, step_facts)}
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
                              flops_smallthinker.flash_attention_step(
                                  model, batch, seq),
                          "flops_per_assignment": flops_smallthinker
                          .expert_flops_per_assignment(model),
                          "assignments_per_step": layers * seq * batch
                          * model["moe_num_active_primary_experts"],
                          "peaks": peaks,
                          "scopes": cfg["scopes"],
                          "kernel_routes": routes,
                          "held_share": held_share, "expert_load": load,
                          "held_share_by_layer": held_by_layer,
                          "gate_zero_share": gate_zero,
                          "dropped": dropped,
                          # the routing of the steps the profiler saw
                          "traced_held_share": per_display(
                              traced_rows, "_held_share")}},
    }
