"""Runner: one Trinity-Mini training job as ONE of eight chips that share
each layer (a prototxt of EMBED / POWER / RMS_NORM / INNER_PRODUCT /
ATTENTION with a window or without positions / SIGMOID / ELTWISE / SILU_GATE
/ MOE_ROUTER with a sigmoid score / MOE layers, the MOE layers holding part
of the experts their routers score), driven through the program's own
``train`` command exactly as ``zaya_train`` drives its model, whose pieces
(and ``lm_train``'s and ``caffe_train``'s) it reuses: the token file,
``build_engine``, ``LmdbFeed``, ``CompileCounter``, ``trace_window``,
``write_job_files``, ``first_step``, ``optimizer_facts``.

What differs is the model's: the keys read from its config.json and the
layers the configuration runs (``layers_run``); top-8 routing, so the
program's choice handed to the reference is eight experts a token and a
MoE layer's counts are assignments; the routers' largest selection bias per
display beside the routing, every MoE layer's own held share per display and
which rung of ``expert_ffn``'s ladder the window's layer-steps took; and the
window / global split of what the flash kernels require. ``correct`` is
decided as ``zaya_train`` decides it, by ``step_check`` on the TIMED path and
``reference_check`` on the trained weights, against ``reference/trinity.py``.

The per-layer readers get the keys ``lm_train`` hands them, ONE SEQUENCE as
the sample; ``lm`` holds what the token cells' readers add, under the keys
every token runner shares (``lm_trace``: ``scopes``, the configuration's
layer-name patterns by part; the required work; the display rows' series).
"""

from __future__ import annotations

import importlib
import math
import os
import shutil
import sys
import time

import device as device_mod
import flops_trinity
import tokengen
from runners.caffe_train import (CompileCounter, LmdbFeed, build_engine,
                                 trace_window)
from runners.lm_train import document_mix
from runners.zaya_train import (expected_first_loss, first_step,
                                write_job_files)

# the keys of the model's config.json the benchmark computes from
MODEL_KEYS = ("hidden_size", "intermediate_size", "moe_intermediate_size",
              "num_attention_heads", "num_key_value_heads", "head_dim",
              "num_experts", "router_num_experts", "num_experts_per_tok",
              "num_shared_experts", "num_hidden_layers", "vocab_size",
              "rms_norm_eps", "rope_theta", "sliding_window", "route_scale",
              "load_balance_coeff", "layers_run")
ATTENTION_FIELDS = ("window", "rope")
MOE_FIELDS = ("num_held", "held_first", "score_func", "route_scale",
              "bias_update_rate")


def refuse_old_program(cell: str) -> None:
    """A program from before the model: fail at once, exit 2."""
    from poseidon_tpu.proto.messages import (AttentionParameter,
                                             MoEParameter, RMSNormParameter)
    missing = [f"attention_param.{f}" for f in ATTENTION_FIELDS
               if not hasattr(AttentionParameter(), f)] \
        + [f"moe_param.{f}" for f in MOE_FIELDS
           if not hasattr(MoEParameter(), f)] \
        + [f"rms_norm_param.{f}" for f in ("num_heads",)
           if not hasattr(RMSNormParameter(), f)]
    if missing:
        print(f"[benchmark] REFUSING: this program has no {missing}; it "
              f"cannot run {cell!r}. Nothing was measured.", file=sys.stderr)
        raise SystemExit(2)


def write_net_files(job: dict, work: str, source: str, batch: int):
    """``zaya_train.write_job_files``, and in a CPU rehearsal the embedding's
    scale cut with the hidden size (that rule cuts whole numbers only)."""
    net_path, solver_path = write_job_files(job, work, source, batch)
    if job["tiny"]:
        cut = job["config"]["cpu_tiny"]["embed_scale"]
        with open(net_path) as f:
            net = f.read()
        assert net.count(cut["from"]) == 1, cut
        with open(net_path, "w") as f:
            f.write(net.replace(cut["from"], cut["to"]))
    return net_path, solver_path


def reference_sizes(cfg: dict, model: dict) -> dict:
    return {"num_hidden_layers": model["num_hidden_layers"],
            "num_dense_layers": model["layers_run"]["dense"],
            "layer_types": model["layers_run"]["layer_types"],
            "num_attention_heads": model["num_attention_heads"],
            "num_key_value_heads": model["num_key_value_heads"],
            "num_experts": model["router_num_experts"],
            "num_experts_per_tok": model["num_experts_per_tok"],
            "route_scale": model["route_scale"],
            "sliding_window": model["sliding_window"],
            "rms_norm_eps": model["rms_norm_eps"],
            "rope_theta": model["rope_theta"]}


def reference_check(job: dict, params: dict, net_path: str, model: dict,
                    seq: int):
    """The program's forward (the run's numeric policy) against the plain
    reference on ONE seeded whole-length sequence and the trained weights
    (``params``, still on the device): logits at the last
    ``reference_positions`` positions against the whole context, and the
    loss over every position. ``correct`` is decided with the program's
    expert choice handed over; the free-running reference and the one with
    its matmul inputs rounded to ``reference_lower_precision`` (which has
    to lie outside the tolerance) are facts beside it. ``route_flips``
    counts, a MoE layer, the handed-over assignments the reference's own
    top-k does not have (8 of 128 by a sigmoid: near-ties are common). Called with the
    Engine closed and its solver state dropped."""
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
    top_k = model["num_experts_per_tok"]
    moe_layers = range(model["layers_run"]["dense"],
                       model["num_hidden_layers"])
    held = range(model["num_experts"])

    def program(p, tok, tgt):
        out = net.apply(p, {"tokens": tok, "targets": tgt}, train=False,
                        keep_blobs=True)
        return {"loss": out.loss, "logits": out.blobs["logits"][:, -last:],
                # each token's k experts: the non-zero gates
                "choice": jnp.stack([
                    jax.lax.top_k(out.blobs[f"l{i}_gates"], top_k)[1]
                    for i in moe_layers])}

    def host(out):
        return {k: np.asarray(v, np.float32 if k != "choice" else np.int32)
                for k, v in out.items()}

    got = host(jax.jit(program)(params, tokens, targets))
    # the same device arrays under the reference's names and blob order
    weights = {l.name: [params[l.name][p.name] for p in l.params]
               for l in net.layers if l.name in params}
    sizes = reference_sizes(cfg, model)

    def reference(w, tok, tgt, choice=None, round_to=None):
        total, out = ref.loss(sizes, w, tok, tgt, held=held, last=last,
                              q_block=last, choice=choice,
                              round_to=round_to)
        return {"loss": total, "logits": out["logits"],
                "route_flips": out["route_flips"]}

    choice = jnp.asarray(got["choice"])
    want = host(jax.jit(reference)(weights, tokens, targets, choice))
    free = host(jax.jit(reference)(weights, tokens, targets))
    low = host(jax.jit(lambda *a: reference(
        *a, round_to=getattr(jnp, cfg["reference_lower_precision"])))(
            weights, tokens, targets, choice))

    def rel(a, b):
        return float(np.linalg.norm((a - b).astype(np.float64))
                     / max(np.linalg.norm(b.astype(np.float64)), 1e-30))

    tol = (ref.TOLERANCE_TINY if job["tiny"] else ref.TOLERANCE)[
        job["traffic"]["precision"]]
    facts = {"loss_program": float(got["loss"]),
             "loss_reference": float(want["loss"]),
             "logits_rel_l2": rel(got["logits"], want["logits"]),
             "route_flips": [int(n) for n in want["route_flips"]],
             "free_running_logits_rel_l2": rel(got["logits"],
                                               free["logits"]),
             "free_running_loss": float(free["loss"]),
             "lower_precision": cfg["reference_lower_precision"],
             "lower_precision_rel_l2": rel(low["logits"], want["logits"]),
             "lower_precision_loss": float(low["loss"]),
             "sequences": 1, "positions": last, "context": seq,
             "tolerance": tol}
    ok = math.isfinite(facts["loss_program"]) \
        and facts["logits_rel_l2"] <= tol["logits_rel_l2"] \
        and abs(facts["loss_program"] - facts["loss_reference"]) \
        <= tol["loss_rel"] * abs(facts["loss_reference"])
    return facts, ok


def step_check(job: dict, model: dict, seq: int, step: dict):
    """The Engine's own compiled step against the reference's: ``step``
    holds the seeded weights (``before``), the change the run's FIRST step
    made to every leaf (``change``), that step's loss, its batch and the
    solver's numbers, all on the host. The reference takes the same step in
    f32 (``train_step``, free-running: the step publishes no expert
    choice), and once more with its matmul inputs rounded to
    ``reference_lower_precision``, which has to lie outside a limit.
    Decided by: the loss (where the tolerance has a limit for it: under
    bf16 it is a fact only); every leaf's change in norm (worst leaf); the
    direction of the change of every leaf of 2**20 numbers or more (worst
    cosine); and every selection bias whose expert's count is not within
    ``bias_margin`` of the even split (the counts are ASSIGNMENTS, 8 a
    token, over all 128 experts the routers score)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    cfg = job["config"]
    ref = importlib.import_module(f"reference.{cfg['reference']}")
    opt = dict(step["opt"], bias_rate=model["load_balance_coeff"])
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

    def against(got, other):
        """Leaf by leaf (the biases apart): how far the norms of the two
        changes lie from each other, and for a leaf of ``cosine_from``
        numbers or more the cosine between them; the worst of each first."""
        rows = []
        for name, blobs in other.items():
            for j, b in enumerate(blobs[:-1] if name in routers else blobs):
                a = got[name][j].astype(np.float64).ravel()
                b = b.astype(np.float64).ravel()
                na, nb = np.linalg.norm(a), np.linalg.norm(b)
                rows.append({"leaf": f"{name}[{j}]", "numbers": b.size,
                             "norm_rel": float(abs(na - nb) / max(nb, 1e-30)),
                             "cosine": float(a @ b / max(na * nb, 1e-300))
                             if b.size >= tol["cosine_from"] else None})
        by_norm = sorted(rows, key=lambda r: -r["norm_rel"])
        by_cosine = sorted((r for r in rows if r["cosine"] is not None),
                           key=lambda r: r["cosine"])
        return {"norm_rel": by_norm[0]["norm_rel"],
                "cosine": by_cosine[0]["cosine"] if by_cosine else 1.0,
                "worst_by_norm": by_norm[:6], "worst_by_cosine": by_cosine[:6]}

    tol = (ref.TOLERANCE_TINY if job["tiny"] else ref.TOLERANCE)[
        job["traffic"]["precision"]]
    routers = sorted((n for n in want["change"] if n.endswith("_router")),
                     key=lambda n: int(n[1:-7]))
    # the selection biases: the program's next value against the sign rule
    # on the reference's own counts. A count within ``bias_margin`` (a share
    # of the EVEN SPLIT, the step's assignments / E: with 128 experts a
    # share of all assignments would exceed the even split itself) of the
    # even split is not compared: the near-ties that rounding flips can
    # carry it across
    counts = np.asarray(want["counts"])                       # (M, E)
    even = counts.sum(1, keepdims=True) / counts.shape[1]
    off_even = np.abs(counts - even) / even
    moved = np.stack([step["change"][n][-1] for n in routers])
    expected = np.stack([want["change"][n][-1] for n in routers])
    differs = np.abs(moved - expected) > 1e-3 * opt["bias_rate"]
    clear = off_even > tol["bias_margin"]
    bias_wrong = int(np.sum(clear & differs))
    program = against(step["change"], want["change"])
    control = against(low["change"], want["change"])
    loss_rel = abs(step["loss"] - float(want["loss"])) \
        / abs(float(want["loss"]))
    facts = {"loss_program": step["loss"],
             "loss_reference": float(want["loss"]),
             "loss_rel": loss_rel,
             "update_norm_rel": program["norm_rel"],
             "update_cosine": program["cosine"],
             "worst_by_norm": program["worst_by_norm"],
             "worst_by_cosine": program["worst_by_cosine"],
             "bias_compared": int(clear.sum()), "bias_of": int(clear.size),
             "bias_wrong": bias_wrong,
             "bias_moved": int(np.sum(moved != 0)),
             "bias_differs_farthest_off_even": float(
                 off_even[differs].max()) if differs.any() else 0.0,
             "grad_norm_reference": float(want["grad_norm"]),
             "first_rate": first_rate,
             "lower_precision": cfg["reference_lower_precision"],
             "lower_precision_loss_rel": abs(
                 float(low["loss"]) - float(want["loss"]))
             / abs(float(want["loss"])),
             "lower_precision_update_norm_rel": control["norm_rel"],
             "lower_precision_update_cosine": control["cosine"],
             "lower_precision_worst_by_cosine": control["worst_by_cosine"][:2],
             "sequences": int(tokens.shape[0]), "context": seq,
             "seconds": dict(took, compare_s=clock() - t),
             "tolerance": tol}
    ok = math.isfinite(step["loss"]) \
        and (tol["step_loss_rel"] is None
             or loss_rel <= tol["step_loss_rel"]) \
        and program["norm_rel"] <= tol["update_norm_rel"] \
        and program["cosine"] >= tol["update_cosine"] \
        and bias_wrong == 0 and facts["bias_compared"] \
        >= tol["bias_compared_share"] * clear.size
    return facts, ok


def run(job: dict) -> dict:
    clock = time.perf_counter
    cfg, traffic, cell = job["config"], job["traffic"], job["cell"]
    chips, tiny = int(cell["chips"]), job["tiny"]
    refuse_old_program(cell["name"])
    model = {k: cfg[k] for k in MODEL_KEYS}
    if tiny:
        model.update(cfg["cpu_tiny"]["sizes"])
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
    net_path, solver_path = write_net_files(job, work, data["source"], batch)

    # the benchmark's own reading of the job: required FLOPs
    per_token = flops_trinity.required_flops_per_token(model, seq)
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
    ref_facts, ref_ok = reference_check(job, params, net_path, model, seq)
    del params                  # the device is the reference's own now
    step_facts, step_ok = step_check(job, model, seq, step)
    del step

    # the step's own routing, as the MOE layers publish it per display:
    # one mean over the layers a display
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
                 "first_display": held_share[:1],
                 "last_display": held_share[-1:],
                 "min": min(held_share, default=None),
                 "max": max(held_share, default=None),
                 "mean": sum(held_share) / max(1, len(held_share)),
                 "per_display": held_share,
                 "warm_up_per_layer": per_layer(warm_rows, "_held_share"),
                 "per_layer": held_by_layer,
                 "window_prefix": held_prefix},
             "held_expert_load_max_over_mean": {
                 "warm_up": per_display(warm_rows, "_expert_load"),
                 "first_display": load[:1], "last_display": load[-1:],
                 "max": max(load, default=None)},
             "selection_bias_max_abs": {
                 "first_display": bias_max[:1],
                 "last_display": bias_max[-1:]},
             "kernel_routes": sorted(set(
                 sections.get("kernel_routes", {}).values())),
             "expert_share": sections.get("expert_share", {}),
             "compiled_step": sections.get("compiled_step", {}),
             "remat": {k: v for k, v in sections.get("remat", {}).items()
                       if k not in ("layers", "segments")},
             "remat_segments": len(sections.get("remat", {}).get(
                 "segments", ())),
             "shared_params": sections.get("shared_params", {}),
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
                          "flash_per_step": flops_trinity.flash_attention_step(
                              model, batch, seq),
                          "flops_per_assignment":
                              flops_trinity.expert_flops_per_assignment(model),
                          "assignments_per_step": moe_layers * seq
                          * batch * model["num_experts_per_tok"],
                          "peaks": peaks,
                          "scopes": cfg["scopes"],
                          "kernel_routes": sorted(set(sections.get(
                              "kernel_routes", {}).values())),
                          "held_share": held_share, "expert_load": load,
                          "held_share_by_layer": held_by_layer,
                          "dropped": dropped,
                          # the routing of the steps the profiler saw
                          "traced_held_share": per_display(
                              traced_rows, "_held_share")}},
    }
