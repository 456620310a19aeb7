"""Runner: one Granite-4.0-H-Micro training job as ONE pipeline stage of four
(a prototxt of EMBED / POWER / RMS_NORM / INNER_PRODUCT / SLICE / SHORT_CONV
with a bias / KDA_DECAY with its step as a top / SSD_SCAN / SILU_GATE /
ATTENTION with a scale of its own and no positions / ELTWISE with
coefficients, one period of ten layers and an eighth of the tied table),
driven through the program's own ``train`` command as every token cell is.
What a token runner does whatever its model comes from
``runners/token_checks.py`` (the window, ``step_check``, ``compared``, the
display rows' series, the stall ledger's totals) and from the runners that
have the rest (``write_job_files``, ``document_mix``).

What is this file's own, and why: ``reference_check``, the trained weights'
forward against ``reference/granite_hybrid.py`` and the LAST mamba layer's
recurrence held on its own, forward (``scan_rel_l2``) AND backward
(``scan_grad_rel_l2``: the routed scan's own six gradients against
``jax.grad`` of the reference's token-by-token ``ssd``), on the program's
own operands, without the skip, before anything is rounded to the compute
type, with the second control beside them (the same recurrence with its
state rounded to bf16 after every token has to lie outside both limits, as
the float8 control lies outside ``update_cosine``); ``scan_leaves``, the
small leaves that only the scan's gradients feed, which ``step_check`` holds
by group in the timed step's own first update; ``expected_first_loss`` for
logits divided by ``logits_scaling``; the mamba layers' mean decay and mean
step per display, in the facts and in ``run["lm"]`` (``ssd_decay_mean``).

The per-layer readers get the keys ``lm_train`` hands them, ONE SEQUENCE as
the sample; ``lm`` holds what the token cells' readers add, under the keys
every token runner shares (``lm_trace``: ``scopes``, the configuration's
layer-name patterns by part; the required work; the display rows' series).
"""

from __future__ import annotations

import math
import os
import sys

import device as device_mod
import flops_granite
import tokengen
from runners.lm_train import document_mix
from runners.token_checks import (compared, display_series, reference_of,
                                  rel, series_mean, stall_totals, step_check,
                                  train_window)
from runners.zaya_train import write_job_files

# the keys of the model's config.json the benchmark computes from
MODEL_KEYS = ("hidden_size", "shared_intermediate_size", "num_hidden_layers",
              "num_attention_heads", "num_key_value_heads", "mamba_n_heads",
              "mamba_d_head", "mamba_d_state", "mamba_n_groups",
              "mamba_expand", "mamba_d_conv", "vocab_size", "rms_norm_eps",
              "embedding_multiplier", "attention_multiplier",
              "residual_multiplier", "logits_scaling", "layers_run")


def refuse_old_program(cell: str) -> None:
    """A program from before the model (no ``zoo.granite_hybrid``, so no
    SSD_SCAN): fail at once, exit 2."""
    from poseidon_tpu.models import zoo
    if not hasattr(zoo, "granite_hybrid"):
        print(f"[benchmark] REFUSING: this program has no "
              f"models/zoo.granite_hybrid; it cannot run {cell!r}. Nothing "
              f"was measured.", file=sys.stderr)
        raise SystemExit(2)


def reference_sizes(model: dict) -> dict:
    """The reference's ``cfg``: the configuration's own keys, the layers
    that are run in ``layer_types``' place."""
    return {**{k: model[k] for k in MODEL_KEYS if k != "layers_run"},
            "layer_types": model["layers_run"]["layer_types"]}


def expected_first_loss(cfg: dict, model: dict) -> float:
    """Fresh weights know nothing of the targets: ln V + var / 2 with var
    the variance of a logit, a unit-RMS state against a row of the tied
    std-``init_std`` table, divided by ``logits_scaling`` (the
    configuration's ``first_loss_why``)."""
    return math.log(model["vocab_size"]) \
        + cfg["init_std"] ** 2 * model["hidden_size"] \
        / model["logits_scaling"] ** 2 / 2


def reference_check(job: dict, params: dict, net_path: str, model: dict,
                    seq: int):
    """The program's forward (the run's numeric policy) against the plain
    reference on ONE seeded whole-length sequence and the trained weights
    (``params``, still on the device): logits at the last
    ``reference_positions`` positions against the whole context, and the
    loss over every position (where the tolerance has a limit for it: under
    bf16 it is a fact only). And the LAST mamba layer's recurrence alone,
    FORWARD AND BACKWARD: the program's scan (``ops/ssd.ssd_scan``, the arm
    ``ssd_route`` gives the layer, through its own ``custom_vjp``) on the
    layer's own operand blobs, against the reference's token-by-token
    ``ssd`` and ``jax.grad`` of it on the same operands. Both sides take the
    blobs' values in f32 (y and each gradient come in their operand's type:
    the layer's one rounding to the compute type would otherwise be all the
    numbers read) and NO skip (D = 0: ``D x`` is exact and can only dilute
    what the state contributes, most of y on fresh weights and little of it
    on the trained operands: ``scan_rel_l2_with_skip`` and
    ``scan_skip_norm_over_y`` are facts; d D does not depend on D).
    ``scan_rel_l2`` is y's distance, ``scan_grad_rel_l2`` the WORST of the
    six gradients' (d x, d dt, d a, d B, d C, d D, each on its own norm)
    under one seeded cotangent: what holds ``ssd_scan_bwd`` at the timed
    sizes. Beside them, as facts: the reference with its matmul inputs
    rounded to ``reference_lower_precision`` and the recurrence with its
    state (and, through ``jax.grad``, the state's cotangent) rounded to
    bf16 after every token, each of which has to lie outside a limit.
    Called with the Engine closed and its solver state dropped."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from poseidon_tpu.core.net import Net
    from poseidon_tpu.ops.ssd import ssd_scan
    from poseidon_tpu.proto.messages import load_net

    cfg = job["config"]
    ref, tol = reference_of(job)
    last = min(int(cfg["reference_positions"]), seq)
    made = tokengen.packed_sequences(job["seed"] + 7919, 1, seq,
                                     model["vocab_size"], document_mix(job))
    tokens, targets = jnp.asarray(made["data"]), jnp.asarray(made["label"])
    net = Net(load_net(net_path), "TRAIN",
              source_shapes={"tokens": (1, seq), "targets": (1, seq)})

    # the last mamba layer's recurrence, held on its own: its operands as
    # the program's blobs (the prototxt's top names)
    at = max(i for i, kind in enumerate(model["layers_run"]["layer_types"])
             if kind == "mamba")
    scan_tops = [f"l{at}_{top}" for top in ("xs", "dt", "a", "B", "C")]
    heads = model["mamba_n_heads"]
    no_skip = jnp.zeros((heads,), jnp.float32)
    d_y = jnp.asarray(np.random.default_rng(job["seed"]).standard_normal(
        (seq, heads, model["mamba_d_head"]), np.float32))

    def program(p, tok, tgt):
        out = net.apply(p, {"tokens": tok, "targets": tgt}, train=False,
                        keep_blobs=True)
        x, dt, a, b, c = (out.blobs[top].astype(jnp.float32)
                          for top in scan_tops)
        scan_in = (x.reshape(x.shape[:2] + (heads, -1)), dt, a, b, c)
        # SSD_SCAN's call and its backward, on the blobs' values in f32
        y, pull = jax.vjp(ssd_scan, *scan_in, no_skip)
        return {"loss": out.loss, "logits": out.blobs["logits"][:, -last:],
                "scan": (y[0],) + tuple(
                    g if g.ndim == 1 else g[0] for g in pull(d_y[None])),
                "scan_in": tuple(t[0] for t in scan_in)}

    def host(out):
        return jax.tree.map(lambda v: np.asarray(v, np.float32), out)

    got = jax.jit(program)(params, tokens, targets)
    scan_in = got.pop("scan_in")
    got = host(got)
    # the same device arrays under the reference's names and blob order
    weights = {l.name: [params[l.name][p.name] for p in l.params]
               for l in net.layers if params.get(l.name)}
    sizes = reference_sizes(model)

    def reference(w, **how):
        total, out = ref.loss(sizes, w, tokens, targets, last=last,
                              q_block=last, **how)
        return {"loss": total, "logits": out["logits"]}

    t_block = next(b for b in (128, 64, 32, 16, 8, 4, 2, 1) if seq % b == 0)

    def recurrence(operands, state_dtype=None):
        """``ref.ssd`` on the program's own operands, one sequence, and its
        gradients under ``d_y``: -> (y, d x, d dt, d a, d B, d C, d D), the
        state f32 or rounded to ``state_dtype`` after every token."""
        rounded = (lambda s: s) if state_dtype is None \
            else (lambda s: ref.narrowed(s, state_dtype))

        def pulled(*ops):
            y = ref.ssd(*ops, t_block=t_block, ckpt=jax.checkpoint,
                        state_round=rounded)
            return jnp.sum(y * d_y), y

        grads, y = jax.grad(pulled, argnums=tuple(range(6)), has_aux=True)(
            *operands, no_skip)
        return (y,) + grads

    def scan_rels(one, other):
        names = ("y", "d_x", "d_dt", "d_a", "d_B", "d_C", "d_D")
        return {n: rel(a, b) for n, a, b in zip(names, one, other)}

    want = host(jax.jit(reference)(weights))
    low = host(jax.jit(lambda w: reference(w, round_to=getattr(
        jnp, cfg["reference_lower_precision"])))(weights))
    scan_want = host(jax.jit(recurrence)(scan_in))
    scan_low = host(jax.jit(lambda x: recurrence(x, jnp.bfloat16))(scan_in))
    scan, scan_control = scan_rels(got["scan"], scan_want), \
        scan_rels(scan_low, scan_want)
    # what the same distance reads with the exact skip in y (as it was
    # compared until the review round): D x + the state's part
    skip_x = np.asarray(params[f"l{at}_ssd_scan"]["D"], np.float32)[
        None, :, None] * np.asarray(scan_in[0], np.float32)
    facts = {"loss_program": float(got["loss"]),
             "loss_reference": float(want["loss"]),
             "logits_rel_l2": rel(got["logits"], want["logits"]),
             "scan_layer": f"l{at}_ssd_scan",
             "scan_rel_l2": scan.pop("y"),
             "scan_grad_rel_l2": max(scan.values()),
             "scan_grads_rel_l2": scan,
             "scan_rel_l2_with_skip": rel(got["scan"][0] + skip_x,
                                          scan_want[0] + skip_x),
             "scan_skip_norm_over_y": float(
                 np.linalg.norm(skip_x.astype(np.float64))
                 / np.linalg.norm((scan_want[0] + skip_x).astype(
                     np.float64))),
             "lower_precision": cfg["reference_lower_precision"],
             "lower_precision_rel_l2": rel(low["logits"], want["logits"]),
             "lower_precision_loss": float(low["loss"]),
             "state_control": {"state": "bfloat16",
                               "scan_rel_l2": scan_control.pop("y"),
                               "scan_grad_rel_l2": max(
                                   scan_control.values()),
                               "scan_grads_rel_l2": scan_control},
             "sequences": 1, "positions": last, "context": seq,
             "tolerance": tol}
    facts["loss_rel"] = abs(
        facts["loss_program"] - facts["loss_reference"]) \
        / abs(facts["loss_reference"])
    ok = math.isfinite(facts["loss_program"]) \
        and facts["logits_rel_l2"] <= tol["logits_rel_l2"] \
        and facts["scan_rel_l2"] <= tol["scan_rel_l2"] \
        and facts["scan_grad_rel_l2"] <= tol["scan_grad_rel_l2"] \
        and (tol["loss_rel"] is None or facts["loss_rel"] <= tol["loss_rel"])
    return facts, ok


def scan_leaves(model: dict) -> dict:
    """The leaves that nothing but the scan's own gradients feed, as
    ``token_checks.grouped_cosines`` takes them, every mamba layer's as ONE
    vector: ``A_log`` behind d a alone, ``dt_bias`` behind d dt (and d a
    through a = dt A), ``D`` behind d D, and the convolution's taps and bias
    by channel, the B and C channels behind d B and d C, the H P value
    channels behind d x. Each lies under ``cosine_from`` (64 and 4 x 4,352
    numbers a layer) and the step rows they pass through are 320 of the
    8,512 rows of a 17M-number leaf: a scan backward with one of its six
    gradients wrong would pass every other limit of the step."""
    inner = model["mamba_n_heads"] * model["mamba_d_head"]
    keys = (inner, inner + 2 * model["mamba_d_state"])
    return {"d_a": [("_ssd_decay", 0, None)],
            "d_dt": [("_ssd_decay", 1, None)],
            "d_D": [("_ssd_scan", 0, None)],
            "d_BC": [("_ssd_conv", 0, keys), ("_ssd_conv", 1, keys)],
            "d_x": [("_ssd_conv", 0, (0, inner)), ("_ssd_conv", 1, (0, inner))]}


def run(job: dict) -> dict:
    cfg, traffic, cell = job["config"], job["traffic"], job["cell"]
    chips, tiny = int(cell["chips"]), job["tiny"]
    refuse_old_program(cell["name"])
    model = {k: cfg[k] for k in MODEL_KEYS}
    if tiny:
        model.update(cfg["cpu_tiny"]["sizes"])
    batch = cfg["cpu_tiny"]["batch_per_chip"] if tiny \
        else int(cell["batch_per_chip"])
    seq = cfg["cpu_tiny"]["seq_len"] if tiny else int(traffic["seq_len"])
    mamba = model["layers_run"]["layer_types"].count("mamba")

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
    per_token = flops_granite.required_flops_per_token(model, seq)
    flops_per_sequence = per_token["total"] * seq
    want_first = expected_first_loss(cfg, model)

    argv = [a.format(solver=solver_path,
                     output_dir=os.path.join(work, "out"))
            for a in traffic["argv"]]
    ran = train_window(job, argv, work, dev["platform"])
    window, seconds, rows = ran["window"], ran["seconds"], ran["rows"]
    first_loss = ran["step"]["loss"]
    # ---- correct? (outside every timed region) -------------------------- #
    ref_facts, ref_ok = reference_check(job, ran.pop("params"), net_path,
                                        model, seq)
    # the device is the reference's own now
    step_facts, step_ok = step_check(job, reference_sizes(model), seq,
                                     ran.pop("step"), scan_leaves(model))

    decay = display_series(rows, "_ssd_decay_mean")
    steps = display_series(rows, "_ssd_dt_mean")
    sections = ran["stats"]["sections"]
    place = sections.get("placement", {})
    low, high = cfg["first_loss_band"]
    checks = {
        "losses_finite": bool(window["losses"]) and all(
            math.isfinite(v) for v in window["losses"]),
        "first_loss": low * want_first <= first_loss <= high * want_first,
        "no_compile_in_window": ran["compiles"] == 0,
        "batch_on_every_chip": len(set(str(place.get(
            "batch_shard_devices", "")).split(","))) == chips
        and int(place.get("param_devices", 0)) == chips,
        "reference": ref_ok,
        "step_reference": step_ok,
        "no_failed_step": window["failed"] == 0,
        "decay_published": len(decay) == mamba and all(
            0.0 < v < 1.0 for vals in decay.values() for v in vals),
        "dt_published": len(steps) == mamba and all(
            0.0 < v and math.isfinite(v)
            for vals in steps.values() for v in vals),
    }
    # per chip: ``batch`` is the sequences ONE chip takes a step
    sequences_per_s = (window["attempted"] - window["failed"]) * batch \
        / seconds
    intervals = [b - a for a, b in zip(window["stamps"],
                                       window["stamps"][1:])]
    # the sample of images_per_s_per_chip is here ONE SEQUENCE, as in the
    # other token cells
    end_to_end = {"setup_s": ran["setup_s"],
                  "images_per_s_per_chip": sequences_per_s}
    if peak:
        end_to_end["mfu_required"] = \
            100.0 * sequences_per_s * flops_per_sequence / peak
    routes = sorted(set(sections.get("kernel_routes", {}).values()))
    state = ref_facts["state_control"]
    facts = {"first_loss": first_loss, "first_loss_expected": want_first,
             "window_losses": window["losses"][-3:], "reference": ref_facts,
             "step_reference": step_facts,
             "checks": checks, "steps": window["attempted"],
             "window_s": seconds, "step_s_warmup": ran["step_s"],
             "first_step_s": ran["first_step_s"],
             "display_intervals_s": intervals,
             # a traced run's stall ledger (the recorder is on): what a
             # slow window lost, and to what
             "stalls": stall_totals(ran["stats"]),
             "batch_per_chip": batch, "seq_len": seq,
             "tokens_per_s_per_chip": sequences_per_s * seq,
             "flops_per_token": per_token, "token_file": data,
             # a layer's last display, and the window's mean over layers
             "decay_mean": {top: vals[-1:] for top, vals in decay.items()},
             "decay_mean_window": series_mean(decay),
             "dt_mean": {top: vals[-1:] for top, vals in steps.items()},
             "dt_mean_window": series_mean(steps),
             "kernel_routes": routes,
             "recurrent_state": sections.get("recurrent_state", {}),
             "compiled_step": sections.get("compiled_step", {}),
             "remat": {k: v for k, v in sections.get("remat", {}).items()
                       if k not in ("layers", "segments")},
             "remat_segments": len(sections.get("remat", {}).get(
                 "segments", ())),
             "placement": place,
             # LAST in the line: what was compared, each beside its limit
             "compared": compared(
                 ref_facts["tolerance"],
                 (first_loss / want_first, low, high),
                 [(k, ref_facts[k]) for k in (
                     "logits_rel_l2", "scan_rel_l2", "scan_grad_rel_l2",
                     "loss_rel")],
                 step_facts,
                 [("float8_logits_rel_l2",
                   ref_facts["lower_precision_rel_l2"], ">",
                   "logits_rel_l2"),
                  ("float8_update_cosine",
                   step_facts["lower_precision_update_cosine"], "<",
                   "update_cosine"),
                  ("bf16_state_scan_rel_l2", state["scan_rel_l2"], ">",
                   "scan_rel_l2"),
                  ("bf16_state_scan_grad_rel_l2", state["scan_grad_rel_l2"],
                   ">", "scan_grad_rel_l2")])}
    return {
        "correct": all(checks.values()),
        "attempted": window["attempted"], "failed": window["failed"],
        "device": dict(dev, memory_peak_bytes=ran["memory_peak"]),
        "end_to_end": end_to_end,
        "facts": facts,
        # what the per-layer readers (layer_metrics/*.py) reduce: the keys
        # caffe_train hands them, one sequence as the sample, plus "lm"
        "layers": {"steps": window["attempted"], "window_s": seconds,
                   "setup_s": ran["setup_s"],
                   "batch_per_chip": batch,
                   "flops_per_image": flops_per_sequence,
                   "peak_flops_per_s": peak,
                   "compiles_in_window": ran["compiles"],
                   "display_intervals_s": intervals,
                   "spans": ran["spans"], "stats": ran["stats"],
                   "memory_peak_bytes": ran["memory_peak"],
                   "trace": ran["trace"],
                   "lm": {"seq_len": seq,
                          "flops_per_step": {
                              k: v * seq * batch
                              for k, v in per_token.items()},
                          "flash_per_step":
                              flops_granite.flash_attention_step(
                                  model, batch, seq),
                          "ssd_scan_per_step":
                              flops_granite.ssd_scan_step(
                                  model, batch, seq),
                          # every display's mean exp(a), all mamba layers
                          "ssd_decay_mean": [v for vals in decay.values()
                                             for v in vals],
                          "peaks": peaks,
                          "scopes": cfg["scopes"],
                          "kernel_routes": routes}},
    }
