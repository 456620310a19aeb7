"""Runner: one Olmo-Hybrid training job as ONE of 2 chips that share each
layer's HEADS (a prototxt of EMBED / RMS_NORM / INNER_PRODUCT / SHORT_CONV /
L2_NORM / KDA_DECAY on a per-head bottom / SIGMOID / POWER / KDA_SCAN with
one decay a head / SILU_GATE / ATTENTION without positions / ELTWISE
layers, every mixer's projections emitting the held heads' columns only),
driven through the program's own ``train`` command exactly as
``kimi_train`` drives its model, whose pieces (and ``zaya_train``'s,
``lm_train``'s and ``caffe_train``'s) it reuses: the token file,
``build_engine``, ``LmdbFeed``, ``CompileCounter``, ``trace_window``,
``write_job_files``, ``first_step``, ``expected_first_loss``.

What is this file's own, and why: the model is dense, so the two
comparisons that decide ``correct`` hand over no expert choice and compare
no selection bias (``reference_check`` on the trained weights,
``step_check`` on the timed path's first step: ``trinity_train``'s without
the routers, against ``reference/olmo_hybrid.py``); ``scan_rel_l2``, the
last linear layer's recurrence held on its own, BEFORE o is rounded to the
compute type, against the reference's ``delta_rule`` on the program's own
operands, and the second control beside it (the same recurrence with its
state rounded to bf16 after every token has to lie outside that limit, as
the float8 control lies outside the logits': the bf16 state reads BELOW the
program's own bf16 noise in the logits, so the logits cannot hold the state
to f32); ``gate_cosine``, the direction of the first update of the leaves
that only the scan's d g and d beta feed (``GATE_LEAVES``: too small for
``cosine_from``, and a norm cannot see a sign); the linear layers' mean
decay and share of beta > 1 per display, in the facts (facts of the run, no
metric: nothing a later PR is meant to move); and ``compared``, every number
that decided ``correct`` beside its limit, LAST in the facts line.

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
import flops_olmo_hybrid
import tokengen
from runners.caffe_train import (CompileCounter, LmdbFeed, build_engine,
                                 trace_window)
from runners.lm_train import document_mix
from runners.zaya_train import (expected_first_loss, first_step,
                                write_job_files)

# the keys of the model's config.json the benchmark computes from
MODEL_KEYS = ("hidden_size", "intermediate_size", "num_hidden_layers",
              "num_attention_heads", "num_key_value_heads",
              "attention_head_dim", "linear_num_key_heads",
              "linear_num_value_heads", "linear_key_head_dim",
              "linear_value_head_dim", "linear_conv_kernel_dim",
              "vocab_size", "rms_norm_eps", "layers_run")
# the leaves that nothing but the scan's gradient of g, and of beta, feeds,
# by layer-name suffix: W_a, A_log and dt_bias behind d g, W_b behind d beta
GATE_LEAVES = {"d_g": ("_gdn_a", "_gdn_decay"), "d_beta": ("_gdn_b",)}


def refuse_old_program(cell: str) -> None:
    """A program from before the model (no ``zoo.olmo_hybrid``, so no
    KDA_SCAN that takes one decay a head): fail at once, exit 2."""
    from poseidon_tpu.models import zoo
    if not hasattr(zoo, "olmo_hybrid"):
        print(f"[benchmark] REFUSING: this program has no "
              f"models/zoo.olmo_hybrid; it cannot run {cell!r}. Nothing was "
              f"measured.", file=sys.stderr)
        raise SystemExit(2)


def reference_sizes(model: dict) -> dict:
    return {"num_hidden_layers": model["num_hidden_layers"],
            "layer_types": model["layers_run"]["layer_types"],
            "num_heads": model["linear_num_key_heads"],
            "rms_norm_eps": model["rms_norm_eps"]}


def _reference(job: dict):
    cfg = job["config"]
    ref = importlib.import_module(f"reference.{cfg['reference']}")
    tol = (ref.TOLERANCE_TINY if job["tiny"] else ref.TOLERANCE)[
        job["traffic"]["precision"]]
    return ref, tol


def _rel(a, b) -> float:
    import numpy as np
    return float(np.linalg.norm((a - b).astype(np.float64))
                 / max(np.linalg.norm(b.astype(np.float64)), 1e-30))


def gate_cosines(got: dict, other: dict) -> dict:
    """{group of ``GATE_LEAVES``: the cosine between two steps' changes
    ({layer: [blobs]}) of that group's leaves, all the linear layers' as ONE
    vector}."""
    import numpy as np

    def as_one(changes, suffixes):
        return np.concatenate([
            np.asarray(b, np.float64).ravel()
            for name in sorted(changes) if name.endswith(suffixes)
            for b in changes[name]])

    out = {}
    for group, suffixes in GATE_LEAVES.items():
        a, b = as_one(got, suffixes), as_one(other, suffixes)
        out[group] = float(a @ b / max(
            np.linalg.norm(a) * np.linalg.norm(b), 1e-300))
    return out


def reference_check(job: dict, params: dict, net_path: str, model: dict,
                    seq: int):
    """The program's forward (the run's numeric policy) against the plain
    reference on ONE seeded whole-length sequence and the trained weights
    (``params``, still on the device): logits at the last
    ``reference_positions`` positions against the whole context, and the
    loss over every position (where the tolerance has a limit for it: under
    bf16 it is a fact only); and the LAST linear layer's recurrence alone,
    the program's scan (``ops/kda.kda_scan``, the arm ``kda_route`` gives
    the layer) on the layer's own operand blobs with o left in f32 — the
    layer's one rounding of o to the compute type would otherwise be all the
    number reads — against the reference's token-by-token ``delta_rule`` on
    the same operands (``scan_rel_l2``: what holds the state to f32, which
    the logits cannot see here). Beside them, as facts: the reference with
    its matmul inputs rounded to ``reference_lower_precision`` and the
    recurrence with its state rounded to bf16 after every token, each of
    which has to lie outside a limit. Called with the Engine closed and its
    solver state dropped."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from poseidon_tpu.core.net import Net
    from poseidon_tpu.ops.kda import kda_scan
    from poseidon_tpu.proto.messages import load_net

    cfg = job["config"]
    ref, tol = _reference(job)
    last = min(int(cfg["reference_positions"]), seq)
    made = tokengen.packed_sequences(job["seed"] + 7919, 1, seq,
                                     model["vocab_size"], document_mix(job))
    tokens, targets = jnp.asarray(made["data"]), jnp.asarray(made["label"])
    net = Net(load_net(net_path), "TRAIN",
              source_shapes={"tokens": (1, seq), "targets": (1, seq)})

    # the last linear layer's recurrence, held on its own: its operands as
    # the program's blobs (the prototxt's top names)
    at = max(i for i, kind in enumerate(model["layers_run"]["layer_types"])
             if kind == "linear")
    scan_tops = [f"l{at}_{top}" for top in ("qn", "kn", "vc", "gdec", "beta")]
    heads = model["linear_num_key_heads"]

    def program(p, tok, tgt):
        out = net.apply(p, {"tokens": tok, "targets": tgt}, train=False,
                        keep_blobs=True)
        q, k, v, g, beta = scan_in = [out.blobs[top] for top in scan_tops]
        split = lambda x: x.reshape(x.shape[:2] + (heads, -1))
        # KDA_SCAN's call, but for v's type: o comes in it, so f32
        o = kda_scan(split(q), split(k), split(v).astype(jnp.float32), g,
                     beta)
        return {"loss": out.loss, "logits": out.blobs["logits"][:, -last:],
                "scan_out": o.reshape(o.shape[:2] + (-1,)),
                "scan_in": scan_in}

    def host(out):
        return {k: np.asarray(v, np.float32) for k, v in out.items()}

    got = jax.jit(program)(params, tokens, targets)
    scan_in = got.pop("scan_in")
    got = host(got)
    # the same device arrays under the reference's names and blob order
    weights = {l.name: [params[l.name][p.name] for p in l.params]
               for l in net.layers if l.name in params}
    sizes = reference_sizes(model)

    def reference(w, **how):
        total, out = ref.loss(sizes, w, tokens, targets, last=last,
                              q_block=last, **how)
        return {"loss": total, "logits": out["logits"]}

    def recurrence(operands, state_dtype=None):
        """``ref.delta_rule`` on the program's own operands, one sequence:
        (1, S, H d) blobs -> (S, H d_v), the state f32 or rounded to
        ``state_dtype`` after every token."""
        q, k, v, g, beta = (x[0].astype(jnp.float32) for x in operands)
        split = lambda x: x.reshape(seq, heads, -1)
        rounded = (lambda s: s) if state_dtype is None \
            else (lambda s: ref.narrowed(s, state_dtype))
        return ref.delta_rule(split(q), split(k), split(v), g, beta,
                              state_round=rounded).reshape(seq, -1)

    want = host(jax.jit(reference)(weights))
    low = host(jax.jit(lambda w: reference(w, round_to=getattr(
        jnp, cfg["reference_lower_precision"])))(weights))
    scan_want = np.asarray(jax.jit(recurrence)(scan_in), np.float32)
    scan_low = np.asarray(jax.jit(lambda x: recurrence(x, jnp.bfloat16))(
        scan_in), np.float32)
    facts = {"loss_program": float(got["loss"]),
             "loss_reference": float(want["loss"]),
             "logits_rel_l2": _rel(got["logits"], want["logits"]),
             "scan_layer": f"l{at}_gdn_scan",
             "scan_rel_l2": _rel(got["scan_out"][0], scan_want),
             "lower_precision": cfg["reference_lower_precision"],
             "lower_precision_rel_l2": _rel(low["logits"], want["logits"]),
             "lower_precision_loss": float(low["loss"]),
             "state_control": {"state": "bfloat16",
                               "scan_rel_l2": _rel(scan_low, scan_want)},
             "sequences": 1, "positions": last, "context": seq,
             "tolerance": tol}
    loss_rel = abs(facts["loss_program"] - facts["loss_reference"]) \
        / abs(facts["loss_reference"])
    ok = math.isfinite(facts["loss_program"]) \
        and facts["logits_rel_l2"] <= tol["logits_rel_l2"] \
        and facts["scan_rel_l2"] <= tol["scan_rel_l2"] \
        and (tol["loss_rel"] is None or loss_rel <= tol["loss_rel"])
    return facts, ok


def step_check(job: dict, model: dict, seq: int, step: dict):
    """The Engine's own compiled step against the reference's: ``step``
    holds the seeded weights (``before``), the change the run's FIRST step
    made to every leaf (``change``), that step's loss, its batch and the
    solver's numbers, all on the host. The reference takes the same step in
    f32 (``train_step``), and once more with its matmul inputs rounded to
    ``reference_lower_precision``, which has to lie outside a limit. Decided
    by: the loss (where the tolerance has a limit for it: under bf16 it is a
    fact only); every leaf's change in norm (worst leaf); the direction of
    the change of every leaf of ``cosine_from`` numbers or more (worst
    cosine); and the direction of the change of the leaves behind the scan's
    d g and behind its d beta, each group's leaves of all the linear layers
    as ONE vector (``GATE_LEAVES``; the worse group: ``gate_cosine``). Those
    leaves (3840 x H, H and H numbers) lie under ``cosine_from``, Adam's
    first change of a leaf has the norm lr sqrt(n) whatever its direction,
    and W_a's std-0.02 entries pass too little of d g on into x for a larger
    leaf to show it: a d g or d beta of the wrong sign would pass every
    other limit."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    cfg = job["config"]
    ref, tol = _reference(job)
    opt = dict(step["opt"])
    first_rate = opt.pop("first_rate")
    sizes = reference_sizes(model)
    low_type = getattr(jnp, cfg["reference_lower_precision"])
    q_block = min(seq, int(cfg["reference_positions"]))

    @jax.jit
    def reference(w, tok, tgt, low):
        return ref.train_step(sizes, w, tok, tgt, opt, q_block=q_block,
                              remat=True, round_to=low_type, round_when=low)

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
        """Leaf by leaf: how far the norms of the two changes lie from each
        other, and for a leaf of ``cosine_from`` numbers or more the cosine
        between them; the worst of each first."""
        rows = []
        for name, blobs in other.items():
            for j, b in enumerate(blobs):
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
        gates = gate_cosines(got, other)
        return {"norm_rel": by_norm[0]["norm_rel"],
                "cosine": by_cosine[0]["cosine"] if by_cosine else 1.0,
                "gate_cosine": min(gates.values()), "gate_cosines": gates,
                "worst_by_norm": by_norm[:6], "worst_by_cosine": by_cosine[:6]}

    program = against(step["change"], want["change"])
    control = against(low["change"], want["change"])
    loss_rel = abs(step["loss"] - float(want["loss"])) \
        / abs(float(want["loss"]))
    facts = {"loss_program": step["loss"],
             "loss_reference": float(want["loss"]),
             "loss_rel": loss_rel,
             "update_norm_rel": program["norm_rel"],
             "update_cosine": program["cosine"],
             "gate_cosine": program["gate_cosine"],
             "gate_cosines": program["gate_cosines"],
             "worst_by_norm": program["worst_by_norm"],
             "worst_by_cosine": program["worst_by_cosine"],
             "grad_norm_reference": float(want["grad_norm"]),
             "first_rate": first_rate,
             "lower_precision": cfg["reference_lower_precision"],
             "lower_precision_loss_rel": abs(
                 float(low["loss"]) - float(want["loss"]))
             / abs(float(want["loss"])),
             "lower_precision_update_norm_rel": control["norm_rel"],
             "lower_precision_update_cosine": control["cosine"],
             "lower_precision_gate_cosine": control["gate_cosine"],
             "lower_precision_gate_cosines": control["gate_cosines"],
             "lower_precision_worst_by_cosine": control["worst_by_cosine"][:2],
             "sequences": int(tokens.shape[0]), "context": seq,
             "seconds": dict(took, compare_s=clock() - t),
             "tolerance": tol}
    ok = math.isfinite(step["loss"]) \
        and (tol["step_loss_rel"] is None
             or loss_rel <= tol["step_loss_rel"]) \
        and program["norm_rel"] <= tol["update_norm_rel"] \
        and program["cosine"] >= tol["update_cosine"] \
        and program["gate_cosine"] >= tol["gate_cosine"]
    return facts, ok


def compared(ref_facts: dict, step_facts: dict, first: tuple) -> list:
    """Every number that decided ``correct`` beside its limit, and the two
    controls beside the limits they have to break. ``first``: the first
    loss over its expectation, and the band's two ends."""
    tol = ref_facts["tolerance"]
    first_over, first_low, first_high = first
    loss_rel = abs(ref_facts["loss_program"] - ref_facts["loss_reference"]) \
        / abs(ref_facts["loss_reference"])
    rows = [("first_loss_over_expected", first_over, ">=", first_low),
            ("first_loss_over_expected", first_over, "<=", first_high),
            ("logits_rel_l2", ref_facts["logits_rel_l2"], "<=",
             tol["logits_rel_l2"]),
            ("scan_rel_l2", ref_facts["scan_rel_l2"], "<=",
             tol["scan_rel_l2"]),
            ("loss_rel", loss_rel, "<=", tol["loss_rel"]),
            ("step_loss_rel", step_facts["loss_rel"], "<=",
             tol["step_loss_rel"]),
            ("update_norm_rel", step_facts["update_norm_rel"], "<=",
             tol["update_norm_rel"]),
            ("update_cosine", step_facts["update_cosine"], ">=",
             tol["update_cosine"]),
            ("gate_cosine", step_facts["gate_cosine"], ">=",
             tol["gate_cosine"]),
            ("control_float8_logits_rel_l2",
             ref_facts["lower_precision_rel_l2"], ">", tol["logits_rel_l2"]),
            ("control_float8_update_cosine",
             step_facts["lower_precision_update_cosine"], "<",
             tol["update_cosine"]),
            ("control_float8_gate_cosine",
             step_facts["lower_precision_gate_cosine"], "<",
             tol["gate_cosine"]),
            ("control_bf16_state_scan_rel_l2",
             ref_facts["state_control"]["scan_rel_l2"], ">",
             tol["scan_rel_l2"])]
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
    batch = cfg["cpu_tiny"]["batch_per_chip"] if tiny \
        else int(cell["batch_per_chip"])
    seq = cfg["cpu_tiny"]["seq_len"] if tiny else int(traffic["seq_len"])
    display = int(traffic["display"])
    linear = model["layers_run"]["layer_types"].count("linear")

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
    per_token = flops_olmo_hybrid.required_flops_per_token(model, seq)
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
    # leave the device first, its weights stay for the checks) ------------ #
    params, eng.params, eng.state = eng.params, None, None
    del eng, feed
    ref_facts, ref_ok = reference_check(job, params, net_path, model, seq)
    del params                  # the device is the reference's own now
    step_facts, step_ok = step_check(job, model, seq, step)
    del step

    def per_layer(suffix):
        """{a layer's top: its value in every display that has it}"""
        tops = sorted({k for r in rows for k in r if k.endswith(suffix)})
        return {top: [r[top] for r in rows if top in r] for top in tops}

    def mean(by_layer):
        vals = [v for series in by_layer.values() for v in series]
        return sum(vals) / len(vals) if vals else None

    decay = per_layer("_decay_mean")
    over = per_layer("_beta_over_one")
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
        "decay_published": len(decay) == linear and all(
            0.0 < v < 1.0 for vals in decay.values() for v in vals),
        "beta_published": len(over) == linear and all(
            0.0 <= v <= 1.0 for vals in over.values() for v in vals),
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
             # a layer's last display, and the window's mean over layers
             "decay_mean": {top: vals[-1:] for top, vals in decay.items()},
             "decay_mean_window": mean(decay),
             "beta_over_one": {top: vals[-1:] for top, vals in over.items()},
             "beta_over_one_window": mean(over),
             "kernel_routes": routes,
             "recurrent_state": sections.get("recurrent_state", {}),
             "compiled_step": sections.get("compiled_step", {}),
             "remat": {k: v for k, v in sections.get("remat", {}).items()
                       if k not in ("layers", "segments")},
             "remat_segments": len(sections.get("remat", {}).get(
                 "segments", ())),
             "placement": place,
             # LAST in the line: what was compared, each beside its limit
             "compared": compared(ref_facts, step_facts,
                                  (first_loss / want_first, low, high))}
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
                              flops_olmo_hybrid.flash_attention_step(
                                  model, batch, seq),
                          "delta_scan_per_step":
                              flops_olmo_hybrid.gdn_scan_step(
                                  model, batch, seq),
                          "peaks": peaks,
                          "scopes": cfg["scopes"],
                          "kernel_routes": routes}},
    }
