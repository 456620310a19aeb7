"""Runner: one NVIDIA-Nemotron-3-Nano-30B-A3B training job as ONE of 16 chips
that share each layer, the first pipeline stage (a prototxt of EMBED /
RMS_NORM, plain and over groups of channels / INNER_PRODUCT / SLICE /
SHORT_CONV with a bias / KDA_DECAY with its step as a top / SSD_SCAN with
eight groups of B / C / SILU_GATE / ATTENTION without positions / MOE_ROUTER
with a sigmoid score / MOE layers of UNGATED experts holding part of what
their routers score / RELU / POWER / ELTWISE, one sub-layer a layer), driven
through the program's own ``train`` command as every token cell is. What a
token runner does whatever its model comes from the runners that have it:
the token file, ``build_engine``, ``LmdbFeed``, ``CompileCounter``,
``trace_window``, ``write_job_files``, ``document_mix``, ``first_step``,
``reference_of``, ``rel``, ``grouped_cosines``, ``compare_changes``, the
display rows' series and the stall ledger's totals.

What is this file's own, and why: ``train_window`` (``token_checks``' with the
routers' ``settle_displays`` before the timed display and the traced steps'
display rows kept); ``reference_check`` (the trained weights' forward against
``reference/nemotron_h.py``; the LAST Mamba-2 layer's GROUPED recurrence
alone, forward AND six gradients, on the program's own operands with the
bf16-state control beside it, as ``granite_train`` holds Granite's one
group; the FIRST sparse layer's routed part alone, the program's ``l<i>_m``
against the reference's dense loop over the held experts on the program's
own input and gates, with the float8 control beside it); ``step_check`` (the
timed path's first step against the reference's: SmallThinker's whole-update
and least-leaf cosines, Granite's groups of leaves that only the scan's
gradients feed); ``compared``.

The per-layer readers get the keys ``lm_train`` hands them, ONE SEQUENCE as
the sample; ``lm`` holds what the token cells' readers add, under the keys
every token runner shares (``lm_trace``: ``scopes``, the configuration's
layer-name patterns by part; the required work; the display rows' series).
"""

from __future__ import annotations

import math
import os
import shutil
import sys
import time

import device as device_mod
import flops_nemotron
import tokengen
from runners.caffe_train import (CompileCounter, LmdbFeed, build_engine,
                                 trace_window)
from runners.lm_train import document_mix
from runners.smallthinker_train import compare_changes
from runners.token_checks import (display_series, grouped_cosines,
                                  reference_of, rel, series_mean,
                                  stall_totals)
from runners.zaya_train import first_step, write_job_files

# the keys of the model's config.json the benchmark computes from
MODEL_KEYS = ("hidden_size", "num_hidden_layers", "mamba_num_heads",
              "mamba_head_dim", "ssm_state_size", "n_groups", "conv_kernel",
              "num_attention_heads", "num_key_value_heads", "head_dim",
              "n_routed_experts", "router_num_experts",
              "num_experts_per_tok", "n_shared_experts",
              "moe_intermediate_size", "moe_shared_expert_intermediate_size",
              "routed_scaling_factor", "bias_update_rate", "vocab_size",
              "norm_eps", "layers_run")


def refuse_old_program(cell: str) -> None:
    """A program from before the model (no ``zoo.nemotron_h``, so no groups
    of B / C in SSD_SCAN and no ungated expert): fail at once, exit 2."""
    from poseidon_tpu.models import zoo
    if not hasattr(zoo, "nemotron_h"):
        print(f"[benchmark] REFUSING: this program has no "
              f"models/zoo.nemotron_h; it cannot run {cell!r}. Nothing was "
              f"measured.", file=sys.stderr)
        raise SystemExit(2)


def reference_sizes(model: dict) -> dict:
    """The reference's ``cfg`` from the configuration's own keys."""
    return {"pattern": model["layers_run"]["pattern"],
            "mamba_num_heads": model["mamba_num_heads"],
            "ssm_state_size": model["ssm_state_size"],
            "n_groups": model["n_groups"],
            "num_attention_heads": model["num_attention_heads"],
            "num_key_value_heads": model["num_key_value_heads"],
            "head_dim": model["head_dim"],
            "num_experts": model["router_num_experts"],
            "num_experts_per_tok": model["num_experts_per_tok"],
            "routed_scaling_factor": model["routed_scaling_factor"],
            "bias_update_rate": model["bias_update_rate"],
            "held_first": 0, "norm_eps": model["norm_eps"]}


def expected_first_loss(cfg: dict, model: dict) -> float:
    """Fresh weights know nothing of the targets: ln V + var / 2 with var
    the variance of a logit, a unit-RMS state against a row of the
    std-``init_std`` untied head (the configuration's ``first_loss_why``)."""
    return math.log(model["vocab_size"]) \
        + cfg["init_std"] ** 2 * model["hidden_size"] / 2


def scan_leaves(model: dict) -> dict:
    """The leaves that nothing but the scan's own gradients feed, as
    ``token_checks.grouped_cosines`` takes them, every Mamba-2 layer's as
    ONE vector (``granite_train.scan_leaves`` with G groups of B / C):
    ``A_log`` behind d a alone, ``dt_bias`` behind d dt, ``D`` behind d D,
    and the convolution's taps and bias by channel, the 2 G N channels of B
    and C behind d B and d C (a sum over a GROUP's heads: a gradient summed
    over all heads, or over the wrong group, turns this vector), the H P
    value channels behind d x."""
    inner = model["mamba_num_heads"] * model["mamba_head_dim"]
    keys = (inner, inner + 2 * model["n_groups"] * model["ssm_state_size"])
    return {"d_a": [("_ssd_decay", 0, None)],
            "d_dt": [("_ssd_decay", 1, None)],
            "d_D": [("_ssd_scan", 0, None)],
            "d_BC": [("_ssd_conv", 0, keys), ("_ssd_conv", 1, keys)],
            "d_x": [("_ssd_conv", 0, (0, inner)), ("_ssd_conv", 1, (0, inner))]}


def train_window(job: dict, argv: list, work: str, platform: str) -> dict:
    """The job through the program's own ``train`` command: warm-up to 1,
    ``display``, ``settle_displays`` more displays (the routers' selection
    biases start to move) and one timed display (all of it set-up; the first
    step's change of every leaf is kept for ``step_check``), then the
    measured window of whole displays nearest ``job["seconds"]``, opened
    and closed on a hard sync, and with ``--trace`` the traced steps after
    it. The Engine is closed, its solver state dropped; its weights
    (``params``) stay on the device for the forward comparison."""
    from poseidon_tpu.runtime.spans import recorder
    clock, traffic = time.perf_counter, job["traffic"]
    display = int(traffic["display"])
    settle = display * max(1, int(traffic["settle_displays"]))
    eng = build_engine(argv)
    try:
        t = clock()
        step = first_step(eng, job["config"])
        first_step_s = clock() - t
        eng.train(max_iter=display)
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
        with CompileCounter() as compiles:
            t0 = clock()
            window = feed.steps(n_steps)
            seconds = clock() - t0
        out = {"step": step, "first_step_s": first_step_s, "step_s": step_s,
               "window": window, "seconds": seconds,
               "setup_s": t0 - job["t_start"], "compiles": compiles.count,
               "spans": recorder.trace_events() if job["trace"] else [],
               "stats": eng.stats.snapshot(),
               "memory_peak": device_mod.memory_peak_bytes(),
               "warm_rows": eng.metrics.rows[:rows_before],
               "rows": eng.metrics.rows[rows_before:], "trace": None,
               "traced_rows": []}
        if job["trace"]:
            kept = os.path.join(work, "trace")
            out["trace"] = trace_window(feed, int(traffic["trace_steps"]),
                                        platform, kept)
            recorder.disable()
            out["traced_rows"] = eng.metrics.rows[out["trace"]["rows_from"]:]
            if job.get("keep_trace"):
                shutil.copytree(kept, job["keep_trace"], dirs_exist_ok=True)
            shutil.rmtree(kept, ignore_errors=True)
    finally:
        eng.close()
    # the Engine's Adam moments leave the device, its weights stay
    out["params"], eng.params, eng.state = eng.params, None, None
    return out


def reference_check(job: dict, params: dict, net_path: str, model: dict,
                    seq: int):
    """The program's forward (the run's numeric policy) against the plain
    reference on ONE seeded whole-length sequence and the trained weights
    (``params``, still on the device): logits at the last
    ``reference_positions`` positions against the whole context, and the
    loss over every position. Two mechanisms held on their own, on the
    program's own operand blobs:

    the LAST Mamba-2 layer's recurrence, FORWARD AND BACKWARD: the program's
    scan (``ops/ssd.ssd_scan`` with B and C in their G groups, the arm
    ``ssd_route`` gives the layer, through its own ``custom_vjp``) against
    the reference's token-by-token ``ssd`` and ``jax.grad`` of it. Both
    sides take the blobs' values in f32 and NO skip (D = 0), as
    ``granite_train.reference_check`` does and for its reasons;
    ``scan_rel_l2`` is y's distance, ``scan_grad_rel_l2`` the WORST of the
    six gradients' (d x, d dt, d a, d B, d C, d D, each on its own norm)
    under one seeded cotangent; beside them the recurrence with its state
    rounded to bf16 after every token, which has to lie outside both;

    the FIRST sparse layer's routed part (the later ones hold next to no
    assignment once trained): the program's blob ``l<i>_m`` (the
    held experts' weighted sum as the timed arm made it) against the
    reference's dense loop over the held experts on the program's own input
    ``l<i>_n`` and the program's own gates ``l<i>_gates`` (so no expert
    choice differs: what is read is the ungated unit), ``routed_rel_l2``;
    beside it the same loop with its matmul inputs rounded to
    ``reference_lower_precision``, which has to lie outside the limit.
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
    pattern = model["layers_run"]["pattern"]
    # the last scan; the FIRST sparse layer: the later ones' fresh routers
    # send a trained model's tokens to no held expert (PERF.md 64)
    at_m, at_e = pattern.rindex("M"), pattern.index("E")
    scan_tops = [f"l{at_m}_{top}" for top in ("xs", "dt", "a", "B", "C")]
    heads, groups = model["mamba_num_heads"], model["n_groups"]
    no_skip = jnp.zeros((heads,), jnp.float32)
    d_y = jnp.asarray(np.random.default_rng(job["seed"]).standard_normal(
        (seq, heads, model["mamba_head_dim"]), np.float32))

    def program(p, tok, tgt):
        out = net.apply(p, {"tokens": tok, "targets": tgt}, train=False,
                        keep_blobs=True)
        x, dt, a, b, c = (out.blobs[top].astype(jnp.float32)
                          for top in scan_tops)
        by_group = lambda t: t.reshape(t.shape[:2] + (groups, -1))
        scan_in = (x.reshape(x.shape[:2] + (heads, -1)), dt, a,
                   by_group(b), by_group(c))
        # SSD_SCAN's call and its backward, on the blobs' values in f32
        y, pull = jax.vjp(ssd_scan, *scan_in, no_skip)
        return {"loss": out.loss, "logits": out.blobs["logits"][:, -last:],
                "scan": (y[0],) + tuple(
                    g if g.ndim == 1 else g[0] for g in pull(d_y[None])),
                "scan_in": tuple(t[0] for t in scan_in),
                "routed": out.blobs[f"l{at_e}_m"][0],
                "routed_in": (out.blobs[f"l{at_e}_n"][0],
                              out.blobs[f"l{at_e}_gates"][0])}

    def host(out):
        return jax.tree.map(lambda v: np.asarray(v, np.float32), out)

    got = jax.jit(program)(params, tokens, targets)
    scan_in, routed_in = got.pop("scan_in"), got.pop("routed_in")
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

    def dense_loop(y, gates, up, down, low=None):
        """The held experts' part on the program's own input and gates:
        every token through every held expert (``ref``'s unit, written
        out), f32 at HIGHEST or with the matmul inputs rounded to ``low``."""
        rnd = (lambda t: t) if low is None else (
            lambda t: ref.narrowed(t, low))
        with jax.default_matmul_precision("highest"):
            y, total = y.astype(jnp.float32), 0.0
            for j in range(up.shape[0]):
                h = jnp.square(jax.nn.relu(rnd(y) @ rnd(up[j]).T))
                total = total + gates[:, j][:, None] * (
                    rnd(h) @ rnd(down[j]).T)
        return total

    def scan_rels(one, other):
        names = ("y", "d_x", "d_dt", "d_a", "d_B", "d_C", "d_D")
        return {n: rel(a, b) for n, a, b in zip(names, one, other)}

    low_type = getattr(jnp, cfg["reference_lower_precision"])
    want = host(jax.jit(reference)(weights))
    low = host(jax.jit(lambda w: reference(w, round_to=low_type))(weights))
    scan_want = host(jax.jit(recurrence)(scan_in))
    scan_low = host(jax.jit(lambda x: recurrence(x, jnp.bfloat16))(scan_in))
    scan, scan_control = scan_rels(got["scan"], scan_want), \
        scan_rels(scan_low, scan_want)
    stacks = weights[f"l{at_e}_moe_experts"]
    routed_want = host(jax.jit(dense_loop)(*routed_in, *stacks))
    routed_low = host(jax.jit(
        lambda *t: dense_loop(*t, low=low_type))(*routed_in, *stacks))
    facts = {"loss_program": float(got["loss"]),
             "loss_reference": float(want["loss"]),
             "logits_rel_l2": rel(got["logits"], want["logits"]),
             "scan_layer": f"l{at_m}_ssd_scan",
             "scan_rel_l2": scan.pop("y"),
             "scan_grad_rel_l2": max(scan.values()),
             "scan_grads_rel_l2": scan,
             "routed_layer": f"l{at_e}_moe_experts",
             "routed_rel_l2": rel(got["routed"], routed_want),
             "routed_tokens_held": int(np.sum(
                 np.asarray(routed_in[1])[:, :stacks[0].shape[0]] > 0)),
             "lower_precision": cfg["reference_lower_precision"],
             "lower_precision_rel_l2": rel(low["logits"], want["logits"]),
             "lower_precision_routed_rel_l2": rel(routed_low, routed_want),
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
        and all(facts[k] <= tol[k] for k in (
            "logits_rel_l2", "scan_rel_l2", "scan_grad_rel_l2",
            "routed_rel_l2")) \
        and (tol["loss_rel"] is None or facts["loss_rel"] <= tol["loss_rel"])
    return facts, ok


def step_check(job: dict, model: dict, seq: int, step: dict):
    """The Engine's own compiled step against the reference's: ``step``
    holds the seeded weights (``before``), the change the run's FIRST step
    made to every leaf (``change``), that step's loss, its batch and the
    solver's numbers, all on the host (``zaya_train.first_step``). The
    reference takes the same step in f32 (``train_step``, free-running: the
    step publishes no expert choice; the selection biases by the sign rule
    on its own counts), and once more with its matmul inputs rounded to
    ``reference_lower_precision``. Decided by: the loss (where the
    tolerance has a limit for it), ``smallthinker_train.compare_changes``'
    three numbers (the worst leaf's change in norm, the direction of the
    WHOLE update, the least cosine of one leaf of ``cosine_from`` numbers or
    more) and the direction of each group of the leaves that only the
    scan's gradients feed (``scan_leaves``, all layers' as one vector)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    cfg = job["config"]
    ref, tol = reference_of(job)
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
    groups = scan_leaves(model)
    program = compare_changes(step["change"], want["change"],
                              tol["cosine_from"])
    control = compare_changes(low["change"], want["change"],
                              tol["cosine_from"])
    by_group = grouped_cosines(step["change"], want["change"], groups)
    low_by_group = grouped_cosines(low["change"], want["change"], groups)
    loss_rel = abs(step["loss"] - float(want["loss"])) \
        / abs(float(want["loss"]))
    counts = np.asarray(want["counts"])                       # (L, E)
    held = model["n_routed_experts"]
    facts = {"loss_program": step["loss"],
             "loss_reference": float(want["loss"]),
             "loss_rel": loss_rel,
             "update_norm_rel": program["norm_rel"],
             "update_cosine": program["cosine"],
             "leaf_cosine_min": program["leaf_cosine"],
             "leaves_compared": program["leaves"],
             "group_cosine": min(by_group.values()),
             "group_cosines": by_group,
             "worst_by_norm": program["worst_by_norm"],
             "worst_by_cosine": program["worst_by_cosine"],
             "reference_held_share": [float(c[:held].sum() / c.sum())
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
             "lower_precision_group_cosines": low_by_group,
             "lower_precision_worst_by_cosine": control["worst_by_cosine"][:2],
             "sequences": int(tokens.shape[0]), "context": seq,
             "seconds": dict(took, compare_s=clock() - t),
             "tolerance": tol}
    ok = math.isfinite(step["loss"]) \
        and (tol["step_loss_rel"] is None
             or loss_rel <= tol["step_loss_rel"]) \
        and program["norm_rel"] <= tol["update_norm_rel"] \
        and program["cosine"] >= tol["update_cosine"] \
        and program["leaf_cosine"] >= tol["leaf_cosine"] \
        and facts["group_cosine"] >= tol["group_cosine"]
    return facts, ok


def compared(ref_facts: dict, step_facts: dict, first: tuple) -> list:
    """Every number that decided ``correct`` beside its limit, then the
    controls beside the limits they have to break. ``first``: the first
    loss over its expectation and the band's two ends."""
    tol = ref_facts["tolerance"]
    first_over, first_low, first_high = first
    state = ref_facts["state_control"]
    rows = [("first_loss_over_expected", first_over, ">=", first_low),
            ("first_loss_over_expected", first_over, "<=", first_high)]
    rows += [(k, ref_facts[k], "<=", tol[k]) for k in (
        "logits_rel_l2", "scan_rel_l2", "scan_grad_rel_l2", "routed_rel_l2",
        "loss_rel")]
    rows += [("step_loss_rel", step_facts["loss_rel"], "<=",
              tol["step_loss_rel"]),
             ("update_norm_rel", step_facts["update_norm_rel"], "<=",
              tol["update_norm_rel"]),
             ("update_cosine", step_facts["update_cosine"], ">=",
              tol["update_cosine"]),
             ("leaf_cosine_min", step_facts["leaf_cosine_min"], ">=",
              tol["leaf_cosine"]),
             ("group_cosine", step_facts["group_cosine"], ">=",
              tol["group_cosine"]),
             ("control_float8_routed_rel_l2",
              ref_facts["lower_precision_routed_rel_l2"], ">",
              tol["routed_rel_l2"]),
             ("control_float8_logits_rel_l2",
              ref_facts["lower_precision_rel_l2"], ">",
              tol["logits_rel_l2"]),
             ("control_bf16_state_scan_rel_l2", state["scan_rel_l2"], ">",
              tol["scan_rel_l2"]),
             ("control_bf16_state_scan_grad_rel_l2",
              state["scan_grad_rel_l2"], ">", tol["scan_grad_rel_l2"])]
    ops = {"<=": lambda a, b: a <= b, ">=": lambda a, b: a >= b,
           ">": lambda a, b: a > b, "<": lambda a, b: a < b}
    return [{"name": name, "value": value, "must_be": op, "limit": limit,
             "holds": None if limit is None else bool(ops[op](value, limit)),
             "decides_correct": not name.startswith("control_")
             and limit is not None}
            for name, value, op, limit in rows]


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
    n = flops_nemotron.layers_run(model)

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
    per_token = flops_nemotron.required_flops_per_token(model, seq)
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
    step_facts, step_ok = step_check(job, model, seq, ran.pop("step"))

    def per_display(some_rows, suffix):
        """One mean over the layers a display row."""
        return [sum(vals) / len(vals) for vals in (
            [v for k, v in r.items() if k.endswith(suffix)]
            for r in some_rows) if vals]

    decay = display_series(rows, "_ssd_decay_mean")
    steps = display_series(rows, "_ssd_dt_mean")
    held_share = per_display(rows, "_held_share")
    held_by_layer = display_series(rows, "_held_share")
    zero_share = per_display(rows, "_act_zero_share")
    load = per_display(rows, "_expert_load")
    bias_max = per_display(rows, "_bias_max_abs")
    dropped = [v for r in rows for k, v in r.items()
               if k.endswith("_dropped")]
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
        "no_dropped_token": bool(dropped) and max(dropped) == 0.0,
        "held_share_published": len(held_by_layer) == n["sparse"]
        and all(0.0 <= s <= 1.0 for s in held_share),
        "act_zero_share_published": len(zero_share) == len(held_share)
        and all(0.0 <= s <= 1.0 for s in zero_share),
        "decay_published": len(decay) == n["mamba"] and all(
            0.0 < v < 1.0 for vals in decay.values() for v in vals),
        "dt_published": len(steps) == n["mamba"] and all(
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
    warm = ran["warm_rows"]
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
             "held_assignment_share": {
                 "even": model["n_routed_experts"]
                 / model["router_num_experts"],
                 "warm_up": per_display(warm, "_held_share"),
                 "mean": sum(held_share) / max(1, len(held_share)),
                 "per_display": held_share,
                 "per_layer_last_display": {
                     top: vals[-1:] for top, vals in held_by_layer.items()}},
             "held_expert_load_max_over_mean": {
                 "first_display": load[:1], "last_display": load[-1:]},
             "act_zero_share": {
                 "warm_up": per_display(warm, "_act_zero_share"),
                 "per_display": zero_share},
             "selection_bias_max_abs": {"first_display": bias_max[:1],
                                        "last_display": bias_max[-1:]},
             # a layer's last display, and the window's mean over layers
             "decay_mean": {top: vals[-1:] for top, vals in decay.items()},
             "decay_mean_window": series_mean(decay),
             "dt_mean": {top: vals[-1:] for top, vals in steps.items()},
             "dt_mean_window": series_mean(steps),
             "kernel_routes": routes,
             "recurrent_state": sections.get("recurrent_state", {}),
             "expert_share": sections.get("expert_share", {}),
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
                              flops_nemotron.flash_attention_step(
                                  model, batch, seq),
                          "ssd_scan_per_step":
                              flops_nemotron.ssd_scan_step(
                                  model, batch, seq),
                          "flops_per_assignment": flops_nemotron
                          .expert_flops_per_assignment(model),
                          "assignments_per_step": n["sparse"] * seq * batch
                          * model["num_experts_per_tok"],
                          # every display's mean exp(a), all Mamba-2 layers
                          "ssd_decay_mean": [v for vals in decay.values()
                                             for v in vals],
                          "peaks": peaks,
                          "scopes": cfg["scopes"],
                          "kernel_routes": routes,
                          "held_share": held_share, "expert_load": load,
                          "held_share_by_layer": held_by_layer,
                          "act_zero_share": zero_share,
                          "dropped": dropped,
                          # the routing of the steps the profiler saw
                          "traced_held_share": per_display(
                              ran["traced_rows"], "_held_share")}},
    }
