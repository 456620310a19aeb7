"""Runner: one GLM-4.7-Flash training job as ONE of 8 chips that share each
layer (a prototxt of EMBED / RMS_NORM / INNER_PRODUCT / SLICE / ATTENTION
with value heads of their own width and a shared key part that really
rotates (``rotary_shared``) / SILU_GATE / MOE_ROUTER with a sigmoid score /
MOE layers holding part of the experts their routers score / CONCAT /
TOKEN_SHIFT with its mark / WEIGHTED_MEAN_LOSS, the embedding and the head
bound to two users each), driven through the program's own ``train`` command
as every token cell is. What a token runner does whatever its model comes
from the runners that have it: the token file, ``build_engine``,
``LmdbFeed``, ``CompileCounter``, ``trace_window``, ``write_job_files``,
``document_mix``, ``export_blobs``, ``optimizer_facts``, the display rows'
series and the stall ledger's totals.

What is this file's own, and why: ``first_step`` (the first step's loss
PARTS beside its total); ``reference_check`` (the trained weights' forward
against ``reference/glm_flash.py``: main AND module logits, both loss parts
apart); ``step_check`` (the timed path's first step against the
reference's: the loss, every leaf's change in norm and direction, the
selection biases by the sign rule on the reference's counts, the module's
block among the routers, and ``NEW_LEAVES`` by GROUP: the leaves that only
this configuration's mechanisms feed, some of them ROWS of a matrix, each
group's change as one vector); ``compared``, every number that decided
``correct`` beside its limit, in the facts line.

The per-layer readers get the keys ``lm_train`` hands them, ONE SEQUENCE as
the sample; ``lm`` holds what the token cells' readers add, under the keys
every token runner shares (``lm_trace``: ``scopes``, the configuration's
layer-name patterns by part; the required work; the display rows' series).
"""

from __future__ import annotations

import math
import os
import re
import shutil
import sys
import time

import device as device_mod
import flops_glm
import tokengen
from runners.caffe_train import (CompileCounter, LmdbFeed, build_engine,
                                 trace_window)
from runners.lm_train import document_mix
from runners.token_checks import (display_series, reference_of, rel,
                                  stall_totals)
from runners.zaya_train import (export_blobs, optimizer_facts,
                                write_job_files)

# the keys of the model's config.json the benchmark computes from
MODEL_KEYS = ("hidden_size", "intermediate_size", "moe_intermediate_size",
              "num_attention_heads", "q_lora_rank", "kv_lora_rank",
              "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim",
              "n_routed_experts", "router_num_experts",
              "num_experts_per_tok", "n_shared_experts",
              "num_hidden_layers", "num_nextn_predict_layers", "vocab_size",
              "rms_norm_eps", "rope_theta", "routed_scaling_factor",
              "bias_update_rate", "mtp_loss_weight", "layers_run")


def refuse_old_program(cell: str) -> None:
    """A program from before the model (no ``zoo.glm_flash``, so no rotary
    shared key part, no forward token shift, no weighted mean): fail at
    once, exit 2."""
    from poseidon_tpu.models import zoo
    if not hasattr(zoo, "glm_flash"):
        print(f"[benchmark] REFUSING: this program has no "
              f"models/zoo.glm_flash; it cannot run {cell!r}. Nothing was "
              f"measured.", file=sys.stderr)
        raise SystemExit(2)


def reference_sizes(model: dict) -> dict:
    """The reference's ``cfg`` from the configuration's own keys."""
    return {"num_hidden_layers": model["num_hidden_layers"],
            "num_dense_layers": model["layers_run"]["dense"],
            "num_heads": model["num_attention_heads"],
            "q_lora_rank": model["q_lora_rank"],
            "kv_lora_rank": model["kv_lora_rank"],
            "qk_nope_head_dim": model["qk_nope_head_dim"],
            "qk_rope_head_dim": model["qk_rope_head_dim"],
            "v_head_dim": model["v_head_dim"],
            "num_experts": model["router_num_experts"],
            "num_experts_per_tok": model["num_experts_per_tok"],
            "route_scale": model["routed_scaling_factor"],
            "rope_theta": float(model["rope_theta"]),
            "rms_norm_eps": model["rms_norm_eps"],
            "mtp_layers": model["layers_run"]["mtp"],
            "mtp_weight": model["mtp_loss_weight"]}


def expected_first_loss(cfg: dict, model: dict) -> dict:
    """Fresh weights know nothing of the targets: EACH cross-entropy starts
    at ln V + var / 2 with var the variance of a logit, a unit-RMS state
    against a row of the std-``init_std`` head; the objective is the first
    plus ``mtp_loss_weight`` of the second (the configuration's
    ``first_loss_why``)."""
    part = math.log(model["vocab_size"]) \
        + cfg["init_std"] ** 2 * model["hidden_size"] / 2
    return {"part": part,
            "total": part * (1.0 + model["mtp_loss_weight"]
                             * model["layers_run"]["mtp"])}


def new_leaves(model: dict) -> dict:
    """{group: [(pattern over a whole layer name, blob index, rows of the
    blob's FIRST axis or None for all of it)]}: the leaves only this
    configuration's mechanisms feed. Each group's change in the first step
    is compared as ONE vector (Adam's first change of a leaf has the norm
    lr sqrt(n) whatever its direction, and most of these lie under
    ``cosine_from`` alone or are rows of a larger leaf)."""
    nope, rope = model["qk_nope_head_dim"], model["qk_rope_head_dim"]
    head = nope + rope
    q_rows = [h * head + j for h in range(model["num_attention_heads"])
              for j in range(nope, head)]
    rank = model["kv_lora_rank"]
    return {
        # the module's own leaves (its routers' selection bias apart)
        "mtp_module": [(r"mtp_\w+", None, None)],
        # W_qb's rotary rows, every block's: the tails that rotate
        "q_rotary_rows": [(r"(l\d+|mtp)_mla_qb", 0, q_rows)],
        # W_kva's 64 rows of the shared key part: rotated once, read by
        # every head, its gradient a sum over heads
        "k_shared_rows": [(r"(l\d+|mtp)_mla_kva", 0,
                           list(range(rank, rank + rope)))],
        # the two arrays with two users each: gradients summed
        "embed_and_head": [(r"embed|lm_head", 0, None)],
    }


def group_cosines(got: dict, other: dict, groups: dict) -> dict:
    """{group: the cosine between two steps' changes ({layer: [blobs]}) of
    that group's leaves, all of them as ONE vector}. A router's last blob,
    its selection bias, is no leaf of the optimizer and is left out."""
    import numpy as np

    def as_one(changes, parts):
        vectors = []
        for pattern, index, rows in parts:
            for name in sorted(changes):
                if not re.fullmatch(pattern, name):
                    continue
                blobs = changes[name][:-1] if name.endswith("_router") \
                    else changes[name]
                for j, blob in enumerate(blobs):
                    if index is None or j == index:
                        blob = np.asarray(blob, np.float64)
                        vectors.append((blob if rows is None
                                        else blob[rows]).ravel())
        return np.concatenate(vectors)

    out = {}
    for group, parts in groups.items():
        a, b = as_one(got, parts), as_one(other, parts)
        out[group] = float(a @ b / max(
            np.linalg.norm(a) * np.linalg.norm(b), 1e-300))
    return out


def first_step(eng, cfg: dict) -> dict:
    """Engine.train to 1 step, and what that step did: its loss and the
    loss's two PARTS, the batch it took (read as the Engine hands it to the
    compiled step: the DATA layer shuffles), the seeded weights before it
    and every leaf's change, on the host for ``step_check``."""
    import numpy as np
    before = export_blobs(eng.train_net, eng.params)
    taken, dispatch = {}, eng._dispatch_train_step

    def watched(batch, *args, **kwargs):
        taken.update({top: np.asarray(rows) for top, rows in batch.items()})
        return dispatch(batch, *args, **kwargs)

    eng._dispatch_train_step = watched
    try:
        row = eng.train(max_iter=1)
    finally:
        del eng._dispatch_train_step
    after = export_blobs(eng.train_net, eng.params)
    return {"loss": row.get("loss", float("nan")),
            "lm_loss": row.get("lm_loss", float("nan")),
            "mtp_loss": row.get("mtp_loss", float("nan")),
            "batch": taken, "before": before,
            "opt": optimizer_facts(eng, cfg),
            "change": {name: [a - b for a, b in zip(blobs, before[name])]
                       for name, blobs in after.items()}}


def reference_check(job: dict, params: dict, net_path: str, model: dict,
                    seq: int):
    """The program's forward (the run's numeric policy) against the plain
    reference on ONE seeded whole-length sequence and the trained weights
    (``params``, still on the device): main AND module logits at the last
    ``reference_positions`` positions against the whole context, and each
    loss part over every position it counts. ``correct`` is decided with
    the program's expert choice handed over; the free-running reference and
    the one with its matmul inputs rounded to ``reference_lower_precision``
    (which has to lie outside a limit) are facts beside it. ``route_flips``
    counts, a sparse block, the handed-over assignments the reference's own
    top-k does not have. Called with the Engine closed and its solver state
    dropped."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from poseidon_tpu.core.net import Net
    from poseidon_tpu.proto.messages import load_net

    cfg = job["config"]
    ref, tol = reference_of(job)
    last = min(int(cfg["reference_positions"]), seq)
    made = tokengen.packed_sequences(job["seed"] + 7919, 1, seq,
                                     model["vocab_size"], document_mix(job))
    tokens, targets = jnp.asarray(made["data"]), jnp.asarray(made["label"])
    net = Net(load_net(net_path), "TRAIN",
              source_shapes={"tokens": (1, seq), "targets": (1, seq)})
    top_k = model["num_experts_per_tok"]
    run = model["layers_run"]
    sparse = [f"l{i}_" for i in range(run["dense"],
                                      model["num_hidden_layers"])] \
        + ["mtp_"] * run["mtp"]
    held = range(model["n_routed_experts"])

    def program(p, tok, tgt):
        out = net.apply(p, {"tokens": tok, "targets": tgt}, train=False,
                        keep_blobs=True)
        return {"loss": out.loss, "lm_loss": out.outputs["lm_loss"],
                "mtp_loss": out.outputs["mtp_loss"],
                "logits": out.blobs["logits"][:, -last:],
                "mtp_logits": out.blobs["mtp_logits"][:, -last:],
                # each token's k experts: the non-zero gates
                "choice": jnp.stack([
                    jax.lax.top_k(out.blobs[p_ + "gates"], top_k)[1]
                    for p_ in sparse])}

    def host(out):
        return {k: np.asarray(v, np.float32 if k != "choice" else np.int32)
                for k, v in out.items()}

    got = host(jax.jit(program)(params, tokens, targets))
    # the same device arrays under the reference's names and blob order
    weights = {l.name: [params[l.name][p.name] for p in l.params]
               for l in net.layers if l.name in params}
    sizes = reference_sizes(model)

    def reference(w, tok, tgt, choice=None, round_to=None):
        total, out = ref.loss(sizes, w, tok, tgt, held=held, last=last,
                              q_block=last, choice=choice,
                              round_to=round_to)
        return {"loss": total, **{k: out[k] for k in (
            "lm_loss", "mtp_loss", "logits", "mtp_logits", "route_flips")}}

    choice = jnp.asarray(got["choice"])
    want = host(jax.jit(reference)(weights, tokens, targets, choice))
    free = host(jax.jit(reference)(weights, tokens, targets))
    low = host(jax.jit(lambda *a: reference(
        *a, round_to=getattr(jnp, cfg["reference_lower_precision"])))(
            weights, tokens, targets, choice))

    def loss_rel(key, a=got, b=want):
        return abs(float(a[key]) - float(b[key])) / abs(float(b[key]))

    facts = {"loss_program": float(got["loss"]),
             "loss_reference": float(want["loss"]),
             "lm_loss_program": float(got["lm_loss"]),
             "lm_loss_reference": float(want["lm_loss"]),
             "mtp_loss_program": float(got["mtp_loss"]),
             "mtp_loss_reference": float(want["mtp_loss"]),
             "logits_rel_l2": rel(got["logits"], want["logits"]),
             "mtp_logits_rel_l2": rel(got["mtp_logits"],
                                      want["mtp_logits"]),
             "loss_rel": loss_rel("lm_loss"),
             "mtp_loss_rel": loss_rel("mtp_loss"),
             "route_flips": [int(n) for n in want["route_flips"]],
             "free_running_logits_rel_l2": rel(got["logits"],
                                               free["logits"]),
             "free_running_loss": float(free["loss"]),
             "lower_precision": cfg["reference_lower_precision"],
             "lower_precision_rel_l2": rel(low["logits"], want["logits"]),
             "lower_precision_mtp_rel_l2": rel(low["mtp_logits"],
                                               want["mtp_logits"]),
             "lower_precision_loss_rel": loss_rel("lm_loss", low),
             "lower_precision_mtp_loss_rel": loss_rel("mtp_loss", low),
             "sequences": 1, "positions": last, "context": seq,
             "tolerance": tol}
    # a limit of None is a fact only (under bf16: the module's loss)
    ok = math.isfinite(facts["loss_program"]) and all(
        tol[k] is None or facts[k] <= tol[k]
        for k in ("logits_rel_l2", "mtp_logits_rel_l2", "loss_rel",
                  "mtp_loss_rel"))
    return facts, ok


def step_check(job: dict, model: dict, seq: int, step: dict):
    """The Engine's own compiled step against the reference's: ``step``
    holds the seeded weights (``before``), the change the run's FIRST step
    made to every leaf (``change``), that step's loss and its two parts,
    its batch and the solver's numbers, all on the host. The reference
    takes the same step in f32 (``train_step``, free-running: the step
    publishes no expert choice), and once more with its matmul inputs
    rounded to ``reference_lower_precision``, which has to lie outside a
    limit. Decided by: the loss (where the tolerance has a limit for it:
    under bf16 it is a fact only); every leaf's change in norm (worst leaf:
    a leaf left unchanged reads 1); the direction of the change of every
    leaf of ``cosine_from`` numbers or more (worst cosine); the direction
    of the change of each group of ``new_leaves`` (worst group against
    ``group_cosine``); and every selection bias whose expert's count is not
    within ``bias_margin`` of the even split (the counts are ASSIGNMENTS, 4
    a token, over all 64 experts the routers score; the module's router is
    the last row)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    cfg = job["config"]
    ref, tol = reference_of(job)
    opt = dict(step["opt"], bias_rate=model["bias_update_rate"])
    first_rate = opt.pop("first_rate")
    sizes = reference_sizes(model)
    held = range(model["n_routed_experts"])
    low_type = getattr(jnp, cfg["reference_lower_precision"])
    q_block = min(seq, int(cfg["reference_positions"]))
    groups = new_leaves(model)

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
    routers = ref.router_names(want["change"])

    def against(got, other):
        """Leaf by leaf (the biases apart): how far the norms of the two
        changes lie from each other, and for a leaf of ``cosine_from``
        numbers or more the cosine between them; the worst of each first;
        the groups."""
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
        by_group = group_cosines(got, other, groups)
        return {"norm_rel": by_norm[0]["norm_rel"],
                "cosine": by_cosine[0]["cosine"] if by_cosine else 1.0,
                "group_cosine": min(by_group.values()),
                "group_cosines": by_group,
                "worst_by_norm": by_norm[:6], "worst_by_cosine": by_cosine[:6]}

    # the selection biases: the program's next value against the sign rule
    # on the reference's own counts. A count within ``bias_margin`` (a share
    # of the EVEN SPLIT, the step's assignments / E) of the even split is
    # not compared: the near-ties that rounding flips can carry it across
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

    def part_rel(key, other=want):
        return abs(step[key] - float(other[key])) / abs(float(other[key]))

    loss_rel = part_rel("loss")
    facts = {"loss_program": step["loss"],
             "loss_reference": float(want["loss"]),
             "loss_rel": loss_rel,
             "lm_loss_rel": part_rel("lm_loss"),
             "mtp_loss_rel": part_rel("mtp_loss"),
             "mtp_loss_over_main": step["mtp_loss"] / step["lm_loss"],
             "update_norm_rel": program["norm_rel"],
             "update_cosine": program["cosine"],
             "group_cosine": program["group_cosine"],
             "group_cosines": program["group_cosines"],
             "worst_by_norm": program["worst_by_norm"],
             "worst_by_cosine": program["worst_by_cosine"],
             "routers": routers,
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
             "lower_precision_group_cosine": control["group_cosine"],
             "lower_precision_group_cosines": control["group_cosines"],
             "lower_precision_worst_by_cosine": control["worst_by_cosine"][:2],
             "sequences": int(tokens.shape[0]), "context": seq,
             "seconds": dict(took, compare_s=clock() - t),
             "tolerance": tol}
    ok = math.isfinite(step["loss"]) \
        and (tol["step_loss_rel"] is None
             or loss_rel <= tol["step_loss_rel"]) \
        and program["norm_rel"] <= tol["update_norm_rel"] \
        and program["cosine"] >= tol["update_cosine"] \
        and program["group_cosine"] >= tol["group_cosine"] \
        and bias_wrong == 0 and facts["bias_compared"] \
        >= tol["bias_compared_share"] * clear.size
    return facts, ok


def compared(ref_facts: dict, step_facts: dict, first: tuple) -> list:
    """Every number that decided ``correct`` beside its limit, and the
    float8 control beside the limits it has to break (at least one)."""
    tol = ref_facts["tolerance"]
    first_over, first_low, first_high = first
    rows = [("first_loss_over_expected", first_over, ">=", first_low),
            ("first_loss_over_expected", first_over, "<=", first_high)]
    rows += [(k, ref_facts[k], "<=", tol[k]) for k in (
        "logits_rel_l2", "mtp_logits_rel_l2", "loss_rel", "mtp_loss_rel")]
    rows += [("step_loss_rel", step_facts["loss_rel"], "<=",
              tol["step_loss_rel"]),
             ("update_norm_rel", step_facts["update_norm_rel"], "<=",
              tol["update_norm_rel"]),
             ("update_cosine", step_facts["update_cosine"], ">=",
              tol["update_cosine"]),
             ("group_cosine", step_facts["group_cosine"], ">=",
              tol["group_cosine"]),
             ("bias_wrong", step_facts["bias_wrong"], "<=", 0),
             ("bias_compared_share",
              step_facts["bias_compared"] / max(1, step_facts["bias_of"]),
              ">=", tol["bias_compared_share"]),
             ("control_float8_logits_rel_l2",
              ref_facts["lower_precision_rel_l2"], ">",
              tol["logits_rel_l2"]),
             ("control_float8_mtp_logits_rel_l2",
              ref_facts["lower_precision_mtp_rel_l2"], ">",
              tol["mtp_logits_rel_l2"]),
             ("control_float8_update_cosine",
              step_facts["lower_precision_update_cosine"], "<",
              tol["update_cosine"]),
             ("control_float8_group_cosine",
              step_facts["lower_precision_group_cosine"], "<",
              tol["group_cosine"])]
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
    sparse_blocks = flops_glm.layers_run(model)["sparse"]

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
    per_token = flops_glm.required_flops_per_token(model, seq)
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
    ref_facts, ref_ok = reference_check(job, params, net_path, model, seq)
    del params                  # the device is the reference's own now
    step_facts, step_ok = step_check(job, model, seq, step)
    del step

    def per_display(some_rows, suffix):
        return [sum(vals) / len(vals) for vals in (
            [v for k, v in r.items() if k.endswith(suffix)]
            for r in some_rows) if vals]

    held_share = per_display(rows, "_held_share")
    held_by_layer = display_series(rows, "_held_share")
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
    # both loss parts, a display: the module's over the main one's
    parts = [(r["lm_loss"], r["mtp_loss"]) for r in rows
             if "lm_loss" in r and "mtp_loss" in r]
    mtp_over_main = [m / l for l, m in parts if l]
    place = after["sections"].get("placement", {})
    low, high = cfg["first_loss_band"]
    first_over = first_loss / want_first["total"]
    checks = {
        "losses_finite": bool(window["losses"]) and all(
            math.isfinite(v) for v in window["losses"]),
        "first_loss": low <= first_over <= high,
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
        "loss_parts_published": len(parts) >= 2 and all(
            math.isfinite(l) and math.isfinite(m) for l, m in parts),
        "shared_params_published": sorted(after["sections"].get(
            "shared_params", {})) == ["head_w", "tok_w"],
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
    facts = {"first_loss": first_loss,
             "first_loss_expected": want_first["total"],
             "window_losses": window["losses"][-3:], "reference": ref_facts,
             "step_reference": step_facts,
             "checks": checks, "steps": window["attempted"],
             "window_s": seconds, "step_s_warmup": step_s,
             "first_step_s": first_step_s,
             "display_intervals_s": intervals,
             "batch_per_chip": batch, "seq_len": seq,
             "tokens_per_s_per_chip": sequences_per_s * seq,
             "flops_per_token": per_token, "token_file": data,
             "loss_parts": {"lm_loss": [l for l, _ in parts][-3:],
                            "mtp_loss": [m for _, m in parts][-3:],
                            "mtp_over_main": mtp_over_main[-3:]},
             "held_assignment_share": {
                 "warm_up": per_display(warm_rows, "_held_share"),
                 "min": min(held_share, default=None),
                 "max": max(held_share, default=None),
                 "mean": sum(held_share) / max(1, len(held_share)),
                 "per_display": held_share,
                 "per_layer": held_by_layer,
                 "window_prefix": held_prefix},
             "held_expert_load_max_over_mean": {
                 "first_display": load[:1], "last_display": load[-1:],
                 "max": max(load, default=None)},
             "selection_bias_max_abs": {
                 "first_display": bias_max[:1],
                 "last_display": bias_max[-1:]},
             "kernel_routes": routes,
             "expert_share": sections.get("expert_share", {}),
             "shared_params": sections.get("shared_params", {}),
             "compiled_step": sections.get("compiled_step", {}),
             "remat": {k: v for k, v in sections.get("remat", {}).items()
                       if k not in ("layers", "segments")},
             "remat_segments": len(sections.get("remat", {}).get(
                 "segments", ())),
             "placement": place,
             "stalls": stall_totals(after),
             # LAST in the line: what was compared, each beside its limit
             "compared": compared(ref_facts, step_facts,
                                  (first_over, low, high))}
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
                          "flash_per_step": flops_glm.flash_attention_step(
                              model, batch, seq),
                          "flops_per_assignment":
                              flops_glm.expert_flops_per_assignment(model),
                          "assignments_per_step": sparse_blocks * seq
                          * batch * model["num_experts_per_tok"],
                          "peaks": peaks,
                          "scopes": cfg["scopes"],
                          "kernel_routes": routes,
                          "held_share": held_share, "expert_load": load,
                          "held_share_by_layer": held_by_layer,
                          "dropped": dropped,
                          "mtp_loss_over_main": mtp_over_main,
                          # the routing of the steps the profiler saw
                          "traced_held_share": per_display(
                              traced_rows, "_held_share")}},
    }
