"""Runner: one Xing4.0-29B-A4B training job as ONE of 8 chips that share each
layer (``glm_train``'s prototxt family — EMBED / RMS_NORM / INNER_PRODUCT /
SLICE / ATTENTION with value heads of their own width, a shared key part that
really rotates and YaRN's frequencies / SILU_GATE / MOE_ROUTER with a sigmoid
score / MOE layers holding part of the experts their routers score — on a
residual STREAM: HC_START, and a sub-layer HC_MAP, HC_READ, HC_WRITE, then
HC_END), driven through the program's own ``train`` command as every token
cell is. What a token runner does whatever its model comes from the runners
that have it: the token file, ``build_engine``, ``LmdbFeed``,
``CompileCounter``, ``trace_window``, ``write_job_files``, ``document_mix``,
``export_blobs``, ``optimizer_facts``, the display rows' series, the stall
ledger's totals, and from ``glm_train`` the first step (``first_step``) and
the cosine of a GROUP of leaves' change (``group_cosines``).

What is this file's own, and why: ``reference_check`` (the trained weights'
forward against ``reference/xing4.py``); ``stream_check`` and
``attention_check`` (the stream's functions and the ATTENTION layer itself,
forward and backward at the timed sizes, on operands of their own that
carry signal where a fresh model's carry none); ``new_leaves`` and ``dead_leaves``
(the leaves only this configuration's mechanisms feed, by group: every
mapping's, W_qb's rotary rows and W_kva's shared-part rows, whose gradients
pass the YaRN angles; and the mapping leaves NOTHING feeds, by construction,
which no comparison may hold: the stream's first read and mix, its last
mix); ``step_check`` (the timed path's first step against the
reference's, the dead leaves left out and counted); ``compared``; the
stream's display counters (``hc_res_err``, the means of p and q).

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
import flops_xing
import tokengen
from runners.caffe_train import (CompileCounter, LmdbFeed, build_engine,
                                 trace_window)
from runners.glm_train import first_step, group_cosines
from runners.lm_train import document_mix
from runners.token_checks import (display_series, reference_of, rel,
                                  stall_totals)
from runners.zaya_train import write_job_files

# the keys of the model's config.json the benchmark computes from
MODEL_KEYS = ("hidden_size", "intermediate_size", "moe_intermediate_size",
              "num_attention_heads", "q_lora_rank", "kv_lora_rank",
              "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim",
              "n_routed_experts", "router_num_experts",
              "num_experts_per_tok", "n_shared_experts",
              "num_hidden_layers", "num_nextn_predict_layers", "vocab_size",
              "rms_norm_eps", "rope_theta", "rope_scaling",
              "routed_scaling_factor", "bias_update_rate", "mtp_loss_weight",
              "hc_mult", "hc_sinkhorn_iters", "hc_eps", "hc_clamp",
              "layers_run")
# the groups of ``new_leaves`` that are FACTS, not limits: on a fresh model
# what reaches p's and the mix's leaves is a difference of nearly equal sums
# that a bf16 stream rounds away (``new_leaves``); ``stream_check`` holds
# those paths on operands that carry signal
FACT_GROUPS = ("hc_pre", "hc_res")
# a mapping's nine blobs, in the layer's order
MAP_BLOBS = ("phi_pre", "phi_post", "phi_res", "b_pre", "b_post", "b_res",
             "a_pre", "a_post", "a_res")


def refuse_old_program(cell: str) -> None:
    """A program from before the model (no ``zoo.xing4``, so no stream
    layers and no YaRN frequencies): fail at once, exit 2."""
    from poseidon_tpu.models import zoo
    if not hasattr(zoo, "xing4"):
        print(f"[benchmark] REFUSING: this program has no "
              f"models/zoo.xing4; it cannot run {cell!r}. Nothing was "
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
            "rope_scaling": model["rope_scaling"],
            "scale_head_dim": model["scale_head_dim"],
            "rms_norm_eps": model["rms_norm_eps"],
            "hc_mult": model["hc_mult"],
            "hc_sinkhorn_iters": model["hc_sinkhorn_iters"],
            "hc_eps": model["hc_eps"], "hc_clamp": model["hc_clamp"],
            "mtp_layers": model["layers_run"]["mtp"],
            "mtp_weight": model["mtp_loss_weight"]}


def expected_first_loss(cfg: dict, model: dict) -> float:
    """Fresh weights know nothing of the targets: ln V + var / 2 with var
    the variance of a logit, the final norm's unit-RMS state against a row
    of the std-``init_std`` head (the configuration's ``first_loss_why``)."""
    return math.log(model["vocab_size"]) \
        + cfg["init_std"] ** 2 * model["hidden_size"] / 2


def dead_leaves(model: dict) -> dict:
    """{mapping layer: the blob indices NOTHING feeds, by construction}:
    the stream's first read and mix (on n copies of the embedding the read
    is a multiple of it, which the norm after it takes out, and the mix is
    its own row sums, 1 to what the Sinkhorn loop leaves) and the stream's
    last mix (only the streams' SUM is read after it, and the mix's columns
    sum to 1). Their gradient is rounding noise on both sides, and Adam's
    first step turns noise into a change of full size and random direction:
    no comparison may hold them, so ``step_check`` leaves them out and says
    how many."""
    last = model["num_hidden_layers"] - 1
    pre, res = (0, 3, 6), (2, 5, 8)
    return {"l0_hc_a_map": pre + res, f"l{last}_hc_f_map": res}


def new_leaves(model: dict) -> dict:
    """{group: [(pattern over a whole layer name, blob index, rows of the
    blob's FIRST axis or None for all of it)]}: the leaves only this
    configuration's mechanisms feed, as ``glm_train.group_cosines`` takes
    them. Each group's change in the first step is compared as ONE vector
    (Adam's first change of a leaf has the norm lr sqrt(n) whatever its
    direction, and most of these lie under ``cosine_from`` alone or are rows
    of a larger leaf)."""
    nope, rope = model["qk_nope_head_dim"], model["qk_rope_head_dim"]
    head = nope + rope
    q_rows = [h * head + j for h in range(model["num_attention_heads"])
              for j in range(nope, head)]
    rank = model["kv_lora_rank"]
    dead = dead_leaves(model)
    maps = [f"l{i}_hc_{s}_map" for i in range(model["num_hidden_layers"])
            for s in "af"]

    def mapping(kind):
        """Phi_<kind>, b_<kind>, a_<kind> of every mapping that something
        feeds."""
        return [(re.escape(name), j, None) for name in maps
                for j, blob in enumerate(MAP_BLOBS)
                if blob.endswith("_" + kind) and j not in dead.get(name, ())]

    return {
        # every mapping's leaves by what they make: the read's p, the
        # write's q, the mix. NO mapping leaf is held alone (``step_check``):
        # on a fresh model the streams are all but copies of one state, so
        # what reaches p and the mix is a DIFFERENCE of nearly equal sums
        # (the norm after the read takes p's common factor out, the Sinkhorn
        # projection the mix's row and column factors), small beside what
        # bf16 rounds away; q's is direct. So hc_pre and hc_res are FACTS
        # (``FACT_GROUPS``) and hc_post a limit
        "hc_pre": mapping("pre"), "hc_post": mapping("post"),
        "hc_res": mapping("res"),
        # W_qb's rotary rows, every block's: the tails that turn by YaRN's
        # angles
        "q_rotary_rows": [(r"l\d+_mla_qb", 0, q_rows)],
        # W_kva's rows of the shared key part: rotated once, read by every
        # head, its gradient a sum over heads
        "k_shared_rows": [(r"l\d+_mla_kva", 0,
                           list(range(rank, rank + rope)))],
    }


def reference_check(job: dict, params: dict, net_path: str, model: dict,
                    seq: int):
    """The program's forward (the run's numeric policy) against the plain
    reference on ONE seeded whole-length sequence and the trained weights
    (``params``, still on the device): logits at the last
    ``reference_positions`` positions against the whole context, and the
    loss over every position. ``correct`` is decided with the program's
    expert choice handed over; the free-running reference and the one with
    its matmul inputs rounded to ``reference_lower_precision`` (which has to
    lie outside a limit) are facts beside it. ``route_flips`` counts, a
    sparse block, the handed-over assignments the reference's own top-k
    does not have. Then the stream's own functions and the ATTENTION layer
    itself, forward and backward, on operands that carry signal on every
    path (``stream_check``, ``attention_check``). Called
    with the Engine closed and its solver state dropped."""
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
    sparse = [f"l{i}_" for i in range(model["layers_run"]["dense"],
                                      model["num_hidden_layers"])]
    held = range(model["n_routed_experts"])
    subs = [f"l{i}_hc_{s}_" for i in range(model["num_hidden_layers"])
            for s in "af"]

    def program(p, tok, tgt):
        out = net.apply(p, {"tokens": tok, "targets": tgt}, train=False,
                        keep_blobs=True)
        return {"loss": out.loss,
                "logits": out.blobs["logits"][:, -last:],
                "res_err": jnp.stack([out.outputs[s + "res_err"]
                                      for s in subs]),
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

    def reference(w, tok, tgt, choice=None, round_to=None, fault=None):
        total, out = ref.loss(sizes, w, tok, tgt, held=held, last=last,
                              q_block=last, choice=choice,
                              round_to=round_to, fault=fault)
        return {"loss": total, **{k: out[k] for k in (
            "logits", "route_flips", "res_err")}}

    choice = jnp.asarray(got["choice"])
    want = host(jax.jit(reference)(weights, tokens, targets, choice))
    free = host(jax.jit(reference)(weights, tokens, targets))
    low = host(jax.jit(lambda *a: reference(
        *a, round_to=getattr(jnp, cfg["reference_lower_precision"])))(
            weights, tokens, targets, choice))
    # res_err_rel's control: no precision moves what a loop leaves, another
    # loop does (the reference's own, cut to ONE iteration)
    one = host(jax.jit(lambda *a: reference(
        *a, fault="sinkhorn_one_iter"))(weights, tokens, targets, choice))

    def loss_rel(a, b=want):
        return abs(float(a["loss"]) - float(b["loss"])) \
            / abs(float(b["loss"]))

    facts = {"loss_program": float(got["loss"]),
             "loss_reference": float(want["loss"]),
             "logits_rel_l2": rel(got["logits"], want["logits"]),
             "loss_rel": loss_rel(got),
             "route_flips": [int(n) for n in want["route_flips"]],
             # what 20 iterations leave, the worst sub-layer: both sides
             "res_err_program": float(got["res_err"].max()),
             "res_err_reference": float(want["res_err"].max()),
             "res_err_rel": rel(got["res_err"], want["res_err"]),
             "one_iteration_res_err_rel": rel(one["res_err"],
                                              want["res_err"]),
             "free_running_logits_rel_l2": rel(got["logits"],
                                               free["logits"]),
             "free_running_loss": float(free["loss"]),
             "lower_precision": cfg["reference_lower_precision"],
             "lower_precision_rel_l2": rel(low["logits"], want["logits"]),
             "lower_precision_loss_rel": loss_rel(low),
             "sequences": 1, "positions": last, "context": seq,
             "tolerance": tol}
    del weights
    facts.update(stream_check(job, model, seq))
    facts.update(attention_check(job, model, net_path, seq))
    ok = math.isfinite(facts["loss_program"]) and facts.pop("stream_ok") \
        and facts.pop("attention_ok") and all(
            tol[k] is None or facts[k] <= tol[k]
            for k in ("logits_rel_l2", "loss_rel", "res_err_rel"))
    return facts, ok


def worst_rels(side: dict, want: dict, forward: tuple):
    """{name: array} of a side's tops and gradients against the
    reference's -> (the worst relative L2 distance among the ``forward``
    names, the worst among the others, every name's). A name that starts
    with ``alone_`` is a fact: in ``rels`` and in neither worst."""
    rels = {k: rel(side[k], want[k]) for k in want}
    return (max(rels[k] for k in forward),
            max(v for k, v in rels.items()
                if k not in forward and not k.startswith("alone_")), rels)


def stream_check(job: dict, model: dict, seq: int) -> dict:
    """The program's own stream functions (``ops/hyper``: the mapping with
    its Sinkhorn loop, the read, the write, as HC_MAP / HC_READ / HC_WRITE
    call them, under the run's numeric policy) FORWARD AND BACKWARD at the
    timed sizes against the reference's ``stream_sublayer``, on operands of
    the check's own: one sequence's stream whose n states DIFFER (one state
    a token plus as much again of each stream's own), a seeded sub-layer
    output, and a mapping with EVERY leaf drawn at random (scales of order
    1, no diagonal in B_res). Why not the model's own: on a fresh model the
    streams are all but copies of one state and the mapping's dynamic part
    is 1% of its logits, so what reaches p's and the mix's leaves there is
    a difference of nearly equal sums, under what a bf16 stream rounds away
    (``new_leaves``); here every path carries signal. ``stream_rel_l2`` is
    the worst of the coefficients', h's, X' 's and its end's (the streams
    summed, HC_END's function) distance,
    ``stream_grad_rel_l2`` the worst of the six gradients' (the three
    matrices; the biases and the scales, n + n + n^2 + 3 numbers, as ONE
    vector ``d_small``; d X; d y; each on its own norm) under seeded
    cotangents of h and X'. Why the small leaves as one: each of their
    numbers is a sum over every token of terms of both signs, and on some
    draws a leaf of 1, 4 or 16 of them lands near zero, where the distance
    on its own norm reads several times its usual (seed 1458287010 on the
    chip: the three scales as one vector 0.0152 against the limit 0.01,
    0.002-0.0054 on twelve other seeds, every other row at its usual);
    together they do not. What each reads alone is a fact (``stream_rels``'
    ``alone_*``). The control beside them: the reference with its stream
    STORED in ``reference_lower_precision`` has to lie outside both
    limits."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from poseidon_tpu.config import policy
    from poseidon_tpu.ops.hyper import hc_end, hc_map, hc_read, hc_write

    cfg = job["config"]
    ref, tol = reference_of(job)
    sizes = reference_sizes(model)
    n, c = model["hc_mult"], model["hidden_size"]
    rng = np.random.default_rng(job["seed"] + 104729)
    normal = lambda *shape: rng.standard_normal(shape, np.float32)  # noqa
    kept = policy().compute_dtype
    xs = jnp.asarray(normal(1, seq, 1, c) + normal(1, seq, n, c), kept)
    y, d_h = (jnp.asarray(normal(1, seq, c), kept) for _ in range(2))
    d_out = jnp.asarray(normal(1, seq, n * c), kept)
    w = {"phi_pre": normal(n, n * c) / math.sqrt(n * c),
         "phi_post": normal(n, n * c) / math.sqrt(n * c),
         "phi_res": normal(n * n, n * c) / math.sqrt(n * c),
         "b_pre": -math.log(max(n - 1, 1)) + 0.5 * normal(n),
         "b_post": 0.5 * normal(n), "b_res": normal(n, n),
         "a_pre": 1.0 + 0.3 * normal(1), "a_post": 1.0 + 0.3 * normal(1),
         "a_res": 1.0 + 0.3 * normal(1)}
    w = {k: jnp.asarray(v, jnp.float32) for k, v in w.items()}
    how = (n, model["hc_sinkhorn_iters"], model["hc_eps"], model["hc_clamp"])

    def pulled(fn, *operands, cotangents):
        """fn -> (coef, h, X', the end of X'): the tops, then the pullback
        of (0, d h, d X', d h once more for the end)."""
        tops, pull = jax.vjp(fn, *operands)
        d_h, d_out = cotangents
        return tops + pull((jnp.zeros_like(tops[0]), d_h, d_out, d_h))

    @jax.jit
    def program(w, x, y, d_h, d_out):
        def passes(w, x, y):
            coef = hc_map(x, w, *how)[0]
            out = hc_write(x, y, coef, n)
            return coef, hc_read(x, coef, n), out, hc_end(out, n)
        return pulled(passes, w, x, y, cotangents=(d_h, d_out))

    def reference(w, x, y, d_h, d_out, stored=None):
        f32 = lambda t: t.astype(jnp.float32)                 # noqa: E731

        def passes(blobs, x, y):
            coef, h, out = ref.stream_sublayer(sizes, blobs, x, y,
                                               stream_dtype=stored)
            return coef, h, out, jnp.sum(out, -2)   # the streams summed
        return pulled(passes, [w[k] for k in MAP_BLOBS], f32(x), f32(y),
                      cotangents=(f32(d_h), f32(d_out).reshape(x.shape)))

    def flat(result):
        """-> {name: array}, a side's forward tops and gradients."""
        coef, h, out, end, d_w, d_x, d_y = result
        d_w = [d_w[k] for k in MAP_BLOBS] if isinstance(d_w, dict) else d_w
        grads = {"d_" + k: g for k, g in zip(MAP_BLOBS, d_w)}
        small = [k for k in grads if not k.startswith("d_phi_")]
        named = {"coef": coef, "h": h, "out": out, "end": end, "d_x": d_x,
                 "d_y": d_y,
                 **{k: g for k, g in grads.items() if k not in small},
                 # the biases' and the scales' gradients (n + n + n^2 + 3
                 # numbers) as ONE vector: each number is a sum over every
                 # token that lands near zero on some draws, where a
                 # distance on a few numbers' own norm means nothing
                 "d_small": jnp.concatenate(
                     [grads[k].reshape(-1) for k in small]),
                 # facts: each of them alone
                 **{"alone_" + k: grads[k] for k in small}}
        return {k: np.asarray(v, np.float32).reshape(-1)
                for k, v in named.items()}

    got = flat(program(w, xs.reshape(1, seq, n * c), y, d_h, d_out))
    want = flat(jax.jit(reference)(w, xs, y, d_h, d_out))
    low_type = getattr(jnp, cfg["reference_lower_precision"])
    low = flat(jax.jit(lambda *a: reference(*a, stored=low_type))(
        w, xs, y, d_h, d_out))

    tops = ("coef", "h", "out", "end")
    fwd, bwd, rels = worst_rels(got, want, tops)
    low_fwd, low_bwd, low_rels = worst_rels(low, want, tops)
    return {"stream_rel_l2": fwd, "stream_grad_rel_l2": bwd,
            "stream_rels": rels,
            "stream_control": {"stream_stored_in":
                               cfg["reference_lower_precision"],
                               "stream_rel_l2": low_fwd,
                               "stream_grad_rel_l2": low_bwd,
                               "stream_rels": low_rels},
            "stream_ok": fwd <= tol["stream_rel_l2"]
            and bwd <= tol["stream_grad_rel_l2"]}


def attention_check(job: dict, model: dict, net_path: str, seq: int) -> dict:
    """The program's own ATTENTION layer (the net's ``l0_mla_attn`` as the
    prototxt has it: 32 heads of [128 ; 64] / 128 on the head-major form,
    ``rotary_shared``, YaRN's frequencies, the scores' scale; on the chip
    the three flash kernels) FORWARD AND BACKWARD at the timed sizes against
    the reference's rotation and masked softmax with the frequencies and the
    scale taken from config.json's ``rope_scaling``, on operands of the
    check's own: seeded q, k, v and shared key part of order 1. Why not the
    model's own: a fresh model's scores are all but zero (std-0.02
    projections), so its softmax is flat whatever multiplies the scores and
    wherever the positions turn; here plain theta's frequencies or a scale
    without mscale^2 move every number. ``attention_rel_l2`` is the
    output's distance, ``attention_grad_rel_l2`` the worst of the four
    gradients' under a seeded cotangent. The control beside them: the
    reference with q, k and v rounded to ``reference_lower_precision`` has
    to lie outside both limits."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from poseidon_tpu.config import policy
    from poseidon_tpu.core.layers import ApplyCtx, create_layer
    from poseidon_tpu.proto.messages import load_net

    cfg = job["config"]
    ref, tol = reference_of(job)
    h, nope, rope, dv = (model["num_attention_heads"],
                         model["qk_nope_head_dim"],
                         model["qk_rope_head_dim"], model["v_head_dim"])
    rng = np.random.default_rng(job["seed"] + 15485863)
    kept = policy().compute_dtype
    drawn = lambda width, by=1.0: jnp.asarray(                  # noqa: E731
        by * rng.standard_normal((1, seq, width), np.float32), kept)
    q, k, v, k_pe, d_o = (drawn(h * (nope + rope), 0.5), drawn(h * nope),
                          drawn(h * dv), drawn(rope), drawn(h * dv))
    lp = next(l for l in load_net(net_path).layers
              if l.name == "l0_mla_attn")
    layer = create_layer(lp, "TRAIN", 0)
    layer.setup([t.shape for t in (q, k, v, k_pe)])

    def pulled(fn, *operands):
        out, pull = jax.vjp(fn, *operands)
        return (out,) + pull(d_o.astype(out.dtype))

    program = jax.jit(lambda *ops: pulled(
        lambda *t: layer.apply({}, list(t), ApplyCtx(train=True))[0], *ops))
    scaling = model["rope_scaling"]
    freqs = ref.yarn_frequencies(rope, float(model["rope_theta"]), scaling)
    scale = ref.softmax_scale(model["scale_head_dim"], scaling)
    q_block = min(seq, int(cfg["reference_positions"]))

    def reference(q, k, v, k_pe):
        def plain(q, k, v, k_pe):
            with jax.default_matmul_precision("highest"):
                qh = q[0].reshape(seq, h, nope + rope)
                out = ref.attention(
                    qh[..., :nope], ref.rotate(qh[..., nope:], freqs),
                    k[0].reshape(seq, h, nope), ref.rotate(k_pe[0], freqs),
                    v[0].reshape(seq, h, dv), scale, q_block,
                    jax.checkpoint)
                return out[None]
        return pulled(plain, *(t.astype(jnp.float32)
                               for t in (q, k, v, k_pe)))

    names = ("out", "d_q", "d_k", "d_v", "d_k_pe")

    def flat(result):
        return {n: np.asarray(t, np.float32).reshape(-1)
                for n, t in zip(names, result)}

    got = flat(program(q, k, v, k_pe))
    want = flat(jax.jit(reference)(q, k, v, k_pe))
    # the control's operands are rounded BEFORE the compiled reference sees
    # them (one dispatch each): inside one program the chip's compiler takes
    # a float32 -> float8 -> float32 round trip out again
    low_type = getattr(jnp, cfg["reference_lower_precision"])
    low = flat(jax.jit(reference)(*(t.astype(low_type).astype(kept)
                                    for t in (q, k, v, k_pe))))

    fwd, bwd, rels = worst_rels(got, want, names[:1])
    low_fwd, low_bwd, _ = worst_rels(low, want, names[:1])
    return {"attention_rel_l2": fwd, "attention_grad_rel_l2": bwd,
            "attention_rels": rels,
            "attention_control": {"operands_rounded_to":
                                  cfg["reference_lower_precision"],
                                  "attention_rel_l2": low_fwd,
                                  "attention_grad_rel_l2": low_bwd},
            "attention_ok": fwd <= tol["attention_rel_l2"]
            and bwd <= tol["attention_grad_rel_l2"]}


def step_check(job: dict, model: dict, seq: int, step: dict):
    """The Engine's own compiled step against the reference's: ``step``
    holds the seeded weights (``before``), the change the run's FIRST step
    made to every leaf (``change``), that step's loss, its batch and the
    solver's numbers, all on the host. The reference
    takes the same step in f32 (``train_step``, free-running: the step
    publishes no expert choice), and once more with its matmul inputs
    rounded to ``reference_lower_precision``, which has to lie outside a
    limit. Decided by: the loss (where the tolerance has a limit for it:
    under bf16 it is a fact only); every leaf's change in norm (worst leaf:
    a leaf left unchanged reads 1), a mapping's leaves by KIND and none of
    them left as it was (``against``); the direction of the change of every
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
    groups, dead = new_leaves(model), dead_leaves(model)

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
        """Leaf by leaf (the biases and the dead leaves apart): how far the
        norms of the two changes lie from each other, and for a leaf of
        ``cosine_from`` numbers or more the cosine between them; the worst
        of each first; the groups. A MAPPING's leaves are held otherwise
        (``new_leaves`` says why no one of them is held alone): direction
        and size by KIND, all the write's leaves as one vector, the read's,
        the mix's (a kind left unchanged reads 1; the ``FACT_GROUPS`` are
        facts in both: a gradient at Adam's eps makes a change of any size
        up to the rate, and one such leaf's reads up to 3.1 against the
        reference's on the chip); and NO live leaf of any kind may stay as
        it was (``mapping_unmoved``; a name's scales as one leaf).
        What each reads alone is a fact: the worst, the range by blob."""
        rows, mapped, kinds = [], [], {}
        for name, blobs in other.items():
            mapping = name.endswith("_map")
            for j, b in enumerate(blobs[:-1] if name in routers else blobs):
                if j in dead.get(name, ()):
                    continue
                a = got[name][j].astype(np.float64).ravel()
                b = b.astype(np.float64).ravel()
                na, nb = np.linalg.norm(a), np.linalg.norm(b)
                (mapped if mapping else rows).append({
                    "leaf": f"{name}[{j}]", "numbers": b.size,
                    "norm_rel": float(abs(na - nb) / max(nb, 1e-30)),
                    "cosine": float(a @ b / max(na * nb, 1e-300))
                    if b.size >= tol["cosine_from"] or mapping else None})
                if mapping:
                    mapped[-1].update(blob=MAP_BLOBS[j],
                                      unmoved=bool(na == 0.0 < nb))
                    sums = kinds.setdefault(
                        "hc_" + MAP_BLOBS[j].split("_")[1], [0.0, 0.0])
                    sums[0] += na * na
                    sums[1] += nb * nb
        by_norm = sorted(rows, key=lambda r: -r["norm_rel"])
        by_cosine = sorted((r for r in rows if r["cosine"] is not None),
                           key=lambda r: r["cosine"])
        by_group = group_cosines(got, other, groups)
        by_kind = {k: float(abs(sa ** 0.5 - sb ** 0.5) / max(sb ** 0.5, 1e-30))
                   for k, (sa, sb) in kinds.items()}
        by_blob = {blob: [r for r in mapped if r["blob"] == blob]
                   for blob in MAP_BLOBS}
        # a scale is ONE number, and a gradient of one number can land so
        # near zero that the leaf's float32 does not move: a name's scales
        # in all the mappings count as one leaf
        unmoved = sum(all(r["unmoved"] for r in of) if blob.startswith("a_")
                      else sum(r["unmoved"] for r in of)
                      for blob, of in by_blob.items())
        return {"norm_rel": by_norm[0]["norm_rel"],
                "cosine": by_cosine[0]["cosine"] if by_cosine else 1.0,
                "group_cosine": min(v for g, v in by_group.items()
                                    if g not in FACT_GROUPS),
                "group_cosines": by_group,
                "mapping_norm_rel": max(v for k, v in by_kind.items()
                                        if k not in FACT_GROUPS),
                "mapping_norm_rels": by_kind,
                "mapping_unmoved": unmoved,
                "mapping_live": len(mapped),
                "worst_by_norm": by_norm[:6], "worst_by_cosine": by_cosine[:6],
                # facts: the mapping leaves one by one
                "mapping_norm_rel_by_blob": {
                    blob: [f(r["norm_rel"] for r in of) for f in (min, max)]
                    for blob, of in by_blob.items()},
                "mapping_leaves": sorted(mapped,
                                         key=lambda r: -r["norm_rel"])[:8]}

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

    loss_rel = abs(step["loss"] - float(want["loss"])) \
        / abs(float(want["loss"]))
    facts = {"loss_program": step["loss"],
             "loss_reference": float(want["loss"]),
             "loss_rel": loss_rel,
             "dead_leaves": sum(len(v) for v in dead.values()),
             "update_norm_rel": program["norm_rel"],
             "update_cosine": program["cosine"],
             "group_cosine": program["group_cosine"],
             "group_cosines": program["group_cosines"],
             "worst_by_norm": program["worst_by_norm"],
             "worst_by_cosine": program["worst_by_cosine"],
             **{k: program[k] for k in (
                 "mapping_norm_rel", "mapping_norm_rels", "mapping_unmoved",
                 "mapping_live", "mapping_norm_rel_by_blob",
                 "mapping_leaves")},
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
             "lower_precision_mapping_norm_rels": control["mapping_norm_rels"],
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
        and program["mapping_norm_rel"] <= tol["mapping_norm_rel"] \
        and program["mapping_unmoved"] == 0 \
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
        "logits_rel_l2", "loss_rel", "res_err_rel", "stream_rel_l2",
        "stream_grad_rel_l2",
        "attention_rel_l2", "attention_grad_rel_l2")]
    rows += [("step_loss_rel", step_facts["loss_rel"], "<=",
              tol["step_loss_rel"]),
             ("update_norm_rel", step_facts["update_norm_rel"], "<=",
              tol["update_norm_rel"]),
             ("mapping_norm_rel", step_facts["mapping_norm_rel"], "<=",
              tol["mapping_norm_rel"]),
             ("mapping_unmoved", step_facts["mapping_unmoved"], "<=", 0),
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
             ("control_one_iteration_res_err_rel",
              ref_facts["one_iteration_res_err_rel"], ">",
              tol["res_err_rel"]),
             ("control_float8_loss_rel",
              ref_facts["lower_precision_loss_rel"], ">", tol["loss_rel"]),
             ("control_float8_stream_rel_l2",
              ref_facts["stream_control"]["stream_rel_l2"], ">",
              tol["stream_rel_l2"]),
             ("control_float8_stream_grad_rel_l2",
              ref_facts["stream_control"]["stream_grad_rel_l2"], ">",
              tol["stream_grad_rel_l2"]),
             ("control_float8_attention_rel_l2",
              ref_facts["attention_control"]["attention_rel_l2"], ">",
              tol["attention_rel_l2"]),
             ("control_float8_attention_grad_rel_l2",
              ref_facts["attention_control"]["attention_grad_rel_l2"], ">",
              tol["attention_grad_rel_l2"]),
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
    # the scores' scale is the prototxt's, from the PUBLISHED head width: a
    # rehearsal cuts the heads and keeps it
    model["scale_head_dim"] = cfg["qk_nope_head_dim"] \
        + cfg["qk_rope_head_dim"]
    if tiny:
        model.update(cfg["cpu_tiny"]["sizes"])
    batch = cfg["cpu_tiny"]["batch_per_chip"] if tiny \
        else int(cell["batch_per_chip"])
    seq = cfg["cpu_tiny"]["seq_len"] if tiny else int(traffic["seq_len"])
    display = int(traffic["display"])
    counted = flops_xing.layers_run(model)

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
    per_token = flops_xing.required_flops_per_token(model, seq)
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

    def per_display(some_rows, suffix, reduce=lambda v: sum(v) / len(v)):
        return [reduce(vals) for vals in (
            [v for k, v in r.items() if k.endswith(suffix)]
            for r in some_rows) if vals]

    held_share = per_display(rows, "_held_share")
    held_by_layer = display_series(rows, "_held_share")
    # which rung each of the WINDOW's MoE layer-steps took: the Engine counts
    # them step by step (cumulative; differenced over the window here)
    held_prefix = {k: after["counters"].get(k, 0) - counted_before.get(k, 0)
                   for k in ("held_prefix_hits", "held_layer_steps")}
    load = per_display(rows, "_expert_load")
    bias_max = per_display(rows, "_bias_max_abs", max)
    dropped = [v for r in rows for k, v in r.items()
               if k.endswith("_dropped")]
    # the stream, a display: what the Sinkhorn iterations leave (the worst
    # sub-layer) and the means of p and q over the sub-layers
    res_err = per_display(rows, "_res_err", max)
    pre_mean = per_display(rows, "_pre_mean")
    post_mean = per_display(rows, "_post_mean")
    sublayers = counted["blocks"] * flops_xing.SUBLAYERS
    place = after["sections"].get("placement", {})
    low, high = cfg["first_loss_band"]
    first_over = first_loss / want_first
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
        # every sub-layer's three counters in every display; the mix
        # doubly stochastic to a few percent (what 20 iterations leave on a
        # diagonal of e^4 is about a thousandth on the worst token of a
        # young model and grows as the mappings train; whether it is the
        # RIGHT remainder is ``res_err_rel``'s to say, against the
        # reference's on the same weights), p inside (0, 1), q inside (0, 2)
        "stream_published": len(res_err) >= 2 and all(
            len([k for k in r if k.endswith("_res_err")]) == sublayers
            for r in rows)
        and all(0.0 <= e < 0.05 for e in res_err)
        and all(0.0 < v < 1.0 for v in pre_mean)
        and all(0.0 < v < 2.0 for v in post_mean),
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
             "first_loss_expected": want_first,
             "window_losses": window["losses"][-3:], "reference": ref_facts,
             "step_reference": step_facts,
             "checks": checks, "steps": window["attempted"],
             "window_s": seconds, "step_s_warmup": step_s,
             "first_step_s": first_step_s,
             "display_intervals_s": intervals,
             "batch_per_chip": batch, "seq_len": seq,
             "tokens_per_s_per_chip": sequences_per_s * seq,
             "flops_per_token": per_token, "token_file": data,
             "stream": {"res_err_max": max(res_err, default=None),
                        "res_err_first_display": res_err[:1],
                        "res_err_last_display": res_err[-1:],
                        "pre_mean": pre_mean[-1:],
                        "post_mean": post_mean[-1:],
                        "bytes_per_step": flops_xing.hc_stream_step(
                            model, batch, seq)["bytes"]},
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
                          "flash_per_step": flops_xing.flash_attention_step(
                              model, batch, seq),
                          "hc_stream_per_step": flops_xing.hc_stream_step(
                              model, batch, seq),
                          "flops_per_assignment":
                              flops_xing.expert_flops_per_assignment(model),
                          "assignments_per_step": counted["sparse"] * seq
                          * batch * model["num_experts_per_tok"],
                          "peaks": peaks,
                          "scopes": cfg["scopes"],
                          "kernel_routes": routes,
                          "held_share": held_share, "expert_load": load,
                          "held_share_by_layer": held_by_layer,
                          "dropped": dropped,
                          "hc_res_err": res_err,
                          # the routing of the steps the profiler saw
                          "traced_held_share": per_display(
                              traced_rows, "_held_share")}},
    }
