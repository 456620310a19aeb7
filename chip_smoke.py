#!/usr/bin/env python3
"""chip_smoke.py — does `train` still start, step and restart on the chip?

Drives the system's main path once, through the entry point a user calls
(``poseidon_tpu.runtime.cli.main(["train", ...])``), at the full width of
bvlc AlexNet: batch 256 x 3 x 227 x 227 per chip, 1000 classes, the LMDB
pipeline, device prefetch and the in-flight window live. Weights and data
are random, made from a seed; only the solver's max_iter / display /
test_iter / test_interval / snapshot are cut. Beside it, the routed
kernels against their XLA arms and one held MOE layer at Trinity-Mini's
shape through both rungs of ``expert_ffn``'s ladder. It claims no speed —
the times it prints are facts about this run on the device it names.

    python chip_smoke.py             # on a TPU host (1 or 4 chips)
    python chip_smoke.py --cpu-tiny  # TEST ONLY: the same flow, cut to CPU
                                     # size, kernels forced + interpreted

A chip belongs to one process at a time, so this process never touches
jax: it runs two children one after the other. ``cold`` takes the steps
and makes every check; ``warm`` is a second fresh process over the same
compile-cache directory and must add nothing to it. Exit code 0 and a last
stdout line ``{"ok": true, "device": {...}}`` mean every phase of both
passed; anything else — no accelerator included — is a non-zero exit and
no result line.
"""

from __future__ import annotations

import argparse
import json
import math
import os
import re
import subprocess
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
# snapshots and LMDBs are gigabytes: they stay in a git-ignored scratch
# directory; only the result summary goes where the chip tool copies back
WORK = os.path.join(ROOT, ".chip_smoke")
RESULT = os.path.join(ROOT, "chiprun_out", "chip_smoke.json")
SOLVER = "examples/imagenet/alexnet_solver.prototxt"
SEED = 0
# the driver's limit is 1200 s for the whole script
COLD_TIMEOUT_S, WARM_TIMEOUT_S = 960, 200


class Size:
    """What is cut for the CPU rehearsal; on the chip nothing is."""

    def __init__(self, tiny: bool):
        self.tiny = tiny
        self.classes = 1000
        self.n_train, self.n_test = (64, 16) if tiny else (1024, 128)
        # bf16 run: >=20 steps with one TEST pass and one mid-run snapshot
        # inside, then a clean display window to time
        self.bf16 = dict(max_iter=24, display=4, test_iter=2,
                         test_interval=12, snapshot=12)
        self.resume = dict(self.bf16, max_iter=28, snapshot=0,
                           snapshot_after_train="false")
        self.short = dict(max_iter=4, display=1, test_iter=1,
                          test_interval=0, snapshot=0,
                          snapshot_after_train="false")
        # the real kernel geometries (N, C, H). Size-5 LRN: AlexNet's two
        # and GoogLeNet's two norm layers at the benchmark cells' batch a
        # chip (batch-minor operands; the tiny one is channel-minor).
        # 3x3 stride-2 max pools: AlexNet's pool1, pool2, pool5, and pool1
        # at a batch of 4 a chip (the kernel's channel-minor orientation,
        # which no benchmark cell runs)
        self.lrn = ([("norm1", (2, 12, 14))] if tiny else
                    [("alexnet norm1", (512, 96, 55)),
                     ("alexnet norm2", (512, 256, 27)),
                     ("googlenet norm1", (128, 64, 56)),
                     ("googlenet norm2", (128, 192, 56))])
        self.pool = ([("pool1", (2, 12, 15))] if tiny else
                     [("pool1", (256, 96, 55)), ("pool2", (256, 256, 27)),
                      ("pool5", (256, 256, 13)),
                      ("pool1 channel-minor", (4, 96, 55))])
        # one held MOE layer (tokens, top-k, experts routed / held, hidden,
        # expert width) of each token cell whose ranks share the experts
        self.moe = ({"tiny": (64, 8, 16, 2, 32, 16)} if tiny else
                    {"kimi_linear": (8192, 8, 256, 8, 2304, 1024),
                     "trinity": (16384, 8, 128, 16, 2048, 1024),
                     "zaya1": (16384, 1, 16, 8, 2048, 2048)})
        # the ATTENTION geometries of the six token cells (sequences, S,
        # query / key-value heads, Dh, Dv, window; 0 = causal)
        self.flash = ({"tiny": (2, 64, 4, 2, 128, 128, 24),
                       "tiny narrow": (1, 64, 2, 2, 24, 16, 0)} if tiny else
                      {"ouro": (1, 8192, 16, 16, 128, 128, 0),
                       "olmoe": (2, 4096, 16, 16, 128, 128, 0),
                       "zaya1": (2, 8192, 8, 2, 128, 128, 0),
                       "trinity window": (2, 8192, 32, 4, 128, 128, 2048),
                       "trinity global": (2, 8192, 32, 4, 128, 128, 0),
                       "smallthinker window": (1, 16384, 28, 4, 128, 128,
                                               4096),
                       "smallthinker global": (1, 16384, 28, 4, 128, 128, 0),
                       "kimi_linear": (1, 8192, 32, 32, 192, 128, 0)})
        # the recurrences of the two delta-rule cells (sequences, S, heads,
        # d_k, d_v, one decay a head?, beta's ceiling): Olmo-Hybrid's Gated
        # DeltaNet at 96 / 192 with beta up to 1.9, Kimi's KDA at 128 / 128.
        # The oracle walks the tokens one by one, so S and the heads are
        # cut; a head's widths, what routes the arm, are not
        self.scan = ({"tiny per-head": (1, 128, 2, 12, 24, True, 1.9),
                      "tiny per-channel": (1, 128, 2, 16, 16, False, 1.0)}
                     if tiny else
                     {"olmo_hybrid": (1, 1024, 4, 96, 192, True, 1.9),
                      "kimi_linear": (1, 1024, 4, 128, 128, False, 1.0)})
        # Granite's Mamba-2 scan (sequences, S, heads, P, N): heads of 64
        # with a state of 128, two heads a lane block, B and C shared
        self.ssd_scan = ({"tiny ssd": (1, 128, 4, 8, 16)} if tiny else
                         {"granite_h": (1, 1024, 8, 64, 128)})


# --------------------------------------------------------------------------- #
# cut-down copies of the example configs (text edits only; same net file)
# --------------------------------------------------------------------------- #

def cut_solver(text: str, **over) -> str:
    for key, val in over.items():
        if key in ("net", "snapshot_prefix"):
            val = f'"{val}"'
        text, n = re.subn(rf"(?m)^{key}:.*$", f"{key}: {val}", text)
        if not n:
            text += f"{key}: {val}\n"
    return text


def write_solver(name: str, net: str, **over) -> str:
    with open(os.path.join(ROOT, SOLVER)) as f:
        text = cut_solver(f.read(), net=net, snapshot_prefix="snap/alexnet",
                          random_seed=SEED, **over)
    path = os.path.join(WORK, f"{name}_solver.prototxt")
    with open(path, "w") as f:
        f.write(text)
    return path


def tiny_net(paths: dict) -> str:
    """--cpu-tiny only: alexnet_train_val.prototxt with batch, crop and
    widths cut and the data sources re-pointed. Layer names and topology
    stay, so every check below runs unchanged."""
    with open(os.path.join(ROOT,
                           "examples/imagenet/alexnet_train_val.prototxt")) as f:
        text = f.read()
    widths = {"96": 16, "256": 32, "384": 32, "4096": 64}
    text = re.sub(r"num_output: (\d+)",
                  lambda m: f"num_output: {widths.get(m[1], m[1])}", text)
    text = re.sub(r"batch_size: \d+", "batch_size: 2", text)
    text = text.replace("crop_size: 227", "crop_size: 67")
    for key, name in (("train", "ilsvrc12_train_lmdb"),
                      ("test", "ilsvrc12_val_lmdb"),
                      ("mean", "ilsvrc12_mean.binaryproto")):
        text = text.replace(f"examples/imagenet/{name}", paths[key])
    path = os.path.join(WORK, "tiny_train_val.prototxt")
    with open(path, "w") as f:
        f.write(text)
    return path


# --------------------------------------------------------------------------- #
# the child: one process, holds the chip
# --------------------------------------------------------------------------- #

def check(cond: bool, what: str) -> None:
    if not cond:
        raise AssertionError(what)


def open_device(tiny: bool) -> dict:
    """Stage the async-collective flags BEFORE the backend exists (once
    jax.devices() has run, cli.main's own staging is silently too late),
    then refuse anything that is not a TPU."""
    from poseidon_tpu import config
    staged = config.enable_tpu_async_collectives()
    import jax
    import jaxlib
    dev = jax.devices()[0]
    info = {"platform": dev.platform, "kind": dev.device_kind,
            "count": len(jax.devices())}
    try:
        import libtpu
        libtpu_version = libtpu.__version__
    except ImportError:
        libtpu_version = None
    print(f"[chip_smoke] platform={dev.platform} "
          f"device_kind={dev.device_kind!r} devices={info['count']} "
          f"jax={jax.__version__} jaxlib={jaxlib.__version__} "
          f"libtpu={libtpu_version} async_collective_flags_staged={staged}",
          flush=True)
    if dev.platform != "tpu" and not tiny:
        print(f"[chip_smoke] REFUSING: jax found no TPU (platform="
              f"{dev.platform!r}); nothing was measured", file=sys.stderr)
        raise SystemExit(2)
    if tiny:
        check(dev.platform == "cpu", "--cpu-tiny is the CPU rehearsal")
        # the rehearsal takes the TPU's arms too: the Pallas max-pool
        # backward and LRN kernels, through the interpreter
        os.environ["POSEIDON_POOL_BWD"] = "pallas"
        os.environ["POSEIDON_PALLAS_LRN"] = "1"
    else:
        check(staged, "async collective flags were not staged")
    return info


def make_data(size: Size) -> dict:
    sys.path.insert(0, os.path.join(ROOT, "examples"))
    import make_synthetic_db
    cut = dict(side=72, out_dir=WORK) if size.tiny else {}
    return make_synthetic_db.build("imagenet", size.n_train, size.n_test,
                                   seed=SEED, **cut)


def run_train(name: str, solver: str, *flags: str) -> dict:
    """One `train` through the CLI; what it reports comes back from the
    artifacts a user would read: stats.yaml and the metric CSVs."""
    from poseidon_tpu.runtime import cli
    from poseidon_tpu.runtime.metrics import read_stats_yaml
    out = os.path.join(WORK, name)
    t0 = time.perf_counter()
    rc = cli.main(["train", f"--solver={solver}", f"--output_dir={out}",
                   *flags])
    wall = time.perf_counter() - t0
    check(rc == 0, f"{name}: cli.main returned {rc}")
    stats = read_stats_yaml(os.path.join(out, "stats.yaml"))

    def rows(kind):
        path = os.path.join(out, f"AlexNet_{kind}_outputs.csv")
        if not os.path.exists(path):
            return []
        with open(path) as f:
            cols = f.readline().strip().split(",")
            return [dict(zip(cols, map(float, ln.strip().split(","))))
                    for ln in f if ln.strip()]

    return {"out": out, "wall_s": round(wall, 2), "stats": stats,
            "train": rows("train"), "test": rows("test0")}


def check_run(name: str, run: dict, size: Size, device: dict, *,
              steps: int, first_loss: bool) -> dict:
    """The checks every train phase shares; returns its facts."""
    stats, n_dev = run["stats"], device["count"]
    losses = [r["loss"] for r in run["train"]] + \
             [r["loss"] for r in run["test"]]
    check(losses and all(math.isfinite(v) for v in losses),
          f"{name}: non-finite or missing loss in {losses}")
    if first_loss:
        want = math.log(size.classes)
        check(abs(losses[0] - want) <= 0.05 * want,
              f"{name}: first displayed loss {losses[0]:.4f} not within 5% "
              f"of ln({size.classes}) = {want:.4f}")
    check(float(stats["counters"]["train_iters"]) == steps,
          f"{name}: ran {stats['counters']['train_iters']} steps, "
          f"wanted {steps}")
    check(stats["device"]["platform"] == device["platform"]
          and int(stats["device"]["count"]) == n_dev,
          f"{name}: engine ran on {stats['device']}")
    # nothing fell off the device path: every (MAX) pool backward and
    # every LRN took its Pallas arm, and the step that ran holds exactly
    # those kernels (an LRN forward + backward is two custom calls, a pool
    # backward one)
    routes = stats["kernel_routes"]
    # the kernels' operand orientation follows the batch a device: the
    # example's 256 fills the lanes, the rehearsal's 2 does not
    minor = f"({'channel' if size.tiny else 'batch'}-minor "
    check(set(routes) == {"norm1", "norm2", "pool1", "pool2", "pool5"}
          and all(routes[k].startswith("lrn=pallas " + minor)
                  for k in ("norm1", "norm2"))
          and all(routes[k].startswith("pool_bwd=pallas " + minor)
                  for k in ("pool1", "pool2", "pool5")),
          f"{name}: kernel routes {routes}")
    # no run here names a layout: `auto` is the channels-last plan on the
    # chip (conv / pool / LRN natively, the one boundary at fc6's flatten)
    # and NCHW on the CPU rehearsal, and the run says so
    plan = stats["conv_layout"]
    on_chip = device["platform"] != "cpu"
    check(plan["asked"] == "auto" and plan["why"] == device["platform"]
          and plan["resolved"] == ("NHWC" if on_chip else "NCHW")
          and plan["boundaries"] == ("pool5->fc6" if on_chip else "none"),
          f"{name}: layout plan {plan}")
    step = stats["compiled_step"]
    check("error" not in step and "pallas_custom_calls" in step,
          f"{name}: the engine could not resolve its step executable and "
          f"fell back: {step} (the log above has the traceback)")
    expect = 0 if size.tiny else sum(
        2 * v.startswith("lrn=pallas") + v.startswith("pool_bwd=pallas")
        for v in routes.values())
    check(int(step["pallas_custom_calls"]) == expect,
          f"{name}: compiled step holds {step['pallas_custom_calls']} "
          f"Pallas custom calls, routing promises {expect}")
    check(set(stats["data_reader"].values()) == {"native"},
          f"{name}: data reader {stats['data_reader']}")
    place = stats["placement"]
    check(len(set(place["batch_shard_devices"].split(","))) == n_dev
          and int(place["param_devices"]) == n_dev
          and place["param_fully_replicated"] == "True",
          f"{name}: placement {place} on {n_dev} devices")
    if n_dev > 1:
        check(int(step["gradient_all_reduces"]) > 0,
              f"{name}: no gradient all-reduce in the compiled step")
    return {"steps": steps, "wall_s": run["wall_s"], "losses": losses,
            "compiled_step": step, "placement": place,
            "host_timers_s": {k: float(stats["timers_sec"][k]) for k in
                              ("train_step", "input_stall", "train_total")},
            "peak_bytes_in_use": stats.get("gauges", {}).get(
                "peak_bytes_in_use", "not reported")}


def check_restart(name: str, facts: dict, *, must_load: bool) -> None:
    """A restart must not compile its step again. Where the aot/ store
    holds the step (the bf16 phase put or found it there) the restart must
    load it: `loaded serialized train step`. Where it does not — the XLA
    cache answered that first compile and the engine does not re-serialize
    what it hands back, or the store could not be written; both are logged
    — the XLA cache must answer."""
    source = facts["compiled_step"]["source"]
    check(source == "loaded" or (source == "xla_cache" and not must_load),
          f"{name}: step source {source!r} ({facts['compiled_step']}); "
          f"wanted {'the serialized executable' if must_load else 'no compile'}")


def steady_ms_per_step(rows: list, after_iter: int) -> float:
    """Wall time between display boundaries past ``after_iter`` — each is a
    hard sync that has read that step's loss back from the device (the
    blocking read), with no TEST pass or snapshot in between."""
    window = [r for r in rows if r["iter"] >= after_iter]
    return round((window[-1]["time"] - window[0]["time"]) * 1e3
                 / (window[-1]["iter"] - window[0]["iter"]), 2)


def check_kernels(size: Size) -> dict:
    """Each routed kernel on the train path against its other arm, on this
    device, at the CNN cells' geometries, to tests/test_kernels.py's
    tolerances:
    the Pallas LRN (compiled; interpreted only under --cpu-tiny) against
    the XLA formulation, the Pallas max-pool backward and the tap-sum
    against select-and-scatter."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from poseidon_tpu.ops import nn as NN
    from poseidon_tpu.ops.pallas_kernels import lrn_fused

    tol = {"float32": dict(fwd=(1e-5, 1e-6), lrn_bwd=(1e-4, 1e-5),
                           pool_bwd=(1e-5, 1e-5)),
           "bfloat16": dict(fwd=(0.05, 0.05), lrn_bwd=(0.05, 0.05),
                            pool_bwd=(0.05, 0.1))}
    facts = {}
    rs = np.random.RandomState(SEED)
    forced_arm = os.environ.get("POSEIDON_POOL_BWD")

    def compiled_kernel(fn, x):
        """The Pallas arm must BE a Mosaic custom call here, not the
        interpreter and not a quiet route to the XLA formulation."""
        jitted = jax.jit(fn)
        check(("tpu_custom_call" in jitted.lower(x).as_text()) != size.tiny,
              f"Pallas arm of {fn} lowered wrongly")
        return jitted(x)

    def close(got, want, key, dtype, what):
        rtol, atol = tol[dtype][key]
        got, want = (np.asarray(a, np.float32) for a in (got, want))
        np.testing.assert_allclose(got, want, rtol=rtol, atol=atol,
                                   err_msg=what)
        facts[what] = float(np.max(np.abs(got - want)))

    for dtype in ("float32", "bfloat16"):
        for name, (n, c, h) in size.lrn:
            x = jnp.asarray(rs.randn(n, c, h, h), dtype)
            args = (5, 1e-4, 0.75, 1.0)

            def sq(fn):
                return lambda x_: jnp.sum(fn(x_, *args).astype(
                    jnp.float32) ** 2)

            close(compiled_kernel(lambda x_: lrn_fused(x_, *args), x),
                  jax.jit(lambda x_: NN.lrn_across_channels(x_, *args))(x),
                  "fwd", dtype, f"lrn_fused fwd {name} {dtype}")
            close(compiled_kernel(jax.grad(sq(lrn_fused)), x),
                  jax.jit(jax.grad(sq(NN.lrn_across_channels)))(x),
                  "lrn_bwd", dtype, f"lrn_fused bwd {name} {dtype}")
        for name, (n, c, h) in size.pool:
            x = jnp.asarray(rs.randn(n, c, h, h), dtype)
            grads = {}
            for arm in ("sas", "taps", "pallas"):
                # the arm is read at trace time: a fresh function per arm
                os.environ["POSEIDON_POOL_BWD"] = arm
                grad = jax.grad(lambda x_: jnp.sum(
                    NN.max_pool(x_, (3, 3), (2, 2), (0, 0), "NCHW").astype(
                        jnp.float32) ** 2))
                grads[arm] = (compiled_kernel(grad, x) if arm == "pallas"
                              else jax.jit(grad)(x))
            for arm in ("taps", "pallas"):
                close(grads["sas"], grads[arm], "pool_bwd", dtype,
                      f"pool_bwd sas vs {arm} max {name} {dtype}")
    # bf16: the four windows over input (2, 2) send it 256 + 1 + 1 + 1; an
    # f32 sum rounds once, to 260, a bf16 accumulator stays at 256 (which
    # is what the TPU's select-and-scatter does once it has a bf16 result)
    # (in every plane of one 16 x 128 register tile of channels x batch:
    # Mosaic does not take the kernel's blocks at a 1 x 1 tile)
    x = jnp.zeros((128, 16, 5, 5), jnp.bfloat16).at[:, :, 2, 2].set(1)
    g = jnp.broadcast_to(jnp.asarray([[256, 1], [1, 1]], jnp.bfloat16),
                         (128, 16, 2, 2))
    for arm, method, pool, scale in (
            ("pallas", "max", NN.max_pool, 1), ("sas", "max", NN.max_pool, 1),
            ("sas", "ave", NN.ave_pool, 9)):                 # 9: AVE's / 9
        os.environ["POSEIDON_POOL_BWD"] = arm
        _, vjp = jax.vjp(jax.jit(lambda x_: pool(
            x_, (3, 3), (2, 2), (0, 0), "NCHW")), x)
        dx = jax.jit(vjp)(g * scale)[0]
        got = facts[f"pool_bwd {arm} {method} bf16 overlap sum"] = float(
            dx[0, 0, 2, 2])
        check(bool(jnp.all(dx[:, :, 2, 2] == 260.0)),
              f"{method} pool backward ({arm}) summed its overlaps in "
              f"bf16: {dx[0, 0]}")
    if forced_arm is None:
        del os.environ["POSEIDON_POOL_BWD"]
    else:
        os.environ["POSEIDON_POOL_BWD"] = forced_arm
    return facts


def check_flash(size: Size) -> dict:
    """The flash kernels (forward, and the backward's single sweep with a
    head's dQ rows resident) at the token cells' ATTENTION geometries
    against the dense op, the output and all three gradients under the bf16
    policy on this device, on both operand forms: head-major (B, H, S, D)
    everywhere, and token-major (B, S, H·D), a head a lane block in the
    index maps, wherever ``flash_operand_form`` sends an ATTENTION layer
    that way (Kimi's 192-wide heads stay head-major). Key-value heads are
    repeated to the query heads outside the kernels, as ``rope_attention``
    does; the dense op runs a head at a time (its scores are S x S)."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax import lax
    from poseidon_tpu.config import policy_scope
    from poseidon_tpu.ops.attention import attention
    from poseidon_tpu.ops.pallas_kernels import (flash_attention,
                                                 flash_operand_form)

    def one(shape):
        b, s, h, g, d, dv, window = shape
        rs = np.random.RandomState(SEED)
        q, k, v, cot = (jnp.asarray(rs.randn(b, n, s, w) * 0.5, jnp.bfloat16)
                        for n, w in ((h, d), (g, d), (g, dv), (h, dv)))
        lanes = lambda t: t.swapaxes(1, 2).reshape(b, s, -1)
        repeat = lambda t: jnp.repeat(t, h // g, axis=1)

        def dense(q, k, v):
            per_head = lambda t: t.reshape((b * h, 1, 1) + t.shape[2:])
            # a head at a time in the backward too: its (S, S) scores are
            # recomputed, not kept for every head at once
            out = lax.map(jax.checkpoint(lambda a: attention(
                *a, causal=True, window=window or None)),
                tuple(per_head(t) for t in (q, repeat(k), repeat(v))))
            return out.reshape(b, h, s, dv)

        def head_major(q, k, v):
            return flash_attention(q, repeat(k), repeat(v), True,
                                   window=window or None)

        def token_major(q, k, v):
            out = flash_attention(lanes(q), lanes(repeat(k)),
                                  lanes(repeat(v)), True,
                                  window=window or None, heads=h)
            return out.reshape(b, s, h, dv).swapaxes(1, 2)

        def stepped(fn):
            return jax.jit(jax.value_and_grad(
                lambda *a: (lambda y: (jnp.sum((y * cot).astype(
                    jnp.float32)), y))(fn(*a)), argnums=(0, 1, 2),
                has_aux=True))

        forms = {"head-major": head_major}
        if flash_operand_form(s, d, dv)[0]:
            forms["token-major"] = token_major
        (_, want), want_grads = stepped(dense)(q, k, v)
        facts = {"operand form": flash_operand_form(s, d, dv)[1]}
        for form, fn in forms.items():
            fn = stepped(fn)
            calls = fn.lower(q, k, v).as_text().count("tpu_custom_call")
            # forward and the backward's single sweep
            check((calls == 2) != size.tiny,
                  f"flash {form} at {shape}: {calls} Mosaic calls lowered")
            (_, got), got_grads = fn(q, k, v)
            for name, a, w in zip(("out", "dq", "dk", "dv"),
                                  (got,) + got_grads, (want,) + want_grads):
                a, w = (np.asarray(t, np.float32) for t in (a, w))
                rel = float(np.linalg.norm(a - w)
                            / max(np.linalg.norm(w), 1e-30))
                facts[f"{form}: {name} relative l2"] = rel
                # bf16 operands and a bf16 result on both sides
                # (tests/test_pallas.py's bound)
                check(np.any(w) and rel < 2e-2,
                      f"flash {form} at {shape}: {name} differs from the "
                      f"dense op by {rel} (relative L2)")
        return facts

    with policy_scope(compute_dtype=jnp.bfloat16):
        return {name: one(shape) for name, shape in size.flash.items()}


def check_scan(size: Size) -> dict:
    """The gated delta rule's scan at the two delta-rule cells' head widths
    against the token-by-token recurrence on this device, the output and all
    five gradients, f32 operands: the per-head decay at 96 / 192 with every
    write at beta 1.9, the per-channel decay at 128 / 128, each through the
    arm ``kda_route`` chose for its shape here (in the facts, with the
    reason where it is not ``pallas``); and Mamba-2's scan at Granite's 64 x
    64 x 128 (two heads a lane block) through the arm ``ssd_route`` chose,
    the output and all six gradients."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from poseidon_tpu.ops.kda import kda_recurrence, kda_route, kda_scan

    def one(shape):
        b, s, h, d_k, d_v, per_head, beta_max = shape
        rs = np.random.RandomState(SEED)
        q, k = rs.randn(2, b, s, h, d_k)
        q /= np.linalg.norm(q, axis=-1, keepdims=True)
        k /= np.linalg.norm(k, axis=-1, keepdims=True)
        g = -np.exp(rs.uniform(-6, 0, size=(b, s, h) if per_head
                               else (b, s, h, d_k)))
        beta = np.full((b, s, h), beta_max) if per_head \
            else beta_max / (1 + np.exp(-rs.randn(b, s, h)))
        args = [jnp.asarray(x, jnp.float32)
                for x in (q, k, rs.randn(b, s, h, d_v), g, beta)]
        cot = jnp.asarray(rs.randn(b, s, h, d_v), jnp.float32)

        def stepped(fn):
            return jax.jit(jax.value_and_grad(
                lambda *a: (lambda y: (jnp.sum(y * cot), y))(
                    fn(*a).astype(jnp.float32)),
                argnums=(0, 1, 2, 3, 4), has_aux=True))

        arm, note = kda_route(s, d_k, d_v, h, 4, per_head=per_head)
        facts = {"arm": arm, "route": note}
        (_, got), got_grads = stepped(kda_scan)(*args)
        (_, want), want_grads = stepped(kda_recurrence)(*args)
        for name, a, w in zip(("out", "dq", "dk", "dv", "dg", "dbeta"),
                              (got,) + got_grads, (want,) + want_grads):
            a, w = (np.asarray(t, np.float64) for t in (a, w))
            rel = float(np.linalg.norm(a - w) / max(np.linalg.norm(w), 1e-30))
            facts[f"{name} relative l2"] = rel
            check(np.all(np.isfinite(a)) and np.any(w) and rel < 1e-3,
                  f"scan at {shape} ({arm}): {name} differs from the "
                  f"recurrence by {rel} (relative L2)")
        return facts

    def ssd(shape):
        """Mamba-2's scan through the arm ``ssd_route`` chose, forward and
        all six gradients (x, dt, a, B, C, D) against the recurrence."""
        from poseidon_tpu.ops.ssd import ssd_recurrence, ssd_route, ssd_scan
        b, s, h, p, n = shape
        rs = np.random.RandomState(SEED)
        dt = np.log1p(np.exp(rs.randn(b, s, h) - 3.0))
        args = [jnp.asarray(x, jnp.float32) for x in (
            rs.randn(b, s, h, p), dt, -dt * rs.uniform(1, 16, h),
            0.3 * rs.randn(b, s, n), 0.3 * rs.randn(b, s, n),
            1 + 0.3 * rs.randn(h))]
        cot = jnp.asarray(rs.randn(b, s, h, p), jnp.float32)

        def stepped(fn):
            return jax.jit(jax.value_and_grad(
                lambda *a: (lambda y: (jnp.sum(y * cot), y))(
                    fn(*a).astype(jnp.float32)),
                argnums=tuple(range(6)), has_aux=True))

        arm, note = ssd_route(s, h, p, n)
        facts = {"arm": arm, "route": note}
        (_, got), got_grads = stepped(ssd_scan)(*args)
        (_, want), want_grads = stepped(ssd_recurrence)(*args)
        for name, a, w in zip(("out", "dx", "ddt", "da", "dB", "dC", "dD"),
                              (got,) + got_grads, (want,) + want_grads):
            a, w = (np.asarray(t, np.float64) for t in (a, w))
            rel = float(np.linalg.norm(a - w) / max(np.linalg.norm(w), 1e-30))
            facts[f"{name} relative l2"] = rel
            check(np.all(np.isfinite(a)) and np.any(w) and rel < 1e-3,
                  f"ssd scan at {shape} ({arm}): {name} differs from the "
                  f"recurrence by {rel} (relative L2)")
        return facts

    return {**{name: one(shape) for name, shape in size.scan.items()},
            **{name: ssd(shape) for name, shape in size.ssd_scan.items()}}


HELD_SHARES = (0.03, 0.06, 0.125, 0.25, 0.5, 1.0)


def check_held_chunks(size: Size) -> dict:
    """One held MOE layer at each token cell's shape through
    ``expert_ffn``'s chunked loop against the straight-line arm over all
    T k rows, forward and every gradient, under the bf16 policy on this
    device: a routing at the even share and one a chunk and a row long (two
    trips, the second all padding but one row). Then the milliseconds of
    forward + backward at 3% to 100% of the assignments live, for the
    rule's chunk length beside half the even share, the even share and
    twice it: what the rule was chosen by (PERF.md, PR 43). ``ragged_dot``
    with rows past the last group, the P-row scatter-adds and the loop with
    a trip count of the run are all lowered for the chip here and nowhere
    on the CPU."""
    import time

    import jax
    import jax.numpy as jnp
    import numpy as np
    from poseidon_tpu.config import policy_scope
    from poseidon_tpu.models import moe

    f32 = jnp.float32

    def ms(fn, *args, calls=5):
        jax.block_until_ready(fn(*args))
        t0 = time.perf_counter()
        jax.block_until_ready([fn(*args) for _ in range(calls)])
        return (time.perf_counter() - t0) / calls * 1e3

    def one(shape):
        t, top_k, n_exp, n_held, d, f = shape
        rows = t * top_k
        chunk = moe.held_chunk_rows(rows, n_held, n_exp)
        rs = np.random.RandomState(SEED)
        x = jnp.asarray(rs.randn(t, d), jnp.bfloat16)
        weights = jnp.asarray(rs.rand(t, top_k) + 0.1, f32)
        gate, up = (jnp.asarray(rs.randn(n_held, f, d) * d ** -0.5, f32)
                    for _ in range(2))
        down = jnp.asarray(rs.randn(n_held, d, f) * f ** -0.5, f32)
        cot = jnp.asarray(rs.randn(t, d), f32)

        def routing(live):
            experts = rs.randint(n_held, n_exp, size=rows)
            experts[rs.permutation(rows)[:live]] = rs.randint(0, n_held, live)
            return moe.expert_sizes(jnp.asarray(experts.reshape(t, top_k)),
                                    n_exp)

        def by_held_expert(flat_e):
            here = flat_e < n_held
            return here, jnp.argsort(jnp.where(here, flat_e, n_held),
                                     stable=True)

        def rule(x, weights, gate, up, down, flat_e, sizes):
            return moe.expert_ffn(x, weights, flat_e, sizes, gate, up, down)

        def chunks_of(p):
            def loop(x, weights, gate, up, down, flat_e, sizes):
                return moe._held_chunks(p, x, weights, gate, up, down,
                                        by_held_expert(flat_e)[1],
                                        sizes[:n_held])
            return loop

        def straight(x, weights, gate, up, down, flat_e, sizes):
            # every T k row gathered, multiplied and brought back by the
            # inverse permutation: no loop, autodiff's derivative
            here, order = by_held_expert(flat_e)
            return moe._held_rows(x, weights, here, order, sizes[:n_held],
                                  gate, up, down)

        def stepped(fn):
            return jax.jit(jax.value_and_grad(
                lambda *a: (lambda y: (jnp.sum(y.astype(f32) * cot), y))(
                    fn(*a)), argnums=(0, 1, 2, 3, 4), has_aux=True))

        facts = {"rows": rows, "chunk": chunk,
                 "loop": moe.held_rows_loop(rows, chunk)}
        both = {"chunks": stepped(chunks_of(chunk)),
                "straight line": stepped(straight)}
        # what expert_ffn itself traces at this shape: the loop from three
        # chunks on, straight-line rows below (ZAYA1), never a conditional
        text = stepped(rule).lower(x, weights, gate, up, down,
                                   *routing(1)).as_text()
        check(("stablehlo.while" in text) == facts["loop"],
              f"the held arm's loop at {shape}: not as held_rows_loop says")
        check("stablehlo.case" not in text and "stablehlo.if" not in text,
              "the held arm traced a conditional")
        for case, live in (("even share", rows * n_held // n_exp),
                           ("over a chunk", min(chunk + 1, rows))):
            args = (x, weights, gate, up, down) + routing(live)
            (_, y), grads = both["chunks"](*args)
            (_, y0), grads0 = both["straight line"](*args)
            for name, a, b in zip(("y", "dx", "dweights", "dgate", "dup",
                                   "ddown"), (y,) + grads, (y0,) + grads0):
                a, b = (np.asarray(v, np.float32) for v in (a, b))
                rel = float(np.linalg.norm(a - b)
                            / max(np.linalg.norm(b), 1e-30))
                facts[f"{case}: {name} largest difference"] = float(
                    np.max(np.abs(a - b)))
                facts[f"{case}: {name} relative l2"] = rel
                # dx, dgate and dup keep in f32 what autodiff rounds to
                # bf16: a bf16 ulp apart at most (tests/test_moe.py)
                check(np.any(b) and rel <= 2.0 ** -7,
                      f"held chunks, {case}: {name} differs from the "
                      f"straight line by {rel} (relative L2)")
            facts[f"{case}: straight line ms"] = ms(both["straight line"],
                                                    *args)
        # the rule's length beside half the even share, the even share
        # and twice it
        tile = moe._ROW_TILE
        even = -(-rows * n_held // (n_exp * tile)) * tile
        lengths = sorted({max(even // 2 // tile, 1) * tile, even,
                          min(2 * even, -(-rows // tile) * tile), chunk})
        routings = {share: routing(int(share * rows))
                    for share in HELD_SHARES}
        for p in lengths:
            fn = both["chunks"] if p == chunk else stepped(chunks_of(p))
            facts[f"ms at chunk {p}"] = {
                str(share): ms(fn, x, weights, gate, up, down, *routed)
                for share, routed in routings.items()}
        return facts

    with policy_scope(compute_dtype=jnp.bfloat16):
        return {name: one(shape) for name, shape in size.moe.items()}


def child_cold(size: Size) -> dict:
    from poseidon_tpu.runtime.compile_cache import (aot_entries,
                                                    cache_entries,
                                                    resolve_cache_dir)
    device = open_device(size.tiny)
    cache = resolve_cache_dir()
    result = {"device": device, "cache_dir": cache,
              "xla_entries_at_start": cache_entries(cache),
              "aot_entries_at_start": aot_entries(cache), "phases": {}}
    phases = result["phases"]
    paths = make_data(size)
    net = (tiny_net(paths) if size.tiny
           else "examples/imagenet/alexnet_train_val.prototxt")

    # 1. what `train` does with no flags: f32, Precision.HIGHEST
    run = run_train("f32", write_solver("f32", net, **size.short))
    phases["train_f32"] = check_run("f32", run, size, device, steps=4,
                                    first_loss=True)

    # 2. the bf16 path, >=20 steps, a TEST pass and a snapshot inside
    run = run_train("bf16", write_solver("bf16", net, **size.bf16), "--bf16")
    facts = check_run("bf16", run, size, device, steps=24, first_loss=True)
    check(len(run["test"]) >= 1, "bf16: no TEST pass ran")
    snap = os.path.join(run["out"], "snap",
                        "alexnet_iter_24.solverstate.npz")
    check(os.path.exists(snap) and os.path.exists(
        snap.replace("_iter_24", "_iter_12")), f"bf16: no snapshot {snap}")
    # on a cache that was empty when this process started, the step can
    # only have been compiled here
    step = facts["compiled_step"]
    held = result["xla_entries_at_start"] + result["aot_entries_at_start"]
    check(held > 0 or step["source"] == "compiled",
          f"bf16: step {step} on an empty cache")
    result["step_in_aot_store"] = step["stored"] in ("yes", "found")
    if not result["step_in_aot_store"]:
        print(f"[chip_smoke] NOTE: the bf16 step is not in {cache}/aot "
              f"({step}); the restarts below must be answered by the XLA "
              f"cache instead", file=sys.stderr)
    facts["ms_per_step_after_warmup"] = steady_ms_per_step(run["train"], 16)
    facts["test_rows"] = run["test"]
    phases["train_bf16"] = facts

    # 3. restore that snapshot and continue: the step comes back from the
    # aot/ store, not from a second compile
    run = run_train("resume", write_solver("resume", net, **size.resume),
                    "--bf16", f"--snapshot={snap}")
    facts = check_run("resume", run, size, device, steps=4, first_loss=False)
    check(int(run["stats"]["gauges"]["iteration"]) == 28,
          f"resume: ended at {run['stats']['gauges']['iteration']}, not 28")
    check_restart("resume", facts, must_load=result["step_in_aot_store"])
    phases["resume"] = facts

    # 4. several chips: fc6/fc7 through SFB's all-gather
    if device["count"] > 1:
        run = run_train("sfb", write_solver("sfb", net, **size.short),
                        "--bf16", "--sfb-auto")
        facts = check_run("sfb", run, size, device, steps=4,
                          first_loss=True)
        layers = run["stats"]["comm"]["per_layer"]
        sfb = sorted(k for k, v in layers.items() if v["strategy"] == "sfb")
        check({"fc6", "fc7"} <= set(sfb), f"sfb: SFB layers {sfb}")
        facts["sfb_layers"] = sfb
        phases["train_sfb_auto"] = facts

    # 5. the kernels, one by one, against their XLA arms
    phases["kernels"] = check_kernels(size)

    # 6. a held MOE layer's chunked loop at the token cells' shapes
    phases["held_chunks"] = check_held_chunks(size)

    # 7. the flash kernels at the token cells' geometries, both operand
    # forms, against the dense op
    phases["flash"] = check_flash(size)

    # 8. the delta-rule scans at the two cells' head widths and Mamba-2's
    # at Granite's, each through the arm its route chose, against the
    # recurrence
    phases["scan"] = check_scan(size)
    result["resume_from"] = snap
    result["net"] = net
    result["xla_entries_at_end"] = cache_entries(cache)
    return result


def child_warm(size: Size, cold: dict) -> dict:
    """A second fresh process over the same cache directory replays the
    resume phase: the step must come from aot/ and the XLA cache must not
    grow by one entry."""
    from poseidon_tpu.runtime.compile_cache import resolve_cache_dir
    device = open_device(size.tiny)
    cache = resolve_cache_dir()
    check(device == cold["device"] and cache == cold["cache_dir"],
          f"warm process sees {device} / {cache}")
    before = set(os.listdir(cache))
    run = run_train("warm", write_solver("resume", cold["net"],
                                         **size.resume),
                    "--bf16", f"--snapshot={cold['resume_from']}")
    facts = check_run("warm", run, size, device, steps=4, first_loss=False)
    added = sorted(n for n in set(os.listdir(cache)) - before
                   if n.endswith("-cache"))
    check_restart("warm", facts, must_load=cold["step_in_aot_store"])
    check(not added, f"warm: new XLA cache entries in {cache}: {added}")
    facts["xla_entries_added"] = len(added)
    return {"device": device, "phases": {"warm_resume": facts}}


# --------------------------------------------------------------------------- #
# the parent: never imports jax
# --------------------------------------------------------------------------- #

def run_child(phase: str, tiny: bool, timeout_s: int) -> dict:
    out = os.path.join(WORK, f"{phase}.json")
    if os.path.exists(out):
        os.unlink(out)
    cmd = [sys.executable, os.path.abspath(__file__), f"--phase={phase}"]
    if tiny:
        cmd.append("--cpu-tiny")
    t0 = time.perf_counter()
    # subprocess.run kills the child at the timeout: nothing this script
    # starts outlives it
    rc = subprocess.run(cmd, cwd=ROOT, timeout=timeout_s).returncode
    if rc != 0:
        print(f"[chip_smoke] {phase} process FAILED (exit {rc})",
              file=sys.stderr)
        raise SystemExit(rc)
    with open(out) as f:
        result = json.load(f)
    result["process_wall_s"] = round(time.perf_counter() - t0, 1)
    return result


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--cpu-tiny", action="store_true",
                    help="TEST ONLY: rehearse the whole flow on CPU at a "
                         "tiny size with the Pallas kernels interpreted")
    ap.add_argument("--phase", choices=["cold", "warm"],
                    help=argparse.SUPPRESS)
    args = ap.parse_args()
    os.chdir(ROOT)
    os.makedirs(WORK, exist_ok=True)
    size = Size(args.cpu_tiny)

    if args.phase:                       # a child: does the work
        sys.path.insert(0, ROOT)
        if args.phase == "cold":
            result = child_cold(size)
        else:
            with open(os.path.join(WORK, "cold.json")) as f:
                result = child_warm(size, json.load(f))
        with open(os.path.join(WORK, f"{args.phase}.json"), "w") as f:
            json.dump(result, f, indent=1)
        return 0

    cold = run_child("cold", args.cpu_tiny, COLD_TIMEOUT_S)
    warm = run_child("warm", args.cpu_tiny, WARM_TIMEOUT_S)
    device = cold["device"]
    label = f"{device['count']}x {device['kind']} ({device['platform']})"
    print(f"\n[chip_smoke] every phase passed on {label}; facts about this "
          f"run, not claims:")
    for proc in (cold, warm):
        for name, facts in proc["phases"].items():
            print(f"[chip_smoke]   {name} [{label}]: "
                  f"{json.dumps(facts, sort_keys=True)}")
    print(f"[chip_smoke]   cold process {cold['process_wall_s']} s "
          f"(cache held {cold['xla_entries_at_start']} XLA entries at "
          f"start, {cold['xla_entries_at_end']} at end), warm process "
          f"{warm['process_wall_s']} s; cache at {cold['cache_dir']}")
    os.makedirs(os.path.dirname(RESULT), exist_ok=True)
    with open(RESULT, "w") as f:
        json.dump({"cold": cold, "warm": warm}, f, indent=1)
    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
