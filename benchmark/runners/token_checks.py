"""What a token cell's runner does whatever its model, in ONE place, for the
runners from ``granite_train`` on to import (the accepted runners keep the
copies they have: an accepted benchmark file is a ``benchmark`` PR's to
edit, PERF.md 36e / 54d): the window (``train_window``: warm-up, the
measured steps between two hard syncs, the traced steps), the step
comparison (``step_check``: the Engine's own first step against the
reference's, with the leaves too small to hold one by one held in GROUPS),
``compared`` (every number that decided ``correct`` beside its limit) and
the display rows' series. The model's own part comes in as arguments: the
reference's ``cfg`` (``sizes``), the groups of leaves, the forward
comparison's rows.
"""

from __future__ import annotations

import math
import os
import shutil
import time

import device as device_mod
from runners.caffe_train import (CompileCounter, LmdbFeed, build_engine,
                                 trace_window)
from runners.olmo_hybrid_train import _reference, _rel
from runners.zaya_train import first_step

reference_of, rel = _reference, _rel
# of the stall ledger (stats section ``stalls``), what the facts line keeps
STALL_TOTALS = ("steps", "pace_ms", "window_ms", "stalls", "lost_ms",
                "lost_ms_by_cause", "longest_ms", "freeze_ms")


def train_window(job: dict, argv: list, work: str, platform: str) -> dict:
    """The job through the program's own ``train`` command: warm-up to 1,
    ``display`` and 2 x ``display`` steps (all of it set-up; the first
    step's change of every leaf is kept for ``step_check``), then the
    measured window of whole displays nearest ``job["seconds"]``, opened
    and closed on a hard sync, and with ``--trace`` the traced steps after
    it. The Engine is closed, its solver state dropped; its weights
    (``params``) stay on the device for the forward comparison."""
    from poseidon_tpu.runtime.spans import recorder
    clock, traffic = time.perf_counter, job["traffic"]
    display = int(traffic["display"])
    eng = build_engine(argv)
    try:
        t = clock()
        step = first_step(eng, job["config"])
        first_step_s = clock() - t
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
        out = {"step": step, "first_step_s": first_step_s, "step_s": step_s,
               "window": window, "seconds": seconds,
               "setup_s": t0 - job["t_start"], "compiles": compiles.count,
               "spans": recorder.trace_events() if job["trace"] else [],
               "stats": eng.stats.snapshot(),
               "memory_peak": device_mod.memory_peak_bytes(),
               "rows": eng.metrics.rows[rows_before:], "trace": None}
        if job["trace"]:
            kept = os.path.join(work, "trace")
            out["trace"] = trace_window(feed, int(traffic["trace_steps"]),
                                        platform, kept)
            recorder.disable()
            if job.get("keep_trace"):
                shutil.copytree(kept, job["keep_trace"], dirs_exist_ok=True)
            shutil.rmtree(kept, ignore_errors=True)
    finally:
        eng.close()
    # the Engine's Adam moments leave the device, its weights stay
    out["params"], eng.params, eng.state = eng.params, None, None
    return out


def display_series(rows: list, suffix: str) -> dict:
    """{a display top that ends in ``suffix``: its value in every display
    row that has it}"""
    tops = sorted({k for r in rows for k in r if k.endswith(suffix)})
    return {top: [r[top] for r in rows if top in r] for top in tops}


def series_mean(by_top: dict):
    vals = [v for series in by_top.values() for v in series]
    return sum(vals) / len(vals) if vals else None


def stall_totals(stats: dict):
    """The window's stall ledger as the program made it (a traced run's:
    the recorder is on), totals only, so that a slow run's facts line says
    what it lost and to what; None where the program published none."""
    sec = stats.get("sections", {}).get("stalls")
    return {k: sec[k] for k in STALL_TOTALS if k in sec} if sec else None


def grouped_cosines(got: dict, other: dict, groups: dict) -> dict:
    """{group: the cosine between two steps' changes ({layer: [blobs]}) of
    that group's leaves, every layer's as ONE vector}. A group is a list of
    (layer-name suffix, blob index, (from, to) along the blob's LAST axis or
    None for all of it)."""
    import numpy as np

    def as_one(changes, parts):
        return np.concatenate([
            np.asarray(changes[name][j], np.float64)[
                ..., slice(*(cut or (None,)))].ravel()
            for suffix, j, cut in parts
            for name in sorted(changes) if name.endswith(suffix)])

    out = {}
    for group, parts in groups.items():
        a, b = as_one(got, parts), as_one(other, parts)
        out[group] = float(a @ b / max(
            np.linalg.norm(a) * np.linalg.norm(b), 1e-300))
    return out


def step_check(job: dict, sizes: dict, seq: int, step: dict,
               groups: dict = None):
    """The Engine's own compiled step against the reference's: ``step``
    holds the seeded weights (``before``), the change the run's FIRST step
    made to every leaf (``change``), that step's loss, its batch and the
    solver's numbers, all on the host (``zaya_train.first_step``). The
    reference takes the same step in f32 (``train_step`` on ``sizes``), and
    once more with its matmul inputs rounded to
    ``reference_lower_precision``, which has to lie outside a limit. Decided
    by: the loss (where the tolerance has a limit for it: under bf16 it is a
    fact only); every leaf's change in norm (worst leaf: a leaf left
    unchanged reads 1); the direction of the change of every leaf of
    ``cosine_from`` numbers or more (worst cosine); and, with ``groups``,
    the direction of the change of each group of smaller leaves, all
    layers' as one vector (``grouped_cosines``; the worst group against
    ``group_cosine``): Adam's first change of a leaf has the norm lr sqrt(n)
    whatever its direction, so a gradient of the wrong sign behind a leaf
    under ``cosine_from`` passes every other limit."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    cfg = job["config"]
    ref, tol = reference_of(job)
    opt = dict(step["opt"])
    first_rate = opt.pop("first_rate")
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
        between them; the worst of each first; the groups."""
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
        by_group = grouped_cosines(got, other, groups or {})
        return {"norm_rel": by_norm[0]["norm_rel"],
                "cosine": by_cosine[0]["cosine"] if by_cosine else 1.0,
                "group_cosine": min(by_group.values(), default=1.0),
                "group_cosines": by_group,
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
             "group_cosine": program["group_cosine"],
             "group_cosines": program["group_cosines"],
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
        and (not groups or program["group_cosine"] >= tol["group_cosine"])
    return facts, ok


def compared(tol: dict, first: tuple, forward: list, step_facts: dict,
             controls: list) -> list:
    """Every number that decided ``correct`` beside its limit, then the
    controls beside the limits they have to break. ``first``: the first loss
    over its expectation and the band's two ends; ``forward``: the forward
    comparison's (name, value) pairs, each against ``tol[name]`` from above;
    ``controls``: (name, value, "<" or ">", the limit's name)."""
    first_over, first_low, first_high = first
    rows = [("first_loss_over_expected", first_over, ">=", first_low),
            ("first_loss_over_expected", first_over, "<=", first_high)]
    rows += [(name, value, "<=", tol[name]) for name, value in forward]
    rows += [("step_loss_rel", step_facts["loss_rel"], "<=",
              tol["step_loss_rel"]),
             ("update_norm_rel", step_facts["update_norm_rel"], "<=",
              tol["update_norm_rel"]),
             ("update_cosine", step_facts["update_cosine"], ">=",
              tol["update_cosine"])]
    if step_facts["group_cosines"]:
        rows.append(("group_cosine", step_facts["group_cosine"], ">=",
                     tol["group_cosine"]))
    rows += [("control_" + name, value, op, tol[limit])
             for name, value, op, limit in controls]
    ops = {"<=": lambda a, b: a <= b, ">=": lambda a, b: a >= b,
           ">": lambda a, b: a > b, "<": lambda a, b: a < b}
    return [{"name": name, "value": value, "must_be": op, "limit": limit,
             "holds": None if limit is None else bool(ops[op](value, limit)),
             "decides_correct": not name.startswith("control_")
             and limit is not None}
            for name, value, op, limit in rows]
