"""Kernels layer: device milliseconds per step in the ATTENTION layers of
every application of every layer of a looped LM — head split, rotary
positions, the flash kernels (forward, its replay under remat, dQ, dK/dV),
head merge. The q, k, v, o projections and the norms are layers of their
own and not in it."""

import scope_trace


def reduce(run: dict):
    if "scopes" not in (run.get("lm") or {}):
        return None
    return scope_trace.ms_per_step(run, layer_types=("ATTENTION",))
