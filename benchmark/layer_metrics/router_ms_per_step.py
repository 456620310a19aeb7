"""Kernels layer: device milliseconds per step in the MoE routers (the
configuration's ``router`` scopes, ``l<i>_router``: the projection or MLP, the
scores, the top-k and its weights): forward, backward and replay."""

import lm_trace


def reduce(run: dict):
    return lm_trace.part_ms_per_step(run, "router")
