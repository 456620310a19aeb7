"""Kernels layer: device milliseconds per step in the MoE routers (the
configuration's ``router`` scopes, ``l<i>_router``: the projection or MLP, the
scores, the top-k and its weights; GLM-4.7-Flash's five and Xing4.0's four
MOE_ROUTER layers: sigmoid scores over 64 experts in f32, top-4 by score +
bias, the balancing rule): forward, backward and replay."""

import lm_trace


def reduce(run: dict):
    return lm_trace.part_ms_per_step(run, "router")
