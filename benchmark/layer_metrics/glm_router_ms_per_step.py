"""Kernels layer: device milliseconds per step in the five MOE_ROUTER layers
(the configuration's ``router`` scopes: sigmoid scores over 64 experts in f32,
top-4 by score + bias, the balancing rule): forward, backward and replay.
``router_ms_per_step`` under a name of this cell's own."""

import lm_trace


def reduce(run: dict):
    return lm_trace.part_ms_per_step(run, "router")
