"""Kernels layer: device milliseconds per step in the four MOE_ROUTER layers
(the configuration's ``router`` scopes: sigmoid scores over 64 experts in
f32, top-4 by score + bias, the balancing rule): forward, backward and
replay."""

import lm_trace


def reduce(run: dict):
    return lm_trace.part_ms_per_step(run, "router")
