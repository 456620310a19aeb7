"""Kernels layer: device milliseconds per step in the MOE_ROUTER layers
(sigmoid scores over 256 experts in f32, top-8 of score + bias, the
renormalised weights, the balancing rule)."""

import kimi_trace


def reduce(run: dict):
    return kimi_trace.part_ms_per_step(run, "router")
