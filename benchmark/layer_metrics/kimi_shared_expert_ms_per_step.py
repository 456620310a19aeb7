"""Kernels layer: device milliseconds per step in the shared expert (its
three projections, its gate and the sum with the routed part)."""

import kimi_trace


def reduce(run: dict):
    return kimi_trace.part_ms_per_step(run, "shared_expert")
