"""Kernels layer: device milliseconds per step in the shared expert (the
configuration's ``shared_expert`` scopes: its three projections, its gate and
the sum with the routed part; in GLM-4.7-Flash's five and Xing4.0's four
sparse blocks the shared experts and their sums with the routed part):
forward, backward and replay."""

import lm_trace


def reduce(run: dict):
    return lm_trace.part_ms_per_step(run, "shared_expert")
