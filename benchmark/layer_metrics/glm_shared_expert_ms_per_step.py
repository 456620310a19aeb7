"""Kernels layer: device milliseconds per step in the shared experts of the
five sparse blocks and their sums with the routed part (the configuration's
``shared_expert`` scopes): forward, backward and replay.
``shared_expert_ms_per_step`` under a name of this cell's own."""

import lm_trace


def reduce(run: dict):
    return lm_trace.part_ms_per_step(run, "shared_expert")
