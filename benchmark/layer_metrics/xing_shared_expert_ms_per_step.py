"""Kernels layer: device milliseconds per step in the shared experts of the
four sparse blocks and their sums with the routed part (the configuration's
``shared_expert`` scopes): forward, backward and replay."""

import lm_trace


def reduce(run: dict):
    return lm_trace.part_ms_per_step(run, "shared_expert")
