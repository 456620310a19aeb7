"""Kernels layer: device milliseconds per step in the MOE layers of the five
sparse blocks, the module's among them (the configuration's ``held_moe``
scopes: sort, the held experts' grouped matmuls in chunks, the combine):
forward, backward and replay. ``held_moe_ms_per_step`` under a name of this
cell's own."""

import lm_trace


def reduce(run: dict):
    return lm_trace.part_ms_per_step(run, "held_moe")
