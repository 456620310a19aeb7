"""Kernels layer: device milliseconds per step in the MOE layers of the four
sparse blocks (the configuration's ``held_moe`` scopes: sort, the held
experts' grouped matmuls in chunks, the combine): forward, backward and
replay."""

import lm_trace


def reduce(run: dict):
    return lm_trace.part_ms_per_step(run, "held_moe")
