"""Kernels layer: device milliseconds per step in the MoE layers whose experts
this chip HOLDS a share of (the configuration's ``held_moe`` scopes,
``l<i>_moe``): the sort, the grouped matmuls over the live rows, the combine;
forward, backward and replay."""

import lm_trace


def reduce(run: dict):
    return lm_trace.part_ms_per_step(run, "held_moe")
