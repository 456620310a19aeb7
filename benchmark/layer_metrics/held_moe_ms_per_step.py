"""Kernels layer: device milliseconds per step in the MoE layers whose experts
this chip HOLDS a share of (the configuration's ``held_moe`` scopes,
``l<i>_moe``; GLM-4.7-Flash's five sparse blocks, the prediction module's
among them, and Xing4.0's four): the sort, the grouped matmuls over the live
rows in chunks, the combine; forward, backward and replay."""

import lm_trace


def reduce(run: dict):
    return lm_trace.part_ms_per_step(run, "held_moe")
