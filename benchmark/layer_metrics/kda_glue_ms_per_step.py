"""Kernels layer: device milliseconds per step in the KDA layers' scopes
that are neither a projection nor the recurrence: the three short
convolutions and their SiLU, the L2 norms, the decay's softplus form, the
write strength's sigmoid, the per-head out-norm, the gate's sigmoid and
product."""

import kimi_trace


def reduce(run: dict):
    return kimi_trace.part_ms_per_step(run, "kda_glue")
