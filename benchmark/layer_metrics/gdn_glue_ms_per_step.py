"""Kernels layer: device milliseconds per step in the linear layers' scopes
that are neither a projection nor the recurrence: the three short
convolutions and their SiLU, the L2 norms, the decay's softplus form, the
write strength's sigmoid and doubling, the per-head out-norm and the SiLU
gate's product."""

import olmo_hybrid_trace


def reduce(run: dict):
    return olmo_hybrid_trace.part_ms_per_step(run, "gdn_glue")
