"""Kernels layer: the MLA layers' device time outside projections and
Pallas calls — the latent's split and norm, head split and merge, the
shared key part joined to every head's own and that join's gradient,
``rowsum(dO * O)``."""

import kimi_trace


def reduce(run: dict):
    return kimi_trace.mla_glue_ms_per_step(run)
