"""Kernels layer: device milliseconds per step in every scope of the linear
layers' token mixers (``l<i>_gdn_*``: the q, k, v, gate, decay,
write-strength and output projections, the short convolutions, the L2 norms,
the decay's form, the recurrence, the gated per-head norm), forward,
backward and what remat replays."""

import olmo_hybrid_trace


def reduce(run: dict):
    return olmo_hybrid_trace.part_ms_per_step(run, "gdn")
