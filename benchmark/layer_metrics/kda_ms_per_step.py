"""Kernels layer: device milliseconds per step in every scope of the KDA
layers' token mixers (``l<i>_kda_*``: the q, k, v, decay, write-strength,
gate and output projections, the short convolutions, the L2 norms, the
decay's form, the recurrence, the gated per-head norm), forward, backward
and what remat replays."""

import kimi_trace


def reduce(run: dict):
    return kimi_trace.part_ms_per_step(run, "kda")
