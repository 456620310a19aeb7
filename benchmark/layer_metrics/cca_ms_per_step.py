"""Kernels layer: device milliseconds per step in every scope of ZAYA1's CCA
sublayer — the pre-norm, the q, k, v1, v2 and o projections, the shift, the
two convolutions, the q-k mean, the L2 norm, the ATTENTION layer (rotary
positions and the flash kernels) and the residual add — forward, backward
and what remat replays."""

import lm_trace


def reduce(run: dict):
    return lm_trace.part_ms_per_step(run, "cca")
