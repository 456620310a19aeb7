"""Kernels layer: device milliseconds per step in every scope of the full
layers' token mixers (``l<i>_attn_*``: q, k, v, o, the two whole-vector
QK-norms, the flash kernels), forward, backward and what remat replays."""

import olmo_hybrid_trace


def reduce(run: dict):
    return olmo_hybrid_trace.part_ms_per_step(run, "attention")
