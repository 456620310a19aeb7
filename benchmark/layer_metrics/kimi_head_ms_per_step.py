"""Kernels layer: device milliseconds per step in the untied head, its
cross-entropy and the loss's mean (``lm_head``, ``lm_nll``, ``lm_loss``)."""

import kimi_trace


def reduce(run: dict):
    return kimi_trace.part_ms_per_step(run, "head")
