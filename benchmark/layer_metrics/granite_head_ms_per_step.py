"""Kernels layer: device milliseconds per step in Granite's head (the
configuration's ``head`` scopes: the division by ``logits_scaling``, the tied
vocabulary projection and the loss over it): forward, backward and replay.
``head_ms_per_step`` under a name of this cell's own."""

import lm_trace


def reduce(run: dict):
    return lm_trace.part_ms_per_step(run, "head")
