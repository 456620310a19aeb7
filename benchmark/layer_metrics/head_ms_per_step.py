"""Kernels layer: device milliseconds per step in the head (the configuration's
``head`` scopes: the vocabulary projection, tied or not, and the loss over it):
forward, backward and replay."""

import lm_trace


def reduce(run: dict):
    return lm_trace.part_ms_per_step(run, "head")
