"""Kernels layer: device milliseconds per step in the head and its loss (the
configuration's ``head`` scopes: ``lm_{head,nll,loss}``, 16,384 rows):
forward, backward and replay."""

import lm_trace


def reduce(run: dict):
    return lm_trace.part_ms_per_step(run, "head")
